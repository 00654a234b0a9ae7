"""Live introspection service tests (utils/statusd.py): endpoint smoke
over a real socket (port 0), the /healthz 200→503 flip on an injected
anomaly, Prometheus text-format validity, histogram merge exactness, and
multihost shard merging in tools/telemetry_report.py --merge.

Everything here is jax-free and cheap (<10s total): the service, the
telemetry registry, and the health state machine are pure-stdlib/numpy —
the tier-1 budget stays untouched. The learn-task end-to-end scrape
(a LIVE training run answering /metrics) lives in test_e2e.py.
"""

import json
import os
import sys
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import numpy as np
import pytest

from cxxnet_tpu.utils import autopsy, health, statusd, telemetry
from cxxnet_tpu.utils.telemetry import HIST_BUCKETS, Histogram

from . import faultinject

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import telemetry_report  # noqa: E402


@pytest.fixture(autouse=True)
def _lockrank_on(monkeypatch):
    """Runtime lock-order enforcement for every registry/SLOTracker/
    flight-recorder lock this suite constructs: a scrape-thread vs
    worker-thread inversion fails as a named LockOrderError instead of
    a deadlock (doc/static_analysis.md)."""
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")


@pytest.fixture()
def registry():
    """A private enabled registry — tests never touch the process-global
    one (other suites rely on it staying disabled)."""
    reg = telemetry._Registry()
    reg.enable()
    yield reg
    reg.disable()


@pytest.fixture()
def server(registry):
    srv = statusd.StatusServer(0, host="127.0.0.1",
                               registry=registry).start()
    yield srv
    srv.stop()


def _get(srv, path):
    """(status_code, body_text) — 4xx/5xx come back as values, not
    exceptions, so tests read the body either way."""
    try:
        r = urlopen("http://127.0.0.1:%d%s" % (srv.port, path), timeout=5)
        return r.status, r.read().decode()
    except HTTPError as e:
        return e.code, e.read().decode()


# ----------------------------------------------------------------------
# endpoint smoke
def test_endpoints_smoke(registry, server):
    with registry.span("train.step"):
        time.sleep(0.001)
    registry.count("train.images", 256)
    registry.gauge("device.bytes_in_use", 12345)
    registry.hist("serve.request", 0.02)
    server.run_info["task"] = "train"
    server.run_info["config"] = [("eta", "0.1")]
    server.progress.update(round=3, num_round=10, batch=17)

    code, metrics = _get(server, "/metrics")
    assert code == 200
    assert "cxxnet_train_images_total" in metrics
    assert "cxxnet_device_bytes_in_use" in metrics
    assert "cxxnet_train_step_seconds_bucket" in metrics
    assert "cxxnet_serve_request_seconds_count" in metrics
    assert "cxxnet_progress_round" in metrics

    code, body = _get(server, "/healthz")
    assert (code, body) == (200, "ok\n")

    code, page = _get(server, "/statusz")
    assert code == 200
    assert "train.step" in page and "train" in page
    assert "device.bytes_in_use" in page

    code, body = _get(server, "/trace")
    assert code == 200
    trace = json.loads(body)
    assert any(t.get("ph") == "X" and t["name"] == "train.step"
               for t in trace["traceEvents"])

    code, body = _get(server, "/bogus")
    assert code == 404 and "/metrics" in body


def test_port_zero_binds_real_port(registry):
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=registry)
    try:
        assert srv.port > 0     # resolved at bind, before start()
    finally:
        srv._httpd.server_close()


def test_out_of_range_port_raises_overflow(registry):
    """socket.bind raises OverflowError (NOT OSError) for ports > 65535:
    the learn-task bind-failure guard catches both — this pins the
    exception type so a stdlib behavior change (or a guard regression
    narrowing the except clause) is caught jax-free."""
    with pytest.raises((OSError, OverflowError)) as e:
        statusd.StatusServer(70000, host="127.0.0.1", registry=registry)
    assert isinstance(e.value, OverflowError)


# ----------------------------------------------------------------------
# healthz flip on an injected anomaly
def test_healthz_flips_on_injected_anomaly(server):
    mon = health.HealthMonitor()
    pol = health.RecoveryPolicy(action="rollback", max_retries=3)
    server.wire_health(pol)
    assert _get(server, "/healthz")[0] == 200

    # inject a NaN step through the real detector (observe checks one
    # step late: feed a follower so the poisoned vector is examined)
    assert mon.observe(0, 4, faultinject.health_vec(float("nan"),
                                                    nan_grads=3)) is None
    anomaly = mon.observe(0, 5, faultinject.health_vec(1.0))
    assert anomaly is not None and anomaly.kind == "nonfinite"
    assert pol.decide(anomaly) == "rollback"

    code, body = _get(server, "/healthz")
    assert code == 503
    assert "unresolved anomaly" in body and "nonfinite" in body
    # the scrape agrees: cxxnet_healthy drops to 0
    assert "cxxnet_healthy" in _get(server, "/metrics")[1]
    assert 'cxxnet_healthy{process="0"} 0' in _get(server, "/metrics")[1]

    pol.resolve()   # the driver finished the rollback restore
    assert _get(server, "/healthz")[0] == 200
    assert 'cxxnet_healthy{process="0"} 1' in _get(server, "/metrics")[1]


def test_healthz_flips_on_overdue_heartbeat(server):
    # huge poll: the watchdog thread never actually fires (no stack-dump
    # noise); channel_status still sees the stale beat
    wd = health.Watchdog(timeout=0.05, action="warn", poll=30.0).start()
    try:
        health.beat("train.step")
        health.beat("io.prefetch")
        assert _get(server, "/healthz")[0] == 200
        # two armed channels: the scrape must stay spec-valid (one TYPE
        # line for the heartbeat family, one series per channel)
        metrics = _get(server, "/metrics")[1]
        _parse_prom(metrics)
        assert metrics.count("cxxnet_heartbeat_age_seconds{") == 2
        health.pause("io.prefetch")   # single-channel from here on
        time.sleep(0.12)
        code, body = _get(server, "/healthz")
        assert code == 503 and "watchdog:train.step" in body
        health.beat("train.step")      # fresh beat re-arms
        assert _get(server, "/healthz")[0] == 200
        health.pause("train.step")     # paused = legitimately silent
        time.sleep(0.12)
        assert _get(server, "/healthz")[0] == 200
    finally:
        wd.stop()


def test_broken_probe_is_a_failure_not_a_crash(server):
    server.register_probe("boom", lambda: 1 / 0)
    code, body = _get(server, "/healthz")
    assert code == 503 and "probe raised" in body
    assert _get(server, "/metrics")[0] == 200   # server survives


# ----------------------------------------------------------------------
# Prometheus text-format validity
def _parse_prom(text):
    """Strict parse: every non-comment line must match the exposition
    grammar; returns {metric_line_name: [(labels, value)]}."""
    series = {}
    typed = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            kind, name = line.split()[1:3]
            assert kind in ("TYPE", "HELP"), line
            if kind == "TYPE":
                # the exposition spec allows ONE TYPE line per metric
                assert name not in typed, "duplicate TYPE for %s" % name
                typed.add(name)
            continue
        m = statusd.PROM_LINE_RE.match(line)
        assert m, "invalid Prometheus line: %r" % line
        name = line.split("{")[0].split(" ")[0]
        val = line.rsplit(" ", 1)[1]
        series.setdefault(name, []).append((line, val))
    return series


def test_prometheus_format_validity(registry, server):
    for d in (0.0005, 0.003, 0.02, 0.02, 1.5):
        registry.hist("train.step", d)
    registry.count("train.images", 512)
    registry.count("weird/name.with-chars", 1)
    registry.gauge("g", -2.5)
    registry.gauge("overflowed", float("inf"))   # renders as +Inf
    registry.gauge("nan_gauge", float("nan"))
    code, text = _get(server, "/metrics")
    assert code == 200
    series = _parse_prom(text)
    assert "cxxnet_weird_name_with_chars_total" in series
    # histogram contract: buckets cumulative & monotone, +Inf == _count
    buckets = [v for line, v in series["cxxnet_train_step_seconds_bucket"]]
    counts = [int(v) for v in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert counts[-1] == 5          # the +Inf bucket holds every sample
    (count_line,) = series["cxxnet_train_step_seconds_count"]
    assert int(count_line[1]) == 5
    (sum_line,) = series["cxxnet_train_step_seconds_sum"]
    assert abs(float(sum_line[1]) - 1.5435) < 1e-6
    # every series carries the process label
    for line, _ in series["cxxnet_train_images_total"]:
        assert 'process="0"' in line


# ----------------------------------------------------------------------
# the step time is the MEAN period; the dispatch keeps its own name
def test_statusz_step_time_is_the_mean_period_not_a_dispatch_percentile(
        registry, server):
    code, page = _get(server, "/statusz")
    assert code == 200 and "step time" not in page      # nothing fed yet
    assert registry.summary()["step_time_ms"] is None
    # six entries a dispatch apart, then four a step, a group (fed, never
    # clocked): what the runtime's limit of steps in flight makes of
    # back-to-back update calls
    for d in ([0.004] * 6 + [0.100] * 4) * 3:
        registry.hist("train.period", d)
        registry.hist("train.dispatch", 0.0035)
    s = registry.summary()
    assert s["step_time_ms"] == pytest.approx(42.4)
    assert s["hists"]["train.period"]["mean_ms"] == pytest.approx(42.4)
    code, page = _get(server, "/statusz")
    (row,) = [ln for ln in page.splitlines() if "step time" in ln]
    assert "42.40ms" in row and "mean of 30 train.period" in row
    assert "p50" not in row
    # the dispatch's percentiles stand under the dispatch's name
    (disp,) = [ln for ln in page.splitlines()
               if ln.startswith("train.dispatch")]
    assert "p50=" in disp and "p99=" in disp
    code, metrics = _get(server, "/metrics")
    series = _parse_prom(metrics)
    (n,) = series["cxxnet_train_period_seconds_count"]
    (total,) = series["cxxnet_train_period_seconds_sum"]
    assert float(total[1]) / int(n[1]) == pytest.approx(0.0424)
    assert "cxxnet_train_dispatch_seconds_bucket" in series


# ----------------------------------------------------------------------
# histogram primitive: merge exactness
def test_histogram_merge_exactness():
    rs = np.random.RandomState(7)
    a_vals = 10.0 ** rs.uniform(-5, 1, 400)
    b_vals = 10.0 ** rs.uniform(-4, 2, 300)
    ha, hb, hall = Histogram(), Histogram(), Histogram()
    for v in a_vals:
        ha.observe(v)
        hall.observe(v)
    for v in b_vals:
        hb.observe(v)
        hall.observe(v)
    merged = Histogram().merge_dict(ha.to_dict()).merge_dict(hb.to_dict())
    # EXACT: merging shard snapshots == observing the union directly
    assert merged.counts == hall.counts
    assert merged.n == hall.n == 700
    assert abs(merged.sum - hall.sum) < 1e-6
    for p in (50, 90, 99):
        assert merged.percentile(p) == hall.percentile(p)
    # percentile estimate lands within one log-spaced bucket of truth
    exact = np.percentile(np.concatenate([a_vals, b_vals]), 90)
    est = merged.percentile(90)
    i = np.searchsorted(HIST_BUCKETS, exact)
    lo = 0.0 if i == 0 else HIST_BUCKETS[i - 1]
    hi = HIST_BUCKETS[min(i, len(HIST_BUCKETS) - 1)]
    assert lo <= est <= hi * 1.0000001


def test_histogram_dict_roundtrip_and_overflow():
    h = Histogram()
    h.observe(5e-7)          # below the first bucket bound
    h.observe(12345.0)       # above the last: +Inf overflow slot
    d = h.to_dict()
    assert d["count"] == 2
    h2 = Histogram().merge_dict(d)
    assert h2.counts == h.counts
    assert h2.counts[0] == 1 and h2.counts[-1] == 1


def test_span_feeds_histogram(registry):
    with registry.span("io.wait"):
        pass
    snap = registry.metrics_snapshot()
    assert snap["hists"]["io.wait"]["count"] == 1


# ----------------------------------------------------------------------
# multihost shards: %d placeholder + telemetry_report --merge
def _write_shard(tmp_path, rank, t0_wall, images, step_durs):
    """One rank's shard via the REAL writer (%d placeholder path), with a
    deterministic wall-clock epoch patched into the pending meta event so
    the merge alignment is assertable."""
    reg = telemetry._Registry()
    reg.enable(str(tmp_path / "shard.%d.jsonl"), process_index=rank)
    next(e for e in reg._pending
         if e["ev"] == "meta")["t0_wall"] = t0_wall
    for d in step_durs:
        # explicit-timing span: feeds both the span stream and the
        # fixed-bucket histogram, like the train loop's probes
        reg.span_event("train.step", reg.t0_perf, d)
    reg.count("train.images", images)
    reg.gauge("last.batch", images)
    reg.record({"ev": "round", "round": 0, "images": images,
                "input_wait_s": 0.1, "step_s": 0.2})
    reg.flush()
    out = reg.log_path
    reg.disable()
    return out


def test_rank_placeholder_expansion(tmp_path):
    reg = telemetry._Registry()
    reg.enable(str(tmp_path / "run.%d.jsonl"), process_index=3)
    assert reg.log_path.endswith("run.3.jsonl")
    reg.disable()
    # no placeholder on rank>0: suffixed instead of clobbering shard 0
    reg.enable(str(tmp_path / "run.jsonl"), process_index=2)
    assert reg.log_path.endswith("run.jsonl.2")
    reg.disable()
    # rank 0 (or single-host) keeps the plain path
    reg.enable(str(tmp_path / "plain.jsonl"), process_index=0)
    assert reg.log_path.endswith("plain.jsonl")
    reg.disable()


def test_events_tagged_with_process_index(tmp_path):
    p = _write_shard(tmp_path, 1, 1000.0, 64, [0.01])
    evs = [json.loads(l) for l in open(p) if l.strip()]
    assert evs and all(e.get("p") == 1 for e in evs)


def test_report_merge_shards(tmp_path, capsys):
    p0 = _write_shard(tmp_path, 0, 1000.0, 100, [0.010, 0.020, 0.030])
    p1 = _write_shard(tmp_path, 1, 1002.5, 140, [0.011, 0.021])
    rc = telemetry_report.main(["--merge", p0, p1, "--json"])
    assert rc == 0
    agg = json.loads(capsys.readouterr().out)
    # counters summed across processes; per-process attribution kept
    assert agg["counters"]["train.images"] == 240
    assert agg["processes"]["0"]["images"] == 100
    assert agg["processes"]["1"]["images"] == 140
    assert agg["processes"]["1"]["counters"]["train.images"] == 140
    assert agg["processes"]["1"]["gauges"]["last.batch"] == 140
    # the merged histogram holds every shard's samples (merge-exact)
    assert agg["hists"]["train.step"]["count"] == 5
    assert agg["spans"]["train.step"]["count"] == 5
    # shard 1's events were re-based onto the shared epoch: its round
    # event lands ~2.5s after shard 0's identical-local-ts round event
    rounds = {r["p"]: r for r in agg["rounds"]}
    assert rounds[1]["ts"] - rounds[0]["ts"] == pytest.approx(2.5,
                                                              abs=0.2)
    # human report renders the per-process breakdown + bucket table
    rc = telemetry_report.main(["--merge", p0, p1])
    out = capsys.readouterr().out
    assert rc == 0
    assert "per-process breakdown" in out
    assert "process 1: 1 rounds, 140 images" in out
    assert "latency histograms" in out and "le=" in out


def test_merge_keeps_unresolved_anomalies_per_process(tmp_path, capsys):
    """Anomaly ids are per-process counters: shard A's resolved id=1
    must NOT resolve shard B's unrelated (unrecovered) id=1 in a merged
    report — the exit-2 CI gate has to keep firing."""
    p0 = _write_shard(tmp_path, 0, 1000.0, 10, [0.01])
    p1 = _write_shard(tmp_path, 1, 1001.0, 10, [0.01])
    with open(p0, "a") as f:
        f.write(json.dumps({"ev": "health_anomaly", "id": 1,
                            "kind": "nonfinite", "round": 0, "batch": 2,
                            "p": 0}) + "\n")
        f.write(json.dumps({"ev": "health_rollback", "anomaly": 1,
                            "p": 0}) + "\n")
    with open(p1, "a") as f:
        f.write(json.dumps({"ev": "health_anomaly", "id": 1,
                            "kind": "nonfinite", "round": 0, "batch": 5,
                            "p": 1}) + "\n")
    rc = telemetry_report.main(["--merge", p0, p1, "--json"])
    capsys.readouterr()
    assert rc == 2          # shard 1's anomaly is still unresolved
    # each shard alone agrees with itself
    assert telemetry_report.main([p0, "--json"]) == 0
    capsys.readouterr()
    assert telemetry_report.main([p1, "--json"]) == 2
    capsys.readouterr()


def test_report_merge_rejects_duplicate_shards(tmp_path, capsys):
    p0 = _write_shard(tmp_path, 0, 1000.0, 10, [0.01])
    with pytest.raises(SystemExit) as e:
        telemetry_report.main(["--merge", p0, p0])
    assert e.value.code == 1


def test_report_merge_rejects_malformed_shards(tmp_path, capsys):
    """Merge-input validation: a shard with no meta event (truncated
    copy) or with foreign histogram buckets must exit 2, not emit a
    silently garbage timeline / IndexError traceback."""
    p0 = _write_shard(tmp_path, 0, 1000.0, 10, [0.01])
    # shard that lost its first line (meta) to e.g. logrotate
    p1 = str(tmp_path / "headless.jsonl")
    with open(p1, "w") as f:
        f.write(json.dumps({"ev": "round", "round": 0, "images": 5,
                            "ts": 0.5, "p": 1}) + "\n")
    with pytest.raises(SystemExit) as e:
        telemetry_report.main(["--merge", p0, p1])
    assert e.value.code == 2
    # shard whose hists snapshot uses a different bucket layout
    p2 = str(tmp_path / "alienbuckets.jsonl")
    with open(p2, "w") as f:
        f.write(json.dumps({"ev": "meta", "t0_wall": 1001.0,
                            "p": 1}) + "\n")
        f.write(json.dumps({"ev": "hists", "ts": 0.1, "p": 1, "hists": {
            "train.step": {"buckets": {"99": 4}, "sum": 1.0,
                           "count": 4}}}) + "\n")
    with pytest.raises(SystemExit) as e:
        telemetry_report.main(["--merge", p0, p2])
    assert e.value.code == 2
    assert "out of range" in capsys.readouterr().err


def test_report_single_log_still_works(tmp_path, capsys):
    p0 = _write_shard(tmp_path, 0, 1000.0, 10, [0.01, 0.02])
    rc = telemetry_report.main([p0, "--json"])
    assert rc == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["counters"]["train.images"] == 10
    assert "processes" not in agg         # single shard: no split section
    assert agg["hists"]["train.step"]["count"] == 2


# ----------------------------------------------------------------------
# empty-histogram sentinel: a declared-but-never-fired series (TTFT on a
# run that served zero requests) renders "n/a", never NaN/0.0 garbage
def test_empty_histogram_sentinel_and_na(registry, server):
    h = Histogram()
    assert h.percentile(50) is None and h.percentile(99) is None
    st = h.stats()
    assert st == {"count": 0, "sum_s": 0.0, "mean_ms": None,
                  "p50_ms": None, "p90_ms": None, "p99_ms": None}
    registry.declare_hist("serve.ttft")
    code, page = _get(server, "/statusz")
    assert code == 200 and "serve.ttft" in page
    assert "n=0 p50=n/a p90=n/a p99=n/a" in page
    # /metrics still exports the (zeroed) bucket series, grammar-valid
    code, metrics = _get(server, "/metrics")
    assert code == 200
    for line in metrics.splitlines():
        if line and not line.startswith("#"):
            assert statusd.PROM_LINE_RE.match(line), line
    assert 'cxxnet_serve_ttft_seconds_bucket{process="0",le="+Inf"} 0' \
        in metrics
    assert 'cxxnet_serve_ttft_seconds_count{process="0"} 0' in metrics
    # JSON sinks carry the sentinel as null, not NaN (strict JSON)
    dumped = json.dumps(registry.summary()["hists"]["serve.ttft"])
    assert "NaN" not in dumped and "null" in dumped


def test_slo_tracker_rolling_window_and_reasons():
    clock = [0.0]
    slo = statusd.SLOTracker(ttft_ms=10.0, p99_ms=100.0,
                             availability=0.99, window_s=30.0,
                             min_requests=3, clock=lambda: clock[0])
    for _ in range(3):
        slo.observe(ok=True, ttft_s=0.005, latency_s=0.05)
    assert slo.snapshot()["alert"] == 0
    # one error + one ttft + one latency violation: 3/6 bad, budget 1%
    slo.observe(ok=False)
    slo.observe(ok=True, ttft_s=0.5)
    slo.observe(ok=True, ttft_s=0.001, latency_s=0.5)
    snap = slo.snapshot()
    assert snap["alert"] == 1 and snap["burn_rate"] >= 1.0
    assert snap["by_reason"] == {"error": 1, "ttft": 1, "latency": 1}
    # the window forgets the entries, but with zero fresh evidence the
    # alert HOLDS — a zero-traffic scrape must not clear a burn that no
    # request ever recovered from (the gate would depend on scrape
    # timing otherwise)
    clock[0] = 31.0
    snap = slo.snapshot()
    assert snap["requests"] == 0 and snap["alert"] == 1
    # recovery requires evidence: min_requests healthy observations
    for _ in range(3):
        slo.observe(ok=True, ttft_s=0.005, latency_s=0.05)
    snap = slo.snapshot()
    assert snap["alert"] == 0 and snap["burn_rate"] == 0.0


def test_slo_burn_transition_events_only(registry):
    """slo_burn events are emitted on TRANSITIONS, not per request —
    the report's exit-2 gate reads the last state."""
    import cxxnet_tpu.utils.telemetry as tmod
    old = tmod._REG
    tmod._REG = registry          # route module-level event() capture
    try:
        clock = [0.0]
        slo = statusd.SLOTracker(ttft_ms=10.0, min_requests=2,
                                 window_s=60.0, clock=lambda: clock[0])
        for _ in range(4):
            slo.observe(ok=True, ttft_s=0.5)     # flips to burning once
        clock[0] = 61.0          # the bad requests age out of the window
        for _ in range(4):
            slo.observe(ok=True, ttft_s=0.001)   # flips back once
    finally:
        tmod._REG = old
    burns = [e for e in registry.recent_events()
             if e.get("ev") == "slo_burn"]
    assert [e["state"] for e in burns] == [1, 0]


# ----------------------------------------------------------------------
# tools: summarize_trace request format
import summarize_trace  # noqa: E402


def test_summarize_trace_request_format(tmp_path, capsys):
    rec = {"id": "12", "outcome": "served", "tokens_in": 3,
           "tokens_out": 8, "total_s": 0.1,
           "phases": {"queue_wait": 0.005, "dispatch": 0.001,
                      "prefill": 0.034, "decode": 0.06},
           "recompiles": [{"name": "jit.decode_prefill",
                           "cause": "new_signature", "dur": 0.02}]}
    p = tmp_path / "req.trace.json"
    p.write_text(json.dumps(telemetry.request_chrome_trace(rec)))
    sys.argv, old = ["summarize_trace.py", str(p)], sys.argv
    try:
        summarize_trace.main()
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "request 12 (served)" in out
    assert "prefill" in out and "decode" in out
    assert "jit.decode_prefill (new_signature)" in out
    assert "phase coverage: 100.0%" in out


# ----------------------------------------------------------------------
def test_batchz_html_and_decode_metrics_render():
    """batchz_html and the prometheus batch section are pure functions
    of a batch snapshot: per-bucket rows, the KV/convoy account lines,
    the iteration-ring table — and the cxxnet_decode_* families render
    Prometheus-valid with bucket labels."""
    snap = {
        "buckets": {"2": {"warm": 1, "active": 1, "kv_bytes": 4096,
                          "kv_live_bytes": 1024, "live_tokens": 16,
                          "alloc_tokens": 128},
                    "4": {"warm": 0, "active": 0, "kv_bytes": 0,
                          "kv_live_bytes": 0, "live_tokens": 0,
                          "alloc_tokens": 0}},
        "capacity": 4, "free_slots": 1, "queue_depth": 3,
        "kv_bytes": 4096, "kv_live_bytes": 1024, "kv_live_pct": 25.0,
        "slot_waste_pct": 50.0, "convoy": 1, "convoys": 2,
        "convoy_iters": 64, "iterations": 10, "slot_iterations": 17,
        "mean_occupancy": 1.7, "flight_cap": 256,
        "flight": [{"iter": 10, "t_wall": 1.0, "bucket": 2,
                    "occupancy": 1, "step_ms": 2.5,
                    "slots": [[0, "7", 9]], "admitted": [["7", 0]],
                    "retired": [["6", 1]], "queue_depth": 3,
                    "queue_age_s": 0.5, "kv_live_pct": 25.0,
                    "age_skew": None, "convoy": 1}]}
    page = statusd.batchz_html(snap)
    assert "decode batch scheduler" in page
    assert "CONVOY" in page and "2 episode(s)" in page
    assert "0:7@9" in page                 # slot:occupant@age
    assert "+7" in page and "-6" in page   # admissions/retirements
    text = statusd.prometheus_metrics(
        {"process": 0, "uptime_s": 1.0, "counters": {}, "gauges": {},
         "hists": {}, "compiles": 0, "compile_s": 0.0}, batch=snap)
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert statusd.PROM_LINE_RE.match(line), line
    assert 'cxxnet_decode_kv_bytes{process="0",bucket="2"} 4096' in text
    assert 'cxxnet_decode_kv_live_bytes{process="0",bucket="2"} 1024' \
        in text
    assert "cxxnet_decode_kv_live_pct" in text
    assert "cxxnet_decode_slot_waste_pct" in text
    assert "cxxnet_decode_convoy" in text
    assert "cxxnet_decode_convoys_total" in text


def test_hbm_decode_kv_row_renders():
    """The perf section charges the live decode KV cache against HBM:
    cxxnet_hbm_decode_kv_bytes renders when the ledger's snapshot
    carries it, and headroom reflects the subtraction upstream."""
    text = statusd.prometheus_metrics(
        {"process": 0, "uptime_s": 1.0, "counters": {}, "gauges": {},
         "hists": {}, "compiles": 0, "compile_s": 0.0},
        perf={"hbm": {"capacity_bytes": 100, "peak_bytes": 40,
                      "decode_kv_bytes": 25, "headroom_bytes": 35},
              "cards": []})
    assert "cxxnet_hbm_decode_kv_bytes" in text
    assert "cxxnet_hbm_headroom_bytes" in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert statusd.PROM_LINE_RE.match(line), line


# ----------------------------------------------------------------------
# endpoint query contract: derived from the ENDPOINTS table, so a new
# endpoint cannot ship without declaring (and honoring) its flags
@pytest.mark.parametrize("path,has_json,has_n", statusd.ENDPOINTS)
def test_endpoint_query_contract(server, path, has_json, has_n):
    qs = "?request=0" if path == "/why" else ""
    code, _ = _get(server, path + qs)
    assert code < 500, (path, code)
    if has_json:
        sep = "&" if qs else "?"
        code, body = _get(server, path + qs + sep + "json=1")
        assert code < 500, (path, code)
        if code == 200:
            json.loads(body)        # 200 + ?json=1 must be strict JSON
    if has_n:
        code, body = _get(server, path + "?n=x")
        assert code == 400 and "integer" in body, (path, code)
        assert _get(server, path + "?n=1")[0] < 500, path


def test_404_lists_every_endpoint(server):
    code, body = _get(server, "/nope")
    assert code == 404
    for p, _, _ in statusd.ENDPOINTS:
        assert p in body, (p, body)


# ----------------------------------------------------------------------
# /why: the per-request slowdown autopsy over a real socket
def test_why_endpoint_replica_autopsy(server):
    fr = telemetry.FlightRecorder()
    fr.record({"id": "42", "outcome": "served", "t_wall": 5.0,
               "total_s": 2.0,
               "phases": {"queue_wait": 0.1, "dispatch": 0.0,
                          "prefill": 1.5, "decode": 0.4},
               "compile_stall_s": 1.4})
    server.flight = fr
    code, body = _get(server, "/why?request=42&json=1")
    assert code == 200
    why = json.loads(body)
    assert why["id"] == "42" and why["hops"] == {}
    aut = why["autopsy"]
    assert aut["primary"] == "compile_stall"
    # acceptance shape: causes tile >= 95% of wall, all 8 named
    assert sum(aut["causes"].values()) >= 0.95 * aut["wall_s"] > 0
    assert set(aut["causes"]) == set(autopsy.CAUSES)
    code, page = _get(server, "/why?request=42")
    assert code == 200
    assert "PRIMARY VERDICT" in page and "compile_stall" in page
    code, body = _get(server, "/why?request=nope")
    assert code == 404 and "/requestz" in body
    code, body = _get(server, "/why")
    assert code == 400 and "request" in body


# ----------------------------------------------------------------------
# /eventz: the incident timeline over a real socket
def test_eventz_timeline(registry, server):
    registry.record({"ev": "kv_pressure", "pressure": 1, "ts": 1.0})
    registry.record({"ev": "serve_drain", "ts": 1.5})
    registry.record({"ev": "kv_pressure", "pressure": 0, "ts": 2.0})
    code, body = _get(server, "/eventz?json=1")
    assert code == 200
    ev = json.loads(body)
    kinds = [(r["kind"], r["state"]) for r in ev["rows"]]
    assert kinds == [("kv_pressure", "begin"), ("serve_drain", "point"),
                     ("kv_pressure", "end")]
    assert ev["shown"] == 3
    walls = [r["t_wall"] for r in ev["rows"]]
    assert walls == sorted(walls)
    # ?n keeps the NEWEST rows (freshest incidents first out the door)
    ev = json.loads(_get(server, "/eventz?json=1&n=1")[1])
    assert ev["shown"] == 1 and ev["rows"][0]["state"] == "end"
    code, page = _get(server, "/eventz")
    assert code == 200 and "kv_pressure" in page


# ----------------------------------------------------------------------
# conservation laws on the scrape path: cxxnet_books_broken latches
def test_books_broken_gauge_latches_in_scrape(registry, server):
    # a PRIVATE auditor on the server: latches must never leak into the
    # process-global one other suites scrape
    aud = telemetry.BooksAuditor(registry=registry)
    server.auditor = aud
    books = {"debit": 2, "credit": 2}
    aud.register("test.books",
                 lambda: None if books["debit"] == books["credit"]
                 else "debit %d != credit %d"
                 % (books["debit"], books["credit"]))
    text = _get(server, "/metrics")[1]
    _parse_prom(text)
    assert 'cxxnet_books_broken{process="0",law="test.books"} 0' in text
    assert "cxxnet_books_laws" in text
    assert "cxxnet_books_sweeps_total" in text
    books["credit"] = 5          # the corruption: books stop balancing
    text = _get(server, "/metrics")[1]
    assert 'cxxnet_books_broken{process="0",law="test.books"} 1' in text
    # sticky: the law reconciling again must NOT clear the latch
    books["credit"] = 2
    text = _get(server, "/metrics")[1]
    _parse_prom(text)
    assert 'cxxnet_books_broken{process="0",law="test.books"} 1' in text
    # unregistering (a drained subsystem) must not hide the latch either
    aud.unregister("test.books")
    text = _get(server, "/metrics")[1]
    assert 'cxxnet_books_broken{process="0",law="test.books"} 1' in text
    # the violation became exactly one transition event in the stream
    evs = [e for e in registry.recent_events()
           if e.get("ev") == "books_broken"]
    assert [(e["law"], e["broken"]) for e in evs] == [("test.books", 1)]


def test_statusd_selftest():
    assert statusd.selftest() == 0
