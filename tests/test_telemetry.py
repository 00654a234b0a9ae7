"""Telemetry subsystem tests: spans, counters, JSONL sink, Chrome trace,
recompile detection, and the disabled-mode zero-overhead contract."""

import json
import os
import time

import numpy as np
import pytest

from cxxnet_tpu.utils import telemetry


@pytest.fixture(autouse=True)
def _isolated_registry():
    """Telemetry is process-global: make every test start and end clean."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _spans(evs):
    return [e for e in evs if e.get("ev") == "span"]


def test_span_nesting_and_timing():
    telemetry.enable()
    with telemetry.span("outer"):
        time.sleep(0.02)
        with telemetry.span("inner"):
            time.sleep(0.01)
    evs = _spans(telemetry.events())
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert inner["dur"] >= 0.01
    assert outer["dur"] >= inner["dur"]
    # the inner span starts inside the outer one
    assert outer["ts"] <= inner["ts"] <= outer["ts"] + outer["dur"]


def test_span_attrs_and_threads():
    telemetry.enable()
    import threading

    def work():
        with telemetry.span("worker.region", shard=3):
            pass

    th = threading.Thread(target=work)
    with telemetry.span("main.region"):
        th.start()
        th.join()
    evs = _spans(telemetry.events())
    by_name = {e["name"]: e for e in evs}
    assert by_name["worker.region"]["shard"] == 3
    # worker thread gets depth 0 on its OWN stack, not nested under main
    assert by_name["worker.region"]["depth"] == 0
    assert by_name["worker.region"]["tid"] != by_name["main.region"]["tid"]


def test_counter_and_gauge_aggregation():
    telemetry.enable()
    telemetry.count("images", 100)
    telemetry.count("images", 28)
    telemetry.count("flushes")
    telemetry.gauge("hbm", 5)
    telemetry.gauge("hbm", 7)   # gauges keep the latest value
    s = telemetry.summary()
    assert s["counters"]["images"] == 128
    assert s["counters"]["flushes"] == 1
    assert s["gauges"]["hbm"] == 7


def test_path_account_adds_a_count_on_or_off():
    """``count_path(name, n)``: the always-on account takes the count
    whether telemetry is enabled or not (the flash kernels' tile counts
    ride on it beside ``attn.flash``); the counter only when enabled."""
    before = telemetry.paths()
    telemetry.count_path("test.tiles", 56)
    telemetry.enable()
    telemetry.count_path("test.tiles", 16)
    telemetry.count_path("test.path")
    paths = telemetry.paths()
    assert paths["test.tiles"] - before.get("test.tiles", 0) == 72
    assert paths["test.path"] - before.get("test.path", 0) == 1
    s = telemetry.summary()
    assert s["counters"]["test.tiles"] == 16 and s["paths"] == paths


def test_summary_span_stats():
    telemetry.enable()
    for _ in range(5):
        with telemetry.span("step"):
            pass
    s = telemetry.summary()["spans"]["step"]
    assert s["count"] == 5
    assert s["total_s"] >= 0
    assert s["p50_ms"] <= s["p99_ms"] <= s["max_ms"] + 1e-9


def test_jsonl_roundtrip(tmp_path):
    log = str(tmp_path / "run.jsonl")
    telemetry.enable(log)
    with telemetry.span("a"):
        with telemetry.span("b"):
            pass
    telemetry.count("n", 2)
    summary = telemetry.finish(close=True)
    assert summary["spans"]["a"]["count"] == 1
    lines = [l for l in open(log).read().splitlines() if l.strip()]
    evs = [json.loads(l) for l in lines]          # every line parses
    kinds = [e["ev"] for e in evs]
    assert kinds[0] == "meta"
    assert kinds[-1] == "summary"
    names = [e["name"] for e in evs if e["ev"] == "span"]
    assert names == ["b", "a"]
    assert evs[-1]["summary"]["counters"]["n"] == 2
    # the chrome trace export lands next to the log and is valid JSON
    trace = json.load(open(log + ".trace.json"))
    assert any(t.get("ph") == "X" and t["name"] == "a"
               for t in trace["traceEvents"])


def test_counters_flushed_incrementally(tmp_path):
    """A crashed run (no finish/summary) keeps its counters: every flush
    writes a counters snapshot when any counter moved."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    log = str(tmp_path / "crash.jsonl")
    telemetry.enable(log)
    telemetry.count("images", 100)
    with telemetry.span("s"):
        pass
    telemetry.flush()                  # round-boundary flush, then "crash"
    telemetry.flush()                  # unchanged counters: no new snapshot
    evs = [json.loads(l) for l in open(log) if l.strip()]
    snaps = [e for e in evs if e["ev"] == "counters"]
    assert len(snaps) == 1
    assert snaps[-1]["counters"]["images"] == 100
    assert not any(e["ev"] == "summary" for e in evs)
    assert telemetry_report.aggregate(evs)["counters"]["images"] == 100


def test_span_event_explicit_timing():
    telemetry.enable()
    import time as _t
    t0 = _t.perf_counter()
    telemetry.span_event("probe", t0, 0.25, phase=1)
    (ev,) = [e for e in telemetry.events() if e.get("ev") == "span"]
    assert ev["name"] == "probe" and ev["dur"] == 0.25 and ev["phase"] == 1
    assert telemetry.summary()["spans"]["probe"]["count"] == 1


def test_chrome_trace_validity():
    telemetry.enable()
    with telemetry.span("region"):
        pass
    telemetry.gauge("mem", 123)
    telemetry.record_compile("jit.x", "new_signature", 0.5)
    trace = json.loads(json.dumps(telemetry.chrome_trace()))
    evs = trace["traceEvents"]
    x = [t for t in evs if t.get("ph") == "X"]
    assert {"region", "compile:jit.x"} == {t["name"] for t in x}
    for t in x:
        assert t["ts"] >= 0 and t["dur"] >= 0 and isinstance(t["pid"], int)
    c = [t for t in evs if t.get("ph") == "C"]
    assert c and c[0]["args"]["value"] == 123


def test_recompile_detector_fires_once_per_signature():
    import jax
    import jax.numpy as jnp
    telemetry.enable()
    fn = telemetry.jit_watch(jax.jit(lambda x: x * 2), "jit.t")
    fn(jnp.zeros((4,)))            # new (signature, shape): compiles
    fn(jnp.zeros((4,)))            # cache hit: no event
    fn(jnp.ones((4,)))             # same shape/dtype: still a hit
    comps = telemetry.summary()["compiles"]
    assert comps["count"] == 1
    assert comps["by_cause"] == {"new_signature": 1}
    fn(jnp.zeros((8,)))            # new shape: one more, cause shape_change
    fn(jnp.zeros((8,)))
    fn(jnp.zeros((4, 2)))
    comps = telemetry.summary()["compiles"]
    assert comps["count"] == 3
    assert comps["by_cause"] == {"new_signature": 1, "shape_change": 2}
    for c in telemetry._REG.compiles:
        assert c["dur"] >= 0


def _tiny_trainer():
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    conf = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 8
  init_sigma = 0.01
layer[+0] = softmax
netconfig = end
input_shape = 1,1,16
batch_size = 4
dev = cpu
eta = 0.1
eval_train = 0
"""
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _tiny_batch():
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = rs.rand(4, 1, 1, 16).astype(np.float32)
    b.label = np.zeros((4, 1), np.float32)
    b.batch_size = 4
    return b


def test_recompile_detector_trainer_cache_keys():
    """Through the Trainer: one compile per jit-cache key, zero on reuse,
    and a cache clear re-attributes the rebuild cause."""
    tr = _tiny_trainer()
    telemetry.enable()
    b = _tiny_batch()
    for _ in range(3):
        tr.update(b)
    comps = telemetry.summary()["compiles"]
    # first call compiles; the 2nd may re-specialize once for the now
    # device-committed donated params (a genuinely new sharding key the
    # detector is SUPPOSED to flag, attributed shape_change)
    n_warm = comps["count"]
    assert 1 <= n_warm <= 2
    assert comps["by_name"] == {"jit.train_step": n_warm}
    assert comps["by_cause"]["new_signature"] == 1
    tr.update(b)                        # steady state: pure cache hit
    assert telemetry.summary()["compiles"]["count"] == n_warm
    tr._clear_jit_cache()               # donation/packing-style rebuild
    tr.update(b)
    comps = telemetry.summary()["compiles"]
    assert comps["count"] == n_warm + 1
    assert comps["by_cause"]["rebuild_after_clear"] == 1
    assert telemetry.summary()["counters"]["jit.cache_clear"] == 1


def test_donated_params_failure_recovery():
    """_forward_nodes/predict_device donate the AUTHORITATIVE params: a
    failure that consumed the donated buffers must not leave the trainer
    silently running on deleted arrays (ADVICE.md). Without a canonical
    copy the trainer marks params unusable with a clear error; with the
    decode cache's canonical copy it rebuilds."""
    tr = _tiny_trainer()
    b = _tiny_batch()
    pred = tr.predict(b)          # healthy path compiles + runs
    assert pred.shape == (4,)

    class Boom(RuntimeError):
        pass

    def explode(params, data, rng):
        # consume the donated buffers like a post-dispatch failure would
        for p in params:
            for v in p.values():
                v.delete()
        raise Boom("execute failed")

    node = tr.net_cfg.param.num_nodes - 1
    tr._jit_cache[("pred", node)] = explode
    with pytest.raises(RuntimeError, match="reload the model"):
        tr.predict(b)
    assert tr.params is None      # marked unusable, not silently broken

    # with a live decode canonical copy the params rebuild instead
    tr2 = _tiny_trainer()
    tr2.predict(b)
    canon = [{k: np.asarray(v) for k, v in p.items()} for p in tr2.params]
    tr2._decode_params = (tr2.params, canon)
    tr2._jit_cache[("pred", node)] = explode
    with pytest.raises(Boom):
        tr2.predict(b)
    assert tr2.params is not None
    for p, c in zip(tr2.params, canon):
        for k in p:
            np.testing.assert_array_equal(np.asarray(p[k]), c[k])
    # and the rebuilt params still drive a working predict
    tr2._jit_cache.pop(("pred", node))
    assert tr2.predict(b).shape == (4,)


def test_disabled_mode_records_nothing():
    assert not telemetry.enabled()
    # span() hands back ONE shared no-op object: no per-call allocation
    s1 = telemetry.span("a")
    s2 = telemetry.span("b", attr=1)
    assert s1 is s2
    with s1:
        pass
    telemetry.count("n", 5)
    telemetry.gauge("g", 1)
    telemetry.record_compile("x", "new_signature", 1.0)
    assert telemetry.events() == []
    s = telemetry.summary()
    assert s["spans"] == {} and s["counters"] == {}
    assert s["compiles"]["count"] == 0


def test_disabled_jit_watch_passthrough():
    import jax
    import jax.numpy as jnp
    fn = telemetry.jit_watch(jax.jit(lambda x: x + 1), "jit.p")
    out = fn(jnp.zeros((2,)))
    assert out.shape == (2,)
    assert telemetry.events() == []


def test_report_tool_roundtrip(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    log = str(tmp_path / "r.jsonl")
    telemetry.enable(log)
    for _ in range(3):
        with telemetry.span("train.step"):
            pass
    telemetry.record_compile("jit.train_step", "new_signature", 0.25)
    telemetry.event({"ev": "round", "round": 0, "images": 300,
                     "input_wait_s": 0.1, "step_s": 0.2})
    telemetry.finish(close=True)

    trace_out = str(tmp_path / "trace.json")
    rc = telemetry_report.main([log, "--trace", trace_out])
    assert rc == 0
    out = capsys.readouterr().out
    assert "train.step" in out and "recompiles" in out
    assert "new_signature" in out
    trace = json.load(open(trace_out))
    assert trace["traceEvents"]
    # --json mode emits a parseable aggregate
    rc = telemetry_report.main([log, "--json"])
    assert rc == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["spans"]["train.step"]["count"] == 3
    assert agg["compiles"]["count"] == 1


def test_report_tool_rejects_malformed(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ev": "span", "name": "a", "ts": 0, "dur": 1}\n'
                   'not json at all\n')
    with pytest.raises(SystemExit) as e:
        telemetry_report.main([str(bad)])
    assert e.value.code == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(SystemExit) as e:
        telemetry_report.main([str(empty)])
    assert e.value.code == 2
    assert telemetry_report.main([str(tmp_path / "missing.jsonl")]) == 1


# ----------------------------------------------------------------------
# kept spans: the third always-on account (beside phases() and paths())
@pytest.fixture
def kept_rings(monkeypatch):
    """An empty account for the test: it is the process's and outlives
    reset(), so what other tests kept would stand in it."""
    monkeypatch.setattr(telemetry._REG, "kept_rings", {})


def _profiled(tmp_path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    return jax.profiler.stop_trace


@pytest.mark.parametrize("mode", ["disabled", "enabled", "profiler"])
def test_a_kept_span_is_recorded_whatever_else_records(mode, tmp_path,
                                                       kept_rings):
    stop = None
    if mode == "enabled":
        telemetry.enable()
    elif mode == "profiler":
        stop = _profiled(tmp_path)
    try:
        with telemetry.span("k.outer", keep=True) as outer:
            with telemetry.span("k.inner", keep=True):
                pass
            with telemetry.span("k.plain"):
                pass
    finally:
        if stop is not None:
            stop()
    kept = telemetry.kept()
    assert set(kept) == {"k.outer", "k.inner"}      # k.plain is not kept
    ((o0, odur),), ((i0, idur),) = kept["k.outer"], kept["k.inner"]
    assert o0 == outer.t0 and odur >= 0
    assert o0 <= i0 and i0 + idur <= o0 + odur      # perf_counter's clock
    # beside the account a kept span is the span it was: the event and
    # the histogram where telemetry is enabled, nothing where it is not
    names = [e["name"] for e in _spans(telemetry.events())]
    hists = telemetry.summary()["hists"]
    if mode == "enabled":
        assert names == ["k.inner", "k.plain", "k.outer"]
        assert hists["k.outer"]["count"] == hists["k.inner"]["count"] == 1
    else:
        assert names == [] and hists == {}


def test_the_ring_holds_the_last_512_of_a_name_and_no_more(kept_rings):
    assert telemetry.KEPT_CAP == 512
    seen = []
    for i in range(10000):
        with telemetry.span("k.many", keep=True) as sp:
            pass
        seen.append(sp.t0)
    with telemetry.span("k.once", keep=True):
        pass
    kept = telemetry.kept()
    assert [t0 for t0, _ in kept["k.many"]] == seen[-512:]
    assert len(kept["k.once"]) == 1                 # a ring a name


def test_kept_outlives_reset_and_enable(kept_rings):
    with telemetry.span("k.a", keep=True):
        pass
    first = telemetry.kept()
    telemetry.reset()
    assert telemetry.kept() == first
    telemetry.enable()
    telemetry.disable()
    with telemetry.span("k.a", keep=True):
        pass
    assert telemetry.kept()["k.a"][:1] == first["k.a"]
    assert len(telemetry.kept()["k.a"]) == 2
    # what kept() hands out is a copy
    telemetry.kept()["k.a"].clear()
    assert len(telemetry.kept()["k.a"]) == 2


def test_a_span_that_is_not_kept_is_still_the_shared_noop(kept_rings):
    with telemetry.span("k.a", keep=True):
        pass
    # the hot path's cost, pinned: identity, not equality
    assert telemetry.span("k.a") is telemetry.span("k.b", attr=1) \
        is telemetry._NULL_SPAN
    assert telemetry.span("k.a", keep=False) is telemetry._NULL_SPAN
    assert telemetry.span("k.a", keep=True) is not telemetry._NULL_SPAN
    with telemetry.span("k.a"):
        pass
    assert len(telemetry.kept()["k.a"]) == 1


def test_two_threads_keep_to_their_own_order_without_loss(kept_rings):
    import sys
    import threading
    n = 200                    # 2 x 200 fits the ring of the shared name
    mine = {"t1": [], "t2": []}

    def work(own):
        for _ in range(n):
            with telemetry.span("k.shared", keep=True):
                with telemetry.span("k." + own, keep=True) as sp:
                    pass
            mine[own].append(sp.t0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(own,))
                   for own in mine]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    kept = telemetry.kept()
    for own, t0s in mine.items():
        assert [t0 for t0, _ in kept["k." + own]] == t0s
    assert len(kept["k.shared"]) == 2 * n
    # every inner span lies inside one outer span of the shared ring
    outers = kept["k.shared"]
    for own in mine:
        for t0, dur in kept["k." + own]:
            assert any(o0 <= t0 and t0 + dur <= o0 + od
                       for o0, od in outers)


def test_summary_step_time_is_the_mean_period(kept_rings):
    telemetry.enable()
    assert telemetry.summary()["step_time_ms"] is None
    # six entries a dispatch apart, then four a step, a group: fed times
    for d in ([0.004] * 6 + [0.100] * 4) * 5:
        telemetry.hist("train.period", d)
    s = telemetry.summary()
    assert s["step_time_ms"] == pytest.approx(42.4)
    h = s["hists"]["train.period"]
    assert h["mean_ms"] == pytest.approx(1e3 * h["sum_s"] / h["count"])
    assert h["p50_ms"] < 6.0 and h["p90_ms"] > 50.0     # neither is the step


@pytest.mark.parametrize("log", ["with_period", "older_without_period"])
def test_report_prints_the_mean_period_as_the_step_time(log, tmp_path,
                                                        capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    path = str(tmp_path / "r.jsonl")
    telemetry.enable(path)
    for d in [0.004] * 6 + [0.100] * 4:
        telemetry.span_event("train.step", 0.0, 0.0036)
        if log == "with_period":
            telemetry.span_event("train.dispatch", 0.0, 0.0035)
            telemetry.hist("train.period", d)
    telemetry.finish(close=True)
    assert telemetry_report.main([path]) == 0
    out = capsys.readouterr().out
    titles = [ln for ln in out.splitlines() if ln.startswith("== ")]
    if log == "with_period":
        assert "== step time (mean of train.period) ==" in titles
        assert "n=10  mean=42.40ms" in out
        assert "== dispatch percentiles (train.dispatch) ==" in titles
    else:
        # what an older log printed, labelled for what it is
        assert not any("step time" in t and "not the step time" not in t
                       for t in titles)
        assert any(t.startswith("== dispatch percentiles (train.step")
                   for t in titles)
    # no percentile stands under a "step time" heading
    for i, ln in enumerate(out.splitlines()):
        if ln.startswith("== step time"):
            assert "p50" not in out.splitlines()[i + 1]
