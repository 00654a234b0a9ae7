"""Fleet observability plane (ISSUE 10): cross-process trace
propagation through the router, stitched Chrome traces, live fleet
metrics federation with EXACT histogram merge, fleet-wide SLO burn,
and outlier-replica detection.

Everything here is jax-free and in-process (servd frontends + statusd
servers on loopback, routers with probing and federation OFF the clock
— every sweep is an explicit call), so the suite stays cheap; the
subprocess chaos lives in test_routerd.py.

The headline guarantees:

* ONE id names a request on every process that touched it — including
  a replica that only SHED it (the retried-request case);
* router ``/trace?request=<id>`` returns one stitched trace whose
  router lane and every replica phase lane share the id, clock-aligned
  on the wall epoch;
* pre-TRACE replicas and TRACE-less clients keep working unchanged
  (the backward-compat acceptance);
* fleet histogram federation is exact: merged bucket counts equal the
  sum of per-replica bucket counts;
* the fleet SLO account fires on a fleet-wide budget violation no
  single replica triggers alone.
"""

import json
import os
import socket
import threading
import time
from urllib.request import urlopen

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from cxxnet_tpu.utils import routerd, servd, statusd, telemetry

from . import faultinject


@pytest.fixture(autouse=True)
def _lockrank_on(monkeypatch):
    """Runtime lock-order enforcement for every router/frontend/statusd
    this suite constructs (the test_servd/test_routerd pattern)."""
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")


def _drain_all(*objs):
    for o in objs:
        if o is None:
            continue
        if hasattr(o, "drain"):
            o.drain(timeout_ms=1000)
        elif hasattr(o, "stop"):
            o.stop()


def wait_until(cond, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError("timed out waiting for " + msg)


def occupy_and_fill(fe, port):
    """Park one request inside a BLOCKING backend and one in the 1-slot
    queue, deterministically: waiting on ``accepted == 2`` alone is
    ambiguous — on a fast machine the second send can race the worker's
    pop of the first and be SHED instead of queued, leaving the queue
    empty and the next request queued behind the blocked backend
    instead of instantly shed. Returns the open sockets."""
    socks = []
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(b"9\n")
    socks.append(s)
    wait_until(lambda: fe._inflight == 1, msg="worker occupied")
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(b"9\n")
    socks.append(s)
    wait_until(lambda: len(fe._q) == 1, msg="queue full")
    return socks


# ----------------------------------------------------------------------
# servd: the TRACE prefix contract
def test_trace_prefix_adopted_and_validated():
    fe = servd.ServeFrontend(lambda toks, seq: [t + 1 for t in toks],
                             drain_ms=2000.0)
    fe.start()
    port = fe.listen(0)
    try:
        # TRACE-less requests keep their dense local ids (unchanged)
        assert faultinject.serve_request(port, "1 2") == "2 3"
        assert fe.flight.get("1")["outcome"] == "served"
        # a TRACE id is adopted as THE request id
        assert faultinject.serve_request(port, "TRACE fleet-7 5") == "6"
        rec = fe.flight.get("fleet-7")
        assert rec is not None and rec["outcome"] == "served"
        # composes with DEADLINE (TRACE first)
        assert faultinject.serve_request(
            port, "TRACE fleet-8 DEADLINE 5000 7") == "8"
        assert fe.flight.get("fleet-8") is not None
        # malformed ids: ERR proto with the machine-readable third token
        for bad in ("TRACE", "TRACE bad/id 1", "TRACE %s 1" % ("y" * 65),
                    "TRACE id,comma 1"):
            resp = faultinject.serve_request(port, bad)
            assert resp.startswith("ERR proto trace"), (bad, resp)
            assert not routerd.retryable(resp)
        # TRACE with no request line is the empty class, like a blank
        assert faultinject.serve_request(
            port, "TRACE fleet-9").startswith("ERR empty")
        # TRACE + ADMIN composes too (the prefix is stripped first)
        assert faultinject.serve_request(
            port, "TRACE fleet-a ADMIN stats").startswith("OK accepted=")
    finally:
        stats = fe.drain()
    assert stats["accepted"] == (stats["served"] + stats["errors"]
                                 + stats["shed"] + stats["deadline"])


def test_admission_shed_leaves_flight_record_under_trace_id():
    """A queue-full shed never dequeues, but it still files a flight
    record under the propagated id — that is what makes the shed hop
    visible in the stitched cross-process trace."""
    release = threading.Event()

    def slow(toks, seq):
        release.wait(10.0)
        return list(toks)

    telemetry.enable()               # in-memory: the shed's event
    fe = servd.ServeFrontend(slow, queue_size=1, drain_ms=2000.0)
    fe.start()
    port = fe.listen(0)
    socks = []
    try:
        socks += occupy_and_fill(fe, port)
        resp = faultinject.serve_request(port, "TRACE shed-1 5")
        assert resp.startswith("ERR busy queue"), resp
        rec = fe.flight.get("shed-1")
        assert rec is not None and rec["outcome"] == "shed", rec
        assert rec["shed_at"] == "queue"
        assert all(v == 0.0 for v in rec["phases"].values())
        assert rec["ttft_s"] is None
        # the shed emits a serve_request_done too — the OFFLINE --fleet
        # join needs the shed hop, not just the live stitch — with
        # NULL phases (never-dispatched events must not deflate the
        # report's percentile table)
        done = [e for e in telemetry.recent_events()
                if e.get("ev") == "serve_request_done"
                and e.get("req") == "shed-1"]
        assert len(done) == 1 and done[0]["outcome"] == "shed"
        assert done[0]["prefill_s"] is None \
            and done[0]["queue_wait_s"] is None
    finally:
        release.set()
        for s in socks:
            s.close()
        fe.drain()
        telemetry.disable()


# ----------------------------------------------------------------------
# router: minting, propagation, retry-under-one-id, stitched trace
def _fleet(n_backends):
    """n in-process replicas (frontend + statusd with the flight ring
    wired, global registry) behind a started router with probing and
    federation off the clock. Returns (router, [fe], [status])."""
    fes, sss = [], []
    for backend, kw in n_backends:
        fe = servd.ServeFrontend(backend, drain_ms=2000.0, **kw)
        fe.start()
        fe.listen(0)
        ss = statusd.StatusServer(0, host="127.0.0.1").start()
        ss.register_probe("serving", fe.health_probe)
        ss.flight = fe.flight
        fes.append(fe)
        sss.append(ss)
    router = routerd.Router(
        [("127.0.0.1", fe.port, ss.port) for fe, ss in zip(fes, sss)],
        probe_ms=3600e3, retries=2, stall_s=5.0, drain_ms=2000.0,
        federate_ms=3600e3, outlier_min_n=1)
    router.start()
    router.listen(0)
    return router, fes, sss


def test_retry_under_one_id_and_stitched_trace():
    """THE acceptance: a request retried across two replicas produces
    ONE stitched Chrome trace from router /trace?request=<id> whose
    router-lane spans and BOTH replicas' phase lanes share the id,
    with clock-aligned timestamps."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return list(toks)

    def fast(toks, seq):
        return [t + 1000 for t in toks]

    router, (fe1, fe2), (s1, s2) = _fleet(
        [(wedged, {"queue_size": 1}), (fast, {})])
    srv = statusd.StatusServer(0, host="127.0.0.1").start()
    srv.fleet = router
    srv.flight = router.flight
    socks = []
    try:
        # wedge replica 1 and fill its 1-slot queue so any pick of it
        # sheds ERR busy queue (zero load, index tie-break -> 1 first)
        socks += occupy_and_fill(fe1, fe1.port)
        assert faultinject.serve_request(router.port, "5") == "1005"
        rrec = router.flight.list()[0]
        tid = rrec["id"]
        assert rrec["outcome"] == "served" and rrec["retries"] == 1
        assert [a["replica"] for a in rrec["attempts"]] \
            == [router._replicas[0].name, router._replicas[1].name]
        assert rrec["attempts"][0]["outcome"].startswith("ERR busy")
        assert rrec["attempts"][0]["retried"] is True
        assert rrec["attempts"][1]["outcome"] == "served"
        # the pick-time candidates rode along (explainable routing)
        assert rrec["attempts"][0]["candidates"], rrec["attempts"][0]
        # ONE id on every process that touched the request — the shed
        # replica included
        assert fe1.flight.get(tid)["outcome"] == "shed"
        assert fe2.flight.get(tid)["outcome"] == "served"
        # the stitched trace off the router's statusd: router lanes
        # (pid 0) + BOTH replica lanes (pid 1, 2), every span tagged
        # with the id, timestamps clock-aligned on the wall epoch
        body = urlopen("http://127.0.0.1:%d/trace?request=%s"
                       % (srv.port, tid), timeout=5).read()
        trace = json.loads(body)
        xs = [t for t in trace["traceEvents"] if t.get("ph") == "X"]
        assert {t["pid"] for t in xs} == {0, 1, 2}, xs
        assert all(t["args"]["request"] == tid for t in xs)
        forwards = [t for t in xs if t["name"].startswith("forward:")]
        assert len(forwards) == 2
        # clock alignment: every lane's events land inside the router's
        # request window (same machine, shared wall clock; generous
        # slack for the wall-vs-monotonic stamp skew)
        req_span = next(t for t in xs if t["name"].startswith("route:"))
        t_hi = req_span["ts"] + req_span["dur"]
        for t in xs:
            assert -5e4 <= t["ts"] <= t_hi + 5e4, (t, t_hi)
        # the replica lanes carry the phase split (prefill present)
        assert any(t["name"] == "prefill" and t["pid"] == 2
                   for t in xs)
    finally:
        release.set()
        for s in socks:
            s.close()
        _drain_all(router, srv, s1, s2, fe1, fe2)


def test_pre_trace_replica_downgrade_and_latch():
    """Backward compat: a pre-PR-10 replica rejects the TRACE prefix
    itself as ERR parse; the router resends the bare line once (the
    parse rejection proves nothing dispatched), latches the replica
    no_trace, and serves the request — the client sees nothing."""
    lines = []

    class OldServer:
        """A pre-TRACE servd: integer tokens only, echo + 1."""

        def __init__(self):
            self.sock = socket.create_server(("127.0.0.1", 0))
            self.sock.settimeout(0.25)
            self.port = self.sock.getsockname()[1]
            self.alive = True
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while self.alive:
                try:
                    conn, _ = self.sock.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return
                with conn:
                    try:
                        line = conn.makefile("r").readline().strip()
                        lines.append(line)
                        try:
                            toks = [int(t) for t in line.split()]
                            resp = " ".join(str(t + 1) for t in toks)
                        except ValueError:
                            resp = "ERR parse non-integer token in request"
                        conn.sendall((resp + "\n").encode())
                    except OSError:
                        pass

        def stop(self):
            self.alive = False
            self.sock.close()

    old = OldServer()
    router = routerd.Router([("127.0.0.1", old.port, old.port)],
                            probe_ms=3600e3, retries=0, stall_s=5.0,
                            drain_ms=1000.0)
    router.start()
    router.listen(0)
    try:
        # first request: traced attempt rejected, bare resend served
        assert faultinject.serve_request(router.port, "1 2") == "2 3"
        assert len(lines) == 2 and lines[0].startswith("TRACE ")
        assert lines[1] == "1 2"
        assert router._replicas[0].no_trace is True
        rec = router.flight.list()[0]
        assert rec["outcome"] == "served"
        assert rec["attempts"][0].get("trace_downgraded") is True
        # latched: the next request goes bare on the FIRST try
        assert faultinject.serve_request(router.port, "7") == "8"
        assert len(lines) == 3 and lines[2] == "7"
        # a genuine client parse error is still relayed verbatim
        assert faultinject.serve_request(
            router.port, "x y").startswith("ERR parse")
    finally:
        _drain_all(router, old)


def test_genuine_parse_error_does_not_latch_new_replica():
    """A TRACE-capable replica answering ERR parse for a genuinely
    malformed request: the bare resend answers the same, the relay is
    verbatim, and the replica is NOT latched no_trace."""
    router, (fe,), (ss,) = _fleet(
        [(lambda toks, seq: list(toks), {})])
    try:
        assert faultinject.serve_request(
            router.port, "not numbers").startswith("ERR parse")
        assert router._replicas[0].no_trace is False
        # and a traced request still propagates normally afterwards
        assert faultinject.serve_request(router.port, "3") == "3"
        tid = router.flight.list()[0]["id"]
        assert fe.flight.get(tid) is not None
    finally:
        _drain_all(router, ss, fe)


def test_trace_ok_latch_skips_downgrade_resend():
    """Once a traced exchange succeeded, the replica has PROVEN it
    parses TRACE — later genuine client parse errors must not pay the
    downgrade resend (a malformed-request flood would otherwise hit
    the replica twice per request, forever)."""
    router, (fe,), (ss,) = _fleet(
        [(lambda toks, seq: [t + 1 for t in toks], {})])
    try:
        assert faultinject.serve_request(router.port, "1") == "2"
        assert router._replicas[0].trace_ok is True
        before = fe.stats()["accepted"]
        assert faultinject.serve_request(
            router.port, "not numbers").startswith("ERR parse")
        # exactly ONE replica-side request for the malformed line —
        # no bare resend against a proven-TRACE replica
        assert fe.stats()["accepted"] == before + 1
        assert router._replicas[0].no_trace is False
    finally:
        _drain_all(router, ss, fe)


def test_router_proto_err_and_client_id_adoption():
    router, (fe,), (ss,) = _fleet(
        [(lambda toks, seq: list(toks), {})])
    try:
        # the router validates TRACE like a replica would
        resp = faultinject.serve_request(router.port, "TRACE bad/id 1")
        assert resp.startswith("ERR proto trace"), resp
        # a client-minted id is adopted fleet-wide, not re-minted
        assert faultinject.serve_request(
            router.port, "TRACE mine-1 4") == "4"
        assert router.flight.get("mine-1")["outcome"] == "served"
        assert fe.flight.get("mine-1")["outcome"] == "served"
        st = router.stats()
        assert st["accepted"] == (st["served"] + st["errors"]
                                  + st["shed"] + st["deadline"])
    finally:
        _drain_all(router, ss, fe)


# ----------------------------------------------------------------------
# federation: exact histogram merge, fleet SLO, outlier detection
def _metric_statusd(hists, slo=None, counters=None):
    """A statusd over a PRIVATE registry pre-loaded with histograms —
    a stand-in replica for the federation pulls (no frontend needed:
    federation reads /metrics?json=1, nothing else)."""
    reg = telemetry._Registry()
    reg.enable()
    for name, values in hists.items():
        for v in values:
            reg.hist(name, v)
    for name, v in (counters or {}).items():
        reg.count(name, v)
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=reg)
    srv.slo = slo
    return srv.start(), reg


def test_fleet_federation_exact_histogram_merge():
    """The acceptance: for every merged series, fleet bucket counts
    equal the SUM of the per-replica bucket counts (shared fixed
    buckets make the merge exact — no re-binning)."""
    s1, reg1 = _metric_statusd(
        {"serve.request": [0.001, 0.002, 0.004, 1.7],
         "serve.ttft": [0.0005, 0.003]},
        counters={"serve.accepted": 4, "serve.requests": 4})
    s2, reg2 = _metric_statusd(
        {"serve.request": [0.001, 0.09, 0.4],
         "serve.ttft": [0.01],
         "serve.queue_wait": [0.0001]},
        counters={"serve.accepted": 3, "serve.requests": 2})
    router = routerd.Router(
        [("127.0.0.1", 1, s1.port), ("127.0.0.1", 2, s2.port)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_min_n=1)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 2
        fed = router.federation_snapshot()
        assert fed["replicas"] == 2
        shards = [reg1.metrics_snapshot()["hists"],
                  reg2.metrics_snapshot()["hists"]]
        assert set(fed["series"]) \
            == {"serve.request", "serve.ttft", "serve.queue_wait"}
        for name, h in fed["series"].items():
            expect = {}
            for shard in shards:
                for i, c in (shard.get(name, {}).get("buckets")
                             or {}).items():
                    expect[i] = expect.get(i, 0) + c
            assert h["buckets"] == expect, (name, h["buckets"], expect)
            assert h["count"] == sum(expect.values())
        # counters sum too
        assert fed["counters"]["serve.accepted"] == 7
        assert fed["counters"]["serve.requests"] == 6
        # and the router's own /metrics carries the federated series,
        # Prometheus-valid, with the summed +Inf bucket count
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        inf = [line for line in metrics.splitlines()
               if line.startswith("cxxnet_fleet_serve_request_seconds"
                                  "_bucket") and 'le="+Inf"' in line]
        assert inf and inf[0].rsplit(" ", 1)[1] == "7", inf
        assert "cxxnet_fleet_serve_accepted_total" in metrics
        assert "cxxnet_fleet_federated_replicas" in metrics
    finally:
        _drain_all(router, rsrv, s1, s2)


def test_fleet_slo_burn_fires_when_no_single_replica_does():
    """The acceptance: each replica stays under its own alert floor
    (bad < min_bad), so neither replica's cxxnet_slo_burn fires — but
    the fleet-wide merged window is over budget AND over the floors,
    so cxxnet_fleet_slo_burn does."""
    trackers = []
    servers = []
    for _ in range(2):
        slo = statusd.SLOTracker(availability=0.999, min_requests=10,
                                 min_bad=3, window_s=300.0)
        for _ in range(8):
            slo.observe(ok=True)
        for _ in range(2):           # 2 bad < min_bad=3: no page
            slo.observe(ok=False)
        assert slo.snapshot()["alert"] == 0, slo.snapshot()
        srv, _ = _metric_statusd({}, slo=slo)
        trackers.append(slo)
        servers.append(srv)
    router = routerd.Router(
        [("127.0.0.1", i + 1, s.port)
         for i, s in enumerate(servers)],
        probe_ms=3600e3, federate_ms=3600e3)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 2
        fslo = router.federation_snapshot()["slo"]
        assert fslo["requests"] == 20 and fslo["bad"] == 4
        assert fslo["burn_rate"] >= 1.0 and fslo["alert"] == 1, fslo
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        assert "cxxnet_fleet_slo_burn" in metrics
        assert any(line.startswith("cxxnet_fleet_slo_burn{")
                   and line.endswith(" 1")
                   for line in metrics.splitlines()), metrics
    finally:
        _drain_all(router, rsrv, *servers)


def test_outlier_replica_detected_and_flagged():
    """One slow replica among three: its p99 diverges past the ratio
    from the fleet median -> outlier gauge 1, transition-only
    fleet_outlier event, flagged row on /fleetz."""
    telemetry.enable()               # in-memory: the transition events
    fast = [0.01] * 30
    servers = [
        _metric_statusd({"serve.request": fast})[0],
        _metric_statusd({"serve.request": fast})[0],
        _metric_statusd({"serve.request": [0.5] * 30})[0],
    ]
    router = routerd.Router(
        [("127.0.0.1", i + 1, s.port) for i, s in enumerate(servers)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_ratio=3.0,
        outlier_min_n=10)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 3
        fed = router.federation_snapshot()
        slow_name = router._replicas[2].name
        assert fed["outliers"][slow_name]["outlier"] is True
        assert all(not fed["outliers"][r.name]["outlier"]
                   for r in router._replicas[:2])
        # transition-only event: a second identical sweep adds nothing
        evs = [e for e in telemetry.recent_events()
               if e.get("ev") == "fleet_outlier"]
        assert len(evs) == 1 and evs[0]["replica"] == slow_name
        assert evs[0]["outlier"] == 1
        assert router.federate_now() == 3
        evs = [e for e in telemetry.recent_events()
               if e.get("ev") == "fleet_outlier"]
        assert len(evs) == 1, evs
        # /fleetz flags the row; /metrics carries the per-replica gauge
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "OUTLIER" in page
        fj = json.loads(urlopen("http://127.0.0.1:%d/fleetz?json=1"
                                % rsrv.port, timeout=5).read())
        slow_row = next(r for r in fj["replicas"]
                        if r["name"] == slow_name)
        assert slow_row["outlier"] is True
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        assert ('cxxnet_fleet_outlier{process="0",replica="%s"} 1'
                % slow_name) in metrics
        assert "cxxnet_fleet_replica_p99_seconds" in metrics
        # a flagged replica that leaves the verdict set (dies) emits
        # its CLEARING transition — outlier=1 with no outlier=0 would
        # page forever on event-based alerting
        router._mark(router._replicas[2], routerd.DEAD, "killed")
        router.federate_now()
        evs = [e for e in telemetry.recent_events()
               if e.get("ev") == "fleet_outlier"]
        assert len(evs) == 2, evs
        assert evs[-1]["replica"] == slow_name \
            and evs[-1]["outlier"] == 0
    finally:
        _drain_all(router, rsrv, *servers)
        telemetry.disable()


def test_outlier_detected_in_two_replica_fleet():
    """Leave-one-out median: the common 2-replica topology can flag
    its slow half (an include-itself median of two values is their
    mean, which no ratio >= 2 can ever exceed)."""
    fast = _metric_statusd({"serve.request": [0.01] * 20})[0]
    slow = _metric_statusd({"serve.request": [1.0] * 20})[0]
    router = routerd.Router(
        [("127.0.0.1", 1, fast.port), ("127.0.0.1", 2, slow.port)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_ratio=3.0,
        outlier_min_n=10)
    router.start()
    try:
        assert router.federate_now() == 2
        verdicts = router.federation_snapshot()["outliers"]
        assert verdicts[router._replicas[1].name]["outlier"] is True
        assert verdicts[router._replicas[0].name]["outlier"] is False
    finally:
        _drain_all(router, fast, slow)


def test_federation_keeps_last_known_snapshot_on_missed_sweep():
    """One transient scrape miss must not make the cxxnet_fleet_*
    counters/buckets dip (Prometheus would read a counter dip as a
    process reset and re-count the replica's lifetime totals): a live
    replica that missed a sweep keeps its last-known snapshot; only a
    DEAD replica leaves the merge."""
    s1 = _metric_statusd({"serve.request": [0.01] * 4},
                         counters={"serve.accepted": 4})[0]
    s2 = _metric_statusd({"serve.request": [0.02] * 3},
                         counters={"serve.accepted": 3})[0]
    router = routerd.Router(
        [("127.0.0.1", 1, s1.port), ("127.0.0.1", 2, s2.port)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_min_n=1)
    router.start()
    try:
        assert router.federate_now() == 2
        assert router.federation_snapshot()["counters"][
            "serve.accepted"] == 7
        # replica 2's statusd goes away (scrape miss) but the replica
        # is NOT dead: its last-known contribution stays in the merge
        s2.stop()
        assert router.federate_now() == 1
        fed = router.federation_snapshot()
        assert fed["replicas"] == 2
        assert fed["counters"]["serve.accepted"] == 7, fed["counters"]
        assert fed["series"]["serve.request"]["count"] == 7
        # a DEAD replica's contribution does leave (a real reset)
        router._mark(router._replicas[1], routerd.DEAD, "killed")
        router.federate_now()
        fed = router.federation_snapshot()
        assert fed["replicas"] == 1
        assert fed["counters"]["serve.accepted"] == 4, fed["counters"]
    finally:
        _drain_all(router, s1)


# ----------------------------------------------------------------------
# multi-tenant QoS on the observability plane (ISSUE 13)
def test_pre_tenant_replica_downgrade_ladder():
    """Backward compat, the TENANT edition of the TRACE downgrade: a
    pre-TENANT replica rejects the prefixed line as ERR parse; the
    router walks the ladder (drop TENANT, then TRACE too), serves the
    request bare, and latches what the replica cannot speak — the
    client sees nothing. A pre-TRACE replica latches BOTH."""
    lines = []

    class OldServer:
        """A pre-TRACE, pre-TENANT servd: integer tokens only."""

        def __init__(self):
            self.sock = socket.create_server(("127.0.0.1", 0))
            self.sock.settimeout(0.25)
            self.port = self.sock.getsockname()[1]
            self.alive = True
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            while self.alive:
                try:
                    conn, _ = self.sock.accept()
                except socket.timeout:
                    continue
                except OSError:
                    return
                with conn:
                    try:
                        line = conn.makefile("r").readline().strip()
                        lines.append(line)
                        try:
                            toks = [int(t) for t in line.split()]
                            resp = " ".join(str(t + 1) for t in toks)
                        except ValueError:
                            resp = ("ERR parse non-integer token in "
                                    "request")
                        conn.sendall((resp + "\n").encode())
                    except OSError:
                        pass

        def stop(self):
            self.alive = False
            self.sock.close()

    old = OldServer()
    router = routerd.Router([("127.0.0.1", old.port, old.port)],
                            probe_ms=3600e3, retries=0, stall_s=5.0,
                            drain_ms=1000.0,
                            tenants="noisy:1,victim:4",
                            tenant_default="victim")
    router.start()
    router.listen(0)
    try:
        # first request: TRACE+TENANT rejected, TRACE-only rejected,
        # bare served — the full ladder, one wire line per rung
        assert faultinject.serve_request(
            router.port, "TENANT noisy 1 2") == "2 3"
        assert len(lines) == 3, lines
        assert lines[0].split()[0] == "TRACE" \
            and lines[0].split()[2] == "TENANT"
        assert lines[1].split()[0] == "TRACE" \
            and "TENANT" not in lines[1]
        assert lines[2] == "1 2"
        r = router._replicas[0]
        assert r.no_trace is True and r.no_tenant is True
        # latched: the next request goes bare on the FIRST wire line,
        # and the tenant is still ACCOUNTED router-side
        assert faultinject.serve_request(
            router.port, "TENANT noisy 7") == "8"
        assert len(lines) == 4 and lines[3] == "7"
        ts = router.tenant_stats()
        assert ts["noisy"]["accepted"] == 2 \
            and ts["noisy"]["served"] == 2
    finally:
        _drain_all(router, old)


def test_tenant_downgrade_skipped_for_proven_replica():
    """The positive latch, TENANT edition: one successful tenant-
    prefixed exchange proves the replica parses TENANT — a later
    genuine client parse error pays NO downgrade resends."""
    fe = servd.ServeFrontend(lambda toks, seq: [t + 1 for t in toks],
                             drain_ms=2000.0,
                             tenants="noisy:1,victim:4",
                             tenant_default="victim")
    fe.start()
    fe.listen(0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    router = routerd.Router([("127.0.0.1", fe.port, ss.port)],
                            probe_ms=3600e3, retries=0, stall_s=5.0,
                            drain_ms=1000.0,
                            tenants="noisy:1,victim:4",
                            tenant_default="victim")
    router.start()
    router.listen(0)
    try:
        assert faultinject.serve_request(
            router.port, "TENANT noisy 1") == "2"
        r = router._replicas[0]
        assert r.trace_ok is True and r.tenant_ok is True
        before = fe.stats()["accepted"]
        assert faultinject.serve_request(
            router.port, "TENANT noisy not numbers") \
            .startswith("ERR parse")
        # exactly ONE replica-side request for the malformed line
        assert fe.stats()["accepted"] == before + 1
        assert r.no_trace is False and r.no_tenant is False
    finally:
        _drain_all(router, ss, fe)


def test_per_tenant_federation_series_and_slo():
    """The per-tenant fleet account: serve.tenant.* counters sum
    exactly, per-tenant hists merge into a fleet p99, per-tenant SLO
    windows merge (victim holds, noisy burns), and the router's
    statusd renders the cxxnet_fleet_tenant_*{tenant=} label rows and
    the cxxnet_slo_tenant_* replica rows — all Prometheus-valid."""
    noisy_slo = statusd.SLOTracker(availability=0.99, min_requests=4,
                                   min_bad=3, window_s=300.0)
    victim_slo = statusd.SLOTracker(availability=0.99, min_requests=4,
                                    min_bad=3, window_s=300.0)
    for _ in range(6):
        noisy_slo.observe(ok=False)
        victim_slo.observe(ok=True)
    shards = []
    for k in (2, 3):
        srv, reg = _metric_statusd(
            {"serve.tenant.noisy.request": [0.001] * k,
             "serve.tenant.victim.request": [0.01] * k},
            counters={"serve.tenant.noisy.accepted": 5 * k,
                      "serve.tenant.noisy.shed": 4 * k,
                      "serve.tenant.victim.accepted": 2 * k,
                      "serve.tenant.victim.served": 2 * k})
        srv.slo_tenants = {"noisy": noisy_slo, "victim": victim_slo}
        shards.append(srv)
    router = routerd.Router(
        [("127.0.0.1", i + 1, s.port)
         for i, s in enumerate(shards)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_min_n=1,
        tenants="noisy:1,victim:4", tenant_default="victim")
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 2
        fed = router.federation_snapshot()
        # counters summed per tenant; fleet p99 from the merged hist
        assert fed["tenants"]["noisy"]["accepted"] == 25
        assert fed["tenants"]["noisy"]["shed"] == 20
        assert fed["tenants"]["victim"]["served"] == 10
        assert fed["tenants"]["noisy"]["p99_ms"] is not None
        # per-tenant merged windows: noisy burns (both shards observed
        # the same trackers here — the merge path is what's pinned),
        # victim holds 0
        assert fed["slo_tenants"]["noisy"]["alert"] == 1
        assert fed["slo_tenants"]["victim"]["alert"] == 0
        # label rows on the router's /metrics, Prometheus-valid
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert ('cxxnet_fleet_tenant_weight{process="0",'
                'tenant="victim"} 4') in metrics
        assert 'cxxnet_fleet_tenant_slo_burn{' in metrics
        assert 'cxxnet_fleet_tenant_p99_seconds{' in metrics
        # ... and the /fleetz tenants section renders
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "tenants (weighted-fair QoS)" in page
        # the replica-side per-tenant rows + json federation feed
        rep_metrics = urlopen("http://127.0.0.1:%d/metrics"
                              % shards[0].port,
                              timeout=5).read().decode()
        assert 'cxxnet_slo_tenant_burn{process="0",tenant="noisy"} 1' \
            in rep_metrics
        mj = json.loads(urlopen("http://127.0.0.1:%d/metrics?json=1"
                                % shards[0].port, timeout=5).read())
        assert mj["slo_tenants"]["victim"]["alert"] == 0
    finally:
        _drain_all(router, rsrv, *shards)


# ----------------------------------------------------------------------
# the offline --fleet report join
def test_fleet_report_joins_router_and_replica_shards(tmp_path, capsys):
    import subprocess
    import sys

    router_log = tmp_path / "router.jsonl"
    rep_a = tmp_path / "rep_a.jsonl"
    rep_b = tmp_path / "rep_b.jsonl"
    router_log.write_text("\n".join(json.dumps(e) for e in [
        {"ev": "meta", "pid": 1, "t0_wall": 1000.0, "ts": 0.0, "p": 0},
        {"ev": "route_request_done", "req": "f-1", "outcome": "served",
         "attempts": 2, "retries": 1,
         "replicas": ["127.0.0.1:71", "127.0.0.1:72"],
         "total_s": 0.25, "ts": 1.0, "p": 0},
        {"ev": "fleet_outlier", "replica": "127.0.0.1:72",
         "outlier": 1, "p99_ms": 90.0, "fleet_p99_ms": 25.0,
         "ts": 2.0, "p": 0},
    ]) + "\n")
    rep_a.write_text("\n".join(json.dumps(e) for e in [
        {"ev": "meta", "pid": 2, "t0_wall": 1000.2, "ts": 0.0, "p": 0},
        {"ev": "serve_request_done", "req": "f-1", "outcome": "shed",
         "tokens": 0, "total_s": 0.0, "queue_wait_s": 0.0,
         "dispatch_s": 0.0, "prefill_s": None, "decode_s": None,
         "recompiles": 0, "ts": 0.8, "p": 0},
    ]) + "\n")
    rep_b.write_text("\n".join(json.dumps(e) for e in [
        {"ev": "meta", "pid": 3, "t0_wall": 1000.1, "ts": 0.0, "p": 0},
        {"ev": "serve_request_done", "req": "f-1", "outcome": "served",
         "tokens": 8, "total_s": 0.2, "ttft_s": 0.04,
         "queue_wait_s": 0.001, "dispatch_s": 0.0001,
         "prefill_s": 0.04, "decode_s": 0.155, "recompiles": 0,
         "ts": 1.1, "p": 0},
    ]) + "\n")
    proc = subprocess.run(
        [sys.executable, "tools/telemetry_report.py", "--fleet",
         str(router_log), str(rep_a), str(rep_b)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "fleet requests (router <-> replica join on trace id)" in out
    assert "routed: 1" in out and "retried: 1" in out
    assert "hop-matched: 1" in out
    # both hops rendered under the one router request, shed + served
    assert "hop p=1" in out and "hop p=2" in out
    assert "router overhead" in out
    assert "OUTLIER" in out
    # duplicate-p shards are exactly why --fleet relabels: --merge on
    # the same inputs refuses
    proc2 = subprocess.run(
        [sys.executable, "tools/telemetry_report.py", "--merge",
         str(router_log), str(rep_a)],
        capture_output=True, text=True, cwd=REPO)
    assert proc2.returncode != 0


# ----------------------------------------------------------------------
# statusd: /requestz parameters on a serving process
class _FakeBatch:
    """A batch_snapshot provider for a stand-in replica's statusd —
    the federation reads the /metrics?json=1 "batch" key, nothing
    else, so the fake needs only the snapshot dict."""

    def __init__(self, snap):
        self._snap = snap
        self.batch_flight = None

    def batch_snapshot(self, ring: int = 0):
        return dict(self._snap)


def test_fleet_decode_account_federates_exactly():
    """The decode KV/convoy account federates EXACTLY: byte sums over
    the replicas' own accounts, live pct recomputed from the sums
    (never a mean of means), convoy replicas counted — and the
    serve.queue_age histogram rides the existing exact serve-series
    merge into cxxnet_fleet_serve_queue_age_seconds."""
    s1, reg1 = _metric_statusd(
        {"serve.queue_age": [0.01, 0.2]},
        counters={"serve.convoys": 1})
    s1.batch = _FakeBatch({"kv_bytes": 1000, "kv_live_bytes": 900,
                           "kv_live_pct": 90.0, "convoy": 1,
                           "convoys": 1, "buckets": {}})
    s2, reg2 = _metric_statusd({"serve.queue_age": [0.05]})
    s2.batch = _FakeBatch({"kv_bytes": 3000, "kv_live_bytes": 300,
                           "kv_live_pct": 10.0, "convoy": 0,
                           "convoys": 0, "buckets": {}})
    router = routerd.Router(
        [("127.0.0.1", 1, s1.port), ("127.0.0.1", 2, s2.port)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_min_n=1)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 2
        fed = router.federation_snapshot()
        dec = fed["decode"]
        assert dec["replicas"] == 2
        assert dec["kv_bytes"] == 4000
        assert dec["kv_live_bytes"] == 1200
        # 1200/4000 = 30% — the EXACT fleet ratio; a mean of the
        # per-replica pcts (90+10)/2 = 50% would be the lie
        assert dec["kv_live_pct"] == 30.0
        assert dec["convoy_replicas"] == 1
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert "cxxnet_fleet_decode_kv_bytes" in metrics
        assert "cxxnet_fleet_decode_kv_live_pct" in metrics
        assert "cxxnet_fleet_decode_convoy_replicas" in metrics
        # the queue-age histogram merged exactly (3 observations)
        inf = [line for line in metrics.splitlines()
               if line.startswith("cxxnet_fleet_serve_queue_age_"
                                  "seconds_bucket")
               and 'le="+Inf"' in line]
        assert inf and inf[0].rsplit(" ", 1)[1] == "3", inf
        # the episode counter sums through the serve.* counter merge
        assert "cxxnet_fleet_serve_convoys_total" in metrics
    finally:
        _drain_all(router, rsrv, s1, s2)


def test_fleetz_shows_per_bucket_batch_load():
    """The router parses ADMIN stats' batch_buckets / bucket.<b>.*
    keys off a REAL batching replica and surfaces them on /fleetz —
    the per-bucket load signal disaggregation will route on."""
    sb = faultinject.slot_backend(buckets=(2, 4), n_new=30,
                                  per_token_s=0.01)
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=4,
                             batch_window_ms=0.0, drain_ms=8000.0)
    fe.start()
    port = fe.listen(0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    router = routerd.Router([("127.0.0.1", port, ss.port)],
                            probe_ms=3600e3, federate_ms=3600e3)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    ts = []
    try:
        ts = [threading.Thread(
            target=faultinject.serve_request,
            args=(port, "%d00" % (i + 1),), kwargs={"timeout": 30.0})
            for i in range(2)]
        for t in ts:
            t.start()
        wait_until(lambda: fe.batch_snapshot()["buckets"]["2"]
                   ["active"] == 2, msg="batch underway")
        router.probe_now()
        snap = router.fleet_snapshot()
        rep = snap["replicas"][0]
        assert rep["buckets"]["2"] == {"warm": 1, "active": 2}
        assert rep["buckets"]["4"] == {"warm": 0, "active": 0}
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "2:2/2" in page, page
    finally:
        for t in ts:
            t.join()
        _drain_all(router, rsrv, fe, ss)


def test_fleet_federates_retained_pool_and_pressure():
    """The retained conversation cache federates EXACTLY
    (doc/robustness.md "Memory governance"): block/hit/token sums over
    the replicas' own pools, the fleet retained hit rate recomputed
    from the TOKEN sums (never a mean of per-replica rates), and
    pressure_replicas counting latched replicas — all riding the
    cxxnet_fleet_decode_* series and the /fleetz paged-kv line."""
    s1, _reg1 = _metric_statusd({})
    s1.batch = _FakeBatch({
        "kv_bytes": 0, "kv_live_bytes": 0, "convoy": 0, "convoys": 0,
        "buckets": {}, "pool": {
            "blocks_total": 8, "blocks_free": 1, "blocks_retained": 5,
            "prefix_hit_tokens": 30, "prompt_tokens": 100,
            "alloc_failures": 0, "retained_hits": 2,
            "retained_hit_tokens": 30, "pressure": 1}})
    s2, _reg2 = _metric_statusd({})
    s2.batch = _FakeBatch({
        "kv_bytes": 0, "kv_live_bytes": 0, "convoy": 0, "convoys": 0,
        "buckets": {}, "pool": {
            "blocks_total": 8, "blocks_free": 6, "blocks_retained": 1,
            "prefix_hit_tokens": 10, "prompt_tokens": 300,
            "alloc_failures": 0, "retained_hits": 1,
            "retained_hit_tokens": 10, "pressure": 0}})
    router = routerd.Router(
        [("127.0.0.1", 1, s1.port), ("127.0.0.1", 2, s2.port)],
        probe_ms=3600e3, federate_ms=3600e3, outlier_min_n=1)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        assert router.federate_now() == 2
        pl = router.federation_snapshot()["decode"]["pool"]
        assert pl["blocks_retained"] == 6
        assert pl["retained_hits"] == 3
        assert pl["retained_hit_tokens"] == 40
        # 40/400 = 10% — the EXACT fleet ratio; a mean of the
        # per-replica rates (30% + 3.33%)/2 ≈ 16.7% would be the lie
        assert pl["retained_hit_rate"] == 10.0
        assert pl["pressure_replicas"] == 1
        metrics = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                          timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        want = {"cxxnet_fleet_decode_kv_block_retained": "6",
                "cxxnet_fleet_decode_retained_hits_total": "3",
                "cxxnet_fleet_decode_retained_hit_rate": "10.0",
                "cxxnet_fleet_decode_kv_pressure_replicas": "1"}
        for name, val in want.items():
            row = [ln for ln in metrics.splitlines()
                   if ln.startswith(name + " ")
                   or ln.startswith(name + "{")]
            assert len(row) == 1 and row[0].endswith(" " + val), \
                (name, row)
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "PRESSURE on 1 replica(s)" in page, page
        assert "6 retained" in page, page
    finally:
        _drain_all(router, rsrv, s1, s2)


def test_fleetz_retained_column_and_garbage_guard(monkeypatch):
    """The router parses ADMIN stats' kv_retained_blocks /
    kv_retained_hits off a REAL retaining replica onto the /fleetz
    retained column; a replica WITHOUT the retained cache renders "-"
    (absence is the capability signal, never a lying 0); and garbage
    values from a foreign replica zero the fields instead of killing
    the prober thread (the PR 13 guard discipline)."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                  kv_pool_blocks=8, kv_block_tokens=4,
                                  kv_retained_frac=1.0)
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=4,
                             batch_window_ms=0.0, drain_ms=8000.0)
    fe.start()
    port = fe.listen(0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    # the retention-less replica: plain echo, no slot backend
    fe2 = servd.ServeFrontend(lambda toks, seq: [t + 1 for t in toks],
                              drain_ms=2000.0)
    fe2.start()
    port2 = fe2.listen(0)
    ss2 = statusd.StatusServer(0, host="127.0.0.1").start()
    ss2.register_probe("serving", fe2.health_probe)
    router = routerd.Router([("127.0.0.1", port, ss.port),
                             ("127.0.0.1", port2, ss2.port)],
                            probe_ms=3600e3, federate_ms=3600e3)
    router.start()
    rsrv = statusd.StatusServer(0, host="127.0.0.1").start()
    rsrv.fleet = router
    try:
        # turn 1 retires into the retained pool; turn 2 extends the
        # same conversation and REVIVES it (>= 1 retained hit)
        faultinject.serve_request(
            port, " ".join(str(t) for t in range(1, 9)), timeout=30.0)
        faultinject.serve_request(
            port, " ".join(str(t) for t in range(1, 13)), timeout=30.0)
        router.probe_now()
        reps = {r["name"]: r
                for r in router.fleet_snapshot()["replicas"]}
        warm = reps["127.0.0.1:%d" % port]
        bare = reps["127.0.0.1:%d" % port2]
        assert warm["kv_retained_hits"] >= 1, warm
        assert isinstance(warm["kv_retained_blocks"], int)
        assert bare["kv_retained_blocks"] is None
        assert bare["kv_retained_hits"] is None
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "%s:%s" % (warm["kv_retained_blocks"],
                          warm["kv_retained_hits"]) in page, page
        # a foreign replica answering garbage for the retained keys:
        # the guarded parse zeroes the fields, the prober survives
        monkeypatch.setattr(
            router, "_replica_stats",
            lambda r: {"queue_depth": 0, "in_flight": 0,
                       "kv_retained_blocks": "grue",
                       "kv_retained_hits": []})
        router.probe_now()        # must not raise / kill the prober
        reps = {r["name"]: r
                for r in router.fleet_snapshot()["replicas"]}
        warm = reps["127.0.0.1:%d" % port]
        assert warm["kv_retained_blocks"] == 0
        assert warm["kv_retained_hits"] == 0
    finally:
        _drain_all(router, rsrv, fe, ss, fe2, ss2)


def test_batchz_and_metrics_render_retained_cache():
    """statusd renders the retained-cache account: the /batchz
    "retained cache:" line (parked/cap/revivals/hit-pct/evictions +
    the MEMORY PRESSURE flag) and the per-process
    cxxnet_decode_retained_* / cxxnet_decode_kv_pressure series —
    pure render off the published pool snapshot."""
    srv = statusd.StatusServer(0, host="127.0.0.1").start()
    srv.batch = _FakeBatch({
        "kv_bytes": 1 << 20, "kv_live_bytes": 1 << 19, "convoy": 0,
        "convoys": 0, "buckets": {}, "pool": {
            "blocks_total": 16, "blocks_free": 4, "block_tokens": 8,
            "pool_bytes": 1 << 20, "prefix_hits": 3,
            "prefix_queries": 5, "prefix_hit_rate": 40.0,
            "prefix_hit_tokens": 40, "prompt_tokens": 100,
            "cow_copies": 0, "alloc_failures": 0,
            "blocks_retained": 5, "retained_cap": 15,
            "retained_hits": 2, "retained_hit_tokens": 30,
            "retained_hit_rate": 30.0, "retained_evictions": 4,
            "kv_retained_pct": 31.25, "pressure": 1}})
    base = "http://127.0.0.1:%d" % srv.port
    try:
        page = urlopen(base + "/batchz", timeout=5).read().decode()
        assert "retained cache: 5 block(s) parked (cap 15), " \
            "2 revival(s) (30.0% of prompt tokens), 4 eviction(s)" \
            in page, page
        assert "MEMORY PRESSURE (shedding)" in page, page
        metrics = urlopen(base + "/metrics", timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        want = {"cxxnet_decode_kv_block_retained": "5",
                "cxxnet_decode_retained_hits_total": "2",
                "cxxnet_decode_retained_hit_tokens_total": "30",
                "cxxnet_decode_retained_evictions_total": "4",
                "cxxnet_decode_retained_hit_rate": "30.0",
                "cxxnet_decode_kv_pressure": "1"}
        for name, val in want.items():
            row = [ln for ln in metrics.splitlines()
                   if ln.startswith(name)]
            assert len(row) == 1 and row[0].endswith(" " + val), \
                (name, row)
    finally:
        _drain_all(srv)


def test_requestz_limit_json_and_single_record():
    fr = telemetry.FlightRecorder(cap=8)
    for i in range(6):
        fr.record({"id": "q-%d" % i, "outcome": "served",
                   "total_s": 0.01 * i, "ttft_s": 0.001,
                   "tokens_out": i,
                   "phases": {"queue_wait": 0.0, "dispatch": 0.0,
                              "prefill": 0.01 * i, "decode": 0.0},
                   "recompiles": []})
    srv = statusd.StatusServer(0, host="127.0.0.1").start()
    srv.flight = fr
    base = "http://127.0.0.1:%d" % srv.port
    try:
        page = urlopen(base + "/requestz", timeout=5).read().decode()
        assert "q-5" in page and "flight recorder" in page
        j = json.loads(urlopen(base + "/requestz?json=1&n=2",
                               timeout=5).read())
        assert j["shown"] == 2 and j["total"] == 6
        assert [r["id"] for r in j["requests"]] == ["q-5", "q-4"]
        one = json.loads(urlopen(base + "/requestz?request=q-3",
                                 timeout=5).read())
        assert one["id"] == "q-3"
        from urllib.error import HTTPError
        try:
            urlopen(base + "/requestz?request=absent", timeout=5)
            raise AssertionError("unknown id should 404")
        except HTTPError as e:
            assert e.code == 404
        try:
            urlopen(base + "/requestz?n=wat", timeout=5)
            raise AssertionError("bad n should 400")
        except HTTPError as e:
            assert e.code == 400
    finally:
        srv.stop()


def test_stitched_chrome_trace_pure_function():
    """Socket-free stitch: lanes offset by their wall epochs, args
    carry the id, a hop without t_wall still renders."""
    router_rec = {
        "id": "p-1", "outcome": "served", "t_wall": 100.0,
        "total_s": 0.3, "retries": 1, "deadline_ms": None,
        "attempts": [
            {"replica": "a:1", "t_off_s": 0.0, "latency_s": 0.05,
             "outcome": "ERR busy queue", "retried": True,
             "candidates": [{"replica": "a:1", "load": 0}]},
            {"replica": "b:2", "t_off_s": 0.06, "latency_s": 0.22,
             "outcome": "served"}]}
    hop = {"id": "p-1", "outcome": "served", "t_wall": 100.07,
           "total_s": 0.2, "ttft_s": 0.05,
           "phases": {"queue_wait": 0.01, "dispatch": 0.001,
                      "prefill": 0.04, "decode": 0.149},
           "recompiles": []}
    trace = routerd.stitched_chrome_trace(router_rec, [("b:2", hop)])
    xs = [t for t in trace["traceEvents"] if t.get("ph") == "X"]
    assert {t["pid"] for t in xs} == {0, 1}
    # the hop's lane is offset by its wall delta (70ms after accept)
    qw = next(t for t in xs if t["name"] == "queue_wait")
    assert abs(qw["ts"] - 70e3) < 1.0, qw
    route_span = next(t for t in xs if t["name"] == "route:served")
    assert route_span["ts"] == 0.0 and route_span["dur"] == 0.3e6
    assert all(t["args"]["request"] == "p-1" for t in xs)
    # router-lane-only view works too (no hops fetched)
    solo = routerd.route_chrome_trace(router_rec)
    assert {t["pid"] for t in solo["traceEvents"]
            if t.get("ph") == "X"} == {0}
