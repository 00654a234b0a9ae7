"""Compile-cliff observability (ISSUE 16): the compile flight
recorder, request/batch stall attribution, the warm-grid readiness
account, the ``warming`` health state, and fleet federation of the
warm fraction.

Everything here is jax-free (the ``compile_ms`` knob on
``faultinject.slot_backend`` replays JitWatch's cache-growth sequence
deterministically) EXCEPT the one real-jit test at the bottom pinning
``ready_programs_pct`` 0 -> 100 across a real decode-session warm-up.

The headline guarantees:

* a request stalled behind a compile carries ``compile_stall_s > 0``
  on its flight record while a warm-bucket request carries EXACTLY 0
  (not "small") — the attribution is causal, not statistical;
* ``/compilez`` renders the bounded ring + readiness from a snapshot
  (pure renderer), answers ``?json=1`` with a stable schema, and 404s
  naming the wiring when no ledger is registered;
* warm-vs-expected is per-bucket exact math over ``str(key)``
  identity — the same identity ``Trainer.expected_decode_grid``
  enumerates;
* the router federates the warm fraction off ADMIN stats onto
  ``/fleetz`` and ``cxxnet_fleet_replica_warm_pct``, with ABSENCE
  (pre-warm-account replica) surfacing as "-"/no row, never 0.
"""

import json
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import pytest

from cxxnet_tpu.utils import perf, routerd, servd, statusd, telemetry

from . import faultinject


@pytest.fixture(autouse=True)
def _lockrank_on(monkeypatch):
    """Runtime lock-order enforcement for every ledger/frontend/router
    this suite constructs (the test_servd pattern): perf.compiles must
    never nest under perf.ledger, and recorder IO must stay outside
    both."""
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")


@pytest.fixture(autouse=True)
def _telemetry():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


@pytest.fixture()
def ledger():
    lg = perf.Ledger().enable()
    yield lg
    lg.disable()


def _drain_all(*objs):
    for o in objs:
        if o is None:
            continue
        if hasattr(o, "drain"):
            o.drain(timeout_ms=2000)
        elif hasattr(o, "stop"):
            o.stop()


GRID = [(("sess_prefill", 3, 0.0, 0), "prefill"),
        (("sess_admit", 2), "2"),
        (("sess_step", 2, 0.0, 0), "2")]


def _cold_frontend(ledger, compile_ms=40, **kw):
    """A batching frontend over a COLD fake backend: the first batch
    per program shape pays a deterministic simulated compile."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=2,
                                  compile_ms=compile_ms)
    ledger.set_expected_grid(GRID)
    kw.setdefault("batch_window_ms", 5.0)
    kw.setdefault("drain_ms", 4000.0)
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=2, **kw)
    fe.start()
    fe.set_warm_account(ledger.readiness, ready_pct=0.0)
    return fe, sb


# ----------------------------------------------------------------------
# warm-grid accounting math (pure ledger)
def test_warm_grid_readiness_math(ledger):
    lg = ledger
    lg.set_expected_grid(GRID)
    rd = lg.readiness()
    assert rd["expected"] == 3 and rd["warm"] == 0
    assert rd["ready_pct"] == 0.0
    assert rd["buckets"]["2"] == {"expected": 2, "warm": 0,
                                  "ready_pct": 0.0}
    # warm one program of the "2" bucket: per-bucket math is exact
    telemetry.record_compile("jit.decode_step", "new_signature", 0.5,
                             key=("sess_step", 2, 0.0, 0))
    lg.on_compile("jit.decode_step", "new_signature", 0.5, fn=None,
                  args=(), key=("sess_step", 2, 0.0, 0))
    rd = lg.readiness()
    assert rd["warm"] == 1 and rd["ready_pct"] == 33.33
    assert rd["buckets"]["2"]["ready_pct"] == 50.0
    assert str(("sess_admit", 2)) in rd["cold_keys"]
    # a key OUTSIDE the grid warms the ring but not the account
    lg.on_compile("jit.train_step", "new_signature", 0.1, fn=None,
                  args=(), key=("train", 8))
    assert lg.readiness()["warm"] == 1
    # reset clears ring+warm but KEEPS the expected grid (a reload
    # owes the whole grid again; the account must not forget its size)
    lg.reset()
    rd = lg.readiness()
    assert rd["expected"] == 3 and rd["warm"] == 0
    assert lg.recent_compiles(10) == []
    # snapshot carries the account; no grid means ready_pct is None
    assert lg.snapshot()["readiness"]["expected"] == 3
    lg.set_expected_grid([])
    assert lg.readiness()["ready_pct"] is None


# ----------------------------------------------------------------------
# stall attribution: flood during warm-up
def test_compile_stall_attribution_cold_vs_warm(ledger):
    """The acceptance shape: requests aboard the COLD first batch carry
    ``compile_stall_s > 0`` (prefill+admit under their own trace
    context, the step cliff fanned out batch-wide from the compile
    window); requests riding the warm bucket afterwards carry EXACTLY
    0.0."""
    fe, _sb = _cold_frontend(ledger)
    try:
        replies = []
        fe.submit("100 101 102", replies.append, wait=True)
        fe.submit("200 201 202", replies.append, wait=True)
        fe.submit("300 301 302", replies.append, wait=True)
        assert len(replies) == 3
        recs = [r for r in fe.flight.list() if r["outcome"] == "served"]
        assert len(recs) == 3
        cold, warm = recs[-1], recs[0]     # the ring is newest-first
        # three 40ms cliffs on the cold request (prefill, admit, step)
        assert cold["compile_stall_s"] == pytest.approx(0.12, abs=0.01)
        assert warm["compile_stall_s"] == 0.0
        # the serve_request_done events carry the same attribution
        evs = [e for e in telemetry.events()
               if e.get("ev") == "serve_request_done"]
        assert evs[0]["compile_stall_s"] > 0
        assert evs[-1]["compile_stall_s"] == 0.0
        # the account went 0 -> 100 across the warm-up
        assert ledger.readiness()["ready_pct"] == 100.0
        assert fe.warm_programs() == (3, 3, 100.0)
    finally:
        _drain_all(fe)


def test_step_cliff_fans_out_to_every_slot_aboard(ledger):
    """The batch-wide case: the step compile stalls EVERY request in
    the batch, not just the one whose admission triggered it — both
    concurrent requests carry the step window's stall."""
    import threading
    fe, _sb = _cold_frontend(ledger, batch_window_ms=50.0)
    try:
        port = fe.listen(0)
        out = []
        ts = [threading.Thread(
            target=lambda i=i: out.append(
                faultinject.serve_request(port, "%d00 1 2" % (i + 1),
                                          timeout=30.0)))
            for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(out) == 2
        recs = [r for r in fe.flight.list() if r["outcome"] == "served"]
        assert len(recs) == 2
        # both aboard the cold batch: both stalled by >= the step cliff
        for r in recs:
            assert r["compile_stall_s"] >= 0.04 - 0.005, recs
    finally:
        _drain_all(fe)


# ----------------------------------------------------------------------
# /compilez: render + ring schema
def test_compilez_endpoint_and_ring_schema(ledger):
    fe, _sb = _cold_frontend(ledger)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.perf = ledger
    try:
        replies = []
        fe.submit("100 101 102", replies.append, wait=True)
        body = json.loads(urlopen(
            "http://127.0.0.1:%d/compilez?json=1" % ss.port,
            timeout=5).read())
        assert body["shown"] == body["total"] == 3
        assert body["readiness"]["ready_pct"] == 100.0
        recs = body["compiles"]
        # newest-first, schema pinned
        assert recs[0]["seq"] > recs[-1]["seq"]
        for r in recs:
            for k in ("name", "key", "cause", "seconds", "ts", "seq",
                      "trigger_request", "trigger_context"):
                assert k in r, (k, r)
        names = {r["name"] for r in recs}
        assert names == {"jit.decode_prefill", "jit.decode_admit",
                         "jit.decode_step"}
        # the step cliff was triggered by the batch window, the
        # prefill/admit cliffs by the request's trace context
        by = {r["name"]: r for r in recs}
        assert by["jit.decode_step"]["trigger_context"] == "step:b2"
        assert by["jit.decode_prefill"]["trigger_request"] is not None
        # ?n= bounds the page; bad n is a 400, not a 500
        body = json.loads(urlopen(
            "http://127.0.0.1:%d/compilez?json=1&n=1" % ss.port,
            timeout=5).read())
        assert body["shown"] == 1 and body["total"] == 3
        with pytest.raises(HTTPError) as ei:
            urlopen("http://127.0.0.1:%d/compilez?n=nope" % ss.port,
                    timeout=5)
        assert ei.value.code == 400
        # HTML render: header, readiness, the trigger column
        page = urlopen("http://127.0.0.1:%d/compilez" % ss.port,
                       timeout=5).read().decode()
        assert "compile flight recorder" in page
        assert "100.0% ready" in page
        assert "step:b2" in page
    finally:
        _drain_all(fe, ss)


def test_compilez_404_names_the_wiring():
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    try:
        with pytest.raises(HTTPError) as ei:
            urlopen("http://127.0.0.1:%d/compilez" % ss.port, timeout=5)
        assert ei.value.code == 404
        assert "perf_ledger=0" in ei.value.read().decode()
    finally:
        ss.stop()


# ----------------------------------------------------------------------
# warming health state
def test_warming_health_state_gates_until_ready(ledger):
    """``serve_warm_ready_pct > 0`` turns a cold replica's health probe
    into 503 "warming" until the grid crosses the gate; the default 0
    keeps a cold replica routable (it pays its cliffs in-band)."""
    fe, _sb = _cold_frontend(ledger)
    try:
        fe.set_warm_account(ledger.readiness, ready_pct=80.0)
        ok, detail = fe.health_probe()
        assert not ok and detail.startswith("warming: 0/3")
        assert "gate 80" in detail
        replies = []
        fe.submit("100 101 102", replies.append, wait=True)
        ok, detail = fe.health_probe()
        assert ok, detail
        # gate disabled: a cold account never blocks the probe
        ledger.reset()
        fe.set_warm_account(ledger.readiness, ready_pct=0.0)
        ok, _ = fe.health_probe()
        assert ok
    finally:
        _drain_all(fe)


# ----------------------------------------------------------------------
# fleet federation of the warm fraction
def test_fleet_federates_warm_fraction(ledger):
    """ADMIN stats carry warm_programs/expected_programs (ints on the
    wire); the router parses them into the replica's warm fraction on
    /fleetz and cxxnet_fleet_replica_warm_pct — and a replica WITHOUT
    the account federates as "-"/no row, never a lying 0."""
    fe, _sb = _cold_frontend(ledger)
    port = fe.listen(0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    # the pre-warm-account replica: plain echo, no slot backend
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
        replies = []
        fe.submit("100 101 102", replies.append, wait=True)
        router.probe_now()
        snap = router.fleet_snapshot()
        reps = {r["name"]: r for r in snap["replicas"]}
        warm = reps["127.0.0.1:%d" % port]
        bare = reps["127.0.0.1:%d" % port2]
        assert warm["warm_programs"] == 3
        assert warm["expected_programs"] == 3
        assert warm["warm_pct"] == 100.0
        assert bare["warm_pct"] is None
        assert bare["warm_programs"] is None
        page = urlopen("http://127.0.0.1:%d/fleetz" % rsrv.port,
                       timeout=5).read().decode()
        assert "100% (3/3)" in page, page
        mets = urlopen("http://127.0.0.1:%d/metrics" % rsrv.port,
                       timeout=5).read().decode()
        row = [ln for ln in mets.splitlines()
               if ln.startswith("cxxnet_fleet_replica_warm_pct")]
        assert len(row) == 1 and 'replica="127.0.0.1:%d"' % port \
            in row[0] and row[0].endswith(" 100.0"), row
    finally:
        _drain_all(router, rsrv, fe, ss, fe2, ss2)


def test_router_marks_warming_replica_and_keeps_refreshing(ledger):
    """A replica 503ing "warming" lands in the WARMING state (not
    BREAKER_OPEN), stays OUT of the routing rotation, and its ADMIN
    stats keep refreshing so the warm fraction climbs on /fleetz while
    it warms."""
    fe, _sb = _cold_frontend(ledger)
    port = fe.listen(0)
    fe.set_warm_account(ledger.readiness, ready_pct=80.0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    router = routerd.Router([("127.0.0.1", port, ss.port)],
                            probe_ms=3600e3, federate_ms=3600e3)
    router.start()
    try:
        router.probe_now()
        snap = router.fleet_snapshot()
        rep = snap["replicas"][0]
        assert rep["state"] == routerd.WARMING, rep
        assert rep["warm_pct"] == 0.0
        assert snap["eligible"] == 0       # warming != routable
        # the replica warms up; the next probe flips it UP
        replies = []
        fe.submit("100 101 102", replies.append, wait=True)
        router.probe_now()
        rep = router.fleet_snapshot()["replicas"][0]
        assert rep["state"] == routerd.UP
        assert rep["warm_pct"] == 100.0
    finally:
        _drain_all(router, fe, ss)


# ----------------------------------------------------------------------
# the ONE real-jit test: ready_programs_pct 0 -> 100 across warm-up
TINY_LM = dict(vocab=64, seq=16, batch_size=2, dim=16, nhead=2,
               nlayer=1, dev="cpu")


def test_ready_programs_pct_real_session_warmup(ledger):
    """Real jax, CPU: a decode-session warm-up over the enumerated
    expected grid drives the readiness account 0 -> 100 with every
    compile's flight record in the ring — the keys the account matches
    are the REAL jit-cache keys, not a parallel bookkeeping scheme."""
    from cxxnet_tpu.models import transformer_lm_trainer
    tr = transformer_lm_trainer(**TINY_LM)
    plen, bucket, n_new = 4, 1, 2
    ledger.set_expected_grid(tr.expected_decode_grid([bucket], [plen]))
    rd = ledger.readiness()
    assert rd["expected"] == 3 and rd["ready_pct"] == 0.0
    sess = tr.decode_session(bucket, n_new)
    try:
        sess.prefill(0, [1, 2, 3, 4], 7)
        while not all(done for _, _, done in sess.step()):
            pass
        sess.retire(0)
    finally:
        sess.close()
    rd = ledger.readiness()
    assert rd["ready_pct"] == 100.0, rd
    assert rd["cold_keys"] == []
    names = {r["name"] for r in ledger.recent_compiles(10)}
    assert {"jit.decode_prefill", "jit.decode_admit",
            "jit.decode_step"} <= names, names
