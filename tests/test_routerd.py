"""Replicated-fleet chaos suite (utils/routerd.py): health-aware
routing over REAL servd replica subprocesses, retry-on-shed, provably
exactly-once forwarding, replica SIGKILL / SIGSTOP-partition / wedge
mid-flood, backoff re-admission, rolling zero-downtime reload, and the
task = route driver's SIGTERM fleet drain.

Everything here is jax-free and real-socket (the replicas are
``servd --stub`` subprocesses from faultinject's fleet helpers; the
stub's backend answers ``tok + model_version`` so tests can SEE which
model served). The fleet invariants under fault injection:

* every request the ROUTER accepts gets exactly one response line;
* a lost-contact attempt (the replica MAY have dispatched it) is
  REPLAYED on a different replica — generation is deterministic, so
  the replay is token-identical — and the original socket is reaped so
  a late answer is discarded+counted, never delivered twice
  (exactly-once to the CLIENT survives the failover);
* router counters reconcile: accepted == served + errors + shed +
  deadline — and so does the fleet-wide ``ADMIN stats`` aggregate over
  the surviving replicas;
* a rolling ``ADMIN reload`` under sustained load is client-invisible
  and holds at most ONE replica out of rotation at a time.
"""

import json
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from cxxnet_tpu.utils import routerd, servd, statusd, telemetry

from . import faultinject

REPO = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _lockrank_on(monkeypatch):
    """Runtime lock-order enforcement for every router/frontend this
    suite constructs (the stub subprocesses inherit the env too): an
    inversion the static analyzer cannot see fails the chaos test as a
    named LockOrderError instead of deadlocking (doc/static_analysis.md
    — the test_servd/test_statusd autouse pattern)."""
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")


def reconciles(stats):
    return stats["accepted"] == (stats["served"] + stats["errors"]
                                 + stats["shed"] + stats["deadline"])


def replica_stats(r):
    """One replica's ADMIN stats as a dict (direct, not via router)."""
    resp = faultinject.serve_request(r.port, "ADMIN stats")
    assert resp and resp.startswith("OK "), resp
    return {k: int(v) for k, _, v in
            (kv.partition("=") for kv in resp[3:].split())}


@pytest.fixture()
def make_router():
    """Factory for started+listening routers over FleetReplica lists
    (or raw specs); everything made here drains at teardown."""
    made = []

    def make(replicas, **kw):
        specs = [r.spec if isinstance(r, faultinject.FleetReplica)
                 else r for r in replicas]
        kw.setdefault("drain_ms", 2000.0)
        kw.setdefault("probe_timeout", 0.5)
        router = routerd.Router(specs, **kw)
        router.start()
        router.listen(0)
        made.append(router)
        return router

    yield make
    for router in made:
        router.drain(timeout_ms=2000)


def wedge_and_park(r, timeout=8.0):
    """Wedge a replica AND confirm a request is parked inside its
    blocked backend. SIGUSR1 delivery is asynchronous: on a fast
    machine a request sent right after ``wedge_replica`` can reach the
    backend BEFORE the handler flips the wedge flag and be served
    instantly — so keep sending fire-and-forget requests until one
    visibly sticks (``in_flight`` holds at 1). Returns the open
    sockets (close them at teardown)."""
    faultinject.wedge_replica(r)
    socks = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = socket.create_connection(("127.0.0.1", r.port), timeout=5)
        s.sendall(b"9\n")
        socks.append(s)
        t0 = time.monotonic()
        while time.monotonic() < t0 + 0.4:
            if replica_stats(r)["in_flight"] >= 1:
                # confirm it HOLDS (a mid-serve flicker is not a park)
                time.sleep(0.1)
                if replica_stats(r)["in_flight"] >= 1:
                    return socks
                break
            time.sleep(0.02)
    raise AssertionError("could not park a request in the wedged "
                         "replica (wedge never took effect?)")


def wait_until(cond, timeout=8.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError("timed out waiting for " + msg)


def spawn_two(kw_a, kw_b=None):
    """Two replicas with DIFFERENT configs, spawned concurrently (the
    homogeneous case is faultinject.spawn_fleet)."""
    procs = [faultinject._start_stub(**kw_a),
             faultinject._start_stub(**(kw_b or {}))]
    out = []
    for proc, args in procs:
        port, sp = faultinject._await_ports(proc)
        r = faultinject.FleetReplica(proc, port, sp, args)
        r.args[r.args.index("--port") + 1] = str(r.port)
        r.args[r.args.index("--status-port") + 1] = str(r.status_port)
        out.append(r)
    return out


# ----------------------------------------------------------------------
# the wire-format retryability contract (what keeps exactly-once safe)
def test_retryability_contract():
    assert routerd.retryable("ERR busy queue full (64)")
    assert routerd.retryable("ERR busy breaker open (circuit)")
    assert routerd.retryable("ERR draining server is shutting down")
    assert routerd.retryable("ERR draining shutdown budget exhausted")
    # the drain-gave-up-on-in-flight case MAY have dispatched
    assert not routerd.retryable(
        "ERR draining backend exceeded the drain budget")
    assert not routerd.retryable("ERR backend RuntimeError('boom')")
    assert not routerd.retryable("ERR parse non-integer token")
    assert not routerd.retryable("ERR deadline expired 5ms ago")
    assert not routerd.retryable("ERR empty request line has no tokens")
    assert not routerd.retryable("2 3 4")


def test_free_slots_load_signal_prefers_batching_replica():
    """The continuous-batching capacity signal: a replica reporting
    free decode slots (``free_slots`` in its ADMIN stats — bucket
    capacity minus active) reads as LESS loaded than an equally busy
    solo replica, so power-of-two routing prefers the one that can
    batch the request into a running decode pass. Old replicas omit
    the field — parsed as 0, ordering unchanged."""
    router = routerd.Router([("127.0.0.1", 1, 2), ("127.0.0.1", 3, 4)],
                            probe_ms=10_000.0)
    a, b = router._replicas
    a.queue_depth, a.in_flight, a.free_slots = 1, 1, 0
    b.queue_depth, b.in_flight, b.free_slots = 1, 1, 3
    assert router._load(b) < router._load(a)
    picked, cands = router._pick(set())
    assert picked is b
    assert all("free_slots" in c for c in cands)
    router._checkin(b)
    # snapshot carries the signal (the /fleetz surface)
    assert b.snapshot(0.0)["free_slots"] == 3
    # absent field == 0 (pre-batching replica): tie broken by index,
    # exactly the pre-batching behavior
    b.free_slots = 0
    picked, _ = router._pick(set())
    assert picked is a
    router._checkin(a)


def test_parse_replicas():
    specs = routerd.parse_replicas(
        "7001:7101, 10.0.0.2:7002:7102\nlocalhost:7003:7103")
    assert specs == [("127.0.0.1", 7001, 7101),
                     ("10.0.0.2", 7002, 7102),
                     ("localhost", 7003, 7103)]
    with pytest.raises(ValueError):
        routerd.parse_replicas("7001")


# ----------------------------------------------------------------------
# routing basics over real replicas: sequential + concurrent traffic,
# least-loaded spread, fleet ADMIN stats aggregation
def test_routes_spreads_and_fleet_stats_reconcile(make_router):
    fleet = faultinject.spawn_fleet(2, delay_ms=40)
    try:
        router = make_router(fleet, probe_ms=50.0)
        for i in range(4):
            assert faultinject.serve_request(
                router.port, "%d" % i) == "%d" % (i + 1)
        responses = faultinject.serve_flood(router.port, ["5"] * 8)
        assert all(r == "6" for r in responses), responses
        st = router.stats()
        assert st["served"] == 12 and reconciles(st)
        # least-loaded dispatch: with 8 concurrent 40ms requests both
        # replicas must have taken real work
        counts = [replica_stats(r)["accepted"] for r in fleet]
        assert all(c >= 1 for c in counts), counts
        assert sum(counts) == 12
        # fleet ADMIN stats aggregates the per-replica counters and the
        # sums reconcile (each replica reconciles, so the fleet does)
        resp = faultinject.serve_request(router.port, "ADMIN stats")
        agg = {k: int(v) for k, _, v in
               (kv.partition("=") for kv in resp[3:].split())}
        assert agg["reachable"] == 2 and agg["replicas"] == 2
        assert agg["accepted"] == 12 and reconciles(agg)
    finally:
        faultinject.stop_fleet(fleet)


# ----------------------------------------------------------------------
# retry-on-shed: ERR busy queue is retried elsewhere, ERR busy breaker
# additionally ejects, ERR backend is never retried
def test_queue_shed_retried_on_other_replica(make_router):
    a, b = spawn_two({"queue": 1})
    socks = []
    try:
        # wedge A (confirmed stuck — see wedge_and_park), then fill its
        # 1-slot queue so any pick of A sheds `ERR busy queue`
        socks += wedge_and_park(a)
        s = socket.create_connection(("127.0.0.1", a.port), timeout=5)
        s.sendall(b"9\n")
        socks.append(s)
        wait_until(lambda: replica_stats(a)["queue_depth"] == 1
                   and replica_stats(a)["in_flight"] == 1,
                   msg="replica A full")
        # probing off the clock: picks are deterministic (zero load,
        # index tie-break -> A first), so the shed+retry is guaranteed
        router = make_router([a, b], probe_ms=3600e3, retries=2)
        assert faultinject.serve_request(router.port, "5") == "6"
        st = router.stats()
        assert st["served"] == 1 and st["retries"] == 1, st
        assert replica_stats(b)["served"] == 1
        # the shed is in A's books, the request is not
        assert replica_stats(a)["shed"] == 1
    finally:
        for s in socks:
            s.close()
        faultinject.unwedge_replica(a)
        faultinject.stop_fleet([a, b])


def test_breaker_shed_ejects_replica(make_router):
    a, b = spawn_two({"explode_every": 1, "breaker_fails": 1})
    try:
        router = make_router([a, b], probe_ms=3600e3, retries=2)
        # dispatched failure: relayed verbatim, NEVER retried
        assert faultinject.serve_request(
            router.port, "1").startswith("ERR backend")
        st = router.stats()
        assert st["errors"] == 1 and st["retries"] == 0, st
        # next pick of A sheds `ERR busy breaker`: retried on B AND A
        # leaves rotation
        assert faultinject.serve_request(router.port, "2") == "3"
        snap = router.fleet_snapshot()
        assert snap["replicas"][0]["state"] == routerd.BREAKER_OPEN
        assert router.stats()["retries"] == 1
        # ejected: the next request goes straight to B, no retry spent
        assert faultinject.serve_request(router.port, "4") == "5"
        assert router.stats()["retries"] == 1
        assert replica_stats(b)["served"] == 2
    finally:
        faultinject.stop_fleet([a, b])


# ----------------------------------------------------------------------
# deterministic replay failover: a replica that dies AFTER accepting
# gets its request REPLAYED on the survivor — the client sees the
# token-exact answer, charged once; route_replay = 0 restores the old
# never-replay verdict
def test_replay_when_replica_dies_after_accepting(make_router):
    a, b = spawn_two({"delay_ms": 500})
    try:
        router = make_router([a, b], probe_ms=3600e3, retries=2,
                             stall_s=5.0)
        out = {}

        def client():
            out["resp"] = faultinject.serve_request(router.port, "7",
                                                    timeout=15)

        t = threading.Thread(target=client)
        t.start()
        # zero load, index tie-break: the request is on A (500ms
        # backend); kill A while it is in flight
        wait_until(lambda: replica_stats(a)["in_flight"] == 1,
                   msg="request in flight on A")
        faultinject.kill_replica(a)
        t.join(timeout=15)
        assert not t.is_alive()
        # the lost attempt was replayed on B: token-exact answer
        # (generation is deterministic — same prompt, same model
        # version, same tokens), client charged exactly once
        assert out["resp"] == "8", out
        st = router.stats()
        assert st["served"] == 1 and st["errors"] == 0, st
        assert st["replays"] == 1 and st["lost_contact"] == 1, st
        assert st["retries"] == 0, st    # replays ride OUTSIDE the
        #                                  retry budget and its counter
        assert reconciles(st)
        assert replica_stats(b)["accepted"] == 1
        # the lost attempt is on A's /fleetz failover account
        snap = router.fleet_snapshot()["replicas"]
        assert snap[0]["lost"] == 1 and snap[1]["lost"] == 0, snap
    finally:
        faultinject.stop_fleet([a, b])


# ----------------------------------------------------------------------
# route_replay = 0: the old exactly-once-beats-availability verdict —
# a lost-contact attempt is answered as an honest ERR, never replayed
def test_replay_off_restores_never_replay(make_router):
    a, b = spawn_two({"delay_ms": 500})
    try:
        router = make_router([a, b], probe_ms=3600e3, retries=2,
                             stall_s=5.0, replay=False)
        out = {}

        def client():
            out["resp"] = faultinject.serve_request(router.port, "7",
                                                    timeout=15)

        t = threading.Thread(target=client)
        t.start()
        wait_until(lambda: replica_stats(a)["in_flight"] == 1,
                   msg="request in flight on A")
        faultinject.kill_replica(a)
        t.join(timeout=15)
        assert not t.is_alive()
        assert out["resp"].startswith("ERR backend"), out
        assert "not retried" in out["resp"]
        st = router.stats()
        assert st["errors"] == 1 and st["replays"] == 0, st
        assert replica_stats(b)["accepted"] == 0
    finally:
        faultinject.stop_fleet([a, b])


# ----------------------------------------------------------------------
# deadline budget: the router forwards the REMAINING budget and answers
# expired budgets itself
def test_deadline_budget_forwarded_and_enforced(make_router):
    mirror = routerd._MirrorReplica().start()
    try:
        router = make_router([("127.0.0.1", mirror.port, mirror.port)],
                             probe_ms=3600e3, retries=0)
        resp = faultinject.serve_request(router.port,
                                         "DEADLINE 400 1 2 3")
        toks = resp.split()
        # the forward carries the minted TRACE id and the REMAINING
        # budget (the mirror echoes the line it was sent)
        assert toks[0] == "TRACE" and servd.valid_trace_id(toks[1])
        assert toks[2] == "DEADLINE" and toks[4:] == ["1", "2", "3"]
        assert 0 < int(toks[3]) <= 400, resp
        assert faultinject.serve_request(
            router.port, "DEADLINE 0 9").startswith("ERR deadline")
        st = router.stats()
        assert st["deadline"] == 1 and reconciles(st)
    finally:
        mirror.stop()


# ----------------------------------------------------------------------
# THE HEADLINE CHAOS GUARANTEE: SIGKILL one replica and partition
# another mid-flood — every request the fleet accepted is answered,
# counters reconcile fleet-wide, and both replicas are ejected then
# re-admitted after recovery via backoff re-probe
def test_kill_and_partition_mid_flood_zero_loss(make_router):
    fleet = faultinject.spawn_fleet(3, delay_ms=40)
    try:
        router = make_router(fleet, probe_ms=100.0, retries=2,
                             stall_s=1.5, probe_backoff_cap_s=0.5)
        n = 24
        responses = [None] * n
        started = threading.Event()

        def client(i):
            started.set()
            try:
                responses[i] = faultinject.serve_request(
                    router.port, "5", timeout=25)
            except OSError:
                responses[i] = None

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(n)]
        for t in ts:
            t.start()
        started.wait(5.0)
        time.sleep(0.15)          # flood in progress
        faultinject.kill_replica(fleet[0])
        faultinject.partition_replica(fleet[1])
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts)
        # ZERO client-visible losses: every accepted request was
        # answered token-exact — the killed replica's in-flight
        # requests replay off its EOF, the partitioned replica's off
        # the stall timeout (their late answers die in the reaper)
        assert all(r == "6" for r in responses), responses
        st = router.stats()
        assert st["accepted"] == n and reconciles(st), st
        assert st["replays"] > 0, st
        # both failed replicas are ejected
        wait_until(lambda: router.fleet_snapshot()["replicas"][0]
                   ["state"] == routerd.DEAD, msg="killed ejected")
        wait_until(lambda: router.fleet_snapshot()["replicas"][1]
                   ["state"] == routerd.DEAD,
                   msg="partitioned ejected")
        # fleet-wide reconciliation over the survivors (the healed
        # partition finishes its frozen requests into dead sockets —
        # still counted, still reconciled)
        faultinject.heal_replica(fleet[1])
        wait_until(lambda: reconciles(replica_stats(fleet[1])),
                   msg="healed replica settles")
        assert reconciles(replica_stats(fleet[2]))
        # recovery: the healed partition AND an operator-restarted
        # replacement for the killed replica are re-admitted by the
        # backoff re-probe (no router restart, no operator action on
        # the router)
        faultinject.restart_replica(fleet[0])
        wait_until(lambda: all(
            r["state"] == routerd.UP
            for r in router.fleet_snapshot()["replicas"]),
            timeout=10.0, msg="fleet re-admitted")
        for i in range(3):
            assert faultinject.serve_request(router.port, "5") == "6"
        assert reconciles(router.stats())
    finally:
        faultinject.stop_fleet(fleet)


# ----------------------------------------------------------------------
# rolling zero-downtime reload: under sustained load, zero
# client-visible errors, every replica reloads, capacity >= N-1
def test_rolling_reload_zero_downtime(make_router):
    fleet = faultinject.spawn_fleet(3, delay_ms=5, reload_ms=100)
    try:
        router = make_router(fleet, probe_ms=100.0, retries=2,
                             reload_timeout_s=15.0)
        stop = threading.Event()
        responses = []
        lock = threading.Lock()

        def load():
            while not stop.is_set():
                r = faultinject.serve_request(router.port, "5",
                                              timeout=15)
                with lock:
                    responses.append(r)

        ts = [threading.Thread(target=load) for _ in range(3)]
        for t in ts:
            t.start()
        time.sleep(0.2)           # sustained load established
        resp = faultinject.serve_request(router.port, "ADMIN reload")
        assert resp.startswith("OK fleet"), resp
        wait_until(lambda: len(router.fleet_snapshot()["windows"]) >= 3
                   and not router.fleet_snapshot()["reloading"],
                   timeout=20.0, msg="rolling reload completes")
        stop.set()
        for t in ts:
            t.join(timeout=15)
        # zero client-visible errors: every response during the roll is
        # an answer from model v1 (6) or v2 (7) — never an ERR, never
        # a dropped line
        assert responses and all(r in ("6", "7") for r in responses), \
            [r for r in responses if r not in ("6", "7")][:5]
        assert "7" in responses, "no request saw the reloaded model"
        # every replica reloaded exactly once. The roll completes on
        # the reload_seen delta — bumped when the reload request is
        # PROCESSED, before the swap itself, deliberately (a no-op
        # roll must not burn the per-replica timeout) — so the last
        # replica's actual swap can lag the roll by up to reload_ms:
        # wait for it instead of racing it (reproduced failing ~1/3 on
        # clean main on this machine before this wait)
        wait_until(lambda: all(replica_stats(r)["reloads"] == 1
                               for r in fleet), timeout=10.0,
                   msg="every replica finished its swap")
        # capacity never below N-1: the drain windows are per-replica
        # and pairwise NON-overlapping (one replica held at a time)
        wins = sorted(router.fleet_snapshot()["windows"],
                      key=lambda w: w["out_s"])
        assert len(wins) == 3
        assert len({w["replica"] for w in wins}) == 3
        for w1, w2 in zip(wins, wins[1:]):
            assert w1["back_s"] <= w2["out_s"], (w1, w2)
        # and the fleet answers the new model afterwards
        assert faultinject.serve_request(router.port, "5") == "7"
    finally:
        faultinject.stop_fleet(fleet)


# ----------------------------------------------------------------------
# wedged replica (accepts, then stalls past serve_stall_s): the probe
# sees its readiness fail and routes around it; unwedge re-admits
def test_wedged_replica_routed_around(make_router):
    a, b = spawn_two({"stall_s": 0.2})
    socks = []
    try:
        router = make_router([a, b], probe_ms=100.0, retries=2,
                             stall_s=2.0)
        socks += wedge_and_park(a)   # a request stuck in A's worker
        # past stall_s the replica's own /healthz fails; the router's
        # probe takes it out of rotation (grouped with breaker_open)
        wait_until(lambda: router.fleet_snapshot()["replicas"][0]
                   ["state"] != routerd.UP, msg="wedged ejected")
        for _ in range(3):
            assert faultinject.serve_request(router.port, "5") == "6"
        assert replica_stats(b)["served"] >= 3
        faultinject.unwedge_replica(a)
        wait_until(lambda: router.fleet_snapshot()["replicas"][0]
                   ["state"] == routerd.UP, msg="unwedged re-admitted")
    finally:
        for s in socks:
            s.close()
        faultinject.stop_fleet([a, b])


# ----------------------------------------------------------------------
# statusd fleet surfaces over a REAL router (in-process replicas keep
# this cheap; the snapshot-shape fake lives in the statusd selftest)
def test_fleetz_and_metrics_surfaces():
    telemetry.enable()
    fe = srv = router = None
    try:
        fe = servd.ServeFrontend(lambda toks, seq: [t + 1 for t in toks],
                                 drain_ms=2000.0)
        fe.start()
        fe.listen(0)
        rs = statusd.StatusServer(0, host="127.0.0.1").start()
        rs.register_probe("serving", fe.health_probe)
        router = routerd.Router([("127.0.0.1", fe.port, rs.port)],
                                probe_ms=3600e3, drain_ms=1000.0)
        router.start()
        router.listen(0)
        router.probe_now()
        srv = statusd.StatusServer(0, host="127.0.0.1").start()
        srv.fleet = router
        srv.register_probe("routing", router.health_probe)
        assert faultinject.serve_request(router.port, "1") == "2"
        from urllib.request import urlopen
        base = "http://127.0.0.1:%d" % srv.port
        fj = json.loads(urlopen(base + "/fleetz?json=1",
                                timeout=5).read())
        assert fj["eligible"] == 1
        assert fj["replicas"][0]["state"] == routerd.UP
        assert fj["stats"]["served"] == 1
        page = urlopen(base + "/fleetz", timeout=5).read().decode()
        assert "serving fleet" in page and fe.port is not None
        metrics = urlopen(base + "/metrics", timeout=5).read().decode()
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert "cxxnet_fleet_replicas" in metrics
        assert "cxxnet_fleet_replica_up" in metrics
        assert 'state="up"' in metrics
        assert urlopen(base + "/healthz", timeout=5).status == 200
        rs.stop()
    finally:
        if router is not None:
            router.drain(timeout_ms=1000)
        if srv is not None:
            srv.stop()
        if fe is not None:
            fe.drain(timeout_ms=1000)
        telemetry.disable()


# ----------------------------------------------------------------------
# the task = route driver: SIGTERM fleet drain through the real CLI
def test_cli_route_task_sigterm_drain():
    fleet = faultinject.spawn_fleet(2)
    p = None
    try:
        import os
        import tempfile
        conf = tempfile.NamedTemporaryFile(
            "w", suffix=".conf", delete=False)
        conf.write("task = route\n"
                   "route_replicas = %s\n"
                   "route_port = 0\n"
                   "route_probe_ms = 100\n"
                   % ",".join("127.0.0.1:%d:%d" % (r.port,
                                                   r.status_port)
                              for r in fleet))
        conf.close()
        env = dict(os.environ, JAX_PLATFORMS="cpu", CXXNET_LOCKRANK="1")
        p = subprocess.Popen(
            [sys.executable, "bin/cxxnet", conf.name],
            stderr=subprocess.PIPE, stdout=subprocess.DEVNULL,
            text=True, cwd=REPO, env=env)
        port = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            line = p.stderr.readline()
            assert line, "driver died before routing (rc=%r)" % p.poll()
            if line.startswith("routerd: routing on port "):
                port = int(line.split()[4])
                break
        assert port is not None
        for i in range(4):
            assert faultinject.serve_request(
                port, "%d" % i, timeout=15) == "%d" % (i + 1)
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=30)
        tail = p.stderr.read()
        assert rc == 0, tail
        assert "routed 4 requests (4 served" in tail, tail
        # the replicas served on: 2 each or 3/1 — the fleet took all 4
        counts = [replica_stats(r)["served"] for r in fleet]
        assert sum(counts) == 4, counts
        os.unlink(conf.name)
    finally:
        if p is not None and p.poll() is None:
            p.kill()
            p.wait()
        faultinject.stop_fleet(fleet)


# ----------------------------------------------------------------------
# ISSUE 13: multi-tenant weighted-fair QoS + closed-loop autoscaler
# (in-process frontends — the subprocess chaos above covers process
# faults; this layer's faults are POLICY faults, cheap to drive
# deterministically with probing/federation/scaling off the clock)
TEN = "noisy:1,victim:4"


def _inproc_replica(backend, slo=False, tenants=TEN, **kw):
    """One in-process replica: tenant-armed frontend + statusd with the
    per-tenant SLO windows wired (the federation feed)."""
    slo_t = {}
    if slo:
        slo_t = {t: statusd.SLOTracker(availability=0.99,
                                       min_requests=4, min_bad=3,
                                       window_s=60.0)
                 for t in ("noisy", "victim")}
    fe = servd.ServeFrontend(
        backend, drain_ms=2000.0, tenants=tenants,
        tenant_default="victim", slo_tenants=slo_t,
        slo=statusd.SLOTracker(availability=0.99, min_requests=8,
                               min_bad=3, window_s=60.0)
        if slo else None, **kw)
    fe.start()
    fe.listen(0)
    ss = statusd.StatusServer(0, host="127.0.0.1").start()
    ss.register_probe("serving", fe.health_probe)
    ss.slo = fe.slo
    ss.slo_tenants = slo_t
    ss.flight = fe.flight
    return fe, ss


def tenant_reconciles(stats_by_tenant):
    for t, st in stats_by_tenant.items():
        assert st["accepted"] == (st["served"] + st["errors"]
                                  + st["shed"] + st["deadline"]), \
            (t, st)


def test_retryability_tenant_verdict_not_retried():
    """The wire-contract pin: ``ERR busy tenant`` proves the request
    never dispatched BUT is the fleet-wide policy verdict — relayed,
    never retried (a flood must not double itself through the retry
    path); the capacity sheds keep retrying as before."""
    assert not routerd.retryable("ERR busy tenant noisy over fair "
                                 "share (...)")
    assert routerd.retryable("ERR busy queue full (64)")
    assert routerd.retryable("ERR busy breaker open (circuit)")


def test_router_tenant_gate_sheds_over_share_on_saturated_fleet(
        make_router):
    """The router's own weighted-fair admission: with every eligible
    replica saturated, a tenant holding >= its weighted share of the
    router's in-flight requests is shed at the door — the victim's
    share is always >= 1, so it is NEVER gated."""
    fe, ss = _inproc_replica(lambda toks, seq: list(toks))
    try:
        router = make_router([("127.0.0.1", fe.port, ss.port)],
                             probe_ms=3600e3, federate_ms=3600e3,
                             tenants=TEN, tenant_default="victim")
        r = router._replicas[0]
        # fake a saturated probe state + a noisy-heavy in-flight set
        with router._lock:
            r.queue_depth, r.free_slots = 3, 0
        with router._slock:
            router._tenant_active["noisy"] = 5
            router._tenant_active["victim"] = 1
        shed = router._tenant_gate("noisy")
        assert shed is not None and shed.split()[:3] \
            == ["ERR", "busy", "tenant"], shed
        assert router._tenant_gate("victim") is None
        # an unsaturated fleet admits everyone
        with router._lock:
            r.queue_depth = 0
            r.free_slots = 2
        assert router._tenant_gate("noisy") is None
    finally:
        fe.drain(timeout_ms=1000)
        ss.stop()


def test_tenant_budget_burns_on_fleet_wide_outage(make_router):
    """A request shed because EVERY attempt was connect-refused never
    reached any replica window — the router's own per-tenant tracker
    must burn for it, or a fleet-wide outage under a tenant flood
    reads cxxnet_fleet_tenant_slo_burn 0 for everyone (the
    burn-reads-0-under-total-overload trap, outage edition)."""
    with socket.socket() as tmp:
        tmp.bind(("127.0.0.1", 0))
        dead = tmp.getsockname()[1]
    slo_t = {t: statusd.SLOTracker(availability=0.99, min_requests=4,
                                   min_bad=3, window_s=60.0)
             for t in ("noisy", "victim")}
    router = make_router([("127.0.0.1", dead, dead)],
                         probe_ms=3600e3, federate_ms=3600e3,
                         retries=1, tenants=TEN,
                         tenant_default="victim", slo_tenants=slo_t)
    for _ in range(4):
        resp = faultinject.serve_request(router.port, "TENANT noisy 5")
        assert resp.startswith("ERR busy fleet"), resp
    assert slo_t["noisy"].snapshot()["alert"] == 1, \
        slo_t["noisy"].snapshot()
    assert slo_t["victim"].snapshot()["alert"] == 0
    st = router.tenant_stats()
    assert st["noisy"]["accepted"] == 4 and st["noisy"]["shed"] == 4
    # ... and the merged fleet account carries it even with zero
    # federated replicas (the router's windows join the merge)
    fed_slo = {}
    router.federate_now()
    snap = router.federation_snapshot()
    if snap is not None:
        fed_slo = snap.get("slo_tenants") or {}
    # no replicas federated (all dead): federation_snapshot may be
    # None — the tracker itself is the pinned behavior above
    if fed_slo:
        assert fed_slo["noisy"]["alert"] == 1


def test_autoscaler_standby_admit_and_retire(make_router):
    """The closed loop in isolation: queued work with zero free slots
    admits the standby (fleet_scale event, /fleetz + series account);
    a quiet fleet retires it after the idle window — with hysteresis
    (cooldown) and the scale_min floor respected."""
    release = threading.Event()

    def slow(toks, seq):
        release.wait(10.0)
        return [t + 1 for t in toks]

    # actives block until released; the standby is fresh idle capacity
    # (a fast backend) — no tenant table: the autoscaler policy is
    # orthogonal to the QoS layer and must work without it
    reps = [_inproc_replica(slow, queue_size=2, tenants=None)
            for _ in range(2)]
    sb = _inproc_replica(lambda toks, seq: [t + 1 for t in toks],
                         queue_size=2, tenants=None)
    telemetry.enable()
    try:
        router = make_router(
            [("127.0.0.1", fe.port, ss.port) for fe, ss in reps],
            probe_ms=3600e3, federate_ms=3600e3,
            standby_replicas=[("127.0.0.1", sb[0].port, sb[1].port)],
            scale_down_idle_s=0.15, scale_cooldown_s=0.0)
        standby = router._replicas[2]
        assert standby.standby and standby.from_standby
        router.probe_now()
        # idle fleet: no action, the standby stays out of /pick
        assert router.autoscale_now() is None
        assert router.health_probe()[1].startswith("routing to 2 of 3")
        # saturate: park one request in each active worker and FILL
        # its 2-slot queue (an arrival must shed, not queue behind the
        # parked work)
        socks = []
        for fe, _ in reps:
            s = socket.create_connection(("127.0.0.1", fe.port),
                                         timeout=5)
            s.sendall(b"9\n")
            socks.append(s)
            wait_until(lambda fe=fe: fe._inflight == 1,
                       msg="worker occupied")
            for k in range(2):
                s = socket.create_connection(("127.0.0.1", fe.port),
                                             timeout=5)
                s.sendall(b"9\n")
                socks.append(s)
                wait_until(lambda fe=fe, k=k: len(fe._q) == k + 1,
                           msg="queued")
        router.probe_now()
        assert router.autoscale_now() == "up"
        assert standby.standby is False
        snap = router.scale_snapshot()
        assert snap["target_replicas"] == 3 and snap["events"] == 1
        assert snap["recent"][-1]["action"] == "up"
        evs = [e for e in telemetry.recent_events()
               if e.get("ev") == "fleet_scale"]
        assert evs and evs[-1]["action"] == "up"
        # traffic now routes to the admitted standby (the actives are
        # wedged full — the pick must find the fresh replica)
        assert faultinject.serve_request(router.port, "5") == "6"
        # quiet down: drain the parked work, then idle past the window
        release.set()
        for s in socks:
            s.close()
        wait_until(lambda: all(fe.stats()["served"] >= 3
                               for fe, _ in reps), msg="drained")
        router.probe_now()
        assert router.autoscale_now() is None      # idle timer starts
        time.sleep(0.2)
        router.probe_now()
        assert router.autoscale_now() == "down"
        assert standby.standby is True
        snap = router.scale_snapshot()
        assert snap["target_replicas"] == 2 and snap["events"] == 2
        evs = [e for e in telemetry.recent_events()
               if e.get("ev") == "fleet_scale"]
        assert evs[-1]["action"] == "down" \
            and evs[-1]["replica"] == standby.name
        # scale_min floor: with the fleet back at 2 primaries, a quiet
        # fleet never retires below the floor
        time.sleep(0.2)
        router.probe_now()
        assert router.autoscale_now() is None
    finally:
        release.set()
        telemetry.disable()
        for fe, ss in reps + [sb]:
            fe.drain(timeout_ms=1000)
            ss.stop()


def test_tenant_flood_chaos_headline(make_router):
    """THE ISSUE-13 acceptance, end to end in-process: one tenant
    floods a 2-replica fleet -> only THAT tenant sheds (the victim's
    requests all serve, its p99 and per-tenant SLO burn hold), the
    autoscaler admits the standby mid-flood, the fleet scales back
    down after the flood — zero silent losses, and the books reconcile
    per tenant on the router AND fleet-wide.

    The fleet is saturated by construction, not by how fast this host
    runs the clients: the replicas hold their work behind a gate until
    all six noisy clients have a request in them, ONE probe reads both
    backlogs, and nothing probes again until the flood is over. (A
    timed flood against 3 ms of work raced the host: under six xdist
    workers a client's round trip took 50-90 ms, the replicas never
    queued, and nothing was shed.)"""
    gate = threading.Event()

    def work(toks, seq):
        gate.wait(30.0)
        time.sleep(0.003)
        return [t + 1 for t in toks]

    reps = [_inproc_replica(work, queue_size=4, slo=True)
            for _ in range(2)]
    sb = _inproc_replica(work, queue_size=4, slo=True)
    telemetry.enable()
    stop = threading.Event()
    flood_over = threading.Event()
    try:
        router = make_router(
            [("127.0.0.1", fe.port, ss.port) for fe, ss in reps],
            probe_ms=3600e3, federate_ms=3600e3, retries=2,
            standby_replicas=[("127.0.0.1", sb[0].port, sb[1].port)],
            scale_up_burn=1.0, scale_down_idle_s=0.2,
            scale_cooldown_s=0.3, tenants=TEN,
            tenant_default="victim",
            # the router's own windows: a flood shed at the DOOR must
            # still burn its tenant's fleet-wide budget
            slo_tenants={t: statusd.SLOTracker(availability=0.99,
                                               min_requests=4,
                                               min_bad=3,
                                               window_s=60.0)
                         for t in ("noisy", "victim")})
        router.probe_now()
        results = {}

        def flood(name, **kw):
            # the duration is a cap: flood_over ends the flood
            results[name] = faultinject.tenant_flood(
                router.port, name, duration_s=60.0, stop=flood_over,
                **kw)

        noisy_th = threading.Thread(target=flood, args=("noisy",),
                                    kwargs={"nclients": 6})
        victim_th = threading.Thread(target=flood, args=("victim",),
                                     kwargs={"nclients": 1})
        noisy_th.start()
        # every noisy client has one request inside a replica (one in
        # the gated work, the rest queued behind it): both replicas
        # hold a backlog, and this probe is the one that reads it
        wait_until(lambda: sum(fe.stats()["accepted"]
                               for fe, _ in reps) == 6, timeout=20.0,
                   msg="six noisy requests inside the replicas")
        router.probe_now()
        gate.set()
        victim_th.start()
        # the flood runs until both tenants have shown what the test
        # is about (counters, not a duration)
        wait_until(lambda: router.tenant_stats().get(
                       "noisy", {}).get("shed", 0) >= 20
                   and router.tenant_stats().get(
                       "victim", {}).get("served", 0) >= 20,
                   timeout=30.0, msg="noisy shed and victim served")
        # mid-flood: queued work with zero free slots admits the standby
        assert router.autoscale_now() == "up"
        flood_over.set()
        noisy_th.join()
        victim_th.join()

        def pace():
            # the prober loop, off the clock: probe + federate + one
            # autoscale pass per turn (what the real thread does per
            # sweep), until the test stops it
            while not stop.is_set():
                router.probe_now()
                router.federate_now()
                router.autoscale_now()
                time.sleep(0.05)

        pacer = threading.Thread(target=pace, daemon=True)
        pacer.start()
        noisy, victim = results["noisy"], results["victim"]
        # zero silent losses: every request of BOTH tenants got its
        # one response line
        assert noisy["lost"] == 0 and victim["lost"] == 0
        # isolation: the flooding tenant shed (with the fair-share
        # verdict), the victim NEVER did — every victim request served
        assert noisy["tenant_shed"] > 0, noisy
        assert victim["shed"] == 0 and victim["errors"] == 0, victim
        assert victim["served"] == victim["sent"] > 0, victim
        # the victim's latency tail holds while the flood rages: its
        # closed-loop p99 stays a couple of dispatch times, far under
        # the second-scale pile-up an unfair queue would give it
        vmax = max(victim["latencies"])
        assert vmax < 1.0, (vmax, victim)
        # the autoscaler admitted the standby DURING the flood (the
        # bounded scale log pins it — the telemetry ring is churned by
        # thousands of flood request events; the fleet_scale JSONL
        # event itself is pinned by the autoscaler unit test)
        snap = router.scale_snapshot()
        assert snap["events"] >= 1
        assert snap["recent"][0]["action"] == "up", snap["recent"]
        # ... and retires it once the flood is gone (the pacer runs
        # the loop)
        wait_until(lambda: router._replicas[2].standby, timeout=6.0,
                   msg="scale-down after the flood")
        # per-tenant SLO: the noisy tenant burned its own fleet-wide
        # budget; the victim's held at 0
        router.federate_now()
        fslo = router.federation_snapshot()["slo_tenants"]
        assert fslo["noisy"]["alert"] == 1, fslo
        assert fslo.get("victim", {"alert": 0})["alert"] == 0, fslo
        stop.set()
        pacer.join(2.0)
        # books reconcile: router-wide, per tenant on the router, per
        # tenant on every replica — and the router's accepted equals
        # exactly what the two floods sent
        st = router.stats()
        assert reconciles(st), st
        assert st["accepted"] == noisy["sent"] + victim["sent"], \
            (st, noisy["sent"], victim["sent"])
        tenant_reconciles(router.tenant_stats())
        for fe, _ in reps + [sb]:
            assert reconciles(fe.stats())
            tenant_reconciles(fe.tenant_stats())
        rt = router.tenant_stats()
        assert rt["victim"]["served"] == victim["served"]
        assert rt["noisy"]["shed"] == noisy["shed"], \
            (rt["noisy"], noisy)
    finally:
        stop.set()
        flood_over.set()
        gate.set()
        telemetry.disable()
        for fe, ss in reps + [sb]:
            fe.drain(timeout_ms=2000)
            ss.stop()


# ----------------------------------------------------------------------
def test_routerd_selftest():
    assert routerd.selftest() == 0
