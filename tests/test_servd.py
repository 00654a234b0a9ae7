"""Serving frontend chaos suite (utils/servd.py): admission control +
load shedding, per-request deadlines, backend supervision + circuit
breaker (open / half-open probe / close), graceful SIGTERM drain, hot
reload, client-disconnect survival, and the statusd readiness-vs-liveness
split — all over real loopback sockets with injected backends.

Everything here is jax-free and cheap (the backend is a plain callable;
port 0 / loopback per memory of the tier-1 budget): the invariants under
fault injection are

* the server never crashes;
* every ACCEPTED request gets exactly one response line (an answer or an
  ``ERR <class>``);
* the counters reconcile: accepted == served + errors + shed + deadline;
* a drained shutdown loses zero accepted requests and exits 0.

The learn-task end-to-end wiring (real model, real generate failures)
lives in tests/test_decode.py::test_cli_serve_task.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import pytest

from cxxnet_tpu.utils import servd, statusd, telemetry

from . import faultinject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _lockrank_on(monkeypatch):
    """Runtime lock-order enforcement for every frontend/breaker/
    tracker this suite constructs (and the stub subprocesses it
    spawns): an inversion the static analyzer cannot see — callback-
    driven, cross-thread — fails the chaos test as a LockOrderError
    naming both locks and both sites instead of deadlocking in
    production (doc/static_analysis.md)."""
    monkeypatch.setenv("CXXNET_LOCKRANK", "1")


def echo(toks, seq):
    return [t + 1 for t in toks]


def reconciles(stats):
    return stats["accepted"] == (stats["served"] + stats["errors"]
                                 + stats["shed"] + stats["deadline"])


@pytest.fixture()
def make_frontend():
    """Factory for started+listening frontends; everything made here is
    drained at teardown (drain is idempotent, so tests may drain too)."""
    made = []

    def make(backend=echo, listen=True, **kw):
        kw.setdefault("drain_ms", 2000.0)
        fe = servd.ServeFrontend(backend, **kw)
        fe.start()
        if listen:
            fe.listen(0)
        made.append(fe)
        return fe

    yield make
    for fe in made:
        fe.drain(timeout_ms=2000)


# ----------------------------------------------------------------------
# basic protocol
def test_tcp_roundtrip_and_reconciliation(make_frontend):
    fe = make_frontend()
    assert faultinject.serve_request(fe.port, "1 2 3") == "2 3 4"
    assert faultinject.serve_request(fe.port, "10") == "11"
    assert faultinject.serve_request(fe.port, "DEADLINE 5000 7") == "8"
    stats = fe.drain()
    assert stats["served"] == 3 and stats["accepted"] == 3
    assert reconciles(stats)


def test_pipelined_requests_one_connection(make_frontend):
    import socket
    fe = make_frontend()
    with socket.create_connection(("127.0.0.1", fe.port),
                                  timeout=5) as c:
        c.sendall(b"1\n2\n3\n")
        f = c.makefile("r")
        assert [f.readline().strip() for _ in range(3)] == ["2", "3", "4"]


def test_pipelined_rejections_stay_in_request_order(make_frontend):
    """The protocol pairs responses to requests positionally, so a
    synchronous rejection (parse error: produced instantly by the reader
    thread) must NOT overtake the answer of an earlier request still
    occupying the worker."""
    import socket
    fe = make_frontend(backend=faultinject.slow_backend(echo, 0.1))
    with socket.create_connection(("127.0.0.1", fe.port),
                                  timeout=5) as c:
        # request 1 holds the worker for 100ms; 'bad x' would be
        # rejected immediately; request 3 queues behind
        c.sendall(b"1\nbad x\n3\n")
        f = c.makefile("r")
        lines = [f.readline().strip() for _ in range(3)]
    assert lines[0] == "2", lines
    assert lines[1].startswith("ERR parse"), lines
    assert lines[2] == "4", lines


def test_unterminated_final_line_is_served(make_frontend):
    """A client that forgets the trailing newline before shutting down
    its write side still gets its answer — the stdin surface serves an
    unterminated final line, so the TCP surface must too (silence here
    IS the framing-bug failure ERR empty exists to prevent)."""
    import socket
    fe = make_frontend()
    with socket.create_connection(("127.0.0.1", fe.port),
                                  timeout=5) as c:
        c.sendall(b"1 2 3")                 # no newline
        c.shutdown(socket.SHUT_WR)
        assert c.makefile("r").readline().strip() == "2 3 4"


def test_halfclosed_client_gets_slow_answer(make_frontend):
    """A client that pipelines its requests and shuts down its write
    side (normal use of a line protocol) must still receive an answer
    that takes longer than the drain budget — the connection waits for
    the response, it is not on a shutdown-related clock."""
    import socket
    fe = make_frontend(backend=faultinject.slow_backend(echo, 1.5),
                       drain_ms=100.0)
    with socket.create_connection(("127.0.0.1", fe.port),
                                  timeout=10) as c:
        c.sendall(b"1 2\n")
        c.shutdown(socket.SHUT_WR)
        assert c.makefile("r").readline().strip() == "2 3"
    stats = fe.stats()
    assert stats["served"] == 1 and stats["client_gone"] == 0


def test_empty_and_parse_rejections(make_frontend):
    fe = make_frontend(vocab=100)
    assert faultinject.serve_request(fe.port, "").startswith("ERR empty")
    assert faultinject.serve_request(
        fe.port, "   ").startswith("ERR empty")
    assert faultinject.serve_request(
        fe.port, "1 nope 2").startswith("ERR parse")
    assert faultinject.serve_request(
        fe.port, "1 999").startswith("ERR parse")
    assert faultinject.serve_request(
        fe.port, "DEADLINE abc 1").startswith("ERR parse")
    # float() accepts these, the protocol must not: a NaN deadline
    # compares False everywhere and silently disables the bound
    assert faultinject.serve_request(
        fe.port, "DEADLINE nan 1").startswith("ERR parse")
    assert faultinject.serve_request(
        fe.port, "DEADLINE inf 1").startswith("ERR parse")
    assert faultinject.serve_request(
        fe.port, "DEADLINE -5 1").startswith("ERR parse")
    assert faultinject.serve_request(
        fe.port, "DEADLINE 100").startswith("ERR empty")
    assert faultinject.serve_request(fe.port, "5 6") == "6 7"
    stats = fe.stats()
    assert stats["empty"] == 3 and stats["errors"] == 9
    assert stats["served"] == 1 and reconciles(stats)


def test_admin_stats_and_unknown(make_frontend):
    fe = make_frontend()
    faultinject.serve_request(fe.port, "1")
    resp = faultinject.serve_request(fe.port, "ADMIN stats")
    assert resp.startswith("OK") and "served=1" in resp
    assert faultinject.serve_request(
        fe.port, "ADMIN frobnicate").startswith("ERR parse")
    # admin lines are control traffic, outside the request reconciliation
    stats = fe.stats()
    assert stats["admin"] == 2 and stats["accepted"] == 1


# ----------------------------------------------------------------------
# deadlines
def test_deadline_expires_in_queue_before_dispatch(make_frontend):
    calls = []

    def counting_slow(toks, seq):
        calls.append(list(toks))
        time.sleep(0.15)
        return echo(toks, seq)

    fe = make_frontend(backend=counting_slow)
    results = {}

    def client(name, line):
        results[name] = faultinject.serve_request(fe.port, line)

    t1 = threading.Thread(target=client, args=("hold", "1 2 3"))
    t1.start()
    time.sleep(0.05)          # the 150ms request now occupies the worker
    t2 = threading.Thread(target=client, args=("doomed",
                                               "DEADLINE 20 4 5"))
    t2.start()
    t1.join()
    t2.join()
    assert results["hold"] == "2 3 4"
    assert results["doomed"].startswith("ERR deadline")
    # answered BEFORE dispatch: the backend never saw the doomed request
    assert [4, 5] not in calls
    stats = fe.stats()
    assert stats["deadline"] == 1 and reconciles(stats)


def test_default_deadline_from_conf(make_frontend):
    fe = make_frontend(backend=faultinject.slow_backend(echo, 0.15),
                       deadline_ms=20.0)
    r = faultinject.serve_flood(fe.port, ["1 2", "3 4"])
    # whichever request wins the worker occupies it past the other's
    # 20ms deadline; at most one can finish in time (and under load even
    # that one may expire before its own dispatch)
    ok = [x for x in r if not x.startswith("ERR")]
    dead = [x for x in r if x.startswith("ERR deadline")]
    assert len(ok) <= 1 and len(ok) + len(dead) == 2, r
    stats = fe.stats()
    assert stats["deadline"] >= 1 and reconciles(stats)


# ----------------------------------------------------------------------
# flood / shedding
def test_flood_sheds_and_every_request_answered(make_frontend):
    fe = make_frontend(backend=faultinject.slow_backend(echo, 0.08),
                       queue_size=2)
    responses = faultinject.serve_flood(fe.port, ["1 2"] * 10)
    assert all(r is not None for r in responses), responses
    ok = [r for r in responses if r == "2 3"]
    busy = [r for r in responses if r.startswith("ERR busy")]
    assert len(ok) + len(busy) == 10 and busy, responses
    stats = fe.stats()
    assert stats["accepted"] == 10
    assert stats["shed"] == len(busy) and stats["served"] == len(ok)
    assert reconciles(stats)


# ----------------------------------------------------------------------
# the ERR busy detail-token split (wire format: the fleet router's
# retryability contract — utils/routerd.py dispatches on token 3)
def test_err_busy_detail_tokens_queue_vs_breaker(make_frontend):
    """Queue-full and breaker-open sheds share the ``busy`` class (the
    2-token parse contract stands) but MUST be distinguishable by the
    third token: ``queue`` is instantly-retryable-elsewhere, ``breaker``
    additionally means "eject this replica from rotation"."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return echo(toks, seq)

    fe = make_frontend(backend=wedged, queue_size=1)
    try:
        fe.submit("1", lambda t: None)       # occupies the worker
        time.sleep(0.1)
        fe.submit("2", lambda t: None)       # fills the 1-slot queue
        resp = faultinject.serve_request(fe.port, "3")
        assert resp.split()[:3] == ["ERR", "busy", "queue"], resp
    finally:
        release.set()
    # breaker-open shed carries the breaker token (admission path)
    fe2 = make_frontend(backend=faultinject.exploding_backend(every=1),
                        breaker_fails=1, breaker_cooldown_ms=60000.0)
    assert faultinject.serve_request(
        fe2.port, "1").startswith("ERR backend")
    resp = faultinject.serve_request(fe2.port, "2")
    assert resp.split()[:3] == ["ERR", "busy", "breaker"], resp


def test_admin_stats_reports_live_load_gauges(make_frontend):
    """ADMIN stats carries the LIVE queue_depth / in_flight gauges (the
    router's load signal) alongside the counters — consistent with the
    admission queue at snapshot time."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return echo(toks, seq)

    fe = make_frontend(backend=wedged, queue_size=4)
    try:
        stats = faultinject.serve_request(fe.port, "ADMIN stats")
        assert "queue_depth=0" in stats and "in_flight=0" in stats
        fe.submit("1", lambda t: None)       # occupies the worker
        time.sleep(0.1)
        fe.submit("2", lambda t: None)       # queued
        fe.submit("3", lambda t: None)       # queued
        stats = faultinject.serve_request(fe.port, "ADMIN stats")
        assert "queue_depth=2" in stats and "in_flight=1" in stats, \
            stats
    finally:
        release.set()


# ----------------------------------------------------------------------
# backend supervision + circuit breaker
def test_backend_exception_answered_and_survived(make_frontend):
    fe = make_frontend(backend=faultinject.exploding_backend(echo,
                                                             every=2))
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    stats = fe.stats()
    assert stats["served"] == 2 and stats["errors"] == 2
    assert fe.breaker.state == "closed"     # never 2 consecutive
    assert reconciles(stats)


def test_backend_returning_garbage_is_a_backend_error(make_frontend):
    """A backend that RETURNS a non-iterable-of-ints (None, a string of
    words, ...) must be answered ERR backend like one that raises — not
    kill the worker thread and strand every queued request."""
    results = iter([None, "not tokens", [5]])
    fe = make_frontend(backend=lambda toks, seq: next(results))
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert faultinject.serve_request(fe.port, "1") == "5"
    assert fe.liveness_probe()[0], "worker thread died"
    assert reconciles(fe.stats())


def test_breaker_opens_sheds_and_recovers(make_frontend):
    backend = faultinject.healing_backend(echo, fail_first=2)
    fe = make_frontend(backend=backend, breaker_fails=2,
                       breaker_cooldown_ms=250.0)
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert fe.breaker.state == "open"
    # open: shed instantly, backend NOT called
    assert faultinject.serve_request(fe.port, "1").startswith("ERR busy")
    assert backend.calls["n"] == 2
    # cooldown elapses; the healed backend's half-open probe closes it
    time.sleep(0.3)
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert fe.breaker.state == "closed"
    stats = fe.stats()
    assert stats["shed"] == 1 and stats["served"] == 1
    assert reconciles(stats)


def test_breaker_halfopen_failure_doubles_cooldown(make_frontend):
    backend = faultinject.healing_backend(echo, fail_first=3)
    fe = make_frontend(backend=backend, breaker_fails=2,
                       breaker_cooldown_ms=200.0)
    for _ in range(2):
        assert faultinject.serve_request(
            fe.port, "1").startswith("ERR backend")
    assert fe.breaker.state == "open"
    time.sleep(0.25)
    # half-open probe fails (3rd injected failure): reopen, doubled
    assert faultinject.serve_request(
        fe.port, "1").startswith("ERR backend")
    assert fe.breaker.state == "open"
    assert faultinject.serve_request(fe.port, "1").startswith("ERR busy")
    time.sleep(0.45)                     # past the doubled 400ms cooldown
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert fe.breaker.state == "closed"
    assert fe.breaker.opens == 0         # reset on close


# ----------------------------------------------------------------------
# client disconnect mid-request
def test_client_disconnect_mid_request_survived(make_frontend):
    fe = make_frontend(backend=faultinject.slow_backend(echo, 0.1))
    faultinject.disconnecting_client(fe.port, "1 2 3")
    time.sleep(0.3)           # worker answers into the dead socket
    # the server survives and keeps serving
    assert faultinject.serve_request(fe.port, "5") == "6"
    stats = fe.stats()
    assert stats["accepted"] == 2 and reconciles(stats)


# ----------------------------------------------------------------------
# hot reload
def test_admin_reload_between_requests_keeps_queue(make_frontend):
    model = {"v": 1}
    reloads = []

    def backend(toks, seq):
        time.sleep(0.05)
        return [t + model["v"] for t in toks]

    def reload_fn():
        model["v"] = 10
        reloads.append(1)
        return True

    fe = make_frontend(backend=backend, reload_fn=reload_fn)
    import socket
    with socket.create_connection(("127.0.0.1", fe.port),
                                  timeout=5) as c:
        f = c.makefile("r")
        c.sendall(b"1\n")
        assert f.readline().strip() == "2"      # pre-reload model
        # a reload scheduled with requests already queued behind it:
        # nothing is dropped, the swap lands between requests, and the
        # queued requests are served by the NEW model
        c.sendall(b"ADMIN reload\n1\n1\n")
        lines = [f.readline().strip() for _ in range(3)]
    assert lines[0].startswith("OK reload")
    assert lines[1:] == ["11", "11"] and reloads
    assert fe.stats()["reloads"] == 1


def test_failing_reload_keeps_model_and_serving(make_frontend, capsys):
    def reload_fn():
        raise RuntimeError("no checkpoint dir")

    fe = make_frontend(reload_fn=reload_fn)
    assert faultinject.serve_request(
        fe.port, "ADMIN reload").startswith("OK")
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert fe.stats()["reloads"] == 0


# ----------------------------------------------------------------------
# drain
def test_drain_answers_every_accepted_request():
    fe = servd.ServeFrontend(faultinject.slow_backend(echo, 0.15),
                             queue_size=16, drain_ms=10000.0)
    fe.start()
    replies = []
    for i in range(4):
        fe.submit("%d" % i, replies.append)
    stats = fe.drain()          # generous budget: everything is served
    assert sorted(replies) == ["1", "2", "3", "4"]
    assert stats["served"] == 4 and reconciles(stats)


def test_drain_budget_exhausted_still_answers():
    fe = servd.ServeFrontend(faultinject.slow_backend(echo, 0.2),
                             queue_size=16)
    fe.start()
    replies = []
    for i in range(5):
        fe.submit("%d" % i, replies.append)
    stats = fe.drain(timeout_ms=150)
    # exactly one response per accepted request: some served, the
    # leftovers explicitly ERR draining — never silence
    assert len(replies) == 5
    assert any(r.startswith("ERR draining") for r in replies)
    assert stats["served"] >= 1 and reconciles(stats)
    # post-drain admissions are refused, and still answered
    fe.submit("9", replies.append)
    assert replies[-1].startswith("ERR draining")


def test_drain_leftovers_burn_slo_budget():
    """Queued requests a drain gives up on (ERR draining) are accepted
    requests the client lost: they must burn error budget like an
    admission shed, or a preemption during overload leaves
    cxxnet_slo_burn reading 0 with every accepted request failed."""
    slo = statusd.SLOTracker(availability=0.999, min_requests=4,
                             min_bad=3, window_s=60.0)
    fe = servd.ServeFrontend(faultinject.slow_backend(echo, 0.5),
                             queue_size=16, slo=slo)
    fe.start()
    replies = []
    for i in range(6):
        fe.submit("%d" % i, replies.append)
    stats = fe.drain(timeout_ms=50)
    assert len(replies) == 6 and reconciles(stats)
    drained = sum(1 for r in replies if r.startswith("ERR draining"))
    assert drained >= 3, replies
    snap = slo.snapshot()
    assert snap["bad"] >= drained, snap
    assert snap["alert"] == 1, snap


def test_stalled_backend_fails_readiness_then_liveness():
    """A backend that BLOCKS without raising is invisible to deadlines
    (pre-dispatch only), the breaker (no exception), and the paused
    worker heartbeat — the stall_after_s bound on the in-flight
    dispatch is what surfaces it: readiness fails past the bound,
    liveness past twice it, both recover when the backend returns."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return echo(toks, seq)

    fe = servd.ServeFrontend(wedged, stall_after_s=0.1, drain_ms=500.0)
    fe.start()
    try:
        fe.submit("1", lambda t: None)
        time.sleep(0.05)            # in flight, under the bound
        assert fe.health_probe()[0] and fe.liveness_probe()[0]
        time.sleep(0.1)             # past stall_after_s: unroutable
        ok, detail = fe.health_probe()
        assert not ok and "stalled" in detail
        assert fe.liveness_probe()[0]     # but not restart-worthy yet
        time.sleep(0.15)            # past 2x: restart signal
        ok, detail = fe.liveness_probe()
        assert not ok and "wedged" in detail
    finally:
        release.set()
    time.sleep(0.2)                 # backend returned: healthy again
    assert fe.health_probe()[0] and fe.liveness_probe()[0]
    fe.drain()


def test_drain_with_wedged_backend_answers_inflight_once():
    """A backend that outlives even the drain budget: the in-flight
    request is answered ERR by drain itself (never silently dropped),
    the final stats reconcile, and when the wedged backend eventually
    returns, the worker's late answer is a no-op — one response line,
    one outcome count, ever."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return echo(toks, seq)

    fe = servd.ServeFrontend(wedged, drain_ms=200.0)
    fe.start()
    replies = []
    fe.submit("1", replies.append)
    time.sleep(0.1)                  # request is in flight
    try:
        stats = fe.drain(timeout_ms=200)
        assert replies and replies[0].startswith("ERR draining"), replies
        assert reconciles(stats) and stats["errors"] == 1
    finally:
        release.set()                # un-wedge the worker thread
    time.sleep(0.3)                  # its late answer must be a no-op
    assert len(replies) == 1
    final = fe.stats()
    assert reconciles(final) and final["served"] == 0
    # the late completion is flight-recorded as abandoned — the backend
    # did the work, but the client got drain's ERR, not this answer
    recs = [r for r in fe.flight.list() if r["outcome"] == "abandoned"]
    assert len(recs) == 1, fe.flight.list()
    assert not any(r["outcome"] == "served" for r in fe.flight.list())


def test_sigterm_drain_loses_zero_accepted_requests():
    """The headline drain contract, against the real process boundary:
    SIGTERM mid-flight → the stub server stops accepting, finishes every
    accepted request, reports reconciled stats, exits 0 — and the
    clients' received responses account for every accepted request."""
    p = subprocess.Popen(
        [sys.executable, "-m", "cxxnet_tpu.utils.servd", "--stub",
         "--delay-ms", "60"],
        stdout=subprocess.PIPE, text=True, cwd=REPO)
    try:
        port = int(p.stdout.readline().split()[-1])
        responses = []
        lock = threading.Lock()

        def client():
            r = faultinject.serve_request(port, "1 2 3", timeout=15)
            with lock:
                responses.append(r)

        ts = [threading.Thread(target=client) for _ in range(8)]
        for t in ts:
            t.start()
        time.sleep(0.15)        # a couple served, the rest queued
        p.send_signal(signal.SIGTERM)
        for t in ts:
            t.join()
        rc = p.wait(timeout=20)
        tail = p.stdout.read()
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc == 0, tail
    stats = json.loads(tail.split("drained ", 1)[1])
    assert reconciles(stats)
    # zero accepted-but-unanswered: every request the server accepted
    # produced a response line some client received
    answered = [r for r in responses if r is not None]
    assert len(answered) == stats["accepted"]
    assert all(r == "2 3 4" or r.startswith("ERR") for r in answered)


# ----------------------------------------------------------------------
# statusd readiness vs liveness (the /healthz split, satellite of this
# PR: 503 while draining or breaker-open, /livez unaffected)
@pytest.fixture()
def status_server():
    reg = telemetry._Registry()
    reg.enable()
    srv = statusd.StatusServer(0, host="127.0.0.1",
                               registry=reg).start()
    yield srv
    srv.stop()
    reg.disable()


def _get(srv, path):
    try:
        r = urlopen("http://127.0.0.1:%d%s" % (srv.port, path), timeout=5)
        return r.status, r.read().decode()
    except HTTPError as e:
        return e.code, e.read().decode()


def test_healthz_flips_on_breaker_and_recovers(make_frontend,
                                               status_server):
    backend = faultinject.healing_backend(echo, fail_first=2)
    fe = make_frontend(backend=backend, breaker_fails=2,
                       breaker_cooldown_ms=200.0)
    status_server.register_probe("serving", fe.health_probe)
    status_server.register_probe("serving.worker", fe.liveness_probe,
                                 liveness=True)
    assert _get(status_server, "/healthz")[0] == 200
    assert _get(status_server, "/livez")[0] == 200
    for _ in range(2):
        faultinject.serve_request(fe.port, "1")
    code, body = _get(status_server, "/healthz")
    assert code == 503 and "circuit breaker open" in body
    # breaker-open is NOT-READY, not NOT-ALIVE: no restart for overload
    assert _get(status_server, "/livez")[0] == 200
    metrics = _get(status_server, "/metrics")[1]
    assert 'cxxnet_healthy{process="0"} 0' in metrics
    assert 'cxxnet_live{process="0"} 1' in metrics
    # successful half-open probe closes the breaker: ready again
    time.sleep(0.25)
    assert faultinject.serve_request(fe.port, "1") == "2"
    assert _get(status_server, "/healthz")[0] == 200
    assert 'cxxnet_healthy{process="0"} 1' \
        in _get(status_server, "/metrics")[1]


def test_healthz_flips_during_drain_livez_stays(make_frontend,
                                                status_server):
    fe = make_frontend()
    status_server.register_probe("serving", fe.health_probe)
    status_server.register_probe("serving.worker", fe.liveness_probe,
                                 liveness=True)
    assert _get(status_server, "/healthz")[0] == 200
    fe.drain()
    code, body = _get(status_server, "/healthz")
    assert code == 503 and "draining" in body
    assert _get(status_server, "/livez")[0] == 200


# ----------------------------------------------------------------------
# watchdog heartbeat channels
def test_watchdog_worker_channel_pauses_when_idle(make_frontend):
    """The serve.worker channel must disarm across idle periods (an
    empty queue is not a hang) while serve.accept keeps beating from the
    accept poll loop — so a watchdog over a quiet server never
    false-alarms."""
    from cxxnet_tpu.utils import health
    wd = health.Watchdog(timeout=1.0, action="warn", poll=30.0).start()
    try:
        fe = make_frontend()
        assert faultinject.serve_request(fe.port, "1") == "2"
        time.sleep(0.3)        # idle: the worker paused its channel
        chans = {c[0]: c[3] for c in health.channel_status()}
        assert "serve.worker" not in chans
        assert chans.get("serve.accept") is False       # armed, fresh
    finally:
        wd.stop()


# ----------------------------------------------------------------------
# stdin-engine path (submit wait=True) + metrics surfacing
def test_sync_submit_keeps_request_order():
    fe = servd.ServeFrontend(echo, drain_ms=2000.0)
    fe.start()
    replies = []
    for line in ("1", "", "2 x", "3"):
        fe.submit(line, replies.append, wait=True)
    assert replies[0] == "2"
    assert replies[1].startswith("ERR empty")
    assert replies[2].startswith("ERR parse")
    assert replies[3] == "4"
    fe.drain()


def test_serve_metrics_reach_prometheus(status_server):
    reg = status_server.registry
    # the frontend records through the module-level telemetry registry;
    # here the series are injected directly to pin the /metrics names
    reg.count("serve.accepted", 10)
    reg.count("serve.requests", 7)
    reg.count("serve.shed", 2)
    reg.count("serve.deadline", 1)
    reg.gauge("serve.queue_depth", 3)
    reg.gauge("serve.in_flight", 1)
    reg.hist("serve.request", 0.05)
    reg.hist("serve.queue_wait", 0.01)
    code, text = _get(status_server, "/metrics")
    assert code == 200
    for needle in ("cxxnet_serve_accepted_total 10",
                   "cxxnet_serve_requests_total 7",
                   "cxxnet_serve_shed_total 2",
                   "cxxnet_serve_deadline_total 1",
                   "cxxnet_serve_queue_depth 3",
                   "cxxnet_serve_in_flight 1"):
        assert needle.split()[0] in text and needle.replace(
            needle.split()[0],
            needle.split()[0] + '{process="0"}') in text, needle
    assert "cxxnet_serve_request_seconds_bucket" in text
    assert "cxxnet_serve_queue_wait_seconds_bucket" in text
    reg.hist("serve.ttft", 0.02)
    reg.gauge("serve.tokens_per_second", 120.5)
    reg.gauge("serve.batch_occupancy", 1)
    text = _get(status_server, "/metrics")[1]
    assert "cxxnet_serve_ttft_seconds_bucket" in text
    assert "cxxnet_serve_tokens_per_second" in text
    assert 'cxxnet_serve_batch_occupancy{process="0"} 1' in text


# ----------------------------------------------------------------------
# tools/telemetry_report.py serving section + unresolved-breaker gate
sys.path.insert(0, os.path.join(REPO, "tools"))
import telemetry_report  # noqa: E402


def _serve_into_log(tmp_path, backend, requests, **kw):
    """Run a frontend against the module-level telemetry registry with a
    real JSONL sink (the learn-task layout), return the log path."""
    log = str(tmp_path / "serve.jsonl")
    telemetry.enable(log)
    try:
        fe = servd.ServeFrontend(backend, **kw)
        fe.start()
        port = fe.listen(0)
        for line in requests:
            faultinject.serve_request(port, line)
        fe.drain()
    finally:
        telemetry.finish(close=True)
    return log


def test_report_serving_section_and_rates(tmp_path, capsys):
    backend = faultinject.healing_backend(echo, fail_first=2)
    log = _serve_into_log(
        tmp_path, backend,
        ["1 2", "3", "4", "5", "DEADLINE 0 6", "7 8"],
        breaker_fails=2, breaker_cooldown_ms=1.0, queue_size=8,
        drain_ms=2000.0)
    # 2 backend failures open the breaker; the 1ms cooldown means the
    # next request probes and (healed) closes it — the log ends healthy
    rc = telemetry_report.main([log, "--json"])
    agg = json.loads(capsys.readouterr().out)
    assert rc == 0
    sv = agg["serving"]
    assert sv["accepted"] == 6 and sv["errors"] == 2
    assert sv["deadline"] == 1 and sv["deadline_miss_rate"] > 0
    assert sv["breaker_transitions"]["open"] == 1
    assert sv["breaker_final"] == {"0": "closed"}
    assert agg["hists"]["serve.request"]["count"] >= 3
    rc = telemetry_report.main([log])
    out = capsys.readouterr().out
    assert rc == 0 and "== serving ==" in out
    assert "breaker transitions" in out


def test_report_serving_section_empty_latency_renders_na(tmp_path, capsys):
    """A run whose only accepted request dies in the queue (deadline 0,
    answered before dispatch) leaves the declared serve.request
    histogram empty — count 0, None percentiles. The serving section's
    latency line must render n/a, not crash on the None sentinel."""
    log = _serve_into_log(tmp_path, echo, ["DEADLINE 0 1"], drain_ms=500.0)
    rc = telemetry_report.main([log])
    out = capsys.readouterr().out
    assert rc == 0 and "== serving ==" in out
    assert "request latency: n=0  p50=n/a  p90=n/a  p99=n/a" in out


def test_report_exit2_on_unresolved_breaker_open(tmp_path, capsys):
    log = _serve_into_log(
        tmp_path, faultinject.exploding_backend(every=1),
        ["1", "2", "3"],
        breaker_fails=2, breaker_cooldown_ms=60000.0, drain_ms=500.0)
    rc = telemetry_report.main([log])
    err = capsys.readouterr().err
    assert rc == 2
    assert "circuit breaker still OPEN" in err


# ----------------------------------------------------------------------
# request tracing: ids, phase attribution, TTFT split, flight recorder,
# /trace?request=<id>, SLO burn (the observability contract the
# throughput arc is graded against — ISSUE 6 tentpole)
PHASES = telemetry.REQUEST_PHASES


def test_request_tracing_end_to_end():
    """The acceptance loop: a loopback serve run answers
    /trace?request=<id> for a just-completed request with a Chrome
    trace whose phase spans cover >= 95% of the request's wall-clock,
    /requestz lists it, and /metrics exports valid serve_ttft_seconds
    buckets."""
    telemetry.enable()        # module registry: the frontend's series
    fe = srv = None
    try:
        srv = statusd.StatusServer(0, host="127.0.0.1").start()
        fe = servd.ServeFrontend(
            faultinject.phased_backend(echo, prefill_s=0.03,
                                       per_token_s=0.005),
            drain_ms=2000.0)
        fe.start()
        fe.listen(0)
        srv.flight = fe.flight
        assert faultinject.serve_request(fe.port, "1 2 3") == "2 3 4"
        rec = fe.flight.list()[0]
        assert rec["outcome"] == "served" and rec["tokens_out"] == 3
        # coverage vs the independently measured accept->observe
        # wall-clock (wall_s), NOT the phase sum total_s — total_s IS
        # the sum, so an assertion against it could never fail
        cover = sum(rec["phases"].values())
        assert cover >= 0.95 * rec["wall_s"]
        # the per-request Chrome trace over HTTP
        code, body = _get(srv, "/trace?request=" + rec["id"])
        assert code == 200
        xs = [e for e in json.loads(body)["traceEvents"]
              if e.get("ph") == "X" and e["name"] in PHASES]
        total_us = max(e["ts"] + e["dur"] for e in xs) \
            - min(e["ts"] for e in xs)
        assert sum(e["dur"] for e in xs) >= 0.95 * total_us
        assert total_us >= 0.95 * rec["wall_s"] * 1e6
        code, _ = _get(srv, "/trace?request=99999")
        assert code == 404
        code, body = _get(srv, "/requestz?json=1")
        assert code == 200
        assert rec["id"] in [r["id"]
                             for r in json.loads(body)["requests"]]
        # HTML by default (the /fleetz//programz ?json=1 contract) and
        # the single-record fetch the cross-process stitch uses
        code, body = _get(srv, "/requestz")
        assert code == 200 and "flight recorder" in body
        code, body = _get(srv, "/requestz?request=" + rec["id"])
        assert code == 200 and json.loads(body)["id"] == rec["id"]
        # /metrics: valid serve_ttft_seconds buckets with the request in
        code, metrics = _get(srv, "/metrics")
        assert code == 200
        for line in metrics.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        m = [line for line in metrics.splitlines()
             if line.startswith("cxxnet_serve_ttft_seconds_bucket")
             and 'le="+Inf"' in line]
        assert m and int(m[0].rsplit(" ", 1)[1]) >= 1, m
    finally:
        if fe is not None:
            fe.drain(timeout_ms=2000)
        if srv is not None:
            srv.stop()
        telemetry.disable()


def test_ttft_split_phase_attribution(make_frontend):
    """The first_token mark splits the backend call into prefill and
    decode; TTFT = queue_wait + dispatch + prefill, strictly less than
    the total for a multi-token answer."""
    fe = make_frontend(backend=faultinject.phased_backend(
        echo, prefill_s=0.05, per_token_s=0.01))
    assert faultinject.serve_request(fe.port, "1 2 3") == "2 3 4"
    rec = fe.flight.list()[0]
    ph = rec["phases"]
    assert ph["prefill"] >= 0.04, ph          # slept 50ms pre-mark
    assert ph["decode"] >= 0.015, ph          # 2 x 10ms post-mark
    # fields round to 6 decimals independently: allow one ulp per term
    assert abs(rec["ttft_s"] - (ph["queue_wait"] + ph["dispatch"]
                                + ph["prefill"])) < 5e-6
    assert rec["ttft_s"] <= rec["total_s"] - 0.01
    assert rec["tokens_per_s"] is not None and rec["tokens_per_s"] > 0


def test_unmarked_backend_falls_back_to_all_prefill(make_frontend):
    """A backend that never marks first_token (no trainer underneath)
    still gets honest attribution: first and last token arrive
    together, so the whole call is prefill and TTFT == total latency
    minus nothing."""
    fe = make_frontend()
    assert faultinject.serve_request(fe.port, "7") == "8"
    rec = fe.flight.list()[0]
    assert rec["phases"]["decode"] == 0.0
    assert abs(rec["ttft_s"] - rec["total_s"]) < 1e-9


def test_trace_context_tags_backend_telemetry(make_frontend):
    """Spans/compiles/counters recorded inside the backend carry the
    request id (telemetry.trace_context propagation through the worker)
    and land attributed in the flight record."""
    telemetry.enable()
    try:
        def backend(toks, seq):
            telemetry.count("decode.tokens", len(toks))
            telemetry.record_compile("jit.decode_step",
                                     "new_signature", 0.01)
            return [t + 1 for t in toks]

        fe = make_frontend(backend=backend)
        assert faultinject.serve_request(fe.port, "5 6") == "6 7"
        rec = fe.flight.list()[0]
        assert [c["name"] for c in rec["recompiles"]] \
            == ["jit.decode_step"]
        assert rec["counts"]["decode.tokens"] == 2
        evs = telemetry.recent_events()
        spans = [e for e in evs if e.get("ev") == "span"
                 and e.get("name") == "serve.request"]
        assert spans and spans[-1].get("req") == rec["id"]
        comps = [e for e in evs if e.get("ev") == "compile"]
        assert comps and comps[-1].get("req") == rec["id"]
        done = [e for e in evs if e.get("ev") == "serve_request_done"]
        assert done and done[-1]["recompiles"] == 1
    finally:
        telemetry.disable()


def test_request_ids_unique_and_deadline_attributed(make_frontend):
    """Ids increase per accepted request; a request that dies in the
    queue (deadline) still leaves a flight record, attributed to
    queue_wait with no backend phases."""
    started = threading.Event()

    def slow(toks, seq):
        started.set()
        time.sleep(0.08)
        return echo(toks, seq)

    fe = make_frontend(backend=slow, queue_size=8)
    # occupy the worker first so the deadlined request is GUARANTEED to
    # out-wait its 10ms budget in the queue (no dispatch-order race)
    first = threading.Thread(
        target=lambda: faultinject.serve_request(fe.port, "1"))
    first.start()
    assert started.wait(5.0)
    resp = faultinject.serve_request(fe.port, "DEADLINE 10 2")
    first.join()
    assert resp.startswith("ERR deadline")
    assert faultinject.serve_request(fe.port, "3") == "4"
    recs = fe.flight.list()
    assert len({r["id"] for r in recs}) == 3
    dl = next(r for r in recs if r["outcome"] == "deadline")
    assert dl["phases"]["prefill"] == 0.0 \
        and dl["phases"]["queue_wait"] > 0 and dl["ttft_s"] is None


def test_flight_recorder_eviction(make_frontend):
    fr = telemetry.FlightRecorder(cap=4)
    for i in range(7):
        fr.record({"id": str(i)})
    assert len(fr) == 4
    assert fr.get("2") is None and fr.get("6")["id"] == "6"
    assert [r["id"] for r in fr.list()] == ["6", "5", "4", "3"]
    # and through the frontend: the ring holds only the newest
    fe = make_frontend(flight_cap=2)
    for line in ("1", "2", "3", "4"):
        faultinject.serve_request(fe.port, line)
    assert len(fe.flight) == 2
    assert [r["tokens_in"] for r in fe.flight.list()] == [1, 1]
    assert fe.flight.get(fe.flight.list()[0]["id"]) is not None


def test_slo_burn_flips_on_slow_flood_not_on_healthy(make_frontend):
    slo = statusd.SLOTracker(ttft_ms=50.0, availability=0.999,
                             min_requests=5, window_s=60.0)
    fe = make_frontend(slo=slo)
    for _ in range(5):
        assert faultinject.serve_request(fe.port, "1") == "2"
    snap = slo.snapshot()
    assert snap["alert"] == 0 and snap["burn_rate"] == 0.0, snap
    # injected slow-request flood: every TTFT blows the 50ms objective
    fe.backend = faultinject.slow_backend(echo, 0.08)
    responses = faultinject.serve_flood(fe.port, ["1"] * 6)
    assert all(r == "2" for r in responses)
    snap = slo.snapshot()
    assert snap["alert"] == 1 and snap["burn_rate"] >= 1.0, snap
    assert snap["by_reason"]["ttft"] >= 6, snap


def test_admission_sheds_burn_slo_budget(make_frontend):
    """Requests shed at the door (queue full / breaker open at accept)
    are availability failures: they must burn the SLO error budget
    exactly like dispatch-time sheds, or a total-overload flood that
    sheds 99% of traffic reads as burn 0 during the worst availability
    incident the server can have."""
    release = threading.Event()

    def wedged(toks, seq):
        release.wait(10.0)
        return echo(toks, seq)

    slo = statusd.SLOTracker(availability=0.99, min_requests=3,
                             window_s=60.0)
    fe = make_frontend(backend=wedged, queue_size=1, slo=slo)
    try:
        fe.submit("1", lambda t: None)   # occupies the worker
        time.sleep(0.1)
        fe.submit("2", lambda t: None)   # fills the 1-slot queue
        sheds = [faultinject.serve_request(fe.port, "3")
                 for _ in range(4)]
        assert all(s.startswith("ERR busy") for s in sheds), sheds
        snap = slo.snapshot()
        assert snap["bad"] >= 4 and snap["by_reason"]["error"] >= 4, snap
        assert snap["alert"] == 1, snap
    finally:
        release.set()


def test_report_request_breakdown_and_slo_exit2(tmp_path, capsys):
    slo = statusd.SLOTracker(ttft_ms=5.0, availability=0.99,
                             min_requests=3, window_s=60.0)
    log = _serve_into_log(
        tmp_path,
        faultinject.phased_backend(echo, prefill_s=0.02,
                                   per_token_s=0.001),
        ["1 2", "3 4", "5 6", "7 8", "DEADLINE 0 9 9"], slo=slo,
        drain_ms=2000.0)
    rc = telemetry_report.main([log, "--json"])
    agg = json.loads(capsys.readouterr().out)
    # every request blew the 5ms TTFT objective: the log ends burning
    assert rc == 2
    rq = agg["requests"]
    assert rq["count"] == 5
    assert rq["outcomes"] == {"served": 4, "deadline": 1}
    # the deadline-expired request never reached the backend: its event
    # carries null prefill/decode (hard zeros would deflate the latency
    # percentiles exactly during the overload this table triages), but
    # its queue_wait/dispatch/total are real
    for ph in ("queue_wait", "dispatch", "total"):
        assert rq["phases"][ph]["count"] == 5, ph
    for ph in ("prefill", "decode", "ttft"):
        assert rq["phases"][ph]["count"] == 4, ph
    assert rq["phases"]["prefill"]["p50_ms"] >= 15.0
    assert len(rq["slowest"]) == 5
    assert agg["slo"]["burning"] == ["0"]
    rc = telemetry_report.main([log])
    captured = capsys.readouterr()
    assert rc == 2
    assert "request breakdown" in captured.out
    assert "top-5 slowest requests" in captured.out
    assert "burn rate still exceeded" in captured.err


# ----------------------------------------------------------------------
# continuous batching: the slot-backend dispatcher (doc/serving.md
# "Continuous batching") driven jax-free through faultinject's fake
# slot backend — coalescing, mid-decode join, per-iteration deadlines,
# exactly-once under drain mid-batch, load/occupancy signals.


def _expect_line(first_tok, n):
    return " ".join(str(first_tok + k) for k in range(1, n + 1))


def test_batch_coalesce_flood_exact_and_occupancy(make_frontend):
    """A concurrent flood coalesces into real batches: every response is
    exact (zero lost, zero duplicated — one aligned answer per
    request), the measured mean occupancy beats 1 sequence/pass, and
    every flight record carries occupancy_at_dispatch."""
    sb = faultinject.slot_backend(buckets=(1, 2, 4), n_new=4,
                                  per_token_s=0.003)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=40.0)
    lines = ["%d 7" % (10 * i) for i in range(1, 9)]
    resps = faultinject.serve_flood(fe.port, lines, timeout=20.0)
    for i, r in enumerate(resps):
        assert r == _expect_line(10 * (i + 1), 4), (i, r)
    assert fe.mean_occupancy() is not None and fe.mean_occupancy() > 1.0
    recs = fe.flight.list()
    assert all(r.get("occupancy_at_dispatch", 0) >= 1 for r in recs)
    assert any(r["occupancy_at_dispatch"] > 1 for r in recs)
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 8


def test_batch_mid_decode_join_after_retire(make_frontend):
    """THE headline: a finished sequence frees its slot and the next
    queued request joins while a straggler is still decoding —
    asserted via the fake backend's iteration journal, with exact
    responses for all three."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  per_token_s=0.01, long_for={100},
                                  long_n_new=40)
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=40.0, drain_ms=8000.0)
    out = [None] * 3

    def ask(i, line):
        out[i] = faultinject.serve_request(fe.port, line, timeout=30.0)

    t1 = threading.Thread(target=ask, args=(0, "100"))   # straggler: 40
    t2 = threading.Thread(target=ask, args=(1, "200"))   # 3 tokens
    t1.start()
    t2.start()
    time.sleep(0.15)                 # straggler mid-decode, 200 done
    t3 = threading.Thread(target=ask, args=(2, "300"))
    t3.start()
    for t in (t1, t2, t3):
        t.join()
    assert out[0] == _expect_line(100, 40)
    assert out[1] == _expect_line(200, 3)
    assert out[2] == _expect_line(300, 3)
    admits = [e for e in sb.journal if e[0] == "admit"]
    retires = [e for e in sb.journal if e[0] == "retire"]
    # request 300 (3rd admit) joined AFTER the first retirement freed a
    # slot and BEFORE the straggler finished: a mid-decode join, pinned
    # by iteration counters, not timing
    join_iter = admits[2][2]
    first_retire_iter = retires[0][2]
    straggler_retire_iter = retires[-1][2]
    assert first_retire_iter <= join_iter < straggler_retire_iter, \
        sb.journal
    stats = fe.drain()
    assert reconciles(stats) and stats["served"] == 3


def test_batch_deadline_retires_mid_decode_others_continue(make_frontend):
    """Per-ITERATION deadline enforcement: an expired sequence retires
    with ERR deadline between iterations while its batchmates keep
    decoding to completion; its flight record keeps the real phases
    (the backend burned them) and its tokens so far."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=40,
                                  per_token_s=0.005)
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=50.0, drain_ms=8000.0)
    out = [None] * 2

    def ask(i, line):
        out[i] = faultinject.serve_request(fe.port, line, timeout=30.0)

    ts = [threading.Thread(target=ask, args=(0, "DEADLINE 100 100")),
          threading.Thread(target=ask, args=(1, "200"))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert out[0].startswith("ERR deadline"), out[0]
    assert out[1] == _expect_line(200, 40)
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["deadline"] == 1 and stats["served"] == 1
    # the retired sequence really decoded before expiring: its record
    # carries tokens and a positive decode phase (not the hard zeros of
    # a never-dispatched expiry)
    rec = next(r for r in fe.flight.list() if r["outcome"] == "deadline")
    assert rec["tokens_out"] >= 1
    assert rec["phases"]["decode"] > 0


def test_batch_drain_mid_batch_exactly_once(make_frontend):
    """Drain with a batch in flight and more queued: every accepted
    request is answered EXACTLY once — completed, ERR draining
    (queued leftovers), or ERR draining backend (the batch the budget
    gave up on) — and the books reconcile."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=30,
                                  per_token_s=0.02)
    fe = make_frontend(None, slot_backend=sb, listen=False, batch_max=2,
                       batch_window_ms=0.0, drain_ms=300.0)
    replies = {}

    def mkreply(i):
        def reply(text):
            replies.setdefault(i, []).append(text)
        return reply

    for i in range(4):                  # 2 into slots, 2 queued
        fe.submit("%d00 7" % (i + 1), mkreply(i))
    time.sleep(0.15)                    # batch underway
    stats = fe.drain(timeout_ms=300)
    assert reconciles(stats), stats
    assert stats["accepted"] == 4
    time.sleep(0.3)                     # a late worker answer would dup
    assert sorted(replies) == [0, 1, 2, 3]
    for i, texts in sorted(replies.items()):
        assert len(texts) == 1, (i, texts)
    assert sum(1 for t in replies.values()
               if t[0].startswith("ERR draining")) >= 2


def test_batch_step_failure_fails_whole_batch_then_recovers(
        make_frontend):
    """A decode-step exception answers every active sequence ERR
    backend (exactly once), counts ONE breaker failure, drops the
    session — and the next request gets a fresh session and succeeds."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=4,
                                  per_token_s=0.005,
                                  explode_on_iterations={2})
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=50.0)
    resps = faultinject.serve_flood(fe.port, ["100", "200"],
                                    timeout=20.0)
    assert all(r.startswith("ERR backend") for r in resps), resps
    assert fe.breaker.state == "closed"     # 1 failure < the threshold
    # recovery: a NEW session serves the next request (iteration 2 of
    # the fresh session explodes again — use a session whose first
    # explosion is spent... the fake's explode set is per-session, so
    # drive past it with single-token steps)
    sb.explode_on.clear()
    assert faultinject.serve_request(fe.port, "300",
                                     timeout=20.0) == _expect_line(300, 4)
    assert len(sb.sessions) >= 2
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["errors"] == 2 and stats["served"] == 1


def test_batch_prefill_failure_closes_session_and_evicts(make_frontend):
    """A prefill failure CLOSES the session (its device state integrity
    is unknown — the DecodeSession contract) and the dispatcher evicts
    it from the warm pool: the failed request answers ERR backend, a
    batchmate already aboard fails with it, and the next request gets
    a FRESH session — a broken session never serves again."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  per_token_s=0.005,
                                  explode_prefill_for={666})
    # queue BEFORE start(): "100" boards first and "666"'s prefill
    # fault kills it in the SAME gathered turn. The TCP-flood version
    # raced arrival order — a fast machine gathered "666" first and
    # alone, so no admission was ever journaled and there was no
    # stepped==0 flush to assert on.
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=2,
                             batch_window_ms=50.0, drain_ms=2000.0)
    replies = {}

    def mkreply(i):
        def reply(text):
            replies.setdefault(i, []).append(text)
        return reply

    events = [fe.submit("100", mkreply(0)), fe.submit("666", mkreply(1))]
    fe.start()
    fe.listen(0)
    for ev in events:
        assert ev.wait(20.0), "request never answered"
    resps = [replies[0][-1], replies[1][-1]]
    assert any(r.startswith("ERR backend") for r in resps), resps
    ok = faultinject.serve_request(fe.port, "200", timeout=20.0)
    assert ok == _expect_line(200, 3)
    assert len(sb.sessions) >= 2        # the closed one was evicted
    assert sb.sessions[0].closed
    # the faulted turn's journal flushed under the REAL bucket: the
    # session was already evicted (sess = None) when the flush ran,
    # and a bucket-0 row would poison /batchz and the report's
    # per-bucket table exactly on the fault path being inspected
    flushes = [r for r in fe.batch_flight.list()
               if r.get("stepped") == 0]
    assert flushes and all(r["bucket"] == 2 for r in flushes), flushes
    stats = fe.drain()
    assert reconciles(stats)


def test_batch_prefill_failure_counts_one_breaker_failure(make_frontend):
    """ONE prefill fault in a coalesced batch costs the breaker exactly
    ONE failure count, however many requests die of it: the dispatcher
    stops admitting into the closed session (each further prefill
    would raise and spuriously count again) and answers the rest
    without re-counting — a single fault must not open the circuit."""
    sb = faultinject.slot_backend(buckets=(8,), n_new=3,
                                  explode_prefill_for=set(
                                      range(100, 700, 100)))
    # queue BEFORE start(): all six requests land in ONE gathered batch
    # deterministically, so exactly one prefill fault covers them all
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=8,
                             batch_window_ms=0.0, breaker_fails=5,
                             drain_ms=2000.0)
    replies = {}

    def mkreply(i):
        def reply(text):
            replies.setdefault(i, []).append(text)
        return reply

    events = [fe.submit("%d00 7" % (i + 1), mkreply(i))
              for i in range(6)]
    fe.start()
    for ev in events:
        assert ev.wait(10.0), "request never answered"
    assert sorted(replies) == list(range(6))
    for i, texts in replies.items():
        assert len(texts) == 1 and texts[0].startswith("ERR backend"), \
            (i, texts)
    assert fe.breaker.state == "closed", fe.breaker.describe()
    assert fe.breaker.consecutive == 1, fe.breaker.consecutive
    stats = fe.drain()
    assert reconciles(stats) and stats["errors"] == 6


def test_batch_prefill_rejection_never_feeds_breaker(make_frontend):
    """A prefill that raises WITHOUT closing the session (pre-dispatch
    validation — e.g. a too-long prompt against a backend with no
    admits() hook) is a deterministic request defect: answered ERR
    backend, breaker untouched — a flood of client defects must not
    open the circuit and shed healthy traffic."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  reject_for={666})
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=0.0, breaker_fails=2)
    for _ in range(3):      # more defects than breaker_fails
        bad = faultinject.serve_request(fe.port, "666", timeout=10.0)
        assert bad.startswith("ERR backend"), bad
    assert fe.breaker.state == "closed"
    assert fe.breaker.consecutive == 0
    assert faultinject.serve_request(fe.port, "100",
                                     timeout=10.0) == _expect_line(100, 3)
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["errors"] == 3 and stats["served"] == 1


def test_batch_fresh_batch_occupancy_stamped_batchwide(make_frontend):
    """Members of ONE coalesced fresh batch share their first decode
    pass: every flight record carries the batch occupancy, not the
    sequential admit order (1, 2, ...) — /requestz must not read
    'not coalesced' for the batch's first member."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  per_token_s=0.002)
    # queue BEFORE start(): both requests land in one gathered batch
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=2,
                             batch_window_ms=0.0, drain_ms=2000.0)
    done = [fe.submit("%d00 7" % (i + 1), lambda t: None)
            for i in range(2)]
    fe.start()
    for ev in done:
        assert ev.wait(10.0)
    occs = sorted(r["occupancy_at_dispatch"] for r in fe.flight.list())
    assert occs == [2, 2], occs
    stats = fe.drain()
    assert reconciles(stats) and stats["served"] == 2


def test_batch_free_slots_load_signal_in_admin_stats(make_frontend):
    """ADMIN stats reports free decode slots (capacity − active): full
    capacity when idle, reduced while a batch decodes — the router's
    prefer-the-replica-that-can-batch-it-in signal. Solo frontends
    omit the field (backward compatible by absence)."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=20,
                                  per_token_s=0.02)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=0.0)

    def stats_field(port, key):
        line = faultinject.serve_request(port, "ADMIN stats",
                                         timeout=5.0)
        kv = dict(p.split("=") for p in line[3:].split())
        return kv.get(key)

    assert stats_field(fe.port, "free_slots") == "4"
    ts = [threading.Thread(
        target=faultinject.serve_request,
        args=(fe.port, "%d00" % (i + 1),), kwargs={"timeout": 30.0})
        for i in range(2)]
    for t in ts:
        t.start()
    time.sleep(0.2)                     # two slots active
    assert stats_field(fe.port, "free_slots") == "2"
    for t in ts:
        t.join()
    solo = make_frontend()              # no slot backend
    line = faultinject.serve_request(solo.port, "ADMIN stats",
                                     timeout=5.0)
    assert "free_slots" not in line


def test_batch_reload_waits_for_inflight_batch(make_frontend):
    """A reload requested mid-batch is deferred until the in-flight
    batch finishes (the slot caches hold the old model's K/V), then
    every warm session is closed and the next request gets a fresh
    session from the reloaded backend."""
    reloads = []
    sb = faultinject.slot_backend(buckets=(2,), n_new=20,
                                  per_token_s=0.01)
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=0.0, drain_ms=8000.0,
                       reload_fn=lambda: reloads.append(1) or True)
    done = []

    def ask():
        done.append(faultinject.serve_request(fe.port, "100",
                                              timeout=30.0))

    t = threading.Thread(target=ask)
    t.start()
    time.sleep(0.05)                    # batch underway
    assert faultinject.serve_request(
        fe.port, "ADMIN reload", timeout=5.0).startswith("OK")
    assert not reloads                  # deferred: batch still decoding
    t.join()
    assert done[0] == _expect_line(100, 20)
    # the worker honors the flag once the batch drains
    deadline = time.monotonic() + 5.0
    while not reloads and time.monotonic() < deadline:
        time.sleep(0.02)
    assert reloads and sb.closed >= 1
    n_sessions = len(sb.sessions)
    assert faultinject.serve_request(fe.port, "200",
                                     timeout=20.0) == _expect_line(200, 20)
    assert len(sb.sessions) == n_sessions + 1
    stats = fe.drain()
    assert reconciles(stats)


def test_batch_admits_check_answers_err_backend(make_frontend):
    """The slot backend's compatibility check (prompt too long for the
    model) answers a deterministic ERR backend without feeding the
    breaker or poisoning the batch."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3, max_prompt=3)
    fe = make_frontend(None, slot_backend=sb, batch_max=2)
    bad = faultinject.serve_request(fe.port, "1 2 3 4 5", timeout=10.0)
    assert bad.startswith("ERR backend"), bad
    assert fe.breaker.state == "closed" and fe.breaker.consecutive == 0
    assert faultinject.serve_request(fe.port, "100",
                                     timeout=10.0) == _expect_line(100, 3)
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["errors"] == 1 and stats["served"] == 1


def test_batch_occupancy_metrics_honest_weighted_mean(make_frontend):
    """The occupancy series is a per-iteration account, not a last-write
    gauge: iterations/slot-iterations counters land in telemetry and
    the weighted mean matches the fake backend's journal exactly."""
    reg = telemetry._Registry()
    reg.enable()
    sb = faultinject.slot_backend(buckets=(2,), n_new=4,
                                  per_token_s=0.002)
    orig = telemetry._REG
    telemetry._REG = reg
    try:
        fe = make_frontend(None, slot_backend=sb, batch_max=2,
                           batch_window_ms=40.0)
        resps = faultinject.serve_flood(fe.port, ["100", "200"],
                                        timeout=20.0)
        assert all(r for r in resps)
        fe.drain()
    finally:
        telemetry._REG = orig
    snap = reg.metrics_snapshot()
    iters = snap["counters"]["serve.batch_iterations"]
    slots = snap["counters"]["serve.batch_slot_iterations"]
    assert iters > 0 and slots / float(iters) == fe.mean_occupancy()
    assert fe.mean_occupancy() > 1.0
    # /statusz surfaces the mean (the honest form of the gauge)
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=reg)
    try:
        srv.start()
        page = urlopen("http://127.0.0.1:%d/statusz" % srv.port,
                       timeout=5).read().decode()
        assert "mean occupancy" in page
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# decode-datapath observability (doc/observability.md "Decode datapath"):
# the iteration flight ring, /batchz, the KV account, and the convoy
# detector — all jax-free against faultinject.slot_backend
def test_batch_iteration_flight_ring(make_frontend):
    """Every decode iteration lands in the scheduler flight ring with
    its composition (slot/occupant/age), admissions/retirements, queue
    pressure, and step latency — and the ring's lifetime weighted mean
    IS the serve.batch_iterations counter-pair mean (the regression
    the honest-occupancy contract demands)."""
    reg = telemetry._Registry()
    reg.enable()
    sb = faultinject.slot_backend(buckets=(2,), n_new=4,
                                  per_token_s=0.002)
    orig = telemetry._REG
    telemetry._REG = reg
    try:
        fe = make_frontend(None, slot_backend=sb, batch_max=2,
                           batch_window_ms=40.0)
        resps = faultinject.serve_flood(fe.port, ["100", "200", "300"],
                                        timeout=20.0)
        assert all(not r.startswith("ERR") for r in resps), resps
        recs = fe.batch_flight.list()
        assert recs, "iteration ring empty after a batched flood"
        # ring records carry the full per-iteration schema, and are
        # JSON-serializable (the /batchz?json=1 contract)
        json.dumps(recs)
        for it in recs:
            assert it["bucket"] == 2
            assert 1 <= it["occupancy"] <= 2
            assert it["occupancy"] == len(it["slots"])
            assert it["step_ms"] >= 0
            for slot, rid, age in it["slots"]:
                assert 0 <= slot < 2 and age >= 0
        # every request was admitted and retired through the journal
        ads = [a[0] for it in recs for a in it["admitted"]]
        rets = [r[0] for it in recs for r in it["retired"]]
        served = [r["id"] for r in fe.flight.list()]
        assert sorted(ads) == sorted(rets) == sorted(served)
        # iteration ordinals are dense and newest-first in the listing
        ords = [it["iter"] for it in recs]
        assert ords == sorted(ords, reverse=True)
        # the regression: ring lifetime tallies == the counter pair
        fe.drain()
    finally:
        telemetry._REG = orig
    snap = reg.metrics_snapshot()
    assert fe.batch_flight.iterations \
        == snap["counters"]["serve.batch_iterations"]
    assert fe.batch_flight.slot_iterations \
        == snap["counters"]["serve.batch_slot_iterations"]
    assert fe.batch_flight.mean_occupancy() == fe.mean_occupancy()


def test_batch_flight_records_scheduling_coordinates(make_frontend):
    """Flight records carry bucket / slot / iterations ([first, last]
    step ordinals) next to occupancy_at_dispatch: two coalesced
    requests have overlapping ranges in the same bucket — the
    who-shared-my-decode join /requestz readers use, no ring needed."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=4,
                                  per_token_s=0.002)
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=2,
                             batch_window_ms=0.0, drain_ms=2000.0)
    done = [fe.submit("%d00 7" % (i + 1), lambda t: None)
            for i in range(2)]
    fe.start()
    for ev in done:
        assert ev.wait(10.0)
    recs = fe.flight.list()
    assert len(recs) == 2
    for r in recs:
        assert r["bucket"] == 2 and r["slot"] in (0, 1)
        lo, hi = r["iterations"]
        assert 1 <= lo <= hi
    (a_lo, a_hi), (b_lo, b_hi) = (r["iterations"] for r in recs)
    assert max(a_lo, b_lo) <= min(a_hi, b_hi), \
        "coalesced requests must share step iterations"
    assert recs[0]["slot"] != recs[1]["slot"]
    fe.drain()
    # an n_new == 1 request finishes at prefill: it never shares a
    # decode pass, so its iterations field is honestly null — and its
    # admission/retirement still reaches the ring as a NON-stepped
    # flush record (out of the occupancy tallies, never misattributed
    # to a later decode iteration)
    sb1 = faultinject.slot_backend(buckets=(2,), n_new=1)
    fe1 = servd.ServeFrontend(None, slot_backend=sb1, drain_ms=2000.0)
    fe1.start()
    fe1.listen(0)
    assert faultinject.serve_request(fe1.port, "100",
                                     timeout=10.0) == "101"
    assert fe1.flight.list()[0]["iterations"] is None
    deadline = time.monotonic() + 5.0
    while not len(fe1.batch_flight) and time.monotonic() < deadline:
        time.sleep(0.01)
    flush = fe1.batch_flight.list()[0]
    assert flush["stepped"] == 0 and flush["step_ms"] is None
    assert [a[0] for a in flush["admitted"]] == ["1"]
    assert [r[0] for r in flush["retired"]] == ["1"]
    assert fe1.batch_flight.iterations == 0    # no decode pass ran
    fe1.drain()


def test_batchz_endpoint_kv_account_and_decode_metrics(make_frontend):
    """/batchz renders the scheduler ring + KV account (HTML and
    ?json=1), /metrics carries the cxxnet_decode_* families
    Prometheus-valid, and the /metrics?json=1 federation feed carries
    the batch account — against the fake backend's deterministic
    geometry (l_max x kv_row_bytes per slot)."""
    reg = telemetry._Registry()
    reg.enable()
    sb = faultinject.slot_backend(buckets=(2, 4), n_new=30,
                                  per_token_s=0.01, l_max=64,
                                  kv_row_bytes=100)
    orig = telemetry._REG
    telemetry._REG = reg
    srv = None
    try:
        fe = make_frontend(None, slot_backend=sb, batch_max=4,
                           batch_window_ms=0.0, drain_ms=8000.0)
        srv = statusd.StatusServer(0, host="127.0.0.1",
                                   registry=reg).start()
        srv.batch = fe
        srv.flight = fe.flight
        base = "http://127.0.0.1:%d" % srv.port
        ts = [threading.Thread(
            target=faultinject.serve_request,
            args=(fe.port, "%d00" % (i + 1),), kwargs={"timeout": 30.0})
            for i in range(2)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + 5.0
        while fe.batch_flight.iterations < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = json.loads(urlopen(base + "/batchz?json=1",
                                  timeout=5).read())
        # the fake geometry: one warm 2-slot session, 64 rows x 100
        # bytes per slot; both slots decoding
        assert snap["buckets"]["2"]["warm"] == 1
        assert snap["buckets"]["2"]["kv_bytes"] == 2 * 64 * 100
        assert snap["kv_bytes"] == 2 * 64 * 100
        assert snap["buckets"]["2"]["active"] == 2
        assert snap["kv_live_pct"] is not None \
            and 0 < snap["kv_live_pct"] <= 100
        assert snap["flight"], "ring missing from /batchz?json=1"
        page = urlopen(base + "/batchz", timeout=5).read().decode()
        assert "decode batch scheduler" in page and "buckets" in page
        m = urlopen(base + "/metrics", timeout=5).read().decode()
        for line in m.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert 'cxxnet_decode_kv_bytes{process="0",bucket="2"} %d' \
            % (2 * 64 * 100) in m
        assert "cxxnet_decode_kv_live_pct" in m
        assert "cxxnet_decode_convoy" in m
        assert "cxxnet_serve_queue_age_seconds_bucket" in m
        feed = json.loads(urlopen(base + "/metrics?json=1",
                                  timeout=5).read())
        assert feed["batch"]["kv_bytes"] == 2 * 64 * 100
        for t in ts:
            t.join()
        fe.drain()
    finally:
        if srv is not None:
            srv.stop()
        telemetry._REG = orig
    # solo processes 404 (the endpoint names its wiring)
    srv2 = statusd.StatusServer(0, host="127.0.0.1").start()
    try:
        urlopen("http://127.0.0.1:%d/batchz" % srv2.port, timeout=5)
        raise AssertionError("/batchz without a frontend should 404")
    except HTTPError as e:
        assert e.code == 404
    finally:
        srv2.stop()


def test_trace_request_merges_slot_gantt_lanes(make_frontend):
    """/trace?request=<id> on a batching replica renders the request's
    scheduler iterations as slot-Gantt lanes: one lane per decode
    slot, bars naming each occupant — the batchmate's id appears in
    the straggler's trace."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  per_token_s=0.005, long_for={100},
                                  long_n_new=12)
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=40.0, drain_ms=8000.0)
    resps = faultinject.serve_flood(fe.port, ["100", "200"],
                                    timeout=20.0)
    assert all(not r.startswith("ERR") for r in resps), resps
    strag = next(r for r in fe.flight.list()
                 if r["tokens_out"] == 12)
    mate = next(r for r in fe.flight.list() if r["tokens_out"] == 3)
    iters = fe.batch_flight.for_request(strag["id"])
    assert iters and iters == sorted(iters, key=lambda i: i["iter"])
    trace = telemetry.request_chrome_trace(strag, batch_iters=iters)
    lanes = [t["args"]["name"] for t in trace["traceEvents"]
             if t.get("name") == "thread_name"]
    assert any(str(n).startswith("batch slot") for n in lanes), lanes
    bars = [t for t in trace["traceEvents"]
            if t.get("tid", 0) >= 10 and t["ph"] == "X"]
    occupants = {b["args"]["occupant"] for b in bars}
    assert strag["id"] in occupants and mate["id"] in occupants, \
        (occupants, strag["id"], mate["id"])
    # and each bar names the iteration range it covers
    assert all(".." in b["args"]["iterations"] for b in bars)
    fe.drain()


def test_admin_stats_batch_buckets(make_frontend):
    """ADMIN stats reports batch_buckets plus per-bucket warm/active
    counts next to free_slots — the per-bucket load signal routerd
    parses onto /fleetz. Solo frontends omit the whole family."""
    sb = faultinject.slot_backend(buckets=(2, 4), n_new=20,
                                  per_token_s=0.02)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=0.0, drain_ms=8000.0)

    def stats(port):
        line = faultinject.serve_request(port, "ADMIN stats",
                                         timeout=5.0)
        return dict(p.split("=") for p in line[3:].split())

    st = stats(fe.port)
    assert st["batch_buckets"] == "2"
    assert st["bucket.2.warm"] == "0" and st["bucket.4.warm"] == "0"
    ts = [threading.Thread(
        target=faultinject.serve_request,
        args=(fe.port, "%d00" % (i + 1),), kwargs={"timeout": 30.0})
        for i in range(2)]
    for t in ts:
        t.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = stats(fe.port)
        if st.get("bucket.2.active") == "2":
            break
        time.sleep(0.02)
    assert st["bucket.2.warm"] == "1" and st["bucket.2.active"] == "2"
    assert st["bucket.4.warm"] == "0" and st["bucket.4.active"] == "0"
    for t in ts:
        t.join()
    fe.drain()
    solo = make_frontend()
    line = faultinject.serve_request(solo.port, "ADMIN stats",
                                     timeout=5.0)
    assert "batch_buckets" not in line and "bucket." not in line


def test_convoy_chaos_straggler_pins_bucket(make_frontend):
    """THE convoy acceptance: two stragglers pin a full 2-slot bucket
    while short requests queue at zero free slots — EXACTLY ONE
    decode_convoy latch transition fires (plus its clearing
    transition), the serve.convoys episode counter reads 1, queue-age
    observations land in serve.queue_age, and ZERO requests are lost
    (every one served exactly). Runs under CXXNET_LOCKRANK=1 (the
    suite's autouse fixture)."""
    reg = telemetry._Registry()
    reg.enable()
    sb = faultinject.slot_backend(buckets=(2,), n_new=3,
                                  per_token_s=0.004,
                                  long_for={100, 200}, long_n_new=40)
    orig = telemetry._REG
    telemetry._REG = reg
    try:
        # queue BEFORE start() (the queue-before-start discipline):
        # the stragglers are popped first DETERMINISTICALLY, pin the
        # whole bucket, and the shorts wait behind them — a TCP flood
        # would race arrival order, and shorts served before both
        # stragglers board would leave the queue empty (no convoy)
        fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=2,
                                 batch_window_ms=0.0, convoy_iters=8,
                                 drain_ms=15000.0)
        replies = {}

        def mkreply(i):
            def reply(text):
                replies.setdefault(i, []).append(text)
            return reply

        lines = ["100", "200", "300", "400", "500"]
        events = [fe.submit(line, mkreply(i))
                  for i, line in enumerate(lines)]
        fe.start()
        for ev in events:
            assert ev.wait(40.0), "request never answered"
        for i, texts in sorted(replies.items()):
            assert len(texts) == 1, (i, texts)
        assert replies[0][0] == _expect_line(100, 40)
        assert replies[1][0] == _expect_line(200, 40)
        for i, first in enumerate((300, 400, 500), start=2):
            assert replies[i][0] == _expect_line(first, 3), \
                (i, replies[i])
        fe.drain()
    finally:
        telemetry._REG = orig
    evs = [e for e in reg.events() if e.get("ev") == "decode_convoy"]
    latches = [e for e in evs if e.get("convoy") == 1]
    clears = [e for e in evs if e.get("convoy") == 0]
    assert len(latches) == 1, evs
    assert latches[0]["bucket"] == 2
    assert latches[0]["age_iters"] >= 8
    assert latches[0]["queue_depth"] >= 1
    assert latches[0]["pinned"] in [r["id"] for r in fe.flight.list()]
    # the latch CLEARED when the stragglers retired and the queue
    # drained into the freed slots — a log must not end latched
    assert len(clears) == 1 and clears[0]["episode_iters"] >= 1
    assert fe._convoy is False and fe._convoys == 1
    snap = reg.metrics_snapshot()
    assert snap["counters"]["serve.convoys"] == 1
    # the queue waited at zero free slots: the age histogram saw it
    assert snap["hists"]["serve.queue_age"]["count"] >= 1
    # and the ring marked the convoy iterations
    assert any(it["convoy"] for it in fe.batch_flight.list())
    stats = fe.drain()
    assert reconciles(stats)


def test_batch_snapshot_kv_live_tracks_decode_progress(make_frontend):
    """kv_live_pct measures REAL cache extent: it grows as a sequence
    decodes (more live rows) and collapses to 0 when every slot
    retires (the dead-slot waste paged KV will reclaim) — while
    kv_bytes stays at the warm session's full allocation."""
    sb = faultinject.slot_backend(buckets=(2,), n_new=30,
                                  per_token_s=0.01, l_max=64,
                                  kv_row_bytes=10)
    fe = make_frontend(None, slot_backend=sb, batch_max=2,
                       batch_window_ms=0.0, drain_ms=8000.0)
    t = threading.Thread(target=faultinject.serve_request,
                         args=(fe.port, "100 2 3"),
                         kwargs={"timeout": 30.0})
    t.start()
    deadline = time.monotonic() + 5.0
    first = None
    while time.monotonic() < deadline:
        snap = fe.batch_snapshot()
        if snap["buckets"]["2"]["active"] == 1:
            first = snap
            break
        time.sleep(0.005)
    assert first is not None, "sequence never observed mid-decode"
    t.join()
    # drained: the warm allocation persists, the live share is gone
    deadline = time.monotonic() + 5.0
    while fe.batch_snapshot()["buckets"]["2"]["active"] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    after = fe.batch_snapshot()
    assert after["kv_bytes"] == first["kv_bytes"] == 2 * 64 * 10
    assert after["kv_live_bytes"] == 0 and after["kv_live_pct"] == 0.0
    assert after["slot_waste_pct"] == 100.0
    assert first["kv_live_bytes"] > 0
    assert fe.decode_kv_bytes() == 2 * 64 * 10
    # drain closes the warm sessions and ZEROES the account: a scrape
    # during the shutdown window (or a later task reading the perf
    # ledger's decode hook) must never see freed memory as allocated
    fe.drain()
    deadline = time.monotonic() + 5.0
    while fe.decode_kv_bytes() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert fe.decode_kv_bytes() == 0
    assert fe.batch_snapshot()["kv_bytes"] == 0


def test_report_batch_scheduler_section(tmp_path, capsys):
    """telemetry_report's batch-scheduler section: per-bucket weighted
    occupancy reconstructed from the transition-only batch_iteration
    events (composition holds constant across the gap to the next
    event — gap-weighting is exact), waste vs the bucket size,
    admission-latency percentiles, and the convoy episode account —
    with the log-ends-latched unresolved flag."""
    evs = [
        {"ev": "meta", "pid": 1, "t0_wall": 0.0},
        {"ev": "batch_iteration", "iter": 1, "bucket": 4,
         "occupancy": 2, "occupancy_after": 2, "queue_depth": 0,
         "step_ms": 3.0, "admitted": ["1", "2"], "retired": [],
         "ts": 1.0},
        {"ev": "batch_iteration", "iter": 5, "bucket": 4,
         "occupancy": 4, "occupancy_after": 4, "queue_depth": 2,
         "step_ms": 3.0, "admitted": ["3", "4"], "retired": [],
         "ts": 2.0},
        # iteration 9 stepped 3 sequences and retired one: occupancy
        # (what decoded) and occupancy_after (what is left) differ —
        # the post-retirement gap must weigh at the AFTER composition
        {"ev": "batch_iteration", "iter": 9, "bucket": 4,
         "occupancy": 3, "occupancy_after": 2, "queue_depth": 0,
         "step_ms": 3.0, "admitted": [], "retired": ["1"], "ts": 3.0},
        # a non-stepped flush (an n_new==1 admission that finished at
        # prefill): journaled, but NOT a decode iteration
        {"ev": "batch_iteration", "iter": 9, "bucket": 4,
         "occupancy": 0, "occupancy_after": 0, "stepped": 0,
         "queue_depth": 0, "step_ms": None, "admitted": ["9"],
         "retired": ["9"], "ts": 3.5},
        {"ev": "decode_convoy", "convoy": 1, "bucket": 4,
         "pinned": "2", "slot": 1, "age_iters": 70,
         "queue_depth": 3, "ts": 4.0},
        {"ev": "serve_request_done", "req": "1", "outcome": "served",
         "tokens": 4, "total_s": 0.1, "queue_wait_s": 0.02,
         "dispatch_s": 0.001, "prefill_s": 0.01, "decode_s": 0.05,
         "recompiles": 0, "ts": 5.0},
    ]
    log = tmp_path / "batch.jsonl"
    log.write_text("".join(json.dumps(e) + "\n" for e in evs))
    rc = telemetry_report.main([str(log), "--json"])
    agg = json.loads(capsys.readouterr().out)
    assert rc == 0
    bt = agg["batch"]
    # exact reconstruction: iter 1 at occ 2 + iters 2..4 at after 2
    # (8), iter 5 at 4 + 6..8 at 4 (16), iter 9 at 3 (3) -> 9
    # iterations, 27 slot-iterations, mean 3.0; the flush event adds
    # its admitted/retired counts but NO iterations
    b4 = bt["buckets"]["4"]
    assert b4["iterations"] == 9
    assert b4["slot_iterations"] == 27
    assert b4["mean_occupancy"] == 3.0
    assert b4["waste_pct"] == 25.0
    assert b4["admitted"] == 5 and b4["retired"] == 2
    assert bt["admission_p99_ms"] == 20.0
    assert bt["convoy_episodes"] == 1
    # the log ENDS with the convoy latched: flagged unresolved
    assert bt["convoy_unresolved"] == ["0"]
    rc = telemetry_report.main([str(log)])
    out = capsys.readouterr().out
    assert rc == 0 and "== batch scheduler" in out
    assert "convoy episodes: 1" in out and "UNRESOLVED" in out
    assert "pinned=2" in out


# ----------------------------------------------------------------------
# ----------------------------------------------------------------------
# multi-tenant weighted-fair QoS (doc/serving.md "Multi-tenant QoS")
TEN = "noisy:1,victim:4"


def park_worker_and_fill(fe, port, tenant, n, first="9"):
    """Occupy the worker with one request, then queue ``n`` more from
    ``tenant`` — deterministically (the occupy_and_fill discipline:
    waiting on counters alone races the worker's pop)."""
    socks = []
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(("TENANT %s %s\n" % (tenant, first)).encode())
    socks.append(s)
    deadline = time.monotonic() + 5.0
    while not fe._inflight and time.monotonic() < deadline:
        time.sleep(0.005)
    assert fe._inflight, "worker never occupied"
    for i in range(n):
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(("TENANT %s %d\n" % (tenant, 10 + i)).encode())
        socks.append(s)
        want = i + 1
        deadline = time.monotonic() + 5.0
        while len(fe._q) < want and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(fe._q) == want, "queue fill stalled at %d" % len(fe._q)
    return socks


def test_tenant_prefix_parse_validation_and_compat(make_frontend):
    """The TENANT wire contract: adopted + accounted, composes with
    TRACE and DEADLINE (TRACE first), malformed/unknown ids are ERR
    proto (deterministic, never dispatched), and prefix-less clients
    ride the default tenant unchanged — the downgrade acceptance."""
    fe = make_frontend(tenants=TEN, tenant_default="victim")
    port = fe.port
    assert faultinject.serve_request(port, "TENANT noisy 1 2") == "2 3"
    assert faultinject.serve_request(
        port, "TRACE t-1 TENANT noisy DEADLINE 5000 7") == "8"
    assert fe.flight.get("t-1")["tenant"] == "noisy"
    # prefix-less clients are the default tenant — wire unchanged
    assert faultinject.serve_request(port, "5") == "6"
    assert fe.flight.list()[0]["tenant"] == "victim"
    for bad in ("TENANT", "TENANT bad!id 1", "TENANT %s 1" % ("x" * 33),
                "TENANT ghost 1"):
        resp = faultinject.serve_request(port, bad)
        assert resp.startswith("ERR proto tenant"), (bad, resp)
    assert faultinject.serve_request(
        port, "TENANT noisy").startswith("ERR empty")
    # TENANT + ADMIN composes (prefixes stripped first); the stats line
    # carries the per-tenant books
    resp = faultinject.serve_request(port, "TENANT noisy ADMIN stats")
    assert resp.startswith("OK ")
    assert "tenant.noisy.accepted=" in resp
    assert "tenant.victim.served=" in resp
    ts = fe.tenant_stats()
    assert ts["noisy"]["accepted"] == 2 and ts["noisy"]["served"] == 2
    assert ts["victim"]["accepted"] == 1
    stats = fe.drain()
    assert reconciles(stats)
    for t, st in fe.tenant_stats().items():
        assert st["accepted"] == (st["served"] + st["errors"]
                                  + st["shed"] + st["deadline"]), (t, st)


def test_tenant_fair_share_shed_and_eviction(make_frontend):
    """The capacity-fairness contract: a borrower over its fair share
    is shed with the ``tenant`` detail token (NOT retryable — the
    policy holds fleet-wide), and an under-share arrival EVICTS the
    borrower's newest queued request instead of being shed itself."""
    from cxxnet_tpu.utils import routerd
    release = threading.Event()

    def slow(toks, seq):
        release.wait(10.0)
        return [t + 1 for t in toks]

    fe = make_frontend(slow, queue_size=4, tenants=TEN,
                       tenant_default="victim")
    port = fe.port
    socks = park_worker_and_fill(fe, port, "noisy", 4)
    try:
        assert fe._q.shares == {"noisy": 1, "victim": 3}
        # noisy is over its share of a full queue: its arrival sheds
        # with the machine-readable "tenant" verdict, which the router
        # must NOT retry (every replica shares the table)
        resp = faultinject.serve_request(port, "TENANT noisy 99")
        assert resp.startswith("ERR busy tenant"), resp
        assert not routerd.retryable(resp)
        assert fe.flight.list()[0]["shed_at"] == "tenant"
        # a victim arrival is UNDER its share: admitted by evicting the
        # borrower's newest queued request (charged to noisy)
        got = []
        done = fe.submit("TENANT victim 50", got.append)
        assert done is not None, "victim was shed instead of admitted"
        assert len(fe._q) == 4 and fe._q.depth("victim") == 1
        ts = fe.tenant_stats()
        assert ts["noisy"]["shed"] == 2      # the arrival + the evictee
        assert ts["victim"]["shed"] == 0
        release.set()
        done.wait(5.0)
        assert got == ["51"]
    finally:
        release.set()
        stats = fe.drain()
        for s in socks:
            s.close()
    assert reconciles(stats)
    for t, st in fe.tenant_stats().items():
        assert st["accepted"] == (st["served"] + st["errors"]
                                  + st["shed"] + st["deadline"]), (t, st)


def test_tenant_weighted_fair_scheduling_order(make_frontend):
    """The stride scheduler: with both tenants backlogged, a weight-4
    tenant gets 4 dispatches for every 1 of a weight-1 tenant — the
    worker pop order interleaves by weight, not arrival order."""
    order = []
    release = threading.Event()

    def recording(toks, seq):
        release.wait(10.0)
        order.append(toks[0])
        return [t + 1 for t in toks]

    fe = make_frontend(recording, queue_size=16, tenants=TEN,
                       tenant_default="victim")
    port = fe.port
    # park the worker, then queue noisy FIRST (arrival order would
    # serve all noisy before any victim)
    socks = park_worker_and_fill(fe, port, "noisy", 4)
    try:
        for i in range(4):
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            s.sendall(("TENANT victim %d\n" % (20 + i)).encode())
            socks.append(s)
        deadline = time.monotonic() + 5.0
        while len(fe._q) < 8 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(fe._q) == 8
        release.set()
        deadline = time.monotonic() + 5.0
        while len(order) < 9 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(order) == 9, order
        # order[0] is the parked noisy request (mid-dispatch when the
        # backlog formed); among the next 5 pops at least 4 are victim
        # (weight 4 vs 1), all queued AFTER every noisy request
        victims = [t for t in order[1:6] if t >= 20]
        assert len(victims) >= 4, order
    finally:
        release.set()
        fe.drain()
        for s in socks:
            s.close()


def test_tenant_slo_isolation(make_frontend):
    """A noisy tenant's sheds burn the NOISY error budget; the victim's
    own tracker holds at 0 — per-tenant SLO floors from the existing
    SLOTracker, per tenant."""
    release = threading.Event()

    def slow(toks, seq):
        release.wait(10.0)
        return list(toks)

    slo_t = {t: statusd.SLOTracker(availability=0.999, min_requests=3,
                                   min_bad=3, window_s=60.0)
             for t in ("noisy", "victim")}
    fe = make_frontend(slow, queue_size=2, tenants=TEN,
                       tenant_default="victim", slo_tenants=slo_t)
    port = fe.port
    # worker parked on noisy, queue FULL of noisy borrowings
    socks = park_worker_and_fill(fe, port, "noisy", 2)
    try:
        for _ in range(3):
            # every further noisy arrival is over-share on a full
            # queue: shed, charged to noisy's own error budget
            resp = faultinject.serve_request(port, "TENANT noisy 7")
            assert resp.startswith("ERR busy tenant"), resp
        assert slo_t["noisy"].snapshot()["alert"] == 1
        assert slo_t["victim"].snapshot()["alert"] == 0
    finally:
        release.set()
        fe.drain()
        for s in socks:
            s.close()


def test_servd_selftest():
    assert servd.selftest() == 0


# -- paged KV block pool: exhaustion is a deterministic queue-wait ----
# (doc/performance.md "Decode KV cache"; CXXNET_LOCKRANK=1 via the
# suite's autouse fixture — the admission gate reads the allocator
# outside servd's locks, and these chaos floods prove no inversion)


def test_paged_kv_exhaustion_deterministic_queue_wait(make_frontend):
    """THE pool-exhaustion acceptance: a flood whose sequences need 2
    blocks each over a 4-block pool can run at most TWO concurrent
    sequences however many slots the bucket has — the gather gate
    defers the rest in FIFO order (deterministic queue-wait: zero
    lost, zero errors, zero device faults, not one KVPoolExhausted
    raised), retirements return blocks mid-decode and the queue
    drains into them, and the /batchz + ADMIN stats + flight-ring
    block columns publish the pressure."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                  per_token_s=0.002,
                                  kv_pool_blocks=4, kv_block_tokens=4)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=0.0, drain_ms=15000.0)
    lines = ["%d %d %d %d" % (10 * i, 10 * i + 1, 10 * i + 2,
                              10 * i + 3) for i in range(1, 9)]
    resps = faultinject.serve_flood(fe.port, lines, timeout=30.0)
    for i, r in enumerate(resps):
        assert r == _expect_line(10 * (i + 1), 4), (i, r)
    # the gate made exhaustion unreachable: the allocator never even
    # SAW an over-ask (admissions deferred in the queue instead)
    assert sb.alloc.alloc_failures == 0
    assert sb.alloc.free_blocks == sb.alloc.usable
    sb.alloc.check()
    # never more concurrent sequences than the pool covers: every
    # iteration record's occupancy respects the BLOCK bound (2), not
    # the slot bound (4), and the ring carries the block columns
    recs = fe.batch_flight.list()
    assert recs
    for r in recs:
        assert r["occupancy"] <= 2, r
        assert r["blocks_total"] == 4 and 0 <= r["blocks_free"] <= 4
    snap = fe.batch_snapshot()
    assert snap["pool"]["blocks_total"] == 4
    assert snap["pool"]["blocks_free"] == 4
    st = dict(kv.split("=") for kv in faultinject.serve_request(
        fe.port, "ADMIN stats", timeout=5.0).split()[1:])
    assert st["kv_blocks_total"] == "4"
    assert st["kv_blocks_free"] == "4"
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 8


def test_paged_kv_exhaustion_requeue_path():
    """With the gather-budget hooks disarmed (a backend that cannot
    predict demand), admission reaches the allocator and raises
    KVPoolExhausted — the dispatcher must REQUEUE to the head (a
    deterministic retry after the next retirement), never answer ERR,
    never count a breaker failure, and still serve every request
    exactly."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                  per_token_s=0.002,
                                  kv_pool_blocks=4, kv_block_tokens=4,
                                  kv_gate=False)
    # queue BEFORE start(): the first gather then holds four requests
    # against a pool of two sequences (2 blocks each of 4), so the
    # allocator refuses DETERMINISTICALLY. A TCP flood raced arrival: on
    # a loaded host the six trickled in one at a time, each retired
    # before the next came, and nothing was ever refused
    fe = servd.ServeFrontend(None, slot_backend=sb, batch_max=4,
                             batch_window_ms=0.0, drain_ms=15000.0)
    replies = {}

    def mkreply(i):
        def reply(text):
            replies.setdefault(i, []).append(text)
        return reply

    lines = ["%d %d %d %d" % (10 * i, 10 * i + 1, 10 * i + 2,
                              10 * i + 3) for i in range(1, 7)]
    events = [fe.submit(line, mkreply(i)) for i, line in enumerate(lines)]
    fe.start()
    for ev in events:
        assert ev.wait(30.0), "request never answered"
    for i in range(6):
        assert replies[i] == [_expect_line(10 * (i + 1), 4)], \
            (i, replies[i])
    # the allocator DID refuse some admissions (the path under test)…
    assert sb.alloc.alloc_failures > 0
    # …and every refusal became a requeue: no error class, no breaker
    # count, no session closed mid-serve, nothing lost
    assert sb.closed == 0
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 6
    assert stats["errors"] == 0 and stats["shed"] == 0
    assert sb.alloc.free_blocks == sb.alloc.usable
    sb.alloc.check()


def test_paged_kv_tenant_fair_queue_gate_and_requeue(make_frontend):
    """Paged KV composes with the PR 12 tenant fair queue: the gather
    gate budgets the queue's ``peek()`` (the virtual-time head — the
    fair queue is not subscriptable), and the defer path requeues
    through its ``appendleft`` (tenant-head insert, stride refunded).
    Both paths flood two tenants over a pool that can hold only two
    concurrent sequences: every request serves exactly, zero errors,
    zero lost, the worker survives, the pool drains back to full."""
    for gate in (True, False):
        sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                      per_token_s=0.002,
                                      kv_pool_blocks=4,
                                      kv_block_tokens=4, kv_gate=gate)
        fe = make_frontend(None, slot_backend=sb, batch_max=4,
                           batch_window_ms=0.0, drain_ms=15000.0,
                           tenants=TEN, tenant_default="victim")
        lines = ["TENANT %s %d %d %d %d"
                 % (("noisy", "victim")[i % 2], 10 * i, 10 * i + 1,
                    10 * i + 2, 10 * i + 3) for i in range(1, 7)]
        resps = faultinject.serve_flood(fe.port, lines, timeout=30.0)
        for i, r in enumerate(resps):
            assert r == _expect_line(10 * (i + 1), 4), (gate, i, r)
        if gate:
            # the budgeted gather never over-admits: the allocator
            # never saw an over-ask even through the fair queue's
            # virtual-time pop order
            assert sb.alloc.alloc_failures == 0
        else:
            # the allocator DID refuse — every refusal requeued via
            # _FairQueue.appendleft (the pre-fix AttributeError path)
            assert sb.alloc.alloc_failures > 0
        assert sb.closed == 0
        stats = fe.drain()
        assert reconciles(stats)
        assert stats["accepted"] == stats["served"] == 6
        assert stats["errors"] == 0 and stats["shed"] == 0
        assert sb.alloc.free_blocks == sb.alloc.usable
        sb.alloc.check()


# -- retained conversation cache: never-OOM memory governance ---------
# (doc/robustness.md "Memory governance"; PR 18. CXXNET_LOCKRANK=1 via
# the autouse fixture — eviction runs under the rank-15 kvblocks.evict
# lock inside the admission path, and these floods prove no inversion)


def _books_reconcile(alloc):
    """The retained invariant, asserted at a quiescent instant: every
    block is live, retained, or free — and nothing else."""
    assert (alloc.live_blocks + alloc.retained_blocks
            + alloc.free_blocks) == alloc.usable
    alloc.check()


def _laws_hold():
    """Sweep the process-global conservation-law auditor and assert no
    serving law latched: the chaos ran with the books provably
    balanced. A latch is sticky, so a single mid-storm violation
    anywhere in the flood fails here even if the books reconcile again
    by the time the assert runs."""
    telemetry.audit_sweep()
    broken = telemetry.auditor().snapshot()["broken"]
    assert not set(broken) & {"serve.books", "serve.tenant_books",
                              "kv.blocks"}, broken


def test_retained_kv_exhaustion_chaos_flood(make_frontend):
    """THE never-OOM acceptance: mixed multi-turn + one-shot traffic
    floods a pool far too small to hold every conversation's cache.
    Turn N+1 of each conversation extends turn N's prompt (the
    retained-revival path: refcount 0 -> 1), one-shot noise churns the
    retained pool through LRU eviction, and true exhaustion (live
    blocks alone exceeding the pool) still defers deterministically.
    Invariants: zero OOM (no KVPoolExhausted escapes — the gate +
    evict-before-defer absorb everything), zero deadlock (the flood
    completes under CXXNET_LOCKRANK=1), zero silent losses (every
    request answered exactly once, token-exact — an evicted-then-
    revived conversation recomputes, never serves stale KV), and the
    books reconcile: live + retained + free == pool, always."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                  per_token_s=0.002,
                                  kv_pool_blocks=12, kv_block_tokens=4,
                                  kv_retained_frac=1.0)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=0.0, drain_ms=15000.0)
    results = {}

    def convo_client(c):
        # a live multi-turn client: turn k+1 is sent the moment turn
        # k answers, its prompt one block longer — the just-retired
        # chain is the NEWEST retained mass, so LRU eviction recycles
        # the noise first and the head of a chain last (leaf-first
        # eviction order): revival is what the design promises here
        out = []
        for turn in range(3):
            p = list(range(100 * c + 1, 100 * c + 5 + 4 * turn))
            line = " ".join(map(str, p))
            out.append((line, faultinject.serve_request(
                fe.port, line, timeout=60.0)))
        results["convo%d" % c] = out

    def noise_client(z):
        # one-shot churn: distinct prompts that only ever park and
        # get evicted — the traffic that would OOM an unguarded pool
        out = []
        for i in range(3):
            t0 = 1000 * z + 10 * i + 1
            line = " ".join(str(t0 + k) for k in range(4))
            out.append((line, faultinject.serve_request(
                fe.port, line, timeout=60.0)))
        results["noise%d" % z] = out

    clients = [threading.Thread(target=convo_client, args=(c,))
               for c in (1, 2, 3)]
    clients += [threading.Thread(target=noise_client, args=(z,))
                for z in (1, 2)]
    for t in clients:
        t.start()
    # the conservation-law auditor sweeps CONTINUOUSLY through the
    # chaos (ISSUE 19 acceptance: books_broken never latches under the
    # eviction storm) — a mid-flight inconsistency a law cannot prove
    # persistent stays inconclusive by design, so any latch IS real
    deadline = time.monotonic() + 120.0
    while any(t.is_alive() for t in clients):
        telemetry.audit_sweep()
        for t in clients:
            t.join(0.05)
        assert time.monotonic() < deadline, \
            "chaos client wedged (deadlock?)"
    _laws_hold()
    for name, out in sorted(results.items()):
        for line, r in out:
            t0 = int(line.split()[0])
            assert r == _expect_line(t0, 4), (name, line, r)
    # the chaos DID exercise the governance, not a comfortable pool:
    # conversations revived retained blocks AND the one-shot churn
    # forced retained evictions
    assert sb.alloc.retained_hits > 0
    assert sb.alloc.retained_hit_tokens > 0
    assert sb.alloc.retained_evictions > 0
    # zero OOM, zero device faults, zero silent losses
    assert sb.closed == 0
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 3 * 3 + 2 * 3
    assert stats["errors"] == 0 and stats["shed"] == 0
    # quiescent books: nothing live, everything parked or free
    assert sb.alloc.live_blocks == 0
    assert sb.alloc.available_blocks == sb.alloc.usable
    sb.alloc.check()


def test_retained_eviction_storm_and_revive_race(make_frontend):
    """The chaos knobs: an eviction storm drains the WHOLE retained
    pool between a gather-time match and its admission, and the
    revive-race knob evicts the LRU leaf before every admission — the
    block a request hoped to revive is exactly the one recycled.
    Admissions must recompute instead of crash, replies stay
    token-exact, and the books reconcile after every round."""
    for knobs in ({"kv_evict_storm": 3}, {"kv_revive_race": True},
                  {"kv_evict_storm": 2, "kv_revive_race": True}):
        sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                      per_token_s=0.002,
                                      kv_pool_blocks=8,
                                      kv_block_tokens=4,
                                      kv_retained_frac=1.0, **knobs)
        fe = make_frontend(None, slot_backend=sb, batch_max=4,
                           batch_window_ms=0.0, drain_ms=15000.0)
        base = list(range(1, 5))
        for turn in range(3):
            # two conversations re-serving the SAME growing prompt +
            # one-shot churn: every admission races the eviction knobs
            lines = [" ".join(map(str, base + list(range(5, 5 + 4 * turn)))),
                     " ".join(map(str, base + list(range(50, 54)))),
                     " ".join(str(9000 + 100 * turn + k)
                              for k in range(4))]
            resps = faultinject.serve_flood(fe.port, lines,
                                            timeout=60.0)
            for line, r in zip(lines, resps):
                t0 = int(line.split()[0])
                assert r == _expect_line(t0, 4), (knobs, turn, line, r)
            _books_reconcile(sb.alloc)
            _laws_hold()        # no conservation law latched mid-storm
        assert sb.closed == 0
        stats = fe.drain()
        assert reconciles(stats)
        assert stats["errors"] == 0 and stats["shed"] == 0
        assert stats["accepted"] == stats["served"] == 9
        assert sb.alloc.live_blocks == 0
        sb.alloc.check()


def test_evict_before_defer_admission(make_frontend):
    """A reservation that the free list cannot cover but free +
    retained CAN must evict and admit — never defer. Sequential
    one-shots fill the retained pool to the brim; a second wave of
    distinct prompts then admits by recycling it: zero alloc_failures
    (the allocator never refused), retained_evictions > 0 (the
    funding), every reply exact."""
    sb = faultinject.slot_backend(buckets=(1,), n_new=4,
                                  kv_pool_blocks=4, kv_block_tokens=4,
                                  kv_retained_frac=1.0)
    fe = make_frontend(None, slot_backend=sb, batch_max=1,
                       batch_window_ms=0.0, drain_ms=15000.0)
    # wave 1: fill retention (each request: 1 registered block parks
    # at retire, 1 scratch block frees) until the cap (4) is reached
    for i in range(1, 5):
        t0 = 10 * i
        line = " ".join(str(t0 + k) for k in range(4))
        assert faultinject.serve_request(fe.port, line,
                                         timeout=30.0) \
            == _expect_line(t0, 4)
    assert sb.alloc.retained_blocks > 0
    retained_before = sb.alloc.retained_blocks
    # wave 2: distinct prompts over a free list too small for them —
    # funded by eviction, not deferred into the queue forever
    for i in range(5, 9):
        t0 = 10 * i
        line = " ".join(str(t0 + k) for k in range(4))
        assert faultinject.serve_request(fe.port, line,
                                         timeout=30.0) \
            == _expect_line(t0, 4)
    assert sb.alloc.alloc_failures == 0
    assert sb.alloc.retained_evictions > 0
    assert sb.alloc.retained_blocks <= sb.alloc.retained_cap
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 8
    _books_reconcile(sb.alloc)


def test_kv_pressure_latch_sheds_retained(make_frontend):
    """The low-headroom pressure latch: when the free list drops under
    kv_pressure_pct percent of the pool, the worker latches
    cxxnet_decode_kv_pressure, sheds retained blocks toward the clear
    threshold through the backend's kv_shed_retained hook, emits ONE
    kv_pressure transition event per edge (hysteresis — no flapping),
    and publishes the latch through /batchz, ADMIN stats and the
    federation feed."""
    sb = faultinject.slot_backend(buckets=(1,), n_new=4,
                                  kv_pool_blocks=8, kv_block_tokens=4,
                                  kv_retained_frac=1.0)
    fe = make_frontend(None, slot_backend=sb, batch_max=1,
                       batch_window_ms=0.0, drain_ms=15000.0,
                       kv_pressure_pct=50.0,
                       kv_pressure_clear_pct=75.0)
    # distinct one-shots park one retained block each: free drops 8 ->
    # 7 -> 6 -> 5 -> 3 (under 50%) -> latch fires, sheds back to >= 6
    for i in range(1, 8):
        t0 = 10 * i
        line = " ".join(str(t0 + k) for k in range(4))
        assert faultinject.serve_request(fe.port, line,
                                         timeout=30.0) \
            == _expect_line(t0, 4)
    assert fe._kv_pressures >= 1
    assert fe._kv_shed_blocks > 0
    assert sb.alloc.retained_evictions > 0
    # hysteresis: after the shed the latch CLEARED (free >= clear_pct)
    snap = fe.batch_snapshot()
    assert snap["pool"]["pressure"] == 0
    assert snap["pool"]["blocks_free"] >= 6
    # the retained sub-fields ride the snapshot for /batchz + bench
    assert "retained_hit_rate" in snap["pool"]
    assert "kv_retained_pct" in snap["pool"]
    # ADMIN stats carries the governance keys (what routerd federates)
    st = dict(kv.split("=") for kv in faultinject.serve_request(
        fe.port, "ADMIN stats", timeout=5.0).split()[1:])
    assert "kv_retained_blocks" in st and "kv_retained_hits" in st
    assert st["kv_pressure"] == "0"
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 7
    _books_reconcile(sb.alloc)


# -- request autopsy on a live flood (utils/autopsy.py; ISSUE 19) -----
def test_autopsy_warm_flood_zero_compile_stall(make_frontend):
    """The autopsy acceptance on a live flood: requests riding the
    warm-up pay the compile cliff (compile_stall > 0 on their
    verdicts), and a warm-bucket flood afterwards attributes EXACTLY
    zero seconds to compile_stall — the classifier must not smear the
    cliff onto requests that rode warm programs. Every verdict tiles
    >= 95% of the request's wall clock."""
    sb = faultinject.slot_backend(buckets=(4,), n_new=4,
                                  per_token_s=0.001, compile_ms=40.0)
    fe = make_frontend(None, slot_backend=sb, batch_max=4,
                       batch_window_ms=0.0, drain_ms=15000.0)
    # warm-up: the first request compiles session + prefill + step
    assert faultinject.serve_request(fe.port, "1 2 3 4",
                                     timeout=30.0) == _expect_line(1, 4)
    warm_rec = fe.flight.list()[0]
    assert warm_rec["compile_stall_s"] > 0
    aut = warm_rec["autopsy"]
    assert aut["causes"]["compile_stall"] > 0
    assert sum(aut["causes"].values()) >= 0.95 * aut["wall_s"] > 0
    # warm flood: the same prompt shape on the warm bucket — the jit-
    # cache twin has seen every key, so zero stall, zero smearing
    lines = [" ".join(str(10 * i + k) for k in range(4))
             for i in range(2, 8)]
    resps = faultinject.serve_flood(fe.port, lines, timeout=30.0)
    for line, r in zip(lines, resps):
        assert r == _expect_line(int(line.split()[0]), 4), (line, r)
    recs = [r for r in fe.flight.list() if r["id"] != warm_rec["id"]]
    assert len(recs) == len(lines)
    for rec in recs:
        aut = rec["autopsy"]
        assert rec["compile_stall_s"] == 0.0
        assert aut["causes"]["compile_stall"] == 0.0       # exactly 0
        assert aut["primary"] != "compile_stall"
        assert sum(aut["causes"].values()) >= 0.95 * aut["wall_s"] > 0
    stats = fe.drain()
    assert reconciles(stats)
    assert stats["accepted"] == stats["served"] == 7
