"""Training-health subsystem: anomaly detection, recovery, watchdog.

A long training run must survive the events that kill or silently corrupt
it in the reference stack: a non-finite loss or gradient (one bad batch,
an overflowing LR), a diverging run (loss spike), a wedged input pipeline
(hung NFS read, dead decode worker), and corrupt data records. Production
frameworks treat all of these as *recoverable* and drive recovery off the
checkpoint machinery (TensorFlow makes user-level checkpoint/restore the
sole fault-tolerance primitive, arxiv 1605.08695 §4.2); PR 2 built the
durable checkpoints, this module makes the stack use them automatically:

* **HealthMonitor** — consumes the per-step health scalars the trainer
  computes INSIDE the jitted step (loss, global grad-norm², non-finite
  gradient element count; nnet/trainer.py ``_make_train_step``). Vectors
  are checked one step LATE: the fetch of step N-1's scalars happens
  after step N was dispatched, so by then the value is resident and the
  host never stalls the device pipeline to look at it. An EMA detector
  additionally flags loss SPIKES (finite divergence). Detected anomalies
  emit ``health_anomaly`` telemetry events.
* **RecoveryPolicy** — the pure-host detect→rollback→skip state machine
  (no jax; ``selftest()`` below simulates it and ``make check`` gates on
  it): on anomaly, roll back to the newest valid checkpoint, quarantine
  the offending (round, batch) window so the replay excludes it
  (``IIterator.skip`` fast-forwards past it), optionally back the LR off
  by ``rollback_backoff`` per retry, and abort with a diagnostic dump
  after ``rollback_max_retries`` consecutive rollbacks.
* **Watchdog** — a daemon thread watching heartbeat channels
  (``beat("train.step")`` from the train loop, ``beat("io.prefetch")``
  from the batch prefetcher): a channel silent past the timeout gets
  all-thread stacks dumped to stderr and a ``watchdog_stall`` telemetry
  event + flush BEFORE any action (``warn``, or ``abort`` = exit code
  70), so a hung run always leaves a diagnosis behind.

learn_task.py wires these behind the conf keys ``health_monitor=1``,
``nonfinite_action=rollback|skip|abort``, ``loss_spike_factor``,
``loss_spike_warmup``, ``rollback_backoff``, ``rollback_max_retries``,
``watchdog_timeout``, ``watchdog_action`` (doc/robustness.md documents
the full recovery state machine and the telemetry events).

This module deliberately imports no jax: the policy logic must be
testable (and ``python -m cxxnet_tpu.utils.health --selftest`` runnable)
on a box with no accelerator stack at all.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import Dict, Optional

import numpy as np

from . import lockrank
from . import telemetry

__all__ = [
    "Anomaly", "TrainingAnomalyError", "HealthMonitor", "RecoveryPolicy",
    "Watchdog", "beat", "pause", "channel_status", "dump_all_stacks",
    "dump_diagnostics", "note_nonfinite", "selftest",
]

# health-vector slot layout, shared with nnet/trainer.py _make_train_step
H_LOSS, H_GNORM_SQ, H_NAN_GRADS, H_OK = 0, 1, 2, 3

# ranked (utils/lockrank.py): anomaly ids are allocated from
# telemetry/watchdog callbacks, so the ordering discipline covers it
_id_lock = lockrank.lock("health.ids")
_next_anomaly_id = [0]


def _new_id() -> int:
    with _id_lock:
        _next_anomaly_id[0] += 1
        return _next_anomaly_id[0]


class Anomaly:
    """One detected training anomaly (which step, what went wrong)."""

    __slots__ = ("id", "kind", "round", "batch", "loss", "grad_norm_sq",
                 "nan_grads")

    def __init__(self, kind: str, round_: int, batch: int, loss: float,
                 grad_norm_sq: float, nan_grads: int):
        self.id = _new_id()
        self.kind = kind
        self.round = int(round_)
        self.batch = int(batch)
        self.loss = float(loss)
        self.grad_norm_sq = float(grad_norm_sq)
        self.nan_grads = int(nan_grads)

    def describe(self) -> str:
        return ("%s at round %d batch %d (loss=%g, grad_norm_sq=%g, "
                "nan_grads=%d)" % (self.kind, self.round, self.batch,
                                   self.loss, self.grad_norm_sq,
                                   self.nan_grads))


class TrainingAnomalyError(RuntimeError):
    """Raised by the train loop when the recovery policy wants a rollback;
    the driver catches it, restores the newest valid checkpoint, and
    re-enters the loop with the offending batch window quarantined."""

    def __init__(self, anomaly: Anomaly):
        super().__init__(anomaly.describe())
        self.anomaly = anomaly


class HealthMonitor:
    """Host-side detector over the per-step health vectors.

    ``observe(round, batch, vec)`` queues the CURRENT step's device vector
    and checks the PREVIOUS one (whose compute has certainly finished by
    the time the next step was dispatched — the ``np.asarray`` fetch never
    introduces a pipeline bubble); ``drain()`` checks whatever is still
    queued (call it before eval/checkpoint so a bad step can never be
    persisted as "good"). Both return the detected :class:`Anomaly` or
    None. Detection identifies the EXACT offending step even though the
    check runs late — the vector is queued with its (round, batch) key.
    """

    def __init__(self, spike_factor: float = 0.0, spike_warmup: int = 20,
                 spike_decay: float = 0.98, gauge_names=None,
                 gauge_limits=None):
        # gauge_names(): names of what the step put behind the four health
        # values (Trainer.health_gauge_names: moe.pairs_held/<layer>,
        # moe.load_max/<layer>); each check keeps them as gauges.
        # gauge_limits(): {one of those names: (name, bound)}
        # (Trainer.health_gauge_limits); each check also keeps gauge
        # ``name`` = 1 where the reading passed the bound, else 0
        # (moe.overflow/<layer>: the pairs held did not fit the sorted
        # side's rows and the layer took its whole-order fallback)
        self.gauge_names = gauge_names
        self.gauge_limits = gauge_limits
        self.spike_factor = float(spike_factor)
        self.spike_warmup = int(spike_warmup)
        self.spike_decay = float(spike_decay)
        self._pending = deque()
        self._ema = 0.0
        self._nseen = 0
        self.anomaly_count = 0

    def observe(self, round_: int, batch: int, health) -> Optional[Anomaly]:
        if health is None:
            return None
        self._pending.append((round_, batch, health))
        if len(self._pending) > 1:
            return self._check(*self._pending.popleft())
        return None

    def drain(self) -> Optional[Anomaly]:
        while self._pending:
            a = self._check(*self._pending.popleft())
            if a is not None:
                return a
        return None

    def reset_pending(self) -> None:
        """Drop queued vectors (they reference a trainer that a rollback
        is about to discard)."""
        self._pending.clear()

    # ------------------------------------------------------------------
    def _check(self, round_: int, batch: int, health) -> Optional[Anomaly]:
        h = np.asarray(health, np.float32)
        loss = float(h[H_LOSS])
        gn_sq = float(h[H_GNORM_SQ])
        nan_grads = int(h[H_NAN_GRADS])
        if self.gauge_names is not None:
            limits = self.gauge_limits() if self.gauge_limits else {}
            for name, v in zip(self.gauge_names(), h[H_OK + 1:]):
                telemetry.gauge(name, float(v))
                if name in limits:
                    over, bound = limits[name]
                    telemetry.gauge(over, float(v > bound))
        if nan_grads > 0:
            # the elements updater _clip_nan silently zeroes (with
            # clip_gradient set) — or that reach the optimizer raw —
            # made visible as a counter instead of vanishing
            telemetry.count("health/nan_grads_zeroed", nan_grads)
        if not (np.isfinite(loss) and np.isfinite(gn_sq)):
            return self._anomaly("nonfinite", round_, batch, loss, gn_sq,
                                 nan_grads)
        if self.spike_factor > 0.0:
            if self._nseen >= self.spike_warmup \
                    and loss > self.spike_factor * max(self._ema, 1e-12):
                return self._anomaly("loss_spike", round_, batch, loss,
                                     gn_sq, nan_grads)
            self._nseen += 1
            self._ema = loss if self._nseen == 1 else (
                self.spike_decay * self._ema
                + (1.0 - self.spike_decay) * loss)
        return None

    def _anomaly(self, kind, round_, batch, loss, gn_sq, nan_grads):
        a = Anomaly(kind, round_, batch, loss, gn_sq, nan_grads)
        self.anomaly_count += 1
        telemetry.count("health/anomalies")
        telemetry.event({"ev": "health_anomaly", "id": a.id, "kind": kind,
                         "round": a.round, "batch": a.batch,
                         "loss": _json_num(loss),
                         "grad_norm_sq": _json_num(gn_sq),
                         "nan_grads": a.nan_grads})
        return a


def _json_num(x: float):
    """NaN/Inf as strings so the JSONL log stays strict-JSON parseable."""
    return float(x) if np.isfinite(x) else repr(float(x))


class RecoveryPolicy:
    """Pure-host state machine mapping anomalies to recovery decisions.

    States: HEALTHY → (anomaly) → one of

    * ``rollback`` — quarantine the offending (round, batch), fold the LR
      backoff into ``lr_scale``, count a retry; the driver restores the
      newest valid checkpoint and replays, skipping quarantined batches.
    * ``skip`` — the trainer's on-device guard already suppressed the
      non-finite update (``nonfinite_action=skip``); nothing to restore.
      Loss spikes are logged only in this mode.
    * ``abort`` — ``nonfinite_action=abort``, or retries exhausted
      (``retries > max_retries``); the driver dumps diagnostics and dies.

    A completed round resets the consecutive-retry counter
    (``on_round_complete``); the quarantine set and ``lr_scale`` persist
    for the rest of the run.
    """

    ACTIONS = ("rollback", "skip", "abort")

    def __init__(self, action: str = "rollback", backoff: float = 1.0,
                 max_retries: int = 2):
        if action not in self.ACTIONS:
            raise ValueError("nonfinite_action must be one of %s, got %r"
                             % ("|".join(self.ACTIONS), action))
        self.action = action
        self.backoff = float(backoff)
        self.max_retries = int(max_retries)
        self.retries = 0          # consecutive rollbacks without a
        #                           completed round
        self.total_rollbacks = 0
        self.lr_scale = 1.0
        self._skip: Dict[int, set] = {}
        # the anomaly currently being recovered from: set by a
        # rollback/abort decision, cleared by resolve() once the driver's
        # restore completes. statusd's /healthz serves 503 while set —
        # the "don't route traffic / don't trust this run" window.
        self.pending: Optional[Anomaly] = None

    def decide(self, anomaly: Anomaly) -> str:
        """'skip' | 'rollback' | 'abort'. A 'rollback' decision has
        already quarantined the offending batch and folded the backoff
        into ``lr_scale`` (apply via Trainer.scale_lr after restoring)."""
        if self.action == "abort":
            self.pending = anomaly
            return "abort"
        if self.action == "skip":
            return "skip"          # suppressed on device: nothing pending
        self.pending = anomaly
        self.retries += 1
        if self.retries > self.max_retries:
            return "abort"
        self.total_rollbacks += 1
        self._skip.setdefault(anomaly.round, set()).add(anomaly.batch)
        if self.backoff != 1.0:
            self.lr_scale *= self.backoff
        return "rollback"

    def resolve(self) -> None:
        """The driver finished recovering (checkpoint restored, replay
        armed): clear the unresolved-anomaly state so /healthz returns to
        200. Aborts never resolve — the endpoint stays 503 for whatever
        scrape catches the dying process."""
        self.pending = None

    def should_skip(self, round_: int, batch: int) -> bool:
        s = self._skip.get(int(round_))
        return s is not None and int(batch) in s

    def skipped(self):
        """The quarantined windows as a JSON-friendly sorted list."""
        return [[r, b] for r in sorted(self._skip)
                for b in sorted(self._skip[r])]

    def on_round_complete(self) -> None:
        self.retries = 0


# ----------------------------------------------------------------------
# watchdog: heartbeat channels + stalled-run stack dumps
_beats: Dict[str, float] = {}
_active_watchdog: Optional["Watchdog"] = None


def beat(channel: str = "train.step") -> None:
    """Heartbeat a liveness channel. No-op unless a Watchdog is running;
    one dict store under the GIL, safe from any thread (the train loop,
    the prefetcher, decode workers)."""
    if _active_watchdog is not None:
        _beats[channel] = time.monotonic()


def pause(channel: str = "train.step") -> None:
    """Disarm a liveness channel for a legitimately-silent phase — the
    round-end eval/checkpoint, the gap between prefetch passes, a long
    first-compile — so the watchdog doesn't false-alarm (or, with
    watchdog_action=abort, kill a healthy run). The next beat() on the
    channel re-arms it. Cheap and safe from any thread."""
    _beats.pop(channel, None)
    wd = _active_watchdog
    if wd is not None:
        wd._fired.pop(channel, None)


def channel_status():
    """Live heartbeat view for statusd: ``[(channel, age_s, timeout_s,
    overdue), ...]`` over every ARMED channel (paused channels are
    legitimately silent and excluded, same as the watchdog's own scan).
    Empty when no watchdog is running — /healthz then has no heartbeat
    opinion at all rather than a stale one."""
    wd = _active_watchdog
    if wd is None:
        return []
    now = time.monotonic()
    return [(ch, now - t, wd.timeout, (now - t) > wd.timeout)
            for ch, t in list(_beats.items())]


def dump_all_stacks(out=None, header: str = "") -> str:
    """Write every thread's current stack to ``out`` (default stderr) —
    the post-mortem a wedged run otherwise never leaves behind."""
    names = {t.ident: t.name + (" [daemon]" if t.daemon else "")
             for t in threading.enumerate()}
    lines = [header] if header else []
    for tid, frame in sorted(sys._current_frames().items()):
        lines.append("--- thread %s (%d) ---" % (names.get(tid, "?"), tid))
        for entry in traceback.format_stack(frame):
            lines.extend(entry.rstrip("\n").splitlines())
    text = "\n".join(lines) + "\n"
    f = out or sys.stderr
    f.write(text)
    try:
        f.flush()
    except Exception:
        pass
    return text


def dump_diagnostics(reason: str, anomaly: Optional[Anomaly] = None,
                     out=None) -> None:
    """The abort path's post-mortem: reason + anomaly + all-thread stacks
    to stderr, telemetry flushed — everything a dying run can still say."""
    f = out or sys.stderr
    f.write("HEALTH ABORT: %s\n" % reason)
    if anomaly is not None:
        f.write("  anomaly: %s\n" % anomaly.describe())
    dump_all_stacks(out=f, header="-- diagnostic all-thread stack dump --")
    try:
        telemetry.flush()
    except Exception:
        pass


class Watchdog:
    """Daemon thread that fires when a heartbeat channel goes silent for
    longer than ``timeout`` seconds.

    Firing means: all-thread stack dump to stderr, ``watchdog_stall``
    telemetry event, telemetry flush — all BEFORE the action. Action
    ``warn`` leaves the process alone (it may recover: a slow NFS read, a
    long GC); ``abort`` exits with code 70 after the dump, the
    hang-converted-to-restartable-death used under a supervisor that
    resumes with ``continue=1``. Each stall fires once; a fresh beat on
    the channel re-arms it. Only channels that have beaten since their
    last ``pause()`` are monitored: call sites disarm across
    legitimately-silent phases (round-end eval/checkpoint, between
    prefetch passes) so those never false-alarm. Size ``timeout`` above
    the worst single-step cost INCLUDING a jit recompile — a mid-round
    recompile is silent time on the step channel like any other.
    """

    def __init__(self, timeout: float, action: str = "warn",
                 poll: Optional[float] = None, on_stall=None):
        if action not in ("warn", "abort"):
            raise ValueError("watchdog_action must be warn|abort, got %r"
                             % action)
        self.timeout = float(timeout)
        self.action = action
        self.poll = poll if poll is not None else \
            max(0.05, min(self.timeout / 4.0, 1.0))
        self.on_stall = on_stall
        self.stalls = 0
        self._fired: Dict[str, float] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Watchdog":
        global _active_watchdog
        _beats.clear()
        self._stop.clear()
        _active_watchdog = self
        self._thread = threading.Thread(target=self._run,
                                        name="cxn-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        global _active_watchdog
        if _active_watchdog is self:
            _active_watchdog = None
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # ------------------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.wait(self.poll):
            now = time.monotonic()
            for ch, t in list(_beats.items()):
                # fire once per stall: remember the beat timestamp we
                # fired for; any newer beat re-arms the channel
                if now - t > self.timeout and self._fired.get(ch) != t:
                    self._fired[ch] = t
                    self._fire(ch, now - t)

    def _fire(self, channel: str, age: float) -> None:
        self.stalls += 1
        dump_all_stacks(header=(
            "WATCHDOG: channel %r silent for %.2fs (timeout %.2fs) — "
            "all-thread stack dump follows" % (channel, age, self.timeout)))
        telemetry.event({"ev": "watchdog_stall", "channel": channel,
                         "stalled_s": round(age, 3),
                         "timeout_s": self.timeout, "action": self.action})
        telemetry.count("health/watchdog_stalls")
        try:
            telemetry.flush()
        except Exception:
            pass
        if self.on_stall is not None:
            try:
                self.on_stall(channel, age)
            except Exception:
                pass
        if self.action == "abort":
            sys.stderr.write(
                "WATCHDOG: aborting the wedged process (exit code 70); "
                "resume with continue=1\n")
            sys.stderr.flush()
            os._exit(70)


# ----------------------------------------------------------------------
_warned_sites = set()


def note_nonfinite(where: str, count: int = 1) -> None:
    """Route a host-observed non-finite metric/eval value through a
    health event (warn once per site + counter) instead of a hard crash.
    The jit metric path cannot raise on NaN, so the reference's host-only
    ``FloatingPointError`` was an inconsistent contract — both paths now
    surface the same way (utils/metric.py). The emitted anomaly carries
    ``resolution: "warned"`` so tools/telemetry_report.py does not count
    it as an unrecovered training anomaly."""
    telemetry.count("health/nonfinite_metric", count)
    telemetry.event({"ev": "health_anomaly", "id": _new_id(),
                     "kind": "metric_nonfinite", "where": where,
                     "count": int(count), "resolution": "warned"})
    if where not in _warned_sites:
        _warned_sites.add(where)
        sys.stderr.write(
            "WARNING: non-finite value(s) in %s; excluded and counted "
            "(health/nonfinite_metric)\n" % where)


# ----------------------------------------------------------------------
def _sim_vec(loss: float, nan_grads: int = 0):
    gn = float("nan") if not np.isfinite(loss) else 1.0
    ok = 1.0 if np.isfinite(loss) else 0.0
    return np.asarray([loss, gn, float(nan_grads), ok], np.float32)


def selftest(verbose: bool = False) -> int:
    """Pure-host simulation of the detect→rollback→skip state machine —
    no jax, no net; ``make check`` gates on it.

    The simulated "trainer" state is the list of (round, batch) updates
    applied; a checkpoint is a copy of that list at each round boundary,
    exactly like learn_task's save schedule. Bad batches yield non-finite
    (or spiking) health vectors through the real HealthMonitor and
    RecoveryPolicy, and the assertions pin the recovery contract: the
    final state equals a clean run with the bad batches excluded, the LR
    backoff compounds per rollback, and retries exhaust into abort.
    """

    class _Roll(Exception):
        pass

    class _Abort(Exception):
        pass

    def run(bad, action="rollback", backoff=1.0, max_retries=2,
            spike=0.0, rounds=3, batches=4):
        mon = HealthMonitor(spike_factor=spike, spike_warmup=1)
        pol = RecoveryPolicy(action=action, backoff=backoff,
                             max_retries=max_retries)
        state = []
        ckpts = {0: []}              # learn_task saves round 0's start too

        def decide(a):
            d = pol.decide(a)
            if d == "abort":
                raise _Abort(a.describe())
            if d == "rollback":
                raise _Roll()
            # 'skip': on-device guard already suppressed it — undo the
            # simulated application the way jnp.where(ok, new, old) does
            state.remove((a.round, a.batch))

        r = 0
        try:
            while r < rounds:
                try:
                    b = 0
                    while b < batches:
                        if pol.should_skip(r, b):
                            b += 1
                            continue
                        is_bad = (r, b) in bad
                        state.append((r, b))
                        loss = (100.0 if spike else float("nan")) \
                            if is_bad else 1.0
                        a = mon.observe(r, b, _sim_vec(loss,
                                                       3 if is_bad else 0))
                        if a is not None:
                            decide(a)
                        b += 1
                    a = mon.drain()
                    if a is not None:
                        decide(a)
                except _Roll:
                    mon.reset_pending()
                    r = max(ckpts)
                    state = list(ckpts[r])
                    continue
                pol.on_round_complete()
                r += 1
                ckpts[r] = list(state)
        except _Abort:
            return state, pol, True
        return state, pol, False

    clean = [(r, b) for r in range(3) for b in range(4)]

    # 1. no anomalies: nothing skipped, nothing rolled back
    state, pol, aborted = run(bad=set())
    assert state == clean and not aborted and pol.total_rollbacks == 0

    # 2. one non-finite batch: rollback + replay excludes exactly it
    state, pol, aborted = run(bad={(1, 2)}, backoff=0.5)
    assert state == [x for x in clean if x != (1, 2)], state
    assert not aborted and pol.total_rollbacks == 1
    assert abs(pol.lr_scale - 0.5) < 1e-12

    # 3. two bad batches in one round: two rollbacks, both excluded,
    #    backoff compounds
    state, pol, aborted = run(bad={(1, 1), (1, 3)}, backoff=0.5)
    assert state == [x for x in clean if x not in ((1, 1), (1, 3))]
    assert pol.total_rollbacks == 2 and abs(pol.lr_scale - 0.25) < 1e-12

    # 4. loss spike drives the same machinery
    state, pol, aborted = run(bad={(2, 0)}, spike=5.0, backoff=0.5)
    assert state == [x for x in clean if x != (2, 0)] and not aborted
    assert pol.total_rollbacks == 1

    # 5. every batch bad: retries exhaust into abort
    state, pol, aborted = run(bad={(0, b) for b in range(4)},
                              max_retries=2)
    assert aborted and pol.retries == 3

    # 6. skip mode: no rollbacks, bad updates suppressed in place
    state, pol, aborted = run(bad={(0, 1), (2, 2)}, action="skip")
    assert state == [x for x in clean if x not in ((0, 1), (2, 2))]
    assert not aborted and pol.total_rollbacks == 0

    # 7. abort mode dies on first anomaly
    state, pol, aborted = run(bad={(0, 0)}, action="abort")
    assert aborted

    if verbose:
        print("health selftest: detect/rollback/skip state machine ok "
              "(7 scenarios)")
    return 0


if __name__ == "__main__":
    if "--selftest" in sys.argv[1:]:
        sys.exit(selftest(verbose=True))
    print(__doc__)
    sys.exit(1)
