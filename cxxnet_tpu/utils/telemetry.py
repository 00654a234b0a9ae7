"""Process-wide structured telemetry: spans, counters, recompile detection.

The reference reported progress with bare printfs per round
(src/cxxnet_main.cpp:330-360); production training systems stand on
first-class runtime instrumentation (TF's system paper, arxiv 1605.08695)
and per-region timing is what drives every subsequent optimization (TVM,
arxiv 1802.04799). This module is that measurement substrate:

* **span timers** — ``with telemetry.span("io.decode"):`` records wall time
  per named region; spans nest (a per-thread stack tracks depth/parent) and
  are safe to emit from worker threads (the decode pool, the prefetcher).
  While a jax profiler session records, a span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, telemetry enabled or not,
  so it lands on the host plane of the same ``.xplane.pb`` as the device
  operations (utils/devtrace.py names each idle gap by it).
* **phases** — ``with telemetry.phase("init.params"):`` is a span that also
  writes its seconds to a small always-on account (``phases()``), for what
  happens once a process and is wanted in runs that enabled nothing:
  ``Trainer.init_model``'s parts and each program's first call
  (``jit.build/<name>``, with jax's own trace / lower / compile /
  cache_load seconds beside it). The account keeps the first occurrence
  of each name and outlives ``reset()``: it says what this process's
  set-up cost. ``summary()``, ``/metrics`` (``cxxnet_phase_seconds``) and
  ``/statusz`` show it.
* **paths** — ``telemetry.count_path("moe.sparse")`` is a counter that
  also writes a small always-on account (``paths()``), for which lowering
  a layer took when its step was traced (``moe.sparse`` / ``moe.dense``,
  ``attn.flash`` / ``attn.dense``, once per traced layer; beside
  ``attn.flash`` the tiles its static schedule holds, ``flash.tiles.full``
  / ``.edge`` / ``.skipped``): like the phase
  account it is the process's and outlives ``reset()``, so that a run that
  enabled nothing can still say what it timed.
* **kept spans** — ``telemetry.span("train.h2d", keep=True)`` is a span that
  also leaves ``(t0, dur)``, on ``time.perf_counter()``'s clock, in a small
  always-on ring of its name's last ``KEPT_CAP`` occurrences (``kept()``):
  the third account, for the few hot-path spans a benchmark metric reads
  in runs that enabled nothing (``Trainer.update`` and its parts). Like the
  other two it is the process's and outlives ``reset()``. Disabled and with
  no profiler a kept span costs two clock reads and one append; a span
  that is not kept stays the shared no-op.
* **counters / gauges** — ``telemetry.count("train.images", n)`` accumulates
  monotonically; ``telemetry.gauge("device.bytes_in_use", v)`` records the
  latest value of a level. ``sample_device_memory()`` snapshots the
  accelerator's allocator stats where the backend exposes them.
* **recompile detector** — ``jit_watch(fn, name, cause=...)`` wraps a jitted
  callable and records a ``compile`` event (with its cause and compile
  seconds) whenever the underlying jit cache grows: exactly once per
  genuinely new (signature, shape) key, never on cache hits.
* **histograms** — ``telemetry.hist("serve.request", seconds)`` feeds a
  fixed LOG-SPACED bucket histogram (``HIST_BUCKETS``: 4 buckets per
  decade, 1µs..1000s, identical in every process), so merging shards from
  a multihost run is exact bucket-count addition — never re-binning.
  Every span duration additionally feeds the histogram of its span name,
  which is what /metrics serves as Prometheus ``_bucket`` series
  (utils/statusd.py).

Sinks:

* a JSONL run log (one event per line; ``enable(path)``), flushed
  incrementally so a crashed run still leaves its telemetry behind;
* a Chrome-trace / Perfetto JSON export built from the span tree
  (``write_chrome_trace`` or ``chrome_trace``), loadable in
  chrome://tracing or https://ui.perfetto.dev;
* an aggregate ``summary()`` dict (per-span totals and percentiles,
  counters, compiles, the step time as the mean ``train.period``) —
  printed by learn_task at end of run.

Disabled (the default) the module is near-zero overhead: ``span()`` returns
a shared no-op context manager (no allocation), counters are one
thread-local read plus a branch (the read keeps per-request attribution
working inside a trace context even when disabled), and no events are
ever buffered. Everything is process-global by design —
one training job per process (the Trainer model), one telemetry stream.

Multihost runs get one stream PER PROCESS: ``enable(path, process_index=i)``
substitutes a ``%d`` rank placeholder in the log path (so shards never
clobber each other), tags every event with ``"p": i``, and
``tools/telemetry_report.py --merge shard*.jsonl`` re-aligns the shards on
the shared wall-clock epoch for one cross-host report.

Request attribution (the serving datapath's measurement contract):

* **trace contexts** — ``with telemetry.trace_context(request_id) as tc:``
  tags every span/event recorded on the same thread underneath it with
  ``"req": request_id``, and accumulates per-request counter deltas and
  recompile events on ``tc`` itself — so one served request's telemetry
  can be pulled apart from everything around it. ``telemetry.mark(name)``
  timestamps a named boundary on the active context (the trainer marks
  ``first_token`` at the prefill/decode split — the TTFT boundary).
  Contexts are thread-local and work even with telemetry DISABLED (the
  marks/attribution still flow; only event emission is gated), because
  the serving SLO layer needs TTFT regardless of whether a JSONL log was
  configured.
* **flight recorder** — ``FlightRecorder`` keeps a bounded ring of the
  last N completed request traces (phase split, token counts, outcome,
  recompiles); statusd serves one as a Chrome trace at
  ``/trace?request=<id>`` (``request_chrome_trace``) and lists the ring
  at ``/requestz``.
"""

from __future__ import annotations

import bisect
import io
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from . import lockrank

__all__ = [
    "enable", "disable", "enabled", "reset", "span", "phase", "phases",
    "kept", "KEPT_CAP",
    "count", "count_path", "paths", "gauge",
    "hist", "event", "record_compile", "jit_watch",
    "sample_device_memory",
    "flush", "finish", "summary", "events",
    "recent_events", "last_event", "wall_epoch", "span_event",
    "percentile", "count_by",
    "chrome_trace", "events_to_chrome", "write_chrome_trace",
    "Histogram", "HIST_BUCKETS", "trace_context", "current_trace", "mark",
    "declare_hist", "TraceContext", "FlightRecorder",
    "request_chrome_trace", "REQUEST_PHASES",
    "CompileWindow", "compile_window", "current_compile_window",
    "BooksAuditor", "auditor", "audit_register", "audit_unregister",
    "audit_sweep",
]

# per-span-name duration history kept for live percentiles (the JSONL log
# keeps everything; this only bounds in-memory state on week-long runs)
_DUR_CAP = 8192
# in-memory event buffer bound when NO log sink drains it (bench/library
# mode): oldest events drop past this; aggregates (summary) are unaffected
_PENDING_CAP = 65536
# recent-event ring kept even WITH a log sink — the /trace endpoint's
# snapshot source (statusd serves a live Chrome trace from it)
_RING_CAP = 4096
# occurrences the always-on account of kept spans holds of each name
KEPT_CAP = 512
# the histogram whose MEAN summary() gives as the train step's time: the
# distance between the entries of back-to-back Trainer.update calls, which
# the trainer feeds. A single distance is not the step's (the runtime lets
# the host run a fixed number of steps ahead, so entries come a few ms
# apart and then one a step), hence no percentile of it.
_STEP_PERIOD = "train.period"

# Fixed log-spaced histogram bucket upper bounds (seconds): 4 per decade,
# 1µs .. 1000s. FIXED for every histogram in every process by design —
# cross-process/shard merging is then exact bucket-count addition (the
# property Prometheus `le` buckets and telemetry_report --merge rely on).
HIST_BUCKETS = tuple(round(10.0 ** (e / 4.0), 10) for e in range(-24, 13))


def fmt_ms(v) -> str:
    """Render a millisecond figure, turning the empty-series sentinel
    (None — ``Histogram`` on zero observations) into "n/a". The ONE
    renderer of the sentinel, shared by /statusz and the report tools
    so the format cannot drift between them."""
    return "n/a" if v is None else "%.2fms" % v


class Histogram:
    """Fixed-bucket latency histogram (see HIST_BUCKETS). ``counts[i]``
    holds observations with value <= HIST_BUCKETS[i] (and > the previous
    bound); the final slot is the +Inf overflow. Mergeable exactly."""

    __slots__ = ("counts", "sum", "n")

    def __init__(self):
        self.counts = [0] * (len(HIST_BUCKETS) + 1)
        self.sum = 0.0
        self.n = 0

    def observe(self, value: float) -> None:
        v = float(value)
        self.counts[bisect.bisect_left(HIST_BUCKETS, v)] += 1
        self.sum += v
        self.n += 1

    def percentile(self, p: float) -> float:
        """Estimated percentile: walk the cumulative counts to the target
        rank, interpolate linearly inside the bucket. Error is bounded by
        the bucket width (~78% per log-spaced step) — exact enough for
        p50/p90/p99 dashboards, and identical no matter how many shards
        were merged to produce the counts. Ranks landing in the +Inf
        overflow slot are CLAMPED to the last bound (1000s): the result
        must stay finite (strict-JSON logs, bench lines), so a tail past
        1000s reads as exactly 1000s — the overflow bucket's count is
        the tell.

        An EMPTY histogram returns None (never NaN, never a fake 0.0):
        a series that was declared but never fired — TTFT on a run that
        served zero requests — has no percentiles, and 0.0ms would read
        as an impossibly fast tail on /statusz and in bench lines. The
        renderers turn None into "n/a"; JSON sinks carry it as null."""
        if self.n == 0:
            return None
        rank = (p / 100.0) * self.n
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            prev = cum
            cum += c
            if cum >= rank:
                lo = HIST_BUCKETS[i - 1] if i > 0 else 0.0
                hi = HIST_BUCKETS[i] if i < len(HIST_BUCKETS) \
                    else HIST_BUCKETS[-1]
                frac = min(1.0, max(0.0, (rank - prev) / c))
                return lo + (hi - lo) * frac
        return HIST_BUCKETS[-1]

    def to_dict(self) -> dict:
        """JSON-friendly sparse snapshot (only nonzero buckets)."""
        return {"buckets": {str(i): c for i, c in enumerate(self.counts)
                            if c},
                "sum": round(self.sum, 9), "count": self.n}

    def merge_dict(self, d: dict) -> "Histogram":
        """Fold a ``to_dict`` snapshot in — EXACT because every histogram
        shares HIST_BUCKETS (shard merge = bucket-count addition). An
        out-of-range bucket index means the snapshot came from a build
        with DIFFERENT buckets (or a corrupted log): merging it would be
        silently wrong, so it raises ValueError for the caller to report."""
        for i, c in (d.get("buckets") or {}).items():
            i = int(i)
            if not 0 <= i < len(self.counts):
                raise ValueError(
                    "histogram bucket index %d out of range (%d buckets) "
                    "— snapshot from a mismatched HIST_BUCKETS version or "
                    "a corrupted log" % (i, len(self.counts)))
            self.counts[i] += int(c)
        self.sum += float(d.get("sum", 0.0))
        self.n += int(d.get("count", 0))
        return self

    def stats(self) -> dict:
        """Summary dict; the mean (sum over count, both kept exactly) and
        the percentile fields are None (rendered "n/a", serialized null)
        when the histogram never observed anything."""
        if self.n == 0:
            return {"count": 0, "sum_s": 0.0, "mean_ms": None,
                    "p50_ms": None, "p90_ms": None, "p99_ms": None}
        return {"count": self.n, "sum_s": round(self.sum, 6),
                "mean_ms": round(1e3 * self.sum / self.n, 4),
                "p50_ms": round(1e3 * self.percentile(50), 4),
                "p90_ms": round(1e3 * self.percentile(90), 4),
                "p99_ms": round(1e3 * self.percentile(99), 4)}


class _NullSpan:
    """Shared no-op context manager returned by span() when disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

_TRACE_ANNOTATION = None     # jax.profiler.TraceAnnotation, once jax is seen


def _recording_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session records,
    else None. jax is looked up only where it is already loaded: servd and
    statusd import this module without it."""
    global _TRACE_ANNOTATION
    ta = _TRACE_ANNOTATION
    if ta is None:
        ta = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                     "TraceAnnotation", None)
        if ta is None:
            return None
        _TRACE_ANNOTATION = ta
    return ta if ta.is_enabled() else None


class _Span:
    __slots__ = ("reg", "name", "attrs", "t0", "depth", "ann", "ring")

    def __init__(self, reg: "_Registry", name: str, attrs, ann=None,
                 ring=None):
        self.reg = reg
        self.name = name
        self.attrs = attrs
        self.ann = ann       # the profiler's annotation of the same name
        self.ring = ring     # a kept span's: its name's (t0, dur) ring

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        if self.reg.enabled:
            stack = self.reg._stack()
            self.depth = len(stack)
            stack.append(self.name)
        else:
            # a kept span with telemetry disabled: nothing but the ring
            self.depth = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self.ring is not None:
            self.ring.append((self.t0, dur))
        if self.depth is not None:
            stack = self.reg._stack()
            if stack and stack[-1] is self.name:
                stack.pop()
            self.reg._record_span(self.name, self.t0, dur, self.depth,
                                  self.attrs)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


# jax's monitoring events that split a program's first call, and the name
# each goes by under the call's ``jit.build/<program>`` phase
_BUILD_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_BUILD_TLS = threading.local()   # .parts: the build open on this thread
_BUILD_LISTENING = False


def _on_build_duration(event, secs, **_kw):
    parts = getattr(_BUILD_TLS, "parts", None)
    part = _BUILD_PARTS.get(event)
    if parts is not None and part is not None:
        # the longest: a nested jit's trace fires the event too, inside
        # the outermost's; the others come once a program
        parts[part] = max(parts.get(part, 0.0), secs)


def _listen_for_build_parts() -> None:
    """One listener a process on jax's monitoring events (jax has no way to
    take one off again), registered at the first build phase: it adds to
    the build that is open on the calling thread, and is a dictionary miss
    otherwise (the events fire when something is traced or compiled, not
    per call)."""
    global _BUILD_LISTENING
    if not _BUILD_LISTENING:
        _BUILD_LISTENING = True
        sys.modules["jax"].monitoring \
            .register_event_duration_secs_listener(_on_build_duration)


class _Phase:
    """A span that also writes its seconds to the registry's always-on
    account (``phases()``), which keeps the first occurrence of a name.
    With ``parts``, jax's own durations of a program's build that arrive
    on this thread meanwhile are kept beside it as ``<name>/<part>``."""

    __slots__ = ("reg", "name", "parts", "outer", "span", "t0")

    def __init__(self, reg: "_Registry", name: str, parts: bool = False):
        self.reg = reg
        self.name = name
        self.parts = {} if parts else None

    def __enter__(self):
        if self.parts is not None:
            _listen_for_build_parts()
            self.outer = getattr(_BUILD_TLS, "parts", None)
            _BUILD_TLS.parts = self.parts
        self.span = self.reg.span(self.name)
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self.span.__exit__(*exc)
        found = {self.name: dur}
        if self.parts is not None:
            _BUILD_TLS.parts = self.outer
            for part, secs in self.parts.items():
                found["%s/%s" % (self.name, part)] = secs
        with self.reg._lock:
            if self.name not in self.reg.phase_s:
                self.reg.phase_s.update(found)
        return False


class TraceContext:
    """One request's attribution scope (``with trace_context(rid):``).

    While active on a thread, every span/event that thread records is
    tagged ``"req": request_id``, counter deltas are mirrored into
    ``self.counts``, recompile events into ``self.compiles``, and
    ``mark(name)`` timestamps named boundaries into ``self.marks``
    (perf_counter stamps — the serving worker turns the trainer's
    ``first_token`` mark into TTFT). Contexts nest (innermost wins) and
    deliberately work with telemetry DISABLED: attribution costs a
    thread-local read, and the SLO layer needs the marks whether or not
    a JSONL sink exists."""

    __slots__ = ("reg", "request_id", "marks", "counts", "compiles", "t0")

    def __init__(self, reg: "_Registry", request_id):
        self.reg = reg
        self.request_id = str(request_id)
        self.marks: Dict[str, float] = {}
        self.counts: Dict[str, float] = {}
        self.compiles: List[dict] = []
        self.t0: Optional[float] = None

    def __enter__(self) -> "TraceContext":
        self.t0 = time.perf_counter()
        self.reg._ctx_stack().append(self)
        return self

    def __exit__(self, *exc):
        stack = self.reg._ctx_stack()
        if stack and stack[-1] is self:
            stack.pop()
        return False

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter()


class CompileWindow:
    """Collects the compile records observed on this thread while
    active — the batching dispatcher's stall-attribution bracket around
    work that runs OUTSIDE any request's trace context (warm-session
    creation, the batch-wide decode step): a compile inside the window
    stalled every request aboard the batch, so the dispatcher fans
    ``window.compiles`` out to their flight records as
    ``compile_stall_s``. Like TraceContext it works with telemetry
    DISABLED (thread-local append, no sink needed) and nests — every
    active window on the thread sees the compile. The label also rides
    the perf ledger's compile flight ring as the trigger context."""

    __slots__ = ("reg", "label", "compiles")

    def __init__(self, reg: "_Registry", label):
        self.reg = reg
        self.label = str(label)
        self.compiles: List[dict] = []

    def __enter__(self) -> "CompileWindow":
        self.reg._win_stack().append(self)
        return self

    def __exit__(self, *exc):
        stack = self.reg._win_stack()
        if stack and stack[-1] is self:
            stack.pop()
        return False

    @property
    def stall_s(self) -> float:
        return round(sum(c["dur"] for c in self.compiles), 6)


class _Registry:
    """The process-wide telemetry state. Use the module-level functions;
    the class exists so tests can build isolated instances."""

    def __init__(self):
        self.enabled = False
        self.log_path: Optional[str] = None
        self._log_f: Optional[io.TextIOBase] = None
        # innermost rank by design: every subsystem records telemetry,
        # so nothing may be acquired while this is held
        self._lock = lockrank.lock("telemetry.registry")
        self._tls = threading.local()
        self.process_index = 0
        # the performance ledger's compile hook (utils/perf.py):
        # called by JitWatch with the compiled callable + call args on
        # every detected compile. Survives reset()/enable() — bench
        # resets telemetry between rows without re-wiring the ledger.
        self.compile_hook = None
        # the always-on account of phases: name -> seconds of its first
        # occurrence in this process (a few dozen entries). Outlives
        # reset()/enable() as compile_hook does: it is the process's
        # set-up, and a JitWatch's first call does not come again.
        self.phase_s: Dict[str, float] = {}
        # the always-on account of paths: name -> times a traced layer
        # took that lowering in this process
        self.path_n: Dict[str, int] = {}
        # the always-on account of kept spans: name -> the (t0, dur) of
        # its last KEPT_CAP occurrences, on time.perf_counter()'s clock
        self.kept_rings: Dict[str, deque] = {}
        self.reset()

    # -- lifecycle -----------------------------------------------------
    def reset(self) -> None:
        with self._lock:
            self._pending: List[dict] = []
            self.counters: Dict[str, float] = {}
            self.gauges: Dict[str, float] = {}
            self.span_agg: Dict[str, list] = {}   # name -> [n, total, max]
            self.span_durs: Dict[str, deque] = {}
            self.hists: Dict[str, Histogram] = {}
            self.compiles: List[dict] = []
            self._flushed_counters: Dict[str, float] = {}
            self._flushed_hist_n: Dict[str, int] = {}
            # recent-event ring (kept even with a log sink): the /trace
            # endpoint's snapshot + last-event-by-kind for /statusz
            self._recent: deque = deque(maxlen=_RING_CAP)
            self.last_by_kind: Dict[str, dict] = {}
            self.t0_perf = time.perf_counter()
            # cxxlint: disable=wallclock — the shard-merge epoch: --merge
            # re-bases shards on the shared wall clock, never a duration
            self.t0_wall = time.time()

    def enable(self, log_path: Optional[str] = None,
               process_index: Optional[int] = None) -> None:
        self.reset()
        if process_index is None:
            # env fallback for library users under the multihost launcher.
            # Deliberately NOT PS_RANK: that var also selects an io shard
            # in single-process debugging (doc/io.md), where redirecting
            # the telemetry log by rank would be wrong.
            v = os.environ.get("CXXNET_WORKER_RANK")
            if v is not None:
                try:
                    process_index = int(v)
                except ValueError:
                    pass
        self.process_index = int(process_index or 0)
        path = log_path or None
        if path and "%d" in path:
            # the multihost shard contract: each rank writes its own file
            path = path.replace("%d", str(self.process_index))
        elif path and self.process_index:
            # no placeholder on a non-zero rank: suffix rather than
            # silently clobber rank 0's shard
            sys.stderr.write(
                "WARNING: telemetry_log %r has no %%d rank placeholder in "
                "a multi-process run; writing %s.%d instead so shard 0 is "
                "not clobbered\n" % (path, path, self.process_index))
            path = "%s.%d" % (path, self.process_index)
        self.log_path = path
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None
        if self.log_path:
            d = os.path.dirname(os.path.abspath(self.log_path))
            if d:
                os.makedirs(d, exist_ok=True)
            self._log_f = open(self.log_path, "w")
        self.enabled = True
        self.record({"ev": "meta", "pid": os.getpid(),
                     "t0_wall": self.t0_wall})

    def disable(self) -> None:
        self.enabled = False
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None
        self.log_path = None

    # -- recording -----------------------------------------------------
    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _ctx_stack(self) -> list:
        s = getattr(self._tls, "ctx", None)
        if s is None:
            s = self._tls.ctx = []
        return s

    def _win_stack(self) -> list:
        s = getattr(self._tls, "win", None)
        if s is None:
            s = self._tls.win = []
        return s

    def trace_context(self, request_id) -> TraceContext:
        return TraceContext(self, request_id)

    def current_trace(self) -> Optional[TraceContext]:
        s = getattr(self._tls, "ctx", None)
        return s[-1] if s else None

    def compile_window(self, label) -> CompileWindow:
        return CompileWindow(self, label)

    def current_compile_window(self) -> Optional[CompileWindow]:
        s = getattr(self._tls, "win", None)
        return s[-1] if s else None

    def mark(self, name: str) -> None:
        """Timestamp a named boundary on this thread's active trace
        context (no-op without one); with telemetry enabled the boundary
        is also recorded as a ``mark`` event in the stream."""
        tc = self.current_trace()
        if tc is not None:
            tc.mark(name)
        if self.enabled:
            self.record({"ev": "mark", "name": name})

    def _ts(self, t_perf: float) -> float:
        return t_perf - self.t0_perf

    def record(self, ev: dict) -> None:
        """Append one raw event (already-shaped dict). No-op if disabled."""
        if not self.enabled:
            return
        if "ts" not in ev:
            ev["ts"] = round(self._ts(time.perf_counter()), 6)
        with self._lock:
            self._append(ev)

    def _append(self, ev: dict) -> None:
        # lock held. Without a sink nothing drains _pending: bound it so
        # an enabled-without-log run (bench mode) cannot leak per-step
        if "p" not in ev:
            ev["p"] = self.process_index
        if "req" not in ev:
            # request attribution: the recording thread's active trace
            # context tags the event (thread-local read — safe under the
            # registry lock, never contended)
            tc = self.current_trace()
            if tc is not None:
                ev["req"] = tc.request_id
        self._pending.append(ev)
        self._recent.append(ev)
        self.last_by_kind[ev.get("ev", "?")] = ev
        if self._log_f is None and len(self._pending) > _PENDING_CAP:
            del self._pending[: _PENDING_CAP // 2]

    def span(self, name: str, keep: bool = False, **attrs):
        ann = _recording_annotation()
        if ann is not None:
            # a profiler session records: the span goes on its clock too
            ann = ann(name, **attrs)
        if keep:
            ring = self.kept_rings.get(name)
            if ring is None:
                with self._lock:
                    ring = self.kept_rings.setdefault(
                        name, deque(maxlen=KEPT_CAP))
            return _Span(self, name, attrs or None, ann, ring)
        if not self.enabled:
            return _NULL_SPAN if ann is None else ann
        return _Span(self, name, attrs or None, ann)

    def kept(self) -> Dict[str, list]:
        """The kept spans' account: name -> [(t0, dur), ...], oldest
        first, at most ``KEPT_CAP`` a name."""
        with self._lock:
            rings = list(self.kept_rings.items())
        return {name: list(ring) for name, ring in rings}

    def phase(self, name: str, parts: bool = False) -> _Phase:
        return _Phase(self, name, parts)

    def phases(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.phase_s)

    def count_path(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.path_n[name] = self.path_n.get(name, 0) + n
        self.count(name, n)

    def paths(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.path_n)

    def span_event(self, name: str, start_perf: float, dur: float,
                   **attrs) -> None:
        """Record a span from explicit perf_counter timings — for call
        sites that must time regardless of telemetry (the train loop's
        probes) or that only know post hoc whether the interval counts."""
        if not self.enabled:
            return
        self._record_span(name, start_perf, dur, len(self._stack()),
                          attrs or None)

    def _record_span(self, name, t0, dur, depth, attrs) -> None:
        if not self.enabled:     # disabled mid-span: drop silently
            return
        ev = {"ev": "span", "name": name, "ts": round(self._ts(t0), 6),
              "dur": round(dur, 6), "depth": depth,
              "tid": threading.get_ident()}
        if attrs:
            ev.update(attrs)
        with self._lock:
            self._append(ev)
            agg = self.span_agg.get(name)
            if agg is None:
                agg = self.span_agg[name] = [0, 0.0, 0.0]
                self.span_durs[name] = deque(maxlen=_DUR_CAP)
            agg[0] += 1
            agg[1] += dur
            if dur > agg[2]:
                agg[2] = dur
            self.span_durs[name].append(dur)
            # every span feeds the mergeable fixed-bucket histogram of its
            # name — the /metrics latency series and the shard-merge feed
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = Histogram()
            h.observe(dur)

    def count(self, name: str, n=1) -> None:
        tc = self.current_trace()
        if tc is not None:
            # per-request attribution rides the thread-local context even
            # with telemetry disabled (the flight recorder's counter view)
            tc.counts[name] = tc.counts.get(name, 0) + n
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def hist(self, name: str, value: float) -> None:
        """Observe one value (seconds) into the named fixed-bucket
        histogram — for latencies measured outside a span (or values that
        are not span-shaped at all)."""
        if not self.enabled:
            return
        with self._lock:
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = Histogram()
            h.observe(value)

    def declare_hist(self, name: str) -> None:
        """Register a histogram series with zero observations, so
        /metrics exports its (empty) bucket series from scrape one and
        /statusz shows it as "n/a" — a dashboard watching serve_ttft
        must see the series exist BEFORE the first request, not appear
        mid-run."""
        if not self.enabled:
            return
        with self._lock:
            self.hists.setdefault(name, Histogram())

    def gauge(self, name: str, value) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value
            self._append(
                {"ev": "gauge", "name": name, "value": value,
                 "ts": round(self._ts(time.perf_counter()), 6)})

    def record_compile(self, name: str, cause: str, seconds: float,
                       key=None) -> None:
        tc = self.current_trace()
        if tc is not None:
            # attribute the compile to the request that paid the cliff;
            # "off" = compile start relative to the context entry (the
            # backend call), so the trace export draws the bar inside
            # the phase that actually paid it — a fresh decode-program
            # compile runs in the decode phase, not prefill
            entry = {"name": name, "cause": cause,
                     "dur": round(seconds, 6)}
            if tc.t0 is not None:
                entry["off"] = round(
                    time.perf_counter() - seconds - tc.t0, 6)
            tc.compiles.append(entry)
        wins = getattr(self._tls, "win", None)
        if wins:
            # every active compile window on the thread sees the
            # compile — the batching dispatcher's batch-wide stall
            # attribution (a step compile stalls ALL slots aboard)
            wentry = {"name": name, "cause": cause,
                      "dur": round(seconds, 6)}
            if key is not None:
                wentry["key"] = str(key)
            for w in wins:
                w.compiles.append(dict(wentry))
        if not self.enabled:
            return
        ev = {"ev": "compile", "name": name, "cause": cause,
              "dur": round(seconds, 6),
              "ts": round(self._ts(time.perf_counter()) - seconds, 6),
              "tid": threading.get_ident()}
        if key is not None:
            ev["key"] = str(key)
        with self._lock:
            self._append(ev)
            self.compiles.append(ev)

    # -- sinks ---------------------------------------------------------
    def flush(self) -> None:
        """Write pending events to the JSONL log (if one is attached),
        plus a counters snapshot when any counter moved since the last
        flush — so a crashed run keeps its counters too, not only its
        spans. Without a log path events stay buffered in memory (the
        bench / library mode — summary() and chrome_trace() read them
        there)."""
        if self._log_f is None:
            return
        with self._lock:
            batch, self._pending = self._pending, []
            counters = None
            if self.counters != self._flushed_counters:
                counters = dict(self.counters)
                self._flushed_counters = dict(counters)
            hists = None
            hist_n = {k: h.n for k, h in self.hists.items()}
            if hist_n != self._flushed_hist_n:
                hists = {k: h.to_dict() for k, h in self.hists.items()}
                self._flushed_hist_n = hist_n
            ts = round(self._ts(time.perf_counter()), 6)
            p = self.process_index
        for ev in batch:
            self._log_f.write(json.dumps(ev) + "\n")
        if counters is not None:
            self._log_f.write(json.dumps(
                {"ev": "counters", "counters": counters,
                 "ts": ts, "p": p}) + "\n")
        if hists is not None:
            # cumulative snapshot, last-wins on re-read — like counters,
            # so a crashed run keeps its histograms to the last flush
            self._log_f.write(json.dumps(
                {"ev": "hists", "hists": hists, "ts": ts, "p": p}) + "\n")
        self._log_f.flush()

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._pending)

    def recent_events(self) -> List[dict]:
        """The last ~_RING_CAP events regardless of sink — the /trace
        endpoint's snapshot source."""
        with self._lock:
            return list(self._recent)

    def last_event(self, kind: str) -> Optional[dict]:
        """Most recent event of the given ``ev`` kind (e.g. "ckpt_save"
        for /statusz's checkpoint-age line)."""
        with self._lock:
            return self.last_by_kind.get(kind)

    def metrics_snapshot(self) -> dict:
        """One consistent point-in-time copy of everything /metrics
        serves: counters, gauges, raw histogram buckets, compile totals,
        uptime — taken under the lock so a scrape mid-step never sees a
        half-updated histogram."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "hists": {k: h.to_dict() for k, h in self.hists.items()},
                "compiles": len(self.compiles),
                "compile_s": round(sum(c["dur"] for c in self.compiles), 6),
                "phases": dict(self.phase_s),
                "uptime_s": time.perf_counter() - self.t0_perf,
                "process": self.process_index,
            }

    def summary(self) -> dict:
        """Aggregate view: per-span totals, counters, gauges, compiles,
        the set-up phases, p50/p90/p99 duration percentiles per span
        name, and ``step_time_ms``: the mean of ``train.period`` (None
        before two back-to-back ``Trainer.update`` calls)."""
        with self._lock:
            period = self.hists.get(_STEP_PERIOD)
            spans = {}
            for name, (n, total, mx) in self.span_agg.items():
                durs = sorted(self.span_durs[name])
                spans[name] = {
                    "count": n, "total_s": round(total, 6),
                    "mean_ms": round(1e3 * total / n, 4),
                    "max_ms": round(1e3 * mx, 4),
                    "p50_ms": round(1e3 * percentile(durs, 50), 4),
                    "p90_ms": round(1e3 * percentile(durs, 90), 4),
                    "p99_ms": round(1e3 * percentile(durs, 99), 4),
                }
            return {
                "spans": spans,
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "hists": {name: h.stats()
                          for name, h in self.hists.items()},
                "step_time_ms": None if period is None
                else period.stats()["mean_ms"],
                "compiles": {
                    "count": len(self.compiles),
                    "total_s": round(sum(c["dur"] for c in self.compiles),
                                     6),
                    "by_cause": count_by(self.compiles, "cause"),
                    "by_name": count_by(self.compiles, "name"),
                },
                "phases": {k: round(v, 6)
                           for k, v in self.phase_s.items()},
                "paths": dict(self.path_n),
            }

    def finish(self, close: bool = False) -> Optional[dict]:
        """Record the end-of-run summary event, flush the log, and (with a
        log path) write the Chrome-trace export next to it. Returns the
        summary dict (None if disabled)."""
        if not self.enabled:
            return None
        s = self.summary()
        if self.log_path:
            self.flush()   # drain events + counters snapshot first, so
            #                the summary below stays the log's last line
        self.record({"ev": "summary", "summary": s,
                     "ts": round(self._ts(time.perf_counter()), 6)})
        if self.log_path:
            self.flush()
            try:
                self.write_chrome_trace(self.log_path + ".trace.json")
            except Exception:
                pass
        if close:
            self.disable()
        return s

    # -- chrome trace ----------------------------------------------------
    def _all_events(self) -> List[dict]:
        """Everything recorded so far: the log file's lines (events already
        flushed) plus the in-memory pending buffer."""
        evs: List[dict] = []
        if self.log_path and os.path.exists(self.log_path):
            if self._log_f is not None:
                self.flush()
            with open(self.log_path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        evs.append(json.loads(line))
            return evs
        return self.events()

    def chrome_trace(self) -> dict:
        return events_to_chrome(self._all_events())

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def percentile(sorted_vals: list, p: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list (shared with
    tools/telemetry_report.py so live and offline numbers agree)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round((p / 100.0)
                                            * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def count_by(evs: List[dict], key: str) -> Dict[str, int]:
    """Histogram of ``ev[key]`` over a list of event dicts."""
    out: Dict[str, int] = {}
    for e in evs:
        k = e.get(key, "?")
        out[k] = out.get(k, 0) + 1
    return out



def events_to_chrome(evs: List[dict]) -> dict:
    """Build a chrome://tracing / Perfetto 'traceEvents' JSON object from a
    list of telemetry events (live or re-read from a JSONL log). Spans and
    compiles become complete ('X') events; gauges become counter ('C')
    tracks. Timestamps are microseconds relative to run start."""
    trace = []
    tids = {}

    def tid_of(ev):
        t = ev.get("tid", 0)
        if t not in tids:
            tids[t] = len(tids)
            trace.append({"ph": "M", "name": "thread_name", "pid": 0,
                          "tid": tids[t],
                          "args": {"name": "thread-%d" % tids[t]}})
        return tids[t]

    for ev in evs:
        kind = ev.get("ev")
        if kind == "span":
            trace.append({
                "ph": "X", "name": ev["name"], "pid": 0,
                "tid": tid_of(ev),
                "ts": round(ev["ts"] * 1e6, 1),
                "dur": round(ev["dur"] * 1e6, 1),
            })
        elif kind == "compile":
            trace.append({
                "ph": "X", "name": "compile:" + ev["name"], "pid": 0,
                "tid": tid_of(ev),
                "ts": round(max(ev.get("ts", 0.0), 0.0) * 1e6, 1),
                "dur": round(ev["dur"] * 1e6, 1),
                "args": {"cause": ev.get("cause", "?")},
            })
        elif kind == "gauge":
            trace.append({
                "ph": "C", "name": ev["name"], "pid": 0,
                "ts": round(ev["ts"] * 1e6, 1),
                "args": {"value": ev.get("value", 0)},
            })
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


# the canonical request-phase order (doc/observability.md glossary):
# queue_wait (accept -> worker pop), dispatch (pop -> backend call),
# prefill (backend call -> first token: TTFT's server-side share),
# decode (first token -> last token). The phases TILE the request's
# wall-clock — their sum is the request's total by construction.
REQUEST_PHASES = ("queue_wait", "dispatch", "prefill", "decode")


class FlightRecorder:
    """Bounded ring of the last N completed request traces — the
    per-request black box the serving frontend fills and statusd serves
    (``/trace?request=<id>`` as a Chrome trace, ``/requestz`` as a
    list). A record is one plain dict::

        {"id": "17", "outcome": "served", "tokens_in": 8, "tokens_out":
         16, "t_wall": <arrival unix time>, "total_s": 0.213,
         "ttft_s": 0.041, "tokens_per_s": 93.1,
         "phases": {"queue_wait": .., "dispatch": .., "prefill": ..,
                    "decode": ..},
         "recompiles": [{"name": "jit.decode_prefill", "cause":
                         "new_signature", "dur": 1.2}, ...],
         "counts": {<per-request counter deltas>}}

    Bounded and lock-guarded; eviction is oldest-first (deque maxlen).
    Jax-free and registry-independent, so it works with telemetry
    disabled — a flight record must survive a run that configured no
    JSONL log."""

    def __init__(self, cap: int = 256):
        self.cap = max(1, int(cap))
        self._lock = lockrank.lock("telemetry.flight")
        self._ring: deque = deque(maxlen=self.cap)

    def record(self, rec: dict) -> None:
        with self._lock:
            self._ring.append(rec)

    def get(self, request_id) -> Optional[dict]:
        rid = str(request_id)
        with self._lock:
            # newest-first: a repeated id resolves to the most recent
            # flight. Repeats happen across frontend restarts feeding
            # one recorder, and with client-chosen TRACE ids — a
            # client that reuses an id (or picks one colliding with a
            # local dense id) shadows the older record here; that is
            # the documented contract (doc/serving.md: choose unique
            # trace ids), not a lookup guarantee
            for rec in reversed(self._ring):
                if rec.get("id") == rid:
                    return rec
        return None

    def list(self) -> List[dict]:
        """Newest-first snapshot of the ring."""
        with self._lock:
            return list(reversed(self._ring))

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def request_chrome_trace(rec: dict, batch_iters=None) -> dict:
    """One flight record -> a Chrome-trace / Perfetto JSON object: the
    phases as back-to-back complete ('X') events on one lane (they tile
    the request's wall-clock), recompiles on a second lane inside the
    phase that paid them. Timestamps are µs relative to request accept,
    so the trace opens in ui.perfetto.dev showing exactly where this
    request's milliseconds went.

    ``batch_iters`` (optional, oldest-first) are the batching
    dispatcher's per-iteration scheduler records containing this
    request (``servd.BatchFlightRecorder.for_request``): they render as
    slot-Gantt lanes — one lane per decode slot, one bar per occupant
    run — aligned on the shared wall epoch (each iteration record's
    ``t_wall`` minus the request's), so the request's bar shows exactly
    which iterations it shared its decode with, and with whom."""
    rid = str(rec.get("id", "?"))
    trace: List[dict] = [
        {"ph": "M", "name": "process_name", "pid": 0,
         "args": {"name": "cxxnet-request %s" % rid}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
         "args": {"name": "phases"}},
    ]
    phases = rec.get("phases") or {}
    t = 0.0
    args = {"request": rid, "outcome": rec.get("outcome", "?"),
            "tokens_in": rec.get("tokens_in", 0),
            "tokens_out": rec.get("tokens_out", 0)}
    for name in REQUEST_PHASES:
        dur = float(phases.get(name, 0.0) or 0.0)
        if dur <= 0.0:
            continue
        trace.append({"ph": "X", "name": name, "pid": 0, "tid": 0,
                      "ts": round(t * 1e6, 1), "dur": round(dur * 1e6, 1),
                      "args": args})
        t += dur
    if t == 0.0:
        # no positive phase at all — an admission shed (honest zero
        # phases: nothing was dequeued or dispatched). The lane must
        # still be VISIBLE in a stitched cross-process trace (the
        # retried-request case renders the shed hop next to the served
        # one), so draw a 1µs marker named for the outcome.
        name = str(rec.get("outcome", "?"))
        if rec.get("shed_at"):
            name += "(%s)" % rec["shed_at"]
        trace.append({"ph": "X", "name": name, "pid": 0, "tid": 0,
                      "ts": 0.0, "dur": 1.0, "args": args})
    comp_t0 = float(phases.get("queue_wait", 0.0) or 0.0) \
        + float(phases.get("dispatch", 0.0) or 0.0)
    if rec.get("recompiles"):
        trace.append({"ph": "M", "name": "thread_name", "pid": 0,
                      "tid": 1, "args": {"name": "recompiles"}})
        ct = comp_t0
        for c in rec["recompiles"]:
            dur = float(c.get("dur", 0.0))
            off = c.get("off")
            # "off" places the bar where the compile actually ran
            # (relative to the backend call = prefill start) — a fresh
            # decode-program compile lands in the decode lane section,
            # matching the phase accounting; records without it (older
            # logs) fall back to stacking from prefill start
            ts = comp_t0 + max(0.0, float(off)) if off is not None \
                else ct
            trace.append({"ph": "X", "name": "compile:%s"
                          % c.get("name", "?"), "pid": 0, "tid": 1,
                          "ts": round(ts * 1e6, 1),
                          "dur": round(dur * 1e6, 1),
                          "args": {"cause": c.get("cause", "?"),
                                   "request": rid}})
            ct = ts + dur
    t0_wall = rec.get("t_wall")
    if batch_iters and t0_wall is not None:
        # slot-Gantt lanes: per slot, contiguous runs of the same
        # occupant merge into one bar (a straggler shows as one long
        # bar next to the short bars of the batchmates that came and
        # went). Each iteration spans [t_wall - step, t_wall] on the
        # shared wall epoch; clock skew vs the request's own accept
        # epoch is sub-ms on one host — good enough for a Gantt.
        runs: Dict[int, dict] = {}       # slot -> open run
        bars: List[tuple] = []           # (slot, closed run)
        for it in batch_iters:
            it_wall = it.get("t_wall")
            if it_wall is None:
                continue
            step_s = float(it.get("step_ms") or 0.0) / 1e3
            start = it_wall - t0_wall - step_s
            end = it_wall - t0_wall
            seen = set()
            for row in it.get("slots") or []:
                slot, occupant = int(row[0]), str(row[1])
                seen.add(slot)
                run = runs.get(slot)
                if run is not None and run["rid"] == occupant:
                    run["end"] = end
                    run["iters"][1] = it.get("iter")
                    continue
                if run is not None:
                    bars.append((slot, run))
                runs[slot] = {"rid": occupant, "start": start,
                              "end": end,
                              "iters": [it.get("iter"),
                                        it.get("iter")]}
            for slot in [s for s in runs if s not in seen]:
                bars.append((slot, runs.pop(slot)))
        bars.extend(runs.items())
        if bars:
            lanes = sorted({slot for slot, _ in bars})
            for slot in lanes:
                trace.append({"ph": "M", "name": "thread_name",
                              "pid": 0, "tid": 10 + slot,
                              "args": {"name": "batch slot %d" % slot}})
            for slot, run in bars:
                trace.append({
                    "ph": "X",
                    "name": run["rid"] if run["rid"] != rid
                    else "%s (this request)" % rid,
                    "pid": 0, "tid": 10 + slot,
                    "ts": round(run["start"] * 1e6, 1),
                    "dur": round(max(run["end"] - run["start"],
                                     1e-6) * 1e6, 1),
                    "args": {"occupant": run["rid"],
                             "iterations": "%s..%s" % tuple(run["iters"]),
                             "request": rid}})
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


class JitWatch:
    """Recompile detector: wraps a jitted callable and records a compile
    event whenever the wrapped jit cache grows — i.e. exactly when XLA
    traced + compiled for a genuinely new (signature, shape) key, and
    never on cache hits. The first detected compile is attributed to
    ``cause`` (what the call site knows: new_signature, rebuild_after_clear,
    decode_cache_drop); later growth on the same program means the inputs'
    shapes/shardings changed ("shape_change")."""

    __slots__ = ("_fn", "_name", "_cause_next", "_reg", "_key", "_built")

    def __init__(self, fn, name: str, cause: str = "new_signature",
                 registry: Optional[_Registry] = None, key=None):
        self._fn = fn
        self._name = name
        self._cause_next = cause
        self._reg = registry or _REG
        # the caller's program key (the trainer's jit-cache key): rides
        # the compile event and the perf ledger's ProgramCard
        self._key = key
        self._built = False

    def _first_call(self, args, kwargs):
        """The one call that traces, lowers and compiles (or loads) the
        program: its seconds go to the always-on phase account as
        ``jit.build/<name>``, with jax's own split of them beside it."""
        self._built = True
        with self._reg.phase("jit.build/" + self._name, parts=True):
            return self(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        if not self._built:
            return self._first_call(args, kwargs)
        reg = self._reg
        if not reg.enabled and reg.current_trace() is None \
                and reg.current_compile_window() is None \
                and reg.compile_hook is None:
            # an active trace context or compile window wants its
            # recompiles attributed (the flight recorder works with
            # telemetry disabled too), and the perf ledger wants its
            # cards either way
            return self._fn(*args, **kwargs)
        try:
            before = self._fn._cache_size()
        except Exception:
            before = None
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        if before is not None:
            try:
                grew = self._fn._cache_size() > before
            except Exception:
                grew = False
            if grew:
                reg.record_compile(self._name, self._cause_next, dt,
                                   key=self._key)
                hook = reg.compile_hook
                if hook is not None:
                    # the perf ledger (utils/perf.py): hand it the
                    # compiled callable + the triggering args so it can
                    # card the program. Supervised — a ledger bug must
                    # not kill the train step that compiled
                    try:
                        hook(self._name, self._cause_next, dt,
                             fn=self._fn, args=args, kwargs=kwargs,
                             key=self._key)
                    except Exception:
                        pass
                self._cause_next = "shape_change"
        return out

    def __getattr__(self, name):
        # forward lower()/trace()/cache introspection to the jitted fn
        return getattr(self._fn, name)


class BooksAuditor:
    """Conservation-law registry: named invariants over the serving
    books — "accepted = served + shed + errors + deadline + abandoned",
    "blocks total = free + live + retained", "tenant charges sum to the
    door books", "fleet sums = Σ replica feeds" — checked on a daemon
    sweep and at every /metrics scrape, so every number the request
    autopsy cites is provably reconciled.

    A law is a callable ``fn() -> Optional[str]``: ``None`` means the
    books reconcile (or the law could not take a consistent snapshot —
    inconclusive PASSES; a law must never false-latch off a racy read:
    use a stable-snapshot double-read and return None when the bracket
    moved), a string is the violation detail. The first violation
    LATCHES the law sticky-broken (``cxxnet_books_broken{law=...}``
    stays 1 until ``reset()``), emits exactly one ``books_broken``
    transition event (``broken: 1`` carrying the detail; ``reset()``
    emits the matching ``broken: 0`` clear), and bumps the
    ``books.violations`` counter — a single bad snapshot can never flap
    the gauge, and telemetry_report's exit-2 gate sees the latch even
    if every later sweep reconciles.

    Laws run OUTSIDE the auditor lock (a law reads other subsystems'
    locked state; rank "telemetry.audit" keeps the latch bookkeeping
    below only the registry itself), and the transition events are
    emitted outside it too. A law that RAISES is counted
    (``law_errors``) but treated as inconclusive: laws are registered
    at start() and unregistered at drain(), and a transient exception
    during concurrent teardown must not break the books."""

    def __init__(self, registry: Optional["_Registry"] = None):
        self._lock = lockrank.lock("telemetry.audit")
        self._registry = registry
        self._laws: Dict[str, object] = {}
        self._broken: Dict[str, str] = {}
        self.violations = 0          # cumulative latches (survives reset)
        self.sweeps = 0
        self.law_errors = 0
        self._thread: Optional[threading.Thread] = None
        self._stop_ev = threading.Event()

    def _reg(self) -> "_Registry":
        return self._registry if self._registry is not None else _REG

    def register(self, name: str, fn) -> None:
        """Install (or replace) the named law."""
        with self._lock:
            self._laws[str(name)] = fn

    def unregister(self, name: str) -> None:
        """Remove the named law. A latch it already tripped STAYS
        latched — a violation observed just before drain must still
        fail the next scrape."""
        with self._lock:
            self._laws.pop(str(name), None)

    def sweep(self) -> Dict[str, Optional[str]]:
        """Evaluate every registered law once. Returns {law: detail}
        (None = reconciled/inconclusive) for this sweep; latch state is
        cumulative and read via snapshot()."""
        with self._lock:
            laws = list(self._laws.items())
        results: Dict[str, Optional[str]] = {}
        errors = 0
        for name, fn in laws:
            try:
                detail = fn()
            except Exception:
                errors += 1
                detail = None
            results[name] = None if detail is None else str(detail)
        newly: List[tuple] = []
        with self._lock:
            self.sweeps += 1
            self.law_errors += errors
            for name, detail in results.items():
                if detail is not None and name not in self._broken:
                    self._broken[name] = detail
                    self.violations += 1
                    newly.append((name, detail))
        reg = self._reg()
        for name, detail in newly:
            reg.count("books.violations")
            reg.record({"ev": "books_broken", "law": name, "broken": 1,
                        "detail": detail})
        return results

    def snapshot(self) -> dict:
        """Point-in-time view for /metrics."""
        with self._lock:
            return {"laws": sorted(self._laws),
                    "broken": dict(self._broken),
                    "violations": self.violations,
                    "sweeps": self.sweeps,
                    "law_errors": self.law_errors}

    def reset(self) -> None:
        """Clear every latch, emitting the ``broken: 0`` transition for
        each — the operator's acknowledge. ``violations`` stays
        cumulative."""
        with self._lock:
            cleared = sorted(self._broken)
            self._broken.clear()
        reg = self._reg()
        for name in cleared:
            reg.record({"ev": "books_broken", "law": name, "broken": 0})

    def start(self, period_s: float = 1.0) -> None:
        """Start the daemon sweep loop (idempotent)."""
        with self._lock:
            if self._thread is not None:
                return
            self._stop_ev.clear()
            t = threading.Thread(target=self._run,
                                 args=(max(0.05, float(period_s)),),
                                 name="books-auditor", daemon=True)
            self._thread = t
        t.start()

    def _run(self, period_s: float) -> None:
        while not self._stop_ev.wait(period_s):
            try:
                self.sweep()
            except Exception:
                pass

    def stop(self) -> None:
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            self._stop_ev.set()
            t.join(timeout=2.0)


# ----------------------------------------------------------------------
# module-level singleton surface
_REG = _Registry()


def enable(log_path: Optional[str] = None,
           process_index: Optional[int] = None) -> None:
    _REG.enable(log_path, process_index=process_index)


def disable() -> None:
    _REG.disable()


def enabled() -> bool:
    return _REG.enabled


def reset() -> None:
    _REG.reset()


def span(name: str, keep: bool = False, **attrs):
    return _REG.span(name, keep, **attrs)


def kept() -> Dict[str, list]:
    """The always-on account of kept spans (``span(name, keep=True)``):
    name -> the (t0, dur) of its last ``KEPT_CAP`` occurrences."""
    return _REG.kept()


def phase(name: str) -> _Phase:
    """A span that also writes the always-on phase account."""
    return _REG.phase(name)


def phases() -> Dict[str, float]:
    """The phase account: name -> seconds of the name's first occurrence
    in this process, recorded whether telemetry is enabled or not."""
    return _REG.phases()


def span_event(name: str, start_perf: float, dur: float, **attrs) -> None:
    _REG.span_event(name, start_perf, dur, **attrs)


def count(name: str, n=1) -> None:
    _REG.count(name, n)


def count_path(name: str, n: int = 1) -> None:
    """A counter that also writes the always-on path account."""
    _REG.count_path(name, n)


def paths() -> Dict[str, int]:
    """The path account: name -> times a traced layer took that lowering
    in this process, recorded whether telemetry is enabled or not."""
    return _REG.paths()


def gauge(name: str, value) -> None:
    _REG.gauge(name, value)


def hist(name: str, value: float) -> None:
    _REG.hist(name, value)


def declare_hist(name: str) -> None:
    _REG.declare_hist(name)


def trace_context(request_id) -> TraceContext:
    return _REG.trace_context(request_id)


def current_trace() -> Optional[TraceContext]:
    return _REG.current_trace()


def compile_window(label) -> CompileWindow:
    """A stall-attribution bracket for work outside any request's trace
    context (``with compile_window("step:b4") as w:`` — then read
    ``w.compiles`` / ``w.stall_s``). Works with telemetry disabled."""
    return _REG.compile_window(label)


def current_compile_window() -> Optional[CompileWindow]:
    return _REG.current_compile_window()


def mark(name: str) -> None:
    _REG.mark(name)


def event(ev: dict) -> None:
    _REG.record(ev)


def record_compile(name: str, cause: str, seconds: float, key=None) -> None:
    _REG.record_compile(name, cause, seconds, key)


def jit_watch(fn, name: str, cause: str = "new_signature",
              key=None) -> JitWatch:
    return JitWatch(fn, name, cause=cause, key=key)


def flush() -> None:
    _REG.flush()


def finish(close: bool = False) -> Optional[dict]:
    return _REG.finish(close=close)


def summary() -> dict:
    return _REG.summary()


def events() -> List[dict]:
    return _REG.events()


def recent_events() -> List[dict]:
    return _REG.recent_events()


def wall_epoch() -> float:
    """The registry's wall-clock epoch: event ``ts`` seconds are
    relative to this, so cross-process alignment (the /eventz incident
    merge, --merge shard re-basing) is ``t0_wall + ts``."""
    return _REG.t0_wall


def last_event(kind: str) -> Optional[dict]:
    return _REG.last_event(kind)


def chrome_trace() -> dict:
    return _REG.chrome_trace()


def write_chrome_trace(path: str) -> str:
    return _REG.write_chrome_trace(path)


# the process-wide conservation-law auditor: subsystems register laws
# at start() (servd's door books, kvblocks' block conservation, routerd's
# federation sums) and unregister them at drain(); statusd sweeps at
# every scrape and exports the latches as cxxnet_books_broken{law=...}
_AUDITOR = BooksAuditor()


def auditor() -> BooksAuditor:
    return _AUDITOR


def audit_register(name: str, fn) -> None:
    _AUDITOR.register(name, fn)


def audit_unregister(name: str) -> None:
    _AUDITOR.unregister(name)


def audit_sweep() -> Dict[str, Optional[str]]:
    return _AUDITOR.sweep()


def sample_device_memory() -> Optional[dict]:
    """The first local device's allocator stats, recorded as gauges
    (device memory high-water) when telemetry is on. The CPU backend keeps
    no such stats and yields None; an accelerator that reports none is an
    error, not a quiet gap in the record."""
    import jax
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if not stats:
        if dev.platform != "cpu":
            raise RuntimeError("%s (%s) reports no memory_stats()"
                               % (dev, dev.device_kind))
        return None
    if _REG.enabled:
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                gauge("device." + k, int(stats[k]))
    return stats
