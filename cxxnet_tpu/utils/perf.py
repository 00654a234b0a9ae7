"""Live program performance ledger: per-compiled-program cost/memory
cards, MFU & roofline-efficiency gauges, HBM headroom accounting, and an
on-demand profiler capture guard.

An offline tool can say "does it fit" (``tools/memory_report.py``
compiles a step and reads XLA's ``memory_analysis()``) and a profiler
trace where the time went (``tools/trace_layers.py``). Neither feeds
the *running* system — production ML infra treats cost models as
first-class runtime objects (TF's system paper, arXiv:1605.08695) and
compile-time cost metadata as the optimization currency (TVM,
arXiv:1802.04799). This module is that runtime spine:

* **DeviceSpec** — the peak-FLOP/s + HBM-bandwidth + HBM-capacity table
  (``DEVICE_SPECS``), keyed by the ``device_kind`` string jax reports
  for the chip, each entry with the source of its figures;
  ``tools/memory_report.py`` reads the same table. A device that is
  not in the table is an error, not a default; a CPU backend has no
  entry, so a CPU run reports no MFU, roofline or headroom figure at
  all.

* **ProgramCard** — one card per (program name, input-shapes signature)
  the trainer compiles. The recompile detector
  (``telemetry.jit_watch``) already sees every compile; with the ledger
  enabled it hands the compiled callable + its call arguments to
  ``Ledger.on_compile``, which records the compile wall time
  immediately and queues an analysis job. The **carder thread**
  completes the card off the hot path: ``fn.lower(shapes)`` (the trace
  is cached from the triggering call — milliseconds) yields XLA
  ``cost_analysis()`` FLOPs + bytes accessed; ``lowered.compile()``
  (a real second compile — the reason this runs on a background
  thread, never inside a serving request) yields ``memory_analysis()``
  argument/temp/output bytes per device. A roofline-predicted
  execution time falls out: ``max(flops/peak_flops, bytes/hbm_bw)``.

* **live gauges** — ``snapshot()`` joins each card against the
  program's *measured* latency histogram (``MEASURED_SERIES``: the
  telemetry series the trainer already feeds — ``train.period``,
  ``decode.prefill``, ``decode.decode``, ...):
  ``mfu_pct`` = flops / (measured time x peak), ``roofline_eff_pct`` =
  predicted / measured time (under 100 = slower than the hardware
  allows). The measured time is the series' p50, but for a series in
  ``MEAN_SERIES`` its mean: the train step's is ``train.period``, the
  distance between the entries of back-to-back ``Trainer.update`` calls,
  whose mean is the step as the device paces it and whose p50 is not
  (the dispatch returns in a few ms until the runtime's limit of steps
  in flight holds it for a whole one). Aggregates:
  ``hbm_peak_bytes`` (max per-device peak over cards — the number the
  paged-KV allocator will be sized against) and ``hbm_headroom_bytes``
  vs the spec capacity. statusd renders all of it: ``/programz`` (the
  per-program table), ``/metrics`` (``cxxnet_program_*`` /
  ``cxxnet_hbm_*`` series), and each completed card lands in the
  telemetry JSONL as a ``program_card`` event for
  ``tools/telemetry_report.py``'s program-ledger section.

* **ProfilerCapture** — the guard behind statusd's ``/profilez?secs=N``:
  one jax.profiler trace capture at a time into a run-scoped directory
  (conf key ``profilez_dir``), so a live slow replica can be xprof'd
  without restarting it. Injectable trace function keeps it testable
  (and the selftest) jax-free.

Jax-free at import (like servd/statusd/health): jax is imported lazily
inside the capture paths, which only run after a jitted call already
proved jax present. ``python -m cxxnet_tpu.utils.perf --selftest``
exercises card math, gauge rendering, /programz + /profilez over a real
socket, and the capture guard with faked analyses; ``make check`` gates
on it. Enabled via the conf key ``perf_ledger`` (learn_task wires it
whenever telemetry is on); disabled, the only cost is the recompile
detector's existing bookkeeping.
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple

from . import lockrank
from . import telemetry

__all__ = [
    "DeviceSpec", "DEVICE_SPECS", "TARGET_DEVICE_KIND", "device_spec",
    "current_device_spec", "MEASURED_SERIES", "MEAN_SERIES", "measured_ms",
    "Ledger", "ProfilerCapture",
    "ledger", "enable", "disable", "enabled", "drain", "reset",
    "decode_bound_tokens_per_s", "shapes_signature", "predicted_seconds",
    "footprint_bytes", "selftest",
]


class DeviceSpec:
    """One chip's roofline constants: peak matmul FLOP/s (bf16), HBM
    bandwidth (bytes/s) and HBM capacity (bytes), with where the figures
    come from. The single source the live ledger AND the offline tools
    read."""

    __slots__ = ("name", "peak_flops", "hbm_bw", "hbm_capacity", "source")

    def __init__(self, name: str, peak_flops: float, hbm_bw: float,
                 hbm_capacity: float, source: str = ""):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.hbm_capacity = float(hbm_capacity)
        self.source = source

    def to_dict(self) -> dict:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "hbm_capacity": self.hbm_capacity,
                "source": self.source}

    def __repr__(self):
        return ("DeviceSpec(%s, %.0f GFLOP/s, %.0f GB/s, %.1f GiB)"
                % (self.name, self.peak_flops / 1e9, self.hbm_bw / 1e9,
                   self.hbm_capacity / 2**30))


# Keyed by ``jax.devices()[0].device_kind`` exactly as the chip reports
# it. Only kinds this repo has run on are listed: the key of any other
# chip is a guess until a run prints it (learn_task's start-up line does).
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "TPU v5 lite": DeviceSpec(
        "TPU v5 lite", 197.0e12, 819.0e9, 16 * 2.0**30,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 16 GB HBM2e at 819 GB/s per chip"),
}

# the chip the offline tools model when no run names one: the TPU v5e
# every on-chip record of this repo comes from
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_spec(kind: str = TARGET_DEVICE_KIND) -> DeviceSpec:
    """The spec for a ``device_kind``; an unknown kind raises, naming
    the kinds the table holds."""
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise KeyError(
            "no peak figures for device_kind %r (perf.DEVICE_SPECS holds "
            "%s): add an entry with its source" % (
                kind, sorted(DEVICE_SPECS))) from None


def current_device_spec() -> Optional[DeviceSpec]:
    """The spec for the device THIS process computes on, or None on a CPU
    backend — a CPU has no honest single peak, so a CPU run gets no MFU,
    roofline or HBM-headroom figure. An accelerator missing from the
    table raises (``device_spec``).

    Initializes the jax backend, so the ledger calls it lazily — at first
    card completion, never at enable() time, which runs before the
    trainer's ``dev =`` platform selection."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return device_spec(dev.device_kind)


# program name -> the telemetry histogram that MEASURES its executions
# (the join key between a card's predicted time and reality). These are
# the series the trainer already feeds.
MEASURED_SERIES = {
    "jit.train_step": "train.period",
    "jit.eval_fwd": "eval.forward",
    "jit.predict": "predict",
    "jit.decode_prefill": "decode.prefill",
    "jit.decode_step": "decode.decode",
    "jit.beam_decode": "decode.beam",
}

# the series whose MEAN is the program's time, where the others' p50 is:
# single periods are bimodal under the runtime's limit of steps in flight
# (several entries a few ms apart, then one a step), their mean is the step
MEAN_SERIES = frozenset({"train.period"})


def measured_ms(series: str, stats: dict):
    """The time a card divides by, from its series' ``Histogram.stats()``."""
    return stats["mean_ms" if series in MEAN_SERIES else "p50_ms"]


_DTYPE_SHORT = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int32": "i32", "int64": "i64", "int8": "i8",
    "uint8": "u8", "uint32": "u32", "bool": "b1",
}


def _leaves(obj):
    """Jax-free pytree leaf walk (list/tuple/dict containers — the only
    shapes the trainer's call signatures use)."""
    if isinstance(obj, (list, tuple)):
        for v in obj:
            yield from _leaves(v)
    elif isinstance(obj, dict):
        for k in sorted(obj, key=str):
            yield from _leaves(obj[k])
    else:
        yield obj


def shapes_signature(args, kwargs=None) -> Tuple[str, str]:
    """(display, hash) signature of a call's input shapes/dtypes —
    the card key's second half. Duck-typed (``.shape``/``.dtype``), so
    fakes work jax-free; non-array leaves (None, python scalars) are
    folded in by repr. The display form is truncated for tables; the
    crc32 hash is the stable key."""
    toks: List[str] = []
    for leaf in _leaves((args, kwargs or {})):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            toks.append("%s[%s]" % (
                _DTYPE_SHORT.get(str(dtype), str(dtype)),
                ",".join(str(int(d)) for d in shape)))
        elif leaf is None:
            continue
        else:
            toks.append(repr(leaf)[:16])
    full = ",".join(toks)
    h = "%08x" % (zlib.crc32(full.encode("utf-8", "replace"))
                  & 0xffffffff)
    if len(full) > 56:
        disp = "%s..(%d args)#%s" % (full[:40], len(toks), h)
    else:
        disp = full or "()"
    return disp, h


def _mem_field(mem, name):
    """Read one memory_analysis field from either the XLA stats object
    (attributes) or a faked dict (tests)."""
    if mem is None:
        return None
    if isinstance(mem, dict):
        v = mem.get(name)
    else:
        v = getattr(mem, name, None)
    return int(v) if v is not None else None


def predicted_seconds(flops, bytes_accessed,
                      spec: Optional[DeviceSpec]) -> Optional[float]:
    """THE roofline execution-time bound: max(flops/peak, bytes/bw).
    None when neither term is known, or without a spec (CPU backend)."""
    if spec is None:
        return None
    bounds = []
    if flops is not None and spec.peak_flops > 0:
        bounds.append(float(flops) / spec.peak_flops)
    if bytes_accessed is not None and spec.hbm_bw > 0:
        bounds.append(float(bytes_accessed) / spec.hbm_bw)
    return max(bounds) if bounds else None


def footprint_bytes(mem) -> Optional[int]:
    """THE per-device program footprint: XLA argument+temp+output bytes
    (the total tools/memory_report.py prints) — shared definition, same
    reason as ``predicted_seconds``. Accepts the XLA stats object or a
    faked dict; None when no field is present."""
    parts = [_mem_field(mem, k) for k in
             ("argument_size_in_bytes", "temp_size_in_bytes",
              "output_size_in_bytes")]
    if all(v is None for v in parts):
        return None
    return sum(v or 0 for v in parts)


# bound on the compile flight ring (per-compile records with trigger
# attribution) — sized like telemetry.FlightRecorder's request ring:
# the full grid of a serving run fits with room for reload rebuilds
COMPILE_RING_CAP = 256


class Ledger:
    """The program performance ledger: cards keyed by (program name,
    shapes hash), completed asynchronously by the carder thread, joined
    against measured latency histograms at snapshot time. One per
    process (the module singleton); tests build isolated instances
    against private telemetry registries."""

    def __init__(self, registry=None, spec: Optional[DeviceSpec] = None,
                 compile_ring_cap: int = COMPILE_RING_CAP):
        # ranked between telemetry.flight and telemetry.registry: card
        # completion emits the program_card event under this lock (the
        # SLOTracker precedent — completion order must match log order)
        self._cond = lockrank.condition("perf.ledger")
        self._registry = registry
        self.spec = spec
        self.enabled = False
        self._cards: Dict[Tuple[str, str], dict] = {}
        self._order: List[Tuple[str, str]] = []
        self._jobs: deque = deque()
        self._busy = 0
        self._thread: Optional[threading.Thread] = None
        # the compile flight recorder (doc/performance.md "Compile
        # cliff"): a bounded ring of per-compile records with trigger
        # attribution (which request / dispatcher window paid the
        # cliff), plus the warm-grid readiness account — the expected
        # program grid vs the keys compiled so far. One lock guards
        # both (rank perf.compiles); the program_compile JSONL event is
        # emitted OUTSIDE it (the IO-outside-the-lock rule).
        self._clock = lockrank.lock("perf.compiles")
        self._ring: deque = deque(maxlen=max(1, int(compile_ring_cap)))
        self._compile_seq = 0
        self._expected: Dict[str, str] = {}   # key str -> bucket label
        self._warm: set = set()               # key strs compiled so far
        # set_decode_kv: a callable returning the serving frontend's
        # live decode KV-cache bytes — the decode cache is persistent
        # device state BETWEEN program executions, so the HBM headroom
        # account must charge it next to the peak program footprint
        self._decode_kv_fn = None

    def _reg(self):
        return self._registry if self._registry is not None \
            else telemetry._REG

    # -- lifecycle -----------------------------------------------------
    def enable(self, spec: Optional[DeviceSpec] = None) -> "Ledger":
        """Arm the ledger and hook the recompile detector. The spec
        stays UNRESOLVED unless given: enable() runs before the trainer
        selects a platform, and probing jax here would initialize the
        default backend under a ``dev = cpu`` run. It resolves lazily —
        via ``current_device_spec()`` — at first card completion /
        snapshot, when a jit provably already ran."""
        with self._cond:
            if spec is not None:
                self.spec = spec
            self.enabled = True
        self._reg().compile_hook = self.on_compile
        return self

    def disable(self, join_timeout: float = 20.0) -> None:
        """Unhook, drop queued jobs, and JOIN the carder thread
        (bounded): a daemon thread still inside a native XLA compile at
        interpreter teardown segfaults the process — the same crash
        class ProfilerCapture.shutdown() guards against."""
        reg = self._reg()
        if reg.compile_hook == self.on_compile:
            reg.compile_hook = None
        with self._cond:
            self.enabled = False
            self._jobs.clear()
            self._cond.notify_all()
            t = self._thread
        if t is not None and t.is_alive():
            t.join(join_timeout)

    def reset(self) -> None:
        with self._cond:
            self._cards.clear()
            del self._order[:]
            self._jobs.clear()
        with self._clock:
            self._ring.clear()
            self._warm.clear()
            # the expected grid survives: it is conf-derived wiring
            # (like the compile hook), not per-run measurement state

    # -- capture -------------------------------------------------------
    def on_compile(self, name: str, cause: str, seconds: float,
                   fn=None, args=(), kwargs=None, key=None) -> None:
        """The recompile detector's hook: called once per genuinely new
        (program, signature) compile with the jitted callable and the
        triggering call's arguments. Records compile wall time NOW;
        queues the cost/memory analysis for the carder thread (the
        memory tier pays a real second compile — never on this, the
        hot, thread). Never raises: a ledger bug must not kill a train
        step or a served request."""
        try:
            if not self.enabled:
                return
            disp, h = shapes_signature(args, kwargs)
            with self._cond:
                existing = self._cards.get((name, h))
                need = fn is not None and (existing is None
                                           or existing["status"] == "new")
            # abstractify OUTSIDE the lock (the work-outside-the-lock
            # rule the carder follows): shape/dtype/sharding metadata
            # survives donation, the buffers may not, and a big params
            # pytree walk must not block a /metrics scrape — and only
            # for a card that still needs analysis (a reload's
            # rebuild_after_clear re-compiles already-carded programs)
            structs = self._abstractify(args, kwargs) if need else None
            with self._cond:
                card = self._cards.get((name, h))
                if card is None:
                    card = self._new_card(name, h, disp, cause, key)
                    self._cards[(name, h)] = card
                    self._order.append((name, h))
                card["compiles"] += 1
                card["compile_s"] = round(card["compile_s"]
                                          + float(seconds), 6)
                if card["status"] == "new" and fn is not None:
                    if structs is not None:
                        card["status"] = "pending"
                        self._jobs.append((name, h, fn, structs[0],
                                           structs[1]))
                        self._cond.notify()
                        self._ensure_thread()
                    else:
                        card["status"] = "error"
                        card["error"] = "could not abstract call args"
            self._record_flight(name, cause, seconds, disp, h, key)
            reg = self._reg()
            reg.count("perf.compile_hooks")
        except Exception:
            reg = self._reg()
            reg.count("perf.capture_errors")

    def _record_flight(self, name, cause, seconds, disp, h, key) -> None:
        """One compile into the flight ring + the warm-grid account,
        with trigger attribution: the active trace context (a serving
        request paying the cliff at prefill) and/or the active compile
        window (the dispatcher's session-creation / batch-step bracket).
        Emits the transition-style ``program_compile``
        JSONL event OUTSIDE the ring lock."""
        reg = self._reg()
        tc = reg.current_trace()
        win = reg.current_compile_window()
        ks = str(key) if key is not None else None
        rec = {"name": name, "key": ks, "cause": cause,
               "shapes": disp, "sig": h,
               "seconds": round(float(seconds), 6),
               # the compile STARTED seconds ago (same convention as
               # the telemetry compile event's ts)
               "ts": round(reg._ts(time.perf_counter()) - seconds, 6),
               "trigger_request": tc.request_id if tc is not None
               else None,
               "trigger_context": win.label if win is not None else None}
        with self._clock:
            self._compile_seq += 1
            rec["seq"] = self._compile_seq
            self._ring.append(dict(rec))
            if ks is not None:
                self._warm.add(ks)
            expected = len(self._expected)
            warm = sum(1 for k in self._expected if k in self._warm)
        ev = {"ev": "program_compile"}
        ev.update(rec)
        if expected:
            # the readiness transition rides the event: the offline
            # report replays warm-up as a 0 -> 100 trajectory
            ev["warm_programs"] = warm
            ev["expected_programs"] = expected
            ev["ready_pct"] = round(100.0 * warm / expected, 2)
        reg.record(ev)

    def recent_compiles(self, n: Optional[int] = None) -> List[dict]:
        """Newest-first snapshot of the compile flight ring."""
        with self._clock:
            out = [dict(r) for r in self._ring]
        out.reverse()
        return out[:n] if n else out

    def set_expected_grid(self, entries) -> None:
        """Register the EXPECTED program grid (the warm-grid readiness
        denominator): an iterable of ``(key, bucket_label)`` pairs — or
        bare keys — where ``key`` is the trainer's jit-cache key for a
        program conf implies will compile (``Trainer.
        expected_decode_grid`` enumerates the serving grid). Replaces
        any previous grid; keys are matched by ``str()`` against the
        keys the recompile detector reports."""
        exp: Dict[str, str] = {}
        for e in entries or ():
            if isinstance(e, (tuple, list)) and len(e) == 2 \
                    and isinstance(e[1], str):
                exp[str(e[0])] = e[1]
            else:
                exp[str(e)] = ""
        with self._clock:
            self._expected = exp

    def readiness(self) -> dict:
        """The warm-grid account: expected vs warm program counts,
        headline ``ready_pct`` (None when no grid is registered —
        absence is the capability signal, like every federation field)
        and the per-bucket-label breakdown."""
        with self._clock:
            exp = dict(self._expected)
            warm_set = set(self._warm)
        buckets: Dict[str, dict] = {}
        warm = 0
        for k, label in sorted(exp.items()):
            st = buckets.setdefault(label or "all",
                                    {"expected": 0, "warm": 0})
            st["expected"] += 1
            if k in warm_set:
                st["warm"] += 1
                warm += 1
        for st in buckets.values():
            st["ready_pct"] = round(100.0 * st["warm"] / st["expected"],
                                    2)
        return {"expected": len(exp), "warm": warm,
                "ready_pct": round(100.0 * warm / len(exp), 2)
                if exp else None,
                "cold_keys": sorted(k for k in exp
                                    if k not in warm_set)[:16],
                "buckets": buckets}

    @staticmethod
    def _new_card(name, h, disp, cause, key) -> dict:
        return {"name": name, "shapes": disp, "sig": h,
                "key": str(key) if key is not None else None,
                "cause": cause, "compiles": 0, "compile_s": 0.0,
                "flops": None, "bytes_accessed": None,
                "arg_bytes": None, "temp_bytes": None, "out_bytes": None,
                "gen_code_bytes": None, "peak_bytes": None,
                "predicted_s": None, "status": "new", "error": None}

    @staticmethod
    def _abstractify(args, kwargs):
        """jax.ShapeDtypeStruct pytrees mirroring the call's arguments
        (shape + dtype + sharding — metadata that survives donated
        buffers being consumed). None on any surprise."""
        try:
            import jax

            def struct(a):
                shape = getattr(a, "shape", None)
                dtype = getattr(a, "dtype", None)
                if shape is None or dtype is None:
                    return a          # python scalar / None: pass through
                sharding = getattr(a, "sharding", None)
                try:
                    return jax.ShapeDtypeStruct(shape, dtype,
                                                sharding=sharding)
                except Exception:
                    return jax.ShapeDtypeStruct(shape, dtype)

            def walk(o):
                if isinstance(o, (list, tuple)):
                    return type(o)(walk(v) for v in o)
                if isinstance(o, dict):
                    return {k: walk(v) for k, v in o.items()}
                return struct(o)

            return walk(list(args)), walk(dict(kwargs or {}))
        except Exception:
            return None

    def _ensure_thread(self) -> None:
        # under the lock
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._carder, name="cxn-perf-carder", daemon=True)
            self._thread.start()

    def _carder(self) -> None:
        """Background card completion: one analysis job at a time, the
        lower/compile work OUTSIDE the lock (a compile in here must
        never block a scrape or the next on_compile)."""
        while True:
            with self._cond:
                while not self._jobs and self.enabled:
                    self._cond.wait(timeout=1.0)
                if not self._jobs:
                    if not self.enabled:
                        return
                    continue
                name, h, fn, sargs, skwargs = self._jobs.popleft()
                self._busy += 1
            cost = mem = None
            err = None
            try:
                cost, mem = self._capture(fn, sargs, skwargs)
            except Exception as e:
                err = "%s: %s" % (type(e).__name__, e)
            try:
                self.complete_card(name, h, cost=cost, mem=mem, error=err)
            finally:
                with self._cond:
                    self._busy -= 1
                    self._cond.notify_all()

    @staticmethod
    def _capture(fn, sargs, skwargs):
        """(cost_analysis dict, memory stats) of the program, from a
        re-lower (cheap: the trace cache is warm from the triggering
        call) + a second compile (the expensive half — why this runs on
        the carder thread)."""
        lowered = fn.lower(*sargs, **skwargs)
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        mem = lowered.compile().memory_analysis()
        return (dict(cost) if cost else {}), mem

    def complete_card(self, name: str, sig: str, cost=None, mem=None,
                      error: Optional[str] = None) -> Optional[dict]:
        """Fill a card's analysis fields (XLA dicts/objects or faked
        test dicts), compute the roofline prediction, and publish the
        ``program_card`` telemetry event. Public so jax-free tests (and
        the selftest) can exercise the math with faked analyses."""
        spec = self.spec or current_device_spec()
        with self._cond:
            card = self._cards.get((name, sig))
            if card is None:
                card = self._new_card(name, sig, sig, "unknown", None)
                self._cards[(name, sig)] = card
                self._order.append((name, sig))
            if error is not None:
                card["status"] = "error"
                card["error"] = error[:200]
            else:
                card["status"] = "ready"
                if cost:
                    f = cost.get("flops")
                    b = cost.get("bytes accessed")
                    card["flops"] = float(f) if f is not None else None
                    card["bytes_accessed"] = float(b) if b is not None \
                        else None
                card["arg_bytes"] = _mem_field(mem,
                                               "argument_size_in_bytes")
                card["temp_bytes"] = _mem_field(mem, "temp_size_in_bytes")
                card["out_bytes"] = _mem_field(mem, "output_size_in_bytes")
                card["gen_code_bytes"] = _mem_field(
                    mem, "generated_code_size_in_bytes")
                card["peak_bytes"] = footprint_bytes(mem)
                card["predicted_s"] = predicted_seconds(
                    card["flops"], card["bytes_accessed"], spec)
            # the spec's peaks ride the event so the offline report can
            # recompute MFU/eff joins without guessing the chip
            ev = {"ev": "program_card",
                  "spec": spec.name if spec else None,
                  "spec_peak_flops": spec.peak_flops if spec else None,
                  "spec_hbm_bw": spec.hbm_bw if spec else None}
            ev.update({k: card[k] for k in (
                "name", "shapes", "sig", "key", "cause", "compiles",
                "compile_s", "flops", "bytes_accessed", "arg_bytes",
                "temp_bytes", "out_bytes", "peak_bytes", "predicted_s",
                "status", "error")})
            reg = self._reg()
            reg.count("perf.cards")
            reg.record(ev)
            return dict(card)

    def drain(self, timeout: float = 10.0) -> bool:
        """Wait for queued analysis jobs to finish (the end-of-run flush
        wants complete cards). True when idle."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._jobs or self._busy:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=min(0.2, left))
        return True

    # -- views ---------------------------------------------------------
    def cards(self) -> List[dict]:
        """Insertion-ordered card copies."""
        with self._cond:
            return [dict(self._cards[k]) for k in self._order]

    def card(self, name: str) -> Optional[dict]:
        """The most recent card for a program name (any signature)."""
        with self._cond:
            for k in reversed(self._order):
                if k[0] == name:
                    return dict(self._cards[k])
        return None

    def snapshot(self) -> dict:
        """Everything the surfaces render: the spec, the cards joined
        against their measured latency histograms (mfu_pct /
        roofline_eff_pct over ``measured_ms``; measured p50+p99), and the
        HBM account
        (peak = max card footprint; headroom vs spec capacity)."""
        spec = self.spec or current_device_spec()
        cards = self.cards()
        needed = {MEASURED_SERIES.get(c["name"]) for c in cards}
        needed.discard(None)
        reg = self._reg()
        stats: Dict[str, dict] = {}
        if needed:
            with reg._lock:
                for s in needed:
                    hist = reg.hists.get(s)
                    if hist is not None and hist.n:
                        stats[s] = hist.stats()
        by_name: Dict[str, int] = {}
        for c in cards:
            by_name[c["name"]] = by_name.get(c["name"], 0) + 1
        peak = None
        for c in cards:
            series = MEASURED_SERIES.get(c["name"])
            st = stats.get(series) if series else None
            c["measured_series"] = series
            # the measured histogram is per program NAME: with several
            # live signatures (decode buckets, train-shape variants)
            # each card's mfu/eff joins a p50 that AGGREGATES its
            # siblings — flagged so /programz readers and the report
            # interpret multi-signature joins accordingly
            c["series_shared_by"] = by_name[c["name"]]
            c["measured_n"] = st["count"] if st else 0
            c["measured_p50_ms"] = st["p50_ms"] if st else None
            c["measured_p99_ms"] = st["p99_ms"] if st else None
            c["measured_ms"] = measured_ms(series, st) if st else None
            c["mfu_pct"] = c["roofline_eff_pct"] = None
            if c["measured_ms"]:
                took_s = c["measured_ms"] / 1e3
                if c["flops"] is not None and spec is not None:
                    c["mfu_pct"] = round(
                        100.0 * c["flops"] / (took_s * spec.peak_flops), 2)
                if c["predicted_s"] is not None:
                    c["roofline_eff_pct"] = round(
                        100.0 * c["predicted_s"] / took_s, 2)
            if c["peak_bytes"] is not None:
                peak = max(peak or 0, c["peak_bytes"])
        decode_kv = None
        fn = self._decode_kv_fn
        if fn is not None:
            try:
                decode_kv = int(fn())
            except Exception:
                decode_kv = None    # the account never kills a scrape
        capacity = spec.hbm_capacity if spec is not None else None
        hbm = {"capacity_bytes": capacity,
               "peak_bytes": peak,
               # the live decode KV cache is a first-class HBM
               # consumer: persistent device state held BETWEEN
               # program executions, so headroom charges it on top of
               # the peak program footprint. Under the PAGED layout
               # decode_kv_bytes is the block pool's REAL array nbytes
               # (block-exact, pinned by test_perf). The HEADROOM row
               # stays conservative in BOTH layouts: the decode-step
               # card's argument bytes already include the cache the
               # decode_kv row charges again — one session's worth
               # dense, up to the whole pool paged (the step program
               # donates the pool arrays). It can only understate
               # free HBM, never overstate it, and the ledger cannot
               # tell which card bytes are the pool's to exclude them.
               "decode_kv_bytes": decode_kv,
               "headroom_bytes":
               (capacity - peak - (decode_kv or 0))
               if peak is not None and capacity is not None else None}
        return {"spec": spec.to_dict() if spec is not None else None,
                "enabled": self.enabled,
                "cards": cards, "hbm": hbm,
                # the warm-grid readiness account (ready_pct None until
                # an expected grid is registered) — statusd exports it
                # as cxxnet_ready_programs_pct (+ per-bucket rows)
                "readiness": self.readiness()}

    def decode_pool_cap_bytes(self,
                              frac: float = 0.5) -> Optional[int]:
        """Byte budget for the PAGED decode KV pool (ROADMAP item 2:
        "sized from the live HBM account"): ``frac`` of what the spec's
        HBM capacity leaves after the peak program footprint measured
        so far. The decode-KV hook is deliberately NOT charged here —
        the pool REPLACES the dense caches that hook reports, so
        charging them would double-count the very bytes being sized.
        None when the ledger is off or the backend has no spec (CPU):
        the pool falls back to dense-equivalent sizing. Conservative by
        construction: cards land as programs compile, so a pool sized at
        serving start sees the train/prefill peak, and
        ``Trainer.decode_kv_pool`` still floors the result at one
        max-length sequence."""
        if not self.enabled:
            return None
        spec = self.spec or current_device_spec()
        if spec is None:
            return None
        peak = 0
        with self._cond:
            for c in self._cards.values():
                pb = c.get("peak_bytes")
                if pb is not None:
                    peak = max(peak, int(pb))
        room = spec.hbm_capacity - peak
        if room <= 0:
            return None
        return int(max(0.0, min(1.0, float(frac))) * room)

    def set_decode_kv(self, fn) -> None:
        """Register the decode KV-cache account hook (``fn() ->
        bytes``; None clears) — servd's batching frontend wires its
        ``decode_kv_bytes`` here so /programz, /statusz and the
        ``cxxnet_hbm_headroom_bytes`` gauge charge the live decode
        cache against HBM (what ROADMAP item 2's paged allocator will
        size against)."""
        self._decode_kv_fn = fn


class ProfilerCapture:
    """The /profilez guard: at most ONE jax.profiler trace capture at a
    time, each into a fresh numbered subdirectory of the run-scoped
    ``outdir`` (conf key ``profilez_dir``). ``start(secs)`` returns
    (ok, detail) immediately — the capture itself runs on a daemon
    thread so the HTTP handler never blocks for the capture window.
    ``trace_fn(secs, path)`` is injectable for jax-free tests; the
    default imports jax and brackets ``start_trace``/``stop_trace``."""

    MAX_SECS = 120.0

    def __init__(self, outdir: str, trace_fn=None):
        self.outdir = outdir
        self._trace_fn = trace_fn or self._jax_trace
        self._lock = lockrank.lock("perf.profilez")
        self._busy = False
        # shutdown() sets _stop to cut an in-flight capture short (the
        # default trace fn polls it between sleep slices) and LATCHES
        # _shutdown so a racing /profilez request cannot start a fresh
        # capture thread into interpreter teardown
        self._stop = threading.Event()
        self._shutdown = False
        self._thread: Optional[threading.Thread] = None
        self.captures = 0
        self.last_path: Optional[str] = None
        self.last_error: Optional[str] = None

    def _jax_trace(self, secs: float, path: str) -> None:
        import jax
        jax.profiler.start_trace(path)
        try:
            # sliced sleep so shutdown() can end the capture early (a
            # preemption must not wait out a 120s window)
            deadline = time.monotonic() + secs
            while time.monotonic() < deadline \
                    and not self._stop.is_set():
                time.sleep(min(0.2, max(0.0,
                                        deadline - time.monotonic())))
        finally:
            jax.profiler.stop_trace()

    def start(self, secs: float) -> Tuple[bool, str]:
        try:
            secs = float(secs)
        except (TypeError, ValueError):
            return False, "secs must be a number"
        if not (0 < secs <= self.MAX_SECS):
            return False, ("secs must be in (0, %g]" % self.MAX_SECS)
        with self._lock:
            if self._shutdown:
                return False, "profiler shut down (process exiting)"
            if self._busy:
                return False, ("capture already in progress (into %s); "
                               "one at a time" % (self.last_path or "?"))
            self._busy = True
            self.captures += 1
            path = os.path.join(self.outdir,
                                "capture_%03d" % self.captures)
            self.last_path = path
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, args=(secs, path),
                name="cxn-profilez", daemon=True)
            # started under the lock: shutdown() can never observe
            # _busy without also seeing a joinable thread
            self._thread.start()
        telemetry.count("perf.profilez_captures")
        telemetry.event({"ev": "profilez", "secs": secs, "path": path})
        return True, path

    def _run(self, secs: float, path: str) -> None:
        err = None
        try:
            os.makedirs(path, exist_ok=True)
            self._trace_fn(secs, path)
        except Exception as e:
            err = "%s: %s" % (type(e).__name__, e)
        with self._lock:
            self._busy = False
            self.last_error = err
        if err:
            # the HTTP 200 went out before the capture ran: make the
            # failure visible — counted, logged, and echoed by the
            # NEXT /profilez response (statusd reads last_error)
            telemetry.count("perf.profilez_errors")
            telemetry.event({"ev": "profilez_error", "path": path,
                             "error": err[:200]})

    def busy(self) -> bool:
        with self._lock:
            return self._busy

    def shutdown(self, timeout: float = 20.0) -> bool:
        """Cut short any in-flight capture and JOIN its thread. MUST run
        before process teardown (learn_task's exit path does): a daemon
        capture thread still inside native profiler code — or the
        first capture's ~10s lazy profiler import — while the
        interpreter exits SEGFAULTS the process (observed rc -11),
        which would turn servd's clean SIGTERM drain into a crash.
        True when the capture finished within the timeout. Latches: a
        /profilez request racing the drain is refused from here on."""
        with self._lock:
            # latch AND set the stop flag under the lock: start() holds
            # it across its _stop.clear() + thread launch, so a racing
            # start either completes first (its thread then sees the
            # flag) or observes the latch and refuses — it can never
            # clear the flag after this set
            self._shutdown = True
            self._stop.set()
            t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        with self._lock:
            return not self._busy

    def wait(self, timeout: float = 30.0) -> bool:
        """Poll until the in-flight capture (if any) finishes — tests
        and the acceptance drive need a join point."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.busy():
                return True
            time.sleep(0.02)
        return not self.busy()


# ----------------------------------------------------------------------
# module-level singleton surface (the learn-task / bench wiring)
_LEDGER = Ledger()


def ledger() -> Ledger:
    return _LEDGER


def enable(spec: Optional[DeviceSpec] = None) -> Ledger:
    return _LEDGER.enable(spec=spec)


def disable() -> None:
    _LEDGER.disable()


def enabled() -> bool:
    return _LEDGER.enabled


def drain(timeout: float = 10.0) -> bool:
    return _LEDGER.drain(timeout)


def reset() -> None:
    _LEDGER.reset()


def set_decode_kv(fn) -> None:
    """Module-level form of ``Ledger.set_decode_kv`` (the learn-task
    serve wiring)."""
    _LEDGER.set_decode_kv(fn)


def decode_bound_tokens_per_s(ntok: int) -> Optional[float]:
    """The decode-step roofline bound for a served request: the scan
    program generates ntok-1 of the request's tokens (the first came
    from prefill), so the hardware-allowed rate is (ntok-1) / the
    program's predicted execution time. None until a decode-step card
    is ready — callers (servd's flight recorder) stay null-safe."""
    if ntok is None or ntok < 2 or not _LEDGER.enabled:
        return None
    card = _LEDGER.card("jit.decode_step")
    if card is None or not card.get("predicted_s"):
        return None
    return round((ntok - 1) / card["predicted_s"], 3)


# ----------------------------------------------------------------------
def selftest(verbose: bool = False) -> int:
    """Jax-free: card math from faked analyses, MFU/headroom joins
    against a private telemetry registry, /programz + /profilez over a
    real socket, the one-capture-at-a-time guard. ``make check`` gates
    on it. Runs under runtime lock-rank enforcement."""
    with lockrank.enforced():
        return _selftest_body(verbose)


def _selftest_body(verbose: bool = False) -> int:
    import json
    from urllib.request import urlopen
    from urllib.error import HTTPError

    reg = telemetry._Registry()
    reg.enable()
    spec = DeviceSpec("test", 100e12, 500e9, 8 * 2.0**30)
    lg = Ledger(registry=reg, spec=spec).enable()
    assert reg.compile_hook == lg.on_compile

    # a faked train-step compile + analysis: flops-bound program
    class _A:
        def __init__(self, shape, dtype="float32"):
            self.shape, self.dtype = shape, dtype
    disp, sig = shapes_signature(([_A((8, 128)), {"w": _A((128, 64))}],),
                                 None)
    lg.on_compile("jit.train_step", "new_signature", 1.25, fn=None,
                  args=([_A((8, 128)), {"w": _A((128, 64))}],), key="k1")
    card = lg.complete_card(
        "jit.train_step", sig,
        cost={"flops": 2.0e12, "bytes accessed": 1.0e9},
        mem={"argument_size_in_bytes": 3 * 2**30,
             "temp_size_in_bytes": 2**30,
             "output_size_in_bytes": 2**20})
    # flops-bound: 2e12/100e12 = 20ms > 1e9/500e9 = 2ms
    assert abs(card["predicted_s"] - 0.02) < 1e-9, card
    assert card["peak_bytes"] == 3 * 2**30 + 2**30 + 2**20
    assert card["status"] == "ready" and card["compile_s"] == 1.25
    # the JSONL event landed
    evs = [e for e in reg.events() if e.get("ev") == "program_card"]
    assert evs and evs[-1]["flops"] == 2.0e12

    # measured join: feed the train.period histogram at 40ms -> MFU 50%
    for _ in range(10):
        reg.hist("train.period", 0.040)
    snap = lg.snapshot()
    c = [c for c in snap["cards"] if c["name"] == "jit.train_step"][0]
    assert c["measured_n"] == 10
    assert c["mfu_pct"] is not None and 35.0 < c["mfu_pct"] < 65.0, c
    assert c["roofline_eff_pct"] is not None \
        and 35.0 < c["roofline_eff_pct"] < 65.0
    assert snap["hbm"]["peak_bytes"] == card["peak_bytes"]
    assert snap["hbm"]["headroom_bytes"] == \
        spec.hbm_capacity - card["peak_bytes"]

    # an error completion keeps the card visible, fields null
    lg.on_compile("jit.predict", "new_signature", 0.2, fn=None,
                  args=(_A((4, 4)),))
    _, sig2 = shapes_signature((_A((4, 4)),), None)
    bad = lg.complete_card("jit.predict", sig2, error="boom")
    assert bad["status"] == "error" and bad["flops"] is None

    # decode bound: needs a ready decode-step card
    assert decode_bound_tokens_per_s(16) is None     # module ledger off
    _, sig3 = shapes_signature((_A((1, 8)),), None)
    lg.on_compile("jit.decode_step", "new_signature", 0.5, fn=None,
                  args=(_A((1, 8)),))
    lg.complete_card("jit.decode_step", sig3,
                     cost={"flops": 1.0e9, "bytes accessed": 5.0e8})
    cardd = lg.card("jit.decode_step")
    assert cardd["predicted_s"] == 5.0e8 / 500e9

    # compile flight ring: per-compile records with trigger
    # attribution (a trace context = the request whose prefill
    # compiled in-band; a compile window = the dispatcher's bracket
    # around batch-wide work) + the warm-grid readiness account
    lg.set_expected_grid([(("sess_step", 2, 0.0, 0), "2"),
                          (("sess_admit", 2), "2"),
                          (("sess_prefill", 8, 0.0, 0), "prefill")])
    rd = lg.readiness()
    assert rd["expected"] == 3 and rd["warm"] == 0 \
        and rd["ready_pct"] == 0.0, rd
    # mirror JitWatch's cache-growth sequence: record_compile (feeds
    # the innermost trace context / every open compile window) then
    # the supervised ledger hook (feeds the ring)
    with reg.trace_context("req-7") as tc7:
        reg.record_compile("jit.decode_prefill", "new_signature", 0.3,
                           key=("sess_prefill", 8, 0.0, 0))
        lg.on_compile("jit.decode_prefill", "new_signature", 0.3,
                      fn=None, args=(_A((1, 8)),),
                      key=("sess_prefill", 8, 0.0, 0))
    assert tc7.compiles and tc7.compiles[0]["dur"] == 0.3
    with reg.compile_window("session:b2") as cwin:
        reg.record_compile("jit.decode_step", "new_signature", 0.7,
                           key=("sess_step", 2, 0.0, 0))
        lg.on_compile("jit.decode_step", "new_signature", 0.7,
                      fn=None, args=(_A((2, 8)),),
                      key=("sess_step", 2, 0.0, 0))
    assert cwin.stall_s == 0.7, cwin.compiles
    assert reg.current_compile_window() is None
    recs = lg.recent_compiles(2)          # newest first
    assert recs[0]["key"] == str(("sess_step", 2, 0.0, 0))
    assert recs[0]["trigger_context"] == "session:b2" \
        and recs[0]["trigger_request"] is None, recs[0]
    assert recs[1]["trigger_request"] == "req-7" \
        and recs[1]["trigger_context"] is None, recs[1]
    assert recs[0]["seq"] > recs[1]["seq"] > 0
    assert recs[0]["seconds"] == 0.7 and recs[0]["shapes"]
    rd = lg.readiness()
    assert rd["warm"] == 2 and rd["ready_pct"] == 66.67, rd
    assert rd["buckets"]["2"] == {"expected": 2, "warm": 1,
                                  "ready_pct": 50.0}, rd
    assert rd["buckets"]["prefill"]["ready_pct"] == 100.0
    assert rd["cold_keys"] == [str(("sess_admit", 2))], rd
    cevs = [e for e in reg.events()
            if e.get("ev") == "program_compile"]
    assert cevs and cevs[-1]["trigger_context"] == "session:b2" \
        and cevs[-1]["warm_programs"] == 2 \
        and cevs[-1]["expected_programs"] == 3, cevs[-1]
    assert lg.snapshot()["readiness"]["ready_pct"] == 66.67

    # /programz + /metrics + /profilez over a real socket
    from . import statusd
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=reg).start()
    srv.perf = lg
    started = []

    def fake_trace(secs, path):
        started.append(path)
        time.sleep(secs)

    import tempfile
    prof = ProfilerCapture(tempfile.mkdtemp(prefix="cxn-perf-selftest-"),
                           trace_fn=fake_trace)
    srv.profiler = prof
    try:
        base = "http://127.0.0.1:%d" % srv.port
        page = urlopen(base + "/programz", timeout=5).read().decode()
        assert "jit.train_step" in page and "MFU" in page
        doc = json.loads(urlopen(base + "/programz?json=1",
                                 timeout=5).read())
        assert doc["hbm"]["peak_bytes"] == card["peak_bytes"]
        assert any(c["name"] == "jit.train_step" for c in doc["cards"])
        m = urlopen(base + "/metrics", timeout=5).read().decode()
        for line in m.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert 'cxxnet_program_mfu_pct{process="0",program=' in m
        assert "cxxnet_hbm_peak_bytes" in m
        assert "cxxnet_hbm_headroom_bytes" in m
        assert "cxxnet_ready_programs_pct" in m
        assert 'cxxnet_ready_programs_bucket_pct{process="0"' \
               ',bucket="2"} 50.0' in m
        # /compilez: the flight ring + readiness render, json contract
        page = urlopen(base + "/compilez", timeout=5).read().decode()
        assert "compile flight recorder" in page \
            and "session:b2" in page and "66.7% ready" in page, page
        doc = json.loads(urlopen(base + "/compilez?json=1&n=2",
                                 timeout=5).read())
        assert doc["shown"] == 2 and doc["total"] >= 4
        assert doc["readiness"]["ready_pct"] == 66.67
        assert doc["compiles"][0]["trigger_context"] == "session:b2"
        try:
            urlopen(base + "/compilez?n=nope", timeout=5)
            raise AssertionError("bad n should 400")
        except HTTPError as e:
            assert e.code == 400
        # profilez: capture starts, a concurrent second one is refused
        r = urlopen(base + "/profilez?secs=0.5", timeout=5)
        assert r.status == 200 and b"capture_001" in r.read()
        try:
            urlopen(base + "/profilez?secs=0.5", timeout=5)
            raise AssertionError("concurrent capture should 409")
        except HTTPError as e:
            assert e.code == 409
        prof.wait(5.0)
        assert started and started[0].endswith("capture_001")
        ok, detail = prof.start(0.01)      # guard released after finish
        assert ok, detail
        prof.wait(5.0)
        try:
            urlopen(base + "/profilez?secs=nope", timeout=5)
            raise AssertionError("bad secs should 400")
        except HTTPError as e:
            assert e.code == 400
        srv.profiler = None
        try:
            urlopen(base + "/profilez?secs=1", timeout=5)
            raise AssertionError("no profiler registered should 404")
        except HTTPError as e:
            assert e.code == 404
        srv.perf = None
        try:
            urlopen(base + "/compilez", timeout=5)
            raise AssertionError("no ledger registered should 404")
        except HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()
        lg.disable()
        reg.disable()
    if verbose:
        print("perf selftest: card math, MFU/headroom joins, compile "
              "ring + readiness, /programz, /compilez, /metrics "
              "program series, /profilez guard ok")
    return 0


if __name__ == "__main__":
    import sys
    if "--selftest" in sys.argv[1:]:
        sys.exit(selftest(verbose=True))
    print(__doc__)
    sys.exit(1)
