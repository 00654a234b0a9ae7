"""Traffic of kind ``resident``: a training window over resident batches.

Two device-resident batches, alternated, the program's training call made
back to back, the host held to at most ``sync_every`` steps ahead of the
device by fetching a value the last step produced. The window runs whole
sync-groups until ``seconds`` have passed and stops at a sync; the rate is
all items of all groups over all that time. Nothing here is best-of or
per-step.

``run`` is what ``run.py`` calls for a mix of this kind. The program under
test and the plain reference are found by the names in the configuration's
file (``"program"`` -> ``programs/<name>.py``, ``"reference"`` ->
``references/<name>.py``); this file touches neither directly.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, List, Optional

from benchmark import compare

SPAN_UPDATE = "bench.update_call"
SPAN_SYNC = "bench.sync"


def run(spec: dict, seed: int, seconds: float, h) -> dict:
    """One run of one cell of this kind. ``h`` is ``run.py``'s harness: it
    finds parts by name, keeps the phases of set-up, gives the tracer, and
    is told when set-up is done and when the window has closed."""
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    chips = cell["chips"]
    batch = cfg["batch_per_chip"] * chips
    steps = traffic["compare_steps"]
    reference = h.part("references", cfg["reference"])
    ref = reference.for_config(spec["conf_text"], cfg, batch)
    make = h.program_factory or h.part("programs", cfg["program"]).Program

    # ---- set-up: one program object, driven through its first steps and
    # then handed to the window
    program = make(spec["conf_text"], cfg, chips, seed, traffic,
                   mark=h.phases.mark)
    got = first_steps(program, ref.hyper, steps)
    h.phases.mark("first_steps_s")
    for _ in range(traffic["warm_steps"]):
        program.step()
    program.sync()
    h.phases.mark("warm_s")
    h.setup_done()

    # ---- the measured window
    tracer = h.tracer(traffic["step_module"])
    win = run_window(
        program.step, program.sync, batch, seconds, traffic["sync_every"],
        span=h.span, trace_group=traffic["trace_group"] if tracer else -1,
        trace_start=tracer.start if tracer else None,
        trace_stop=tracer.stop if tracer else None)
    h.window_closed()
    # what the program counted in the window's last step, where its adapter
    # can say (after the window's last sync: nothing waits for it): for the
    # reader of the line, no metric is made of it
    gauges = program.gauges() if hasattr(program, "gauges") else {}

    # ---- the comparison, once the window has closed and the peak is read
    program.release()
    del program
    t_ref = time.perf_counter()
    want = ref.run(seed, steps)
    ref_s = time.perf_counter() - t_ref
    nums = compare.numbers(got, want)
    nums["window_failed_steps"] = {"value": float(win["failed_steps"]),
                                   "at": ""}
    rate = win["items_per_s"] / chips
    return {
        "attempted": win["steps"], "failed": win["failed_steps"],
        "end_to_end": {"train_items_per_s_per_chip": rate},
        "numbers": nums, "limits": {"window_failed_steps": 0.0},
        "ctx": {"window": win, "flops_per_item":
                reference.train_flops_per_item(spec["conf_text"], cfg),
                "reference": reference, "conf_text": spec["conf_text"],
                "cfg": cfg, "want": want},
        "run": {"window_s": win["elapsed_s"], "groups": win["groups"],
                "reference_s": ref_s, "items_per_s_per_chip": rate,
                **({"gauges": gauges} if gauges else {})},
    }


def first_steps(program, hyper: dict, n_steps: int = 3) -> dict:
    """Drive the window's own call through its first steps and keep what the
    comparison reads. The first of them compiles (or loads) the step."""
    losses, grad = [], None
    for i in range(n_steps):
        program.step()
        losses.append(program.sync())
        if i == 0:
            grad = program.first_gradient_norms(hyper)
    return {"loss": losses, "grad_norm": grad,
            "change_norm": program.change_norms()}


def run_window(step: Callable[[], None], sync: Callable[[], float],
               items_per_step: int, seconds: float, sync_every: int,
               clock: Callable[[], float] = time.perf_counter,
               span: Optional[Callable] = None,
               trace_group: int = -1,
               trace_start: Optional[Callable] = None,
               trace_stop: Optional[Callable] = None) -> dict:
    """Whole sync-groups until ``seconds`` have passed. Group ``trace_group``
    (if any) runs between ``trace_start()`` and ``trace_stop()``; the time
    those two take is outside every group but inside the window."""
    import contextlib
    span = span or (lambda name: contextlib.nullcontext())
    groups: List[dict] = []
    failed = 0
    want_trace = trace_start is not None and trace_group >= 0
    t0 = clock()
    now = t0
    while now - t0 < seconds or (want_trace and len(groups) <= trace_group):
        traced = want_trace and len(groups) == trace_group
        if traced:
            trace_start()
        g0 = clock()
        calls = []
        for _ in range(sync_every):
            c0 = clock()
            with span(SPAN_UPDATE):
                step()
            calls.append(clock() - c0)
        with span(SPAN_SYNC):
            loss = sync()
        g1 = clock()
        if traced:
            trace_stop()
        if not loss == loss or loss in (float("inf"), float("-inf")):
            failed += sync_every
        groups.append({"seconds": g1 - g0, "calls": calls, "traced": traced})
        now = clock()
    elapsed = now - t0
    steps = sync_every * len(groups)
    off = [g for g in groups if not g["traced"]]
    off_s = sum(g["seconds"] for g in off)
    off_calls = [c for g in off for c in g["calls"]]
    return {
        "steps": steps,
        "failed_steps": failed,
        "elapsed_s": elapsed,
        # the end-to-end rate: all items of the window over all its time
        "items_per_s": steps * items_per_step / elapsed,
        # the rate over the groups that ran with the profiler off
        "items_per_s_profiler_off":
            (len(off) * sync_every * items_per_step / off_s) if off_s else None,
        "update_call_ms_median":
            1e3 * statistics.median(off_calls) if off_calls else None,
        "groups": len(groups),
    }
