"""From a profiler trace (``.xplane.pb``) to numbers.

``reduce_trace`` reads the file with ``jax.profiler.ProfileData`` and nothing
else, and returns, for the stretch between the first and the last run of the
train-step module: each device's busy time (the union of its ``XLA Ops``
intervals), each class of operation's own time, the operations that took
most time, and the longest idle gaps, each named by the harness span the host
was inside at the gap's middle. ``reduce_events`` is the same arithmetic on
plain tuples, which is what the tests drive.

An operation's class is read from its HLO text, which is the event's name on
a TPU: the opcode, and for a fusion its ``kind``. XLA's fusion names are not
stable from one build of the program to the next; the classes are.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=k([A-Za-z]+)")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]            # name, start ns, duration ns


def op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def op_class(hlo: str) -> str:
    """matmul | pool_bwd | collective | loop | reduce | copy | other."""
    name = op_name(hlo)
    body = hlo.split(" = ", 1)[1] if " = " in hlo else ""
    m = _OPCODE.search(" " + body)
    opcode = m.group(1) if m else name.split(".")[0]
    if any(opcode.startswith(c) or name.startswith(c) for c in _COLLECTIVES):
        return "collective"
    if opcode in ("convolution", "dot") or "convolution" in name \
            or name.startswith("dot"):
        return "matmul"
    if opcode == "select-and-scatter" or name.startswith("select-and-scatter"):
        return "pool_bwd"
    if opcode == "fusion":
        kind = _KIND.search(body)
        kind = kind.group(1) if kind else ""
        # on a TPU a convolution or dot with what is fused into it is an
        # "output" fusion; loops are elementwise, inputs are reductions
        return {"Output": "matmul", "Convolution": "matmul", "Loop": "loop",
                "Input": "reduce"}.get(kind, "other")
    if opcode in ("copy", "transpose", "bitcast", "copy-start", "copy-done",
                  "concatenate", "slice", "dynamic-slice", "pad"):
        return "copy"
    return "other"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(ops: List[Event]) -> List[Tuple[str, float]]:
    """Each event's duration less what the events nested in it cover."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = [[name, dur] for name, _, dur in order]
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= dur
        stack.append(i)
    return [(n, max(d, 0.0)) for n, d in out]


def reduce_events(devices: Dict[str, Dict[str, List[Event]]],
                  host_spans: Iterable[Event], step_module: str,
                  top: int = 10) -> Optional[dict]:
    """``devices``: plane name -> {"modules": [...], "ops": [...]}. Returns
    nothing where no device ran the step module twice: there is then no
    stretch to take a share of."""
    per_dev = {}
    for dev, lines in devices.items():
        steps = [e for e in lines.get("modules", ())
                 if e[0].split("(")[0] == step_module]
        if len(steps) < 2:
            continue
        t0 = min(e[1] for e in steps)
        t1 = max(e[1] + e[2] for e in steps)
        ops = [e for e in lines.get("ops", ())
               if e[1] >= t0 and e[1] + e[2] <= t1]
        if not ops:
            continue
        busy = _union([(s, s + d) for _, s, d in ops])
        per_dev[dev] = {"t0": t0, "t1": t1, "ops": ops, "busy": busy,
                        "busy_ns": sum(b - a for a, b in busy),
                        "steps": len(steps)}
    if not per_dev:
        return None
    name, d = max(per_dev.items(), key=lambda kv: kv[1]["busy_ns"])
    classes: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    for hlo, self_ns in _self_times(d["ops"]):
        cls = op_class(hlo)
        classes[cls] = classes.get(cls, 0.0) + self_ns
        key = "%s__%s_" % (op_name(hlo), cls)
        by_op[key] = by_op.get(key, 0.0) + self_ns
    spans = list(host_spans)
    gaps = []
    edges = [(d["t0"], d["t0"])] + d["busy"] + [(d["t1"], d["t1"])]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            mid = 0.5 * (a + b)
            inside = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
            # the innermost span that covers the middle of the gap
            label = min(inside, key=lambda s: s[2])[0] if inside else "none"
            gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(by_op.items(), key=lambda kv: -kv[1])
    window_ns = d["t1"] - d["t0"]
    return {
        "device": name,
        "devices": len(per_dev),
        "steps": d["steps"],
        "window_s": window_ns * 1e-9,
        "busy_s": d["busy_ns"] * 1e-9,
        "busy_s_mean": sum(v["busy_ns"] for v in per_dev.values())
        * 1e-9 / len(per_dev),
        "class_s": {k: v * 1e-9 for k, v in sorted(classes.items())},
        "device_ops": [[k, v * 1e-9] for k, v in ops_sorted[:top]],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
    }


def read_xplane(path: str, span_prefix: str = "bench."):
    """(devices, host spans) of an ``.xplane.pb`` file."""
    import jax
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] = [(e.name, float(e.start_ns),
                                   float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return devices, spans


def reduce_trace(path: str, step_module: str) -> Optional[dict]:
    devices, spans = read_xplane(path)
    return reduce_events(devices, spans, step_module)
