"""From a profiler trace (``.xplane.pb``) to numbers.

``reduce_trace`` reads the file's events with ``jax.profiler.ProfileData``
and returns, for the stretch between the first and the last run of the
train-step module: each device's busy time (the union of its ``XLA Ops``
intervals), each class of operation's own time, the operations that took
most time, the longest idle gaps, each named by the harness span the host
was inside at the gap's middle, and each scope's own time (``scope_s``).
``reduce_events`` is the same arithmetic on plain tuples, which is what the
tests drive.

An operation's class is read from its HLO text, which is the event's name on
a TPU: the opcode, and for a fusion its ``kind``. XLA's fusion names are not
stable from one build of the program to the next; the classes are.

An operation's scope is read from its ``tf_op``: the name stack jax gives
the operation, ``jit(step)/jvp(conv1)/conv_general_dilated:``, with the
``jax.named_scope``s the program opened in it. ``tf_op`` is a stat of the
event's *metadata*, which ``ProfileData`` does not hand out, so ``tf_ops``
decodes the three message types it needs (plane, line, event metadata) from
the file's wire format; times stay ``ProfileData``'s. ``scope_of`` puts a
``tf_op`` to a phase and a scope by rules that name no layer.
"""

from __future__ import annotations

import fnmatch
import re
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=k([A-Za-z]+)")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

PHASES = ("forward", "backward", "update", "other")
NO_SCOPE = "-"                 # no ``tf_op``, or one that opens no scope
SUB_SCOPE_MARK = "~"           # a scope a layer opens inside its own

# name, start ns, duration ns and, for a device operation read from a file,
# its ``tf_op``
Event = Tuple[str, float, float]


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


def _self_times(ops: List[Event]) -> List[Tuple[Event, float]]:
    """(event, its duration less what the events nested in it cover)."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = [[e, e[2]] for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= e[2]
        stack.append(i)
    return [(e, max(d, 0.0)) for e, d in out]


_JIT = re.compile(r"^jit\([^)]*\)/")
_WRAP = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\(")


def _unwrap(component: str) -> str:
    """``transpose(jvp(conv1))`` -> ``conv1``."""
    return _WRAP.sub("", component).rstrip(")")


def scope_of(tf_op: str) -> Tuple[str, str]:
    """(phase, scope) of one ``tf_op``. The first component after
    ``jit(...)`` decides both: ``jvp(<layer>)`` is the forward pass,
    ``transpose(jvp(<layer>))`` the backward, ``update/<layer>`` the
    optimizer, anything else ``other`` under its own name. A later
    component that starts with ``~``, bare or wrapped
    (``transpose(jvp(~experts))``), is the layer's marked sub-scope:
    ``jvp(b0_att)/~core/pallas_call:`` is ``b0_att/core``. The last
    component is the primitive. An operation with no ``tf_op`` (the
    compiler's own) and one in no scope stand under ``-``."""
    if not tf_op:
        return "other", NO_SCOPE
    parts = _JIT.sub("", tf_op.rsplit(":", 1)[0]).split("/")
    head, inner = parts[0], parts[1:-1]
    if head == "update":
        return "update", inner[0] if inner else NO_SCOPE
    if "transpose(" in head:
        phase = "backward"
    elif "jvp(" in head:
        phase = "forward"
    else:
        # the first component is a primitive (no scope), or a scope
        # outside any transform: the step's own, or a forward-only program's
        return "other", head if len(parts) > 1 else NO_SCOPE
    layer = _unwrap(head)
    if not layer:
        return phase, NO_SCOPE
    sub = next((c for c in map(_unwrap, inner)
                if c.startswith(SUB_SCOPE_MARK)), None)
    if sub:
        layer = "%s/%s" % (layer, sub[len(SUB_SCOPE_MARK):])
    return phase, layer


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
        busy = _union([(e[1], e[1] + e[2]) for e in ops])
        per_dev[dev] = {"t0": t0, "t1": t1, "ops": ops, "busy": busy,
                        "busy_ns": sum(b - a for a, b in busy),
                        "steps": len(steps)}
    if not per_dev:
        return None
    name, d = max(per_dev.items(), key=lambda kv: kv[1]["busy_ns"])
    classes: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    scopes: Dict[str, Dict[str, float]] = {}
    for op, self_ns in _self_times(d["ops"]):
        hlo = op[0]
        cls = op_class(hlo)
        classes[cls] = classes.get(cls, 0.0) + self_ns
        key = "%s__%s_" % (op_name(hlo), cls)
        by_op[key] = by_op.get(key, 0.0) + self_ns
        phase, scope = scope_of(op[3] if len(op) > 3 else "")
        row = scopes.setdefault(phase, {})
        row[scope] = row.get(scope, 0.0) + self_ns
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
        # phase -> scope -> seconds of own time over the whole stretch
        "scope_s": {p: {k: v * 1e-9 for k, v in sorted(scopes[p].items())}
                    for p in PHASES if p in scopes},
        "device_ops": [[k, v * 1e-9] for k, v in ops_sorted[:top]],
        "idle_gaps": [[k, v] for k, v in gaps[:top]],
    }


def scope_seconds(scope_s: Optional[Dict[str, Dict[str, float]]],
                  scopes: Iterable[str],
                  phases: Optional[Iterable[str]] = None) -> Optional[float]:
    """Seconds of the rows of a reduction's ``scope_s`` whose scope a glob
    of ``scopes`` names (``*_att/core``), in ``phases`` (all where none is
    given); nothing where no row matches, which is not 0 seconds."""
    scopes = list(scopes)
    rows = [s for phase, by_scope in (scope_s or {}).items()
            if phases is None or phase in phases
            for name, s in by_scope.items()
            if any(fnmatch.fnmatchcase(name, g) for g in scopes)]
    return sum(rows) if rows else None


# ------------------------------------------------- the file's wire format
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a length-delimited
    value is a memoryview into the file's bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError("not an xplane protobuf (wire type %d)" % wire)
        yield key >> 3, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _field(buf, number: int, default=0):
    return next((v for f, v in _fields(buf) if f == number), default)


def tf_ops(path: str) -> Dict[str, List[Tuple[str, str]]]:
    """device plane -> (name, ``tf_op``) of each event of its ``XLA Ops``
    line, in the file's order. XSpace.planes = 1; XPlane: name = 2, lines =
    3, event_metadata = 4 and stat_metadata = 5 (maps: key = 1, value = 2);
    XLine: name = 2, events = 4; XEvent.metadata_id = 1; XEventMetadata:
    name = 2, stats = 5; XStat: metadata_id = 1, str_value = 5, ref_value =
    7 (the id of a stat metadata whose name is the string)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, List[Tuple[str, str]]] = {}
    for f, plane in _fields(data):
        plane_name = _text(_field(plane, 2, b"")) if f == 1 else ""
        if not plane_name.startswith(DEVICE_PLANE):
            continue
        lines, events, stats = [], {}, {}
        for g, v in _fields(plane):
            if g == 3:
                lines.append(v)
            elif g == 4:
                events[_field(v, 1)] = _field(v, 2, b"")
            elif g == 5:
                stats[_field(v, 1)] = _text(_field(_field(v, 2, b""), 2,
                                                   b""))
        meta: Dict[int, Tuple[str, str]] = {}

        def name_and_tf_op(mid: int) -> Tuple[str, str]:
            if mid not in meta:
                name, tf_op = "", ""
                for g, v in _fields(events.get(mid, b"")):
                    if g == 2:
                        name = _text(v)
                    elif g == 5 and stats.get(_field(v, 1)) == "tf_op":
                        ref = _field(v, 7, None)
                        tf_op = stats.get(ref, "") if ref is not None \
                            else _text(_field(v, 5, b""))
                meta[mid] = (name, tf_op)
            return meta[mid]
        for line in lines:
            if _text(_field(line, 2, b"")) == OPS_LINE:
                out[plane_name] = [
                    name_and_tf_op(_field(v, 1))
                    for g, v in _fields(line) if g == 4]
    return out


def read_xplane(path: str, span_prefix: str = "bench."):
    """(devices, host spans) of an ``.xplane.pb`` file. Times are
    ``ProfileData``'s; an operation's ``tf_op`` is the one ``tf_ops`` finds
    at the same place of the same line, where the names agree."""
    import jax
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    named = tf_ops(path)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key == "modules":
                    lines[key] = [(e.name, float(e.start_ns),
                                   float(e.duration_ns)) for e in line.events]
                elif key == "ops":
                    events = list(line.events)
                    scoped = named.get(plane.name, ())
                    if [e.name for e in events] != [n for n, _ in scoped]:
                        scoped = [("", "")] * len(events)
                    lines[key] = [(e.name, float(e.start_ns),
                                   float(e.duration_ns), tf_op)
                                  for e, (_, tf_op) in zip(events, scoped)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return devices, spans


def reduce_trace(path: str, step_module: str) -> Optional[dict]:
    devices, spans = read_xplane(path)
    return reduce_events(devices, spans, step_module)
