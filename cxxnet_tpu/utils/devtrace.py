"""A profiler trace (``.xplane.pb``) put to the program's layers.

The compiled train step names what it does (``jax.named_scope`` in
nnet/net.py and nnet/trainer.py), so every device operation's ``tf_op``
stat in the profiler's file reads

    jit(step)/jvp(conv1)/conv_general_dilated        forward of conv1
    jit(step)/transpose(jvp(conv1))/...              backward of conv1
    jit(step)/update/conv1/...                       conv1's optimizer
    jit(step)/health/...                             health_monitor's sums

and ``telemetry.span`` puts the program's host spans (``train.update`` >
``train.h2d``, ``train.step`` > ``train.args``, ``train.dispatch``) on the
same clock. This module reads the
file and reduces it, for the stretch between the first and the last run of
the step module on the busiest device: device self time by phase and by
layer x phase, the host spans' self times, and the longest idle gaps, each
named by the innermost program span that covers its middle.
``tools/trace_layers.py`` prints it.

``tf_op`` is a stat of an event's *metadata*, which
``jax.profiler.ProfileData`` does not hand out, and the protobuf message
ships only inside tensorflow; so ``read_xplane`` decodes the five message
types it needs (planes, lines, events, event and stat metadata) from the
wire format. Busy time, self time and the stretch are defined as
``benchmark/trace_reduce.py`` defines them, and a test holds the two to the
same ``busy_s`` on the same file.

A row is exact to a fusion: a fusion goes to the scope of its own
``tf_op``, which is its root instruction's. XLA may have fused a
neighbour's bias or relu into it, and the file does not say so. What the
compiler made itself carries no ``tf_op`` at all (on a TPU: the bit-packed
relu and max-pool masks it keeps for the backward pass in place of the
activations, 3-4% of a conv net's step, and copies between memory spaces);
such an operation goes to the scope of the first operation that reads its
result, since that one is why it exists, and stays under ``NO_TF_OP`` where
the trace shows no reader with a name. ``named_share`` counts what a
``tf_op`` of its own names; what was named through a reader is
``via_reader_share``, beside it and not in it.

Two reductions of one file: ``benchmark/trace_reduce.py`` is the
benchmark's, classes by opcode, and may not be edited by a PR that is not
the benchmark's own; this one reads the names. They share the definitions
of the stretch, the union and self time, held equal by a test. The
``benchmark`` issue that brings per-layer trace metrics makes them one:
``trace_reduce`` takes this module's ``read_xplane`` and arithmetic, and
its own ``_union`` / ``_self_times`` / ``ProfileData`` reader go (PERF.md
section 7).
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import struct
from typing import Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PHASES = ("forward", "backward", "update", "health", "other")
TOP_GAPS = 10                 # idle gaps kept, the longest
REPORT_ROWS = 30              # layer x phase rows the text shows
REPORT_OPS_PER_ROW = 4
# scopes of the train step that are no layer's and no phase of their own
STEP_SCOPES = ("clip", "accum", "guard")
# the mark of a scope a layer opens inside its own (layer.base.sub_scope):
# a layer's rows are split by them
SUB_SCOPE_MARK = "~"
# the program's host spans (telemetry.span / telemetry.phase)
SPAN_PREFIXES = ("train.", "init.", "jit.", "io.")
UNNAMED = "(no scope)"
NO_TF_OP = "(no tf_op)"
STALE_WARNING = (
    "this executable was compiled before the scopes existed; compile into a "
    "fresh cache (jax's persistent cache ignores metadata in its key, so it "
    "handed back the old program: point JAX_COMPILATION_CACHE_DIR at an "
    "empty directory for this one run)")

Event = Tuple[str, float, float]            # name, start ns, duration ns


class Op(NamedTuple):
    """One device operation of the trace."""
    name: str                 # the HLO text on a TPU
    start_ns: float
    dur_ns: float
    tf_op: str = ""           # jit(step)/jvp(<scope>)/<primitive>:
    flops: float = 0.0
    bytes_accessed: float = 0.0


# ---------------------------------------------------------------- the file
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview into the file's bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError("not an xplane protobuf (wire type %d)" % wire)
        yield key >> 3, wire, val


def _stat(buf) -> Tuple[int, object]:
    """An XStat: (metadata id, value); ``ref_value`` comes back as
    ("ref", id) for the caller to look up."""
    key, val = 0, None
    for f, wire, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = v
        elif f == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = ("ref", v)
    return key, val


def _map_entry(buf) -> Tuple[int, object]:
    key, val = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf) -> Tuple[str, list, Dict[int, object], Dict[int, str]]:
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, val = _map_entry(v)
            event_meta[key] = val
        elif f == 5:
            key, val = _map_entry(v)
            for g, _, w in _fields(val):
                if g == 2:
                    stat_names[key] = bytes(w).decode()
    return name, lines, event_meta, stat_names


def _event_metadata(buf, stat_names: Dict[int, str]) -> dict:
    """name and the three stats kept of an XEventMetadata."""
    out = {"name": "", "tf_op": "", "flops": 0.0, "bytes_accessed": 0.0}
    for f, _, v in _fields(buf):
        if f == 2:
            out["name"] = bytes(v).decode("utf-8", "replace")
        elif f == 5:
            key, val = _stat(v)
            stat = stat_names.get(key)
            if stat in out:
                if isinstance(val, tuple):
                    val = stat_names.get(val[1], "")
                out[stat] = val if stat == "tf_op" else float(val or 0)
    return out


def _line(buf) -> Tuple[str, int, list]:
    name, t0_ns, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            t0_ns = v
        elif f == 4:
            meta = offset_ps = dur_ps = 0
            for g, _, w in _fields(v):
                if g == 1:
                    meta = w
                elif g == 2:
                    offset_ps = w
                elif g == 3:
                    dur_ps = w
            events.append((meta, offset_ps, dur_ps))
    return name, t0_ns, events


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` under a profile directory (``profile_dir``,
    ``/profilez``'s), or the file itself."""
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                         "*.xplane.pb"))) \
        or sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError("no *.xplane.pb under %r" % path)
    return hits[-1]


def read_xplane(path: str):
    """(devices, host spans) of an ``.xplane.pb``: ``devices`` maps a device
    plane to its ``modules`` (``Event``) and ``ops`` (``Op``); host spans
    are every event of the host planes' lines, as (name, start ns,
    duration ns, line)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float, str]] = []
    for f, _, plane in _fields(data):
        if f != 1:
            continue
        name, lines, event_meta, stat_names = _plane(plane)
        is_device = name.startswith(DEVICE_PLANE)
        if not is_device and not name.startswith(HOST_PLANE):
            continue
        meta: Dict[int, dict] = {}
        for raw in lines:
            line_name, t0_ns, events = _line(raw)
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line_name)
            if is_device and key is None:
                continue
            rows = []
            for mid, offset_ps, dur_ps in events:
                m = meta.get(mid)
                if m is None:
                    m = meta[mid] = _event_metadata(
                        event_meta.get(mid, b""), stat_names)
                start, dur = t0_ns + offset_ps / 1000.0, dur_ps / 1000.0
                if key == "ops":
                    rows.append(Op(m["name"], start, dur, m["tf_op"],
                                   m["flops"], m["bytes_accessed"]))
                elif is_device:
                    rows.append((m["name"], start, dur))
                else:
                    spans.append((m["name"], start, dur,
                                  "%s/%s" % (name, line_name)))
            if is_device:
                devices.setdefault(name, {})[key] = rows
    return devices, spans


# ------------------------------------------------------------- the scopes
_JIT = re.compile(r"^jit\([^)]*\)/")
_WRAP = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\(")
# a pipelined step runs its stage bodies inside shard_map's own control
# flow, so a layer's scope stands behind these components of the path
SHARD_MAP = "shard_map"
_CONTROL = re.compile(r"^(shard_map|while|body|cond|closed_call|checkpoint|"
                      r"rematted_computation|remat\d*|branch_\d+_fun)$")


def scope_of(tf_op: str) -> Tuple[str, str]:
    """(phase, layer) of one ``tf_op``. The first component after
    ``jit(...)`` decides both: ``update/<layer>`` and ``health`` are phases
    of their own, ``transpose(...)`` is the backward pass, ``jvp(...)`` the
    forward; what stands in no scope comes back as ``UNNAMED``, an
    operation with no ``tf_op`` at all as ``NO_TF_OP``. Under an empty
    ``jvp()`` a leading ``shard_map`` and its control flow are skipped
    (``jvp()/shard_map/while/body/.../conv1/...`` is conv1's); what the
    pipeline runs between the layers stands under ``shard_map`` itself.
    A layer's own sub-scope (a component that starts with
    ``SUB_SCOPE_MARK``, bare or inside ``jvp(...)`` / ``transpose(...)``)
    is kept: ``jvp(b0_att)/~core/...`` is ``b0_att/core``."""
    if not tf_op:
        return "other", NO_TF_OP
    parts = _JIT.sub("", tf_op.rsplit(":", 1)[0]).split("/")
    head = parts[0]
    if head == "update":
        return "update", parts[1] if len(parts) > 2 else UNNAMED
    if head == "health":
        return "health", "health"
    if head in STEP_SCOPES:
        return "other", head
    layer = _WRAP.sub("", head).rstrip(")")
    if not layer and parts[1:2] == [SHARD_MAP]:
        inner = [c for c in parts[1:-1] if not _CONTROL.match(c)]
        layer = inner[0] if inner and "(" not in inner[0] else SHARD_MAP
    if layer:
        # a sub-scope opened under a vjp of the layer's own (a sparse moe
        # layer's backward makes its forward anew) stands wrapped:
        # ``transpose(jvp(~experts))``
        sub = next((c for c in (_WRAP.sub("", c).rstrip(")")
                                for c in parts[1:-1])
                    if c.startswith(SUB_SCOPE_MARK)), None)
        if sub:
            layer = "%s/%s" % (layer, sub[len(SUB_SCOPE_MARK):])
    if "transpose(" in head:
        return "backward", layer or UNNAMED
    if "jvp(" in head:
        return "forward", layer or UNNAMED
    # the first component is a primitive (no scope), or a scope outside
    # any transform, as in a forward-only program
    return "other", (head if len(parts) > 1 else UNNAMED)


_MATMUL = re.compile(r"\s(convolution|dot)\(|kind=k(Output|Convolution)")


def is_matmul(hlo: str) -> bool:
    """A convolution, a dot, or the fusion XLA builds around one."""
    return bool(_MATMUL.search(hlo)) or hlo.lstrip("%").startswith(
        ("convolution", "dot"))


def op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")


def scopes_by_op(ops: List[Op]) -> Dict[str, Tuple[str, str, bool]]:
    """op name -> (phase, layer, whether through a reader). An operation
    with no ``tf_op`` takes the scope of the first operation, in order of
    running, that reads its result (found by name in that one's HLO text)
    and has or so finds a scope."""
    first: Dict[str, Op] = {}
    for o in sorted(ops, key=lambda o: o.start_ns):
        first.setdefault(op_name(o.name), o)
    readers: Dict[str, List[str]] = {}
    for name, o in first.items():
        for operand in _OPERAND.findall(o.name.split(" = ", 1)[-1]):
            if operand in first and operand != name:
                readers.setdefault(operand, []).append(name)
    out: Dict[str, Tuple[str, str, bool]] = {}

    def scope(name: str) -> Tuple[str, str, bool]:
        if name not in out:
            out[name] = scope_of(first[name].tf_op) + (False,)
            if not first[name].tf_op:
                for reader in readers.get(name, ()):
                    phase, layer, _ = scope(reader)
                    if layer != NO_TF_OP:
                        out[name] = (phase, layer, True)
                        break
        return out[name]
    for name in first:
        scope(name)
    return out


# ---------------------------------------------------------- the arithmetic
def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events: list) -> List[Tuple[object, float]]:
    """(event, its duration less what the events nested in it cover);
    ``events`` hold start and duration at [1] and [2]."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[e, e[2]] for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= e[2]
        stack.append(i)
    return [(e, max(d, 0.0)) for e, d in out]


def reduce_ops(devices: Dict[str, Dict[str, list]],
               host_spans: Iterable[tuple],
               step_module: str = "jit_step") -> Optional[dict]:
    """The reduction, on plain tuples. ``devices``: plane -> {"modules":
    [Event], "ops": [Op or a tuple laid out like one]}; ``host_spans``:
    (name, start ns, duration ns[, line]). Nothing comes back where no
    device ran ``step_module`` twice: there is then no stretch."""
    best = None
    for dev, lines in devices.items():
        steps = [e for e in lines.get("modules", ())
                 if e[0].split("(")[0] == step_module]
        if len(steps) < 2:
            continue
        t0 = min(e[1] for e in steps)
        t1 = max(e[1] + e[2] for e in steps)
        ops = [Op(*e) for e in lines.get("ops", ())
               if e[1] >= t0 and e[1] + e[2] <= t1]
        if not ops:
            continue
        busy = _union([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
        busy_ns = sum(b - a for a, b in busy)
        if best is None or busy_ns > best["busy_ns"]:
            best = {"device": dev, "t0": t0, "t1": t1, "ops": ops,
                    "busy": busy, "busy_ns": busy_ns, "steps": len(steps)}
    if best is None:
        return None

    phase_ns = dict.fromkeys(PHASES, 0.0)
    rows: Dict[Tuple[str, str], dict] = {}
    self_ns = stale_ns = via_reader_ns = 0.0
    scopes = scopes_by_op(best["ops"])
    for op, ns in self_times(best["ops"]):
        phase, layer, via_reader = scopes[op_name(op.name)]
        phase_ns[phase] += ns
        self_ns += ns
        if via_reader:
            via_reader_ns += ns
        row = rows.setdefault((layer, phase), {
            "layer": layer, "phase": phase, "ns": 0.0, "ops": {},
            "flops": 0.0, "bytes_accessed": 0.0, "matmul": False})
        row["ns"] += ns
        name = op_name(op.name)
        row["ops"][name] = row["ops"].get(name, 0.0) + ns
        if is_matmul(op.name):
            row["matmul"] = True
            row["flops"] += op.flops
            row["bytes_accessed"] += op.bytes_accessed
            if layer == UNNAMED:
                stale_ns += ns

    def share(ns):
        return 100.0 * ns / self_ns if self_ns else 0.0
    layers = []
    for row in sorted(rows.values(), key=lambda r: -r["ns"]):
        out = {"layer": row["layer"], "phase": row["phase"],
               "self_s": row["ns"] * 1e-9, "share": share(row["ns"]),
               "ops": [[k, v * 1e-9] for k, v in
                       sorted(row["ops"].items(), key=lambda kv: -kv[1])]}
        if row["matmul"]:
            # what the profiler recorded for the row's convolutions and
            # dots over the whole stretch
            out["flops"] = row["flops"]
            out["bytes_accessed"] = row["bytes_accessed"]
        layers.append(out)
    # named by a tf_op of its own; what a reader named is beside it
    named_ns = sum(r["ns"] for r in rows.values()
                   if r["layer"] not in (UNNAMED, NO_TF_OP)) - via_reader_ns

    program = [s for s in host_spans if s[0].startswith(SPAN_PREFIXES)]
    by_line: Dict[object, list] = {}
    for s in program:
        by_line.setdefault(s[3] if len(s) > 3 else None, []).append(s)
    span_rows: Dict[str, list] = {}      # name -> [self ns, durations]
    for line_spans in by_line.values():
        for s, ns in self_times(line_spans):
            r = span_rows.setdefault(s[0], [0.0, []])
            r[0] += ns
            r[1].append(s[2])
    gaps = []
    edges = [(best["t0"], best["t0"])] + best["busy"] \
        + [(best["t1"], best["t1"])]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            mid = 0.5 * (a + b)
            inside = [s for s in program if s[1] <= mid <= s[1] + s[2]]
            # the innermost span that covers the middle of the gap
            label = min(inside, key=lambda s: s[2])[0] if inside else "none"
            gaps.append([label, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    window_ns = best["t1"] - best["t0"]
    return {
        "device": best["device"],
        "module": step_module,
        "steps": best["steps"],
        "window_s": window_ns * 1e-9,
        "busy_s": best["busy_ns"] * 1e-9,
        "idle_share": 100.0 * (1.0 - best["busy_ns"] / window_ns)
        if window_ns else 0.0,
        "phase_share": {p: share(phase_ns[p]) for p in PHASES},
        "phase_s": {p: phase_ns[p] * 1e-9 for p in PHASES},
        "named_share": share(named_ns),
        "via_reader_share": share(via_reader_ns),
        "stale_share": share(stale_ns),
        "warning": STALE_WARNING if stale_ns else None,
        "layers": layers,
        "host_spans": [{"name": k, "count": len(durs),
                        "total_s": sum(durs) * 1e-9, "self_s": own * 1e-9,
                        "median_s": statistics.median(durs) * 1e-9,
                        "max_s": max(durs) * 1e-9}
                       for k, (own, durs) in sorted(
                           span_rows.items(), key=lambda kv: -kv[1][0])],
        "idle_gaps": gaps[:TOP_GAPS],
        "idle_gap_s_by_span": _sum_by_label(gaps),
    }


def _sum_by_label(gaps) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for label, secs in gaps:
        out[label] = out.get(label, 0.0) + secs
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def reduce_trace(path: str, step_module: str = "jit_step") -> Optional[dict]:
    devices, spans = read_xplane(find_xplane(path))
    return reduce_ops(devices, spans, step_module)


# ---------------------------------------------------------------- the text
def format_report(r: dict) -> str:
    """What ``tools/trace_layers.py`` prints."""
    rows, ops_per_row = REPORT_ROWS, REPORT_OPS_PER_ROW
    out = ["device %s: %d runs of %s, stretch %.6f s, busy %.6f s, idle "
           "%.3f%%" % (r["device"], r["steps"], r["module"], r["window_s"],
                       r["busy_s"], r["idle_share"])]
    if r["warning"]:
        out.append("WARNING: %s (%.1f%% of busy time is convolutions and "
                   "dots under an empty scope)"
                   % (r["warning"], r["stale_share"]))
    out.append("")
    out.append("device self time by phase (shares of busy time; %.2f%% "
               "stands under a named scope by its own tf_op, %.2f%% more "
               "through the operation that reads its result: the compiler's "
               "own masks and copies, which carry no tf_op)"
               % (r["named_share"], r["via_reader_share"]))
    for p in PHASES:
        out.append("  %-9s %10.6f s %6.2f%%"
                   % (p, r["phase_s"][p], r["phase_share"][p]))
    out.append("")
    out.append("device self time by layer and phase, the dearest first (a "
               "row is exact to a fusion: a fusion goes to the scope of its "
               "root instruction)")
    out.append("  %-44s %-9s %10s %7s  %s"
               % ("layer", "phase", "self s", "share", "operations"))
    for row in r["layers"][:rows]:
        ops = ", ".join("%s %.1f ms" % (k, 1e3 * v)
                        for k, v in row["ops"][:ops_per_row])
        if len(row["ops"]) > ops_per_row:
            ops += ", +%d more" % (len(row["ops"]) - ops_per_row)
        out.append("  %-44s %-9s %10.6f %6.2f%%  %s"
                   % (row["layer"][:44], row["phase"], row["self_s"],
                      row["share"], ops))
        if "flops" in row and row["self_s"]:
            out.append("  %-44s %-9s recorded for its convolutions and dots:"
                       " %.4g flop, %.4g bytes" % ("", "", row["flops"],
                                                   row["bytes_accessed"]))
    if len(r["layers"]) > rows:
        out.append("  ... %d more rows (--json has them all)"
                   % (len(r["layers"]) - rows))
    out.append("")
    out.append("host spans of the program (telemetry.span on the profiler's "
               "clock)")
    if not r["host_spans"]:
        out.append("  none: no train.* / init.* / jit.* / io.* span was "
                   "open while the profiler recorded")
    for s in r["host_spans"]:
        out.append("  %-28s x%-5d total %10.6f s  self %10.6f s  median "
                   "%9.3f ms  max %9.3f ms"
                   % (s["name"], s["count"], s["total_s"], s["self_s"],
                      1e3 * s["median_s"], 1e3 * s["max_s"]))
    out.append("")
    out.append("longest idle gaps of the device, each named by the "
               "innermost program span over its middle")
    for label, secs in r["idle_gaps"]:
        out.append("  %-28s %10.3f us" % (label, 1e6 * secs))
    out.append("idle time by span: " + ", ".join(
        "%s %.3f ms" % (k, 1e3 * v)
        for k, v in r["idle_gap_s_by_span"].items()))
    return "\n".join(out)
