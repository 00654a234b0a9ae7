"""The comparison that decides ``correct``: what the timed path produced in
its first steps against the plain reference, number by number, each with a
limit of its own (``benchmark/limits/<workload>.json``; PERF.md gives the
readings every limit was set from).

Losses are compared step by step. Norms are compared by the worst leaf: the
gap between the program's norm and the reference's -- not the norm of their
difference -- over the reference's norm of that leaf or of the median leaf,
whichever is larger, since some gradients are all but nought.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone and is left out of the change
_TINY_GRADIENT = 1e-3


def worst_leaf(got: Dict[str, float], ref: Dict[str, float],
               skip=()) -> Tuple[float, str]:
    floor = statistics.median(ref.values())
    worst, at = 0.0, ""
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        if leaf not in got:
            return float("inf"), leaf
        gap = abs(got[leaf] - r) / max(r, floor, 1e-30)
        if not gap <= worst:          # also catches a nan
            worst, at = gap, leaf
    return worst, at


def numbers(got: dict, ref: dict) -> Dict[str, dict]:
    """name -> {"value", "at"}: every number the comparison reads."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], ref["loss"])):
        out["loss%d" % (i + 1)] = {"value": abs(a - b) / abs(b), "at": ""}
    if len(got["loss"]) != len(ref["loss"]):
        out["loss%d" % len(ref["loss"])] = {"value": float("inf"), "at": ""}
    v, at = worst_leaf(got["grad_norm"], ref["grad_norm"])
    out["grad_worst"] = {"value": v, "at": at}
    med = statistics.median(ref["grad_norm"].values())
    still = [n for n, g in ref["grad_norm"].items()
             if g < _TINY_GRADIENT * med]
    v, at = worst_leaf(got["change_norm"], ref["change_norm"], skip=still)
    out["change_worst"] = {"value": v, "at": at}
    return out


def judge(nums: Dict[str, dict], limits: Dict[str, float]) -> List[dict]:
    """One row per number, each beside its limit. A number that the limits
    do not name, or a limit with no number, fails: nothing passes by default.
    A limit of ``None`` names a number that is read and shown but not
    compared, because no limit would hold (PERF.md gives its readings)."""
    rows = []
    for name in sorted(set(nums) | set(limits)):
        value = nums.get(name, {}).get("value")
        limit = limits.get(name)
        if name in limits and limit is None:
            ok = value is not None
        else:
            ok = value is not None and limit is not None and value <= limit
        rows.append({"name": name, "value": value, "limit": limit,
                     "at": nums.get(name, {}).get("at", ""), "ok": bool(ok)})
    return rows
