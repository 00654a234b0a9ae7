#!/usr/bin/env python
"""Summarize a per-request serving trace (statusd
``/trace?request=<id>``, utils/servd flight recorder).

Usage: python tools/summarize_trace.py <trace.json[.gz]>

Prints the phase split (queue_wait / dispatch / prefill / decode,
doc/observability.md) with percentages of the request's wall-clock, the
recompiles the request paid, and the phase coverage — the one-slow-request
triage view without opening Perfetto.

A jax profiler capture (``profile_dir``, ``/profilez``) is read by
``tools/trace_layers.py``, which puts device time to layers.
"""

import gzip
import json
import sys

# the serving request-phase lanes (telemetry.REQUEST_PHASES — literal
# here so the tool stays dependency-free and runs on a bare checkout)
REQUEST_PHASES = ("queue_wait", "dispatch", "prefill", "decode")


def load_trace(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def summarize_request(events) -> None:
    """Per-request trace: phase table + recompiles + coverage."""
    xs = [e for e in events if e.get("ph") == "X" and "dur" in e]
    phases = [e for e in xs if e["name"] in REQUEST_PHASES]
    rid = outcome = "?"
    for e in phases:
        args = e.get("args") or {}
        rid = args.get("request", rid)
        outcome = args.get("outcome", outcome)
    # the phases TILE the request's wall-clock (utils/servd) — the
    # phase lane, not the recompile annotations, defines the total
    t0 = min(e["ts"] for e in phases or xs)
    t1 = max(e["ts"] + e["dur"] for e in phases or xs)
    total = max(t1 - t0, 1e-9)
    covered = sum(e["dur"] for e in phases)
    print("request %s (%s): total %.2fms" % (rid, outcome, total / 1e3))
    print("%-12s %10s %6s" % ("phase", "ms", "pct"))
    by_name = {e["name"]: e for e in phases}
    for name in REQUEST_PHASES:
        e = by_name.get(name)
        if e is not None:
            print("%-12s %10.2f %5.1f%%"
                  % (name, e["dur"] / 1e3, 100.0 * e["dur"] / total))
    comps = [e for e in xs if e["name"].startswith("compile:")]
    for e in comps:
        print("%-12s %10.2f        %s (%s)"
              % ("recompile", e["dur"] / 1e3, e["name"][len("compile:"):],
                 (e.get("args") or {}).get("cause", "?")))
    print("phase coverage: %.1f%% of request wall-clock"
          % (100.0 * covered / total))


def main():
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    path = sys.argv[1]
    events = load_trace(path).get("traceEvents", [])
    if not any(e.get("ph") == "X" and e.get("name") in REQUEST_PHASES
               for e in events):
        raise SystemExit(
            "%s holds no request phase lanes; a jax profiler capture is "
            "read by tools/trace_layers.py" % path)
    print("trace: %s" % path)
    summarize_request(events)


if __name__ == "__main__":
    main()
