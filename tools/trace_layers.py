#!/usr/bin/env python
"""Put a jax profiler trace to the program's layers.

Usage: python tools/trace_layers.py <profile dir or .xplane.pb>
                                    [--module jit_step] [--json]

For the stretch between the first and the last run of the step module on
the busiest device: device self time by phase (forward / backward / update /
health / other) and by layer x phase with the operations that make each row
up, the program's host spans (``train.update`` > ``train.h2d``,
``train.step`` > ``train.args``, ``train.dispatch``) with count and self
time, and the ten longest idle gaps,
each named by the innermost program span that covers its middle. The
arithmetic is cxxnet_tpu/utils/devtrace.py; doc/observability.md says where
the names come from. Needs neither jax nor a chip.

A trace is written by ``profile_dir = <dir>`` (the second round of a
training run), statusd's ``/profilez?secs=N`` or
``tests/fixtures/record_layers_trace.py``.
"""

import argparse
import importlib.util
import json
import os
import sys

DEVTRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "cxxnet_tpu", "utils", "devtrace.py")


def main(argv=None) -> int:
    # by path, not through the package: cxxnet_tpu/__init__ imports jax
    spec = importlib.util.spec_from_file_location("devtrace", DEVTRACE)
    devtrace = importlib.util.module_from_spec(spec)
    sys.modules["devtrace"] = devtrace
    spec.loader.exec_module(devtrace)

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a profile directory or an .xplane.pb")
    ap.add_argument("--module", default="jit_step",
                    help="the XLA module whose runs bound the stretch")
    ap.add_argument("--json", action="store_true",
                    help="the whole reduction as one JSON object")
    args = ap.parse_args(argv)
    try:
        path = devtrace.find_xplane(args.path)
    except FileNotFoundError as e:
        print(e, file=sys.stderr)
        return 1
    reduced = devtrace.reduce_trace(path, args.module)
    if reduced is None:
        print("no device ran module %r twice in %s: there is no stretch to "
              "reduce (--module names another)" % (args.module, path),
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(reduced))
    else:
        print("trace: %s" % path)
        print(devtrace.format_report(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main())
