"""Readings that the comparison's limits are set from, taken on the chip at
the cell's own size, all in one process (set-up is most of a run):

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3]

For each seed: the program's first steps against the reference (the lower
readings). For each control seed: the reference computed in the precision
below the configuration's (``fp8``; ``bf16`` beside it, which should read
what the program reads), and the reference with each fault a training cell
can have planted in it (half of the batch left out; on four chips the
exchange left out, which leaves each chip a quarter), each put in the
program's place against the same reference (the upper readings). Where
both count something of the run (a language model's pairs held), the
program's own count after its last step stands beside the reference's,
step by step. What it read goes to
``chiprun_out/calibrate/<workload>.json``. A benchmark run never
calls this; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT = os.path.join("chiprun_out", "calibrate")


def main(argv=None) -> int:
    from benchmark import compare, run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    chips = cell["chips"]
    run.check_device(chips)
    run.enable_compile_cache()
    kind = run.load_part(run.BENCH_DIR, "windows", traffic["kind"])
    reference = run.load_part(run.BENCH_DIR, "references", cfg["reference"])
    program_of = run.load_part(run.BENCH_DIR, "programs", cfg["program"])
    batch = cfg["batch_per_chip"] * chips
    steps = traffic["compare_steps"]

    def make_ref(**kw):
        return reference.for_config(spec["conf_text"], cfg, batch, **kw)
    ref = make_ref()
    wants, out = {}, {"workload": args.workload, "program": {}, "control": {},
                      "counted": {}}

    def want(seed):
        if seed not in wants:
            t = time.perf_counter()
            wants[seed] = ref.run(seed, steps)
            print("reference", seed, "%.2f s" % (time.perf_counter() - t),
                  flush=True)
        return wants[seed]

    def save():
        os.makedirs(OUT, exist_ok=True)
        with open(os.path.join(OUT, args.workload + ".json"), "w") as f:
            json.dump(out, f, indent=1)

    def show(tag, seed, nums):
        row = {k: v["value"] for k, v in nums.items()}
        print(tag, seed, json.dumps(row), json.dumps(
            {k: v["at"] for k, v in nums.items() if v["at"]}), flush=True)
        return row

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        program = program_of.Program(spec["conf_text"], cfg, chips, seed,
                                     traffic)
        got = kind.first_steps(program, ref.hyper, steps)
        said = program.gauges() if hasattr(program, "gauges") else {}
        program.release()
        del program
        out["program"][seed] = show("program", seed,
                                    compare.numbers(got, want(seed)))
        if said or want(seed).get("pairs_held"):
            out["counted"][seed] = {
                "program_last_step": said,
                "reference": want(seed).get("pairs_held")}
            print("counted", seed, json.dumps(out["counted"][seed]),
                  flush=True)
        save()
    controls = {"fp8": {"precision": "fp8"}, "bf16": {"precision": "bf16"},
                "half_batch": {"rows_used": batch // 2}}
    if chips > 1:
        controls["no_exchange"] = {"rows_used": batch // chips}
    for name, how in controls.items():
        other = make_ref(**how)
        out["control"][name] = {}
        for seed in [int(s) for s in args.control_seeds.split(",") if s]:
            out["control"][name][seed] = show(
                name, seed, compare.numbers(other.run(seed, steps),
                                            want(seed)))
        del other
        save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
