"""Record a profiler trace of a few train steps (run on the chip):

    python3 tests/fixtures/record_layers_trace.py --out chiprun_out/layers \
        [--workload alexnet-resident] [--shape 3,67,67] [--rows 16] [--steps 4]
        [--no-trace]

One of the benchmark's cells (its conf, its program adapter, its resident
batches), at the cell's own size or cut down by ``--shape`` / ``--rows``,
compiled into a fresh compile cache: jax's persistent cache ignores metadata
in its key, so a warm one hands back an executable from before the scopes.
Three groups of ``--steps`` steps run back to back, the middle one under the
profiler; the file lands at ``<out>/<workload>.xplane.pb`` and the last line
printed gives each group's seconds (what tracing costs while it is on) and
``phases``, the program's phase account (``telemetry.phases()``): what
``init_model`` and the step's first call took, the latter with jax's own
trace / lower / compile / cache_load beside it, and ``paths``, its path
account (``telemetry.paths()``: which lowering each traced layer took and,
for the flash kernels, the tiles their schedule holds). With ``--no-trace`` nothing
is traced and the compile cache is the process's usual one, as the
benchmark's set-up finds it: run it twice, and the second line is the split
of ``step_build_s`` on a warm cache.

The fixture of tests/test_trace_layers.py is AlexNet's conf at 3x67x67,
16 rows, four steps:

    python3 tests/fixtures/record_layers_trace.py --out chiprun_out/layers \
        --shape 3,67,67 --rows 16 --steps 4
    cp chiprun_out/layers/alexnet-resident.xplane.pb \
        tests/fixtures/layers.xplane.pb
    python3 tools/trace_layers.py tests/fixtures/layers.xplane.pb --json \
        | python3 -m json.tool --indent 1 --sort-keys \
        > tests/fixtures/layers.expected.json
"""

import argparse
import atexit
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", default="alexnet-resident")
    ap.add_argument("--shape", help="c,h,w in place of the cell's own")
    ap.add_argument("--rows", type=int, help="rows a chip, in place of the "
                    "cell's own")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--no-trace", action="store_true",
                    help="set-up and the first step only, on the usual "
                    "compile cache: the phase account of a warm start")
    args = ap.parse_args(argv)

    if not args.no_trace:
        # before jax is imported: an empty cache of this run's own, gone
        # again when the process ends
        fresh = tempfile.mkdtemp(prefix="fresh_jax_cache_")
        atexit.register(shutil.rmtree, fresh, ignore_errors=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = fresh
    import jax
    from benchmark import run
    from cxxnet_tpu.utils import telemetry
    if args.no_trace:
        run.enable_compile_cache()
    spec = run.resolve(args.workload)
    cfg = dict(spec["cfg"])
    if args.shape:
        cfg["input_shape"] = [int(v) for v in args.shape.split(",")]
    if args.rows:
        cfg["batch_per_chip"] = cfg["ref_block"] = args.rows
    chips = spec["cell"]["chips"]
    run.check_device(chips)
    program = run.load_part(run.BENCH_DIR, "programs", cfg["program"]).Program(
        spec["conf_text"], cfg, chips, 7, spec["traffic"])
    t0 = time.perf_counter()
    program.step()
    program.sync()
    first_step_s = time.perf_counter() - t0
    d = jax.devices()[0]
    line = {"workload": args.workload, "input_shape": cfg.get("input_shape"),
            "rows_per_chip": cfg["batch_per_chip"],
            "first_step_s": first_step_s, "phases": telemetry.phases(),
            "paths": telemetry.paths(),
            "device": {"platform": d.platform, "kind": d.device_kind,
                       "count": chips}}
    if args.no_trace:
        print(json.dumps(line))
        return 0
    for _ in range(3):
        program.step()
    program.sync()

    os.makedirs(args.out, exist_ok=True)
    trace_dir = tempfile.mkdtemp(prefix="layers_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # spans come from telemetry.span
    groups = []
    for traced in (False, True, False):
        if traced:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            program.step()
        program.sync()
        groups.append(time.perf_counter() - t0)
        if traced:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    kept = os.path.join(args.out, args.workload + ".xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(trace_dir, ignore_errors=True)
    health = getattr(getattr(program, "trainer", None), "last_health", None)
    if health is not None:
        # the traced stretch's last step: loss, gradient norm, and whatever
        # the layers put behind them (a sparse moe layer's pairs held and
        # fullest expert)
        line["last_health"] = [float(v) for v in health]
    line.update(steps=args.steps,
                group_s={"before": groups[0], "traced": groups[1],
                         "after": groups[2]},
                xplane=kept, xplane_bytes=os.path.getsize(kept))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
