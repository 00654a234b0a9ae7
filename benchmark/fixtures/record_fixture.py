"""Record the small trace the reduction's test reads (run on the chip):

    python3 benchmark/fixtures/record_fixture.py chiprun_out/fixture

AlexNet's conf at 3x67x67 and 16 rows, one traced sync-group of 4 steps.
Copy the ``.xplane.pb`` it leaves to ``benchmark/fixtures/tiny.xplane.pb``
and write ``tiny.expected.json`` from ``trace_reduce.reduce_trace`` on it.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out: str) -> int:
    import jax
    from benchmark import run
    spec = run.resolve("alexnet-resident")
    cfg = dict(spec["cfg"], input_shape=[3, 67, 67], batch_per_chip=16,
               ref_block=16)
    run.check_device(1)
    window = run.load_part(run.BENCH_DIR, "windows", spec["traffic"]["kind"])
    program = run.load_part(run.BENCH_DIR, "programs", cfg["program"]).Program(
        spec["conf_text"], cfg, 1, 7, spec["traffic"])
    for _ in range(3):
        program.step()
    program.sync()
    os.makedirs(out, exist_ok=True)
    tracer = run.Tracer("jit_step",
                        keep_to=os.path.join(out, "tiny.xplane.pb"))
    window.run_window(program.step, program.sync, 16, 0.0, 4,
                      span=jax.profiler.TraceAnnotation, trace_group=0,
                      trace_start=tracer.start, trace_stop=tracer.stop)
    print(tracer.reduced)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
