"""Record the small trace with scopes in it that ``scope_s`` is tested on
(run on the chip; ``tiny.xplane.pb`` dates from before the program named
its scopes and stays as it is):

    python3 benchmark/fixtures/record_scoped_fixture.py chiprun_out/fixture

A two-layer program of this file's own, nothing of the program under test:
layer ``l0`` (a product and a tanh), layer ``l1`` with a marked sub-scope
``~core`` under ``jax.checkpoint`` (so that its backward pass makes the
forward anew under ``checkpoint/rematted_computation``) and a second,
``~gate``, inside a ``custom_vjp`` whose backward takes a vjp of its own
(which is what writes a wrapped ``transpose(jvp(~gate))``), a loss in no
scope, the gradients' joint norm under a scope of the step's own (``clip``:
every update waits for it, so XLA cannot fuse an update into the product
that makes its gradient) and SGD under ``update/<layer>``. One traced group
of 4 steps
of ``jit_step``, compiled into no cache. It leaves ``scoped.xplane.pb`` and
``scoped.expected.json`` (``trace_reduce.reduce_trace`` on it) in the
directory given: copy both to ``benchmark/fixtures/``.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ROWS, WIDTH, STEPS = 256, 512, 4


def make_step():
    import jax
    import jax.numpy as jnp

    def gate_of(h, w):
        with jax.named_scope("~gate"):
            return h * jax.nn.sigmoid(h @ w)

    @jax.custom_vjp
    def gate(h, w):
        return gate_of(h, w)

    def gate_fwd(h, w):
        return gate_of(h, w), (h, w)

    def gate_bwd(res, g):
        return jax.vjp(gate_of, *res)[1](g)
    gate.defvjp(gate_fwd, gate_bwd)

    def core(h, w):
        with jax.named_scope("~core"):
            return jnp.maximum(h @ w, 0.0)

    def step(params, x, y):
        def loss_of(p):
            with jax.named_scope("l0"):
                h = jnp.tanh(x @ p["l0"])
            with jax.named_scope("l1"):
                h = jax.checkpoint(core)(h, p["l1"])
                h = gate(h, p["l1_gate"])
            return jnp.mean(jnp.square(h - y))
        loss, grads = jax.value_and_grad(loss_of)(params)
        with jax.named_scope("clip"):
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                for g in grads.values()))
            scale = jnp.minimum(1.0, 1.0 / (norm + 1e-6))
        new = {}
        for name in sorted(params):
            with jax.named_scope("update"), jax.named_scope(
                    name.split("_")[0]):
                new[name] = (params[name].astype(jnp.float32)
                             - 0.01 * scale * grads[name]
                             ).astype(params[name].dtype)
        return new, loss
    return jax.jit(step)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from benchmark import run, trace_reduce
    run.check_device(1)
    step = make_step()
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    params = {name: 0.05 * jax.random.normal(k, (WIDTH, WIDTH), jnp.bfloat16)
              for name, k in zip(("l0", "l1", "l1_gate"), keys)}
    x = jax.random.normal(keys[3], (ROWS, WIDTH), jnp.bfloat16)
    y = jax.random.normal(keys[4], (ROWS, WIDTH), jnp.bfloat16)
    for _ in range(3):
        params, loss = step(params, x, y)
    float(loss)
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "scoped.xplane.pb")
    tracer = run.Tracer("jit_step", keep_to=kept)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.update_call"):
        for _ in range(STEPS):
            params, loss = step(params, x, y)
    with jax.profiler.TraceAnnotation("bench.sync"):
        float(loss)
    tracer.stop()
    expected = dict(trace_reduce.reduce_trace(kept, "jit_step"),
                    step_module="jit_step")
    with open(os.path.join(out, "scoped.expected.json"), "w") as f:
        json.dump(expected, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(expected["scope_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
