#!/usr/bin/env python
"""Capture an xprof trace of the benched train steps for MFU analysis.

Usage: python tools/profile_bench.py [alexnet|googlenet] [outdir]

Writes a jax profiler trace (xplane) under outdir (default
./profile_out/<model>); put its device time to layers with
tools/trace_layers.py, or open it in TensorBoard's profile plugin.
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def main():
    from cxxnet_tpu.utils import enable_compile_cache
    enable_compile_cache()
    model = sys.argv[1] if len(sys.argv) > 1 else "alexnet"
    outdir = sys.argv[2] if len(sys.argv) > 2 else \
        os.path.join("profile_out", model)
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.models import alexnet_trainer, googlenet_trainer
    from cxxnet_tpu.io.data import DataBatch

    bf16 = "eval_train = 0\ncompute_dtype = bfloat16\n"
    if model == "alexnet":
        batch, hw = 256, 227
        tr = alexnet_trainer(batch_size=batch, input_hw=hw, dev="tpu",
                             extra_cfg=bf16)
    else:
        batch, hw = 128, 224
        tr = googlenet_trainer(batch_size=batch, input_hw=hw, dev="tpu",
                               extra_cfg=bf16)

    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(rs.rand(batch, 3, hw, hw).astype(np.float32))
    b.label = jax.device_put(
        rs.randint(0, 1000, (batch, 1)).astype(np.float32))
    b.batch_size = batch

    for _ in range(3):               # compile + warm
        tr.update(b)
    float(jnp.sum(next(v for p in tr.params for v in p.values())))

    os.makedirs(outdir, exist_ok=True)
    with jax.profiler.trace(outdir):
        for _ in range(10):
            tr.update(b)
        float(jnp.sum(next(v for p in tr.params for v in p.values())))
    print("trace written to", outdir)


if __name__ == "__main__":
    main()
