#!/usr/bin/env python
"""Paired channels_last A/B on the bench models (adjacent runs, so
shared-chip drift cancels). One JSON line per variant.

Usage: python tools/layout_ab.py [vgg|alexnet|googlenet|resnet|all]
Default: the two variants still unmeasured (vgg b64, alexnet b1024).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

BF16 = "eval_train = 0\ncompute_dtype = bfloat16\n"


def measure(tr, shape, nclass, batch, steps=15):
    """Shared A/B measurement protocol (pool_ab.py imports it too so the
    two tools' numbers stay methodology-comparable): device-resident
    batch, 3-step warmup, value-fetch sync (waits for the last step like
    block_until_ready: every step rewrites the params it fetches from),
    best of two timed passes."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(rs.rand(batch, *shape).astype(np.float32))
    b.label = jax.device_put(
        rs.randint(0, nclass, (batch, 1)).astype(np.float32))
    b.batch_size = batch

    def sync():
        float(jnp.sum(next(v for p in tr.params for v in p.values())))

    for _ in range(3):
        tr.update(b)
    sync()
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            tr.update(b)
        sync()
        best = max(best, steps * batch / (time.perf_counter() - t0))
    return best


def ab(name, make, shape, nclass, batch, steps=15):
    for cl in (0, 1):
        tr = make("channels_last = %d\n" % cl)
        ips = measure(tr, shape, nclass, batch, steps)
        print(json.dumps({"variant": "%s_cl%d" % (name, cl),
                          "img_per_sec": round(ips, 1)}), flush=True)


def main():
    from cxxnet_tpu.utils import enable_compile_cache
    enable_compile_cache()
    from cxxnet_tpu import models as M
    which = sys.argv[1] if len(sys.argv) > 1 else "default"
    if which in ("vgg", "all", "default"):
        ab("vgg16_b64", lambda e: M.vgg_trainer(
            batch_size=64, input_hw=224, dev="tpu", remat=1,
            extra_cfg=BF16 + e), (3, 224, 224), 1000, 64)
    if which in ("alexnet", "all", "default"):
        ab("alexnet_b1024", lambda e: M.alexnet_trainer(
            batch_size=1024, input_hw=227, dev="tpu",
            extra_cfg=BF16 + e), (3, 227, 227), 1000, 1024)
    if which in ("googlenet", "all"):
        ab("googlenet_b128", lambda e: M.googlenet_trainer(
            batch_size=128, input_hw=224, dev="tpu",
            extra_cfg=BF16 + e), (3, 224, 224), 1000, 128, steps=30)
    if which in ("resnet", "all"):
        ab("resnet18_b128", lambda e: M.resnet_trainer(
            batch_size=128, input_hw=224, dev="tpu",
            extra_cfg=BF16 + e), (3, 224, 224), 1000, 128, steps=30)


if __name__ == "__main__":
    main()
