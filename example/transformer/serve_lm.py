#!/usr/bin/env python
"""Serving demo: train the lm.conf transformer briefly, then exercise
every serving surface on the SAME weights and check they agree token
for token:

1. in-process  — Trainer.generate (KV-cached jitted scan)
2. artifacts   — export_decode -> api.load_decode (prefill/step
                 StableHLO pair, params baked in, jax-only at serving
                 time, versioned CXTF frames)
3. tensor-parallel — the same model served with model_parallel = 2 on
                 a virtual device mesh (weights Megatron-sharded; run
                 with XLA_FLAGS=--xla_force_host_platform_device_count=8
                 JAX_PLATFORMS=cpu to try it without a TPU slice)

Usage: python serve_lm.py [steps] [dev]   (default 150 steps; ~100%
next-token accuracy is reached around 400 — serving agreement holds at
any step. ``dev`` overrides lm.conf's ``dev = tpu``, e.g. ``cpu``)
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

from train_lm import make_batch  # the cyclic-walk corpus


def main():
    steps = int(sys.argv[1]) if len(sys.argv) > 1 else 150
    dev = ["dev = %s" % sys.argv[2]] if len(sys.argv) > 2 else []
    import jax
    from cxxnet_tpu import api
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    from cxxnet_tpu.utils import serializer

    with open(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "lm.conf")) as f:
        conf = "\n".join([f.read()] + dev)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for s in range(steps):
        tr.update(make_batch(rs, tr.batch_size))
    print("trained %d steps" % steps)

    prompts = np.stack([np.arange(8) % 28, (3 * np.arange(8) + 1) % 28])
    n_new = 8

    # 1. in-process KV-cached generation
    got = tr.generate(prompts, n_new)
    print("in-process generate:", got.tolist())

    # 2. standalone artifacts: prefill + step StableHLO pair
    pre_b, step_b = tr.export_decode(batch_size=2,
                                     prompt_len=prompts.shape[1])
    with tempfile.TemporaryDirectory() as td:
        p1, p2 = os.path.join(td, "pre.hlo"), os.path.join(td, "step.hlo")
        open(p1, "wb").write(pre_b)
        open(p2, "wb").write(step_b)
        gen = api.load_decode(p1, p2)
        got_art = gen(prompts, n_new)
    assert np.array_equal(got_art, got), "artifact loop must match"
    print("artifact decode loop: MATCH")

    # 3. tensor-parallel serving (skipped without >= 2 devices)
    if len(jax.devices()) >= 2:
        w = serializer.Writer()
        tr.save_model(w)
        tr2 = Trainer()
        for k, v in parse_config_string(conf):
            tr2.set_param(k, v)
        tr2.set_param("dev", "%s:0-%d" % (jax.devices()[0].platform,
                                          len(jax.devices()) - 1))
        tr2.set_param("model_parallel", "2")
        tr2.init_model()
        tr2.load_model(serializer.Reader(w.getvalue()))
        got_tp = tr2.generate(prompts, n_new)
        assert np.array_equal(got_tp, got), "tp serving must match"
        print("tensor-parallel serving (mp=2): MATCH")
    else:
        print("tensor-parallel serving: skipped (1 device)")
    print("SERVING DEMO PASSED")


if __name__ == "__main__":
    main()
