#!/usr/bin/env python
"""Per-model HBM memory report from XLA's compiled-program analysis.

Usage: python tools/memory_report.py [model]
           [--pp K|--zero|--fsdp|--tp K] [n_devices]

Compiles the model's train step (without executing it) and prints XLA's
memory_analysis(): argument (param/opt-state) bytes, temp (activation)
bytes, output bytes — per device. Run on the 8-device virtual CPU mesh
(no TPU needed: set JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8) to see how the
parallelism keys change the per-device footprint:

  python tools/memory_report.py mlp            # replicated baseline
  python tools/memory_report.py mlp --zero     # ZeRO opt-state sharding
  python tools/memory_report.py mlp --pp 4     # stage-packed pipeline
  python tools/memory_report.py alexnet --tp 2 # Megatron fullc sharding
  python tools/memory_report.py deep --pp 4 --remat  # PP + activation
                                               # remat (the AD stash knob)

The PP case: AD differentiates through the fill-drain scan
(parallel/pipeline.py), stashing every tick's boundary activations plus
stage internals — n_micro + n_stages - 1 ticks of them. That stash is
XLA "temp" bytes here; ``--remat`` checkpoints every trunk layer so
the backward recomputes stage internals instead of stashing them (the
per-microbatch memory/compute trade: temp bytes down, ~1/3 more
FLOPs). ``deep`` is a uniform 16-layer trunk built for pp4.

This turns the ZeRO / pipeline memory claims (doc/multichip.md) into
measured bytes; tests/test_compose.py asserts the shard-size ratios, this
tool shows the absolute numbers for any config.
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def build(model, extra):
    from cxxnet_tpu.models import (alexnet_trainer, googlenet_trainer,
                                   transformer_lm_trainer)
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    import jax
    n = "%s:0-%d" % (jax.default_backend(),
                     int(os.environ.get("_NDEV", "8")) - 1)
    if model == "alexnet":
        return alexnet_trainer(batch_size=32, input_hw=67, dev=n,
                               extra_cfg=extra), (32, 3, 67, 67), 1000
    if model == "googlenet":
        return googlenet_trainer(batch_size=16, input_hw=128, dev=n,
                                 extra_cfg=extra), (16, 3, 128, 128), 1000
    if model == "lm":
        tr = transformer_lm_trainer(vocab=512, seq=256, batch_size=8,
                                    dim=128, nhead=4, nlayer=2, dev=n,
                                    extra_cfg=extra)
        return tr, (8, 1, 1, 256), 512
    if model == "deep":
        # uniform 16-layer trunk: the natural pp4 customer; wide enough
        # (512) that the per-tick AD stash dominates the report
        conf = "netconfig = start\n"
        for i in range(16):
            conf += ("layer[+1] = fullc:d%d\n  nhidden = 512\n"
                     "  init_sigma = 0.05\n" % i)
            conf += "layer[+1] = relu\n"
        conf += """layer[+1] = fullc:head
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig = end
input_shape = 1,1,512
batch_size = 64
eta = 0.1
momentum = 0.9
dev = %s
""" % n + extra
        tr = Trainer()
        for k, v in parse_config_string(conf):
            tr.set_param(k, v)
        tr.init_model()
        return tr, (64, 1, 1, 512), 10
    conf = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 512
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 256
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc3
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig = end
input_shape = 1,1,784
batch_size = 64
eta = 0.1
momentum = 0.9
dev = %s
""" % n + extra
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr, (64, 1, 1, 784), 10


def main():
    args = [a for a in sys.argv[1:]]
    model = args[0] if args and not args[0].startswith("--") else "mlp"
    extra = ""
    consumed = set()
    for flag, key in (("--pp", "pipeline_parallel"),
                      ("--micro", "pipeline_micro"),
                      ("--tp", "model_parallel")):
        if flag in args:
            i = args.index(flag)
            extra += "%s = %s\n" % (key, args[i + 1])
            consumed.add(i + 1)
    if "--zero" in args:
        extra += "update_on_server = 1\n"
    if "--fsdp" in args:
        extra += "fsdp = 1\n"
    if "--remat" in args:
        extra += "remat = 1\n"
    tail = [a for i, a in enumerate(args)
            if i > 0 and i not in consumed and a.isdigit()]
    ndev = int(tail[-1]) if tail else None

    import jax
    if ndev:
        os.environ["_NDEV"] = str(ndev)
    tr, shape, nclass = build(model, extra)
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(0)
    b = DataBatch()
    if model == "lm":
        b.data = rs.randint(0, nclass, shape).astype(np.float32)
        b.label = rs.randint(0, nclass,
                             (shape[0], shape[3])).astype(np.float32)
    else:
        b.data = rs.rand(*shape).astype(np.float32)
        b.label = rs.randint(0, nclass, (shape[0], 1)).astype(np.float32)
    b.batch_size = shape[0]
    lowered = tr.lower_update(b)
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    if m is None:
        print("backend exposes no memory_analysis()")
        return
    def gb(x):
        return "%.2f MiB" % (x / (1 << 20))
    print("model=%s extra=%r devices=%d" %
          (model, extra.replace("\n", " "), tr.mesh.devices.size
           if tr.mesh is not None else 1))
    print("  per-device argument (params+opt state):",
          gb(m.argument_size_in_bytes))
    print("  per-device temp (activations/workspace):",
          gb(m.temp_size_in_bytes))
    print("  per-device output:", gb(m.output_size_in_bytes))
    print("  generated code:", gb(m.generated_code_size_in_bytes))
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    print("  total per device:", gb(total))
    # headroom vs the shared DeviceSpec table (cxxnet_tpu/utils/perf.py
    # — the same capacity the live ledger's cxxnet_hbm_headroom_bytes
    # gauge reports, so offline sizing and runtime accounting agree)
    from cxxnet_tpu.utils import perf
    spec = perf.device_spec()   # the target chip, whatever compiled this
    print("  %s HBM capacity: %s  ->  headroom: %s (%.1f%% used)"
          % (spec.name, gb(spec.hbm_capacity),
             gb(spec.hbm_capacity - total),
             100.0 * total / spec.hbm_capacity))


if __name__ == "__main__":
    main()
