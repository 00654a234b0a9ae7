"""Write the lowered train step of each benchmark configuration as text,
locations stripped, so that two trees can be compared without a chip:

    cd <parent tree> && python <this file> /tmp/lowered/parent [--tpu-like]
    cd <change tree> && python <this file> /tmp/lowered/change [--tpu-like]
    diff -r /tmp/lowered/parent /tmp/lowered/change

Conf text, ``extra_cfg``, keys, layers and dtype are the configuration's
own; the batch (and the sequence) are cut to what a CPU lowers in seconds.
``alexnet-b2048`` is also lowered on a four-device mesh (``dev = cpu:0-3``),
as ``alexnet-dp4`` runs it; the language model also with every expert held
(``nexpert_held = 64``: the sparse ``moe`` lowering's whole sorted side, no
bound and no ``cond``, PR 33). Each is lowered twice: ``Trainer.lower_update``
and the step the cells really run (``health_monitor = 1``).

``--tpu-like`` follows the branches the chip takes as far as a CPU lowering
can: ``channels_last = 1`` (auto turns it on only on a TPU), the Pallas
kernels forced on through the test hook (the interpreter's lowering), batch
128 so that the channels-last LRN kernel tiles. Nothing here is a timing."""
import hashlib
import json
import os
import re
import sys

if __name__ == "__main__":      # as a script: before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, os.getcwd())

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402
from cxxnet_tpu import ops                               # noqa: E402
from cxxnet_tpu.io.data import DataBatch                 # noqa: E402
from cxxnet_tpu.nnet.trainer import Trainer              # noqa: E402
from cxxnet_tpu.utils.config import parse_config_string  # noqa: E402


def build(name, chips, batch, seq, tpu_like, all_held=False):
    cfg = json.load(open("benchmark/configs/%s.json" % name))
    conf = open("benchmark/" + cfg["conf"]).read() + "\n" \
        + cfg.get("extra_cfg", "")
    if all_held:
        conf = conf.replace("nexpert_held = 16", "nexpert_held = 64")
    dev = "cpu" if chips == 1 else "cpu:0-%d" % (chips - 1)
    n = batch * chips
    b = DataBatch()
    b.batch_size = n
    if seq is None:
        shape = cfg["input_shape"]
        conf += "\ninput_shape = %s\nbatch_size = %d\n" % (
            ",".join(map(str, shape)), n)
        if tpu_like:
            conf += "channels_last = 1\n"
        b.data = np.zeros([n] + shape, np.float32)
        b.label = np.zeros((n, 1), np.float32)
    else:
        conf += ("\ninput_shape = 1,1,%d\nbatch_size = %d\n"
                 "label_vec[0,%d) = label\n" % (seq, n, seq))
        b.data = np.zeros((n, 1, 1, seq), np.float32)
        b.label = np.zeros((n, seq), np.float32)
    tr = Trainer()
    for k, v in parse_config_string(conf + "dev = %s\nseed = 7\n" % dev):
        tr.set_param(k, v)
    tr.init_model()
    return tr, b


def health_step(tr, b):
    step = tr._get_step(True, False, False, False, True)
    return step.lower(tr.params, tr.opt_state, None, None,
                      tr._shard_batch(b.data), tr._shard_batch(b.label),
                      jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0))


def stripped(lowered):
    txt = re.sub(r"\s*loc\([^\n]*\)\s*$", "", lowered.as_text(), flags=re.M)
    return re.sub(r"^#loc.*$", "", txt, flags=re.M)


def main():
    out = sys.argv[1]
    tpu_like = "--tpu-like" in sys.argv[2:]
    if tpu_like:
        ops.set_use_pallas(True)
    os.makedirs(out, exist_ok=True)
    batch = 128 if tpu_like else 8
    for name, chips, rows, seq, all_held in (
            ("alexnet-b2048", 1, batch, None, False),
            ("alexnet-b2048", 4, batch, None, False),
            ("googlenet-b512", 1, batch, None, False),
            ("smallthinker-21b-ep4-l4", 1, 1, 512, False),
            ("smallthinker-21b-ep4-l4", 1, 1, 512, True)):
        tr, b = build(name, chips, rows, seq, tpu_like, all_held)
        for kind, low in (("lower_update", tr.lower_update(b)),
                          ("health_step", health_step(tr, b))):
            txt = stripped(low)
            tag = "%s.chips%d.%s%s" % (name, chips, kind,
                                       ".all_held" if all_held else "")
            with open(os.path.join(out, tag + ".mlir"), "w") as f:
                f.write(txt)
            print(tag, len(txt.splitlines()), "lines",
                  hashlib.sha256(txt.encode()).hexdigest()[:16], flush=True)


if __name__ == "__main__":
    main()
