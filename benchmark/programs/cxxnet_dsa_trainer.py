"""Program ``cxxnet_dsa_trainer``: the trainer under test on a language
model with learned sparse attention, as a configuration's ``"program"`` key
names it.

``cxxnet_lm_trainer`` with other weights: the rows and labels are its own
(``lm_inputs.make_tokens``: a row of ``seq_len`` ids, the label the next
token), the leaves count each attention layer's indexer and the heads'
norms and are made from the seed by ``dsa_inputs``. What the window calls
(``step``, ``sync``, ``gauges``, the norms, ``release``) is the other
adapter's, unchanged; ``gauges`` then also carries what the trainer names
``dsa.selected/<layer>`` and ``dsa.index_loss/<layer>``.
"""

from __future__ import annotations

from typing import Callable

from benchmark.programs import cxxnet_lm_trainer


class Program(cxxnet_lm_trainer.Program):
    """The trainer, its two resident batches and the calls a window makes.
    One chip: the cell is one chip's share of its deployment."""

    def __init__(self, conf_text: str, cfg: dict, chips: int, seed: int,
                 traffic: dict, mark: Callable[[str], None] = lambda n: None):
        import jax
        import jax.numpy as jnp
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        from benchmark import dsa_inputs, inputs, lm_inputs, netconf

        if chips != 1 or traffic.get("batch_sharding"):
            raise ValueError("program cxxnet_dsa_trainer runs one chip's "
                             "share; the cell asks for %d chips" % chips)
        seq = cfg["seq_len"]
        self.rows = cfg["batch_per_chip"] // seq
        platform = jax.devices()[0].platform
        conf = conf_text + "\n" + cfg.get("extra_cfg", "") + (
            "\ninput_shape = 1,1,%d\nbatch_size = %d\n"
            "label_vec[0,%d) = label\ndev = %s\nseed = %d\n"
            % (seq, self.rows, seq, platform, seed & 0x7FFFFFFF))
        self.trainer = Trainer()
        for key, val in parse_config_string(conf):
            self.trainer.set_param(key, val)
        self.trainer.init_model()
        mark("build.trainer_s")

        layers, glob = netconf.parse(conf_text)
        self.leaves = dsa_inputs.leaves_of(layers)
        self.key = inputs.seed_key(seed)
        # the weights the run starts from: made here from the seed, not
        # taken from the program, so that the reference can make the same
        make_w = dsa_inputs.params_from_seed(layers, glob, cfg)
        start = jax.block_until_ready(jax.jit(make_w)(self.key))
        mark("build.weights_s")
        for _, name, tag, _ in self.leaves:
            self.trainer.set_weight(start[name].pop(tag), name, tag)
        del start
        mark("build.set_weight_s")
        make_b = jax.jit(lambda k, i: lm_inputs.make_tokens(
            k, i, self.rows, seq, lm_inputs.vocab_of(layers)),
            static_argnums=1)
        self.batches = []
        for i in range(2):
            b = DataBatch()
            b.data, b.label = make_b(self.key, i)
            b.batch_size = self.rows
            self.batches.append(b)
        jax.block_until_ready(self.batches[1].data)
        mark("build.batches_s")
        self.steps_done = 0

        def norms(tree):
            return jax.tree.map(
                lambda v: jnp.sqrt(jnp.sum(jnp.square(v))), tree)

        def change_norms(now, key):
            start = make_w(key)
            return norms(jax.tree.map(
                lambda w, w0: w.reshape(w0.shape) - w0, now, start))
        # one program each, not an operation a leaf
        self._norms = jax.jit(norms)
        self._change_norms = jax.jit(change_norms)
