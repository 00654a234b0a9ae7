"""Program ``cxxnet_lm_trainer``: the trainer under test on a language
model, as a configuration's ``"program"`` key names it.

Like ``cxxnet_trainer`` it touches the program only through what
``bin/cxxnet`` itself uses for ``task = train`` (``Trainer()``,
``set_param`` per conf pair, ``init_model``, ``set_weight``, ``update``) and
four attributes read, never written: ``last_health`` (the step's own loss
and, behind the four health values, what ``health_gauge_names`` names),
``opt_state`` (AdamW's first moment after the first step) and ``params``.

The configuration's ``batch_per_chip`` counts TOKENS a step (the window's
items) and ``seq_len`` the length of a row, so the trainer's batch is
``batch_per_chip / seq_len`` sequences. Weights and token batches are made
from the seed by ``lm_inputs``; the start weights are not kept beside the
trainer's own three copies but made again from the seed where the change is
read.
"""

from __future__ import annotations

from typing import Callable, Dict


class Program:
    """The trainer, its two resident batches of token ids and the calls a
    window makes. One chip: the cell is one chip's share of its deployment,
    and the exchange between chips comes with the cell that needs it."""

    def __init__(self, conf_text: str, cfg: dict, chips: int, seed: int,
                 traffic: dict, mark: Callable[[str], None] = lambda n: None):
        import jax
        import jax.numpy as jnp
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        from benchmark import inputs, lm_inputs, netconf

        if chips != 1 or traffic.get("batch_sharding"):
            raise ValueError("program cxxnet_lm_trainer runs one chip's "
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
        self.leaves = lm_inputs.leaves_of(layers)
        self.key = inputs.seed_key(seed)
        # the weights the run starts from: made here from the seed, not
        # taken from the program, so that the reference can make the same
        make_w = lm_inputs.params_from_seed(layers, glob, cfg)
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

    def step(self) -> None:
        """The window's one call: the next batch through ``update``."""
        self.trainer.update(self.batches[self.steps_done % 2])
        self.steps_done += 1

    def sync(self) -> float:
        """Wait for the last step by fetching its loss."""
        return float(self.trainer.last_health[0])

    def gauges(self) -> Dict[str, float]:
        """What the last step's layers counted: the values the step returns
        behind its four health values, by the trainer's names for them
        (``moe.pairs_held/<layer>``, ``moe.load_max/<layer>``). Call it
        after a ``sync``: it fetches the vector the sync waited for."""
        import jax
        names = getattr(self.trainer, "health_gauge_names", None) or []
        values = jax.device_get(self.trainer.last_health)[4:]
        return {n: float(v) for n, v in zip(names, values)}

    def _leaves(self, tree_of) -> dict:
        idx = self.trainer.net.cfg.get_layer_index
        key_of = {name: dict(self.trainer.net.layers[idx(name)].visit_order())
                  for _, name, _, _ in self.leaves}
        out = {}
        for _, name, tag, _ in self.leaves:
            out.setdefault(name, {})[tag] = tree_of(idx(name),
                                                    key_of[name][tag])
        return out

    def first_gradient_norms(self, hyper: dict) -> Dict[str, float]:
        """After exactly one step: the gradient as the optimizer got it,
        worked out of AdamW's first moment ``m1 = (1 - beta1) g``."""
        import jax
        if self.steps_done != 1:
            raise RuntimeError("read the first gradient after one step")
        opt = self.trainer.opt_state
        m1 = jax.device_get(self._norms(
            self._leaves(lambda i, key: opt[i][key]["m1"])))
        return {"%s:%s" % (n, tag): float(v) / (1.0 - hyper[n][tag]["beta1"])
                for n, d in m1.items() for tag, v in d.items()}

    def change_norms(self) -> Dict[str, float]:
        """The norm of each leaf's change since the start."""
        import jax
        now = self._leaves(lambda i, key: self.trainer.params[i][key])
        return _flat(jax.device_get(self._change_norms(now, self.key)))

    def release(self) -> None:
        """Free the program's state and the batches before the reference:
        by name, because a ``Trainer`` sits in reference cycles (its jitted
        step closes over it) and 11 GB waiting for the collector is the
        reference's out-of-memory."""
        import gc
        import jax
        tr = self.trainer
        held = (tr.params, tr.opt_state, tr.last_health,
                [(b.data, b.label) for b in self.batches])
        self.trainer = self.batches = None
        for leaf in jax.tree.leaves(held):
            if isinstance(leaf, jax.Array):
                leaf.delete()
        gc.collect()


def _flat(tree) -> Dict[str, float]:
    return {"%s:%s" % (n, tag): float(v)
            for n, d in tree.items() for tag, v in d.items()}
