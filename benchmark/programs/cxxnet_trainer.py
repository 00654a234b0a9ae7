"""Program ``cxxnet_trainer``: the trainer under test, as a configuration's
``"program"`` key names it.

This file is the one place that touches the program, and only through what
``bin/cxxnet`` itself uses for ``task = train``: ``Trainer()``, ``set_param``
per conf pair, ``init_model``, ``set_weight``, ``update`` -- plus three
attributes read, never written: ``last_health`` (the step's own loss, there
because the configuration sets ``health_monitor = 1``), ``opt_state``
(momentum after the first step) and ``params``.
"""

from __future__ import annotations

from typing import Callable, Dict


class Program:
    """The trainer, its two resident batches and the calls a window makes.
    Set-up builds one and the window gets that same object. ``traffic``
    says how the batches are laid: on the one chip, or (``batch_sharding``)
    split over the named axis of the trainer's mesh."""

    def __init__(self, conf_text: str, cfg: dict, chips: int, seed: int,
                 traffic: dict, mark: Callable[[str], None] = lambda n: None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        from benchmark import inputs, netconf

        self.chips = chips
        self.batch = cfg["batch_per_chip"] * chips
        platform = jax.devices()[0].platform
        dev = platform if chips == 1 else "%s:0-%d" % (platform, chips - 1)
        conf = conf_text + "\n" + cfg.get("extra_cfg", "") + (
            "\ninput_shape = %s\nbatch_size = %d\ndev = %s\nseed = %d\n"
            % (",".join(str(v) for v in cfg["input_shape"]), self.batch, dev,
               seed & 0x7FFFFFFF))
        self.trainer = Trainer()
        for key, val in parse_config_string(conf):
            self.trainer.set_param(key, val)
        self.trainer.init_model()
        mark("build.trainer_s")

        mesh, axis = self.trainer.mesh, traffic.get("batch_sharding")
        if (mesh is None) != (axis is None):
            raise ValueError(
                "the mix lays its batches over mesh axis %r, and the trainer "
                "on %d chip(s) has %s" % (axis, chips, "no mesh" if mesh is
                                          None else "a mesh"))
        rows = cfg["ref_block"]
        self.layers, glob = netconf.parse(conf_text)
        key = inputs.seed_key(seed)
        make_w = lambda k: inputs.make_params(          # noqa: E731
            self.layers, glob, cfg["input_shape"], k)
        make_b = lambda k, i: inputs.make_batch(        # noqa: E731
            k, i, self.batch // rows, rows, cfg["input_shape"],
            cfg["n_class"])
        if mesh is None:
            make_w, make_b = jax.jit(make_w), jax.jit(make_b)
        else:
            make_w = jax.jit(make_w, out_shardings=NamedSharding(mesh, P()))
            make_b = jax.jit(make_b,
                             out_shardings=NamedSharding(mesh, P(axis)))
        # the weights the run starts from: made here from the seed, not
        # taken from the program, so that the reference can make the same
        self.start = jax.block_until_ready(make_w(key))
        mark("build.weights_s")
        for name, leaves in self.start.items():
            for tag, val in leaves.items():
                self.trainer.set_weight(val, name, tag)
        mark("build.set_weight_s")
        self.batches = []
        for i in range(2):
            b = DataBatch()
            b.data, b.label = make_b(key, i)
            b.batch_size = self.batch
            self.batches.append(b)
        jax.block_until_ready(self.batches[1].data)
        mark("build.batches_s")
        self.steps_done = 0

        def norms(tree):
            return jax.tree.map(
                lambda v: jnp.sqrt(jnp.sum(jnp.square(v))), tree)

        def grad_norms(mom, start, lr, wd):
            # m1 = -lr * (g + wd * w0), so g = -m1 / lr - wd * w0
            return norms(jax.tree.map(
                lambda m, w0, a, b: -m.reshape(w0.shape) / a - b * w0,
                mom, start, lr, wd))

        def change_norms(now, start):
            return norms(jax.tree.map(
                lambda w, w0: w.reshape(w0.shape) - w0, now, start))
        # one program each, not an operation a leaf
        self._grad_norms = jax.jit(grad_norms)
        self._change_norms = jax.jit(change_norms)

    def step(self) -> None:
        """The window's one call: the next batch through ``update``."""
        self.trainer.update(self.batches[self.steps_done % 2])
        self.steps_done += 1

    def sync(self) -> float:
        """Wait for the last step by fetching its loss."""
        return float(self.trainer.last_health[0])

    def _leaves(self, tree_of) -> dict:
        idx = self.trainer.net.cfg.get_layer_index
        return {name: {tag: tree_of(idx(name), tag) for tag in leaves}
                for name, leaves in self.start.items()}

    def first_gradient_norms(self, hyper: dict) -> Dict[str, float]:
        """After exactly one step: the gradient as the optimizer got it,
        worked out of its momentum ``m1 = -lr * (g + wd * w0)``."""
        import jax
        if self.steps_done != 1:
            raise RuntimeError("read the first gradient after one step")
        opt = self.trainer.opt_state
        mom = self._leaves(lambda i, tag: opt[i][tag]["m"])
        lr, wd = ({n: {tag: float(hyper[n][tag][k]) for tag in leaves}
                   for n, leaves in self.start.items()} for k in ("lr", "wd"))
        return _flat(jax.device_get(
            self._grad_norms(mom, self.start, lr, wd)))

    def change_norms(self) -> Dict[str, float]:
        """The norm of each leaf's change since the start."""
        import jax
        now = self._leaves(lambda i, tag: self.trainer.params[i][tag])
        return _flat(jax.device_get(self._change_norms(now, self.start)))

    def release(self) -> None:
        """Free the program's state and the batches before the reference."""
        self.trainer = None
        self.batches = None
        self.start = None


def _flat(tree) -> Dict[str, float]:
    return {"%s:%s" % (n, tag): float(v)
            for n, d in tree.items() for tag, v in d.items()}
