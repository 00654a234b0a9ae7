"""Reference ``convnet``, as a configuration's ``"reference"`` key names it:
a conv net's training step in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision.

It reads the conf text through ``netconf``, makes its weights and rows from
the seed through ``inputs``, and imports nothing of the program under test.
No layout tracking, no fusion, no kernels, no mesh: NCHW, one block of rows
at a time, gradients summed over the blocks, then momentum SGD with weight
decay on float32 weights as cxxnet's ``sgd`` updater states it:

    m <- momentum * m - lr * (g + wd * w);  w <- w + m

``precision`` other than ``highest`` gives the control: the same mathematics
with every operand of a convolution or matrix product, and every gradient
that comes back into one, rounded to the lower type first (``fp8``: e4m3
operands and e5m2 gradients, each scaled to its tensor's largest value, the
way an fp8 recipe would be written; ``bf16``: plain rounding). ``rows_used``
below the batch gives the planted faults: a mean over part of the batch.

Dropout is part of the timed configuration, and which units a step drops
moves every loss and gradient norm by a few per cent at these batch sizes:
far more than the rounding the comparison is there to see. So the
configuration states its dropout stream (``dropout_stream`` in its file), a
function of the seed, the step and the layer's place in the netconfig alone,
and the reference draws the same masks from the seed itself with
``jax.random``. It takes them from the configuration, not from the program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import inputs, model_flops, netconf

_HIGHEST = lax.Precision.HIGHEST
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0
PRECISIONS = ("highest", "bf16", "fp8")


def _fake_quant(x, dtype, fmax):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _round_operand(x, precision):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return _fake_quant(x, jnp.float8_e4m3fn, _E4M3_MAX)
    return x


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, precision):
    return y


def _rc_fwd(y, precision):
    return y, None


def _rc_bwd(precision, _, g):
    if precision == "bf16":
        return (g.astype(jnp.bfloat16).astype(jnp.float32),)
    if precision == "fp8":
        return (_fake_quant(g, jnp.float8_e5m2, _E5M2_MAX),)
    return (g,)


_round_cotangent.defvjp(_rc_fwd, _rc_bwd)


def _pool(x, mode, k, s, p):
    h, w = x.shape[2], x.shape[3]
    oh, ow = netconf.pool_out(h, k, s, p), netconf.pool_out(w, k, s, p)
    # ceil mode: the last window may hang over the edge
    eh = max((oh - 1) * s + k - (h + 2 * p), 0)
    ew = max((ow - 1) * s + k - (w + 2 * p), 0)
    pad = [(0, 0), (0, 0), (p, p + eh), (p, p + ew)]
    if mode == "max_pooling":
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                                 (1, 1, s, s), pad)
    return lax.reduce_window(x, 0.0, lax.add, (1, 1, k, k),
                             (1, 1, s, s), pad) / float(k * k)


def _lrn(x, n, alpha, beta, knorm):
    lo = n // 2
    sq = lax.reduce_window(x * x, 0.0, lax.add, (1, n, 1, 1), (1, 1, 1, 1),
                           [(0, 0), (lo, n - 1 - lo), (0, 0), (0, 0)])
    return x * jnp.power(knorm + (alpha / n) * sq, -beta)


def loss_sum(layers: List[netconf.Layer], precision: str, params, data,
             label, masks):
    """Sum over the rows of the softmax cross-entropy, training mode.
    ``masks[i]`` says which units dropout layer ``i`` keeps."""
    vals = {"0": data}
    loss = None
    for i, lay in enumerate(layers):
        a = vals[lay.ins[0]]
        t = lay.type
        if t == "conv":
            s, p = lay.geti("stride", 1), lay.geti("pad")
            y = lax.conv_general_dilated(
                _round_operand(a, precision),
                _round_operand(params[lay.name]["wmat"], precision),
                (s, s), [(p, p), (p, p)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=lay.geti("ngroup", 1),
                precision=_HIGHEST)
            y = _round_cotangent(y, precision)
            if "bias" in params[lay.name]:
                y = y + params[lay.name]["bias"][None, :, None, None]
            out = [y]
        elif t == "fullc":
            a2 = a.reshape(a.shape[0], -1)
            y = jnp.dot(_round_operand(a2, precision),
                        _round_operand(params[lay.name]["wmat"], precision).T,
                        precision=_HIGHEST)
            y = _round_cotangent(y, precision)
            if "bias" in params[lay.name]:
                y = y + params[lay.name]["bias"]
            out = [y.reshape(y.shape[0], 1, 1, -1)]
        elif t == "relu":
            out = [jnp.maximum(a, 0.0)]
        elif t in ("max_pooling", "avg_pooling"):
            out = [_pool(a, t, lay.geti("kernel_size"), lay.geti("stride", 1),
                         lay.geti("pad"))]
        elif t == "lrn":
            out = [_lrn(a, lay.geti("local_size", 3), lay.getf("alpha"),
                        lay.getf("beta"), lay.getf("knorm", 1.0))]
        elif t == "flatten":
            out = [a.reshape(a.shape[0], 1, 1, -1)]
        elif t == "dropout":
            keep = 1.0 - lay.getf("threshold")
            out = [jnp.where(masks[i], a / keep, 0.0)]
        elif t == "split":
            out = [a] * len(lay.outs)
        elif t == "ch_concat":
            out = [jnp.concatenate([vals[n] for n in lay.ins], axis=1)]
        elif t == "softmax":
            logp = jax.nn.log_softmax(a.reshape(a.shape[0], -1), axis=-1)
            idx = label[:, 0].astype(jnp.int32)
            loss = -jnp.sum(jnp.take_along_axis(logp, idx[:, None], axis=1))
            out = [a]
        else:
            raise netconf.ConfError("layer type %r" % t)
        for n, v in zip(lay.outs, out):
            vals[n] = v
    if loss is None:
        raise netconf.ConfError("the net has no softmax loss")
    return loss


def _lr_at(p: dict, epoch):
    e = jnp.asarray(epoch, jnp.float32)
    lr = jnp.asarray(p["lr"], jnp.float32)
    if p["schedule"] == "expdecay":
        lr = p["lr"] * jnp.power(p["gamma"], e / p["step"])
    return jnp.maximum(lr, min(p["minimum_lr"], p["lr"]))


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {"%s:%s" % (n, tag): jnp.sqrt(jnp.sum(jnp.square(v)))
            for n, d in tree.items() for tag, v in d.items()}


class Reference:
    """Three steps of training from a seed; ``run`` returns what the
    comparison reads: each step's loss, the norm of every leaf's first
    gradient and of its change over the steps."""

    def __init__(self, conf_text: str, input_shape, n_class: int,
                 batch: int, block: int, precision: str = "highest",
                 rows_used: int = 0, mask_dtype: str = "bfloat16"):
        if precision not in PRECISIONS:
            raise ValueError("precision %r" % precision)
        self.layers, self.glob = netconf.parse(conf_text)
        self.input_shape = tuple(input_shape)
        self.n_class = n_class
        self.batch = batch
        self.block = min(block, batch)
        self.rows_used = rows_used or batch
        if batch % self.block or self.rows_used % self.block:
            raise ValueError("block %d does not divide batch %d / rows %d"
                             % (self.block, batch, self.rows_used))
        by_name = {lay.name: lay for lay in netconf.weighted(self.layers)}
        self.hyper = {
            n: {tag: netconf.updater_params(self.glob, by_name[n], tag)
                for tag in d}
            for n, d in inputs.weight_shapes(self.layers,
                                             self.input_shape).items()}
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        def block_loss(params, data, label, masks, j):
            rows = {i: lax.dynamic_slice_in_dim(m, j * self.block, self.block)
                    for i, m in masks.items()}
            return loss_sum(self.layers, precision, params, data, label, rows)
        self._grad = jax.jit(jax.value_and_grad(block_loss))
        self._init = jax.jit(self._start)
        self._block = jax.jit(
            lambda key, batch_id, j: inputs.make_block(
                key, batch_id, j, self.block, self.input_shape, n_class))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)
        self._update = jax.jit(self._apply, donate_argnums=(0, 1))
        self._norms_of = jax.jit(_norms)
        self._change = jax.jit(lambda new, old: _norms(
            jax.tree.map(jnp.subtract, new, old)))
        shapes = netconf.infer_shapes(self.layers, self.input_shape)
        self._dropouts = [(i, (batch,) + shapes[lay.ins[0]],
                           1.0 - lay.getf("threshold"))
                          for i, lay in enumerate(self.layers)
                          if lay.type == "dropout"]
        self._mask_dtype = jnp.dtype(mask_dtype)
        self._masks = jax.jit(self._draw_masks)

    def _start(self, key):
        params = inputs.make_params(self.layers, self.glob, self.input_shape,
                                    key)
        return (params, jax.tree.map(jnp.copy, params),
                jax.tree.map(jnp.zeros_like, params))

    def _apply(self, params, mom, grads, epoch):
        new_p, new_m = {}, {}
        for n, d in params.items():
            new_p[n], new_m[n] = {}, {}
            for tag, w in d.items():
                h = self.hyper[n][tag]
                g = grads[n][tag] / float(self.rows_used)
                # cxxnet clamps the momentum at its final_momentum, 0.9
                m = mom[n][tag] * min(h["momentum"], 0.9) \
                    - _lr_at(h, epoch) * (g + h["wd"] * w)
                new_p[n][tag], new_m[n][tag] = w + m, m
        return new_p, new_m

    @classmethod
    def for_config(cls, conf_text: str, cfg: dict, batch: int, **kw):
        """The reference of one configuration file at one global batch."""
        return cls(conf_text, cfg["input_shape"], cfg["n_class"], batch,
                   cfg["ref_block"],
                   mask_dtype=cfg["dropout_stream"]["dtype"], **kw)

    def _draw_masks(self, seed31, step):
        """The configuration's dropout stream: layer ``i`` of step ``step``
        (from 0) keeps the units where a uniform draw of the compute type,
        keyed ``fold_in(fold_in(PRNGKey(seed), step + 1), i)`` and shaped
        like the layer's whole batch, lies under ``1 - threshold``."""
        base = jax.random.fold_in(jax.random.PRNGKey(seed31), step + 1)
        return {i: jax.random.uniform(jax.random.fold_in(base, i), shape,
                                      self._mask_dtype) < keep
                for i, shape, keep in self._dropouts}

    def run(self, seed: int, n_steps: int = 3) -> dict:
        key = inputs.seed_key(seed)
        params, start, mom = self._init(key)
        losses, grad_norms = [], None
        for step in range(n_steps):
            total, grads = [], None
            masks = self._masks(int(seed) & 0x7FFFFFFF, step)
            for j in range(self.rows_used // self.block):
                data, label = self._block(key, step % 2, j)
                val, g = self._grad(params, data, label, masks, j)
                total.append(val)
                grads = g if grads is None else self._add(grads, g)
            losses.append(sum(float(v) for v in total) / self.rows_used)
            if step == 0:
                grad_norms = {n: float(v) / self.rows_used for n, v in
                              jax.device_get(self._norms_of(grads)).items()}
            params, mom = self._update(params, mom, grads, step)
        change = jax.device_get(self._change(params, start))
        return {"loss": losses, "grad_norm": grad_norms,
                "change_norm": {n: float(v) for n, v in change.items()}}


# what a window kind asks of a reference's file
for_config = Reference.for_config


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained item of this configuration."""
    return model_flops.train_flops_per_item(conf_text, cfg["input_shape"])
