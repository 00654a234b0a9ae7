"""Everything a run makes from ``--seed``: the key, the weights, the batches.

The program under test and the plain reference are both fed from here, so
that the same seed gives both the same weights and the same rows; neither
takes anything the other has made. All of it is made on the device.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import netconf


def seed_key(seed: int):
    """A key for any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def weight_shapes(layers, input_shape) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {"wmat": OIHW or (out, in), "bias": (out,)}."""
    shapes = netconf.infer_shapes(layers, input_shape)
    out = {}
    for lay in netconf.weighted(layers):
        c, _, w = shapes[lay.ins[0]]
        if lay.type == "conv":
            k, co = lay.geti("kernel_size"), lay.geti("nchannel")
            wm = (co, c // lay.geti("ngroup", 1), k, k)
        else:
            co = lay.geti("nhidden")
            wm = (co, w)
        out[lay.name] = {"wmat": wm}
        if not lay.geti("no_bias"):
            out[lay.name]["bias"] = (co,)
    return out


def make_params(layers, glob, input_shape, key):
    """cxxnet's init rule, drawn on the device: ``random_type`` gaussian
    (``init_sigma``) or xavier (uniform within sqrt(3 / (fan_in + fan_out)),
    fans taken per group), biases at ``init_bias``. All weights come out of
    one flat draw of each kind, cut into leaves: one generator call, not one
    a layer, which is what keeps the program small. Call it under one jit."""
    shapes = weight_shapes(layers, input_shape)
    plan, sizes = [], {"xavier": 0, "gaussian": 0}
    for lay in netconf.weighted(layers):
        def get(name, default, lay=lay):
            return lay.params.get(name, glob.get(name, default))
        wm = shapes[lay.name]["wmat"]
        g = lay.geti("ngroup", 1) if lay.type == "conv" else 1
        rtype = get("random_type", "gaussian")
        if rtype == "xavier":
            scale = float(get("init_uniform", -1.0))
            if scale <= 0:
                scale = math.sqrt(3.0 / (math.prod(wm[1:]) + wm[0] // g))
        elif rtype == "gaussian":
            scale = float(get("init_sigma", 0.01))
        else:
            raise netconf.ConfError("random_type %r" % rtype)
        plan.append((lay.name, rtype, scale, sizes[rtype], wm,
                     float(get("init_bias", 0.0))))
        sizes[rtype] += math.prod(wm)
    draw = {}
    if sizes["xavier"]:
        draw["xavier"] = jax.random.uniform(
            jax.random.fold_in(key, 1), (sizes["xavier"],), jnp.float32,
            -1.0, 1.0)
    if sizes["gaussian"]:
        draw["gaussian"] = jax.random.normal(
            jax.random.fold_in(key, 2), (sizes["gaussian"],), jnp.float32)
    params = {}
    for name, rtype, scale, off, wm, bias in plan:
        n = math.prod(wm)
        params[name] = {"wmat": scale * draw[rtype][off:off + n].reshape(wm)}
        if "bias" in shapes[name]:
            params[name]["bias"] = jnp.full(shapes[name]["bias"], bias,
                                            jnp.float32)
    return params


def make_block(key, batch_id: int, block_id, rows: int, input_shape,
               n_class: int):
    """Rows ``[block_id * rows, (block_id + 1) * rows)`` of resident batch
    ``batch_id``: float32 pixels as an iterator would deliver them from
    8-bit images (k / 256, k drawn evenly from 0..255, a quarter of the
    generator's work for float32 draws), and a class each. Every row
    differs."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + batch_id), block_id)
    data = jax.random.bits(k, (rows,) + tuple(input_shape), jnp.uint8) \
        .astype(jnp.float32) * (1.0 / 256.0)
    label = jax.random.randint(jax.random.fold_in(k, 1), (rows, 1), 0,
                               n_class).astype(jnp.float32)
    return data, label


def make_batch(key, batch_id: int, n_blocks: int, rows: int, input_shape,
               n_class: int):
    """A whole resident batch: its blocks, one after another."""
    data, label = jax.vmap(
        lambda j: make_block(key, batch_id, j, rows, input_shape, n_class)
    )(jnp.arange(n_blocks))
    return (data.reshape((n_blocks * rows,) + tuple(input_shape)),
            label.reshape(n_blocks * rows, 1))
