"""Everything a run of a language-model cell with short-conv mixers, a
dense leading layer and sigmoid routing makes from ``--seed``: the weights
(a ``shortconv`` layer's three leaves, a dense ``ffn`` layer's three, a
``moe`` layer's expert bias) and the tokens (``lm_inputs.make_tokens``,
unchanged). ``lm_inputs``' counterpart for LFM2-24B-A2B's language model;
the program under test (``programs/cxxnet_lfm2_trainer.py``) and the plain
reference (``references/lfm2_moe.py``) are both fed from here. All of it
is made on the device.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import bd_inputs, lm_inputs
from .inputs import seed_key

# leaves no gradient trains: the router's expert bias chooses experts and
# is held fixed through a run (the program's ``Layer.fixed_keys``)
FIXED = ("expert_bias",)
# the axis of each new leaf that counts the residual stream's units: the
# conv's own channels (w_conv's rows, w_in's columns) are not the model's
AXES = {"shortconv": {"w_in": 0, "w_out": 1},
        "ffn": {"wmat": 0, "up": 0, "down": 1}}


def weight_shapes(layers) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {tag: shape}: ``bd_inputs.weight_shapes`` (the heads'
    norms with ``qk_norm``), ``w_in (d, 3d)``, ``w_conv (d, kernel)``,
    ``w_out (d, d)`` of a ``shortconv`` layer, ``wmat`` / ``up (d, f)``,
    ``down (f, d)`` of an ``ffn`` layer and ``expert_bias (nexpert,)`` of
    a ``moe`` layer with ``expert_bias = 1``; in the layers' order."""
    known = bd_inputs.weight_shapes(layers)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    out = {}
    for lay in layers:
        if lay.type == "shortconv":
            out[lay.name] = {"w_in": (d, 3 * d),
                             "w_conv": (d, lay.geti("kernel", 3)),
                             "w_out": (d, d)}
        elif lay.type == "ffn":
            f = lay.geti("nhidden")
            out[lay.name] = {"wmat": (d, f), "up": (d, f), "down": (f, d)}
        elif lay.name in known:
            out[lay.name] = known[lay.name]
            if lay.type == "moe" and lay.geti("expert_bias"):
                out[lay.name]["expert_bias"] = (lay.geti("nexpert"),)
    return out


def leaves_of(layers) -> List[tuple]:
    """(index, layer name, tag, shape) of every weight, in order."""
    out = []
    for name, tags in weight_shapes(layers).items():
        for tag, shape in tags.items():
            out.append((len(out), name, tag, shape))
    return out


def model_axes(layers) -> Dict[str, Dict[str, int]]:
    """``lm_inputs.model_axes`` and the new layers' (``AXES``)."""
    out = lm_inputs.model_axes(layers)
    out.update({lay.name: AXES[lay.type] for lay in layers
                if lay.type in AXES})
    return out


def params_from_seed(layers, glob: Dict[str, str], cfg: dict):
    """``key -> params``, by ``lm_inputs.make_params``' rule: the model of
    the configuration's ``weights_base_seed`` (seed 0 where it states
    none), its hidden units reordered by one permutation the run's seed
    draws. Every matrix normal(0, sigma), the conv's taps too; a gain
    ones; an expert bias ``mean + sd * normal(0, 1)`` by the
    configuration's ``expert_bias`` (noughts where it states none). A
    leaf with no model axis (the heads' norms, the taps, the bias) is not
    reordered."""
    leaves = leaves_of(layers)
    sigmas = lm_inputs.sigmas_of(layers, glob)
    axes = model_axes(layers)
    base_key = seed_key(cfg.get("weights_base_seed", 0))
    draw = cfg.get("expert_bias", {"mean": 0.0, "sd": 0.0})
    width = next(lay.geti("nhidden") for lay in layers
                 if lay.type == "embed")

    def make(key):
        order = jax.random.permutation(jax.random.fold_in(key, 99), width)
        params = {}
        for i, name, tag, shape in leaves:
            if tag in FIXED:
                leaf = draw["mean"] + draw["sd"] * jax.random.normal(
                    jax.random.fold_in(base_key, 100 + i), shape,
                    jnp.float32)
            else:
                leaf = lm_inputs.make_leaf(base_key, i, shape, sigmas[name])
            axis = axes.get(name, {}).get(tag)
            if axis is not None:
                leaf = jnp.take(leaf, order, axis=axis)
            params.setdefault(name, {})[tag] = leaf
        return params
    return make
