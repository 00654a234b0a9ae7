"""Everything a run of a language-model cell with learned sparse attention
makes from ``--seed``: the weights, which count an indexer's leaves in each
attention layer, and the tokens (``lm_inputs.make_tokens``, unchanged).
``lm_inputs``' counterpart for a model whose attention layers carry
``attn_mask = dsa``; the program under test (``programs/cxxnet_lm_trainer``
through ``programs/cxxnet_dsa_trainer.py``) and the plain reference
(``references/keye_dsa.py``) are both fed from here. All of it is made on
the device.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import bd_inputs, lm_inputs
from .inputs import seed_key

# an indexer's three projections read the residual stream (their axis 0 is
# the model's); its key's LayerNorm has the head's axis alone
INDEX_MATRICES = ("widx_q", "widx_k", "widx_w")
INDEX_NORM = ("idx_gain", "idx_bias")
# leaves with no model axis: not reordered by the run's seed
NO_MODEL_AXIS = bd_inputs.HEAD_NORMS + INDEX_NORM


def weight_shapes(layers) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {tag: shape}: ``bd_inputs.weight_shapes`` (the heads'
    norms with ``qk_norm``) and, for an attention layer under ``attn_mask =
    dsa``, the indexer's: ``widx_q (d, J di)``, ``widx_k (d, di)``,
    ``widx_w (d, J)``, ``idx_gain`` and ``idx_bias (di,)``."""
    out = bd_inputs.weight_shapes(layers)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    for lay in layers:
        if lay.type == "attention" and lay.params.get("attn_mask") == "dsa":
            J, di = lay.geti("index_heads"), lay.geti("index_dim")
            out[lay.name].update({"widx_q": (d, J * di), "widx_k": (d, di),
                                  "widx_w": (d, J), "idx_gain": (di,),
                                  "idx_bias": (di,)})
    return out


def leaves_of(layers) -> List[tuple]:
    """(index, layer name, tag, shape) of every weight, in order."""
    out = []
    for name, tags in weight_shapes(layers).items():
        for tag, shape in tags.items():
            out.append((len(out), name, tag, shape))
    return out


def make_leaf(key, index: int, tag: str, shape, sigma: float):
    """``lm_inputs.make_leaf`` (a matrix normal(0, sigma), a gain ones),
    and a LayerNorm's bias noughts."""
    if tag == "idx_bias":
        return jnp.zeros(shape, jnp.float32)
    return lm_inputs.make_leaf(key, index, shape, sigma)


def params_from_seed(layers, glob: Dict[str, str], cfg: dict):
    """``key -> params``, by ``lm_inputs.make_params``' rule: the model of
    the configuration's ``weights_base_seed`` (seed 0 where it states
    none), its hidden units reordered by one permutation the run's seed
    draws. A leaf with no model axis (the heads' norms, the indexer's
    LayerNorm) is not reordered."""
    leaves = leaves_of(layers)
    sigmas = lm_inputs.sigmas_of(layers, glob)
    axes = lm_inputs.model_axes(layers)
    base_key = seed_key(cfg.get("weights_base_seed", 0))
    width = next(lay.geti("nhidden") for lay in layers
                 if lay.type == "embed")

    def make(key):
        order = jax.random.permutation(jax.random.fold_in(key, 99), width)
        params = {}
        for i, name, tag, shape in leaves:
            leaf = make_leaf(base_key, i, tag, shape, sigmas[name])
            if tag in INDEX_MATRICES:
                leaf = jnp.take(leaf, order, axis=0)
            elif tag not in NO_MODEL_AXIS:
                leaf = jnp.take(leaf, order, axis=axes[name][tag])
            params.setdefault(name, {})[tag] = leaf
        return params
    return make
