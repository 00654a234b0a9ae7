"""Everything a run of a block-diffusion language-model cell makes from
``--seed``: the weights, the tokens and their noise. ``lm_inputs``'
counterpart for a model trained by diffusion over blocks (a sequence of L
tokens runs as 2 L rows, a noised copy before the clean one); the program
under test (``programs/cxxnet_bdlm_trainer.py``) and the plain reference
(``references/sdar_moe.py``) are both fed from here, so that the same seed
gives both the same weights, tokens, masks and loss weights. All of it is
made on the device.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import lm_inputs
from .inputs import seed_key

# the leaves an attention layer with ``qk_norm = 1`` keeps beside its two
# matrices: one gain vector over the head's features each, shared by the
# heads. Their axis is the head's, not the model's.
HEAD_NORMS = ("qnorm", "knorm")


def weight_shapes(layers) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {tag: shape}: ``lm_inputs.weight_shapes`` and, for an
    attention layer with ``qk_norm``, the two gains of ``head_dim``."""
    out = lm_inputs.weight_shapes(layers)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    for lay in layers:
        if lay.type == "attention" and lay.geti("qk_norm"):
            dh = lay.geti("head_dim") or d // lay.geti("nhead")
            out[lay.name].update({tag: (dh,) for tag in HEAD_NORMS})
    return out


def leaves_of(layers) -> List[tuple]:
    """(index, layer name, tag, shape) of every weight, in order."""
    out = []
    for name, tags in weight_shapes(layers).items():
        for tag, shape in tags.items():
            out.append((len(out), name, tag, shape))
    return out


def params_from_seed(layers, glob: Dict[str, str], cfg: dict):
    """``key -> params``, by ``lm_inputs.make_params``' rule: the model of
    the configuration's ``weights_base_seed`` (seed 0 where it states
    none), its hidden units reordered by one permutation the run's seed
    draws. A leaf with no model axis (the heads' norms) is not reordered."""
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
            leaf = lm_inputs.make_leaf(base_key, i, shape, sigmas[name])
            if tag not in HEAD_NORMS:
                leaf = jnp.take(leaf, order, axis=axes[name][tag])
            params.setdefault(name, {})[tag] = leaf
        return params
    return make


def noise(tokens, key, block_len: int, t_min: float, t_max: float,
          mask_id: int):
    """``tokens`` (rows, L) -> the noised copy and the loss weights, both
    (rows, L) float32: each block of ``block_len`` positions draws t
    uniformly from [t_min, t_max], each of its positions becomes
    ``mask_id`` with probability t, and a masked position weighs 1 / t
    (an unmasked one nothing)."""
    rows, L = tokens.shape
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (rows, L // block_len), jnp.float32,
                           t_min, t_max)
    t = jnp.repeat(t, block_len, axis=1)
    masked = jax.random.uniform(k_m, (rows, L), jnp.float32) < t
    x0 = tokens.astype(jnp.float32)
    return (jnp.where(masked, jnp.float32(mask_id), x0),
            jnp.where(masked, 1.0 / t, 0.0))


def make_batch(key, batch_id: int, rows: int, seq_len: int, vocab: int,
               cfg: dict):
    """Resident batch ``batch_id``: ``rows`` sequences of ``seq_len`` ids
    x_0 drawn by Zipf's law with exponent 1 over the ids held but the last,
    which is the mask's (``lm_inputs.make_tokens``' draw, one id fewer),
    noised by the configuration's ``block_len``, ``t_min`` and ``t_max``.
    ``data`` (rows, 1, 1, 2 seq_len): the noised copy, then the clean one;
    ``label`` (rows, 2 seq_len): x_0, then the loss weights. Float32, the
    program's convention for ids and labels."""
    mask_id = vocab - 1
    cdf = jnp.cumsum(1.0 / jnp.arange(1, mask_id + 1, dtype=jnp.float32))
    u = jax.random.uniform(jax.random.fold_in(key, 1000 + batch_id),
                           (rows, seq_len), jnp.float32)
    x0 = jnp.minimum(jnp.searchsorted(cdf, u * cdf[-1]), mask_id - 1)
    xt, weight = noise(x0, jax.random.fold_in(key, 2000 + batch_id),
                       cfg["block_len"], cfg["t_min"], cfg["t_max"], mask_id)
    x0 = x0.astype(jnp.float32)
    return (jnp.concatenate([xt, x0], axis=1).reshape(rows, 1, 1,
                                                      2 * seq_len),
            jnp.concatenate([x0, weight], axis=1))
