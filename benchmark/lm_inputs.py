"""Everything a run of a language-model cell makes from ``--seed``: the
weights and the token batches. ``inputs.py``'s counterpart for a model that
is no conv net; the program under test (``programs/cxxnet_lm_trainer.py``)
and the plain reference (``references/moe_lm.py``) are both fed from here,
so that the same seed gives both the same weights and the same tokens.
All of it is made on the device.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import netconf
from .inputs import seed_key


def vocab_of(layers) -> int:
    return next(lay.geti("vocab_size") for lay in layers
                if lay.type == "embed")


def sigma_of(glob: Dict[str, str]) -> float:
    """The spread of every matrix: the conf's global ``init_sigma``."""
    if glob.get("random_type", "gaussian") != "gaussian":
        raise netconf.ConfError("random_type %r" % glob["random_type"])
    return float(glob.get("init_sigma", 0.01))


def weight_shapes(layers: List[netconf.Layer]) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {tag: shape}, the shapes the program keeps."""
    out, d = {}, None
    for lay in layers:
        if lay.type == "embed":
            d = lay.geti("nhidden")
            out[lay.name] = {"wmat": (lay.geti("vocab_size"), d)}
        elif lay.type == "rmsnorm":
            out[lay.name] = {"gain": (d,)}
        elif lay.type == "attention":
            nh = lay.geti("nhead")
            dh = lay.geti("head_dim") or d // nh
            nkv = lay.geti("nkvhead") or nh
            out[lay.name] = {"wmat": (d, (nh + 2 * nkv) * dh),
                             "wo": (nh * dh, d)}
        elif lay.type == "moe":
            e, f = lay.geti("nexpert"), lay.geti("nhidden")
            held = lay.geti("nexpert_held") or e
            out[lay.name] = {"wmat": (held, d, f), "gate": (e, d),
                             "up": (held, d, f), "down": (held, f, d)}
        elif lay.type == "conv":
            out[lay.name] = {"wmat": (lay.geti("nchannel"), d)}
    return out


def model_axes(layers: List[netconf.Layer]) -> Dict[str, Dict[str, int]]:
    """layer name -> {tag: the axis of that leaf that counts the model's
    hidden units}, the width of the residual stream: ``weight_shapes``'
    shapes, the axis that is ``d`` there."""
    by_type = {"embed": {"wmat": 1}, "rmsnorm": {"gain": 0},
               "attention": {"wmat": 0, "wo": 1},
               "moe": {"wmat": 1, "gate": 1, "up": 1, "down": 2},
               "conv": {"wmat": 1}}
    return {lay.name: by_type[lay.type] for lay in layers
            if lay.type in by_type}


def make_leaf(key, index: int, shape, sigma: float):
    """Leaf ``index`` (its place in ``weight_shapes``' order) of the weights
    a seed gives: normal(0, sigma), or ones for a norm's gain (one axis)."""
    if len(shape) == 1:
        return jnp.ones(shape, jnp.float32)
    return sigma * jax.random.normal(jax.random.fold_in(key, 100 + index),
                                     shape, jnp.float32)


def leaves_of(layers) -> List[tuple]:
    """(index, layer name, tag, shape) of every weight, in order."""
    out = []
    for name, tags in weight_shapes(layers).items():
        for tag, shape in tags.items():
            out.append((len(out), name, tag, shape))
    return out


def sigmas_of(layers, glob: Dict[str, str]) -> Dict[str, float]:
    """layer name -> the spread of its matrices: the layer's own
    ``init_sigma`` where the conf gives one, else the global key."""
    default = sigma_of(glob)
    return {lay.name: lay.getf("init_sigma", default) for lay in layers}


def make_tokens(key, batch_id: int, rows: int, seq_len: int, vocab: int):
    """Resident batch ``batch_id``: ``rows`` sequences of ``seq_len + 1``
    token ids drawn by Zipf's law with exponent 1 over the ``vocab`` ids
    held (id i with weight 1 / (i + 1), as text's frequent tokens take the
    low ids of a vocabulary); the input is each but the last, the label the
    next token. Float32, the program's convention for ids and labels."""
    cdf = jnp.cumsum(1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32))
    u = jax.random.uniform(jax.random.fold_in(key, 1000 + batch_id),
                           (rows, seq_len + 1), jnp.float32)
    ids = jnp.minimum(jnp.searchsorted(cdf, u * cdf[-1]), vocab - 1)
    ids = ids.astype(jnp.float32)
    return ids[:, :-1].reshape(rows, 1, 1, seq_len), ids[:, 1:]


def make_params(leaves, sigmas: Dict[str, float], key, base_key,
                axes: Dict[str, Dict[str, int]]):
    """layer name -> {tag: array} of all the weights (``sigmas``: what
    ``sigmas_of`` gives; ``axes``: ``model_axes``'). Call it under one jit.

    Every seed gives the SAME model with its hidden units in another
    order: the leaves are drawn from ``base_key`` (the configuration's
    ``weights_base_seed``), and ``key`` (the run's ``--seed``) draws one
    permutation of the model's width that every leaf's model axis is
    reordered by (embedding columns, the norms' gains, the rows of what
    reads the stream, the columns of what writes it). That is an exact
    symmetry of the model: every token meets the same experts under every
    seed, so a step is the same work, and only the order of the sums, and
    with it the rounding, differs."""
    params = {}
    for i, name, tag, shape in leaves:
        params.setdefault(name, {})[tag] = make_leaf(base_key, i, shape,
                                                     sigmas[name])
    width = next(shape[axes[name][tag]] for _, name, tag, shape in leaves)
    order = jax.random.permutation(jax.random.fold_in(key, 99), width)
    for _, name, tag, _ in leaves:
        params[name][tag] = jnp.take(params[name][tag], order,
                                     axis=axes[name][tag])
    return params


def params_from_seed(layers, glob: Dict[str, str], cfg: dict):
    """``key -> params``: what a run of a configuration starts from, for
    the program's adapter and the reference alike. The model is the one of
    the configuration's ``weights_base_seed`` (``make_params`` says what
    the run's seed then does); a ``cfg`` that states none, as the program's
    own tests make them, gets the model of seed 0."""
    leaves, sigmas = leaves_of(layers), sigmas_of(layers, glob)
    base_key = seed_key(cfg.get("weights_base_seed", 0))
    axes = model_axes(layers)
    return lambda key: make_params(leaves, sigmas, key, base_key, axes)
