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


def make_params(leaves, sigmas: Dict[str, float], key):
    """layer name -> {tag: array} of all the weights (``sigmas``: what
    ``sigmas_of`` gives). Call it under one jit."""
    params = {}
    for i, name, tag, shape in leaves:
        params.setdefault(name, {})[tag] = make_leaf(key, i, shape,
                                                     sigmas[name])
    return params
