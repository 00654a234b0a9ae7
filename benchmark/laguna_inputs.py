"""Everything a run of a language-model cell with gated attention heads and
shared experts makes from ``--seed``: the weights (an ``attention`` layer's
``wgate`` under ``head_gate = 1``, a ``moe`` layer's shared expert under
``nshared_hidden``, a dense ``ffn`` layer's three) and the tokens
(``lm_inputs.make_tokens``, unchanged). ``lm_inputs``' counterpart for
Laguna-XS.2's language model; the program under test
(``programs/cxxnet_laguna_trainer.py``) and the plain reference
(``references/laguna_moe.py``) are both fed from here. All of it is made
on the device.
"""

from __future__ import annotations

from typing import Dict, List

from . import lfm2_inputs, lm_inputs
from .inputs import seed_key

# the axis of each new leaf that counts the residual stream's units
AXES = {"attention": {"wgate": 0},
        "moe": {"shared_wmat": 0, "shared_up": 0, "shared_down": 1}}


def weight_shapes(layers) -> Dict[str, Dict[str, tuple]]:
    """layer name -> {tag: shape}: ``lfm2_inputs.weight_shapes`` (the
    dense ``ffn`` layer's leaves among them), ``wgate (d, nhead)`` of an
    ``attention`` layer with ``head_gate = 1`` and ``shared_wmat`` /
    ``shared_up (d, f)``, ``shared_down (f, d)`` of a ``moe`` layer with
    ``nshared_hidden = f``; in the layers' order."""
    out = lfm2_inputs.weight_shapes(layers)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    for lay in layers:
        if lay.type == "attention" and lay.geti("head_gate"):
            out[lay.name]["wgate"] = (d, lay.geti("nhead"))
        elif lay.type == "moe" and lay.geti("nshared_hidden"):
            f = lay.geti("nshared_hidden")
            out[lay.name].update(shared_wmat=(d, f), shared_up=(d, f),
                                 shared_down=(f, d))
    return out


def leaves_of(layers) -> List[tuple]:
    """(index, layer name, tag, shape) of every weight, in order."""
    out = []
    for name, tags in weight_shapes(layers).items():
        for tag, shape in tags.items():
            out.append((len(out), name, tag, shape))
    return out


def model_axes(layers) -> Dict[str, Dict[str, int]]:
    """``lfm2_inputs.model_axes`` and the new leaves' (``AXES``)."""
    out = lfm2_inputs.model_axes(layers)
    for lay in layers:
        if lay.type in AXES:
            out[lay.name] = dict(out[lay.name], **AXES[lay.type])
    return out


def params_from_seed(layers, glob: Dict[str, str], cfg: dict):
    """``key -> params`` by ``lm_inputs.make_params``' rule: the model of the
    configuration's ``weights_base_seed`` (seed 0 where it states none),
    its hidden units reordered by one permutation the run's seed draws.
    Every leaf here has a model axis."""
    leaves, sigmas = leaves_of(layers), lm_inputs.sigmas_of(layers, glob)
    base_key = seed_key(cfg.get("weights_base_seed", 0))
    axes = model_axes(layers)
    return lambda key: lm_inputs.make_params(leaves, sigmas, key, base_key,
                                             axes)
