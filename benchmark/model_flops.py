"""Model FLOPs per trained item, from the conf text and the input shape.

Counts what the model needs and nothing of how the program computes it: the
multiply-adds of every ``conv`` and ``fullc`` layer, forward once and
backward twice (towards the input and towards the weights), two operations
each. Pooling, LRN, activations, softmax and the update are left out, as is
any recomputation, so a share of the peak taken from this count cannot pass
100% on a sound run. The first layer's backward towards the input is counted
although no program needs it: the usual 3x convention, stated here.
"""

from __future__ import annotations

from typing import List, Tuple

from . import netconf


def forward_macs(layers, input_shape) -> List[Tuple[str, int]]:
    """(layer name, multiply-adds of one item's forward pass)."""
    shapes = netconf.infer_shapes(layers, input_shape)
    out = []
    for lay in netconf.weighted(layers):
        c, h, w = shapes[lay.ins[0]]
        co, oh, ow = shapes[lay.outs[0]]
        if lay.type == "conv":
            k = lay.geti("kernel_size")
            macs = oh * ow * co * (c // lay.geti("ngroup", 1)) * k * k
        else:
            macs = w * ow
        out.append((lay.name, macs))
    return out


def train_flops_per_item(conf_text: str, input_shape) -> float:
    layers, _ = netconf.parse(conf_text)
    return 3.0 * 2.0 * sum(m for _, m in forward_macs(layers, input_shape))
