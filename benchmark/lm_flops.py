"""Operations and bytes of a decoder-only mixture-of-experts language model,
from its conf text: what ``train_flops_per_item`` (the whole step's share of
the peak) and a kernel's share of its roofline both read.

Counts what the model needs and nothing of how the program computes it.
Attention counts the keys its mask lets a query see (a causal triangle, cut
to the window where the layer has one), the experts count the token-expert
pairs routed to experts HELD here, the head counts the vocabulary rows held.
Norms, rotations, softmax, the gates' products and the update are left out,
as is any recomputation, so a share of the peak taken from this count
cannot pass 100% on a sound run. An item is a trained token; training is
the usual three times the forward pass.
"""

from __future__ import annotations

from typing import Dict, List

from . import netconf


def mean_keys(seq_len: int, window: int = 0) -> float:
    """Keys a query sees, averaged over the positions of one sequence:
    position t sees t + 1 of them, and at most ``window``."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2.0
    return (window * (window + 1) / 2.0
            + (seq_len - window) * window) / seq_len


def _dims(lay: netconf.Layer, d: int) -> Dict[str, int]:
    nh = lay.geti("nhead", 1)
    return {"nh": nh, "nkv": lay.geti("nkvhead") or nh,
            "dh": lay.geti("head_dim") or d // nh,
            "window": lay.geti("attn_window")}


def forward_macs(conf_text: str, seq_len: int) -> List[tuple]:
    """(layer name, part, multiply-adds of one token's forward pass). The
    experts count the token-expert pairs that even routing sends to the
    experts held (``top_k * nexpert_held / nexpert`` a token)."""
    layers, _ = netconf.parse(conf_text)
    out, d = [], None
    for lay in layers:
        if lay.type == "embed":
            d = lay.geti("nhidden")
        elif lay.type == "attention":
            a = _dims(lay, d)
            q, kv = a["nh"] * a["dh"], a["nkv"] * a["dh"]
            out.append((lay.name, "qkv", d * (q + 2 * kv)))
            # q k^T and p v, each head's dh per key seen
            out.append((lay.name, "core",
                        2 * q * mean_keys(seq_len, a["window"])))
            out.append((lay.name, "out", q * d))
        elif lay.type == "moe":
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            pairs = (k or e) * held / e
            mats = 3 if lay.params.get("expert_act") == "reglu" else 1
            out.append((lay.name, "route", d * e))
            out.append((lay.name, "experts",
                        pairs * mats * d * lay.geti("nhidden")))
        elif lay.type == "conv":
            out.append((lay.name, "head", d * lay.geti("nchannel")))
    return out


def train_flops_per_item(conf_text: str, seq_len: int) -> float:
    """Model FLOPs of one trained token: forward once, backward twice."""
    return 3.0 * 2.0 * sum(m for _, _, m in forward_macs(conf_text, seq_len))


def expert_product(pairs: float, d: int, width: int, held: int,
                   mats: int = 3, itemsize: int = 2) -> Dict[str, float]:
    """The grouped products of one ``moe`` layer's forward over ``pairs``
    rows: FLOPs, and the bytes it cannot avoid (every matrix held read
    once, each row read and written once a product)."""
    flops = 2.0 * pairs * mats * d * width
    return {"flops": flops,
            "bytes": itemsize * mats * (held * d * width
                                        + pairs * (d + width))}


def flash_attention(seq_len: int, nh: int, nkv: int, dh: int,
                    window: int = 0, itemsize: int = 2) -> Dict[str, float]:
    """One sequence's attention core, forward: FLOPs by the mask, and q, k,
    v read and the output written once."""
    flops = 4.0 * nh * dh * mean_keys(seq_len, window) * seq_len
    return {"flops": flops,
            "bytes": itemsize * seq_len * dh * (2 * nh + 2 * nkv)}
