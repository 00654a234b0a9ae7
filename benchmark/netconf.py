"""The benchmark's own reading of a cxxnet conf text.

``parse`` turns the text into the layer list of its ``netconfig`` section
and the global ``key = value`` pairs; ``infer_shapes`` walks that list from
the input shape. ``model_flops`` and ``reference`` both stand on this walk,
and neither imports anything of the program under test.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

_LAYER_KEY = re.compile(r"^layer\[([^\]]*)\]$")
# layer types whose output has its input's shape
_SAME_SHAPE = ("relu", "lrn", "dropout", "softmax")


class ConfError(ValueError):
    pass


@dataclass
class Layer:
    type: str
    name: str
    ins: List[str]
    outs: List[str]
    params: Dict[str, str] = field(default_factory=dict)

    def geti(self, key: str, default: int = 0) -> int:
        return int(self.params.get(key, default))

    def getf(self, key: str, default: float = 0.0) -> float:
        return float(self.params.get(key, default))


def _pairs(text: str) -> List[Tuple[str, str]]:
    out = []
    for raw in text.splitlines():
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        if "=" not in line:
            raise ConfError("expected 'key = value', got %r" % raw)
        key, val = line.split("=", 1)
        out.append((key.strip(), val.strip()))
    return out


def parse(text: str) -> Tuple[List[Layer], Dict[str, str]]:
    """(layers of the netconfig section in order, global pairs)."""
    layers: List[Layer] = []
    glob: Dict[str, str] = {}
    in_net = False
    last_out = "0"
    for key, val in _pairs(text):
        if key == "netconfig":
            in_net = val == "start"
            continue
        m = _LAYER_KEY.match(key)
        if m is None:
            if in_net and layers:
                layers[-1].params[key] = val
            else:
                glob[key] = val
            continue
        if not in_net:
            raise ConfError("layer outside netconfig=start/end: %r" % key)
        ltype, _, lname = val.partition(":")
        spec = m.group(1).strip()
        if spec.startswith("+"):
            # "+0" loops on the last layer's output, "+1" opens a new node
            ins = [last_out]
            outs = [last_out] if spec[1:].split(":")[0] == "0" \
                else ["_n%d" % len(layers)]
        else:
            if "->" not in spec:
                raise ConfError("bad layer spec %r" % key)
            a, b = spec.split("->", 1)
            ins = [s.strip() for s in a.split(",")]
            outs = [s.strip() for s in b.split(",")]
        layers.append(Layer(ltype.strip(), lname.strip() or
                            "%s%d" % (ltype.strip(), len(layers)), ins, outs))
        last_out = outs[-1]
    if not layers:
        raise ConfError("no netconfig section")
    return layers, glob


def conv_out(x: int, k: int, s: int, p: int) -> int:
    return (x + 2 * p - k) // s + 1


def pool_out(x: int, k: int, s: int, p: int) -> int:
    """cxxnet's pooling output: ceil mode, the last window starting inside."""
    x = x + 2 * p
    return min(x - k + s - 1, x - 1) // s + 1


def infer_shapes(layers: List[Layer], input_shape) -> Dict[str, tuple]:
    """node name -> (c, h, w) of one item; a flat node is (1, 1, n)."""
    shapes: Dict[str, tuple] = {"0": tuple(int(v) for v in input_shape)}
    for lay in layers:
        ins = [shapes[n] for n in lay.ins]
        c, h, w = ins[0]
        t = lay.type
        if t == "conv":
            k, s, p = lay.geti("kernel_size"), lay.geti("stride", 1), \
                lay.geti("pad")
            g = lay.geti("ngroup", 1)
            co = lay.geti("nchannel")
            if k <= 0 or co <= 0 or c % g or co % g:
                raise ConfError("conv %s: bad sizes" % lay.name)
            out = [(co, conv_out(h, k, s, p), conv_out(w, k, s, p))]
        elif t in ("max_pooling", "avg_pooling"):
            k, s, p = lay.geti("kernel_size"), lay.geti("stride", 1), \
                lay.geti("pad")
            out = [(c, pool_out(h, k, s, p), pool_out(w, k, s, p))]
        elif t == "flatten":
            out = [(1, 1, c * h * w)]
        elif t == "fullc":
            if c != 1 or h != 1:
                raise ConfError("fullc %s: input is not flat" % lay.name)
            out = [(1, 1, lay.geti("nhidden"))]
        elif t == "split":
            out = [ins[0]] * len(lay.outs)
        elif t == "ch_concat":
            if any(s[1:] != ins[0][1:] for s in ins):
                raise ConfError("ch_concat %s: maps differ" % lay.name)
            out = [(sum(s[0] for s in ins), h, w)]
        elif t in _SAME_SHAPE:
            out = [ins[0]]
        else:
            raise ConfError("layer type %r is not known to the benchmark's "
                            "walk; add it to benchmark/netconf.py's "
                            "successor" % t)
        for n, s in zip(lay.outs, out):
            shapes[n] = s
    return shapes


def weighted(layers: List[Layer]) -> List[Layer]:
    return [lay for lay in layers if lay.type in ("conv", "fullc")]


def updater_params(glob: Dict[str, str], lay: Layer, tag: str) -> dict:
    """lr, wd, momentum and the lr schedule of one weight, as the conf's
    global and tag-scoped (``wmat:lr``) keys set them; later keys win and a
    layer's own keys come after the globals."""
    p = {"lr": 0.01, "wd": 0.0, "momentum": 0.9, "schedule": "constant",
         "gamma": 0.5, "step": 1, "minimum_lr": 1e-5}
    for src in (glob, lay.params):
        for key, val in src.items():
            if key.startswith(tag + ":"):
                key = key[len(tag) + 1:]
            elif key.split(":", 1)[0] in ("wmat", "bias"):
                continue
            if key in ("lr", "eta"):
                p["lr"] = float(val)
            elif key in ("wd", "momentum"):
                p[key] = float(val)
            elif key in ("lr:schedule", "eta:schedule"):
                p["schedule"] = val
            elif key in ("lr:gamma", "lr:minimum_lr"):
                p[key[3:]] = float(val)
            elif key == "lr:step":
                p["step"] = int(val)
    if p["schedule"] not in ("constant", "expdecay"):
        raise ConfError("lr schedule %r is not in the reference yet"
                        % p["schedule"])
    return p
