"""Reference ``laguna_moe``, as a configuration's ``"reference"`` key names
it: one next-token training step of Laguna-XS.2's language model (full and
sliding-window grouped-query attention with a head count each, a sigmoid
gate on every head's output, YaRN over half of each head on the full
layers, a dense leading layer, sigmoid routing scaled by 2.5 beside a
shared expert), in straightforward ``jax.numpy``, float32, matrix products
at ``highest`` precision. It reads the conf text through ``netconf.parse``,
makes its weights and tokens from the seed (``laguna_inputs``,
``lm_inputs``), and imports nothing of the program under test.

For one sequence of L rows x (L, d), each block ``out = h1 + mlp(norm(h1))``
with ``h1 = x + attention(norm(x))``, ``norm(x) = x rsqrt(mean(x^2) + eps) g``:

    attention  q, k, v = n W_q, n W_k, n W_v     nhead / nkvhead heads of dh
               q, k = rot(q), rot(k)   the first R features of a head turned
                      in (i, i + R/2) pairs by cos / sin of t w_i, the
                      other dh - R passed through (R = rope_dim, else dh)
               a = softmax(q k^T / sqrt(dh), causal; keys t - c < W where
                   the layer has a window W) v
               out = W_o [sigmoid(n W_gate)_head * a]
    dense ffn  W_d(silu(h W_g) * h W_u)
    moe        s = sigmoid(h W_r)  (all nexpert outputs), S = top_k of s
               w_e = routed_scale * s_e / (sum_{S} s + 1e-6), e in S
               y = sum_{e in S, e held} w_e E_e(h) + E_shared(h)
               E(h) = W_d(silu(h W_g) * h W_u)

then a last rmsnorm, the untied head, and the mean next-token
cross-entropy. The frequencies w_i are base^(-2i/R), or under ``rope_type
= yarn`` YaRN's (``yarn_tables``, written out here in numpy from the
paper's equations as transformers' ``_compute_yarn_parameters`` applies
them), and then cos and sin both carry its attention factor, so only the
turned part of q.k is scaled. No kernels, no sort but ``lax.top_k``'s, no
grouped product: the masked (L, L) scores a block of queries at a time,
every expert held applied to every row (a block of rows at a time), the
routing weights masking the sum. What experts that are not held would add
is left out, as in the program. Then AdamW by hand on float32 weights
(``sdar_moe``'s lines).

Departures from the published model, each the configuration's
``assumed``: the gate is one scalar a head (the card writes ``gating:
true``; its family's later config writes ``per-head``); the routing's
scores are sigmoids, normalized over the k chosen and then scaled (no key
names the scoring; a ``routed_scaling_factor`` of 2.5 is DeepSeek-V3's
convention); the shared expert has no gate of its own and there is no
QK-norm (no key names either); the model is one chip's share of 8
(``deployment``).

``precision`` other than ``highest`` gives the control (every operand of a
matrix product, and every gradient that comes back into one, rounded
first, by ``convnet``'s rules). ``rows_used`` below the batch leaves the
positions past it out of the cross-entropy. The faults this model can have
of its own: ``unwindowed`` (the window layers see every earlier key),
``yarn = False`` (plain frequencies at the layer's theta over the turned
features, no attention factor), ``gate = False`` (the heads' gate left
out), ``routed_scale`` (a scale other than the layer's, 1 for the fault).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import laguna_inputs, lm_flops, lm_inputs, netconf
from benchmark.inputs import seed_key
from benchmark.references import keye_dsa
from benchmark.references.convnet import PRECISIONS
from benchmark.references.lfm2_moe import _mm, _swiglu_rows, ffn
from benchmark.references.sdar_moe import _pieces, _rmsnorm

_QUERY_BLOCK = 512          # queries a pass of the scores takes
_ROW_BLOCK = 4096           # rows a pass of the experts takes

_SOUND = {"unwindowed": False, "yarn": True, "gate": True,
          "routed_scale": None}


def yarn_tables(base: float, rot: int, factor: float, original: float,
                beta_fast: float, beta_slow: float, attention_factor: float):
    """YaRN's (rot / 2,) frequencies and its attention factor, numpy
    float64. A pair i turns base^(-2i/rot) radians a position; over the
    ``original`` positions the model was fitted to that is r(i) turns.
    Pairs that turn more than ``beta_fast`` times keep their frequency,
    those that turn fewer than ``beta_slow`` times take it over
    ``factor``, and between the two the share kept falls linearly in i:
    the pair where r = beta is ``rot ln(original / (2 pi beta)) / (2 ln
    base)``, the fast end rounded down and the slow end up."""
    i = np.arange(rot // 2, dtype=np.float64)
    w = np.power(float(base), -2.0 * i / rot)

    def pair_at(turns):
        return rot * math.log(original / (2.0 * math.pi * turns)) \
            / (2.0 * math.log(base))
    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), rot - 1)
    if hi == lo:
        hi += 0.001
    interp = np.minimum(np.maximum((i - lo) / (hi - lo), 0.0), 1.0)
    scale = attention_factor or 0.1 * math.log(factor) + 1.0
    return (1.0 - interp) * w + interp * (w / factor), scale


def rope_of(lay, dh: int, yarn: bool = True):
    """(features turned, (R/2,) float32 frequencies, scale of cos / sin) of
    an attention layer's rotation."""
    rot = lay.geti("rope_dim") or dh
    base = lay.getf("rope_base", 10000.0)
    if lay.params.get("rope_type", "default") == "yarn" and yarn:
        w, scale = yarn_tables(
            base, rot, lay.getf("yarn_factor"), lay.getf("yarn_original_len"),
            lay.getf("yarn_beta_fast", 32.0), lay.getf("yarn_beta_slow", 1.0),
            lay.getf("yarn_attention_factor"))
        return rot, jnp.asarray(w, jnp.float32), scale
    half = rot // 2
    return rot, jnp.power(base, -jnp.arange(half, dtype=jnp.float32)
                          / half), 1.0


def rotate(x, rot: int, freq, scale: float):
    """(heads, T, dh): the first ``rot`` features turned in (i, i + rot/2)
    pairs at positions 0..T-1, the others passed through."""
    half = rot // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], -1)


def _attention(lay, precision, w, h, faults):
    """One sequence's rows (T, d) through one attention layer."""
    T, d = h.shape
    nh = lay.geti("nhead")
    dh = lay.geti("head_dim") or d // nh
    nkv = lay.geti("nkvhead") or nh
    if not lay.geti("causal") or lay.geti("qk_norm") \
            or lay.params.get("attn_mask", "causal") != "causal":
        raise netconf.ConfError("%s: the reference knows causal attention "
                                "with no QK-norm alone" % lay.name)
    window = 0 if faults["unwindowed"] else lay.geti("attn_window")
    qkv = _mm(precision, h, w["wmat"])
    split = lambda t, n: t.reshape(T, n, dh).transpose(1, 0, 2)  # noqa: E731
    q = split(qkv[:, :nh * dh], nh)
    k = split(qkv[:, nh * dh:(nh + nkv) * dh], nkv)
    v = split(qkv[:, (nh + nkv) * dh:], nkv)
    if lay.geti("rope"):
        rot, freq, scale = rope_of(lay, dh, faults["yarn"])
        q, k = rotate(q, rot, freq, scale), rotate(k, rot, freq, scale)
    blk = min(T, _QUERY_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))

    def block(args):
        i, qb = args                          # (nkv, group, blk, dh)
        s = _mm(precision, qb, k[:, None].swapaxes(-1, -2)) * dh ** -0.5
        t = i * blk + jnp.arange(blk)[:, None]
        c = jnp.arange(T)[None, :]
        keep = (c <= t) & ((t - c < window) if window else True)
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm(precision, a, v[:, None])
    qb = q.reshape(nkv, nh // nkv, T // blk, blk, dh).transpose(2, 0, 1, 3, 4)
    out = lax.map(jax.checkpoint(block), (jnp.arange(T // blk), qb))
    out = out.transpose(1, 2, 0, 3, 4).reshape(nh, T, dh)
    if lay.geti("head_gate") and faults["gate"]:
        g = jax.nn.sigmoid(_mm(precision, h, w["wgate"]))      # (T, nh)
        out = out * g.T[:, :, None]
    return _mm(precision, out.transpose(1, 0, 2).reshape(T, nh * dh),
               w["wo"])


def route(lay, logits, scale):
    """(T, nexpert) routing weights, nought for an expert not chosen."""
    e, k = lay.geti("nexpert"), lay.geti("top_k")
    k = k if 0 < k < e else e
    if lay.params.get("score") != "sigmoid":
        raise netconf.ConfError("%s: the reference knows sigmoid scores "
                                "alone" % lay.name)
    s = jax.nn.sigmoid(logits)
    idx = lax.top_k(s, k)[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = scale * w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return jnp.sum(jax.nn.one_hot(idx, e, dtype=w.dtype) * w[..., None], 1)


def moe(lay, precision, w, u, scale=None):
    """One sequence's rows (T, d) through the experts held, all of them on
    all rows, and the shared expert, a block of rows at a time; and the
    (row, expert) pairs whose expert is held here."""
    T, d = u.shape
    held, _, f = w["wmat"].shape
    lo = lay.geti("expert_offset")
    if lay.params.get("expert_act") != "swiglu":
        raise netconf.ConfError("%s: the reference knows swiglu experts "
                                "alone" % lay.name)
    scale = lay.getf("routed_scale", 1.0) if scale is None else scale
    wide = lambda m: m.transpose(1, 0, 2).reshape(d, held * f)  # noqa: E731

    def block(ub):
        probs = route(lay, _mm(precision, ub, w["gate"].T), scale)
        mine = probs[:, lo:lo + held]
        y = _swiglu_rows(precision, ub, wide(w["wmat"]), wide(w["up"]),
                         w["down"].reshape(held * f, d),
                         jnp.repeat(mine, f, axis=1))
        if lay.geti("nshared_hidden"):
            y = y + _swiglu_rows(precision, ub, w["shared_wmat"],
                                 w["shared_up"], w["shared_down"])
        return y, jnp.sum(mine > 0)
    blk = min(T, _ROW_BLOCK)
    y, pairs = lax.map(jax.checkpoint(block), u.reshape(T // blk, blk, d))
    return y.reshape(T, d), jnp.sum(pairs)


def apply_layers(layers, precision, params, vals, said, faults):
    """Apply ``layers`` in order to the node values ``vals`` of one
    sequence (name -> array; node "0" holds the L ids), in place; each
    ``moe`` layer's pairs held go to ``said["pairs_held"]``."""
    for lay in layers:
        w = params.get(lay.name)
        a = vals[lay.ins[0]]
        if lay.type == "embed":
            out = w["wmat"][a]
        elif lay.type == "rmsnorm":
            out = _rmsnorm(a, w["gain"], lay.getf("eps", 1e-6))
        elif lay.type == "attention":
            out = _attention(lay, precision, w, a, faults)
        elif lay.type == "ffn":
            out = ffn(lay, precision, w, a)
        elif lay.type == "add":
            out = sum(vals[n] for n in lay.ins)
        elif lay.type == "moe":
            if len(lay.ins) != 1:
                raise netconf.ConfError("%s: the router reads the experts' "
                                        "own input here" % lay.name)
            out, n = moe(lay, precision, w, a, faults["routed_scale"])
            said.setdefault("pairs_held", {})[lay.name] = n
        elif lay.type == "conv":
            out = _mm(precision, a, w["wmat"].T)
        elif lay.type == "softmax":
            out = a
        else:
            raise netconf.ConfError("layer type %r" % lay.type)
        vals[lay.outs[0]] = out
    return vals


def forward(layers, precision, params, ids, faults=None):
    """One sequence's L ids -> logits (L, vocab) and what the layers
    counted. Each block runs under ``jax.checkpoint``: what stays alive
    across a cut is the residual stream."""
    faults = dict(_SOUND, **(faults or {}))
    vals, said = {"0": ids}, {}
    for piece in _pieces(layers):
        need = {n: vals[n] for lay in piece for n in lay.ins if n in vals}
        last = piece[-1].outs[0]

        def run(p, xs, piece=piece, last=last):
            counted = {}
            out = apply_layers(piece, precision, p, dict(xs), counted,
                               faults)[last]
            return out, counted
        out, counted = jax.checkpoint(run)(params, need)
        vals = {last: out}
        for what, by_layer in counted.items():
            said.setdefault(what, {}).update(by_layer)
    return vals[layers[-1].outs[0]], said


def loss_mean(layers, precision, params, data, label, rows_used,
              faults=None):
    """The batch's loss as the program states it (mean next-token
    cross-entropy over a sequence's positions, positions from
    ``rows_used`` on left out), summed over the sequences, one at a time,
    and divided by their number; and what the layers counted, summed."""
    rows, L = label.shape

    def one(args):
        ids, lab, used = args
        logits, said = forward(layers, precision, params, ids, faults)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, lab[:, None].astype(jnp.int32),
                                  axis=1)[:, 0]
        return jnp.sum(jnp.where(used, ce, 0.0)) / L, said
    ids = data.reshape(rows, L).astype(jnp.int32)
    used = (jnp.arange(rows * L) < rows_used).reshape(rows, L)
    loss, said = lax.map(one, (ids, label, used))
    return jnp.sum(loss) / rows, jax.tree.map(jnp.sum, said)


class Reference(keye_dsa.Reference):
    """``keye_dsa``'s three steps of training from a seed (``sdar_moe``'s
    AdamW by hand) on this model, AdamW's two moments kept on the host
    between updates (``run``). ``run`` returns what the comparison reads,
    each step's loss and the norm of every leaf's first gradient and of its
    change over the steps; beside them, read by no comparison,
    ``pairs_held`` (each ``moe`` layer's pairs held in each step, which
    ``kernel_work`` counts the experts' products by)."""

    def __init__(self, conf_text: str, cfg: dict, batch: int,
                 precision: str = "highest", rows_used: int = 0,
                 unwindowed: bool = False, yarn: bool = True,
                 gate: bool = True, routed_scale=None):
        if precision not in PRECISIONS:
            raise ValueError("precision %r" % precision)
        self.layers, self.glob = netconf.parse(conf_text)
        seq_len = cfg["seq_len"]
        if batch % seq_len:
            raise ValueError("batch of %d tokens is no whole number of "
                             "sequences of %d" % (batch, seq_len))
        self.seq_len, self.rows = seq_len, batch // seq_len
        self.batch = batch
        self.rows_used = rows_used or batch
        self.vocab = lm_inputs.vocab_of(self.layers)
        self.leaves = laguna_inputs.leaves_of(self.layers)
        self._weights = laguna_inputs.params_from_seed(self.layers,
                                                       self.glob, cfg)
        by_name = {lay.name: lay for lay in self.layers}
        adam = {"beta1": float(self.glob.get("beta1", 0.9)),
                "beta2": float(self.glob.get("beta2", 0.999)),
                "eps": float(self.glob.get("adam_eps", 1e-8))}
        if self.glob.get("updater") != "adamw":
            raise netconf.ConfError("the reference trains with adamw")
        self.hyper = {}
        for _, name, tag, _ in self.leaves:
            p = netconf.updater_params(self.glob, by_name[name], tag)
            self.hyper.setdefault(name, {})[tag] = dict(
                adam, lr=p["lr"], wd=p["wd"])
        faults = {"unwindowed": unwindowed, "yarn": yarn, "gate": gate,
                  "routed_scale": routed_scale}

        def loss(params, data, label):
            return loss_mean(self.layers, precision, params, data, label,
                             self.rows_used, faults)
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        self._grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
        self._init = jax.jit(self._weights)
        self._batch = jax.jit(lambda key, batch_id: lm_inputs.make_tokens(
            key, batch_id, self.rows, self.seq_len, self.vocab),
            static_argnums=1)
        self._update = jax.jit(self._apply, donate_argnums=(0, 1, 2))
        self._norms_of = jax.jit(keye_dsa._norms)
        self._change = jax.jit(lambda new, key: keye_dsa._norms(
            jax.tree.map(jnp.subtract, new, self._weights(key))))

    def run(self, seed: int, n_steps: int = 3) -> dict:
        """``keye_dsa.Reference.run``'s loop with AdamW's two moments on the
        host but for the update: the chip holds the float32 weights, the
        step's gradient and the products that make it while the gradient is
        made. At 691.6 M values the weights and both moments are 8.3 GB,
        and beside the gradient's 5.7 GB that does not fit in a chip of
        16.9 GB after the program's run."""
        key = seed_key(seed)
        params = self._init(key)
        moments = [jax.tree.map(lambda p: np.zeros(p.shape, np.float32),
                                params) for _ in range(2)]
        losses, grad_norms, counted = [], None, {}
        with jax.default_matmul_precision("highest"):
            for step in range(n_steps):
                data, label = self._batch(key, step % 2)
                (loss, said), grads = self._grad(params, data, label)
                losses.append(float(loss))
                for what, by_layer in jax.device_get(said).items():
                    for name, n in by_layer.items():
                        counted.setdefault(what, {}).setdefault(
                            name, []).append(float(n))
                if step == 0:
                    grad_norms = {n: float(v) for n, v in jax.device_get(
                        self._norms_of(grads)).items()}
                params, m1, m2 = self._update(
                    params, *jax.device_put(moments), grads, step)
                del grads
                moments = jax.device_get([m1, m2])
                del m1, m2
            change = jax.device_get(self._change(params, key))
        return dict({"loss": losses, "grad_norm": grad_norms,
                     "change_norm": {n: float(v)
                                     for n, v in change.items()}},
                    **counted)


# what a window kind asks of a reference's file
for_config = Reference.for_config


def _model(conf_text: str):
    layers, _ = netconf.parse(conf_text)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    return layers, d


def forward_macs(conf_text: str, seq_len: int):
    """(layer name, part, multiply-adds of ONE SEQUENCE's forward pass):
    L rows through every product: attention's projections (the gate's
    among them) and its core over the keys its mask keeps, the dense FFN's
    three matrices, the router, the experts held (their three matrices at
    even routing: ``top_k * nexpert_held / nexpert`` pairs a row), the
    shared expert's three, the head."""
    layers, d = _model(conf_text)
    out = []
    for lay in layers:
        if lay.type == "attention":
            a = lm_flops._dims(lay, d)
            q, kv = a["nh"] * a["dh"], a["nkv"] * a["dh"]
            out.append((lay.name, "qkv", seq_len * d * (q + 2 * kv)))
            out.append((lay.name, "gate",
                        seq_len * d * a["nh"] * lay.geti("head_gate")))
            out.append((lay.name, "core", 2 * q * seq_len
                        * lm_flops.mean_keys(seq_len, a["window"])))
            out.append((lay.name, "out", seq_len * q * d))
        elif lay.type == "ffn":
            out.append((lay.name, "ffn", seq_len * 3 * d
                        * lay.geti("nhidden")))
        elif lay.type == "moe":
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            out.append((lay.name, "route", seq_len * d * e))
            out.append((lay.name, "experts", seq_len * (k or e) * held / e
                        * 3 * d * lay.geti("nhidden")))
            out.append((lay.name, "shared", seq_len * 3 * d
                        * lay.geti("nshared_hidden")))
        elif lay.type == "conv":
            out.append((lay.name, "head",
                        seq_len * d * lay.geti("nchannel")))
    return out


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained token: forward once, backward twice."""
    seq = cfg["seq_len"]
    return 3.0 * 2.0 * sum(m for _, _, m in forward_macs(conf_text, seq)) \
        / seq


def _cores(layers, d, seq, seqs, windowed_only):
    """Every attention core's work a step (forward x 3): 4 dh FLOPs a kept
    score a head; q, k, v, the output and their gradients moved once."""
    flops = bytes_ = 0.0
    for lay in (lay for lay in layers if lay.type == "attention"):
        a = lm_flops._dims(lay, d)
        if windowed_only and not a["window"]:
            continue
        one = lm_flops.flash_attention(seq, a["nh"], a["nkv"], a["dh"],
                                       a["window"])
        flops += 3.0 * seqs * one["flops"]
        bytes_ += 2.0 * seqs * one["bytes"]
    return {"flops": flops, "bytes": bytes_}


def kernel_work(conf_text: str, cfg: dict, name: str, ctx: dict):
    """FLOPs and bytes one training step of this configuration needs of the
    named kernel, all layers that run it summed: the model's operations and
    the bytes it cannot avoid, the forward pass three times over, nothing
    made again counted. ``flash_attention``: every attention layer's core
    by its mask (causal, or the window's keys). ``window_attention``: the
    cores of the layers with a window alone. ``shared_expert``: every
    ``moe`` layer's shared expert, three products over every row (its
    matrices read once, the rows read and written once a product).
    ``expert_product``: every ``moe`` layer's three products over the pairs
    its experts here hold on the run's two resident batches, as this
    reference counted them in its own first two steps
    (``ctx["want"]["pairs_held"]``, their mean; even routing's where no
    reference has run); ``pairs_a_step`` says how many. Nothing the
    program says of itself is counted. Nothing for a name not known
    here."""
    layers, d = _model(conf_text)
    seq = cfg["seq_len"]
    seqs = cfg["batch_per_chip"] // seq
    if name in ("flash_attention", "window_attention"):
        return _cores(layers, d, seq, seqs, name == "window_attention")
    moes = [lay for lay in layers if lay.type == "moe"]
    if name == "shared_expert":
        works = [lm_flops.expert_product(seqs * seq, d,
                                         lay.geti("nshared_hidden"), 1, 3)
                 for lay in moes if lay.geti("nshared_hidden")]
        return {key: 3.0 * sum(w[key] for w in works)
                for key in ("flops", "bytes")}
    if name == "expert_product":
        counted = (ctx.get("want") or {}).get("pairs_held") or {}
        works, said = [], {"pairs_a_step": 0.0}
        for lay in moes:
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            by_step = counted.get(lay.name, [])[:2]
            pairs = sum(by_step) / len(by_step) if by_step \
                else seqs * seq * (k or e) * held / e
            works.append(lm_flops.expert_product(
                pairs, d, lay.geti("nhidden"), held, 3))
            said["pairs_a_step"] += pairs
        return dict(said, **{key: 3.0 * sum(w[key] for w in works)
                             for key in ("flops", "bytes")})
    return None
