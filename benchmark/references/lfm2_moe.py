"""Reference ``lfm2_moe``, as a configuration's ``"reference"`` key names it:
one next-token training step of LFM2-24B-A2B's language model (gated
short-conv mixers beside grouped-query attention, a dense leading layer,
sigmoid routing with an expert bias), in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision. It reads the conf text
through ``netconf.parse``, makes its weights and tokens from the seed
(``lfm2_inputs``, ``lm_inputs``), and imports nothing of the program
under test.

For one sequence of L rows x (L, d), each block ``out = h1 + ffn(norm(h1))``
with ``h1 = x + mixer(norm(x))``, ``norm(x) = x rsqrt(mean(x^2) + eps) g``:

    conv mixer       [B | C | X] = h W_in;  u = B * X
                     v_t = sum_{j<K} w[:, j] u_{t-K+1+j}   (zeros before 0)
                     y = (C * v) W_out
    attention mixer  q, k, v = h W_q, h W_k, h W_v   nhead / nkvhead heads
                     q, k = rope(rmsnorm(q; g_q)), rope(rmsnorm(k; g_k))
                     a = causal softmax(q k^T / sqrt(head_dim)) v;  a W_o
    dense ffn        W_d(silu(h W_g) * h W_u)
    moe              s = sigmoid(h W_r)  (all nexpert outputs)
                     S = the top_k experts of s + b   (b the expert bias)
                     w_e = s_e / (sum_{S} s + 1e-6), e in S
                     y = sum_{e in S, e held} w_e W_d,e(silu(h W_g,e) * h W_u,e)

then a last rmsnorm, the untied head, and the mean next-token
cross-entropy. No kernels, no sort but ``lax.top_k``'s, no grouped
product: the full (L, L) causal scores a block of queries at a time, every
expert held applied to every row (a block of rows at a time), the routing
weights masking the sum. What experts that are not held would add is left
out, as in the program. Then AdamW by hand on float32 weights
(``sdar_moe``'s lines); the expert bias is a fixed leaf: no gradient
reaches it and no step moves it.

``precision`` other than ``highest`` gives the control (every operand of a
matrix product, and every gradient that comes back into one, rounded
first, by ``convnet``'s rules). ``rows_used`` below the batch leaves the
positions past it out of the cross-entropy. The faults this model can have
of its own: ``conv_ahead`` (the conv reads one position ahead,
``u_{t-K+2+j}``: the next token leaks in), ``bias_choice = False`` (the
bias left out of the selection), ``bias_weight = True`` (the weights taken
from the biased scores).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import lfm2_inputs, lm_flops, lm_inputs, netconf
from benchmark.references.convnet import (PRECISIONS, _round_cotangent,
                                          _round_operand)
from benchmark.references import keye_dsa
from benchmark.references.sdar_moe import _pieces, _rmsnorm, _rope

_HIGHEST = lax.Precision.HIGHEST
_QUERY_BLOCK = 512          # queries a pass of the scores takes
_ROW_BLOCK = 4096           # rows a pass of a wide FFN takes


def _mm(precision, a, b):
    return _round_cotangent(
        jnp.matmul(_round_operand(a, precision), _round_operand(b, precision),
                   precision=_HIGHEST), precision)


def shortconv(lay, precision, w, h, ahead=False):
    """One sequence's rows (T, d) through one conv mixer."""
    T, d = h.shape
    if lay.geti("bias"):
        raise netconf.ConfError("%s: a conv bias is not in the reference"
                                % lay.name)
    bcx = _mm(precision, h, w["w_in"])
    u = bcx[:, :d] * bcx[:, 2 * d:]
    taps = w["w_conv"]                                  # (d, K)
    K = taps.shape[1]
    back = K - 1 - int(ahead)          # v_t reads u_{t - back + j}
    up = jnp.pad(u, ((back, K - 1 - back), (0, 0)))
    v = sum(up[j:j + T] * taps[:, j] for j in range(K))
    return _mm(precision, bcx[:, d:2 * d] * v, w["w_out"])


def _attention(lay, precision, w, h):
    """One sequence's rows (T, d) through one causal attention layer."""
    T, d = h.shape
    nh = lay.geti("nhead")
    dh = lay.geti("head_dim") or d // nh
    nkv = lay.geti("nkvhead") or nh
    if not lay.geti("causal") or lay.params.get("attn_mask", "causal") \
            != "causal" or lay.geti("attn_window"):
        raise netconf.ConfError("%s: the reference knows full causal "
                                "attention alone" % lay.name)
    qkv = _mm(precision, h, w["wmat"])
    split = lambda t, n: t.reshape(T, n, dh).transpose(1, 0, 2)  # noqa: E731
    q = split(qkv[:, :nh * dh], nh)
    k = split(qkv[:, nh * dh:(nh + nkv) * dh], nkv)
    v = split(qkv[:, (nh + nkv) * dh:], nkv)
    if lay.geti("qk_norm"):
        eps = lay.getf("qk_norm_eps", 1e-6)
        q, k = _rmsnorm(q, w["qnorm"], eps), _rmsnorm(k, w["knorm"], eps)
    if lay.geti("rope"):
        pos, base = jnp.arange(T), lay.getf("rope_base", 10000.0)
        q, k = _rope(q, pos, base), _rope(k, pos, base)
    blk = min(T, _QUERY_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))

    def block(args):
        i, qb = args                          # (nkv, group, blk, dh)
        s = _mm(precision, qb, k[:, None].swapaxes(-1, -2)) * dh ** -0.5
        t = i * blk + jnp.arange(blk)[:, None]
        a = jax.nn.softmax(jnp.where(jnp.arange(T)[None, :] <= t, s,
                                     -jnp.inf), axis=-1)
        return _mm(precision, a, v[:, None])
    qb = q.reshape(nkv, nh // nkv, T // blk, blk, dh).transpose(2, 0, 1, 3, 4)
    out = lax.map(jax.checkpoint(block), (jnp.arange(T // blk), qb))
    out = out.transpose(1, 2, 0, 3, 4).reshape(nh, T, dh)
    return _mm(precision, out.transpose(1, 0, 2).reshape(T, nh * dh),
               w["wo"])


def route(lay, logits, bias, bias_choice=True, bias_weight=False):
    """(T, nexpert) routing weights, nought for an expert not chosen, and
    the (T, nexpert) choice the scores alone would make."""
    e, k = lay.geti("nexpert"), lay.geti("top_k")
    k = k if 0 < k < e else e
    if lay.params.get("score") != "sigmoid":
        raise netconf.ConfError("%s: the reference knows sigmoid scores "
                                "alone" % lay.name)
    s = jax.nn.sigmoid(logits)
    b = lax.stop_gradient(bias)
    idx = lax.top_k(s + b if bias_choice else s, k)[1]
    w = jnp.take_along_axis(s + b if bias_weight else s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    hot = lambda i: jax.nn.one_hot(i, e, dtype=w.dtype)  # noqa: E731
    return (jnp.sum(hot(idx) * w[..., None], 1),
            jnp.sum(hot(lax.top_k(s, k)[1]), 1))


def _swiglu_rows(precision, u, wg, wu, wd, scale=None):
    """``(silu(u Wg) * (u Wu)) Wd`` of rows u, each of the f columns of a
    row weighted by ``scale`` first where given."""
    a = jax.nn.silu(_mm(precision, u, wg)) * _mm(precision, u, wu)
    return _mm(precision, a if scale is None else a * scale, wd)


def ffn(lay, precision, w, u):
    """One sequence's rows (T, d) through the dense gated FFN, a block of
    rows at a time."""
    T, d = u.shape
    blk = min(T, _ROW_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))
    return lax.map(jax.checkpoint(lambda ub: _swiglu_rows(
        precision, ub, w["wmat"], w["up"], w["down"])),
        u.reshape(T // blk, blk, d)).reshape(T, d)


def moe(lay, precision, w, u, bias_choice=True, bias_weight=False):
    """One sequence's rows (T, d) through the experts held, all of them on
    all rows, a block of rows at a time; the (row, expert) pairs whose
    expert is held here; and the choices the bias moved (chosen with it,
    not without)."""
    T, d = u.shape
    held, _, f = w["wmat"].shape
    lo = lay.geti("expert_offset")
    if lay.params.get("expert_act") != "swiglu":
        raise netconf.ConfError("%s: the reference knows swiglu experts "
                                "alone" % lay.name)
    bias = w.get("expert_bias", jnp.zeros((lay.geti("nexpert"),)))
    wide = lambda m: m.transpose(1, 0, 2).reshape(d, held * f)  # noqa: E731

    def block(ub):
        probs, plain = route(lay, _mm(precision, ub, w["gate"].T), bias,
                             bias_choice, bias_weight)
        mine = probs[:, lo:lo + held]
        y = _swiglu_rows(precision, ub, wide(w["wmat"]), wide(w["up"]),
                         w["down"].reshape(held * f, d),
                         jnp.repeat(mine, f, axis=1))
        return y, jnp.sum(mine > 0), jnp.sum((probs > 0) & (plain == 0))
    y, pairs, moved = lax.map(jax.checkpoint(block), u.reshape(
        T // min(T, _ROW_BLOCK), min(T, _ROW_BLOCK), d))
    return y.reshape(T, d), jnp.sum(pairs), jnp.sum(moved)


def apply_layers(layers, precision, params, vals, said, faults):
    """Apply ``layers`` in order to the node values ``vals`` of one
    sequence (name -> array; node "0" holds the L ids), in place. What the
    ``moe`` layers count goes to ``said``: ``pairs_held`` and
    ``bias_moved`` by layer."""
    for lay in layers:
        w = params.get(lay.name)
        a = vals[lay.ins[0]]
        if lay.type == "embed":
            out = w["wmat"][a]
        elif lay.type == "rmsnorm":
            out = _rmsnorm(a, w["gain"], lay.getf("eps", 1e-6))
        elif lay.type == "shortconv":
            out = shortconv(lay, precision, w, a, faults["conv_ahead"])
        elif lay.type == "attention":
            out = _attention(lay, precision, w, a)
        elif lay.type == "ffn":
            out = ffn(lay, precision, w, a)
        elif lay.type == "add":
            out = sum(vals[n] for n in lay.ins)
        elif lay.type == "moe":
            if len(lay.ins) != 1:
                raise netconf.ConfError("%s: the router reads the experts' "
                                        "own input here" % lay.name)
            out, n, moved = moe(lay, precision, w, a, faults["bias_choice"],
                                faults["bias_weight"])
            said.setdefault("pairs_held", {})[lay.name] = n
            said.setdefault("bias_moved", {})[lay.name] = moved
        elif lay.type == "conv":
            out = _mm(precision, a, w["wmat"].T)
        elif lay.type == "softmax":
            out = a
        else:
            raise netconf.ConfError("layer type %r" % lay.type)
        vals[lay.outs[0]] = out
    return vals


_SOUND = {"conv_ahead": False, "bias_choice": True, "bias_weight": False}


def forward(layers, precision, params, ids, faults=None):
    """One sequence's L ids -> logits (L, vocab) and what the layers
    counted (``apply_layers``). Each block runs under ``jax.checkpoint``:
    what stays alive across a cut is the residual stream."""
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
    """``keye_dsa``'s three steps of training from a seed (its loop,
    ``sdar_moe``'s AdamW by hand) on this model. ``run`` returns what the
    comparison reads, each step's loss and the norm of every trained
    leaf's first gradient and of every leaf's change over the steps (the
    expert bias is no trained leaf: it has no gradient here and its change
    is 0); beside them, read by
    no comparison, ``pairs_held`` (each ``moe`` layer's pairs held in each
    step, which ``kernel_work`` counts the experts' products by) and
    ``bias_moved`` (the choices the bias changed)."""

    def __init__(self, conf_text: str, cfg: dict, batch: int,
                 precision: str = "highest", rows_used: int = 0,
                 conv_ahead: bool = False, bias_choice: bool = True,
                 bias_weight: bool = False):
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
        self.leaves = lfm2_inputs.leaves_of(self.layers)
        self._weights = lfm2_inputs.params_from_seed(self.layers, self.glob,
                                                     cfg)
        by_name = {lay.name: lay for lay in self.layers}
        adam = {"beta1": float(self.glob.get("beta1", 0.9)),
                "beta2": float(self.glob.get("beta2", 0.999)),
                "eps": float(self.glob.get("adam_eps", 1e-8))}
        if self.glob.get("updater") != "adamw":
            raise netconf.ConfError("the reference trains with adamw")
        self.hyper = {}
        for _, name, tag, _ in self.leaves:
            p = netconf.updater_params(self.glob, by_name[name], tag)
            fixed = tag in lfm2_inputs.FIXED       # a step of 0 keeps it
            self.hyper.setdefault(name, {})[tag] = dict(
                adam, lr=0.0 if fixed else p["lr"],
                wd=0.0 if fixed else p["wd"])
        faults = {"conv_ahead": conv_ahead, "bias_choice": bias_choice,
                  "bias_weight": bias_weight}

        def loss(params, data, label):
            return loss_mean(self.layers, precision, params, data, label,
                             self.rows_used, faults)
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        self._grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
        self._init = jax.jit(self._start)
        self._batch = jax.jit(lambda key, batch_id: lm_inputs.make_tokens(
            key, batch_id, self.rows, self.seq_len, self.vocab),
            static_argnums=1)
        self._update = jax.jit(self._apply, donate_argnums=(0, 1, 2))
        self._norms_of = jax.jit(keye_dsa._norms)
        self._change = jax.jit(lambda new, key: keye_dsa._norms(
            jax.tree.map(jnp.subtract, new, self._weights(key))))

    def run(self, seed: int, n_steps: int = 3) -> dict:
        """``keye_dsa.Reference.run``'s numbers, the fixed leaves left out of
        the first gradient; their change, 0, stays for the program's to be
        held to (``programs/cxxnet_lfm2_trainer.py``)."""
        out = super().run(seed, n_steps)
        out["grad_norm"] = {n: v for n, v in out["grad_norm"].items()
                            if n.split(":")[1] not in lfm2_inputs.FIXED}
        return out


# what a window kind asks of a reference's file
for_config = Reference.for_config


def _model(conf_text: str):
    layers, _ = netconf.parse(conf_text)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    return layers, d


def forward_macs(conf_text: str, seq_len: int):
    """(layer name, part, multiply-adds of ONE SEQUENCE's forward pass):
    L rows through every product: a conv mixer's two (``in`` d x 3d,
    ``out`` d x d), attention's projections and its core over the causal
    triangle, the dense FFN's three matrices, the router and the experts
    (their three matrices at even routing: ``top_k * nexpert_held /
    nexpert`` pairs a row), the head. The conv's taps and gates are no
    product and are not counted here (``kernel_work("short_conv")``)."""
    layers, d = _model(conf_text)
    out, tri = [], seq_len * (seq_len + 1) / 2.0
    for lay in layers:
        if lay.type == "shortconv":
            out.append((lay.name, "conv_in", seq_len * d * 3 * d))
            out.append((lay.name, "conv_out", seq_len * d * d))
        elif lay.type == "attention":
            a = lm_flops._dims(lay, d)
            q, kv = a["nh"] * a["dh"], a["nkv"] * a["dh"]
            out.append((lay.name, "qkv", seq_len * d * (q + 2 * kv)))
            out.append((lay.name, "core", 2 * q * tri))
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
        elif lay.type == "conv":
            out.append((lay.name, "head",
                        seq_len * d * lay.geti("nchannel")))
    return out


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained token: forward once, backward twice."""
    seq = cfg["seq_len"]
    return 3.0 * 2.0 * sum(m for _, _, m in forward_macs(conf_text, seq)) \
        / seq


def short_conv(d: int, K: int, tokens: float,
               itemsize: int = 2) -> Dict[str, float]:
    """One conv mixer's core in one training step over ``tokens`` rows:
    K d multiply-adds and two gate products a row forward, twice that
    backward; the bytes the core cannot avoid: B, C, X read and y written
    once forward, dy with B, C, X read and dB, dC, dX written once
    backward, and the taps read both ways and their float32 gradient
    written."""
    fwd = tokens * (2.0 * K * d + 2.0 * d)
    moved = tokens * itemsize * d * ((3 + 1) + (1 + 3 + 3))
    return {"flops": 3.0 * fwd,
            "bytes": moved + d * K * (2 * itemsize + 4)}


def kernel_work(conf_text: str, cfg: dict, name: str, ctx: dict):
    """FLOPs and bytes one training step of this configuration needs of the
    named kernel, all layers that run it summed: the model's operations and
    the bytes it cannot avoid, the forward pass three times over, nothing
    made again counted. ``flash_attention``: every attention layer's core
    over the causal triangle (4 head_dim FLOPs a score a head; q, k, v, the
    output and their gradients moved once). ``expert_product``: every
    ``moe`` layer's three products over the pairs its experts here hold on
    the run's two resident batches, as this reference counted them in its
    own first two steps (``ctx["want"]["pairs_held"]``, their mean; even
    routing's where no reference has run); ``pairs_a_step`` says how many.
    ``short_conv``: what runs under a conv mixer's ``core`` scope
    (``short_conv`` above). Nothing the program says of itself is counted.
    Nothing for a name not known here."""
    layers, d = _model(conf_text)
    seq = cfg["seq_len"]
    seqs = cfg["batch_per_chip"] // seq
    tri = seq * (seq + 1) / 2.0
    if name == "flash_attention":
        flops = bytes_ = 0.0
        for lay in (lay for lay in layers if lay.type == "attention"):
            a = lm_flops._dims(lay, d)
            flops += 3.0 * seqs * 4.0 * a["nh"] * a["dh"] * tri
            bytes_ += 2.0 * seqs * 2 * seq * a["dh"] * (
                2 * a["nh"] + 2 * a["nkv"])
        return {"flops": flops, "bytes": bytes_}
    if name == "short_conv":
        works = [short_conv(d, lay.geti("kernel", 3), seqs * seq)
                 for lay in layers if lay.type == "shortconv"]
        return {key: sum(w[key] for w in works)
                for key in ("flops", "bytes")}
    if name == "expert_product":
        counted = (ctx.get("want") or {}).get("pairs_held") or {}
        works, said = [], {"pairs_a_step": 0.0}
        for lay in (lay for lay in layers if lay.type == "moe"):
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
