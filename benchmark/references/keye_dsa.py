"""Reference ``keye_dsa``, as a configuration's ``"reference"`` key names
it: one next-token training step of a mixture-of-experts language model
whose attention is learned sparse attention (a DeepSeek-Sparse-Attention
indexer: Keye-VL-2.0-30B-A3B's language model), in straightforward
``jax.numpy``, float32, matrix products at ``highest`` precision. It reads
the conf text through ``netconf.parse``, makes its weights and tokens from
the seed (``dsa_inputs``, ``lm_inputs``), and imports nothing of the
program under test.

For one sequence of L rows x (L, d), every block alike:

    h  = rmsnorm(x; g1)
    q, k, v = h W_q, h W_k, h W_v       nhead / nkvhead heads of head_dim
    q, k = rope(rmsnorm(q; g_q)), rope(rmsnorm(k; g_k))
    hd = stop_gradient(h)               the indexer reads the stream detached
    qI = rope(hd Widx_q)                J heads of di
    kI = rope(layernorm(hd Widx_k; gain, bias))         one head of di
    w  = (hd Widx_w) J^-1/2 di^-1/2
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t
    S_t = the min(t + 1, topk) keys s <= t of largest I[t, s]: the first
          min(t + 1, topk) indices lax.top_k gives (equal scores: lower s)
    a[t, g, s] = softmax over s in S_t of q[t, g] . k[s] / sqrt(head_dim)
    x' = x + (sum_{s in S_t} a[t, g, s] v[s])_g W_o
    p[t, s] = stop_gradient(mean_g a[t, g, s])
    L_idx = (1 / L) sum_t sum_{s in S_t} p[t, s] (log p[t, s]
                                   - log softmax_{S_t}(I[t, .])[s])
    out = x' + moe(rmsnorm(x'; g2))     ``sdar_moe``'s layer: top-k of the
                                        router's softmax over the k largest,
                                        SwiGLU experts, the experts held

then a last rmsnorm, the untied head, and

    loss = mean next-token cross-entropy + sum over the layers of L_idx.

No kernels, no sort but ``lax.top_k``'s, no grouped product: the full
(L, L) index scores and masked scores a block of queries at a time, the
selection scattered from top_k's indices, every expert held applied to
every row. Then AdamW by hand on float32 weights (``sdar_moe``'s lines).

``precision`` other than ``highest`` gives the control (every operand of a
matrix product, and every gradient that comes back into one, rounded first,
by ``convnet``'s rules; the index scores' products among them, so a control
may select other keys). ``rows_used`` below the batch leaves the positions
past it out of the cross-entropy. The faults this model can have of its
own: ``topk`` (another number of keys a query; ``0`` = all of them, the
selection left out), ``index_loss = False`` (L_idx left out: the indexer's
leaves get no gradient), ``detach = False`` (the indexer reads the stream
attached, so L_idx reaches the model's leaves).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import dsa_inputs, lm_flops, lm_inputs, netconf
from benchmark.inputs import seed_key
from benchmark.references.convnet import (PRECISIONS, _round_cotangent,
                                          _round_operand)
from benchmark.references import sdar_moe
from benchmark.references.sdar_moe import _pieces, _rmsnorm, _rope, moe

_HIGHEST = lax.Precision.HIGHEST
_QUERY_BLOCK = 512          # queries a pass of the scores takes


def _mm(precision, a, b):
    return _round_cotangent(
        jnp.matmul(_round_operand(a, precision), _round_operand(b, precision),
                   precision=_HIGHEST), precision)


def _layernorm(x, gain, bias, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gain + bias


def topk_of(lay, L: int, topk: Optional[int]) -> int:
    """Keys a query keeps: the layer's ``index_topk``, or the fault's; 0
    keeps all; never more than the sequence has."""
    k = lay.geti("index_topk") if topk is None else topk
    return min(k or L, L)


def select_rows(scores, first_row, k: int):
    """(rows, L) index scores of queries ``first_row``.. -> (rows, L)
    bool: the min(t + 1, k) keys s <= t of largest score, scattered from
    ``lax.top_k``'s indices (it puts equal scores' lower index first, and
    the keys past the diagonal, at -inf, last)."""
    rows, L = scores.shape
    t = first_row + jnp.arange(rows)[:, None]
    masked = jnp.where(jnp.arange(L)[None, :] <= t, scores, -jnp.inf)
    _, idx = lax.top_k(masked, k)
    valid = jnp.arange(k)[None, :] <= t                 # the first t + 1
    return jnp.zeros((rows, L), bool).at[
        jnp.broadcast_to(jnp.arange(rows)[:, None], idx.shape), idx].set(
            valid)


def _attention(lay, precision, w, h, topk, detach, keep_sel=False):
    """One sequence's rows (T, d) through one attention layer: its output,
    the indexer's loss L_idx, the pairs selected (and, ``keep_sel``, the
    (T, T) selection itself, for the tests)."""
    T, d = h.shape
    nh = lay.geti("nhead")
    dh = lay.geti("head_dim") or d // nh
    nkv = lay.geti("nkvhead") or nh
    J, di = lay.geti("index_heads"), lay.geti("index_dim")
    if lay.params.get("attn_mask") != "dsa" or not lay.geti("causal"):
        raise netconf.ConfError("%s: the reference knows causal learned "
                                "sparse attention alone" % lay.name)
    k_sel = topk_of(lay, T, topk)
    base = lay.getf("rope_base", 10000.0)
    pos = jnp.arange(T)
    qkv = _mm(precision, h, w["wmat"])
    split = lambda t, n, f: t.reshape(T, n, f).transpose(1, 0, 2)  # noqa: E731
    q = split(qkv[:, :nh * dh], nh, dh)
    k = split(qkv[:, nh * dh:(nh + nkv) * dh], nkv, dh)
    v = split(qkv[:, (nh + nkv) * dh:], nkv, dh)
    if lay.geti("qk_norm"):
        q, k = _rmsnorm(q, w["qnorm"], 1e-6), _rmsnorm(k, w["knorm"], 1e-6)
    hd = lax.stop_gradient(h) if detach else h
    qi = split(_mm(precision, hd, w["widx_q"]), J, di)
    ki = _layernorm(_mm(precision, hd, w["widx_k"]), w["idx_gain"],
                    w["idx_bias"])[None]
    if lay.geti("rope"):
        q, k = _rope(q, pos, base), _rope(k, pos, base)
        qi, ki = _rope(qi, pos, base), _rope(ki, pos, base)
    wi = _mm(precision, hd, w["widx_w"]) * (J ** -0.5 * di ** -0.5)
    blk = min(T, _QUERY_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))

    def block(args):
        i, qb, qib, wb = args       # (nkv, group, blk, dh), (J, blk, di)
        z = jnp.maximum(_mm(precision, qib, ki.swapaxes(-1, -2)), 0.0)
        index = jnp.einsum("jts,tj->ts", z, wb, precision=_HIGHEST)
        sel = select_rows(lax.stop_gradient(index), i * blk, k_sel)
        s = _mm(precision, qb, k[:, None].swapaxes(-1, -2)) * dh ** -0.5
        a = jax.nn.softmax(jnp.where(sel, s, -jnp.inf), axis=-1)
        out = _mm(precision, a, v[:, None])
        p = lax.stop_gradient(jnp.mean(a, axis=(0, 1)))         # (blk, T)
        logq = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), axis=-1)
        live = sel & (p > 0.0)
        kl = jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                          - jnp.where(live, logq, 0.0)),
                               0.0))
        return out, kl, jnp.sum(sel), (sel if keep_sel else None)
    qb = q.reshape(nkv, nh // nkv, T // blk, blk, dh).transpose(2, 0, 1, 3, 4)
    qib = qi.reshape(J, T // blk, blk, di).transpose(1, 0, 2, 3)
    out, kl, n_sel, sel = lax.map(
        jax.checkpoint(block),
        (jnp.arange(T // blk), qb, qib, wi.reshape(T // blk, blk, J)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(nh, T, dh)
    out = _mm(precision, out.transpose(1, 0, 2).reshape(T, nh * dh),
              w["wo"])
    if keep_sel:
        sel = sel.reshape(T, T)
    return out, jnp.sum(kl) / T, jnp.sum(n_sel), sel


def apply_layers(layers, precision, params, vals, said, topk, detach,
                 keep_sel=False):
    """Apply ``layers`` in order to the node values ``vals`` of one
    sequence (name -> array; node "0" holds the L ids), in place. What the
    layers count goes to ``said``: ``pairs_held`` by ``moe`` layer,
    ``index_loss`` and ``selected`` by attention layer."""
    for lay in layers:
        w = params.get(lay.name)
        a = vals[lay.ins[0]]
        if lay.type == "embed":
            out = w["wmat"][a]
        elif lay.type == "rmsnorm":
            out = _rmsnorm(a, w["gain"], lay.getf("eps", 1e-6))
        elif lay.type == "attention":
            out, kl, n, sel = _attention(lay, precision, w, a, topk, detach,
                                         keep_sel)
            said.setdefault("index_loss", {})[lay.name] = kl
            said.setdefault("selected", {})[lay.name] = n
            if keep_sel:
                said.setdefault("selection", {})[lay.name] = sel
        elif lay.type == "add":
            out = sum(vals[n] for n in lay.ins)
        elif lay.type == "moe":
            if len(lay.ins) != 1:
                raise netconf.ConfError("%s: the router reads the experts' "
                                        "own input here" % lay.name)
            out, n = moe(lay, precision, w, a)
            said.setdefault("pairs_held", {})[lay.name] = n
        elif lay.type == "conv":
            out = _mm(precision, a, w["wmat"].T)
        elif lay.type == "softmax":
            out = a
        else:
            raise netconf.ConfError("layer type %r" % lay.type)
        vals[lay.outs[0]] = out
    return vals


def forward(layers, precision, params, ids, topk=None, detach=True,
            keep_sel=False):
    """One sequence's L ids -> logits (L, vocab) and what the layers
    counted (``apply_layers``). Each transformer block runs under
    ``jax.checkpoint``: what stays alive across a cut is the residual
    stream."""
    vals, said = {"0": ids}, {}
    for piece in _pieces(layers):
        need = {n: vals[n] for lay in piece for n in lay.ins if n in vals}
        last = piece[-1].outs[0]

        def run(p, xs, piece=piece, last=last):
            counted = {}
            out = apply_layers(piece, precision, p, dict(xs), counted, topk,
                               detach, keep_sel)[last]
            return out, counted
        out, counted = jax.checkpoint(run)(params, need)
        vals = {last: out}
        for what, by_layer in counted.items():
            said.setdefault(what, {}).update(by_layer)
    return vals[layers[-1].outs[0]], said


def loss_mean(layers, precision, params, data, label, rows_used, topk=None,
              index_loss=True, detach=True):
    """The batch's loss as the model states it (mean next-token
    cross-entropy over a sequence's positions, positions from ``rows_used``
    on left out, plus every layer's L_idx), summed over the sequences and
    divided by their number as the program does; and what the layers
    counted, summed over the sequences."""
    rows, L = label.shape

    def one(ids, lab, used):
        logits, said = forward(layers, precision, params, ids, topk, detach)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, lab[:, None].astype(jnp.int32),
                                  axis=1)[:, 0]
        loss = jnp.sum(jnp.where(used, ce, 0.0)) / L
        if index_loss:
            loss = loss + sum(said["index_loss"].values())
        return loss, said
    ids = data.reshape(rows, L).astype(jnp.int32)
    used = (jnp.arange(rows * L) < rows_used).reshape(rows, L)
    loss, said = jax.vmap(one)(ids, label, used)
    return jnp.sum(loss) / rows, jax.tree.map(jnp.sum, said)


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {"%s:%s" % (n, tag): jnp.sqrt(jnp.sum(jnp.square(v)))
            for n, d in tree.items() for tag, v in d.items()}


class Reference(sdar_moe.Reference):
    """``sdar_moe``'s three steps of training from a seed (its AdamW by
    hand: ``_start``, ``_apply``) on this model; ``run`` returns what the
    comparison reads: each step's loss, the norm of every leaf's first
    gradient and of its change over the steps. Beside them, read by no
    comparison: ``pairs_held`` (each ``moe`` layer's pairs held in each of
    the steps, which ``kernel_work`` counts the experts' products by),
    ``selected`` and ``index_loss`` (each attention layer's selected pairs
    and L_idx in each step, summed over the sequences)."""

    def __init__(self, conf_text: str, cfg: dict, batch: int,
                 precision: str = "highest", rows_used: int = 0,
                 topk: Optional[int] = None, index_loss: bool = True,
                 detach: bool = True):
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
        self.leaves = dsa_inputs.leaves_of(self.layers)
        self._weights = dsa_inputs.params_from_seed(self.layers, self.glob,
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
            self.hyper.setdefault(name, {})[tag] = dict(
                adam, lr=p["lr"], wd=p["wd"])

        def loss(params, data, label):
            return loss_mean(self.layers, precision, params, data, label,
                             self.rows_used, topk, index_loss, detach)
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        self._grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
        self._init = jax.jit(self._start)
        self._batch = jax.jit(lambda key, batch_id: lm_inputs.make_tokens(
            key, batch_id, self.rows, self.seq_len, self.vocab),
            static_argnums=1)
        self._update = jax.jit(self._apply, donate_argnums=(0, 1, 2))
        self._norms_of = jax.jit(_norms)
        self._change = jax.jit(lambda new, key: _norms(
            jax.tree.map(jnp.subtract, new, self._weights(key))))

    def run(self, seed: int, n_steps: int = 3) -> dict:
        key = seed_key(seed)
        params, m1, m2 = self._init(key)
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
                params, m1, m2 = self._update(params, m1, m2, grads, step)
                del grads
            change = jax.device_get(self._change(params, key))
        return dict({"loss": losses, "grad_norm": grad_norms,
                     "change_norm": {n: float(v)
                                     for n, v in change.items()}},
                    **counted)


# what a window kind asks of a reference's file
for_config = Reference.for_config


def kept_scores(seq_len: int, topk: int) -> float:
    """Query-key pairs a selection keeps in one sequence:
    sum_t min(t + 1, topk)."""
    k = min(topk or seq_len, seq_len)
    return k * (k + 1) / 2.0 + (seq_len - k) * float(k)


def _model(conf_text: str):
    layers, _ = netconf.parse(conf_text)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    return layers, d


def _index_dims(lay):
    return lay.geti("index_heads"), lay.geti("index_dim")


def forward_macs(conf_text: str, seq_len: int):
    """(layer name, part, multiply-adds of ONE SEQUENCE's forward pass):
    L rows through every projection (the indexer's three too), router and
    expert product (the experts at even routing: ``top_k * nexpert_held /
    nexpert`` pairs a row), the core by the scores the selection keeps,
    the index scores by the causal triangle, the head."""
    layers, d = _model(conf_text)
    out, tri = [], seq_len * (seq_len + 1) / 2.0
    for lay in layers:
        if lay.type == "attention":
            a = lm_flops._dims(lay, d)
            q, kv = a["nh"] * a["dh"], a["nkv"] * a["dh"]
            J, di = _index_dims(lay)
            out.append((lay.name, "qkv", seq_len * d * (q + 2 * kv)))
            out.append((lay.name, "core", 2 * q * kept_scores(
                seq_len, lay.geti("index_topk"))))
            out.append((lay.name, "out", seq_len * q * d))
            out.append((lay.name, "index_proj",
                        seq_len * d * (J * di + di + J)))
            out.append((lay.name, "index_scores", J * di * tri))
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


def _index_backward_macs(lay, seq_len: int) -> float:
    """Multiply-adds the index scores' backward needs in one sequence: the
    gradient is nought off the selected pairs, and on each of them qI's
    and kI's products take J di each."""
    J, di = _index_dims(lay)
    return 2.0 * J * di * kept_scores(seq_len, lay.geti("index_topk"))


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained token: every part forward once and
    backward twice, but the indexer's: its projections' backward is the
    weights' alone (its input is detached: twice the forward in all), its
    scores' backward runs over the selected pairs."""
    seq = cfg["seq_len"]
    layers, _ = _model(conf_text)
    times = {"index_proj": 2.0, "index_scores": 1.0}
    macs = sum(times.get(part, 3.0) * m
               for _, part, m in forward_macs(conf_text, seq))
    macs += sum(_index_backward_macs(lay, seq) for lay in layers
                if lay.type == "attention")
    return 2.0 * macs / seq


def kernel_work(conf_text: str, cfg: dict, name: str, ctx: dict):
    """FLOPs and bytes one training step of this configuration needs of the
    named kernel, all layers that run it summed: the model's operations and
    the bytes it cannot avoid, nothing made again counted.
    ``flash_attention``: every attention layer's core by the scores the
    selection keeps, sum_t min(t + 1, topk) a head (4 head_dim FLOPs a kept
    score a head, x 3; q, k, v, the output and their gradients moved once).
    ``index_scores``: what runs under the layers' ``index`` scope: the
    indexer's three projections (forward and the weights' gradient: the
    input is detached) and the index scores, forward over the causal
    triangle (2 J di FLOPs a pair) and backward over the selected pairs
    alone (4 J di a pair); the bytes of the stream read, of qI, kI and w
    and of their gradients, once. ``select``: no FLOPs; the bytes it
    cannot avoid, the causal triangle's float32 scores read once and a bit
    a pair written. ``expert_product``: ``sdar_moe``'s count, the pairs the
    experts here hold as this reference counted them in its own first two
    steps. Nothing the program says of itself is counted. Nothing for a
    name not known here."""
    layers, d = _model(conf_text)
    seq = cfg["seq_len"]
    seqs = cfg["batch_per_chip"] // seq
    tri = seq * (seq + 1) / 2.0
    att = [lay for lay in layers if lay.type == "attention"]
    said = {}
    if name == "flash_attention":
        flops = bytes_ = 0.0
        for lay in att:
            a = lm_flops._dims(lay, d)
            flops += 3.0 * seqs * 4.0 * a["nh"] * a["dh"] * kept_scores(
                seq, lay.geti("index_topk"))
            bytes_ += 2.0 * seqs * 2 * seq * a["dh"] * (
                2 * a["nh"] + 2 * a["nkv"])
        return {"flops": flops, "bytes": bytes_}
    if name == "index_scores":
        flops = bytes_ = 0.0
        for lay in att:
            J, di = _index_dims(lay)
            width = J * di + di + J
            flops += seqs * 2.0 * (2.0 * seq * d * width + J * di * tri
                                   + _index_backward_macs(lay, seq))
            bytes_ += seqs * 2.0 * seq * (d + 2 * width)
        return {"flops": flops, "bytes": bytes_}
    if name == "select":
        return {"flops": 0.0,
                "bytes": len(att) * seqs * tri * (4.0 + 1.0 / 8.0)}
    if name == "expert_product":
        counted = (ctx.get("want") or {}).get("pairs_held") or {}
        works, said["pairs_a_step"] = [], 0.0
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
