"""Reference ``moe_lm``, as a configuration's ``"reference"`` key names it: a
decoder-only mixture-of-experts language model's training step in
straightforward ``jax.numpy``, float32, matrix products at ``highest``
precision. It reads the conf text through ``netconf.parse``, makes its
weights and tokens from the seed (``lm_inputs``), and imports nothing of the program under
test. For a sequence of T tokens, x the residual stream entering a block:

    r  = x W_r                          router logits, read from the block's
                                        INPUT (the moe layer's second input)
    h  = rmsnorm(x; g1)                 x * rsqrt(mean(x^2) + eps) * g1
    q, k, v = h W_q, h W_k, h W_v       nhead / nkvhead heads of head_dim
    q, k = rope(q), rope(k)             where the layer has ``rope = 1``:
                                        whole head, half-split pairs
    a  = softmax(q k^T / sqrt(dh) + M) v    M causal, and within the last
                                        ``attn_window`` keys where set; query
                                        head j reads key-value head j // group
    x' = x + a W_o
    u  = rmsnorm(x'; g2)
    S  = top-k of r; w = softmax(r[S])
    y  = sum_{e in S, e held} w_e (relu(u Wg_e) * (u Wu_e)) Wd_e
    out = x' + y

then a last rmsnorm, the untied head, and the mean next-token cross-entropy.
No kernels, no sort, no grouped product, no cache: the full (T, T) masked
scores, computed a block of queries at a time so that T = 8192 fits, and
EVERY expert held applied to EVERY token, the routing weights (nought for
an expert a token did not choose) masking the sum. What experts that are
not held would add is left out, as in the program (one chip's share of an
expert-parallel layer). Then AdamW by hand on float32 weights:

    m <- b1 m + (1 - b1) g;  v <- b2 v + (1 - b2) g^2
    w <- w - lr (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd w)

Departures from the equations as published: none known; what the source
does not give is listed in the configuration's ``assumed``.

``precision`` other than ``highest`` gives the control (every operand of a
matrix product, and every gradient that comes back into one, rounded first,
by ``convnet``'s rules); ``rows_used`` below the batch leaves the last
tokens out of the loss: the planted fault.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import lm_flops, lm_inputs, netconf
from benchmark.inputs import seed_key
from benchmark.references.convnet import (PRECISIONS, _round_cotangent,
                                          _round_operand)

_HIGHEST = lax.Precision.HIGHEST
_QUERY_BLOCK = 512


def _mm(precision, a, b):
    return _round_cotangent(
        jnp.matmul(_round_operand(a, precision), _round_operand(b, precision),
                   precision=_HIGHEST), precision)


def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def _rope(x, base):
    """(heads, T, dh): rotate the (first half, second half) pairs."""
    half = x.shape[-1] // 2
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
    ang = pos * jnp.power(base, -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(lay, precision, w, h):
    """One sequence (T, d) through one attention layer."""
    T, d = h.shape
    nh = lay.geti("nhead")
    dh = lay.geti("head_dim") or d // nh
    nkv = lay.geti("nkvhead") or nh
    window = lay.geti("attn_window")
    qkv = _mm(precision, h, w["wmat"])
    split = lambda t, n: t.reshape(T, n, dh).transpose(1, 0, 2)  # noqa: E731
    q = split(qkv[:, :nh * dh], nh)
    k = split(qkv[:, nh * dh:(nh + nkv) * dh], nkv)
    v = split(qkv[:, (nh + nkv) * dh:], nkv)
    if lay.geti("rope"):
        base = lay.getf("rope_base", 10000.0)
        q, k = _rope(q, base), _rope(k, base)
    blk = min(T, _QUERY_BLOCK)
    if T % blk:
        raise netconf.ConfError("sequence %d is no multiple of %d" % (T, blk))
    kpos = jnp.arange(T)[None, :]

    def block(args):
        i, qi = args                          # qi: (nkv, group, blk, dh)
        s = _mm(precision, qi, k[:, None].swapaxes(-1, -2)) * dh ** -0.5
        qpos = i * blk + jnp.arange(blk)[:, None]
        keep = qpos >= kpos if lay.geti("causal") else kpos >= 0
        if window:
            keep = keep & (qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm(precision, p, v[:, None])
    qb = q.reshape(nkv, nh // nkv, T // blk, blk, dh).transpose(2, 0, 1, 3, 4)
    out = lax.map(jax.checkpoint(block), (jnp.arange(T // blk), qb))
    out = out.transpose(1, 2, 0, 3, 4).reshape(nh, T, dh)
    return _mm(precision, out.transpose(1, 0, 2).reshape(T, nh * dh),
               w["wo"])


def route(lay, logits):
    """(T, nexpert) routing weights, nought for an expert not chosen."""
    e, k = lay.geti("nexpert"), lay.geti("top_k")
    k = k if 0 < k < e else e
    vals, idx = lax.top_k(logits, k)
    w = jax.nn.softmax(vals, axis=-1)
    return jnp.sum(jax.nn.one_hot(idx, e, dtype=w.dtype) * w[..., None], 1)


def _moe(lay, precision, w, u, x_router):
    """One sequence through the experts held: all of them on all tokens
    (``tests/test_smallthinker.py`` holds the program's layer to it)."""
    return _moe_counted(lay, precision, w, u, x_router)[0]


def _moe_counted(lay, precision, w, u, x_router):
    """``_moe``'s output and, beside it, the number of (token, expert)
    pairs whose expert is held here: the rows a sparse lowering of this
    layer has work on."""
    T, d = u.shape
    held, _, f = w["wmat"].shape
    lo = lay.geti("expert_offset")
    probs = route(lay, _mm(precision, x_router, w["gate"].T))[:, lo:lo + held]
    pairs = jnp.sum(probs > 0)
    mask = jnp.repeat(probs, f, axis=1)                     # (T, held * f)
    wide = lambda m: m.transpose(1, 0, 2).reshape(d, held * f)  # noqa: E731
    a = jnp.maximum(_mm(precision, u, wide(w["wmat"])), 0.0)
    if lay.params.get("expert_act", "relu") == "relu":
        # one matrix an expert: the weighted sum of the experts' outputs
        return jnp.sum((a * mask).reshape(T, held, f), axis=1), pairs
    a = a * _mm(precision, u, wide(w["up"]))
    return _mm(precision, a * mask, w["down"].reshape(held * f, d)), pairs


def apply_layers(layers, precision, params, vals, pairs=None):
    """Apply ``layers`` in order to the node values ``vals`` of one
    sequence (name -> array; node "0" holds the token ids), in place.
    ``pairs`` (a dict, where given) gets each ``moe`` layer's count of the
    pairs its experts here held, by the layer's name."""
    for lay in layers:
        w = params.get(lay.name)
        a = vals[lay.ins[0]]
        if lay.type == "embed":
            out = w["wmat"][a]
        elif lay.type == "rmsnorm":
            out = _rmsnorm(a, w["gain"], lay.getf("eps", 1e-6))
        elif lay.type == "attention":
            out = _attention(lay, precision, w, a)
        elif lay.type == "add":
            out = sum(vals[n] for n in lay.ins)
        elif lay.type == "moe":
            out, held = _moe_counted(lay, precision, w, a,
                                     vals[lay.ins[-1]])
            if pairs is not None:
                pairs[lay.name] = held
        elif lay.type == "conv":
            out = _mm(precision, a, w["wmat"].T)
        elif lay.type == "softmax":
            out = a
        else:
            raise netconf.ConfError("layer type %r" % lay.type)
        vals[lay.outs[0]] = out
    return vals


def _pieces(layers):
    """The layer list cut after every second ``add``: a transformer block a
    piece (the embedding with the first, the last norm and the head after
    the last)."""
    pieces, start, adds = [], 0, 0
    for i, lay in enumerate(layers):
        adds += lay.type == "add"
        if lay.type == "add" and adds % 2 == 0:
            pieces.append(layers[start:i + 1])
            start = i + 1
    return pieces + [layers[start:]]


def forward(layers, precision, params, ids):
    """One sequence of token ids (T,) -> logits (T, vocab) and {``moe``
    layer: the pairs its experts here held}. Each piece runs under
    ``jax.checkpoint``: what stays alive across a cut is the residual
    stream, and the backward pass holds one block's intermediates."""
    vals, pairs = {"0": ids}, {}
    for piece in _pieces(layers):
        need = {n: vals[n] for lay in piece for n in lay.ins if n in vals}
        last = piece[-1].outs[0]

        def run(p, xs, piece=piece, last=last):
            held = {}
            out = apply_layers(piece, precision, p, dict(xs), held)[last]
            return out, held
        out, held = jax.checkpoint(run)(params, need)
        vals = {last: out}
        pairs.update(held)
    return vals[layers[-1].outs[0]], pairs


def logits_of(layers, precision, params, ids):
    """``forward``'s logits alone."""
    return forward(layers, precision, params, ids)[0]


def loss_sum(layers, precision, params, data, label, rows_used):
    """Sum of the next-token cross-entropy over the first ``rows_used``
    tokens of the batch (rows one after another), and ``forward``'s pairs
    held summed over the batch's rows. ``data`` (rows, 1, 1, L) and
    ``label`` (rows, L) as the program gets them."""
    rows, L = label.shape

    def one(ids, lab, used):
        logits, pairs = forward(layers, precision, params, ids)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, lab[:, None].astype(jnp.int32),
                                  axis=1)[:, 0]
        return jnp.sum(jnp.where(used, ce, 0.0)), pairs
    ids = data.reshape(rows, L).astype(jnp.int32)
    used = (jnp.arange(rows * L) < rows_used).reshape(rows, L)
    ce, pairs = jax.vmap(one)(ids, label, used)
    return jnp.sum(ce), jax.tree.map(jnp.sum, pairs)


def _norms(tree) -> Dict[str, jnp.ndarray]:
    return {"%s:%s" % (n, tag): jnp.sqrt(jnp.sum(jnp.square(v)))
            for n, d in tree.items() for tag, v in d.items()}


class Reference:
    """Three steps of training from a seed; ``run`` returns what the
    comparison reads: each step's loss, the norm of every leaf's first
    gradient and of its change over the steps. Beside them, read by no
    comparison, ``pairs_held``: each ``moe`` layer's pairs held in each of
    the steps, which ``kernel_work`` counts the experts' products by."""

    def __init__(self, conf_text: str, cfg: dict, batch: int,
                 precision: str = "highest", rows_used: int = 0):
        if precision not in PRECISIONS:
            raise ValueError("precision %r" % precision)
        self.layers, self.glob = netconf.parse(conf_text)
        seq_len = cfg["seq_len"]
        if batch % seq_len:
            raise ValueError("batch of %d tokens is no whole number of "
                             "sequences of %d" % (batch, seq_len))
        self.seq_len, self.rows = seq_len, batch // seq_len
        self.batch = batch                         # tokens a step
        self.rows_used = rows_used or batch
        self.vocab = lm_inputs.vocab_of(self.layers)
        self.leaves = lm_inputs.leaves_of(self.layers)
        self._weights = lm_inputs.params_from_seed(self.layers, self.glob,
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
            return loss_sum(self.layers, precision, params, data, label,
                            self.rows_used)
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        self._grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
        self._init = jax.jit(self._start)
        self._tokens = jax.jit(lambda key, batch_id: lm_inputs.make_tokens(
            key, batch_id, self.rows, self.seq_len, self.vocab))
        self._update = jax.jit(self._apply, donate_argnums=(0, 1, 2))
        self._norms_of = jax.jit(_norms)
        self._change = jax.jit(lambda new, key: _norms(
            jax.tree.map(jnp.subtract, new, self._weights(key))))

    def _start(self, key):
        params = self._weights(key)
        zeros = jax.tree.map(jnp.zeros_like, params)
        return params, zeros, jax.tree.map(jnp.zeros_like, params)

    def _apply(self, params, m1, m2, grads, step):
        t = jnp.asarray(step, jnp.float32) + 1.0
        new_p, new_m1, new_m2 = {}, {}, {}
        for n, d in params.items():
            new_p[n], new_m1[n], new_m2[n] = {}, {}, {}
            for tag, w in d.items():
                h = self.hyper[n][tag]
                g = grads[n][tag] / float(self.rows_used)
                a = h["beta1"] * m1[n][tag] + (1.0 - h["beta1"]) * g
                b = h["beta2"] * m2[n][tag] \
                    + (1.0 - h["beta2"]) * jnp.square(g)
                ahat = a / (1.0 - jnp.power(h["beta1"], t))
                bhat = b / (1.0 - jnp.power(h["beta2"], t))
                new_p[n][tag] = w - h["lr"] * (
                    ahat / (jnp.sqrt(bhat) + h["eps"]) + h["wd"] * w)
                new_m1[n][tag], new_m2[n][tag] = a, b
        return new_p, new_m1, new_m2

    @classmethod
    def for_config(cls, conf_text: str, cfg: dict, batch: int, **kw):
        """The reference of one configuration file at one global batch
        (``batch`` counts tokens, as ``batch_per_chip`` does)."""
        return cls(conf_text, cfg, batch, **kw)

    def run(self, seed: int, n_steps: int = 3) -> dict:
        key = seed_key(seed)
        params, m1, m2 = self._init(key)
        losses, grad_norms, pairs_held = [], None, {}
        with jax.default_matmul_precision("highest"):
            for step in range(n_steps):
                data, label = self._tokens(key, step % 2)
                (total, pairs), grads = self._grad(params, data, label)
                losses.append(float(total) / self.rows_used)
                for name, n in jax.device_get(pairs).items():
                    pairs_held.setdefault(name, []).append(int(n))
                if step == 0:
                    grad_norms = {
                        n: float(v) / self.rows_used for n, v in
                        jax.device_get(self._norms_of(grads)).items()}
                params, m1, m2 = self._update(params, m1, m2, grads, step)
                del grads
            change = jax.device_get(self._change(params, key))
        return {"loss": losses, "grad_norm": grad_norms,
                "change_norm": {n: float(v) for n, v in change.items()},
                "pairs_held": pairs_held}


# what a window kind asks of a reference's file
for_config = Reference.for_config


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained token of this configuration."""
    return lm_flops.train_flops_per_item(conf_text, cfg["seq_len"])


def kernel_work(conf_text: str, cfg: dict, name: str, ctx: dict):
    """FLOPs and bytes one training step of this configuration needs of the
    named kernel, all layers that run it summed: the model's operations and
    the bytes it cannot avoid, the forward pass three times over, nothing
    made again counted. ``flash_attention``: every attention layer's core by
    its mask. ``expert_product``: every ``moe`` layer's grouped products
    over the pairs its experts here hold on the run's two resident batches,
    as this reference counted them in its own first two steps
    (``ctx["want"]["pairs_held"]``, the mean of the two: the window
    alternates them; a recipe under which the routing drifts through a run
    makes this the count of the run's start, PERF.md section 6), and over
    even routing's where no reference has run; ``pairs_a_step`` says how
    many that was. Nothing the program says of itself is counted. Nothing
    for a name not known here."""
    layers, _ = netconf.parse(conf_text)
    seq = cfg["seq_len"]
    rows = cfg["batch_per_chip"] // seq
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    said = {}
    if name == "flash_attention":
        each = rows                     # a sequence at a time
        works = [lm_flops.flash_attention(seq, a["nh"], a["nkv"], a["dh"],
                                          a["window"])
                 for a in (lm_flops._dims(lay, d) for lay in layers
                           if lay.type == "attention")]
    elif name == "expert_product":
        each = 1                        # the pairs are the whole step's
        counted = (ctx.get("want") or {}).get("pairs_held") or {}
        works, said["pairs_a_step"] = [], 0.0
        for lay in (lay for lay in layers if lay.type == "moe"):
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            by_step = counted.get(lay.name, [])[:2]
            pairs = sum(by_step) / len(by_step) if by_step \
                else rows * seq * (k or e) * held / e
            mats = 3 if lay.params.get("expert_act") == "reglu" else 1
            works.append(lm_flops.expert_product(
                pairs, d, lay.geti("nhidden"), held, mats))
            said["pairs_a_step"] += pairs
    else:
        return None
    return dict(said, **{key: 3.0 * each * sum(w[key] for w in works)
                         for key in ("flops", "bytes")})
