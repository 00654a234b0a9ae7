"""Reference ``sdar_moe``, as a configuration's ``"reference"`` key names it:
one training step of a mixture-of-experts language model trained by
diffusion over blocks (block diffusion language models, arXiv:2503.09573;
the SDAR recipe), in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision. It reads the conf text through
``netconf.parse``, makes its weights, tokens and noise from the seed
(``bd_inputs``), and imports nothing of the program under test.

A sequence of L tokens x_0 runs as 2 L rows: rows 0..L-1 the noised copy
x_t, rows L..2L-1 the clean copy x_0. Row r stands at position p(r) = r mod
L, in block b(r) = p(r) // B. With x the residual stream entering a block:

    h  = rmsnorm(x; g1)                 x * rsqrt(mean(x^2) + eps) * g1
    q, k, v = h W_q, h W_k, h W_v       nhead / nkvhead heads of head_dim
    q, k = rmsnorm(q; g_q), rmsnorm(k; g_k)   over the head's features, one
                                        gain vector each, shared by the heads
    q, k = rope(q), rope(k)             at p(r); whole head, half-split pairs
    a  = softmax(q k^T / sqrt(dh) + M) v      query head j reads key-value
                                        head j // group; M keeps (r, c) where
        r, c noised:  b(r) == b(c)      r noised, c clean:  b(c) <  b(r)
        r clean, c noised:  never       r, c clean:         b(c) <= b(r)
    x' = x + a W_o
    u  = rmsnorm(x'; g2)
    S  = top-k of u W_r; w = softmax((u W_r)[S])
    y  = sum_{e in S, e held} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
    out = x' + y

then a last rmsnorm and the untied head on rows 0..L-1 ALONE, and

    loss = (1 / L) sum_i weight_i CE(logits_i, x_0,i)

with weight_i = 1 / t of position i's block where x_t,i is the mask token
and 0 elsewhere; row i predicts token i, no shift. No kernels, no sort, no
grouped product: the full (2 L, 2 L) masked scores a block of queries at a
time, and EVERY expert held applied to EVERY row (a block of rows at a
time), the routing weights masking the sum. What experts that are not held
would add is left out, as in the program (one chip's share of an
expert-parallel layer). Then AdamW by hand on float32 weights:

    m <- b1 m + (1 - b1) g;  v <- b2 v + (1 - b2) g^2
    w <- w - lr (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd w)

``precision`` other than ``highest`` gives the control (every operand of a
matrix product, and every gradient that comes back into one, rounded first,
by ``convnet``'s rules). ``rows_used`` below the batch leaves the positions
past it out of the loss, half of the masked tokens at half the batch; and
``mask = "causal"`` (plain causal over the 2 L rows) or ``wrap = False``
(row r at position r) plant the two faults a block-diffusion step can have
of its own.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import bd_inputs, lm_flops, lm_inputs, netconf
from benchmark.inputs import seed_key
from benchmark.references.convnet import (PRECISIONS, _round_cotangent,
                                          _round_operand)

_HIGHEST = lax.Precision.HIGHEST
_QUERY_BLOCK = 512          # queries a pass of the scores takes
_ROW_BLOCK = 4096           # rows a pass of the experts takes


def _mm(precision, a, b):
    return _round_cotangent(
        jnp.matmul(_round_operand(a, precision), _round_operand(b, precision),
                   precision=_HIGHEST), precision)


def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def _rope(x, pos, base):
    """(heads, T, dh) at positions ``pos`` (T,): rotate the (first half,
    second half) pairs."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.power(
        base, -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def keep(rows_q, rows_k, half: int, block_len: int):
    """(queries, keys) bool for row indices ``rows_q`` (n, 1) and
    ``rows_k`` (1, m) of the 2 ``half`` rows: the scores the mask keeps."""
    bq, bk = (rows_q % half) // block_len, (rows_k % half) // block_len
    nq, nk = rows_q < half, rows_k < half
    return jnp.where(nq & nk, bq == bk,
                     jnp.where(nq, bk < bq, ~nk & (bk <= bq)))


def _attention(lay, precision, w, h, mask: str, wrap: bool):
    """One sequence's 2 L rows (T, d) through one attention layer."""
    T, d = h.shape
    nh = lay.geti("nhead")
    dh = lay.geti("head_dim") or d // nh
    nkv = lay.geti("nkvhead") or nh
    B = lay.geti("block_len")
    if lay.params.get("attn_mask") != "blockdiff" or not B:
        raise netconf.ConfError("%s: the reference knows the block-"
                                "diffusion mask alone" % lay.name)
    qkv = _mm(precision, h, w["wmat"])
    split = lambda t, n: t.reshape(T, n, dh).transpose(1, 0, 2)  # noqa: E731
    q = split(qkv[:, :nh * dh], nh)
    k = split(qkv[:, nh * dh:(nh + nkv) * dh], nkv)
    v = split(qkv[:, (nh + nkv) * dh:], nkv)
    if lay.geti("qk_norm"):
        q, k = _rmsnorm(q, w["qnorm"], 1e-6), _rmsnorm(k, w["knorm"], 1e-6)
    if lay.geti("rope"):
        pos = jnp.arange(T) % (T // 2) if wrap else jnp.arange(T)
        base = lay.getf("rope_base", 10000.0)
        q, k = _rope(q, pos, base), _rope(k, pos, base)
    blk = min(T, _QUERY_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))
    rows_k = jnp.arange(T)[None, :]

    def block(args):
        i, qi = args                          # qi: (nkv, group, blk, dh)
        s = _mm(precision, qi, k[:, None].swapaxes(-1, -2)) * dh ** -0.5
        rows_q = i * blk + jnp.arange(blk)[:, None]
        kept = rows_q >= rows_k if mask == "causal" \
            else keep(rows_q, rows_k, T // 2, B)
        p = jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1)
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


def moe(lay, precision, w, u):
    """One sequence's rows (T, d) through the experts held, all of them on
    all rows, a block of rows at a time; and the number of (row, expert)
    pairs whose expert is held here: the rows a sparse lowering of this
    layer has work on."""
    T, d = u.shape
    held, _, f = w["wmat"].shape
    lo = lay.geti("expert_offset")
    if lay.params.get("expert_act") != "swiglu":
        raise netconf.ConfError("%s: the reference knows swiglu experts "
                                "alone" % lay.name)
    wide = lambda m: m.transpose(1, 0, 2).reshape(d, held * f)  # noqa: E731
    blk = min(T, _ROW_BLOCK)
    if T % blk:
        raise netconf.ConfError("%d rows are no multiple of %d" % (T, blk))

    def block(ub):
        probs = route(lay, _mm(precision, ub, w["gate"].T))[:, lo:lo + held]
        a = jax.nn.silu(_mm(precision, ub, wide(w["wmat"]))) \
            * _mm(precision, ub, wide(w["up"]))
        y = _mm(precision, a * jnp.repeat(probs, f, axis=1),
                w["down"].reshape(held * f, d))
        return y, jnp.sum(probs > 0)
    y, pairs = lax.map(jax.checkpoint(block), u.reshape(T // blk, blk, d))
    return y.reshape(T, d), jnp.sum(pairs)


def apply_layers(layers, precision, params, vals, pairs, mask, wrap):
    """Apply ``layers`` in order to the node values ``vals`` of one
    sequence (name -> array; node "0" holds the 2 L ids), in place; each
    ``moe`` layer's pairs held go to ``pairs`` by the layer's name."""
    for lay in layers:
        w = params.get(lay.name)
        a = vals[lay.ins[0]]
        if lay.type == "embed":
            out = w["wmat"][a]
        elif lay.type == "rmsnorm":
            # ``seq_rows``: the first rows alone go on (the noised copy)
            a = a[:lay.geti("seq_rows") or a.shape[0]]
            out = _rmsnorm(a, w["gain"], lay.getf("eps", 1e-6))
        elif lay.type == "attention":
            out = _attention(lay, precision, w, a, mask, wrap)
        elif lay.type == "add":
            out = sum(vals[n] for n in lay.ins)
        elif lay.type == "moe":
            if len(lay.ins) != 1:
                raise netconf.ConfError("%s: the router reads the experts' "
                                        "own input here" % lay.name)
            out, pairs[lay.name] = moe(lay, precision, w, a)
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


def forward(layers, precision, params, ids, mask="blockdiff", wrap=True):
    """One sequence's 2 L ids -> logits (L, vocab) and {``moe`` layer: the
    pairs its experts here held}. Each piece runs under ``jax.checkpoint``:
    what stays alive across a cut is the residual stream."""
    vals, pairs = {"0": ids}, {}
    for piece in _pieces(layers):
        need = {n: vals[n] for lay in piece for n in lay.ins if n in vals}
        last = piece[-1].outs[0]

        def run(p, xs, piece=piece, last=last):
            held = {}
            out = apply_layers(piece, precision, p, dict(xs), held, mask,
                               wrap)[last]
            return out, held
        out, held = jax.checkpoint(run)(params, need)
        vals = {last: out}
        pairs.update(held)
    return vals[layers[-1].outs[0]], pairs


def loss_mean(layers, precision, params, data, label, rows_used,
              mask="blockdiff", wrap=True):
    """The batch's loss as the model states it, summed over its rows (the
    program divides by the rows of its batch: one here), positions from
    ``rows_used`` on left out; and ``forward``'s pairs held summed over
    the rows. ``data`` (rows, 1, 1, 2 L) and ``label`` (rows, 2 L): x_0,
    then the loss weights, as the program gets them."""
    rows, L2 = label.shape
    L = L2 // 2

    def one(ids, x0, weight, used):
        logits, pairs = forward(layers, precision, params, ids, mask, wrap)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, x0[:, None].astype(jnp.int32),
                                  axis=1)[:, 0]
        return jnp.sum(jnp.where(used, weight * ce, 0.0)) / L, pairs
    ids = data.reshape(rows, L2).astype(jnp.int32)
    used = (jnp.arange(rows * L) < rows_used).reshape(rows, L)
    loss, pairs = jax.vmap(one)(ids, label[:, :L], label[:, L:], used)
    return jnp.sum(loss) / rows, jax.tree.map(jnp.sum, pairs)


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
                 precision: str = "highest", rows_used: int = 0,
                 mask: str = "blockdiff", wrap: bool = True):
        if precision not in PRECISIONS:
            raise ValueError("precision %r" % precision)
        self.layers, self.glob = netconf.parse(conf_text)
        seq_len = cfg["seq_len"]
        if batch % seq_len:
            raise ValueError("batch of %d tokens is no whole number of "
                             "sequences of %d" % (batch, seq_len))
        self.seq_len, self.rows = seq_len, batch // seq_len
        self.batch = batch                         # tokens of x_0 a step
        self.rows_used = rows_used or batch
        self.vocab = lm_inputs.vocab_of(self.layers)
        self.leaves = bd_inputs.leaves_of(self.layers)
        self._weights = bd_inputs.params_from_seed(self.layers, self.glob,
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
                             self.rows_used, mask, wrap)
        # few programs, each whole: every one is a load from the compile
        # cache in every run of every later check
        self._grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
        self._init = jax.jit(self._start)
        self._batch = jax.jit(lambda key, batch_id: bd_inputs.make_batch(
            key, batch_id, self.rows, self.seq_len, self.vocab, cfg))
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
                h, g = self.hyper[n][tag], grads[n][tag]
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
        (``batch`` counts tokens of x_0, as ``batch_per_chip`` does)."""
        return cls(conf_text, cfg, batch, **kw)

    def run(self, seed: int, n_steps: int = 3) -> dict:
        key = seed_key(seed)
        params, m1, m2 = self._init(key)
        losses, grad_norms, pairs_held = [], None, {}
        with jax.default_matmul_precision("highest"):
            for step in range(n_steps):
                data, label = self._batch(key, step % 2)
                (loss, pairs), grads = self._grad(params, data, label)
                losses.append(float(loss))
                for name, n in jax.device_get(pairs).items():
                    pairs_held.setdefault(name, []).append(int(n))
                if step == 0:
                    grad_norms = {n: float(v) for n, v in jax.device_get(
                        self._norms_of(grads)).items()}
                params, m1, m2 = self._update(params, m1, m2, grads, step)
                del grads
            change = jax.device_get(self._change(params, key))
        return {"loss": losses, "grad_norm": grad_norms,
                "change_norm": {n: float(v) for n, v in change.items()},
                "pairs_held": pairs_held}


# what a window kind asks of a reference's file
for_config = Reference.for_config


def kept_scores(seq_len: int, block_len: int) -> float:
    """Scores the mask keeps, a head: with nb blocks of B positions, the
    clean copy's block-causal part B^2 nb (nb + 1) / 2, the noised
    queries' clean keys B^2 nb (nb - 1) / 2, and the noised copy's block
    diagonal nb B^2: L^2 + L B."""
    nb, b2 = seq_len // block_len, float(block_len) ** 2
    return b2 * nb * (nb + 1) / 2 + b2 * nb * (nb - 1) / 2 + nb * b2


def _model(conf_text: str):
    layers, _ = netconf.parse(conf_text)
    d = next(lay.geti("nhidden") for lay in layers if lay.type == "embed")
    return layers, d


def forward_macs(conf_text: str, seq_len: int):
    """(layer name, part, multiply-adds of ONE SEQUENCE's forward pass):
    2 L rows through every projection, router and expert product (the
    experts at even routing: ``top_k * nexpert_held / nexpert`` pairs a
    row), the scores the mask keeps, the head on L rows."""
    layers, d = _model(conf_text)
    out, rows = [], 2 * seq_len
    for lay in layers:
        if lay.type == "attention":
            a = lm_flops._dims(lay, d)
            q, kv = a["nh"] * a["dh"], a["nkv"] * a["dh"]
            out.append((lay.name, "qkv", rows * d * (q + 2 * kv)))
            out.append((lay.name, "core", 2 * q * kept_scores(
                seq_len, lay.geti("block_len"))))
            out.append((lay.name, "out", rows * q * d))
        elif lay.type == "moe":
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            out.append((lay.name, "route", rows * d * e))
            out.append((lay.name, "experts", rows * (k or e) * held / e
                        * 3 * d * lay.geti("nhidden")))
        elif lay.type == "conv":
            out.append((lay.name, "head",
                        seq_len * d * lay.geti("nchannel")))
    return out


def train_flops_per_item(conf_text: str, cfg: dict) -> float:
    """Model FLOPs of one trained token (one of the L tokens of x_0; both
    of its rows' work is its): forward once, backward twice."""
    seq = cfg["seq_len"]
    return 3.0 * 2.0 * sum(m for _, _, m in forward_macs(conf_text, seq)) \
        / seq


def kernel_work(conf_text: str, cfg: dict, name: str, ctx: dict):
    """FLOPs and bytes one training step of this configuration needs of the
    named kernel, all layers that run it summed: the model's operations and
    the bytes it cannot avoid, the forward pass three times over, nothing
    made again counted. ``flash_attention``: every attention layer's core
    by the scores its mask keeps (4 head_dim FLOPs a kept score a head; q,
    k, v, the output and their gradients moved once). ``expert_product``:
    every ``moe`` layer's products over the pairs its experts here hold on
    the run's two resident batches, as this reference counted them in its
    own first two steps (``ctx["want"]["pairs_held"]``, their mean; even
    routing's where no reference has run); ``pairs_a_step`` says how many
    that was. Nothing the program says of itself is counted. Nothing for a
    name not known here."""
    layers, d = _model(conf_text)
    seq = cfg["seq_len"]
    seqs = cfg["batch_per_chip"] // seq
    said = {}
    if name == "flash_attention":
        flops = bytes_ = 0.0
        for lay in (lay for lay in layers if lay.type == "attention"):
            a = lm_flops._dims(lay, d)
            flops += 3.0 * seqs * 4.0 * a["nh"] * a["dh"] * kept_scores(
                seq, lay.geti("block_len"))
            bytes_ += 2.0 * seqs * 2 * (2 * seq) * a["dh"] * (
                2 * a["nh"] + 2 * a["nkv"])
        return {"flops": flops, "bytes": bytes_}
    if name == "expert_product":
        counted = (ctx.get("want") or {}).get("pairs_held") or {}
        works, said["pairs_a_step"] = [], 0.0
        for lay in (lay for lay in layers if lay.type == "moe"):
            e, k = lay.geti("nexpert"), lay.geti("top_k")
            held = lay.geti("nexpert_held") or e
            by_step = counted.get(lay.name, [])[:2]
            pairs = sum(by_step) / len(by_step) if by_step \
                else seqs * 2 * seq * (k or e) * held / e
            works.append(lm_flops.expert_product(
                pairs, d, lay.geti("nhidden"), held, 3))
            said["pairs_a_step"] += pairs
        return dict(said, **{key: 3.0 * sum(w[key] for w in works)
                             for key in ("flops", "bytes")})
    return None
