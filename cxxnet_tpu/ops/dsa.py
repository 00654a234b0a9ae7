"""Learned sparse attention's indexer (DeepSeek-Sparse-Attention): the
index scores, the exact selection, and the loss the indexer learns from.

For queries t and keys s of one sequence, J index heads of ``di`` features
on ONE key head:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          float32
    S_t     = the min(t + 1, k) keys s <= t of largest I[t, s], in the
              order of ``order_key``: the floats' own, with -0.0 read as
              +0.0, a NaN beyond the infinity of its sign, and equal
              scores to the lower s first
    L_idx   = (1 / L) sum_t KL(p[t, .] || softmax_{S_t}(I[t, .]))

with p the main attention's probabilities on S_t, summed over its heads and
detached (``ops.flash_attn.selected_probs``).

``index_scores`` keeps no (J, L, L) array, forward or backward: both walk
the queries a block at a time and the backward makes a block's products
again from qI, kI and w. On a TPU the layer takes the backward from one
kernel that visits the causal tiles alone (``fused``,
``ops/dsa_index_pallas.py``): it reads the scores' gradient on s <= t
only, which holds for the layer's, ``index_loss``'s, nought above the
diagonal; the lines here are the path elsewhere and that kernel's golden
model. ``select`` is exact: the set ``lax.top_k`` gives
on rows whose ``topk``-th value is no signed zero, written as an int8
(b, L, L) array, which is what the flash kernels stream
(``flash_attention_selected``). On a TPU the layer takes the same array
from one kernel that sorts nothing (``ops/dsa_select_pallas.py``); the
lines here are the path elsewhere and that kernel's golden model.
``index_loss`` keeps one (b, L, L) float32 residual, its own gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 512           # queries a pass of the index scores takes
_INT_MIN = jnp.iinfo(jnp.int32).min


def _blocks(L: int) -> int:
    """The queries a pass takes: QUERY_BLOCK where it divides L, else all."""
    return QUERY_BLOCK if L % QUERY_BLOCK == 0 else L


def _products(qb, k):
    """(b, J, B, di) x (b, L, di) -> (b, J, B, L) float32."""
    return jnp.einsum("bjtd,bsd->bjts", qb, k,
                      preferred_element_type=jnp.float32)


def _by_block(x, axis: int, blk: int):
    """``x`` with ``axis`` split into (blocks, blk) and the blocks first."""
    shape = x.shape[:axis] + (x.shape[axis] // blk, blk) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def index_scores(qi, ki, w, fused=False):
    """``qi`` (b, J, L, di), ``ki`` (b, L, di), ``w`` (b, L, J) float32 ->
    I (b, L, L) float32, every pair (the caller keeps s <= t). With
    ``fused`` (the caller gates on ``ops.dsa_index_bwd_supported``) the
    backward is ``ops.dsa_index_bwd``'s kernel, the gradient of the scores
    on s <= t alone: the gradient it is handed must be nought above the
    diagonal, as ``index_loss``'s is."""
    return _scores_fwd(qi, ki, w, fused)[0]


def _scores_fwd(qi, ki, w, fused):
    L = qi.shape[2]
    blk = _blocks(L)

    def block(args):
        qb, wb = args
        z = jnp.maximum(_products(qb, ki), 0.0)
        return jnp.einsum("bjts,btj->bts", z, wb)
    out = lax.map(block, (_by_block(qi, 2, blk), _by_block(w, 1, blk)))
    out = jnp.moveaxis(out, 0, 1).reshape(qi.shape[0], L, L)
    return out, (qi, ki, w)


def _scores_bwd(fused, res, g):
    if fused:
        from . import dsa_index_bwd
        return dsa_index_bwd(*res, g)
    qi, ki, w = res
    b, J, L, di = qi.shape
    blk = _blocks(L)

    def block(dk, args):
        qb, wb, gb = args                       # gb: (b, B, L)
        z = _products(qb, ki)
        dwb = jnp.einsum("bjts,bts->btj", jnp.maximum(z, 0.0), gb)
        # the products' operands in the compute type, as the MXU takes them
        dz = jnp.where(z > 0.0, gb[:, None] * wb.transpose(0, 2, 1)[..., None],
                       0.0).astype(qb.dtype)
        dqb = jnp.einsum("bjts,bsd->bjtd", dz, ki,
                         preferred_element_type=jnp.float32)
        dk = dk + jnp.einsum("bjts,bjtd->bsd", dz, qb,
                             preferred_element_type=jnp.float32)
        return dk, (dqb, dwb)
    dk, (dq, dw) = lax.scan(
        block, jnp.zeros(ki.shape, jnp.float32),
        (_by_block(qi, 2, blk), _by_block(w, 1, blk), _by_block(g, 1, blk)))
    dq = jnp.moveaxis(dq, 0, 2).reshape(b, J, L, di)
    dw = jnp.moveaxis(dw, 0, 1).reshape(b, L, J)
    return dq.astype(qi.dtype), dk.astype(ki.dtype), dw.astype(w.dtype)


index_scores.defvjp(_scores_fwd, _scores_bwd)


def order_key(scores):
    """Float32 scores as int32 keys of the same order, the selection's:
    the bits of a float that is not negative order as they stand, those of
    a negative one in reverse. ``-0.0`` becomes ``+0.0`` first, by a select
    on the bits (``top_k`` on the floats puts it below; an ``x + 0.0`` a
    simplifier may drop). A total order: the NaNs stand beyond the
    infinity of their sign."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits == _INT_MIN, 0, bits)
    return bits ^ ((bits >> 31) & 0x7fffffff)


def select(scores, topk: int):
    """``scores`` (b, L, L) float32 -> int8 (b, L, L): 1 on the
    min(t + 1, topk) keys s <= t of largest score in row t by
    ``order_key``, equal scores to the lower s. Exact, and that many on
    every row whatever its values: ``lax.top_k`` over the keys finds the
    row's ``topk``-th and the last index it took at that value, and a key
    is kept where it is greater, or equal and its index no greater. Rows
    t < topk keep every key at or before them and are not sorted."""
    L = scores.shape[-1]
    k = min(topk, L)
    t = jnp.arange(L, dtype=jnp.int32)[:, None]
    s = jnp.arange(L, dtype=jnp.int32)[None, :]
    causal = s <= t
    if k == L:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    # the rows that choose
    key = jnp.where(causal[k:], order_key(scores[:, k:]), _INT_MIN)
    vals, idx = lax.top_k(key, k)
    thr = vals[..., -1:]
    last = jnp.max(jnp.where(vals == thr, idx.astype(jnp.int32), -1),
                   axis=-1, keepdims=True)
    chosen = (key > thr) | ((key == thr) & (s <= last))
    keep = jnp.concatenate(
        [jnp.broadcast_to(causal[:k], scores[:, :k].shape),
         causal[k:] & chosen], axis=1)
    return keep.astype(jnp.int8)


def kept_scores(L: int, topk: int) -> int:
    """Query-key pairs a selection keeps in one sequence:
    sum_t min(t + 1, topk)."""
    k = min(topk, L)
    return k * (k + 1) // 2 + (L - k) * k


@jax.custom_vjp
def index_loss(scores, sel, p):
    """sum_t KL(p[t, .] || softmax_{S_t}(scores[t, .])) of each sequence,
    (b,) float32: ``sel`` int8 marks S_t, ``p`` (b, L, L) is nought
    outside it and sums to 1 over it. Differentiable in ``scores`` alone."""
    return _loss_fwd(scores, sel, p)[0]


def _loss_fwd(scores, sel, p):
    keep = sel != 0
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    live = keep & (p > 0.0)
    term = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                - jnp.where(live, logq, 0.0)), 0.0)
    # d/dI of -sum_s p log q with sum_s p = 1 over the kept keys
    grad = jnp.where(keep, jnp.exp(logq) * jnp.sum(p, -1, keepdims=True) - p,
                     0.0)
    return jnp.sum(term, axis=(1, 2)), grad


def _loss_bwd(grad, g):
    return g[:, None, None] * grad, None, None


index_loss.defvjp(_loss_fwd, _loss_bwd)


def selected_probs_plain(q, k, sel, scale):
    """``flash_attn.selected_probs`` and the attention's own output in
    plain lines, for the dense path: the (b, nkv, g, L, L) probabilities
    whole. Returns (probabilities, their mean over the heads)."""
    b, nh, L, d = q.shape
    nkv = k.shape[1]
    qg = q.reshape(b, nkv, nh // nkv, L, d)
    s = jnp.einsum("bngqd,bnkd->bngqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    a = jax.nn.softmax(jnp.where((sel != 0)[:, None, None], s, -jnp.inf), -1)
    return a, jnp.mean(a, axis=(1, 2))
