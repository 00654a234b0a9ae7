"""Flash kernels for the ring-attention step (sequence parallelism).

The XLA ring step (parallel/ring.py _ring_attention_local) keeps memory
O(chunk*skv) but still round-trips its score tiles through HBM between
the two einsums. These kernels run one ring step's online-softmax update
entirely in VMEM, mirroring the single-chip flash kernel
(ops/flash_attn.py) with two differences:

* the (m, l, acc) softmax state is a CARRY: initialized from the previous
  ring step's values (input_output_aliased, accumulated in the revisited
  output window) instead of from (-inf, 0, 0);
* the causal mask uses DYNAMIC global offsets — at ring step t a device
  holds the K/V block of device (idx - t) mod n, so the query/key global
  positions are traced values, streamed in through SMEM. Fully-masked
  tiles therefore cannot be skipped statically; their probability mass is
  zeroed explicitly (the finite NEG_INF stand-in makes exp() NaN-free).

The backward kernels compute one ring step's dq and (dk, dv) block
contributions from the saved per-row logsumexp, FlashAttention-2 style;
parallel/ring.py accumulates dq locally and rotates (dk, dv) with their
K/V block so each block arrives home with every device's contribution.

Validated in interpret mode on CPU against the dense reference
(tests/test_ring_flash.py) and compiled on the chip by
tools/check_tpu_kernels.py. Taken wherever Pallas runs and the shape
tiles: parallel/ring.py _ring_flash_enabled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from .flash_attn import NEG_INF, _dims


def _pick_block(L: int, target: int = 256) -> int:
    """Sequence tile: lane-aligned (multiple of 128) so the (bq, bk) score
    tile maps onto the MXU cleanly; ring shards are uniform, so an exact
    divisor always exists (supports())."""
    for b in (target, 128):
        if L % b == 0:
            return b
    return target if L >= target else 128


def supports(sq: int, skv: int, d: int) -> bool:
    """Ring-step kernel constraints: lane-aligned local sequence blocks
    (no padding path — ring shards are uniform) and sublane-aligned d."""
    return (pltpu is not None and sq >= 128 and sq % 128 == 0
            and skv >= 128 and skv % 128 == 0 and d % 8 == 0)


def _causal_keep(off_ref, q_blk, kv_blk, block_q, block_k, window=0):
    qpos = off_ref[0] + q_blk * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = off_ref[1] + kv_blk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = qpos >= kpos
    if window > 0:
        keep = jnp.logical_and(keep, qpos - kpos < window)
    return keep


def _tile_needed(off_ref, q_blk, kv_blk, block_q, block_k, causal,
                 window=0):
    """Traced tile-level skip predicate (offsets are dynamic): False when
    the tile is entirely above the causal diagonal or entirely older than
    the sliding window — its matmuls are skipped wholesale, which under a
    causal ring drops roughly half the ring steps' compute."""
    if not causal:
        return True
    q_start = off_ref[0] + q_blk * block_q
    k_start = off_ref[1] + kv_blk * block_k
    need = k_start <= q_start + (block_q - 1)
    if window > 0:
        need = jnp.logical_and(
            need, q_start - (k_start + block_k - 1) < window)
    return need


def _fwd_step_kernel(off_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                     m_out, l_out, acc_out, *, scale, causal,
                     block_q, block_k, window=0):
    kv_i = pl.program_id(2)
    q_blk = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _():
        # the (g, i) output window is revisited across the sequential kv
        # steps — it IS the accumulator; seed it with the ring carry
        m_out[...] = m_in[...]
        l_out[...] = l_in[...]
        acc_out[...] = acc_in[...]

    @pl.when(_tile_needed(off_ref, q_blk, kv_i, block_q, block_k,
                          causal, window))
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk) f32
        if causal:
            s = jnp.where(_causal_keep(off_ref, q_blk, kv_i, block_q,
                                       block_k, window), s, NEG_INF)
        m_prev = m_out[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            # partially-masked rows whose m is still NEG_INF would get
            # exp(0) mass on masked entries; kill it explicitly
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        m_out[0] = m_new
        l_out[0] = l_out[0] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_out[0] = acc_out[0] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def _dq_step_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dq_in, dq_out, *, scale, causal,
                    block_q, block_k, window=0):
    kv_i = pl.program_id(2)
    q_blk = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _():
        dq_out[...] = dq_in[...]

    @pl.when(_tile_needed(off_ref, q_blk, kv_i, block_q, block_k,
                          causal, window))
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_keep(off_ref, q_blk, kv_i, block_q,
                                       block_k, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])        # masked: exp(-1e30 - lse) == 0
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_out[0] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)


def _dkv_step_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dk_in, dv_in, dk_out, dv_out,
                     *, scale, causal, block_q, block_k, window=0):
    q_i = pl.program_id(2)
    kv_blk = pl.program_id(1)

    @pl.when(q_i == 0)
    def _():
        dk_out[...] = dk_in[...]
        dv_out[...] = dv_in[...]

    @pl.when(_tile_needed(off_ref, q_i, kv_blk, block_q, block_k,
                          causal, window))
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if causal:
            s = jnp.where(_causal_keep(off_ref, q_i, kv_blk, block_q,
                                       block_k, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dv_out[0] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_out[0] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM) if pltpu is not None \
        else pl.BlockSpec(memory_space=None)


def fwd_step(q, k_blk, v_blk, m, l, acc, offs, *, causal, scale,
             interpret, window=0):
    """One ring step's online-softmax update.

    q: (bh, sq, d); k_blk/v_blk: (bh, skv, d); m/l: (bh, sq, 1) f32;
    acc: (bh, sq, d) f32; offs: (2,) int32 [q_global_off, kv_global_off].
    Returns updated (m, l, acc)."""
    bh, sq, d = q.shape
    skv = k_blk.shape[1]
    bq, bk = _pick_block(sq), _pick_block(skv)
    kern = functools.partial(_fwd_step_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, window=window)
    q_spec = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0))
    m_spec = pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, i, 0))
    acc_spec = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, skv // bk),
        in_specs=[_smem_spec(), q_spec, kv_spec, kv_spec,
                  m_spec, m_spec, acc_spec],
        out_specs=[m_spec, m_spec, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=None if interpret else _dims(),
        interpret=interpret,
    )(offs, q, k_blk, v_blk, m, l, acc)


def dq_step(q, k_blk, v_blk, do, lse, delta, dq, offs, *, causal, scale,
            interpret, window=0):
    """Accumulate one ring step's dq contribution into ``dq`` (f32)."""
    bh, sq, d = q.shape
    skv = k_blk.shape[1]
    bq, bk = _pick_block(sq), _pick_block(skv)
    kern = functools.partial(_dq_step_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, window=window)
    q_spec = pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0))
    r_spec = pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, i, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, skv // bk),
        in_specs=[_smem_spec(), q_spec, kv_spec, kv_spec, q_spec,
                  r_spec, r_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        input_output_aliases={7: 0},
        compiler_params=None if interpret else _dims(),
        interpret=interpret,
    )(offs, q, k_blk, v_blk, do, lse, delta, dq)


def dkv_step(q, k_blk, v_blk, do, lse, delta, dk, dv, offs, *, causal,
             scale, interpret, window=0):
    """Accumulate one ring step's (dk, dv) contributions for the rotating
    K/V block into ``dk``/``dv`` (f32, travel with the block)."""
    bh, sq, d = q.shape
    skv = k_blk.shape[1]
    bq, bk = _pick_block(sq), _pick_block(skv)
    kern = functools.partial(_dkv_step_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, window=window)
    # grid: kv tile resident (dim 1), q tiles stream (dim 2)
    q_spec = pl.BlockSpec((1, bq, d), lambda g, j, i: (g, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda g, j, i: (g, j, 0))
    r_spec = pl.BlockSpec((1, bq, 1), lambda g, j, i: (g, i, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, skv // bk, sq // bq),
        in_specs=[_smem_spec(), q_spec, kv_spec, kv_spec, q_spec,
                  r_spec, r_spec, kv_spec, kv_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, skv, d), jnp.float32),
        ],
        input_output_aliases={7: 0, 8: 1},
        compiler_params=None if interpret else _dims(),
        interpret=interpret,
    )(offs, q, k_blk, v_blk, do, lse, delta, dk, dv)
