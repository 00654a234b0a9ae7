"""Sequence / context parallelism: ring attention and Ulysses all-to-all.

The reference framework is a 2014 CNN trainer with no sequence axis
(SURVEY.md §5 "Long-context: ABSENT"), so this module is green-field
TPU-first design: long sequences are sharded over a mesh ``sp`` axis and
attention runs either as

* **ring attention** — K/V blocks rotate around the ICI ring via ppermute
  while each device keeps its local Q block and accumulates the softmax
  online (numerically stable log-sum-exp carry). Comm per step is one
  neighbor hop, fully overlappable with the block matmul; memory is
  O(seq/n_devices) per device, enabling sequences that don't fit one chip.
* **Ulysses** — one all-to-all swaps sequence sharding for head sharding,
  attention runs dense locally, and a second all-to-all swaps back. Cheaper
  at moderate sequence lengths when heads >= devices.

Everything is expressed with shard_map + lax collectives so XLA schedules
the ICI transfers; the scan over ring steps is reverse-differentiable
(ppermute has a transpose rule), so the same code serves training.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collectives
from ._compat import shard_map


def attention_reference(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None, window: int = 0,
                        q_offset=0, block_len: int = 0):
    """Plain single-device attention, the golden model for the parallel
    variants. q: (batch, heads, seq, head_dim); k/v may carry FEWER heads
    (grouped-query attention): nkv must divide nh and each group of
    nh/nkv query heads attends to one shared k/v head — no materialized
    broadcast. window > 0 (requires causal) keeps only the last ``window``
    keys per query — sliding-window attention (Mistral-style local
    attention). ``q_offset`` (static or traced) is the global position of
    q's first row when q is a chunk of a longer sequence (the in-pipeline
    sequence-parallel path computes each sp rank's query chunk against
    the full k/v). ``block_len`` > 0 (neither causal nor windowed) is the
    block-diffusion training mask: the rows are a noised and a clean copy
    of one sequence, ``block_diffusion_keep`` says which scores stay."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    assert window == 0 or causal, "window attention requires causal"
    assert not block_len or not (causal or window), \
        "the block-diffusion mask is neither causal nor windowed"
    b, nh, sq, d = q.shape
    nkv = k.shape[1]
    assert nh % nkv == 0, "query heads must be a multiple of kv heads"
    g = nh // nkv
    qg = q.reshape(b, nkv, g, sq, d)
    s = jnp.einsum("bngqd,bnkd->bngqk", qg, k) * scale
    if causal:
        skv = k.shape[2]
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(skv)[None, :]
        keep = qpos >= kpos
        if window > 0:
            keep = jnp.logical_and(keep, qpos - kpos < window)
        s = jnp.where(keep, s, -jnp.inf)
    if block_len:
        s = jnp.where(block_diffusion_keep(sq, block_len), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngqk,bnkd->bngqd", p, v).reshape(b, nh, sq, d)


def block_diffusion_keep(rows: int, block_len: int):
    """(rows, rows) bool: the scores block-diffusion training keeps
    (Arriola et al. 2025, arXiv:2503.09573). Rows [0, rows / 2) are the
    noised copy of a sequence and the rest its clean copy; a row's
    position is its place in its copy and its block ``position //
    block_len``. A noised query keeps the noised keys of its own block
    and the clean keys of earlier blocks; a clean query keeps the clean
    keys of its own block and earlier ones, and no noised key."""
    half = rows // 2
    r = jnp.arange(rows)
    blk, noised = (r % half) // block_len, r < half
    bq, bk = blk[:, None], blk[None, :]
    nq, nk = noised[:, None], noised[None, :]
    return jnp.where(nq & nk, bq == bk,
                     jnp.where(nq, bk < bq, ~nk & (bk <= bq)))


def decode_attention_chunked(q, k, v, *, pos, scale: Optional[float] = None,
                             window: int = 0, chunk: int = 256):
    """Single-position cache attention that reads only the LIVE prefix.

    Equals ``attention_reference(q, k, v, causal=True, q_offset=pos)``
    for a one-row query at global position ``pos`` (traced), but instead
    of scoring against the full static-length cache it runs an online-
    softmax ``lax.while_loop`` over ``chunk``-row cache blocks
    [c_lo, pos // chunk] — a flash-decode step in plain XLA. The dense
    path reads L_max rows per generated token regardless of position
    (static shapes), which the r5 decode trace showed is ~2x the useful
    traffic on average (doc/performance.md, decode roofline); here the
    loop bound is data-dependent, which XLA's while supports. With
    ``window > 0`` the loop also starts at the first chunk inside the
    window (the dense path merely masks those reads). Accumulation is
    float32 (better than the dense path's activation-dtype softmax).

    q: (b, nh, 1, d); k/v: (b, nkv, L_max, d) caches, GQA-sized.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, nh, sq, d = q.shape
    assert sq == 1, "decode_attention_chunked is a single-position step"
    nkv, l_max = k.shape[1], k.shape[2]
    assert nh % nkv == 0, "query heads must be a multiple of kv heads"
    assert l_max % chunk == 0, \
        "cache length %d must be divisible by decode_chunk %d" \
        % (l_max, chunk)
    g = nh // nkv
    qg = q.reshape(b, nkv, g, d).astype(jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    c_hi = pos // chunk                       # last live chunk, inclusive
    if window > 0:
        c_lo = jnp.maximum(0, (pos - (window - 1)) // chunk)
    else:
        c_lo = jnp.int32(0)

    def body(carry):
        c, m, l, acc = carry
        kc = lax.dynamic_slice(k, (0, 0, c * chunk, 0),
                               (b, nkv, chunk, d)).astype(jnp.float32)
        vc = lax.dynamic_slice(v, (0, 0, c * chunk, 0),
                               (b, nkv, chunk, d)).astype(jnp.float32)
        s = jnp.einsum("bngd,bnkd->bngk", qg, kc) * scale
        kpos = c * chunk + jnp.arange(chunk)[None, None, None, :]
        keep = kpos <= pos
        if window > 0:
            keep = jnp.logical_and(keep, pos - kpos < window)
        s = jnp.where(keep, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # exp(-inf - -inf) would be nan on the first all-masked chunk;
        # m_new is finite whenever any key is live, and c_lo..c_hi always
        # contains live keys, so guard only the carry rescale
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha[..., None] \
            + jnp.einsum("bngk,bnkd->bngd", p, vc)[:, :, :, None, :]
        return c + 1, m_new, l_new, acc_new

    m0 = jnp.full((b, nkv, g, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, 1), jnp.float32)
    acc0 = jnp.zeros((b, nkv, g, 1, d), jnp.float32)
    _, _, l, acc = lax.while_loop(
        lambda carry: carry[0] <= c_hi, body, (c_lo, m0, l0, acc0))
    out = acc[:, :, :, 0, :] / l
    return out.reshape(b, nh, 1, d).astype(q.dtype)


# per-step score tiles are capped at (RING_Q_CHUNK, skv): the local block
# computation runs as a sequential lax.map over query chunks, so memory per
# device stays O(chunk * skv) instead of O((L/n)^2) — the single-chip flash
# kernel's tiling idea applied inside the ring step
RING_Q_CHUNK = 1024


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: float, q_chunk: int = 0, window: int = 0):
    """Per-shard body: online-softmax over rotating K/V blocks.

    q: (b, h, sq, d) local query block; k, v: (b, nkv, skv, d) local
    key/value blocks — nkv may be smaller than h (grouped-query attention):
    the ring then rotates the nkv-sized blocks (GQA's bandwidth saving
    applies to the ICI hops) and each step broadcasts to the query heads
    only transiently for the tile compute. Runs axis_size steps; at step t
    the device holds the K/V block originally on device (idx - t) mod n.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    kv_groups = h // k.shape[1]
    q_off = idx * sq
    q_chunk = min(sq, q_chunk if q_chunk > 0 else RING_Q_CHUNK)
    while sq % q_chunk != 0:     # largest divisor <= requested chunk
        q_chunk -= 1
    n_chunks = sq // q_chunk

    def chunked(arr):
        # (b, h, sq, ...) -> (n_chunks, b, h, q_chunk, ...): lax.map's
        # leading axis, so one (q_chunk, skv) score tile is live at a time
        return arr.reshape(arr.shape[:2] + (n_chunks, q_chunk) +
                           arr.shape[3:]).transpose(
                               (2, 0, 1, 3) + tuple(
                                   4 + i for i in range(arr.ndim - 3)))

    # q and the (m, l, acc) carry live in chunked layout for the whole
    # scan — the transposes happen once outside, not per ring step
    q_ch = chunked(q)                                    # (nc, b, h, qc, d)
    m0 = jnp.full((n_chunks, b, h, q_chunk), -jnp.inf, q.dtype)
    l0 = jnp.zeros((n_chunks, b, h, q_chunk), q.dtype)
    acc0 = jnp.zeros((n_chunks, b, h, q_chunk, d), q.dtype)

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - t) % n  # whose block we hold this step
        kpos = src * skv + jnp.arange(skv)[None, :]
        # GQA: expand kv heads to the query heads for this step's tiles
        # only — the scan carry (and the ring hop below) stay nkv-sized
        k_cmp = k_blk if kv_groups == 1 else \
            jnp.repeat(k_blk, kv_groups, axis=1)
        v_cmp = v_blk if kv_groups == 1 else \
            jnp.repeat(v_blk, kv_groups, axis=1)

        def one_chunk(args):
            ci, q_c, m_c, l_c, acc_c = args

            def compute(_):
                s = jnp.einsum("bhqd,bhkd->bhqk", q_c, k_cmp) * scale
                if causal:
                    qpos = (q_off + ci * q_chunk +
                            jnp.arange(q_chunk)[:, None])
                    keep = qpos >= kpos
                    if window > 0:
                        keep = jnp.logical_and(keep, qpos - kpos < window)
                    s_ = jnp.where(keep, s, -jnp.inf)
                else:
                    s_ = s
                m_new = jnp.maximum(m_c, jnp.max(s_, axis=-1))
                # guard fully-masked rows (all -inf): exp(-inf - -inf)
                alpha = jnp.where(jnp.isinf(m_c) & jnp.isinf(m_new),
                                  jnp.zeros_like(m_c),
                                  jnp.exp(m_c - m_new))
                p = jnp.exp(s_ - m_new[..., None])
                p = jnp.where(jnp.isinf(s_) & (s_ < 0),
                              jnp.zeros_like(p), p)
                l_new = l_c * alpha + jnp.sum(p, axis=-1)
                acc_new = acc_c * alpha[..., None] + \
                    jnp.einsum("bhqk,bhkd->bhqd", p, v_cmp)
                return m_new, l_new, acc_new

            if not causal:
                return compute(None)
            # skip the whole chunk x block tile when it is entirely above
            # the causal diagonal or entirely older than the window — the
            # chunk map is a sequential lax.map, so cond executes one
            # branch (roughly halving causal ring compute)
            q_start = q_off + ci * q_chunk
            k_start = src * skv
            need = k_start <= q_start + (q_chunk - 1)
            if window > 0:
                need = jnp.logical_and(
                    need, q_start - (k_start + skv - 1) < window)
            return lax.cond(need, compute,
                            lambda _: (m_c, l_c, acc_c), None)

        # remat: without it AD would save every chunk's (qc, skv) p tile,
        # re-materializing the O(sq*skv) residual the chunking removes —
        # the backward pass recomputes s/p per chunk instead
        m, l, acc = lax.map(jax.checkpoint(one_chunk),
                            (jnp.arange(n_chunks), q_ch, m, l, acc))
        # rotate K/V to the next device on the ring (skippable on the last
        # step, but keeping it unconditional keeps the scan body uniform)
        k_blk = collectives.ring_shift(k_blk, axis_name)
        v_blk = collectives.ring_shift(v_blk, axis_name)
        return (k_blk, v_blk, m, l, acc), None

    (_, _, _, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0),
                                    jnp.arange(n))
    # back to (b, h, sq, d), normalized
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# flash-kernel ring step (_ring_flash_enabled) — ops/ring_flash.py
# runs each ring step's online-softmax update fully in VMEM; backward is a
# second ring pass (dq accumulates locally, dk/dv travel with their block)
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_local(q, k, v, axis_name, causal, scale, interpret,
                      window=0):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret,
                             window)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret,
                    window=0):
    from ..ops import ring_flash as rf
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nkv = k.shape[1]
    g = h // nkv
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf, vf = (t.reshape(b * nkv, skv, d) for t in (k, v))

    def expand(blk):
        # GQA: broadcast the nkv kv heads to the query heads for the
        # kernel call only — the ring hop stays nkv-sized
        if g == 1:
            return blk
        return jnp.repeat(blk.reshape(b, nkv, skv, d), g,
                          axis=1).reshape(bh, skv, d)

    from ..ops.flash_attn import NEG_INF
    m0 = jnp.full((bh, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, sq, 1), jnp.float32)
    acc0 = jnp.zeros((bh, sq, d), jnp.float32)

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - t) % n
        offs = jnp.stack([idx * sq, src * skv]).astype(jnp.int32)
        m, l, acc = rf.fwd_step(qf, expand(k_blk), expand(v_blk), m, l,
                                acc, offs, causal=causal, scale=scale,
                                interpret=interpret, window=window)
        k_blk = collectives.ring_shift(k_blk, axis_name)
        v_blk = collectives.ring_shift(v_blk, axis_name)
        return (k_blk, v_blk, m, l, acc), None

    (_, _, m, l, acc), _ = lax.scan(step, (kf, vf, m0, l0, acc0),
                                    jnp.arange(n))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe).astype(q.dtype).reshape(b, h, sq, d)
    lse = m + jnp.log(l_safe)                                # (bh, sq, 1)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, interpret, window, res, g):
    from ..ops import ring_flash as rf
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nkv = k.shape[1]
    groups = h // nkv
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf, vf = (t.reshape(b * nkv, skv, d) for t in (k, v))

    def expand(blk):
        if groups == 1:
            return blk
        return jnp.repeat(blk.reshape(b, nkv, skv, d), groups,
                          axis=1).reshape(bh, skv, d)

    def group_sum(full):
        # (b*h, skv, d) query-head-resolution grads -> kv-head resolution
        return full.reshape(b, nkv, groups, skv, d).sum(axis=2).reshape(
            b * nkv, skv, d)

    dof = g.reshape(bh, sq, d)
    of = out.reshape(bh, sq, d)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (bh, sq, 1)
    dq0 = jnp.zeros((bh, sq, d), jnp.float32)
    dkv0 = jnp.zeros((b * nkv, skv, d), jnp.float32)

    def step(carry, t):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        src = (idx - t) % n
        offs = jnp.stack([idx * sq, src * skv]).astype(jnp.int32)
        k_full, v_full = expand(k_blk), expand(v_blk)
        dq = rf.dq_step(qf, k_full, v_full, dof, lse, delta, dq, offs,
                        causal=causal, scale=scale, interpret=interpret,
                        window=window)
        if groups == 1:
            dk_blk, dv_blk = rf.dkv_step(
                qf, k_full, v_full, dof, lse, delta, dk_blk, dv_blk, offs,
                causal=causal, scale=scale, interpret=interpret,
                window=window)
        else:
            # GQA: the kernel produces query-head-resolution kv grads;
            # group-sum them into the nkv-sized accumulators that ride
            # the ring
            zero = jnp.zeros((bh, skv, d), jnp.float32)
            dkf, dvf = rf.dkv_step(
                qf, k_full, v_full, dof, lse, delta, zero, zero, offs,
                causal=causal, scale=scale, interpret=interpret,
                window=window)
            dk_blk = dk_blk + group_sum(dkf)
            dv_blk = dv_blk + group_sum(dvf)
        # rotate the K/V block together with its gradient accumulators:
        # after n shifts each block is home with every device's
        # contribution summed in
        k_blk = collectives.ring_shift(k_blk, axis_name)
        v_blk = collectives.ring_shift(v_blk, axis_name)
        dk_blk = collectives.ring_shift(dk_blk, axis_name)
        dv_blk = collectives.ring_shift(dv_blk, axis_name)
        return (k_blk, v_blk, dk_blk, dv_blk, dq), None

    (_, _, dk, dv, dq), _ = lax.scan(
        step, (kf, vf, dkv0, dkv0, dq0), jnp.arange(n))
    shape_q = (b, h, sq, d)
    shape_kv = (b, nkv, skv, d)
    return (dq.astype(q.dtype).reshape(shape_q),
            dk.astype(k.dtype).reshape(shape_kv),
            dv.astype(v.dtype).reshape(shape_kv))


_ring_flash_local.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_flash_enabled(sq: int, skv: int, d: int) -> bool:
    """The ring step takes the flash kernels wherever Pallas runs (a TPU,
    or a test's ops.set_use_pallas(True), which runs them in the
    interpreter) and the per-device shape tiles; elsewhere the dense
    step."""
    from .. import ops as _ops
    from ..ops import ring_flash as rf
    return _ops.use_pallas() and rf.supports(sq, skv, d)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   batch_axis: Optional[str] = None, q_chunk: int = 0,
                   window: int = 0):
    """Ring attention over sequence-sharded q, k, v: (b, h, seq, d) with seq
    sharded on ``axis_name``. Returns output with the same sharding.
    ``batch_axis`` names a mesh axis to shard the batch dim over (pass the
    trainer's "data" axis on a (data, sp) mesh — a None batch spec would
    replicate the global batch on every chip). ``q_chunk`` caps the live
    score tile per ring step (default RING_Q_CHUNK)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(batch_axis, None, axis_name, None)
    n = mesh.shape[axis_name]
    sq = q.shape[2] // n
    if _ring_flash_enabled(sq, k.shape[2] // n, q.shape[-1]):
        from .. import ops
        interpret = ops.pallas_interpret()
        fn = shard_map(
            lambda q_, k_, v_: _ring_flash_local(
                q_, k_, v_, axis_name, causal, scale, interpret, window),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale, q_chunk=q_chunk,
                          window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float,
                   window: int = 0):
    n = lax.axis_size(axis_name)

    def seq_to_heads(x):
        # (b, h, s/n, d) -> (b, h/n, s, d): split heads, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # after the all-to-all each device holds h/n full-length heads — the
    # single-chip flash kernel applies as-is, keeping the local attention
    # O(L) in memory instead of materializing the (L, L) score matrix.
    # GQA: the all-to-alls above moved nkv-sized k/v; both the flash
    # kernel (grouped BlockSpec row map) and the dense reference consume
    # grouped k/v natively.
    from .. import ops
    if ops.use_pallas() and ops.flash_supported(qh.shape[2], qh.shape[3]):
        out = ops.flash_attention(qh, kh, vh, causal=causal, scale=scale,
                                  window=window)
    else:
        out = attention_reference(qh, kh, vh, causal=causal, scale=scale,
                                  window=window)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis_name: str = "sp",
                      causal: bool = False, scale: Optional[float] = None,
                      batch_axis: Optional[str] = None, window: int = 0):
    """Ulysses sequence parallelism: all-to-all seq->heads, dense local
    attention, all-to-all back. Requires heads % axis_size == 0.
    ``batch_axis`` as in ring_attention."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis_name]
    if q.shape[1] % n != 0:
        raise ValueError("ulysses needs heads (%d) divisible by sp axis (%d)"
                         % (q.shape[1], n))
    if k.shape[1] % n != 0:
        raise ValueError("ulysses needs kv heads (%d) divisible by sp axis "
                         "(%d); broadcast k/v to the query heads first"
                         % (k.shape[1], n))
    spec = P(batch_axis, None, axis_name, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name,
                          causal=causal, scale=scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
