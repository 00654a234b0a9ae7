"""Single-chip flash attention: blocked online-softmax fwd + bwd in Pallas.

The framework's long-context story has two tiers (SURVEY.md §5): across
chips the sequence shards over the mesh "sp" axis (parallel/ring.py); on
one chip this kernel keeps attention O(L) in memory by never materializing
the (L, L) score matrix — Q tiles stay resident while K/V tiles stream
through VMEM and the softmax is accumulated online (running max + sum, the
same log-sum-exp carry ring attention uses across devices).

The tile schedule (PR 31; ``_Geom`` holds it, ``_tiling`` sizes it).
Each kernel's grid is (rows, resident tiles, steps): a resident tile
meets only the streamed tiles that hold a score it keeps — on or under
the causal diagonal, inside the sliding window, before the padded tail —
so ``steps`` is the longest such run, the streamed block's index is the
run's start plus the step, and a step past the run's end names the block
already held (nothing moves) and computes nothing. A step's body walks
its streamed block in ``sub`` columns; each (resident, sub) score tile is
skipped, taken whole (no iota, compare or select), or masked, by one
static-shape predicate each: only tiles that straddle the diagonal, the
window's trailing edge or the tail take ``_mask``. Tiles are sized from
(L, d, dtype, window) for the chip's VMEM, not a constant.

A third mask beside causal and causal-with-window (PR 36): block
diffusion's training mask (``block_len`` > 0). The rows are a noised and
a clean copy of one sequence; a resident tile then meets TWO runs of
streamed tiles (``kv_runs`` / ``q_runs``: a noised query tile its own
blocks' noised keys and the clean keys before its block, a clean key tile
the noised queries past its block and the clean ones from it on), which
``_walk`` lays end to end as index arithmetic on the grid step: the same
three kernels, the same ``kind`` / ``mask`` protocol comparing block
indices, no table, no second kernel. The quadrant of clean queries and
noised keys is never visited.

A fourth mask, and the first that is data (PR 38): a selection
(``flash_attention_selected``). Each query keeps the keys an int8 array
``sel (b, L, L)`` marks (learned sparse attention: an indexer chose them,
all at or under the diagonal). The schedule is the causal one; every
visited tile is an edge tile whose mask is the tile of ``sel`` streamed
beside k and not index arithmetic. ``sel`` is read queries first
everywhere: the kernels on transposed scores turn a pass's rows of it in
VMEM (no transpose of it in HBM). The other three masks' kernels trace as
before: they get no such operand.

Forward: grid (batch*heads, q tiles, kv steps); the (m, l, acc) carry
lives in VMEM scratch across the kv steps, m and l replicated along the
lanes; the MXU sees (block_q, d) x (d, sub) and (block_q, sub) x (sub, d)
matmuls. Saves the per-row logsumexp for backward, lane-dense:
(batch*heads, 1, L).

Backward (FlashAttention-2 factorization): with P = exp(S - lse) the
gradients are
    dV = Pᵀ dO
    dS = P ∘ (dO Vᵀ - D),  D = rowsum(dO ∘ O)
    dQ = scale · dS K
    dK = scale · dSᵀ Q
recomputing S blockwise from the saved lse; D is computed once (fused XLA
reduce) and, like lse, travels as a lane-dense (batch*heads, 1, L) row.

One kernel (``_bwd_kernel``) makes all three from one visit of each
live score tile: five products and one ``exp`` a tile. It works on the
transposed scores (kv rows, q lanes), so lse and D broadcast along
sublanes. Its grid is (kv head, the group's query head, kv tile, q step):
dK and dV of the kv head's whole length stay in float32 VMEM across the
group's heads, dQ of the current head's whole length across the kv tiles,
kept transposed (d, L) so that its product ``Kᵀ dSᵀ`` is a plain one, Kᵀ
made once a kv tile; each is written once, when its last contribution is
in — no partial sum goes through HBM. Where those accumulators do not fit
VMEM (``fused_bwd_supported``: long L) the backward is the two kernels it
replaced, which stay its golden model: a dQ kernel (q tiles resident, kv
streams, lse and D turned into lane-replicated columns once per q tile)
and a dK/dV kernel on the transposed scores whose grid rows are the
key-value heads and whose steps run over the group's query heads x the q
tiles that reach the kv tile, so dK and dV are summed over the group in
float32 scratch and written once at key-value resolution — O(L) memory
end to end.

Numerics are golden-tested against the dense reference on CPU
(interpret=True) in tests/test_flash_attention.py and on the chip by
tools/check_tpu_kernels.py. The kernel-escape-hatch precedent in the
reference is the hand-written insanity pooling plan
(src/layer/insanity_pooling_layer-inl.hpp:13-100).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

NEG_INF = -1e30  # finite stand-in for -inf: keeps exp()/max() NaN-free
_LANES = 128
# what one kernel's blocks, scratch and score tiles may add up to by
# _vmem_bytes; the compiler is given twice that (a v5e core has 128 MiB)
VMEM_BUDGET = 32 << 20
# the most the fused backward may ask of the compiler: its tiles' part
# twice, as above, and its whole-length accumulators once (_accumulators)
BWD_VMEM_LIMIT = 100 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims=_NN):
    """A product on the MXU: operands in their own dtype (bf16 on the fast
    path), float32 accumulation. The precision is spelled out: Mosaic
    refuses a process-wide ``highest`` (tests/conftest.py) for bf16."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


class _Geom(NamedTuple):
    """The static geometry of one kernel's tile walk: block sizes, tile
    counts, and what decides whether a score is kept. Every method takes
    python / numpy integers (the tests, the tile counts) or traced ones
    (index maps, kernel bodies): ``xp`` is numpy or jax.numpy."""
    bq: int
    bk: int
    sub: int        # columns of the streamed block one pass of a body takes
    n_q: int
    n_k: int
    causal: bool
    window: int
    kv_len: int
    # block diffusion (``block_len`` > 0): the rows are two copies of one
    # sequence, [noised | clean], of ``kv_len // 2`` positions each, in
    # blocks of ``block_len`` positions. A noised query keeps the noised
    # keys of its own block and the clean keys of earlier blocks; a clean
    # query keeps the clean keys of its own block and earlier ones. No
    # tile straddles the two copies (``_geom`` holds the tiles to that).
    block_len: int = 0
    # a selection (``select``; causal besides): which scores of a visited
    # tile are kept is data, an operand of the kernel, so no visited tile
    # is ``full`` and ``mask`` is not asked
    select: bool = False

    @property
    def masks(self) -> bool:
        return bool(self.causal or self.block_len
                    or self.n_k * self.bk > self.kv_len)

    @property
    def half(self) -> int:
        return self.kv_len // 2

    def kv_range(self, i, xp=jnp):
        """First and last kv tile holding a kept score of q tile ``i``."""
        if not self.causal:
            return 0 * i, 0 * i + (self.n_k - 1)
        hi = xp.minimum(((i + 1) * self.bq - 1) // self.bk, self.n_k - 1)
        lo = 0 * i
        if self.window > 0:
            lo = xp.maximum(i * self.bq - self.window + 1, 0) // self.bk
        return lo, hi

    def q_range(self, j, xp=jnp):
        """First and last q tile holding a kept score of kv tile ``j``."""
        if not self.causal:
            return 0 * j, 0 * j + (self.n_q - 1)
        lo = (j * self.bk) // self.bq
        hi = 0 * j + (self.n_q - 1)
        if self.window > 0:
            hi = xp.minimum(
                (self.window + (j + 1) * self.bk - 2) // self.bq, hi)
        return lo, hi

    def kv_runs(self, i, xp=jnp):
        """Block diffusion: the TWO runs of kv tiles that hold a kept score
        of q tile ``i``, (first, last) of the run among the noised keys and
        of the run among the clean ones; an empty run has last < first. A
        noised tile: its own blocks' noised keys, and the clean keys below
        its last row's block. A clean tile: the clean keys up to its last
        row's block."""
        B, H = self.block_len, self.half
        first, last = i * self.bq, (i + 1) * self.bq - 1
        noised = first < H
        p0, p1 = first % H, last % H
        nk = H // self.bk                   # kv tiles a copy
        lo_a = xp.where(noised, (p0 // B * B) // self.bk, 0 * i)
        hi_a = xp.where(
            noised, (xp.minimum((p1 // B + 1) * B, H) - 1) // self.bk,
            0 * i - 1)
        hi_b = nk + xp.where(
            noised, (p1 // B * B - 1) // self.bk,
            (xp.minimum((p1 // B + 1) * B, H) - 1) // self.bk)
        return lo_a, hi_a, 0 * i + nk, hi_b

    def q_runs(self, j, xp=jnp):
        """Block diffusion: the two runs of q tiles that hold a kept score
        of kv tile ``j``, among the noised queries and among the clean
        ones. A noised key tile: the noised queries of its own blocks,
        and no clean one. A clean key tile: the noised queries past its
        first row's block, and the clean queries from that block on."""
        B, H = self.block_len, self.half
        first, last = j * self.bk, (j + 1) * self.bk - 1
        noised = first < H
        p0, p1 = first % H, last % H
        nq = H // self.bq                   # q tiles a copy
        lo_a = xp.where(noised, (p0 // B * B) // self.bq,
                        xp.minimum(((p0 // B + 1) * B) // self.bq, nq))
        hi_a = xp.where(
            noised, (xp.minimum((p1 // B + 1) * B, H) - 1) // self.bq,
            0 * j + (nq - 1))
        lo_b = nq + xp.where(noised, 0 * j, (p0 // B * B) // self.bq)
        hi_b = xp.where(noised, 0 * j + (nq - 1), 0 * j + (2 * nq - 1))
        return lo_a, hi_a, lo_b, hi_b

    @staticmethod
    def _walk(runs, step, xp=jnp):
        """Step ``step`` of a walk over two runs, the second wholly past
        the first: the tile it names and the walk's last tile. A step past
        the end names a tile past the last."""
        lo_a, hi_a, lo_b, hi_b = runs
        n_a = hi_a - lo_a + 1
        tile = xp.where(step < n_a, lo_a + step, lo_b + (step - n_a))
        return tile, xp.where(hi_b >= lo_b, hi_b, hi_a)

    def kv_tile(self, i, step, xp=jnp):
        """The kv tile q tile ``i`` meets at a grid step, and the last tile
        of its walk: the step computes while the first is not past the
        second."""
        if self.block_len:
            return self._walk(self.kv_runs(i, xp), step, xp)
        lo, hi = self.kv_range(i, xp)
        return lo + step, hi

    def q_tile(self, j, step, steps=0, xp=jnp):
        """``kv_tile`` for the dK/dV kernel; with ``steps``, ``step`` is a
        grid step of that kernel, which run over the group's heads x
        ``steps`` q steps."""
        if self.block_len:
            return self._walk(self.q_runs(j, xp),
                              step % steps if steps else step, xp)
        lo, hi = self.q_range(j, xp)
        return lo + (step % steps if steps else step), hi

    def kv_block(self, i, step, xp=jnp):
        """The kv tile q tile ``i`` holds at a grid step: its walk's tile,
        and past the walk's end the last one again (nothing moves)."""
        tile, last = self.kv_tile(i, step, xp)
        return xp.minimum(tile, last)

    def q_block(self, j, step, xp=jnp):
        tile, last = self.q_tile(j, step, xp=xp)
        return xp.minimum(tile, last)

    @staticmethod
    def _longest(runs) -> int:
        lo_a, hi_a, lo_b, hi_b = runs
        return int(np.max(np.maximum(hi_a - lo_a + 1, 0)
                          + np.maximum(hi_b - lo_b + 1, 0)))

    def kv_steps(self) -> int:
        if self.block_len:
            return self._longest(self.kv_runs(np.arange(self.n_q), np))
        lo, hi = self.kv_range(np.arange(self.n_q), np)
        return int(np.max(hi - lo)) + 1

    def q_steps(self) -> int:
        if self.block_len:
            return self._longest(self.q_runs(np.arange(self.n_k), np))
        lo, hi = self.q_range(np.arange(self.n_k), np)
        return int(np.max(hi - lo)) + 1

    def _quadrant(self, q0, k0, xp=jnp):
        """Block diffusion: the scores of a tile that starts at (q0, k0)
        are kept where ``lo <= key's block - query's block <= hi``; the
        clean queries keep no noised key (lo > hi). Also the two starts as
        positions of their copies."""
        H = self.half
        qn, kn = q0 < H, k0 < H
        far = 2 * H                     # more blocks than there are
        hi = xp.where(xp.logical_and(qn, xp.logical_not(kn)), -1, 0)
        lo = xp.where(kn, xp.where(qn, 0, far), -far)
        return lo, hi, q0 % H, k0 % H

    def kind(self, q0, nq, k0, nk, xp=jnp):
        """(needed, full) of the score tile of queries [q0, q0 + nq) and
        keys [k0, k0 + nk): it holds a kept score; it holds no other."""
        needed = k0 < self.kv_len
        full = k0 + nk <= self.kv_len
        if self.select:
            full = full & False
        if self.block_len:
            B = self.block_len
            lo, hi, pq, pk = self._quadrant(q0, k0, xp)
            # the least and the greatest difference of blocks in the tile
            least = pk // B - (pq + (nq - 1)) // B
            most = (pk + (nk - 1)) // B - pq // B
            needed = needed & (least <= hi) & (most >= lo)
            full = full & (most <= hi) & (least >= lo)
        if self.causal:
            needed = xp.logical_and(needed, k0 <= q0 + (nq - 1))
            full = xp.logical_and(full, k0 + (nk - 1) <= q0)
            if self.window > 0:
                needed = xp.logical_and(
                    needed, q0 - (k0 + (nk - 1)) < self.window)
                full = xp.logical_and(
                    full, q0 + (nq - 1) - k0 < self.window)
        return needed, full

    def mask(self, s, q0, k0, k_axis):
        """NEG_INF on the scores the contract drops; keys run along
        ``k_axis`` of ``s``. Only edge tiles pay for this."""
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
        keep = kpos < self.kv_len
        if self.block_len:
            lo, hi, pq, pk = self._quadrant(q0, k0)
            # kpos as a position of its copy: k0 - pk is the copy's start
            diff = self._block_of(kpos - (k0 - pk)) - self._block_of(
                pq + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                              1 - k_axis))
            keep = keep & (diff <= hi) & (diff >= lo)
        if self.causal:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                 1 - k_axis)
            keep = jnp.logical_and(keep, qpos >= kpos)
            if self.window > 0:
                keep = jnp.logical_and(keep, qpos - kpos < self.window)
        return jnp.where(keep, s, NEG_INF)

    def _block_of(self, pos):
        """Block index of an array of positions (none negative)."""
        B = self.block_len
        if B & (B - 1) == 0:
            return jax.lax.shift_right_logical(pos, B.bit_length() - 1)
        return jax.lax.div(pos, B)


def tile_counts(g: _Geom) -> Tuple[int, int, int]:
    """(full, edge, skipped) score tiles of (bq, sub) one head's forward
    grid holds: what telemetry's ``flash.tiles.*`` count."""
    q0, k0 = np.meshgrid(np.arange(g.n_q) * g.bq,
                         np.arange(g.n_k * g.bk // g.sub) * g.sub,
                         indexing="ij")
    needed, full = g.kind(q0, g.bq, k0, g.sub, np)
    n_full = int(np.sum(needed & full))
    n_edge = int(np.sum(needed & ~full))
    return n_full, n_edge, q0.size - n_full - n_edge


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) statistic at width ``n``."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _to_row(x):
    """Lane-replicated (n, 128) -> the lane-dense (1, n) row."""
    return x.T[:1, :]


def _to_col(row):
    """Lane-dense (1, n) row -> lane-replicated (n, 128)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _visit(g: _Geom, q0, nq, k0, nk, live, body):
    """Run ``body(masked)`` for the score tile at (q0, k0) if the grid
    step is live and the tile holds a kept score: the plain body where no
    score is dropped, the masking one where the tile straddles an edge."""
    if not g.masks:
        pl.when(live)(functools.partial(body, False))
        return
    needed, full = g.kind(q0, nq, k0, nk)
    needed = jnp.logical_and(live, needed)
    pl.when(jnp.logical_and(needed, full))(functools.partial(body, False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(full)))(
        functools.partial(body, True))


def _selected(sel_ref, s, c: int):
    """NEG_INF on the scores ``s`` of a body's pass ``c`` that the
    selection's int8 block does not mark: its columns ``c`` of ``s``'s
    width (the block lies as the scores do, the streamed side on the
    lanes)."""
    sub = s.shape[1]
    keep = sel_ref[0, :, c * sub:(c + 1) * sub].astype(jnp.int32) != 0
    return jnp.where(keep, s, NEG_INF)


def _selected_t(sel_ref, st, c: int):
    """``_selected`` for transposed scores ``st`` (keys on the sublanes):
    the selection's block lies queries first, so pass ``c`` takes its rows
    ``c`` of ``st``'s width and turns them in VMEM."""
    sub = st.shape[1]
    keep = sel_ref[0, c * sub:(c + 1) * sub, :].astype(jnp.int32).T != 0
    return jnp.where(keep, st, NEG_INF)


def _with_sel(kernel, n_in: int):
    """``kernel`` as pallas_call sees it when the selection's block is its
    operand ``n_in``, after the plain kernel's own."""
    def run(*refs, **kw):
        return kernel(*refs[:n_in], *refs[n_in + 1:], sel_ref=refs[n_in],
                      **kw)
    return run


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, g, sel_ref=None):
    i, t = pl.program_id(1), pl.program_id(2)
    j, hi = g.kv_tile(i, t)
    sub, d = g.sub, q_ref.shape[-1]

    @pl.when(t == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(c, masked):
        q = q_ref[0]
        k = k_ref[0, c * sub:(c + 1) * sub, :]
        v = v_ref[0, c * sub:(c + 1) * sub, :]
        s = _dot(q, k, _NT) * scale                         # (bq, sub) f32
        if masked and sel_ref is not None:
            s = _selected(sel_ref, s, c)
        elif masked:
            s = g.mask(s, i * g.bq, j * g.bk + c * sub, 1)
        m_prev = m_scr[...]                                 # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, sub))                 # (bq, sub) f32
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, d) + _dot(
            p.astype(v.dtype), v)

    for c in range(g.bk // sub):
        _visit(g, i * g.bq, g.bq, j * g.bk + c * sub, sub, j <= hi,
               functools.partial(body, c))

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / _lanes(l, d)).astype(o_ref.dtype)
        # lse = m + log(l): per-row logsumexp for the backward recompute
        lse_ref[0] = _to_row(m_scr[...] + jnp.log(l))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               lse_scr, delta_scr, dq_scr, *, scale, g, sel_ref=None):
    i, t = pl.program_id(1), pl.program_id(2)
    j, hi = g.kv_tile(i, t)
    sub = g.sub

    @pl.when(t == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _to_col(lse_ref[0])
        delta_scr[...] = _to_col(delta_ref[0])

    def body(c, masked):
        q, do = q_ref[0], do_ref[0]
        k = k_ref[0, c * sub:(c + 1) * sub, :]
        v = v_ref[0, c * sub:(c + 1) * sub, :]
        s = _dot(q, k, _NT) * scale
        if masked and sel_ref is not None:
            s = _selected(sel_ref, s, c)
        elif masked:
            s = g.mask(s, i * g.bq, j * g.bk + c * sub, 1)
        p = jnp.exp(s - _lanes(lse_scr[...], sub))          # (bq, sub) f32
        dp = _dot(do, v, _NT)
        # dS without its scale: dq takes it once, in float32, at the end
        ds = p * (dp - _lanes(delta_scr[...], sub))
        dq_scr[...] += _dot(ds.astype(k.dtype), k)

    for c in range(g.bk // sub):
        _visit(g, i * g.bq, g.bq, j * g.bk + c * sub, sub, j <= hi,
               functools.partial(body, c))

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, g, steps, sel_ref=None):
    j, t = pl.program_id(1), pl.program_id(2)
    # t runs over the group's heads x the q steps
    i, hi = g.q_tile(j, t, steps)
    sub = g.sub

    @pl.when(t == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(c, masked):
        # transposed scores: keys on the sublanes, queries on the lanes,
        # so lse and delta are rows and no product transposes an operand
        k, v = k_ref[0], v_ref[0]
        q = q_ref[0, c * sub:(c + 1) * sub, :]
        do = do_ref[0, c * sub:(c + 1) * sub, :]
        st = _dot(k, q, _NT) * scale                        # (bk, sub)
        if masked and sel_ref is not None:
            st = _selected_t(sel_ref, st, c)
        elif masked:
            st = g.mask(st, i * g.bq + c * sub, j * g.bk, 0)
        pt = jnp.exp(st - lse_ref[0, :, c * sub:(c + 1) * sub])
        dv_scr[...] += _dot(pt.astype(do.dtype), do)
        dpt = _dot(v, do, _NT)
        dst = pt * (dpt - delta_ref[0, :, c * sub:(c + 1) * sub])
        dk_scr[...] += _dot(dst.astype(q.dtype), q)

    for c in range(g.bq // sub):
        _visit(g, i * g.bq + c * sub, sub, j * g.bk, g.bk, i <= hi,
               functools.partial(body, c))

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dqt_scr, dk_scr, dv_scr, kt_scr,
                *, scale, g, sel_ref=None):
    """dQ, dK and dV from one visit of each live score tile. Grid step
    (kv head, the group's head h, kv tile j, q step t); the scores are
    transposed as in ``_dkv_kernel``. dK / dV of the kv head's whole
    length (``dk_scr``, ``dv_scr``) gather over the group's heads, dQᵀ of
    head h's whole length (``dqt_scr``, (d, L)) over the kv tiles."""
    h, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    i, hi = g.q_tile(j, t)
    sub = g.sub
    last = t == pl.num_programs(3) - 1
    rows = pl.ds(pl.multiple_of(j * g.bk, g.bk), g.bk)

    @pl.when((j == 0) & (t == 0))
    def _():
        dqt_scr[...] = jnp.zeros_like(dqt_scr)

    @pl.when(t == 0)
    def _():
        kt_scr[...] = k_ref[0].T
        @pl.when(h == 0)
        def _():
            dk_scr[rows, :] = jnp.zeros((g.bk, dk_scr.shape[1]), jnp.float32)
            dv_scr[rows, :] = jnp.zeros((g.bk, dv_scr.shape[1]), jnp.float32)

    def body(c, masked):
        k, v = k_ref[0], v_ref[0]
        q = q_ref[0, c * sub:(c + 1) * sub, :]
        do = do_ref[0, c * sub:(c + 1) * sub, :]
        st = _dot(k, q, _NT) * scale                        # (bk, sub)
        if masked and sel_ref is not None:
            st = _selected_t(sel_ref, st, c)
        elif masked:
            st = g.mask(st, i * g.bq + c * sub, j * g.bk, 0)
        pt = jnp.exp(st - lse_ref[0, :, c * sub:(c + 1) * sub])
        dv_scr[rows, :] += _dot(pt.astype(do.dtype), do)
        dpt = _dot(v, do, _NT)
        dst = (pt * (dpt - delta_ref[0, :, c * sub:(c + 1) * sub])).astype(
            q.dtype)
        dk_scr[rows, :] += _dot(dst, q)
        cols = pl.ds(pl.multiple_of(i * g.bq + c * sub, sub), sub)
        dqt_scr[:, cols] += _dot(kt_scr[...], dst)          # (d, sub)

    for c in range(g.bq // sub):
        _visit(g, i * g.bq + c * sub, sub, j * g.bk, g.bk, i <= hi,
               functools.partial(body, c))

    @pl.when((j == pl.num_programs(2) - 1) & last)
    def _():
        # dQ's block is the head's whole length: turned a q tile at a time
        def turn(n, carry):
            cols = pl.ds(pl.multiple_of(n * g.bq, g.bq), g.bq)
            dq_ref[0, cols, :] = (dqt_scr[:, cols].T * scale).astype(
                dq_ref.dtype)
            return carry
        jax.lax.fori_loop(0, g.n_q, turn, 0)

    @pl.when((h == pl.num_programs(1) - 1) & last)
    def _():
        dk_ref[0] = (dk_scr[rows, :] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[rows, :].astype(dv_ref.dtype)


class Tiles(NamedTuple):
    """One kernel's tiling: q and kv rows a grid step holds, and the
    columns of the streamed block (kv; q in the dK/dV and the fused
    backward kernels) one pass of the body takes."""
    bq: int
    bk: int
    sub: int


def _vmem_bytes(kernel: str, t: Tiles, d: int, itemsize: int) -> int:
    """What the ``fwd``, ``dq`` or ``dkv`` kernel keeps in VMEM with
    these tiles, by arithmetic: its double-buffered blocks and rows of
    statistics, its float32 scratch, and the float32 score tiles of one
    pass (s, p and a mask's select going forward; s, p, dp, ds and their
    bf16 copies going back). ``bwd``, the fused backward, has the dK/dV
    kernel's blocks, Kᵀ of a kv tile for scratch and one score tile more
    (dSᵀ as dQ's operand); its whole-length part is ``_accumulators``."""
    lanes = max(d, _LANES)
    n_q, n_k = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4),
                "bwd": (2, 4)}[kernel]
    blocks = itemsize * lanes * (n_q * t.bq + n_k * t.bk)
    rows = 0 if kernel == "fwd" else 2 * 8 * 4 * t.bq
    resident = t.bk if kernel in ("dkv", "bwd") else t.bq
    scratch = {"dkv": 4 * resident * 2 * lanes,
               "bwd": itemsize * d * resident}.get(
                   kernel, 4 * resident * (2 * _LANES + lanes))
    scores = 4 * resident * t.sub * {"fwd": 3, "bwd": 6}.get(kernel, 5)
    return 2 * (blocks + rows) + scratch + scores


def _accumulators(t: Tiles, L: int, d: int, itemsize: int) -> int:
    """The fused backward's whole-length VMEM: dQᵀ of one head and dK, dV
    of one kv head in float32, and dQ's output block, double-buffered."""
    lanes = max(d, _LANES)
    Lq, Lk = _padded_len(L, t.bq), _padded_len(L, t.bk)
    return 4 * (d * Lq + 2 * lanes * Lk) + 2 * itemsize * lanes * Lq


def _fit(L: int, target: int) -> int:
    """The largest lane-aligned block up to ``target`` that pads L by at
    most a sixteenth; 128 (and whatever padding that takes) otherwise."""
    b = target
    while b > _LANES and -(-L // b) * b - L > L // 16:
        b //= 2
    return b


def _pow2(x: float, lo: int, hi: int) -> int:
    """The power of two nearest ``x`` (in ratio) within [lo, hi]."""
    return min(hi, max(lo, 1 << max(0, round(math.log2(max(x, 1.0))))))


def _tiling(L: int, d: int, itemsize: int, window: int,
            block_len: int = 0) -> Tuple[Tiles, Tiles, Tiles]:
    """Tiles of the forward, dQ and dK/dV kernels for one shape, by what
    the chip read (PERF.md section 5, PR 31: a v5e at L 512, 2,048 and
    8,192, windows 0 to 4,096). The streamed block is long — the keys a
    late query sees, up to 1,024 — so that a grid step's products outweigh
    its fixed cost (2,048 read 2-3% faster still, and doubled the bodies
    to trace and lower); the resident tile is about half the keys a query
    sees on average, up to 1,024: larger, and the tiles on the diagonal
    and the window's edge compute mostly masked scores; the body takes
    the streamed block in ``sub`` columns, 512 going forward and up to
    1,024 going back, so that the resident accumulators are revisited
    seldom and the float32 score tiles stay inside VMEM_BUDGET. Short
    sequences get blocks that divide them or pad them by under a lane
    tile. The query group does not enter: the dK/dV kernel's steps grow
    with it, its tiles do not. Under the block-diffusion mask the L rows
    are two copies of L / 2 positions, a query sees the keys of about its
    position as a causal one does, and the sizes are those of L / 2, cut
    to divide it: no tile straddles the copies."""
    half = L // 2 if block_len else 0
    L = half or L
    span = min(L, window) if window else L
    mean = span * (1.0 - span / (2.0 * L))
    res = _fit(L, _pow2(mean / 2, _LANES, 1024))
    stream = _fit(L, min(1024, max(_LANES, 1 << (span.bit_length() - 1))))
    while half % res:
        res //= 2
    while half % stream:
        stream //= 2
    sub_f, sub_b = min(stream, 512), min(stream, max(res, 512))

    def tiles():
        return (Tiles(res, stream, sub_f), Tiles(res, stream, sub_b),
                Tiles(stream, res, sub_b))

    def fits():
        return all(_vmem_bytes(kernel, t, d, itemsize) <= VMEM_BUDGET
                   for kernel, t in zip(("fwd", "dq", "dkv"), tiles()))
    # a head size the budget was not read at: the backward's wide pass
    # goes first, then the streamed block, then the resident tile
    while not fits() and max(res, stream) > _LANES:
        if sub_b > sub_f:
            sub_b //= 2
        elif stream > _LANES:
            stream //= 2
            sub_f, sub_b = min(sub_f, stream), min(sub_b, stream)
        else:
            res //= 2
    return tiles()


def supports(L: int, d: int, block_len: int = 0) -> bool:
    """Shapes the kernel path accepts: any L >= 128 (padded to a lane-
    aligned tile, tail masked in-kernel) and a sublane-aligned head dim;
    under the block-diffusion mask two copies of whole lane tiles."""
    if block_len and L % (2 * _LANES):
        return False
    return pltpu is not None and L >= 128 and d % 8 == 0


def fused_bwd_supported(L: int, d: int, dtype, window: int = 0,
                        block_len: int = 0, select: bool = False,
                        tiles=None) -> bool:
    """Whether the backward of these (L, d) operands takes the fused
    kernel: what it asks of the compiler (``_vmem_limit``: the dK/dV
    tiles' part twice, the whole-length accumulators once) within
    BWD_VMEM_LIMIT. Past it, long L, the backward is the split pair.
    ``tiles`` as ``flash_attention`` takes them."""
    itemsize = jnp.dtype(dtype).itemsize
    t = (tiles or _tiling(L, d, itemsize, window, block_len))[2]
    return _vmem_limit("bwd", t, d, itemsize, select, L) <= BWD_VMEM_LIMIT


def _vmem_limit(kernel: str, t: Tiles, d: int, itemsize: int,
                select: bool = False, L: int = 0) -> int:
    """The VMEM a kernel asks of the compiler: twice its tiles' part, with
    a selection's int8 block (double-buffered) and one pass's tile of it
    widened to 32 bits for the compare (and that turned, on transposed
    scores); for the fused backward its accumulators once besides."""
    turned = kernel in ("dkv", "bwd")
    resident = t.bk if turned else t.bq
    sel = (2 * t.bq * t.bk + 4 * resident * t.sub * (1 + turned)) \
        if select else 0
    whole = _accumulators(t, L, d, itemsize) if kernel == "bwd" else 0
    return 2 * (_vmem_bytes(kernel, t, d, itemsize) + sel) + whole


def _dims(vmem_bytes: Optional[int] = None, grid_dims: int = 3):
    # the innermost stream dim carries the scratch accumulator across steps:
    # must be sequential ("arbitrary"); batch*heads and the tile dim are
    # parallel (Mosaic may split them over the two TensorCores). The fused
    # backward's accumulators span its head and kv-tile dims too
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) + (
            ("parallel", "arbitrary") if grid_dims == 3
            else ("arbitrary",) * (grid_dims - 1)),
        vmem_limit_bytes=vmem_bytes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, interpret: bool = False,
                    window: int = 0, tiles=None, block_len: int = 0):
    """Memory-O(L) attention. q: (b, h, L, d) -> (b, h, L, d); k/v may
    carry FEWER heads (grouped-query attention, nkv | h): the kernels read
    the shared kv head per query group through the BlockSpec index map, so
    K/V HBM footprint and traffic stay nkv-sized.

    Same contract as parallel.attention_reference (incl. sliding
    ``window``, causal-only, and ``block_len`` > 0: the block-diffusion
    mask over rows that are a noised and a clean copy of one sequence,
    neither causal nor windowed); the caller gates on supports().
    `interpret=True` runs the kernels in the Pallas interpreter so CPU
    tests cover the exact kernel code. ``tiles`` is the tests' way to run
    several tiles at a small L: three ``Tiles`` (forward, dQ, dK/dV; the
    fused backward takes the dK/dV kernel's) in place of ``_tiling``'s
    choice.
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, interpret, window, tiles,
                        block_len)
    return out


def _merge_bh(x):
    b, h, L, d = x.shape
    return x.reshape(b * h, L, d)


def _pad_seq(x, Lp, axis=1):
    L = x.shape[axis]
    if L == Lp:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, Lp - L)
    return jnp.pad(x, pad)


def _padded_len(L: int, block: int) -> int:
    return -(-L // block) * block


def _kv_row_map(nh: int, nkv: int):
    """Grid row (over b*nh) -> K/V array row (over b*nkv): grouped-query
    attention reads the SHARED kv head of each query-head group straight
    from the nkv-sized array — K/V HBM footprint and traffic stay
    nkv-sized, never broadcast to the query heads."""
    grp = nh // nkv
    def to_kv(g):
        return (g // nh) * nkv + (g % nh) // grp
    return to_kv


def _geom(t: Tiles, L: int, causal: bool, window: int,
          block_len: int = 0, select: bool = False) -> _Geom:
    if select:
        assert causal and not window and not block_len, \
            "a selection is causal, with no window and no blocks"
    if block_len:
        assert not causal and not window, \
            "the block-diffusion mask is neither causal nor windowed"
        assert L % 2 == 0 and L // 2 % t.bq == 0 and L // 2 % t.bk == 0, \
            "block diffusion: tiles (%d, %d) must divide each copy's %d " \
            "rows" % (t.bq, t.bk, L // 2)
    return _Geom(t.bq, t.bk, t.sub, -(-L // t.bq), -(-L // t.bk),
                 bool(causal), int(window), L, int(block_len), bool(select))


def _params(kernel, t: Tiles, d, dtype, interpret, select=False, L=0):
    if interpret:
        return None
    return _dims(_vmem_limit(kernel, t, d, jnp.dtype(dtype).itemsize, select,
                             L), 4 if kernel == "bwd" else 3)


def _sel_operand(kernel, n_in: int, sel, block, index_map):
    """(kernel, extra specs, extra operands) of a call: with a selection
    its block goes in as operand ``n_in``, behind the plain kernel's."""
    if sel is None:
        return kernel, [], []
    return _with_sel(kernel, n_in), [pl.BlockSpec(block, index_map)], [sel]


def _fwd_call(qf, kf, vf, L, to_kv, t: Tiles, causal, scale, window,
              interpret, block_len=0, sel=None):
    """Forward kernel on merged, padded (rows, Lp, d) arrays: the output
    and the lane-dense logsumexp (rows, 1, Lpq). ``sel`` (b, Lpq, Lpk)
    int8: the selection, where the mask is one."""
    bh, Lpq, d = qf.shape
    g = _geom(t, L, causal, window, block_len, sel is not None)
    kv_spec = pl.BlockSpec(
        (1, t.bk, d), lambda r, i, s: (to_kv(r), g.kv_block(i, s), 0))
    heads = bh // sel.shape[0] if sel is not None else 1
    kernel, sel_specs, sel_args = _sel_operand(
        _fwd_kernel, 3, sel, (1, t.bq, t.bk),
        lambda r, i, s: (r // heads, i, g.kv_block(i, s)))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, g=g),
        grid=(bh, g.n_q, g.kv_steps()),
        in_specs=[
            pl.BlockSpec((1, t.bq, d), lambda r, i, s: (r, i, 0)),
            kv_spec, kv_spec,
        ] + sel_specs,
        out_specs=[
            pl.BlockSpec((1, t.bq, d), lambda r, i, s: (r, i, 0)),
            pl.BlockSpec((1, 1, t.bq), lambda r, i, s: (r, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Lpq, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, 1, Lpq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.bq, _LANES), jnp.float32),
            pltpu.VMEM((t.bq, _LANES), jnp.float32),
            pltpu.VMEM((t.bq, d), jnp.float32),
        ],
        compiler_params=_params("fwd", t, d, qf.dtype, interpret,
                                sel is not None),
        interpret=interpret,
    )(qf, kf, vf, *sel_args)


def _dq_call(qf, kf, vf, dof, lse, delta, L, to_kv, t: Tiles, causal,
             scale, window, interpret, block_len=0, sel=None):
    bh, Lpq, d = qf.shape
    g = _geom(t, L, causal, window, block_len, sel is not None)
    q_spec = pl.BlockSpec((1, t.bq, d), lambda r, i, s: (r, i, 0))
    kv_spec = pl.BlockSpec(
        (1, t.bk, d), lambda r, i, s: (to_kv(r), g.kv_block(i, s), 0))
    row_spec = pl.BlockSpec((1, 1, t.bq), lambda r, i, s: (r, 0, i))
    heads = bh // sel.shape[0] if sel is not None else 1
    kernel, sel_specs, sel_args = _sel_operand(
        _dq_kernel, 6, sel, (1, t.bq, t.bk),
        lambda r, i, s: (r // heads, i, g.kv_block(i, s)))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, g=g),
        grid=(bh, g.n_q, g.kv_steps()),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + sel_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, Lpq, d), qf.dtype),
        scratch_shapes=[
            pltpu.VMEM((t.bq, _LANES), jnp.float32),
            pltpu.VMEM((t.bq, _LANES), jnp.float32),
            pltpu.VMEM((t.bq, d), jnp.float32),
        ],
        compiler_params=_params("dq", t, d, qf.dtype, interpret,
                                sel is not None),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta, *sel_args)


def _dkv_call(qf, kf, vf, dof, lse, delta, L, grp, t: Tiles, causal,
              scale, window, interpret, block_len=0, sel=None):
    """dK and dV at key-value resolution: grid rows are the kv heads, the
    streamed dimension runs over the group's query heads x the q tiles
    that reach the resident kv tile. ``sel`` (b, Lpq, Lpk) int8: the
    selection, queries first; the kernel turns its tiles."""
    bkv, Lpk, d = kf.shape
    g = _geom(t, L, causal, window, block_len, sel is not None)
    steps = g.q_steps()
    kvheads = bkv // sel.shape[0] if sel is not None else 1
    kernel, sel_specs, sel_args = _sel_operand(
        _dkv_kernel, 6, sel, (1, t.bq, t.bk),
        lambda r, j, s: (r // kvheads, g.q_block(j, s % steps), j))

    # step s: query head s // steps of kv head r's group, its q tile
    q_spec = pl.BlockSpec((1, t.bq, d), lambda r, j, s: (
        r * grp + s // steps, g.q_block(j, s % steps), 0))
    row_spec = pl.BlockSpec((1, 1, t.bq), lambda r, j, s: (
        r * grp + s // steps, 0, g.q_block(j, s % steps)))
    kv_spec = pl.BlockSpec((1, t.bk, d), lambda r, j, s: (r, j, 0))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, g=g, steps=steps),
        grid=(bkv, g.n_k, grp * steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + sel_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, Lpk, d), kf.dtype),
            jax.ShapeDtypeStruct((bkv, Lpk, d), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.bk, d), jnp.float32),
            pltpu.VMEM((t.bk, d), jnp.float32),
        ],
        compiler_params=_params("dkv", t, d, qf.dtype, interpret,
                                sel is not None),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta, *sel_args)


def _bwd_call(qf, kf, vf, dof, lse, delta, L, grp, t: Tiles, causal,
              scale, window, interpret, block_len=0, sel=None):
    """dQ, dK and dV from the fused kernel, on the dK/dV kernel's tiles:
    grid (kv heads, the group's query heads, kv tiles, q steps). ``sel``
    (b, Lpq, Lpk) int8: the selection, queries first."""
    bkv, Lpk, d = kf.shape
    bh, Lpq, _ = qf.shape
    g = _geom(t, L, causal, window, block_len, sel is not None)
    kvheads = bkv // sel.shape[0] if sel is not None else 1
    kernel, sel_specs, sel_args = _sel_operand(
        _bwd_kernel, 6, sel, (1, t.bq, t.bk),
        lambda r, h, j, s: (r // kvheads, g.q_block(j, s), j))
    # step (r, h, j, s): query head h of kv head r's group, its q tile
    q_spec = pl.BlockSpec((1, t.bq, d), lambda r, h, j, s: (
        r * grp + h, g.q_block(j, s), 0))
    row_spec = pl.BlockSpec((1, 1, t.bq), lambda r, h, j, s: (
        r * grp + h, 0, g.q_block(j, s)))
    kv_spec = pl.BlockSpec((1, t.bk, d), lambda r, h, j, s: (r, j, 0))
    # dK / dV leave at the group's last head: before it their block stands
    # still, so nothing is written back
    dkv_spec = pl.BlockSpec((1, t.bk, d), lambda r, h, j, s: (
        r, jnp.where(h == grp - 1, j, 0), 0))
    dq_spec = pl.BlockSpec((1, Lpq, d), lambda r, h, j, s: (
        r * grp + h, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, g=g),
        grid=(bkv, grp, g.n_k, g.q_steps()),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + sel_specs,
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Lpq, d), qf.dtype),
            jax.ShapeDtypeStruct((bkv, Lpk, d), kf.dtype),
            jax.ShapeDtypeStruct((bkv, Lpk, d), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, Lpq), jnp.float32),
            pltpu.VMEM((Lpk, d), jnp.float32),
            pltpu.VMEM((Lpk, d), jnp.float32),
            pltpu.VMEM((d, t.bk), kf.dtype),
        ],
        compiler_params=_params("bwd", t, d, qf.dtype, interpret,
                                sel is not None, L),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta, *sel_args)


def _shape_tiles(q, k, window, tiles, block_len=0):
    b, h, L, d = q.shape
    assert h % k.shape[1] == 0, "query heads must be a multiple of kv heads"
    if tiles is None:
        tiles = _tiling(L, d, jnp.dtype(q.dtype).itemsize, window,
                        block_len)
    return tiles


def schedule(q, k, causal: bool, window: int = 0,
             block_len: int = 0, select: bool = False) -> dict:
    """What ``flash_attention`` will do with these (b, heads, L, d)
    operands, from their shapes alone: the forward kernel's q tile and the
    kv columns one pass of its body takes (``block_q``, ``block_k``), and
    how many such score tiles of one head's grid hold no masked score,
    straddle an edge, or are never visited (``full``, ``edge``,
    ``skipped``). Under a selection (``select``) every visited tile is an
    edge tile: its mask is data."""
    fwd = _shape_tiles(q, k, window, None, block_len)[0]
    full, edge, skipped = tile_counts(
        _geom(fwd, q.shape[2], causal, window, block_len, select))
    return {"block_q": fwd.bq, "block_k": fwd.sub, "full": full,
            "edge": edge, "skipped": skipped}


def _flash_fwd(q, k, v, causal, scale, interpret, window=0, tiles=None,
               block_len=0, sel=None):
    b, h, L, d = q.shape
    if scale is None:
        scale = d ** -0.5
    assert window == 0 or causal, "window attention requires causal"
    t = _shape_tiles(q, k, window, tiles, block_len)[0]
    Lq, Lk = _padded_len(L, t.bq), _padded_len(L, t.bk)
    qf = _pad_seq(_merge_bh(q), Lq)
    kf, vf = (_pad_seq(_merge_bh(x), Lk) for x in (k, v))
    out, lse = _fwd_call(qf, kf, vf, L, _kv_row_map(h, k.shape[1]), t,
                         causal, scale, window, interpret, block_len,
                         None if sel is None else _pad_sel(sel, Lq, Lk))
    out = out[:, :L].reshape(b, h, L, d)
    # the residual is trimmed to L: the backward pads to its own tiles
    return out, (q, k, v, out, lse[:, :, :L])


def _flash_bwd(causal, scale, interpret, window, tiles, block_len, res, g,
               sel=None, fused=None):
    """The three gradients: from the fused kernel where
    ``fused_bwd_supported`` (or ``fused``, the tests' and the tools' way
    to ask for either path) says so, else from the split pair."""
    q, k, v, out, lse = res
    b, h, L, d = q.shape
    nkv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    _, t_dq, t_dkv = _shape_tiles(q, k, window, tiles, block_len)
    if fused is None:
        fused = fused_bwd_supported(L, d, q.dtype, window, block_len,
                                    sel is not None, tiles)
    # D = rowsum(dO ∘ O), computed once here (cheap elementwise + reduce,
    # XLA fuses it) and given to the kernels as a lane-dense row like
    # lse; padded rows have dO = 0 so their D is 0 and every padded-row
    # contribution to dk/dv vanishes
    delta = jnp.sum(_merge_bh(g).astype(jnp.float32)
                    * _merge_bh(out).astype(jnp.float32),
                    axis=-1)[:, None, :]

    def padded(t):
        """A kernel's operands at its own tiles, the selection's last."""
        Lq, Lk = _padded_len(L, t.bq), _padded_len(L, t.bk)
        qf, dof = (_pad_seq(_merge_bh(x), Lq) for x in (q, g))
        kf, vf = (_pad_seq(_merge_bh(x), Lk) for x in (k, v))
        return (qf, kf, vf, dof, _pad_seq(lse, Lq, 2),
                _pad_seq(delta, Lq, 2)), (
                    None if sel is None else _pad_sel(sel, Lq, Lk))

    if fused:
        args, mask = padded(t_dkv)
        dq, dk, dv = _bwd_call(*args, L, h // nkv, t_dkv, causal, scale,
                               window, interpret, block_len, mask)
    else:
        args, mask = padded(t_dq)
        dq = _dq_call(*args, L, _kv_row_map(h, nkv), t_dq, causal, scale,
                      window, interpret, block_len, mask)
        args, mask = padded(t_dkv)
        dk, dv = _dkv_call(*args, L, h // nkv, t_dkv, causal, scale,
                           window, interpret, block_len, mask)
    return (dq[:, :L].reshape(b, h, L, d),
            dk[:, :L].reshape(b, nkv, L, d),
            dv[:, :L].reshape(b, nkv, L, d))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# the fourth mask: a selection that is data


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_selected(q, k, v, sel, scale: Optional[float] = None,
                             interpret: bool = False, tiles=None):
    """``flash_attention`` where query t keeps the keys ``sel[b, t, :]``
    marks: ``sel`` (b, L, L) int8, nonzero = kept, every kept key at or
    under the diagonal and at least one a query. Returns the output and
    the rows' logsumexp (b, h, L) float32 over the kept scores (what
    ``selected_probs`` reads); the logsumexp passes no gradient back."""
    out, res = _selected_fwd(q, k, v, sel, scale, interpret, tiles)
    return out, res[4].reshape(q.shape[:3])


def _pad_sel(sel, Lq: int, Lk: int):
    return jnp.pad(sel, ((0, 0), (0, Lq - sel.shape[1]),
                         (0, Lk - sel.shape[2])))


def _selected_fwd(q, k, v, sel, scale, interpret, tiles):
    """``_flash_fwd`` under the selection; the residual carries it."""
    out, res = _flash_fwd(q, k, v, True, scale, interpret, 0, tiles, 0, sel)
    return out, res + (sel,)


def _selected_vjp_fwd(q, k, v, sel, scale, interpret, tiles):
    out, res = _selected_fwd(q, k, v, sel, scale, interpret, tiles)
    return (out, res[4].reshape(q.shape[:3])), res


def _selected_vjp_bwd(scale, interpret, tiles, res, g):
    # the logsumexp's cotangent, g[1], is not read
    sel = res[5]
    return _flash_bwd(True, scale, interpret, 0, tiles, 0, res[:5], g[0],
                      sel) + (np.zeros(sel.shape, jax.dtypes.float0),)


flash_attention_selected.defvjp(_selected_vjp_fwd, _selected_vjp_bwd)


def _probs_kernel(q_ref, k_ref, lse_ref, sel_ref, p_ref, *, scale, nh, grp):
    """One (bq, bk) tile of the heads' mean probability: for every query
    head exp(q k^T scale - lse) on the kept scores, summed, over nh. A
    tile wholly above the diagonal is nought."""
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = p_ref.shape[1], p_ref.shape[2]

    @pl.when(j * bk > i * bq + (bq - 1))
    def _():
        p_ref[0] = jnp.zeros((bq, bk), jnp.float32)

    @pl.when(j * bk <= i * bq + (bq - 1))
    def _():
        keep = sel_ref[0].astype(jnp.int32) != 0
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(nh):
            s = _dot(q_ref[0, h], k_ref[0, h // grp], _NT) * scale
            lse = _lanes(_to_col(lse_ref[0, h:h + 1, :]), bk)
            acc = acc + jnp.exp(s - lse)
        p_ref[0] = jnp.where(keep, acc * (1.0 / nh), 0.0)


def selected_probs(q, k, lse, sel, scale: Optional[float] = None,
                   interpret: bool = False, block: Tuple[int, int] = None):
    """The target an indexer learns from: ``(1 / nh) sum_h softmax_h`` of
    the attention ``flash_attention_selected`` computed, on the kept
    scores and nought elsewhere, (b, L, L) float32, made a (bq, bk) tile
    at a time with every head's scores of the tile summed in VMEM: no
    (heads, L, L) array. ``lse`` (b, nh, L) is that call's logsumexp. No
    gradient is defined: the caller detaches its operands."""
    b, nh, L, d = q.shape
    nkv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    bq, bk = block or (min(512, _fit(L, 512)), min(512, _fit(L, 512)))
    Lq, Lk = _padded_len(L, bq), _padded_len(L, bk)
    qf, kf = _pad_seq(q, Lq, 2), _pad_seq(k, Lk, 2)
    lsef = _pad_seq(lse.astype(jnp.float32), Lq, 2)
    itemsize = jnp.dtype(q.dtype).itemsize
    vmem = (2 * (itemsize * max(d, _LANES) * (nh * bq + nkv * bk)
                 + 4 * max(nh, 8) * bq + bq * bk + 4 * bq * bk)
            + 4 * 4 * bq * bk)
    p = pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, nh=nh,
                          grp=nh // nkv),
        grid=(b, Lq // bq, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, nh, bq, d), lambda r, i, j: (r, 0, i, 0)),
            pl.BlockSpec((1, nkv, bk, d), lambda r, i, j: (r, 0, j, 0)),
            pl.BlockSpec((1, nh, bq), lambda r, i, j: (r, 0, i)),
            pl.BlockSpec((1, bq, bk), lambda r, i, j: (r, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, Lq, Lk), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=2 * vmem),
        interpret=interpret,
    )(qf, kf, lsef, _pad_sel(sel, Lq, Lk))
    return p[:, :L, :L]
