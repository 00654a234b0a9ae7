"""Between the qkv dot and the attention core, one pass each way (PR 37).

The dot's output is ``(b, L, (nh + 2 nkv) dh)``: the heads of q, k and v
side by side in the lanes. The core wants ``q (b, nh, L, dh)`` and ``k``,
``v (b, nkv, L, dh)``, q and k normed over each head's features (QK-norm)
and rotated by position (rope) where the layer says so. As plain HLO that
is three slices, three transposes, a float32 copy, a lane reduction and a
cast for each norm, two half-width slices, four products, a concatenate
and a cast for each rotation, and autodiff's mirror of all of it: some
twenty passes over the rows at the memory's rate. Here it is one kernel
forward and one backward:

* forward: a grid step holds a tile of rows at their whole width, walks
  the heads in a rolled loop, each a lane-tile-aligned column slice, and
  writes each to its row block of the output: the head split is index
  arithmetic of the BlockSpecs and the loop, no transpose runs. In registers, in float32: the norm's
  ``x rsqrt(mean(x^2) + eps) gain``, then the rotation as ``x cos +
  roll(x, dh/2) sin`` on the whole row, with ``(L, dh)`` tables (``sin``
  carries the first half's minus sign). One rounding, at the store.
* backward: reads dq, dk, dv and (where there is a norm) the saved qkv,
  makes the statistics again, applies the rotation's transpose (the
  negative angle) and the norm's analytic backward, and writes d(qkv) in
  the dot's layout, plus each grid step's partial of the gains'
  gradients, summed outside. The only residual is qkv itself, and a
  rotation alone keeps none.

``rope`` and ``norm`` are what the operands say (tables given, gains
given); a layer with neither has no use for this file. The golden model is
AttentionLayer's own plain lines (tests/test_qk_prep.py, in the
interpreter; tools/check_tpu_kernels.py qkprep, compiled).
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

_LANES = 128
_EPS = 1e-6
# a grid step's blocks (rows x the whole width: three of them backward),
# double-buffered, stay under this; the compiler is given twice as much
_VMEM_BUDGET = 20 << 20


def row_tile(L: int, width: int, itemsize: int) -> int:
    """Rows a grid step holds: the largest power of two up to 512 that
    divides L and keeps the backward's blocks in the VMEM budget; 0 where
    L is no multiple of 16 (a bf16 sublane tile)."""
    for t in (512, 256, 128, 64, 32, 16):
        if L % t == 0 and 2 * 3 * t * width * itemsize <= _VMEM_BUDGET:
            return t
    return 0


def supports(L: int, dh: int, width: int, itemsize: int) -> bool:
    """Shapes the kernels take: heads of whole lane tiles, rows in whole
    tiles of ``row_tile``."""
    return (pltpu is not None and dh % _LANES == 0
            and row_tile(L, width, itemsize) > 0)


def _params(interpret, blocks_bytes):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=max(2 * blocks_bytes, 16 << 20))


def _inv_rms(x):
    return jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + _EPS)


def _cols(col, dh):
    """Column block ``col`` (a head) of a row at the whole width: a
    lane-tile-aligned slice, also where ``col`` is a loop's counter. The
    kernels walk the heads in rolled loops: unrolled, a cell's forty heads
    cost seconds of tracing and lowering a step."""
    return pl.ds(pl.multiple_of(col * dh, dh), dh)


def _fwd_kernel(*refs, nh, nkv, dh, norm, rope):
    refs = list(refs)
    x_ref = refs.pop(0)
    gains = (refs.pop(0)[...], refs.pop(0)[...]) if norm else (None, None)
    if rope:
        cos, sin = refs.pop(0)[...], refs.pop(0)[...]
    q_ref, k_ref, v_ref = refs

    def prep(out_ref, n, first, gain):
        def head(h, carry):
            x = x_ref[0, :, _cols(first + h, dh)].astype(jnp.float32)
            if norm:
                x = x * _inv_rms(x) * gain
            if rope:
                x = x * cos + pltpu.roll(x, dh // 2, 1) * sin
            out_ref[0, h] = x.astype(out_ref.dtype)
            return carry
        jax.lax.fori_loop(0, n, head, 0)

    def copy(h, carry):
        v_ref[0, h] = x_ref[0, :, _cols(nh + nkv + h, dh)]
        return carry

    prep(q_ref, nh, 0, gains[0])
    prep(k_ref, nkv, nh, gains[1])
    jax.lax.fori_loop(0, nkv, copy, 0)


def _bwd_kernel(*refs, nh, nkv, dh, norm, rope):
    refs = list(refs)
    dq_ref, dk_ref, dv_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    if norm:
        x_ref = refs.pop(0)
        gains = (refs.pop(0)[...], refs.pop(0)[...])
    if rope:
        cos, sin = refs.pop(0)[...], refs.pop(0)[...]
    dx_ref = refs.pop(0)

    def back(d_ref, n, first, which):
        """d(input columns) of the ``n`` heads from column block ``first``
        on and, under a norm, this tile's row of their gain's gradient."""
        def head(h, acc):
            cols = _cols(first + h, dh)
            dy = d_ref[0, h].astype(jnp.float32)
            if rope:
                # the transpose of the rotation: by the negative angle
                dy = dy * cos - pltpu.roll(dy, dh // 2, 1) * sin
            if norm:
                x = x_ref[0, :, cols].astype(jnp.float32)
                inv = _inv_rms(x)
                xn = x * inv
                acc = acc + dy * xn
                u = dy * gains[which]
                dy = (u - xn * jnp.mean(u * xn, axis=-1, keepdims=True)) * inv
            dx_ref[0, :, cols] = dy.astype(dx_ref.dtype)
            return acc
        acc = jax.lax.fori_loop(
            0, n, head, jnp.zeros(d_ref.shape[2:], jnp.float32))
        if norm:
            refs[which][0] = jnp.sum(acc, axis=0, keepdims=True)

    def copy(h, carry):
        dx_ref[0, :, _cols(nh + nkv + h, dh)] = dv_ref[0, h]
        return carry

    back(dq_ref, nh, 0, 0)
    back(dk_ref, nkv, nh, 1)
    jax.lax.fori_loop(0, nkv, copy, 0)


def _specs(nh, nkv, dh, t, qnorm, knorm, cos, sin):
    """BlockSpecs over the grid (batch, row tiles): the rows at their
    whole width, a head-major operand's tile of every head; and what both
    kernels read beside the rows, with its specs: the gains as float32
    (1, dh) where there is a norm, a tile of each table where there is a
    rotation."""
    width = (nh + 2 * nkv) * dh
    wide = pl.BlockSpec((1, t, width), lambda i, j: (i, j, 0))

    def heads(n):
        return pl.BlockSpec((1, n, t, dh), lambda i, j: (i, 0, j, 0))

    extras, specs = [], []
    if qnorm is not None:
        extras += [g.astype(jnp.float32).reshape(1, dh)
                   for g in (qnorm, knorm)]
        specs += [pl.BlockSpec((1, dh), lambda i, j: (0, 0))] * 2
    if cos is not None:
        extras += [cos, sin]
        specs += [pl.BlockSpec((t, dh), lambda i, j: (j, 0))] * 2
    return wide, heads, extras, specs


def _fwd_call(qkv, qnorm, knorm, cos, sin, nh, nkv, dh, interpret):
    b, L, width = qkv.shape
    itemsize = qkv.dtype.itemsize
    t = row_tile(L, width, itemsize)
    wide, heads, extras, specs = _specs(nh, nkv, dh, t, qnorm, knorm, cos,
                                        sin)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nh=nh, nkv=nkv, dh=dh,
                          norm=qnorm is not None, rope=cos is not None),
        grid=(b, L // t),
        in_specs=[wide] + specs,
        out_specs=[heads(nh), heads(nkv), heads(nkv)],
        out_shape=[jax.ShapeDtypeStruct((b, n, L, dh), qkv.dtype)
                   for n in (nh, nkv, nkv)],
        compiler_params=_params(interpret, 2 * 2 * t * width * itemsize),
        interpret=interpret,
        name="qk_prep_fwd",
    )(qkv, *extras)


def _bwd_call(dq, dk, dv, qkv, qnorm, knorm, cos, sin, dh, interpret):
    b, nh, L, _ = dq.shape
    nkv = dk.shape[1]
    norm = qnorm is not None
    width = (nh + 2 * nkv) * dh
    itemsize = dq.dtype.itemsize
    t = row_tile(L, width, itemsize)
    nt = L // t
    wide, heads, extras, specs = _specs(nh, nkv, dh, t, qnorm, knorm, cos,
                                        sin)
    out_specs = [wide]
    out_shape = [jax.ShapeDtypeStruct((b, L, width), dq.dtype)]
    if norm:
        # a grid step's own row of each gain's gradient
        out_specs += [pl.BlockSpec((1, 1, dh),
                                   lambda i, j: (i * nt + j, 0, 0))] * 2
        out_shape += [jax.ShapeDtypeStruct((b * nt, 1, dh), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, nh=nh, nkv=nkv, dh=dh, norm=norm,
                          rope=cos is not None),
        grid=(b, nt),
        in_specs=([heads(nh), heads(nkv), heads(nkv)]
                  + ([wide] if norm else []) + specs),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(
            interpret, 2 * (3 if norm else 2) * t * width * itemsize),
        interpret=interpret,
        name="qk_prep_bwd",
    )(dq, dk, dv, *([qkv] if norm else []), *extras)
    if not norm:
        return out[0], None, None
    dx, dgq, dgk = out
    return (dx, jnp.sum(dgq, axis=(0, 1)).astype(qnorm.dtype),
            jnp.sum(dgk, axis=(0, 1)).astype(knorm.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def qk_prep(qkv, qnorm, knorm, cos, sin, nh: int, nkv: int, dh: int,
            interpret: bool = False):
    """``qkv (b, L, (nh + 2 nkv) dh)`` -> ``q (b, nh, L, dh)``, ``k``,
    ``v (b, nkv, L, dh)`` in qkv's type; q and k normed by ``qnorm`` /
    ``knorm`` (dh,) where given (both or neither) and rotated by the
    ``(L, dh)`` float32 tables ``cos`` / ``sin`` where given (``sin``
    signed: minus on the first half). The caller gates on supports()."""
    return _fwd_call(qkv, qnorm, knorm, cos, sin, nh, nkv, dh, interpret)


def _qk_prep_fwd(qkv, qnorm, knorm, cos, sin, nh, nkv, dh, interpret):
    out = _fwd_call(qkv, qnorm, knorm, cos, sin, nh, nkv, dh, interpret)
    # the norm's backward reads its input again; a rotation's needs none
    return out, (qkv if qnorm is not None else None, qnorm, knorm, cos, sin)


def _qk_prep_bwd(nh, nkv, dh, interpret, res, g):
    qkv, qnorm, knorm, cos, sin = res
    dx, dgq, dgk = _bwd_call(*g, qkv, qnorm, knorm, cos, sin, dh, interpret)
    zero = None if cos is None else jnp.zeros_like(cos)
    return dx, dgq, dgk, zero, zero


qk_prep.defvjp(_qk_prep_fwd, _qk_prep_bwd)
