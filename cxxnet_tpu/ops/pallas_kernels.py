"""Pallas TPU kernels for ops XLA doesn't fuse well.

The reference proves it needs a custom-kernel escape hatch (the hand-written
`InsanityPoolingExp` Plan::Eval, src/layer/insanity_pooling_layer-inl.hpp:13-100,
and mshadow's chpool for LRN); on TPU that escape hatch is Pallas
(SURVEY.md §2.11). Kernels here:

* ``lrn``: AlexNet cross-channel LRN, forward + analytic backward fused into
  one VMEM pass each. The channel-window sum is expressed as a static banded
  0/1 matrix multiplied on the MXU — (c, c) x (c, h*w) — instead of nsize
  shifted adds on the VPU: one systolic pass computes the whole window sum,
  and the band matrix transposes for the mirrored-window term in backward.
* ``lrn_nhwc``: the same layer for channels-last nets, on the layout XLA
  gives their activations on the chip (batch minor), the backward
  recomputing the norm: the residual is x alone.
* ``uniform`` / ``rrelu_mask``: the insanity layer's per-element random
  negative slope drawn with the on-core PRNG (pltpu.prng_random_bits) — no
  HBM round trip for the mask.

The LRN kernels have an `interpret` switch so their numerics are unit-tested
on CPU (tests/test_pallas.py) against the pure-XLA implementations in
ops/__init__. The PRNG kernels are TPU-only (pltpu's PRNG primitives have no
CPU interpret path) and are validated on-device by tools/check_tpu_kernels.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


def _band_matrix(c: int, nsize: int) -> np.ndarray:
    """W[i, j] = 1 iff channel j is in i's LRN window
    [i - nsize//2, i - nsize//2 + nsize) — mshadow chpool's neighborhood."""
    lo = nsize // 2
    w = np.zeros((c, c), np.float32)
    for i in range(c):
        w[i, max(0, i - lo): min(c, i - lo + nsize)] = 1.0
    return w


def _lrn_fwd_kernel(x_ref, band_ref, o_ref, n_ref, *, salpha, beta, knorm):
    # compute in f32 regardless of the activation dtype (bf16 nets); the
    # norm residual n_ref stays f32, the output is cast back
    x = x_ref[0].astype(jnp.float32)
    sq = x * x
    norm = knorm + salpha * jnp.dot(band_ref[...], sq,
                                    preferred_element_type=jnp.float32)
    n_ref[0] = norm
    o_ref[0] = (x * norm ** (-beta)).astype(o_ref.dtype)


def _lrn_bwd_kernel(x_ref, band_ref, n_ref, g_ref, dx_ref, *, salpha, beta):
    x = x_ref[0].astype(jnp.float32)
    norm = n_ref[0]
    g = g_ref[0].astype(jnp.float32)
    # dx_m = g_m n_m^-b - 2 a b x_m * sum_{i: m in w(i)} g_i x_i n_i^{-b-1}
    # the mirrored window is the band transpose
    inner = g * x * norm ** (-beta - 1.0)
    s = jax.lax.dot_general(band_ref[...], inner,
                            dimension_numbers=(((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    dx_ref[0] = (g * norm ** (-beta)
                 - (2.0 * salpha * beta) * x * s).astype(dx_ref.dtype)


def _lrn_call(x4d, nsize, salpha, beta, knorm, interpret):
    b, c, h, w = x4d.shape
    x = x4d.reshape(b, c, h * w)
    band = jnp.asarray(_band_matrix(c, nsize))
    out, norm = pl.pallas_call(
        functools.partial(_lrn_fwd_kernel, salpha=salpha, beta=beta,
                          knorm=knorm),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((c, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, c, h * w), x.dtype),
                   jax.ShapeDtypeStruct((b, c, h * w), jnp.float32)],
        interpret=interpret,
    )(x, band)
    return out.reshape(b, c, h, w), norm


def _lrn_bwd_call(x4d, norm, g4d, nsize, salpha, beta, interpret):
    b, c, h, w = x4d.shape
    x = x4d.reshape(b, c, h * w)
    g = g4d.reshape(b, c, h * w)
    band = jnp.asarray(_band_matrix(c, nsize))
    dx = pl.pallas_call(
        functools.partial(_lrn_bwd_kernel, salpha=salpha, beta=beta),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((c, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, c, h * w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, h * w), x.dtype),
        interpret=interpret,
    )(x, band, norm, g)
    return dx.reshape(b, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn(x, nsize: int, alpha: float, beta: float, knorm: float,
        interpret: bool = False):
    """Fused Pallas LRN (reference numerics: src/layer/lrn_layer-inl.hpp:52-60,
    salpha = alpha / nsize)."""
    out, _ = _lrn_call(x, nsize, alpha / nsize, beta, knorm, interpret)
    return out


def _lrn_fwd(x, nsize, alpha, beta, knorm, interpret):
    out, norm = _lrn_call(x, nsize, alpha / nsize, beta, knorm, interpret)
    return out, (x, norm)


def _lrn_bwd(nsize, alpha, beta, knorm, interpret, res, g):
    x, norm = res
    dx = _lrn_bwd_call(x, norm, g, nsize, alpha / nsize, beta, interpret)
    return (dx,)


lrn.defvjp(_lrn_fwd, _lrn_bwd)


# ---------------------------------------------------------------------------
# Channels-last LRN: one HBM pass each way
# ---------------------------------------------------------------------------
# XLA lays a channels-last activation of a conv net out batch-minor on the
# TPU ((N, H, W, C) as {0,3,2,1}: N on the lanes, C on the sublanes), so the
# kernel takes the array as (H*W, C, N) -- a bitcast of that layout, no copy
# -- and the channel window sum is the same band product as the NCHW
# kernel's, (C, C) x (C, lanes). The backward recomputes the norm from x:
# an f32 norm residual would cost four more HBM passes than the recompute.
_LRN_NHWC_BLOCK_BYTES = 2 << 20    # one operand's block in VMEM
_LRN_NHWC_SLAB_BYTES = 2 << 20     # one (C, lanes) float32 intermediate
_LRN_NHWC_VMEM_BYTES = 64 << 20    # of the 128 MiB a v5e core has


def lrn_nhwc_fits(shape, dtype) -> bool:
    """True when the channels-last kernel tiles (N, H, W, C): the batch
    fills whole lane tiles and the channels whole sublane tiles."""
    if len(shape) != 4 or jnp.dtype(dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    n, _, _, c = shape
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return n % 128 == 0 and c % sublanes == 0 and c <= 512


def _band_sum(band, v, split):
    """band (C, C) of 0/1, exact in bf16, times v (C, lanes) f32 on the
    MXU, which is idle otherwise: measured within 2-8% of a kernel with no
    window sum at all. One bf16 pass carries the bits today's bf16 square
    has; ``split`` (float32 activations) sends v as a bf16 hi/lo pair, 16
    bits a term, for 40% more time (an f32 product at ``highest`` takes
    180% more)."""
    # the precision spelled out: bf16 operands are exact here, and a
    # process-wide jax_default_matmul_precision must not reach the kernel
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT)
    hi = v.astype(jnp.bfloat16)
    s = dot(band, hi)
    if split:
        s = s + dot(band, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16))
    return s


def _neg_pow(norm, beta):
    """norm ** -beta; the usual 0.75 as two square roots."""
    if beta == 0.75:
        r = jax.lax.rsqrt(norm)
        return r * jnp.sqrt(r)
    return jnp.exp(-beta * jnp.log(norm))


def _lrn_nhwc_fwd_kernel(x_ref, band_ref, o_ref, *, salpha, beta, knorm):
    band = band_ref[...]
    split = x_ref.dtype == jnp.float32

    def slab(i, carry):
        x = x_ref[i].astype(jnp.float32)
        norm = knorm + salpha * _band_sum(band, x * x, split)
        o_ref[i] = (x * _neg_pow(norm, beta)).astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, x_ref.shape[0], slab, 0)


def _lrn_nhwc_bwd_kernel(x_ref, g_ref, band_ref, bandt_ref, dx_ref, *,
                         salpha, beta, knorm):
    band = band_ref[...]
    bandt = bandt_ref[...]
    split = x_ref.dtype == jnp.float32

    def slab(i, carry):
        x = x_ref[i].astype(jnp.float32)
        g = g_ref[i].astype(jnp.float32)
        norm = knorm + salpha * _band_sum(band, x * x, split)
        gp = g * _neg_pow(norm, beta)
        # the rule of _lrn_bwd_kernel, the norm recomputed
        s = _band_sum(bandt, gp * x / norm, split)
        dx_ref[i] = (gp - (2.0 * salpha * beta) * x * s).astype(dx_ref.dtype)
        return carry
    jax.lax.fori_loop(0, x_ref.shape[0], slab, 0)


def _lrn_nhwc_call(kernel, acts, bands, interpret):
    """``kernel`` over (H*W, C, N) operands, a block of a few H*W rows by
    all channels by one lane tile of the batch, the widest that divides N
    (measured: the wider the faster); the ragged last block of rows is
    masked by the pipeline (rows are independent)."""
    hw, c, n = acts[0].shape
    widest = max(128, _LRN_NHWC_SLAB_BYTES // (4 * c) // 128 * 128)
    nb = next(t for t in range(min(n, widest), 0, -128) if n % t == 0)
    hb = max(1, min(hw, _LRN_NHWC_BLOCK_BYTES
                    // (c * nb * acts[0].dtype.itemsize)))
    act = pl.BlockSpec((hb, c, nb), lambda i, j: (i, 0, j))
    band = pl.BlockSpec((c, c), lambda i, j: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(hw, hb), n // nb),
        in_specs=[act] * len(acts) + [band] * len(bands),
        out_specs=act,
        out_shape=jax.ShapeDtypeStruct((hw, c, n), acts[0].dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_LRN_NHWC_VMEM_BYTES),
        interpret=interpret,
    )(*acts, *bands)


def _to_hw_c_n(x):
    n, h, w, c = x.shape
    return jnp.transpose(x, (1, 2, 3, 0)).reshape(h * w, c, n)


def _from_hw_c_n(y, shape):
    n, h, w, c = shape
    return jnp.transpose(y.reshape(h, w, c, n), (3, 0, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_nhwc(x, nsize: int, alpha: float, beta: float, knorm: float,
             interpret: bool = False):
    """Fused channels-last LRN, x (N, H, W, C) with ``lrn_nhwc_fits``:
    the forward reads x and writes y, the backward reads x and g and
    writes dx; arithmetic in f32 inside, the residual is x alone."""
    return _lrn_nhwc_fwd(x, nsize, alpha, beta, knorm, interpret)[0]


def _lrn_nhwc_fwd(x, nsize, alpha, beta, knorm, interpret):
    band = jnp.asarray(_band_matrix(x.shape[3], nsize), jnp.bfloat16)
    kernel = functools.partial(_lrn_nhwc_fwd_kernel, salpha=alpha / nsize,
                               beta=beta, knorm=knorm)
    y = _lrn_nhwc_call(kernel, [_to_hw_c_n(x)], [band], interpret)
    return _from_hw_c_n(y, x.shape), x


def _lrn_nhwc_bwd(nsize, alpha, beta, knorm, interpret, x, g):
    band = _band_matrix(x.shape[3], nsize)
    kernel = functools.partial(_lrn_nhwc_bwd_kernel, salpha=alpha / nsize,
                               beta=beta, knorm=knorm)
    dx = _lrn_nhwc_call(
        kernel, [_to_hw_c_n(x), _to_hw_c_n(g)],
        [jnp.asarray(band, jnp.bfloat16), jnp.asarray(band.T, jnp.bfloat16)],
        interpret)
    return (_from_hw_c_n(dx, x.shape),)


lrn_nhwc.defvjp(_lrn_nhwc_fwd, _lrn_nhwc_bwd)


# ---------------------------------------------------------------------------
# RReLU (insanity layer) with in-kernel PRNG
# ---------------------------------------------------------------------------
def _uniform_kernel(seed_ref, u_ref):
    # one grid step = one (block_rows, 128) tile; re-seed per block so each
    # tile draws an independent stream and the whole array never has to fit
    # in VMEM at once. prng_seed hashes its operands, so (seed, block) pairs
    # never alias across neighboring seeds the way seed+block would.
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    # prng_random_bits yields int32; shift logically as uint32, then bitcast
    # back to int32 (top byte now zero) since Mosaic can't cast uint32->f32.
    # 24 high bits -> exact float32 uniform [0, 1) ladder.
    bits = pltpu.bitcast(pltpu.prng_random_bits(u_ref.shape), jnp.uint32) >> 8
    u = pltpu.bitcast(bits, jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    u_ref[...] = u.astype(u_ref.dtype)


def uniform(seed, shape, dtype=jnp.float32) -> jnp.ndarray:
    """U[0, 1) tensor drawn with the on-core TPU PRNG — no HBM round trip
    for the random bits. `seed` may be a traced int32 scalar. TPU-only:
    pltpu's PRNG primitives have no CPU interpret path, so this kernel is
    validated on-device (tools/check_tpu_kernels.py) rather than in the CPU
    suite."""
    if pltpu is None:
        raise RuntimeError(
            "pallas uniform needs TPU support (jax.experimental.pallas.tpu)")
    flat = int(np.prod(shape))
    # pad the flat draw up to a (rows, 128) lane tile, then grid over row
    # blocks so VMEM holds one ~1 MB tile at a time regardless of total size
    cols = 128
    rows = -(-flat // cols)
    block_rows = min(rows, 2048)
    grid = -(-rows // block_rows)
    seed_arr = jnp.asarray([seed], jnp.int32).reshape((1,))
    u = pl.pallas_call(
        _uniform_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((grid * block_rows, cols), dtype),
    )(seed_arr)
    return u.reshape(-1)[:flat].reshape(shape)


def rrelu_mask(seed, shape, lb, ub, dtype=jnp.float32) -> jnp.ndarray:
    """Per-element random slope in [lb, ub) — the insanity/RReLU divisor
    (reference src/layer/insanity_layer-inl.hpp:14 divides the negative part
    by U[lb, ub]); the consumer applies ops.xelu(x, mask). The affine
    transform runs in XLA (fuses with the consumer) so lb/ub may be traced
    (calm_start/calm_end annealing)."""
    u = uniform(seed, shape, dtype)
    return u * (ub - lb) + lb


def rrelu(x, seed, lb: float, ub: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training-mode insanity/RReLU forward. Returns (out, slope_mask); the
    slope draw happens in-kernel, the elementwise division stays in XLA so
    autodiff gives the xelu gradient for free."""
    mask = rrelu_mask(seed, x.shape, lb, ub, x.dtype)
    return jnp.where(x > 0, x, x / mask), mask
# (The fused max-pool backward kernel that lived here through r4 was
# deleted after losing its on-chip A/B 2:1 to XLA select-and-scatter —
# see ops.pool2d and onchip_logs/poolab.log.)
