"""The index scores' backward of learned sparse attention as one kernel
(PR 41): ``ops/dsa._scores_bwd``'s three gradients from a walk over the
causal tiles alone, each tile's products made again in VMEM.

For queries t and keys s, J heads j, ``z = qI[t, j] . kI[s]``:

    dz[j, t, s] = where(z > 0, g[t, s] w[t, j], 0)    in the compute type
    dq[j, t]    = sum_s dz[j, t, s] kI[s]
    dk[s]       = sum_{j, t} dz[j, t, s] qI[t, j]
    dw[t, j]    = sum_s relu(z) g[t, s]                float32

with ``g`` read on s <= t alone: the caller's gradient is nought above the
diagonal (``dsa.index_loss``'s is, its ``keep`` is causal), and the tiles
wholly above it are never visited. A grid step takes one (query block,
key block) pair of (t, t) tiles and loops over the heads, one float32
product a head:

* the key blocks of a query block run in order and add into its dq (a
  float32 scratch) and dw; both are written when the row of tiles ends;
* dk, kept transposed (di, L) so that no product transposes an operand,
  stays in VMEM across the whole grid of a sequence and is written once;
* the steps past a row's last live key block do nothing, and their index
  maps repeat the last live block, so they move no bytes;
* only the tiles astride the diagonal mask ``g``, once a step, before the
  head loop.

qI and kI come in transposed beside themselves (XLA's small passes over
16 MB and 1 MB at the cell's shape), so the three products are plain
(m, k) x (k, n) ones on the MXU. The golden model is ``dsa._scores_bwd``:
tests/test_dsa.py in the interpreter, tools/check_tpu_kernels.py dsa
compiled, at the cell's shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

_LANES = 128
# a grid step's blocks, scratch and one head's float32 tiles stay under
# this by _vmem_bytes; the compiler is given twice as much (a v5e core has
# 128 MiB)
_VMEM_BUDGET = 32 << 20


def tile(L: int) -> int:
    """Queries and keys a grid step takes: the most of 512, 256 and 128
    that divides L, else 0. At the cell's shape the kernel takes 2.40 ms
    with 512, 3.86 with 256, 2.57 with 1,024; the head loop unrolled
    takes 2.18 with 512 but compiles in 12.5 s against 1.2 (chip run and
    a compile for a described v5e, PR 41)."""
    return next((t for t in (512, 256, _LANES) if L % t == 0), 0)


def _vmem_bytes(L: int, J: int, di: int, t: int, itemsize: int) -> int:
    """VMEM of a grid step, the features padded to whole lane tiles."""
    lanes = -(-di // _LANES) * _LANES
    streamed = (J * t * (lanes + di) * itemsize     # qI's block, transposed
                + t * (lanes + di) * itemsize       # kI's
                + t * t * 4                         # g's
                + 2 * t * _LANES * 4                # w's and dw's
                + J * t * lanes * itemsize)         # dq's
    resident = 2 * di * L * 4                       # dK, transposed
    scratch = J * t * lanes * 4 + t * _LANES * 4 + t * t * 4
    head = 4 * t * t * 4          # one head's product and what is made of it
    return 2 * streamed + resident + scratch + head


def supports(L: int, J: int, di: int, itemsize: int) -> bool:
    """Shapes the kernel takes: sequences of whole tiles, index heads of a
    width the MXU's operands tile (a multiple of 8), and a grid step
    inside the VMEM budget for operands of ``itemsize`` bytes."""
    t = tile(L)
    return (pltpu is not None and t > 0 and J > 0 and di % 8 == 0
            and _vmem_bytes(L, J, di, t, itemsize) <= _VMEM_BUDGET)


def _dot(a, b):
    """(m, k) x (k, n) on the MXU, float32 accumulation. The precision is
    spelled out: Mosaic refuses a process-wide ``highest``
    (tests/conftest.py) for bf16 operands; float32 ones take it."""
    prec = (lax.Precision.HIGHEST if a.dtype == jnp.float32
            else lax.Precision.DEFAULT)
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=prec,
                           preferred_element_type=jnp.float32)


def _kernel(q_ref, qt_ref, k_ref, kt_ref, w_ref, g_ref,
            dq_ref, dkt_ref, dw_ref, dq_scr, dw_scr, gm_scr, *, t):
    i, j = pl.program_id(1), pl.program_id(2)
    J = q_ref.shape[1]
    t0, s0 = i * t, j * t

    @pl.when((i == 0) & (j == 0))
    def _():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    def heads(g_src):
        k, kt, w = k_ref[0], kt_ref[0], w_ref[0]
        lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
        cols = pl.ds(pl.multiple_of(s0, _LANES), t)

        def head(h, carry):
            z = _dot(q_ref[0, h], kt)                           # (t, t)
            g = g_src[...]
            wj = jnp.sum(jnp.where(lane == h, w, 0.0), axis=1, keepdims=True)
            dw = jnp.sum(jnp.maximum(z, 0.0) * g, axis=1, keepdims=True)
            dw_scr[...] += jnp.where(lane == h, dw, 0.0)
            # the products' operands in the compute type, as the plain
            # lines round them
            dz = jnp.where(z > 0.0, g * wj, 0.0).astype(k.dtype)
            dq_scr[h] += _dot(dz, k)
            dkt_ref[0, :, cols] += _dot(qt_ref[0, h], dz)
            return carry
        lax.fori_loop(0, J, head, 0)

    @pl.when(j < i)
    def _():
        heads(g_ref.at[0])

    @pl.when(j == i)
    def _():
        row = t0 + lax.broadcasted_iota(jnp.int32, (t, t), 0)
        col = s0 + lax.broadcasted_iota(jnp.int32, (t, t), 1)
        gm_scr[...] = jnp.where(col <= row, g_ref[0], 0.0)
        heads(gm_scr)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_scr[...]


@functools.partial(jax.jit, static_argnums=(4,))
def scores_bwd(qi, ki, w, g, interpret: bool = False):
    """``dsa._scores_bwd``'s (dq, dk, dw) of ``qi`` (b, J, L, di), ``ki``
    (b, L, di), ``w`` (b, L, J) float32 and the scores' gradient ``g``
    (b, L, L) float32, read on s <= t alone. The caller gates on
    supports(). Jitted, so that a step's layers of one shape trace and
    lower the kernel once between them."""
    b, J, L, di = qi.shape
    t = tile(L)
    n = L // t

    def live(i, j):
        return jnp.minimum(j, i)    # steps past the diagonal repeat it
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=2 * _vmem_bytes(L, J, di, t, qi.dtype.itemsize))
    dq, dkt, dw = pl.pallas_call(
        functools.partial(_kernel, t=t),
        grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, J, t, di), lambda c, i, j: (c, 0, i, 0)),
            pl.BlockSpec((1, J, di, t), lambda c, i, j: (c, 0, 0, i)),
            pl.BlockSpec((1, t, di), lambda c, i, j: (c, live(i, j), 0)),
            pl.BlockSpec((1, di, t), lambda c, i, j: (c, 0, live(i, j))),
            pl.BlockSpec((1, t, J), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, t, t), lambda c, i, j: (c, i, live(i, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, J, t, di), lambda c, i, j: (c, 0, i, 0)),
            pl.BlockSpec((1, di, L), lambda c, i, j: (c, 0, 0)),
            pl.BlockSpec((1, t, J), lambda c, i, j: (c, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(qi.shape, qi.dtype),
                   jax.ShapeDtypeStruct((b, di, L), jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((J, t, di), jnp.float32),
                        pltpu.VMEM((t, J), jnp.float32),
                        pltpu.VMEM((t, t), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="dsa_index_bwd",
    )(qi, jnp.swapaxes(qi, 2, 3), ki, jnp.swapaxes(ki, 1, 2), w, g)
    return dq, jnp.swapaxes(dkt, 1, 2).astype(ki.dtype), dw.astype(w.dtype)
