"""The exact top-k selection of learned sparse attention without a sort
(PR 39): ``ops/dsa.select``'s int8 array from one kernel that reads a block
of rows once and writes its selection once.

The selection needs two numbers a row, not an order among the keys: the
``topk``-th largest score ``thr`` and, where scores tie at it, the last
index taken at that value. On ``dsa.order_key``'s int32 (which orders as
the floats do) both come from counting. A grid step holds ``rows`` rows at
their whole length in VMEM and walks them a lane-wide column tile at a
time:

* one pass writes the keys to a scratch block, ``INT32_MIN`` above the
  diagonal;
* 32 passes find ``thr`` a bit at a time, the sign first: ``cand = thr ^
  bit`` stands where ``count(key >= cand) >= topk``;
* one pass rewrites the scratch as a code: -1 where the key is above
  ``thr``, the key's index where it equals ``thr``, ``INT32_MAX`` elsewhere
  and above the diagonal; so ``count(code <= x)`` is the keys above ``thr``
  plus the ties at or before index ``x``;
* ceil(log2 L) passes find ``last``, the smallest ``x`` with ``count(code <=
  x) >= topk``, a bit at a time;
* the last pass writes ``int8(code <= last)``.

The counts add up in a (rows, 128) int32 accumulator and cross the lanes
once a pass. Column chunks wholly above the block's last row hold no key
and are not walked (the trip count comes from ``program_id``); blocks whose
rows all stand before ``topk`` write the causal mask and search nothing. A
row t keeps exactly min(t + 1, topk) keys whatever its scores hold: the
key is a total order. The loops are rolled, so the kernel compiles in a
second or two.

The golden model is ``ops/dsa.select`` (``lax.top_k`` over the same keys):
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

from . import dsa

_LANES = 128
_INT_MIN = jnp.iinfo(jnp.int32).min
_INT_MAX = jnp.iinfo(jnp.int32).max
# a grid step's blocks (float32 in and int8 out, double-buffered, and the
# int32 scratch: 13 bytes a score) stay under this; the compiler is given
# twice as much
_VMEM_BUDGET = 20 << 20


def row_block(L: int) -> int:
    """Rows a grid step holds: the most of 128, 64 and 32 (the int8
    tile's sublanes) that divide L and keep the step's blocks in the VMEM
    budget, else 0. At 8,192 x 8,192 with 2,048 kept the kernel takes
    1.86 ms with 128, 2.14 with 64, 2.77 with 32 (chip run, PR 39)."""
    for r in (128, 64, 32):
        if L % r == 0 and 13 * r * L <= _VMEM_BUDGET:
            return r
    return 0


def supports(L: int, topk: int) -> bool:
    """Shapes the kernel takes: rows of whole lane tiles in whole row
    blocks, and a selection that selects (0 < topk < L)."""
    return (pltpu is not None and L % _LANES == 0 and 0 < topk < L
            and row_block(L) > 0)


def _int8(kept):
    return jnp.where(kept, 1, 0).astype(jnp.int8)


def _kernel(s_ref, out_ref, key_ref, *, topk, rows, chunk):
    L = s_ref.shape[2]
    r0 = pl.program_id(1) * rows
    # chunks that hold a key at or before the block's last row
    live = (r0 + rows + chunk - 1) // chunk
    t = r0 + lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

    def walk(lo, hi, tile, carry=0):
        """``tile(columns, their indices, carry) -> carry`` over the lane
        tiles of chunks [lo, hi)."""
        def body(c, carry):
            for j in range(chunk // _LANES):
                off = pl.multiple_of(c * chunk + j * _LANES, _LANES)
                carry = tile(pl.ds(off, _LANES), lane + off, carry)
            return carry
        return lax.fori_loop(lo, hi, body, carry)

    @pl.when(r0 + rows <= topk)
    def _():
        def causal(cols, s, carry):
            out_ref[0, :, cols] = _int8(s <= t)
            return carry
        walk(0, L // chunk, causal)

    @pl.when(r0 + rows > topk)
    def _():
        def count(compare, cand):
            def tile(cols, s, acc):
                return acc + jnp.where(compare(key_ref[:, cols], cand), 1, 0)
            acc = walk(0, live, tile, jnp.zeros((rows, _LANES), jnp.int32))
            return jnp.sum(acc, axis=1, keepdims=True)

        def keys(cols, s, carry):
            key_ref[:, cols] = jnp.where(
                s <= t, dsa.order_key(s_ref[0, :, cols]), _INT_MIN)
            return carry
        walk(0, live, keys)

        def thr_bit(i, thr):
            cand = thr ^ jnp.left_shift(jnp.int32(1), 31 - i)
            return jnp.where(count(jnp.greater_equal, cand) >= topk, cand,
                             thr)
        thr = lax.fori_loop(0, 32, thr_bit,
                            jnp.full((rows, _LANES), _INT_MIN, jnp.int32))

        def codes(cols, s, carry):
            key = key_ref[:, cols]
            # rows before topk (a block astride it) keep every causal key
            code = jnp.where((key > thr) | (t < topk), -1,
                             jnp.where(key == thr, s, _INT_MAX))
            key_ref[:, cols] = jnp.where(s <= t, code, _INT_MAX)
            return carry
        walk(0, live, codes)

        nbits = max(1, (L - 1).bit_length())

        def last_bit(i, last):
            bit = jnp.left_shift(jnp.int32(1), nbits - 1 - i)
            # the largest index with this bit clear under the bits so far
            enough = count(jnp.less_equal, last | (bit - 1)) >= topk
            return jnp.where(enough, last, last | bit)
        last = lax.fori_loop(0, nbits, last_bit,
                             jnp.zeros((rows, _LANES), jnp.int32))

        def chosen(cols, s, carry):
            out_ref[0, :, cols] = _int8(key_ref[:, cols] <= last)
            return carry
        walk(0, live, chosen)

        def nothing(cols, s, carry):
            out_ref[0, :, cols] = jnp.zeros((rows, _LANES), jnp.int8)
            return carry
        walk(live, L // chunk, nothing)


@functools.partial(jax.jit, static_argnums=(1, 2))
def select(scores, topk: int, interpret: bool = False):
    """``scores`` (b, L, L) float32 -> int8 (b, L, L), ``dsa.select``'s
    array. The caller gates on supports(). Jitted, so that a step's
    layers of one shape trace and lower the kernel once between them."""
    b, L, _ = scores.shape
    rows = row_block(L)
    # columns a rolled step walks: 256 to 2,048 read within 0.05 ms of
    # each other at the cell's shape
    chunk = next(c for c in (512, 256, _LANES) if L % c == 0)
    block = pl.BlockSpec((1, rows, L), lambda i, j: (i, j, 0))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=max(2 * 13 * rows * L, 16 << 20))
    return pl.pallas_call(
        functools.partial(_kernel, topk=topk, rows=rows, chunk=chunk),
        grid=(b, L // rows),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, L, L), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, L), jnp.int32)],
        compiler_params=params,
        interpret=interpret,
        name="dsa_select",
    )(scores)
