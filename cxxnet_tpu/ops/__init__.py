"""TPU-native op library: the jax/XLA equivalents of the mshadow expressions
consumed by the reference (inventory: SURVEY.md §2.11).

Each function here replaces one mshadow expression-template kernel:
conv2d          <- unpack_patch2col + dot + swapaxis   (src/layer/convolution_layer-inl.hpp:79-105)
pool2d          <- pool<Reducer> / unpool              (src/layer/pooling_layer-inl.hpp)
chpool_sum      <- chpool<red::sum>                    (LRN, src/layer/lrn_layer-inl.hpp:55-60)
softmax         <- mshadow::Softmax                    (src/layer/loss/softmax_layer-inl.hpp)

Design notes (TPU):
* conv lowers to the MXU through lax.conv_general_dilated with
  feature_group_count for grouped conv (ngroup) — no im2col materialization,
  XLA tiles directly.
* pooling/LRN lower to lax.reduce_window; XLA fuses the elementwise pre/post
  ops into the window reduction.
* shape semantics replicate the reference exactly (ceil-mode pooling with
  clamp) so config-declared nets produce identical node shapes.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def conv_out_dim(x: int, k: int, s: int, p: int) -> int:
    """Conv output size, reference: src/layer/convolution_layer-inl.hpp:180-183."""
    return (x + 2 * p - k) // s + 1


def pool_out_dim(x: int, k: int, s: int) -> int:
    """Pooling output size (ceil-mode with clamp),
    reference: src/layer/pooling_layer-inl.hpp:104-106."""
    return min(x - k + s - 1, x - 1) // s + 1


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
           pad: Tuple[int, int] = (0, 0), groups: int = 1,
           layout: str = "NCHW") -> jnp.ndarray:
    """2-D convolution. x: (N, C, H, W) — or (N, H, W, C) with
    layout="NHWC", the TPU-preferred channels-last activation layout
    (measured +24% on the inception topology, doc/performance.md).
    w is always (O, C/groups, KH, KW) OIHW — the reference's storage layout
    — so params, checkpoints, and TP shardings are layout-independent; XLA
    folds the (small) kernel transpose into its conv emitter.

    Result dtype follows the inputs: under bf16 mixed precision the MXU
    still accumulates each pass in f32 internally, and keeping the output
    bf16 gives JAX's conv transpose matching dtypes (a forced f32
    preferred_element_type breaks the backward pass for bf16 operands)."""
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=(layout, "OIHW", layout),
        feature_group_count=groups,
    )


def to_nhwc(x: jnp.ndarray) -> jnp.ndarray:
    """(N, C, H, W) -> (N, H, W, C)."""
    return jnp.transpose(x, (0, 2, 3, 1))


def to_nchw(x: jnp.ndarray) -> jnp.ndarray:
    """(N, H, W, C) -> (N, C, H, W)."""
    return jnp.transpose(x, (0, 3, 1, 2))


def _pool_padding(h: int, w: int, k: Tuple[int, int], s: int):
    oh, ow = pool_out_dim(h, k[0], s), pool_out_dim(w, k[1], s)
    ph = max((oh - 1) * s + k[0] - h, 0)
    pw = max((ow - 1) * s + k[1] - w, 0)
    return (oh, ow), (ph, pw)


def pool2d(x: jnp.ndarray, mode: str, kernel: Tuple[int, int], stride: int,
           pad: Tuple[int, int] = (0, 0),
           layout: str = "NCHW") -> jnp.ndarray:
    """Pooling with the reference's ceil-mode output shape.

    mode: 'max' | 'sum' | 'avg'. avg divides by k*k regardless of padding,
    matching src/layer/pooling_layer-inl.hpp:44-46. ``pad`` adds symmetric
    input padding first (beyond the reference — needed for same-size pool
    towers, e.g. GoogLeNet's 3x3/1 pool branch); max pads with -inf, so
    padding never wins the max. layout="NHWC" pools a channels-last input
    (window over axes 1,2).

    The max mode's backward is XLA's select-and-scatter: on a tie one
    input of the window gets the gradient, where the reference's unpool
    gives it to every tied input; the gradient's sum is the same.
    """
    if layout == "NHWC":
        n, h, w, c = x.shape
    else:
        n, c, h, w = x.shape
    py, px = pad
    (_, _), (ph, pw) = _pool_padding(h + 2 * py, w + 2 * px, kernel, stride)
    if layout == "NHWC":
        window = (1, kernel[0], kernel[1], 1)
        strides = (1, stride, stride, 1)
        padding = [(0, 0), (py, py + ph), (px, px + pw), (0, 0)]
    else:
        window = (1, 1, kernel[0], kernel[1])
        strides = (1, 1, stride, stride)
        padding = [(0, 0), (0, 0), (py, py + ph), (px, px + pw)]
    if mode == "max":
        return lax.reduce_window(x, -jnp.inf, lax.max, window,
                                 strides, padding)
    elif mode in ("sum", "avg"):
        out = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if mode == "avg":
            out = out * (1.0 / (kernel[0] * kernel[1]))
    else:
        raise ValueError("unknown pooling mode %s" % mode)
    return out


def chpool_sum(x: jnp.ndarray, nsize: int, axis: int = 1) -> jnp.ndarray:
    """Cross-channel sliding-window sum (mshadow chpool<red::sum>).

    For channel i, sums channels [i - nsize//2, i - nsize//2 + nsize) clipped
    to the valid range — the AlexNet LRN neighborhood. ``axis`` is the
    channel dimension (1 for NCHW, 3 for NHWC).
    """
    pad_lo = nsize // 2
    pad_hi = nsize - 1 - pad_lo
    window = [1, 1, 1, 1]
    window[axis] = nsize
    padding = [(0, 0)] * 4
    padding[axis] = (pad_lo, pad_hi)
    return lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=tuple(window),
        window_strides=(1, 1, 1, 1),
        padding=padding,
    )


def lrn_xla(x: jnp.ndarray, nsize: int, alpha: float, beta: float,
            knorm: float) -> jnp.ndarray:
    """Pure-XLA LRN (reduce_window channel sum), the golden model for the
    Pallas kernel and the non-TPU fallback."""
    salpha = alpha / nsize
    norm = chpool_sum(jnp.square(x), nsize) * salpha + knorm
    return x * jnp.power(norm, -beta)


_use_pallas = None  # tri-state: None = auto (TPU only), True/False = forced


def set_use_pallas(flag) -> None:
    """Force (True/False) or reset (None = auto) Pallas kernel dispatch."""
    global _use_pallas
    _use_pallas = flag


def use_pallas() -> bool:
    if _use_pallas is not None:
        return _use_pallas
    return jax.default_backend() == "tpu"


def preload_pallas() -> None:
    """Start importing the Pallas kernels on a helper thread, where they
    will be taken (use_pallas). jax's Pallas package takes a second to
    import (1.25 s of ``setup_s`` on a one-chip machine, 2 s on four, PR 28) and is
    first needed when a step with such a layer is traced; until then
    set-up waits on the device for seconds with the interpreter idle.
    Whoever asks for the module before the import has ended waits for it
    (the import lock)."""
    if use_pallas():
        import importlib
        import threading
        threading.Thread(target=importlib.import_module,
                         args=(__name__ + ".pallas_kernels",),
                         name="preload-pallas", daemon=True).start()


def pallas_interpret() -> bool:
    """Off the TPU the Pallas kernels run in the interpreter, so forced-on
    tests (and CPU debugging) execute the exact kernel code; on the TPU
    they are compiled by Mosaic."""
    return jax.default_backend() != "tpu"


def lrn_nhwc(x: jnp.ndarray, nsize: int, alpha: float, beta: float,
             knorm: float) -> jnp.ndarray:
    """Channels-last LRN in plain HLO: the cross-channel window sum is a
    reduce_window over the last axis and the backward is jax's autodiff of
    it. The non-TPU path and the golden model of the fused kernel
    (pallas_kernels.lrn_nhwc)."""
    salpha = alpha / nsize
    norm = chpool_sum(jnp.square(x), nsize, axis=3) * salpha + knorm
    return x * jnp.power(norm, -beta)


def lrn_fused(shape, dtype, layout: str = "NCHW") -> bool:
    """Whether ``lrn`` takes a fused Pallas kernel for this input: on a TPU
    (use_pallas), and channels-last only a shape the kernel tiles.
    LRNLayer asks with the per-device shape: on a mesh the kernel has to
    run inside shard_map (pallas_call has no partitioning rule)."""
    if not use_pallas():
        return False
    if layout == "NHWC":
        from . import pallas_kernels
        return pallas_kernels.lrn_nhwc_fits(shape, dtype)
    return True


def lrn_reduce_window(x: jnp.ndarray, nsize: int, alpha: float, beta: float,
                      knorm: float, layout: str = "NCHW") -> jnp.ndarray:
    """The reduce_window path of either layout (lrn_xla / lrn_nhwc), where
    no fused kernel is taken; counts ``lrn.fallback``."""
    from ..utils import telemetry
    telemetry.count("lrn.fallback")
    if layout == "NHWC":
        return lrn_nhwc(x, nsize, alpha, beta, knorm)
    return lrn_xla(x, nsize, alpha, beta, knorm)


def lrn(x: jnp.ndarray, nsize: int, alpha: float, beta: float, knorm: float,
        layout: str = "NCHW") -> jnp.ndarray:
    """Local response normalization across channels
    (reference: src/layer/lrn_layer-inl.hpp:52-60). On a TPU both layouts
    take a fused Pallas kernel (one HBM pass each way, analytic backward,
    the window sum a band product on the MXU): NCHW always, NHWC where
    the shape tiles (batch a multiple of 128, channels of the sublane
    tile); elsewhere the reduce_window path. Counts ``lrn.fused`` /
    ``lrn.fallback`` once per traced layer."""
    if not lrn_fused(x.shape, x.dtype, layout):
        return lrn_reduce_window(x, nsize, alpha, beta, knorm, layout)
    from ..utils import telemetry
    from . import pallas_kernels
    telemetry.count("lrn.fused")
    kernel = (pallas_kernels.lrn_nhwc if layout == "NHWC"
              else pallas_kernels.lrn)
    return kernel(x, nsize, alpha, beta, knorm, pallas_interpret())


def flash_supported(L: int, d: int, block_len: int = 0) -> bool:
    """True when (seq, head_dim) fits the Pallas flash-attention tiling
    (under the block-diffusion mask of ``block_len``, if given)."""
    from . import flash_attn as _fa
    return _fa.supports(L, d, block_len)


def flash_attention(q, k, v, *, causal: bool = False, scale=None,
                    window: int = 0, block_len: int = 0):
    """Memory-O(L) blocked attention (ops/flash_attn.py). window > 0
    (causal only) keeps the last ``window`` keys per query —
    sliding-window attention; out-of-window kv tiles are skipped
    wholesale. block_len > 0 (neither causal nor windowed) is the
    block-diffusion training mask over a noised and a clean copy."""
    from . import flash_attn as _fa
    return _fa.flash_attention(q, k, v, causal, scale, pallas_interpret(),
                               window, None, block_len)


def flash_schedule(q, k, causal: bool, window: int = 0,
                   block_len: int = 0, select: bool = False) -> dict:
    """The static tile schedule ``flash_attention`` walks for these
    operands (ops/flash_attn.schedule): what telemetry's ``flash.*``
    gauges and counters report. ``select``: under a selection."""
    from . import flash_attn as _fa
    return _fa.schedule(q, k, causal, window, block_len, select)


def flash_attention_selected(q, k, v, sel):
    """Blocked attention where query t keeps the keys the int8 array
    ``sel (b, L, L)`` marks (learned sparse attention; ops/dsa.select
    makes it): the output and the rows' logsumexp (b, heads, L)."""
    from . import flash_attn as _fa
    return _fa.flash_attention_selected(q, k, v, sel, None,
                                        pallas_interpret())


def selected_probs(q, k, lse, sel):
    """The heads' mean probability of ``flash_attention_selected``'s
    attention on the kept scores, (b, L, L) float32, a tile at a time
    (ops/flash_attn.selected_probs)."""
    from . import flash_attn as _fa
    return _fa.selected_probs(q, k, lse, sel, None, pallas_interpret())


def qk_prep_supported(L: int, dh: int, width: int, dtype) -> bool:
    """True when the fused pass between the qkv dot and the attention core
    (ops/qk_prep_pallas.py) takes these shapes: ``L`` rows of ``width`` lanes,
    heads of ``dh``."""
    from . import qk_prep_pallas as _qp
    return _qp.supports(L, dh, width, jnp.dtype(dtype).itemsize)


def qk_prep(qkv, qnorm, knorm, cos, sin, nh: int, nkv: int, dh: int):
    """The dot's ``qkv (b, L, (nh + 2 nkv) dh)`` to the core's ``q (b, nh,
    L, dh)``, ``k``, ``v (b, nkv, L, dh)`` in one kernel each way: the
    heads' split, QK-norm where ``qnorm`` / ``knorm`` are given, the
    rotation where the float32 ``(L, dh)`` tables ``cos`` / ``sin`` are
    (ops/qk_prep_pallas.py)."""
    from . import qk_prep_pallas as _qp
    return _qp.qk_prep(qkv, qnorm, knorm, cos, sin, nh, nkv, dh,
                       pallas_interpret())


def dsa_select_supported(L: int, topk: int) -> bool:
    """True when the selection's kernel (ops/dsa_select_pallas.py) takes
    rows of ``L`` scores and ``topk`` keys a query."""
    from . import dsa_select_pallas as _ds
    return _ds.supports(L, topk)


def dsa_select(scores, topk: int):
    """Learned sparse attention's exact selection, ``ops/dsa.select``'s
    int8 (b, L, L) array of the float32 ``scores``, from one kernel that
    sorts nothing: a row's ``topk``-th value found by counting
    (ops/dsa_select_pallas.py)."""
    from . import dsa_select_pallas as _ds
    return _ds.select(scores, topk, pallas_interpret())


def dsa_index_bwd_supported(L: int, J: int, di: int, dtype) -> bool:
    """True when the index scores' backward kernel
    (ops/dsa_index_pallas.py) takes sequences of ``L`` rows and ``J``
    index heads of ``di`` in ``dtype``."""
    from . import dsa_index_pallas as _di
    return _di.supports(L, J, di, jnp.dtype(dtype).itemsize)


def dsa_index_bwd(qi, ki, w, g):
    """``ops/dsa._scores_bwd``'s (dq, dk, dw) from one kernel that walks
    the causal tiles alone and makes each tile's products again in VMEM;
    ``g`` is read on s <= t (ops/dsa_index_pallas.py)."""
    from . import dsa_index_pallas as _di
    return _di.scores_bwd(qi, ki, w, g, pallas_interpret())


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of the grouped product's kernel: rows in 512s (the callers pad
    to it), the contraction and the output columns whole up to 1024 and
    else in the largest lane-aligned divisor — few grid steps, a few MiB
    of VMEM."""
    def tile(x):
        if x <= 1024:
            return x
        return next((t for t in (1024, 768, 640, 512, 384, 256, 128)
                     if x % t == 0), 512)
    return (min(m, 512), tile(k), tile(n))


def _megablox():
    # the package's own ``gmm`` attribute is its custom_vjp function and
    # shadows the module of that name
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _gmm(lhs, rhs, sizes):
    """megablox ``gmm`` with tiles chosen per product. Its own VJP
    (megablox.ops) hands the forward's tiles to the two backward products,
    whose contraction and column sizes are the forward's swapped."""
    _mb = _megablox()
    m, (_, k, n) = lhs.shape[0], rhs.shape
    return _mb.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling(m, k, n),
                   interpret=pallas_interpret())


def _gmm_fwd(lhs, rhs, sizes):
    return _gmm(lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_bwd(res, g):
    _mb = _megablox()
    lhs, rhs, sizes = res
    m, (ng, k, n) = lhs.shape[0], rhs.shape
    interpret = pallas_interpret()
    d_lhs = _mb.gmm(g, rhs, sizes, lhs.dtype, _gmm_tiling(m, n, k),
                    transpose_rhs=True, interpret=interpret)
    d_rhs = _mb.tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype,
                     _gmm_tiling(m, k, n), num_actual_groups=ng,
                     interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """Rows of ``lhs`` (m, k), sorted by group, each times its group's
    matrix of ``rhs`` (g, k, n): rows ``[sum(sizes[:i]), sum(sizes[:i+1]))``
    take ``rhs[i]``. The sizes may add up to less than m; the rows beyond
    come out as exact zeros, and cost nothing on a TPU. There it is the
    megablox kernel (a grid over the row tiles that hold work: the cost
    follows the rows, not m x g), elsewhere ``lax.ragged_dot`` (XLA's own
    lowering, which the CPU backend expands densely)."""
    m = lhs.shape[0]
    if not use_pallas():
        return lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
    tm = min(512, -(-m // 128) * 128)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # one group more than rhs has matrices, holding the rows with no work:
    # the kernel then zeroes them itself, forward and backward
    rest = (m + pad - jnp.sum(group_sizes)).astype(jnp.int32)
    sizes = jnp.concatenate([group_sizes.astype(jnp.int32), rest[None]])
    out = _gmm(lhs, rhs, sizes)
    return out[:m] if pad else out


def softmax(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jax.nn.softmax(x, axis=axis)


def xelu(x: jnp.ndarray, b) -> jnp.ndarray:
    """Leaky relu with *divisor* b (reference op::xelu, src/layer/op.h:56-60)."""
    return jnp.where(x > 0, x, x / b)


def mxelu(x: jnp.ndarray, m) -> jnp.ndarray:
    """Leaky relu with *multiplier* m (reference op::mxelu,
    src/layer/prelu_layer-inl.hpp:10-14)."""
    return jnp.where(x > 0, x, x * m)
