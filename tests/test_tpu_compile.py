"""Kernels of the training path compiled for a described TPU v5e, at the
widths the benchmark's cells run: what interpret mode cannot see (Mosaic's
tiling rules, the VMEM a block takes, the layout XLA gives the operands).
Nothing runs: there is no chip here, so no time and no result.

The topology is described inside a fixture, never at import: only the
worker that is handed this file loads the TPU's library (see the
on-chip-measurement guide, section 2). Keep such tests in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu.ops import pallas_kernels


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


# (N, H, W, C) of the two LRN layers of GoogLeNet b512 and AlexNet b2048,
# and one float32 case (the hi/lo pair of the band product)
@pytest.mark.parametrize("shape,dtype", [
    ((512, 56, 56, 64), jnp.bfloat16),
    ((512, 56, 56, 192), jnp.bfloat16),
    ((2048, 27, 27, 96), jnp.bfloat16),
    ((2048, 13, 13, 256), jnp.bfloat16),
    ((256, 56, 56, 192), jnp.float32),
])
def test_channels_last_lrn_compiles_without_a_copy(one_chip, shape, dtype):
    n, h, w, c = shape
    assert pallas_kernels.lrn_nhwc_fits(shape, dtype)
    # the layout XLA gives a conv net's activations on the chip: batch
    # minor, (N, H, W, C) as {0,3,2,1}, here as a (H, W, C, N) argument
    arg = jax.ShapeDtypeStruct((h, w, c, n), dtype, sharding=one_chip)

    def both(xt, gt):
        y, vjp = jax.vjp(
            lambda v: pallas_kernels.lrn_nhwc(v, 5, 1e-4, 0.75, 1.0),
            jnp.transpose(xt, (3, 0, 1, 2)))
        dx, = vjp(jnp.transpose(gt, (3, 0, 1, 2)))
        return (jnp.transpose(y, (1, 2, 3, 0)),
                jnp.transpose(dx, (1, 2, 3, 0)))
    text = jax.jit(both).lower(arg, arg).compile().as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 2
    # the kernel's (H*W, C, N) view is a bitcast of that layout: a copy
    # or a transpose beside it would cost a pass over the tensor each
    assert not re.search(r"= \S+ (copy|transpose)\(", text), text


# the sparse expert product of the language-model cell, at the published
# widths and a quarter of its tokens: the grouped-product kernel has to take
# Mosaic's tiling at d 2560 / width 768, and the layer's cost has to follow
# the pairs, not pairs x experts. With every expert held the sorted side has
# all k T rows and there is no branch; a share of 16 of 64 (the cell's) gets
# 4,608 of its 12,288 rows and a second branch, today's, for a step whose
# pairs held do not fit them (PR 33)
@pytest.mark.parametrize("nexp, k", [(16, 4), (64, 6)])
def test_sparse_moe_layer_compiles_and_its_cost_follows_the_pairs(
        one_chip, nexp, k):
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import lm_flops
    from cxxnet_tpu import ops
    from cxxnet_tpu.layer.base import ApplyContext
    from cxxnet_tpu.layer.layers import MoELayer
    d, width, seq, held = 2560, 768, 2048, 16
    lay = MoELayer()
    for key, val in {"nexpert": nexp, "top_k": k, "nhidden": width,
                     "expert_act": "reglu", "nexpert_held": held}.items():
        lay.set_param(key, str(val))
    lay.infer_shape([(1, d, 1, seq)] * 2)
    shapes = {"gate": (nexp, d), "experts": (held, d, width),
              "up": (held, d, width), "down": (held, width, d)}

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def layer(params, u, x):
        ctx = ApplyContext(train=True, channels_last=True)
        return lay.apply(params, [u, x], ctx)[0]
    ops.set_use_pallas(True)
    interpret, ops.pallas_interpret = ops.pallas_interpret, lambda: False
    try:
        # Mosaic refuses the process default ("highest") for bf16 operands
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(layer).lower(
                {key: arg(s) for key, s in shapes.items()},
                arg((1, 1, seq, d)), arg((1, 1, seq, d))).compile()
    finally:
        ops.set_use_pallas(None)
        ops.pallas_interpret = interpret
    text = compiled.as_text()
    kernels = 'custom_call_target="tpu_custom_call"'
    pairs, rows = seq * k, lay._sorted_rows(seq * k)
    if nexp == held:
        assert rows == pairs and " conditional(" not in text
        assert len(re.findall(kernels, text)) == 3
        want = lm_flops.expert_product(pairs, d, width, held)["flops"] \
            + 2 * seq * d * nexp
        got = compiled.cost_analysis()["flops"]
        # every expert on every token would read held / k = 4 times the pairs
        assert want <= got < 2 * want, (got, want)
        return
    assert rows == 4608 < pairs == 12288
    # branch 0 is the whole sorted side, branch 1 its first `rows` rows
    (names,) = re.findall(r"branch_computations=\{([^}]*)\}", text)
    bodies = [re.search(r"^%s \(.*?^\}" % re.escape(n.strip()), text,
                        re.M | re.S).group(0) for n in names.split(",")]
    for body, m in zip(bodies, (pairs, rows)):
        calls = [ln for ln in body.splitlines() if kernels in ln]
        assert len(calls) == 3, body
        # the three products read and write m rows: rows x 2560 in and out
        # of the layer's width, rows x 768 between them
        for ln in calls:
            assert set(re.findall(r"bf16\[(\d+),(?:768|2560)\]", ln)) \
                == {str(m)}, ln
    # and beside them: the bounded branch makes one k T-row array, the token
    # side's gather of the combine; everything of the sorted side has `rows`
    def made(body, m, width):
        return len(re.findall(r"= bf16\[%d,%d\]" % (m, width), body))
    assert made(bodies[1], pairs, 768) == 0
    assert made(bodies[1], pairs, d) == 1 < made(bodies[0], pairs, d)
    assert made(bodies[1], rows, 768) and made(bodies[1], rows, d)


# the language-model cell's attention core (PR 31): 28 query heads on 4
# key-value heads of 128 over 8,192 tokens, bf16, the global layer and a
# 4,096 window. Mosaic has to take the tiles `_tiling` chooses (blocks of
# (1, 1, bq) statistics rows, their transposes, score tiles of up to
# 1,024 x 1,024 float32 under the VMEM limit the kernels ask for), and the
# backward has to be the three kernels alone: dK / dV leave the kernel at
# key-value resolution, so no sum over the group stands beside it
@pytest.mark.parametrize("window", [0, 4096])
def test_flash_kernels_compile_at_the_cells_shape(one_chip, window):
    from cxxnet_tpu.ops import flash_attn
    q = jax.ShapeDtypeStruct((1, 28, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)
    for kernel, t in zip(("fwd", "dq", "dkv"),
                         flash_attn._tiling(8192, 128, 2, window)):
        assert flash_attn._vmem_bytes(kernel, t, 128, 2) \
            <= flash_attn.VMEM_BUDGET

    def both(q_, k_, v_, do_):
        out, vjp = jax.vjp(lambda a, b, c: flash_attn.flash_attention(
            a, b, c, True, None, False, window), q_, k_, v_)
        return (out,) + vjp(do_)
    compiled = jax.jit(both).lower(q, kv, kv, q).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 3
    assert [o.shape for o in compiled.out_info] == [
        q.shape, q.shape, kv.shape, kv.shape]
    # nothing of query-head size is summed after the kernels
    assert not re.search(r"bf16\[\d+,7,8192,128\]", text), text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# the block-diffusion cell's attention (`sdar-ep8-train-8k`): 32 query heads
# on 4 key-value heads of 128, 16,384 rows (a noised and a clean copy of
# 8,192 tokens), block length 4. The two-run walk is scalar arithmetic in
# the index maps and the kernels' bodies (floor divisions, selects), the mask
# a shift and two compares on an edge tile: Mosaic has to take both
def test_flash_kernels_compile_under_the_block_diffusion_mask(one_chip):
    from cxxnet_tpu.ops import flash_attn
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16,
                              sharding=one_chip)
    for kernel, t in zip(("fwd", "dq", "dkv"),
                         flash_attn._tiling(16384, 128, 2, 0, 4)):
        assert flash_attn._vmem_bytes(kernel, t, 128, 2) \
            <= flash_attn.VMEM_BUDGET

    def both(q_, k_, v_, do_):
        out, vjp = jax.vjp(lambda a, b, c: flash_attn.flash_attention(
            a, b, c, False, None, False, 0, None, 4), q_, k_, v_)
        return (out,) + vjp(do_)
    compiled = jax.jit(both).lower(q, kv, kv, q).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 3
    assert [o.shape for o in compiled.out_info] == [
        q.shape, q.shape, kv.shape, kv.shape]
    assert not re.search(r"bf16\[\d+,8,16384,128\]", text), text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# the learned-sparse-attention cell's attention (`keye-ep8-train-8k`, PR 38):
# 32 query heads on 4 key-value heads of 128 over 8,192 tokens, a selection
# of 2,048 keys a query streamed as an int8 block beside k (its transpose
# beside q in dK / dV) and widened to 32 bits for the compare, and the
# target pass whose tile sums all 32 heads' probabilities in VMEM. Mosaic
# has to take the int8 blocks and their tiling, the four kernels have to
# stand alone but for the mask's transpose and pads, and no (heads, L, L)
# array may be made beside them
def test_flash_kernels_compile_under_a_selection(one_chip):
    from cxxnet_tpu.ops import flash_attn
    L = 8192
    q = jax.ShapeDtypeStruct((1, 32, L, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, L, 128), jnp.bfloat16,
                              sharding=one_chip)
    sel = jax.ShapeDtypeStruct((1, L, L), jnp.int8, sharding=one_chip)

    def both(q_, k_, v_, sel_, do_):
        (out, lse), vjp = jax.vjp(
            lambda a, b, c: flash_attn.flash_attention_selected(
                a, b, c, sel_), q_, k_, v_)
        p = flash_attn.selected_probs(q_, k_, lse, sel_)
        return (out, p) + vjp((do_, jnp.zeros_like(lse)))
    compiled = jax.jit(both).lower(q, kv, kv, sel, q).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 4
    assert [o.shape for o in compiled.out_info] == [
        q.shape, (1, L, L), q.shape, kv.shape, kv.shape]
    assert not re.search(r"\[(1,)?32,8192,8192\]", text), text
    # beside the kernels: the selection's transpose for dK / dV (67 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 80 << 20


# the pass between the qkv dot and the flash kernels (PR 37) at the two
# language-model cells' shapes, and one float32 case with heads of two lane
# tiles: a row tile at the whole width (5,120 / 4,608 lanes) has to fit the
# VMEM the kernels ask for, the lane roll and the per-head column slices
# have to take Mosaic's tiling, and the two kernels have to stand alone: no
# transpose, no copy and no float32 tensor of the rows beside them
@pytest.mark.parametrize("L, nh, nkv, dh, norm, rope, dtype", [
    (16384, 32, 4, 128, True, True, jnp.bfloat16),    # sdar-ep8-train-8k
    (8192, 28, 4, 128, False, True, jnp.bfloat16),    # smallthinker-ep4-...
    (2048, 8, 2, 128, True, False, jnp.bfloat16),
    (1024, 4, 2, 256, True, True, jnp.float32),
], ids=["sdar", "smallthinker", "norm-alone", "heads-of-256-float32"])
def test_qk_prep_kernels_compile_at_the_cells_shapes(one_chip, L, nh, nkv,
                                                     dh, norm, rope, dtype):
    from cxxnet_tpu.ops import qk_prep_pallas

    def arg(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    width = (nh + 2 * nkv) * dh
    assert qk_prep_pallas.supports(L, dh, width, jnp.dtype(dtype).itemsize)
    gain = arg((dh,), jnp.float32) if norm else None
    table = arg((L, dh), jnp.float32) if rope else None
    heads = [arg((1, n, L, dh)) for n in (nh, nkv, nkv)]

    def both(qkv, qn, kn, cos, sin, dq, dk, dv):
        out, vjp = jax.vjp(lambda x, a, c: qk_prep_pallas.qk_prep(
            x, a, c, cos, sin, nh, nkv, dh), qkv, qn, kn)
        return out, vjp((dq, dk, dv))
    compiled = jax.jit(both).lower(arg((1, L, width)), gain, gain, table,
                                   table, *heads).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 2
    assert not re.search(r"= \S+ (copy|transpose)\(", text), text
    # the only residual is qkv itself: nothing of the rows' size is made
    # beside the kernels' own results
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# the selection of the learned-sparse-attention cell without a sort
# (PR 39): 8,192 rows of 8,192 float32 scores, 2,048 keys a query. Mosaic
# has to take a 128-row block at its whole length beside its int32 scratch,
# the int32 counts' lane reduction, the rolled loops whose trip count comes
# from the grid position, and the int8 store; the kernel has to stand
# alone: HBM holds the scores and the selection and nothing else, and
# nothing is sorted
def test_the_selection_kernel_compiles_at_the_cells_shape(one_chip):
    from cxxnet_tpu.ops import dsa_select_pallas
    L, topk = 8192, 2048
    assert dsa_select_pallas.supports(L, topk)
    scores = jax.ShapeDtypeStruct((1, L, L), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda s: dsa_select_pallas.select(s, topk)).lower(
        scores).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 1
    assert not re.search(r"= \S+ (sort|copy|transpose)\(", text), text
    assert "TopK" not in text
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert (out.shape, out.dtype) == ((1, L, L), jnp.int8)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# the index scores' backward of the same cell as one kernel over the causal
# tiles (PR 41): 16 index heads of 64 on 8,192 rows, bf16, the scores'
# float32 gradient. Mosaic has to take the 512 x 512 tiles with the head
# loop inside, the dynamic lane offset of the resident transposed dK, and
# the index maps that repeat the diagonal's blocks past it; nothing of the
# plain lines' (16, 512, 8,192) float32 products goes through HBM
def test_the_index_backward_kernel_compiles_at_the_cells_shape(one_chip):
    from cxxnet_tpu.ops import dsa_index_pallas
    L, J, di = 8192, 16, 64
    assert dsa_index_pallas.supports(L, J, di, 2)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(dsa_index_pallas.scores_bwd).lower(
        arg((1, J, L, di), jnp.bfloat16), arg((1, L, di), jnp.bfloat16),
        arg((1, L, J), jnp.float32), arg((1, L, L), jnp.float32)).compile()
    text = compiled.as_text()
    assert len(re.findall('custom_call_target="tpu_custom_call"', text)) == 1
    assert not re.search(r"= \S+ (dot|convolution)\(", text), text
    dq, dk, dw = jax.tree_util.tree_leaves(compiled.out_info)
    assert (dq.shape, dq.dtype) == ((1, J, L, di), jnp.bfloat16)
    assert (dk.shape, dk.dtype) == ((1, L, di), jnp.bfloat16)
    assert (dw.shape, dw.dtype) == ((1, L, J), jnp.float32)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
