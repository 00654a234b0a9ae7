"""Flash attention Pallas kernel: golden tests vs the dense reference.

Runs the exact kernel code on CPU via the Pallas interpreter
(ops/flash_attn.py interpret=True); the compiled path is validated on
the chip by tools/check_tpu_kernels.py.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu import ops
from cxxnet_tpu.ops import dsa
from cxxnet_tpu.ops.flash_attn import flash_attention, supports
from cxxnet_tpu.parallel.ring import attention_reference


def _rand_qkv(rs, b=2, h=3, L=256, d=64, dtype=jnp.float32):
    mk = lambda: jnp.asarray(rs.randn(b, h, L, d), dtype)
    return mk(), mk(), mk()


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, causal):
        q, k, v = _rand_qkv(np.random.RandomState(0))
        out = flash_attention(q, k, v, causal, None, True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _rand_qkv(np.random.RandomState(1))

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal, None, True)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: attention_reference(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_uneven_block_count(self):
        # L = 384 -> block 128, 3 kv steps: exercises carry across a
        # non-power-of-two stream
        q, k, v = _rand_qkv(np.random.RandomState(2), L=384)
        out = flash_attention(q, k, v, True, None, True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_inputs(self):
        q, k, v = _rand_qkv(np.random.RandomState(3), dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, True, None, True)
        ref = attention_reference(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.1, atol=0.1)

    def test_custom_scale(self):
        q, k, v = _rand_qkv(np.random.RandomState(4))
        out = flash_attention(q, k, v, False, 0.05, True)
        ref = attention_reference(q, k, v, scale=0.05)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_supports(self):
        assert supports(256, 64)
        assert supports(8192, 128)
        assert supports(200, 64)         # unaligned L: padded + tail-masked
        assert not supports(64, 64)      # too short (dense is fine there)
        assert not supports(256, 63)     # unaligned head dim

    @pytest.mark.parametrize("L", [200, 300])
    @pytest.mark.parametrize("causal", [False, True])
    def test_unaligned_length_padded(self, L, causal):
        q, k, v = _rand_qkv(np.random.RandomState(5), L=L)
        out = flash_attention(q, k, v, causal, None, True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        gf = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal, None, True))), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(attention_reference(
            q, k, v, causal=causal))), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestLayerDispatch:
    """AttentionLayer routes through the flash kernel when Pallas is on."""

    def _trainer(self):
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        conf = """
netconfig = start
layer[+1:att1] = attention:att1
  nhead = 2
  causal = 1
  init_sigma = 0.05
layer[+1] = flatten
layer[+1:head] = fullc:head
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 32,1,256
batch_size = 4
eta = 0.1
dev = cpu
"""
        tr = Trainer()
        for key, val in parse_config_string(conf):
            tr.set_param(key, val)
        tr.init_model()
        return tr

    def test_flash_path_matches_dense_path(self):
        from cxxnet_tpu.io.data import DataBatch
        rs = np.random.RandomState(0)
        b = DataBatch()
        b.data = rs.rand(4, 32, 1, 256).astype(np.float32)
        b.label = rs.randint(0, 4, (4, 1)).astype(np.float32)
        b.batch_size = 4

        def run(force):
            ops.set_use_pallas(force)
            try:
                tr = self._trainer()
                tr.update(b)
                return np.asarray(jax.device_get(tr.params[0]["wqkv"]))
            finally:
                ops.set_use_pallas(None)

        w_flash = run(True)    # interpret-mode kernels on CPU
        w_dense = run(False)
        np.testing.assert_allclose(w_flash, w_dense, rtol=2e-4, atol=2e-4)


class TestFlashOnMesh:
    """On a data-parallel mesh (no sp axis) the flash kernel runs under
    shard_map with the batch left sharded — pallas_call has no GSPMD rule."""

    def test_data_mesh_matches_dense(self):
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        conf = """
netconfig = start
layer[+1:att1] = attention:att1
  nhead = 2
  causal = 1
  init_sigma = 0.05
layer[+1] = flatten
layer[+1:head] = fullc:head
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 32,1,256
batch_size = 8
eta = 0.1
dev = cpu:0-3
"""
        rs = np.random.RandomState(0)
        b = DataBatch()
        b.data = rs.rand(8, 32, 1, 256).astype(np.float32)
        b.label = rs.randint(0, 4, (8, 1)).astype(np.float32)
        b.batch_size = 8

        def run(force):
            ops.set_use_pallas(force)
            try:
                tr = Trainer()
                for key, val in parse_config_string(conf):
                    tr.set_param(key, val)
                tr.init_model()
                assert tr.mesh is not None and "data" in tr.mesh.axis_names
                tr.update(b)
                return np.asarray(jax.device_get(tr.params[0]["wqkv"]))
            finally:
                ops.set_use_pallas(None)

        np.testing.assert_allclose(run(True), run(False),
                                   rtol=2e-4, atol=2e-4)


class TestFlashGQA:
    """Grouped-query attention in the kernels: k/v carry nkv < h heads and
    the BlockSpec row map reads the shared head per group — no broadcast
    materialized. Goldened against the grouped dense reference."""

    def _qkv(self, rs, b=2, h=4, nkv=2, L=256, d=32, dtype=jnp.float32):
        q = jnp.asarray(rs.randn(b, h, L, d), dtype)
        k = jnp.asarray(rs.randn(b, nkv, L, d), dtype)
        v = jnp.asarray(rs.randn(b, nkv, L, d), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, causal):
        q, k, v = self._qkv(np.random.RandomState(3))
        out = flash_attention(q, k, v, causal, None, True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_mqa_single_kv_head(self):
        q, k, v = self._qkv(np.random.RandomState(4), h=4, nkv=1)
        out = flash_attention(q, k, v, True, None, True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = self._qkv(np.random.RandomState(5), L=128)

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal, None, True)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: attention_reference(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
        # kv grads come back at kv-head resolution
        assert gf[1].shape == k.shape

    def test_window_grouped(self):
        q, k, v = self._qkv(np.random.RandomState(6), L=256)
        out = flash_attention(q, k, v, True, None, True, 64)
        ref = attention_reference(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_padded_length_grouped(self):
        q, k, v = self._qkv(np.random.RandomState(7), L=200)
        out = flash_attention(q, k, v, True, None, True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------- the tile schedule (PR 31)
from cxxnet_tpu.ops import flash_attn as fa  # noqa: E402
from cxxnet_tpu.ops.flash_attn import Tiles  # noqa: E402


def _kept(g):
    """Brute force over the padded grid: the scores the contract keeps."""
    qpos = np.arange(g.n_q * g.bq)[:, None]
    kpos = np.arange(g.n_k * g.bk)[None, :]
    keep = np.broadcast_to(kpos < g.kv_len, (qpos.size, kpos.size))
    if g.causal:
        keep = keep & (qpos >= kpos)
        if g.window:
            keep = keep & (qpos - kpos < g.window)
    return keep.reshape(g.n_q, g.bq, g.n_k, g.bk).transpose(0, 2, 1, 3)


SCHEDULES = [(L, bq, bk, window, causal)
             for L in (128, 300, 512, 1000, 1536)
             for bq, bk in ((128, 128), (128, 256), (256, 128), (128, 512),
                            (512, 128), (256, 256))
             for window, causal in ((0, False), (0, True), (1, True),
                                    (96, True), (128, True), (200, True),
                                    (256, True), (640, True), (4096, True))]


class TestTileSchedule:
    """Which tiles the three kernels visit, and which of them they mask,
    against a brute-force count of kept scores."""

    @pytest.mark.parametrize("L,bq,bk,window,causal", SCHEDULES)
    def test_visits_and_edges_match_brute_force(self, L, bq, bk, window,
                                                causal):
        g = fa._geom(Tiles(bq, bk, bk), L, causal, window)
        keep = _kept(g)
        any_kept, all_kept = keep.any((2, 3)), keep.all((2, 3))
        want = {(i, j) for i, j in zip(*np.nonzero(any_kept))}
        # the forward / dQ walk: a run of kv tiles for each q tile
        lo, hi = g.kv_range(np.arange(g.n_q), np)
        assert g.kv_steps() == max(hi - lo) + 1
        fwd = {(i, j) for i in range(g.n_q)
               for j in range(lo[i], hi[i] + 1)}
        # the dK/dV walk: a run of q tiles for each kv tile
        lo, hi = g.q_range(np.arange(g.n_k), np)
        assert g.q_steps() == max(hi - lo) + 1
        dkv = {(i, j) for j in range(g.n_k)
               for i in range(lo[j], hi[j] + 1)}
        assert fwd == want and dkv == want
        # the bodies' own predicates, tile by tile
        q0, k0 = np.meshgrid(np.arange(g.n_q) * bq, np.arange(g.n_k) * bk,
                             indexing="ij")
        needed, full = g.kind(q0, bq, k0, bk, np)
        np.testing.assert_array_equal(needed, any_kept)
        np.testing.assert_array_equal(needed & ~full, any_kept & ~all_kept)
        n_full, n_edge, n_skip = fa.tile_counts(g)
        assert (n_full, n_edge) == (all_kept.sum(),
                                    (any_kept & ~all_kept).sum())
        assert n_full + n_edge + n_skip == g.n_q * g.n_k
        assert g.masks == bool((~all_kept).any())

    def test_the_parents_schedule_of_the_cells_global_layer(self):
        # ISSUE 31: 256 x 256 tiles at L 8192 read 496 / 32 / 496 a head
        g = fa._geom(Tiles(256, 256, 256), 8192, True, 0)
        assert fa.tile_counts(g) == (496, 32, 496)
        g = fa._geom(Tiles(256, 256, 256), 8192, True, 4096)
        assert sum(fa.tile_counts(g)[:2]) == 408

    def test_sub_tiles_are_counted_at_their_own_width(self):
        g = fa._geom(Tiles(256, 1024, 256), 2048, True, 512)
        whole = fa._geom(Tiles(256, 256, 256), 2048, True, 512)
        assert fa.tile_counts(g) == fa.tile_counts(whole)


class TestTileChoice:
    """``_tiling``: what the shape alone decides."""

    CELL = dict(d=128, itemsize=2)

    @pytest.mark.parametrize("L", [128, 384, 2048, 8192, 16384])
    @pytest.mark.parametrize("window", [0, 4096])
    def test_lane_aligned_and_inside_the_stated_budget(self, L, window):
        tiles = fa._tiling(L, window=window, **self.CELL)
        for kernel, t in zip(("fwd", "dq", "dkv"), tiles):
            assert t.bq % 128 == 0 and t.bk % 128 == 0 and t.sub % 128 == 0
            streamed = t.bq if kernel == "dkv" else t.bk
            assert streamed % t.sub == 0
            assert fa._vmem_bytes(kernel, t, 128, 2) <= fa.VMEM_BUDGET
            # padding stays small: a sixteenth, or up to one lane tile
            for b in (t.bq, t.bk):
                assert -(-L // b) * b - L <= max(L // 16, 127)

    def test_the_cells_shape_does_not_get_256_by_256(self):
        for window in (0, 4096):
            for t in fa._tiling(8192, window=window, **self.CELL):
                assert (t.bq, t.bk) != (256, 256)
                assert max(t.bq, t.bk) >= 512

    @pytest.mark.parametrize("L,res,stream", [
        (128, 128, 128), (200, 128, 128), (256, 128, 256), (384, 128, 128),
        (512, 128, 512), (1000, 256, 512), (2048, 512, 1024)])
    def test_short_sequences_get_blocks_that_divide_or_pad(self, L, res,
                                                           stream):
        fwd, dq, dkv = fa._tiling(L, 64, 4, 0)
        assert (fwd.bq, fwd.bk) == (dq.bq, dq.bk) == (res, stream)
        assert (dkv.bq, dkv.bk) == (stream, res)
        assert -(-L // stream) * stream - L < 128

    def test_a_wide_head_shrinks_the_tiles_into_the_budget(self):
        for d in (256, 512, 1024):
            tiles = fa._tiling(8192, d, 2, 0)
            for kernel, t in zip(("fwd", "dq", "dkv"), tiles):
                assert fa._vmem_bytes(kernel, t, d, 2) <= fa.VMEM_BUDGET
                assert min(t) >= 128

    def test_a_short_window_caps_the_streamed_block(self):
        fwd, _, dkv = fa._tiling(8192, 128, 2, 96)
        assert fwd == dkv == Tiles(128, 128, 128)
        # a window of 1,024 takes the band walk (tests/test_flash_band.py)
        # but in the split backward's dQ kernel, whose block it caps
        fwd, dq, dkv = fa._tiling(8192, 128, 2, 1024)
        assert dq == Tiles(512, 1024, 512)
        assert fwd == dkv == fa.BandTiles(2048, 2048, 128)


def _vs_dense(rs, L, h, nkv, d, window, tiles, causal=True, tol=1e-4):
    """Forward and all three gradients against the dense reference,
    float32, the kernels in the interpreter with the tiles given."""
    q = jnp.asarray(rs.randn(1, h, L, d), jnp.float32)
    k = jnp.asarray(rs.randn(1, nkv, L, d), jnp.float32)
    v = jnp.asarray(rs.randn(1, nkv, L, d), jnp.float32)
    w = jnp.asarray(rs.randn(1, h, L, d), jnp.float32)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w)
    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal, None, True, window, tiles))
    want = run(lambda q, k, v: attention_reference(
        q, k, v, causal=causal, window=window))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


class TestTiledKernels:
    """The kernels at tilings ``_tiling`` only gives long sequences, run at
    small L: rectangular tiles, sub-columns, runs that start past tile 0,
    steps past a run's end, the group summed in the dK/dV kernel."""

    RECT = (Tiles(128, 256, 128), Tiles(256, 128, 128), Tiles(256, 128, 128))
    WIDE = (Tiles(128, 512, 256), Tiles(128, 512, 256), Tiles(512, 128, 256))

    @pytest.mark.parametrize("causal", [False, True])
    def test_rectangular_tiles(self, causal):
        _vs_dense(np.random.RandomState(11), 512, 2, 2, 32, 0, self.RECT,
                  causal)

    @pytest.mark.parametrize("window", [200, 256])
    def test_window_ending_inside_a_tile_and_on_a_boundary(self, window):
        _vs_dense(np.random.RandomState(12), 768, 2, 1, 32, window,
                  self.RECT)

    @pytest.mark.parametrize("causal", [False, True])
    def test_length_no_multiple_of_either_block(self, causal):
        _vs_dense(np.random.RandomState(13), 600, 2, 2, 32, 0, self.RECT,
                  causal)

    @pytest.mark.parametrize("window", [0, 200])
    def test_group_of_seven_sums_dk_dv_in_the_kernel(self, window):
        _vs_dense(np.random.RandomState(14), 512, 7, 1, 32, window,
                  self.RECT)

    def test_a_q_tile_with_full_edge_and_skipped_tiles(self):
        tiles = (Tiles(128, 128, 128),) * 3
        g = fa._geom(tiles[0], 1024, True, 300)
        q0 = 7 * 128                       # the last q tile's row of tiles
        needed, full = g.kind(q0, 128, np.arange(8) * 128, 128, np)
        assert (needed & full).any() and (needed & ~full).any() \
            and (~needed).any()
        _vs_dense(np.random.RandomState(15), 1024, 1, 1, 16, 300, tiles)

    def test_sub_columns_of_a_wide_streamed_block(self):
        _vs_dense(np.random.RandomState(16), 1024, 2, 1, 16, 300, self.WIDE)

    def test_bf16_group_grads_come_back_at_kv_resolution(self):
        rs = np.random.RandomState(17)
        q = jnp.asarray(rs.randn(1, 4, 256, 32), jnp.bfloat16)
        k = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.bfloat16)
        v = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.bfloat16)
        dq, dk, dv = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, None, True, 0, self.RECT).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=True)), argnums=(0, 1, 2))(
                *(t.astype(jnp.float32) for t in (q, k, v)))
        for a, b in zip((dq, dk, dv), ref):
            assert a.dtype == jnp.bfloat16 and a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), rtol=0.1, atol=0.1)


# ------------------------------------------------- the fused backward
def _selection(rs, L, share=0.3):
    """A random causal selection (1, L, L) int8 that keeps the diagonal."""
    pos = np.arange(L)
    keep = (rs.rand(L, L) < share) | (pos[None, :] == pos[:, None])
    return jnp.asarray((keep & (pos[None, :] <= pos[:, None]))[None],
                       jnp.int8)


def _selected_reference(sel):
    def attend(q, k, v):
        probs, _ = dsa.selected_probs_plain(q, k, sel, q.shape[-1] ** -0.5)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v).reshape(q.shape)
    return attend


SMALL = (Tiles(128, 128, 128),) * 3
FUSED_CASES = [
    # mask (causal, window, block_len, selection), L, h, nkv, d, tiles
    ("causal-group1-heads64", (True, 0, 0, False), 256, 2, 2, 64, None),
    ("causal-pads-group4-wide", (True, 0, 0, False), 600, 4, 1, 32,
     TestTiledKernels.WIDE),
    ("window-group4-heads128", (True, 200, 0, False), 768, 4, 1, 128,
     TestTiledKernels.RECT),
    ("full-pads-heads128", (False, 0, 0, False), 300, 2, 1, 128, None),
    ("blockdiff-group4-tiles", (False, 0, 4, False), 512, 4, 1, 64, SMALL),
    ("selection-group7-pads", (True, 0, 0, True), 384, 7, 1, 64,
     TestTiledKernels.RECT),
]


@pytest.mark.parametrize("mask,L,h,nkv,d,tiles",
                         [c[1:] for c in FUSED_CASES],
                         ids=[c[0] for c in FUSED_CASES])
def test_the_fused_backward_matches_the_split_pair_and_the_reference(
        mask, L, h, nkv, d, tiles):
    """dQ, dK and dV of the one kernel against the dQ and dK/dV pair on
    the same forward's residuals, and against the dense reference's
    gradients: every mask, groups of 1, 4 and 7 (dK / dV summed over the
    group in the kernel), heads of 32, 64 and 128, lengths that pad, and
    several tiles a head at small L."""
    causal, window, block_len, select = mask
    rs = np.random.RandomState(L + h)
    q, w = (jnp.asarray(rs.randn(1, h, L, d), jnp.float32) for _ in "qw")
    k, v = (jnp.asarray(rs.randn(1, nkv, L, d), jnp.float32) for _ in "kv")
    sel = _selection(rs, L) if select else None
    _, res = fa._flash_fwd(q, k, v, causal, None, True, window, tiles,
                           block_len, sel)

    def backward(fused):
        return fa._flash_bwd(causal, None, True, window, tiles, block_len,
                             res, w, sel, fused)
    fused, split = backward(True), backward(False)
    attend = _selected_reference(sel) if select else (
        lambda q_, k_, v_: attention_reference(
            q_, k_, v_, causal=causal, window=window, block_len=block_len))
    want = jax.vjp(attend, q, k, v)[1](w)
    for a, b, c in zip(fused, split, want):
        assert a.shape == b.shape == c.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def _kernels_in_the_gradient(q, k, v):
    jaxpr = jax.make_jaxpr(jax.grad(lambda q_, k_, v_: jnp.sum(
        flash_attention(q_, k_, v_, True, None, True)), argnums=(0, 1, 2)))(
            q, k, v)
    return str(jaxpr).count("pallas_call[")


def test_the_gate_gives_the_split_pair_where_the_accumulators_do_not_fit(
        monkeypatch):
    """``fused_bwd_supported`` by the VMEM arithmetic alone: true at the
    four language-model cells' shapes, false at 32,768 rows of 128, whose
    whole-length float32 accumulators (48 MiB) and dQ's block take the
    kernel past BWD_VMEM_LIMIT; and where it is false the backward is the
    pair of kernels, the same shape rule run at a small L with the limit
    brought under what it asks."""
    bf16 = jnp.bfloat16
    assert fa.fused_bwd_supported(16384, 128, bf16, block_len=4)     # sdar
    assert fa.fused_bwd_supported(8192, 128, bf16, select=True)      # keye
    assert fa.fused_bwd_supported(8192, 128, bf16, 4096)     # smallthinker
    assert fa.fused_bwd_supported(8192, 64, bf16)                    # lfm2
    assert not fa.fused_bwd_supported(32768, 128, bf16)
    t = fa._tiling(32768, 128, 2, 0)[2]
    assert fa._accumulators(t, 32768, 128, 2) >= 48 << 20

    L, d = 256, 64
    q, k, v = _rand_qkv(np.random.RandomState(8), b=1, h=2, L=L, d=d)
    assert fa.fused_bwd_supported(L, d, jnp.float32)
    assert _kernels_in_the_gradient(q, k, v) == 2       # forward + fused
    asks = fa._vmem_limit("bwd", fa._tiling(L, d, 4, 0)[2], d, 4, False, L)
    monkeypatch.setattr(fa, "BWD_VMEM_LIMIT", asks - 1)
    assert not fa.fused_bwd_supported(L, d, jnp.float32)
    assert _kernels_in_the_gradient(q, k, v) == 3       # forward, dQ, dK/dV
