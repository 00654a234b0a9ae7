"""The flash kernels' band walk (ops/flash_attn.py ``BandTiles``,
``_Geom.band_plan``): a causal window narrower than the tiles the row walk
would take is walked in square blocks whose bodies take (sub x sub)
sub-tiles by a static plan of the step's offset. The kernels in the
interpreter against the dense reference; the schedule by numpy alone; the
path account at the cells' attention shapes by tracing the step alone."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu import models, ops
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.ops import flash_attn as fa
from cxxnet_tpu.ops.flash_attn import BandTiles, Tiles, flash_attention
from cxxnet_tpu.parallel.ring import attention_reference
from cxxnet_tpu.utils import telemetry
from cxxnet_tpu.utils.config import parse_config_string


def _operands(rs, L, h, nkv, d, dtype=jnp.float32):
    q, w = (jnp.asarray(rs.randn(1, h, L, d), dtype) for _ in "qw")
    k, v = (jnp.asarray(rs.randn(1, nkv, L, d), dtype) for _ in "kv")
    return q, k, v, w


# -------------------------------------------- the kernels against the dense
BAND_CASES = [
    # name, L, window, query heads, kv heads, head size
    ("1024-w128-group6", 1024, 128, 6, 1, 32),
    ("1024-w200-group8", 1024, 200, 8, 1, 32),
    ("1024-w384-group6-heads128", 1024, 384, 6, 1, 128),
    ("1024-w512-group8", 1024, 512, 16, 2, 16),
    ("2048-w512-group6", 2048, 512, 6, 1, 16),
    ("2048-w384-group8", 2048, 384, 8, 1, 16),
    ("1000-w200-pads-group8", 1000, 200, 8, 1, 32),
    ("2000-w512-pads-group6", 2000, 512, 6, 1, 16),
]


@pytest.mark.parametrize("L,window,h,nkv,d", [c[1:] for c in BAND_CASES],
                         ids=[c[0] for c in BAND_CASES])
def test_the_band_walk_matches_the_dense_reference(L, window, h, nkv, d):
    """Forward, dQ, dK and dV of the band walk, the tiles ``_tiling``
    gives, against the dense reference in float32: windows of a sub-tile,
    of none of its multiples, of three and of four; a padded tail (where
    the split pair's gradients on the same forward's residuals are held to
    the fused kernel's too); query groups of 6 and 8."""
    fwd, _, dkv = fa._tiling(L, d, 4, window)
    assert isinstance(fwd, BandTiles) and isinstance(dkv, BandTiles)
    assert fa.fused_bwd_supported(L, d, jnp.float32, window)
    q, k, v, w = _operands(np.random.RandomState(L + window), L, h, nkv, d)
    out, vjp = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, True, None, True, window), q, k, v)
    got = (out,) + vjp(w)
    ref_out, ref_vjp = jax.vjp(lambda a, b, c: attention_reference(
        a, b, c, causal=True, window=window), q, k, v)
    want = (ref_out,) + ref_vjp(w)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    if L % 128:
        _, res = fa._flash_fwd(q, k, v, True, None, True, window)
        split = fa._flash_bwd(True, None, True, window, None, 0, res, w,
                              fused=False)
        for a, b in zip(got[1:], split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def test_bf16_band_grads_come_back_at_kv_resolution():
    """bf16 operands, heads of 128 in groups of 8, a window of 512: the
    band walk's outputs in bf16, within the bf16 flash tests' bounds of
    the float32 reference."""
    rs = np.random.RandomState(5)
    q, k, v, w = _operands(rs, 1024, 8, 1, 128, jnp.bfloat16)
    out, vjp = jax.vjp(lambda a, b, c: flash_attention(
        a, b, c, True, None, True, 512), q, k, v)
    got = (out,) + vjp(w)
    f32 = [t.astype(jnp.float32) for t in (q, k, v, w)]
    ref_out, ref_vjp = jax.vjp(lambda a, b, c: attention_reference(
        a, b, c, causal=True, window=512), *f32[:3])
    for a, b in zip(got, (ref_out,) + ref_vjp(f32[3])):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.1)


# -------------------------------------------------------- the schedule
def _visits(g, turned=False):
    """{(q sub-tile, kv sub-tile): masked} that the band walk's live steps
    visit, from the plans, in the forward's or the backward's walk."""
    sub = g.sub
    seen = {}
    if turned:
        lo, hi = g.q_range(np.arange(g.n_k), np)
        steps = [(i, j) for j in range(g.n_k)
                 for i in range(lo[j], hi[j] + 1)]
    else:
        lo, hi = g.kv_range(np.arange(g.n_q), np)
        steps = [(i, j) for i in range(g.n_q)
                 for j in range(lo[i], hi[i] + 1)]
    for i, j in steps:
        offset = i * g.bq - j * g.bk if turned else j * g.bk - i * g.bq
        for r, runs in enumerate(g.band_plan(offset, turned)):
            for c, n, masked in runs:
                for x in range(c, c + n):
                    rows, cols = r, x
                    if turned:
                        rows, cols = x, r
                    at = (i * g.bq // sub + rows, j * g.bk // sub + cols)
                    assert at not in seen
                    seen[at] = masked
    return seen


@pytest.mark.parametrize("L,block,sub,window", [
    (1024, 512, 128, 128), (1024, 512, 128, 200), (1024, 512, 128, 384),
    (1000, 512, 128, 512), (2048, 512, 256, 512), (2048, 256, 128, 512),
    (4096, 1024, 128, 300), (8192, 512, 128, 512)])
def test_the_plans_visit_the_kept_sub_tiles_and_mask_only_the_edges(
        L, block, sub, window):
    """Against a brute-force count over the padded square: the forward's
    and the backward's walks each visit every sub-tile that holds a kept
    score once and no other, and mask exactly those that hold a dropped
    one beside it; ``tile_counts`` counts what they visit."""
    g = fa._geom(BandTiles(block, block, sub), L, True, window)
    n = g.n_q * g.bq
    pos = np.arange(n)
    keep = (pos[:, None] >= pos[None, :]) \
        & (pos[:, None] - pos[None, :] < window)
    tiles = keep.reshape(n // sub, sub, n // sub, sub).transpose(0, 2, 1, 3)
    any_kept, all_kept = tiles.any((2, 3)), tiles.all((2, 3))
    want = {(a, b): not all_kept[a, b] for a, b in zip(*np.nonzero(any_kept))}
    assert _visits(g) == want
    assert _visits(g, turned=True) == want
    full, edge, skipped = fa.tile_counts(g)
    assert (full, edge) == (sum(not m for m in want.values()),
                            sum(want.values()))
    assert full + edge + skipped == (n // sub) ** 2


def test_laguna_window_layers_visit_a_quarter_more_than_they_keep():
    """`laguna-ep8-train-8k`'s window layers (8,192 rows, a window of
    512, heads of 128, bf16): the row walk's tiles of 256 x 512 visit
    twice the kept scores and mask every tile; the band walk visits at
    most 1.30 times them, masks 40% of its sub-tiles, and takes no more
    grid steps a head either way than the row walk's 64."""
    L, window = 8192, 512
    kept = L * window - window * (window - 1) / 2
    row = fa._geom(Tiles(256, 512, 512), L, True, window)
    full, edge, _ = fa.tile_counts(row)
    assert (full, edge) == (0, 62)
    assert (full + edge) * 256 * 512 / kept == pytest.approx(2.0, abs=1e-3)
    assert row.n_q * row.kv_steps() == 64
    fwd, _, dkv = fa._tiling(L, 128, 2, window)
    assert fwd == dkv == BandTiles(2048, 2048, 128)
    g = fa._geom(fwd, L, True, window)
    full, edge, _ = fa.tile_counts(g)
    assert (full + edge) * g.sub ** 2 / kept <= 1.30
    assert edge / (full + edge) == pytest.approx(0.40, abs=0.01)
    bwd = fa._geom(dkv, L, True, window)
    assert g.n_q * g.kv_steps() <= 64 and bwd.n_k * bwd.q_steps() <= 64
    sched = fa.schedule(jnp.zeros((1, 64, L, 128), jnp.bfloat16),
                        jnp.zeros((1, 8, L, 128), jnp.bfloat16), True, window)
    assert sched["band"] and (sched["full"], sched["edge"]) == (full, edge)
    assert (sched["block_q"], sched["block_k"]) == (128, 128)


# every other attention shape a cell runs, and the tiles it had before the
# band walk: (L, head size, window, block length) -> forward, dQ, dK/dV
ROW = (Tiles(1024, 1024, 512), Tiles(1024, 1024, 1024),
       Tiles(1024, 1024, 1024))
OTHER_CELLS = [
    # smallthinker's global layer, keye's selection, laguna's full layers
    ("causal-8192", (8192, 128, 0, 0), ROW),
    ("smallthinker-window-4096", (8192, 128, 4096, 0), ROW),
    ("sdar-blockdiff-16384", (16384, 128, 0, 4), ROW),
    ("lfm2-heads64", (8192, 64, 0, 0), ROW),
]


@pytest.mark.parametrize("shape,want", [c[1:] for c in OTHER_CELLS],
                         ids=[c[0] for c in OTHER_CELLS])
def test_every_other_cells_shape_keeps_its_tiles(shape, want):
    L, d, window, block_len = shape
    got = fa._tiling(L, d, 2, window, block_len)
    assert got == want
    assert all(type(t) is Tiles for t in got)
    q = jnp.zeros((1, 8, L, d), jnp.bfloat16)
    assert not fa.schedule(q, q, not block_len, window, block_len)["band"]


def test_windows_outside_the_band_keep_the_row_walk():
    """Under a sub-tile's width and past a block the row walk stays; so it
    does where the sequence's block is narrower than the window."""
    for window in (96, 127, 2049, 4096):
        assert not any(t.band for t in fa._tiling(8192, 128, 2, window))
    for window in (128, 640, 2048):
        assert fa._tiling(8192, 128, 2, window)[0] \
            == BandTiles(2048, 2048, 128)
    assert not fa._tiling(640, 128, 2, 512)[0].band


# ------------------------------------------------------ the path account
def _traced_paths(conf, seq):
    """The ``attn.*`` path account of one traced train step (nothing
    lowered, nothing run) with the kernels forced on."""
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    zeros = np.zeros((1, 1, 1, seq), np.float32)
    before = telemetry.paths()
    ops.set_use_pallas(True)
    try:
        tr._get_step(True, False, False, False).trace(
            tr.params, tr.opt_state, None, None, tr._shard_batch(zeros),
            tr._shard_batch(zeros.reshape(1, seq)), np.int32(0),
            jax.random.PRNGKey(0))
    finally:
        ops.set_use_pallas(None)
    return {k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0) and k.startswith("attn.")}


SMALL_WIDTHS = dict(vocab=96, dim=64, nkvhead=2, head_dim=128,
                    expert_width=32)


def test_the_band_is_counted_in_lagunas_window_layers_only():
    """The layer layout of `laguna-ep8-train-8k` (two full layers, three
    in a window of 512) and of `smallthinker-ep4-train-8k` (one global
    layer, three in a window of 4,096) at their 8,192 rows and heads of
    128, narrow elsewhere: ``attn.flash.band`` 3 and 0."""
    seq, bf16 = 8192, "compute_dtype = bfloat16\n"
    laguna = models.laguna_moe_conf(
        seq=seq, dev="cpu", extra_cfg=bf16, full_heads=6, window_heads=8,
        layer_types=models.LAGUNA_LAYER_TYPES[:5], n_dense=1,
        dense_width=96, n_expert=16, top_k=4, shared_width=24, n_held=4,
        **SMALL_WIDTHS)
    paths = _traced_paths(laguna, seq)
    assert paths["attn.flash"] == 5 and paths["attn.flash.band"] == 3
    smallthinker = models.smallthinker_conf(
        seq=seq, dev="cpu", extra_cfg=bf16, nhead=4, nlayer=4, n_expert=8,
        top_k=2, **SMALL_WIDTHS)
    paths = _traced_paths(smallthinker, seq)
    assert paths["attn.flash"] == 4 and "attn.flash.band" not in paths
