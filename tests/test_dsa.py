"""Learned sparse attention in training (``attn_mask = dsa``), the program's
parts on the CPU at a small size: the index scores and their blocked
backward, the exact selection (the plain lines and the kernel that sorts nothing,
``ops/dsa_select_pallas.py``, in the interpreter), the fourth mask of the
flash kernels (``ops/flash_attn.py``, in the interpreter) and the target pass,
``AttentionLayer`` with its indexer, the loss term a layer that is no loss
layer adds to the step's, and the whole block through ``Trainer.update`` on
the forced flash path; the index scores' backward as one kernel over the
causal tiles (``ops/dsa_index_pallas.py``, in the interpreter) against
their plain lines. The plain side is the benchmark's reference
(``benchmark/references/keye_dsa.py``); the whole model against it through
the cell's own ``run_cell`` is ``tests/benchmark/test_keye_dsa.py``."""

import io
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import dsa_inputs, netconf  # noqa: E402
from benchmark.references import keye_dsa  # noqa: E402
from cxxnet_tpu import models, ops  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.layer.base import ApplyContext  # noqa: E402
from cxxnet_tpu.layer.layers import AttentionLayer, MoELayer  # noqa: E402
from cxxnet_tpu.ops import dsa  # noqa: E402
from cxxnet_tpu.ops import flash_attn as fa  # noqa: E402
from cxxnet_tpu.ops.flash_attn import Tiles  # noqa: E402
from cxxnet_tpu.utils import telemetry  # noqa: E402

D, L, NEXP, WIDTH = 64, 64, 8, 32
KEYS = {"nhead": 4, "nkvhead": 2, "head_dim": 16, "causal": 1,
        "attn_mask": "dsa", "index_heads": 2, "index_dim": 8,
        "index_topk": 8, "qk_norm": 1, "rope": 1, "rope_base": 10000000}


# ------------------------------------------------ index scores and selection
def _plain_scores(qi, ki, w):
    z = jnp.maximum(jnp.einsum("bjtd,bsd->bjts", qi, ki), 0.0)
    return jnp.einsum("bjts,btj->bts", z, w)


def _index_operands(rs, b, J, rows, di):
    return (jnp.asarray(rs.randn(b, J, rows, di), jnp.float32),
            jnp.asarray(rs.randn(b, rows, di), jnp.float32),
            jnp.asarray(rs.randn(b, rows, J), jnp.float32))


@pytest.mark.parametrize("rows", [96, 1024], ids=["one_block", "two_blocks"])
def test_the_index_scores_and_their_blocked_backward_agree(rows):
    """Forward and the three gradients against plain autodiff; at 1,024
    rows the queries go two blocks of 512 and kI's gradient is summed
    over them."""
    qi, ki, w = _index_operands(np.random.RandomState(0), 2, 3, rows, 8)
    g = jnp.asarray(np.random.RandomState(1).randn(2, rows, rows),
                    jnp.float32)
    got, vjp = jax.vjp(dsa.index_scores, qi, ki, w)
    want, vjp_w = jax.vjp(_plain_scores, qi, ki, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(vjp(g), vjp_w(g)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)


def _causal_grad(rs, rows, case):
    """(2, rows, rows) float32 gradients of the scores, nought above the
    diagonal as ``index_loss``'s is: dense on the causal triangle, on a
    selection's pairs alone, or on the tiles of 128 astride the diagonal
    alone, so that whole tiles below it are nought."""
    g = np.tril(rs.randn(2, rows, rows)).astype(np.float32)
    if case == "selection":
        scores = _plain_scores(*_index_operands(rs, 2, 2, rows, 8))
        g = g * np.asarray(dsa.select(scores, rows // 8), np.float32)
    elif case == "diagonal_tiles":
        tile = np.arange(rows) // 128
        g = g * (tile[:, None] == tile[None, :])
    return jnp.asarray(g)


@pytest.mark.parametrize("rows,J,di", [(128, 3, 8), (384, 2, 16)],
                         ids=["one_tile", "three_by_three_tiles"])
@pytest.mark.parametrize("case", ["causal", "selection", "diagonal_tiles"])
def test_the_fused_index_backward_is_the_plain_one(rows, J, di, case):
    """The kernel in the interpreter (``fused``) against the plain blocked
    lines and plain autodiff, the gradient nought above the diagonal: at
    384 rows the tiles are 128, so kI's gradient is summed over three
    query blocks, dq and dw over up to three key blocks, and the tiles
    astride the diagonal mask the gradient."""
    rs = np.random.RandomState(7)
    assert ops.dsa_index_bwd_supported(rows, J, di, jnp.float32)
    qi, ki, w = _index_operands(rs, 2, J, rows, di)
    g = _causal_grad(rs, rows, case)
    ops.set_use_pallas(True)
    try:
        got, vjp = jax.vjp(lambda *a: dsa.index_scores(*a, True), qi, ki, w)
        fused = vjp(g)
    finally:
        ops.set_use_pallas(None)
    np.testing.assert_array_equal(got, dsa.index_scores(qi, ki, w))
    plain = jax.vjp(dsa.index_scores, qi, ki, w)[1](g)
    auto = jax.vjp(_plain_scores, qi, ki, w)[1](g)
    for a, b, c in zip(fused, plain, auto):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-3)


def test_the_fused_index_backward_reads_no_gradient_above_the_diagonal():
    """A gradient that is NOT nought above the diagonal: the kernel reads
    the causal triangle of it alone, so it gives the plain lines' answer
    on the triangle and nothing of the rest."""
    rs = np.random.RandomState(8)
    rows = 256
    qi, ki, w = _index_operands(rs, 1, 2, rows, 8)
    g = jnp.asarray(rs.randn(1, rows, rows), jnp.float32)
    ops.set_use_pallas(True)
    try:
        fused = jax.vjp(lambda *a: dsa.index_scores(*a, True),
                        qi, ki, w)[1](g)
    finally:
        ops.set_use_pallas(None)
    plain = jax.vjp(dsa.index_scores, qi, ki, w)[1](jnp.tril(g))
    for a, b in zip(fused, plain):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows,J,di,why", [
    (64, 2, 8, "rows that are no whole lane tiles"),
    (200, 16, 64, "rows that are no whole lane tiles"),
    (512, 2, 12, "heads of a width the MXU's operands do not tile"),
    (128 * 1024, 16, 64, "a resident dK beyond the VMEM budget")])
def test_the_index_backward_kernel_refuses_what_it_does_not_tile(
        rows, J, di, why):
    assert not ops.dsa_index_bwd_supported(rows, J, di, jnp.bfloat16), why


def _sets_by_top_k(scores, topk):
    """Row t's kept keys as top_k orders them: the first min(t + 1, topk)
    indices over the causal scores."""
    rows = scores.shape[-1]
    masked = jnp.where(jnp.tril(jnp.ones((rows, rows), bool)), scores,
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(topk, rows))
    idx = np.asarray(idx)
    keep = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(rows):
            keep[b, t, idx[b, t, :min(t + 1, topk)]] = True
    return keep


def test_the_selection_is_top_ks_set_and_early_rows_keep_everything():
    rows, topk = 96, 10
    scores = _plain_scores(*_index_operands(np.random.RandomState(2), 2, 2,
                                            rows, 8))
    sel = np.asarray(dsa.select(scores, topk)) != 0
    np.testing.assert_array_equal(sel, _sets_by_top_k(scores, topk))
    counts = sel.sum(-1)
    np.testing.assert_array_equal(
        counts, np.broadcast_to(np.minimum(np.arange(rows) + 1, topk),
                                counts.shape))
    assert sel.sum() == 2 * dsa.kept_scores(rows, topk) \
        == 2 * keye_dsa.kept_scores(rows, topk)
    # t + 1 <= topk: the whole causal row, and nothing past the diagonal
    np.testing.assert_array_equal(sel[:, :topk, :topk],
                                  np.broadcast_to(np.tril(np.ones(
                                      (topk, topk), bool)), (2, topk, topk)))
    assert not np.triu(sel, 1).any()
    # the reference scatters the same sets
    ref = np.asarray(keye_dsa.select_rows(scores[0], 0, topk))
    np.testing.assert_array_equal(ref, sel[0])
    # more keys asked for than the sequence has: plain causal
    np.testing.assert_array_equal(
        np.asarray(dsa.select(scores, 4096)) != 0,
        np.broadcast_to(np.tril(np.ones((rows, rows), bool)), sel.shape))


def test_a_planted_tie_astride_the_last_place_falls_to_the_lower_index():
    """Row 40 keeps 4 keys; three scores tie at what would be the third
    to fifth place: the two lower indices are kept, in the program and in
    the reference alike."""
    rows, topk = 48, 4
    scores = np.full((1, rows, rows), -5.0, np.float32)
    scores[0, :, 0] = -6.0
    scores[0, 40, [7, 30]] = 3.0, 2.0
    scores[0, 40, [12, 21, 35]] = 1.0           # the tie: two places left
    sel = np.asarray(dsa.select(jnp.asarray(scores), topk))[0] != 0
    assert sorted(np.flatnonzero(sel[40])) == [7, 12, 21, 30]
    ref = np.asarray(keye_dsa.select_rows(jnp.asarray(scores[0]), 0, topk))
    np.testing.assert_array_equal(ref, sel)
    # every other row is one long tie at -5: the lowest indices past key 0
    assert sorted(np.flatnonzero(sel[20])) == [1, 2, 3, 4]


# ------------------------------------------- the selection without a sort
def _sets_by_a_stable_sort(scores, topk):
    """Row t's kept keys by numpy alone: the causal keys in a stable sort
    by the order the docstring states (the float's bits, a negative's
    reversed, -0.0 as +0.0), the first min(t + 1, topk) of them."""
    bits = np.asarray(scores).view(np.int32).astype(np.int64)
    bits[bits == -(1 << 31)] = 0
    key = np.where(bits < 0, bits ^ 0x7fffffff, bits)
    keep = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            order = np.argsort(-key[b, t, :t + 1], kind="stable")
            keep[b, t, order[:topk]] = True
    return keep


def _row_counts(sel, topk):
    rows = sel.shape[-1]
    return np.broadcast_to(np.minimum(np.arange(rows) + 1, topk),
                           sel.shape[:2])


@pytest.mark.parametrize("rows,topk", [(256, 40), (384, 128)],
                         ids=["a_block_astride_topk", "whole_blocks"])
def test_the_fused_selection_is_the_plain_one_and_top_ks_set(rows, topk):
    """The kernel in the interpreter on two sequences of random scores:
    row blocks of 128, so at ``topk`` 40 the first block holds rows that
    keep everything and rows that choose; at 384 rows the first block
    searches nothing, and the chunks of 128 columns past a block's last
    row are not walked."""
    assert ops.dsa_select_supported(rows, topk)
    scores = _plain_scores(*_index_operands(np.random.RandomState(11), 2, 2,
                                            rows, 8))
    sel = np.asarray(ops.dsa_select(scores, topk))
    assert sel.dtype == np.int8 and sel.shape == scores.shape
    np.testing.assert_array_equal(sel, np.asarray(dsa.select(scores, topk)))
    np.testing.assert_array_equal(sel != 0, _sets_by_top_k(scores, topk))
    np.testing.assert_array_equal(sel.sum(-1), _row_counts(sel, topk))
    assert sel.sum() == 2 * dsa.kept_scores(rows, topk)


def _hard_rows(case, rows, topk):
    """(1, rows, rows) float32 scores whose rows are hard for a search on
    the bits, and the keys row 100 must keep where the case plants them."""
    rs = np.random.RandomState(12)
    x = rs.randn(1, rows, rows).astype(np.float32)
    want = None
    if case == "tie_astride_the_last_place":
        x[:] = -5.0
        x[0, :, 0] = -6.0
        x[0, 100, 40:40 + topk - 3] = 3.0
        x[0, 100, [12, 21, 35, 90, 95]] = 1.0   # the tie: three places left
        want = [12, 21, 35] + list(range(40, 40 + topk - 3))
    elif case == "a_row_of_one_value":
        x[:] = 0.25
        want = list(range(topk))
    elif case == "signed_zeros_astride_the_last_place":
        x = np.where(rs.rand(1, rows, rows) < 0.5, 0.0, -0.0).astype(
            np.float32)
        x[0, :, 1] = -0.0
        x[0, :, 2] = 0.0
        x[0, 100, [50, 70]] = 1.0, 2.0
        x[0, :, 5] = -1.0
        want = [s for s in range(rows) if s != 5][:topk - 2] + [50, 70]
    elif case == "negative_scores_only":
        x = -np.abs(x) - 1e-3
    elif case == "infinities":
        x[rs.rand(1, rows, rows) < 0.85] = -np.inf
        x[rs.rand(1, rows, rows) < 0.05] = np.inf
    elif case == "denormals":
        x = (rs.randint(-40, 40, (1, rows, rows)).astype(np.int64)
             & 0x807fffff).astype(np.uint32).view(np.float32)
        assert np.all(np.abs(x) < np.finfo(np.float32).tiny)
    elif case == "nans_of_both_signs":
        x[rs.rand(1, rows, rows) < 0.1] = np.nan
        x[rs.rand(1, rows, rows) < 0.1] = -np.nan
        x[0, 90:, :40] = np.nan                 # more NaNs than places
    else:
        raise AssertionError(case)
    return np.ascontiguousarray(x, np.float32), want


@pytest.mark.parametrize("case", [
    "tie_astride_the_last_place", "a_row_of_one_value",
    "signed_zeros_astride_the_last_place", "negative_scores_only",
    "infinities", "denormals", "nans_of_both_signs"])
def test_rows_that_are_hard_for_a_search_on_the_bits(case):
    """Kernel and plain lines give one array, a stable sort by the stated
    order gives the same sets, and a row keeps min(t + 1, topk) keys
    whatever it holds: equal scores to the lower index, -0.0 and +0.0 one
    value, the infinities and the NaNs in their places in the order."""
    rows, topk = 128, 20
    x, want = _hard_rows(case, rows, topk)
    fused = np.asarray(ops.dsa_select(jnp.asarray(x), topk))
    plain = np.asarray(dsa.select(jnp.asarray(x), topk))
    np.testing.assert_array_equal(fused, plain)
    np.testing.assert_array_equal(fused != 0, _sets_by_a_stable_sort(x, topk))
    np.testing.assert_array_equal(fused.sum(-1), _row_counts(fused, topk))
    assert not np.triu(fused[0], 1).any()
    if want is not None:
        assert sorted(np.flatnonzero(fused[0, 100])) == sorted(want)


def test_the_plain_selection_counts_signed_zeros_as_one_value():
    """What ``lax.top_k`` on the floats did not: -0.0 below +0.0 there,
    equal in the compare behind it, and a row whose last place fell on a
    zero kept more than ``topk`` keys."""
    rs = np.random.RandomState(13)
    x = np.round(rs.randn(1, 96, 96) * 2) / 2
    x = np.where(rs.rand(1, 96, 96) < 0.5, x, -x).astype(np.float32)
    assert (np.signbit(x) & (x == 0)).any() and (~np.signbit(x) & (x == 0)
                                                 ).any()
    sel = np.asarray(dsa.select(jnp.asarray(x), 32))
    np.testing.assert_array_equal(sel.sum(-1), _row_counts(sel, 32))
    np.testing.assert_array_equal(sel != 0, _sets_by_a_stable_sort(x, 32))


@pytest.mark.parametrize("rows,topk,why", [
    (64, 8, "rows that are no whole lane tiles"),
    (200, 30, "rows that are no whole lane tiles"),
    (256, 256, "a selection that keeps every key"),
    (256, 4096, "a selection that keeps every key"),
    (128 * 1024, 2048, "a row block beyond the VMEM budget")])
def test_the_selection_kernel_refuses_what_it_does_not_tile(rows, topk, why):
    assert not ops.dsa_select_supported(rows, topk), why


def test_the_index_loss_and_its_gradient_agree_with_the_plain_lines():
    rs = np.random.RandomState(3)
    rows, topk = 96, 12
    scores = jnp.asarray(rs.randn(2, rows, rows), jnp.float32)
    sel = dsa.select(scores, topk)
    p = jnp.where(sel != 0, jnp.asarray(rs.rand(2, rows, rows),
                                        jnp.float32), 0.0)
    p = p / jnp.sum(p, -1, keepdims=True)
    p = p.at[0, 50, 50].set(0.0)              # a kept key of no weight

    def plain(s):
        logq = jax.nn.log_softmax(jnp.where(sel != 0, s, -jnp.inf), -1)
        live = (sel != 0) & (p > 0)
        return jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(
            live, p, 1.0)) - jnp.where(live, logq, 0.0)), 0.0), (1, 2))
    got, vjp = jax.vjp(lambda s: dsa.index_loss(s, sel, p), scores)
    want, vjp_w = jax.vjp(plain, scores)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    g = jnp.asarray([0.5, 2.0])
    np.testing.assert_allclose(vjp(g)[0], vjp_w(g)[0], rtol=1e-4, atol=1e-6)
    assert float(got.min()) > 0.0             # a divergence


# ---------------------------------------------- the kernels under a selection
def _vs_plain(rs, rows, h, nkv, d, topk, tiles, block=None, tol=1e-4):
    q = jnp.asarray(rs.randn(2, h, rows, d), jnp.float32)
    k = jnp.asarray(rs.randn(2, nkv, rows, d), jnp.float32)
    v = jnp.asarray(rs.randn(2, nkv, rows, d), jnp.float32)
    do = jnp.asarray(rs.randn(2, h, rows, d), jnp.float32)
    sel = dsa.select(jnp.asarray(rs.randn(2, rows, rows), jnp.float32),
                     topk)

    def flash(q, k, v):
        return fa.flash_attention_selected(q, k, v, sel, None, True,
                                           tiles)[0]

    def plain(q, k, v):
        probs, _ = dsa.selected_probs_plain(q, k, sel, d ** -0.5)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v).reshape(q.shape)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do)
    for a, b in zip(run(flash), run(plain)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)
    _, lse = fa.flash_attention_selected(q, k, v, sel, None, True, tiles)
    p = fa.selected_probs(q, k, lse, sel, None, True, block)
    want = dsa.selected_probs_plain(q, k, sel, d ** -0.5)[1]
    np.testing.assert_allclose(np.asarray(p), np.asarray(want), rtol=tol,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, rtol=1e-5)
    assert not np.asarray(p)[np.asarray(sel) == 0].any()


@pytest.mark.parametrize("rows,topk,tiles,block", [
    (384, 40, (Tiles(128, 128, 128),) * 3, (128, 128)),
    (512, 100, (Tiles(128, 256, 128), Tiles(128, 256, 128),
                Tiles(256, 128, 128)), (256, 128)),
    (200, 30, (Tiles(128, 128, 128),) * 3, (128, 128)),
    (256, 4096, None, None)],
    ids=["3x3_tiles", "rectangular", "padded_tail", "topk_over_L"])
def test_the_kernels_under_a_selection_agree_with_the_plain_lines(
        rows, topk, tiles, block):
    """Forward, dQ, dK, dV and the target pass in the interpreter, a group
    of two on two sequences (each with a selection of its own): square and
    rectangular tiles, a tail that the tiles pad, and a selection that
    keeps every causal key."""
    _vs_plain(np.random.RandomState(4), rows, 4, 2, 16, topk, tiles, block)


def test_under_a_selection_every_visited_tile_is_an_edge_tile():
    """The schedule is the causal one and no tile is taken whole; the
    other masks' schedules read as they did."""
    q, k = jnp.zeros((1, 4, 1024, 16)), jnp.zeros((1, 2, 1024, 16))
    causal = fa.schedule(q, k, True)
    sel = fa.schedule(q, k, True, 0, 0, True)
    assert sel["full"] == 0 and sel["edge"] == causal["full"] + causal["edge"]
    assert sel["skipped"] == causal["skipped"] > 0
    assert (sel["block_q"], sel["block_k"]) == (causal["block_q"],
                                                causal["block_k"])
    g = fa._geom(Tiles(128, 128, 128), 512, True, 0)
    assert not g.select and fa.tile_counts(g) == (6, 4, 6)
    with pytest.raises(AssertionError, match="a selection is causal"):
        fa._geom(Tiles(128, 128, 128), 512, False, 0, 0, True)
    with pytest.raises(AssertionError, match="a selection is causal"):
        fa._geom(Tiles(128, 128, 128), 512, True, 128, 0, True)


# --------------------------------------------------------------- the layer
def _attention(rows=L, **keys):
    lay = AttentionLayer()
    for k, v in dict(KEYS, **keys).items():
        lay.set_param(k, str(v))
    lay.set_param("batch_size", "2")
    lay.infer_shape([(2, D, 1, rows)])
    return lay


def _attention_weights(lay, seed=4):
    rs = np.random.RandomState(seed)
    w = {k: jnp.asarray(v) * 10 for k, v in lay.init_params(rs).items()}
    for key, n in (("qnorm", 16), ("knorm", 16), ("idx_gain", 8)):
        if key in w:                          # gains that are not all one
            w[key] = jnp.asarray(1.0 + 0.3 * rs.randn(n), jnp.float32)
    w["idx_bias"] = jnp.asarray(0.2 * rs.randn(8), jnp.float32)
    return w


def _ref_layer(**keys):
    return netconf.Layer("attention", "a", ["x"], ["y"], {
        k: str(v) for k, v in dict(KEYS, **keys).items()})


def _reference_attention(w, x, topk=None, detach=True, **keys):
    """The reference's layer on each sequence of ``x`` (b, D, 1, rows):
    the output in the program's layout, and the sequences' L_idx."""
    ww = dict(w, wmat=w["wqkv"])
    rows = x.shape[-1]
    outs = [keye_dsa._attention(_ref_layer(**keys), "highest", ww,
                                x[i].reshape(D, rows).T, topk, detach)
            for i in range(x.shape[0])]
    return (jnp.stack([o[0].T.reshape(D, 1, rows) for o in outs]),
            jnp.stack([o[1] for o in outs]))


def _apply(lay, w, x, train=True, **ctx):
    c = ApplyContext(train=train, **ctx)
    c.conn_index = 7
    y, = lay.apply(w, [jnp.asarray(x)], c)
    return y, c


@pytest.mark.parametrize("keys", [{}, {"index_topk": 24}, {"qk_norm": 0},
                                  {"rope": 0}, {"index_heads": 4}],
                         ids=["k8", "k24", "no_qk_norm", "nope", "4_heads"])
def test_the_layer_agrees_with_the_reference_output_loss_and_gradients(keys):
    """The output, L_idx (the term the layer adds to the step's loss, here
    over a batch of two), and the gradient of output-and-loss in every
    leaf and in the input: the indexer's leaves learn from L_idx alone,
    the others and the stream from the output alone."""
    x = jnp.asarray(np.random.RandomState(5).randn(2, D, 1, L), jnp.float32)
    lay = _attention(**keys)
    w = _attention_weights(lay)
    assert set(w) == {"wqkv", "wo", "widx_q", "widx_k", "widx_w",
                      "idx_gain", "idx_bias"} | (
        {"qnorm", "knorm"} if lay.qk_norm else set())
    mix = jnp.asarray(np.random.RandomState(6).randn(2, D, 1, L),
                      jnp.float32)

    def program(w, x):
        y, c = _apply(lay, w, x)
        return jnp.sum(y * mix) + sum(c.losses), (y, c.losses,
                                                  c.layer_stats[7])

    def reference(w, x):
        y, kl = _reference_attention(w, x, **keys)
        return jnp.sum(y * mix) + jnp.sum(kl) / 2, (y, kl)
    (_, (y, losses, stats)), got = jax.value_and_grad(
        program, (0, 1), has_aux=True)(w, x)
    (_, (y_ref, kl)), want = jax.value_and_grad(
        reference, (0, 1), has_aux=True)(w, x)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-5)
    assert len(losses) == 1
    np.testing.assert_allclose(losses[0], jnp.sum(kl) / 2, rtol=1e-5)
    n_sel, kl_mean = np.asarray(stats)
    assert n_sel == dsa.kept_scores(L, lay.index_topk)
    np.testing.assert_allclose(kl_mean, jnp.mean(kl), rtol=1e-5)
    for key in w:
        np.testing.assert_allclose(got[0][key], want[0][key], rtol=2e-4,
                                   atol=2e-5, err_msg=key)
        assert float(jnp.abs(want[0][key]).max()) > 0, key
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-5)
    # each part of the mechanism is in it: read without the selection,
    # or with the indexer attached, the reference says otherwise
    assert np.abs(y - _reference_attention(w, x, topk=0, **keys)[0]
                  ).max() > 1e-3
    attached = jax.grad(lambda x: jnp.sum(_reference_attention(
        w, x, detach=False, **keys)[1]))(x)
    assert float(jnp.abs(attached).max()) > 1e-4


def test_the_selected_sets_are_the_references_as_sets():
    x = np.random.RandomState(8).randn(1, D, 1, L).astype(np.float32)
    lay = _attention()
    w = _attention_weights(lay)
    seq = jnp.asarray(x).reshape(1, D, L).transpose(0, 2, 1)
    sel = np.asarray(dsa.select(dsa.index_scores(
        *lay._index_operands(seq, w)), lay.index_topk))[0] != 0
    ref = keye_dsa._attention(_ref_layer(), "highest",
                              dict(w, wmat=w["wqkv"]), seq[0], None, True,
                              keep_sel=True)[3]
    np.testing.assert_array_equal(sel, np.asarray(ref))
    assert sel.sum() == dsa.kept_scores(L, 8)


def test_the_indexers_leaves_have_tags_and_are_saved_and_loaded():
    lay = _attention()
    assert lay.visit_order() == [
        ("wmat", "wqkv"), ("wo", "wo"), ("qnorm", "qnorm"),
        ("knorm", "knorm"), ("widx_q", "widx_q"), ("widx_k", "widx_k"),
        ("widx_w", "widx_w"), ("idx_gain", "idx_gain"),
        ("idx_bias", "idx_bias")]
    w = lay.init_params(np.random.RandomState(0))
    assert w["widx_q"].shape == (D, 16) and w["widx_k"].shape == (D, 8)
    assert w["widx_w"].shape == (D, 2)
    assert (w["idx_gain"] == 1).all() and (w["idx_bias"] == 0).all()
    from cxxnet_tpu.utils.serializer import Reader, Writer
    w = {k: np.asarray(v) for k, v in _attention_weights(lay).items()}
    buf = io.BytesIO()
    lay.save_model(Writer(buf), w)
    buf.seek(0)
    back = _attention().load_model(Reader(buf))
    assert set(back) == set(w)
    for key in w:
        np.testing.assert_array_equal(back[key], w[key])
    assert _attention(attn_mask="causal", index_heads=0, index_dim=0,
                      index_topk=0).visit_order()[-1] == ("knorm", "knorm")


@pytest.mark.parametrize("keys,said", [
    ({"causal": 0}, "causal must be 1 and attn_window 0"),
    ({"attn_window": 16}, "a window together with a selection is not"),
    ({"index_topk": 0}, "needs index_heads, index_dim and index_topk"),
    ({"index_dim": 7}, "index_dim must be even"),
    ({"block_len": 4}, "block_len is the block of attn_mask = blockdiff"),
    ({"attn_mask": "causal"}, "are the indexer of attn_mask = dsa"),
    ({"attn_mask": "sparse"}, "attn_mask must be")])
def test_keys_that_do_not_go_with_the_selection_are_refused(keys, said):
    with pytest.raises(ValueError, match=said):
        _attention(**keys)


def test_a_cache_position_and_sequence_parallelism_are_refused_in_words():
    lay = _attention()
    w = _attention_weights(lay)
    x = np.zeros((2, D, 1, L), np.float32)
    with pytest.raises(ValueError, match="selection inside prefill / "
                                         "decode from a cache are not"):
        _apply(lay, w, x, decode_pos=0)

    class _Mesh:
        axis_names, shape = ("sp",), {"sp": 2}
    with pytest.raises(ValueError, match="under sequence parallelism"):
        _apply(lay, w, x, mesh=_Mesh())


def test_scoring_adds_no_loss_and_gives_the_training_rows():
    x = np.random.RandomState(9).randn(2, D, 1, L).astype(np.float32)
    lay = _attention()
    w = _attention_weights(lay)
    y, c = _apply(lay, w, x, train=False)
    assert not c.losses and not c.layer_stats
    np.testing.assert_array_equal(y, _apply(lay, w, x)[0])


def test_the_flash_path_runs_the_selection_and_counts_it_once_a_layer():
    """Forced through the interpreter at 512 rows: ``attn.flash`` and
    ``attn.dsa`` and ``loss.index`` once each, the schedule's tiles (all
    edge) and the gauges; the dense path counts the selection too, and
    both give the same rows and the same loss."""
    rows = 512
    lay = _attention(rows, index_topk=64)
    w = _attention_weights(lay)
    x = np.random.RandomState(6).randn(2, D, 1, rows).astype(np.float32)

    def delta(force):
        before = telemetry.paths()
        telemetry.enable()
        ops.set_use_pallas(force)
        try:
            y, c = _apply(lay, w, x)
            gauges = telemetry.summary()["gauges"]
        finally:
            ops.set_use_pallas(None)
            telemetry.disable()
            telemetry.reset()
        return np.asarray(y), float(c.losses[0]), gauges, {
            k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0)}

    y_flash, kl_flash, gauges, paths = delta(True)
    sched = ops.flash_schedule(jnp.zeros((2, 4, rows, 16)),
                               jnp.zeros((2, 2, rows, 16)), True, 0, 0,
                               True)
    assert gauges["dsa.topk"] == 64
    assert gauges["dsa.kept_scores"] == dsa.kept_scores(rows, 64)
    assert gauges["flash.block_q"] == sched["block_q"]
    assert sched["full"] == 0 and sched["edge"] > 0
    assert paths == {"attn.flash": 1, "attn.dsa": 1, "loss.index": 1,
                     "attn.select.fused": 1, "attn.index_bwd.fused": 1,
                     "attn.prep.xla": 1, "flash.tiles.edge": sched["edge"],
                     **({"flash.tiles.skipped": sched["skipped"]}
                        if sched["skipped"] else {})}
    y_dense, kl_dense, gauges, paths = delta(False)
    assert paths == {"attn.dense": 1, "attn.dsa": 1, "loss.index": 1,
                     "attn.select.xla": 1, "attn.index_bwd.xla": 1,
                     "attn.prep.xla": 1}
    np.testing.assert_allclose(y_flash, y_dense, rtol=2e-4, atol=2e-5)
    assert kl_flash == pytest.approx(kl_dense, rel=1e-5)


def test_a_layer_whose_rows_the_selection_kernel_does_not_tile_counts_xla():
    """64 rows with the kernels forced on: ``ops.dsa_select_supported``
    says no, the layer takes the plain lines and says so, and gives what
    it gives with the kernels off."""
    lay = _attention()
    w = _attention_weights(lay)
    x = np.random.RandomState(6).randn(2, D, 1, L).astype(np.float32)
    assert not ops.dsa_select_supported(L, lay.index_topk)
    before = telemetry.paths()
    ops.set_use_pallas(True)
    try:
        y, _ = _apply(lay, w, x)
    finally:
        ops.set_use_pallas(None)
    paths = {k: n - before.get(k, 0) for k, n in telemetry.paths().items()
             if n != before.get(k, 0)}
    assert paths.get("attn.select.xla") == 1
    assert "attn.select.fused" not in paths
    assert not ops.dsa_index_bwd_supported(L, lay.index_heads, lay.index_dim,
                                           jnp.float32)
    assert paths.get("attn.index_bwd.xla") == 1
    assert "attn.index_bwd.fused" not in paths
    np.testing.assert_allclose(y, _apply(lay, w, x)[0], rtol=2e-4, atol=2e-5)


# ------------------------------------------- a share of the deployment's layer
def _moe_layer(held=0, offset=0):
    lay = MoELayer()
    for k, v in {"nexpert": NEXP, "top_k": 2, "nhidden": WIDTH,
                 "expert_act": "swiglu", "nexpert_held": held or NEXP,
                 "expert_offset": offset}.items():
        lay.set_param(k, str(v))
    lay.infer_shape([(1, D, 1, L)])
    return lay


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """A deployment of four chips a layer at this size: attention and
    indexer are replicated (computed alike everywhere: counted once), each
    chip's experts give their part of the layer's result; the parts summed
    are what the uncut reference's attention + experts give."""
    rs = np.random.RandomState(10)
    x = jnp.asarray(rs.randn(1, D, 1, L), jnp.float32)
    att = _attention()
    w_att = _attention_weights(att)
    w_moe = {"gate": jnp.asarray(rs.randn(NEXP, D) * 0.3, jnp.float32),
             "experts": jnp.asarray(rs.randn(NEXP, D, WIDTH) * 0.2,
                                    jnp.float32),
             "up": jnp.asarray(rs.randn(NEXP, D, WIDTH) * 0.2, jnp.float32),
             "down": jnp.asarray(rs.randn(NEXP, WIDTH, D) * 0.2,
                                 jnp.float32)}
    y, c = _apply(att, w_att, x)
    stream = x + y                              # what every chip holds
    parts = []
    for chip in range(4):
        lo = 2 * chip
        share = dict(w_moe, **{k: w_moe[k][lo:lo + 2]
                               for k in ("experts", "up", "down")})
        out, = _moe_layer(2, lo).apply(
            share, [stream], ApplyContext(train=True))
        parts.append(out)
    got = stream + sum(parts)
    # the uncut reference: one attention with its indexer, all 8 experts
    rows = stream.reshape(D, L).T
    y_ref, kl = _reference_attention(w_att, x)
    moe_lay = netconf.Layer("moe", "m", ["x"], ["y"], {
        "nexpert": str(NEXP), "top_k": "2", "nhidden": str(WIDTH),
        "expert_act": "swiglu", "expert_offset": "0"})
    whole, pairs = keye_dsa.moe(moe_lay, "highest",
                                dict(w_moe, wmat=w_moe["experts"]),
                                (x + y_ref).reshape(D, L).T)
    want = (x + y_ref).reshape(D, L).T + whole
    np.testing.assert_allclose(got.reshape(D, L).T, want, rtol=1e-4,
                               atol=1e-4)
    assert int(pairs) == 2 * L                  # every pair is held somewhere
    np.testing.assert_allclose(c.losses[0], kl[0] / 2, rtol=1e-5)
    del rows


# ------------------------------------------------- the step, from conf text
SMALL = dict(vocab=96, dim=D, nhead=4, nkvhead=2, head_dim=16, nlayer=2,
             n_expert=16, top_k=4, expert_width=WIDTH, n_held=4,
             expert_offset=2, index_heads=2, index_dim=8, index_topk=40)


def _trainer(conf, force):
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    ops.set_use_pallas(force)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _batch(seq, rows=1):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, seq + 1), 0,
                                96).astype(jnp.float32)
    b = DataBatch()
    b.data = tokens[:, :-1].reshape(rows, 1, 1, seq)
    b.label = tokens[:, 1:]
    b.batch_size = rows
    return b


@pytest.mark.parametrize("over, prep", [
    ({}, {"attn.prep.xla": 2}),
    (dict(nhead=2, nkvhead=1, head_dim=128, nlayer=4),
     {"attn.prep.fused": 4}),
], ids=["heads-of-16", "heads-of-128"])
def test_the_step_on_the_forced_flash_path_counts_its_paths(over, prep):
    """``Trainer.update`` from ``models.keye_dsa_conf``'s text with the
    kernels forced on (the interpreter), 256 rows: every attention layer
    takes the flash kernels under its selection and adds its loss, every
    ``moe`` layer the sparse lowering on a bounded sorted side; each
    health check holds ``dsa.selected/<layer>`` at the static
    ``dsa.kept_scores``; and the step gives the dense path's loss."""
    seq = 256
    small = dict(SMALL, **over)
    n = small["nlayer"]
    conf = models.keye_dsa_conf(
        seq=seq, dev="cpu", extra_cfg="eval_train = 0\nhealth_monitor = 1\n"
                                      "seed = 3\n", **small)
    b = _batch(seq)

    def run(force):
        before = telemetry.paths()
        try:
            tr = _trainer(conf, force)
            tr.update(b)
            health = np.asarray(tr.last_health)
        finally:
            ops.set_use_pallas(None)
        return health, tr.health_gauge_names, {
            k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0)}
    health, names, paths = run(True)
    assert {k: n for k, n in paths.items()
            if not k.startswith("flash.tiles.")} == dict({
        "attn.flash": n, "attn.dsa": n, "attn.select.fused": n,
        "attn.index_bwd.fused": n, "loss.index": n, "moe.sparse": n,
        "moe.bounded": n}, **prep)
    assert "flash.tiles.full" not in paths
    said = dict(zip(names, health[4:]))
    for i in range(n):
        assert said["dsa.selected/b%d_att" % i] == dsa.kept_scores(seq, 40)
        assert 0.0 < said["dsa.index_loss/b%d_att" % i] < 5.0
    assert np.isfinite(health[0]) and 2.0 < health[0] < 12.0
    dense, _, paths = run(False)
    assert paths.get("attn.dense") == n and "attn.flash" not in paths
    assert paths.get("attn.dsa") == n and paths.get("loss.index") == n
    assert paths.get("attn.select.xla") == n
    assert "attn.select.fused" not in paths
    assert paths.get("attn.index_bwd.xla") == n
    assert "attn.index_bwd.fused" not in paths
    assert health[0] == pytest.approx(dense[0], rel=1e-5)


def test_the_loss_term_survives_remat_and_stays_out_of_predict():
    """A layer that is no loss layer adds a term to the step's loss: under
    ``remat = 1`` on the attention layers (the layer's apply inside
    ``jax.checkpoint``) the step's loss, the layers' readings and the
    first gradient are those of ``remat = 0``; ``predict`` (a forward
    pass that trains nothing) runs the selection and adds nothing."""
    seq = 128
    extra = "eval_train = 0\nhealth_monitor = 1\nseed = 3\n"
    b = _batch(seq, 2)
    read = {}
    for remat in ("moe", "moe attention"):
        conf = models.keye_dsa_conf(seq=seq, batch_size=2, dev="cpu",
                                    extra_cfg=extra, remat=remat, **SMALL)
        try:
            tr = _trainer(conf, False)
            tr.update(b)
        finally:
            ops.set_use_pallas(None)
        idx = tr.net.cfg.get_layer_index("b0_att")
        read[remat] = (np.asarray(tr.last_health),
                       np.asarray(tr.opt_state[idx]["widx_q"]["m1"]),
                       np.asarray(tr.opt_state[idx]["wqkv"]["m1"]))
    for a, c in zip(read["moe"], read["moe attention"]):
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-9)
    assert np.abs(read["moe"][1]).max() > 0       # the indexer learns
    # the loss is the cross-entropy plus the layers' terms
    names = tr.health_gauge_names
    kl = sum(v for n, v in zip(names, read["moe"][0][4:])
             if n.startswith("dsa.index_loss/"))
    assert 0.05 < kl < read["moe"][0][0]
    out = tr.predict(b)
    assert np.asarray(out).shape[0] == 2


def test_the_builder_writes_the_published_model():
    layers, glob = netconf.parse(models.keye_dsa_conf())
    kinds = [lay.type for lay in layers]
    assert kinds.count("attention") == kinds.count("moe") == 48
    att = next(lay for lay in layers if lay.type == "attention")
    assert (att.geti("nhead"), att.geti("nkvhead"), att.geti("head_dim"),
            att.geti("qk_norm"), att.geti("rope"), att.geti("causal")) == (
        32, 4, 128, 1, 1, 1)
    assert att.params["attn_mask"] == "dsa"
    assert (att.geti("index_heads"), att.geti("index_dim"),
            att.geti("index_topk")) == (16, 64, 2048)
    assert att.getf("rope_base") == 1e7
    moe = next(lay for lay in layers if lay.type == "moe")
    assert (moe.geti("nexpert"), moe.geti("top_k"), moe.geti("nhidden"),
            moe.geti("nexpert_held")) == (128, 8, 768, 128)
    assert moe.params["expert_act"] == "swiglu" and len(moe.ins) == 1
    n = sum(int(np.prod(s)) for tags in
            dsa_inputs.weight_shapes(layers).values() for s in tags.values())
    assert 30.0e9 < n < 31.0e9            # "30B"
    per_layer = sum(int(np.prod(s)) for t, s in dsa_inputs.weight_shapes(
        layers)["b0_att"].items() if t.startswith(("widx", "idx")))
    assert per_layer == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128
    assert glob["updater"] == "adamw"
    for tag in ("gain", "qnorm", "knorm", "idx_gain", "idx_bias"):
        assert glob[tag + ":wd"] == "0.0"
    assert glob["input_shape"] == "1,1,8192"
    assert glob["label_vec[0,8192)"] == "label"
