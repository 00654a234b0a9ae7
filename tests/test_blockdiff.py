"""Training by diffusion over blocks, the program's parts on the CPU at a
small size: the third mask of the flash kernels (``ops/flash_attn.py``, in
the interpreter), ``AttentionLayer`` with the heads' norms and the mask,
``MoELayer`` with SwiGLU experts, the weighted per-position loss, the norm
that reads the first copy alone, the batch ``io.blockdiff`` makes, and the
whole block through ``Trainer.update`` on the forced flash path. The plain
side is the benchmark's reference (``benchmark/references/sdar_moe.py``);
the whole model against it through the cell's own ``run_cell`` is
``tests/benchmark/test_sdar_moe.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bd_inputs, netconf  # noqa: E402
from benchmark.references import sdar_moe  # noqa: E402
from cxxnet_tpu import models, ops  # noqa: E402
from cxxnet_tpu.io.blockdiff import noise_batch  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.layer.base import ApplyContext, LabelInfo  # noqa: E402
from cxxnet_tpu.layer.layers import (AttentionLayer, MoELayer,  # noqa: E402
                                     RMSNormLayer, SoftmaxLayer)
from cxxnet_tpu.ops import flash_attn as fa  # noqa: E402
from cxxnet_tpu.ops.flash_attn import Tiles, flash_attention  # noqa: E402
from cxxnet_tpu.parallel.ring import (attention_reference,  # noqa: E402
                                      block_diffusion_keep)
from cxxnet_tpu.utils import telemetry  # noqa: E402

D, L, NEXP, WIDTH = 64, 32, 16, 32


# ------------------------------------------------- the mask and its schedule
def test_the_mask_is_the_papers_three_parts():
    keep = np.asarray(block_diffusion_keep(16, 2))          # L 8, B 2
    blk = np.arange(8) // 2
    np.testing.assert_array_equal(keep[:8, :8], blk[:, None] == blk[None])
    np.testing.assert_array_equal(keep[:8, 8:], blk[None] < blk[:, None])
    assert not keep[8:, :8].any()
    np.testing.assert_array_equal(keep[8:, 8:], blk[None] <= blk[:, None])
    assert keep.diagonal().all()              # every row keeps itself
    assert int(keep.sum()) == sdar_moe.kept_scores(8, 2) == 8 * 8 + 8 * 2
    np.testing.assert_array_equal(
        keep, np.asarray(sdar_moe.keep(jnp.arange(16)[:, None],
                                       jnp.arange(16)[None, :], 8, 2)))


def _kept(g):
    keep = np.asarray(block_diffusion_keep(g.kv_len, g.block_len))
    return keep.reshape(g.n_q, g.bq, g.n_k, g.bk).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("half,bq,bk,block", [
    (384, 128, 128, 4), (384, 128, 128, 12), (384, 128, 128, 1),
    (512, 128, 256, 4), (512, 256, 128, 24), (512, 128, 128, 160),
    (256, 128, 128, 256), (1024, 256, 512, 4)])
def test_the_walks_visit_the_tiles_with_a_kept_score_and_no_other(
        half, bq, bk, block):
    """The grid's own index arithmetic, in numpy: every (q tile, step) of
    the forward / dQ grid and every (kv tile, step) of the dK/dV grid names
    a tile; a live step's tile holds a kept score, a step past the walk's
    end holds the block it had, and together the live steps are exactly the
    tiles a brute-force count of the mask finds."""
    g = fa._geom(Tiles(bq, bk, bk), 2 * half, False, 0, block)
    keep = _kept(g)
    any_kept, all_kept = keep.any((2, 3)), keep.all((2, 3))
    want = {(i, j) for i, j in zip(*np.nonzero(any_kept))}
    fwd, longest = [], 0
    for i in range(g.n_q):
        held = None
        for s in range(g.kv_steps()):
            tile, last = (int(x) for x in g.kv_tile(i, s, np))
            block_at = int(g.kv_block(i, s, np))
            if tile <= last:
                fwd.append((i, tile))
                assert block_at == tile
                longest = max(longest, s + 1)
            else:
                assert block_at == held     # nothing moves past the end
            held = block_at
    assert longest == g.kv_steps()
    dkv, longest = [], 0
    for j in range(g.n_k):
        held = None
        for s in range(g.q_steps()):
            tile, last = (int(x) for x in g.q_tile(j, s, g.q_steps(), xp=np))
            block_at = int(g.q_block(j, s, np))
            if tile <= last:
                dkv.append((tile, j))
                assert block_at == tile
                longest = max(longest, s + 1)
            else:
                assert block_at == held
            held = block_at
    assert longest == g.q_steps()
    assert len(fwd) == len(set(fwd)) and set(fwd) == want
    assert len(dkv) == len(set(dkv)) and set(dkv) == want
    # the bodies' own predicates, tile by tile, and the counts telemetry has
    q0, k0 = np.meshgrid(np.arange(g.n_q) * bq, np.arange(g.n_k) * bk,
                         indexing="ij")
    needed, full = g.kind(q0, bq, k0, bk, np)
    np.testing.assert_array_equal(needed, any_kept)
    np.testing.assert_array_equal(needed & full, all_kept)
    n_full, n_edge, n_skip = fa.tile_counts(g)
    assert (n_full, n_edge) == (all_kept.sum(),
                                (any_kept & ~all_kept).sum())
    assert n_full + n_edge + n_skip == g.n_q * g.n_k and g.masks


def test_the_cells_schedule():
    """16,384 rows, block length 4, heads of 128, bf16: tiles of 1,024 that
    do not straddle the copies; a head's forward grid holds 112 whole score
    tiles of (1,024, 512) and 48 on an edge (80 tiles of 1,024 x 1,024 for
    64.03 of kept scores), and never visits 352."""
    fwd, dq, dkv = fa._tiling(16384, 128, 2, 0, 4)
    assert fwd == Tiles(1024, 1024, 512)
    assert dq == dkv == Tiles(1024, 1024, 1024)
    for kernel, t in zip(("fwd", "dq", "dkv"), (fwd, dq, dkv)):
        assert fa._vmem_bytes(kernel, t, 128, 2) <= fa.VMEM_BUDGET
    g = fa._geom(fwd, 16384, False, 0, 4)
    assert (g.kv_steps(), fa._geom(dkv, 16384, False, 0, 4).q_steps()) \
        == (9, 16)      # a clean key tile: 8 clean q tiles and 8 noised
    sched = fa.schedule(jnp.zeros((1, 32, 16384, 128), jnp.bfloat16),
                        jnp.zeros((1, 4, 16384, 128), jnp.bfloat16),
                        False, 0, 4)
    assert sched == {"block_q": 1024, "block_k": 512, "full": 112,
                     "edge": 48, "skipped": 352}
    # the other masks' tiles are what they were
    assert fa._tiling(8192, 128, 2, 0) == (
        Tiles(1024, 1024, 512), Tiles(1024, 1024, 1024),
        Tiles(1024, 1024, 1024))


@pytest.mark.parametrize("rows,ok", [(768, True), (512, True), (640, False),
                                     (300, False)])
def test_two_copies_of_whole_lane_tiles_take_the_kernels(rows, ok):
    assert fa.supports(rows, 16, 4) is ok
    assert fa.supports(rows, 16)
    if ok:
        for kernel, t in zip(("fwd", "dq", "dkv"),
                             fa._tiling(rows, 16, 4, 0, 4)):
            assert (rows // 2) % t.bq == 0 and (rows // 2) % t.bk == 0
            assert (t.bq if kernel == "dkv" else t.bk) % t.sub == 0


def test_a_tile_across_the_copies_and_a_second_mask_are_refused():
    with pytest.raises(AssertionError, match="divide each copy"):
        fa._geom(Tiles(256, 128, 128), 768, False, 0, 4)
    with pytest.raises(AssertionError, match="neither causal nor windowed"):
        fa._geom(Tiles(128, 128, 128), 768, True, 0, 4)


def _vs_dense(rs, rows, h, nkv, d, block, tiles, tol=1e-4):
    q = jnp.asarray(rs.randn(1, h, rows, d), jnp.float32)
    k = jnp.asarray(rs.randn(1, nkv, rows, d), jnp.float32)
    v = jnp.asarray(rs.randn(1, nkv, rows, d), jnp.float32)
    w = jnp.asarray(rs.randn(1, h, rows, d), jnp.float32)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w)
    got = run(lambda q, k, v: flash_attention(
        q, k, v, False, None, True, 0, tiles, block))
    want = run(lambda q, k, v: attention_reference(q, k, v,
                                                   block_len=block))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("block", [4, 12])
def test_the_kernels_agree_with_the_dense_masked_softmax(block):
    """Forward, dQ, dK, dV in the interpreter, 3 x 3 tiles a quadrant, a
    group of two: a block length that divides the tile and one that does
    not (a block then lies across two tiles, both on an edge)."""
    _vs_dense(np.random.RandomState(1), 768, 4, 2, 32, block,
              (Tiles(128, 128, 128),) * 3)


def test_rectangular_tiles_and_sub_columns():
    _vs_dense(np.random.RandomState(2), 1024, 2, 1, 16, 4,
              (Tiles(128, 256, 128), Tiles(128, 256, 128),
               Tiles(256, 128, 128)))


# --------------------------------------------------------------- the layers
def _attention(rows=2 * L, **keys):
    lay = AttentionLayer()
    for k, v in dict({"nhead": 4, "nkvhead": 2, "head_dim": 16,
                      "attn_mask": "blockdiff", "block_len": 4,
                      "qk_norm": 1, "rope": 1, "rope_base": 1000000},
                     **keys).items():
        lay.set_param(k, str(v))
    lay.infer_shape([(2, D, 1, rows)])
    return lay


def _attention_weights(lay, seed=4):
    rs = np.random.RandomState(seed)
    w = {k: jnp.asarray(v) * 10 for k, v in lay.init_params(rs).items()}
    for key in ("qnorm", "knorm"):        # gains that are not all one
        if key in w:
            w[key] = jnp.asarray(1.0 + 0.3 * rs.randn(16), jnp.float32)
    return w


def _reference_attention(w, x, mask="blockdiff", wrap=True, **keys):
    lay = netconf.Layer("attention", "a", ["x"], ["y"], {
        k: str(v) for k, v in dict({
            "nhead": 4, "nkvhead": 2, "head_dim": 16, "block_len": 4,
            "attn_mask": "blockdiff", "qk_norm": 1, "rope": 1,
            "rope_base": 1000000}, **keys).items()})
    ww = dict(w, wmat=w["wqkv"])
    rows = x.shape[-1]
    return np.stack([np.asarray(sdar_moe._attention(
        lay, "highest", ww, x[i].reshape(D, rows).T, mask, wrap)
    ).T.reshape(D, 1, rows) for i in range(x.shape[0])])


def _apply(lay, w, x, **ctx):
    y, = lay.apply(w, [jnp.asarray(x)], ApplyContext(train=True, **ctx))
    return np.asarray(y)


@pytest.mark.parametrize("keys", [{}, {"block_len": 8}, {"qk_norm": 0},
                                  {"rope": 0}],
                         ids=["b4", "b8", "no_qk_norm", "nope"])
def test_attention_with_the_heads_norms_and_the_mask_agrees(keys):
    x = np.random.RandomState(5).randn(2, D, 1, 2 * L).astype(np.float32)
    lay = _attention(**keys)
    w = _attention_weights(lay)
    assert set(w) == {"wqkv", "wo"} | (
        {"qnorm", "knorm"} if lay.qk_norm else set())
    got = _apply(lay, w, x)
    np.testing.assert_allclose(got, _reference_attention(w, x, **keys),
                               rtol=1e-4, atol=1e-5)
    # and each of the mechanism's parts is in it: another mask, positions
    # that do not wrap, read otherwise
    assert np.abs(got - _reference_attention(w, x, mask="causal",
                                             **keys)).max() > 1e-3
    if lay.rope:
        assert np.abs(got - _reference_attention(w, x, wrap=False,
                                                 **keys)).max() > 1e-3


def test_the_heads_norms_are_leaves_with_tags_of_their_own():
    lay = _attention()
    assert lay.visit_order() == [("wmat", "wqkv"), ("wo", "wo"),
                                 ("qnorm", "qnorm"), ("knorm", "knorm")]
    w = lay.init_params(np.random.RandomState(0))
    assert w["qnorm"].shape == w["knorm"].shape == (16,)
    assert (w["qnorm"] == 1).all() and (w["knorm"] == 1).all()
    assert _attention(qk_norm=0).visit_order() == [("wmat", "wqkv"),
                                                   ("wo", "wo")]


@pytest.mark.parametrize("keys,said", [
    ({"causal": 1}, "causal and attn_window must be 0"),
    ({"block_len": 0}, "needs block_len"),
    ({"block_len": 5}, "two copies of whole blocks"),
    ({"attn_mask": "causal", "causal": 1}, "block_len is the block of"),
    ({"attn_mask": "sliding"}, "attn_mask must be")])
def test_keys_that_do_not_go_with_the_mask_are_refused(keys, said):
    with pytest.raises(ValueError, match=said):
        _attention(**keys)


def test_decoding_under_the_mask_is_refused_not_run_wrong():
    lay = _attention()
    x = np.zeros((2, D, 1, 2 * L), np.float32)
    with pytest.raises(ValueError, match="decoding a block of tokens"):
        _apply(lay, _attention_weights(lay), x, decode_pos=0)


def test_the_flash_path_runs_the_mask_and_counts_it_once_a_layer():
    """Forced through the interpreter at 512 rows: ``attn.flash`` and
    ``attn.blockdiff`` once each, the schedule's tiles by kind and the
    block length as a gauge; the dense path counts the mask too, and both
    give the same rows."""
    rows = 512
    lay = _attention(rows)
    w = _attention_weights(lay)
    x = np.random.RandomState(6).randn(1, D, 1, rows).astype(np.float32)

    def delta(force):
        before = telemetry.paths()
        telemetry.enable()
        ops.set_use_pallas(force)
        try:
            y = _apply(lay, w, x)
            gauges = telemetry.summary()["gauges"]
        finally:
            ops.set_use_pallas(None)
            telemetry.disable()
            telemetry.reset()
        return y, gauges, {
            k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0)}

    y_flash, gauges, paths = delta(True)
    sched = ops.flash_schedule(jnp.zeros((1, 4, rows, 16)),
                               jnp.zeros((1, 2, rows, 16)), False, 0, 4)
    kinds = {k: sched[k] for k in ("full", "edge", "skipped")}
    assert gauges["attn.block_len"] == 4
    assert gauges["flash.block_q"] == sched["block_q"]
    assert kinds["skipped"] >= sum(kinds.values()) // 4   # the dead quadrant
    # heads of 16: the plain lines between the qkv dot and the core
    assert paths == dict({"attn.flash": 1, "attn.blockdiff": 1,
                          "attn.prep.xla": 1}, **{
        "flash.tiles." + k: n for k, n in kinds.items() if n})
    y_dense, gauges, paths = delta(False)
    assert paths == {"attn.dense": 1, "attn.blockdiff": 1,
                     "attn.prep.xla": 1}
    np.testing.assert_allclose(y_flash, y_dense, rtol=2e-4, atol=2e-5)


def _moe_layer(held=0, offset=0, seq=L, act="swiglu"):
    lay = MoELayer()
    for k, v in {"nexpert": NEXP, "top_k": 4, "nhidden": WIDTH,
                 "nexpert_held": held or NEXP, "expert_offset": offset,
                 "expert_act": act}.items():
        lay.set_param(k, str(v))
    assert lay.infer_shape([(2, D, 1, seq)]) == [(2, D, 1, seq)]
    return lay


def _moe_weights(seed=2):
    rs = np.random.RandomState(seed)
    return {"gate": jnp.asarray(rs.randn(NEXP, D), jnp.float32),
            "experts": jnp.asarray(rs.randn(NEXP, D, WIDTH) * 0.2,
                                   jnp.float32),
            "up": jnp.asarray(rs.randn(NEXP, D, WIDTH) * 0.2, jnp.float32),
            "down": jnp.asarray(rs.randn(NEXP, WIDTH, D) * 0.2,
                                jnp.float32)}


def _share(w, lo, n):
    return dict(w, **{k: w[k][lo:lo + n] for k in ("experts", "up",
                                                   "down")})


def _reference_moe(w, u, held=0, offset=0):
    lay = netconf.Layer("moe", "m", ["u"], ["y"], {
        "nexpert": str(NEXP), "top_k": "4", "nhidden": str(WIDTH),
        "nexpert_held": str(held or NEXP), "expert_offset": str(offset),
        "expert_act": "swiglu"})
    ww = _share({"gate": w["gate"], "experts": w["experts"],
                 "up": w["up"], "down": w["down"]}, offset, held or NEXP)
    ww["wmat"] = ww.pop("experts")
    seq = u.shape[-1]
    out, pairs = zip(*[sdar_moe.moe(lay, "highest", ww,
                                    u[i].reshape(D, seq).T)
                       for i in range(u.shape[0])])
    return (np.stack([np.asarray(r).T.reshape(D, 1, seq) for r in out]),
            int(sum(pairs)))


def _apply_moe(lay, w, u):
    ctx = ApplyContext(train=True)
    ctx.conn_index = 0
    y, = lay.apply(w, [jnp.asarray(u)], ctx)
    return np.asarray(y), np.asarray(ctx.layer_stats[0])


def test_the_eight_shares_of_swiglu_experts_add_up_to_the_uncut_layer():
    """What ties one chip's share to the model: 16 experts in 8 shares of
    2 (one input: the router reads what the experts read), each against
    the reference's share, and their sum against the uncut layer's."""
    u = np.random.RandomState(1).randn(2, D, 1, L).astype(np.float32)
    w = _moe_weights()
    assert [k for _, k in _moe_layer().visit_order()] == [
        "experts", "gate", "up", "down"]
    whole, stats = _apply_moe(_moe_layer(), w, u)
    want, pairs = _reference_moe(w, u)
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-5)
    assert pairs == stats[0] == 2 * L * 4
    parts, held = [], 0
    for lo in range(0, NEXP, 2):
        y, st = _apply_moe(_moe_layer(held=2, offset=lo), _share(w, lo, 2),
                           u)
        want, pairs = _reference_moe(w, u, held=2, offset=lo)
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
        assert pairs == st[0]
        parts.append(y)
        held += st[0]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert held == 2 * L * 4                  # every pair is held once
    # silu is in it: the same weights as reglu experts read otherwise
    other, _ = _apply_moe(_moe_layer(act="reglu"), w, u)
    assert np.abs(other - whole).max() > 1e-3


def test_an_unknown_expert_act_is_refused():
    with pytest.raises(ValueError, match="relu, reglu or swiglu"):
        MoELayer().set_param("expert_act", "geglu")


@pytest.mark.parametrize("channels_last", [False, True])
def test_the_last_norm_reads_the_first_copy_alone(channels_last):
    lay = RMSNormLayer()
    lay.set_param("seq_rows", str(L))
    assert lay.infer_shape([(2, D, 1, 2 * L)]) == [(2, D, 1, L)]
    rs = np.random.RandomState(3)
    x = rs.randn(2, D, 1, 2 * L).astype(np.float32)
    gain = jnp.asarray(1.0 + 0.2 * rs.randn(D), jnp.float32)
    xin = x.transpose(0, 2, 3, 1) if channels_last else x
    y = _apply(lay, {"gain": gain}, xin, channels_last=channels_last)
    if channels_last:
        y = y.transpose(0, 3, 1, 2)
    want = np.stack([np.asarray(sdar_moe._rmsnorm(
        jnp.asarray(x[i, :, 0, :L].T), gain, 1e-6)).T for i in range(2)])
    np.testing.assert_allclose(y[:, :, 0], want, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="seq_rows"):
        lay.infer_shape([(2, D, 1, L - 1)])


def test_the_loss_weighs_each_position_by_its_field():
    """``weight_target``: (1 / L) sum_i w_i CE_i, a position of weight 0
    gets no gradient; without the key the loss is what it was."""
    vocab = 24
    rs = np.random.RandomState(8)
    logits = jnp.asarray(rs.randn(2, vocab, 1, L), jnp.float32)
    label = rs.randint(0, vocab, (2, L)).astype(np.float32)
    weight = np.where(rs.rand(2, L) < 0.6, 1.0 / rs.uniform(
        0.45, 0.95, (2, L)), 0.0).astype(np.float32)

    def loss(lay, x):
        ctx = ApplyContext(train=True, labels=LabelInfo(
            {"label": jnp.asarray(label), "loss_weight": jnp.asarray(
                weight)}))
        lay.apply({}, [x], ctx)
        return ctx.losses[0]
    lay = SoftmaxLayer()
    for k, v in (("seq", "1"), ("batch_size", "2"),
                 ("weight_target", "loss_weight")):
        lay.set_param(k, v)
    before = telemetry.paths().get("loss.weighted", 0)
    got, grad = jax.value_and_grad(lambda x: loss(lay, x))(logits)
    assert telemetry.paths()["loss.weighted"] == before + 1
    logp = np.asarray(jax.nn.log_softmax(logits[:, :, 0], axis=1))
    ce = -np.take_along_axis(logp, label[:, None].astype(int), 1)[:, 0]
    assert float(got) == pytest.approx((weight * ce).sum() / L / 2, rel=1e-5)
    grad = np.asarray(grad)[:, :, 0]
    assert (grad[:, :][np.broadcast_to(weight[:, None] == 0,
                                       grad.shape)] == 0).all()
    assert np.abs(grad).max() > 0
    plain = SoftmaxLayer()
    for k, v in (("seq", "1"), ("batch_size", "2")):
        plain.set_param(k, v)
    assert float(loss(plain, logits)) == pytest.approx(ce.sum() / L / 2,
                                                       rel=1e-5)
    flat = SoftmaxLayer()
    flat.set_param("weight_target", "loss_weight")
    with pytest.raises(ValueError, match="needs seq = 1"):
        flat.apply({}, [logits], ApplyContext(train=True))


# ---------------------------------------------------- the batch and the step
def test_the_batch_is_two_copies_and_two_fields():
    rows, seq, mask_id = 3, 512, 999
    tokens = jax.random.randint(jax.random.PRNGKey(0), (rows, seq), 0, 999)
    key = jax.random.PRNGKey(7)
    data, label = noise_batch(tokens, key, 4, 0.45, 0.95, mask_id)
    assert data.shape == (rows, 1, 1, 2 * seq) and label.shape == (rows,
                                                                   2 * seq)
    ids = np.asarray(data).reshape(rows, 2 * seq)
    xt, x0 = ids[:, :seq], ids[:, seq:]
    x0_field, weight = np.asarray(label)[:, :seq], np.asarray(label)[:, seq:]
    np.testing.assert_array_equal(x0, np.asarray(tokens))
    np.testing.assert_array_equal(x0_field, x0)
    masked = xt == mask_id
    np.testing.assert_array_equal(xt[~masked], x0[~masked])
    np.testing.assert_array_equal(weight > 0, masked)
    # t in [0.45, 0.95] a block: the share masked, the weight 1 / t, one t
    # for the positions of a block
    assert 0.64 < masked.mean() < 0.76
    assert weight[masked].min() >= 1 / 0.95 - 1e-6
    assert weight[masked].max() <= 1 / 0.45 + 1e-6
    by_block = weight.reshape(rows, seq // 4, 4)
    top = by_block.max(-1, keepdims=True)
    assert np.all((by_block == 0) | (by_block == top))
    assert len(np.unique(top)) > 100
    # the benchmark's own noising, which feeds the reference, is the same
    xt_b, weight_b = bd_inputs.noise(tokens, key, 4, 0.45, 0.95, mask_id)
    np.testing.assert_array_equal(np.asarray(xt_b), xt)
    np.testing.assert_array_equal(np.asarray(weight_b), weight)
    with pytest.raises(ValueError, match="whole number of blocks"):
        noise_batch(tokens[:, :510], key, 4, 0.45, 0.95, mask_id)


SMALL = dict(vocab=96, dim=D, nhead=4, nkvhead=2, head_dim=16, nlayer=2,
             n_expert=NEXP, top_k=4, expert_width=WIDTH, n_held=4,
             expert_offset=2)


# heads of 16 keep the plain lines between the qkv dot and the core; heads
# of 128 (the published size, four layers as the benchmark's cell) take
# the fused pass in every layer (PR 37)
@pytest.mark.parametrize("over, prep", [
    ({}, {"attn.prep.xla": 2}),
    (dict(nhead=2, nkvhead=1, head_dim=128, nlayer=4),
     {"attn.prep.fused": 4}),
], ids=["heads-of-16", "heads-of-128"])
def test_the_step_on_the_forced_flash_path_counts_its_paths(over, prep):
    """``Trainer.update`` from ``models.sdar_moe_conf``'s text with the
    kernels forced on (the interpreter), 512 rows: every attention layer
    takes the flash kernels under the new mask, every ``moe`` layer the
    sparse lowering on a bounded sorted side, the loss its weights; and the
    step gives the dense path's loss."""
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    seq = 256
    small = dict(SMALL, **over)
    n = small["nlayer"]
    conf = models.sdar_moe_conf(
        seq=seq, dev="cpu", extra_cfg="eval_train = 0\nhealth_monitor = 1\n"
                                      "seed = 3\n", **small)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0, 95)
    b = DataBatch()
    b.data, b.label = noise_batch(tokens, jax.random.PRNGKey(2), 4, 0.45,
                                  0.95, 95)
    b.batch_size = 1

    def run(force):
        before = telemetry.paths()
        ops.set_use_pallas(force)
        try:
            tr = Trainer()
            for k, v in parse_config_string(conf):
                tr.set_param(k, v)
            tr.init_model()
            tr.update(b)
            loss = float(tr.last_health[0])
        finally:
            ops.set_use_pallas(None)
        return loss, {k: n - before.get(k, 0)
                      for k, n in telemetry.paths().items()
                      if n != before.get(k, 0)}
    loss, paths = run(True)
    assert {k: n for k, n in paths.items()
            if not k.startswith("flash.tiles.")} == dict({
        "attn.flash": n, "attn.blockdiff": n, "moe.sparse": n,
        "moe.bounded": n, "loss.weighted": 1}, **prep)
    assert np.isfinite(loss) and 2.0 < loss < 12.0
    dense, paths = run(False)
    assert paths.get("attn.dense") == n and "attn.flash" not in paths
    assert paths.get("attn.prep.xla") == n and "attn.prep.fused" not in paths
    assert loss == pytest.approx(dense, rel=1e-5)


def test_the_builder_writes_the_published_model():
    layers, glob = netconf.parse(models.sdar_moe_conf())
    kinds = [lay.type for lay in layers]
    assert kinds.count("attention") == kinds.count("moe") == 48
    att = next(lay for lay in layers if lay.type == "attention")
    assert (att.geti("nhead"), att.geti("nkvhead"), att.geti("head_dim"),
            att.geti("qk_norm"), att.geti("rope"), att.geti("causal"),
            att.geti("block_len")) == (32, 4, 128, 1, 1, 0, 4)
    assert att.params["attn_mask"] == "blockdiff"
    assert att.getf("rope_base") == 1e6
    moe = next(lay for lay in layers if lay.type == "moe")
    assert (moe.geti("nexpert"), moe.geti("top_k"), moe.geti("nhidden"),
            moe.geti("nexpert_held")) == (128, 8, 768, 128)
    assert moe.params["expert_act"] == "swiglu"
    assert len(moe.ins) == 1          # the router reads the normed stream
    last = next(lay for lay in layers if lay.name == "norm_f")
    assert last.geti("seq_rows") == 8192
    loss = layers[-1]
    assert loss.type == "softmax" and \
        loss.params["weight_target"] == "loss_weight"
    n = sum(int(np.prod(s)) for tags in
            bd_inputs.weight_shapes(layers).values() for s in tags.values())
    assert 30.0e9 < n < 31.0e9            # "30B"
    assert glob["updater"] == "adamw" and glob["qnorm:wd"] == "0.0"
    assert glob["input_shape"] == "1,1,16384"
    assert glob["label_vec[8192,16384)"] == "loss_weight"
