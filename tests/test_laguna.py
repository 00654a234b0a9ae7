"""Laguna-XS.2's language model, the program's parts on the CPU at a small
size: the heads' output gate, rotation of part of a head with YaRN's
tables (``attention``), the shared expert and the routed scale (``moe``),
the flash kernels at a window with query groups of 3 and 4, and the whole
model through the trainer from ``models.laguna_moe_conf``'s text. The
plain side is the benchmark's reference (``benchmark/references/
laguna_moe.py``); the model against it through the cell's own ``run_cell``
is ``tests/benchmark/test_laguna_moe.py``."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import laguna_inputs, lm_inputs, netconf  # noqa: E402
from benchmark.inputs import seed_key  # noqa: E402
from benchmark.references import laguna_moe  # noqa: E402
from cxxnet_tpu import models, ops  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.layer.base import ApplyContext  # noqa: E402
from cxxnet_tpu.layer.layers import (AttentionLayer, MoELayer,  # noqa: E402
                                     yarn_inv_freq)
from cxxnet_tpu.nnet.trainer import Trainer  # noqa: E402
from cxxnet_tpu.utils import telemetry  # noqa: E402
from cxxnet_tpu.utils.config import parse_config_string  # noqa: E402

D, L = 64, 64
# a small model: d 64, 6 heads on full layers and 8 on window
# layers over 2 key-value heads of 16, a window of 8, 16 experts of which
# 4 are held; YaRN over half a head, its ramp over the middle pairs
SMALL_ROPE = dict(theta=1e4, rope_dim=8, factor=4.0, original_len=64,
                  beta_fast=4.0, beta_slow=1.0, attention_factor=0.0)
SMALL = dict(vocab=96, dim=D, full_heads=6, window_heads=8, nkvhead=2,
             head_dim=16, layer_types=models.LAGUNA_LAYER_TYPES[:5],
             window=8, full_rope=SMALL_ROPE, n_dense=1, dense_width=96,
             n_expert=16, top_k=4, expert_width=32, shared_width=24,
             n_held=4, expert_offset=2)
CFG = {"weights_base_seed": 11}
# matrices at normal(0, 0.1) and not the model's 0.02: at a width of 64 the
# smaller spread leaves every attention layer all but uniform, and what the
# rotations and the window decide would hardly show
SIGMA = ("init_sigma = 0.02\n", "init_sigma = 0.1\n")


def _net():
    return models.laguna_moe_netconfig(**SMALL).replace(*SIGMA)


def _conf(seq, batch_size, extra_cfg):
    return models.laguna_moe_conf(seq=seq, batch_size=batch_size,
                                  dev="cpu", extra_cfg=extra_cfg,
                                  **SMALL).replace(*SIGMA)


def _layer(cls, shape, **keys):
    lay = cls()
    for k, v in keys.items():
        lay.set_param(k, str(v))
    lay.infer_shape([shape])
    return lay


def _ref_layer(kind, **keys):
    return netconf.Layer(kind, "x", ["a"], ["b"],
                         {k: str(v) for k, v in keys.items()})


# --------------------------------------------------- the rotation's tables
def test_yarn_tables_are_the_formula_written_out():
    """The published full layers' frequencies (theta 5e5 over 64 features,
    factor 64, 4096 positions, beta 64 / 1), program and reference, against
    the equations by hand: pair i turns 5e5^(-i/32) radians a position;
    the correction range is pairs 5 (64 turns: 64 ln(4096 / 128 pi) /
    2 ln 5e5 = 5.66, rounded down) to 16 (one turn: 15.8, rounded up); the
    pairs below it keep their frequency, those from it on take a 64th,
    and pair i between takes (i - 5) / 11 of the 64th."""
    plain = np.array([5e5 ** (-i / 32.0) for i in range(32)])
    lo = math.floor(64 * math.log(4096 / (2 * math.pi * 64))
                    / (2 * math.log(5e5)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi))
                   / (2 * math.log(5e5)))
    assert (lo, hi) == (5, 16)
    want = [p if i <= 5 else p / 64 if i >= 16 else
            p * (1 - (i - 5) / 11) + p / 64 * (i - 5) / 11
            for i, p in enumerate(plain)]
    prog = yarn_inv_freq(5e5, 64, 64.0, 4096, 64.0, 1.0)
    ref, scale = laguna_moe.yarn_tables(5e5, 64, 64.0, 4096, 64.0, 1.0, 0.0)
    np.testing.assert_allclose(prog, want, rtol=1e-12)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert scale == pytest.approx(1.4158883083359672, rel=1e-15)
    # the layer's tables: its frequencies, cos and sin times the factor
    lay = _layer(AttentionLayer, (1, D, 1, 32), nhead=4, head_dim=128,
                 causal=1, rope=1, rope_base=5e5, rope_dim=64,
                 rope_type="yarn", yarn_factor=64, yarn_original_len=4096,
                 yarn_beta_fast=64, yarn_beta_slow=1,
                 yarn_attention_factor=1.4158883083359672)
    cos, sin = lay._rope_angles(32, 32)
    ang = np.arange(32)[:, None] * np.asarray(want, np.float32)[None]
    np.testing.assert_allclose(cos, 1.4158883083359672 * np.cos(ang),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin, 1.4158883083359672 * np.sin(ang),
                               rtol=1e-5, atol=1e-6)


def test_partial_rotation_turns_the_first_features_and_passes_the_rest():
    """rope_dim = 8 of 16: feature i < 4 pairs with i + 4 and turns by the
    position's angle; features 8-15 come out as they went in; program and
    reference agree. At position 0 nothing turns, so the tables' factor
    alone scales the first 8."""
    rs = np.random.RandomState(0)
    lay = _layer(AttentionLayer, (1, D, 1, L), nhead=4, head_dim=16,
                 causal=1, rope=1, rope_base=1e4, rope_dim=8)
    x = jnp.asarray(rs.randn(1, 4, L, 16), jnp.float32)
    got = np.asarray(lay._apply_rope(x, 0, 8))
    xs = np.asarray(x)
    np.testing.assert_array_equal(got[..., 8:], xs[..., 8:])
    t, i = 7, 2
    w = 1e4 ** (-i / 4.0)
    a, b = xs[0, :, t, i], xs[0, :, t, i + 4]
    np.testing.assert_allclose(got[0, :, t, i],
                               a * np.cos(t * w) - b * np.sin(t * w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[0, :, t, i + 4],
                               a * np.sin(t * w) + b * np.cos(t * w),
                               rtol=1e-5, atol=1e-6)
    ref = _ref_layer("attention", rope_dim=8, rope_base=1e4)
    rot, freq, scale = laguna_moe.rope_of(ref, 16)
    np.testing.assert_allclose(got[0], laguna_moe.rotate(x[0], rot, freq,
                                                         scale),
                               rtol=1e-6, atol=1e-6)
    yarn = _layer(AttentionLayer, (1, D, 1, L), nhead=4, head_dim=16,
                  causal=1, rope=1, rope_dim=8, rope_type="yarn",
                  yarn_factor=4, yarn_original_len=64, yarn_beta_fast=4)
    at0 = np.asarray(yarn._apply_rope(x, 0, 8))[:, :, 0]
    np.testing.assert_allclose(at0[..., :8], (0.1 * math.log(4) + 1)
                               * xs[:, :, 0, :8], rtol=1e-6)
    np.testing.assert_array_equal(at0[..., 8:], xs[:, :, 0, 8:])


def test_keys_that_do_not_go_together_are_refused():
    shape = (1, D, 1, L)
    base = dict(nhead=4, head_dim=16, causal=1)
    with pytest.raises(ValueError, match="rope_dim = 12 must be"):
        _layer(AttentionLayer, shape, rope=1, rope_dim=12, **dict(
            base, head_dim=8))
    with pytest.raises(ValueError, match="with rope = 1"):
        _layer(AttentionLayer, shape, rope_dim=8, **base)
    with pytest.raises(ValueError, match="mean nothing without rope_type"):
        _layer(AttentionLayer, shape, rope=1, yarn_factor=4, **base)
    with pytest.raises(ValueError, match="rope_type = yarn needs"):
        _layer(AttentionLayer, shape, rope=1, rope_type="yarn", **base)
    with pytest.raises(ValueError, match="rope_type must be"):
        _layer(AttentionLayer, shape, rope=1, rope_type="ntk", **base)
    moe = dict(nexpert=8, top_k=2, nhidden=16)
    with pytest.raises(ValueError, match="needs expert_act reglu or swiglu"):
        _layer(MoELayer, shape, nshared_hidden=8, **moe)
    with pytest.raises(ValueError, match="routed_scale must be positive"):
        _layer(MoELayer, shape, routed_scale=0, **moe)


# ------------------------------------------------------------- the gate
def test_the_gate_at_a_weight_of_nought_is_half_the_ungated_output():
    """sigmoid(0) = 1/2 on every head and position: the gated layer's
    output before wo is exactly half the ungated one's, so with the same
    wo its output is half too; one head's gate moves that head alone."""
    rs = np.random.RandomState(1)
    keys = dict(nhead=6, nkvhead=2, head_dim=16, causal=1)
    gated = _layer(AttentionLayer, (2, D, 1, L), head_gate=1, **keys)
    plain = _layer(AttentionLayer, (2, D, 1, L), **keys)
    w = {"wqkv": jnp.asarray(rs.randn(D, 160) * 0.2, jnp.float32),
         "wo": jnp.asarray(rs.randn(96, D) * 0.2, jnp.float32)}
    x = jnp.asarray(rs.randn(2, D, 1, L), jnp.float32)
    ctx = ApplyContext(train=False)
    want = np.asarray(plain.apply(w, [x], ctx)[0])
    zero = dict(w, wgate=jnp.zeros((D, 6), jnp.float32))
    got = np.asarray(gated.apply(zero, [x], ctx)[0])
    np.testing.assert_array_equal(got, want * np.float32(0.5))
    seq = x.reshape(2, D, L).transpose(0, 2, 1)
    out = jnp.asarray(rs.randn(2, 6, L, 16), jnp.float32)
    one = np.zeros((D, 6), np.float32)
    one[:, 4] = 1.0
    g = np.asarray(gated._gate(seq, jnp.asarray(one), out))
    np.testing.assert_array_equal(g[:, [0, 1, 2, 3, 5]],
                                  0.5 * np.asarray(out)[:, [0, 1, 2, 3, 5]])
    s = 1 / (1 + np.exp(-np.asarray(seq).sum(-1)))          # (2, L)
    np.testing.assert_allclose(g[:, 4], s[..., None] * np.asarray(out)[:, 4],
                               rtol=1e-5)
    assert [t for t, _ in gated.visit_order()] == ["wmat", "wo", "wgate"]


# ------------------------------------------ the shared expert, the scale
def _moe(held=16, offset=0, **keys):
    return _layer(MoELayer, (1, D, 1, L), **dict(
        {"nexpert": 16, "top_k": 4, "nhidden": 16, "expert_act": "swiglu",
         "nexpert_held": held, "expert_offset": offset, "score": "sigmoid",
         "routed_scale": 2.5, "nshared_hidden": 24}, **keys))


def _moe_weights(rs, e=16, f=16, fs=24):
    m = lambda *s: jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)  # noqa
    return {"gate": m(e, D) * 1.5, "experts": m(e, D, f), "up": m(e, D, f),
            "down": m(e, f, D), "shared_wmat": m(D, fs),
            "shared_up": m(D, fs), "shared_down": m(fs, D)}


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """A deployment of eight chips a layer at this size: each chip holds
    two experts (its ``expert_offset``), the router all sixteen outputs
    and the shared expert; the eight shares' routed parts summed, plus the
    shared expert once, are the uncut reference layer's result, and every
    pair is held somewhere."""
    rs = np.random.RandomState(4)
    w = _moe_weights(rs)
    x = jnp.asarray(rs.randn(1, D, 1, L), jnp.float32)
    rows = x.reshape(D, L).T
    shared = laguna_moe._swiglu_rows("highest", rows, w["shared_wmat"],
                                     w["shared_up"], w["shared_down"])
    parts = []
    with jax.default_matmul_precision("highest"):
        for chip in range(8):
            share = dict(w, **{k: w[k][2 * chip:2 * chip + 2]
                               for k in ("experts", "up", "down")})
            ctx = ApplyContext(train=True)
            ctx.conn_index = 0
            out, = _moe(held=2, offset=2 * chip).apply(share, [x], ctx)
            parts.append(out.reshape(D, L).T - shared)
        got = sum(parts) + shared
        ref = _ref_layer("moe", nexpert=16, top_k=4, score="sigmoid",
                         expert_act="swiglu", expert_offset=0,
                         routed_scale=2.5, nshared_hidden=24)
        whole, pairs = laguna_moe.moe(ref, "highest",
                                      dict(w, wmat=w["experts"]), rows)
    np.testing.assert_allclose(got, whole, rtol=1e-4, atol=1e-5)
    assert int(pairs) == 4 * L
    # the routed part scales with routed_scale and the shared one does not
    one, _ = laguna_moe.moe(ref, "highest", dict(w, wmat=w["experts"]),
                            rows, scale=1.0)
    np.testing.assert_allclose(whole - shared, 2.5 * (one - shared),
                               rtol=1e-4, atol=1e-5)


def test_the_routing_weights_carry_the_scale():
    """The top 4 sigmoid scores over their sum (+ 1e-6), times 2.5, in
    the program's ``_top`` and the reference's ``route``."""
    lay = _moe()
    logits = jnp.asarray(np.random.RandomState(2).randn(3, 16), jnp.float32)
    idx, w = lay._top(logits)
    s = np.asarray(jax.nn.sigmoid(logits))
    top = np.argsort(-s, -1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(top, -1))
    sel = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(w, 2.5 * sel / (sel.sum(-1, keepdims=True)
                                               + 1e-6), rtol=1e-6)
    probs = laguna_moe.route(_ref_layer("moe", nexpert=16, top_k=4,
                                        score="sigmoid"), logits, 2.5)
    np.testing.assert_allclose(np.asarray(probs).sum(-1),
                               np.asarray(w).sum(-1), rtol=1e-6)
    assert [t for t, _ in lay.visit_order()] == [
        "wmat", "gate", "up", "down", "shared_wmat", "shared_up",
        "shared_down"]


# --------------------------------------- the flash kernels at a window
@pytest.mark.parametrize("nh,window", [(6, 0), (8, 8)])
def test_the_flash_kernels_at_query_groups_of_3_and_4(nh, window):
    """In the interpreter: 6 and 8 query heads on 2 key-value heads of 16,
    256 rows, causal and in a window of 8; forward and the three
    gradients against the dense reference."""
    from cxxnet_tpu.ops import flash_attn as fa
    from cxxnet_tpu.parallel import attention_reference
    rs = np.random.RandomState(6)
    rows = 256
    q = jnp.asarray(rs.randn(1, nh, rows, 16), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, rows, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, rows, 16), jnp.float32)
    g = jnp.asarray(rs.randn(1, nh, rows, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, True, window), q, k, v)
        want, vjp_w = jax.vjp(lambda a, b, c: attention_reference(
            a, b, c, causal=True, window=window), q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        for a, b in zip(vjp(g), vjp_w(g)):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


# ------------------------------------------ the whole model, from conf text
def _trainer(conf):
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _set_from_seed(tr, conf_text, seed):
    layers, glob = netconf.parse(conf_text)
    params = laguna_inputs.params_from_seed(layers, glob, CFG)(
        seed_key(seed))
    for _, name, tag, _ in laguna_inputs.leaves_of(layers):
        tr.set_weight(np.asarray(params[name][tag]), name, tag)
    return layers, params


def test_the_model_agrees_with_the_reference():
    """The small model through the trainer's own loss function against
    ``laguna_moe.loss_mean`` on the same weights and tokens: the logits, the
    loss and the gradient of every leaf. Both sides float32 at
    ``highest``, so the gaps are the order of the sums: the probabilities
    the softmax node holds and the loss to 1e-4 of themselves (rtol, as
    ``tests/test_lfm2.py`` holds its model: the sums of 64 features and of
    the experts are taken in other orders), every leaf's gradient to 1e-3
    of its norm (the gradient's sums run over 64 rows besides, and the
    shared expert's and the gate's reach every row)."""
    tr = _trainer(_conf(L, 2, "seed = 3\n"))
    layers, params = _set_from_seed(tr, _net(), 5)
    data, label = lm_inputs.make_tokens(seed_key(5), 0, 2, L, 96)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(tr._loss_fn, has_aux=True)(
            tr.params, data, label, jax.random.PRNGKey(0), 0)
        values, _ = tr.net.forward(tr.params, data,
                                   labels=tr.net.label_info_from(label),
                                   train=True, rng=jax.random.PRNGKey(0))
        (want, said), want_g = jax.value_and_grad(
            laguna_moe.loss_mean, argnums=2, has_aux=True)(
            layers, "highest", params, data, label, 2 * L)
        logits = [laguna_moe.forward(layers, "highest", params,
                                     data[i].reshape(L).astype(jnp.int32))[0]
                  for i in range(2)]
    node = tr.net_cfg.node_name_map["logits"]
    got = np.asarray(values[node]).reshape(2, 96, L).transpose(0, 2, 1)
    np.testing.assert_allclose(got, jax.nn.softmax(np.stack(logits), -1),
                               rtol=1e-4, atol=1e-7)
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    idx = tr.net_cfg.get_layer_index
    seen = set()
    for _, name, tag, _ in laguna_inputs.leaves_of(layers):
        key = dict(tr.net.layers[idx(name)].visit_order())[tag]
        ref = np.asarray(want_g[name][tag])
        got = np.asarray(grads[idx(name)][key]).reshape(ref.shape)
        assert np.linalg.norm(got - ref) <= 1e-3 * np.linalg.norm(ref), \
            (name, tag)
        assert np.abs(ref).max() > 0, (name, tag)
        seen.add(tag)
    assert {"wgate", "shared_wmat", "shared_up", "shared_down"} <= seen
    assert set(said["pairs_held"]) == {"b1w_moe", "b2w_moe", "b3w_moe",
                                       "b4_moe"}
    # each of the model's faults moves the reference's logits far past
    # the gap between the two sound sides
    ids = data[0].reshape(L).astype(jnp.int32)
    for fault in ({"unwindowed": True}, {"yarn": False}, {"gate": False},
                  {"routed_scale": 1.0}):
        with jax.default_matmul_precision("highest"):
            other = laguna_moe.forward(layers, "highest", params, ids,
                                       fault)[0]
        assert np.abs(np.asarray(other - logits[0])).max() > 1e-2, fault


def test_the_new_leaves_travel_with_the_model(tmp_path):
    """The gate and the shared expert's three leaves come back from a saved
    model as they were set, and three steps move them."""
    conf = _conf(L, 1, "seed = 3\neta = 0.01\n")
    tr = _trainer(conf)
    _set_from_seed(tr, _net(), 9)
    i, j = (tr.net_cfg.get_layer_index(n) for n in ("b0_att", "b2w_moe"))
    before = {k: np.asarray(tr.params[j][k]) for k in (
        "shared_wmat", "shared_up", "shared_down")}
    before["wgate"] = np.asarray(tr.params[i]["wgate"])
    b = DataBatch()
    b.data, b.label = lm_inputs.make_tokens(seed_key(9), 0, 1, L, 96)
    b.batch_size = 1
    for _ in range(3):
        tr.update(b)
    now = {k: np.asarray(tr.params[j][k]) for k in before if k != "wgate"}
    now["wgate"] = np.asarray(tr.params[i]["wgate"])
    for k in before:
        assert np.abs(now[k] - before[k]).max() > 0, k
    from cxxnet_tpu.utils import serializer
    path = str(tmp_path / "m.bin")
    with open(path, "wb") as f:
        tr.save_model(serializer.Writer(f))
    tr2 = Trainer()
    for k, v in parse_config_string(conf):
        tr2.set_param(k, v)
    with open(path, "rb") as f:
        tr2.load_model(serializer.Reader(f))
    np.testing.assert_array_equal(np.asarray(tr2.params[i]["wgate"]),
                                  now["wgate"])
    np.testing.assert_array_equal(np.asarray(tr2.params[j]["shared_down"]),
                                  now["shared_down"])
    got, shape = tr.get_weight("b0_att", "wgate")
    assert tuple(shape) == (D, 6)


def test_the_step_on_the_forced_flash_path_counts_its_paths():
    """``Trainer.update`` from the conf text with the kernels forced on
    (the interpreter), 256 rows: every attention layer takes the flash
    kernels and the gate, the full layers YaRN over part of a head, every
    ``moe`` layer the sparse lowering and its shared expert, once each;
    the loss is the dense path's."""
    seq = 256
    conf = _conf(seq, 1, "eval_train = 0\nhealth_monitor = 1\nseed = 3\n")
    b = DataBatch()
    b.data, b.label = lm_inputs.make_tokens(seed_key(4), 0, 1, seq, 96)
    b.batch_size = 1

    def run(force):
        before = telemetry.paths()
        ops.set_use_pallas(force)
        try:
            tr = _trainer(conf)
            tr.update(b)
            health = np.asarray(tr.last_health)
        finally:
            ops.set_use_pallas(None)
        return health, {k: n - before.get(k, 0)
                        for k, n in telemetry.paths().items()
                        if n != before.get(k, 0)}
    health, paths = run(True)
    assert {k: n for k, n in paths.items()
            if not k.startswith("flash.tiles.")} == {
        "attn.flash": 5, "attn.bwd.fused": 5, "attn.prep.xla": 5,
        "attn.gate": 5, "attn.rope.yarn": 2, "attn.rope.partial": 2,
        "moe.sparse": 4, "moe.sigmoid": 4, "moe.bounded": 4,
        "moe.shared": 4}
    assert "attn.dense" not in paths
    dense, paths = run(False)
    assert paths.get("attn.dense") == 5 and "attn.flash" not in paths
    assert np.isfinite(health[0]) and 2.0 < health[0] < 8.0
    assert health[0] == pytest.approx(dense[0], rel=1e-5)


def test_the_builder_writes_the_published_model():
    """The defaults are Laguna-XS.2's config.json: 40 blocks, full
    attention every fourth from the first, 48 query heads there and 64 on
    the window layers, 8 key-value heads of 128, a window of 512, YaRN on
    the full layers over 64 features; one dense layer of 8,192, then 39
    ``moe`` layers of 256 experts of 512, top 8, scale 2.5, a shared
    expert of 512; eps 1e-6; vocabulary 100,352."""
    layers, glob = netconf.parse(models.laguna_moe_netconfig())
    att = [lay for lay in layers if lay.type == "attention"]
    assert len(att) == 40
    for i, a in enumerate(att):
        full = i % 4 == 0
        assert a.name == ("b%d_att" if full else "b%dw_att") % i
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) \
            == (48 if full else 64, 8, 128)
        assert a.geti("head_gate") == 1 and a.geti("causal") == 1
        assert a.geti("attn_window") == (0 if full else 512)
        if full:
            assert (a.getf("rope_base"), a.geti("rope_dim"),
                    a.params["rope_type"], a.getf("yarn_factor"),
                    a.geti("yarn_original_len"), a.getf("yarn_beta_fast"),
                    a.getf("yarn_beta_slow")) == (
                5e5, 64, "yarn", 64.0, 4096, 64.0, 1.0)
            assert a.getf("yarn_attention_factor") == 1.4158883083359672
        else:
            assert a.getf("rope_base") == 1e4 and not a.geti("rope_dim")
            assert "rope_type" not in a.params
    ffn = [lay for lay in layers if lay.type in ("ffn", "moe")]
    assert [lay.type for lay in ffn] == ["ffn"] + ["moe"] * 39
    assert ffn[0].geti("nhidden") == 8192
    for m in ffn[1:]:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held"), m.geti("nshared_hidden")) \
            == (256, 8, 512, 256, 512)
        assert m.params["score"] == "sigmoid" and m.getf("routed_scale") \
            == 2.5 and "expert_bias" not in m.params
    assert {lay.getf("eps") for lay in layers if lay.type == "rmsnorm"} \
        == {1e-6}
    assert lm_inputs.vocab_of(layers) == 100352
    assert glob["init_sigma"] == "0.02"
