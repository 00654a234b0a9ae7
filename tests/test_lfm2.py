"""LFM2-24B-A2B's language model, the program's parts on the CPU at a small
size: the gated short-conv mixer (``shortconv``), sigmoid routing with an
expert bias in ``moe``, the dense gated FFN (``ffn``), attention and the
flash kernels at a head of 64 on 8 key-value heads, and the whole model
through the trainer from ``models.lfm2_moe_conf``'s text. The plain side is
the benchmark's reference (``benchmark/references/lfm2_moe.py``); the model
against it through the cell's own ``run_cell`` is
``tests/benchmark/test_lfm2_moe.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lfm2_inputs, lm_inputs, netconf  # noqa: E402
from benchmark.inputs import seed_key  # noqa: E402
from benchmark.references import lfm2_moe  # noqa: E402
from cxxnet_tpu import models, ops  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.layer.base import ApplyContext  # noqa: E402
from cxxnet_tpu.layer.layers import (AttentionLayer, GatedFFNLayer,  # noqa
                                     MoELayer, ShortConvLayer)
from cxxnet_tpu.nnet.trainer import Trainer  # noqa: E402
from cxxnet_tpu.utils import telemetry  # noqa: E402
from cxxnet_tpu.utils.config import parse_config_string  # noqa: E402

D, L = 64, 32
# the issue's small model: d 64, 4 heads over 2 key-value heads of 16, 8
# experts with 4 held, one dense and four mixed layers
SMALL = dict(vocab=96, dim=D, nhead=4, nkvhead=2, head_dim=16,
             layer_types=("conv", "full_attention", "conv", "conv", "conv"),
             n_dense=1, dense_width=96, n_expert=8, top_k=4, expert_width=32,
             n_held=4, expert_offset=2)
CFG = {"weights_base_seed": 11, "expert_bias": {"mean": 0.2, "sd": 0.05}}


def _layer(cls, shape, **keys):
    lay = cls()
    for k, v in keys.items():
        lay.set_param(k, str(v))
    lay.infer_shape([shape])
    return lay


def _ref_layer(kind, **keys):
    return netconf.Layer(kind, "x", ["a"], ["b"],
                         {k: str(v) for k, v in keys.items()})


# ------------------------------------------------------------ the conv mixer
def _conv_weights(rs, d=D, K=3):
    return {"w_in": jnp.asarray(rs.randn(d, 3 * d) * 0.2, jnp.float32),
            "w_conv": jnp.asarray(rs.randn(d, K), jnp.float32),
            "w_out": jnp.asarray(rs.randn(d, d) * 0.2, jnp.float32)}


@pytest.mark.parametrize("channels_last", [False, True])
def test_the_conv_mixer_is_the_references(channels_last):
    """Forward and every gradient against ``lfm2_moe.shortconv``, row by
    row; both float32, so the gap is the order of the sums (5e-6 of
    values of order one)."""
    rs = np.random.RandomState(0)
    lay = _layer(ShortConvLayer, (2, D, 1, L))
    w = _conv_weights(rs)
    x = jnp.asarray(rs.randn(2, D, 1, L), jnp.float32)
    ref = _ref_layer("shortconv", kernel=3)

    def prog(w, x):
        ctx = ApplyContext(train=True, channels_last=channels_last)
        xin = jnp.transpose(x, (0, 2, 3, 1)) if channels_last else x
        y, = lay.apply(w, [xin], ctx)
        return jnp.transpose(y, (0, 3, 1, 2)) if channels_last else y

    def plain(w, x):
        rows = x.reshape(2, D, L).transpose(0, 2, 1)
        y = jax.vmap(lambda h: lfm2_moe.shortconv(ref, "highest", w, h))(
            rows)
        return y.transpose(0, 2, 1).reshape(2, D, 1, L)
    g = jnp.asarray(rs.randn(2, D, 1, L), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(prog, w, x)
        want, vjp_w = jax.vjp(plain, w, x)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-6)
        for a, b in zip(jax.tree.leaves(vjp(g)), jax.tree.leaves(vjp_w(g))):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5)
    # the fault the comparison must see: the conv one position ahead
    ahead = jax.vmap(lambda h: lfm2_moe.shortconv(ref, "highest", w, h,
                                                  ahead=True))(
        x.reshape(2, D, L).transpose(0, 2, 1))
    assert np.abs(np.asarray(ahead) - np.asarray(
        want.reshape(2, D, L).transpose(0, 2, 1))).max() > 1e-2


def test_the_conv_is_causal_and_reads_no_other_row():
    """A change of token t + 1, or of row 1, leaves row 0's outputs up to t
    as they were; a change of token t moves position t."""
    rs = np.random.RandomState(1)
    lay = _layer(ShortConvLayer, (2, D, 1, L))
    w = _conv_weights(rs)
    x = rs.randn(2, D, 1, L).astype(np.float32)
    ctx = ApplyContext(train=False)
    base = np.asarray(lay.apply(w, [jnp.asarray(x)], ctx)[0])
    t = 17
    later = x.copy()
    later[0, :, 0, t + 1:] += rs.randn(D, L - t - 1).astype(np.float32)
    other = x.copy()
    other[1] += rs.randn(D, 1, L).astype(np.float32)
    now = x.copy()
    now[0, :, 0, t] += 1.0
    for changed in (later, other):
        y = np.asarray(lay.apply(w, [jnp.asarray(changed)], ctx)[0])
        np.testing.assert_array_equal(y[0, :, 0, :t + 1],
                                      base[0, :, 0, :t + 1])
    np.testing.assert_array_equal(
        np.asarray(lay.apply(w, [jnp.asarray(other)], ctx)[0])[0], base[0])
    y = np.asarray(lay.apply(w, [jnp.asarray(now)], ctx)[0])
    np.testing.assert_array_equal(y[0, :, 0, :t], base[0, :, 0, :t])
    assert np.abs(y[0, :, 0, t] - base[0, :, 0, t]).max() > 1e-3


def test_the_conv_core_is_its_equations():
    """``short_conv`` against the equations written out at one position:
    u = B x, v_t = w0 u_{t-2} + w1 u_{t-1} + w2 u_t, C v; zeros before a
    row's start."""
    from cxxnet_tpu.layer.layers import short_conv
    rs = np.random.RandomState(2)
    bcx = rs.randn(2, L, 3 * D).astype(np.float32)
    w = rs.randn(D, 3).astype(np.float32)
    got = np.asarray(short_conv(jnp.asarray(bcx), jnp.asarray(w)))
    B, C, X = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    u = B * X
    for t in (0, 1, 2, L - 1):
        v = sum(w[:, j] * u[:, t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        np.testing.assert_allclose(got[:, t], C[:, t] * v, rtol=1e-5,
                                   atol=1e-6)


def test_what_the_conv_mixer_does_not_write_is_refused():
    lay = _layer(ShortConvLayer, (2, D, 1, L))
    ctx = ApplyContext(train=False)
    ctx.decode_pos = 0
    with pytest.raises(ValueError, match="decoding from a cache"):
        lay.apply(_conv_weights(np.random.RandomState(0)),
                  [jnp.zeros((2, D, 1, L))], ctx)
    assert [t for t, _ in lay.visit_order()] == ["w_in", "w_conv", "w_out"]


# ------------------------------------------------- sigmoid routing, the bias
def _moe(held=8, offset=0, **keys):
    return _layer(MoELayer, (2, D, 1, L), **dict(
        {"nexpert": 8, "top_k": 4, "nhidden": 16, "expert_act": "swiglu",
         "nexpert_held": held, "expert_offset": offset, "score": "sigmoid",
         "expert_bias": 1}, **keys))


def _moe_weights(rs, e=8, f=16):
    return {"gate": jnp.asarray(rs.randn(e, D) * 0.3, jnp.float32),
            "experts": jnp.asarray(rs.randn(e, D, f) * 0.2, jnp.float32),
            "up": jnp.asarray(rs.randn(e, D, f) * 0.2, jnp.float32),
            "down": jnp.asarray(rs.randn(e, f, D) * 0.2, jnp.float32),
            "expert_bias": jnp.asarray(rs.randn(e) * 0.3, jnp.float32)}


def test_the_selection_follows_score_plus_bias_and_the_weights_the_score():
    """Planted: token 0's unbiased top 4 are experts 0-3, the bias lifts
    expert 7 past expert 3; the weights are the chosen ones' sigmoid
    scores over their sum (+ 1e-6); the reference's ``route`` reads the
    same."""
    lay = _moe()
    logits = jnp.asarray([[3.0, 2.5, 2.0, 1.0, 0.5, -1.0, -2.0, 0.9]])
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0, 0.2], jnp.float32)
    idx, w = lay._top(logits, bias)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 2, 7]
    s = jax.nn.sigmoid(logits)[0]
    want = np.asarray(s)[[0, 1, 2, 7]]
    want = want / (want.sum() + 1e-6)
    order = np.argsort(np.asarray(idx)[0])
    np.testing.assert_allclose(np.asarray(w)[0][order], want, rtol=1e-6)
    # no bias: the scores' own top 4
    assert sorted(np.asarray(lay._top(logits)[0])[0].tolist()) == [0, 1, 2, 3]
    ref = _ref_layer("moe", nexpert=8, top_k=4, score="sigmoid")
    probs, plain = lfm2_moe.route(ref, logits, bias)
    np.testing.assert_allclose(np.asarray(probs)[0][[0, 1, 2, 7]], want,
                               rtol=1e-6)
    assert np.asarray(plain)[0].tolist() == [1, 1, 1, 1, 0, 0, 0, 0]
    # the two faults move what they should
    left_out = lfm2_moe.route(ref, logits, bias, bias_choice=False)[0]
    assert (np.asarray(left_out)[0] > 0).tolist() == [1, 1, 1, 1, 0, 0, 0, 0]
    biased = np.asarray(lfm2_moe.route(ref, logits, bias,
                                       bias_weight=True)[0])[0]
    assert biased[7] > np.asarray(probs)[0][7]


def test_the_bias_chooses_and_gets_no_gradient():
    """Through the sparse lowering: the bias's gradient is nought, the
    router's is not, and the output follows the bias's choice."""
    rs = np.random.RandomState(3)
    lay = _moe()
    w = _moe_weights(rs)
    x = jnp.asarray(rs.randn(2, D, 1, L), jnp.float32)

    def out(w):
        ctx = ApplyContext(train=True)
        ctx.conn_index = 0
        return jnp.sum(lay.apply(w, [x], ctx)[0] ** 2)
    g = jax.grad(out)(w)
    assert not np.asarray(g["expert_bias"]).any()
    assert np.abs(np.asarray(g["gate"])).max() > 0
    moved = dict(w, expert_bias=w["expert_bias"] + jnp.asarray(
        rs.randn(8) * 2.0, jnp.float32))
    assert abs(float(out(moved)) - float(out(w))) > 1e-3


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """A deployment of eight chips a layer at this size: each chip holds
    one expert (its ``expert_offset``), the router all eight outputs and
    the bias; the eight shares summed are the uncut reference layer's
    result, and every pair is held somewhere."""
    rs = np.random.RandomState(4)
    w = _moe_weights(rs)
    x = jnp.asarray(rs.randn(1, D, 1, L), jnp.float32)
    parts = []
    for chip in range(8):
        share = dict(w, **{k: w[k][chip:chip + 1]
                           for k in ("experts", "up", "down")})
        ctx = ApplyContext(train=True)
        ctx.conn_index = 0
        out, = _moe(held=1, offset=chip).apply(share, [x], ctx)
        parts.append(out)
    got = sum(parts).reshape(D, L).T
    ref = _ref_layer("moe", nexpert=8, top_k=4, score="sigmoid",
                     expert_act="swiglu", expert_offset=0)
    with jax.default_matmul_precision("highest"):
        whole, pairs, _ = lfm2_moe.moe(ref, "highest",
                                       dict(w, wmat=w["experts"]),
                                       x.reshape(D, L).T)
    np.testing.assert_allclose(got, whole, rtol=1e-4, atol=1e-5)
    assert int(pairs) == 4 * L


def test_keys_that_do_not_go_together_are_refused():
    with pytest.raises(ValueError, match="needs score = sigmoid"):
        _moe(score="softmax")
    with pytest.raises(ValueError, match="score must be"):
        _moe(score="tanh")


# ------------------------------------------------------------ the dense ffn
def test_the_dense_ffn_is_its_one_line_formula():
    rs = np.random.RandomState(5)
    lay = _layer(GatedFFNLayer, (2, D, 1, L), nhidden=48)
    w = {"wmat": rs.randn(D, 48).astype(np.float32) * 0.2,
         "up": rs.randn(D, 48).astype(np.float32) * 0.2,
         "down": rs.randn(48, D).astype(np.float32) * 0.2}
    x = rs.randn(2, D, 1, L).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y, = lay.apply({k: jnp.asarray(v) for k, v in w.items()},
                       [jnp.asarray(x)], ApplyContext(train=False))
    h = x.reshape(2, D, L).transpose(0, 2, 1)
    a = h @ w["wmat"]
    want = ((a / (1 + np.exp(-a)) * (h @ w["up"])) @ w["down"]) \
        .transpose(0, 2, 1)
    np.testing.assert_allclose(np.asarray(y).reshape(2, D, L), want,
                               rtol=1e-5, atol=1e-5)
    assert [t for t, _ in lay.visit_order()] == ["wmat", "up", "down"]


# --------------------------------------------- attention at a head of 64
@pytest.mark.parametrize("causal", [True, False])
def test_the_flash_kernels_at_heads_of_64_on_8_kv_heads(causal):
    """In the interpreter: 16 query heads on 8 key-value heads of 64, 256
    rows, forward and the three gradients against the dense reference; a
    head of 64 takes the head of 128's tiles."""
    from cxxnet_tpu.ops import flash_attn as fa
    from cxxnet_tpu.parallel import attention_reference
    rs = np.random.RandomState(6)
    rows = 256
    q = jnp.asarray(rs.randn(1, 16, rows, 64), jnp.float32)
    k = jnp.asarray(rs.randn(1, 8, rows, 64), jnp.float32)
    v = jnp.asarray(rs.randn(1, 8, rows, 64), jnp.float32)
    g = jnp.asarray(rs.randn(1, 16, rows, 64), jnp.float32)
    assert ops.flash_supported(rows, 64)
    assert fa._tiling(8192, 64, 2, 0) == fa._tiling(8192, 128, 2, 0)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda a, b, c: fa.flash_attention(
            a, b, c, causal, None, True, 0), q, k, v)
        want, vjp_w = jax.vjp(lambda a, b, c: attention_reference(
            a, b, c, causal=causal), q, k, v)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        for a, b in zip(vjp(g), vjp_w(g)):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_the_qk_pass_at_heads_of_64_takes_the_plain_lines():
    """``ops.qk_prep`` takes heads of whole lane tiles and its own eps: at
    64, or at a norm of 1e-5, the layer counts ``attn.prep.xla`` under
    forced kernels and gives the reference's q / k (norm eps 1e-5, then
    the rotation at theta 1e6)."""
    assert not ops.qk_prep_supported(512, 64, 2048 + 2 * 512, jnp.bfloat16)
    assert not ops.qk_prep_supported(512, 128, 4096 + 1024, jnp.bfloat16,
                                     1e-5)
    rs = np.random.RandomState(7)
    rows, nh, nkv, dh = 256, 4, 2, 64
    lay = _layer(AttentionLayer, (1, D, 1, rows), nhead=nh, nkvhead=nkv,
                 head_dim=dh, causal=1, qk_norm=1, qk_norm_eps=1e-5, rope=1,
                 rope_base=1e6)
    qkv = jnp.asarray(rs.randn(1, rows, (nh + 2 * nkv) * dh), jnp.float32)
    gains = {"qnorm": jnp.asarray(rs.rand(dh) + 0.5, jnp.float32),
             "knorm": jnp.asarray(rs.rand(dh) + 0.5, jnp.float32)}
    before = telemetry.paths()
    ops.set_use_pallas(True)
    try:
        q, k, v = lay._heads(qkv, gains, ApplyContext(train=True))
    finally:
        ops.set_use_pallas(None)
    assert telemetry.paths()["attn.prep.xla"] \
        == before.get("attn.prep.xla", 0) + 1
    from benchmark.references.sdar_moe import _rmsnorm, _rope
    pos = jnp.arange(rows)
    t = qkv[0]
    want_q = _rope(_rmsnorm(t[:, :nh * dh].reshape(rows, nh, dh)
                            .transpose(1, 0, 2), gains["qnorm"], 1e-5),
                   pos, 1e6)
    want_k = _rope(_rmsnorm(t[:, nh * dh:(nh + nkv) * dh]
                            .reshape(rows, nkv, dh).transpose(1, 0, 2),
                            gains["knorm"], 1e-5), pos, 1e6)
    np.testing.assert_allclose(q[0], want_q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(k[0], want_k, rtol=1e-5, atol=1e-5)


# ------------------------------------------ the whole model, from conf text
def _trainer(conf):
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _set_from_seed(tr, conf_text, seed):
    layers, glob = netconf.parse(conf_text)
    params = lfm2_inputs.params_from_seed(layers, glob, CFG)(seed_key(seed))
    for _, name, tag, _ in lfm2_inputs.leaves_of(layers):
        tr.set_weight(np.asarray(params[name][tag]), name, tag)
    return layers, params


def test_the_model_agrees_with_the_reference():
    """The small model through the trainer's own loss function against
    ``lfm2_moe.loss_mean`` on the same weights and tokens: the logits, the
    loss and the gradient of every leaf. Both sides float32 at
    ``highest``; the gaps are the order of the sums: 1e-5 of the
    probabilities the softmax node holds, 1e-6 of the loss, 1e-4 of a leaf's gradient norm (2e-4 of
    its largest entry). The expert bias has a gradient of nought on both
    sides."""
    net = models.lfm2_moe_netconfig(**SMALL)
    conf = models.lfm2_moe_conf(seq=L, batch_size=2, dev="cpu",
                                extra_cfg="seed = 3\n", **SMALL)
    tr = _trainer(conf)
    layers, params = _set_from_seed(tr, net, 5)
    data, label = lm_inputs.make_tokens(seed_key(5), 0, 2, L, 96)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(tr._loss_fn, has_aux=True)(
            tr.params, data, label, jax.random.PRNGKey(0), 0)
        values, _ = tr.net.forward(tr.params, data,
                                   labels=tr.net.label_info_from(label),
                                   train=True, rng=jax.random.PRNGKey(0))
        (want, said), want_g = jax.value_and_grad(
            lfm2_moe.loss_mean, argnums=2, has_aux=True)(
            layers, "highest", params, data, label, 2 * L)
        logits = [lfm2_moe.forward(layers, "highest", params,
                                   data[i].reshape(L).astype(jnp.int32))[0]
                  for i in range(2)]
    # the node the softmax layer writes in place: the probabilities
    node = tr.net_cfg.node_name_map["logits"]
    got = np.asarray(values[node]).reshape(2, 96, L).transpose(0, 2, 1)
    np.testing.assert_allclose(got, jax.nn.softmax(np.stack(logits), -1),
                               rtol=1e-5, atol=1e-7)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    idx = tr.net_cfg.get_layer_index
    for _, name, tag, _ in lfm2_inputs.leaves_of(layers):
        key = dict(tr.net.layers[idx(name)].visit_order())[tag]
        got, ref = np.asarray(grads[idx(name)][key]), np.asarray(
            want_g[name][tag])
        if tag in lfm2_inputs.FIXED:
            assert not got.any() and not ref.any(), name
            continue
        got = got.reshape(ref.shape)
        scale = np.linalg.norm(ref)
        assert np.linalg.norm(got - ref) <= 1e-4 * scale, (name, tag)
        np.testing.assert_allclose(got, ref, atol=2e-4 * np.abs(ref).max(),
                                   rtol=0)
    assert set(said["pairs_held"]) == {"b%d_moe" % i for i in range(1, 5)}


def test_the_expert_bias_is_held_fixed_and_travels_with_the_model(tmp_path):
    """Three steps move every trained leaf and not the bias, which has no
    updater and no optimizer state, stays float32 under a bf16 compute
    type, and comes back from a saved model as it was set."""
    conf = models.lfm2_moe_conf(
        seq=L, batch_size=2, dev="cpu",
        extra_cfg="compute_dtype = bfloat16\nseed = 3\neta = 0.01\n",
        **SMALL)
    tr = _trainer(conf)
    net = models.lfm2_moe_netconfig(**SMALL)
    _, params = _set_from_seed(tr, net, 9)
    i = tr.net_cfg.get_layer_index("b2_moe")
    assert "expert_bias" not in tr.updaters[i]
    assert "expert_bias" not in tr.opt_state[i]
    bias = np.asarray(params["b2_moe"]["expert_bias"])
    before = np.asarray(tr.params[i]["gate"])
    b = DataBatch()
    b.data, b.label = lm_inputs.make_tokens(seed_key(9), 0, 2, L, 96)
    b.batch_size = 2
    for _ in range(3):
        tr.update(b)
    np.testing.assert_array_equal(np.asarray(tr.params[i]["expert_bias"]),
                                  bias)
    assert tr.params[i]["expert_bias"].dtype == jnp.float32
    assert np.abs(np.asarray(tr.params[i]["gate"]) - before).max() > 0
    got, shape = tr.get_weight("b2_moe", "expert_bias")
    np.testing.assert_array_equal(np.asarray(got).reshape(shape), bias)
    from cxxnet_tpu.utils import serializer
    path = str(tmp_path / "m.bin")
    with open(path, "wb") as f:
        tr.save_model(serializer.Writer(f))
    tr2 = Trainer()
    for k, v in parse_config_string(conf):
        tr2.set_param(k, v)
    with open(path, "rb") as f:
        tr2.load_model(serializer.Reader(f))
    np.testing.assert_array_equal(np.asarray(tr2.params[i]["expert_bias"]),
                                  bias)


def test_the_step_on_the_forced_flash_path_counts_its_paths():
    """``Trainer.update`` from the conf text with the kernels forced on
    (the interpreter), 256 rows: the attention layer takes the flash
    kernels and the plain qk lines (heads of 16), every ``moe`` layer the
    sparse lowering (its sorted side whole: 1.5 x the even share in tiles
    of 512 is all 1,024 pairs) and sigmoid scores, once each; the loss is
    the dense path's."""
    seq = 256
    conf = models.lfm2_moe_conf(
        seq=seq, batch_size=1, dev="cpu",
        extra_cfg="eval_train = 0\nhealth_monitor = 1\nseed = 3\n", **SMALL)
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
        "attn.flash": 1, "attn.prep.xla": 1, "attn.bwd.fused": 1,
        "moe.sparse": 4,
        "moe.sigmoid": 4}
    dense, paths = run(False)
    assert paths.get("attn.dense") == 1 and "attn.flash" not in paths
    assert np.isfinite(health[0]) and 2.0 < health[0] < 8.0
    assert health[0] == pytest.approx(dense[0], rel=1e-5)


def test_the_builder_writes_the_published_model():
    """The defaults are LFM2-24B-A2B's config.json: 40 blocks, 30 conv
    mixers and 10 attention in the published order, two dense layers of
    11,776 then 38 ``moe`` layers of 64 experts of 1,536, top 4 by sigmoid
    + bias; 32 heads on 8 of 64, theta 1e6, eps 1e-5; vocabulary 65,536."""
    layers, glob = netconf.parse(models.lfm2_moe_netconfig())
    kinds = [lay.type for lay in layers
             if lay.type in ("shortconv", "attention")]
    assert kinds == [{"conv": "shortconv", "full_attention": "attention"}[t]
                     for t in models.LFM2_LAYER_TYPES]
    assert kinds.count("shortconv") == 30 and len(kinds) == 40
    ffn = [lay for lay in layers if lay.type in ("ffn", "moe")]
    assert [lay.type for lay in ffn] == ["ffn"] * 2 + ["moe"] * 38
    assert all(lay.geti("nhidden") == 11776 for lay in ffn[:2])
    for m in ffn[2:]:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held")) == (64, 4, 1536, 64)
        assert m.params["score"] == "sigmoid" and m.geti("expert_bias") == 1
        assert len(m.ins) == 1
    for a in (lay for lay in layers if lay.type == "attention"):
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) \
            == (32, 8, 64)
        assert a.getf("rope_base") == 1e6 and a.getf("qk_norm_eps") == 1e-5
    for c in (lay for lay in layers if lay.type == "shortconv"):
        assert c.geti("kernel") == 3
    assert {lay.getf("eps") for lay in layers if lay.type == "rmsnorm"} \
        == {1e-5}
    assert lm_inputs.vocab_of(layers) == 65536
    assert glob["init_sigma"] == "0.02"
