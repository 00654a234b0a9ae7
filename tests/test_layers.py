"""Golden tests for layer forward/backward numerics against numpy references.

This is the framework's version of the reference's PairTestLayer differential
testing idea (src/layer/pairtest_layer-inl.hpp): each XLA layer is checked
against an independent numpy implementation of the reference semantics.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu import ops
from cxxnet_tpu.layer import ApplyContext, LabelInfo, factory
from cxxnet_tpu.layer import layers as L


def ctx(train=False, seed=0):
    return ApplyContext(train=train, rng=jax.random.PRNGKey(seed))


def rand(shape, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(*shape).astype(np.float32)


# ---------------------------------------------------------------------------
# fullc
# ---------------------------------------------------------------------------
def test_fullc_forward_backward():
    lay = L.FullConnectLayer()
    lay.set_param("nhidden", "5")
    out_shapes = lay.infer_shape([(4, 1, 1, 7)])
    assert out_shapes == [(4, 1, 1, 5)]
    params = lay.init_params(np.random.RandomState(0))
    assert params["wmat"].shape == (5, 7)
    assert params["bias"].shape == (5,)

    x = rand((4, 1, 1, 7))
    y = lay.apply(params, [jnp.asarray(x)], ctx())[0]
    expect = x.reshape(4, 7) @ params["wmat"].T + params["bias"]
    np.testing.assert_allclose(np.asarray(y).reshape(4, 5), expect, rtol=1e-5)

    # grads match the reference formulas: gW = dy^T . x ; gb = sum_rows(dy);
    # dx = dy . W (fullc_layer-inl.hpp:121-130)
    def f(p, xx):
        return jnp.sum(lay.apply(p, [xx], ctx())[0] * 2.0)

    gp, gx = jax.grad(f, argnums=(0, 1))(params, jnp.asarray(x))
    dy = np.full((4, 5), 2.0, np.float32)
    np.testing.assert_allclose(np.asarray(gp["wmat"]), dy.T @ x.reshape(4, 7), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gp["bias"]), dy.sum(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx).reshape(4, 7), dy @ params["wmat"], rtol=1e-5)


def test_fullc_init_gaussian_stats():
    lay = L.FullConnectLayer()
    lay.set_param("nhidden", "400")
    lay.set_param("init_sigma", "0.05")
    lay.infer_shape([(2, 1, 1, 300)])
    params = lay.init_params(np.random.RandomState(3))
    assert abs(float(params["wmat"].std()) - 0.05) < 0.005


def test_fullc_init_xavier_bound():
    lay = L.FullConnectLayer()
    lay.set_param("nhidden", "50")
    lay.set_param("random_type", "xavier")
    lay.infer_shape([(2, 1, 1, 100)])
    params = lay.init_params(np.random.RandomState(3))
    bound = np.sqrt(3.0 / 150)
    assert float(np.abs(params["wmat"]).max()) <= bound + 1e-6


# ---------------------------------------------------------------------------
# conv
# ---------------------------------------------------------------------------
def _np_conv(x, w_oihw, stride, pad, groups=1):
    n, c, h, ww = x.shape
    o, cg, kh, kw = w_oihw.shape
    xp = np.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    oh = (h + 2 * pad[0] - kh) // stride + 1
    ow = (ww + 2 * pad[1] - kw) // stride + 1
    out = np.zeros((n, o, oh, ow), np.float32)
    og = o // groups
    for g in range(groups):
        for oc in range(g * og, (g + 1) * og):
            for i in range(oh):
                for j in range(ow):
                    patch = xp[:, g * cg:(g + 1) * cg,
                               i * stride:i * stride + kh,
                               j * stride:j * stride + kw]
                    out[:, oc, i, j] = np.einsum(
                        "nchw,chw->n", patch, w_oihw[oc])
    return out


@pytest.mark.parametrize("groups,pad,stride", [(1, (0, 0), 1), (2, (1, 1), 2)])
def test_conv_matches_numpy(groups, pad, stride):
    lay = L.ConvolutionLayer()
    lay.set_param("nchannel", "4")
    lay.set_param("kernel_size", "3")
    lay.set_param("stride", str(stride))
    lay.set_param("pad", str(pad[0]))
    lay.set_param("ngroup", str(groups))
    out_shape = lay.infer_shape([(2, 4, 8, 8)])[0]
    params = lay.init_params(np.random.RandomState(0))
    x = rand((2, 4, 8, 8), seed=1)
    y = lay.apply(params, [jnp.asarray(x)], ctx())[0]
    assert tuple(y.shape) == out_shape
    w_oihw = params["wmat"].reshape(4, 4 // groups, 3, 3)
    expect = _np_conv(x, w_oihw, stride, pad, groups) + params["bias"].reshape(1, -1, 1, 1)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4, atol=1e-5)


def test_conv_shape_formula():
    # reference formula: (x + 2p - k) / s + 1 (convolution_layer-inl.hpp:180)
    lay = L.ConvolutionLayer()
    lay.set_param("nchannel", "32")
    lay.set_param("kernel_size", "3")
    lay.set_param("stride", "2")
    lay.set_param("pad", "1")
    assert lay.infer_shape([(100, 1, 28, 28)]) == [(100, 32, 14, 14)]


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
def _np_pool(x, mode, k, s):
    n, c, h, w = x.shape
    oh = min(h - k + s - 1, h - 1) // s + 1
    ow = min(w - k + s - 1, w - 1) // s + 1
    out = np.zeros((n, c, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, :, i * s:min(i * s + k, h), j * s:min(j * s + k, w)]
            if mode == "max":
                out[:, :, i, j] = patch.max(axis=(2, 3))
            else:
                out[:, :, i, j] = patch.sum(axis=(2, 3))
                if mode == "avg":
                    out[:, :, i, j] /= k * k
    return out


@pytest.mark.parametrize("mode,cls", [
    ("max", L.MaxPoolingLayer), ("sum", L.SumPoolingLayer), ("avg", L.AvgPoolingLayer)])
@pytest.mark.parametrize("hw,k,s", [(8, 3, 2), (7, 2, 2), (5, 3, 3)])
def test_pooling_matches_numpy(mode, cls, hw, k, s):
    lay = cls()
    lay.set_param("kernel_size", str(k))
    lay.set_param("stride", str(s))
    oshape = lay.infer_shape([(2, 3, hw, hw)])[0]
    x = rand((2, 3, hw, hw), seed=2)
    y = lay.apply({}, [jnp.asarray(x)], ctx())[0]
    expect = _np_pool(x, mode, k, s)
    assert tuple(y.shape) == oshape == expect.shape
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-5, atol=1e-6)


def _np_unpool(x, g_of_y, k, s, p):
    """Gradient of a ceil-mode max pool over (n, c, h, w) by a plain
    unpool: pad by p with -inf, and each window's upstream gradient
    ``g_of_y(max)`` goes to its largest input (the input has no ties)."""
    n, c, h, w = x.shape
    oh = ops.pool_out_dim(h + 2 * p, k, s)
    ow = ops.pool_out_dim(w + 2 * p, k, s)
    xp = np.full((n, c, max((oh - 1) * s + k, h + 2 * p),
                  max((ow - 1) * s + k, w + 2 * p)), -np.inf, np.float64)
    xp[:, :, p:p + h, p:p + w] = x
    dxp = np.zeros_like(xp)
    ni, ci = np.indices((n, c))
    for i in range(oh):
        for j in range(ow):
            win = xp[:, :, i * s:i * s + k, j * s:j * s + k].reshape(n, c, -1)
            a = win.argmax(axis=2)
            np.add.at(dxp, (ni, ci, i * s + a // k, j * s + a % k),
                      g_of_y(win.max(axis=2)))
    return dxp[:, :, p:p + h, p:p + w]


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
@pytest.mark.parametrize("h,w,k,s,p", [(13, 13, 3, 2, 0), (8, 8, 2, 2, 0),
                                       (14, 14, 3, 1, 1), (7, 9, 3, 3, 0)])
def test_max_pool_backward_matches_numpy_unpool(h, w, k, s, p, layout):
    """pool2d's max backward (XLA's select-and-scatter) against a plain
    numpy unpool, on an input without ties."""
    x = rand((2, 3, h, w), seed=7)

    def loss(x_):
        return jnp.sum(jnp.sin(ops.pool2d(x_, "max", (k, k), s, (p, p),
                                          layout=layout)) * 1.7)

    xin = jnp.asarray(x)
    if layout == "NHWC":
        xin = ops.to_nhwc(xin)
    dx = jax.grad(loss)(xin)
    if layout == "NHWC":
        dx = ops.to_nchw(dx)
    want = _np_unpool(x, lambda y: 1.7 * np.cos(y), k, s, p)
    np.testing.assert_allclose(np.asarray(dx), want, rtol=1e-5, atol=1e-6)


def test_max_pool_backward_on_ties_picks_one_winner():
    """The stated deviation from the reference's unpool (PARITY.md): on a
    tie select-and-scatter gives the window's gradient to one input, not
    to every tied one; the gradient's sum is the same."""
    ones = jnp.ones((1, 1, 4, 4), jnp.float32)
    dx = np.asarray(jax.grad(lambda x_: jnp.sum(
        ops.pool2d(x_, "max", (2, 2), 2)))(ones))
    assert dx.sum() == 4.0
    for i in (0, 2):
        for j in (0, 2):
            win = dx[0, 0, i:i + 2, j:j + 2]
            assert sorted(win.ravel()) == [0.0, 0.0, 0.0, 1.0]


def test_relu_max_pooling_fused():
    lay = L.ReluMaxPoolingLayer()
    lay.set_param("kernel_size", "2")
    lay.set_param("stride", "2")
    x = rand((2, 3, 6, 6), seed=3)
    y = lay.apply({}, [jnp.asarray(x)], ctx())[0]
    expect = _np_pool(np.maximum(x, 0), "max", 2, 2)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-6)


# ---------------------------------------------------------------------------
# lrn / batchnorm
# ---------------------------------------------------------------------------
def test_lrn_matches_numpy():
    lay = L.LRNLayer()
    lay.set_param("local_size", "5")
    lay.set_param("alpha", "0.001")
    lay.set_param("beta", "0.75")
    lay.set_param("knorm", "1.0")
    x = rand((2, 8, 4, 4), seed=4)
    y = lay.apply({}, [jnp.asarray(x)], ctx())[0]

    # numpy reference: chpool window [c - n//2, c - n//2 + n)
    n, ch, h, w = x.shape
    salpha = 0.001 / 5
    norm = np.zeros_like(x)
    for c in range(ch):
        lo, hi = max(0, c - 2), min(ch, c + 3)
        norm[:, c] = (x[:, lo:hi] ** 2).sum(axis=1) * salpha + 1.0
    expect = x * norm ** -0.75
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-5, atol=1e-6)


def test_batch_norm_conv_mode():
    lay = L.BatchNormLayer()
    lay.infer_shape([(8, 4, 5, 5)])
    params = lay.init_params(np.random.RandomState(0))
    x = rand((8, 4, 5, 5), seed=5)
    y = lay.apply(params, [jnp.asarray(x)], ctx(train=True))[0]
    mu = x.mean(axis=(0, 2, 3), keepdims=True)
    var = x.var(axis=(0, 2, 3), keepdims=True)
    expect = (x - mu) / np.sqrt(var + 1e-10)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4, atol=1e-5)
    # eval mode recomputes batch stats (reference quirk)
    y_eval = lay.apply(params, [jnp.asarray(x)], ctx(train=False))[0]
    np.testing.assert_allclose(np.asarray(y_eval), expect, rtol=1e-4, atol=1e-5)


def test_batch_norm_fc_mode():
    lay = L.BatchNormLayer()
    lay.infer_shape([(8, 1, 1, 10)])
    params = lay.init_params(np.random.RandomState(0))
    x = rand((8, 1, 1, 10), seed=6)
    y = lay.apply(params, [jnp.asarray(x)], ctx(train=True))[0]
    mu = x.mean(axis=0, keepdims=True)
    var = x.var(axis=0, keepdims=True)
    expect = (x - mu) / np.sqrt(var + 1e-10)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# activations & misc
# ---------------------------------------------------------------------------
def test_xelu_divides_negative():
    lay = L.XeluLayer()
    lay.set_param("b", "4.0")
    x = np.array([[-8.0, 2.0]], np.float32).reshape(1, 1, 1, 2)
    y = lay.apply({}, [jnp.asarray(x)], ctx())[0]
    np.testing.assert_allclose(np.asarray(y).ravel(), [-2.0, 2.0])


def test_insanity_eval_uses_mean_slope():
    lay = L.InsanityLayer()
    lay.set_param("lb", "2")
    lay.set_param("ub", "6")
    x = np.array([[-8.0, 8.0]], np.float32).reshape(1, 1, 1, 2)
    y = lay.apply({}, [jnp.asarray(x)], ctx(train=False))[0]
    np.testing.assert_allclose(np.asarray(y).ravel(), [-2.0, 8.0])


def test_insanity_train_bounds():
    lay = L.InsanityLayer()
    lay.set_param("lb", "2")
    lay.set_param("ub", "6")
    lay.infer_shape([(4, 1, 1, 100)])
    x = -np.ones((4, 1, 1, 100), np.float32)
    y = np.asarray(lay.apply({}, [jnp.asarray(x)], ctx(train=True))[0])
    assert (y <= -1.0 / 6 + 1e-6).all() and (y >= -1.0 / 2 - 1e-6).all()


def test_prelu_forward():
    lay = L.PReluLayer()
    lay.infer_shape([(2, 3, 4, 4)])
    params = lay.init_params(np.random.RandomState(0))
    x = rand((2, 3, 4, 4), seed=7)
    y = lay.apply(params, [jnp.asarray(x)], ctx())[0]
    expect = np.where(x > 0, x, x * 0.25)
    np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-6)


def test_dropout_train_and_eval():
    lay = L.DropoutLayer()
    lay.set_param("threshold", "0.5")
    lay.infer_shape([(2, 1, 1, 1000)])
    x = np.ones((2, 1, 1, 1000), np.float32)
    y_eval = lay.apply({}, [jnp.asarray(x)], ctx(train=False))[0]
    np.testing.assert_array_equal(np.asarray(y_eval), x)
    y = np.asarray(lay.apply({}, [jnp.asarray(x)], ctx(train=True))[0])
    assert set(np.unique(y)).issubset({0.0, 2.0})
    assert abs((y == 2.0).mean() - 0.5) < 0.08


def test_flatten_concat_split():
    fl = L.FlattenLayer()
    assert fl.infer_shape([(2, 3, 4, 5)]) == [(2, 1, 1, 60)]
    x = rand((2, 3, 4, 5))
    y = fl.apply({}, [jnp.asarray(x)], ctx())[0]
    np.testing.assert_array_equal(np.asarray(y).ravel(), x.ravel())

    cc = L.ChConcatLayer()
    assert cc.infer_shape([(2, 3, 4, 4), (2, 5, 4, 4)]) == [(2, 8, 4, 4)]
    sp = L.SplitLayer()
    sp.n_out = 3
    outs = sp.infer_shape([(2, 3, 4, 4)])
    assert len(outs) == 3


def test_maxout():
    lay = L.MaxoutLayer()
    lay.set_param("ngroup", "2")
    assert lay.infer_shape([(2, 1, 1, 6)]) == [(2, 1, 1, 3)]
    x = np.arange(6, dtype=np.float32).reshape(1, 1, 1, 6)
    y = lay.apply({}, [jnp.asarray(np.concatenate([x, x]))], ctx())[0]
    np.testing.assert_allclose(np.asarray(y)[0].ravel(), [1, 3, 5])


def test_insanity_pooling_eval_is_maxpool():
    lay = L.InsanityPoolingLayer()
    lay.set_param("kernel_size", "2")
    lay.set_param("stride", "2")
    x = rand((2, 3, 6, 6), seed=8)
    y = lay.apply({}, [jnp.asarray(x)], ctx(train=False))[0]
    np.testing.assert_allclose(np.asarray(y), _np_pool(x, "max", 2, 2), rtol=1e-6)


def test_insanity_pooling_train_bounded():
    lay = L.InsanityPoolingLayer()
    lay.set_param("kernel_size", "2")
    lay.set_param("stride", "2")
    x = rand((2, 3, 6, 6), seed=9)
    y = np.asarray(lay.apply({}, [jnp.asarray(x)], ctx(train=True))[0])
    assert y.max() <= x.max() + 1e-6


# ---------------------------------------------------------------------------
# loss layers
# ---------------------------------------------------------------------------
def test_softmax_loss_grad_matches_reference():
    lay = L.SoftmaxLayer()
    lay.set_param("batch_size", "4")
    x = rand((4, 1, 1, 3), seed=10)
    labels = np.array([[0.0], [2.0], [1.0], [2.0]], np.float32)
    c = ctx()
    c.labels = LabelInfo({"label": jnp.asarray(labels)})

    # forward output is softmax
    y = lay.apply({}, [jnp.asarray(x)], c)[0]
    p = np.exp(x.reshape(4, 3) - x.reshape(4, 3).max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(y).reshape(4, 3), p, rtol=1e-5)

    # grad of the registered loss wrt logits == (p - onehot)/batch
    def f(xx):
        cc = ctx()
        cc.labels = LabelInfo({"label": jnp.asarray(labels)})
        lay.apply({}, [xx], cc)
        return cc.losses[0]

    g = np.asarray(jax.grad(f)(jnp.asarray(x))).reshape(4, 3)
    onehot = np.eye(3, dtype=np.float32)[labels[:, 0].astype(int)]
    np.testing.assert_allclose(g, (p - onehot) / 4.0, rtol=1e-4, atol=1e-6)


def test_l2_loss_grad():
    lay = L.L2LossLayer()
    lay.set_param("batch_size", "2")
    x = rand((2, 1, 1, 3), seed=11)
    labels = rand((2, 3), seed=12)

    def f(xx):
        cc = ctx()
        cc.labels = LabelInfo({"label": jnp.asarray(labels)})
        lay.apply({}, [xx], cc)
        return cc.losses[0]

    g = np.asarray(jax.grad(f)(jnp.asarray(x))).reshape(2, 3)
    np.testing.assert_allclose(g, (x.reshape(2, 3) - labels) / 2.0, rtol=1e-5)


def test_multi_logistic_grad():
    lay = L.MultiLogisticLayer()
    lay.set_param("batch_size", "2")
    x = rand((2, 1, 1, 3), seed=13)
    labels = (rand((2, 3), seed=14) > 0).astype(np.float32)

    def f(xx):
        cc = ctx()
        cc.labels = LabelInfo({"label": jnp.asarray(labels)})
        lay.apply({}, [xx], cc)
        return cc.losses[0]

    g = np.asarray(jax.grad(f)(jnp.asarray(x))).reshape(2, 3)
    sig = 1 / (1 + np.exp(-x.reshape(2, 3)))
    np.testing.assert_allclose(g, (sig - labels) / 2.0, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------
def test_factory_type_ids():
    assert factory.get_layer_type("fullc") == 1
    assert factory.get_layer_type("softmax") == 2
    assert factory.get_layer_type("share:fc1") == 0
    assert factory.get_layer_type("pairtest-conv-conv") == 1024 * 10 + 10


def test_factory_creates_all_known_types():
    for name, tid in factory._NAME2TYPE.items():
        lay = factory.create_layer(tid)
        assert lay is not None


def test_pairtest_layer_runs():
    pt = factory.create_layer(factory.get_layer_type("pairtest-relu-relu"))
    x = rand((2, 1, 1, 4))
    c = ctx()
    y = pt.apply({}, [jnp.asarray(x)], c)[0]
    np.testing.assert_allclose(np.asarray(y), np.maximum(x, 0))
    assert float(c.pairtest_diffs[0]) < 1e-5


def test_softmax_label_smoothing():
    """label_smooth=eps: loss equals (1-eps)*CE + eps*uniform-CE, and the
    logit gradient is p - ((1-eps)*onehot + eps/K)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from cxxnet_tpu.layer import factory
    from cxxnet_tpu.layer.base import ApplyContext, LabelInfo

    rs = np.random.RandomState(0)
    logits = rs.randn(4, 5).astype(np.float32)
    y = rs.randint(0, 5, (4, 1)).astype(np.float32)
    eps = 0.1

    lay = factory.create_layer(factory.get_layer_type("softmax"))
    lay.set_param("label_smooth", str(eps))
    lay.set_param("batch_size", "4")
    lay.infer_shape([(4, 1, 1, 5)])

    def loss(x):
        ctx = ApplyContext(train=True, labels=LabelInfo({"label": jnp.asarray(y)}))
        lay.apply({}, [x.reshape(4, 1, 1, 5)], ctx)
        return sum(ctx.losses)

    g = jax.grad(loss)(jnp.asarray(logits)).reshape(4, 5)
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    smoothed = np.full((4, 5), eps / 5, np.float32)
    smoothed[np.arange(4), y[:, 0].astype(int)] += 1 - eps
    # loss layers scale by grad_scale/batch (=1/4 here)
    np.testing.assert_allclose(np.asarray(g), (p - smoothed) / 4,
                               rtol=1e-5, atol=1e-6)
