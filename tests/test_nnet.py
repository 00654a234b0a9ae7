"""NetConfig DSL + NeuralNet + updater tests."""

import numpy as np
import jax.numpy as jnp
import pytest

from cxxnet_tpu.nnet.config import NetConfig
from cxxnet_tpu.nnet.net import NeuralNet
from cxxnet_tpu.updater import create_updater, encode_data_key, decode_tag
from cxxnet_tpu.utils import serializer
from cxxnet_tpu.utils.config import parse_config_string


MLP_CONF = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 100
  init_sigma = 0.01
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 10
  init_sigma = 0.01
layer[+0] = softmax
netconfig=end
input_shape = 1,1,784
batch_size = 100
"""


def make_cfg(text):
    cfg = NetConfig()
    cfg.configure(parse_config_string(text))
    return cfg


def test_netconfig_mlp_structure():
    cfg = make_cfg(MLP_CONF)
    assert cfg.node_names == ["in", "fc1", "sg1", "fc2"]
    assert cfg.param.num_nodes == 4
    assert cfg.param.num_layers == 4
    assert cfg.param.input_shape == (1, 1, 784)
    # layer[+0] softmax is a self-loop on the top node
    assert cfg.layers[3].nindex_in == [3] and cfg.layers[3].nindex_out == [3]
    # layer name map has the named layers
    assert cfg.layer_name_map["fc1"] == 0
    assert cfg.layer_name_map["fc2"] == 2
    # per-layer config captured
    assert ("nhidden", "100") in cfg.layercfg[0]
    assert ("nhidden", "10") in cfg.layercfg[2]
    # global keys in defcfg
    assert ("batch_size", "100") in cfg.defcfg


def test_netconfig_conv_numeric_nodes():
    cfg = make_cfg("""
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  stride = 2
  pad = 1
  nchannel = 32
layer[1->2] = max_pooling
  kernel_size = 3
  stride = 2
layer[2->3] = flatten
layer[3->3] = dropout
layer[3->4] = fullc
  nhidden = 10
layer[4->4] = softmax
netconfig=end
input_shape = 1,28,28
""")
    assert cfg.param.num_nodes == 5
    assert cfg.layers[0].nindex_in == [0] and cfg.layers[0].nindex_out == [1]
    net = NeuralNet(cfg, 16)
    assert net.node_shapes[1] == (16, 32, 14, 14)
    assert net.node_shapes[2] == (16, 32, 7, 7)
    assert net.node_shapes[3] == (16, 1, 1, 32 * 49)
    assert net.node_shapes[4] == (16, 1, 1, 10)


def test_netconfig_shared_layer():
    cfg = make_cfg("""
netconfig=start
layer[+1:h1] = fullc:shared_fc
  nhidden = 8
layer[+1:h2] = relu
layer[h2->h3] = share[shared_fc]
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
""")
    assert cfg.layers[2].primary_layer_index == 0
    net = NeuralNet(cfg, 4)
    params = net.init_params(0)
    assert params[2] == {}  # shared layer holds no params
    values, _ = net.forward(params, np.zeros((4, 1, 1, 8), np.float32))
    assert values[3].shape == (4, 1, 1, 8)


def test_netconfig_save_load_roundtrip():
    cfg = make_cfg(MLP_CONF)
    w = serializer.Writer()
    cfg.save_net(w)
    blob = w.getvalue()
    cfg2 = NetConfig()
    cfg2.load_net(serializer.Reader(blob))
    assert cfg2.node_names == cfg.node_names
    assert len(cfg2.layers) == len(cfg.layers)
    for a, b in zip(cfg.layers, cfg2.layers):
        assert a == b
    assert cfg2.param.input_shape == cfg.param.input_shape


def test_netconfig_label_vec():
    cfg = make_cfg("label_vec[1,4) = extra_label\n" + MLP_CONF)
    assert cfg.label_name_map == {"label": 0, "extra_label": 1}
    assert cfg.label_range == [(0, 1), (1, 4)]


def test_netconfig_split_concat():
    cfg = make_cfg("""
netconfig=start
layer[0->1,2] = split
layer[1->3] = fullc:a
  nhidden = 4
layer[2->4] = fullc:b
  nhidden = 6
layer[3,4->5] = concat
layer[+0] = softmax
netconfig=end
input_shape = 1,1,8
""")
    net = NeuralNet(cfg, 2)
    assert net.node_shapes[5] == (2, 1, 1, 10)
    params = net.init_params(0)
    values, _ = net.forward(params, np.ones((2, 1, 1, 8), np.float32))
    assert values[5].shape == (2, 1, 1, 10)


def test_netconfig_undefined_node_raises():
    with pytest.raises(ValueError):
        make_cfg("""
netconfig=start
layer[bogus->1] = fullc
  nhidden = 4
netconfig=end
""")


# ---------------------------------------------------------------------------
# updaters
# ---------------------------------------------------------------------------
def test_data_key_encoding():
    assert encode_data_key(3, "wmat") == 12
    assert encode_data_key(3, "bias") == 13
    assert decode_tag(12) == "wmat"
    assert decode_tag(13) == "bias"


def test_sgd_matches_reference_formula():
    up = create_updater("sgd", "wmat")
    up.set_param("eta", "0.1")
    up.set_param("momentum", "0.9")
    up.set_param("wd", "0.01")
    w = np.ones((3, 3), np.float32)
    g = np.full((3, 3), 0.5, np.float32)
    st = up.init_state(w)
    w1, st1 = up.apply(jnp.asarray(w), jnp.asarray(g), st, 0)
    # m = 0.9*0 - 0.1*(0.5 + 0.01*1) = -0.051 ; w = 1 - 0.051
    np.testing.assert_allclose(np.asarray(w1), 1 - 0.051, rtol=1e-6)
    w2, _ = up.apply(w1, jnp.asarray(g), st1, 1)
    m2 = 0.9 * (-0.051) - 0.1 * (0.5 + 0.01 * float(np.asarray(w1)[0, 0]))
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w1) + m2, rtol=1e-6)


def test_sgd_clip_zeroes_nan():
    up = create_updater("sgd", "wmat")
    up.set_param("eta", "1.0")
    up.set_param("momentum", "0.0")
    up.set_param("clip_gradient", "0.25")
    w = np.zeros((3,), np.float32)
    g = np.array([np.nan, 10.0, -10.0], np.float32)
    w1, _ = up.apply(jnp.asarray(w), jnp.asarray(g), up.init_state(w), 0)
    np.testing.assert_allclose(np.asarray(w1), [0.0, -0.25, 0.25], rtol=1e-6)


def test_nag_update():
    up = create_updater("nag", "wmat")
    up.set_param("eta", "0.1")
    up.set_param("momentum", "0.9")
    w = np.ones((2,), np.float32)
    g = np.full((2,), 1.0, np.float32)
    st = up.init_state(w)
    w1, st1 = up.apply(jnp.asarray(w), jnp.asarray(g), st, 0)
    # old_m=0; m = -0.1; w += 1.9*m - 0.9*0 = 1 - 0.19
    np.testing.assert_allclose(np.asarray(w1), 0.81, rtol=1e-6)


def test_adam_reference_semantics():
    up = create_updater("adam", "wmat")
    up.set_param("eta", "0.001")
    w = np.ones((2,), np.float32)
    g = np.full((2,), 2.0, np.float32)
    st = up.init_state(w)
    w1, st1 = up.apply(jnp.asarray(w), jnp.asarray(g), st, 0)
    fix1 = 1 - 0.9 ** 1
    fix2 = 1 - 0.999 ** 1
    lr_t = 0.001 * np.sqrt(fix2) / fix1
    m1 = 0.1 * 2.0
    m2 = 0.001 * 4.0
    expect = 1 - lr_t * (m1 / (np.sqrt(m2) + 1e-8))
    np.testing.assert_allclose(np.asarray(w1), expect, rtol=1e-5)


def test_adamw_decoupled_decay():
    up = create_updater("adamw", "wmat")
    up.set_param("eta", "0.01")
    up.set_param("wd", "0.1")
    w = np.ones((2,), np.float32)
    g = np.full((2,), 2.0, np.float32)
    st = up.init_state(w)
    w1, st1 = up.apply(jnp.asarray(w), jnp.asarray(g), st, 0)
    # standard AdamW: m=0.1*2, v=0.001*4, bias-corrected; wd scales w
    # directly (decoupled), NOT folded into the gradient like 'adam'
    mhat = (0.1 * 2.0) / (1 - 0.9)
    vhat = (0.001 * 4.0) / (1 - 0.999)
    expect = 1 - 0.01 * (mhat / (np.sqrt(vhat) + 1e-8) + 0.1 * 1.0)
    np.testing.assert_allclose(np.asarray(w1), expect, rtol=1e-5)
    # second step exercises the state carry
    w2, _ = up.apply(w1, jnp.asarray(g), st1, 1)
    assert np.all(np.asarray(w2) < np.asarray(w1))


def test_adamw_e2e_trains():
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.io.data import DataBatch
    conf = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 1,1,8
batch_size = 16
updater = adamw
eta = 0.01
wd = 0.01
dev = cpu
"""
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = rs.rand(16, 1, 1, 8).astype(np.float32)
    b.label = rs.randint(0, 4, (16, 1)).astype(np.float32)
    b.batch_size = 16
    for _ in range(60):
        tr.update(b)
    pred = tr.predict(b)
    acc = float(np.mean(pred == b.label[:, 0]))
    assert acc >= 0.9, acc


def test_tag_scoped_optimizer_keys():
    """'wmat:beta1' must reach the adam-family updaters with the tag
    stripped (regression: subclasses compared the raw key)."""
    up = create_updater("adamw", "wmat")
    up.set_param("wmat:beta1", "0.95")
    up.set_param("bias:beta2", "0.5")    # other tag: ignored
    assert up.beta1 == 0.95
    assert up.beta2 == 0.999
    up2 = create_updater("adam", "bias")
    up2.set_param("bias:beta1", "0.2")
    assert up2.decay1 == 0.2


def test_small_lr_not_clamped_up():
    """eta below the 1e-5 default lr_minimum is honored exactly — the
    floor never raises lr above the requested base (regression: 3e-6
    silently became 1e-5)."""
    up = create_updater("sgd", "wmat")
    up.set_param("eta", "3e-6")
    up.set_param("momentum", "0.0")
    lr, _ = up.param.schedule_epoch(0)
    np.testing.assert_allclose(float(lr), 3e-6, rtol=1e-6)


def test_lr_schedules():
    up = create_updater("sgd", "wmat")
    up.set_param("eta", "0.1")
    up.set_param("lr:schedule", "expdecay")
    up.set_param("lr:gamma", "0.1")
    up.set_param("lr:step", "100")
    lr, _ = up.param.schedule_epoch(0)
    np.testing.assert_allclose(float(lr), 0.1, rtol=1e-6)
    lr, _ = up.param.schedule_epoch(100)
    np.testing.assert_allclose(float(lr), 0.01, rtol=1e-5)
    lr, _ = up.param.schedule_epoch(10000)
    np.testing.assert_allclose(float(lr), 1e-5, rtol=1e-4)  # clamped to minimum


def test_cosine_schedule_with_warmup():
    up = create_updater("sgd", "wmat")
    up.set_param("eta", "0.1")
    up.set_param("lr:schedule", "cosine")
    up.set_param("lr:total", "1000")
    up.set_param("lr:minimum_lr", "0.001")
    up.set_param("lr:warmup", "10")
    lr0, _ = up.param.schedule_epoch(0)          # first warmup step: lr/10
    np.testing.assert_allclose(float(lr0), 0.1 * (1 / 10.0), rtol=1e-5)
    lr_mid, _ = up.param.schedule_epoch(500)     # cosine midpoint
    np.testing.assert_allclose(float(lr_mid), (0.1 + 0.001) / 2, rtol=1e-4)
    lr_end, _ = up.param.schedule_epoch(1000)    # floor at lr:minimum_lr
    np.testing.assert_allclose(float(lr_end), 0.001, rtol=1e-4)
    lr_past, _ = up.param.schedule_epoch(5000)   # clamped past the horizon
    np.testing.assert_allclose(float(lr_past), 0.001, rtol=1e-4)


def test_tag_scoped_params():
    up_w = create_updater("sgd", "wmat")
    up_b = create_updater("sgd", "bias")
    for up in (up_w, up_b):
        up.set_param("eta", "0.1")
        up.set_param("wmat:lr", "0.5")
        up.set_param("bias:wd", "0.25")
    assert up_w.param.base_lr == 0.5
    assert up_b.param.base_lr == 0.1
    assert up_w.param.wd == 0.0
    assert up_b.param.wd == 0.25


@pytest.mark.slow
def test_inception_dag_memorizes():
    """GoogLeNet-flavored DAG (split -> parallel conv towers -> ch_concat)
    built purely from the netconfig DSL trains to memorization.
    Slow tier: a ~50s convergence soak — the DAG build/step/fusion
    coverage rides tier-1 via test_fusion and the example-config
    smokes; this adds only the memorization endpoint."""
    import numpy as np
    from cxxnet_tpu.models import inception_trainer
    from cxxnet_tpu.io.data import DataBatch

    tr = inception_trainer()
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = rs.rand(16, 3, 16, 16).astype(np.float32)
    b.label = rs.randint(0, 10, (16, 1)).astype(np.float32)
    b.batch_size = 16
    for _ in range(400):
        tr.update(b)
    assert (tr.predict(b) == b.label[:, 0]).mean() == 1.0
