"""Sibling-conv fusion pass (nnet/net.py _sibling_conv_plan).

Inception-style modules issue several narrow 1x1 convs over the same split
value; the fusion pass runs them as one wider conv. These tests pin (a) the
plan on GoogLeNet-shaped nets, (b) numerical equality of forward and grads
vs the unfused net, and (c) the safety cut when a self-loop layer mutates a
member's input node between siblings.
"""

import numpy as np
import jax
import jax.numpy as jnp

from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string

HEAD = """
netconfig=start
layer[0->s] = conv:stem
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[s->sa,sb,sc,sd] = split
layer[sa->a1] = conv:b1
  kernel_size = 1
  nchannel = 4
layer[a1->a2] = relu
layer[sb->b1] = conv:b3r
  kernel_size = 1
  nchannel = 6
layer[b1->b2] = relu
layer[b2->b3] = conv:b3
  kernel_size = 3
  pad = 1
  nchannel = 8
layer[b3->b4] = relu
"""

TAIL = """
layer[sc->c1] = conv:c5r
  kernel_size = 1
  nchannel = 3
layer[c1->c2] = relu
layer[c2->c3] = conv:c5
  kernel_size = 5
  pad = 2
  nchannel = 4
layer[c3->c4] = relu
layer[sd->d1] = max_pooling
  kernel_size = 3
  stride = 1
  pad = 1
layer[d1->d2] = conv:dproj
  kernel_size = 1
  nchannel = 4
layer[d2->d3] = relu
layer[a2,b4,c4,d3->cc] = ch_concat
layer[cc->gp] = avg_pooling
  kernel_size = 4
  stride = 4
layer[gp->fl] = flatten
layer[fl->out] = fullc:head
  nhidden = 5
layer[+0] = softmax
netconfig=end
random_type = xavier
metric = error
input_shape = 3,8,8
batch_size = 4
dev = cpu
eta = 0.05
"""

MODULE_CONF = HEAD + TAIL
# same module but with a self-loop relu rewriting node sc between the
# sibling 1x1 convs — the plan must cut the group before conv:c5r
MUTATED_CONF = HEAD + "layer[sc->sc] = relu\n" + TAIL


def _trainer(conf, extra=""):
    tr = Trainer()
    for k, v in parse_config_string(conf + extra):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _conv_indices(tr, names):
    by_name = {}
    for i, info in enumerate(tr.net_cfg.layers):
        by_name[info.name] = i
    return [by_name[n] for n in names]


def _loss_and_grads(tr, x, y):
    li = tr.net.label_info_from(y)

    def loss_fn(params):
        _, loss = tr.net.forward(params, x, labels=li, train=True,
                                 rng=jax.random.PRNGKey(7))
        return loss

    loss, grads = jax.value_and_grad(loss_fn)(tr.params)
    return loss, grads


def _assert_matches_unfused(conf, seed=3):
    tr1 = _trainer(conf)
    tr0 = _trainer(conf, "fuse_sibling_convs = 0\n")
    rs = np.random.RandomState(seed)
    x = rs.rand(4, 3, 8, 8).astype(np.float32)
    y = rs.randint(0, 5, (4, 1)).astype(np.float32)
    l1, g1 = _loss_and_grads(tr1, x, y)
    l0, g0 = _loss_and_grads(tr0, x, y)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat0 = jax.tree_util.tree_leaves(g0)
    assert len(flat1) == len(flat0)
    for a, b in zip(flat1, flat0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_plan_groups_sibling_1x1s():
    tr = _trainer(MODULE_CONF)
    plan = tr.net._sibling_conv_plan()
    assert len(plan) == 1
    (group,) = plan.values()
    assert group == _conv_indices(tr, ["b1", "b3r", "c5r"])


def test_plan_disabled_by_key():
    tr = _trainer(MODULE_CONF, "fuse_sibling_convs = 0\n")
    assert tr.net._sibling_conv_plan() == {}


def test_fused_matches_unfused_forward_and_grads():
    _assert_matches_unfused(MODULE_CONF, seed=0)


def test_self_loop_mutation_cuts_group():
    tr = _trainer(MUTATED_CONF)
    plan = tr.net._sibling_conv_plan()
    assert len(plan) == 1
    (group,) = plan.values()
    # conv:c5r reads sc AFTER the self-loop relu rewrote it; fusing it with
    # the pre-mutation siblings would read the stale value
    assert group == _conv_indices(tr, ["b1", "b3r"])
    _assert_matches_unfused(MUTATED_CONF, seed=1)


def test_mutation_before_leader_excludes_member():
    """A self-loop rewriting a split-aliased input node BEFORE the leader
    must exclude that member (it reads the mutated value; the leader's
    input holds the pre-split copy)."""
    conf = HEAD.replace(
        "layer[sb->b1] = conv:b3r",
        "layer[sb->sb] = relu\nlayer[sb->b1] = conv:b3r") + TAIL
    tr = _trainer(conf)
    plan = tr.net._sibling_conv_plan()
    assert len(plan) == 1
    (group,) = plan.values()
    assert group == _conv_indices(tr, ["b1", "c5r"])
    _assert_matches_unfused(conf)


def test_self_loop_conv_never_fuses():
    """A conv that rewrites its own input node (layer[s->s]) is both a
    writer and a reader of s; fusing it with another conv over s would
    feed the sibling the pre-rewrite value."""
    conf = """
netconfig=start
layer[0->s] = conv:stem
  kernel_size = 1
  nchannel = 3
layer[s->s] = conv:selfloop
  kernel_size = 1
  nchannel = 3
layer[s->y] = conv:other
  kernel_size = 1
  nchannel = 4
layer[y->fl] = flatten
layer[fl->out] = fullc:head
  nhidden = 5
layer[+0] = softmax
netconfig=end
random_type = xavier
metric = error
input_shape = 3,8,8
batch_size = 4
dev = cpu
eta = 0.05
"""
    tr = _trainer(conf)
    assert tr.net._sibling_conv_plan() == {}
    _assert_matches_unfused(conf)


def test_input_node_self_loop_is_mutable():
    """Graph inputs carry an implicit writer: a self-loop on node 0 makes
    it two-writer, so convs reading node 0 refuse to fuse."""
    conf = """
netconfig=start
layer[0->0] = relu
layer[0->a] = conv:ca
  kernel_size = 1
  nchannel = 3
layer[0->b] = conv:cb
  kernel_size = 1
  nchannel = 3
layer[a,b->cc] = ch_concat
layer[cc->fl] = flatten
layer[fl->out] = fullc:head
  nhidden = 5
layer[+0] = softmax
netconfig=end
random_type = xavier
metric = error
input_shape = 3,8,8
batch_size = 4
dev = cpu
eta = 0.05
"""
    tr = _trainer(conf)
    assert tr.net._sibling_conv_plan() == {}
    _assert_matches_unfused(conf)


def test_googlenet_plan_has_nine_modules():
    from cxxnet_tpu.models import googlenet_trainer
    tr = googlenet_trainer(batch_size=2, dev="cpu")
    plan = tr.net._sibling_conv_plan()
    groups = list(plan.values())
    assert len(groups) == 9
    assert all(len(g) == 3 for g in groups)


def test_training_equivalence_over_steps():
    """Five SGD steps fused vs unfused stay numerically together."""
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(2)
    x = rs.rand(4, 3, 8, 8).astype(np.float32)
    y = rs.randint(0, 5, (4, 1)).astype(np.float32)
    outs = []
    for extra in ("", "fuse_sibling_convs = 0\n"):
        tr = _trainer(MODULE_CONF, extra)
        b = DataBatch()
        b.data, b.label, b.batch_size = x, y, 4
        for _ in range(5):
            tr.update(b)
        outs.append([np.asarray(jax.device_get(v))
                     for v in jax.tree_util.tree_leaves(tr.params)])
    assert len(outs[0]) == len(outs[1])
    for a, b_ in zip(outs[0], outs[1]):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-5)


def test_fusion_on_data_parallel_mesh():
    """Fused sibling convs under dev=cpu:0-7 (replicated weights, sharded
    batch) train and match the single-device loss trajectory."""
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(4)
    x = rs.rand(8, 3, 8, 8).astype(np.float32)
    y = rs.randint(0, 5, (8, 1)).astype(np.float32)
    losses = []
    for dev in ("cpu", "cpu:0-7"):
        tr = _trainer(MODULE_CONF.replace("batch_size = 4",
                                          "batch_size = 8")
                      .replace("dev = cpu", "dev = %s" % dev))
        assert tr.net._sibling_conv_plan()
        b = DataBatch()
        b.data, b.label, b.batch_size = x, y, 8
        for _ in range(3):
            tr.update(b)
        li = tr.net.label_info_from(y)
        _, loss = tr.net.forward(tr.params, x, labels=li, train=False)
        losses.append(float(loss))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)
