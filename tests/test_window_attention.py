"""Sliding-window (local) attention — `attn_window` on the attention layer,
`window=` on every attention path (dense reference, single-chip flash,
XLA ring, flash ring, ulysses). Causal-only by contract.
"""


import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from cxxnet_tpu import ops
from cxxnet_tpu.parallel import ring

W = 96  # window under one tile (exercises partial masks)


def _qkv(b=1, h=2, s=512, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: rs.randn(b, h, s, d).astype(np.float32)
    return mk(), mk(), mk()


def _manual_window(q, k, v, window):
    s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    L = q.shape[2]
    qpos = np.arange(L)[:, None]
    kpos = np.arange(L)[None, :]
    keep = (qpos >= kpos) & (qpos - kpos < window)
    s_ = jnp.where(jnp.asarray(keep), s_, -jnp.inf)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_reference_window():
    q, k, v = _qkv(seed=1)
    out = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_manual_window(q, k, v, W)),
                               rtol=1e-5, atol=1e-6)


def test_flash_window_matches_reference():
    q, k, v = _qkv(seed=2)
    out = ops.flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=True, window=W)
    ref = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_window_grads():
    q, k, v = _qkv(seed=3)
    w = np.random.RandomState(7).randn(*q.shape).astype(np.float32)
    gf = jax.grad(lambda q_: jnp.sum(ops.flash_attention(
        q_, k, v, causal=True, window=W) * w))(jnp.asarray(q))
    gr = jax.grad(lambda q_: jnp.sum(ring.attention_reference(
        q_, k, v, causal=True, window=W) * w))(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def test_ring_xla_window():
    q, k, v = _qkv(seed=4)
    out = ring.ring_attention(q, k, v, _mesh(), causal=True, window=W)
    ref = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_window():
    ops.set_use_pallas(True)
    try:
        q, k, v = _qkv(seed=5)
        out = ring.ring_attention(q, k, v, _mesh(), causal=True, window=W)
    finally:
        ops.set_use_pallas(None)
    ref = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_window():
    q, k, v = _qkv(h=8, seed=6)
    out = ring.ulysses_attention(q, k, v, _mesh(), causal=True, window=W)
    ref = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_layer_attn_window_requires_causal():
    from cxxnet_tpu.layer import factory
    lay = factory.create_layer(factory.get_layer_type("attention"))
    lay.set_param("nhead", "2")
    lay.set_param("attn_window", "8")
    with pytest.raises(ValueError):
        lay.infer_shape([(2, 16, 1, 32)])


def test_layer_window_matches_reference():
    from cxxnet_tpu.layer import factory
    from cxxnet_tpu.layer.base import ApplyContext
    d, nh, L, b = 16, 2, 32, 2
    lay = factory.create_layer(factory.get_layer_type("attention"))
    lay.set_param("nhead", str(nh))
    lay.set_param("causal", "1")
    lay.set_param("attn_window", "8")
    lay.infer_shape([(b, d, 1, L)])
    rs = np.random.RandomState(0)
    params = {k_: jnp.asarray(v_)
              for k_, v_ in lay.init_params(rs).items()}
    x = rs.randn(b, d, 1, L).astype(np.float32)
    (out,) = lay.apply(params, [jnp.asarray(x)], ApplyContext(train=False))
    # manual: same weights, windowed reference attention
    dh = d // nh
    seq = x.reshape(b, d, L).transpose(0, 2, 1)
    qkv = np.asarray(seq @ params["wqkv"])
    q, k, v = np.split(qkv, 3, axis=-1)
    hd = lambda t: t.reshape(b, L, nh, dh).transpose(0, 2, 1, 3)
    att = ring.attention_reference(
        jnp.asarray(hd(q)), jnp.asarray(hd(k)), jnp.asarray(hd(v)),
        causal=True, window=8)
    ref = (np.asarray(att).transpose(0, 2, 1, 3).reshape(b, L, d)
           @ np.asarray(params["wo"])).transpose(0, 2, 1).reshape(b, d, 1, L)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_flash_window_with_skipped_tiles():
    """L=768 (three 256-tiles) with window=96: the (q_blk=2, kv_blk=0)
    tile is entirely out of window and must be statically skipped —
    exercises _block_needed's window branch, not just the mask."""
    q, k, v = _qkv(s=768, seed=8)
    out = ops.flash_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=True, window=W)
    ref = ring.attention_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    w = np.random.RandomState(3).randn(*q.shape).astype(np.float32)
    gf = jax.grad(lambda q_: jnp.sum(ops.flash_attention(
        q_, k, v, causal=True, window=W) * w))(jnp.asarray(q))
    gr = jax.grad(lambda q_: jnp.sum(ring.attention_reference(
        q_, k, v, causal=True, window=W) * w))(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_ring_flash_window_with_skipped_blocks():
    """8-device ring at L=1024, window=96: most ring steps hold blocks
    entirely out of window (skipped by the traced tile predicate) and the
    result must still match the dense reference, incl. grads."""
    ops.set_use_pallas(True)
    try:
        q, k, v = _qkv(s=1024, seed=9)
        mesh = _mesh(8)
        out = ring.ring_attention(q, k, v, mesh, causal=True, window=W)
        ref = ring.attention_reference(q, k, v, causal=True, window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        w = np.random.RandomState(4).randn(*q.shape).astype(np.float32)
        gf = jax.grad(lambda q_: jnp.sum(ring.ring_attention(
            q_, k, v, mesh, causal=True, window=W) * w))(jnp.asarray(q))
    finally:
        ops.set_use_pallas(None)
    gr = jax.grad(lambda q_: jnp.sum(ring.attention_reference(
        q_, k, v, causal=True, window=W) * w))(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=3e-4, atol=3e-4)


def test_layer_negative_window_rejected():
    from cxxnet_tpu.layer import factory
    lay = factory.create_layer(factory.get_layer_type("attention"))
    lay.set_param("nhead", "2")
    lay.set_param("causal", "1")
    lay.set_param("attn_window", "-4096")
    with pytest.raises(ValueError):
        lay.infer_shape([(2, 16, 1, 32)])


def test_trainer_sp_window_e2e():
    """DSL attention with attn_window under seq_parallel=2: a train step
    runs and the eval forward matches the single-device windowed net."""
    from cxxnet_tpu.models import transformer_lm_netconfig
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch

    conf = transformer_lm_netconfig(40, dim=32, nhead=4, nlayer=1)
    conf = conf.replace("  causal = 1\n",
                        "  causal = 1\n  attn_window = 16\n")
    base = (conf + "input_shape = 1,1,64\nbatch_size = 4\n"
            "label_vec[0,64) = label\nupdater = adam\neta = 0.003\n"
            "eval_train = 0\n")
    rs = np.random.RandomState(0)
    x = rs.randint(0, 40, (4, 1, 1, 64)).astype(np.float32)
    y = rs.randint(0, 40, (4, 64)).astype(np.float32)
    losses = []
    for dev_extra in ("dev = cpu\n", "dev = cpu:0-1\nseq_parallel = 2\n"):
        tr = Trainer()
        for k_, v_ in parse_config_string(base + dev_extra):
            tr.set_param(k_, v_)
        tr.init_model()
        b = DataBatch()
        b.data, b.label, b.batch_size = x, y, 4
        tr.update(b)
        li = tr.net.label_info_from(y)
        _, loss = tr.net.forward(tr.params, x, labels=li, train=False,
                                 mesh=tr.mesh)
        losses.append(float(loss))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)
