"""The attention layer (long-context path): DSL integration, causal masking,
and sequence parallelism (ring / Ulysses over the mesh "sp" axis) matching
the single-device numerics."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cxxnet_tpu import api

CFG = """
netconfig = start
layer[+1:att1] = attention:att1
  nhead = 4
  causal = %(causal)d
  sp_mode = %(sp_mode)s
  init_sigma = 0.1
layer[+1:ffn] = conv:ffn
  kernel_size = 1
  nchannel = 16
  init_sigma = 0.1
layer[+1] = relu
layer[+1] = flatten
layer[+1:head] = fullc:head
  nhidden = 5
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 16,1,16
batch_size = 8
eta = 0.1
momentum = 0.0
seed = 7
"""


def _data(seed=0):
    rs = np.random.RandomState(seed)
    return (rs.rand(8, 16, 1, 16).astype(np.float32),
            rs.randint(0, 5, 8).astype(np.float32))


def _build(dev, causal=0, sp_mode="ring", extra=""):
    net = api.Net(dev=dev, cfg=CFG % {"causal": causal, "sp_mode": sp_mode}
                  + extra)
    net.init_model()
    return net


def test_attention_net_memorizes():
    x, y = _data()
    net = _build("cpu")
    for _ in range(400):
        net.update(x, y)
    assert (net.predict(x) == y).mean() >= 0.85


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [0, 1])
def test_seq_parallel_matches_single_device(sp_mode, causal):
    """seq_parallel=4 over the virtual mesh must reproduce single-device
    outputs (same seed => same init params)."""
    x, _ = _data(1)
    single = _build("cpu", causal=causal, sp_mode=sp_mode)
    sharded = _build("cpu:0-7", causal=causal, sp_mode=sp_mode,
                     extra="seq_parallel = 4\n")
    assert sharded.net_.mesh is not None
    assert dict(zip(sharded.net_.mesh.axis_names,
                    sharded.net_.mesh.devices.shape)) == {"data": 2, "sp": 4}
    a = np.asarray(single.extract(x, "top[-1]"), np.float32)
    b = np.asarray(sharded.extract(x, "top[-1]"), np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_seq_parallel_trains():
    # same data as the single-device memorize test: the sharded trainer must
    # reach the same fit (seed-2 data happens to be a hard draw at this eta
    # on a single device too, so it is not used here)
    x, y = _data()
    net = _build("cpu:0-7", extra="seq_parallel = 4\n")
    for _ in range(400):
        net.update(x, y)
    assert (net.predict(x) == y).mean() >= 0.85


def test_attention_save_load_and_weight_tags(tmp_path):
    x, _ = _data(3)
    net = _build("cpu")
    p1 = net.extract(x, "top[-1]")
    path = str(tmp_path / "att.model")
    net.save_model(path)
    net2 = api.Net(dev="cpu", cfg=CFG % {"causal": 0, "sp_mode": "ring"})
    net2.load_model(path)
    p2 = net2.extract(x, "top[-1]")
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-5, atol=1e-6)
    # both attention weights reachable through the weight ABI
    wqkv = net.get_weight("att1", "wmat")
    wo = net.get_weight("att1", "wo")
    assert wqkv.shape == (16, 48)
    assert wo.shape == (16, 16)
    net.set_weight(np.zeros_like(wo), "att1", "wo")
    assert np.all(net.get_weight("att1", "wo") == 0)


def test_seq_len_divisibility_error():
    bad = CFG.replace("input_shape = 16,1,16", "input_shape = 16,1,10")
    net = api.Net(dev="cpu:0-7",
                  cfg=bad % {"causal": 0, "sp_mode": "ring"}
                  + "seq_parallel = 4\nbatch_size = 8\n")
    net.init_model()
    x = np.random.RandomState(0).rand(8, 16, 1, 10).astype(np.float32)
    y = np.zeros(8, np.float32)
    with pytest.raises(ValueError, match="divisible by"):
        net.update(x, y)


class TestRoPE:
    def _layer(self, d=16, nhead=2, rope=1):
        from cxxnet_tpu.layer import factory
        lay = factory.create_layer(factory.get_layer_type("attention"))
        lay.set_param("nhead", str(nhead))
        lay.set_param("causal", "0")
        if rope:
            lay.set_param("rope", "1")
        lay.infer_shape([(2, d, 1, 8)])
        return lay

    def test_relative_position_property(self):
        """With identical inputs at every position, rotary scores depend
        only on the offset i-j: the rotation phase cancels absolutely."""
        import numpy as np
        import jax.numpy as jnp
        lay = self._layer()
        x = np.random.RandomState(0).randn(1, 1, 1, 16).astype(np.float32)
        q = jnp.asarray(np.broadcast_to(x, (1, 1, 12, 16)))
        qr = lay._apply_rope(q)
        s = np.asarray(jnp.einsum("bhqd,bhkd->bhqk", qr, qr))[0, 0]
        for off in range(-3, 4):
            diag = np.diagonal(s, offset=off)
            np.testing.assert_allclose(diag, diag[0], rtol=1e-4, atol=1e-5)

    def test_rope_trains_and_saves(self):
        """rope=1 through the DSL: trains, and the checkpoint round-trips
        (no new tensors — rope is positional math, not weights)."""
        import numpy as np
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        from cxxnet_tpu.io.data import DataBatch
        conf = """
netconfig = start
layer[+1:emb] = embed:emb
  vocab_size = 30
  nhidden = 16
  pos_embed = 0
  init_sigma = 0.05
layer[emb->att] = attention:att
  nhead = 2
  causal = 1
  rope = 1
  init_sigma = 0.05
layer[emb,att->res] = add
layer[res->logits] = conv:head
  kernel_size = 1
  nchannel = 30
  init_sigma = 0.05
layer[+0] = softmax
  seq = 1
netconfig = end
input_shape = 1,1,8
batch_size = 4
label_vec[0,8) = label
updater = adam
eta = 0.01
dev = cpu
metric = error
"""
        tr = Trainer()
        for k, v in parse_config_string(conf):
            tr.set_param(k, v)
        tr.init_model()
        rs = np.random.RandomState(0)
        b = DataBatch()
        b.data = rs.randint(0, 30, (4, 1, 1, 8)).astype(np.float32)
        b.label = rs.randint(0, 30, (4, 8)).astype(np.float32)
        b.batch_size = 4
        losses = []
        for _ in range(30):
            tr.update(b)
        li = tr.net.label_info_from(b.label)
        _, loss = tr.net.forward(tr.params, b.data, labels=li, train=False)
        assert float(loss) < 3.0   # learned something vs ~log(30)=3.4


class TestGQA:
    def test_mqa_matches_manual_reference(self):
        """nkvhead=1 (multi-query): layer output equals dense reference
        attention with the single k/v head broadcast to all query heads."""
        import numpy as np
        import jax.numpy as jnp
        from cxxnet_tpu.layer import factory
        from cxxnet_tpu.layer.base import ApplyContext
        from cxxnet_tpu.parallel import attention_reference

        d, nh, L, b = 16, 4, 8, 2
        dh = d // nh
        lay = factory.create_layer(factory.get_layer_type("attention"))
        lay.set_param("nhead", str(nh))
        lay.set_param("nkvhead", "1")
        lay.set_param("causal", "1")
        lay.infer_shape([(b, d, 1, L)])
        rs = np.random.RandomState(0)
        params = lay.init_params(rs)
        assert params["wqkv"].shape == (d, d + 2 * dh)
        x = rs.randn(b, d, 1, L).astype(np.float32)
        (out,) = lay.apply({k: jnp.asarray(v) for k, v in params.items()},
                           [jnp.asarray(x)], ApplyContext(train=False))

        seq = x.reshape(b, d, L).transpose(0, 2, 1)
        qkv = seq @ params["wqkv"]
        q = qkv[..., :d].reshape(b, L, nh, dh).transpose(0, 2, 1, 3)
        k = qkv[..., d:d + dh].reshape(b, L, 1, dh).transpose(0, 2, 1, 3)
        v = qkv[..., d + dh:].reshape(b, L, 1, dh).transpose(0, 2, 1, 3)
        k = np.broadcast_to(k, (b, nh, L, dh))
        v = np.broadcast_to(v, (b, nh, L, dh))
        att = np.asarray(attention_reference(
            jnp.asarray(q), jnp.asarray(np.ascontiguousarray(k)),
            jnp.asarray(np.ascontiguousarray(v)), causal=True))
        ref = (att.transpose(0, 2, 1, 3).reshape(b, L, d)
               @ params["wo"]).transpose(0, 2, 1).reshape(b, d, 1, L)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-4, atol=2e-5)

    def test_gqa_trains_and_roundtrips(self):
        import numpy as np
        from cxxnet_tpu.models import transformer_lm_trainer
        from cxxnet_tpu.io.data import DataBatch
        from cxxnet_tpu.utils import serializer
        tr = transformer_lm_trainer(
            vocab=30, seq=16, batch_size=4, dim=32, nhead=4, nlayer=1,
            dev="cpu", extra_cfg="")
        # GQA via the DSL requires the key inside the attention layer scope;
        # easier to pin through a fresh conf
        from cxxnet_tpu.nnet.trainer import Trainer
        from cxxnet_tpu.utils.config import parse_config_string
        from cxxnet_tpu.models import transformer_lm_netconfig
        conf = transformer_lm_netconfig(30, dim=32, nhead=4, nlayer=1)
        conf = conf.replace("  causal = 1\n", "  causal = 1\n  nkvhead = 2\n")
        conf += ("input_shape = 1,1,16\nbatch_size = 4\n"
                 "label_vec[0,16) = label\nupdater = adam\neta = 0.003\n"
                 "dev = cpu\n")
        tr = Trainer()
        for k, v in parse_config_string(conf):
            tr.set_param(k, v)
        tr.init_model()
        rs = np.random.RandomState(0)
        b = DataBatch()
        b.data = rs.randint(0, 30, (4, 1, 1, 16)).astype(np.float32)
        b.label = rs.randint(0, 30, (4, 16)).astype(np.float32)
        b.batch_size = 4
        for _ in range(3):
            tr.update(b)
        w = serializer.Writer()
        tr.save_model(w)
        blob = w.getvalue()
        tr2 = Trainer()
        for k, v in parse_config_string(conf):
            tr2.set_param(k, v)
        tr2.load_model(serializer.Reader(blob))
        p1 = np.asarray(tr.params[1]["wqkv"])
        p2 = np.asarray(tr2.params[1]["wqkv"])
        np.testing.assert_array_equal(p1, p2)


class TestGQAParallelPaths:
    """Grouped K/V flows through the sp paths without a pre-broadcast —
    the ring hops / all-to-alls move nkvhead-sized blocks (ADVICE r2)."""

    def _qkv(self, b=2, nh=4, nkv=2, L=16, d=8, seed=5):
        import numpy as np
        rs = np.random.RandomState(seed)
        q = rs.randn(b, nh, L, d).astype(np.float32)
        k = rs.randn(b, nkv, L, d).astype(np.float32)
        v = rs.randn(b, nkv, L, d).astype(np.float32)
        return q, k, v

    def _expanded_ref(self, q, k, v, causal):
        import numpy as np
        import jax.numpy as jnp
        from cxxnet_tpu.parallel import attention_reference
        g = q.shape[1] // k.shape[1]
        kf = np.repeat(k, g, axis=1)
        vf = np.repeat(v, g, axis=1)
        return np.asarray(attention_reference(
            jnp.asarray(q), jnp.asarray(kf), jnp.asarray(vf),
            causal=causal))

    def test_reference_grouped_matches_broadcast(self):
        import numpy as np
        import jax.numpy as jnp
        from cxxnet_tpu.parallel import attention_reference
        q, k, v = self._qkv()
        out = np.asarray(attention_reference(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True))
        np.testing.assert_allclose(out, self._expanded_ref(q, k, v, True),
                                   rtol=1e-5, atol=1e-6)

    def test_ring_grouped_matches_reference(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from cxxnet_tpu.parallel import ring_attention
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        q, k, v = self._qkv(L=32)
        out = np.asarray(ring_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            causal=True))
        np.testing.assert_allclose(out, self._expanded_ref(q, k, v, True),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.slow
    def test_ring_grouped_grads_match(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from cxxnet_tpu.parallel import (attention_reference,
                                         ring_attention)
        mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
        q, k, v = self._qkv(L=32)

        def loss_ring(q_, k_, v_):
            return jnp.sum(jnp.square(ring_attention(
                q_, k_, v_, mesh, causal=True)))

        def loss_ref(q_, k_, v_):
            return jnp.sum(jnp.square(attention_reference(
                q_, k_, v_, causal=True)))

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        # the kv grads come back at kv-head resolution
        assert g_ring[1].shape == k.shape

    def test_ulysses_grouped_matches_reference(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from cxxnet_tpu.parallel import ulysses_attention
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        q, k, v = self._qkv(L=32)   # nh=4, nkv=2: both divisible by sp=2
        out = np.asarray(ulysses_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
            causal=True))
        np.testing.assert_allclose(out, self._expanded_ref(q, k, v, True),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.slow
    def test_ring_flash_grouped_matches_reference(self):
        import os
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from cxxnet_tpu.parallel import ring_attention
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        # tile-aligned shapes so the flash ring step engages (interpret
        # mode on CPU); nkv=2 < nh=4
        q, k, v = self._qkv(b=1, nh=4, nkv=2, L=512, d=16)
        from cxxnet_tpu import ops
        ops.set_use_pallas(True)
        try:
            def loss(q_, k_, v_):
                return jnp.sum(jnp.square(ring_attention(
                    q_, k_, v_, mesh, causal=True)))
            out = np.asarray(ring_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), mesh,
                causal=True))
            gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        finally:
            ops.set_use_pallas(None)
        np.testing.assert_allclose(out, self._expanded_ref(q, k, v, True),
                                   rtol=2e-4, atol=2e-4)
        assert gk.shape == k.shape and gv.shape == v.shape
        # grads against the dense grouped reference
        from cxxnet_tpu.parallel import attention_reference

        def loss_ref(q_, k_, v_):
            return jnp.sum(jnp.square(attention_reference(
                q_, k_, v_, causal=True)))
        rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                                   rtol=2e-3, atol=2e-3)
