"""Flash-kernel ring attention (ops/ring_flash.py).

Runs the exact kernel code on the virtual CPU mesh via the Pallas
interpreter and goldens it against the dense reference — forward and
gradients, causal and not. The compiled path is validated on the chip by
tools/check_tpu_kernels.py.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu.parallel import ring
from cxxnet_tpu.parallel._compat import shard_map  # noqa: F401  (env check)
from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401


def _mesh(n=4):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs, ("sp",))


def _qkv(b=1, h=2, s=512, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: rs.randn(b, h, s, d).astype(np.float32)
    return mk(), mk(), mk()


@pytest.fixture
def flash_ring_env():
    from cxxnet_tpu import ops
    ops.set_use_pallas(True)        # kernels run interpreted on CPU
    yield
    ops.set_use_pallas(None)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(flash_ring_env, causal):
    q, k, v = _qkv(seed=1)
    mesh = _mesh()
    out = ring.ring_attention(q, k, v, mesh, causal=causal)
    ref = ring.attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(flash_ring_env, causal):
    q, k, v = _qkv(seed=2)
    mesh = _mesh()
    w = np.random.RandomState(9).randn(*q.shape).astype(np.float32)

    def loss_flash(q_, k_, v_):
        return jnp.sum(ring.ring_attention(q_, k_, v_, mesh,
                                           causal=causal) * w)

    def loss_ref(q_, k_, v_):
        return jnp.sum(ring.attention_reference(q_, k_, v_,
                                                causal=causal) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("seq, tiles", [(512, True), (32, False)],
                         ids=["shape_tiles", "shape_too_small"])
@pytest.mark.parametrize("pallas", [True, False],
                         ids=["pallas_on", "pallas_off"])
def test_ring_step_choice(pallas, seq, tiles):
    """The ring step is the flash kernels where Pallas runs AND the
    per-device block tiles (s / 4 of 128 rows; 8 is under the 128-lane
    tile), the dense step elsewhere; either way the result is right."""
    from cxxnet_tpu import ops
    q, k, v = _qkv(s=seq, seed=3)
    mesh = _mesh()
    ops.set_use_pallas(pallas)
    try:
        assert ring._ring_flash_enabled(seq // 4, seq // 4, 16) \
            == (pallas and tiles)
        out = ring.ring_attention(q, k, v, mesh, causal=True)
    finally:
        ops.set_use_pallas(None)
    # auto, off the TPU: dense
    assert not ring._ring_flash_enabled(seq // 4, seq // 4, 16)
    ref = ring.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_trainer_sp_path_with_ring_flash(flash_ring_env):
    """End-to-end DSL attention under seq_parallel=2 with the flash ring
    step: one train step runs and produces a finite loss."""
    import numpy as np
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.io.data import DataBatch
    rs = np.random.RandomState(0)
    tr = transformer_lm_trainer(vocab=50, seq=512, batch_size=2, dim=64,
                                nhead=4, nlayer=1, dev="cpu:0-1",
                                extra_cfg="seq_parallel = 2\n"
                                          "eval_train = 0\n")
    b = DataBatch()
    b.data = rs.randint(0, 50, (2, 1, 1, 512)).astype(np.float32)
    b.label = rs.randint(0, 50, (2, 512)).astype(np.float32)
    b.batch_size = 2
    tr.update(b)
    li = tr.net.label_info_from(b.label)
    _, loss = tr.net.forward(tr.params, b.data, labels=li, train=False,
                             mesh=tr.mesh)
    assert np.isfinite(float(loss))


def test_bf16_forward_close_to_f32(flash_ring_env):
    """bf16 operands (the trainer's compute dtype) stay within bf16
    tolerance of the f32 dense reference — accumulation is f32 in-kernel."""
    q, k, v = _qkv(seed=6)
    mesh = _mesh()
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    out = ring.ring_attention(qb, kb, vb, mesh, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = ring.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05)
