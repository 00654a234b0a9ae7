#!/usr/bin/env python
"""On-device validation of the TPU-only Pallas kernels.

The CPU test suite covers the LRN kernels in interpret mode; the PRNG
kernels (pallas_kernels.uniform / rrelu_mask) use pltpu.prng_random_bits,
which has no CPU interpret path, so this script exercises them on the real
chip: distribution sanity of the uniform draw, the insanity layer's
train-mode forward/backward through the on-core mask, and the Pallas-vs-XLA
LRN numerics compiled for TPU (NCHW, and channels-last at the four shapes
of the benchmark's cells).

Run: python tools/check_tpu_kernels.py   (requires a TPU-backed jax)
     python tools/check_tpu_kernels.py blockdiff   (the flash kernels under
     the block-diffusion mask at its cell's shape alone, ~1 min)
     python tools/check_tpu_kernels.py qkprep   (the fused pass between the
     qkv dot and the attention core at both language-model cells' shapes
     beside the plain lines, ~1 min)
     python tools/check_tpu_kernels.py dsa   (learned sparse attention at
     `keye-ep8-train-8k`'s shape: index scores, the selection by top_k and
     by the kernel that sorts nothing, the flash kernels under the
     selection and the target pass, each alone, ~2 min)
"""

import functools
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np
import jax
import jax.numpy as jnp


def main():
    from cxxnet_tpu.utils import enable_compile_cache
    enable_compile_cache()
    if jax.default_backend() != "tpu":
        sys.exit("check_tpu_kernels: needs a TPU backend, jax found %r"
                 % jax.default_backend())
    if sys.argv[1:] == ["blockdiff"]:
        _check_flash_under_the_block_diffusion_mask(np.random.RandomState(0))
        return
    if sys.argv[1:] == ["qkprep"]:
        _check_qk_prep_at_the_cells_shapes(np.random.RandomState(0))
        return
    if sys.argv[1:] == ["dsa"]:
        _check_learned_sparse_attention(np.random.RandomState(0))
        return
    from cxxnet_tpu import ops
    from cxxnet_tpu.ops import pallas_kernels
    from cxxnet_tpu.layer import base, layers

    # --- uniform: range, mean/var, determinism per seed ---
    u = np.asarray(jax.jit(
        lambda s: pallas_kernels.uniform(s, (512, 512)))(jnp.int32(7)))
    assert 0.0 <= u.min() and u.max() < 1.0, (u.min(), u.max())
    assert abs(u.mean() - 0.5) < 5e-3, u.mean()
    assert abs(u.var() - 1.0 / 12) < 5e-3, u.var()
    u2 = np.asarray(jax.jit(
        lambda s: pallas_kernels.uniform(s, (512, 512)))(jnp.int32(7)))
    assert np.array_equal(u, u2), "same seed must reproduce"
    u3 = np.asarray(jax.jit(
        lambda s: pallas_kernels.uniform(s, (512, 512)))(jnp.int32(8)))
    assert not np.array_equal(u, u3), "different seed must differ"
    print("uniform kernel: OK (mean=%.4f var=%.4f)" % (u.mean(), u.var()))

    # --- uniform at conv-activation scale: must exceed VMEM (~16 MB) and
    # still compile thanks to the row-block grid ---
    big_shape = (64, 96, 55, 55)  # ~74 MB f32, AlexNet conv1-sized
    ub = np.asarray(jax.jit(
        lambda s: pallas_kernels.uniform(s, big_shape))(jnp.int32(11)))
    assert 0.0 <= ub.min() and ub.max() < 1.0
    assert abs(ub.mean() - 0.5) < 2e-3, ub.mean()
    # per-block reseeding must not repeat the stream across blocks
    flat = ub.reshape(-1)
    assert not np.array_equal(flat[: 2048 * 128],
                              flat[2048 * 128: 2 * 2048 * 128])
    print("uniform kernel large (%.0f MB): OK (mean=%.4f)"
          % (ub.nbytes / 1e6, ub.mean()))

    # --- insanity layer train path through the on-core mask ---
    lay = layers.InsanityLayer()
    lay.set_param("lb", "5")
    lay.set_param("ub", "10")
    x = jnp.asarray(np.random.RandomState(0).randn(8, 16).astype(np.float32))
    ctx = base.ApplyContext(train=True, rng=jax.random.PRNGKey(3))

    def loss(x):
        return jnp.sum(lay.apply({}, [x], ctx)[0])

    out = lay.apply({}, [x], ctx)[0]
    xn = np.asarray(x)
    on = np.asarray(out)
    pos = xn > 0
    assert np.array_equal(on[pos], xn[pos]), "positive part must pass through"
    slope = xn[~pos] / on[~pos]
    assert (slope >= 5 - 1e-3).all() and (slope <= 10 + 1e-3).all(), \
        (slope.min(), slope.max())
    g = np.asarray(jax.grad(loss)(x))
    assert np.array_equal(g[pos], np.ones_like(g[pos]))
    assert ((g[~pos] >= 1 / 10 - 1e-5) & (g[~pos] <= 1 / 5 + 1e-5)).all()
    print("insanity on-core mask: OK (slope in [%.2f, %.2f])"
          % (slope.min(), slope.max()))

    # --- Pallas LRN vs XLA LRN compiled on TPU, f32 + bf16 ---
    x4 = np.random.RandomState(1).randn(4, 32, 14, 14).astype(np.float32)
    for dt, rtol in ((jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)):
        xd = jnp.asarray(x4, dt)
        a = np.asarray(jax.jit(lambda v: pallas_kernels.lrn(
            v, 5, 0.001, 0.75, 1.0))(xd), np.float32)
        b = np.asarray(jax.jit(lambda v: ops.lrn_xla(
            v, 5, 0.001, 0.75, 1.0))(xd), np.float32)
        np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol)
        ga = np.asarray(jax.grad(lambda v: jnp.sum(jnp.square(
            pallas_kernels.lrn(v, 5, 0.001, 0.75, 1.0))))(xd), np.float32)
        gb = np.asarray(jax.grad(lambda v: jnp.sum(jnp.square(
            ops.lrn_xla(v, 5, 0.001, 0.75, 1.0))))(xd), np.float32)
        np.testing.assert_allclose(ga, gb, rtol=rtol * 10, atol=rtol * 10)
        print("pallas lrn vs xla on TPU (%s): OK" % np.dtype(dt).name)

    # --- channels-last LRN at the four shapes of the benchmark's cells,
    # through ops.lrn's own dispatch, against the reduce_window path in
    # float32: forward and gradient, compared on the device ---
    from cxxnet_tpu.utils import telemetry
    lrn_args = (5, 0.0001, 0.75, 1.0)

    def lrn_pair(f, x, g):
        y, vjp = jax.vjp(f, x)
        return y, vjp(g.astype(y.dtype))[0]

    def worst(a, b):
        # the largest gap over the golden's own scale
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                     / jnp.max(jnp.abs(b)))
    for shape in ((512, 56, 56, 64), (512, 56, 56, 192),
                  (2048, 27, 27, 96), (2048, 13, 13, 256)):
        for dt, tol in ((jnp.bfloat16, 1e-2), (jnp.float32, 1e-4)):
            if dt == jnp.float32 and shape[3] == 192:
                continue        # 2.5 GB a tensor: the bf16 case covers it
            kx, kg = jax.random.split(jax.random.PRNGKey(shape[3]))
            x = (2 * jax.random.normal(kx, shape, jnp.float32)).astype(dt)
            g = jax.random.normal(kg, shape, jnp.float32).astype(dt)
            assert ops.lrn_fused(shape, dt, "NHWC"), (shape, dt)
            y, dx = jax.jit(lambda x, g: lrn_pair(
                lambda v: ops.lrn(v, *lrn_args, layout="NHWC"), x, g))(x, g)
            ry, rdx = jax.jit(lambda x, g: lrn_pair(
                lambda v: ops.lrn_nhwc(v, *lrn_args),
                x.astype(jnp.float32), g.astype(jnp.float32)))(x, g)
            ey, edx = worst(y, ry), worst(dx, rdx)
            assert ey < tol and edx < tol, (shape, dt, ey, edx)
            del x, g, y, dx, ry, rdx
        print("channels-last lrn %s on TPU: OK" % (shape,))
    with telemetry.trace_context("lrn-dispatch") as tc:
        jax.eval_shape(lambda v: ops.lrn(v, *lrn_args, layout="NHWC"),
                       jax.ShapeDtypeStruct((100, 8, 8, 64), jnp.bfloat16))
    assert tc.counts == {"lrn.fallback": 1}, tc.counts
    print("channels-last lrn: a batch of 100 falls back: OK")

    # --- flash attention: compiled kernels vs dense reference ---
    # tolerance covers the dense reference's default-precision MXU einsums
    from cxxnet_tpu.ops import flash_attn
    from cxxnet_tpu.parallel.ring import attention_reference
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.float32)
    k = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.float32)
    v = jnp.asarray(rs.randn(2, 4, 512, 64), jnp.float32)
    for causal in (False, True):
        out = np.asarray(flash_attn.flash_attention(q, k, v, causal))
        ref = np.asarray(attention_reference(q, k, v, causal=causal))
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
        gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            flash_attn.flash_attention(q, k, v, causal))),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            attention_reference(q, k, v, causal=causal))),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-2)
        print("flash attention on TPU (causal=%s): OK" % causal)
    # sliding window: out-of-window tiles statically skipped, compiled
    outw = np.asarray(flash_attn.flash_attention(
        q, k, v, True, None, False, 96))
    refw = np.asarray(attention_reference(q, k, v, causal=True, window=96))
    np.testing.assert_allclose(outw, refw, rtol=2e-2, atol=2e-2)
    print("flash attention window=96 on TPU: OK")
    # unaligned length: padded tiles + in-kernel tail mask, compiled
    q2 = jnp.asarray(rs.randn(1, 2, 300, 64), jnp.float32)
    out = np.asarray(flash_attn.flash_attention(q2, q2, q2, True))
    ref = np.asarray(attention_reference(q2, q2, q2, causal=True))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    print("flash attention unaligned L=300 on TPU: OK")
    # grouped-query attention: kv heads read via the BlockSpec row map
    kg = jnp.asarray(rs.randn(2, 2, 512, 64), jnp.float32)
    vg = jnp.asarray(rs.randn(2, 2, 512, 64), jnp.float32)
    outg = np.asarray(flash_attn.flash_attention(q, kg, vg, True))
    refg = np.asarray(attention_reference(q, kg, vg, causal=True))
    np.testing.assert_allclose(outg, refg, rtol=2e-2, atol=2e-2)
    gq, gk, gv = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(jnp.sin(
        flash_attn.flash_attention(q_, k_, v_, True))),
        argnums=(0, 1, 2)))(q, kg, vg)
    assert gk.shape == kg.shape and gv.shape == vg.shape
    rq, rk, rv = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(jnp.sin(
        attention_reference(q_, k_, v_, causal=True))),
        argnums=(0, 1, 2)))(q, kg, vg)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=5e-2, atol=5e-2)
    print("flash attention GQA (4q/2kv heads) on TPU: OK")
    # the shipped LM shapes, bf16: forward and BOTH backward kernels (dq,
    # dk/dv) at L=2048 against the dense reference, and at L=8192 — where
    # the dense (L, L) scores would not be worth their memory — finite,
    # O(L) memory
    def flash_loss(q_, k_, v_):
        return jnp.sum(jnp.sin(flash_attn.flash_attention(
            q_, k_, v_, True).astype(jnp.float32)))

    def dense_loss(q_, k_, v_):
        return jnp.sum(jnp.sin(attention_reference(
            q_, k_, v_, causal=True).astype(jnp.float32)))

    for L in (2048, 8192):
        qb, kb, vb = (jnp.asarray(rs.randn(1, 8, L, 64), jnp.bfloat16)
                      for _ in range(3))
        gf = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(qb, kb, vb)
        for g in gf:
            assert np.isfinite(float(jnp.sum(g.astype(jnp.float32))))
        if L == 2048:
            gr = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(
                qb, kb, vb)
            for a, b in zip(gf, gr):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-1, atol=1e-1)
        print("flash attention L=%d bf16 fwd + dq + dk/dv: OK" % L)
    _check_flash_at_the_cells_shape(rs)
    _check_flash_under_the_block_diffusion_mask(rs)
    _check_qk_prep_at_the_cells_shapes(rs)

    # --- ring-step flash kernels, compiled ---
    # a 1-device sp mesh exercises the full kernel set (SMEM offsets,
    # aliased carries, dq/dkv accumulators) through Mosaic; multi-device
    # ring semantics are goldened on the CPU mesh (tests/test_ring_flash.py)
    from cxxnet_tpu.parallel import ring as ring_mod
    from jax.sharding import Mesh
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("sp",))
    q3 = jnp.asarray(rs.randn(1, 2, 512, 64), jnp.float32)
    for causal in (False, True):
        out = np.asarray(ring_mod.ring_attention(
            q3, q3, q3, mesh1, causal=causal))
        ref = np.asarray(attention_reference(q3, q3, q3, causal=causal))
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    g = jax.jit(jax.grad(lambda q: jnp.sum(ring_mod.ring_attention(
        q, q3, q3, mesh1, causal=True))))(q3)
    assert np.isfinite(float(jnp.sum(g)))
    print("ring-flash step kernels compiled (n=1 ring): OK")

    # --- channels_last conv-stack layout, compiled on-chip -------------
    # one bf16 train step of a conv->relu->lrn->bn->relu_max_pooling net
    # with channels_last forced BOTH ways; first-conv weights after the
    # step must agree — the on-chip compile/parity smoke for the NHWC
    # paths this chain hits (full per-layer coverage incl. ch_concat and
    # the sibling fusion is tests/test_layout.py on the CPU mesh)
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    from cxxnet_tpu.io.data import DataBatch
    cl_conf = """
netconfig = start
layer[0->1] = conv:k1
  kernel_size = 5
  stride = 2
  nchannel = 32
  random_type = xavier
layer[1->2] = relu
layer[2->3] = lrn
  local_size = 5
  alpha = 0.0001
  beta = 0.75
layer[3->4] = batch_norm:kb
layer[4->5] = relu_max_pooling
  kernel_size = 3
  stride = 2
layer[5->6] = flatten
layer[6->7] = fullc:kf
  nhidden = 10
  init_sigma = 0.01
layer[7->7] = softmax
netconfig = end
input_shape = 3,63,63
batch_size = 16
eta = 0.05
eval_train = 0
compute_dtype = bfloat16
dev = tpu
"""
    db = DataBatch()
    db.data = rs.rand(16, 3, 63, 63).astype(np.float32)
    db.label = (rs.randint(0, 10, (16, 1))).astype(np.float32)
    db.batch_size = 16
    weights = []
    for cl in (0, 1):
        t2 = Trainer()
        for k, v in parse_config_string(
                cl_conf + "channels_last = %d\n" % cl):
            t2.set_param(k, v)
        t2.init_model()
        t2.update(db)
        weights.append(np.asarray(
            jax.device_get(t2.params[0]["wmat"]), np.float32))
    assert np.isfinite(weights[0]).all() and np.isfinite(weights[1]).all()
    # bf16 step, different physical layouts: close, not bitwise
    np.testing.assert_allclose(weights[0], weights[1], rtol=2e-2, atol=2e-4)
    print("channels_last train-step parity on-chip: OK")

    # --- depthwise conv (feature_group_count = C) compiles + steps ------
    # mobilenet's distinct XLA-TPU path: grouped conv at
    # the one-channel-per-group extreme, under bf16 + channels_last
    from cxxnet_tpu.models import mobilenet_trainer
    mnt = mobilenet_trainer(batch_size=8, input_hw=32, dev="tpu",
                            n_class=10, base_ch=8,
                            blocks=((16, 1), (32, 2)),
                            extra_cfg="eval_train = 0\n"
                                      "compute_dtype = bfloat16\n")
    db3 = DataBatch()
    db3.data = rs.rand(8, 3, 32, 32).astype(np.float32)
    db3.label = rs.randint(0, 10, (8, 1)).astype(np.float32)
    db3.batch_size = 8
    mnt.update(db3)
    assert np.isfinite(np.asarray(
        jax.device_get(mnt.params[0]["wmat"]), np.float32)).all()
    print("depthwise (ngroup=C) conv train step on-chip: OK")

    print("ALL TPU KERNEL CHECKS PASSED")


def _ms(fn, *args, n=5):
    """Wall-clock ms of one call of a jitted ``fn``, warm, mean of n."""
    import time
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def _both(attend, q_, k_, v_, do_):
    """An attention's output and its three gradients."""
    out, vjp = jax.vjp(attend, q_, k_, v_)
    return (out,) + vjp(do_)


def _check_flash_at_the_cells_shape(rs):
    """The language-model cell's attention (`smallthinker-ep4-train-8k`:
    28 query heads on 4 key-value heads of 128, 8,192 tokens, bf16, the
    global layer and a 4,096 window): forward and the three gradients
    against the dense reference, one key-value head's group at a time
    (its float32 scores are 1.9 GB), then the kernels' time and their
    rate by the mask's FLOPs, so that a reader has both without a trace."""
    from benchmark import lm_flops
    from cxxnet_tpu.ops import flash_attn
    from cxxnet_tpu.parallel.ring import attention_reference
    nh, nkv, L, d = 28, 4, 8192, 128
    grp = nh // nkv
    q, do = (jnp.asarray(rs.randn(1, nh, L, d), jnp.bfloat16)
             for _ in range(2))
    k, v = (jnp.asarray(rs.randn(1, nkv, L, d), jnp.bfloat16)
            for _ in range(2))

    for window in (0, 4096):
        flash = jax.jit(functools.partial(_both, lambda q_, k_, v_: (
            flash_attn.flash_attention(q_, k_, v_, True, None, False,
                                       window))))
        dense = jax.jit(functools.partial(_both, lambda q_, k_, v_: (
            attention_reference(q_, k_, v_, causal=True, window=window))))
        got = flash(q, k, v, do)
        for h in range(nkv):
            rows = slice(h * grp, (h + 1) * grp)
            want = dense(q[:, rows], k[:, h:h + 1], v[:, h:h + 1],
                         do[:, rows])
            for a, b in zip(got, want):
                a = a[:, rows] if a.shape[1] == nh else a[:, h:h + 1]
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-1, atol=1e-1)
        fwd = jax.jit(lambda q_, k_, v_: flash_attn.flash_attention(
            q_, k_, v_, True, None, False, window))
        flops = lm_flops.flash_attention(L, nh, nkv, d, window)["flops"]
        t_f, t_fb = _ms(fwd, q, k, v), _ms(flash, q, k, v, do)
        print("flash attention at the cell's shape, window=%d: OK; forward "
              "%.2f ms = %.1f TFLOP/s, forward + backward %.2f ms = %.1f "
              "TFLOP/s (by the mask's FLOPs, x1 and x3.5)"
              % (window, t_f, flops / t_f / 1e9, t_fb,
                 3.5 * flops / t_fb / 1e9))


def _check_flash_under_the_block_diffusion_mask(rs):
    """The block-diffusion cell's attention (`sdar-ep8-train-8k`: 16,384
    rows, the noised and the clean copy of 8,192 tokens, block length 4,
    heads of 128, bf16): forward and the three gradients against the dense
    masked softmax on one key-value head with a group of two (the tiles do
    not follow the heads; a head's float32 scores are 1 GB), then, at the
    cell's 32 query heads on 4, each kernel's time and its rate by the
    scores the mask keeps (a gradient asked for alone leaves the other
    backward kernel dead code)."""
    from cxxnet_tpu.ops import flash_attn
    from cxxnet_tpu.parallel.ring import attention_reference
    L, d, B = 16384, 128, 4

    def operands(nh, nkv):
        q, do = (jnp.asarray(rs.randn(1, nh, L, d), jnp.bfloat16)
                 for _ in range(2))
        k, v = (jnp.asarray(rs.randn(1, nkv, L, d), jnp.bfloat16)
                for _ in range(2))
        return q, k, v, do

    def flash(q_, k_, v_):
        return flash_attn.flash_attention(q_, k_, v_, False, None, False,
                                          0, None, B)
    q, k, v, do = operands(2, 1)
    got = jax.jit(functools.partial(_both, flash))(q, k, v, do)
    want = jax.jit(functools.partial(_both, lambda q_, k_, v_: (
        attention_reference(q_, k_, v_, block_len=B))))(q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-1, atol=1e-1)
    del got, want

    nh, nkv = 32, 4
    q, k, v, do = operands(nh, nkv)
    half = L // 2
    flops = 4.0 * nh * d * (half * half + half * B)     # forward, kept scores
    t_f = _ms(jax.jit(flash), q, k, v)
    t_q = _ms(jax.jit(lambda *a: _both(flash, *a)[1]), q, k, v, do) - t_f
    t_kv = _ms(jax.jit(lambda *a: _both(flash, *a)[2:]), q, k, v, do) - t_f
    t_fb = _ms(jax.jit(functools.partial(_both, flash)), q, k, v, do)
    sched = flash_attn.schedule(q, k, False, 0, B)
    print("flash attention under the block-diffusion mask at the cell's "
          "shape: OK; tiles %s; forward %.2f ms = %.1f TFLOP/s, dQ %.2f ms "
          "= %.1f TFLOP/s, dK/dV %.2f ms = %.1f TFLOP/s, forward + backward "
          "%.2f ms = %.1f TFLOP/s (by the kept scores' FLOPs, x1, x1, x1.5 "
          "and x3.5)" % (sched, t_f, flops / t_f / 1e9, t_q,
                         flops / t_q / 1e9, t_kv, 1.5 * flops / t_kv / 1e9,
                         t_fb, 3.5 * flops / t_fb / 1e9))


def _check_learned_sparse_attention(rs):
    """The parts of an attention layer under ``attn_mask = dsa`` at
    `keye-ep8-train-8k`'s shape (8,192 rows, 32 query heads on 4 of 128,
    an indexer of 16 heads of 64, 2,048 keys a query, bf16), each alone:
    the index scores forward and backward (the plain lines, and since
    PR 41 the kernel over the causal tiles against them: each gradient's
    worst gap, both paths' ms), the selection (by ``lax.top_k``
    and by the kernel that sorts nothing), the three flash kernels under
    the selection, the target pass. First, on one key-value head with a
    group of two, the selection against ``lax.top_k``'s set, the fused
    selection against the plain one array for array, and the flash
    kernels against the plain lines; then each part's ms and its
    rate (TFLOP/s by the kept scores for the kernels, by the causal
    triangle for the index scores; GB/s of the scores read for the
    selection)."""
    from cxxnet_tpu import ops
    from cxxnet_tpu.ops import dsa, dsa_index_pallas, flash_attn
    L, d, J, di, topk = 8192, 128, 16, 64, 2048

    def operands(nh, nkv):
        q, do = (jnp.asarray(rs.randn(1, nh, L, d), jnp.bfloat16)
                 for _ in range(2))
        k, v = (jnp.asarray(rs.randn(1, nkv, L, d), jnp.bfloat16)
                for _ in range(2))
        return q, k, v, do
    qi = jnp.asarray(rs.randn(1, J, L, di), jnp.bfloat16)
    ki = jnp.asarray(rs.randn(1, L, di), jnp.bfloat16)
    w = jnp.asarray(rs.randn(1, L, J) / 32.0, jnp.float32)
    scores = jax.jit(dsa.index_scores)(qi, ki, w)
    plain_select = jax.jit(functools.partial(dsa.select, topk=topk))
    sel = plain_select(scores)

    # the selection is top_k's set, a row's count min(t + 1, topk)
    @jax.jit
    def by_top_k(s_):
        rows = jnp.arange(L)[:, None]
        masked = jnp.where(jnp.arange(L)[None, :] <= rows, s_[0], -jnp.inf)
        _, idx = jax.lax.top_k(masked, topk)
        took = jnp.arange(topk)[None, :] <= rows
        hit = jnp.zeros((L, L), jnp.int32).at[
            jnp.broadcast_to(rows, idx.shape), idx].add(
                took.astype(jnp.int32))
        return hit
    assert np.array_equal(np.asarray(by_top_k(scores)) > 0,
                          np.asarray(sel[0]) != 0)
    assert int(jnp.sum(sel.astype(jnp.int32))) == dsa.kept_scores(L, topk)

    # the kernel that sorts nothing gives the plain lines' array: on these
    # scores, and on scores of few values and both zeros (rounded to
    # quarters, signs at random), where rows tie astride the last place
    assert ops.dsa_select_supported(L, topk)
    fused = functools.partial(ops.dsa_select, topk=topk)
    assert np.array_equal(np.asarray(fused(scores)), np.asarray(sel))
    few = jnp.round(scores * 4) / 4 * jnp.asarray(
        rs.choice([-1.0, 1.0], (1, L, L)), jnp.float32)
    tied = fused(few)
    assert np.array_equal(np.asarray(tied), np.asarray(plain_select(few)))
    assert int(jnp.sum(tied.astype(jnp.int32))) == dsa.kept_scores(L, topk)
    del few, tied

    def flash(q_, k_, v_):
        return flash_attn.flash_attention_selected(q_, k_, v_, sel)[0]

    def plain(q_, k_, v_):
        probs, _ = dsa.selected_probs_plain(q_, k_, sel, d ** -0.5)
        return jnp.einsum("bngqk,bnkd->bngqd", probs.astype(v_.dtype), v_,
                          preferred_element_type=jnp.float32).reshape(
                              q_.shape).astype(q_.dtype)
    q, k, v, do = operands(2, 1)
    got = jax.jit(functools.partial(_both, flash))(q, k, v, do)
    want = jax.jit(functools.partial(_both, plain))(q, k, v, do)
    gap = 0.0
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=1e-1, atol=1e-1)
        gap = max(gap, float(np.max(np.abs(a - b)) / np.max(np.abs(b))))
    lse = jax.jit(lambda *a: flash_attn.flash_attention_selected(
        *a, sel)[1])(q, k, v)
    p = jax.jit(lambda q_, k_, l_: flash_attn.selected_probs(
        q_, k_, l_, sel))(q, k, lse)
    p_want = jax.jit(lambda q_, k_: dsa.selected_probs_plain(
        q_, k_, sel, d ** -0.5)[1])(q, k)
    gap_p = float(jnp.max(jnp.abs(p - p_want)))
    assert gap_p < 2e-2 and abs(float(jnp.mean(jnp.sum(p, -1))) - 1) < 1e-2
    del got, want, p, p_want

    nh, nkv = 32, 4
    q, k, v, do = operands(nh, nkv)
    kept = dsa.kept_scores(L, topk)
    flops = 4.0 * nh * d * kept                  # forward, kept scores
    tri = L * (L + 1) / 2
    t_f = _ms(jax.jit(flash), q, k, v)
    t_q = _ms(jax.jit(lambda *a: _both(flash, *a)[1]), q, k, v, do) - t_f
    t_kv = _ms(jax.jit(lambda *a: _both(flash, *a)[2:]), q, k, v, do) - t_f
    lse = jax.jit(lambda *a: flash_attn.flash_attention_selected(
        *a, sel)[1])(q, k, v)
    t_p = _ms(jax.jit(lambda q_, k_, l_: flash_attn.selected_probs(
        q_, k_, l_, sel)), q, k, lse)
    t_i = _ms(jax.jit(dsa.index_scores), qi, ki, w)
    g = jnp.asarray(rs.randn(1, L, L), jnp.float32)
    t_ib = _ms(jax.jit(lambda *a: jax.vjp(dsa.index_scores, *a[:3])[1](
        a[3])), qi, ki, w, g) - t_i
    # the index scores' backward as one kernel over the causal tiles
    # (PR 41) against the plain lines, the scores' gradient shaped as the
    # layer's: random on the selection, nought elsewhere
    assert ops.dsa_index_bwd_supported(L, J, di, qi.dtype)
    g_sel = g * sel.astype(jnp.float32)
    plain_bwd = jax.jit(lambda *a: dsa._scores_bwd(False, a[:3], a[3]))
    fused_bwd = jax.jit(ops.dsa_index_bwd)
    gaps_ib = []
    for a, b in zip(fused_bwd(qi, ki, w, g_sel), plain_bwd(qi, ki, w, g_sel)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        gaps_ib.append(float(np.max(np.abs(a - b)) / np.max(np.abs(b))))
    assert max(gaps_ib) < 2e-2, gaps_ib
    t_ibp = _ms(plain_bwd, qi, ki, w, g_sel)
    t_ibf = _ms(fused_bwd, qi, ki, w, g_sel)
    tile = dsa_index_pallas.tile(L)
    n_t = L // tile
    ib_flops = 3 * 2.0 * J * di * n_t * (n_t + 1) / 2 * tile ** 2
    print("index scores' backward at the cell's shape: the kernel over the "
          "causal tiles against the plain lines, worst gap dq %.2e dk %.2e "
          "dw %.2e of the largest value; plain %.2f ms = %.1f TFLOP/s, "
          "kernel %.2f ms = %.1f TFLOP/s (three products over the %d "
          "causal tiles of %d)"
          % (*gaps_ib, t_ibp, ib_flops / t_ibp / 1e9, t_ibf,
             ib_flops / t_ibf / 1e9, n_t * (n_t + 1) // 2, tile))
    t_s = _ms(plain_select, scores)
    t_sf = _ms(fused, scores)
    t_l = _ms(jax.jit(jax.value_and_grad(lambda s_, p_: jnp.sum(
        dsa.index_loss(s_, sel, p_)))), scores, jnp.abs(g) / L)
    sched = flash_attn.schedule(q, k, True, 0, 0, True)
    i_flops = 2.0 * J * di * tri
    print("learned sparse attention at the cell's shape: OK (worst gap to "
          "the plain lines %.2e of the largest value, target %.2e); tiles "
          "%s, %d of %d causal scores kept; index scores %.2f ms = %.1f "
          "TFLOP/s (by the causal triangle), their backward %.2f ms; "
          "selection by lax.top_k %.2f ms = %.1f GB/s of scores read, by "
          "the kernel that sorts nothing (array-equal) %.2f ms = %.1f GB/s; "
          "flash forward "
          "%.2f ms = %.1f TFLOP/s, dQ %.2f ms = %.1f TFLOP/s, dK/dV %.2f ms "
          "= %.1f TFLOP/s (by the kept scores' FLOPs, x1, x1, x1.5); "
          "target pass %.2f ms = %.1f TFLOP/s (2 head_dim FLOPs a kept "
          "score a head); index loss and its gradient %.2f ms"
          % (gap, gap_p, sched, kept, int(tri), t_i, i_flops / t_i / 1e9,
             t_ib, t_s, 4.0 * L * L / t_s / 1e6, t_sf,
             4.0 * L * L / t_sf / 1e6, t_f, flops / t_f / 1e9,
             t_q, flops / t_q / 1e9, t_kv, 1.5 * flops / t_kv / 1e9, t_p,
             flops / 2 / t_p / 1e9, t_l))


def _check_qk_prep_at_the_cells_shapes(rs):
    """``AttentionLayer._heads`` (the qkv dot's output to the core's q, k,
    v) at the two language-model cells' shapes, bf16: `sdar-ep8-train-8k`
    (16,384 rows under the block-diffusion mask, 32 heads on 4 of 128,
    QK-norm and the rotation) and `smallthinker-ep4-train-8k` (8,192 rows,
    28 on 4, the rotation alone). The fused kernels (ops/qk_prep_pallas.py)
    against the layer's plain lines: the worst gap of the three outputs
    and of every gradient, then each path's ms forward and backward and
    the kernels' GB/s by one honest pass (every operand read once, every
    result written once). Wall clock around jitted calls: a call's
    dispatch rides on each, which at these fractions of a millisecond is
    a sizeable part; a trace of the step has the kernels' own time
    (PERF.md section 5)."""
    from cxxnet_tpu import ops
    from cxxnet_tpu.layer import base, layers
    ctx = base.ApplyContext(train=True)
    for cell, L, nh, nkv, dh, conf in (
            ("sdar-ep8-train-8k", 16384, 32, 4, 128,
             {"qk_norm": "1", "attn_mask": "blockdiff", "block_len": "4",
              "rope_base": "1000000"}),
            ("smallthinker-ep4-train-8k", 8192, 28, 4, 128,
             {"causal": "1", "attn_window": "4096",
              "rope_base": "1500000"})):
        layer = layers.AttentionLayer()
        for key, val in dict(conf, nhead=nh, nkvhead=nkv, head_dim=dh,
                             rope=1).items():
            layer.set_param(key, str(val))
        width = (nh + 2 * nkv) * dh
        qkv = jnp.asarray(rs.randn(1, L, width), jnp.bfloat16)
        params = {key: jnp.asarray(1 + 0.1 * rs.randn(dh), jnp.float32)
                  for key in layer._norm_keys()}
        dout = tuple(jnp.asarray(rs.randn(1, n, L, dh), jnp.bfloat16)
                     for n in (nh, nkv, nkv))

        def both(forward, x, p, g):
            out, vjp = jax.vjp(forward, x, p)
            return out, vjp(g)

        got, ms = {}, {}
        for path, flag in (("fused", True), ("xla", False)):
            ops.set_use_pallas(flag)
            try:
                # a function of its own for each path: jit's cache knows
                # nothing of the flag
                fwd = jax.jit(lambda x, p: layer._heads(x, p, ctx))
                fb = jax.jit(functools.partial(
                    both, lambda x, p: layer._heads(x, p, ctx)))
                got[path] = jax.tree_util.tree_leaves(fb(qkv, params, dout))
                t_f = _ms(fwd, qkv, params, n=20)
                ms[path] = (t_f, _ms(fb, qkv, params, dout, n=20) - t_f)
            finally:
                ops.set_use_pallas(None)
        gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)))
                      / jnp.max(jnp.abs(b.astype(jnp.float32))))
                for a, b in zip(got["fused"], got["xla"])]
        # one rounding fewer (none between norm and rotation): a bf16 ulp
        assert max(gaps) < 2e-2, gaps
        rows, tables = 2.0 * L * width, 2 * 4.0 * L * dh
        gb_f = (2 * rows + tables) / 1e9
        gb_b = ((3 if layer.qk_norm else 2) * rows + tables) / 1e9
        print("qk_prep at %s's shape: OK (worst gap of q, k, v and the "
              "gradients, over the largest value: %.2e); fused forward "
              "%.3f ms = %.0f GB/s, backward %.3f ms = %.0f GB/s; the plain "
              "lines forward %.3f ms, backward %.3f ms"
              % (cell, max(gaps), ms["fused"][0],
                 gb_f / ms["fused"][0] * 1e3, ms["fused"][1],
                 gb_b / ms["fused"][1] * 1e3, ms["xla"][0], ms["xla"][1]))


if __name__ == "__main__":
    main()
