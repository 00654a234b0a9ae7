"""Pallas kernel numerics vs the pure-XLA goldens, run in interpreter mode
on CPU (the same kernels compile for TPU; tools/check_tpu_kernels.py
exercises them there)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cxxnet_tpu import ops
from cxxnet_tpu.ops import pallas_kernels


class TestLRNPallas:
    def _x(self, seed=0, shape=(2, 16, 5, 5)):
        return np.random.RandomState(seed).randn(*shape).astype(np.float32)

    @pytest.mark.parametrize("nsize", [3, 5])
    def test_forward_matches_xla(self, nsize):
        x = self._x()
        out = pallas_kernels.lrn(x, nsize, 0.001, 0.75, 1.0, True)
        ref = ops.lrn_xla(x, nsize, 0.001, 0.75, 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_grad_matches_xla(self):
        x = self._x(1)

        def f_pl(x):
            return jnp.sum(jnp.square(
                pallas_kernels.lrn(x, 5, 0.001, 0.75, 1.0, True)))

        def f_xla(x):
            return jnp.sum(jnp.square(ops.lrn_xla(x, 5, 0.001, 0.75, 1.0)))

        g = jax.grad(f_pl)(x)
        g_ref = jax.grad(f_xla)(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-6)

    def test_band_matrix_window(self):
        # channel 0's window is clipped at the bottom like mshadow chpool
        w = pallas_kernels._band_matrix(6, 5)
        np.testing.assert_array_equal(w[0], [1, 1, 1, 0, 0, 0])
        np.testing.assert_array_equal(w[3], [0, 1, 1, 1, 1, 1])
        np.testing.assert_array_equal(w[5], [0, 0, 0, 1, 1, 1])

    def test_dispatch_flag(self):
        x = self._x(2)
        ops.set_use_pallas(False)
        try:
            a = ops.lrn(x, 3, 0.001, 0.75, 1.0)
        finally:
            ops.set_use_pallas(None)
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(ops.lrn_xla(x, 3, 0.001, 0.75, 1.0)))
        assert ops.use_pallas() == (jax.default_backend() == "tpu")


class TestLRNBf16:
    def test_bf16_forward_and_grad(self):
        """bf16 activations must work through the Pallas LRN (computation is
        promoted to f32 in-kernel, outputs cast back)."""
        x = np.random.RandomState(3).randn(2, 8, 4, 4).astype(np.float32)
        xb = jnp.asarray(x, jnp.bfloat16)
        out = pallas_kernels.lrn(xb, 5, 0.001, 0.75, 1.0, True)
        assert out.dtype == jnp.bfloat16
        ref = ops.lrn_xla(jnp.asarray(x), 5, 0.001, 0.75, 1.0)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2,
            atol=1e-2)

        def f(xb):
            return jnp.sum(jnp.square(
                pallas_kernels.lrn(xb, 5, 0.001, 0.75, 1.0, True)))

        g = jax.grad(f)(xb)
        assert g.dtype == jnp.bfloat16
        g_ref = jax.grad(lambda x: jnp.sum(jnp.square(
            ops.lrn_xla(x, 5, 0.001, 0.75, 1.0))))(jnp.asarray(x))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(g_ref), rtol=5e-2,
            atol=5e-2)


LRN_ARGS = (0.01, 0.75, 1.0)      # alpha large enough to move the norm


def _nhwc(seed, c, dtype, n=128, h=1, w=5):
    x = 3 * np.random.RandomState(seed).randn(n, h, w, c)
    return jnp.asarray(x.astype(np.float32), dtype)


class TestLRNChannelsLast:
    """pallas_kernels.lrn_nhwc (interpret mode) against ops.lrn_nhwc, the
    reduce_window path, in float32."""

    @staticmethod
    def _two_row_blocks(monkeypatch, c, dtype):
        # H*W = 5 rows in blocks of 2: three grid steps, the last ragged
        monkeypatch.setattr(pallas_kernels, "_LRN_NHWC_BLOCK_BYTES",
                            2 * c * 128 * jnp.dtype(dtype).itemsize)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("nsize", [3, 5])
    @pytest.mark.parametrize("c", [64, 96, 192, 256])
    def test_forward_and_grad_match_reduce_window(self, monkeypatch, c,
                                                  nsize, dtype):
        self._two_row_blocks(monkeypatch, c, dtype)
        x = _nhwc(c + nsize, c, dtype)
        x32 = x.astype(jnp.float32)

        def loss(f):
            return lambda v: jnp.sum(jnp.sin(f(v).astype(jnp.float32)))
        fused = lambda v: pallas_kernels.lrn_nhwc(       # noqa: E731
            v, nsize, *LRN_ARGS, True)
        gold = lambda v: ops.lrn_nhwc(v, nsize, *LRN_ARGS)  # noqa: E731
        out, g = fused(x), jax.grad(loss(fused))(x)
        assert out.dtype == dtype and g.dtype == dtype
        assert out.shape == x.shape and g.shape == x.shape
        ref, g_ref = gold(x32), jax.grad(loss(gold))(x32)
        # float32: the window sum goes through a bf16 hi/lo pair (16 bits
        # a term); bf16: the tolerances of the NCHW bf16 test
        fwd_tol, grad_tol = ((dict(rtol=1e-4, atol=1e-4),) * 2
                             if dtype == jnp.float32 else
                             (dict(rtol=2e-2, atol=1e-2),
                              dict(rtol=5e-2, atol=5e-2)))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), **fwd_tol)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(g_ref), **grad_tol)

    def test_even_window_uses_the_mirrored_band(self, monkeypatch):
        # nsize 4 is not symmetric: the backward's window is the
        # transpose of the forward's
        self._two_row_blocks(monkeypatch, 64, jnp.float32)
        x = _nhwc(4, 64, jnp.float32)
        g = jax.grad(lambda v: jnp.sum(jnp.sin(
            pallas_kernels.lrn_nhwc(v, 4, *LRN_ARGS, True))))(x)
        g_ref = jax.grad(lambda v: jnp.sum(jnp.sin(
            ops.lrn_nhwc(v, 4, *LRN_ARGS))))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("shape,dtype,fits", [
        ((128, 3, 3, 64), jnp.float32, True),
        ((2048, 13, 13, 256), jnp.bfloat16, True),
        ((100, 3, 3, 64), jnp.float32, False),     # batch: no lane tile
        ((128, 3, 3, 60), jnp.float32, False),     # channels: no sublane tile
        ((128, 3, 3, 72), jnp.bfloat16, False),    # bf16 packs 16 sublanes
        ((128, 3, 3, 64), jnp.float16, False),
        ((128, 9, 64), jnp.float32, False),
    ])
    def test_fits(self, shape, dtype, fits):
        assert pallas_kernels.lrn_nhwc_fits(shape, dtype) is fits


class TestLRNDispatch:
    """ops.lrn picks by what it can see: platform, layout, shape; the
    counters say which."""

    @pytest.fixture(autouse=True)
    def forced_on(self):
        ops.set_use_pallas(True)     # the CPU runs the kernel interpreted
        yield
        ops.set_use_pallas(None)

    def _counts(self, fn, *args):
        from cxxnet_tpu.utils import telemetry
        with telemetry.trace_context("lrn") as tc:
            out = fn(*args)
        return out, dict(tc.counts)

    def test_nhwc_takes_the_kernel(self):
        x = _nhwc(0, 64, jnp.float32)
        out, counts = self._counts(
            lambda v: ops.lrn(v, 5, *LRN_ARGS, layout="NHWC"), x)
        assert counts == {"lrn.fused": 1}
        jaxpr = str(jax.make_jaxpr(
            lambda v: ops.lrn(v, 5, *LRN_ARGS, layout="NHWC"))(x))
        assert "pallas_call" in jaxpr and "reduce_window" not in jaxpr
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ops.lrn_nhwc(x, 5, *LRN_ARGS)),
            rtol=1e-4, atol=1e-4)

    def test_untileable_shape_takes_reduce_window(self):
        x = _nhwc(1, 64, jnp.float32, n=100)
        out, counts = self._counts(
            lambda v: ops.lrn(v, 5, *LRN_ARGS, layout="NHWC"), x)
        assert counts == {"lrn.fallback": 1}
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ops.lrn_nhwc(x, 5, *LRN_ARGS)))

    @pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
    def test_pallas_off_takes_reduce_window(self, layout):
        ops.set_use_pallas(False)
        x = _nhwc(2, 64, jnp.float32)
        f = lambda v: ops.lrn(v, 5, *LRN_ARGS, layout=layout)  # noqa: E731
        out, counts = self._counts(f, x)
        assert counts == {"lrn.fallback": 1}
        jaxpr = str(jax.make_jaxpr(f)(x))
        # the reduce_window path itself, no transpose to NCHW and back
        assert "pallas_call" not in jaxpr and "transpose" not in jaxpr
        gold = ops.lrn_nhwc if layout == "NHWC" else ops.lrn_xla
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(gold(x, 5, *LRN_ARGS)))

    def test_preload_only_where_kernels_are_taken(self, monkeypatch):
        import importlib
        import threading
        asked = []
        monkeypatch.setattr(importlib, "import_module", asked.append)

        def preload():
            ops.preload_pallas()
            for t in threading.enumerate():
                if t.name == "preload-pallas":
                    t.join(10)
        preload()
        assert asked == ["cxxnet_tpu.ops.pallas_kernels"]
        ops.set_use_pallas(False)
        preload()
        assert len(asked) == 1

    def test_off_the_tpu_takes_reduce_window(self):
        ops.set_use_pallas(None)
        _, counts = self._counts(
            lambda v: ops.lrn(v, 5, *LRN_ARGS, layout="NHWC"),
            _nhwc(3, 64, jnp.float32))
        assert counts == {"lrn.fallback": 1}


class TestLRNOnADataMesh:
    """LRNLayer under a 4-device ``data`` mesh: the kernel runs inside
    shard_map on each device's rows -- equal to one device's result, and
    no all-gather of the batch in the compiled step."""

    @pytest.fixture(autouse=True)
    def forced_on(self):
        ops.set_use_pallas(True)
        yield
        ops.set_use_pallas(None)

    def _layer(self):
        from cxxnet_tpu.layer import layers
        lay = layers.LRNLayer()
        for k, v in (("local_size", "5"), ("alpha", "0.01"),
                     ("beta", "0.75"), ("knorm", "1")):
            lay.set_param(k, v)
        return lay

    def _fwd_and_grad(self, lay, ctx):
        def both(x):
            f = lambda v: jnp.sum(jnp.sin(                # noqa: E731
                lay.apply({}, [v], ctx)[0]))
            return lay.apply({}, [x], ctx)[0], jax.grad(f)(x)
        return both

    def test_equal_to_one_device_and_no_all_gather(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from cxxnet_tpu.layer import base
        from cxxnet_tpu.utils import telemetry
        lay = self._layer()
        x = _nhwc(5, 64, jnp.float32, n=512, h=2, w=2)
        one = base.ApplyContext(train=True, channels_last=True)
        y1, g1 = jax.jit(self._fwd_and_grad(lay, one))(x)

        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        ctx = base.ApplyContext(train=True, channels_last=True, mesh=mesh)
        sharded = NamedSharding(mesh, P("data"))
        fn = jax.jit(self._fwd_and_grad(lay, ctx), in_shardings=sharded,
                     out_shardings=(sharded, sharded))
        xs = jax.device_put(x, sharded)
        with telemetry.trace_context("lrn") as tc:
            compiled = fn.lower(xs).compile()
        assert tc.counts.get("lrn.fused", 0) >= 1
        assert "lrn.fallback" not in tc.counts
        text = compiled.as_text()
        assert "all-gather" not in text and "all-to-all" not in text
        y4, g4 = compiled(xs)
        assert y4.sharding.is_equivalent_to(sharded, 4)
        np.testing.assert_allclose(np.asarray(y4), np.asarray(y1),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g4), np.asarray(g1),
                                   rtol=1e-6, atol=1e-6)

    def test_rows_a_device_that_do_not_tile_fall_back(self):
        # 128 rows over 4 devices are 32 a device: no lane tile, so the
        # layer leaves the reduce_window path to the partitioner
        from jax.sharding import Mesh
        from cxxnet_tpu.layer import base
        from cxxnet_tpu.utils import telemetry
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        ctx = base.ApplyContext(train=True, channels_last=True, mesh=mesh)
        x = _nhwc(6, 64, jnp.float32, n=128)
        with telemetry.trace_context("lrn") as tc:
            out = self._layer().apply({}, [x], ctx)[0]
        assert dict(tc.counts) == {"lrn.fallback": 1}
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ops.lrn_nhwc(x, 5, *LRN_ARGS)))

    def test_inside_a_pipeline_stage_called_bare(self):
        # manual_tp: the stage body is per-device already, no shard_map
        from jax.sharding import Mesh
        from cxxnet_tpu.layer import base
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        ctx = base.ApplyContext(train=True, channels_last=True, mesh=mesh,
                                manual_tp=True)
        x = _nhwc(7, 64, jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda v: self._layer().apply({}, [v], ctx)[0])(x))
        assert "pallas_call" in jaxpr and "shard_map" not in jaxpr


# (TestMaxPoolBackwardKernel was deleted with the fused Pallas max-pool
# backward kernel: it lost its on-chip A/B 2:1 to select-and-scatter —
# onchip_logs/poolab.log. The reference-exact tie semantics remain
# covered by tests/test_layers.py::test_max_pool_mask_backward.)
