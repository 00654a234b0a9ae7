"""The fused pass between the qkv dot and the attention core
(ops/qk_prep_pallas.py, PR 37) in the Pallas interpreter against its golden
model, ``AttentionLayer._heads``'s plain lines (the head split by slice and
transpose, ``_rms_norm``, ``_apply_rope``): values and every gradient; and
every case that must keep the plain lines does, and says so in the path
account (``attn.prep.fused`` / ``attn.prep.xla``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import ops
from cxxnet_tpu.layer.base import ApplyContext
from cxxnet_tpu.layer.layers import AttentionLayer
from cxxnet_tpu.ops import qk_prep_pallas
from cxxnet_tpu.utils import telemetry


def _layer(nh=4, nkv=2, dh=128, **keys):
    lay = AttentionLayer()
    for k, v in dict(keys, nhead=nh, nkvhead=nkv, head_dim=dh).items():
        lay.set_param(k, str(v))
    return lay


def _operands(lay, b, L, dtype, seed=0):
    rs = np.random.RandomState(seed)
    nh, nkv, dh = lay.nhead, lay.nkvhead or lay.nhead, lay.head_dim
    qkv = jnp.asarray(rs.randn(b, L, (nh + 2 * nkv) * dh), dtype)
    gains = {k: jnp.asarray(1 + 0.2 * rs.randn(dh), jnp.float32)
             for k in lay._norm_keys()}
    # a weight for each output element: the cotangents
    dout = tuple(jnp.asarray(rs.randn(b, n, L, dh), jnp.float32)
                 for n in (nh, nkv, nkv))
    return qkv, gains, dout


def _run(lay, qkv, gains, dout, force, **ctx):
    """(q, k, v), (d qkv, d gains) and what the path account gained, with
    the kernels forced on (the interpreter) or off."""
    before = telemetry.paths()
    ops.set_use_pallas(force)
    try:
        def loss(x, g):
            out = lay._heads(x, g, ApplyContext(train=True, **ctx))
            return sum(jnp.sum(o.astype(jnp.float32) * w)
                       for o, w in zip(out, dout)), out
        grads, out = jax.grad(loss, (0, 1), has_aux=True)(qkv, gains)
    finally:
        ops.set_use_pallas(None)
    return out, grads, {k: n - before.get(k, 0)
                        for k, n in telemetry.paths().items()
                        if n != before.get(k, 0)}


def _gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# the layer's two flags, grouped and plain heads, positions that wrap at
# L/2 (the block-diffusion mask's two copies), both compute types
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("keys", [
    dict(qk_norm=1, rope=1),
    dict(rope=1, causal=1),
    dict(qk_norm=1),
    dict(qk_norm=1, rope=1, nkv=4),
    dict(qk_norm=1, rope=1, attn_mask="blockdiff", block_len=4),
    dict(rope=1, attn_mask="blockdiff", block_len=4, rope_base=1e6),
], ids=["norm+rope", "rope", "norm", "norm+rope-mha", "norm+rope-wrap",
        "rope-wrap"])
def test_the_kernels_give_the_plain_lines_values_and_gradients(keys, dtype):
    lay = _layer(**keys)
    qkv, gains, dout = _operands(lay, 2, 64, dtype)
    out, grads, paths = _run(lay, qkv, gains, dout, True)
    want, want_grads, want_paths = _run(lay, qkv, gains, dout, False)
    assert paths == {"attn.prep.fused": 1}
    assert want_paths == {"attn.prep.xla": 1}
    # float32: the same arithmetic in another order; bf16: one rounding
    # fewer (none between norm and rotation), so up to an ulp of the
    # largest value
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for a, b in zip(out, want):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        assert _gap(a, b) <= tol
    assert np.array_equal(np.asarray(out[2], np.float32),
                          np.asarray(want[2], np.float32))     # v: a copy
    assert _gap(grads[0], want_grads[0]) <= tol
    assert set(grads[1]) == set(lay._norm_keys())
    for key in grads[1]:
        assert grads[1][key].dtype == gains[key].dtype
        assert _gap(grads[1][key], want_grads[1][key]) <= tol


def test_positions_wrap_at_half_the_rows_under_the_block_diffusion_mask():
    """Row r of the second copy is rotated like row r - L/2 of the first,
    in the kernels as in the plain lines; without the mask it is not."""
    L = 64
    one = np.random.RandomState(3).randn(1, L // 2, 8 * 128)
    qkv = jnp.asarray(np.concatenate([one, one], axis=1), jnp.float32)
    for keys, same in ((dict(attn_mask="blockdiff", block_len=4), True),
                       (dict(causal=1), False)):
        lay = _layer(rope=1, **keys)
        (q, k, _), _, paths = _run(lay, qkv, {}, _operands(lay, 1, L,
                                                           jnp.float32)[2],
                                   True)
        assert paths == {"attn.prep.fused": 1}
        for t in (q, k):
            t = np.asarray(t)
            assert np.allclose(t[:, :, :L // 2], t[:, :, L // 2:],
                               atol=1e-6) == same


def test_several_row_tiles_and_batches_sum_the_gains_gradient():
    """1,024 rows are two tiles of 512 a batch row: the gains' gradient is
    the sum of four grid steps' partial rows."""
    lay = _layer(nh=2, nkv=1, qk_norm=1, rope=1)
    width = 4 * 128
    assert qk_prep_pallas.row_tile(1024, width, 4) == 512
    qkv, gains, dout = _operands(lay, 2, 1024, jnp.float32, seed=5)
    out, grads, paths = _run(lay, qkv, gains, dout, True)
    want, want_grads, _ = _run(lay, qkv, gains, dout, False)
    assert paths == {"attn.prep.fused": 1}
    for a, b in zip(out + (grads[0],), want + (want_grads[0],)):
        assert _gap(a, b) <= 2e-6
    for key in ("qnorm", "knorm"):
        assert _gap(grads[1][key], want_grads[1][key]) <= 5e-6


@pytest.mark.parametrize("L, width, itemsize, tile", [
    (16384, 5120, 2, 256),      # sdar-ep8-train-8k
    (8192, 4608, 2, 256),       # smallthinker-ep4-train-8k
    (1024, 512, 4, 512),
    (48, 512, 2, 16),
    (100, 512, 2, 0),           # no multiple of a bf16 sublane tile
])
def test_the_row_tile_follows_the_shape(L, width, itemsize, tile):
    assert qk_prep_pallas.row_tile(L, width, itemsize) == tile
    assert qk_prep_pallas.supports(L, 128, width, itemsize) == bool(tile)
    if tile:
        assert L % tile == 0
        assert 6 * tile * width * itemsize <= 20 << 20


class _Mesh:
    """Anything that is not None: the layer asks nothing else of it before
    it decides."""
    axis_names = ("data",)
    shape = {"data": 1}


# every case that keeps today's lines, with the kernels forced on
@pytest.mark.parametrize("keys, ctx", [
    (dict(dh=64, qk_norm=1, rope=1), {}),
    (dict(qk_norm=1, rope=1), {"decode_pos": 0}),
    (dict(qk_norm=1, rope=1), {"decode_pos": 3}),
    (dict(qk_norm=1, rope=1), {"mesh": _Mesh()}),
    (dict(), {}),
    (dict(rope=1, L=40), {}),
], ids=["head-of-64", "prefill-at-0", "decode-at-3", "a-mesh",
        "neither-flag", "rows-no-tile"])
def test_every_other_case_takes_the_plain_lines_and_says_so(keys, ctx):
    L = keys.pop("L", 32)
    lay = _layer(**keys)
    qkv, gains, dout = _operands(lay, 1, L, jnp.float32, seed=7)
    out, grads, paths = _run(lay, qkv, gains, dout, True, **ctx)
    want, want_grads, want_paths = _run(lay, qkv, gains, dout, False, **ctx)
    assert paths == want_paths == {"attn.prep.xla": 1}
    for a, b in zip(jax.tree_util.tree_leaves((out, grads)),
                    jax.tree_util.tree_leaves((want, want_grads))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_layer_takes_the_kernels_inside_its_qkv_scope():
    """``apply`` end to end on the forced path: the fused pass and the
    flash kernels give the dense path's rows, and the path account names
    both."""
    L, d = 256, 64
    lay = _layer(nh=2, nkv=1, qk_norm=1, rope=1, causal=1)
    lay.infer_shape([(1, d, 1, L)])
    rs = np.random.RandomState(9)
    w = {"wqkv": jnp.asarray(0.1 * rs.randn(d, 4 * 128), jnp.float32),
         "wo": jnp.asarray(0.1 * rs.randn(2 * 128, d), jnp.float32),
         "qnorm": jnp.asarray(1 + 0.1 * rs.randn(128), jnp.float32),
         "knorm": jnp.asarray(1 + 0.1 * rs.randn(128), jnp.float32)}
    x = jnp.asarray(rs.randn(1, d, 1, L), jnp.float32)

    def run(force):
        before = telemetry.paths()
        ops.set_use_pallas(force)
        try:
            y, = lay.apply(w, [x], ApplyContext(train=True))
        finally:
            ops.set_use_pallas(None)
        return np.asarray(y), {
            k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0) and not k.startswith("flash.")}
    y, paths = run(True)
    assert paths == {"attn.prep.fused": 1, "attn.flash": 1,
                     "attn.bwd.fused": 1}
    y_plain, paths = run(False)
    assert paths == {"attn.prep.xla": 1, "attn.dense": 1}
    np.testing.assert_allclose(y, y_plain, rtol=2e-4, atol=2e-5)
