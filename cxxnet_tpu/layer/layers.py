"""All layer implementations.

Each class reimplements one reference layer's behavior (config surface, shape
inference, numerics, checkpoint fields) as a pure jax function; the reference
file is cited per class. Backward passes come from autodiff — the reference's
hand-written Backprop gradients are exactly the analytic gradients of these
forward functions, which our golden tests verify (tests/test_layers.py).
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from .. import ops
from .base import (ApplyContext, Layer, LayerParam, Shape4, check,
                   sub_scope)


def _seed_from_key(key) -> jnp.ndarray:
    """int32 seed scalar from a PRNG key (typed or raw uint32 pair), for
    kernels that use the on-core TPU PRNG."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.reshape(-1)[-1].astype(jnp.int32)


def _flat2d(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0], -1)


# ---------------------------------------------------------------------------
# manual tensor parallelism inside pipeline stage bodies (ctx.manual_tp):
# output-feature sharding with GROUP-LOCAL collectives. The weight's
# leading (output) dim is a sequence of contiguous row blocks — one for a
# plain fullc/conv, g for a grouped conv, one per member for a fused
# sibling conv — and each model rank computes every block's 1/mp share.
# The tiled all_gather then returns channels in [rank, block] order;
# manual_tp_unpermute's static permutation restores the canonical
# [block, rows] order. ONE implementation serves all three layer paths so
# their pp x tp semantics cannot drift apart.
# ---------------------------------------------------------------------------
def manual_axis_size(ctx, axis):
    """Size of a composed mesh axis when applying inside a pipeline stage
    body (ctx.manual_tp), else 1 — layers use it to decide whether their
    manual-parallel path engages."""
    if not ctx.manual_tp or ctx.mesh is None:
        return 1
    return ctx.mesh.shape[axis] if axis in ctx.mesh.axis_names else 1


def manual_tp_blocks(shape0, blocks, mp):
    """The row-block sizes along the weight's output dim if every block
    divides by mp, else None (caller falls back to replicated compute)."""
    if mp <= 1 or any(n % mp for n in blocks) or sum(blocks) != shape0:
        return None
    return blocks


def manual_tp_local_rows(w, blocks, mp):
    """Slice this model rank's share of every row block and concatenate."""
    midx = jax.lax.axis_index("model")
    parts, off = [], 0
    for n in blocks:
        loc = n // mp
        parts.append(jax.lax.dynamic_slice_in_dim(
            w, off + midx * loc, loc, 0))
        off += n
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


def manual_tp_unpermute(blocks, mp):
    """Static channel permutation mapping the tiled-gather order
    [rank, block, local-rows] back to canonical [block, rows]; None when
    the gather order is already canonical (single block)."""
    if len(blocks) == 1:
        return None
    L = sum(n // mp for n in blocks)
    perm, off_j = [], 0
    for n in blocks:
        loc = n // mp
        for r in range(mp):
            perm.extend(range(r * L + off_j, r * L + off_j + loc))
        off_j += loc
    return np.asarray(perm)


def manual_tp_gather(y, blocks, mp, axis):
    """Group-local all_gather of the sharded output dim + reorder."""
    y = jax.lax.all_gather(y, "model", axis=axis, tiled=True)
    perm = manual_tp_unpermute(blocks, mp)
    if perm is not None:
        y = jnp.take(y, perm, axis=axis)
    return y


# ---------------------------------------------------------------------------
# dense layers
# ---------------------------------------------------------------------------
class FullConnectLayer(Layer):
    """Dense layer: out = in . W^T + b  (src/layer/fullc_layer-inl.hpp:14).

    W is stored (num_hidden, num_input) exactly like the reference so model
    files are interchangeable. On TPU the matmul runs on the MXU; XLA fuses
    the bias add.
    """

    type_name = "fullc"

    def __init__(self):
        super().__init__()
        self.fullc_gather = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "fullc_gather":
            self.fullc_gather = int(val)

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "FullcLayer: only support 1-1 connection")
        b, c, h, w = in_shapes[0]
        check(c == 1 and h == 1, "FullcLayer: input need to be a matrix")
        check(self.param.num_hidden > 0, "FullcLayer: must set nhidden correctly")
        if self.param.num_input_node == 0:
            self.param.num_input_node = w
        else:
            check(self.param.num_input_node == w,
                  "FullcLayer: input hidden nodes is not consistent")
        return [(b, 1, 1, self.param.num_hidden)]

    def init_params(self, rng):
        p = self.param
        wmat = p.rand_init_weight(rng, (p.num_hidden, p.num_input_node),
                                  in_num=p.num_input_node, out_num=p.num_hidden)
        out = {"wmat": wmat}
        if p.no_bias == 0:
            out["bias"] = np.full((p.num_hidden,), p.init_bias, np.float32)
        return out

    def apply(self, params, inputs, ctx):
        x = _flat2d(inputs[0])
        w = params["wmat"]
        mp = manual_axis_size(ctx, "model")
        blocks = manual_tp_blocks(w.shape[0], [w.shape[0]], mp)
        if blocks:
            # column parallelism inside a pipeline stage body (manual
            # shard_map): each model rank computes its slice of the output
            # features and the group-local all-gather rebuilds the full
            # row — 1/mp of the matmul FLOPs per device, collectives only
            # among model pairs at this pipe rank. The weight-grad psum
            # over model comes from the shard_map transpose (replicated
            # input ⇒ summed cotangents), mirroring fullc_gather's local
            # recompute (src/updater/async_updater-inl.hpp:67-92).
            y = manual_tp_gather(x @ manual_tp_local_rows(w, blocks, mp).T,
                                 blocks, mp, axis=1)
        else:
            y = x @ w.T
        if self.param.no_bias == 0:
            y = y + params["bias"]
        return [y.reshape(y.shape[0], 1, 1, y.shape[1])]

    def visit_order(self):
        if self.param.no_bias == 0:
            return [("wmat", "wmat"), ("bias", "bias")]
        return [("wmat", "wmat")]

    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["wmat"])
        w.write_tensor(params.get("bias", np.zeros((self.param.num_hidden,), np.float32)))

    def load_model(self, r):
        self.param.load(r)
        wmat = r.read_tensor()
        bias = r.read_tensor()
        out = {"wmat": wmat}
        if self.param.no_bias == 0:
            out["bias"] = bias
        return out


class FixConnectLayer(Layer):
    """Frozen dense layer whose weight comes from a sparse-matrix text file
    (src/layer/fixconn_layer-inl.hpp:14). File format: header "nrow ncol nnz"
    then nnz lines of "row col value". No weight gradient."""

    type_name = "fixconn"

    def __init__(self):
        super().__init__()
        self.fname_weight = "NULL"

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "fixconn_weight":
            self.fname_weight = val

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "FixConnLayer: only support 1-1 connection")
        b, c, h, w = in_shapes[0]
        check(c == 1 and h == 1, "FixConnLayer: input need to be a matrix")
        check(self.param.num_hidden > 0, "FixConnLayer: must set nhidden correctly")
        check(self.fname_weight != "NULL", "FixConnLayer: must specify fixconn_weight")
        wm = np.zeros((self.param.num_hidden, w), np.float32)
        with open(self.fname_weight) as f:
            toks = f.read().split()
        nrow, ncol, nnz = int(toks[0]), int(toks[1]), int(toks[2])
        check(nrow == wm.shape[0] and ncol == wm.shape[1],
              "FixConnLayer: fixconn_weight shape do not match architecture")
        for i in range(nnz):
            x, y, v = int(toks[3 + 3 * i]), int(toks[4 + 3 * i]), float(toks[5 + 3 * i])
            check(0 <= x < wm.shape[0] and 0 <= y < wm.shape[1],
                  "FixConnLayer: fixconn_weight index exceed matrix shape")
            wm[x, y] = v
        self._wmat = wm
        return [(b, 1, 1, self.param.num_hidden)]

    def init_params(self, rng):
        return {"wmat": self._wmat}

    # the frozen weight still travels with the model so a loaded net runs
    # without re-reading the sparse text file
    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["wmat"])

    def load_model(self, r):
        self.param.load(r)
        wmat = r.read_tensor()
        self._wmat = wmat
        return {"wmat": wmat}

    def apply(self, params, inputs, ctx):
        w = jax.lax.stop_gradient(params["wmat"])
        x = _flat2d(inputs[0])
        y = x @ w.T
        return [y.reshape(y.shape[0], 1, 1, y.shape[1])]


class BiasLayer(Layer):
    """Self-loop additive bias on flat nodes (src/layer/bias_layer-inl.hpp:14)."""

    type_name = "bias"
    self_loop = True

    def infer_shape(self, in_shapes):
        b, c, h, w = in_shapes[0]
        check(c == 1 and h == 1, "BiasLayer only works for flatten node so far")
        if self.param.num_input_node == 0:
            self.param.num_input_node = w
        else:
            check(self.param.num_input_node == w,
                  "BiasLayer: input hidden nodes is not consistent")
        return [in_shapes[0]]

    def init_params(self, rng):
        return {"bias": np.full((self.param.num_input_node,),
                                self.param.init_bias, np.float32)}

    def apply(self, params, inputs, ctx):
        return [inputs[0] + params["bias"]]

    def visit_order(self):
        return [("bias", "bias")]

    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["bias"])

    def load_model(self, r):
        self.param.load(r)
        return {"bias": r.read_tensor()}


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------
class ActivationLayer(Layer):
    """Elementwise activation (src/layer/activation_layer-inl.hpp:12 over the
    op structs in src/layer/op.h)."""

    fn = staticmethod(lambda x: x)
    layout_support = "any"

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "ActivationLayer only support 1-1 connection")
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        return [self.fn(inputs[0])]


class ReluLayer(ActivationLayer):
    type_name = "relu"
    fn = staticmethod(lambda x: jnp.maximum(x, 0.0))


class SigmoidLayer(ActivationLayer):
    type_name = "sigmoid"
    fn = staticmethod(jax.nn.sigmoid)


class TanhLayer(ActivationLayer):
    type_name = "tanh"
    fn = staticmethod(jnp.tanh)


class SoftplusLayer(ActivationLayer):
    """softplus is parseable in the reference (layer.h:331) but missing from
    its factory — we implement it properly instead of erroring."""
    type_name = "softplus"
    fn = staticmethod(jax.nn.softplus)


class XeluLayer(Layer):
    """Leaky relu with divisor b: y = x > 0 ? x : x/b
    (src/layer/xelu_layer-inl.hpp:15)."""

    type_name = "xelu"
    layout_support = "any"

    def __init__(self):
        super().__init__()
        self.b = 5.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "b":
            self.b = float(val)

    def infer_shape(self, in_shapes):
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        return [ops.xelu(inputs[0], self.b)]


class InsanityLayer(Layer):
    """RReLU (src/layer/insanity_layer-inl.hpp:14): during training the
    negative part is divided by a per-element random slope in [lb, ub]; at
    eval by the mean slope. calm_start/calm_end linearly anneal [lb, ub]
    toward the midpoint (the reference accumulates the shrink statefully
    across forward calls; we use the intended linear schedule on the update
    counter)."""

    type_name = "insanity"
    layout_support = "any"

    def __init__(self):
        super().__init__()
        self.lb = 5.0
        self.ub = 10.0
        self.calm_start = 0
        self.calm_end = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "lb":
            self.lb = float(val)
        if name == "ub":
            self.ub = float(val)
        if name == "calm_start":
            self.calm_start = int(val)
        if name == "calm_end":
            self.calm_end = int(val)

    def infer_shape(self, in_shapes):
        return [in_shapes[0]]

    def _bounds(self, epoch):
        mid = (self.lb + self.ub) / 2.0
        if self.calm_end > self.calm_start:
            frac = jnp.clip((epoch - self.calm_start)
                            / float(self.calm_end - self.calm_start), 0.0, 1.0)
        else:
            frac = 0.0
        ub = self.ub - (self.ub - mid) * frac
        lb = self.lb + (mid - self.lb) * frac
        return lb, ub

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        lb, ub = self._bounds(ctx.epoch)
        if ctx.train:
            if ops.use_pallas():
                # draw the slope with the on-core TPU PRNG (no HBM round
                # trip for the random bits); stop_gradient as the mask is a
                # constant of the draw, not a function of x
                from ..ops import pallas_kernels
                seed = _seed_from_key(ctx.rng)
                mask = jax.lax.stop_gradient(pallas_kernels.rrelu_mask(
                    seed, x.shape, lb, ub, x.dtype))
            else:
                u = jax.random.uniform(ctx.rng, x.shape, x.dtype)
                mask = u * (ub - lb) + lb
            return [ops.xelu(x, mask)]
        return [ops.xelu(x, (self.lb + self.ub) / 2.0)]


class PReluLayer(Layer):
    """Learnable per-channel negative slope, optional training noise
    (src/layer/prelu_layer-inl.hpp:48). Slope mask is clipped to [0, 1];
    y = x > 0 ? x : x * mask."""

    type_name = "prelu"

    def __init__(self):
        super().__init__()
        self.init_slope = 0.25
        self.init_random = 0
        self.random = 0.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "init_slope":
            self.init_slope = float(val)
        if name == "random_slope":
            self.init_random = int(val)
        if name == "random":
            self.random = float(val)

    def infer_shape(self, in_shapes):
        b, c, h, w = in_shapes[0]
        self.channel = w if c == 1 else c
        self.is_fc = (c == 1)
        return [in_shapes[0]]

    def init_params(self, rng):
        if self.init_random == 0:
            slope = np.full((self.channel,), self.init_slope, np.float32)
        else:
            slope = (rng.uniform(0, 1, (self.channel,)) * self.init_slope).astype(np.float32)
        return {"slope": slope}

    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        slope = params["slope"]
        bshape = ((1, 1, 1, self.channel)
                  if self.is_fc or ctx.channels_last
                  else (1, self.channel, 1, 1))
        mask = jnp.broadcast_to(slope.reshape(bshape), x.shape)
        if ctx.train and self.random != 0.0:
            u = jax.random.uniform(ctx.rng, x.shape, x.dtype)
            mask = mask * (1 + u * self.random * 2.0 - self.random)
        mask = jnp.clip(mask, 0.0, 1.0)
        return [ops.mxelu(x, mask)]

    def visit_order(self):
        # the reference visits the slope under the "bias" tag
        # (prelu_layer-inl.hpp ApplyVisitor)
        return [("bias", "slope")]

    def save_model(self, w, params):
        w.write_tensor(params["slope"])

    def load_model(self, r):
        return {"slope": r.read_tensor()}


class MaxoutLayer(Layer):
    """Channel-group maxout. The reference parses ``maxout`` (layer.h:342)
    but never implemented it; we provide the standard formulation: every
    ``ngroup`` *adjacent* channels (features for flat input) form one piece
    reduced with max, so out[j] = max(in[j*g : (j+1)*g])."""

    type_name = "maxout"

    def infer_shape(self, in_shapes):
        b, c, h, w = in_shapes[0]
        g = self.param.num_group
        if c == 1:
            check(w % g == 0, "maxout: input width must divide ngroup")
            return [(b, 1, 1, w // g)]
        check(c % g == 0, "maxout: input channels must divide ngroup")
        return [(b, c // g, h, w)]

    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        g = self.param.num_group
        if ctx.channels_last:
            b, h, w, c = x.shape
            return [jnp.max(x.reshape(b, h, w, c // g, g), axis=4)]
        b, c, h, w = x.shape
        if c == 1:
            return [jnp.max(x.reshape(b, 1, 1, w // g, g), axis=4)]
        return [jnp.max(x.reshape(b, c // g, g, h, w), axis=2)]


# ---------------------------------------------------------------------------
# shape / routing layers
# ---------------------------------------------------------------------------
class FlattenLayer(Layer):
    """(b,c,h,w) -> (b,1,1,c*h*w) (src/layer/flatten_layer-inl.hpp:11)."""

    type_name = "flatten"

    def infer_shape(self, in_shapes):
        b, c, h, w = in_shapes[0]
        return [(b, 1, 1, c * h * w)]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        return [x.reshape(x.shape[0], 1, 1, -1)]


class ConcatLayer(Layer):
    """N->1 concat along dim 3 (src/layer/concat_layer-inl.hpp:12)."""

    type_name = "concat"
    dim = 3

    def infer_shape(self, in_shapes):
        check(1 < len(in_shapes) <= 4, "Concat layer supports 2-4 inputs")
        oshape = list(in_shapes[0])
        total = 0
        for s in in_shapes:
            total += s[self.dim]
            for j in range(4):
                if j != self.dim:
                    check(s[j] == oshape[j], "Concat shape doesn't match")
        oshape[self.dim] = total
        return [tuple(oshape)]

    def apply(self, params, inputs, ctx):
        return [jnp.concatenate(inputs, axis=self.dim)]


class ChConcatLayer(ConcatLayer):
    """N->1 concat along the channel dim (layer_impl-inl.hpp:62)."""
    type_name = "ch_concat"
    dim = 1
    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        axis = 3 if ctx.channels_last else 1
        return [jnp.concatenate(inputs, axis=axis)]


class SplitLayer(Layer):
    """1->N copy forward, summed gradients backward
    (src/layer/split_layer-inl.hpp:12)."""

    type_name = "split"
    layout_support = "any"

    def __init__(self, n_out: int = 2):
        super().__init__()
        # fan-out; the net sets this from the connection's out-node count
        # before infer_shape (the reference derives it from nodes_out.size())
        self.n_out = n_out

    def infer_shape(self, in_shapes):
        return [in_shapes[0]] * self.n_out

    def apply(self, params, inputs, ctx):
        return [inputs[0]] * self.n_out


class DropoutLayer(Layer):
    """Inverted dropout, self-loop (src/layer/dropout_layer-inl.hpp:12)."""

    type_name = "dropout"
    self_loop = True
    layout_support = "any"

    def __init__(self):
        super().__init__()
        self.threshold = 0.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "threshold":
            self.threshold = float(val)

    def infer_shape(self, in_shapes):
        check(0.0 <= self.threshold < 1.0, "DropoutLayer: invalid dropout threshold")
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if not ctx.train:
            return [x]
        pkeep = 1.0 - self.threshold
        mask = (jax.random.uniform(ctx.rng, x.shape, x.dtype) < pkeep) / pkeep
        return [x * mask]


# ---------------------------------------------------------------------------
# convolution / pooling / normalization
# ---------------------------------------------------------------------------
class ConvolutionLayer(Layer):
    """Grouped 2-D convolution (src/layer/convolution_layer-inl.hpp:13).

    The reference im2cols and GEMMs on a chunked batch; on TPU this is one
    XLA convolution on the MXU with feature_group_count = ngroup. Weights are
    stored in the reference's (ngroup, co/g, ci/g*kh*kw) layout for model
    compatibility and reshaped to OIHW at apply time (a free reshape under
    jit)."""

    type_name = "conv"

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "ConvolutionLayer only support 1-1 connection")
        p = self.param
        b, c, h, w = in_shapes[0]
        check(c % p.num_group == 0, "input channels must divide group size")
        check(p.num_channel % p.num_group == 0, "output channels must divide group size")
        check(p.num_channel > 0, "must set nchannel correctly")
        check(p.kernel_height > 0 and p.kernel_width > 0, "must set kernel_size correctly")
        check(p.kernel_width <= w + 2 * p.pad_x
              and p.kernel_height <= h + 2 * p.pad_y,
              "kernel size exceed input")
        if p.num_input_channel == 0:
            p.num_input_channel = c
        else:
            check(p.num_input_channel == c,
                  "ConvolutionLayer: number of input channels is not consistent")
        oh = ops.conv_out_dim(h, p.kernel_height, p.stride, p.pad_y)
        ow = ops.conv_out_dim(w, p.kernel_width, p.stride, p.pad_x)
        return [(b, p.num_channel, oh, ow)]

    def init_params(self, rng):
        p = self.param
        g = p.num_group
        shape = (g, p.num_channel // g,
                 p.num_input_channel // g * p.kernel_height * p.kernel_width)
        wmat = p.rand_init_weight(rng, shape, in_num=shape[2], out_num=shape[1])
        out = {"wmat": wmat}
        if p.no_bias == 0:
            out["bias"] = np.full((p.num_channel,), p.init_bias, np.float32)
        return out

    def _kernel_oihw(self, wmat: jnp.ndarray) -> jnp.ndarray:
        p = self.param
        return wmat.reshape(p.num_channel, p.num_input_channel // p.num_group,
                            p.kernel_height, p.kernel_width)

    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        p = self.param
        layout = "NHWC" if ctx.channels_last else "NCHW"
        w = self._kernel_oihw(params["wmat"])
        mp = manual_axis_size(ctx, "model")
        g = p.num_group
        blocks = manual_tp_blocks(p.num_channel, [p.num_channel // g] * g,
                                  mp)
        if blocks:
            # output-feature-sharded convolution inside a pipeline stage
            # body (the manual twin of tp_spec's P(None, "model", None)
            # GSPMD placement): each model rank convolves its 1/mp share
            # of every group's output channels (group structure survives:
            # every group shrinks equally) and the group-local all-gather
            # + unpermute rebuilds the canonical map — same split the
            # reference's ngroup put in-layer
            # (src/layer/convolution_layer-inl.hpp:92-96)
            y = ops.conv2d(inputs[0], manual_tp_local_rows(w, blocks, mp),
                           stride=p.stride, pad=(p.pad_y, p.pad_x),
                           groups=g, layout=layout)
            y = manual_tp_gather(y, blocks, mp,
                                 axis=3 if ctx.channels_last else 1)
        elif (p.kernel_height == p.kernel_width == p.stride == 1 and g == 1
              and p.pad_y == p.pad_x == 0
              and inputs[0].shape[1 if ctx.channels_last else 2] == 1):
            # a 1x1 conv over a sequence node (b, C, 1, L) is a product per
            # position (the transformer stacks' FFN and vocabulary head):
            # as a dot. XLA's convolution with one row of 8,192 positions
            # ran the head at an eighth of the dot's rate on the chip
            # (PERF.md section 6, PR 29); feature maps (h > 1) keep conv2d
            w2 = w.reshape(p.num_channel, p.num_input_channel)
            y = jnp.dot(inputs[0], w2.T) if ctx.channels_last else \
                jnp.einsum("bchl,oc->bohl", inputs[0], w2)
        else:
            y = ops.conv2d(inputs[0], w, stride=p.stride,
                           pad=(p.pad_y, p.pad_x),
                           groups=g, layout=layout)
        if p.no_bias == 0:
            bshape = (1, 1, 1, -1) if ctx.channels_last else (1, -1, 1, 1)
            y = y + params["bias"].reshape(bshape)
        return [y]

    def visit_order(self):
        if self.param.no_bias == 0:
            return [("wmat", "wmat"), ("bias", "bias")]
        return [("wmat", "wmat")]

    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["wmat"])
        w.write_tensor(params.get("bias",
                                  np.zeros((self.param.num_channel,), np.float32)))

    def load_model(self, r):
        self.param.load(r)
        wmat = r.read_tensor()
        bias = r.read_tensor()
        out = {"wmat": wmat}
        if self.param.no_bias == 0:
            out["bias"] = bias
        return out


class PoolingLayer(Layer):
    """max/sum/avg pooling with the reference's ceil-mode shapes
    (src/layer/pooling_layer-inl.hpp:17)."""

    mode = "max"
    layout_support = "nhwc"

    def infer_shape(self, in_shapes):
        p = self.param
        b, c, h, w = in_shapes[0]
        check(p.kernel_height > 0 and p.kernel_width > 0,
              "must set kernel_size correctly")
        h2, w2 = h + 2 * p.pad_y, w + 2 * p.pad_x
        check(p.kernel_width <= w2 and p.kernel_height <= h2,
              "kernel size exceed input")
        oh = ops.pool_out_dim(h2, p.kernel_height, p.stride)
        ow = ops.pool_out_dim(w2, p.kernel_width, p.stride)
        return [(b, c, oh, ow)]

    def _pre(self, x):
        return x

    def apply(self, params, inputs, ctx):
        p = self.param
        x = self._pre(inputs[0])
        layout = "NHWC" if ctx.channels_last else "NCHW"
        return [ops.pool2d(x, self.mode, (p.kernel_height, p.kernel_width),
                           p.stride, pad=(p.pad_y, p.pad_x), layout=layout)]


class MaxPoolingLayer(PoolingLayer):
    type_name = "max_pooling"
    mode = "max"


class SumPoolingLayer(PoolingLayer):
    type_name = "sum_pooling"
    mode = "sum"


class AvgPoolingLayer(PoolingLayer):
    type_name = "avg_pooling"
    mode = "avg"


class ReluMaxPoolingLayer(MaxPoolingLayer):
    """Fused relu-then-maxpool (layer_impl-inl.hpp:55-56); XLA fuses the relu
    into the reduce_window."""
    type_name = "relu_max_pooling"

    def _pre(self, x):
        return jnp.maximum(x, 0.0)


class InsanityPoolingLayer(MaxPoolingLayer):
    """Stochastic jittered max-pooling
    (src/layer/insanity_pooling_layer-inl.hpp:13-100): during training each
    source pixel is, with probability 1-p_keep, displaced one step
    up/down/left/right (equiprobable, clamped to the image) before the max
    window reduction. Expressed as a gather + reduce_window — the autodiff
    gradient equals the reference's InsanityUnPooling. Eval = plain max-pool
    of the undisplaced input."""

    type_name = "insanity_max_pooling"

    def __init__(self):
        super().__init__()
        self.p_keep = 1.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "keep":
            self.p_keep = float(val)

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if ctx.train:
            if ctx.channels_last:
                b, h, w, c = x.shape
                yy = jnp.arange(h).reshape(1, h, 1, 1)
                xx = jnp.arange(w).reshape(1, 1, w, 1)
            else:
                b, c, h, w = x.shape
                yy = jnp.arange(h).reshape(1, 1, h, 1)
                xx = jnp.arange(w).reshape(1, 1, 1, w)
            flag = jax.random.uniform(ctx.rng, x.shape, x.dtype)
            delta = (1.0 - self.p_keep) / 4.0
            loc_y = jnp.broadcast_to(yy, x.shape)
            loc_x = jnp.broadcast_to(xx, x.shape)
            loc_y = jnp.where((flag >= self.p_keep) & (flag < self.p_keep + delta),
                              jnp.maximum(loc_y - 1, 0), loc_y)
            loc_y = jnp.where((flag >= self.p_keep + delta) & (flag < self.p_keep + 2 * delta),
                              jnp.minimum(loc_y + 1, h - 1), loc_y)
            loc_x = jnp.where((flag >= self.p_keep + 2 * delta) & (flag < self.p_keep + 3 * delta),
                              jnp.maximum(loc_x - 1, 0), loc_x)
            loc_x = jnp.where(flag >= self.p_keep + 3 * delta,
                              jnp.minimum(loc_x + 1, w - 1), loc_x)
            flat_idx = loc_y * w + loc_x
            if ctx.channels_last:
                # displace over the flattened spatial axis, channels minor
                xf = x.reshape(b, h * w, c)
                x = jnp.take_along_axis(
                    xf, flat_idx.reshape(b, h * w, c), axis=1)
                x = x.reshape(b, h, w, c)
            else:
                xf = x.reshape(b, c, h * w)
                x = jnp.take_along_axis(
                    xf, flat_idx.reshape(b, c, h * w), axis=2)
                x = x.reshape(b, c, h, w)
        # base-class pooling handles layout AND ceil-mode padding (the
        # inherited infer_shape accounts for pad_y/pad_x, so apply must
        # too — a direct pool2d call without pad would shrink the node)
        return super().apply(params, [x], ctx)


class LRNLayer(Layer):
    """AlexNet cross-channel LRN (src/layer/lrn_layer-inl.hpp:12)."""

    type_name = "lrn"
    layout_support = "nhwc"

    def __init__(self):
        super().__init__()
        self.nsize = 3
        self.alpha = 0.0
        self.beta = 0.0
        self.knorm = 1.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "local_size":
            self.nsize = int(val)
        if name == "alpha":
            self.alpha = float(val)
        if name == "beta":
            self.beta = float(val)
        if name == "knorm":
            self.knorm = float(val)

    def infer_shape(self, in_shapes):
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        layout = "NHWC" if ctx.channels_last else "NCHW"
        x = inputs[0]
        args = (self.nsize, self.alpha, self.beta, self.knorm, layout)
        mesh = ctx.mesh
        data = 1
        if mesh is not None and "data" in mesh.axis_names:
            data = mesh.shape["data"]
        if layout != "NHWC" or data == 1 or ctx.manual_tp:
            # one device, or inside a pipeline stage (manual_tp), where
            # the code is per-device already
            return [ops.lrn(x, *args)]
        # the channels-last kernel is pointwise in the batch and
        # pallas_call has no GSPMD partitioning rule: under shard_map with
        # the batch left on "data" (as attention's flash kernel), or the
        # partitioner gathers the global batch on every chip. Decided by
        # the rows a device holds
        if x.shape[0] % data or not ops.lrn_fused(
                (x.shape[0] // data,) + x.shape[1:], x.dtype, layout):
            return [ops.lrn_reduce_window(x, *args)]
        from ..parallel._compat import shard_map
        from jax.sharding import PartitionSpec as P
        spec = P("data", None, None, None)
        return [shard_map(lambda v: ops.lrn(v, *args), mesh=mesh,
                          in_specs=(spec,), out_specs=spec)(x)]


class BatchNormLayer(Layer):
    """Batch normalization (src/layer/batch_norm_layer-inl.hpp:14).

    Reference quirk reproduced by default: eval mode recomputes minibatch
    statistics — no running averages (doc/layer.md caveat). Opt in to
    running statistics with ``moving_average = 1`` (+ ``bn_momentum``,
    default 0.9): training then tracks EMA mean/var (recorded through
    ctx.state_updates, merged into params by the trainer after the step),
    and eval normalizes with them — making batch-1 inference sound."""

    type_name = "batch_norm"

    def __init__(self):
        super().__init__()
        self.init_slope = 1.0
        self.init_bias = 0.0
        self.eps = 1e-10
        self.moving_average = 0
        self.bn_momentum = 0.9

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "init_slope":
            self.init_slope = float(val)
        if name == "init_bias":
            self.init_bias = float(val)
        if name == "eps":
            self.eps = float(val)
        if name == "moving_average":
            self.moving_average = int(val)
        if name == "bn_momentum":
            self.bn_momentum = float(val)

    def infer_shape(self, in_shapes):
        b, c, h, w = in_shapes[0]
        self.is_fc = (c == 1)
        self.channel = w if self.is_fc else c
        return [in_shapes[0]]

    def init_params(self, rng):
        out = {"slope": np.full((self.channel,), self.init_slope, np.float32),
               "bias": np.full((self.channel,), self.init_bias, np.float32)}
        if self.moving_average:
            out["running_mean"] = np.zeros((self.channel,), np.float32)
            out["running_var"] = np.ones((self.channel,), np.float32)
        return out

    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if self.is_fc or ctx.channels_last:
            # flat features, or conv-mode channels-last: C is minor
            axes = (0, 1, 2)
            bshape = (1, 1, 1, self.channel)
        else:
            axes = (0, 2, 3)
            bshape = (1, self.channel, 1, 1)
        use_running = self.moving_average and not ctx.train
        if use_running:
            mean = params["running_mean"].reshape(bshape).astype(x.dtype)
            var = params["running_var"].reshape(bshape).astype(x.dtype)
        else:
            mean = jnp.mean(x, axis=axes).reshape(bshape)
            var = jnp.mean(jnp.square(x - mean), axis=axes).reshape(bshape)
        if self.moving_average and ctx.train:
            m = self.bn_momentum
            # chain off any pending update so weight-shared BN folds every
            # shared application's batch stats into the EMA, not just the
            # last one
            km, kv = ((ctx.layer_index, "running_mean"),
                      (ctx.layer_index, "running_var"))
            base_mean = ctx.state_updates.get(km, params["running_mean"])
            base_var = ctx.state_updates.get(kv, params["running_var"])
            new_mean = (m * base_mean
                        + (1 - m) * mean.reshape(-1).astype(jnp.float32))
            new_var = (m * base_var
                       + (1 - m) * var.reshape(-1).astype(jnp.float32))
            ctx.state_updates[km] = jax.lax.stop_gradient(new_mean)
            ctx.state_updates[kv] = jax.lax.stop_gradient(new_var)
        xhat = (x - mean) / jnp.sqrt(var + self.eps)
        slope = params["slope"].reshape(bshape)
        bias = params["bias"].reshape(bshape)
        return [xhat * slope + bias]

    def visit_order(self):
        # reference visits slope under "wmat", bias under "bias"; running
        # stats are deliberately absent (no optimizer, no weight ABI)
        return [("wmat", "slope"), ("bias", "bias")]

    def state_keys(self):
        return ("running_mean", "running_var") if self.moving_average else ()

    def save_model(self, w, params):
        w.write_tensor(params["slope"])
        w.write_tensor(params["bias"])
        if self.moving_average:
            w.write_tensor(params["running_mean"])
            w.write_tensor(params["running_var"])

    def load_model(self, r):
        out = {"slope": r.read_tensor(), "bias": r.read_tensor()}
        if self.moving_average:
            out["running_mean"] = r.read_tensor()
            out["running_var"] = r.read_tensor()
        return out


# ---------------------------------------------------------------------------
# loss layers (self-loop): forward transforms the node, and the scalar loss
# they contribute has exactly the reference's hand-set gradient:
#   d loss / d logits = (transformed - target) * grad_scale/(batch*update_period)
# (reference: loss_layer_base-inl.hpp:55-66 — note we keep the whole thing
# on-device instead of the reference's CPU roundtrip :88-100)
# ---------------------------------------------------------------------------
class LossLayerBase(Layer):
    self_loop = True
    is_loss = True

    def __init__(self):
        super().__init__()
        self.target = "label"
        self.batch_size = 1
        self.update_period = 1
        self.grad_scale = 1.0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "batch_size":
            self.batch_size = int(val)
        if name == "update_period":
            self.update_period = int(val)
        if name == "target":
            self.target = val
        if name == "grad_scale":
            self.grad_scale = float(val)

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "LossLayer: only support 1-1 connection")
        return [in_shapes[0]]

    def _scale(self):
        return self.grad_scale / (self.batch_size * self.update_period)

    def transform(self, x2d):
        """Forward transform of the node (e.g. softmax)."""
        return x2d

    def loss_term(self, x2d, label):
        """Scalar loss whose gradient wrt x2d matches the reference grad."""
        raise NotImplementedError

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        x2d = _flat2d(x)
        out = self.transform(x2d)
        if ctx.labels is not None:
            label = ctx.labels.field(self.target)
            ctx.losses.append(self.loss_term(x2d, label))
        return [out.reshape(x.shape)]


class SoftmaxLayer(LossLayerBase):
    """Softmax + cross-entropy (src/layer/loss/softmax_layer-inl.hpp:12).
    grad = (p - onehot(label)) * scale == d/dlogits of scale * sum_i CE_i.

    ``seq = 1`` (beyond the reference) switches to per-position CE for
    sequence nodes (b, vocab, 1, L): softmax over the channel (vocab) dim at
    every position, with the target field carrying L labels per row — the
    language-modeling loss for the attention stack."""

    type_name = "softmax"

    def __init__(self):
        super().__init__()
        self.seq = 0
        # label_smooth = eps (beyond the reference): targets become
        # (1-eps) one-hot + eps/K uniform; grad = (p - smoothed) * scale
        self.label_smooth = 0.0
        # weight_target = <label field> (seq = 1 only): each position's CE
        # is multiplied by that field's value before the sum (0 leaves a
        # position out; a masked-token loss weights the masked ones)
        self.weight_target = ""

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "seq":
            self.seq = int(val)
        if name == "label_smooth":
            self.label_smooth = float(val)
            check(0.0 <= self.label_smooth < 1.0,
                  "label_smooth must be in [0, 1)")
        if name == "weight_target":
            self.weight_target = val

    def transform(self, x2d):
        return jax.nn.softmax(x2d, axis=-1)

    def _ce(self, logp, target_logp_row):
        eps = self.label_smooth
        if eps == 0.0:
            return -target_logp_row
        k = logp.shape[-1]
        return -((1.0 - eps) * target_logp_row
                 + eps / k * jnp.sum(logp, axis=-1))

    def loss_term(self, x2d, label):
        logp = jax.nn.log_softmax(x2d, axis=-1)
        idx = label[:, 0].astype(jnp.int32)
        tgt = jnp.take_along_axis(logp, idx[:, None], axis=1)[:, 0]
        return jnp.sum(self._ce(logp, tgt)) * self._scale()

    @property
    def layout_support(self):
        # the per-position loss reads (b, L, vocab): channels-last is its
        # own layout, and a transpose of the logits (1.2 GB in float32 at
        # 8,192 x 37,984) is a pass over them each way. The flat form
        # flattens in NCHW order and stays there.
        return "nhwc" if self.seq else "nchw"

    def apply(self, params, inputs, ctx):
        if not self.seq:
            check(not self.weight_target,
                  "softmax: weight_target needs seq = 1")
            return super().apply(params, inputs, ctx)
        x = inputs[0]
        if ctx.channels_last:
            b, h, L, v = x.shape
            logits = x.reshape(b, L, v)
        else:
            b, v, h, L = x.shape
            logits = x.reshape(b, v, L).transpose(0, 2, 1)  # (b, L, v)
        check(h == 1, "softmax seq=1 needs a (batch, vocab, 1, seq) node")
        out = jax.nn.softmax(logits, axis=-1)
        if ctx.labels is not None:
            label = ctx.labels.field(self.target)          # (b, L)
            check(label.shape[1] == L,
                  "softmax seq=1: label field width %d != seq length %d"
                  % (label.shape[1], L))
            logp = jax.nn.log_softmax(logits, axis=-1)
            idx = label.astype(jnp.int32)[..., None]
            tgt = jnp.take_along_axis(logp, idx, axis=2)[..., 0]
            ce = self._ce(logp, tgt)
            if self.weight_target:
                from ..utils import telemetry
                telemetry.count_path("loss.weighted")
                weight = ctx.labels.field(self.weight_target)
                check(weight.shape == ce.shape,
                      "softmax seq=1: weight field %s of shape %s for %s "
                      "positions" % (self.weight_target, weight.shape,
                                     ce.shape))
                ce = ce * weight.astype(ce.dtype)
            ctx.losses.append(jnp.sum(ce) / L * self._scale())
        if ctx.channels_last:
            return [out.reshape(b, 1, L, v)]
        return [out.transpose(0, 2, 1).reshape(b, v, 1, L)]


class L2LossLayer(LossLayerBase):
    """Identity forward; grad = (x - y) * scale
    (src/layer/loss/l2_loss_layer-inl.hpp:12)."""

    type_name = "l2_loss"

    def loss_term(self, x2d, label):
        return 0.5 * jnp.sum(jnp.square(x2d - label)) * self._scale()


class MultiLogisticLayer(LossLayerBase):
    """Elementwise sigmoid + logistic loss
    (src/layer/loss/multi_logistic_layer-inl.hpp:12).
    grad = (sigmoid(x) - y) * scale."""

    type_name = "multi_logistic"

    def transform(self, x2d):
        return jax.nn.sigmoid(x2d)

    def loss_term(self, x2d, label):
        # sum BCE with logits; gradient wrt x2d is sigmoid(x) - y
        bce = jnp.maximum(x2d, 0) - x2d * label + jnp.log1p(jnp.exp(-jnp.abs(x2d)))
        return jnp.sum(bce) * self._scale()


def yarn_inv_freq(base: float, rot: int, factor: float, original_len: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's (rot / 2,) rotation frequencies, float64 (Peng et al. 2023;
    transformers' ``_compute_yarn_parameters``): the plain frequencies
    ``base^(-2i / rot)`` where a pair turns more than ``beta_fast`` times
    over ``original_len`` positions, those over ``factor`` where it turns
    fewer than ``beta_slow`` times, and a linear ramp over the pairs
    between, the range's ends rounded outward."""
    half = rot // 2
    plain = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def pair_of(turns):
        return rot * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


class AttentionLayer(Layer):
    """Multi-head self-attention over sequence nodes (b, D, 1, L) — channels
    hold d_model so `conv kernel_size=1` serves as the position-wise FFN in
    transformer stacks. Beyond the reference (a CNN framework with no
    sequence axis); the long-context path of this framework.

    With a mesh carrying an "sp" axis (trainer config `seq_parallel = k`) the
    sequence dimension is sharded and attention runs as ring attention (K/V
    blocks rotating over ICI, `sp_mode = ring`, the default) or Ulysses
    all-to-all (`sp_mode = ulysses`). Single-device on TPU it runs the
    Pallas flash-attention kernel (ops/flash_attn.py — O(L) memory, no
    (L, L) score matrix) when shapes are tile-aligned, dense attention
    otherwise. Numerics match attention_reference in all modes
    (tests/test_parallel.py, tests/test_flash_attention.py)."""

    type_name = "attention"

    def __init__(self):
        super().__init__()
        self.nhead = 1
        self.causal = 0
        self.sp_mode = "ring"
        # rope = 1: rotary position embedding on q/k (relative positions
        # enter through the score phase; composes with every attention
        # path since the rotation happens before dispatch). Pair with
        # embed pos_embed = 0.
        self.rope = 0
        self.rope_base = 10000.0
        # rope_dim > 0: rotate the first rope_dim features of each head
        # (the (first half, second half) pairs within them) and pass the
        # others through; 0 -> the whole head
        self.rope_dim = 0
        # rope_type = yarn: YaRN's frequencies (Peng et al. 2023, as
        # transformers' _compute_yarn_parameters makes them) from
        # yarn_factor, yarn_original_len (the positions the model was
        # fitted to), yarn_beta_fast / yarn_beta_slow (the correction
        # range, in rotations), and cosines and sines both scaled by
        # yarn_attention_factor (0 -> 0.1 ln(yarn_factor) + 1)
        self.rope_type = "default"
        self.yarn = {"yarn_factor": 1.0, "yarn_original_len": 0.0,
                     "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
                     "yarn_attention_factor": 0.0}
        self.yarn_keys = set()          # the yarn_* keys the conf gave
        # head_gate = 1: each head's output times sigmoid(h W_gate), one
        # scalar a head and position from the layer's input h (leaf wgate,
        # d x nhead), before wo
        self.head_gate = 0
        # nkvhead < nhead: grouped-query attention — k/v projections carry
        # only nkvhead heads, broadcast to the query heads at dispatch
        # (0 -> = nhead, classic MHA)
        self.nkvhead = 0
        # head_dim > 0: the heads' own size, where nhead * head_dim is not
        # d_model (wqkv is d x (nhead + 2 nkvhead) head_dim, wo
        # (nhead head_dim) x d); 0 -> d_model / nhead
        self.head_dim = 0
        # attn_window > 0 (causal only): sliding-window attention — each
        # query sees only the last attn_window keys; flash kernels skip
        # out-of-window tiles wholesale
        self.attn_window = 0
        # decode_chunk > 0: KV-cached decode steps read the cache via a
        # chunked online-softmax while-loop (flash-decode) instead of
        # scoring the full static-length cache — the dense path's L_max
        # read per token is ~2x the useful traffic on average
        # (doc/performance.md decode roofline). Opt-in until measured.
        self.decode_chunk = 0
        # qk_norm = 1: an rmsnorm over each head's features on q and on k,
        # before the rotation (leaves qnorm / knorm of head_dim, shared by
        # the heads; eps qk_norm_eps, 1e-6 unless set)
        self.qk_norm = 0
        self.qk_norm_eps = 1e-6
        # attn_mask = blockdiff with block_len = B: block-diffusion
        # training. The rows are [noised copy | clean copy] of one
        # sequence, both at positions 0..rows/2-1 (the rotation wraps),
        # under parallel.block_diffusion_keep's mask
        self.attn_mask = "causal"
        self.block_len = 0
        # attn_mask = dsa with index_heads = J, index_dim = di, index_topk
        # = k: learned sparse attention in training (DeepSeek-Sparse-
        # Attention). An indexer of J heads of di features on one key head
        # scores every causal pair from the DETACHED input, each query
        # attends to the min(t + 1, k) keys of largest score (ops/dsa.py:
        # exact, lax.top_k's set), and the indexer learns from the
        # attention it selected for: the layer adds (1 / L) sum_t KL(mean
        # over heads of the attention's probabilities || softmax of the
        # index scores, both on the selected keys) to the step's loss.
        # Leaves widx_q, widx_k, widx_w, and the key's LayerNorm idx_gain /
        # idx_bias. Needs causal = 1.
        self.index_heads = 0
        self.index_dim = 0
        self.index_topk = 0
        self.batch_size = 1
        self.update_period = 1
        # what a layer under attn_mask = dsa leaves in ctx.layer_stats:
        # the pairs the step attended, and the index loss a sequence
        self.stat_names = ()
        self.stat_limits = {}

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "nhead":
            self.nhead = int(val)
        if name == "qk_norm":
            self.qk_norm = int(val)
        if name == "qk_norm_eps":
            self.qk_norm_eps = float(val)
        if name == "attn_mask":
            check(val in ("causal", "blockdiff", "dsa"),
                  "attn_mask must be causal (what the key causal then "
                  "decides), blockdiff or dsa")
            self.attn_mask = val
        if name == "block_len":
            self.block_len = int(val)
        if name in ("index_heads", "index_dim", "index_topk", "batch_size",
                    "update_period"):
            setattr(self, name, int(val))
        if name == "causal":
            self.causal = int(val)
        if name == "rope":
            self.rope = int(val)
        if name == "rope_base":
            self.rope_base = float(val)
        if name == "rope_dim":
            self.rope_dim = int(val)
        if name == "rope_type":
            check(val in ("default", "yarn"),
                  "rope_type must be default or yarn")
            self.rope_type = val
        if name in self.yarn:
            self.yarn[name] = float(val)
            self.yarn_keys.add(name)
        if name == "head_gate":
            self.head_gate = int(val)
        if name == "nkvhead":
            self.nkvhead = int(val)
        if name == "head_dim":
            self.head_dim = int(val)
        if name == "attn_window":
            self.attn_window = int(val)
        if name == "decode_chunk":
            self.decode_chunk = int(val)
        if name == "sp_mode":
            check(val in ("ring", "ulysses"),
                  "sp_mode must be ring or ulysses")
            self.sp_mode = val

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "AttentionLayer only support 1-1 connection")
        b, d, h, L = in_shapes[0]
        check(h == 1, "attention input must be (batch, d_model, 1, seq)")
        if self.head_dim:
            check(self.head_dim % 8 == 0,
                  "attention: head_dim = %d is not a multiple of 8, the "
                  "sublane tile of the flash kernels (ops/flash_attn.py)"
                  % self.head_dim)
        else:
            check(d % self.nhead == 0,
                  "nhead must divide d_model (or set head_dim)")
        self.param.num_input_channel = d
        if self.rope:
            check(self._dh() % 2 == 0,
                  "attention: rope rotates pairs of features and needs an "
                  "even head size, got %d (key head_dim, else d_model / "
                  "nhead)" % self._dh())
        if self.rope_dim:
            check(self.rope and self.rope_dim % 2 == 0
                  and 0 < self.rope_dim <= self._dh(),
                  "attention: rope_dim = %d must be an even number of a "
                  "head's %d features, with rope = 1"
                  % (self.rope_dim, self._dh()))
        if self.rope_type == "yarn":
            check(self.rope and self.yarn["yarn_factor"] > 1
                  and self.yarn["yarn_original_len"] > 0,
                  "attention: rope_type = yarn needs rope = 1, yarn_factor "
                  "> 1 and yarn_original_len, the positions the model was "
                  "fitted to")
        else:
            check(not self.yarn_keys,
                  "attention: %s mean nothing without rope_type = yarn"
                  % ", ".join(sorted(self.yarn_keys)))
        if self.nkvhead:
            check(self.nhead % self.nkvhead == 0,
                  "nkvhead must divide nhead")
        if self.attn_window:
            check(self.attn_window > 0, "attn_window must be positive")
            check(self.causal, "attn_window requires causal = 1")
        if self.attn_mask == "blockdiff":
            check(self.block_len > 0,
                  "attn_mask = blockdiff needs block_len, the positions a "
                  "block holds")
            check(not self.causal and not self.attn_window,
                  "attn_mask = blockdiff is its own mask: causal and "
                  "attn_window must be 0")
            check(L % 2 == 0 and (L // 2) % self.block_len == 0,
                  "attn_mask = blockdiff: the %d rows must be two copies "
                  "of whole blocks of %d" % (L, self.block_len))
        else:
            check(not self.block_len,
                  "block_len is the block of attn_mask = blockdiff and "
                  "means nothing under another mask")
        if self._dsa():
            check(self.index_heads > 0 and self.index_dim > 0
                  and self.index_topk > 0,
                  "attn_mask = dsa needs index_heads, index_dim and "
                  "index_topk: the indexer's heads, their size and the keys "
                  "a query keeps")
            check(self.causal and not self.attn_window,
                  "attn_mask = dsa selects among the keys at or before the "
                  "query: causal must be 1 and attn_window 0 (a window "
                  "together with a selection is not written)")
            check(self.index_dim % 2 == 0,
                  "attn_mask = dsa: the indexer's heads are rotated over "
                  "all their features, index_dim must be even")
            self.stat_names = ("dsa.selected", "dsa.index_loss")
        else:
            check(not (self.index_heads or self.index_dim
                       or self.index_topk),
                  "index_heads / index_dim / index_topk are the indexer of "
                  "attn_mask = dsa and mean nothing under another mask")
        return [in_shapes[0]]

    def _dsa(self):
        return self.attn_mask == "dsa"

    def _blockdiff(self):
        """The block length where the mask is block diffusion's, else 0."""
        return self.block_len if self.attn_mask == "blockdiff" else 0

    def _dh(self):
        return self.head_dim or self.param.num_input_channel // self.nhead

    def _rope_angles(self, L, half, offset=0):
        """Float32 cosines and sines, (L, half), of the rotation's angles:
        row r stands at position ``offset`` + r, under the block-diffusion
        mask at r mod L/2. Under ``rope_type = yarn`` the frequencies are
        YaRN's (``yarn_inv_freq``) and both tables carry its attention
        factor."""
        pos = offset + jnp.arange(L, dtype=jnp.float32)[:, None]
        if self._blockdiff():
            pos = pos % (L // 2)
        if self.rope_type == "yarn":
            y = self.yarn
            inv = jnp.asarray(yarn_inv_freq(
                self.rope_base, 2 * half, y["yarn_factor"],
                y["yarn_original_len"], y["yarn_beta_fast"],
                y["yarn_beta_slow"]), jnp.float32)
            scale = y["yarn_attention_factor"] or \
                0.1 * math.log(y["yarn_factor"]) + 1.0
            ang = pos * inv
            return jnp.cos(ang) * scale, jnp.sin(ang) * scale
        inv = jnp.power(self.rope_base,
                        -jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos * inv                                     # (L, half)
        return jnp.cos(ang), jnp.sin(ang)

    def _apply_rope(self, x, offset=0, rot=0):
        """Rotary embedding on (b, nh, L, dh): rotate the (first-half,
        second-half) feature pairs of the first ``rot`` features (all where
        0) by position-dependent angles (Su et al. 2021) and pass the
        others through — relative offsets enter the q.k phase directly.
        ``offset`` is the global position of row 0 (KV-cached decode
        steps). Under the block-diffusion mask the rows are two copies of
        one sequence and row r stands at position r mod L/2."""
        rot = rot or x.shape[-1]
        half = rot // 2
        cos, sin = self._rope_angles(x.shape[2], half, offset)
        # the rotation itself in float32 whatever the compute type: a
        # bf16 cosine keeps 8 bits of an angle that runs to thousands
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:rot].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin,
                               x1 * sin + x2 * cos], axis=-1).astype(x.dtype)
        if rot < x.shape[-1]:
            out = jnp.concatenate([out, x[..., rot:]], axis=-1)
        return out

    def _kv_width(self):
        return (self.nkvhead or self.nhead) * self._dh()

    def init_params(self, rng):
        d = self.param.num_input_channel
        qw = self.nhead * self._dh()     # = d unless head_dim is set
        w = qw + 2 * self._kv_width()    # [q | k | v] columns; 3d for MHA
        out = {"wqkv": self.param.rand_init_weight(
                   rng, (d, w), in_num=d, out_num=w),
               "wo": self.param.rand_init_weight(
                   rng, (qw, d), in_num=qw, out_num=d)}
        for key in self._norm_keys():
            out[key] = np.ones((self._dh(),), np.float32)
        if self.head_gate:
            out["wgate"] = self.param.rand_init_weight(
                rng, (d, self.nhead), in_num=d, out_num=self.nhead)
        if self._dsa():
            J, di = self.index_heads, self.index_dim
            for key, n in (("widx_q", J * di), ("widx_k", di),
                           ("widx_w", J)):
                out[key] = self.param.rand_init_weight(
                    rng, (d, n), in_num=d, out_num=n)
            out["idx_gain"] = np.ones((di,), np.float32)
            out["idx_bias"] = np.zeros((di,), np.float32)
        return out

    def _norm_keys(self):
        return ("qnorm", "knorm") if self.qk_norm else ()

    def _index_keys(self):
        """The indexer's leaves, in the order they are saved and visited:
        three projections and its key's LayerNorm."""
        return ("widx_q", "widx_k", "widx_w", "idx_gain",
                "idx_bias") if self._dsa() else ()

    def _own_keys(self):
        """The leaves saved, loaded and visited under their own names, in
        that order: the heads' norms, the indexer's, the output gate."""
        return self._norm_keys() + self._index_keys() + (
            ("wgate",) if self.head_gate else ())

    def save_model(self, w, params):
        self.param.save(w)
        for key in ("wqkv", "wo") + self._own_keys():
            w.write_tensor(params[key])

    def load_model(self, r):
        self.param.load(r)
        return {key: r.read_tensor() for key in
                ("wqkv", "wo") + self._own_keys()}

    def visit_order(self):
        # wo gets its own tag: one array per tag so the GetWeight/SetWeight
        # ABI (and per-tag updater scoping, e.g. wo:lr) can reach both
        return [("wmat", "wqkv"), ("wo", "wo")] + [
            (key, key) for key in self._own_keys()]


    layout_support = "nhwc"

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if ctx.channels_last:
            # physical (b, 1, L, d) for logical (b, d, 1, L): (b, L, d) is
            # a pure reshape — channels-last IS attention's native layout,
            # and the whole transformer block chain (embed-out conversion
            # aside) then flows NHWC with zero per-block transposes
            b, _, L, d = x.shape
            seq = x.reshape(b, L, d)
        else:
            b, d, _, L = x.shape
            seq = x.reshape(b, d, L).transpose(0, 2, 1)      # (b, L, d)
        qw = self.nhead * self._dh()
        # sub-scopes qkv / core / out: tools/trace_layers.py splits the
        # layer's device time by them
        with sub_scope("qkv"):
            qkv = jnp.dot(seq, params["wqkv"])        # (b, L, qw + 2*kvw)
            q, k, v = self._heads(qkv, params, ctx)
        if self._dsa():
            out = self._dsa_core(seq, q, k, v, params, ctx)
        else:
            with sub_scope("core"):
                out = self._core(q, k, v, ctx)
        if self.head_gate:
            with sub_scope("gate"):
                out = self._gate(seq, params["wgate"], out)
        with sub_scope("out"):
            out = out.transpose(0, 2, 1, 3).reshape(b, L, qw)  # merge heads
            out = jnp.dot(out, params["wo"])
        if ctx.channels_last:
            return [out.reshape(b, 1, L, d)]
        return [out.transpose(0, 2, 1).reshape(b, d, 1, L)]

    def _gate(self, seq, wgate, out):
        """The core's output (b, nh, L, dh) times ``sigmoid(seq wgate)``
        (b, L, nh), one scalar a head and position, in float32. Counts
        ``attn.gate`` once per traced layer."""
        from ..utils import telemetry
        telemetry.count_path("attn.gate")
        g = jax.nn.sigmoid(jnp.dot(seq, wgate,
                                   preferred_element_type=jnp.float32))
        return (out.astype(jnp.float32)
                * jnp.transpose(g, (0, 2, 1))[..., None]).astype(out.dtype)

    def _heads(self, qkv, params, ctx):
        """The qkv dot's output (b, L, qw + 2 kvw) as the core's operands:
        q (b, nh, L, dh), k and v (b, nkv, L, dh), q and k normed over
        each head's features (``qk_norm``) and rotated (``rope``: the
        first ``rope_dim`` features, YaRN's tables under ``rope_type =
        yarn``). Counts ``attn.prep.fused`` / ``attn.prep.xla``, and
        ``attn.rope.partial`` / ``attn.rope.yarn`` where they apply, once
        per traced layer."""
        from ..utils import telemetry
        b, L, _ = qkv.shape
        nh, dh = self.nhead, self._dh()
        nkv = self.nkvhead or nh
        qw, kvw = nh * dh, self._kv_width()
        if self.rope and 0 < self.rope_dim < dh:
            telemetry.count_path("attn.rope.partial")
        if self.rope and self.rope_type == "yarn":
            telemetry.count_path("attn.rope.yarn")
        if self._prep_fused(qkv, ctx):
            # one kernel each way for the split, the norms and the
            # rotation (ops/qk_prep_pallas.py); the lines below are its
            # golden model and the path everywhere else
            telemetry.count_path("attn.prep.fused")
            cos = sin = None
            if self.rope:
                cos, sin = self._rope_angles(L, dh // 2)
                cos = jnp.concatenate([cos, cos], axis=-1)
                sin = jnp.concatenate([-sin, sin], axis=-1)
            return ops.qk_prep(qkv, params.get("qnorm"), params.get("knorm"),
                               cos, sin, nh, nkv, dh)
        telemetry.count_path("attn.prep.xla")

        def heads(t, n):  # (b, L, n*dh) -> (b, n, L, dh)
            return t.reshape(b, L, n, dh).transpose(0, 2, 1, 3)

        q = heads(qkv[..., :qw], nh)
        k = heads(qkv[..., qw:qw + kvw], nkv)
        v = heads(qkv[..., qw + kvw:], nkv)
        if self.qk_norm:
            # over each head's features, (b, heads, L, dh)
            q = _rms_norm(q, params["qnorm"], self.qk_norm_eps, 3)
            k = _rms_norm(k, params["knorm"], self.qk_norm_eps, 3)
        if self.rope:
            off = ctx.decode_pos if ctx.decode_pos is not None else 0
            rot = (self.rope_dim,) if self.rope_dim else ()
            q = self._apply_rope(q, off, *rot)
            k = self._apply_rope(k, off, *rot)
        return q, k, v

    def _index_operands(self, seq, params):
        """The indexer's reading of the layer's input (b, L, d), detached:
        qI (b, J, L, di) and kI (b, L, di), the key through its LayerNorm,
        both rotated over all di features by the model's tables, and the
        heads' weights w (b, L, J) float32, scaled J^-1/2 di^-1/2."""
        b, L, _ = seq.shape
        J, di = self.index_heads, self.index_dim
        hd = jax.lax.stop_gradient(seq)
        qi = jnp.dot(hd, params["widx_q"]).reshape(b, L, J, di) \
            .transpose(0, 2, 1, 3)
        ki = jnp.dot(hd, params["widx_k"]).astype(jnp.float32)
        mean = jnp.mean(ki, -1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), -1, keepdims=True)
        ki = (ki - mean) * jax.lax.rsqrt(var + 1e-6) \
            * params["idx_gain"].astype(jnp.float32) \
            + params["idx_bias"].astype(jnp.float32)
        ki = ki.astype(seq.dtype)[:, None]                  # one key head
        if self.rope:
            qi, ki = self._apply_rope(qi), self._apply_rope(ki)
        w = jnp.dot(hd, params["widx_w"],
                    preferred_element_type=jnp.float32) \
            * (J ** -0.5 * di ** -0.5)
        return qi, ki[:, 0], w

    def _dsa_core(self, seq, q, k, v, params, ctx):
        """The core under a learned selection: index scores (in training
        their backward from one kernel over the causal tiles where it takes
        the shape, ``ops.dsa_index_bwd``, else the plain blocked lines:
        ``attn.index_bwd.fused`` / ``attn.index_bwd.xla`` once per traced
        layer; the loss's gradient, the only one they get, is nought above
        the diagonal), the exact
        top-k (one kernel that sorts nothing where it takes the shape,
        ``ops.dsa_select``, else ``lax.top_k``: ``attn.select.fused`` /
        ``attn.select.xla`` once per traced layer), the attention on the
        selected keys (in the flash kernels where they take the shape,
        the selection streamed as an int8 mask), and in training the
        indexer's loss, which joins the step's (``ctx.losses``) from
        here. Sub-scopes index / select / core / index_loss."""
        from ..ops import dsa
        from ..utils import telemetry
        b, nh, L, dh = q.shape
        mesh = ctx.mesh
        check(ctx.decode_pos is None,
              "attention: attn_mask = dsa is written for training and "
              "scoring whole sequences; the indexer's key cache and a "
              "selection inside prefill / decode from a cache are not "
              "written")
        check(manual_axis_size(ctx, "sp") <= 1
              and "sp" not in getattr(mesh, "axis_names", ()),
              "attention: attn_mask = dsa under sequence parallelism "
              "(ring / ulysses) is not written")
        telemetry.count_path("attn.dsa")
        telemetry.gauge("dsa.topk", min(self.index_topk, L))
        telemetry.gauge("dsa.kept_scores", dsa.kept_scores(L, self.index_topk))
        # pallas_call has no partitioning rule: no mesh, or a manual one
        kernels = ops.use_pallas() and (mesh is None or ctx.manual_tp)
        with sub_scope("index"):
            qi, ki, w = self._index_operands(seq, params)
            fused = kernels and ops.dsa_index_bwd_supported(
                L, qi.shape[1], qi.shape[3], qi.dtype)
            if ctx.train:
                telemetry.count_path("attn.index_bwd.fused" if fused
                                     else "attn.index_bwd.xla")
            scores = dsa.index_scores(qi, ki, w, fused)     # (b, L, L) f32
        with sub_scope("select"):
            picked = jax.lax.stop_gradient(scores)
            if kernels and ops.dsa_select_supported(L, self.index_topk):
                telemetry.count_path("attn.select.fused")
                sel = ops.dsa_select(picked, self.index_topk)
            else:
                telemetry.count_path("attn.select.xla")
                sel = dsa.select(picked, self.index_topk)
        flash = kernels and ops.flash_supported(L, dh)
        with sub_scope("core"):
            if flash:
                self._count_flash(q, k, True, ctx.train, select=True)
                out, lse = ops.flash_attention_selected(q, k, v, sel)
            else:
                telemetry.count_path("attn.dense")
                probs, p = dsa.selected_probs_plain(q, k, sel, dh ** -0.5)
                out = jnp.einsum(
                    "bngqk,bnkd->bngqd", probs.astype(v.dtype), v,
                    preferred_element_type=jnp.float32) \
                    .reshape(b, nh, L, dh).astype(q.dtype)
        if not ctx.train:
            return out
        with sub_scope("index_loss"):
            telemetry.count_path("loss.index")
            if flash:
                p = ops.selected_probs(*jax.lax.stop_gradient((q, k, lse)),
                                       sel)
            p = jax.lax.stop_gradient(p)
            kl = dsa.index_loss(scores, sel, p) / L         # (b,)
            ctx.losses.append(
                jnp.sum(kl) / (self.batch_size * self.update_period))
            # a sequence's count in whole numbers: float32 holds them
            # exactly to 2^24, a sum over the batch's need not fit
            n_sel = jnp.sum(sel, axis=(1, 2), dtype=jnp.int32)
            ctx.layer_stats[ctx.conn_index] = jnp.stack(
                [jnp.mean(n_sel.astype(jnp.float32)), jnp.mean(kl)])
        return out

    def _prep_fused(self, qkv, ctx):
        """Whether the pass from the qkv dot to the core takes the fused
        kernels: on a TPU (use_pallas), a training or scoring pass on one
        device (no cache position, no mesh: pallas_call has no
        partitioning rule), a layer with a norm or a rotation to fuse, a
        rotation of whole heads (the kernels pair feature i with i + dh/2),
        and a shape and a norm's eps the kernels take
        (ops.qk_prep_supported)."""
        return (ops.use_pallas() and (self.rope or self.qk_norm)
                and not self.rope_dim % self._dh()
                and ctx.decode_pos is None and ctx.mesh is None
                and ops.qk_prep_supported(qkv.shape[1], self._dh(),
                                          qkv.shape[2], qkv.dtype,
                                          self.qk_norm_eps))

    def _count_flash(self, q, k, causal, train, select=False):
        """The path account's ``attn.flash`` and, beside it, the static
        tile schedule the kernels will walk for these shapes: the forward
        tile as gauges, and the (block_q, block_k) score tiles of one
        head's grid that take no mask, take one, and are never visited.
        In training also which backward the shape takes: the fused kernel
        (``attn.bwd.fused``) or the dQ and dK/dV pair (``attn.bwd.split``);
        and ``attn.flash.band`` where a window narrower than the tiles takes
        the band walk, whose sub-tiles are then the tiles counted."""
        from ..utils import telemetry
        sched = ops.flash_schedule(q, k, causal, self.attn_window,
                                   self._blockdiff(), select)
        telemetry.count_path("attn.flash")
        if sched["band"]:
            telemetry.count_path("attn.flash.band")
        if train:
            fused = ops.flash_fused_bwd_supported(
                q.shape[2], q.shape[3], q.dtype, self.attn_window,
                self._blockdiff(), select)
            telemetry.count_path("attn.bwd.fused" if fused
                                 else "attn.bwd.split")
        telemetry.gauge("flash.block_q", sched["block_q"])
        telemetry.gauge("flash.block_k", sched["block_k"])
        for kind in ("full", "edge", "skipped"):
            telemetry.count_path("flash.tiles." + kind, sched[kind])

    def _core(self, q, k, v, ctx):
        """softmax(q k^T / sqrt(dh) + mask) v on (b, heads, L, dh), by the
        path the context asks for. Counts ``attn.flash`` / ``attn.dense``
        once per traced layer (telemetry's path account)."""
        from ..parallel import (attention_reference, ring_attention,
                                ulysses_attention)
        from ..utils import telemetry
        b, nh, L, dh = q.shape
        nkv = k.shape[1]
        mesh = ctx.mesh
        bd = self._blockdiff()
        if bd:
            check(ctx.decode_pos is None,
                  "attention: attn_mask = blockdiff is the training mask "
                  "over a noised and a clean copy; decoding a block of "
                  "tokens a step from a cache is not written")
            check(manual_axis_size(ctx, "sp") <= 1
                  and "sp" not in getattr(mesh, "axis_names", ()),
                  "attention: attn_mask = blockdiff under sequence "
                  "parallelism (ring / ulysses) is not written")
            telemetry.count_path("attn.blockdiff")
            telemetry.gauge("attn.block_len", bd)
        if ctx.decode_pos is not None:
            # KV-cached decode step: write this input's k/v into the cache
            # at [decode_pos, decode_pos + L) and attend the queries
            # against the WHOLE cache with global causal offsets — future
            # (unwritten) slots are masked by the same qpos >= kpos rule.
            # O(L_max * d) per generated token instead of recomputing the
            # full prefix (Trainer.generate).
            li = ctx.conn_index
            ck = ctx.kv_cache[(li, "k")]
            cv = ctx.kv_cache[(li, "v")]
            pos = ctx.decode_pos
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, 0, pos, 0))
            ctx.cache_updates[(li, "k")] = ck
            ctx.cache_updates[(li, "v")] = cv
            if isinstance(pos, int) and pos == 0 and L > 1:
                # PREFILL (statically at position 0): attention over the
                # chunk itself equals cache attention at offset 0 (slots
                # past L are causally masked anyway) — and unlocks the
                # O(L)-memory flash kernel for long prompts, instead of
                # (L, l_max) dense scores against the cache
                if ops.use_pallas() and ops.flash_supported(L, dh):
                    self._count_flash(q, k, True, ctx.train)
                    out = ops.flash_attention(q, k, v, causal=True,
                                              window=self.attn_window)
                else:
                    telemetry.count_path("attn.dense")
                    out = attention_reference(
                        q, k, v, causal=True, scale=dh ** -0.5,
                        window=self.attn_window)
            elif isinstance(pos, int) and pos > 0:
                # static-offset SUFFIX prefill (paged shared-prefix
                # admission, doc/performance.md "Decode KV cache"):
                # positions [pos, pos + L) computed against the
                # statically sliced live cache [0, pos + L). The
                # softmax width equals the prompt length — the same
                # reduction width the full chunk prefill above uses —
                # so a prefix-reused admission's logits stay bitwise
                # identical to prefilling the whole prompt (the
                # paged-vs-dense token-exactness pin). Only paged
                # suffix prefills pass a static nonzero offset; every
                # per-token decode loop traces ``pos``.
                out = attention_reference(
                    q, ck[:, :, :pos + L, :], cv[:, :, :pos + L, :],
                    causal=True, scale=dh ** -0.5,
                    window=self.attn_window, q_offset=pos)
            elif self.decode_chunk > 0 and L == 1 \
                    and ck.shape[2] % self.decode_chunk == 0:
                # flash-decode: online-softmax while-loop over live cache
                # chunks only (parallel/ring.py decode_attention_chunked)
                from ..parallel.ring import decode_attention_chunked
                out = decode_attention_chunked(
                    q, ck, cv, pos=pos, scale=dh ** -0.5,
                    window=self.attn_window, chunk=self.decode_chunk)
            else:
                out = attention_reference(
                    q, ck, cv, causal=True, scale=dh ** -0.5,
                    window=self.attn_window, q_offset=pos)
        elif (sp_n := manual_axis_size(ctx, "sp")) > 1:
            # sequence parallelism inside a pipeline stage body (manual
            # shard_map): k/v are ALREADY replicated over sp (the pipeline
            # boundary stream is), so the ring's k/v rotation buys nothing
            # here — each sp rank computes its own QUERY chunk against the
            # full k/v with zero communication (global causal offsets via
            # q_offset) and the group-local gather rebuilds the sequence.
            # The O(L^2) score memory and FLOPs shard 1/sp per device.
            # (A ppermute-based ring inside the rank-divergent lax.switch
            # would deadlock: collective-permute rendezvous is global, not
            # per-pair — same constraint as the TP design, see
            # parallel/pipeline.py. psum/all_gather are group-local.)
            from ..parallel import ring as _ring
            check(L % sp_n == 0,
                  "attention: seq length %d must be divisible by "
                  "seq_parallel %d" % (L, sp_n))
            sidx = jax.lax.axis_index("sp")
            chunk = L // sp_n
            q_l = jax.lax.dynamic_slice_in_dim(q, sidx * chunk, chunk, 2)
            out_l = _ring.attention_reference(
                q_l, k, v, causal=bool(self.causal), scale=dh ** -0.5,
                window=self.attn_window, q_offset=sidx * chunk)
            out = jax.lax.all_gather(out_l, "sp", axis=2, tiled=True)
        elif mesh is not None and "sp" in getattr(mesh, "axis_names", ()):
            sp = mesh.shape["sp"]
            check(L % sp == 0,
                  "attention: seq length %d must be divisible by "
                  "seq_parallel %d" % (L, sp))
            if self.sp_mode == "ulysses":
                check(nh % sp == 0,
                      "ulysses: nhead %d must be divisible by "
                      "seq_parallel %d" % (nh, sp))
                if nkv != nh and nkv % sp != 0:
                    # ulysses' head-split all-to-all needs sp | kv heads;
                    # broadcast up front when the grouping doesn't divide
                    k = jnp.repeat(k, nh // nkv, axis=1)
                    v = jnp.repeat(v, nh // nkv, axis=1)
            # ring (and divisible ulysses) consume grouped k/v directly:
            # the ICI hops move nkvhead-sized blocks — GQA's bandwidth
            # saving applies to the sequence-parallel comm
            fn = ring_attention if self.sp_mode == "ring" \
                else ulysses_attention
            # shard batch over 'data' too when present — otherwise the
            # attention block would replicate the global batch per chip
            batch_axis = "data" if "data" in mesh.axis_names else None
            out = fn(q, k, v, mesh, causal=bool(self.causal),
                     batch_axis=batch_axis, window=self.attn_window)
        elif ops.use_pallas() and ops.flash_supported(L, dh, bd):
            # per-chip long-context path: blocked online-softmax Pallas
            # kernel, O(L) memory instead of the (L, L) score matrix. On a
            # mesh (no sp axis here) the kernel is batch-pointwise, so it
            # runs under shard_map with the batch dim left on "data" —
            # pallas_call has no GSPMD partitioning rule of its own.
            # GQA: the kernel reads grouped k/v natively (BlockSpec row
            # map) — K/V HBM traffic stays nkvhead-sized
            causal = bool(self.causal)
            self._count_flash(q, k, causal, ctx.train)
            if mesh is None or ctx.manual_tp:
                # inside a pipeline stage body the code is ALREADY
                # per-device (the stage shard_map sliced the microbatch);
                # opening another shard_map would nest and fail
                out = ops.flash_attention(q, k, v, causal=causal,
                                          window=self.attn_window,
                                          block_len=bd)
            else:
                from ..parallel._compat import shard_map
                from jax.sharding import PartitionSpec as P
                batch_axis = ("data" if "data" in mesh.axis_names
                              and mesh.shape["data"] > 1 else None)
                spec = P(batch_axis, None, None, None)
                win = self.attn_window
                out = shard_map(
                    lambda q_, k_, v_: ops.flash_attention(
                        q_, k_, v_, causal=causal, window=win,
                        block_len=bd),
                    mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec)(q, k, v)
        else:
            telemetry.count_path("attn.dense")
            out = attention_reference(q, k, v, causal=bool(self.causal),
                                      window=self.attn_window,
                                      block_len=bd)
        return out


class EmbedLayer(Layer):
    """Token embedding (beyond the reference — the sequence-model front
    end): input node (b, 1, 1, L) of token ids (stored as floats, the
    framework's label convention), output (b, nhidden, 1, L) of embedding
    vectors. Weight (vocab_size, nhidden) under the standard 'wmat' tag.
    Gradients flow through jnp.take's scatter-add transpose."""

    type_name = "embed"
    integer_inputs = True

    def __init__(self):
        super().__init__()
        self.vocab_size = 0
        self.pos_embed = 0
        self._seq_len = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "vocab_size":
            self.vocab_size = int(val)
        if name == "pos_embed":
            self.pos_embed = int(val)

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "EmbedLayer only support 1-1 connection")
        b, c, h, L = in_shapes[0]
        check(c == 1 and h == 1,
              "embed input must be (batch, 1, 1, seq) token ids")
        check(self.vocab_size > 0, "must set vocab_size")
        check(self.param.num_hidden > 0, "must set nhidden (embedding dim)")
        self._seq_len = L
        return [(b, self.param.num_hidden, 1, L)]

    def init_params(self, rng):
        d = self.param.num_hidden
        out = {"wmat": self.param.rand_init_weight(
            rng, (self.vocab_size, d), in_num=self.vocab_size, out_num=d)}
        if self.pos_embed:
            # learned positional embedding, zero-init (pos_embed = 1)
            out["pos"] = np.zeros((self._seq_len, d), np.float32)
        return out

    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["wmat"])
        if self.pos_embed:
            w.write_tensor(params["pos"])

    def load_model(self, r):
        self.param.load(r)
        out = {"wmat": r.read_tensor()}
        if self.pos_embed:
            out["pos"] = r.read_tensor()
        return out

    def visit_order(self):
        if self.pos_embed:
            return [("wmat", "wmat"), ("bias", "pos")]
        return [("wmat", "wmat")]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        b, _, _, L = x.shape
        ids = x.reshape(b, L).astype(jnp.int32)
        emb = jnp.take(params["wmat"], ids, axis=0)        # (b, L, d)
        if self.pos_embed:
            pos = params["pos"]
            if ctx.decode_pos is not None:
                # decode step: the input covers positions
                # [decode_pos, decode_pos + L)
                pos = jax.lax.dynamic_slice_in_dim(
                    pos, ctx.decode_pos, L, 0)
            emb = emb + pos
        return [emb.transpose(0, 2, 1).reshape(b, -1, 1, L)]


class Im2SeqLayer(Layer):
    """(b, d, h, w) feature map -> (b, d, 1, h*w) sequence of h*w
    patch/position vectors (beyond the reference): the bridge from the
    conv stack to the attention stack — a patch-embedding conv
    (kernel_size = stride = patch) followed by im2seq is a ViT front end.
    Position order is row-major (h-major), matching embed's pos_embed
    indexing. Pure reshape in NCHW; under channels_last the physical
    (b, h, w, d) flattens to the attention-native (b, 1, hw, d) with the
    channel axis untouched."""

    type_name = "im2seq"
    layout_support = "nhwc"

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "Im2SeqLayer only support 1-1 connection")
        b, d, h, w = in_shapes[0]
        return [(b, d, 1, h * w)]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if ctx.channels_last:
            b, h, w, d = x.shape
            return [x.reshape(b, 1, h * w, d)]
        b, d, h, w = x.shape
        return [x.reshape(b, d, 1, h * w)]


class AddLayer(Layer):
    """Elementwise sum of 2-4 same-shaped inputs (beyond the reference,
    which only ships concat): the residual-connection primitive for
    transformer stacks. Backward broadcasts the gradient to every input."""

    type_name = "add"
    layout_support = "any"

    def infer_shape(self, in_shapes):
        check(2 <= len(in_shapes) <= 4, "AddLayer takes 2-4 inputs")
        for s in in_shapes[1:]:
            check(s == in_shapes[0], "add: input shapes must all match")
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        out = inputs[0]
        for x in inputs[1:]:
            out = out + x
        return [out]


def _rms_norm(x, gain, eps, axis):
    """``x * rsqrt(mean(x^2) + eps) * gain`` along ``axis`` of a 4-D
    ``x``, the statistics in float32 whatever the compute type."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
                        + eps)
    shape = [1, 1, 1, 1]
    shape[axis] = -1
    gain = gain.astype(jnp.float32).reshape(shape)
    return (xf * inv * gain).astype(x.dtype)


class RMSNormLayer(Layer):
    """Root-mean-square norm over the channels of every position (beyond
    the reference; Zhang & Sennrich 2019): ``x * rsqrt(mean_c(x^2) + eps)
    * gain``, no mean taken off and no bias, the statistics in float32
    whatever the compute type. Sequence nodes (b, D, 1, L) and feature maps
    normalise over D; flat (b, 1, 1, w) nodes over w. ``gain`` (tag
    ``gain``) starts at one. Channels-last is its native layout (the sum
    runs over the lane axis). ``seq_rows = n`` on a sequence node reads
    its first n positions only and gives a node of n (what follows a
    stack that carried a second copy of the sequence beside the first):
    the slice fuses into the norm's read, the other positions are not
    copied."""

    type_name = "rmsnorm"
    layout_support = "nhwc"

    def __init__(self):
        super().__init__()
        self.eps = 1e-6
        self.seq_rows = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "eps":
            self.eps = float(val)
        if name == "seq_rows":
            self.seq_rows = int(val)

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "RMSNormLayer only support 1-1 connection")
        b, c, h, w = in_shapes[0]
        self._flat = c == 1 and h == 1
        self.param.num_input_channel = w if self._flat else c
        if self.seq_rows:
            check(not self._flat and h == 1 and 0 < self.seq_rows <= w,
                  "rmsnorm: seq_rows = %d needs a sequence node (batch, d, "
                  "1, seq) of at least as many positions" % self.seq_rows)
            return [(b, c, h, self.seq_rows)]
        return [in_shapes[0]]

    def init_params(self, rng):
        return {"gain": np.ones((self.param.num_input_channel,), np.float32)}

    def visit_order(self):
        return [("gain", "gain")]

    def save_model(self, w, params):
        self.param.save(w)
        w.write_tensor(params["gain"])

    def load_model(self, r):
        self.param.load(r)
        return {"gain": r.read_tensor()}

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        axis = 3 if (ctx.channels_last or self._flat) else 1
        if self.seq_rows:
            x = x[:, :, :self.seq_rows] if ctx.channels_last \
                else x[..., :self.seq_rows]
        return [_rms_norm(x, params["gain"], self.eps, axis)]


def _rows_or_zero(x, at):
    """Row ``at[i]`` of ``x``, and a zero row where ``at[i]`` lies beyond
    ``x``: ``x`` is the head of a longer array whose other rows nobody
    made, because none of them holds anything."""
    if x.shape[0] == at.shape[0]:       # a whole permutation: none beyond
        return x[at]
    return x.at[at].get(mode="fill", fill_value=0)


@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """``x[perm]`` for a permutation whose inverse is known: the backward
    is the gather ``g[inv]``, not the scatter-add jax would transpose the
    forward's gather into. ``x`` may be the first m rows of the permuted
    array only, ``inv`` then the first m of the inverse: the rows beyond
    read as zeros, and the backward gathers m rows."""
    return _rows_or_zero(x, perm)


def _permute_rows_fwd(x, perm, inv):
    return _rows_or_zero(x, perm), (perm, inv)


def _permute_rows_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@jax.custom_vjp
def _dispatch_rows(x, perm, inv):
    """Row p of the result is token ``perm[p] % T`` of ``x`` (T, d): the
    sorted order of the (choice, token) pairs, ``k`` choices a token, with
    no (k T, d) copy of ``x`` made first; ``perm`` may be the sorted
    order's first m pairs only. Backward: the gather ``g[inv]`` (a zero
    for a pair beyond those m) summed over the choices."""
    return x[perm % x.shape[0]]


def _dispatch_rows_fwd(x, perm, inv):
    return x[perm % x.shape[0]], (inv, x.shape[0])


def _dispatch_rows_bwd(res, g):
    inv, T = res
    return (jnp.sum(_rows_or_zero(g, inv).reshape(-1, T, g.shape[-1]),
                    axis=0), None, None)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


def _expert_rows(act, mm, x, mats):
    """One expert's function of its rows, by the product ``mm(rows, stack
    of matrices)`` the lowering gives: ``act`` is ``expert_act``."""
    a = mm(x, mats["experts"])
    if act == "relu":
        return jnp.maximum(a, 0.0)
    gate = jax.nn.silu(a) if act == "swiglu" else jnp.maximum(a, 0.0)
    return mm(gate * mm(x, mats["up"]), mats["down"])


def _sorted_side(act, m, x2, w, mats, order, inv, sizes):
    """A sparse ``moe`` layer's output (T, dout) from the first ``m`` pairs
    of the sorted order: exact where ``sum(sizes) <= m``, since the pairs
    of experts held come first."""
    T, k = w.shape
    head = order[:m]
    with sub_scope("dispatch"):
        rows = _dispatch_rows(x2, head, inv)
    with sub_scope("experts"):
        ys = _expert_rows(
            act, lambda a, mat: ops.grouped_matmul(a, mat, sizes), rows, mats)
    with sub_scope("combine"):
        # back to (choice, token) order; a pair not held is a zero row
        ys = _permute_rows(ys, inv, head).reshape(k, T, -1)
        return jnp.einsum("kto,tk->to", ys, w.astype(ys.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _either_side(act, m, x2, w, mats, order, inv, sizes):
    """``_sorted_side`` on the sorted order's first m rows where the pairs
    held fit them, and on all of them where they do not: the same result
    either way, no pair dropped. The backward chooses again and makes the
    chosen side's forward anew, so that nothing whose shape depends on the
    choice has to pass from one ``cond`` to the other (jax would hand on
    both sides' residuals, the side not taken as zeros)."""
    return jax.lax.cond(
        jnp.sum(sizes) <= m,
        functools.partial(_sorted_side, act, m),
        functools.partial(_sorted_side, act, order.shape[0]),
        x2, w, mats, order, inv, sizes)


def _either_side_fwd(act, m, *args):
    return _either_side(act, m, *args), args


def _either_side_bwd(act, m, res, g):
    x2, w, mats, order, inv, sizes = res

    def back(n, g, x2, w, mats):
        return jax.vjp(lambda *a: _sorted_side(
            act, n, *a, order, inv, sizes), x2, w, mats)[1](g)
    return jax.lax.cond(
        jnp.sum(sizes) <= m, functools.partial(back, m),
        functools.partial(back, order.shape[0]),
        g, x2, w, mats) + (None, None, None)


_either_side.defvjp(_either_side_fwd, _either_side_bwd)


class MoELayer(Layer):
    """Mixture-of-experts FFN (beyond the reference — the scale-out sibling
    of fullc): a router over ``nexpert`` experts gives every token
    ``top_k`` of them and their weights, and the layer returns the
    weighted sum of what those experts make of the token.

    Keys: ``nexpert``; ``top_k`` (0 = all): the weights are the softmax
    over the token's top k float32 logits (which is the softmax over all
    experts renormalised over those k); ``score = sigmoid`` instead: the
    top k of ``sigmoid(logits) + b`` are chosen, weighted by their
    ``sigmoid(logits)`` over its sum over the k (+ 1e-6), where ``b`` is
    the leaf ``expert_bias`` (``expert_bias = 1``: ``nexpert`` float32
    values that choose and never weight, a fixed leaf: no gradient, no
    update; without it b = 0);
    ``routed_scale``: the routing weights times this factor (1 unless
    set), so the routed sum with them;
    ``nshared_hidden`` > 0: a shared expert of that width, of the routed
    experts' kind, over every row and added once, unweighted (leaves
    ``shared_wmat``, ``shared_up``, ``shared_down``; sub-scope shared);
    ``expert_act`` = ``relu`` (one matrix an expert, ``nhidden`` outputs),
    ``reglu`` (three: ``relu(x Wg) * (x Wu)`` of width ``nhidden``, then
    ``Wd`` back to the input's width) or ``swiglu`` (the same three with
    ``silu`` for the relu); ``nexpert_held`` / ``expert_offset``:
    this layer HOLDS only experts ``[offset, offset + held)`` of the
    ``nexpert`` the router scores — one chip's share of an expert-parallel
    layer. What the absent experts would add is left out of the result;
    nothing here stands in for the chips that hold them.

    Input: a flat node (b, 1, 1, d) or a sequence node (b, d, 1, L); a
    second input node, if given, is what the router reads (a block whose
    router sees its un-normed input while the experts see the normed one).

    Two lowerings. **dense** (flat input): every expert held processes
    every token and the routing weights mask the sum — static shapes, no
    sort; its cost is held / top_k times the useful work. **sparse**
    (sequence input, the only lowering taken there): the token-expert
    pairs are sorted by expert, rows gathered, one grouped matrix product
    a matrix over the experts held (``ops.grouped_matmul``), and the rows
    gathered back and summed per token — no capacity, no dropped token at
    any load. The sorted side's cost follows the pairs held: the pairs of
    experts held sort first, and a share of the experts
    (``nexpert_held < nexpert``) gathers, multiplies and differentiates
    only the first ``M = min(k T, round_up(ceil(3/2 k T held / nexpert),
    512))`` rows of the sorted order, one and a half times its part at even
    routing in whole row tiles of the grouped product, a number the shape
    alone gives. A step whose pairs held exceed M takes all k T rows
    instead (a ``cond`` on ``sum(sizes) <= M``, forward and again
    backward: the same result, nothing dropped; the bounded form makes its
    forward anew in its backward, as ``remat = 1`` would, so that the two
    sides pass no residuals between the ``cond``s). With every expert held
    M = k T and there is no ``cond``. The token side keeps its k T rows
    (PERF.md section 5 has what the chip read of the products and of the
    gathers either side of them). Counts ``moe.sparse`` / ``moe.dense``
    once per traced layer and ``moe.bounded`` beside the first where M < k T
    (gauges ``moe.rows``, ``moe.rows_full``).

    With a mesh carrying an "ep" axis (trainer key ``expert_parallel = k``)
    the dense form's expert dimension shards over the mesh
    (parallel.expert_parallel_ffn): each device runs its local experts and
    one psum combines — composes with the "data" axis for dp x ep. The
    sparse form raises under an ep mesh: its token exchange between chips
    is not written.
    """

    type_name = "moe"
    layout_support = "nhwc"
    # what the sparse lowering leaves in ctx.layer_stats, in this order:
    # the pairs routed to experts held and the fullest expert's load
    stat_names = ("moe.pairs_held", "moe.load_max")
    # {one of them: (gauge, bound)}, set when the sparse lowering is traced:
    # the pairs held against the rows its sorted side has, as
    # utils/health.HealthMonitor keeps it (moe.overflow/<layer>)
    stat_limits = {}

    def __init__(self):
        super().__init__()
        self.n_expert = 0
        self.top_k = 0
        self.n_held = 0
        self.expert_offset = 0
        self.expert_act = "relu"
        self.score = "softmax"
        self.expert_bias = 0
        self.routed_scale = 1.0
        self.nshared_hidden = 0

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "routed_scale":
            self.routed_scale = float(val)
        if name == "nshared_hidden":
            self.nshared_hidden = int(val)
        if name == "score":
            check(val in ("softmax", "sigmoid"),
                  "score must be softmax or sigmoid")
            self.score = val
        if name == "expert_bias":
            self.expert_bias = int(val)
        if name == "nexpert":
            self.n_expert = int(val)
        if name == "top_k":
            self.top_k = int(val)
        if name == "nexpert_held":
            self.n_held = int(val)
        if name == "expert_offset":
            self.expert_offset = int(val)
        if name == "expert_act":
            check(val in ("relu", "reglu", "swiglu"),
                  "expert_act must be relu, reglu or swiglu")
            self.expert_act = val

    def _held(self):
        return self.n_held or self.n_expert

    def _gated(self):
        """Three matrices an expert (reglu, swiglu) and not one."""
        return self.expert_act != "relu"

    def infer_shape(self, in_shapes):
        check(1 <= len(in_shapes) <= 2,
              "MoELayer takes one input, or two: the experts' and the "
              "router's")
        check(len(in_shapes) == 1 or in_shapes[1] == in_shapes[0],
              "moe: the router's input must have the experts' input's shape")
        b, c, h, w = in_shapes[0]
        check(h == 1,
              "moe input must be flat (batch, 1, 1, d) or a sequence "
              "(batch, d, 1, seq); add a flatten layer first")
        self._seq = c > 1
        check(self.n_expert > 0, "must set nexpert")
        check(self.param.num_hidden > 0, "must set nhidden")
        check(self.top_k <= self.n_expert, "top_k cannot exceed nexpert")
        check(not self.expert_bias or self.score == "sigmoid",
              "moe: expert_bias chooses among sigmoid scores; it needs "
              "score = sigmoid")
        check(self.routed_scale > 0, "moe: routed_scale must be positive")
        check(self.nshared_hidden >= 0 and (
            not self.nshared_hidden or self._gated()),
              "moe: the shared expert is one of the routed experts' kind "
              "and needs expert_act reglu or swiglu")
        check(0 <= self.expert_offset
              and self.expert_offset + self._held() <= self.n_expert,
              "moe: experts [expert_offset, expert_offset + nexpert_held) "
              "must lie within nexpert")
        din = c if self._seq else w
        self.param.num_input_node = din
        dout = din if self._gated() else self.param.num_hidden
        return [(b, dout, 1, w) if self._seq else (b, 1, 1, dout)]

    def init_params(self, rng):
        din, f = self.param.num_input_node, self.param.num_hidden
        e, held = self.n_expert, self._held()
        out = {
            "gate": self.param.rand_init_weight(
                rng, (e, din), in_num=din, out_num=e),
            "experts": self.param.rand_init_weight(
                rng, (held, din, f), in_num=din, out_num=f),
        }
        if self._gated():
            out["up"] = self.param.rand_init_weight(
                rng, (held, din, f), in_num=din, out_num=f)
            out["down"] = self.param.rand_init_weight(
                rng, (held, f, din), in_num=f, out_num=din)
        if self.nshared_hidden:
            fs = self.nshared_hidden
            out["shared_wmat"] = self.param.rand_init_weight(
                rng, (din, fs), in_num=din, out_num=fs)
            out["shared_up"] = self.param.rand_init_weight(
                rng, (din, fs), in_num=din, out_num=fs)
            out["shared_down"] = self.param.rand_init_weight(
                rng, (fs, din), in_num=fs, out_num=din)
        out.update((key, np.zeros((e,), np.float32))
                   for key in self.fixed_keys())
        return out

    def _keys(self):
        return ("gate", "experts") + (
            ("up", "down") if self._gated() else ())

    def _shared_keys(self):
        return ("shared_wmat", "shared_up", "shared_down") \
            if self.nshared_hidden else ()

    def fixed_keys(self):
        return ("expert_bias",) if self.expert_bias else ()

    def save_model(self, w, params):
        self.param.save(w)
        import struct
        w.write_raw(struct.pack("<ii", self.n_expert, self.top_k))
        for key in self._keys() + self._shared_keys() + self.fixed_keys():
            w.write_tensor(params[key])

    def load_model(self, r):
        self.param.load(r)
        import struct
        self.n_expert, self.top_k = struct.unpack("<ii", r.read_raw(8))
        return {key: r.read_tensor() for key in
                self._keys() + self._shared_keys() + self.fixed_keys()}

    def visit_order(self):
        # ``experts`` is the one matrix of a relu expert and the gate
        # matrix Wg of a reglu or swiglu one; ``gate`` is the router
        return [("wmat", "experts"), ("gate", "gate")] + (
            [("up", "up"), ("down", "down")] if self._gated() else []) + [
            (key, key) for key in self._shared_keys() + self.fixed_keys()]

    def _top(self, logits, bias=None):
        """(T, k) expert indices and their weights, float32: by
        ``score`` and, under sigmoid scores, the expert bias (which
        chooses and does not weight); the weights times ``routed_scale``."""
        k = self.top_k if 0 < self.top_k < self.n_expert else self.n_expert
        # exact-k from top_k indices (a >=kth-value threshold would keep
        # every tied expert — common in bf16)
        if self.score == "softmax":
            vals, idx = jax.lax.top_k(logits, k)
            w = jax.nn.softmax(vals, axis=-1)
        else:
            s = jax.nn.sigmoid(logits)
            pick = s if bias is None else \
                s + jax.lax.stop_gradient(bias).astype(s.dtype)
            idx = jax.lax.top_k(pick, k)[1]
            w = jnp.take_along_axis(s, idx, axis=-1)
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        if self.routed_scale != 1.0:
            w = w * self.routed_scale
        return idx, w

    def _gate_probs(self, x2, gate, bias=None):
        """(T, nexpert) routing weights, nought outside each token's top k."""
        logits = jnp.dot(x2, gate.T, preferred_element_type=jnp.float32)
        idx, w = self._top(logits, bias)
        return jnp.sum(jax.nn.one_hot(idx, self.n_expert, dtype=w.dtype)
                       * w[..., None], axis=1)

    def _dense(self, x2, probs, params):
        lo = self.expert_offset
        p_held = probs[:, lo: lo + self._held()].astype(x2.dtype)
        mm = lambda a, w: jnp.einsum(                       # noqa: E731
            "ti,eio->eto" if a.ndim == 2 else "eti,eio->eto", a, w)
        return jnp.einsum(
            "eto,te->to", _expert_rows(self.expert_act, mm, x2, params),
            p_held)

    def _sparse(self, x2, xr, params, ctx):
        from ..utils import telemetry
        T = x2.shape[0]
        held, lo = self._held(), self.expert_offset
        with sub_scope("route"):
            logits = jnp.dot(xr, params["gate"].T,
                             preferred_element_type=jnp.float32)
            idx, w = self._top(logits, params.get("expert_bias"))
            k = idx.shape[1]
            # pair p = j T + t is token t's j-th choice: choice-major, so
            # that (k T, d) <-> (k, T, d) splits a leading axis (k = 6 on
            # the sublanes would be a padded copy each time)
            local = idx.T.reshape(-1) - lo
            mine = (local >= 0) & (local < held)
            # pairs of experts held first, by expert; the others last
            key = jnp.where(mine, local, held).astype(jnp.int32)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inv = jnp.zeros_like(order).at[order].set(
                jnp.arange(T * k, dtype=jnp.int32))
            sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32),
                            axis=0)
            ctx.layer_stats[ctx.conn_index] = jnp.stack(
                [jnp.sum(sizes), jnp.max(sizes)]).astype(jnp.float32)
        pairs = T * k
        rows = self._sorted_rows(pairs)
        if rows < pairs:
            telemetry.count_path("moe.bounded")
        telemetry.gauge("moe.rows", rows)
        telemetry.gauge("moe.rows_full", pairs)
        self.stat_limits = {"moe.pairs_held": ("moe.overflow", rows)}
        mats = {key: params[key] for key in self._keys()[1:]}
        args = (self.expert_act, rows, x2, w, mats, order, inv, sizes)
        if rows == pairs:
            return _sorted_side(*args)
        return _either_side(*args)

    def _sorted_rows(self, pairs):
        """The static row count of the sorted side for ``pairs`` (choice,
        token) pairs: one and a half times this share's part of them at
        even routing, in whole row tiles of the grouped product; all of
        them where every expert is held."""
        held = self._held()
        if held == self.n_expert:
            return pairs
        even = -(-3 * pairs * held // (2 * self.n_expert))
        return min(pairs, -(-even // 512) * 512)

    def apply(self, params, inputs, ctx):
        from ..parallel import expert_parallel_ffn
        from ..utils import telemetry
        x = inputs[0]
        xr = inputs[1] if len(inputs) > 1 else x
        b = x.shape[0]
        if self._seq and not ctx.channels_last:
            x, xr = (jnp.transpose(v, (0, 2, 3, 1)) for v in (x, xr))
        d = self.param.num_input_node
        x2, xr2 = x.reshape(-1, d), xr.reshape(-1, d)
        mesh = ctx.mesh
        n_ep = manual_axis_size(ctx, "ep")
        on_ep = n_ep > 1 or (not ctx.manual_tp and mesh is not None
                             and "ep" in getattr(mesh, "axis_names", ()))
        if on_ep:
            check(not self._seq,
                  "moe: a sequence input takes the sparse lowering, whose "
                  "token exchange over the mesh's 'ep' axis is not written; "
                  "one chip's share runs with nexpert_held / expert_offset "
                  "and no ep axis")
            check(self.expert_act == "relu" and self._held() == self.n_expert,
                  "moe: the 'ep' mesh paths take relu experts, all held")
        if self.score == "sigmoid":
            telemetry.count_path("moe.sigmoid")
        if self._seq:
            telemetry.count_path("moe.sparse")
            out = self._sparse(x2, xr2, params, ctx)
        else:
            telemetry.count_path("moe.dense")
            probs = self._gate_probs(xr2, params["gate"],
                                     params.get("expert_bias"))
            if n_ep > 1:
                # same contract as expert_parallel_ffn (parallel/tensor.py):
                # an indivisible expert count fails loudly, not silently
                # dense
                check(self.n_expert % n_ep == 0,
                      "expert_parallel_ffn: n_experts %d not divisible by "
                      "mesh axis 'ep' size %d" % (self.n_expert, n_ep))
                # expert parallelism inside a pipeline stage body (manual
                # shard_map): each ep rank runs its slice of the expert
                # stack through the SAME per-device body
                # expert_parallel_ffn wraps in shard_map (which cannot nest
                # here) — dense local experts, group-local psum combine
                from ..parallel.tensor import _ep_local
                loc = self.n_expert // n_ep
                eidx = jax.lax.axis_index("ep")
                w_l = jax.lax.dynamic_slice_in_dim(params["experts"],
                                                   eidx * loc, loc, 0)
                p_l = jax.lax.dynamic_slice_in_dim(probs, eidx * loc, loc, 1)
                out = _ep_local(x2, w_l, p_l.astype(x2.dtype),
                                axis_name="ep")
            elif on_ep:
                batch_axis = "data" if "data" in mesh.axis_names else None
                out = expert_parallel_ffn(x2, params["experts"],
                                          probs.astype(x2.dtype),
                                          mesh, batch_axis=batch_axis)
            else:
                out = self._dense(x2, probs, params)
        if self.routed_scale != 1.0:
            telemetry.gauge("moe.routed_scale", self.routed_scale)
        if self.nshared_hidden:
            with sub_scope("shared"):
                telemetry.count_path("moe.shared")
                out = out.astype(x2.dtype) + _expert_rows(
                    self.expert_act, jnp.dot, x2,
                    {"experts": params["shared_wmat"],
                     "up": params["shared_up"],
                     "down": params["shared_down"]})
        out = out.astype(x.dtype)
        if not self._seq:
            return [out.reshape(b, 1, 1, -1)]
        out = out.reshape(x.shape[:3] + (-1,))              # (b, 1, L, dout)
        return [out if ctx.channels_last
                else jnp.transpose(out, (0, 3, 1, 2))]


def _seq_rows(x, ctx):
    """A sequence node (b, d, 1, L), or its channels-last (b, 1, L, d), as
    (b, L, d)."""
    if ctx.channels_last:
        b, _, L, d = x.shape
        return x.reshape(b, L, d)
    b, d, _, L = x.shape
    return x.reshape(b, d, L).transpose(0, 2, 1)


def _seq_node(y, ctx):
    """``_seq_rows``' inverse: (b, L, d) back to the node's layout."""
    b, L, d = y.shape
    if ctx.channels_last:
        return y.reshape(b, 1, L, d)
    return y.transpose(0, 2, 1).reshape(b, d, 1, L)


def short_conv(bcx, w):
    """The gated short convolution's core on ``bcx (b, L, 3d)`` = [B | C |
    x] and the taps ``w (d, K)``: ``u = B * x``, ``v_t = sum_j w[:, j]
    u_{t - K + 1 + j}`` (depthwise, causal within each row, zeros before
    its start), ``C * v``, (b, L, d) in ``bcx``'s dtype. K shifted slices
    of one zero-padded copy, products in float32: XLA fuses the pass into
    one loop each way."""
    d, K = w.shape
    L = bcx.shape[1]
    B, C, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
               for i in range(3))
    u = jnp.pad(B * x, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    v = sum(u[:, j:j + L] * wf[:, j] for j in range(K))
    return (C * v).astype(bcx.dtype)


class _NamedLeavesLayer(Layer):
    """A layer whose leaves are saved, loaded and visited under their own
    names, in the order of ``_KEYS``."""

    _KEYS: tuple = ()

    def save_model(self, w, params):
        self.param.save(w)
        for key in self._KEYS:
            w.write_tensor(params[key])

    def load_model(self, r):
        self.param.load(r)
        return {key: r.read_tensor() for key in self._KEYS}

    def visit_order(self):
        return [(key, key) for key in self._KEYS]


class ShortConvLayer(_NamedLeavesLayer):
    """Gated short causal convolution over a sequence node (b, d, 1, L)
    (beyond the reference; LFM2's conv mixer, ``Lfm2ShortConv``):
    ``[B | C | x] = h W_in``, ``u = B * x``, ``v_t = sum_j w[:, j]
    u_{t-K+1+j}`` depthwise and causal within each row of the batch, zeros
    before a row's start, ``y = (C * v) W_out``; both gates linear, no
    nonlinearity; no conv bias (LFM2's ``conv_bias`` is false). Key
    ``kernel`` (K, default 3: ``conv_L_cache``). Leaves ``w_in (d, 3d)``,
    ``w_conv (d, K)``, ``w_out (d, d)``. Sub-scopes in / core / out
    (``AttentionLayer``'s qkv / core / out). Training and scoring whole
    sequences only: the conv's K - 1 rows of state a sequence in a
    decode cache are not written (ROADMAP R5)."""

    type_name = "shortconv"
    layout_support = "nhwc"

    def __init__(self):
        super().__init__()
        self.kernel = 3

    def set_param(self, name, val):
        super().set_param(name, val)
        if name == "kernel":
            self.kernel = int(val)

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1,
              "ShortConvLayer only support 1-1 connection")
        b, d, h, L = in_shapes[0]
        check(h == 1 and d > 1,
              "shortconv input must be a sequence (batch, d_model, 1, seq)")
        check(self.kernel >= 1, "shortconv: kernel must be positive")
        self.param.num_input_channel = d
        return [in_shapes[0]]

    def init_params(self, rng):
        d, K = self.param.num_input_channel, self.kernel
        return {"w_in": self.param.rand_init_weight(
                    rng, (d, 3 * d), in_num=d, out_num=3 * d),
                "w_conv": self.param.rand_init_weight(
                    rng, (d, K), in_num=K, out_num=K),
                "w_out": self.param.rand_init_weight(
                    rng, (d, d), in_num=d, out_num=d)}

    _KEYS = ("w_in", "w_conv", "w_out")

    def apply(self, params, inputs, ctx):
        check(ctx.decode_pos is None,
              "shortconv: decoding from a cache is not written (the conv's "
              "%d rows of state a sequence beside the key-value cache)"
              % (self.kernel - 1))
        check(manual_axis_size(ctx, "sp") <= 1
              and "sp" not in getattr(ctx.mesh, "axis_names", ()),
              "shortconv: a sequence split over 'sp' is not written")
        seq = _seq_rows(inputs[0], ctx)
        with sub_scope("in"):
            bcx = jnp.dot(seq, params["w_in"])
        with sub_scope("core"):
            y = short_conv(bcx, params["w_conv"])
        with sub_scope("out"):
            out = jnp.dot(y, params["w_out"])
        return [_seq_node(out, ctx)]


class GatedFFNLayer(_NamedLeavesLayer):
    """Dense gated feed-forward over a sequence node (b, d, 1, L) (beyond
    the reference; the leading dense layers of a mixture-of-experts LM):
    ``W_d(silu(h W_g) * h W_u)`` a position, width ``nhidden``. One
    swiglu expert of ``moe``'s, by the same product code
    (``_expert_rows``) and the same tags: ``wmat`` (W_g, d x f), ``up``
    (d x f), ``down`` (f x d)."""

    type_name = "ffn"
    layout_support = "nhwc"

    def infer_shape(self, in_shapes):
        check(len(in_shapes) == 1, "GatedFFNLayer only support 1-1 "
              "connection")
        b, d, h, L = in_shapes[0]
        check(h == 1 and d > 1,
              "ffn input must be a sequence (batch, d_model, 1, seq)")
        check(self.param.num_hidden > 0, "ffn: must set nhidden")
        self.param.num_input_channel = d
        return [in_shapes[0]]

    def init_params(self, rng):
        d, f = self.param.num_input_channel, self.param.num_hidden
        return {"wmat": self.param.rand_init_weight(
                    rng, (d, f), in_num=d, out_num=f),
                "up": self.param.rand_init_weight(
                    rng, (d, f), in_num=d, out_num=f),
                "down": self.param.rand_init_weight(
                    rng, (f, d), in_num=f, out_num=d)}

    _KEYS = ("wmat", "up", "down")

    def apply(self, params, inputs, ctx):
        seq = _seq_rows(inputs[0], ctx)
        mats = {"experts": params["wmat"], "up": params["up"],
                "down": params["down"]}
        return [_seq_node(_expert_rows("swiglu", jnp.dot, seq, mats), ctx)]
