"""Layer type ids, name parsing, and the layer factory.

Mirrors the reference's type enumeration and string parser
(src/layer/layer.h:284-361) and factory dispatch
(src/layer/layer_impl-inl.hpp:37-76). Type ids are kept numerically identical
so serialized net structures are interchangeable.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import layers as L
from .base import Layer, check
from .extern import ExternLayer

# type ids (src/layer/layer.h:284-315)
kSharedLayer = 0
kFullConnect = 1
kSoftmax = 2
kRectifiedLinear = 3
kSigmoid = 4
kTanh = 5
kSoftplus = 6
kFlatten = 7
kDropout = 8
kConv = 10
kMaxPooling = 11
kSumPooling = 12
kAvgPooling = 13
kLRN = 15
kBias = 17
kConcat = 18
kXelu = 19
kCaffe = 20
kReluMaxPooling = 21
kMaxout = 22
kSplit = 23
kInsanity = 24
kInsanityPooling = 25
kL2Loss = 26
kMultiLogistic = 27
kChConcat = 28
kPRelu = 29
kBatchNorm = 30
kFixConnect = 31
kAttention = 32
kEmbed = 33
kAdd = 34
kMoE = 35
kIm2Seq = 36
kRMSNorm = 37
kShortConv = 38
kGatedFFN = 39
kPairTestGap = 1024

_NAME2TYPE = {
    "fullc": kFullConnect,
    "fixconn": kFixConnect,
    "bias": kBias,
    "softmax": kSoftmax,
    "relu": kRectifiedLinear,
    "sigmoid": kSigmoid,
    "tanh": kTanh,
    "softplus": kSoftplus,
    "flatten": kFlatten,
    "dropout": kDropout,
    "conv": kConv,
    "relu_max_pooling": kReluMaxPooling,
    "max_pooling": kMaxPooling,
    "sum_pooling": kSumPooling,
    "avg_pooling": kAvgPooling,
    "lrn": kLRN,
    "concat": kConcat,
    "xelu": kXelu,
    "maxout": kMaxout,
    "split": kSplit,
    "insanity": kInsanity,
    "insanity_max_pooling": kInsanityPooling,
    "l2_loss": kL2Loss,
    "multi_logistic": kMultiLogistic,
    "ch_concat": kChConcat,
    "prelu": kPRelu,
    "batch_norm": kBatchNorm,
    # the reference's caffe-plugin slot; "extern" is the native name, and
    # "caffe" is kept as an alias so reference configs parse (the op itself
    # must be registered via register_extern — see layer/extern.py)
    "extern": kCaffe,
    "caffe": kCaffe,
    "attention": kAttention,
    "embed": kEmbed,
    "add": kAdd,
    "moe": kMoE,
    "im2seq": kIm2Seq,
    "rmsnorm": kRMSNorm,
    "shortconv": kShortConv,
    "ffn": kGatedFFN,
}

_TYPE2CLS = {
    kFullConnect: L.FullConnectLayer,
    kFixConnect: L.FixConnectLayer,
    kBias: L.BiasLayer,
    kSoftmax: L.SoftmaxLayer,
    kRectifiedLinear: L.ReluLayer,
    kSigmoid: L.SigmoidLayer,
    kTanh: L.TanhLayer,
    kSoftplus: L.SoftplusLayer,
    kFlatten: L.FlattenLayer,
    kDropout: L.DropoutLayer,
    kConv: L.ConvolutionLayer,
    kReluMaxPooling: L.ReluMaxPoolingLayer,
    kMaxPooling: L.MaxPoolingLayer,
    kSumPooling: L.SumPoolingLayer,
    kAvgPooling: L.AvgPoolingLayer,
    kLRN: L.LRNLayer,
    kConcat: L.ConcatLayer,
    kXelu: L.XeluLayer,
    kMaxout: L.MaxoutLayer,
    kSplit: L.SplitLayer,
    kInsanity: L.InsanityLayer,
    kInsanityPooling: L.InsanityPoolingLayer,
    kL2Loss: L.L2LossLayer,
    kMultiLogistic: L.MultiLogisticLayer,
    kChConcat: L.ChConcatLayer,
    kPRelu: L.PReluLayer,
    kBatchNorm: L.BatchNormLayer,
    kCaffe: ExternLayer,
    kAttention: L.AttentionLayer,
    kEmbed: L.EmbedLayer,
    kAdd: L.AddLayer,
    kMoE: L.MoELayer,
    kIm2Seq: L.Im2SeqLayer,
    kRMSNorm: L.RMSNormLayer,
    kShortConv: L.ShortConvLayer,
    kGatedFFN: L.GatedFFNLayer,
}


def get_layer_type(name: str) -> int:
    """Parse a layer type name to its id (reference GetLayerType,
    src/layer/layer.h:322-361), including share:<tag> and
    pairtest-<master>-<slave>."""
    if name.startswith("share"):
        return kSharedLayer
    if name.startswith("pairtest-"):
        rest = name[len("pairtest-"):]
        parts = rest.split("-", 1)
        check(len(parts) == 2, "pairtest must be pairtest-master-slave")
        return kPairTestGap * get_layer_type(parts[0]) + get_layer_type(parts[1])
    if name in _NAME2TYPE:
        return _NAME2TYPE[name]
    raise ValueError('unknown layer type: "%s"' % name)


class PairTestLayer(Layer):
    """Differential-testing layer (src/layer/pairtest_layer-inl.hpp:15):
    runs master and slave implementations on the same input, uses the
    master's output, and records the max relative forward deviation into
    ctx.pairtest_diffs for the harness to assert on (tolerance 1e-5 in the
    reference compare logic :160-199)."""

    type_name = "pairtest"

    def __init__(self, master: Layer, slave: Layer):
        super().__init__()
        self.master = master
        self.slave = slave
        self.self_loop = master.self_loop

    def set_param(self, name, val):
        self.master.set_param(name, val)
        self.slave.set_param(name, val)

    def infer_shape(self, in_shapes):
        mshape = self.master.infer_shape(in_shapes)
        sshape = self.slave.infer_shape(in_shapes)
        check(mshape == sshape, "pairtest: master/slave shapes disagree")
        return mshape

    def init_params(self, rng):
        # both implementations share one set of weights (the reference copies
        # master weights into the slave each round)
        return self.master.init_params(rng)

    def apply(self, params, inputs, ctx):
        mout = self.master.apply(params, inputs, ctx)
        sout = self.slave.apply(params, inputs, ctx)
        diffs = []
        for a, b in zip(mout, sout):
            rel = jnp.max(jnp.abs(a - b) / (jnp.maximum(
                jnp.maximum(jnp.abs(a), jnp.abs(b)), 1e-6)))
            diffs.append(rel)
        if not hasattr(ctx, "pairtest_diffs"):
            ctx.pairtest_diffs = []
        ctx.pairtest_diffs.extend(diffs)
        return mout

    def visit_order(self):
        return self.master.visit_order()

    def save_model(self, w, params):
        self.master.save_model(w, params)

    def load_model(self, r):
        return self.master.load_model(r)


def create_layer(type_id: int) -> Layer:
    """Create a layer by numeric type id (reference CreateLayer_,
    src/layer/layer_impl-inl.hpp:37-76)."""
    if type_id >= kPairTestGap:
        master = create_layer(type_id // kPairTestGap)
        slave = create_layer(type_id % kPairTestGap)
        return PairTestLayer(master, slave)
    if type_id == kSharedLayer:
        raise ValueError("shared layer is created by the net, not the factory")
    if type_id not in _TYPE2CLS:
        raise ValueError("unsupported layer type id %d" % type_id)
    return _TYPE2CLS[type_id]()
