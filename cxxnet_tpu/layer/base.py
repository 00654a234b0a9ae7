"""Layer abstraction: shape inference + parameter init + pure-function apply.

TPU-native redesign of the reference's ILayer ABI
(src/layer/layer.h:162-279). The reference mutates device nodes in place
(Forward/Backprop pairs with hand-written gradients); here each layer is a
pure function ``apply(params, inputs, ctx) -> outputs`` and the backward pass
comes from jax autodiff of the summed loss — inside one jitted train step, so
XLA sees the whole graph and fuses/overlaps freely.

Key correspondences:
* InitConnection (shape inference + cstate alloc)  -> infer_shape()
* InitModel (weight init via Random<xpu>)          -> init_params(rng)
* Forward(is_train)                                -> apply(..., ctx.train)
* Backprop (hand-written)                          -> jax.grad of loss layers
* ApplyVisitor weight access                       -> params dict pytree
* SaveModel/LoadModel                              -> save_model()/load_model()
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..utils import serializer

Shape4 = Tuple[int, int, int, int]


class LayerParam:
    """Common numeric layer parameters; mirrors src/layer/param.h:15-142.

    The reference serializes this struct verbatim into model files; save()/
    load() reproduce its exact 328-byte layout (18 scalar fields +
    int reserved[64]) so checkpoints are structurally identical.
    """

    def __init__(self):
        self.num_hidden = 0
        self.init_sigma = 0.01
        self.init_sparse = 10
        self.init_uniform = -1.0
        self.init_bias = 0.0
        self.num_channel = 0
        self.random_type = 0
        self.num_group = 1
        self.kernel_height = 0
        self.kernel_width = 0
        self.stride = 1
        self.pad_y = 0
        self.pad_x = 0
        self.no_bias = 0
        self.temp_col_max = 64 << 18
        self.silent = 0
        self.num_input_channel = 0
        self.num_input_node = 0

    def set_param(self, name: str, val: str) -> None:
        if name == "init_sigma":
            self.init_sigma = float(val)
        if name == "init_uniform":
            self.init_uniform = float(val)
        if name == "init_bias":
            self.init_bias = float(val)
        if name == "init_sparse":
            self.init_sparse = int(val)
        if name == "random_type":
            if val == "gaussian":
                self.random_type = 0
            elif val in ("uniform", "xavier"):
                self.random_type = 1
            elif val == "kaiming":
                self.random_type = 2
            else:
                raise ValueError("invalid random_type %s" % val)
        if name == "nhidden":
            self.num_hidden = int(val)
        if name == "nchannel":
            self.num_channel = int(val)
        if name == "ngroup":
            self.num_group = int(val)
        if name == "kernel_size":
            self.kernel_width = self.kernel_height = int(val)
        if name == "kernel_height":
            self.kernel_height = int(val)
        if name == "kernel_width":
            self.kernel_width = int(val)
        if name == "stride":
            self.stride = int(val)
        if name == "pad":
            self.pad_y = self.pad_x = int(val)
        if name == "pad_y":
            self.pad_y = int(val)
        if name == "pad_x":
            self.pad_x = int(val)
        if name == "no_bias":
            self.no_bias = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "temp_col_max":
            self.temp_col_max = int(val) << 18

    # --- binary serialization (reference struct write, fullc_layer-inl.hpp:46) ---
    def save(self, w: serializer.Writer) -> None:
        import struct
        w.write_raw(struct.pack(
            "<i f i f f i i i i i i i i i i i i i",
            self.num_hidden, self.init_sigma, self.init_sparse,
            self.init_uniform, self.init_bias, self.num_channel,
            self.random_type, self.num_group, self.kernel_height,
            self.kernel_width, self.stride, self.pad_y, self.pad_x,
            self.no_bias, self.temp_col_max, self.silent,
            self.num_input_channel, self.num_input_node))
        w.write_raw(b"\x00" * (64 * 4))  # reserved[64]

    def load(self, r: serializer.Reader) -> None:
        import struct
        vals = struct.unpack("<i f i f f i i i i i i i i i i i i i",
                             r.read_raw(18 * 4))
        (self.num_hidden, self.init_sigma, self.init_sparse,
         self.init_uniform, self.init_bias, self.num_channel,
         self.random_type, self.num_group, self.kernel_height,
         self.kernel_width, self.stride, self.pad_y, self.pad_x,
         self.no_bias, self.temp_col_max, self.silent,
         self.num_input_channel, self.num_input_node) = vals
        r.read_raw(64 * 4)

    def rand_init_weight(self, rng: np.random.RandomState,
                         shape: Tuple[int, ...],
                         in_num: int, out_num: int) -> np.ndarray:
        """Weight init: gaussian / xavier-uniform / kaiming
        (reference: src/layer/param.h:113-138)."""
        if self.random_type == 0:
            return rng.normal(0.0, self.init_sigma, size=shape).astype(np.float32)
        elif self.random_type == 1:
            a = math.sqrt(3.0 / (in_num + out_num))
            if self.init_uniform > 0:
                a = self.init_uniform
            return rng.uniform(-a, a, size=shape).astype(np.float32)
        elif self.random_type == 2:
            if self.num_hidden > 0:
                sigma = math.sqrt(2.0 / self.num_hidden)
            else:
                sigma = math.sqrt(
                    2.0 / (self.num_channel * self.kernel_width * self.kernel_height))
            return rng.normal(0.0, sigma, size=shape).astype(np.float32)
        raise ValueError("unsupported random_type %d" % self.random_type)


class LabelInfo:
    """Named label fields of a batch; mirrors layer::LabelInfo
    (src/layer/layer.h:96-121). Fields are views into the batch's label
    matrix, selected by the ``label_vec[a,b) = name`` config ranges."""

    def __init__(self, fields: Dict[str, jnp.ndarray]):
        self.fields = fields

    def field(self, name: str):
        if name not in self.fields:
            raise KeyError("unknown label target=%s" % name)
        return self.fields[name]


@dataclass
class ApplyContext:
    """Per-application context threaded through the net's forward pass."""
    train: bool
    rng: Optional[jax.Array] = None            # per-layer folded PRNG key
    labels: Optional[LabelInfo] = None
    losses: List[jnp.ndarray] = field(default_factory=list)
    # number of optimizer steps taken, for annealing layers (insanity)
    epoch: jnp.ndarray = 0
    # device mesh of the running trainer (None single-device); layers with
    # sharded algorithms (attention w/ sequence parallelism) read it
    mesh: object = None
    # index of the layer currently applying (its params slot); set by the
    # net's forward loop
    layer_index: int = -1
    # the CONNECTION index (distinct even when share[...] ties the params
    # slot): identity for per-application state like KV caches
    conn_index: int = -1
    # non-gradient parameter updates recorded during the forward (batch-norm
    # running statistics): {(layer_index, param_key): new_value}; the
    # trainer merges them into params after the optimizer step
    state_updates: Dict = field(default_factory=dict)
    # True when the layer's 4-D inputs arrive channels-last (N,H,W,C) —
    # the TPU-preferred activation layout. Set per layer by the net's
    # forward loop for layers declaring layout_support == "nhwc"; logical
    # shapes, params, and checkpoints stay reference-NCHW throughout
    channels_last: bool = False
    # True when the layer is applying INSIDE a pipeline stage body: the
    # body is a manual shard_map over EVERY mesh axis, so any composed
    # parallelism must be explicit — a layer whose axis is on the mesh
    # ("model" for fullc/conv TP, "ep" for moe) slices its local weight
    # shard by lax.axis_index and combines with group-local collectives
    # (see parallel/pipeline.py on why GSPMD can't do it here)
    manual_tp: bool = False
    # KV-cached autoregressive decoding (Trainer.generate): the global
    # position of the current input's first sequence slot (traced scalar;
    # None = normal full-sequence forward). Position-aware layers read it
    # (embed pos rows, RoPE angles) and attention attends its queries
    # against the cache instead of the in-batch keys
    decode_pos: object = None
    # per-attention-layer k/v caches, keyed (layer_index, "k"/"v"):
    # (b, nkv, L_max, dh) arrays read by attention's decode path; the
    # position-updated caches are written to cache_updates
    kv_cache: Dict = field(default_factory=dict)
    cache_updates: Dict = field(default_factory=dict)
    # small per-layer readings of the step itself, {conn_index: float32
    # vector} in the order of the layer's ``stat_names`` (moe: pairs held,
    # fullest expert's load); with health_monitor = 1 the train step
    # returns them behind the health vector
    layer_stats: Dict = field(default_factory=dict)


class Layer:
    """Base layer. Subclasses define shape inference, init, and apply."""

    type_name = "none"
    self_loop = False      # reference self-loop layers: in node == out node
    is_loss = False
    # True when inputs are integer ids stored as floats (embed): such nodes
    # must never be cast to a low-precision compute dtype — bf16 cannot
    # represent ids above ~256 exactly
    integer_inputs = False
    # Activation-layout contract under the net's channels_last mode:
    #   "nchw"  — apply() requires reference (N,C,H,W) inputs (default)
    #   "any"   — elementwise/routing: runs on either layout unchanged
    #   "nhwc"  — has a channels-last fast path; apply() reads
    #             ctx.channels_last to pick its axes
    layout_support = "nchw"

    def __init__(self):
        self.param = LayerParam()
        # rematerialization flag (config key ``remat``): when set, this
        # layer's activations are recomputed in the backward pass instead
        # of saved — the TPU HBM<->FLOPs trade (jax.checkpoint). Set
        # globally (before the first layer line) or per layer.
        self.remat = 0

    # --- configuration -----------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        if name == "remat":
            self.remat = int(val)
        self.param.set_param(name, val)

    # --- graph assembly ----------------------------------------------------
    def infer_shape(self, in_shapes: List[Shape4]) -> List[Shape4]:
        """Given input node shapes (b, c, h, w), return output node shapes.
        Must also finalize any derived params (e.g. num_input_node)."""
        raise NotImplementedError

    def init_params(self, rng: np.random.RandomState) -> Dict[str, np.ndarray]:
        """Initialize weights on host; {} for parameterless layers."""
        return {}

    # --- execution ---------------------------------------------------------
    def apply(self, params: Dict[str, jnp.ndarray],
              inputs: List[jnp.ndarray], ctx: ApplyContext) -> List[jnp.ndarray]:
        raise NotImplementedError

    # --- serialization -----------------------------------------------------
    def save_model(self, w: serializer.Writer, params: Dict[str, np.ndarray]) -> None:
        """Serialize layer params; default: nothing (parameterless layers)."""

    def load_model(self, r: serializer.Reader) -> Dict[str, np.ndarray]:
        return {}

    # weight visitor order: the (tag, array-key) pairs exposed to updaters,
    # mirroring ApplyVisitor (e.g. fullc visits "wmat" then "bias")
    def visit_order(self) -> List[Tuple[str, str]]:
        return []

    # non-trainable state param keys (BN running stats and the like):
    # excluded from visit_order BY the layer, skipped by the bf16 compute
    # cast, updated through ctx.state_updates — declare them here so the
    # contract lives in one place
    def state_keys(self) -> Tuple[str, ...]:
        return ()

    # fixed param keys: in visit_order (SetWeight / GetWeight and the
    # checkpoint reach them) but trained by nothing: no updater, no
    # optimizer state, no decay, kept float32 under a compute dtype
    def fixed_keys(self) -> Tuple[str, ...]:
        return ()


def sub_scope(name: str):
    """A ``jax.named_scope`` a layer opens inside its own, for a part of its
    work that a trace should show apart. The leading ``~`` marks it as such:
    ``utils/devtrace.scope_of`` splits the layer's rows by any component so
    marked (``jvp(b0_att)/~core/...`` is the row ``b0_att/core``) and needs
    no list of the names layers choose."""
    return jax.named_scope("~" + name)


def check(cond: bool, msg: str, *args) -> None:
    """Fail-fast invariant check (reference utils::Check, src/utils/utils.h)."""
    if not cond:
        raise ValueError(msg % args if args else msg)
