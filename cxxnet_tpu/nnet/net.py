"""NeuralNet: the assembled DAG as a pure forward function over a params pytree.

TPU-native counterpart of NeuralNet<xpu> (src/nnet/neural_net-inl.hpp:23-297).
The reference owns device nodes and mutates them through per-connection
Forward/Backprop with per-tensor async PS sync; here the whole forward (and,
via jax.grad, backward) is one traceable function executed inside a single
jitted train step — XLA handles scheduling, fusion and collective overlap.

Weight sharing (``share:<tag>``) maps to connections applying the primary
connection's layer object with the primary's params — autodiff then sums the
shared gradients, matching the reference's accumulation into one gwmat.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import ops
from ..layer import factory
from ..layer.base import ApplyContext, LabelInfo, Layer, check
from ..layer.layers import ConvolutionLayer, SplitLayer
from ..utils import serializer
from .config import NetConfig

Params = List[Dict[str, jnp.ndarray]]

_SCOPE_UNSAFE = re.compile(r"[^A-Za-z0-9_.+\-]")


def scope_name(name: str) -> str:
    """A name fit for one component of an ``op_name`` path: the profiler's
    ``tf_op`` reads ``jit(step)/jvp(<scope>)/<primitive>``, so a scope
    holds no ``/``, no parentheses, no ``:`` and no blank."""
    return _SCOPE_UNSAFE.sub("_", name)


class NeuralNet:
    def __init__(self, cfg: NetConfig, batch_size: int,
                 infer_shapes: bool = True,
                 compute_dtype: Optional[jnp.dtype] = None,
                 input_scale: float = 1.0,
                 input_mean=None,
                 fuse_siblings: bool = True,
                 channels_last: bool = False):
        """infer_shapes=False skips shape inference entirely — used for the
        weight-copy (finetune) path, which only deserializes params and never
        runs the net (reference CopyModelFrom, nnet_impl-inl.hpp:101-134).

        compute_dtype=bfloat16 enables mixed precision (a TPU-first feature
        beyond the reference): activations and the layer-visible params are
        cast to bf16 so matmuls/convs run the MXU's native dtype, while the
        master params, the loss layers, and the optimizer stay float32.

        input_scale/input_mean (trainer keys input_divideby / input_scale /
        input_mean_value) apply ``(x - mean) * scale`` ON DEVICE to the data
        node — the TPU-native deferred-normalization path: the host pipeline
        ships uint8 (AugmentIterator output_uint8=1), quartering H2D
        bandwidth, and the cast+normalize fuses into the first conv.

        channels_last=True runs the conv stack's activations in the
        TPU-preferred (N, H, W, C) layout on device (trainer key
        ``channels_last``; measured +24% raw-jax on the inception topology,
        doc/performance.md). Logical node shapes, params, model
        files, and every user-visible tensor stay reference-NCHW: the
        forward loop tracks a per-node physical layout, feeds channels-last
        to layers declaring layout_support "nhwc"/"any", auto-converts
        around NCHW-only layers, and converts observable node values back
        before they leave the net."""
        self.cfg = cfg
        self.max_batch = batch_size
        self.compute_dtype = compute_dtype
        self.fuse_siblings = fuse_siblings
        self.channels_last = bool(channels_last)
        self._fuse_plan: Optional[Dict[int, List[int]]] = None
        self.input_scale = float(input_scale)
        self.input_mean = None if input_mean is None else \
            np.asarray(input_mean, np.float32)
        self.layers: List[Layer] = []        # one per connection (shared -> primary obj)
        self.is_shared: List[bool] = []
        self.node_shapes: List[Tuple[int, int, int, int]] = []
        self._build_layers()
        if infer_shapes:
            self._infer_shapes()

    # ------------------------------------------------------------------
    def _build_layers(self) -> None:
        cfg = self.cfg
        for i, info in enumerate(cfg.layers):
            if info.type == factory.kSharedLayer:
                assert info.primary_layer_index >= 0, "primary_layer_index problem"
                check(info.primary_layer_index < len(self.layers),
                      "shared layer primary_layer_index exceed bound")
                self.layers.append(self.layers[info.primary_layer_index])
                self.is_shared.append(True)
                continue
            lay = factory.create_layer(info.type)
            if hasattr(lay, "n_out"):  # split: fan-out = connection's out arity
                lay.n_out = max(len(info.nindex_out), 1)
            for k, v in cfg.defcfg:
                lay.set_param(k, v)
            for k, v in cfg.layercfg[i]:
                lay.set_param(k, v)
            self.layers.append(lay)
            self.is_shared.append(False)

    def layer_scope(self, i: int) -> str:
        """The ``jax.named_scope`` of connection ``i`` in every compiled
        program: the name the conf gives the layer (``layer[...] =
        conv:conv1`` -> ``conv1``), else ``<type>_<index>``. It is what
        puts a device operation of the profiler's trace to its layer
        (utils/devtrace.py)."""
        return scope_name(self.cfg.layers[i].name
                          or "%s_%d" % (self.layers[i].type_name, i))

    def group_scope(self, members) -> str:
        """One scope for a fused group, named by its members."""
        return "+".join(self.layer_scope(j) for j in members)

    def _infer_shapes(self) -> None:
        """Shape inference sweep (InitConnection semantics)."""
        cfg = self.cfg
        shapes: List[Optional[Tuple[int, int, int, int]]] = \
            [None] * cfg.param.num_nodes
        c, h, w = cfg.param.input_shape
        shapes[0] = (self.max_batch, c, h, w)
        for i in range(cfg.param.extra_data_num):
            es = cfg.extra_shape[i * 3: i * 3 + 3]
            shapes[i + 1] = (self.max_batch, es[0], es[1], es[2])
        for i, info in enumerate(cfg.layers):
            in_shapes = []
            for j in info.nindex_in:
                check(shapes[j] is not None,
                      "node %d used before defined" % j)
                in_shapes.append(shapes[j])
            out_shapes = self.layers[i].infer_shape(in_shapes)
            check(len(out_shapes) == len(info.nindex_out),
                  "layer %d: output arity mismatch" % i)
            for j, s in zip(info.nindex_out, out_shapes):
                shapes[j] = s
        self.node_shapes = shapes  # type: ignore[assignment]

    # ------------------------------------------------------------------
    def init_params(self, seed: int = 0) -> Params:
        params: Params = []
        for i, lay in enumerate(self.layers):
            if self.is_shared[i]:
                params.append({})
            else:
                rng = np.random.RandomState(seed + i * 9973)
                params.append(lay.init_params(rng))
        return params

    # --- shared numerics rules (used by forward and forward_pipelined) ---
    def _integer_id_nodes(self) -> set:
        """Nodes carrying integer ids stored as floats: inputs of
        integer_inputs layers (embed) plus their transitive producers, so
        ids routed through pass-through layers (split/concat) are protected
        at the graph input too. These must never be cast to a low-precision
        compute dtype — bf16 corrupts ids above ~256."""
        cfg = self.cfg
        id_nodes = set()
        for i, info in enumerate(cfg.layers):
            if self.layers[i].integer_inputs:
                id_nodes.update(info.nindex_in)
        changed = bool(id_nodes)
        while changed:
            changed = False
            for info in cfg.layers:
                if any(o in id_nodes for o in info.nindex_out):
                    new = set(info.nindex_in) - id_nodes
                    if new:
                        id_nodes |= new
                        changed = True
        return id_nodes

    def _cast_params_compute(self, params: Params) -> Params:
        """Cast master params to the compute dtype for the layer-visible
        view; grads flow back in f32. Non-trainable state
        (layer.state_keys(), e.g. BN running stats) stays f32 so EMAs never
        accumulate bf16 rounding."""
        cdt = self.compute_dtype
        with jax.named_scope("cast_params"):
            return [
                {k: (jnp.asarray(v).astype(cdt)
                     if (jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                         and k not in self.layers[i].state_keys()) else v)
                 for k, v in p.items()}
                for i, p in enumerate(params)]

    # --- sibling-conv fusion (TPU perf pass; beyond the reference) ---
    def _sibling_conv_plan(self) -> Dict[int, List[int]]:
        """Groups of distinct convolutions that read the same value (same
        input node, or nodes aliased through identity ``split`` fan-outs)
        with identical geometry. Each group runs as ONE wider conv at apply
        time — inception-style 1x1 branch/reduce convs (e.g. GoogLeNet's
        three per module) are individually too narrow to fill the MXU's
        128-wide systolic dimension; concatenated along the output-channel
        dim they become a single large matmul with per-channel-identical
        numerics. Keyed by leader (first member) layer index."""
        if self._fuse_plan is not None:
            return self._fuse_plan
        groups: Dict[int, List[int]] = {}
        cfg = self.cfg
        if self.fuse_siblings:
            immutable, chain = self._fusion_graph_tools()
            by_key: Dict[tuple, List[int]] = {}
            for i, info in enumerate(cfg.layers):
                lay = self.layers[i]
                if (self.is_shared[i]
                        or type(lay) is not ConvolutionLayer
                        or len(info.nindex_in) != 1
                        or len(info.nindex_out) != 1):
                    continue
                p = lay.param
                if p.num_group != 1:
                    continue
                root = chain(info.nindex_in[0])
                # the out node must be ours alone: a second writer would
                # overwrite the (early) fused result in a different order
                if root is None or not immutable(info.nindex_out[0]):
                    continue
                key = (root, p.kernel_height, p.kernel_width,
                       p.stride, p.pad_y, p.pad_x, p.no_bias)
                by_key.setdefault(key, []).append(i)

            for cand in by_key.values():
                # single-writer chains make every member's input value
                # immutable and identical, so fusing at the leader's
                # position is safe regardless of where members sit
                if len(cand) >= 2:
                    groups[cand[0]] = list(cand)
        self._fuse_plan = groups
        return groups

    def _fusion_graph_tools(self):
        """(immutable, chain): the value-safety rules the sibling plan
        rests on (pinned by tests/test_fusion.py MUTATED_CONF):

        immutable(n): the node's value never changes after its first
        definition — at most one writer (a second writer is a self-loop
        rewrite hazard). Graph inputs (data + extra_data) carry an
        implicit writer (-1), set by the harness before layer 0.

        chain(n): the alias chain n -> canonical through identity
        ``split`` copies; None if any node on it can be rewritten
        (fusion members must read a value that is immutable AND shared
        with their siblings)."""
        cfg = self.cfg
        writers: Dict[int, List[int]] = {
            n: [-1] for n in range(1 + cfg.param.extra_data_num)}
        for i, info in enumerate(cfg.layers):
            for o in info.nindex_out:
                writers.setdefault(o, []).append(i)

        def immutable(n):
            return len(writers.get(n, ())) <= 1

        alias = {}
        for i, info in enumerate(cfg.layers):
            if isinstance(self.layers[i], SplitLayer) \
                    and not self.is_shared[i]:
                for o in info.nindex_out:
                    if o != info.nindex_in[0]:
                        alias[o] = info.nindex_in[0]

        def chain(n):
            seen = set()
            while True:
                if not immutable(n):
                    return None
                if n not in alias or n in seen:
                    return n
                seen.add(n)
                n = alias[n]

        return immutable, chain

    # --- channels-last layout tracking ---
    def _image_like(self, n: int) -> bool:
        """Nodes eligible for the channels-last layout: real multi-channel
        feature maps. Excluded: flat (b,1,1,w) matrices, (b,C,1,1) channel
        vectors (transposing buys nothing), and single-channel (b,1,h,w)
        maps — BN/PRelu treat c==1 nodes as per-width fc features
        (is_fc), which a physical transpose would silently misalign."""
        b, c, h, w = self.node_shapes[n]
        return c > 1 and (h > 1 or w > 1)

    @staticmethod
    def _relayout(v, frm: str, to: str):
        if frm == to or v.ndim != 4:
            return v
        with jax.named_scope("relayout"):
            return ops.to_nhwc(v) if to == "NHWC" else ops.to_nchw(v)

    def _apply_fused_siblings(self, g: List[int], params, values,
                              layouts, ctx=None) -> None:
        """One conv over the concatenated (along O) member kernels, sliced
        back to each member's output node. When every member asks for
        ``remat``, the fused conv is checkpointed as a unit. Inside a
        pipeline stage body (ctx.manual_tp) the fused kernel takes the
        same manual output-feature sharding as a plain conv — each model
        rank convolves every member's 1/mp share and the group-local
        gather + unpermute restores the canonical member order."""
        from ..layer.layers import (manual_axis_size, manual_tp_blocks,
                                    manual_tp_local_rows, manual_tp_gather)
        cfg = self.cfg
        p0 = self.layers[g[0]].param
        n_in = cfg.layers[g[0]].nindex_in[0]
        want = ("NHWC" if (self.channels_last and self._image_like(n_in))
                else "NCHW")
        x = values[n_in]
        if layouts[n_in] != want:
            x = self._relayout(x, layouts[n_in], want)
            values[n_in] = x
            layouts[n_in] = want
        mp = manual_axis_size(ctx, "model") if ctx is not None else 1
        member_ch = [self.layers[j].param.num_channel for j in g]
        tp_blocks = manual_tp_blocks(sum(member_ch), member_ch, mp)

        def fused(xv, member_params):
            w = jnp.concatenate(
                [self.layers[j]._kernel_oihw(member_params[k]["wmat"])
                 for k, j in enumerate(g)], axis=0)
            if tp_blocks:
                y = ops.conv2d(xv, manual_tp_local_rows(w, tp_blocks, mp),
                               stride=p0.stride, pad=(p0.pad_y, p0.pad_x),
                               layout=want)
                y = manual_tp_gather(y, tp_blocks, mp,
                                     axis=3 if want == "NHWC" else 1)
            else:
                y = ops.conv2d(xv, w, stride=p0.stride,
                               pad=(p0.pad_y, p0.pad_x), layout=want)
            if p0.no_bias == 0:
                b = jnp.concatenate(
                    [member_params[k]["bias"] for k in range(len(g))])
                y = y + b.reshape((1, 1, 1, -1) if want == "NHWC"
                                  else (1, -1, 1, 1))
            return y

        if all(self.layers[j].remat for j in g):
            fused = jax.checkpoint(fused)
        # one scope for the group: the fused conv is no member's alone
        with jax.named_scope(self.group_scope(g)):
            y = fused(x, [params[j] for j in g])
            off = 0
            for j in g:
                n = self.layers[j].param.num_channel
                out_n = cfg.layers[j].nindex_out[0]
                values[out_n] = (y[..., off:off + n] if want == "NHWC"
                                 else y[:, off:off + n])
                layouts[out_n] = want
                off += n

    def _apply_remat(self, lay, pidx, p, ins, ctx):
        """jax.checkpoint around a pure layer apply (config key ``remat``):
        the layer's activations are recomputed during the backward pass
        instead of saved, trading FLOPs for HBM — how deep stacks and long
        contexts fit on a chip. Only side-effect-free layers qualify (no
        state updates, no pairtest diffs, no loss layer); the rng and
        epoch are passed as arguments so the recompute replays the
        identical stochastic draw. What such a layer adds to the step's
        loss of itself (``ctx.losses``: any layer may append a term; an
        attention layer under a learned selection does) and its readings
        (``ctx.layer_stats``) leave the checkpointed body as its outputs."""
        def pure(pp, xs, rng, epoch):
            c2 = ApplyContext(train=ctx.train, labels=None,
                              epoch=epoch, mesh=ctx.mesh,
                              channels_last=ctx.channels_last,
                              manual_tp=ctx.manual_tp)
            c2.rng = rng
            c2.layer_index = getattr(ctx, "layer_index", pidx)
            c2.conn_index = ctx.conn_index
            return (tuple(lay.apply(pp, list(xs), c2)), c2.layer_stats,
                    tuple(c2.losses))
        outs, stats, losses = jax.checkpoint(pure)(p, tuple(ins), ctx.rng,
                                                   ctx.epoch)
        ctx.layer_stats.update(stats)
        ctx.losses.extend(losses)
        return list(outs)

    def _apply_layer_range(self, params, values, ctx, base_rng,
                           lo: int, hi: int, layouts=None):
        """Apply layers [lo, hi) in place on the node-values list, with the
        per-layer rng fold and the losses-run-in-f32 rule.

        ``layouts`` tracks each node value's physical layout
        ("NCHW"/"NHWC") under channels_last mode; conversions are inserted
        only at boundaries between layout worlds (in a typical CNN: one
        transpose of the data node into the first conv and one back at
        flatten — XLA folds both into the adjacent ops). Returns the
        layouts list so callers can convert escaping values back."""
        cfg = self.cfg
        cdt = self.compute_dtype
        if layouts is None:
            layouts = ["NCHW"] * cfg.param.num_nodes
        fuse_groups = self._sibling_conv_plan()
        fused_done: set = set()
        for i in range(lo, hi):
            if i in fused_done:
                continue
            g = fuse_groups.get(i)
            if g is not None and g[-1] < hi:
                self._apply_fused_siblings(g, params, values, layouts,
                                           ctx=ctx)
                fused_done.update(g)
                continue
            info = cfg.layers[i]
            lay = self.layers[i]
            pidx = (info.primary_layer_index if self.is_shared[i] else i)
            ctx.rng = jax.random.fold_in(base_rng, i)
            ctx.layer_index = pidx
            # connection identity (distinct even for share-tied layers):
            # the KV-cache key — two tied attention layers share weights
            # but must NOT share a cache
            ctx.conn_index = i
            sup = lay.layout_support
            if (self.channels_last and sup == "nhwc"
                    and all(self._image_like(j) for j in info.nindex_in)):
                want = "NHWC"
            elif sup == "any" and info.nindex_in:
                want = layouts[info.nindex_in[0]]
            else:
                want = "NCHW"
            ctx.channels_last = (want == "NHWC")
            ins = []
            for j in info.nindex_in:
                v = values[j]
                if layouts[j] != want:
                    # write the converted value back so further consumers
                    # of the node reuse one transpose (CSE also catches it)
                    v = self._relayout(v, layouts[j], want)
                    values[j] = v
                    layouts[j] = want
                ins.append(v)
            with jax.named_scope(self.layer_scope(i)):
                if cdt is not None and lay.is_loss:
                    # losses always in f32 (softmax/log numerics)
                    ins = [x.astype(jnp.float32) for x in ins]
                if (lay.remat and not lay.is_loss and not lay.state_keys()
                        and ctx.decode_pos is None
                        and not isinstance(lay, factory.PairTestLayer)):
                    # remat is a training-memory trade; the KV-cached
                    # decode forward skips it (no backward — and cache
                    # updates could not escape a jax.checkpoint body anyway)
                    outs = self._apply_remat(lay, pidx, params[pidx], ins,
                                             ctx)
                else:
                    outs = lay.apply(params[pidx], ins, ctx)
            for j, v in zip(info.nindex_out, outs):
                values[j] = v
                layouts[j] = want if v.ndim == 4 else "NCHW"
        return layouts

    def _normalize_input(self, x):
        """Device-side input normalization ``(x - mean) * scale``. With the
        host pipeline shipping raw uint8 (AugmentIterator output_uint8=1)
        this replaces the iterator's divideby/mean_value arithmetic
        (iter_image.py AugmentIterator._set_data) at zero cost — XLA fuses
        it into the first conv's input read. Channel order of input_mean
        matches the augmenter's mean_value key (b, g, r)."""
        if self.input_scale == 1.0 and self.input_mean is None:
            return x
        with jax.named_scope("input"):
            x = x.astype(jnp.float32)
            if self.input_mean is not None:
                x = x - jnp.asarray(self.input_mean).reshape(1, -1, 1, 1)
            if self.input_scale != 1.0:
                x = x * self.input_scale
            return x

    def forward(self, params: Params, data, extra_data=(),
                labels: Optional[LabelInfo] = None, train: bool = False,
                rng=None, epoch=0, mesh=None, decode_pos=None,
                kv_cache=None):
        """Run the DAG; returns (node_values list, total_loss scalar).

        ``decode_pos``/``kv_cache`` select the KV-cached decode mode
        (Trainer.generate): the data covers sequence positions
        [decode_pos, decode_pos + L) and attention layers attend against
        (and update) the caches; the position-updated caches land in
        ``self._last_cache_updates``."""
        cfg = self.cfg
        cdt = self.compute_dtype
        values: List[Optional[jnp.ndarray]] = [None] * cfg.param.num_nodes
        values[0] = self._normalize_input(jnp.asarray(data))
        if self.node_shapes:
            # fail fast on iterator/net shape drift (e.g. a flat mnist
            # iterator feeding a conv net declared 1,28,28) instead of
            # letting a zero-sized conv output surface as a confusing
            # matmul error downstream
            check(tuple(values[0].shape[1:]) == tuple(self.node_shapes[0][1:]),
                  "input batch shape %r does not match the declared "
                  "input_shape %r — check the iterator configuration "
                  "(e.g. mnist input_flat)"
                  % (tuple(values[0].shape[1:]),
                     tuple(self.node_shapes[0][1:])))
        for i, ex in enumerate(extra_data):
            values[i + 1] = jnp.asarray(ex)
        if cdt is not None:
            id_nodes = self._integer_id_nodes()
            with jax.named_scope("input"):
                values = [v if v is None or i in id_nodes else v.astype(cdt)
                          for i, v in enumerate(values)]
            params = self._cast_params_compute(params)
        ctx = ApplyContext(train=train, labels=labels, epoch=epoch,
                           mesh=mesh, decode_pos=decode_pos,
                           kv_cache=kv_cache or {})
        base_rng = rng if rng is not None else jax.random.PRNGKey(0)
        layouts = self._apply_layer_range(params, values, ctx, base_rng,
                                          0, len(cfg.layers))
        self._last_cache_updates = ctx.cache_updates
        self._last_layer_stats = ctx.layer_stats
        # every escaping node value is reference-NCHW; the transposes of
        # values the caller never reads are dead code XLA eliminates
        for n, lo_ in enumerate(layouts):
            if lo_ == "NHWC" and values[n] is not None:
                values[n] = self._relayout(values[n], "NHWC", "NCHW")
        total_loss = sum(ctx.losses) if ctx.losses else jnp.zeros(())
        self._last_pairtest_diffs = getattr(ctx, "pairtest_diffs", [])
        # non-gradient param updates (BN running stats); valid only when
        # read immediately after this call within the same trace
        self._last_state_updates = ctx.state_updates
        return values, total_loss

    # ------------------------------------------------------------------
    # pipeline parallelism (config key pipeline_parallel = k)
    def _pipeline_chain_prefix(self) -> int:
        """Length of the non-loss prefix, verifying it is a topologically
        ordered DAG: every layer reads only the data node or nodes already
        written by an earlier layer (in-place rewrites allowed). Branched
        nets (split / concat / inception-style fan-out) are accepted —
        stage cuts carry the full live set of boundary nodes
        (_pipeline_live_set), not a single activation."""
        cfg = self.cfg
        first_loss = next(
            (i for i, lay in enumerate(self.layers) if lay.is_loss),
            len(cfg.layers))
        check(first_loss > 0, "pipeline_parallel: empty non-loss prefix")
        written = {0}
        for i in range(first_loss):
            info = cfg.layers[i]
            for n in info.nindex_in:
                check(n in written,
                      "pipeline_parallel: layer %d (%s) reads node %d "
                      "before any layer writes it — the prefix must be "
                      "topologically ordered"
                      % (i, self.layers[i].type_name, n))
            written.update(info.nindex_out)
        return first_loss

    def _pipeline_live_set(self, cut: int, first_loss: int):
        """Nodes whose values must cross the stage boundary after ``cut``
        layers: nodes holding a value (the data node, or written by a
        layer < cut) that are still needed — read by a layer >= cut at or
        before the node's next in-place rewrite (an in-place layer reads
        its input before overwriting it), or, at the final cut, part of
        the net's observable output (the last prefix layer's out nodes,
        which predict/extract_feature read after the loss tail)."""
        cfg = self.cfg
        n_layers = len(cfg.layers)
        writers: Dict[int, List[int]] = {}
        readers: Dict[int, List[int]] = {}
        for i, info in enumerate(cfg.layers):
            for n in info.nindex_in:
                readers.setdefault(n, []).append(i)
            for n in info.nindex_out:
                writers.setdefault(n, []).append(i)
        final_outs = (set(cfg.layers[first_loss - 1].nindex_out)
                      if cut >= first_loss else set())
        live = []
        for n in range(cfg.param.num_nodes):
            has_value = (n == 0) or any(w < cut
                                        for w in writers.get(n, ()))
            if not has_value:
                continue
            nxt = min((w for w in writers.get(n, ()) if w >= cut),
                      default=n_layers)
            if (n in final_outs
                    or any(cut <= r <= nxt for r in readers.get(n, ()))):
                live.append(n)
        return tuple(live)

    def _partition_stages(self, n_layers: int, k: int, param_sizes=None):
        """Split layers [0, n_layers) into k contiguous stages minimizing
        the maximum stage cost — the pipeline's step time is set by its
        slowest stage.

        Cost proxy per layer: output activation elements (cheap elementwise
        work) plus, when ``param_sizes`` is given, params x output spatial
        extent — the per-sample MAC count of a conv/dense layer. The MAC
        term both balances compute and spreads parameter bytes across
        stages (each rank OWNS its stage's params in the packed PP mode, so
        a stage hoarding the param-heavy tail would defeat the memory
        scaling)."""
        cfg = self.cfg
        costs = []
        for i in range(n_layers):
            c = sum(int(np.prod(self.node_shapes[n][1:]))
                    for n in cfg.layers[i].nindex_out)
            if param_sizes is not None:
                shape = self.node_shapes[cfg.layers[i].nindex_out[0]]
                spatial = (int(np.prod(shape[2:])) if len(shape) > 2 else 1)
                c += int(param_sizes[i]) * spatial
            costs.append(c)
        k = min(k, n_layers)
        prefix = np.concatenate([[0], np.cumsum(costs, dtype=np.float64)])

        def seg(a, b):
            return prefix[b] - prefix[a]

        # dp[j][i] = minimal max-stage-cost splitting first i layers into j
        INF = float("inf")
        dp = [[INF] * (n_layers + 1) for _ in range(k + 1)]
        cut = [[0] * (n_layers + 1) for _ in range(k + 1)]
        dp[0][0] = 0.0
        for j in range(1, k + 1):
            for i in range(j, n_layers + 1):
                for m in range(j - 1, i):
                    v = max(dp[j - 1][m], seg(m, i))
                    if v < dp[j][i]:
                        dp[j][i] = v
                        cut[j][i] = m
        bounds = [n_layers]
        for j in range(k, 0, -1):
            bounds.append(cut[j][bounds[-1]])
        bounds.reverse()
        return [(bounds[s], bounds[s + 1]) for s in range(k)]

    def pipeline_plan(self, params, k):
        """The stage partition shared by the Trainer's parameter packing
        and forward_pipelined — ONE source of truth for stage boundaries
        (the packed-entry offsets are built from the same plan). Returns
        (stages, first_loss); validates the chain shape. Stateful layers
        (BN running stats) are supported — their state rides the
        pipeline's scan carry (forward_pipelined state slots) — but a
        SHARED stateful layer must land in its primary's stage so exactly
        one pipe rank owns (and chains) the slot."""
        first_loss = self._pipeline_chain_prefix()
        psizes = [sum(int(np.prod(np.shape(v)))
                      for v in params[i].values())
                  for i in range(first_loss)]
        stages = self._partition_stages(first_loss, k, param_sizes=psizes)
        stages += [(first_loss, first_loss)] * (k - len(stages))
        stage_of = {i: s for s, (lo, hi) in enumerate(stages)
                    for i in range(lo, hi)}
        for i in range(first_loss):
            if self.is_shared[i] and self.layers[i].state_keys():
                pidx = self.cfg.layers[i].primary_layer_index
                check(stage_of.get(pidx) == stage_of.get(i),
                      "pipeline_parallel: shared stateful layer %d must "
                      "fall in the same stage as its primary %d (one pipe "
                      "rank must own the state slot)" % (i, pidx))
        return stages, first_loss

    def forward_pipelined(self, params, data, labels=None, train=True,
                          rng=None, epoch=0, mesh=None, n_micro=None,
                          axis="pipe", packed_entries=None, stages=None):
        """GPipe forward: the non-loss prefix (any topologically ordered
        DAG — branches, split/concat fan, in-place rewrites) runs as a
        k-stage heterogeneous pipeline over the mesh's ``axis``
        (parallel.pipeline_apply_stages); each stage's padded stream
        carries the flattened concat of the cut's live node set. The loss
        layers run replicated on the gathered final live set, so numerics
        match the single-device net.

        Green-field beyond the reference (SURVEY.md §2.9 "Not present").
        Note: BN batch statistics are per-microbatch (standard GPipe
        semantics).

        ``packed_entries`` (the Trainer's stage-packing plan, a list per
        stage of (layer, key, offset, shape) tuples) selects the
        PARAMETER-SHARDED mode: ``params[-1]["__pp_packed__"]`` is a
        (k, F_p) flat array sharded over the pipe axis — each rank owns
        exactly its own stage's parameter bytes (the per-device model
        ownership of the reference's worker threads,
        src/nnet/neural_net-inl.hpp:304-628) and unpacks its row locally,
        with zero parameter communication. Without it stage params ride
        in replicated (the small-model fast path)."""
        from .. import parallel as par
        from ..parallel._compat import _patch_key_zeros
        _patch_key_zeros()   # grad-of-switch PRNG workaround (see _compat)

        cfg = self.cfg
        cdt = self.compute_dtype
        k = mesh.shape[axis]
        if stages is None:
            stages, first_loss = self.pipeline_plan(params, k)
        else:
            first_loss = self._pipeline_chain_prefix()
        batch = data.shape[0]
        if not n_micro:
            n_micro = k
        check(batch % n_micro == 0,
              "pipeline_parallel: batch_size %d not divisible by %d "
              "microbatches" % (batch, n_micro))
        mb = batch // n_micro

        packed = None
        if packed_entries is not None:
            packed = params[-1]["__pp_packed__"]
        if cdt is not None:
            # cast only the per-layer entries (loss tail runs f32 anyway;
            # packed stage params are cast after the in-stage unpack)
            params = self._cast_params_compute(
                params[: len(self.layers)]) + list(
                    params[len(self.layers):])
        base_rng = rng if rng is not None else jax.random.PRNGKey(0)

        def node_size(n):
            return int(np.prod(self.node_shapes[n][1:]))

        # boundary s = the LIVE SET of nodes crossing the cut before stage
        # s (a single node for linear chains; several for branched DAGs —
        # each stage's padded stream carries their flattened concat)
        boundaries = [self._pipeline_live_set(0, first_loss)]
        for (lo, hi) in stages:
            boundaries.append(self._pipeline_live_set(hi, first_loss)
                              if hi > lo else boundaries[-1])
        F = max(sum(node_size(n) for n in b) for b in boundaries)

        # token-id boundaries stay f32 (same protection as forward(); the
        # padded carry then runs f32 and each stage casts its own input)
        id_nodes = self._integer_id_nodes()
        boundary_nodes = {n for b in boundaries for n in b}
        stream_dtype = (jnp.float32
                        if (cdt is None or (boundary_nodes & id_nodes))
                        else cdt)

        # non-gradient layer state (BN running stats) rides the pipeline's
        # scan carry as one flat f32 (S,) vector: each stage seeds
        # ctx.state_updates for its own layers from the incoming vector
        # (so the EMA chains across microbatches in order, like
        # single-device sequential batches) and writes the updated slots
        # back; per-stage slot ownership is combined by pipeline_apply's
        # state_masks psum, and composed data shards are pmean-ed.
        entry_at = {}
        if packed_entries is not None:
            for s_, es in enumerate(packed_entries):
                for (li, key, eoff, eshape) in es:
                    entry_at[(li, key)] = (s_, eoff, eshape)
        stage_of = {i: s_ for s_, (lo, hi) in enumerate(stages)
                    for i in range(lo, hi)}
        state_slots = []   # (layer, key, off, size, shape)
        soff = 0
        for i in range(first_loss):
            if self.is_shared[i]:
                continue
            for key in self.layers[i].state_keys():
                if packed_entries is not None:
                    shape = tuple(entry_at[(i, key)][2])
                else:
                    shape = tuple(np.shape(params[i][key]))
                sz = int(np.prod(shape)) if shape else 1
                state_slots.append((i, key, soff, sz, shape))
                soff += sz
        S = soff
        state0 = state_masks = None
        slots_by_stage: Dict[int, list] = {}
        if state_slots:
            parts = []
            for (i, key, _, sz, shape) in state_slots:
                if packed_entries is not None:
                    s_, eoff, _ = entry_at[(i, key)]
                    v = packed[s_, eoff: eoff + sz]
                else:
                    v = jnp.ravel(params[i][key])
                parts.append(v.astype(jnp.float32))
            state0 = jnp.concatenate(parts)
            masks = np.zeros((k, S), bool)
            for slot in state_slots:
                i, _, so, sz = slot[0], slot[1], slot[2], slot[3]
                masks[stage_of[i], so: so + sz] = True
                slots_by_stage.setdefault(stage_of[i], []).append(slot)
            state_masks = jnp.asarray(masks)

        def run_stage_layers(p, padded, s, micro_id, state_in=None):
            lo, hi = stages[s]
            ctx = ApplyContext(train=train, labels=None, epoch=epoch,
                               mesh=mesh, manual_tp=True)
            own_slots = slots_by_stage.get(s, ())
            if state_in is not None:
                for (i, key, so, sz, shape) in own_slots:
                    ctx.state_updates[(i, key)] = \
                        state_in[so: so + sz].reshape(shape)
            vals = [None] * cfg.param.num_nodes
            off = 0
            for n in boundaries[s]:
                sz = node_size(n)
                # batch dim left as -1: under a composed data axis the
                # shard_map body sees the per-device microbatch shard
                v = padded[:, off: off + sz].reshape(
                    (-1,) + tuple(self.node_shapes[n][1:]))
                if cdt is not None and n not in id_nodes:
                    v = v.astype(cdt)
                vals[n] = v
                off += sz
            # fold the microbatch index so stochastic layers (dropout,
            # insanity) draw fresh noise per microbatch, not one shared mask
            mb_rng = jax.random.fold_in(base_rng, micro_id)
            louts = self._apply_layer_range(p, vals, ctx, mb_rng, lo, hi)
            for n in boundaries[s + 1]:
                if louts[n] == "NHWC":
                    # the stage stream carries reference-NCHW bytes
                    vals[n] = self._relayout(vals[n], "NHWC", "NCHW")
            ys = [vals[n].reshape(vals[n].shape[0], -1)
                  .astype(stream_dtype) for n in boundaries[s + 1]]
            y = jnp.concatenate(ys, axis=1) if len(ys) > 1 else ys[0]
            y = jnp.pad(y, ((0, 0), (0, F - y.shape[1])))
            if state_in is None:
                return y
            st_out = state_in
            for (i, key, so, sz, shape) in own_slots:
                st_out = st_out.at[so: so + sz].set(
                    jnp.ravel(ctx.state_updates[(i, key)])
                    .astype(jnp.float32))
            return y, st_out

        def unpack_stage(s, row):
            """Rebuild stage s's per-layer param dicts from its flat row
            (static offsets — pure slicing, stays on the owning rank)."""
            pl: List[Dict[str, jnp.ndarray]] = \
                [{} for _ in range(len(self.layers))]
            for (li, key, off, shape) in packed_entries[s]:
                v = row[off: off + int(np.prod(shape))].reshape(shape)
                if (cdt is not None
                        and key not in self.layers[li].state_keys()):
                    # non-trainable state (BN running stats) stays f32,
                    # same rule as _cast_params_compute
                    v = v.astype(cdt)
                pl[li][key] = v
            return pl

        def make_stage(s):
            if state_slots:
                def body(p, padded, micro_id, state_in):
                    if packed is not None:
                        # p is this rank's (1, F_p) packed row
                        p = unpack_stage(s, p[0])
                    return run_stage_layers(p, padded, s, micro_id,
                                            state_in)
            else:
                def body(p, padded, micro_id):
                    if packed is not None:
                        # p is this rank's (1, F_p) packed row
                        p = unpack_stage(s, p[0])
                    return run_stage_layers(p, padded, s, micro_id)
            # GPipe re-materialization: each stage's activations are
            # recomputed in the backward pipeline instead of saved —
            # O(boundary) live memory per stage. It also keeps every
            # lax.switch branch's residual set = its (shape-uniform)
            # inputs, which jax's cond partial-eval requires (internal
            # PRNG-key residuals from stochastic layers differ per branch
            # otherwise and trip its typematch invariant, jax 0.9).
            return jax.checkpoint(body)

        xd = self._normalize_input(jnp.asarray(data)).astype(stream_dtype)
        x_stream = xd.reshape(n_micro, mb, -1)
        x_stream = jnp.pad(
            x_stream, ((0, 0), (0, 0), (0, F - x_stream.shape[2])))
        dp_axis = "data" if (mesh is not None
                             and "data" in mesh.axis_names
                             and mesh.shape["data"] > 1) else None
        from jax.sharding import PartitionSpec as P
        out = par.pipeline_apply_stages(
            [make_stage(s) for s in range(k)],
            packed if packed is not None else params, x_stream, mesh,
            axis=axis, batch_spec=dp_axis,
            params_spec=P(axis, None) if packed is not None else None,
            state0=state0, state_masks=state_masks)
        st_out = None
        if state_slots:
            out, st_out = out
        # unpack the final live set; loss tail runs replicated on it
        # (tiny compute on (batch, nclass)-sized nodes)
        values = [None] * cfg.param.num_nodes
        off = 0
        for n in boundaries[-1]:
            sz = node_size(n)
            values[n] = out[:, :, off: off + sz].reshape(
                (batch,) + tuple(self.node_shapes[n][1:]))
            off += sz
        ctx = ApplyContext(train=train, labels=labels, epoch=epoch,
                           mesh=mesh)
        louts = self._apply_layer_range(params, values, ctx, base_rng,
                                        first_loss, len(cfg.layers))
        for n, lo_ in enumerate(louts):
            if lo_ == "NHWC" and values[n] is not None:
                values[n] = self._relayout(values[n], "NHWC", "NCHW")
        total_loss = sum(ctx.losses) if ctx.losses else jnp.zeros(())
        self._last_pairtest_diffs = getattr(ctx, "pairtest_diffs", [])
        # prefix state came back through the pipeline's state carry; tail
        # layers (replicated) recorded theirs on ctx directly
        ups = dict(ctx.state_updates)
        if st_out is not None:
            for (i, key, so, sz, shape) in state_slots:
                ups[(i, key)] = st_out[so: so + sz].reshape(shape)
        self._last_state_updates = ups
        return values, total_loss

    # ------------------------------------------------------------------
    def label_info_from(self, label_batch, as_numpy: bool = False) -> LabelInfo:
        """Build named label fields from a (batch, label_width) matrix using
        the config's label_vec ranges (GetLabelInfo, nnet_impl-inl.hpp:257-272).

        as_numpy=True keeps fields as host arrays (for metrics); default
        wraps them as jnp for use inside the jitted step."""
        fields = {}
        lb = np.asarray(label_batch) if as_numpy else jnp.asarray(label_batch)
        for name, idx in self.cfg.label_name_map.items():
            begin, end = self.cfg.label_range[idx]
            fields[name] = lb[:, begin:end]
        return LabelInfo(fields)

    # ------------------------------------------------------------------
    def save_model_blob(self, params: Params) -> bytes:
        from ..parallel import fetch_global
        w = serializer.Writer()
        for i, lay in enumerate(self.layers):
            if not self.is_shared[i]:
                lay.save_model(w, {k: fetch_global(v)
                                   for k, v in params[i].items()})
        return w.getvalue()

    def load_model_blob(self, blob: bytes) -> Params:
        r = serializer.Reader(blob)
        params: Params = []
        for i, lay in enumerate(self.layers):
            if self.is_shared[i]:
                params.append({})
            else:
                params.append({k: v for k, v in lay.load_model(r).items()})
        return params

    # weight access (SetWeight/GetWeight, nnet_impl-inl.hpp:243-270)
    def get_weight(self, params: Params, layer_name: str, tag: str):
        idx = self.cfg.get_layer_index(layer_name)
        for t, key in self.layers[idx].visit_order():
            if t == tag:
                from ..parallel import fetch_global
                arr = fetch_global(params[idx][key])
                shape = list(arr.shape)
                return arr.reshape(arr.shape[0], -1) if arr.ndim > 1 \
                    else arr.reshape(1, -1), shape
        raise ValueError("layer %s has no weight tag %s" % (layer_name, tag))

    def set_weight(self, params: Params, value: np.ndarray,
                   layer_name: str, tag: str) -> None:
        idx = self.cfg.get_layer_index(layer_name)
        for t, key in self.layers[idx].visit_order():
            if t == tag:
                cur = params[idx][key]
                params[idx][key] = jnp.asarray(
                    np.asarray(value).reshape(np.shape(cur)), jnp.float32)
                return
        raise ValueError("layer %s has no weight tag %s" % (layer_name, tag))
