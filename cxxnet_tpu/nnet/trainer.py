"""Trainer: the INetTrainer surface over one jitted, mesh-sharded train step.

Reimplements CXXNetThreadTrainer (src/nnet/nnet_impl-inl.hpp:16-455) and the
INetTrainer ABI (src/nnet/nnet.h:18-92) TPU-first:

* the reference spawns one worker thread per GPU, slices the batch, and syncs
  gradients per-tensor through mshadow-ps; here the global batch is sharded
  over the mesh 'data' axis and XLA inserts the all-reduce over ICI — the
  whole fwd/bwd/update is ONE compiled program per (shapes, do_update).
* ``update_period`` gradient accumulation keeps a device-resident grad
  buffer; loss layers pre-scale by 1/(batch*update_period) so plain
  summation matches the reference (nnet_impl-inl.hpp:146-150).
* ``epoch_counter`` counts optimizer updates and is a traced scalar, so LR
  schedules don't trigger recompiles.
* ``update_on_server=1`` maps to ZeRO-style sharded optimizer state
  (weight-update sharding) instead of parameter-server processes.
"""

from __future__ import annotations

import json
import re
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..layer.base import check
from ..updater import create_updater
from ..utils import serializer
from ..utils import telemetry
from ..utils.metric import MetricSet
# re-exported: the paged DecodeSession raises it; the jax-free servd
# catches it by type from utils.kvblocks directly
from ..utils.kvblocks import KVPoolExhausted  # noqa: F401
from .. import ops
from .. import parallel
from .config import NetConfig
from .net import NeuralNet


def _sample_pick(temperature: float, top_k: int):
    """Next-token chooser over a (b, vocab) softmax row: greedy argmax at
    temperature 0, else sampling from log-probs / temperature (optionally
    truncated to the ``top_k`` most likely tokens). ONE implementation
    shared by ``Trainer.generate`` (solo dispatch) and ``DecodeSession``
    (batched dispatch) — the token-exactness contract between the two
    keys on the sampling math never drifting."""
    temperature, top_k = float(temperature), int(top_k)
    check(top_k >= 0, "generate: top_k must be >= 0")

    def pick(probs, step_key):
        if temperature <= 0.0:
            return jnp.argmax(probs, axis=1)
        lg = jnp.log(jnp.maximum(probs, 1e-30)) / temperature
        if top_k and top_k < lg.shape[1]:
            # exact-k mask from top_k indices (same pattern as the
            # moe gate, layers.py — a >=kth-value threshold would
            # keep every tied token)
            _, idx = jax.lax.top_k(lg, top_k)
            keep = jnp.sum(jax.nn.one_hot(idx, lg.shape[1],
                                          dtype=jnp.float32),
                           axis=1) > 0
            lg = jnp.where(keep, lg, -jnp.inf)
        return jax.random.categorical(step_key, lg, axis=1)

    return pick


def _updater_signature(up):
    """Hashable hyper-parameter signature for grouping packed-stage tensors
    whose updates are identical elementwise programs (same kind, same
    schedule/decay/clip settings — only the tensor data differs). All
    UpdaterParam and subclass fields are primitives."""
    pf = tuple(sorted((k, v) for k, v in vars(up.param).items()
                      if k not in ("tag", "silent")))
    ex = tuple(sorted((k, v) for k, v in vars(up).items() if k != "param"))
    return (up.kind,) + pf + ex


class Trainer:
    """Net trainer; one instance per training job (reference INetTrainer)."""

    def __init__(self):
        self.cfg_pairs: List[Tuple[str, str]] = []
        self.net_cfg = NetConfig()
        self.net: Optional[NeuralNet] = None
        self.batch_size = 100
        self.update_period = 1
        self.compute_dtype = None
        self.test_on_server = 0
        self.sample_counter = 0
        self.eval_train = 1
        self.epoch_counter = 0
        self.seed = 0
        self.silent = 0
        self.dev_spec = "tpu"
        self.type_pserver = "UNSPECIFIED"
        self.update_on_server = 0
        self.model_parallel = 1
        self.seq_parallel = 1
        self.pipeline_parallel = 1
        self.pipeline_micro = 0     # microbatches; 0 -> pipeline_parallel
        self.expert_parallel = 1
        self.input_scale = 1.0      # device-side input normalization
        self.input_mean = None
        self.fuse_sibling_convs = 1  # sibling-conv fusion pass (net.py)
        self.channels_last = -1     # NHWC conv-stack layout: -1 auto
        #                             (on for TPU backends), 0/1 force
        self.fsdp = 0               # ZeRO-3 param sharding over data
        self.clip_global_norm = 0.0  # 0 -> off (per-tensor clip_gradient
        #                              remains the reference-parity knob)
        # health_monitor=1: every train step additionally returns a tiny
        # on-device health vector [loss, grad_norm_sq, nan_grad_elems, ok]
        # computed INSIDE the jitted program — no extra device sync; the
        # host-side monitor (utils/health.py, wired by learn_task) reads
        # it one step late. nonfinite_action="skip" further guards the
        # step on device: a non-finite loss/grad keeps the old
        # params/opt/accumulators (jnp.where select), so one bad batch
        # can never poison the weights even without a rollback.
        self.health_monitor = 0
        self.nonfinite_action = "rollback"
        self.last_health = None     # device array of the LAST step's vector
        # names of what follows the four in that vector (set when the step
        # is traced): gauges the host monitor keeps at each check
        self.health_gauge_names = []
        # {one of those names: (gauge that says the reading passed its
        # static bound, the bound)}: a moe layer's pairs held against the
        # rows its sorted side has
        self.health_gauge_limits = {}
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        self.eval_node_names: List[Optional[str]] = []  # None -> last node
        self.mesh = None
        self.params = None
        self.opt_state = None
        self._pp_entries = None   # stage-packing plan (pipeline_parallel)
        self._pp_entry_index = {}  # (layer, key) -> (stage, offset, shape)
        self.grad_accum = None
        self._metric_accum = None   # on-device (n_metrics, 2) stat sums
        self._rng_counter = 0
        self._jit_cache: Dict = {}
        # telemetry: program keys ever built, surviving _jit_cache.clear()
        # — a recompile of a PREVIOUSLY seen key is a rebuild (donation
        # path / packing change cleared the cache), not a new signature
        self._jit_seen_keys = set()
        # entry time of the previous update() where the next call's entry
        # is one step's period later (the histogram train.period); None where
        # something else came between: a round's start, an eval, a
        # checkpoint, a cleared (init_model, load_model) or newly built step
        self._last_update_t0 = None

    # ------------------------------------------------------------------
    # configuration (reference SetParam, nnet_impl-inl.hpp:31-69)
    def set_param(self, name: str, val: str) -> None:
        if name == "dev":
            self.dev_spec = val
        if name == "batch_size":
            self.batch_size = int(val)
        if name == "update_period":
            self.update_period = int(val)
        if name == "eval_train":
            self.eval_train = int(val)
        if name == "seed":
            self.seed = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "param_server":
            self.type_pserver = val
        if name == "update_on_server":
            self.update_on_server = int(val)
        if name == "model_parallel":
            self.model_parallel = int(val)
        if name == "seq_parallel":
            self.seq_parallel = int(val)
        if name == "pipeline_parallel":
            self.pipeline_parallel = int(val)
        if name == "pipeline_micro":
            self.pipeline_micro = int(val)
        if name == "expert_parallel":
            self.expert_parallel = int(val)
        if name == "test_on_server":
            self.test_on_server = int(val)
        if name == "fuse_sibling_convs":
            self.fuse_sibling_convs = int(val)
        if name == "channels_last":
            self.channels_last = int(val)
        if name == "fsdp":
            self.fsdp = int(val)
        if name == "clip_global_norm":
            self.clip_global_norm = float(val)
        if name == "health_monitor":
            self.health_monitor = int(val)
        if name == "nonfinite_action":
            check(val in ("rollback", "skip", "abort"),
                  "nonfinite_action must be rollback, skip, or abort")
            self.nonfinite_action = val
        if name == "compute_dtype":
            check(val in ("float32", "bfloat16", "bf16"),
                  "compute_dtype must be float32 or bfloat16")
            self.compute_dtype = (jnp.bfloat16 if val in ("bfloat16", "bf16")
                                  else None)
        # device-side input normalization (pairs with the iterators'
        # output_uint8=1 deferred-normalization path, doc/io.md)
        if name == "input_divideby":
            self.input_scale = 1.0 / float(val)
        if name == "input_scale":
            self.input_scale = float(val)
        if name == "input_mean_value":
            self.input_mean = [float(x) for x in val.split(",")]
        if name.startswith("metric"):
            m = re.match(r"metric\[([^,\]]+)(?:,([^\]]+))?\]$", name)
            if m:
                label_name = m.group(1)
                node_name = m.group(2)
                self.metric.add_metric(val, label_name)
                self.train_metric.add_metric(val, label_name)
                self.eval_node_names.append(node_name)
            else:
                self.metric.add_metric(val, "label")
                self.train_metric.add_metric(val, "label")
                self.eval_node_names.append(None)
        self.cfg_pairs.append((name, val))

    # ------------------------------------------------------------------
    def _setup_mesh(self) -> None:
        """Build ONE mesh composing every requested parallelism axis.

        The reference composes its two strategies freely — DP over device
        threads plus in-layer model splitting (ngroup grouped conv,
        src/nnet/nnet_impl-inl.hpp:146-172 +
        src/layer/convolution_layer-inl.hpp:92-96); the TPU equivalent is
        one device mesh whose axes each carry one strategy:

            (data, [pipe], [ep], [sp], [model])

        Axis order puts 'data' outermost (its gradient all-reduce is the
        least frequent collective, so it may ride DCN across slices) and
        'model' innermost (per-layer TP collectives want adjacent chips on
        ICI). Axes of size 1 are omitted so single-strategy configs keep
        their existing 2-D meshes. dp is derived: whatever device count
        remains after the explicit axes divide it.

        pipeline_parallel composes with EVERY other axis (data, tensor,
        sequence, expert parallelism): stage bodies run tp/sp/ep MANUALLY
        — fullc/conv slice their output-feature shard and all-gather over
        model pairs local to their own pipe rank; attention slices its
        QUERY chunk and attends to the (already-replicated) full k/v with
        global causal offsets, sharding the O(L^2) scores 1/sp (NOT a
        ppermute ring — collective-permute rendezvous is global and would
        deadlock in the rank-divergent switch branches); moe runs its
        local expert slice and psums over ep. All collectives are
        group-local all-reduce/all-gather by construction (an automatic
        axis would instead let Shardy put mesh-wide resharding
        collectives inside the divergent branches — a deadlock).
        """
        # (both callers have held dev's kind against the backend already)
        ids = parallel.parse_device_spec(self.dev_spec)[1]
        n_avail = len(jax.devices())
        n = len(ids) if ids else 1
        check(n <= n_avail,
              "dev = %s asks for %d devices, but the %s backend has %d"
              % (self.dev_spec, n, jax.default_backend(), n_avail))
        mp = self.model_parallel
        sp = self.seq_parallel
        pp = self.pipeline_parallel
        ep = self.expert_parallel
        ways = mp * sp * pp * ep
        check(n % ways == 0,
              "device count %d must be divisible by model_parallel * "
              "seq_parallel * pipeline_parallel * expert_parallel = %d"
              % (n, ways))
        dp = n // ways
        if pp > 1:
            n_micro = self.pipeline_micro or pp
            check(self.batch_size % n_micro == 0,
                  "batch_size must be divisible by the microbatch count "
                  "(pipeline_micro, default pipeline_parallel)")
            check(dp == 1 or (self.batch_size // n_micro) % dp == 0,
                  "microbatch size (batch_size / pipeline_micro) must be "
                  "divisible by the data-parallel degree")
        else:
            check(dp == 1 or self.batch_size % dp == 0,
                  "batch_size must be divisible by the data-parallel degree")
        if n <= 1:
            # a one-device run computes on the process's first device
            first = jax.local_devices()[0].id
            check(not ids or ids[0] == first,
                  "dev = %s names device %s, but a one-device run "
                  "computes on device %d, the first of this process"
                  % (self.dev_spec, ids and ids[0], first))
            self.mesh = None
            return
        axes, sizes = ["data"], [dp]
        for name, size in (("pipe", pp), ("ep", ep), ("sp", sp),
                           ("model", mp)):
            if size > 1:
                axes.append(name)
                sizes.append(size)
        nproc = jax.process_count()
        if nproc > 1 and n == n_avail and dp % nproc == 0:
            # multi-host: hybrid DCN x ICI layout — the data axis splits
            # across processes (slices) first, so the model/sp/ep/pipe
            # collectives never cross a host boundary (the reference's
            # dist-PS only ever crossed hosts for gradients too,
            # src/nnet/nnet_ps_server.cpp)
            self.mesh = parallel.create_hybrid_mesh(
                (dp // nproc,) + tuple(sizes[1:]),
                (nproc,) + (1,) * (len(sizes) - 1),
                tuple(axes))
        else:
            self.mesh = parallel.create_mesh(ids[:n] if ids else None,
                                             tuple(axes), tuple(sizes))

    def _place_params(self) -> None:
        """Tensor/expert-parallel placement: device_put params (and matching
        opt state) with the model/ep-axis shardings; GSPMD partitions the
        matmuls (shard_map consumes the ep placements directly). With
        ``fsdp = 1`` the placements additionally split each weight over the
        data axis (ZeRO-3): GSPMD all-gathers weights just-in-time and
        reduce-scatters gradients, so param/grad/opt memory scales 1/dp."""
        self._tp_shardings = None
        self._fsdp_shardings = None
        if self.mesh is None:
            return
        # with dp == 1 there is nothing to shard over — fsdp degenerates
        # to plain placement (callers can assert on _fsdp_shardings).
        # fsdp x pipeline: stage packing already owns the parameter bytes
        # (1/k per pipe rank), so per-layer ZeRO-3 placement is skipped and
        # fsdp=1 instead means ZeRO-1 on the packed optimizer state — see
        # _pp_pack/_pp_zero1 (opt bytes scale 1/(k*dp)).
        use_fsdp = bool(self.fsdp) and self.pipeline_parallel == 1 \
            and "data" in self.mesh.axis_names \
            and self.mesh.shape["data"] > 1
        if not use_fsdp and not ("model" in self.mesh.axis_names
                                 or "ep" in self.mesh.axis_names):
            return
        from ..parallel.sharding import fsdp_shardings, param_shardings
        shards = None
        if "model" in self.mesh.axis_names or "ep" in self.mesh.axis_names:
            shards = param_shardings(self.mesh, self.net.layers, self.params)
            self._tp_shardings = shards
        if use_fsdp:
            shards = fsdp_shardings(self.mesh, self.net.layers,
                                    self.params, base_shardings=shards)
            self._fsdp_shardings = shards
        self.params = [
            {k: jax.device_put(jnp.asarray(v), shards[i][k])
             for k, v in p.items()}
            for i, p in enumerate(self.params)]
        if self.opt_state is not None:
            self.opt_state = [
                {k: jax.tree.map(
                    lambda s: jax.device_put(jnp.asarray(s), shards[i][k])
                    if getattr(s, "shape", None) == self.params[i][k].shape
                    else s, st)
                 for k, st in p.items()}
                for i, p in enumerate(self.opt_state)]

    def _resolve_channels_last(self) -> bool:
        """channels_last = -1 (auto) turns the NHWC conv-stack layout on
        exactly where it pays: TPU backends (the MXU/VPU want C minor;
        measured +24% on inception, doc/performance.md). CPU/GPU
        keep reference NCHW. 0/1 force either way (the ablation knob)."""
        if self.channels_last >= 0:
            return bool(self.channels_last)
        return jax.default_backend() == "tpu"

    def _init_net_structure(self) -> None:
        # pin the requested platform FIRST: net construction below probes
        # jax (channels_last auto-resolution), which would initialize the
        # default backend and take the platform choice away from dev = cpu
        parallel.ensure_platform(parallel.parse_device_spec(self.dev_spec)[0])
        self.net_cfg.configure(self.cfg_pairs)
        self.net = NeuralNet(self.net_cfg, self.batch_size,
                             compute_dtype=self.compute_dtype,
                             input_scale=self.input_scale,
                             input_mean=self.input_mean,
                             fuse_siblings=bool(self.fuse_sibling_convs),
                             channels_last=self._resolve_channels_last())
        self._setup_mesh()
        # resolve eval nodes (metric[label,node] -> node id; default last)
        self.eval_nodes: List[int] = []
        if not self.eval_node_names:
            # always keep the last node for Predict
            pass
        for nm in self.eval_node_names:
            if nm is None:
                self.eval_nodes.append(self.net_cfg.param.num_nodes - 1)
            else:
                check(nm in self.net_cfg.node_name_map,
                      "metric: unknown node name %s" % nm)
                self.eval_nodes.append(self.net_cfg.node_name_map[nm])
        self._build_updaters()
        self._clear_jit_cache()

    def _build_updaters(self) -> None:
        """One Updater per (connection, weight tag), configured from global +
        per-layer cfg (reference InitUpdaters, neural_net-inl.hpp:177-203);
        none for a layer's fixed leaves (``Layer.fixed_keys``)."""
        self.updaters: List[Dict[str, object]] = []
        for i, lay in enumerate(self.net.layers):
            ups: Dict[str, object] = {}
            if not self.net.is_shared[i]:
                for tag, key in lay.visit_order():
                    if key in lay.fixed_keys():
                        continue
                    up = create_updater(self.net_cfg.updater_type, tag)
                    for k, v in self.net_cfg.defcfg:
                        up.set_param(k, v)
                    for k, v in self.net_cfg.layercfg[i]:
                        up.set_param(k, v)
                    ups[key] = up
            self.updaters.append(ups)

    def init_model(self) -> None:
        # phases, not spans: set-up happens once, and `setup_s` wants it
        # read in runs where nothing enabled telemetry
        with telemetry.phase("init.model"):
            with telemetry.phase("init.structure"):
                self._init_net_structure()
                if any(lay.type_name == "lrn" for lay in self.net.layers):
                    # its kernel is needed when the step is traced: fetch
                    # the library while set-up waits on the device
                    ops.preload_pallas()
            with telemetry.phase("init.params"):
                self.params = self.net.init_params(self.seed)
            with telemetry.phase("init.opt"):
                self._init_opt()
            with telemetry.phase("init.pack"):
                self._pp_pack()

    # ------------------------------------------------------------------
    # pipeline-parallel parameter packing: each pipe rank OWNS its stage's
    # parameter (and optimizer-state) bytes — the per-device model
    # ownership the reference gets from one NeuralNet per worker thread
    # (src/nnet/neural_net-inl.hpp:304-628). Stage params flatten into a
    # (k, F_p) array sharded P("pipe"); stage bodies slice their own row
    # locally (zero parameter communication).
    _PACKED = "__pp_packed__"

    def _pp_plan(self):
        return self.net.pipeline_plan(self.params,
                                      self.mesh.shape["pipe"])

    def _pp_zero1(self) -> bool:
        """fsdp composed with pipeline_parallel: ZeRO-1 inside each stage —
        packed optimizer state sharded (pipe, data), 1/(k*dp) bytes per
        device. (Stage packing already gives 1/k params per rank; sharding
        the PARAMS further over data would force an all-gather of the stage
        weights inside every microbatch tick of the scan, so opt-state
        sharding is the profitable half of fsdp here.)"""
        return (bool(self.fsdp) and self.pipeline_parallel > 1
                and self.mesh is not None
                and "data" in self.mesh.axis_names
                and self.mesh.shape["data"] > 1)

    def _pp_pack(self) -> None:
        """Move prefix-stage params + opt state into the packed arrays.
        No-op unless pipeline_parallel > 1 on a live mesh."""
        if self.pipeline_parallel <= 1 or self.mesh is None \
                or "pipe" not in self.mesh.axis_names:
            return
        stages, first_loss = self._pp_plan()
        stage_of = {}
        for s, (lo, hi) in enumerate(stages):
            for i in range(lo, hi):
                stage_of[i] = s
        for i in range(first_loss):
            if self.net.is_shared[i]:
                pidx = self.net_cfg.layers[i].primary_layer_index
                check(stage_of.get(pidx) == stage_of.get(i),
                      "pipeline_parallel: shared layer %d and its primary "
                      "%d must fall in the same pipeline stage" % (i, pidx))
        for i in range(first_loss, len(self.net.layers)):
            if self.net.is_shared[i]:
                pidx = self.net_cfg.layers[i].primary_layer_index
                check(pidx >= first_loss,
                      "pipeline_parallel: loss-tail shared layer %d cannot "
                      "reference prefix primary %d" % (i, pidx))
        entries, sizes = [], []
        for (lo, hi) in stages:
            off, es = 0, []
            for i in range(lo, hi):
                if self.net.is_shared[i]:
                    continue
                for key in sorted(self.params[i]):
                    shape = tuple(np.shape(self.params[i][key]))
                    es.append((i, key, off, shape))
                    off += int(np.prod(shape)) if shape else 1
            entries.append(es)
            sizes.append(off)
        F_p = max(1, max(sizes))
        if self._pp_zero1():
            # ZeRO-1 shards the flat dim over data: pad to a multiple of dp
            # (pad elements are zeros with gid -1 — never updated)
            dp = self.mesh.shape["data"]
            F_p = -(-F_p // dp) * dp
        sh = NamedSharding(self.mesh, P("pipe", None))

        def build(getv, sharding=sh):
            rows = []
            for es in entries:
                vec = np.zeros(F_p, np.float32)
                for (i, key, off, shape) in es:
                    v = getv(i, key)
                    if v is None:      # no state for this tensor: zeros
                        continue
                    a = np.asarray(v, np.float32).ravel()
                    vec[off: off + a.size] = a
                rows.append(vec)
            return jax.device_put(np.stack(rows), sharding)

        packed = build(lambda i, k_: parallel.fetch_global(
            self.params[i][k_]))
        # frozen params (fixconn) carry no optimizer state: pack zeros for
        # them and remember which (layer, key) pairs really have state
        self._pp_opt_keys = {(i, key) for es in entries
                             for (i, key, _, _) in es
                             if key in self.opt_state[i]}
        sub_keys = sorted({sk for es in entries for (i, key, _, _) in es
                           for sk in self.opt_state[i].get(key, {})})
        opt_sh = sh
        if self._pp_zero1():
            # fsdp x pp = ZeRO-1 inside each stage: the packed optimizer
            # state additionally shards its flat dim over the data axis —
            # each (pipe, data) device owns 1/(k*dp) of the opt bytes and
            # computes only its slice of the elementwise update; GSPMD
            # all-gathers the updated params (whose sharding stays
            # P("pipe", None)). The vectorized group update below is what
            # makes this clean: it is elementwise over (k, F_p), so the
            # constraint partitions it with zero resharding.
            opt_sh = NamedSharding(self.mesh, P("pipe", "data"))
        packed_opt = {sk: build(
            lambda i, k_: parallel.fetch_global(self.opt_state[i][k_][sk])
            if k_ in self.opt_state[i] else None, opt_sh)
            for sk in sub_keys}
        # vectorized update plan: group packed tensors by updater
        # hyper-parameter signature; the step then runs ONE elementwise
        # update per group over the whole (k, F_p) array and selects by a
        # static group-id map — O(#groups) ops instead of O(#tensors)
        # dynamic-update-slices (a 100-layer trunk compiles the same as a
        # 5-layer one). Entries with no updater (fixconn frozen weights,
        # BN running stats) keep gid -1 and are never selected.
        groups: List[object] = []
        gid_of: Dict[tuple, int] = {}
        gid_map = np.full((len(entries), F_p), -1, np.int8)
        for s, es in enumerate(entries):
            for (i, key, off, shape) in es:
                up = self.updaters[i].get(key)
                if up is None:
                    continue
                check(getattr(up, "elementwise", False),
                      "pipeline_parallel: updater '%s' for layer %d key %s "
                      "declares elementwise=False (per-tensor reductions); "
                      "the packed stage update would be wrong for it" %
                      (up.kind, i, key))
                sig = _updater_signature(up)
                if sig not in gid_of:
                    check(len(groups) < 127,
                          "pipeline_parallel: more than 127 distinct "
                          "updater configurations in packed stages")
                    gid_of[sig] = len(groups)
                    groups.append(up)
                size = int(np.prod(shape)) if shape else 1
                gid_map[s, off:off + size] = gid_of[sig]
        self._pp_groups = groups
        # device-resident and pipe-sharded: closing over a committed Array
        # makes it a hoisted jit const that KEEPS its sharding — an inline
        # np constant would be replicated per device (k*F_p bytes, more
        # than the 4*F_p param shard it selects over)
        self._pp_gid = jax.device_put(gid_map, sh)
        for es in entries:
            for (i, key, _, _) in es:
                del self.params[i][key]
                self.opt_state[i].pop(key, None)
        self.params.append({self._PACKED: packed})
        self.opt_state.append({self._PACKED: packed_opt})
        self._pp_entries = entries
        self._pp_entry_index = {(i, key): (s, off, shape)
                                for s, es in enumerate(entries)
                                for (i, key, off, shape) in es}
        self._pp_stages = stages
        self.grad_accum = None   # tree structure changed
        self._clear_jit_cache()

    def _pp_unpack(self) -> None:
        """Restore canonical per-layer params/opt state (host-side)."""
        if self._pp_entries is None:
            return
        self.params = self.canonical_params()
        self.opt_state = self._canonical_opt_state()
        self._pp_entries = None
        self._pp_entry_index = {}
        self._pp_stages = None
        self._pp_groups = []
        self._pp_gid = None
        self.grad_accum = None   # tree structure changed
        self._clear_jit_cache()

    def canonical_params(self):
        """Per-layer params list regardless of the PP packing (the form
        checkpoints, get_weight, and the C ABI see)."""
        if self._pp_entries is None:
            return self.params
        packed = parallel.fetch_global(self.params[-1][self._PACKED])
        out = [dict(p) for p in self.params[:-1]]
        for s, es in enumerate(self._pp_entries):
            for (i, key, off, shape) in es:
                size = int(np.prod(shape)) if shape else 1
                out[i][key] = jnp.asarray(
                    packed[s, off: off + size].reshape(shape))
        return out

    def _canonical_opt_state(self):
        if self._pp_entries is None:
            return self.opt_state
        popt = {sk: parallel.fetch_global(v)
                for sk, v in self.opt_state[-1][self._PACKED].items()}
        out = [dict(p) for p in self.opt_state[:-1]]
        for s, es in enumerate(self._pp_entries):
            for (i, key, off, shape) in es:
                if (i, key) not in self._pp_opt_keys:
                    continue
                size = int(np.prod(shape)) if shape else 1
                out[i][key] = {
                    sk: jnp.asarray(v[s, off: off + size].reshape(shape))
                    for sk, v in popt.items()}
        return out

    def _init_opt(self) -> None:
        self.opt_state = []
        for i, ups in enumerate(self.updaters):
            st = {}
            for key, up in ups.items():
                st[key] = up.init_state(np.asarray(self.params[i][key]))
            self.opt_state.append(st)
        self.grad_accum = None
        self._metric_accum = None
        self.sample_counter = 0
        self._place_params()

    # ------------------------------------------------------------------
    # checkpointing (reference SaveModel/LoadModel, nnet_impl-inl.hpp:81-100)
    _OPT_MAGIC = b"CXNOPT01"

    def save_model(self, w: serializer.Writer) -> None:
        """Serialize net structure + params + optimizer state.

        Multi-process: collective — every process must call it (it gathers
        mesh-sharded arrays via parallel.fetch_global; a rank-guarded call
        deadlocks). Write the file on one rank, but CALL on all.

        Checkpoints are always CANONICAL (per-layer tensors): the PP
        stage-packing is a runtime placement, so a pipeline_parallel=4 run
        resumes fine as single-device or any other parallelism config."""
        self._last_update_t0 = None
        self.net_cfg.save_net(w)
        w.write_raw(np.int64(self.epoch_counter).tobytes())
        blob = self.net.save_model_blob(self.canonical_params())
        w.write_uint64(len(blob))
        w.write_raw(blob)
        # versioned optimizer-state section (beyond the reference, which
        # drops momentum on resume, nnet_impl-inl.hpp:82-87). Appended after
        # the model blob so readers of the original format still load the
        # file; load_model restores it when the magic is present.
        ow = serializer.Writer()
        opt_state = self._canonical_opt_state()
        ow.write_uint64(len(opt_state))
        for st in opt_state:
            ow.write_uint64(len(st))
            for key in sorted(st):
                ow.write_string(key)
                sub = st[key]
                ow.write_uint64(len(sub))
                for sk in sorted(sub):
                    ow.write_string(sk)
                    ow.write_tensor(np.asarray(
                        parallel.fetch_global(sub[sk]), np.float32))
        blob = ow.getvalue()
        w.write_raw(self._OPT_MAGIC)
        w.write_uint64(len(blob))
        w.write_raw(blob)

    def _load_opt_state(self, r: serializer.Reader) -> None:
        """Restore the optional optimizer-state section; missing section
        (pre-optimizer-checkpoint file) leaves the fresh init states."""
        magic = r.f.read(len(self._OPT_MAGIC))
        if magic != self._OPT_MAGIC:
            return
        r.read_uint64()  # section length (unused; we parse the content)
        n = r.read_uint64()
        check(n == len(self.opt_state),
              "optimizer state layer count %d != %d" % (n, len(self.opt_state)))
        for st in self.opt_state:
            nk = r.read_uint64()
            for _ in range(nk):
                key = r.read_string()
                check(key in st, "optimizer state has unknown weight "
                      "tag %r (updater type changed?)" % key)
                ns = r.read_uint64()
                for _ in range(ns):
                    sk = r.read_string()
                    val = r.read_tensor()
                    check(sk in st[key] and
                          np.shape(st[key][sk]) == val.shape,
                          "optimizer state %r/%r shape mismatch" % (key, sk))
                    st[key][sk] = jnp.asarray(val)
        self._place_params()   # re-apply TP shardings to restored state

    # training-state section (preemption-tolerant full-state resume): the
    # host-side step state a weights+optimizer checkpoint does NOT cover —
    # the rng stream position, the update_period phase, in-flight grad
    # accumulation, and the on-device train-metric sums. With it a
    # preempted run resumes bit-for-bit MID-schedule; without it (old
    # files) resume still works, from round-start weights. Written by
    # save_training_state AFTER save_model's sections, guarded by
    # checkpoint.STATE_MAGIC so old readers (and load_model) ignore it.
    def save_training_state(self, w: serializer.Writer,
                            extra: Optional[dict] = None) -> None:
        """Append the versioned training-state section. ``extra`` carries
        the driver's cursor (round counter, iterator batch position).
        Multi-process: collective (grad accum may be mesh-sharded) —
        call on every process, write the stream on one."""
        from ..utils import checkpoint as ckpt
        sw = serializer.Writer()
        meta = {"rng_counter": int(self._rng_counter),
                "sample_counter": int(self.sample_counter)}
        if extra:
            meta.update(extra)
        ga = self.grad_accum
        ma = self._metric_accum
        meta["has_grad_accum"] = ga is not None
        meta["has_metric_accum"] = ma is not None
        sw.write_string(json.dumps(meta, sort_keys=True))
        if ma is not None:
            sw.write_tensor(np.asarray(jax.device_get(ma), np.float32))
        if ga is not None:
            sw.write_uint64(len(ga))
            for d in ga:
                sw.write_uint64(len(d))
                for key in sorted(d):
                    sw.write_string(key)
                    sw.write_tensor(np.asarray(
                        parallel.fetch_global(d[key]), np.float32))
        blob = sw.getvalue()
        w.write_raw(ckpt.STATE_MAGIC)
        w.write_uint64(len(blob))
        w.write_raw(blob)

    def load_training_state(self, r: serializer.Reader) -> Optional[dict]:
        """Parse the optional training-state section into a dict (missing
        section — old checkpoint — returns None). Application is separate
        (restore_training_state): the driver's continue-path eval runs
        between load and the train loop and must not consume the restored
        rng/metric state."""
        from ..utils import checkpoint as ckpt
        magic = r.f.read(len(ckpt.STATE_MAGIC))
        if magic != ckpt.STATE_MAGIC:
            return None
        nbytes = r.read_uint64()
        sr = serializer.Reader(r.read_raw(nbytes))
        meta = json.loads(sr.read_string())
        state = dict(meta)
        if meta.get("has_metric_accum"):
            state["metric_accum"] = sr.read_tensor()
        if meta.get("has_grad_accum"):
            ga = []
            for _ in range(sr.read_uint64()):
                d = {}
                for _ in range(sr.read_uint64()):
                    key = sr.read_string()
                    d[key] = sr.read_tensor()
                ga.append(d)
            state["grad_accum"] = ga
        return state

    def restore_training_state(self, state: Optional[dict]) -> None:
        """Apply a loaded training-state dict. Counters always apply;
        grad/metric accumulators apply only when their tree matches the
        current net+parallelism config (a resume under a DIFFERENT mesh
        layout drops them with a warning — correct at update boundaries,
        just not bit-identical mid-accumulation)."""
        if not state:
            return
        if "rng_counter" in state:
            self._rng_counter = int(state["rng_counter"])
        if "sample_counter" in state:
            self.sample_counter = int(state["sample_counter"])
        ma = state.get("metric_accum")
        if ma is not None:
            if np.shape(ma) == (len(self.train_metric), 2):
                self._metric_accum = jnp.asarray(np.asarray(ma, np.float32))
            elif not self.silent:
                print("WARNING: checkpoint train-metric state does not "
                      "match the current metric set; dropped")
        ga = state.get("grad_accum")
        if ga is not None:
            ok = len(ga) == len(self.params) and all(
                set(d) == set(p)
                and all(tuple(np.shape(d[k])) == tuple(np.shape(p[k]))
                        for k in d)
                for d, p in zip(ga, self.params))
            if ok:
                self.grad_accum = [
                    {k: jnp.asarray(
                        np.asarray(v, np.float32),
                        dtype=getattr(self.params[i][k], "dtype",
                                      np.float32))
                     for k, v in d.items()}
                    for i, d in enumerate(ga)]
            elif not self.silent:
                print("WARNING: checkpoint gradient-accumulation state "
                      "does not match the current net/parallelism config; "
                      "dropped (resume is exact only at update "
                      "boundaries)")

    def load_model(self, r: serializer.Reader) -> None:
        self.net_cfg.load_net(r)
        self.epoch_counter = int(np.frombuffer(r.read_raw(8), np.int64)[0])
        # rebuild with training cfg applied on top of the loaded structure;
        # shape inference must wait until the model blob restores each
        # layer's LayerParam (nhidden etc.) — the reference likewise loads
        # params before InitConnection (neural_net-inl.hpp LoadModel)
        parallel.ensure_platform(parallel.parse_device_spec(self.dev_spec)[0])
        self.net_cfg.configure(self.cfg_pairs)
        self.net = NeuralNet(self.net_cfg, self.batch_size,
                             infer_shapes=False,
                             compute_dtype=self.compute_dtype,
                             input_scale=self.input_scale,
                             input_mean=self.input_mean,
                             fuse_siblings=bool(self.fuse_sibling_convs),
                             channels_last=self._resolve_channels_last())
        self._setup_mesh()
        self.eval_nodes = [self.net_cfg.param.num_nodes - 1 if nm is None
                           else self.net_cfg.node_name_map[nm]
                           for nm in self.eval_node_names]
        self._clear_jit_cache()
        nbytes = r.read_uint64()
        self.params = self.net.load_model_blob(r.read_raw(nbytes))
        self.net._infer_shapes()
        # updaters after the blob: layers whose weight set is data-dependent
        # (extern ops) only know their keys once params are restored
        self._build_updaters()
        self._init_opt()
        self._load_opt_state(r)
        self._pp_pack()

    def copy_model_from(self, r: serializer.Reader) -> None:
        """Finetune: copy weights of name-matched layers from another model
        (reference CopyModelFrom, nnet_impl-inl.hpp:101-134)."""
        self.init_model()
        self._pp_unpack()   # copy into canonical form; repacked below
        old_cfg = NetConfig()
        old_cfg.load_net(r)
        np.frombuffer(r.read_raw(8), np.int64)  # old epoch_counter, discarded
        self.epoch_counter = 0
        nbytes = r.read_uint64()
        old_net = NeuralNet(old_cfg, 1, infer_shapes=False)
        old_params = old_net.load_model_blob(r.read_raw(nbytes))
        for i, old_info in enumerate(old_cfg.layers):
            if not old_info.name:
                continue
            for j, new_info in enumerate(self.net_cfg.layers):
                if new_info.name == old_info.name:
                    if self.silent == 0:
                        print("Copying layer %s" % old_info.name)
                    # merge, don't replace: init_model may have created
                    # state keys (BN running stats) the old model lacks
                    self.params[j].update(
                        {k: jnp.asarray(v)
                         for k, v in old_params[i].items()})
        self._decode_params = None   # per-dict update above is in place
        self._init_opt()
        self._pp_pack()

    # ------------------------------------------------------------------
    def start_round(self, round_: int) -> None:
        self.round = round_
        self._last_update_t0 = None
        if self.test_on_server:
            self.check_replica_consistency()

    def check_replica_consistency(self, atol: float = 0.0) -> None:
        """Distributed-consistency check (the reference's `test_on_server`,
        src/updater/async_updater-inl.hpp:148-153: workers pull the server's
        weights each round and CheckWeight them against local replicas).
        TPU equivalent: parameters replicated across the mesh must hold
        bitwise-identical shards on every device; sharded axes are skipped
        (each device owns a distinct slice)."""
        if self.mesh is None:
            return
        for i, p in enumerate(self.params):
            for key, v in p.items():
                arr = jnp.asarray(v)
                shards = getattr(arr, "addressable_shards", None)
                if not shards or len(shards) < 2:
                    continue
                # only compare shards covering the same index range
                by_index = {}
                for s in shards:
                    by_index.setdefault(str(s.index), []).append(s)
                for idx, group in by_index.items():
                    if len(group) < 2:
                        continue
                    ref = np.asarray(group[0].data)
                    for s in group[1:]:
                        diff = np.max(np.abs(np.asarray(s.data) - ref)) \
                            if ref.size else 0.0
                        check(diff <= atol,
                              "TestSync: layer %d %s replicas diverged on "
                              "devices %s vs %s (max |diff| = %g)"
                              % (i, key, group[0].device, s.device,
                                 float(diff)))

    # ------------------------------------------------------------------
    # the jitted steps
    def _loss_fn(self, params, data, label, rng, epoch, with_stats=False):
        labels = self.net.label_info_from(label)
        if self.pipeline_parallel > 1:
            values, loss = self.net.forward_pipelined(
                params, data, labels=labels, train=True, rng=rng,
                epoch=epoch, mesh=self.mesh,
                n_micro=self.pipeline_micro or None,
                packed_entries=self._pp_entries,
                stages=getattr(self, "_pp_stages", None))
        else:
            values, loss = self.net.forward(params, data, labels=labels,
                                            train=True, rng=rng, epoch=epoch,
                                            mesh=self.mesh)
        stats = None
        if with_stats:
            for n in self.eval_nodes:
                check(values[n] is not None,
                      "metric node %d lives inside the pipelined prefix; "
                      "with pipeline_parallel only the loss-tail nodes are "
                      "observable" % n)
            # train metrics reduce to (sum, count) on device — no per-step
            # host fetch (the eval_train=1 sync the reference hid in its
            # worker threads)
            eval_outs = [
                values[n].reshape(values[n].shape[0], -1).astype(jnp.float32)
                for n in self.eval_nodes]
            stats = self.train_metric.device_stats(eval_outs, labels)
        state_ups = getattr(self.net, "_last_state_updates", {})
        layer_stats = dict(getattr(self.net, "_last_layer_stats", {}))
        return loss, (stats, state_ups, layer_stats)

    def _apply_updates(self, params, grads, opt_state, epoch):
        new_params = [dict(p) for p in params]
        new_opt = [dict(s) for s in opt_state]
        # scopes ``update/<layer>``: the profiler's trace puts each device
        # operation of the optimizer to its layer (utils/devtrace.py)
        for i, ups in enumerate(self.updaters):
            for key, up in ups.items():
                if key not in params[i]:
                    continue   # lives in the PP packed array (below)
                with jax.named_scope("update/" + self.net.layer_scope(i)):
                    w, st = up.apply(params[i][key], grads[i][key],
                                     opt_state[i][key], epoch)
                new_params[i][key] = w
                new_opt[i][key] = st
        if self._pp_entries is not None:
            # stage-packed params: ONE vectorized elementwise update per
            # updater-config group over the whole (k, F_p) array, selected
            # by the static group-id map built at pack time — compile cost
            # O(#groups), not O(#tensors), so a 100-layer trunk compiles
            # like a 5-layer one. gid -1 (fixconn frozen weights, BN
            # running stats, row padding) is never selected: those elements
            # keep their values even where their grads are nonzero
            # (fixconn weights participate in the forward), matching the
            # reference's frozen-weight skip.
            packed = params[-1][self._PACKED]
            gpk = grads[-1][self._PACKED]
            spk = opt_state[-1][self._PACKED]
            gid = self._pp_gid   # pipe-sharded device array (see _pp_pack)
            new_pk = packed
            new_spk = dict(spk)
            with jax.named_scope("update/packed"):
                for g_id, up in enumerate(self._pp_groups):
                    w2, st2 = up.apply(packed, gpk, spk, epoch)
                    sel = gid == np.int8(g_id)
                    new_pk = jnp.where(sel, w2, new_pk)
                    for sk, v2 in st2.items():
                        new_spk[sk] = jnp.where(sel, v2, new_spk[sk])
            sh = NamedSharding(self.mesh, P("pipe", None))
            opt_sh = NamedSharding(self.mesh, P("pipe", "data")) \
                if self._pp_zero1() else sh
            new_params[-1][self._PACKED] = \
                jax.lax.with_sharding_constraint(new_pk, sh)
            new_opt[-1][self._PACKED] = {
                sk: jax.lax.with_sharding_constraint(v, opt_sh)
                for sk, v in new_spk.items()}
        fsdp_sh = getattr(self, "_fsdp_shardings", None)
        if fsdp_sh is not None:
            # ZeRO-3: the updated weights and their opt state keep the
            # fsdp placement (grads arrive reduce-scattered to it; the
            # elementwise update never leaves the shard). Tensors fsdp
            # leaves replicated (1-D biases/norm scales, non-divisible
            # weights) still get their opt state ZeRO-sharded, so the
            # mode strictly subsumes update_on_server
            from ..parallel.sharding import zero_sharding
            for i, sh_map in enumerate(fsdp_sh):
                for key, sh in sh_map.items():
                    if key in new_params[i]:
                        new_params[i][key] = \
                            jax.lax.with_sharding_constraint(
                                new_params[i][key], sh)
                    if key not in new_opt[i]:
                        continue
                    if any(a is not None for a in sh.spec):
                        new_opt[i][key] = jax.tree.map(
                            lambda x, sh=sh:
                            jax.lax.with_sharding_constraint(x, sh)
                            if getattr(x, "ndim", 0) == len(sh.spec) else x,
                            new_opt[i][key])
                    else:
                        new_opt[i][key] = jax.tree.map(
                            lambda x: jax.lax.with_sharding_constraint(
                                x, zero_sharding(self.mesh, x)),
                            new_opt[i][key])
        elif self.mesh is not None and self.update_on_server:
            from ..parallel.sharding import shard_opt_state_with_specs
            base = getattr(self, "_tp_shardings", None)
            if self._pp_entries is not None:
                # keep the ZeRO-1 (pipe, data) placement when fsdp is also
                # on — update_on_server must not undo the stronger split
                sh = NamedSharding(
                    self.mesh,
                    P("pipe", "data") if self._pp_zero1() else
                    P("pipe", None))
                base = list(base) if base is not None else \
                    [{} for _ in range(len(new_opt) - 1)]
                base = base + [{self._PACKED: sh}]
            new_opt = shard_opt_state_with_specs(self.mesh, new_opt, base)
        return new_params, new_opt

    def _make_train_step(self, do_update: bool, accumulate: bool,
                         with_accum: bool, with_stats: bool,
                         with_health: bool = False):
        # with_health: the step returns [loss, grad_norm_sq,
        # nan_grad_elems, ok] as a 4-float device vector — computed in
        # the compiled program over the FRESH (pre-accumulation) grads,
        # so detection pins the offending batch, not the running sum.
        # guard (nonfinite_action="skip"): additionally suppress the
        # whole state transition on device when the step is non-finite.
        guard = with_health and self.nonfinite_action == "skip"

        def step(params, opt_state, grad_accum, metric_accum,
                 data, label, epoch, rng):
            (loss, (stats, state_ups, layer_stats)), grads = \
                jax.value_and_grad(
                self._loss_fn, has_aux=True)(params, data, label, rng,
                                             epoch, with_stats)
            health = None
            ok = None
            # the scopes below (health, accum, clip, guard; update/<layer>
            # in _apply_updates) name what is no layer's in the trace
            if with_health:
                with jax.named_scope("health"):
                    leaves = jax.tree_util.tree_leaves(grads)
                    gn_sq = sum(jnp.vdot(g, g) for g in leaves) \
                        .astype(jnp.float32)
                    # the elements updater._clip_nan would silently zero
                    # (telemetry counter health/nan_grads_zeroed, read by
                    # the host monitor)
                    nan_elems = sum(jnp.sum(jnp.isnan(g)) for g in leaves)
                    lossf = loss.astype(jnp.float32)
                    ok = jnp.isfinite(lossf) & jnp.isfinite(gn_sq)
                    health = jnp.stack([lossf, gn_sq,
                                        nan_elems.astype(jnp.float32),
                                        ok.astype(jnp.float32)])
                    # behind the four: the layers' own readings of this
                    # step (moe: pairs held, fullest expert), named by
                    # health_gauge_names in the same order
                    names, limits = [], {}
                    for i in sorted(layer_stats):
                        lay = self.net.layers[i]
                        scope = self.net.layer_scope(i)
                        names += ["%s/%s" % (n, scope)
                                  for n in lay.stat_names]
                        for n, (over, bound) in lay.stat_limits.items():
                            limits["%s/%s" % (n, scope)] = (
                                "%s/%s" % (over, scope), bound)
                    self.health_gauge_names = names
                    self.health_gauge_limits = limits
                    health = jnp.concatenate(
                        [health] + [layer_stats[i]
                                    for i in sorted(layer_stats)])
            if guard:
                prev = (params, opt_state, grad_accum, metric_accum)
            if accumulate:
                with jax.named_scope("accum"):
                    grads = jax.tree.map(jnp.add, grad_accum, grads)
            if do_update:
                if self.clip_global_norm > 0:
                    # whole-model norm clip (beyond the reference's
                    # per-tensor clip_gradient): one scale for every
                    # tensor preserves the gradient direction
                    with jax.named_scope("clip"):
                        leaves = jax.tree_util.tree_leaves(grads)
                        gn = jnp.sqrt(sum(jnp.vdot(g, g) for g in leaves))
                        scale = jnp.minimum(
                            1.0,
                            self.clip_global_norm / jnp.maximum(gn, 1e-12))
                        grads = jax.tree.map(lambda g: g * scale, grads)
                params, opt_state = self._apply_updates(
                    params, grads, opt_state, epoch)
                if with_accum:
                    with jax.named_scope("accum"):
                        grads = jax.tree.map(jnp.zeros_like, grads)
            if state_ups:
                # non-gradient updates (BN running stats): direct assignment
                params = [dict(p) for p in params]
                for (i, key), val in state_ups.items():
                    if key in params[i]:
                        params[i][key] = val
                    else:
                        # the tensor lives in the PP packed row: write the
                        # slot in place (static offsets; the .at update
                        # stays on the rank owning that stage's shard)
                        s, off, shape = self._pp_entry_index[(i, key)]
                        size = int(np.prod(shape)) if shape else 1
                        pk = params[-1][self._PACKED]
                        params[-1][self._PACKED] = pk.at[
                            s, off: off + size].set(
                                jnp.ravel(val).astype(pk.dtype))
            if with_stats:
                metric_accum = metric_accum + stats
            if guard:
                # non-finite step: keep EVERY piece of the old state
                # (params, optimizer, grad accumulation, metric sums) —
                # the bad batch contributes nothing, training continues.
                # Referencing both the donated inputs and the updated
                # values is fine: the program is functional; donation is
                # a buffer-aliasing hint, not a consume.
                def sel(n, o):
                    return jnp.where(ok, n, o)
                with jax.named_scope("guard"):
                    params = jax.tree.map(sel, params, prev[0])
                    opt_state = jax.tree.map(sel, opt_state, prev[1])
                    if with_accum:
                        grads = jax.tree.map(sel, grads, prev[2])
                    if with_stats:
                        metric_accum = sel(metric_accum, prev[3])
            # when update_period == 1 no grad-accumulator state is carried
            # at all (no params-sized zero tree in HBM, no donate/add)
            return (params, opt_state,
                    grads if with_accum else None, metric_accum, health)

        jitted = jax.jit(step, donate_argnums=(0, 1, 2, 3))
        return jitted

    def _clear_jit_cache(self) -> None:
        """Drop every cached program (packing/layout/model change). The
        telemetry counter is what the report reads as rebuild pressure;
        _jit_seen_keys survives so the recompile detector attributes the
        recompiles to ``rebuild_after_clear``, not new signatures."""
        if self._jit_cache:
            telemetry.count("jit.cache_clear")
        self._jit_cache.clear()
        self._last_update_t0 = None

    def _watched_jit(self, key, name: str, build):
        """Build-or-fetch a jitted program in ``_jit_cache``, wrapped in
        the telemetry recompile detector. The detector records one compile
        event per genuinely new (signature, shape) key with its cause:
        ``new_signature`` (first build of this program key),
        ``rebuild_after_clear`` (the cache was cleared — packing change /
        model reload — and a previously seen program recompiles), and
        ``shape_change`` (same program, new input shapes/shardings)."""
        if key not in self._jit_cache:
            cause = ("rebuild_after_clear" if key in self._jit_seen_keys
                     else "new_signature")
            self._jit_seen_keys.add(key)
            # the cache key rides the compile event and the perf
            # ledger's ProgramCard (utils/perf.py) as the program's
            # stable identity
            self._jit_cache[key] = telemetry.jit_watch(build(), name,
                                                       cause=cause,
                                                       key=key)
        return self._jit_cache[key]

    def _get_step(self, do_update: bool, accumulate: bool,
                  with_accum: bool, with_stats: bool,
                  with_health: bool = False):
        k = ("train", do_update, accumulate, with_accum, with_stats,
             with_health)

        def build():
            # the call that builds a step lasts seconds: no period's start
            self._last_update_t0 = None
            return self._make_train_step(do_update, accumulate, with_accum,
                                         with_stats, with_health)
        return self._watched_jit(k, "jit.train_step", build)

    def _shard_batch(self, arr):
        if not isinstance(arr, jax.Array):
            # bytes that cross the host link: a resident batch moves none
            telemetry.count("io.h2d_bytes",
                            int(getattr(arr, "nbytes", 0) or 0))
        if self.mesh is None:
            return jnp.asarray(arr)
        sh = parallel.batch_sharding(self.mesh)
        nproc = jax.process_count()
        if nproc > 1:
            a = np.asarray(arr)
            if a.shape[0] * nproc == self.batch_size:
                # per-host LOCAL shard (dist_num_worker-sharded corpora:
                # each host decodes only its slice of the global batch);
                # assemble the global array from process-local rows
                return jax.make_array_from_process_local_data(sh, a)
            # else: every host carries the identical global batch and
            # device_put places the local rows (valid only when hosts
            # read the same unsharded data stream)
        # straight from the host: jnp.asarray first would land the whole
        # batch on the first device and slice it there for the others
        return jax.device_put(
            arr if isinstance(arr, jax.Array) else np.asarray(arr), sh)

    def _next_rng(self):
        self._rng_counter += 1
        return jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                  self._rng_counter)

    def lower_update(self, batch):
        """Lower (trace without executing) the standard one-batch train
        step — tools/memory_report.py compiles the result and reads XLA's
        per-device HBM memory_analysis()."""
        step = self._get_step(True, False, False, False)
        data = self._shard_batch(batch.data)
        label = self._shard_batch(batch.label)
        # fixed key: only shape/dtype matter for lowering, and drawing from
        # _next_rng() here would shift the training RNG stream (breaking
        # inspect-then-train vs train bit-reproducibility)
        return step.lower(self.params, self.opt_state, None, None,
                          data, label, jnp.asarray(0, jnp.int32),
                          jax.random.PRNGKey(0))

    def update(self, batch) -> None:
        """One mini-batch (reference Update, nnet_impl-inl.hpp:141-185)."""
        # the whole call: under a profiler session the train.* spans land
        # on the host plane of the trace (telemetry.span), where each idle
        # gap of the device is put down to one of them. The four kept ones
        # (the call, and h2d + args + dispatch inside it; what is left is
        # the call's self time) also stand in telemetry.kept() in a run
        # that enabled nothing: the benchmark's host-side metrics
        # cxxlint: disable=timed-dispatch — host time by design, like
        # train.dispatch inside it: device time is read from the trace
        with telemetry.span("train.update", keep=True) as call:
            # back-to-back calls enter one step's period apart ON AVERAGE
            # (the runtime holds the host to a fixed number of steps in
            # flight, so entries come a few ms apart and then one a step):
            # the histogram's mean is the step time, no percentile of it is
            last, self._last_update_t0 = self._last_update_t0, call.t0
            if last is not None:
                telemetry.hist("train.period", call.t0 - last)
            need_update = (self.sample_counter + 1) % self.update_period == 0
            accumulate = self.sample_counter % self.update_period != 0
            with_accum = self.update_period > 1
            with_stats = self.eval_train != 0 and len(self.train_metric) > 0
            with_health = self.health_monitor != 0
            step = self._get_step(need_update, accumulate, with_accum,
                                  with_stats, with_health)
            with telemetry.span("train.h2d", keep=True):
                data = self._shard_batch(batch.data)
                label = self._shard_batch(batch.label)
            if with_accum and self.grad_accum is None:
                self.grad_accum = jax.tree.map(
                    lambda x: jnp.zeros_like(x),
                    [{k: v for k, v in p.items()} for p in self.params])
            if with_stats and self._metric_accum is None:
                self._metric_accum = jnp.zeros(
                    (len(self.train_metric), 2), jnp.float32)
            # train.step (the name health, the perf ledger, the report and
            # /metrics know) = train.args, every small dispatch that only
            # makes an argument, + train.dispatch, the jitted call alone
            # from its call to its return (plus any trace+compile, which
            # the jit watch separates out): execution is async, and a call
            # blocks for a whole step once the runtime's limit of steps in
            # flight is reached. The step's time is train.period's mean;
            # device time is read from the trace
            # cxxlint: disable=timed-dispatch — host time by design
            with telemetry.span("train.step"):
                with telemetry.span("train.args", keep=True):
                    epoch = jnp.asarray(self.epoch_counter, jnp.int32)
                    rng = self._next_rng()
                # cxxlint: disable=timed-dispatch — dispatch-only by design
                with telemetry.span("train.dispatch", keep=True):
                    (self.params, self.opt_state, self.grad_accum,
                     self._metric_accum, self.last_health) = \
                        step(self.params, self.opt_state, self.grad_accum,
                             self._metric_accum, data, label, epoch, rng)
            if telemetry.enabled():
                telemetry.count("train.images",
                                batch.batch_size - batch.num_batch_padd)
            self.sample_counter += 1
            if self.sample_counter >= self.update_period:
                self.sample_counter = 0
                self.epoch_counter += 1

    def scale_lr(self, factor: float) -> None:
        """Multiply every updater's base learning rate by ``factor`` —
        the health policy's rollback backoff (learn_task applies the
        ACCUMULATED scale after each checkpoint restore, since a restore
        rebuilds the updaters at their configured LR). base_lr is a
        trace-time constant, so the jit cache is cleared and the next
        step recompiles; backoffs are rare by construction."""
        if factor == 1.0:
            return
        for ups in self.updaters:
            for up in ups.values():
                up.param.base_lr *= factor
        telemetry.count("health.lr_backoff")
        self._clear_jit_cache()

    # ------------------------------------------------------------------
    def _eval_values(self, params, data, rng, node_ids):
        """Eval-mode forward (traced inside jit) returning the requested
        node values; shared by _forward_nodes and predict_device."""
        if self.pipeline_parallel > 1:
            values, _ = self.net.forward_pipelined(
                params, data, train=False, rng=rng, mesh=self.mesh,
                n_micro=self.pipeline_micro or None,
                packed_entries=self._pp_entries,
                stages=getattr(self, "_pp_stages", None))
            for n in node_ids:
                check(values[n] is not None,
                      "node %d lives inside the pipelined prefix; "
                      "with pipeline_parallel only loss-tail "
                      "nodes are observable" % n)
        else:
            values, _ = self.net.forward(params, data, train=False,
                                         rng=rng, mesh=self.mesh)
        return [values[n] for n in node_ids]

    def _swap_params(self, new_params) -> None:
        """Adopt the param list a donate-and-return eval program handed
        back. The returned arrays ALIAS the donated inputs (same device
        buffers, same values, same shardings) — numerically this is a
        no-op; it exists because a remote PJRT runtime may round-trip
        every large non-aliased input buffer on every execute call.
        Donating params and returning them keeps eval/predict/decode at
        train-step dispatch cost on such a runtime and costs nothing on
        a local one (whether a local TPU needs it: not measured). The
        decode cache is re-keyed to the new list identity so serving
        calls don't re-gather."""
        old = self.params
        self.params = new_params
        dp = getattr(self, "_decode_params", None)
        if dp is not None and dp[0] is old:
            self._decode_params = (new_params, dp[1])

    def _recover_donated_params(self) -> None:
        """Failure path for programs that donate the AUTHORITATIVE
        self.params (_forward_nodes / predict_device): if the jitted eval
        died at execute time (OOM, runtime error) AFTER consuming the
        donated buffers, the trainer would otherwise be left permanently
        on deleted arrays. Mirror the decode paths' recovery: rebuild from
        the decode cache's canonical copy when one is keyed to this exact
        params list, else mark params unusable with a clear error (the
        caller sees the original exception chained)."""
        params = self.params
        if params is None:
            return
        try:
            deleted = any(
                bool(getattr(v, "is_deleted", None) and v.is_deleted())
                for p in params for v in p.values())
        except Exception:
            deleted = True
        if not deleted:
            return      # trace-time failure: donation never happened
        telemetry.count("eval.params_donation_loss")
        dp = getattr(self, "_decode_params", None)
        if dp is not None and dp[0] is params and self._pp_entries is None:
            # host round trip through the decode copy, then re-place with
            # the training shardings
            self._decode_params = None
            self.params = [
                {k: jnp.asarray(np.asarray(parallel.fetch_global(v)))
                 for k, v in p.items()} for p in dp[1]]
            self._place_params()
            return
        self.params = None
        self._decode_params = None
        raise RuntimeError(
            "eval program failed after donating self.params; the device "
            "buffers are consumed and no canonical copy exists — reload "
            "the model (load_model) before continuing")

    def _forward_nodes(self, batch, node_ids: Tuple[int, ...]):
        """Jitted eval forward returning the requested nodes."""
        k = ("fwd", node_ids)

        def build():
            def fwd(params, data, rng):
                return self._eval_values(params, data, rng, node_ids), params
            return jax.jit(fwd, donate_argnums=(0,))

        prog = self._watched_jit(k, "jit.eval_fwd", build)
        data = self._shard_batch(batch.data)
        try:
            # cxxlint: disable=timed-dispatch — the host fetch (asarray /
            # allgather below) syncs right after; blocking inside the
            # span would serialize eval against the input pipeline
            with telemetry.span("eval.forward"):
                outs, new_params = prog(self.params, data, self._next_rng())
        except Exception:
            self._recover_donated_params()
            raise
        self._swap_params(new_params)
        if jax.process_count() > 1:
            # outputs are sharded over the GLOBAL mesh: a plain np.asarray
            # cannot see other processes' shards — gather to host so
            # evaluate/predict/extract keep single-host semantics (every
            # process holds the full global batch result)
            from jax.experimental import multihost_utils
            outs = [multihost_utils.process_allgather(o, tiled=True)
                    for o in outs]
        return outs

    def predict_device(self, batch):
        """On-device prediction: the last node's per-row argmax (or its
        scalar column) computed INSIDE the jitted program, returned as a
        (batch,) jax.Array with no host fetch. predict() wraps this with
        the fetch; serving loops call it directly so only (batch,)
        floats ever cross the wire instead of the (batch, nclass) logit
        matrix (reference Predict + TransformPred,
        nnet_impl-inl.hpp:186-299 — the transform runs on device here)."""
        node = self.net_cfg.param.num_nodes - 1
        k = ("pred", node)

        def build():
            def prog(params, data, rng):
                out = self._eval_values(params, data, rng, (node,))[0]
                out = out.reshape(out.shape[0], -1)
                if out.shape[1] != 1:
                    return jnp.argmax(out, axis=1).astype(jnp.float32), params
                return out[:, 0], params
            return jax.jit(prog, donate_argnums=(0,))

        fn = self._watched_jit(k, "jit.predict", build)
        data = self._shard_batch(batch.data)
        try:
            # cxxlint: disable=timed-dispatch — async return IS the
            # contract: serving loops consume the device array without a
            # host fetch (api.predict_device); its own latency series
            # exists precisely because blocking here would lie
            with telemetry.span("predict"):
                pred, new_params = fn(self.params, data, self._next_rng())
        except Exception:
            self._recover_donated_params()
            raise
        self._swap_params(new_params)
        return pred

    def predict(self, batch) -> np.ndarray:
        """Argmax (or scalar) prediction per row of the last node
        (reference Predict + TransformPred, nnet_impl-inl.hpp:186-299)."""
        out = self.predict_device(batch)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            out = multihost_utils.process_allgather(out, tiled=True)
        return np.asarray(out)

    def _resolve_node(self, node_name: str) -> int:
        """Node id from a name or a top[-k] offset (reference
        ExtractFeature resolution, nnet_impl-inl.hpp:204-215)."""
        m = re.match(r"top\[-(\d+)\]$", node_name)
        if m:
            offset = int(m.group(1))
            nnode = self.net_cfg.param.num_nodes
            check(1 <= offset <= nnode,
                  "ExtractFeature: offset must be within num_node range")
            return nnode - offset
        check(node_name in self.net_cfg.node_name_map,
              "ExtractFeature: cannot find node name: %s" % node_name)
        return self.net_cfg.node_name_map[node_name]

    def extract_feature(self, batch, node_name: str) -> np.ndarray:
        out = self._forward_nodes(batch, (self._resolve_node(node_name),))[0]
        return np.asarray(out)

    def generate(self, prompts, n_new: int, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 prompt_lens=None) -> np.ndarray:
        """KV-cached autoregressive generation for sequence nets
        (embed/attention stacks): one decode step per new token attends
        against per-layer k/v caches instead of recomputing the full
        prefix — O(L_max * d) per token, the serving decode loop the
        reference's pred task has no analogue of.

        prompts: (batch, prompt_len) integer token matrix; returns the
        (batch, n_new) continuation. ``prompt_lens`` (optional, (batch,)
        ints <= prompt_len) serves a RAGGED batch: row r's real prompt is
        its first prompt_lens[r] tokens and its continuation starts
        there — the shared-length prefix prefills as one chunk, the rest
        of each prompt streams through the decode steps, and every row's
        n_new tokens come back aligned. temperature 0 (default) = greedy
        argmax; > 0 samples from softmax(logits / temperature),
        optionally truncated to the ``top_k`` most likely tokens first.
        The whole generation runs as ONE jitted lax.scan (cached per
        (batch, min/max prompt_len, n_new, sampling) signature — ragged
        length PATTERNS share compilations); positions are bounded by the
        training sequence length (the pos-embed table / cache size).
        Single-device: sharded or stage-packed training params are
        gathered canonical first.
        """
        prompts = np.asarray(prompts)
        check(prompts.ndim == 2, "generate: prompts must be (batch, len)")
        b, max_p = prompts.shape
        if prompt_lens is None:
            lens = np.full(b, max_p, np.int32)
        else:
            lens = np.asarray(prompt_lens, np.int32)
            check(lens.shape == (b,) and lens.min() >= 1
                  and lens.max() <= max_p,
                  "generate: prompt_lens must be (batch,) ints in "
                  "[1, prompts.shape[1]]")
        plen = int(lens.min())       # shared prefix -> chunked prefill
        l_max = self.net_cfg.param.input_shape[2]
        total = int(lens.max()) + n_new
        check(total <= l_max,
              "generate: prompt_len %d + n_new %d exceeds the net's "
              "sequence length %d" % (int(lens.max()), n_new, l_max))
        if n_new <= 0:
            return np.zeros((b, 0), np.int32)

        key = ("decode", b)
        if getattr(self, "_decode_net", None) is None \
                or self._decode_net[0] != key:
            if getattr(self, "_decode_net", None) is not None:
                # batch-signature change drops every decode program
                telemetry.count("decode.cache_drop")
                self._decode_cause = "decode_cache_drop"
            else:
                self._decode_cause = "new_signature"
            self._decode_net = (key, self._seq_net(b, 1))
            self._prefill_nets = {}
            self._decode_fns = {}
            self._decode_params = None
        net2 = self._decode_net[1]
        if plen not in self._prefill_nets:
            self._prefill_nets[plen] = self._seq_net(b, plen)
        pre_net = self._prefill_nets[plen]
        params = self._decode_params_current()
        _, cache_keys, cache_shapes, cache_dtype = \
            self._decode_cache_specs(net2, b, l_max)

        temperature, top_k = float(temperature), int(top_k)
        fkey = (plen, total, temperature, top_k)
        # a fresh entry means THIS call pays the decode-program compile:
        # the TTFT stamp below must not charge it to prefill
        fresh_fns = fkey not in self._decode_fns
        if fresh_fns:
            last = net2.cfg.param.num_nodes - 1
            pick = _sample_pick(temperature, top_k)

            def place(toks, t, picked, lens):
                """Column t+1: the row's own prompt token while t+1
                is still inside its prompt, else the picked token."""
                cur = jax.lax.dynamic_slice(
                    toks, (0, t + 1), (b, 1))[:, 0]
                new = jnp.where(t + 1 < lens, cur, picked)
                return jax.lax.dynamic_update_slice(
                    toks, new[:, None], (0, t + 1))

            # The generation is TWO jitted programs split at the
            # first-token boundary — the TTFT split the serving layer
            # measures (doc/observability.md), and the same seam
            # iteration-granularity batching will schedule at later.
            # Same RNG folds, same cache contents as the old single
            # program: token-exact.
            def run_prefill(params, toks, key, lens):
                caches = {k: jnp.zeros(sh, cache_dtype)
                          for k, sh in zip(cache_keys, cache_shapes)}
                # chunked prefill: ONE forward covers the shared prefix
                # [0, plen) and fills every cache; its last row yields the
                # candidate token for position plen
                pre = jax.lax.dynamic_slice(toks, (0, 0), (b, plen))
                values, _ = pre_net.forward(
                    params, pre.reshape(b, 1, 1, plen).astype(jnp.float32),
                    train=False, decode_pos=0, kv_cache=caches)
                caches = dict(pre_net._last_cache_updates)
                first = pick(values[last].reshape(b, -1, plen)[:, :, -1],
                             jax.random.fold_in(key, plen - 1)
                             ).astype(toks.dtype)
                toks = place(toks, plen - 1, first, lens)
                # params donated-and-returned: see _swap_params — keeps
                # the decode copy runtime-resident across serving calls.
                # ``first`` is returned UNDONATED so the caller can block
                # on the first token alone while the decode program runs.
                return toks, caches, first, params

            def run_decode(params, toks, caches, key, lens):
                def step(carry, t):
                    toks, caches = carry
                    tok_t = jax.lax.dynamic_slice(toks, (0, t), (b, 1))
                    data = tok_t.reshape(b, 1, 1, 1).astype(jnp.float32)
                    values, _ = net2.forward(params, data, train=False,
                                             decode_pos=t,
                                             kv_cache=caches)
                    nxt = pick(values[last].reshape(b, -1),
                               jax.random.fold_in(key, t)
                               ).astype(toks.dtype)
                    toks = place(toks, t, nxt, lens)
                    return (toks, dict(net2._last_cache_updates)), None

                (toks, _), _ = jax.lax.scan(
                    step, (toks, caches), jnp.arange(plen, total - 1))
                return toks, params

            cause = getattr(self, "_decode_cause", "new_signature")
            self._decode_fns[fkey] = (
                telemetry.jit_watch(
                    jax.jit(run_prefill, donate_argnums=(0,)),
                    "jit.decode_prefill", cause=cause,
                    key=("decode", b) + fkey),
                telemetry.jit_watch(
                    # toks flows prefill -> decode exactly once and is
                    # returned: donate it so the scan updates in place
                    # (caches are NOT donated — they have no matching
                    # output to alias, so donation would only warn)
                    jax.jit(run_decode, donate_argnums=(0, 1)),
                    "jit.decode_step", cause=cause,
                    key=("decode", b) + fkey))
        toks0 = np.zeros((b, l_max), np.int32)
        toks0[:, :max_p] = prompts
        # (padding beyond a ragged row's real prompt is never read: the
        # prefill covers only the shared [0, min(lens)) prefix, and every
        # later column a step reads was either a real prompt token or
        # place()-written at the previous step)
        try:
            with telemetry.span("decode.generate", new_tokens=n_new):
                t0 = time.perf_counter()
                pre_fn, dec_fn = self._decode_fns[fkey]
                key_dev = jax.random.PRNGKey(seed)
                lens_dev = jnp.asarray(lens)
                toks_dev, caches, first_dev, new_dparams = pre_fn(
                    params, jnp.asarray(toks0), key_dev, lens_dev)
                run_decode = total > plen + 1
                if run_decode and not fresh_fns:
                    # compiled decode program: dispatch the scan BEFORE
                    # blocking on the first token — async dispatch keeps
                    # the chip busy while the host timestamps TTFT
                    toks_dev, new_dparams = dec_fn(
                        new_dparams, toks_dev, caches, key_dev, lens_dev)
                jax.block_until_ready(first_dev)
                t_first = time.perf_counter()
                # the TTFT boundary: the serving worker's trace context
                # picks this mark up (utils/servd._observe_request)
                telemetry.mark("first_token")
                telemetry.span_event("decode.prefill", t0, t_first - t0)
                if run_decode and fresh_fns:
                    # fresh decode program: jax.jit traces and compiles
                    # synchronously inside this call, so dispatching it
                    # before the block above would charge the whole
                    # compile to prefill/TTFT — the device had the first
                    # token long before. Stamp first, pay the compile
                    # where it belongs: in the decode phase.
                    toks_dev, new_dparams = dec_fn(
                        new_dparams, toks_dev, caches, key_dev, lens_dev)
                toks = np.asarray(toks_dev)        # blocks for the rest
                if total > plen + 1:
                    telemetry.span_event(
                        "decode.decode", t_first,
                        time.perf_counter() - t_first,
                        tokens=int(b * (total - plen - 1)))
        except Exception:
            # the donated decode copy may be consumed even on failure —
            # drop the cache so the next call regathers from self.params
            self._decode_params = None
            # a FIRST call that failed may have cached programs that
            # never actually compiled: keeping them would make the
            # retry look non-fresh and dispatch the decode program
            # before the first-token block, charging its synchronous
            # compile to prefill/TTFT — evict so the retry takes the
            # fresh path. A warmed signature keeps its programs: they
            # are known-compiled, and evicting would make every
            # transient backend failure cost the retry a recompile
            # cliff (misattributed to that innocent request)
            if fresh_fns:
                self._decode_fns.pop(fkey, None)
            telemetry.count("decode.cache_drop")
            raise
        self._decode_params = (self._decode_params[0], new_dparams)
        telemetry.count("decode.tokens", int(b) * int(n_new))
        return np.stack([toks[r, lens[r]: lens[r] + n_new]
                         for r in range(b)])

    def _decode_params_current(self):
        """Gathered-canonical params on device for the decode paths,
        re-fetched only when the params changed — the ONE staleness rule
        generate and beam_generate share. CONTRACT: the key is the params
        LIST identity — training reassigns the list, so that path is
        covered structurally; any mutator that edits the param dicts in
        place (set_weight, copy_model_from today) must set
        self._decode_params = None itself. (Leaf-id keys would be
        unsound: id() values recycle after GC; holding leaf refs would
        pin the previous params in device memory.)"""
        if getattr(self, "_decode_params", None) is None \
                or self._decode_params[0] is not self.params:
            telemetry.count("decode.param_regather")
            canon = [
                {k: jnp.asarray(np.asarray(parallel.fetch_global(v)))
                 for k, v in p.items()}
                for p in self.canonical_params()]
            mesh = self._decode_mesh()
            if mesh is not None:
                # tensor-parallel serving: place the decode copy with the
                # SAME Megatron shardings training uses (fullc/conv wmat
                # split over the model axis, attention replicated —
                # parallel/sharding.py:tp_spec); GSPMD partitions the
                # decode matmuls and the argmax/sampling runs on gathered
                # logits. A model whose FFN/head weights need tp to fit
                # one chip's HBM is served the same way it was trained.
                from ..parallel.sharding import param_shardings
                shards = param_shardings(mesh, self.net.layers, canon)
                canon = [
                    {k: jax.device_put(v, shards[i][k])
                     for k, v in p.items()}
                    for i, p in enumerate(canon)]
            self._decode_params = (self.params, canon)
        return self._decode_params[1]

    def _decode_mesh(self):
        """The serving mesh: ``model_parallel`` devices on one "model"
        axis (the first tp group — serving needs no data axis; the batch
        rides every device). None = single-device decode."""
        if self.model_parallel <= 1 or self.mesh is None \
                or "model" not in self.mesh.axis_names:
            return None
        if getattr(self, "_decode_mesh_cache", None) is None:
            devs = np.asarray(self.mesh.devices).reshape(
                -1, self.mesh.shape["model"])[0]
            self._decode_mesh_cache = jax.sharding.Mesh(devs, ("model",))
        return self._decode_mesh_cache

    def _seq_net(self, batch_size: int, seq_len: int) -> "NeuralNet":
        """A NeuralNet over the same config at a different sequence
        length (the decode/prefill nets — weights stay the trainer's,
        and so does the compute dtype: a bf16-trained model decodes in
        bf16)."""
        import copy
        cfg2 = copy.deepcopy(self.net_cfg)
        cfg2.param.input_shape = (1, 1, seq_len)
        return NeuralNet(cfg2, batch_size,
                         compute_dtype=self.compute_dtype)

    @staticmethod
    def _decode_cache_specs(net2, b: int, l_max: int):
        """(att_idx, cache_keys, cache_shapes, cache_dtype) for a decode
        net — THE cache layout contract, shared by generate and
        export_decode so live decoding and exported artifacts cannot
        drift apart. Caches live in the net's compute dtype (a
        bf16-trained model keeps bf16 activations end to end and halves
        serving cache bytes). Also enforces the decode preconditions
        (attention present, causal)."""
        att_idx = [i for i, lay in enumerate(net2.layers)
                   if getattr(lay, "type_name", "") == "attention"]
        check(bool(att_idx), "decode: the net has no attention layers")
        for i in att_idx:
            check(bool(net2.layers[i].causal),
                  "decode: attention layer %d is not causal" % i)
        keys, shapes = [], []
        for i in att_idx:
            lay = net2.layers[i]
            for nm in ("k", "v"):
                keys.append((i, nm))
                shapes.append((b, lay.nkvhead or lay.nhead, l_max,
                               lay._dh()))
        return att_idx, keys, shapes, net2.compute_dtype or jnp.float32

    def beam_generate(self, prompts, n_new: int,
                      beam: int = 4) -> np.ndarray:
        """KV-cached beam search: width-``beam`` exact search over summed
        log-probabilities, returning each row's best continuation
        (batch, n_new). Beams ride the decode batch dim (b*beam rows);
        each step re-ranks beam x vocab candidates and REORDERS the k/v
        caches to the surviving beams' parents (a batch-dim gather —
        the cache machinery is shared with generate()). Fixed horizon
        (no stop-token handling); uniform prompt lengths.
        """
        prompts = np.asarray(prompts)
        check(prompts.ndim == 2,
              "beam_generate: prompts must be (batch, len)")
        b, plen = prompts.shape
        B = int(beam)
        check(B >= 1, "beam_generate: beam must be >= 1")
        l_max = self.net_cfg.param.input_shape[2]
        total = plen + n_new
        check(total <= l_max,
              "beam_generate: prompt_len %d + n_new %d exceeds the "
              "net's sequence length %d" % (plen, n_new, l_max))
        if n_new <= 0:
            return np.zeros((b, 0), np.int32)
        key = ("beam", b, B)
        if getattr(self, "_beam_net", None) is None \
                or self._beam_net[0] != key:
            self._beam_net = (key, self._seq_net(b * B, 1))
            self._beam_prefill = {}
            self._beam_fns = {}
        net2 = self._beam_net[1]
        if plen not in self._beam_prefill:
            self._beam_prefill[plen] = self._seq_net(b, plen)
        pre_net = self._beam_prefill[plen]
        params = self._decode_params_current()
        _, cache_keys, pre_shapes, cache_dtype = \
            self._decode_cache_specs(pre_net, b, l_max)
        last = net2.cfg.param.num_nodes - 1

        fkey = (plen, total)
        if fkey not in self._beam_fns:

            def logp(probs):
                return jnp.log(jnp.maximum(probs, 1e-30))

            def run(params, toks):
                # prefill on the raw batch, then expand row r -> r*B..:
                # every beam of a row starts from the same prompt caches
                caches = {k: jnp.zeros(sh, cache_dtype)
                          for k, sh in zip(cache_keys, pre_shapes)}
                values, _ = pre_net.forward(
                    params,
                    toks[:, :plen].reshape(b, 1, 1, plen)
                    .astype(jnp.float32),
                    train=False, decode_pos=0, kv_cache=caches)
                caches = {k: jnp.repeat(v, B, axis=0)
                          for k, v in
                          pre_net._last_cache_updates.items()}
                lp = logp(values[last].reshape(b, -1, plen)[:, :, -1])
                V = lp.shape[1]
                k0 = min(B, V)
                scores, tok0 = jax.lax.top_k(lp, k0)       # (b, B)
                if k0 < B:   # vocab smaller than beam: pad dead beams
                    padd = B - k0
                    scores = jnp.pad(scores, ((0, 0), (0, padd)),
                                     constant_values=-jnp.inf)
                    tok0 = jnp.pad(tok0, ((0, 0), (0, padd)))
                hist = jnp.repeat(toks, B, axis=0)         # (b*B, l_max)
                hist = jax.lax.dynamic_update_slice(
                    hist, tok0.reshape(-1, 1).astype(hist.dtype),
                    (0, plen))

                def step(carry, t):
                    hist, scores, caches = carry
                    tok_t = jax.lax.dynamic_slice(
                        hist, (0, t), (b * B, 1))
                    values, _ = net2.forward(
                        params,
                        tok_t.reshape(b * B, 1, 1, 1).astype(jnp.float32),
                        train=False, decode_pos=t, kv_cache=caches)
                    caches = dict(net2._last_cache_updates)
                    lp = logp(values[last].reshape(b * B, -1))
                    cand = (scores.reshape(b, B, 1)
                            + lp.reshape(b, B, -1)).reshape(b, -1)
                    scores, idx = jax.lax.top_k(cand, B)   # (b, B)
                    parent = idx // lp.shape[1]
                    tok = (idx % lp.shape[1]).astype(hist.dtype)
                    rows = (jnp.arange(b)[:, None] * B
                            + parent).reshape(-1)
                    caches = {k: jnp.take(v, rows, axis=0)
                              for k, v in caches.items()}
                    hist = jnp.take(hist, rows, axis=0)
                    hist = jax.lax.dynamic_update_slice(
                        hist, tok.reshape(-1, 1), (0, t + 1))
                    return (hist, scores, caches), None

                if total > plen + 1:
                    (hist, scores, caches), _ = jax.lax.scan(
                        step, (hist, scores, caches),
                        jnp.arange(plen, total - 1))
                best = jnp.argmax(scores, axis=1)          # (b,)
                rows = jnp.arange(b) * B + best
                # params donated-and-returned: see _swap_params
                return jnp.take(hist, rows, axis=0), scores, params

            self._beam_fns[fkey] = telemetry.jit_watch(
                jax.jit(run, donate_argnums=(0,)), "jit.beam_decode",
                key=("beam", b, B) + fkey)
        toks0 = np.zeros((b, l_max), np.int32)
        toks0[:, :plen] = prompts
        try:
            with telemetry.span("decode.beam", beam=B):
                hist, _, new_dparams = self._beam_fns[fkey](
                    params, jnp.asarray(toks0))
        except Exception:
            # donated decode copy may be consumed even on failure
            self._decode_params = None
            telemetry.count("decode.cache_drop")
            raise
        self._decode_params = (self._decode_params[0], new_dparams)
        return np.asarray(hist)[:, plen:total]

    def decode_session(self, nslots: int, n_new: int,
                       temperature: float = 0.0,
                       top_k: int = 0,
                       kv_pool: "Optional[KVBlockPool]" = None
                       ) -> "DecodeSession":
        """A batched decode session over ``nslots`` independent KV-cache
        slots — the iteration-granularity serving datapath
        (doc/serving.md "Continuous batching"). ``prefill`` admits one
        request into a free slot, ``step`` advances every active slot
        one token, ``retire`` frees a finished slot so the next queued
        request joins MID-DECODE instead of waiting out the stragglers.
        Per-request output is token-exact vs a solo ``generate`` of the
        same request (per-slot RNG keyed on the request's own seed).
        Programs are cached per (bucket, sampling) signature in the
        trainer's jit cache: a request joining a warm bucket never
        recompiles (the arXiv:1802.04799 latency cliff).

        ``kv_pool`` (``decode_kv_pool``) swaps the session's dense
        slot-major cache for the PAGED layout (doc/performance.md
        "Decode KV cache"): per-slot block tables over a shared
        free-list block pool, shared-prefix block reuse, token-exact
        vs the dense session."""
        return DecodeSession(self, nslots, n_new,
                             temperature=temperature, top_k=top_k,
                             kv_pool=kv_pool)

    def decode_kv_pool(self, block: int, pool_tokens: int = 0,
                       prefix_reuse: bool = True,
                       bytes_cap: Optional[int] = None,
                       retained_frac: float = 1.0) -> "KVBlockPool":
        """The process-wide paged decode KV pool (created on first use,
        shared by every paged ``decode_session`` whatever its bucket —
        sharing across buckets is what makes a shared system prompt
        prefill ONCE fleet-of-buckets-wide). Keyed on the params
        generation: a model reload (``params`` reassigned) or a
        different block size drops the old pool (its blocks hold
        old-weight K/V) and builds a fresh one."""
        check(self.params is not None,
              "decode_kv_pool: init_model/load_model first")
        p = getattr(self, "_kv_pool", None)
        if p is not None and (p.closed or p.bs != int(block)
                              or p._params_key is not self.params):
            self.release_kv_pool()
            p = None
        if p is None:
            p = KVBlockPool(self, int(block), pool_tokens=pool_tokens,
                            prefix_reuse=prefix_reuse,
                            bytes_cap=bytes_cap,
                            retained_frac=retained_frac)
            self._kv_pool = p
        return p

    def release_kv_pool(self) -> None:
        """Drop the paged pool's device arrays (worker drain / model
        reload): the KV account must read 0 the moment the serving
        datapath lets go — freed HBM reported as allocated is the
        account lying. Idempotent."""
        p = getattr(self, "_kv_pool", None)
        if p is not None:
            p.release()
        self._kv_pool = None

    def expected_decode_grid(self, buckets, plens, temperature:
                             float = 0.0, top_k: int = 0,
                             kv_block: int = 0):
        """Enumerate the EXPECTED serving program grid as ``(key,
        bucket_label)`` pairs — the jit-cache keys a serving datapath
        over these ``buckets`` (slot counts) and ``plens`` (declared
        prompt lengths, ``serve_plen_buckets``) will compile, exactly
        as ``DecodeSession`` keys them. Feeding the pairs to
        ``perf.Ledger.set_expected_grid`` turns the compile flight
        recorder into the warm-grid readiness account (doc/
        observability.md): warm-vs-expected per bucket,
        ``cxxnet_ready_programs_pct``, the ``warming`` health state.

        Pure enumeration — no params, no device, no compile. Prefill
        keys land under the ``"prefill"`` bucket label (they are
        per-prompt-length, shared by every slot bucket); admit/step
        keys under their slot count. The paged suffix-prefill reuse
        variants (``p0 > 0`` — one per observed shared-prefix length)
        are deliberately NOT enumerated: their population is
        input-dependent, so they compile lazily and simply do not
        gate readiness."""
        temperature, top_k = float(temperature), int(top_k)
        grid = []
        for plen in sorted({int(p) for p in plens}):
            check(plen >= 1, "expected_decode_grid: plen must be >= 1")
            if kv_block > 0:
                l_max = self.net_cfg.param.input_shape[2]
                bs = int(kv_block)
                check(l_max % bs == 0,
                      "expected_decode_grid: kv_block %d must divide "
                      "the net's sequence length %d" % (bs, l_max))
                grid.append((("sess_prefill_paged", plen, 0,
                              l_max // bs, bs, temperature, top_k),
                             "prefill"))
            else:
                grid.append((("sess_prefill", plen, temperature,
                              top_k), "prefill"))
        for b in sorted({max(1, int(b)) for b in buckets}):
            if kv_block > 0:
                l_max = self.net_cfg.param.input_shape[2]
                bs = int(kv_block)
                T = l_max // bs
                grid.append((("sess_admit_paged", b, T), str(b)))
                grid.append((("sess_step_paged", b, T, bs,
                              temperature, top_k), str(b)))
            else:
                grid.append((("sess_admit", b), str(b)))
                grid.append((("sess_step", b, temperature, top_k),
                             str(b)))
        return grid

    def export_decode(self, batch_size: int, prompt_len: int,
                      compat: bool = True):
        """AOT-export the KV-cached decode loop as TWO self-contained
        StableHLO artifacts (params baked in, jax-only at serving time —
        the decode counterpart of export_forward):

        * prefill: (batch, prompt_len) int32 tokens ->
          (last-position softmax row, cache tuple)
        * step:    ((batch,) int32 token, () int32 position, cache tuple)
          -> (softmax row, updated cache tuple)

        The serving host drives its own loop (sampling policy, stop
        conditions, batching) and threads the opaque cache tuple between
        calls — `api.load_decode` ships a reference loop. Returns
        (prefill_bytes, step_bytes).

        BOUND: exported artifacts are single-chip (params baked in as
        one canonical copy) — a model whose weights need tensor
        parallelism to fit one chip's HBM must be served in-process via
        generate()/beam_generate() under ``model_parallel`` (the decode
        params stay Megatron-sharded, _decode_params_current), not via
        export.
        """
        from jax import export as jexport
        check(self.params is not None,
              "export_decode: init_model/load_model first")
        b, plen = int(batch_size), int(prompt_len)
        l_max = self.net_cfg.param.input_shape[2]
        check(0 < plen <= l_max,
              "export_decode: prompt_len must be in [1, %d]" % l_max)
        net2, pre_net = self._seq_net(b, 1), self._seq_net(b, plen)
        params = [{k: np.asarray(parallel.fetch_global(v))
                   for k, v in p.items()}
                  for p in self.canonical_params()]
        _, cache_keys, cache_shapes, cache_dtype = \
            self._decode_cache_specs(net2, b, l_max)
        last = net2.cfg.param.num_nodes - 1

        def prefill(toks):
            caches = {k: jnp.zeros(sh, cache_dtype)
                      for k, sh in zip(cache_keys, cache_shapes)}
            values, _ = pre_net.forward(
                params, toks.reshape(b, 1, 1, plen).astype(jnp.float32),
                train=False, decode_pos=0, kv_cache=caches)
            cu = pre_net._last_cache_updates
            probs = values[last].reshape(b, -1, plen)[:, :, -1]
            return probs, tuple(cu[k] for k in cache_keys)

        def step(tok, pos, caches):
            values, _ = net2.forward(
                params, tok.reshape(b, 1, 1, 1).astype(jnp.float32),
                train=False, decode_pos=pos,
                kv_cache=dict(zip(cache_keys, caches)))
            cu = net2._last_cache_updates
            return (values[last].reshape(b, -1),
                    tuple(cu[k] for k in cache_keys))

        platforms = ("cpu", "tpu") if compat else None
        cache_specs = tuple(jax.ShapeDtypeStruct(sh, cache_dtype)
                            for sh in cache_shapes)
        pre_exp = jexport.export(jax.jit(prefill), platforms=platforms)(
            jax.ShapeDtypeStruct((b, plen), jnp.int32))
        step_exp = jexport.export(jax.jit(step), platforms=platforms)(
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32), cache_specs)
        # versioned frame: loaders check format version + that the two
        # artifacts share ONE cache-layout contract (utils/artifact.py;
        # the reference's model-blob version guard, nnet_config.h:126-145)
        from ..utils import artifact
        meta = {"cache_fingerprint": artifact.cache_fingerprint(
                    cache_keys, cache_shapes, cache_dtype),
                "batch": b, "prompt_len": plen, "l_max": int(l_max)}
        return (artifact.frame("decode_prefill", meta, pre_exp.serialize()),
                artifact.frame("decode_step", meta, step_exp.serialize()))

    def export_forward(self, node_name: str = "", batch_size: int = 0,
                       compat: bool = True) -> bytes:
        """AOT-compile-and-serialize the inference forward as a portable
        StableHLO artifact (jax.export): trained params are baked in as
        constants, so the artifact is fully self-contained — loadable in
        any process with `cxxnet_tpu.api.load_exported` and runnable
        WITHOUT the framework, the config file, or the model file (a
        framework-free host strips the versioned 12-byte+JSON header —
        magic "CXTF", two <II fields (version, header_len), header —
        then jax.export.deserialize's the payload; utils/artifact.py).
        The TPU-native deployment story
        the reference covered with its C wrapper + model files
        (wrapper/cxxnet_wrapper.h:36-230): here the whole net is one
        compiler artifact.

        node_name: "" = the last node (the pred/pred_raw surface), else a
        named node or top[-k] (the extract surface). batch_size: 0 = the
        training batch size; -1 = a SYMBOLIC batch dim — one artifact
        serves any batch size n >= 1 (jax.export shape polymorphism; the
        serving runtime re-specializes per distinct n and caches, so a
        latency-sensitive deployment still sees fixed-shape executables).
        compat=True exports with maximum platform compatibility (CPU +
        TPU lowering).
        """
        from jax import export as jexport
        check(self.params is not None,
              "export_forward: init_model/load_model first")
        node_id = (self.net_cfg.param.num_nodes - 1 if not node_name
                   else self._resolve_node(node_name))
        if batch_size < 0:
            (bs,) = jexport.symbolic_shape("b")
        else:
            bs = batch_size or self.batch_size
        c, h, w = self.net_cfg.param.input_shape
        # a serving artifact is single-device: gather any sharded/packed
        # params to host canonical form and trace a mesh-free forward
        params = [{k: np.asarray(parallel.fetch_global(v))
                   for k, v in p.items()}
                  for p in self.canonical_params()]
        net = self.net

        def fwd(data):
            values, _ = net.forward(params, data, train=False,
                                    rng=jax.random.PRNGKey(0))
            return values[node_id]

        spec = jax.ShapeDtypeStruct((bs, c, h, w), jnp.float32)
        platforms = ("cpu", "tpu") if compat else None
        exp = jexport.export(jax.jit(fwd),
                             platforms=platforms)(spec)
        from ..utils import artifact
        return artifact.frame(
            "forward", {"input_shape": [int(c), int(h), int(w)],
                        "batch": (-1 if batch_size < 0 else int(bs))},
            exp.serialize())

    def evaluate(self, iter_eval, data_name: str) -> str:
        """Run metrics over an eval iterator; padding rows dropped
        (reference Evaluate, nnet_impl-inl.hpp:224-243)."""
        self._last_update_t0 = None
        ret = ""
        if self.eval_train != 0 and len(self.train_metric):
            if self._metric_accum is not None:
                # the only host fetch of train-metric state: round boundary
                self.train_metric.absorb(jax.device_get(self._metric_accum))
                self._metric_accum = None
            ret += self.train_metric.print_str("train")
            self.train_metric.clear()
        if iter_eval is None:
            return ret
        self.metric.clear()
        node_ids = tuple(self.eval_nodes)
        iter_eval.before_first()
        while iter_eval.next():
            batch = iter_eval.value()
            outs = self._forward_nodes(batch, node_ids)
            local_n = batch.data.shape[0]
            mask = np.zeros(local_n, bool)
            mask[:local_n - batch.num_batch_padd] = True
            labels_np = np.asarray(batch.label)
            if outs[0].shape[0] != local_n:
                # per-host shard mode: scores came back for the GLOBAL
                # batch in mesh data-axis device order. Lift labels and
                # the validity mask to global arrays with the SAME
                # NamedSharding used for the data, so their row order
                # matches the scores by construction — a raw
                # process_allgather concatenates in process-index order,
                # which differs from device order on hybrid DCN x ICI
                # meshes and would silently misalign the metrics
                sh = parallel.batch_sharding(self.mesh)
                labels_np = parallel.fetch_global(
                    jax.make_array_from_process_local_data(sh, labels_np))
                mask = parallel.fetch_global(
                    jax.make_array_from_process_local_data(
                        sh, mask)).astype(bool)
            scores = [np.asarray(o).reshape(o.shape[0], -1)[mask]
                      for o in outs]
            labels = self.net.label_info_from(labels_np[mask],
                                              as_numpy=True)
            self.metric.add_eval(scores, labels)
        ret += self.metric.print_str(data_name)
        return ret

    # ------------------------------------------------------------------
    # every tag a layer's visit_order gives: fullc/conv (wmat, bias),
    # attention (wo; qnorm, knorm with qk_norm = 1; the indexer's widx_q,
    # widx_k, widx_w, idx_gain, idx_bias with attn_mask = dsa; wgate with
    # head_gate = 1), moe (gate = the router, up, down; shared_wmat,
    # shared_up, shared_down with nshared_hidden; expert_bias), rmsnorm
    # (gain), shortconv (w_in, w_conv, w_out)
    _WEIGHT_TAGS = ("bias", "wmat", "wo", "gate", "up", "down", "gain",
                    "qnorm", "knorm", "widx_q", "widx_k", "widx_w",
                    "idx_gain", "idx_bias", "w_in", "w_conv", "w_out",
                    "expert_bias", "wgate", "shared_wmat", "shared_up",
                    "shared_down")

    def set_weight(self, value: np.ndarray, layer_name: str, tag: str) -> None:
        check(tag in self._WEIGHT_TAGS,
              "SetWeight: weight tag can only be one of %s"
              % ", ".join(self._WEIGHT_TAGS))
        # params mutate in place below; the decode cache keys on list
        # identity and would otherwise serve stale weights to generate()
        self._decode_params = None
        if self._pp_entries is not None:
            self._pp_unpack()
            self.net.set_weight(self.params, value, layer_name, tag)
            self._pp_pack()
            return
        self.net.set_weight(self.params, value, layer_name, tag)

    def get_weight(self, layer_name: str, tag: str):
        check(tag in self._WEIGHT_TAGS,
              "GetWeight: weight tag can only be one of %s"
              % ", ".join(self._WEIGHT_TAGS))
        return self.net.get_weight(self.canonical_params(), layer_name, tag)


def _kv_gather_views(pools, tabs, T: int, bs: int):
    """Materialize contiguous dense cache views from block pools via
    block tables — the paged layout's read side. ``tabs`` is ``(T,)``
    (one b=1 row) or ``(S, T)`` (the slot-major batch); a pool is
    ``(NB, 1, nkv, bs, dh)`` per cache key and the view restores the
    exact dense shape ``(..., 1, nkv, T*bs, dh)``, so the per-row
    decode math downstream is BITWISE the dense session's (transpose/
    reshape are pure layout; garbage gathered through scratch-block
    entries only ever covers causally masked positions, whose softmax
    weight is exactly zero)."""
    out = {}
    for k, p in pools.items():
        g = p[tabs]
        if tabs.ndim == 1:
            # (T, 1, nkv, bs, dh) -> (1, nkv, T*bs, dh)
            out[k] = g.transpose(1, 2, 0, 3, 4).reshape(
                g.shape[1], g.shape[2], T * bs, g.shape[4])
        else:
            # (S, T, 1, nkv, bs, dh) -> (S, 1, nkv, T*bs, dh)
            out[k] = g.transpose(0, 2, 3, 1, 4, 5).reshape(
                g.shape[0], g.shape[2], g.shape[3], T * bs, g.shape[5])
    return out


def _session_row_step(net1, last, pick):
    """ONE decode slot's step — the per-row math both the dense and the
    paged session step programs vmap over slots. A single definition so
    the two layouts cannot drift: the paged step runs literally this on
    gathered views."""

    def one(params, toks_r, caches_r, key_r, pos_r):
        # EXACTLY the solo decode step at b=1, with this row's
        # own position/cache/key
        tok = jax.lax.dynamic_slice(toks_r, (pos_r,), (1,))
        data = tok.reshape(1, 1, 1, 1).astype(jnp.float32)
        values, _ = net1.forward(params, data, train=False,
                                 decode_pos=pos_r,
                                 kv_cache=caches_r)
        caches2 = dict(net1._last_cache_updates)
        nxt = pick(values[last].reshape(1, -1),
                   jax.random.fold_in(key_r, pos_r)
                   )[0].astype(toks_r.dtype)
        toks2 = jax.lax.dynamic_update_slice(
            toks_r, nxt[None], (pos_r + 1,))
        return toks2, caches2, nxt

    return one


class KVBlockPool:
    """Device half of the paged decode KV cache (doc/performance.md
    "Decode KV cache"): one fixed pool of KV blocks per attention-cache
    key — ``(NB, 1, nkv, block, dh)``, block id 0 reserved as the
    scratch block — plus the host-side free-list allocator
    (utils/kvblocks.BlockAllocator) that owns every placement decision.
    Shared by every paged ``DecodeSession`` of this trainer: the pool
    (not the session) is the HBM footprint, and ``account()`` is
    block-exact — ``pool_bytes`` IS the arrays' nbytes, at all times.

    Sizing: ``pool_tokens`` cache rows (rounded up to blocks, floored
    at one max-length sequence), clamped under ``bytes_cap`` when the
    perf ledger's HBM account provides one
    (``perf.decode_pool_cap_bytes``: capacity − peak program
    footprint). Exhaustion is the ALLOCATOR's verdict — admission
    evicts retained conversation blocks before deferring
    (``retained_frac`` caps the retained pool; doc/robustness.md
    "Memory governance"); the device never OOMs allocating a cache
    row.

    Lifecycle: created lazily by ``Trainer.decode_kv_pool``, keyed on
    the params generation; ``release()`` (worker drain, model reload)
    drops the arrays and the account reads 0. A device fault inside a
    program that DONATED the pools latches ``closed`` — integrity
    unknown, every session on it refuses, the next session creation
    rebuilds."""

    def __init__(self, trainer: Trainer, block: int,
                 pool_tokens: int = 0, prefix_reuse: bool = True,
                 bytes_cap: Optional[int] = None,
                 retained_frac: float = 1.0):
        from ..utils import kvblocks
        check(block >= 1, "decode_kv_pool: block must be >= 1")
        self.tr = trainer
        self.bs = int(block)
        self.l_max = trainer.net_cfg.param.input_shape[2]
        check(self.l_max % self.bs == 0,
              "decode_kv_pool: block %d must divide the net's sequence "
              "length %d" % (self.bs, self.l_max))
        self.T = self.l_max // self.bs
        self._params_key = trainer.params
        net1 = trainer._seq_net(1, 1)
        (_, self.cache_keys, shapes1, self.cache_dtype) = \
            trainer._decode_cache_specs(net1, 1, self.l_max)
        self._block_shapes = {
            k: (sh[0], sh[1], self.bs, sh[3])
            for k, sh in zip(self.cache_keys, shapes1)}
        itemsize = jnp.dtype(self.cache_dtype).itemsize
        self.block_bytes = sum(
            int(np.prod(sh)) * itemsize
            for sh in self._block_shapes.values())
        usable = max(-(-int(pool_tokens) // self.bs)
                     if pool_tokens else self.T, self.T)
        if bytes_cap:
            # the HBM-account clamp: whole pool (scratch included)
            # under the budget, still floored at one full sequence
            usable = max(self.T,
                         min(usable,
                             int(bytes_cap) // self.block_bytes - 1))
        nb = usable + 1                       # + the scratch block 0
        self.pools = {k: jnp.zeros((nb,) + self._block_shapes[k],
                                   self.cache_dtype)
                      for k in self.cache_keys}
        self.alloc = kvblocks.BlockAllocator(
            nb, self.bs, prefix_reuse=prefix_reuse,
            retained_frac=retained_frac)
        self.closed = False
        import weakref
        self._sessions = weakref.WeakSet()

    @property
    def nbytes(self) -> int:
        """The pool's REAL device footprint (array metadata, no
        transfer) — the value ``cxxnet_decode_kv_bytes`` /
        ``cxxnet_hbm_decode_kv_bytes`` are pinned equal to."""
        if self.closed or self.pools is None:
            return 0
        return sum(int(getattr(a, "nbytes", 0))
                   for a in self.pools.values())

    def fits(self, plen: int, n_new: int) -> bool:
        """Whether the sequence can EVER hold its blocks — False is a
        deterministic request defect (the admits() gate), never a
        queue-wait."""
        return self.alloc.fits(plen, n_new)

    def reservable(self, plen: int, n_new: int, toks=None) -> bool:
        return not self.closed \
            and self.alloc.reservable(plen, n_new, toks)

    def account(self) -> Optional[dict]:
        """Block-exact pool account (host metadata arithmetic — safe
        outside any lock): allocator tallies + ``pool_bytes`` (the
        real nbytes) + live tokens summed over the open sessions.
        ``kv_live_bytes`` counts LOGICAL live rows — shared-prefix
        rows count once per holder, so heavy sharing can push the
        live share past what the physical blocks hold (that is the
        reuse win, not an accounting error). None once released."""
        if self.closed:
            return None
        live = 0
        for s in list(self._sessions):
            if getattr(s, "closed", False):
                continue
            for i in range(s.nslots):
                if s._active[i]:
                    live += s._plen[i] + (s.n_new - 1 - s._remaining[i])
        a = self.alloc.account()
        a.update(pool_bytes=self.nbytes,
                 block_bytes=self.block_bytes,
                 live_tokens=live,
                 kv_live_bytes=live * (self.block_bytes // self.bs))
        return a

    def release(self) -> None:
        """Drop the device arrays; every open session on this pool is
        implicitly dead (their _check_live latches on ``closed``).
        Idempotent."""
        self.closed = True
        self.pools = None


class DecodeSession:
    """Iteration-granularity batched decode over a fixed slot batch.

    The continuous-batching serving datapath (doc/serving.md): where
    ``generate`` runs one monolithic jitted scan per call — a finished
    sequence holds its slot until the longest one ends, and a new
    request cannot join mid-flight — a session owns ``nslots``
    independent decode slots with per-slot KV cache rows, per-slot
    positions, and per-slot RNG keys, scheduled one TOKEN at a time:

    * ``prefill(slot, toks, seed)`` admits one request into a free slot
      (the same b=1 per-prompt-length prefill program solo dispatch
      compiles, then a jitted scatter inserts its cache/token rows into
      the slot-major batch state) and returns its first token;
    * ``step()`` advances ALL active slots one token — ONE jitted
      program per bucket size: the b=1 decode step ``jax.vmap``-ed over
      the slot axis, so every slot runs exactly the solo per-row math
      (per-slot ``decode_pos``, per-slot cache row, per-slot
      ``fold_in(PRNGKey(seed), pos)``) and batch composition never
      enters a request's tokens — token-exact vs solo dispatch;
    * ``retire(slot)`` frees a finished slot, so the NEXT queued request
      joins mid-decode instead of waiting out the stragglers.

    Programs cache in the trainer's jit cache per (bucket, sampling)
    signature — ``("sess_step", nslots, temperature, top_k)`` extends
    the ``_decode_fns`` keying — so a request joining a WARM bucket
    never triggers a recompile (the compile-is-the-latency-cliff
    constraint, arXiv:1802.04799); only a new bucket size, a new prompt
    length, or a new sampling signature compiles. A retired slot's
    stale cache tail is never read: attention masks to [0, pos] and a
    new occupant's prefill overwrites [0, plen) before any step reads.

    Single-consumer by design (the servd worker thread); NOT
    thread-safe. The session serves the params the trainer had at
    creation — after a model reload (``trainer.params`` reassigned)
    every call raises, because the slot caches hold OLD-weight K/V;
    the dispatcher closes sessions before reloading.
    """

    def __init__(self, trainer: Trainer, nslots: int, n_new: int,
                 temperature: float = 0.0, top_k: int = 0,
                 kv_pool: Optional[KVBlockPool] = None):
        check(nslots >= 1, "decode_session: nslots must be >= 1")
        check(n_new >= 1, "decode_session: n_new must be >= 1")
        self.tr = trainer
        self.nslots = int(nslots)
        self.n_new = int(n_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._params_key = trainer.params   # staleness guard (identity)
        self.l_max = trainer.net_cfg.param.input_shape[2]
        # the b=1 decode net: ONE row's step; step() vmaps it over slots
        self._net1 = trainer._seq_net(1, 1)
        (_, self._cache_keys, self._cache_shapes1, self._cache_dtype) = \
            trainer._decode_cache_specs(self._net1, 1, self.l_max)
        self._last = self._net1.cfg.param.num_nodes - 1
        self._pick = _sample_pick(self.temperature, self.top_k)
        # paged layout (doc/performance.md "Decode KV cache"): the K/V
        # rows live in the trainer-wide block pool; the session owns
        # only per-slot BLOCK TABLES (device (nslots, T) int32 — the
        # step program gathers its dense views through them) plus the
        # host allocation mirror. Dense layout: slot-major cache
        # arrays, exactly as before.
        self.pool = kv_pool
        self._caches = None
        self._tables_dev = None
        self._slot_blocks: List[Optional[List[int]]] = []
        if kv_pool is not None:
            check(kv_pool.tr is trainer
                  and kv_pool._params_key is trainer.params
                  and not kv_pool.closed,
                  "decode_session: the kv pool belongs to another "
                  "trainer/params generation (model reload?) — open a "
                  "fresh pool via decode_kv_pool")
            self._tables_dev = jnp.zeros((self.nslots, kv_pool.T),
                                         jnp.int32)
            self._slot_blocks = [None] * self.nslots
            kv_pool._sessions.add(self)
        self._toks = jnp.zeros((self.nslots, self.l_max), jnp.int32)
        if kv_pool is None:
            # slot-major device state. Caches keep the b=1 dim —
            # (nslots, 1, nkvhead, l_max, dh) — so the vmapped per-row
            # forward sees exactly the solo (1, nkvhead, l_max, dh)
            # cache shape.
            self._caches = {k: jnp.zeros((self.nslots,) + sh,
                                         self._cache_dtype)
                            for k, sh in zip(self._cache_keys,
                                             self._cache_shapes1)}
        # per-slot RNG keys and positions live ON DEVICE: the admit
        # program seeds a slot's row, the step program returns pos+1 —
        # zero per-iteration H2D on the serving hot path (a retired
        # slot's device pos keeps advancing harmlessly; admission
        # resets it). The host mirrors only what scheduling needs.
        k0 = np.asarray(jax.random.PRNGKey(0))
        self._keys_dev = jnp.zeros((self.nslots,) + k0.shape, k0.dtype)
        self._pos_dev = jnp.zeros(self.nslots, jnp.int32)
        self._active = [False] * self.nslots
        self._remaining = [0] * self.nslots
        # per-slot prompt length: with _remaining it gives the live
        # cache extent (plen + tokens generated) the KV occupancy
        # account reads — host scheduling metadata, never a device
        # fetch (the deleted _pos mirror was write-only; this is read
        # by kv_account every decode iteration)
        self._plen = [0] * self.nslots
        self.closed = False

    # -- bookkeeping ---------------------------------------------------
    @property
    def active_count(self) -> int:
        return sum(self._active)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.nslots) if not self._active[s]]

    def kv_account(self) -> dict:
        """The session's live KV/HBM occupancy account (doc/
        performance.md "Decode KV cache"): ``kv_bytes`` is the REAL
        allocated cache footprint (sum of the slot-major cache arrays'
        nbytes — device-array metadata, no transfer), ``kv_live_bytes``
        prorates it by the cache rows actually holding K/V (each active
        slot's prompt length + tokens generated so far, vs the
        ``nslots * l_max`` rows allocated). The gap — padding to l_max
        plus dead slots — is exactly what a paged KV cache (ROADMAP
        item 2) would reclaim; servd publishes it as
        ``cxxnet_decode_kv_live_pct``. A closed session accounts 0 (its
        arrays are released).

        PAGED sessions account blocks HELD, not arrays owned: the pool
        is the allocation (``KVBlockPool.account`` carries the
        block-exact ``pool_bytes``) and this session's ``kv_bytes`` is
        its block tables' claim — ``blocks_held * block_bytes``, where
        a prefix block shared with another session counts per holder
        (so the per-bucket rows sum to >= the physically used bytes
        under sharing; the headline/total always comes from the
        pool)."""
        if self.closed or (self._caches is None and self.pool is None):
            return {"bucket": self.nslots, "l_max": self.l_max,
                    "active": 0, "kv_bytes": 0, "kv_live_bytes": 0,
                    "live_tokens": 0, "alloc_tokens": 0}
        live = sum(self._plen[s]
                   + (self.n_new - 1 - self._remaining[s])
                   for s in range(self.nslots) if self._active[s])
        if self.pool is not None:
            held = sum(len(b) for b in self._slot_blocks if b)
            bb = 0 if self.pool.closed else self.pool.block_bytes
            alloc = held * self.pool.bs
            return {"bucket": self.nslots, "l_max": self.l_max,
                    "active": self.active_count,
                    "kv_bytes": held * bb,
                    "kv_live_bytes": live * (bb // self.pool.bs),
                    "live_tokens": live, "alloc_tokens": alloc,
                    "paged": 1, "blocks_held": held}
        kv_bytes = sum(int(getattr(a, "nbytes", 0))
                       for a in self._caches.values())
        alloc = self.nslots * self.l_max
        return {"bucket": self.nslots, "l_max": self.l_max,
                "active": self.active_count, "kv_bytes": kv_bytes,
                "kv_live_bytes": int(round(kv_bytes * live / alloc))
                if alloc else 0,
                "live_tokens": live, "alloc_tokens": alloc}

    def _check_live(self) -> None:
        check(not self.closed, "decode_session: session is closed")
        if self.pool is not None and self.pool.closed:
            # the pool died under us (device fault in a program that
            # donated it, or an explicit release): this session's block
            # tables point into freed/unknown state — same latch-then-
            # raise discipline as staleness below
            self.closed = True
            check(False,
                  "decode_session: the kv block pool is closed — open "
                  "a fresh session (the dispatcher rebuilds the pool)")
        if self.tr.params is not self._params_key:
            # staleness IS the never-serve-again condition the closed
            # flag encodes: latch it BEFORE raising, so the dispatcher
            # (which keys session eviction on `closed`) drops this
            # session from its warm pool instead of re-offering it —
            # and counts the fault against the backend, not the request
            self.closed = True
            check(False,
                  "decode_session: stale session — the trainer's "
                  "params changed (model reload); close it and open a "
                  "new one (the slot caches hold old-weight K/V)")

    # -- programs (trainer jit cache: recompile-watched, keyed) --------
    def _prefill_fn(self, plen: int):
        cache_keys, shapes1 = self._cache_keys, self._cache_shapes1
        cache_dtype, last, pick = self._cache_dtype, self._last, self._pick
        tr = self.tr

        def build():
            pre_net = tr._seq_net(1, plen)

            def run(params, toks, key):
                caches = {k: jnp.zeros((1,) + sh[1:], cache_dtype)
                          for k, sh in zip(cache_keys, shapes1)}
                pre = jax.lax.dynamic_slice(toks, (0, 0), (1, plen))
                values, _ = pre_net.forward(
                    params,
                    pre.reshape(1, 1, 1, plen).astype(jnp.float32),
                    train=False, decode_pos=0, kv_cache=caches)
                caches = dict(pre_net._last_cache_updates)
                first = pick(values[last].reshape(1, -1, plen)[:, :, -1],
                             jax.random.fold_in(key, plen - 1)
                             ).astype(toks.dtype)
                toks = jax.lax.dynamic_update_slice(
                    toks, first[:, None], (0, plen))
                # params donated-and-returned (see _swap_params): the
                # decode copy stays runtime-resident across requests
                return toks, caches, first, params
            return jax.jit(run, donate_argnums=(0,))

        return tr._watched_jit(
            ("sess_prefill", plen, self.temperature, self.top_k),
            "jit.decode_prefill", build)

    def _admit_fn(self):
        def build():
            def run(btoks, bcaches, bkeys, bpos, toks1, caches1, key1,
                    pos1, slot):
                btoks = jax.lax.dynamic_update_slice(
                    btoks, toks1, (slot, 0))
                bc = {k: jax.lax.dynamic_update_slice(
                    bcaches[k], caches1[k][None].astype(bcaches[k].dtype),
                    (slot, 0, 0, 0, 0)) for k in bcaches}
                bkeys = jax.lax.dynamic_update_slice(
                    bkeys, key1[None].astype(bkeys.dtype), (slot, 0))
                bpos = jax.lax.dynamic_update_slice(
                    bpos, pos1[None].astype(bpos.dtype), (slot,))
                return btoks, bc, bkeys, bpos
            return jax.jit(run, donate_argnums=(0, 1, 2, 3))

        return self.tr._watched_jit(("sess_admit", self.nslots),
                                    "jit.decode_admit", build)

    def _step_fn(self):
        net1, last, pick = self._net1, self._last, self._pick

        def build():
            # the per-row step math is ONE definition shared with the
            # paged step program (_session_row_step) — the two cache
            # layouts cannot drift
            one = _session_row_step(net1, last, pick)

            def run(params, toks, caches, keys, pos):
                # inactive slots are stepped too (fixed shapes — that is
                # what bucketing is for): their writes land past a DEAD
                # slot's parked position where nobody reads, and
                # admission overwrites the row. Every row's pos advances
                # on device (returned +1) — active rows match the host's
                # bookkeeping; a dead row's runaway pos is irrelevant
                # and reset at its next admission.
                toks2, caches2, nxt = jax.vmap(
                    one, in_axes=(None, 0, 0, 0, 0))(
                        params, toks, caches, keys, pos)
                return toks2, caches2, nxt, pos + 1, params
            return jax.jit(run, donate_argnums=(0, 1, 2, 4))

        return self.tr._watched_jit(
            ("sess_step", self.nslots, self.temperature, self.top_k),
            "jit.decode_step", build)

    # -- paged programs (block-table layout; doc/performance.md) -------
    def _prefill_fn_paged(self, plen: int, p0: int):
        """Paged admission program for (prompt length, reuse offset):
        gather the slot's b=1 dense view through ``gather_row``
        (shared-prefix content included — the copy-on-write source
        rides here), run the SUFFIX forward [p0, plen) (p0 = 0 is the
        whole-prompt chunk prefill, bitwise the dense session's), pick
        the first token with the solo RNG fold, and scatter the
        written blocks back to ``wb_ids``. A fresh (plen, p0) pair
        compiles once — exactly the per-prompt-length discipline the
        dense prefill already has."""
        pool, last, pick = self.pool, self._last, self._pick
        bs, T = pool.bs, pool.T
        k0 = p0 // bs
        nwb = -(-plen // bs) - k0              # blocks written [k0, ..)
        tr = self.tr

        def build():
            net = tr._seq_net(1, plen - p0)

            def run(params, pools, gather_row, wb_ids, toks, key):
                views = _kv_gather_views(pools, gather_row, T, bs)
                L = plen - p0
                sub = jax.lax.dynamic_slice(toks, (0, p0), (1, L))
                values, _ = net.forward(
                    params,
                    sub.reshape(1, 1, 1, L).astype(jnp.float32),
                    train=False, decode_pos=p0, kv_cache=views)
                cu = net._last_cache_updates
                first = pick(values[last].reshape(1, -1, L)[:, :, -1],
                             jax.random.fold_in(key, plen - 1)
                             ).astype(toks.dtype)
                toks = jax.lax.dynamic_update_slice(
                    toks, first[:, None], (0, plen))
                pools2 = {}
                for k in pools:
                    row = cu[k]                # (1, nkv, l_max, dh)
                    blocks = row.reshape(
                        row.shape[0], row.shape[1], T, bs,
                        row.shape[3]).transpose(2, 0, 1, 3, 4)
                    pools2[k] = pools[k].at[wb_ids].set(
                        blocks[k0:k0 + nwb].astype(pools[k].dtype))
                # params donated-and-returned (see _swap_params)
                return toks, pools2, first, params
            return jax.jit(run, donate_argnums=(0, 1, 4))

        return tr._watched_jit(
            ("sess_prefill_paged", plen, p0, T, bs, self.temperature,
             self.top_k), "jit.decode_prefill", build)

    def _admit_fn_paged(self):
        """Scatter one slot's row into the paged session state (toks /
        RNG key / position / block table) — also the RETIRE program
        with an all-zero row: a dead slot's table must point at the
        scratch block so its runaway device writes can never land in a
        block the free list re-issued to someone else."""
        def build():
            def run(btoks, bkeys, bpos, btabs, toks1, key1, pos1, tab1,
                    slot):
                btoks = jax.lax.dynamic_update_slice(
                    btoks, toks1, (slot, 0))
                bkeys = jax.lax.dynamic_update_slice(
                    bkeys, key1[None].astype(bkeys.dtype), (slot, 0))
                bpos = jax.lax.dynamic_update_slice(
                    bpos, pos1[None].astype(bpos.dtype), (slot,))
                btabs = jax.lax.dynamic_update_slice(
                    btabs, tab1[None].astype(btabs.dtype), (slot, 0))
                return btoks, bkeys, bpos, btabs
            return jax.jit(run, donate_argnums=(0, 1, 2, 3))

        return self.tr._watched_jit(
            ("sess_admit_paged", self.nslots, self.pool.T),
            "jit.decode_admit", build)

    def _step_fn_paged(self):
        """Paged decode step: gather every slot's dense view through
        its block table, run EXACTLY the dense per-row step
        (_session_row_step) vmapped over slots, then scatter each
        slot's written block back to the pool. One program per
        (bucket, table width, block, sampling) signature; the pool
        arrays ride the donate-and-return chain like the dense
        caches."""
        net1, last, pick = self._net1, self._last, self._pick
        pool = self.pool
        bs, T = pool.bs, pool.T

        def build():
            one = _session_row_step(net1, last, pick)

            def run(params, pools, toks, keys, pos, tabs):
                views = _kv_gather_views(pools, tabs, T, bs)
                toks2, views2, nxt = jax.vmap(
                    one, in_axes=(None, 0, 0, 0, 0))(
                        params, toks, views, keys, pos)
                # write back each slot's CURRENT block (the only block
                # a step writes). A dead slot's clipped index resolves
                # through its zeroed table row to the scratch block —
                # duplicate scratch writes are garbage nobody reads.
                bi = jnp.clip(pos // bs, 0, T - 1)
                wb = jnp.take_along_axis(tabs, bi[:, None], axis=1)[:, 0]
                pools2 = {}
                for k in pools:
                    v2 = views2[k]          # (S, 1, nkv, l_max, dh)
                    nkv, dh = v2.shape[2], v2.shape[4]
                    blk = jax.vmap(
                        lambda row, b: jax.lax.dynamic_slice(
                            row, (0, 0, b * bs, 0),
                            (1, nkv, bs, dh)))(v2, bi)
                    pools2[k] = pools[k].at[wb].set(
                        blk.astype(pools[k].dtype))
                return toks2, pools2, nxt, pos + 1, params
            return jax.jit(run, donate_argnums=(0, 1, 2, 4))

        return self.tr._watched_jit(
            ("sess_step_paged", self.nslots, T, bs, self.temperature,
             self.top_k), "jit.decode_step", build)

    # -- scheduling surface -------------------------------------------
    def prefill(self, slot: int, toks, seed: int) -> Tuple[int, bool]:
        """Admit one request into free ``slot``: run its b=1 prefill,
        scatter the KV/token rows into the batch state, block on and
        return ``(first_token, done)`` — ``done`` when ``n_new == 1``
        finished the request at admission. Marks ``first_token`` on the
        active trace context (the serving TTFT boundary, exactly like
        solo ``generate``)."""
        self._check_live()
        check(0 <= slot < self.nslots and not self._active[slot],
              "decode_session: slot %r is not free" % (slot,))
        toks = [int(t) for t in toks]
        plen = len(toks)
        check(plen >= 1, "decode_session: empty prompt")
        check(plen + self.n_new <= self.l_max,
              "decode_session: prompt len %d + n_new %d exceeds the "
              "net's sequence length %d" % (plen, self.n_new, self.l_max))
        params = self.tr._decode_params_current()
        t1 = np.zeros((1, self.l_max), np.int32)
        t1[0, :plen] = toks
        key = np.asarray(jax.random.PRNGKey(int(seed)))
        if self.pool is not None:
            return self._prefill_paged(slot, toks, plen, params, t1,
                                       key)
        pre_fn, admit_fn = self._prefill_fn(plen), self._admit_fn()
        try:
            t0 = time.perf_counter()
            toks1, caches1, first, new_params = pre_fn(
                params, jnp.asarray(t1), jnp.asarray(key))
            (self._toks, self._caches, self._keys_dev,
             self._pos_dev) = admit_fn(
                self._toks, self._caches, self._keys_dev,
                self._pos_dev, toks1, caches1, jnp.asarray(key),
                jnp.asarray(plen, jnp.int32),
                jnp.asarray(slot, jnp.int32))
            first = int(np.asarray(first)[0])   # blocks: the first token
        except Exception:
            # the donated decode copy may be consumed even on failure —
            # and the admit scatter DONATES the batch toks/caches, so
            # the session's device state integrity is unknown too:
            # close it (the dispatcher answers the batch and opens a
            # fresh session; a broken one must never serve again)
            self.tr._decode_params = None
            self.closed = True
            raise
        t_first = time.perf_counter()
        # the TTFT boundary mark the serving worker's trace context
        # picks up (utils/servd) — same contract as solo generate
        telemetry.mark("first_token")
        telemetry.span_event("decode.prefill", t0, t_first - t0)
        self.tr._decode_params = (self.tr._decode_params[0], new_params)
        self._active[slot] = True
        self._remaining[slot] = self.n_new - 1
        self._plen[slot] = plen
        telemetry.count("decode.tokens")
        return first, self._remaining[slot] == 0

    def _prefill_paged(self, slot: int, toks, plen: int, params, t1,
                       key) -> Tuple[int, bool]:
        """Paged admission: reserve blocks (shared prefix refcounted —
        the reused positions are NOT recomputed: prefill-once), run the
        suffix prefill + block writeback, scatter the slot row + block
        table. Raises ``KVPoolExhausted`` BEFORE any device work when
        the free list cannot cover the request — the session stays
        open (servd's ``reservable`` gate defers the request instead
        of ever reaching this)."""
        pool = self.pool
        ticket = pool.alloc.admit(toks, self.n_new)
        if ticket is None:
            raise KVPoolExhausted(
                "decode_session: kv block pool exhausted (%d free + %d "
                "retained of %d) — request needs %d fresh blocks; "
                "defer admission"
                % (pool.alloc.free_blocks, pool.alloc.retained_blocks,
                   pool.alloc.usable,
                   pool.alloc.blocks_for(plen, self.n_new)))
        ids, p0 = ticket.ids, ticket.p0
        pre_fn = self._prefill_fn_paged(plen, p0)
        admit_fn = self._admit_fn_paged()
        grow = np.zeros(pool.T, np.int32)
        grow[:len(ticket.gather_ids)] = ticket.gather_ids
        k0 = p0 // pool.bs
        nwb = -(-plen // pool.bs) - k0
        wb = np.asarray(ids[k0:k0 + nwb], np.int32)
        trow = np.zeros(pool.T, np.int32)
        trow[:len(ids)] = ids
        try:
            t0 = time.perf_counter()
            toks1, pool.pools, first, new_params = pre_fn(
                params, pool.pools, jnp.asarray(grow), jnp.asarray(wb),
                jnp.asarray(t1), jnp.asarray(key))
            (self._toks, self._keys_dev, self._pos_dev,
             self._tables_dev) = admit_fn(
                self._toks, self._keys_dev, self._pos_dev,
                self._tables_dev, toks1, jnp.asarray(key),
                jnp.asarray(plen, jnp.int32), jnp.asarray(trow),
                jnp.asarray(slot, jnp.int32))
            first = int(np.asarray(first)[0])   # blocks: the first token
        except Exception:
            # the prefill DONATED the pool arrays: their integrity is
            # unknown — the pool (and with it every session's block
            # tables and the allocator books) is dead; the dispatcher
            # opens a fresh session and the trainer rebuilds the pool
            self.tr._decode_params = None
            self.closed = True
            pool.release()
            raise
        t_first = time.perf_counter()
        # publish the FULL prompt blocks for reuse only after the
        # prefill landed (a faulted admission's blocks hold garbage)
        pool.alloc.register(ticket, toks)
        self._slot_blocks[slot] = list(ids)
        telemetry.mark("first_token")
        telemetry.span_event("decode.prefill", t0, t_first - t0)
        self.tr._decode_params = (self.tr._decode_params[0], new_params)
        self._active[slot] = True
        self._remaining[slot] = self.n_new - 1
        self._plen[slot] = plen
        telemetry.count("decode.tokens")
        return first, self._remaining[slot] == 0

    def step(self) -> List[Tuple[int, int, bool]]:
        """Advance every active slot one token (one jitted pass over the
        whole bucket); blocks on the token vector — iteration
        granularity is the scheduling seam. Returns ``[(slot, token,
        done), ...]`` for slots that still owed tokens."""
        self._check_live()
        if self.active_count == 0:
            return []
        params = self.tr._decode_params_current()
        try:
            t0 = time.perf_counter()
            if self.pool is not None:
                (self._toks, self.pool.pools, nxt, self._pos_dev,
                 new_params) = self._step_fn_paged()(
                    params, self.pool.pools, self._toks,
                    self._keys_dev, self._pos_dev, self._tables_dev)
            else:
                (self._toks, self._caches, nxt, self._pos_dev,
                 new_params) = self._step_fn()(
                    params, self._toks, self._caches, self._keys_dev,
                    self._pos_dev)
            nxt = np.asarray(nxt)               # blocks: this iteration
        except Exception:
            self.tr._decode_params = None
            self.closed = True      # batch state integrity unknown
            if self.pool is not None:
                self.pool.release()   # the step donated the pool arrays
            raise
        telemetry.span_event("decode.step", t0,
                             time.perf_counter() - t0,
                             slots=self.active_count)
        self.tr._decode_params = (self.tr._decode_params[0], new_params)
        out = []
        for s in range(self.nslots):
            if not self._active[s] or self._remaining[s] <= 0:
                continue
            self._remaining[s] -= 1
            out.append((s, int(nxt[s]), self._remaining[s] == 0))
        telemetry.count("decode.tokens", len(out))
        return out

    def retire(self, slot: int) -> None:
        """Free a finished (or abandoned) slot — the next queued request
        joins mid-decode here. Dense layout: device state is left in
        place (a dead slot's rows are never read, admission overwrites
        them). Paged layout: the slot's blocks return to the free list
        NOW (mid-decode — the reclaim the paged design exists for) and
        its table row is reset to the scratch block, so the dead
        slot's still-stepping device writes can never corrupt a block
        the free list re-issues."""
        if not 0 <= slot < self.nslots:
            return
        self._active[slot] = False
        self._remaining[slot] = 0
        self._plen[slot] = 0
        if self.pool is None or not self._slot_blocks:
            return
        ids, self._slot_blocks[slot] = self._slot_blocks[slot], None
        if not self.closed and not self.pool.closed:
            try:
                zkey = np.zeros_like(np.asarray(jax.random.PRNGKey(0)))
                (self._toks, self._keys_dev, self._pos_dev,
                 self._tables_dev) = self._admit_fn_paged()(
                    self._toks, self._keys_dev, self._pos_dev,
                    self._tables_dev,
                    jnp.zeros((1, self.l_max), jnp.int32),
                    jnp.asarray(zkey), jnp.asarray(0, jnp.int32),
                    jnp.zeros(self.pool.T, jnp.int32),
                    jnp.asarray(slot, jnp.int32))
            except Exception:
                # retire must never raise (it runs on cleanup paths):
                # a failed table reset leaves device state unknown —
                # latch this session AND the pool dead instead
                self.closed = True
                self.pool.release()
        if ids and not self.pool.closed:
            self.pool.alloc.free(ids)

    def close(self) -> None:
        """Release the device state (the per-slot caches — or, paged,
        the block-table claims on the shared pool — are the session's
        HBM footprint). Idempotent."""
        if self.pool is not None and not self.pool.closed:
            for s in range(self.nslots):
                if self._slot_blocks and self._slot_blocks[s]:
                    self.pool.alloc.free(self._slot_blocks[s])
                    self._slot_blocks[s] = None
        self.closed = True
        self._toks = None
        self._caches = None
        self._keys_dev = None
        self._pos_dev = None
        self._tables_dev = None


def create_net(net_type: int = 0) -> Trainer:
    """Factory (reference CreateNet<xpu>, src/nnet/nnet.h:99-100); net_type 0
    is the threaded trainer, the only type in the reference."""
    return Trainer()
