"""The learn task driver: train / finetune / pred / extract from a config file.

Reimplements CXXNetLearnTask (src/cxxnet_main.cpp:16-478) — same config keys,
task loop, checkpoint naming (models/%04d.model with a leading net_type int),
``continue=1`` auto-resume scan, pred/extract output formats — driving the
TPU trainer instead of GPU worker threads.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from .io import create_iterator
from .nnet.trainer import Trainer, create_net
from .utils import checkpoint as ckpt
from .utils import health
from .utils import perf
from .utils import serializer
from .utils import statusd
from .utils import compile_cache_stats, telemetry
from .utils.config import ConfigIterator


class _SeededSession:
    """Maps the dispatcher's per-request dispatch ordinal onto the conf
    sampling seed — ``seed = gen_seed + seq``, exactly what the solo
    backend passes to ``generate``; the per-slot RNG therefore keys on
    the request's dispatch ordinal, never on batch composition, and
    batched streams are token-exact vs solo dispatch."""

    def __init__(self, inner, seed0: int):
        self._inner = inner
        self._seed0 = int(seed0)
        self.nslots = inner.nslots

    @property
    def closed(self):
        return self._inner.closed

    def prefill(self, slot, toks, seq):
        return self._inner.prefill(slot, toks, self._seed0 + int(seq))

    def step(self):
        return self._inner.step()

    def retire(self, slot):
        self._inner.retire(slot)

    def free_slots(self):
        return self._inner.free_slots()

    def kv_account(self):
        # the live KV/HBM occupancy account rides through untouched —
        # servd's per-bucket account and /batchz read the REAL session
        # geometry (cache nbytes, live token extents)
        return self._inner.kv_account()

    def close(self):
        self._inner.close()


class _SlotBackendAdapter:
    """Continuous-batching slot backend over ``Trainer.decode_session``
    — what servd's batching dispatcher drives when ``serve_buckets`` is
    set (doc/serving.md "Continuous batching"). Reads the trainer
    THROUGH the task so a hot reload's swapped-in trainer serves the
    next session (the dispatcher closes every session before a
    reload — slot caches hold the old model's K/V)."""

    def __init__(self, task, buckets, kv_block: int = 0,
                 kv_pool_frac: float = 0.5, prefix_reuse: bool = True,
                 retained_frac: float = 1.0):
        self.task = task
        self.buckets = list(buckets)
        # serve_kv_block > 0 arms the PAGED decode KV cache
        # (doc/performance.md "Decode KV cache"): every session this
        # adapter opens shares one trainer-wide block pool, sized at
        # dense-equivalent capacity (largest bucket x l_max rows) and
        # clamped under serve_kv_pool_frac of the perf ledger's live
        # HBM headroom when the ledger is on
        self.kv_block = int(kv_block)
        self.kv_pool_frac = float(kv_pool_frac)
        self.prefix_reuse = bool(prefix_reuse)
        # serve_retained_frac: retired conversations stay trie-resident
        # (evictable, refcount 0) up to this fraction of the pool — the
        # multi-turn warm-cache (doc/robustness.md "Memory governance")
        self.retained_frac = float(retained_frac)

    def admits(self, toks):
        t = self.task
        l_max = t.net_trainer.net_cfg.param.input_shape[2]
        if len(toks) + t.gen_new > l_max:
            return ("prompt len %d + gen_new %d exceeds the net's "
                    "sequence length %d" % (len(toks), t.gen_new, l_max))
        return None

    def _pool(self):
        """The shared paged pool (created on first use, re-created
        across a hot reload by ``decode_kv_pool``'s params-generation
        key). None in dense mode."""
        if self.kv_block <= 0:
            return None
        t = self.task
        l_max = t.net_trainer.net_cfg.param.input_shape[2]
        cap = perf.ledger().decode_pool_cap_bytes(self.kv_pool_frac) \
            if perf.enabled() else None
        return t.net_trainer.decode_kv_pool(
            self.kv_block,
            pool_tokens=max(self.buckets) * l_max,
            prefix_reuse=self.prefix_reuse, bytes_cap=cap,
            retained_frac=self.retained_frac)

    def _live_pool(self):
        """The pool if it EXISTS and is open — the account/gate hooks
        must never create one (they run per publish, even idle)."""
        if self.kv_block <= 0:
            return None
        p = getattr(self.task.net_trainer, "_kv_pool", None)
        return None if p is None or p.closed else p

    def kv_pool_account(self):
        """servd's block-exact pool account hook (None in dense mode
        or before the first paged session)."""
        p = self._live_pool()
        return p.account() if p is not None else None

    def kv_free_blocks(self):
        """Admissible headroom for servd's gather budget (None
        disarms). Free PLUS evictable-retained blocks — reporting the
        bare free list under retention would defer requests forever
        while reclaimable memory sits parked (the evict-before-defer
        livelock)."""
        p = self._live_pool()
        return p.alloc.available_blocks if p is not None else None

    def kv_shed_retained(self, target_free):
        """servd's pressure-latch shed hook: evict retained (LRU,
        deepest-suffix-first) until the free list reaches
        ``target_free``. Returns blocks recycled (0 in dense mode)."""
        p = self._live_pool()
        if p is None:
            return 0
        return p.alloc.evict_retained(target_free=target_free)

    def kv_fresh_blocks(self, toks):
        """Blocks an admission would pull off the free list right now
        (prefix-credited) — servd pops a queued request only when this
        fits the budget, so pool exhaustion is a deterministic FIFO
        queue-wait, never a device OOM."""
        p = self._live_pool()
        if p is None:
            return None
        return p.alloc.fresh_need(len(toks), self.task.gen_new, toks)

    def session(self, bucket):
        t = self.task
        return _SeededSession(
            t.net_trainer.decode_session(
                bucket, t.gen_new, temperature=t.gen_temperature,
                top_k=t.gen_topk, kv_pool=self._pool()),
            t.gen_seed)


class LearnTask:
    def __init__(self):
        self.task = "train"
        self.net_type = 0
        self.reset_net_type = -1
        self.net_trainer: Optional[Trainer] = None
        self.itr_train = None
        self.itr_pred = None
        self.itr_evals = []
        self.eval_names: List[str] = []
        self.name_model_dir = "models"
        self.num_round = 10
        self.test_io = 0
        # profile_dir=<dir>: capture a jax profiler (xprof) trace of the
        # second training round into <dir> (the first round compiles).
        # Replaces the reference's wall-clock-only observability
        # (SURVEY.md §5 tracing/profiling).
        self.profile_dir = ""
        # telemetry_log=<path>: structured JSONL run log (spans, counters,
        # compile events; utils/telemetry.py). A Chrome-trace export is
        # written next to it (<path>.trace.json) at end of run, and the
        # end-of-run summary table prints unless silent. Multihost runs
        # put a %d rank placeholder in the path (one shard per process;
        # merge with tools/telemetry_report.py --merge).
        self.telemetry_log = ""
        # status_port=<p>: live introspection HTTP service
        # (utils/statusd.py, doc/observability.md): /metrics (Prometheus),
        # /healthz (200/503 off the watchdog + recovery state), /statusz
        # (human page), /trace (Chrome-trace ring snapshot). Port 0 binds
        # an ephemeral port (printed); -1 (default) = off. Binds loopback
        # unless status_host widens it (0.0.0.0 lets a Prometheus server
        # on another host scrape — the endpoints are unauthenticated).
        self.status_port = -1
        self.status_host = ""
        self._status_telemetry = False
        # perf_ledger=1 (default): the live program performance ledger
        # (utils/perf.py) — every compiled program gets a cost/memory
        # card (XLA cost_analysis FLOPs, memory_analysis bytes, a
        # roofline-predicted time vs the measured latency histogram),
        # rendered at /programz, as cxxnet_program_*//cxxnet_hbm_*
        # metrics, and as program_card JSONL events. Armed only when
        # telemetry is on (telemetry_log or status_port); the memory
        # tier pays one background re-compile per new program — set
        # perf_ledger=0 to card nothing.
        self.perf_ledger = 1
        # profilez_dir=<dir>: where /profilez?secs=N on-demand profiler
        # captures land (one numbered subdir per capture). Default:
        # "profilez" next to the telemetry log (or ./profilez).
        self.profilez_dir = ""
        self._perf_enabled = False
        self.silent = 0
        self.start_counter = 0
        self.max_round = 1 << 31
        self.continue_training = 0
        self.save_period = 1
        # checkpoint robustness knobs (doc/robustness.md): retention
        # (ckpt_keep_last=N keeps the newest N numbered checkpoints,
        # ckpt_keep_every=K additionally keeps every K-th as a long-horizon
        # anchor; 0 = keep all, the reference behavior), IO retries with
        # exponential backoff for flaky NFS/GCS-fuse mounts, durable
        # fsync (ckpt_fsync=0 trades durability for test speed), and the
        # SIGTERM/SIGINT emergency-checkpoint handler (preempt_save=0
        # restores the default die-on-signal behavior)
        self.ckpt_keep_last = 0
        self.ckpt_keep_every = 0
        self.ckpt_retries = 2
        self.ckpt_fsync = 1
        self.preempt_save = 1
        # training-health watchdog + automatic recovery (utils/health.py,
        # doc/robustness.md): health_monitor=1 turns on per-step
        # non-finite/loss-spike detection; on anomaly the policy rolls
        # back to the newest valid checkpoint, replays with the offending
        # batch window quarantined (nonfinite_action=rollback), suppresses
        # the bad update on device (skip), or dies with a diagnostic dump
        # (abort / retries exhausted). watchdog_timeout>0 starts a thread
        # that dumps all-thread stacks when the step loop or the prefetch
        # pipeline goes silent.
        self.health_monitor = 0
        self.nonfinite_action = "rollback"
        self.loss_spike_factor = 0.0     # 0 = spike detection off
        self.loss_spike_warmup = 20
        self.rollback_backoff = 1.0      # LR scale per rollback (1 = off)
        self.rollback_max_retries = 2
        self.watchdog_timeout = 0.0      # seconds; 0 = watchdog off
        self.watchdog_action = "warn"
        self._health: Optional[health.HealthMonitor] = None
        self._recovery: Optional[health.RecoveryPolicy] = None
        self._start_counter_conf = False
        # resume cursor recovered from a checkpoint's training-state
        # section: applied right before the train loop (after the
        # continue-path eval, which must not consume the restored rng)
        self._resume_state = None
        self._resume_batches = 0
        self._preempt: Optional[ckpt.PreemptionGuard] = None
        self._preempt_noted = False
        self._stop_training = False
        self.name_model_in = "NULL"
        self.name_pred = "pred.txt"
        self.print_step = 100
        self.extract_node_name = ""
        self.name_export = "model.stablehlo"
        self.export_batch = 0
        self.name_prompt_in = "prompts.txt"
        self.name_gen_out = "gen.txt"
        # serving frontend (utils/servd.py, doc/serving.md): task = serve
        # always runs through it (bounded admission queue + shedding,
        # deadlines, backend supervision + circuit breaker, graceful
        # drain, ADMIN reload / SIGHUP hot model reload); serve_port >= 0
        # ADDITIONALLY serves the TCP line protocol (0 = ephemeral,
        # printed; loopback unless serve_host widens it)
        self.serve_port = -1
        self.serve_host = ""
        self.serve_queue = 64
        self.serve_deadline_ms = 0.0     # 0 = no default deadline
        self.serve_drain_ms = 5000.0
        self.serve_breaker_fails = 5
        self.serve_breaker_cooldown_ms = 1000.0
        self.serve_stall_s = 120.0       # wedged-backend probe bound
        # continuous batching (doc/serving.md "Continuous batching"):
        # serve_buckets = "1,2,4,8" arms the iteration-granularity
        # batching dispatcher over Trainer.decode_session — queued
        # compatible requests coalesce (up to serve_batch_max within a
        # serve_batch_window_ms gather window) into the smallest bucket
        # that fits, and a finished sequence frees its slot to the next
        # queued request MID-DECODE. Empty = one request per decode
        # pass (the pre-batching solo dispatch).
        self.serve_buckets = ""
        self.serve_batch_max = 8
        self.serve_batch_window_ms = 2.0
        # serve_kv_block > 0 arms the PAGED decode KV cache
        # (doc/performance.md "Decode KV cache"): the batched sessions'
        # dense slot-major caches become fixed-size KV blocks of this
        # many tokens on a shared free-list pool — per-slot block
        # tables, shared-prefix prefill-once reuse (serve_prefix_reuse),
        # mid-decode block reclaim at retirement, block-budgeted
        # admission (exhaustion = deterministic queue-wait). Must
        # divide the net's sequence length. 0 (default) = dense.
        self.serve_kv_block = 0
        # fraction of the perf ledger's live HBM headroom the pool may
        # claim (bytes_cap on Trainer.decode_kv_pool; ledger off = no
        # cap, the pool sizes at dense-equivalent capacity)
        self.serve_kv_pool_frac = 0.5
        self.serve_prefix_reuse = 1
        # retained conversation cache (doc/robustness.md "Memory
        # governance"): a retired sequence's registered blocks stay
        # trie-resident at refcount 0 — evictable headroom, not a
        # commitment — so the next turn of a multi-turn conversation
        # revives its prefix instead of re-prefilling it. Cap as a
        # fraction of the usable pool; 0 restores free-instantly.
        self.serve_retained_frac = 1.0
        # KV pressure latch: free-list percentage below which servd
        # sheds retained mass proactively (cxxnet_decode_kv_pressure),
        # and the hysteresis clear threshold it sheds back up to
        self.serve_kv_pressure_pct = 10.0
        self.serve_kv_pressure_clear_pct = 25.0
        # decode-datapath observability (doc/observability.md "Decode
        # datapath"): the iteration-level scheduler flight ring behind
        # statusd /batchz (one record per decode iteration: slots,
        # admissions/retirements, queue pressure, KV utilization), and
        # the convoy threshold — a sequence aboard >=
        # serve_convoy_iters step iterations while queued work waits
        # at zero free slots latches cxxnet_decode_convoy and emits
        # ONE decode_convoy transition event per episode
        self.serve_batch_flight_cap = 256
        self.serve_convoy_iters = 64
        # compile-cliff observability (doc/observability.md "Compile
        # flight recorder"): serve_plen_buckets declares the prompt
        # lengths clients are padded/bucketed to — with serve_buckets
        # it spans the EXPECTED program grid
        # (Trainer.expected_decode_grid), arming the warm-grid
        # readiness account: cxxnet_ready_programs_pct, /compilez,
        # per-replica warm fraction on /fleetz. Empty = no declared
        # grid (readiness reads "-" everywhere; compiles still ring).
        self.serve_plen_buckets = ""
        # serve_warm_ready_pct > 0 gates readiness on the warm grid:
        # /healthz answers 503 "warming: ..." (router state WARMING —
        # probed, never routed) until that percentage of the expected
        # programs has compiled. 0 (default) keeps a cold replica
        # routable — it serves, it just pays compile cliffs in-band.
        self.serve_warm_ready_pct = 0.0
        # serving SLOs + request tracing (doc/observability.md "Request
        # tracing & SLOs"): every request gets a phase-attributed trace
        # in a bounded flight recorder (statusd /trace?request=<id>,
        # /requestz) and feeds a rolling error-budget account — a
        # request that errors, or blows slo_ttft_ms / slo_p99_ms, burns
        # budget; the cxxnet_slo_burn gauge flips at >= 1x burn rate.
        # Latency objectives default 0 = availability-only SLO.
        self.slo_ttft_ms = 0.0
        self.slo_p99_ms = 0.0
        self.slo_availability = 0.999
        self.slo_window_s = 300.0
        self.serve_flight_cap = 256
        # fleet router (utils/routerd.py, doc/serving.md "Replicated
        # serving fleet"): task = route spreads client connections over
        # the servd replicas listed in route_replicas (health-aware
        # least-loaded dispatch, retry-on-shed, rolling ADMIN reload,
        # SIGTERM fleet drain). No model is loaded — the router is a
        # pure fleet-layer process.
        self.route_port = 0              # 0 = ephemeral, printed
        self.route_host = ""
        self.route_replicas = ""         # host:port:status_port, comma-sep
        self.route_probe_ms = 200.0
        self.route_retries = 2
        self.route_stall_s = 30.0        # per-attempt response bound
        # fleet observability plane (doc/observability.md "Fleet
        # observability"): the router's per-request flight ring (every
        # routed request's candidates/attempts/retries — /requestz,
        # stitched /trace?request=<id>), the federation cadence (pull +
        # exactly merge every replica's serve histograms/SLO window
        # into cxxnet_fleet_* series; 0 = off), and the per-replica
        # outlier detector thresholds (p99 vs fleet median).
        self.route_flight_cap = 256
        self.fleet_federate_ms = 1000.0
        self.fleet_outlier_ratio = 3.0
        self.fleet_outlier_min_n = 20
        # closed-loop fleet autoscaler (doc/robustness.md "Fleet
        # autoscaling"): route_standby_replicas lists pre-provisioned
        # host:port:status_port replicas held OUT of dispatch until the
        # policy loop — fleet SLO burn >= route_scale_up_burn, or
        # queued work with zero free decode slots — admits one; an
        # admitted standby idle for route_scale_down_idle_s retires
        # back to standby. Bounds default to [primary count, total];
        # at most one action per route_scale_cooldown_s (hysteresis).
        self.route_standby_replicas = ""
        self.route_scale_min = 0         # 0 = the primary count
        self.route_scale_max = 0         # 0 = primaries + standbys
        self.route_scale_up_burn = 1.0
        self.route_scale_down_idle_s = 30.0
        self.route_scale_cooldown_s = 10.0
        # multi-tenant weighted-fair QoS (doc/serving.md "Multi-tenant
        # QoS"): route_tenants = "free:1,paid:4" arms per-tenant
        # weighted-fair admission on BOTH the router and the servd
        # replicas (share the same value fleet-wide), per-tenant
        # counters/SLO floors, and fair-share shed charging; clients
        # name their tenant with the TENANT <id> wire prefix, and
        # prefix-less clients are the serve_tenant_default tenant.
        self.route_tenants = ""
        self.serve_tenant_default = "default"
        # zero-loss failover (doc/robustness.md "Failover & hedging"):
        # route_replay re-executes a lost-contact generation attempt on
        # a surviving replica (deterministic stack: token-identical;
        # guarded by the replica reload count so a replay never splices
        # model generations); route_hedge_ms launches one duplicate of
        # a still-unanswered request after that many ms (-1 = track the
        # federated serve p99, 0 = off), first answer wins, capped at
        # route_hedge_max_pct of in-flight and denied to tenants over
        # fair share.
        self.route_replay = 1
        self.route_hedge_ms = 0.0
        self.route_hedge_max_pct = 10.0
        self.gen_new = 16
        self.gen_temperature = 0.0
        self.gen_topk = 0
        self.gen_seed = 0
        self.output_format = 1
        self.device = "tpu"
        # multi-host launch (replaces the reference's PS/MPI launcher,
        # bin/cxxnet.ps + mpi.conf): coordinator/num_worker/worker_rank
        # bring up the jax distributed runtime before device init; the
        # values also default from env (CXXNET_NUM_WORKER,
        # CXXNET_WORKER_RANK / PS_RANK)
        self.coordinator = ""
        self.num_worker = 0
        self.worker_rank = -1
        self.cfg: List[Tuple[str, str]] = [("dev", "tpu")]

    # ------------------------------------------------------------------
    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            print("Usage: <config>")
            return 0
        for name, val in ConfigIterator(argv[0], argv[1:]):
            self.set_param(name, val)
        pidx = None
        if self.coordinator or self.num_worker > 1:
            from .parallel import init_distributed
            init_distributed(
                coordinator_address=self.coordinator or None,
                num_processes=self.num_worker or None,
                process_id=self.worker_rank if self.worker_rank >= 0
                else None)
            # distributed runtime is up: tag this process's telemetry
            # shard / metric series with its rank
            import jax
            pidx = jax.process_index()
        if self.telemetry_log:
            telemetry.enable(self.telemetry_log, process_index=pidx)
            telemetry.event({"ev": "run_meta", "task": self.task,
                             "dev": self.device})
        if self.status_port >= 0:
            if not telemetry.enabled():
                # /metrics and /statusz read the telemetry registry: run
                # it in-memory (no JSONL sink) when no log was configured
                telemetry.enable(process_index=pidx)
                self._status_telemetry = True
            try:
                srv = statusd.start(self.status_port,
                                    host=self.status_host)
            except (OSError, OverflowError) as e:
                # a taken/privileged port — or an out-of-range one, which
                # socket.bind raises as OverflowError — must not kill a
                # training run over an observability feature: warn, run
                # blind
                sys.stderr.write(
                    "WARNING: statusd: cannot bind port %d (%s); live "
                    "introspection disabled for this run\n"
                    % (self.status_port, e))
                if self._status_telemetry:
                    telemetry.disable()
                    self._status_telemetry = False
            else:
                statusd.set_run_info(task=self.task, dev=self.device,
                                     config=list(self.cfg))
                if not self.silent:
                    # stderr: operational chatter — task = serve's stdout
                    # is a response stream (one line per request)
                    print("statusd: live introspection on port %d "
                          "(/metrics /healthz /livez /statusz /trace)"
                          % srv.port, file=sys.stderr, flush=True)
        if statusd.active() is not None:
            # /profilez rides statusd alone — on-demand profiling has
            # no dependency on (and must survive disabling) the ledger
            pdir = self.profilez_dir or os.path.join(
                os.path.dirname(self.telemetry_log) or ".", "profilez")
            statusd.set_profiler(perf.ProfilerCapture(pdir))
        if self.perf_ledger and telemetry.enabled():
            # the program performance ledger rides the recompile
            # detector: every program this run compiles gets a
            # cost/memory card (/programz, cxxnet_program_* series,
            # program_card JSONL events)
            perf.enable()
            self._perf_enabled = True
            statusd.set_perf(perf.ledger())
        try:
            with telemetry.span("init"):
                # the router is a pure fleet-layer process: no net, no
                # iterators, no jax use — replicas own the models
                if self.task != "route":
                    self.init()
                    if self._perf_enabled:
                        # the backend is live now: a chip the peak table
                        # does not hold stops the run here, by name
                        perf.ledger().spec = perf.current_device_spec()
            # serve's stdout carries exactly one response line per
            # request — every other line of that task goes to stderr
            chat = sys.stderr if self.task == "serve" else sys.stdout
            if self.task != "route" and not self.silent:
                print(self._device_line(), file=chat, flush=True)
            if not self.silent:
                print("initializing end, start working", file=chat)
            if self.task in ("train", "finetune"):
                self.task_train()
            elif self.task == "pred":
                self.task_predict()
            elif self.task == "pred_raw":
                self.task_predict_raw()
            elif self.task == "extract":
                self.task_extract_feature()
            elif self.task == "export":
                self.task_export()
            elif self.task == "generate":
                self.task_generate()
            elif self.task == "serve":
                self.task_serve()
            elif self.task == "route":
                self.task_route()
            if self.task != "route" and not self.silent:
                print(self._device_summary(), file=chat, flush=True)
        finally:
            if self._perf_enabled:
                # let queued card analyses land in the JSONL before the
                # summary event seals the log
                perf.drain(10.0)
                perf.disable()
                self._perf_enabled = False
            srv = statusd.active()
            if srv is not None and srv.profiler is not None:
                # an in-flight /profilez capture must be stopped and
                # JOINED before teardown — a daemon thread inside
                # native profiler code at interpreter exit segfaults,
                # turning a clean drain into rc -11
                srv.profiler.shutdown()
            if self.status_port >= 0:
                statusd.stop()
            if self.telemetry_log:
                summary = telemetry.finish(close=True)
                if summary and not self.silent:
                    self._print_telemetry_summary(summary)
            elif self._status_telemetry:
                telemetry.disable()
                self._status_telemetry = False
        return 0

    def _device_line(self) -> str:
        """What this run computes on, as jax reports it."""
        import jax
        devs = jax.devices()
        mesh = self.net_trainer.mesh if self.net_trainer else None
        return ("device: platform=%s device_kind=%r devices=%d/%d jax=%s "
                "compile_cache=%s"
                % (devs[0].platform, devs[0].device_kind,
                   mesh.devices.size if mesh is not None else 1, len(devs),
                   jax.__version__, compile_cache_stats()["dir"]))

    @staticmethod
    def _device_summary() -> str:
        """End-of-run account: each local device's peak allocation (the
        CPU backend keeps none) and the persistent compile cache's use."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        cc = compile_cache_stats()
        return ("device: peak_bytes_in_use=%s compile_cache requests=%d "
                "hits=%d misses=%d"
                % (json.dumps(peaks), cc["requests"], cc["hits"],
                   cc["misses"]))

    def set_param(self, name: str, val: str) -> None:
        if val == "default":
            return
        if name == "net_type":
            self.net_type = int(val)
        if name == "reset_net_type":
            self.reset_net_type = int(val)
        if name == "print_step":
            self.print_step = int(val)
        if name == "continue":
            self.continue_training = int(val)
        if name == "save_model":
            self.save_period = int(val)
        if name == "start_counter":
            self.start_counter = int(val)
            self._start_counter_conf = True
        if name == "model_in":
            self.name_model_in = val
        if name == "model_dir":
            self.name_model_dir = val
        if name == "num_round":
            self.num_round = int(val)
        if name == "max_round":
            self.max_round = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "task":
            self.task = val
        if name == "dev":
            self.device = val
        if name == "test_io":
            self.test_io = int(val)
        if name == "profile_dir":
            self.profile_dir = val
        if name == "telemetry_log":
            self.telemetry_log = val
        if name == "status_port":
            self.status_port = int(val)
        if name == "perf_ledger":
            self.perf_ledger = int(val)
        if name == "profilez_dir":
            self.profilez_dir = val
        if name == "status_host":
            self.status_host = val
        if name == "ckpt_keep_last":
            self.ckpt_keep_last = int(val)
        if name == "ckpt_keep_every":
            self.ckpt_keep_every = int(val)
        if name == "ckpt_retries":
            self.ckpt_retries = int(val)
        if name == "ckpt_fsync":
            self.ckpt_fsync = int(val)
        if name == "preempt_save":
            self.preempt_save = int(val)
        if name == "health_monitor":
            self.health_monitor = int(val)
        if name == "nonfinite_action":
            self.nonfinite_action = val
        if name == "loss_spike_factor":
            self.loss_spike_factor = float(val)
        if name == "loss_spike_warmup":
            self.loss_spike_warmup = int(val)
        if name == "rollback_backoff":
            self.rollback_backoff = float(val)
        if name == "rollback_max_retries":
            self.rollback_max_retries = int(val)
        if name == "watchdog_timeout":
            self.watchdog_timeout = float(val)
        if name == "watchdog_action":
            self.watchdog_action = val
        if name == "coordinator":
            self.coordinator = val
        if name == "num_worker":
            self.num_worker = int(val)
        if name == "worker_rank":
            self.worker_rank = int(val)
        if name == "serve_port":
            self.serve_port = int(val)
        if name == "serve_host":
            self.serve_host = val
        if name == "serve_queue":
            self.serve_queue = int(val)
        if name == "serve_deadline_ms":
            self.serve_deadline_ms = float(val)
        if name == "serve_drain_ms":
            self.serve_drain_ms = float(val)
        if name == "serve_breaker_fails":
            self.serve_breaker_fails = int(val)
        if name == "serve_breaker_cooldown_ms":
            self.serve_breaker_cooldown_ms = float(val)
        if name == "serve_stall_s":
            self.serve_stall_s = float(val)
        if name == "serve_buckets":
            self.serve_buckets = val
        if name == "serve_batch_max":
            self.serve_batch_max = int(val)
        if name == "serve_batch_window_ms":
            self.serve_batch_window_ms = float(val)
        if name == "serve_kv_block":
            self.serve_kv_block = int(val)
        if name == "serve_kv_pool_frac":
            self.serve_kv_pool_frac = float(val)
        if name == "serve_prefix_reuse":
            self.serve_prefix_reuse = int(val)
        if name == "serve_retained_frac":
            self.serve_retained_frac = float(val)
        if name == "serve_kv_pressure_pct":
            self.serve_kv_pressure_pct = float(val)
        if name == "serve_kv_pressure_clear_pct":
            self.serve_kv_pressure_clear_pct = float(val)
        if name == "serve_batch_flight_cap":
            self.serve_batch_flight_cap = int(val)
        if name == "serve_convoy_iters":
            self.serve_convoy_iters = int(val)
        if name == "serve_plen_buckets":
            self.serve_plen_buckets = val
        if name == "serve_warm_ready_pct":
            self.serve_warm_ready_pct = float(val)
        if name == "slo_ttft_ms":
            self.slo_ttft_ms = float(val)
        if name == "slo_p99_ms":
            self.slo_p99_ms = float(val)
        if name == "slo_availability":
            self.slo_availability = float(val)
        if name == "slo_window_s":
            self.slo_window_s = float(val)
        if name == "serve_flight_cap":
            self.serve_flight_cap = int(val)
        if name == "route_port":
            self.route_port = int(val)
        if name == "route_host":
            self.route_host = val
        if name == "route_replicas":
            self.route_replicas = val
        if name == "route_probe_ms":
            self.route_probe_ms = float(val)
        if name == "route_retries":
            self.route_retries = int(val)
        if name == "route_stall_s":
            self.route_stall_s = float(val)
        if name == "route_flight_cap":
            self.route_flight_cap = int(val)
        if name == "route_standby_replicas":
            self.route_standby_replicas = val
        if name == "route_scale_min":
            self.route_scale_min = int(val)
        if name == "route_scale_max":
            self.route_scale_max = int(val)
        if name == "route_scale_up_burn":
            self.route_scale_up_burn = float(val)
        if name == "route_scale_down_idle_s":
            self.route_scale_down_idle_s = float(val)
        if name == "route_scale_cooldown_s":
            self.route_scale_cooldown_s = float(val)
        if name == "route_tenants":
            self.route_tenants = val
        if name == "serve_tenant_default":
            self.serve_tenant_default = val
        if name == "route_replay":
            self.route_replay = int(val)
        if name == "route_hedge_ms":
            self.route_hedge_ms = float(val)
        if name == "route_hedge_max_pct":
            self.route_hedge_max_pct = float(val)
        if name == "fleet_federate_ms":
            self.fleet_federate_ms = float(val)
        if name == "fleet_outlier_ratio":
            self.fleet_outlier_ratio = float(val)
        if name == "fleet_outlier_min_n":
            self.fleet_outlier_min_n = int(val)
        if name == "extract_node_name":
            self.extract_node_name = val
        if name == "export_out":
            self.name_export = val
        if name == "export_batch":
            self.export_batch = int(val)
        if name == "prompt_in":
            self.name_prompt_in = val
        if name == "gen_out":
            self.name_gen_out = val
        if name == "gen_new":
            self.gen_new = int(val)
        if name == "gen_temperature":
            self.gen_temperature = float(val)
        if name == "gen_topk":
            self.gen_topk = int(val)
        if name == "gen_seed":
            self.gen_seed = int(val)
        if name == "output_format":
            self.output_format = 1 if val == "txt" else 0
        self.cfg.append((name, val))

    # ------------------------------------------------------------------
    def init(self) -> None:
        if self.task == "train" and self.continue_training:
            if self._sync_latest_model() == 0:
                raise RuntimeError(
                    "Init: Cannot find models for continue training. "
                    "Please specify it by model_in instead.")
            print("Init: Continue training from round %d" % self.start_counter)
            self._create_iterators()
            return
        self.continue_training = 0
        if self.name_model_in == "NULL":
            assert self.task == "train", "must specify model_in if not training"
            self.net_trainer = self._create_net()
            self.net_trainer.init_model()
        elif self.task == "finetune":
            self._copy_model()
        else:
            self._load_model()
        self._create_iterators()

    def _model_path(self, counter: int) -> str:
        return os.path.join(self.name_model_dir, "%04d.model" % counter)

    def _sync_latest_model(self) -> int:
        """Find and load the newest VALID checkpoint in model_dir.

        Replaces the reference's stop-at-first-hole scan (:135-157), which
        silently restarted from scratch whenever save_period > 1 left gaps
        in the numbering. This scan lists every <counter>.model (gaps
        fine), ranks an emergency (mid-round preemption) checkpoint by the
        progress recorded in its training-state section, verifies CRC
        framing and a full parse newest-first, quarantines anything
        corrupt to <name>.corrupt, and falls back to the next-newest valid
        file — a torn or bit-flipped checkpoint costs at most one save
        interval, never the run."""
        d = self.name_model_dir
        # candidates: (progress = (resume_counter, batches_done), path,
        # prefetched payload or None). A numbered checkpoint c resumes at
        # (c + 1, 0); the emergency file carries its cursor inside.
        cands = [((c + 1, 0), p, None, None)
                 for c, p in ckpt.scan_checkpoints(d)
                 if c >= self.start_counter]
        epath = os.path.join(d, ckpt.EMERGENCY_NAME)
        if os.path.exists(epath):
            try:
                payload, fmt = ckpt.read_verified(
                    epath, retries=self.ckpt_retries)
                st = ckpt.peek_state(payload) or {}
                prog = (int(st.get("start_counter", 0)),
                        int(st.get("batches_done", 0)))
                if prog[0] > self.start_counter:
                    cands.append((prog, epath, payload, fmt))
            except ckpt.CheckpointCorruptError as e:
                ckpt.quarantine(epath, reason=str(e))
            except OSError as e:     # unreadable even after retries:
                sys.stderr.write(    # skip, but never quarantine
                    "WARNING: cannot read %s (%s); skipping\n" % (epath, e))
        cands.sort(key=lambda t: t[0], reverse=True)
        for prog, path, payload, fmt in cands:
            try:
                if payload is None:
                    payload, fmt = ckpt.read_verified(
                        path, retries=self.ckpt_retries)
            except ckpt.CheckpointCorruptError as e:
                ckpt.quarantine(path, reason=str(e))
                continue
            except OSError as e:
                sys.stderr.write(
                    "WARNING: cannot read %s (%s); skipping\n" % (path, e))
                continue
            try:
                r = serializer.Reader(payload)
                self.net_type = r.read_int32()
                net = self._create_net()
                net.load_model(r)
                state = net.load_training_state(r)
            except Exception as e:
                if fmt == "v1":
                    # the CRC verified, so the bytes are exactly what the
                    # writer saved: this is a net/config mismatch, NOT
                    # file corruption. Abort loudly instead of
                    # destructively quarantining healthy checkpoints.
                    raise RuntimeError(
                        "checkpoint %s is intact (CRC verified) but "
                        "failed to load: %s — likely a net/updater config "
                        "mismatch with the current run; fix the config "
                        "(or remove the file) and retry" % (path, e)) \
                        from e
                # legacy file without integrity framing: a parse failure
                # here IS the corruption signal — quarantine and fall back
                ckpt.quarantine(path, reason=str(e))
                continue
            self.net_trainer = net
            self.start_counter = prog[0]
            self._resume_state = state
            self._resume_batches = prog[1] if state is not None else 0
            telemetry.event({"ev": "ckpt_restore", "path": path,
                             "counter": prog[0] - 1,
                             "batches_done": self._resume_batches})
            if not self.silent and self._resume_batches:
                # stderr: this scan also runs on a serve hot reload,
                # where stdout is the response stream
                print("Init: resuming mid-round from %s (%d batches into "
                      "round %d)" % (path, self._resume_batches,
                                     prog[0] - 1), file=sys.stderr)
            return 1
        return 0

    def _read_model_file(self, path: str) -> serializer.Reader:
        """Open a model file with integrity verification: framed files
        (this writer) are CRC-checked, footer-less seed/legacy files pass
        through untouched; a torn or bit-flipped file raises
        CheckpointCorruptError instead of deserializing garbage."""
        payload, _ = ckpt.read_verified(path, retries=self.ckpt_retries)
        return serializer.Reader(payload)

    def _load_model(self) -> None:
        base = os.path.basename(self.name_model_in)
        try:
            self.start_counter = int(base.split(".")[0])
        except ValueError:
            # proceeding with a guessed counter silently mis-numbers every
            # subsequent checkpoint (and the continue=1 scan keyed on it),
            # so for TRAINING an un-inferable name is an error unless the
            # config pins the counter explicitly. Inference tasks (pred /
            # extract / export / generate / serve) never use the counter —
            # arbitrary model names stay fine there.
            if not self._start_counter_conf and self.task == "train":
                raise ValueError(
                    "Cannot infer start_counter from model name %r: "
                    "expected '<counter>.model' (the save_model naming, "
                    "e.g. 0042.model). Rename the file or set "
                    "start_counter=<n> in the config." % self.name_model_in
                ) from None
        r = self._read_model_file(self.name_model_in)
        self.net_type = r.read_int32()
        self.net_trainer = self._create_net()
        self.net_trainer.load_model(r)
        self.start_counter += 1

    def _copy_model(self) -> None:
        r = self._read_model_file(self.name_model_in)
        self.net_type = r.read_int32()
        self.net_trainer = self._create_net()
        self.net_trainer.copy_model_from(r)

    def _is_writer(self) -> bool:
        """Multi-process: every rank serializes (fetch_global is
        collective) but exactly one touches the filesystem."""
        import jax
        return jax.process_count() <= 1 or jax.process_index() == 0

    def _write_checkpoint(self, name: str, resume_counter: int,
                          batches_done: int) -> None:
        """Serialize net_type + model + optimizer + training state and
        atomically write it with integrity framing (tmp + fsync + rename,
        CRC32 footer) and retry-with-backoff on transient IO errors."""
        t0 = time.perf_counter()
        w = serializer.Writer()
        w.write_int32(self.net_type)
        self.net_trainer.save_model(w)
        self.net_trainer.save_training_state(
            w, extra={"start_counter": int(resume_counter),
                      "batches_done": int(batches_done)})
        if not self._is_writer():
            return
        payload = w.f.getbuffer()   # zero-copy view of the BytesIO buffer
        os.makedirs(self.name_model_dir, exist_ok=True)
        ckpt.write_checkpoint(name, payload, fsync=bool(self.ckpt_fsync),
                              retries=self.ckpt_retries)
        telemetry.event({"ev": "ckpt_save", "path": name,
                         "bytes": len(payload),
                         "counter": int(resume_counter) - 1,
                         "batches_done": int(batches_done),
                         "seconds": round(time.perf_counter() - t0, 6)})

    def _save_model(self, force: bool = False) -> bool:
        """Round-boundary checkpoint; returns whether a file was written.

        The counter is checked BEFORE the increment (the reference
        incremented first, so save_period=k saved rounds k-1, 2k-1, ...
        and never round 0); the session's final round — num_round reached
        OR the max_round per-invocation cap exhausted — saves regardless
        of save_period (``force``), so a clean exit never loses work."""
        counter = self.start_counter
        self.start_counter += 1
        if self.save_period == 0:
            return False
        if counter % self.save_period != 0 and not force:
            return False
        self._write_checkpoint(self._model_path(counter),
                               self.start_counter, 0)
        if self._is_writer():
            # a numbered checkpoint strictly supersedes any emergency
            # file (its progress tuple is newer by construction)
            epath = os.path.join(self.name_model_dir, ckpt.EMERGENCY_NAME)
            try:
                if os.path.exists(epath):
                    os.remove(epath)
            except OSError:
                pass
            ckpt.gc_stale_tmp(self.name_model_dir)
            if self.ckpt_keep_last > 0:
                ckpt.apply_retention(self.name_model_dir,
                                     keep_last=self.ckpt_keep_last,
                                     keep_every=self.ckpt_keep_every)
        return True

    def _save_emergency(self, batches_done: int) -> None:
        """One mid-round emergency checkpoint at a step boundary (the
        preemption path): full state including the iterator cursor, so
        resume re-enters the SAME round and fast-forwards past the
        already-trained batches."""
        name = os.path.join(self.name_model_dir, ckpt.EMERGENCY_NAME)
        with telemetry.span("checkpoint", kind="emergency"):
            self._write_checkpoint(name, self.start_counter, batches_done)
        if not self.silent:
            print("preemption: emergency checkpoint -> %s (round %d, "
                  "batch %d)" % (name, self.start_counter - 1,
                                 batches_done))

    def _preempt_requested(self) -> bool:
        if self._preempt is None or not self._preempt.requested:
            return False
        if not self._preempt_noted:
            # the signal handler only sets flags (async-signal safety:
            # telemetry's lock may be held by this very thread when the
            # signal lands) — the loop emits the event on first notice
            self._preempt_noted = True
            telemetry.event({"ev": "preempt_signal",
                             "signum": self._preempt.signum})
        return True

    @staticmethod
    def _iter_chain_stable(it) -> bool:
        """Whether every iterator in the chain replays an identical epoch
        order after restart (exact mid-round resume; see IIterator)."""
        while it is not None:
            if not getattr(it, "stable_epoch_order", True):
                return False
            it = getattr(it, "base", None)
        return True

    def _create_net(self) -> Trainer:
        if self.reset_net_type != -1:
            self.net_type = self.reset_net_type
        net = create_net(self.net_type)
        for k, v in self.cfg:
            net.set_param(k, v)
        return net

    def _create_iterators(self) -> None:
        """Sectioned iterator parsing (reference :214-264): data=/eval=/pred=
        blocks terminated by iter=end; keys outside blocks are defaults
        applied to every iterator."""
        flag = 0
        evname = ""
        itcfg: List[Tuple[str, str]] = []
        defcfg: List[Tuple[str, str]] = []
        for name, val in self.cfg:
            if name == "data":
                flag = 1
                continue
            if name == "eval":
                evname = val
                flag = 2
                continue
            if name == "pred":
                flag = 3
                self.name_pred = val
                continue
            if name == "iter" and val == "end":
                assert flag != 0, "wrong configuration file"
                if flag == 1 and self.task not in ("pred", "export", "generate"):
                    assert self.itr_train is None, "can only have one data"
                    self.itr_train = create_iterator(itcfg)
                if flag == 2 and self.task not in ("pred", "export", "generate"):
                    self.itr_evals.append(create_iterator(itcfg))
                    self.eval_names.append(evname)
                if flag == 3 and self.task in ("pred", "pred_raw", "extract"):
                    assert self.itr_pred is None, "can only have one data:test"
                    self.itr_pred = create_iterator(itcfg)
                flag = 0
                itcfg = []
                continue
            if flag == 0:
                defcfg.append((name, val))
            else:
                itcfg.append((name, val))
        for itr in ([self.itr_train] if self.itr_train else []) + \
                ([self.itr_pred] if self.itr_pred else []) + self.itr_evals:
            for k, v in defcfg:
                itr.set_param(k, v)
            itr.init()

    # ------------------------------------------------------------------
    def task_train(self) -> None:
        start = time.monotonic()   # elapsed-time origin: never wall clock
        self._stop_training = False
        self._preempt_noted = False
        # cooperative preemption is single-process only: the stop flag is
        # per-rank, so in a multi-process run ranks would observe the
        # signal at different step boundaries and issue MISMATCHED
        # collectives (one rank in the emergency save's fetch_global,
        # another in the next train step) — a distributed hang. Multi-host
        # fleets rely on the round-boundary checkpoints instead.
        import jax
        enabled = bool(self.preempt_save) and jax.process_count() <= 1
        if self.preempt_save and not enabled and not self.silent:
            print("preempt_save: disabled (multi-process run — emergency "
                  "checkpoints require single-process training)")
        if self.health_monitor:
            self._health = health.HealthMonitor(
                spike_factor=self.loss_spike_factor,
                spike_warmup=self.loss_spike_warmup,
                gauge_names=lambda: self.net_trainer.health_gauge_names,
                gauge_limits=lambda: self.net_trainer.health_gauge_limits)
            self._recovery = health.RecoveryPolicy(
                action=self.nonfinite_action,
                backoff=self.rollback_backoff,
                max_retries=self.rollback_max_retries)
            # /healthz serves 503 while an anomaly is unresolved (the
            # watchdog heartbeat channels are consulted unconditionally)
            statusd.wire_health(self._recovery)
        wd = None
        if self.watchdog_timeout > 0:
            # the step channel arms itself at the FIRST completed batch
            # (pre-arming would false-alarm on a first-compile longer
            # than the timeout) and is paused across eval/checkpoint
            wd = health.Watchdog(self.watchdog_timeout,
                                 action=self.watchdog_action).start()
        with ckpt.PreemptionGuard(enabled=enabled) as guard:
            self._preempt = guard
            try:
                self._task_train_loop(start)
            finally:
                self._preempt = None
                if wd is not None:
                    wd.stop()

    def _task_train_loop(self, start: float) -> None:
        if self.continue_training == 0 and self.name_model_in == "NULL":
            self._save_model()
        else:
            if not self.silent:
                print("continuing from round %d" % (self.start_counter - 1))
            for itr, nm in zip(self.itr_evals, self.eval_names):
                sys.stderr.write(self.net_trainer.evaluate(itr, nm))
            sys.stderr.write("\n")
            sys.stderr.flush()
        # apply the checkpoint's training-state cursor HERE — after the
        # continue-path eval above (which draws from the rng stream and
        # would absorb a restored metric accumulator), right before the
        # first update, so a preempted run resumes bit-for-bit
        if self._resume_state is not None:
            self.net_trainer.restore_training_state(self._resume_state)
            self._resume_state = None
        if self.itr_train is None:
            return
        if self.test_io != 0:
            print("start I/O test")
        cc = self.max_round
        rounds_done = 0
        profiling = False
        while self.start_counter <= self.num_round and cc > 0:
            cc -= 1
            rnd = self.start_counter - 1
            if self.profile_dir and rounds_done == 1:
                import jax
                jax.profiler.start_trace(self.profile_dir)
                profiling = True
            statusd.update_progress(round=rnd, num_round=self.num_round)
            if not self.silent:
                print("update round %d" % rnd)
            # the session's last round — by the schedule (num_round) OR by
            # the per-invocation cap (max_round) — always checkpoints, so
            # a clean exit never loses finished rounds to save_period gaps
            last_round = (cc == 0 or self.start_counter == self.num_round)
            try:
                with telemetry.span("round", round=rnd):
                    stats = self._train_one_round(
                        start, skip_batches=self._resume_batches,
                        final_round=last_round)
            except health.TrainingAnomalyError as e:
                # rollback: restore the newest valid checkpoint and
                # re-enter the loop; the offending batch window is
                # quarantined so the replay excludes it. (A rollback
                # attempt consumes one unit of the max_round budget —
                # irrelevant at the default cap, and it bounds a
                # pathological rollback storm under a tight one.)
                self._recover_from_anomaly(e.anomaly)
                continue
            if self._recovery is not None:
                self._recovery.on_round_complete()
            self._resume_batches = 0
            t_input, t_step, t_eval, t_ckpt, n_img = stats
            wall = t_input + t_step
            if self.test_io != 0:
                print("round %d: io-only %.1f images/sec" %
                      (rnd, n_img / t_input if t_input > 0 else 0.0))
            elif not self.silent and wall > 0:
                print("round %d: input-wait %.1f%% (io %.1f img/s when "
                      "blocked, step %.1f img/s)" %
                      (rnd, 100.0 * t_input / wall,
                       n_img / t_input if t_input > 0 else float("inf"),
                       n_img / t_step if t_step > 0 else float("inf")))
            if telemetry.enabled():
                # the per-round breakdown as ONE structured event (the
                # telemetry-backed form of the prints above; per-batch
                # io.wait / train.step spans carry the fine grain)
                telemetry.event({
                    "ev": "round", "round": rnd, "images": n_img,
                    "input_wait_s": round(t_input, 6),
                    "step_s": round(t_step, 6),
                    "eval_s": round(t_eval, 6),
                    "checkpoint_s": round(t_ckpt, 6)})
                telemetry.sample_device_memory()
                telemetry.flush()
            rounds_done += 1
            if profiling:
                import jax
                jax.profiler.stop_trace()
                profiling = False
                if not self.silent:
                    print("profiler trace written to %s (device time by "
                          "layer and phase: python tools/trace_layers.py %s)"
                          % (self.profile_dir, self.profile_dir))
            if self._stop_training:
                telemetry.event({"ev": "preempt_exit", "round": rnd})
                if not self.silent:
                    print("preemption: checkpointed, exiting cleanly "
                          "(resume with continue=1)")
                return
        if not self.silent:
            print("updating end, %.0f sec in all"
                  % (time.monotonic() - start))

    def _train_one_round(self, start: float, skip_batches: int = 0,
                         final_round: bool = False):
        """One pass over itr_train + eval + checkpoint. Returns the round
        breakdown (input-wait, step, eval, checkpoint seconds, images) —
        the input-starvation probe the reference treats as a design axis
        (thread_buffer.h:22): time blocked on the input pipeline
        (next+value) vs in the device step is the number that says
        whether the loader keeps up."""
        sample_counter = 0
        hm = self._health
        rnd = self.start_counter - 1
        self.net_trainer.start_round(self.start_counter)
        self.itr_train.before_first()
        t_input = t_step = t_eval = t_ckpt = 0.0
        n_img = 0
        batches_done = 0
        if skip_batches:
            # mid-round resume: replay the round's prefix without compute
            # (base iterators seek O(1); buffered chains drain batches)
            if not self._iter_chain_stable(self.itr_train):
                print("WARNING: the training iterator's epoch order is "
                      "not replay-stable (windowed shuffle); mid-round "
                      "resume is approximate — some prefix batches may "
                      "repeat or be skipped this round")
            with telemetry.span("resume.skip", batches=skip_batches):
                batches_done = self.itr_train.skip(skip_batches)
            sample_counter = batches_done
            if not self.silent:
                print("resume: fast-forwarded %d batches into round %d"
                      % (batches_done, self.start_counter - 1))
        while True:
            t0 = time.perf_counter()
            if self._recovery is not None \
                    and self._recovery.should_skip(rnd, batches_done):
                # quarantined batch window (a prior anomaly): fast-forward
                # the data cursor past it without training — the rollback
                # replay's exclusion of the offending batch
                if self.itr_train.skip(1) == 0:
                    break
                telemetry.event({"ev": "health_skip_batch", "round": rnd,
                                 "batch": batches_done})
                telemetry.count("health/batches_skipped")
                sample_counter += 1
                batches_done += 1
                continue
            if not self.itr_train.next():
                break
            batch = self.itr_train.value()
            t1 = time.perf_counter()
            t_input += t1 - t0
            # span recorded post hoc so the terminal (exhausted) next()
            # never shows up as an io.wait — the span totals match the
            # round event's input_wait_s exactly
            telemetry.span_event("io.wait", t0, t1 - t0)
            if self.test_io == 0:
                self.net_trainer.update(batch)
                t_step += time.perf_counter() - t1
                if hm is not None:
                    # check the PREVIOUS step's health vector (pipelined:
                    # its compute is done, the fetch cannot stall us)
                    anomaly = hm.observe(rnd, batches_done,
                                         self.net_trainer.last_health)
                    if anomaly is not None:
                        self._on_anomaly(anomaly)
            health.beat("train.step")
            n_img += batch.batch_size - batch.num_batch_padd
            sample_counter += 1
            batches_done += 1
            statusd.update_progress(batch=batches_done)
            if sample_counter % self.print_step == 0 and not self.silent:
                print("round %8d:[%8d] %.0f sec elapsed" %
                      (self.start_counter - 1, sample_counter,
                       time.monotonic() - start))
            if self.test_io == 0 and self._preempt_requested():
                # preemption at a step boundary: one emergency checkpoint
                # with the iterator cursor, then a clean exit — the
                # user-level checkpoint/restore recovery contract
                t0 = time.perf_counter()
                bad = hm.drain() if hm is not None else None
                if bad is not None:
                    # never persist post-anomaly state as a checkpoint:
                    # resume restarts from the last numbered one instead
                    telemetry.event({"ev": "health_anomaly_at_preempt",
                                     "anomaly": bad.id})
                else:
                    self._save_emergency(batches_done)
                t_ckpt = time.perf_counter() - t0
                self._stop_training = True
                return t_input, t_step, t_eval, t_ckpt, n_img
        # eval + checkpoint are legitimately step-silent: disarm the step
        # channel so the watchdog doesn't false-alarm (re-armed by the
        # next round's first batch)
        health.pause("train.step")
        if hm is not None:
            # settle the round's health BEFORE eval/checkpoint: a bad
            # final step must roll back, never be saved as "good"
            anomaly = hm.drain()
            if anomaly is not None:
                self._on_anomaly(anomaly)
        if self.test_io == 0:
            t0 = time.perf_counter()
            # one write for the whole line: whatever else lands on stderr
            # while the evals run (a compiler's log line) must not split
            # the "[round] metric:value ..." record tools parse
            line = "[%d]" % self.start_counter
            if not self.itr_evals:
                with telemetry.span("eval", dataset="train"):
                    line += self.net_trainer.evaluate(None, "train")
            for itr, nm in zip(self.itr_evals, self.eval_names):
                with telemetry.span("eval", dataset=nm):
                    line += self.net_trainer.evaluate(itr, nm)
            sys.stderr.write(line + "\n")
            sys.stderr.flush()
            t_eval = time.perf_counter() - t0
        t0 = time.perf_counter()
        with telemetry.span("checkpoint"):
            saved = self._save_model(force=final_round)
        t_ckpt = time.perf_counter() - t0
        if self._preempt_requested():
            # signal arrived during eval/checkpoint: the round is complete;
            # if save_period skipped the round checkpoint, write an
            # emergency one so no finished work is lost
            if not saved:
                self._save_emergency(0)
            self._stop_training = True
        return t_input, t_step, t_eval, t_ckpt, n_img

    # ------------------------------------------------------------------
    # training-health recovery (utils/health.py, doc/robustness.md)
    def _on_anomaly(self, anomaly) -> None:
        """Route a detected anomaly through the recovery policy: 'skip'
        logs and continues (the device guard already suppressed the bad
        update), 'rollback' unwinds the round via TrainingAnomalyError,
        'abort' dumps diagnostics and dies."""
        decision = self._recovery.decide(anomaly)
        if decision == "skip":
            # the on-device guard only suppresses NON-FINITE steps; a
            # finite loss spike in skip mode was APPLIED to the weights
            # and is logged, not suppressed — event + counter say which
            suppressed = anomaly.kind == "nonfinite"
            if not self.silent:
                print("health: %s -> %s" % (
                    anomaly.describe(),
                    "skip (update suppressed on device)" if suppressed
                    else "logged (skip mode does not suppress finite "
                         "spikes)"))
            telemetry.event({"ev": "health_skip", "anomaly": anomaly.id,
                            "kind": anomaly.kind, "round": anomaly.round,
                             "batch": anomaly.batch,
                             "suppressed": suppressed})
            telemetry.count("health/updates_suppressed" if suppressed
                            else "health/spikes_logged")
            return
        if not self.silent:
            print("health: %s -> %s" % (anomaly.describe(), decision))
        if decision == "abort":
            reason = ("nonfinite_action=abort" if self.nonfinite_action ==
                      "abort" else "%d consecutive rollbacks exhausted "
                      "rollback_max_retries=%d" % (self._recovery.retries,
                                                   self.rollback_max_retries))
            telemetry.event({"ev": "health_abort", "anomaly": anomaly.id,
                             "reason": reason})
            health.dump_diagnostics(reason, anomaly)
            raise RuntimeError(
                "health: training anomaly (%s); aborting: %s"
                % (anomaly.describe(), reason))
        raise health.TrainingAnomalyError(anomaly)

    def _recover_from_anomaly(self, anomaly) -> None:
        """Roll back to the newest valid checkpoint and let the train
        loop re-enter the restored round; the offending batch window is
        excluded on replay (RecoveryPolicy.should_skip) and the
        accumulated LR backoff is re-applied to the fresh trainer."""
        pol = self._recovery
        telemetry.event({"ev": "health_rollback", "anomaly": anomaly.id,
                         "retry": pol.retries, "round": anomaly.round,
                         "batch": anomaly.batch, "lr_scale": pol.lr_scale,
                         "skip": pol.skipped()})
        telemetry.count("health/rollbacks")
        health.pause("train.step")   # checkpoint reload is step-silent
        self._health.reset_pending()
        self._resume_state = None
        self._resume_batches = 0
        # any valid checkpoint qualifies: drop the scan floor before the
        # rescan (it normally encodes "don't resume older than the run's
        # own progress", which is exactly what a rollback must undo)
        self.start_counter = 0
        if self._sync_latest_model() == 0:
            raise RuntimeError(
                "health: anomaly at round %d batch %d requires a rollback "
                "but no valid checkpoint exists in %s (save_model=0?); "
                "cannot recover" % (anomaly.round, anomaly.batch,
                                    self.name_model_dir))
        if not self.silent:
            print("health: rolled back to round %d (retry %d/%d, lr x%g)"
                  % (self.start_counter - 1, pol.retries,
                     self.rollback_max_retries, pol.lr_scale))
        if self._resume_state is not None:
            self.net_trainer.restore_training_state(self._resume_state)
            self._resume_state = None
        if not self._iter_chain_stable(self.itr_train):
            print("WARNING: the training iterator's epoch order is not "
                  "replay-stable (windowed shuffle); the rollback replay "
                  "sees a different batch order and the quarantined "
                  "window is positional — recovery is approximate")
        self.net_trainer.scale_lr(pol.lr_scale)
        # recovery complete (checkpoint restored, replay armed): flip
        # /healthz back to 200
        pol.resolve()

    @staticmethod
    def _print_telemetry_summary(summary: dict) -> None:
        """End-of-run telemetry table: top spans by total time, compile
        cost, counters — the at-a-glance per-phase breakdown."""
        spans = summary.get("spans", {})
        print("---- telemetry summary ----")
        if spans:
            print("%-18s %7s %10s %9s %9s %9s" %
                  ("span", "count", "total_s", "p50_ms", "p99_ms",
                   "max_ms"))
            for name, a in sorted(spans.items(),
                                  key=lambda kv: -kv[1]["total_s"])[:12]:
                print("%-18s %7d %10.3f %9.2f %9.2f %9.2f" %
                      (name, a["count"], a["total_s"], a["p50_ms"],
                       a["p99_ms"], a["max_ms"]))
        if summary.get("step_time_ms") is not None:
            print("step time: %.2fms (mean of train.period)"
                  % summary["step_time_ms"])
        comp = summary.get("compiles", {})
        if comp.get("count"):
            print("compiles: %d (%.2fs) %s" %
                  (comp["count"], comp["total_s"],
                   " ".join("%s=%d" % kv
                            for kv in sorted(comp["by_cause"].items()))))
        for name, v in sorted(summary.get("counters", {}).items()):
            print("counter %-24s %s" % (name, v))
        for name, v in sorted(summary.get("gauges", {}).items()):
            print("gauge   %-24s %s" % (name, v))

    def task_predict(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        print("start predicting...")
        with open(self.name_pred, "w") as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                pred = self.net_trainer.predict(batch)
                assert batch.num_batch_padd < batch.batch_size, \
                    "num batch pad must be smaller"
                for v in pred[: len(pred) - batch.num_batch_padd]:
                    fo.write("%g\n" % v)
        print("finished prediction, write into %s" % self.name_pred)

    def task_predict_raw(self) -> None:
        """task = pred_raw: one space-separated row of raw output-node
        values (class probabilities after softmax) per input row. The
        reference ACCEPTS this task string in its iterator wiring
        (src/cxxnet_main.cpp:242) and its kaggle_bowl example depends on
        it (example/kaggle_bowl/pred.conf + make_submission.py), but its
        task dispatch never implements it — implemented here the way the
        submission maker expects."""
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        print("start predicting (raw)...")
        with open(self.name_pred, "w") as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                out = self.net_trainer.extract_feature(batch, "top[-1]")
                out = np.asarray(out).reshape(out.shape[0], -1)
                assert batch.num_batch_padd < batch.batch_size, \
                    "num batch pad must be smaller"
                for row in out[: len(out) - batch.num_batch_padd]:
                    fo.write(" ".join("%g" % v for v in row) + "\n")
        print("finished prediction, write into %s" % self.name_pred)

    def task_extract_feature(self) -> None:
        assert self.itr_pred is not None, \
            "must specify a predict iterator to generate predictions"
        assert self.extract_node_name != "", \
            "extract node name must be specified in task extract_feature."
        print("start predicting...")
        name_meta = self.name_pred + ".meta"
        nrow = 0
        dshape = (0, 0, 0)
        mode = "w" if self.output_format else "wb"
        with open(self.name_pred, mode) as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value()
                pred = self.net_trainer.extract_feature(
                    batch, self.extract_node_name)
                sz = pred.shape[0] - batch.num_batch_padd
                nrow += sz
                for j in range(sz):
                    row = pred[j].reshape(-1)
                    if self.output_format:
                        fo.write(" ".join("%g" % x for x in row) + " \n")
                    else:
                        fo.write(row.astype("<f4").tobytes())
                if sz:
                    dshape = pred.shape[1:]
        with open(name_meta, "w") as fm:
            fm.write("%d,%d,%d,%d\n" % (nrow, dshape[0], dshape[1], dshape[2]))
        print("finished prediction, write into %s" % self.name_pred)

    def task_generate(self) -> None:
        """task = generate: KV-cached continuation of token-id prompts
        (sequence nets; model_in required). ``prompt_in`` is a text file
        of space-separated integer token ids, one prompt per line —
        lines may have DIFFERENT lengths (ragged batch; per-row prompt
        lengths feed Trainer.generate's prompt_lens). ``gen_new`` tokens
        are appended per prompt with greedy decoding by default
        (gen_temperature / gen_topk / gen_seed for sampling) and written
        to ``gen_out``, one space-separated id line per prompt."""
        rows = []
        with open(self.name_prompt_in) as f:
            for line in f:
                line = line.split()
                if line:
                    rows.append([int(t) for t in line])
        assert rows, "prompt_in %s has no prompts" % self.name_prompt_in
        from .utils.servd import embed_vocab
        vocab = embed_vocab(self.net_trainer.net)
        if vocab:
            bad = [t for r in rows for t in r if not 0 <= t < vocab]
            assert not bad, (
                "prompt_in contains token ids outside the net's "
                "vocab_size %d (e.g. %d) — wrong tokenizer? (jit would "
                "silently clamp them)" % (vocab, bad[0]))
        lens = [len(r) for r in rows]
        max_p = max(lens)
        prompts = [r + [0] * (max_p - len(r)) for r in rows]
        out = self.net_trainer.generate(
            prompts, self.gen_new, temperature=self.gen_temperature,
            top_k=self.gen_topk, seed=self.gen_seed, prompt_lens=lens)
        with open(self.name_gen_out, "w") as fo:
            for row in out:
                fo.write(" ".join(str(int(t)) for t in row) + "\n")
        print("generated %d x %d tokens into %s"
              % (out.shape[0], out.shape[1], self.name_gen_out))

    def task_serve(self) -> None:
        """task = serve: online serving through the production frontend
        (utils/servd.py, doc/serving.md). The stdin/stdout line loop of
        the reference-era task is still the default surface — each input
        line is one prompt of space-separated token ids, answered with
        one line (the gen_new-token continuation, or ``ERR <class>``) —
        but every request now runs through the frontend engine: backend
        supervision (an exception answers ``ERR backend`` and feeds the
        circuit breaker instead of killing the loop), per-request
        deadlines (``DEADLINE <ms>`` prefix / serve_deadline_ms),
        admission control, hot model reload (``ADMIN reload`` / SIGHUP
        picks up the newest valid checkpoint in model_dir between
        requests), and graceful drain on SIGTERM/SIGINT (finish accepted
        requests within serve_drain_ms, flush telemetry, exit 0).
        serve_port >= 0 additionally serves concurrent TCP clients with
        the same line protocol; after stdin EOF the process then keeps
        serving until a drain signal. The KV-cached decode program is
        compiled per prompt-length signature and reused across requests
        (bucket client-side prompt lengths to keep compilations few);
        batch is 1 per request by design — the latency-bound serving
        case; use task = generate for offline batch throughput."""
        import signal

        from .utils import servd

        vocab = servd.embed_vocab(self.net_trainer.net)
        statusd.update_progress(served=0, errors=0)

        def backend(toks, seq):
            # reads net_trainer THROUGH self so a hot reload's swapped-in
            # trainer serves the very next request
            return self.net_trainer.generate(
                [toks], self.gen_new, temperature=self.gen_temperature,
                top_k=self.gen_topk, seed=self.gen_seed + seq)[0]

        def newest_ckpt_sig():
            # identity of the newest checkpoint candidates (newest
            # numbered + emergency file): any new or rewritten file
            # changes the signature, so a matching one means a reload
            # would re-load the very model being served
            paths = []
            cands = ckpt.scan_checkpoints(self.name_model_dir)
            if cands:
                paths.append(cands[-1][1])
            epath = os.path.join(self.name_model_dir,
                                 ckpt.EMERGENCY_NAME)
            if os.path.exists(epath):
                paths.append(epath)
            sig = []
            for p in paths:
                try:
                    fst = os.stat(p)
                except OSError:
                    continue
                sig.append((os.path.realpath(p), fst.st_mtime_ns,
                            fst.st_size))
            return tuple(sig)

        # seed the signature when the model being served IS the newest
        # candidate, so an operator's blind SIGHUP loop starts out free
        served_sig = [newest_ckpt_sig()]
        if served_sig[0] and not (
                len(served_sig[0]) == 1 and served_sig[0][0][0]
                == os.path.realpath(self.name_model_in)):
            served_sig[0] = None

        def reload_fn():
            # a reload that would re-load the checkpoint already being
            # served must be FREE: rebuilding the trainer discards every
            # compiled decode program — the recompile latency cliff —
            # for a bit-identical model
            sig = newest_ckpt_sig()
            if sig and sig == served_sig[0]:
                if not self.silent:
                    print("serve: reload skipped — already serving the "
                          "newest checkpoint", file=sys.stderr,
                          flush=True)
                return False
            # newest valid checkpoint in model_dir (the continue=1 scan:
            # CRC-verified newest-first, corrupt files quarantined);
            # nothing valid = keep the current model and say so
            prev_counter = self.start_counter
            self.start_counter = 0
            if self._sync_latest_model() == 0:
                self.start_counter = prev_counter
                sys.stderr.write(
                    "WARNING: serve reload: no valid checkpoint in %s; "
                    "keeping the current model\n" % self.name_model_dir)
                return False
            served_sig[0] = sig
            # the old model's paged KV pool holds old-weight K/V and
            # the reload path has already closed every session on it:
            # release NOW so the HBM account reads 0 until the first
            # post-reload admission rebuilds the pool (the account must
            # never report freed memory as allocated)
            try:
                self.net_trainer.release_kv_pool()
            except Exception:
                pass
            if not self.silent:
                # stderr: stdout is the response stream (one line per
                # request — a banner there desyncs positional clients)
                print("serve: reloaded model (round %d checkpoint)"
                      % (self.start_counter - 1), file=sys.stderr,
                      flush=True)
            return True

        # SLO error-budget account: every completed request feeds it;
        # the burn-rate gauges ride /metrics and the transition events
        # ride the telemetry log (report exit-2 gate)
        slo = statusd.SLOTracker(
            ttft_ms=self.slo_ttft_ms, p99_ms=self.slo_p99_ms,
            availability=self.slo_availability,
            window_s=self.slo_window_s)
        # multi-tenant QoS: the SAME route_tenants value the fleet
        # router enforces (the fairness verdict must agree fleet-wide),
        # with one SLOTracker per tenant — same objectives, separate
        # error budgets, so a noisy tenant's sheds cannot burn the
        # victim's window
        tenants = servd.parse_tenants(self.route_tenants)
        slo_tenants = {}
        if tenants:
            if self.serve_tenant_default not in tenants:
                tenants[self.serve_tenant_default] = 1.0
            slo_tenants = {
                t: statusd.SLOTracker(
                    ttft_ms=self.slo_ttft_ms, p99_ms=self.slo_p99_ms,
                    availability=self.slo_availability,
                    window_s=self.slo_window_s)
                for t in tenants}
            if not self.silent:
                print("serve: multi-tenant QoS on (%s; default %r)"
                      % (",".join("%s:%g" % kv
                                  for kv in sorted(tenants.items())),
                         self.serve_tenant_default),
                      file=sys.stderr, flush=True)
        # continuous batching: serve_buckets = "1,2,4,8" swaps the
        # one-request-per-pass worker for the iteration-granularity
        # batching dispatcher over Trainer.decode_session (the slot
        # counts are the compile-once bucket grid — keep it short, each
        # bucket is one decode-step program)
        slot_backend = None
        bucket_list = [int(x) for x in
                       str(self.serve_buckets).replace(",", " ").split()]
        if bucket_list:
            slot_backend = _SlotBackendAdapter(
                self, bucket_list, kv_block=self.serve_kv_block,
                kv_pool_frac=self.serve_kv_pool_frac,
                prefix_reuse=bool(self.serve_prefix_reuse),
                retained_frac=self.serve_retained_frac)
            if not self.silent:
                print("serve: continuous batching on (buckets %s, "
                      "batch_max %d, window %.1fms%s)"
                      % (sorted(set(bucket_list)), self.serve_batch_max,
                         self.serve_batch_window_ms,
                         ", paged kv block %d" % self.serve_kv_block
                         if self.serve_kv_block > 0 else ""),
                      file=sys.stderr, flush=True)
        fe = servd.ServeFrontend(
            backend, queue_size=self.serve_queue,
            deadline_ms=self.serve_deadline_ms,
            drain_ms=self.serve_drain_ms,
            breaker_fails=self.serve_breaker_fails,
            breaker_cooldown_ms=self.serve_breaker_cooldown_ms,
            stall_after_s=self.serve_stall_s,
            vocab=vocab, reload_fn=reload_fn,
            slo=slo, flight_cap=self.serve_flight_cap,
            slot_backend=slot_backend,
            batch_max=self.serve_batch_max,
            batch_window_ms=self.serve_batch_window_ms,
            batch_flight_cap=self.serve_batch_flight_cap,
            convoy_iters=self.serve_convoy_iters,
            kv_pressure_pct=self.serve_kv_pressure_pct,
            kv_pressure_clear_pct=self.serve_kv_pressure_clear_pct,
            tenants=tenants, tenant_default=self.serve_tenant_default,
            slo_tenants=slo_tenants)
        fe.start()
        # request introspection: /trace?request=<id> + /requestz serve
        # the flight ring, /metrics + /statusz the SLO account (no-ops
        # without status_port)
        statusd.set_flight_recorder(fe.flight)
        statusd.set_slo(slo)
        statusd.set_slo_tenants(slo_tenants)
        if slot_backend is not None:
            # decode-datapath observability (doc/observability.md
            # "Decode datapath"): /batchz + the cxxnet_decode_* series
            # + the /trace slot-Gantt lanes serve from the frontend's
            # iteration ring, and the perf ledger charges the live
            # decode KV cache against HBM headroom
            statusd.set_batch(fe)
            perf.set_decode_kv(fe.decode_kv_bytes)
            plen_list = [int(x) for x in
                         str(self.serve_plen_buckets)
                         .replace(",", " ").split()]
            if plen_list and getattr(self, "_perf_enabled", False):
                # warm-grid readiness (doc/observability.md "Compile
                # flight recorder"): declare the expected program grid
                # on the ledger (serve_buckets x serve_plen_buckets x
                # admit/step variants), wire the frontend's warm
                # account to it — cxxnet_ready_programs_pct, the ADMIN
                # warm_programs/expected_programs ints the router
                # federates, and (serve_warm_ready_pct > 0) the
                # "warming" health gate
                perf.ledger().set_expected_grid(
                    self.net_trainer.expected_decode_grid(
                        bucket_list, plen_list,
                        temperature=self.gen_temperature,
                        top_k=self.gen_topk,
                        kv_block=self.serve_kv_block))
                fe.set_warm_account(
                    perf.ledger().readiness,
                    ready_pct=self.serve_warm_ready_pct)
        if self.serve_port >= 0:
            try:
                port = fe.listen(self.serve_port, host=self.serve_host)
            except (OSError, OverflowError) as e:
                # like the statusd bind guard: a taken port must not kill
                # serving — warn, fall back to the stdin surface
                sys.stderr.write(
                    "WARNING: servd: cannot bind port %d (%s); TCP "
                    "serving disabled, stdin loop only\n"
                    % (self.serve_port, e))
            else:
                if not self.silent:
                    # stderr, not stdout: stdout carries exactly one
                    # response line per stdin request
                    print("servd: serving on port %d (line protocol; "
                          "DEADLINE/ADMIN prefixes, ERR classes — "
                          "doc/serving.md)" % port, file=sys.stderr,
                          flush=True)
        # /healthz flips 503 while draining or breaker-open (readiness);
        # /livez only dies with the worker thread (liveness)
        statusd.register_probe("serving", fe.health_probe)
        statusd.register_probe("serving.worker", fe.liveness_probe,
                               liveness=True)
        wd = None
        if self.watchdog_timeout > 0:
            # the serve.accept / serve.worker channels beat from the
            # frontend's threads (paused across idle periods)
            wd = health.Watchdog(self.watchdog_timeout,
                                 action=self.watchdog_action).start()
        old_hup = None
        try:
            # SIGHUP = hot reload; the handler only sets a flag
            # (async-signal safety, like PreemptionGuard)
            old_hup = signal.signal(
                signal.SIGHUP, lambda s, f: fe.request_reload())
        except (AttributeError, ValueError, OSError):
            pass                 # no SIGHUP (platform) / not main thread
        stdin_done = threading.Event()

        def pump():
            reply = lambda text: print(text, flush=True)  # noqa: E731
            for line in sys.stdin:
                # wait=True keeps responses in request order — the stdin
                # contract — while still running the full engine path
                fe.submit(line.rstrip("\n"), reply, wait=True)
            stdin_done.set()

        threading.Thread(target=pump, name="cxn-serve-stdin",
                         daemon=True).start()
        try:
            with ckpt.PreemptionGuard() as guard:
                # serve until drain is requested; a stdin EOF ends a
                # pipe-driven run unless TCP clients are being served
                # (then only the signal does — sleep, don't spin)
                while not guard.requested:
                    if stdin_done.is_set() and not fe.listening:
                        break
                    time.sleep(0.1)
                if guard.requested:
                    telemetry.event({"ev": "preempt_signal",
                                     "signum": guard.signum})
                    if not self.silent:
                        print("serve: drain requested (signal %s); "
                              "finishing accepted requests"
                              % guard.signum, file=sys.stderr, flush=True)
        finally:
            stats = fe.drain()
            if wd is not None:
                wd.stop()
            if old_hup is not None:
                try:
                    signal.signal(signal.SIGHUP, old_hup)
                except (ValueError, OSError):
                    pass
        telemetry.event(dict({"ev": "serve_done"}, **stats))
        print("served %d prompts (%d request errors)"
              % (stats["served"], stats["errors"]),
              file=sys.stderr, flush=True)
        if stats["shed"] or stats["deadline"]:
            print("  shed %d, deadline-expired %d (of %d accepted)"
                  % (stats["shed"], stats["deadline"], stats["accepted"]),
                  file=sys.stderr, flush=True)

    def task_route(self) -> None:
        """task = route: the replicated-fleet router (utils/routerd.py,
        doc/serving.md "Replicated serving fleet"). Speaks the exact
        servd line protocol on ``route_port`` and spreads client
        connections over the ``task = serve`` replicas listed in
        ``route_replicas`` (``host:serve_port:status_port``, comma
        separated): health-aware dispatch fed by each replica's statusd
        ``/healthz`` + load gauges, least-loaded power-of-two-choices,
        transparent retry of never-dispatched sheds on another replica
        within the client's remaining DEADLINE budget, dead-replica
        ejection with exponential-backoff re-probe, and fleet-level
        ``ADMIN reload`` (or SIGHUP) rolled across replicas one drain
        window at a time — capacity never drops below N-1. SIGTERM/
        SIGINT drains the router (in-flight routed requests finish,
        counters reconcile, exit 0); replicas are their own processes
        and drain on their own signals."""
        import signal

        from .utils import routerd, servd

        replicas = routerd.parse_replicas(self.route_replicas)
        assert replicas, \
            "task = route needs route_replicas = host:port:status_port[,...]"
        route_tenants = servd.parse_tenants(self.route_tenants)
        if route_tenants and self.serve_tenant_default \
                not in route_tenants:
            route_tenants[self.serve_tenant_default] = 1.0
        router = routerd.Router(
            replicas, probe_ms=self.route_probe_ms,
            retries=self.route_retries, stall_s=self.route_stall_s,
            drain_ms=self.serve_drain_ms,
            flight_cap=self.route_flight_cap,
            federate_ms=self.fleet_federate_ms,
            outlier_ratio=self.fleet_outlier_ratio,
            outlier_min_n=self.fleet_outlier_min_n,
            standby_replicas=self.route_standby_replicas,
            scale_min=self.route_scale_min,
            scale_max=self.route_scale_max,
            scale_up_burn=self.route_scale_up_burn,
            scale_down_idle_s=self.route_scale_down_idle_s,
            scale_cooldown_s=self.route_scale_cooldown_s,
            tenants=self.route_tenants,
            tenant_default=self.serve_tenant_default,
            replay=bool(self.route_replay),
            hedge_ms=self.route_hedge_ms,
            hedge_max_pct=self.route_hedge_max_pct,
            # the router's own per-tenant windows (door sheds): same
            # objectives as the replicas', merged into the federated
            # per-tenant burn account
            slo_tenants={
                t: statusd.SLOTracker(
                    ttft_ms=self.slo_ttft_ms, p99_ms=self.slo_p99_ms,
                    availability=self.slo_availability,
                    window_s=self.slo_window_s)
                for t in route_tenants})
        router.start()
        port = router.listen(self.route_port, host=self.route_host)
        # one synchronous sweep so /fleetz and the first dispatches see
        # probed state, not optimism (a dead replica listed in the conf
        # is ejected before traffic arrives)
        router.probe_now()
        statusd.set_fleet(router)
        statusd.set_slo_tenants(router.slo_tenants)
        # the routing flight ring: /requestz lists every routed
        # request's attempts, /trace?request=<id> stitches the
        # cross-process trace (set_fleet makes /trace prefer the
        # stitched view on this process)
        statusd.set_flight_recorder(router.flight)
        statusd.register_probe("routing", router.health_probe)
        statusd.register_probe("routing.prober", router.liveness_probe,
                               liveness=True)
        if not self.silent:
            up = sum(1 for r in router._replicas
                     if r.state == routerd.UP)
            print("routerd: routing on port %d over %d replicas "
                  "(%d up; servd line protocol — doc/serving.md)"
                  % (port, len(replicas), up), file=sys.stderr,
                  flush=True)
        wd = None
        if self.watchdog_timeout > 0:
            wd = health.Watchdog(self.watchdog_timeout,
                                 action=self.watchdog_action).start()
        # SIGHUP = rolling fleet reload. The handler only sets a flag
        # (request_rolling_reload takes locks — not async-signal-safe);
        # the main loop converts it.
        hup_flag = {"on": False}
        old_hup = None
        try:
            old_hup = signal.signal(
                signal.SIGHUP,
                lambda s, f: hup_flag.update(on=True))
        except (AttributeError, ValueError, OSError):
            pass                 # no SIGHUP (platform) / not main thread
        try:
            with ckpt.PreemptionGuard() as guard:
                while not guard.requested:
                    if hup_flag["on"]:
                        hup_flag["on"] = False
                        if router.request_rolling_reload() \
                                and not self.silent:
                            print("route: rolling fleet reload "
                                  "started (SIGHUP)", file=sys.stderr,
                                  flush=True)
                    time.sleep(0.1)
                telemetry.event({"ev": "preempt_signal",
                                 "signum": guard.signum})
                if not self.silent:
                    print("route: fleet drain requested (signal %s)"
                          % guard.signum, file=sys.stderr, flush=True)
        finally:
            stats = router.drain()
            if wd is not None:
                wd.stop()
            if old_hup is not None:
                try:
                    signal.signal(signal.SIGHUP, old_hup)
                except (ValueError, OSError):
                    pass
        telemetry.event(dict({"ev": "route_done"}, **stats))
        print("routed %d requests (%d served, %d errors, %d shed, "
              "%d deadline, %d retries)"
              % (stats["accepted"], stats["served"], stats["errors"],
                 stats["shed"], stats["deadline"], stats["retries"]),
              file=sys.stderr, flush=True)

    def task_export(self) -> None:
        """task = export: AOT-compile the inference forward (params baked
        in) into a self-contained StableHLO artifact at export_out.
        extract_node_name selects a named node / top[-k] (default: the
        last node, the pred surface); export_batch overrides the batch
        dimension (default batch_size; -1 = symbolic batch, one artifact
        serves any n >= 1). Reload anywhere with
        cxxnet_tpu.api.load_exported — serving needs jax only."""
        blob = self.net_trainer.export_forward(
            node_name=self.extract_node_name,
            batch_size=self.export_batch)
        with open(self.name_export, "wb") as fo:
            fo.write(blob)
        print("exported forward (%d bytes) into %s"
              % (len(blob), self.name_export))


def main(argv: List[str]) -> int:
    return LearnTask().run(argv)
