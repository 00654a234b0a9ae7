"""Benchmark harness for the BASELINE configs.

Default (no args): AlexNet ImageNet-shape training throughput — prints ONE
JSON line {"metric", "value", "unit", "vs_baseline"} for the driver.
Baseline target (BASELINE.md): 2000 images/sec/chip on AlexNet.

`python bench.py all` additionally benches the other BASELINE configs
(GoogLeNet, MNIST MLP/conv, kaggle_bowl-shaped net), one JSON line each —
the AlexNet headline line is always printed LAST so drivers reading the
final line see the headline metric.

`python bench.py pipeline` benches the END-TO-END input pipeline: a real
JPEG imgbinx corpus is packed on the fly and AlexNet trains from
imgbinx -> decode pool -> augment -> threadbuffer, measuring pipeline-fed
img/s next to (a) the device-resident synthetic number and (b) the
io-only rate (iterating without training — the reference's test_io mode,
src/cxxnet_main.cpp:363-376). Pipeline-fed throughput is bounded by the
host's JPEG decode rate whenever the decode pool has too few cores to
keep the chip busy; the io-only line tells you which side bound the run.

Measures the steady-state train step (forward + backward + SGD update) with
device-resident input — the input pipeline overlaps H2D via the
threadbuffer prefetcher in real training, and per-step train metrics are
off (eval_train=0) as they would be for a throughput run. bf16 mixed
precision (the TPU-native recipe). The final value fetch forces a full
device sync so async dispatch cannot inflate the number (it waits like
block_until_ready and also proves the value is fetchable).

Every mode but `io` runs in ONE process that must find a TPU: on any
other platform it exits non-zero before measuring anything, so no row
of this file is ever a CPU number under a device metric's name.
"""

import json
import os
import sys
import time

import numpy as np


def _timed_rate(tr, b, steps, units_per_step):
    """Shared measurement protocol: 3-step warmup, then two timed passes
    reporting the better. The sync is a value-fetch of the first param
    tensor (first layer may be weightless): every step donates and
    rewrites the params, so the fetch waits for the last step exactly as
    block_until_ready would."""
    import jax.numpy as jnp

    def sync():
        float(jnp.sum(next(v for p in tr.params for v in p.values())))

    for _ in range(3):
        tr.update(b)
    sync()
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            tr.update(b)
        sync()
        best = max(best, steps * units_per_step
                   / (time.perf_counter() - t0))
    return best


def _throughput(tr, shape, nclass, batch, steps=30):
    import jax
    from cxxnet_tpu.io.data import DataBatch

    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(rs.rand(batch, *shape).astype(np.float32))
    b.label = jax.device_put(
        rs.randint(0, nclass, (batch, 1)).astype(np.float32))
    b.batch_size = batch
    return _timed_rate(tr, b, steps, batch)


BF16 = "eval_train = 0\ncompute_dtype = bfloat16\n"


def bench_alexnet():
    from cxxnet_tpu.models import alexnet_trainer
    batch = 256
    tr = alexnet_trainer(batch_size=batch, input_hw=227, dev="tpu",
                         extra_cfg=BF16)
    ips = _throughput(tr, (3, 227, 227), 1000, batch)
    return {
        "metric": "alexnet_imagenet_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(ips / 2000.0, 4),
    }


def bench_alexnet_b1024():
    """Large-batch variant: fills the MXU better (measured ~18.3k img/s on
    v5e). Kept as a secondary line; the batch-256 headline stays the
    cross-round comparable (the reference recipe's batch,
    example/ImageNet/ImageNet.conf)."""
    from cxxnet_tpu.models import alexnet_trainer
    batch = 1024
    tr = alexnet_trainer(batch_size=batch, input_hw=227, dev="tpu",
                         extra_cfg=BF16)
    ips = _throughput(tr, (3, 227, 227), 1000, batch, steps=15)
    return {"metric": "alexnet_imagenet_b1024_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": round(ips / 2000.0, 4)}


def bench_googlenet():
    from cxxnet_tpu.models import googlenet_trainer
    batch = 128
    tr = googlenet_trainer(batch_size=batch, input_hw=224, dev="tpu",
                           extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch)
    return {"metric": "googlenet_imagenet_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": round(ips / 2000.0, 4)}


def bench_googlenet_b256():
    """Large-batch inception variant: the b128 headline under-fills the
    MXU on the narrow tower convs (22.7% MFU, tools/roofline.py); doubling
    the batch doubles the per-tower matmul rows at constant weight
    traffic. Secondary line — b128 stays the cross-round comparable."""
    from cxxnet_tpu.models import googlenet_trainer
    batch = 256
    tr = googlenet_trainer(batch_size=batch, input_hw=224, dev="tpu",
                           extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch, steps=15)
    return {"metric": "googlenet_imagenet_b256_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": round(ips / 2000.0, 4)}


def bench_resnet():
    from cxxnet_tpu.models import resnet_trainer
    batch = 128
    tr = resnet_trainer(batch_size=batch, input_hw=224, dev="tpu",
                        extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch)
    # no reference baseline: the family postdates the reference
    return {"metric": "resnet18_imagenet_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_mobilenet():
    from cxxnet_tpu.models import mobilenet_trainer
    batch = 256
    tr = mobilenet_trainer(batch_size=batch, input_hw=224, dev="tpu",
                           extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch)
    # no reference baseline: depthwise separability postdates the ref
    return {"metric": "mobilenet_imagenet_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_vgg():
    from cxxnet_tpu.models import vgg_trainer
    batch = 64
    tr = vgg_trainer(batch_size=batch, input_hw=224, dev="tpu",
                     remat=1, extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch)
    # no reference baseline: VGG postdates the reference's example set
    return {"metric": "vgg16_imagenet_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def _conf_trainer(netconfig, shape, batch, extra=""):
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    conf = (netconfig +
            "input_shape = %s\n" % ",".join(str(s) for s in shape) +
            "batch_size = %d\ndev = tpu\neta = 0.1\n" % batch + extra)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


MNIST_MLP = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 100
  init_sigma = 0.01
layer[+1] = sigmoid
layer[+1] = fullc:fc2
  nhidden = 10
  init_sigma = 0.01
layer[+0] = softmax
netconfig = end
"""

MNIST_CONV = """
netconfig = start
layer[0->1] = conv:cv1
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = 32
  random_type = xavier
layer[1->2] = max_pooling
  kernel_size = 3
  stride = 2
layer[2->3] = flatten
layer[3->3] = dropout
  threshold = 0.5
layer[3->4] = fullc:fc1
  nhidden = 100
  init_sigma = 0.01
layer[4->5] = sigmoid
layer[5->6] = fullc:fc2
  nhidden = 10
  init_sigma = 0.01
layer[6->6] = softmax
netconfig = end
"""

BOWL = """
netconfig = start
layer[0->1] = conv:c1
  kernel_size = 5
  nchannel = 32
  random_type = xavier
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = conv:c2
  kernel_size = 3
  nchannel = 64
  random_type = xavier
layer[4->5] = relu
layer[5->6] = max_pooling
  kernel_size = 3
  stride = 2
layer[6->7] = flatten
layer[7->8] = fullc:f1
  nhidden = 256
  random_type = xavier
layer[8->9] = relu
layer[9->10] = fullc:f2
  nhidden = 121
  random_type = xavier
layer[10->10] = softmax
netconfig = end
"""


def _bench_lm(metric, L, batch, steps, attn_extra=""):
    """Shared LM bench harness: build the L-long decoder (vocab 8192,
    dim 512, 8 heads, 4 blocks), feed a device-resident random token
    batch, report tokens/sec via the common _timed_rate protocol."""
    import jax
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.io.data import DataBatch
    tr = transformer_lm_trainer(
        vocab=8192, seq=L, batch_size=batch, dim=512, nhead=8, nlayer=4,
        dev="tpu", extra_cfg=BF16, attn_extra=attn_extra)
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(
        rs.randint(0, 8192, (batch, 1, 1, L)).astype(np.float32))
    b.label = jax.device_put(
        rs.randint(0, 8192, (batch, L)).astype(np.float32))
    b.batch_size = batch
    best = _timed_rate(tr, b, steps=steps, units_per_step=batch * L)
    return {"metric": metric, "value": round(best, 1),
            "unit": "tokens/sec/chip", "vs_baseline": None}


def bench_vit():
    """ViT-S/16-shaped (224x224, patch 16, dim 384, 12 blocks, 6 heads)
    training throughput — the DSL-composed vision-transformer family
    (patch-embed conv -> im2seq -> RoPE attention blocks); no reference
    baseline (the family postdates the reference)."""
    from cxxnet_tpu.models import vit_trainer
    batch = 128
    tr = vit_trainer(n_class=1000, image_hw=224, patch=16, dim=384,
                     nhead=6, nlayer=12, ffn_mult=4, batch_size=batch,
                     dev="tpu", extra_cfg=BF16)
    ips = _throughput(tr, (3, 224, 224), 1000, batch, steps=15)
    return {"metric": "vit_s16_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_transformer_lm():
    """Long-context LM training throughput: tokens/sec at L=2048 bf16
    (flash attention path; no reference baseline — the reference is a CNN
    framework with no sequence axis, SURVEY.md §5)."""
    return _bench_lm("transformer_lm_L2048_tokens_per_sec_per_chip",
                     L=2048, batch=8, steps=20)


def bench_transformer_lm_long():
    """Long-context recipe: L=8192 bf16 with GQA (nkvhead=2), sliding
    window 1024, and RoPE — the flash-attention + window path end to end
    (no reference baseline; the reference is a CNN framework)."""
    return _bench_lm(
        "transformer_lm_L8192_gqa_window_tokens_per_sec_per_chip",
        L=8192, batch=2, steps=10,
        attn_extra="nkvhead = 2\nattn_window = 1024\nrope = 1\n")


def bench_alexnet_infer():
    """Inference throughput (the reference's `pred` task shape): forward
    + on-device argmax via predict_device, batch 256 bf16. Calls are
    chained with ONE value-fetch sync per timed pass — the serving-loop
    regime (results stay on device; a per-call host fetch would measure
    the host round trip, which bench_alexnet_latency_b1 covers)."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.models import alexnet_trainer
    from cxxnet_tpu.io.data import DataBatch
    batch = 256
    tr = alexnet_trainer(batch_size=batch, input_hw=227, dev="tpu",
                         extra_cfg=BF16)
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(rs.rand(batch, 3, 227, 227).astype(np.float32))
    b.label = jax.device_put(np.zeros((batch, 1), np.float32))
    b.batch_size = batch
    out = None
    for _ in range(3):
        out = tr.predict_device(b)
    float(jnp.sum(out))
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            out = tr.predict_device(b)
        float(jnp.sum(out))   # one sync for the chained pass
        best = max(best, n * batch / (time.perf_counter() - t0))
    return {"metric": "alexnet_infer_images_per_sec_per_chip",
            "value": round(best, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_alexnet_latency_b1():
    """Serving latency: single-image (batch=1) forward, milliseconds per
    call including the host round trip — the number a latency-sensitive
    deployment of the exported artifact sees (throughput rows measure the
    opposite regime). Median of 50 calls after warmup."""
    import jax
    from cxxnet_tpu.models import alexnet_trainer
    from cxxnet_tpu.io.data import DataBatch
    tr = alexnet_trainer(batch_size=1, input_hw=227, dev="tpu",
                         extra_cfg=BF16)
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = jax.device_put(rs.rand(1, 3, 227, 227).astype(np.float32))
    b.label = jax.device_put(np.zeros((1, 1), np.float32))
    b.batch_size = 1
    for _ in range(5):
        tr.predict(b)
    times = []
    for _ in range(50):
        t0 = time.perf_counter()
        tr.predict(b)   # device_get inside forces the sync
        times.append(time.perf_counter() - t0)
    med_ms = sorted(times)[len(times) // 2] * 1e3
    return {"metric": "alexnet_infer_latency_batch1",
            "value": round(med_ms, 3), "unit": "ms",
            "vs_baseline": None}


def _lm_decode(metric, batch, L, plen, extra=""):
    """Serving decode throughput: KV-cached greedy generation
    (Trainer.generate) — tokens/sec across `batch` streams from `plen`
    to the full context. Judged against the analytic HBM-bandwidth bound
    (`tools/roofline.py --decode`), not MFU."""
    from cxxnet_tpu.models import transformer_lm_trainer
    tr = transformer_lm_trainer(vocab=8192, seq=L, batch_size=batch,
                                dim=512, nhead=8, nlayer=4, dev="tpu",
                                extra_cfg=BF16 + extra)
    rs = np.random.RandomState(0)
    prompts = rs.randint(0, 8192, (batch, plen))
    n_new = L - plen
    tr.generate(prompts, n_new)   # compile + warm
    t0 = time.perf_counter()
    tr.generate(prompts, n_new)
    dt = time.perf_counter() - t0
    return {"metric": metric,
            "value": round(batch * n_new / dt, 2), "unit": "tokens/sec",
            "vs_baseline": None}


def bench_lm_decode():
    return _lm_decode("lm_decode_tokens_per_sec_per_chip", 8, 2048, 64)


def bench_lm_decode_b1():
    """Interactive single-stream decode: the latency-bound serving case."""
    return _lm_decode("lm_decode_b1_tokens_per_sec_per_chip", 1, 2048, 64)


def bench_lm_decode_long():
    """Long-context GQA + sliding-window serving: the window caps the KV
    read so the bound stays flat past L=1024."""
    return _lm_decode(
        "lm_decode_L8192_tokens_per_sec_per_chip", 8, 8192, 64,
        extra="nkvhead = 2\nattn_window = 1024\nrope = 1\n")


def bench_lm_decode_chunked():
    """The flash-decode while-loop (decode_chunk): reads only the live
    cache prefix per step instead of the full static length — the dense
    path's known ~2x read overhead (doc/performance.md decode roofline).
    Token-exactness is pinned in tests/test_decode.py; this row decides
    whether the while-loop overhead beats the saved bandwidth on-chip."""
    return _lm_decode("lm_decode_chunked_tokens_per_sec_per_chip",
                      8, 2048, 64, extra="decode_chunk = 256\n")


def bench_lm_decode_long_chunked():
    """Chunked decode under the long-context recipe: with a 1024 window
    the loop reads at most 5 x 256-row chunks per step regardless of
    position, vs the dense path's masked 8192-row read."""
    return _lm_decode(
        "lm_decode_L8192_chunked_tokens_per_sec_per_chip", 8, 8192, 64,
        extra="nkvhead = 2\nattn_window = 1024\nrope = 1\n"
              "decode_chunk = 256\n")


def bench_lm_decode_b1_chunked():
    """Interactive single-stream decode with flash-decode: batch 1 is
    where the dense full-cache read is the largest share of bytes/token
    (decode roofline: b1 sits at 42% of bound with the dense read)."""
    return _lm_decode("lm_decode_b1_chunked_tokens_per_sec_per_chip",
                      1, 2048, 64, extra="decode_chunk = 256\n")


def bench_serve_load():
    """Serve-under-load: concurrent clients against the servd frontend
    (utils/servd.py) on loopback — end-to-end per-request p50/p99
    latency (socket + admission queue + KV-cached decode) and shed rate,
    so tools/bench_compare.py gates serving-latency regressions
    (unit ms = direction-aware, higher is worse) the way it already
    gates throughput. One prompt-length signature: the decode program
    compiles once and every request rides the cached fast path."""
    import socket
    import threading
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import servd
    from cxxnet_tpu.utils.telemetry import percentile
    vocab, L, plen, n_new = 8192, 256, 32, 16
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)

    def backend(toks, seq):
        return tr.generate(np.asarray([toks]), n_new)[0]

    rs = np.random.RandomState(0)
    prompt = rs.randint(0, vocab, plen).tolist()
    backend(prompt, 0)              # compile the (1, plen) decode once
    fe = servd.ServeFrontend(backend, queue_size=64)
    fe.start()
    port = fe.listen(0)
    nclients, per = 4, 8
    line = " ".join(map(str, prompt))
    lats, nshed, nerr, nsent = [], [0], [0], [0]
    lock = threading.Lock()

    def client():
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=300) as c:
            f = c.makefile("r")
            for _ in range(per):
                t0 = time.perf_counter()
                c.sendall((line + "\n").encode())
                resp = f.readline()
                dt = time.perf_counter() - t0
                with lock:
                    nsent[0] += 1
                    if not resp:
                        # connection torn down: an error, NOT a ~0ms
                        # latency sample that would deflate the gated
                        # p50/p99 of a degraded run
                        nerr[0] += 1
                    elif resp.startswith("ERR busy"):
                        nshed[0] += 1       # shed = admission rejection
                    elif resp.startswith("ERR"):
                        nerr[0] += 1        # backend/deadline: not shed
                    else:
                        lats.append(dt)
                if not resp:
                    break

    threads = [threading.Thread(target=client) for _ in range(nclients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fe.drain()
    lats.sort()
    # server-side phase attribution from the flight recorder: TTFT
    # (accept -> first token, the trainer's prefill/decode split) and
    # queue wait — the sub-fields the batching PR's before/after is
    # graded on (bench_compare gates them via "<metric>.<field>" keys)
    recs = [r for r in fe.flight.list() if r["outcome"] == "served"]
    ttfts = sorted(r["ttft_s"] for r in recs
                   if r.get("ttft_s") is not None)
    qwaits = sorted(r["phases"]["queue_wait"] for r in recs)
    # rates over requests actually ISSUED: a client whose connection died
    # stops early, and its unsent requests must not pad the denominator
    # (a fully degraded run would otherwise understate its error rate)
    total = max(1, nsent[0])
    return {"metric": "serve_loopback_p99_latency_ms",
            "value": round(1e3 * percentile(lats, 99), 3) if lats
            else None,
            "unit": "ms", "vs_baseline": None,
            "p50_ms": round(1e3 * percentile(lats, 50), 3) if lats
            else None,
            "ttft_p99_ms": round(1e3 * percentile(ttfts, 99), 3)
            if ttfts else None,
            "queue_wait_p99_ms": round(1e3 * percentile(qwaits, 99), 3)
            if qwaits else None,
            "shed_rate": round(nshed[0] / float(total), 4),
            "error_rate": round(nerr[0] / float(total), 4),
            "requests": nsent[0]}


class _PagedSlotBackend:
    """The serve benches' slot backend over the PAGED decode KV cache
    (doc/performance.md "Decode KV cache") — the same pool/session/
    admission-gate hook surface learn_task's production adapter
    exposes, minus the task indirection: sessions share one
    ``Trainer.decode_kv_pool``, admission is block-budgeted through
    ``kv_fresh_blocks``/``kv_free_blocks``, and ``kv_pool_account``
    feeds the /batchz + prefix_hit_rate sub-fields. The dispatcher's
    seq ordinal doubles as the sampling seed (greedy in the benches,
    so it only names the stream)."""

    def __init__(self, tr, buckets, n_new, block, pool_tokens,
                 prefix_reuse=True, retained_frac=1.0):
        self.tr = tr
        self.buckets = list(buckets)
        self.n_new = int(n_new)
        self.block = int(block)
        self.pool_tokens = int(pool_tokens)
        self.prefix_reuse = bool(prefix_reuse)
        self.retained_frac = float(retained_frac)

    def _pool(self):
        return self.tr.decode_kv_pool(self.block,
                                      pool_tokens=self.pool_tokens,
                                      prefix_reuse=self.prefix_reuse,
                                      retained_frac=self.retained_frac)

    def _live_pool(self):
        p = getattr(self.tr, "_kv_pool", None)
        return None if p is None or p.closed else p

    def session(self, nslots):
        return self.tr.decode_session(nslots, self.n_new,
                                      kv_pool=self._pool())

    def kv_pool_account(self):
        p = self._live_pool()
        return p.account() if p is not None else None

    def kv_free_blocks(self):
        # free + evictable-retained: under retention the gather budget
        # must see parked blocks as headroom (evict-before-defer)
        p = self._live_pool()
        return p.alloc.available_blocks if p is not None else None

    def kv_shed_retained(self, target_free):
        p = self._live_pool()
        if p is None:
            return 0
        return p.alloc.evict_retained(target_free=target_free)

    def kv_fresh_blocks(self, toks):
        p = self._live_pool()
        if p is None:
            return None
        return p.alloc.fresh_need(len(toks), self.n_new, toks)


def bench_serve_throughput():
    """Continuous-batching serving throughput: a closed-loop N-client
    flood through the BATCHING frontend (utils/servd.py slot_backend
    path over Trainer.decode_session) — the requests/sec/chip lever the
    batching arc is graded on, next to serve_loopback_p99_latency_ms.
    Headline value is rps (HIGHER is better — bench_compare keys the
    direction off the non-ms unit and the *_rps name); sub-fields carry
    the latency tail (p50/p99), the measured mean batch occupancy
    (sequences per decode pass — the coalescing proof), and the
    roofline decode-step bound (tokens/s) from the performance ledger:
    the ceiling the measured tokens/s reports against.

    The flood runs over the PAGED KV cache (serve_kv_block semantics;
    doc/performance.md "Decode KV cache"): ``kv_live_pct`` is the
    before/after headline — the dense PR 13 baseline read ~14% (every
    slot owned an l_max row); paged, waste is bounded by block
    granularity, so the mean should sit near 100 x live_rows /
    (blocks_held x block). ``prefix_hit_rate`` (identical prompts
    here, so it climbs fast after the first admission) and the
    exhaustion-defer count ride along, null-safe on a dense run."""
    import socket
    import threading
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import perf, servd, telemetry
    from cxxnet_tpu.utils.telemetry import percentile
    vocab, L, plen, n_new = 8192, 256, 32, 16
    bucket = 4
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)

    backend = _PagedSlotBackend(tr, [bucket], n_new, block=16,
                                pool_tokens=bucket * L)
    fe = servd.ServeFrontend(None, slot_backend=backend,
                             queue_size=64, batch_max=bucket,
                             batch_window_ms=5.0,
                             # size the iteration ring for the WHOLE
                             # flood: at degraded occupancy the run is
                             # up to ~(nclients*per+1)*n_new
                             # iterations, and a silently truncated
                             # window would bias kv_live_pct /
                             # queue_age_p99_ms newest-ward exactly
                             # when the bench should catch a regression
                             batch_flight_cap=4096)
    fe.start()
    port = fe.listen(0)
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, vocab, plen).tolist()
    line = " ".join(map(str, prompt))
    # warm the bucket: compiles (prefill + step + admit) happen here,
    # not inside the measured window
    from cxxnet_tpu.utils.servd import _ask
    _ask(port, line, timeout=600.0)
    occ0 = (fe._occ_iters, fe._occ_slots)
    iter0 = fe._iter_ord
    # bracket the flood for the autopsy/books sub-fields: records
    # before this mark are warm-up (whose verdicts MAY carry
    # compile_stall), and the auditor's violation count is deltaed so
    # other rows in this process cannot leak into this one
    nrec0 = len(fe.flight.list())
    telemetry.audit_sweep()
    books0 = telemetry.auditor().snapshot()["violations"]
    nclients, per = 6, 6
    lats, nerr, nsent = [], [0], [0]
    lock = threading.Lock()

    def client():
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=600) as c:
            f = c.makefile("r")
            for _ in range(per):
                t0 = time.perf_counter()
                c.sendall((line + "\n").encode())
                resp = f.readline()
                dt = time.perf_counter() - t0
                with lock:
                    nsent[0] += 1
                    if not resp or resp.startswith("ERR"):
                        nerr[0] += 1
                    else:
                        lats.append(dt)
                if not resp:
                    break

    threads = [threading.Thread(target=client) for _ in range(nclients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    d_iters = fe._occ_iters - occ0[0]
    d_slots = fe._occ_slots - occ0[1]
    # the decode-datapath observability sub-fields (null-safe): mean
    # live-KV utilization and queue-age p99 over the flood window's
    # iteration records — kv_live_pct is THE paged-KV before/after
    # baseline (ROADMAP item 2: the reclaimable padding+dead-slot
    # share), queue_age_p99_ms the admission-pressure tail
    win = [r for r in fe.batch_flight.list() if r["iter"] > iter0]
    kv_pcts = [r["kv_live_pct"] for r in win
               if r.get("kv_live_pct") is not None]
    qages = sorted(r["queue_age_s"] for r in win
                   if r.get("queue_age_s") is not None)
    # the paged-pool account (null-safe: None end to end on a dense
    # backend) — prefix_hit_rate is token-weighted, recomputed by the
    # snapshot from the allocator's lifetime tallies
    snap = fe.batch_snapshot() or {}
    pool = snap.get("pool") or {}
    # the autopsy plane over the flood window: every flood request's
    # stamped verdict (warm bucket -> compile_stall share exactly 0),
    # plus the conservation-law auditor's verdict — swept BEFORE
    # drain, while this frontend's laws are still registered
    telemetry.audit_sweep()
    books1 = telemetry.auditor().snapshot()["violations"]
    allrec = fe.flight.list()                # newest first
    floodrec = allrec[:max(0, len(allrec) - nrec0)]
    verdicts = {}
    stall_s = wall_s = 0.0
    for rec in floodrec:
        aut = rec.get("autopsy")
        if not aut:
            continue
        verdicts[aut["primary"]] = verdicts.get(aut["primary"], 0) + 1
        stall_s += float((aut.get("causes") or {})
                         .get("compile_stall", 0.0))
        wall_s += float(aut.get("wall_s") or 0.0)
    fe.drain()
    lats.sort()
    total = max(1, nsent[0])
    return {"metric": "serve_throughput_rps",
            "value": round(len(lats) / wall, 3) if lats and wall > 0
            else None,
            "unit": "req/s", "vs_baseline": None,
            "p50_ms": round(1e3 * percentile(lats, 50), 3) if lats
            else None,
            "p99_ms": round(1e3 * percentile(lats, 99), 3) if lats
            else None,
            "mean_batch_occupancy": round(d_slots / float(d_iters), 3)
            if d_iters else None,
            "decode_bound_tokens_per_s":
            perf.decode_bound_tokens_per_s(n_new),
            "kv_live_pct": round(sum(kv_pcts) / len(kv_pcts), 2)
            if kv_pcts else None,
            "prefix_hit_rate": pool.get("prefix_hit_rate"),
            "kv_blocks_total": pool.get("blocks_total"),
            "kv_defers": pool.get("alloc_failures"),
            "queue_age_p99_ms": round(1e3 * percentile(qages, 99), 3)
            if qages else None,
            "error_rate": round(nerr[0] / float(total), 4),
            # the self-explaining-telemetry sub-fields: the flood's
            # primary-verdict histogram, the compile-stall share of
            # its wall time (0.0 on this warm bucket — any rise means
            # the flood paid a cliff), and the auditor's violation
            # delta across the row (0 on a healthy run; gated by
            # bench_compare as worse-when-higher)
            "autopsy_verdicts": verdicts or None,
            "autopsy_compile_stall_pct":
            round(100.0 * stall_s / wall_s, 3) if wall_s > 0 else None,
            "books_violations": books1 - books0,
            "requests": nsent[0], "bucket": bucket}


def bench_serve_prefix_reuse():
    """Shared-system-prompt serving flood over the paged KV cache: N
    closed-loop clients send prompts that share one long system
    prefix (full blocks) and differ only in a short user tail — the
    chatbot/agent fleet shape. The shared blocks prefill ONCE
    (refcounted in the pool's prefix trie); every later admission
    gathers them and computes only its tail, so the prefill phase
    shrinks by the hit rate. Headline is rps (HIGHER better);
    ``prefix_hit_rate`` should approach 100 x shared/plen once the
    flood is warm, and ``ttft_p99_ms`` carries the time-to-first-token
    win the reuse buys. CPU-measurable (tiny model, greedy), null-safe
    (a dense backend would simply report null prefix fields)."""
    import socket
    import threading
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import servd
    from cxxnet_tpu.utils.telemetry import percentile
    vocab, L, n_new = 8192, 256, 8
    block, shared, tail = 16, 48, 8        # plen 56: 3 shared blocks
    bucket = 4
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)
    backend = _PagedSlotBackend(tr, [bucket], n_new, block=block,
                                pool_tokens=bucket * L)
    fe = servd.ServeFrontend(None, slot_backend=backend,
                             queue_size=64, batch_max=bucket,
                             batch_window_ms=5.0,
                             batch_flight_cap=4096)
    fe.start()
    port = fe.listen(0)
    rs = np.random.RandomState(7)
    system = rs.randint(0, vocab, shared).tolist()

    def prompt_line(i):
        # one shared system prefix, a per-request user tail: request i
        # reuses blocks request 0 loaded (the prefill-once contract)
        tl = ((np.arange(tail) * 31 + i * 7) % vocab).tolist()
        return " ".join(map(str, system + tl))

    # warm: the first admission prefills the WHOLE prompt and compiles
    # the (plen, 0) program; the second compiles the (plen, shared)
    # suffix program — both outside the measured window
    from cxxnet_tpu.utils.servd import _ask
    _ask(port, prompt_line(10001), timeout=600.0)
    _ask(port, prompt_line(10002), timeout=600.0)
    nclients, per = 6, 4
    lats, ttfts, nerr, nsent = [], [], [0], [0]
    lock = threading.Lock()

    def client(ci):
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=600) as c:
            f = c.makefile("r")
            for j in range(per):
                t0 = time.perf_counter()
                c.sendall((prompt_line(ci * per + j) + "\n").encode())
                resp = f.readline()
                dt = time.perf_counter() - t0
                with lock:
                    nsent[0] += 1
                    if not resp or resp.startswith("ERR"):
                        nerr[0] += 1
                    else:
                        lats.append(dt)
                if not resp:
                    break

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(nclients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    # TTFT from the request flight ring: the prefill phase is where
    # prefix reuse pays (only the tail is computed)
    ttfts = [1e3 * r["ttft_s"] for r in fe.flight.list()
             if r.get("ttft_s") is not None]
    snap = fe.batch_snapshot() or {}
    pool = snap.get("pool") or {}
    fe.drain()
    lats.sort()
    total = max(1, nsent[0])
    return {"metric": "serve_prefix_reuse_rps",
            "value": round(len(lats) / wall, 3) if lats and wall > 0
            else None,
            "unit": "req/s", "vs_baseline": None,
            "p50_ms": round(1e3 * percentile(lats, 50), 3) if lats
            else None,
            "p99_ms": round(1e3 * percentile(lats, 99), 3) if lats
            else None,
            "ttft_p99_ms": round(percentile(sorted(ttfts), 99), 3)
            if ttfts else None,
            "prefix_hit_rate": pool.get("prefix_hit_rate"),
            "prefix_hits": pool.get("prefix_hits"),
            "cow_copies": pool.get("cow_copies"),
            "kv_live_pct": snap.get("kv_live_pct"),
            "kv_defers": pool.get("alloc_failures"),
            "error_rate": round(nerr[0] / float(total), 4),
            "requests": nsent[0], "bucket": bucket,
            "shared_tokens": shared, "prompt_tokens": shared + tail}


def bench_serve_multiturn_ttft():
    """Multi-turn conversation TTFT over the RETAINED conversation
    cache (doc/robustness.md "Memory governance"): turn N+1 extends
    turn N's prompt, so with retention the retired chain REVIVES at
    admission (refcount 0 -> 1) and prefill computes only the new
    tail; with a cold trie (serve_retained_frac 0 — the PR 15
    free-instantly contract) every turn re-prefills the whole
    conversation. Headline is the warm-trie turn-N+1 TTFT in ms
    (LOWER is better — the *_ms direction rule); ``cold_ttft_ms``
    carries the same turn over the cold trie, measured with identical
    programs (both program shapes warmed outside the window), so
    warm < cold is pure recompute avoided, not compile skew.
    ``prefix_hit_rate``/``kv_retained_pct`` pin that the warm pass
    really served from retained mass. CPU-measurable (tiny model,
    greedy, sequential turns)."""
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import servd
    from cxxnet_tpu.utils.servd import _ask
    from cxxnet_tpu.utils.telemetry import percentile
    vocab, L, n_new = 8192, 256, 8
    block, bucket = 16, 2
    # a LONG turn 1 (most of the context window) and a short turn-2
    # tail: the shape where retention pays — turn 2 revives 192 tokens
    # and computes 16, vs a 208-token cold re-prefill
    base, grow, nconv = 192, 16, 4
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)
    rs = np.random.RandomState(11)
    # conversations: distinct content, identical shape — turn 2's
    # prompt is turn 1's plus one grown block
    convs = [rs.randint(0, vocab, base + grow).tolist()
             for _ in range(nconv + 1)]

    def run_pass(retained_frac):
        backend = _PagedSlotBackend(tr, [bucket], n_new, block=block,
                                    pool_tokens=bucket * L,
                                    retained_frac=retained_frac)
        fe = servd.ServeFrontend(None, slot_backend=backend,
                                 queue_size=64, batch_max=bucket,
                                 batch_window_ms=5.0,
                                 batch_flight_cap=4096)
        fe.start()
        port = fe.listen(0)
        # warm BOTH program shapes outside the window: the cold pass
        # prefills (base+grow, 0), the warm pass (base, 0) then the
        # revived suffix (base+grow, base) — conversation 0 is the
        # sacrificial compile turn in each pass
        toks = convs[0]
        _ask(port, " ".join(map(str, toks[:base])), timeout=600.0)
        _ask(port, " ".join(map(str, toks)), timeout=600.0)
        for conv in convs[1:]:
            _ask(port, " ".join(map(str, conv[:base])), timeout=600.0)
            _ask(port, " ".join(map(str, conv)), timeout=600.0)
        # turn-N+1 TTFT from the flight ring, keyed by prompt length
        # (only final turns are base+grow tokens long); the ring is
        # newest-first, so the sacrificial compile turn is LAST
        ttfts = [1e3 * r["ttft_s"] for r in fe.flight.list()
                 if r.get("ttft_s") is not None
                 and r.get("tokens_in") == base + grow][:-1]
        snap = fe.batch_snapshot() or {}
        pool = snap.get("pool") or {}
        fe.drain()
        tr.release_kv_pool()
        return ttfts, pool

    cold_ttfts, _ = run_pass(0.0)
    warm_ttfts, pool = run_pass(1.0)
    warm = (round(percentile(sorted(warm_ttfts), 50), 3)
            if warm_ttfts else None)
    cold = (round(percentile(sorted(cold_ttfts), 50), 3)
            if cold_ttfts else None)
    return {"metric": "serve_multiturn_ttft", "value": warm,
            "unit": "ms", "vs_baseline": None,
            "cold_ttft_ms": cold,
            "ttft_speedup": round(cold / warm, 3)
            if warm and cold else None,
            "prefix_hit_rate": pool.get("prefix_hit_rate"),
            "retained_hit_rate": pool.get("retained_hit_rate"),
            "kv_retained_pct": pool.get("kv_retained_pct"),
            "retained_hits": pool.get("retained_hits"),
            "retained_evictions": pool.get("retained_evictions"),
            "conversations": nconv, "turn_tokens": base + grow,
            "revived_tokens": base}


def bench_serve_fleet():
    """Fleet-under-load: the same loopback flood as
    serve_loopback_p99_latency_ms, but through the replicated-fleet
    router (utils/routerd.py) over TWO local servd replicas — the
    serving topology doc/serving.md "Replicated serving fleet" ships.
    End-to-end p50/p99 through router+replica, plus the fleet-health
    sub-fields the chaos arc is graded on: shed_rate (admission sheds
    that survived retry) and retry_rate (retries per issued request).
    The two replicas share one chip (and one decode program, behind a
    lock — replica concurrency buys admission/failover, not parallel
    decode on a single chip), so the row measures ROUTER overhead and
    fleet correctness, not extra throughput; gated direction-aware by
    bench_compare (ms unit, *_rate sub-fields) next to the
    single-replica row."""
    import socket
    import threading
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import routerd, servd, statusd
    from cxxnet_tpu.utils.telemetry import percentile
    vocab, L, plen, n_new = 8192, 256, 32, 16
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)
    # ONE compiled decode program serves both replicas: generate() is
    # not reentrant, so the backend serializes on a lock (the fleet's
    # win is availability; a single chip has no parallel decode to give)
    gen_lock = threading.Lock()

    def backend(toks, seq):
        with gen_lock:
            return tr.generate(np.asarray([toks]), n_new)[0]

    rs = np.random.RandomState(0)
    prompt = rs.randint(0, vocab, plen).tolist()
    backend(prompt, 0)              # compile the (1, plen) decode once
    replicas, status = [], []
    for _ in range(2):
        fe = servd.ServeFrontend(backend, queue_size=64)
        fe.start()
        fe.listen(0)
        ss = statusd.StatusServer(0, host="127.0.0.1").start()
        ss.register_probe("serving", fe.health_probe)
        replicas.append(fe)
        status.append(ss)
    router = routerd.Router(
        [("127.0.0.1", fe.port, ss.port)
         for fe, ss in zip(replicas, status)],
        probe_ms=100.0, retries=2)
    router.start()
    rport = router.listen(0)
    nclients, per = 4, 8
    line = " ".join(map(str, prompt))
    lats, nshed, nerr, nsent = [], [0], [0], [0]
    lock = threading.Lock()

    def client():
        with socket.create_connection(("127.0.0.1", rport),
                                      timeout=300) as c:
            f = c.makefile("r")
            for _ in range(per):
                t0 = time.perf_counter()
                c.sendall((line + "\n").encode())
                resp = f.readline()
                dt = time.perf_counter() - t0
                with lock:
                    nsent[0] += 1
                    if not resp:
                        nerr[0] += 1        # torn connection != 0ms
                    elif resp.startswith("ERR busy"):
                        nshed[0] += 1       # shed survived the retries
                    elif resp.startswith("ERR"):
                        nerr[0] += 1
                    else:
                        lats.append(dt)
                if not resp:
                    break

    threads = [threading.Thread(target=client) for _ in range(nclients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rstats = router.drain()
    for fe in replicas:
        fe.drain()
    for ss in status:
        ss.stop()
    # fleet TTFT through the trace join: the router minted ONE id per
    # request and stamped it on every forward, so the replica flight
    # record that carries the honest device-level ttft_s is found by
    # id — the same join `telemetry_report.py --fleet` does offline
    routed = {rec["id"] for rec in router.flight.list()
              if rec.get("outcome") == "served"}
    ttfts = sorted(rec["ttft_s"] for fe in replicas
                   for rec in fe.flight.list()
                   if rec.get("id") in routed
                   and rec.get("ttft_s") is not None)
    lats.sort()
    total = max(1, nsent[0])
    return {"metric": "serve_fleet_p99_latency_ms",
            "value": round(1e3 * percentile(lats, 99), 3) if lats
            else None,
            "unit": "ms", "vs_baseline": None,
            "p50_ms": round(1e3 * percentile(lats, 50), 3) if lats
            else None,
            "ttft_p99_ms": round(1e3 * percentile(ttfts, 99), 3)
            if ttfts else None,
            "shed_rate": round(nshed[0] / float(total), 4),
            "retry_rate": round(rstats.get("retries", 0)
                                / float(total), 4),
            "error_rate": round(nerr[0] / float(total), 4),
            "replicas": len(replicas),
            "requests": nsent[0]}


def bench_serve_tenant_isolation():
    """Multi-tenant QoS under a noisy-tenant flood, through the
    replicated fleet: 2 active replicas + 1 standby behind the router,
    tenants ``noisy:1,victim:4`` fleet-wide, per-tenant SLO windows
    federating. A closed-loop noisy flood saturates the fleet while a
    light victim workload runs beside it — the row measures the three
    isolation guarantees ISSUE 13's chaos arc is graded on: the
    victim's p99 (headline, ms — holds while the flood sheds), the
    noisy tenant's shed rate (HIGHER is the fairness actually engaging
    — bench_compare knows this direction), and the autoscaler's
    scale-up ADMISSION latency (flood start -> standby admitted into
    rotation, driven live by the router's prober loop). Admission is a
    stub-side measure: the standby here serves the same warm backend,
    so "admitted" == "useful". Against a cold real replica it is NOT —
    the admitted standby still owes its compile grid; the honest
    admitted->useful gap is what ``serve_scale_up_to_first_token_s``
    (the cold-start row) measures. Null-safe like every serve row."""
    import threading
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import routerd, servd, statusd
    from cxxnet_tpu.utils.telemetry import percentile
    from tests import faultinject
    vocab, L, plen, n_new = 8192, 256, 32, 8
    tenants = "noisy:1,victim:4"
    tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=8,
                                dim=256, nhead=4, nlayer=2, dev="tpu",
                                extra_cfg=BF16)
    gen_lock = threading.Lock()

    def backend(toks, seq):
        with gen_lock:
            return tr.generate(np.asarray([toks]), n_new)[0]

    rs = np.random.RandomState(0)
    prompt = rs.randint(0, vocab, plen).tolist()
    backend(prompt, 0)              # compile the (1, plen) decode once
    line = " ".join(map(str, prompt))

    def replica():
        slo_t = {t: statusd.SLOTracker(availability=0.99,
                                       min_requests=4, min_bad=3,
                                       window_s=60.0)
                 for t in ("noisy", "victim")}
        fe = servd.ServeFrontend(backend, queue_size=8,
                                 tenants=tenants,
                                 tenant_default="victim",
                                 slo_tenants=slo_t,
                                 slo=statusd.SLOTracker(
                                     availability=0.99, min_requests=8,
                                     min_bad=3, window_s=60.0))
        fe.start()
        fe.listen(0)
        ss = statusd.StatusServer(0, host="127.0.0.1").start()
        ss.register_probe("serving", fe.health_probe)
        ss.slo = fe.slo
        ss.slo_tenants = slo_t
        ss.flight = fe.flight
        return fe, ss

    actives = [replica() for _ in range(2)]
    standby = replica()
    router = routerd.Router(
        [("127.0.0.1", fe.port, ss.port) for fe, ss in actives],
        probe_ms=100.0, retries=2, federate_ms=200.0,
        standby_replicas=[("127.0.0.1", standby[0].port,
                           standby[1].port)],
        scale_up_burn=1.0, scale_down_idle_s=3600.0,
        scale_cooldown_s=0.5, tenants=tenants,
        tenant_default="victim")
    router.start()
    rport = router.listen(0)
    router.probe_now()
    flood_s = 4.0
    results = {}
    t0 = time.perf_counter()

    def flood(name, **kw):
        results[name] = faultinject.tenant_flood(rport, name,
                                                 duration_s=flood_s,
                                                 toks=line, **kw)

    ths = [threading.Thread(target=flood, args=("noisy",),
                            kwargs={"nclients": 6}),
           threading.Thread(target=flood, args=("victim",),
                            kwargs={"nclients": 2})]
    for t in ths:
        t.start()
    # the autoscaler runs live on the prober cadence: poll for its
    # scale-up while the flood is on — flood start -> standby admitted
    scale_latency = None
    while time.perf_counter() - t0 < flood_s:
        if router.scale_snapshot()["events"] > 0:
            scale_latency = time.perf_counter() - t0
            break
        time.sleep(0.05)
    for t in ths:
        t.join()
    router.drain()
    for fe, ss in actives + [standby]:
        fe.drain(timeout_ms=2000)
        ss.stop()
    noisy, victim = results.get("noisy"), results.get("victim")
    vlats = sorted(victim["latencies"]) if victim else []
    nlats = sorted(noisy["latencies"]) if noisy else []

    def rate(d, key):
        return round(d[key] / float(d["sent"]), 4) \
            if d and d["sent"] else None

    return {"metric": "serve_tenant_isolation",
            "value": round(1e3 * percentile(vlats, 99), 3) if vlats
            else None,
            "unit": "ms", "vs_baseline": None,
            "victim_p99_ms": round(1e3 * percentile(vlats, 99), 3)
            if vlats else None,
            "victim_p50_ms": round(1e3 * percentile(vlats, 50), 3)
            if vlats else None,
            "victim_shed_rate": rate(victim, "shed"),
            "noisy_shed_rate": rate(noisy, "shed"),
            "noisy_p99_ms": round(1e3 * percentile(nlats, 99), 3)
            if nlats else None,
            "fleet_scale_admission_latency_s": round(scale_latency, 3)
            if scale_latency is not None else None,
            "lost": (victim["lost"] if victim else 0)
            + (noisy["lost"] if noisy else 0),
            "victim_requests": victim["sent"] if victim else 0,
            "noisy_requests": noisy["sent"] if noisy else 0}


def bench_serve_chaos_availability():
    """Availability through a SIGKILL: a 3-replica batched fleet
    (``servd --stub`` subprocesses — a kill must take a PROCESS, and
    the row grades the router's failover datapath, which is
    model-free: replay/hedge correctness against the real decode
    backend is tests/test_failover.py's job) floods through the
    router with deterministic replay on, one replica is SIGKILLed
    mid-flood with requests decoding aboard its batch, and the row
    reports the fraction of flood requests answered OK (headline,
    pct — bench_compare gates it worse-when-LOWER) plus the failover
    engagement sub-fields: error_rate, replays (a drop to zero means
    the failover path stopped firing), and the p99 of requests issued
    inside the kill window next to the overall p99. Null-safe like
    every serve row."""
    import threading
    from cxxnet_tpu.utils import routerd, telemetry
    from cxxnet_tpu.utils.telemetry import percentile
    from tests import faultinject
    fleet = faultinject.spawn_fleet(3, batch_max=4, n_new=8,
                                    per_token_ms=10)
    router = routerd.Router([r.spec for r in fleet], probe_ms=100.0,
                            retries=2, stall_s=2.0,
                            probe_backoff_cap_s=0.5)
    router.start()
    rport = router.listen(0)
    router.probe_now()
    # conservation-law bracket: the router's books must reconcile
    # through the SIGKILL (deltaed so other rows cannot leak in)
    telemetry.audit_sweep()
    books0 = telemetry.auditor().snapshot()["violations"]
    flood_s, kill_at, kill_win = 3.0, 0.8, 1.0
    lock = threading.Lock()
    samples = []                     # (t_issue_rel, latency_s, ok)
    t0 = time.perf_counter()
    stop_at = t0 + flood_s
    faultinject.kill9(fleet[0], delay_s=kill_at)

    def client(i):
        while time.perf_counter() < stop_at:
            t1 = time.perf_counter()
            try:
                resp = faultinject.serve_request(rport, "%d" % (10 + i),
                                                 timeout=10)
            except OSError:
                resp = None
            ok = bool(resp) and not resp.startswith("ERR")
            with lock:
                samples.append((t1 - t0, time.perf_counter() - t1, ok))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # sweep while the router's laws are still registered: a kill that
    # corrupted the route books must show up HERE, not vanish at drain
    telemetry.audit_sweep()
    books1 = telemetry.auditor().snapshot()["violations"]
    rstats = router.drain()
    faultinject.stop_fleet(fleet)
    sent = len(samples)
    lats = sorted(dt for _, dt, ok in samples if ok)
    kill_lats = sorted(dt for ti, dt, ok in samples
                       if ok and kill_at <= ti < kill_at + kill_win)
    nok = len(lats)
    return {"metric": "serve_chaos_availability",
            "value": round(100.0 * nok / sent, 3) if sent else None,
            "unit": "pct", "vs_baseline": None,
            "error_rate": round((sent - nok) / float(sent), 4)
            if sent else None,
            "replays": rstats.get("replays", 0),
            "lost_contact": rstats.get("lost_contact", 0),
            "p99_ms": round(1e3 * percentile(lats, 99), 3) if lats
            else None,
            "kill_window_p99_ms": round(1e3 * percentile(kill_lats,
                                                         99), 3)
            if kill_lats else None,
            # the metrics auditor's verdict on the kill: route books
            # must reconcile through a SIGKILL (worse-when-higher)
            "books_violations": books1 - books0,
            "replicas": len(fleet), "requests": sent}


def bench_serve_hedged_tail():
    """What tail hedging buys: a 2-replica fleet with one deliberate
    straggler (``servd --stub`` subprocesses, one at ``--delay-ms
    200`` — stub-based for the same reason as the chaos row: the
    hedge race is router-layer, model-free), flooded twice with the
    SAME client schedule — hedging off, then ``route_hedge_ms = 40``.
    Headline: the hedged p99 (ms, worse-when-HIGHER as usual);
    ``p99_unhedged_ms`` rides along as the honest before, and
    ``hedges`` / ``hedge_wins`` gate worse-when-LOWER (zero means the
    hedge lane stopped engaging and the headline quietly became the
    unhedged tail). Null-safe like every serve row."""
    import threading
    from cxxnet_tpu.utils import routerd
    from cxxnet_tpu.utils.telemetry import percentile
    from tests import faultinject

    def flood(rport, n=40, nclients=4):
        lats, lock = [], threading.Lock()

        def client(k):
            for j in range(n // nclients):
                t1 = time.perf_counter()
                try:
                    resp = faultinject.serve_request(
                        rport, "%d" % (10 + k + j), timeout=10)
                except OSError:
                    resp = None
                if resp and not resp.startswith("ERR"):
                    with lock:
                        lats.append(time.perf_counter() - t1)

        ths = [threading.Thread(target=client, args=(k,))
               for k in range(nclients)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return sorted(lats)

    out = {"unhedged": None, "hedged": None, "stats": {}}
    for mode, hedge_ms in (("unhedged", 0.0), ("hedged", 40.0)):
        a = faultinject._start_stub(delay_ms=200.0)
        b = faultinject._start_stub()
        procs = []
        for proc, args in (a, b):
            port, sp = faultinject._await_ports(proc)
            r = faultinject.FleetReplica(proc, port, sp, args)
            procs.append(r)
        router = routerd.Router([r.spec for r in procs],
                                probe_ms=100.0, retries=1,
                                hedge_ms=hedge_ms)
        router.start()
        rport = router.listen(0)
        router.probe_now()
        out[mode] = flood(rport)
        st = router.drain()
        if mode == "hedged":
            out["stats"] = st
        faultinject.stop_fleet(procs)
    hl, ul, st = out["hedged"], out["unhedged"], out["stats"]
    return {"metric": "serve_hedged_tail",
            "value": round(1e3 * percentile(hl, 99), 3) if hl
            else None,
            "unit": "ms", "vs_baseline": None,
            "p99_unhedged_ms": round(1e3 * percentile(ul, 99), 3)
            if ul else None,
            "p50_ms": round(1e3 * percentile(hl, 50), 3) if hl
            else None,
            "hedges": st.get("hedges", 0),
            "hedge_wins": st.get("hedge_wins", 0),
            "discarded_late": st.get("discarded_late", 0),
            "requests": len(hl) + len(ul)}


def bench_serve_cold_start():
    """HONEST cold-start / scale-up / reload latency against a REAL
    jax replica (doc/performance.md "Compile cliff") — three rows,
    measured in one run so they share the trainer:

    * ``serve_cold_start_to_ready_s``: trainer construction -> the
      full expected program grid warm (``ready_pct`` 100 after the
      warm-up sweep over ``plens``) — what a replica actually owes
      before it is USEFUL, not merely admitted.
    * ``serve_scale_up_to_first_token_s``: the first request against
      the cold replica -> its first token, server-side TTFT from the
      flight recorder, with the in-band compile stall attributed
      (``compile_stall_s``) — the admitted->useful gap the
      tenant-isolation row's ``fleet_scale_admission_latency_s``
      deliberately does NOT include.
    * ``serve_reload_capacity_dip``: a steady closed-loop flood with a
      rolling reload fired mid-flood (``reload_fn`` drops the jit
      cache, the real model-swap cost) — fractional completions/sec
      lost in the post-reload window vs the pre-reload window, stalls
      attributed on the post-reload requests (``reload_stall_s``).

    A PRIVATE perf ledger owns the warm account so programs warmed by
    earlier bench rows cannot pre-warm the grid (cold start must start
    at 0%% ready); the shared ledger's recompile hook is re-armed on
    the way out. Null-safe like every serve row."""
    from cxxnet_tpu.models import transformer_lm_trainer
    from cxxnet_tpu.utils import perf, servd
    from cxxnet_tpu.utils.servd import _ask
    vocab, L, n_new = 8192, 64, 4
    plens, bucket = [8, 16], 1
    shared_was_enabled = perf.enabled()
    lg = perf.Ledger().enable()
    fe = None
    t0 = time.perf_counter()
    try:
        tr = transformer_lm_trainer(vocab=vocab, seq=L, batch_size=4,
                                    dim=128, nhead=4, nlayer=2,
                                    dev="tpu", extra_cfg=BF16)
        lg.set_expected_grid(tr.expected_decode_grid([bucket], plens))

        class _Dense:
            # dense slot backend over the real decode datapath — the
            # minimal duck interface (buckets + session)
            buckets = [bucket]

            def session(self, nslots):
                return tr.decode_session(nslots, n_new)

        def reload_fn():
            # the real model-swap cost: the decode programs die with
            # the old params; the warm account resets with them so the
            # readiness series stays honest through the roll
            tr._clear_jit_cache()
            lg.reset()
            return True

        fe = servd.ServeFrontend(None, slot_backend=_Dense(),
                                 queue_size=32, batch_max=bucket,
                                 batch_window_ms=2.0,
                                 reload_fn=reload_fn)
        fe.start()
        fe.set_warm_account(lg.readiness, ready_pct=0.0)
        port = fe.listen(0)
        rs = np.random.RandomState(0)
        lines = [" ".join(map(str, rs.randint(0, vocab, p)))
                 for p in plens]
        # warm-up sweep: one request per declared prompt length — the
        # first pays prefill+admit+step compiles IN-BAND (scale-up to
        # first token), the rest fill out the prefill grid
        t_ready = None
        for ln in lines:
            _ask(port, ln, timeout=600.0)
            rd = lg.readiness()
            if t_ready is None and rd.get("ready_pct") == 100.0:
                t_ready = time.perf_counter() - t0
        served = [r for r in fe.flight.list()
                  if r["outcome"] == "served"]
        first = served[0] if served else {}
        rd = lg.readiness()
        # steady closed-loop flood (batch-1 capacity), rolling reload
        # fired mid-flood: the dip is completions/sec after vs before
        nflood, reload_at = 12, 6
        done_ts, t_r = [], None
        k0 = len(served)
        t_flood = time.perf_counter()
        for i in range(nflood):
            if i == reload_at:
                fe.request_reload()
                t_r = time.perf_counter()
            _ask(port, lines[0], timeout=600.0)
            done_ts.append(time.perf_counter())
        dip = None
        if t_r is not None and done_ts:
            w = min(t_r - t_flood, done_ts[-1] - t_r)
            pre = sum(1 for t in done_ts if t_r - w < t <= t_r)
            post = sum(1 for t in done_ts if t_r < t <= t_r + w)
            if pre:
                dip = round(max(0.0, 1.0 - post / float(pre)), 4)
        flood_recs = [r for r in fe.flight.list()
                      if r["outcome"] == "served"][k0 + reload_at:]
        stalls = [r.get("compile_stall_s") or 0.0 for r in flood_recs]
        rd_after = lg.readiness()
        return [
            {"metric": "serve_cold_start_to_ready_s",
             "value": round(t_ready, 3) if t_ready is not None
             else None,
             "unit": "s", "vs_baseline": None,
             "ready_programs_pct": rd.get("ready_pct"),
             "programs_expected": rd.get("expected"),
             "programs_warm": rd.get("warm")},
            {"metric": "serve_scale_up_to_first_token_s",
             "value": round(first["ttft_s"], 3)
             if first.get("ttft_s") is not None else None,
             "unit": "s", "vs_baseline": None,
             "compile_stall_s": first.get("compile_stall_s")},
            {"metric": "serve_reload_capacity_dip",
             "value": dip, "unit": "ratio", "vs_baseline": None,
             "reload_stall_s": round(max(stalls), 6) if stalls
             else None,
             "ready_programs_pct": rd_after.get("ready_pct"),
             "flood_requests": len(done_ts)},
        ]
    finally:
        if fe is not None:
            fe.drain(timeout_ms=2000)
        lg.disable()
        if shared_was_enabled:
            # give the recompile hook back to the shared ledger
            perf.enable()


def bench_mnist_mlp():
    tr = _conf_trainer(MNIST_MLP, (1, 1, 784), 100, extra=BF16)
    ips = _throughput(tr, (1, 1, 784), 10, 100, steps=100)
    return {"metric": "mnist_mlp_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_mnist_conv():
    tr = _conf_trainer(MNIST_CONV, (1, 28, 28), 100, extra=BF16)
    ips = _throughput(tr, (1, 28, 28), 10, 100, steps=100)
    return {"metric": "mnist_conv_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def bench_bowl():
    tr = _conf_trainer(BOWL, (3, 40, 40), 64, extra=BF16)
    ips = _throughput(tr, (3, 40, 40), 121, 64, steps=60)
    # reference: ~5 min to convergence on a GTX 780 (no throughput number)
    return {"metric": "kaggle_bowl_images_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec/chip",
            "vs_baseline": None}


def _make_jpeg_corpus(dirname, n, hw=256, n_class=1000, quality=90):
    """Synthesize an ImageNet-shaped JPEG corpus + .lst (reference list
    format: index label filename)."""
    import cv2
    os.makedirs(dirname, exist_ok=True)
    rs = np.random.RandomState(0)
    lst_path = os.path.join(dirname, "bench.lst")
    # a few noise textures stamped with per-image shifts: realistic JPEG
    # entropy without n full random draws
    protos = [rs.randint(0, 255, (hw, hw, 3), np.uint8) for _ in range(8)]
    with open(lst_path, "w") as lst:
        for i in range(n):
            img = np.roll(protos[i % 8], i * 37 % hw, axis=1)
            fname = "b_%05d.jpg" % i
            cv2.imwrite(os.path.join(dirname, fname), img,
                        [cv2.IMWRITE_JPEG_QUALITY, quality])
            lst.write("%d %d %s\n" % (i, i % n_class, fname))
    return lst_path


def _pipeline_iterator(lst_path, bin_path, batch, decode_thread=None):
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.utils.config import parse_config_string
    cfg = """
iter = imgbinx
  image_list = "%s"
  image_bin = "%s"
  shuffle = 1
  rand_crop = 1
  rand_mirror = 1
  output_uint8 = 1
  batch_size = %d
  round_batch = 1
  input_shape = 3,227,227
  silent = 1
%s
iter = threadbuffer
  silent = 1
""" % (lst_path, bin_path, batch,
       "  decode_thread = %d" % decode_thread if decode_thread else "")
    pairs = [(k, v) for k, v in parse_config_string(cfg)]
    it = create_iterator(pairs)
    it.init()
    return it


def bench_alexnet_pipeline(io_only=False):
    """imgbinx -> augment -> threadbuffer -> trainer, real JPEG decode.
    io_only=True stops before the trainer: the host-side feed benchmark
    (no device) — `python bench.py io`."""
    import tempfile
    if not io_only:
        import jax
        import jax.numpy as jnp
        from cxxnet_tpu.models import alexnet_trainer

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from im2bin import im2bin

    batch = 256
    n_img = 2048
    out = []
    with tempfile.TemporaryDirectory() as td:
        lst = _make_jpeg_corpus(os.path.join(td, "imgs"), n_img)
        bin_path = os.path.join(td, "bench.bin")
        im2bin(lst, os.path.join(td, "imgs"), bin_path)

        # io-only rate (decode + augment + batch, no device work) at a
        # worker sweep: the host-feed scaling curve to put against the
        # measured device rate, and the recipe for sizing decode_thread
        # (flat on a host with fewer free cores than workers).
        ncore = os.cpu_count() or 1
        for nw in (1, 2, 4):
            it = _pipeline_iterator(lst, bin_path, batch, decode_thread=nw)
            for _ in it:  # warm-up epoch: page cache + decode-pool spin-up
                pass
            t0 = time.perf_counter()
            n = sum(b.batch_size - b.num_batch_padd for b in it)
            io_ips = n / (time.perf_counter() - t0)
            it.close()
            out.append({"metric":
                        "alexnet_pipeline_io_only_images_per_sec_w%d" % nw,
                        "value": round(io_ips, 2), "unit": "images/sec",
                        "vs_baseline": None, "host_cores": ncore})
        # feed margin vs 15,047 img/s/chip, the driver's AlexNet b256 run
        # of 2026-07-29 (earlier set-up, not re-measured): >1 means this
        # host feeds the chip
        out.append({"metric": "alexnet_pipeline_feed_margin_vs_15047",
                    "value": round(io_ips / 15047.0, 4), "unit": "ratio",
                    "vs_baseline": None, "host_cores": ncore})
        if io_only:
            return out

        # pipeline-fed training: uint8 ships over H2D (4x less than f32),
        # normalization happens on device (input_divideby); fresh iterator
        # at the default decode_thread (independent of the sweep above)
        it = _pipeline_iterator(lst, bin_path, batch)
        tr = alexnet_trainer(batch_size=batch, input_hw=227, dev="tpu",
                             extra_cfg=BF16 + "input_divideby = 256\n")
        for b in it:        # warm-up epoch: jit compile + steady decode
            tr.update(b)
        t0 = time.perf_counter()
        n = 0
        t_input = 0.0       # host blocked on the loader (the starvation
                            # fraction the train loop also reports)
        for _ in range(2):  # two measured epochs
            ti = time.perf_counter()
            for b in it:
                t_input += time.perf_counter() - ti
                tr.update(b)
                n += b.batch_size - b.num_batch_padd
                ti = time.perf_counter()
        float(jnp.sum(next(v for p in tr.params for v in p.values())))
        wall = time.perf_counter() - t0
        ips = n / wall
        out.append({"metric": "alexnet_pipeline_fed_images_per_sec_per_chip",
                    "value": round(ips, 2), "unit": "images/sec/chip",
                    "vs_baseline": round(ips / 2000.0, 4),
                    "input_wait_frac": round(t_input / wall, 4)})
        # stop the decode pool + prefetch thread so later benches in the
        # same process don't contend for host cores
        it.close()
    return out


def _attach_perf(result):
    """Fold the performance ledger's card for the row's main program
    into the bench line: ``predicted_step_ms`` (roofline), ``mfu_pct``
    (vs the measured step histogram), ``hbm_peak_bytes`` (XLA per-device
    footprint; tools/bench_compare.py gates the sub-fields opt-in)."""
    from cxxnet_tpu.utils import perf
    lg = perf.ledger()
    if not lg.enabled:
        return result
    lg.drain(20.0)
    snap = lg.snapshot()
    card = None
    # the row's main program: train rows compiled a train step; decode
    # rows a decode scan; inference rows a predict program
    for name in ("jit.train_step", "jit.decode_step", "jit.predict"):
        ready = [c for c in snap["cards"]
                 if c["name"] == name and c["status"] == "ready"]
        if ready:
            card = ready[-1]
            break
    if card is not None:
        result["predicted_step_ms"] = (
            round(card["predicted_s"] * 1e3, 4)
            if card["predicted_s"] is not None else None)
        result["hbm_peak_bytes"] = card["peak_bytes"]
        result["mfu_pct"] = card["mfu_pct"]
    lg.reset()
    return result


def _attach_telemetry(result):
    """Fold the per-phase telemetry breakdown (top spans, compile count/
    seconds, counters since the last bench) into a bench line, so
    BENCH_*.json carries the breakdown instead of one opaque number.
    The step-time HISTOGRAM percentiles (fixed log-spaced buckets, the
    same series /metrics scrapes live) ride along as "step_ms" — the
    p50/p90/p99 tail a mean-throughput number hides."""
    from cxxnet_tpu.utils import telemetry
    if telemetry.enabled():
        # the ledger joins the measured histograms, so it reads BEFORE
        # the reset below wipes them
        _attach_perf(result)
        # one summary() pass feeds both views (it sorts every span's
        # duration history — don't do that twice per bench line)
        s = telemetry.summary()
        result["telemetry"] = telemetry.brief_summary(summary=s)
        h = s.get("hists", {}).get("train.step")
        if h and h["count"]:
            result["step_ms"] = {"p50": h["p50_ms"], "p90": h["p90_ms"],
                                 "p99": h["p99_ms"]}
        telemetry.reset()
    return result


def _require_tpu():
    """Fail before measuring anything unless jax computes on a TPU, and
    return the device facts every run of this file states up front."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench.py measures on a TPU only; jax found platform "
                 "%r (%s). No row was measured." % (dev.platform,
                                                    dev.device_kind))
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices())}


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "io":
        # host-side feed bench: decode + augment only, no device
        for line in bench_alexnet_pipeline(io_only=True):
            print(json.dumps(line), flush=True)
        return
    from cxxnet_tpu.utils import enable_compile_cache, perf, telemetry
    enable_compile_cache()
    print(json.dumps({"device": _require_tpu()}), flush=True)
    # in-memory telemetry (no JSONL sink): each bench line gets the
    # spans/compiles recorded during ITS run attached by _attach_telemetry
    telemetry.enable()
    # the program ledger: every bench row's compiled programs get
    # cost/memory cards -> predicted_step_ms / mfu_pct / hbm_peak_bytes
    perf.enable()
    if len(sys.argv) > 1 and sys.argv[1] == "all":
        for fn in (bench_mnist_mlp, bench_mnist_conv, bench_bowl,
                   bench_googlenet, bench_googlenet_b256,
                   bench_resnet, bench_vgg, bench_mobilenet,
                   bench_transformer_lm, bench_transformer_lm_long,
                   bench_vit, bench_alexnet_b1024, bench_alexnet_infer,
                   bench_alexnet_latency_b1, bench_lm_decode,
                   bench_lm_decode_b1, bench_lm_decode_long,
                   bench_lm_decode_chunked, bench_lm_decode_long_chunked,
                   bench_lm_decode_b1_chunked, bench_serve_load,
                   bench_serve_throughput, bench_serve_prefix_reuse,
                   bench_serve_multiturn_ttft,
                   bench_serve_fleet,
                   bench_serve_tenant_isolation,
                   bench_serve_chaos_availability,
                   bench_serve_hedged_tail):
            print(json.dumps(_attach_telemetry(fn())), flush=True)
        # the cold-start family shares one run (one trainer, three
        # rows) — list-returning, like the pipeline rows below
        for line in bench_serve_cold_start():
            print(json.dumps(_attach_telemetry(line)), flush=True)
    if len(sys.argv) > 1 and sys.argv[1] in ("all", "pipeline"):
        lines = bench_alexnet_pipeline()
        if lines:
            _attach_telemetry(lines[-1])
        for line in lines:
            print(json.dumps(line), flush=True)
    # the headline row is always the LAST line
    print(json.dumps(_attach_telemetry(bench_alexnet())), flush=True)


if __name__ == "__main__":
    main()
