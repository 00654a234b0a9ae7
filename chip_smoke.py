#!/usr/bin/env python
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user would call, at
the full width the repo ships, and exits 0 only if every phase passed ON A
TPU:

* **alexnet**  `bin/cxxnet` on example/ImageNet/ImageNet.conf as written
  (AlexNet, 3x227x227, batch 256, grouped convs, LRN, imgbin +
  threadbuffer) with ``compute_dtype=bfloat16 max_round=1``, fed by a
  seeded synthetic 256x256 JPEG corpus packed by the native ``bin/im2bin``
  and read by the native page reader. It must take its steps, print a
  finite error line and write its round checkpoint.
* **lm**  the widest LM the repo has (vocab 8192, d512, 8 heads, 4 blocks,
  L=2048, batch 8, bf16) trained through ``Trainer.update`` on
  example/transformer/train_lm.py's cyclic-walk corpus until next-token
  accuracy is ~1, with the compiled Pallas flash kernel in the lowered
  step; it saves a checkpoint and the greedy continuations of seeded
  prompts.
* **serve_paged / serve_solo**  ``task = serve`` on that checkpoint over
  TCP, once with ``serve_buckets`` + ``serve_kv_block`` (the batched,
  paged path) and once on the default solo path; every answer token-exact
  against the lm phase's file, no ``ERR`` anywhere, ``/metrics`` scraped,
  SIGTERM, exit code 0.
* **alexnet_dp4 / alexnet_dp4_zero**  with four or more devices, the
  AlexNet phase again on ``dev=tpu:0-3`` (and with
  ``update_on_server=1``); every device must hold memory. With fewer
  devices the phase is reported "not run", never as passed.

A chip belongs to one process at a time, so this parent never imports jax
or anything of the repo: every phase is a child, run one after another.
The native runtime is rebuilt from the tracked sources first
(``make -B``), and corpus, confs, checkpoints and expected continuations
are all generated from seeds under ``chip_smoke_out/``; the children's
logs and the result go to ``chiprun_out/chip_smoke/``.

The last line of stdout is one JSON object with exactly ``ok`` and
``device`` (``platform``, ``kind``, ``count``, as jax reports them): the
line the driver reads. The line before it is the full result, also one
JSON object (and ``chiprun_out/chip_smoke/result.json``): versions, how
the kernel switches resolved, peak device memory, the compile cache's
directory and use, and each phase's result. The wall and compile seconds
printed per phase are smoke timings of one cold run, not metrics. On any
platform but a TPU the script exits non-zero, naming the platform, and
prints no result.

Usage: python chip_smoke.py [--only phase,phase]
"""

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_out")
LOGS = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# the shipped widths; tests/test_chip_smoke.py runs the same children at a
# tiny size on the CPU to keep the plumbing debugged off the chip
FULL = {
    "img_hw": 256, "crop": 227, "batch": 256, "n_train": 1280,
    "n_test": 256, "n_class": 100,
    "vocab": 8192, "dim": 512, "nhead": 8, "nlayer": 4, "seq": 2048,
    "lm_batch": 8, "dtype": "bfloat16", "lm_max_steps": 1500,
    "lm_eval_every": 25, "lm_min_acc": 0.999,
    "prompt_lens": [64, 128], "gen_new": 32, "n_prompts": 6,
    "kv_block": 128,
}
PHASES = ("alexnet", "alexnet_dp4", "alexnet_dp4_zero", "lm",
          "serve_paged", "serve_solo")


class PhaseFailed(Exception):
    pass


def need(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


# ----------------------------------------------------------------------
# children: the only code here that imports jax or the repo
# ----------------------------------------------------------------------

def child_probe(_args):
    """The device as jax reports it, versions, and where the children's
    compile cache will live."""
    sys.path.insert(0, ROOT)
    import jax
    import jaxlib
    from cxxnet_tpu.utils import enable_compile_cache
    dev = jax.devices()[0]
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:   # no such distribution off the TPU installation
        libtpu = None
    print(json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "compile_cache_dir": enable_compile_cache()}))


def child_corpus(args):
    """Seeded JPEG corpus + lists, the way example/ImageNet/run.sh
    --synth makes them (packing is the parent's: the native bin/im2bin)."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_io_image import make_images
    sz = args.sizes
    n = sz["n_train"] + sz["n_test"]
    make_images(os.path.join(args.out, "imgs"), n=n,
                n_class=sz["n_class"], hw=sz["img_hw"])
    with open(os.path.join(args.out, "imgs", "img.lst")) as f:
        lines = f.readlines()
    with open(os.path.join(args.out, "NameList.train"), "w") as f:
        f.writelines(lines[:sz["n_train"]])
    with open(os.path.join(args.out, "NameList.test"), "w") as f:
        f.writelines(lines[sz["n_train"]:])
    print(json.dumps({"images": n}))


def child_busy(args):
    """Per-device busy time from the profiler trace under args.out: the
    summed durations of the executed XLA modules on each TPU plane, by
    module (the trailing fingerprint of a module's name dropped)."""
    import glob
    import jax
    path = sorted(glob.glob(os.path.join(
        args.out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    busy = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            mods = busy.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        name = e.name.split("(")[0]
                        mods[name] = mods.get(name, 0) + e.duration_ns
    print(json.dumps({"busy_ns": busy}))


def child_lm(args):
    """Train the LM through Trainer.update until the walk is learned,
    save a CLI-loadable checkpoint, write the serve conf and the greedy
    continuations serving must reproduce."""
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "example", "transformer"))
    import numpy as np
    import jax
    from cxxnet_tpu import models, ops
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils import (compile_cache_stats, enable_compile_cache,
                                  serializer, telemetry)
    from cxxnet_tpu.utils import checkpoint as ckpt
    from cxxnet_tpu.utils.config import parse_config_string
    import train_lm

    enable_compile_cache()
    sz = args.sizes
    t_start = time.perf_counter()
    conf = models.transformer_lm_conf(
        vocab=sz["vocab"], seq=sz["seq"], batch_size=sz["lm_batch"],
        dim=sz["dim"], nhead=sz["nhead"], nlayer=sz["nlayer"], dev=args.dev,
        extra_cfg="eval_train = 0\ncompute_dtype = %s\n" % sz["dtype"])
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()

    rs = np.random.RandomState(0)
    eval_b = train_lm.make_batch(np.random.RandomState(999),
                                 sz["lm_batch"], sz["seq"])
    hlo = tr.lower_update(eval_b).as_text()
    flash_mosaic = "tpu_custom_call" in hlo

    t0 = time.perf_counter()
    tr.update(train_lm.make_batch(rs, sz["lm_batch"], sz["seq"]))
    jax.block_until_ready(tr.params)
    compile_s = time.perf_counter() - t0
    steps, acc, good = 1, 0.0, 0
    # two consecutive clean evals: one can be a lucky dip of a loss spike
    while good < 2 and steps < sz["lm_max_steps"]:
        for _ in range(sz["lm_eval_every"]):
            tr.update(train_lm.make_batch(rs, sz["lm_batch"], sz["seq"]))
        steps += sz["lm_eval_every"]
        acc = train_lm.next_token_accuracy(tr, eval_b)
        good = good + 1 if acc >= sz["lm_min_acc"] else 0
        print("lm step %d: second-half next-token accuracy %.4f"
              % (steps, acc), flush=True)
    params_finite = all(
        bool(np.isfinite(np.asarray(v, np.float32)).all())
        for p in tr.params for v in p.values())

    model = os.path.join(args.out, "lm_models", "0001.model")
    os.makedirs(os.path.dirname(model), exist_ok=True)
    w = serializer.Writer()
    w.write_int32(0)
    tr.save_model(w)
    ckpt.write_checkpoint(model, w.f.getbuffer())

    # prompts are walks of the corpus, so a trained model's margins are
    # wide and bf16 rounding cannot flip a greedy choice between programs
    prs = np.random.RandomState(7)
    expected = []
    for i in range(sz["n_prompts"]):
        plen = sz["prompt_lens"][i % len(sz["prompt_lens"])]
        walk = (prs.randint(0, train_lm.VOCAB)
                + prs.randint(1, 5) * np.arange(plen + sz["gen_new"])
                ) % train_lm.VOCAB
        got = tr.generate(np.asarray([walk[:plen]]), sz["gen_new"])[0]
        expected.append({
            "prompt": [int(t) for t in walk[:plen]],
            "continuation": [int(t) for t in got],
            "follows_walk": bool((got == walk[plen:]).all())})
    with open(os.path.join(args.out, "lm_expected.json"), "w") as f:
        json.dump(expected, f)
    with open(os.path.join(args.out, "serve.conf"), "w") as f:
        f.write(conf + "task = serve\nmodel_in = %s\ngen_new = %d\n"
                % (model, sz["gen_new"]))
    print(json.dumps({
        "steps": steps, "accuracy": acc, "params_finite": params_finite,
        "flash_mosaic": flash_mosaic,
        "use_pallas": ops.use_pallas(),
        "pallas_interpret": ops.pallas_interpret(),
        "channels_last": bool(tr.net.channels_last),
        "follow_walk": sum(e["follows_walk"] for e in expected),
        "compile_s": round(compile_s, 2),
        "wall_s": round(time.perf_counter() - t_start, 2),
        "memory_stats": telemetry.sample_device_memory(),
        "compile_cache": compile_cache_stats()}))


# ----------------------------------------------------------------------
# the parent: stdlib only
# ----------------------------------------------------------------------

def run_child(name, cmd, cwd=None, timeout=900):
    """Run one child to its end; its output goes to LOGS/<name>.log and
    comes back as text. A non-zero exit is a failed phase."""
    log = os.path.join(LOGS, name + ".log")
    with open(log, "w") as f:
        p = subprocess.run(cmd, cwd=cwd, stdout=f, stderr=subprocess.STDOUT,
                           timeout=timeout)
    with open(log) as f:
        text = f.read()
    need(p.returncode == 0, "%s: exit code %d; tail of %s:\n%s"
         % (name, p.returncode, log, text[-1500:]))
    return text


def self_child(name, phase, sizes, dev, out=None, timeout=900):
    text = run_child(name, [
        sys.executable, os.path.abspath(__file__), "--child", phase,
        "--out", out or WORK, "--sizes", json.dumps(sizes), "--dev", dev],
        timeout=timeout)
    # the child's result is the last JSON line of its output
    lines = [ln for ln in text.splitlines() if ln.startswith("{")]
    need(lines, "%s printed no result" % name)
    return json.loads(lines[-1])


def device_summary(text):
    """learn_task's end-of-run line: per-device peak bytes + cache use."""
    m = re.search(r"device: peak_bytes_in_use=(\[[^\]]*\]) compile_cache "
                  r"requests=(\d+) hits=(\d+) misses=(\d+)", text)
    need(m, "no end-of-run 'device:' line")
    return {"peak_bytes_in_use": json.loads(m.group(1)),
            "compile_cache": {"requests": int(m.group(2)),
                              "hits": int(m.group(3)),
                              "misses": int(m.group(4))}}


def devices_used(text):
    """From learn_task's start-up line."""
    m = re.search(r"device: platform=\S+ device_kind='[^']*' "
                  r"devices=(\d+)/\d+ ", text)
    need(m, "no start-up 'device:' line")
    return int(m.group(1))


def build_native():
    run_child("build_native", ["make", "-B", "lib/libcxxnet_tpu_core.so",
                               "bin/im2bin"], cwd=ROOT)


def make_corpus(sizes):
    self_child("corpus", "corpus", sizes, "none")
    for part, lst in (("TRAIN", "NameList.train"), ("TEST", "NameList.test")):
        run_child("im2bin_" + part, [
            os.path.join(ROOT, "bin", "im2bin"), os.path.join(WORK, lst),
            os.path.join(WORK, "imgs") + os.sep,
            os.path.join(WORK, part + ".BIN")])
    # the stock conf points two directories up (reference layout): the
    # same rewrite example/ImageNet/run.sh makes
    with open(os.path.join(ROOT, "example", "ImageNet",
                           "ImageNet.conf")) as f:
        conf = f.read()
    for name in ("NameList", "TRAIN", "TEST"):
        conf = conf.replace("../../" + name, "./" + name)
    with open(os.path.join(WORK, "ImageNet.smoke.conf"), "w") as f:
        f.write(conf)


def phase_alexnet(tag, sizes, dev, extra=(), want_devices=1):
    """AlexNet from the imgbin pipeline through bin/cxxnet. On several
    devices it runs a second round under the profiler (learn_task traces
    round 2 into profile_dir), and every device must show its share of
    the memory and of the busy time."""
    mdir = os.path.join(WORK, "models_" + tag)
    rounds = 1
    if want_devices > 1:
        trace = os.path.join(WORK, "trace_" + tag)
        extra = tuple(extra) + ("profile_dir=%s" % trace,)
        rounds = 2
    os.makedirs(os.path.join(WORK, "models"), exist_ok=True)  # mean image
    t0 = time.perf_counter()
    text = run_child(tag, [
        sys.executable, os.path.join(ROOT, "bin", "cxxnet"),
        "ImageNet.smoke.conf", "compute_dtype=%s" % sizes["dtype"],
        "max_round=%d" % rounds,
        "print_step=1", "dev=%s" % dev, "model_dir=%s" % mdir,
        "batch_size=%d" % sizes["batch"],
        "input_shape=3,%d,%d" % (sizes["crop"], sizes["crop"])]
        + list(extra), cwd=WORK)
    wall = time.perf_counter() - t0
    steps = len(re.findall(r"^round +\d+:\[ *\d+\] ", text, re.M))
    need(steps >= 4, "took %d steps, need >= 4" % steps)
    need(text.count("page_reader=native") == 2,
         "the native page reader did not serve both iterators")
    m = re.search(r"^\[%d\](.*)$" % rounds, text, re.M)
    need(m, "no round metric line")
    metrics = {k: float(v) for k, v in
               re.findall(r"(\S+?-[\w@]+):([-+.\w]+)", m.group(1))}
    need(metrics and all(v == v and abs(v) != float("inf")
                         for v in metrics.values()),
         "metric line not finite: %r" % m.group(0))
    first = os.path.join(mdir, "0000.model")
    last = os.path.join(mdir, "%04d.model" % rounds)
    need(os.path.exists(last), "no round checkpoint %s" % last)
    with open(first, "rb") as a, open(last, "rb") as b:
        need(a.read() != b.read(), "a round of training left the "
             "checkpoint identical to the initial one")
    ds = device_summary(text)
    need(devices_used(text) == want_devices,
         "ran on %d devices, wanted %d" % (devices_used(text), want_devices))
    busy = None
    if want_devices > 1:
        # (peaks are not even: the first device also carries set-up)
        need(all(ds["peak_bytes_in_use"][:want_devices]),
             "a device held no memory: %r" % ds["peak_bytes_in_use"])
        busy = self_child("busy_" + tag, "busy", sizes, "none",
                          out=trace)["busy_ns"]
        need(len(busy) == want_devices, "trace has %d TPU planes, wanted "
             "%d" % (len(busy), want_devices))
        # the module the traced round spent most device time in is the
        # train step: no device may idle while others run it. A module's
        # time on a device includes its waits in the all-reduce, so the
        # shares are not equal (measured 0.57-0.72 of the largest); a
        # device left out of the work would show nothing
        total = {}
        for mods in busy.values():
            for name, ns in mods.items():
                total[name] = total.get(name, 0) + ns
        top = max(total, key=total.get)
        share = [mods.get(top, 0) for mods in busy.values()]
        need(min(share) > 0.25 * max(share),
             "module %s did not run evenly on every device: ns %r; all "
             "modules: %r" % (top, share, busy))
    step_rates = re.findall(r"step ([\d.]+) img/s", text)
    return dict(ds, steps=steps, metrics=metrics, wall_s=round(wall, 2),
                devices_used=want_devices, busy_ns=busy,
                last_round_step_img_per_s_smoke=float(step_rates[-1])
                if step_rates else None)


def ask(port, prompt, timeout=300):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as c:
        c.sendall((" ".join(map(str, prompt)) + "\n").encode())
        return c.makefile("r").readline().strip()


def phase_serve(name, sizes, extra, concurrent):
    """task = serve over TCP on the lm phase's checkpoint."""
    with open(os.path.join(WORK, "lm_expected.json")) as f:
        expected = json.load(f)
    log = os.path.join(LOGS, name + ".log")
    t0 = time.perf_counter()
    with open(log, "w") as f:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "bin", "cxxnet"),
             os.path.join(WORK, "serve.conf"), "serve_port=0",
             "status_port=0"] + list(extra),
            stdin=subprocess.PIPE, stdout=f, stderr=subprocess.STDOUT,
            cwd=WORK)
    try:
        ports = {}
        deadline = time.monotonic() + 600
        while len(ports) < 2:
            need(proc.poll() is None, "server exited with code %s before "
                 "serving; see %s" % (proc.returncode, log))
            need(time.monotonic() < deadline, "no ports after 600 s")
            time.sleep(0.5)
            with open(log) as f:
                text = f.read()
            for key, pat in (("serve", r"servd: serving on port (\d+)"),
                             ("status", r"statusd: live introspection on "
                                        r"port (\d+)")):
                m = re.search(pat, text)
                if m:
                    ports[key] = int(m.group(1))
        ready_s = time.perf_counter() - t0
        answers = [None] * len(expected)

        def one(i):
            answers[i] = ask(ports["serve"], expected[i]["prompt"])

        t1 = time.perf_counter()
        if concurrent:
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(expected))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
        else:
            for i in range(len(expected)):
                one(i)
        answer_s = time.perf_counter() - t1
        for i, (a, e) in enumerate(zip(answers, expected)):
            need(a is not None, "request %d never answered" % i)
            need(not a.startswith("ERR"), "request %d answered %r" % (i, a))
            need([int(t) for t in a.split()] == e["continuation"],
                 "request %d not token-exact:\n got  %s\n want %s"
                 % (i, a, e["continuation"]))
        with urllib.request.urlopen("http://127.0.0.1:%d/metrics"
                                    % ports["status"], timeout=30) as r:
            metrics = r.read().decode()
        with open(os.path.join(LOGS, name + ".metrics.txt"), "w") as f:
            f.write(metrics)
        need("cxxnet_" in metrics, "/metrics has no cxxnet_ series")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        need(rc == 0, "exit code %d after SIGTERM" % rc)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    with open(log) as f:
        text = f.read()
    need(not re.search(r"^ERR ", text, re.M), "an ERR line in %s" % log)
    need("served %d prompts (0 request errors)" % len(expected) in text,
         "server did not report %d served, 0 errors" % len(expected))
    return dict(device_summary(text), requests=len(expected),
                ready_s=round(ready_s, 2), answer_s=round(answer_s, 2),
                wall_s=round(time.perf_counter() - t0, 2),
                metrics_lines=metrics.count("\n"))


SWITCHES = ("use_pallas", "pallas_interpret", "channels_last",
            "flash_mosaic")


def phase_lm(sizes, dev, require_tpu):
    """The lm child, then what its result must say."""
    lm = self_child("lm", "lm", sizes, dev, timeout=1000)
    need(lm["accuracy"] >= sizes["lm_min_acc"] and lm["params_finite"],
         "LM did not learn the walk: accuracy %.4f after %d steps"
         % (lm["accuracy"], lm["steps"]))
    if require_tpu:
        need([lm[k] for k in SWITCHES] == [True, False, True, True],
             "kernel switches resolved wrong on the chip: %r"
             % {k: lm[k] for k in SWITCHES})
    return lm


def run_smoke(sizes, only=PHASES, require_tpu=True):
    """Run the phases in order; returns (ok, summary dict)."""
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(LOGS, exist_ok=True)
    device = self_child("probe", "probe", sizes, "none", timeout=300)
    if require_tpu and device["platform"] != "tpu":
        sys.exit("chip_smoke: jax found platform %r (%s x %d), not a TPU; "
                 "nothing was run and there is no result"
                 % (device["platform"], device["device_kind"],
                    device["n_devices"]))
    kind = device["platform"]
    results = {}

    def phase(name, fn, *a, **kw):
        if name not in only:
            results[name] = {"outcome": "not run: not selected"}
            return
        t0 = time.perf_counter()
        try:
            r = dict(fn(*a, **kw), outcome="passed")
        except (PhaseFailed, subprocess.TimeoutExpired, OSError,
                ValueError, KeyError) as e:
            r = {"outcome": "FAILED", "error": str(e)}
        results[name] = r
        print("phase %-16s %-6s wall %6.1f s%s  (smoke timing, not a "
              "metric)%s" % (
                  name, r["outcome"], time.perf_counter() - t0,
                  "  compile %.1f s" % r["compile_s"]
                  if "compile_s" in r else "",
                  "\n  " + r["error"] if "error" in r else ""),
              flush=True)

    if any(p.startswith("alexnet") for p in only):
        try:
            build_native()
            make_corpus(sizes)
        except PhaseFailed as e:
            sys.exit("chip_smoke: set-up failed: %s" % e)
        print("native runtime built from the tracked sources; corpus "
              "packed by bin/im2bin", flush=True)
    phase("alexnet", phase_alexnet, "alexnet", sizes, kind)
    for name, extra in (("alexnet_dp4", ()),
                        ("alexnet_dp4_zero", ("update_on_server=1",))):
        if device["n_devices"] >= 4:
            phase(name, phase_alexnet, name, sizes, kind + ":0-3",
                  extra=extra, want_devices=4)
        else:
            results[name] = {"outcome": "not run: %d device"
                             % device["n_devices"]}
            print("phase %-16s not run: %d device"
                  % (name, device["n_devices"]), flush=True)
    phase("lm", phase_lm, sizes, kind, require_tpu)
    lm = results["lm"]
    # solo runs second: its decode programs are the ones the lm phase
    # compiled for the expected file, so the second launch shows the
    # shared persistent cache being hit by a later process
    phase("serve_paged", phase_serve, "serve_paged", sizes,
          ("serve_buckets=1,2,4", "serve_kv_block=%d" % sizes["kv_block"]),
          True)
    phase("serve_solo", phase_serve, "serve_solo", sizes, (), False)

    selected = [results[p] for p in only]
    # (a selected phase is "not run" only for want of devices)
    ok = all(r["outcome"] == "passed" or r["outcome"].startswith("not run:")
             for r in selected) \
        and any(r["outcome"] == "passed" for r in selected)
    peaks = [p for r in results.values()
             for p in (r.get("peak_bytes_in_use") or []) if p]
    if lm.get("memory_stats"):
        peaks.append(lm["memory_stats"]["peak_bytes_in_use"])
    summary = {
        "ok": ok,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"],
                   "count": device["n_devices"]},
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "n_devices": device["n_devices"],
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "switches": {k: lm[k] for k in SWITCHES if k in lm},
        "peak_memory_bytes": max(peaks) if peaks else None,
        "memory_stats": lm.get("memory_stats"),
        "compile_cache": {
            "dir": device["compile_cache_dir"],
            "by_phase": {n: r["compile_cache"] for n, r in results.items()
                         if "compile_cache" in r}},
        "phases": results,
        "note": "wall_s / compile_s / *_smoke are timings of one cold "
                "smoke run, not metrics",
    }
    with open(os.path.join(LOGS, "result.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return ok, summary


def verdict_line(summary):
    """The last line of stdout: ``ok`` and the device, nothing else."""
    return json.dumps({"ok": bool(summary["ok"]),
                       "device": {k: summary["device"][k]
                                  for k in ("platform", "kind", "count")}})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default=",".join(PHASES),
                    help="comma-separated phases to run (default: all)")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--sizes", type=json.loads, help=argparse.SUPPRESS)
    ap.add_argument("--dev", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        {"probe": child_probe, "corpus": child_corpus, "lm": child_lm,
         "busy": child_busy}[args.child](args)
        return
    missing = [p for p in ("bin/cxxnet", "Makefile", "cxxnet_tpu",
                           "example/ImageNet/ImageNet.conf")
               if not os.path.exists(os.path.join(ROOT, p))]
    if missing:
        sys.exit("chip_smoke: %s is not the root of the repo (missing %s)"
                 % (ROOT, ", ".join(missing)))
    only = tuple(p for p in args.only.split(",") if p)
    unknown = [p for p in only if p not in PHASES]
    if unknown:
        sys.exit("chip_smoke: unknown phase(s) %s; phases are %s"
                 % (unknown, ", ".join(PHASES)))
    ok, summary = run_smoke(FULL, only=only)
    print(json.dumps(summary))
    print(verdict_line(summary), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
