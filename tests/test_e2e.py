"""End-to-end tests: config file -> CLI task driver -> trained model.

This is the framework's version of the reference's "example configs as
integration tests" strategy (SURVEY.md §4.4): MNIST-format data, the MNIST
MLP/conv configs, train/continue/pred/extract tasks.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

from cxxnet_tpu.learn_task import LearnTask

from . import synth_mnist


MLP_CONF = """
data = train
iter = mnist
    path_img = "{train_img}"
    path_label = "{train_lab}"
    shuffle = 1
iter = end
eval = test
iter = mnist
    path_img = "{test_img}"
    path_label = "{test_lab}"
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 64
  init_sigma = 0.01
layer[+1:sg1] = sigmoid:se1
layer[sg1->fc2] = fullc:fc2
  nhidden = 10
  init_sigma = 0.01
layer[+0] = softmax
netconfig=end

input_shape = 1,1,784
batch_size = 100

dev = cpu
save_model = 1
model_dir = {model_dir}
num_round = {num_round}
max_round = {num_round}
train_eval = 1
random_type = gaussian
eta = 0.2
momentum = 0.9
wd  = 0.0
metric = error
eval_train = 1
silent = 1
"""

CONV_CONF = """
data = train
iter = mnist
    path_img = "{train_img}"
    path_label = "{train_lab}"
    input_flat = 0
    shuffle = 1
iter = end
eval = test
iter = mnist
    input_flat = 0
    path_img = "{test_img}"
    path_label = "{test_lab}"
iter = end

netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = 16
  random_type = xavier
layer[1->2] = max_pooling
  kernel_size = 3
  stride = 2
layer[2->3] = flatten
layer[3->3] = dropout
  threshold = 0.2
layer[3->4] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[4->5] = relu
layer[5->6] = fullc:fc2
  nhidden = 10
  init_sigma = 0.1
layer[6->6] = softmax
netconfig=end

input_shape = 1,28,28
batch_size = 100
dev = cpu
save_model = 15
model_dir = {model_dir}
num_round = {num_round}
max_round = {num_round}
eta = 0.1
momentum = 0.9
clip_gradient = 5.0
wd  = 0.0
metric = error
eval_train = 1
silent = 1
"""


@pytest.fixture(scope="module")
def mnist_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("mnist_data")
    return synth_mnist.make_dataset(str(d))


def write_conf(tmp_path, template, data, num_round=3, **extra):
    conf = template.format(model_dir=str(tmp_path / "models"),
                           num_round=num_round, **data, **extra)
    p = tmp_path / "test.conf"
    p.write_text(conf)
    return str(p)


def run_task(conf_path, *overrides):
    task = LearnTask()
    task.run([conf_path] + list(overrides))
    return task


def final_eval_error(task):
    return {name: m.get() for name, m in
            zip(["test"], task.net_trainer.metric.evals)}


def test_mnist_mlp_trains(tmp_path, mnist_data, capsys):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=4)
    task = run_task(conf)
    # model files written with reference naming
    assert os.path.exists(str(tmp_path / "models" / "0001.model"))
    # final eval error must be far below chance (0.9)
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.35, "eval error %f did not improve" % err


def test_mnist_mlp_continue_resume(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=2)
    run_task(conf)
    assert os.path.exists(str(tmp_path / "models" / "0002.model"))
    # continue training picks up the newest model
    task2 = run_task(conf, "continue=1", "num_round=3")
    assert task2.start_counter == 4
    assert os.path.exists(str(tmp_path / "models" / "0003.model"))


def test_resume_matches_uninterrupted_run(tmp_path, mnist_data):
    """continue=1 end-to-end: train 2 rounds, stop, resume to 4 — the final
    metrics AND every weight must match an uninterrupted 4-round run
    bit-for-bit (the checkpoint carries optimizer state, rng-stream
    position, and round counters; CPU backend is deterministic)."""
    da, db = tmp_path / "a", tmp_path / "b"
    da.mkdir(), db.mkdir()
    conf_a = write_conf(da, MLP_CONF, mnist_data, num_round=4)
    task_a = run_task(conf_a)
    conf_b = write_conf(db, MLP_CONF, mnist_data, num_round=2)
    run_task(conf_b)
    task_b = run_task(conf_b, "continue=1", "num_round=4")
    assert task_b.start_counter == task_a.start_counter == 5
    assert (task_b.net_trainer.metric.evals[0].get()
            == task_a.net_trainer.metric.evals[0].get())
    assert task_b.net_trainer._rng_counter == task_a.net_trainer._rng_counter
    assert task_b.net_trainer.epoch_counter == task_a.net_trainer.epoch_counter
    pa = task_a.net_trainer.canonical_params()
    pb = task_b.net_trainer.canonical_params()
    for la, lb in zip(pa, pb):
        assert set(la) == set(lb)
        for k in la:
            assert np.array_equal(np.asarray(la[k]), np.asarray(lb[k])), k


def test_mnist_pred_task(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=2)
    run_task(conf)
    pred_file = str(tmp_path / "pred.txt")
    conf2 = conf  # reuse; add pred section via overrides is messy — write new conf
    text = open(conf).read().replace(
        "data = train", "pred = %s\niter = mnist\n  path_img = \"%s\"\n"
        "  path_label = \"%s\"\niter = end\ndata = train" %
        (pred_file, mnist_data["test_img"], mnist_data["test_lab"]))
    p = tmp_path / "pred.conf"
    p.write_text(text)
    run_task(str(p), "task=pred", "model_in=%s" %
             str(tmp_path / "models" / "0002.model"))
    preds = np.loadtxt(pred_file)
    assert preds.shape[0] == 200
    assert set(np.unique(preds)).issubset(set(range(10)))


def test_mnist_extract_task(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=1)
    run_task(conf)
    out_file = str(tmp_path / "feat.txt")
    text = open(conf).read().replace(
        "data = train", "pred = %s\niter = mnist\n  path_img = \"%s\"\n"
        "  path_label = \"%s\"\niter = end\ndata = train" %
        (out_file, mnist_data["test_img"], mnist_data["test_lab"]))
    p = tmp_path / "extract.conf"
    p.write_text(text)
    run_task(str(p), "task=extract", "extract_node_name=sg1",
             "model_in=%s" % str(tmp_path / "models" / "0001.model"))
    feats = np.loadtxt(out_file)
    assert feats.shape == (200, 64)
    meta = open(out_file + ".meta").read().strip()
    assert meta == "200,1,1,64"


def test_mnist_finetune_task(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=2)
    run_task(conf)
    task = run_task(conf, "task=finetune",
                    "model_in=%s" % str(tmp_path / "models" / "0002.model"),
                    "num_round=1", "model_dir=%s" % str(tmp_path / "models_ft"))
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.5  # finetuning from a trained model stays good


def test_mnist_conv_trains(tmp_path, mnist_data):
    conf = write_conf(tmp_path, CONV_CONF, mnist_data, num_round=4)
    task = run_task(conf)
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.5, "conv eval error %f did not improve" % err


def test_mnist_mlp_multidevice(tmp_path, mnist_data):
    """Data-parallel over the virtual 8-device CPU mesh (dev=cpu:0-3 maps to
    4 devices; replaces the reference's dev=gpu:0-3 worker threads)."""
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=4)
    task = run_task(conf, "dev=cpu:0-3")
    assert task.net_trainer.mesh is not None
    assert task.net_trainer.mesh.devices.size == 4
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.35, "multi-device eval error %f" % err


def test_mnist_mlp_composed_parallelism(tmp_path, mnist_data):
    """The full CLI pipeline (iterators, metrics, checkpoints) on a
    composed mesh: pp x tp x dp + ZeRO-1 (fsdp=1) over the 8-device
    virtual mesh — training must converge exactly like the plain run."""
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=4)
    task = run_task(conf, "dev=cpu:0-7", "pipeline_parallel=2",
                    "model_parallel=2", "fsdp=1")
    mesh = task.net_trainer.mesh
    assert (mesh.shape["data"], mesh.shape["pipe"],
            mesh.shape["model"]) == (2, 2, 2)
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.35, "composed-mesh eval error %f" % err
    assert os.path.exists(str(tmp_path / "models" / "0001.model"))


def test_update_period_accumulation(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=6)
    task = run_task(conf, "update_period=2", "eta=0.4")
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.5
    # epoch counter counts updates: 6 rounds * 6 batches / 2
    assert task.net_trainer.epoch_counter == 18


def test_threadbuffer_chain(tmp_path, mnist_data):
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=4)
    text = open(conf).read().replace(
        "    shuffle = 1\niter = end",
        "    shuffle = 1\niter = threadbuffer\niter = end")
    p = tmp_path / "tb.conf"
    p.write_text(text)
    task = run_task(str(p))
    err = task.net_trainer.metric.evals[0].get()
    assert err < 0.5


def test_test_on_server_consistency(tmp_path, mnist_data):
    """test_on_server=1: every StartRound asserts data-parallel replicas are
    bitwise in sync across the mesh (reference semantics:
    async_updater-inl.hpp:148-153 CheckWeight against the server copy)."""
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=2)
    task = run_task(conf, "dev=cpu:0-3", "test_on_server=1")
    tr = task.net_trainer
    # the explicit call must also pass after training
    tr.check_replica_consistency()
    # and it must detect forced divergence
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    key = next(iter(tr.params[0]))
    arr = np.asarray(tr.params[0][key])
    devs = tr.mesh.devices.reshape(-1)
    shards = []
    for i, d in enumerate(devs):
        a = arr.copy()
        if i == 1:
            a[(0,) * a.ndim] += 1.0  # poison one replica
        shards.append(jax.device_put(a, d))
    tr.params[0][key] = jax.make_array_from_single_device_arrays(
        arr.shape, NamedSharding(tr.mesh, P()), shards)
    with pytest.raises(ValueError, match="TestSync"):
        tr.check_replica_consistency()


def test_telemetry_logged_train_run(tmp_path, mnist_data, capsys):
    """telemetry_log=<path>: a train run leaves a parseable JSONL log with
    per-round io.wait/train.step/eval spans, >= 1 recorded compile event,
    round breakdown events, a final summary event, and a valid
    Chrome-trace export next to it; the report tool renders it."""
    from cxxnet_tpu.utils import telemetry
    log = str(tmp_path / "run.jsonl")
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=2)
    try:
        run_task(conf, "telemetry_log=%s" % log, "silent=0")
    finally:
        telemetry.disable()   # process-global: never leak into other tests
    out = capsys.readouterr().out
    assert "telemetry summary" in out       # end-of-run table printed
    events = [json.loads(l) for l in open(log).read().splitlines()
              if l.strip()]
    span_names = {e["name"] for e in events if e["ev"] == "span"}
    assert {"io.wait", "train.step", "train.h2d", "eval", "checkpoint",
            "round", "init"} <= span_names
    compiles = [e for e in events if e["ev"] == "compile"]
    assert len(compiles) >= 1
    assert any(e["name"] == "jit.train_step" for e in compiles)
    rounds = [e for e in events if e["ev"] == "round"]
    assert len(rounds) == 2
    for r in rounds:
        assert r["images"] == 600 and r["step_s"] >= 0
    assert events[-1]["ev"] == "summary"
    summ = events[-1]["summary"]
    assert summ["spans"]["train.step"]["count"] == 12   # 2 rounds x 6
    assert summ["counters"]["train.images"] == 1200
    assert summ["counters"]["io.h2d_bytes"] > 0
    # chrome trace loads as valid JSON with complete events
    trace = json.load(open(log + ".trace.json"))
    assert any(t.get("ph") == "X" and t["name"] == "train.step"
               for t in trace["traceEvents"])
    # the report tool renders the log
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report
    assert telemetry_report.main([log]) == 0
    rep = capsys.readouterr().out
    assert "train.step" in rep and "rounds" in rep


def test_telemetry_disabled_adds_no_events(tmp_path, mnist_data):
    """Without telemetry_log the same run records nothing: no events are
    buffered and span() returns the shared no-op (the zero-overhead-when-
    disabled contract on the per-step hot path)."""
    from cxxnet_tpu.utils import telemetry
    telemetry.disable()
    telemetry.reset()
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=1)
    run_task(conf)
    assert not telemetry.enabled()
    assert telemetry.events() == []
    s = telemetry.summary()
    assert s["spans"] == {} and s["counters"] == {}
    assert telemetry.span("x") is telemetry.span("y")


def test_train_loop_input_wait_probe(tmp_path, mnist_data, capsys):
    """The train loop reports the input-starvation fraction per round
    (reference design axis: device-feed overlap, thread_buffer.h:22) and
    test_io=1 reports the io-only feed rate."""
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=1)
    run_task(conf, "silent=0")
    out = capsys.readouterr().out
    m = re.search(r"input-wait +([0-9.]+)% \(io ([0-9.inf]+) img/s", out)
    assert m, out
    assert 0.0 <= float(m.group(1)) <= 100.0
    run_task(conf, "test_io=1", "continue=0")
    out = capsys.readouterr().out
    m = re.search(r"io-only ([0-9.]+) images/sec", out)
    assert m, out
    assert float(m.group(1)) > 0


def test_live_statusd_scrape_during_training(tmp_path, mnist_data):
    """The acceptance path for status_port: while a training run is LIVE,
    /metrics answers with Prometheus text including the step-latency
    histogram buckets, /healthz answers 200, /statusz shows round/batch
    progress — and the service (plus its in-memory telemetry) shuts down
    with the run."""
    import threading
    import time
    import urllib.request
    from cxxnet_tpu.utils import statusd, telemetry

    # far more rounds than needed: the test stops the run right after
    # the scrape (the cooperative _stop_training round-boundary exit)
    conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=500)
    task = LearnTask()
    done = threading.Event()
    err = []

    def run():
        try:
            task.run([conf, "status_port=0", "preempt_save=0",
                      "save_model=0"])
        except Exception as e:      # surfaced by the main thread
            err.append(e)
        finally:
            done.set()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    try:
        deadline = time.time() + 90
        srv = None
        while time.time() < deadline and not done.is_set():
            srv = statusd.active()
            if srv is not None and srv.progress.get("batch"):
                break
            time.sleep(0.05)
        assert srv is not None and srv.progress.get("batch"), \
            "statusd never served a completed batch (err=%r)" % err
        base = "http://127.0.0.1:%d" % srv.port
        metrics = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert "cxxnet_train_step_seconds_bucket" in metrics
        assert "cxxnet_io_wait_seconds_bucket" in metrics
        assert "cxxnet_train_images_total" in metrics
        assert 'le="+Inf"' in metrics
        assert urllib.request.urlopen(
            base + "/healthz", timeout=10).status == 200
        page = urllib.request.urlopen(
            base + "/statusz", timeout=10).read().decode()
        assert "progress" in page and "train.step" in page
    finally:
        task._stop_training = True   # cooperative stop at the round edge
        done.wait(timeout=120)
    th.join(timeout=10)
    assert not err, err
    assert statusd.active() is None       # stopped with the run
    assert not telemetry.enabled()        # in-memory registry released


def test_statusd_bind_failure_does_not_kill_the_run(tmp_path, mnist_data,
                                                    capsys):
    """An unbindable status_port (taken by another process) must warn
    and train blind — never crash a training job over observability —
    and must not leak the in-memory telemetry registry it enabled."""
    import socket
    from cxxnet_tpu.utils import statusd, telemetry
    blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    blocker.bind(("0.0.0.0", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        conf = write_conf(tmp_path, MLP_CONF, mnist_data, num_round=1)
        task = run_task(conf, "status_port=%d" % port, "preempt_save=0")
        assert task.start_counter == 2          # the round still trained
    finally:
        blocker.close()
    assert "cannot bind port %d" % port in capsys.readouterr().err
    assert statusd.active() is None
    assert not telemetry.enabled()
    # (the out-of-range-port OverflowError variant of this contract is
    # pinned jax-free in test_statusd.py — no second train run here)
