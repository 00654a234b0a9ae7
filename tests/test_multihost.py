"""Real multi-process distributed training: 2 worker processes x 4 virtual
CPU devices = one 8-device global mesh over the Gloo CPU backend — the
closest this sandbox gets to multi-host DCN. Validates init_distributed,
global-mesh trainer steps, and cross-process replica consistency (the
reference's dist-PS role, SURVEY.md §2.9 row 2)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent('''
import os, sys
# JAX_PLATFORMS / XLA_FLAGS come from the parent via virtual_cpu_env(4)
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
from cxxnet_tpu.parallel import init_distributed
rank = int(sys.argv[1])
init_distributed(%(coord)r, 2, rank)
assert jax.process_count() == 2
assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

import numpy as np
import jax.numpy as jnp
from jax.experimental import multihost_utils
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string
from cxxnet_tpu.io.data import DataBatch

conf = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig = end
input_shape = 1,1,32
batch_size = 16
eta = 0.1
dev = cpu:0-7
seed = 3
"""
tr = Trainer()
for k, v in parse_config_string(conf):
    tr.set_param(k, v)
tr.init_model()
assert tr.mesh is not None and tr.mesh.devices.size == 8

rs = np.random.RandomState(0)  # identical global batch on both hosts
b = DataBatch()
b.data = rs.rand(16, 1, 1, 32).astype(np.float32)
b.label = rs.randint(0, 10, (16, 1)).astype(np.float32)
b.batch_size = 16
for _ in range(5):
    tr.update(b)

# replica consistency ACROSS processes: every host's local shard of the
# (replicated) weights must be identical — host-side allgather of numpy
local = np.asarray(tr.params[0]["wmat"].addressable_shards[0].data)
gathered = multihost_utils.process_allgather(local)
assert gathered.shape[0] == 2
np.testing.assert_array_equal(gathered[0], gathered[1])
assert np.isfinite(gathered).all()
print("RANK%%d_OK" %% rank)

# multi-process fetch paths: predict gathers the mesh-sharded forward
# output to every host; save_model serializes ZeRO-sharded (update_on_server)
# optimizer state through parallel.fetch_global
tr2 = Trainer()
for k, v in parse_config_string(conf + "update_on_server = 1\\n"):
    tr2.set_param(k, v)
tr2.init_model()
for _ in range(2):
    tr2.update(b)
pred = tr2.predict(b)
assert pred.shape == (16,)
from cxxnet_tpu.utils import serializer
w = serializer.Writer()
tr2.save_model(w)
blob = w.getvalue()
assert len(blob) > 1000
gathered_pred = multihost_utils.process_allgather(pred)
np.testing.assert_array_equal(gathered_pred[0], gathered_pred[1])
print("RANK%%d_SAVE_OK" %% rank)

# per-host LOCAL-shard feeding (dist_num_worker-sharded corpora): each
# host supplies only its 8-row slice of the 16-row global batch;
# make_array_from_process_local_data must assemble the same global batch,
# so training matches the identical-global-batch run exactly
tr3 = Trainer()
for k, v in parse_config_string(conf):
    tr3.set_param(k, v)
tr3.init_model()
lo = rank * 8
b3 = DataBatch()
b3.data = b.data[lo:lo + 8]
b3.label = b.label[lo:lo + 8]
b3.batch_size = 16
for _ in range(5):
    tr3.update(b3)
w_full = np.asarray(tr.params[0]["wmat"].addressable_shards[0].data)
w_shard = np.asarray(tr3.params[0]["wmat"].addressable_shards[0].data)
np.testing.assert_allclose(w_shard, w_full, rtol=1e-6, atol=1e-7)
pred3 = tr3.predict(b3)          # shard-fed predict returns GLOBAL rows
assert pred3.shape == (16,)
print("RANK%%d_SHARD_OK" %% rank)

# fsdp across processes: params shard over the data axis spanning BOTH
# hosts (1/8 addressable), numerics match the replicated run, and
# save_model gathers the cross-process shards through fetch_global
tr5 = Trainer()
for k, v in parse_config_string(conf + "fsdp = 1\\n"):
    tr5.set_param(k, v)
tr5.init_model()
w5 = tr5.params[0]["wmat"]
assert np.asarray(w5.addressable_shards[0].data).size * 8 == w5.size, \
    w5.sharding
for _ in range(5):
    tr5.update(b)
w5 = tr5.params[0]["wmat"]
assert np.asarray(w5.addressable_shards[0].data).size * 8 == w5.size, \
    w5.sharding
from cxxnet_tpu.parallel import fetch_global
w5_full = np.asarray(fetch_global(w5))
np.testing.assert_allclose(w5_full[:, :], np.asarray(
    fetch_global(tr.params[0]["wmat"])), rtol=1e-6, atol=1e-7)
w = serializer.Writer()
tr5.save_model(w)
assert len(w.getvalue()) > 1000
print("RANK%%d_FSDP_OK" %% rank)

# hybrid DCN x ICI mesh: with model_parallel the trainer auto-builds the
# mesh so TP pairs stay INSIDE a process (ICI) while the data axis spans
# the two processes (DCN) — parallel.create_hybrid_mesh wired end-to-end
tr4 = Trainer()
for k, v in parse_config_string(conf + "model_parallel = 2\\n"):
    tr4.set_param(k, v)
tr4.init_model()
assert tr4.mesh.axis_names == ("data", "model")
assert tr4.mesh.shape["data"] == 4 and tr4.mesh.shape["model"] == 2
mdev = tr4.mesh.devices          # (data=4, model=2) device array
for i in range(4):
    row_procs = {d.process_index for d in mdev[i]}
    assert len(row_procs) == 1, (
        "model-axis pair %%d crosses processes: %%r" %% (i, row_procs))
for _ in range(5):
    tr4.update(b)
w4 = np.asarray(tr4.params[0]["wmat"].addressable_shards[0].data)
assert np.isfinite(w4).all()
# eval metrics must align labels with the hybrid mesh's data-axis DEVICE
# order (global arrays), not process-allgather order — feed per-host
# shards so the global-gather branch actually runs
class _OneBatchIter:
    def __init__(self, b): self.b = b; self.done = False
    def before_first(self): self.done = False
    def next(self):
        if self.done: return False
        self.done = True; return True
    def value(self): return self.b
b4 = DataBatch()
b4.data = b.data[lo:lo + 8]
b4.label = b.label[lo:lo + 8]
b4.batch_size = 16
tr4.metric.add_metric("error", "label")
tr4.eval_nodes = [tr4.net_cfg.param.num_nodes - 1]
s = tr4.evaluate(_OneBatchIter(b4), "hybrid")
assert "hybrid-error" in s
# cross-check: the aligned metric equals the error computed host-side on
# the full global batch
pred4 = tr4.predict(b4)
err_ref = float(np.mean(pred4 != b.label[:, 0]))
err_got = float(s.split("hybrid-error:")[1].split()[0])
assert abs(err_got - err_ref) < 1e-6, (err_got, err_ref)
print("RANK%%d_HYBRID_OK" %% rank)

# pipeline parallelism across the 2-process mesh: mesh (data=2, pipe=4)
# puts each pipe group on one process's 4 local devices (ppermute hops
# ride the intra-process "ICI"; the data all-reduce crosses "DCN"), and
# stage params pack sharded by pipe rank as in single-process runs
tr5 = Trainer()
for k, v in parse_config_string(conf + "pipeline_parallel = 4\\n"):
    tr5.set_param(k, v)
tr5.init_model()
assert tr5.mesh.axis_names == ("data", "pipe")
assert tr5.mesh.shape["data"] == 2 and tr5.mesh.shape["pipe"] == 4
for i in range(tr5.mesh.shape["data"]):
    row_procs = {d.process_index for d in tr5.mesh.devices[i]}
    assert len(row_procs) == 1, (
        "pipe group %%d crosses processes: %%r" %% (i, row_procs))
for _ in range(3):
    tr5.update(b)
canon5 = tr5.canonical_params()
w5 = np.asarray(canon5[0]["wmat"])
gathered5 = multihost_utils.process_allgather(w5)
np.testing.assert_array_equal(gathered5[0], gathered5[1])
assert np.isfinite(gathered5).all()
pred5 = tr5.predict(b)
assert pred5.shape == (16,)
print("RANK%%d_PP_OK" %% rank)
''')


def test_two_process_distributed_training(tmp_path):
    prog = WORKER % {"repo": REPO, "coord": "localhost:45683"}
    from cxxnet_tpu.parallel import virtual_cpu_env
    env = virtual_cpu_env(4)
    procs = [subprocess.Popen(
        [sys.executable, "-c", prog, str(r)], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env) for r in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, "rank %d failed:\n%s" % (r, out[-2000:])
        assert ("RANK%d_OK" % r) in out
        assert ("RANK%d_SAVE_OK" % r) in out
        assert ("RANK%d_SHARD_OK" % r) in out
        assert ("RANK%d_FSDP_OK" % r) in out
        assert ("RANK%d_HYBRID_OK" % r) in out
        assert ("RANK%d_PP_OK" % r) in out


FAULT_WORKER = r'''
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, REPO)
from cxxnet_tpu.parallel import init_distributed
rank = int(sys.argv[1])
phase = sys.argv[2]          # ref | crash | resume
coord = sys.argv[3]
workdir = sys.argv[4]
init_distributed(coord, 2, rank)

import numpy as np
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string
from cxxnet_tpu.utils import serializer
from cxxnet_tpu.io.data import DataBatch

conf = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig = end
input_shape = 1,1,32
batch_size = 16
eta = 0.1
momentum = 0.9
update_on_server = 1
dev = cpu:0-7
seed = 3
"""

def make_trainer():
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    return tr

rs = np.random.RandomState(0)
batches = []
for _ in range(6):
    b = DataBatch()
    b.data = rs.rand(16, 1, 1, 32).astype(np.float32)
    b.label = rs.randint(0, 10, (16, 1)).astype(np.float32)
    b.batch_size = 16
    batches.append(b)

def save(tr, path):
    # collective: every rank calls save_model; rank 0 writes the file
    w = serializer.Writer()
    tr.save_model(w)
    if rank == 0:
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(w.getvalue())
        os.replace(tmp, path)

if phase == "ref":
    tr = make_trainer(); tr.init_model()
    for b in batches:
        tr.update(b)
    save(tr, os.path.join(workdir, "ref.model"))
    print("RANK%d_REF_DONE" % rank, flush=True)
elif phase == "crash":
    tr = make_trainer(); tr.init_model()
    for b in batches[:3]:
        tr.update(b)
    save(tr, os.path.join(workdir, "ckpt.model"))
    print("RANK%d_CKPT_WRITTEN" % rank, flush=True)
    # keep training the next round until the driver SIGKILLs us mid-step
    i = 0
    while True:
        tr.update(batches[3 + i % 3])
        i += 1
elif phase == "resume":
    # the reference's recovery story: restart with continue=1 and resume
    # from the newest checkpoint (src/cxxnet_main.cpp:109-118,135-157)
    tr = make_trainer()
    with open(os.path.join(workdir, "ckpt.model"), "rb") as f:
        tr.load_model(serializer.Reader(f.read()))
    assert tr.epoch_counter == 3
    for b in batches[3:]:
        tr.update(b)
    save(tr, os.path.join(workdir, "resumed.model"))
    print("RANK%d_RESUME_DONE" % rank, flush=True)
'''


def test_kill_and_resume_bitwise(tmp_path):
    """Kill a worker mid-round; relaunch; continuation from the checkpoint
    (incl. ZeRO-sharded optimizer state) is BITWISE identical to the
    uninterrupted 2-process run."""
    import signal
    import time
    from cxxnet_tpu.parallel import virtual_cpu_env
    env = virtual_cpu_env(4)
    wd = str(tmp_path)
    prog = "REPO = %r\n" % REPO + FAULT_WORKER

    def spawn(phase, port):
        return [subprocess.Popen(
            [sys.executable, "-c", prog, str(r), phase,
             "localhost:%d" % port, wd],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env) for r in range(2)]

    # uninterrupted reference run
    procs = spawn("ref", 45701)
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, "ref rank %d:\n%s" % (r, out[-2000:])

    # crash run: wait for the checkpoint, then SIGKILL rank 1 mid-round,
    # then rank 0 (the job is dead once a worker is gone — the reference
    # exits via utils::Error too; recovery is restart + continue)
    procs = spawn("crash", 45703)
    ckpt = os.path.join(wd, "ckpt.model")
    deadline = time.time() + 240
    while not os.path.exists(ckpt) and time.time() < deadline:
        time.sleep(0.5)
        assert all(p.poll() is None for p in procs), [
            p.communicate()[0][-800:] for p in procs if p.poll() is not None]
    assert os.path.exists(ckpt), "checkpoint never appeared"
    time.sleep(1.0)          # let the next round get going
    procs[1].send_signal(signal.SIGKILL)
    time.sleep(0.5)
    procs[0].send_signal(signal.SIGKILL)
    for p in procs:
        p.wait(timeout=60)

    # relaunch with the checkpoint
    procs = spawn("resume", 45705)
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=240)
        assert p.returncode == 0, "resume rank %d:\n%s" % (r, out[-2000:])
        assert ("RANK%d_RESUME_DONE" % r) in out

    with open(os.path.join(wd, "ref.model"), "rb") as f:
        ref = f.read()
    with open(os.path.join(wd, "resumed.model"), "rb") as f:
        resumed = f.read()
    assert ref == resumed, "resumed run diverged from uninterrupted run"
