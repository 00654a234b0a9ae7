"""16-device composition tier (VERDICT r4 weak #6): axis-layout and
divisibility bugs that only appear past 8 devices — pp4 x tp2 x dp2, and
the 4-axis attention mesh with a REAL data axis — exercised on a
16-device virtual CPU backend in a subprocess (the in-process conftest
mesh is pinned to 8)."""

import os
import pytest
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PP4_SCRIPT = textwrap.dedent('''
import sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
import numpy as np
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string
from cxxnet_tpu.io.data import DataBatch

assert len(jax.devices()) == 16

CONF = """
netconfig = start
layer[+1:fc1] = fullc:fc1
  nhidden = 24
  init_sigma = 0.1
layer[+1] = relu
layer[+1:fc2] = fullc:fc2
  nhidden = 24
  init_sigma = 0.1
layer[+1] = relu
layer[+1:fc3] = fullc:fc3
  nhidden = 12
  init_sigma = 0.1
layer[+1] = relu
layer[+1:fc4] = fullc:fc4
  nhidden = 6
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 1,1,10
batch_size = 16
eta = 0.1
momentum = 0.9
"""

def trainer(extra):
    tr = Trainer()
    for k, v in parse_config_string(CONF + extra):
        tr.set_param(k, v)
    tr.init_model()
    return tr

tr = trainer("dev = cpu:0-15\\npipeline_parallel = 4\\n"
             "model_parallel = 2\\n")
ref = trainer("dev = cpu\\n")
assert tr.mesh.axis_names == ("data", "pipe", "model")
assert (tr.mesh.shape["data"], tr.mesh.shape["pipe"],
        tr.mesh.shape["model"]) == (2, 4, 2)

rs = np.random.RandomState(7)
for _ in range(4):
    b = DataBatch()
    b.data = rs.rand(16, 1, 1, 10).astype(np.float32)
    b.label = rs.randint(0, 6, (16, 1)).astype(np.float32)
    b.batch_size = 16
    tr.update(b)
    ref.update(b)
for p_t, p_r in zip(tr.canonical_params(), ref.params):
    for key in p_r:
        np.testing.assert_allclose(
            np.asarray(p_t[key]), np.asarray(p_r[key]),
            rtol=2e-4, atol=2e-4, err_msg=key)
print("OK pp4xtp2xdp2")
''')


def _run(script, timeout=900):
    from cxxnet_tpu.parallel import virtual_cpu_env
    env = virtual_cpu_env(16)
    p = subprocess.run([sys.executable, "-c", script % {"repo": REPO}],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    return p.stdout


def test_pp4_tp2_dp2_matches_single_device():
    out = _run(PP4_SCRIPT)
    assert "OK pp4xtp2xdp2" in out


@pytest.mark.slow
def test_dryrun_multichip_16():
    """The full dryrun at 16 devices: deep-pp tier (pp4 x tp2 x dp2 +
    ZeRO-1) and the 4-axis attention mesh with dp=2."""
    from cxxnet_tpu.parallel import virtual_cpu_env
    env = virtual_cpu_env(16)
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "import __graft_entry__; "
         "__graft_entry__.dryrun_multichip(16)" % REPO],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert ("dryrun_multichip OK: 16 cpu devices (virtual CPU mesh"
            in p.stdout)
