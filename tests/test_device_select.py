"""Device selection and the compile cache's place: a run never lands on a
device the config did not ask for, and the cache directory can be set from
outside."""

import os
import subprocess
import sys

import pytest

from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONF = """
netconfig = start
layer[+1] = fullc:fc
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 1,1,8
batch_size = 16
eta = 0.1
"""


def _init(dev):
    tr = Trainer()
    for k, v in parse_config_string(CONF + "dev = %s\n" % dev):
        tr.set_param(k, v)
    tr.init_model()
    return tr


@pytest.mark.parametrize("dev", ["tpu", "gpu", "tpu:0-3"])
def test_accelerator_dev_on_a_cpu_backend_raises(dev):
    """``dev = tpu`` (the default) or ``gpu`` with no accelerator used to
    train on the CPU and say nothing."""
    with pytest.raises(RuntimeError, match="runs on the cpu backend"):
        _init(dev)


def test_default_dev_is_tpu_and_raises_too():
    tr = Trainer()
    for k, v in parse_config_string(CONF):
        tr.set_param(k, v)
    with pytest.raises(RuntimeError, match="dev = tpu requested"):
        tr.init_model()


def test_more_devices_than_exist_raises():
    """``dev = cpu:0-15`` on 8 devices used to become an 8-device run."""
    with pytest.raises(ValueError, match="asks for 16 devices.*has 8"):
        _init("cpu:0-15")
    assert _init("cpu:0-7").mesh.devices.size == 8


def test_device_ids_that_do_not_exist_raise():
    """Ids that do not resolve used to select "the first N devices"."""
    with pytest.raises(ValueError, match="device ids"):
        _init("cpu:6,9")


def test_single_device_other_than_the_first_raises():
    """``dev = cpu:1`` used to compute on device 0 and say nothing."""
    with pytest.raises(ValueError, match="computes on device 0"):
        _init("cpu:1")
    assert _init("cpu:0").mesh is None


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="unknown device kind"):
        _init("npu")


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    import jax
    from cxxnet_tpu.utils import compile_cache_stats, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache_stats()["dir"] == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax itself reads it and the
    program sets no other directory in code."""
    code = ("import jax\n"
            "from cxxnet_tpu.utils import enable_compile_cache\n"
            "seen = []\n"
            "orig = jax.config.update\n"
            "jax.config.update = lambda k, v: (seen.append(k), "
            "orig(k, v))\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print('jax_compilation_cache_dir' in seen)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path), str(tmp_path), "False"]
