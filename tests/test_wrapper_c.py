"""End-to-end test of the C ABI (wrapper/cxxnet_wrapper.cc): compiles and
runs the pure-C smoke program, which drives the embedded-interpreter net +
iterator handles (reference surface wrapper/cxxnet_wrapper.h:36-230)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.synth_mnist import make_dataset


@pytest.fixture(scope="module")
def wrapper_bin():
    try:
        subprocess.run(["make", "bin/test_wrapper_c"], cwd=REPO, check=True,
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("native toolchain unavailable")
    return os.path.join(REPO, "bin", "test_wrapper_c")


def test_c_abi_end_to_end(wrapper_bin, tmp_path):
    make_dataset(str(tmp_path), n_train=200, n_test=50)
    env = dict(os.environ)
    env["CXXNET_TPU_ROOT"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    # the C process embeds its own interpreter; drop this pytest process's
    # forced-host-device XLA flags so they don't leak in
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([wrapper_bin, str(tmp_path)], env=env,
                       capture_output=True, text=True, timeout=600)
    sys.stderr.write(r.stderr)
    assert r.returncode == 0, r.stderr
    assert "C WRAPPER SMOKE TEST PASSED" in r.stderr
    assert "C WRAPPER GENERATE LEG PASSED" in r.stderr
    assert "C WRAPPER ITERATOR LEG PASSED" in r.stderr
