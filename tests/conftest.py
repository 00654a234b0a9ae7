"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding is tested without TPU hardware via
xla_force_host_platform_device_count, as the driver does for
__graft_entry__.dryrun_multichip.

JAX_PLATFORMS is forced to cpu here, before jax is imported, so the suite
never reaches for an accelerator whatever the caller's environment says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# full-precision matmuls on CPU for golden tests
jax.config.update("jax_default_matmul_precision", "highest")

assert len(jax.devices()) == 8, (
    "tests require 8 virtual CPU devices, got %s" % jax.devices())


def pytest_configure(config):
    # register the tier split: tier-1 verify runs `-m 'not slow'` — fast
    # tests (telemetry, units, small e2e) must stay unmarked so they ride
    # in tier-1; long soak/sweep tests opt out with @pytest.mark.slow
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from tier-1 verify "
        "(-m 'not slow')")
