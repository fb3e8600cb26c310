"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The platform is pinned with jax.config.update AFTER import and BEFORE
first backend use, so it holds whatever JAX_PLATFORMS the environment
set. XLA_FLAGS still applies because no backend has been initialized
yet at conftest time. Pallas kernels run in interpret mode on the CPU.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 "
        "(`-m 'not slow'`)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection suites (wire-level seeds are "
        "also marked slow so tier-1 stays fast)")
