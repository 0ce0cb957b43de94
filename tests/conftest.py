"""Test harness configuration.

JAX tests run on a virtual 8-device CPU mesh, steered by ``JAX_PLATFORMS``
(default ``cpu``, set before jax is imported; spawned server processes
inherit it). ``JAX_PLATFORMS=tpu`` runs the suite against a real chip,
which enables the compiled-Pallas kernel tests skipped on the CPU mesh.
The chip itself is driven by ``python chip_smoke.py``.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line("markers", "benchmark: performance test")
    config.addinivalue_line("markers", "integration: spawns real server processes")
