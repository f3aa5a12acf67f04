"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is validated on
``xla_force_host_platform_device_count=8`` CPU devices (same XLA partitioner as TPU).

The tier-1 command already sets JAX_PLATFORMS=cpu; the virtual-device flag and the
platform are pinned here too (before jax is imported) so that a bare ``pytest`` on a
machine with a chip never takes the chip.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) >= 8, "tests need the 8-device virtual CPU mesh"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 gate "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection tests "
        "(runtime/faults.py harness); fast ones stay in tier-1")
