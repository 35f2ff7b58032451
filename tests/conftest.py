"""Test configuration: tests run on XLA's CPU backend with 8 virtual
devices, so sharding/collective paths are exercised without a chip and a
test can never claim one. Must run before jax is imported anywhere.
(The chip is exercised by chip_smoke.py, through the chip tool.)"""

import os

# force, don't setdefault: a machine with a chip exports JAX_PLATFORMS
# naming it
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_storage(tmp_path):
    return str(tmp_path / "storage")
