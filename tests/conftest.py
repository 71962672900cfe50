"""Test harness: 8 virtual CPU devices for the multi-device tests.

Set BEFORE any jax import (device count locks at first init).  The 512-dev
forcing is reserved for launch/dryrun.py only (per the brief); 8 devices
keeps the suite's shard_map/GSPMD coverage honest while smoke tests simply
use device 0.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    return jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def mesh_pod():
    return jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)


@pytest.fixture(scope="session")
def mesh_data8():
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
