"""Test session setup.

Multi-device collective tests need >1 device, so the *test process* runs
with 8 host platform devices.  This is process-local: benchmarks and the
dry-run launcher configure their own device counts (1 and 512 respectively)
at the top of their own entry points — nothing here leaks into them.
"""

import os

# Must run before jax initializes its backends.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 host devices, got {len(ds)}"
    return ds


@pytest.fixture(scope="session")
def mesh8(devices):
    """1-D 8-way mesh for collective tests."""
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture(scope="session")
def mesh24(devices):
    """2x4 mesh: 'pod' x 'data' for hierarchical schedules."""
    return jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def mesh_dm(devices):
    """2x4 mesh: 'data' x 'model' for train-step tests."""
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
