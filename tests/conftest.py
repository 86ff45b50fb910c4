"""Test config: run JAX on a virtual 8-device CPU mesh.

The tests never take a chip: sharding correctness is validated on forced
host devices (``chip_smoke.py`` is what runs on the real ones). The
explicit ``JAX_PLATFORMS=cpu`` is also what every process the tests
start inherits, and what lets a ``quorum_backend=tpu`` role run on CPU
XLA instead of refusing to start (device.py).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def need_8_devices():
    """Skip unless the forced 8-device CPU mesh is available (shared by
    every multichip test module)."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device forced-CPU mesh")


def make_mesh(group_dim: int, slot_dim: int) -> Mesh:
    """The standard (group, slot) test mesh over the forced devices."""
    devices = np.asarray(jax.devices()[:group_dim * slot_dim])
    return Mesh(devices.reshape(group_dim, slot_dim), ("group", "slot"))


@pytest.fixture
def mesh_factory(need_8_devices):
    """make_mesh with the 8-device availability check applied."""
    return make_mesh
