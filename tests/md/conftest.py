import os
# Must run before jax initializes — this suite is spawned in a subprocess
# by tests/test_multidevice.py with KAMPING_MD=1.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="session")
def mesh8():
    return jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture(scope="session")
def mesh2x4():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
