"""Virtual-cluster substrate: the jax mesh API + in-process
multi-topology testing.

* ``repro.substrate.compat``  — ``shard_map`` / ``make_mesh`` /
  ``axis_size`` / axis types for the installed jax (0.9).  Import jax mesh
  and shard_map APIs from here, never from jax directly.
* ``repro.substrate.cluster`` — ``VirtualCluster``: builds the two-tier
  (pods x chips) mesh and wraps collective bodies so one check sweeps a
  whole topology matrix in-process.  Call ``ensure_host_device_count(n)``
  before jax initializes its backends (the test suite does this in
  ``tests/conftest.py``) to provide the fake host CPU devices.
"""

from repro.substrate import compat
from repro.substrate.cluster import (VirtualCluster, default_matrix,
                                     ensure_host_device_count)
from repro.substrate.compat import auto_axis_types, make_mesh, shard_map

__all__ = [
    "compat",
    "VirtualCluster",
    "default_matrix",
    "ensure_host_device_count",
    "auto_axis_types",
    "make_mesh",
    "shard_map",
]
