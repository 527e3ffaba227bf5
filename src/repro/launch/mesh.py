"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state -- required because the
dry-run sets ``xla_force_host_platform_device_count`` before first jax init
while tests and benches must keep seeing 1 device.

Topology (TPU v5e target):
  single pod:  (16, 16)      axes ("data", "model")   = 256 chips
  multi pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``model`` carries TP/EP collectives (intra-pod ICI); ``data`` carries the DP
gradient reduction; ``pod`` is pure data parallelism across the slower
inter-pod links -- nothing but gradient all-reduce ever crosses it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX's default is now explicit sharding, under which a reshape of a
    sharded operand must state its output sharding.  The model code leaves
    that to the partitioner (GSPMD propagation), so every mesh here is
    built with automatic axes.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess tests (host platform devices)."""
    return make_mesh(shape, axes)


def chips(mesh) -> int:
    return mesh.devices.size
