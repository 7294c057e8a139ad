"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches
jax device state -- smoke tests see 1 device; only the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import) materializes the 256/512-way meshes.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh",
           "make_serving_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the compiler partitions
    whatever the programs do not place explicitly."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: 16x16 = 256 chips per pod; 2 pods for multi_pod.

    Axes: ``data`` (DP; composed with ``pod`` for cross-pod pure DP) and
    ``model`` (TP/EP).  ``pod`` is the outermost axis so cross-pod
    collectives (the slow DCN/ICI-limited hop) carry only the gradient
    all-reduce.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess tests (8 forced host devices)."""
    return make_mesh(shape, axes)


def make_serving_mesh(devices: int | None = None, *, axis: str = "shard"):
    """1-D mesh for the serving read path: ``devices`` chips (default:
    all visible) along one ``axis`` the stacked sweep shards its
    segment dimension over (``ShardedMutableP2HIndex.set_mesh`` /
    ``stacked_sweep_query(mesh=...)``).  CPU hosts simulate the chips
    via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set
    before the first jax import)."""
    n = jax.device_count() if devices is None else int(devices)
    return make_mesh((n,), (axis,))
