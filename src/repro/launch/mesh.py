"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod's worth).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis joins
``data`` in every batch/FSDP sharding rule (DATA_AXES), so gradient
reduction is hierarchical: reduce within a pod over ICI, then across pods
over DCN — exactly the layout a 1000+-node job uses, just with more pods.

Defined as a function (never at module import) so importing this module
never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before the first jax import.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map_fn():
    """The ``shard_map`` transform (``jax.shard_map``)."""
    return jax.shard_map


def mesh_context(mesh):
    """Activate ``mesh`` for sharding-constraint resolution."""
    return jax.sharding.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for unit tests on the single CPU device."""
    return make_mesh((data, model), ("data", "model"))
