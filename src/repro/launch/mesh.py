"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
sees the 512 placeholder devices it forces before any jax import).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh", "use_mesh", "dp_axes",
           "DP_AXES"]


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh that
    ``dist.sharding.shard_hint`` and bare-PartitionSpec sharding
    constraints resolve against."""
    return jax.set_mesh(mesh)


def _auto_axes(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU integration tests (requires forced device count)."""
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (('pod','data') when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


DP_AXES = ("pod", "data")
