"""Production meshes.  Functions, not module-level constants — importing this
module never touches jax device state (required: the dry-run sets
``xla_force_host_platform_device_count`` before first jax init)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh with ``Auto`` axes: the sharding rules place arrays through
    ``with_sharding_constraint``, which ``make_mesh``'s default
    ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``data`` carries DP/FSDP, ``model`` carries TP/SP/EP; the ``pod``
    axis is pure DP (gradient all-reduce crosses DCN, never FSDP — see
    DESIGN.md §3)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_small_mesh(shape=(2, 4), axes=("data", "model")):
    """Reduced mesh for CI-sized dry-run tests (8 host devices)."""
    return _auto_mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_of(mesh) -> str:
    return "model"
