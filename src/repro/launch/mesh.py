"""Production meshes.

Defined as functions (not module constants) so importing this module never
touches jax device state — required because the dry-run pins the device
count via XLA_FLAGS before any jax initialisation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(devices: int = 8):
    """Small CPU mesh for integration tests (data x model = devices)."""
    model = 2 if devices % 2 == 0 else 1
    return jax.make_mesh((devices // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """Data-parallel axis names for batch sharding."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def fsdp_axis(mesh) -> str:
    """Parameter/optimizer FSDP axis (within-pod)."""
    return "data"


def tp_axis(mesh) -> str:
    return "model"
