"""Mesh construction. ``make_production_mesh`` is a FUNCTION so importing
this module never touches jax device state (the dry-run must set XLA_FLAGS
before the first jax call)."""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def _auto(n):
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """Target TPU v5e topology: 16x16 = 256 chips per pod; 2 pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over available devices (CPU smoke tests, examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def describe(mesh) -> str:
    return f"mesh(shape={dict(mesh.shape)}, devices={mesh.devices.size})"
