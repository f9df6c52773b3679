"""Production mesh construction (function, never module-level state —
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_axes(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = 256 chips/pod; multi_pod adds a leading pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (elastic restarts, tests)."""
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def dp_size(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in ("pod", "data")
                     if a in mesh.axis_names)
