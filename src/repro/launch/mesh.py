"""Production mesh construction (single-pod 16×16 and multi-pod 2×16×16).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_axes(n: int) -> tuple:
    """``axis_types`` for an n-axis mesh whose shardings the compiler may
    propagate (``jax.make_mesh`` defaults to Explicit axes)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=auto_axes(2))
