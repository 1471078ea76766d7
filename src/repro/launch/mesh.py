"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_smoke_mesh", "POD_SHAPE",
           "MULTI_POD_SHAPE"]

POD_SHAPE = (16, 16)                       # 256 chips (one v5e pod)
MULTI_POD_SHAPE = (2, 16, 16)              # 2 pods = 512 chips


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_smoke_mesh(data: int = 2, model: int = 4,
                    pod: Optional[int] = None) -> Mesh:
    """Small virtual mesh for CPU tests (requires >= data*model*(pod or 1)
    visible devices, e.g. via xla_force_host_platform_device_count)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
