"""Session-wide mesh context.

A single contextvar holds the live `jax.sharding.Mesh`; model code asks
`current_mesh()` at trace time and lowers to the matching collectives /
sharding constraints. Keeping it out of function signatures lets the same
model code serve single-device tests, GSPMD, and explicit shard_map paths.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["use_mesh", "current_mesh", "data_axes_of", "axis_size",
           "shard_hint", "shard_tp_ctx", "shard_tp"]

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_mesh", default=None)

# Set (> 0) while tracing the body of a TP shard_map: model code and the
# kernel dispatcher see per-shard local shapes there, so the Pallas routes
# re-engage even though `current_mesh()` is still live (DESIGN.md §14).
_SHARD_TP: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_shard_tp", default=0)


@contextlib.contextmanager
def shard_tp_ctx(tp: int):
    """Mark the dynamic extent as the inside of a shard_map body whose
    model-axis size is ``tp``. Entered at trace time by the TP serving
    wrapper (serve/engine.py) and the TP parity tests; everything that
    keys kernel selection off the mesh (`dispatch.pallas_route_active`,
    the models' TP branches) consults `shard_tp()` to distinguish
    "global GSPMD graph under a mesh" from "per-shard body"."""
    token = _SHARD_TP.set(int(tp))
    try:
        yield int(tp)
    finally:
        _SHARD_TP.reset(token)


def shard_tp() -> int:
    """Model-axis size of the enclosing shard_map body (0 outside one)."""
    return _SHARD_TP.get()


def _auto_axes(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """The same devices and axis names with every axis ``Auto``.
    `jax.make_mesh` defaults to ``Explicit`` axes, under which every
    jitted computation must itself run inside ``jax.set_mesh``; the model
    code here places work with GSPMD sharding constraints and shard_map
    instead, which is what ``Auto`` axes mean."""
    auto = jax.sharding.AxisType.Auto
    if mesh is None or all(t == auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(auto,) * len(mesh.axis_names))


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Make `mesh` the session mesh for the dynamic extent of the block.
    A mesh built with ``Explicit`` axes (the `jax.make_mesh` default) is
    taken with ``Auto`` axes over the same devices."""
    mesh = _auto_axes(mesh)
    token = _MESH.set(mesh)
    try:
        yield mesh
    finally:
        _MESH.reset(token)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def data_axes_of(mesh) -> Tuple[str, ...]:
    """Batch-parallel axes, in mesh order ("pod" before "data")."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(name: str) -> int:
    """Size of a mesh axis under the current mesh (1 when absent)."""
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def shard_hint(x: jax.Array, *entries) -> jax.Array:
    """Divisibility-safe `with_sharding_constraint`.

    One entry per leading dim of ``x`` (missing entries = None): an axis
    name, a tuple of axis names, or None. Axes absent from the live mesh
    are dropped; a dim that doesn't divide the requested axis product falls
    back to replication instead of erroring. No-op without a mesh.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = []
    for dim, e in zip(x.shape, entries + (None,) * (x.ndim - len(entries))):
        axes = tuple(a for a in ((e,) if isinstance(e, str) else (e or ()))
                     if a in mesh.axis_names)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if axes and n > 1 and dim % n == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
