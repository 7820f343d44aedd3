"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
while smoke tests and benches must keep seeing the single real device.
"""

from __future__ import annotations

import jax

__all__ = ["MeshSpecError", "validate_mesh_spec", "make_production_mesh",
           "make_mesh", "dp_axes"]


class MeshSpecError(ValueError):
    """Structured mesh-spec rejection: what was asked, what was wrong.

    ``needed``/``available``/``deficit`` are populated for device-count
    failures so callers (the planner, the launcher) can report or recover
    programmatically instead of parsing the message."""

    def __init__(self, message: str, *, shape=None, axes=None,
                 needed: int | None = None, available: int | None = None):
        super().__init__(message)
        self.shape = tuple(shape) if shape is not None else None
        self.axes = tuple(axes) if axes is not None else None
        self.needed = needed
        self.available = available
        self.deficit = (needed - available
                        if needed is not None and available is not None
                        else None)


def validate_mesh_spec(shape, axes, available: int | None = None) -> int:
    """Validate a ``(shape, axes)`` mesh request; returns the device count
    it needs.  The ONE validator shared by :func:`make_mesh` and the
    auto-sharding planner (``repro.planner``) — positive dims, matching
    lengths, unique non-empty axis names, and (when ``available`` is
    given) enough devices, with the deficit named in the error."""
    shape = tuple(shape)
    axes = tuple(axes)
    if not shape:
        raise MeshSpecError("empty mesh shape", shape=shape, axes=axes)
    if len(shape) != len(axes):
        raise MeshSpecError(
            f"mesh shape {shape} has {len(shape)} dims but axes {axes} "
            f"name {len(axes)}", shape=shape, axes=axes)
    bad = [d for d in shape if not (isinstance(d, int) and d >= 1)]
    if bad:
        raise MeshSpecError(
            f"mesh shape {shape} has non-positive dim(s) {bad}; every axis "
            "must be an int >= 1", shape=shape, axes=axes)
    if len(set(axes)) != len(axes) or any(not a for a in axes):
        raise MeshSpecError(
            f"mesh axes {axes} must be unique non-empty names",
            shape=shape, axes=axes)
    n = 1
    for d in shape:
        n *= d
    if available is not None and available < n:
        raise MeshSpecError(
            f"mesh {shape} over axes {axes} needs {n} devices but only "
            f"{available} are visible ({n - available} short) — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before any "
            "jax import (see launch/dryrun.py) or plan a smaller layout",
            shape=shape, axes=axes, needed=n, available=available)
    return n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (hillclimbing explores non-default layouts).  Uses the
    first prod(shape) devices — documented behaviour, so a 512-device
    dry-run host can build both the 256-chip single-pod and the 512-chip
    multi-pod mesh — after :func:`validate_mesh_spec` has vetted the
    request (raising :class:`MeshSpecError` naming the deficit when the
    host is short on devices)."""
    devs = jax.devices()
    n = validate_mesh_spec(shape, axes, available=len(devs))
    import numpy as _np

    return jax.sharding.Mesh(
        _np.array(devs[:n]).reshape(tuple(shape)), tuple(axes)
    )


def dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel axes present on this mesh (pod is outer DP)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)

