"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
initialisation, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh with Auto axis types (shard_map-compatible)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes for this mesh ((pod, data) when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def model_axis(mesh) -> str:
    return "model"
