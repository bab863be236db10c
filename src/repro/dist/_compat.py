"""The distributed layer's shard_map and mesh entry points.

Every ``repro.dist`` module and every multi-device test snippet imports
``shard_map``, ``shard_map_unchecked`` and ``make_mesh`` from here.
"""
from __future__ import annotations

import jax

from repro.launch.mesh import make_mesh  # noqa: F401  (re-export)

shard_map = jax.shard_map


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes check off: pallas_call has
    no rule for it, so a body that may dispatch to the Pallas kernels (the
    pencil FFTs with ``backend="pallas"``) must opt out."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    """Tiled all_to_all on one array: local ``split_axis`` shrinks by the
    axis size, ``concat_axis`` grows by it (peer-major order)."""
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis,
                              tiled=True)
