"""Mesh normalization for the sharded graph programs.

``jax.make_mesh`` gives every axis the ``Explicit`` type by default, under
which sharding is part of each array's type and ops such as a row slice of
a sharded table must name their output sharding.  The graph programs
(features, slabs and label vectors sharded row-wise over ``data``, with
explicit all_to_all exchanges inside ``shard_map``) are written for
``Auto`` axes, where the compiler propagates shardings.  They run on an
``Auto``-typed view of whatever mesh the caller passes: the same devices,
in the same order, under the same axis names.
"""

from __future__ import annotations

from jax.sharding import AxisType, Mesh


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis typed ``Auto`` (itself if already so)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
