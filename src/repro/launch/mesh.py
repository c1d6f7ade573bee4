"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, while smoke tests and benchmarks see the 1 real device.

Axes:
  pod    — inter-pod data parallelism (2 pods in the multi-pod dry run;
           gradients cross DCI once per step)
  data   — intra-pod data/FSDP axis (16-way)
  model  — tensor/expert parallel axis (16-way)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# Auto axes: the parameter / batch specs and activation constraints
# (launch/sharding.py, distributed/activation_sharding.py) are bare
# PartitionSpecs resolved against the context mesh.


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n // n_model, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh: jax.sharding.Mesh):
    """The batch / FSDP axes of a mesh (everything except 'model')."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in names if a != "model")
