"""Pipeline parallelism: GPipe-style schedule over a `pipe` mesh axis.

Optional plan (off by default): the production meshes (16x16, 2x16x16)
have no dedicated pipeline axis — at 512 chips every assigned config fits
via FSDP+TP, and a pipeline axis would only dilute the DP batch.  PP
becomes the right trade beyond ~10k chips (or for >1T params), so the
machinery is provided and tested, ready to be given an axis.

Design: each of P stages holds its layer block's parameters; microbatches
stream through with ``jax.lax.ppermute`` moving activations stage->stage.
The classic GPipe schedule runs P + M - 1 ticks for M microbatches; every
stage computes on every tick (idle ticks process garbage that is masked
out), which is the standard fixed-shape SPMD formulation.

Bubble fraction = (P - 1) / (P + M - 1); with M >= 4P the overhead is
<20%, and the §Perf story for >1T configs would combine this with the
existing FSDP/TP axes (PP x FSDP x TP 3D plan).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import PartitionSpec as P

from repro.distributed.mesh import auto_axes


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: jax.sharding.Mesh, *, axis: str = "pipe",
                   microbatches: int) -> jax.Array:
    """Run ``stage_fn`` as a P-stage pipeline over microbatches.

    Args:
      stage_fn: (params_slice, activations (mb, ...)) -> activations.
      stage_params: pytree whose leaves have leading axis P (one slice per
        stage); sharded over ``axis``.
      x: (batch, ...) activations, batch % microbatches == 0.
      mesh: mesh containing ``axis`` of size P.
      microbatches: M.

    Returns y = stage_{P-1}(... stage_0(x)) with the same shape as x.
    """
    p = mesh.shape[axis]
    b = x.shape[0]
    assert b % microbatches == 0, (b, microbatches)
    mb = b // microbatches

    def per_stage(params, xs):
        # params: this stage's slice (leading axis 1); xs: full (B, ...)
        params = jax.tree.map(lambda t: t[0], params)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = p + microbatches - 1
        micro = xs.reshape((microbatches, mb) + xs.shape[1:])
        buf = jnp.zeros_like(micro)            # collected outputs

        def tick(carry, t):
            state, buf = carry                 # state: (mb, ...) in flight
            # stage 0 injects microbatch t (if any are left)
            inject = jnp.take(micro, jnp.minimum(t, microbatches - 1),
                              axis=0)
            state = jnp.where(stage_id == 0,
                              jnp.where(t < microbatches, inject, state),
                              state)
            out = stage_fn(params, state)
            # last stage collects microbatch (t - P + 1)
            slot = t - (p - 1)
            buf = jnp.where(
                (stage_id == p - 1) & (slot >= 0),
                jax.lax.dynamic_update_slice_in_dim(
                    buf, out[None], jnp.maximum(slot, 0), axis=0),
                buf)
            # shift activations to the next stage
            state = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % p) for i in range(p)])
            return (state, buf), None

        state0 = jnp.zeros((mb,) + xs.shape[1:], xs.dtype)
        # mark carries as device-varying (they diverge per stage)
        state0 = pcast(state0, (axis,), to="varying")
        buf = pcast(buf, (axis,), to="varying")
        (_, buf), _ = jax.lax.scan(tick, (state0, buf),
                                   jnp.arange(n_ticks))
        # each stage emits its buffer; only the last stage's is real
        return buf.reshape(xs.shape)[None]

    out = shard_map(
        per_stage, mesh=auto_axes(mesh),
        in_specs=(P(axis), P()),
        out_specs=P(axis),
    )(stage_params, x)
    return out[p - 1]
