"""Pallas TPU kernel: fused Stars leader-scoring (the paper's hot spot).

Scoring leaders against window members is where Stars spends its FLOPs (the
paper's Fig. 1 metric *is* this op count).  Per window the op is a skinny
(s x d) @ (d x W) matmul followed by normalization and masking.  A naive
lowering issues a gather (leaders), a gather (members), two normalizations
and a batched matmul — five HBM round-trips of the (nw, W, d) member tensor.

This kernel fuses normalize + matmul + mask for a grid of windows: one
window's leaders and members are staged in VMEM, squared-norms are computed
on the VPU, the similarity tile runs on the MXU, and masked entries are
written as -inf so the consumer can threshold/top-k without re-reading
features.  HBM traffic drops to one read of each feature tile plus the
(s x W) similarity write.

Blocking and numerics are ``window_score``'s (kernels/window_score.py):
``BLOCK_WINDOWS`` windows per grid step over (BLOCK_WINDOWS, s) /
(BLOCK_WINDOWS, W) flag tiles, normalization by division by sqrt and a
HIGHEST-precision contraction, so on TPU the similarities keep float32
accuracy instead of the MXU's default bfloat16 operand rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.window_score import (BLOCK_WINDOWS, _similarity,
                                        out_struct)


def _leader_score_kernel(l_ref, m_ref, lok_ref, mok_ref, out_ref, *,
                         normalized: bool):
    sims = _similarity(l_ref[...], m_ref[...], normalized)  # (B, s, w)
    # Mosaic cannot shape-cast i1 vectors: widen flags before broadcasting
    lok = lok_ref[...].astype(jnp.int32)[:, :, None] != 0
    mok = mok_ref[...].astype(jnp.int32)[:, None, :] != 0
    out_ref[...] = jnp.where(lok & mok, sims, -jnp.inf)


def leader_score(leaders: jax.Array, members: jax.Array,
                 leader_ok: jax.Array, member_ok: jax.Array, *,
                 normalized: bool = True,
                 interpret: bool = False) -> jax.Array:
    """Masked cosine/dot similarity tiles per window.

    leaders: (nw, s, d); members: (nw, w, d);
    leader_ok: (nw, s) bool; member_ok: (nw, w) bool -> (nw, s, w) float32.
    """
    nw, s, d = leaders.shape
    _, w, _ = members.shape
    bw = BLOCK_WINDOWS
    rows = lambda *tail: pl.BlockSpec((bw,) + tail,
                                      lambda i: (i,) + (0,) * len(tail))
    return pl.pallas_call(
        functools.partial(_leader_score_kernel, normalized=normalized),
        grid=(pl.cdiv(nw, bw),),
        in_specs=[rows(s, d), rows(w, d), rows(s), rows(w)],
        out_specs=rows(s, w),
        out_shape=out_struct((nw, s, w), jnp.float32, leaders, members,
                             leader_ok, member_ok),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(leaders, members, leader_ok, member_ok)
