"""Pallas TPU kernel: fully fused Stars window scoring (the build hot path).

``leader_score`` fused normalize+matmul+mask; the scoring loop around it
still materialized the (rows, s, W) candidate grid plus leader/member gid
broadcasts in HBM, re-read them to apply the self/bucket/extension/refresh
masks, and re-read them again to count comparisons.  This kernel folds the
ENTIRE per-window scoring pipeline of ``core/stars._score_windows`` into
one pass: leaders and members are staged in VMEM once per window,
squared-norms run on the VPU, the similarity tile on the MXU, the full
emit-mask chain (validity, self-slot, upper-triangle, same-bucket,
extension watermark, refresh watermark + window sample) is applied in
registers, and the per-window comparison / emit counters reduce in VMEM —
so the only HBM traffic is one read of each feature tile and the masked
(s, W) result write.  Pallas's grid pipeline double-buffers the per-window
input tiles automatically (window i+1's tiles stream in while window i
computes).

Numerics contract: normalization divides by sqrt(sum^2 + 1e-12) and the
contraction is ``dot_general`` over the feature axis at HIGHEST precision —
the exact ops of ``ref.leader_score_ref``.  The discrete outputs (emit mask,
counters, the -inf validity pattern) are exactly equal to the oracle's; the
similarity floats agree to ~1 ulp but not bitwise, because XLA fuses the
normalize->contract chain differently in this grid program than in the
batched oracle (FMA contraction — the same drift any two jit scopes can
show).  HIGHEST matters on TPU only: the default precision for float32
operands rounds them to bfloat16, which moves cosine similarities by up to
3.7e-3 at d=100 on a v5e (enough to reorder near-tied top-k candidates);
on CPU the contraction is float32 either way.  Dispatch
(``ops.window_score``) picks exactly one implementation per backend, so
mesh/single-device edge-for-edge parity never compares floats across the
two paths.

Blocking: each grid step scores ``BLOCK_WINDOWS`` windows, so every 2-D
per-slot operand is read as a (BLOCK_WINDOWS, s) / (BLOCK_WINDOWS, W) tile
(sublane-aligned rows, full-width lanes — the tiling Mosaic accepts) and
the per-window counters come back as (BLOCK_WINDOWS, 1) columns.  The grid
is ``cdiv(nw, BLOCK_WINDOWS)``: the last step's out-of-range window rows
read unspecified values and their writes are dropped, so no input is ever
padded (the (nw, W, d) member tile is the largest array of a repetition).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")

# Windows per grid step: 8 fills the sublanes of the 2-D per-slot tiles,
# and 8 x (W, d) float32 member tiles stay ~1 MB of VMEM at the paper's
# W=250, d=100 (double-buffered by the grid pipeline).
BLOCK_WINDOWS = 8


def out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """A kernel output's type: ``shape``/``dtype``, varying over the same
    mesh axes as the inputs ``like``.  Inside ``shard_map`` (the mesh
    backend's scoring and emit phases) Pallas needs the outputs' varying
    axes; outside it they are empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _normalize(x):
    # division by sqrt, NOT rsqrt-multiply: same op sequence as ref.py
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)


def _similarity(lead, memb, normalized: bool):
    """(B, s, d) x (B, w, d) -> (B, s, w) float32 similarity tiles."""
    lead = lead.astype(jnp.float32)
    memb = memb.astype(jnp.float32)
    if normalized:
        lead, memb = _normalize(lead), _normalize(memb)
    return jax.lax.dot_general(lead, memb, (((2,), (2,)), ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _window_score_kernel(l_ref, m_ref, lslot_ref, lgid_ref, gid_ref,
                         lok_ref, mok_ref, lbuck_ref, buck_ref, keep_ref,
                         sims_ref, emit_ref, comp_ref, emitted_ref, *,
                         normalized: bool, allpairs: bool,
                         match_bucket: bool, new_from: int,
                         refresh_below: int, r1: Optional[float]):
    sims = _similarity(l_ref[...], m_ref[...], normalized)  # (B, s, w)
    lead = lambda ref: ref[...][:, :, None]                 # (B, s, 1)
    memb = lambda ref: ref[...][:, None, :]                 # (B, 1, w)
    # Mosaic cannot shape-cast i1 vectors: widen flags before broadcasting
    flag = lambda x: x.astype(jnp.int32)
    lok = flag(lok_ref[...])[:, :, None] != 0
    mok = flag(mok_ref[...])[:, None, :] != 0

    mask0 = lok & mok
    slot = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 2)
    mask = mask0 & (lead(lslot_ref) != slot)
    if allpairs:
        mask &= lead(lslot_ref) < slot
    if match_bucket:
        mask &= lead(lbuck_ref) == memb(buck_ref)
    if new_from > 0:
        nf = jnp.int32(new_from)
        mask &= (lead(lgid_ref) >= nf) | (memb(gid_ref) >= nf)
    if refresh_below > 0:
        rb = jnp.int32(refresh_below)
        mask &= flag(keep_ref[...])[:, :, None] != 0
        mask &= (lead(lgid_ref) < rb) & (memb(gid_ref) < rb)

    sims_ref[...] = jnp.where(mask0, sims, _NEG_INF)
    emit = mask
    if r1 is not None:
        emit &= sims > r1
    emit_ref[...] = emit
    count = lambda m: jnp.sum(jnp.sum(m.astype(jnp.int32), axis=2), axis=1,
                              keepdims=True)                # (B, 1)
    comp_ref[...] = count(mask)
    emitted_ref[...] = count(emit)


def window_score(leaders: jax.Array, members: jax.Array,
                 leader_slot: jax.Array, lead_gid: jax.Array,
                 gid: jax.Array, leader_ok: jax.Array, member_ok: jax.Array,
                 lead_bucket: jax.Array, bucket: jax.Array,
                 keep: jax.Array, *, normalized: bool = True,
                 allpairs: bool = False, match_bucket: bool = False,
                 new_from: int = 0, refresh_below: int = 0,
                 r1: Optional[float] = None, interpret: bool = False):
    """Fused masked window scoring; see ``ref.window_score_ref`` for the
    argument/return contract (shapes, mask chain, counter semantics)."""
    nw, s, d = leaders.shape
    _, w, _ = members.shape
    bw = BLOCK_WINDOWS
    kernel = functools.partial(
        _window_score_kernel, normalized=normalized, allpairs=allpairs,
        match_bucket=match_bucket, new_from=new_from,
        refresh_below=refresh_below, r1=r1)
    rows = lambda *tail: pl.BlockSpec((bw,) + tail,
                                      lambda i: (i,) + (0,) * len(tail))
    inputs = (leaders, members, leader_slot, lead_gid, gid, leader_ok,
              member_ok, lead_bucket, bucket, keep.reshape(nw, 1))
    sims, emit, comp, emitted = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nw, bw),),
        in_specs=[rows(s, d), rows(w, d),
                  rows(s), rows(s), rows(w),      # leader_slot, lead_gid, gid
                  rows(s), rows(w),               # leader_ok, member_ok
                  rows(s), rows(w),               # lead_bucket, bucket
                  rows(1)],                       # keep
        out_specs=[rows(s, w), rows(s, w), rows(1), rows(1)],
        out_shape=[out_struct((nw, s, w), jnp.float32, *inputs),
                   out_struct((nw, s, w), jnp.bool_, *inputs),
                   out_struct((nw, 1), jnp.int32, *inputs),
                   out_struct((nw, 1), jnp.int32, *inputs)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*inputs)
    return sims, emit, comp.reshape(nw), emitted.reshape(nw)
