"""Pallas TPU kernel: per-node top-k degree-slab merge (edge accumulator).

The streaming edge accumulator (graph/accumulator.py) keeps, for every node,
a fixed-capacity slab of its k heaviest candidate edges as `(nbr, w)` rows of
shape (n, k).  Each repetition contributes a bucketed batch of per-node
candidates (n, kin); this kernel fuses the whole slab update into one VMEM
pass per node row:

  1. **dedup** — the same neighbour may already sit in the slab (earlier
     repetition) or appear twice in the batch; only its max-weight instance
     survives, which matches the host merge's "duplicates keep max weight",
  2. **rank** — surviving entries are ranked by (weight desc, nbr asc),
  3. **compact** — the top k are scattered to their rank position via a
     one-hot reduction (TPU has no in-register scatter), so the output slab
     stays sorted by weight.

A naive lowering materializes the (n, k + kin) concatenation, an argsort and
two gathers in HBM; here every step stays in VMEM and HBM traffic is exactly
one read of both slabs + one write of the result.

TPU layout: each grid step merges ``BLOCK_ROWS`` node rows (sublanes) at
once.  The slab and the batch are staged side by side in a lane-aligned
VMEM row — slab in lanes [0, k), batch from lane ``round_up(k, 128)`` —
with empty (-1 / -inf) padding between and after, which keeps the original
[slab | batch] position order of every real entry.  All-pairs comparisons
then run as K - 1 lane rotations of that row (``pltpu.roll``; K = the
padded width, 512 at k = kin = 250) instead of (K, K) matrices, so the
working set is a handful of (BLOCK_ROWS, K) vectors regardless of k.  A
rotation pairs every lane with each other lane exactly once over the K - 1
shifts whichever way it turns, and the rotated position row carries the
tie-break order along, so the result does not depend on the rotation's
direction.  The compaction is a third rotation sweep: the entry whose rank
equals a lane's index lands there.

Empty slots carry nbr = -1 / w = -inf and sort to the tail, so saturation
(full slab, heavier batch) and warm-up (half-empty slab) need no special
cases.  Ranking ties break deterministically by neighbour id; two entries
with equal weight AND equal neighbour are duplicates by definition and the
earlier position wins, so ranks are unique among survivors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.window_score import out_struct

# Node rows per grid step: one sublane tile, so each (rows, K) operand of
# the rotation sweeps is K / 128 vregs and the sweeps stay in registers.
BLOCK_ROWS = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _topk_merge_kernel(snbr_ref, sw_ref, inbr_ref, iw_ref, onbr_ref, ow_ref,
                       nbr_buf, w_buf, *, k: int, kin: int, k_pad: int):
    nbr_buf[...] = jnp.full(nbr_buf.shape, -1, jnp.int32)
    w_buf[...] = jnp.full(w_buf.shape, -jnp.inf, jnp.float32)
    nbr_buf[:, :k] = snbr_ref[...]
    w_buf[:, :k] = sw_ref[...]
    nbr_buf[:, k_pad:k_pad + kin] = inbr_ref[...]
    w_buf[:, k_pad:k_pad + kin] = iw_ref[...]
    nbr = nbr_buf[...]                                       # (R, K)
    valid = nbr >= 0
    w = jnp.where(valid, w_buf[...], -jnp.inf)
    kk = nbr.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, nbr.shape, 1)
    roll = lambda x, t: pltpu.roll(x, t, 1)

    # 1) dedup: lane i is a duplicate if some j holds the same neighbour
    #    and beats it (heavier, or equally heavy at an earlier position).
    def dup_step(t, dup):
        nbr_j, w_j, pos_j = roll(nbr, t), roll(w, t), roll(pos, t)
        beats = (w_j > w) | ((w_j == w) & (pos_j < pos))
        return dup | ((nbr_j == nbr) & (nbr_j >= 0) & beats).astype(jnp.int32)

    dup = jax.lax.fori_loop(1, kk, dup_step, jnp.zeros_like(nbr))
    keep = (valid & (dup == 0)).astype(jnp.int32)

    # 2) rank among survivors by (w desc, nbr asc); unique post-dedup.
    def rank_step(t, rank):
        keep_j, w_j, nbr_j = roll(keep, t), roll(w, t), roll(nbr, t)
        outranks = (w_j > w) | ((w_j == w) & (nbr_j < nbr))
        return rank + jnp.where(outranks, keep_j, 0)

    rank = jax.lax.fori_loop(1, kk, rank_step, jnp.zeros_like(nbr))
    dest = jnp.where((keep > 0) & (rank < k), rank, -1)     # -1: no lane

    # 3) compact: the survivor of rank r moves to lane r.
    def place_step(t, out):
        out_nbr, out_w = out
        hit = roll(dest, t) == pos
        return (jnp.where(hit, roll(nbr, t), out_nbr),
                jnp.where(hit, roll(w, t), out_w))

    out_nbr, out_w = jax.lax.fori_loop(
        0, kk, place_step,
        (jnp.full_like(nbr, -1), jnp.full_like(w, -jnp.inf)))
    onbr_ref[...] = out_nbr[:, :k]
    ow_ref[...] = out_w[:, :k]


def topk_merge(slab_nbr: jax.Array, slab_w: jax.Array,
               inc_nbr: jax.Array, inc_w: jax.Array, *,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Merge per-node candidate batches into top-k degree slabs.

    slab_nbr/slab_w: (n, k) current slabs (int32 / float32; -1 / -inf empty).
    inc_nbr/inc_w:   (n, kin) incoming per-node candidates, same encoding.
    Returns the updated (n, k) slabs, rows sorted by weight descending.
    """
    n, k = slab_nbr.shape
    kin = inc_nbr.shape[1]
    k_pad = _round_up(k, 128)
    width = k_pad + _round_up(kin, 128)
    rb = BLOCK_ROWS
    rows = lambda cols: pl.BlockSpec((rb, cols), lambda i: (i, 0))
    inputs = (slab_nbr, slab_w.astype(jnp.float32), inc_nbr,
              inc_w.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_topk_merge_kernel, k=k, kin=kin, k_pad=k_pad),
        grid=(pl.cdiv(n, rb),),
        in_specs=[rows(k), rows(k), rows(kin), rows(kin)],
        out_specs=[rows(k), rows(k)],
        out_shape=[out_struct((n, k), jnp.int32, *inputs),
                   out_struct((n, k), jnp.float32, *inputs)],
        scratch_shapes=[pltpu.VMEM((rb, width), jnp.int32),
                        pltpu.VMEM((rb, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*inputs)
