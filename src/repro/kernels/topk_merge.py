"""Pallas TPU kernel: per-node top-k degree-slab merge (edge accumulator).

The streaming edge accumulator (graph/accumulator.py) keeps, for every node,
a fixed-capacity slab of its k heaviest candidate edges as `(nbr, w)` rows of
shape (n, k).  Each repetition contributes a bucketed batch of per-node
candidates (n, kin); this kernel fuses the whole slab update into one VMEM
pass per node row:

  1. **group** — a bitonic sorting network orders the row's entries by
     (nbr asc, weight desc), so every neighbour's instances sit side by
     side, heaviest first,
  2. **dedup** — an entry whose left neighbour holds the same nbr loses,
     which is exactly the host merge's "duplicates keep max weight",
  3. **rank and compact** — a second network orders the survivors by
     (weight desc, nbr asc); lanes [0, k) are the new slab, already
     compacted and sorted by weight.

A naive lowering materializes the (n, k + kin) concatenation, an argsort and
two gathers in HBM; here every step stays in VMEM and HBM traffic is exactly
one read of both slabs + one write of the result.

TPU layout: each grid step merges ``BLOCK_ROWS`` node rows (sublanes) at
once.  The slab and the batch are staged side by side in a lane-aligned
VMEM row — slab in lanes [0, k), batch from lane ``round_up(k, 128)`` —
padded with empty lanes to K, the next power of two (512 at k = kin = 250).
The row is held as K / 128 column blocks of 128 lanes.  A network over K
lanes has log2 K (log2 K + 1) / 2 compare-exchange substages (45 at
K = 512), each pairing lane i with lane i ^ d.  For d >= 128 the partner
sits in another block at the same lane, so the substage is a compare and
selects between whole blocks; for d < 128 it is two static rotations of
each block (``pltpu.roll`` by d and 128 - d) and a select by the lane's
bit d.  The sort directions are iota-derived masks.  So a row costs
O(K log^2 K) vector work with static shifts, against the O(K^2) of an
all-pairs comparison.

Empty slots carry nbr = -1 / w = -inf on input and output; inside the
networks they are (nbr = INT32_MAX, w = -inf) and sort to the tail, so
saturation (full slab, heavier batch) and warm-up (half-empty slab) need no
special cases.  The result is the reference's (``ref.topk_merge_ref``) bit
for bit, whatever the order of the inputs, for weights neither NaN nor
subnormal: ranking ties break by neighbour id, and of a neighbour's
equally heavy instances (only +0.0 and -0.0 differ while comparing equal)
the one at the earliest [slab | batch] position survives, as in the
reference's stable sort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.window_score import out_struct

# Node rows per grid step: four sublane tiles, so each (rows, 128) block of
# the networks is four vregs.  A substage is a short chain (rotate, compare,
# select) repeated on every block; more independent vregs per chain keep
# the units busy.  On a v5e, at k = kin = 250, 65,536 rows took 61.5 ms at
# 8 rows, 33.0 ms at 16 and 21.0 ms at 32.
BLOCK_ROWS = 32
LANES = 128
INT_MAX = 2**31 - 1


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bitonic_sort(a: list, b: list, before) -> None:
    """Sort the row held as blocks ``a[j]``, ``b[j]`` (two words per lane)
    in place, so that lane order follows ``before(pa, pb, a, b)``: the
    entry (pa, pb) strictly precedes (a, b).  The order must be total on
    distinct entries; entries that tie are equal, so either may go first."""
    nb = len(a)
    lane = jax.lax.broadcasted_iota(jnp.int32, a[0].shape, 1)
    roll = lambda x, t: pltpu.roll(x, t, 1)
    s = 2
    while s <= nb * LANES:                 # sorted runs of s lanes,
        d = s // 2                         # ascending where lane & s == 0
        while d >= LANES:
            m = d // LANES
            for j in range(nb):
                if j & m:
                    continue
                lo, hi = (j, j + m) if (j * LANES) & s == 0 else (j + m, j)
                t = before(a[hi], b[hi], a[lo], b[lo])
                a[lo], a[hi] = (jnp.where(t, a[hi], a[lo]),
                                jnp.where(t, a[lo], a[hi]))
                b[lo], b[hi] = (jnp.where(t, b[hi], b[lo]),
                                jnp.where(t, b[lo], b[hi]))
            d //= 2
        while d >= 1:
            upper = (lane & d) != 0        # partner at lane - d
            # lanes that keep the later entry of their pair
            later = upper ^ ((lane & s) != 0) if s < LANES else upper
            for j in range(nb):
                lat = later if s < LANES or (j * LANES) & s == 0 else ~later
                pa = jnp.where(upper, roll(a[j], d), roll(a[j], LANES - d))
                pb = jnp.where(upper, roll(b[j], d), roll(b[j], LANES - d))
                take = before(pa, pb, a[j], b[j]) ^ lat
                a[j] = jnp.where(take, pa, a[j])
                b[j] = jnp.where(take, pb, b[j])
            d //= 2
        s *= 2


def _by_nbr(pn, pk, n, key):
    """(nbr asc, weight key desc)."""
    return (pn < n) | ((pn == n) & (pk > key))


def _by_weight(pw, pn, w, n):
    """(weight desc, nbr asc); +0.0 and -0.0 are one weight."""
    return (pw > w) | ((pw == w) & (pn < n))


def _flip(bits):
    """Between float32 bits and an int32 of the same order (-0.0 just below
    +0.0); its own inverse."""
    return bits ^ ((bits >> 31) & INT_MAX)


def _weight_key(w, pos, width: int):
    """The first network's int32 key of weight ``w`` at row position ``pos``.

    A zero weight's key holds its position, earliest highest, and its sign
    in bit 0: it lies in [0, 2 width), between the keys of the negative
    weights (<= -2) and those of the positive ones, shifted up by 2 width.
    So of a neighbour's zero-weight instances the earliest survives, as in
    the reference's stable sort."""
    o = _flip(jax.lax.bitcast_convert_type(w, jnp.int32))
    zero = 2 * (width - 1 - pos) + (o + 1)
    return jnp.where(o > 0, o + 2 * width, jnp.where(o < -1, o, zero))


def _key_weight(key, width: int):
    """The weight of a ``_weight_key``."""
    o = jnp.where(key >= 2 * width, key - 2 * width,
                  jnp.where(key < 0, key, (key & 1) - 1))
    return jax.lax.bitcast_convert_type(_flip(o), jnp.float32)


def _topk_merge_kernel(snbr_ref, sw_ref, inbr_ref, iw_ref, onbr_ref, ow_ref,
                       nbr_buf, w_buf, *, k: int, kin: int, k_pad: int):
    nbr_buf[...] = jnp.full(nbr_buf.shape, -1, jnp.int32)
    w_buf[...] = jnp.full(w_buf.shape, -jnp.inf, jnp.float32)
    nbr_buf[:, :k] = snbr_ref[...]
    w_buf[:, :k] = sw_ref[...]
    nbr_buf[:, k_pad:k_pad + kin] = inbr_ref[...]
    w_buf[:, k_pad:k_pad + kin] = iw_ref[...]
    width = nbr_buf.shape[1]
    cols = [slice(j, j + LANES) for j in range(0, width, LANES)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (nbr_buf.shape[0], LANES), 1)
    nbr, key = [], []
    for c in cols:
        valid = nbr_buf[:, c] >= 0
        nbr.append(jnp.where(valid, nbr_buf[:, c], INT_MAX))
        key.append(_weight_key(jnp.where(valid, w_buf[:, c], -jnp.inf),
                               lane + c.start, width))

    _bitonic_sort(nbr, key, _by_nbr)

    # dedup: a lane whose left neighbour holds the same nbr loses
    w = []
    for j, n in enumerate(nbr):
        prev = pltpu.roll(nbr[j - 1], 1, 1) if j else jnp.full_like(n, -1)
        left = jnp.where(lane == 0, prev, pltpu.roll(n, 1, 1))
        keep = (n != left) & (n != INT_MAX)
        w.append(jnp.where(keep, _key_weight(key[j], width), -jnp.inf))

    _bitonic_sort(w, nbr, _by_weight)

    for j in range(-(-k // LANES)):
        nbr_buf[:, cols[j]] = jnp.where(w[j] == -jnp.inf, -1, nbr[j])
        w_buf[:, cols[j]] = w[j]
    onbr_ref[...] = nbr_buf[:, :k]
    ow_ref[...] = w_buf[:, :k]


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
    k_pad = _round_up(k, LANES)
    width = 1 << (k_pad + _round_up(kin, LANES) - 1).bit_length()   # K
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
