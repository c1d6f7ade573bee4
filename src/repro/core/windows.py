"""Sort-and-window machinery: TPU-native bucketing (DESIGN.md §3).

The paper's CPU implementation buckets points in hash maps.  On TPU we make
bucketing a *sort* followed by a reshape into fixed-size windows:

  * **LSH mode (Stars 1)**: points sort by a folded bucket id with a random
    tiebreak.  Buckets become contiguous runs; the reshape into windows of
    size W implements the paper's "randomly partition large buckets into
    size-constrained sub-buckets" verbatim (the random tiebreak IS the random
    partition).  A same-bucket mask restores exact bucket semantics inside
    each window.

  * **SortingLSH mode (Stars 2)**: points sort lexicographically by their
    (h_1, ..., h_M) hash words (exact, via lax.sort with num_keys=M), then a
    random shift r ~ [W/2, W] offsets the window boundaries, exactly as in
    the Stars 2 listing.

Everything is fixed-shape: windows are (n_windows, W) slot grids with a
validity mask, so the same jitted program serves every repetition.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hashing

INVALID = jnp.int32(-1)

# Bucket id carried by padding slots (gid -1).  Pad slots used to inherit
# bucket 0 — a *real* folded bucket id — so the validity mask was the only
# thing standing between a pad slot and a phantom same-bucket match with a
# genuine bucket-0 point (tests/test_windows.py
# test_pad_slot_bucket_aliasing_forced_collision forces the collision).
# The sentinel makes the separation structural; the
# single-device scatter and the mesh slot blocks (distributed/sorter.py
# ``distributed_window_blocks``) share this constant so the two paths build
# bit-identical bucket grids.
PAD_BUCKET = jnp.uint32(0xFFFFFFFF)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Windows:
    """Fixed-shape windowed view of one repetition's sorted order.

    Attributes:
      gid:    (n_windows, W) int32 original point ids; -1 on padding slots.
      valid:  (n_windows, W) bool.
      bucket: (n_windows, W) uint32 folded bucket id (LSH mode) or zeros
              (sorting mode, where the window itself is the bucket);
              ``PAD_BUCKET`` on padding slots in either mode.
    """

    gid: jax.Array
    valid: jax.Array
    bucket: jax.Array

    @property
    def n_windows(self) -> int:
        return self.gid.shape[0]

    @property
    def window(self) -> int:
        return self.gid.shape[1]


def _scatter_to_slots(perm_gid: jax.Array, perm_bucket: jax.Array,
                      offset: jax.Array, n_slots: int, w: int) -> Windows:
    """Place the sorted sequence into padded slots starting at ``offset``."""
    n = perm_gid.shape[0]
    slots_gid = jnp.full((n_slots,), INVALID)
    slots_bucket = jnp.full((n_slots,), PAD_BUCKET)
    pos = offset + jnp.arange(n, dtype=jnp.int32)
    slots_gid = slots_gid.at[pos].set(perm_gid)
    slots_bucket = slots_bucket.at[pos].set(perm_bucket)
    gid = slots_gid.reshape(-1, w)
    return Windows(gid=gid, valid=gid >= 0, bucket=slots_bucket.reshape(-1, w))


def window_layout(mode: str, n: int, window: int,
                  shift_key: Optional[jax.Array] = None):
    """(slot offset, padded slot count) for one repetition's window grid.

    The single source of truth for how a sorted sequence of ``n`` points
    lays out into windows: LSH mode starts at slot 0 with ceil(n/W)*W
    slots; SortingLSH mode draws the Stars 2 random first-block size
    r ~ [W/2, W] from ``shift_key`` (offset W - r) and pads one extra
    window of slots.  Consumed by the sort-and-scatter constructors below
    AND by the mesh backend's permutation-fed reconstruction
    (core/builder.py ``_MeshBackend``) — sharing it makes the mesh
    edge-for-edge parity structural rather than two hand-synced copies.
    """
    if mode == "lsh":
        return jnp.int32(0), window_slot_count(mode, n, window)
    if mode != "sorting":
        raise ValueError(f"unknown mode {mode!r}")
    r = jax.random.randint(shift_key, (), window // 2, window + 1)
    offset = (jnp.int32(window) - r).astype(jnp.int32)
    return offset, window_slot_count(mode, n, window)


def window_slot_count(mode: str, n: int, window: int) -> int:
    """Static padded slot count of one repetition's window grid.

    The key-independent half of :func:`window_layout`: the slot count only
    depends on (mode, n, W) — the random SortingLSH shift moves the
    ``offset`` within the fixed grid, never its size — so shard layouts can
    be computed before any per-repetition key exists.
    """
    if mode == "lsh":
        return ((n + window - 1) // window) * window
    if mode != "sorting":
        raise ValueError(f"unknown mode {mode!r}")
    return ((n + window - 1) // window + 1) * window


def shard_row_layout(mode: str, n: int, window: int,
                     p: int) -> Tuple[int, int, int]:
    """Static window-row partition of one repetition's grid over ``p`` shards.

    Maps a shard's block to its global window rows for the windows-sharded
    mesh scoring phase (core/builder.py ``_MeshBackend``): shard ``i`` owns
    the round-robin STRIPED global rows ``{i, i + p, i + 2p, ...}`` (see
    :func:`shard_row_permutation`).  Returns ``(n_windows, rows_per_shard,
    padded_slots)`` where ``n_windows`` is the real global row count
    (``window_slot_count / W``), ``rows_per_shard = ceil(n_windows / p)``
    and ``padded_slots = p * rows_per_shard * W`` (>= the real slot count;
    overflow rows beyond ``n_windows`` hold no points and score nothing).

    Striping is the occupancy-weighted split: window occupancy is
    monotone-structured — full rows first, then one partially-filled tail
    row, then empty padding rows — so a contiguous split hands the last
    shard all of the light tail while the others carry only full rows.
    Round-robin striping spreads the tail across shards (per-shard real-row
    counts differ by at most 1, and the sub-full rows land on distinct
    shards) while keeping shapes static and the split knowable before any
    per-repetition key exists.

    Ownership is defined in *slot* space, after the sorting-mode shift is
    applied (slot = global sort rank + offset, see ``window_layout``), so a
    window whose members straddle two shards' sample-sort output blocks
    still has exactly ONE owner and arrives whole: the sorter's
    reduce-scatter (``distributed_window_blocks``) routes every member to
    the shard owning its slot — physical placement goes through
    :func:`shard_row_permutation` — which plays the role of halo rows at
    block boundaries without any second boundary exchange.
    """
    if p < 1:
        raise ValueError(f"shard count must be >= 1: {p}")
    n_slots = window_slot_count(mode, n, window)
    n_windows = n_slots // window
    rows_per_shard = -(-n_windows // p)
    return n_windows, rows_per_shard, p * rows_per_shard * window


def shard_row_permutation(row, rows_per_shard: int, p: int):
    """Physical position of global window row ``row`` under row striping.

    A bijection on ``[0, p * rows_per_shard)``: global row ``r`` lands at
    physical row ``(r % p) * rows_per_shard + r // p``, i.e. shard
    ``r % p``, local row ``r // p`` — so shard ``i`` scores the strided
    global rows ``i, i + p, i + 2p, ...`` (see :func:`shard_row_layout`
    for why striping levels valid-slot occupancy).  The identity when
    ``p == 1``.  Works elementwise on traced int arrays.
    """
    return (row % p) * rows_per_shard + row // p


def lsh_windows(bucket_id: jax.Array, *, window: int,
                tiebreak: jax.Array) -> Windows:
    """Stars 1 bucketing: sort by (bucket_id, random tiebreak), window, mask.

    Args:
      bucket_id: (n,) uint32 folded sketch (lsh.bucket_key output).
      window:    max bucket size W (the paper's bucket-size cap).
      tiebreak:  (n,) uint32 random priorities (fresh per repetition) — makes
                 the sub-bucket partition of oversized buckets uniformly random.
    """
    n = bucket_id.shape[0]
    gids = jnp.arange(n, dtype=jnp.int32)
    # gid is the last key: residual (bucket, tiebreak) ties resolve by gid,
    # as in the mesh's packed keys, whatever the backend's sort stability
    _, _, perm_gid = jax.lax.sort((bucket_id, tiebreak, gids), num_keys=3)
    perm_bucket = bucket_id[perm_gid]
    offset, n_slots = window_layout("lsh", n, window)
    return _scatter_to_slots(perm_gid, perm_bucket, offset, n_slots, window)


def sorting_lsh_windows(words: jax.Array, *, window: int,
                        shift_key: jax.Array,
                        tiebreak: jax.Array) -> Windows:
    """Stars 2 windowing: exact lexicographic sort + random-shift blocks.

    Args:
      words:     (n, M) uint32 hash words (h_1..h_M per point), compared
                 lexicographically (word 0 first).
      window:    W (paper: W = 16k for k-ANN; W = 250 in experiments).
      shift_key: PRNG key for the random shift r ~ [W/2, W].
      tiebreak:  (n,) uint32 random priorities for tie-breaking equal keys.
    """
    n, m = words.shape
    gids = jnp.arange(n, dtype=jnp.int32)
    operands = tuple(words[:, i] for i in range(m)) + (tiebreak, gids)
    out = jax.lax.sort(operands, num_keys=m + 2)    # gid resolves ties
    perm_gid = out[-1]
    # Random first-block size r in [W/2, W] -> slot offset (W - r) in [0, W/2].
    offset, n_slots = window_layout("sorting", n, window, shift_key)
    return _scatter_to_slots(perm_gid, jnp.zeros((n,), jnp.uint32),
                             offset, n_slots, window)


def global_row_draw(draw, nw: int, row_offset,
                    total_rows: Optional[int], fill,
                    stride: int = 1) -> jax.Array:
    """Gather rows ``row_offset + stride * [0, nw)`` out of a
    globally-shaped PRNG draw.

    ``draw(rows)`` must be a pure function of its row count (e.g. a uniform
    over one captured key): the draw is ALWAYS issued at the global row
    count ``total_rows`` (or ``nw`` when ``total_rows`` is None — the
    single-device case, where the slice is the whole grid) so the stream a
    given global window row receives is independent of how rows are
    partitioned across shards.  ``stride`` > 1 serves the round-robin row
    striping (``shard_row_permutation``): shard i reads global rows
    ``i, i + p, ...`` with ``row_offset=i, stride=p``.  Rows past
    ``total_rows`` (the padded tail of an uneven partition) read ``fill``,
    which callers choose to mean "invalid".  ``row_offset`` may be traced
    (the gather keeps shapes static).
    """
    if total_rows is None:
        return draw(nw)
    full = draw(total_rows)
    idx = jnp.asarray(row_offset, jnp.int32) \
        + jnp.int32(stride) * jnp.arange(nw, dtype=jnp.int32)
    take = jnp.take(full, jnp.minimum(idx, total_rows - 1), axis=0)
    oob = (idx >= total_rows).reshape((nw,) + (1,) * (full.ndim - 1))
    return jnp.where(oob, fill, take)


def sample_leaders(windows: Windows, *, s: int, key: jax.Array,
                   row_offset=0, total_rows: Optional[int] = None,
                   stride: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Sample up to ``s`` uniformly random leaders per window.

    ``windows`` may be a row subset of a larger grid (the windows-sharded
    mesh scoring phase): ``total_rows`` is then the GLOBAL row count and
    the subset holds global rows ``row_offset + stride * [0, nw)``
    (``stride = p`` under round-robin row striping).  The priority draw is
    always shaped by the global grid and gathered, so every shard's rows
    see exactly the draw the single-device path would give them — the
    leader sample is keyed by global window row, not by who scores it.
    The draw is O(total slots) elementwise; the top-k selection (the
    superlinear part) runs on the subset only.

    Returns:
      leader_slot: (n_windows, s) int32 slot index within the window.
      leader_ok:   (n_windows, s) bool — False where a window had fewer than
                   s valid points (excess leader slots are disabled).
    """
    nw, w = windows.gid.shape
    pri = global_row_draw(
        lambda rows: jax.random.uniform(key, (rows, w)), nw,
        row_offset, total_rows, fill=-1.0, stride=stride)
    pri = jnp.where(windows.valid, pri, -1.0)
    vals, slots = jax.lax.top_k(pri, s)
    # valid slots carry uniform draws in [0, 1), invalid slots exactly -1.0:
    # a draw of exactly 0.0 is a VALID leader, so the boundary is inclusive
    # (`> 0.0` silently disabled that leader and could under-fill a window
    # with >= s valid members)
    return slots.astype(jnp.int32), vals >= 0.0
