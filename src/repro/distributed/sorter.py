"""Distributed sample-sort over shard_map — the TeraSort [35] analogue.

The paper sorts nR sketches with TeraSort on a CPU fleet (Appendix C.1).
On a TPU mesh the same job is a classic MPC sample sort along the `data`
axis:

  1. local sort of each shard's keys,
  2. splitter selection: each shard contributes p quantiles; an all_gather
     + sort yields p-1 global splitters,
  3. partition: each key is binned by splitter (lexicographic compare) and
     packed into a fixed-capacity (p, cap, words) send buffer — fixed shapes
     mean over-capacity keys are dropped and *counted* (the same graceful
     degradation as the paper's bucket-size caps; drops are zero for
     near-uniform hash keys unless cap is set adversarially small),
  4. ONE all_to_all exchanges the stacked (keys..., payload) buffer
     (``jax.lax.all_to_all``; bytes recorded in
     ``accumulator.transfer_stats['all_to_all_bytes']``),
  5. local merge-sort of the received keys (invalid slots carry all-ones
     sentinel keys and sort to the tail).

Keys may be **multi-word**: an (n, nk) uint32 matrix sorts
lexicographically by word 0 first — this is what lets the mesh backend
reproduce the single-device SortingLSH order exactly (packed sketch words
as the leading keys, the random tiebreak word after them).  The payload
rides as the FINAL sort key, so ties in every key word resolve by payload
(ascending gid) — the same total order as ``jax.lax.sort`` with a stable
trailing gid operand on one device.

The output is a globally sorted sequence distributed shard-contiguously:
shard i holds keys <= shard i+1's — exactly what SortingLSH windowing
needs.  Two consumers build on it:

  * :func:`distributed_window_blocks` — the mesh build's scoring input:
    every sorted element is scattered at its window SLOT (global rank +
    sorting-mode shift) and a reduce-scatter hands each shard the
    contiguous slot block of the ~n_windows/p window rows it will score
    (``windows.shard_row_layout``), buckets riding along.  Nothing O(n)
    is replicated, and slot-space ownership delivers boundary-straddling
    windows whole to their one owner.
  * :func:`distributed_argsort` — the replicated *global permutation*
    (each shard scatters its payloads at their global ranks, then a psum
    replicates the result); kept for consumers that genuinely need the
    full (n,) view.

Collective cost: one tiny all_gather + one O(n/p) all_to_all (recorded as
cross-shard slices in ``transfer_stats['all_to_all_bytes']``), plus the
O(slots/p)-per-shard reduce-scatter (or psum) of int32 ids — the
roofline-optimal exchange for a single-pass sort.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import all_to_all, axis_size, psum_scatter

SENTINEL = jnp.uint32(0xFFFFFFFF)


def pack_bit_fields(fields: Sequence[jax.Array],
                    widths: Sequence[int]) -> jax.Array:
    """Pack per-row bit fields into a big-endian uint32 word stream.

    ``fields[i]`` is a (n,) uint32 array whose low ``widths[i]`` bits are
    the field value (higher bits are masked off); fields concatenate
    MSB-first into a bitstream laid out over ``ceil(sum(widths) / 32)``
    words, word 0 most significant.  Because the layout is big-endian,
    lexicographic comparison of the packed words equals lexicographic
    comparison of the field tuples — packed keys sort exactly like their
    unpacked multi-word counterparts, at the wire width the run actually
    needs (``distributed_window_blocks`` ``payload_bits`` mode).  Each
    width must be <= 32 (a field spans at most two words); zero-width
    fields are legal no-ops (used to zero-pad the stream so a trailing
    field lands in the LOW bits of the last word).

    Returns (n, nwords) uint32.  Inverse: :func:`unpack_bit_fields`.
    """
    total = sum(widths)
    nwords = -(-total // 32)
    n = fields[0].shape[0]
    words = [jnp.zeros((n,), jnp.uint32) for _ in range(nwords)]
    off = 0
    for f, w in zip(fields, widths):
        if w < 0 or w > 32:
            raise ValueError(f"field width {w} not in [0, 32]")
        if w == 0:
            continue
        f = f.astype(jnp.uint32)
        if w < 32:
            f = f & jnp.uint32((1 << w) - 1)
        end = off + w
        for j in range(off // 32, (end - 1) // 32 + 1):
            wend = 32 * (j + 1)
            if end > wend:          # field continues into the next word
                part = f >> jnp.uint32(end - wend)
            elif end < wend:
                part = f << jnp.uint32(wend - end)
            else:
                part = f
            words[j] = words[j] | part
        off = end
    return jnp.stack(words, axis=-1)


def unpack_bit_fields(words: jax.Array,
                      widths: Sequence[int]) -> Tuple[jax.Array, ...]:
    """Inverse of :func:`pack_bit_fields`: (n, nwords) uint32 -> field tuple.

    Round-trips exactly: ``unpack_bit_fields(pack_bit_fields(fs, ws), ws)``
    recovers every field's low ``ws[i]`` bits (higher input bits were
    masked at pack time).
    """
    total = sum(widths)
    if words.shape[-1] != -(-total // 32):
        raise ValueError(
            f"{words.shape[-1]} words cannot hold {total} bits")
    outs = []
    off = 0
    for w in widths:
        end = off + w
        acc = jnp.zeros(words.shape[:-1], jnp.uint32)
        if w:
            for j in range(off // 32, (end - 1) // 32 + 1):
                wstart, wend = 32 * j, 32 * (j + 1)
                lo_b = max(0, wend - end)
                nb = (wend - max(off, wstart)) - lo_b
                chunk = words[..., j] >> jnp.uint32(lo_b)
                if nb < 32:
                    chunk = chunk & jnp.uint32((1 << nb) - 1)
                acc = acc | (chunk << jnp.uint32(end - min(end, wend)))
        outs.append(acc)
        off = end
    return tuple(outs)


def _packed_payload(last_word: jax.Array, gid_bits: int) -> jax.Array:
    """Recover the int32 payload embedded in a packed key's final bits.

    The all-ones gid field (what SENTINEL rows carry) decodes to -1;
    ``gid_bits = int(n).bit_length()`` guarantees real gids (< n <=
    2^gid_bits - 1) never collide with it.
    """
    mask = jnp.uint32((1 << gid_bits) - 1)
    gid_u = last_word & mask
    return jnp.where(gid_u == mask, jnp.int32(-1), gid_u.astype(jnp.int32))


def _key_words(keys: jax.Array) -> Tuple[jax.Array, ...]:
    """(n,) or (n, nk) uint32 -> tuple of (n,) word columns, most
    significant first."""
    if keys.ndim == 1:
        return (keys,)
    return tuple(keys[:, i] for i in range(keys.shape[1]))


def _lex_less(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> jax.Array:
    """Elementwise a < b for multi-word keys (word 0 most significant)."""
    lt = jnp.zeros(jnp.broadcast_shapes(a[0].shape, b[0].shape), bool)
    eq = jnp.ones_like(lt)
    for aw, bw in zip(a, b):
        lt |= eq & (aw < bw)
        eq &= aw == bw
    return lt


def exchange_capacity(n_local: int, p: int, capacity_factor: float) -> int:
    """Per-destination-shard slot capacity of one fixed-shape exchange.

    Exact integer arithmetic — ``int(capacity_factor * n_local / p) + 1``
    rounds through a float64 product, which at tera-scale ``n_local``
    (>= 2^53 / factor) can land BELOW the true value and silently
    under-size the exchange (extra counted drops where the configured
    headroom should have absorbed the imbalance).  ``as_integer_ratio``
    is exact for every binary float, so ``num * n_local // (den * p)``
    reproduces floor(factor * n_local / p) at any scale.  Shared by the
    sample-sort partition, the feature fetch and the edge emit
    (stars_dist._emit_capacity).
    """
    num, den = float(capacity_factor).as_integer_ratio()
    return num * n_local // (den * p) + 1


_exchange_capacity = exchange_capacity      # internal call sites / back-compat


def _sample_sort_shard(keys: Tuple[jax.Array, ...], payload: jax.Array, *,
                       axis: str, capacity_factor: float,
                       payload_bits: Optional[int] = None):
    """Body run per shard under shard_map.

    keys: tuple of (n_local,) uint32 words (lexicographic, word 0 first);
    payload: (n_local,) int32 (point ids; -1 marks rows to ignore).
    Returns (sorted_keys tuple (p*cap,), sorted_payload, valid, dropped).

    ``payload_bits`` switches on the bit-packed wire format: the payload
    gid is already embedded as the final ``payload_bits`` bits of the last
    key word (``pack_bit_fields``), so the separate payload operand is
    ignored — the keys alone are the total order (the embedded gid IS the
    tiebreak), the exchange ships ``nk`` words instead of ``nk + 1``, and
    the payload is re-derived from the received keys.  Sentinel rows are
    all-ones in every word, whose gid field decodes to -1 exactly as the
    bitcast payload word did.
    """
    p = axis_size(axis)
    nk = len(keys)
    n_local = payload.shape[0]
    cap = _exchange_capacity(n_local, p, capacity_factor)

    # 1) local sort; the payload is the FINAL key, so equal key words
    #    resolve deterministically by ascending id (matches a stable
    #    single-device sort with a trailing gid operand).  Packed keys
    #    carry the gid in their final bits, so the keys alone suffice.
    if payload_bits is None:
        out = jax.lax.sort((*keys, payload), num_keys=nk + 1)
        keys_s, pay_s = out[:nk], out[-1]
    else:
        keys_s = tuple(jax.lax.sort(keys, num_keys=nk))
        pay_s = _packed_payload(keys_s[-1], payload_bits)

    # 2) splitters: p local quantiles -> all_gather -> global splitters
    q_idx = (jnp.arange(p) * n_local) // p
    all_q = tuple(jax.lax.all_gather(kw[q_idx], axis).reshape(-1)
                  for kw in keys_s)                          # nk x (p*p,)
    all_q = jax.lax.sort(all_q, num_keys=nk)
    spl_idx = jnp.arange(1, p) * p
    splitters = tuple(q[spl_idx] for q in all_q)             # nk x (p-1,)

    # 3) partition into fixed-capacity bins: bin = #splitters < key
    #    (lexicographic), so equal keys always land in the same bin
    bins = jnp.sum(_lex_less(tuple(s[None, :] for s in splitters),
                             tuple(k[:, None] for k in keys_s)),
                   axis=1).astype(jnp.int32)                 # sorted asc
    # rank within bin: bins is non-decreasing because keys are sorted
    bin_start = jnp.searchsorted(bins, jnp.arange(p)).astype(jnp.int32)
    rank = jnp.arange(n_local, dtype=jnp.int32) - bin_start[bins]
    live = pay_s >= 0          # payload -1 marks padding: never shipped,
    keep = live & (rank < cap)  # never counted as a dropped key (they sort
    dropped = jnp.sum(live & ~keep).astype(jnp.int32)[None]   # after reals)
    r_idx = jnp.where(keep, rank, cap)     # cap is out of bounds -> dropped

    # 4) ONE exchange: keys (and, unpacked mode, the bitcast payload)
    #    stacked into a (p, cap, wire_words) uint32 buffer; sentinel slots
    #    are all-ones in every word, which decodes as payload -1 in both
    #    wire formats.
    if payload_bits is None:
        vals = jnp.stack(
            keys_s + (jax.lax.bitcast_convert_type(pay_s, jnp.uint32),),
            axis=-1)                                       # (n_local, nk+1)
    else:
        vals = jnp.stack(keys_s, axis=-1)                  # (n_local, nk)
    wire = vals.shape[-1]
    send = jnp.full((p, cap, wire), SENTINEL)
    send = send.at[bins, r_idx].set(vals, mode="drop")
    recv = all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=False)
    recv = recv.reshape(-1, wire)
    recv_k = tuple(recv[:, i] for i in range(nk))

    # 5) local merge (sentinels sort to the tail; payload again final key)
    if payload_bits is None:
        recv_p = jax.lax.bitcast_convert_type(recv[:, nk], jnp.int32)
        out = jax.lax.sort((*recv_k, recv_p), num_keys=nk + 1)
        out_k, out_p = out[:nk], out[-1]
    else:
        out_k = tuple(jax.lax.sort(recv_k, num_keys=nk))
        out_p = _packed_payload(out_k[-1], payload_bits)
    valid = out_p >= 0
    return out_k, out_p, valid, dropped


def _record_exchange(p: int, n_local: int, wire_words: int,
                     capacity_factor: float) -> None:
    """Host-side accounting of one sort exchange's all_to_all volume.

    Counts ``p * (p - 1)`` buffer slices — the p diagonal self-buckets of
    the (p, cap, words) send buffer stay on their own shard and never
    cross the interconnect, so including them (as this used to, p * p)
    over-reported cross-shard traffic by p/(p-1)x (2x at p=2).
    ``transfer_stats['all_to_all_bytes']`` is cross-shard bytes ONLY,
    and is exactly 0 on a 1-shard mesh.  ``wire_words`` is the per-row
    uint32 count actually shipped — bytes are accounted at WIRE width
    (``nk`` packed key words, or ``nk + 1`` with the separate payload
    word), not at any logical unpacked width.
    """
    from repro.graph.accumulator import record_all_to_all
    cap = exchange_capacity(n_local, p, capacity_factor)
    record_all_to_all(p * (p - 1) * cap * wire_words * 4)


def distributed_sort(keys: jax.Array, payload: jax.Array,
                     mesh: jax.sharding.Mesh, *, axis: str = "data",
                     capacity_factor: float = 2.0):
    """Globally sort (keys, payload) sharded over ``axis``.

    ``keys``: (n,) uint32, or (n, nk) uint32 for lexicographic multi-word
    keys (word 0 most significant).  Returns (keys', payload', valid,
    dropped) with the same sharding and key rank; the concatenation of
    shards in axis order is globally sorted.  Rows with payload -1 are
    treated as invalid (they sort by their keys but come back with
    ``valid`` False).
    """
    from jax.sharding import PartitionSpec as P

    words = _key_words(keys)
    nk = len(words)
    p = mesh.shape[axis]
    _record_exchange(p, keys.shape[0] // p, nk + 1, capacity_factor)
    outs = _sort_jit(payload, *words, mesh=mesh, axis=axis,
                     capacity_factor=capacity_factor)
    out_k = outs[0] if nk == 1 else jnp.stack(outs[:nk], axis=-1)
    return out_k, outs[nk], outs[nk + 1], outs[nk + 2]


# shard_map runs EAGERLY unless jitted: every call re-traces the body and
# interprets it shard by shard — seconds of pure overhead per repetition
# (this was most of the mesh build's wall time).  The sort entry points
# therefore route through module-level jits keyed on the static config;
# per-repetition values (payloads, slot offsets) stay traced so rounds
# share one compilation.
@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "capacity_factor"))
def _sort_jit(payload, *words, mesh, axis, capacity_factor):
    from jax.sharding import PartitionSpec as P

    nk = len(words)

    def body(*args):
        out_k, out_p, valid, dropped = _sample_sort_shard(
            args[:nk], args[nk], axis=axis, capacity_factor=capacity_factor)
        return (*out_k, out_p, valid, dropped)

    return shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(axis) for _ in range(nk + 1)),
        out_specs=tuple(P(axis) for _ in range(nk + 3)),
    )(*words, payload)


def distributed_window_blocks(keys: jax.Array, gids: jax.Array,
                              mesh: jax.sharding.Mesh, *,
                              slot_offset: jax.Array, total_slots: int,
                              axis: str = "data",
                              capacity_factor: float = 2.0,
                              bucket_word: Optional[int] = None,
                              payload_bits: Optional[int] = None,
                              window: Optional[int] = None):
    """Sample-sort (keys, gids) and hand each shard its OWN window slot block.

    The windows-sharded successor of :func:`distributed_argsort`: instead
    of collapsing the sort to a replicated (n,) permutation that every
    shard then re-expands into the full window grid, each sorted element
    is scattered at its window SLOT (global sort rank + ``slot_offset`` —
    the same position ``windows._scatter_to_slots`` gives it on one
    device) and a single reduce-scatter leaves shard i holding exactly the
    contiguous ``total_slots / p`` slot block of the window rows it will
    score (``windows.shard_row_layout``).  Because ownership is decided in
    slot space AFTER the sorting-mode shift, a window whose members come
    from several shards' sorted output arrives whole at its one owner —
    no halo exchange, no window ever straddles two owners unscored.

    ``bucket_word`` names the key word carrying the folded LSH bucket id
    (the LSH-mode sort key IS the bucket), which rides the same
    reduce-scatter so the owner can rebuild bucket runs; empty slots come
    back as gid -1 with the ``windows.PAD_BUCKET`` sentinel in either
    mode.

    ``payload_bits`` enables the bit-packed wire format: the caller built
    ``keys`` with :func:`pack_bit_fields` ending in a ``payload_bits``-wide
    gid field, so the sample sort ships keys only (no payload word — see
    ``_sample_sort_shard``) and ``gids`` is consulted solely for shapes.
    ``window`` (the window width W) switches slot placement to the
    round-robin row striping of ``windows.shard_row_permutation``, so the
    blocks each shard receives are its STRIDED global window rows
    ``i, i + p, ...`` — the occupancy-levelling split of
    ``windows.shard_row_layout`` — rather than a contiguous range.

    Collective cost per repetition: the sample sort's one all_to_all
    (recorded, cross-shard slices only) plus two O(total_slots) int32
    reduce-scatters — the replicated-permutation psum this replaces moved
    the same order of id bytes, so the win is the O(n*W/p) scoring, not
    this exchange.  Over-capacity sort drops surface exactly as in
    ``distributed_argsort``: the slot stays empty and the drop is counted.

    Returns ``(block_gid, block_bucket, dropped)``: (total_slots,) int32 /
    uint32 sharded over ``axis`` (shard i owns slots
    ``[i * total_slots/p, ...)``), and (p,) int32 dropped-key counts.
    """
    words = _key_words(keys)
    nk = len(words)
    p = mesh.shape[axis]
    if total_slots % p:
        raise ValueError(f"total_slots {total_slots} not divisible by {p}")
    if window is not None and total_slots % (p * window):
        raise ValueError(
            f"total_slots {total_slots} not divisible by p*W {p * window}")
    _record_exchange(p, gids.shape[0] // p,
                     nk if payload_bits is not None else nk + 1,
                     capacity_factor)
    return _window_blocks_jit(
        jnp.asarray(slot_offset, jnp.int32), gids, *words, mesh=mesh,
        axis=axis, capacity_factor=capacity_factor, total_slots=total_slots,
        bucket_word=bucket_word, payload_bits=payload_bits, window=window)


# see _sort_jit: jit the shard_map so per-repetition calls (slot_offset is
# traced — it changes every round) reuse one compiled program
@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "capacity_factor",
                                    "total_slots", "bucket_word",
                                    "payload_bits", "window"))
def _window_blocks_jit(slot_offset, gids, *words, mesh, axis,
                       capacity_factor, total_slots, bucket_word,
                       payload_bits, window):
    from jax.sharding import PartitionSpec as P

    from repro.core.windows import PAD_BUCKET, shard_row_permutation

    nk = len(words)
    p = mesh.shape[axis]

    def body(offset, *args):
        out_k, out_p, valid, dropped = _sample_sort_shard(
            args[:nk], args[nk], axis=axis, capacity_factor=capacity_factor,
            payload_bits=payload_bits)
        local_count = jnp.sum(valid).astype(jnp.int32)
        counts = jax.lax.all_gather(local_count, axis)       # (p,)
        me = jax.lax.axis_index(axis)
        rank0 = jnp.sum(jnp.where(jnp.arange(p) < me, counts, 0))
        local_rank = jnp.cumsum(valid).astype(jnp.int32) - valid
        # dropped/invalid rows aim out of bounds -> mode="drop"
        slot = jnp.where(valid, offset + rank0 + local_rank,
                         jnp.int32(total_slots))
        if window is not None:
            # physical placement under row striping: global row r of the
            # grid lives on shard r % p at local row r // p, so the
            # reduce-scatter below hands each shard its strided rows
            rps_rows = total_slots // (p * window)
            gr = slot // window
            col = slot - gr * window
            slot = jnp.where(
                valid,
                shard_row_permutation(gr, rps_rows, p) * window + col,
                jnp.int32(total_slots))
        gbuf = jnp.zeros((total_slots,), jnp.int32).at[slot].add(
            out_p + 1, mode="drop")
        block_gid = psum_scatter(gbuf, axis, scatter_dimension=0,
                                 tiled=True) - 1
        if bucket_word is None:
            block_bucket = jnp.where(block_gid >= 0, jnp.uint32(0),
                                     PAD_BUCKET)
        else:
            bw = jnp.where(valid, out_k[bucket_word], jnp.uint32(0))
            bbuf = jnp.zeros((total_slots,), jnp.uint32).at[slot].add(
                bw, mode="drop")
            bsum = psum_scatter(bbuf, axis, scatter_dimension=0, tiled=True)
            block_bucket = jnp.where(block_gid >= 0, bsum, PAD_BUCKET)
        return block_gid, block_bucket, dropped

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(),) + tuple(P(axis) for _ in range(nk + 1)),
        out_specs=(P(axis), P(axis), P(axis)),
    )(slot_offset, *words, gids)


def distributed_argsort(keys: jax.Array, gids: jax.Array,
                        mesh: jax.sharding.Mesh, n_out: int, *,
                        axis: str = "data", capacity_factor: float = 2.0):
    """Global sort permutation of (keys, gids), replicated on every shard.

    The sample-sort output is shard-contiguous; here each shard computes
    the *global rank* of its slice (all_gather of the p valid-counts ->
    prefix offset), scatters its payloads at those ranks into an (n_out,)
    buffer and a psum replicates the result — an O(n) collective on int32
    ids, never on feature rows.  Slot i of the result is the gid with
    global rank i; -1 marks ranks that lost their element to a capacity
    drop (``dropped`` > 0, zero for near-uniform keys).

    Rows with gid -1 (padding) are excluded from the permutation entirely:
    give them all-ones keys so they cannot displace real keys mid-stream.
    """
    words = _key_words(keys)
    p = mesh.shape[axis]
    _record_exchange(p, gids.shape[0] // p, len(words) + 1, capacity_factor)
    return _argsort_jit(gids, *words, mesh=mesh, axis=axis,
                        capacity_factor=capacity_factor, n_out=n_out)


# see _sort_jit: jitted so repeated calls share one compiled program
@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "capacity_factor",
                                    "n_out"))
def _argsort_jit(gids, *words, mesh, axis, capacity_factor, n_out):
    from jax.sharding import PartitionSpec as P

    nk = len(words)
    p = mesh.shape[axis]

    def body(*args):
        out_k, out_p, valid, dropped = _sample_sort_shard(
            args[:nk], args[nk], axis=axis, capacity_factor=capacity_factor)
        local_count = jnp.sum(valid).astype(jnp.int32)
        counts = jax.lax.all_gather(local_count, axis)       # (p,)
        me = jax.lax.axis_index(axis)
        offset = jnp.sum(jnp.where(jnp.arange(p) < me, counts, 0))
        local_rank = jnp.cumsum(valid).astype(jnp.int32) - valid
        grank = jnp.where(valid, offset + local_rank, n_out)  # n_out: drop
        perm = jnp.zeros((n_out,), jnp.int32).at[grank].add(
            out_p + 1, mode="drop")
        perm = jax.lax.psum(perm, axis)
        return perm - 1, dropped

    return shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(axis) for _ in range(nk + 1)),
        out_specs=(P(), P(axis)),
    )(*words, gids)
