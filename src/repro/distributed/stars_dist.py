"""Distributed Stars: the graph-build pipeline on a device mesh.

The mesh build is a backend of the unified session API — constructing
``GraphBuilder(features, cfg, mesh=mesh)`` shards the feature table and the
degree slabs row-wise over the ``data`` axis and runs, per repetition
(paper §4, adapted per DESIGN.md §3):

  1. sketch    — each `data` shard sketches its own points (no comms) and
                 packs the hash words + random tiebreak into multi-word
                 sort keys,
  2. sort      — distributed sample-sort of (key, gid) pairs straight to
                 per-shard WINDOW SLOT BLOCKS
                 (sorter.distributed_window_blocks): one reduce-scatter in
                 window-slot space hands each shard the contiguous
                 ~n_windows/p rows it owns — the same total order as the
                 single-device ``jax.lax.sort``, never replicated,
  3. window    — each shard reshapes its slot block into ITS window rows;
                 leader sampling and refresh masks are keyed by global
                 window row (core/stars.py ``_score_windows`` row-slice
                 mode), so draws match the single-device path exactly,
  4. join+score— :func:`fetch_rows_all_to_all` (this module) fetches the
                 feature (+ prefilter) rows of each shard's window slots
                 from their owner shards in one explicit request/response
                 all_to_all pair (the DHT / shuffle-join analogue, now a
                 metered exchange instead of an XLA-inserted gather), and
                 each shard scores ONLY its ~n_windows/p rows — per-shard
                 scoring FLOPs are O(n*W/p),
  5. emit      — :func:`accumulate_all_to_all` (this module) buckets each
                 emitted (node, nbr, w) insertion triple by the shard that
                 owns the node's slab row, ships ALL cross-shard edge
                 traffic in ONE all_to_all, and folds the received triples
                 into the local slab shard with the regular accumulator
                 machinery.  No XLA-inserted scatter/gather collectives
                 remain on the emit or feature-join paths, and every
                 all_to_all exchange's cross-shard bytes are recorded in
                 ``accumulator.transfer_stats['all_to_all_bytes']``
                 (off-diagonal slices only — exactly 0 at p=1; the sort's
                 O(4 bytes/point) id reduce-scatter stays unrecorded, like
                 the replicated-permutation psum it replaced).

The host never sees per-repetition edges: one slab fetch per ``finalize()``
produces the Graph, the same single-transfer contract as the single-device
backend.  Because phases 2-4 reproduce the single-device order, draws and
floats exactly — every global window row is scored exactly once, by one
shard — and phase 5 routes every triple to its owning row before the same
top-k fold, the mesh build is **edge-for-edge identical** to the
single-device build (tests/test_mesh_parity.py).  See
``repro.core.builder._MeshBackend`` for the driver; this module keeps the
fetch + emit primitives and the legacy one-shot entry point.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import all_to_all

from repro.core.spanner import Graph
from repro.core.stars import StarsConfig
from repro.distributed.sorter import (exchange_capacity, pack_bit_fields,
                                      unpack_bit_fields)
from repro.graph import accumulator as acc_lib

_U32_ONES = jnp.uint32(0xFFFFFFFF)


def _emit_capacity(m2: int, p: int, capacity_factor: float) -> int:
    """Per-destination-shard triple capacity of one emit exchange.

    Delegates to :func:`repro.distributed.sorter.exchange_capacity` — the
    exact-integer sizing shared by every fixed-shape exchange (the float
    product it replaces could under-size tera-scale buffers).
    """
    return exchange_capacity(m2, p, capacity_factor)


def _emit_widths(n_pad: int, p: int, exact_weights: bool):
    """Packed emit-triple field widths ``(loc, nbr, weight)`` in bits.

    A triple ships as ``loc`` (destination-local slab row,
    ceil(log2(rows + 1)) bits — the all-ones value is reserved as the
    sentinel, which ``int.bit_length`` leaves >= rows), ``nbr`` (global
    gid, sized by the padded table) and the weight (float32 bits, or the
    top 16 = bfloat16 when ``exact_weights`` is False) — typically 2
    words instead of the 3 fixed int32 words this packing replaced.
    """
    rows = n_pad // p
    return (int(rows).bit_length(), int(n_pad).bit_length(),
            32 if exact_weights else 16)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("mesh", "axis", "capacity_factor",
                                    "exact_weights"))
def _emit_exchange(slab_nbr, slab_w, slab_ver, *streams,
                   mesh, axis: str, capacity_factor: float,
                   exact_weights: bool):
    """shard_map body wrapper: bucket-by-owner -> one all_to_all -> fold.

    ``streams`` is one or more flattened (src, dst, w, valid) quadruples —
    consecutive repetitions coalesce their emits into ONE exchange by
    passing several (builder.run_round_pair); locals are concatenated
    INSIDE the shard body, so no resharding collective is inserted.
    Triples cross the wire bit-packed (``_emit_widths``).
    """
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]
    n_pad = slab_nbr.shape[0]
    rows = n_pad // p
    widths = _emit_widths(n_pad, p, exact_weights)
    nwords = -(-sum(widths) // 32)
    ns = len(streams) // 4

    def emit_shard(nbr_l, w_l, ver_l, *stream_l):
        src_l = jnp.concatenate([stream_l[4 * i] for i in range(ns)])
        dst_l = jnp.concatenate([stream_l[4 * i + 1] for i in range(ns)])
        w_c = jnp.concatenate([stream_l[4 * i + 2] for i in range(ns)])
        ok_c = jnp.concatenate([stream_l[4 * i + 3] for i in range(ns)])
        # self-loop / invalid-id exclusion happens HERE, on global ids
        ok = ok_c & (src_l >= 0) & (dst_l >= 0) & (src_l != dst_l)
        # one insertion triple per endpoint (same doubling as accumulate)
        node = jnp.concatenate([src_l, dst_l]).astype(jnp.int32)
        nbr = jnp.concatenate([dst_l, src_l]).astype(jnp.int32)
        ww = jnp.concatenate([w_c, w_c]).astype(jnp.float32)
        ok2 = jnp.concatenate([ok, ok])
        m2 = node.shape[0]
        cap_send = _emit_capacity(m2, p, capacity_factor)

        # bucket by the shard owning the node's slab row (block row layout)
        owner = jnp.where(ok2, jnp.clip(node // rows, 0, p - 1), p)
        iota = jnp.arange(m2, dtype=jnp.int32)
        owner_s, idx_s = jax.lax.sort((owner.astype(jnp.int32), iota),
                                      num_keys=1)
        start = jnp.searchsorted(owner_s, jnp.arange(p)).astype(jnp.int32)
        rank = iota - start[jnp.clip(owner_s, 0, p - 1)]
        live = owner_s < p
        keep = live & (rank < cap_send)
        dropped = jnp.sum(live & ~keep).astype(jnp.int32)[None]

        node_s = node[idx_s]
        # ship the row in the DESTINATION shard's local coordinates
        loc = (node_s - owner_s * rows).astype(jnp.uint32)
        ww_s = ww[idx_s]
        if exact_weights:
            wfield = jax.lax.bitcast_convert_type(ww_s, jnp.uint32)
        else:
            wfield = jax.lax.bitcast_convert_type(
                ww_s.astype(jnp.bfloat16), jnp.uint16).astype(jnp.uint32)
        vals = pack_bit_fields((loc, nbr[idx_s].astype(jnp.uint32), wfield),
                               widths)                     # (m2, nwords)
        send = jnp.full((p, cap_send, nwords), _U32_ONES)
        b_idx = jnp.where(keep, owner_s, 0)
        r_idx = jnp.where(keep, rank, cap_send)            # OOB -> dropped
        send = send.at[b_idx, r_idx].set(vals, mode="drop")

        # THE exchange: every cross-shard edge insertion of this round
        recv = all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=False)
        recv = recv.reshape(-1, nwords)
        loc_u, nbr_u, w_u = unpack_bit_fields(recv, widths)
        node_r = loc_u.astype(jnp.int32)
        nbr_r = nbr_u.astype(jnp.int32)
        if exact_weights:
            w_r = jax.lax.bitcast_convert_type(w_u, jnp.float32)
        else:
            w_r = jax.lax.bitcast_convert_type(w_u << jnp.uint32(16),
                                               jnp.float32)
        # sentinel slots unpack loc all-ones >= rows (fields are unsigned)
        ok_r = node_r < rows

        state = acc_lib._fold_triples(
            acc_lib.EdgeAccumulator(nbr=nbr_l, w=w_l, ver=ver_l),
            node_r, nbr_r, w_r, ok_r)
        return state.nbr, state.w, state.ver, dropped

    return shard_map(
        emit_shard, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis))
        + tuple(P(axis) for _ in streams),
        out_specs=(P(axis, None), P(axis, None), P(axis), P(axis)),
    )(slab_nbr, slab_w, slab_ver, *streams)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "capacity_factor"))
def _fetch_exchange(table, *gid_parts, mesh, axis: str,
                    capacity_factor: float):
    """shard_map body wrapper: request rows by owner -> two all_to_alls.

    ``gid_parts`` is one or more per-slot gid arrays — consecutive
    repetitions coalesce their feature fetches into ONE request/response
    pair by passing several (builder.run_round_pair).  Parts concatenate
    INSIDE the shard body (no resharding collective) and the answers are
    split back out per part, so callers see per-part (rows, ok) results.
    """
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]
    rows = table.shape[0] // p              # feature rows per owner shard
    d = table.shape[1]
    nparts = len(gid_parts)

    def fetch_shard(table_l, *gid_ls):
        sizes = [g.shape[0] for g in gid_ls]
        gid_l = jnp.concatenate(gid_ls)
        s = gid_l.shape[0]
        cap = exchange_capacity(s, p, capacity_factor)
        live = gid_l >= 0
        owner = jnp.where(live, jnp.clip(gid_l // rows, 0, p - 1), p)
        iota = jnp.arange(s, dtype=jnp.int32)
        owner_s, idx_s = jax.lax.sort((owner.astype(jnp.int32), iota),
                                      num_keys=1)
        start = jnp.searchsorted(owner_s, jnp.arange(p)).astype(jnp.int32)
        rank = iota - start[jnp.clip(owner_s, 0, p - 1)]
        live_s = owner_s < p
        keep = live_s & (rank < cap)
        dropped = jnp.sum(live_s & ~keep).astype(jnp.int32)[None]

        # request rows in the OWNER's local coordinates
        loc = gid_l[idx_s] - owner_s * rows
        b_idx = jnp.where(keep, owner_s, 0)
        r_idx = jnp.where(keep, rank, cap)             # OOB -> dropped
        send_req = jnp.full((p, cap), -1, jnp.int32).at[b_idx, r_idx].set(
            jnp.where(keep, loc, -1), mode="drop")
        recv_req = all_to_all(send_req, axis, split_axis=0, concat_axis=0,
                              tiled=False)             # (p, cap) asks for me
        ok_req = (recv_req >= 0) & (recv_req < rows)
        resp = table_l[jnp.clip(recv_req, 0, rows - 1)]
        resp = jnp.where(ok_req[..., None], resp, 0)   # (p, cap, d)
        recv_rows = all_to_all(resp, axis, split_axis=0, concat_axis=0,
                               tiled=False)            # answers, my layout
        got = recv_rows[b_idx, jnp.where(keep, rank, 0)]
        out = jnp.zeros((s, d), table_l.dtype).at[idx_s].set(
            jnp.where(keep[:, None], got, 0))
        ok = jnp.zeros((s,), bool).at[idx_s].set(keep)
        outs, oks, off = [], [], 0
        for sz in sizes:
            outs.append(out[off:off + sz])
            oks.append(ok[off:off + sz])
            off += sz
        return (*outs, *oks, dropped)

    return shard_map(
        fetch_shard, mesh=mesh,
        in_specs=(P(axis, None),) + tuple(P(axis) for _ in gid_parts),
        out_specs=tuple(P(axis, None) for _ in gid_parts)
        + tuple(P(axis) for _ in gid_parts) + (P(axis),),
    )(table, *gid_parts)


def fetch_rows_all_to_all(table: jax.Array, gids: jax.Array, *, mesh,
                          axis: str = "data", capacity_factor: float = 2.0):
    """Gather ``table`` rows for per-shard gid lists via explicit exchanges.

    The owner-keyed feature fetch of the windows-sharded scoring phase
    (core/builder.py ``_MeshBackend``): each shard holds the gids of the
    window slots it will score (``sorter.distributed_window_blocks``) and
    needs those points' feature rows, which live wherever the row-block
    layout put them (gid // (n_pad/p)).  Same bucket-by-owner + fixed
    capacity + single all_to_all pattern as :func:`accumulate_all_to_all`,
    doubled into a request/response pair:

      1. bucket my gids by owner shard, localize, ship the (p, cap) int32
         request buffer in one all_to_all,
      2. every owner gathers the asked-for rows from its local table block
         and ships the (p, cap, d) response back in a second all_to_all
         (the answers land aligned with my request slots),
      3. scatter responses back to slot order.

    This makes the scoring-phase feature join an explicit, metered
    exchange instead of an XLA-inserted gather collective: both buffers
    are recorded in ``transfer_stats['all_to_all_bytes']`` (cross-shard
    slices only — the diagonal never moves).  Per shard the volume is
    O(slots/p * d): each shard fetches features for its ~n/p window slots
    ONCE per repetition, the distributed analogue of the single-device
    path reading each member row once per window it appears in.

    Over-capacity requests are dropped and counted, and the affected slot
    comes back with ``ok`` False — the scorer invalidates it (a counted,
    graceful comparison loss, never a garbage similarity).  Zero drops at
    the default factor: slot owners are hash-random, so per-owner request
    counts concentrate at slots/p with 2x headroom.

    ``gids`` may be a single (S,) array or a TUPLE of arrays — the latter
    coalesces the fetches of consecutive repetitions into the same
    request/response pair (amortizing the two all_to_all launches across
    a repetition pair); the return becomes per-part tuples.

    Args:
      table: (n_pad, d) row-sharded table (features, or features with
        packed prefilter words bitcast alongside); n_pad % p == 0.
      gids:  (S,) int32 global ids per slot, -1 for empty slots; sharded.
        Or a tuple of such arrays to batch several fetches.
    Returns:
      (rows (S, d) slot-aligned, ok (S,) bool, dropped (p,) int32); with a
      tuple input, ``rows`` and ``ok`` are per-part tuples.
    """
    p = mesh.shape[axis]
    is_tuple = isinstance(gids, (tuple, list))
    parts = tuple(gids) if is_tuple else (gids,)
    if table.shape[0] % p:
        raise ValueError(f"table rows {table.shape[0]} not divisible by "
                         f"mesh axis {p}")
    for g in parts:
        if g.shape[0] % p:
            raise ValueError(f"slot count {g.shape[0]} not divisible by "
                             f"mesh axis {p}")
    total = sum(g.shape[0] for g in parts)
    cap = exchange_capacity(total // p, p, capacity_factor)
    acc_lib.record_all_to_all(p * (p - 1) * cap * 4)               # requests
    acc_lib.record_all_to_all(p * (p - 1) * cap * table.shape[1] * 4)
    res = _fetch_exchange(table, *parts, mesh=mesh, axis=axis,
                          capacity_factor=capacity_factor)
    n = len(parts)
    outs, oks, dropped = res[:n], res[n:2 * n], res[2 * n]
    if is_tuple:
        return outs, oks, dropped
    return outs[0], oks[0], dropped


def accumulate_all_to_all(state: acc_lib.EdgeAccumulator,
                          src, dst, w, valid, *, mesh, axis: str = "data",
                          capacity_factor: float = 4.0,
                          exact_weights: bool = True
                          ) -> Tuple[acc_lib.EdgeAccumulator, jax.Array]:
    """Fold a candidate stream into row-sharded slabs via ONE all_to_all.

    The explicit-emit replacement for relying on XLA scatter collectives:
    each shard doubles its local stream into directed (node, nbr, w)
    insertion triples, buckets them by the shard owning ``node``'s slab row
    (block row layout: row i lives on shard ``i // (n_pad/p)``), and ships
    the stacked fixed-capacity buffers in a single all_to_all.  The
    receiving shard localizes rows and runs the normal accumulator fold
    (``_fold_triples``) on its slab shard — per-row results depend only on
    the per-row candidate multiset, so the sharded fold is edge-for-edge
    identical to a single-device ``accumulate`` of the same stream.

    Over-capacity triples are dropped and *counted* (returned per shard;
    zero for near-uniform hash orders at the default ``capacity_factor``),
    the sorter's graceful-degradation contract.  Exchange volume is
    recorded host-side in ``transfer_stats['all_to_all_bytes']`` at the
    WIRE width: triples ship bit-packed (``_emit_widths``), with
    ``exact_weights=False`` additionally truncating weights to bfloat16
    in flight (the StarsConfig escape hatch keeps them float32).

    ``src``/``dst``/``w``/``valid`` may each be a single array or a TUPLE
    of per-repetition streams (same arity across the four) — the latter
    coalesces the emits of consecutive repetitions into ONE exchange.

    Args:
      state: EdgeAccumulator whose row count is a multiple of the axis size.
      src/dst/w/valid: equally-shaped candidate stream(s) (any rank).
    Returns:
      (new state, (p,) int32 dropped-triple counts).
    """
    p = mesh.shape[axis]
    n_pad = state.nbr.shape[0]
    if n_pad % p:
        raise ValueError(f"slab rows {n_pad} not divisible by mesh axis {p}")
    if not isinstance(src, (tuple, list)):
        src, dst, w, valid = (src,), (dst,), (w,), (valid,)
    streams, m2 = [], 0
    for s_i, d_i, w_i, v_i in zip(src, dst, w, valid):
        s_i, d_i = s_i.ravel(), d_i.ravel()
        w_i, v_i = w_i.ravel(), v_i.ravel()
        pad = (-s_i.shape[0]) % p
        if pad:
            s_i = jnp.pad(s_i, (0, pad), constant_values=-1)
            d_i = jnp.pad(d_i, (0, pad), constant_values=-1)
            w_i = jnp.pad(w_i, (0, pad))
            v_i = jnp.pad(v_i, (0, pad))
        m2 += 2 * (s_i.shape[0] // p)
        streams += [s_i, d_i, w_i, v_i]
    nwords = -(-sum(_emit_widths(n_pad, p, exact_weights)) // 32)
    # p*(p-1) slices: the p diagonal self-buckets of the send buffer never
    # cross the interconnect (all_to_all_bytes is cross-shard-only)
    acc_lib.record_all_to_all(
        p * (p - 1) * _emit_capacity(m2, p, capacity_factor) * nwords * 4)
    nbr, ww, ver, dropped = _emit_exchange(
        state.nbr, state.w, state.ver, *streams,
        mesh=mesh, axis=axis, capacity_factor=capacity_factor,
        exact_weights=exact_weights)
    return acc_lib.EdgeAccumulator(nbr=nbr, w=ww, ver=ver), dropped


def build_graph_distributed(dense: jax.Array, cfg: StarsConfig,
                            mesh: jax.sharding.Mesh) -> Graph:
    """Multi-device Stars build; `dense` is (n, d), sharded or shardable.

    DEPRECATED one-shot wrapper over
    ``GraphBuilder(dense, cfg, mesh=mesh)`` (kept for older call sites).
    """
    from repro.core.builder import GraphBuilder
    builder = GraphBuilder(dense, cfg, mesh=mesh)
    builder.add_reps(cfg.r)
    return builder.finalize()
