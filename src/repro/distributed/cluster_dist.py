"""Zero-gather clustering on the mesh-sharded degree slabs.

The paper's sparse graph exists to serve downstream clustering (§5 runs
Affinity clustering; Theorem 2.5/A.3 reduce approximate single-linkage to
connected components of the two-hop spanner) — but running those host-side
means ``finalize()`` gathers the whole (n, k) slab image first, which at
tera-scale is exactly the bottleneck the distributed build removed.  This
module runs both primitives directly on the row-sharded slabs instead:

  * :func:`connected_components_mesh` — min-label propagation.  Labels are
    an (n_pad,) int32 vector sharded like the slab rows (block layout,
    owner = gid // (n_pad/p)).  Per round each shard (1) PULLS the labels
    of its slab neighbours through :func:`stars_dist.fetch_rows_all_to_all`
    with the label vector as a 1-column table, (2) takes the per-row min,
    (3) PUSHES the row min back to each neighbour's owner through the same
    bucket-by-owner fixed-capacity all_to_all idiom (scatter-min), then
    (4) pointer-jumps ``label = label[label]`` — more label pulls — until
    stable.  Monotone decreasing labels converge to the min gid of each
    component, which is bit-identical to the host union-find's root
    (``connected_components_np`` hooks larger roots onto smaller, so its
    roots are component minima too).

  * :func:`affinity_mesh` — sharded Boruvka/Affinity.  Per round each
    shard pulls the cluster labels of its slab neighbours, builds
    (lo_cluster, hi_cluster, lo_node, hi_node, w) records for its
    inter-cluster slab entries and ships them to the owner of
    ``lo_cluster``; the owner dedups the doubled slab entries by node
    pair, computes the mean original weight per cluster pair (true
    average linkage over the slab multigraph), ships each pair's
    candidate to the hi-side owner in a second exchange, selects every
    local cluster's best incident edge (max weight, smallest-mate
    tie-break), and hooks ``parent[max(c, b)] <- min(c, b)`` via
    scatter-min.  Distributed pointer jumping compresses ``parent``, and
    ``labels = parent[labels]`` is one more label pull.

Every exchange is the owner-keyed all_to_all pattern of
``distributed/stars_dist.py`` and is metered under
``transfer_stats['all_to_all_*']`` and ``['all_to_all_bytes.cluster']``
(cross-shard slices only, 0 at p=1).
Nothing O(n * k) ever leaves the devices: ``transfer_stats['edge_fetches']``
and ``['bytes']`` stay untouched (asserted in tests/test_cluster.py); the
only device->host traffic is the final (n,) int32 label vector, metered
under ``transfer_stats['cluster_label_*']``, plus O(1) convergence /
live-count scalars per round.

Capacity: label owners here are NEIGHBOUR gids — similarity-clustered, not
hash-random — so per-owner request counts can concentrate arbitrarily.
All exchanges therefore default to ``capacity_factor = p`` (full capacity,
drops impossible); at bench scale the buffers are small, and callers can
trade headroom for wire volume once drop-tolerant variants matter.

Parity: the host ``affinity_clustering`` also recomputes each cluster
pair's mean over the ORIGINAL edge weights every round and breaks
equal-weight ties by the smallest mate id, so both paths make the same
merges and return the same labels (tests/test_cluster.py asserts it at
p=1/2/4).  The means are float32 on the mesh and float64 sums on the host,
so a tie that only the rounding decides could still split them.
Connected components has no weights to average, so it is exact.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import all_to_all

from repro.distributed.sorter import exchange_capacity
from repro.graph import accumulator as acc_lib

_BIG = jnp.int32(2**31 - 1)
_NEG = jnp.float32(-jnp.inf)


def _label_sharding(mesh, axis: str):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis))


def _iota_labels(n_pad: int, mesh, axis: str) -> jax.Array:
    """Identity labels, row-block sharded like the slabs (pad rows label
    themselves: they have no slab entries, so they stay inert singletons)."""
    return jax.device_put(jnp.arange(n_pad, dtype=jnp.int32),
                          _label_sharding(mesh, axis))


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("mesh", "axis", "op", "capacity_factor"))
def _scatter_exchange_jit(vec, idx, val, *, mesh, axis: str, op: str,
                          capacity_factor: float):
    """Owner-keyed scatter-combine: ship (idx, val) to owner(idx), fold.

    The push half of the label-propagation idiom — same bucket-by-owner +
    fixed capacity + single all_to_all as ``stars_dist._emit_exchange``,
    with the fold being elementwise min/max instead of a slab top-k merge.
    ``idx`` entries of -1 are dead slots.
    """
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]
    n_pad = vec.shape[0]
    rows = n_pad // p

    def body(vec_l, idx_l, val_l):
        m = idx_l.shape[0]
        cap = exchange_capacity(m, p, capacity_factor)
        live = idx_l >= 0
        owner = jnp.where(live, jnp.clip(idx_l // rows, 0, p - 1), p)
        iota = jnp.arange(m, dtype=jnp.int32)
        owner_s, pos_s = jax.lax.sort((owner.astype(jnp.int32), iota),
                                      num_keys=1)
        start = jnp.searchsorted(owner_s, jnp.arange(p)).astype(jnp.int32)
        rank = iota - start[jnp.clip(owner_s, 0, p - 1)]
        live_s = owner_s < p
        keep = live_s & (rank < cap)
        dropped = jnp.sum(live_s & ~keep).astype(jnp.int32)[None]

        # ship rows in the OWNER's local coordinates; -1 marks empty slots
        loc = jnp.where(keep, idx_l[pos_s] - owner_s * rows, -1)
        vals = jnp.stack([loc, val_l[pos_s]], axis=-1)
        send = jnp.full((p, cap, 2), -1, jnp.int32)
        b_idx = jnp.where(keep, owner_s, 0)
        r_idx = jnp.where(keep, rank, cap)              # OOB -> dropped
        send = send.at[b_idx, r_idx].set(vals, mode="drop")
        recv = all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=False).reshape(-1, 2)
        loc_r, val_r = recv[:, 0], recv[:, 1]
        ok = (loc_r >= 0) & (loc_r < rows)
        tgt = jnp.where(ok, loc_r, rows)                # rows == OOB, dropped
        if op == "min":
            vec_l = vec_l.at[tgt].min(jnp.where(ok, val_r, _BIG),
                                      mode="drop")
        else:
            vec_l = vec_l.at[tgt].max(jnp.where(ok, val_r, -_BIG),
                                      mode="drop")
        return vec_l, dropped

    return shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=(P(axis), P(axis)))(vec, idx, val)


def _scatter_exchange(vec, idx, val, *, mesh, axis: str, op: str,
                      capacity_factor: float):
    """Metered wrapper: records the exchange's cross-shard wire bytes."""
    p = mesh.shape[axis]
    cap = exchange_capacity(idx.shape[0] // p, p, capacity_factor)
    acc_lib.record_all_to_all(p * (p - 1) * cap * 2 * 4, "cluster")
    return _scatter_exchange_jit(vec, idx, val, mesh=mesh, axis=axis, op=op,
                                 capacity_factor=capacity_factor)


_min2 = jax.jit(jnp.minimum)
_any_neq = jax.jit(lambda a, b: jnp.any(a != b))
_sum_i64 = jax.jit(lambda a: jnp.sum(a.astype(jnp.int32)))
_flatten = jax.jit(lambda a: a.reshape(-1))


def _pull(table_vec, gids, *, mesh, axis: str, capacity_factor: float):
    """Label pull: ``table_vec[gids]`` as an owner-keyed request/response
    exchange (the 1-column-table reuse of ``fetch_rows_all_to_all``)."""
    # lazy: stars_dist pulls in repro.core, which imports back through
    # repro.kernels -> repro.distributed while initializing
    from repro.distributed.stars_dist import fetch_rows_all_to_all
    got, ok, _ = fetch_rows_all_to_all(table_vec[:, None], gids, mesh=mesh,
                                       axis=axis,
                                       capacity_factor=capacity_factor)
    return _flatten(got), ok


def _pointer_jump(vec, *, mesh, axis: str, capacity_factor: float,
                  max_iters: int = 64) -> Tuple[jax.Array, int]:
    """Distributed ``vec = vec[vec]`` to fixpoint (path compression).

    ``vec`` is monotone (vec[i] <= i), so each squaring halves chain depth:
    fixpoint in O(log n_pad) pulls.  The per-iteration convergence check is
    one O(1) scalar sync, not an edge fetch.
    """
    for it in range(max_iters):
        nxt, _ = _pull(vec, vec, mesh=mesh, axis=axis,
                       capacity_factor=capacity_factor)
        nxt = _min2(vec, nxt)
        if not bool(jax.device_get(_any_neq(nxt, vec))):
            return nxt, it + 1
        vec = nxt
    return vec, max_iters


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _cc_local(labels, nbr, nl, okf, *, mesh, axis: str):
    """Per-shard half of one CC round: row min + push candidates.

    Returns (new local labels, push idx (n_pad*k,), push val) — the push
    stream routes each row's min to every neighbour's owner.
    """
    from jax.sharding import PartitionSpec as P

    def body(lab_l, nbr_l, nl_l, ok_l):
        rows_l, k = nbr_l.shape
        nl2 = nl_l.reshape(rows_l, k)
        okm = ok_l.reshape(rows_l, k) & (nbr_l >= 0)
        nl2 = jnp.where(okm, nl2, _BIG)
        m = jnp.minimum(lab_l, nl2.min(axis=1))
        idx = jnp.where(okm, nbr_l, -1).reshape(-1)
        val = jnp.broadcast_to(m[:, None], (rows_l, k)).reshape(-1)
        return m, idx, val

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis), P(axis, None), P(axis), P(axis)),
                     out_specs=(P(axis), P(axis), P(axis)))(
                         labels, nbr, nl, okf)


def connected_components_mesh(nbr: jax.Array, *, n: int, mesh,
                              axis: str = "data", max_rounds: int = 64,
                              capacity_factor: Optional[float] = None
                              ) -> Tuple[np.ndarray, Dict[str, int]]:
    """Connected components of the slab graph, labels never gathered.

    Args:
      nbr: (n_pad, k) int32 row-sharded slab neighbour table (-1 empty);
        the symmetric closure of the slabs is the component graph, exactly
        like ``Graph.from_degree_slabs`` + ``connected_components_np``.
      n: real row count (pad rows are inert singletons and are trimmed).
    Returns:
      (labels (n,) int64 numpy — the min gid of each component, identical
      to the host union-find's roots — and an info dict with the round /
      pull counts).  Raises RuntimeError if ``max_rounds`` is hit before
      convergence (the same contract as ``connected_components_jax``).
    """
    p = mesh.shape[axis]
    n_pad, k = nbr.shape
    if n_pad % p:
        raise ValueError(f"slab rows {n_pad} not divisible by mesh axis {p}")
    cf = float(p) if capacity_factor is None else capacity_factor
    labels = _iota_labels(n_pad, mesh, axis)
    nbr_flat = _flatten(nbr)
    rounds, jumps, converged = 0, 0, False
    for _ in range(max_rounds):
        prev = labels
        nl, okf = _pull(labels, nbr_flat, mesh=mesh, axis=axis,
                        capacity_factor=cf)
        labels, push_idx, push_val = _cc_local(labels, nbr, nl, okf,
                                               mesh=mesh, axis=axis)
        labels, _ = _scatter_exchange(labels, push_idx, push_val, mesh=mesh,
                                      axis=axis, op="min",
                                      capacity_factor=cf)
        labels, j = _pointer_jump(labels, mesh=mesh, axis=axis,
                                  capacity_factor=cf)
        rounds += 1
        jumps += j
        if not bool(jax.device_get(_any_neq(labels, prev))):
            converged = True
            break
    if not converged:
        raise RuntimeError(
            f"connected_components_mesh: labels still changing after "
            f"max_rounds={max_rounds}")
    out = np.asarray(jax.device_get(labels), np.int64)[:n]
    acc_lib.transfer_stats["cluster_label_fetches"] += 1
    acc_lib.transfer_stats["cluster_label_bytes"] += n * 4
    return out, {"rounds": rounds, "jump_pulls": jumps,
                 "converged": converged}


# --------------------------------------------------------------------------- #
# Affinity (sharded Boruvka)
# --------------------------------------------------------------------------- #


def _select_caps(n_pad: int, k: int, p: int) -> Tuple[int, int]:
    """Static capacities of the two in-round record exchanges (full
    capacity — cluster-pair owners are similarity-skewed, never dropped)."""
    rows = n_pad // p
    cap1 = exchange_capacity(rows * k, p, float(p))
    cap2 = exchange_capacity(p * cap1, p, float(p))
    return cap1, cap2


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "min_similarity"))
def _affinity_select(labels, nbr, w, nl, okf, *, mesh, axis: str,
                     min_similarity: Optional[float]):
    """One Boruvka selection on the mesh: records -> means -> best edges.

    Two owner-keyed all_to_alls inside one shard_map program:

      1. every valid inter-cluster slab entry ships
         (lo_c, hi_c, lo_node, hi_node, w_bits) to owner(lo_c),
      2. the owner sorts by (lo_c, hi_c, lo_node, hi_node), dedups the
         doubled slab entries by node pair, segment-means each cluster
         pair's ORIGINAL weights, and ships (hi_c, lo_c, mean_bits) to
         owner(hi_c) so both endpoints see the candidate,
      3. each shard takes its per-local-cluster best candidate (max mean
         weight, smallest mate gid on ties) and emits the hook edge
         ``parent[max(c, mate)] <- min(c, mate)`` as a scatter-min stream.

    Returns (hook_idx (n_pad,), hook_val (n_pad,), per-shard valid-record
    counts (p,)) — the record count drives the host-side stop condition.
    """
    from jax.sharding import PartitionSpec as P

    p = mesh.shape[axis]
    n_pad, k = nbr.shape
    rows = n_pad // p
    cap1, cap2 = _select_caps(n_pad, k, p)
    r1 = p * cap1

    def to_owner(key, cols, cap):
        mm = key.shape[0]
        live = key >= 0
        owner = jnp.where(live, jnp.clip(key // rows, 0, p - 1), p)
        iota = jnp.arange(mm, dtype=jnp.int32)
        owner_s, pos_s = jax.lax.sort((owner.astype(jnp.int32), iota),
                                      num_keys=1)
        start = jnp.searchsorted(owner_s, jnp.arange(p)).astype(jnp.int32)
        rank = iota - start[jnp.clip(owner_s, 0, p - 1)]
        keep = (owner_s < p) & (rank < cap)
        vals = jnp.stack([c[pos_s] for c in cols], axis=-1)
        send = jnp.full((p, cap, len(cols)), _BIG)
        b_idx = jnp.where(keep, owner_s, 0)
        r_idx = jnp.where(keep, rank, cap)              # OOB -> dropped
        send = send.at[b_idx, r_idx].set(vals, mode="drop")
        recv = all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=False)
        return recv.reshape(-1, len(cols))

    def body(lab_l, nbr_l, w_l, nl_l, ok_l):
        row0 = (jax.lax.axis_index(axis) * rows).astype(jnp.int32)
        u_gid = row0 + jnp.arange(rows, dtype=jnp.int32)
        cl_u = lab_l[:, None]                           # (rows, 1)
        cl_v = nl_l.reshape(rows, k)
        okm = ok_l.reshape(rows, k) & (nbr_l >= 0)
        valid = okm & (cl_u != cl_v)
        if min_similarity is not None:
            valid &= w_l >= min_similarity
        lo_c = jnp.minimum(cl_u, cl_v)
        hi_c = jnp.maximum(cl_u, cl_v)
        lo_n = jnp.minimum(u_gid[:, None], nbr_l)
        hi_n = jnp.maximum(u_gid[:, None], nbr_l)
        wbits = jax.lax.bitcast_convert_type(w_l.astype(jnp.float32),
                                             jnp.int32)
        n_rec = jnp.sum(valid).astype(jnp.int32)[None]

        # exchange 1: records to the lo-cluster owner
        key1 = jnp.where(valid, lo_c, -1).reshape(-1)
        cols1 = [x.reshape(-1) for x in
                 (jnp.broadcast_to(lo_c, (rows, k)),
                  jnp.broadcast_to(hi_c, (rows, k)), lo_n, hi_n,
                  jnp.broadcast_to(wbits, (rows, k)))]
        recv1 = to_owner(key1, cols1, cap1)             # (r1, 5)
        rlo, rhi = recv1[:, 0], recv1[:, 1]
        rln, rhn, rwb = recv1[:, 2], recv1[:, 3], recv1[:, 4]
        rvalid = (rlo >= 0) & (rlo != _BIG)
        slo, shi, sln, shn, swb = jax.lax.sort(
            (jnp.where(rvalid, rlo, _BIG), jnp.where(rvalid, rhi, _BIG),
             jnp.where(rvalid, rln, _BIG), jnp.where(rvalid, rhn, _BIG),
             rwb), num_keys=4)
        sw = jax.lax.bitcast_convert_type(swb, jnp.float32)
        svalid = slo != _BIG
        neq_pair = ((slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1]))
        neq_node = (neq_pair | (sln[1:] != sln[:-1]) | (shn[1:] != shn[:-1]))
        first_node = jnp.ones((r1,), bool).at[1:].set(neq_node)
        first_pair = jnp.ones((r1,), bool).at[1:].set(neq_pair)
        uniq = first_node & svalid                      # node-pair dedup
        seg = jnp.cumsum(first_pair.astype(jnp.int32)) - 1
        wsum = jax.ops.segment_sum(jnp.where(uniq, sw, 0.0), seg,
                                   num_segments=r1)
        cnt = jax.ops.segment_sum(uniq.astype(jnp.float32), seg,
                                  num_segments=r1)
        pair_valid = first_pair & svalid
        mw = jnp.where(pair_valid,
                       wsum[seg] / jnp.maximum(cnt[seg], 1.0), _NEG)

        # exchange 2: each pair's candidate to the hi-cluster owner
        key2 = jnp.where(pair_valid, shi, -1)
        mwbits = jax.lax.bitcast_convert_type(mw, jnp.int32)
        recv2 = to_owner(key2, [shi, slo, mwbits], cap2)  # (p*cap2, 3)
        v2 = (recv2[:, 0] >= 0) & (recv2[:, 0] != _BIG)
        c2 = jnp.where(v2, recv2[:, 0] - row0, rows)
        m2 = recv2[:, 1]
        w2 = jnp.where(v2, jax.lax.bitcast_convert_type(recv2[:, 2],
                                                        jnp.float32), _NEG)

        # merged candidate list: lo-side (local) + hi-side (received)
        c1 = jnp.where(pair_valid, slo - row0, rows)
        cc = jnp.concatenate([c1, c2])                  # local cluster row
        mm_ = jnp.concatenate([shi, m2])                # mate cluster gid
        ww_ = jnp.concatenate([mw, w2])
        seg_ids = jnp.clip(cc, 0, rows)                 # rows == trash
        best_w = jax.ops.segment_max(ww_, seg_ids, num_segments=rows + 1)
        is_best = (ww_ == best_w[seg_ids]) & (ww_ > _NEG) & (cc < rows)
        mate = jax.ops.segment_min(jnp.where(is_best, mm_, _BIG), seg_ids,
                                   num_segments=rows + 1)[:rows]
        has = (best_w[:rows] > _NEG) & (mate != _BIG)
        lo_e = jnp.minimum(u_gid, mate)
        hi_e = jnp.maximum(u_gid, mate)
        hook_idx = jnp.where(has, hi_e, -1)
        hook_val = jnp.where(has, lo_e, _BIG)
        return hook_idx, hook_val, n_rec

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis), P(axis, None), P(axis, None),
                               P(axis), P(axis)),
                     out_specs=(P(axis), P(axis), P(axis)))(
                         labels, nbr, w, nl, okf)


@functools.partial(jax.jit, static_argnames=("n",))
def _mask_real(labels, *, n: int):
    """labels of real rows, -1 on pad rows (dead scatter slots)."""
    gid = jnp.arange(labels.shape[0], dtype=jnp.int32)
    return jnp.where(gid < n, labels, -1)


def _live_clusters(labels, *, n: int, mesh, axis: str,
                   capacity_factor: float) -> int:
    """Distinct labels among real rows: scatter-mark + O(1) scalar sum."""
    n_pad = labels.shape[0]
    marks = jax.device_put(jnp.zeros(n_pad, jnp.int32),
                           _label_sharding(mesh, axis))
    marks, _ = _scatter_exchange(marks, _mask_real(labels, n=n),
                                 jnp.ones(n_pad, jnp.int32), mesh=mesh,
                                 axis=axis, op="max",
                                 capacity_factor=capacity_factor)
    return int(jax.device_get(_sum_i64(marks)))


def affinity_mesh(nbr: jax.Array, w: jax.Array, *, n: int, mesh,
                  axis: str = "data", target_clusters: int = 1,
                  max_rounds: int = 32,
                  min_similarity: Optional[float] = None,
                  capacity_factor: Optional[float] = None
                  ) -> Tuple[np.ndarray, Dict[str, int]]:
    """Average-Affinity clustering on the sharded slabs (module docstring).

    Mirrors the host loop's stop conditions: break when live clusters <=
    ``target_clusters``, when no valid inter-cluster records remain, or at
    ``max_rounds``.  Returns ((n,) densified int64 labels, info dict).
    """
    p = mesh.shape[axis]
    n_pad, k = nbr.shape
    if n_pad % p:
        raise ValueError(f"slab rows {n_pad} not divisible by mesh axis {p}")
    cf = float(p) if capacity_factor is None else capacity_factor
    cap1, cap2 = _select_caps(n_pad, k, p)
    labels = _iota_labels(n_pad, mesh, axis)
    nbr_flat = _flatten(nbr)
    rounds = 0
    for _ in range(max_rounds):
        live = _live_clusters(labels, n=n, mesh=mesh, axis=axis,
                              capacity_factor=cf)
        if live <= target_clusters:
            break
        nl, okf = _pull(labels, nbr_flat, mesh=mesh, axis=axis,
                        capacity_factor=cf)
        acc_lib.record_all_to_all(p * (p - 1) * cap1 * 5 * 4, "cluster")
        acc_lib.record_all_to_all(p * (p - 1) * cap2 * 3 * 4, "cluster")
        hook_idx, hook_val, n_rec = _affinity_select(
            labels, nbr, w, nl, okf, mesh=mesh, axis=axis,
            min_similarity=min_similarity)
        if int(jax.device_get(_sum_i64(n_rec))) == 0:
            break
        parent = _iota_labels(n_pad, mesh, axis)
        parent, _ = _scatter_exchange(parent, hook_idx, hook_val, mesh=mesh,
                                      axis=axis, op="min",
                                      capacity_factor=cf)
        parent, _ = _pointer_jump(parent, mesh=mesh, axis=axis,
                                  capacity_factor=cf)
        relabeled, _ = _pull(parent, labels, mesh=mesh, axis=axis,
                             capacity_factor=cf)
        labels = relabeled
        rounds += 1
    host = np.asarray(jax.device_get(labels), np.int64)[:n]
    acc_lib.transfer_stats["cluster_label_fetches"] += 1
    acc_lib.transfer_stats["cluster_label_bytes"] += n * 4
    _, dense = np.unique(host, return_inverse=True)
    return dense.astype(np.int64), {"rounds": rounds,
                                    "clusters": int(dense.max()) + 1
                                    if dense.size else 0}
