"""Device-resident streaming edge accumulator with on-device degree capping.

The build loop used to ship every repetition's full candidate tensor to the
host and re-run an O(E log E) lexsort-dedup plus a full degree cap on the
growing union each flush — at scale the host merge, not the MXU scoring, was
the bottleneck.  This module keeps edge accumulation on device instead:

  * state is a fixed-capacity per-node top-k table — `(n, k)` slabs of
    `(nbr, w)` pairs (`EdgeAccumulator`), `k` derived from ``degree_cap``,
  * each repetition's masked candidate stream is folded in by
    :func:`accumulate`: the stream is doubled (one instance per endpoint),
    deduplicated and bucketed into per-node candidate rows with two
    fixed-shape device sorts, then merged into the slabs by the
    ``topk_merge`` op (Pallas kernel on TPU, jnp reference on CPU),
  * the host sees edges exactly once per build: :func:`to_graph` fetches the
    slabs and compacts them via ``Graph.from_degree_slabs``.

Incremental per-node top-k capping is exact: a candidate outside a node's
running top-k can never re-enter (the pool only grows, so the k-th weight is
non-decreasing), and an edge survives the final union iff it is in the top-k
of *either* endpoint — precisely the paper's "keep the 250 closest points
for each node" applied to the deduplicated union, i.e. the semantics of the
old host merge.  Duplicates keep their max weight at every stage, matching
``Graph.from_candidates``.  (Equal-weight ties at the capacity boundary may
resolve differently than the host lexsort's stable order; real-valued
similarities make exact ties measure-zero.)

Related work reaches the same design point: KDE-based similarity-graph
construction and Cluster-and-Conquer both bound per-node candidate pools
*during* construction rather than deduplicating a global stream afterwards.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels import ops as kernel_ops

_BIG = jnp.int32(2**31 - 1)

# Host-transfer accounting: every fetch of edge payload off device goes
# through to_graph(), so "one device->host edge transfer per build" is a
# checkable invariant (see benchmarks/accumulator_bench.py).  Checkpoint
# snapshots (GraphBuilder.checkpoint) are tracked separately — they are
# deliberate, user-requested transfers, not part of the build loop.
# ``all_to_all_*`` counts the *device-to-device* buffer volume of every
# explicit exchange (the sample-sort partition, the scoring-phase feature
# fetch and the mesh edge emit of distributed/stars_dist.py) — the comms
# side of the tera-scale story, measurable per build and asserted in
# tests.  ``all_to_all_bytes`` is CROSS-SHARD volume only: each (p, cap,
# ...) exchange buffer's p diagonal self-buckets stay on their own shard,
# so recorders count p*(p-1) slices — the stat is exactly 0 on a 1-shard
# mesh, and no longer over-reports interconnect traffic by p/(p-1)x
# (``all_to_all_calls`` still counts every exchange, diagonal included).
# Bytes are counted at WIRE width, not logical width: bit-packed sort
# keys (distributed/sorter.py ``pack_bit_fields`` — hash bits + a 20-bit
# tiebreak + ceil(log2 n) gid bits instead of fixed int32 words) count
# their packed word count, packed emit triples (stars_dist._emit_exchange)
# count their loc/nbr/weight field words, and bf16-quantized edge weights
# (StarsConfig.exact_weights=False) count 16 bits — the stat tracks what
# actually crosses the interconnect, so shrinking the wire format shrinks
# the stat at identical logical traffic (benchmarks/roofline.py divides
# it by ``comparisons`` for the bytes-per-comparison roofline rows).
# ``all_to_all_bytes.<exchange>`` splits the same bytes by exchange (the
# sample sort, the feature fetch, the edge emit, the cluster label
# rounds); ``all_to_all_bytes`` is their sum.
# ``delta_*`` meters the incremental serving path (GraphBuilder
# ``finalize(delta=True)`` / repro.service): a delta fetch ships the (n,)
# int32 per-row version vector plus ONLY the slab rows whose version
# advanced past the last ship — O(changed rows), not the O(n * k) full
# image a plain finalize pays.  ``delta_rows`` counts the rows shipped, so
# bytes-per-changed-row is derivable; the full-vs-delta economics are the
# ``delta_finalize`` row of benchmarks/builder_bench.py.
# ``cluster_label_*`` meters the zero-gather clustering path
# (repro.distributed.cluster_dist / GraphBuilder.cluster): label rounds
# run entirely on device through metered all_to_all exchanges, and the
# ONLY device->host payload is the final (n,) int32 label vector —
# ``edge_fetches`` / ``bytes`` stay untouched by any number of
# clusterings, which is the tentpole invariant tests assert.
# ``feature_page_*`` meters the out-of-core feature path
# (repro.similarity.store.PagedFeatureStore): ``feature_page_bytes``
# counts host->device page-fault traffic (faults * page bytes — the paged
# analogue of ``all_to_all_bytes``, deterministic given shapes/seed and
# gated in benchmarks/run.py --check), ``feature_page_faults`` /
# ``feature_page_hits`` the pool miss/re-use split, and
# ``feature_page_peak_bytes`` the high-water device-resident pool bytes —
# the bounded-peak invariant (<= the configured pool budget) tests
# assert for builds whose table exceeds device residency.
# ``embed_page_*`` is the same metering for measure-STATE pages (the
# cached tower embeddings of a learned measure, similarity/measure.py):
# state pages share the one LRU pool with feature pages, so
# ``feature_page_peak_bytes`` is the combined high-water while the
# fault/byte traffic splits by kind.
transfer_stats: Dict[str, int] = {"edge_fetches": 0, "bytes": 0,
                                  "checkpoint_fetches": 0,
                                  "checkpoint_bytes": 0,
                                  "all_to_all_calls": 0,
                                  "all_to_all_bytes": 0,
                                  "all_to_all_bytes.sort": 0,
                                  "all_to_all_bytes.fetch": 0,
                                  "all_to_all_bytes.emit": 0,
                                  "all_to_all_bytes.cluster": 0,
                                  "delta_fetches": 0,
                                  "delta_bytes": 0,
                                  "delta_rows": 0,
                                  "cluster_label_fetches": 0,
                                  "cluster_label_bytes": 0,
                                  "feature_page_bytes": 0,
                                  "feature_page_faults": 0,
                                  "feature_page_hits": 0,
                                  "feature_page_peak_bytes": 0,
                                  "embed_page_bytes": 0,
                                  "embed_page_faults": 0,
                                  "embed_page_hits": 0}


def reset_transfer_stats() -> None:
    for k in transfer_stats:
        transfer_stats[k] = 0


def record_all_to_all(nbytes: int, exchange: str) -> None:
    """Account one explicit all_to_all of ``exchange`` (``sort``,
    ``fetch``, ``emit`` or ``cluster``): CROSS-SHARD buffer bytes moved,
    i.e. the p*(p-1) off-diagonal slices, computed host-side from static
    shapes — callers exclude their diagonal self-buckets."""
    transfer_stats["all_to_all_calls"] += 1
    transfer_stats["all_to_all_bytes"] += int(nbytes)
    transfer_stats[f"all_to_all_bytes.{exchange}"] += int(nbytes)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeAccumulator:
    """Per-node top-k edge table; functional state, jit/donation-friendly.

    Attributes:
      nbr: (n, k) int32 neighbour ids, sorted by weight desc; -1 = empty.
      w:   (n, k) float32 edge weights; -inf on empty slots.
      ver: (n,) int32 per-row monotonic version.  Every fold that CHANGES a
        row (any nbr/w entry differs after the merge) bumps that row's
        version by one; folds that leave a row bit-identical do not.  This
        is the generalized staleness watermark the delta-serving path reads:
        a row needs re-shipping iff its version advanced past the consumer's
        last fetch (GraphBuilder ``finalize(delta=True)``; Z-set semantics
        in repro/service).  Versions are device-side int32 *offsets*; the
        session rolls them up into host int64 logical versions
        (``GraphBuilder._ver_base`` + checkpoint ``ver`` field) per the
        per-chunk-int32 / host-int64 counter policy, and they shard
        row-wise exactly like the slabs on a mesh.  Absolute values are
        fold-granularity dependent — the mesh emit coalesces repetition
        pairs into one fold, bumping a twice-changed row once where the
        single-device path bumps twice — so only "advanced since X"
        comparisons are meaningful; the CHANGED-ROW SET of any round
        sequence is backend-identical (tests/test_service.py).
    """

    nbr: jax.Array
    w: jax.Array
    ver: jax.Array

    @property
    def n(self) -> int:
        return self.nbr.shape[0]

    @property
    def capacity(self) -> int:
        return self.nbr.shape[1]

    @staticmethod
    def create(n: int, capacity: int) -> "EdgeAccumulator":
        return EdgeAccumulator(
            nbr=jnp.full((n, capacity), -1, jnp.int32),
            w=jnp.full((n, capacity), -jnp.inf, jnp.float32),
            ver=jnp.zeros((n,), jnp.int32))


def grow(state: EdgeAccumulator, n: int,
         capacity: Optional[int] = None) -> EdgeAccumulator:
    """Grow the slab table to ``n`` rows (and optionally more columns).

    New rows/slots start empty (-1 / -inf); existing entries are preserved
    verbatim.  Column growth pads at the tail, which keeps every row's
    weight-descending invariant (padding weight -inf sorts last).  Used by
    GraphBuilder.extend (row growth for inserted points) and by uncapped
    session builds whose repetition budget outgrows the initial worst-case
    capacity (column growth).
    """
    n0, cap0 = state.nbr.shape
    capacity = cap0 if capacity is None else capacity
    if n < n0 or capacity < cap0:
        raise ValueError(f"cannot shrink slabs: ({n0},{cap0})->({n},{capacity})")
    if (n, capacity) == (n0, cap0):
        return state
    pad = ((0, n - n0), (0, capacity - cap0))
    return EdgeAccumulator(
        nbr=jnp.pad(state.nbr, pad, constant_values=-1),
        w=jnp.pad(state.w, pad, constant_values=-jnp.inf),
        ver=jnp.pad(state.ver, (0, n - n0)))   # new rows start at version 0


def to_host(state: EdgeAccumulator):
    """Snapshot the slabs (+ row versions) to host numpy arrays.

    Tracked under ``transfer_stats['checkpoint_*']`` — NOT as a build edge
    fetch, so the one-fetch-per-finalize invariant stays checkable.
    """
    import numpy as np
    nbr, w, ver = jax.device_get((state.nbr, state.w, state.ver))
    transfer_stats["checkpoint_fetches"] += 1
    transfer_stats["checkpoint_bytes"] += (int(nbr.nbytes) + int(w.nbytes)
                                           + int(ver.nbytes))
    return np.asarray(nbr), np.asarray(w), np.asarray(ver)


def from_host(nbr, w, ver=None) -> EdgeAccumulator:
    """Rebuild device-resident slabs from a host snapshot (restore).

    ``ver`` defaults to all-zero row versions (pre-versioning snapshots,
    and callers that only care about the edge payload).
    """
    nbr = jnp.asarray(nbr, jnp.int32)
    return EdgeAccumulator(
        nbr=nbr, w=jnp.asarray(w, jnp.float32),
        ver=(jnp.zeros((nbr.shape[0],), jnp.int32) if ver is None
             else jnp.asarray(ver, jnp.int32)))


def capacity_for(degree_cap: Optional[int], n: int, *,
                 reps: int = 1, per_rep_bound: int = 0) -> int:
    """Slab capacity for a build.

    With a degree cap the capacity IS the cap (clamped to n-1 possible
    neighbours).  Without one the build is inherently unbounded; we
    materialize the worst case ``reps * per_rep_bound`` distinct neighbours
    a node can accumulate — fine for the small-n baselines that run
    uncapped, ruinous at scale (so is an uncapped build).
    """
    if degree_cap is not None:
        return max(1, min(degree_cap, n - 1))
    bound = reps * per_rep_bound if per_rep_bound > 0 else n - 1
    return max(1, min(n - 1, bound))


def accumulate(state: EdgeAccumulator, src: jax.Array, dst: jax.Array,
               w: jax.Array, valid: jax.Array) -> EdgeAccumulator:
    """Fold one masked candidate stream into the degree slabs (pure, jit).

    src/dst/w/valid: equally-shaped arrays (any rank; flattened).  Invalid,
    negative-id and self-loop entries are ignored.  Each surviving candidate
    is inserted under both endpoints, so the final union over slabs contains
    an edge iff it ranks top-k for at least one endpoint.
    """
    with jax.named_scope(scopes.FOLD_DEDUP):
        src = src.ravel().astype(jnp.int32)
        dst = dst.ravel().astype(jnp.int32)
        w = w.ravel().astype(jnp.float32)
        ok = valid.ravel() & (src >= 0) & (dst >= 0) & (src != dst)

        # one instance per endpoint: insert (dst, w) under src and vice versa
        node = jnp.concatenate([src, dst])
        nbr = jnp.concatenate([dst, src])
        ww = jnp.concatenate([w, w])
        ok2 = jnp.concatenate([ok, ok])
    return _fold_triples(state, node, nbr, ww, ok2)


def _fold_triples(state: EdgeAccumulator, node: jax.Array, nbr: jax.Array,
                  ww: jax.Array, ok2: jax.Array) -> EdgeAccumulator:
    """Fold directed (node, nbr, w) insertion triples into the slabs.

    The slab-row half of :func:`accumulate` — each triple inserts ``nbr``
    under row ``node`` only (callers wanting both endpoints double the
    stream first, as ``accumulate`` does).  The mesh emit path
    (distributed/stars_dist.py) calls this per shard AFTER routing every
    triple to its owner via all_to_all, with ``node`` already localized to
    shard-row coordinates — per-node results depend only on the per-row
    candidate multiset, which is what makes the sharded build edge-for-edge
    equal to the single-device one.

    Rows whose post-merge slab content differs from the pre-merge content
    get their ``ver`` bumped by one (an (n, k) equality reduce against the
    donated input — exact change detection, so a candidate that is already
    present or loses to the incumbent top-k does NOT dirty the row for the
    delta-serving path).  Because the bump rides inside the same jit
    program as the fold, versions stay consistent under donation and under
    the mesh's sharded per-shard folds (each shard bumps only its own row
    block, exactly like the slab data itself).
    """
    with jax.named_scope(scopes.FOLD_DEDUP):
        batch = _fold_dedup(node, nbr, ww, ok2)
    with jax.named_scope(scopes.FOLD_BUCKET):
        rows = _fold_bucket(*state.nbr.shape, *batch)
    with jax.named_scope(scopes.FOLD_MERGE):
        return _fold_merge(state, *rows)


# The fold's three stages as functions of their own, so that the mesh emit
# (distributed/stars_dist.py) can run each as a shard_map under its stage.


def _fold_dedup(node: jax.Array, nbr: jax.Array, ww: jax.Array,
                ok2: jax.Array):
    """Step 1: the stream sorted by (node, nbr, weight desc), and which
    elements are the first of their (node, nbr) group."""
    node = node.astype(jnp.int32)
    nbr = nbr.astype(jnp.int32)
    ww = ww.astype(jnp.float32)
    # NB: no node != nbr check here — self-loop exclusion happens on
    # GLOBAL ids in the caller (``node`` may be in shard-row
    # coordinates).
    ok2 = ok2 & (node >= 0) & (nbr >= 0)

    node_k = jnp.where(ok2, node, _BIG)
    nbr_k = jnp.where(ok2, nbr, _BIG)
    negw = jnp.where(ok2, -ww, jnp.inf)

    # 1) dedup within the batch: group by (node, nbr), heaviest instance
    #    first; later instances of a group are dropped.
    node_s, nbr_s, negw_s = jax.lax.sort((node_k, nbr_k, negw), num_keys=3)
    first = jnp.concatenate(
        [jnp.ones((1,), bool),
         (node_s[1:] != node_s[:-1]) | (nbr_s[1:] != nbr_s[:-1])])
    keep = first & (node_s != _BIG)
    return node_s, nbr_s, negw_s, keep


def _fold_bucket(n: int, cap: int, node_s: jax.Array, nbr_s: jax.Array,
                 negw_s: jax.Array, keep: jax.Array):
    """Steps 2 and 2b: the kept elements ranked per node into (n, kin)
    candidate rows for slabs of ``n`` rows and ``cap`` columns."""
    m2 = node_s.shape[0]
    kin = min(cap, m2)
    # 2) bucket: per-node rank by weight, scatter the top kin of each node
    #    into fixed (n, kin) candidate rows.  Candidates beyond rank kin
    #    (>= cap) can never enter the final top-cap, so dropping them here
    #    is exact.
    node_k2 = jnp.where(keep, node_s, _BIG)
    negw2 = jnp.where(keep, negw_s, jnp.inf)
    nbr_k2 = jnp.where(keep, nbr_s, _BIG)
    iota1 = jnp.arange(m2, dtype=jnp.int32)
    node_f, negw_f, nbr_f, p1 = jax.lax.sort(
        (node_k2, negw2, nbr_k2, iota1), num_keys=3)
    starts = jnp.searchsorted(node_f, jnp.arange(n, dtype=jnp.int32))
    live = node_f != _BIG
    node_c = jnp.where(live, node_f, 0)
    rank = (jnp.arange(m2, dtype=jnp.int32)
            - starts[node_c].astype(jnp.int32))
    slot = jnp.where(live & (rank < kin), rank, kin)     # kin -> dropped
    inc_nbr = jnp.full((n, kin), -1, jnp.int32).at[node_c, slot].set(
        nbr_f, mode="drop")
    inc_w = jnp.full((n, kin), -jnp.inf, jnp.float32).at[node_c, slot].set(
        -negw_f, mode="drop")

    # 2b) CPU only: nbr-ascending companion view of the same survivors, so
    #     the merge-path slab merge needs no sort at all (the step-1 order
    #     is already (node, nbr); a few stream-length scatters re-express
    #     it per node row).  TPU skips this — the Pallas kernel dedups in
    #     VMEM and never reads the companion view.
    presorted = None
    if not kernel_ops.pallas_by_default():
        # weight-order slot of every step-1 element (kin == dropped/dead)
        wrank1 = jnp.zeros((m2,), jnp.int32).at[p1].set(slot)
        surv1 = (wrank1 < kin).astype(jnp.int32)
        excl = jnp.cumsum(surv1) - surv1             # survivors before e
        starts1 = jnp.searchsorted(node_s, jnp.arange(n, dtype=jnp.int32))
        node1 = jnp.where(node_s != _BIG, node_s, 0)
        nbr_rank = excl - excl[starts1[node1]]       # rank among node's
        slot_bn = jnp.where(surv1 == 1, nbr_rank, kin)  # survivors, by nbr
        nbr_bn = jnp.full((n, kin), _BIG, jnp.int32).at[
            node1, slot_bn].set(nbr_s, mode="drop")
        negw_bn = jnp.full((n, kin), jnp.inf, jnp.float32).at[
            node1, slot_bn].set(negw_s, mode="drop")
        idx_bn = jnp.full((n, kin), kin, jnp.int32).at[node1, slot_bn].set(
            wrank1, mode="drop")
        presorted = (nbr_bn, negw_bn, idx_bn)
    return inc_nbr, inc_w, presorted


def _fold_merge(state: EdgeAccumulator, inc_nbr: jax.Array,
                inc_w: jax.Array, presorted) -> EdgeAccumulator:
    """Step 3: the candidate rows merged into the slabs, and the row-version
    bump of every row that changed."""
    # 3) merge into the running slabs (Pallas on TPU; sort-free merge-path
    #    jnp ref on CPU — both sides are weight-sorted and deduped by
    #    construction)
    new_nbr, new_w = kernel_ops.topk_merge(
        state.nbr, state.w, inc_nbr, inc_w, sorted_inputs=True,
        inc_presorted=presorted)
    # exact per-row change detection (empty slots compare equal: -1 == -1,
    # and -inf == -inf is True in IEEE) -> bump changed rows' versions
    changed = jnp.any((new_nbr != state.nbr) | (new_w != state.w), axis=1)
    return EdgeAccumulator(nbr=new_nbr, w=new_w,
                           ver=state.ver + changed.astype(jnp.int32))


def to_graph(state: EdgeAccumulator, *,
             stats: Optional[Dict[str, float]] = None):
    """THE device->host edge transfer: fetch slabs once, compact to a Graph."""
    from repro.core.spanner import Graph

    nbr, w = jax.device_get((state.nbr, state.w))
    transfer_stats["edge_fetches"] += 1
    transfer_stats["bytes"] += int(nbr.nbytes) + int(w.nbytes)
    return Graph.from_degree_slabs(state.n, nbr, w, stats=stats)
