"""The always-on serving loop: batched absorption + device-resident queries.

``ServeSession`` wraps a live :class:`~repro.core.builder.GraphBuilder` in
a request loop — the deployment shape of the paper's evolving-corpus
story.  Requests enter a BOUNDED queue (backpressure: a full queue rejects
the submit and counts it) and the loop drains them in FIFO order:

  * **extend requests** coalesce — consecutive inserts are concatenated
    (up to ``ServeConfig.batch_window`` requests) and absorbed by ONE
    ``builder.extend()`` call, amortizing the repetition rounds across the
    batch exactly like the builder amortizes them across points.  After
    each absorb round the session optionally emits the Z-set delta
    (``finalize(delta=True)``) to its ``on_delta`` consumer — downstream
    replicas stay current at O(changed rows) per round.
  * **two-hop neighbour queries** are answered BETWEEN rounds straight
    from the device-resident slabs: a one-hop row read plus a gather of
    neighbour rows, fused in one jit program (:func:`two_hop_neighbors`).
    No global edge fetch happens — ``transfer_stats['edge_fetches']`` and
    ``['bytes']`` stay untouched by any number of queries (asserted in
    tests/test_service.py), only the tiny (m, q_cap) answer crosses to the
    host (metered per session as ``query_bytes``).
  * **clustering requests** (``submit_cluster``) run
    ``builder.cluster(...)`` between rounds — the zero-gather label rounds
    of ``repro.distributed.cluster_dist`` over the same device-resident
    slabs, so a session serves features -> graph -> cluster labels without
    ever gathering the (n, k) slab image either (only the (n,) label
    vector crosses, metered per session as ``cluster_label_bytes``).

Per-session accounting (``ServeSession.stats``) mirrors the accumulator's
``transfer_stats`` idiom: ``queries_served``, ``delta_rows_shipped``,
``delta_bytes``, ``queue_depth_hwm``, ``rejections``,
``query_truncations`` and friends — the numbers a fleet scheduler reads.

Query semantics match ``Graph.from_degree_slabs`` + ``two_hop_sets`` on a
finalized graph: the edge set is the SYMMETRIC closure of the slabs (an
edge exists iff it sits in at least one endpoint's row), realized on
device as the forward row read combined with a reverse scan of the slab
table (``nbr == q``) — which is why answers agree set-for-set with the
host-side spanner path while never materializing the global edge list.
Each member is scored by its best path-bottleneck weight
(direct weight for one-hop members, ``max_u min(w(q,u), w(u,v))`` for
two-hop members) and the top ``query_capacity`` are returned; answers
that would exceed the cap are truncated and counted.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.builder import GraphBuilder, as_point_features
from repro.graph import accumulator as acc_lib


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs of one serving session.

    Attributes:
      batch_window: max consecutive extend requests coalesced into one
        ``builder.extend()`` absorb round.
      max_queue: bounded-queue depth; submits beyond it are rejected
        (``stats['rejections']``) and return None.
      reps_per_absorb: repetitions per absorb round (None = ``cfg.r``).
      query_capacity: top-q answer size per queried node; larger two-hop
        neighbourhoods truncate (``stats['query_truncations']``).
      emit_deltas: emit a Z-set delta after every absorb round (the
        ``on_delta`` stream); off for fire-and-forget ingestion.
    """

    batch_window: int = 64
    max_queue: int = 1024
    reps_per_absorb: Optional[int] = None
    query_capacity: int = 128
    emit_deltas: bool = True


class Ticket:
    """Handle for one submitted request; ``result`` is set when served."""

    __slots__ = ("kind", "done", "result")

    def __init__(self, kind: str):
        self.kind = kind
        self.done = False
        self.result: Any = None

    def _resolve(self, result: Any) -> None:
        self.result = result
        self.done = True


@functools.partial(jax.jit, static_argnames=("q_cap",))
def two_hop_neighbors(nbr: jax.Array, w: jax.Array, q: jax.Array, *,
                      q_cap: int):
    """Two-hop neighbourhoods of query nodes ``q``, on device.

    One fused program over the (n, k) slabs: symmetric one-hop weights of
    each query (forward row scatter + reverse ``nbr == q`` scan), then the
    second hop through every one-hop member u (forward row[u] scatter +
    reverse containment gather), keeping the best bottleneck weight
    ``min(w(q,u), w(u,v))`` per member.  Queries run one after another
    (``lax.map``), so the working set is O(n * k) whatever the batch size —
    a single query's intermediates already take ~3 GB at n = 2^20, k = 250.
    O(m * n * k) compute, O(m * q_cap) output — nothing O(n * k) ever
    leaves the device.

    Returns (ids (m, q_cap) int32 with -1 fill, weights (m, q_cap),
    member_count (m,) int32, truncated scalar int32).
    """
    ids, top_w, count = jax.lax.map(
        lambda qi: _two_hop_one(nbr, w, qi, q_cap), q)
    truncated = jnp.sum(count > q_cap).astype(jnp.int32)
    return ids, top_w, count, truncated


def _two_hop_one(nbr: jax.Array, w: jax.Array, q: jax.Array, q_cap: int):
    """One query of :func:`two_hop_neighbors`: (ids, weights, count)."""
    n, k = nbr.shape
    qc = jnp.clip(q, 0, n - 1)
    valid_q = (q >= 0) & (q < n)
    neg_inf = jnp.float32(-jnp.inf)

    # symmetric one-hop weights (n,): the forward row scatters into a grid
    # with a dump slot at n; the reverse scan catches edges recorded only
    # in the OTHER endpoint's row (the from_degree_slabs union semantics)
    row_n, row_w = nbr[qc], w[qc]                       # (k,)
    tgt = jnp.where(row_n >= 0, row_n, n)
    grid = jnp.full((n + 1,), neg_inf).at[tgt].max(row_w)[:n]
    rev = jnp.where(nbr == qc, w, neg_inf).max(axis=1)  # (n,)
    one_w = jnp.where(valid_q, jnp.maximum(grid, rev), neg_inf)

    # second hop through every one-hop u: forward = row[u] entries,
    # reverse = rows v whose slab contains u; bottleneck-weight scoring
    fw = jnp.minimum(one_w[:, None], w)                 # (n, k)
    uidx = jnp.where(nbr >= 0, nbr, n)                  # (n, k)
    two_f = jnp.full((n + 1,), neg_inf).at[uidx].max(fw)[:n]
    one_pad = jnp.concatenate([one_w, jnp.full((1,), neg_inf)])
    two_r = jnp.minimum(one_pad[uidx], w).max(axis=1)
    two_w = jnp.maximum(two_f, two_r)

    score = jnp.maximum(one_w, two_w)
    score = jnp.where(jnp.arange(n) != qc, score, neg_inf)
    count = (score > neg_inf).sum().astype(jnp.int32)
    top_w, top_i = jax.lax.top_k(score, q_cap)
    ids = jnp.where(top_w > neg_inf, top_i.astype(jnp.int32), -1)
    return ids, top_w, count


class ServeSession:
    """Always-on loop over a bounded request queue (see module docstring).

    Args:
      builder: a GraphBuilder that has run at least one repetition
        (extension rounds need the base points scored; the builder itself
        enforces this, the session checks up front for a clear error).
      config: ServeConfig knobs.
      on_delta: optional callback receiving each emitted SlabDelta.

    Thread model: ``submit_*`` are safe from any thread (lock-guarded
    deque); the loop itself (``step`` / ``run_until_idle`` /
    ``serve_forever``) is single-threaded — one absorb-or-answer at a
    time, the same round discipline as the builder.
    """

    def __init__(self, builder: GraphBuilder,
                 config: Optional[ServeConfig] = None,
                 on_delta: Optional[Callable] = None):
        if builder.reps_done == 0:
            raise ValueError(
                "serve over an unscored builder: run add_reps() first "
                "(extension rounds only score new-vs-all pairs)")
        self.builder = builder
        self.config = config or ServeConfig()
        self._on_delta = on_delta
        self._queue: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._shutdown = False
        self._stats: Dict[str, int] = {
            "extends_absorbed": 0, "absorb_rounds": 0, "points_absorbed": 0,
            "queries_served": 0, "query_bytes": 0, "query_truncations": 0,
            "deltas_emitted": 0, "delta_rows_shipped": 0, "delta_bytes": 0,
            "clusterings_served": 0, "cluster_label_bytes": 0,
            "rejections": 0, "queue_depth_hwm": 0,
            # paged-feature-store sessions: page traffic the session's
            # absorbs drove (zero on resident stores); embed_page_* is the
            # measure-state (cached embeddings) share of that traffic
            "feature_page_bytes": 0, "feature_page_faults": 0,
            "embed_page_bytes": 0, "embed_page_faults": 0,
        }

    # -- submission (any thread) ---------------------------------------- #
    def _submit(self, kind: str, payload) -> Optional[Ticket]:
        ticket = Ticket(kind)
        with self._lock:
            if len(self._queue) >= self.config.max_queue:
                self._stats["rejections"] += 1
                return None
            self._queue.append((kind, payload, ticket))
            depth = len(self._queue)
            if depth > self._stats["queue_depth_hwm"]:
                self._stats["queue_depth_hwm"] = depth
        return ticket

    def submit_extend(self, features) -> Optional[Ticket]:
        """Queue points for insertion; None = rejected (queue full).

        The resolved ticket carries ``{'first_gid', 'count'}`` — gids are
        assigned at ABSORB time in queue order, so they are stable under
        coalescing.
        """
        return self._submit("extend", features)

    def submit_query(self, node_ids) -> Optional[Ticket]:
        """Queue a two-hop neighbourhood query for ``node_ids``; None =
        rejected.  The resolved ticket carries ``{'nodes', 'ids',
        'weights', 'counts'}`` (host numpy, -1-padded top-q rows)."""
        return self._submit("query", np.asarray(node_ids, np.int32).ravel())

    def submit_cluster(self, method: str = "affinity",
                       **params) -> Optional[Ticket]:
        """Queue a clustering of the CURRENT graph; None = rejected.

        Served between rounds by ``builder.cluster(method, **params)`` —
        the zero-gather mesh label rounds, no global edge fetch.  The
        resolved ticket carries ``{'labels', 'info'}`` ((n,) host labels
        for the graph as of serving time, observing every
        previously-queued insert)."""
        return self._submit("cluster", (method, dict(params)))

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def stats(self) -> Dict[str, int]:
        """Per-session accounting snapshot (transfer_stats idiom)."""
        with self._lock:
            return dict(self._stats)

    # -- the loop (single-threaded) ------------------------------------- #
    def step(self) -> bool:
        """Serve the next request group; False when the queue is empty.

        Consecutive extend requests at the head coalesce into one absorb
        round (up to ``batch_window``); a query request is served alone,
        between rounds, so it observes every previously-queued insert.
        """
        batch: List = []
        query = None
        with self._lock:
            if not self._queue:
                return False
            if self._queue[0][0] == "extend":
                while (self._queue and self._queue[0][0] == "extend"
                       and len(batch) < self.config.batch_window):
                    batch.append(self._queue.popleft())
            else:
                query = self._queue.popleft()
        if batch:
            self._absorb(batch)
        else:
            self._answer(query)
        return True

    def run_until_idle(self) -> Dict[str, int]:
        """Drain the queue completely; returns the stats snapshot."""
        while self.step():
            pass
        return self.stats

    def serve_forever(self, poll_s: float = 0.005) -> None:
        """Loop until :meth:`shutdown` — the always-on deployment shape."""
        while not self._shutdown:
            if not self.step():
                time.sleep(poll_s)

    def shutdown(self) -> None:
        self._shutdown = True

    # -- internals ------------------------------------------------------ #
    def _absorb(self, batch: List) -> None:
        feats = [as_point_features(payload) for _, payload, _ in batch]
        merged = feats[0]
        for f in feats[1:]:
            merged = merged.concat(f)
        first_gid = self.builder.n
        page_keys = ("feature_page_bytes", "feature_page_faults",
                     "embed_page_bytes", "embed_page_faults")
        page_before = {k: acc_lib.transfer_stats[k] for k in page_keys}
        self.builder.extend(merged, reps=self.config.reps_per_absorb)
        with self._lock:
            self._stats["absorb_rounds"] += 1
            self._stats["extends_absorbed"] += len(batch)
            self._stats["points_absorbed"] += merged.n
            for k in page_keys:
                self._stats[k] += (acc_lib.transfer_stats[k]
                                   - page_before[k])
        gid = first_gid
        for (_, _, ticket), f in zip(batch, feats):
            ticket._resolve({"first_gid": gid, "count": f.n})
            gid += f.n
        if self.config.emit_deltas:
            before = acc_lib.transfer_stats["delta_bytes"]
            delta = self.builder.finalize(delta=True)
            with self._lock:
                self._stats["deltas_emitted"] += 1
                self._stats["delta_rows_shipped"] += int(delta.rows.shape[0])
                self._stats["delta_bytes"] += (
                    acc_lib.transfer_stats["delta_bytes"] - before)
            if self._on_delta is not None:
                self._on_delta(delta)

    def _answer(self, request) -> None:
        kind, payload, ticket = request
        if kind == "cluster":
            method, params = payload
            labels, info = self.builder.cluster(method, return_info=True,
                                                **params)
            with self._lock:
                self._stats["clusterings_served"] += 1
                self._stats["cluster_label_bytes"] += int(labels.size) * 4
            ticket._resolve({"labels": labels, "info": info})
            return
        node_ids = payload
        state = self.builder.slab_state()
        q_cap = min(self.config.query_capacity, self.builder.n)
        ids, weights, counts, truncated = jax.device_get(
            two_hop_neighbors(state.nbr, state.w,
                              jnp.asarray(node_ids, jnp.int32),
                              q_cap=q_cap))
        ids, weights, counts = map(np.asarray, (ids, weights, counts))
        with self._lock:
            self._stats["queries_served"] += int(node_ids.shape[0])
            self._stats["query_bytes"] += (int(ids.nbytes)
                                           + int(weights.nbytes))
            self._stats["query_truncations"] += int(truncated)
        ticket._resolve({"nodes": node_ids, "ids": ids,
                         "weights": weights, "counts": counts})
