"""Unified ``GraphBuilder`` session API with incremental point insertion.

The paper's deployment story is an *evolving* corpus: tera-scale graphs
rebuilt as embeddings and points change.  The one-shot entry points
(``build_graph`` / ``allpairs_graph`` / ``build_graph_distributed``) each
re-implemented the repetition loop, the accumulator lifecycle and the stats
plumbing; none could add points without a full rebuild.  This module owns
all of that once, as a session:

    builder = GraphBuilder(features, cfg)          # slabs live on device
    builder.add_reps(cfg.r)                        # run repetitions
    builder.extend(new_points, reps=cfg.r)         # insert points, score
                                                   #   new-vs-all only
    builder.refresh_reps(2, fraction=0.5)          # rescore a sampled set
                                                   #   of old-old windows
    ckpt = builder.checkpoint()                    # slabs+counters -> host
    builder = GraphBuilder.restore(feats, cfg, ckpt)
    graph = builder.finalize()                     # THE device->host fetch

Design points:

  * **Candidate sources are pluggable** (``CANDIDATE_SOURCES``): the
    windowed LSH / SortingLSH repetitions of core/stars.py ('lsh-stars',
    'sorting-stars' and their non-Stars 'allpairs' scorings) and the
    brute-force blocked sweep ('allpairs'), selected by
    ``StarsConfig.source_name``.  A source binds (features, new_from) to a
    compiled round program; the builder only sequences rounds.
  * **Backends**: single device (default) or a mesh (``mesh=`` constructor
    argument) with features and slabs sharded row-wise over the ``data``
    axis, the distributed sample-sort pipeline of distributed/sorter.py
    and the explicit all_to_all edge emit of distributed/stars_dist.py.
    The mesh build — including ``extend`` and ``checkpoint``/``restore``
    across different mesh sizes — is **edge-for-edge identical** to the
    single-device build (see ``_MeshBackend`` for the row-padding reshard
    rule and tests/test_mesh_parity.py for the proof obligations).
  * **Incremental insertion**: ``extend`` appends rows to the feature table,
    grows the slab table (grow pads at the tail, preserving row invariants)
    and runs repetitions whose candidate streams are masked to pairs
    touching at least one new point.  Old-old edges stay untouched in the
    slabs, new points are scored against everything that windows next to
    them — the union over all reps keeps the two-hop spanner property of a
    fresh build at equal total repetitions (verified in tests/test_builder):
    comparisons drop by the old-old fraction, recall matches within noise.
  * **Staleness repair**: the flip side of that masking is that old points
    never re-window against each other, so a LONG stream of extensions
    leaves the old-old edge set reflecting only the repetitions that ran
    while one endpoint was new.  ``refresh_reps`` runs repetitions masked
    the *inverse* way — old-old pairs only, inside a PRNG-sampled fraction
    of windows — and ``cfg.refresh_rate`` arms an automatic decaying
    rescore that ``extend()`` invokes, bounding staleness geometrically in
    session length (tests/test_refresh.py demonstrates the recall bound).
    The watermark, refresh counters and fractional auto-refresh credit ride
    through ``BuilderCheckpoint``, so a restored session refreshes exactly
    like the uncheckpointed one — on any mesh size.
  * **One transfer**: edges cross device->host exactly once per
    ``finalize()`` (``accumulator.to_graph``); ``checkpoint()`` snapshots
    are accounted separately (``transfer_stats['checkpoint_*']``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.core import lsh as lsh_lib
from repro.core.spanner import Graph
from repro.core.stars import StarsConfig, _prefilter_sketch, _rep_candidates
from repro.distributed.mesh import auto_axes
from repro.graph import accumulator as acc_lib
from repro.similarity import pair_cache as pc_lib
from repro.similarity.measure import Measure, make_measure
from repro.similarity.measures import PointFeatures
from repro.similarity.store import (FeatureStore, PagedFeatureStore,
                                    ResidentFeatureStore, make_feature_store)

FeaturesLike = Union[PointFeatures, jax.Array, np.ndarray, FeatureStore]


def as_point_features(features) -> PointFeatures:
    """Accept a PointFeatures or a bare (n, d) dense array."""
    if isinstance(features, PointFeatures):
        return features
    return PointFeatures(dense=jnp.asarray(features))


def as_feature_store(features: FeaturesLike,
                     cfg: StarsConfig) -> FeatureStore:
    """The session's FeatureStore: pass one through, or build the one
    ``cfg.feature_store`` names around raw features."""
    if isinstance(features, FeatureStore):
        return features
    if not isinstance(features, PointFeatures):
        # the paged store keeps its table on HOST — don't bounce a raw
        # array through device placement just to pull it straight back
        features = (PointFeatures(dense=np.asarray(features))
                    if cfg.feature_store == "paged"
                    else as_point_features(features))
    return make_feature_store(features, cfg.feature_store,
                              page_rows=cfg.feature_page_rows,
                              pool_bytes=cfg.feature_pool_bytes)


# --------------------------------------------------------------------------- #
# Candidate sources (single-device)
# --------------------------------------------------------------------------- #


def _bind_span(key, miss: bool):
    """``stars.bind`` around a round whose program ``key`` a backend has
    not bound yet: the bind and the round's first call, which compiles
    the program or loads it from the compilation cache."""
    if not miss:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(scopes.BIND, key=repr(key))


class RepetitionSource:
    """Windowed LSH / SortingLSH repetitions (Stars 1/2 and non-Stars).

    One round == one repetition of core/stars.py's per-repetition device
    program: sketch with a fresh hash draw, sort+window, score leader tiles,
    fold the masked candidate stream into the slabs — all in one jit program
    with the slab state donated.

    Scoring goes through a :class:`repro.similarity.measure.Measure`:
    ``measure_state``, when bound, is the per-point state table the
    measure's ``precompute`` produced (cached tower embeddings), so tiles
    only pay the pair head; ``cache_slots`` > 0 additionally threads a
    :class:`repro.similarity.pair_cache.PairCache` through the round —
    the bound program consumes the candidate stream's ``cmp`` lane mask,
    swaps cached scores in on hits, and re-derives the emit mask
    (``cmp & (w > r1)``, exactly the in-stream formula), so cache-on
    builds stay edge-for-edge equal to cache-off while
    ``expensive_comparisons`` (= misses) drops on re-visited pairs.
    """

    def __init__(self, cfg: StarsConfig,
                 measure: Optional[Measure] = None):
        self.cfg = cfg
        self.measure = (measure if measure is not None else
                        make_measure(cfg.measure, alpha=cfg.mixture_alpha))

    def bind(self, features: PointFeatures, new_from: int,
             refresh_below: int = 0,
             refresh_fraction: float = 1.0,
             measure_state: Optional[jax.Array] = None,
             cache_slots: int = 0) -> Callable:
        cfg = self.cfg
        measure = self.measure
        prefilter = (
            _prefilter_sketch(features, cfg.hamming_prefilter_bits, cfg.seed)
            if cfg.hamming_prefilter_bits > 0 else None)

        # the feature tables ride as ARGUMENTS: a closed-over device array
        # becomes a constant baked into the executable (n * d floats — a
        # 1.2 GB TPU executable at n = 2^20, d = 100, slow to compile and
        # too large for the persistent compilation cache)
        tables = (features, prefilter, measure_state)

        if cache_slots <= 0:
            @functools.partial(jax.jit, donate_argnums=0)
            def round_step(state, rep_index, probs, tables):
                feats, pref, mstate = tables
                out = _rep_candidates(cfg, feats, measure, pref,
                                      rep_index, new_from=new_from,
                                      refresh_below=refresh_below,
                                      refresh_fraction=refresh_fraction,
                                      refresh_probs=probs, state=mstate)
                state = acc_lib.accumulate(state, out["src"], out["dst"],
                                           out["w"], out["emit"])
                return state, {k: out[k] for k in
                               ("comparisons", "emitted", "prefilter_ops",
                                "scored_windows")}

            return lambda state, rep, probs=None: round_step(
                state, jnp.int32(rep), probs, tables)

        r1 = cfg.r1

        @functools.partial(jax.jit, donate_argnums=(0, 3))
        def round_step_cached(state, rep_index, probs, cache, tables):
            feats, pref, mstate = tables
            out = _rep_candidates(cfg, feats, measure, pref,
                                  rep_index, new_from=new_from,
                                  refresh_below=refresh_below,
                                  refresh_fraction=refresh_fraction,
                                  refresh_probs=probs, state=mstate)
            w, cache, hits, misses, evictions = pc_lib.lookup_insert(
                cache, out["src"], out["dst"], out["w"], out["cmp"])
            # hits return the bit-identical score the tile recomputed (see
            # pair_cache.py's correctness contract), so re-deriving the
            # emit mask from the post-cache weights reproduces the
            # in-stream emit lanes exactly
            emit = out["cmp"] & (w > r1) if r1 is not None else out["cmp"]
            state = acc_lib.accumulate(state, out["src"], out["dst"],
                                       w, emit)
            counters = {"comparisons": out["comparisons"],
                        "emitted": jnp.sum(emit).astype(jnp.int32),
                        "prefilter_ops": out["prefilter_ops"],
                        "scored_windows": out["scored_windows"],
                        "expensive_comparisons": misses,
                        "cache_hits": hits, "cache_misses": misses,
                        "cache_evictions": evictions}
            return state, counters, cache

        return lambda state, rep, probs=None, cache=None: round_step_cached(
            state, jnp.int32(rep), probs, cache, tables)


class AllPairsSource:
    """Brute-force *AllPair* sweep: exact n^2/2 comparisons, blocked.

    One round == one full blocked sweep (repetitions are pointless for an
    exact scorer, so ``add_reps(1)``).  Each fixed-shape (block x block)
    tile is scored AND folded into the slabs in one jit program; on an
    extension round only blocks touching new points are visited and the
    pair mask keeps new-vs-all pairs, so comparisons drop from C(n,2) to
    C(n,2) - C(n_old,2) exactly.
    """

    def __init__(self, cfg: StarsConfig,
                 measure: Optional[Measure] = None):
        self.cfg = cfg
        self.measure = (measure if measure is not None else
                        make_measure(cfg.measure, alpha=cfg.mixture_alpha))

    def bind(self, features: PointFeatures, new_from: int,
             refresh_below: int = 0,
             refresh_fraction: float = 1.0,
             measure_state: Optional[jax.Array] = None,
             cache_slots: int = 0) -> Callable:
        if refresh_below > 0:
            # unreachable through the session (refresh_reps rejects the
            # exact source before binding), kept as a structural guard
            raise ValueError("the exact 'allpairs' source has no sampling "
                             "staleness to refresh")
        if cache_slots > 0:
            raise ValueError("the exact 'allpairs' sweep scores every pair "
                             "once — a pair-score cache cannot hit")
        cfg = self.cfg
        measure = self.measure
        n = features.n
        block = min(cfg.allpairs_block, max(n, 1))
        r1 = cfg.r1

        @functools.partial(jax.jit, donate_argnums=0)
        def block_step(state, a0, b0, features, measure_state):
            # tables as arguments, not constants (RepetitionSource.bind)
            ids_a = a0 + jnp.arange(block, dtype=jnp.int32)
            ids_b = b0 + jnp.arange(block, dtype=jnp.int32)
            clamp_a = jnp.minimum(ids_a, n - 1)
            clamp_b = jnp.minimum(ids_b, n - 1)
            fa = features.take(clamp_a)
            fb = features.take(clamp_b)
            if measure_state is not None:
                sims = measure(fa, fb, measure_state[clamp_a],
                               measure_state[clamp_b])
            else:
                sims = measure(fa, fb)
            aa = jnp.broadcast_to(ids_a[:, None], (block, block))
            bb = jnp.broadcast_to(ids_b[None, :], (block, block))
            keep = (aa < bb) & (bb < n)
            if new_from > 0:
                keep &= bb >= jnp.int32(new_from)   # aa < bb: bb is the new side
            if r1 is not None:
                keep &= sims > r1
            return acc_lib.accumulate(state, aa, bb, sims, keep)

        def round_step(state, rep, probs=None):
            del rep, probs                           # the sweep is exact
            for a0 in range(0, n, block):
                for b0 in range(a0, n, block):
                    if new_from > 0 and b0 + block <= new_from:
                        continue                     # both endpoints old
                    state = block_step(state, jnp.int32(a0), jnp.int32(b0),
                                       features, measure_state)
            comps = n * (n - 1) // 2 - new_from * (new_from - 1) // 2
            return state, {"comparisons": comps}

        return round_step


CANDIDATE_SOURCES: Dict[str, Callable] = {
    "lsh-stars": RepetitionSource,
    "lsh-allpairs": RepetitionSource,
    "sorting-stars": RepetitionSource,
    "sorting-allpairs": RepetitionSource,
    "allpairs": AllPairsSource,
}


# --------------------------------------------------------------------------- #
# Backends
# --------------------------------------------------------------------------- #


class _SingleDeviceBackend:
    """Feature table + slab state on the default device.

    Features ride in a :class:`ResidentFeatureStore`; the round programs
    close over the store's PointFeatures directly (bit-exact, zero
    indirection on the hot path).  A stateful Measure's per-point state
    (the cached tower embeddings) is computed once per build/extend
    (``ensure_measure_state``) and attached to the store as a device
    table; ``cfg.pair_cache_slots`` > 0 additionally threads a
    device-resident pair-score cache through the windowed round programs
    (expensive measures only)."""

    def __init__(self, store: ResidentFeatureStore, cfg: StarsConfig,
                 measure: Optional[Measure] = None):
        name = cfg.source_name
        if name not in CANDIDATE_SOURCES:
            raise ValueError(f"unknown candidate source {name!r}; "
                             f"known: {sorted(CANDIDATE_SOURCES)}")
        self.store = store
        self.measure = (measure if measure is not None else
                        make_measure(cfg.measure, alpha=cfg.mixture_alpha))
        self.source = CANDIDATE_SOURCES[name](cfg, self.measure)
        self._pair_cache = (
            pc_lib.create(cfg.pair_cache_slots)
            if (cfg.pair_cache_slots > 0 and self.measure.expensive
                and isinstance(self.source, RepetitionSource)) else None)
        self._embedded = 0          # rows whose measure state is current
        self._embed_fn = None
        # (new_from, refresh_below, refresh_fraction) -> compiled round
        # program; cleared on extend() (shapes change)
        self._bound: Dict = {}

    @property
    def features(self) -> PointFeatures:
        return self.store.features

    @property
    def n(self) -> int:
        return self.store.n

    def init_state(self, capacity: int) -> acc_lib.EdgeAccumulator:
        return acc_lib.EdgeAccumulator.create(self.n, capacity)

    def place_state(self, state: acc_lib.EdgeAccumulator):
        return state

    def grow_state(self, state, n: int, capacity: int):
        return acc_lib.grow(state, n, capacity)

    def trim(self, state: acc_lib.EdgeAccumulator) -> acc_lib.EdgeAccumulator:
        return state                # rows are never padded on one device

    def ensure_measure_state(self) -> int:
        """Run the measure's precompute over rows not yet embedded (all of
        them on the first build, only the appended tail after an extend);
        returns how many rows were embedded (0 for stateless measures)."""
        if self.measure.state_width is None:
            return 0
        n = self.store.n
        new = n - self._embedded
        if new <= 0:
            return 0
        if self._embed_fn is None:
            self._embed_fn = jax.jit(self.measure.precompute)
        feats = self.features
        if self._embedded == 0:
            self.store.attach_state(self._embed_fn(feats))
        else:
            tail = PointFeatures(dense=feats.dense[self._embedded:n])
            self.store.append_state(self._embed_fn(tail))
        self._embedded = n
        return new

    def run_round(self, state, rep_index: int, new_from: int,
                  refresh_below: int = 0, refresh_fraction: float = 1.0,
                  refresh_probs=None):
        self.ensure_measure_state()
        key = (new_from, refresh_below, refresh_fraction)
        with _bind_span(key, key not in self._bound):
            if key not in self._bound:
                mstate = (self.store.state_table
                          if self.measure.state_width is not None else None)
                self._bound[key] = self.source.bind(
                    self.features, new_from, refresh_below, refresh_fraction,
                    measure_state=mstate,
                    cache_slots=(self._pair_cache.slots
                                 if self._pair_cache is not None else 0))
            if self._pair_cache is not None:
                state, counters, self._pair_cache = self._bound[key](
                    state, rep_index, refresh_probs, self._pair_cache)
                return state, counters
            return self._bound[key](state, rep_index, refresh_probs)

    def extend(self, new_features: PointFeatures) -> None:
        self.store.append(new_features)
        # the pair cache survives extends unrejected: gids are append-only
        # stable, so cached (lo, hi) -> score entries stay correct
        self._bound = {}            # shapes changed; rebind lazily

    def cluster_mesh(self):
        """Trivial 1-device mesh for the zero-gather clustering programs
        (repro.distributed.cluster_dist runs one code path at every p;
        p=1 parity with p=2/4 is proven in tests/test_cluster.py)."""
        if not hasattr(self, "_cluster_mesh"):
            self._cluster_mesh = auto_axes(jax.make_mesh((1,), ("data",)))
        return self._cluster_mesh, "data"


def _refresh_window_count(cfg: StarsConfig, n: int) -> int:
    """Global window-row count of the current grid — the length of the
    per-row refresh probability vector (``GraphBuilder._refresh_probs``)
    and of the host-side refresh-age ledger.  The same ``n_windows`` that
    ``windows.shard_row_layout`` reports, derivable without a mesh."""
    from repro.core import windows as win_lib
    return (win_lib.window_slot_count(cfg.mode, n, cfg.window)
            // cfg.window)


def _sketch_keys(cfg: StarsConfig, n: int, words: jax.Array, rep):
    """Sketch words -> BIT-PACKED sort keys (+ gids, bucket ids).

    The key-packing half of the mesh sketch phase, factored out so the
    resident path (fused sketch+pack jit over the device table) and the
    paged path (pack over STREAMED words) run the identical integer
    program.  The sort key is the big-endian field stream (hash fields,
    top ``TIEBREAK_BITS`` of the random tiebreak, zero pad, gid) packed to
    ``ceil(bits/32)`` words (``sorter.pack_bit_fields``); the trailing gid
    field doubles as payload and tiebreak-of-last-resort.  Rows past ``n``
    (mesh padding) get all-ones keys and gid -1: they sort to the tail and
    never enter the permutation.
    """
    from repro.core.stars import TIEBREAK_BITS, _rep_keys
    from repro.distributed.sorter import pack_bit_fields
    gid_bits = int(n).bit_length()
    k_tie, _, _, _ = _rep_keys(cfg, rep)
    n_pad = words.shape[0]
    gids = jnp.arange(n_pad, dtype=jnp.int32)
    real = gids < n
    # the SAME (n,) tiebreak draw as the single-device path, looked up
    # per gid
    tb = jax.random.bits(k_tie, (n,), jnp.uint32)
    tb = jnp.where(real, tb[jnp.minimum(gids, n - 1)],
                   jnp.uint32(0xFFFFFFFF))
    if cfg.mode == "lsh":
        bucket = lsh_lib.bucket_key(words, cfg.family)
        # full-width leading field: key word 0 IS the bucket id, which
        # distributed_window_blocks(bucket_word=0) relies on
        fields, widths = [bucket], [32]
    elif cfg.family.kind in ("simhash", "mixture"):
        bucket = jnp.zeros((n_pad,), jnp.uint32)
        m = words.shape[1]
        fields = [words[:, j].astype(jnp.uint32) for j in range(m)]
        widths = [1] * m                 # one BIT per hash word
    else:
        bucket = jnp.zeros((n_pad,), jnp.uint32)
        m = words.shape[1]
        fields = [words[:, j] for j in range(m)]
        widths = [32] * m                # full-width lexicographic
    tie = tb >> jnp.uint32(32 - TIEBREAK_BITS)
    pad = (-(sum(widths) + TIEBREAK_BITS + gid_bits)) % 32
    fields += [tie, jnp.zeros((n_pad,), jnp.uint32),
               gids.astype(jnp.uint32)]
    widths += [TIEBREAK_BITS, pad, gid_bits]
    keys = pack_bit_fields(fields, widths)
    keys = jnp.where(real[:, None], keys, jnp.uint32(0xFFFFFFFF))
    return keys, jnp.where(real, gids, -1), bucket


def _stream_sketch_words(store: PagedFeatureStore, cfg: StarsConfig, rep,
                         words_fns: Dict, n_rows: int) -> jax.Array:
    """Row-chunked sketch through a paged store: ``(n_rows, m)`` words.

    Bit-equal to the one-shot resident sketch: the hash projection depends
    only on (d, rep_seed), so sketching row blocks independently computes
    the identical per-row matmul/threshold (verified empirically for the
    simhash family on XLA — row-blocked and fused matmuls agree bitwise).
    ``n_rows`` may exceed ``store.n`` (mesh row padding): overflow rows
    gather the store's -1 sentinel, read zero rows, and sketch to exactly
    the words the resident path computes for its zero padding.  Only one
    pool-sized feature chunk is device-resident at a time; the (n, m)
    word block itself is an O(n) summary outside the feature budget.
    """
    chunk = max(store.page_rows,
                min(store.pool_pages * store.page_rows, n_rows))
    fn = words_fns.get(chunk)
    if fn is None:
        @jax.jit
        @scopes.scoped(scopes.SKETCH)
        def words_chunk(x, rep):
            rep_seed = jnp.asarray(rep, jnp.uint32) ^ jnp.uint32(cfg.seed)
            return lsh_lib.sketch(PointFeatures(dense=x), cfg.family,
                                  rep_seed=rep_seed)
        fn = words_fns.setdefault(chunk, words_chunk)
    idx = np.arange(n_rows, dtype=np.int64)
    idx[store.n:] = -1
    parts = []
    for c0 in range(0, n_rows, chunk):
        blk = idx[c0:c0 + chunk]
        if blk.size < chunk:
            blk = np.concatenate(
                [blk, np.full(chunk - blk.size, -1, np.int64)])
        parts.append(fn(store.gather(blk).dense, rep))
    words = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return words[:n_rows]


def _stream_embed_rows(store: PagedFeatureStore, measure: Measure,
                       lo: int, hi: int, embed_fns: Dict) -> np.ndarray:
    """Measure-state rows ``[lo, hi)`` streamed through a paged store.

    The paged analogue of the resident one-shot ``precompute``: feature
    rows stream through the page pool in pool-sized chunks, each chunk is
    embedded on device, and the (hi - lo, E) state block lands on HOST
    (the store pages it back in under ``transfer_stats['embed_page_*']``).
    Chunks are padded to a fixed shape (sentinel -1 gathers zero rows, as
    in ``_stream_sketch_words``) so one jit program serves every chunk —
    and row-blocked embedding is bitwise equal to the resident one-shot
    embed, the same row-independence the streamed sketch relies on.
    """
    count = hi - lo
    chunk = max(store.page_rows,
                min(store.pool_pages * store.page_rows, count))
    fn = embed_fns.get(chunk)
    if fn is None:
        fn = embed_fns.setdefault(chunk, jax.jit(
            lambda x: measure.precompute(PointFeatures(dense=x))))
    idx = np.arange(lo, hi, dtype=np.int64)
    parts = []
    for c0 in range(0, count, chunk):
        blk = idx[c0:c0 + chunk]
        if blk.size < chunk:
            blk = np.concatenate(
                [blk, np.full(chunk - blk.size, -1, np.int64)])
        parts.append(np.asarray(jax.device_get(fn(store.gather(blk).dense))))
    rows = np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return rows[:count]


class _PagedBackend:
    """Single-process build over a host-paged feature table: ``n`` bounded
    by HOST memory, peak device-resident *feature* bytes bounded by the
    store's page-pool budget (``StarsConfig.feature_pool_bytes``).

    Windowed sources run each repetition in three streamed stages:

      1. **sketch**: stream the hash words through the store in pool-sized
         row chunks (``_stream_sketch_words``) — bit-equal to the resident
         one-shot sketch because the projection is row-independent,
      2. **grid**: build the window grid on device from the words — gids,
         validity and bucket ids are O(n) summaries that stay pinned (only
         the O(n*d) feature table pages),
      3. **score**: walk the grid in window-row chunks sized so one
         chunk's gathered member block fits the page pool, gather each
         chunk's rows through the store, and run the SAME
         ``_score_windows`` with ``row_offset=chunk_start,
         total_rows=n_windows, stride=1`` — the global-row-keyed subset
         mode whose PRNG/mask equivalence the mesh backend already proves
         edge-for-edge — folding into the slabs chunk by chunk.

    Sentinel slots of a padded final chunk gather ZERO rows (the store's
    -1 contract, identical to the mesh fetch's zero-fill) and carry
    valid=False, so they never score.  Per-chunk counters concatenate like
    per-shard mesh counters and host-sum to the resident totals; the
    'allpairs' source streams its blocked sweep through the store with the
    same masks as ``AllPairsSource``.  tests/test_store.py holds the build
    to graph AND counter equality with the resident backend, and to the
    pool bound via ``transfer_stats['feature_page_peak_bytes']``.
    """

    def __init__(self, store: PagedFeatureStore, cfg: StarsConfig,
                 measure: Optional[Measure] = None):
        windowed = ("lsh-stars", "sorting-stars",
                    "lsh-allpairs", "sorting-allpairs")
        if cfg.source_name not in windowed + ("allpairs",):
            raise ValueError(
                f"unknown candidate source {cfg.source_name!r}; "
                f"known: {sorted(CANDIDATE_SOURCES)}")
        if cfg.hamming_prefilter_bits > 0:
            raise NotImplementedError(
                "feature_store='paged' does not support the Hamming "
                "prefilter (its packed words would need their own paging); "
                "unset hamming_prefilter_bits or use feature_store="
                "'resident'")
        self.store = store
        self.cfg = cfg
        self.measure = (measure if measure is not None else
                        make_measure(cfg.measure, alpha=cfg.mixture_alpha))
        self._embedded = 0           # rows whose measure state is current
        self._embed_fns: Dict = {}   # chunk-rows -> streamed embed jit
        self._words_fns: Dict = {}   # chunk-rows -> streamed sketch jit
        self._win_fns: Dict = {}     # n -> jitted grid builder
        self._chunk_fns: Dict = {}   # (C, nw, masks...) -> scoring chunk jit
        self._block_fns: Dict = {}   # (block, new_from) -> allpairs jit

    @property
    def n(self) -> int:
        return self.store.n

    # slab state: identical to the single-device backend (slabs are O(n*k)
    # device arrays, deliberately outside the feature pool budget)
    def init_state(self, capacity: int) -> acc_lib.EdgeAccumulator:
        return acc_lib.EdgeAccumulator.create(self.n, capacity)

    def place_state(self, state: acc_lib.EdgeAccumulator):
        return state

    def grow_state(self, state, n: int, capacity: int):
        return acc_lib.grow(state, n, capacity)

    def trim(self, state: acc_lib.EdgeAccumulator) -> acc_lib.EdgeAccumulator:
        return state

    def cluster_mesh(self):
        if not hasattr(self, "_cluster_mesh"):
            self._cluster_mesh = auto_axes(jax.make_mesh((1,), ("data",)))
        return self._cluster_mesh, "data"

    def ensure_measure_state(self) -> int:
        """Stream-embed rows not yet covered by the store's state table
        (all rows on the first build, the appended tail after an extend);
        returns how many rows were embedded (0 for stateless measures)."""
        if self.measure.state_width is None:
            return 0
        n = self.store.n
        new = n - self._embedded
        if new <= 0:
            return 0
        rows = _stream_embed_rows(self.store, self.measure,
                                  self._embedded, n, self._embed_fns)
        if self._embedded == 0:
            self.store.attach_state(rows)
        else:
            self.store.append_state(rows)
        self._embedded = n
        return new

    # -- windowed repetitions ------------------------------------------- #
    def _chunk_rows(self, nw: int) -> int:
        """Window rows per scoring chunk: the largest count whose gathered
        (C * window, d [+ E state]) member block fits the page-pool
        budget (a stateful measure's chunks gather state rows alongside
        the feature rows, through the same pool)."""
        width = self.store.d + (self.measure.state_width or 0)
        row_bytes = self.cfg.window * width * self.store.dtype.itemsize
        return int(max(1, min(nw, self.store.pool_bytes // max(row_bytes, 1))))

    def _win_fn(self):
        n, fn = self.store.n, None
        fn = self._win_fns.get(n)
        if fn is None:
            from repro.core.stars import _rep_keys, _rep_window_grid
            cfg = self.cfg

            @jax.jit
            def build_grid(words, rep):
                k_tie, k_shift, _, _ = _rep_keys(cfg, rep)
                return _rep_window_grid(cfg, words, k_tie, k_shift)

            fn = self._win_fns.setdefault(n, build_grid)
        return fn

    def _bind_chunk(self, C: int, nw: int, new_from: int,
                    refresh_below: int, refresh_fraction: float):
        key = (C, nw, new_from, refresh_below, refresh_fraction)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        from repro.core import windows as win_lib
        from repro.core.stars import _rep_keys, _score_windows
        cfg = self.cfg
        w = cfg.window
        measure_fn = self.measure
        has_state = self.measure.state_width is not None
        has_probs = refresh_below > 0

        @functools.partial(jax.jit, donate_argnums=0)
        def chunk_step(state, block, gid_c, valid_c, bucket_c, rep, row0,
                       *rest):
            rest = list(rest)
            mstate = (rest.pop(0).reshape(C * w, -1) if has_state else None)
            probs = rest.pop(0) if has_probs else None
            win = win_lib.Windows(gid=gid_c, valid=valid_c, bucket=bucket_c)
            feats = PointFeatures(dense=block.reshape(C * w, -1))
            member_index = jnp.arange(C * w, dtype=jnp.int32).reshape(C, w)
            _, _, k_lead, k_refresh = _rep_keys(cfg, rep)
            out = _score_windows(cfg, feats, measure_fn, None, win, k_lead,
                                 new_from=new_from,
                                 refresh_below=refresh_below,
                                 refresh_fraction=refresh_fraction,
                                 k_refresh=k_refresh, row_offset=row0,
                                 total_rows=nw, stride=1,
                                 member_index=member_index,
                                 refresh_probs=probs, state=mstate)
            state = acc_lib.accumulate(state, out["src"], out["dst"],
                                       out["w"], out["emit"])
            return state, {k: out[k] for k in
                           ("comparisons", "emitted", "prefilter_ops",
                            "scored_windows")}

        return self._chunk_fns.setdefault(key, chunk_step)

    def run_round(self, state, rep_index: int, new_from: int,
                  refresh_below: int = 0, refresh_fraction: float = 1.0,
                  refresh_probs=None):
        self.ensure_measure_state()
        if self.cfg.source_name == "allpairs":
            if refresh_below > 0:
                raise ValueError("the exact 'allpairs' source has no "
                                 "sampling staleness to refresh")
            return self._run_allpairs(state, new_from)
        nw = _refresh_window_count(self.cfg, self.store.n)
        C = self._chunk_rows(nw)
        key = (C, nw, new_from, refresh_below, refresh_fraction)
        with _bind_span(key, self.store.n not in self._win_fns
                        or key not in self._chunk_fns):
            rep = jnp.int32(rep_index)
            words = _stream_sketch_words(self.store, self.cfg, rep,
                                         self._words_fns, self.store.n)
            win = self._win_fn()(words, rep)
            pad = (-nw) % C
            gid = jnp.pad(win.gid, ((0, pad), (0, 0)), constant_values=-1)
            valid = jnp.pad(win.valid, ((0, pad), (0, 0)))
            bucket = jnp.pad(win.bucket, ((0, pad), (0, 0)),
                             constant_values=np.uint32(0xFFFFFFFF))
            probs = ()
            if refresh_below > 0:
                if refresh_probs is None:
                    refresh_probs = jnp.full((nw,), refresh_fraction,
                                             jnp.float32)
                probs = (jnp.asarray(refresh_probs, jnp.float32),)
            chunk_fn = self._bind_chunk(C, nw, new_from, refresh_below,
                                        refresh_fraction)
            has_state = self.measure.state_width is not None
            per_chunk = []
            for c0 in range(0, nw, C):
                gid_c = gid[c0:c0 + C]
                gid_np = np.asarray(jax.device_get(gid_c))
                block = self.store.gather(gid_np).dense
                extra = ((self.store.gather_state(gid_np),)
                         if has_state else ())
                state, cnt = chunk_fn(state, block, gid_c,
                                      valid[c0:c0 + C], bucket[c0:c0 + C],
                                      rep, jnp.int32(c0), *extra, *probs)
                per_chunk.append(cnt)
            counters = {k: jnp.concatenate([jnp.ravel(c[k])
                                            for c in per_chunk])
                        for k in per_chunk[0]}
            return state, counters

    # -- the exact blocked sweep ---------------------------------------- #
    def _run_allpairs(self, state, new_from: int):
        cfg = self.cfg
        n = self.store.n
        block = min(cfg.allpairs_block, max(n, 1))
        key = (block, new_from)
        has_state = self.measure.state_width is not None
        block_fn = self._block_fns.get(key)
        if block_fn is None:
            measure_fn = self.measure
            r1 = cfg.r1

            @functools.partial(jax.jit, donate_argnums=0)
            def block_step(state, fa, fb, a0, b0, *rest):
                ids_a = a0 + jnp.arange(block, dtype=jnp.int32)
                ids_b = b0 + jnp.arange(block, dtype=jnp.int32)
                if has_state:
                    sims = measure_fn(PointFeatures(dense=fa),
                                      PointFeatures(dense=fb),
                                      rest[0], rest[1])
                else:
                    sims = measure_fn(PointFeatures(dense=fa),
                                      PointFeatures(dense=fb))
                aa = jnp.broadcast_to(ids_a[:, None], (block, block))
                bb = jnp.broadcast_to(ids_b[None, :], (block, block))
                keep = (aa < bb) & (bb < n)
                if new_from > 0:
                    keep &= bb >= jnp.int32(new_from)
                if r1 is not None:
                    keep &= sims > r1
                return acc_lib.accumulate(state, aa, bb, sims, keep)

            block_fn = self._block_fns.setdefault(key, block_step)
        # same clamped block ids as AllPairsSource (rows past n re-read
        # row n-1; the keep mask discards them) — sequential blocks give
        # near-perfect page locality
        for a0 in range(0, n, block):
            ia = np.minimum(np.arange(a0, a0 + block), n - 1)
            fa = self.store.gather(ia).dense
            sa = (self.store.gather_state(ia),) if has_state else ()
            for b0 in range(a0, n, block):
                if new_from > 0 and b0 + block <= new_from:
                    continue
                ib = np.minimum(np.arange(b0, b0 + block), n - 1)
                fb = self.store.gather(ib).dense
                sb = (self.store.gather_state(ib),) if has_state else ()
                state = block_fn(state, fa, fb, jnp.int32(a0),
                                 jnp.int32(b0), *sa, *sb)
        comps = n * (n - 1) // 2 - new_from * (new_from - 1) // 2
        return state, {"comparisons": comps}

    def extend(self, new_features: PointFeatures) -> None:
        self.store.append(new_features)
        self._win_fns = {}          # shapes changed; rebind lazily
        self._chunk_fns = {}
        self._block_fns = {}


class _MeshBackend:
    """Mesh-sharded build: features, slabs AND scoring partitioned over
    ``data``.

    Phases per repetition (paper §4; distributed/stars_dist.py docstring has
    the full data path):

      1. per-shard sketch into multi-word sort keys (no comms),
      2. distributed sample-sort straight to per-shard *window slot blocks*
         (sorter.distributed_window_blocks): every sorted element is
         scattered at its global window slot (rank + sorting-mode shift)
         and one reduce-scatter hands shard i exactly the contiguous
         ~``n_windows/p`` window rows it owns
         (``windows.shard_row_layout``) — slot-space ownership means a
         window whose members straddle two shards' sorted output still
         arrives whole at its single owner, with no halo exchange,
      3. owner-keyed feature fetch (stars_dist.fetch_rows_all_to_all): each
         shard requests the feature (+ prefilter) rows of its ~n/p window
         slots from their home shards in one request/response all_to_all
         pair — the scoring-phase comms term, recorded in
         ``transfer_stats['all_to_all_bytes']`` like every other exchange,
      4. sharded scoring: each shard runs the SAME ``_score_windows``
         (core/stars.py) on only its rows, with a global window-row offset
         so leader draws and refresh/extension masks are keyed identically
         to the single-device path — per-shard scoring FLOPs are
         O(n*W/p), not the O(n*W) a replicated grid used to pay,
      5. explicit edge emit (stars_dist.accumulate_all_to_all): the
         now-partial per-shard candidate streams bucket insertion triples
         by owner shard and ship in ONE all_to_all before the local slab
         fold; counters concatenate across shards and sum to the
         single-device totals.

    Because the sorted order, PRNG draws and scoring floats are identical
    to one device — each global window row is scored exactly once, by
    exactly one shard, from the same member gids and feature rows — the
    mesh build remains edge-for-edge equal to the single-device build at
    any shard count (tests/test_mesh_parity.py), with per-shard scored
    window rows ≈ n_windows/p (the ``scored_windows`` counter).

    **Row layout / reshard rule**: the point count is padded up to
    ``n_pad = ceil(n / p) * p`` and both the feature table and the slab
    table are sharded in contiguous row blocks of ``n_pad / p`` — every
    shard within one (padded) row of even.  ``extend()`` re-pads: old pad
    rows are sliced off, the new rows appended, the table padded to the new
    ``n_pad`` and re-placed (the pad-and-reshard step; slab rows likewise
    via ``accumulator.grow`` + re-place).  Row ownership is always
    ``gid // (n_pad / p)``, which is what the feature fetch and the emit
    use to route requests and triples.  Checkpoints and graphs only ever
    see the first ``n`` rows (``trim``).
    """

    SORT_CAPACITY_FACTOR = 2.0
    # emit triples bucket by hash-random owner: per-destination counts
    # concentrate hard around m2/p, so 2x headroom is already ~12 sigma at
    # bench scale (the 4x it replaced paid double the wire for no fewer
    # drops — measured zero at both)
    EMIT_CAPACITY_FACTOR = 2.0
    FETCH_CAPACITY_FACTOR = 2.0

    def __init__(self, store: FeatureStore, cfg: StarsConfig, mesh,
                 measure: Optional[Measure] = None):
        windowed = ("lsh-stars", "sorting-stars",
                    "lsh-allpairs", "sorting-allpairs")
        if cfg.source_name not in windowed:
            raise NotImplementedError(
                f"mesh backend supports the windowed repetition sources "
                f"{windowed}, got {cfg.source_name!r}")
        if cfg.measure not in ("cosine", "dot", "learned"):
            raise NotImplementedError(
                "mesh backend scores cosine/dot or a state-complete "
                "learned measure (the tera-scale settings)")
        self.cfg = cfg
        self.mesh = auto_axes(mesh)
        self.axis = "data"
        self.p = mesh.shape[self.axis]
        self.measure = (measure if measure is not None else
                        make_measure(cfg.measure, alpha=cfg.mixture_alpha))
        if cfg.measure == "learned":
            # the scoring fetch ships ONE row-sharded table per slot; a
            # learned measure rides it as its E-float embedding rows (the
            # wire diet), which requires the pair head to need nothing but
            # the embeddings
            if not self.measure.state_complete:
                raise NotImplementedError(
                    "mesh learned scoring ships tower embeddings instead "
                    "of feature rows, so the measure must be "
                    "state-complete (TwoTowerConfig.pair_features in "
                    "('embed', 'none')); pair_features='raw' needs the "
                    "raw feature rows at every tile")
            if cfg.hamming_prefilter_bits > 0:
                raise NotImplementedError(
                    "mesh learned scoring does not combine with the "
                    "Hamming prefilter (the prefilter words ride the "
                    "feature fetch table the wire diet replaces)")
        if not isinstance(store, FeatureStore):
            # direct construction with raw features (tests, tools) — the
            # GraphBuilder path always hands a store
            store = ResidentFeatureStore(as_point_features(store))
        self.store = store
        self._paged = isinstance(store, PagedFeatureStore)
        self._n = store.n
        self._d = store.d
        if self._paged:
            # features stay on HOST; the sketch streams pool-sized row
            # chunks through the store and the scoring-phase fetch gathers
            # each shard's window rows the same way (no resident table)
            self.dense = None
            self._words_fns: Dict = {}   # chunk-rows -> streamed sketch jit
        else:
            self._place_features(jnp.asarray(store.features.dense))
            # single copy: the store's checkpoint/extend views alias the
            # padded sharded table instead of keeping the original alive
            store._rebind(PointFeatures(dense=self.dense), self._n)
        self._sketches: Dict = {}   # n -> sketch_fn (mask-independent)
        self._offsets: Dict = {}    # n -> offset_fn (window shift per rep)
        self._fetch_tables: Dict = {}   # n -> row-sharded fetch table
        self._bound: Dict = {}      # (n, new_from, refresh...) -> score_fn
        self._state_tab = None      # padded row-sharded measure state
        self._embedded = 0          # rows whose measure state is current
        self._embed_fn = None
        self._embed_fns: Dict = {}  # paged: chunk-rows -> streamed embed

    # -- padded row layout ---------------------------------------------- #
    @property
    def n(self) -> int:
        return self._n

    def _pad_rows(self, n: int) -> int:
        return -(-n // self.p) * self.p

    @property
    def _feature_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(self.axis, None))

    @property
    def _slab_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return acc_lib.EdgeAccumulator(
            nbr=self._feature_sharding, w=self._feature_sharding,
            ver=NamedSharding(self.mesh, P(self.axis)))

    def _place_features(self, dense: jax.Array) -> None:
        pad = self._pad_rows(self._n) - self._n
        if pad:
            dense = jnp.pad(dense, ((0, pad), (0, 0)))
        self.dense = jax.device_put(dense, self._feature_sharding)

    # -- slab state ----------------------------------------------------- #
    def init_state(self, capacity: int) -> acc_lib.EdgeAccumulator:
        return jax.device_put(
            acc_lib.EdgeAccumulator.create(self._pad_rows(self._n), capacity),
            self._slab_sharding)

    def place_state(self, state: acc_lib.EdgeAccumulator):
        """Place an unpadded (n, k) state (e.g. a restored checkpoint):
        pad rows to the mesh multiple, then shard row-blocks."""
        return jax.device_put(acc_lib.grow(state, self._pad_rows(self._n)),
                              self._slab_sharding)

    def grow_state(self, state, n: int, capacity: int):
        return jax.device_put(
            acc_lib.grow(state, self._pad_rows(n), capacity),
            self._slab_sharding)

    def trim(self, state: acc_lib.EdgeAccumulator) -> acc_lib.EdgeAccumulator:
        """The real rows of the padded slab table (checkpoint/finalize view:
        what leaves the device is always the unpadded (n, k) slab image, so
        snapshots restore bit-exactly onto ANY mesh size or one device)."""
        if state.n == self._n:
            return state
        return acc_lib.EdgeAccumulator(nbr=state.nbr[:self._n],
                                       w=state.w[:self._n],
                                       ver=state.ver[:self._n])

    # -- measure state (cached embeddings) ------------------------------ #
    def ensure_measure_state(self) -> int:
        """Embed rows not yet covered by the measure-state table.

        Resident: the new rows are embedded in one jit batch and the
        padded row-sharded state table rebuilt around the UNTOUCHED old
        embeddings (extend never re-embeds, so old scores stay bitwise
        stable).  Paged: rows stream through the host store exactly like
        the single-process paged backend (``_stream_embed_rows``), and the
        scoring fetch later pages them back in under ``embed_page_*``.
        Returns how many rows were embedded (0 for stateless measures).
        """
        if self.measure.state_width is None:
            return 0
        n = self._n
        new = n - self._embedded
        if new <= 0:
            return 0
        if self._paged:
            rows = _stream_embed_rows(self.store, self.measure,
                                      self._embedded, n, self._embed_fns)
            if self._embedded == 0:
                self.store.attach_state(rows)
            else:
                self.store.append_state(rows)
        else:
            if self._embed_fn is None:
                self._embed_fn = jax.jit(
                    lambda x: self.measure.precompute(
                        PointFeatures(dense=x)))
            new_rows = self._embed_fn(self.dense[self._embedded:n])
            tab = (new_rows if self._state_tab is None else
                   jnp.concatenate([self._state_tab[:self._embedded],
                                    new_rows], axis=0))
            pad = self._pad_rows(n) - n
            if pad:
                tab = jnp.pad(tab, ((0, pad), (0, 0)))
            self._state_tab = jax.device_put(tab, self._feature_sharding)
            self._fetch_tables = {}     # the fetch table IS the state
        self._embedded = n
        return new

    # -- the per-repetition programs ------------------------------------ #
    def _bind(self, new_from: int, refresh_below: int = 0,
              refresh_fraction: float = 1.0):
        if self.measure.state_width is not None and self._embedded < self._n:
            self.ensure_measure_state()
        if self._n not in self._sketches:
            self._sketches[self._n] = (self._bind_keys() if self._paged
                                       else self._bind_sketch())
        if self._n not in self._offsets:
            self._offsets[self._n] = self._bind_offset()
        if not self._paged and self._n not in self._fetch_tables:
            self._fetch_tables[self._n] = self._build_fetch_table()
        key = (self._n, new_from, refresh_below, refresh_fraction)
        if key not in self._bound:
            self._bound[key] = self._bind_score(new_from, refresh_below,
                                                refresh_fraction)
        return (self._sketches[self._n], self._offsets[self._n],
                self._fetch_tables.get(self._n), self._bound[key])

    def _bind_sketch(self):
        """The per-shard sketch into BIT-PACKED sort keys.

        Sketch + the shared ``_sketch_keys`` packing program, fused in one
        jit over the resident sharded table (see ``_sketch_keys`` for the
        key layout and the pad-row sentinel rule)."""
        cfg = self.cfg
        n = self._n

        @jax.jit
        @scopes.scoped(scopes.SKETCH)
        def sketch_phase(x, rep):
            rep_seed = jnp.asarray(rep, jnp.uint32) ^ jnp.uint32(cfg.seed)
            words = lsh_lib.sketch(PointFeatures(dense=x), cfg.family,
                                   rep_seed=rep_seed)
            return _sketch_keys(cfg, n, words, rep)

        return sketch_phase

    def _bind_keys(self):
        """Paged variant of ``_bind_sketch``: the words arrive already
        computed (streamed through the store in pool-sized chunks,
        ``_stream_sketch_words``); only the packing runs here.  Same
        integer program on bit-equal words -> identical sort keys."""
        cfg = self.cfg
        n = self._n

        @jax.jit
        @scopes.scoped(scopes.SKETCH)
        def keys_phase(words, rep):
            return _sketch_keys(cfg, n, words, rep)

        return keys_phase

    def _bind_offset(self):
        """Tiny per-repetition program: the window grid's slot offset.

        The sorting-mode random shift (``window_layout``) must be known
        BEFORE the sort scatters elements to their window slots
        (``distributed_window_blocks`` owns slots, not ranks), so it is
        computed up front from the same ``k_shift`` draw the single-device
        path uses.
        """
        from repro.core import windows as win_lib
        from repro.core.stars import _rep_keys
        cfg = self.cfg
        n = self._n

        @jax.jit
        def offset_phase(rep):
            _, k_shift, _, _ = _rep_keys(cfg, rep)
            offset, _ = win_lib.window_layout(cfg.mode, n, cfg.window,
                                              k_shift)
            return offset

        return offset_phase

    def _build_fetch_table(self):
        """The row-sharded table the scoring-phase fetch serves rows from:
        the padded feature table, with the packed Hamming-prefilter words
        bitcast alongside as extra float32 columns when the prefilter is
        armed (ONE exchange covers both).  A state-complete learned
        measure serves its (n_pad, E) embedding table INSTEAD — the
        embedding wire diet: when E < d the owner-keyed fetch ships
        proportionally fewer ``all_to_all_bytes``."""
        from repro.core.stars import _prefilter_sketch
        if self.measure.state_width is not None:
            return self._state_tab
        if self.cfg.hamming_prefilter_bits <= 0:
            return self.dense
        if self.dense.dtype != jnp.float32:
            raise NotImplementedError(
                "mesh prefilter fetch packs prefilter words next to "
                f"float32 features; got dtype {self.dense.dtype}")
        pref = _prefilter_sketch(PointFeatures(dense=self.dense),
                                 self.cfg.hamming_prefilter_bits,
                                 self.cfg.seed)
        table = jnp.concatenate(
            [self.dense,
             jax.lax.bitcast_convert_type(pref, jnp.float32)], axis=1)
        return jax.device_put(table, self._feature_sharding)

    def _bind_score(self, new_from: int, refresh_below: int = 0,
                    refresh_fraction: float = 1.0):
        """The windows-sharded scoring program.

        Each shard reshapes its slot block into its ~n_windows/p window
        rows and runs the shared ``_score_windows`` on ONLY those rows —
        feature/prefilter lookups go through local slot ids into the
        fetched block (``member_index``), leader and refresh draws are
        keyed by global window row (``row_offset``/``total_rows``), and
        the emitted global-gid streams feed the emit exchange directly.
        Per-shard scoring work is O(n*W/p); nothing O(n*W) is replicated
        — the one replicated residue is the O(n)-elementwise global PRNG
        draw each shard issues before slicing its rows
        (``windows.global_row_draw``), W-fold below the scoring tiles.
        """
        from jax.sharding import PartitionSpec as P

        from repro.core import windows as win_lib
        from repro.core.stars import _rep_keys, _score_windows
        cfg = self.cfg
        n = self._n
        w = cfg.window
        d = int(self._d)
        p = self.p
        nw, rps, _ = win_lib.shard_row_layout(cfg.mode, n, w, self.p)
        axis = self.axis
        measure_fn = self.measure
        stateful = self.measure.state_width is not None
        use_pref = cfg.hamming_prefilter_bits > 0
        # refresh rounds carry a replicated per-global-row keep-probability
        # vector (the age-weighted sample, GraphBuilder._refresh_probs)
        has_probs = refresh_below > 0

        def score_shard(gid_blk, bucket_blk, tab_blk, ok_blk, rep, *rest):
            probs = rest[0] if has_probs else None
            # round-robin row striping (windows.shard_row_permutation):
            # this shard's block holds global window rows i, i + p, ...
            row0 = jax.lax.axis_index(axis)
            # a counted fetch drop invalidates its slot (graceful, like a
            # sort drop); the bucket value stays so the surviving slots'
            # run structure is untouched
            gid_grid = jnp.where(ok_blk, gid_blk, -1).reshape(rps, w)
            win = win_lib.Windows(gid=gid_grid, valid=gid_grid >= 0,
                                  bucket=bucket_blk.reshape(rps, w))
            if stateful:
                # wire-diet block: the fetched rows ARE the E-float
                # embeddings; no feature rows, no prefilter words
                feats, mstate, pref = None, tab_blk, None
            else:
                feats = PointFeatures(dense=tab_blk[:, :d])
                mstate = None
                pref = (jax.lax.bitcast_convert_type(tab_blk[:, d:],
                                                     jnp.uint32)
                        if use_pref else None)
            _, _, k_lead, k_refresh = _rep_keys(cfg, rep)
            member_index = jnp.arange(rps * w, dtype=jnp.int32).reshape(
                rps, w)
            out = _score_windows(cfg, feats, measure_fn, pref, win, k_lead,
                                 new_from=new_from,
                                 refresh_below=refresh_below,
                                 refresh_fraction=refresh_fraction,
                                 k_refresh=k_refresh, row_offset=row0,
                                 total_rows=nw, stride=p,
                                 member_index=member_index,
                                 refresh_probs=probs, state=mstate)
            return (out["src"], out["dst"], out["w"], out["emit"],
                    out["comparisons"], out["emitted"],
                    out["prefilter_ops"], out["scored_windows"][None])

        return jax.jit(jax.shard_map(
            score_shard, mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis, None), P(axis), P())
            + ((P(),) if has_probs else ()),
            out_specs=tuple(P(axis) for _ in range(8))))

    def _sort_round(self, rep):
        """sketch + distributed sort of one repetition -> slot blocks."""
        from repro.core import windows as win_lib
        from repro.distributed.sorter import distributed_window_blocks
        sketch_fn = self._sketches[self._n]
        offset_fn = self._offsets[self._n]
        if self._paged:
            words = _stream_sketch_words(self.store, self.cfg, rep,
                                         self._words_fns,
                                         self._pad_rows(self._n))
            words = jax.device_put(words, self._feature_sharding)
            keys, gids, _bucket = sketch_fn(words, rep)
        else:
            keys, gids, _bucket = sketch_fn(self.dense, rep)
        _, _, total_slots = win_lib.shard_row_layout(
            self.cfg.mode, self._n, self.cfg.window, self.p)
        return distributed_window_blocks(
            keys, gids, self.mesh, slot_offset=offset_fn(rep),
            total_slots=total_slots, axis=self.axis,
            capacity_factor=self.SORT_CAPACITY_FACTOR,
            bucket_word=0 if self.cfg.mode == "lsh" else None,
            payload_bits=int(self._n).bit_length(),
            window=self.cfg.window)

    def _probs_arg(self, refresh_below: int, refresh_fraction: float,
                   refresh_probs):
        """The score program's refresh-probability operand (refresh rounds
        only); a missing vector falls back to the uniform sample."""
        if refresh_below <= 0:
            return ()
        if refresh_probs is None:
            refresh_probs = jnp.full(
                (_refresh_window_count(self.cfg, self._n),),
                refresh_fraction, jnp.float32)
        return (jnp.asarray(refresh_probs, jnp.float32),)

    def _fetch_rows_paged(self, blk_gid):
        """Owner-keyed fetch without a device-resident table.

        The slot gids come back to the host and the paged store serves the
        rows (metered as ``feature_page_*`` traffic instead of all_to_all
        volume); the block goes back row-sharded.  Invalid slots (gid -1)
        read ZERO rows with ok False — exactly the contract
        ``fetch_rows_all_to_all`` applies to dropped/invalid slots, so the
        scoring program is unchanged.  A state-complete learned measure
        serves its E-float embedding rows instead (``embed_page_*``).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        gids = np.asarray(jax.device_get(blk_gid))
        host_rows = (self.store.gather_state(gids)
                     if self.measure.state_width is not None
                     else self.store.gather(gids).dense)
        rows = jax.device_put(host_rows, self._feature_sharding)
        ok = jax.device_put(jnp.asarray(gids >= 0),
                            NamedSharding(self.mesh, P(self.axis)))
        return rows, ok

    def run_round(self, state, rep_index: int, new_from: int,
                  refresh_below: int = 0, refresh_fraction: float = 1.0,
                  refresh_probs=None):
        from repro.distributed.stars_dist import (accumulate_all_to_all,
                                                  fetch_rows_all_to_all)
        key = (self._n, new_from, refresh_below, refresh_fraction)
        with _bind_span(key, key not in self._bound):
            _, _, fetch_table, score_fn = self._bind(
                new_from, refresh_below, refresh_fraction)
            rep = jnp.int32(rep_index)
            blk_gid, blk_bucket, drop_sort = self._sort_round(rep)
            if self._paged:
                rows, rows_ok = self._fetch_rows_paged(blk_gid)
                drop_fetch = jnp.zeros((1,), jnp.int32)
            else:
                rows, rows_ok, drop_fetch = fetch_rows_all_to_all(
                    fetch_table, blk_gid, mesh=self.mesh, axis=self.axis,
                    capacity_factor=self.FETCH_CAPACITY_FACTOR)
            probs = self._probs_arg(refresh_below, refresh_fraction,
                                    refresh_probs)
            (src, dst, wts, emit, comparisons, emitted, pref_ops,
             scored) = score_fn(blk_gid, blk_bucket, rows, rows_ok, rep,
                                *probs)
            state, drop_emit = accumulate_all_to_all(
                state, src, dst, wts, emit,
                mesh=self.mesh, axis=self.axis,
                capacity_factor=self.EMIT_CAPACITY_FACTOR,
                exact_weights=self.cfg.exact_weights)
            counters = {"comparisons": comparisons, "emitted": emitted,
                        "prefilter_ops": pref_ops, "scored_windows": scored}
            counters["dropped"] = jnp.concatenate(
                [jnp.ravel(drop_sort), jnp.ravel(drop_fetch),
                 jnp.ravel(drop_emit)])
            return state, counters

    def run_round_pair(self, state, rep_index: int, new_from: int,
                       refresh_below: int = 0, refresh_fraction: float = 1.0,
                       refresh_probs=(None, None)):
        """Two consecutive repetitions sharing one fetch and one emit.

        The sorts stay per-repetition (each needs its own hash draw and
        splitters), but the feature fetch batches both repetitions' slot
        gids into ONE request/response pair and the edge emit ships both
        candidate streams in ONE exchange
        (``fetch_rows_all_to_all`` / ``accumulate_all_to_all`` tuple
        mode) — 5 all_to_all launches per pair instead of 8.  Scoring is
        per repetition with the SAME bound program as ``run_round``, and
        the coalesced fold is order-equivalent to two sequential folds
        (per-row top-k of a multiset union), so pairing changes no edge.

        Returns ``(state, counters_a, counters_b)`` — per-repetition
        counter dicts, so the session's per-round stats stream (and the
        per-round bench readers) see the same granularity as unpaired
        rounds; the shared fetch/emit drop counts ride with the first.
        """
        from repro.distributed.stars_dist import (accumulate_all_to_all,
                                                  fetch_rows_all_to_all)
        if self._paged:
            # the fetch isn't an exchange here (the store serves rows from
            # host), so there is nothing to coalesce; two sequential
            # rounds are the same fold order-equivalence the resident
            # pair relies on
            state, counters_a = self.run_round(
                state, rep_index, new_from, refresh_below, refresh_fraction,
                refresh_probs[0])
            state, counters_b = self.run_round(
                state, rep_index + 1, new_from, refresh_below,
                refresh_fraction, refresh_probs[1])
            return state, counters_a, counters_b
        key = (self._n, new_from, refresh_below, refresh_fraction)
        with _bind_span(key, key not in self._bound):
            _, _, fetch_table, score_fn = self._bind(
                new_from, refresh_below, refresh_fraction)
            rep_a, rep_b = jnp.int32(rep_index), jnp.int32(rep_index + 1)
            gid_a, bucket_a, drop_sort_a = self._sort_round(rep_a)
            gid_b, bucket_b, drop_sort_b = self._sort_round(rep_b)
            (rows_a, rows_b), (ok_a, ok_b), drop_fetch = fetch_rows_all_to_all(
                fetch_table, (gid_a, gid_b), mesh=self.mesh, axis=self.axis,
                capacity_factor=self.FETCH_CAPACITY_FACTOR)
            probs_a = self._probs_arg(refresh_below, refresh_fraction,
                                      refresh_probs[0])
            probs_b = self._probs_arg(refresh_below, refresh_fraction,
                                      refresh_probs[1])
            out_a = score_fn(gid_a, bucket_a, rows_a, ok_a, rep_a, *probs_a)
            out_b = score_fn(gid_b, bucket_b, rows_b, ok_b, rep_b, *probs_b)
            state, drop_emit = accumulate_all_to_all(
                state, (out_a[0], out_b[0]), (out_a[1], out_b[1]),
                (out_a[2], out_b[2]), (out_a[3], out_b[3]),
                mesh=self.mesh, axis=self.axis,
                capacity_factor=self.EMIT_CAPACITY_FACTOR,
                exact_weights=self.cfg.exact_weights)
            counters_a = {"comparisons": out_a[4], "emitted": out_a[5],
                          "prefilter_ops": out_a[6],
                          "scored_windows": out_a[7],
                          "dropped": jnp.concatenate(
                              [jnp.ravel(drop_sort_a), jnp.ravel(drop_fetch),
                               jnp.ravel(drop_emit)])}
            counters_b = {"comparisons": out_b[4], "emitted": out_b[5],
                          "prefilter_ops": out_b[6],
                          "scored_windows": out_b[7],
                          "dropped": jnp.ravel(drop_sort_b)}
            return state, counters_a, counters_b

    def extend(self, new_features: PointFeatures) -> None:
        if self._paged:
            self.store.append(new_features)
            self._n = self.store.n
        else:
            old_n = self._n
            new_rows = jnp.asarray(new_features.dense, self.dense.dtype)
            self._n = old_n + int(new_rows.shape[0])
            pad = self._pad_rows(self._n) - self._n      # pad-and-reshard

            @functools.partial(jax.jit,
                               out_shardings=self._feature_sharding)
            def repad(old, new):
                table = jnp.concatenate([old[:old_n], new], axis=0)
                return jnp.pad(table, ((0, pad), (0, 0)))

            self.dense = repad(self.dense, new_rows)
            self.store._rebind(PointFeatures(dense=self.dense), self._n)
        self._sketches = {}         # shapes changed; rebind lazily
        self._offsets = {}
        self._fetch_tables = {}
        self._bound = {}

    def cluster_mesh(self):
        return self.mesh, self.axis


# --------------------------------------------------------------------------- #
# The session
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class BuilderCheckpoint:
    """Host-side snapshot of a build session (resumable tera-scale builds).

    Plain numpy payloads — trivially serializable with np.savez.  Restoring
    into a session with the same features/config and running the remaining
    repetitions is bit-identical to never having checkpointed (repetition
    randomness derives from cfg.seed and the repetition index alone) —
    which is why ``cfg`` rides along: restore() refuses a mismatched config
    rather than silently continuing with different hash draws or slab
    sizing.

    Two flavours share this class:

      * **full** (``GraphBuilder.checkpoint()``): ``nbr``/``w`` hold the
        unpadded (n, k) slab image, ``ver`` the per-row logical versions,
        ``base_seq`` its position in the session's delta stream;
        ``delta_chain`` is None.
      * **delta** (``GraphBuilder.checkpoint(delta=True)``): ``nbr``/``w``
        are None — the payload is ``delta_chain``, the tuple of
        :class:`repro.service.delta.SlabDelta` records emitted since the
        full checkpoint whose stream position is ``base_seq``.
        ``restore(..., base=full_ckpt)`` replays the chain onto the full
        image bit-exactly, on any mesh size — a compressed checkpoint
        whose size is O(changed rows), not O(n * k).
    """

    n: int
    capacity: int
    reps_done: int
    nbr: Optional[np.ndarray]
    w: Optional[np.ndarray]
    stats: Dict[str, int]
    cfg: StarsConfig
    # staleness-repair state (GraphBuilder.refresh_reps): the old-old
    # watermark, how many refresh repetitions ran, and the fractional
    # auto-refresh credit the decaying policy has banked — carried so a
    # restored session refreshes exactly like the uncheckpointed one would
    # have, on any mesh size.
    refresh_watermark: int = 0
    refresh_reps: int = 0
    refresh_credit: float = 0.0
    # per-global-window-row refresh ages (rounds since last sampled) — the
    # age-weighted refresh bias's memory; None until a refresh round runs
    refresh_age: Optional[np.ndarray] = None
    # versioned-slab state (delta serving / delta checkpoints): the (n,)
    # int64 LOGICAL row versions (host base + device offset, see
    # accumulator.EdgeAccumulator.ver) — None only for pre-versioning
    # snapshots, which restore with all-zero versions.
    ver: Optional[np.ndarray] = None
    # how many deltas the session's delta stream had emitted when this
    # snapshot was cut (full checkpoints sync the ship shadow to their own
    # image, so a delta chain starting at base_seq composes from it)
    base_seq: int = 0
    # delta checkpoints only: the SlabDelta chain since the base_seq full
    # checkpoint, consecutive seqs (base_seq+1, ..., base_seq+len(chain))
    delta_chain: Optional[tuple] = None
    # Measure.fingerprint() of the session's similarity measure (a sha256
    # over learned tower params/config; None for unkeyed measures).
    # restore() refuses a mismatch: resuming under different tower params
    # would silently mix differently-scored edges into the same slabs.
    measure_fingerprint: Optional[str] = None


class GraphBuilder:
    """A graph-build session owning device-resident degree slabs.

    Args:
      features: PointFeatures (or a bare (n, d) dense array).
      cfg:      StarsConfig; ``cfg.source_name`` selects the candidate
                source, ``cfg.degree_cap`` sizes the slabs.
      mesh:     optional jax Mesh with a 'data' axis — shards features
                and slabs over it.  Any axis types are accepted (including
                the ``Explicit`` axes ``jax.make_mesh`` defaults to): the
                build runs on an ``Auto``-typed view of the same devices
                (``repro.distributed.mesh.auto_axes``).
      measure:  for ``cfg.measure='learned'``: a
                :class:`repro.similarity.measure.LearnedMeasure` (two-phase
                embed/score — enables the embedding cache, the mesh wire
                diet and the checkpoint fingerprint) or any Measure.
      learned_apply: LEGACY two-tower apply fn for measure='learned'; the
                bare ``(fa, fb) -> sims`` closure is wrapped as an
                ``OpaqueLearnedMeasure`` (every tile pays the full model).

    Methods: ``add_reps`` / ``extend`` / ``refresh_reps`` / ``checkpoint``
    / ``restore`` / ``finalize``; all state mutation is in-place on the
    session, device arrays are donated between rounds.
    """

    def __init__(self, features: FeaturesLike, cfg: StarsConfig, *,
                 mesh=None, learned_apply: Optional[Callable] = None,
                 measure: Optional[Measure] = None):
        if measure is not None and learned_apply is not None:
            raise ValueError(
                "pass either measure= or the legacy learned_apply=, not "
                "both (they would name two different scoring functions)")
        if cfg.refresh_rate < 0:
            raise ValueError(f"refresh_rate must be >= 0: {cfg.refresh_rate}")
        if cfg.refresh_rate > 0 and not cfg.refresh_fraction > 0:
            # the auto policy would burn full sketch+sort rounds whose
            # window sample is empty — report it at construction, exactly
            # like the manual refresh_reps(fraction=0) path does
            raise ValueError(
                f"refresh_rate > 0 needs a positive refresh_fraction "
                f"(got {cfg.refresh_fraction}): auto-refresh rounds would "
                f"sample zero windows and repair nothing")
        self.cfg = cfg
        self._learned_apply = learned_apply
        self._measure = make_measure(
            cfg.measure, alpha=cfg.mixture_alpha,
            learned=measure if measure is not None else learned_apply)
        self._cache_on = cfg.pair_cache_slots > 0
        self._embed_rows = 0
        store = as_feature_store(features, cfg)
        self._store = store
        paged = isinstance(store, PagedFeatureStore)
        if self._cache_on:
            # the pair-score cache is single-device, device-resident,
            # windowed-source state — reject the combinations it cannot
            # serve up front, naming the config knob
            if not self._measure.expensive:
                raise ValueError(
                    f"pair_cache_slots={cfg.pair_cache_slots} only pays "
                    f"for an expensive (learned) measure; "
                    f"measure={cfg.measure!r} is closed-form")
            if mesh is not None or paged:
                raise NotImplementedError(
                    "the pair-score cache is device-resident single-device "
                    "state; it does not combine with mesh= or "
                    "feature_store='paged' (set pair_cache_slots=0)")
            if cfg.source_name == "allpairs":
                raise ValueError(
                    "the exact 'allpairs' sweep scores every pair once — "
                    "a pair cache cannot hit (set pair_cache_slots=0)")
        if mesh is not None:
            # validate the store/backend contract HERE, naming the
            # offending constructor argument — not deep inside a backend
            # phase where the caller can't see which input was wrong
            if store.d is None:
                raise ValueError(
                    "mesh backend requires dense features: the features= "
                    "argument carries no dense block (set-only features "
                    "run on the single-device 'resident' store; supported "
                    "feature stores on a mesh: 'resident' and 'paged', "
                    "both dense-only)")
            if paged and cfg.hamming_prefilter_bits > 0:
                raise NotImplementedError(
                    "cfg.feature_store='paged' does not support the "
                    "Hamming prefilter on a mesh (the packed prefilter "
                    "words ride the resident fetch table); unset "
                    "hamming_prefilter_bits or use feature_store="
                    "'resident'")
            self._backend = _MeshBackend(store, cfg, mesh,
                                         measure=self._measure)
        elif paged:
            self._backend = _PagedBackend(store, cfg, self._measure)
        else:
            self._backend = _SingleDeviceBackend(store, cfg, self._measure)
        self._reps_done = 0
        self._counters: List[Dict] = []
        self._stats_base: Dict[str, int] = {}
        # staleness tracking: gids below the watermark are "old" — their
        # mutual pairs stopped being scored when the watermark last moved
        # (extend() masks them out).  refresh_reps() rescores a sampled
        # subset; the credit accumulator drives the automatic policy.
        self._refresh_below = 0
        self._refresh_reps = 0
        self._refresh_credit = 0.0
        self._refresh_age: Optional[np.ndarray] = None
        # versioned-slab serving state.  Logical row version i is
        # ``_ver_base + state.ver[i]`` (host int64 base + device int32
        # offset, the per-chunk-int32/host-int64 counter policy); the ship
        # shadow is the host image of the rows the delta stream has shipped
        # so far, against which finalize(delta=True) diffs.  ``_delta_log``
        # accumulates every emitted SlabDelta since the last FULL
        # checkpoint — the chain a checkpoint(delta=True) packages.
        self._ver_base = 0
        self._shadow_nbr: Optional[np.ndarray] = None
        self._shadow_w: Optional[np.ndarray] = None
        self._shipped_ver: Optional[np.ndarray] = None
        self._delta_seq = 0
        self._delta_log: List = []
        self._last_full_seq: Optional[int] = None
        self._capacity = cfg.slab_capacity(self.n, reps=max(cfg.r, 1))
        # Slabs are allocated lazily (first round / checkpoint / finalize):
        # restore() injects the checkpoint state instead, so resuming never
        # double-allocates the dominant device structure.
        self._state: Optional[acc_lib.EdgeAccumulator] = None

    def _validate_extend(self, nf: PointFeatures) -> None:
        """Surface store/backend contract violations up front, naming the
        offending argument — not from deep inside a backend phase."""
        store = self._store
        if nf.dense is None and store.d is not None:
            raise ValueError(
                f"extend(new_features=...): no dense block, but the "
                f"session's {self.cfg.feature_store!r} feature store holds "
                f"a dense (n, {store.d}) table")
        if (nf.dense is not None and store.dtype is not None
                and nf.dense.dtype != store.dtype):
            raise ValueError(
                f"extend(new_features=...): dense dtype {nf.dense.dtype} "
                f"does not match the session's feature store dtype "
                f"{store.dtype} (append never silently casts — the casted "
                f"rows would score differently than the originals)")

    # ------------------------------------------------------------------ #
    @property
    def n(self) -> int:
        """Number of points currently in the session."""
        return self._backend.n

    @property
    def feature_store(self) -> FeatureStore:
        """The session's FeatureStore (resident or paged)."""
        return self._store

    @property
    def measure(self) -> Measure:
        """The session's similarity Measure (two-phase contract)."""
        return self._measure

    @property
    def reps_done(self) -> int:
        return self._reps_done

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def refresh_watermark(self) -> int:
        """Points with gid below this are "old": their mutual pairs are the
        session's staleness exposure (0 until the first extend())."""
        return self._refresh_below

    @property
    def stats(self) -> Dict[str, int]:
        """Running session totals (comparisons, emitted, refresh_reps, ...)
        as host ints — the same dict a ``finalize()`` would attach to the
        Graph at this point.  Syncs the pending per-round device counters
        (cheap: they are rolled up every few rounds anyway), never the edge
        slabs."""
        return self._merged_stats()

    # ------------------------------------------------------------------ #
    def add_reps(self, reps: Optional[int] = None, *,
                 progress: Optional[Callable[[int], None]] = None
                 ) -> "GraphBuilder":
        """Run ``reps`` more repetitions (default cfg.r) into the slabs.

        One 'repetition' of the brute-force 'allpairs' source is a full
        exact n^2/2 sweep, so it allows exactly one (its default); a
        repeat would only re-score identical pairs and inflate the
        comparisons stat that defines the AllPair baseline.
        """
        if self.cfg.source_name == "allpairs":
            reps = 1 if reps is None else reps
            if reps != 1 or self._reps_done > 0:
                raise ValueError(
                    "the 'allpairs' source is exact: one sweep per point "
                    "set (use extend() to cover inserted points)")
        else:
            reps = self.cfg.r if reps is None else reps
        self._run_rounds(reps, new_from=0, progress=progress)
        return self

    def extend(self, new_features: FeaturesLike,
               reps: Optional[int] = None, *,
               progress: Optional[Callable[[int], None]] = None
               ) -> "GraphBuilder":
        """Append points and run ``reps`` new-vs-all repetitions.

        The slab table grows by the new rows (old edges untouched); the
        extension repetitions window ALL points but only score pairs with
        at least one new endpoint, so the incremental cost is the new-vs-all
        fraction of a full rebuild at equal repetitions.  The single-leader
        LSH-Stars source instead rescores every sub-bucket a new point
        lands in (a star is that graph's only intra-bucket connectivity;
        see ``_rep_lsh_stars``) — still skipping the untouched majority.

        On a mesh backend the feature and slab tables are re-padded to the
        new ``ceil(n/p)*p`` row multiple and re-placed (the pad-and-reshard
        step); the extension rounds then run the same masked scoring, so
        mesh extend() remains edge-for-edge equal to single-device extend.

        Every extend() advances the staleness watermark to the pre-insert
        point count, and — with ``cfg.refresh_rate`` > 0 — banks
        ``reps * refresh_rate`` refresh credit, immediately running the
        whole-repetition part of it as sampled old-old refresh rounds
        (:meth:`refresh_reps`).  Long-running sessions thereby bound their
        old-old staleness without user intervention: the unrefreshed
        window mass decays as ``(1 - refresh_fraction)^t`` in the number
        of refresh rounds t.
        """
        if self._reps_done == 0:
            raise ValueError(
                "extend() before any repetitions: the original points "
                "would never be scored against each other (extension "
                "rounds mask old-old pairs); run add_reps() first")
        if self.cfg.source_name == "allpairs":
            reps = 1 if reps is None else reps
            if reps != 1:
                raise ValueError("the 'allpairs' source is exact: one "
                                 "new-vs-all sweep per extension")
        else:
            reps = self.cfg.r if reps is None else reps
        # wrap WITHOUT device placement: jnp.asarray would silently
        # downcast a float64 host array before the dtype check below
        # could see it
        if isinstance(new_features, PointFeatures):
            nf = new_features
        elif isinstance(new_features, (jax.Array, np.ndarray)):
            nf = PointFeatures(dense=new_features)
        else:
            nf = PointFeatures(dense=np.asarray(new_features))
        if nf.n == 0:
            # nothing to score — and the staleness watermark must NOT
            # advance (old_n == n here, so advancing would mark every
            # point "old" without having run the rounds that cover it)
            return self
        self._validate_extend(nf)
        old_n = self.n
        self._backend.extend(nf)
        self._refresh_below = old_n
        self._run_rounds(reps, new_from=old_n, progress=progress)
        # the automatic decaying-rescore policy ('allpairs' is exact per
        # point set — it has no sampling staleness to repair)
        if self.cfg.refresh_rate > 0 and self.cfg.source_name != "allpairs":
            self._refresh_credit += reps * self.cfg.refresh_rate
            auto = int(self._refresh_credit)
            if auto:
                self._refresh_credit -= auto
                self._run_rounds(auto, new_from=0,
                                 refresh_below=self._refresh_below,
                                 refresh_fraction=self.cfg.refresh_fraction,
                                 progress=progress)
        return self

    def refresh_reps(self, reps: int = 1, *,
                     fraction: Optional[float] = None,
                     progress: Optional[Callable[[int], None]] = None
                     ) -> "GraphBuilder":
        """Run ``reps`` staleness-repair repetitions over old-old windows.

        Incremental ``extend()`` masks its rounds to new-vs-all pairs, so
        pairs among points below the watermark (everything predating the
        most recent extension) are only ever scored by the repetitions run
        while one of them was new — after many extensions their edge set
        goes stale relative to the evolved corpus.  A refresh repetition is
        the exact inverse of an extension repetition: it sketches and
        windows ALL current points with a fresh hash draw, then scores only
        pairs whose endpoints BOTH predate the watermark, inside a
        PRNG-sampled ``fraction`` of windows (``cfg.refresh_fraction`` by
        default).  Each round samples windows independently, so the
        probability a given old-old window has gone unrefreshed decays
        geometrically — a *decaying rescore* that bounds staleness at a
        small fraction of rebuild cost.  Runs through the same shared
        scoring path as every other round (core/stars.py
        ``_score_windows``), so mesh sessions stay edge-for-edge equal to
        single-device ones, refresh rounds included.

        Refresh work is visible in ``stats['refresh_reps']`` and
        ``stats['refresh_comparisons']`` (also counted in the
        ``comparisons`` total) and rides through checkpoints.
        """
        if self.cfg.source_name == "allpairs":
            raise ValueError("the exact 'allpairs' source scores every "
                             "pair once — it has no sampling staleness "
                             "to refresh")
        if self._refresh_below <= 0:
            raise ValueError(
                "nothing to refresh: no extend() has run, so no old-old "
                "pair is masked out of the repetition stream yet")
        fraction = (self.cfg.refresh_fraction if fraction is None
                    else fraction)
        if not 0.0 < fraction:
            raise ValueError(f"refresh fraction must be positive: {fraction}")
        self._run_rounds(reps, new_from=0,
                         refresh_below=self._refresh_below,
                         refresh_fraction=fraction, progress=progress)
        return self

    # Per-round counters are tiny device arrays, but a long-lived session
    # pinning one dict per repetition (plus per-shard dropped arrays on a
    # mesh) leaks device memory linearly in session length — so they are
    # rolled up to host ints every K rounds.  K > 1 keeps a little async
    # dispatch pipelining between the roll-up syncs.
    COUNTER_ROLLUP_EVERY = 8

    def _run_rounds(self, reps: int, new_from: int, *,
                    refresh_below: int = 0, refresh_fraction: float = 1.0,
                    progress: Optional[Callable[[int], None]] = None) -> None:
        # embed once per build/extend, BEFORE any round binds: only rows
        # the preceding extend() appended are new (stats['embed_rows'])
        self._embed_rows += self._backend.ensure_measure_state()
        self._grow(self.n, self._reps_done + reps)
        refresh = refresh_below > 0
        pair_fn = getattr(self._backend, "run_round_pair", None)
        done = 0
        while done < reps:
            with jax.profiler.TraceAnnotation(scopes.ROUND):
                rep0 = self._reps_done
                if pair_fn is not None and reps - done >= 2:
                    # coalesced repetition pair (mesh backend): the refresh
                    # probability vectors are computed SEQUENTIALLY — the
                    # second round's bias sees the first round's host-side
                    # age advance, exactly as two unpaired rounds would
                    probs = (self._next_refresh_probs(rep0, refresh_fraction)
                             if refresh else None,
                             self._next_refresh_probs(rep0 + 1,
                                                      refresh_fraction)
                             if refresh else None)
                    self._state, counters_a, counters_b = pair_fn(
                        self._state, rep0, new_from,
                        refresh_below=refresh_below,
                        refresh_fraction=refresh_fraction,
                        refresh_probs=probs)
                    self._note_round(counters_a, refresh, progress)
                    self._note_round(counters_b, refresh, progress)
                    done += 2
                else:
                    probs = (self._next_refresh_probs(rep0, refresh_fraction)
                             if refresh else None)
                    self._state, counters = self._backend.run_round(
                        self._state, rep0, new_from,
                        refresh_below=refresh_below,
                        refresh_fraction=refresh_fraction,
                        refresh_probs=probs)
                    self._note_round(counters, refresh, progress)
                    done += 1

    def _note_round(self, counters: Dict, refresh: bool,
                    progress: Optional[Callable[[int], None]]) -> None:
        if refresh:
            counters = dict(counters)
            counters["refresh_comparisons"] = counters["comparisons"]
            self._refresh_reps += 1
        self._counters.append(counters)
        if len(self._counters) >= self.COUNTER_ROLLUP_EVERY:
            self._roll_up_counters()
        if progress is not None:
            progress(self._reps_done)
        self._reps_done += 1

    def _next_refresh_probs(self, rep_index: int,
                            fraction: float) -> np.ndarray:
        """Per-global-window-row keep probabilities of ONE refresh round,
        advancing the host age ledger past it.

        The age-weighted sampling bias: a window's keep probability scales
        with ``1 + rounds-since-last-sampled``, normalized so the expected
        sampled mass stays ``fraction`` of the grid — windows the uniform
        sample kept missing become increasingly likely, tightening the
        geometric staleness-decay tail without extra rounds.  The ledger
        advance replays the round's keep draw on the host (the SAME
        ``k_refresh`` uniform the device issues, ``_rep_keys``), so ages
        reflect exactly the windows the device round sampled — identically
        on every backend, which keeps mesh and single-device sessions
        drawing identical refresh samples.  At ``fraction >= 1.0`` every
        window is kept and the bias degenerates to uniform.
        """
        from repro.core.stars import _rep_keys
        nw = _refresh_window_count(self.cfg, self.n)
        ages = self._refresh_age
        if ages is None:
            ages = np.zeros(nw, np.int64)
        elif ages.shape[0] < nw:        # extend() grew the grid: new rows
            ages = np.concatenate(      # start fresh (age 0)
                [ages, np.zeros(nw - ages.shape[0], np.int64)])
        if fraction >= 1.0:
            probs = np.full(nw, fraction, np.float32)
        else:
            weight = 1.0 + ages.astype(np.float64)
            probs = (fraction * weight / weight.mean()).astype(np.float32)
        k_refresh = _rep_keys(self.cfg, jnp.int32(rep_index))[3]
        draw = np.asarray(jax.random.uniform(k_refresh, (nw,)))
        self._refresh_age = np.where(draw < probs, 0, ages + 1)
        return probs

    def _grow(self, n: int, reps_total: int) -> None:
        cap = max(self._capacity,
                  self.cfg.slab_capacity(n, reps=max(reps_total, 1)))
        if self._state is None:
            with jax.profiler.TraceAnnotation(scopes.GROW):
                self._capacity = cap
                self._state = self._backend.init_state(cap)
        elif n > self._state.n or cap > self._capacity:
            with jax.profiler.TraceAnnotation(scopes.GROW):
                self._state = self._backend.grow_state(self._state, n, cap)
                self._capacity = cap

    def _ensure_state(self) -> acc_lib.EdgeAccumulator:
        if self._state is None:
            self._state = self._backend.init_state(self._capacity)
        return self._state

    # ------------------------------------------------------------------ #
    def _merged_stats(self) -> Dict[str, int]:
        totals = dict(self._stats_base)
        with jax.profiler.TraceAnnotation(scopes.COUNTERS):
            pending = jax.device_get(self._counters)
        for counters in pending:
            for key, val in counters.items():
                totals[key] = totals.get(key, 0) + int(
                    np.sum(np.asarray(val, np.int64)))
        # session-absolute values (NOT summable across roll-ups): overwrite
        # whatever a previous roll-up or restored checkpoint left behind
        totals["reps"] = self._reps_done
        totals["refresh_reps"] = self._refresh_reps
        totals.setdefault("refresh_comparisons", 0)
        if self._measure.expensive and not self._cache_on:
            # without the pair cache every counted comparison pays the
            # model; mirrored (not summed) so roll-ups can't double-count
            totals["expensive_comparisons"] = totals.get("comparisons", 0)
        if self._measure.state_width is not None:
            # rows this session ran precompute over (a restored session
            # re-embeds everything: measure state is not checkpointed)
            totals["embed_rows"] = self._embed_rows
        return totals

    def _roll_up_counters(self) -> Dict[str, int]:
        stats = self._merged_stats()
        self._counters = []
        self._stats_base = dict(stats)
        return stats

    # -- versioned slabs / delta serving -------------------------------- #
    def slab_state(self) -> acc_lib.EdgeAccumulator:
        """The live device-resident (n, k) slab view (mesh padding trimmed).

        No host transfer happens here — this is the view the serving loop's
        two-hop query program reads directly on device
        (repro.service.session), and what delta fetches gather changed rows
        from.
        """
        return self._backend.trim(self._ensure_state())

    def cluster(self, method: str = "affinity", *, target_clusters: int = 1,
                max_rounds: int = 32,
                min_similarity: Optional[float] = None,
                return_info: bool = False):
        """Cluster the CURRENT slab graph on device — zero edge fetches.

        The third leg of the production story (build -> serve -> cluster):
        runs the mesh-sharded clustering programs of
        ``repro.distributed.cluster_dist`` directly on the live padded slab
        state (the single-device backend runs the same programs on a
        trivial 1-device mesh), so features -> graph -> labels never ships
        the (n, k) slab image off device.  Only the final (n,) int32 label
        vector crosses to the host, metered under
        ``transfer_stats['cluster_label_*']``;
        ``transfer_stats['edge_fetches']`` / ``['bytes']`` stay untouched
        by any number of cluster() calls (asserted in tests).

        Args:
          method: ``"components"`` — connected components of the slab
            graph's symmetric closure; labels are each component's min
            gid, identical to ``connected_components_np`` on the
            finalized graph.  Or ``"affinity"`` — sharded Boruvka /
            average-Affinity; densified labels equal to the host
            ``affinity_clustering`` on the finalized graph (see
            cluster_dist's parity note).
          target_clusters / min_similarity: affinity stop knobs (as in
            ``affinity_clustering``); ignored by "components".
          max_rounds: label-round budget for either method.
          return_info: also return the {rounds, ...} info dict.
        Returns:
          (n,) int64 numpy labels, or (labels, info) with return_info.
        """
        from repro.distributed import cluster_dist
        state = self._ensure_state()           # padded mesh view, on device
        mesh, axis = self._backend.cluster_mesh()
        if method == "components":
            labels, info = cluster_dist.connected_components_mesh(
                state.nbr, n=self.n, mesh=mesh, axis=axis,
                max_rounds=max_rounds)
        elif method == "affinity":
            labels, info = cluster_dist.affinity_mesh(
                state.nbr, state.w, n=self.n, mesh=mesh, axis=axis,
                target_clusters=target_clusters, max_rounds=max_rounds,
                min_similarity=min_similarity)
        else:
            raise ValueError(f"unknown clustering method {method!r}; "
                             f"known: 'components', 'affinity'")
        if return_info:
            return labels, info
        return labels

    def row_versions(self) -> np.ndarray:
        """Current (n,) int64 LOGICAL row versions (``_ver_base`` + device
        offsets).  Fetches only the int32 version vector — a diagnostic /
        testing aid, deliberately not metered as a delta fetch."""
        state = self._backend.trim(self._ensure_state())
        return self._ver_base + np.asarray(jax.device_get(state.ver),
                                           np.int64)

    @property
    def delta_seq(self) -> int:
        """How many deltas this session's delta stream has emitted."""
        return self._delta_seq

    def _ensure_shadow(self, n: int, k: int) -> None:
        """Create or grow the host-side ship shadow to (n, k).

        The shadow starts EMPTY with shipped version 0: logical version 0
        means empty-since-creation (every fold bumps), so an all-zero
        baseline is exactly "nothing shipped yet" — the first delta ships
        every row that ever changed, later ones only what changed since.
        Rows added later start at shipped version ``_ver_base`` (their
        untouched logical version), so an untouched insert ships nothing.
        """
        if self._shadow_nbr is None:
            self._shadow_nbr = np.full((n, k), -1, np.int32)
            self._shadow_w = np.full((n, k), -np.inf, np.float32)
            self._shipped_ver = np.zeros((n,), np.int64)
            return
        n0, k0 = self._shadow_nbr.shape
        if n > n0 or k > k0:
            nbr = np.full((n, k), -1, np.int32)
            w = np.full((n, k), -np.inf, np.float32)
            nbr[:n0, :k0] = self._shadow_nbr
            w[:n0, :k0] = self._shadow_w
            sv = np.full((n,), self._ver_base, np.int64)
            sv[:n0] = self._shipped_ver
            self._shadow_nbr, self._shadow_w, self._shipped_ver = nbr, w, sv

    def _emit_delta(self):
        """Advance the delta stream one step: fetch changed rows, diff.

        THE delta device->host transfer: ships the (n,) int32 version
        vector plus only the slab rows whose logical version advanced past
        the ship shadow — O(changed rows), metered under
        ``transfer_stats['delta_*']``.  The Z-set diff against the shadow
        (repro.service.delta.diff_rows) turns the row images into
        (node, nbr, w, ±1) records; the shadow then advances past them.
        """
        from repro.service.delta import SlabDelta, diff_rows
        state = self.slab_state()
        n, k = int(state.n), int(state.capacity)
        ver_dev = np.asarray(jax.device_get(state.ver), np.int64)
        logical = self._ver_base + ver_dev
        acc_lib.transfer_stats["delta_fetches"] += 1
        acc_lib.transfer_stats["delta_bytes"] += n * 4   # the version vector
        n_old = 0 if self._shadow_nbr is None else self._shadow_nbr.shape[0]
        k_old = 0 if self._shadow_nbr is None else self._shadow_nbr.shape[1]
        self._ensure_shadow(n, k)
        changed = np.flatnonzero(logical > self._shipped_ver[:n])
        if changed.size:
            idx = jnp.asarray(changed.astype(np.int32))
            new_nbr, new_w = map(np.asarray, jax.device_get(
                (state.nbr[idx], state.w[idx])))
            acc_lib.transfer_stats["delta_bytes"] += (int(new_nbr.nbytes)
                                                      + int(new_w.nbytes))
        else:
            new_nbr = np.zeros((0, k), np.int32)
            new_w = np.zeros((0, k), np.float32)
        acc_lib.transfer_stats["delta_rows"] += int(changed.size)
        node, nbr_r, w_r, sign = diff_rows(
            changed.astype(np.int32),
            self._shadow_nbr[changed], self._shadow_w[changed],
            new_nbr, new_w)
        self._delta_seq += 1
        delta = SlabDelta(
            seq=self._delta_seq, n_old=n_old, n_new=n, k_old=k_old, k_new=k,
            rows=changed.astype(np.int32), row_ver=logical[changed].copy(),
            node=node, nbr=nbr_r, w=w_r, sign=sign)
        self._shadow_nbr[changed] = new_nbr
        self._shadow_w[changed] = new_w
        self._shipped_ver[changed] = logical[changed]
        self._delta_log.append(delta)
        return delta

    # ------------------------------------------------------------------ #
    def checkpoint(self, delta: bool = False) -> BuilderCheckpoint:
        """Snapshot the session to host arrays (resumable builds).

        **Full** (default): the UNPADDED (n, k) slab image plus per-row
        versions (mesh backends trim their row padding first), so a
        checkpoint taken on one mesh restores bit-exactly onto any other
        mesh size — or a single device.  A full checkpoint also SYNCS the
        delta-stream ship shadow to its own image (reusing the
        already-fetched arrays, no extra transfer): external delta
        consumers re-baseline from the checkpoint image, and delta
        checkpoints chain from it.

        **Delta** (``delta=True``): no slab image — the payload is the
        chain of SlabDelta records emitted since the last full checkpoint
        (including one cut right now for any unshipped changes), O(changed
        rows) instead of O(n * k).  Requires a prior full ``checkpoint()``
        this session; ``restore(..., base=that_full_checkpoint)`` replays
        the chain bit-exactly.
        """
        if delta:
            if self._last_full_seq is None:
                raise ValueError(
                    "checkpoint(delta=True) needs a prior full "
                    "checkpoint() in this session to chain from")
            self._emit_delta()          # capture unshipped changes
            # after an emit, shipped versions == logical versions exactly
            return BuilderCheckpoint(
                n=self.n, capacity=self._capacity,
                reps_done=self._reps_done,
                nbr=None, w=None, stats=self._roll_up_counters(),
                cfg=self.cfg,
                refresh_watermark=self._refresh_below,
                refresh_reps=self._refresh_reps,
                refresh_credit=self._refresh_credit,
                refresh_age=(None if self._refresh_age is None
                             else self._refresh_age.copy()),
                ver=self._shipped_ver[:self.n].copy(),
                base_seq=self._last_full_seq,
                delta_chain=tuple(self._delta_log),
                measure_fingerprint=self._measure.fingerprint())
        nbr, w, ver_dev = acc_lib.to_host(
            self._backend.trim(self._ensure_state()))
        logical = self._ver_base + np.asarray(ver_dev, np.int64)
        k = nbr.shape[1]
        self._ensure_shadow(self.n, k)
        self._shadow_nbr[:self.n, :k] = nbr
        self._shadow_w[:self.n, :k] = w
        self._shipped_ver[:self.n] = logical
        self._delta_log = []
        self._last_full_seq = self._delta_seq
        return BuilderCheckpoint(
            n=self.n, capacity=self._capacity, reps_done=self._reps_done,
            nbr=nbr, w=w, stats=self._roll_up_counters(), cfg=self.cfg,
            refresh_watermark=self._refresh_below,
            refresh_reps=self._refresh_reps,
            refresh_credit=self._refresh_credit,
            refresh_age=(None if self._refresh_age is None
                         else self._refresh_age.copy()),
            ver=logical, base_seq=self._delta_seq,
            measure_fingerprint=self._measure.fingerprint())

    @classmethod
    def restore(cls, features: FeaturesLike, cfg: StarsConfig,
                ckpt: BuilderCheckpoint, *, base: Optional[
                    BuilderCheckpoint] = None, mesh=None,
                learned_apply: Optional[Callable] = None,
                measure: Optional[Measure] = None) -> "GraphBuilder":
        """Resume a session from a checkpoint (same features + config).

        The measure must match too: ``ckpt.measure_fingerprint`` (a sha256
        over learned tower params/config) is compared against the restoring
        session's measure and a mismatch raises — resuming under different
        tower params would silently mix differently-scored edges into the
        checkpointed slabs.

        A DELTA checkpoint (``ckpt.delta_chain`` set) additionally needs
        ``base=`` — the full checkpoint it chains from — and restores by
        replaying the chain onto the base image
        (repro.service.delta.replay_chain), bit-exactly and onto any mesh
        size.  The restored session's delta stream is re-anchored at the
        restored image (ship shadow = image): a consumer holding the same
        checkpoint(s) keeps receiving exact increments.  Delta
        *checkpoints* need a fresh full ``checkpoint()`` first, though —
        the restored session has no full snapshot of its own to chain
        from.
        """
        if cfg != ckpt.cfg:
            raise ValueError(
                "checkpoint was built under a different StarsConfig — "
                "resuming would mix hash draws / slab sizing silently: "
                f"{ckpt.cfg} vs {cfg}")
        if ckpt.delta_chain is not None:
            if base is None:
                raise ValueError(
                    "delta checkpoint: pass base=<the full checkpoint its "
                    "chain starts from> (base_seq "
                    f"{ckpt.base_seq})")
            if base.delta_chain is not None or base.nbr is None:
                raise ValueError("base= must be a FULL checkpoint")
            if base.cfg != cfg:
                raise ValueError("base checkpoint has a different "
                                 "StarsConfig")
            if base.base_seq != ckpt.base_seq:
                raise ValueError(
                    f"delta chain starts at stream seq {ckpt.base_seq}, "
                    f"but base checkpoint was cut at seq {base.base_seq}")
            from repro.service.delta import replay_chain
            nbr, w = replay_chain(base.nbr, base.w, ckpt.delta_chain)
            ver = ckpt.ver
        else:
            nbr, w, ver = ckpt.nbr, ckpt.w, ckpt.ver
        builder = cls(features, cfg, mesh=mesh, learned_apply=learned_apply,
                      measure=measure)
        fp_ckpt = getattr(ckpt, "measure_fingerprint", None)
        fp_now = builder._measure.fingerprint()
        if fp_ckpt != fp_now:
            raise ValueError(
                "checkpoint was built under a different similarity "
                "measure (tower params/config fingerprint "
                f"{fp_ckpt!r} vs {fp_now!r}) — resuming would mix "
                "differently-scored edges into the same slabs")
        if builder.n != ckpt.n:
            raise ValueError(f"checkpoint holds {ckpt.n} points, features "
                             f"have {builder.n}")
        if ver is None:                 # pre-versioning snapshot
            ver = np.zeros((ckpt.n,), np.int64)
        ver = np.asarray(ver, np.int64)
        # int64 logical -> host base + device int32 offset (exact rebase)
        vbase = int(ver.min()) if ckpt.n else 0
        builder._ver_base = vbase
        builder._capacity = ckpt.capacity
        builder._state = builder._backend.place_state(
            acc_lib.from_host(nbr, w, (ver - vbase).astype(np.int32)))
        # re-anchor the delta stream at the restored image (copies: the
        # shadow mutates in place as deltas ship; ckpt arrays must not)
        builder._shadow_nbr = np.array(nbr, np.int32)
        builder._shadow_w = np.array(w, np.float32)
        builder._shipped_ver = ver.copy()
        builder._delta_seq = ckpt.base_seq + len(ckpt.delta_chain or ())
        builder._reps_done = ckpt.reps_done
        builder._stats_base = dict(ckpt.stats)
        builder._refresh_below = ckpt.refresh_watermark
        builder._refresh_reps = ckpt.refresh_reps
        builder._refresh_credit = ckpt.refresh_credit
        builder._refresh_age = (None if ckpt.refresh_age is None
                                else np.asarray(ckpt.refresh_age, np.int64))
        return builder

    def finalize(self, *, delta: bool = False):
        """Fetch edges off device: the whole graph, or only what changed.

        Default: the slabs cross device->host ONCE
        (``accumulator.to_graph``) and compact into a :class:`Graph`.  The
        session stays usable: more rounds can follow, and a later
        ``finalize()`` counts as its own single fetch.

        ``delta=True``: instead of the O(n * k) full image, fetch only the
        rows whose version advanced since the last ship and return a
        :class:`repro.service.delta.SlabDelta` — the Z-set change stream
        (additions + retractions vs the previously-shipped image) that a
        consumer applies to its replica (``apply_delta``) to track the
        device slabs row-exactly.  Metered under
        ``transfer_stats['delta_*']``; the first delta of a session ships
        every row that ever changed (the consumer starts from nothing),
        later ones only the increment.
        """
        if delta:
            return self._emit_delta()
        return acc_lib.to_graph(self._backend.trim(self._ensure_state()),
                                stats=self._roll_up_counters())
