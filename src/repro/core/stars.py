"""The Stars graph-building algorithms (paper §3, listings *Stars 1* / *Stars 2*).

Four algorithm variants, matching the paper's experimental grid (§5):

  mode="lsh",     scoring="stars"    -> LSH + Stars        (Stars 1)
  mode="lsh",     scoring="allpairs" -> LSH + non-Stars    (baseline)
  mode="sorting", scoring="stars"    -> SortingLSH + Stars (Stars 2)
  mode="sorting", scoring="allpairs" -> SortingLSH + non-Stars (baseline)

plus the brute-force ``allpairs_graph`` (the paper's *AllPair*).

Each repetition r of R:
  1. sketch the points with a fresh draw from the hash family,
  2. sort + window (core/windows.py) — LSH buckets or SortingLSH blocks,
  3. sample ``s`` random leaders per window (Stars) or take all pairs
     (non-Stars),
  4. score leader x member similarity tiles on the MXU (Pallas
     ``leader_score`` kernel on TPU; fused jnp path on CPU), masked by
     validity / self / same-bucket, and emit edges.

The *number of similarity comparisons* — the paper's headline efficiency
metric (Fig. 1) — is counted exactly as the number of unmasked scored pairs.

Edge accumulation is device-resident (graph/accumulator.py): every
repetition's masked candidate stream folds into fixed-capacity per-node
top-k slabs on device, and the host sees edges exactly once per build via
``Graph.from_degree_slabs``.  This removes the old per-repetition
device->host transfer and the repeated host-side lexsort-dedup/degree-cap
of the growing union; incremental per-node capping is exact because the
candidate pool only grows, so an edge outside a node's running top-k can
never re-enter.

Beyond-paper optimization (EXPERIMENTS.md §Perf): an optional *Hamming
prefilter* reuses packed SimHash bits to discard pairs whose estimated angle
is far above the threshold BEFORE the expensive measure (learned / Jaccard /
mixture) is evaluated, cutting full comparisons further at equal recall.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.core import lsh as lsh_lib
from repro.core import windows as win_lib
from repro.core.spanner import Graph
from repro.graph import accumulator as acc_lib
from repro.kernels import ops as kernel_ops
from repro.similarity.measures import PointFeatures
from repro.similarity.store import masked_take

# Random sort-tiebreak resolution, in bits.  The tiebreak only has to
# randomize the relative order of equal-sketch points; 20 bits make a
# same-window collision (which still resolves deterministically, by gid)
# vanishingly rare while letting the mesh wire format pack the tiebreak
# into 20 bits instead of a full word (core/builder.py ``_bind_sketch``).
# The single-device path truncates its draw to the SAME top bits so both
# backends sort identical keys.
TIEBREAK_BITS = 20


@dataclasses.dataclass(frozen=True)
class StarsConfig:
    """Configuration for one graph build.

    Attributes mirror the paper's notation:
      mode:      'lsh' (Stars 1) or 'sorting' (Stars 2 / SortingLSH).
      scoring:   'stars' (s random leaders) or 'allpairs' (non-Stars baseline).
      family:    hash family config (kind + sketch dimension M).
      measure:   similarity measure name (similarity/measures.py).
      r:         number of repetitions / sketches R (paper: 25/100/400).
      window:    W — SortingLSH window size, or the LSH bucket-size cap.
      leaders:   s — leaders per window (paper: 1/5/10/25).
      r1:        edge threshold (threshold spanners); None emits all scored.
      degree_cap:keep only the k heaviest edges per node (paper: 250).
      hamming_prefilter_bits / max_dist: beyond-paper prefilter (see module
                 docstring); disabled when bits == 0.
      score_chunk: windows scored per lax.map step (memory knob).
      seed:      root seed; every repetition folds its index into it.
      refresh_fraction / refresh_rate: the session staleness-repair knobs
                 (GraphBuilder.refresh_reps).  A *refresh repetition* masks
                 its candidate stream to a PRNG-sampled ``refresh_fraction``
                 of windows and to old-old pairs only (the inverse of the
                 extension rounds' new-vs-all masking), re-touching the
                 neighborhoods incremental extend() leaves stale.
                 ``refresh_rate`` > 0 arms the automatic policy: every
                 ``extend()`` banks ``reps * refresh_rate`` refresh credit
                 and runs the whole-repetition part of it immediately after
                 the extension rounds.  Because each refresh repetition
                 samples windows independently, the probability an old-old
                 window has not been rescored after t refresh repetitions
                 decays as (1 - refresh_fraction)^t — staleness is bounded
                 geometrically in session length, at a
                 ``refresh_rate * refresh_fraction * old_fraction^2``
                 fraction of a rebuild's scoring cost.  0 disables the
                 automatic policy (manual ``refresh_reps()`` still works).

    The accumulator's slab capacity is derived from ``degree_cap`` (the
    paper's k=250); with ``degree_cap=None`` the worst-case per-node degree
    ``r * (window + leaders)`` is materialized instead, which is only meant
    for small uncapped baselines.
    """

    mode: str = "sorting"
    scoring: str = "stars"
    family: lsh_lib.HashFamilyConfig = lsh_lib.HashFamilyConfig()
    measure: str = "cosine"
    r: int = 25
    window: int = 250
    leaders: int = 25
    r1: Optional[float] = None
    degree_cap: Optional[int] = 250
    hamming_prefilter_bits: int = 0
    hamming_prefilter_max: int = 0
    mixture_alpha: float = 0.5
    score_chunk: int = 8
    seed: int = 0
    source: Optional[str] = None
    allpairs_block: int = 2048
    refresh_fraction: float = 0.25
    refresh_rate: float = 0.0
    # Mesh wire precision for emitted edge weights: True ships float32
    # (edge-for-edge identical to single-device — the parity default);
    # False quantizes in-flight weights to bf16, halving the emit
    # exchange's dominant word at a <1% two-hop-recall cost
    # (tests/test_mesh_parity.py exercises both).  Single-device builds
    # never ship weights, so the flag only affects the mesh backend.
    exact_weights: bool = True
    # Feature-store backend (repro.similarity.store): 'resident' keeps the
    # (n, d) table device-resident (today's semantics, the default);
    # 'paged' keeps it in HOST memory as ``feature_page_rows``-row pages
    # and serves gathers through a device LRU page pool bounded by
    # ``feature_pool_bytes`` — so n can exceed device memory at
    # edge-for-edge-identical output (window scoring streams in
    # pool-sized window-row chunks; page traffic is metered under
    # ``transfer_stats['feature_page_*']``).  Dense measures only.
    feature_store: str = "resident"
    feature_page_rows: int = 512
    feature_pool_bytes: int = 64 << 20
    # Pair-score cache slots (similarity/pair_cache.py): > 0 arms a
    # device-resident hash-slot cache keyed by (gid_lo, gid_hi) so refresh
    # rounds and overlapping repetitions never re-pay an EXPENSIVE
    # measure's pair head for an already-scored pair.  Only meaningful for
    # expensive (learned) measures on the resident windowed backend; the
    # ``expensive_comparisons`` stat then counts cache misses instead of
    # every unmasked lane.  0 disables the cache.
    pair_cache_slots: int = 0

    def __post_init__(self) -> None:
        if not 0.0 <= self.mixture_alpha <= 1.0:
            raise ValueError(
                f"StarsConfig.mixture_alpha={self.mixture_alpha!r}: the "
                "mixture weight must lie in [0, 1]")
        if self.pair_cache_slots < 0:
            raise ValueError(
                f"StarsConfig.pair_cache_slots={self.pair_cache_slots!r}: "
                "must be >= 0 (0 disables the pair-score cache)")

    @property
    def source_name(self) -> str:
        """Candidate-source name (core/builder.py registry).

        Defaults to '<mode>-<scoring>' (e.g. 'sorting-stars'); set
        ``source='allpairs'`` for the brute-force AllPair sweep, which
        ignores mode/window/leaders entirely.
        """
        return self.source if self.source is not None \
            else f"{self.mode}-{self.scoring}"

    def slab_capacity(self, n: int, *, reps: Optional[int] = None) -> int:
        """Per-node accumulator capacity for an n-point build.

        ``reps`` overrides the config's R for session builds that run more
        repetitions than initially planned (GraphBuilder.add_reps)."""
        if self.source_name == "allpairs":
            return acc_lib.capacity_for(self.degree_cap, n)
        return acc_lib.capacity_for(self.degree_cap, n,
                                    reps=self.r if reps is None else reps,
                                    per_rep_bound=self.window + self.leaders)


# --------------------------------------------------------------------------- #
# Per-repetition device program
# --------------------------------------------------------------------------- #


def _prefilter_sketch(features: PointFeatures, bits: int,
                      seed: int) -> jax.Array:
    """Packed SimHash bits shared by all repetitions (prefilter only).

    The config seed is folded into the projection so two builds with
    different seeds don't share prefilter error patterns; the 0xBEEF stream
    id keeps the prefilter draw disjoint from the per-repetition sketches
    (which fold small rep indices into the same root key).
    """
    key = jax.random.fold_in(jax.random.key(seed), 0xBEEF)
    proj = jax.random.normal(key, (features.dense.shape[-1], bits),
                             features.dense.dtype)
    return lsh_lib.pack_bits(lsh_lib.simhash_bits(features.dense, proj))


def _score_tile(measure_fn, features: Optional[PointFeatures],
                a_gid: jax.Array, b_gid: jax.Array,
                measure_name: str = "",
                state: Optional[jax.Array] = None) -> jax.Array:
    """Similarity tile between gathered id tiles a_gid (..., A), b_gid (..., B).

    ``state``, when given, is the per-point Measure state table (the
    cached tower embeddings of a learned measure); the same clamp-gather
    as ``masked_take`` hands the gathered state tiles to the measure so
    only the pair head runs per pair.  ``features`` may then be None for
    state-complete measures (the mesh wire-diet path fetches only the E
    state columns).  ``measure_fn`` may be a ``similarity.measure.Measure``
    or a legacy 2-arg ``(fa, fb) -> sims`` closure — the latter is only
    ever called with ``state is None``.
    """
    sa = sb = None
    if state is not None:
        sa = jnp.take(state, jnp.maximum(a_gid, 0), axis=0)
        sb = jnp.take(state, jnp.maximum(b_gid, 0), axis=0)
    fa = fb = None
    if features is not None:
        fa = masked_take(features, a_gid)
        fb = masked_take(features, b_gid)
    if measure_name in ("cosine", "dot") and fa is not None \
            and fa.dense is not None:
        # Route through the fused leader_score kernel (Pallas on TPU,
        # jnp reference on CPU): normalize+matmul+mask in one VMEM pass.
        ok_a = jnp.ones(fa.dense.shape[:-1], bool)
        ok_b = jnp.ones(fb.dense.shape[:-1], bool)
        return kernel_ops.leader_score(
            fa.dense, fb.dense, ok_a, ok_b,
            normalized=measure_name == "cosine")
    if sa is not None:
        return measure_fn(fa, fb, sa, sb)
    return measure_fn(fa, fb)


def _refresh_window_sample(k_refresh: jax.Array, nw: int, fraction: float,
                           row_offset=0,
                           total_rows: Optional[int] = None,
                           stride: int = 1,
                           probs: Optional[jax.Array] = None) -> jax.Array:
    """(nw,) bool: the PRNG-sampled window subset one refresh round rescores.

    Drawn from the per-repetition ``k_refresh`` stream (``_rep_keys``), so
    the single-device and mesh backends sample identical windows — the
    refresh analogue of the shared leader draw.  Like the leader draw, the
    uniform is issued at the GLOBAL row count and row-gathered
    (``windows.global_row_draw``; ``stride`` > 1 under the mesh's striped
    row split), so a shard scoring a subset of a ``total_rows`` grid
    samples exactly the windows the single-device path would.

    ``probs``, when given, is the (total_rows,)-or-(nw,) per-GLOBAL-row
    keep probability array (the age-weighted refresh bias computed on the
    host, GraphBuilder._refresh_probs); ``fraction`` is then ignored.
    With uniform probs equal to ``fraction`` the sample is bit-identical
    to the fraction compare.  Values >= 1.0 keep every window (uniform
    draws live in [0, 1)), which makes a full-fraction refresh round the
    exact complement of an extension round over the same windows.
    """
    draw = win_lib.global_row_draw(
        lambda rows: jax.random.uniform(k_refresh, (rows,)), nw,
        row_offset, total_rows, fill=2.0,        # overflow rows never kept
        stride=stride)
    if probs is None:
        return draw < fraction
    pr = win_lib.global_row_draw(
        lambda rows: probs[:rows], nw, row_offset, total_rows, fill=-1.0,
        stride=stride)
    return draw < pr


def _scored_rows(nw: int, row_offset, total_rows: Optional[int],
                 stride: int = 1) -> jax.Array:
    """How many REAL global window rows this scoring call owns.

    Each global window row is owned by exactly one scoring call (the whole
    grid on one device; rows ``row_offset + stride * [0, nw)`` per shard
    on the mesh), so summing this counter across calls of one repetition
    gives exactly ``n_windows`` — the invariant tests/test_mesh_parity.py
    asserts, and the per-shard work measure behind the sharded-scoring
    bench row (overflow rows of an uneven partition are not counted: they
    hold no points and score nothing).
    """
    if total_rows is None:
        return jnp.int32(nw)
    r0 = jnp.asarray(row_offset, jnp.int32)
    return jnp.clip((total_rows - r0 + stride - 1) // stride, 0, nw)


def _rep_lsh_stars(cfg: StarsConfig, features: PointFeatures, measure_fn,
                   prefilter, win, *, new_from: int = 0,
                   refresh_below: int = 0, refresh_fraction: float = 1.0,
                   k_refresh: Optional[jax.Array] = None,
                   row_offset=0, total_rows: Optional[int] = None,
                   stride: int = 1,
                   member_index: Optional[jax.Array] = None,
                   refresh_probs: Optional[jax.Array] = None,
                   state: Optional[jax.Array] = None):
    """Stars 1 scoring: every member compares to its bucket's leader only.

    O(n) comparisons per repetition — the paper's quadratic->linear win.

    ``new_from`` > 0 restricts scoring to *sub-buckets containing at least
    one point with gid >= new_from* (incremental extension; see
    GraphBuilder.extend).  Unlike the multi-leader windowed path, a star is
    this graph's ONLY intra-bucket connectivity: a new member q reaches its
    old bucket-mates x exclusively via q - leader - x, so the whole touched
    star must be (re)scored, not just the new-endpoint pairs — the
    locality-driven repair rule of Cluster-and-Conquer-style builders.
    Untouched buckets (the vast majority for a small insertion) are still
    skipped entirely.

    ``refresh_below`` > 0 is the staleness-repair inverse (see
    :func:`_score_windows`): only pairs with BOTH endpoints below the
    watermark, in a ``refresh_fraction`` window sample drawn from
    ``k_refresh``, are scored.

    ``row_offset`` / ``total_rows`` / ``member_index`` have the same
    row-slice semantics as :func:`_score_windows` (the windows-sharded
    mesh scoring phase).
    """
    nw, w_sz = win.gid.shape
    use_pref = cfg.hamming_prefilter_bits > 0
    refresh = refresh_below > 0

    chunk = max(1, min(cfg.score_chunk * 8, nw))
    nw_pad = ((nw + chunk - 1) // chunk) * chunk
    pad = nw_pad - nw
    pad_w = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    gid = pad_w(win.gid)
    valid = pad_w(win.valid)
    bucket = pad_w(win.bucket)
    fidx = pad_w(win.gid if member_index is None else member_index)
    if refresh:
        keep_win = pad_w(_refresh_window_sample(
            k_refresh, nw, refresh_fraction, row_offset, total_rows,
            stride, refresh_probs))
    resh = lambda x: x.reshape((nw_pad // chunk, chunk) + x.shape[1:])

    def score_chunk(args):
        if refresh:
            gid_c, valid_c, bucket_c, fidx_c, keep_c = args   # (chunk, W)
        else:
            gid_c, valid_c, bucket_c, fidx_c = args           # (chunk, W)
        prev = jnp.concatenate(
            [jnp.zeros_like(bucket_c[:, :1]) ^ jnp.uint32(0xA5A5A5A5),
             bucket_c[:, :-1]], axis=1)
        is_head = (bucket_c != prev)
        is_head = is_head.at[:, 0].set(True)
        slot_ids = jnp.arange(w_sz, dtype=jnp.int32)[None, :]
        head_slot = jax.lax.cummax(
            jnp.where(is_head, slot_ids, 0), axis=1)      # (chunk, W)
        head_gid = jnp.take_along_axis(gid_c, head_slot, axis=1)
        head_fidx = jnp.take_along_axis(fidx_c, head_slot, axis=1)
        head_ok = jnp.take_along_axis(valid_c, head_slot, axis=1)

        # leaders skip self; an INVALID head disables its whole run — a
        # no-op on contiguous grids (a valid member never follows an
        # invalid head: pad runs are bucket-separated), load-bearing when
        # a mesh fetch drop invalidates a head slot mid-run (the member
        # would otherwise score against the zeroed fetched row)
        mask = valid_c & head_ok & (head_slot != slot_ids)
        if new_from > 0:
            nf = jnp.int32(new_from)
            is_new = valid_c & (gid_c >= nf)
            seg = jax.lax.cumsum(is_head.astype(jnp.int32), axis=1)
            rows_c = jnp.arange(gid_c.shape[0], dtype=jnp.int32)[:, None]
            seg_new = jnp.zeros((gid_c.shape[0], w_sz + 1), jnp.int32)
            seg_new = seg_new.at[rows_c, seg].max(is_new.astype(jnp.int32))
            mask &= jnp.take_along_axis(seg_new, seg, axis=1) > 0
        if refresh:
            rb = jnp.int32(refresh_below)
            mask &= keep_c[:, None] & (head_gid < rb) & (gid_c < rb)
        pref_ops = jnp.zeros((), jnp.int32)
        if use_pref:
            pref_ops = jnp.sum(mask).astype(jnp.int32)
            ham = lsh_lib.hamming_pairwise(
                prefilter[jnp.maximum(head_fidx, 0)][..., None, :],
                prefilter[jnp.maximum(fidx_c, 0)][..., None, :])[..., 0, 0]
            mask &= ham <= cfg.hamming_prefilter_max
        # row-wise member-vs-own-leader similarity: (chunk*W, 1, 1) tiles
        a = head_fidx.reshape(-1, 1)
        b = fidx_c.reshape(-1, 1)
        sims = _score_tile(measure_fn, features, a, b,
                           measure_name=cfg.measure, state=state)[:, 0, 0]
        sims = sims.reshape(gid_c.shape).astype(jnp.float32)
        comparisons = jnp.sum(mask).astype(jnp.int32)
        emit = mask
        if cfg.r1 is not None:
            emit &= sims > cfg.r1
        # per-chunk int32 like 'comparisons': summed on host as int64 so
        # tera-scale emit counts never overflow a device integer
        emitted = jnp.sum(emit).astype(jnp.int32)
        return (head_gid.reshape(-1), gid_c.reshape(-1),
                sims.reshape(-1), emit.reshape(-1), mask.reshape(-1),
                comparisons, emitted, pref_ops)

    operands = (resh(gid), resh(valid), resh(bucket), resh(fidx))
    if refresh:
        operands += (resh(keep_win),)
    outs = jax.lax.map(score_chunk, operands)
    src, dst, wts, emit, cmp, comp_chunks, emit_chunks, pref_chunks = outs
    src, dst, wts, emit, cmp = (
        x.reshape(-1) for x in (src, dst, wts, emit, cmp))
    return dict(src=src, dst=dst, w=wts, emit=emit, cmp=cmp,
                emitted=emit_chunks,
                comparisons=comp_chunks, prefilter_ops=pref_chunks,
                scored_windows=_scored_rows(nw, row_offset, total_rows,
                                            stride))


def _rep_keys(cfg: StarsConfig, rep_index: jax.Array):
    """The per-repetition PRNG keys, derived ONCE here so the single-device
    and mesh paths draw identical randomness:
    (k_tie, k_shift, k_lead, k_refresh).

    ``k_refresh`` (the refresh-round window sample) is folded in with a
    fixed stream id rather than widening the split, so the first three
    draws — and with them every pre-refresh build — stay bit-identical.
    """
    key = jax.random.fold_in(jax.random.key(cfg.seed), rep_index)
    k_tie, k_shift, k_lead = jax.random.split(key, 3)
    k_refresh = jax.random.fold_in(key, 0x5EF5)
    return k_tie, k_shift, k_lead, k_refresh


@scopes.scoped(scopes.WINDOWS)
def _rep_window_grid(cfg: StarsConfig, words: jax.Array,
                     k_tie: jax.Array,
                     k_shift: jax.Array) -> win_lib.Windows:
    """One repetition's window grid from its sketch words.

    The sort-and-window half of :func:`_rep_candidates`, factored out so
    the paged backend (core/builder.py ``_PagedBackend``) can build the
    IDENTICAL grid from words it streamed through the host feature store
    (the sketch projection is row-independent, so chunked words are
    bit-equal to the one-shot sketch).
    """
    n = words.shape[0]
    # keep only the top TIEBREAK_BITS: value order is identical to the
    # mesh backend's packed 20-bit tiebreak field (builder._sketch_keys),
    # and gid remains the final resolver of residual ties on both paths
    tiebreak = jax.random.bits(k_tie, (n,), jnp.uint32) \
        & jnp.uint32(((1 << TIEBREAK_BITS) - 1) << (32 - TIEBREAK_BITS))
    if cfg.mode == "lsh":
        bucket = lsh_lib.bucket_key(words, cfg.family)
        return win_lib.lsh_windows(bucket, window=cfg.window,
                                   tiebreak=tiebreak)
    if cfg.mode == "sorting":
        if cfg.family.kind in ("simhash", "mixture"):
            # one-bit words: pack them MSB-first (the mesh key layout,
            # builder._sketch_keys) so the sort compares ceil(M/32) words
            # instead of M — the same lexicographic order
            from repro.distributed.sorter import pack_bit_fields
            m = words.shape[1]
            words = pack_bit_fields([words[:, j] for j in range(m)], [1] * m)
        return win_lib.sorting_lsh_windows(
            words, window=cfg.window, shift_key=k_shift, tiebreak=tiebreak)
    raise ValueError(f"unknown mode {cfg.mode!r}")


def _rep_candidates(cfg: StarsConfig, features: PointFeatures,
                    measure_fn, prefilter, rep_index: jax.Array, *,
                    new_from: int = 0, refresh_below: int = 0,
                    refresh_fraction: float = 1.0,
                    refresh_probs: Optional[jax.Array] = None,
                    state: Optional[jax.Array] = None):
    """One repetition: sketch, window, score; returns the candidate stream.

    Returns dict with the full fixed-shape 'src','dst','w' stream plus its
    'emit' mask (the accumulator consumes the stream masked, so no device
    compaction is needed), and per-chunk 'comparisons' / 'emitted' /
    'prefilter_ops' int32 counts (summed on host as int64 — a tera-scale
    build overflows any full-stream device int32 sum).

    ``new_from`` > 0 masks out pairs whose endpoints BOTH predate an
    incremental extension (gid < new_from): old-old edges are already in the
    accumulator slabs, so extension repetitions only pay for new-vs-all
    comparisons (GraphBuilder.extend).  Exception: the single-leader
    LSH-Stars path rescores whole touched sub-buckets instead (see
    ``_rep_lsh_stars``).  The mask is applied before the comparison
    counters, so `stats['comparisons']` reflects the saving.

    ``refresh_below`` > 0 selects the inverse mask — only OLD-OLD pairs
    (both gids below the watermark), within a ``refresh_fraction`` sample
    of windows — for the staleness-repair rounds of
    ``GraphBuilder.refresh_reps``.  The two masks are mutually exclusive
    per round.
    """
    with jax.named_scope(scopes.SKETCH):
        rep_seed = jnp.asarray(rep_index, jnp.uint32) ^ jnp.uint32(cfg.seed)
        k_tie, k_shift, k_lead, k_refresh = _rep_keys(cfg, rep_index)

        words = lsh_lib.sketch(features, cfg.family, rep_seed=rep_seed)
    win = _rep_window_grid(cfg, words, k_tie, k_shift)

    return _score_windows(cfg, features, measure_fn, prefilter, win, k_lead,
                          new_from=new_from, refresh_below=refresh_below,
                          refresh_fraction=refresh_fraction,
                          k_refresh=k_refresh, refresh_probs=refresh_probs,
                          state=state)


@scopes.scoped(scopes.SCORE)
def _score_windows(cfg: StarsConfig, features: Optional[PointFeatures],
                   measure_fn, prefilter, win: win_lib.Windows,
                   k_lead: jax.Array, *, new_from: int = 0,
                   refresh_below: int = 0, refresh_fraction: float = 1.0,
                   k_refresh: Optional[jax.Array] = None,
                   row_offset=0, total_rows: Optional[int] = None,
                   stride: int = 1,
                   member_index: Optional[jax.Array] = None,
                   refresh_probs: Optional[jax.Array] = None,
                   state: Optional[jax.Array] = None):
    """Score one repetition's windows into a masked candidate stream.

    ``state`` is the per-point Measure state table (see ``_score_tile``);
    with a state-complete measure ``features`` may be None — the mesh
    wire-diet fetch then only ships state columns.  The generic (chunked)
    paths additionally return ``cmp``, the flat per-lane comparison mask
    (exactly the lanes ``comparisons`` sums), which the pair-score cache
    consumes in the bound round program.

    The scoring half of :func:`_rep_candidates`, factored out so the mesh
    backend (core/builder.py ``_MeshBackend``) can feed it windows built
    from the *distributed* sort: given identical window / ``k_lead`` /
    ``k_refresh`` inputs the emitted stream — gids, float weights, masks
    and comparison counts — is identical to the single-device path, which
    is what makes mesh builds edge-for-edge equal
    (tests/test_mesh_parity.py), refresh rounds included.
    ``features`` may be a padded table (extra rows are never addressed:
    every gid in a valid window slot is a real point).

    ``refresh_below`` > 0 masks to OLD-OLD pairs (both gids < watermark)
    inside a ``refresh_fraction`` PRNG sample of windows — the exact
    inverse of the ``new_from`` extension mask, shared by both backends
    through this one function (see GraphBuilder.refresh_reps).

    **Row-subset (windows-sharded) mode** — the mesh backend scores only
    its own ~``n_windows/p`` rows per shard instead of replicating the
    whole grid: ``win`` then holds the global window rows ``row_offset +
    stride * [0, nw)`` (``stride = p`` under the striped row split of
    ``windows.shard_row_layout``) and ``total_rows`` is the global row
    count.  Every PRNG draw (leaders, refresh sample) is issued at the
    global shape and row-gathered, so draws are keyed by global window row
    exactly as on one device.  ``member_index``, when given, is a
    (rows, W) index grid used for feature/prefilter gathers INSTEAD of
    ``win.gid`` — the mesh passes local slot ids into a slot-aligned
    feature block fetched by one explicit owner-keyed all_to_all
    (distributed/stars_dist.fetch_rows_all_to_all), so scoring never
    touches the global feature table.  Emitted src/dst are always global
    gids.  The returned ``scored_windows`` counts the real global rows
    this call owns (summing to ``n_windows`` across one repetition's
    calls).

    **Fused kernel path**: dense cosine/dot scoring without the Hamming
    prefilter routes through ``kernel_ops.window_score`` — gather leaders
    and members once, then one fused op (Pallas on TPU, jnp oracle on CPU;
    bit-identical either way) produces similarities, the emit mask and
    per-window counters, with no ``lax.map`` chunking and no padded
    (nw_pad, s, W) intermediate stream.  Counters come back per WINDOW
    (nw,) instead of per chunk; the host sum is shape-agnostic.
    """
    nw, w_sz = win.gid.shape
    if cfg.mode == "lsh" and cfg.scoring == "stars":
        # Paper Stars 1: ONE uniformly random leader per (sub-)bucket per
        # repetition.  The sort tiebreak is a fresh random priority, so
        # within-bucket order is uniform — the FIRST slot of every bucket
        # run IS a uniform random leader.  Window-initial slots start a new
        # run (= the paper's random sub-bucket split at the size cap).
        return _rep_lsh_stars(cfg, features, measure_fn, prefilter, win,
                              new_from=new_from,
                              refresh_below=refresh_below,
                              refresh_fraction=refresh_fraction,
                              k_refresh=k_refresh, row_offset=row_offset,
                              total_rows=total_rows, stride=stride,
                              member_index=member_index,
                              refresh_probs=refresh_probs, state=state)
    if cfg.scoring == "stars":
        leader_slot, leader_ok = win_lib.sample_leaders(
            win, s=cfg.leaders, key=k_lead,
            row_offset=row_offset, total_rows=total_rows, stride=stride)
    elif cfg.scoring == "allpairs":
        leader_slot = jnp.broadcast_to(jnp.arange(w_sz, dtype=jnp.int32),
                                       (nw, w_sz))
        leader_ok = win.valid
    else:
        raise ValueError(f"unknown scoring {cfg.scoring!r}")
    s = leader_slot.shape[1]
    refresh = refresh_below > 0

    if (cfg.measure in ("cosine", "dot") and features is not None
            and features.dense is not None
            and cfg.hamming_prefilter_bits <= 0):
        fidx = win.gid if member_index is None else member_index
        lead_fidx = jnp.take_along_axis(fidx, leader_slot, axis=1)
        lead_gid = jnp.take_along_axis(win.gid, leader_slot, axis=1)
        lead_bucket = jnp.take_along_axis(win.bucket, leader_slot, axis=1)
        lead = masked_take(features, lead_fidx).dense
        memb = masked_take(features, fidx).dense
        if refresh:
            keep_win = _refresh_window_sample(
                k_refresh, nw, refresh_fraction, row_offset, total_rows,
                stride, refresh_probs)
        else:
            keep_win = jnp.ones((nw,), bool)
        sims, emit, comparisons, emitted = kernel_ops.window_score(
            lead, memb, leader_slot, lead_gid, win.gid, leader_ok,
            win.valid, lead_bucket, win.bucket, keep_win,
            normalized=cfg.measure == "cosine",
            allpairs=cfg.scoring == "allpairs",
            match_bucket=cfg.mode == "lsh", new_from=new_from,
            refresh_below=refresh_below, r1=cfg.r1)
        src = jnp.broadcast_to(lead_gid[:, :, None], sims.shape)
        dst = jnp.broadcast_to(win.gid[:, None, :], sims.shape)
        return dict(src=src.reshape(-1), dst=dst.reshape(-1),
                    w=sims.reshape(-1), emit=emit.reshape(-1),
                    emitted=emitted, comparisons=comparisons,
                    prefilter_ops=jnp.zeros((nw,), jnp.int32),
                    scored_windows=_scored_rows(nw, row_offset, total_rows,
                                                stride))

    # Pad the window axis to a multiple of the scoring chunk.
    chunk = max(1, min(cfg.score_chunk, nw))
    nw_pad = ((nw + chunk - 1) // chunk) * chunk
    pad = nw_pad - nw
    pad_w = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    gid = pad_w(win.gid)
    valid = pad_w(win.valid)
    bucket_w = pad_w(win.bucket)
    fidx = pad_w(win.gid if member_index is None else member_index)
    leader_slot = pad_w(leader_slot)
    leader_ok = pad_w(leader_ok)
    if refresh:
        keep_win = pad_w(_refresh_window_sample(
            k_refresh, nw, refresh_fraction, row_offset, total_rows,
            stride, refresh_probs))

    resh = lambda x: x.reshape((nw_pad // chunk, chunk) + x.shape[1:])
    same_bucket_mode = cfg.mode == "lsh"
    allpairs = cfg.scoring == "allpairs"
    use_pref = cfg.hamming_prefilter_bits > 0

    def score_chunk(args):
        if refresh:
            gid_c, valid_c, bucket_c, fidx_c, lslot_c, lok_c, keep_c = args
        else:
            gid_c, valid_c, bucket_c, fidx_c, lslot_c, lok_c = args
        lead_gid = jnp.take_along_axis(gid_c, lslot_c, axis=1)
        lead_fidx = jnp.take_along_axis(fidx_c, lslot_c, axis=1)
        lead_bucket = jnp.take_along_axis(bucket_c, lslot_c, axis=1)
        mask = (lok_c[:, :, None] & valid_c[:, None, :])
        # exclude self-comparison (slot identity, robust to duplicate gids)
        mask &= lslot_c[:, :, None] != jnp.arange(w_sz, dtype=jnp.int32)[None, None, :]
        if allpairs:
            # count each unordered pair once: upper triangle
            mask &= (lslot_c[:, :, None]
                     < jnp.arange(w_sz, dtype=jnp.int32)[None, None, :])
        if same_bucket_mode:
            mask &= lead_bucket[:, :, None] == bucket_c[:, None, :]
        if new_from > 0:
            nf = jnp.int32(new_from)
            mask &= (lead_gid[:, :, None] >= nf) | (gid_c[:, None, :] >= nf)
        if refresh:
            rb = jnp.int32(refresh_below)
            mask &= keep_c[:, None, None]
            mask &= (lead_gid[:, :, None] < rb) & (gid_c[:, None, :] < rb)
        pref_ops = jnp.zeros((), jnp.int32)
        if use_pref:
            pref_ops = jnp.sum(mask).astype(jnp.int32)
            ham = lsh_lib.hamming_pairwise(
                prefilter[jnp.maximum(lead_fidx, 0)],
                prefilter[jnp.maximum(fidx_c, 0)])
            mask &= ham <= cfg.hamming_prefilter_max
        sims = _score_tile(measure_fn, features, lead_fidx, fidx_c,
                           measure_name=cfg.measure, state=state)
        # Per-chunk int32 counts; summed on host as Python ints so tera-scale
        # comparison/emit counts never overflow a device integer.
        comparisons = jnp.sum(mask).astype(jnp.int32)
        emit = mask
        if cfg.r1 is not None:
            emit &= sims > cfg.r1
        emitted = jnp.sum(emit).astype(jnp.int32)
        src = jnp.broadcast_to(lead_gid[:, :, None], sims.shape)
        dst = jnp.broadcast_to(gid_c[:, None, :], sims.shape)
        return (src.reshape(-1), dst.reshape(-1),
                sims.reshape(-1).astype(jnp.float32), emit.reshape(-1),
                jnp.broadcast_to(mask, sims.shape).reshape(-1),
                comparisons, emitted, pref_ops)

    operands = (resh(gid), resh(valid), resh(bucket_w), resh(fidx),
                resh(leader_slot), resh(leader_ok))
    if refresh:
        operands += (resh(keep_win),)
    outs = jax.lax.map(score_chunk, operands)
    src, dst, wts, emit, cmp, comp_chunks, emit_chunks, pref_chunks = outs

    src, dst, wts, emit, cmp = (
        x.reshape(-1) for x in (src, dst, wts, emit, cmp))
    return dict(src=src, dst=dst, w=wts, emit=emit, cmp=cmp,
                emitted=emit_chunks,
                comparisons=comp_chunks, prefilter_ops=pref_chunks,
                scored_windows=_scored_rows(nw, row_offset, total_rows,
                                            stride))


# --------------------------------------------------------------------------- #
# Public builders
# --------------------------------------------------------------------------- #


def build_graph(features: PointFeatures, cfg: StarsConfig, *,
                learned_apply: Optional[Callable] = None,
                progress: Optional[Callable[[int], None]] = None) -> Graph:
    """Run R repetitions of Stars/non-Stars and return the merged graph.

    DEPRECATED one-shot wrapper over :class:`repro.core.builder.GraphBuilder`
    (kept so the paper-repro scripts and older call sites keep working).
    The session API additionally supports incremental repetitions, point
    insertion, and checkpoint/resume; see core/builder.py.
    """
    from repro.core.builder import GraphBuilder
    builder = GraphBuilder(features, cfg, learned_apply=learned_apply)
    builder.add_reps(cfg.r, progress=progress)
    return builder.finalize()


def allpairs_graph(features: PointFeatures, measure: str = "cosine", *,
                   r1: Optional[float] = None,
                   degree_cap: Optional[int] = None,
                   block: int = 2048, mixture_alpha: float = 0.5,
                   learned_apply: Optional[Callable] = None) -> Graph:
    """Brute-force *AllPair* baseline: exact n^2/2 comparisons, blocked.

    DEPRECATED one-shot wrapper over the 'allpairs' candidate source of
    :class:`repro.core.builder.GraphBuilder` (one round == one full blocked
    sweep; edges reach the host once, at finalize).
    """
    from repro.core.builder import GraphBuilder
    cfg = StarsConfig(source="allpairs", measure=measure, r=1, r1=r1,
                      degree_cap=degree_cap, mixture_alpha=mixture_alpha,
                      allpairs_block=block)
    builder = GraphBuilder(features, cfg, learned_apply=learned_apply)
    builder.add_reps(1)
    return builder.finalize()
