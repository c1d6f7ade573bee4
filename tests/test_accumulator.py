"""Edge accumulator (graph/accumulator.py + kernels/topk_merge.py).

The load-bearing claim: the device-resident, degree-bounded accumulator is
*edge-for-edge equivalent* to the legacy host merge (concatenate each
repetition's emitted candidates, lexsort-dedup keeping max weight, degree-cap
the union), on both LSH and SortingLSH modes — while touching the host
exactly once per build.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # property tests skip, plain tests still run
    from _hypothesis_stub import given, settings, st

from repro.core import HashFamilyConfig, StarsConfig, build_graph
from repro.core.spanner import Graph
from repro.core.stars import _rep_candidates
from repro.data import mnist_like_points
from repro.graph import accumulator as acc_lib
from repro.kernels import ref
from repro.kernels.topk_merge import topk_merge
from repro.similarity.measures import pairwise_similarity


def _legacy_host_merge_build(feats, cfg):
    """The pre-accumulator builder: per-rep device->host transfer, host
    lexsort-dedup of the growing union, degree cap on every flush."""
    measure_fn = pairwise_similarity(cfg.measure, alpha=cfg.mixture_alpha)
    rep_fn = jax.jit(lambda r: _rep_candidates(cfg, feats, measure_fn,
                                               None, r))
    g = Graph(feats.n, np.empty(0, np.int64), np.empty(0, np.int64),
              np.empty(0, np.float32), {})
    for rep in range(cfg.r):
        out = jax.device_get(rep_fn(jnp.int32(rep)))
        keep = out["emit"]
        g = g.merged_with(Graph.from_candidates(
            feats.n, out["src"][keep], out["dst"][keep], out["w"][keep],
            np.ones(int(keep.sum()), bool)))
        if cfg.degree_cap is not None:
            g = g.degree_cap(cfg.degree_cap)
    return g


def _edge_dict(g):
    return {(int(s), int(d)): float(w)
            for s, d, w in zip(g.src, g.dst, g.w)}


@pytest.mark.parametrize("mode,m,window", [("lsh", 8, 128),
                                           ("sorting", 16, 64)])
def test_accumulator_matches_legacy_host_merge(mode, m, window):
    feats, _ = mnist_like_points(n=600, d=24, classes=6, spread=0.25, seed=0)
    cfg = StarsConfig(mode=mode, scoring="stars",
                      family=HashFamilyConfig("simhash", m=m),
                      measure="cosine", r=8, window=window, leaders=8,
                      degree_cap=20, seed=7)
    g_new = build_graph(feats, cfg)
    g_old = _legacy_host_merge_build(feats, cfg)
    e_new, e_old = _edge_dict(g_new), _edge_dict(g_old)
    assert set(e_new) == set(e_old)
    np.testing.assert_allclose([e_new[e] for e in sorted(e_new)],
                               [e_old[e] for e in sorted(e_old)],
                               rtol=0, atol=0)


def test_build_graph_single_device_to_host_transfer():
    feats, _ = mnist_like_points(n=400, d=16, classes=4, spread=0.2, seed=1)
    cfg = StarsConfig(mode="sorting", scoring="stars",
                      family=HashFamilyConfig("simhash", m=16),
                      measure="cosine", r=5, window=64, leaders=8,
                      degree_cap=10, seed=3)
    acc_lib.reset_transfer_stats()
    g = build_graph(feats, cfg)
    assert g.num_edges > 0
    assert acc_lib.transfer_stats["edge_fetches"] == 1
    assert acc_lib.transfer_stats["bytes"] == 400 * 10 * 8  # int32 + f32 slabs


@pytest.mark.fast
def test_topk_merge_saturates_at_capacity():
    k = 4
    # full slab of heavy edges; batch below the floor must not displace
    slab_nbr = jnp.asarray([[10, 11, 12, 13]], jnp.int32)
    slab_w = jnp.asarray([[0.9, 0.8, 0.7, 0.6]], jnp.float32)
    inc_nbr = jnp.asarray([[20, 21, 22, 23]], jnp.int32)
    inc_w = jnp.asarray([[0.5, 0.4, 0.3, 0.2]], jnp.float32)
    nbr, w = ref.topk_merge_ref(slab_nbr, slab_w, inc_nbr, inc_w)
    np.testing.assert_array_equal(np.asarray(nbr), [[10, 11, 12, 13]])

    # a heavier batch evicts exactly the lightest slab entries, in order
    inc_w2 = jnp.asarray([[0.95, 0.75, 0.1, 0.05]], jnp.float32)
    nbr2, w2 = ref.topk_merge_ref(slab_nbr, slab_w, inc_nbr, inc_w2)
    np.testing.assert_array_equal(np.asarray(nbr2), [[20, 10, 11, 21]])
    np.testing.assert_allclose(np.asarray(w2), [[0.95, 0.9, 0.8, 0.75]])

    # duplicates merge to max weight instead of occupying two slots
    inc_nbr3 = jnp.asarray([[12, 12, 30, -1]], jnp.int32)
    inc_w3 = jnp.asarray([[0.85, 0.65, 0.75, -np.inf]], jnp.float32)
    nbr3, w3 = ref.topk_merge_ref(slab_nbr, slab_w, inc_nbr3, inc_w3)
    np.testing.assert_array_equal(np.asarray(nbr3), [[10, 12, 11, 30]])
    np.testing.assert_allclose(np.asarray(w3), [[0.9, 0.85, 0.8, 0.75]])


@pytest.mark.fast
def test_topk_merge_sorted_ref_matches_general_ref():
    """The merge-path formulation == the re-sort oracle on inputs satisfying
    its preconditions (rows weight-sorted desc, per-row-unique neighbours,
    -1/-inf tails), with and without the precomputed nbr-order view —
    including cross-input duplicates at equal and differing weights."""
    rs = np.random.RandomState(7)
    n, k, kin = 16, 9, 7

    def rows(cols):
        nbr = np.full((n, cols), -1, np.int32)
        w = np.full((n, cols), -np.inf, np.float32)
        for i in range(n):
            nv = rs.randint(0, cols + 1)
            nbr[i, :nv] = rs.permutation(3 * cols)[:nv]
            w[i, :nv] = -np.sort(-rs.rand(nv).astype(np.float32))
        return nbr, w

    for _ in range(20):
        snbr, sw = rows(k)
        inbr, iw = rows(kin)
        for i in range(n):        # inject cross-input duplicates
            va, vb = np.flatnonzero(snbr[i] >= 0), np.flatnonzero(inbr[i] >= 0)
            if va.size and vb.size:
                a, j = rs.choice(va), rs.choice(vb)
                if snbr[i][a] not in inbr[i]:
                    inbr[i][j] = snbr[i][a]
                    if rs.rand() < 0.5:
                        iw[i][j] = sw[i][a]          # equal-weight duplicate
                    order = np.argsort(-iw[i], kind="stable")
                    inbr[i], iw[i] = inbr[i][order], iw[i][order]
        args = tuple(jnp.asarray(x) for x in (snbr, sw, inbr, iw))
        g_nbr, g_w = ref.topk_merge_ref(*args)
        s_nbr, s_w = ref.topk_merge_sorted_ref(*args)
        np.testing.assert_array_equal(np.asarray(g_nbr), np.asarray(s_nbr))
        np.testing.assert_array_equal(np.asarray(g_w), np.asarray(s_w))
        # the accumulate-fed path: companion view precomputed
        big = jnp.int32(2**31 - 1)
        iota = jnp.broadcast_to(jnp.arange(kin, dtype=jnp.int32), (n, kin))
        inbr_j, iw_j = args[2], args[3]
        pres = jax.lax.sort(
            (jnp.where(inbr_j >= 0, inbr_j, big),
             jnp.where(inbr_j >= 0, -iw_j, jnp.inf), iota),
            num_keys=2, dimension=1)
        p_nbr, p_w = ref.topk_merge_sorted_ref(*args, inc_presorted=pres)
        np.testing.assert_array_equal(np.asarray(g_nbr), np.asarray(p_nbr))
        np.testing.assert_array_equal(np.asarray(g_w), np.asarray(p_w))


# --------------------------------------------------------------------------- #
# Property tests: the sort-free merge path vs the re-sort oracle
# --------------------------------------------------------------------------- #


def _accumulator_rows(rs, n, cols, nbr_pool, weight_of, empty_prob):
    """Rows satisfying topk_merge_sorted_ref's preconditions: per-row-unique
    neighbours, weight-sorted descending, -1/-inf tails; ``weight_of(nbr,
    row)`` assigns weights (shared across inputs to manufacture cross-input
    duplicates and ties); ``empty_prob`` yields all-sentinel rows."""
    nbr = np.full((n, cols), -1, np.int32)
    w = np.full((n, cols), -np.inf, np.float32)
    for i in range(n):
        if rs.rand() < empty_prob:
            continue                       # adversarial: all-sentinel row
        nv = rs.randint(1, cols + 1)
        picks = rs.choice(nbr_pool, size=nv, replace=False)
        vals = np.asarray([weight_of(p, i) for p in picks], np.float32)
        order = np.argsort(-vals, kind="stable")
        nbr[i, :nv] = picks[order]
        w[i, :nv] = vals[order]
    return nbr, w


def _sorted_ref_outputs(snbr, sw, inbr, iw):
    """(merge-path, merge-path with precomputed companion view) outputs."""
    args = tuple(jnp.asarray(x) for x in (snbr, sw, inbr, iw))
    s_nbr, s_w = ref.topk_merge_sorted_ref(*args)
    n, kin = inbr.shape
    big = jnp.int32(2**31 - 1)
    iota = jnp.broadcast_to(jnp.arange(kin, dtype=jnp.int32), (n, kin))
    pres = jax.lax.sort(
        (jnp.where(args[2] >= 0, args[2], big),
         jnp.where(args[2] >= 0, -args[3], jnp.inf), iota),
        num_keys=2, dimension=1)
    p_nbr, p_w = ref.topk_merge_sorted_ref(*args, inc_presorted=pres)
    return (np.asarray(s_nbr), np.asarray(s_w),
            np.asarray(p_nbr), np.asarray(p_w))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 24), st.integers(1, 12),
       st.integers(1, 12), st.floats(0.0, 0.4))
def test_topk_merge_sorted_ref_property_distinct_weights(
        seed, n, k, kin, empty_prob):
    """With distinct per-neighbour weights (cross-input duplicates share
    their neighbour's weight or sit strictly below it), the merge path is
    EXACTLY the re-sort oracle — including all-sentinel rows and
    duplicate-heavy pools — with and without the companion view."""
    rs = np.random.RandomState(seed)
    pool = np.arange(2 * max(k, kin), dtype=np.int32)
    base = {(p, i): np.float32(0.05 * (j + 1))
            for i in range(n)
            for j, p in enumerate(rs.permutation(pool))}
    snbr, sw = _accumulator_rows(rs, n, k, pool,
                                 lambda p, i: base[(p, i)], empty_prob)
    # the inc instance of a shared neighbour ties exactly or sits strictly
    # between grid levels (0.05j vs 0.05j - 0.001): dedup max-wins either way
    inbr, iw = _accumulator_rows(
        rs, n, kin, pool,
        lambda p, i: base[(p, i)] - (np.float32(0.001)
                                     if rs.rand() < 0.5 else 0.0),
        empty_prob)
    g_nbr, g_w = ref.topk_merge_ref(*(jnp.asarray(x) for x in
                                      (snbr, sw, inbr, iw)))
    s_nbr, s_w, p_nbr, p_w = _sorted_ref_outputs(snbr, sw, inbr, iw)
    np.testing.assert_array_equal(np.asarray(g_nbr), s_nbr)
    np.testing.assert_array_equal(np.asarray(g_w), s_w)
    np.testing.assert_array_equal(np.asarray(g_nbr), p_nbr)
    np.testing.assert_array_equal(np.asarray(g_w), p_w)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 16), st.integers(1, 10),
       st.integers(1, 10), st.integers(1, 4), st.floats(0.0, 0.5))
def test_topk_merge_sorted_ref_property_adversarial_ties(
        seed, n, k, kin, levels, empty_prob):
    """Under massed equal-weight ties between DIFFERENT neighbours the two
    formulations may legitimately pick different tie-breaks at the capacity
    boundary (documented policy: slab-before-batch vs nbr-ascending), so
    assert semantic top-k equivalence instead of bit equality: identical
    per-row weight multisets, per-row-unique neighbours, every kept weight
    the dedup-max of its neighbour, rows weight-descending with aligned
    sentinel tails — and the companion-view path bit-equal to the plain
    merge path."""
    rs = np.random.RandomState(seed)
    pool = np.arange(2 * max(k, kin), dtype=np.int32)
    grid = np.linspace(0.0, 1.0, levels).astype(np.float32)
    shared = {(p, i): np.float32(grid[rs.randint(levels)])
              for i in range(n) for p in pool}
    weight_of = lambda p, i: shared[(p, i)]   # ties across AND within rows
    snbr, sw = _accumulator_rows(rs, n, k, pool, weight_of, empty_prob)
    inbr, iw = _accumulator_rows(rs, n, kin, pool, weight_of, empty_prob)
    g_nbr, g_w = ref.topk_merge_ref(*(jnp.asarray(x) for x in
                                      (snbr, sw, inbr, iw)))
    g_nbr, g_w = np.asarray(g_nbr), np.asarray(g_w)
    s_nbr, s_w, p_nbr, p_w = _sorted_ref_outputs(snbr, sw, inbr, iw)
    np.testing.assert_array_equal(s_nbr, p_nbr)
    np.testing.assert_array_equal(s_w, p_w)
    for i in range(n):
        # dedup-max of the union, per neighbour
        union = {}
        for nb, ww in zip(np.concatenate([snbr[i], inbr[i]]),
                          np.concatenate([sw[i], iw[i]])):
            if nb >= 0:
                union[int(nb)] = max(union.get(int(nb), -np.inf), float(ww))
        valid = s_nbr[i] >= 0
        kept = s_nbr[i][valid]
        assert len(set(kept.tolist())) == len(kept)          # unique nbrs
        for nb, ww in zip(kept, s_w[i][valid]):
            assert ww == union[int(nb)]                      # max-wins dedup
        # the top-k weight multiset is tie-invariant: must match the oracle
        np.testing.assert_array_equal(np.sort(s_w[i][valid]),
                                      np.sort(g_w[i][g_nbr[i] >= 0]))
        # weight-descending rows, sentinels only in the tail
        assert np.all(np.diff(s_w[i][valid]) <= 0)
        assert np.all(valid[:int(valid.sum())])
        assert np.all(s_w[i][~valid] == -np.inf)


@pytest.mark.fast
def test_topk_merge_sorted_ref_all_sentinel_rows():
    """Fully-empty inputs (the first repetition of a cold session) and
    empty-vs-partial rows round-trip unchanged through the merge path."""
    for k, kin in [(1, 1), (4, 2), (3, 7)]:
        empty_s = (np.full((3, k), -1, np.int32),
                   np.full((3, k), -np.inf, np.float32))
        empty_i = (np.full((3, kin), -1, np.int32),
                   np.full((3, kin), -np.inf, np.float32))
        s_nbr, s_w, p_nbr, p_w = _sorted_ref_outputs(*empty_s, *empty_i)
        for out in (s_nbr, p_nbr):
            np.testing.assert_array_equal(out, empty_s[0])
        for out in (s_w, p_w):
            np.testing.assert_array_equal(out, empty_s[1])
        # empty slab, one real inc entry lands in slot 0
        inbr = empty_i[0].copy()
        iw = empty_i[1].copy()
        inbr[1, 0], iw[1, 0] = 5, 0.5
        s_nbr, s_w, _, _ = _sorted_ref_outputs(*empty_s, inbr, iw)
        assert s_nbr[1, 0] == 5 and s_w[1, 0] == np.float32(0.5)
        assert np.all(s_nbr[[0, 2]] == -1)


def _merge_inputs(case, n, k, kin, rs):
    """(slab_nbr, slab_w, inc_nbr, inc_w) of one kernel case, -1 / -inf on
    empty slots."""
    def rand(cols, ids):
        return (rs.randint(-1, ids, (n, cols)).astype(np.int32),
                rs.rand(n, cols).astype(np.float32))

    def distinct(cols, ids):
        return np.stack([rs.permutation(ids)[:cols] for _ in range(n)]
                        ).astype(np.int32)

    if case == "random":
        (snbr, sw), (inbr, iw) = rand(k, 3 * k), rand(kin, 3 * kin)
    elif case == "slab_in_batch":       # every batch id is also a slab id,
        snbr = distinct(k, 10 * k)      # at another weight
        sw = rs.rand(n, k).astype(np.float32)
        inbr = np.take_along_axis(snbr, rs.randint(0, k, (n, kin)), 1)
        iw = rs.rand(n, kin).astype(np.float32)
    elif case == "batch_dups":          # a few ids, each many times
        snbr, sw = rand(k, 10 * k)
        inbr, iw = rand(kin, 4)
    elif case == "tied_weights":        # ties across different neighbours
        snbr, inbr = distinct(k, 4 * (k + kin)), rand(kin, 4 * (k + kin))[0]
        sw = (rs.randint(1, 4, (n, k)) / 4).astype(np.float32)
        iw = (rs.randint(1, 4, (n, kin)) / 4).astype(np.float32)
    elif case == "empty_and_full":      # even rows empty; odd rows full,
        both = distinct(k + kin, 4 * (k + kin))     # no id twice
        snbr, inbr = both[:, :k], both[:, k:]
        sw = rs.rand(n, k).astype(np.float32)
        iw = rs.rand(n, kin).astype(np.float32)
        snbr[::2], inbr[::2] = -1, -1
    elif case == "signed_zeros":        # +-0 and -inf weights, id INT32_MAX
        ids = max(8, (k + kin) // 2)    # about two instances an id
        (snbr, _), (inbr, _) = rand(k, ids), rand(kin, ids)
        snbr[snbr == 7], inbr[inbr == 7] = 2**31 - 1, 2**31 - 1
        pick = np.array([0.0, -0.0, -np.inf, -0.5], np.float32)
        sw = pick[rs.randint(0, 4, (n, k))]
        iw = pick[rs.randint(0, 4, (n, kin))]
    sw[snbr < 0], iw[inbr < 0] = -np.inf, -np.inf
    return tuple(map(jnp.asarray, (snbr, sw, inbr, iw)))


# one compile per shape, shared by the cases of that shape
_merge_interpret = jax.jit(lambda *a: topk_merge(*a, interpret=True))
_MERGE_CASES = [("random", 1, 4, 4), ("random", 17, 8, 8),
                ("random", 64, 16, 8), ("random", 5, 3, 9),
                ("random", 11, 250, 250)]
_MERGE_CASES = [pytest.param(*c, id="-".join(map(str, c[1:])))
                for c in _MERGE_CASES] + [
    ("slab_in_batch", 11, 250, 250), ("batch_dups", 11, 250, 250),
    ("tied_weights", 11, 250, 250), ("empty_and_full", 11, 250, 250),
    ("signed_zeros", 11, 250, 250),
    ("random", 11, 120, 250),           # k != kin, a slab under 128 lanes
    ("slab_in_batch", 11, 300, 250),    # a row that pads to 1,024 lanes
    ("batch_dups", 17, 8, 8), ("tied_weights", 17, 8, 8),
    ("empty_and_full", 17, 8, 8), ("signed_zeros", 17, 8, 8)]


@pytest.mark.fast
@pytest.mark.parametrize("case,n,k,kin", _MERGE_CASES)
def test_topk_merge_kernel_matches_ref(case, n, k, kin):
    rs = np.random.RandomState(n * k + kin)
    args = _merge_inputs(case, n, k, kin, rs)
    r_nbr, r_w = ref.topk_merge_ref(*args)
    p_nbr, p_w = _merge_interpret(*args)
    np.testing.assert_array_equal(np.asarray(r_nbr), np.asarray(p_nbr))
    # bit for bit: the sign of a zero weight too
    np.testing.assert_array_equal(np.asarray(r_w).view(np.int32),
                                  np.asarray(p_w).view(np.int32))


@pytest.mark.fast
def test_accumulate_is_incremental_top_k_of_union():
    """Streaming updates == one-shot degree cap of the whole union."""
    rs = np.random.RandomState(0)
    n, cap = 40, 5
    state = acc_lib.EdgeAccumulator.create(n, cap)
    union = Graph(n, np.empty(0, np.int64), np.empty(0, np.int64),
                  np.empty(0, np.float32), {})
    step = jax.jit(acc_lib.accumulate)
    for _ in range(4):
        src = rs.randint(0, n, 300)
        dst = rs.randint(0, n, 300)
        w = rs.rand(300).astype(np.float32)
        valid = rs.rand(300) < 0.7
        state = step(state, jnp.asarray(src), jnp.asarray(dst),
                     jnp.asarray(w), jnp.asarray(valid))
        union = union.merged_with(
            Graph.from_candidates(n, src, dst, w, valid))
    g = acc_lib.to_graph(state)
    expect = union.degree_cap(cap)
    assert _edge_dict(g) == _edge_dict(expect)
