"""Downstream graph algorithms: CC, affinity, VMeasure, single-linkage."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # property tests skip, plain tests still run
    from _hypothesis_stub import given, settings, st

from repro.core.spanner import Graph
from repro.graph import (affinity_clustering, connected_components_jax,
                         connected_components_np,
                         single_linkage_from_spanners, v_measure)
from repro.graph.components import num_components


def _canon(labels):
    _, inv = np.unique(labels, return_inverse=True)
    return inv


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(5, 60), st.floats(0.0, 0.2))
def test_cc_jax_matches_union_find(seed, n, density):
    rs = np.random.RandomState(seed)
    e = int(density * n * n) + 1
    src = rs.randint(0, n, e)
    dst = rs.randint(0, n, e)
    l1 = _canon(connected_components_np(n, src, dst))
    l2 = _canon(np.asarray(connected_components_jax(n, src, dst)))
    assert np.array_equal(l1, l2)


def test_vmeasure_perfect_and_degenerate():
    t = np.array([0, 0, 1, 1, 2, 2])
    assert v_measure(t, t)["v"] == pytest.approx(1.0)
    # all-in-one clustering: complete (c=1) but not homogeneous
    m = v_measure(t, np.zeros(6, int))
    assert m["completeness"] == pytest.approx(1.0)
    assert m["homogeneity"] == pytest.approx(0.0, abs=1e-9)
    # permuting labels must not change the score
    perm = np.array([2, 2, 0, 0, 1, 1])
    assert v_measure(t, perm)["v"] == pytest.approx(1.0)


def test_vmeasure_known_value():
    """Cross-check against the definitional formula on a small table."""
    t = np.array([0, 0, 0, 1, 1, 1])
    p = np.array([0, 0, 1, 1, 2, 2])
    m = v_measure(t, p)
    # manual: H(C)=ln2, H(C|K): clusters {00},{01},{11} ->
    #   p(k)= 1/3 each; H(C|K)= 1/3*0 + 1/3*ln2 + 1/3*0 = ln2/3
    h = 1 - (np.log(2) / 3) / np.log(2)
    assert m["homogeneity"] == pytest.approx(h)


def test_affinity_recovers_well_separated_clusters():
    rs = np.random.RandomState(0)
    n_per, k = 40, 4
    labels_true = np.repeat(np.arange(k), n_per)
    n = n_per * k
    src, dst, w = [], [], []
    for i in range(n):
        for j in range(i + 1, n):
            same = labels_true[i] == labels_true[j]
            if same and rs.rand() < 0.3:
                src.append(i); dst.append(j); w.append(0.9 + 0.1 * rs.rand())
            elif not same and rs.rand() < 0.02:
                src.append(i); dst.append(j); w.append(0.1 * rs.rand())
    g = Graph.from_candidates(n, np.array(src), np.array(dst),
                              np.array(w, np.float32),
                              np.ones(len(src), bool))
    pred = affinity_clustering(g, target_clusters=k, min_similarity=0.5)
    assert v_measure(labels_true, pred)["v"] > 0.95


def test_single_linkage_sweep_theorem_a3():
    """Components at threshold r separate pairs with sim >= r (Thm A.3)."""
    rs = np.random.RandomState(1)
    n = 60
    pts = np.concatenate([rs.randn(n // 2, 2) * 0.1,
                          rs.randn(n // 2, 2) * 0.1 + 5.0])
    sims = -np.linalg.norm(pts[:, None] - pts[None], axis=-1)  # neg distance
    sims = np.exp(sims)                      # similarity in (0, 1]
    iu = np.triu_indices(n, 1)
    g = Graph.from_candidates(n, iu[0], iu[1],
                              sims[iu].astype(np.float32),
                              np.ones(iu[0].size, bool))
    labels, r = single_linkage_from_spanners(g.threshold(0.05), 2,
                                             r_min=0.05, r_max=1.0)
    truth = np.repeat([0, 1], n // 2)
    assert v_measure(truth, labels)["v"] == pytest.approx(1.0)


def _csr_recall(g, queries, truths, hops, k_cap):
    """neighbor_recall spelled out over the whole-graph CSR."""
    indptr, nbrs, _ = g.to_csr()
    adj = lambda v: nbrs[indptr[v]:indptr[v + 1]]
    ratios = []
    for q, truth in zip(queries, truths):
        found = set(adj(q).tolist())
        if hops == 2:
            found |= {int(u) for z in adj(q) for u in adj(z)}
        inter = len(found & set(truth.tolist()))
        ratios.append(1.0 if k_cap is not None and inter >= k_cap
                      else inter / truth.size)
    return float(np.mean(ratios))


@pytest.mark.parametrize("hops,k_cap", [(1, None), (2, None), (2, 5)])
def test_neighbor_recall_on_graph_and_slabs_matches_csr(hops, k_cap):
    from repro.graph import neighbor_recall
    rs = np.random.RandomState(hops * 10 + (k_cap or 0))
    n, k = 80, 2
    nbr = rs.randint(-1, n, (n, k))
    nbr[nbr == np.arange(n)[:, None]] = -1          # slabs hold no self-loops
    w = np.where(nbr >= 0, rs.rand(n, k), -np.inf).astype(np.float32)
    g = Graph.from_degree_slabs(n, nbr, w)
    queries = rs.choice(n, 12, replace=False)
    truths = [rs.choice(n, 6, replace=False) for _ in queries]
    want = _csr_recall(g, queries, truths, hops, k_cap)
    assert 0.0 < want < 1.0
    assert neighbor_recall(g, queries, truths, hops=hops,
                           k_cap=k_cap) == pytest.approx(want)


def test_two_hop_sets():
    # path graph 0-1-2-3
    g = Graph.from_candidates(4, np.array([0, 1, 2]), np.array([1, 2, 3]),
                              np.ones(3, np.float32), np.ones(3, bool))
    th = g.two_hop_sets(np.array([0]))[0]
    assert set(th.tolist()) == {1, 2}
