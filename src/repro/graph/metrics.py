"""Evaluation metrics from the paper's empirical study (§5).

  * ``v_measure``             — VMeasure [36]: harmonic mean of homogeneity
                                and completeness (Fig. 4).
  * ``neighbor_recall``       — fraction of (approximate) k-nearest
                                neighbours found in 1 or 2 hops (Fig. 2,
                                SortingLSH variants).
  * ``two_hop_threshold_recall`` — fraction of ground-truth pairs with
                                similarity >= r reachable in <= 2 hops using
                                edges of weight >= r1 (Fig. 2, LSH variants;
                                r1 = 0.495 is the paper's "relaxed" setting).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.core.spanner import Graph


def v_measure(labels_true: np.ndarray, labels_pred: np.ndarray) -> dict:
    """VMeasure score [36] via the contingency table. Returns h, c, v."""
    labels_true = np.asarray(labels_true)
    labels_pred = np.asarray(labels_pred)
    n = labels_true.size
    _, t = np.unique(labels_true, return_inverse=True)
    _, p = np.unique(labels_pred, return_inverse=True)
    nt, npred = t.max() + 1, p.max() + 1
    cont = np.zeros((nt, npred))
    np.add.at(cont, (t, p), 1.0)
    pij = cont / n
    pi = pij.sum(1)
    pj = pij.sum(0)

    def _ent(px):
        nz = px[px > 0]
        return -np.sum(nz * np.log(nz))

    h_c = _ent(pi)          # H(C)
    h_k = _ent(pj)          # H(K)
    nz = pij > 0
    h_c_given_k = -np.sum(pij[nz] * (np.log(pij[nz])
                                     - np.log(np.broadcast_to(pj, pij.shape)[nz])))
    h_k_given_c = -np.sum(pij[nz] * (np.log(pij[nz])
                                     - np.log(np.broadcast_to(pi[:, None], pij.shape)[nz])))
    h = 1.0 if h_c == 0 else 1.0 - h_c_given_k / h_c
    c = 1.0 if h_k == 0 else 1.0 - h_k_given_c / h_k
    v = 0.0 if (h + c) == 0 else 2 * h * c / (h + c)
    return {"homogeneity": float(h), "completeness": float(c), "v": float(v)}


def _adjacency(graph: Graph, nodes: np.ndarray) -> dict:
    """Symmetric neighbour arrays of ``nodes`` from one pass over the edge
    list — no whole-graph CSR, so a few thousand queries on a 10^8-edge
    graph cost seconds."""
    member = np.zeros(graph.n, bool)
    member[nodes] = True
    fwd, bwd = member[graph.src], member[graph.dst]
    ends = np.concatenate([graph.src[fwd], graph.dst[bwd]])
    nbrs = np.concatenate([graph.dst[fwd], graph.src[bwd]])
    order = np.argsort(ends, kind="stable")
    ends, nbrs = ends[order], nbrs[order].astype(np.int64)
    lo = np.searchsorted(ends, nodes, side="left")
    hi = np.searchsorted(ends, nodes, side="right")
    return {int(v): nbrs[a:b] for v, a, b in zip(nodes, lo, hi)}


def neighbor_recall(graph: Graph, queries: np.ndarray,
                    true_neighbors: Sequence[np.ndarray], *,
                    hops: int = 2, k_cap: Optional[int] = None) -> float:
    """Mean over queries of |found within `hops`| / |true| (paper Fig. 2).

    ``true_neighbors[i]`` are the ground-truth (approximate) nearest
    neighbours of ``queries[i]``.  If ``k_cap`` is given and at least k_cap
    neighbours are found, the ratio is clamped to 1 (paper: "if we can find
    more than 100 approximate 100-nearest neighbors, we regard the ratio
    as 1").

    With the symmetric adjacency N, t lies within two hops of q iff
    t in N(q) or N(t) meets N(q), so only the queries' and the truths'
    neighbour lists are needed (``hops`` is 1 or 2).
    """
    queries = np.asarray(queries, np.int64)
    truths = [np.asarray(t, np.int64) for t in true_neighbors]
    wanted = [queries] + (truths if hops != 1 else [])
    adj = _adjacency(graph, np.unique(np.concatenate(wanted)))
    ratios = []
    for q, truth in zip(queries, truths):
        if truth.size == 0:
            continue
        one = adj[int(q)]
        found = np.unique(truth)
        hit = np.isin(found, one)
        if hops != 1 and one.size:
            hit |= np.array([np.isin(adj[int(t)], one).any() for t in found])
        inter = int(hit.sum())
        if k_cap is not None and inter >= k_cap:
            ratios.append(1.0)
        else:
            ratios.append(inter / truth.size)
    return float(np.mean(ratios)) if ratios else 0.0


def two_hop_threshold_recall(graph: Graph, queries: np.ndarray,
                             true_neighbors: Sequence[np.ndarray], *,
                             min_edge_w: float) -> float:
    """Fraction of ground-truth near neighbours (sim >= r2) reachable within
    two hops where *every edge* on the path has weight >= min_edge_w."""
    g = graph.threshold(min_edge_w)
    two_hop = g.two_hop_sets(np.asarray(queries))
    ratios = []
    for found, truth in zip(two_hop, true_neighbors):
        truth = np.asarray(truth)
        if truth.size == 0:
            continue
        inter = np.intersect1d(found, truth).size
        ratios.append(inter / truth.size)
    return float(np.mean(ratios)) if ratios else 0.0
