"""Downstream clustering suite: host parity, the two label bugfixes, and
the zero-gather mesh clustering path.

Host half (runs in-process):
  * jax-vs-numpy connected-components parity on adversarial graphs — long
    chains (pointer-jumping depth), stars, forests, singleton / empty-edge
    cases,
  * the convergence contract: hitting ``max_iters`` RAISES instead of
    returning a silent non-partition (regression — the pre-fix code
    returned unconverged labels), and ``return_converged=True`` surfaces
    the flag without a host sync,
  * the int32 label guard: an id range past int32 without jax x64 raises
    instead of silently wrapping (the per-chunk-int32/host-int64 policy),
  * ``_contract_edges`` grouping: regression for the int64 composite-key
    wraparound that aliased distinct cluster pairs at tera-scale ids, plus
    randomized parity against a brute-force dict group-by,
  * affinity determinism/edge cases: equal-weight ties, empty edge lists.

Mesh half (``dist``-marked, forced-device subprocesses at p=1/2/4):
  * ``builder.cluster("components")`` labels are IDENTICAL to the host
    union-find on the finalized graph, at every shard count,
  * ``builder.cluster("affinity")`` labels are IDENTICAL to the host
    ``affinity_clustering`` on the finalized graph,
  * the tentpole invariant: ``transfer_stats['edge_fetches']`` and
    ``['bytes']`` stay ZERO through any number of clusterings — labels
    are produced without a single global edge fetch; only the (n,) label
    vector crosses (``cluster_label_*``), and the label rounds' wire
    traffic shows up in ``all_to_all_bytes`` (cross-shard only: 0 at
    p=1, > 0 at p>1),
  * ServeSession ``submit_cluster`` serves labels between rounds with the
    same zero-fetch contract.
"""

import numpy as np
import pytest

from repro.graph.affinity import _contract_edges, affinity_clustering
from repro.graph.components import (connected_components_jax,
                                    connected_components_np)
from repro.core.spanner import Graph
from repro.testing import run_forced_devices as _run_sub

pytestmark = pytest.mark.cluster


def _canon(labels):
    """Partition-canonical relabeling (first-occurrence order)."""
    _, inv = np.unique(np.asarray(labels), return_inverse=True)
    return inv


# --------------------------------------------------------------------------- #
# connected components: host parity + the two fixed contracts
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("n,edges", [
    # long chain: worst-case label-propagation distance
    (3000, [(i, i + 1) for i in range(2999)]),
    # star: depth 1, breadth n
    (500, [(0, i) for i in range(1, 500)]),
    # two chains + singletons (multiple components, isolated nodes)
    (120, [(i, i + 1) for i in range(49)]
          + [(60 + i, 61 + i) for i in range(49)]),
    # empty edge list: every node its own component
    (17, []),
])
def test_cc_jax_matches_np_adversarial(n, edges):
    src = np.array([e[0] for e in edges], np.int64)
    dst = np.array([e[1] for e in edges], np.int64)
    ref = connected_components_np(n, src, dst)
    lab = np.asarray(connected_components_jax(n, src, dst))
    # both label a component by its min gid — exact equality, not just
    # partition equivalence
    assert np.array_equal(lab, ref)


def test_cc_jax_unconverged_raises():
    """Regression: pre-fix code returned silently-unconverged labels."""
    n = 4096
    src, dst = np.arange(n - 1), np.arange(1, n)
    with pytest.raises(RuntimeError, match="max_iters"):
        connected_components_jax(n, src, dst, max_iters=1)
    lab, conv = connected_components_jax(n, src, dst, max_iters=1,
                                         return_converged=True)
    assert not bool(conv)
    assert np.unique(np.asarray(lab)).size > 1     # honestly partial
    lab, conv = connected_components_jax(n, src, dst,
                                         return_converged=True)
    assert bool(conv)
    assert np.unique(np.asarray(lab)).size == 1


def test_cc_jax_int32_guard():
    """Regression: pre-fix code allocated int32 labels for any n — ids past
    2^31 would silently wrap (numpy reference is int64)."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: the int64 path is legal here")
    with pytest.raises(OverflowError, match="int32"):
        connected_components_jax(2**31 + 5, np.array([0]), np.array([1]))


# --------------------------------------------------------------------------- #
# _contract_edges: the composite-key collision + randomized parity
# --------------------------------------------------------------------------- #


def test_contract_edges_int64_key_collision():
    """Regression: ``lo * (hi.max()+1) + hi`` wraps int64 — with
    hi.max()+1 = 2^33, the pairs (a, h) and (a + 2^31, h) differed by
    exactly 2^31 * 2^33 = 2^64 ≡ 0, so the pre-fix grouping merged two
    DISTINCT cluster pairs and averaged their weights together."""
    a, h = 5, 2**33 - 1
    cu = np.array([a, a + 2**31], np.int64)
    cv = np.array([h, h], np.int64)
    w = np.array([1.0, 3.0], np.float32)
    lo, hi, mw = _contract_edges(cu, cv, w)
    assert lo.size == 2, "distinct cluster pairs aliased by key overflow"
    got = {(int(l), int(hh)): float(m) for l, hh, m in zip(lo, hi, mw)}
    assert got == {(a, h): 1.0, (a + 2**31, h): 3.0}


def test_contract_edges_matches_dict_groupby():
    rng = np.random.default_rng(0)
    cu = rng.integers(0, 40, 500)
    cv = rng.integers(0, 40, 500)
    w = rng.normal(size=500).astype(np.float32)
    lo, hi, mw = _contract_edges(cu, cv, w)
    ref = {}
    for u, v, ww in zip(cu, cv, w):
        if u == v:
            continue
        ref.setdefault((min(u, v), max(u, v)), []).append(ww)
    assert {(int(a), int(b)) for a, b in zip(lo, hi)} == set(ref)
    for a, b, m in zip(lo, hi, mw):
        assert m == pytest.approx(np.mean(ref[(a, b)]), rel=1e-5)
    # output sorted by (lo, hi): the grouping key, now explicit
    assert np.array_equal(np.lexsort((hi, lo)), np.arange(lo.size))


# --------------------------------------------------------------------------- #
# affinity: adversarial host cases
# --------------------------------------------------------------------------- #


def test_affinity_equal_weight_ties_deterministic():
    """All-equal weights: every edge ties.  The partition must still be
    valid (chains collapse) and two runs must agree exactly."""
    n = 64
    src = np.arange(n - 1, dtype=np.int64)
    dst = np.arange(1, n, dtype=np.int64)
    g = Graph(n=n, src=src, dst=dst, w=np.ones(n - 1, np.float32))
    lab1 = affinity_clustering(g, target_clusters=1)
    lab2 = affinity_clustering(g, target_clusters=1)
    assert np.array_equal(lab1, lab2)
    assert np.unique(lab1).size == 1


def test_affinity_empty_and_singletons():
    g = Graph(n=9, src=np.array([], np.int64), dst=np.array([], np.int64),
              w=np.array([], np.float32))
    lab = affinity_clustering(g, target_clusters=1)
    assert np.array_equal(lab, np.arange(9))       # nothing to merge
    # two tight pairs + isolated nodes; min_similarity cuts the weak link
    g2 = Graph(n=6, src=np.array([0, 2, 1], np.int64),
               dst=np.array([1, 3, 2], np.int64),
               w=np.array([0.9, 0.8, 0.1], np.float32))
    lab2 = affinity_clustering(g2, target_clusters=1, min_similarity=0.5)
    assert lab2[0] == lab2[1] and lab2[2] == lab2[3]
    assert lab2[0] != lab2[2]
    assert np.unique(lab2).size == 4               # 2 pairs + 2 singletons


def test_affinity_target_clusters_stops_merging():
    """Two mutual-best pairs bridged weakly: round 1 lands exactly on two
    clusters, so target_clusters=2 must stop there (Boruvka merges every
    live cluster per round, so only round boundaries are observable —
    this construct puts the target ON one)."""
    g = Graph(n=4, src=np.array([0, 2, 1], np.int64),
              dst=np.array([1, 3, 2], np.int64),
              w=np.array([0.9, 0.9, 0.1], np.float32))
    lab = affinity_clustering(g, target_clusters=2)
    assert np.unique(lab).size == 2
    assert lab[0] == lab[1] and lab[2] == lab[3] and lab[0] != lab[2]
    # without the target the bridge goes too
    assert np.unique(affinity_clustering(g, target_clusters=1)).size == 1


# --------------------------------------------------------------------------- #
# the zero-gather mesh path (forced-device subprocesses)
# --------------------------------------------------------------------------- #

# NB: indented to match the test bodies exactly — the concatenation is
# dedented as ONE block (see tests/test_mesh_parity.py).
_COMMON = """
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import GraphBuilder, HashFamilyConfig, StarsConfig
        from repro.data import mnist_like_points
        from repro.graph import accumulator as acc_lib
        from repro.graph.affinity import affinity_clustering
        from repro.graph.components import connected_components_np
        from repro.graph.metrics import v_measure
"""


@pytest.mark.dist
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_mesh_cluster_zero_gather_parity(devices):
    """The tentpole: labels at p=1/2/4 with zero edge fetches first.

    components == host union-find exactly; affinity labels == the host
    path's (both average original edge weights every round and break ties
    by the smallest mate id); transfer_stats prove nothing O(n*k) left the
    device before the labels did.
    """
    res = _run_sub(_COMMON + f"""
        feats, y = mnist_like_points(n=402, d=24, classes=6, spread=0.12,
                                     seed=0)
        cfg = StarsConfig(mode="sorting", scoring="stars",
                          family=HashFamilyConfig("simhash", m=16),
                          measure="cosine", r=6, window=64, leaders=8,
                          degree_cap=16, seed=7)
        mesh = jax.make_mesh(({devices},), ("data",))
        b = GraphBuilder(feats.dense, cfg, mesh=mesh)
        b.add_reps(6)
        acc_lib.reset_transfer_stats()
        lab_cc, info_cc = b.cluster("components", return_info=True)
        lab_af, info_af = b.cluster("affinity", target_clusters=6,
                                    return_info=True)
        ts = dict(acc_lib.transfer_stats)
        g = b.finalize()                       # the ONE edge fetch, AFTER
        host_cc = connected_components_np(g.n, g.src, g.dst)
        host_af = affinity_clustering(g, target_clusters=6)
        out = {{
            "edge_fetches_before_labels": ts["edge_fetches"],
            "edge_bytes_before_labels": ts["bytes"],
            "a2a_bytes": ts["all_to_all_bytes"],
            "a2a_calls": ts["all_to_all_calls"],
            "label_fetches": ts["cluster_label_fetches"],
            "label_bytes": ts["cluster_label_bytes"],
            "cc_exact": bool(np.array_equal(lab_cc, host_cc)),
            "cc_rounds": info_cc["rounds"],
            "cc_converged": info_cc["converged"],
            "af_exact": bool(np.array_equal(lab_af, host_af)),
            "af_rounds": info_af["rounds"],
            "v_mesh_vs_host": v_measure(host_af, lab_af)["v"],
            "v_host_truth": v_measure(y, host_af)["v"],
            "v_mesh_truth": v_measure(y, lab_af)["v"],
        }}
        print(json.dumps(out))
    """, devices)
    # ZERO global edge fetches before cluster labels — the tentpole
    assert res["edge_fetches_before_labels"] == 0
    assert res["edge_bytes_before_labels"] == 0
    # the only device->host payload: two (n,) int32 label vectors
    assert res["label_fetches"] == 2
    assert res["label_bytes"] == 2 * 402 * 4
    # label rounds ride the metered exchange idiom: cross-shard bytes are
    # exactly 0 on one shard and real traffic beyond
    if devices == 1:
        assert res["a2a_bytes"] == 0
    else:
        assert res["a2a_bytes"] > 0
    assert res["a2a_calls"] > 0
    assert res["cc_exact"], res
    assert res["cc_converged"]
    # Affinity: the same linkage and tie-break as the host path, so the
    # same merges, round for round
    assert res["af_exact"], res
    assert res["v_mesh_vs_host"] >= 0.6, res
    assert res["v_mesh_truth"] >= res["v_host_truth"] - 0.15, res


@pytest.mark.dist
def test_mesh_cluster_components_identical_across_shardings():
    """Min-gid component labels are integer-exact, so every shard count
    must produce the SAME labels bit-for-bit."""
    outs = []
    for devices in (1, 2, 4):
        res = _run_sub(_COMMON + f"""
        feats, _ = mnist_like_points(n=302, d=16, classes=5, spread=0.2,
                                     seed=3)
        cfg = StarsConfig(mode="lsh", scoring="stars",
                          family=HashFamilyConfig("simhash", m=8),
                          measure="cosine", r=4, window=64, leaders=8,
                          degree_cap=12, seed=11)
        mesh = jax.make_mesh(({devices},), ("data",))
        b = GraphBuilder(feats.dense, cfg, mesh=mesh)
        b.add_reps(4)
        lab = b.cluster("components")
        print(json.dumps({{"labels": np.asarray(lab).tolist()}}))
        """, devices)
        outs.append(res["labels"])
    assert outs[0] == outs[1] == outs[2]


def test_single_device_cluster_matches_host():
    """builder.cluster on the default single-device backend (trivial
    1-device mesh) — same contract as the mesh path, in-process."""
    from repro.core import GraphBuilder, HashFamilyConfig, StarsConfig
    from repro.data import mnist_like_points
    from repro.graph import accumulator as acc_lib
    from repro.graph.metrics import v_measure

    feats, y = mnist_like_points(n=240, d=16, classes=4, spread=0.12,
                                 seed=5)
    cfg = StarsConfig(mode="sorting", scoring="stars",
                      family=HashFamilyConfig("simhash", m=16),
                      measure="cosine", r=5, window=48, leaders=8,
                      degree_cap=12, seed=2)
    b = GraphBuilder(feats, cfg)
    b.add_reps(5)
    acc_lib.reset_transfer_stats()
    lab_cc = b.cluster("components")
    lab_af = b.cluster("affinity", target_clusters=4)
    assert acc_lib.transfer_stats["edge_fetches"] == 0
    assert acc_lib.transfer_stats["bytes"] == 0
    assert acc_lib.transfer_stats["cluster_label_fetches"] == 2
    g = b.finalize()
    assert np.array_equal(lab_cc, connected_components_np(g.n, g.src, g.dst))
    host_af = affinity_clustering(g, target_clusters=4)
    assert v_measure(host_af, lab_af)["v"] >= 0.6
    with pytest.raises(ValueError, match="unknown clustering method"):
        b.cluster("kmeans")


@pytest.mark.serve
def test_serve_session_cluster_requests():
    """submit_cluster serves labels between rounds, zero edge fetches."""
    from repro.core import GraphBuilder, HashFamilyConfig, StarsConfig
    from repro.data import mnist_like_points
    from repro.graph import accumulator as acc_lib
    from repro.service import ServeSession

    feats, _ = mnist_like_points(n=160, d=16, classes=4, spread=0.15,
                                 seed=9)
    cfg = StarsConfig(mode="sorting", scoring="stars",
                      family=HashFamilyConfig("simhash", m=8),
                      measure="cosine", r=4, window=32, leaders=6,
                      degree_cap=10, seed=4)
    b = GraphBuilder(feats.dense[:140], cfg)
    b.add_reps(4)
    session = ServeSession(b)
    t_ext = session.submit_extend(feats.dense[140:])
    t_cl = session.submit_cluster("components")
    acc_lib.reset_transfer_stats()
    session.run_until_idle()
    assert t_ext.done and t_cl.done
    # the clustering observed the queued insert (FIFO: extend first)
    assert t_cl.result["labels"].shape == (160,)
    assert t_cl.result["info"]["converged"]
    assert acc_lib.transfer_stats["edge_fetches"] == 0
    assert acc_lib.transfer_stats["bytes"] == 0
    stats = session.stats
    assert stats["clusterings_served"] == 1
    assert stats["cluster_label_bytes"] == 160 * 4
    # served-between-rounds labels == a direct cluster() on the same state
    assert np.array_equal(t_cl.result["labels"], b.cluster("components"))
