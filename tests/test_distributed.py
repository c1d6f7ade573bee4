"""Multi-device tests: run in subprocesses with 8 forced host devices so
the main test process keeps the real device count (the dry-run rule).
Edge-for-edge equivalence of the mesh graph build lives in
tests/test_mesh_parity.py; this module keeps the sorter, training and
legacy-wrapper coverage."""

import pytest

from repro.testing import run_forced_devices

pytestmark = pytest.mark.dist


def _run_sub(code: str) -> dict:
    return run_forced_devices(code, devices=8)


def test_distributed_sort_is_globally_sorted():
    res = _run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sorter import distributed_sort
        mesh = jax.make_mesh((8,), ("data",))
        n = 8 * 512
        rs = np.random.RandomState(0)
        keys = jnp.asarray(rs.randint(0, 2**32, n, dtype=np.uint32))
        payload = jnp.arange(n, dtype=jnp.int32)
        k, p, valid, dropped = distributed_sort(keys, payload, mesh)
        k = np.asarray(k); v = np.asarray(valid); p = np.asarray(p)
        kept = k[v]
        ok_sorted = bool(np.all(np.diff(kept.astype(np.int64)) >= 0))
        # payload follows its key
        orig = np.asarray(keys)
        ok_payload = bool(np.all(orig[p[v]] == kept))
        print(json.dumps({"sorted": ok_sorted, "payload": ok_payload,
                          "dropped": int(np.sum(np.asarray(dropped))),
                          "kept": int(v.sum()), "n": n}))
    """)
    assert res["sorted"] and res["payload"]
    assert res["dropped"] == 0
    assert res["kept"] == res["n"]


def test_distributed_argsort_replicates_global_permutation():
    """distributed_argsort (the replicated-permutation view kept for
    consumers that need the full (n,) order — the mesh build itself now
    consumes per-shard window blocks) returns exactly the host argsort,
    on every shard, with gid tiebreaks for equal keys."""
    res = _run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sorter import distributed_argsort
        mesh = jax.make_mesh((8,), ("data",))
        n = 8 * 256
        rs = np.random.RandomState(1)
        # few distinct values -> plenty of ties for the gid tiebreak
        keys = jnp.asarray(rs.randint(0, 64, n, dtype=np.uint32))
        gids = jnp.arange(n, dtype=jnp.int32)
        perm, dropped = distributed_argsort(keys, gids, mesh, n)
        expect = np.argsort(np.asarray(keys), kind="stable")
        print(json.dumps({
            "equal": bool((np.asarray(perm) == expect).all()),
            "dropped": int(np.sum(np.asarray(dropped))),
        }))
    """)
    assert res["equal"]
    assert res["dropped"] == 0


def test_distributed_stars_matches_single_device_recall():
    res = _run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import StarsConfig, HashFamilyConfig, build_graph
        from repro.distributed.stars_dist import build_graph_distributed
        from repro.data import mnist_like_points
        from repro.graph import neighbor_recall

        feats, _ = mnist_like_points(n=2048, d=32, classes=8, spread=0.2,
                                     seed=5)
        cfg = StarsConfig(mode="sorting", scoring="stars",
                          family=HashFamilyConfig("simhash", m=24),
                          measure="cosine", r=20, window=128, leaders=10,
                          degree_cap=50, seed=2)
        g1 = build_graph(feats, cfg)
        mesh = jax.make_mesh((8,), ("data",))
        g2 = build_graph_distributed(feats.dense, cfg, mesh)

        x = np.asarray(feats.dense)
        xn = x / np.linalg.norm(x, axis=1, keepdims=True)
        sims = xn @ xn.T
        np.fill_diagonal(sims, -np.inf)
        queries = np.arange(64)
        truth = [np.argsort(-sims[q])[:10] for q in queries]
        r1 = neighbor_recall(g1, queries, truth, hops=2, k_cap=10)
        r2 = neighbor_recall(g2, queries, truth, hops=2, k_cap=10)
        print(json.dumps({"single": r1, "dist": r2,
                          "comp1": g1.stats["comparisons"],
                          "comp2": g2.stats["comparisons"],
                          "dropped": g2.stats["dropped"]}))
    """)
    assert res["single"] > 0.8
    assert res["dist"] > 0.7 * res["single"]   # boundary effects tolerated
    assert res["dropped"] == 0


def test_sharded_train_step_matches_single_device():
    res = _run_sub("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import ModelConfig, init_params
        from repro.train import AdamWConfig, TrainState, make_train_step
        from repro.launch.sharding import plan_param_specs, batch_specs, named
        from repro.launch.mesh import make_host_mesh
        from repro.launch.specs import abstract_params
        from repro.data import token_stream_batch
        from repro.distributed import activation_sharding

        cfg = ModelConfig(name="t", kind="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=4, d_ff=128, vocab=256,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          remat=False)
        params, axes = init_params(cfg, jax.random.key(0))
        opt = AdamWConfig(lr=1e-3)
        state = TrainState.create(opt, params)
        batch = {"tokens": token_stream_batch(0, batch=8, seq_len=32,
                                              vocab=cfg.vocab)}
        step = make_train_step(cfg, opt)
        s_ref, m_ref = jax.jit(step)(state, batch)

        mesh = make_host_mesh(n_model=2)            # (4, 2) data x model
        shapes, _ = abstract_params(cfg)
        pspecs = plan_param_specs(cfg, axes, mesh, shapes)
        p_sh = named(mesh, pspecs)
        state_sh = TrainState(params=p_sh,
                              opt_state={"m": p_sh, "v": p_sh,
                                         "step": NamedSharding(mesh, P())},
                              error_state=None,
                              step=NamedSharding(mesh, P()))
        b_sh = named(mesh, batch_specs(cfg, batch, mesh))
        with mesh, activation_sharding(mesh):
            s_d, m_d = jax.jit(step, in_shardings=(state_sh, b_sh))(
                state, batch)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(s_ref.params),
                                jax.tree.leaves(s_d.params)))
        print(json.dumps({"loss_ref": float(m_ref["loss"]),
                          "loss_dist": float(m_d["loss"]),
                          "max_param_diff": d}))
    """)
    assert res["loss_ref"] == pytest.approx(res["loss_dist"], abs=1e-4)
    assert res["max_param_diff"] < 1e-3


def test_production_mesh_shapes():
    res = _run_sub("""
        import json, os
        # 8 forced devices cannot host 512; just validate the mesh builder
        # geometry logic via a tiny stand-in of the same code path.
        import jax
        from repro.launch import mesh as M
        m = jax.make_mesh((4, 2), ("data", "model"))
        print(json.dumps({"dp": M.dp_axes(m), "axes": list(m.axis_names)}))
    """)
    assert res["dp"] == ["data"]
    assert res["axes"] == ["data", "model"]
