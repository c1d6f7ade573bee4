#!/usr/bin/env python3
"""Smoke run of the Stars graph build on TPU, at deployment size.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh build, on four chips

One chip: the Random1B corpus generator at its published widths (d=100,
100 modes, sigma=0.1; paper Appendix D.1) with n = 2^20 points, built as
SortingLSH + Stars with the paper's D.2 parameters (SimHash M=24, W=250,
s=25, degree cap k=250) through ``GraphBuilder``, then served through
``ServeSession``:

  build    ``add_reps`` one repetition at a time; every repetition must
           score every window row exactly once (``scored_windows``)
  serve    two-hop ``submit_query`` batches, one ``submit_extend`` of 1% of
           n (the extension rounds of ``GraphBuilder.extend``), a query of
           the new points, one ``submit_cluster("components")``
  finalize the one device->host edge fetch, compacted into a ``Graph``
  kernels  each Pallas kernel (``use_pallas=True``) against its jnp oracle
           (``use_pallas=False``) on the chip at this run's shapes:
           discrete outputs equal, similarities within SIM_TOL; plus how
           far the chip's default float32 matmul precision would move
           similarities and sketch bits
  recall   two-hop 10-NN recall of 1,024 sampled points against exact
           neighbours from a plain jnp matmul on the chip, at least
           RECALL_FLOOR

Four chips (``--chips 4``) runs only the mesh path: a p=4 mesh build over
``jax.devices()`` compared slab for slab with a single-device build on
``devices[0]`` at the one-chip n, then one mesh build at 4x n, reporting
recall, ``all_to_all_bytes`` and drops.

Everything runs in this one process.  Earlier lines print phase times (with
compile time apart), counters, recall and peak device bytes; the last line
is the JSON verdict ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits nonzero before doing anything.  Data is generated from
``--seed``.  JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

LOG2_N = 20                 # one chip's corpus: n = 2^20 points
REPS = 4                    # build repetitions
EXT_REPS = 2                # repetitions per absorbed extension
QUERIES = 1024              # recall sample
NN = 10                     # recall's k
SIM_TOL = 1e-5              # |kernel - oracle| on similarities (HIGHEST)
RECALL_FLOOR = 0.13         # two-hop 10-NN recall at n = 2^20 (PERF.md)
MESH_REPS = 2               # one coalesced repetition pair on the mesh


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class _Clock:
    """Wall time of each phase, with compile time (lowering to XLA plus the
    backend compile, once per program) apart.  Tracing stays in the run
    time: nested jits trace inside their caller, so its events overlap."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.compile_s += duration

    def phase(self, name: str):
        return _Phase(self, name)


class _Phase:
    def __init__(self, clock: _Clock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.compile_s
        return self

    def __exit__(self, exc_type, *_):
        wall = time.perf_counter() - self.t0
        comp = self.clock.compile_s - self.c0
        if exc_type is None:
            _log(f"phase {self.name}: wall_s={wall:.3f} "
                 f"compile_s={comp:.3f} run_s={wall - comp:.3f}")
        return False


def _deployment(seed: int):
    """SortingLSH + Stars at the paper's D.2 parameters, cosine."""
    from repro.core import HashFamilyConfig, StarsConfig
    return StarsConfig(mode="sorting", scoring="stars",
                       family=HashFamilyConfig("simhash", m=24),
                       measure="cosine", r=REPS, window=250, leaders=25,
                       degree_cap=250, seed=seed)


def _corpus(n: int, seed: int) -> jax.Array:
    """Random1B generator (Appendix D.1) at d=100, 100 modes, sigma=0.1."""
    from repro.data import gaussian_mixture_points
    feats, _ = gaussian_mixture_points(n, d=100, modes=100, std=0.1,
                                       seed=seed)
    return feats.dense


def _n_windows(cfg, n: int) -> int:
    from repro.core.windows import window_slot_count
    return window_slot_count(cfg.mode, n, cfg.window) // cfg.window


@functools.partial(jax.jit, static_argnums=2)
def _knn_block(xn: jax.Array, q: jax.Array, k: int) -> jax.Array:
    sims = jnp.dot(xn[q], xn.T, precision=jax.lax.Precision.HIGHEST)
    sims = sims.at[jnp.arange(q.shape[0]), q].set(-jnp.inf)
    return jax.lax.top_k(sims, k)[1]


def _exact_knn(x: jax.Array, queries: np.ndarray, k: int) -> np.ndarray:
    """Exact cosine k-NN of ``queries`` (self excluded): one float32 matmul
    per 128-query block on the chip, at HIGHEST precision."""
    xn = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return np.concatenate([
        np.asarray(_knn_block(xn, jnp.asarray(queries[i:i + 128]), k))
        for i in range(0, len(queries), 128)])


def _sample(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).choice(n, QUERIES, replace=False)


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _time_call(fn, *args, **kw):
    """(result, seconds) of one call after a warm-up call, so compile time
    stays out; ends on ``block_until_ready``."""
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def _delta(after: dict, before: dict, key: str) -> int:
    return int(after.get(key, 0)) - int(before.get(key, 0))


# --------------------------------------------------------------------------- #
# One chip
# --------------------------------------------------------------------------- #


def _build_and_serve(clock: _Clock, seed: int):
    from repro.core import GraphBuilder
    from repro.graph import accumulator as acc_lib
    from repro.service import ServeConfig, ServeSession

    n = 1 << LOG2_N
    n_ext = n // 100
    cfg = _deployment(seed)
    with clock.phase("data"):
        corpus = _corpus(n + n_ext, seed)
        base, ext = jax.block_until_ready((corpus[:n], corpus[n:]))
        del corpus
    _log(f"corpus: n={n} extension={n_ext} d={base.shape[1]}")

    builder = GraphBuilder(base, cfg)
    del base
    nw = _n_windows(cfg, n)
    for rep in range(REPS):
        before = builder.stats
        with clock.phase(f"build rep={rep}"):
            builder.add_reps(1)
            jax.block_until_ready(builder.slab_state())
        after = builder.stats
        scored = _delta(after, before, "scored_windows")
        _log(f"rep {rep}: comparisons={_delta(after, before, 'comparisons')}"
             f" emitted={_delta(after, before, 'emitted')} "
             f"scored_windows={scored} n_windows={nw}")
        _check(scored == nw, f"rep {rep} scored {scored} of {nw} windows")
    _check(int(builder.stats.get("dropped", 0)) == 0, "dropped candidates")

    session = ServeSession(builder, ServeConfig(reps_per_absorb=EXT_REPS,
                                                emit_deltas=False))
    before = builder.stats
    with clock.phase("serve"):
        tickets = [session.submit_query([0]),
                   session.submit_query([n // 2]),
                   session.submit_extend(ext),
                   session.submit_query([n + n_ext - 1]),    # a new point
                   session.submit_cluster("components")]
        served = session.run_until_idle()
    after = builder.stats
    _check(all(t is not None and t.done for t in tickets), "unserved request")
    scored = _delta(after, before, "scored_windows")
    nw_ext = _n_windows(cfg, n + n_ext)
    _log(f"extend: points={n_ext} reps={EXT_REPS} "
         f"comparisons={_delta(after, before, 'comparisons')} "
         f"scored_windows={scored} n_windows={nw_ext}")
    # a repetition scores at most n_windows rows, so the sum pins each one
    _check(scored == EXT_REPS * nw_ext, "extension rounds skipped windows")
    for t in (tickets[0], tickets[1], tickets[3]):
        _check(bool((t.result["counts"] > 0).all()), "empty query answer")
    labels = tickets[4].result["labels"]
    _log(f"serve: queries={served['queries_served']} "
         f"absorbed={served['points_absorbed']} "
         f"first_gid={tickets[2].result['first_gid']} "
         f"components={len(np.unique(labels))}")
    _check(tickets[2].result["first_gid"] == n
           and labels.shape == (n + n_ext,), "extension saw the wrong n")

    acc_lib.reset_transfer_stats()
    with clock.phase("finalize"):
        graph = builder.finalize()
    _check(acc_lib.transfer_stats["edge_fetches"] == 1, "edge fetches")
    _log(f"finalize: edges={graph.num_edges} "
         f"fetched_bytes={acc_lib.transfer_stats['bytes']} "
         f"stats={json.dumps({k: int(v) for k, v in graph.stats.items()})}")
    return builder, graph


def _kernels_vs_oracle(clock: _Clock, builder, seed: int) -> None:
    """Each Pallas kernel against its jnp oracle on the chip, at this run's
    shapes: one window grid over every point, and 2^16 slab rows."""
    from repro.core import windows as win_lib
    from repro.core.lsh import sketch
    from repro.kernels import ops
    from repro.similarity.measures import PointFeatures

    cfg = _deployment(seed)
    x = builder.feature_store.features.dense
    n = x.shape[0]
    nw = _n_windows(cfg, n)
    key = jax.random.key(seed + 1)
    with clock.phase("kernels"):
        perm = jax.random.permutation(key, n).astype(jnp.int32)
        gid = jnp.full((nw * cfg.window,), -1, jnp.int32).at[:n].set(perm)
        gid = gid.reshape(nw, cfg.window)
        win = win_lib.Windows(gid=gid, valid=gid >= 0,
                              bucket=jnp.zeros(gid.shape, jnp.uint32))
        lslot, lok = win_lib.sample_leaders(win, s=cfg.leaders,
                                            key=jax.random.fold_in(key, 1))
        lgid = jnp.take_along_axis(gid, lslot, axis=1)
        lead, memb = x[jnp.maximum(lgid, 0)], x[jnp.maximum(gid, 0)]
        keep = jax.random.uniform(jax.random.fold_in(key, 2), (nw,)) < 0.5
        args = (lead, memb, lslot, lgid, gid, lok, win.valid,
                jnp.zeros(lgid.shape, jnp.uint32), win.bucket, keep)
        # the build's mask, and the extension + refresh masks with a
        # similarity threshold; with r1 the emit mask depends on the floats,
        # so lanes within SIM_TOL of r1 are exempt from its equality
        for kw in ({}, {"new_from": n // 4, "refresh_below": n // 2,
                        "r1": 0.5}):
            got, kernel_s = _time_call(ops.window_score, *args,
                                       use_pallas=True, **kw)
            want, oracle_s = _time_call(ops.window_score, *args,
                                        use_pallas=False, **kw)
            sims, sims_ref = np.asarray(got[0]), np.asarray(want[0])
            _check(np.array_equal(np.isneginf(sims), np.isneginf(sims_ref)),
                   f"window_score {kw}: -inf pattern")
            fin = np.isfinite(sims_ref)
            err = float(np.max(np.abs(sims[fin] - sims_ref[fin])))
            _check(err <= SIM_TOL, f"window_score {kw}: sims off by {err}")
            clear = np.abs(sims_ref - kw.get("r1", -2.0)) > SIM_TOL
            _check(np.array_equal(np.asarray(got[1])[clear],
                                  np.asarray(want[1])[clear]),
                   f"window_score {kw}: emit")
            _check(np.array_equal(np.asarray(got[2]), np.asarray(want[2])),
                   f"window_score {kw}: comparisons")
            # per-window emitted counts: equal on every window without an
            # exempt lane; elsewhere off by at most its exempt lanes
            exempt = (~clear).sum(axis=(1, 2))
            gap = np.abs(np.asarray(got[3]).astype(np.int64)
                         - np.asarray(want[3]))
            _check(not gap[exempt == 0].any() and (gap <= exempt).all(),
                   f"window_score {kw}: emitted")
            if "r1" in kw:
                _log(f"  lanes within SIM_TOL of r1: {int(exempt.sum())} in "
                     f"{int((exempt > 0).sum())} windows, emitted off by "
                     f"{int(gap.sum())}")
            _log(f"kernel window_score {kw}: discrete outputs equal, "
                 f"max|dsim|={err:.3e}, comparisons="
                 f"{int(np.asarray(got[2]).sum())}, kernel_s={kernel_s:.4f}"
                 f" oracle_s={oracle_s:.4f}")
            if not kw:
                highest = sims_ref

        got, want = (np.asarray(ops.leader_score(lead, memb, lok, win.valid,
                                                 use_pallas=u))
                     for u in (True, False))
        _check(np.array_equal(np.isneginf(got), np.isneginf(want)),
               "leader_score: -inf pattern")
        fin = np.isfinite(want)
        err = float(np.max(np.abs(got[fin] - want[fin])))
        _check(err <= SIM_TOL, f"leader_score: sims off by {err}")
        _log(f"kernel leader_score: -inf pattern equal, max|dsim|={err:.3e}")

        state = builder.slab_state()
        rows = np.random.default_rng(seed).choice(n, min(n, 1 << 16),
                                                  replace=False)
        other = np.roll(rows, 1)          # another row's slab as the batch
        merge_in = (state.nbr[rows], state.w[rows],
                    state.nbr[other], state.w[other])
        got, kernel_s = _time_call(ops.topk_merge, *merge_in,
                                   use_pallas=True)
        want, oracle_s = _time_call(ops.topk_merge, *merge_in,
                                    use_pallas=False)
        _check(all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(got, want)), "topk_merge")
        _log(f"kernel topk_merge: {len(rows)} rows x k={state.capacity}: "
             f"nbr and w equal, kernel_s={kernel_s:.4f} "
             f"oracle_s={oracle_s:.4f}")

    # what HIGHEST buys over the chip's default float32 matmul precision
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    default = np.asarray(jnp.einsum("nsd,nwd->nsw", unit(lead), unit(memb)))
    fin = np.isfinite(highest)
    drift = float(np.max(np.abs(default[fin] - highest[fin])))
    feats = PointFeatures(dense=x)
    bits = sketch(feats, cfg.family, rep_seed=0)
    with jax.default_matmul_precision("highest"):
        bits_highest = sketch(feats, cfg.family, rep_seed=0)
    flipped = float(jnp.mean(bits != bits_highest))
    _log(f"precision: default vs HIGHEST matmul: max|dsim|={drift:.3e}, "
         f"sketch bits flipped={flipped:.3e}")


def _recall(clock: _Clock, graph, x: jax.Array, seed: int) -> float:
    """Two-hop 10-NN recall of QUERIES sampled points, beside the recall a
    graph of the same mean degree reaches by chance (two-hop sets of
    ~degree^2 random points).  Recall grows with R; the smoke runs few."""
    from repro.graph import neighbor_recall
    n = x.shape[0]
    queries = _sample(n, seed)
    with clock.phase("recall"):
        truth = _exact_knn(x, queries, NN)
        recall = neighbor_recall(graph, queries, list(truth), hops=2)
    degree = 2 * graph.num_edges / n
    _log(f"recall: two_hop_{NN}nn={recall:.4f} queries={QUERIES} "
         f"mean_degree={degree:.1f} chance={min(1.0, degree**2 / n):.4f}")
    return recall


def one_chip(seed: int) -> None:
    clock = _Clock()
    builder, graph = _build_and_serve(clock, seed)
    _kernels_vs_oracle(clock, builder, seed)
    recall = _recall(clock, graph, builder.feature_store.features.dense, seed)
    _check(recall >= RECALL_FLOOR, f"recall {recall} < {RECALL_FLOOR}")
    _log(f"peak_bytes_in_use={_peak_bytes(jax.devices()[0])} "
         f"compile_s_total={clock.compile_s:.3f}")


# --------------------------------------------------------------------------- #
# Four chips
# --------------------------------------------------------------------------- #


def _slab_recall(nbr: np.ndarray, queries: np.ndarray, truth) -> float:
    """Two-hop recall of the graph the (n, k) slab table ``nbr`` finalizes
    to, without compacting all of it: the edges that touch a query or a
    true neighbour are all that two-hop recall reads."""
    from repro.core.spanner import Graph
    from repro.graph import neighbor_recall
    n, k = nbr.shape
    member = np.zeros(n + 1, bool)       # member[-1]: empty entries (-1)
    member[queries] = True
    member[np.concatenate(truth)] = True
    flat = nbr.ravel()
    hit = np.flatnonzero((np.repeat(member[:n], k) | member[flat])
                         & (flat >= 0))
    ones = np.ones(hit.size)
    sub = Graph.from_candidates(n, hit // k, flat[hit], ones, ones)
    return neighbor_recall(sub, queries, truth, hops=2)


def four_chips(seed: int) -> None:
    from repro.core import GraphBuilder
    from repro.graph import accumulator as acc_lib

    clock = _Clock()
    devices = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("data",), devices=devices)
    cfg = _deployment(seed)
    n = 1 << LOG2_N
    with clock.phase("data"):
        x = jax.block_until_ready(_corpus(n, seed))

    with clock.phase("single-device build"):
        single = GraphBuilder(x, cfg).add_reps(MESH_REPS)
        state = single.slab_state()
        ref = jax.device_get((state.nbr, state.w))
    ref_stats = single.stats
    del single, state
    with clock.phase("mesh p=4 build"):
        meshed = GraphBuilder(x, cfg, mesh=mesh).add_reps(MESH_REPS)
        state = meshed.slab_state()
        got = jax.device_get((state.nbr, state.w))
    stats = meshed.stats
    del meshed, state
    equal = all(np.array_equal(a, b) for a, b in zip(got, ref))
    _log(f"mesh p=4 vs single device: slabs_equal={equal} "
         f"slab_entries={int((ref[0] >= 0).sum())} "
         f"comparisons={stats['comparisons']}/{ref_stats['comparisons']} "
         f"dropped={int(stats.get('dropped', 0))}")
    _check(equal, "mesh slabs differ from the single-device build")
    _check(stats["comparisons"] == ref_stats["comparisons"]
           and stats["scored_windows"] == ref_stats["scored_windows"]
           == MESH_REPS * _n_windows(cfg, n), "mesh counters")
    _check(int(stats.get("dropped", 0)) == 0, "mesh drops")
    del ref, got, x

    big_n = 4 * n
    with clock.phase("data 4x"):
        x = jax.block_until_ready(_corpus(big_n, seed + 1))
    acc_lib.reset_transfer_stats()
    with clock.phase("mesh p=4 build 4x"):
        meshed = GraphBuilder(x, cfg, mesh=mesh).add_reps(MESH_REPS)
        nbr = np.asarray(jax.device_get(meshed.slab_state().nbr))
    stats = meshed.stats
    wire = dict(acc_lib.transfer_stats)
    queries = _sample(big_n, seed)
    with clock.phase("recall 4x"):
        truth = _exact_knn(x, queries, NN)
        recall = _slab_recall(nbr, queries, list(truth))
    _log(f"mesh 4x: n={big_n} two_hop_{NN}nn={recall:.4f} "
         f"all_to_all_bytes={wire['all_to_all_bytes']} "
         f"all_to_all_calls={wire['all_to_all_calls']} "
         f"dropped={int(stats.get('dropped', 0))} "
         f"comparisons={stats['comparisons']}")
    _check(int(stats.get("dropped", 0)) == 0, "mesh 4x drops")
    _check(stats["scored_windows"] == MESH_REPS * _n_windows(cfg, big_n),
           "mesh 4x skipped windows")
    _log("peak_bytes_in_use="
         + ",".join(str(_peak_bytes(d)) for d in devices)
         + f" compile_s_total={clock.compile_s:.3f}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _log(f"device: {devices[0].device_kind} x{len(devices)} "
         f"jax={jax.__version__} compile_cache={cache} entries={warm}")
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
