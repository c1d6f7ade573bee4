"""Closed-loop build traffic on a mesh: ``build_loop``'s caller, with the
``GraphBuilder``'s features and slabs row-sharded over the cell's chips
(the configuration's ``mesh``, on ``jax.devices()[:chips]``).

``add_reps(reps_per_call)`` with two repetitions a call runs one coalesced
repetition pair (``run_round_pair``: two sample sorts, one feature-fetch
pair, one edge-emit exchange).  Parameters, the end-to-end metric and the
check are ``build_loop``'s; the corpus is made row-sharded, each chip
drawing its own rows, so that no chip holds a whole copy of it.  A
repetition fails when it scores fewer window rows than the grid has, or
when the sort, the fetch or the emit drops anything.  Each call logs every
exchange's wire bytes and drops per repetition.
"""

from __future__ import annotations

import json

import jax

from bench.drivers import build_loop

EXCHANGES = ("sort", "fetch", "emit")


def _wire(stats: dict) -> dict:
    return {x: stats[f"all_to_all_bytes.{x}"] for x in EXCHANGES}


class Session(build_loop.Session):
    """Set-up at construction: the mesh, the sharded corpus from
    ``data_seed``, the builder and the warm-up calls."""

    def __init__(self, config: dict, traffic: dict, data_seed: int,
                 make_data, log):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core import GraphBuilder
        from repro.graph import accumulator as acc_lib
        self.config, self.traffic, self.log = config, traffic, log
        self.data_seed = data_seed
        self.transfer_stats = acc_lib.transfer_stats
        spec = config["mesh"]
        mesh = jax.make_mesh(tuple(spec["shape"]), tuple(spec["axes"]),
                             devices=jax.devices()[:config["chips"]])
        rows = NamedSharding(mesh, P(spec["axes"][0], None))
        # the one copy of the corpus, made in row blocks, and the builder's
        # table (the reference reads it too, once the window has closed)
        self.x = jax.block_until_ready(jax.jit(
            lambda seed: make_data(config, seed), out_shardings=rows)(
                data_seed))
        self.n = self.x.shape[0]
        self.nw = build_loop.n_windows(self.n, config["window"])
        self.builder = GraphBuilder(self.x, build_loop._stars_config(config),
                                    mesh=mesh)
        self.per_call = traffic["reps_per_call"]
        self.stats = {}
        self.failed = self.attempted = 0
        for _ in range(traffic["warmup_calls"]):
            self.call()
        self.warm_failed, self.failed, self.attempted = self.failed, 0, 0

    def call(self) -> None:
        """``build_loop``'s call, which fails a call on any drop (the
        program's ``dropped`` is the sum of the three exchanges'), and the
        exchanges' log line."""
        prev, sent = self.stats, _wire(self.transfer_stats)
        super().call()
        wire = _wire(self.transfer_stats)
        per_rep = {x: (wire[x] - sent[x]) / self.per_call for x in EXCHANGES}
        dropped = {x: self.stats.get(f"dropped.{x}", 0)
                   - prev.get(f"dropped.{x}", 0) for x in EXCHANGES}
        self.log(f"exchanges per repetition: wire_bytes={json.dumps(per_rep)}"
                 f" dropped={json.dumps(dropped)}")
