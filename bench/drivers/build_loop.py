"""Closed-loop build traffic: one caller adds repetitions to a resident
``GraphBuilder`` (``add_reps(reps_per_call)``, then ``block_until_ready``
on the slabs and a read of the build's counters), call after call.

Parameters, from the traffic file: ``reps_per_call``, ``warmup_calls``
(calls made in set-up, so that the round program is loaded before the
window), ``check_rows`` (slab rows the check compares).  End-to-end
metrics: ``build_rate``, n x the window's repetitions over the window's
wall time.  A repetition fails when it scores fewer window rows than the
grid has, or drops candidates.
"""

from __future__ import annotations

import json
from typing import Dict

import jax
import numpy as np

from bench import reference
from bench.roofline import n_windows

SAMPLE_SALT = 0x5A3E       # the check's row sample is its own stream


def _stars_config(config: dict):
    from repro.core import HashFamilyConfig, StarsConfig
    return StarsConfig(mode=config["mode"], scoring=config["scoring"],
                       family=HashFamilyConfig(config["family"],
                                               m=config["m"]),
                       measure=config["measure"], r=config["r"],
                       window=config["window"], leaders=config["leaders"],
                       degree_cap=config["degree_cap"], seed=config["seed"])


class Session:
    """Set-up at construction: the corpus from ``data_seed``, the builder
    and the warm-up calls."""

    def __init__(self, config: dict, traffic: dict, data_seed: int,
                 make_data, log):
        from repro.core import GraphBuilder
        self.config, self.traffic, self.log = config, traffic, log
        self.data_seed = data_seed
        self.x = jax.block_until_ready(make_data(config, data_seed))
        self.n = self.x.shape[0]
        self.nw = n_windows(self.n, config["window"])
        self.builder = GraphBuilder(self.x, _stars_config(config))
        self.per_call = traffic["reps_per_call"]
        self.stats: Dict[str, int] = {}
        self.failed = self.attempted = 0
        for _ in range(traffic["warmup_calls"]):
            self.call()
        self.warm_failed, self.failed, self.attempted = self.failed, 0, 0

    def call(self) -> None:
        annotate = jax.profiler.TraceAnnotation
        with annotate("bench.add_reps"):
            self.builder.add_reps(self.per_call)
        with annotate("bench.block"):
            jax.block_until_ready(self.builder.slab_state())
        with annotate("bench.counters"):
            stats = self.builder.stats
        scored = stats["scored_windows"] - self.stats.get("scored_windows", 0)
        dropped = stats.get("dropped", 0) - self.stats.get("dropped", 0)
        if scored != self.per_call * self.nw or dropped:
            self.failed += self.per_call
            self.log(f"failed call: scored_windows={scored} of "
                     f"{self.per_call * self.nw}, dropped={dropped}")
        self.stats = stats
        self.attempted += self.per_call

    def counts(self) -> Dict[str, int]:
        """What per-layer readers divide by: the window's repetitions."""
        return {"reps": self.attempted}

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        self.log(f"n={self.n} n_windows={self.nw} "
                 f"stats={json.dumps(self.stats)}")
        return {"build_rate": self.n * self.attempted / window_s}

    def check(self) -> Dict[str, dict]:
        """The sampled slab rows against the reference, once the window is
        closed: the rows come to the host and the builder goes first."""
        config = self.config
        rng = np.random.default_rng([self.data_seed, SAMPLE_SALT])
        rows = np.sort(rng.choice(
            self.n, min(self.n, self.traffic["check_rows"]), replace=False))
        slabs = self.builder.slab_state()
        nbr, w = jax.device_get((slabs.nbr[rows], slabs.w[rows]))
        reps = self.builder.reps_done
        del slabs
        self.builder = None
        limits = config["check"]
        k = min(config["degree_cap"], self.n - 1)
        cands, ref_w, high_w = reference.reference_rows(
            self.x, config, config["seed"], reps, rows)
        got = reference.compare(np.asarray(nbr), np.asarray(w), cands, ref_w,
                                high_w, k, tie_tol=2 * limits["weight_gap"])
        self.log(f"check: rows={len(rows)} reps={reps} {json.dumps(got)}")
        checks = {name: {"value": got[name], "limit": limits[name]}
                  for name in ("rows_wrong", "weight_gap", "high_share")}
        checks["failed_reps"] = {"value": self.failed + self.warm_failed,
                                 "limit": 0}
        return checks
