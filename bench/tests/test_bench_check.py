"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a small size: a sound build passes, and each fault that a
one-chip build can have is caught.  The control (the reference weighed at
``Precision.HIGH``) fails it too, at the cell's width and at MNIST's 784,
where only ``high_share`` tells it from float32."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference

SMALL = dict(n=2048, window=50, leaders=5, degree_cap=20, m=12)


def _cell(**over):
    cell = harness.resolve("random1b-build")
    return dataclasses.replace(
        cell, config=dict(cell.config, **SMALL, **over),
        traffic=dict(cell.traffic, check_rows=256))


def _run(cell, seed=2**33 + 5):
    return harness.run_cell(cell, seed, 0.2, False, t0=time.perf_counter(),
                            devices=jax.devices()[:1])


def _state_unchanged(monkeypatch):
    from repro.graph import accumulator
    monkeypatch.setattr(accumulator, "accumulate", lambda state, *a: state)


def _half_the_windows(monkeypatch):
    from repro.kernels import ops
    score = ops.window_score

    def half(*args, **kw):
        sims, emit, comparisons, emitted = score(*args, **kw)
        keep = jnp.arange(emit.shape[0]) % 2 == 0
        return sims, emit & keep[:, None, None], comparisons, emitted
    monkeypatch.setattr(ops, "window_score", half)


def _answer_altered(monkeypatch):
    from repro.kernels import ops
    score = ops.window_score

    def altered(*args, **kw):
        sims, emit, comparisons, emitted = score(*args, **kw)
        return sims.at[:, 0, :].add(1e-4), emit, comparisons, emitted
    monkeypatch.setattr(ops, "window_score", altered)


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_sound_build_is_correct(seed):
    res = _run(_cell(d=32), seed)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_windows,
                                   _answer_altered])
def test_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    res = _run(_cell(d=32))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("d", [100, 784])
def test_control_is_not_correct(d):
    cell = _cell(d=d)
    cfg, limits = cell.config, cell.config["check"]
    k = cfg["degree_cap"]
    failed = []
    for seed in range(3):
        x = cell.generator.make(cfg, harness.data_seed(seed))
        rows = np.arange(0, cfg["n"], 8)
        cands, ref_w, high_w = reference.reference_rows(
            x, cfg, cfg["seed"], 4, rows)
        nbr, w = reference.control_rows(cands, high_w, k)
        got = reference.compare(nbr, w, cands, ref_w, high_w, k,
                                tie_tol=2 * limits["weight_gap"])
        failed.append(any(got[name] > limits[name] for name in limits))
    assert all(failed)
