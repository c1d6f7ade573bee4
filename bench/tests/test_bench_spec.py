"""BENCHMARK.json against the files it names, and the runner's refusal to
run without a chip."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, trace

ROOT = harness.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(workload):
    cell = harness.resolve(workload)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    assert callable(cell.generator.make) and hasattr(cell.driver, "Session")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "build_rate"}
    assert cell.per_layer and set(cell.readers) == {
        m["name"] for m in cell.per_layer}
    # a reader that finds nothing to read returns nothing
    empty = trace.Trace([trace.Event("/host:CPU", "python",
                                     trace.WINDOW_SPAN, 0.0, 1e9)])
    run = harness.Run(cell, "TPU v5 lite", {"reps": 1}, empty,
                      log=lambda _: None)
    assert all(read(run) is None for read in cell.readers.values())


def test_spec_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for conf in SPEC["configs"]:
        path = os.path.join(ROOT, conf["file"])
        assert conf["file"].startswith("bench/") and os.path.isfile(path)
        config = json.load(open(path))
        assert set(conf["reduced"]) == set(config["reduced"])
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "generators", config["generator"] + ".py"))
    for w in SPEC["workloads"]:
        traffic = json.load(open(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", traffic["driver"] + ".py"))
    for m in SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_run_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


class _Chip:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_the_fullest_chip_sets_the_memory_peak():
    """A cell is sized by its fullest chip; every chip's peak is logged."""
    lines = []
    chips = [_Chip({"peak_bytes_in_use": 7}), _Chip({"peak_bytes_in_use": 9}),
             _Chip(None), _Chip({"peak_bytes_in_use": 8})]
    assert harness.memory_peak(chips, lines.append) == 9
    assert lines == ["memory_peak_bytes by chip: [7, 9, -1, 8]"]
    assert harness.memory_peak([_Chip(None)], lines.append) == -1
