"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to a cell is found by name from ``BENCHMARK.json``:
its configuration (``configs[].file``) and the corpus generator it names
(``bench/generators/<generator>.py``, ``make(config, seed)``), its traffic
mix (``bench/traffic/<traffic>.json``) and the driver that mix names
(``bench/drivers/<driver>.py``, a ``Session`` that sets up, makes one call
of the window, reports its end-to-end metrics and checks what the window
produced), and the readers of its per-layer metrics
(``bench/metrics/<metric>.py``, each with ``read(run)``).  A cell reports
every end-to-end metric, and the per-layer metrics that move one of them.

The window repeats the session's call until it has lasted ``--seconds``
and closes at that call's end.  ``--trace 1`` runs the same window under
the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.trace import WINDOW_SPAN

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".bench_jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    generator: ModuleType
    driver: ModuleType
    readers: Dict[str, Callable]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str) -> ModuleType:
    """``root/bench/<kind>/<name>.py``, loaded by its path."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = _json(os.path.join(root, conf["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 cell["traffic"] + ".json"))
    e2e = spec["end_to_end"]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if m["moves"] in moved]
    return Cell(workload, cell["chips"], config, traffic, e2e, layer,
                load_module(root, "generators", config["generator"]),
                load_module(root, "drivers", traffic["driver"]),
                {m["name"]: load_module(root, "metrics", m["name"]).read
                 for m in layer})


def data_seed(seed: int) -> int:
    """The data seed, 31 bits, from any whole number."""
    word = np.random.SeedSequence([int(seed < 0), abs(seed)]).generate_state(1)
    return int(word[0] & 0x7FFFFFFF)


class Clock:
    """Compile time (lowering plus backend compile) from JAX's monitoring
    events, so compiles inside the window show.  From ``chip_smoke.py``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.compile_s, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.compile_s += duration
            self.compiles += event == self.EVENTS[1]


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets: the cell, the device kind, the
    session's counts of the window's work, and the window's trace.  On
    several chips the trace reads one device, the busiest in the window
    (``bench/trace.py``): its times are one chip's, so a reader that sets
    them against work counts that chip's share of the cell's work."""
    cell: Cell
    device_kind: str
    counts: Dict[str, int]
    trace: object
    log: Callable[[str], None] = log

    def per_rep_ms(self, seconds: float) -> Optional[float]:
        """``seconds`` of the window per repetition, in ms, or None where
        the trace holds none."""
        return 1e3 * seconds / self.counts["reps"] if seconds > 0 else None


def memory_peak(devices, log: Callable[[str], None] = log) -> int:
    """The fullest chip's ``peak_bytes_in_use`` (-1 where none reports
    one), logging every chip's."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    log(f"memory_peak_bytes by chip: {peaks}")
    return max(peaks)


def log_trace(tr) -> None:
    """The reduction's view of the window: each device's busy time, and
    the paced device's stage split and labelled idle gaps."""
    busy = tr.busy_s_by_device
    log(f"trace: busy_s={tr.busy_s} window_s={tr.window_s} "
        f"ops={len(tr.ops)} host_spans={len(tr.spans)} paced={tr.device} "
        f"busy_s_by_device={json.dumps(busy)}"
        + (f" busy_max_over_min={max(busy.values()) / min(busy.values())}"
           if busy and min(busy.values()) > 0 else ""))
    split = tr.stages()
    log("stages: " + " ".join(f"{k or 'unscoped'}={v:.9f}"
                              for k, v in split.items())
        + f" sum={sum(split.values()):.9f} busy_s={tr.busy_s:.9f}"
        + (f" unscoped_share={100 * split[''] / tr.busy_s:.6f}%"
           if tr.busy_s else ""))
    log(f"idle gaps by innermost span: {tr.idle_gaps(6)}")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, devices) -> dict:
    """One run on ``devices``, the cell's chips; returns the result line's
    object."""
    import jax

    clock = Clock()
    session = cell.driver.Session(cell.config, cell.traffic, data_seed(seed),
                                  cell.generator.make, log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    compiles0 = clock.compiles
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            session.call()
            if time.perf_counter() - t_start >= seconds:
                break
    window_s = time.perf_counter() - t_start
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_start - t0
    log(f"setup_s={setup_s:.4f} compile_s={clock.compile_s:.4f} "
        f"window_s={window_s:.4f} counts={json.dumps(session.counts())} "
        f"compiles_in_window={clock.compiles - compiles0}")
    values = dict(session.end_to_end(window_s), setup_s=setup_s)
    peak = memory_peak(devices)

    result = {"correct": False, "attempted": session.attempted,
              "failed": session.failed}
    if trace:
        from bench import trace as trace_lib
        tr = trace_lib.Trace(trace_lib.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log_trace(tr)
        run = Run(cell, devices[0].device_kind, session.counts(), tr)
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev_extra = {"busy_s": tr.busy_s, "window_s": tr.window_s}
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        dev_extra = {}

    # the check, once the window is closed and the peak read
    t_check = time.perf_counter()
    checks = session.check()
    del session
    log(f"check_s={time.perf_counter() - t_check:.4f}")
    result["correct"] = result["attempted"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    result["metrics"] = metrics
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": peak, **dev_extra}
    result["checks"] = checks
    return result


def main(t0: float) -> int:
    import argparse
    parser = argparse.ArgumentParser(description="Run one benchmark cell.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    cell = resolve(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} TPU chip(s), found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={devices[0].device_kind} "
        f"x{len(devices)} jax={jax.__version__}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t0=t0, devices=devices[:cell.chips])
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
