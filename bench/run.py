#!/usr/bin/env python3
"""Run one cell of the Stars build benchmark on the chips of this machine.

    python3 bench/run.py --workload random1b-build --seed 7 --seconds 20 \
        --trace 0

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  Without a TPU, or with
fewer chips than the cell asks for, it exits nonzero before any work.  The
last line of standard output is the result as one JSON object; the numbers
that decide ``correct`` are also the last lines of standard error.  JAX's
compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.bench_jax_cache/`` in the checkout.
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(T0))
