"""Device peaks and the least time of the window scoring kernel.

``peaks.json`` holds each device's published peaks keyed by JAX's
``device_kind``; a device that is not there is an error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str, path: str = _PEAKS) -> Dict[str, float]:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def n_windows(n: int, window: int) -> int:
    """Window rows of one SortingLSH repetition: ceil(n / W) plus the row
    that the random shift of the first block spills into."""
    return -(-n // window) + 1


def window_score_least_s(nw: int, s: int, w: int, d: int,
                         peak: Dict[str, float],
                         chips: int = 1) -> Dict[str, float]:
    """The least time in which one chip can score its share of the leaders
    against their windows, whatever implements it.  Of the ``nw`` window
    rows a chip scores ``windows`` = ceil(nw / chips), every row on one
    chip.  The time is the larger of their multiply-adds (2 windows s W d
    FLOPs) at the bf16 peak and one read of their leader and member rows
    (windows (W + s) d float32 bytes) at the HBM bandwidth."""
    windows = -(-nw // chips)
    flops = 2.0 * windows * s * w * d
    nbytes = 4.0 * windows * (w + s) * d
    compute_s = flops / peak["bf16_flops_per_s"]
    memory_s = nbytes / peak["hbm_bytes_per_s"]
    return {"windows": windows, "flops": flops, "bytes": nbytes,
            "compute_s": compute_s, "memory_s": memory_s,
            "least_s": max(compute_s, memory_s),
            "bound": "compute" if compute_s >= memory_s else "memory"}
