"""Reduction of a profiler trace of the measured window to device times.

``load(path)`` flattens the JAX profiler's ``.xplane.pb`` into ``Event``s;
``Trace`` answers what the per-layer metric readers ask: the window (the
host span ``bench.window`` that the harness opens around it), the device's
busy time (the union of its op intervals inside the window), the time of
an op class or of a kernel by name, the ops that took most time, and the
longest idle gaps labelled by the host span that was open meanwhile (the
spans a driver opens, named ``bench.<what the host does>``).  A trace holds
one device: every cell runs on one chip.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List["Event"]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns)))
    return events


def load_json(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(**e) for e in json.load(f)]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted [start, end] intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def parse_op(text: str) -> Tuple[str, str]:
    """(instruction, opcode) of a device op's event name.  On a TPU the
    name is the op's HLO text, ``%sort.29 = (s32[8]{0}, ...) sort(...)``:
    the instruction is ``sort.29`` and the opcode ``sort``; a plain name
    stands for both."""
    m = re.match(r"%?(\S+) = ", text)
    if not m:
        return text, base_name(text)
    rest = text[m.end():]
    if rest.startswith("("):                    # a tuple of result shapes
        depth = 0
        for i, c in enumerate(rest):
            depth += (c in "({[") - (c in ")}]")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    op = re.match(r"([\w-]+)\(", rest)
    return m.group(1), op.group(1) if op else ""


def base_name(instruction: str) -> str:
    """An instruction's name without its numeric suffix and clone tags
    (``window_score.1`` -> ``window_score``)."""
    return re.sub(r"(\.(\d+|clone))+$", "", instruction)


class Trace:
    """The device ops of one traced window."""

    def __init__(self, events: Sequence[Event]):
        spans = [e for e in events if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        w = max(spans, key=lambda e: e.dur_ns)
        self.start_ns, self.end_ns = w.start_ns, w.end_ns
        def inside(e: Event) -> bool:
            return e.end_ns > self.start_ns and e.start_ns < self.end_ns
        self.host = [e for e in events if inside(e) and e.name != WINDOW_SPAN
                     and e.name.startswith(HOST_SPAN_PREFIX)]
        self.ops = [e for e in events if DEVICE_PLANE.match(e.plane)
                    and e.line == OPS_LINE and inside(e)]
        planes = {e.plane for e in self.ops}
        if len(planes) > 1:
            raise ValueError(f"ops of {len(planes)} devices in the trace; "
                             f"the reduction reads one")

    def _clip(self, e: Event) -> Tuple[float, float]:
        return max(e.start_ns, self.start_ns), min(e.end_ns, self.end_ns)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def busy_intervals(self) -> List[List[float]]:
        return union_ns(self._clip(e) for e in self.ops)

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran."""
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9

    def time_s(self, pred: Callable[[Event], bool]) -> float:
        """Summed device time of the ops ``pred`` selects."""
        total = 0.0
        for e in self.ops:
            if pred(e):
                s, t = self._clip(e)
                total += t - s
        return total * 1e-9

    def class_s(self, *opcodes: str) -> float:
        """Device time of the ops with one of these HLO opcodes."""
        return self.time_s(lambda e: parse_op(e.name)[1] in opcodes)

    def kernel_s(self, names: Sequence[str]) -> float:
        """Device time of a kernel's events: the ops whose instruction is
        named after it (a Pallas call takes the name of its jitted
        wrapper: ``window_score.1``)."""
        return self.time_s(
            lambda e: base_name(parse_op(e.name)[0]) in names)

    def top_ops(self, k: int = 10) -> List[List]:
        """[[instruction, seconds], ...] of the ops that took most device
        time."""
        per: Dict[str, float] = {}
        for e in self.ops:
            s, t = self._clip(e)
            name = parse_op(e.name)[0]
            per[name] = per.get(name, 0.0) + (t - s) * 1e-9
        return [list(kv) for kv in
                sorted(per.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """[[label, seconds], ...]: the longest gaps in the device's busy
        time inside the window, labelled by the host span open at the
        gap's midpoint (``host`` where none is)."""
        busy = [x for iv in self.busy_intervals() for x in iv]
        edges = [self.start_ns] + busy + [self.end_ns]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                label = next((h.name for h in self.host
                              if h.start_ns <= mid < h.end_ns), "host")
                gaps.append([label, (e - s) * 1e-9])
        return sorted(gaps, key=lambda g: -g[1])[:k]
