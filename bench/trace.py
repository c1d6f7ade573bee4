"""Reduction of a profiler trace of the measured window to device times.

``load(path)`` reads the newest ``.xplane.pb`` once into ``Event``s: every
device op with the ``op_name`` scope the program traced it under (looked
up in the optimized HLO that the profiler stores, ``bench/stages.py``),
and every host span.  ``Trace`` answers what the per-layer metric readers
ask about the window, the host span ``bench.window`` that the harness
opens around it: the device's busy time (the union of its op intervals
inside the window), the time of an op class, a kernel or a program stage,
the ops that took most time, the host spans, and the longest idle gaps
labelled by the innermost host span open meanwhile (``bench.<step>``
around the benchmark's calls, ``stars.<step>`` inside the program's).

A window of several chips reduces per device.  Each device's busy time is
the union of its own ops' intervals, and every device-time reading comes
from one device, the paced one: the busiest in the window, the lowest
index on a tie.  In an SPMD round every chip waits at each collective for
the slowest, so the busiest chip sets the build's pace; a wait inside a
collective is an op on its device, which is how an exposed exchange
shows.  Its idle share is the time in which even the busiest chip had
nothing to run: what the host costs.  On one chip the paced device is the
only one.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

from bench import stages

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "stars.")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""          # a device op's op_name, where the HLO names it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``, each
    device op with the ``op_name`` of its instruction in the program whose
    run (an event of its device's ``XLA Modules`` line) it falls in."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    programs = stages.xspace_programs(raw)
    events = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = [(m.start_ns, m.start_ns + m.duration_ns,
                 programs.get(m.name, {}))
                for m in lines.get(MODULES_LINE, [])
                ] if DEVICE_PLANE.match(plane.name) else []
        for line, evs in lines.items():
            for ev in evs:
                start, scope = float(ev.start_ns), ""
                if runs and line == OPS_LINE:
                    names = next((p for s, e, p in runs if s <= start < e),
                                 {})
                    scope = names.get(parse_op(ev.name)[0], "")
                events.append(Event(plane.name, line, ev.name, start,
                                    float(ev.duration_ns), scope))
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
    """The device ops and host spans of one traced window."""

    def __init__(self, events: Sequence[Event]):
        spans = [e for e in events if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        w = max(spans, key=lambda e: e.dur_ns)
        self.start_ns, self.end_ns = w.start_ns, w.end_ns
        by_device: Dict[str, List[Event]] = {}
        self.spans = []
        for e in events:
            if not (e.end_ns > self.start_ns and e.start_ns < self.end_ns):
                continue
            if DEVICE_PLANE.match(e.plane):
                if e.line == OPS_LINE:
                    by_device.setdefault(e.plane, []).append(e)
            elif e.name.startswith(SPAN_PREFIXES) and e.name != WINDOW_SPAN:
                self.spans.append(e)
        self.devices = sorted(
            by_device, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
        self.busy_s_by_device = {
            d: self._union_s(by_device[d]) for d in self.devices}
        self.device = max(self.devices, key=self.busy_s_by_device.get,
                          default=None)
        self.ops = by_device.get(self.device, [])

    def _clip(self, e: Event) -> Tuple[float, float]:
        return max(e.start_ns, self.start_ns), min(e.end_ns, self.end_ns)

    def _union_s(self, events: Iterable[Event]) -> float:
        """Seconds covered by the union of ``events``' clipped intervals."""
        return sum(e - s for s, e in union_ns(map(self._clip, events))) * 1e-9

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran on the paced device."""
        return self.busy_s_by_device.get(self.device, 0.0)

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

    def scope_s(self, *names: str) -> float:
        """Seconds in which an op ran whose ``op_name`` holds one of
        ``names`` as a component: the union of their intervals, so that a
        loop and the ops of its body count once."""
        return self._union_s(e for e in self.ops
                             if not set(names).isdisjoint(e.scope.split("/")))

    def stages(self) -> Dict[str, float]:
        """{stage: scope_s(stage)} of every ``stars.`` component in the
        window, and under ``""`` the seconds of the ops that hold none."""
        names = {c for e in self.ops for c in e.scope.split("/")
                 if c.startswith(stages.STAGE_PREFIX)}
        out = {name: self.scope_s(name) for name in sorted(names)}
        out[""] = self._union_s(e for e in self.ops
                                if names.isdisjoint(e.scope.split("/")))
        return out

    def span_s(self, name: str) -> float:
        """Summed seconds of the host spans called ``name``."""
        return sum(t - s for s, t in (self._clip(e) for e in self.spans
                                      if e.name == name)) * 1e-9

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
        time inside the window, labelled by the innermost (shortest) host
        span open at the gap's midpoint (``host`` where none is)."""
        busy = [x for iv in union_ns(map(self._clip, self.ops)) for x in iv]
        edges = [self.start_ns] + busy + [self.end_ns]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                open_ = [h for h in self.spans
                         if h.start_ns <= mid < h.end_ns]
                label = (min(open_, key=lambda h: h.dur_ns).name
                         if open_ else "host")
                gaps.append([label, (e - s) * 1e-9])
        return sorted(gaps, key=lambda g: -g[1])[:k]
