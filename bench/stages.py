"""The program's own names in a traced window: device stages and host spans.

The program traces each part of a repetition under a ``jax.named_scope``
(``stars.<stage>``), which XLA keeps in each op's ``op_name``, and opens
host spans on the profiler's clock (``stars.<step>``).  The profiler stores
each live program's optimized HLO in the trace (plane ``/host:metadata``):
``read`` looks each device op up there, in the program whose run (an event
of the device's ``XLA Modules`` line) it falls in.  An op that XLA's passes
made and left without a traced ``op_name`` (a scatter expanded into sorts,
a buffer allocation, a parameter's copy, a merged constant) takes that of the latest of its
operands that has one; failing that, that of its first user that has one;
failing that, in a loop body, that of the loop.

``bench/trace.py`` keeps no op names and drops the trace file before the
metric readers run, so ``install()`` wraps its ``load`` to keep a
``Scoped`` reading of the same file; ``of(run)`` gives the readers a
``StageTrace`` of the run's window.  ``load``'s own events, and every
number read from them, are unchanged.  A trace of a program without
stages or spans reads 0 for them, and a trace this module cannot read
leaves ``of(run)`` None.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import sys
import traceback
from typing import Dict, List, Optional, Tuple

from bench import trace as trace_lib

STAGE_PREFIX = "stars."
SPAN_PREFIXES = ("bench.", "stars.")
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Op:
    start_ns: float
    end_ns: float
    instruction: str
    scope: str


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Scoped:
    """A trace's device ops with their scopes, and its named host spans."""
    ops: List[Op]
    spans: List[Span]


def read(raw: bytes) -> Scoped:
    """The ``Scoped`` reading of a serialized ``XSpace`` (``.xplane.pb``)."""
    from jax.profiler import ProfileData
    programs = xspace_programs(raw)
    ops, spans = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        if trace_lib.DEVICE_PLANE.match(plane.name):
            runs = [(m.start_ns, m.start_ns + m.duration_ns,
                     programs.get(m.name, {}))
                    for m in lines.get(MODULES_LINE, [])]
            for ev in lines.get(trace_lib.OPS_LINE, []):
                start = float(ev.start_ns)
                names = next((p for s, e, p in runs if s <= start < e), {})
                instruction = trace_lib.parse_op(ev.name)[0]
                ops.append(Op(start, start + float(ev.duration_ns),
                              instruction, names.get(instruction, "")))
        else:
            spans += [Span(ev.name, float(ev.start_ns),
                           float(ev.start_ns + ev.duration_ns))
                      for evs in lines.values() for ev in evs
                      if ev.name.startswith(SPAN_PREFIXES)]
    return Scoped(ops, spans)


# -- the optimized HLO the profiler stores in the trace -------------------- #

def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varint and
    fixed-width fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not understood")
        yield field, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _ints(value) -> List[int]:
    """A repeated integer field's values, packed (bytes) or not (int)."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def xspace_programs(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op_name}} of every HLO module the profiler
    stored in a serialized ``XSpace``, keyed as the device's ``XLA
    Modules`` events name the program's runs (``jit_round_step(<id>)``)."""
    programs: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(raw):
        if field != 1:                                  # XSpace.planes
            continue
        fields = list(_fields(plane))
        if dict(fields).get(2, b"").decode() != METADATA_PLANE:
            continue
        for f, entry in fields:
            if f != 4:                                  # event_metadata
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = dict(meta).get(2, b"").decode()
            for f2, stat in meta:
                proto = dict(_fields(stat)).get(6) if f2 == 5 else None
                module = dict(_fields(proto)).get(1) if proto else None
                if module:                              # HloProto.hlo_module
                    programs[name] = hlo_op_names(module)
    return programs


def _traced(op_name: str) -> bool:
    """Whether ``op_name`` names the primitive an op was traced from: not
    empty, an argument's name, or the bare path of the jit that XLA made
    the op in (``jit(f)/jit(g)``, a constant it merged or hoisted)."""
    return "/" in op_name and not op_name.rsplit("/", 1)[1].startswith("jit(")


def hlo_op_names(module: bytes) -> Dict[str, str]:
    """{instruction: op_name} of a serialized ``HloModuleProto``, an op
    without a traced ``op_name`` taking one from its operands, its users or
    its caller (module docstring)."""
    comps = []
    for f, comp in _fields(module):
        if f != 3:                                      # computations
            continue
        cid, instrs = 0, []
        for f2, v in _fields(comp):
            if f2 == 5:
                cid = v
            elif f2 == 2:                               # instructions
                ins = {"id": 0, "op": "", "operands": [], "calls": []}
                for f3, w in _fields(v):
                    if f3 == 1:
                        ins["name"] = w.decode()
                    elif f3 == 7:                       # OpMetadata.op_name
                        ins["op"] = dict(_fields(w)).get(2, b"").decode()
                    elif f3 == 35:
                        ins["id"] = w
                    elif f3 == 36:
                        ins["operands"] += _ints(w)
                    elif f3 == 38:
                        ins["calls"] += _ints(w)
                instrs.append(ins)
        comps.append((cid, instrs))
    order = [ins for _, instrs in comps for ins in instrs]
    by_id = {ins["id"]: ins for ins in order}
    traced = {ins["id"]: _traced(ins["op"]) for ins in order}
    users: Dict[int, List[dict]] = {}
    callers: Dict[int, List[dict]] = {}
    for ins in order:
        for o in ins["operands"]:
            users.setdefault(o, []).append(ins)
        for c in ins["calls"]:
            callers.setdefault(c, []).append(ins)

    def inherit(ins, sources) -> bool:
        named = [x for x in sources if traced[x["id"]]]
        if traced[ins["id"]] or not named:
            return False
        ins["op"], traced[ins["id"]] = named[0]["op"], True
        return True

    for ins in order:                 # operands come first in a computation
        inherit(ins, [by_id[o] for o in reversed(ins["operands"])])
    for ins in reversed(order):
        inherit(ins, users.get(ins["id"], []))
    changed = True
    while changed:                    # nested loop bodies: a level a pass
        changed = False
        for cid, instrs in comps:
            for ins in instrs:
                changed |= inherit(ins, callers.get(cid, []))
    return {ins["name"]: ins["op"] for ins in order}


# -- the readers' view of one window --------------------------------------- #

def _union_s(intervals) -> float:
    return sum(e - s for s, e in trace_lib.union_ns(intervals)) * 1e-9


class StageTrace:
    """The ``Scoped`` reading of one window, ops and spans clipped to it."""

    def __init__(self, scoped: Scoped, start_ns: float, end_ns: float):
        self.start_ns, self.end_ns = start_ns, end_ns
        self.ops = [o for o in scoped.ops if self._inside(o)]
        self.spans = [s for s in scoped.spans if self._inside(s)
                      and s.name != trace_lib.WINDOW_SPAN]

    def _inside(self, e) -> bool:
        return e.end_ns > self.start_ns and e.start_ns < self.end_ns

    def _clip(self, e) -> Tuple[float, float]:
        return max(e.start_ns, self.start_ns), min(e.end_ns, self.end_ns)

    def scope_s(self, *stages: str) -> float:
        """Seconds in which an op ran whose ``op_name`` holds one of
        ``stages`` as a component: the union of their intervals, so that a
        loop and the ops of its body count once."""
        return _union_s(self._clip(o) for o in self.ops
                        if not set(stages).isdisjoint(o.scope.split("/")))

    def stages(self) -> Dict[str, float]:
        """{stage: scope_s(stage)} of every ``stars.`` component in the
        window, and under ``""`` the seconds of the ops that hold none."""
        names = {c for o in self.ops for c in o.scope.split("/")
                 if c.startswith(STAGE_PREFIX)}
        out = {name: self.scope_s(name) for name in sorted(names)}
        out[""] = _union_s(self._clip(o) for o in self.ops
                           if names.isdisjoint(o.scope.split("/")))
        return out

    def span_s(self, name: str) -> float:
        """Summed seconds of the host spans called ``name``."""
        return sum(t - s for s, t in (self._clip(e) for e in self.spans
                                      if e.name == name)) * 1e-9

    def idle_gaps(self, k: int = 10) -> List[List]:
        """[[label, seconds], ...]: the longest gaps in the device's busy
        time, labelled by the innermost (shortest) host span open at the
        gap's midpoint (``host`` where none is)."""
        busy = [x for iv in trace_lib.union_ns(map(self._clip, self.ops))
                for x in iv]
        edges = [self.start_ns] + busy + [self.end_ns]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                spans = [h for h in self.spans
                         if h.start_ns <= mid < h.end_ns]
                label = (min(spans, key=lambda h: h.end_ns - h.start_ns).name
                         if spans else "host")
                gaps.append([label, (e - s) * 1e-9])
        return sorted(gaps, key=lambda g: -g[1])[:k]


# -- hooked into the harness's trace loading ------------------------------- #

_last: Dict[str, object] = {"scoped": None, "view": None}


def install() -> None:
    """Wrap ``bench.trace.load`` (once) so that each trace it loads is also
    read here; its own result is returned unchanged."""
    load = trace_lib.load
    if getattr(load, "keeps_stages", False):
        return

    @functools.wraps(load)
    def load_and_read(trace_dir: str):
        events = load(trace_dir)
        _last["scoped"] = _last["view"] = None
        paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        try:
            with open(paths[-1], "rb") as f:
                _last["scoped"] = read(f.read())
        except Exception:               # the other metrics read on
            print("bench.stages: trace not read:", file=sys.stderr)
            traceback.print_exc()
        return events

    load_and_read.keeps_stages = True
    trace_lib.load = load_and_read


def of(run) -> Optional[StageTrace]:
    """The ``StageTrace`` of ``run``'s window, once per run (logging the
    stage split, the unscoped share and the labelled idle gaps)."""
    scoped = _last["scoped"]
    if scoped is None:
        return None
    view = _last["view"]
    if view is not None and view[0] is run:
        return view[1]
    st = StageTrace(scoped, run.trace.start_ns, run.trace.end_ns)
    split = st.stages()
    busy = run.trace.busy_s
    run.log("stages: " + " ".join(f"{k or 'unscoped'}={v:.9f}"
                                  for k, v in split.items())
            + f" sum={sum(split.values()):.9f} busy_s={busy:.9f}"
            + (f" unscoped_share={100 * split[''] / busy:.6f}%"
               if busy else ""))
    run.log(f"idle gaps by innermost span: {st.idle_gaps(6)}")
    _last["view"] = (run, st)
    return st


def per_rep_ms(run, seconds) -> Optional[float]:
    """A reader's result: ``seconds`` of the window per repetition, in
    ms, or None where the trace holds none."""
    return 1e3 * seconds / run.counts["reps"] if seconds > 0 else None
