"""The program's names for its device ops, from the optimized HLO that the
profiler stores in a trace.

The program traces each part of a repetition under a ``jax.named_scope``
(``stars.<stage>``), which XLA keeps in each op's ``op_name``.  TPU op
events carry no ``op_name``, but the profiler stores each live program's
optimized HLO in the trace (plane ``/host:metadata``): ``xspace_programs``
reads it, ``{program: {instruction: op_name}}``, and ``bench/trace.py``
looks each device op up there, in the program whose run it falls in.  An op
that XLA's passes made and left without a traced ``op_name`` (a scatter
expanded into sorts, a buffer allocation, a parameter's copy, a merged
constant) takes that of the latest of its operands that has one; failing
that, that of its first user that has one; failing that, in a loop body,
that of the loop.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

STAGE_PREFIX = "stars."
METADATA_PLANE = "/host:metadata"


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
