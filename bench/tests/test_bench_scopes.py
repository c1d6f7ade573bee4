"""The program's stages and spans read from a trace: ``bench/trace.py``,
with the HLO lookup of ``bench/stages.py``."""

import dataclasses
import json
import os

import pytest

from bench import harness, stages, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "random1b_rep_stages.json")
PR12 = os.path.join(DATA, "random1b_rep_trace.json")
MESH4 = os.path.join(DATA, "random1b_mesh4_rep_stages.json")
BUCKET = "jit(round_step)/stars.fold.bucket"

# reader -> the program's names it reads (repro/scopes.py)
READERS = {"sketch_ms_per_rep": ("SKETCH",),
           "window_sort_ms_per_rep": ("WINDOWS",),
           "score_stage_ms_per_rep": ("SCORE",),
           "fold_ms_per_rep": ("FOLD_DEDUP", "FOLD_BUCKET"),
           "host_round_ms_per_rep": ("ROUND",)}
DEV = "/device:TPU:0"


def _op(start, end, scope, name="op", device=DEV):
    return trace.Event(device, trace.OPS_LINE, name, start, end - start,
                       scope)


def _span(name, start, end):
    return trace.Event("/host:CPU", "python", name, start, end - start)


EVENTS = [
    _op(100, 600, f"{BUCKET}/jit(searchsorted)/while", "while.5"),
    _op(150, 250, f"{BUCKET}/jit(searchsorted)/while/body/gather"),
    _op(300, 400, f"{BUCKET}/jit(searchsorted)/while/body/gather"),
    _op(40, 90, "jit(round_step)/stars.fold.dedup/sort"),
    _op(700, 900, "jit(round_step)/stars.fold.merge/pallas_call"),
    _op(920, 930, "jit(convert_element_type)/convert_element_type"),
    _op(990, 1100, "jit(round_step)/stars.sketch/dot_general"),
    _span(trace.WINDOW_SPAN, 0, 1000),
    _span("bench.add_reps", 0, 100),
    _span("stars.round", 2, 38),
    _span("stars.bind", 2, 30),
    _span("bench.block", 100, 940),
    _span("bench.counters", 940, 1000),
    _span("stars.counters", 945, 985),
    _span("stars.round", 1500, 1600)]                # after the window


def _recorded(path):
    """A recorded window: ``ops`` as [start, end, instruction, scope] (on
    TPU:0) or [device index, start, end, instruction, scope], ``spans``
    as [name, start, end]."""
    with open(path) as f:
        rec = json.load(f)
    events = []
    for o in rec["ops"]:
        device = f"/device:TPU:{o[0]}" if len(o) == 5 else DEV
        start, end, instruction, scope = o[-4:]
        events.append(_op(start, end, scope, instruction, device))
    events += [_span(*s) for s in rec["spans"]]
    return rec, trace.Trace(events)


def test_a_loop_and_its_body_count_once():
    st = trace.Trace(EVENTS)
    assert st.scope_s("stars.fold.bucket") == pytest.approx(500e-9)
    assert st.scope_s("stars.fold.dedup",
                      "stars.fold.bucket") == pytest.approx(550e-9)
    assert st.stages() == {
        "stars.fold.bucket": pytest.approx(500e-9),
        "stars.fold.dedup": pytest.approx(50e-9),
        "stars.fold.merge": pytest.approx(200e-9),
        "stars.sketch": pytest.approx(10e-9),        # cut at the window
        "": pytest.approx(10e-9)}
    assert st.scope_s("stars.fold") == 0             # whole components only


def test_stages_come_from_the_paced_device():
    """A busier device's stages replace TPU:0's; a less busy one's are
    not read, and add nothing to the unscoped remainder."""
    score = "jit(round_step)/stars.score/dot_general"
    busier = trace.Trace(EVENTS + [
        _op(0, 600, score, device="/device:TPU:1"),
        _op(600, 1000, "jit(round_step)/all-to-all", device="/device:TPU:1")])
    assert busier.device == "/device:TPU:1"
    assert busier.stages() == {"stars.score": pytest.approx(600e-9),
                               "": pytest.approx(400e-9)}
    assert busier.scope_s("stars.fold.bucket") == 0
    idle = trace.Trace(EVENTS + [_op(0, 5, score, device="/device:TPU:1"),
                                 _op(5, 9, "", device="/device:TPU:1")])
    assert idle.device == DEV
    assert idle.stages() == trace.Trace(EVENTS).stages()
    assert sum(idle.stages().values()) == pytest.approx(idle.busy_s)


def test_idle_gaps_take_the_innermost_span():
    st = trace.Trace(EVENTS)
    gaps = dict((label, s) for label, s in reversed(st.idle_gaps(10)))
    assert gaps["stars.bind"] == pytest.approx(40e-9)          # 0-40
    assert gaps["bench.block"] == pytest.approx(100e-9)        # 600-700
    assert gaps["stars.counters"] == pytest.approx(60e-9)      # 930-990
    assert st.span_s("stars.round") == pytest.approx(36e-9)
    assert st.span_s("stars.grow") == 0


def _msg(*fields):
    """A protobuf message from (field, value) pairs: ints as varints,
    bytes and str length-delimited."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += varint(field << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(field << 3 | 2) + varint(len(value)) + value
    return out


def _ins(iid, name, op="", operands=(), calls=()):
    fields = [(1, name), (35, iid)]
    if op:
        fields.append((7, _msg((2, op))))
    fields += [(36, o) for o in operands] + [(38, c) for c in calls]
    return _msg(*fields)


def test_unnamed_ops_take_a_neighbour_op_name():
    body = _msg((1, "body"), (5, 1),
                (2, _ins(10, "param.1")),
                (2, _ins(11, "fusion.406", "", [10])))
    entry = _msg((1, "main"), (5, 2),
                 (2, _ins(20, "x", "x")),
                 (2, _ins(21, "copy.10", "tables[0].dense", [20])),
                 (2, _ins(22, "sort.16", "jit(f)/stars.fold.dedup/sort",
                          [21])),
                 (2, _ins(23, "fusion.8", "", [22])),
                 (2, _ins(24, "custom-call.5", "")),
                 (2, _ins(25, "fusion.9", f"{BUCKET}/scatter", [24, 23])),
                 (2, _ins(26, "while.5", f"{BUCKET}/while", [25], [1])))
    names = stages.hlo_op_names(_msg((1, "m"), (3, body), (3, entry)))
    assert names["fusion.8"] == "jit(f)/stars.fold.dedup/sort"  # operand
    assert names["copy.10"] == "jit(f)/stars.fold.dedup/sort"   # user
    assert names["custom-call.5"] == f"{BUCKET}/scatter"        # user
    assert names["fusion.406"] == f"{BUCKET}/while"             # caller
    assert names["sort.16"] == "jit(f)/stars.fold.dedup/sort"


def test_the_trace_keeps_the_programs_hlo(tmp_path):
    """A CPU trace: the profiler stores the HLO of a program compiled
    before the trace began, with the scopes it was traced under, and the
    harness's reading of the trace keeps the program's host spans."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("stars.sketch"):
            return jnp.sort(x * 2.0)

    x = jnp.arange(256, dtype=jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("stars.round"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    programs = stages.xspace_programs(path.read_bytes())
    ops = [op for names in programs.values() for op in names.values()]
    assert any("stars.sketch/" in op for op in ops)

    tr = trace.Trace(trace.load(str(tmp_path)))
    assert [s.name for s in tr.spans if s.name.startswith("stars.")] \
        == ["stars.round"]


def test_pr12_readings_with_the_readers_installed():
    """Every metric's reader is loaded; the first recorded repetition (no
    scopes, no program spans) reads as it did, and the stage and span
    readers read nothing from it."""
    cell = harness.resolve("random1b-build", ROOT)
    tr = trace.Trace(trace.load_json(PR12))
    run = harness.Run(cell, "TPU v5 lite", {"reps": 1}, tr,
                      log=lambda msg: None)
    got = {name: read(run) for name, read in cell.readers.items()}
    assert got["sort_ms_per_rep"] == pytest.approx(916.050955)
    assert got["topk_merge_ms_per_rep"] == pytest.approx(20296.09766)
    assert got["window_score_ms_per_rep"] == pytest.approx(1.467841)
    assert got["device_idle.build"] == pytest.approx(
        100 * (1 - 22.785784076 / 22.789555845))
    assert all(got[name] is None for name in READERS)


def test_readers_read_the_programs_names():
    from repro import scopes
    read = set()
    for name, constants in READERS.items():
        module = harness.load_module(ROOT, "metrics", name)
        names = getattr(module, "STAGES", getattr(module, "SPANS", None))
        assert names == tuple(getattr(scopes, c) for c in constants)
        read.update(names)
    # the merge stage is topk_merge_ms_per_rep plus the version bump
    assert set(scopes.STAGES) - read == {scopes.FOLD_MERGE}
    assert stages.STAGE_PREFIX == "stars." and all(
        s.startswith(stages.STAGE_PREFIX)
        for s in scopes.STAGES + scopes.SPANS)


def test_recorded_chip_stages():
    """One random1b-build repetition traced on a v5e: its ops with their
    scopes and its host spans, read by the new readers as on the chip."""
    rec, st = _recorded(RECORDED)
    assert (st.start_ns, st.end_ns) == tuple(rec["window"])
    chip = rec["read_on_chip"]
    assert 1e3 * st.scope_s("stars.sketch") == pytest.approx(
        chip["sketch_ms_per_rep"])
    assert 1e3 * st.scope_s("stars.windows") == pytest.approx(
        chip["window_sort_ms_per_rep"])
    assert 1e3 * st.scope_s("stars.score") == pytest.approx(
        chip["score_stage_ms_per_rep"])
    assert 1e3 * st.scope_s("stars.fold.dedup", "stars.fold.bucket") \
        == pytest.approx(chip["fold_ms_per_rep"])
    assert 1e3 * st.span_s("stars.round") == pytest.approx(
        chip["host_round_ms_per_rep"])
    split = st.stages()
    busy = chip["busy_s"]
    assert sum(split.values()) == pytest.approx(busy, rel=1e-6)
    assert split[""] <= 0.01 * busy
    assert 1e3 * split["stars.fold.merge"] == pytest.approx(
        chip["topk_merge_ms_per_rep"], rel=0.01)
    assert not [s for s in st.spans if s.name == "stars.bind"]
    assert all(label != "host" for label, s in st.idle_gaps(50)
               if s > 1e-4)


def test_recorded_four_chip_trace():
    """One repetition of the mesh build (random1b at n = 2^16 on a (4,)
    mesh of v5e chips): four devices, the paced one's stages and its
    unscoped remainder sum to its busy time, and the scoring kernel's
    share of its roofline, one chip's windows, stays under 100%."""
    rec, tr = _recorded(MESH4)
    full = rec["read_from_the_whole_trace"]
    assert tr.devices == [f"/device:TPU:{i}" for i in range(4)]
    assert tr.device == full["device"]
    assert tr.busy_s_by_device == pytest.approx(full["busy_s_by_device"])
    split = tr.stages()
    assert split == pytest.approx(full["stages"])
    assert abs(sum(split.values()) - tr.busy_s) <= 1e-9
    cell = harness.resolve("random1b-build", ROOT)
    cell = dataclasses.replace(cell, chips=4,
                               config=dict(cell.config, n=rec["n"]))
    run = harness.Run(cell, "TPU v5 lite", {"reps": 1}, tr,
                      log=lambda msg: None)
    share = cell.readers["window_score_roofline"](run)
    assert share == pytest.approx(full["window_score_roofline"])
    assert 0 < share <= 100
