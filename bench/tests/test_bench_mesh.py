"""The four-chip mesh cell, ``random1b-mesh4-build``, on CPU devices: its
driver against the plain reference and the one-device build, each
exchange's counted bytes, and every op of the mesh round under one stage;
then its per-layer readers on recorded four-chip traces."""

import json
import os

import pytest

from bench import harness, trace
from repro.core import windows
from repro.core.stars import TIEBREAK_BITS
from repro.distributed.sorter import exchange_capacity
from repro.testing import run_forced_devices

ROOT = harness.ROOT
DATA = os.path.join(os.path.dirname(__file__), "data")
MESH4 = os.path.join(DATA, "random1b_mesh4_rep_stages.json")
SCOPED = os.path.join(DATA, "random1b_mesh4_pair_stages.json")
CELL = "random1b-mesh4-build"
N, SEED, CHECK_ROWS = 4096, 7, 256
EXCHANGE_READERS = ("emit_ms_per_rep", "fetch_ms_per_rep",
                    "collective_ms_per_rep")

# Runs on four forced CPU devices: the cell's driver at random1b's widths
# and n = 4096 (three calls of a repetition pair: the warm-up, one, and one
# traced), a one-device build_loop session on the same corpus, the check,
# the control, and the stages of every program the traced call ran.
_MESH_RUN = """
import glob, json, tempfile
import jax, numpy as np
from jax.profiler import ProfileData
from bench import harness, reference, stages
from bench.drivers import build_loop
from repro.graph import accumulator as acc_lib

lines = []
cell = harness.resolve("%(cell)s")
config = dict(cell.config, n=%(n)d)
traffic = dict(cell.traffic, check_rows=%(rows)d)
acc_lib.reset_transfer_stats()
mesh = cell.driver.Session(config, traffic, %(seed)d, cell.generator.make,
                           lines.append)
mesh.call()
wire = {k: v for k, v in acc_lib.transfer_stats.items()
        if k.startswith("all_to_all_bytes")}
tmp = tempfile.mkdtemp()
jax.profiler.start_trace(tmp)
mesh.call()
jax.profiler.stop_trace()
raw = open(glob.glob(tmp + "/**/*.xplane.pb", recursive=True)[0], "rb").read()
ran = sorted({ev.name[len("PjitFunction("):-1]
              for plane in ProfileData.from_serialized_xspace(raw).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("PjitFunction(")})

def modules(raw):
    # {program: its serialized HloModuleProto}, as stages.xspace_programs
    # finds them in the trace's metadata plane
    out = {}
    for field, plane in stages._fields(raw):
        fields = list(stages._fields(plane)) if field == 1 else []
        if dict(fields).get(2, b"").decode() != stages.METADATA_PLANE:
            continue
        for f, entry in fields:
            meta = list(stages._fields(dict(stages._fields(entry)).get(
                2, b""))) if f == 4 else []
            for f2, stat in meta:
                proto = dict(stages._fields(stat)).get(6) if f2 == 5 else None
                module = dict(stages._fields(proto)).get(1) if proto else None
                if module:
                    out[dict(meta)[2].decode()] = module
    return out

FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
programs = {}
for name, module in modules(raw).items():
    if name.split("(")[0][len("jit_"):] not in ran:
        continue
    names = stages.hlo_op_names(module)
    fields = dict(stages._fields(module))
    ops = []
    for f, comp in stages._fields(module):
        comp = list(stages._fields(comp)) if f == 3 else []
        if dict(comp).get(5) != fields.get(6):              # the entry
            continue
        for ins in (dict(stages._fields(v)) for f2, v in comp if f2 == 2):
            if ins[2].decode() not in FREE:
                ops.append(sorted({c for c in names[ins[1].decode()].split("/")
                                   if c.startswith("stars.")}))
    programs[name.split("(")[0]] = ops

one = build_loop.Session(dict(config, chips=1), traffic, %(seed)d,
                         cell.generator.make, lines.append)
for _ in range(2):
    one.call()
a, b = mesh.builder.slab_state(), one.builder.slab_state()
same = bool(np.array_equal(np.asarray(a.nbr), np.asarray(b.nbr))
            and np.array_equal(np.asarray(a.w), np.asarray(b.w)))
reps = mesh.builder.reps_done
stats = mesh.stats
checks = mesh.check()

# the control: the same rows weighed at three bfloat16 passes
rng = np.random.default_rng([mesh.data_seed, build_loop.SAMPLE_SALT])
rows = np.sort(rng.choice(config["n"], %(rows)d, replace=False))
k = config["degree_cap"]
cands, ref_w, high_w = reference.reference_rows(
    mesh.x, config, config["seed"], reps, rows)
nbr, w = reference.control_rows(cands, high_w, k)
control = reference.compare(nbr, w, cands, ref_w, high_w, k,
                            tie_tol=2 * config["check"]["weight_gap"])
print(json.dumps({"checks": checks, "same": same, "reps": reps,
                  "one_reps": one.builder.reps_done, "stats": stats,
                  "wire": wire, "control": control, "ran": ran,
                  "programs": programs, "lines": lines}))
"""


@pytest.fixture(scope="module")
def mesh_run():
    return run_forced_devices(
        _MESH_RUN % dict(cell=CELL, n=N, rows=CHECK_ROWS, seed=SEED),
        devices=4, extra_pythonpath=(ROOT,), timeout=600)


def test_mesh_session_is_correct_and_equals_one_device(mesh_run):
    """Two calls after the warm-up: the sampled rows match the reference,
    no repetition fails, and the slabs equal a one-device build_loop
    session's bit for bit."""
    checks = mesh_run["checks"]
    assert checks["rows_wrong"]["value"] == 0
    assert checks["failed_reps"]["value"] == 0
    assert checks["weight_gap"]["value"] <= checks["weight_gap"]["limit"]
    assert checks["high_share"]["value"] <= checks["high_share"]["limit"]
    assert mesh_run["reps"] == mesh_run["one_reps"] == 6
    assert mesh_run["same"]


def test_the_control_fails_the_check(mesh_run):
    """The reference at three bfloat16 passes is not correct by the cell's
    limits: its weights carry the whole three-pass rounding error."""
    limits = harness.resolve(CELL).config["check"]
    control = mesh_run["control"]
    assert control["high_share"] > limits["high_share"]
    assert control["weight_gap"] > limits["weight_gap"]


def test_each_exchange_is_counted(mesh_run):
    """The wire bytes of the warm-up and one call (two repetition pairs),
    split by exchange, sum to ``all_to_all_bytes``, and each is its closed
    form from the exchanges' shapes and ``exchange_capacity``; the driver
    logs them per repetition, with no drop anywhere."""
    p, d = 4, 100
    cfg = harness.resolve(CELL).config
    w, s = cfg["window"], cfg["leaders"]
    wire = mesh_run["wire"]
    assert wire["all_to_all_bytes"] == sum(
        wire[f"all_to_all_bytes.{x}"] for x in ("sort", "fetch", "emit"))
    assert wire["all_to_all_bytes.cluster"] == 0
    _, rps, total_slots = windows.shard_row_layout("sorting", N, w, p)
    key_words = -(-(cfg["m"] + TIEBREAK_BITS + N.bit_length()) // 32)
    sort = 2 * p * (p - 1) * exchange_capacity(N // p, p, 2.0) * key_words * 4
    cap = exchange_capacity(2 * total_slots // p, p, 2.0)
    fetch = p * (p - 1) * cap * 4 * (1 + d)
    triples = 2 * 2 * rps * s * w                   # a pair, doubled
    emit_words = -(-((N // p).bit_length() + N.bit_length() + 32) // 32)
    emit = p * (p - 1) * exchange_capacity(triples, p, 2.0) * emit_words * 4
    pairs = 2
    assert wire["all_to_all_bytes.sort"] == pairs * sort
    assert wire["all_to_all_bytes.fetch"] == pairs * fetch
    assert wire["all_to_all_bytes.emit"] == pairs * emit
    stats = mesh_run["stats"]
    assert stats["dropped"] == 0 and all(
        stats[f"dropped.{x}"] == 0 for x in ("sort", "fetch", "emit"))
    logged = [json.loads(line.split("wire_bytes=")[1].split(" dropped=")[0])
              for line in mesh_run["lines"] if "wire_bytes=" in line]
    assert logged[0] == {"sort": sort / 2, "fetch": fetch / 2,
                         "emit": emit / 2}


def test_every_op_of_the_mesh_round_has_one_stage(mesh_run):
    """A warm call dispatches only the round's six programs, and every op
    of their entry computations carries exactly one ``stars.`` stage, as
    ``bench/stages.py`` resolves it; none is both the emit and the fold."""
    assert mesh_run["ran"] == sorted([
        "sketch_phase", "offset_phase", "_window_blocks_jit",
        "_fetch_exchange", "score_shard", "_emit_exchange"])
    found = {}
    for name, ops in mesh_run["programs"].items():
        assert [op for op in ops if len(op) != 1] == [], name
        found[name] = {op[0] for op in ops}
    assert found == {
        "jit_sketch_phase": {"stars.sketch"},
        "jit_offset_phase": {"stars.windows"},
        "jit__window_blocks_jit": {"stars.windows"},
        "jit__fetch_exchange": {"stars.fetch"},
        "jit_score_shard": {"stars.score"},
        "jit__emit_exchange": {"stars.emit", "stars.fold.dedup",
                               "stars.fold.bucket", "stars.fold.merge"}}


def _recorded(path, name_of=lambda name: name):
    """A recorded window: ``ops`` as [device index, start, end, name, the
    stars. components of its op_name], ``spans`` as [name, start, end];
    ``name_of`` gives the event name of a recorded op name."""
    with open(path) as f:
        rec = json.load(f)
    events = [trace.Event(f"/device:TPU:{dev}", trace.OPS_LINE, name_of(name),
                          start, end - start, scope)
              for dev, start, end, name, scope in rec["ops"]]
    events += [trace.Event("/host:CPU", "python", name, start, end - start)
               for name, start, end in rec["spans"]]
    return rec, trace.Trace(events)


def _read(path, reps, name_of=lambda name: name):
    cell = harness.resolve(CELL, ROOT)
    rec, tr = _recorded(path, name_of)
    run = harness.Run(cell, "TPU v5 lite", {"reps": reps}, tr,
                      log=lambda msg: None)
    return rec, tr, {name: cell.readers[name](run) for name in
                     EXCHANGE_READERS}


def _with_opcode(name):
    """The recording before the stages keeps each op's instruction alone.
    XLA names an instruction it makes after its opcode (``all-reduce.3``);
    JAX names an all_to_all after its primitive (``all_to_all.3``): give
    those their opcode, in the HLO text a TPU op carries."""
    if trace.base_name(name) != "all_to_all":
        return name
    return f"%{name} = () all-to-all("


def test_exchange_readers_on_the_recording_before_the_stages():
    """The four-chip recording made before the exchanges had stages: the
    collectives read, the stage readers read nothing."""
    rec, tr, got = _read(MESH4, 1, _with_opcode)
    collective = sum(e.dur_ns for e in tr.ops if trace.base_name(
        trace.parse_op(e.name)[0]) in ("all_to_all", "all-reduce")) * 1e-6
    assert got["collective_ms_per_rep"] == pytest.approx(collective)
    assert 0.3 < got["collective_ms_per_rep"] < 0.4
    assert got["emit_ms_per_rep"] is None and got["fetch_ms_per_rep"] is None


def test_exchange_readers_on_the_scoped_recording():
    """One call of the cell's window (a repetition pair) traced on four
    chips with the exchanges' stages, trimmed to the paced chip, each op
    with its HLO text to the opcode: its split sums to its busy time and
    to what the chip read, at most 5% of it is unscoped, and the three
    readers read what they read on the chip."""
    rec, tr, got = _read(SCOPED, 2)
    split = tr.stages()
    chip = rec["read_from_this_window"]
    assert tr.device == chip["device"]
    assert tr.busy_s == pytest.approx(chip["busy_s"], abs=1e-9)
    assert sum(split.values()) == pytest.approx(tr.busy_s, abs=1e-9)
    assert split == pytest.approx(chip["stages"], abs=1e-9)
    assert split[""] <= 0.05 * tr.busy_s
    assert {"stars.emit", "stars.fetch", "stars.windows"} <= set(split)
    for name in EXCHANGE_READERS:
        assert got[name] > 0
        assert got[name] == pytest.approx(chip["metrics"][name]), name


def test_exchange_readers_read_nothing_on_one_chip():
    """On the one-chip recording of random1b-build the new readers read
    nothing, so that cell reports none of them."""
    cell = harness.resolve("random1b-build", ROOT)
    with open(os.path.join(DATA, "random1b_rep_stages.json")) as f:
        rec = json.load(f)
    events = [trace.Event("/device:TPU:0", trace.OPS_LINE, name, start,
                          end - start, scope)
              for start, end, name, scope in rec["ops"]]
    events += [trace.Event("/host:CPU", "python", name, start, end - start)
               for name, start, end in rec["spans"]]
    run = harness.Run(cell, "TPU v5 lite", {"reps": 1}, trace.Trace(events),
                      log=lambda msg: None)
    assert run.trace.busy_s > 0
    for name in EXCHANGE_READERS:
        module = harness.load_module(ROOT, "metrics", name)
        assert module.read(run) is None, name
