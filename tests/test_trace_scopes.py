"""The build round's named stages and host spans (``repro/scopes.py``).

Every op of the round's entry computation of a kind that does work (fusion,
sort, scatter, gather, custom-call, while) must carry exactly one
``stars.`` stage in its ``op_name``, as the benchmark's reader
(``bench/stages.py``) resolves it from the optimized HLO, on the chip's
compiler (a described ``v5e:2x2``, skipped where none can be described)
and on the CPU's.  The host spans are read from a CPU profiler trace.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import stages as stage_reader
from bench.trace import parse_op
from repro import scopes
from repro.core import GraphBuilder, HashFamilyConfig, StarsConfig
from repro.core.builder import RepetitionSource
from repro.graph import accumulator as acc_lib
from repro.kernels import ops as kernel_ops
from repro.similarity.measures import PointFeatures

N, D, K = 1 << 14, 100, 250     # random1b's widths at n = 2^14
RANDOM1B = StarsConfig(mode="sorting", scoring="stars",
                       family=HashFamilyConfig("simhash", m=24),
                       measure="cosine", r=400, window=250, leaders=25,
                       degree_cap=K, seed=0)
WORK = ("fusion", "sort", "scatter", "gather", "custom-call", "while")


def _round(nbr, w, ver, rep, dense):
    step = RepetitionSource(RANDOM1B).bind(PointFeatures(dense=dense), 0)
    return step(acc_lib.EdgeAccumulator(nbr, w, ver), rep)


def _compile(sharding):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.jit(_round).lower(
        arg((N, K), jnp.int32), arg((N, K), jnp.float32),
        arg((N,), jnp.int32), arg((), jnp.int32),
        arg((N, D), jnp.float32)).compile()


def _entry_stages(compiled):
    """{instruction: [its stars. stages]} of the entry computation's ops
    of the kinds in ``WORK``."""
    (module,) = compiled.runtime_executable().hlo_modules()
    names = stage_reader.hlo_op_names(
        module.as_serialized_hlo_module_proto())
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    out = {}
    for line in entry.splitlines()[1:]:
        name, opcode = parse_op(line.strip().removeprefix("ROOT "))
        if opcode in WORK:
            out[name] = [c for c in names[name].split("/")
                         if c.startswith(stage_reader.STAGE_PREFIX)]
    return out


def _one_stage_each(compiled):
    got = _entry_stages(compiled)
    assert {op: st for op, st in got.items() if len(st) != 1} == {}
    return got


@pytest.fixture(scope="module")
def v5e_round():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    native = kernel_ops.pallas_by_default
    kernel_ops.pallas_by_default = lambda: True      # the chip's dispatch
    jax.clear_caches()          # no jnp-path trace of the kernels is reused
    try:
        return _compile(SingleDeviceSharding(topo.devices[0]))
    finally:
        kernel_ops.pallas_by_default = native
        jax.clear_caches()      # nor this Pallas-path one on the CPU


def test_v5e_round_ops_carry_one_stage(v5e_round):
    got = _one_stage_each(v5e_round)
    assert {s for st in got.values() for s in st} == set(scopes.STAGES)
    assert got["topk_merge.1"] == [scopes.FOLD_MERGE]
    assert got["window_score.1"] == [scopes.SCORE]


def test_cpu_round_ops_carry_one_stage():
    got = _one_stage_each(_compile(SingleDeviceSharding(jax.devices()[0])))
    assert {s for st in got.values() for s in st} == set(scopes.STAGES)


def _spans(tmp_path):
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    return sorted((ev.start_ns, ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(str(path)).planes
                  for line in plane.lines for ev in line.events
                  if ev.name in scopes.SPANS)


def test_add_reps_spans(tmp_path):
    x = jax.random.normal(jax.random.key(0), (600, 8))
    builder = GraphBuilder(x, StarsConfig(r=3, window=50, leaders=5,
                                          degree_cap=10))
    jax.profiler.start_trace(str(tmp_path))
    builder.add_reps(1)
    builder.add_reps(1)
    builder.stats
    jax.profiler.stop_trace()
    names = [name for _, name, _ in _spans(tmp_path)]
    assert names == [scopes.GROW, scopes.ROUND, scopes.BIND, scopes.ROUND,
                     scopes.COUNTERS]
    (bind,) = [stats for _, name, stats in _spans(tmp_path)
               if name == scopes.BIND]
    assert bind["key"] == repr((0, 0, 1.0))
