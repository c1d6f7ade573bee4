"""Ahead-of-time compiles of the graph path's Pallas kernels for a TPU v5e.

The CPU suite runs the kernels in interpret mode, which accepts block
shapes, dtypes and VMEM footprints that the chip's compiler (Mosaic)
refuses.  Here each kernel is lowered natively and compiled for one chip
of a described ``v5e:2x2`` topology at the widths of ``chip_smoke.py``
(Random1B d=100 at n = 2^20, W=250, s=25, k=250): nothing runs, but every
compile error the chip would raise is raised here.  The topology is
described inside a fixture, so collecting this file never loads the TPU
library, and tests skip (never fail) where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import leader_score as ls
from repro.kernels import topk_merge as tm
from repro.kernels import window_score as ws

N = 1 << 20                     # chip_smoke's one-chip corpus
NW = (N + 249) // 250 + 1       # SortingLSH window rows at W=250
S, W, D, K = 25, 250, 100, 250


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel, natively
    return compiled


@pytest.mark.parametrize("masks", [
    {},                                            # the build round
    dict(allpairs=True, match_bucket=True, new_from=7, refresh_below=11,
         r1=0.5),                                  # every mask stage armed
])
def test_window_score_compiles(one_chip, masks):
    _compile(lambda *a: ws.window_score(*a, **masks), one_chip,
             ((NW, S, D), jnp.float32), ((NW, W, D), jnp.float32),
             ((NW, S), jnp.int32), ((NW, S), jnp.int32),
             ((NW, W), jnp.int32), ((NW, S), jnp.bool_),
             ((NW, W), jnp.bool_), ((NW, S), jnp.uint32),
             ((NW, W), jnp.uint32), ((NW,), jnp.bool_))


def test_leader_score_compiles(one_chip):
    _compile(ls.leader_score, one_chip,
             ((NW, S, D), jnp.float32), ((NW, W, D), jnp.float32),
             ((NW, S), jnp.bool_), ((NW, W), jnp.bool_))


@pytest.mark.parametrize("rows,k", [
    pytest.param(N, K, id=str(N)),                      # build
    pytest.param(N + N // 100, K, id=str(N + N // 100)),  # after extend
    pytest.param(N, 384, id="1024-lanes"),   # a row that pads to 1,024
])
def test_topk_merge_compiles(one_chip, rows, k):
    compiled = _compile(tm.topk_merge, one_chip,
                        ((rows, k), jnp.int32), ((rows, k), jnp.float32),
                        ((rows, K), jnp.int32), ((rows, K), jnp.float32))
    # the merge streams rows through VMEM: nothing slab-sized is staged
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)
