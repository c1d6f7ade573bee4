"""Names the graph build gives its work in a profiler trace.

Device stages are ``jax.named_scope`` names on the shared round functions,
so the resident, paged and mesh paths carry the same names; XLA keeps them
in each op's ``op_name`` metadata.  The eight stages are disjoint and cover
a repetition: ``STAGES``, the six that every backend runs, and on a mesh
``MESH_STAGES``, its two exchanges:

  ``stars.sketch``       the repetition's draws and the hash sketch
                         (core/stars.py ``_rep_candidates``; the paged and
                         mesh sketch phases, with the mesh's key packing)
  ``stars.windows``      the sort into windows (``_rep_window_grid``; on a
                         mesh the window offset and the sample sort with
                         its reduce-scatter, distributed/sorter.py)
  ``stars.fetch``        the mesh's feature fetch: requests bucketed by
                         owner, both all_to_alls, the answers scattered
                         back to slot order (distributed/stars_dist.py)
  ``stars.score``        leader draw, gathers, scoring, lane broadcast
                         (``_score_windows``)
  ``stars.emit``         the mesh's edge emit: the doubled triples, the
                         bucket-by-owner sort, packing, the all_to_all and
                         the unpack, closed before the fold opens
  ``stars.fold.dedup``   the doubled candidate stream and its dedup sort
                         (graph/accumulator.py ``accumulate``, step 1 of
                         ``_fold_triples``)
  ``stars.fold.bucket``  the per-node ranking into (n, kin) candidate rows
                         (steps 2 and 2b)
  ``stars.fold.merge``   the slab merge and the row-version bump (step 3)

Host spans are ``jax.profiler.TraceAnnotation``s on the profiler's clock,
the one the device ops are timed on; each costs next to nothing when no
profiler runs:

  ``stars.round``     one dispatch of ``GraphBuilder._run_rounds``: bind
                      lookup, dispatch and the counter bookkeeping
  ``stars.bind``      a round that binds a new program (its first call
                      compiles or loads it); the bind key is its ``key``
  ``stars.grow``      ``GraphBuilder._grow`` allocating or growing slabs
  ``stars.counters``  the device_get of the round counters, where the host
                      waits on the device
"""

import functools

import jax

SKETCH = "stars.sketch"
WINDOWS = "stars.windows"
FETCH = "stars.fetch"
SCORE = "stars.score"
EMIT = "stars.emit"
FOLD_DEDUP = "stars.fold.dedup"
FOLD_BUCKET = "stars.fold.bucket"
FOLD_MERGE = "stars.fold.merge"
STAGES = (SKETCH, WINDOWS, SCORE, FOLD_DEDUP, FOLD_BUCKET, FOLD_MERGE)
MESH_STAGES = (FETCH, EMIT)

ROUND = "stars.round"
BIND = "stars.bind"
GROW = "stars.grow"
COUNTERS = "stars.counters"
SPANS = (ROUND, BIND, GROW, COUNTERS)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
