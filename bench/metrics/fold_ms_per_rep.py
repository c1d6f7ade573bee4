"""Device time of the slab fold before the merge per repetition (ms): the
ops the program traces under ``stars.fold.dedup`` and ``stars.fold.bucket``
(the doubled stream, its dedup sort, the per-node ranking and the (n, kin)
candidate-row scatters, graph/accumulator.py), read by
``bench/stages.py``.  The merge kernel is ``topk_merge_ms_per_rep``."""

from bench import stages

STAGES = ("stars.fold.dedup", "stars.fold.bucket")
stages.install()


def read(run):
    st = stages.of(run)
    return stages.per_rep_ms(run, st.scope_s(*STAGES)) if st else None
