"""Device time of the slab fold before the merge per repetition (ms): the
ops the program traces under ``stars.fold.dedup`` and ``stars.fold.bucket``
(the doubled stream, its dedup sort, the per-node ranking and the (n, kin)
candidate-row scatters, graph/accumulator.py).  The merge kernel is
``topk_merge_ms_per_rep``."""

STAGES = ("stars.fold.dedup", "stars.fold.bucket")


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
