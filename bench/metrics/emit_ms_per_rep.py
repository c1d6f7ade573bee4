"""Device time of the mesh's edge emit exchange per repetition (ms): the
ops the program traces under ``stars.emit`` (the doubled triples, the
bucket-by-owner sort, packing, the all_to_all and the unpack,
distributed/stars_dist.py ``_emit_exchange``), on the paced chip.  The
fold that follows is ``fold_ms_per_rep`` and ``topk_merge_ms_per_rep``."""

STAGES = ("stars.emit",)


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
