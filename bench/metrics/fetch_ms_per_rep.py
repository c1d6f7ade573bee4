"""Device time of the mesh's feature fetch exchange per repetition (ms):
the ops the program traces under ``stars.fetch`` (requests bucketed by
owner, the request and the response all_to_all, the answers scattered
back to slot order, distributed/stars_dist.py ``_fetch_exchange``), on
the paced chip."""

STAGES = ("stars.fetch",)


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
