"""Device time of the sketch stage per repetition (ms): the ops the
program traces under ``stars.sketch`` (the repetition's draws and the hash
projection, core/stars.py ``_rep_candidates``)."""

STAGES = ("stars.sketch",)


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
