"""Device time of the sort into windows per repetition (ms): the ops the
program traces under ``stars.windows`` (tiebreak draw, bit packing, the
sort and the slot scatter, core/stars.py ``_rep_window_grid``)."""

STAGES = ("stars.windows",)


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
