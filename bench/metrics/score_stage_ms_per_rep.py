"""Device time of the scoring stage per repetition (ms): the ops the
program traces under ``stars.score`` (leader draw, feature gathers, the
scoring kernel and the lane broadcast, core/stars.py
``_score_windows``)."""

STAGES = ("stars.score",)


def read(run):
    return run.per_rep_ms(run.trace.scope_s(*STAGES))
