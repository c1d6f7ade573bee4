"""Device time of the scoring stage per repetition (ms): the ops the
program traces under ``stars.score`` (leader draw, feature gathers, the
scoring kernel and the lane broadcast, core/stars.py ``_score_windows``),
read by ``bench/stages.py``."""

from bench import stages

STAGES = ("stars.score",)
stages.install()


def read(run):
    st = stages.of(run)
    return stages.per_rep_ms(run, st.scope_s(*STAGES)) if st else None
