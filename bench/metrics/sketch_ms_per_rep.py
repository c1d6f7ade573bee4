"""Device time of the sketch stage per repetition (ms): the ops the
program traces under ``stars.sketch`` (the repetition's draws and the hash
projection, core/stars.py ``_rep_candidates``), read by
``bench/stages.py``."""

from bench import stages

STAGES = ("stars.sketch",)
stages.install()


def read(run):
    st = stages.of(run)
    return stages.per_rep_ms(run, st.scope_s(*STAGES)) if st else None
