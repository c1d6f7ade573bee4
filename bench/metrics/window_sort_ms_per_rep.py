"""Device time of the sort into windows per repetition (ms): the ops the
program traces under ``stars.windows`` (tiebreak draw, bit packing, the
sort and the slot scatter, core/stars.py ``_rep_window_grid``), read by
``bench/stages.py``."""

from bench import stages

STAGES = ("stars.windows",)
stages.install()


def read(run):
    st = stages.of(run)
    return stages.per_rep_ms(run, st.scope_s(*STAGES)) if st else None
