"""Host time of the builder's rounds per repetition (ms): the program's
``stars.round`` spans (bind lookup, dispatch and counter bookkeeping of
``GraphBuilder._run_rounds``) summed over the window, read by
``bench/stages.py``."""

from bench import stages

SPANS = ("stars.round",)
stages.install()


def read(run):
    st = stages.of(run)
    return (stages.per_rep_ms(run, sum(map(st.span_s, SPANS))) if st
            else None)
