"""Host time of the builder's rounds per repetition (ms): the program's
``stars.round`` spans (bind lookup, dispatch and counter bookkeeping of
``GraphBuilder._run_rounds``) summed over the window."""

SPANS = ("stars.round",)


def read(run):
    return run.per_rep_ms(sum(map(run.trace.span_s, SPANS)))
