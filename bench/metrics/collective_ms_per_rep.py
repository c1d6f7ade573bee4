"""Device time of the collectives per repetition (ms), on the paced chip:
the ops whose HLO opcode is a collective, synchronous or the start and
end of an asynchronous one.  A chip that waits inside a collective for a
peer is busy in that op, so this is the exchanges' exposed time: the
sample sort's all_to_all, all_gather and reduce-scatter, the feature
fetch's two all_to_alls and the edge emit's one."""

COLLECTIVES = ("all-to-all", "reduce-scatter", "all-gather", "all-reduce",
               "collective-permute")
CLASSES = COLLECTIVES + tuple(f"{c}-{part}" for c in COLLECTIVES
                              for part in ("start", "done"))


def read(run):
    return run.per_rep_ms(run.trace.class_s(*CLASSES))
