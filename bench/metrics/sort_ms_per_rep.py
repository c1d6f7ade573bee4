"""Device time of HLO sort ops per repetition (ms): the sort into windows
(core/windows.py) and the slab fold's two sorts (graph/accumulator.py)."""

CLASSES = ("sort",)


def read(run):
    return run.per_rep_ms(run.trace.class_s(*CLASSES))
