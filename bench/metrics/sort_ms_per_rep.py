"""Device time of HLO sort ops per repetition (ms): the sort into windows
(core/windows.py) and the slab fold's two sorts (graph/accumulator.py)."""

CLASSES = ("sort",)


def read(run):
    t = run.trace.class_s(*CLASSES)
    return 1e3 * t / run.counts["reps"] if t > 0 else None
