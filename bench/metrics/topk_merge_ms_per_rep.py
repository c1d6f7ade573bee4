"""Device time of the slab fold's top-k merge kernel per repetition (ms)
(kernels/topk_merge.py), matched by the names in ``NAMES``."""

NAMES = ("topk_merge",)


def read(run):
    t = run.trace.kernel_s(NAMES)
    return 1e3 * t / run.counts["reps"] if t > 0 else None
