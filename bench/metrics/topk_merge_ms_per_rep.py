"""Device time of the slab fold's top-k merge kernel per repetition (ms)
(kernels/topk_merge.py), matched by the names in ``NAMES``."""

NAMES = ("topk_merge",)


def read(run):
    return run.per_rep_ms(run.trace.kernel_s(NAMES))
