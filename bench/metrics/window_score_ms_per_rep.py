"""Device time of the window scoring kernel per repetition (ms)
(kernels/window_score.py), matched by the names in ``NAMES``."""

NAMES = ("window_score",)


def read(run):
    return run.per_rep_ms(run.trace.kernel_s(NAMES))
