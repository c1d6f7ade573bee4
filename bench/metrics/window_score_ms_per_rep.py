"""Device time of the window scoring kernel per repetition (ms)
(kernels/window_score.py), matched by the names in ``NAMES``."""

NAMES = ("window_score",)


def read(run):
    t = run.trace.kernel_s(NAMES)
    return 1e3 * t / run.counts["reps"] if t > 0 else None
