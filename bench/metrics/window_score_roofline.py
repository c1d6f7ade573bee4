"""The window scoring kernel's share of its roofline (%): the least time
in which one chip can score its share of the leaders against their
windows (see ``bench/roofline.py``), over the kernel's device time on the
trace's device, per repetition."""

from bench import roofline

NAMES = ("window_score",)


def read(run):
    t = run.trace.kernel_s(NAMES)
    if t <= 0:
        return None
    c, reps = run.cell.config, run.counts["reps"]
    least = roofline.window_score_least_s(
        roofline.n_windows(c["n"], c["window"]), c["leaders"], c["window"],
        c["d"], roofline.peaks(run.device_kind), chips=run.cell.chips)
    run.log(f"window_score roofline: {least['bound']}-bound, "
            f"windows={least['windows']} of {run.cell.chips} chip(s), "
            f"least_s={least['least_s']:.6g} flops={least['flops']:.6g} "
            f"bytes={least['bytes']:.6g} kernel_s_per_rep={t / reps:.6g}")
    return 100.0 * least["least_s"] * reps / t
