"""The roofline arithmetic and the peaks table."""

import pytest

from bench import roofline


def test_window_score_least_time_at_random1b_shapes():
    peak = roofline.peaks("TPU v5 lite")
    nw = roofline.n_windows(1 << 20, 250)
    assert nw == 4196                       # every repetition's window rows
    got = roofline.window_score_least_s(nw, 25, 250, 100, peak)
    assert got["flops"] == 2 * 4196 * 25 * 250 * 100
    assert got["bytes"] == 4 * 4196 * 275 * 100
    assert got["compute_s"] == pytest.approx(got["flops"] / 197e12)
    assert got["memory_s"] == pytest.approx(got["bytes"] / 819e9)
    assert got["bound"] == "memory" and got["least_s"] == got["memory_s"]


def test_wide_rows_stay_memory_bound():
    got = roofline.window_score_least_s(
        roofline.n_windows(70_000, 250), 25, 250, 784,
        roofline.peaks("TPU v5 lite"))
    # 2 s W d / (4 (W + s) d) = 11.4 FLOP per byte, under v5e's 240
    assert got["bound"] == "memory"


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_one_chip_counts_its_share_of_the_windows():
    """On a mesh each chip scores its ceil(nw / chips) of the window rows;
    at one chip the count, and every number, is the whole grid's."""
    peak = roofline.peaks("TPU v5 lite")
    nw = roofline.n_windows(1 << 20, 250)
    whole = roofline.window_score_least_s(nw, 25, 250, 100, peak)
    assert roofline.window_score_least_s(nw, 25, 250, 100, peak,
                                         chips=1) == whole
    share = roofline.window_score_least_s(nw, 25, 250, 100, peak, chips=4)
    assert share["windows"] == -(-4196 // 4) == 1049
    assert share == roofline.window_score_least_s(1049, 25, 250, 100, peak)
    assert share["least_s"] < whole["least_s"] / 3.99
