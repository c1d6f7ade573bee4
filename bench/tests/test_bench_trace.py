"""The reduction from a profiler trace to device times."""

import dataclasses
import os

import pytest

from bench import trace

DEV = "/device:TPU:0"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "random1b_rep_trace.json")


def _op(hlo, start, end):
    return trace.Event(DEV, trace.OPS_LINE, hlo, start, end - start)


def _host(name, start, end):
    return trace.Event("/host:CPU", "python", name, start, end - start)


EVENTS = [
    _host(trace.WINDOW_SPAN, 0, 1000),
    _host("bench.add_reps", 0, 90), _host("bench.block", 90, 980),
    _host("bench.counters", 980, 1000),
    _op("%sort.1 = (s32[8]{0:T(1024)}, f32[8]{0}) sort(s32[8]{0} %a)",
        100, 300),
    _op("%fusion.2 = s32[8]{0:T(1024)S(1)} fusion(s32[8]{0} %sort.1)",
        250, 400),
    _op("%window_score.1 = (f32[2,3]{1,0:T(8,128)}) custom-call(%b)",
        700, 750),
    _op("%get-tuple-element.3 = f32[2,3] get-tuple-element(%window_score.1)",
        750, 760),
    _op("%topk_merge.1 = (s32[4,2], f32[4,2]) custom-call(%c, %d)", 800, 900),
    _op("%fusion.5 = f32[8] fusion(f32[8] %e)", 950, 1050),   # cut at the end
    _op("%sort.6 = s32[8] sort(s32[8] %f)", 1100, 1200),      # after the window
    trace.Event(DEV, "XLA Modules", "jit_round_step", 100, 900),
]


def test_hlo_text_names_parse():
    assert trace.parse_op(EVENTS[4].name) == ("sort.1", "sort")
    assert trace.parse_op(EVENTS[6].name) == ("window_score.1", "custom-call")
    assert trace.parse_op("sort.3") == ("sort.3", "sort")
    assert trace.base_name("broadcast.2295.clone.1") == "broadcast"


def test_busy_time_is_the_union_of_op_intervals():
    tr = trace.Trace(EVENTS)
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.busy_s == pytest.approx((300 + 60 + 100 + 50) * 1e-9)


def test_op_classes_and_kernels_by_name():
    tr = trace.Trace(EVENTS)
    assert tr.class_s("sort") == pytest.approx(200e-9)
    # a kernel's consumers name it as an operand, and do not count
    assert tr.kernel_s(["window_score"]) == pytest.approx(50e-9)
    assert tr.kernel_s(["topk_merge"]) == pytest.approx(100e-9)
    assert tr.top_ops(2) == [["sort.1", pytest.approx(200e-9)],
                             ["fusion.2", pytest.approx(150e-9)]]


def test_idle_gaps_name_the_host_span():
    gaps = trace.Trace(EVENTS).idle_gaps(10)
    assert gaps[0] == ["bench.block", pytest.approx(300e-9)]
    assert gaps[1] == ["bench.add_reps", pytest.approx(100e-9)]
    assert [g[0] for g in gaps].count("bench.block") == 3
    assert sum(g[1] for g in gaps) == pytest.approx(490e-9)


def test_recorded_chip_trace():
    """One random1b-build repetition traced on a v5e (the device ops and
    the harness's spans, each op's HLO text cut to its name and opcode)."""
    tr = trace.Trace(trace.load_json(RECORDED))
    assert tr.window_s == pytest.approx(22.789555845)
    assert tr.busy_s == pytest.approx(22.785784076)
    assert tr.class_s("sort") == pytest.approx(0.916050955)
    assert tr.kernel_s(["window_score"]) == pytest.approx(0.001467841)
    assert tr.kernel_s(["topk_merge"]) == pytest.approx(20.29609766)
    assert tr.top_ops(1) == [["topk_merge.1", pytest.approx(20.29609766)]]
    assert tr.idle_gaps(1)[0][0] == "bench.block"


def test_any_bench_span_labels_a_gap():
    events = [_host(trace.WINDOW_SPAN, 0, 100), _host("bench.query", 0, 40),
              _op("%sort.1 = s32[8] sort(s32[8] %a)", 40, 100)]
    assert trace.Trace(events).idle_gaps(10) == [
        ["bench.query", pytest.approx(40e-9)]]


def _on(device, events):
    return [dataclasses.replace(e, plane=device)
            if e.plane == DEV else e for e in events]


def test_the_busiest_device_sets_the_pace():
    """Each device's busy time is its own union; every device-time reading
    comes from the busiest device, here TPU:1, whose gaps fall elsewhere."""
    other = _on("/device:TPU:1", [
        _op("%sort.1 = s32[8] sort(s32[8] %a)", 0, 450),
        _op("%topk_merge.1 = (s32[4,2], f32[4,2]) custom-call(%c)", 500, 880),
        _op("%window_score.1 = (f32[2,3]) custom-call(%b)", 880, 970)])
    tr = trace.Trace(EVENTS + other)
    assert tr.devices == [DEV, "/device:TPU:1"]
    assert tr.busy_s_by_device == {DEV: pytest.approx(510e-9),
                                   "/device:TPU:1": pytest.approx(920e-9)}
    assert tr.device == "/device:TPU:1"
    assert tr.busy_s == pytest.approx(920e-9)
    assert tr.class_s("sort") == pytest.approx(450e-9)
    assert tr.kernel_s(["topk_merge"]) == pytest.approx(380e-9)
    assert tr.kernel_s(["window_score"]) == pytest.approx(90e-9)
    assert tr.top_ops(1) == [["sort.1", pytest.approx(450e-9)]]
    assert tr.idle_gaps(10) == [["bench.block", pytest.approx(50e-9)],
                                ["bench.counters", pytest.approx(30e-9)]]


def test_a_tie_goes_to_the_lowest_index():
    """Devices list in index order (TPU:2 before TPU:10); of two equally
    busy devices the lower index is read."""
    events = [_host(trace.WINDOW_SPAN, 0, 100)]
    for device, start in (("/device:TPU:10", 0), ("/device:TPU:2", 50)):
        events += _on(device, [_op("%sort.1 = s32[8] sort(s32[8] %a)",
                                   start, start + 40)])
    tr = trace.Trace(events)
    assert tr.devices == ["/device:TPU:2", "/device:TPU:10"]
    assert tr.device == "/device:TPU:2"
    assert tr.idle_gaps(10) == [["host", pytest.approx(50e-9)],
                                ["host", pytest.approx(10e-9)]]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.Trace(EVENTS[1:])
