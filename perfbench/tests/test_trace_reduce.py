"""trace_reduce: the union-not-sum rule on planes made by hand, and the
recorded v5e trace (perfbench/tests/fixtures/v5e_window.xplane.pb)."""

from pathlib import Path

import pytest

from perfbench import trace_reduce

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_window.xplane.pb"


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v} for k, v in lines.items()]}


def test_union_length_merges_overlaps_and_skips_gaps():
    assert trace_reduce.union_length([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace_reduce.union_length([]) == 0
    assert trace_reduce.union_length([(3, 4)]) == 1


def test_busy_is_the_union_of_the_op_intervals_not_a_sum_over_lines():
    # a module of 1 s spans two ops of 0.4 s; a second line repeats the time:
    # summed over lines that would be 1.8 s of "busy" in a 1.5 s window
    p = plane(
        "/device:TPU:0",
        XLA_Modules=[("jit_fused", 0, 1_000_000_000)],
        XLA_Ops=[("fusion.1", 0, 400_000_000), ("custom-call.2", 500_000_000, 400_000_000)],
        Steps=[("0", 0, 1_500_000_000)],
    )
    out = trace_reduce.reduce_planes([p, plane("/host:CPU", threads=[("x", 0, 5)])], chips=1)
    assert out["window_s"] == pytest.approx(1.5)
    assert out["busy_s"] == pytest.approx(0.8)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["ops"][0][1] == pytest.approx(0.4)
    assert out["modules"] == [["jit_fused", pytest.approx(1.0), 1]]
    assert out["idle_gaps_s"][0] == pytest.approx(0.6)  # after the last op, to the window's end


def test_overlapping_ops_of_one_line_do_not_count_twice():
    p = plane("/device:TPU:0", XLA_Ops=[("a", 0, 100), ("b", 50, 100), ("c", 500, 100)])
    out = trace_reduce.reduce_planes([p])
    assert out["busy_s"] == pytest.approx(250e-9)
    assert out["window_s"] == pytest.approx(600e-9)


def test_two_chips_are_averaged_and_the_chip_count_is_checked():
    a = plane("/device:TPU:0", XLA_Ops=[("a", 0, 100), ("a", 900, 100)])
    b = plane("/device:TPU:1", XLA_Ops=[("a", 0, 500), ("a", 500, 500)])
    out = trace_reduce.reduce_planes([a, b], chips=2)
    assert out["busy_s"] == pytest.approx((200 + 1000) / 2 * 1e-9)
    with pytest.raises(ValueError, match="1 chips"):
        trace_reduce.reduce_planes([a, b], chips=1)


def test_a_trace_without_a_device_plane_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce_planes([plane("/host:CPU", threads=[("x", 0, 5)])])


def test_the_recorded_v5e_trace_reduces_to_a_busy_time_inside_its_window():
    out = trace_reduce.reduce_planes(trace_reduce.read_planes(FIXTURE), chips=1)
    assert out["chips"][0]["plane"] == "/device:TPU:0"
    assert 0 < out["busy_s"] <= out["window_s"]
    lines = {p["name"]: p["lines"] for p in out["planes"]}["/device:TPU:0"]
    assert lines["XLA Ops"] > 0 and lines["XLA Modules"] > 0
    # a sum over the lines would pass the window; the union does not
    summed = sum(sec for _, sec in out["ops"]) + sum(sec for _, sec, _ in out["modules"])
    assert summed > out["busy_s"]
