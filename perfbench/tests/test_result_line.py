"""The result line's validator against good and bad lines."""

import copy
import json

import pytest

from perfbench import result_line
from perfbench.manifest import load_manifest

MANIFEST = load_manifest()
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 7_500_000_000}
E2E = {"query_p50_ms": 80.5, "query_p95_ms": 200.25, "queries_per_s": 9.5, "setup_s": 61.0}
LAYER = {
    "generator_late_ms": 0.4, "frontend_overhead_ms": 2.0, "broker_time_ms": 70.0, "compiles_in_window": 0.0,
    "device_busy_ms_per_query": 20.0, "device_idle_share": 75.0, "groupby_kernel_share": 60.0,
}  # fmt: skip


def plain(workload="ssb-groupby-closed"):
    return result_line.build(MANIFEST, workload, False, correct=True, attempted=100, failed=0, values=E2E, device=dict(DEVICE))


def traced(workload="ssb-groupby-closed", **device):
    dev = {**DEVICE, "window_s": 4.0, "busy_s": 1.0, **device}
    return result_line.build(
        MANIFEST, workload, True, correct=True, attempted=100, failed=0, values={**E2E, **LAYER}, device=dev,
        breakdown={"device_ops": [["fusion.1", 0.5]], "idle_gaps": [["unattributed", 0.01]]},
    )  # fmt: skip


@pytest.mark.parametrize("workload", [w["name"] for w in MANIFEST["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_good_lines_pass_and_are_strict_json(workload, trace):
    line = traced(workload) if trace else plain(workload)
    text = result_line.validate(line, MANIFEST, workload, trace, chips=1)
    back = json.loads(text)
    assert set(back) >= {"correct", "attempted", "failed", "metrics", "device"}
    section = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in MANIFEST[section] if workload in m.get("workloads", [workload])}
    assert set(back["metrics"]) == listed
    assert "\n" not in text


def test_a_plain_line_has_only_end_to_end_metrics_and_a_traced_one_only_per_layer():
    assert "device_idle_share" not in plain()["metrics"]
    assert "query_p50_ms" not in traced()["metrics"]
    # only the group-by cell reports a completed rate; the rate cell has no group-by kernel share
    assert "queries_per_s" not in plain("ssb-q1-rate")["metrics"]
    assert "queries_per_s" not in plain("tpch-q1q6-closed")["metrics"]
    assert "groupby_kernel_share" not in traced("ssb-q1-rate")["metrics"]


def _drop(key):
    def f(line):
        del line[key]
    return f


def _set(path, value):
    def f(line):
        node = line
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = value
    return f


BAD_PLAIN = {
    "missing correct": _drop("correct"),
    "missing attempted": _drop("attempted"),
    "missing failed": _drop("failed"),
    "missing metrics": _drop("metrics"),
    "missing device": _drop("device"),
    "NaN value": _set(["metrics", "query_p50_ms", "value"], float("nan")),
    "infinite value": _set(["metrics", "query_p95_ms", "value"], float("inf")),
    "value is a string": _set(["metrics", "setup_s", "value"], "61"),
    "wrong unit": _set(["metrics", "queries_per_s", "unit"], "qps"),
    "metric of the traced run": _set(["metrics", "device_idle_share"], {"value": 1.0, "unit": "%"}),
    "end-to-end metric missing": lambda line: line["metrics"].pop("setup_s"),
    "correct is a string": _set(["correct"], "true"),
    "failed above attempted": _set(["failed"], 101),
    "no memory peak": lambda line: line["device"].pop("memory_peak_bytes"),
    "memory peak of 0": _set(["device", "memory_peak_bytes"], 0),
    "wrong chip count": _set(["device", "count"], 4),
}

BAD_TRACED = {
    "busy_s of 0": _set(["device", "busy_s"], 0.0),
    "busy_s above window_s": _set(["device", "busy_s"], 4.5),
    "busy_s missing": lambda line: line["device"].pop("busy_s"),
    "window_s missing": lambda line: line["device"].pop("window_s"),
    "busy_s NaN": _set(["device", "busy_s"], float("nan")),
    "an end-to-end metric in a traced line": _set(["metrics", "query_p50_ms"], {"value": 1.0, "unit": "ms"}),
    "breakdown with 11 ops": _set(["breakdown", "device_ops"], [[f"op{i}", 0.1] for i in range(11)]),
    "breakdown with a NaN": _set(["breakdown", "idle_gaps"], [["unattributed", float("nan")]]),
}


@pytest.mark.parametrize("name", sorted(BAD_PLAIN))
def test_bad_plain_lines_are_refused(name):
    line = copy.deepcopy(plain())
    BAD_PLAIN[name](line)
    with pytest.raises(result_line.InvalidLine):
        result_line.validate(line, MANIFEST, "ssb-groupby-closed", False, chips=1)


@pytest.mark.parametrize("name", sorted(BAD_TRACED))
def test_bad_traced_lines_are_refused(name):
    line = copy.deepcopy(traced())
    BAD_TRACED[name](line)
    with pytest.raises(result_line.InvalidLine):
        result_line.validate(line, MANIFEST, "ssb-groupby-closed", True, chips=1)


def test_a_metric_of_another_cell_is_refused():
    line = copy.deepcopy(plain("ssb-q1-rate"))
    line["metrics"]["queries_per_s"] = {"value": 8.0, "unit": "queries/s"}  # a closed-loop cell's metric
    with pytest.raises(result_line.InvalidLine, match="queries_per_s"):
        result_line.validate(line, MANIFEST, "ssb-q1-rate", False, chips=1)
    line = copy.deepcopy(traced("ssb-q1-rate"))
    line["metrics"]["groupby_kernel_share"] = {"value": 8.0, "unit": "%"}  # the rate cell bypasses that kernel
    with pytest.raises(result_line.InvalidLine, match="groupby_kernel_share"):
        result_line.validate(line, MANIFEST, "ssb-q1-rate", True, chips=1)


def test_a_reader_that_found_nothing_leaves_its_metric_out_and_the_line_stands():
    values = {k: v for k, v in {**E2E, **LAYER}.items() if k != "groupby_kernel_share"}
    line = result_line.build(
        MANIFEST, "ssb-groupby-closed", True, correct=True, attempted=5, failed=0, values=values,
        device={**DEVICE, "window_s": 4.0, "busy_s": 3.9},
    )  # fmt: skip
    assert "groupby_kernel_share" not in line["metrics"]
    result_line.validate(line, MANIFEST, "ssb-groupby-closed", True, chips=1)
