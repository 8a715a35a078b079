"""The per-layer metrics that read the program's phase ledger (`spanTimesMs`,
`counters`, `deviceWork` of a broker response) and the trace's `jit_seg_*`
modules: each on a synthetic `run`, the gap attribution on synthetic planes,
and the discipline that they came as new files and new entries only."""

import importlib
import json
import subprocess
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.manifest import load_manifest, metrics_of
from perfbench.tools import gap_attribution

ROOT = Path(__file__).resolve().parents[2]

NEW = {
    "broker_self_ms": ("program_counter", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "wire_ms": ("program_counter", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "wire_bytes_per_query": ("program_counter", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "server_host_ms": ("program_counter", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "server_device_wait_ms": ("program_counter", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "device_launches_per_query": ("device_trace", ["ssb-groupby-closed", "ssb-q1-rate", "tpch-q1q6-closed"]),
    "groupby_kernel_mxu_share": ("device_trace", ["ssb-groupby-closed", "tpch-q1q6-closed"]),
    "groupby_kernel_hbm_share": ("device_trace", ["ssb-groupby-closed", "tpch-q1q6-closed"]),
}


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


def sample(sent, done, doc):
    return SimpleNamespace(sent=sent, done=done, doc=doc, error=None)


def doc(request, scatter, execute, wait, req_bytes=400, rsp_bytes=600, work=None):
    return {
        "timeUsedMs": request,
        "spanTimesMs": {"broker.request": request, "broker.scatter": scatter, "server.execute": execute,
                        "server.device_wait": wait},
        "counters": {"wireRequestBytes": req_bytes, "wireResponseBytes": rsp_bytes},
        "deviceWork": work or {},
    }  # fmt: skip


def run_of(docs, trace=None, window=(0.0, 10.0)):
    good = [sample(float(i), float(i) + 0.5, d) for i, d in enumerate(docs)]
    return {"good": good, "samples": good, "trace": trace, "trace_window": window, "seconds": 10.0}


@pytest.mark.parametrize(
    "name,want",
    [
        ("broker_self_ms", 20.0),  # 120 - 100
        ("wire_ms", 10.0),  # 100 - 90
        ("server_host_ms", 65.0),  # 60, 65, 100
        ("server_device_wait_ms", 30.0),
        ("wire_bytes_per_query", 1000.0),
    ],
)
def test_a_span_reader_takes_the_median_over_the_answered_queries(name, want):
    docs = [doc(120.0, 100.0, 90.0, 30.0), doc(100.0, 90.0, 85.0, 20.0, 100, 200), doc(400.0, 300.0, 200.0, 100.0, 900, 900)]
    assert reader(name).read(run_of(docs)) == pytest.approx(want)
    # a response of a program without the ledger is passed over; with none that has it, nothing is read
    old = {"timeUsedMs": 5.0}
    assert reader(name).read(run_of(docs + [old, old])) is not None
    assert reader(name).read(run_of([old, old])) is None
    assert reader(name).read(run_of([])) is None


def test_a_span_reader_needs_both_of_its_spans():
    half = doc(120.0, 100.0, 90.0, 30.0)
    del half["spanTimesMs"]["server.execute"]
    assert reader("wire_ms").read(run_of([half])) is None
    assert reader("server_host_ms").read(run_of([half])) is None
    assert reader("broker_self_ms").read(run_of([half])) == pytest.approx(20.0)


# the two shares and the launches, on a trace and the responses' device work made by hand:
# program A ran 6 times in the trace at 2e9 flop and 1e6 bytes of the kernel a launch, B 4 times at
# 5e8 flop and 4e6 bytes, C (no group-by kernel) 20 times; the kernel's ops took 0.1 s + 0.1 s
WORK = {
    "seg_groupby_aaaaaaaa": {"launches": 3, "rows": 30, "kernels": {"ops.grouped_planes": {"calls": 3, "bytes": 3e6, "flops": 6e9}}},
    "seg_groupby_bbbbbbbb": {"launches": 2, "rows": 20, "kernels": {"ops.grouped_planes2": {"calls": 4, "bytes": 8e6, "flops": 1e9}}},
    "seg_agg_cccccccc": {"launches": 5, "rows": 50, "kernels": {}},
}  # fmt: skip
TRACE = {
    "window_s": 4.0, "busy_s": 1.0, "chips": [{"plane": "/device:TPU:0"}],
    "modules": [["jit_seg_groupby_aaaaaaaa(111)", 0.5, 6], ["jit_seg_groupby_bbbbbbbb(222)", 0.3, 4],
                ["jit_seg_agg_cccccccc(333)", 0.1, 20], ["jit_convert_element_type(9)", 0.01, 2]],
    "ops": [["_planes_impl.1", 0.1], ["ops_grouped_planes2_impl.3", 0.1], ["fusion.2", 0.5]],
}  # fmt: skip


def test_the_kernel_shares_are_the_traced_launches_work_over_the_kernels_seconds():
    docs = [doc(10.0, 9.0, 8.0, 7.0, work=WORK), doc(10.0, 9.0, 8.0, 7.0, work=WORK)]
    run = run_of(docs, TRACE)
    flops = 6 * 2e9 + 4 * 5e8  # 1.4e10 in 0.2 s = 7e10 flop/s of 1.97e14
    assert reader("groupby_kernel_mxu_share").read(run) == pytest.approx(100 * flops / 0.2 / 197e12)
    nbytes = 6 * 1e6 + 4 * 4e6  # 2.2e7 in 0.2 s = 1.1e8 B/s of 8.19e11
    assert reader("groupby_kernel_hbm_share").read(run) == pytest.approx(100 * nbytes / 0.2 / 819e9)
    # queries_in_trace: two queries, each wholly inside the window
    assert reader("device_launches_per_query").read(run) == pytest.approx((6 + 4 + 20) / 2)


@pytest.mark.parametrize("name", ["groupby_kernel_mxu_share", "groupby_kernel_hbm_share", "device_launches_per_query"])
def test_a_trace_reader_finds_nothing_in_an_old_trace_or_without_one(name):
    old_names = {**TRACE, "modules": [["jit_run(111)", 0.5, 6], ["jit_run(222)", 0.3, 4]]}
    docs = [doc(10.0, 9.0, 8.0, 7.0, work=WORK)]
    assert reader(name).read(run_of(docs, None)) is None
    assert reader(name).read(run_of(docs, old_names)) is None  # the parent's program under this PR's readers
    if name != "device_launches_per_query":
        assert reader(name).read(run_of([{"timeUsedMs": 1.0}], TRACE)) is None  # no deviceWork in the responses
        assert reader(name).read(run_of(docs, {**TRACE, "ops": [["fusion.2", 0.5]]})) is None  # no kernel op


def test_the_new_metrics_are_new_entries_at_the_end_and_new_files():
    manifest = load_manifest(ROOT)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW) :] == list(NEW)
    for m in manifest["per_layer"][-len(NEW) :]:
        mod = reader(m["name"])
        source, cells = NEW[m["name"]]
        assert (m["layer"], m["unit"], m["moves"], m["source"]) == (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
        assert m["source"] == source and m["workloads"] == cells and mod.NEEDS_TRACE == (source == "device_trace")
        assert m["better"] == ("higher" if m["name"].endswith("_share") else "lower")
    assert {m["name"] for m in metrics_of(manifest, "per_layer", "ssb-q1-rate")} >= set(NEW) - {
        "groupby_kernel_mxu_share", "groupby_kernel_hbm_share",
    }  # fmt: skip
    # the discipline of test_a_new_cell_is_new_files_and_new_entries_only: against the commit the
    # benchmark was accepted at, no file under perfbench/ is edited or deleted, only added
    base = "6ab0543489d32b1dfb0247f859e8f26c36271ce9"
    known = subprocess.run(["git", "cat-file", "-e", base], cwd=ROOT, capture_output=True).returncode == 0
    if not known:
        pytest.skip("not a checkout with the benchmark's commit in it")
    diff = subprocess.run(["git", "diff", "--name-status", base, "--", "perfbench", "BENCHMARK.json"],
                          cwd=ROOT, capture_output=True, text=True, check=True).stdout.split("\n")  # fmt: skip
    touched = [ln.split("\t") for ln in diff if ln.strip()]
    assert [t for t in touched if t[0] != "A" and t[1] != "BENCHMARK.json"] == []
    old = json.loads(subprocess.run(["git", "show", f"{base}:BENCHMARK.json"], cwd=ROOT, capture_output=True,
                                    text=True, check=True).stdout)  # fmt: skip
    assert {k: v for k, v in manifest.items() if k != "per_layer"} == {k: v for k, v in old.items() if k != "per_layer"}
    assert manifest["per_layer"][: len(old["per_layer"])] == old["per_layer"]


# -- perfbench/tools/gap_attribution.py -------------------------------------------------------


def test_gaps_are_named_by_the_innermost_server_span_that_covers_them():
    ms = 1e6
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("fusion", 0, 1 * ms), ("fusion", 11 * ms, 1 * ms), ("fusion", 32 * ms, 1 * ms), ("fusion", 33.5 * ms, 1 * ms),
            ("fusion", 50 * ms, 1 * ms)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "thread-1", "events": [("server.request", 0, 30 * ms), ("server.execute", 1 * ms, 25 * ms),
                                            ("server.dispatch", 2 * ms, 8 * ms), ("broker.reduce", 0, 50 * ms)]},
            {"name": "thread-2", "events": [("server.request", 40 * ms, 5 * ms)]},
        ]},
    ]  # fmt: skip
    r = gap_attribution.report(planes, min_ms=1.0)
    # gaps: 1-11 (dispatch owns 8 of 10 ms), 12-32 (execute 14, request 4, nobody 2), 34.5-50 (nobody 10.5, request 5);
    # 33-33.5 is under a millisecond and is left out
    assert r["gaps"] == 3 and r["host_spans"] == 4
    assert [(round(g["ms"], 1), g["name"]) for g in r["longest"]] == [
        (20.0, "server.execute"), (15.5, gap_attribution.NOBODY), (10.0, "server.dispatch"),
    ]  # fmt: skip
    by = r["idle_by_name_ms"]
    assert by["server.dispatch"] == pytest.approx(8.0) and by["server.execute"] == pytest.approx(1 + 1 + 14)
    assert by["server.request"] == pytest.approx(4 + 5) and by[gap_attribution.NOBODY] == pytest.approx(2 + 10.5)
    assert sum(by.values()) == pytest.approx(r["gaps_ms"]) and r["idle_ms"] == pytest.approx(46.0)


def test_a_trace_without_annotations_reads_as_nobodys():
    ms = 1e6
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [("f", 0, ms), ("f", 5 * ms, ms)]}]},
              {"name": "/host:CPU", "lines": [{"name": "t", "events": [("PjitFunction(run)", 0, 3 * ms)]}]}]  # fmt: skip
    r = gap_attribution.report(planes)
    assert r["host_spans"] == 0 and r["idle_by_name_ms"] == {gap_attribution.NOBODY: pytest.approx(4.0)}
