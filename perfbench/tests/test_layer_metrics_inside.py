"""The per-layer metrics that read inside the widest spans (PR 37): the stages
of `broker.reduce`, the answer's encoding, the gather on the clock and on the
CPU, the server's host work on the CPU, the launches, and first stagings —
each on a synthetic `run`, on an answer of a program without its spans, and
against its entry in `BENCHMARK.json`; and the gap attribution on a staging
nested in a dispatch."""

import importlib
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.manifest import load_manifest
from perfbench.tools import gap_attribution

ROOT = Path(__file__).resolve().parents[2]
GATHER_CELLS = ["ssb-groupby-closed", "ssb4-groupby-closed", "ssb4-serverloss-closed", "tsbs-hosthour-closed"]
STAGING_CELLS = ["tsbs-hosthour-closed", "ssb4-serverloss-closed"]
# since PR 40's second hand-in `tsbs-hosthour-closed` stages its table in set-up (`warmup.statements`):
# its windows count 0 first touches and have none to time
STAGE_TIMED_CELLS = ["ssb4-serverloss-closed"]
ALL_CELLS = [w["name"] for w in load_manifest(ROOT)["workloads"]]

# name -> (what the three answers below must read, the cells that list it)
INSIDE = {
    "broker_reduce_merge_ms": (30.0, GATHER_CELLS),
    "broker_reduce_rows_ms": (50.0 + 8.0, GATHER_CELLS),
    "broker_reduce_order_ms": (12.0, GATHER_CELLS),
    "broker_encode_ms": (9.0, ALL_CELLS[:7]),
    "broker_gather_clock_ms": (6.0 + 100.0, GATHER_CELLS),
    # the two on the thread clock: a mean over the answers (scales 0.5, 1, 3)
    "broker_gather_cpu_ms": ((20.0 + 95.0) * 1.5, GATHER_CELLS),
    # ... of the answers that staged nothing: the middle one alone
    "server_host_cpu_ms": (10.0 + 2.0, [c for c in ALL_CELLS[:7] if c not in ("ssb-q1-rate", "tpch-q1q6-closed")]),
    "server_launch_ms": (7.0, ALL_CELLS[:7]),
    "segments_staged_in_window": (3.0, STAGING_CELLS),  # a sum over the window, not a median: 2 + 0 + 1
    "segment_stage_ms": ((800.0 + 300.0) / 2, STAGE_TIMED_CELLS),  # ms a query that staged: the median of the two
    "broker_result_ms": (4.0, ALL_CELLS[:7]),
}


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


def answer(scale: float, staged: int = 0, stage_ms: float | None = None) -> dict:
    """An answer whose every span is `scale` times the middle answer's."""
    times = {
        "broker.request": 400.0, "broker.scatter": 250.0, "broker.reduce": 100.0, "broker.reduce.merge": 30.0,
        "broker.reduce.rows": 50.0, "broker.reduce.order": 12.0, "broker.reduce.project": 8.0, "broker.http.encode": 9.0,
        "broker.scatter.tail": 6.0, "broker.wire.decode": 80.0, "server.execute": 200.0, "server.device_wait": 120.0,
        "server.plan": 1.5, "server.dispatch_all": 41.0, "server.dispatch": 40.0, "server.unpack": 2.5,
        "server.launch": 7.0, "broker.result": 4.0,
    }  # fmt: skip
    cpu = {"broker.wire.decode": 20.0, "broker.reduce": 95.0, "server.dispatch_all": 10.0, "server.unpack": 2.0}
    doc = {
        "timeUsedMs": 400.0 * scale,
        "spanTimesMs": {n: v * scale for n, v in times.items()},
        "spanCpuMs": {n: v * scale for n, v in cpu.items()},
        "counters": {"segmentsStaged": staged},
    }
    if stage_ms is not None:
        doc["spanTimesMs"]["server.stage"] = stage_ms
    return doc


def run_of(docs):
    good = [SimpleNamespace(sent=float(i), done=float(i) + 0.5, doc=d, error=None) for i, d in enumerate(docs)]
    return {"good": good, "samples": good, "trace": None, "trace_window": None, "seconds": 10.0}


#: what the parent of PR 37 answers: the ledger's three keys, none of the new spans, no second clock
PARENT = {
    "timeUsedMs": 5.0,
    "spanTimesMs": {"broker.request": 5.0, "broker.scatter": 4.0, "broker.reduce": 0.5, "broker.wire.decode": 0.2,
                    "server.execute": 3.0, "server.dispatch": 1.0, "server.plan": 0.1, "server.unpack": 0.1},
    "spanSelfMs": {},
    "counters": {"wireRequestBytes": 400, "hostToDeviceTransfers": 3},
}  # fmt: skip


@pytest.mark.parametrize("name", list(INSIDE))
def test_a_reader_of_the_inside_reads_its_spans_and_nothing_of_a_program_without_them(name):
    docs = [answer(0.5, staged=2, stage_ms=800.0), answer(1.0), answer(3.0, staged=1, stage_ms=300.0)]
    assert reader(name).read(run_of(docs)) == pytest.approx(INSIDE[name][0])
    # the parent's answers beside them are passed over; with none but the parent's, nothing is read
    assert reader(name).read(run_of(docs + [PARENT, PARENT])) == pytest.approx(INSIDE[name][0])
    assert reader(name).read(run_of([PARENT, PARENT])) is None
    assert reader(name).read(run_of([{"exceptions": [{"message": "no ledger at all"}]}])) is None
    assert reader(name).read(run_of([])) is None
    assert reader(name).NEEDS_TRACE is False


@pytest.mark.parametrize("name", list(INSIDE))
def test_an_entry_of_the_inside_is_what_its_reader_says(name):
    (m,) = [m for m in load_manifest(ROOT)["per_layer"] if m["name"] == name]
    mod = reader(name)
    assert (m["layer"], m["unit"], m["moves"], m["source"]) == (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
    assert m["workloads"] == INSIDE[name][1] and m["better"] == "lower"
    assert m["source"] == ("program_counter" if name == "segments_staged_in_window" else "program_span")
    # a layer the benchmark already names, letter for letter
    assert m["layer"] in {e["layer"] for e in load_manifest(ROOT)["per_layer"] if e["name"] not in INSIDE}


def test_the_entries_of_the_inside_stand_together_and_in_order():
    """A new entry goes at the end: PR 39's `reduce_row_stages_per_query` follows the eleven."""
    names = [m["name"] for m in load_manifest(ROOT)["per_layer"]]
    first = names.index(next(iter(INSIDE)))
    assert names[first : first + len(INSIDE)] == list(INSIDE)
    assert names[first + len(INSIDE) :] == ["reduce_row_stages_per_query"]


def test_a_window_with_no_first_touch_stages_nothing_and_has_no_cost_to_read():
    run = run_of([answer(1.0), answer(2.0)])
    assert reader("segments_staged_in_window").read(run) == 0.0
    assert reader("segment_stage_ms").read(run) is None
    # a server that staged but was not the query's slowest leaves its count and no span: counted, not timed
    assert reader("segments_staged_in_window").read(run_of([answer(1.0, staged=4)])) == 4.0
    assert reader("segment_stage_ms").read(run_of([answer(1.0, staged=4)])) is None


def test_a_stage_only_some_queries_run_joins_the_sum_where_it_ran():
    with_having = answer(1.0)
    with_having["spanTimesMs"]["broker.reduce.having"] = 5.0
    assert reader("broker_reduce_rows_ms").read(run_of([with_having])) == pytest.approx(50.0 + 5.0 + 8.0)
    assert reader("broker_reduce_rows_ms").read(run_of([with_having, answer(1.0), answer(1.0)])) == pytest.approx(58.0)


def test_a_reading_of_the_thread_clock_is_a_mean_over_the_answers_not_a_median_of_ticks():
    """A span of 4 ms under a clock of 10 ms ticks reads 0 in six answers of ten and 10 in four."""
    docs = [answer(1.0) for _ in range(10)]
    for i, d in enumerate(docs):
        d["spanCpuMs"] = {"server.dispatch_all": 10.0 if i < 4 else 0.0, "server.unpack": 0.0}
    assert reader("server_host_cpu_ms").read(run_of(docs)) == pytest.approx(4.0)
    # an answer that met a first touch copied a segment inside its dispatch: that CPU time is the staging
    # metrics' to tell, and the mean leaves the answer out (all of them staged: nothing to read)
    docs[0]["spanTimesMs"]["server.stage"], docs[0]["spanCpuMs"]["server.dispatch_all"] = 900.0, 880.0
    assert reader("server_host_cpu_ms").read(run_of(docs)) == pytest.approx(30.0 / 9)
    assert reader("server_host_cpu_ms").read(run_of(docs[:1])) is None


def test_a_sum_needs_every_one_of_its_spans():
    doc = answer(1.0)
    del doc["spanTimesMs"]["broker.scatter.tail"], doc["spanCpuMs"]["server.unpack"]
    assert reader("broker_gather_clock_ms").read(run_of([doc])) is None
    assert reader("server_host_cpu_ms").read(run_of([doc])) is None
    assert reader("broker_gather_cpu_ms").read(run_of([doc])) == pytest.approx(115.0)


def test_a_gap_under_a_dispatch_is_named_by_the_staging_or_the_launch_inside_it():
    """`server.dispatch` > `server.stage`, then `server.launch`: the innermost `server.*` span owns the piece."""
    ms = 1e6
    spans = sorted([
        (0.0, 500 * ms, "server.request"), (1 * ms, 480 * ms, "server.execute"), (10 * ms, 460 * ms, "server.dispatch"),
        (12 * ms, 400 * ms, "server.stage"), (401 * ms, 455 * ms, "server.launch"),
    ])  # fmt: skip
    owned = gap_attribution.attribute((5 * ms, 470 * ms), spans)
    assert owned["server.stage"] == pytest.approx(388 * ms) and owned["server.launch"] == pytest.approx(54 * ms)
    assert owned["server.dispatch"] == pytest.approx((2 + 1 + 5) * ms) and owned["server.execute"] == pytest.approx((5 + 10) * ms)
    assert gap_attribution.name_gap((5 * ms, 470 * ms), spans)["name"] == "server.stage"
    # the transfer waited for inside the launch that follows a staging: the gap is the launch's
    assert gap_attribution.name_gap((400 * ms, 456 * ms), spans)["name"] == "server.launch"
