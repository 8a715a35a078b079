"""`reduce_row_stages_per_query` (PR 39): the mean of the answers' own
`reduceRowStages`, nothing of a program without the counter, and its entry in
`BENCHMARK.json`: there once, as the reader says, wherever in `per_layer` it
stands, with PR 37's eleven still in their order."""

from pathlib import Path

import pytest

from perfbench.manifest import load_manifest, metrics_of
from perfbench.tests.test_layer_metrics_inside import INSIDE, PARENT, answer, reader, run_of

ROOT = Path(__file__).resolve().parents[2]
NAME = "reduce_row_stages_per_query"
GROUPING_CELLS = [
    "ssb-groupby-closed", "tpch-q1q6-closed", "ssb-citygroups-closed", "ssb4-groupby-closed", "ssb4-serverloss-closed",
    "tsbs-hosthour-closed",
]  # fmt: skip


def counted(stages: int) -> dict:
    doc = answer(1.0)
    doc["counters"]["reduceRowStages"] = stages
    return doc


def test_the_reader_takes_the_mean_over_the_answers_that_carry_the_counter():
    assert reader(NAME).read(run_of([counted(0), counted(0), counted(0)])) == 0.0
    assert reader(NAME).read(run_of([counted(2), counted(0), counted(1), counted(0)])) == pytest.approx(0.75)
    # a program without the counter beside one with it is passed over; alone, it gives nothing to read
    assert reader(NAME).read(run_of([counted(3), PARENT, answer(1.0)])) == pytest.approx(3.0)
    assert reader(NAME).read(run_of([PARENT, answer(1.0)])) is None
    assert reader(NAME).read(run_of([{"exceptions": [{"message": "no ledger at all"}]}])) is None
    assert reader(NAME).read(run_of([])) is None
    assert reader(NAME).NEEDS_TRACE is False


def test_the_entry_is_there_once_and_is_what_the_reader_says():
    manifest = load_manifest(ROOT)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.count(NAME) == 1 and [n for n in names if n in INSIDE] == list(INSIDE)
    m = manifest["per_layer"][names.index(NAME)]
    mod = reader(NAME)
    assert (m["layer"], m["unit"], m["moves"], m["source"]) == (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
    assert m["source"] == "program_counter" and m["better"] == "lower" and m["workloads"] == GROUPING_CELLS
    # a layer the benchmark already names, letter for letter
    assert m["layer"] in {e["layer"] for e in manifest["per_layer"] if e["name"] != NAME}
    # every cell that groups reports it; the one that does not, does not
    for w in manifest["workloads"]:
        listed = NAME in {e["name"] for e in metrics_of(manifest, "per_layer", w["name"])}
        assert listed == (w["name"] != "ssb-q1-rate")
