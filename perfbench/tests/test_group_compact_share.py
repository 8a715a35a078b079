"""PR 45's one per-layer metric, `group_compact_served_share`: its entry in
`BENCHMARK.json` (the last of `per_layer`, the cell `ssb-citygroups-closed`
alone) and what its reader makes of the answers' counters."""

import importlib
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.manifest import load_cell, load_manifest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = load_manifest(ROOT)
NAME, CELL = "group_compact_served_share", "ssb-citygroups-closed"
READER = importlib.import_module(f"perfbench.layer_metrics.{NAME}")


def answer(compact: int | None, again: int = 0, launches: int = 15) -> SimpleNamespace:
    counters = {"segmentsDispatched": launches, "hostToDeviceTransfers": launches}
    if compact is not None:
        counters.update(groupCompactSegments=compact, groupCompactFallbacks=again)
    return SimpleNamespace(template="q3.2", doc={"spanTimesMs": {"server.execute": 40.0}, "counters": counters}, error=None, sent=0.0, done=1.0)


def run_of(good):
    return {"good": good, "samples": good, "trace": None, "trace_window": (0.0, 1.0), "seconds": 10.0, "config": load_cell(MANIFEST, CELL, ROOT)["config"]}


def test_the_entry_is_the_last_of_per_layer_and_lists_the_one_cell():
    entry = MANIFEST["per_layer"][-1]
    assert entry["name"] == NAME and entry["workloads"] == [CELL] and entry["better"] == "higher"
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (READER.LAYER, READER.UNIT, READER.MOVES, READER.SOURCE)
    assert READER.NEEDS_TRACE is False and entry["layer"] in {m["layer"] for m in MANIFEST["per_layer"][:-1]}


def test_the_reader_reads_the_counters_off_the_answers():
    assert READER.read(run_of([answer(15), answer(15)])) == pytest.approx(100.0)
    # one overflow in a query: fourteen served of sixteen launched
    assert READER.read(run_of([answer(15, again=1, launches=16)])) == pytest.approx(100.0 * 14 / 16)
    # a query the planner left dense pulls the share down by its launches
    assert READER.read(run_of([answer(15), answer(0)])) == pytest.approx(50.0)
    assert READER.read(run_of([answer(0)])) == 0.0


def test_a_program_without_the_counters_gives_nothing_to_read():
    assert READER.read(run_of([answer(None), answer(None)])) is None
    assert READER.read(run_of([])) is None
    assert READER.read(run_of([SimpleNamespace(template="q3.2", doc={"exceptions": [{"message": "x"}]}, error=None)])) is None
    assert READER.read(run_of([answer(15), answer(None)])) == pytest.approx(100.0)  # what can be read is read
