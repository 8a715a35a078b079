"""The two readers of the broker's fail-over counters (`numLegsFailedOver`,
`numStaleRouteRetries` of an answer): the mean over the window's answered
queries, nothing where the program has no such field, and entries in
`BENCHMARK.json` that say what the modules say."""

import importlib
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.layer_metrics import _loss
from perfbench.manifest import load_manifest

ROOT = Path(__file__).resolve().parents[2]
READERS = {"failover_legs_per_query": "numLegsFailedOver", "stale_route_retries_per_query": "numStaleRouteRetries"}


def run_of(docs):
    good = [SimpleNamespace(sent=float(i), done=float(i) + 0.5, doc=d, error=None) for i, d in enumerate(docs)]
    return {"good": good, "samples": good, "trace": None, "seconds": 10.0}


@pytest.mark.parametrize("name,field", list(READERS.items()))
def test_the_reader_takes_the_mean_of_the_answers_own_field(name, field):
    read = importlib.import_module(f"perfbench.layer_metrics.{name}").read
    healthy = {"numServersQueried": 4, "numServersResponded": 4, field: 0}
    assert read(run_of([healthy] * 4)) == 0.0
    assert read(run_of([healthy, healthy, {**healthy, field: 1}, {**healthy, field: 2}])) == pytest.approx(0.75)
    # an answer of a program from before the counter is passed over; with none that has it, nothing is read
    old = {"numServersQueried": 4, "numServersResponded": 4}
    assert read(run_of([old, {**healthy, field: 1}])) == 1.0
    assert read(run_of([old, old])) is None
    assert read(run_of([])) is None


@pytest.mark.parametrize("name", list(READERS))
def test_the_manifest_entry_is_what_the_reader_says(name):
    mod = importlib.import_module(f"perfbench.layer_metrics.{name}")
    (entry,) = [m for m in load_manifest(ROOT)["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (_loss.LAYER_FAILOVER, "count", "query_p95_ms", "program_counter")
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE, mod.NEEDS_TRACE) == (_loss.LAYER_FAILOVER, "count", "query_p95_ms", "program_counter", False)
    assert entry["better"] == "lower" and entry["workloads"] == ["ssb4-serverloss-closed"]
