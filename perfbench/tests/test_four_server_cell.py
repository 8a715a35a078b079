"""`ssb4-groupby-closed`, the manifest's four-server cell (PR 27): its
configuration is `ssb-flat-1srv`'s deployment over four servers, and its traced
rehearsal reads four servers' traces.

`test_run_rehearsal.py` was written while no cell of the manifest had more
than one server: its `test_a_rehearsal_ends_in_a_valid_line[ssb4-groupby-closed-1]`
expects one trace file of every cell, and its
`test_a_four_server_cell_is_a_configuration_and_entries_only` writes the
configuration this PR brings over it. A PR that adds a cell may edit no file
the benchmark has, so both stand as they were and fail; what they held of the
cell is held here, for the next `benchmark` PR to fold back (PERF.md section 7)."""

import importlib
import json

import pytest

from perfbench import result_line
from perfbench.datasets import ssb_flat_4srv
from perfbench.manifest import load_cell, load_manifest, metrics_of
from perfbench.tests.test_run_rehearsal import ROOT, forget_seed, run_cell, trace_files

CELL = "ssb4-groupby-closed"
MANIFEST = load_manifest(ROOT)


def test_the_configuration_is_the_one_server_twins_over_four_servers():
    cfg = load_cell(MANIFEST, CELL, ROOT)["config"]
    twin = json.loads((ROOT / "perfbench" / "configs" / "ssb-flat-1srv.json").read_text())
    assert list(cfg) == list(twin)  # the same keys, in the same order
    same = ("segmentRows", "replication", "table", "broker", "cacheSeeds")
    assert {k: cfg[k] for k in same} == {k: twin[k] for k in same}
    mine, theirs = (importlib.import_module(f"perfbench.datasets.{c['dataset']}") for c in (cfg, twin))
    for name in ("TABLE", "SCHEMA", "TEMPLATES", "segment", "vocabs"):  # what the harness reads of a dataset: the twin's own objects
        assert getattr(mine, name) is getattr(theirs, name)
    assert cfg["guarantees"].keys() == twin["guarantees"].keys()
    assert cfg["guarantees"]["doubleSumRelTolerance"] == twin["guarantees"]["doubleSumRelTolerance"] == 0.0
    assert cfg["servers"] == cfg["chips"] == load_cell(MANIFEST, CELL, ROOT)["entry"]["chips"] == 4
    # an equal share of whole segments a server, and more of them than one chip holds (the twin's 15 are 7.2 GB of 16)
    assert cfg["rows"] % (cfg["segmentRows"] * cfg["servers"]) == 0
    assert cfg["rows"] // cfg["segmentRows"] > 2 * twin["rows"] // twin["segmentRows"]
    assert cfg["rehearsal"]["rows"] % (cfg["rehearsal"]["segmentRows"] * cfg["servers"]) == 0
    assert str(cfg["rows"] // 1_000_000) in cfg["guarantees"]["complete"].replace(",", "")
    assert list(cfg["reduced"]) == next(c for c in MANIFEST["configs"] if c["name"] == cfg["name"])["reduced"]


def test_a_program_that_cannot_load_the_table_inside_a_run_is_refused_at_once(tmp_path):
    """The parent of PR 27 ran this cell into the run's 360 s limit and was killed there; it has to fail cleanly."""
    path, _ = ssb_flat_4srv.LANDS_UPLOADS
    (tmp_path / path).parent.mkdir(parents=True)
    (tmp_path / path).write_text("class Controller:\n    def upload_segment(self, table, segment): ...\n")
    with pytest.raises(SystemExit, match="do not load inside a run"):
        ssb_flat_4srv.require_a_program_that_lands_uploads(tmp_path)
    ssb_flat_4srv.require_a_program_that_lands_uploads(ROOT)  # this program's controller lands an upload as sent


def test_the_cell_reports_what_its_one_server_twin_reports_and_the_skew():
    mine = {m["name"] for m in metrics_of(MANIFEST, "per_layer", CELL)}
    twin = {m["name"] for m in metrics_of(MANIFEST, "per_layer", "ssb-groupby-closed")}
    assert mine == twin | {"scatter_skew_ms"}
    assert {m["name"] for m in metrics_of(MANIFEST, "end_to_end", CELL)} == {m["name"] for m in metrics_of(MANIFEST, "end_to_end", "ssb-groupby-closed")}


def test_the_traced_rehearsal_reads_four_servers():
    forget_seed("ssb-flat-4srv", 2_700_000_071)  # a cold set-up: the uploads are part of what is held here
    rc, line, out = run_cell(ROOT, CELL, 2_700_000_071, 1)
    assert rc == 0, out[-3000:]
    result_line.validate(line, MANIFEST, CELL, True, chips=4)
    assert line["correct"] is True and line["failed"] == 0 and line["device"]["count"] == 4, out[-3000:]
    assert trace_files(out) == [f"server_{i}" for i in range(4)]
    assert 0 < line["metrics"]["scatter_skew_ms"]["value"] < 1000  # the slowest server's execution less the fastest's
    assert line["metrics"]["broker_gather_ms"]["value"] > 0
    assert '"segments_uploaded_again": 0' in out  # 2 / 2 / 2 / 2 by the controller's own assignment, nothing sent again
