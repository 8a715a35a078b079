"""A configuration declares its tables (PR 40): `tables` in a configuration file, `TABLES` in a dataset module.

What a configuration without `tables` sends and builds is held to what it sent and built before the harness
could read a declaration; a fixture dataset of two tables (`fixtures/two_tables/`: a fact table with a declared
star-tree, an inverted index and a raw dimension, beside a dimension table with a primary key, `isDimTable`,
`"everyServer"`) goes through set-up as OS processes on one and on two servers; a declaration that cannot be
loaded ends set-up by the key's or the table's name before a segment is built."""

import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import datagen, tables
from perfbench.cluster import RunFailure
from perfbench.manifest import load_manifest
from perfbench.tests.test_run_rehearsal import ROOT, copy_of_the_benchmark

FIXTURE = ROOT / "perfbench" / "tests" / "fixtures" / "two_tables"
MANIFEST = load_manifest(ROOT)

# ---------------------------------------------------------------------------
# nothing declared: the same bytes to the controller, the same bytes a segment
# ---------------------------------------------------------------------------

#: sha256 over the `.ptseg` directory (each file's name, then its bytes) of segment 0 at the configuration's rehearsal
#: size, seed 2400000040, as `datagen.build_segment` + `write_segment` made it at eff5d87, the parent of PR 40
SEGMENT_DIGEST_AT_THE_PARENT = {
    "ssb-flat-1srv": "e8afaaadb84d6951723373faf3efa92c45e70a8dca0197483d1fcdd61b1265d1",
    "ssb-flat-4srv": "e8afaaadb84d6951723373faf3efa92c45e70a8dca0197483d1fcdd61b1265d1",
    "ssb-flat-4srv-r2": "e8afaaadb84d6951723373faf3efa92c45e70a8dca0197483d1fcdd61b1265d1",
    "tpch-lineitem-1srv": "f3ae3a14618bffc205fbb870a6e81490cf53abbefcfe7a2a927262a6f15da17b",
    "tsbs-cpu-1srv": "7248e0f92b55e4825fa4d2e12983d2d9c4f6a90c0447feb6a20e9c71b4ebb0a8",
}


def rehearsal_config(name: str) -> dict:
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = json.loads((ROOT / entry["file"]).read_text())
    tables.rehearse(config)
    return config


class RecordingController:
    """In `RemoteControllerClient`'s place: keeps what set-up sends."""

    sent: list = []

    def __init__(self, url):
        pass

    def add_schema(self, schema):
        self.sent.append(("schema", schema.to_json()))

    def add_table(self, config):
        self.sent.append(("table", config.to_json()))


@pytest.mark.parametrize("name", sorted(SEGMENT_DIGEST_AT_THE_PARENT))
def test_a_configuration_that_declares_nothing_is_loaded_as_before(name, monkeypatch):
    from pinot_tpu.cluster import http
    from pinot_tpu.common import TableConfig
    from pinot_tpu.segment.builder import write_segment

    assert name in {c["name"] for c in MANIFEST["configs"]} and len(MANIFEST["configs"]) == len(SEGMENT_DIGEST_AT_THE_PARENT)
    config = rehearsal_config(name)
    assert "tables" not in config
    ds = datagen.dataset_module(config["dataset"])
    (table,) = declared = tables.declared(config, ds)
    assert table == {"name": ds.TABLE, "generator": ds.TABLE, "rows": config["rows"], "segmentRows": config["segmentRows"],
                     "replication": config["replication"], "schema": {}, "tableConfig": {}, "fact": True}  # fmt: skip
    # to the controller: the module's schema and the program's default table config at the configuration's replication
    monkeypatch.setattr(http, "RemoteControllerClient", RecordingController)
    monkeypatch.setattr(RecordingController, "sent", [])
    datagen.create_tables(ds, "http://nowhere", declared)
    assert RecordingController.sent == [
        ("schema", datagen.program_schema(ds).to_json()),
        ("table", TableConfig(ds.TABLE, replication=config["replication"]).to_json()),
    ]
    # a segment: no index, the encodings of before, the file's bytes those of the parent's direct assembly
    cols = ds.segment(2_400_000_040, 0, config["segmentRows"], config)
    seg = datagen.build_segment(ds, cols, f"{ds.TABLE}_0", table)
    assert seg.extras == {}
    for c, _, role in ds.SCHEMA:
        assert seg.columns[c].is_dict_encoded == (role == "dimension"), c
    with tempfile.TemporaryDirectory() as d:
        seg_dir = Path(write_segment(seg, d))
        h = hashlib.sha256()
        for f in sorted(seg_dir.iterdir()):
            h.update(f.name.encode())
            h.update(f.read_bytes())
    assert h.hexdigest() == SEGMENT_DIGEST_AT_THE_PARENT[name]


# ---------------------------------------------------------------------------
# the declaration as data: what `tables.declared` makes of it, and what it refuses
# ---------------------------------------------------------------------------

DS = SimpleNamespace(
    __name__="perfbench.datasets.two", TABLE="fact", SCHEMA=[("k", "INT", "dimension"), ("v", "LONG", "metric")], segment=len,
    TABLES={"dim": {"schema": [("pk", "INT", "dimension"), ("name", "STRING", "dimension")], "segment": max}},
)  # fmt: skip
CONFIG = {
    "name": "two", "rows": 600, "segmentRows": 100, "servers": 2, "replication": 1,
    "rehearsal": {"rows": 60, "segmentRows": 10},
    "tables": [
        {"name": "fact", "tableConfig": {"indexing": {"invertedIndexColumns": ["k"]}}},
        {"name": "names", "generator": "dim", "rows": 50, "segmentRows": 50, "rehearsal": {"rows": 5, "segmentRows": 5},
         "replication": "everyServer", "schema": {"primaryKeyColumns": ["pk"]}, "tableConfig": {"extra": {"isDimTable": True}}},
    ],
}  # fmt: skip


def test_the_fact_table_comes_last_at_the_configurations_own_sizes():
    names, fact = tables.declared(copy.deepcopy(CONFIG), DS)
    assert names == {"name": "names", "generator": "dim", "rows": 50, "segmentRows": 50, "replication": 2, "fact": False,
                     "schema": {"primaryKeyColumns": ["pk"]}, "tableConfig": {"extra": {"isDimTable": True}}}  # fmt: skip
    assert fact == {"name": "fact", "generator": "fact", "rows": 600, "segmentRows": 100, "replication": 1, "fact": True,
                    "schema": {}, "tableConfig": {"indexing": {"invertedIndexColumns": ["k"]}}}  # fmt: skip
    assert tables.generator(DS, "fact") == {"schema": DS.SCHEMA, "segment": len} and tables.generator(DS, "dim")["segment"] is max
    assert tables.segment_names(fact) == [f"fact_{i}" for i in range(6)] and tables.segment_sizes(names) == [50]
    small = copy.deepcopy(CONFIG)
    tables.rehearse(small)
    names, fact = tables.declared(small, DS)
    assert (names["rows"], names["segmentRows"], fact["rows"], fact["segmentRows"]) == (5, 5, 60, 10)


def _with(path: list, value) -> dict:
    config = copy.deepcopy(CONFIG)
    at = config
    for k in path[:-1]:
        at = at[k]
    if value is None:
        del at[path[-1]]
    else:
        at[path[-1]] = value
    return config


@pytest.mark.parametrize(
    "config,named",
    [
        (_with(["tables", 1, "generator"], "supplier"), r"generates no table 'supplier'.*\['dim', 'fact'\]"),
        (_with(["tables", 1, "rows"], 100), r"'names'.*\"everyServer\" and has 2 segments, not one"),
        (_with(["tables", 1, "indexes"], ["pk"]), r"'names'.*keys \['indexes'\]"),
        (_with(["tables", 1, "segmentRows"], None), r"'names'.*lacks \['segmentRows'\]"),
        (_with(["tables", 1, "replication"], 3), r"'names'.*replication 3 is neither"),
        (_with(["tables", 1, "schema"], {"primaryKeyColumns": ["id"]}), r"'names'.*columns \['id'\]"),
        (_with(["tables", 1, "schema"], {"primaryKey": ["pk"]}), r"'names'.*schema has keys \['primaryKey'\]"),
        (_with(["tables", 0, "rows"], 600), r"'fact'.*its \['rows'\] are the configuration's own"),
        (_with(["tables", 0, "name"], "other"), r"'other'.*lacks \['rows', 'segmentRows', 'replication'\]"),
        (_with(["tables", 0], CONFIG["tables"][1]), r"'names'.*declared twice"),
        (_with(["tables"], [CONFIG["tables"][1]]), r"not 'fact', which its templates query"),
    ],
)
def test_a_declaration_that_cannot_be_loaded_is_refused_by_name(config, named):
    with pytest.raises(RunFailure, match=named):
        tables.declared(config, DS)


# ---------------------------------------------------------------------------
# two tables through set-up, as OS processes
# ---------------------------------------------------------------------------

LOOKS_AT_THE_CLUSTER = """
import json, sys
from pathlib import Path
from perfbench import run
from perfbench.cluster import http_json

if __name__ == "__main__":
    warm_up = run.warm_up

    def look_then_warm_up(cluster, *a, **kw):
        seen = {
            "configs": {t["name"]: http_json(f"{cluster.controller}/tables/{t['name']}") for t in cluster.tables},
            "schemas": {t["name"]: http_json(f"{cluster.controller}/tables/{t['name']}/schema") for t in cluster.tables},
            "hosted": {t["name"]: cluster.hosted(t["name"]) for t in cluster.tables},
            "ideal": {t["name"]: cluster.ideal_state(t["name"]) for t in cluster.tables},
            "dir": str(cluster.dir), "timing": cluster.timing,
        }
        Path(sys.argv[0]).with_suffix(".json").write_text(json.dumps(seen))
        return warm_up(cluster, *a, **kw)

    run.warm_up = look_then_warm_up
    try:
        code = run.main(sys.argv[1:])
    except run.RunFailure as e:  # as run.py's own entry ends such a run
        print(f"perfbench: run failed: {e}", file=sys.stderr)
        code = 2
    sys.exit(code)
"""


def copy_with_the_fixture(tmp_path: Path, servers: int = 1, change=None) -> dict:
    """The benchmark copied beside the program with the fixture's dataset, configuration and traffic laid in as
    new files, and a manifest that names them: no file the benchmark has is edited."""
    before = copy_of_the_benchmark(tmp_path)
    bench = tmp_path / "perfbench"
    shutil.copy(FIXTURE / "ssb_star.py", bench / "datasets" / "ssb_star.py")
    shutil.copy(FIXTURE / "modes-closed2.json", bench / "traffic" / "modes-closed2.json")
    config = json.loads((FIXTURE / "ssb-star-fixture.json").read_text())
    config["servers"] = config["chips"] = servers
    if change:
        change(config)
    (bench / "configs" / "ssb-star-fixture.json").write_text(json.dumps(config))
    manifest = load_manifest(ROOT)
    manifest["configs"].append({"name": "ssb-star-fixture", "source": "test", "reduced": ["scaleFactor"],
                                "file": "perfbench/configs/ssb-star-fixture.json", "why": "test"})  # fmt: skip
    manifest["workloads"].append({"name": "ssb-star-modes", "config": "ssb-star-fixture", "traffic": "modes-closed2",
                                  "chips": servers, "why": "test"})  # fmt: skip
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "looks.py").write_text(LOOKS_AT_THE_CLUSTER)
    assert {p: p.read_bytes() for p in before} == before
    return config


def run_the_fixture(tmp_path: Path, seed: int, script: str = "looks.py") -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"} | {"PYTHONPATH": str(tmp_path)}
    return subprocess.run(
        [sys.executable, str(tmp_path / script), "--workload", "ssb-star-modes", "--seed", str(seed), "--seconds", "3", "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900,
    )  # fmt: skip


@pytest.mark.parametrize("servers", [1, 2])
def test_two_declared_tables_are_loaded_hosted_and_answered_from(servers, tmp_path):
    from pinot_tpu.segment.loader import load_segment

    config = copy_with_the_fixture(tmp_path, servers)
    p = run_the_fixture(tmp_path, 2_400_000_100 + servers)
    assert p.returncode == 0, (p.stdout + p.stderr)[-3000:]
    # a plain grouped query over the fact table, one the star table can answer and one it cannot: as the reference says
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["max_abs_diff"] == {"value": 0.0, "limit": 0.0} and line["compared"]["rows_missing_or_extra"]["value"] == 0
    seen = json.loads((tmp_path / "looks.json").read_text())
    # the controller holds both table configs as declared, the dimension table at one replica a server
    declared = {t["name"]: t["tableConfig"] for t in config["tables"]}
    for name, want in declared.items():
        got = seen["configs"][name]
        assert got["tableName"] == name and datagen._unread(want, got, "") == []
    assert seen["configs"]["customer"]["extra"] == {"isDimTable": True} and seen["configs"]["customer"]["replication"] == servers
    assert seen["configs"]["lineorder"]["replication"] == 1
    assert seen["configs"]["lineorder"]["indexing"]["starTreeConfigs"][0]["dimensionsSplitOrder"] == ["lo_shipmode", "lo_orderpriority", "lo_linenumber"]
    assert seen["schemas"]["customer"]["primaryKeyColumns"] == ["c_custkey"] and seen["schemas"]["lineorder"]["primaryKeyColumns"] == []
    fields = [f["name"] for f in seen["schemas"]["lineorder"]["fields"]]  # the 17 of `lineorder`, dimensions first
    assert fields == [c for c, _, role in _lineorder() if role == "dimension"] + [c for c, _, role in _lineorder() if role == "metric"]
    # every server hosts the dimension table whole; the fact table's six segments are shared out evenly, each kept once
    ids = [f"server_{i}" for i in range(servers)]
    assert seen["hosted"]["customer"] == {sid: ["customer_0"] for sid in ids}
    assert seen["ideal"]["customer"] == {"customer_0": {sid: "ONLINE" for sid in ids}}
    assert sorted(s for segs in seen["hosted"]["lineorder"].values() for s in segs) == [f"lineorder_{i}" for i in range(6)]
    assert {len(segs) for segs in seen["hosted"]["lineorder"].values()} == {6 // servers}
    # a fact segment as a server loaded it: the raw dimension, the star table, the inverted index
    held = next(sid for sid, segs in seen["hosted"]["lineorder"].items() if "lineorder_0" in segs)
    seg = load_segment(Path(seen["dir"]) / f"data_{held}" / "lineorder" / "lineorder_0")
    assert list(seg.columns) == fields and len(fields) == 17
    assert not seg.columns["lo_orderkey"].is_dict_encoded and seg.columns["lo_custkey"].is_dict_encoded
    (star,) = seg.extras["startree"]
    assert star.dimensions == ["lo_shipmode", "lo_orderpriority", "lo_linenumber"] and star.function_column_pairs == ["SUM__lo_revenue"]
    assert 0 < star.n_rows <= 7 * 5 * 7 and int(star.arrays["__count"].sum()) == seg.n_docs == 8000
    assert list(seg.extras["inverted"]) == ["lo_shipmode"] and len(seg.extras["inverted"]["lo_shipmode"].doc_ids) == 8000
    dim = load_segment(Path(seen["dir"]) / f"data_{ids[-1]}" / "customer" / "customer_0")
    assert dim.n_docs == 30_000 and dim.schema.primary_key_columns == ["c_custkey"] and not dim.extras.get("startree")
    # the set-up line's detail, a table
    detail = seen["timing"]["tables"]
    assert list(detail) == ["customer", "lineorder"] and detail["customer"]["rows"] == 30_000 and detail["customer"]["segments"] == 1
    assert detail["lineorder"]["segments"] == 6 and detail["lineorder"]["fileBytes"] > 0 and detail["lineorder"]["index_s_per_segment"] > 0
    assert 0 < detail["lineorder"]["starRecords_per_segment"] <= 245 and "starRecords_per_segment" not in detail["customer"]
    assert '"segments_uploaded_again": 0' in p.stdout


def _lineorder() -> list:
    from perfbench.datasets import ssb_flat

    return [row for row in ssb_flat.SCHEMA if row[0].startswith("lo_")]


def _unknown_key(config):
    config["tables"][1]["tableConfig"]["indexing"]["invertedIndexColumn"] = ["lo_shipmode"]


def _ungenerated_table(config):
    config["tables"][0]["generator"] = "supplier"


def _every_server_in_pieces(config):
    config["tables"][0]["segmentRows"] = config["tables"][0]["rehearsal"]["segmentRows"] = 10_000


@pytest.mark.parametrize(
    "change,named",
    [
        (_unknown_key, "table 'lineorder': the program's TableConfig.from_json does not read tableConfig keys ['indexing.invertedIndexColumn']"),
        (_ungenerated_table, "generates no table 'supplier'"),
        (_every_server_in_pieces, "table 'customer' of configuration ssb-star-fixture is declared \"everyServer\" and has 3 segments, not one"),
    ],
)
def test_a_declaration_that_cannot_be_loaded_ends_set_up_before_a_segment_is_built(change, named, tmp_path):
    copy_with_the_fixture(tmp_path, 1, change)
    p = run_the_fixture(tmp_path, 2_400_000_110)
    assert p.returncode == 2, (p.stdout + p.stderr)[-3000:]
    assert "perfbench: run failed: " in p.stderr and named in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith('{"correct"')] and not (tmp_path / "looks.json").exists()
    seed_dir = tmp_path / "perfbench" / ".cache" / "ssb-star-fixture-rehearsal" / "2400000110"
    assert not (seed_dir / "built").exists() and not list(seed_dir.glob("deep/*/*")) and not (seed_dir / "complete.json").exists()
