"""`ssb-flat-startree-1srv` and its cell `ssbtree-flights-closed` (PR 44): the
flat SSB table with two star-trees declared. The configuration loads through
`tables.declared` and is the flat twin's but for the trees; the traffic file
holds the nine templates in equal shares; the three star-tree readers on a
recorded answer and on a program without the counters; and the cell rehearses
on the CPU to `correct: true` against the flat reference, to the unit, six of
its nine templates answered from a star table."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from perfbench import datagen, tables
from perfbench.datasets import ssb_flat as flat
from perfbench.manifest import load_cell, load_manifest
from perfbench.tests.test_run_rehearsal import ROOT

CELL = "ssbtree-flights-closed"
CONFIG = "ssb-flat-startree-1srv"
MANIFEST = load_manifest(ROOT)
NEW = ["startree_served_share", "startree_plan_ms", "startree_builds_in_window"]
#: the ten older entries that list `ssb-groupby-closed` and `ssbstar-groupby-closed` and take this cell's name after them
TEN = ["groupby_kernel_share", "broker_self_ms", "wire_ms", "wire_bytes_per_query", "server_host_ms", "server_device_wait_ms",
       "device_launches_per_query", "groupby_kernel_mxu_share", "groupby_kernel_hbm_share", "broker_gather_ms"]  # fmt: skip
TREE_A = {"dimensionsSplitOrder": ["d_year", "p_category", "p_brand1", "s_region"], "functionColumnPairs": ["SUM__lo_revenue", "COUNT__*"], "maxLeafRecords": 10000}
TREE_B = {"dimensionsSplitOrder": ["d_year", "c_region", "c_nation", "s_region", "s_nation", "p_mfgr", "p_category"],
          "functionColumnPairs": ["SUM__lo_revenue", "SUM__lo_supplycost", "COUNT__*"], "maxLeafRecords": 10000}  # fmt: skip
STAR_ANSWERED = ["q2.1", "q2.2", "q2.3", "q3.1", "q4.1", "q4.2"]


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


# ---------------------------------------------------------------------------
# the configuration, the traffic and the manifest
# ---------------------------------------------------------------------------


def test_the_configuration_is_the_flat_twins_with_two_star_trees_declared():
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    (table,) = tables.declared(config, flat)
    assert (table["name"], table["rows"], table["segmentRows"], table["replication"], table["fact"]) == ("lineorder", 60_000_000, 4_000_000, 1, True)
    assert table["tableConfig"] == {"tableType": "OFFLINE", "indexing": {"starTreeConfigs": [TREE_A, TREE_B]}}
    # what the program reads of it is what the file says: two trees, nothing else indexed
    indexing = datagen.table_config(table).indexing
    assert [(t.dimensions_split_order, t.function_column_pairs, t.max_leaf_records) for t in indexing.star_tree_configs] == [
        (t["dimensionsSplitOrder"], t["functionColumnPairs"], 10000) for t in (TREE_A, TREE_B)
    ]
    assert not indexing.inverted_index_columns and not indexing.range_index_columns and not indexing.bloom_filter_columns
    twin = load_cell(MANIFEST, "ssb-groupby-closed", ROOT)["config"]
    for k in ("dataset", "scaleFactor", "rows", "segmentRows", "servers", "chips", "replication", "rehearsal", "broker", "cacheSeeds", "reduced"):
        assert config[k] == twin[k], k  # the pair differs in the index alone
    assert config["assumed"][: len(twin["assumed"])] == twin["assumed"] and len(config["assumed"]) > len(twin["assumed"])
    assert {k: config["guarantees"][k] for k in twin["guarantees"]} == twin["guarantees"]
    assert set(config["guarantees"]) - set(twin["guarantees"]) == {"indexEqualsScan"}
    assert list(config["reduced"]) == ["scaleFactor"] and "noInvertedRangeOrBloomIndex" in config["indexes"]
    # every split dimension and stored column is the generator's own
    columns = [c for c, _, _ in flat.SCHEMA]
    for tree in (TREE_A, TREE_B):
        assert all(d in columns for d in tree["dimensionsSplitOrder"])
        assert all(p.split("__", 1)[1] in columns + ["*"] for p in tree["functionColumnPairs"])


def test_a_rehearsal_sized_segment_carries_both_star_tables():
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    tables.rehearse(config)
    (table,) = tables.declared(config, flat)
    seg = datagen.build_segment(flat, flat.segment(4_400_000_002, 0, 2_000, config), "lineorder_0", table)
    a, b = seg.extras["startree"]
    assert sorted(seg.extras) == ["startree"]
    assert (a.dimensions, a.function_column_pairs) == (TREE_A["dimensionsSplitOrder"], ["SUM__lo_revenue"])
    assert (b.dimensions, b.function_column_pairs) == (TREE_B["dimensionsSplitOrder"], ["SUM__lo_revenue", "SUM__lo_supplycost"])
    assert 0 < a.n_rows <= 2_000 and 0 < b.n_rows <= 2_000 and int(a.arrays["__count"].sum()) == int(b.arrays["__count"].sum()) == 2_000
    assert a.arrays["SUM__lo_revenue"].dtype.kind == b.arrays["SUM__lo_supplycost"].dtype.kind == "i"


def test_the_traffic_file_holds_the_nine_templates_in_equal_shares():
    cell = load_cell(MANIFEST, CELL, ROOT)
    traffic = cell["traffic"]
    assert traffic["templates"] == {t: 1 for t in ["q1.1", "q1.2", "q1.3", *STAR_ANSWERED]}
    assert set(traffic["templates"]) <= set(flat.TEMPLATES)
    twin = load_cell(MANIFEST, "ssb-groupby-closed", ROOT)["traffic"]
    assert list(twin["templates"]) == STAR_ANSWERED
    for k in ("loop", "limit", "timeoutMs", "maxQueries", "warmup", "check", "trace"):
        assert traffic[k] == twin[k], k  # `flights234-closed4`'s own keys, flight 1's three templates added
    assert traffic["loop"] == {"kind": "closed", "clients": 4} and traffic["check"] == {"perTemplate": 2}


def test_the_manifest_gains_the_cell_and_its_metrics_and_nothing_else_moves():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "flights1234-closed4", 1)
    assert MANIFEST["workloads"][-1] is cell and MANIFEST["configs"][-1]["name"] == CONFIG
    entry = MANIFEST["configs"][-1]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json" and entry["reduced"] == ["scaleFactor"] and len(entry["source"]) <= 200
    assert json.loads((ROOT / entry["file"]).read_text())["name"] == CONFIG
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == NEW
    for name in NEW:
        mod = reader(name)
        m = by_name[name]
        assert (m["layer"], m["unit"], m["moves"], m["source"]) == (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
        assert m["workloads"] == [CELL] and mod.NEEDS_TRACE is False
    for name in TEN:
        assert by_name[name]["workloads"][-2:] == ["ssbstar-groupby-closed", CELL]
    listed = [m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(TEN + NEW)
    end_to_end = [m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])]
    assert end_to_end == ["query_p50_ms", "query_p95_ms", "setup_s"]  # and not queries_per_s
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 2 and len(MANIFEST["workloads"]) == 9 and len(MANIFEST["configs"]) == 7


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


def answer(template: str, star_segments: int, plan_ms: float | None, builds: int = 0, launches: int = 15) -> SimpleNamespace:
    spans = {"server.execute": 40.0}
    if plan_ms is not None:
        spans["server.plan.startree"] = plan_ms
    doc = {
        "spanTimesMs": spans,
        "counters": {"starTreeSegments": star_segments, "starTreeRecords": star_segments * 35_000, "starTreeBuilds": builds, "segmentsDispatched": launches},
    }
    return SimpleNamespace(template=template, doc=doc, error=None, sent=0.0, done=1.0)


#: what the parent of PR 44 answers: the swap made (slowly), and neither the counters nor the span to say so
PARENT = SimpleNamespace(
    template="q2.1", error=None, sent=0.0, done=1.0,
    doc={"spanTimesMs": {"server.execute": 240.0}, "counters": {"hostToDeviceTransfers": 15, "segmentsDispatched": 15}, "deviceWork": {}},
)  # fmt: skip


def run_of(good):
    return {"good": good, "samples": good, "trace": None, "trace_window": (0.0, 1.0), "seconds": 10.0, "config": load_cell(MANIFEST, CELL, ROOT)["config"]}


def test_the_three_readers_read_the_counters_and_the_span_off_the_answers():
    good = [answer("q2.1", 15, 0.4), answer("q4.2", 15, 0.9), answer("q1.1", 0, None), answer("q3.1", 15, 0.6, builds=3)]
    assert reader("startree_served_share").read(run_of(good)) == pytest.approx(75.0)
    assert reader("startree_served_share").read(run_of(good[:3])) == pytest.approx(100.0 * 30 / 45)
    # a segment a star table stopped answering shows at once: 14 of a query's 15
    assert reader("startree_served_share").read(run_of([answer("q2.1", 14, 0.4)])) == pytest.approx(100.0 * 14 / 15)
    assert reader("startree_served_share").read(run_of([answer("q1.1", 0, None)])) == 0.0
    # the plan span is a star-answered answer's: the scanned one has none and does not pull the median down
    assert reader("startree_plan_ms").read(run_of(good)) == pytest.approx(0.6)
    assert reader("startree_plan_ms").read(run_of([answer("q1.1", 0, None)])) is None
    assert reader("startree_builds_in_window").read(run_of(good)) == 3.0
    assert reader("startree_builds_in_window").read(run_of(good[:3])) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_gives_nothing_to_read(name):
    mod = reader(name)
    assert mod.read(run_of([PARENT, PARENT])) is None
    assert mod.read(run_of([])) is None
    assert mod.read(run_of([SimpleNamespace(template="q2.1", doc={"exceptions": [{"message": "x"}]}, error=None)])) is None
    assert mod.read(run_of([answer("q2.1", 15, 0.5), PARENT])) is not None  # what can be read is read


# ---------------------------------------------------------------------------
# the cell, rehearsed
# ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_to_the_unit_with_six_of_nine_templates_star_answered():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "4400000021", "--seconds", "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )  # fmt: skip
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    assert compared["max_abs_diff"] == {"value": 0.0, "limit": 0.0}
    assert compared["rows_missing_or_extra"]["value"] == 0 and compared["order_violations"]["value"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["startree_builds_in_window"] == 0 and metrics["compiles_in_window"] == 0 and metrics["startree_plan_ms"] > 0
    # six of the nine templates in every segment: two thirds, to what a short window's draw of the mix leaves
    assert 50.0 < metrics["startree_served_share"] < 85.0
    assert len([ln for ln in p.stdout.splitlines() if ln.startswith("[perfbench] check #")]) >= 18  # two of each template
