"""`ssb-star-1srv` and its cell `ssbstar-groupby-closed` (PR 41): the star
dataset's tables are what the flat table was joined from; the cell rehearses
on the CPU to `correct: true` against the flat reference, to the unit; the
three lookup readers on a recorded answer and on a program without the span."""

import importlib
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import tables
from perfbench.datasets import ssb_flat as flat
from perfbench.datasets import ssb_star_lookup as star
from perfbench.manifest import load_cell, load_manifest
from perfbench.tests.test_run_rehearsal import ROOT

CELL = "ssbstar-groupby-closed"
MANIFEST = load_manifest(ROOT)
NEW = ["lookup_plan_ms", "lookup_builds_in_window", "lookup_gather_hbm_share"]
#: the ten older entries that list `ssb-groupby-closed` and take the twin's name at the end of their lists
TEN = ["groupby_kernel_share", "broker_self_ms", "wire_ms", "wire_bytes_per_query", "server_host_ms", "server_device_wait_ms",
       "device_launches_per_query", "groupby_kernel_mxu_share", "groupby_kernel_hbm_share", "broker_gather_ms"]  # fmt: skip


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


# ---------------------------------------------------------------------------
# the dataset
# ---------------------------------------------------------------------------


def numpy_lookup(dim: dict, key: str, dest: str, fk: np.ndarray) -> np.ndarray:
    """The plain join: sort the dimension's keys, searchsorted, take. Every key has its row here."""
    keys = dim[key].values()
    order = np.argsort(keys, kind="stable")
    at = np.searchsorted(keys[order], fk)
    assert (keys[order][at] == fk).all()
    return dim[dest].values()[order[at]]


@pytest.mark.parametrize("seed", [2_410_000_041, 3_999_999_999])
def test_the_star_tables_are_what_the_flat_table_was_joined_from(seed):
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    tables.rehearse(config)
    declared = {t["name"]: t for t in tables.declared(config, star)}
    dims = {name: star.TABLES[name]["segment"](seed, 0, declared[name]["rows"], config) for name in ("customer", "supplier", "part", "dates")}
    seg = star.segment(seed, 1, config["segmentRows"], config)
    joined = [name for name, _, _ in flat.SCHEMA if not name.startswith("lo_")]
    assert len(joined) == 13 and [name for name, _, _ in star.SCHEMA] == [n for n, _, _ in flat.SCHEMA if n.startswith("lo_")]
    for name in joined:
        table, key, fk = star.JOINS[name[0]]
        assert np.array_equal(numpy_lookup(dims[table], key, name, seg[fk].values()), seg[name].values()), name
    assert len(dims["dates"]["d_datekey"].codes) == 2556 and dims["dates"]["d_datekey"].values()[[0, -1]].tolist() == [19920101, 19981230]


def test_the_dimension_tables_carry_the_sources_columns_but_its_free_text():
    """SSB rev. 3's `customer` 8, `supplier` 7, `part` 9 and `date` 17 columns, less names, addresses and phones."""
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    tables.rehearse(config)
    declared = {t["name"]: t for t in tables.declared(config, star)}
    widths = {name: len(star.TABLES[name]["schema"]) for name in ("customer", "supplier", "part", "dates")}
    assert widths == {"customer": 8 - 3, "supplier": 7 - 3, "part": 9 - 1, "dates": 17}
    distinct = {}
    for name in widths:
        cols = star.TABLES[name]["segment"](7, 0, declared[name]["rows"], config)
        assert list(cols) == [c for c, _, _ in star.TABLES[name]["schema"]]
        for c, col in cols.items():
            assert len(col.codes) == declared[name]["rows"] and (col.vocab is None or (np.sort(col.vocab) == col.vocab).all()), c
            distinct[c] = len(np.unique(col.codes))
    assert [distinct[c] for c in ("c_mktsegment", "p_color", "p_type", "p_size", "p_container")] == [5, 92, 150, 50, 40]
    assert [distinct[c] for c in ("d_date", "d_dayofweek", "d_month", "d_sellingseason", "d_daynuminyear", "d_holidayfl")] == [2556, 7, 12, 5, 366, 2]
    dates = star.dates(7, 0, 2556, config)
    assert dates["d_dayofweek"].values()[0] == "Wednesday" and dates["d_daynuminweek"].values()[0] == 4  # 1992-01-01
    assert dates["d_date"].values()[59] == "February 29, 1992" and dates["d_lastdayinmonthfl"].values()[59] == 1


def test_every_template_is_the_flat_one_with_its_attributes_looked_up():
    assert list(star.TEMPLATES) == list(flat.TEMPLATES) and len(star.TEMPLATES) == 13
    for name, t in star.TEMPLATES.items():
        f = flat.TEMPLATES[name]
        assert t.spec is f.spec and t.draw is f.draw
        # outside its lookUp calls the query names no dimension attribute, and inside them what the flat one named
        assert "lookUp(" in t.sql and not star._ATTRIBUTE.search(re.sub(r"lookUp\([^)]*\)", "", t.sql))
        assert re.sub(r"lookUp\('[a-z]+', '([a-z0-9_]+)', '[a-z_]+', lo_[a-z]+\)", r"\1", t.sql) == f.sql
    assert star.TEMPLATES["q2.1"].sql == (
        "SELECT SUM(lo_revenue), lookUp('dates', 'd_year', 'd_datekey', lo_orderdate), lookUp('part', 'p_brand1', 'p_partkey', lo_partkey) "
        "FROM lineorder WHERE lookUp('part', 'p_category', 'p_partkey', lo_partkey) = '{category}' "
        "AND lookUp('supplier', 's_region', 's_suppkey', lo_suppkey) = '{region}' "
        "GROUP BY lookUp('dates', 'd_year', 'd_datekey', lo_orderdate), lookUp('part', 'p_brand1', 'p_partkey', lo_partkey) "
        "ORDER BY lookUp('dates', 'd_year', 'd_datekey', lo_orderdate), lookUp('part', 'p_brand1', 'p_partkey', lo_partkey) LIMIT 1000"
    )


def test_the_configuration_declares_the_star():
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    declared = tables.declared(config, star)
    assert [(t["name"], t["rows"], t["replication"], t["fact"]) for t in declared] == [
        ("customer", 300_000, 1, False), ("supplier", 20_000, 1, False), ("part", 800_000, 1, False), ("dates", 2556, 1, False),
        ("lineorder", 60_000_000, 1, True),
    ]  # fmt: skip
    sz = flat.sizes(config)
    assert (sz["customers"], sz["suppliers"], sz["parts"]) == (300_000, 20_000, 800_000)
    for t in declared[:-1]:
        assert t["tableConfig"]["extra"] == {"isDimTable": True} and len(t["schema"]["primaryKeyColumns"]) == 1
    flat_config = load_cell(MANIFEST, "ssb-groupby-closed", ROOT)["config"]
    for k in ("scaleFactor", "rows", "segmentRows", "servers", "chips", "replication", "rehearsal", "broker"):
        assert config[k] == flat_config[k], k  # the flat twin's own: the pair differs in the join alone
    assert set(flat_config["guarantees"]) < set(config["guarantees"]) and "dimensionWhole" in config["guarantees"]


def test_the_manifest_gains_the_cell_and_its_metrics_and_nothing_else_moves():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ssb-star-1srv", "flights234-closed4", 1)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        mod = reader(name)
        m = by_name[name]
        assert (m["layer"], m["unit"], m["moves"], m["source"]) == (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE)
        assert m["workloads"] == [CELL]
    for name in TEN:
        assert by_name[name]["workloads"][-1] == CELL and "ssb-groupby-closed" in by_name[name]["workloads"]
    listed = [m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(TEN + NEW)
    end_to_end = [m["name"] for m in MANIFEST["end_to_end"] if CELL in m.get("workloads", [CELL])]
    assert end_to_end == ["query_p50_ms", "query_p95_ms", "setup_s"]  # and not queries_per_s


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

PROGRAM = "seg_groupby_0123abcd"


def answer(template: str, plan_ms: float, builds: int, launches: int = 15) -> SimpleNamespace:
    doc = {
        "spanTimesMs": {"server.execute": 900.0, "server.plan.lookup": plan_ms},
        "counters": {"lookupOperandBuilds": builds, "lookupOperandBytesStaged": builds * 4096, "lookupMisses": 0},
        "deviceWork": {PROGRAM: {"launches": launches, "rows": launches * 4_000_000,
                                 "kernels": {"query.lookup_gather": {"calls": 4 * launches, "bytes": 1.0, "flops": 0.0}}}},
    }  # fmt: skip
    return SimpleNamespace(template=template, doc=doc, error=None, sent=0.0, done=1.0)


#: what the parent of PR 41 could answer at best: a ledger without the span, the counter or the registered gather
PARENT = SimpleNamespace(
    template="q2.1", error=None, sent=0.0, done=1.0,
    doc={"spanTimesMs": {"server.execute": 5.0}, "counters": {"hostToDeviceTransfers": 15},
         "deviceWork": {PROGRAM: {"launches": 15, "rows": 60_000_000, "kernels": {"ops.grouped_planes2": {"calls": 15, "bytes": 1.0, "flops": 1.0}}}}},
)  # fmt: skip


def run_of(good, trace=None):
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    return {"good": good, "samples": good, "trace": trace, "trace_window": (0.0, 1.0), "seconds": 10.0, "config": config}


def test_the_plan_span_and_the_build_counter_are_read_off_the_answers():
    good = [answer("q2.1", 0.5, 0), answer("q3.1", 0.9, 0), answer("q4.2", 1.3, 2)]
    assert reader("lookup_plan_ms").read(run_of(good)) == pytest.approx(0.9)
    assert reader("lookup_builds_in_window").read(run_of(good)) == 2.0
    assert reader("lookup_builds_in_window").read(run_of(good[:2])) == 0.0
    for name in ("lookup_plan_ms", "lookup_builds_in_window"):
        assert reader(name).read(run_of(good + [PARENT])) is not None
        assert reader(name).read(run_of([PARENT, PARENT])) is None
        assert reader(name).read(run_of([SimpleNamespace(template="q2.1", doc={"exceptions": [{"message": "x"}]}, error=None)])) is None
        assert reader(name).read(run_of([])) is None and reader(name).NEEDS_TRACE is False


def test_the_gather_share_counts_its_bytes_from_the_templates_and_the_configuration():
    mod = reader("lookup_gather_hbm_share")
    config = load_cell(MANIFEST, CELL, ROOT)["config"]
    # q2.1: d_year through dates, p_brand1 and p_category through part, s_region through supplier
    want_q21 = 4 * 4_000_000 * 8.0 + (2556 + 2 * 800_000 + 20_000) * 4.0
    assert mod.bytes_of_a_launch(star.TEMPLATES["q2.1"].sql, config) == want_q21
    # q4.2 names d_year three times and reads it once: six lookUps, not eight
    want_q42 = 6 * 4_000_000 * 8.0 + (300_000 + 2 * 20_000 + 2 * 800_000 + 2556) * 4.0
    assert mod.bytes_of_a_launch(star.TEMPLATES["q4.2"].sql, config) == want_q42
    assert mod.bytes_of_a_launch(flat.TEMPLATES["q4.2"].sql, config) == 0.0  # the flat query joins nothing
    trace = {"modules": [[f"jit_{PROGRAM}(123)", 2.0, 30], ["jit_seg_agg_ffffffff(9)", 5.0, 100]], "ops": []}
    good = [answer("q2.1", 0.5, 0), answer("q2.1", 0.5, 0)]
    got = mod.read(run_of(good, trace))
    assert got == pytest.approx(100.0 * 30 * want_q21 / 2.0 / 819e9) and 0 < got < 100
    # two templates behind one program: a launch's bytes are the mean over the launches reported
    mixed = mod.read(run_of([answer("q2.1", 0.5, 0), answer("q4.2", 0.5, 0)], trace))
    assert mixed == pytest.approx(100.0 * 30 * (want_q21 + want_q42) / 2 / 2.0 / 819e9)
    assert mod.NEEDS_TRACE is True
    # nothing to read: no trace; a program without the registered gather; a trace without the program's launches
    assert mod.read(run_of(good)) is None
    assert mod.read(run_of([PARENT, PARENT], trace)) is None
    assert mod.read(run_of(good, {"modules": [["jit_seg_agg_ffffffff(9)", 5.0, 100]], "ops": []})) is None
    assert mod.read(run_of([], trace)) is None
    flat_run = {**run_of(good, trace), "config": load_cell(MANIFEST, "ssb-groupby-closed", ROOT)["config"]}
    assert mod.read(flat_run) is None  # a configuration that declares no tables


# ---------------------------------------------------------------------------
# the cell, rehearsed
# ---------------------------------------------------------------------------


def test_the_cell_rehearses_correct_to_the_unit_against_the_flat_reference():
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "4100000021", "--seconds", "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )  # fmt: skip
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    assert compared["max_abs_diff"] == {"value": 0.0, "limit": 0.0}
    assert compared["rows_missing_or_extra"]["value"] == 0 and compared["order_violations"]["value"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["lookup_builds_in_window"] == 0 and metrics["compiles_in_window"] == 0 and metrics["lookup_plan_ms"] > 0
    assert len([ln for ln in p.stdout.splitlines() if ln.startswith("[perfbench] check #")]) >= 12  # two of each template
