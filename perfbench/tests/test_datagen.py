"""The datagen's direct assembly of a segment equals what the program's own
SegmentBuilder makes of the same rows: same dictionaries, same forward
indexes, same statistics, and the same answers from the engine."""

import importlib

import numpy as np
import pytest

from perfbench import datagen

CFG = {"scaleFactor": 1}


@pytest.mark.parametrize("name", ["ssb_flat", "tpch_lineitem"])
def test_the_direct_segment_equals_the_builders(name):
    from pinot_tpu.segment import SegmentBuilder

    ds = importlib.import_module(f"perfbench.datasets.{name}")
    cols = ds.segment(2_200_000_001, 0, 3_000, CFG)
    direct = datagen.build_segment(ds, cols, "seg_0")
    raw = {}
    for c, col in cols.items():
        v = col.values()
        raw[c] = v.astype(object) if v.dtype.kind == "U" else v
    built = SegmentBuilder(datagen.program_schema(ds)).build(raw, "seg_0")
    _same_columns(direct, built)
    assert direct.extras == built.extras == {}


def _same_columns(direct, built):
    assert list(direct.columns) == list(built.columns)
    for c in direct.columns:
        a, b = direct.columns[c], built.columns[c]
        assert a.is_dict_encoded == b.is_dict_encoded, c
        assert a.forward.dtype == b.forward.dtype and np.array_equal(a.forward, b.forward), c
        if a.is_dict_encoded:
            assert np.array_equal(a.dictionary.values, b.dictionary.values), c
        assert a.stats.to_dict() == b.stats.to_dict(), c


DECLARED = {
    "tableType": "OFFLINE",
    "indexing": {
        "noDictionaryColumns": ["lo_orderkey"],  # a dimension kept raw
        "dictionaryColumns": ["lo_quantity"],  # a metric coded
        "invertedIndexColumns": ["c_region", "p_category"],
        "rangeIndexColumns": ["lo_discount", "lo_orderdate"],  # a raw metric and a coded dimension
        "bloomFilterColumns": ["lo_orderkey", "p_brand1"],
        "sortedColumn": "lo_orderkey",
        "starTreeConfigs": [
            {"dimensionsSplitOrder": ["d_year", "p_category", "s_region"], "maxLeafRecords": 10000,
             "functionColumnPairs": ["SUM__lo_revenue", "SUM__lo_supplycost", "MAX__lo_quantity", "COUNT__*"]},
        ],
    },
}  # fmt: skip


def _same_arrays(a, b, where):
    assert type(a) is type(b), where
    for k, v in vars(a).items():
        w = getattr(b, k)
        if isinstance(v, np.ndarray):
            assert v.dtype == w.dtype and np.array_equal(v, w), f"{where}.{k}"
        elif isinstance(v, dict):
            assert list(v) == list(w) and all(np.array_equal(v[x], w[x]) and v[x].dtype == w[x].dtype for x in v), f"{where}.{k}"
        else:
            assert v == w, f"{where}.{k}"


def test_a_declared_table_config_gives_the_builders_segment_indexes_and_all():
    """Encodings the other way round, a star-tree, inverted, range and bloom indexes: the direct assembly follows
    the declaration as `SegmentBuilder(schema, table_config).build` does, and the engine answers a query the star
    table can answer, and one it cannot, the same from both."""
    from pinot_tpu.query import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    ds = importlib.import_module("perfbench.datasets.ssb_flat")
    table = {"name": "lineorder", "generator": "lineorder", "replication": 1, "schema": {}, "tableConfig": DECLARED}
    cols = ds.segment(2_400_000_002, 0, 3_000, CFG)
    direct = datagen.build_segment(ds, cols, "seg_0", table)
    raw = {c: (v.astype(object) if v.dtype.kind == "U" else v) for c, v in ((c, cols[c].values()) for c in cols)}
    config = datagen.table_config(table)
    assert config.indexing.star_tree_configs[0].dimensions_split_order == ["d_year", "p_category", "s_region"]
    built = SegmentBuilder(datagen.program_schema(ds, table), config).build(raw, "seg_0")
    _same_columns(direct, built)
    assert not direct.columns["lo_orderkey"].is_dict_encoded and direct.columns["lo_quantity"].is_dict_encoded
    assert sorted(direct.extras) == sorted(built.extras) == ["bloom", "inverted", "range", "startree"]
    for kind in ("bloom", "inverted", "range"):
        assert list(direct.extras[kind]) == list(built.extras[kind]) == DECLARED["indexing"][f"{kind}{'Filter' if kind == 'bloom' else 'Index'}Columns"]
        for c in direct.extras[kind]:
            _same_arrays(direct.extras[kind][c], built.extras[kind][c], f"{kind}[{c}]")
    (a,), (b,) = direct.extras["startree"], built.extras["startree"]
    _same_arrays(a, b, "startree")
    assert 0 < a.n_rows <= 7 * 25 * 5 and a.function_column_pairs == ["SUM__lo_revenue", "SUM__lo_supplycost", "MAX__lo_quantity"]
    from_star = "SELECT d_year, SUM(lo_revenue), COUNT(*) FROM lineorder WHERE s_region = 'ASIA' GROUP BY d_year ORDER BY d_year LIMIT 100"
    scanned = "SELECT d_year, SUM(lo_revenue - lo_supplycost) FROM lineorder WHERE c_region = 'ASIA' GROUP BY d_year ORDER BY d_year LIMIT 100"
    for sql in (from_star, scanned):
        got, want = QueryEngine([direct]).execute(sql), QueryEngine([built]).execute(sql)
        assert got.rows == want.rows and len(got.rows) == 7, sql
    plan = QueryEngine([direct]).execute("EXPLAIN PLAN FOR " + from_star).rows
    assert any("STARTREE_SWAP" in str(r[0]) for r in plan), plan


def test_a_key_the_program_does_not_read_ends_the_declaration_by_name():
    table = {"name": "lineorder", "generator": "lineorder", "replication": 1, "schema": {}}
    for wrong, named in (
        ({"indexing": {"invertedIndexColumn": ["c_region"]}}, "indexing.invertedIndexColumn"),
        ({"segmentsConfig": {"replication": 2}}, "segmentsConfig"),
        ({"indexing": {"starTreeConfigs": [{"dimensionsSplitOrder": ["d_year"], "skipStarNodeCreation": ["d_year"]}]}}, "indexing.starTreeConfigs.0.skipStarNodeCreation"),
        ({"tableName": "other"}, "tableName"),
    ):
        with pytest.raises(ValueError, match=named.replace(".", r"\.")):
            datagen.table_config({**table, "tableConfig": wrong})
    kept = datagen.table_config({**table, "replication": 2, "tableConfig": {"timeColumn": "lo_orderdate", "extra": {"isDimTable": True, "anything": 1}}})
    assert kept.extra == {"isDimTable": True, "anything": 1} and kept.time_column == "lo_orderdate" and kept.replication == 2 and kept.table_name == "lineorder"
