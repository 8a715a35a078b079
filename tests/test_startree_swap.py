"""The star-tree swap on the engine's normal path (PR 44).

A star-answered segment is planned over its star table and ENQUEUED like any
other: nothing waits inside the dispatch loop, a query waits once
(`kernels.wait_packed`) for star and raw segments together, and the star
program's partial is mapped back to the layout the query asked for. A SUM /
AVG of a sum or difference of columns is served from the stored `SUM__<col>`
pairs; integer pairs are accumulated and staged as integers; and what the old
blocking path guarded still is: upsert's valid docs, null handling, FILTER
(WHERE), a star plan that falls back, the sparse group-by's collision.

The SSB differential builds the benchmark's own table (`ssb-flat-startree-1srv`,
trees A and B as its configuration file declares them) beside the same rows
with no index, as the harness builds them.
"""

import json
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

from perfbench import datagen, tables
from pinot_tpu.common import DataType, IndexingConfig, Schema, TableConfig
from pinot_tpu.common.config import StarTreeIndexConfig
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import QueryEngine, engine as engine_mod, host_exec, kernels, plan as plan_mod, startree_exec
from pinot_tpu.query.plan import DeviceFallback
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.segment.builder import write_segment
from pinot_tpu.segment.startree import build_star_table, star_table_as_segment

ROOT = Path(__file__).resolve().parent.parent

SCHEMA = Schema.build(
    "sales",
    dimensions=[("country", DataType.STRING), ("device", DataType.STRING), ("year", DataType.INT)],
    metrics=[("a", DataType.LONG), ("b", DataType.LONG), ("c", DataType.INT), ("x", DataType.DOUBLE), ("y", DataType.DOUBLE)],
)
STAR = StarTreeIndexConfig(
    dimensions_split_order=["country", "device", "year"],
    function_column_pairs=["SUM__a", "SUM__b", "SUM__c", "SUM__x", "SUM__y", "MIN__a", "MAX__c", "COUNT__*"],
)


def _rows(seed: int, n: int = 6000) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "country": np.array([f"C{i:02d}" for i in range(20)], dtype=object)[rng.integers(0, 20, n)],
        "device": np.array(["phone", "desktop", "tablet"], dtype=object)[rng.integers(0, 3, n)],
        "year": rng.integers(2018, 2024, n).astype(np.int32),
        "a": rng.integers(1, 10_000_000, n).astype(np.int64),
        "b": rng.integers(0, 9_000_000, n).astype(np.int64),
        "c": rng.integers(-500, 500, n).astype(np.int32),
        "x": rng.random(n) * 100.0,
        "y": rng.random(n) * 100.0,
    }


@pytest.fixture(scope="module")
def sales():
    """(engine over three star-tree segments and two without, engine over the same rows with no index, the rows)."""
    config = TableConfig("sales", indexing=IndexingConfig(star_tree_configs=[STAR]))
    chunks = [_rows(40 + i) for i in range(5)]
    mixed = [SegmentBuilder(SCHEMA, config if i < 3 else None).build(rows, f"s{i}") for i, rows in enumerate(chunks)]
    plain = [SegmentBuilder(SCHEMA).build(rows, f"p{i}") for i, rows in enumerate(chunks)]
    frame = pd.concat([pd.DataFrame({k: (v.astype(str) if v.dtype == object else v) for k, v in rows.items()}) for rows in chunks])
    return QueryEngine(mixed), QueryEngine(plain), frame


def _counters(eng, sql: str):
    with request_ledger("q-star") as led:
        res = eng.execute(sql)
    return res, led.response_fields()["counters"]


# ---------------------------------------------------------------------------
# (a) enqueue, then wait once
# ---------------------------------------------------------------------------

AGG = "SELECT SUM(a), COUNT(*), SUM(a - b) FROM sales WHERE device = 'phone'"
GROUP_BY = "SELECT country, year, SUM(a - b), AVG(c) FROM sales WHERE device IN ('phone', 'tablet') GROUP BY country, year ORDER BY country, year LIMIT 200"


@pytest.mark.parametrize("sql", [AGG, GROUP_BY], ids=["aggregation", "group_by"])
def test_star_and_raw_segments_are_enqueued_together_and_waited_for_once(sales, monkeypatch, sql):
    eng, plain, _ = sales
    waits = []
    real = kernels.wait_packed

    def counting(results, checkpoint=None):
        waits.append(sum(r._host is None for r in results))  # launches this call has to wait for
        return real(results, checkpoint)

    monkeypatch.setattr(engine_mod, "wait_packed", counting)
    monkeypatch.setattr(kernels, "wait_packed", counting)  # a PackedResult's own wait, where none was made for it
    ctx = eng.make_context(sql)
    with request_ledger("q-enqueue") as led:
        pend, pruned = eng._dispatch_all(ctx)
        # nothing was waited for inside the dispatch loop: every segment's result is still in flight
        assert waits == [] and pruned == 0
        assert [d[0] for _, d in pend] == ["dev"] * 5 and all(d[2]._host is None for _, d in pend)
        swapped = [seg.name for seg, d in pend if d[4] is not None]
        assert swapped == ["s0", "s1", "s2"]
        partials, scanned, scan = eng._resolve_partials(ctx, pend, pruned)
    assert waits == [5]  # one call for the five launches, star and raw alike; no unpack waited again
    counters = led.response_fields()["counters"]
    assert counters["deviceReadbackWaits"] == 1 and counters["segmentsDispatched"] == 5
    assert counters["starTreeSegments"] == 3
    assert counters["starTreeRecords"] == sum(seg.extras["startree"][0].n_rows for seg, d in pend if d[4] is not None)
    assert eng.reduce(ctx, partials) == plain.execute(sql).rows
    # scan attribution keeps the mode `startree` for the three, and they report the records they read, not the rows
    by_path = {key.rsplit(":", 1)[1]: n for key, n in scan["predicates"].items()}
    assert by_path.pop("STARTREE_INDEX") == 3 and sum(by_path.values()) == 2
    assert scanned < plain.execute(sql).num_docs_scanned


def test_a_star_table_is_wrapped_once_and_counted_then(sales):
    config = TableConfig("sales", indexing=IndexingConfig(star_tree_configs=[STAR]))
    eng = QueryEngine([SegmentBuilder(SCHEMA, config).build(_rows(77), f"w{i}") for i in range(2)])
    first, c1 = _counters(eng, AGG)
    again, c2 = _counters(eng, AGG)
    assert (c1["starTreeBuilds"], c1["starTreeSegments"]) == (2, 2)
    assert (c2["starTreeBuilds"], c2["starTreeSegments"]) == (0, 2) and first.rows == again.rows


def test_the_plan_span_is_recorded_once_a_swapped_segment(sales):
    eng, _, _ = sales
    with request_ledger("q-span") as led:
        eng.execute(GROUP_BY)
    spans = led.to_wire()["spans"]
    total, _self, n, _cpu = spans["server.plan.startree"]
    assert n == 3 and total >= 0.0
    # the way back to the query's own aggregates is a span of its own, inside the segment's unpack
    assert spans["server.unpack.startree"][2] == 3 and spans["server.unpack.startree"][0] <= spans["server.unpack"][0]
    with request_ledger("q-nospan") as led:
        eng.execute("SELECT COUNT(*) FROM sales WHERE a > 5")  # a raw measure in the filter: no table matches
    assert not [n for n in led.to_wire()["spans"] if n.endswith(".startree")] and led.response_fields()["counters"]["starTreeSegments"] == 0


# ---------------------------------------------------------------------------
# (b) SSB, with trees A and B against no index
# ---------------------------------------------------------------------------

SSB_SEED, SSB_ROWS, SSB_SEGMENTS = 4_400_000_044, 3_000, 3
STAR_ANSWERED = {"q2.1", "q2.2", "q2.3", "q3.1", "q4.1", "q4.2"}
SSB_TEMPLATES = ["q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2", "q3.3", "q3.4", "q4.1", "q4.2", "q4.3"]


@pytest.fixture(scope="module")
def ssb():
    """SSB's flat table at a rehearsal's size, as the harness builds it: under `ssb-flat-startree-1srv`'s
    declaration (trees A and B) and under none."""
    config = json.loads((ROOT / "perfbench" / "configs" / "ssb-flat-startree-1srv.json").read_text())
    tables.rehearse(config)
    ds = datagen.dataset_module(config["dataset"])
    (declared,) = tables.declared(config, ds)
    indexed, bare = [], []
    for i in range(SSB_SEGMENTS):
        cols = ds.segment(SSB_SEED, i, SSB_ROWS, config)
        indexed.append(datagen.build_segment(ds, cols, f"lineorder_{i}", declared))
        bare.append(datagen.build_segment(ds, cols, f"lineorder_{i}"))
    assert [len(seg.extras["startree"]) for seg in indexed] == [2] * SSB_SEGMENTS and not any(seg.extras for seg in bare)
    return ds, QueryEngine(indexed), QueryEngine(bare)


@pytest.mark.parametrize("template", SSB_TEMPLATES)
def test_ssb_answers_with_the_two_trees_equal_the_scan_to_the_unit(ssb, template):
    ds, indexed, bare = ssb
    assert sorted(ds.TEMPLATES) == sorted(SSB_TEMPLATES)
    t = ds.TEMPLATES[template]
    rng = np.random.default_rng(SSB_SEED)
    for _ in range(2):
        sql = t.render(t.draw(rng))
        got, counters = _counters(indexed, sql)
        want = bare.execute(sql)
        assert got.rows == want.rows, sql
        assert counters["starTreeSegments"] == (SSB_SEGMENTS if template in STAR_ANSWERED else 0), sql
        assert (counters["starTreeRecords"] > 0) == (template in STAR_ANSWERED)
        assert counters["deviceReadbackWaits"] == 1 and counters["segmentsDispatched"] == SSB_SEGMENTS


def test_a_star_tables_plan_keeps_the_spec_and_program_it_had_before_the_compact_group_space(ssb):
    """Q2.1 over tree A's table of the first segment: 7 years x 900-odd brands, under plan.COMPACT_MIN_GROUPS.
    The group spec and the program's name are PR 45's parent's, written down."""
    ds, indexed, _ = ssb
    t = ds.TEMPLATES["q2.1"]
    swap = startree_exec.swap(indexed.segments[0], indexed.make_context(t.render(t.draw(np.random.default_rng(SSB_SEED)))))
    plan = plan_mod.plan_segment(swap.seg, swap.ctx)
    assert (plan.spec[2], kernels.program_name(plan.spec)) == (("groups", ("d_year", "p_brand1"), 6656, 4), "seg_groupby_a2212025")


def test_ssb_integer_pairs_are_staged_as_integers(ssb):
    _, indexed, _ = ssb
    seg = indexed.segments[0]
    a, b = seg.extras["startree"]
    assert a.dimensions == ["d_year", "p_category", "p_brand1", "s_region"] and a.function_column_pairs == ["SUM__lo_revenue"]
    assert b.function_column_pairs == ["SUM__lo_revenue", "SUM__lo_supplycost"]
    for st in (a, b):
        assert all(st.arrays[p].dtype == np.int64 for p in ["__count", *st.function_column_pairs])
        star = star_table_as_segment(seg, st)
        assert all(star.columns[p].data_type == DataType.LONG for p in st.function_column_pairs)
        assert int(st.arrays["__count"].sum()) == seg.n_docs
        assert int(st.arrays["SUM__lo_revenue"].sum()) == int(seg.columns["lo_revenue"].materialize().astype(np.int64).sum())


# ---------------------------------------------------------------------------
# (c) a linear aggregate is served from stored pairs; nothing else is
# ---------------------------------------------------------------------------

MATCHED = [
    ("SUM(a - b)", lambda f: int((f.a - f.b).sum())),
    ("SUM(a + b - c)", lambda f: int((f.a + f.b - f.c).sum())),
    ("SUM(-a + b)", lambda f: int((f.b - f.a).sum())),
    ("SUM(a - (b - c))", lambda f: int((f.a - f.b + f.c).sum())),
    ("SUM(a + a - b)", lambda f: int((2 * f.a - f.b).sum())),
    ("AVG(a - b)", lambda f: float((f.a - f.b).sum()) / len(f)),
]
NOT_MATCHED = ["SUM(a * b)", "SUM(a - 1)", "SUM(a / b)", "MIN(a - b)", "MAX(a + c)", "SUM(a - a)", "SUM(x - y)", "AVG(x + a)", "SUM(a % b)"]


@pytest.mark.parametrize("agg, want", MATCHED, ids=[m[0] for m in MATCHED])
def test_a_sum_of_columns_is_matched_and_exact(sales, agg, want):
    eng, plain, frame = sales
    scalar, counters = _counters(eng, f"SELECT {agg} FROM sales WHERE device = 'desktop'")
    assert counters["starTreeSegments"] == 3
    assert scalar.rows == [[want(frame[frame.device == "desktop"])]]
    sql = f"SELECT year, {agg} FROM sales GROUP BY year ORDER BY year LIMIT 10"
    grouped, counters = _counters(eng, sql)
    assert counters["starTreeSegments"] == 3
    assert grouped.rows == [[int(y), want(g)] for y, g in frame.groupby("year")] == plain.execute(sql).rows


@pytest.mark.parametrize("agg", NOT_MATCHED)
def test_what_does_not_distribute_over_a_pre_aggregate_is_not_matched(sales, agg):
    eng, plain, _ = sales
    sql = f"SELECT {agg} FROM sales WHERE year >= 2020"
    (st,) = eng.segments[0].extras["startree"]
    assert not startree_exec.matches(eng.make_context(sql), st)
    got, counters = _counters(eng, sql)
    assert counters["starTreeSegments"] == 0 and got.rows == plain.execute(sql).rows


def test_one_stored_pair_an_aggregate_hands_the_star_programs_frame_on_as_it_is():
    frame = pd.DataFrame({"k0": ["x", "y"], "a0p0": [1.0, 2.0], "a1p0": [3.0, 4.0]})
    ctx = QueryEngine([SegmentBuilder(SCHEMA).build(_rows(5, 50), "one")]).make_context("SELECT country, SUM(a), MIN(a) FROM sales GROUP BY country")
    star_ctx, mapping = startree_exec._rewrite(ctx)
    assert mapping == [("sum", ((0, 1),)), ("copy", 1)] and startree_exec._convert_frame(ctx, mapping, frame) is frame
    swapped = [("copy", 1), ("sum", ((0, 1),))]  # MIN(a), SUM(a) asked the other way round: columns change places
    out = startree_exec._convert_frame(ctx, swapped, frame)
    assert out is not frame and out["a0p0"].tolist() == [3.0, 4.0] and out["a1p0"].tolist() == [1.0, 2.0] and out["k0"].tolist() == ["x", "y"]


def test_each_stored_sum_is_asked_for_once():
    eng = QueryEngine([SegmentBuilder(SCHEMA).build(_rows(5, 50), "one")])
    ctx = eng.make_context("SELECT SUM(a - b), AVG(a), SUM(b), COUNT(*), AVG(a - b) FROM sales")
    star_ctx, mapping = startree_exec._rewrite(ctx)
    assert [str(s.arg) for s in star_ctx.aggregations] == ["SUM__a", "SUM__b", "__count"]
    assert mapping == [("sum", ((0, 1), (1, -1))), ("avg", ((0, 1),), 2), ("sum", ((1, 1),)), ("count", 2), ("avg", ((0, 1), (1, -1)), 2)]


# ---------------------------------------------------------------------------
# (d) integer pairs are integers, exact where a float64 pair is not
# ---------------------------------------------------------------------------


def test_integer_pairs_stay_int64_and_exact_past_2_to_the_53(tmp_path):
    n = 4096
    rng = np.random.default_rng(9)
    schema = Schema.build("big", dimensions=[("k", DataType.INT)], metrics=[("v", DataType.LONG), ("w", DataType.INT), ("f", DataType.DOUBLE)])
    data = {
        "k": (np.arange(n) % 8).astype(np.int32),
        "v": ((1 << 52) + 2 * rng.integers(0, 1 << 20, n) + 1).astype(np.int64),  # odd, so every sum's low bits matter
        "w": rng.integers(-1000, 1000, n).astype(np.int32),
        "f": rng.random(n),
    }
    star_cfg = StarTreeIndexConfig(dimensions_split_order=["k"], function_column_pairs=["SUM__v", "MIN__v", "MAX__w", "AVG__w", "SUM__f"])
    seg = SegmentBuilder(schema, TableConfig("big", indexing=IndexingConfig(star_tree_configs=[star_cfg]))).build(data, "b0")
    (st,) = seg.extras["startree"]
    exact = [sum(int(x) for x in data["v"][data["k"] == k]) for k in range(8)]  # python integers: no width to leave
    assert min(exact) > 1 << 53
    assert st.arrays["SUM__v"].dtype == np.int64 and st.arrays["SUM__v"].tolist() == exact
    in_float = pd.Series(data["v"].astype(np.float64)).groupby(data["k"]).sum().to_numpy()
    assert [int(x) for x in in_float] != exact  # what the parent stored: the sums rounded to 53 bits
    assert {p: st.arrays[p].dtype for p in ("MIN__v", "MAX__w", "SUM__w", "SUM__f", "__count")} == {
        "MIN__v": np.int64, "MAX__w": np.int64, "SUM__w": np.int64, "SUM__f": np.float64, "__count": np.int64,
    }  # fmt: skip
    star = star_table_as_segment(seg, st)
    kinds = {p: (star.columns[p].data_type, star.columns[p].forward.dtype) for p in ("SUM__v", "SUM__w", "SUM__f")}
    assert kinds == {"SUM__v": (DataType.LONG, np.int64), "SUM__w": (DataType.LONG, np.int64), "SUM__f": (DataType.DOUBLE, np.float64)}
    # persisted and loaded, the pairs are the integers they were
    loaded = load_segment(write_segment(seg, tmp_path))
    (back,) = loaded.extras["startree"]
    assert back.arrays["SUM__v"].dtype == np.int64 and back.arrays["SUM__v"].tolist() == exact


def test_a_sum_that_could_leave_int64_is_kept_as_the_scan_adds_it():
    """A LONG null's placeholder is int64's minimum: a record's int64 sum of two wraps, the scan's float64 only rounds."""
    schema = Schema.build("n", dimensions=[("k", DataType.INT)], metrics=[("v", DataType.LONG)])
    data = {"k": np.zeros(4, np.int32), "v": np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).min, 5, 7], np.int64)}
    seg = SegmentBuilder(schema).build(data, "n0")
    st = build_star_table(seg, StarTreeIndexConfig(dimensions_split_order=["k"], function_column_pairs=["SUM__v", "MIN__v"]))
    assert st.arrays["SUM__v"].dtype == np.float64 and st.arrays["SUM__v"][0] == pytest.approx(-(2.0**64))
    assert st.arrays["MIN__v"].dtype == np.int64  # an extreme cannot overflow


def test_a_star_table_persisted_by_the_parent_still_loads_and_answers(sales, tmp_path):
    """Before PR 44 every stored pair was float64; such a file loads, is staged as DOUBLE and answers what the
    scan answers. Its pairs serve no sum of several columns: that is for integer pairs."""
    _, plain, _ = sales
    config = TableConfig("sales", indexing=IndexingConfig(star_tree_configs=[STAR]))
    seg = SegmentBuilder(SCHEMA, config).build(_rows(40), "old0")
    (st,) = seg.extras["startree"]
    for p in st.function_column_pairs:
        st.arrays[p] = st.arrays[p].astype(np.float64)  # the parent's build_star_table: `.astype(np.float64)`, `g.sum()`
    loaded = load_segment(write_segment(seg, tmp_path))
    (old,) = loaded.extras["startree"]
    assert all(old.arrays[p].dtype == np.float64 for p in old.function_column_pairs)
    assert all(star_table_as_segment(loaded, old).columns[p].data_type == DataType.DOUBLE for p in old.function_column_pairs)
    eng, ref = QueryEngine([loaded]), QueryEngine([SegmentBuilder(SCHEMA).build(_rows(40), "ref0")])
    sql = "SELECT device, SUM(a), MIN(a), AVG(c) FROM sales WHERE year > 2019 GROUP BY device ORDER BY device LIMIT 10"
    got, counters = _counters(eng, sql)
    assert counters["starTreeSegments"] == 1 and got.rows == ref.execute(sql).rows
    both, counters = _counters(eng, "SELECT SUM(a - b) FROM sales")
    assert counters["starTreeSegments"] == 0 and both.rows == ref.execute("SELECT SUM(a - b) FROM sales").rows


# ---------------------------------------------------------------------------
# (e) what the blocking path guarded
# ---------------------------------------------------------------------------


def _star_segment(name: str, seed: int = 40):
    return SegmentBuilder(SCHEMA, TableConfig("sales", indexing=IndexingConfig(star_tree_configs=[STAR]))).build(_rows(seed), name)


def test_no_swap_under_upserts_valid_docs():
    seg, rows = _star_segment("u0"), _rows(40)
    valid = np.arange(seg.n_docs) % 3 != 0
    seg.extras["valid_docs"] = lambda n: valid[:n]
    got, counters = _counters(QueryEngine([seg]), AGG)
    keep = valid & (rows["device"] == "phone")
    assert counters["starTreeSegments"] == 0
    assert got.rows == [[int(rows["a"][keep].sum()), int(keep.sum()), int((rows["a"] - rows["b"])[keep].sum())]]


def test_no_swap_under_null_handling_with_a_null_vector():
    rng = np.random.default_rng(33)
    n = 2000
    schema = Schema.build("s", dimensions=[("d", DataType.STRING)], metrics=[("v", DataType.LONG)])
    v = rng.integers(1, 50, n).astype(object)
    v[rng.random(n) < 0.3] = None
    data = {"d": np.asarray(["x", "y"], dtype=object)[rng.integers(0, 2, n)], "v": v}
    star = StarTreeIndexConfig(dimensions_split_order=["d"], function_column_pairs=["SUM__v"])
    seg = SegmentBuilder(schema, TableConfig("s", indexing=IndexingConfig(null_handling=True, star_tree_configs=[star]))).build(data, "st0")
    eng = QueryEngine([seg])
    got, counters = _counters(eng, "SET enableNullHandling=true; SELECT SUM(v) FROM s WHERE d = 'x'")
    assert counters["starTreeSegments"] == 0
    assert got.rows == [[sum(e for e, d in zip(v, data["d"]) if e is not None and d == "x")]]
    _, counters = _counters(eng, "SELECT COUNT(*) FROM s WHERE d = 'x'")
    assert counters["starTreeSegments"] == 1  # without the option the placeholders stand, as they always did


@pytest.mark.parametrize(
    "sql",
    [
        "SELECT SUM(a) FILTER (WHERE device = 'phone') FROM sales",
        "SELECT COUNT(*) FROM sales WHERE country IS NULL",
        "SELECT COUNT(*) FROM sales WHERE country IS NOT NULL AND year > 2019",
    ],
)
def test_no_swap_for_filter_where_and_is_null(sales, sql):
    eng, plain, _ = sales
    got, counters = _counters(eng, sql)
    assert counters["starTreeSegments"] == 0 and got.rows == plain.execute(sql).rows


def test_a_star_plan_that_falls_back_answers_from_the_star_table_on_the_host(sales, monkeypatch):
    eng, plain, _ = sales
    real = engine_mod.plan_segment
    hosted = []

    def refuses_star_tables(seg, ctx, **kw):
        if seg.name.endswith("__star"):
            raise DeviceFallback("a star table the device cannot plan", reason="test")
        return real(seg, ctx, **kw)

    real_host = QueryEngine._host_segment

    def host(self, seg, ctx, extra_mask=None):
        hosted.append(seg.name)
        return real_host(self, seg, ctx, extra_mask=extra_mask)

    monkeypatch.setattr(engine_mod, "plan_segment", refuses_star_tables)
    monkeypatch.setattr(QueryEngine, "_host_segment", host)
    for sql in (AGG, GROUP_BY):
        hosted.clear()
        ctx = eng.make_context(sql)
        with request_ledger("q-fallback") as led:
            pend, pruned = eng._dispatch_all(ctx)
            partials, scanned, _ = eng._resolve_partials(ctx, pend, pruned)
        assert hosted == ["s0__star", "s1__star", "s2__star"]  # the few records, not the raw rows
        assert [(d[0], eng._scan_mode(d)) for _, d in pend] == [("ready", "startree")] * 3 + [("dev", "device")] * 2
        assert led.response_fields()["counters"]["starTreeSegments"] == 3
        assert eng.reduce(ctx, partials) == plain.execute(sql).rows


def test_the_sparse_paths_collision_reruns_the_star_table_on_the_host(sales, monkeypatch):
    eng, plain, _ = sales
    hosted = []
    real_frame = host_exec.group_frame

    def frame(seg, ctx, mask):
        hosted.append((seg.name, [str(a.arg) for a in ctx.aggregations]))
        return real_frame(seg, ctx, mask)

    monkeypatch.setattr(host_exec, "group_frame", frame)
    sql = "SELECT country, device, year, SUM(a - b), COUNT(*) FROM sales GROUP BY country, device, year ORDER BY country, device, year LIMIT 1000"
    want = plain.execute(sql).rows
    # 360 present groups and 64 slots: the sorted compaction's slots collide in every segment, star and raw alike
    monkeypatch.setattr(plan_mod, "MAX_DENSE_GROUPS", 64)
    got, counters = _counters(eng, sql)
    assert got.rows == want and len(want) == 360
    assert counters["starTreeSegments"] == 3 and counters["deviceReadbackWaits"] == 1
    # a swapped segment reruns its star table under the rewritten query; a raw one its rows under the query's own
    assert hosted[:3] == [(f"s{i}__star", ["SUM__a", "SUM__b", "__count"]) for i in range(3)]
    assert [name for name, _ in hosted[3:]] == ["s3", "s4"]
