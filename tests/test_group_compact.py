"""The compact group space (plan.group_spec's "groups_compact", PR 45): where
the product of a group-by's single-value key cardinalities reaches
plan.COMPACT_MIN_GROUPS, the fused program renumbers each key by the values
the filter leaves and contracts over plan.COMPACT_SLOTS slots
(kernels._compact_groups); a segment whose surviving combinations pass the
slots is launched again under the plan it had before (the dense space up to
plan.MAX_DENSE_GROUPS, the sort-compaction path past it).

Every answer is held to the host executor's (`QueryEngine._host_segment`,
numpy over the same segments) over three segments whose dictionaries differ.
The byte-plane kernel is on, interpreted (`PINOT_TPU_PALLAS=1`), as the chip
runs it; one group of cases runs without it, as the CPU does.
"""

import numpy as np
import pytest

import pinot_tpu  # noqa: F401  (x64 before jax.numpy is touched)
from pinot_tpu.common import DataType, IndexingConfig, Schema, TableConfig
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import QueryEngine, kernels
from pinot_tpu.query import plan as plan_mod
from pinot_tpu.query.plan import plan_segment
from pinot_tpu.segment import SegmentBuilder

SLOTS = plan_mod.COMPACT_SLOTS
ROWS = 6000
CITIES = 60  # a customer's and a supplier's: 60 x 60 x 7 years = 25,200 dense groups, past COMPACT_MIN_GROUPS


def _columns(i: int, nulls: bool = False) -> dict:
    """Segment `i`: it draws its cities from a range that moves with `i`, so the keys' dictionaries differ in
    values and in size; `*_nation` is the city's tenth (the filter of Q3.2's shape: on another column, correlated
    with a key), `*_band` a wide key's forty; `a` and `b` are 1,200 values each (1.44M dense groups, past
    MAX_DENSE_GROUPS)."""
    rng = np.random.default_rng(450 + i)
    c_city = rng.integers(5 * i, CITIES - 3 * i, ROWS).astype(np.int32)
    s_city = rng.integers(2 * i, CITIES, ROWS).astype(np.int32)
    a, b = rng.integers(0, 1200, ROWS).astype(np.int32), rng.integers(0, 1200 - 100 * i, ROWS).astype(np.int32)
    rev = rng.integers(-50_000, 1_000_000, ROWS).astype(object if nulls else np.int64)
    if nulls:
        rev[rng.random(ROWS) < 0.2] = None
    return {
        "c_city": c_city, "s_city": s_city, "c_nation": c_city // 10, "s_nation": s_city // 10,
        "year": rng.integers(1992, 1999, ROWS).astype(np.int32),
        "a": a, "b": b, "a_band": a // 40, "b_band": b // 40,
        "disc": rng.integers(0, 11, ROWS).astype(np.int32),
        "rev": rev, "price": np.round(rng.random(ROWS) * 1e5, 2) - 2e4,
    }  # fmt: skip


SCHEMA = Schema.build(
    "t",
    dimensions=[(c, DataType.INT) for c in ("c_city", "s_city", "c_nation", "s_nation", "year", "a", "b", "a_band", "b_band", "disc")],
    metrics=[("rev", DataType.LONG), ("price", DataType.DOUBLE)],
)


@pytest.fixture(scope="module")
def segs():
    return [SegmentBuilder(SCHEMA).build(_columns(i), f"t_{i}") for i in range(3)]


@pytest.fixture(params=["1"], ids=["kernel-interpreted"])
def kernel_on(request, monkeypatch):
    """The programs traced with the byte-plane kernel on (interpreted; "0": off), and dropped afterwards."""
    monkeypatch.setenv("PINOT_TPU_PALLAS", request.param)
    kernels.get_packed_kernel.cache_clear()
    yield request.param
    kernels.get_packed_kernel.cache_clear()


def run(segments, sql: str):
    """(rows, counters, deviceWork) of `sql` through the device path, and the rows the host executor gives."""
    eng = QueryEngine(segments)
    with request_ledger("q-compact") as led:
        got = eng.execute(sql).rows
    ctx = eng.make_context(sql)
    want = eng.reduce(ctx, [eng._host_segment(seg, ctx, extra_mask=_valid(seg))[0] for seg in segments])
    wire = led.response_fields()
    return got, [list(r) for r in want], wire["counters"], wire["deviceWork"]


def _valid(seg):
    valid = seg.extras.get("valid_docs")
    return valid(seg.n_docs) if valid is not None else None


def same(got, want) -> None:
    """Keys, counts and integer sums to the unit; a DOUBLE's sum to its last bits (the limbs round once, numpy by pairs)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for x, y in zip(g, w):
            if isinstance(y, float) and not float(y).is_integer():
                assert x == pytest.approx(y, rel=1e-12), (g, w)
            else:
                assert x == y, (g, w)


def kinds(segments, sql: str, **kw) -> set:
    eng = QueryEngine(segments)
    ctx = eng.make_context(sql)
    return {plan_segment(seg, ctx, valid_mask=_valid(seg), **kw).spec[2][0] for seg in segments}


#: the filter on another column that is correlated with a key (Q3.2's shape), and on the keys themselves (Q3.3's)
FILTERS = {
    ("q3.2", "three-keys"): "c_nation = 2 AND s_nation = 3 AND year >= 1993 AND year <= 1997",
    ("q3.3", "three-keys"): "(c_city = 21 OR c_city = 27) AND (s_city = 33 OR s_city = 38) AND year >= 1993",
    ("q3.2", "two-keys"): "a_band = 3 AND b_band >= 5 AND b_band <= 6",
    ("q3.3", "two-keys"): "a >= 100 AND a < 160 AND b >= 500 AND b < 620",
}
KEYS = {"two-keys": "a, b", "three-keys": "c_city, s_city, year"}
Q32 = FILTERS["q3.2", "three-keys"]
AGGS = {
    "count": "COUNT(*)",
    "int-sum": "SUM(rev)",
    "int-avg": "AVG(rev)",
    "min-max": "MIN(rev), MAX(rev), MINMAXRANGE(price)",
    "double-sum": "SUM(price), AVG(price)",
    "distinctcount": "DISTINCTCOUNT(disc), COUNT(*)",
    "expression": "SUM(rev - disc), SUM(price * (1 - disc / 100))",
}


def sql_of(keys: str, aggs: str, where: str | None) -> str:
    where = f" WHERE {where}" if where else ""
    return f"SELECT {keys}, {aggs} FROM t{where} GROUP BY {keys} ORDER BY {keys} LIMIT 100000"


@pytest.mark.parametrize("agg", list(AGGS))
@pytest.mark.parametrize("keys", list(KEYS))
@pytest.mark.parametrize("shape", ["q3.2", "q3.3"])
def test_the_compact_space_gives_the_host_executors_answer(segs, kernel_on, shape, keys, agg):
    sql = sql_of(KEYS[keys], AGGS[agg], FILTERS[shape, keys])
    assert kinds(segs, sql) == {"groups_compact"}
    got, want, counters, work = run(segs, sql)
    assert len(want) > 0
    same(got, want)
    assert (counters["groupCompactSegments"], counters["groupCompactFallbacks"], counters["segmentsDispatched"]) == (3, 0, 3)
    assert counters["deviceReadbackWaits"] == 1
    # a program's static work names the prelude, one call a renumbered key, and the kernel over the slots, not the
    # product (segments whose dictionaries round to other widths are other programs)
    assert sum(w["kernels"]["query.group_compact"]["calls"] for w in work.values()) == 3 * (2 if keys == "two-keys" else 3)
    for w in work.values():
        planes = w["kernels"]["ops.grouped_planes2"]
        rows = -(-ROWS // 4096) * 4096  # a launch's, in whole chunks of the kernel
        plane_rows = planes["flops"] / planes["calls"] / (2.0 * rows * SLOTS)  # the kernel's MACs: rows x slots x plane rows, never x 25,200
        assert plane_rows == int(plane_rows) and plane_rows <= 4 + 4 + 2 * 12 + 1


@pytest.mark.parametrize("kernel_on", ["1", "0"], ids=["kernel-interpreted", "kernel-off"], indirect=True)
@pytest.mark.parametrize("agg", ["int-sum", "min-max", "double-sum", "distinctcount"])
def test_the_compact_space_with_the_kernel_on_or_off(segs, kernel_on, agg):
    sql = sql_of(KEYS["three-keys"], AGGS[agg], Q32)
    got, want, counters, work = run(segs, sql)
    same(got, want)
    assert counters["groupCompactSegments"] == 3 and counters["groupCompactFallbacks"] == 0
    assert any("ops.grouped_planes2" in w["kernels"] for w in work.values()) == (kernel_on == "1")


def test_a_distinct_over_a_large_product_takes_the_compact_space(segs, kernel_on):
    eng = QueryEngine(segs)
    sql = "SELECT DISTINCT c_city, s_city, year FROM t WHERE c_nation = 1 AND s_nation = 4 ORDER BY c_city, s_city, year LIMIT 100000"
    assert kinds(segs, sql) == {"groups_compact"}
    with request_ledger("q-distinct") as led:
        got = eng.execute(sql).rows
    ctx = eng.make_context(sql)
    want = eng.reduce(ctx, [eng._host_segment(seg, ctx)[0] for seg in segs])
    assert got == [list(r) for r in want] and len(got) > 0
    assert led.response_fields()["counters"]["groupCompactFallbacks"] == 0


def test_no_row_passes(segs, kernel_on):
    """`total` 0: an empty frame a segment, nothing launched again."""
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*)", "c_nation = 2 AND c_city = 51")  # no city 51 in nation 2, and no column's range says so
    got, want, counters, _ = run(segs, sql)
    assert got == want == []
    assert (counters["groupCompactSegments"], counters["groupCompactFallbacks"]) == (3, 0)
    eng = QueryEngine(segs)
    partials, scanned, _ = eng.partials(eng.make_context(sql))
    assert scanned == 0 and all(len(p) == 0 for p in partials)


def test_an_upsert_mask_passes_through(kernel_on):
    segments = [SegmentBuilder(SCHEMA).build(_columns(i), f"u_{i}") for i in range(2)]
    for i, seg in enumerate(segments):
        live = np.random.default_rng(70 + i).random(ROWS) < 0.5
        seg.extras["valid_docs"] = lambda n, m=live: m[:n]
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*), MAX(price)", Q32)
    assert kinds(segments, sql) == {"groups_compact"}
    got, want, counters, _ = run(segments, sql)
    same(got, want)
    assert len(want) > 0 and counters["groupCompactSegments"] == 2 and counters["groupCompactFallbacks"] == 0
    without = QueryEngine([SegmentBuilder(SCHEMA).build(_columns(i), f"u_{i}") for i in range(2)]).execute(sql).rows
    assert sum(r[-2] for r in got) < sum(r[-2] for r in without)  # the mask bit: fewer rows counted


def test_null_handling_passes_through(kernel_on):
    cfg = TableConfig("t", indexing=IndexingConfig(null_handling=True))
    segments = [SegmentBuilder(SCHEMA, cfg).build(_columns(i, nulls=True), f"n_{i}") for i in range(2)]
    sql = "SET enableNullHandling = true; " + sql_of(KEYS["three-keys"], "SUM(rev), COUNT(rev), COUNT(*), AVG(rev)", Q32)
    assert kinds(segments, sql) == {"groups_compact"}
    got, want, counters, _ = run(segments, sql)
    same(got, want)
    assert counters["groupCompactFallbacks"] == 0
    assert any(r[-3] < r[-2] for r in got)  # some group holds a null: COUNT(rev) under COUNT(*)
    # and a filter on the nullable column itself (the three-valued WHERE rides in the mask the prelude reads)
    sql = "SET enableNullHandling = true; " + sql_of(KEYS["three-keys"], "COUNT(*)", Q32 + " AND rev > 1000")
    got, want, _, _ = run(segments, sql)
    same(got, want)


# ---------------------------------------------------------------------------
# overflow: the rows decide, and the answer stays the reference's
# ---------------------------------------------------------------------------


def test_an_overflowing_segment_is_launched_again_under_the_dense_plan(segs, kernel_on):
    """No filter on the cities: some 50 x 55 x 7 combinations of present values, past the 4,096 slots."""
    one = segs[:1]
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*), MIN(price)", "year >= 1992")
    eng = QueryEngine(one)
    ctx = eng.make_context(sql)
    compact, dense = plan_segment(one[0], ctx), plan_segment(one[0], ctx, compact=False)
    assert (compact.spec[2][0], dense.spec[2][0]) == ("groups_compact", "groups")
    got, want, counters, work = run(one, sql)
    same(got, want)
    assert (counters["groupCompactSegments"], counters["groupCompactFallbacks"], counters["segmentsDispatched"]) == (1, 1, 2)
    assert counters["deviceReadbackWaits"] == 2  # the query's wait, and one more for what was launched again
    # two programs, never one that holds both contractions: the compact one's kernel over the slots, the dense one's over the product
    assert set(work) == {kernels.program_name(compact.spec), kernels.program_name(dense.spec)}
    flops = {name: w["kernels"]["ops.grouped_planes2"]["flops"] for name, w in work.items()}
    assert flops[kernels.program_name(dense.spec)] / flops[kernels.program_name(compact.spec)] == dense.spec[2][2] / SLOTS
    assert "query.group_compact" not in work[kernels.program_name(dense.spec)]["kernels"]


def test_overflows_of_a_query_are_enqueued_together_and_waited_for_once_more(segs, kernel_on):
    """Two of three segments overflow (the third's filter leaves few values): one more wait, for both."""
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*)", "c_city < 42")  # segment 2 holds cities 10..53 only
    got, want, counters, _ = run(segs, sql)
    same(got, want)
    assert counters["groupCompactSegments"] == 3 and 1 <= counters["groupCompactFallbacks"] <= 3
    assert counters["deviceReadbackWaits"] == 2
    assert counters["segmentsDispatched"] == 3 + counters["groupCompactFallbacks"]


def test_an_overflow_met_outside_the_batched_path_is_launched_again_too(segs, kernel_on):
    """`partials_iter` and `_execute_segment` finish a segment by themselves (`_finish_segment`)."""
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*)", None)
    eng = QueryEngine(segs[:2])
    ctx = eng.make_context(sql)
    with request_ledger("q-iter") as led:
        streamed = [partial for _, partial, _, _ in eng.partials_iter(ctx)]
    assert led.response_fields()["counters"]["groupCompactFallbacks"] == 2
    want = eng.reduce(ctx, [eng._host_segment(seg, ctx)[0] for seg in segs[:2]])
    same(eng.reduce(ctx, streamed), [list(r) for r in want])


def test_a_product_past_the_dense_limit_compacts(segs, kernel_on):
    sql = sql_of("a, b", "SUM(rev), COUNT(*)", "a >= 100 AND a < 160 AND b < 50")
    assert kinds(segs, sql) == {"groups_compact"} and kinds(segs, sql, compact=False) == {"groups_sparse"}
    got, want, counters, work = run(segs, sql)
    same(got, want)
    assert len(want) > 30 and (counters["groupCompactSegments"], counters["groupCompactFallbacks"]) == (3, 0)
    assert not any("sort" in k for w in work.values() for k in w["kernels"])


def test_a_product_past_the_dense_limit_overflows_into_the_sort_compaction_path(segs, kernel_on, monkeypatch):
    one = segs[:1]
    sql = sql_of("a, b", "SUM(rev), COUNT(*)", "a < 600")
    eng = QueryEngine(one)
    ctx = eng.make_context(sql)
    sparse = plan_segment(one[0], ctx, compact=False)
    assert sparse.spec[2][0] == "groups_sparse"

    def no_host(*_a, **_k):
        raise AssertionError("the overflow went to the host executor")

    got, want, counters, work = run(one, sql)
    monkeypatch.setattr("pinot_tpu.query.host_exec.group_frame", no_host)
    again = QueryEngine(one).execute(sql).rows  # the second launch is the device's: nothing reaches the host's group-by
    same(got, want)
    assert again == got and len(got) > SLOTS // 4
    assert (counters["groupCompactSegments"], counters["groupCompactFallbacks"]) == (1, 1)
    assert kernels.program_name(sparse.spec) in work


# ---------------------------------------------------------------------------
# a key too wide to renumber
# ---------------------------------------------------------------------------


def test_a_key_wider_than_the_presence_bound_is_carried_whole(segs, kernel_on, monkeypatch):
    """With the bound at 16 the cities (up to 60 values) are carried at their cardinality and the year alone is
    renumbered: 50-odd x 50-odd x (one year) fits the slots."""
    monkeypatch.setattr(plan_mod, "COMPACT_MAX_KEY_CARD", 16)
    sql = sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*), MAX(price)", "year = 1995")
    eng = QueryEngine(segs)
    ctx = eng.make_context(sql)
    widths = [plan_segment(seg, ctx).spec[2][4] for seg in segs]
    assert all(w[0][0] == w[1][0] == "whole" and w[2] == ("rank", 8) for w in widths)
    assert [w[0][1] for w in widths] == [seg.columns["c_city"].cardinality for seg in segs]
    got, want, counters, _ = run(segs, sql)
    same(got, want)
    assert len(want) > 500 and (counters["groupCompactSegments"], counters["groupCompactFallbacks"]) == (3, 0)
    # two years: 2 x 50-odd x 50-odd passes the slots, and the dense plan answers
    got, want, counters, _ = run(segs, sql_of(KEYS["three-keys"], "SUM(rev), COUNT(*)", "year >= 1995 AND year <= 1996"))
    same(got, want)
    assert counters["groupCompactFallbacks"] == 3


def test_keys_too_wide_for_the_slots_keep_the_plan_they_had(segs, monkeypatch):
    """What the carried keys contribute alone passes the slots: no launch could fit, so none is tried."""
    monkeypatch.setattr(plan_mod, "COMPACT_MAX_KEY_CARD", 16)
    assert kinds(segs, sql_of("a, b", "COUNT(*)", None)) == {"groups_sparse"}
    monkeypatch.setattr(plan_mod, "COMPACT_SLOTS", 2048)
    assert kinds(segs, sql_of(KEYS["three-keys"], "COUNT(*)", None)) == {"groups"}


def test_under_the_threshold_nothing_changes(segs):
    eng = QueryEngine(segs)
    sql = sql_of("c_city, s_city", "SUM(rev)", Q32)  # 60 x 60 = 3,600 dense groups
    ctx = eng.make_context(sql)
    for seg in segs:
        assert plan_segment(seg, ctx).spec == plan_segment(seg, ctx, compact=False).spec and plan_segment(seg, ctx).spec[2][0] == "groups"
    with request_ledger("q-dense") as led:
        eng.execute(sql)
    assert led.response_fields()["counters"]["groupCompactSegments"] == 0


def test_a_multi_value_key_keeps_its_spec():
    from pinot_tpu.common import FieldSpec

    schema = Schema.build("m", dimensions=[("k", DataType.INT), ("j", DataType.INT)], metrics=[("v", DataType.LONG)])
    schema.add(FieldSpec("tags", DataType.INT, single_value=False))
    rng = np.random.default_rng(3)
    n = 2000
    tags = np.empty(n, dtype=object)
    for i in range(n):
        tags[i] = rng.integers(0, 40, int(rng.integers(1, 3))).astype(np.int32).tolist()
    data = {"k": rng.integers(0, 30, n).astype(np.int32), "j": rng.integers(0, 30, n).astype(np.int32), "tags": tags,
            "v": rng.integers(0, 100, n).astype(np.int64)}  # fmt: skip
    seg = SegmentBuilder(schema).build(data, "m0")
    eng = QueryEngine([seg])
    spec = plan_segment(seg, eng.make_context("SELECT k, j, tags, SUM(v) FROM m GROUP BY k, j, tags LIMIT 10")).spec[2]
    assert spec[0] == "groups_mv" and 30 * 30 * 40 >= plan_mod.COMPACT_MIN_GROUPS


def test_explain_names_the_slots():
    seg = SegmentBuilder(SCHEMA).build(_columns(0), "e_0")
    rows = QueryEngine([seg]).execute("EXPLAIN PLAN FOR " + sql_of(KEYS["three-keys"], "SUM(rev)", Q32)).rows
    assert any(r[0] == f"GROUP_BY(keys=['c_city', 's_city', 'year'], ng={SLOTS})" for r in rows)


# ---------------------------------------------------------------------------
# the prelude by itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [8, 32, 40, 256, 1000])
@pytest.mark.parametrize("share", [0.0, 0.02, 1.0])
def test_a_key_is_renumbered_by_the_values_that_pass(width, share):
    rng = np.random.default_rng(width)
    n = 20_000
    ids = rng.integers(0, width, n).astype(np.int32)
    mask = rng.random(n) < share
    ranks, held, values = (np.asarray(x) for x in kernels._compact_key(ids, mask, width))
    present = np.unique(ids[mask])
    assert int(held) == len(present)
    assert values.tolist() == present.tolist() + [width] * (width - len(present))
    assert np.array_equal(ranks[mask], np.searchsorted(present, ids[mask]))
