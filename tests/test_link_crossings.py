"""A query crosses the host-device link by the query, not by the segment and
the operand (query/kernels.py `stage_operand`, `dispatch_plan_packed`,
`wait_packed`; query/engine.py `_resolve_partials`): operands go in with the
launch as the numpy arrays the plan holds, every result vector's copy to the
host starts at the enqueue, and a query waits for its vectors once.

What must hold: the batched path's partials are `_execute_segment`'s,
segment by segment, for every kind of plan and operand; the two counters an
answer carries (`hostToDeviceTransfers`, `deviceReadbackWaits`) do not grow
with the operand count; a stable operand is staged once; deadlines still cut
a query between segments; the streaming path still yields segment by
segment. Counts and equalities only: no test here reads a clock.
"""

import time

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.common import CacheConfig, DataType, FieldSpec, Schema, TableConfig
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import QueryEngine
from pinot_tpu.query import kernels
from pinot_tpu.query.context import Deadline, QueryTimeoutError
from pinot_tpu.query.plan import plan_segment
from pinot_tpu.segment import SegmentBuilder

N_SEGMENTS = 3
ROWS = 600


def _schema():
    schema = Schema.build(
        "t",
        dimensions=[("d", DataType.INT), ("s", DataType.STRING), ("hi", DataType.INT), ("lo", DataType.INT)],
        metrics=[("v", DataType.LONG), ("x", DataType.DOUBLE)],
    )
    schema.add(FieldSpec("tags", DataType.STRING, single_value=False))
    return schema


def _data(i: int, rows: int = ROWS) -> dict:
    """Segment `i` of the table. The dictionaries of `d` and `s` grow with
    `i`, so LUT operands differ in shape from segment to segment."""
    rng = np.random.default_rng(100 + i)
    vocab = [f"tag{k}" for k in range(6)]
    tags = np.empty(rows, dtype=object)
    for r in range(rows):
        tags[r] = list(rng.choice(vocab, size=int(rng.integers(0, 4)), replace=False))
    return {
        "d": rng.integers(0, 5 + 7 * i, rows).astype(np.int32),
        "s": np.array([f"s{k}" for k in rng.integers(0, 3 + 2 * i, rows)], dtype=object),
        "hi": rng.integers(0, 3000, rows).astype(np.int32),
        "lo": rng.integers(0, 100000, rows).astype(np.int32),
        "v": rng.integers(1, 1000, rows).astype(np.int64),
        "x": rng.random(rows),
        "tags": tags,
    }


@pytest.fixture(scope="module")
def table():
    schema = _schema()
    data = [_data(i) for i in range(N_SEGMENTS)]
    segs = [SegmentBuilder(schema).build(d, f"t_{i}") for i, d in enumerate(data)]
    df = pd.concat([pd.DataFrame(d) for d in data], ignore_index=True)
    return QueryEngine(segs), segs, df


@pytest.fixture(scope="module")
def upsert_table():
    """The same table with a validity mask a segment, as an upsert table's
    segments carry one: the plan gains a docmask operand of the padded doc
    length."""
    schema = _schema()
    segs = []
    for i in range(N_SEGMENTS):
        seg = SegmentBuilder(schema).build(_data(i), f"u_{i}")
        mask = np.random.default_rng(7 + i).random(ROWS) < 0.6
        seg.extras["valid_docs"] = lambda n, m=mask: m[:n]
        segs.append(seg)
    return QueryEngine(segs), segs


def _same(a, b) -> None:
    """Partials compared by value, whatever a plan's partial is made of."""
    if isinstance(a, pd.DataFrame):
        pd.testing.assert_frame_equal(a, b)
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b)
        for x, y in zip(a, b):
            _same(x, y)
    elif isinstance(a, np.ndarray):
        np.testing.assert_array_equal(a, b)
    else:
        assert a == b


#: plan kind -> (fixture, SQL); every kind of operand the lowering emits rides in one of them
QUERIES = {
    "aggregation": ("table", "SELECT COUNT(*), SUM(v), MIN(x), AVG(x) FROM t WHERE d BETWEEN 1 AND 9 AND v > 10"),
    "dense_groupby": ("table", "SELECT d, s, SUM(v), COUNT(*) FROM t WHERE s IN ('s0', 's2', 's4') GROUP BY d, s LIMIT 1000"),
    "sparse_groupby": ("table", "SELECT d, hi, lo, SUM(v) FROM t GROUP BY d, hi, lo LIMIT 5000"),
    "distinct": ("table", "SELECT DISTINCT d, s FROM t WHERE v < 900 LIMIT 1000"),
    "selection": ("table", "SELECT d, s, v FROM t WHERE x < 0.5 LIMIT 50"),
    "selection_order_by": ("table", "SELECT d, v FROM t WHERE s <> 's1' ORDER BY v DESC, d LIMIT 40"),
    "upsert_docmask": ("upsert_table", "SELECT d, SUM(v), COUNT(*) FROM t WHERE v > 5 GROUP BY d LIMIT 1000"),
    "mv_filter": ("table", "SELECT COUNT(*), SUM(v) FROM t WHERE tags IN ('tag1', 'tag4') AND d > 0"),
    "stable_operand": ("table", "SELECT DISTINCTCOUNTHLL(s), COUNT(*) FROM t WHERE d < 12"),
}


@pytest.mark.parametrize("kind", list(QUERIES))
def test_batched_partials_equal_the_one_segment_path(kind, request):
    fixture, sql = QUERIES[kind]
    eng, segs = request.getfixturevalue(fixture)[:2]
    ctx = eng.make_context(sql)
    plans = [plan_segment(seg, ctx, valid_mask=_valid(seg)) for seg in segs]  # every segment on the device path
    if kind == "sparse_groupby":
        # first the compact space, whose 4,096 slots the ~600 distinct rows a segment pass; then the sort-compaction path
        assert all(p.spec[2][0] == "groups_compact" for p in plans)
        assert all(plan_segment(seg, ctx, compact=False).spec[2][0] == "groups_sparse" for seg in segs)
    if kind == "upsert_docmask":
        assert all(any(o.dtype == bool and o.ndim == 1 for o in p.operands) for p in plans)
    if kind == "stable_operand":
        assert all(any(o is seg.columns["s"].dictionary.hll_hash_pad() for o in p.operands) for seg, p in zip(segs, plans))
    with request_ledger("q-batched", "server") as led:
        partials, scanned, _ = eng.partials(ctx)
    counters = led.to_wire()["counters"]
    # one wait a query; one more where compact launches overflowed, for all of them enqueued again together
    again = counters.get("groupCompactFallbacks", 0)
    assert again == (len(segs) if kind == "sparse_groupby" else 0) and counters["deviceReadbackWaits"] == 1 + bool(again)
    want = [eng._execute_segment(seg, ctx) for seg in segs]
    assert len(partials) == len(segs) and scanned == sum(m for _, m in want)
    for got, (partial, _) in zip(partials, want):
        _same(got, partial)


def _valid(seg):
    valid = seg.extras.get("valid_docs")
    return valid(seg.n_docs) if valid is not None else None


def test_every_plan_operand_is_a_numpy_array_of_its_final_dtype(table):
    """What `jnp.asarray` made of each operand is what the jitted call now
    gets handed: an ndarray, so never a weakly typed Python number."""
    eng, segs, _ = table
    for _, sql in QUERIES.values():
        ctx = eng.make_context(sql)
        for o in plan_segment(segs[0], ctx).operands:
            assert isinstance(o, np.ndarray) and o.dtype != object


# ---------------------------------------------------------------------------
# the two counters of an answer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def broker(tmp_path_factory):
    """One server, four segments: an answer's counters are that server's."""
    controller = Controller(PropertyStore(), tmp_path_factory.mktemp("link_crossings"))
    controller.register_server("server_0", Server("server_0"))
    schema = _schema()
    controller.add_schema(schema)
    controller.add_table(TableConfig("t"))
    segs = [SegmentBuilder(schema).build(_data(i, rows=200), f"t_{i}") for i in range(4)]
    for seg in segs:
        controller.upload_segment("t", seg)
    return Broker(controller, cache_config=CacheConfig(enabled=False)), segs


FEW_OPERANDS = "SELECT COUNT(*) FROM t WHERE v > 3"
MANY_OPERANDS = (
    "SELECT d, SUM(v), MIN(x), COUNT(*) FROM t WHERE v > 3 AND v < 990 AND x > 0.01 AND x < 0.99 "
    "AND s IN ('s0', 's1', 's2') AND d BETWEEN 0 AND 20 AND hi <> 7 GROUP BY d LIMIT 100"
)


def test_an_answers_link_crossings_do_not_grow_with_the_operand_count(broker):
    broker, segs = broker
    few, many = broker.execute(FEW_OPERANDS).to_dict(), broker.execute(MANY_OPERANDS).to_dict()
    assert not few.get("exceptions") and not many.get("exceptions")
    eng = QueryEngine(segs)
    n_few = len(plan_segment(segs[0], eng.make_context(FEW_OPERANDS)).operands)
    n_many = len(plan_segment(segs[0], eng.make_context(MANY_OPERANDS)).operands)
    assert n_many >= n_few + 5
    for doc in (few, many):
        c = doc["counters"]
        assert c["segmentsDispatched"] == 4
        assert c["hostToDeviceTransfers"] == 4  # one a launch: the operands go with it
        assert c["deviceReadbackWaits"] == 1  # one wait for the four vectors
        assert doc["spanTimesMs"]["server.device_wait"] <= doc["spanTimesMs"]["server.execute"]


def test_an_answer_with_nothing_dispatched_still_carries_both_counters(broker):
    doc = broker[0].execute("SELECT COUNT(*) FROM t WHERE v > 100000").to_dict()  # every segment pruned by min/max
    assert doc["counters"]["segmentsDispatched"] == 0
    assert doc["counters"]["hostToDeviceTransfers"] == 0 and doc["counters"]["deviceReadbackWaits"] == 0


def test_every_launch_is_one_call_of_the_fused_kernel_in_the_registry(table):
    """`/debug/roofline` `kernels[].calls` of `query.fused_packed` is what
    the benchmark reads to see that the device path was the path."""
    from pinot_tpu.common.kernel_obs import KERNELS

    def calls():
        return sum(k["calls"] for k in KERNELS.roofline()["kernels"] if k["kernel"] == "query.fused_packed")

    eng, segs, _ = table
    before = calls()
    eng.partials(eng.make_context(QUERIES["aggregation"][1]))
    assert calls() == before + len(segs)


# ---------------------------------------------------------------------------
# stable operands
# ---------------------------------------------------------------------------


def test_a_stable_operand_is_staged_once_over_many_queries():
    schema = Schema.build("h", dimensions=[("s", DataType.STRING)], metrics=[("v", DataType.LONG)])
    rng = np.random.default_rng(5)
    segs = [
        SegmentBuilder(schema).build(
            {"s": np.array([f"k{k}" for k in rng.integers(0, 40, 300)], dtype=object), "v": rng.integers(0, 9, 300).astype(np.int64)},
            f"h_{i}",
        )
        for i in range(2)
    ]
    eng = QueryEngine(segs)
    sql = "SELECT DISTINCTCOUNTHLL(s) FROM h WHERE v > 1"
    want = eng.execute(sql).rows
    transfers = []
    for _ in range(4):
        with request_ledger(f"q-stable-{len(transfers)}", "server") as led:
            assert eng.execute(sql).rows == want
        transfers.append(led.to_wire()["counters"]["hostToDeviceTransfers"])
    assert transfers == [2, 2, 2, 2]  # the launches; the first execute above staged the two hash tables
    for seg in segs:
        hv = seg.columns["s"].dictionary.hll_hash_pad()
        staged = kernels.stage_operand(hv)
        assert staged is not hv and staged is kernels.stage_operand(hv)
        np.testing.assert_array_equal(np.asarray(staged), hv)
    # an array no owner declared stable goes in as it is, every time
    lut = np.arange(8, dtype=np.int32)
    assert kernels.stage_operand(lut) is lut


def test_first_staging_of_a_stable_operand_counts_as_a_transfer():
    hv = kernels.mark_stable_operand(np.arange(16, dtype=np.uint32))
    with request_ledger("q-first", "server") as led:
        kernels.stage_operand(hv)
        kernels.stage_operand(hv)
    assert led.to_wire()["counters"]["hostToDeviceTransfers"] == 1


# ---------------------------------------------------------------------------
# operand shapes that differ from segment to segment
# ---------------------------------------------------------------------------


def test_segments_whose_dictionaries_differ_in_cardinality_answer_exactly(table):
    eng, segs, df = table
    cards = [seg.columns["d"].cardinality for seg in segs]
    assert len(set(cards)) == len(cards)
    res = eng.execute("SELECT d, SUM(v), COUNT(*) FROM t WHERE d IN (0, 2, 3, 11, 17) AND s <> 's0' GROUP BY d ORDER BY d LIMIT 100")
    sub = df[df.d.isin([0, 2, 3, 11, 17]) & (df.s != "s0")]
    want = sub.groupby("d").agg(sv=("v", "sum"), n=("v", "size")).reset_index().sort_values("d")
    assert [[int(a), int(b), int(c)] for a, b, c in res.rows] == [[int(r.d), int(r.sv), int(r.n)] for r in want.itertuples()]


# ---------------------------------------------------------------------------
# deadlines and streaming
# ---------------------------------------------------------------------------


class _ExpiresAfter(Deadline):
    """A deadline that runs out after a number of checks: between two
    segments, wherever the engine checks there."""

    __slots__ = ("checks", "_limit")

    def __init__(self, limit: int):
        super().__init__(time.time() + 3600)
        self.checks, self._limit = 0, limit

    def check(self, where: str = "") -> None:
        self.checks += 1
        if self.checks > self._limit:
            self.deadline_ts = time.time() - 1
        super().check(where)


def test_a_deadline_that_runs_out_between_two_dispatches_raises(table):
    eng, segs, _ = table
    ctx = eng.make_context(QUERIES["aggregation"][1])
    ctx.deadline = _ExpiresAfter(1)
    with pytest.raises(QueryTimeoutError, match=f"segment {segs[1].name}"):
        eng.partials(ctx)


def test_a_deadline_that_runs_out_between_two_readbacks_raises(table):
    eng, segs, _ = table
    ctx = eng.make_context(QUERIES["aggregation"][1])
    ctx.deadline = _ExpiresAfter(len(segs) + 1)  # every dispatch and the first vector's wait pass
    pend, pruned = eng._dispatch_all(ctx)
    with pytest.raises(QueryTimeoutError, match=f"segment {segs[1].name}"):
        eng._resolve_partials(ctx, pend, pruned)
    arrived = [disp[2]._host is not None for _, disp in pend]
    assert arrived == [True] + [False] * (len(segs) - 1)  # the wait stopped where the deadline did


def test_a_deadline_that_runs_out_between_two_conversions_raises(table):
    eng, segs, _ = table
    ctx = eng.make_context(QUERIES["dense_groupby"][1])
    ctx.deadline = _ExpiresAfter(2 * len(segs) + 1)  # dispatches, the whole wait and the first conversion pass
    with pytest.raises(QueryTimeoutError, match=f"segment {segs[1].name}"):
        eng.partials(ctx)


def test_partials_iter_still_streams_segment_by_segment(table):
    """The streaming path enqueues a segment when the consumer asks for it
    and not before: a consumer that stops early costs no further launches."""
    eng, segs, _ = table
    ctx = eng.make_context(QUERIES["selection"][1])
    want = [eng._execute_segment(seg, ctx) for seg in segs]
    with request_ledger("q-stream", "server") as led:
        it = eng.partials_iter(ctx)
        seg, partial, matched, _ = next(it)
        assert seg is segs[0] and matched == want[0][1]
        _same(partial, want[0][0])
        after_one = dict(led.to_wire()["counters"])
        rest = list(it)
    assert after_one == {"hostToDeviceTransfers": 1, "deviceReadbackWaits": 1}
    assert [s for s, *_ in rest] == segs[1:]
    for (_, partial, matched, _), (p, m) in zip(rest, want[1:]):
        assert matched == m
        _same(partial, p)
    assert led.to_wire()["counters"] == {"hostToDeviceTransfers": len(segs), "deviceReadbackWaits": len(segs)}
