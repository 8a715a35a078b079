"""An expression GROUP BY key over one dictionary-coded column runs on the
device path (plan.expr_key): the expression is evaluated over the segment's
dictionary at plan time and the rows gather their bucket through a code ->
bucket operand. Every form here is compared with plain numpy over the same
rows, over several segments whose dictionaries differ, and no segment may
leave the device path; the keys the form does not cover still fall back, each
under the reason its `server.deviceFallbacks{reason=}` label carries.
"""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.common import DataType, FieldSpec, Schema
from pinot_tpu.common.metrics import ServerMeter, server_metrics
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import QueryEngine
from pinot_tpu.query.kernels import _plan_inputs, program_name
from pinot_tpu.query.plan import DeviceFallback, plan_segment
from pinot_tpu.segment import SegmentBuilder

T0 = 1_700_000_000_000  # epoch milliseconds; not on an hour's edge
HOUR = 3_600_000


def fallbacks() -> dict[str, int]:
    """`server.deviceFallbacks` by label set: `{reason="..."}`, and '' for the bare meter."""
    name = ServerMeter.DEVICE_FALLBACKS.value
    return {key[len(name) :]: m["count"] for key, m in server_metrics().snapshot().items() if key.startswith(name)}


@pytest.fixture(scope="module")
def table():
    """Three segments of a 10 s metrics table: consecutive stretches of time
    of different lengths, the last with every third timestamp missing, so the
    `ts` dictionaries differ in values and in size (and so do the buckets)."""
    rng = np.random.default_rng(35)
    schema = Schema.build(
        "cpu",
        dimensions=[("hostname", DataType.STRING), ("ts", DataType.LONG)],
        metrics=[("usage_user", DataType.DOUBLE), ("bytes", DataType.LONG), ("usage_system", DataType.DOUBLE)],
    )
    schema.add(FieldSpec("labels", DataType.STRING, single_value=False))
    hosts = np.array([f"host_{i}" for i in range(5)], dtype=object)
    segs, frames, start = [], [], T0
    for s, steps in enumerate((700, 400, 900)):
        ticks = np.arange(steps)
        if s == 2:
            ticks = ticks[ticks % 3 != 1]
        ts = np.repeat(start + ticks * 10_000, len(hosts)).astype(np.int64)
        n = len(ts)
        data = {
            "hostname": np.tile(hosts, len(ticks)),
            "ts": ts,
            "usage_user": rng.uniform(0, 100, n),
            "bytes": rng.integers(0, 5_000_000_000, n).astype(np.int64),  # past int32: a LONG on the device too
        }
        labels = np.empty(n, dtype=object)
        for i, k in enumerate(rng.integers(1, 3, n)):
            labels[i] = [f"label_{j}" for j in rng.choice(4, k, replace=False)]
        data["labels"] = labels
        data["usage_system"] = rng.uniform(0, 100, n)
        segs.append(SegmentBuilder(schema).build(data, f"cpu_{s}"))
        frames.append(pd.DataFrame(data))
        start += steps * 10_000
    return QueryEngine(segs), pd.concat(frames, ignore_index=True), segs


#: SQL of the key, and the same over a numpy array of `ts`
KEYS = {
    "datetrunc-hour": ("DATETRUNC('hour', ts)", lambda ts: ts // HOUR * HOUR),
    "datetrunc-minute": ("DATETRUNC('minute', ts)", lambda ts: ts // 60_000 * 60_000),
    "datetrunc-day": ("DATETRUNC('day', ts)", lambda ts: ts // 86_400_000 * 86_400_000),
    "datetimeconvert": ("DATETIMECONVERT(ts, '1:MILLISECONDS:EPOCH', '1:HOURS:EPOCH', '1:HOURS')", lambda ts: ts // HOUR),
    "division": ("ts / 3600000", lambda ts: ts.astype(np.float64) / 3_600_000.0),
}
WINDOW = (T0 + 1_234 * 10_000 // 3 // 10_000 * 10_000, T0 + 1_700 * 10_000)  # cuts the first segment short and the last off


@pytest.mark.parametrize("filtered", [False, True], ids=["all-rows", "range-filter"])
@pytest.mark.parametrize("form", list(KEYS))
def test_expression_key_on_the_device(table, form, filtered):
    eng, df, _ = table
    sql_key, np_key = KEYS[form]
    where = f"WHERE ts >= {WINDOW[0]} AND ts < {WINDOW[1]} " if filtered else ""
    before = fallbacks()
    res = eng.execute(
        f"SELECT hostname, {sql_key}, AVG(usage_user), SUM(bytes), COUNT(*) FROM cpu {where}"
        f"GROUP BY hostname, {sql_key} ORDER BY {sql_key}, hostname LIMIT 100000"
    )
    assert fallbacks() == before, "a segment left the device path"
    rows = df[(df.ts >= WINDOW[0]) & (df.ts < WINDOW[1])] if filtered else df
    want = (
        rows.assign(key=np_key(rows.ts.to_numpy()))
        .groupby(["key", "hostname"], sort=True)
        .agg(avg=("usage_user", "mean"), total=("bytes", "sum"), n=("bytes", "size"))
        .reset_index()
    )
    assert len(res.rows) == len(want) > 0
    got = pd.DataFrame(res.rows, columns=["hostname", "key", "avg", "total", "n"])
    assert got.hostname.tolist() == want.hostname.tolist()  # the ORDER BY on the key, then the host
    np.testing.assert_allclose(got.key.to_numpy(np.float64), want.key.to_numpy(np.float64), rtol=0, atol=0)
    np.testing.assert_allclose(got.avg, want.avg, rtol=1e-12)
    np.testing.assert_allclose(got.total, want.total.astype(np.float64), rtol=1e-12)
    assert got.n.tolist() == want.n.tolist()


#: the group spaces beside the dense one, each with an expression key among its keys
OTHER_SPACES = {
    # ids in value space: the bucket gathers through the owning doc
    "a-multi-value-key": ("labels", "datetrunc-hour", "groups_mv"),
    # one segment's 700 timestamps x 5 hosts x 700 quotients: past MAX_DENSE_GROUPS, and more combinations than the
    # compact space's slots, so launched again, sorted and compacted
    "sort-compaction": ("ts, hostname", "division", "groups_sparse"),
}


@pytest.mark.parametrize("space", list(OTHER_SPACES))
def test_expression_key_in_the_other_group_spaces(table, space):
    eng, df, segs = table
    keys, form, kind = OTHER_SPACES[space]
    sql_key, np_key = KEYS[form]
    if kind == "groups_sparse":
        eng, df = QueryEngine(segs[:1]), df.iloc[: segs[0].n_docs]
    sql = (
        f"SELECT {keys}, {sql_key}, COUNT(*), AVG(usage_user) FROM cpu WHERE hostname <> 'host_3' "
        f"GROUP BY {keys}, {sql_key} LIMIT 100000"
    )
    assert plan_segment(segs[0], eng.make_context(sql), compact=False).spec[2][0] == kind
    before = fallbacks()
    with request_ledger(f"q-{space}") as led:
        res = eng.execute(sql)
    assert fallbacks() == before
    counters = led.response_fields()["counters"]
    assert (counters["groupCompactSegments"], counters["groupCompactFallbacks"]) == ((1, 1) if kind == "groups_sparse" else (0, 0))
    by = [k.strip() for k in keys.split(",")] + ["key"]
    rows = df[df.hostname != "host_3"].assign(key=lambda d: np_key(d.ts.to_numpy()))
    want = (rows.explode("labels") if "labels" in by else rows).groupby(by).agg(n=("usage_user", "size"), avg=("usage_user", "mean"))
    got = pd.DataFrame(res.rows, columns=by + ["n", "avg"]).set_index(by).sort_index()
    assert got.index.equals(want.index) and got.n.tolist() == want.n.tolist()
    np.testing.assert_allclose(got.avg, want.avg, rtol=1e-12)


def test_the_key_is_planned_over_the_dictionary(table):
    """The group spec carries the expression as a gather through an operand
    of the dictionary's (padded) size, the key's cardinality is the buckets
    present in the segment, and a bare column keeps the entry it always had."""
    eng, _, segs = table
    ctx = eng.make_context("SELECT hostname, DATETRUNC('hour', ts), COUNT(*) FROM cpu GROUP BY hostname, DATETRUNC('hour', ts) LIMIT 10")
    plan = plan_segment(segs[0], ctx)
    kind, keys, ng, _ = plan.spec[2]
    assert kind == "groups" and keys[0] == "hostname" and keys[1][:2] == ("remap", "ts")
    remap = plan.operands[keys[1][2]]
    assert remap.dtype == np.int32 and len(remap) == 1024  # 700 timestamps, padded to a power of two
    hours = np.unique(segs[0].columns["ts"].dictionary.values // HOUR)
    assert plan.group_cols[1][1].cardinality == len(hours) and ng == 256  # 5 hosts x 2 or 3 hours, in 256-steps
    bare = plan_segment(segs[0], eng.make_context("SELECT hostname, COUNT(*) FROM cpu GROUP BY hostname LIMIT 10"))
    assert bare.spec[2][1] == ("hostname",)


def test_device_work_names_the_keys_gather_once_a_launch(table, monkeypatch):
    """The rows' buckets are gathered through the operand in blocks (four to
    eight a segment here); a launch's `deviceWork` names `query.key_gather`
    once, over the segment's padded rows and not a block's."""
    from pinot_tpu.common.trace import request_ledger
    from pinot_tpu.query import kernels
    from pinot_tpu.segment.segment import padded_len

    _, _, segs = table
    monkeypatch.setattr(kernels, "_GATHER_BLOCK", 512)
    kernels.get_packed_kernel.cache_clear()  # the programs are traced afresh, under the small block
    try:
        with request_ledger("key-gather") as led:
            res = QueryEngine(segs).execute("SELECT DATETRUNC('hour', ts), COUNT(*) FROM cpu GROUP BY DATETRUNC('hour', ts) LIMIT 1000")
    finally:
        kernels.get_packed_kernel.cache_clear()
    assert sum(r[1] for r in res.rows) == sum(seg.n_docs for seg in segs)
    padded = [padded_len(seg.n_docs) for seg in segs]
    assert all(p >= 4 * kernels._GATHER_BLOCK for p in padded)
    entries = [1024, 512, 1024]  # 700, 400 and 600 timestamps, each padded to a power of two
    (work,) = led.to_wire()["deviceWork"].values()
    assert (work["launches"], work["rows"]) == (3, sum(padded))
    assert work["kernels"]["query.key_gather"] == {"calls": 3, "bytes": float(sum(p * 8 + e * 4 for p, e in zip(padded, entries))), "flops": 0.0}


#: queries that differ only in which raw column they read as a value: `{m}` is the column
ONE_SHAPE = {
    "host-and-hour": "SELECT hostname, DATETRUNC('hour', ts), AVG({m}) FROM cpu WHERE ts >= %d GROUP BY hostname, DATETRUNC('hour', ts) LIMIT 100000" % WINDOW[0],
    "no-group": "SELECT SUM({m}), MAX({m} * 2) FROM cpu WHERE hostname <> 'host_3'",
    "beside-another": "SELECT hostname, SUM(bytes), AVG({m}) FROM cpu GROUP BY hostname LIMIT 10",
}


@pytest.mark.parametrize("shape", list(ONE_SHAPE))
def test_the_program_does_not_name_the_raw_column_it_reads(table, shape):
    """A raw column read as a value reaches the program by its place
    (`plan.value_columns`, "@0"), not by its name: the mean of one metric and
    the mean of another are one spec, one program name and one set of
    arguments, so a dashboard that switches its metric compiles nothing
    (PERF.md, PR 35: ten metrics were twenty programs). Each reads its own column."""
    eng, df, segs = table
    plans = {m: plan_segment(segs[0], eng.make_context(ONE_SHAPE[shape].format(m=m))) for m in ("usage_user", "usage_system")}
    a, b = plans.values()
    assert a.spec == b.spec and program_name(a.spec) == program_name(b.spec)
    assert a.value_columns[-1] == "usage_user" and b.value_columns[-1] == "usage_system" and "usage_user" not in repr(a.spec)
    dev = segs[0].to_device_cached()
    assert list(_plan_inputs(a, dev)[0]) == list(_plan_inputs(b, dev)[0])
    answers = {m: eng.execute(ONE_SHAPE[shape].format(m=m)).rows for m in plans}
    assert answers["usage_user"] != answers["usage_system"]
    if shape == "no-group":
        rows = df[df.hostname != "host_3"]
        for m, ((total, top),) in answers.items():
            np.testing.assert_allclose([total, top], [rows[m].sum(), rows[m].max() * 2], rtol=1e-12)
    elif shape == "beside-another":
        for m, got in answers.items():
            want = df.groupby("hostname").agg(total=("bytes", "sum"), avg=(m, "mean"))
            np.testing.assert_allclose([r[1:] for r in sorted(got)], want.to_numpy(np.float64), rtol=1e-12)


#: keys the form does not cover, and the reason each falls back under
STILL_ON_THE_HOST = {
    "raw-column": ("bytes", "group_key_raw_column"),
    "expression-over-a-raw-column": ("bytes / 1000", "group_key_raw_column"),
    "two-columns": ("ts + bytes", "group_key_several_columns"),
    "case": ("CASE WHEN ts > 0 THEN 1 ELSE 0 END", "group_key_expression_form"),
}


@pytest.mark.parametrize("case", list(STILL_ON_THE_HOST))
def test_keys_that_still_fall_back_say_why(table, case):
    eng, df, segs = table
    key, reason = STILL_ON_THE_HOST[case]
    sql = f"SELECT {key}, COUNT(*) FROM cpu WHERE hostname = 'host_1' GROUP BY {key} LIMIT 100000"
    with pytest.raises(DeviceFallback) as raised:
        plan_segment(segs[0], eng.make_context(sql))
    assert raised.value.reason == reason
    label = '{reason="' + reason + '"}'
    before = fallbacks().get(label, 0)
    res = eng.execute(sql)
    assert fallbacks()[label] == before + len(segs)
    assert sum(r[-1] for r in res.rows) == int((df.hostname == "host_1").sum())
