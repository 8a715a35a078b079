"""Microbenchmark suite, on the host: each bench prints one JSON line;
`python -m benchmarks.micro [name ...]` runs all, or those whose name
contains one of the given filters.

What it holds are overhead budgets of the program's own planes (admission,
cache, hedging, tracing, profiler, SLO, aggregator, storage, kernel and scan
observability, front end, lint run time), each asserted inside its bench and
named by a CI step, and two host codecs `tests/test_benchmarks.py` keeps
runnable (`fwd_unpack_native`: the C++ bit-unpack; `datatable_serde`). None
of them is a speed result: how fast the system is comes from
`python3 -m perfbench.run` on the chip (PERF.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _time_host(fn, iters=10):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e3


def bench_fwd_unpack_native(n=4_000_000, bits=7):
    from pinot_tpu import native

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 1 << bits, n).astype(np.int32)
    packed = native.bitpack(ids, bits)
    return {
        "metric": "fwd_index_bitunpack_native",
        "value": _time_host(lambda: native.bitunpack(packed, n, bits)),
        "unit": "ms",
        "n": n,
    }


def bench_datatable_serde(n=200_000):
    import pandas as pd

    from pinot_tpu.common import datatable

    rng = np.random.default_rng(0)
    frame = pd.DataFrame(
        {
            "k0": np.array([f"key{i % 997}" for i in range(n)], dtype=object),
            "a0p0": rng.integers(0, 10**9, n),
            "a1p0": rng.random(n),
        }
    )
    payload = datatable.encode(frame)
    return {
        "metric": "datatable_roundtrip",
        "value": _time_host(lambda: datatable.decode(datatable.encode(frame)), iters=5),
        "unit": "ms",
        "bytes": len(payload),
    }


def bench_admission_overhead(n=120_000):
    """Admission-plane cost on the broker request path: the same single-table
    aggregation with the scheduler/admission tier disabled vs at defaults.
    The per-query hot cost is one decide() (queue-state read + M/M/c
    projection + gauge updates) plus one scheduler submit/result handoff;
    time the armed decide() directly and hold its projected share of the
    query wall to the <2% budget — the stable form of the wall-clock
    assertion."""
    import shutil
    import tempfile

    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.common.config import SchedulerConfig
    from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
    from pinot_tpu.cluster.admission import AdmissionController
    from pinot_tpu.query.context import Deadline
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(23)
    root = tempfile.mkdtemp(prefix="pinot_tpu_adm_")
    try:
        controller = Controller(PropertyStore(), os.path.join(root, "ds"))
        for i in range(2):
            controller.register_server(f"s{i}", Server(f"s{i}"))
        schema = Schema.build(
            "t", dimensions=[("k", DataType.INT)], metrics=[("m", DataType.LONG)]
        )
        controller.add_schema(schema)
        controller.add_table(TableConfig("t", replication=2))
        builder = SegmentBuilder(schema)
        for i in range(4):
            controller.upload_segment(
                "t",
                builder.build(
                    {
                        "k": rng.integers(0, 64, n // 4).astype(np.int32),
                        "m": rng.integers(1, 10, n // 4).astype(np.int64),
                    },
                    f"t_{i}",
                ),
            )
        q = "SELECT k, SUM(m) FROM t GROUP BY k ORDER BY k LIMIT 10"

        broker_off = Broker(controller, scheduler_config=SchedulerConfig(enabled=False))
        off_ms = _time_host(lambda: broker_off.execute(q), iters=7)
        broker_on = Broker(controller)
        try:
            on_ms = _time_host(lambda: broker_on.execute(q), iters=7)
        finally:
            broker_on.shutdown()

        # Direct measure of one armed admission decision against a live
        # scheduler with a warm service-time estimate: exactly one decide()
        # runs per broker request, so per_decide_us projected against the
        # query wall must sit inside the 2% budget.
        ac = AdmissionController(SchedulerConfig())
        try:
            ac.note_service_time("t", off_ms)
            deadline = Deadline.from_timeout_ms(3_600_000.0)
            decides = 100_000
            t0 = time.perf_counter()
            for _ in range(decides):
                ac.decide("t", deadline)
            per_decide_us = (time.perf_counter() - t0) / decides * 1e6
        finally:
            ac.stop()
        projected_pct = per_decide_us / (off_ms * 1e3) * 100
        assert projected_pct < 2.0, (
            f"admission decide {per_decide_us:.2f}µs = {projected_pct:.2f}% of "
            f"{off_ms:.1f}ms query — over the 2% request-path budget"
        )
        return {
            "metric": "admission_overhead",
            "value": round(on_ms - off_ms, 3),
            "unit": "ms",
            "n": n,
            "off_ms": round(off_ms, 3),
            "on_ms": round(on_ms, 3),
            "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
            "decide_us": round(per_decide_us, 4),
            "projected_pct_per_query": round(projected_pct, 3),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_cache_overhead(n=120_000):
    """Query-cache cost on the MISS path (the hit path is the win, the miss
    path is the tax): the same aggregation with the cache plane disabled vs
    at defaults, driven with a never-repeating WHERE literal so every lookup
    misses. The per-miss hot cost is one key build (normalize is already paid
    by the parse tier; routing-version reads dominate) + one result_get miss
    + one clone/estimate/result_put; time those ops directly against a live
    broker and hold their projected share of the query wall to the <2%
    budget — the stable form of the wall-clock assertion (same shape as
    admission_overhead)."""
    import shutil
    import tempfile

    from pinot_tpu.common import CacheConfig, DataType, Schema, TableConfig
    from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(29)
    root = tempfile.mkdtemp(prefix="pinot_tpu_cache_")
    try:
        controller = Controller(PropertyStore(), os.path.join(root, "ds"))
        for i in range(2):
            controller.register_server(f"s{i}", Server(f"s{i}"))
        schema = Schema.build(
            "t", dimensions=[("k", DataType.INT)], metrics=[("m", DataType.LONG)]
        )
        controller.add_schema(schema)
        controller.add_table(TableConfig("t", replication=2))
        builder = SegmentBuilder(schema)
        for i in range(4):
            controller.upload_segment(
                "t",
                builder.build(
                    {
                        "k": rng.integers(0, 64, n // 4).astype(np.int32),
                        "m": rng.integers(1, 10, n // 4).astype(np.int64),
                    },
                    f"t_{i}",
                ),
            )

        # unique literal per execution => the result tier misses every time
        counter = [0]

        def q():
            counter[0] += 1
            return f"SELECT k, SUM(m) FROM t WHERE k < {64 + counter[0]} GROUP BY k ORDER BY k LIMIT 10"

        broker_off = Broker(controller, cache_config=CacheConfig(enabled=False))
        try:
            off_ms = _time_host(lambda: broker_off.execute(q()), iters=7)
        finally:
            broker_off.shutdown()
        broker_on = Broker(controller)
        try:
            on_ms = _time_host(lambda: broker_on.execute(q()), iters=7)

            # Direct measure of the added miss-path ops against the live
            # broker: key build + result-tier miss + put of a small response.
            stmt, normalized = broker_on._compile(q())
            probe = broker_on.execute(q())
            ops = 20_000
            # the snapshot's one validating call is made with the caches off too
            snaps = {"t": broker_on._route_snapshot("t")}
            t0 = time.perf_counter()
            for i in range(ops):
                key, versions, _ = broker_on._cache_key(stmt, snaps, normalized)
                miss_key = (f"{normalized}#{i}", key[1])
                broker_on.caches.result_get(miss_key, versions)
                broker_on.caches.result_put(
                    miss_key, probe, versions, realtime=False
                )
            per_op_us = (time.perf_counter() - t0) / ops * 1e6
        finally:
            broker_on.shutdown()
        projected_pct = per_op_us / (off_ms * 1e3) * 100
        assert projected_pct < 2.0, (
            f"cache miss-path ops {per_op_us:.2f}µs = {projected_pct:.2f}% of "
            f"{off_ms:.1f}ms query — over the 2% request-path budget"
        )
        return {
            "metric": "cache_overhead",
            "value": round(on_ms - off_ms, 3),
            "unit": "ms",
            "n": n,
            "off_ms": round(off_ms, 3),
            "on_ms": round(on_ms, 3),
            "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
            "miss_ops_us": round(per_op_us, 4),
            "projected_pct_per_query": round(projected_pct, 3),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_hedge_overhead(n=120_000):
    """Hedged-scatter cost on the happy path (no stragglers): the same
    aggregation with hedging disabled (plain pool.map fan-out) vs enabled
    (per-leg future + EWMA-delay wait). With healthy servers every primary
    returns before its hedge delay, so no hedges issue and the whole cost is
    bookkeeping: one _hedge_delay_s + timed result() per leg plus one
    _hedge_record per reply. Time that bookkeeping directly and hold its
    projected share of the query wall to the <2% budget — the stable form of
    the wall-clock assertion (same shape as admission_overhead)."""
    import shutil
    import tempfile

    from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.common.config import ResilienceConfig
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(29)
    root = tempfile.mkdtemp(prefix="pinot_tpu_hedge_")
    try:
        controller = Controller(PropertyStore(), os.path.join(root, "ds"))
        for i in range(2):
            controller.register_server(f"s{i}", Server(f"s{i}"))
        schema = Schema.build(
            "t", dimensions=[("k", DataType.INT)], metrics=[("m", DataType.LONG)]
        )
        controller.add_schema(schema)
        controller.add_table(TableConfig("t", replication=2))
        builder = SegmentBuilder(schema)
        for i in range(4):
            controller.upload_segment(
                "t",
                builder.build(
                    {
                        "k": rng.integers(0, 64, n // 4).astype(np.int32),
                        "m": rng.integers(1, 10, n // 4).astype(np.int64),
                    },
                    f"t_{i}",
                ),
            )
        q = "SELECT k, SUM(m) FROM t GROUP BY k ORDER BY k LIMIT 10"

        broker_off = Broker(controller)  # hedge_enabled defaults False
        try:
            off_ms = _time_host(lambda: broker_off.execute(q), iters=7)
        finally:
            broker_off.shutdown()
        broker_on = Broker(controller, resilience=ResilienceConfig(hedge_enabled=True))
        try:
            on_ms = _time_host(lambda: broker_on.execute(q), iters=7)
            hedges_issued = broker_on.hedge_snapshot()["hedgesIssued"]

            # Direct measure of the per-leg bookkeeping against the live
            # broker: a 2-server scatter pays 2x (delay lookup + record);
            # project that against the query wall for the budget assertion.
            ops = 100_000
            t0 = time.perf_counter()
            for _ in range(ops):
                broker_on._hedge_delay_s("s0", "t")
                broker_on._hedge_record("s0", "t", 5.0)
            per_leg_us = (time.perf_counter() - t0) / ops * 1e6
        finally:
            broker_on.shutdown()
        projected_pct = 2 * per_leg_us / (off_ms * 1e3) * 100
        assert hedges_issued == 0, (
            f"{hedges_issued} hedges issued with healthy servers — the happy "
            "path must not spend hedge budget"
        )
        assert projected_pct < 2.0, (
            f"hedge bookkeeping {per_leg_us:.2f}µs/leg = {projected_pct:.2f}% of "
            f"{off_ms:.1f}ms query — over the 2% request-path budget"
        )
        return {
            "metric": "hedge_overhead",
            "value": round(on_ms - off_ms, 3),
            "unit": "ms",
            "n": n,
            "off_ms": round(off_ms, 3),
            "on_ms": round(on_ms, 3),
            "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
            "per_leg_us": round(per_leg_us, 4),
            "projected_pct_per_query": round(projected_pct, 3),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_trace_overhead(n=200_000, dim=2_000):
    """Tracing-plane cost on the v2 hot path: the same multistage
    join+group-by untraced vs under an active sampled trace. With sampling
    off the per-site cost is one ContextVar read inside `trace_event()`;
    time that disabled guard directly and hold its projected share of the
    query wall to the <2% budget — the stable form of the assertion."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.trace import TraceContext, start_trace, trace_event
    from pinot_tpu.multistage import MultistageEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(23)
    fact_s = Schema.build("fact", dimensions=[("k", DataType.INT)], metrics=[("m", DataType.LONG)])
    dim_s = Schema.build("dim", dimensions=[("k", DataType.INT)], metrics=[("w", DataType.LONG)])
    fact = SegmentBuilder(fact_s).build(
        {"k": rng.integers(0, dim, n).astype(np.int32), "m": rng.integers(1, 10, n).astype(np.int64)},
        "f0",
    )
    d = SegmentBuilder(dim_s).build(
        {"k": np.arange(dim, dtype=np.int32), "w": rng.integers(1, 5, dim).astype(np.int64)}, "d0"
    )
    eng = MultistageEngine({"fact": [fact], "dim": [d]}, n_workers=2)
    q = "SELECT dim.k, SUM(fact.m) FROM fact JOIN dim ON fact.k = dim.k GROUP BY dim.k ORDER BY dim.k LIMIT 10"
    off_ms = _time_host(lambda: eng.execute(q), iters=7)

    def traced():
        with start_trace(request_id="bench", context=TraceContext.mint(), service="broker"):
            eng.execute(q)

    on_ms = _time_host(traced, iters=7)

    # Direct measure of one disabled event site: with no active trace the
    # whole of trace_event() is a ContextVar read and a None compare. A query
    # crosses well under 1000 such sites, so per_call_us * 1000 projected
    # against the untraced wall must sit inside the 2% budget.
    calls = 100_000
    t0 = time.perf_counter()
    for _ in range(calls):
        trace_event("bench")
    per_call_us = (time.perf_counter() - t0) / calls * 1e6
    projected_pct = per_call_us * 1000 / (off_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"disabled trace_event {per_call_us:.2f}µs x1000 = {projected_pct:.2f}% of "
        f"{off_ms:.1f}ms query — over the 2% hot-loop budget"
    )
    return {
        "metric": "trace_overhead",
        "value": round(on_ms - off_ms, 3),
        "unit": "ms",
        "n": n,
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
        "disabled_event_us": round(per_call_us, 4),
        "projected_pct_at_1000_sites": round(projected_pct, 3),
    }


def bench_profiler_overhead(n=200_000, dim=2_000):
    """Sampling-profiler cost on the v2 hot path: the same multistage
    join+group-by with the continuous profiler daemon off vs on at the
    default rate. The profiled threads pay nothing per operation — the cost
    is the daemon's O(threads x stack depth) walk, hz times a second — so
    the stable assertion projects the measured per-tick cost at the default
    rate against the query wall and holds it to the <2% budget (matching the
    stats/deadline/trace budget benches)."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.profiler import SamplingProfiler
    from pinot_tpu.multistage import MultistageEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(29)
    fact_s = Schema.build("fact", dimensions=[("k", DataType.INT)], metrics=[("m", DataType.LONG)])
    dim_s = Schema.build("dim", dimensions=[("k", DataType.INT)], metrics=[("w", DataType.LONG)])
    fact = SegmentBuilder(fact_s).build(
        {"k": rng.integers(0, dim, n).astype(np.int32), "m": rng.integers(1, 10, n).astype(np.int64)},
        "f0",
    )
    d = SegmentBuilder(dim_s).build(
        {"k": np.arange(dim, dtype=np.int32), "w": rng.integers(1, 5, dim).astype(np.int64)}, "d0"
    )
    eng = MultistageEngine({"fact": [fact], "dim": [d]}, n_workers=2)
    q = "SELECT dim.k, SUM(fact.m) FROM fact JOIN dim ON fact.k = dim.k GROUP BY dim.k ORDER BY dim.k LIMIT 10"
    off_ms = _time_host(lambda: eng.execute(q), iters=7)

    prof = SamplingProfiler()
    prof.start()
    try:
        on_ms = _time_host(lambda: eng.execute(q), iters=7)
    finally:
        prof.stop()

    # Direct measure of one sampling tick (all threads walked + folded),
    # projected at the default rate against the query wall: ticks-per-query
    # x per-tick cost must sit inside the 2% budget.
    ticks = 200
    t0 = time.perf_counter()
    for _ in range(ticks):
        prof.sample_once()
    per_tick_ms = (time.perf_counter() - t0) / ticks * 1e3
    ticks_per_query = prof.hz * off_ms / 1e3
    projected_pct = per_tick_ms * ticks_per_query / off_ms * 100
    assert projected_pct < 2.0, (
        f"profiler tick {per_tick_ms:.3f}ms x {prof.hz}Hz = {projected_pct:.2f}% of "
        f"{off_ms:.1f}ms query — over the 2% hot-loop budget"
    )
    return {
        "metric": "profiler_overhead",
        "value": round(on_ms - off_ms, 3),
        "unit": "ms",
        "n": n,
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
        "tick_ms": round(per_tick_ms, 4),
        "hz": prof.hz,
        "projected_pct_at_default_hz": round(projected_pct, 3),
    }


def bench_slo_overhead(cycles=200):
    """SloEvaluator cost per scrape cycle with a full long-window history
    (360 samples at the 10s default interval), a cluster scope plus 8
    per-table override scopes, and both objective kinds active. The SLO
    plane runs on the controller's periodic thread, never the query hot
    path, so the budget is against the scrape interval: one observe+evaluate
    must stay under 2% of it."""
    from pinot_tpu.common.slo import SloEvaluator

    clock = {"t": 0.0}
    ev = SloEvaluator(
        {
            "availability": 0.999,
            "p99LatencyMs": 100.0,
            "tables": {f"t{i}": {"p99LatencyMs": 50.0} for i in range(8)},
        },
        now_fn=lambda: clock["t"],
    )
    bounds = [0.5 * 2**i for i in range(20)] + [float("inf")]

    def sample(i):
        q = 1000 * (i + 1)
        buckets = [(b, min(q, q * (j + 1) // len(bounds))) for j, b in enumerate(bounds)]
        tables = {
            f"t{k}": {"queries": q // 8, "errors": i, "latencyBuckets": buckets} for k in range(8)
        }
        return {
            "queries": q,
            "errors": i,
            "latencyBuckets": buckets,
            "tables": tables,
            "exemplars": [{"traceId": f"tr{i}", "table": "t0", "timeMs": 120.0}],
        }

    for i in range(360):  # fill the long window: worst-case history scan
        clock["t"] += 10.0
        ev.observe(sample(i))
    t0 = time.perf_counter()
    for i in range(360, 360 + cycles):
        clock["t"] += 10.0
        ev.observe(sample(i))
    per_cycle_ms = (time.perf_counter() - t0) / cycles * 1e3
    interval_ms = 10_000.0
    projected_pct = per_cycle_ms / interval_ms * 100
    assert projected_pct < 2.0, (
        f"SLO evaluation {per_cycle_ms:.2f}ms/cycle = {projected_pct:.2f}% of the "
        f"{interval_ms:.0f}ms scrape interval — over the 2% budget"
    )
    return {
        "metric": "slo_overhead",
        "value": round(per_cycle_ms, 3),
        "unit": "ms",
        "cycles": cycles,
        "history": 360,
        "scopes": 9,
        "projected_pct_of_interval": round(projected_pct, 3),
    }


def bench_aggregator_scrape(cycles=50):
    """Full ClusterMetricsAggregator cycle over 2 brokers + 6 servers with 16
    labelled tables each: fetch (injected, includes the nodes' snapshot
    serialization — normally paid node-side, so this over-counts), fold with
    counter-reset detection, cross-node histogram merge, gauge publication,
    and SLO evaluation. Budget: one cycle under 2% of the 10s scrape
    interval, i.e. the aggregator thread stays >98% idle."""
    import tempfile

    from pinot_tpu.cluster.controller import Controller
    from pinot_tpu.cluster.metadata import PropertyStore
    from pinot_tpu.cluster.periodic import ClusterMetricsAggregator
    from pinot_tpu.common.metrics import MetricsRegistry

    controller = Controller(PropertyStore(), tempfile.mkdtemp(prefix="aggbench_"))
    regs: dict = {}
    for i in range(2):
        controller.register_broker(f"broker-{i}", f"broker-{i}", 80)
        regs[f"broker-{i}"] = (MetricsRegistry("broker"), "broker")
    for i in range(6):
        controller.store.set(
            f"/instances/server-{i}", {"host": f"server-{i}", "port": 80, "alive": True, "tags": []}
        )
        regs[f"server-{i}"] = (MetricsRegistry("server"), "server")

    rng = np.random.default_rng(8)

    def tick(reg, role):
        if role == "broker":
            reg.meter("broker.queries").mark(50)
            t = reg.timer("broker.queryTotalMs")
            for v in rng.uniform(1, 200, 50):
                t.update_ms(float(v))
            for k in range(16):
                reg.meter("broker.tableQueries", table=f"t{k}", tenant="g").mark(3)
                reg.timer("broker.tableLatencyMs", table=f"t{k}").update_ms(float(rng.uniform(1, 200)))
        else:
            reg.meter("server.queries").mark(50)
            t = reg.timer("server.queryExecutionMs")
            for v in rng.uniform(0.5, 100, 50):
                t.update_ms(float(v))

    def fetch(url):
        rest = url.split("//", 1)[1]
        hostport, _, path = rest.partition("/")
        nid = hostport.split(":")[0]
        reg, role = regs[nid]
        if path.startswith("metrics"):
            return json.dumps(reg.snapshot())
        if path.startswith("debug/workload"):
            return json.dumps(
                {
                    "rollups": [
                        {
                            "tenant": "g",
                            "table": f"t{k}",
                            "queries": 10,
                            "cpuTimeNs": 1000,
                            "allocatedBytes": 0,
                            "segmentsExecuted": 4,
                            "queriesKilled": 0,
                        }
                        for k in range(16)
                    ]
                }
            )
        return json.dumps([{"traceId": "tr", "table": "t0", "timeMs": 120.0, "sql": "SELECT 1"}])

    agg = ClusterMetricsAggregator(
        controller, fetch=fetch, objectives={"availability": 0.999, "p99LatencyMs": 500.0}
    )
    for reg, role in regs.values():
        tick(reg, role)
    agg.run_once()  # warmup fold (first-scrape baseline capture)
    total = 0.0
    for _ in range(cycles):
        for reg, role in regs.values():
            tick(reg, role)
        t0 = time.perf_counter()
        agg.run_once()
        total += time.perf_counter() - t0
    per_cycle_ms = total / cycles * 1e3
    interval_ms = agg.interval_sec * 1e3
    projected_pct = per_cycle_ms / interval_ms * 100
    assert projected_pct < 2.0, (
        f"aggregator cycle {per_cycle_ms:.2f}ms = {projected_pct:.2f}% of the "
        f"{interval_ms:.0f}ms scrape interval — over the 2% budget"
    )
    return {
        "metric": "aggregator_scrape",
        "value": round(per_cycle_ms, 3),
        "unit": "ms",
        "cycles": cycles,
        "nodes": len(regs),
        "projected_pct_of_interval": round(projected_pct, 3),
    }


def bench_atomic_write_overhead(size=4 * 1024 * 1024):
    """Crash-consistent write cost vs a bare write (fsync held equal so the
    delta is the tmp-name + rename + fault-guard mechanics, not disk sync).
    The production fast path through the storage.write fault guard is one
    dict check per file write; it is timed directly and its projected share
    of a segment write must sit inside the 2% budget — the stable form of
    the wall-clock assertion (fsync noise can't flake it)."""
    import tempfile
    from pathlib import Path

    from pinot_tpu.common.durability import atomic_write_bytes
    from pinot_tpu.common.faults import FAULTS

    data = os.urandom(size)
    with tempfile.TemporaryDirectory(prefix="pinot_tpu_bench_") as td:
        bare_path = Path(td) / "bare.bin"
        atomic_path = Path(td) / "atomic.bin"
        bare_ms = _time_host(lambda: bare_path.write_bytes(data), iters=7)
        atomic_ms = _time_host(lambda: atomic_write_bytes(atomic_path, data, fsync=False), iters=7)

    FAULTS.reset()  # production state: guard is one empty-dict check
    checks = 100_000
    t0 = time.perf_counter()
    for _ in range(checks):
        FAULTS.maybe_fail("storage.write", data)
    per_call_us = (time.perf_counter() - t0) / checks * 1e6
    # one guard call per file write, projected against the bare write wall
    projected_pct = per_call_us / (bare_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"storage.write guard {per_call_us:.2f}µs = {projected_pct:.2f}% of a "
        f"{bare_ms:.1f}ms write — over the 2% budget"
    )
    return {
        "metric": "atomic_write_overhead",
        "value": round(atomic_ms - bare_ms, 3),
        "unit": "ms",
        "size_bytes": size,
        "bare_ms": round(bare_ms, 3),
        "atomic_ms": round(atomic_ms, 3),
        "overhead_pct": round((atomic_ms / bare_ms - 1.0) * 100, 1),
        "guard_us_per_write": round(per_call_us, 4),
        "projected_pct": round(projected_pct, 3),
    }


def bench_store_cas_overhead(n_docs=200):
    """Multi-process-safe property store cost: a versioned, flock-guarded
    `set` vs a bare crash-consistent JSON write of the same doc. The CAS
    machinery per write is the flock lock/unlock pair + the fault-point
    guard + the fence check (a no-op read when unfenced); its per-call cost
    is timed directly and its projected share of one `set` must sit inside
    the 2% budget — the stable form of the wall-clock assertion (page-cache
    noise on the version re-read can't flake it)."""
    import tempfile
    from pathlib import Path

    from pinot_tpu.cluster.metadata import PropertyStore
    from pinot_tpu.common.durability import atomic_write_json
    from pinot_tpu.common.faults import FAULTS

    doc = {"segment": "t_0", "servers": ["s0", "s1"], "docs": 123456, "crc": "deadbeef"}
    with tempfile.TemporaryDirectory(prefix="pinot_tpu_cas_") as td:
        root = Path(td)
        store = PropertyStore(root / "store")
        i = [0]

        def bare():
            i[0] += 1
            atomic_write_json(root / f"bare_{i[0] % n_docs}.json", {"__v": i[0], "doc": doc})

        def versioned_set():
            i[0] += 1
            store.set(f"/tables/t/segments/seg_{i[0] % n_docs}", doc)

        bare_ms = _time_host(bare, iters=200)
        set_ms = _time_host(versioned_set, iters=200)

        # the cross-process exclusion mechanics, isolated: one flock
        # LOCK_EX/LOCK_UN pair + the production-state fault guard per set
        FAULTS.reset()
        cycles = 20_000
        t0 = time.perf_counter()
        for _ in range(cycles):
            with store._exclusive():
                FAULTS.maybe_fail("store.cas")
        per_call_us = (time.perf_counter() - t0) / cycles * 1e6

    projected_pct = per_call_us / (set_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"store CAS guard {per_call_us:.2f}µs = {projected_pct:.2f}% of a "
        f"{set_ms:.3f}ms set — over the 2% budget"
    )
    return {
        "metric": "store_cas_overhead",
        "value": round(set_ms - bare_ms, 3),
        "unit": "ms",
        "bare_write_ms": round(bare_ms, 3),
        "versioned_set_ms": round(set_ms, 3),
        "overhead_pct": round((set_ms / bare_ms - 1.0) * 100, 1),
        "lock_guard_us_per_set": round(per_call_us, 4),
        "projected_pct": round(projected_pct, 3),
    }


def bench_scrub_overhead(n_segments=8, rows=20_000):
    """Integrity-scrubber duty cycle: a full CRC sweep of a server's local
    copies vs one budget-throttled increment. The throttle is the overhead
    contract — at the default 30s interval, one increment's wall share must
    stay under the 2% budget, and a 1-byte budget must scan exactly one
    segment per call (the incremental-coverage proof)."""
    import tempfile
    from pathlib import Path

    from pinot_tpu.cluster import Controller, PropertyStore, Server
    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(23)
    with tempfile.TemporaryDirectory(prefix="pinot_tpu_scrub_") as td:
        root = Path(td)
        controller = Controller(PropertyStore(root / "zk"), root / "deepstore")
        server = Server("server_0", data_dir=root / "data")
        controller.register_server("server_0", server)
        schema = Schema.build(
            "t", dimensions=[("d", DataType.INT)], metrics=[("m", DataType.LONG)]
        )
        controller.add_schema(schema)
        controller.add_table(TableConfig("t", replication=1))
        b = SegmentBuilder(schema)
        for i in range(n_segments):
            seg = b.build(
                {
                    "d": rng.integers(0, 100, rows).astype(np.int32),
                    "m": rng.integers(1, 10, rows).astype(np.int64),
                },
                f"t_{i}",
            )
            controller.upload_segment("t", seg)
        full_ms = _time_host(lambda: server.scrub(), iters=5)
        one = server.scrub(io_budget_bytes=1)
        assert one["verified"] == 1, f"1-byte budget must scan one segment, got {one}"
        throttled_ms = _time_host(lambda: server.scrub(io_budget_bytes=1), iters=5)
        seg_bytes = one["bytesScanned"]
    duty_pct = throttled_ms / 30_000.0 * 100  # share of the default interval
    assert duty_pct < 2.0, (
        f"one throttled scrub increment {throttled_ms:.1f}ms = {duty_pct:.2f}% "
        "of the 30s interval — over the 2% budget"
    )
    return {
        "metric": "scrub_overhead",
        "value": round(throttled_ms, 3),
        "unit": "ms",
        "n_segments": n_segments,
        "segment_bytes": seg_bytes,
        "full_sweep_ms": round(full_ms, 3),
        "throttled_ms": round(throttled_ms, 3),
        "duty_pct_at_30s_interval": round(duty_pct, 4),
    }


def bench_lint_runtime():
    """pinotlint must stay fast enough to sit in tier-1 and CI: a whole-package
    run (all five checkers, ~200 modules) is asserted under the 10s budget on
    CPU. Parse + visit dominates; there is no jax work in the analyzer."""
    from pinot_tpu.devtools.lint import lint_paths

    pkg = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "pinot_tpu")
    t0 = time.perf_counter()
    findings = lint_paths([pkg], require_reason=True)
    wall_s = time.perf_counter() - t0
    assert not findings, f"package must lint clean: {findings[:3]}"
    assert wall_s < 10.0, f"whole-package lint took {wall_s:.1f}s — over the 10s CI budget"
    return {
        "metric": "lint_runtime",
        "value": round(wall_s * 1e3, 3),
        "unit": "ms",
        "findings": len(findings),
    }


def bench_kernel_obs_overhead(n=300_000):
    """Kernel-observability cost on the single-stage hot path: the same
    packed group-by dispatch with the KernelRegistry disabled vs enabled.
    Enabled adds one perf_counter pair, a dict fold, three metric updates
    and an accountant sample per kernel invocation; disabled is a single
    attribute check, timed directly like the trace/deadline guards."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.kernel_obs import KERNELS
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(29)
    schema = Schema.build("t", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)])
    seg = SegmentBuilder(schema).build(
        {"d": rng.integers(0, 64, n).astype(np.int32), "v": rng.integers(0, 1000, n).astype(np.int64)},
        "t_0",
    )
    eng = QueryEngine([seg])
    q = "SELECT d, SUM(v), COUNT(*) FROM t GROUP BY d"
    eng.execute(q)  # compile

    KERNELS.configure(enabled=False)
    try:
        off_ms = _time_host(lambda: eng.execute(q), iters=9)
    finally:
        KERNELS.configure(enabled=True)
    KERNELS.reset_stats()
    on_ms = _time_host(lambda: eng.execute(q), iters=9)
    assert KERNELS.total_device_ms() >= 0.0 and KERNELS.stats_snapshot()

    # Direct measure of the disabled guard: one `self._enabled` check plus
    # the lambda call. A query crosses a handful of timed_sync sites; even
    # projected at 1000 the share of the query wall must stay inside 2%.
    calls = 100_000
    KERNELS.configure(enabled=False)
    try:
        t0 = time.perf_counter()
        for _ in range(calls):
            KERNELS.timed_sync("query.fused", lambda: None)
        per_call_us = (time.perf_counter() - t0) / calls * 1e6
    finally:
        KERNELS.configure(enabled=True)
    projected_pct = per_call_us * 1000 / (off_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"disabled timed_sync {per_call_us:.2f}µs x1000 = {projected_pct:.2f}% of "
        f"{off_ms:.1f}ms query — over the 2% hot-loop budget"
    )
    return {
        "metric": "kernel_obs_overhead",
        "value": round(on_ms - off_ms, 3),
        "unit": "ms",
        "n": n,
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
        "disabled_guard_us": round(per_call_us, 4),
        "projected_pct_at_1000_sites": round(projected_pct, 3),
    }


def bench_scan_obs_overhead(n=300_000):
    """Scan-path attribution cost on the single-stage hot path: the same
    filtered group-by with scan observability disabled vs enabled. Enabled
    adds, per segment, one leaf classification walk over the (tiny) filter
    tree, a few dict folds, a heat-registry record, and the meter marks;
    disabled is one module-flag read plus the record_index_probe contextvar
    guard inside the index structures, timed directly like the
    trace/deadline/kernel guards."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.segment_heat import HEAT
    from pinot_tpu.query import scan_stats
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(41)
    schema = Schema.build("t", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)])
    seg = SegmentBuilder(schema).build(
        {"d": rng.integers(0, 64, n).astype(np.int32), "v": rng.integers(0, 1000, n).astype(np.int64)},
        "t_0",
    )
    eng = QueryEngine([seg])
    q = "SELECT d, SUM(v), COUNT(*) FROM t WHERE v > 100 GROUP BY d"
    eng.execute(q)  # compile

    scan_stats.configure(False)
    try:
        off_ms = _time_host(lambda: eng.execute(q), iters=9)
    finally:
        scan_stats.configure(True)
    HEAT.reset()
    on_ms = _time_host(lambda: eng.execute(q), iters=9)
    assert HEAT.snapshot(top=1)["segments"], "heat registry saw no folds while enabled"
    HEAT.reset()

    # Direct measure of the disabled probe guard: record_index_probe with no
    # collector installed is one ContextVar read and a None compare — the
    # only per-index-lookup cost the feature adds. Even projected at 1000
    # probe sites per query the share of the wall must stay inside 2%.
    calls = 100_000
    t0 = time.perf_counter()
    for _ in range(calls):
        scan_stats.record_index_probe("bloom", 8)
    per_call_us = (time.perf_counter() - t0) / calls * 1e6
    projected_pct = per_call_us * 1000 / (off_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"disabled record_index_probe {per_call_us:.2f}µs x1000 = {projected_pct:.2f}% of "
        f"{off_ms:.1f}ms query — over the 2% hot-loop budget"
    )
    return {
        "metric": "scan_obs_overhead",
        "value": round(on_ms - off_ms, 3),
        "unit": "ms",
        "n": n,
        "off_ms": round(off_ms, 3),
        "on_ms": round(on_ms, 3),
        "overhead_pct": round((on_ms / off_ms - 1.0) * 100, 1),
        "disabled_guard_us": round(per_call_us, 4),
        "projected_pct_at_1000_sites": round(projected_pct, 3),
    }


def bench_frontend_obs_overhead(iters=20_000):
    """Frontend request-lifecycle bookkeeping cost per HTTP request: one
    PhaseTimeline (construct, activate, the seven hot-path marks, finish
    with its timer folds) plus the ConnTracker request-transition pair —
    everything the instrumented handler adds to /query/sql beyond what the
    un-instrumented handler already did. Projected against the minimal
    broker-side request wall (a small single-stage group-by), the share
    must stay inside the same 2% hot-path budget as the other planes."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.frontend_obs import ConnTracker, PhaseTimeline
    from pinot_tpu.common.metrics import get_registry, reset_registries
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(31)
    n = 200_000
    schema = Schema.build("t", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)])
    seg = SegmentBuilder(schema).build(
        {"d": rng.integers(0, 64, n).astype(np.int32), "v": rng.integers(0, 1000, n).astype(np.int64)},
        "t_0",
    )
    eng = QueryEngine([seg])
    q = "SELECT d, SUM(v), COUNT(*) FROM t GROUP BY d"
    eng.execute(q)  # compile
    req_ms = _time_host(lambda: eng.execute(q), iters=9)

    reset_registries()
    reg = get_registry("broker")
    tracker = ConnTracker("broker")
    tracker.conn_opened()
    marks = ("headersRead", "bodyRead", "parse", "execute", "serialize", "write", "drain")
    t0 = time.perf_counter()
    for _ in range(iters):
        tracker.request_started()
        tl = PhaseTimeline("broker")
        tl.activate()
        for m in marks:
            tl.mark(m)
        tl.deactivate()
        tl.finish(reg)
        tracker.request_finished(256, 1024)
    per_req_us = (time.perf_counter() - t0) / iters * 1e6
    tracker.conn_closed(1.0, iters)
    reset_registries()

    projected_pct = per_req_us / (req_ms * 1e3) * 100
    assert projected_pct < 2.0, (
        f"frontend bookkeeping {per_req_us:.2f}µs/request = {projected_pct:.2f}% "
        f"of the {req_ms:.1f}ms hot request — over the 2% budget"
    )
    return {
        "metric": "frontend_obs_overhead",
        "value": round(per_req_us, 3),
        "unit": "us_per_request",
        "hot_request_ms": round(req_ms, 3),
        "projected_pct": round(projected_pct, 3),
    }


ALL = [
    bench_fwd_unpack_native,
    bench_datatable_serde,
    bench_admission_overhead,
    bench_cache_overhead,
    bench_hedge_overhead,
    bench_trace_overhead,
    bench_profiler_overhead,
    bench_slo_overhead,
    bench_aggregator_scrape,
    bench_atomic_write_overhead,
    bench_store_cas_overhead,
    bench_scrub_overhead,
    bench_kernel_obs_overhead,
    bench_scan_obs_overhead,
    bench_frontend_obs_overhead,
    bench_lint_runtime,
]


def main(argv=None):
    import pinot_tpu  # noqa: F401 — x64/platform setup before jax use

    names = (argv or sys.argv[1:]) or None
    for b in ALL:
        tag = b.__name__.removeprefix("bench_")
        if names and not any(f in tag for f in names):
            continue
        try:
            out = b()
            if out.get("value") is not None:
                out["value"] = round(out["value"], 3)
        except Exception as e:  # noqa: BLE001 — report, keep going
            out = {"metric": tag, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
