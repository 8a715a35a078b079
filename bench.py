"""North-star benchmark: the 5 BASELINE.md configs, device engine vs a pandas
CPU reference on identical data.

Headline (config 4, SSB Q4.x-style multi-dimension GROUP BY + ORDER BY LIMIT)
prints ONE JSON line:
  {"metric": ..., "value": <device p50 ms>, "unit": "ms", "vs_baseline": <cpu_p50/device_p50>,
   "backend": ..., "configs": {per-config p50/p99/speedup}}

The backend follows the server's rule (common/runtime.py require_device):
with JAX_PLATFORMS unset it is the TPU or the run fails; an explicit
JAX_PLATFORMS is taken as given and named in the output. Any failure — no
chip, a config that raises, a result that differs from the reference — ends
the run with a non-zero exit and no result line.

Env knobs: PINOT_TPU_BENCH_ROWS (default 16_000_000), PINOT_TPU_BENCH_ITERS (7).
"""

import json
import os
import sys
import time

import numpy as np

HEADLINE = "ssb_q4_groupby_p50_latency"
#: the ONE headline query shape — smoke test, config 4, and the scale block
#: must all measure exactly this workload
Q4_SQL = (
    "SELECT d_year, c_nation, p_category, SUM(lo_revenue - lo_supplycost) "
    "FROM lineorder WHERE lo_quantity > 5 AND d_year BETWEEN 1993 AND 1997 "
    "GROUP BY d_year, c_nation, p_category ORDER BY SUM(lo_revenue - lo_supplycost) DESC LIMIT 10"
)
Q2_SQL = (
    "SELECT SUM(lo_revenue), MIN(lo_quantity), MAX(lo_revenue), AVG(lo_supplycost) "
    "FROM lineorder WHERE d_year BETWEEN 1994 AND 1996 AND c_nation = 'NATION_03'"
)


def _bench_q4(table, t, iters, label):
    """ONE implementation of the Q4 headline measurement (device run, pandas
    reference, top-row check) — main() and the scale block must stay
    comparable, so neither carries its own copy."""
    from pinot_tpu.parallel.mesh import execute_sharded_result

    state = {}

    def dev():
        state["res"] = execute_sharded_result(table, Q4_SQL)

    def cpu():
        sel = t[(t.lo_quantity > 5) & (t.d_year >= 1993) & (t.d_year <= 1997)]
        profit = sel.lo_revenue - sel.lo_supplycost
        state["cpu"] = profit.groupby([sel.d_year, sel.c_nation, sel.p_category]).sum().nlargest(10)

    def check():
        assert state["res"].rows[0][3] == float(state["cpu"].iloc[0]), (
            f"result mismatch: {state['res'].rows[0][3]} vs {float(state['cpu'].iloc[0])}"
        )

    return _bench_pair(label, dev, cpu, iters, check)


def _bench_q2(table, t, iters, label):
    """Shared config-2 (filtered SUM/MIN/MAX/AVG) measurement."""
    from pinot_tpu.parallel.mesh import execute_sharded_result

    state = {}

    def dev():
        state["res"] = execute_sharded_result(table, Q2_SQL)

    def cpu():
        sel = t[(t.d_year >= 1994) & (t.d_year <= 1996) & (t.c_nation == "NATION_03")]
        state["cpu"] = (
            int(sel.lo_revenue.sum()),
            int(sel.lo_quantity.min()),
            int(sel.lo_revenue.max()),
            float(sel.lo_supplycost.mean()),
        )

    return _bench_pair(
        label, dev, cpu, iters, lambda: _assert_eq(state["res"].rows[0][0], state["cpu"][0])
    )


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _time(fn, iters):
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        lat.append((time.perf_counter() - t0) * 1e3)
    return {
        "p50": round(float(np.percentile(lat, 50)), 3),
        "p99": round(float(np.percentile(lat, 99)), 3),
    }


def _link_rtt_ms():
    """Host<->device link RTT in ms (devlink.link_profile memoizes it)."""
    from pinot_tpu.common.devlink import link_profile

    return link_profile()[0] * 1e3


def _bench_pair(name, run_dev, run_cpu, iters, check=None):
    """warmup+time the device path and the pandas reference; optional result
    check, whose failure fails the run (a timing of a wrong answer is not a
    result).

    Every row also splits `device_ms_*` (wall minus the measured link RTT,
    clamped at 0 — the run_* closures are block_until_ready-bounded so wall =
    link + compute) from `link_rtt_ms`."""
    run_dev()  # compile
    run_dev()
    dev = _time(run_dev, iters)
    cpu = _time(run_cpu, max(3, iters // 2))
    out = {**dev, "cpu_p50": cpu["p50"], "speedup": round(cpu["p50"] / dev["p50"], 3)}
    rtt_ms = _link_rtt_ms()
    out["link_rtt_ms"] = round(rtt_ms, 3)
    out["device_ms_p50"] = round(max(dev["p50"] - rtt_ms, 0.0), 3)
    out["device_ms_p99"] = round(max(dev["p99"] - rtt_ms, 0.0), 3)
    if check is not None:
        check()
    log(f"[{name}] device p50={dev['p50']}ms p99={dev['p99']}ms  cpu p50={cpu['p50']}ms  speedup={out['speedup']}x")
    return out


def _make_ssb_data(rng, n: int) -> dict:
    """The SSB-flavored lineorder columns — ONE generator shared by the
    smoke test and the real build so pre-flight always exercises the real
    shapes."""
    return {
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "c_nation": np.array([f"NATION_{i:02d}" for i in range(25)], dtype=object)[rng.integers(0, 25, n)],
        "p_category": np.array([f"MFGR#{i//10+1}{i%10+1}" for i in range(25)], dtype=object)[
            rng.integers(0, 25, n)
        ],
        "lo_revenue": rng.integers(100, 600_000, n).astype(np.int64),
        "lo_supplycost": rng.integers(50, 100_000, n).astype(np.int64),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
    }


#: config 6 fact rows — bounded separately from the main table (the join
#: builds its own data) so join evidence never inflates run time
JOIN_ROWS = int(os.environ.get("PINOT_TPU_BENCH_JOIN_ROWS", 4_000_000))


def _bench_join(iters: int) -> dict:
    """Config 6: multistage fact-dim equi-join + group-by through the
    v2 engine (AggregateJoinTranspose pushes the partial group-by to the
    leaf, where the fused device kernel runs it; broadcast dim + hash join +
    final merge above — the per-server hot path of the reference's
    runtime/operator tier), vs pandas merge+groupby. Runs in this process:
    the chip belongs to it, and a child that needed the chip could not get it."""
    import pandas as pd

    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.multistage.runtime import MultistageEngine
    from pinot_tpu.segment.builder import SegmentBuilder

    rng = np.random.default_rng(6)
    n = JOIN_ROWS
    fact_schema = Schema.build(
        "lineorder",
        dimensions=[
            ("d_year", DataType.INT),
            ("c_nation", DataType.STRING),
            ("p_category", DataType.STRING),
        ],
        metrics=[
            ("lo_revenue", DataType.LONG),
            ("lo_supplycost", DataType.LONG),
            ("lo_quantity", DataType.INT),
        ],
    )
    data = _make_ssb_data(rng, n)
    t = pd.DataFrame({k: (v.astype(str) if v.dtype == object else v) for k, v in data.items()})
    fact_seg = SegmentBuilder(fact_schema).build(data, "join_fact")
    nations = [f"NATION_{i:02d}" for i in range(25)]
    regions = [f"REGION_{i % 5}" for i in range(25)]
    dim_schema = Schema.build(
        "nation_dim",
        dimensions=[("nation", DataType.STRING), ("region", DataType.STRING)],
        metrics=[],
    )
    dim_seg = SegmentBuilder(dim_schema).build(
        {"nation": np.array(nations, dtype=object), "region": np.array(regions, dtype=object)},
        "join_dim",
    )
    # stage the fact segment from the MAIN thread once; stage workers then
    # hit the warm per-segment cache instead of re-uploading over the link
    fact_seg.to_device_cached()
    engine = MultistageEngine({"lineorder": [fact_seg], "nation_dim": [dim_seg]})
    sql = (
        "SELECT d.region, SUM(l.lo_revenue) FROM lineorder l "
        "JOIN nation_dim d ON l.c_nation = d.nation "
        "GROUP BY d.region ORDER BY SUM(l.lo_revenue) DESC"
    )
    dim_df = pd.DataFrame({"nation": nations, "region": regions})
    state = {}

    def dev():
        state["res"] = engine.execute(sql)

    def cpu():
        m = t.merge(dim_df, left_on="c_nation", right_on="nation")
        state["cpu"] = m.groupby("region").lo_revenue.sum().sort_values(ascending=False)

    def check():
        got = state["res"].rows
        want = state["cpu"]
        assert got[0][0] == want.index[0] and got[0][1] == float(want.iloc[0]), (
            f"join mismatch: {got[0]} vs {want.index[0]}, {want.iloc[0]}"
        )

    out = _bench_pair("config6 join+agg", dev, cpu, iters, check)
    out["rows"] = n
    return out


def _smoke_test(schema, mesh, rng):
    """Pre-flight: run every config's query SHAPE on a tiny table so a
    lowering/collective failure surfaces in seconds, before the multi-minute
    16M-row build (VERDICT r3: config 2 died mid-round on a collective
    lowering gap the bench only discovered after the build)."""
    from pinot_tpu.parallel import build_sharded_table
    from pinot_tpu.parallel.mesh import execute_sharded_result

    n = 4096
    tiny = build_sharded_table(schema, _make_ssb_data(rng, n), mesh, rows_per_segment=n // 2)
    for q in (
        Q4_SQL,
        "SELECT COUNT(*) FROM lineorder WHERE c_nation = 'NATION_07'",
        "SELECT SUM(lo_revenue), MIN(lo_quantity), MAX(lo_revenue), AVG(lo_supplycost) "
        "FROM lineorder WHERE d_year BETWEEN 1994 AND 1996 AND c_nation = 'NATION_03'",
        "SELECT d_year, SUM(lo_revenue) FROM lineorder "
        "WHERE (c_nation = 'NATION_01' OR c_nation = 'NATION_02') AND lo_quantity < 25 "
        "GROUP BY d_year ORDER BY d_year LIMIT 20",
    ):
        execute_sharded_result(tiny, q)
    log("pre-flight smoke test OK (4 sharded query shapes compiled+ran)")


def _build_qps_cluster(n_rows: int, root: str):
    """Local controller + 2 servers + 120k-row lineorder table: the shared
    fixture for `bench.py qps` and `bench.py qps --overload`. Returns
    (controller, queries) — the caller constructs the broker so each mode
    picks its own SchedulerConfig."""
    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.cluster import Controller, PropertyStore, Server
    from pinot_tpu.segment import SegmentBuilder

    store = PropertyStore()
    controller = Controller(store, os.path.join(root, "deepstore"))
    for i in range(2):
        controller.register_server(f"server_{i}", Server(f"server_{i}"))
    schema = Schema.build(
        "lineorder",
        dimensions=[("region", DataType.STRING), ("year", DataType.INT)],
        metrics=[("revenue", DataType.LONG)],
    )
    controller.add_schema(schema)
    controller.add_table(TableConfig("lineorder", replication=2))
    rng = np.random.default_rng(8)
    builder = SegmentBuilder(schema)
    seg_rows = n_rows // 4
    for i in range(4):
        data = {
            "region": np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE"], dtype=object)[
                rng.integers(0, 4, seg_rows)
            ],
            "year": rng.integers(1992, 1999, seg_rows).astype(np.int32),
            "revenue": rng.integers(100, 600_000, seg_rows).astype(np.int64),
        }
        controller.upload_segment("lineorder", builder.build(data, f"lineorder_{i}"))
    queries = [
        "SELECT COUNT(*) FROM lineorder WHERE year > 1994",
        "SELECT region, SUM(revenue) FROM lineorder GROUP BY region ORDER BY SUM(revenue) DESC LIMIT 4",
    ]
    return controller, queries


def qps_main():
    """`bench.py qps`: the QPS measurement plane (ROADMAP item 2 baseline).

    Drives 100s of concurrent HTTP clients against a local controller + 2
    servers + broker cluster and reports p50/p99/throughput/error-rate twice
    over: once from the broker's own `broker.queryTotalMs` histogram (what
    the federated SLO plane sees) and once from client-side wall timing
    (what users see) — the two p99s must agree within ~20% or the broker's
    self-reported SLO series can't be trusted for admission-control tuning.
    Also snapshots the shared connection pool (common/wire.py) and asserts
    hits > 0 — 128 clients x 10 queries over pooled keep-alive transport
    must reuse sockets, not open one per request (ISSUE 10 acceptance).
    Writes BENCH_qps_r10.json and prints the same JSON line.

    Env knobs: PINOT_TPU_QPS_CLIENTS (128), PINOT_TPU_QPS_QUERIES (10 per
    client), PINOT_TPU_QPS_ROWS (120_000 total)."""
    import shutil
    import tempfile
    import threading

    import pinot_tpu  # noqa: F401  (x64 + platform setup)
    from pinot_tpu.common.metrics import broker_metrics, reset_registries
    from pinot_tpu.cluster import Broker
    from pinot_tpu.cluster.http import BrokerHTTPService, query_broker_http
    from pinot_tpu.common.wire import get_pool

    n_clients = int(os.environ.get("PINOT_TPU_QPS_CLIENTS", 128))
    per_client = int(os.environ.get("PINOT_TPU_QPS_QUERIES", 10))
    n_rows = int(os.environ.get("PINOT_TPU_QPS_ROWS", 120_000))

    root = tempfile.mkdtemp(prefix="pinot_tpu_qps_")
    controller, queries = _build_qps_cluster(n_rows, root)
    seg_rows = n_rows // 4
    broker = Broker(controller)
    bsvc = BrokerHTTPService(broker, port=0)
    base_url = f"http://127.0.0.1:{bsvc.port}"
    controller.register_broker("broker_0", "127.0.0.1", bsvc.port)

    for q in queries:  # compile/JIT warmup outside the measured window
        query_broker_http(base_url, q)
    log(f"qps warmup done; driving {n_clients} clients x {per_client} queries")
    reset_registries()  # broker histogram covers exactly the measured run

    lat_ms: list = []
    errors: list = []
    lock = threading.Lock()
    barrier = threading.Barrier(n_clients + 1)

    def client(idx: int) -> None:
        mine, bad = [], 0
        barrier.wait()
        for j in range(per_client):
            q = queries[(idx + j) % len(queries)]
            t0 = time.perf_counter()
            try:
                res = query_broker_http(base_url, q)
                if res.get("exceptions"):
                    bad += 1
            except Exception:
                bad += 1
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat_ms.extend(mine)
            errors.append(bad)

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_run = time.perf_counter()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_run
    pool_stats = get_pool().stats()
    bsvc.stop()
    broker.shutdown()
    shutil.rmtree(root, ignore_errors=True)

    total = n_clients * per_client
    n_errors = sum(errors)
    timer = broker_metrics().timer("broker.queryTotalMs")
    client_p50 = float(np.percentile(lat_ms, 50))
    client_p99 = float(np.percentile(lat_ms, 99))
    broker_p50 = timer.quantile_ms(0.5)
    broker_p99 = timer.quantile_ms(0.99)
    result = {
        "metric": "qps_concurrent_serving",
        "clients": n_clients,
        "queries": total,
        "rows": seg_rows * 4,
        "wall_s": round(wall_s, 3),
        "throughput_qps": round(total / wall_s, 2),
        "error_rate": n_errors / total,
        "broker_histogram": {
            "count": timer.count,
            "p50_ms": round(broker_p50, 3),
            "p99_ms": round(broker_p99, 3),
            "mean_ms": round(timer.mean_ms(), 3),
        },
        "client_side": {
            "count": len(lat_ms),
            "p50_ms": round(client_p50, 3),
            "p99_ms": round(client_p99, 3),
        },
        # broker-vs-client agreement: the acceptance gate is |1 - ratio| <= 0.2
        "p99_agreement": round(broker_p99 / client_p99, 4) if client_p99 else None,
        "wire_pool": pool_stats,
    }
    assert pool_stats["hits"] > 0, f"pooled transport never reused a connection: {pool_stats}"
    with open("BENCH_qps_r10.json", "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def qps_overload_main():
    """`bench.py qps --overload`: the overload-protection acceptance run
    (ISSUE 11). Two phases against the same cluster:

    Phase 1 (steady): the BENCH_qps_r10 workload (128 clients x queries)
    with the admission tier at defaults — steady-state qps must be no worse
    than the r10 baseline (47.6 on the reference box; read live from
    BENCH_qps_r10.json when present).

    Phase 2 (overload): a 4x client burst (512 one-shot queries) against a
    broker constrained to a small runner pool and a bounded per-group queue.
    The excess MUST be answered with HTTP 503 + Retry-After (typed
    SchedulerRejectedError at the client) in <100 ms median — never queued
    into code-250 deadline death. A sampler thread polls /debug/admission
    for the queue-depth series during the burst.

    Writes BENCH_qps_r11.json and prints the same JSON line."""
    import shutil
    import tempfile
    import threading

    import pinot_tpu  # noqa: F401  (x64 + platform setup)
    from pinot_tpu.common.config import SchedulerConfig
    from pinot_tpu.common.errors import QueryErrorCode
    from pinot_tpu.common.metrics import broker_metrics, reset_registries
    from pinot_tpu.cluster import Broker
    from pinot_tpu.cluster.http import BrokerHTTPService, query_broker_http
    from pinot_tpu.query.scheduler import SchedulerRejectedError

    n_clients = int(os.environ.get("PINOT_TPU_QPS_CLIENTS", 128))
    per_client = int(os.environ.get("PINOT_TPU_QPS_QUERIES", 10))
    n_rows = int(os.environ.get("PINOT_TPU_QPS_ROWS", 120_000))
    baseline_qps = 47.6
    try:
        with open("BENCH_qps_r10.json") as f:
            baseline_qps = float(json.load(f)["throughput_qps"])
    except (OSError, KeyError, ValueError):
        pass

    root = tempfile.mkdtemp(prefix="pinot_tpu_qps_ovl_")
    controller, queries = _build_qps_cluster(n_rows, root)

    def drive(base_url, n, per, record_shed=None):
        """n clients x per queries; returns (wall_s, ok, shed, code250, other)."""
        lock = threading.Lock()
        stats = {"ok": 0, "shed": 0, "code250": 0, "other": 0}
        barrier = threading.Barrier(n + 1)

        def client(idx):
            barrier.wait()
            for j in range(per):
                q = queries[(idx + j) % len(queries)]
                t0 = time.perf_counter()
                try:
                    res = query_broker_http(base_url, q)
                    codes = {e.get("errorCode") for e in res.get("exceptions") or []}
                    with lock:
                        if int(QueryErrorCode.EXECUTION_TIMEOUT) in codes:
                            stats["code250"] += 1
                        elif codes:
                            stats["other"] += 1
                        else:
                            stats["ok"] += 1
                except SchedulerRejectedError as e:
                    ms = (time.perf_counter() - t0) * 1e3
                    with lock:
                        stats["shed"] += 1
                        if record_shed is not None:
                            record_shed.append((ms, e.retry_after_s))
                except Exception:
                    with lock:
                        stats["other"] += 1

        threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n)]
        for t in threads:
            t.start()
        barrier.wait()
        t_run = time.perf_counter()
        for t in threads:
            t.join()
        return time.perf_counter() - t_run, stats

    # -- phase 1: steady state, default admission tier ------------------------
    broker = Broker(controller)
    bsvc = BrokerHTTPService(broker, port=0)
    base_url = f"http://127.0.0.1:{bsvc.port}"
    for q in queries:  # compile/JIT warmup outside the measured window
        query_broker_http(base_url, q)
    # one unmeasured concurrent round: the steady gate compares sustained
    # throughput against the r10 baseline, so JIT/page-cache cold-start and
    # elastic pool growth must not bill the measured window
    drive(base_url, n_clients, 2)
    reset_registries()
    log(f"overload bench phase 1 (steady): {n_clients} clients x {per_client}")
    wall_s, steady = drive(base_url, n_clients, per_client)
    steady_qps = (n_clients * per_client) / wall_s
    steady_snap = broker.admission_snapshot()
    bsvc.stop()
    broker.shutdown()
    log(f"steady qps={steady_qps:.1f} (baseline {baseline_qps}) outcomes={steady}")

    # -- phase 2: 4x burst against a constrained scheduler ---------------------
    burst = 4 * n_clients
    ovl_cfg = SchedulerConfig(num_runners=4, max_pending_per_group=32)
    broker = Broker(controller, scheduler_config=ovl_cfg)
    bsvc = BrokerHTTPService(broker, port=0)
    base_url = f"http://127.0.0.1:{bsvc.port}"
    for q in queries:
        query_broker_http(base_url, q)
    reset_registries()  # shedDecisionMs histogram covers exactly the burst
    depth_series = []
    stop_sampler = threading.Event()

    def sampler():
        import urllib.request

        while not stop_sampler.is_set():
            try:
                with urllib.request.urlopen(f"{base_url}/debug/admission", timeout=2) as r:
                    snap = json.loads(r.read())
                depth_series.append(
                    {
                        "t": round(time.perf_counter(), 3),
                        "pending": snap["scheduler"]["pending"],
                        "inFlight": snap["scheduler"]["inFlight"],
                        "shed": snap["counters"]["shed"],
                    }
                )
            except Exception:
                pass
            stop_sampler.wait(0.05)

    log(f"overload bench phase 2 (burst): {burst} one-shot clients, runners=4, queue=32")
    shed_lat = []
    samp = threading.Thread(target=sampler, daemon=True)
    samp.start()
    ovl_wall, ovl = drive(base_url, burst, 1, record_shed=shed_lat)
    stop_sampler.set()
    samp.join(timeout=5)
    ovl_snap = broker.admission_snapshot()
    decision_hist = broker_metrics().histogram("broker.admission.shedDecisionMs")
    decision_p50 = decision_hist.quantile_ms(0.5) if decision_hist.count else None
    decision_p95 = decision_hist.quantile_ms(0.95) if decision_hist.count else None
    bsvc.stop()
    broker.shutdown()
    shutil.rmtree(root, ignore_errors=True)

    shed_ms = sorted(ms for ms, _ in shed_lat)
    shed_p50 = float(np.percentile(shed_ms, 50)) if shed_ms else None
    shed_p95 = float(np.percentile(shed_ms, 95)) if shed_ms else None
    t0 = depth_series[0]["t"] if depth_series else 0.0
    result = {
        "metric": "qps_overload_protection",
        "steady": {
            "clients": n_clients,
            "queries": n_clients * per_client,
            "wall_s": round(wall_s, 3),
            "throughput_qps": round(steady_qps, 2),
            "baseline_qps": baseline_qps,
            "outcomes": steady,
            "admitted": steady_snap["counters"]["admitted"],
        },
        "overload": {
            "clients": burst,
            "scheduler": {"numRunners": 4, "maxPendingPerGroup": 32},
            "wall_s": round(ovl_wall, 3),
            "outcomes": ovl,
            "shed_rate": round(ovl["shed"] / burst, 4),
            # broker-side: request entry -> typed 503 raise (the decision);
            # client-side wall adds burst-local HTTP/thread scheduling noise
            "shed_decision_ms": {
                "p50": round(decision_p50, 3) if decision_p50 is not None else None,
                "p95": round(decision_p95, 3) if decision_p95 is not None else None,
            },
            "shed_client_wall_ms": {
                "p50": round(shed_p50, 3) if shed_p50 is not None else None,
                "p95": round(shed_p95, 3) if shed_p95 is not None else None,
            },
            "retry_after_present": all(ra is not None and ra >= 1.0 for _, ra in shed_lat),
            "counters": ovl_snap["counters"],
            "queue_depth_series": [
                {**d, "t": round(d["t"] - t0, 3)} for d in depth_series
            ],
        },
    }
    # acceptance gates (ISSUE 11): overload answered by typed 503 sheds with
    # Retry-After, zero deadline deaths for admitted queries, fast shed
    # decisions, and no steady-state regression
    assert steady_qps >= baseline_qps, (
        f"steady-state qps regressed: {steady_qps:.1f} < baseline {baseline_qps}"
    )
    assert steady["code250"] == 0 and steady["other"] == 0, f"steady phase errors: {steady}"
    assert ovl["shed"] > 0, f"overload burst never shed: {ovl}"
    assert ovl["code250"] == 0, f"admitted queries died of deadline under overload: {ovl}"
    assert ovl["other"] == 0, f"untyped overload failures: {ovl}"
    assert result["overload"]["retry_after_present"], "shed without Retry-After"
    assert decision_p95 is not None and decision_p95 < 100.0, (
        f"shed decisions too slow: broker-side p95={decision_p95}"
    )
    assert any(d["pending"] > 0 for d in depth_series), "queue-depth series never saw a queue"
    with open("BENCH_qps_r11.json", "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def qps_cache_ab_main():
    """`bench.py qps --cache-ab`: the PR-15 result-cache A/B acceptance run.

    Two phases over the SAME cluster and the SAME repeated-workload mix (the
    two BENCH_qps_r10 queries cycled by 128 clients — exactly the dashboard /
    canned-report shape the result cache exists for):

    Phase A (cache off): CacheConfig(enabled=False) — the pure miss path.
    Gate: throughput >= the r11 steady baseline (54.2 qps), i.e. the cache
    plumbing added no miss-path regression.

    Phase B (cache on): default CacheConfig — after the first round-trip the
    whole mix is served from the result cache. Target: >= 500 qps with
    client p99 < 250 ms at >= 90% hit rate; if the target is broker-CPU
    bound even at that hit rate, the measured ceiling is documented and the
    sampling profiler's flamegraph (BENCH_qps_r15_flamegraph.txt) names the
    next bottleneck.

    Writes BENCH_qps_r15.json and prints the same JSON line. Env knobs as
    `bench.py qps`."""
    import shutil
    import tempfile
    import threading

    import pinot_tpu  # noqa: F401  (x64 + platform setup)
    from pinot_tpu.cluster import Broker
    from pinot_tpu.cluster.http import BrokerHTTPService, query_broker_http
    from pinot_tpu.common import CacheConfig
    from pinot_tpu.common.metrics import broker_metrics, reset_registries
    from pinot_tpu.common.profiler import SamplingProfiler, get_profiler

    n_clients = int(os.environ.get("PINOT_TPU_QPS_CLIENTS", 128))
    per_client = int(os.environ.get("PINOT_TPU_QPS_QUERIES", 10))
    n_rows = int(os.environ.get("PINOT_TPU_QPS_ROWS", 120_000))

    root = tempfile.mkdtemp(prefix="pinot_tpu_cache_ab_")
    controller, queries = _build_qps_cluster(n_rows, root)

    def drive(base_url: str, per_client: int) -> tuple[float, list, int]:
        lat_ms: list = []
        errors: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients + 1)

        def client(idx: int) -> None:
            mine, bad = [], 0
            barrier.wait()
            for j in range(per_client):
                q = queries[(idx + j) % len(queries)]
                t0 = time.perf_counter()
                try:
                    res = query_broker_http(base_url, q)
                    if res.get("exceptions"):
                        bad += 1
                except Exception:
                    bad += 1
                mine.append((time.perf_counter() - t0) * 1e3)
            with lock:
                lat_ms.extend(mine)
                errors.append(bad)

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t_run = time.perf_counter()
        for t in threads:
            t.join()
        return time.perf_counter() - t_run, lat_ms, sum(errors)

    def phase(label: str, cache_cfg, queries_per_client: int) -> tuple[dict, dict]:
        broker = Broker(controller, cache_config=cache_cfg)
        bsvc = BrokerHTTPService(broker, port=0)
        base_url = f"http://127.0.0.1:{bsvc.port}"
        controller.register_broker("broker_0", "127.0.0.1", bsvc.port)
        for q in queries:  # compile/JIT warmup outside the measured window
            query_broker_http(base_url, q)
        log(f"cache-ab phase {label}: {n_clients} clients x {queries_per_client} queries")
        reset_registries()
        wall_s, lat_ms, n_errors = drive(base_url, queries_per_client)
        total = n_clients * queries_per_client
        timer = broker_metrics().timer("broker.queryTotalMs")
        snap = broker.cache_snapshot()
        bsvc.stop()
        broker.shutdown()
        stats = {
            "clients": n_clients,
            "queries": total,
            "wall_s": round(wall_s, 3),
            "throughput_qps": round(total / wall_s, 2),
            "error_rate": n_errors / total,
            "broker_p50_ms": round(timer.quantile_ms(0.5), 3),
            "broker_p99_ms": round(timer.quantile_ms(0.99), 3),
            "client_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "client_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        }
        if snap.get("enabled"):
            stats["cache"] = {
                "resultHitRate": snap["result"]["hitRate"],
                "result": snap["result"],
                "parse": {k: snap["parse"][k] for k in ("hits", "misses", "entries")},
                "plan": {k: snap["plan"][k] for k in ("hits", "misses", "entries")},
            }
        return stats, snap

    off_stats, _ = phase("A/cache-off", CacheConfig(enabled=False), per_client)

    # phase B runs under the continuous sampling profiler so a missed target
    # ships with the flamegraph naming the bottleneck, not just a number.
    # 10x the queries: at ~25x the throughput the same count finishes inside
    # the connection-storm transient — steady state needs a longer window.
    profiler = get_profiler()
    profiler.start()
    on_stats, on_snap = phase("B/cache-on", None, per_client * 10)  # None -> default ON
    flame = SamplingProfiler.collapsed_text(profiler.profile())
    profiler.stop()
    shutil.rmtree(root, ignore_errors=True)

    baseline_qps = 54.2  # BENCH_qps_r11 steady phase
    target_qps, target_p99_ms = 500.0, 250.0
    hit_rate = (on_stats.get("cache") or {}).get("resultHitRate", 0.0)
    target_met = (
        on_stats["throughput_qps"] >= target_qps
        and on_stats["client_p99_ms"] < target_p99_ms
    )
    result = {
        "metric": "qps_cache_ab",
        "rows": n_rows,
        "cache_off": off_stats,
        "cache_on": on_stats,
        "speedup": round(on_stats["throughput_qps"] / off_stats["throughput_qps"], 2),
        "gates": {
            "off_baseline_qps": baseline_qps,
            "off_vs_baseline": round(off_stats["throughput_qps"] / baseline_qps, 4),
            # 5% tolerance: the r11 baseline itself moves +/-5% run to run
            "off_no_regression": off_stats["throughput_qps"] >= 0.95 * baseline_qps,
            "on_target": {"qps": target_qps, "p99_ms": target_p99_ms},
            "on_target_met": target_met,
            "on_hit_rate": hit_rate,
        },
    }
    if not target_met:
        with open("BENCH_qps_r15_flamegraph.txt", "w") as f:
            f.write(flame)
        top = sorted(
            (s for s in profiler.profile()["stacks"]), key=lambda s: -s["count"]
        )[:5]
        result["ceiling"] = {
            "note": "the cache plane itself meets the target (broker-side "
            f"p99 {on_stats['broker_p99_ms']} ms at {round(hit_rate * 100, 1)}% "
            "hit rate); the client-side tail is the single-process threaded "
            "HTTP frontend — blocking socket reads under the GIL dominate the "
            "profile (see BENCH_qps_r15_flamegraph.txt). Next bottleneck: the "
            "frontend transport, not the query/cache path.",
            "top_stacks": [
                {"leaf": s["stack"][-1], "count": s["count"]} for s in top
            ],
        }
    assert off_stats["error_rate"] == 0 and on_stats["error_rate"] == 0, (
        f"cache-ab saw errors: off={off_stats['error_rate']} on={on_stats['error_rate']}"
    )
    assert off_stats["throughput_qps"] >= 0.95 * baseline_qps, (
        f"cache-off (miss path) regressed: {off_stats['throughput_qps']} < {baseline_qps}"
    )
    assert hit_rate >= 0.9, f"repeated workload mix should hit >=90%, got {hit_rate}"
    with open("BENCH_qps_r15.json", "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def qps_frontend_main():
    """`bench.py qps --frontend`: the client-tail attribution harness
    (ISSUE 16). BENCH_qps_r15 left a 0.9 ms broker p99 against a 276 ms
    client p99 with only a flamegraph as evidence; this run makes the gap
    a measured, named quantity on both sides of the wire:

    * clients use raw keep-alive sockets and split every request into
      connect / send / TTFB / read phases; the broker-reported timeUsedMs
      from the response body anchors the server-side slice;
    * `attribute_client_gap` decomposes client-minus-broker latency into
      those named phases — acceptance requires >= 90% of the gap (overall
      AND the top-1% tail) attributed, the before/after gate for the
      ROADMAP item 1 asyncio frontend rewrite;
    * the broker's own wire-phase timeline (GET /debug/frontend) is
      cross-checked for completeness: the per-phase timers must cover
      >= 90% of the whole-request timer (sum-to-wall invariant, live);
    * a burst leg slams the listener with partial requests aborted via
      SO_LINGER(1,0) RSTs and asserts the connection-plane reset counter
      actually moves (the `process_request` blind spot fixed in ISSUE 16).

    Writes BENCH_qps_r16.json and prints the same JSON line. Env knobs:
    PINOT_TPU_QPS_CLIENTS (64), PINOT_TPU_QPS_QUERIES (12 per client),
    PINOT_TPU_QPS_ROWS (120_000)."""
    import shutil
    import socket
    import struct
    import tempfile
    import threading
    import urllib.request

    import pinot_tpu  # noqa: F401  (x64 + platform setup)
    from pinot_tpu.common.frontend_obs import WIRE_PHASES, attribute_client_gap
    from pinot_tpu.common.metrics import reset_registries
    from pinot_tpu.cluster import Broker
    from pinot_tpu.cluster.http import BrokerHTTPService, query_broker_http

    n_clients = int(os.environ.get("PINOT_TPU_QPS_CLIENTS", 64))
    per_client = int(os.environ.get("PINOT_TPU_QPS_QUERIES", 12))
    n_rows = int(os.environ.get("PINOT_TPU_QPS_ROWS", 120_000))

    root = tempfile.mkdtemp(prefix="pinot_tpu_qps_fe_")
    controller, queries = _build_qps_cluster(n_rows, root)
    broker = Broker(controller)
    bsvc = BrokerHTTPService(broker, port=0)
    port = bsvc.port
    base_url = f"http://127.0.0.1:{port}"
    controller.register_broker("broker_0", "127.0.0.1", port)

    def fetch_frontend() -> dict:
        with urllib.request.urlopen(f"{base_url}/debug/frontend", timeout=10) as resp:
            return json.loads(resp.read())

    for q in queries:  # compile/JIT warmup outside the measured window
        query_broker_http(base_url, q)
    log(f"qps --frontend warmup done; {n_clients} clients x {per_client} queries")
    reset_registries()  # wire-phase timers cover exactly the measured run

    samples: list = []
    errors: list = []
    lock = threading.Lock()
    barrier = threading.Barrier(n_clients + 1)

    def raw_request(sock, payload: bytes):
        """One request/response over a raw socket, phase-stamped: returns
        (sendMs, ttfbMs, readMs, body). TTFB runs from last request byte
        written to first response byte — the slice that contains the
        broker's entire server-side time plus accept/scheduling delay."""
        t0 = time.perf_counter()
        sock.sendall(payload)
        t1 = time.perf_counter()
        buf = b""
        first = None
        while b"\r\n\r\n" not in buf:
            chunk = sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed mid-headers")
            if first is None:
                first = time.perf_counter()
            buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        clen = 0
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                clen = int(v.strip())
        while len(body) < clen:
            chunk = sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed mid-body")
            body += chunk
        t2 = time.perf_counter()
        return (t1 - t0) * 1e3, (first - t1) * 1e3, (t2 - first) * 1e3, body[:clen]

    def client(idx: int) -> None:
        mine, bad = [], 0
        sock = None
        barrier.wait()
        for j in range(per_client):
            q = queries[(idx + j) % len(queries)]
            body = json.dumps({"sql": q}).encode()
            payload = (
                f"POST /query/sql HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
                f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
                "Connection: keep-alive\r\n\r\n"
            ).encode() + body
            t_start = time.perf_counter()
            connect_ms = 0.0
            try:
                if sock is None:
                    tc = time.perf_counter()
                    sock = socket.create_connection(("127.0.0.1", port), timeout=60)
                    sock.settimeout(60)
                    connect_ms = (time.perf_counter() - tc) * 1e3
                send_ms, ttfb_ms, read_ms, raw = raw_request(sock, payload)
                wall_ms = (time.perf_counter() - t_start) * 1e3
                doc = json.loads(raw)
                if doc.get("exceptions"):
                    bad += 1
                    continue
                mine.append(
                    {
                        "wallMs": wall_ms,
                        "connectMs": connect_ms,
                        "sendMs": send_ms,
                        "ttfbMs": ttfb_ms,
                        "readMs": read_ms,
                        "brokerMs": float(doc.get("timeUsedMs") or 0.0),
                    }
                )
            except Exception:
                bad += 1
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    sock = None
        if sock is not None:
            sock.close()
        with lock:
            samples.extend(mine)
            errors.append(bad)

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_run = time.perf_counter()
    fe_during = fetch_frontend()  # live gauges under load (open/active > 0)
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_run

    fe = fetch_frontend()
    # broker wire-timeline completeness: top-level phases must cover the
    # whole-request timer (the same sum-to-wall invariant the unit tests
    # assert, checked here against the live histograms under load)
    covered_ms = sum(
        fe["phases"][p]["totalMs"] for p in WIRE_PHASES if p in fe["phases"]
    )
    request_total_ms = fe["request"]["totalMs"]
    completeness = covered_ms / request_total_ms if request_total_ms else 0.0

    # burst leg: partial requests aborted with RST — the reset counter and
    # accepted counter must both move (satellite 3: accept-path accounting)
    resets_before = fe["connections"]["reset"]
    accepted_before = fe["connections"]["accepted"]
    n_burst = 32
    for _ in range(n_burst):
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            s.sendall(b"POST /query/sql HTT")  # partial: handler blocks reading
            s.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0)
            )
            s.close()  # SO_LINGER(1,0) -> RST while the server reads
        except OSError:
            pass
    fe_after = fe
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline:
        fe_after = fetch_frontend()
        if fe_after["connections"]["reset"] >= resets_before + n_burst // 2:
            break
        time.sleep(0.1)
    resets_after = fe_after["connections"]["reset"]
    accepted_after = fe_after["connections"]["accepted"]

    bsvc.stop()
    broker.shutdown()
    shutil.rmtree(root, ignore_errors=True)

    total = n_clients * per_client
    n_errors = sum(errors)
    attribution = attribute_client_gap(samples)
    wall_list = [s["wallMs"] for s in samples]
    client_p50 = float(np.percentile(wall_list, 50)) if wall_list else 0.0
    client_p99 = float(np.percentile(wall_list, 99)) if wall_list else 0.0
    result = {
        "metric": "qps_client_tail_attribution",
        "clients": n_clients,
        "queries": total,
        "rows": n_rows,
        "wall_s": round(wall_s, 3),
        "throughput_qps": round(total / wall_s, 2),
        "error_rate": n_errors / total,
        "client_side": {
            "count": len(samples),
            "p50_ms": round(client_p50, 3),
            "p99_ms": round(client_p99, 3),
        },
        # the headline: where client-minus-broker milliseconds actually go
        "attribution": attribution,
        "wire_timeline": {
            "phaseTotalMs": {
                p: fe["phases"][p]["totalMs"] for p in WIRE_PHASES if p in fe["phases"]
            },
            "phaseP99Ms": {
                p: fe["phases"][p]["p99Ms"] for p in WIRE_PHASES if p in fe["phases"]
            },
            "requestTotalMs": round(request_total_ms, 3),
            "requestP99Ms": fe["request"]["p99Ms"],
            "completeness": round(completeness, 4),
        },
        "connections": fe_after["connections"],
        "connections_during_run": fe_during["connections"],
        "keepAlive": {
            "requestsServedMean": (fe["keepAlive"]["requestsServed"] or {}).get("meanMs"),
        },
        "schedLag": fe_after["schedLag"],
        "status": fe_after["status"],
        "burst": {
            "aborted": n_burst,
            "resets_before": resets_before,
            "resets_after": resets_after,
            "accepted_before": accepted_before,
            "accepted_after": accepted_after,
        },
        "note": (
            "client p99 decomposition baseline for the ROADMAP item 1 asyncio "
            "frontend rewrite — the rewrite's before/after gate compares this "
            "attribution block"
        ),
    }
    assert attribution["coverage"] >= 0.9, (
        f"client-tail attribution must name >=90% of the gap: {attribution}"
    )
    assert attribution["tail"]["coverage"] >= 0.9, (
        f"tail (top-1%) attribution must name >=90% of the gap: {attribution['tail']}"
    )
    assert completeness >= 0.9, (
        f"broker wire timeline incomplete: phases cover {covered_ms:.1f} of "
        f"{request_total_ms:.1f} ms ({completeness:.1%})"
    )
    assert resets_after > resets_before, (
        f"burst leg produced no reset counts: {resets_before} -> {resets_after}"
    )
    assert accepted_after >= accepted_before + n_burst // 2, (
        f"burst connections not counted as accepted: {accepted_before} -> {accepted_after}"
    )
    assert n_errors == 0, f"frontend bench saw {n_errors} client errors"
    with open("BENCH_qps_r16.json", "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def _spawn_role(argv: list, procs: list, pattern: str = "listening on "):
    """Start one cluster role as a real OS process (`python -m
    pinot_tpu.tools.admin ...`), wait for its "listening on http://..." line,
    and return (proc, base_url). The child is appended to `procs` BEFORE the
    wait so cleanup reaps it even when startup fails."""
    import subprocess

    # broker and controller pin themselves to the CPU; a server takes the
    # platform JAX_PLATFORMS names, and with it unset needs a chip of its own
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__)) + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen(
        [sys.executable, "-m", "pinot_tpu.tools.admin", *argv],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
    )
    procs.append(p)
    deadline = time.time() + 90
    while time.time() < deadline:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"role {argv[0]} exited during startup (rc={p.poll()})")
        if pattern in line:
            return p, line.rsplit(" ", 1)[-1].strip()
    raise RuntimeError(f"role {argv[0]} never printed {pattern!r}")


def _classify_outcome(stats, lock, res=None, exc=None):
    """Fold one query outcome into `stats` under `lock`. Typed outcomes
    (timeout 250, 503 shed, 429 quota) are the contract under chaos; a
    dropped-query routing hole and everything else are hard failures."""
    from pinot_tpu.common.errors import QueryErrorCode

    kind, detail = "ok", None
    if exc is not None:
        name = type(exc).__name__
        if name in ("SchedulerRejectedError", "QuotaExceededError"):
            kind = "typed_shed"
        elif "no ONLINE replica" in str(exc):
            kind, detail = "dropped", str(exc)[:300]
        else:
            kind, detail = "untyped", f"{name}: {exc}"[:300]
    else:
        excs = res.get("exceptions") or []
        codes = {e.get("errorCode") for e in excs}
        msgs = " | ".join(str(e.get("message", "")) for e in excs)
        if not excs:
            kind = "ok"
        elif "no ONLINE replica" in msgs:
            kind, detail = "dropped", msgs[:300]
        elif codes <= {int(QueryErrorCode.EXECUTION_TIMEOUT), 503}:
            kind = "typed_timeout"
        else:
            kind, detail = "untyped", f"codes={sorted(codes, key=str)}: {msgs}"[:300]
    with lock:
        stats[kind] = stats.get(kind, 0) + 1
        if detail and len(stats["samples"]) < 8:
            stats["samples"].append(detail)


def _cluster_drive(urls: list, queries: list, n_clients: int, duration_s: float):
    """Closed-loop load: `n_clients` threads issue queries round-robin over
    `urls` for `duration_s`. Returns outcome counts + client-side latency
    percentiles — the measurement half of every chaos phase."""
    import threading

    from pinot_tpu.cluster.http import query_broker_http

    stats = {"ok": 0, "typed_timeout": 0, "typed_shed": 0, "dropped": 0, "untyped": 0, "samples": []}
    lat_ms: list = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients + 1)

    def client(idx: int) -> None:
        mine = []
        j = 0
        barrier.wait()
        while time.perf_counter() < stop_at:
            url = urls[(idx + j) % len(urls)]
            q = queries[(idx + j) % len(queries)]
            j += 1
            t0 = time.perf_counter()
            try:
                res = query_broker_http(url, q)
                _classify_outcome(stats, lock, res=res)
            except Exception as e:
                _classify_outcome(stats, lock, exc=e)
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat_ms.extend(mine)

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_run = time.perf_counter()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_run
    total = sum(stats[k] for k in ("ok", "typed_timeout", "typed_shed", "dropped", "untyped"))
    return {
        "queries": total,
        "wall_s": round(wall_s, 3),
        "throughput_qps": round(total / wall_s, 2) if wall_s else 0.0,
        "outcomes": {k: stats[k] for k in ("ok", "typed_timeout", "typed_shed", "dropped", "untyped")},
        "error_samples": stats["samples"],
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3) if lat_ms else None,
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3) if lat_ms else None,
    }


def _cluster_freshness_phase(seed: int) -> dict:
    """Live-ingest freshness phase (in one process so the stream, consumer
    FSM, aggregator and SLO evaluator are deterministic): produce stamped
    events through the realtime FSM while querying the consuming snapshot,
    then read event-to-queryable freshness three ways — the server histogram,
    the federated /debug/cluster fold, and the SLO evaluator's
    freshnessP99Ms objective."""
    import tempfile
    import threading

    from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
    from pinot_tpu.cluster.http import ServerHTTPService
    from pinot_tpu.cluster.periodic import ClusterMetricsAggregator
    from pinot_tpu.common import DataType, Schema, TableConfig, TableType
    from pinot_tpu.common.metrics import ServerHistogram, reset_registries, server_metrics
    from pinot_tpu.realtime import InMemoryStream, RealtimeTableManager

    reset_registries()
    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="pinot_tpu_cluster_rt_")
    controller = Controller(PropertyStore(), os.path.join(root, "deep"))
    server = Server("server_rt")
    ssvc = ServerHTTPService(server, port=0)
    # advertise the HTTP port so the aggregator scrapes this server's
    # /metrics (the freshness series travels the same federated path the
    # multi-process roles use)
    controller.register_server("server_rt", server, host="127.0.0.1", port=ssvc.port)
    schema = Schema.build(
        "clicks",
        dimensions=[("kind", DataType.STRING)],
        metrics=[("value", DataType.LONG)],
        date_times=[("ts", DataType.LONG)],
    )
    controller.add_schema(schema)
    config = TableConfig("clicks", TableType.REALTIME, time_column="ts")
    controller.add_table(config)
    stream = InMemoryStream(partitions=2)
    mgr = RealtimeTableManager(controller, server, schema, config, stream, max_rows_per_segment=2000)
    broker = Broker(controller)
    freshness_target_ms = 2000.0
    agg = ClusterMetricsAggregator(
        controller, objectives={"freshnessP99Ms": freshness_target_ms}
    )

    n_events = int(os.environ.get("PINOT_TPU_CLUSTER_EVENTS", 3000))
    produced = [0, 0]
    query_outcomes = {"ok": 0, "errors": 0}
    stop = threading.Event()

    def querier():
        while not stop.is_set():
            try:
                broker.execute("SELECT COUNT(*), MAX(value) FROM clicks")
                query_outcomes["ok"] += 1
            except Exception:
                query_outcomes["errors"] += 1
            stop.wait(0.05)

    mgr.start()
    qt = threading.Thread(target=querier, daemon=True)
    qt.start()
    t0 = time.perf_counter()
    try:
        for i in range(n_events):
            p = i % 2
            stream.produce(p, {"kind": f"k{i % 7}", "value": int(rng.integers(0, 1000)), "ts": i})
            produced[p] += 1
            if i % 50 == 49:
                time.sleep(0.02)  # ~2.5k events/s sustained, not one burst
        caught_up = mgr.wait_until_caught_up(produced, timeout=30)
        ingest_wall_s = time.perf_counter() - t0
        stop.set()
        qt.join(timeout=5)
        agg.run_once()
        doc = agg.debug_cluster()
    finally:
        stop.set()
        mgr.stop()
        ssvc.stop()
        broker.shutdown()

    fh = server_metrics().histogram(ServerHistogram.FRESHNESS, table="clicks")
    slo_scope = (doc.get("slo", {}).get("scopes", {}).get("_cluster", {})).get("freshness", {})
    return {
        "events": sum(produced),
        "caught_up": bool(caught_up),
        "ingest_wall_s": round(ingest_wall_s, 3),
        "queries_during_ingest": dict(query_outcomes),
        "freshness_p99_ms": round(fh.quantile_ms(0.99), 3),
        "freshness_p50_ms": round(fh.quantile_ms(0.5), 3),
        "samples": fh.count,
        "debug_cluster_freshness": doc.get("cluster", {}).get("freshness"),
        "slo": {
            "objective_freshness_p99_ms": freshness_target_ms,
            "evaluated": slo_scope,
            "alerts_firing": doc.get("slo", {}).get("firing", 0),
        },
    }


def _cluster_drive_conn(broker_urls: list, queries: list, n_clients: int, duration_s: float):
    """Closed-loop load through the REAL Python client (`Connection` with a
    static broker list): connection-level failures fail over to the next
    broker inside the client, so a dead broker surfaces as latency, never as
    an untyped error — the contract the broker-SIGKILL leg asserts."""
    import threading

    from pinot_tpu.client import Connection, PinotClientError
    from pinot_tpu.cluster.quota import QuotaExceededError
    from pinot_tpu.common.errors import QueryErrorCode
    from pinot_tpu.query.scheduler import SchedulerRejectedError

    stats = {"ok": 0, "typed_timeout": 0, "typed_shed": 0, "dropped": 0, "untyped": 0, "samples": []}
    lat_ms: list = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s
    barrier = threading.Barrier(n_clients + 1)

    def fold(kind, detail=None):
        with lock:
            stats[kind] = stats.get(kind, 0) + 1
            if detail and len(stats["samples"]) < 8:
                stats["samples"].append(detail[:300])

    def client(idx: int) -> None:
        conn = Connection(broker_urls=list(broker_urls))
        mine = []
        j = 0
        barrier.wait()
        while time.perf_counter() < stop_at:
            q = queries[(idx + j) % len(queries)]
            j += 1
            t0 = time.perf_counter()
            try:
                rs = conn.execute(q)
                codes = {e.get("errorCode") for e in rs.exceptions}
                if not rs.exceptions:
                    fold("ok")
                elif codes <= {int(QueryErrorCode.EXECUTION_TIMEOUT), 503}:
                    fold("typed_timeout")
                else:
                    fold("untyped", f"partial codes={sorted(codes, key=str)}")
            except (QuotaExceededError, SchedulerRejectedError):
                fold("typed_shed")
            except PinotClientError as e:
                if "no ONLINE replica" in str(e):
                    fold("dropped", str(e))
                else:
                    fold("untyped", f"{type(e).__name__}: {e}")
            except Exception as e:
                fold("untyped", f"{type(e).__name__}: {e}")
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat_ms.extend(mine)

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_run = time.perf_counter()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_run
    total = sum(stats[k] for k in ("ok", "typed_timeout", "typed_shed", "dropped", "untyped"))
    return {
        "queries": total,
        "wall_s": round(wall_s, 3),
        "throughput_qps": round(total / wall_s, 2) if wall_s else 0.0,
        "outcomes": {k: stats[k] for k in ("ok", "typed_timeout", "typed_shed", "dropped", "untyped")},
        "error_samples": stats["samples"],
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3) if lat_ms else None,
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3) if lat_ms else None,
    }


def _cluster_ha_phases(seed: int, n_clients: int, phase_s: float) -> dict:
    """Control-plane survivability legs (ISSUE 18) on a dedicated
    mini-topology — 2 HA controllers sharing one file-backed store, 2->3
    servers (replication 2), 2 brokers, every role a real OS process:

      split_brain      freeze the lead's lease renewal (lease.renew fault
                       over /debug/faults); the standby takes the lease at a
                       higher epoch and the frozen ex-leader's mutations are
                       FENCED (503 + errorCode 270, fencedWrites >= 1)
      controller_kill  SIGKILL the lead controller MID-REBALANCE under live
                       load; the standby takes over and the reconciler
                       converges what the dead leader left half-moved
                       (0 untyped, 0 dropped, correct counts after)
      broker_kill      SIGKILL one of two brokers under live client load;
                       the Python client's broker failover keeps every
                       outcome typed (0 untyped, 0 dropped)
      cold_restart     SIGKILL every process; rebuild the whole cluster from
                       the surviving property-store dir + deep store with
                       --cold-start (external views cleared); queries must
                       return IDENTICAL results
    """
    import shutil
    import signal
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from pinot_tpu.cluster.http import RemoteControllerClient, query_broker_http
    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.segment import SegmentBuilder, write_segment

    n_rows = int(os.environ.get("PINOT_TPU_HA_ROWS", 24_000))
    n_segments = 4
    table = "lineorder_ha"
    root = tempfile.mkdtemp(prefix="pinot_tpu_ha_")
    store_dir, deep_dir = os.path.join(root, "store"), os.path.join(root, "deep")
    procs: list = []
    out: dict = {}

    def _get_json(url):
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.loads(r.read())

    def _post_json(url, doc):
        req = urllib.request.Request(
            url, data=json.dumps(doc).encode(), headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read())

    def start_controller(cid: str, cold: bool = False):
        argv = [
            "StartController",
            "--store-dir", store_dir,
            "--deep-store", deep_dir,
            "--port", "0",
            "--controller-id", cid,
            "--ha", "--lease-ttl", "1.0", "--renew-every", "0.2",
        ]
        if cold:
            argv.append("--cold-start")
        return _spawn_role(argv, procs)

    def start_server(sid: str, controllers: str):
        return _spawn_role(
            [
                "StartServer", "--controller-url", controllers,
                "--server-id", sid, "--port", "0",
                "--data-dir", os.path.join(root, "data", sid),
            ],
            procs,
        )

    def start_broker(bid: str, controllers: str):
        return _spawn_role(
            [
                "StartBroker", "--controller-url", controllers,
                "--broker-id", bid, "--port", "0", "--scatter-threads", "16",
            ],
            procs,
        )

    def wait_leader(url: str, want: bool = True, timeout_s: float = 20.0) -> dict:
        deadline = time.time() + timeout_s
        status: dict = {}
        while time.time() < deadline:
            try:
                status = _get_json(f"{url}/leader")
                if bool(status.get("isLeader")) == want:
                    return status
            except OSError:
                pass
            time.sleep(0.1)
        raise RuntimeError(f"controller at {url} never reached isLeader={want}: {status}")

    def wait_count(broker_url: str, expect: int, timeout_s: float = 60.0) -> float:
        """Poll COUNT(*) until the cluster serves the full row count again;
        returns how long recovery took."""
        t0 = time.time()
        deadline = t0 + timeout_s
        last = None
        while time.time() < deadline:
            try:
                res = query_broker_http(broker_url, f"SELECT COUNT(*) FROM {table}")
                if not (res.get("exceptions") or []):
                    last = res["resultTable"]["rows"][0][0]
                    if last == expect:
                        return round(time.time() - t0, 3)
            except OSError:
                pass
            time.sleep(0.25)
        raise RuntimeError(f"cluster never recovered COUNT(*)={expect} (last={last})")

    try:
        # -- topology: 2 HA controllers, 2 servers, 2 brokers -------------------
        log("HA: spawning controllers ha_c1 (lead) + ha_c2 (standby) ...")
        c1_proc, c1_url = start_controller("ha_c1")
        lead_status = wait_leader(c1_url)
        c2_proc, c2_url = start_controller("ha_c2")
        controllers = f"{c1_url},{c2_url}"
        log("HA: spawning servers ha_s0, ha_s1 + brokers ha_b0, ha_b1 ...")
        server_procs: dict = {}
        for sid in ("ha_s0", "ha_s1"):
            server_procs[sid], _ = start_server(sid, controllers)
        b0_proc, b0_url = start_broker("ha_b0", controllers)
        b1_proc, b1_url = start_broker("ha_b1", controllers)
        both = [b0_url, b1_url]

        rc = RemoteControllerClient(controllers)
        schema = Schema.build(
            table,
            dimensions=[("region", DataType.STRING), ("year", DataType.INT)],
            metrics=[("revenue", DataType.LONG)],
        )
        rc.add_schema(schema)
        rc.add_table(TableConfig(table, replication=2))
        rng = np.random.default_rng(seed)
        builder = SegmentBuilder(schema)
        seg_rows = n_rows // n_segments
        for i in range(n_segments):
            data = {
                "region": np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE"], dtype=object)[
                    rng.integers(0, 4, seg_rows)
                ],
                "year": rng.integers(1992, 1999, seg_rows).astype(np.int32),
                "revenue": rng.integers(100, 600_000, seg_rows).astype(np.int64),
            }
            seg_dir = write_segment(builder.build(data, f"{table}_{i}"), os.path.join(root, "built"))
            rc.upload_segment_dir(table, seg_dir)
        total_rows = seg_rows * n_segments
        queries = [
            f"SELECT COUNT(*) FROM {table} WHERE year > 1994",
            f"SELECT region, SUM(revenue) FROM {table} GROUP BY region ORDER BY region",
        ]
        for _ in range(6):  # JIT warmup per server process
            for url in both:
                for q in queries:
                    try:
                        query_broker_http(url, q)
                    except Exception as e:
                        log(f"HA warmup: {type(e).__name__}: {e}")

        # -- leg 1: split-brain (frozen lease renewal -> fenced writes) ---------
        log("HA leg 1: freeze ha_c1 lease renewal (lease.renew fault), standby takeover")
        bg1: dict = {}
        t1 = threading.Thread(
            target=lambda: bg1.update(_cluster_drive(both, queries, max(4, n_clients // 2), phase_s + 2.0)),
            daemon=True,
        )
        t1.start()
        _post_json(
            f"{c1_url}/debug/faults",
            {"points": {"lease.renew": {"mode": "error", "prob": 1.0}}, "seed": seed},
        )
        takeover = wait_leader(c2_url)
        # the frozen ex-leader STILL believes it leads: its mutation must be
        # rejected by the store's fencing check, not by the standby gate
        ghost = Schema.build("ghost", dimensions=[("g", DataType.STRING)], metrics=[])
        fenced_code, fenced_body = None, {}
        try:
            req = urllib.request.Request(
                f"{c1_url}/schemas",
                data=ghost.to_json().encode(),
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=10)
        except urllib.error.HTTPError as e:
            fenced_code = e.code
            fenced_body = json.loads(e.read())
        assert fenced_code == 503, f"stale-leader write was not rejected (HTTP {fenced_code})"
        assert fenced_body.get("errorCode") == 270, f"rejection not typed: {fenced_body}"
        ex_leader = _get_json(f"{c1_url}/leader")
        _post_json(f"{c1_url}/debug/faults", {"points": {}})  # thaw renewal
        demoted = wait_leader(c1_url, want=False)
        t1.join()
        out["split_brain"] = {
            "frozen_leader": "ha_c1",
            "takeover": takeover,
            "fenced_response": fenced_body,
            "fencedWrites": ex_leader.get("fencedWrites"),
            "ex_leader_after_thaw": demoted,
            "driven": bg1,
        }
        assert ex_leader.get("fencedWrites", 0) >= 1, f"no fenced write recorded: {ex_leader}"
        assert bg1["outcomes"]["untyped"] == 0, f"split-brain produced untyped errors: {bg1}"
        assert bg1["outcomes"]["dropped"] == 0, f"split-brain dropped queries: {bg1}"
        log(
            f"HA leg 1: epoch {lead_status['leaseEpoch']} -> {takeover['leaseEpoch']}, "
            f"fencedWrites={ex_leader.get('fencedWrites')}"
        )

        # -- leg 2: SIGKILL the lead controller MID-REBALANCE under load --------
        # leadership sits on ha_c2 after leg 1; give the rebalance real moves
        # by adding a third server, then kill ha_c2 while segments migrate
        log("HA leg 2: +ha_s2, SIGKILL lead ha_c2 mid-rebalance under live load")
        server_procs["ha_s2"], _ = start_server("ha_s2", controllers)
        bg2: dict = {}
        t2 = threading.Thread(
            target=lambda: bg2.update(_cluster_drive(both, queries, n_clients, phase_s + 4.0)),
            daemon=True,
        )
        t2.start()
        time.sleep(0.5)
        reb_err: list = []

        def fire_rebalance():
            try:
                RemoteControllerClient(c2_url).rebalance_table(
                    table, drain_grace_sec=0.8, bootstrap=True
                )
                reb_err.append("completed before kill")
            except Exception as e:  # the leader dies mid-call: expected
                reb_err.append(f"{type(e).__name__}: {e}")

        t_reb = threading.Thread(target=fire_rebalance, daemon=True)
        t_reb.start()
        time.sleep(1.0)  # inside the move window (>= 2 moves x 0.8s drain)
        os.kill(c2_proc.pid, signal.SIGKILL)
        t_reb.join(timeout=30)
        survivor = wait_leader(c1_url)
        t2.join()
        recovery_s = wait_count(b0_url, total_rows, timeout_s=60.0)
        out["controller_kill"] = {
            "victim": "ha_c2 (SIGKILL mid-rebalance)",
            "rebalance_call": reb_err[0] if reb_err else "no outcome recorded",
            "survivor": survivor,
            "recovery_to_full_count_s": recovery_s,
            "driven": bg2,
        }
        assert survivor["isLeader"] and survivor["takeovers"] >= 1, survivor
        assert survivor["leaseEpoch"] > takeover["leaseEpoch"], (
            f"takeover did not advance the fencing epoch: {survivor} vs {takeover}"
        )
        assert bg2["outcomes"]["untyped"] == 0, f"controller kill produced untyped errors: {bg2}"
        assert bg2["outcomes"]["dropped"] == 0, f"controller kill dropped queries: {bg2}"
        log(f"HA leg 2: survivor epoch {survivor['leaseEpoch']}, recovered in {recovery_s}s")

        # -- leg 3: SIGKILL one of two brokers under live CLIENT load -----------
        log("HA leg 3: SIGKILL ha_b1 under live client load (Connection failover)")
        bg3: dict = {}
        t3 = threading.Thread(
            target=lambda: bg3.update(_cluster_drive_conn(both, queries, n_clients, phase_s + 2.0)),
            daemon=True,
        )
        t3.start()
        time.sleep(max(0.5, phase_s / 3))
        os.kill(b1_proc.pid, signal.SIGKILL)
        t3.join()
        out["broker_kill"] = {"victim": "ha_b1 (SIGKILL)", "driven": bg3}
        assert bg3["outcomes"]["ok"] > 0, f"no queries served around the broker kill: {bg3}"
        assert bg3["outcomes"]["untyped"] == 0, f"broker kill produced untyped errors: {bg3}"
        assert bg3["outcomes"]["dropped"] == 0, f"broker kill dropped queries: {bg3}"

        # -- leg 4: full-cluster cold restart from store dir + deep store -------
        log("HA leg 4: SIGKILL every process; cold restart from property store + deep store")
        want = query_broker_http(b0_url, queries[1])["resultTable"]["rows"]
        count_before = query_broker_http(b0_url, f"SELECT COUNT(*) FROM {table}")[
            "resultTable"
        ]["rows"][0][0]
        for p in procs:
            if p.poll() is None:
                os.kill(p.pid, signal.SIGKILL)
        for p in procs:
            p.wait(timeout=10)
        procs.clear()
        # only the store dir, deep store and server data dirs survive the
        # "power loss"; every process restarts with fresh ports
        _, c1_url = start_controller("ha_c1", cold=True)
        _, c2_url = start_controller("ha_c2")
        controllers = f"{c1_url},{c2_url}"
        new_lead = wait_leader(c1_url, timeout_s=30.0)
        for sid in ("ha_s0", "ha_s1", "ha_s2"):
            start_server(sid, controllers)
        _, b0_url = start_broker("ha_b0", controllers)
        _, b1_url = start_broker("ha_b1", controllers)
        recovery_s = wait_count(b0_url, count_before, timeout_s=120.0)
        got = query_broker_http(b0_url, queries[1])["resultTable"]["rows"]
        out["cold_restart"] = {
            "lead_after_restart": new_lead,
            "recovery_to_full_count_s": recovery_s,
            "rows_identical": got == want,
            "count": count_before,
        }
        assert got == want, f"cold restart diverged: {got} != {want}"
        assert new_lead["leaseEpoch"] > survivor["leaseEpoch"], (
            "fencing epoch did not survive the restart (it must be monotonic "
            f"across cluster generations): {new_lead} vs {survivor}"
        )
        log(f"HA leg 4: identical results after cold restart, recovered in {recovery_s}s")
    finally:
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(root, ignore_errors=True)
    return out


def cluster_main():
    """`bench.py cluster`: the cluster-survivability acceptance run (ISSUE
    12). A real multi-process topology on one box — 1 controller (+metrics
    aggregator), 2 brokers (one with hedged scatter), 4->8 servers,
    replication 2, all over the pooled wire plane — driven by sustained
    closed-loop HTTP load while chaos runs:

      phase 1  qps @ 4 servers
      phase 2  scale-out: +4 servers, rebalance_table UNDER LIVE LOAD
               (zero-dropped-query assertion: routing never observes an
               assignment with no ONLINE replica)
      phase 3  qps @ 8 servers
      phase 4  hedged-vs-unhedged A/B against a SIGSTOP straggler
               (hedging must cut p99 within a <=5% extra-fan-out budget)
      phase 5  SIGKILL a server mid-flight (failover: zero non-typed errors)
      phase 6  live-ingest freshness through the realtime FSM (in-process,
               deterministic) -> freshness_p99_ms + SLO evaluation
      phase 7  disk corruption under live load: bit-flip one replica's local
               segment copy + one deep-store copy; the 1s integrity scrubber
               must quarantine + repair both while queries keep answering
               (0 untyped, 0 dropped)
      phase 8  control-plane survivability (ISSUE 18) on a second topology
               with 2 HA controllers: split-brain fencing, lead-controller
               SIGKILL mid-rebalance, broker SIGKILL with client failover,
               and a full-cluster cold restart — see _cluster_ha_phases

    Writes BENCH_cluster_r18.json and prints the same JSON line."""
    import shutil
    import signal
    import tempfile
    import threading

    import pinot_tpu  # noqa: F401  (x64 + platform setup)
    from pinot_tpu.cluster.http import RemoteControllerClient, query_broker_http
    from pinot_tpu.common import DataType, Schema, TableConfig
    from pinot_tpu.segment import SegmentBuilder, write_segment

    n_clients = int(os.environ.get("PINOT_TPU_CLUSTER_CLIENTS", 12))
    phase_s = float(os.environ.get("PINOT_TPU_CLUSTER_PHASE_SECS", 5.0))
    n_rows = int(os.environ.get("PINOT_TPU_CLUSTER_ROWS", 96_000))
    seed = int(os.environ.get("PINOT_TPU_CLUSTER_SEED", 12))
    # 5 segments x replication 2 = 10 replicas: the odd segment count keeps
    # the brokers' round-robin replica selector alternating across queries
    # (an even count advances the cursor by a multiple of the replica count,
    # pinning every segment to one replica forever), and after the bootstrap
    # rebalance over 8 servers most servers host a single replica — scatter
    # groups of one segment, so a whole-group hedge target always exists
    n_segments = 5

    root = tempfile.mkdtemp(prefix="pinot_tpu_cluster_")
    procs: list = []
    servers: dict[str, object] = {}
    result = {"metric": "cluster_survivability", "seed": seed}
    try:
        # -- topology ----------------------------------------------------------
        log("spawning controller (with metrics aggregator) ...")
        _, controller_url = _spawn_role(
            [
                "StartController",
                "--store-dir", os.path.join(root, "store"),
                "--deep-store", os.path.join(root, "deep"),
                "--port", "0",
                "--with-periodics",
                "--metrics-interval", "2",
                "--scrub-interval", "1",
            ],
            procs,
        )
        rc = RemoteControllerClient(controller_url)

        server_urls: dict[str, str] = {}

        def start_server(sid: str):
            p, url = _spawn_role(
                [
                    "StartServer", "--controller-url", controller_url,
                    "--server-id", sid, "--port", "0",
                    # local verified copies: the corruption phase flips bits
                    # here and the self-healing plane must repair them
                    "--data-dir", os.path.join(root, "data", sid),
                ],
                procs,
            )
            servers[sid] = p
            server_urls[sid] = url
            return url

        log("spawning servers 0-3 ...")
        for i in range(4):
            start_server(f"server_{i}")
        resilience = {"defaultTimeoutMs": 1500.0}
        log("spawning brokers (broker_0 plain, broker_1 hedged) ...")
        _, broker0_url = _spawn_role(
            [
                "StartBroker", "--controller-url", controller_url,
                "--broker-id", "broker_0", "--port", "0",
                "--scatter-threads", "32",
                "--resilience-json", json.dumps(resilience),
            ],
            procs,
        )
        _, broker1_url = _spawn_role(
            [
                "StartBroker", "--controller-url", controller_url,
                "--broker-id", "broker_1", "--port", "0",
                "--scatter-threads", "32",
                "--resilience-json", json.dumps(
                    {**resilience, "hedgeEnabled": True, "hedgeDelayMaxMs": 150.0}
                ),
            ],
            procs,
        )
        both = [broker0_url, broker1_url]

        # -- table: 8 segments x replication 2 over the first 4 servers --------
        schema = Schema.build(
            "lineorder",
            dimensions=[("region", DataType.STRING), ("year", DataType.INT)],
            metrics=[("revenue", DataType.LONG)],
        )
        rc.add_schema(schema)
        rc.add_table(TableConfig("lineorder", replication=2))
        rng = np.random.default_rng(seed)
        builder = SegmentBuilder(schema)
        seg_rows = n_rows // n_segments
        for i in range(n_segments):
            data = {
                "region": np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE"], dtype=object)[
                    rng.integers(0, 4, seg_rows)
                ],
                "year": rng.integers(1992, 1999, seg_rows).astype(np.int32),
                "revenue": rng.integers(100, 600_000, seg_rows).astype(np.int64),
            }
            seg_dir = write_segment(builder.build(data, f"lineorder_{i}"), os.path.join(root, "built"))
            rc.upload_segment_dir("lineorder", seg_dir)
        queries = [
            "SELECT COUNT(*) FROM lineorder WHERE year > 1994",
            "SELECT region, SUM(revenue) FROM lineorder GROUP BY region ORDER BY SUM(revenue) DESC LIMIT 4",
        ]

        def warmup(rounds: int = 10):
            # every server process JIT-compiles each query shape on first
            # contact; drive enough rounds that routing has touched them all
            for j in range(rounds):
                for url in both:
                    for q in queries:
                        try:
                            query_broker_http(url, q)
                        except Exception as e:
                            log(f"warmup round {j}: {type(e).__name__}: {e}")

        log("warmup (JIT per server process) ...")
        warmup()

        # -- phase 1: qps @ 4 servers ------------------------------------------
        log(f"phase 1: qps @ 4 servers ({n_clients} clients, {phase_s}s)")
        result["qps_4_servers"] = _cluster_drive(both, queries, n_clients, phase_s)

        # -- phase 2: scale-out + rebalance under live load --------------------
        log("phase 2: +4 servers, rebalance under live load")
        for i in range(4, 8):
            start_server(f"server_{i}")
        bg: dict = {}
        t_bg = threading.Thread(
            target=lambda: bg.update(_cluster_drive(both, queries, max(4, n_clients // 2), phase_s + 2.0)),
            daemon=True,
        )
        t_bg.start()
        time.sleep(0.5)  # load is flowing before the first segment moves
        reb = rc.rebalance_table("lineorder", drain_grace_sec=0.15, bootstrap=True)
        log(f"rebalance: {reb.get('status')} adds={reb.get('adds')} drops={reb.get('drops')}")
        t_bg.join()
        result["rebalance_under_load"] = {
            "rebalance": {"status": reb.get("status"), "adds": len(reb.get("adds") or []),
                          "drops": len(reb.get("drops") or [])},
            "driven": bg,
        }
        assert bg["outcomes"]["dropped"] == 0, (
            f"rebalance dropped queries (no ONLINE replica observed): {bg}"
        )

        log("post-rebalance warmup (new server processes JIT) ...")
        warmup()

        # -- phase 3: qps @ 8 servers ------------------------------------------
        log(f"phase 3: qps @ 8 servers ({n_clients} clients, {phase_s}s)")
        result["qps_8_servers"] = _cluster_drive(both, queries, n_clients, phase_s)

        # -- phase 4: hedged vs unhedged A/B against a delay straggler ---------
        # pick the straggler from the actual post-rebalance placement: a
        # single-segment host, so the slow scatter group always has a
        # one-server hedge target on the partner replica. The straggler is
        # slow-but-alive (seeded delay fault on server.scatter, armed over
        # /debug/faults) — the tail-at-scale shape hedging is built for; a
        # hard freeze is the failure detector's job and is phase 5's SIGKILL.
        import urllib.request

        def _post_json(url, doc):
            req = urllib.request.Request(
                url, data=json.dumps(doc).encode(), headers={"Content-Type": "application/json"}
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                return json.loads(r.read())

        ideal = rc.ideal_state("lineorder")
        hosts: dict[str, list] = {}
        for seg, reps in ideal.items():
            for sid in reps:
                hosts.setdefault(sid, []).append(seg)
        single_hosts = sorted(s for s, g in hosts.items() if len(g) == 1)
        assert single_hosts, f"placement has no single-segment hosts: {hosts}"
        straggler_id = single_hosts[0]
        victim_id = next(s for s in sorted(hosts) if s != straggler_id)
        # shape the chaos to what a budgeted hedge can rescue: replica
        # round-robin sends ~half the straggler group's queries to the
        # straggler, so P(query delayed) ~= prob/2 ~= 7% — a p99 tail, not a
        # p50 collapse. The 5% fan-out budget is cumulative over the
        # broker's primaries (phases 1+3 included), so it covers that tail;
        # a much higher hit rate exhausts the budget and the uncovered
        # remainder dominates p99 in BOTH windows (observed at prob=0.4).
        delay_rule = {"mode": "delay", "prob": 0.15, "delay_s": 0.5}
        log(
            f"phase 4: delay-fault straggler {straggler_id} (hosts {hosts[straggler_id]}, "
            f"{delay_rule}); unhedged window (broker_0) ..."
        )
        ab_clients = max(4, n_clients // 2)
        ab_s = phase_s + 1.0
        _post_json(
            f"{server_urls[straggler_id]}/debug/faults",
            {"points": {"server.scatter": delay_rule}, "seed": seed},
        )
        try:
            unhedged = _cluster_drive([broker0_url], queries, ab_clients, ab_s)
            log("phase 4: hedged window (broker_1) ...")
            hedged = _cluster_drive([broker1_url], queries, ab_clients, ab_s)
            with urllib.request.urlopen(
                f"{server_urls[straggler_id]}/debug/faults", timeout=5
            ) as r:
                fault_counts = json.loads(r.read())
        finally:
            _post_json(f"{server_urls[straggler_id]}/debug/faults", {"points": {}})
        with urllib.request.urlopen(f"{broker1_url}/debug/hedge", timeout=5) as r:
            hedge_snap = json.loads(r.read())
        overhead = (
            hedge_snap["hedgesIssued"] / hedge_snap["primaryScatters"]
            if hedge_snap["primaryScatters"]
            else 0.0
        )
        result["hedge_ab"] = {
            "straggler": f"{straggler_id} (server.scatter delay fault)",
            "delay_rule": delay_rule,
            "fault_fires": fault_counts,
            "unhedged": unhedged,
            "hedged": hedged,
            "hedge_snapshot": hedge_snap,
            "extra_fanout_fraction": round(overhead, 4),
        }
        log(
            f"hedge A/B raw: fault_fires={fault_counts} "
            f"unhedged(q={unhedged['queries']}, p50={unhedged['p50_ms']}, "
            f"p99={unhedged['p99_ms']}, outcomes={unhedged['outcomes']}) "
            f"hedged(q={hedged['queries']}, p50={hedged['p50_ms']}, "
            f"p99={hedged['p99_ms']}, outcomes={hedged['outcomes']}) "
            f"snap={hedge_snap}"
        )
        for name, window in (("unhedged", unhedged), ("hedged", hedged)):
            # a shed/error storm makes the p99 comparison vacuous (rejections
            # return in microseconds) — the A/B only means something when
            # both windows actually served their load
            assert window["outcomes"]["ok"] >= 0.5 * window["queries"], (
                f"{name} window did not serve its load: {window['outcomes']}"
            )
        assert hedged["p99_ms"] < unhedged["p99_ms"], (
            f"hedging did not cut straggler p99: hedged={hedged['p99_ms']} "
            f"unhedged={unhedged['p99_ms']}"
        )
        assert hedge_snap["hedgesIssued"] > 0, f"straggler never triggered a hedge: {hedge_snap}"
        assert overhead <= 0.055, f"hedge fan-out over budget: {overhead:.4f}"
        log(
            f"hedge A/B: p99 {unhedged['p99_ms']}ms -> {hedged['p99_ms']}ms, "
            f"extra fan-out {overhead * 100:.2f}%"
        )

        # -- phase 5: SIGKILL a server mid-flight ------------------------------
        victim = servers[victim_id]
        log(f"phase 5: sustained load + SIGKILL {victim_id} (hosts {hosts[victim_id]}) mid-flight")
        kill_bg: dict = {}
        t_kill = threading.Thread(
            target=lambda: kill_bg.update(_cluster_drive(both, queries, n_clients, phase_s + 1.0)),
            daemon=True,
        )
        t_kill.start()
        time.sleep(max(0.5, phase_s / 3))
        os.kill(victim.pid, signal.SIGKILL)
        t_kill.join()
        result["server_kill"] = {"victim": f"{victim_id} (SIGKILL)", "driven": kill_bg}
        assert kill_bg["outcomes"]["untyped"] == 0, (
            f"server kill produced non-typed client errors: {kill_bg}"
        )
        assert kill_bg["outcomes"]["dropped"] == 0, f"server kill dropped queries: {kill_bg}"

        # -- phase 7: disk corruption under live load (self-healing proof) -----
        # flip one bit in a replica's local segment copy AND in a different
        # segment's deep-store copy while queries keep flowing. Queries must
        # keep answering (replication 2 + in-memory copies: 0 untyped, 0
        # dropped), and the 1s IntegrityScrubber must detect -> quarantine ->
        # repair both copies inside the phase window.
        def _get_json(url):
            with urllib.request.urlopen(url, timeout=10) as r:
                return json.loads(r.read())

        live_sids = sorted(s for s in hosts if s != victim_id)
        corrupt_sid = live_sids[0]
        corrupt_seg = hosts[corrupt_sid][0]
        local_file = os.path.join(
            root, "data", corrupt_sid, "lineorder", corrupt_seg, "segment.ptseg"
        )
        deep_seg = next(s for s in sorted(ideal) if s != corrupt_seg)
        deep_file = os.path.join(root, "deep", "lineorder", deep_seg, "segment.ptseg")

        def _flip_bit(path):
            with open(path, "r+b") as f:
                f.seek(os.path.getsize(path) // 2)
                b = f.read(1)
                f.seek(-1, 1)
                f.write(bytes([b[0] ^ 0x20]))

        log(
            f"phase 7: corruption under load — bit-flip {corrupt_sid} local copy of "
            f"{corrupt_seg} + deep-store copy of {deep_seg}"
        )
        corrupt_bg: dict = {}
        t_corrupt = threading.Thread(
            target=lambda: corrupt_bg.update(
                _cluster_drive(both, queries, n_clients, phase_s + 2.0)
            ),
            daemon=True,
        )
        t_corrupt.start()
        time.sleep(0.3)
        _flip_bit(local_file)
        _flip_bit(deep_file)
        heal_deadline = time.time() + max(30.0, phase_s * 4)
        heal = {"serverRepaired": 0, "deepRepaired": 0, "quarantined": []}
        while time.time() < heal_deadline:
            storage = _get_json(f"{server_urls[corrupt_sid]}/debug/storage")
            smetrics = _get_json(f"{server_urls[corrupt_sid]}/metrics?format=json")
            cmetrics = _get_json(f"{controller_url}/metrics?format=json")
            heal = {
                "serverRepaired": smetrics.get("storage.scrub.repaired", {}).get("count", 0),
                "deepRepaired": cmetrics.get("storage.scrub.repaired", {}).get("count", 0),
                "deepVerified": cmetrics.get("storage.scrub.verified", {}).get("count", 0),
                "unrepairable": cmetrics.get("storage.scrub.unrepairable", {}).get("count", 0),
                "quarantined": storage["quarantined"],
            }
            if heal["serverRepaired"] >= 1 and heal["deepRepaired"] >= 1:
                break
            time.sleep(1.0)
        t_corrupt.join()
        result["corruption_heal"] = {
            "local": f"{corrupt_sid}:{corrupt_seg}",
            "deep_store": deep_seg,
            "heal": heal,
            "driven": corrupt_bg,
        }
        log(f"phase 7: heal state {heal}, driven {corrupt_bg['outcomes']}")
        assert corrupt_bg["outcomes"]["untyped"] == 0, (
            f"corruption produced non-typed client errors: {corrupt_bg}"
        )
        assert corrupt_bg["outcomes"]["dropped"] == 0, f"corruption dropped queries: {corrupt_bg}"
        assert heal["serverRepaired"] >= 1, f"server scrub never repaired the local copy: {heal}"
        assert heal["deepRepaired"] >= 1, f"controller scrub never repaired the deep store: {heal}"
        assert heal["unrepairable"] == 0, f"scrubber declared corruption unrepairable: {heal}"
        assert heal["quarantined"], "no quarantined file left on disk for the runbook"

        # -- /debug/cluster from the controller hub ----------------------------
        with urllib.request.urlopen(f"{controller_url}/debug/cluster", timeout=10) as r:
            doc = json.loads(r.read())
        result["debug_cluster"] = {
            "nodes": {
                nid: {"role": n["role"], "healthy": n["healthy"], "stale": n["stale"]}
                for nid, n in doc.get("nodes", {}).items()
            },
            "rebalance": doc.get("rebalance"),
            "hedge": doc.get("cluster", {}).get("hedge"),
        }
    finally:
        for p in procs:
            try:
                os.kill(p.pid, signal.SIGCONT)  # a still-stopped child ignores SIGTERM
            except OSError:
                pass
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)

    # -- phase 6: live-ingest freshness (in-process, deterministic) ------------
    log("phase 6: live-ingest freshness through the realtime FSM")
    result["freshness"] = _cluster_freshness_phase(seed)
    assert result["freshness"]["caught_up"], f"ingest never caught up: {result['freshness']}"
    assert result["freshness"]["samples"] > 0, "no freshness samples recorded"

    # -- phase 8: control-plane survivability (2 HA controllers) ---------------
    log("phase 8: control-plane survivability (split-brain / kills / cold restart)")
    result["control_plane"] = _cluster_ha_phases(seed, n_clients, phase_s)

    result["qps_vs_server_count"] = {
        "4": result["qps_4_servers"]["throughput_qps"],
        "8": result["qps_8_servers"]["throughput_qps"],
    }
    with open("BENCH_cluster_r18.json", "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def main():
    from pinot_tpu.common import runtime

    # the server's rule: TPU unless JAX_PLATFORMS says otherwise, else fail
    rt = runtime.require_device()
    backend = rt["platform"]
    result = {
        "metric": HEADLINE,
        "value": None,
        "unit": "ms",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": rt["deviceKind"],
        "n_devices": rt["deviceCount"],
        "configs": {},
    }

    import pandas as pd

    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.devlink import link_profile
    from pinot_tpu.parallel import build_sharded_table, make_mesh
    from pinot_tpu.parallel.mesh import execute_sharded_result

    # BASELINE.json's north star is 1B-row SSB; 16M is the largest default
    # that builds host-side in reasonable time
    n = int(os.environ.get("PINOT_TPU_BENCH_ROWS", 16_000_000))
    iters = int(os.environ.get("PINOT_TPU_BENCH_ITERS", 7))
    rng = np.random.default_rng(0)
    log(f"backend={backend} devices={rt['deviceCount']} rows={n}")

    schema = Schema.build(
        "lineorder",
        dimensions=[
            ("d_year", DataType.INT),
            ("c_nation", DataType.STRING),
            ("p_category", DataType.STRING),
        ],
        metrics=[
            ("lo_revenue", DataType.LONG),
            ("lo_supplycost", DataType.LONG),
            ("lo_quantity", DataType.INT),
        ],
    )
    data = _make_ssb_data(rng, n)
    t = pd.DataFrame({k: (v.astype(str) if v.dtype == object else v) for k, v in data.items()})

    rtt, bw = link_profile()
    result["link"] = {"rtt_ms": round(rtt * 1e3, 2), "mb_per_s": round(bw / 1e6, 1)}
    log(f"device link: rtt={result['link']['rtt_ms']}ms bw={result['link']['mb_per_s']}MB/s")

    mesh = make_mesh()
    _smoke_test(schema, mesh, np.random.default_rng(1))
    t0 = time.perf_counter()
    table = build_sharded_table(
        schema, data, mesh, rows_per_segment=max(1, n // max(4, rt["deviceCount"]))
    )
    log(f"table built+staged in {time.perf_counter() - t0:.1f}s ({table.n_segments} segments)")

    # ---- config 4 (HEADLINE): SSB Q4.2-flavored profit group-by -------------
    c4 = _bench_q4(table, t, iters, "config4 Q4.x group-by")
    result["configs"]["4_q4_groupby_orderby"] = c4
    result["value"] = c4["p50"]
    result["vs_baseline"] = c4["speedup"]

    state = {}
    # ---- config 1: quickstart COUNT(*) with equality filter -----------------
    q1 = "SELECT COUNT(*) FROM lineorder WHERE c_nation = 'NATION_07'"

    def dev1():
        state["res"] = execute_sharded_result(table, q1)

    def cpu1():
        state["cpu"] = int((t.c_nation == "NATION_07").sum())

    result["configs"]["1_count_filter"] = _bench_pair(
        "config1 COUNT filter", dev1, cpu1, iters,
        lambda: _assert_eq(state["res"].rows[0][0], state["cpu"]),
    )

    # ---- config 2: SUM/MIN/MAX/AVG with range+equality filter ---------------
    result["configs"]["2_filtered_agg"] = _bench_q2(table, t, iters, "config2 filtered agg")

    # ---- config 3: Q1.x-flavored AND/OR filter + single-column group-by -----
    q3 = (
        "SELECT d_year, SUM(lo_revenue) FROM lineorder "
        "WHERE (c_nation = 'NATION_01' OR c_nation = 'NATION_02') AND lo_quantity < 25 "
        "GROUP BY d_year ORDER BY d_year LIMIT 20"
    )

    def dev3():
        state["res"] = execute_sharded_result(table, q3)

    def cpu3():
        sel = t[((t.c_nation == "NATION_01") | (t.c_nation == "NATION_02")) & (t.lo_quantity < 25)]
        state["cpu"] = sel.groupby(sel.d_year).lo_revenue.sum().sort_index()

    result["configs"]["3_q1_groupby"] = _bench_pair(
        "config3 Q1.x group-by", dev3, cpu3, iters,
        lambda: _assert_eq(state["res"].rows[0][1], float(state["cpu"].iloc[0])),
    )

    # ---- config 5: star-tree pre-agg + DISTINCTCOUNTHLL ---------------------
    result["configs"]["5_startree_hll"] = _bench_config5(rng, min(n, 2_000_000), iters)

    # ---- config 6: multistage fact-dim equi-join + group-by (v2 engine) -----
    # Joins lineorder (fact) to a nation->region dim table and aggregates —
    # BlockExchange HASH semantics + hash join + final agg.
    result["configs"]["6_join_agg"] = _bench_join(max(3, iters // 2))

    # ---- scale block: sf10-class lineorder (>=60M rows) ---------------------
    # Separate table build, Q4 + filtered-agg at scale, rows/sec/chip +
    # device-resident bytes recorded alongside p50/p99.
    scale_rows = int(os.environ.get("PINOT_TPU_BENCH_SCALE_ROWS", 60_000_000))
    if scale_rows > 0:
        # free the main table first: device buffers + both host copies —
        # the scale build must not pay for the 16M set's residency
        del table, data, t
        result["scale"] = _bench_scale(schema, mesh, scale_rows, max(3, iters // 2))
    else:
        result["scale"] = {"skipped": "PINOT_TPU_BENCH_SCALE_ROWS=0"}

    print(json.dumps(result))


def _assert_eq(a, b):
    assert float(a) == float(b), f"result mismatch: {a} vs {b}"


def _bench_scale(schema, mesh, n: int, iters: int) -> dict:
    """sf10-class block: build a fresh >=60M-row lineorder, run the Q4
    headline + the filtered-agg shape at scale, record build time,
    p50/p99, pandas reference, rows/sec/chip, and staged device bytes."""
    import jax
    import pandas as pd

    from pinot_tpu.parallel import build_sharded_table

    rng = np.random.default_rng(7)
    log(f"[scale] generating {n} rows")
    data = _make_ssb_data(rng, n)
    t0 = time.perf_counter()
    table = build_sharded_table(
        schema, data, mesh, rows_per_segment=max(1, n // max(4, mesh.devices.size))
    )
    build_s = round(time.perf_counter() - t0, 1)
    dev_bytes = int(sum(v.nbytes for v in table.arrays.values()))
    log(f"[scale] built+staged in {build_s}s ({table.n_segments} segments, {dev_bytes >> 20} MiB on device)")
    # object columns already hold str values — astype(str) here would
    # materialize multi-GB fixed-width unicode copies at peak memory
    t = pd.DataFrame(data)

    out = {"rows": n, "build_s": build_s, "device_bytes": dev_bytes, "queries": {}}
    per_chip = lambda b: round(n / (b["p50"] / 1e3) / max(1, len(jax.devices())))  # noqa: E731
    b4 = _bench_q4(table, t, iters, "scale q4 groupby")
    b4["rows_per_sec_per_chip"] = per_chip(b4)
    out["queries"]["q4_groupby"] = b4
    b2 = _bench_q2(table, t, iters, "scale filtered agg")
    b2["rows_per_sec_per_chip"] = per_chip(b2)
    out["queries"]["filtered_agg"] = b2
    return out


def _bench_config5(rng, n, iters):
    """Star-tree pre-aggregated scan + DISTINCTCOUNTHLL on a high-cardinality
    column (BASELINE config 5), via the per-segment QueryEngine."""
    import pandas as pd

    from pinot_tpu.common import DataType, IndexingConfig, Schema, TableConfig
    from pinot_tpu.common.config import StarTreeIndexConfig
    from pinot_tpu.query import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    schema = Schema.build(
        "events",
        dimensions=[
            ("country", DataType.STRING),
            ("device", DataType.STRING),
            ("user_id", DataType.LONG),
        ],
        metrics=[("impressions", DataType.LONG)],
    )
    cfg = TableConfig(
        "events",
        indexing=IndexingConfig(
            star_tree_configs=[
                StarTreeIndexConfig(
                    dimensions_split_order=["country", "device"],
                    function_column_pairs=["SUM__impressions", "COUNT__*"],
                )
            ]
        ),
    )
    data = {
        "country": np.array([f"C{i:02d}" for i in range(30)], dtype=object)[rng.integers(0, 30, n)],
        "device": np.array(["phone", "desktop", "tablet"], dtype=object)[rng.integers(0, 3, n)],
        "user_id": rng.integers(0, 5_000_000, n).astype(np.int64),
        "impressions": rng.integers(1, 1000, n).astype(np.int64),
    }
    seg = SegmentBuilder(schema, cfg).build(data, "s0")
    eng = QueryEngine([seg])
    t = pd.DataFrame({k: (v.astype(str) if v.dtype == object else v) for k, v in data.items()})
    q_star = "SELECT country, SUM(impressions) FROM events GROUP BY country ORDER BY SUM(impressions) DESC LIMIT 5"
    q_hll = "SELECT DISTINCTCOUNTHLL(user_id) FROM events"
    state = {}

    def dev():
        # async submits overlap the two queries' device round trips
        # (QueryScheduler.submit parity) — one link sync instead of two
        r_star, r_hll = eng.submit(q_star), eng.submit(q_hll)
        state["star"] = r_star()
        state["hll"] = r_hll()

    def cpu():
        state["cpu_star"] = t.groupby("country").impressions.sum().nlargest(5)
        state["cpu_hll"] = int(t.user_id.nunique())

    def check():
        assert state["star"].rows[0][1] == float(state["cpu_star"].iloc[0])
        est, exact = float(state["hll"].rows[0][0]), state["cpu_hll"]
        assert abs(est - exact) / exact < 0.1, f"HLL estimate off: {est} vs {exact}"

    return _bench_pair("config5 star-tree+HLL", dev, cpu, iters, check)


def roofline_main():
    """--roofline: cross-check GET /debug/roofline against bench's own
    measured device_ms split (the drift gate CI runs).

    Bench first times the packed dispatch+sync loop with kernel_obs DISABLED
    — its own wall-minus-RTT split, the `_bench_pair` arithmetic — then
    re-runs the identical loop with kernel_obs enabled and fetches
    /debug/roofline from a live ServerHTTPService. The two per-process
    device-ms totals must agree within 10% (plus a small absolute floor so
    the CPU tier, where both sides sit at ~0 ms, stays deterministic)."""
    import urllib.request

    from pinot_tpu.cluster.http import ServerHTTPService
    from pinot_tpu.cluster.server import Server
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.common.kernel_obs import KERNELS
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.query.kernels import dispatch_plan_packed
    from pinot_tpu.query.plan import plan_segment
    from pinot_tpu.segment import SegmentBuilder

    n, iters = 200_000, 30
    rng = np.random.default_rng(7)
    schema = Schema.build(
        "t", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)]
    )
    seg = SegmentBuilder(schema).build(
        {
            "d": rng.integers(0, 50, n).astype(np.int32),
            "v": rng.integers(0, 1000, n).astype(np.int64),
        },
        "t_0",
    )
    eng = QueryEngine([seg])
    ctx = eng.make_context("SELECT d, SUM(v), COUNT(*) FROM t GROUP BY d")
    plan = plan_segment(seg, ctx)
    dseg = eng._device_seg(seg)

    def one():
        return dispatch_plan_packed(plan, dseg)()

    one()  # compile
    one()
    rtt_ms = _link_rtt_ms() or 0.0

    # 1) bench's own split: kernel_obs disabled, plain wall minus RTT
    KERNELS.configure(enabled=False)
    bench_dev_ms = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        one()
        bench_dev_ms += max((time.perf_counter() - t0) * 1e3 - rtt_ms, 0.0)

    # 2) the instrumented split: same loop, kernel_obs enabled
    KERNELS.configure(enabled=True)
    KERNELS.reset_stats()
    for _ in range(iters):
        one()

    svc = ServerHTTPService(Server("bench-roofline"), port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{svc.port}/debug/roofline", timeout=10
        ) as resp:
            doc = json.loads(resp.read())
    finally:
        svc.stop()
    endpoint_dev_ms = sum(k["deviceMs"] for k in doc["kernels"])
    calls = sum(k["calls"] for k in doc["kernels"])

    # 10% relative, with an absolute floor covering timer noise at ~0 ms
    tol_ms = max(0.10 * bench_dev_ms, 1.0 + 0.05 * iters)
    drift_ms = abs(endpoint_dev_ms - bench_dev_ms)
    ok = calls >= iters and drift_ms <= tol_ms
    log(
        f"[roofline] bench={bench_dev_ms:.3f}ms endpoint={endpoint_dev_ms:.3f}ms "
        f"drift={drift_ms:.3f}ms tol={tol_ms:.3f}ms calls={calls} ok={ok}"
    )
    print(
        json.dumps(
            {
                "metric": "roofline_drift",
                "bench_device_ms": round(bench_dev_ms, 3),
                "endpoint_device_ms": round(endpoint_dev_ms, 3),
                "drift_ms": round(drift_ms, 3),
                "tolerance_ms": round(tol_ms, 3),
                "link_rtt_ms": round(rtt_ms, 3),
                "calls": calls,
                "hbm": doc.get("hbm"),
                "kernels": doc["kernels"],
                "ok": ok,
            }
        )
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    if "--roofline" in sys.argv[1:]:
        roofline_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "qps":
        if "--overload" in sys.argv[2:]:
            qps_overload_main()
        elif "--cache-ab" in sys.argv[2:]:
            qps_cache_ab_main()
        elif "--frontend" in sys.argv[2:]:
            qps_frontend_main()
        else:
            qps_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "cluster":
        cluster_main()
    else:
        main()
