"""Kernel & memory observability plane (common/kernel_obs.py).

Deterministic throughout: a timing is the fenced wall as it is (nothing is
probed or subtracted), HBM assertions run against the host estimator (CPU
tier-1 has no `memory_stats()`), and the aggregator test drives the
federated scrape with an injected fetch — no sockets except the one
loopback `/debug/roofline` round-trip, which binds port 0.
"""

import json
import time
import urllib.request
from functools import lru_cache

import numpy as np
import pytest

from pinot_tpu.common import DataType, ObservabilityConfig, Schema
from pinot_tpu.common.accounting import default_accountant
from pinot_tpu.common.kernel_obs import (
    CacheObserver,
    HostHbmEstimator,
    KernelRegistry,
    KERNELS,
    shape_bucket,
)
from pinot_tpu.common.metrics import reset_registries, server_metrics
from pinot_tpu.common.trace import start_trace
from pinot_tpu.common import kernel_obs


@pytest.fixture
def cpu_roof(monkeypatch):
    """A 10 GB/s roof for the CPU test device (it has no published peak)."""
    monkeypatch.setitem(kernel_obs.DEVICE_PEAKS, "cpu", {"hbmGBps": 10.0, "source": "test"})


def _registry():
    r = KernelRegistry()
    r.register(
        "unit.k",
        cost_model=lambda s: (s.get("rows", 0) * 8.0, s.get("rows", 0) * 2.0),
    )
    return r


# -- shape buckets -----------------------------------------------------------


def test_shape_bucket_pow2_ranges():
    assert shape_bucket(1) == "2^0"
    assert shape_bucket(1024) == "2^10"
    assert shape_bucket(1025) == "2^10"  # [2^10, 2^11)
    assert shape_bucket(2047) == "2^10"
    assert shape_bucket(2048) == "2^11"
    assert shape_bucket(0) == "0"
    assert shape_bucket(-5) == "0"
    assert shape_bucket("not a number") == "0"
    # cardinality stays bounded no matter the workload: 1..10^6 -> ~20 labels
    assert len({shape_bucket(n) for n in range(1, 1_000_000, 997)}) <= 21


# -- registration ------------------------------------------------------------


def test_register_and_double_register():
    r = _registry()
    assert r.is_registered("unit.k")
    assert r.kernel_names() == ["unit.k"]
    with pytest.raises(ValueError, match="already registered"):
        r.register("unit.k")


def test_record_unregistered_is_silent_noop():
    r = _registry()
    r.record("never.registered", 5.0, rows=10)
    assert r.stats_snapshot() == {}


# -- timing ------------------------------------------------------------------


def test_timed_sync_records_stats():
    r = _registry()
    out = r.timed_sync("unit.k", lambda: (time.sleep(0.005), 42)[1], rows=1024)
    assert out == 42
    snap = r.stats_snapshot()
    s = snap[("unit.k", "2^10")]
    assert s["calls"] == 1
    assert s["deviceMs"] >= 4.0  # slept 5 ms: the fenced wall, nothing subtracted
    assert s["bytesMoved"] == 1024 * 8.0
    assert s["flops"] == 1024 * 2.0
    assert r.total_device_ms() == pytest.approx(s["deviceMs"])


def test_timed_sync_disabled_is_pass_through():
    r = _registry()
    r.configure(enabled=False)
    assert not r.enabled
    assert r.timed_sync("unit.k", lambda: 7, rows=8) == 7
    assert r.stats_snapshot() == {}


def test_timed_sync_passes_through_under_outer_jit():
    # inside an outer jax trace the result is a Tracer: nothing concrete to
    # fence, so timed_sync must return it untouched and record nothing
    jax = pytest.importorskip("jax")
    r = _registry()
    f = jax.jit(lambda x: r.timed_sync("unit.k", lambda: x + 1, rows=4))
    assert float(f(1.0)) == 2.0
    assert r.stats_snapshot() == {}


# -- HBM accounting ----------------------------------------------------------


def test_hbm_estimator_math():
    h = HostHbmEstimator()
    h.alloc(100)
    h.alloc(50)
    assert (h.live, h.peak) == (150, 150)
    h.free(50)
    assert (h.live, h.peak) == (100, 150)
    # transient moves peak, not live, and returns the modeled footprint
    assert h.transient(200) == 300
    assert (h.live, h.peak) == (100, 300)
    h.free(10_000)  # over-free clamps at zero
    assert h.live == 0
    h.reset()
    assert (h.live, h.peak) == (0, 0)


def test_hbm_snapshot_is_deterministic_on_cpu():
    r = _registry()
    r.record("unit.k", 1.0, rows=100)
    snap = r.hbm_snapshot()
    assert snap["source"] in ("estimator", "device")
    if snap["source"] == "estimator":  # the CPU tier-1 path
        assert snap["peakBytes"] == 800  # 100 rows * 8 B, transient footprint
        assert snap["liveBytes"] == 0


# -- roofline math -----------------------------------------------------------


def test_roofline_math(cpu_roof):
    r = KernelRegistry()
    # 1e9 bytes in 1s -> 1 GB/s achieved against a 10 GB/s roof
    r.register("m.k", cost_model=lambda s: (1e9, 2e9))
    r.record("m.k", 1000.0, rows=16)
    doc = r.roofline()
    assert doc["hbmPeakGBps"] == 10.0 and doc["hbmPeakSource"] == "test"
    assert doc["platform"] == "cpu" and doc["deviceKind"] == "cpu"
    (row,) = doc["kernels"]
    assert row["kernel"] == "m.k" and row["shape"] == "2^4"
    assert row["achievedGBps"] == pytest.approx(1.0)
    assert row["arithmeticIntensity"] == pytest.approx(2.0)
    assert row["pctOfPeak"] == pytest.approx(10.0)
    assert row["rooflineGap"] == pytest.approx(10.0)
    assert row["lostMs"] == pytest.approx(900.0)  # 90% of 1000ms below the roof
    assert doc["offenders"] == [row]
    assert doc["registered"] == ["m.k"]


def test_roofline_without_a_known_peak_gives_no_percentages():
    """A device that is not in DEVICE_PEAKS (the CPU test device) gets its
    achieved numbers and no roof: nothing assumes another device's peak."""
    r = KernelRegistry()
    r.register("m.k", cost_model=lambda s: (1e9, 2e9))
    r.record("m.k", 1000.0, rows=16)
    doc = r.roofline()
    assert doc["hbmPeakGBps"] is None and doc["hbmPeakSource"] is None
    (row,) = doc["kernels"]
    assert row["achievedGBps"] == pytest.approx(1.0)
    assert row["pctOfPeak"] is None and row["rooflineGap"] is None and row["lostMs"] is None
    assert doc["offenders"] == []


def test_kernel_traced_into_outer_jit_is_counted_inlined():
    import jax
    import jax.numpy as jnp

    r = _registry()
    outer = jax.jit(lambda x: r.timed_sync("unit.k", lambda: x * 2, rows=8))
    outer(jnp.arange(8))
    doc = r.roofline()
    assert doc["inlined"] == {"unit.k": 1} and doc["kernels"] == []


def test_roofline_offenders_ranked_by_lost_ms_not_gap(cpu_roof):
    r = KernelRegistry()
    # `tiny` has the worse gap (1000x) but is microscopic; `big` burns real
    # time below the roof and must rank first
    r.register("tiny", cost_model=lambda s: (1e4, 0.0))
    r.register("big", cost_model=lambda s: (1e9, 0.0))
    r.record("tiny", 1.0, rows=1)
    r.record("big", 2000.0, rows=1)
    offenders = r.roofline()["offenders"]
    assert [o["kernel"] for o in offenders] == ["big", "tiny"]
    assert offenders[0]["lostMs"] > offenders[1]["lostMs"]
    # zero-duration rows have no achieved bandwidth: excluded from offenders
    r.record("tiny", 0.0, rows=4096)
    assert all(o["rooflineGap"] is not None for o in r.roofline()["offenders"])


# -- metrics + accountant + trace wiring -------------------------------------


def test_record_emits_labelled_metric_families():
    reset_registries()
    r = _registry()
    r.record("unit.k", 3.0, rows=1024)
    r.record("unit.k", 2.0, rows=1024)
    reg = server_metrics()
    assert reg.timer("engine.kernel.deviceMs", kernel="unit.k", shape="2^10").count == 2
    assert reg.meter("engine.kernel.invocations", kernel="unit.k", shape="2^10").count == 2
    assert reg.meter("engine.kernel.bytesMoved", kernel="unit.k", shape="2^10").count == 2 * 1024 * 8
    # the HBM gauges are set where they are read (/metrics, /debug/roofline), not per record
    r.publish_hbm_gauges()
    assert reg.gauge("engine.hbm.peakBytes").value == 1024 * 8


def test_device_ms_attributed_to_query_scope():
    default_accountant.reset_rollups()
    r = _registry()
    with default_accountant.scope("kq-1", table="t", tenant="gold"):
        r.record("unit.k", 5.0, rows=100)
        r.record("unit.k", 2.5, rows=100)
    st = default_accountant.recent_query_stats("kq-1")
    assert st["deviceMs"] == pytest.approx(7.5)
    assert st["peakHbmBytes"] == 800  # max over both transient footprints
    # merge_recent (the server->broker qid alias) sums ms, maxes HBM
    default_accountant.merge_recent("kq-1", {"deviceMs": 2.5, "peakHbmBytes": 500})
    st = default_accountant.recent_query_stats("kq-1")
    assert st["deviceMs"] == pytest.approx(10.0)
    assert st["peakHbmBytes"] == 800


def test_workload_rollup_folds_device_ms_and_peak_hbm():
    default_accountant.reset_rollups()
    r = _registry()
    with default_accountant.scope("kq-a", table="t", tenant="gold"):
        r.record("unit.k", 4.0, rows=1000)
    with default_accountant.scope("kq-b", table="t", tenant="gold"):
        r.record("unit.k", 6.0, rows=500)
    (roll,) = [w for w in default_accountant.workload_rollups() if w["table"] == "t"]
    assert roll["deviceMs"] == pytest.approx(10.0)  # counter: sums
    assert roll["peakHbmBytes"] == 8000  # high-watermark: max, not 12000


def test_record_lands_on_active_trace():
    r = _registry()
    with start_trace("req-7") as tr:
        r.record("unit.k", 2.5, rows=64)
    d = tr.to_dict()
    (ev,) = [e for e in d.get("events", []) if e["name"] == "kernel.execute"]
    assert ev["attrs"]["kernel"] == "unit.k"
    assert ev["attrs"]["shape"] == "2^6"
    assert ev["attrs"]["deviceMs"] == pytest.approx(2.5)
    assert d["phaseTimesMs"]["deviceExecution"] == pytest.approx(2.5)


def test_cache_observer_hit_miss_evict_counters():
    reset_registries()

    @lru_cache(maxsize=2)
    def f(x):
        return x * 2

    obs = CacheObserver(f, cache="unit")
    f(1), f(1), f(2)
    obs.observe()
    reg = server_metrics()
    assert reg.meter("engine.kernelCache.hits", cache="unit").count == 1
    assert reg.meter("engine.kernelCache.misses", cache="unit").count == 2
    assert reg.gauge("engine.kernelCache.size", cache="unit").value == 2
    f(3), f(4)  # pushes 1 and 2 out of the size-2 cache
    obs.observe()
    assert reg.meter("engine.kernelCache.misses", cache="unit").count == 4
    assert reg.meter("engine.kernelCache.evictions", cache="unit").count == 2
    # observe() is delta-folding: calling it again adds nothing
    obs.observe()
    assert reg.meter("engine.kernelCache.misses", cache="unit").count == 4


# -- end-to-end: engine -> global registry -----------------------------------


def test_engine_query_records_fused_kernel():
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    schema = Schema.build("t", dimensions=[("b", DataType.INT)], metrics=[("a", DataType.LONG)])
    rng = np.random.default_rng(3)
    seg = SegmentBuilder(schema).build(
        {"b": rng.integers(0, 4, 800).astype(np.int32),
         "a": rng.integers(0, 100, 800).astype(np.int64)},
        "t_0",
    )
    KERNELS.configure(enabled=True)
    KERNELS.reset_stats()
    eng = QueryEngine([seg])
    res = eng.execute("SELECT b, SUM(a) FROM t GROUP BY b")
    assert len(res.rows) == 4
    snap = KERNELS.stats_snapshot()
    fused = {k: v for k, v in snap.items() if k[0].startswith("query.fused")}
    assert fused and all(v["calls"] >= 1 and v["bytesMoved"] > 0 for v in fused.values())


# -- HTTP surfaces -----------------------------------------------------------


def test_debug_roofline_endpoint(cpu_roof):
    import pinot_tpu.query.kernels  # noqa: F401 — registers the query.* roots
    from pinot_tpu.cluster.http import ServerHTTPService
    from pinot_tpu.cluster.server import Server

    KERNELS.configure(enabled=True)
    KERNELS.reset_stats()
    KERNELS.record("query.fused", 2.0, rows=1024, cols=4)
    KERNELS.record("query.fused_packed", 1.0, rows=2048, cols=3)
    svc = ServerHTTPService(Server("obs-http"), port=0)
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{svc.port}/debug/roofline", timeout=10) as rsp:
            doc = json.loads(rsp.read())
        with urllib.request.urlopen(f"http://127.0.0.1:{svc.port}/debug/roofline?top=1", timeout=10) as rsp:
            top1 = json.loads(rsp.read())
    finally:
        svc.stop()
    assert doc["enabled"] is True
    # `kernels[].kernel` and `.calls` are what the benchmark reads of this document (perfbench/cluster.py `kernel_calls`)
    assert {k["kernel"]: k["calls"] for k in doc["kernels"]} == {"query.fused": 1, "query.fused_packed": 1}
    assert "query.fused" in doc["registered"]
    assert doc["hbm"]["source"] in ("estimator", "device")
    assert len(top1["offenders"]) <= 1 and len(doc["offenders"]) == 2


def test_aggregator_merges_roofline_and_workload_into_cluster(tmp_path):
    from pinot_tpu.cluster import Controller, PropertyStore
    from pinot_tpu.cluster.periodic import ClusterMetricsAggregator

    def roof_row(device_ms, nbytes):
        return {"kernel": "query.fused", "shape": "2^10", "calls": 5,
                "deviceMs": device_ms, "bytesMoved": nbytes, "flops": 100}

    def wl_row(device_ms, peak):
        return {"tenant": "gold", "table": "t", "queries": 5, "cpuTimeNs": 10,
                "allocatedBytes": 0, "segmentsExecuted": 5, "queriesKilled": 0,
                "deviceMs": device_ms, "peakHbmBytes": peak}

    per_node = {
        "server-0": {"roofline": [roof_row(1000.0, 500_000_000)], "workload": [wl_row(4.0, 100)]},
        "server-1": {"roofline": [roof_row(1000.0, 500_000_000)], "workload": [wl_row(6.0, 900)]},
    }

    def fetch(url):
        host = url.split("//")[1].split(":")[0]
        if "/metrics" in url:
            return json.dumps({})
        if "/debug/workload" in url:
            return json.dumps({"rollups": per_node[host]["workload"]})
        if "/debug/roofline" in url:
            return json.dumps({"kernels": per_node[host]["roofline"], "hbmPeakGBps": 819.0})
        raise AssertionError(f"unexpected scrape url {url}")

    controller = Controller(PropertyStore(), tmp_path / "deepstore")
    controller.register_server("server-0", None, host="server-0", port=80)
    controller.register_server("server-1", None, host="server-1", port=80)
    agg = ClusterMetricsAggregator(controller, fetch=fetch, now_fn=lambda: 1000.0)
    r = agg.run_once()
    assert all(r["scraped"].values())
    doc = agg.debug_cluster()

    roof = doc["cluster"]["roofline"]
    (merged,) = roof["kernels"]
    assert merged["calls"] == 10 and merged["deviceMs"] == pytest.approx(2000.0)
    assert merged["bytesMoved"] == 1_000_000_000
    # 1e9 bytes over 2s = 0.5 GB/s, recomputed from the merged totals
    assert merged["achievedGBps"] == pytest.approx(0.5)
    # the roof is the one the servers stated for their device
    assert roof["offenders"] and roof["hbmPeakGBps"] == 819.0

    wl = doc["cluster"]["workload"]["gold/t"]
    assert wl["deviceMs"] == pytest.approx(10.0)  # sums across servers
    assert wl["peakHbmBytes"] == 900  # high-watermark: max across servers


# -- config ------------------------------------------------------------------


def test_observability_config_kernel_obs_roundtrip():
    cfg = ObservabilityConfig(kernel_obs_enabled=False)
    d = cfg.to_dict()
    assert d["kernelObsEnabled"] is False
    back = ObservabilityConfig.from_dict(json.loads(json.dumps(d)))
    assert back.kernel_obs_enabled is False
    # defaults stay on: the plane is live out of the box
    dflt = ObservabilityConfig.from_dict({})
    assert dflt.kernel_obs_enabled is True
