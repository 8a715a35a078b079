"""The one span primitive (common/trace.py `span`): nesting and self time,
its second clock (the thread's CPU time), the phase ledger that leaves with
every v1 broker response (`spanTimesMs`, `spanSelfMs`, `spanCpuMs`,
`counters`, `deviceWork`), the stable names of the fused per-segment
programs, and the spans' arrival in a profiler trace.

No test asserts an overhead: order relations between spans that enclose each
other, counts, children that add up to their parent, and of the two clocks
that a sleep costs no CPU and a spin costs its wall.
"""

import contextlib
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.cluster.http import BrokerHTTPService, RemoteServerClient, ServerHTTPService
from pinot_tpu.common import CacheConfig, DataType, Schema, TableConfig
from pinot_tpu.common.trace import (
    PhaseLedger,
    ServerQueryPhase,
    active_ledger,
    bind_request,
    count,
    phase_timer,
    record_span,
    request_ledger,
    span,
    start_trace,
)
from pinot_tpu.query.scheduler import make_scheduler
from pinot_tpu.segment import SegmentBuilder

AGG = "SELECT COUNT(*), SUM(v) FROM t WHERE v > 3"
GROUP_BY = "SELECT d, SUM(v) FROM t GROUP BY d ORDER BY d LIMIT 10"

BROKER_SPANS = {"broker.request", "broker.compile", "broker.route", "broker.scatter", "broker.reduce"}
SERVER_SPANS = {
    "server.execute", "server.plan", "server.prune", "server.dispatch_all", "server.dispatch", "server.device_wait",
    "server.unpack",
}  # fmt: skip
WIRE_SPANS = {"broker.wire.encode", "broker.wire.decode", "server.wire.decode"}
RESPONSE_KEYS = ("spanTimesMs", "spanSelfMs", "spanCpuMs", "counters", "deviceWork")
# what a query over HTTP adds inside the widest spans: the front end, a leg on the clock, the answer's making
INSIDE_SPANS = {
    "broker.http.read", "broker.http.encode", "broker.wire.call", "broker.scatter.tail", "broker.result", "server.launch",
}  # fmt: skip
# the spans that read the thread clock too: what a per-layer metric tells work from waiting in
CPU_SPANS = {"broker.wire.decode", "broker.reduce", "server.dispatch_all", "server.unpack"}
#: the thread clock of the benchmark's machine ticks at 10 ms and books a tick late (PERF.md section 6, PR 37)
TICK_MS = 10.0
REDUCE_STAGES = ("broker.reduce.merge", "broker.reduce.rows", "broker.reduce.order", "broker.reduce.project")


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------


def test_span_outside_a_request_is_inert():
    assert active_ledger() is None
    with span("server.dispatch", segment="s0") as sp:
        sp.set_attr("rows", 4)
    record_span("server.queue", 1.0)
    count("segmentsDispatched")
    assert sp.ms >= 0.0 and active_ledger() is None


def test_child_time_is_not_counted_twice():
    with request_ledger("q-nest") as led:
        with span("outer"):
            with span("inner"):
                with span("leaf"):
                    pass
            with span("inner"):
                pass
            record_span("queued", 2.0)
    s = led.to_wire()["spans"]
    assert s["inner"][2] == 2 and s["outer"][2] == 1 and s["queued"] == [2.0, 2.0, 1, None]
    # self = total less what the children cover: the three levels add up to the outer total
    assert s["leaf"][1] == s["leaf"][0]
    assert s["inner"][1] == pytest.approx(s["inner"][0] - s["leaf"][0])
    assert s["outer"][1] == pytest.approx(max(s["outer"][0] - s["inner"][0] - 2.0, 0.0))
    fields = led.response_fields()
    assert set(fields) == set(RESPONSE_KEYS)
    assert fields["counters"] == {
        "wireRequestBytes": 0, "wireResponseBytes": 0, "serversMerged": 0, "scatterSkewMs": 0,
        "hostToDeviceTransfers": 0, "deviceReadbackWaits": 0, "groupedLimbFallbacks": 0,
        "reduceRowStages": 0, "segmentsStaged": 0, "segmentsDispatched": 0, "rowsDispatched": 0,
        "lookupOperandBuilds": 0, "lookupOperandBytesStaged": 0, "lookupMisses": 0,
        "starTreeSegments": 0, "starTreeRecords": 0, "starTreeBuilds": 0,
        "groupCompactSegments": 0, "groupCompactFallbacks": 0,
    }  # fmt: skip


def test_a_sleeping_span_costs_no_cpu():
    with request_ledger("q-sleep") as led:
        with span("sleeps", cpu=True) as sp:
            time.sleep(0.1)
        with span("untimed") as plain:  # the thread clock is read where a span asks
            pass
    total, _, _, cpu = led.to_wire()["spans"]["sleeps"]
    assert total >= 100.0 and cpu <= 2 * TICK_MS + 5.0  # a coarse clock may book a tick or two of earlier work here
    assert (sp.ms, sp.cpu_ms) == (total, cpu)
    assert plain.cpu_ms is None and led.to_wire()["spans"]["untimed"][3] is None
    assert led.response_fields()["spanCpuMs"] == {"sleeps": round(cpu, 3)}


def test_a_spinning_span_costs_its_spin_in_cpu():
    """30 ms of the thread's own CPU time, spun by the thread clock: the span reads them, to a tick or two of a
    coarse clock, however long the box's other work made that take on the wall (alone on a core the two clocks
    agree; six workers on the driver's box halve the spin's share, which is what the second clock is there to show)."""
    with span("spins", cpu=True) as sp:
        until = time.thread_time() + 0.03
        while time.thread_time() < until:
            pass
        closed_at = time.perf_counter()
    assert 30.0 <= sp.cpu_ms <= 30.0 + 2 * TICK_MS and sp.cpu_ms <= sp.ms + TICK_MS
    assert sp.end == pytest.approx(closed_at, abs=5e-3)


def test_phase_timer_is_transparent_to_the_ledger():
    """A phase timer has no span name of its own: it stays out of the
    ledger and hides no child from the span around it."""
    with request_ledger("q-phase") as led:
        with span("outer"):
            with phase_timer(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                with span("inner"):
                    pass
    s = led.to_wire()["spans"]
    assert set(s) == {"outer", "inner"}
    assert s["outer"][1] == pytest.approx(s["outer"][0] - s["inner"][0])


def test_span_in_a_scheduler_worker_lands_under_its_request():
    sched = make_scheduler("fcfs")
    sched.start()
    try:
        seen = {}

        def job():
            seen["ledger"] = active_ledger()
            with span("server.plan"):
                pass
            return threading.get_ident()

        with request_ledger("q-sched", "server") as led:
            with span("server.execute"):
                worker = sched.submit(job, table="t").result()
        assert worker != threading.get_ident() and seen["ledger"] is led
        s = led.to_wire()["spans"]
        # the worker's span is a child of the span that submitted it
        assert s["server.execute"][1] == pytest.approx(s["server.execute"][0] - s["server.plan"][0])
    finally:
        sched.stop()
    assert active_ledger() is None


def test_bind_request_carries_the_request_into_a_pool_thread():
    from concurrent.futures import ThreadPoolExecutor

    def leg():
        with span("broker.wire.encode"):
            pass
        return active_ledger()

    with ThreadPoolExecutor(max_workers=1) as pool:
        with request_ledger("q-pool") as led:
            with span("broker.scatter"):
                assert pool.submit(bind_request(leg)).result() is led
        # nothing of the request stays behind in the pool's thread
        assert pool.submit(active_ledger).result() is None
    assert "broker.wire.encode" in led.to_wire()["spans"]


def test_a_role_gets_its_own_ledger():
    with request_ledger("q-roles", "broker") as broker_led:
        with span("broker.scatter"):
            with request_ledger("q-roles", "server") as server_led:
                assert server_led is not broker_led and server_led.qid == "q-roles"
                with request_ledger("q-roles", "server") as again:  # HTTP handler, then execute_partials
                    assert again is server_led
                with span("server.execute"):
                    pass
            assert active_ledger() is broker_led
    assert "server.execute" in server_led.to_wire()["spans"]
    # the server's time is not a child of the broker's span: in-process and over HTTP read alike
    b = broker_led.to_wire()["spans"]["broker.scatter"]
    assert b[0] == b[1]


@pytest.mark.parametrize("executes", [[40.0], [40.0, 90.0], [70.0, 90.0, 40.0, 55.5]], ids=["1-server", "2-servers", "4-servers"])
def test_merge_of_servers_is_the_max_and_work_adds_up(executes):
    """The spans are the slowest server's, taken whole; counters and device
    work add up over all of them; the skew is the slowest less the fastest."""

    def doc(ms, rows):
        # a server's wait is the longer, the shorter its execution: a span-by-span max would pair
        # the slowest server's `server.execute` with the fastest's `server.device_wait`
        return {
            "spans": {"server.execute": [ms, 1.0, 1, ms / 2], "server.device_wait": [100.0 - ms, 100.0 - ms, 3, None]},
            "counters": {"wireRequestBytes": 7},
            "deviceWork": {"seg_agg_00000001": {"launches": 3, "rows": rows, "kernels": {
                "ops.grouped_planes": {"calls": 3, "bytes": 10.0, "flops": 20.0}}}},
        }  # fmt: skip

    n = len(executes)
    led = PhaseLedger("q-merge")
    led.merge_servers([doc(ms, 300 * (i + 1)) for i, ms in enumerate(executes)])
    out = led.response_fields()
    assert out["spanTimesMs"] == {"server.execute": max(executes), "server.device_wait": 100.0 - max(executes)}
    assert out["spanSelfMs"] == {"server.execute": 1.0, "server.device_wait": 100.0 - max(executes)}
    # the cpu is the slowest server's too, whole: not a sum over servers, not another server's
    assert out["spanCpuMs"] == {"server.execute": max(executes) / 2}  # a span that read no thread clock has none
    rows = 300 * n * (n + 1) // 2
    # what was dispatched is read off the merged device work
    assert out["counters"]["segmentsDispatched"] == 3 * n and out["counters"]["rowsDispatched"] == rows
    assert out["counters"]["wireRequestBytes"] == 7 * n
    assert out["counters"]["serversMerged"] == n
    assert out["counters"]["scatterSkewMs"] == max(executes) - min(executes)
    work = out["deviceWork"]["seg_agg_00000001"]
    assert work["launches"] == 3 * n and work["rows"] == rows
    assert work["kernels"]["ops.grouped_planes"] == {"calls": 3 * n, "bytes": 10.0 * n, "flops": 20.0 * n}
    # a second leg (hybrid table: offline, then realtime) comes after the first: its time adds
    led.merge_servers([doc(10.0, 100)])
    out = led.response_fields()
    assert out["spanTimesMs"]["server.execute"] == max(executes) + 10.0
    assert out["spanCpuMs"]["server.execute"] == max(executes) / 2 + 5.0
    assert out["counters"]["serversMerged"] == n + 1 and out["counters"]["scatterSkewMs"] == max(executes) - min(executes)
    led.merge_servers([])  # a leg whose segments were all pruned
    assert led.response_fields()["counters"]["serversMerged"] == n + 1
    # a server from before the second clock sends three numbers a span: it adds no cpu
    led.merge_servers([{"spans": {"server.execute": [5.0, 5.0, 1]}}])
    assert led.response_fields()["spanCpuMs"]["server.execute"] == max(executes) / 2 + 5.0


def test_span_joins_the_request_trace_tree_when_one_is_active():
    with start_trace("q-tree") as tr:
        with span("server.execute"):
            with phase_timer(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                with span("server.dispatch", segment="s0") as sp:
                    sp.set_attr("rows", 8)
    d = tr.to_dict()
    assert "queryPlanExecution" in d["phaseTimesMs"]
    (root,) = d["spans"]
    assert root["name"] == "server.execute"
    (child,) = root["children"]  # the phase timer adds no node of its own
    assert child["name"] == "server.dispatch" and child["attrs"] == {"segment": "s0", "rows": 8}


# ---------------------------------------------------------------------------
# the load path's spans, outside any request
# ---------------------------------------------------------------------------


def test_the_load_paths_spans_feed_metrics_without_a_ledger(tmp_path):
    """`controller.upload` and its four children, and the server's
    `server.load`, are `span`s with a phase: no request's ledger is open
    around an upload, so what is left of them is the role's `/metrics` timer
    (and, under a profiler session, the annotation)."""
    import io
    import tarfile

    from pinot_tpu.common.metrics import get_registry
    from pinot_tpu.segment.builder import write_segment

    schema = Schema.build("u", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)])
    controller = Controller(PropertyStore(), tmp_path / "deep")
    controller.register_server("server_0", Server("server_0", data_dir=tmp_path / "data"))
    controller.add_schema(schema)
    controller.add_table(TableConfig("u"))
    seg = SegmentBuilder(schema).build({"d": np.arange(50, dtype=np.int32), "v": np.arange(50, dtype=np.int64)}, "u_0")
    seg_dir = write_segment(seg, tmp_path / "built")
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tf:
        tf.add(seg_dir, arcname=seg_dir.name)
    names = {
        "controller": ["segmentUpload", "segmentUploadUntar", "segmentUploadVerify", "segmentUploadPublish", "segmentUploadTransition"],
        "server": ["segmentLoad"],
    }
    before = {(role, n): get_registry(role).timer(f"{role}.phase.{n}Ms").count for role, ns in names.items() for n in ns}
    assert active_ledger() is None
    assert controller.upload_segment_archive("u", buf.getvalue()) == ("u_0", ["server_0"])
    for (role, n), count in before.items():
        timer = get_registry(role).timer(f"{role}.phase.{n}Ms")
        assert timer.count == count + 1, (role, n)
    whole = get_registry("controller").timer("controller.phase.segmentUploadMs")
    parts = [get_registry("controller").timer(f"controller.phase.{n}Ms") for n in names["controller"][1:]]
    assert whole.max_ms >= max(t.min_ms for t in parts)
    # the in-process entry shares the publish and transition steps, and the server's load
    controller.upload_segment("u", SegmentBuilder(schema).build({"d": np.arange(5, dtype=np.int32), "v": np.arange(5, dtype=np.int64)}, "u_1"))
    assert get_registry("controller").timer("controller.phase.segmentUploadPublishMs").count == before[("controller", "segmentUploadPublish")] + 2
    assert get_registry("server").timer("server.phase.segmentLoadMs").count == before[("server", "segmentLoad")] + 2
    assert get_registry("controller").timer("controller.phase.segmentUploadMs").count == before[("controller", "segmentUpload")] + 1


# ---------------------------------------------------------------------------
# every broker answer carries the ledger
# ---------------------------------------------------------------------------


def _load(controller, n_segments=4, rows=200):
    schema = Schema.build("t", dimensions=[("d", DataType.INT)], metrics=[("v", DataType.LONG)])
    controller.add_schema(schema)
    controller.add_table(TableConfig("t"))
    b = SegmentBuilder(schema)
    for i in range(n_segments):
        controller.upload_segment(
            "t", b.build({"d": np.arange(rows, dtype=np.int32) % 5, "v": np.arange(rows, dtype=np.int64)}, f"t_{i}")
        )


@pytest.fixture(scope="module")
def inproc(tmp_path_factory):
    controller = Controller(PropertyStore(), tmp_path_factory.mktemp("spans_inproc"))
    for i in range(2):
        controller.register_server(f"server_{i}", Server(f"server_{i}"))
    _load(controller)
    return Broker(controller, cache_config=CacheConfig(enabled=False))


@pytest.fixture(scope="module")
def over_http(tmp_path_factory):
    controller = Controller(PropertyStore(), tmp_path_factory.mktemp("spans_http"))
    servers = {f"server_{i}": Server(f"server_{i}", scheduler="fcfs") for i in range(2)}
    services = {sid: ServerHTTPService(s, port=0) for sid, s in servers.items()}
    for sid, svc in services.items():
        controller.register_server(sid, RemoteServerClient(f"http://127.0.0.1:{svc.port}"))
    _load(controller)
    broker = Broker(controller, cache_config=CacheConfig(enabled=False))
    front = BrokerHTTPService(broker, port=0)
    yield f"http://127.0.0.1:{front.port}"
    front.stop()
    for svc in services.values():
        svc.stop()


def _post(url, sql):
    req = urllib.request.Request(
        f"{url}/query/sql", json.dumps({"sql": sql}).encode(), {"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=120) as rsp:
        return json.loads(rsp.read())


def _check_ledger(doc, names, n_segments, wire: bool):
    for key in RESPONSE_KEYS:
        assert key in doc, key
    total, own, cpu = doc["spanTimesMs"], doc["spanSelfMs"], doc["spanCpuMs"]
    assert set(total) == set(own) and CPU_SPANS & set(total) == set(cpu)
    assert all(v >= 0.0 for v in cpu.values())
    assert names <= set(total), sorted(names - set(total))
    assert total["broker.request"] >= total["broker.scatter"] >= total["server.execute"] >= total["server.device_wait"]
    assert all(0.0 <= own[k] <= total[k] + 1e-6 for k in total)
    c = doc["counters"]
    assert c["segmentsDispatched"] == n_segments and c["rowsDispatched"] > 0
    assert (c["wireRequestBytes"] > 0 and c["wireResponseBytes"] > 0) if wire else c["wireRequestBytes"] == 0
    assert sum(w["launches"] for w in doc["deviceWork"].values()) == n_segments
    assert sum(w["rows"] for w in doc["deviceWork"].values()) == c["rowsDispatched"]
    assert all(re.match(r"^seg_[a-z]+_[0-9a-f]{8}$", name) for name in doc["deviceWork"])


@pytest.mark.parametrize("sql,kind", [(AGG, "agg"), (GROUP_BY, "groupby")])
def test_untraced_query_in_process_answers_with_the_ledger(inproc, sql, kind):
    doc = inproc.execute(sql).to_dict()
    assert "traceInfo" not in doc and "traceId" not in doc
    _check_ledger(doc, BROKER_SPANS | SERVER_SPANS, n_segments=4, wire=False)
    assert all(name.startswith(f"seg_{kind}_") for name in doc["deviceWork"])


@pytest.mark.parametrize("sql,kind", [(AGG, "agg"), (GROUP_BY, "groupby")])
def test_untraced_query_over_http_answers_with_the_ledger(over_http, sql, kind):
    doc = _post(over_http, sql)
    assert not doc.get("exceptions") and "traceInfo" not in doc
    # the servers run a scheduler here, so the queue wait is a span too
    _check_ledger(doc, BROKER_SPANS | SERVER_SPANS | WIRE_SPANS | INSIDE_SPANS | {"server.queue"}, n_segments=4, wire=True)
    assert all(name.startswith(f"seg_{kind}_") for name in doc["deviceWork"])
    total = doc["spanTimesMs"]
    # a leg is encode + call + decode, and the tail starts where the last call ended: inside the scatter
    assert total["broker.scatter.tail"] <= total["broker.scatter"]
    assert total["broker.http.encode"] > 0.0 and total["broker.http.read"] > 0.0
    assert CPU_SPANS == set(doc["spanCpuMs"])


def test_the_stages_of_a_group_by_add_up_to_the_reduce(over_http):
    doc = _post(over_http, GROUP_BY)
    total = doc["spanTimesMs"]
    assert set(REDUCE_STAGES) <= set(total) and "broker.reduce.having" not in total
    assert sum(total[n] for n in REDUCE_STAGES) == pytest.approx(total["broker.reduce"], rel=0.02, abs=0.5)
    assert doc["spanSelfMs"]["broker.reduce"] <= max(0.02 * total["broker.reduce"], 0.5)
    # no stage of it left the columns; a HAVING reads a row env a group, and says so
    assert doc["counters"]["reduceRowStages"] == 0
    having = _post(over_http, "SELECT d, SUM(v) FROM t GROUP BY d HAVING SUM(v) > 0 ORDER BY d LIMIT 10")
    assert "broker.reduce.having" in having["spanTimesMs"] and having["counters"]["reduceRowStages"] == 1
    # an aggregation has two stages only, a span each
    agg = _post(over_http, AGG)["spanTimesMs"]
    assert {"broker.reduce.merge", "broker.reduce.rows"} <= set(agg) and not {"broker.reduce.order", "broker.reduce.project"} & set(agg)


def test_the_answer_carries_the_time_of_its_own_encoding(over_http):
    """The rows are encoded inside `broker.http.encode`, the envelope with the ledger's fields after it: the
    spliced answer is what `json.dumps` of the whole would be."""
    doc = _post(over_http, GROUP_BY)
    assert doc["spanTimesMs"]["broker.http.encode"] > 0.0
    assert doc["resultTable"]["rows"] == [[d, sum(v for v in range(200) if v % 5 == d) * 4] for d in range(5)]
    from pinot_tpu.query.result import ResultTable

    res = ResultTable(columns=["a", "b"], rows=[[1, "x\"y"], [None, 2.5]], num_servers_queried=2, span_stats={"spanTimesMs": {"s": 1.0}})
    assert res.to_json(json.dumps(res.rows).encode()) == json.dumps(res.to_dict()).encode()
    assert ResultTable(columns=[], rows=[]).to_json(b"[]") == json.dumps(ResultTable(columns=[], rows=[]).to_dict()).encode()


def test_in_process_callers_see_no_front_end_and_no_wire(inproc):
    doc = inproc.execute(GROUP_BY).to_dict()
    total = doc["spanTimesMs"]
    assert not any(n.startswith(("broker.http", "broker.wire")) for n in total) and "broker.scatter.tail" not in total
    assert {"broker.result", "server.launch", *REDUCE_STAGES} <= set(total)
    assert doc["spanCpuMs"].keys() == {"broker.reduce", "server.dispatch_all", "server.unpack"}


def test_a_query_s_dispatches_sit_under_the_one_span_that_reads_the_thread_clock_for_them(inproc):
    doc = inproc.execute(AGG).to_dict()
    total, cpu = doc["spanTimesMs"], doc["spanCpuMs"]
    # the run of dispatches and the pruning between them, whole: the one reading covers what its span covers
    assert total["server.dispatch"] + total["server.prune"] <= total["server.dispatch_all"] + 1e-6
    assert total["server.launch"] <= total["server.dispatch"] <= total["server.dispatch_all"]
    assert "server.dispatch" not in cpu and 0.0 <= cpu["server.dispatch_all"] <= total["server.dispatch_all"] + TICK_MS
    assert doc["spanSelfMs"]["server.dispatch_all"] <= total["server.dispatch_all"] - total["server.dispatch"] + 1e-6


def test_the_hedge_and_the_selector_are_fed_the_leg_whole(tmp_path):
    """What the hedge's timer waits on is the leg's future — encode, call and decode — so that is what its
    latency model holds: a handle that spends 30 ms after its answer arrived, as a decode would, is 30 ms slower."""

    class SlowAfterTheAnswer:
        def __init__(self, server):
            self._server = server

        def __getattr__(self, name):
            return getattr(self._server, name)

        def execute_partials(self, *args, **kwargs):
            out = self._server.execute_partials(*args, **kwargs)
            time.sleep(0.03)
            return out

    controller = Controller(PropertyStore(), tmp_path)
    controller.register_server("server_0", SlowAfterTheAnswer(Server("server_0")))
    _load(controller, n_segments=1)
    broker = Broker(controller, cache_config=CacheConfig(enabled=False))
    doc = broker.execute(AGG).to_dict()
    assert broker._hedge_ewma[("server_0", "t")] >= 30.0 + doc["spanTimesMs"]["server.execute"]


def test_first_touch_of_a_segment_is_staged_inside_the_query_and_counted(tmp_path):
    controller = Controller(PropertyStore(), tmp_path)
    controller.register_server("server_0", Server("server_0"))
    _load(controller, n_segments=3)
    broker = Broker(controller, cache_config=CacheConfig(enabled=False))
    first, second = broker.execute(AGG).to_dict(), broker.execute(AGG).to_dict()
    assert first["counters"]["segmentsStaged"] == 3 and first["spanTimesMs"]["server.stage"] > 0.0
    # staging is inside the dispatch that first touched the segment
    assert first["spanTimesMs"]["server.stage"] <= first["spanTimesMs"]["server.dispatch"]
    assert second["counters"]["segmentsStaged"] == 0
    assert "server.stage" not in second["spanTimesMs"]


def test_sampled_query_over_http_keeps_ledger_and_subtrees(over_http):
    doc = _post(over_http, "SET trace=true; " + AGG)
    _check_ledger(doc, BROKER_SPANS | SERVER_SPANS | WIRE_SPANS, n_segments=4, wire=True)
    # the trace document's phase keys keep their names, beside the new top-level keys
    assert {"requestCompilation", "brokerReduce"} <= set(doc["traceInfo"]["phaseTimesMs"])
    subtrees = doc["traceInfo"]["processes"]
    assert len(subtrees) == 2 and all("ledger" not in sub for sub in subtrees)

    def names(spans):
        for s in spans:
            yield s["name"]
            yield from names(s.get("children", ()))

    assert {"server.plan", "server.dispatch", "server.device_wait"} <= set(names(subtrees[0]["spans"]))


def test_streamed_selection_answers_with_the_broker_side_of_the_ledger(inproc):
    doc = inproc.execute("SELECT d, v FROM t LIMIT 5").to_dict()
    for key in RESPONSE_KEYS:
        assert key in doc
    assert {"broker.request", "broker.route"} <= set(doc["spanTimesMs"])


# ---------------------------------------------------------------------------
# stable program names, static device work
# ---------------------------------------------------------------------------

_NAME_SCRIPT = """
import pinot_tpu
from pinot_tpu.query.kernels import program_name
spec = ("agg", ("and", (("cmp", "ge", ("raw", "v"), 0), ("in_lut", "d", 1))), ("groups", ("d", "e"), 35), (("sum", ("raw", "v")), ("count",)))
print(program_name(spec), program_name(spec[:2] + (None,) + spec[3:]), program_name(("select", spec[1], (("raw", "v"),), frozenset({"b", "a", "c"}))))
"""


def test_program_name_is_the_same_in_every_process():
    outs = []
    for seed in ("1", "77"):
        env = {**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-c", _NAME_SCRIPT], env=env, capture_output=True, text=True, timeout=300, check=True
        )
        outs.append(out.stdout.split())
    assert outs[0] == outs[1]
    groupby, agg, select = outs[0]
    assert all(re.match(r"^seg_[a-z]+_[0-9a-f]{8}$", n) for n in outs[0])
    assert (groupby[:12], agg[:8], select[:11]) == ("seg_groupby_", "seg_agg_", "seg_select_")
    assert groupby[-8:] != agg[-8:]  # two specs, two names


def test_jitted_program_carries_its_name():
    from pinot_tpu.query.kernels import get_packed_kernel, program_name

    spec = ("agg", ("const", True), None, (("count",),))
    assert get_packed_kernel(spec).__name__ == program_name(spec)


def test_device_work_of_a_group_by_is_the_kernels_cost_model(tmp_path, monkeypatch):
    """The Pallas byte-plane kernel is traced into the fused program: the
    response's `deviceWork` holds its registered cost model at the shapes the
    trace saw, once per launch."""
    from pinot_tpu.ops.groupby_pallas import PLANES_CHUNK, _planes_cost

    monkeypatch.setenv("PINOT_TPU_PALLAS", "1")  # the chip's path, interpreted on the CPU
    controller = Controller(PropertyStore(), tmp_path)
    controller.register_server("server_0", Server("server_0"))
    schema = Schema.build("w", dimensions=[("g", DataType.INT)], metrics=[("m", DataType.INT)])
    controller.add_schema(schema)
    controller.add_table(TableConfig("w"))
    b = SegmentBuilder(schema)
    n, groups = 3000, 7
    for i in range(2):
        cols = {"g": np.arange(n, dtype=np.int32) % groups, "m": (np.arange(n, dtype=np.int32) * 3) % 1000}
        controller.upload_segment("w", b.build(cols, f"w_{i}"))
    broker = Broker(controller, cache_config=CacheConfig(enabled=False))
    res = broker.execute("SELECT g, SUM(m), COUNT(*) FROM w WHERE m < 990 GROUP BY g ORDER BY g LIMIT 20")
    m = (np.arange(n) * 3) % 1000
    want = [[g, 2 * int(m[(np.arange(n) % groups == g) & (m < 990)].sum())] for g in range(groups)]
    assert [r[:2] for r in res.rows] == want
    doc = res.to_dict()
    ((name, work),) = doc["deviceWork"].items()
    assert name.startswith("seg_groupby_") and work["launches"] == 2
    seg_rows = work["rows"] // 2
    assert seg_rows >= n
    kernel = work["kernels"]["ops.grouped_planes2"]  # the name says which kernel ran
    assert kernel["calls"] == 2  # one pallas_call a launch
    # the kernel pads the segment's docs to its chunk; one int32 value column is four
    # byte planes and the count; the planner rounds the dense group space (7 values
    # of `g`) up to a step of 256 (plan.group_spec)
    rows = -(-seg_rows // PLANES_CHUNK) * PLANES_CHUNK
    nbytes, flops = _planes_cost({"rows": rows, "groups": 256, "planes": 5})
    assert flops == rows * 256 * 10.0  # a MAC per (doc, group, plane row)
    assert kernel["bytes"] == pytest.approx(2 * nbytes)
    assert kernel["flops"] == pytest.approx(2 * flops)


# ---------------------------------------------------------------------------
# on the profiler's clock
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def time_limit(seconds: int):
    """This test's own limit: a profiler that hangs fails one test, not the run."""

    def expired(signum, frame):
        raise TimeoutError(f"no end after {seconds} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def test_spans_reach_the_profiler_trace_with_the_query_id(tmp_path, inproc):
    import jax
    from jax.profiler import ProfileData

    inproc.execute(AGG)  # compiled before the trace starts
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # what perfbench/server_main.py sets
    with time_limit(120):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            doc = inproc.execute(AGG).to_dict()
        finally:
            jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("server.dispatch", "server.device_wait", "broker.request"):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    # a launch a segment; one wait a server for its two segments' vectors
    assert len(found["server.dispatch"]) == 4 and len(found["server.device_wait"]) == 2
    qids = {str(st["qid"]) for evs in found.values() for st in evs}
    assert len(qids) == 1 and re.match(r"^q\d+$", qids.pop())
    programs = {str(st["program"]) for st in found["server.dispatch"]}
    assert programs == set(doc["deviceWork"])
    assert all(int(st["rows"]) > 0 and str(st["segment"]).startswith("t_") for st in found["server.dispatch"])


def test_launch_and_staging_reach_the_profiler_trace_with_the_query_id(tmp_path):
    """What `gap_attribution` names a device gap by under `server.dispatch`: the launch, and a fresh table's
    first staging, both annotations of the host plane with the broker's query id."""
    import jax
    from jax.profiler import ProfileData

    controller = Controller(PropertyStore(), tmp_path / "ds")
    controller.register_server("server_0", Server("server_0"))
    _load(controller, n_segments=2)
    broker = Broker(controller, cache_config=CacheConfig(enabled=False))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with time_limit(120):
        jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
        try:
            doc = broker.execute(AGG).to_dict()
        finally:
            jax.profiler.stop_trace()
    (path,) = list((tmp_path / "trace").rglob("*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("server.launch", "server.stage", "server.dispatch"):
                        found.setdefault(ev.name, []).append((ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    assert len(found["server.launch"]) == len(found["server.stage"]) == len(found["server.dispatch"]) == 2
    assert {str(st["qid"]) for evs in found.values() for _, _, st in evs} == {str(next(iter(found["server.dispatch"]))[2]["qid"])}
    assert {str(st["program"]) for _, _, st in found["server.launch"]} == set(doc["deviceWork"])
    assert all(str(st["segment"]).startswith("t_") and int(st["bytes"]) > 0 and int(st["columns"]) == 2 for _, _, st in found["server.stage"])
    # each lies inside a dispatch: the innermost `server.*` span over a gap there is the launch or the staging
    for name in ("server.launch", "server.stage"):
        assert all(any(a <= s and e <= b for a, b, _ in found["server.dispatch"]) for s, e, _ in found[name])
