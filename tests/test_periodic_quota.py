"""Controller periodic tasks, query quotas, query logging.

Reference test model: SegmentStatusChecker/RetentionManager tests in
pinot-controller, HelixExternalViewBasedQueryQuotaManager tests,
QueryLogger rate-limit tests (SURVEY.md §5.3/§5.5).
"""

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.cluster.periodic import (
    MissingConsumingSegmentFinder,
    PeriodicTaskScheduler,
    RebalanceChecker,
    RetentionManager,
    SegmentStatusChecker,
)
from pinot_tpu.cluster.quota import QueryLogger, QueryQuotaManager, QuotaExceededError
from pinot_tpu.common import DataType, Schema, TableConfig, TableType
from pinot_tpu.segment import SegmentBuilder


def _schema(name="t"):
    return Schema.build(
        name, dimensions=[("k", DataType.STRING)], metrics=[("v", DataType.LONG)], date_times=[("ts", DataType.LONG)]
    )


def _mk(tmp_path, tc: TableConfig):
    controller = Controller(PropertyStore(), tmp_path / "ds")
    server = Server("s0")
    controller.register_server("s0", server)
    schema = _schema(tc.table_name)
    controller.add_schema(schema)
    controller.add_table(tc)
    return controller, server, schema


def _seg(schema, name, ts):
    n = len(ts)
    return SegmentBuilder(schema).build(
        {
            "k": np.array(["x"] * n, dtype=object),
            "v": np.ones(n, dtype=np.int64),
            "ts": np.asarray(ts, dtype=np.int64),
        },
        name,
    )


def test_segment_status_checker(tmp_path):
    controller, server, schema = _mk(tmp_path, TableConfig("t", replication=2, time_column="ts"))
    controller.register_server("s1", Server("s1"))
    controller.upload_segment("t", _seg(schema, "a", [1, 2]))
    res = SegmentStatusChecker(controller).run_once()
    assert res["t"] == {"segments": 1, "minReplicas": 2, "percent": 100}
    # degrade one replica
    controller.set_segment_state("t", "a", "s1", None)
    res = SegmentStatusChecker(controller).run_once()
    assert res["t"]["minReplicas"] == 1 and res["t"]["percent"] == 50


def test_retention_manager_purges_old_segments(tmp_path):
    tc = TableConfig("t", time_column="ts")
    tc.extra = {"retention": {"value": 100}}
    controller, server, schema = _mk(tmp_path, tc)
    controller.upload_segment("t", _seg(schema, "old", [10, 20]))
    controller.upload_segment("t", _seg(schema, "new", [950, 990]))
    rm = RetentionManager(controller, now_fn=lambda: 1000.0)
    res = rm.run_once()
    assert res["t"]["purged"] == ["old"]
    assert list(controller.ideal_state("t")) == ["new"]
    # idempotent
    assert rm.run_once()["t"]["purged"] == []


def test_retention_skips_tables_without_config(tmp_path):
    controller, server, schema = _mk(tmp_path, TableConfig("t", time_column="ts"))
    controller.upload_segment("t", _seg(schema, "a", [1]))
    assert RetentionManager(controller, now_fn=lambda: 1e12).run_once()["t"]["purged"] == []


def test_rebalance_checker_detects_and_fixes(tmp_path):
    controller, server, schema = _mk(tmp_path, TableConfig("t", replication=2, time_column="ts"))
    controller.upload_segment("t", _seg(schema, "a", [1]))
    controller.register_server("s1", Server("s1"))
    res = RebalanceChecker(controller).run_once()
    assert res["t"]["needsRebalance"] is True
    res = RebalanceChecker(controller, auto_fix=True).run_once()
    assert res["t"].get("fixed") is True
    assert RebalanceChecker(controller).run_once()["t"]["needsRebalance"] is False


def test_missing_consuming_segment_finder(tmp_path):
    tc = TableConfig("rt", TableType.REALTIME, time_column="ts")
    tc.extra = {"streamPartitions": 2}
    controller, server, schema = _mk(tmp_path, tc)
    controller.set_segment_state("rt", "rt__0__0", "s0", "CONSUMING")
    res = MissingConsumingSegmentFinder(controller).run_once()
    assert res["rt"]["missingPartitions"] == [1]
    controller.set_segment_state("rt", "rt__1__0", "s0", "CONSUMING")
    assert MissingConsumingSegmentFinder(controller).run_once()["rt"]["missingPartitions"] == []


def test_scheduler_runs_in_background(tmp_path):
    import time

    controller, server, schema = _mk(tmp_path, TableConfig("t", time_column="ts"))
    runs = []

    class Probe(SegmentStatusChecker):
        interval_sec = 0.01

        def process_table(self, table):
            runs.append(table)
            return {}

    sched = PeriodicTaskScheduler()
    sched.register(Probe(controller))
    sched.start()
    try:
        for _ in range(100):
            if len(runs) >= 2:
                break
            time.sleep(0.02)
    finally:
        sched.stop()
    assert len(runs) >= 2


def test_task_survives_bad_table(tmp_path):
    controller, server, schema = _mk(tmp_path, TableConfig("t", time_column="ts"))

    class Boom(SegmentStatusChecker):
        def process_table(self, table):
            raise RuntimeError("boom")

    res = Boom(controller).run_once()
    assert "boom" in res["t"]["error"]


# -- quota -------------------------------------------------------------------


def test_query_quota_enforced(tmp_path):
    tc = TableConfig("t", time_column="ts")
    tc.extra = {"queryQuotaQps": 3}
    controller, server, schema = _mk(tmp_path, tc)
    q = QueryQuotaManager(controller)
    for _ in range(3):
        q.acquire("t")
    with pytest.raises(QuotaExceededError):
        q.acquire("t")
    # unknown / unquota'd tables admit freely
    q.acquire("other")


def test_broker_rejects_over_quota(tmp_path):
    tc = TableConfig("t", time_column="ts")
    tc.extra = {"queryQuotaQps": 2}
    controller, server, schema = _mk(tmp_path, tc)
    controller.upload_segment("t", _seg(schema, "a", [1]))
    broker = Broker(controller)
    # the window's second stands still: a first query that compiles for longer than that, on a loaded box,
    # must not let the third through
    broker.quota = QueryQuotaManager(controller, clock=lambda: 1000.0)
    assert broker.execute("SELECT COUNT(*) FROM t").rows[0][0] == 1
    broker.execute("SELECT COUNT(*) FROM t")
    with pytest.raises(QuotaExceededError):
        broker.execute("SELECT COUNT(*) FROM t")


# -- query log ---------------------------------------------------------------


def test_query_logger_rate_limit_and_dropped_count(caplog):
    import logging

    ql = QueryLogger(max_rate_per_sec=2)
    with caplog.at_level(logging.INFO, logger="pinot_tpu.querylog"):
        assert ql.log("q1", "t", 1.0, 10)
        assert ql.log("q2", "t", 1.0, 10)
        assert not ql.log("q3", "t", 1.0, 10)  # dropped
    assert ql.emitted == 2 and ql.dropped_total == 1
    assert "query=q1" in caplog.text


def test_broker_logs_queries(tmp_path, caplog):
    import logging

    controller, server, schema = _mk(tmp_path, TableConfig("t", time_column="ts"))
    controller.upload_segment("t", _seg(schema, "a", [1, 2]))
    ql = QueryLogger()
    broker = Broker(controller, query_logger=ql)
    with caplog.at_level(logging.INFO, logger="pinot_tpu.querylog"):
        broker.execute("SELECT COUNT(*) FROM t")
        with pytest.raises(KeyError):
            broker.execute("SELECT COUNT(*) FROM missing")
    assert ql.emitted == 2
    assert "exception=KeyError" in caplog.text
