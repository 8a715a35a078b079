"""A star schema served by OS processes: two server processes behind a
broker, the fact table's segments shared between them, the four dimension
tables hosted whole by both (`"everyServer"`), loaded through the controller
as the benchmark's launcher loads any table (`perfbench.run.Cluster`, on the
CPU at the rehearsal's sizes).

Holds what ROADMAP D17 was: a server that is an OS process keeps its own
dimension tables — the flag and the primary key reach it with the state
transition — so a `lookUp` query is answered by both servers on the device
path, and a server that was killed and started again answers it once it has
reloaded. The answers are compared with the benchmark's plain reference over
the pre-joined flat table of the same seed.
"""

import shutil
import time

import numpy as np
import pytest

from perfbench import check, refeval
from perfbench import tables as tables_mod
from perfbench.cluster import http_json, metric_total, ready_doc
from perfbench.loadgen import Client
from perfbench.manifest import ROOT
from perfbench.run import CACHE, Cluster

SEED = 4_100_000_011
CONFIG = {
    "name": "ssb-star-2srv-test", "dataset": "ssb_star_lookup", "scaleFactor": 1, "rows": 48000, "segmentRows": 8000,
    "servers": 2, "chips": 1, "replication": 1, "cacheSeeds": 1, "broker": {"cache": {"enabled": False}},
    "guarantees": {"doubleSumRelTolerance": 0.0},
    "tables": [
        *(
            {"name": name, "generator": name, "rows": rows, "segmentRows": rows, "replication": "everyServer",
             "schema": {"primaryKeyColumns": [key]}, "tableConfig": {"tableType": "OFFLINE", "extra": {"isDimTable": True}}}
            for name, rows, key in (("customer", 30000, "c_custkey"), ("supplier", 2000, "s_suppkey"),
                                    ("part", 200000, "p_partkey"), ("dates", 2556, "d_datekey"))
        ),
        {"name": "lineorder", "generator": "lineorder"},
    ],
}  # fmt: skip


@pytest.fixture(scope="module")
def cluster():
    shutil.rmtree(CACHE / f"{CONFIG['name']}-rehearsal" / str(SEED), ignore_errors=True)
    log_dir = ROOT / "perfbench_out" / CONFIG["name"] / str(SEED)
    shutil.rmtree(log_dir, ignore_errors=True)
    log_dir.mkdir(parents=True)
    c = Cluster({"config": CONFIG, "entry": {"chips": 1}}, SEED, True, log_dir, None)
    try:
        c.up()
        yield c
    finally:
        c.roles.stop_all()


def answer_and_reference(cluster, template: str, draw_seed: int):
    """(the broker's answer to one drawn query of `template`, the plain reference's rows for it)."""
    t = cluster.ds.TEMPLATES[template]
    params = t.draw(np.random.default_rng(draw_seed))
    client = Client(cluster.broker, 120_000)
    try:
        doc = client.send(t.render(params))
    finally:
        client.close()
    partials = [
        check.reference_partials(CONFIG["dataset"], SEED, i, n, CONFIG, [(template, params)])[0] for i, n in enumerate(cluster.sizes)
    ]
    return doc, refeval.finish(t.spec, refeval.merge(partials), cluster.ds.vocabs(CONFIG))


def assert_answers(cluster, template: str, draw_seed: int) -> dict:
    doc, want = answer_and_reference(cluster, template, draw_seed)
    assert not doc.get("exceptions"), doc.get("exceptions")
    assert doc["numServersQueried"] == doc["numServersResponded"] == 2 and doc["totalDocs"] == CONFIG["rows"]
    numbers = check.compare_rows(cluster.ds.TEMPLATES[template].spec, doc["resultTable"]["rows"], want)
    assert numbers == {"rows_missing_or_extra": 0, "order_violations": 0, "max_abs_diff": 0.0, "max_rel_err": 0.0}, numbers
    assert len(want) > 0
    return doc


def test_both_servers_host_every_dimension_table_whole(cluster):
    for table in tables_mod.declared(CONFIG, cluster.ds)[:-1]:
        hosted = cluster.hosted(table["name"])
        assert {sid: len(segs) for sid, segs in hosted.items()} == {"server_0": 1, "server_1": 1}, (table["name"], hosted)
    # and each says what they hold: the tables' columns on the host, gauges taken when they are read
    for url in cluster.servers.values():
        gauges = http_json(f"{url}/metrics?format=json")
        assert gauges["server.dimTableBytes"]["value"] > 0 and gauges["server.lookupOperandBytes"]["value"] == 0


@pytest.mark.parametrize("template", ["q2.1", "q3.1", "q4.2"])
def test_a_lookup_query_is_answered_by_both_servers_on_the_device_path(cluster, template):
    doc = assert_answers(cluster, template, 1)
    assert all("query.lookup_gather" in w["kernels"] for w in doc["deviceWork"].values()), doc["deviceWork"]
    assert doc["counters"]["segmentsDispatched"] == 6 and doc["counters"]["lookupMisses"] == 0
    again = assert_answers(cluster, template, 2)  # other parameters, the same operands
    assert again["counters"]["lookupOperandBuilds"] == 0 and again["counters"]["reduceRowStages"] == 0
    for sid, url in cluster.servers.items():
        assert metric_total(url, "server.deviceFallbacks") == 0, sid
        assert http_json(f"{url}/metrics?format=json")["server.lookupOperandBytes"]["value"] > 0


def test_a_server_killed_and_started_again_answers_after_its_reload(cluster):
    """Its dimension tables come back with its segments, before it is ready for a query."""
    share = cluster.share_of("server_1")
    assert all(len(segs) > 0 for segs in share.values()), share
    cluster.roles.kill("server_1")
    cluster.restart_server("server_1", 120)
    url = cluster.servers["server_1"]
    ready_doc(url, 120)
    deadline = time.monotonic() + 120
    while not all(share[t] <= set(http_json(f"{url}/segments/{t}")) for t in share):
        assert time.monotonic() < deadline, "server_1 did not get its segments back"
        time.sleep(0.2)
    ready_doc(url, 120)  # no transition in flight: every dimension table rebuilt
    deadline = time.monotonic() + 60
    while True:  # the broker may hold the dead process's connection for one more answer
        doc, _ = answer_and_reference(cluster, "q4.1", 3)
        if not doc.get("exceptions") and doc.get("numServersResponded") == 2:
            break
        assert time.monotonic() < deadline, doc.get("exceptions")
        time.sleep(0.5)
    doc = assert_answers(cluster, "q4.1", 3)
    assert all("query.lookup_gather" in w["kernels"] for w in doc["deviceWork"].values())
    assert metric_total(url, "server.deviceFallbacks") == 0
