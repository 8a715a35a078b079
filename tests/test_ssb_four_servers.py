"""A table spread over four servers answers as one server's does, and as the
plain reference: all 13 SSB templates (`perfbench.datasets.ssb_flat`) over the
same seeded table, through broker → scatter → four servers → gather, in
process. The benchmark's rehearsal (`perfbench/tests`) covers the six
templates of `ssb4-groupby-closed` over HTTP; this covers the flights it
leaves out, and the counters the gather adds (ISSUE 27)."""

import numpy as np
import pytest

from perfbench import check, datagen, refeval
from perfbench.datasets import ssb_flat as ds
from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.common import TableConfig
from pinot_tpu.common.config import CacheConfig

CFG = {"scaleFactor": 1}
SEED, SEGMENTS, ROWS = 2_700_000_011, 8, 6_000


@pytest.fixture(scope="module")
def table():
    return [ds.segment(SEED, i, ROWS, CFG) for i in range(SEGMENTS)]


def _cluster(tmp, table, n_servers):
    controller = Controller(PropertyStore(), tmp)
    for i in range(n_servers):
        controller.register_server(f"server_{i}", Server(f"server_{i}"))
    controller.add_schema(datagen.program_schema(ds))
    controller.add_table(TableConfig(ds.TABLE, replication=1))
    for i, cols in enumerate(table):
        controller.upload_segment(ds.TABLE, datagen.build_segment(ds, cols, f"{ds.TABLE}_{i}"))
    return controller, Broker(controller, cache_config=CacheConfig(enabled=False))


@pytest.fixture(scope="module")
def four(tmp_path_factory, table):
    return _cluster(tmp_path_factory.mktemp("ssb4"), table, 4)


@pytest.fixture(scope="module")
def one(tmp_path_factory, table):
    return _cluster(tmp_path_factory.mktemp("ssb1"), table, 1)


def test_the_table_is_spread_evenly(four):
    controller, _ = four
    per_server = {}
    for replicas in controller.ideal_state(ds.TABLE).values():
        (sid,) = replicas
        per_server[sid] = per_server.get(sid, 0) + 1
    assert per_server == {f"server_{i}": SEGMENTS // 4 for i in range(4)}


@pytest.mark.parametrize("name", sorted(ds.TEMPLATES))
def test_four_servers_answer_as_one_and_as_the_reference(four, one, table, name):
    template = ds.TEMPLATES[name]
    params = template.draw(np.random.default_rng([SEED, sorted(ds.TEMPLATES).index(name)]))
    sql = template.render(params)
    doc4, doc1 = four[1].execute(sql).to_dict(), one[1].execute(sql).to_dict()
    for doc, servers in ((doc4, 4), (doc1, 1)):
        assert not doc.get("exceptions"), doc.get("exceptions")
        assert doc["totalDocs"] == SEGMENTS * ROWS
        assert doc["numServersQueried"] == doc["numServersResponded"] == doc["counters"]["serversMerged"] == servers
    want = refeval.finish(
        template.spec, refeval.merge([refeval.partial(template.spec, params, cols) for cols in table]), ds.vocabs(CFG)
    )
    for doc in (doc4, doc1):
        numbers = check.compare_rows(template.spec, doc["resultTable"]["rows"], want)
        ok, lines, _ = check.judge(numbers, template.spec.exact, 0.0)
        assert ok, lines
    assert doc4["resultTable"]["rows"] == doc1["resultTable"]["rows"]
    assert doc4["counters"]["scatterSkewMs"] > 0 and doc1["counters"]["scatterSkewMs"] == 0
    # the servers' spans are one server's, whole: its wait lies inside its execution
    assert doc4["spanTimesMs"]["server.execute"] >= doc4["spanTimesMs"]["server.device_wait"]


def test_the_gathers_counters_add_up_over_the_servers(four):
    doc = four[1].execute(f"SELECT COUNT(*) FROM {ds.TABLE}").to_dict()
    assert doc["resultTable"]["rows"] == [[SEGMENTS * ROWS]]
    assert doc["numServersResponded"] == 4 and doc["counters"]["serversMerged"] == 4
    assert doc["counters"]["segmentsDispatched"] == SEGMENTS
