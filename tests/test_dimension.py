"""Dimension tables + lookUp() UDF.

Reference test model: DimensionTableDataManager tests +
LookupTransformFunctionTest (SURVEY.md §2.4 InstanceDataManager row).
"""

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.cluster.dimension import DimensionTableDataManager
from pinot_tpu.common import DataType, Schema, TableConfig
from pinot_tpu.segment import SegmentBuilder


@pytest.fixture
def cluster(tmp_path):
    controller = Controller(PropertyStore(), tmp_path / "ds")
    controller.register_server("s0", Server("s0"))
    # fact table
    fact_schema = Schema.build(
        "orders", dimensions=[("cust_id", DataType.INT)], metrics=[("amount", DataType.LONG)]
    )
    controller.add_schema(fact_schema)
    controller.add_table(TableConfig("orders"))
    controller.upload_segment(
        "orders",
        SegmentBuilder(fact_schema).build(
            {"cust_id": np.array([1, 2, 3, 1, 9], dtype=np.int32), "amount": np.array([10, 20, 30, 40, 50], dtype=np.int64)},
            "orders_0",
        ),
    )
    # dimension table
    dim_schema = Schema.build(
        "customers",
        dimensions=[("cust_id", DataType.INT), ("nation", DataType.STRING)],
        metrics=[("credit", DataType.LONG)],
        primary_key_columns=["cust_id"],
    )
    controller.add_schema(dim_schema)
    dim_cfg = TableConfig("customers")
    dim_cfg.extra = {"isDimTable": True}
    controller.add_table(dim_cfg)
    controller.upload_segment(
        "customers",
        SegmentBuilder(dim_schema).build(
            {
                "cust_id": np.array([1, 2, 3], dtype=np.int32),
                "nation": np.array(["US", "FR", "JP"], dtype=object),
                "credit": np.array([100, 200, 300], dtype=np.int64),
            },
            "customers_0",
        ),
    )
    return controller


def test_dim_table_registered_and_refreshed(cluster):
    # the table's manager is the hosting server's own, rebuilt from the segments it hosts
    server = cluster.servers()["s0"]
    dim = server.dim_tables.get("customers")
    assert dim.size == 3
    assert dim.lookup((2,))["nation"] == "FR"
    # refresh on new upload: later rows win per PK
    dim_schema = cluster.get_schema("customers")
    cluster.upload_segment(
        "customers",
        SegmentBuilder(dim_schema).build(
            {
                "cust_id": np.array([2, 4], dtype=np.int32),
                "nation": np.array(["DE", "BR"], dtype=object),
                "credit": np.array([250, 400], dtype=np.int64),
            },
            "customers_1",
        ),
    )
    assert server.dim_tables.get("customers").generation > dim.generation
    dim = server.dim_tables.get("customers")
    assert dim.size == 4
    assert dim.lookup((2,))["nation"] == "DE"


def test_lookup_udf_in_selection_and_groupby(cluster):
    broker = Broker(cluster)
    res = broker.execute(
        "SELECT cust_id, LOOKUP('customers', 'nation', 'cust_id', cust_id), amount FROM orders LIMIT 10"
    )
    by_cust = {r[0]: r[1] for r in res.rows}
    assert by_cust[1] == "US" and by_cust[2] == "FR" and by_cust[9] == "null"  # miss -> null
    # numeric lookup inside an aggregation
    res = broker.execute("SELECT SUM(LOOKUP('customers', 'credit', 'cust_id', cust_id)) FROM orders WHERE cust_id <= 3")
    assert res.rows[0][0] == 100 + 200 + 300 + 100


def test_lookup_unknown_dim_table_raises(cluster):
    broker = Broker(cluster)
    with pytest.raises(Exception, match="no dimension table"):
        broker.execute("SELECT LOOKUP('nope', 'x', 'cust_id', cust_id) FROM orders LIMIT 1")


def test_lookup_wrong_pk_raises(cluster):
    broker = Broker(cluster)
    with pytest.raises(Exception, match="must match dim table PK"):
        broker.execute("SELECT LOOKUP('customers', 'nation', 'amount', amount) FROM orders LIMIT 1")


def test_dim_manager_direct():
    m = DimensionTableDataManager("d", ["k"])

    class FakeSeg:
        n_docs = 2

        class _CI:
            def __init__(self, vals):
                self._v = np.asarray(vals)

            def materialize(self):
                return self._v

        columns = {"k": _CI(["a", "b"]), "v": _CI([1.5, 2.5])}

    m.load_segments([FakeSeg()])
    assert m.lookup(("a",))["v"] == 1.5
    out = m.lookup_column("v", [("a",), ("zz",), ("b",)])
    assert out[0] == 1.5 and np.isnan(out[1]) and out[2] == 2.5


def test_lookup_column_all_miss_string_stays_string():
    """String-ness comes from the table schema, not per-batch hit values: an
    all-miss batch on a string column must return 'null' strings, not NaNs."""
    m = DimensionTableDataManager("d", ["k"])

    class FakeSeg:
        n_docs = 2

        class _CI:
            def __init__(self, vals):
                self._v = np.asarray(vals)

            def materialize(self):
                return self._v

        columns = {"k": _CI(["a", "b"]), "name": _CI(["x", "y"])}

    m.load_segments([FakeSeg()])
    out = m.lookup_column("name", [("zz",), ("zw",)])
    assert list(out) == ["null", "null"]


def test_lookup_column_schema_string_before_any_segment_load():
    """Schema-declared string columns return 'null' strings on all-miss
    lookups even when ZERO segments are loaded."""
    from pinot_tpu.common import DataType, Schema

    schema = Schema.build(
        "d", dimensions=[("k", DataType.STRING), ("name", DataType.STRING)],
        metrics=[("v", DataType.DOUBLE)], primary_key_columns=["k"],
    )
    m = DimensionTableDataManager("d", ["k"], schema=schema)
    out = m.lookup_column("name", [("zz",)])
    assert list(out) == ["null"]
    out = m.lookup_column("v", [("zz",)])
    assert np.isnan(out[0])
