"""A dataset of two tables, for `test_declared_tables.py`: SSB's `lineorder` as
the 17-column fact table it is, and `customer` beside it as a dimension table.

Not a dataset of the benchmark: the tests copy it into a copy's `datasets/`,
with the configuration and the traffic files beside it. It is `ssb_flat`'s
generator cut two ways. `segment()` returns the flat table's 30 columns: the
harness builds the 17 that `SCHEMA` names and ignores the rest, and the
reference of a query that reaches a customer's attribute through the
dimension table is the flat spec over the same rows. `customer()` returns the
customers `ssb_flat` joins in, by key.
"""

from __future__ import annotations

import numpy as np

from perfbench.datasets import ssb_flat as flat
from perfbench.refeval import Column, Spec, Template

TABLE = "lineorder"
SCHEMA = [row for row in flat.SCHEMA if row[0].startswith("lo_")]
CUSTOMER = [("c_custkey", "INT", "dimension"), ("c_city", "STRING", "dimension"), ("c_nation", "STRING", "dimension"),
            ("c_region", "STRING", "dimension")]  # fmt: skip

vocabs = flat.vocabs
segment = flat.segment


def customer(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    """The whole of `customer` as one segment: key, city, nation, region."""
    sz = flat.sizes(config)
    assert index == 0 and n == sz["customers"], f"customer has {sz['customers']} rows at this scale factor, not {n}"
    dim = flat._dimensions(seed, sz["customers"], sz["suppliers"], sz["parts"])
    return {
        "c_custkey": Column(np.arange(1, n + 1, dtype=np.int32)),
        "c_city": Column(dim["c_city"], flat.CITIES),
        "c_nation": Column(dim["c_nation"], flat.NATIONS),
        "c_region": Column(dim["c_region"], flat.REGIONS),
    }


TABLES = {"lineorder": {"schema": SCHEMA, "segment": segment}, "customer": {"schema": CUSTOMER, "segment": customer}}


def _draw_discounts(rng):
    d = int(rng.integers(0, 9))
    return {"d0": d, "d1": d + 2}


_REGION_OF_CUSTKEY = "lookUp('customer', 'c_region', 'c_custkey', lo_custkey)"

TEMPLATES = {
    # filter and keys on split dimensions of the declared star-tree, a plain SUM and COUNT: the star table can answer it
    "modes": Template(
        "SELECT lo_shipmode, lo_orderpriority, SUM(lo_revenue), COUNT(*) FROM lineorder WHERE lo_linenumber <= {lines} "
        "GROUP BY lo_shipmode, lo_orderpriority ORDER BY lo_shipmode, lo_orderpriority LIMIT 1000",
        lambda rng: {"lines": int(rng.integers(1, 8))},
        Spec(
            lambda c, p: flat._between(c, "lo_linenumber", 1, p["lines"]),
            keys=["lo_shipmode", "lo_orderpriority"], aggs=[("sum", flat._val("lo_revenue")), ("count", None)],
            select=["lo_shipmode", "lo_orderpriority", "agg0", "agg1"], order=[("lo_shipmode", False), ("lo_orderpriority", False)],
        ),
    ),
    # a filter on a metric: no star table holds it, the scan answers
    "discounts": Template(
        "SELECT lo_shipmode, SUM(lo_extendedprice * lo_discount) FROM lineorder WHERE lo_discount BETWEEN {d0} AND {d1} "
        "GROUP BY lo_shipmode ORDER BY lo_shipmode LIMIT 1000",
        _draw_discounts,
        Spec(
            lambda c, p: flat._between(c, "lo_discount", p["d0"], p["d1"]),
            keys=["lo_shipmode"], aggs=[("sum", flat._revenue_q1)], select=["lo_shipmode", "agg0"], order=[("lo_shipmode", False)],
        ),
    ),
    # the star join through the dimension table; its reference is the flat table's c_region
    "custregion": Template(
        f"SELECT {_REGION_OF_CUSTKEY}, SUM(lo_revenue) FROM lineorder WHERE lo_quantity < {{q}} "
        f"GROUP BY {_REGION_OF_CUSTKEY} ORDER BY {_REGION_OF_CUSTKEY} LIMIT 1000",
        lambda rng: {"q": int(rng.integers(20, 31))},
        Spec(
            lambda c, p: c["lo_quantity"].codes < p["q"],
            keys=["c_region"], aggs=[("sum", flat._val("lo_revenue"))], select=["c_region", "agg0"], order=[("c_region", False)],
        ),
    ),
}  # fmt: skip
