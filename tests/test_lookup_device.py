"""lookUp on the device, held to a plain reference of the join.

The reference is written here, in numpy, and imports nothing of
`cluster/dimension.py` or `query/plan.py`: sort the dimension's keys,
`searchsorted`, take, the destination's null where no row has the key. Every
query runs twice against it: through broker -> server -> the fused program
(no `server.deviceFallbacks` may move, the answer's `deviceWork` names
`query.lookup_gather`) and through the host executor, segment by segment.

The fact table's three segments hold different customers and products, so
their foreign-key dictionaries differ in values and in size; `customers` is
two segments with repeated keys (the later segment wins); some fact rows
have a key no dimension row has.
"""

import math

import numpy as np
import pytest

from pinot_tpu.cluster import Broker, Controller, PropertyStore, Server
from pinot_tpu.common import DataType, Schema, TableConfig
from pinot_tpu.common.metrics import ServerMeter, server_metrics
from pinot_tpu.common.types import FieldSpec
from pinot_tpu.query.engine import QueryEngine
from pinot_tpu.segment import SegmentBuilder

NATIONS = np.array(["BR", "DE", "FR", "JP", "US"], dtype=object)
REGION_OF = {"BR": "AM", "US": "AM", "DE": "EU", "FR": "EU", "JP": "AS"}


def fallbacks() -> dict[str, int]:
    """`server.deviceFallbacks` by label set: `{reason="..."}`."""
    name = ServerMeter.DEVICE_FALLBACKS.value
    return {key[len(name) :]: m["count"] for key, m in server_metrics().snapshot().items() if key.startswith(name)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def ref_lookup(dim_keys: np.ndarray, dim_values: np.ndarray, fk: np.ndarray, null):
    """dim_values[the row whose key is fk], `null` where no row has it. Of a repeated key the last row."""
    order = np.argsort(dim_keys, kind="stable")
    sk = dim_keys[order]
    at = np.searchsorted(sk, fk, side="right") - 1  # the last of equal keys: later rows win
    hit = (at >= 0) & (sk[np.maximum(at, 0)] == fk)
    out = np.empty(len(fk), dtype=object if isinstance(null, str) else np.float64)
    out[:] = null
    out[hit] = dim_values[order[at[hit]]]
    return out


def norm(v):
    """A cell as both paths and the reference agree to write it: NaN as None, whole floats as ints."""
    if isinstance(v, (float, np.floating)):
        if math.isnan(v):
            return None
        return int(v) if float(v).is_integer() else round(float(v), 9)
    if isinstance(v, (np.integer, int)):
        return int(v)
    return str(v)


def ref_rows(joined: dict, where, keys: list[str], agg: str):
    """GROUP BY `keys` over the joined columns: [key..., SUM(amount) | COUNT(*)], sorted by key."""
    mask = where(joined) if where is not None else np.ones(len(joined["amount"]), dtype=bool)
    groups: dict[tuple, int] = {}
    cols = [joined[k][mask] for k in keys]
    vals = joined["amount"][mask] if agg == "sum" else np.ones(int(mask.sum()), dtype=np.int64)
    for i in range(int(mask.sum())):
        k = tuple(norm(c[i]) for c in cols)
        groups[k] = groups.get(k, 0) + int(vals[i])
    return sorted(([*k, v] for k, v in groups.items()), key=lambda r: [(x is None, x) for x in r[:-1]])


# ---------------------------------------------------------------------------
# the cluster
# ---------------------------------------------------------------------------

CUSTOMERS = Schema.build(
    "customers",
    dimensions=[("cust_id", DataType.INT), ("nation", DataType.STRING), ("region", DataType.STRING), ("tier", DataType.INT),
                ("band", DataType.INT)],
    metrics=[("credit", DataType.LONG)],
    primary_key_columns=["cust_id"],
)
PRODUCTS = Schema.build(
    "products",
    dimensions=[("prod_id", DataType.INT), ("brand", DataType.STRING)],
    metrics=[("weight", DataType.DOUBLE)],
    primary_key_columns=["prod_id"],
)
PRICES = Schema.build(  # a composite primary key
    "prices",
    dimensions=[("prod_id", DataType.INT), ("tier", DataType.INT), ("label", DataType.STRING)],
    primary_key_columns=["prod_id", "tier"],
)


def customers_segment(ids: np.ndarray, shift: int) -> dict:
    nation = NATIONS[(ids + shift) % len(NATIONS)]
    return {
        "cust_id": ids.astype(np.int32),
        "nation": nation,
        "region": np.array([REGION_OF[n] for n in nation], dtype=object),
        "tier": (1 + (ids + shift) % 3).astype(np.int32),
        "band": (ids // 10).astype(np.int32),  # rises with the key
        "credit": ((ids + shift) * 10).astype(np.int64),
    }


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    rng = np.random.default_rng(41)
    controller = Controller(PropertyStore(), tmp_path_factory.mktemp("ds"))
    server = Server("s0")
    controller.register_server("s0", server)
    orders = Schema.build(
        "orders", dimensions=[("cust_id", DataType.INT), ("prod_id", DataType.INT), ("tier", DataType.INT)],
        metrics=[("amount", DataType.LONG), ("qty", DataType.INT)],
    )  # fmt: skip
    orders.add(FieldSpec("cust_ids", DataType.INT, single_value=False))
    for schema, dim in ((CUSTOMERS, True), (PRODUCTS, True), (PRICES, True), (orders, False)):
        controller.add_schema(schema)
        cfg = TableConfig(schema.name)
        if dim:
            cfg.extra = {"isDimTable": True}
        controller.add_table(cfg)
    # customers: two segments; keys 5, 6 and 7 come again in the second, which wins
    dims = {"customers": [customers_segment(np.arange(1, 41), 0), customers_segment(np.array([5, 6, 7, 41, 42, 43, 44, 45]), 2)]}
    pid = np.arange(100, 131)
    dims["products"] = [{
        "prod_id": pid.astype(np.int32),
        "brand": np.array([f"brand#{i % 7}" for i in pid], dtype=object),
        "weight": (pid % 11).astype(np.float64) + 0.5,
    }]  # fmt: skip
    pp, tt = np.meshgrid(np.arange(100, 110), np.arange(1, 4), indexing="ij")
    dims["prices"] = [{
        "prod_id": pp.ravel().astype(np.int32), "tier": tt.ravel().astype(np.int32),
        "label": np.array([f"p{p}t{t}" for p, t in zip(pp.ravel(), tt.ravel())], dtype=object),
    }]  # fmt: skip
    for table, schema in (("customers", CUSTOMERS), ("products", PRODUCTS), ("prices", PRICES)):
        for i, data in enumerate(dims[table]):
            controller.upload_segment(table, SegmentBuilder(schema).build(data, f"{table}_{i}"))
    # orders: three segments over different customers and products; ids past 45 / 130 have no dimension row
    facts = []
    for s, (n, c_lo, c_hi, p_lo, p_hi) in enumerate(((300, 1, 30, 100, 120), (180, 20, 50, 110, 135), (240, 35, 60, 100, 135))):
        data = {
            "cust_id": rng.integers(c_lo, c_hi + 1, n).astype(np.int32),
            "prod_id": rng.integers(p_lo, p_hi + 1, n).astype(np.int32),
            "tier": rng.integers(1, 4, n).astype(np.int32),
            "amount": rng.integers(1, 1000, n).astype(np.int64),
            "qty": rng.integers(1, 46, n).astype(np.int32),
        }
        mv = np.empty(n, dtype=object)
        for i in range(n):
            mv[i] = [int(data["cust_id"][i])]
        data["cust_ids"] = mv
        controller.upload_segment("orders", SegmentBuilder(orders).build(data, f"orders_{s}"))
        facts.append(data)
    fact = {k: np.concatenate([f[k] for f in facts]) for k in facts[0] if k != "cust_ids"}
    return {"controller": controller, "server": server, "broker": Broker(controller), "fact": fact, "dims": dims, "orders": orders}


def joined_columns(star) -> dict:
    """The fact table with every attribute joined in by the plain reference."""
    fact, dims = star["fact"], star["dims"]
    cust = {k: np.concatenate([d[k] for d in dims["customers"]]) for k in dims["customers"][0]}
    prod = dims["products"][0]
    out = dict(fact)
    for dest, null in (("nation", "null"), ("region", "null"), ("tier", np.nan), ("band", np.nan), ("credit", np.nan)):
        out[f"c.{dest}"] = ref_lookup(cust["cust_id"], cust[dest], fact["cust_id"], null)
    for dest, null in (("brand", "null"), ("weight", np.nan)):
        out[f"p.{dest}"] = ref_lookup(prod["prod_id"], prod[dest], fact["prod_id"], null)
    return out


def C(dest: str) -> str:
    return f"lookUp('customers', '{dest}', 'cust_id', cust_id)"


def P(dest: str) -> str:
    return f"lookUp('products', '{dest}', 'prod_id', prod_id)"


def _nan_lt(j, col, x):
    with np.errstate(invalid="ignore"):
        return j[col] < x


#: name -> (WHERE in SQL | None, the same over the joined columns, [(key in SQL, joined column)], "sum" | "count")
CASES = {
    "group-string": (None, None, [(C("nation"), "c.nation")], "sum"),
    "group-numeric-metric": (None, None, [(C("credit"), "c.credit")], "sum"),
    "group-numeric-dimension": (None, None, [(C("tier"), "c.tier")], "count"),
    "group-two-tables": (None, None, [(C("region"), "c.region"), (P("brand"), "p.brand")], "sum"),
    "group-double": (None, None, [(P("weight"), "p.weight")], "count"),
    # `band` rises with the key, and is gathered as any other destination is
    "group-rising": (None, None, [(C("band"), "c.band"), (C("nation"), "c.nation")], "sum"),
    "filter-rising": (f"{C('band')} BETWEEN 1 AND 2 AND {C('region')} <> 'AS'", lambda j: (j["c.band"] >= 1) & (j["c.band"] <= 2) & (j["c.region"] != "AS"), [], "count"),
    "filter-eq": (f"{C('nation')} = 'FR'", lambda j: j["c.nation"] == "FR", [], "sum"),
    "filter-neq": (f"{C('nation')} <> 'FR'", lambda j: j["c.nation"] != "FR", [], "sum"),
    "filter-in-one-run": (f"{C('nation')} IN ('FR', 'DE')", lambda j: np.isin(j["c.nation"], ["FR", "DE"]), [], "count"),
    "filter-in-two-runs": (f"{C('nation')} IN ('BR', 'JP')", lambda j: np.isin(j["c.nation"], ["BR", "JP"]), [], "sum"),
    "filter-not-in": (f"{C('nation')} NOT IN ('BR', 'JP')", lambda j: ~np.isin(j["c.nation"], ["BR", "JP"]), [], "count"),
    "filter-between-numeric": (f"{C('credit')} BETWEEN 100 AND 250", lambda j: (j["c.credit"] >= 100) & (j["c.credit"] <= 250), [], "sum"),
    "filter-between-string": (f"{C('nation')} BETWEEN 'DE' AND 'JP'", lambda j: (j["c.nation"] >= "DE") & (j["c.nation"] <= "JP"), [], "sum"),
    "filter-gt-numeric": (f"{C('credit')} > 200", lambda j: j["c.credit"] > 200, [], "count"),
    "filter-lt-double": (f"{P('weight')} < 4", lambda j: _nan_lt(j, "p.weight", 4), [], "sum"),
    "filter-lt-string": (f"{C('nation')} < 'FR'", lambda j: j["c.nation"] < "FR", [], "count"),
    "filter-like": (f"{P('brand')} LIKE 'brand#1%'", lambda j: np.array([str(b).startswith("brand#1") for b in j["p.brand"]]), [], "count"),
    "filter-the-null-itself": (f"{C('nation')} = 'null'", lambda j: j["c.nation"] == "null", [], "count"),
    "filter-and-group": (
        f"{C('region')} = 'EU' AND {P('weight')} < 8 AND qty < 40",
        lambda j: (j["c.region"] == "EU") & _nan_lt(j, "p.weight", 8) & (j["qty"] < 40),
        [(C("nation"), "c.nation"), (C("tier"), "c.tier")], "sum",
    ),
    "filter-or-and-group-same-lookup": (
        f"({C('tier')} = 1 OR {C('tier')} = 3) AND {C('nation')} <> 'US'",
        lambda j: ((j["c.tier"] == 1) | (j["c.tier"] == 3)) & (j["c.nation"] != "US"),
        [(C("tier"), "c.tier"), (P("brand"), "p.brand")], "count",
    ),
}  # fmt: skip


def sql_of(case) -> str:
    where, _, keys, agg = case
    agg_sql = "SUM(amount)" if agg == "sum" else "COUNT(*)"
    key_sql = ", ".join(k for k, _ in keys)
    return (
        f"SELECT {key_sql + ', ' if keys else ''}{agg_sql} FROM orders"
        + (f" WHERE {where}" if where else "")
        + (f" GROUP BY {key_sql} ORDER BY {key_sql} LIMIT 10000" if keys else "")
    )


def want_of(star, case) -> list[list]:
    _, where, keys, agg = case
    rows = ref_rows(joined_columns(star), where, [col for _, col in keys], agg)
    return rows or [[0]]  # no GROUP BY, no match: one row


def rows_of(result_rows) -> list[list]:
    rows = [[norm(v) for v in r] for r in result_rows]
    return sorted(rows, key=lambda r: [(x is None, x) for x in r[:-1]])


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_device_path_answers_as_the_plain_reference(star, name):
    before = fallbacks()
    doc = star["broker"].execute(sql_of(CASES[name])).to_dict()
    assert rows_of(doc["resultTable"]["rows"]) == want_of(star, CASES[name])
    assert fallbacks() == before, "a segment left the device path"
    # one program for the three segments, whose foreign-key dictionaries differ; its gathers are named, one a foreign key and launch
    (work,) = works = list(doc["deviceWork"].values())
    assert work["launches"] == 3
    foreign_keys = len({fk for fk in ("cust_id", "prod_id") if f", {fk})" in sql_of(CASES[name])})
    assert sum(w["kernels"]["query.lookup_gather"]["calls"] for w in works) == 3 * foreign_keys
    assert doc["counters"]["reduceRowStages"] == 0


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_host_path_answers_as_the_plain_reference(star, name):
    server = star["server"]
    segs = [server.get_segment_object("orders", s) for s in server.segments_of("orders")]
    eng = QueryEngine(segs)
    ctx = eng.make_context(sql_of(CASES[name]))
    with server.dim_tables.serving():
        partials = [eng._host_segment(seg, ctx)[0] for seg in segs]
    assert rows_of(QueryEngine.reduce(ctx, partials)) == want_of(star, CASES[name])


def test_rows_without_a_dimension_row_are_counted_and_kept(star):
    """A key without a row gives the destination's null and its own bucket, never a dropped fact row."""
    doc = star["broker"].execute(f"SELECT {C('nation')}, COUNT(*) FROM orders GROUP BY {C('nation')} LIMIT 100").to_dict()
    fact = star["fact"]
    missing = int((fact["cust_id"] > 45).sum())
    assert missing > 0 and doc["counters"]["lookupMisses"] == missing
    by_key = {r[0]: r[1] for r in doc["resultTable"]["rows"]}
    assert by_key["null"] == missing and sum(by_key.values()) == len(fact["cust_id"])
    # two lookUps through one foreign key count a row's miss once; a second table's misses add
    both = star["broker"].execute(
        f"SELECT {C('nation')}, {C('tier')}, {P('brand')}, COUNT(*) FROM orders GROUP BY {C('nation')}, {C('tier')}, {P('brand')} LIMIT 10000"
    ).to_dict()
    assert both["counters"]["lookupMisses"] == missing + int((fact["prod_id"] > 130).sum())


def test_every_segment_shares_one_dense_group_space(star):
    """The key's buckets are the destination's dictionary and one for misses, whatever a segment's foreign keys are."""
    from pinot_tpu.query.kernels import program_name
    from pinot_tpu.query.plan import plan_segment

    server = star["server"]
    eng = QueryEngine([server.get_segment_object("orders", s) for s in server.segments_of("orders")])
    ctx = eng.make_context(sql_of(CASES["group-two-tables"]))
    with server.dim_tables.serving():
        plans = [plan_segment(seg, ctx) for seg in eng.segments]
    assert len({p.spec for p in plans}) == 1
    # the group spec and the program's name as PR 45's parent gave them: far under plan.COMPACT_MIN_GROUPS, the plan it always had
    region, brand = ("lookup", "cust_id", 0, 1, 2, 3, True), ("lookup", "prod_id", 4, 5, 6, 7, True)
    assert plans[0].spec[2] == ("groups", (("lookup_key", region), ("lookup_key", brand)), 256, 8)
    assert program_name(plans[0].spec) == "seg_groupby_8444e3b6"
    cards = [[ci.cardinality for _, ci in p.group_cols] for p in plans]
    assert cards == [[3 + 1, 7 + 1]] * 3  # regions AM, AS, EU; brands #0..#6
    assert [len({ci.cardinality for ci in (seg.columns["cust_id"], seg.columns["prod_id"])}) for seg in eng.segments] != [1, 1, 1]


def test_the_destinations_of_a_table_share_one_gathered_word(star):
    """Every attribute's code is a bit field of the operand a foreign key is gathered through."""
    server = star["server"]
    dim = server.dim_tables.get("customers")
    first = server.get_segment_object("orders", "orders_0").columns["cust_id"].dictionary
    fk = np.asarray(first.values)
    words = {dim.field(c)[0] for c in ("nation", "region", "tier", "band")}
    assert words == {0} and len({dim.field(c)[1] for c in ("nation", "region", "tier", "band")}) == 4
    operand, built = dim.operand(first, 0)
    assert dim.operand(first, 0) == (operand, False) or not built
    for dest in ("nation", "region", "tier", "band"):
        _, shift, mask = dim.field(dest)
        got = dim.decode_table(dest)[(operand[: len(fk)] >> shift) & mask]
        assert list(got) == list(dim.lookup_column(dest, [fk]))
        assert set((operand[len(fk) :] >> shift) & mask) <= {len(dim.dest_values(dest))}  # the padding reads as a miss


def test_the_steady_state_builds_and_ships_no_operand(star):
    sql = sql_of(CASES["filter-and-group"])
    star["broker"].execute(sql)
    doc = star["broker"].execute(sql).to_dict()  # computed again: an answer that read a dimension table is not cached
    assert doc["counters"]["lookupOperandBuilds"] == 0 and doc["counters"]["lookupOperandBytesStaged"] == 0
    # the operands are staged once: a launch's own transfer and nothing more
    assert doc["counters"]["hostToDeviceTransfers"] == doc["counters"]["segmentsDispatched"] == 3
    assert "server.plan.lookup" in doc["spanTimesMs"]
    tables, operands = star["server"].dim_tables.resident_bytes()
    assert tables > 0 and operands > 0


FALLBACKS = {
    "lookup_raw_key": f"SELECT lookUp('customers', 'nation', 'cust_id', qty), COUNT(*) FROM orders GROUP BY lookUp('customers', 'nation', 'cust_id', qty) LIMIT 100",
    "lookup_mv_key": "SELECT COUNT(*) FROM orders WHERE lookUp('customers', 'nation', 'cust_id', cust_ids) = 'FR'",
    "lookup_composite_key": "SELECT lookUp('prices', 'label', 'prod_id', prod_id, 'tier', tier), COUNT(*) FROM orders "
                            "GROUP BY lookUp('prices', 'label', 'prod_id', prod_id, 'tier', tier) LIMIT 1000",
    "lookup_key_expression": "SELECT COUNT(*) FROM orders WHERE lookUp('customers', 'nation', 'cust_id', cust_id + 0) = 'FR'",
    "lookup_in_value": f"SELECT SUM({C('credit')}) FROM orders WHERE cust_id <= 45",
    "lookup_unknown_column": "SELECT COUNT(*) FROM orders WHERE lookUp('customers', 'planet', 'cust_id', cust_id) = 'FR'",
}  # fmt: skip


@pytest.mark.parametrize("reason", sorted(FALLBACKS))
def test_any_other_form_falls_back_under_a_reason_of_its_own(star, reason):
    before = fallbacks().get(f'{{reason="{reason}"}}', 0)
    if reason == "lookup_mv_key":  # which the host refuses by name: no lookUp has a row a value
        with pytest.raises(Exception, match="lookUp by a multi-value column is not supported"):
            star["broker"].execute(FALLBACKS[reason])
        assert fallbacks().get(f'{{reason="{reason}"}}', 0) > before
        return
    rows = star["broker"].execute(FALLBACKS[reason]).to_dict()["resultTable"]["rows"]
    assert fallbacks().get(f'{{reason="{reason}"}}', 0) == before + 3, fallbacks()
    fact, j = star["fact"], joined_columns(star)
    if reason == "lookup_raw_key":
        cust = {k: np.concatenate([d[k] for d in star["dims"]["customers"]]) for k in ("cust_id", "nation")}
        by_qty = ref_lookup(cust["cust_id"], cust["nation"], fact["qty"], "null")
        want = sorted([str(n), int((by_qty == n).sum())] for n in set(by_qty))
        assert sorted(rows) == want
    elif reason == "lookup_key_expression":
        assert rows == [[int((j["c.nation"] == "FR").sum())]]
    elif reason == "lookup_composite_key":
        prices = star["dims"]["prices"][0]
        label = {(int(p), int(t)): l for p, t, l in zip(prices["prod_id"], prices["tier"], prices["label"])}
        want: dict[str, int] = {}
        for p, t in zip(fact["prod_id"], fact["tier"]):
            k = label.get((int(p), int(t)), "null")
            want[k] = want.get(k, 0) + 1
        assert sorted(rows) == sorted([k, v] for k, v in want.items())
    elif reason == "lookup_in_value":
        assert rows == [[float(np.nansum(j["c.credit"][fact["cust_id"] <= 45]))]]
    else:  # a column the table lacks: every row the null substitute (a number's NaN equals nothing)
        assert rows == [[0]]


def test_a_replaced_dimension_segment_is_read_by_the_next_answer(star):
    """The table gets a new generation; operands of the old one are dropped, and built once more, once."""
    controller, broker, server = star["controller"], star["broker"], star["server"]
    sql = f"SELECT {C('nation')}, COUNT(*) FROM orders WHERE cust_id = 44 GROUP BY {C('nation')} LIMIT 10"
    (before,) = broker.execute(sql).to_dict()["resultTable"]["rows"]
    generation = server.dim_tables.get("customers").generation
    moved = customers_segment(np.array([5, 6, 7, 41, 42, 43, 44, 45]), 3)  # every one of them moves nation
    controller.upload_segment("customers", SegmentBuilder(CUSTOMERS).build(moved, "customers_1"))
    star["dims"]["customers"][1] = moved
    assert server.dim_tables.get("customers").generation > generation
    first = broker.execute(sql).to_dict()
    (after,) = first["resultTable"]["rows"]
    assert after[0] == moved["nation"][6] != before[0] and after[1] == before[1]
    # one a launched segment of the fact table (the first segment's customers end at 30: pruned by value)
    assert first["counters"]["lookupOperandBuilds"] == first["counters"]["segmentsDispatched"] == 2
    again = broker.execute(sql).to_dict()
    assert again["counters"]["lookupOperandBuilds"] == 0 and again["resultTable"]["rows"] == [after]
    assert again["counters"]["segmentsDispatched"] == 2
    # and the whole table reads as the reference over the new rows
    doc = broker.execute(sql_of(CASES["group-string"])).to_dict()
    assert rows_of(doc["resultTable"]["rows"]) == want_of(star, CASES["group-string"])


def test_a_dropped_dimension_segment_takes_its_rows_along(star):
    controller, broker, server = star["controller"], star["broker"], star["server"]
    size = server.dim_tables.get("customers").size
    controller.delete_segment("customers", "customers_1")
    assert server.dim_tables.get("customers").size == size - 5  # 41..45 go; 5, 6 and 7 fall back to the first segment's rows
    star["dims"]["customers"].pop()
    doc = broker.execute(sql_of(CASES["group-string"])).to_dict()
    assert rows_of(doc["resultTable"]["rows"]) == want_of(star, CASES["group-string"])


def test_a_server_that_hosts_no_segment_of_a_dimension_table_forgets_it(tmp_path):
    controller, server = Controller(PropertyStore(), tmp_path), Server("s0")
    controller.register_server("s0", server)
    controller.add_schema(PRODUCTS)
    cfg = TableConfig("products")
    cfg.extra = {"isDimTable": True}
    controller.add_table(cfg)
    data = {"prod_id": np.arange(3, dtype=np.int32), "brand": np.array(["a", "b", "c"], dtype=object), "weight": np.ones(3)}
    controller.upload_segment("products", SegmentBuilder(PRODUCTS).build(data, "products_0"))
    assert server.dim_tables.get("products").size == 3 and server.dim_tables.resident_bytes()[0] > 0
    controller.delete_segment("products", "products_0")
    assert server.dim_tables.tables() == {} and server._dim_keys == {} and server.dim_tables.resident_bytes() == (0, 0)
    with pytest.raises(KeyError, match="no dimension table 'products' loaded"):
        server.dim_tables.get("products")


@pytest.mark.parametrize("dests, gathers", [(("a", "b"), 1), (("a", "c"), 2), (("c", "a"), 2)])
def test_a_wide_dimension_table_is_gathered_a_word_at_a_time(tmp_path, dests, gathers):
    """Three attributes of 2000 values are 11 bits each: two share the first 31-bit word of the operand, the
    third has a word of its own, and a query that reads both words gathers its foreign key twice a launch."""
    controller, server = Controller(PropertyStore(), tmp_path), Server("s0")
    controller.register_server("s0", server)
    wide = Schema.build("wide", dimensions=[("id", DataType.INT)] + [(c, DataType.STRING) for c in "abc"], primary_key_columns=["id"])
    facts = Schema.build("facts", dimensions=[("wide_id", DataType.INT)], metrics=[("amount", DataType.LONG)])
    for schema, extra in ((wide, {"isDimTable": True}), (facts, {})):
        controller.add_schema(schema)
        cfg = TableConfig(schema.name)
        cfg.extra = extra
        controller.add_table(cfg)
    rng = np.random.default_rng(43)
    ids = np.arange(1, 3001)
    dim = {"id": ids.astype(np.int32), **{c: np.array([f"{c}{(i * m) % 2000:04d}" for i in ids], dtype=object) for c, m in zip("abc", (1, 7, 11))}}
    controller.upload_segment("wide", SegmentBuilder(wide).build(dim, "wide_0"))
    fact = {"wide_id": rng.integers(1, 3200, 4000).astype(np.int32), "amount": rng.integers(1, 100, 4000).astype(np.int64)}
    controller.upload_segment("facts", SegmentBuilder(facts).build(fact, "facts_0"))
    mgr = server.dim_tables.get("wide")
    assert [mgr.field(c)[0] for c in "abc"] == [0, 0, 1]

    def W(dest):
        return f"lookUp('wide', '{dest}', 'id', wide_id)"

    key, filtered = dests
    before = fallbacks()
    doc = Broker(controller).execute(
        f"SELECT {W(key)}, SUM(amount) FROM facts WHERE {W(filtered)} < '{filtered}0100' GROUP BY {W(key)} ORDER BY {W(key)} LIMIT 5000"
    ).to_dict()
    assert fallbacks() == before
    joined = {c: ref_lookup(dim["id"], dim[c], fact["wide_id"], "null") for c in dests}
    mask = joined[filtered] < f"{filtered}0100"
    want: dict[str, int] = {}
    for k, v in zip(joined[key][mask], fact["amount"][mask]):
        want[k] = want.get(k, 0) + int(v)
    assert doc["resultTable"]["rows"] == [[k, want[k]] for k in sorted(want)]
    (work,) = doc["deviceWork"].values()
    assert work["kernels"]["query.lookup_gather"]["calls"] == gathers
    assert doc["counters"]["lookupOperandBuilds"] == gathers and doc["counters"]["lookupMisses"] == int((fact["wide_id"] > 3000).sum())


def test_a_blocked_gather_is_counted_once_over_the_segments_rows(star, monkeypatch):
    """The gather walks a segment's codes in blocks (four a segment here); a
    launch's `deviceWork` still names `query.lookup_gather` once a (foreign
    key, word), over the segment's padded rows and not a block's."""
    from pinot_tpu.query import kernels
    from pinot_tpu.segment.segment import padded_len

    monkeypatch.setattr(kernels, "_GATHER_BLOCK", 256)
    kernels.get_packed_kernel.cache_clear()  # the programs are traced afresh, under the small block
    try:
        doc = star["broker"].execute(sql_of(CASES["group-two-tables"])).to_dict()
    finally:
        kernels.get_packed_kernel.cache_clear()
    assert rows_of(doc["resultTable"]["rows"]) == want_of(star, CASES["group-two-tables"])
    server, rows, moved = star["server"], 0, 0.0
    for s in range(3):
        seg = server.get_segment_object("orders", f"orders_{s}")
        assert padded_len(seg.n_docs) == 4 * kernels._GATHER_BLOCK
        rows += padded_len(seg.n_docs)
        for dim, fk in (("customers", "cust_id"), ("products", "prod_id")):
            operand, _ = server.dim_tables.get(dim).operand(seg.columns[fk].dictionary, 0)
            moved += padded_len(seg.n_docs) * 8 + len(operand) * 4
    (work,) = doc["deviceWork"].values()
    assert (work["launches"], work["rows"]) == (3, rows)
    assert work["kernels"]["query.lookup_gather"] == {"calls": 3 * 2, "bytes": moved, "flops": 0.0}


# ---------------------------------------------------------------------------
# the table as columns
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("keys", ["dense-int", "sparse-int", "string", "composite"])
def test_a_lookup_of_an_array_of_keys_is_the_reference(keys):
    from pinot_tpu.cluster.dimension import DimensionTableDataManager

    rng = np.random.default_rng(7)
    n = 500
    if keys == "dense-int":
        k = [np.arange(1, n + 1, dtype=np.int32)]
    elif keys == "sparse-int":
        k = [np.sort(rng.choice(10 * n, n, replace=False)).astype(np.int64)]
    elif keys == "string":
        k = [np.array([f"k{i:04d}" for i in rng.choice(10 * n, n, replace=False)], dtype=object)]
    else:
        k = [rng.integers(0, 30, n).astype(np.int32), np.array([f"b{i}" for i in rng.integers(0, 30, n)], dtype=object)]
    names = [f"k{i}" for i in range(len(k))]
    name_col = np.array([f"name{i % 37}" for i in range(n)], dtype=object)
    score = rng.integers(0, 1000, n).astype(np.int64)
    schema = Schema.build(
        "d", dimensions=[(c, DataType.STRING if a.dtype == object else DataType.INT) for c, a in zip(names, k)] + [("name", DataType.STRING)],
        metrics=[("score", DataType.LONG)], primary_key_columns=names,
    )  # fmt: skip
    half = n // 2
    segs = [
        SegmentBuilder(schema).build({**{c: a[s] for c, a in zip(names, k)}, "name": name_col[s], "score": score[s]}, f"d_{i}")
        for i, s in enumerate((slice(0, half), slice(half, n)))
    ]
    m = DimensionTableDataManager("d", names, schema=schema)
    m.load_segments(segs)
    # probes: every key, and as many that are not there
    if keys == "composite":
        probe = [np.concatenate([k[0], k[0] + 100]), np.concatenate([k[1], k[1]])]
        combined = np.array([f"{a}|{b}" for a, b in zip(*k)], dtype=object)
        probe_combined = np.array([f"{a}|{b}" for a, b in zip(*probe)], dtype=object)
    else:
        absent = np.array([f"zz{i}" for i in range(n)], dtype=object) if keys == "string" else k[0].astype(np.int64) + 100_000
        probe = [np.concatenate([k[0], absent])]
        combined, probe_combined = k[0], probe[0]
    assert list(m.lookup_column("name", probe)) == list(ref_lookup(combined, name_col, probe_combined, "null"))
    got, want = m.lookup_column("score", probe), ref_lookup(combined, score, probe_combined, np.nan)
    assert np.array_equal(got, want, equal_nan=True) and got.dtype == np.float64
    assert m.size == len(set(combined.tolist()))
