"""Star Schema Benchmark, `lineorder` joined flat with the attributes of
`date`, `customer`, `supplier` and `part` that its 13 queries touch.

Source: O'Neil, O'Neil, Chen, "Star Schema Benchmark", rev. 3 (2009), dbgen's
rules for cardinalities and value ranges. Money is in cents, as dbgen's
integers are. What is assumed, not the source's: see the configuration file.
"""

from __future__ import annotations

import functools

import numpy as np

from perfbench.datasets._dbgen import order_lines, retail_price_cents
from perfbench.refeval import Column, Spec, Template, code_of, code_range

TABLE = "lineorder"

SCHEMA = [
    ("lo_orderkey", "LONG", "dimension"),
    ("lo_linenumber", "INT", "dimension"),
    ("lo_custkey", "INT", "dimension"),
    ("lo_partkey", "INT", "dimension"),
    ("lo_suppkey", "INT", "dimension"),
    ("lo_orderdate", "INT", "dimension"),
    ("lo_orderpriority", "STRING", "dimension"),
    ("lo_shippriority", "INT", "dimension"),
    ("lo_quantity", "INT", "metric"),
    ("lo_extendedprice", "LONG", "metric"),
    ("lo_ordtotalprice", "LONG", "metric"),
    ("lo_discount", "INT", "metric"),
    ("lo_revenue", "LONG", "metric"),
    ("lo_supplycost", "LONG", "metric"),
    ("lo_tax", "INT", "metric"),
    ("lo_commitdate", "INT", "dimension"),
    ("lo_shipmode", "STRING", "dimension"),
    ("d_year", "INT", "dimension"),
    ("d_yearmonthnum", "INT", "dimension"),
    ("d_yearmonth", "STRING", "dimension"),
    ("d_weeknuminyear", "INT", "dimension"),
    ("c_city", "STRING", "dimension"),
    ("c_nation", "STRING", "dimension"),
    ("c_region", "STRING", "dimension"),
    ("s_city", "STRING", "dimension"),
    ("s_nation", "STRING", "dimension"),
    ("s_region", "STRING", "dimension"),
    ("p_mfgr", "STRING", "dimension"),
    ("p_category", "STRING", "dimension"),
    ("p_brand1", "STRING", "dimension"),
]

_REGION_OF = {
    "ALGERIA": "AFRICA", "ETHIOPIA": "AFRICA", "KENYA": "AFRICA", "MOROCCO": "AFRICA",
    "MOZAMBIQUE": "AFRICA", "ARGENTINA": "AMERICA", "BRAZIL": "AMERICA", "CANADA": "AMERICA",
    "PERU": "AMERICA", "UNITED STATES": "AMERICA", "INDIA": "ASIA", "INDONESIA": "ASIA",
    "JAPAN": "ASIA", "CHINA": "ASIA", "VIETNAM": "ASIA", "FRANCE": "EUROPE", "GERMANY": "EUROPE",
    "ROMANIA": "EUROPE", "RUSSIA": "EUROPE", "UNITED KINGDOM": "EUROPE", "EGYPT": "MIDDLE EAST",
    "IRAN": "MIDDLE EAST", "IRAQ": "MIDDLE EAST", "JORDAN": "MIDDLE EAST",
    "SAUDI ARABIA": "MIDDLE EAST",
}  # fmt: skip
NATIONS = np.array(sorted(_REGION_OF))
REGIONS = np.array(sorted(set(_REGION_OF.values())))
_NATION_REGION = np.array([code for code in np.searchsorted(REGIONS, [_REGION_OF[n] for n in NATIONS])])
# SSB city: the nation's name cut or padded to 9 characters, and a digit
_CITY_GRID = np.array([[f"{n[:9]:<9}{d}" for d in range(10)] for n in NATIONS])
CITIES = np.sort(_CITY_GRID.ravel())
_CITY_CODE = np.searchsorted(CITIES, _CITY_GRID)  # [nation code, digit] -> city code
MFGRS = np.array([f"MFGR#{m}" for m in range(1, 6)])
_CATEGORY_GRID = np.array([[f"MFGR#{m}{c}" for c in range(1, 6)] for m in range(1, 6)])
CATEGORIES = np.sort(_CATEGORY_GRID.ravel())
_BRAND_GRID = np.array([[f"{cat}{b}" for b in range(1, 41)] for cat in _CATEGORY_GRID.ravel()])
BRANDS = np.sort(_BRAND_GRID.ravel())
_BRAND_CODE = np.searchsorted(BRANDS, _BRAND_GRID)  # [mfgr*5 + category, brand] -> code
_CATEGORY_CODE = np.searchsorted(CATEGORIES, _CATEGORY_GRID.ravel())
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECI", "5-LOW"])
SHIPMODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])

# dbgen's order dates run 1992-01-01 .. 1998-08-02; commit dates up to 90 days on
_DAY0 = np.datetime64("1992-01-01")
ORDER_DAYS = int((np.datetime64("1998-08-02") - _DAY0).astype(int)) + 1
_days = _DAY0 + np.arange(ORDER_DAYS + 90)
_y = _days.astype("datetime64[Y]").astype(int) + 1970
_m = _days.astype("datetime64[M]").astype(int) % 12 + 1
_dom = (_days - _days.astype("datetime64[M]")).astype(int) + 1
_doy = (_days - _days.astype("datetime64[Y]")).astype(int)
DATEKEY = (_y * 10000 + _m * 100 + _dom).astype(np.int64)  # day index -> yyyymmdd, ascending
YEARS = np.arange(1992, 1999)
YEARMONTHNUMS = np.unique(_y * 100 + _m)
_MONTH = np.array(["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep", "Oct", "Nov", "Dec"])
_ym_str = np.char.add(_MONTH[_m - 1], _y.astype(str))
YEARMONTHS = np.unique(_ym_str)
WEEKS = np.arange(1, 54)
# what the queries' parameters are drawn from: the months that have orders
_ORDER_YMNUMS = np.unique((_y * 100 + _m)[:ORDER_DAYS])
_ORDER_YMS = np.unique(_ym_str[:ORDER_DAYS])
_DAY_YEAR = (_y - 1992).astype(np.int32)
_DAY_YMNUM = np.searchsorted(YEARMONTHNUMS, _y * 100 + _m).astype(np.int32)
_DAY_YM = np.searchsorted(YEARMONTHS, _ym_str).astype(np.int32)
_DAY_WEEK = (_doy // 7).astype(np.int32)  # code of week number 1 + doy // 7


def sizes(config: dict) -> dict:
    """Dimension table sizes at the configuration's scale factor, by dbgen's rules."""
    sf = config["scaleFactor"]
    return {
        "customers": int(30_000 * sf),
        "suppliers": int(2_000 * sf),
        "parts": int(200_000 * (1 + int(np.floor(np.log2(sf))))) if sf >= 1 else int(200_000 * sf),
    }


def vocabs(config: dict) -> dict[str, np.ndarray]:
    sz = sizes(config)
    return {
        "lo_linenumber": np.arange(1, 8),
        "lo_custkey": np.arange(1, sz["customers"] + 1),
        "lo_partkey": np.arange(1, sz["parts"] + 1),
        "lo_suppkey": np.arange(1, sz["suppliers"] + 1),
        "lo_orderdate": DATEKEY,
        "lo_commitdate": DATEKEY,
        "lo_orderpriority": PRIORITIES,
        "lo_shippriority": np.array([0]),
        "lo_shipmode": SHIPMODES,
        "d_year": YEARS,
        "d_yearmonthnum": YEARMONTHNUMS,
        "d_yearmonth": YEARMONTHS,
        "d_weeknuminyear": WEEKS,
        "c_city": CITIES, "c_nation": NATIONS, "c_region": REGIONS,
        "s_city": CITIES, "s_nation": NATIONS, "s_region": REGIONS,
        "p_mfgr": MFGRS, "p_category": CATEGORIES, "p_brand1": BRANDS,
    }  # fmt: skip


@functools.lru_cache(maxsize=2)
def _dimensions(seed: int, customers: int, suppliers: int, parts: int) -> dict:
    """customer, supplier and part attributes by key, the same for every segment of a seed."""
    out = {}
    for who, n, stream in (("c", customers, 1), ("s", suppliers, 2)):
        rng = np.random.default_rng([seed, 1_000_000 + stream])
        nation = rng.integers(0, 25, n).astype(np.int32)
        out[f"{who}_nation"] = nation
        out[f"{who}_city"] = _CITY_CODE[nation, rng.integers(0, 10, n)].astype(np.int32)
        out[f"{who}_region"] = _NATION_REGION[nation].astype(np.int32)
    rng = np.random.default_rng([seed, 1_000_003])
    mfgr = rng.integers(0, 5, parts)
    cat = mfgr * 5 + rng.integers(0, 5, parts)
    out["p_mfgr"] = mfgr.astype(np.int32)
    out["p_category"] = _CATEGORY_CODE[cat].astype(np.int32)
    out["p_brand1"] = _BRAND_CODE[cat, rng.integers(0, 40, parts)].astype(np.int32)
    pk = np.arange(1, parts + 1, dtype=np.int64)
    out["p_price"] = retail_price_cents(pk)
    return out


def segment(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    """Segment `index`: `n` consecutive lineorder rows in order-key order."""
    sz = sizes(config)
    dim = _dimensions(seed, sz["customers"], sz["suppliers"], sz["parts"])
    voc = vocabs(config)
    rng = np.random.default_rng([seed, index])
    order, linenumber, n_orders = order_lines(rng, n)

    cust = rng.integers(0, sz["customers"], n_orders)[order]
    day = rng.integers(0, ORDER_DAYS, n_orders).astype(np.int32)[order]
    priority = rng.integers(0, 5, n_orders).astype(np.int32)[order]
    part = rng.integers(0, sz["parts"], n)
    supp = rng.integers(0, sz["suppliers"], n)
    quantity = rng.integers(1, 51, n).astype(np.int32)
    discount = rng.integers(0, 11, n).astype(np.int32)
    tax = rng.integers(0, 9, n).astype(np.int32)
    commit = day + rng.integers(30, 91, n).astype(np.int32)
    shipmode = rng.integers(0, 7, n).astype(np.int32)

    price = dim["p_price"][part]
    extended = quantity * price
    revenue = extended * (100 - discount) // 100
    total = np.bincount(order, weights=(revenue * (100 + tax) // 100).astype(np.float64)).astype(np.int64)

    cols = {
        "lo_orderkey": Column(np.int64(index) * n + order),
        "lo_linenumber": linenumber,
        "lo_custkey": cust.astype(np.int32),
        "lo_partkey": part.astype(np.int32),
        "lo_suppkey": supp.astype(np.int32),
        "lo_orderdate": day,
        "lo_orderpriority": priority,
        "lo_shippriority": np.zeros(n, dtype=np.int32),
        "lo_quantity": Column(quantity),
        "lo_extendedprice": Column(extended),
        "lo_ordtotalprice": Column(total[order]),
        "lo_discount": Column(discount),
        "lo_revenue": Column(revenue),
        "lo_supplycost": Column(price * 6 // 10),
        "lo_tax": Column(tax),
        "lo_commitdate": commit,
        "lo_shipmode": shipmode,
        "d_year": _DAY_YEAR[day],
        "d_yearmonthnum": _DAY_YMNUM[day],
        "d_yearmonth": _DAY_YM[day],
        "d_weeknuminyear": _DAY_WEEK[day],
        "c_city": dim["c_city"][cust], "c_nation": dim["c_nation"][cust], "c_region": dim["c_region"][cust],
        "s_city": dim["s_city"][supp], "s_nation": dim["s_nation"][supp], "s_region": dim["s_region"][supp],
        "p_mfgr": dim["p_mfgr"][part], "p_category": dim["p_category"][part], "p_brand1": dim["p_brand1"][part],
    }  # fmt: skip
    return {
        name: c if isinstance(c, Column) else Column(c, voc[name])
        for name, c in ((name, cols[name]) for name, _, _ in SCHEMA)
    }


# ---------------------------------------------------------------------------
# the 13 queries, flat. Parameters are drawn over SSB's own substitution
# domains; LIMIT is set above what each query's filter lets through (SSB has
# none, the program's default is 10).
# ---------------------------------------------------------------------------


def _eq(cols, name, value):
    return cols[name].codes == code_of(cols[name], value)


def _between(cols, name, lo, hi):
    c = cols[name]
    if c.vocab is None:
        return (c.codes >= lo) & (c.codes <= hi)
    a, b = code_range(c, lo, hi)
    return (c.codes >= a) & (c.codes < b)


def _val(name):
    return lambda cols: cols[name].codes


def _revenue_q1(cols):
    return cols["lo_extendedprice"].codes * cols["lo_discount"].codes


def _profit(cols):
    return cols["lo_revenue"].codes - cols["lo_supplycost"].codes


def _pick(rng, values):
    return values[int(rng.integers(0, len(values)))].item()


def _two_cities(rng):
    nation = int(rng.integers(0, 25))
    a, b = rng.choice(10, 2, replace=False)
    return _CITY_GRID[nation, a].item(), _CITY_GRID[nation, b].item()


def _year_range(rng):
    lo = int(rng.integers(1992, 1994))
    return {"y0": lo, "y1": lo + 5}


def _draw_q11(rng):
    d = int(rng.integers(1, 10))
    return {"year": int(rng.integers(1992, 1999)), "d0": d - 1, "d1": d + 1, "q": int(rng.integers(20, 31))}


def _draw_q12(rng):
    d, q = int(rng.integers(0, 9)), int(rng.integers(1, 42))
    return {"ym": _pick(rng, _ORDER_YMNUMS), "d0": d, "d1": d + 2, "q0": q, "q1": q + 9}


def _draw_q13(rng):
    d, q = int(rng.integers(0, 9)), int(rng.integers(1, 42))
    return {"week": int(rng.integers(1, 53)), "year": int(rng.integers(1992, 1998)),
            "d0": d, "d1": d + 2, "q0": q, "q1": q + 9}  # fmt: skip


def _draw_q22(rng):
    cat, b = _pick(rng, _CATEGORY_GRID.ravel()), int(rng.choice([11, 12, 21, 22, 31, 32]))
    return {"b0": f"{cat}{b}", "b1": f"{cat}{b + 7}", "region": _pick(rng, REGIONS)}


def _draw_q33(rng):
    (c0, c1), (s0, s1) = _two_cities(rng), _two_cities(rng)
    return {"c0": c0, "c1": c1, "s0": s0, "s1": s1, **_year_range(rng)}


def _draw_q34(rng):
    (c0, c1), (s0, s1) = _two_cities(rng), _two_cities(rng)
    return {"c0": c0, "c1": c1, "s0": s0, "s1": s1, "ym": _pick(rng, _ORDER_YMS)}


def _draw_q41(rng):
    m = rng.choice(5, 2, replace=False)
    return {"region": _pick(rng, REGIONS), "m0": MFGRS[m[0]].item(), "m1": MFGRS[m[1]].item()}


def _draw_q42(rng):
    y = int(rng.integers(1992, 1998))
    return {**_draw_q41(rng), "y0": y, "y1": y + 1}


def _draw_q43(rng):
    y = int(rng.integers(1992, 1998))
    return {"region": _pick(rng, REGIONS), "nation": _pick(rng, NATIONS),
            "category": _pick(rng, CATEGORIES), "y0": y, "y1": y + 1}  # fmt: skip


def _q3_where_cities(cols, p):
    return (
        (_eq(cols, "c_city", p["c0"]) | _eq(cols, "c_city", p["c1"]))
        & (_eq(cols, "s_city", p["s0"]) | _eq(cols, "s_city", p["s1"]))
    )


def _q4_where(cols, p):
    return (
        _eq(cols, "c_region", p["region"]) & _eq(cols, "s_region", p["region"])
        & (_eq(cols, "p_mfgr", p["m0"]) | _eq(cols, "p_mfgr", p["m1"]))
    )  # fmt: skip


_Q1_SUM = [("sum", _revenue_q1)]
_REV = [("sum", _val("lo_revenue"))]
_PROFIT = [("sum", _profit)]

TEMPLATES = {
    "q1.1": Template(
        "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder WHERE d_year = {year} "
        "AND lo_discount BETWEEN {d0} AND {d1} AND lo_quantity < {q}",
        _draw_q11,
        Spec(
            lambda c, p: _eq(c, "d_year", p["year"]) & _between(c, "lo_discount", p["d0"], p["d1"])
            & (c["lo_quantity"].codes < p["q"]),
            aggs=_Q1_SUM, select=["agg0"],
        ),
    ),
    "q1.2": Template(
        "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder WHERE d_yearmonthnum = {ym} "
        "AND lo_discount BETWEEN {d0} AND {d1} AND lo_quantity BETWEEN {q0} AND {q1}",
        _draw_q12,
        Spec(
            lambda c, p: _eq(c, "d_yearmonthnum", p["ym"]) & _between(c, "lo_discount", p["d0"], p["d1"])
            & _between(c, "lo_quantity", p["q0"], p["q1"]),
            aggs=_Q1_SUM, select=["agg0"],
        ),
    ),
    "q1.3": Template(
        "SELECT SUM(lo_extendedprice * lo_discount) FROM lineorder WHERE d_weeknuminyear = {week} "
        "AND d_year = {year} AND lo_discount BETWEEN {d0} AND {d1} AND lo_quantity BETWEEN {q0} AND {q1}",
        _draw_q13,
        Spec(
            lambda c, p: _eq(c, "d_weeknuminyear", p["week"]) & _eq(c, "d_year", p["year"])
            & _between(c, "lo_discount", p["d0"], p["d1"]) & _between(c, "lo_quantity", p["q0"], p["q1"]),
            aggs=_Q1_SUM, select=["agg0"],
        ),
    ),
    "q2.1": Template(
        "SELECT SUM(lo_revenue), d_year, p_brand1 FROM lineorder WHERE p_category = '{category}' "
        "AND s_region = '{region}' GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 LIMIT 1000",
        lambda rng: {"category": _pick(rng, CATEGORIES), "region": _pick(rng, REGIONS)},
        Spec(
            lambda c, p: _eq(c, "p_category", p["category"]) & _eq(c, "s_region", p["region"]),
            keys=["d_year", "p_brand1"], aggs=_REV, select=["agg0", "d_year", "p_brand1"],
            order=[("d_year", False), ("p_brand1", False)],
        ),
    ),
    "q2.2": Template(
        "SELECT SUM(lo_revenue), d_year, p_brand1 FROM lineorder WHERE p_brand1 BETWEEN '{b0}' AND '{b1}' "
        "AND s_region = '{region}' GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 LIMIT 1000",
        _draw_q22,
        Spec(
            lambda c, p: _between(c, "p_brand1", p["b0"], p["b1"]) & _eq(c, "s_region", p["region"]),
            keys=["d_year", "p_brand1"], aggs=_REV, select=["agg0", "d_year", "p_brand1"],
            order=[("d_year", False), ("p_brand1", False)],
        ),
    ),
    "q2.3": Template(
        "SELECT SUM(lo_revenue), d_year, p_brand1 FROM lineorder WHERE p_brand1 = '{brand}' "
        "AND s_region = '{region}' GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 LIMIT 1000",
        lambda rng: {"brand": _pick(rng, BRANDS), "region": _pick(rng, REGIONS)},
        Spec(
            lambda c, p: _eq(c, "p_brand1", p["brand"]) & _eq(c, "s_region", p["region"]),
            keys=["d_year", "p_brand1"], aggs=_REV, select=["agg0", "d_year", "p_brand1"],
            order=[("d_year", False), ("p_brand1", False)],
        ),
    ),
    "q3.1": Template(
        "SELECT c_nation, s_nation, d_year, SUM(lo_revenue) FROM lineorder WHERE c_region = '{region}' "
        "AND s_region = '{region}' AND d_year >= {y0} AND d_year <= {y1} "
        "GROUP BY c_nation, s_nation, d_year ORDER BY d_year ASC, SUM(lo_revenue) DESC LIMIT 1000",
        lambda rng: {"region": _pick(rng, REGIONS), **_year_range(rng)},
        Spec(
            lambda c, p: _eq(c, "c_region", p["region"]) & _eq(c, "s_region", p["region"])
            & _between(c, "d_year", p["y0"], p["y1"]),
            keys=["c_nation", "s_nation", "d_year"], aggs=_REV,
            select=["c_nation", "s_nation", "d_year", "agg0"], order=[("d_year", False), ("agg0", True)],
        ),
    ),
    "q3.2": Template(
        "SELECT c_city, s_city, d_year, SUM(lo_revenue) FROM lineorder WHERE c_nation = '{nation}' "
        "AND s_nation = '{nation}' AND d_year >= {y0} AND d_year <= {y1} "
        "GROUP BY c_city, s_city, d_year ORDER BY d_year ASC, SUM(lo_revenue) DESC LIMIT 1000",
        lambda rng: {"nation": _pick(rng, NATIONS), **_year_range(rng)},
        Spec(
            lambda c, p: _eq(c, "c_nation", p["nation"]) & _eq(c, "s_nation", p["nation"])
            & _between(c, "d_year", p["y0"], p["y1"]),
            keys=["c_city", "s_city", "d_year"], aggs=_REV,
            select=["c_city", "s_city", "d_year", "agg0"], order=[("d_year", False), ("agg0", True)],
        ),
    ),
    "q3.3": Template(
        "SELECT c_city, s_city, d_year, SUM(lo_revenue) FROM lineorder WHERE (c_city = '{c0}' OR c_city = '{c1}') "
        "AND (s_city = '{s0}' OR s_city = '{s1}') AND d_year >= {y0} AND d_year <= {y1} "
        "GROUP BY c_city, s_city, d_year ORDER BY d_year ASC, SUM(lo_revenue) DESC LIMIT 1000",
        _draw_q33,
        Spec(
            lambda c, p: _q3_where_cities(c, p) & _between(c, "d_year", p["y0"], p["y1"]),
            keys=["c_city", "s_city", "d_year"], aggs=_REV,
            select=["c_city", "s_city", "d_year", "agg0"], order=[("d_year", False), ("agg0", True)],
        ),
    ),
    "q3.4": Template(
        "SELECT c_city, s_city, d_year, SUM(lo_revenue) FROM lineorder WHERE (c_city = '{c0}' OR c_city = '{c1}') "
        "AND (s_city = '{s0}' OR s_city = '{s1}') AND d_yearmonth = '{ym}' "
        "GROUP BY c_city, s_city, d_year ORDER BY d_year ASC, SUM(lo_revenue) DESC LIMIT 1000",
        _draw_q34,
        Spec(
            lambda c, p: _q3_where_cities(c, p) & _eq(c, "d_yearmonth", p["ym"]),
            keys=["c_city", "s_city", "d_year"], aggs=_REV,
            select=["c_city", "s_city", "d_year", "agg0"], order=[("d_year", False), ("agg0", True)],
        ),
    ),
    "q4.1": Template(
        "SELECT d_year, c_nation, SUM(lo_revenue - lo_supplycost) FROM lineorder WHERE c_region = '{region}' "
        "AND s_region = '{region}' AND (p_mfgr = '{m0}' OR p_mfgr = '{m1}') "
        "GROUP BY d_year, c_nation ORDER BY d_year, c_nation LIMIT 1000",
        _draw_q41,
        Spec(
            _q4_where, keys=["d_year", "c_nation"], aggs=_PROFIT,
            select=["d_year", "c_nation", "agg0"], order=[("d_year", False), ("c_nation", False)],
        ),
    ),
    "q4.2": Template(
        "SELECT d_year, s_nation, p_category, SUM(lo_revenue - lo_supplycost) FROM lineorder "
        "WHERE c_region = '{region}' AND s_region = '{region}' AND (d_year = {y0} OR d_year = {y1}) "
        "AND (p_mfgr = '{m0}' OR p_mfgr = '{m1}') GROUP BY d_year, s_nation, p_category "
        "ORDER BY d_year, s_nation, p_category LIMIT 1000",
        _draw_q42,
        Spec(
            lambda c, p: _q4_where(c, p) & (_eq(c, "d_year", p["y0"]) | _eq(c, "d_year", p["y1"])),
            keys=["d_year", "s_nation", "p_category"], aggs=_PROFIT,
            select=["d_year", "s_nation", "p_category", "agg0"],
            order=[("d_year", False), ("s_nation", False), ("p_category", False)],
        ),
    ),
    "q4.3": Template(
        "SELECT d_year, s_city, p_brand1, SUM(lo_revenue - lo_supplycost) FROM lineorder "
        "WHERE c_region = '{region}' AND s_nation = '{nation}' AND (d_year = {y0} OR d_year = {y1}) "
        "AND p_category = '{category}' GROUP BY d_year, s_city, p_brand1 "
        "ORDER BY d_year, s_city, p_brand1 LIMIT 1000",
        _draw_q43,
        Spec(
            lambda c, p: _eq(c, "c_region", p["region"]) & _eq(c, "s_nation", p["nation"])
            & (_eq(c, "d_year", p["y0"]) | _eq(c, "d_year", p["y1"])) & _eq(c, "p_category", p["category"]),
            keys=["d_year", "s_city", "p_brand1"], aggs=_PROFIT,
            select=["d_year", "s_city", "p_brand1", "agg0"],
            order=[("d_year", False), ("s_city", False), ("p_brand1", False)],
        ),
    ),
}  # fmt: skip
