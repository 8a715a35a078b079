"""Star Schema Benchmark as the star it defines: `lineorder` with its 17
columns, and `customer`, `supplier`, `part` and `dates` beside it as
dimension tables, joined at query time by `lookUp`.

Source: O'Neil, O'Neil, Chen, "Star Schema Benchmark", rev. 3 (2009). The
tables *are* what `ssb_flat` joins its flat table from: the dimension tables
are `ssb_flat._dimensions` of the same seed by key and the calendar by the
same formulas (carried on to the source's 2,556 days from 1992-01-01, which end
at 1998-12-30: seven years hold two leap days, and dbgen's `date` stops a day short),
and `segment()` is the flat table's, all 30 columns: the harness builds the 17
that `SCHEMA` names and the plain reference reads the other 13. So every
template keeps the flat template's own `Spec` and draw, and
`perfbench/refeval.py` judges each joined answer against the pre-joined
table of the same rows, to the unit.

The dimension tables carry the source's columns but its free text (names,
addresses, phones: the flat twin's cut): the attributes the queries touch are
the flat table's, the others (`c_mktsegment`, `p_color`, `p_type`, `p_size`,
`p_container`, `date`'s other twelve) are drawn from streams of their own or
computed from the calendar, so that a dimension table's width — and with it
how many operand words a foreign key is gathered through — is the source's.

The queries are the flat module's thirteen with each dimension attribute
reached through its dimension table, letter for letter otherwise:
`c_x` -> `lookUp('customer', 'c_x', 'c_custkey', lo_custkey)`, `s_x`, `p_x`
and `d_x` likewise through `supplier`, `part` and `dates`.
"""

from __future__ import annotations

import re

import numpy as np

from perfbench.datasets import ssb_flat as flat
from perfbench.refeval import Column, Template

TABLE = "lineorder"
SCHEMA = [row for row in flat.SCHEMA if row[0].startswith("lo_")]

def _dims(*cols) -> list[tuple]:
    return [(name, kind, "dimension") for name, kind in cols]


# the source's columns but its free text (names, addresses, phones: the flat twin's cut), in the source's order
CUSTOMER = _dims(("c_custkey", "INT"), ("c_city", "STRING"), ("c_nation", "STRING"), ("c_region", "STRING"), ("c_mktsegment", "STRING"))
SUPPLIER = _dims(("s_suppkey", "INT"), ("s_city", "STRING"), ("s_nation", "STRING"), ("s_region", "STRING"))
PART = _dims(("p_partkey", "INT"), ("p_mfgr", "STRING"), ("p_category", "STRING"), ("p_brand1", "STRING"), ("p_color", "STRING"),
             ("p_type", "STRING"), ("p_size", "INT"), ("p_container", "STRING"))  # fmt: skip
DATES = _dims(("d_datekey", "INT"), ("d_date", "STRING"), ("d_dayofweek", "STRING"), ("d_month", "STRING"), ("d_year", "INT"),
              ("d_yearmonthnum", "INT"), ("d_yearmonth", "STRING"), ("d_daynuminweek", "INT"), ("d_daynuminmonth", "INT"),
              ("d_daynuminyear", "INT"), ("d_monthnuminyear", "INT"), ("d_weeknuminyear", "INT"), ("d_sellingseason", "STRING"),
              ("d_lastdayinweekfl", "INT"), ("d_lastdayinmonthfl", "INT"), ("d_holidayfl", "INT"), ("d_weekdayfl", "INT"))  # fmt: skip

# dbgen's vocabularies of the attributes no query touches, sorted
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"])
COLORS = np.array(sorted(
    "almond antique aquamarine azure beige bisque black blanched blue blush brown burlywood burnished chartreuse chiffon "
    "chocolate coral cornflower cornsilk cream cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid pale papaya peach peru pink plum powder puff purple "
    "red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke snow spring steel tan thistle tomato turquoise "
    "violet wheat white yellow".split()
))  # fmt: skip
TYPES = np.array(sorted(
    f"{a} {b} {c}" for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
    for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED") for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
))  # fmt: skip
CONTAINERS = np.array(sorted(
    f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP") for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
))  # fmt: skip
_WEEKDAY = np.array(["Thursday", "Friday", "Saturday", "Sunday", "Monday", "Tuesday", "Wednesday"])  # 1970-01-01 was a Thursday
_MONTH_NAME = np.array(["January", "February", "March", "April", "May", "June", "July", "August", "September", "October",
                        "November", "December"])  # fmt: skip
_SEASON = np.array(["Winter", "Winter", "Spring", "Spring", "Spring", "Summer", "Summer", "Summer", "Fall", "Fall", "Fall", "Christmas"])

vocabs = flat.vocabs
segment = flat.segment

# the calendar by `ssb_flat`'s formulas, over SSB's whole `date` table and not only the days that have orders
DATE_ROWS = 2556  # the source's count: 1992-01-01 .. 1998-12-30
_days = flat._DAY0 + np.arange(DATE_ROWS)
_y = _days.astype("datetime64[Y]").astype(int) + 1970
_m = _days.astype("datetime64[M]").astype(int) % 12 + 1
_dom = (_days - _days.astype("datetime64[M]")).astype(int) + 1
_doy = (_days - _days.astype("datetime64[Y]")).astype(int)
_ym_str = np.char.add(flat._MONTH[_m - 1], _y.astype(str))
_dow = _days.astype(int) % 7  # into _WEEKDAY
_last_dom = ((_days.astype("datetime64[M]") + 1).astype("datetime64[D]") - _days).astype(int) == 1


def _coded(values: np.ndarray) -> Column:
    return Column(*reversed(np.unique(values, return_inverse=True)))


def _keyed(seed: int, index: int, n: int, config: dict, who: str, size: str, key: str, attrs: dict) -> dict[str, Column]:
    """A dimension table of `ssb_flat._dimensions` whole, as one segment: keys 1..n and the attributes by key."""
    sz = flat.sizes(config)
    assert index == 0 and n == sz[size], f"{who} has {sz[size]} rows at this scale factor, not {n}"
    dim = flat._dimensions(seed, sz["customers"], sz["suppliers"], sz["parts"])
    return {key: Column(np.arange(1, n + 1, dtype=np.int32)), **{c: Column(dim[c], vocab) for c, vocab in attrs.items()}}


def customer(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    rng = np.random.default_rng([seed, 2_000_001])  # a stream of its own: the flat table's attributes stay what they are
    return {
        **_keyed(seed, index, n, config, "customer", "customers", "c_custkey",
                 {"c_city": flat.CITIES, "c_nation": flat.NATIONS, "c_region": flat.REGIONS}),
        "c_mktsegment": Column(rng.integers(0, len(SEGMENTS), n).astype(np.int32), SEGMENTS),
    }  # fmt: skip


def supplier(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    return _keyed(seed, index, n, config, "supplier", "suppliers", "s_suppkey",
                  {"s_city": flat.CITIES, "s_nation": flat.NATIONS, "s_region": flat.REGIONS})  # fmt: skip


def part(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    rng = np.random.default_rng([seed, 2_000_003])
    return {
        **_keyed(seed, index, n, config, "part", "parts", "p_partkey",
                 {"p_mfgr": flat.MFGRS, "p_category": flat.CATEGORIES, "p_brand1": flat.BRANDS}),
        "p_color": Column(rng.integers(0, len(COLORS), n).astype(np.int32), COLORS),
        "p_type": Column(rng.integers(0, len(TYPES), n).astype(np.int32), TYPES),
        "p_size": Column(rng.integers(1, 51, n).astype(np.int32)),
        "p_container": Column(rng.integers(0, len(CONTAINERS), n).astype(np.int32), CONTAINERS),
    }  # fmt: skip


def dates(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    """SSB's `date` table, a row a day from 1992-01-01, the same for every seed and scale factor."""
    assert index == 0 and n == DATE_ROWS, f"dates has {DATE_ROWS} rows, not {n}"
    ints = {
        "d_datekey": _y * 10000 + _m * 100 + _dom,
        "d_year": _y,
        "d_yearmonthnum": _y * 100 + _m,
        "d_daynuminweek": 1 + (_dow + 4) % 7,  # Sunday is 1, as dbgen numbers it
        "d_daynuminmonth": _dom,
        "d_daynuminyear": 1 + _doy,
        "d_monthnuminyear": _m,
        "d_weeknuminyear": 1 + _doy // 7,
        "d_lastdayinweekfl": _dow == 2,  # Saturday
        "d_lastdayinmonthfl": _last_dom,
        "d_holidayfl": ((_m == 1) & (_dom == 1)) | ((_m == 7) & (_dom == 4)) | ((_m == 12) & (_dom == 25)),
        "d_weekdayfl": (_dow != 2) & (_dow != 3),
    }
    strings = {
        "d_date": np.char.add(np.char.add(np.char.add(_MONTH_NAME[_m - 1], " "), np.char.add(_dom.astype(str), ", ")), _y.astype(str)),
        "d_dayofweek": _WEEKDAY[_dow],
        "d_month": _MONTH_NAME[_m - 1],
        "d_yearmonth": _ym_str,
        "d_sellingseason": _SEASON[_m - 1],
    }
    cols = {**{c: Column(v.astype(np.int32)) for c, v in ints.items()}, **{c: _coded(v) for c, v in strings.items()}}
    return {c: cols[c] for c, _, _ in DATES}


TABLES = {
    "lineorder": {"schema": SCHEMA, "segment": segment},
    "customer": {"schema": CUSTOMER, "segment": customer},
    "supplier": {"schema": SUPPLIER, "segment": supplier},
    "part": {"schema": PART, "segment": part},
    "dates": {"schema": DATES, "segment": dates},
}

#: attribute prefix -> (dimension table, its primary key, the fact table's foreign key)
JOINS = {"c": ("customer", "c_custkey", "lo_custkey"), "s": ("supplier", "s_suppkey", "lo_suppkey"),
         "p": ("part", "p_partkey", "lo_partkey"), "d": ("dates", "d_datekey", "lo_orderdate")}  # fmt: skip
_ATTRIBUTE = re.compile(r"\b([cspd])_[a-z0-9]+\b")


def star(sql: str) -> str:
    """A flat query with every dimension attribute reached through its dimension table."""

    def joined(m: re.Match) -> str:
        table, key, fk = JOINS[m.group(1)]
        return f"lookUp('{table}', '{m.group(0)}', '{key}', {fk})"

    return _ATTRIBUTE.sub(joined, sql)


TEMPLATES = {name: Template(star(t.sql), t.draw, t.spec) for name, t in flat.TEMPLATES.items()}
