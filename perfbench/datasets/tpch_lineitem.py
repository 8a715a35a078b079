"""TPC-H `lineitem`, as upstream Pinot's own harness loads it
(contrib/pinot-druid-benchmark: dbgen -s8, lineitem only, dates as
'yyyy-MM-dd' strings, prices DOUBLE).

Source: TPC-H specification rev. 2/3, clause 4.2.3 (dbgen's column rules) and
2.4.1 / 2.4.6 (Q1, Q6 and their substitution parameters). What is assumed,
not the source's: see the configuration file.
"""

from __future__ import annotations

import functools

import numpy as np

from perfbench.datasets._dbgen import order_lines, retail_price_cents
from perfbench.refeval import Column, Spec, Template, code_range

TABLE = "lineitem"

SCHEMA = [
    ("l_orderkey", "LONG", "dimension"),
    ("l_partkey", "INT", "dimension"),
    ("l_suppkey", "INT", "dimension"),
    ("l_linenumber", "INT", "dimension"),
    ("l_quantity", "LONG", "metric"),
    ("l_extendedprice", "DOUBLE", "metric"),
    ("l_discount", "DOUBLE", "metric"),
    ("l_tax", "DOUBLE", "metric"),
    ("l_returnflag", "STRING", "dimension"),
    ("l_linestatus", "STRING", "dimension"),
    ("l_shipdate", "STRING", "dimension"),
    ("l_commitdate", "STRING", "dimension"),
    ("l_receiptdate", "STRING", "dimension"),
    ("l_shipinstruct", "STRING", "dimension"),
    ("l_shipmode", "STRING", "dimension"),
    ("l_comment", "STRING", "dimension"),
]

_DAY0 = np.datetime64("1992-01-01")
ORDER_DAYS = int((np.datetime64("1998-08-02") - _DAY0).astype(int)) + 1
DATES = (_DAY0 + np.arange(ORDER_DAYS + 151)).astype(str)  # ship <= order + 121, receipt <= ship + 30
_CUTOFF = int((np.datetime64("1995-06-17") - _DAY0).astype(int))  # dbgen's CURRENTDATE
RETURNFLAGS = np.array(["A", "N", "R"])
LINESTATUS = np.array(["F", "O"])
SHIPINSTRUCT = np.array(["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"])
SHIPMODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
_WORDS = (
    "furiously carefully quickly slyly blithely even final ironic regular special express bold "
    "pending silent unusual requests deposits packages accounts instructions theodolites foxes "
    "pinto beans dependencies platelets ideas asymptotes sleep wake nag haggle cajole boost "
    "detect integrate above across after against along among around beside"
).split()
COMMENT_POOL = 65_536


def sizes(config: dict) -> dict:
    sf = config["scaleFactor"]
    return {"parts": int(200_000 * sf), "suppliers": int(10_000 * sf)}


@functools.lru_cache(maxsize=1)
def _comments() -> np.ndarray:
    """A fixed pool of variable-width comment texts from dbgen's word list
    (dbgen draws 10..43 characters of a generated text; here 3..8 words)."""
    rng = np.random.default_rng(20_240_923)
    texts = {" ".join(rng.choice(_WORDS, int(rng.integers(3, 9)))) for _ in range(COMMENT_POOL)}
    return np.array(sorted(texts))


def vocabs(config: dict) -> dict[str, np.ndarray]:
    sz = sizes(config)
    return {
        "l_partkey": np.arange(1, sz["parts"] + 1),
        "l_suppkey": np.arange(1, sz["suppliers"] + 1),
        "l_linenumber": np.arange(1, 8),
        "l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUS,
        "l_shipdate": DATES, "l_commitdate": DATES, "l_receiptdate": DATES,
        "l_shipinstruct": SHIPINSTRUCT, "l_shipmode": SHIPMODES, "l_comment": _comments(),
    }  # fmt: skip


def segment(seed: int, index: int, n: int, config: dict) -> dict[str, Column]:
    """Segment `index`: `n` consecutive lineitem rows in order-key order."""
    sz = sizes(config)
    voc = vocabs(config)
    rng = np.random.default_rng([seed, index])
    order, linenumber, n_orders = order_lines(rng, n)

    orderdate = rng.integers(0, ORDER_DAYS, n_orders).astype(np.int32)[order]
    part = rng.integers(0, sz["parts"], n)
    quantity = rng.integers(1, 51, n)
    ship = orderdate + rng.integers(1, 122, n).astype(np.int32)
    commit = orderdate + rng.integers(30, 91, n).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n).astype(np.int32)
    price_cents = retail_price_cents(part + 1)
    returned = np.where(rng.integers(0, 2, n) == 0, 0, 2)  # A or R
    cols = {
        "l_orderkey": Column(np.int64(index) * n + order),
        "l_partkey": part.astype(np.int32),
        "l_suppkey": rng.integers(0, sz["suppliers"], n).astype(np.int32),
        "l_linenumber": linenumber,
        "l_quantity": Column(quantity),
        "l_extendedprice": Column(quantity * price_cents / 100.0),
        "l_discount": Column(rng.integers(0, 11, n) / 100.0),
        "l_tax": Column(rng.integers(0, 9, n) / 100.0),
        "l_returnflag": np.where(receipt <= _CUTOFF, returned, 1).astype(np.int32),
        "l_linestatus": (ship > _CUTOFF).astype(np.int32),
        "l_shipdate": ship, "l_commitdate": commit, "l_receiptdate": receipt,
        "l_shipinstruct": rng.integers(0, 4, n).astype(np.int32),
        "l_shipmode": rng.integers(0, 7, n).astype(np.int32),
        "l_comment": rng.integers(0, len(voc["l_comment"]), n).astype(np.int32),
    }  # fmt: skip
    return {
        name: c if isinstance(c, Column) else Column(c, voc[name])
        for name, c in ((name, cols[name]) for name, _, _ in SCHEMA)
    }


# ---------------------------------------------------------------------------
# Q1 and Q6 with TPC-H's substitution parameters
# ---------------------------------------------------------------------------


def _v(name):
    return lambda cols: cols[name].codes


def _disc_price(cols):
    return cols["l_extendedprice"].codes * (1 - cols["l_discount"].codes)


def _charge(cols):
    return cols["l_extendedprice"].codes * (1 - cols["l_discount"].codes) * (1 + cols["l_tax"].codes)


def _draw_q1(rng):
    delta = int(rng.integers(60, 121))
    return {"date": str(np.datetime64("1998-12-01") - delta)}


def _draw_q6(rng):
    year, d = int(rng.integers(1993, 1998)), int(rng.integers(2, 10))
    return {"d0": f"{year}-01-01", "d1": f"{year + 1}-01-01", "lo": f"{(d - 1) / 100:.2f}",
            "hi": f"{(d + 1) / 100:.2f}", "q": int(rng.integers(24, 26))}  # fmt: skip


def _q1_where(cols, p):
    _, b = code_range(cols["l_shipdate"], "", p["date"])
    return cols["l_shipdate"].codes < b


def _q6_where(cols, p):
    ship = cols["l_shipdate"]
    a = int(np.searchsorted(ship.vocab, p["d0"], "left"))
    b = int(np.searchsorted(ship.vocab, p["d1"], "left"))
    # discounts are whole hundredths: compare them as such, as the source's decimals would
    disc = np.rint(cols["l_discount"].codes * 100)
    lo, hi = round(float(p["lo"]) * 100), round(float(p["hi"]) * 100)
    return (ship.codes >= a) & (ship.codes < b) & (disc >= lo) & (disc <= hi) & (cols["l_quantity"].codes < p["q"])


TEMPLATES = {
    "q1": Template(
        "SELECT l_returnflag, l_linestatus, SUM(l_quantity), SUM(l_extendedprice), "
        "SUM(l_extendedprice * (1 - l_discount)), SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
        "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*) FROM lineitem "
        "WHERE l_shipdate <= '{date}' GROUP BY l_returnflag, l_linestatus "
        "ORDER BY l_returnflag, l_linestatus LIMIT 10",
        _draw_q1,
        Spec(
            _q1_where,
            keys=["l_returnflag", "l_linestatus"],
            aggs=[("sum", _v("l_quantity")), ("sum", _v("l_extendedprice")), ("sum", _disc_price),
                  ("sum", _charge), ("avg", _v("l_quantity")), ("avg", _v("l_extendedprice")),
                  ("avg", _v("l_discount")), ("count", None)],
            select=["l_returnflag", "l_linestatus"] + [f"agg{i}" for i in range(8)],
            order=[("l_returnflag", False), ("l_linestatus", False)],
            exact=False,
        ),
    ),
    "q6": Template(
        "SELECT SUM(l_extendedprice * l_discount) FROM lineitem WHERE l_shipdate >= '{d0}' "
        "AND l_shipdate < '{d1}' AND l_discount BETWEEN {lo} AND {hi} AND l_quantity < {q}",
        _draw_q6,
        Spec(
            _q6_where,
            aggs=[("sum", lambda cols: cols["l_extendedprice"].codes * cols["l_discount"].codes)],
            select=["agg0"],
            exact=False,
        ),
    ),
}  # fmt: skip
