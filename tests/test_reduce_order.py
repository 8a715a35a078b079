"""_order_groups: the ORDER BY over columns (every key a number or a rank,
then one np.lexsort) must order identically to the general _OrderKey
comparison sort for every key shape — multi-key, ASC/DESC mixes, null ranking
(nulls-as-largest, OrderByExpressionContext default), strings (ranks of the
distinct values), and what only the comparison sort can order (mixed types,
bool, >2^53 ints: the fallback, counted as `reduceRowStages`)."""

import math
import random
from unittest import mock

import numpy as np
import pytest

from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import ast
from pinot_tpu.query.reduce import _Groups, _OrderKey, _order_groups


class _OB:
    def __init__(self, name, desc=False):
        self.expr = ast.Identifier(name)
        self.desc = desc


def _groups(rows):
    """Row dicts as the reduce's columns."""
    return _Groups(len(rows), {k: [r[k] for r in rows] for k in (rows[0] if rows else {})})


def _entered(self, other):
    raise AssertionError("the comparison sort was entered")


def _order_rows(rows, obs, comparison_sort=True):
    """(rows in ORDER BY's order, `reduceRowStages` of the sort). `comparison_sort=False`: the
    _OrderKey sort must not be entered, its comparison raises."""
    with request_ledger("order") as led, mock.patch.object(_OrderKey, "__lt__", _OrderKey.__lt__ if comparison_sort else _entered):
        perm = _order_groups(_groups(rows), obs, {})
    assert sorted(perm.tolist()) == list(range(len(rows)))
    return [rows[i] for i in perm], led.to_wire()["counters"].get("reduceRowStages", 0)


def _reference_sort(rows, obs):
    return sorted(
        rows,
        key=lambda e: tuple(_OrderKey(e[ob.expr.name], ob.desc) for ob in obs),
    )


def _stable_check(rows, obs, row_stages=0):
    got, counted = _order_rows(list(rows), obs, comparison_sort=row_stages > 0)
    want = _reference_sort(rows, obs)
    # the same row objects in the same places: ties included
    assert [id(r) for r in got] == [id(r) for r in want]
    assert counted == row_stages


@pytest.mark.parametrize("desc1,desc2", [(False, False), (True, False), (False, True), (True, True)])
def test_numeric_multikey_matches_reference(desc1, desc2):
    rng = random.Random(7)
    rows = [
        {"a": rng.choice([None, 1, 2, 3, 2.5]), "b": rng.uniform(-5, 5), "i": i}
        for i in range(200)
    ]
    _stable_check(rows, [_OB("a", desc1), _OB("b", desc2)])


def test_nulls_rank_largest_both_directions():
    rows = [{"a": v} for v in [3, None, 1, float("nan"), 2]]
    asc, _ = _order_rows(list(rows), [_OB("a")])
    vals = [r["a"] for r in asc]
    assert vals[:3] == [1, 2, 3] and all(
        v is None or math.isnan(v) for v in vals[3:]
    )
    desc, _ = _order_rows(list(rows), [_OB("a", desc=True)])
    vals = [r["a"] for r in desc]
    assert vals[2:] == [3, 2, 1] and all(
        v is None or math.isnan(v) for v in vals[:2]
    )


def test_string_keys_sort_as_ranks():
    rows = [{"s": v} for v in ["pear", None, "apple", "mango"]]
    out, counted = _order_rows(list(rows), [_OB("s")], comparison_sort=False)
    assert [r["s"] for r in out] == ["apple", "mango", "pear", None] and counted == 0


def test_big_int_precision_fallback():
    # adjacent >2^53 ints collapse in float64; the fallback must keep them
    a, b = (1 << 60) + 1, (1 << 60)
    assert float(a) == float(b)
    rows = [{"v": a}, {"v": b}]
    out, counted = _order_rows(list(rows), [_OB("v")])
    assert [r["v"] for r in out] == [b, a] and counted == 1
    # the same ints as numpy's scalars (an object column's values): the guard reads them too
    rows = [{"v": np.int64(a)}, {"v": np.int64(b)}]
    out, counted = _order_rows(list(rows), [_OB("v")])
    assert [r["v"] for r in out] == [b, a] and counted == 1


def test_stability_preserved_on_ties():
    rows = [{"k": 1, "tag": i} for i in range(50)]
    out, _ = _order_rows(list(rows), [_OB("k")])
    assert [r["tag"] for r in out] == list(range(50))


def test_nan_ranks_largest_with_a_string_key_beside_it():
    # NaN in the primary must rank largest whatever the secondary key is made of
    rows = [
        {"a": float("nan"), "s": "x"},
        {"a": 1.0, "s": "y"},
        {"a": 2.0, "s": "z"},
    ]
    out, counted = _order_rows(list(rows), [_OB("a"), _OB("s")], comparison_sort=False)
    assert [r["s"] for r in out] == ["y", "z", "x"] and counted == 0


def test_nan_ranks_largest_on_fallback_path_too():
    # a bool secondary key forces the _OrderKey fallback; NaN in the primary
    # must still rank largest, agreeing with the lexsort
    rows = [
        {"a": float("nan"), "s": True},
        {"a": 1.0, "s": False},
        {"a": 2.0, "s": True},
        {"a": 1.0, "s": True},
    ]
    out, counted = _order_rows(list(rows), [_OB("a"), _OB("s", desc=True)])
    assert [(r["a"], r["s"]) for r in out[:3]] == [(1.0, True), (1.0, False), (2.0, True)] and counted == 1
    assert math.isnan(out[3]["a"])


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_a_string_key_alone(desc):
    rng = random.Random(11)
    rows = [{"s": rng.choice(["kiwi", "fig", "lime", "date", "plum", ""]), "i": i} for i in range(300)]
    _stable_check(rows, [_OB("s", desc)])


def _tsbs_rows(seed=3, hosts=4000, hours=13):
    rows = [{"name": f"host_{h}", "hour": 3_600_000 * t, "avg": float(h + t)} for h in range(hosts) for t in range(hours)]
    random.Random(seed).shuffle(rows)
    return rows


@pytest.mark.parametrize(
    "keys",
    [
        (("hour", False), ("name", False)),  # numeric then string: the TSBS query's own order
        (("name", False), ("hour", False)),  # string then numeric
        (("hour", True), ("name", False)),
        (("name", True), ("hour", True)),
    ],
    ids=lambda keys: "-".join(f"{k}{'v' if d else '^'}" for k, d in keys),
)
@pytest.mark.parametrize("hour_type", [int, np.int64], ids=["int", "np-int64"])
def test_the_tsbs_shape_never_enters_the_comparison_sort(keys, hour_type):
    """4000 names x 13 hours, shuffled, by (hour, name) and (name, hour): one lexsort, the oracle's order."""
    rows = [{**r, "hour": hour_type(r["hour"])} for r in _tsbs_rows()]
    obs = [_OB(k, d) for k, d in keys]
    got, counted = _order_rows(rows, obs, comparison_sort=False)
    sign = {k: -1 if d else 1 for k, d in keys}
    rank = {n: i for i, n in enumerate(sorted({r["name"] for r in rows}))}
    want = sorted(rows, key=lambda r: tuple(sign[k] * (rank[r[k]] if k == "name" else r[k]) for k, _ in keys))
    assert [id(r) for r in got] == [id(r) for r in want] and counted == 0


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_none_among_strings_ranks_largest(desc):
    rows = [{"s": v, "i": i} for i, v in enumerate(["b", None, "a", None, "c", "a"])]
    out, counted = _order_rows(list(rows), [_OB("s", desc)], comparison_sort=False)
    want = ["c", "b", "a", "a"] if desc else ["a", "a", "b", "c"]
    assert [r["s"] for r in out] == ([None, None] + want if desc else want + [None, None]) and counted == 0
    assert [r["i"] for r in out if r["s"] is None] == [1, 3] and [r["i"] for r in out if r["s"] == "a"] == [2, 5]


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_duplicate_hour_name_pairs_keep_the_merge_order(desc):
    """Ties on (hour, name) stay in the order they came in, under ASC and DESC: the third column tells."""
    rng = random.Random(5)
    rows = [{"hour": rng.randrange(4), "name": rng.choice("abc"), "seq": i} for i in range(400)]
    _stable_check(rows, [_OB("hour", desc), _OB("name", desc)])
    out, _ = _order_rows(list(rows), [_OB("hour", desc), _OB("name", desc)])
    for (h, n) in {(r["hour"], r["name"]) for r in rows}:
        seqs = [r["seq"] for r in out if (r["hour"], r["name"]) == (h, n)]
        assert seqs == sorted(seqs) and len(seqs) > 1


def test_strings_collate_by_code_point():
    names = ["a", "Z", "é", "z", "A", "", "ß", "e", "zz", "Zebra", "éa", "\U0001f600", "日本"]
    rows = [{"s": v} for v in names]
    out, _ = _order_rows(list(rows), [_OB("s")], comparison_sort=False)
    got = [r["s"] for r in out]
    assert got == sorted(names) and got.index("Z") < got.index("a") < got.index("é")
    out, _ = _order_rows(list(rows), [_OB("s", desc=True)], comparison_sort=False)
    assert [r["s"] for r in out] == sorted(names, reverse=True)


def test_a_str_int_mix_raises_as_the_comparison_sort_does():
    rows = [{"v": "a"}, {"v": 1}, {"v": "b"}]
    with pytest.raises(TypeError) as want:
        _reference_sort(rows, [_OB("v")])
    with pytest.raises(TypeError) as got:
        _order_rows(list(rows), [_OB("v")])
    assert str(got.value) == str(want.value)


@pytest.mark.parametrize(
    "values",
    [
        [True, False, True, None, False],  # bool: not a number to ORDER BY
        [(1 << 53) + 1, 1 << 53, 3, None],  # ints float64 collapses
        [(1 << 60) + 1, 0.5, 1 << 60, 2.0],  # an int past 2^53 next to floats
        [b"b", b"a", None, b"c"],  # bytes
    ],
    ids=["bool", "int-past-2^53", "big-int-and-floats", "bytes"],
)
@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_what_only_the_comparison_sort_can_order_still_takes_it(values, desc):
    rows = [{"v": v, "s": "x", "i": i} for i, v in enumerate(values)]
    _stable_check(rows, [_OB("v", desc), _OB("s")], row_stages=1)


def test_no_rows_no_order():
    perm = _order_groups(_Groups(0, {"a": [], "s": []}), [_OB("a"), _OB("s", True)], {})
    assert perm.tolist() == []
