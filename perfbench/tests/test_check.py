"""What decides `correct`: the compared numbers against their limits, and the share of queries that failed."""

import pytest

from perfbench import check
from perfbench.refeval import Spec

SPEC = Spec(where=None, keys=["k"], aggs=[("sum", None)], select=["k", "agg0"], order=[("k", False)])


@pytest.mark.parametrize(
    "answers_ok,failed,attempted,want",
    [
        (True, 0, 450, True),
        (True, 4, 450, True),  # 1 in 100 may fail
        (True, 5, 450, False),
        (True, 1, 42, False),  # a window of 42 queries allows none
        (True, 343, 400, False),  # a broker that shed most of the window (PERF.md, PR 23)
        (False, 0, 450, False),
    ],
)
def test_a_run_with_failed_queries_is_not_correct(answers_ok, failed, attempted, want):
    assert check.run_correct(answers_ok, failed, attempted) is want


@pytest.mark.parametrize(
    "got,numbers",
    [
        ([["a", 10], ["b", 20]], {"rows_missing_or_extra": 0, "order_violations": 0, "max_abs_diff": 0.0}),
        ([["a", 10], ["b", 21]], {"rows_missing_or_extra": 0, "order_violations": 0, "max_abs_diff": 1.0}),
        ([["b", 20], ["a", 10]], {"rows_missing_or_extra": 0, "order_violations": 1, "max_abs_diff": 0.0}),
        ([["a", 10]], {"rows_missing_or_extra": 1, "order_violations": 0, "max_abs_diff": 0.0}),
        ([["a", None], ["b", 20]], {"rows_missing_or_extra": 1, "order_violations": 0, "max_abs_diff": 0.0}),
    ],
)
def test_each_number_of_a_compared_answer(got, numbers):
    out = check.compare_rows(SPEC, got, [["a", 10], ["b", 20]])
    assert {k: out[k] for k in numbers} == numbers
    ok, lines, _ = check.judge(out, exact=True, rel_tol=0.0)
    assert ok is (max(numbers.values()) == 0) and all("limit=" in ln for ln in lines)


# ---------------------------------------------------------------------------
# the reference's aggregates: a sum adds up over the segments, an extreme stands
# ---------------------------------------------------------------------------

import numpy as np

from perfbench import refeval
from perfbench.refeval import Column

VOCAB = np.array(["a", "b", "c"])
EXTREMES = Spec(
    where=lambda c, p: c["v"].codes >= p["floor"], keys=["k"],
    aggs=[("min", lambda c: c["v"].codes), ("max", lambda c: c["v"].codes), ("sum", lambda c: c["v"].codes), ("count", None), ("avg", lambda c: c["v"].codes)],
    select=["k", "agg0", "agg1", "agg2", "agg3", "agg4"], order=[("k", False)], exact=False,
)  # fmt: skip
SEGMENTS = [
    {"k": Column(np.array([0, 0, 1, 2]), VOCAB), "v": Column(np.array([5.0, -3.0, 7.5, 1.0]))},
    {"k": Column(np.array([1, 1, 0]), VOCAB), "v": Column(np.array([9.25, -8.0, 4.0]))},
]


@pytest.mark.parametrize("acc", [np.float64, np.float32])
def test_a_groups_extreme_stands_where_its_sum_adds_up(acc):
    """`min | max` beside `sum | count | avg`: a segment's partial keeps each group's extreme, the merge keeps the
    smaller or the larger of two and adds the others, and the float32 control, which is about how sums are added up, leaves them alone."""
    parts = [refeval.partial(EXTREMES, {"floor": -100.0}, cols, acc) for cols in SEGMENTS]
    assert parts[0]["kinds"] == ["min", "max", "sum", "count", "avg"]
    assert parts[0]["groups"] == {0: [-3.0, 5.0, 2.0, 2.0, 2.0, 2], 1: [7.5, 7.5, 7.5, 1.0, 7.5, 1], 2: [1.0, 1.0, 1.0, 1.0, 1.0, 1]}
    merged = refeval.merge(parts)
    assert merged["n"] == 7 and merged["groups"][1] == [-8.0, 9.25, 8.75, 3.0, 8.75, 3]
    rows = refeval.finish(EXTREMES, merged, {"k": VOCAB})
    assert rows == [["a", -3.0, 5.0, 6.0, 3.0, 2.0], ["b", -8.0, 9.25, 8.75, 3.0, 8.75 / 3], ["c", 1.0, 1.0, 1.0, 1.0, 1.0]]


def test_a_filter_moves_an_extreme_and_no_row_leaves_its_identity():
    parts = [refeval.partial(EXTREMES, {"floor": 4.5}, cols) for cols in SEGMENTS]
    rows = refeval.finish(EXTREMES, refeval.merge(parts), {"k": VOCAB})
    assert rows == [["a", 5.0, 5.0, 5.0, 1.0, 5.0], ["b", 7.5, 9.25, 16.75, 2.0, 8.375]]  # group c matched nothing and is no row
    # without GROUP BY a query answers one row even over no rows: the extremes read their identities, as the program's MIN and MAX do
    alone = Spec(where=EXTREMES.where, aggs=EXTREMES.aggs[:4], select=["agg0", "agg1", "agg2", "agg3"])
    nothing = [refeval.partial(alone, {"floor": 1e9}, cols) for cols in SEGMENTS]
    assert refeval.finish(alone, refeval.merge(nothing), {}) == [[float("inf"), float("-inf"), 0.0, 0.0]]
    one = refeval.merge([refeval.partial(alone, {"floor": 9.0}, cols) for cols in SEGMENTS])  # one segment matched nothing
    assert refeval.finish(alone, one, {}) == [[9.25, 9.25, 9.25, 1.0]]


def test_a_wrong_extreme_is_a_difference_like_any_other():
    want = refeval.finish(EXTREMES, refeval.merge([refeval.partial(EXTREMES, {"floor": -100.0}, cols) for cols in SEGMENTS]), {"k": VOCAB})
    got = [list(r) for r in want]
    got[1][1] = -7.0  # group b's MIN, off by one
    numbers = check.compare_rows(EXTREMES, got, want)
    assert numbers["max_rel_err"] == pytest.approx(1 / 8) and not check.judge(numbers, exact=False, rel_tol=1e-11)[0]
    assert check.judge(check.compare_rows(EXTREMES, want, want), exact=False, rel_tol=1e-11)[0]
