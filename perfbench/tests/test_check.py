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
    ok, lines = check.judge(out, exact=True, rel_tol=0.0)
    assert ok is (max(numbers.values()) == 0) and all("limit=" in ln for ln in lines)
