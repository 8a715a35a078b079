"""reduce_group_by keeps the merged groups as columns; a dict a group is made
only where a stage needs `eval_scalar` / `eval_having`. Whole answers must be
what the row-by-row reduce gave (a row env a group -> eval_having -> the
_OrderKey sort -> eval_scalar an item a row): the same rows, the same order,
the same Python type a cell, the same column types and the same JSON bytes.
`reduceRowStages` counts the stages of an answer that left the columns."""

import json

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.common.trace import request_ledger
from pinot_tpu.query import reduce as R
from pinot_tpu.query.context import QueryContext, canonical
from pinot_tpu.query.result import PlainRows, ResultTable

NAMES = ["host_é", "Host_1", "host_10", "host_2", "a", "Z"]


def _row_by_row(ctx, frames):
    """The reduce as it was before the groups stayed columns."""
    frames = [f for f in frames if len(f)]
    if not frames:
        return []
    aliases = R._alias_map(ctx)
    merged, null_on = R._merge_group_frames(ctx, frames)
    n = len(merged)
    fin = []
    for i, a in enumerate(ctx.aggregations):
        parts = [merged[f"a{i}p{j}"].tolist() for j in range(R.parts_of(a.func))]
        fin.append(R._finalize_column(a, tuple(parts) if len(parts) == 2 else parts[0], null_on, n))
    keys = [merged[f"k{i}"].tolist() for i in range(len(ctx.group_by))]
    envs = []
    for ri in range(n):
        env = {}
        for g, vals in zip(ctx.group_by, keys):
            k = vals[ri]
            env[canonical(g)] = None if null_on and R._is_null_partial(k) else k
        for a, vals in zip(ctx.aggregations, fin):
            env[a.name] = vals[ri]
        envs.append(env)
    if ctx.having is not None:
        envs = [e for e in envs if R.eval_having(ctx.having, e, aliases)]
    if ctx.order_by:
        envs = sorted(
            envs,
            key=lambda e: tuple(R._OrderKey(R.eval_scalar(ob.expr, e, aliases), ob.desc) for ob in ctx.order_by),
        )
    envs = envs[ctx.offset : ctx.offset + ctx.limit]
    return [[R.eval_scalar(it.expr, e, aliases) for it in ctx.select_items] for e in envs]


def _frames(ctx, n_frames=3, hours=5, seed=0, null_sums=False, nan_hours=False, null_names=False):
    """Server partials of `GROUP BY name, hour` (or `name` alone): every (name, hour) in two of the frames."""
    rng = np.random.default_rng(seed)
    pairs = [(n, h) for n in NAMES for h in range(hours)]
    out = []
    for f in range(n_frames):
        mine = [p for i, p in enumerate(pairs) if (i + f) % n_frames != 0]
        rng.shuffle(mine)
        names = np.array([p[0] for p in mine], dtype=object)
        if null_names:
            names[::7] = None
        frame = {"k0": names}
        if len(ctx.group_by) == 2:
            hrs = np.array([3_600_000 * p[1] for p in mine], dtype=np.float64 if nan_hours else np.int64)
            if nan_hours:
                hrs[::5] = np.nan
            frame["k1"] = hrs
        for i, a in enumerate(ctx.aggregations):
            func = R.MV_TWIN.get(a.func, a.func)
            if func == "count":
                frame[f"a{i}p0"] = rng.integers(1, 9, len(mine))
            else:
                vals = np.round(rng.uniform(-50, 50, len(mine)), 3)
                if null_sums:
                    vals[[p[0] == "a" for p in mine]] = np.nan  # every partial of the group: SUM finalizes to NULL
                frame[f"a{i}p0"] = vals
                if func == "avg":
                    frame[f"a{i}p1"] = rng.integers(1, 9, len(mine))
        out.append(pd.DataFrame(frame))
    return out


def _same_cell(a, b):
    return type(a) is type(b) and (a == b or (a != a and b != b))


# (id, sql, frame options, reduceRowStages)
CASES = [
    ("plain-refs", "SELECT name, DATETRUNC('hour', ts), AVG(x) FROM t GROUP BY name, DATETRUNC('hour', ts) "
     "ORDER BY DATETRUNC('hour', ts), name LIMIT 60000", {}, 0),
    ("plain-refs-desc", "SELECT DATETRUNC('hour', ts), name, SUM(x), COUNT(*) FROM t GROUP BY name, DATETRUNC('hour', ts) "
     "ORDER BY name DESC, DATETRUNC('hour', ts) DESC LIMIT 60000", {}, 0),
    ("no-order", "SELECT name, MAX(x) FROM t GROUP BY name LIMIT 4", {}, 0),
    ("order-by-alias", "SELECT name AS n, SUM(x) AS total FROM t GROUP BY name ORDER BY total DESC, n LIMIT 10", {}, 0),
    ("order-by-aggregate-not-selected", "SELECT name FROM t GROUP BY name ORDER BY MIN(x), name LIMIT 10", {}, 0),
    ("literal-item-and-key", "SELECT name, 7, 'k', COUNT(*) FROM t GROUP BY name ORDER BY 1 + 1, name DESC LIMIT 10", {}, 1),
    ("post-aggregation", "SELECT name, SUM(x) / COUNT(*) FROM t GROUP BY name ORDER BY SUM(x) / COUNT(*) DESC, name LIMIT 10", {}, 2),
    ("post-aggregation-item-only", "SELECT name, SUM(x) / COUNT(*) AS mean, SUM(x) - 1 FROM t GROUP BY name ORDER BY name LIMIT 3", {}, 1),
    ("post-aggregation-alias-key", "SELECT name, SUM(x) * 2 AS twice FROM t GROUP BY name ORDER BY twice LIMIT 10", {}, 2),
    ("division-by-zero", "SELECT name, SUM(x) / (COUNT(*) - COUNT(*)), COUNT(*) % 3 FROM t GROUP BY name ORDER BY name LIMIT 10", {}, 1),
    ("having", "SELECT name, SUM(x) FROM t GROUP BY name HAVING SUM(x) > 0 ORDER BY name LIMIT 10", {}, 1),
    ("having-null-aggregate", "SET enableNullHandling = true; SELECT name, SUM(x), COUNT(*) FROM t GROUP BY name "
     "HAVING NOT SUM(x) > 1000 ORDER BY SUM(x) DESC, name LIMIT 10", {"null_sums": True}, 1),
    ("having-null-aggregate-kleene", "SET enableNullHandling = true; SELECT name, SUM(x) FROM t GROUP BY name "
     "HAVING SUM(x) > 1000 OR COUNT(*) > 0 ORDER BY SUM(x), name LIMIT 10", {"null_sums": True}, 1),
    ("having-keeps-none", "SELECT name, SUM(x) FROM t GROUP BY name HAVING SUM(x) > 1e9 ORDER BY name LIMIT 10", {}, 1),
    ("null-aggregate-ordered", "SET enableNullHandling = true; SELECT name, DATETRUNC('hour', ts), SUM(x) FROM t "
     "GROUP BY name, DATETRUNC('hour', ts) ORDER BY SUM(x) DESC, name LIMIT 60000", {"null_sums": True}, 0),
    ("offset-inside", "SELECT name, DATETRUNC('hour', ts), COUNT(*) FROM t GROUP BY name, DATETRUNC('hour', ts) "
     "ORDER BY name, DATETRUNC('hour', ts) LIMIT 7 OFFSET 11", {}, 0),
    ("offset-over-the-end", "SELECT name, DATETRUNC('hour', ts), COUNT(*) FROM t GROUP BY name, DATETRUNC('hour', ts) "
     "ORDER BY name, DATETRUNC('hour', ts) LIMIT 10 OFFSET 25", {}, 0),
    ("offset-past-the-end", "SELECT name, COUNT(*) FROM t GROUP BY name ORDER BY name LIMIT 10 OFFSET 100", {}, 0),
    ("offset-no-order", "SELECT name, DATETRUNC('hour', ts), COUNT(*) FROM t GROUP BY name, DATETRUNC('hour', ts) LIMIT 5 OFFSET 4", {}, 0),
    ("zero-groups", "SELECT name, COUNT(*) FROM t GROUP BY name ORDER BY name LIMIT 10", {"n_frames": 0}, 0),
    ("nan-key-null-on", "SET enableNullHandling = true; SELECT name, DATETRUNC('hour', ts), SUM(x) FROM t "
     "GROUP BY name, DATETRUNC('hour', ts) ORDER BY DATETRUNC('hour', ts) DESC, name LIMIT 60000", {"nan_hours": True}, 0),
    ("nan-key-null-off", "SELECT name, DATETRUNC('hour', ts), SUM(x) FROM t GROUP BY name, DATETRUNC('hour', ts) "
     "ORDER BY DATETRUNC('hour', ts), name DESC LIMIT 60000", {"nan_hours": True}, 0),
    ("null-name-null-on", "SET enableNullHandling = true; SELECT name, SUM(x) FROM t GROUP BY name ORDER BY name DESC LIMIT 10",
     {"null_names": True}, 0),
    ("null-name-null-off", "SELECT name, SUM(x) FROM t GROUP BY name ORDER BY name LIMIT 10", {"null_names": True}, 0),
]  # fmt: skip


@pytest.mark.parametrize("sql,opts,row_stages", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_a_whole_answer_is_what_the_row_by_row_reduce_gave(sql, opts, row_stages):
    ctx = QueryContext.from_sql(sql)
    frames = _frames(ctx, **opts)
    want = _row_by_row(ctx, frames)
    with request_ledger("reduce") as led:
        got = R.reduce_group_by(ctx, frames)
    assert led.to_wire()["counters"].get("reduceRowStages", 0) == row_stages
    assert isinstance(got, list) and all(type(r) is list for r in got)
    if opts.get("n_frames") != 0 and "OFFSET 100" not in sql and "1e9" not in sql:
        assert got, "a case that compares nothing"
    old, new = R.build_result(ctx, want), R.build_result(ctx, got)
    assert [len(r) for r in new.rows] == [len(r) for r in old.rows]
    for r_new, r_old in zip(new.rows, old.rows):
        assert all(_same_cell(a, b) for a, b in zip(r_new, r_old)), (r_new, r_old)
    assert not any(isinstance(v, np.generic) for r in new.rows for v in r)
    old_doc, new_doc = old.to_dict()["resultTable"], new.to_dict()["resultTable"]
    assert new_doc["dataSchema"] == old_doc["dataSchema"]
    assert json.dumps(new_doc["rows"]).encode() == json.dumps(old_doc["rows"]).encode()
    # columns of `tolist()` values, str and None: the result table takes the rows as they are
    if got:
        assert isinstance(got, PlainRows) and new.rows is got


def test_a_numpy_scalar_in_a_column_leaves_the_rows_to_the_result_table():
    """A finalizer that answers in numpy scalars (an object column: no `tolist()` made it): not vouched for."""
    from pinot_tpu.query import ast

    groups = R._Groups(2, {"name": ["a", "b"], "est": [np.float64(1.5), np.float64(2.5)], "n": [1, 2]})
    rows = R._project(groups, [ast.Identifier("name"), ast.Identifier("est")], {})
    assert not isinstance(rows, PlainRows) and type(rows[0][1]) is np.float64
    assert [type(v) for v in ResultTable(columns=["name", "est"], rows=rows).rows[0]] == [str, float]
    assert isinstance(R._project(groups, [ast.Identifier("name"), ast.Identifier("n")], {}), PlainRows)


def test_every_other_caller_of_the_result_table_is_converted_as_before():
    res = ResultTable(columns=["a", "b"], rows=[[np.float64(1.5), np.int64(2)], [None, "x"]])
    assert res.rows == [[1.5, 2], [None, "x"]] and [type(v) for v in res.rows[0]] == [float, int]
    assert res.column_types == ["DOUBLE", "LONG"]
    plain = PlainRows([[1.5, 2], [None, "x"]])
    res = ResultTable(columns=["a", "b"], rows=plain)
    assert res.rows is plain and res.column_types == ["DOUBLE", "LONG"]
    assert json.dumps(res.to_dict()["resultTable"]["rows"]) == "[[1.5, 2], [null, \"x\"]]"


def test_a_row_env_is_made_once_an_answer_and_only_for_what_needs_one(monkeypatch):
    made = []
    envs = R._Groups.envs

    def counting(self):
        if self._envs is None:
            made.append(self.n)
        return envs(self)

    monkeypatch.setattr(R._Groups, "envs", counting)
    plain = QueryContext.from_sql("SELECT name, SUM(x) FROM t GROUP BY name ORDER BY SUM(x) DESC, name LIMIT 2")
    R.reduce_group_by(plain, _frames(plain))
    assert made == []
    # HAVING builds them over the merged groups; the stages after it read those, gathered
    both = QueryContext.from_sql(
        "SELECT name, SUM(x) + 1 FROM t GROUP BY name HAVING COUNT(*) > 0 ORDER BY SUM(x) + 1 DESC LIMIT 2"
    )
    R.reduce_group_by(both, _frames(both))
    assert made == [len(NAMES)]
    # a computed select item alone: envs of the two kept rows, not of the groups the LIMIT cut
    made.clear()
    item = QueryContext.from_sql("SELECT name, SUM(x) + 1 FROM t GROUP BY name ORDER BY name LIMIT 2")
    R.reduce_group_by(item, _frames(item))
    assert made == [2]
