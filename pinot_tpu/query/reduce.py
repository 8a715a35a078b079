"""Broker-side reduce: merge per-segment partials into a final ResultTable.

Reference parity: BrokerReduceService.reduceOnDataTable (pinot-core/.../query/
reduce/BrokerReduceService.java:54,61) and the per-type reducers
(GroupByDataTableReducer, AggregationDataTableReducer, SelectionDataTableReducer)
plus HavingFilterHandler / PostAggregationHandler. Partials arrive as plain
host structures (scalars / pandas DataFrames), whether they came off the
device path or the host fallback executor — one merge path for both.

Partial formats:
  AGGREGATION: list aligned with ctx.aggregations; entries by func:
      count -> int, sum -> float, min/max -> float, avg -> (sum, count),
      minmaxrange -> (min, max), distinctcount -> set of values
  GROUP_BY / DISTINCT: DataFrame with key columns k0..k{n-1} and partial
      columns a{i}p{j} (agg i, part j)
  SELECTION: DataFrame with positional columns c0..c{n-1}
  SELECTION_ORDER_BY: same + "__key" sort column
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np
import pandas as pd

from pinot_tpu.common.trace import count, span
from pinot_tpu.query import ast
from pinot_tpu.query import funnel as _funnel
from pinot_tpu.query.context import QueryContext, canonical
from pinot_tpu.query.result import PlainRows, ResultTable

# number of partial slots per aggregation function
PART_COUNTS = {"avg": 2, "minmaxrange": 2, "avgmv": 2, "minmaxrangemv": 2}

# MV aggregations produce partials shaped exactly like their single-value
# twins (CountMVAggregationFunction et al. reuse the SV merge logic in the
# reference too) — reduce-side handling maps through this table.
MV_TWIN = {
    "countmv": "count",
    "summv": "sum",
    "minmv": "min",
    "maxmv": "max",
    "avgmv": "avg",
    "distinctcountmv": "distinctcount",
    "minmaxrangemv": "minmaxrange",
    "distinctsummv": "distinctsum",
    "distinctavgmv": "distinctavg",
    "distinctcountbitmapmv": "distinctcountbitmap",
    "distinctcounthllmv": "distinctcounthll",
    "percentilemv": "percentile",
    "percentileestmv": "percentileest",
    "percentiletdigestmv": "percentiletdigest",
    "percentilekllmv": "percentilekll",
    "percentilerawestmv": "percentilerawest",
    "percentilerawtdigestmv": "percentilerawtdigest",
    "percentilerawkllmv": "percentilerawkll",
    "distinctcounthllplusmv": "distinctcounthllplus",
    "distinctcountrawhllmv": "distinctcountrawhll",
    "distinctcountrawhllplusmv": "distinctcountrawhllplus",
}


def parts_of(func: str) -> int:
    return PART_COUNTS.get(func, 1)


# ---------------------------------------------------------------------------
# scalar expression evaluation over an environment (post-aggregation, having,
# order-by on merged results)
# ---------------------------------------------------------------------------


def _column_name(expr: ast.Expr, names, aliases: dict[str, ast.Expr] | None = None) -> str | None:
    """The one of `names` (an env's keys, the groups' columns) that `expr`
    reads as it stands, or None where it is a constant or has to be computed:
    an Identifier by its name or down its alias chain, anything else by its
    canonical text (a whole expression may itself be a group key, e.g. GROUP
    BY year-1990; an aggregation is a column under its canonical name)."""
    if isinstance(expr, ast.Literal):
        return None
    if isinstance(expr, ast.Identifier):
        if expr.name in names:
            return expr.name
        if aliases and expr.name in aliases:
            return _column_name(aliases[expr.name], names, aliases)
        return None
    cn = canonical(expr)
    if cn in names:
        return cn
    # COUNT(DISTINCT x) was canonicalized to distinctcount(x)
    if isinstance(expr, ast.FunctionCall) and expr.name == "count" and expr.distinct:
        alt = canonical(ast.FunctionCall("distinctcount", expr.args))
        if alt in names:
            return alt
    return None


def eval_scalar(expr: ast.Expr, env: dict[str, Any], aliases: dict[str, ast.Expr] | None = None):
    if isinstance(expr, ast.Literal):
        return expr.value
    name = _column_name(expr, env, aliases)
    if name is not None:
        return env[name]
    if isinstance(expr, ast.Identifier):
        if aliases and expr.name in aliases:
            return eval_scalar(aliases[expr.name], env, aliases)  # an alias of something computed
        raise KeyError(f"unknown reference {expr.name!r} in post-aggregation context")
    if isinstance(expr, ast.FunctionCall):
        raise KeyError(f"aggregation {canonical(expr)!r} not computed")
    if isinstance(expr, ast.BinaryOp):
        l = eval_scalar(expr.left, env, aliases)
        r = eval_scalar(expr.right, env, aliases)
        if l is None or r is None:
            return None  # null propagates through post-aggregation arithmetic
        if expr.op == "+":
            return l + r
        if expr.op == "-":
            return l - r
        if expr.op == "*":
            return l * r
        if expr.op == "/":
            return float(l) / float(r) if r != 0 else float("inf") if l > 0 else float("-inf") if l < 0 else float("nan")
        if expr.op == "%":
            return math.fmod(l, r)
    raise ValueError(f"cannot evaluate {expr} at reduce stage")


def eval_having(f: ast.FilterExpr, env: dict[str, Any], aliases: dict[str, ast.Expr] | None = None) -> "bool | None":
    """Three-valued HAVING evaluation: returns None for unknown (a NULL
    aggregate compared to anything). The filtering caller treats None as
    falsy, but NOT(unknown) stays unknown (Kleene), so unknown must
    propagate rather than collapse to False early."""
    if isinstance(f, ast.And):
        vals = [eval_having(c, env, aliases) for c in f.children]
        if any(v is False for v in vals):
            return False
        return None if any(v is None for v in vals) else True
    if isinstance(f, ast.Or):
        vals = [eval_having(c, env, aliases) for c in f.children]
        if any(v is True for v in vals):
            return True
        return None if any(v is None for v in vals) else False
    if isinstance(f, ast.Not):
        v = eval_having(f.child, env, aliases)
        return None if v is None else not v
    if isinstance(f, ast.Compare):
        l = eval_scalar(f.left, env, aliases)
        r = eval_scalar(f.right, env, aliases)
        if l is None or r is None:
            return None  # NULL comparison is unknown
        return {
            ast.CompareOp.EQ: lambda: l == r,
            ast.CompareOp.NEQ: lambda: l != r,
            ast.CompareOp.LT: lambda: l < r,
            ast.CompareOp.LTE: lambda: l <= r,
            ast.CompareOp.GT: lambda: l > r,
            ast.CompareOp.GTE: lambda: l >= r,
        }[f.op]()
    if isinstance(f, ast.Between):
        v = eval_scalar(f.expr, env, aliases)
        if v is None:
            return None  # unknown
        ok = eval_scalar(f.low, env, aliases) <= v <= eval_scalar(f.high, env, aliases)
        return not ok if f.negated else ok
    if isinstance(f, ast.In):
        v = eval_scalar(f.expr, env, aliases)
        if v is None:
            return None  # unknown
        vals = {eval_scalar(x, env, aliases) for x in f.values}
        return (v not in vals) if f.negated else (v in vals)
    if isinstance(f, ast.DistinctFrom):
        l = eval_scalar(f.left, env, aliases)
        r = eval_scalar(f.right, env, aliases)
        ln = _is_null_partial(l)
        rn = _is_null_partial(r)
        m = (ln != rn) or (not ln and not rn and l != r)
        return not m if f.negated else m
    if isinstance(f, ast.BoolAssert):
        v = eval_scalar(f.expr, env, aliases)
        # SQL assertion: never unknown — null fails IS TRUE/FALSE, passes NOT
        truthy = not _is_null_partial(v) and bool(v) and str(v).lower() not in ("false", "0")
        pos = truthy if f.want_true else (not _is_null_partial(v) and not truthy)
        return not pos if f.negated else pos
    raise ValueError(f"unsupported HAVING predicate: {f}")


# ---------------------------------------------------------------------------
# merge functions
# ---------------------------------------------------------------------------


def _is_null_partial(x) -> bool:
    """True when a partial is the null-handling "no non-null rows" sentinel:
    None (host paths) or NaN (device kernels / pandas min_count merges)."""
    return x is None or (isinstance(x, float) and x != x)


def _merge_agg_partials(func: str, a, b, null_on: bool = False):
    from pinot_tpu.query.aggregates import EXT_AGGS
    from pinot_tpu.query.funnel import FUNNEL_AGGS, merge as funnel_merge

    if func in FUNNEL_AGGS:
        return funnel_merge(func, a, b)
    func = MV_TWIN.get(func, func)
    if func in EXT_AGGS:
        return EXT_AGGS[func].merge(a, b)
    if func == "sum":
        # null partial (see _is_null_partial) = "no non-null rows seen":
        # identity under merge, finalized to NULL only if it survives.
        # None is always the sentinel; NaN only under null handling (with
        # null handling OFF a stored-NaN DOUBLE sum must keep IEEE
        # propagation — review r4)
        if a is None or (null_on and _is_null_partial(a)):
            return b
        if b is None or (null_on and _is_null_partial(b)):
            return a
        return a + b
    if func == "count":
        return a + b
    if func == "min":
        return min(a, b)
    if func == "max":
        return max(a, b)
    if func == "avg":
        return (a[0] + b[0], a[1] + b[1])
    if func == "minmaxrange":
        return (min(a[0], b[0]), max(a[1], b[1]))
    if func in ("distinctcount", "distinctcountbitmap"):
        return a | b
    if func == "distinctcounthll":
        if isinstance(a, (set, frozenset)):
            return a | b
        return np.maximum(a, b)
    if func == "percentileest":
        if isinstance(a, tuple) and len(a) == 3:  # (hist counts, lo, hi)
            return (a[0] + b[0], a[1], a[2])
        return np.concatenate([a, b])  # exact-values fallback mode
    if func == "percentiletdigest":
        from pinot_tpu.query.quantile_sketch import td_merge

        return td_merge(a, b)
    if func == "percentile":
        return np.concatenate([a, b])
    if func == "mode":
        out = dict(a)
        for k, v in b.items():
            out[k] = out.get(k, 0) + v
        return out
    raise AssertionError(func)


def _exact_percentile(values: np.ndarray, pct: float) -> float:
    from pinot_tpu.query.aggregates import exact_percentile

    return exact_percentile(values, pct)


def _finalize(a, p, null_on: bool = False):
    """Finalize a merged partial. `a` is the AggregationInfo. Under
    enableNullHandling (null_on), aggregations that never saw a non-null
    value yield NULL instead of the neutral default — reference
    NullableSingleInputAggregationFunction keeps an Object holder that
    stays null over all-null input (SumAggregationFunction.java with
    nullHandlingEnabled)."""
    from pinot_tpu.query.sketches import hist_estimate, hll_estimate

    from pinot_tpu.query.aggregates import EXT_AGGS

    from pinot_tpu.query.funnel import FUNNEL_AGGS, finalize as funnel_finalize

    if a.func in FUNNEL_AGGS:
        return funnel_finalize(a.func, p, a.extra)
    func = MV_TWIN.get(a.func, a.func)
    if func in EXT_AGGS:
        return EXT_AGGS[func].finalize(p, a.extra)
    if func == "count":
        return int(p)
    if func == "sum":
        if null_on and _is_null_partial(p):
            return None
        return float(p)
    if func in ("min", "max"):
        v = float(p)
        if null_on and (_is_null_partial(v) or v == (math.inf if func == "min" else -math.inf)):
            return None
        return v
    if func == "avg":
        if not p[1]:
            return None if null_on else float("-inf")  # Pinot: avg of 0 docs -> default
        s = p[0]
        if null_on and _is_null_partial(s):
            return None
        return float(s) / p[1]
    if func == "minmaxrange":
        lo, hi = float(p[0]), float(p[1])
        if null_on and (_is_null_partial(lo) or _is_null_partial(hi) or (lo == math.inf and hi == -math.inf)):
            return None
        return hi - lo
    if func in ("distinctcount", "distinctcountbitmap"):
        return len(p)
    if func == "distinctcounthll":
        # grouped/host partials are exact sets; device partials are registers
        return len(p) if isinstance(p, (set, frozenset)) else hll_estimate(np.asarray(p))
    if func == "percentileest":
        if isinstance(p, tuple):
            return hist_estimate(np.asarray(p[0]), p[1], p[2], a.extra[0])
        if null_on and len(p) == 0:
            return None
        return _exact_percentile(p, a.extra[0])
    if func == "percentiletdigest":
        from pinot_tpu.query.quantile_sketch import td_quantile

        if null_on and p[1] == 0:
            return None  # empty digest under null handling
        return td_quantile(p, a.extra[0])
    if func == "percentile":
        if null_on and len(p) == 0:
            return None
        return _exact_percentile(p, a.extra[0])
    if func == "mode":
        if not p:
            return None if null_on else float("-inf")
        best = max(p.values())
        return float(min(k for k, v in p.items() if v == best))  # Pinot MODE ties -> MIN
    raise AssertionError(func)


def _finalize_column(a, parts, null_on: bool, n: int) -> list:
    """Finalize one aggregation over ALL merged groups at once. The scalar
    reducers (count/sum/min/max/avg/minmaxrange) vectorize to one numpy pass
    + tolist — identical results to per-row _finalize, which dominated the
    broker reduce at thousands of groups. Object-valued partials (sets,
    sketches, or columns where None leaked into a numeric partial) fall back
    to the per-row path via the TypeError/ValueError guard."""
    func = MV_TWIN.get(a.func, a.func)
    try:
        if func == "count":
            return np.asarray(parts, dtype=np.int64).tolist()
        if func == "sum":
            arr = np.asarray(parts, dtype=np.float64)
            out = arr.tolist()
            if null_on:
                for j in np.flatnonzero(np.isnan(arr)):
                    out[j] = None
            return out
        if func in ("min", "max"):
            arr = np.asarray(parts, dtype=np.float64)
            out = arr.tolist()
            if null_on:
                bad = np.isnan(arr) | (arr == (np.inf if func == "min" else -np.inf))
                for j in np.flatnonzero(bad):
                    out[j] = None
            return out
        if func == "avg":
            s = np.asarray(parts[0], dtype=np.float64)
            c = np.asarray(parts[1], dtype=np.float64)
            with np.errstate(divide="ignore", invalid="ignore"):
                out = (s / c).tolist()
            zero = c == 0
            if null_on:
                for j in np.flatnonzero(zero | np.isnan(s)):
                    out[j] = None
            else:
                for j in np.flatnonzero(zero):
                    out[j] = float("-inf")  # Pinot: avg of 0 docs -> default
            return out
        if func == "minmaxrange":
            lo = np.asarray(parts[0], dtype=np.float64)
            hi = np.asarray(parts[1], dtype=np.float64)
            out = (hi - lo).tolist()
            if null_on:
                bad = np.isnan(lo) | np.isnan(hi) | ((lo == np.inf) & (hi == -np.inf))
                for j in np.flatnonzero(bad):
                    out[j] = None
            return out
    except (TypeError, ValueError):
        pass
    if parts_of(a.func) == 2:
        return [_finalize(a, (parts[0][ri], parts[1][ri]), null_on) for ri in range(n)]
    return [_finalize(a, parts[ri], null_on) for ri in range(n)]


def _alias_map(ctx: QueryContext) -> dict[str, ast.Expr]:
    return {it.alias: it.expr for it in ctx.select_items if it.alias}


def reduce_aggregation(ctx: QueryContext, partials: list[list]) -> list[list]:
    """Merge AGGREGATION partials -> single result row per the select list."""
    from pinot_tpu.query.context import null_handling_enabled

    null_on = null_handling_enabled(ctx.options)
    with span("broker.reduce.merge", frames=len(partials), rows=1):
        if not partials:
            merged = None
        else:
            merged = list(partials[0])
            for p in partials[1:]:
                merged = [
                    _merge_agg_partials(a.func, m, x, null_on)
                    for a, m, x in zip(ctx.aggregations, merged, p)
                ]
    with span("broker.reduce.rows", rows=1):
        env: dict[str, Any] = {}
        if merged is None:
            # zero segments contributed (all pruned): under null handling the
            # SUM holder was never set -> None partial -> NULL
            merged = [
                None if null_on and MV_TWIN.get(a.func, a.func) == "sum" else _empty_partial(a.func, a.extra)
                for a in ctx.aggregations
            ]
        for a, p in zip(ctx.aggregations, merged):
            env[a.name] = _finalize(a, p, null_on)
        aliases = _alias_map(ctx)
        row = [eval_scalar(it.expr, env, aliases) for it in ctx.select_items]
    return [row]


def _empty_partial(func: str, extra: tuple = ()):
    from pinot_tpu.query.aggregates import EXT_AGGS
    from pinot_tpu.query.funnel import FUNNEL_AGGS, empty_partial as funnel_empty

    if func in FUNNEL_AGGS:
        return funnel_empty(func, extra)
    func = MV_TWIN.get(func, func)
    if func in EXT_AGGS:
        return EXT_AGGS[func].empty(extra)
    if func == "percentiletdigest":
        from pinot_tpu.query.quantile_sketch import td_create

        return td_create()
    if func == "count":
        return 0
    if func == "sum":
        return 0.0
    if func == "min":
        return float("inf")
    if func == "max":
        return float("-inf")
    if func == "avg":
        return (0.0, 0)
    if func == "minmaxrange":
        return (float("inf"), float("-inf"))
    if func in ("distinctcount", "distinctcountbitmap", "distinctcounthll"):
        return set()
    if func in ("percentile", "percentileest"):
        return np.zeros(0)
    if func == "mode":
        return {}
    raise AssertionError(func)


def reduce_group_by(ctx: QueryContext, frames: list[pd.DataFrame]) -> list[list]:
    """Merge GROUP BY partials -> rows. The merged groups stay columns from
    pandas' merge on; a row is made once, at the end, for the groups OFFSET /
    LIMIT keep. Each stage under its own span (the children of
    `broker.reduce`; a stage the query has not, has no span). A stage that
    could not stay in columns counts one `reduceRowStages`."""
    frames = [f for f in frames if len(f)]
    if not frames:
        return []
    aliases = _alias_map(ctx)
    with span("broker.reduce.merge", frames=len(frames)) as sp:
        merged, null_on = _merge_group_frames(ctx, frames)
        sp.set_attr("rows", len(merged))
    with span("broker.reduce.rows", rows=len(merged)):
        groups = _group_columns(ctx, merged, null_on)
    if ctx.having is not None:
        with span("broker.reduce.having", rows=groups.n):
            count("reduceRowStages")  # eval_having's three-valued logic reads a row env
            keep = [i for i, e in enumerate(groups.envs()) if eval_having(ctx.having, e, aliases)]
            groups = groups.take(np.asarray(keep, dtype=np.intp))
    order = np.arange(groups.n)
    if ctx.order_by:
        with span("broker.reduce.order", rows=groups.n, keys=len(ctx.order_by)):
            order = _order_groups(groups, ctx.order_by, aliases)
    with span("broker.reduce.project") as sp:
        groups = groups.take(order[ctx.offset : ctx.offset + ctx.limit])  # the cut first: only the kept rows are made
        sp.set_attr("rows", groups.n)
        return _project(groups, [it.expr for it in ctx.select_items], aliases)


def _merge_group_frames(ctx: QueryContext, frames: list[pd.DataFrame]) -> tuple[pd.DataFrame, bool]:
    """One frame of the servers' partials, a row a group: (merged, null handling on)."""
    key_cols = [f"k{i}" for i in range(len(ctx.group_by))]
    df = pd.concat(frames, ignore_index=True)
    # merge partials per group: scalar reducers via .agg, object-valued
    # reducers (sets / value arrays / counters) via .apply (pandas agg
    # rejects non-scalar returns)
    agg_map: dict[str, Any] = {}
    apply_map: dict[str, Any] = {}

    def _merge_counters(s):
        out: dict = {}
        for c in s:
            for k, v in c.items():
                out[k] = out.get(k, 0) + v
        return out

    from pinot_tpu.query.context import null_handling_enabled

    null_on = null_handling_enabled(ctx.options)
    for i, a in enumerate(ctx.aggregations):
        func = MV_TWIN.get(a.func, a.func)
        if func in ("count", "sum", "avg"):
            for j in range(parts_of(a.func)):
                if null_on and func in ("sum", "avg") and j == 0:
                    # min_count=1: an all-NaN (all-null) group merges to NaN,
                    # which _finalize turns into NULL — plain "sum" would
                    # collapse it to 0
                    agg_map[f"a{i}p{j}"] = lambda s: s.sum(min_count=1)
                else:
                    agg_map[f"a{i}p{j}"] = "sum"
        elif func == "min":
            agg_map[f"a{i}p0"] = "min"
        elif func == "max":
            agg_map[f"a{i}p0"] = "max"
        elif func == "minmaxrange":
            agg_map[f"a{i}p0"] = "min"
            agg_map[f"a{i}p1"] = "max"
        elif func in ("distinctcount", "distinctcountbitmap"):
            apply_map[f"a{i}p0"] = lambda s: set().union(*s)  # single-pass
        elif func in ("distinctcounthll", "percentileest"):
            # shared merge table: HLL register rows / histogram tuples and
            # their legacy set / exact-value forms all merge correctly
            from functools import reduce as _reduce

            apply_map[f"a{i}p0"] = lambda s, _f=func: _reduce(
                lambda x, y: _merge_agg_partials(_f, x, y), s
            )
        elif func == "percentiletdigest":
            from functools import reduce as _reduce

            from pinot_tpu.query.quantile_sketch import td_merge as _tdm

            apply_map[f"a{i}p0"] = lambda s, _m=_tdm: _reduce(_m, s)
        elif func == "percentile":
            apply_map[f"a{i}p0"] = lambda s: np.concatenate([np.asarray(x, dtype=np.float64) for x in s])
        elif func == "mode":
            apply_map[f"a{i}p0"] = _merge_counters
        elif func in _funnel.FUNNEL_AGGS:
            from functools import reduce as _reduce

            apply_map[f"a{i}p0"] = lambda s, _f=a.func: _reduce(
                lambda x, y: _funnel.merge(_f, x, y), s
            )
        else:
            from functools import reduce as _reduce

            from pinot_tpu.query.aggregates import EXT_AGGS

            if func not in EXT_AGGS:
                raise AssertionError(a.func)
            apply_map[f"a{i}p0"] = lambda s, _m=EXT_AGGS[func].merge: _reduce(_m, s)
    if agg_map or apply_map:
        g = df.groupby(key_cols, sort=False, dropna=False)
        merged = g.agg(agg_map).reset_index() if agg_map else g.size().reset_index().drop(columns=[0])
        for col, fn in apply_map.items():
            merged[col] = g[col].apply(fn).values
    else:
        merged = df.drop_duplicates(subset=key_cols).reset_index(drop=True)
    return merged, null_on


class _Groups:
    """The merged groups as columns: for each group key and each aggregation,
    under the name a row env gives it (`canonical(g)`, `a.name`), the list of
    its Python values. ORDER BY and the select list resolve an expression to a
    column once (`values`, by `_column_name` as `eval_scalar` does); what names
    no column is left to `eval_scalar` / `eval_having` over row envs, made once
    an answer and only then (`envs`)."""

    __slots__ = ("n", "cols", "_envs")

    def __init__(self, n: int, cols: dict[str, list]):
        self.n = n
        self.cols = cols
        self._envs: list[dict] | None = None

    def values(self, expr: ast.Expr, aliases) -> tuple[list, bool]:
        """(`expr` over every group, whether it took a row env a group: an
        expression that is neither a column nor a constant is `eval_scalar`'s,
        and the stage counts that)."""
        if isinstance(expr, ast.Literal):
            return [expr.value] * self.n, False
        name = _column_name(expr, self.cols, aliases)
        if name is not None:
            return self.cols[name], False
        return [eval_scalar(expr, e, aliases) for e in self.envs()], True

    def envs(self) -> list[dict]:
        """A dict a group, as `eval_scalar` / `eval_having` read one: the only place one is made."""
        if self._envs is None:
            names = list(self.cols)
            self._envs = [dict(zip(names, vals)) for vals in zip(*self.cols.values())]
        return self._envs

    def take(self, idx: np.ndarray) -> _Groups:
        """The groups `idx` names, in its order."""
        at = idx.tolist()

        def pick(vals):
            return [vals[i] for i in at]

        out = _Groups(len(at), {k: pick(v) for k, v in self.cols.items()})
        if self._envs is not None:
            out._envs = pick(self._envs)
        return out


def _group_columns(ctx: QueryContext, merged: pd.DataFrame, null_on: bool) -> _Groups:
    """The merged frame as `_Groups`: keys as they are, aggregates finalized, both a column at a time."""
    n_rows = len(merged)
    cols: dict[str, list] = {}
    # Series.tolist(): plain Python values of the column's own dtype (iterrows()
    # would coerce a row to one type, and cost ~70us a group)
    for i, g in enumerate(ctx.group_by):
        vals = merged[f"k{i}"].tolist()
        if null_on:  # NaN key = the null group (host NaN substitution)
            vals = [None if _is_null_partial(k) else k for k in vals]
        cols[canonical(g)] = vals
    for i, a in enumerate(ctx.aggregations):
        if parts_of(a.func) == 2:
            parts = (merged[f"a{i}p0"].tolist(), merged[f"a{i}p1"].tolist())
        else:
            parts = merged[f"a{i}p0"].tolist()
        cols[a.name] = _finalize_column(a, parts, null_on, n_rows)
    return _Groups(n_rows, cols)


_EXACT_INT = 1 << 53  # past it float64 collapses distinct ints
_NUMBER_TYPES = (int, float, np.integer, np.floating)


def _lexsort_key(vals: list, desc: bool) -> tuple[np.ndarray, np.ndarray] | None:
    """One ORDER BY key as the two float64 columns `np.lexsort` reads: the null
    mask (nulls rank largest: first under DESC, last under ASC) and the value,
    negated under DESC. Numbers ride as they are; strings as the rank of each
    value among the column's distinct values, which Python's `sorted` orders
    (so the collation is `_OrderKey`'s: code points). None for a column only
    `_OrderKey` can order: mixed types, bool, bytes, an int float64 cannot hold."""
    kinds = set(map(type, vals)) - {type(None)}
    if str in kinds and kinds <= {str, float}:
        codes, distinct = pd.factorize(np.asarray(vals, dtype=object))  # None and NaN: -1, the nulls
        distinct = distinct.tolist()
        if float in kinds and not all(type(d) is str for d in distinct):
            return None  # a number among the strings (a NaN is a null, and no distinct value)
        rank = np.empty(len(distinct), np.float64)
        rank[sorted(range(len(distinct)), key=distinct.__getitem__)] = np.arange(len(distinct))
        null = codes < 0
        arr = rank[codes]
    elif all(issubclass(k, _NUMBER_TYPES) and k is not bool for k in kinds):
        try:
            arr = np.asarray(vals, dtype=np.float64)  # None -> nan
        except OverflowError:
            return None
        null = np.isnan(arr)
        if any(issubclass(k, (int, np.integer)) for k in kinds) and (np.abs(arr[~null]) >= _EXACT_INT).any():
            return None
    else:
        return None
    return (null != desc).astype(np.float64), np.where(null, 0.0, -arr if desc else arr)


def _order_groups(groups: _Groups, order_by, aliases) -> np.ndarray:
    """The permutation ORDER BY puts the groups in: every key a numeric column
    or a rank (`_lexsort_key`), then one stable `np.lexsort`. Where a key can be
    neither, the general `_OrderKey` sort over the same pre-evaluated columns,
    over indices; it is stable too, so ties keep the merge's order either way."""
    cols, lex = [], []
    rowwise = False
    for ob in order_by:
        vals, by_row = groups.values(ob.expr, aliases)
        rowwise |= by_row
        cols.append(vals)
        if lex is not None:
            key = _lexsort_key(vals, ob.desc)
            lex = lex + list(key) if key is not None else None  # None from the first key only `_OrderKey` can order
    if lex is not None:
        # np.lexsort: LAST key is primary -> reversed, ob_1's null mask
        # dominates, then its values, then ob_2's mask / values, ...
        order = np.lexsort(lex[::-1])
    else:
        rowwise = True
        descs = [ob.desc for ob in order_by]
        order = np.asarray(
            sorted(range(groups.n), key=lambda i: tuple(_OrderKey(c[i], d) for c, d in zip(cols, descs))),
            dtype=np.intp,
        )
    if rowwise:
        count("reduceRowStages")
    return order


def _project(groups: _Groups, exprs: list, aliases) -> list[list]:
    """The select list over `groups`, as row lists: a column an item, one `zip`.
    `PlainRows` where no column holds a numpy scalar (each is asked for its types)."""
    out, plain, rowwise = [], True, False
    for expr in exprs:
        vals, by_row = groups.values(expr, aliases)
        rowwise |= by_row
        plain = plain and not any(issubclass(k, np.generic) for k in set(map(type, vals)))
        out.append(vals)
    if rowwise:
        count("reduceRowStages")
    rows = map(list, zip(*out))
    return PlainRows(rows) if plain else list(rows)


class _OrderKey:
    """Comparable wrapper implementing DESC via reversed comparison."""

    __slots__ = ("v", "desc")

    def __init__(self, v, desc):
        self.v = v
        self.desc = desc

    def __lt__(self, other):
        a, b = (other.v, self.v) if self.desc else (self.v, other.v)
        # nulls rank as the largest value (OrderByExpressionContext default):
        # None/NaN is never < anything; anything non-null is < None/NaN
        # (NaN = the device kernels' null sentinel — must agree with the
        # np.lexsort fast path, which ranks it with None)
        if _is_null_partial(a):
            return False
        if _is_null_partial(b):
            return True
        return a < b

    def __eq__(self, other):
        if _is_null_partial(self.v) or _is_null_partial(other.v):
            return _is_null_partial(self.v) and _is_null_partial(other.v)
        return self.v == other.v


def reduce_distinct(ctx: QueryContext, frames: list[pd.DataFrame]) -> list[list]:
    frames = [f for f in frames if len(f)]
    if not frames:
        return []
    nkeys = len(ctx.select_items)
    key_cols = [f"k{i}" for i in range(nkeys)]
    with span("broker.reduce.merge", frames=len(frames)) as sp:
        df = pd.concat(frames, ignore_index=True).drop_duplicates(subset=key_cols)
        sp.set_attr("rows", len(df))
    if ctx.order_by:
        with span("broker.reduce.order", rows=len(df), keys=len(ctx.order_by)):
            aliases = _alias_map(ctx)
            name_of = {canonical(it.expr): f"k{i}" for i, it in enumerate(ctx.select_items)}
            by, asc = [], []
            for ob in ctx.order_by:
                cn = canonical(ob.expr)
                if cn not in name_of and aliases and cn in aliases:
                    cn = canonical(aliases[cn])
                if cn not in name_of:
                    raise ValueError(f"DISTINCT ORDER BY must reference selected columns: {cn}")
                by.append(name_of[cn])
                asc.append(not ob.desc)
            from pinot_tpu.common.sorting import sort_nulls_largest

            df = sort_nulls_largest(df, by, asc)
    with span("broker.reduce.project") as sp:
        df = df.iloc[ctx.offset : ctx.offset + ctx.limit]
        sp.set_attr("rows", len(df))
        return df[key_cols].values.tolist()


def reduce_selection(ctx: QueryContext, frames: list[pd.DataFrame]) -> list[list]:
    frames = [f for f in frames if len(f)]
    if not frames:
        return []
    with span("broker.reduce.merge", frames=len(frames)) as sp:
        df = pd.concat(frames, ignore_index=True)
        sp.set_attr("rows", len(df))
    with span("broker.reduce.project") as sp:
        df = df.iloc[ctx.offset : ctx.offset + ctx.limit]
        sp.set_attr("rows", len(df))
        return df.values.tolist()


def reduce_selection_order_by(ctx: QueryContext, frames: list[pd.DataFrame]) -> list[list]:
    frames = [f for f in frames if len(f)]
    if not frames:
        return []
    with span("broker.reduce.merge", frames=len(frames)) as sp:
        df = pd.concat(frames, ignore_index=True)
        sp.set_attr("rows", len(df))
    key_cols = [c for c in df.columns if str(c).startswith("__key")]
    with span("broker.reduce.order", rows=len(df), keys=len(key_cols)):
        asc = [not ob.desc for ob in ctx.order_by[: len(key_cols)]]
        from pinot_tpu.common.sorting import sort_nulls_largest

        df = sort_nulls_largest(df, key_cols, asc)
    with span("broker.reduce.project") as sp:
        df = df.iloc[ctx.offset : ctx.offset + ctx.limit]
        sp.set_attr("rows", len(df))
        return df.drop(columns=key_cols).values.tolist()


def apply_gapfill(ctx: QueryContext, rows: list[list]) -> list[list]:
    """Broker-side gap filling (reference: GapfillProcessor,
    pinot-core/.../query/reduce/GapfillProcessor.java). Emits exactly one pass
    over the [start, end) bucket range in step increments: rows whose time
    value lands on a bucket are kept (rows outside the range are dropped);
    missing buckets are synthesized with per-column FILL modes —
    FILL_PREVIOUS_VALUE carries the last emitted value forward,
    FILL_DEFAULT_VALUE emits 0, otherwise None."""
    gf = ctx.gapfill
    assert gf is not None
    n = len(ctx.select_items)
    integral = all(float(v).is_integer() for v in (gf.start, gf.step))
    nbuckets = max(0, int(math.ceil((gf.end - gf.start) / gf.step)))
    # bucket-index matching (not exact float equality) so fractional steps
    # don't miss rows to rounding
    by_bucket: dict[int, list[list]] = {}
    for r in rows:
        try:
            idx = (float(r[gf.col_index]) - gf.start) / gf.step
        except (TypeError, ValueError):
            continue
        b = int(round(idx))
        if 0 <= b < nbuckets and abs(idx - b) < 1e-9:
            by_bucket.setdefault(b, []).append(r)
    out: list[list] = []
    prev: list | None = None
    for b in range(nbuckets):
        t = gf.start + b * gf.step
        hit = by_bucket.get(b)
        if hit:
            out.extend(hit)
            prev = hit[-1]
            continue
        row: list = [None] * n
        row[gf.col_index] = int(t) if integral else t
        for j in range(n):
            if j == gf.col_index:
                continue
            mode = gf.fills.get(j)
            if mode == "FILL_PREVIOUS_VALUE" and prev is not None:
                row[j] = prev[j]
            elif mode == "FILL_DEFAULT_VALUE":
                row[j] = 0
        out.append(row)
    return out


def build_result(ctx: QueryContext, rows: list[list], **stats) -> ResultTable:
    if ctx.gapfill is not None:
        rows = apply_gapfill(ctx, rows)
    cols = [ctx.output_name(it) for it in ctx.select_items]
    return ResultTable(columns=cols, rows=rows, **stats)
