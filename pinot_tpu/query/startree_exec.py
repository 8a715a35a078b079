"""Query-side star-tree swap: rewrite matching queries onto pre-agg tables.

Reference parity: StarTreeUtils.extractAggregationFunctionPairs + the
executor swap in AggregationPlanNode/GroupByPlanNode (pinot-core/.../startree/
executor/StarTreeAggregationExecutor.java:36, StarTreeGroupByExecutor.java:45).
A query matches when its filter and group keys touch only split dimensions
and every aggregation derives from the stored pairs; it is then planned as an
ordinary query over the star table segment (shared dictionaries keep all
dict-id predicate lowering intact), enqueued like any other segment's program
(engine._dispatch_segment), and once the query's one wait is over the partials
are mapped back into the original aggregation layout so the broker reduce
never knows.

A SUM / AVG argument may be a sum or difference of columns (`a - b`, `a + b -
c`, `-a + b`): the star program is asked for each stored `SUM__<col>` once and
the mapping recombines them with their signs. Nothing else distributes over a
pre-aggregate — a product, a division, a constant term, a MIN / MAX of an
expression — and none of it matches.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace

import numpy as np

from pinot_tpu.common.trace import count, record_span
from pinot_tpu.query import ast
from pinot_tpu.query.context import AggregationInfo, QueryContext, QueryType, _collect_filter_identifiers
from pinot_tpu.segment.startree import StarTable, star_table_as_segment


def _linear_terms(expr) -> dict[str, int] | None:
    """{column: coefficient} of an expression that is a sum or difference of
    columns, None of any other: `a - b` -> {a: 1, b: -1}, `-a + b` (the parser's
    `(0 - a) + b`) -> {a: -1, b: 1}. A column whose terms cancel is dropped."""
    if isinstance(expr, ast.Identifier):
        return {expr.name: 1}
    if not isinstance(expr, ast.BinaryOp) or expr.op not in ("+", "-"):
        return None
    right = _linear_terms(expr.right)
    # a leading sign is parsed as 0 - x: the one constant that is no constant term
    left = {} if expr.op == "-" and expr.left == ast.Literal(0) else _linear_terms(expr.left)
    if left is None or right is None:
        return None
    out = dict(left)
    for col, k in right.items():
        out[col] = out.get(col, 0) + (k if expr.op == "+" else -k)
    return {col: k for col, k in out.items() if k}


def _supports(st: StarTable, a: AggregationInfo) -> bool:
    if a.arg is None or isinstance(a.arg, ast.Identifier):
        return st.supports_agg(a.func, a.arg.name if a.arg is not None else None)
    if a.func not in ("sum", "avg"):
        return False
    terms = _linear_terms(a.arg)
    # integer pairs only: a difference of two DOUBLE sums is not the sum of the differences to
    # rounding (it cancels where the rows' differences do not), and the swap may not change an answer
    return bool(terms) and all(
        st.supports_agg("sum", col) and st.arrays[f"SUM__{col}"].dtype.kind == "i" for col in terms
    )


def _null_dependent(f) -> bool:
    """Predicates whose truth depends on the NULL VECTOR (IS NULL /
    IS DISTINCT FROM): the star table bakes nulls into placeholder values,
    so these must run the per-doc path."""
    if f is None:
        return False
    if isinstance(f, (ast.IsNull, ast.DistinctFrom, ast.BoolAssert)):
        return True
    if isinstance(f, (ast.And, ast.Or)):
        return any(_null_dependent(c) for c in f.children)
    if isinstance(f, ast.Not):
        return _null_dependent(f.child)
    return False


def matches(ctx: QueryContext, st: StarTable) -> bool:
    if ctx.query_type not in (QueryType.AGGREGATION, QueryType.GROUP_BY):
        return False
    if not ctx.aggregations:
        return False
    if _null_dependent(ctx.filter):
        return False
    dims = set(st.dimensions)
    fcols: set[str] = set()
    _collect_filter_identifiers(ctx.filter, fcols)
    if not fcols.issubset(dims):
        return False
    for g in ctx.group_by:
        if not isinstance(g, ast.Identifier) or g.name not in dims:
            return False
    for a in ctx.aggregations:
        if a.filter is not None:
            # FILTER(WHERE ...) cannot be applied to pre-aggregated rows
            return False
        if not _supports(st, a):
            return False
    return True


def _rewrite(ctx: QueryContext) -> tuple[QueryContext, list[tuple]]:
    """Build the star-side context. Returns (star_ctx, mapping) where mapping
    entry i describes how to rebuild original agg i from star agg partial
    indices: (kind, star_indices...); a SUM / AVG names its stored sums as
    ((star index, coefficient), ...)."""
    star_aggs: list[AggregationInfo] = []
    mapping: list[tuple] = []
    stored: dict[str, int] = {}  # a stored sum (and the count) is asked for once, however many aggregates read it

    def add(func: str, col: str) -> int:
        star_aggs.append(AggregationInfo(func, ast.Identifier(col), f"{func}({col})#star{len(star_aggs)}"))
        return len(star_aggs) - 1

    def stored_sum(col: str) -> int:
        if col not in stored:
            stored[col] = add("sum", col)
        return stored[col]

    def sums(a: AggregationInfo) -> tuple:
        return tuple((stored_sum(f"SUM__{col}"), k) for col, k in _linear_terms(a.arg).items())

    for a in ctx.aggregations:
        col = a.arg.name if isinstance(a.arg, ast.Identifier) else None
        if a.func == "count":
            mapping.append(("count", stored_sum("__count")))
        elif a.func == "sum":
            mapping.append(("sum", sums(a)))
        elif a.func == "min":
            mapping.append(("copy", add("min", f"MIN__{col}")))
        elif a.func == "max":
            mapping.append(("copy", add("max", f"MAX__{col}")))
        elif a.func == "avg":
            mapping.append(("avg", sums(a), stored_sum("__count")))
        elif a.func == "minmaxrange":
            mapping.append(("pair", add("min", f"MIN__{col}"), add("max", f"MAX__{col}")))
        elif a.func in ("distinctcount", "distinctcountbitmap", "distinctcounthll"):
            mapping.append(("copy", add(a.func, col)))
        else:
            raise AssertionError(a.func)
    star_ctx = replace(ctx, aggregations=star_aggs, hints=dict(ctx.hints))
    return star_ctx, mapping


def _combine(terms: tuple, part):
    """sum of coefficient x stored sum, `part(j)` the star program's j-th partial (a number or a column of them);
    one stored sum as it stands: the scan's own partial."""
    total = None
    for j, k in terms:
        p = part(j) if k == 1 else part(j) * k
        total = p if total is None else total + p
    return total


def _convert_scalar(mapping, star_partial):
    out = []
    for m in mapping:
        kind = m[0]
        if kind == "count":
            out.append(int(star_partial[m[1]]))
        elif kind == "copy":
            out.append(star_partial[m[1]])
        elif kind == "sum":
            out.append(_combine(m[1], star_partial.__getitem__))
        elif kind == "avg":
            out.append((float(_combine(m[1], star_partial.__getitem__)), int(star_partial[m[2]])))
        elif kind == "pair":
            out.append((float(star_partial[m[1]]), float(star_partial[m[2]])))
    return out


def _convert_frame(ctx, mapping, frame):
    if all(m in (("sum", ((i, 1),)), ("copy", i)) for i, m in enumerate(mapping)):
        # one stored pair an aggregate, in the query's order: the star program's columns are the query's own, name for name
        return frame
    import pandas as pd

    # arrays in, one frame out: a frame made of another's Series is aligned and copied column by column
    data = {f"k{i}": frame[f"k{i}"].array for i in range(len(ctx.group_by))}

    def star_col(j, part=0):
        return frame[f"a{j}p{part}"].to_numpy()

    for i, m in enumerate(mapping):
        kind = m[0]
        if kind == "count":
            data[f"a{i}p0"] = star_col(m[1]).astype(np.int64)
        elif kind == "copy":
            data[f"a{i}p0"] = star_col(m[1])
        elif kind == "sum":
            data[f"a{i}p0"] = _combine(m[1], star_col)
        elif kind == "avg":
            data[f"a{i}p0"] = _combine(m[1], star_col).astype(np.float64)
            data[f"a{i}p1"] = star_col(m[2]).astype(np.int64)
        elif kind == "pair":
            data[f"a{i}p0"] = star_col(m[1]).astype(np.float64)
            data[f"a{i}p1"] = star_col(m[2]).astype(np.float64)
    return pd.DataFrame(data)


@dataclass(frozen=True)
class StarSwap:
    """One segment's swap onto a star table: what the engine plans and launches in the segment's
    place, and the way back to the original aggregation layout."""

    seg: object  # the star table as a segment (ImmutableSegment)
    ctx: QueryContext  # the query over its stored pairs
    mapping: list

    def convert(self, ctx: QueryContext, partial):
        """The star program's partial in the layout `ctx`, the original query, asks for
        (span `server.unpack.startree`, inside the segment's `server.unpack`)."""
        t0 = time.perf_counter()
        if ctx.query_type == QueryType.AGGREGATION:
            out = _convert_scalar(self.mapping, partial)
        else:
            out = _convert_frame(ctx, self.mapping, partial)
        record_span("server.unpack.startree", (time.perf_counter() - t0) * 1e3)
        return out


def swap(seg, ctx: QueryContext) -> StarSwap | None:
    """The segment's swap for this query, None when no star table of it matches. The star segment is
    wrapped at its first use and kept with its parent (`starTreeBuilds`; the dispatch that follows
    stages it). Span `server.plan.startree`, one a swapped segment: the match, the rewrite and that lookup."""
    t0 = time.perf_counter()
    for idx, st in enumerate(seg.extras.get("startree") or []):
        if not matches(ctx, st):
            continue
        cache_key = f"startree_seg:{idx}"
        star_seg = seg.extras.get(cache_key)
        if star_seg is None:
            star_seg = seg.extras[cache_key] = star_table_as_segment(seg, st)
            count("starTreeBuilds")
        found = StarSwap(star_seg, *_rewrite(ctx))
        record_span("server.plan.startree", (time.perf_counter() - t0) * 1e3)
        return found
    return None
