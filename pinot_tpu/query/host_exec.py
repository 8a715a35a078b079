"""Host (numpy/pandas) fallback executor.

Reference parity: plays the role of Pinot's non-optimized operator paths (e.g.
NoDictionary*GroupKeyGenerator, ExpressionFilterOperator) for query shapes the
device lowering doesn't cover yet: high-cardinality or expression GROUP BY,
DISTINCTCOUNT in group-by, transform functions. Produces the SAME partial
formats as the device path (see reduce.py), so the broker reduce never knows
which executor ran a segment. Correctness-first; the set of shapes landing
here shrinks as device lowerings are added.
"""

from __future__ import annotations

import re

import numpy as np
import pandas as pd

from pinot_tpu.common.types import DataType
from pinot_tpu.query import ast
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.plan import PlanError, _like_to_regex
from pinot_tpu.query.reduce import parts_of
from pinot_tpu.segment.segment import ImmutableSegment


def lookup_call(expr: "ast.FunctionCall"):
    """(the serving server's dimension table, destination column, the key
    expressions) of a lookUp('dimTable','destColumn','pk1',expr1[,'pk2',expr2...])
    call, checked as the host evaluator and the device lowering both need it."""
    from pinot_tpu.cluster.dimension import get_dim_table

    if len(expr.args) < 4 or len(expr.args) % 2 != 0:
        raise PlanError("lookup requires (dimTable, destColumn, pkCol, pkExpr, ...)")
    lits = expr.args[:2]
    if not all(isinstance(a, ast.Literal) for a in lits):
        raise PlanError("lookup dimTable/destColumn must be string literals")
    dim = get_dim_table(str(lits[0].value))
    pk_cols = [str(a.value) for a in expr.args[2::2] if isinstance(a, ast.Literal)]
    if pk_cols != dim.pk_columns:
        raise PlanError(f"lookup join keys {pk_cols} must match dim table PK {dim.pk_columns}")
    return dim, str(lits[1].value), list(expr.args[3::2])


def eval_value(seg: ImmutableSegment, expr: ast.Expr) -> np.ndarray:
    if isinstance(expr, ast.Identifier):
        if expr.name == "$docId":
            return np.arange(seg.n_docs, dtype=np.int64)
        if expr.name == "$segmentName":
            return np.full(seg.n_docs, seg.name, dtype=object)
        if expr.name == "$hostName":
            import socket

            return np.full(seg.n_docs, socket.gethostname(), dtype=object)
        ci = seg.columns.get(expr.name)
        if ci is None:
            raise PlanError(f"unknown column {expr.name!r}")
        return ci.materialize()
    if isinstance(expr, ast.Literal):
        return np.full(seg.n_docs, expr.value)
    if isinstance(expr, ast.CaseWhen):
        conds = [filter_mask(seg, c) for c, _ in expr.whens]
        vals = [np.asarray(eval_value(seg, v)) for _, v in expr.whens]
        n = seg.n_docs
        vals = [np.broadcast_to(v, (n,)) if v.ndim == 0 else v for v in vals]
        if expr.else_ is not None:
            default = np.asarray(eval_value(seg, expr.else_))
            default = np.broadcast_to(default, (n,)) if default.ndim == 0 else default
        else:
            # null-handling-disabled default (CaseTransformFunction parity):
            # 0 for numeric branches, 'null' for string branches
            is_str = any(v.dtype == object or v.dtype.kind in "US" for v in vals)
            default = np.full(n, "null" if is_str else 0, dtype=object if is_str else np.float64)
        if any(v.dtype == object or v.dtype.kind in "US" for v in vals):
            vals = [v.astype(object) for v in vals]
            default = default.astype(object)
        return np.select(conds, vals, default=default)
    if isinstance(expr, ast.FunctionCall):
        name = expr.name
        if name == "map_value":
            # map_value(col, 'key'): dense per-key column via the map index
            # when present, else per-row document parse (StandardIndexes map
            # entry parity)
            if (
                len(expr.args) != 2
                or not isinstance(expr.args[0], ast.Identifier)
                or not isinstance(expr.args[1], ast.Literal)
            ):
                raise PlanError("map_value requires (column, 'key')")
            col, key = expr.args[0].name, str(expr.args[1].value)
            mi = seg.extras.get("map", {}).get(col)
            if mi is not None:
                return mi.value_column(key)
            import json as _json

            ci = seg.columns.get(col)
            if ci is None:
                raise PlanError(f"unknown column {col!r}")
            out = np.full(seg.n_docs, None, dtype=object)
            for i, v in enumerate(ci.materialize()):
                if isinstance(v, dict):
                    doc = v
                else:
                    try:
                        doc = _json.loads(v) if v else {}
                    except (ValueError, TypeError):
                        continue  # non-JSON row -> None
                if isinstance(doc, dict):
                    out[i] = doc.get(key)
            return out
        if name == "lookup":
            # lookUp('dimTable','destColumn','pk1',expr1[,'pk2',expr2...])
            # (LookupTransformFunction parity): the key columns looked up whole,
            # one sorted probe a column (cluster/dimension.py), no tuple a row
            dim, dest, key_exprs = lookup_call(expr)
            if any(_mv_column(seg, a) is not None for a in key_exprs):
                raise PlanError(f"lookUp by a multi-value column is not supported: {expr}")
            return dim.lookup_column(dest, [np.asarray(eval_value(seg, a)) for a in key_exprs])
        if name == "coalesce":
            # first non-null argument per row (CoalesceTransformFunction):
            # null = the column null-vector OR a NaN/None cell. Accumulate in
            # object space (args may mix numeric/string dtypes incl. numpy
            # '<U' string columns); all-numeric results narrow back.
            out = np.full(seg.n_docs, None, dtype=object)
            filled = np.zeros(seg.n_docs, dtype=bool)
            for a in expr.args:
                v = np.asarray(eval_value(seg, a))
                v = np.broadcast_to(v, (seg.n_docs,)) if v.ndim == 0 else v
                miss = expr_null_mask(seg, a)
                miss = miss.copy() if miss is not None else np.zeros(seg.n_docs, dtype=bool)
                if v.dtype == object:
                    miss |= np.asarray([x is None for x in v])
                elif np.issubdtype(v.dtype, np.floating):
                    miss |= np.isnan(v)
                take = ~filled & ~miss
                out[take] = v[take]
                filled |= take
                if filled.all():
                    break
            if filled.all() and all(
                isinstance(x, (int, float, np.integer, np.floating)) and not isinstance(x, bool)
                for x in out
            ):
                return out.astype(np.float64)
            return out
        if name in _ARRAY_FUNCS and len(expr.args) == 1:
            mvci = _mv_column(seg, expr.args[0])
            if mvci is not None:
                return _ARRAY_FUNCS[name](mvci)
        if name in _VECTOR_UNARY and len(expr.args) == 1:
            mvci = _mv_column(seg, expr.args[0])
            if mvci is not None:
                vecs = _vectors_of(mvci)
                if name == "vectordims":
                    return np.full(len(vecs), vecs.shape[1], dtype=np.int64)
                return np.sqrt((vecs * vecs).sum(axis=-1))
        if name in _VECTOR_BINARY and len(expr.args) == 2:
            sides = []
            for a in expr.args:
                mvci = _mv_column(seg, a)
                if mvci is not None:
                    sides.append(_vectors_of(mvci))
                elif isinstance(a, ast.ArrayLiteral):
                    # elements are raw python numbers (sql._array_element)
                    sides.append(np.asarray([float(v) for v in a.values])[None, :])
                else:
                    sides = None
                    break
            if sides is not None and sides[0].shape[-1] == sides[1].shape[-1]:
                res = _vector_binary(name, sides[0], sides[1])
                if res.shape[0] == 1 and seg.n_docs != 1:
                    # both sides literal: constant result per doc
                    res = np.full(seg.n_docs, float(res[0]))
                return res
    if isinstance(expr, (ast.BinaryOp, ast.FunctionCall)):
        from pinot_tpu.query.transforms import STRING_FUNCS, apply_scalar, apply_string_func

        # arithmetic, CAST, the time rewrites and the device functions: the
        # step the planner's expression GROUP BY key takes over a dictionary
        out = apply_scalar(expr, lambda e: eval_value(seg, e))
        if out is not NotImplemented:
            return out
        if isinstance(expr, ast.FunctionCall) and expr.name in STRING_FUNCS:
            base = eval_value(seg, expr.args[0])
            lit_args = tuple(a.value for a in expr.args[1:] if isinstance(a, ast.Literal))
            derived, _ = apply_string_func(expr.name, base, lit_args)
            return derived
    raise PlanError(f"unsupported value expression in host executor: {expr}")


_CMPS = {
    ast.CompareOp.EQ: lambda a, b: a == b,
    ast.CompareOp.NEQ: lambda a, b: a != b,
    ast.CompareOp.LT: lambda a, b: a < b,
    ast.CompareOp.LTE: lambda a, b: a <= b,
    ast.CompareOp.GT: lambda a, b: a > b,
    ast.CompareOp.GTE: lambda a, b: a >= b,
}


def _coerce_lit(v):
    return v


def _mv_column(seg: ImmutableSegment, expr) -> "object | None":
    """ColumnIndex when expr is an MV identifier, else None."""
    if isinstance(expr, ast.Identifier):
        ci = seg.columns.get(expr.name)
        if ci is not None and ci.is_mv:
            return ci
    return None


def _mv_flat_values(ci) -> np.ndarray:
    return ci.dictionary.get_many(ci.forward) if ci.dictionary is not None else ci.forward


def _array_length(ci) -> np.ndarray:
    return np.asarray(ci.lens, dtype=np.int64)


def _array_numeric_reduce(ci, op: str) -> np.ndarray:
    """Per-doc reduction over an MV column's values (Array{Sum,Min,Max,
    Average}TransformFunction). Empty arrays reduce to NaN (finalized to
    NULL upstream); string MVs reject."""
    flat = _mv_flat_values(ci)
    if flat.dtype == object or flat.dtype.kind in ("U", "S"):
        raise PlanError(f"{op} requires a numeric multi-value column")
    flat = flat.astype(np.float64)
    docs = ci.flat_docids()
    n = len(ci.lens)
    empty = np.asarray(ci.lens) == 0
    if op in ("arraysum", "arrayaverage"):
        s = np.zeros(n, dtype=np.float64)
        np.add.at(s, docs, flat)
        if op == "arrayaverage":
            s = s / np.maximum(np.asarray(ci.lens, dtype=np.float64), 1.0)
    elif op == "arraymin":
        s = np.full(n, np.inf)
        np.minimum.at(s, docs, flat)
    else:  # arraymax
        s = np.full(n, -np.inf)
        np.maximum.at(s, docs, flat)
    return np.where(empty, np.nan, s)


_ARRAY_FUNCS = {
    "arraylength": _array_length,
    "cardinality": _array_length,
    "arraysum": lambda ci: _array_numeric_reduce(ci, "arraysum"),
    "arrayaverage": lambda ci: _array_numeric_reduce(ci, "arrayaverage"),
    "arraymin": lambda ci: _array_numeric_reduce(ci, "arraymin"),
    "arraymax": lambda ci: _array_numeric_reduce(ci, "arraymax"),
}


def _vectors_of(ci) -> np.ndarray:
    """(n_docs, dim) float matrix from a uniform-length numeric MV column."""
    flat = _mv_flat_values(ci)
    if flat.dtype == object or flat.dtype.kind in ("U", "S"):
        raise PlanError("vector functions require a numeric multi-value column")
    lens = np.asarray(ci.lens)
    if len(lens) == 0 or (lens != lens[0]).any() or lens[0] == 0:
        raise PlanError("vector functions require uniform non-empty vector lengths")
    return flat.astype(np.float64).reshape(len(lens), int(lens[0]))


def _vector_binary(name: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if name == "innerproduct":
        return (a * b).sum(axis=-1)
    if name == "l1distance":
        return np.abs(a - b).sum(axis=-1)
    if name == "l2distance":
        return np.sqrt(((a - b) ** 2).sum(axis=-1))
    # cosinedistance: 1 - cos_sim; zero-norm rows -> NaN (reference default)
    na = np.sqrt((a * a).sum(axis=-1))
    nb = np.sqrt((b * b).sum(axis=-1))
    denom = na * nb
    with np.errstate(invalid="ignore", divide="ignore"):
        sim = (a * b).sum(axis=-1) / denom
    return np.where(denom == 0, np.nan, 1.0 - sim)


#: VectorTransformFunctions parity (core/operator/transform/function/
#: VectorTransformFunctions.java): binary distance/similarity over a float
#: MV column and an ARRAY[...] literal (or two MV columns), plus unary
#: VECTORDIMS / VECTORNORM.
_VECTOR_BINARY = ("cosinedistance", "innerproduct", "l1distance", "l2distance")
_VECTOR_UNARY = ("vectordims", "vectornorm")


def _mv_any_match(ci, flat_pred: np.ndarray) -> np.ndarray:
    """Reduce a flat per-value predicate to per-doc any-match (the host twin
    of the kernel's mv_any scatter-or)."""
    m = np.zeros(len(ci.lens), dtype=bool)
    np.logical_or.at(m, ci.flat_docids(), np.asarray(flat_pred, dtype=bool))
    return m


def value_predicate(v: np.ndarray, f) -> np.ndarray:
    """The mask of one filter leaf over `v`, the single values of its
    expression: a Compare against a literal on its right, a Between, an In, a
    Like or a RegexpLike. What `filter_mask` evaluates over a segment's rows,
    and what the device lowering of a lookUp filter evaluates over the
    destination's distinct values (plan._Lowering.lookup_filter), so that the
    two paths hold one meaning."""
    if isinstance(f, ast.Compare):
        rv = _coerce_lit(f.right.value)
        if isinstance(rv, str) and v.dtype == object:
            v = v.astype(str)
        with np.errstate(invalid="ignore"):
            return np.asarray(_CMPS[f.op](v, rv), dtype=bool)
    if isinstance(f, ast.Between):
        if v.dtype == object:
            v = v.astype(str)
        with np.errstate(invalid="ignore"):
            m = (v >= f.low.value) & (v <= f.high.value)
        return ~m if f.negated else m
    if isinstance(f, ast.In):
        vals = [x.value for x in f.values if isinstance(x, ast.Literal)]
        if v.dtype == object:
            v = v.astype(str)
            vals = [str(x) for x in vals]
        m = np.isin(v, np.asarray(vals))
        return ~m if f.negated else m
    if isinstance(f, ast.Like):
        rx = re.compile(_like_to_regex(f.pattern))
        m = np.asarray([bool(rx.fullmatch(x)) for x in v.astype(str)], dtype=bool)
        return ~m if f.negated else m
    if isinstance(f, ast.RegexpLike):
        rx = re.compile(f.pattern)
        return np.asarray([bool(rx.search(x)) for x in v.astype(str)], dtype=bool)
    raise PlanError(f"no predicate over values: {f}")


def filter_mask(seg: ImmutableSegment, f: ast.FilterExpr | None) -> np.ndarray:
    n = seg.n_docs
    if f is None:
        return np.ones(n, dtype=bool)
    if isinstance(f, ast.And):
        m = np.ones(n, dtype=bool)
        for c in f.children:
            m &= filter_mask(seg, c)
        return m
    if isinstance(f, ast.Or):
        m = np.zeros(n, dtype=bool)
        for c in f.children:
            m |= filter_mask(seg, c)
        return m
    if isinstance(f, ast.Not):
        return ~filter_mask(seg, f.child)
    if isinstance(f, ast.Compare):
        left, op, right = f.left, f.op, f.right
        if isinstance(left, ast.Literal) and not isinstance(right, ast.Literal):
            left, right = right, left
            from pinot_tpu.query.plan import _FLIP

            op = _FLIP[op]
        mvci = _mv_column(seg, left)
        if mvci is not None and isinstance(right, ast.Literal):
            # MV semantics: positive predicates = any value matches; NEQ
            # matches docs where NO value equals (exclusion)
            flat = _mv_flat_values(mvci)
            rv = right.value
            if isinstance(rv, str) and flat.dtype == object:
                flat = flat.astype(str)
            pos_op = ast.CompareOp.EQ if op == ast.CompareOp.NEQ else op
            m = _mv_any_match(mvci, _CMPS[pos_op](flat, rv))
            return ~m if op == ast.CompareOp.NEQ else m
        lv = eval_value(seg, left)
        if isinstance(right, ast.Literal):
            return value_predicate(lv, ast.Compare(op, left, right))
        return np.asarray(_CMPS[op](lv, eval_value(seg, right)), dtype=bool)
    if isinstance(f, ast.Between):
        lo = f.low.value if isinstance(f.low, ast.Literal) else None
        hi = f.high.value if isinstance(f.high, ast.Literal) else None
        if lo is None or hi is None:
            raise PlanError("BETWEEN bounds must be literals")
        mvci = _mv_column(seg, f.expr)
        if mvci is not None:
            v = _mv_flat_values(mvci)
            if v.dtype == object:
                v = v.astype(str)
            m = _mv_any_match(mvci, (v >= lo) & (v <= hi))
            return ~m if f.negated else m
        return value_predicate(eval_value(seg, f.expr), f)
    if isinstance(f, ast.In):
        vals = [x.value for x in f.values if isinstance(x, ast.Literal)]
        mvci = _mv_column(seg, f.expr)
        if mvci is not None:
            v = _mv_flat_values(mvci)
            if v.dtype == object:
                v = v.astype(str)
                vals = [str(x) for x in vals]
            m = _mv_any_match(mvci, np.isin(v, np.asarray(vals)))
            return ~m if f.negated else m
        return value_predicate(eval_value(seg, f.expr), f)
    if isinstance(f, (ast.Like, ast.RegexpLike)):
        return value_predicate(eval_value(seg, f.expr), f)
    if isinstance(f, ast.IsNull):
        if isinstance(f.expr, ast.Identifier):
            nv = seg.extras.get("null", {}).get(f.expr.name)
            if nv is not None:
                from pinot_tpu import native

                nulls = native.bm_to_bool(nv, n)
                return ~nulls if f.negated else nulls
        return np.full(n, bool(f.negated))
    if isinstance(f, ast.BoolAssert):
        v = np.asarray(eval_value(seg, f.expr))
        nulls = expr_null_mask(seg, f.expr)
        nulls = nulls if nulls is not None else np.zeros(n, dtype=bool)
        if v.dtype == object or v.dtype.kind in ("U", "S"):
            truthy = np.asarray(
                [x is not None and bool(x) and str(x).lower() not in ("false", "0") for x in v]
            )
        else:
            truthy = v.astype(np.float64) != 0
        pos = (truthy if f.want_true else ~truthy) & ~nulls
        # IS NOT TRUE / IS NOT FALSE include the null rows (3-valued NOT)
        return ~pos if f.negated else pos
    if isinstance(f, ast.DistinctFrom):
        l = eval_value(seg, f.left)
        r = eval_value(seg, f.right)
        nl = expr_null_mask(seg, f.left)
        nr = expr_null_mask(seg, f.right)
        nl = nl if nl is not None else np.zeros(n, dtype=bool)
        nr = nr if nr is not None else np.zeros(n, dtype=bool)
        with np.errstate(invalid="ignore"):
            neq = np.asarray(l != r, dtype=bool)
        m = (neq & ~nl & ~nr) | (nl ^ nr)
        return ~m if f.negated else m
    if isinstance(f, ast.PredicateFunction):
        return predicate_function_mask(seg, f)
    raise PlanError(f"unsupported filter in host executor: {f}")


def predicate_function_mask(seg: ImmutableSegment, f: "ast.PredicateFunction") -> np.ndarray:
    """Index-probe predicates -> bool doc mask (TextMatch/JsonMatch/
    VectorSimilarity filter-operator parity; shared by device + host paths)."""
    n = seg.n_docs

    def _col(i: int) -> str:
        if len(f.args) <= i or not isinstance(f.args[i], ast.Identifier):
            raise PlanError(f"{f.name} argument {i} must be a column")
        return f.args[i].name

    def _lit(i: int):
        if len(f.args) <= i or not isinstance(f.args[i], ast.Literal):
            raise PlanError(f"{f.name} argument {i} must be a literal")
        return f.args[i].value

    if f.name == "text_match":
        col = _col(0)
        ti = seg.extras.get("text", {}).get(col)
        if ti is None:
            raise PlanError(f"TEXT_MATCH requires a text index on column {col!r}")
        return ti.search(str(_lit(1)))
    if f.name == "json_match":
        col = _col(0)
        ji = seg.extras.get("json", {}).get(col)
        if ji is None:
            raise PlanError(f"JSON_MATCH requires a json index on column {col!r}")
        return ji.match(str(_lit(1)))
    if f.name == "vector_similarity":
        col = _col(0)
        vi = seg.extras.get("vector", {}).get(col)
        if vi is None:
            raise PlanError(f"VECTOR_SIMILARITY requires a vector index on column {col!r}")
        if len(f.args) < 2 or not isinstance(f.args[1], ast.ArrayLiteral):
            raise PlanError("VECTOR_SIMILARITY(col, ARRAY[...], topK)")
        k = int(_lit(2)) if len(f.args) > 2 else 10
        mask = np.zeros(n, dtype=bool)
        mask[vi.top_k(np.asarray(f.args[1].values, dtype=np.float32), k)] = True
        return mask
    if f.name == "st_within_distance":
        from pinot_tpu.segment.indexes import haversine_m

        qlat, qlng, radius = float(_lit(2)), float(_lit(3)), float(_lit(4))
        if isinstance(f.args[0], ast.Identifier) and isinstance(f.args[1], ast.Identifier):
            gi = seg.extras.get("geo", {}).get(f"{f.args[0].name},{f.args[1].name}")
            if gi is not None:
                # grid-cell candidates first, exact haversine refine on the
                # (usually tiny) candidate set only
                cand = gi.candidate_docs(qlat, qlng, radius)
                mask = np.zeros(n, dtype=bool)
                if len(cand):
                    lat_c = seg.columns[f.args[0].name].materialize(cand).astype(np.float64)
                    lng_c = seg.columns[f.args[1].name].materialize(cand).astype(np.float64)
                    mask[cand[haversine_m(lat_c, lng_c, qlat, qlng) <= radius]] = True
                return mask
        lat = eval_value(seg, f.args[0]).astype(np.float64)
        lng = eval_value(seg, f.args[1]).astype(np.float64)
        return haversine_m(lat, lng, qlat, qlng) <= radius
    raise PlanError(f"unknown predicate function {f.name}")


# ---------------------------------------------------------------------------
# partial producers (formats documented in reduce.py)
# ---------------------------------------------------------------------------


_MV_AGGS = (
    "countmv",
    "summv",
    "minmv",
    "maxmv",
    "avgmv",
    "distinctcountmv",
    "minmaxrangemv",
    "distinctsummv",
    "distinctavgmv",
    "distinctcountbitmapmv",
    "distinctcounthllmv",
    "percentilemv",
    "percentileestmv",
    "percentiletdigestmv",
    "percentilekllmv",
    "percentilerawestmv",
    "percentilerawtdigestmv",
    "percentilerawkllmv",
    "distinctcounthllplusmv",
    "distinctcountrawhllmv",
    "distinctcountrawhllplusmv",
)
_MV_SET_AGGS = ("distinctcountmv", "distinctsummv", "distinctavgmv", "distinctcountbitmapmv", "distinctcounthllmv")
# flat matched values as the partial (the SV twins merge by concatenation)
_MV_VALUES_AGGS = (
    "percentilemv",
    "percentileestmv",
    "percentiletdigestmv",
    "percentilekllmv",
    "percentilerawestmv",
    "percentilerawtdigestmv",
    "percentilerawkllmv",
)
# HLL-register partials (the SV twins merge via elementwise np.maximum)
_MV_REG_AGGS = ("distinctcounthllplusmv", "distinctcountrawhllmv", "distinctcountrawhllplusmv")


def _funnel_mod():
    from pinot_tpu.query import funnel

    return funnel


def _theta_filter_masks(seg: ImmutableSegment, extra: tuple) -> list[np.ndarray]:
    """Doc masks for a filtered DISTINCTCOUNTTHETASKETCH's filter predicates
    (one per clause) — the single shared parse site for the scalar and
    grouped paths."""
    from pinot_tpu.query.aggregates import parse_theta_extra
    from pinot_tpu.query.sql import parse_sql

    _params, filters, _postagg = parse_theta_extra(extra)
    return [
        filter_mask(seg, parse_sql(f"SELECT * FROM _t WHERE {f}").where) for f in filters
    ]


def _theta_filtered_partial(seg: ImmutableSegment, a, mask: np.ndarray):
    """DISTINCTCOUNTTHETASKETCH with filter expressions: one KMV sketch per
    filter predicate, combined at reduce by the SET_* post-aggregation
    (DistinctCountThetaSketchAggregationFunction parity)."""
    from pinot_tpu.query.aggregates import _theta_compute

    fmasks = _theta_filter_masks(seg, a.extra)
    v = eval_value(seg, a.arg)
    if not fmasks:
        return _theta_compute(v[mask], None, ())
    return ("multi", [_theta_compute(v[mask & fm], None, ()) for fm in fmasks])


def _mv_agg_column(seg: ImmutableSegment, a) -> "object":
    if not isinstance(a.arg, ast.Identifier):
        raise PlanError(f"{a.func} requires an MV column argument")
    ci = seg.columns.get(a.arg.name)
    if ci is None or not ci.is_mv:
        raise PlanError(f"{a.func} requires a multi-value column")
    return ci


def _mv_values_to_twin(func: str, arr: np.ndarray, extra: tuple):
    """Matched flat values -> the SV twin's partial format. The sketch
    twins (tdigest/kll and their raw variants) now keep real bounded
    sketches, so the MV path must build the same partial shape or the
    reduce merge would mix value arrays with sketch tuples."""
    arr = np.asarray(arr, dtype=np.float64)
    if func in ("percentiletdigestmv", "percentilerawtdigestmv", "percentilerawestmv"):
        from pinot_tpu.query.aggregates import _td_comp
        from pinot_tpu.query.quantile_sketch import td_from_values

        return td_from_values(arr, _td_comp(extra))
    if func in ("percentilekllmv", "percentilerawkllmv"):
        from pinot_tpu.query.aggregates import _kll_k
        from pinot_tpu.query.quantile_sketch import kll_from_values

        return kll_from_values(arr, _kll_k(extra))
    return arr


def _mv_scalar_partial(func: str, flat: np.ndarray, extra: tuple = ()):
    """Partial over the matched flat values, shaped like the SV twin's."""
    if func == "countmv":
        return int(len(flat))
    if func in _MV_SET_AGGS:
        return set(flat.tolist())
    if func in _MV_VALUES_AGGS:
        return _mv_values_to_twin(func, flat, extra)
    if func in _MV_REG_AGGS:
        if func in ("distinctcounthllplusmv", "distinctcountrawhllplusmv"):
            from pinot_tpu.query.aggregates import _hpp_p
            from pinot_tpu.query.distinct_sketch import hllplus_registers

            return hllplus_registers(flat, _hpp_p(extra))
        from pinot_tpu.query.sketches import np_hll_registers

        return np_hll_registers(flat)
    v = flat.astype(np.float64)
    if func == "summv":
        return float(v.sum())
    if func == "minmv":
        return float(v.min()) if len(v) else float("inf")
    if func == "maxmv":
        return float(v.max()) if len(v) else float("-inf")
    if func == "minmaxrangemv":
        return (
            float(v.min()) if len(v) else float("inf"),
            float(v.max()) if len(v) else float("-inf"),
        )
    # avgmv
    return (float(v.sum()), int(len(v)))


def _mv_doc_partials(
    func: str, ci, mask: np.ndarray, value_mask: "np.ndarray | None" = None
) -> dict[str, np.ndarray]:
    """Per-doc pre-aggregates for MV group-by (masked-doc aligned): the
    group merge then only needs the SV twin's sum/min/max/union. `value_mask`
    (FILTER(WHERE) clauses) excludes a doc's VALUES while keeping its row
    aligned with the frame — excluded docs contribute neutral partials."""
    n = len(ci.lens)
    docids = ci.flat_docids()
    vm = value_mask if value_mask is not None else mask
    if func == "countmv":
        lens = ci.lens if value_mask is None else np.where(vm, ci.lens, 0)
        return {"p0": lens[mask].astype(np.int64)}
    flat = _mv_flat_values(ci)
    if func in _MV_SET_AGGS or func in _MV_VALUES_AGGS or func in _MV_REG_AGGS:
        # build cells only for masked docs — a selective filter must not pay
        # a python loop over the whole segment; register-family docs carry
        # value sets too (converted to registers once per merged group)
        sel = np.nonzero(mask)[0]
        cells = np.empty(len(sel), dtype=object)
        off = ci.offsets()
        values_mode = func in _MV_VALUES_AGGS
        empty_chunk = flat[:0]
        for i, d in enumerate(sel):
            chunk = flat[off[d] : off[d + 1]] if vm[d] else empty_chunk
            cells[i] = chunk.astype(np.float64) if values_mode else set(chunk.tolist())
        return {"p0": cells}
    v = flat.astype(np.float64)
    if value_mask is not None:
        # filtered: scatter only the included docs' values (the unfiltered
        # path below keeps its zero-copy direct scatter)
        vv = vm[docids]
        docids = docids[vv]
        v = v[vv]
    if func == "summv":
        s = np.zeros(n, dtype=np.float64)
        np.add.at(s, docids, v)
        return {"p0": s[mask]}
    if func == "minmv":
        m = np.full(n, np.inf)
        np.minimum.at(m, docids, v)
        return {"p0": m[mask]}
    if func == "maxmv":
        m = np.full(n, -np.inf)
        np.maximum.at(m, docids, v)
        return {"p0": m[mask]}
    if func == "minmaxrangemv":
        lo = np.full(n, np.inf)
        hi = np.full(n, -np.inf)
        np.minimum.at(lo, docids, v)
        np.maximum.at(hi, docids, v)
        return {"p0": lo[mask], "p1": hi[mask]}
    # avgmv
    s = np.zeros(n, dtype=np.float64)
    np.add.at(s, docids, v)
    lens = ci.lens if value_mask is None else np.where(vm, ci.lens, 0)
    return {"p0": s[mask], "p1": lens[mask].astype(np.int64)}


def _null_doc_mask(seg: ImmutableSegment, a) -> "np.ndarray | None":
    """Docs where any arg column of aggregation `a` is null (null vector
    index), or None when no arg has one. Decompressed bool masks are cached
    per (segment, column): one bitmap expansion however many aggregations
    read the column."""
    from pinot_tpu.native import bm_to_bool
    from pinot_tpu.query.ast import Identifier

    cache = getattr(seg, "_null_bool_cache", None)
    if cache is None:
        cache = seg._null_bool_cache = {}
    nulls = None
    for arg in (a.arg, a.arg2):
        if not isinstance(arg, Identifier):
            continue
        nv = (seg.extras or {}).get("null", {}).get(arg.name)
        if nv is None:
            continue
        b = cache.get(arg.name)
        if b is None:
            b = cache[arg.name] = bm_to_bool(nv, seg.n_docs)
        nulls = b if nulls is None else (nulls | b)
    return nulls


def filter_mask_null_aware(seg: ImmutableSegment, f: "ast.FilterExpr | None") -> np.ndarray:
    """Three-valued (Kleene) filter evaluation under enableNullHandling
    (Pinot null-handling WHERE semantics): a predicate over a null input is
    UNKNOWN, AND/OR/NOT combine by Kleene logic, and only definitely-TRUE
    rows survive. IS NULL / IS [NOT] DISTINCT FROM are never unknown."""
    t, _n = _filter3(seg, f)
    return t


def _filter3(seg: ImmutableSegment, f: "ast.FilterExpr | None") -> tuple:
    """(true_mask, unknown_mask) pair for one filter node."""
    n_docs = seg.n_docs
    if f is None:
        return np.ones(n_docs, dtype=bool), np.zeros(n_docs, dtype=bool)
    if isinstance(f, ast.And):
        t = np.ones(n_docs, dtype=bool)
        u = np.zeros(n_docs, dtype=bool)
        any_false = np.zeros(n_docs, dtype=bool)
        for c in f.children:
            ct, cu = _filter3(seg, c)
            t &= ct
            u |= cu
            any_false |= ~ct & ~cu
        return t, u & ~any_false  # Kleene AND: FALSE dominates UNKNOWN
    if isinstance(f, ast.Or):
        t = np.zeros(n_docs, dtype=bool)
        u = np.zeros(n_docs, dtype=bool)
        for c in f.children:
            ct, cu = _filter3(seg, c)
            t |= ct
            u |= cu
        return t, u & ~t  # Kleene OR: TRUE dominates UNKNOWN
    if isinstance(f, ast.Not):
        ct, cu = _filter3(seg, f.child)
        return ~ct & ~cu, cu  # NOT(unknown) = unknown
    if isinstance(f, (ast.IsNull, ast.DistinctFrom, ast.BoolAssert)):
        # never unknown: these consume the null vectors exactly (IS [NOT]
        # TRUE/FALSE is a SQL assertion — nulls are definitively excluded
        # by the positive forms and included by the NOT forms)
        return filter_mask(seg, f), np.zeros(n_docs, dtype=bool)
    # leaf predicate: unknown wherever ANY referenced column is null
    # (tested expression, BETWEEN bounds, IN values, predicate args)
    from pinot_tpu.query.context import _collect_filter_identifiers

    t = filter_mask(seg, f)
    refs: set[str] = set()
    _collect_filter_identifiers(f, refs)
    nulls = None
    for name in refs:
        nv = (seg.extras or {}).get("null", {}).get(name)
        if nv is None:
            continue
        from pinot_tpu.native import bm_to_bool

        b = bm_to_bool(nv, n_docs)
        nulls = b if nulls is None else (nulls | b)
    if nulls is None or not nulls.any():
        return t, np.zeros(n_docs, dtype=bool)
    return t & ~nulls, nulls


def _nan_mask_values(v: np.ndarray, excluded: np.ndarray, func: str) -> np.ndarray:
    """Substitute excluded rows with NaN/None so pandas reducers skip them.
    Strings and identity-sensitive functions keep object/None cells: a
    float64 cast would collapse int values above 2^53 AND change the hash
    bit-pattern HLL/theta sketches use (device partials hash the INT
    pattern — a float-hashed host partial would double-count on merge)."""
    identity = v.dtype.kind in "iu" and (
        func.startswith("distinct") or func in ("idset", "mode", "sumprecision")
    )
    if v.dtype == object or v.dtype.kind in "US" or identity:
        v = v.astype(object)
        v[excluded] = None
        return v
    return np.where(excluded, np.nan, v.astype(np.float64))


def _dropna_typed(s: "pd.Series") -> np.ndarray:
    """dropna() that restores int64 dtype for object cells holding ints —
    hash-based sketches must see the original integer bit patterns."""
    s2 = s.dropna()
    if s2.dtype == object and len(s2):
        first = s2.iloc[0]
        if isinstance(first, (int, np.integer)) and not isinstance(first, bool):
            return s2.to_numpy().astype(np.int64)
    return s2.to_numpy()


def agg_partials(seg: ImmutableSegment, ctx: QueryContext, query_mask: np.ndarray) -> list:
    from pinot_tpu.query.aggregates import EXT_AGGS
    from pinot_tpu.query.context import null_handling_enabled

    null_on = null_handling_enabled(ctx.options)
    out = []
    for a in ctx.aggregations:
        # FILTER (WHERE ...) intersects into the query mask per aggregation
        # (Kleene evaluation under null handling, matching the WHERE clause)
        if a.filter is None:
            mask = query_mask
        elif null_on:
            mask = query_mask & filter_mask_null_aware(seg, a.filter)
        else:
            mask = query_mask & filter_mask(seg, a.filter)
        if null_on:
            nulls = _null_doc_mask(seg, a)
            if nulls is not None:
                mask = mask & ~nulls
        if a.func == "count":
            out.append(int(mask.sum()))
            continue
        if a.func in _MV_AGGS:
            ci = _mv_agg_column(seg, a)
            vm = mask[ci.flat_docids()]
            flat = _mv_flat_values(ci)[vm]
            out.append(_mv_scalar_partial(a.func, flat, a.extra))
            continue
        if a.func in _funnel_mod().FUNNEL_AGGS:
            out.append(_funnel_mod().segment_partial(seg, a, mask))
            continue
        if a.func == "distinctcounttheta" and a.extra:
            out.append(_theta_filtered_partial(seg, a, mask))
            continue
        if a.func in EXT_AGGS:
            spec = EXT_AGGS[a.func]
            v = eval_value(seg, a.arg)[mask] if a.arg is not None else None
            v2 = eval_value(seg, a.arg2)[mask] if a.arg2 is not None else None
            out.append(spec.compute(v, v2, a.extra))
            continue
        if a.func in ("distinctcount", "distinctcountbitmap"):
            v = eval_value(seg, a.arg)[mask]
            out.append(set(v.tolist()))
            continue
        if a.func == "distinctcounthll":
            from pinot_tpu.query.sketches import np_hll_registers

            v = eval_value(seg, a.arg)[mask]
            out.append(np_hll_registers(v))
            continue
        if a.func == "percentileest":
            v = eval_value(seg, a.arg)[mask].astype(np.float64)
            bounds = ctx.hints.get("est_bounds", {}).get(a.name)
            if bounds is None:
                out.append(v)  # exact-values mode (merged by concatenation)
            else:
                from pinot_tpu.query.sketches import np_est_hist

                lo, hi = bounds
                out.append((np_est_hist(v, lo, hi), lo, hi))
            continue
        if a.func == "percentiletdigest":
            from pinot_tpu.query.aggregates import _td_comp
            from pinot_tpu.query.quantile_sketch import td_from_values

            out.append(td_from_values(eval_value(seg, a.arg)[mask].astype(np.float64), _td_comp(a.extra)))
            continue
        if a.func == "percentile":
            out.append(eval_value(seg, a.arg)[mask].astype(np.float64))
            continue
        if a.func == "mode":
            v = eval_value(seg, a.arg)[mask]
            vals, counts = np.unique(v, return_counts=True)
            out.append({float(k): int(c) for k, c in zip(vals, counts)})
            continue
        v = eval_value(seg, a.arg)[mask].astype(np.float64)
        if a.func == "sum":
            # None partial = "no non-null rows" under null handling; merge
            # treats it as identity and _finalize yields NULL
            out.append(float(v.sum()) if len(v) else (None if null_on else 0.0))
        elif a.func == "min":
            out.append(float(v.min()) if len(v) else float("inf"))
        elif a.func == "max":
            out.append(float(v.max()) if len(v) else float("-inf"))
        elif a.func == "avg":
            out.append((float(v.sum()), int(len(v))))
        elif a.func == "minmaxrange":
            out.append(
                (float(v.min()) if len(v) else float("inf"), float(v.max()) if len(v) else float("-inf"))
            )
        else:
            raise PlanError(f"unsupported aggregation in host executor: {a.func}")
    return out


def group_frame(seg: ImmutableSegment, ctx: QueryContext, mask: np.ndarray) -> pd.DataFrame:
    from pinot_tpu.query.aggregates import EXT_AGGS
    from pinot_tpu.query.context import null_handling_enabled

    null_on = null_handling_enabled(ctx.options)
    data = {}
    mv_key_cols: list[str] = []
    mv_key_str: dict[str, bool] = {}
    for i, g in enumerate(ctx.group_by):
        ci_g = seg.columns.get(g.name) if isinstance(g, ast.Identifier) else None
        if ci_g is not None and ci_g.is_mv:
            # MV group key: keep per-doc value arrays; explode below so each
            # doc contributes once per value (per cartesian combination when
            # several MV keys group together — Pinot MV group-by semantics)
            v = eval_value(seg, g)[mask]
            data[f"k{i}"] = [list(x) for x in v]
            mv_key_cols.append(f"k{i}")
            mv_key_str[f"k{i}"] = ci_g.data_type.value in ("STRING", "JSON", "BYTES")
            continue
        v = eval_value(seg, g)[mask]
        if null_on:
            nm = expr_null_mask(seg, g)
            if nm is not None and nm.any():
                # null keys form their own group (reference group-by null
                # semantics): substitute None over the stored placeholder.
                # Object dtype keeps int64 keys exact (no float widening);
                # groupby(dropna=False) below keeps the None group.
                v = v.astype(object)
                v[nm[mask]] = None
                data[f"k{i}"] = v
                continue
        data[f"k{i}"] = v.astype(str) if v.dtype == object else v
    filtered_ok = {"count", "sum", "min", "max", "avg", "minmaxrange"}
    mv_docaggs: dict[int, dict[str, np.ndarray]] = {}
    theta_nf: dict[int, int] = {}  # agg index -> number of theta filter clauses
    null_aggs: set[int] = set()  # agg indices with null rows substituted
    for i, a in enumerate(ctx.aggregations):
        if a.filter is not None:
            fmask = (
                filter_mask_null_aware(seg, a.filter)
                if null_on
                else filter_mask(seg, a.filter)
            )
            data[f"f{i}"] = fmask[mask]
        if a.func == "count":
            # COUNT(col) under null handling counts non-null rows only
            if null_on and a.arg is not None:
                nulls = _null_doc_mask(seg, a)
                if nulls is not None and nulls.any():
                    cn = ~nulls[mask]
                    if a.filter is not None:
                        cn = cn & data[f"f{i}"]
                    data[f"cn{i}"] = cn
                    null_aggs.add(i)
            continue
        if a.func in _MV_AGGS:
            # per-doc pre-aggregation over the flat layout; the group merge
            # then reuses the SV twin's reducers (sum/min/max/union).
            # FILTER(WHERE) excludes values doc-wise via the value mask.
            ci = _mv_agg_column(seg, a)
            vmask = (fmask & mask) if a.filter is not None else None
            for suffix, arr in _mv_doc_partials(a.func, ci, mask, vmask).items():
                data[f"m{i}{suffix}"] = arr
            mv_docaggs[i] = True
            continue
        if a.func == "distinctcounttheta" and a.extra:
            # filtered sketches per group: one bool column per filter clause;
            # the group apply below builds a ("multi", [sketch...]) partial the
            # shared _theta_merge_any/_theta_finalize_any reducers understand.
            # A FILTER(WHERE) clause intersects every sketch mask.
            fmasks = _theta_filter_masks(seg, a.extra)
            for j, fm in enumerate(fmasks):
                fmm = fm[mask]
                if a.filter is not None:
                    fmm = fmm & data[f"f{i}"]
                data[f"tf{i}_{j}"] = fmm
            theta_nf[i] = len(fmasks)
            data[f"v{i}"] = eval_value(seg, a.arg)[mask]
            continue
        if a.func in _funnel_mod().FUNNEL_AGGS:
            fun = _funnel_mod()
            steps = a.extra[-1]
            bits = np.zeros(int(mask.sum()), dtype=np.int64)
            for k, s in enumerate(steps):
                sm = filter_mask(seg, s)
                if a.filter is not None:
                    # FILTER(WHERE): excluded docs join no step (bits stay 0)
                    sm = sm & fmask
                bits |= sm[mask].astype(np.int64) << k
            data[f"fb{i}"] = bits
            if fun.is_windowed(a.func):
                data[f"fc{i}"] = eval_value(seg, a.arg2)[mask]
                data[f"ft{i}"] = np.asarray(eval_value(seg, a.arg), dtype=np.float64)[mask]
            else:
                data[f"fc{i}"] = eval_value(seg, a.arg)[mask]
            continue
        v = eval_value(seg, a.arg)[mask]
        if a.filter is not None:
            # excluded docs become NaN/None; pandas reducers skip them and
            # the empty-group defaults are patched to match the device kernel
            v = _nan_mask_values(v, ~data[f"f{i}"], a.func)
            if a.func not in filtered_ok:
                # non-core functions (distinctcount/percentile/mode/EXT/...)
                # reuse the NaN-skipping reducers the null-handling path added
                null_aggs.add(i)
        if null_on:
            nulls = _null_doc_mask(seg, a)
            if nulls is not None and nulls.any():
                v = _nan_mask_values(v, nulls[mask], a.func)
                null_aggs.add(i)
        data[f"v{i}"] = v
        if a.arg2 is not None:
            data[f"w{i}"] = eval_value(seg, a.arg2)[mask]
    df = pd.DataFrame(data)
    for c in mv_key_cols:
        df = df.explode(c, ignore_index=True)
    if mv_key_cols and len(df):
        # docs with empty value lists join no group
        df = df.dropna(subset=mv_key_cols).reset_index(drop=True)
        for c in mv_key_cols:
            df[c] = df[c].astype(str) if mv_key_str[c] else pd.to_numeric(df[c])
    if len(df) == 0:
        cols = {f"k{i}": [] for i in range(len(ctx.group_by))}
        for i, a in enumerate(ctx.aggregations):
            for j in range(parts_of(a.func)):
                cols[f"a{i}p{j}"] = []
        return pd.DataFrame(cols)
    key_cols = [f"k{i}" for i in range(len(ctx.group_by))]
    g = df.groupby(key_cols, sort=False, dropna=False)
    out = g.size().rename("__size").reset_index()
    for i, a in enumerate(ctx.aggregations):
        filtered = a.filter is not None
        if i in mv_docaggs:
            if a.func in ("countmv", "summv"):
                out[f"a{i}p0"] = g[f"m{i}p0"].sum().values
            elif a.func == "minmv":
                out[f"a{i}p0"] = g[f"m{i}p0"].min().values
            elif a.func == "maxmv":
                out[f"a{i}p0"] = g[f"m{i}p0"].max().values
            elif a.func == "avgmv":
                out[f"a{i}p0"] = g[f"m{i}p0"].sum().values
                out[f"a{i}p1"] = g[f"m{i}p1"].sum().values
            elif a.func == "minmaxrangemv":
                out[f"a{i}p0"] = g[f"m{i}p0"].min().values
                out[f"a{i}p1"] = g[f"m{i}p1"].max().values
            elif a.func in _MV_VALUES_AGGS:
                out[f"a{i}p0"] = g[f"m{i}p0"].apply(
                    lambda s, _f=a.func, _e=a.extra: _mv_values_to_twin(
                        _f, np.concatenate([np.asarray(x, dtype=np.float64) for x in s]), _e
                    )
                ).values
            elif a.func in _MV_REG_AGGS:
                # group-merged value set -> registers, matching the SV twin's
                # partial format so reduce merges via np.maximum
                if a.func in ("distinctcounthllplusmv", "distinctcountrawhllplusmv"):
                    from pinot_tpu.query.aggregates import _hpp_p
                    from pinot_tpu.query.distinct_sketch import hllplus_registers

                    def _regs(v, _p=_hpp_p(a.extra)):
                        return hllplus_registers(v, _p)

                else:
                    from pinot_tpu.query.sketches import np_hll_registers as _regs

                out[f"a{i}p0"] = g[f"m{i}p0"].apply(
                    lambda s, _r=_regs: _r(np.asarray(list(set().union(*s))))
                ).values
            else:  # distinct*-mv set partials
                out[f"a{i}p0"] = g[f"m{i}p0"].agg(lambda s: set().union(*s)).values
            continue
        if a.func in _funnel_mod().FUNNEL_AGGS:
            fun = _funnel_mod()
            nsteps = len(a.extra[-1])
            if fun.is_windowed(a.func):
                def _fpart(sub, _i=i):
                    b = sub[f"fb{_i}"].to_numpy(np.int64)
                    keep = b != 0
                    return fun.events_partial(
                        sub[f"fc{_i}"].to_numpy()[keep],
                        sub[f"ft{_i}"].to_numpy(np.float64)[keep],
                        b[keep],
                    )
            else:
                def _fpart(sub, _i=i, _n=nsteps):
                    b = sub[f"fb{_i}"].to_numpy(np.int64)
                    c = sub[f"fc{_i}"].to_numpy()
                    return [set(c[(b & (1 << k)) != 0].tolist()) for k in range(_n)]
            out[f"a{i}p0"] = g.apply(_fpart, include_groups=False).values
            continue
        if a.func == "count":
            if i in null_aggs:
                out[f"a{i}p0"] = g[f"cn{i}"].sum().values
            elif filtered:
                out[f"a{i}p0"] = g[f"f{i}"].sum().values
            else:
                out[f"a{i}p0"] = out["__size"]
        elif a.func == "sum":
            if null_on:
                # min_count=1 keeps all-null (or all-filter-excluded) groups
                # NaN -> finalized to NULL, matching the device kernel
                out[f"a{i}p0"] = g[f"v{i}"].sum(min_count=1).values.astype(np.float64)
            else:
                out[f"a{i}p0"] = np.nan_to_num(g[f"v{i}"].sum().values.astype(np.float64))
        elif a.func == "min":
            v = g[f"v{i}"].min().values.astype(np.float64)
            out[f"a{i}p0"] = np.where(np.isnan(v), np.inf, v) if (filtered or i in null_aggs) else v
        elif a.func == "max":
            v = g[f"v{i}"].max().values.astype(np.float64)
            out[f"a{i}p0"] = np.where(np.isnan(v), -np.inf, v) if (filtered or i in null_aggs) else v
        elif a.func == "avg":
            if null_on:
                out[f"a{i}p0"] = g[f"v{i}"].sum(min_count=1).values.astype(np.float64)
            else:
                out[f"a{i}p0"] = np.nan_to_num(g[f"v{i}"].sum().values.astype(np.float64))
            if i in null_aggs:
                # null handling: count non-NaN rows — v already folds in the
                # FILTER mask (excluded rows were NaN-ed first), so this is
                # filter-passing AND non-null
                out[f"a{i}p1"] = g[f"v{i}"].count().values
            elif filtered:
                out[f"a{i}p1"] = g[f"f{i}"].sum().values
            else:
                out[f"a{i}p1"] = out["__size"]
        elif a.func == "minmaxrange":
            lo = g[f"v{i}"].min().values.astype(np.float64)
            hi = g[f"v{i}"].max().values.astype(np.float64)
            if filtered:
                lo = np.where(np.isnan(lo), np.inf, lo)
                hi = np.where(np.isnan(hi), -np.inf, hi)
            out[f"a{i}p0"] = lo
            out[f"a{i}p1"] = hi
        elif a.func in ("distinctcount", "distinctcountbitmap"):
            if i in null_aggs:
                out[f"a{i}p0"] = g[f"v{i}"].agg(lambda s: set(s.dropna().tolist())).values
            else:
                out[f"a{i}p0"] = g[f"v{i}"].agg(lambda s: set(s.tolist())).values
        elif a.func == "distinctcounthll":
            # register partials, SAME format as the device matrix path: a
            # host-fallback segment then merges with device segments via
            # np.maximum instead of crashing on set|ndarray
            from pinot_tpu.query.sketches import np_hll_registers

            out[f"a{i}p0"] = g[f"v{i}"].apply(
                lambda s, _na=(i in null_aggs): np_hll_registers(
                    _dropna_typed(s) if _na else s.to_numpy()
                )
            ).values
        elif a.func == "percentileest" and ctx.hints.get("est_bounds", {}).get(a.name):
            # histogram tuples over the engine's global bounds, matching the
            # device matrix path's partial format
            from pinot_tpu.query.sketches import np_est_hist

            lo_b, hi_b = ctx.hints["est_bounds"][a.name]
            out[f"a{i}p0"] = g[f"v{i}"].apply(
                lambda s, _lo=lo_b, _hi=hi_b, _na=(i in null_aggs): (
                    np_est_hist(np.asarray(s.dropna() if _na else s), _lo, _hi),
                    _lo,
                    _hi,
                )
            ).values
        elif a.func == "percentiletdigest":
            from pinot_tpu.query.aggregates import _td_comp
            from pinot_tpu.query.quantile_sketch import td_from_values

            out[f"a{i}p0"] = g[f"v{i}"].apply(
                lambda s, _na=(i in null_aggs), _c=_td_comp(a.extra): td_from_values(
                    np.asarray(s.dropna() if _na else s, dtype=np.float64), _c
                )
            ).values
        elif a.func in ("percentile", "percentileest"):
            # .apply, not .agg: pandas agg rejects array-valued reducers
            out[f"a{i}p0"] = g[f"v{i}"].apply(
                lambda s, _na=(i in null_aggs): np.asarray(
                    s.dropna() if _na else s, dtype=np.float64
                )
            ).values
        elif a.func == "mode":
            def _counter(s, _na=(i in null_aggs)):
                vals, counts = np.unique(np.asarray(s.dropna() if _na else s), return_counts=True)
                return {float(k): int(c) for k, c in zip(vals, counts)}

            out[f"a{i}p0"] = g[f"v{i}"].apply(_counter).values
        elif a.func == "distinctcounttheta" and a.extra:
            from pinot_tpu.query.aggregates import _theta_compute

            def _theta_multi(sub, _i=i, _nf=theta_nf[i]):
                v = sub[f"v{_i}"].to_numpy()
                if _nf == 0:
                    return _theta_compute(v, None, ())
                return (
                    "multi",
                    [
                        _theta_compute(v[sub[f"tf{_i}_{_j}"].to_numpy(bool)], None, ())
                        for _j in range(_nf)
                    ],
                )

            out[f"a{i}p0"] = g.apply(_theta_multi, include_groups=False).values
        elif a.func in EXT_AGGS:
            spec = EXT_AGGS[a.func]
            na = i in null_aggs
            if a.arg2 is not None:
                parts = g.apply(
                    lambda sub, _i=i, _s=spec, _a=a, _na=na: _s.compute(
                        *(
                            lambda s2: (s2[f"v{_i}"].to_numpy(), s2[f"w{_i}"].to_numpy())
                        )(sub.dropna(subset=[f"v{_i}"]) if _na else sub),
                        _a.extra,
                    ),
                    include_groups=False,
                )
            else:
                parts = g[f"v{i}"].apply(
                    lambda s, _s=spec, _a=a, _na=na: _s.compute(
                        _dropna_typed(s) if _na else s.to_numpy(), None, _a.extra
                    )
                )
            out[f"a{i}p0"] = parts.values
        else:
            raise PlanError(f"unsupported aggregation in host executor: {a.func}")
    return out.drop(columns=["__size"])


def distinct_frame(seg: ImmutableSegment, ctx: QueryContext, mask: np.ndarray) -> pd.DataFrame:
    data = {}
    mv_cols: list[str] = []
    mv_str: dict[str, bool] = {}
    for i, it in enumerate(ctx.select_items):
        ci_s = seg.columns.get(it.expr.name) if isinstance(it.expr, ast.Identifier) else None
        if ci_s is not None and ci_s.is_mv:
            # SELECT DISTINCT mv_col: one row per VALUE (mirrors the device
            # path's value-space group ids and group_frame's explode)
            v = eval_value(seg, it.expr)[mask]
            data[f"k{i}"] = [list(x) for x in v]
            mv_cols.append(f"k{i}")
            mv_str[f"k{i}"] = ci_s.data_type.value in ("STRING", "JSON", "BYTES")
            continue
        v = eval_value(seg, it.expr)[mask]
        data[f"k{i}"] = v.astype(str) if v.dtype == object else v
    df = pd.DataFrame(data)
    for c in mv_cols:
        df = df.explode(c, ignore_index=True)
    if mv_cols and len(df):
        df = df.dropna(subset=mv_cols).reset_index(drop=True)
        for c in mv_cols:
            df[c] = df[c].astype(str) if mv_str[c] else pd.to_numeric(df[c])
    return df.drop_duplicates()


def expr_null_mask(seg: ImmutableSegment, expr) -> "np.ndarray | None":
    """Docs where ANY column referenced by expr is null (null-propagation:
    an expression over a null input is null), or None when no referenced
    column has a null vector."""
    from pinot_tpu.native import bm_to_bool
    from pinot_tpu.query.context import _collect_identifiers

    if isinstance(expr, ast.FunctionCall) and expr.name == "coalesce":
        # COALESCE is null only where ALL arguments are null — the generic
        # union-of-identifiers propagation would mark rows null exactly
        # where the function exists to provide a fallback
        m = None
        for a in expr.args:
            am = expr_null_mask(seg, a)
            if am is None:
                return None  # some argument is never null -> result never null
            m = am if m is None else (m & am)
        return m

    idents: set[str] = set()
    _collect_identifiers(expr, idents)
    nulls = None
    for name in idents:
        nv = (seg.extras or {}).get("null", {}).get(name)
        if nv is None:
            continue
        b = bm_to_bool(nv, seg.n_docs)
        nulls = b if nulls is None else (nulls | b)
    return nulls


def _selection_nulls(seg: ImmutableSegment, ctx: QueryContext, expr) -> "np.ndarray | None":
    """Null mask for a selected expression under enableNullHandling, else
    None (selection rows then emit None instead of the stored placeholder —
    BaseResultsBlock null-handling parity)."""
    from pinot_tpu.query.context import null_handling_enabled

    if not null_handling_enabled(ctx.options):
        return None
    return expr_null_mask(seg, expr)


def _null_subst(v: np.ndarray, nm: np.ndarray) -> np.ndarray:
    out = v.astype(object)
    out[nm] = None
    return out


def selection_frame(seg: ImmutableSegment, ctx: QueryContext, mask: np.ndarray, k: int) -> pd.DataFrame:
    idx = np.nonzero(mask)[0][:k]
    data = {}
    for i, it in enumerate(ctx.select_items):
        v = eval_value(seg, it.expr)[idx]
        nm = _selection_nulls(seg, ctx, it.expr)
        data[f"c{i}"] = _null_subst(v, nm[idx]) if nm is not None else v
    return pd.DataFrame(data)


def selection_ob_frame(seg: ImmutableSegment, ctx: QueryContext, mask: np.ndarray, k: int) -> pd.DataFrame:
    keys = []
    for j, ob in enumerate(ctx.order_by):
        v = eval_value(seg, ob.expr)
        nm = _selection_nulls(seg, ctx, ob.expr)
        if nm is not None:
            # null keys become NaN/None; sort_nulls_largest below ranks them
            # as the largest value (last for ASC, FIRST for DESC) per the
            # reference default. Object columns must keep None — no
            # astype(str) which would emit 'None'.
            if v.dtype == object or v.dtype.kind in "US":
                v = v.astype(object)
                v[nm] = None
            else:
                v = np.where(nm, np.nan, v.astype(np.float64))
            keys.append((f"__key{j}", v, not ob.desc))
        else:
            keys.append((f"__key{j}", v.astype(str) if v.dtype == object else v, not ob.desc))
    df = pd.DataFrame({name: v for name, v, _ in keys})
    df = df[mask]
    proj = {}
    for i, it in enumerate(ctx.select_items):
        v = eval_value(seg, it.expr)[mask]
        nm = _selection_nulls(seg, ctx, it.expr)
        proj[f"c{i}"] = _null_subst(v, nm[mask]) if nm is not None else v
    for c, v in proj.items():
        df[c] = v
    from pinot_tpu.common.sorting import sort_nulls_largest

    df = sort_nulls_largest(df, [n for n, _, _ in keys], [a for _, _, a in keys])
    return df.head(k)
