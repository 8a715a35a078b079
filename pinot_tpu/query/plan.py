"""Per-segment physical planning: QueryContext -> (static spec, dynamic operands).

Reference parity: InstancePlanMakerImplV2.makeSegmentPlanNode (pinot-core/.../
plan/maker/InstancePlanMakerImplV2.java:291) + the filter operators
(core/operator/filter/) and predicate evaluators. Redesigned for XLA:

 * The *spec* is a hashable nested tuple describing the program shape
   (predicate kinds, aggregation set, group layout, static padded sizes).
   Kernels are compiled once per spec (compile cache ~ Pinot's plan cache).
 * All literals/bounds/LUTs are *operands* (dynamic device inputs), so
   `WHERE league='NL'` and `WHERE league='AL'` share one compiled program.
 * Predicates on dictionary-encoded columns lower to integer id compares with
   host-resolved bounds (the sorted-dictionary trick from
   BaseDictionaryBasedPredicateEvaluator); IN/LIKE/REGEXP lower to a boolean
   LUT over dict ids, gathered per doc. LUT/dict-value arrays are padded to
   powers of two so different cardinalities reuse compiled programs.
 * Dense group ids are sum(ids_i * stride_i) — the cardinality-product scheme
   of DictionaryBasedGroupKeyGenerator.java:119-130 — fed to segment_sum with
   a pow2-padded static group count.

When a query shape has no device path yet (high-cardinality group-by,
expression group keys, over-budget grouped distinct matrices), lowering
raises `DeviceFallback` and the engine runs the host executor instead
(correctness first; the fallback set shrinks each round).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from pinot_tpu.common.types import DataType
from pinot_tpu.query import ast
from pinot_tpu.query.ast import CompareOp, Expr, FilterExpr
from pinot_tpu.query.context import AggregationInfo, QueryContext, QueryType
from pinot_tpu.segment.segment import ImmutableSegment, narrows_to_int32

MAX_DENSE_GROUPS = 1 << 20

#: most real groups for which a group-by states its real group count, which
#: turns its non-int32 SUM / AVG / MIN / MAX into dense masked reductions
#: (kernels._grouped_reduce). From the v5e's sweep at 4M rows
#: (benchmarks/grouped_dense_ab.py; PERF.md §6, PR 28 and PR 36): the f64
#: scatter is 285-340 ms up to 4096 groups, the dense form 0.025 ms a slot —
#: 0.3 ms at 8, 6.5 at 256, 102 at 4096, still 3.3x ahead. Measured since (PR 36):
#: dense 203 / 299 / 407 / 1626 ms at 8192 / 12,032 / 16,384 / 65,536 groups
#: against the scatter's 378 / 434 / 418 / 468 — the two meet at 16,384 — and
#: a SUM as limbs on the Pallas kernel (kernels._grouped_all, which takes
#: what states no real count) 2.6 ms up to 1024 groups, 4.3 at 4096, 8.9 at
#: 12,032, 39 at 65,536: under the dense form from 128 groups on, which this
#: threshold does not yet use (PERF.md §7).
DENSE_REDUCE_MAX_GROUPS = 4096

#: the dense product of a single-value group-by's key cardinalities from which
#: the program contracts the groups the filter leaves and not the product
#: (`group_spec`'s kind "groups_compact", kernels._compact_groups): every key
#: is renumbered by the values that survive the filter and the byte-plane
#: kernel runs over COMPACT_SLOTS slots, whatever the product — also past
#: MAX_DENSE_GROUPS, where the sort-compaction path stays as the fallback of
#: a segment whose surviving combinations pass the slots. On the v5e at 4M
#: rows a compact launch is 2.1-2.9 ms whatever the product, a dense one 1.8-2.3
#: ms at 4,096 groups, 4.3-4.5 at 16,384, 22 at 65,536, 99 at 437,500
#: (benchmarks/planes_ab.py --compact; PERF.md §6, PR 45): they meet between
#: 4,096 and 8,192. The constant stands at 16,384 so that no group-by of
#: fewer groups changes its program (PERF.md §7).
COMPACT_MIN_GROUPS = 16384
#: slots of the compact group space: one hi tile of the kernel at G2 = 32
COMPACT_SLOTS = 4096
#: the widest key that is renumbered, by two dense passes of (32 values a
#: word, rows) compares; a wider one is carried whole, at its cardinality
COMPACT_MAX_KEY_CARD = 4096

# Virtual columns provided at query time (VirtualColumnProvider parity,
# pinot-segment-local/.../segment/virtualcolumn/VirtualColumnProvider.java).
VIRTUAL_COLUMNS = ("$docId", "$segmentName", "$hostName")


class DeviceFallback(Exception):
    """Query shape has no device lowering yet; use the host executor.
    `reason` is the class of the message, the `reason` label of
    `server.deviceFallbacks`: given where the message names a column or a
    function, else the message's own words."""

    def __init__(self, message: str, reason: str | None = None):
        super().__init__(message)
        self.reason = reason or "_".join(re.findall(r"[A-Za-z][A-Za-z0-9+-]*", message)[:8]).lower()


def mark_device_fallback(e: Exception, where: str) -> None:
    """Count a segment that left the device path under the reason of what it
    raised (`server.deviceFallbacks{reason=}`; a PlanError caught in a
    fallback's place counts as `plan_error`), and say the first of each reason
    in the server's log: `where` and the message."""
    import logging

    from pinot_tpu.common.metrics import ServerMeter, server_metrics

    meter = server_metrics().meter(ServerMeter.DEVICE_FALLBACKS, reason=getattr(e, "reason", "plan_error"))
    if meter.count == 0:
        logging.getLogger("pinot_tpu.query").warning("%s runs on the host executor: %s (the first of its reason)", where, e)
    meter.mark()


class PlanError(ValueError):
    """Query is invalid against this segment/schema."""


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _eighths(n: int) -> int:
    """`n` rounded up to eighths of its next power of two, at least 8 (6 -> 8, 175 -> 192, 4000 -> 4096)."""
    step = max(8, _pow2(n) // 8)
    return -(-n // step) * step


def group_strides(cards: list, dtype=np.int64) -> np.ndarray:
    """Row-major strides over group-key cardinalities: ids dot strides gives
    the dense group id (DictionaryBasedGroupKeyGenerator.java:119-130)."""
    strides = np.ones(len(cards), dtype=dtype)
    for i in range(len(cards) - 2, -1, -1):
        strides[i] = strides[i + 1] * max(cards[i + 1], 1)
    return strides


@dataclass
class KeyBuckets:
    """What an expression GROUP BY key's ids decode through: the distinct
    values the expression takes over its column's dictionary, ascending, as
    a dictionary of their own. A bare column's ColumnIndex has the same two
    attributes."""

    dictionary: Any

    @property
    def cardinality(self) -> int:
        return self.dictionary.cardinality


@dataclass
class SegmentPlan:
    spec: tuple  # static, hashable — keys the kernel compile cache
    operands: tuple  # numpy arrays/scalars fed as dynamic inputs
    columns: tuple[str, ...]  # device arrays the kernel reads, in order
    # raw columns read as values, which the spec names by position ("@0", "@1", ...: _Lowering.raw_value)
    value_columns: tuple[str, ...] = ()
    # host-side decode info
    group_cols: list[tuple[str, Any]] = field(default_factory=list)  # (key, ColumnIndex | KeyBuckets)
    select_decode: list[tuple] = field(default_factory=list)
    aggs: list[AggregationInfo] = field(default_factory=list)
    # multi-key ORDER BY composite: [(col, card, desc, kind, offset)], most
    # significant key first — the host decomposes the composite rank back
    # into per-key sort values
    ob_decomp: list[tuple] | None = None


class _Lowering:
    def __init__(self, seg: ImmutableSegment, ctx: QueryContext, compact: bool = True):
        self.seg = seg
        self.ctx = ctx
        self.compact = compact  # False: a large dense group space keeps the spec it had before "groups_compact" (plan_segment)
        self.operands: list[Any] = []
        self.columns: list[str] = []
        self.value_columns: list[str] = []  # see raw_value
        self._group_ng = 1  # set by group_spec; agg budget checks consult it
        self._group_real = None  # set by group_spec: real groups of a dense group space
        self.group_cols: list[tuple[str, Any]] = []  # set by group_spec: (key, what its ids decode through)
        # null docmask operand index per frozenset of columns: one decode +
        # one device transfer however many Kleene leaves reference them
        self._null_mask_ops: dict[frozenset, int] = {}
        # (dimension table, destination, foreign-key column) -> the spec node of its gather (lookup_node)
        self._lookups: dict[tuple, tuple] = {}
        # (dimension table, foreign-key column, operand word) -> the operand's index: one gather serves its destinations
        self._lookup_words: dict[tuple, int] = {}

    # -- operand / column registration --------------------------------------

    def op_idx(self, value) -> int:
        # a numpy array of its final dtype, whatever kind of value came in:
        # the jitted program is handed the plan's operands as they are
        # (kernels.stage_operand), and a bare Python number there would be
        # weakly typed, trace another program and promote differently
        self.operands.append(np.asarray(value))
        return len(self.operands) - 1

    def use_col(self, col: str) -> str:
        if col not in self.seg.columns:
            raise PlanError(f"unknown column {col!r} in table {self.ctx.table}")
        if col not in self.columns:
            self.columns.append(col)
            if self.seg.columns[col].is_mv:
                # flattened MV: kernels also need the owning-doc-id vector
                self.columns.append(f"{col}!docs")
        return col

    def raw_value(self, col: str) -> tuple:
        """A raw single-value column read as a value: ("raw", "@<i>"), the
        column named by its place among the plan's `value_columns` and not by
        its name, so that queries that differ only in which raw column they
        read (AVG(usage_user), AVG(usage_irq)) share one program."""
        if col not in self.seg.columns:
            raise PlanError(f"unknown column {col!r} in table {self.ctx.table}")
        if col not in self.value_columns:
            self.value_columns.append(col)
        return ("raw", f"@{self.value_columns.index(col)}")

    def _raw_column(self, vspec: tuple):
        """The segment's column behind a ("raw", ...) value spec, named or placed."""
        name = vspec[1]
        return self.seg.columns[self.value_columns[int(name[1:])] if name.startswith("@") else name]

    def _mv_wrap(self, col: str, spec: tuple) -> tuple:
        """Wrap a flat (per-value) predicate spec into MV any-match doc
        semantics. Top-level NOT stays OUTSIDE the wrap: Pinot's MV exclusion
        predicates (NEQ / NOT IN) match docs where NO value satisfies the
        positive form (reference: NotEqualsPredicateEvaluator applyMV)."""
        if spec[0] == "const":
            return spec
        if spec[0] == "not":
            return ("not", self._mv_wrap(col, spec[1]))
        nv = self.op_idx(np.int32(len(self.seg.columns[col].forward)))
        return ("mv_any", col, spec, nv)

    def null_wrap(self, info: AggregationInfo, spec: tuple) -> tuple:
        """enableNullHandling: AND a non-null doc mask over the aggregation
        (rows whose arg column is null are skipped — NullableSingleInput-
        AggregationFunction parity). No null vector -> spec unchanged.
        The mask comes from the SAME helper the host executor uses, so the
        two paths cannot diverge."""
        from pinot_tpu.query.host_exec import _null_doc_mask

        nulls = _null_doc_mask(self.seg, info)
        inner = spec
        while inner[0] == "masked":
            inner = inner[2]
        if inner[0] == "sum":
            # SUM cannot distinguish "all rows null" (or "no rows matched" —
            # both NULL under null handling) from a genuine 0 via a sentinel
            # (min/max use +/-inf); the kernel emits NaN for empty groups so
            # the reduce finalizes them to NULL. Wrapped even without a null
            # vector: a FILTER/WHERE matching zero rows must also yield NULL.
            nn = ("const", True) if nulls is None or not nulls.any() else self.docmask_spec(~nulls)
            return ("masked_nan_empty", nn, spec)
        if nulls is None or not nulls.any():
            return spec
        return ("masked", self.docmask_spec(~nulls), spec)

    def docmask_spec(self, mask: np.ndarray) -> tuple:
        """Host-computed doc mask -> device filter operand (the TPU analog of
        Pinot's index filter operators handing a RoaringBitmap to the tree)."""
        from pinot_tpu.segment.segment import padded_len

        pad = padded_len(self.seg.n_docs)
        m = np.zeros(pad, dtype=bool)
        m[: len(mask)] = mask
        return ("docmask", self.op_idx(m))

    # -- value expressions ---------------------------------------------------

    def value_spec(self, expr: Expr) -> tuple:
        """Lower a value expression to a spec computing per-doc float64/int
        values on device."""
        if isinstance(expr, ast.Identifier):
            if expr.name == "$docId":
                return ("docid",)
            if expr.name in VIRTUAL_COLUMNS:
                raise DeviceFallback(f"virtual column {expr.name} in value context runs host-side", reason="virtual_column_value")
            ci = self.seg.columns.get(expr.name)
            if ci is None:
                raise PlanError(f"unknown column {expr.name!r}")
            if ci.is_mv:
                raise DeviceFallback(
                    f"MV column {expr.name!r} in value context runs host-side (use the *MV aggregations)",
                    reason="mv_column_value",
                )
            if ci.data_type in (DataType.STRING, DataType.BYTES, DataType.JSON):
                raise PlanError(f"column {expr.name!r} is not numeric")
            if not ci.is_dict_encoded:
                return self.raw_value(expr.name)
            self.use_col(expr.name)
            # operand: dictionary values padded to pow2 (repeat last value)
            dv = np.asarray(ci.dictionary.values)
            pad = _pow2(max(len(dv), 1))
            if len(dv) == 0:
                dv = np.zeros(1, dtype=ci.data_type.np_dtype)
            if len(dv) < pad:
                dv = np.concatenate([dv, np.full(pad - len(dv), dv[-1], dtype=dv.dtype)])
            return ("dictval", expr.name, self.op_idx(dv))
        if isinstance(expr, ast.Literal):
            if not isinstance(expr.value, (int, float, bool)):
                raise PlanError(f"non-numeric literal in value expression: {expr}")
            return ("lit", self.op_idx(np.float64(expr.value)))
        if isinstance(expr, ast.BinaryOp):
            return ("bin", expr.op, self.value_spec(expr.left), self.value_spec(expr.right))
        if isinstance(expr, ast.FunctionCall):
            return self._function_value(expr)
        if isinstance(expr, ast.CaseWhen):
            # CASE -> chained jnp.where over the branch masks
            # (CaseTransformFunction parity). Missing ELSE takes the numeric
            # default 0 (Pinot's null-handling-disabled behavior); string
            # results don't lower (host path handles them).
            branch_vals = [v for _, v in expr.whens] + (
                [expr.else_] if expr.else_ is not None else []
            )
            for val in branch_vals:
                if isinstance(val, ast.Literal) and not isinstance(val.value, (int, float, bool)):
                    raise DeviceFallback("non-numeric CASE branches run host-side")
                if isinstance(val, ast.Identifier):
                    ci = self.seg.columns.get(val.name)
                    if ci is not None and ci.data_type in (
                        DataType.STRING,
                        DataType.BYTES,
                        DataType.JSON,
                    ):
                        raise DeviceFallback("string-typed CASE branches run host-side")
            whens = tuple(
                (self.filter_spec(cond), self.value_spec(val)) for cond, val in expr.whens
            )
            else_spec = (
                self.value_spec(expr.else_)
                if expr.else_ is not None
                else ("lit", self.op_idx(np.float64(0.0)))
            )
            return ("case", whens, else_spec)
        raise PlanError(f"unsupported value expression: {expr}")

    def _function_value(self, expr: ast.FunctionCall) -> tuple:
        from pinot_tpu.query.transforms import (
            DEVICE_FUNCS,
            STRING_FUNCS,
            TIME_REWRITES,
            apply_string_func,
            rewrite_time_convert,
        )

        name = expr.name
        if name in TIME_REWRITES:
            rw = rewrite_time_convert(expr)
            if rw is not None:
                return self.value_spec(rw)
        if name == "map_value":
            # map-index key reads return object values: host-side
            raise DeviceFallback("map_value runs host-side (map index probe)")
        if name == "lookup":
            # on the device a lookUp is a GROUP BY key or a filter's left side (lookup_node);
            # as a value (an aggregate's argument, a selected column) the host evaluates it
            raise DeviceFallback("lookUp as a value (an aggregate's argument, a selected column) runs host-side", reason="lookup_in_value")
        if name == "cast":
            if len(expr.args) != 2 or not isinstance(expr.args[1], ast.Literal):
                raise PlanError("CAST requires CAST(expr AS type)")
            target = str(expr.args[1].value).upper()
            if target in ("INT", "LONG", "TIMESTAMP", "BOOLEAN"):
                return ("cast_int", self.value_spec(expr.args[0]))
            if target in ("FLOAT", "DOUBLE"):
                return ("cast_float", self.value_spec(expr.args[0]))
            raise DeviceFallback(f"CAST to {target} runs host-side", reason="cast_target")
        if name in DEVICE_FUNCS:
            arity, _ = DEVICE_FUNCS[name]
            if len(expr.args) != arity:
                raise PlanError(f"{name} expects {arity} args, got {len(expr.args)}")
            return ("fn", name, tuple(self.value_spec(a) for a in expr.args))
        if name in STRING_FUNCS:
            # numeric-returning string functions (strlen, startswith, ...) over
            # a dict column become a derived value table gathered by ids —
            # cardinality-sized host work, doc-sized device gather.
            derived, is_str, col = self._derived_string_values(expr)
            if is_str:
                # string-valued projection: the host executor evaluates it
                # (device selections return numeric/id columns only)
                raise DeviceFallback(f"string-valued {name}(...) runs host-side", reason="string_valued_function")
            self.use_col(col)
            pad = _pow2(max(len(derived), 1))
            dv = derived
            if len(dv) == 0:
                dv = np.zeros(1, dtype=np.float64)
            if len(dv) < pad:
                dv = np.concatenate([dv, np.full(pad - len(dv), dv[-1])])
            return ("dictval", col, self.op_idx(dv))
        raise DeviceFallback(f"transform function {name} has no device lowering yet", reason="transform_function")

    def _derived_string_values(self, expr: ast.FunctionCall):
        """Evaluate a string function over a dict column's VALUES host-side.
        Returns (derived value array, returns_string, column name)."""
        from pinot_tpu.query.transforms import apply_string_func

        if not expr.args or not isinstance(expr.args[0], ast.Identifier):
            raise DeviceFallback(f"{expr.name} over non-column args runs host-side", reason="string_function_args")
        col = expr.args[0].name
        ci = self.seg.columns.get(col)
        if ci is None:
            raise PlanError(f"unknown column {col!r}")
        if not ci.is_dict_encoded:
            raise DeviceFallback(f"{expr.name} over raw column runs host-side", reason="string_function_raw_column")
        lit_args = []
        for a in expr.args[1:]:
            if not isinstance(a, ast.Literal):
                raise DeviceFallback(f"{expr.name} with non-literal args runs host-side", reason="string_function_args")
            lit_args.append(a.value)
        derived, is_str = apply_string_func(expr.name, ci.dictionary.values, tuple(lit_args))
        return derived, is_str, col

    def _string_fn_lut(self, expr: ast.FunctionCall, pred) -> tuple:
        """Predicate over a string-function-of-dict-column lowers to a LUT
        over dict ids (evaluated per distinct value host-side)."""
        derived, is_str, col = self._derived_string_values(expr)
        if not is_str:
            raise PlanError(f"{expr.name} is not string-valued")
        self.use_col(col)
        lut = np.zeros(_pow2(max(len(derived), 1)), dtype=bool)
        for i, v in enumerate(derived):
            if pred(str(v)):
                lut[i] = True
        if not lut.any():
            return ("const", False)
        if lut[: max(len(derived), 1)].all():
            return ("const", True)
        return ("in_lut", col, self.op_idx(lut))

    # -- lookUp -----------------------------------------------------------------

    def lookup_node(self, expr: ast.FunctionCall) -> tuple:
        """lookUp('dim', 'dest', 'pk', fk) over one single-value dictionary-coded
        column `fk`: (the spec node of the rows' destination codes, the
        dimension table, dest). The node is ("lookup", fk, operand, shift,
        mask, miss code, counts misses): the serving server's resident
        fk code -> codes table (DimensionTableDataManager.operand: built once
        a segment, foreign key and table generation, marked stable here, so
        staged once and kept on the chip) holds every destination's code as a
        bit field, so a launch gathers once a foreign key and operand word
        however many destinations its query reads, a query in the steady
        state builds and ships nothing, and every segment shares one program;
        a key without a dimension row gathers the miss code, one past the
        destination's last. The first node of each (dimension table, fk) has
        the launch count its misses (kernels._lookup_codes). Any other form
        of the call falls back, under a reason of its own."""
        from pinot_tpu.common.trace import count, span
        from pinot_tpu.query.host_exec import lookup_call
        from pinot_tpu.query.kernels import mark_stable_operand

        dim, dest, key_exprs = lookup_call(expr)
        if len(key_exprs) != 1:
            raise DeviceFallback(f"lookUp by the composite key {dim.pk_columns} runs host-side", reason="lookup_composite_key")
        (key,) = key_exprs
        if not isinstance(key, ast.Identifier) or key.name in VIRTUAL_COLUMNS:
            raise DeviceFallback(f"lookUp by the expression {key} runs host-side", reason="lookup_key_expression")
        fk = key.name
        node = self._lookups.get((dim.table, dest, fk))
        if node is not None:
            return node, dim, dest
        ci = self.seg.columns.get(fk)
        if ci is None:
            raise PlanError(f"unknown column {fk!r}")
        if ci.is_mv:
            raise DeviceFallback(f"lookUp by the multi-value column {fk} runs host-side", reason="lookup_mv_key")
        if not ci.is_dict_encoded:
            raise DeviceFallback(f"lookUp by the raw column {fk} runs host-side", reason="lookup_raw_key")
        if not dim.has_column(dest):
            raise DeviceFallback(f"lookUp of {dest!r}, which {dim.table} has not, runs host-side", reason="lookup_unknown_column")
        self.use_col(fk)
        word, shift, mask = dim.field(dest)
        if (dim.table, fk, word) not in self._lookup_words:
            with span("server.plan.lookup", dim=dim.table, dest=dest) as sp:
                operand, built = dim.operand(ci.dictionary, word)
                sp.set_attr("cached", not built)
            if built:
                mark_stable_operand(operand)
                count("lookupOperandBuilds")
                count("lookupOperandBytesStaged", operand.nbytes)
            self._lookup_words[(dim.table, fk, word)] = self.op_idx(operand)
        first = not any(d == dim.table and f == fk for d, _, f in self._lookups)
        miss = len(dim.dest_values(dest))
        node = ("lookup", fk, self._lookup_words[(dim.table, fk, word)], self.op_idx(np.int32(shift)),
                self.op_idx(np.int32(mask)), self.op_idx(np.int32(miss)), first)  # fmt: skip
        self._lookups[(dim.table, dest, fk)] = node
        return node, dim, dest

    @staticmethod
    def _is_lookup(expr) -> bool:
        return isinstance(expr, ast.FunctionCall) and expr.name == "lookup"

    def lookup_filter(self, f) -> tuple:
        """A Compare against a literal, Between, In, Like or RegexpLike whose
        left side is a lookUp. The predicate is evaluated host-side over the
        destination's distinct values and its null substitute (the host
        executor's own `value_predicate`: a few hundred values, whatever the
        foreign key's dictionary holds), and the rows' gathered destination
        codes are tested against the result: two integer compares where the
        codes that pass (or those that fail) are one run — every =, <>, <, >
        and BETWEEN over a sorted dictionary — else a gather through a small
        boolean table. Composing the predicate onto the foreign key's codes
        would save nothing on the device (it is the same gather through the
        same million entries) and would build and ship an operand of the
        foreign key's size with every query and segment, which the resident
        operand exists to avoid."""
        from pinot_tpu.query.host_exec import value_predicate

        node, dim, dest = self.lookup_node(f.left if isinstance(f, ast.Compare) else f.expr)
        hits = value_predicate(dim.decode_table(dest), f)
        if not hits.any():
            return ("const", False)
        if hits.all():
            return ("const", True)
        for want, wrap in ((True, lambda spec: spec), (False, lambda spec: ("not", spec))):
            at = np.flatnonzero(hits == want)
            if at[-1] - at[0] + 1 == len(at):
                return wrap(("lookup_range", node, self.op_idx(np.int32(at[0])), self.op_idx(np.int32(at[-1]))))
        lut = np.zeros(_pow2(len(hits)), dtype=bool)
        lut[: len(hits)] = hits
        return ("lookup_lut", node, self.op_idx(lut))

    def lookup_key(self, g: ast.FunctionCall) -> tuple[tuple, "KeyBuckets"]:
        """A lookUp as a GROUP BY key: a gathered key as `expr_key`'s, whose
        buckets are the destination's distinct values and one bucket more for
        the rows whose key has no dimension row ('null', or NaN for a numeric
        destination, as the host evaluator answers) — the same for every
        segment, so all of them share one program and one dense group space."""
        from pinot_tpu.segment.dictionary import Dictionary

        node, dim, dest = self.lookup_node(g)
        table = dim.decode_table(dest)
        dt = DataType.STRING if table.dtype == object else DataType.DOUBLE
        return ("lookup_key", node), KeyBuckets(Dictionary(dt, table.astype(str) if table.dtype == object else table))

    # -- filters -------------------------------------------------------------

    def filter_spec(self, f: FilterExpr | None) -> tuple:
        if f is None:
            return ("const", True)
        if isinstance(f, ast.And):
            kids = [self.filter_spec(c) for c in f.children]
            if any(k == ("const", False) for k in kids):
                return ("const", False)
            kids = [k for k in kids if k != ("const", True)]
            if not kids:
                return ("const", True)
            return kids[0] if len(kids) == 1 else ("and", tuple(kids))
        if isinstance(f, ast.Or):
            kids = [self.filter_spec(c) for c in f.children]
            if any(k == ("const", True) for k in kids):
                return ("const", True)
            kids = [k for k in kids if k != ("const", False)]
            if not kids:
                return ("const", False)
            return kids[0] if len(kids) == 1 else ("or", tuple(kids))
        if isinstance(f, ast.Not):
            k = self.filter_spec(f.child)
            if k[0] == "const":
                return ("const", not k[1])
            return ("not", k)
        if isinstance(f, ast.Compare):
            return self._compare(f)
        if isinstance(f, (ast.Between, ast.In, ast.Like, ast.RegexpLike)) and self._is_lookup(f.expr):
            if isinstance(f, ast.Between) and not (isinstance(f.low, ast.Literal) and isinstance(f.high, ast.Literal)):
                raise PlanError("BETWEEN bounds must be literals")
            if isinstance(f, ast.In) and not all(isinstance(v, ast.Literal) for v in f.values):
                raise PlanError("IN values must be literals")
            return self.lookup_filter(f)
        if isinstance(f, ast.Between):
            spec = self._range(f.expr, f.low, f.high, True, True)
            return ("not", spec) if f.negated else spec
        if isinstance(f, ast.In):
            return self._in(f)
        if isinstance(f, ast.Like):
            pattern = _like_to_regex(f.pattern)
            spec = self._regex_lut(f.expr, pattern, full=True)
            return ("not", spec) if f.negated else spec
        if isinstance(f, ast.RegexpLike):
            return self._regex_lut(f.expr, f.pattern, full=False)
        if isinstance(f, ast.IsNull):
            if isinstance(f.expr, ast.Identifier):
                nv = self.seg.extras.get("null", {}).get(f.expr.name)
                if nv is not None:
                    from pinot_tpu import native

                    nulls = native.bm_to_bool(nv, self.seg.n_docs)
                    return self.docmask_spec(~nulls if f.negated else nulls)
            # no null vector (Pinot default null handling): IS NULL matches nothing
            return ("const", bool(f.negated))
        if isinstance(f, ast.DistinctFrom):
            return self._distinct_from(f)
        if isinstance(f, ast.PredicateFunction):
            return self._predicate_function(f)
        if isinstance(f, ast.BoolAssert):
            raise DeviceFallback("IS [NOT] TRUE/FALSE runs host-side")
        raise PlanError(f"unsupported filter: {f}")

    def where_spec(self, f: "FilterExpr | None") -> tuple:
        """Filter lowering that keeps nullable columns ON DEVICE under
        enableNullHandling: when any referenced column has a null vector, the
        filter lowers to a three-valued (true, unknown) Kleene pair tree
        (k3root) instead of forcing a host fallback (round-3 cliff). Without
        nullable refs (or with null handling off) this is plain filter_spec."""
        from pinot_tpu.query.context import _collect_filter_identifiers, null_handling_enabled

        if f is not None and null_handling_enabled(self.ctx.options):
            refs: set[str] = set()
            _collect_filter_identifiers(f, refs)
            if any((self.seg.extras or {}).get("null", {}).get(c) is not None for c in refs):
                return ("k3root", self.filter3_spec(f))
        return self.filter_spec(f)

    def filter3_spec(self, f: FilterExpr) -> tuple:
        """Three-valued lowering mirroring host_exec._filter3 node-for-node:
        every leaf predicate carries the union of its referenced columns'
        null vectors as a docmask operand; AND/OR/NOT combine (t, u) pairs
        with Kleene semantics in the kernel (_filter_k3)."""
        from pinot_tpu.query.context import _collect_filter_identifiers

        if isinstance(f, ast.And):
            return ("k3_and", tuple(self.filter3_spec(c) for c in f.children))
        if isinstance(f, ast.Or):
            return ("k3_or", tuple(self.filter3_spec(c) for c in f.children))
        if isinstance(f, ast.Not):
            return ("k3_not", self.filter3_spec(f.child))
        if isinstance(f, (ast.IsNull, ast.DistinctFrom)):
            # never unknown: these evaluate null vectors exactly
            return ("k3_exact", self.filter_spec(f))
        spec = self.filter_spec(f)
        refs: set[str] = set()
        _collect_filter_identifiers(f, refs)
        nullable = frozenset(
            c for c in refs if (self.seg.extras or {}).get("null", {}).get(c) is not None
        )
        if not nullable:
            return ("k3_exact", spec)
        idx = self._null_mask_ops.get(nullable)
        if idx is None:
            from pinot_tpu import native

            nulls = None
            for name in nullable:
                b = native.bm_to_bool(self.seg.extras["null"][name], self.seg.n_docs)
                nulls = b if nulls is None else (nulls | b)
            if not nulls.any():
                return ("k3_exact", spec)
            idx = self.docmask_spec(nulls)[1]
            self._null_mask_ops[nullable] = idx
        return ("k3_leaf", spec, idx)

    def _distinct_from(self, f: "ast.DistinctFrom") -> tuple:
        """IS [NOT] DISTINCT FROM: (l != r AND both non-null) OR (exactly one
        null) — composed from the NEQ compare lowering plus null docmasks."""
        from pinot_tpu.query.host_exec import expr_null_mask

        neq = self._compare(ast.Compare(ast.CompareOp.NEQ, f.left, f.right))
        nl = expr_null_mask(self.seg, f.left)
        nr = expr_null_mask(self.seg, f.right)
        if nl is None and nr is None:
            spec = neq
        else:
            nl_spec = self.docmask_spec(nl) if nl is not None else ("const", False)
            nr_spec = self.docmask_spec(nr) if nr is not None else ("const", False)
            xor = (
                "or",
                (
                    ("and", (nl_spec, ("not", nr_spec))),
                    ("and", (nr_spec, ("not", nl_spec))),
                ),
            )
            spec = ("or", (("and", (neq, ("not", nl_spec), ("not", nr_spec))), xor))
        return ("not", spec) if f.negated else spec

    def _predicate_function(self, f: ast.PredicateFunction) -> tuple:
        from pinot_tpu.query.host_exec import predicate_function_mask

        if f.name == "st_within_distance":
            # ST_WITHIN_DISTANCE(lat, lng, qlat, qlng, radius_m): pure device
            # compare over the vectorized haversine; geo index prunes segments
            if len(f.args) != 5 or not isinstance(f.args[4], ast.Literal):
                raise PlanError("ST_WITHIN_DISTANCE(lat, lng, qlat, qlng, radius_m)")
            dist = ast.FunctionCall("st_distance", tuple(f.args[:4]))
            return ("cmp_lit", "LTE", self.value_spec(dist), self.op_idx(np.float64(f.args[4].value)))
        # TEXT_MATCH / JSON_MATCH / VECTOR_SIMILARITY: host index probe -> mask
        return self.docmask_spec(predicate_function_mask(self.seg, f))

    def _compare(self, f: ast.Compare) -> tuple:
        left, op, right = f.left, f.op, f.right
        if isinstance(left, ast.Literal) and not isinstance(right, ast.Literal):
            left, right = right, left
            op = _FLIP[op]
        if isinstance(left, ast.Literal) and isinstance(right, ast.Literal):
            return ("const", _const_compare(op, left.value, right.value))
        if not isinstance(right, ast.Literal):
            # column-vs-column / expr-vs-expr compare: numeric expr compare
            lv, rv = self.value_spec(left), self.value_spec(right)
            return ("cmp2", op.name, lv, rv)
        value = right.value
        if self._is_lookup(left):
            return self.lookup_filter(ast.Compare(op, left, right))
        if isinstance(left, ast.Identifier) and left.name not in VIRTUAL_COLUMNS:
            ci = self.seg.columns.get(left.name)
            if ci is None:
                raise PlanError(f"unknown column {left.name!r}")
            inner = (
                self._dict_compare(left.name, ci, op, value)
                if ci.is_dict_encoded
                else self._raw_compare(left.name, ci, op, value)
            )
            return self._mv_wrap(left.name, inner) if ci.is_mv else inner
        if self._is_string_fn(left):
            sv = str(value)
            pred = {
                CompareOp.EQ: lambda v: v == sv,
                CompareOp.NEQ: lambda v: v != sv,
                CompareOp.LT: lambda v: v < sv,
                CompareOp.LTE: lambda v: v <= sv,
                CompareOp.GT: lambda v: v > sv,
                CompareOp.GTE: lambda v: v >= sv,
            }[op]
            return self._string_fn_lut(left, pred)
        # predicate over computed expression, e.g. a+b > 5
        vs = self.value_spec(left)
        return ("cmp_lit", op.name, vs, self.op_idx(np.float64(value)))

    @staticmethod
    def _is_string_fn(expr) -> bool:
        from pinot_tpu.query.transforms import STRING_FUNCS

        if not (isinstance(expr, ast.FunctionCall) and expr.name in STRING_FUNCS):
            return False
        is_str = STRING_FUNCS[expr.name][2]
        if callable(is_str):  # arg-dependent result type (jsonextractscalar)
            args = tuple(a.value for a in expr.args[1:] if isinstance(a, ast.Literal))
            return is_str(args)
        return is_str

    def _dict_compare(self, col: str, ci, op: CompareOp, value) -> tuple:
        d = ci.dictionary
        if op == CompareOp.EQ:
            i = d.index_of(value)
            if i < 0:
                return ("const", False)
            return self._id_range_filter(col, ci, i, i)
        if op == CompareOp.NEQ:
            i = d.index_of(value)
            if i < 0:
                return ("const", True)
            return ("not", self._id_range_filter(col, ci, i, i))
        if op == CompareOp.LT:
            lo, hi = d.id_range_for(None, value, True, False)
        elif op == CompareOp.LTE:
            lo, hi = d.id_range_for(None, value, True, True)
        elif op == CompareOp.GT:
            lo, hi = d.id_range_for(value, None, False, True)
        else:  # GTE
            lo, hi = d.id_range_for(value, None, True, True)
        if lo > hi:
            return ("const", False)
        # MV skips the const-True shortcut: a doc with an empty value list
        # must not match even a full-dictionary range
        if lo == 0 and hi == d.cardinality - 1 and not ci.is_mv:
            return ("const", True)
        return self._id_range_filter(col, ci, lo, hi)

    def _id_range_filter(self, col: str, ci, lo: int, hi: int) -> tuple:
        """Dict-id interval filter. On a sorted column (SortedIndexReader
        parity: the forward index IS the index) the id interval maps to one
        contiguous doc range via two binary searches — the kernel then tests
        iota bounds and the column never needs to be read on device."""
        if ci.stats.is_sorted:
            start = int(np.searchsorted(ci.forward, lo, side="left"))
            end = int(np.searchsorted(ci.forward, hi, side="right"))
            return ("doc_range", self.op_idx(np.int32(start)), self.op_idx(np.int32(end)))
        self.use_col(col)
        return ("range_ids", col, self.op_idx(np.int32(lo)), self.op_idx(np.int32(hi)))

    def _raw_compare(self, col: str, ci, op: CompareOp, value) -> tuple:
        if ci.stats.is_sorted and op != CompareOp.NEQ:
            n = len(ci.forward)
            left = int(np.searchsorted(ci.forward, value, side="left"))
            right = int(np.searchsorted(ci.forward, value, side="right"))
            start, end = {
                CompareOp.EQ: (left, right),
                CompareOp.LT: (0, left),
                CompareOp.LTE: (0, right),
                CompareOp.GT: (right, n),
                CompareOp.GTE: (left, n),
            }[op]
            if start >= end:
                return ("const", False)
            return ("doc_range", self.op_idx(np.int32(start)), self.op_idx(np.int32(end)))
        self.use_col(col)
        # integer columns compare natively (f64 is emulated on TPU): rewrite
        # fractional literals into equivalent integer bounds first
        fwd_dtype = ci.forward.dtype
        if np.issubdtype(fwd_dtype, np.integer) and isinstance(value, (int, float)) and not isinstance(value, bool):
            iop, ival = _int_compare(op, float(value))
            if iop is None:
                return ("const", ival)
            info = np.iinfo(fwd_dtype)
            if info.min <= ival <= info.max:
                return ("cmp_raw", iop.name, col, self.op_idx(np.asarray(ival, dtype=fwd_dtype)))
            # literal out of the column dtype's range: statically decidable
            if iop in (CompareOp.LT, CompareOp.LTE):
                return ("const", ival > info.max)
            if iop in (CompareOp.GT, CompareOp.GTE):
                return ("const", ival < info.min)
            return ("const", op == CompareOp.NEQ)
        v = self.op_idx(np.asarray(value, dtype=np.float64))
        return ("cmp_raw", op.name, col, v)

    def _range(self, expr: Expr, low: Expr, high: Expr, lo_incl: bool, hi_incl: bool) -> tuple:
        if (
            isinstance(expr, ast.Identifier)
            and isinstance(low, ast.Literal)
            and isinstance(high, ast.Literal)
        ):
            ci0 = self.seg.columns.get(expr.name)
            if ci0 is not None and not ci0.is_dict_encoded and np.issubdtype(ci0.forward.dtype, np.integer):
                # raw integer column: two native integer compares. For MV the
                # whole conjunction wraps as ONE flat predicate — a doc
                # matches when a SINGLE value lies in the range
                spec = (
                    "and",
                    (
                        self._raw_compare(expr.name, ci0, CompareOp.GTE if lo_incl else CompareOp.GT, low.value),
                        self._raw_compare(expr.name, ci0, CompareOp.LTE if hi_incl else CompareOp.LT, high.value),
                    ),
                )
                return self._mv_wrap(expr.name, spec) if ci0.is_mv else spec
        return self._range_generic(expr, low, high, lo_incl, hi_incl)

    def _range_generic(self, expr: Expr, low: Expr, high: Expr, lo_incl: bool, hi_incl: bool) -> tuple:
        if not isinstance(low, ast.Literal) or not isinstance(high, ast.Literal):
            raise PlanError("BETWEEN bounds must be literals")
        if isinstance(expr, ast.Identifier):
            ci = self.seg.columns.get(expr.name)
            if ci is None:
                raise PlanError(f"unknown column {expr.name!r}")
            if ci.is_dict_encoded:
                lo, hi = ci.dictionary.id_range_for(low.value, high.value, lo_incl, hi_incl)
                if lo > hi:
                    return ("const", False)
                if lo == 0 and hi == ci.dictionary.cardinality - 1 and not ci.is_mv:
                    return ("const", True)
                spec = self._id_range_filter(expr.name, ci, lo, hi)
                return self._mv_wrap(expr.name, spec) if ci.is_mv else spec
        vs = self.value_spec(expr)
        return (
            "and",
            (
                ("cmp_lit", "GTE" if lo_incl else "GT", vs, self.op_idx(np.float64(low.value))),
                ("cmp_lit", "LTE" if hi_incl else "LT", vs, self.op_idx(np.float64(high.value))),
            ),
        )

    def _in(self, f: ast.In) -> tuple:
        values = []
        for v in f.values:
            if not isinstance(v, ast.Literal):
                raise PlanError("IN values must be literals")
            values.append(v.value)
        if isinstance(f.expr, ast.Identifier):
            ci = self.seg.columns.get(f.expr.name)
            if ci is None:
                raise PlanError(f"unknown column {f.expr.name!r}")
            if ci.is_dict_encoded:
                self.use_col(f.expr.name)
                ids = ci.dictionary.ids_for_values(values)
                if len(ids) == 0:
                    spec = ("const", False)
                else:
                    lut = np.zeros(_pow2(max(ci.dictionary.cardinality, 1)), dtype=bool)
                    lut[ids] = True
                    spec = ("in_lut", f.expr.name, self.op_idx(lut))
                if ci.is_mv:
                    spec = self._mv_wrap(f.expr.name, spec)
                return ("not", spec) if f.negated and spec[0] != "const" else (
                    ("const", not spec[1]) if f.negated else spec
                )
        if self._is_string_fn(f.expr):
            vals = {str(v) for v in values}
            spec = self._string_fn_lut(f.expr, lambda v: v in vals)
            if f.negated:
                return ("const", not spec[1]) if spec[0] == "const" else ("not", spec)
            return spec
        # raw numeric IN: sorted-membership probe — searchsorted + one gather,
        # O(docs * log k) instead of the old O(docs * k) broadcast compare,
        # so long IN lists stay flat (VERDICT r2 weak #6)
        vs = self.value_spec(f.expr)
        int_ok = all(
            isinstance(v, (int, bool)) or (isinstance(v, float) and v == int(v)) for v in values
        )
        col_dt = None
        if vs[0] == "raw":
            ci_in = self._raw_column(vs)
            col_dt = ci_in.forward.dtype
            # match to_device's lossless int64->int32 narrowing: the operand
            # dtype must equal the DEVICE dtype or the kernel-side cast wraps
            # out-of-range literals (and can even de-sort the probe array)
            if narrows_to_int32(ci_in):
                col_dt = np.dtype(np.int32)
        if int_ok and col_dt is not None and np.issubdtype(col_dt, np.integer):
            info = np.iinfo(col_dt)
            in_range = [int(v) for v in values if info.min <= int(v) <= info.max]
            if not in_range:
                return ("const", bool(f.negated))
            vals = np.unique(np.asarray(in_range, dtype=col_dt))
        else:
            vals = np.unique(np.asarray([np.float64(v) for v in values], dtype=np.float64))
        pad = _pow2(len(vals))
        if len(vals) < pad:
            vals = np.concatenate([vals, np.full(pad - len(vals), vals[-1])])
        spec = ("in_sorted", vs, self.op_idx(vals))
        return ("not", spec) if f.negated else spec

    def _regex_lut(self, expr: Expr, pattern: str, full: bool) -> tuple:
        if self._is_string_fn(expr):
            rx = re.compile(pattern)
            match = rx.fullmatch if full else rx.search
            return self._string_fn_lut(expr, lambda v: bool(match(v)))
        if not isinstance(expr, ast.Identifier):
            raise PlanError("LIKE/REGEXP_LIKE requires a column")
        ci = self.seg.columns.get(expr.name)
        if ci is None:
            raise PlanError(f"unknown column {expr.name!r}")
        if not ci.is_dict_encoded:
            raise PlanError("LIKE/REGEXP_LIKE requires a dictionary-encoded column")
        self.use_col(expr.name)
        fst = self.seg.extras.get("fst", {}).get(expr.name)
        if fst is not None:
            # FST index: prefix patterns are two binary searches; general
            # regexes memoize their dict-id LUT (nativefst parity)
            ids = fst.matching_ids(pattern, full)
            lut = np.zeros(_pow2(max(ci.dictionary.cardinality, 1)), dtype=bool)
            lut[: len(ids)] = ids
        else:
            rx = re.compile(pattern)
            match = rx.fullmatch if full else rx.search
            lut = np.zeros(_pow2(max(ci.dictionary.cardinality, 1)), dtype=bool)
            for i, v in enumerate(ci.dictionary.values):
                if match(str(v)):
                    lut[i] = True
        if not lut.any():
            return ("const", False)
        return ("in_lut", expr.name, self.op_idx(lut))

    def multi_ob_spec(self, order_by) -> tuple:
        """Composite rank key for multi-key ORDER BY (the sorting twin of
        DictionaryBasedGroupKeyGenerator's cardinality product,
        DictionaryBasedGroupKeyGenerator.java:119-130): ascending composite
        order == the requested multi-key order. Returns (kspec, decomp)."""
        entries = []  # (col, card, desc, kind, offset)
        total = 1
        for ob in order_by:
            if not isinstance(ob.expr, ast.Identifier):
                raise DeviceFallback("expression ORDER BY keys run host-side")
            ci = self.seg.columns.get(ob.expr.name)
            if ci is None:
                raise PlanError(f"unknown column {ob.expr.name!r}")
            if ci.is_mv:
                raise DeviceFallback("MV ORDER BY keys run host-side")
            if ci.is_dict_encoded:
                entries.append((ob.expr.name, max(ci.cardinality, 1), ob.desc, "ids", 0))
            elif np.issubdtype(ci.forward.dtype, np.integer):
                lo_v, hi_v = int(ci.stats.min_value), int(ci.stats.max_value)
                card = hi_v - lo_v + 1
                i32 = np.iinfo(np.int32)
                # the offset/extreme literals ride as int32 operands: values
                # outside int32 (a narrow range at a huge base still has a
                # huge offset) must fall back, not overflow
                if card <= 0 or card > (1 << 31) or lo_v < i32.min or hi_v > i32.max:
                    raise DeviceFallback("wide-range int ORDER BY key runs host-side")
                entries.append((ob.expr.name, card, ob.desc, "rawoff", lo_v))
            else:
                raise DeviceFallback("float/string-raw multi-key ORDER BY runs host-side")
            total *= entries[-1][1]
            if total > (1 << 31) - 1:
                raise DeviceFallback("ORDER BY key-rank product exceeds int32; host-side")

        # composite = sum(rank_i * stride_i), most significant key first
        strides = [1] * len(entries)
        for i in range(len(entries) - 2, -1, -1):
            strides[i] = strides[i + 1] * entries[i + 1][1]
        kspec = None
        for (col, card, desc, kind, off), stride in zip(entries, strides):
            self.use_col(col)
            base: tuple = ("ids" if kind == "ids" else "raw", col)
            if kind == "rawoff" and off != 0:
                base = ("bin", "-", base, ("lit", self.op_idx(np.int32(off))))
            if desc:
                base = ("bin", "-", ("lit", self.op_idx(np.int32(card - 1))), base)
            term = (
                base
                if stride == 1
                else ("bin", "*", base, ("lit", self.op_idx(np.int32(stride))))
            )
            kspec = term if kspec is None else ("bin", "+", kspec, term)
        return kspec, entries

    # -- aggregations --------------------------------------------------------

    def agg_spec(self, info: AggregationInfo, grouped: bool) -> tuple:
        if info.filter is not None:
            # FILTER (WHERE ...): the per-agg mask ANDs into the query mask
            # (FilteredAggregationFunction parity) — the wrapper carries the
            # extra filter spec around the inner aggregation spec
            import dataclasses

            inner = dataclasses.replace(info, filter=None)
            return ("masked", self.where_spec(info.filter), self.agg_spec(inner, grouped))
        if info.func == "count":
            return ("count",)
        if info.func in ("distinctcount", "distinctcountbitmap"):
            if isinstance(info.arg, ast.Identifier):
                ci = self.seg.columns.get(info.arg.name)
                if ci is not None and ci.is_dict_encoded and not ci.is_mv:
                    pad = _pow2(max(ci.cardinality, 1))
                    if grouped and self._group_ng * pad > (1 << 24):
                        # per-group presence matrix over budget: host sets
                        raise DeviceFallback(
                            "grouped DISTINCTCOUNT presence matrix exceeds device budget"
                        )
                    self.use_col(info.arg.name)
                    return ("distinct_ids", info.arg.name, pad)
            raise DeviceFallback("DISTINCTCOUNT on raw/expression args runs host-side")
        if info.func == "distinctcounthll":
            if grouped:
                from pinot_tpu.query.sketches import HLL_LOG2M

                if self._group_ng * (1 << HLL_LOG2M) > (1 << 22):
                    raise DeviceFallback("grouped HLL register matrix exceeds device budget")
            return self._hll_spec(info)
        if info.func == "percentileest":
            if grouped:
                from pinot_tpu.query.sketches import EST_BINS

                if self._group_ng * EST_BINS > (1 << 22):
                    raise DeviceFallback("grouped percentileest histogram matrix exceeds device budget")
            return self._hist_spec(info)
        if info.func in ("percentile", "percentiletdigest", "mode"):
            raise DeviceFallback(f"{info.func} runs host-side (full-values / counter intermediate)", reason="aggregation_full_values")
        if info.func in ("sum", "min", "max", "avg", "minmaxrange"):
            if info.arg is None:
                raise PlanError(f"{info.func} requires an argument")
            return (info.func, self.value_spec(info.arg))
        if info.func in ("countmv", "summv", "minmv", "maxmv", "avgmv", "distinctcountmv"):
            return self._mv_agg_spec(info, grouped)
        if info.func in ("funnelcount", "funnelcompletecount"):
            # un-ordered bitmap-strategy funnel (FunnelCountAggregationFunction
            # set/bitmap strategy): per-step presence vectors over the
            # correlation column's dict-id space — K scatter-or passes fused
            # into the segment program; the host converts rows to value sets
            if grouped:
                raise DeviceFallback("funnel aggregations inside GROUP BY run host-side")
            if not isinstance(info.arg, ast.Identifier):
                raise DeviceFallback("FUNNELCOUNT correlation expression runs host-side")
            ci = self.seg.columns.get(info.arg.name)
            if ci is None or not ci.is_dict_encoded or ci.is_mv:
                raise DeviceFallback("FUNNELCOUNT needs a dict-encoded SV correlation column")
            steps = info.extra[-1]
            stepspecs = tuple(self.filter_spec(s) for s in steps)
            col = self.use_col(info.arg.name)
            return ("funnel_steps", col, _pow2(max(ci.cardinality, 1)), stepspecs)
        raise DeviceFallback(f"aggregation {info.func} has no device lowering yet", reason="aggregation_function")

    def _mv_agg_spec(self, info: AggregationInfo, grouped: bool) -> tuple:
        """MV aggregations over the flattened layout (reference:
        core/query/aggregation/function/*MVAggregationFunction.java). The doc
        mask gathers to value positions; the reduction itself is the same
        dense 1-D kernel the SV twin uses."""
        if not isinstance(info.arg, ast.Identifier):
            raise PlanError(f"{info.func} requires an MV column argument")
        ci = self.seg.columns.get(info.arg.name)
        if ci is None:
            raise PlanError(f"unknown column {info.arg.name!r}")
        if not ci.is_mv:
            raise PlanError(f"{info.func} requires a multi-value column, {info.arg.name!r} is single-value")
        col = self.use_col(info.arg.name)
        nv = self.op_idx(np.int32(len(ci.forward)))
        if info.func == "countmv":
            return ("mv_count", col, nv)
        if info.func == "distinctcountmv":
            if grouped:
                raise DeviceFallback("DISTINCTCOUNTMV inside GROUP BY runs host-side for now")
            if not ci.is_dict_encoded:
                raise DeviceFallback("DISTINCTCOUNTMV on raw MV columns runs host-side")
            return ("mv_distinct_ids", col, _pow2(max(ci.cardinality, 1)), nv)
        if ci.data_type in (DataType.STRING, DataType.BYTES, DataType.JSON):
            raise PlanError(f"{info.func} requires a numeric MV column")
        if ci.is_dict_encoded:
            dv = np.asarray(ci.dictionary.values)
            pad = _pow2(max(len(dv), 1))
            if len(dv) == 0:
                dv = np.zeros(1, dtype=ci.data_type.np_dtype)
            if len(dv) < pad:
                dv = np.concatenate([dv, np.full(pad - len(dv), dv[-1], dtype=dv.dtype)])
            vspec = ("dictval", col, self.op_idx(dv))
        else:
            vspec = ("raw", col)
        return (f"mv_{info.func[:-2]}", vspec, col, nv)

    def _hll_spec(self, info: AggregationInfo) -> tuple:
        from pinot_tpu.query.sketches import HLL_LOG2M

        if isinstance(info.arg, ast.Identifier):
            ci = self.seg.columns.get(info.arg.name)
            if ci is None:
                raise PlanError(f"unknown column {info.arg.name!r}")
            if ci.is_dict_encoded:
                # the dictionary owns a memoized padded hash table, marked as
                # a stable operand so its staged HBM copy survives across
                # queries (a high-cardinality table is MBs; re-shipping it per
                # query would dwarf the register-update kernel)
                self.use_col(info.arg.name)
                return (
                    "hll",
                    ("gather", info.arg.name, self.op_idx(ci.dictionary.hll_hash_pad())),
                    HLL_LOG2M,
                )
        # raw numeric column / numeric expression: device-side bit-mix hashing
        if info.arg is None:
            raise PlanError("distinctcounthll requires an argument")
        return ("hll", ("mix", self.value_spec(info.arg)), HLL_LOG2M)

    def _hist_spec(self, info: AggregationInfo) -> tuple:
        from pinot_tpu.query.sketches import EST_BINS

        bounds = self.ctx.hints.get("est_bounds", {}).get(info.name)
        if bounds is None:
            raise DeviceFallback("percentileest without global bounds runs host-side")
        lo, hi = bounds
        if not (hi > lo):
            raise DeviceFallback("degenerate percentileest bounds run host-side")
        inv_width = EST_BINS / (hi - lo)
        return (
            "hist",
            self.value_spec(info.arg),
            self.op_idx(np.float64(lo)),
            self.op_idx(np.float64(inv_width)),
            EST_BINS,
        )

    # -- group-by ------------------------------------------------------------

    # cap on the (base MV flat values x other MV max-len) pair space of a
    # two-MV-key device group-by
    MAX_MV2_PAIRS = 1 << 23

    def expr_key(self, g: Expr) -> tuple[tuple, "KeyBuckets"]:
        """An expression GROUP BY key over one single-value dictionary-coded
        column (DATETRUNC('hour', ts), DATETIMECONVERT, ts / 3600000): the
        expression is evaluated over the column's dictionary, the distinct
        results in ascending order are the key's buckets, and the device
        gathers each row's bucket through a code -> bucket operand, padded to
        a power of two so that segments whose dictionaries differ share a
        program. Returns the key's entry in the group spec and what its
        bucket indices decode through. Any other expression falls back,
        under a reason of its own."""
        from pinot_tpu.common.trace import span
        from pinot_tpu.query.context import _collect_identifiers
        from pinot_tpu.query.transforms import apply_scalar
        from pinot_tpu.segment.dictionary import Dictionary

        if self._is_lookup(g):
            return self.lookup_key(g)
        names: set[str] = set()
        _collect_identifiers(g, names)
        if len(names) != 1:
            raise DeviceFallback(
                f"GROUP BY expression over {len(names)} columns runs host-side",
                reason="group_key_several_columns" if names else "group_key_no_column",
            )
        (col,) = names
        if col in VIRTUAL_COLUMNS:
            raise DeviceFallback(f"GROUP BY virtual column {col} runs host-side", reason="group_key_virtual_column")
        ci = self.seg.columns.get(col)
        if ci is None:
            raise PlanError(f"unknown column {col!r}")
        if not ci.is_dict_encoded or ci.is_mv:
            raise DeviceFallback(
                f"GROUP BY expression over {'multi-value' if ci.is_mv else 'raw'} column {col} runs host-side",
                reason="group_key_mv_column" if ci.is_mv else "group_key_raw_column",
            )
        with span("server.plan.group_key", column=col) as sp:
            values = np.asarray(ci.dictionary.values)

            def over(e: Expr) -> np.ndarray:
                if isinstance(e, ast.Identifier):
                    return values
                if isinstance(e, ast.Literal):
                    return np.full(len(values), e.value)
                out = apply_scalar(e, over)
                if out is NotImplemented:
                    raise DeviceFallback(f"GROUP BY expression {g} runs host-side", reason="group_key_expression_form")
                return out

            results = np.asarray(over(g))
            if results.dtype.kind not in "iuf":
                raise DeviceFallback(f"GROUP BY expression {g} is not numeric", reason="group_key_not_numeric")
            buckets, index = np.unique(results, return_inverse=True)
            remap = np.zeros(_pow2(max(len(index), 1)), dtype=np.int32)
            remap[: len(index)] = index
            sp.set_attr("buckets", len(buckets))
        self.use_col(col)
        dt = DataType.DOUBLE if buckets.dtype.kind == "f" else DataType.LONG
        return ("remap", col, self.op_idx(remap)), KeyBuckets(Dictionary(dt, buckets.astype(dt.np_dtype)))

    def group_spec(self) -> tuple:
        """The GROUP BY keys as the program's group spec, by the shape of the
        plan alone: ("groups", ...) a dense space of the keys' product, up to
        MAX_DENSE_GROUPS; ("groups_mv", ...) / ("groups_mv2", ...) with one or
        two multi-value keys; ("groups_compact", keys, slots, dense strides,
        widths) where the product of single-value keys reaches
        COMPACT_MIN_GROUPS — each key renumbered on the device by the values
        the filter leaves, COMPACT_SLOTS slots, whatever the product; and
        ("groups_sparse", ...), the sort-compaction path, past
        MAX_DENSE_GROUPS where a key too wide to renumber keeps the compact
        form out, and as the fallback of a compact segment that overflowed."""
        cols = []
        cards = []
        mv_cols: list[str] = []
        self.group_cols = []
        for g in self.ctx.group_by:
            if not isinstance(g, ast.Identifier):
                key, buckets = self.expr_key(g)
                cols.append(key)
                cards.append(buckets.cardinality)
                self.group_cols.append((str(g), buckets))
                continue
            if g.name in VIRTUAL_COLUMNS:
                raise DeviceFallback(f"GROUP BY virtual column {g.name} runs host-side", reason="group_key_virtual_column")
            ci = self.seg.columns.get(g.name)
            if ci is None:
                raise PlanError(f"unknown column {g.name!r}")
            if not ci.is_dict_encoded:
                raise DeviceFallback(f"GROUP BY on raw column {g.name} runs host-side for now", reason="group_key_raw_column")
            if ci.is_mv:
                mv_cols.append(g.name)
            self.use_col(g.name)
            cols.append(g.name)
            cards.append(ci.cardinality)
            self.group_cols.append((g.name, ci))
        if len(mv_cols) > 2:
            raise DeviceFallback("3+ MV GROUP BY keys run host-side (explode)")
        if len(mv_cols) == 2 and mv_cols[0] == mv_cols[1]:
            # repeated MV key: the pair kernel would only produce diagonal
            # (v, v) combinations, not the full cartesian square
            raise DeviceFallback("repeated MV GROUP BY key runs host-side (explode)")
        num_groups = 1
        for c in cards:
            num_groups *= max(c, 1)
        if num_groups > MAX_DENSE_GROUPS:
            if mv_cols:
                raise DeviceFallback("high-cardinality MV GROUP BY runs host-side")
            if num_groups >= (1 << 62):
                raise DeviceFallback("group cardinality product overflows int64 gids")
        # what the keys too wide to renumber contribute to the compact space whatever the filter
        carried = math.prod(c for c in cards if c > COMPACT_MAX_KEY_CARD)
        if self.compact and not mv_cols and num_groups >= COMPACT_MIN_GROUPS and carried <= COMPACT_SLOTS:
            # a large product of single-value keys: the program renumbers
            # each key by the values the filter leaves and contracts over
            # COMPACT_SLOTS slots (kernels._compact_groups), the slot table
            # rides back as the sort-compaction path's does. The rows decide
            # whether the slots suffice; where they do not the engine
            # launches the segment again under the spec below, which is what
            # `compact=False` plans. Widths are rounded as with_real_groups
            # rounds, so near-alike dictionaries share a compile.
            widths = tuple(("rank", _eighths(max(c, 1))) if c <= COMPACT_MAX_KEY_CARD else ("whole", c) for c in cards)
            self._group_ng = COMPACT_SLOTS
            return ("groups_compact", tuple(cols), COMPACT_SLOTS, self.op_idx(group_strides(cards, np.int64)), widths)
        if num_groups > MAX_DENSE_GROUPS:
            # high-cardinality product: sort-compaction path — dense 64-bit
            # gids are sorted on device, run-length compacted to slots, and
            # the aggregation runs over the compact slot space. The slot
            # budget U bounds PRESENT groups (<= n_docs), not the product.
            # Reference: NoDictionaryMultiColumnGroupKeyGenerator.java:56
            # (hash-table group ids) — redesigned as sort-compaction, which
            # is what maps onto the TPU (lax.sort rides the VPU; a serial
            # hash table would not vectorize).
            strides64 = group_strides(cards, np.int64)
            u = min(_pow2(max(self.seg.n_docs, 256)), MAX_DENSE_GROUPS)
            self._group_ng = u
            return ("groups_sparse", tuple(cols), u, self.op_idx(strides64))
        strides = group_strides(cards, np.int32)
        # round ng to 256 steps: the pallas kernel's lo width steps by 8 per
        # 1024 groups (groupby_pallas.grid_for), so a finer bucket buys no
        # MXU work. A pow2 bucket would nearly double the contraction at e.g.
        # 4375 groups, while 256-step buckets still keep the kernel compile
        # cache warm across near-alike queries (the Pinot plan-cache
        # normalization tradeoff)
        ng = ((max(num_groups, 1) + 255) // 256) * 256
        self._group_ng = ng
        self._group_real = max(num_groups, 1)
        if len(mv_cols) == 2:
            return self._group_spec_mv2(cols, ng, strides, mv_cols)
        if mv_cols:
            # one MV key lowers: group ids live in VALUE space (each doc
            # contributes once per value — Pinot MV group-by semantics)
            nv = self.op_idx(np.int32(len(self.seg.columns[mv_cols[0]].forward)))
            return ("groups_mv", tuple(cols), ng, self.op_idx(strides), mv_cols[0], nv)
        return ("groups", tuple(cols), ng, self.op_idx(strides))

    def with_real_groups(self, gspec: tuple, aggs: tuple) -> tuple:
        """A dense single-value group spec with the real group count appended
        where the program can use it: some SUM / AVG / MIN / MAX / MINMAXRANGE
        is over a value that is not int32 on the device, and the count is
        small enough for the dense form of that reduction
        (kernels._grouped_reduce). The count is rounded up to eighths of its
        next power of two, at least 8, so near-alike tables share a compile
        (6 -> 8, 175 -> 192, 4000 -> 4096). Every other group-by — int32
        metrics and COUNT only, as all of SSB; more groups; MV keys; the
        sort-compaction path — keeps the spec, and with it the program name
        and compile-cache key, it has always had."""
        if gspec[0] != "groups":
            return gspec
        real = min(_eighths(self._group_real), gspec[2])
        if real > DENSE_REDUCE_MAX_GROUPS:
            return gspec

        def inner(a):
            while a[0] in ("masked", "masked_nan_empty"):
                a = a[2]
            return a

        wide = any(
            a[0] in ("sum", "avg", "min", "max", "minmaxrange") and not self._is_i32(a[1])
            for a in map(inner, aggs)
        )
        return gspec + (real,) if wide else gspec

    def _is_i32(self, vspec: tuple) -> bool:
        """True only where a value spec is certainly int32 on the device
        (kernels._value): an INT column, a LONG one that to_device narrows, an
        int32 dictionary's values, and +, -, *, % of such."""
        kind = vspec[0]
        if kind == "raw":
            ci = self._raw_column(vspec)
            return ci.forward.dtype == np.int32 or narrows_to_int32(ci)
        if kind == "dictval":
            return self.operands[vspec[2]].dtype == np.int32
        if kind == "bin" and vspec[1] in "+-*%":
            return self._is_i32(vspec[2]) and self._is_i32(vspec[3])
        return False

    def _group_spec_mv2(self, cols, ng, strides, mv_cols) -> tuple:
        """Two MV keys: per-doc cartesian pairs in a dense (base flat values x
        other max-len) pair space. The base's flat layout supplies one axis;
        the other column contributes Lb padded positions per pair row, masked
        by its per-doc length (DictionaryBasedGroupKeyGenerator MV cartesian
        semantics, pinot-core/.../groupby/DictionaryBasedGroupKeyGenerator.java)."""
        from pinot_tpu.segment.segment import padded_len

        def _maxlen(name: str) -> int:
            lens = self.seg.columns[name].lens
            return int(lens.max()) if len(lens) else 0

        a, b = mv_cols
        # pick the base that minimizes the pair space
        if padded_len(len(self.seg.columns[b].forward)) * _maxlen(a) < padded_len(
            len(self.seg.columns[a].forward)
        ) * _maxlen(b):
            a, b = b, a
        lb = _maxlen(b)
        if lb == 0:
            # other column has no values anywhere: no doc joins any group
            raise DeviceFallback("MV GROUP BY key with no values runs host-side")
        ci_b = self.seg.columns[b]
        pairs = padded_len(len(self.seg.columns[a].forward)) * lb
        if pairs > self.MAX_MV2_PAIRS:
            raise DeviceFallback(
                f"two-MV-key pair space {pairs} exceeds device budget {self.MAX_MV2_PAIRS}",
                reason="mv_pair_space",
            )
        pad = padded_len(self.seg.n_docs)
        off = ci_b.offsets()[: self.seg.n_docs].astype(np.int32)
        lens = ci_b.lens.astype(np.int32)
        # pad+1 entries: flat-padding docids point one past the padded doc
        # range; zero lengths there make every such pair invalid
        off_p = np.zeros(pad + 1, dtype=np.int32)
        len_p = np.zeros(pad + 1, dtype=np.int32)
        off_p[: self.seg.n_docs] = off
        len_p[: self.seg.n_docs] = lens
        nv_a = self.op_idx(np.int32(len(self.seg.columns[a].forward)))
        return (
            "groups_mv2",
            tuple(cols),
            ng,
            self.op_idx(strides),
            a,
            nv_a,
            b,
            self.op_idx(off_p),
            self.op_idx(len_p),
            lb,
        )


_FLIP = {
    CompareOp.EQ: CompareOp.EQ,
    CompareOp.NEQ: CompareOp.NEQ,
    CompareOp.LT: CompareOp.GT,
    CompareOp.LTE: CompareOp.GTE,
    CompareOp.GT: CompareOp.LT,
    CompareOp.GTE: CompareOp.LTE,
}


def _int_compare(op: CompareOp, x: float):
    """Rewrite `int_col <op> x` into an equivalent integer-literal compare.
    Returns (op, int literal), or (None, bool) when statically decided
    (fractional EQ/NEQ)."""
    import math

    if x == int(x):
        return op, int(x)
    if op == CompareOp.EQ:
        return None, False
    if op == CompareOp.NEQ:
        return None, True
    if op == CompareOp.GT:  # v > 5.5  <=>  v > 5
        return CompareOp.GT, math.floor(x)
    if op == CompareOp.GTE:  # v >= 5.5 <=>  v >= 6
        return CompareOp.GTE, math.ceil(x)
    if op == CompareOp.LT:  # v < 5.5  <=>  v < 6
        return CompareOp.LT, math.ceil(x)
    return CompareOp.LTE, math.floor(x)  # v <= 5.5 <=> v <= 5


def _const_compare(op: CompareOp, a, b) -> bool:
    return {
        CompareOp.EQ: a == b,
        CompareOp.NEQ: a != b,
        CompareOp.LT: a < b,
        CompareOp.LTE: a <= b,
        CompareOp.GT: a > b,
        CompareOp.GTE: a >= b,
    }[op]


def _like_to_regex(pattern: str) -> str:
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def plan_filter_mask(seg: ImmutableSegment, filt, valid_mask=None, kleene: bool = False) -> SegmentPlan:
    """Lower ONLY a filter expression into a device mask program. This is the
    multistage leaf Scan's fused-filter path (LeafStageTransferableBlock-
    Operator parity, pinot-query-runtime/.../operator/
    LeafStageTransferableBlockOperator.java:87 — the leaf stage bridges into
    the single-stage engine): the v2 leaf evaluates its pushed-down filter
    with the same fused XLA mask kernel the v1 engine uses, instead of host
    numpy. Raises DeviceFallback for host-only predicates."""
    from types import SimpleNamespace

    shim = SimpleNamespace(
        table=seg.schema.name,
        hints={},
        group_by=[],
        options={"enablenullhandling": "true"} if kleene else {},
    )
    lo = _Lowering(seg, shim)
    fspec = lo.where_spec(filt) if kleene else lo.filter_spec(filt)
    if valid_mask is not None:
        vm = lo.docmask_spec(np.asarray(valid_mask, dtype=bool))
        fspec = ("and", (vm, fspec))
    return SegmentPlan(
        spec=("mask", fspec),
        operands=tuple(lo.operands),
        columns=tuple(lo.columns),
        value_columns=tuple(lo.value_columns),
        group_cols=[],
        aggs=[],
    )


def plan_segment(seg: ImmutableSegment, ctx: QueryContext, valid_mask=None, compact: bool = True) -> SegmentPlan:
    """Lower a query against one segment. Raises DeviceFallback when the host
    executor must take over. `valid_mask` lets the caller pass an
    already-materialized upsert validity snapshot (avoids computing the
    bitmap twice when lowering later falls back to the host path).
    `compact=False` plans a large group space without "groups_compact"
    (`group_spec`): the engine's second launch of a segment whose groups
    passed the compact slots, and the sharded executor, which has no second
    launch."""
    lo = _Lowering(seg, ctx, compact)
    from pinot_tpu.query.context import null_handling_enabled as _nhe

    if _nhe(ctx.options):
        from pinot_tpu.query.host_exec import expr_null_mask as _enm

        if any(_enm(seg, g) is not None for g in ctx.group_by):
            # null keys must form their own group (reference group-by null
            # semantics); the host path substitutes None into the key column
            raise DeviceFallback("null-handling group-by key runs host-side")
    # three-valued WHERE stays on device: where_spec lowers nullable-column
    # filters to a Kleene (true, unknown) pair tree (round-3 host cliff gone)
    fspec = lo.where_spec(ctx.filter)

    if valid_mask is None:
        valid = seg.extras.get("valid_docs") if seg.extras else None
        if valid is not None:
            valid_mask = valid(seg.n_docs)
    if valid_mask is not None:
        # upsert/dedup visibility: only latest-per-PK docs count. The CURRENT
        # validDocIds bitmap rides as a mask OPERAND (docmask), not a baked-in
        # constant: operands are runtime inputs, so concurrent ingestion
        # flipping validity never recompiles the kernel (the spec tuple —
        # the compile-cache key — is unchanged). Parity:
        # ConcurrentMapPartitionUpsertMetadataManager validDocIds snapshots
        # consulted per query by the filter operators.
        vm = lo.docmask_spec(np.asarray(valid_mask, dtype=bool))
        fspec = ("and", (vm, fspec))

    if ctx.query_type in (QueryType.AGGREGATION, QueryType.GROUP_BY):
        from pinot_tpu.query.context import null_handling_enabled

        grouped = ctx.query_type == QueryType.GROUP_BY
        gspec = lo.group_spec() if grouped else None
        aggs = tuple(lo.agg_spec(a, grouped) for a in ctx.aggregations)
        if null_handling_enabled(ctx.options):
            aggs = tuple(lo.null_wrap(a, s) for a, s in zip(ctx.aggregations, aggs))
        if gspec is not None and gspec[0] in ("groups_mv", "groups_mv2"):
            # MV group ids are value-space; *MV aggregations are themselves
            # value-space over a (possibly different) MV column — the
            # combined gather semantics run host-side (explode)
            def _has_mv(a):
                return a[0].startswith("mv_") or (a[0] in ("masked", "masked_nan_empty") and _has_mv(a[2]))

            if any(_has_mv(a) for a in aggs):
                raise DeviceFallback("MV aggregations under an MV GROUP BY run host-side")
        if gspec is not None:
            gspec = lo.with_real_groups(gspec, aggs)
        spec = ("agg", fspec, gspec, aggs)
        plan = SegmentPlan(
            spec=spec,
            operands=tuple(lo.operands),
            columns=tuple(lo.columns),
            value_columns=tuple(lo.value_columns),
            group_cols=lo.group_cols if gspec else [],
            aggs=list(ctx.aggregations),
        )
        return plan

    if ctx.query_type == QueryType.DISTINCT:
        saved = ctx.group_by
        ctx.group_by = [it.expr for it in ctx.select_items]
        try:
            gspec = lo.group_spec()
        finally:
            ctx.group_by = saved
        spec = ("agg", fspec, gspec, ())
        return SegmentPlan(
            spec=spec,
            operands=tuple(lo.operands),
            columns=tuple(lo.columns),
            value_columns=tuple(lo.value_columns),
            group_cols=lo.group_cols,
            aggs=[],
        )

    # SELECTION / SELECTION_ORDER_BY
    from pinot_tpu.query.context import null_handling_enabled

    if null_handling_enabled(ctx.options):
        from pinot_tpu.query.host_exec import expr_null_mask

        exprs = [it.expr for it in ctx.select_items] + [ob.expr for ob in ctx.order_by]
        if any(expr_null_mask(seg, e) is not None for e in exprs):
            # rows must emit None (null-propagating through expressions) and
            # ORDER BY must sort nulls last: the host path substitutes via
            # the null vector
            raise DeviceFallback("null-handling selection runs host-side")
    proj = []
    decode = []
    for item in ctx.select_items:
        e = item.expr
        if isinstance(e, ast.Star):
            raise DeviceFallback("SELECT * expansion handled by engine")
        if isinstance(e, ast.Identifier):
            if e.name in VIRTUAL_COLUMNS:
                # $docId / $segmentName / $hostName (VirtualColumnProvider
                # parity): docids come off-device, constants decode host-side
                proj.append(("docid",))
                decode.append(("virt", e.name))
                continue
            ci = seg.columns.get(e.name)
            if ci is None:
                raise PlanError(f"unknown column {e.name!r}")
            if ci.is_mv:
                raise DeviceFallback("MV column selection runs host-side (ragged rows)")
            lo.use_col(e.name)
            if ci.is_dict_encoded:
                proj.append(("ids", e.name))
                decode.append(("dict", e.name))
            else:
                proj.append(("raw", e.name))
                decode.append(("rawcol", e.name))
        else:
            proj.append(lo.value_spec(e))
            decode.append(("expr", None))
    k = ctx.limit + ctx.offset
    ob_decomp = None
    if ctx.query_type == QueryType.SELECTION_ORDER_BY:
        if len(ctx.order_by) != 1:
            # multi-key ORDER BY: composite rank key on device — each key
            # maps to its rank (dict id IS rank order; bounded ints shift by
            # min), ranks combine by cardinality-product strides exactly like
            # dense group ids, and ONE top_k sorts all keys at once.
            # Per-key DESC flips the rank (card-1 - rank).
            kspec, ob_decomp = lo.multi_ob_spec(ctx.order_by)
            spec = ("select_ob", fspec, tuple(proj), kspec, False, k)
        else:
            ob = ctx.order_by[0]
            key = ob.expr
            if isinstance(key, ast.Identifier) and key.name in seg.columns and seg.columns[key.name].is_dict_encoded:
                lo.use_col(key.name)
                kspec = ("ids", key.name)  # dict id order == value order
            else:
                kspec = lo.value_spec(key)
            spec = ("select_ob", fspec, tuple(proj), kspec, ob.desc, k)
    else:
        spec = ("select", fspec, tuple(proj), k)
    return SegmentPlan(
        spec=spec,
        operands=tuple(lo.operands),
        columns=tuple(lo.columns),
        value_columns=tuple(lo.value_columns),
        select_decode=decode,
        aggs=[],
        ob_decomp=ob_decomp,
    )
