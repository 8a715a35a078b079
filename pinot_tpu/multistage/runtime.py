"""Multistage (v2) runtime: mailboxes, operators, OpChain workers.

Reference parity:
- MailboxService / GrpcSendingMailbox / InMemorySendingMailbox
  (pinot-query-runtime/.../mailbox/MailboxService.java:40) -> in-process
  MailboxService with per-(receiver stage, worker, sender stage) queues.
- BlockExchange strategies (runtime/operator/exchange/BlockExchange.java:50-59)
  -> singleton / hash / broadcast / random senders.
- OpChainSchedulerService (runtime/executor/OpChainSchedulerService.java:37)
  -> one thread per (stage, worker); blocks stream through queues, so stages
  pipeline naturally.
- Operators (runtime/operator/: HashJoinOperator, AggregateOperator,
  SortOperator, WindowAggregateOperator, set ops, LeafStageTransferableBlock-
  Operator) -> columnar (pandas/numpy) implementations for intermediate
  stages; LEAF work runs the fused v1 DEVICE engine: Scan filters execute
  the mask kernel (_leaf_filter_mask) and partial aggregates over a Scan run
  whole-segment fused programs (_try_leaf_device_partial). Aggregation is
  two-phase (partial below the exchange, final above — AggregateOperator
  LEAF/FINAL parity) whenever every function has a mergeable partial.

Intermediate blocks are columnar DataFrames with positional integer column
labels aligned to each logical node's `fields`.
"""

from __future__ import annotations

import queue
import threading
import time as _time
from dataclasses import dataclass, field as dfield

import numpy as np
import pandas as pd

from pinot_tpu.multistage import logical as L
from pinot_tpu.multistage.stats import (
    StageStatsCollector,
    analyze_rows,
    merge_stage_stats,
    stats_enabled,
)
from pinot_tpu.query import ast, host_exec
from pinot_tpu.query.context import canonical
from pinot_tpu.query.result import ResultTable

_EOS = ("__eos__",)


class MailboxService:
    """In-process mailbox fabric: queues keyed by
    (receiver stage, receiver worker, sender stage)."""

    def __init__(self):
        self._queues: dict[tuple, queue.Queue] = {}
        self._lock = threading.Lock()

    def _q(self, recv_stage: int, recv_worker: int, send_stage: int) -> queue.Queue:
        key = (recv_stage, recv_worker, send_stage)
        with self._lock:
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = queue.Queue()
            return q

    def send(self, send_stage: int, recv_stage: int, recv_worker: int, payload) -> None:
        if callable(payload):  # lazily-built frame (trailing EOS with stats)
            payload = payload()
        self._q(recv_stage, recv_worker, send_stage).put(payload)

    #: receive deadline; None blocks forever (in-process engine). The
    #: distributed engine sets one so a dead remote sender fails the query
    #: instead of hanging the receiving OpChain (GrpcMailbox deadline parity).
    receive_timeout: float | None = None
    #: per-query Deadline (query.context.Deadline) — when set, receives poll
    #: in short slices so cancellation/expiry interrupts a blocked OpChain
    #: within ~0.2s instead of after receive_timeout
    deadline = None

    def _get_one(self, q: queue.Queue, recv_stage: int, recv_worker: int, send_stage: int):
        deadline = self.deadline
        if deadline is None and self.receive_timeout is None:
            return q.get()
        t_start = _time.monotonic()
        where = f"stage {send_stage} -> ({recv_stage}, w{recv_worker})"
        while True:
            if deadline is not None:
                deadline.check(where)
            slice_t = 0.2
            if self.receive_timeout is not None:
                left = self.receive_timeout - (_time.monotonic() - t_start)
                if left <= 0:
                    raise RuntimeError(
                        f"mailbox receive timed out after {self.receive_timeout}s: {where}"
                    ) from None
                slice_t = min(slice_t, left)
            if deadline is not None:
                rem = deadline.remaining()
                if rem is not None:
                    slice_t = min(slice_t, max(rem, 0.01))
            try:
                return q.get(timeout=slice_t)
            except queue.Empty:
                continue

    def receive_all(
        self,
        recv_stage: int,
        recv_worker: int,
        send_stage: int,
        n_senders: int,
        stats_out: list | None = None,
    ):
        """Drain blocks from n_senders until each sent EOS. Raises on error.
        An EOS may carry the sender's accumulated operator-stats records
        (("__eos__", [records]) — MultiStageQueryStats-in-trailing-block
        parity); they are appended to `stats_out` when the receiver collects."""
        from pinot_tpu.common.trace import ServerQueryPhase, phase_timer

        q = self._q(recv_stage, recv_worker, send_stage)
        blocks: list[pd.DataFrame] = []
        eos = 0
        while eos < n_senders:
            # transport-wait attribution: time blocked on upstream senders,
            # separated from this stage's own compute in phaseTimesMs and the
            # server.phase.mailboxReceiveWaitMs timer
            with phase_timer(ServerQueryPhase.MAILBOX_RECEIVE_WAIT, role="server"):
                item = self._get_one(q, recv_stage, recv_worker, send_stage)
            if item is _EOS or (isinstance(item, tuple) and item and item[0] == "__eos__"):
                eos += 1
                if stats_out is not None and isinstance(item, tuple) and len(item) > 1 and item[1]:
                    stats_out.extend(item[1])
            elif isinstance(item, tuple) and item and item[0] == "__err__":
                # the marker carries the sender's error code (third slot) so a
                # deadline/cancel failure crossing a mailbox re-raises as its
                # distinct class instead of degrading to a generic RuntimeError
                from pinot_tpu.common.errors import QueryErrorCode
                from pinot_tpu.query.context import QueryCancelledError, QueryTimeoutError

                code = item[2] if len(item) > 2 else None
                msg = f"upstream stage {send_stage} failed: {item[1]}"
                if code == QueryErrorCode.EXECUTION_TIMEOUT:
                    raise QueryTimeoutError(msg)
                if code == QueryErrorCode.QUERY_CANCELLATION:
                    raise QueryCancelledError(msg)
                raise RuntimeError(msg)
            else:
                blocks.append(item)
        return blocks


# ---------------------------------------------------------------------------
# Expression evaluation over blocks
# ---------------------------------------------------------------------------


def _series(v, n: int) -> pd.Series:
    return pd.Series(np.full(n, v), dtype=object if isinstance(v, str) else None)


def eval_expr(expr: ast.Expr, fields: list[L.Field], df: pd.DataFrame) -> pd.Series:
    if not isinstance(expr, ast.Literal):
        c = canonical(expr)
        hits = [i for i, f in enumerate(fields) if f.canon == c]
        if len(hits) == 1:
            return df.iloc[:, hits[0]]
    if isinstance(expr, ast.Identifier):
        return df.iloc[:, L.resolve(fields, expr.name)]
    if isinstance(expr, ast.Literal):
        return _series(expr.value, len(df))
    if isinstance(expr, ast.BinaryOp):
        l = eval_expr(expr.left, fields, df)
        r = eval_expr(expr.right, fields, df)
        # object cells holding None (null-handling scans / NULL aggregates)
        # would TypeError under arithmetic: coerce to float with NaN, which
        # propagates and is emitted as None at the result boundary
        if l.dtype == object:
            l = pd.to_numeric(l, errors="coerce")
        if r.dtype == object:
            r = pd.to_numeric(r, errors="coerce")
        if expr.op == "+":
            return l + r
        if expr.op == "-":
            return l - r
        if expr.op == "*":
            return l * r
        if expr.op == "/":
            return l.astype(np.float64) / r.astype(np.float64)
        if expr.op == "%":
            return l % r
        raise L.PlanV2Error(f"unknown operator {expr.op}")
    if isinstance(expr, ast.CaseWhen):
        n = len(df)
        conds = [np.asarray(eval_filter(c, fields, df), bool) for c, _ in expr.whens]
        vals = [np.asarray(eval_expr(v, fields, df)) for _, v in expr.whens]
        if expr.else_ is not None:
            default = np.asarray(eval_expr(expr.else_, fields, df))
        else:
            is_str = any(v.dtype == object or v.dtype.kind in "US" for v in vals)
            default = np.full(n, "null" if is_str else 0, dtype=object if is_str else np.float64)
        if any(v.dtype == object or v.dtype.kind in "US" for v in vals):
            vals = [v.astype(object) for v in vals]
            default = default.astype(object)
        return pd.Series(np.select(conds, vals, default=default), index=df.index)
    if isinstance(expr, ast.FunctionCall):
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
                return eval_expr(rw, fields, df)
        if name == "cast":
            v = eval_expr(expr.args[0], fields, df)
            target = str(expr.args[1].value).upper()
            if target in ("INT", "LONG", "TIMESTAMP", "BOOLEAN"):
                return pd.Series(np.trunc(v.to_numpy(dtype=np.float64)).astype(np.int64), index=v.index)
            if target in ("FLOAT", "DOUBLE"):
                return v.astype(np.float64)
            if target == "STRING":
                return v.map(str)
            raise L.PlanV2Error(f"unsupported CAST target {target}")
        if name in DEVICE_FUNCS:
            _, fn = DEVICE_FUNCS[name]
            args = [eval_expr(a, fields, df).to_numpy() for a in expr.args]
            out = np.asarray(fn(np, *args))
            return pd.Series(out, index=df.index)
        if name in STRING_FUNCS:
            base = eval_expr(expr.args[0], fields, df).to_numpy()
            lit_args = tuple(a.value for a in expr.args[1:] if isinstance(a, ast.Literal))
            derived, _ = apply_string_func(name, base, lit_args)
            return pd.Series(derived, index=df.index)
    raise L.PlanV2Error(f"unsupported expression in multistage runtime: {expr}")


_CMPS = {
    ast.CompareOp.EQ: lambda a, b: a == b,
    ast.CompareOp.NEQ: lambda a, b: a != b,
    ast.CompareOp.LT: lambda a, b: a < b,
    ast.CompareOp.LTE: lambda a, b: a <= b,
    ast.CompareOp.GT: lambda a, b: a > b,
    ast.CompareOp.GTE: lambda a, b: a >= b,
}


def eval_filter(f: ast.FilterExpr, fields: list[L.Field], df: pd.DataFrame) -> np.ndarray:
    if isinstance(f, ast.And):
        m = eval_filter(f.children[0], fields, df)
        for c in f.children[1:]:
            m = m & eval_filter(c, fields, df)
        return m
    if isinstance(f, ast.Or):
        m = eval_filter(f.children[0], fields, df)
        for c in f.children[1:]:
            m = m | eval_filter(c, fields, df)
        return m
    if isinstance(f, ast.Not):
        return ~eval_filter(f.child, fields, df)
    if isinstance(f, ast.Compare):
        l = eval_expr(f.left, fields, df)
        r = eval_expr(f.right, fields, df)
        if l.dtype == object or r.dtype == object:
            # None cells (null-handling scans / NULL aggregates) would
            # TypeError under elementwise comparison: NULL comparison is
            # unknown -> row filtered. Restricted to object dtype so
            # stored-NaN DOUBLEs keep IEEE comparison semantics when null
            # handling is off (review r4).
            na = (pd.isna(l) | pd.isna(r)).to_numpy()
            if na.any():
                out = np.zeros(len(df), dtype=bool)
                keep = ~na
                with np.errstate(invalid="ignore"):
                    out[keep] = np.asarray(
                        _CMPS[f.op](l.to_numpy()[keep], r.to_numpy()[keep])
                    ).astype(bool)
                return out
        with np.errstate(invalid="ignore"):
            return np.asarray(_CMPS[f.op](l.to_numpy(), r.to_numpy())).astype(bool)
    if isinstance(f, ast.DistinctFrom):
        l = eval_expr(f.left, fields, df)
        r = eval_expr(f.right, fields, df)
        nl = pd.isna(l).to_numpy()
        nr = pd.isna(r).to_numpy()
        with np.errstate(invalid="ignore"):
            neq = np.asarray(l.to_numpy() != r.to_numpy(), dtype=bool)
        m = (neq & ~nl & ~nr) | (nl ^ nr)
        return ~m if f.negated else m
    if isinstance(f, ast.Between):
        v = eval_expr(f.expr, fields, df).to_numpy()
        lo = eval_expr(f.low, fields, df).to_numpy()
        hi = eval_expr(f.high, fields, df).to_numpy()
        with np.errstate(invalid="ignore"):
            m = (v >= lo) & (v <= hi)
        return ~m if f.negated else m
    if isinstance(f, ast.In):
        v = eval_expr(f.expr, fields, df)
        vals = [x.value for x in f.values if isinstance(x, ast.Literal)]
        m = v.isin(vals).to_numpy()
        return ~m if f.negated else m
    if isinstance(f, ast.Like):
        from pinot_tpu.query.plan import _like_to_regex

        v = eval_expr(f.expr, fields, df).map(str)
        m = v.str.fullmatch(_like_to_regex(f.pattern)).fillna(False).to_numpy()
        return ~m if f.negated else m
    if isinstance(f, ast.RegexpLike):
        v = eval_expr(f.expr, fields, df).map(str)
        return v.str.contains(f.pattern, regex=True).fillna(False).to_numpy()
    if isinstance(f, ast.IsNull):
        m = eval_expr(f.expr, fields, df).isna().to_numpy()
        return ~m if f.negated else m
    raise L.PlanV2Error(f"unsupported filter {f}")


# ---------------------------------------------------------------------------
# Key normalization + hashing (consistent across both join sides)
# ---------------------------------------------------------------------------


def _norm_key(s: pd.Series) -> pd.Series:
    # all numerics widen to double so INT = DOUBLE joins hash/compare equal on
    # both sides (Pinot widens numeric comparisons the same way)
    if s.dtype.kind in "iubf":
        return s.astype(np.float64)
    out = s.astype(object).copy()
    nn = s.notna()
    out[nn] = out[nn].map(str)
    return out


def _key_frame(exprs: list[ast.Expr], fields: list[L.Field], df: pd.DataFrame) -> pd.DataFrame:
    return pd.DataFrame({f"__k{i}": _norm_key(eval_expr(e, fields, df)) for i, e in enumerate(exprs)})


def _hash_partition(keydf: pd.DataFrame, n: int) -> np.ndarray:
    if n == 1 or keydf.empty:
        return np.zeros(len(keydf), dtype=np.int64)
    h = pd.util.hash_pandas_object(keydf.fillna(0), index=False).to_numpy()
    return (h % np.uint64(n)).astype(np.int64)


# ---------------------------------------------------------------------------
# Device paths for intermediate operators (SortOperator / LookupJoinOperator
# parity on the TPU): engaged for large numeric blocks, pandas otherwise.
# Counters let tests assert which path ran.
# ---------------------------------------------------------------------------

#: minimum rows before a device dispatch beats host pandas (sync overhead)
DEVICE_SORT_MIN = 1 << 16
DEVICE_JOIN_MIN = 1 << 16

DEVICE_OP_STATS = {"sort": 0, "join": 0, "window": 0}


def sorted_frame(df: pd.DataFrame, by: list, descs: list[bool], reset_index: bool = False) -> pd.DataFrame:
    """Stable multi-key sort with device dispatch above DEVICE_SORT_MIN and
    pandas mergesort fallback — the ONE sort implementation the Sort node
    and the window operator share."""
    perm = None
    if len(df) >= DEVICE_SORT_MIN:
        perm = _device_sort_perm([df[c].to_numpy() for c in by], descs)
    if perm is not None:
        out = df.take(perm)
    else:
        from pinot_tpu.common.sorting import sort_nulls_largest

        out = sort_nulls_largest(df, by, [not d for d in descs])
    return out.reset_index(drop=True) if reset_index else out


def _device_scan_economical(
    ship_bytes: int, readback_bytes: int, host_cost_s: float, round_trips: int = 2
) -> bool:
    """THE economic gate for device intermediate ops that ship whole columns
    and read results back (sort perms, window scans, join probes): the
    modeled link cost must beat the host cost. On a co-located chip the link
    moves GB/s and the gate passes above the size thresholds; on a slow
    attachment (tens of ms per round trip, MB/s) it declines — the
    AdaptiveServerSelector philosophy applied to the accelerator link.
    Callers must run their cheap dtype/shape rejections FIRST: pricing the
    link triggers the one-time devlink probe (~2 RTTs + 8MB)."""
    from pinot_tpu.common.devlink import transfer_cost_s

    return transfer_cost_s(ship_bytes + readback_bytes, round_trips=round_trips) <= host_cost_s


def _device_sort_perm(keys: list[np.ndarray], descs: list[bool]) -> "np.ndarray | None":
    """Stable multi-key sort permutation computed on device (lax.sort under
    jnp.lexsort). Returns None when a key is non-numeric or float-with-NaN
    (pandas NaN-last semantics differ) — caller falls back to pandas.
    DESC uses lossless monotone flips: bitwise NOT for ints, negation for
    floats (int64 negation could overflow at INT64_MIN; ~v cannot)."""
    import jax.numpy as jnp

    prepped = []
    for v, desc in zip(keys, descs):
        if not np.issubdtype(v.dtype, np.number):
            return None
        if np.issubdtype(v.dtype, np.floating):
            if np.isnan(v).any():
                return None
            prepped.append(-v if desc else v)
        else:
            prepped.append(~v if desc else v)
    n = len(keys[0]) if keys else 0
    ship = sum(k.nbytes for k in keys)
    # host mergesort ~ 150ns/row/key; perm readback is one int64 vector
    if not _device_scan_economical(ship, 8 * n, 150e-9 * n * max(1, len(keys)) + 2e-3):
        return None
    # jnp.lexsort: LAST key is primary -> reverse significance order
    perm = jnp.lexsort(tuple(jnp.asarray(k) for k in reversed(prepped)))
    DEVICE_OP_STATS["sort"] += 1
    return np.asarray(perm)


def _device_window_cum(fname: str, gk: np.ndarray, v: "np.ndarray | None", n: int) -> "np.ndarray | None":
    """Segmented cumulative window aggregate on device (rows pre-sorted by
    (partition, order), so partitions are contiguous): one associative
    segmented scan — combine((f1,v1),(f2,v2)) = (f1|f2, f2 ? v2 : op(v1,v2))
    with f = partition-start flags — computes running SUM/MIN/MAX/COUNT with
    reset at every partition boundary (WindowAggregateOperator parity for
    the default UNBOUNDED PRECEDING..CURRENT ROW frame). Returns None below
    the size threshold or for non-numeric / NaN inputs (pandas skipna
    cumulative semantics differ) — the pandas path takes over."""
    if n < DEVICE_SORT_MIN or fname not in ("sum", "avg", "count", "min", "max", "row_number"):
        return None
    if v is not None:
        if not np.issubdtype(v.dtype, np.number):
            return None
        if np.issubdtype(v.dtype, np.floating) and np.isnan(v).any():
            return None
    # host groupby-cumsum ~ 80ns/row; ship keys+values, read one vector back
    ship = gk.nbytes + (v.nbytes if v is not None else 0)
    if not _device_scan_economical(ship, 8 * n, 80e-9 * n + 2e-3):
        return None
    import jax
    import jax.numpy as jnp

    gk_d = jnp.asarray(gk)
    start = jnp.concatenate([jnp.ones(1, bool), gk_d[1:] != gk_d[:-1]])

    def seg_scan(op, vals):
        def comb(a, b):
            af, av = a
            bf, bv = b
            return (af | bf, jnp.where(bf, bv, op(av, bv)))

        _, out = jax.lax.associative_scan(comb, (start, vals))
        return out

    add = jnp.add
    if fname in ("row_number", "count"):
        out = seg_scan(add, jnp.ones(n, jnp.int64))
    elif fname == "sum":
        # integer values upcast to int64 exactly like pandas groupby.cumsum
        # (an int32 running sum would wrap past 2^31 on the device otherwise)
        vv = jnp.asarray(v, jnp.int64) if np.issubdtype(v.dtype, np.integer) else jnp.asarray(v)
        out = seg_scan(add, vv)
    elif fname == "avg":
        s = seg_scan(add, jnp.asarray(v, jnp.float64))
        c = seg_scan(add, jnp.ones(n, jnp.float64))
        out = s / c
    elif fname == "min":
        out = seg_scan(jnp.minimum, jnp.asarray(v))
    else:
        out = seg_scan(jnp.maximum, jnp.asarray(v))
    DEVICE_OP_STATS["window"] += 1
    return np.asarray(out)


#: pair-count blowup guard for device equi-joins (many-to-many keys)
DEVICE_JOIN_MAX_PAIRS = 1 << 25


def _join_key_pair(ls: pd.Series, rs: pd.Series) -> "tuple[np.ndarray, np.ndarray] | None":
    """Project one join-key column pair onto a COMMON comparable dtype:
    numeric when both sides hold numbers (object cells from null-handling
    scans / null-extended outer outputs coerce back to float), string when
    both sides hold strings. Returns None for cross-kind pairs (int vs str)
    so equality semantics match the pandas fallback exactly — a stringified
    compare would both drop 1 vs 1.0 matches and invent 1 vs "1" matches
    (review r4). Null cells may come out as NaN; callers mask them via the
    l_null/r_null sentinels."""

    def _as_numeric(s: pd.Series) -> np.ndarray | None:
        v = s.to_numpy()
        if v.dtype != object and np.issubdtype(v.dtype, np.number):
            return v
        if v.dtype == object:
            cells = v[~pd.isna(v)]
            # actual number objects only, checked over EVERY cell (at C speed
            # via infer_dtype) — a sampled prefix would let a numeric string
            # past the window survive pd.to_numeric and invent 1 == "1"
            if len(cells) and pd.api.types.infer_dtype(cells, skipna=True) in (
                "integer",
                "floating",
                "mixed-integer-float",
            ):
                num = pd.to_numeric(s, errors="coerce")
                if bool((num.notna() | s.isna()).all()):
                    return num.to_numpy(np.float64)
        return None

    ln, rn = _as_numeric(ls), _as_numeric(rs)
    if ln is not None and rn is not None:
        return ln, rn
    if ln is not None or rn is not None:
        return None  # one side numeric, the other strings

    def _as_str(s: pd.Series) -> np.ndarray | None:
        v = s.to_numpy()
        if v.dtype == object:
            cells = v[~pd.isna(v)]
            if len(cells) and pd.api.types.infer_dtype(cells, skipna=True) != "string":
                return None  # mixed-content object column: don't stringify
        return np.where(pd.isna(v), "", np.asarray(v, dtype=object)).astype(str)

    lstr, rstr = _as_str(ls), _as_str(rs)
    if lstr is None or rstr is None:
        return None
    return lstr, rstr


def _encode_join_keys(
    lk: pd.DataFrame, rk: pd.DataFrame, l_null: np.ndarray, r_null: np.ndarray
) -> "tuple[np.ndarray, np.ndarray] | None":
    """Combine N join-key columns into ONE int64 code per row on each side —
    the dictionary-id analog for intermediate blocks, so ANY equi-join
    (multi-key, string keys) rides the device sort+searchsorted path.

    Per key: one joint np.unique over both sides yields dense codes that are
    equal iff the values are equal across sides; codes fold together by
    cardinality strides with a re-compression after every fold (post-
    compression cardinality <= n_l + n_r < 2^31, so the stride product
    never overflows int64). Null-key rows get sentinel codes that can never
    match. Returns None when a key's dtypes can't be joined (mixed
    int/str object columns)."""
    lcodes: np.ndarray | None = None
    rcodes: np.ndarray | None = None
    for c in lk.columns:
        pair = _join_key_pair(lk[c], rk[c])
        if pair is None:
            return None  # cross-dtype (numeric vs string) keys: fallback
        lv, rv = pair
        both = np.concatenate([lv, rv])
        both = np.nan_to_num(both) if both.dtype.kind == "f" else both
        _, codes = np.unique(both, return_inverse=True)
        codes = codes.astype(np.int64)
        card = int(codes.max()) + 1 if len(codes) else 1
        lc, rc = codes[: len(lv)], codes[len(lv) :]
        if lcodes is None:
            lcodes, rcodes = lc, rc
        else:
            comb = np.concatenate([lcodes, rcodes]) * card + codes
            _, comp = np.unique(comb, return_inverse=True)
            comp = comp.astype(np.int64)
            lcodes, rcodes = comp[: len(lv)], comp[len(lv) :]
    assert lcodes is not None and rcodes is not None
    # null keys never match anything (not even other nulls)
    lcodes = np.where(l_null, np.int64(-1), lcodes)
    rcodes = np.where(r_null, np.int64(-2), rcodes)
    return lcodes, rcodes


def _device_join_economical(lk: np.ndarray, rk: np.ndarray) -> bool:
    """Whether shipping both key vectors plus the per-row index readback over
    the measured device link beats a host hash join (~70ns/input row)."""
    readback = 8 * len(lk)  # lo + count index vectors, int32 each
    host_cost = 70e-9 * (len(lk) + len(rk)) + 2e-3
    return _device_scan_economical(lk.nbytes + rk.nbytes, readback, host_cost, round_trips=8)


def _device_equi_join(
    lk: np.ndarray, rk: np.ndarray, force: bool = False
) -> "tuple[np.ndarray, np.ndarray] | None":
    """General inner equi-join on a numeric key: device direct-address /
    sort+searchsorted probe, then one vectorized host expansion of the match
    ranges. Handles duplicate build keys (the unique case degenerates to
    ranges of width <= 1 — LookupJoinOperator's shape). Returns (left row
    indices, right row indices) of matched pairs, or None when dtypes/NaNs/
    pair-count don't fit — or when the measured device link makes shipping
    both sides plus the per-row index readback slower than a host hash join
    (attachments differ by orders of magnitude in bytes/s, so the decision
    comes from the measured link profile, not a row threshold).
    `force` skips that economic gate (benchmarks measuring the device path)."""
    import jax.numpy as jnp

    if not (np.issubdtype(lk.dtype, np.number) and np.issubdtype(rk.dtype, np.number)):
        return None
    if not force and not _device_join_economical(lk, rk):
        return None
    if (np.issubdtype(lk.dtype, np.floating) and np.isnan(lk).any()) or (
        np.issubdtype(rk.dtype, np.floating) and np.isnan(rk).any()
    ):
        return None
    if len(rk) == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    if (np.issubdtype(lk.dtype, np.integer) and np.issubdtype(rk.dtype, np.integer)) or (
        lk.dtype == np.float64 and rk.dtype == np.float64
    ):
        # same-mesh HASH exchange tier (BlockExchange HASH_DISTRIBUTED as
        # all_to_all in shard_map): repartition both sides by key across
        # the devices and probe per shard. Declines (None) on duplicate
        # build keys / 1-device mesh; the single-device path then runs.
        # Multistage blocks normalize numerics to f64 — NaN-free f64 keys
        # (NaN was rejected above) bitcast to int64, which preserves
        # equality exactly (-0.0 normalized to +0.0 first).
        from pinot_tpu.parallel import shuffle

        if lk.dtype == np.float64:
            mk_l = np.where(lk == 0.0, 0.0, lk).view(np.int64)
            mk_r = np.where(rk == 0.0, 0.0, rk).view(np.int64)
        else:
            mk_l, mk_r = lk, rk
        mesh_out = shuffle.mesh_equi_join(mk_l, mk_r)
        if mesh_out is None:
            # the unique-key (build) side may be the LEFT one — the mesh
            # kernel only requires uniqueness on its right operand, so probe
            # the other way around and swap the returned pairs back
            swapped = shuffle.mesh_equi_join(mk_r, mk_l)
            if swapped is not None:
                mesh_out = (swapped[1], swapped[0])
        if mesh_out is not None:
            DEVICE_OP_STATS["join"] += 1
            DEVICE_OP_STATS["mesh_join"] = DEVICE_OP_STATS.get("mesh_join", 0) + 1
            li, ri = mesh_out
            return li.astype(np.int64), ri.astype(np.int64)
    order = np.argsort(rk, kind="stable")
    srk = rk[order]
    j_lk = jnp.asarray(lk)
    # direct addressing needs BOTH sides integral: a float probe key would
    # truncate through the idx cast and match the wrong slot (5.7 "==" 5)
    span = (
        int(srk[-1]) - int(srk[0]) + 1
        if len(srk)
        and np.issubdtype(srk.dtype, np.integer)
        and np.issubdtype(lk.dtype, np.integer)
        else 0
    )
    if 0 < span <= max(16 * len(srk), 1 << 20) and span <= (1 << 25):
        # bounded-span integer keys: device direct-address probe. Two
        # scatters build (first-index, count) tables over the key span and
        # two gathers probe them — constant gather rounds and int32
        # readbacks, vs searchsorted's ~17 binary-search gather rounds over
        # the probe vector and int64 lo/hi readbacks (on TPU the gather
        # round is the unit of cost: 4M-probe join measured ~10x faster).
        rmin = int(srk[0])
        j_keys = (jnp.asarray(srk) - rmin).astype(jnp.int32)
        pos = jnp.arange(len(srk), dtype=jnp.int32)
        lo_t = jnp.full((span,), len(srk), dtype=jnp.int32).at[j_keys].min(pos)
        cnt_t = jnp.zeros((span,), dtype=jnp.int32).at[j_keys].add(1)
        valid = (j_lk >= rmin) & (j_lk <= int(srk[-1]))
        idx = jnp.clip(j_lk - rmin, 0, span - 1).astype(jnp.int32)
        lo = np.asarray(lo_t[idx]).astype(np.int64)
        # mask on device: ONE int32 counts readback, not counts + bool mask
        counts = np.asarray(jnp.where(valid, cnt_t[idx], 0)).astype(np.int64)
    else:
        j_srk = jnp.asarray(srk)
        lo = np.asarray(jnp.searchsorted(j_srk, j_lk, side="left"))
        hi = np.asarray(jnp.searchsorted(j_srk, j_lk, side="right"))
        counts = hi - lo
    total = int(counts.sum())
    if total > DEVICE_JOIN_MAX_PAIRS:
        return None  # many-to-many blowup: pandas hash join handles it
    lidx = np.repeat(np.arange(len(lk), dtype=np.int64), counts)
    starts = np.repeat(lo, counts)
    offs = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(counts) - counts, counts)
    ridx = order[starts + offs]
    DEVICE_OP_STATS["join"] += 1
    return lidx, ridx


# ---------------------------------------------------------------------------
# Aggregation over blocks
# ---------------------------------------------------------------------------


def _agg_series(func: str, g, vals_col: str, extra: tuple, vals2_col: str | None = None):
    from pinot_tpu.query.aggregates import EXT_AGGS

    if func in EXT_AGGS:
        spec = EXT_AGGS[func]
        if vals2_col is not None:
            return g.apply(
                lambda sub: spec.finalize(
                    spec.compute(sub[vals_col].to_numpy(), sub[vals2_col].to_numpy(), extra), extra
                ),
                include_groups=False,
            )
        return g[vals_col].apply(
            lambda s: spec.finalize(spec.compute(s.to_numpy(), None, extra), extra)
        )
    if func == "count":
        return g.size() if vals_col is None else g[vals_col].size()
    sel = g[vals_col]
    if func == "sum":
        return sel.sum(min_count=1)
    if func == "min":
        return sel.min()
    if func == "max":
        return sel.max()
    if func == "avg":
        return sel.mean()
    if func in ("distinctcount", "distinctcountbitmap", "distinctcounthll"):
        return sel.nunique()
    if func == "minmaxrange":
        return sel.max() - sel.min()
    if func in ("percentile", "percentileest", "percentiletdigest"):
        return sel.quantile(extra[0] / 100.0)
    if func == "mode":
        return sel.agg(lambda s: float(s.mode().iloc[0]) if len(s.mode()) else np.nan)
    raise L.PlanV2Error(f"unsupported aggregation {func} in multistage runtime")


def _agg_scalar(func: str, s: pd.Series, extra: tuple, s2: pd.Series | None = None):
    from pinot_tpu.query.aggregates import EXT_AGGS

    if func in EXT_AGGS:
        spec = EXT_AGGS[func]
        return spec.finalize(
            spec.compute(
                s.to_numpy() if s is not None else None,
                s2.to_numpy() if s2 is not None else None,
                extra,
            ),
            extra,
        )
    if func == "count":
        return len(s)
    if len(s) == 0:
        return np.nan
    if func == "sum":
        return s.sum()
    if func == "min":
        return s.min()
    if func == "max":
        return s.max()
    if func == "avg":
        return s.mean()
    if func in ("distinctcount", "distinctcountbitmap", "distinctcounthll"):
        return s.nunique()
    if func == "minmaxrange":
        return s.max() - s.min()
    if func in ("percentile", "percentileest", "percentiletdigest"):
        return s.quantile(extra[0] / 100.0)
    if func == "mode":
        m = s.mode()
        return float(m.iloc[0]) if len(m) else np.nan
    raise L.PlanV2Error(f"unsupported aggregation {func} in multistage runtime")


# ---------------------------------------------------------------------------
# Node execution
# ---------------------------------------------------------------------------


@dataclass
class RunCtx:
    stage: L.Stage
    worker: int
    mailbox: MailboxService
    stages: dict[int, L.Stage]
    segments: dict[str, list]  # table -> segments
    n_senders: dict[int, int]  # stage id -> parallelism
    # distributed leaf mode: this worker's segment dict already holds ONLY
    # its share (the server's assigned replicas), so Scan takes all of them
    # instead of modulo-splitting by worker index
    scan_local_all: bool = False
    # per-query SET options (threaded from StagePlan.options)
    options: dict = dfield(default_factory=dict)
    # per-operator runtime stats accumulator (None = collection disabled,
    # the default — `trace=true` / EXPLAIN ANALYZE turn it on)
    stats: StageStatsCollector | None = None


def _empty_df(n_cols: int) -> pd.DataFrame:
    return pd.DataFrame({i: pd.Series(dtype=object) for i in range(n_cols)})


def _leaf_filter_mask(seg, filt, null_on: bool = False, stats=None, node=None) -> np.ndarray:
    """Leaf Scan filter on the fused device kernel (LeafStageTransferableBlock-
    Operator.java:87 parity: the v2 leaf runs the v1 engine's path). Falls
    back to the host numpy evaluator for host-only predicates; each side is
    counted in server metrics so tests/operators can assert which path ran.
    When a StageStatsCollector is threaded in, the device time / fallback is
    also attributed to the owning Scan operator's stats."""
    from pinot_tpu.common.metrics import ServerMeter, server_metrics
    from pinot_tpu.query.kernels import run_plan
    from pinot_tpu.query.plan import DeviceFallback, PlanError, mark_device_fallback, plan_filter_mask

    t0 = _time.perf_counter() if stats is not None else 0.0
    try:
        # null_on lowers nullable-column predicates to the device Kleene
        # (true, unknown) pair tree — same semantics as the v1 where_spec
        plan = plan_filter_mask(seg, filt, kleene=null_on)
        mask = np.asarray(run_plan(plan, seg.to_device_cached()))[: seg.n_docs]
    except (DeviceFallback, PlanError) as e:
        mark_device_fallback(e, f"the leaf filter of segment {seg.name}")
        if stats is not None:
            stats.add_fallback(node)
        return (
            host_exec.filter_mask_null_aware(seg, filt)
            if null_on
            else host_exec.filter_mask(seg, filt)
        )
    server_metrics().meter(ServerMeter.MULTISTAGE_LEAF_DEVICE_SCANS).mark()
    if stats is not None:
        stats.add_device(node, (_time.perf_counter() - t0) * 1e3)
    return mask


def exec_node(node: L.Node, ctx: RunCtx) -> pd.DataFrame:
    """Stats-instrumented dispatch: when the ctx carries a collector, each
    operator's rows/blocks/wall time is recorded around the real execution
    (MultiStageOperator.registerExecution parity); the disabled path is one
    attribute check."""
    # operator block boundary = the deadline/cancel enforcement point
    # (QueryThreadContext deadline checks between blocks); a slow stage
    # terminates itself instead of relying on the receiver's timeout
    dl = ctx.mailbox.deadline
    if dl is not None:
        dl.check(type(node).__name__)
    st = ctx.stats
    if st is None:
        return _exec_node(node, ctx)
    t0 = _time.perf_counter()
    df = _exec_node(node, ctx)
    st.record_exec(
        node,
        len(df),
        (_time.perf_counter() - t0) * 1e3,
        blocks=0 if isinstance(node, L.StageInput) else 1,
    )
    return df


def _exec_node(node: L.Node, ctx: RunCtx) -> pd.DataFrame:
    if isinstance(node, L.StageInput):
        blocks = ctx.mailbox.receive_all(
            ctx.stage.id, ctx.worker, node.stage_id, ctx.n_senders[node.stage_id],
            stats_out=ctx.stats.upstream if ctx.stats is not None else None,
        )
        if ctx.stats is not None:
            ctx.stats.add_blocks(node, len(blocks))  # blocks received, not emitted
        blocks = [b for b in blocks if len(b)]
        if not blocks:
            return _empty_df(len(node.fields))
        out = pd.concat(blocks, ignore_index=True)
        # Fresh per-receiver columns Index: concat of equal indexes reuses the
        # sender's Index object, and pandas' lazily-built index engine is not
        # thread-safe — two receiver threads sharing one Index object can see
        # a half-populated hashtable and raise a transient KeyError on the
        # first get_loc (e.g. in groupby).
        out.columns = pd.RangeIndex(out.shape[1])
        return out

    if isinstance(node, L.Scan):
        from pinot_tpu.query.context import null_handling_enabled

        null_on = null_handling_enabled(ctx.options)
        from pinot_tpu.common.faults import FAULTS, InjectedFault
        from pinot_tpu.common.trace import trace_event

        segs = ctx.segments.get(node.table, [])
        mine = segs if ctx.scan_local_all else segs[ctx.worker :: ctx.stage.parallelism]
        frames = []
        for seg in mine:
            if ctx.mailbox.deadline is not None:
                ctx.mailbox.deadline.check(f"scan {seg.name}")
            try:
                FAULTS.maybe_fail("segment.execute")
            except InjectedFault:
                trace_event("fault.injected", point="segment.execute", segment=seg.name)
                raise
            mask = (
                _leaf_filter_mask(seg, node.filter, null_on=null_on, stats=ctx.stats, node=node)
                if node.filter is not None
                else None
            )
            valid = seg.extras.get("valid_docs")
            if valid is not None:
                vm = valid(seg.n_docs)
                mask = vm if mask is None else (mask & vm)
            data = {}
            for i, col in enumerate(node.columns):
                v = seg.columns[col].materialize()
                if null_on:
                    nv = (seg.extras or {}).get("null", {}).get(col)
                    if nv is not None:
                        from pinot_tpu.native import bm_to_bool

                        nm = bm_to_bool(nv, seg.n_docs)
                        v = v.astype(object)
                        v[nm] = None  # None cells, not stored placeholders
                data[i] = v[mask] if mask is not None else v
            frames.append(pd.DataFrame(data))
        if not frames:
            return _empty_df(len(node.fields))
        return pd.concat(frames, ignore_index=True)

    if isinstance(node, L._RootCollect):
        return exec_node(node.input, ctx)

    if isinstance(node, L.FilterNode):
        df = exec_node(node.input, ctx)
        if df.empty:
            return df
        m = eval_filter(node.condition, node.input.fields, df)
        return df[m].reset_index(drop=True)

    if isinstance(node, L.Project):
        df = exec_node(node.input, ctx)
        out = {}
        for i, e in enumerate(node.exprs):
            out[i] = eval_expr(e, node.input.fields, df).reset_index(drop=True)
        return pd.DataFrame(out) if out else _empty_df(0)

    if isinstance(node, L.Rename):
        df = exec_node(node.input, ctx)
        sub = df.iloc[:, : node.n_visible].copy()
        sub.columns = range(node.n_visible)
        return sub

    if isinstance(node, L.Aggregate):
        return _exec_aggregate(node, ctx)

    if isinstance(node, L.Distinct):
        df = exec_node(node.input, ctx)
        return df.drop_duplicates(ignore_index=True)

    if isinstance(node, L.Join):
        return _exec_join(node, ctx)

    if isinstance(node, L.WindowNode):
        return _exec_window(node, ctx)

    if isinstance(node, L.Sort):
        df = exec_node(node.input, ctx)
        if node.keys and len(df):
            df = sorted_frame(
                df, [k for k, _ in node.keys], [d for _, d in node.keys], reset_index=True
            )
        if node.offset or node.limit is not None:
            end = None if node.limit is None else node.offset + node.limit
            df = df.iloc[node.offset : end].reset_index(drop=True)
        if node.drop_hidden_after is not None:
            df = df.iloc[:, : node.drop_hidden_after]
        return df

    if isinstance(node, L.SetOp):
        l = exec_node(node.left, ctx)
        r = exec_node(node.right, ctx)
        r.columns = l.columns = range(l.shape[1])
        if node.kind == "union":
            out = pd.concat([l, r], ignore_index=True)
            return out if node.all else out.drop_duplicates(ignore_index=True)
        cols = list(l.columns)
        if node.all:
            # bag semantics via per-duplicate ordinals: the k-th copy on the
            # left pairs with the k-th copy on the right
            l = l.assign(__ord=l.groupby(cols, dropna=False).cumcount())
            r = r.assign(__ord=r.groupby(cols, dropna=False).cumcount())
            on = cols + ["__ord"]
            if node.kind == "intersect":
                return l.merge(r, how="inner", on=on)[cols].reset_index(drop=True)
            m = l.merge(r, how="left", on=on, indicator=True)
            return m[m["_merge"] == "left_only"][cols].reset_index(drop=True)
        lu = l.drop_duplicates()
        ru = r.drop_duplicates()
        if node.kind == "intersect":
            return lu.merge(ru, how="inner", on=cols).reset_index(drop=True)
        # except
        m = lu.merge(ru, how="left", on=cols, indicator=True)
        return (
            m[m["_merge"] == "left_only"].drop(columns="_merge").reset_index(drop=True)
        )

    raise L.PlanV2Error(f"cannot execute node {type(node).__name__}")


_FILTERED_AGGS = {"count", "sum", "min", "max", "avg"}


def _exec_aggregate(node: L.Aggregate, ctx: RunCtx) -> pd.DataFrame:
    if node.mode == "partial":
        # leaf pattern first: Scan input + plain-column keys/args runs the
        # fused v1 device engine WITHOUT materializing scan rows
        t0 = _time.perf_counter() if ctx.stats is not None else 0.0
        leaf = _try_leaf_device_partial(node, ctx)
        if leaf is not None:
            if ctx.stats is not None:
                ctx.stats.add_device(node, (_time.perf_counter() - t0) * 1e3)
            return leaf
        from pinot_tpu.query.context import null_handling_enabled as _nhe

        return _exec_partial_aggregate(node, exec_node(node.input, ctx), _nhe(ctx.options))
    if node.mode == "final":
        from pinot_tpu.query.context import null_handling_enabled as _nhe

        return _exec_final_aggregate(node, exec_node(node.input, ctx), _nhe(ctx.options))
    from pinot_tpu.query.context import null_handling_enabled

    null_on = null_handling_enabled(ctx.options)
    df = exec_node(node.input, ctx)
    infields = node.input.fields
    n_groups = len(node.group_exprs)
    if n_groups == 0:
        row = []
        for a in node.aggs:
            sub = df
            if a.filter is not None and len(df):
                sub = df[np.asarray(eval_filter(a.filter, infields, df), bool)]
            s = eval_expr(a.arg, infields, sub) if a.arg is not None else pd.Series(np.zeros(len(sub)))
            s2 = eval_expr(a.arg2, infields, sub) if a.arg2 is not None else None
            if null_on and a.arg is not None and a.func in ("count", "sum", "min", "max", "avg", "minmaxrange"):
                s = s[pd.notna(s)]  # null-handling: aggregate non-null cells only
            if null_on and a.func == "sum" and len(s) == 0:
                row.append(None)  # all-null/empty SUM -> NULL (holder never set)
                continue
            row.append(_agg_scalar(a.func, s, a.extra, s2))
        return pd.DataFrame({i: [v] for i, v in enumerate(row)})
    if df.empty:
        return _empty_df(len(node.fields))
    work = {}
    for i, g in enumerate(node.group_exprs):
        work[f"g{i}"] = eval_expr(g, infields, df).reset_index(drop=True)
    for j, a in enumerate(node.aggs):
        fm = None
        if a.filter is not None:
            if a.func not in _FILTERED_AGGS:
                raise L.PlanV2Error(f"FILTER(WHERE) on {a.func} inside GROUP BY is not supported")
            fm = np.asarray(eval_filter(a.filter, infields, df), bool)
        if a.func == "count":
            # the indicator folds in FILTER — the arg column must NOT be
            # summed (COUNT(col) keeps its arg since round 3). Under
            # enableNullHandling, COUNT(col) counts non-null cells only
            # (v2 scans materialize None cells), matching v1.
            ind = fm if fm is not None else np.ones(len(df), dtype=bool)
            if a.arg is not None and null_on:
                ind = ind & pd.notna(eval_expr(a.arg, infields, df)).to_numpy()
            work[f"v{j}"] = pd.Series(ind.astype(np.int64))
        elif a.arg is not None:
            v = eval_expr(a.arg, infields, df).reset_index(drop=True)
            if fm is not None:
                # excluded rows -> NaN; pandas reducers skip them
                v = pd.Series(np.where(fm, v.to_numpy(np.float64), np.nan))
            work[f"v{j}"] = v
        if a.arg2 is not None:
            work[f"w{j}"] = eval_expr(a.arg2, infields, df).reset_index(drop=True)
    wdf = pd.DataFrame(work)
    gb = wdf.groupby([f"g{i}" for i in range(n_groups)], dropna=False, sort=False)
    outs = []
    for j, a in enumerate(node.aggs):
        col = f"v{j}" if f"v{j}" in work else None
        col2 = f"w{j}" if a.arg2 is not None else None
        if a.func == "count":
            outs.append(gb[col].sum().rename(f"a{j}"))
            continue
        s = _agg_series(a.func, gb, col, a.extra, col2)
        if a.filter is not None and a.func in ("min", "max"):
            # all-NaN groups (FILTER matched no rows): same +/-inf sentinels
            # as the v1 host path / device kernel (host_exec.group_frame)
            s = s.fillna(np.inf if a.func == "min" else -np.inf)
        outs.append(s.rename(f"a{j}"))
    if outs:
        res = pd.concat(outs, axis=1).reset_index()
    else:
        res = gb.size().reset_index().iloc[:, :n_groups]
    res.columns = range(res.shape[1])
    return res


def _try_leaf_device_partial(node: L.Aggregate, ctx: RunCtx) -> pd.DataFrame | None:
    """PartialAggregate directly over a Scan with plain-column keys/args:
    run the fused v1 device engine per segment (LeafStageTransferableBlock-
    Operator.java:87 parity — the leaf stage IS the single-stage engine) and
    emit its mergeable group frames as the partial block. Returns None when
    the pattern doesn't match (pandas partial takes over)."""
    scan = node.input
    if not isinstance(scan, L.Scan):
        return None
    for g in node.group_exprs:
        if not isinstance(g, ast.Identifier):
            return None
    for a in node.aggs:
        if a.arg is not None and not isinstance(a.arg, ast.Identifier):
            return None
        if a.arg2 is not None:
            return None
    from pinot_tpu.query.context import QueryContext, QueryType
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.query.reduce import parts_of

    segs = ctx.segments.get(scan.table, [])
    mine = segs if ctx.scan_local_all else segs[ctx.worker :: ctx.stage.parallelism]
    strip = lambda e: ast.Identifier(e.name.split(".", 1)[1]) if "." in e.name else e  # noqa: E731
    import dataclasses as _dc

    aggs = [
        _dc.replace(
            a,
            arg=strip(a.arg) if isinstance(a.arg, ast.Identifier) else a.arg,
        )
        for a in node.aggs
    ]
    qctx = QueryContext(
        statement=None,
        table=scan.table,
        query_type=QueryType.GROUP_BY if node.group_exprs else QueryType.AGGREGATION,
        select_items=[],
        aggregations=aggs,
        group_by=[strip(g) for g in node.group_exprs],
        filter=scan.filter,
        having=None,
        order_by=[],
        limit=1 << 30,
        offset=0,
        options=dict(ctx.options),
    )
    from pinot_tpu.common.faults import InjectedFault
    from pinot_tpu.query.context import QueryCancelledError, QueryTimeoutError

    qctx.deadline = ctx.mailbox.deadline
    eng = QueryEngine(mine)
    try:
        partials, _matched, _scan = eng.partials(qctx, mine)
    except (QueryTimeoutError, QueryCancelledError, InjectedFault):
        raise  # deadline/cancel/chaos must fail the stage, not fall back
    except Exception:
        return None  # column/type not lowerable: pandas partial takes over
    from pinot_tpu.common.metrics import ServerMeter, server_metrics

    if mine:
        server_metrics().meter(ServerMeter.MULTISTAGE_LEAF_DEVICE_SCANS).mark(len(mine))
    k = len(node.group_exprs)
    if not node.group_exprs:
        # scalar partials: one row of part columns per segment
        rows = []
        for p in partials:
            row = []
            for a, part in zip(node.aggs, p):
                row.extend(part if parts_of(a.func) == 2 else [part])
            rows.append(row)
        if not rows:
            return _empty_df(len(node.fields))
        return pd.DataFrame({i: [r[i] for r in rows] for i in range(len(node.fields))})
    frames = [f for f in partials if hasattr(f, "columns") and len(f)]
    if not frames:
        return _empty_df(len(node.fields))
    out = pd.concat(frames, ignore_index=True)
    # k0..kN + a{i}p{j} -> positional columns matching node.fields
    order = [f"k{i}" for i in range(k)]
    for i, a in enumerate(node.aggs):
        order.extend(f"a{i}p{j}" for j in range(parts_of(a.func)))
    out = out[order]
    out.columns = range(out.shape[1])
    return out


def _exec_partial_aggregate(node: L.Aggregate, df: pd.DataFrame, null_on: bool = False) -> pd.DataFrame:
    """Pandas partial over an arbitrary input block: emits the v1 mergeable
    partial layout [keys..., per-agg parts...] (host_exec.group_frame's
    column formats). Under enableNullHandling (null_on), COUNT(col) skips
    null cells and SUM emits NaN for all-null input (review r4 — this path
    must agree with the plain grouped path and the v1 engine)."""
    from pinot_tpu.query.reduce import parts_of

    infields = node.input.fields
    k = len(node.group_exprs)
    if df.empty:
        return _empty_df(len(node.fields))
    work: dict = {}
    for i, g in enumerate(node.group_exprs):
        work[f"g{i}"] = eval_expr(g, infields, df).reset_index(drop=True)
    masks = []
    vals = []
    for a in node.aggs:
        fm = None
        if a.filter is not None:
            fm = np.asarray(eval_filter(a.filter, infields, df), bool)
        masks.append(fm)
        vals.append(
            eval_expr(a.arg, infields, df).reset_index(drop=True) if a.arg is not None else None
        )

    def _partial_cols(sub_idx=None):
        cols: list = []
        for a, fm, v in zip(node.aggs, masks, vals):
            vv = None if v is None else (v if sub_idx is None else v.iloc[sub_idx])
            mm = fm if sub_idx is None else (None if fm is None else fm[sub_idx])
            if vv is not None and mm is not None:
                vv = pd.Series(np.where(mm, vv.to_numpy(np.float64), np.nan))
            if a.func == "count":
                if null_on and vv is not None:
                    nn = pd.notna(vv).to_numpy()  # COUNT(col) skips nulls
                    cols.append(int((nn & mm).sum() if mm is not None else nn.sum()))
                else:
                    cols.append(
                        int(mm.sum())
                        if mm is not None
                        else (len(df) if sub_idx is None else len(sub_idx))
                    )
            elif a.func == "sum":
                arr = vv.to_numpy(np.float64)
                nn = arr[~np.isnan(arr)]
                # NaN partial = "no non-null rows" under null handling
                cols.append(float(nn.sum()) if len(nn) else (float("nan") if null_on else 0.0))
            elif a.func in ("min", "max"):
                arr = vv.to_numpy(np.float64)
                arr = arr[~np.isnan(arr)]
                if a.func == "min":
                    cols.append(float(arr.min()) if len(arr) else float("inf"))
                else:
                    cols.append(float(arr.max()) if len(arr) else float("-inf"))
            elif a.func == "avg":
                arr = vv.to_numpy(np.float64)
                cols.append(float(np.nansum(arr)))
                cols.append(int(np.count_nonzero(~np.isnan(arr))))
            elif a.func == "minmaxrange":
                arr = vv.to_numpy(np.float64)
                arr = arr[~np.isnan(arr)]
                cols.append(float(arr.min()) if len(arr) else float("inf"))
                cols.append(float(arr.max()) if len(arr) else float("-inf"))
            elif a.func in ("distinctcount", "distinctcountbitmap"):
                cols.append(set(vv.dropna().tolist()))
            elif a.func == "distinctcounthll":
                # registers, matching the leaf device partial format (a mixed
                # set|registers merge would crash in the final stage)
                from pinot_tpu.query.sketches import np_hll_registers

                cols.append(np_hll_registers(vv.dropna().to_numpy()))
            elif a.func == "percentiletdigest":
                from pinot_tpu.query.aggregates import _td_comp
                from pinot_tpu.query.quantile_sketch import td_from_values

                cols.append(td_from_values(np.asarray(vv.dropna(), dtype=np.float64), _td_comp(a.extra)))
            else:  # percentile: exact-values partial
                cols.append(np.asarray(vv.dropna(), dtype=np.float64))
        return cols

    if k == 0:
        cols = _partial_cols()
        return pd.DataFrame({i: [v] for i, v in enumerate(cols)})
    key_df = pd.DataFrame({f"g{i}": work[f"g{i}"] for i in range(k)})
    by = [f"g{i}" for i in range(k)] if k > 1 else "g0"
    rows = []
    # .indices, not .groups: with dropna=False a NaN key (e.g. LEFT JOIN
    # unmatched rows) makes .groups raise "Categorical categories cannot be
    # null" in pandas 2.x; .indices also yields positions directly
    for key, pos in key_df.groupby(by, dropna=False, sort=False).indices.items():
        key_vals = list(key) if isinstance(key, tuple) else [key]
        rows.append(key_vals + _partial_cols(pos))
    ncols = k + sum(parts_of(a.func) for a in node.aggs)
    return pd.DataFrame({i: [r[i] for r in rows] for i in range(ncols)})


def _exec_final_aggregate(node: L.Aggregate, df: pd.DataFrame, null_on: bool = False) -> pd.DataFrame:
    """Merge partial columns per group and finalize. The per-function merge
    is reduce._merge_agg_partials — the SAME table the broker reduce uses —
    so partial formats (sets vs HLL registers, value arrays, counters) never
    drift between the v1 and v2 engines."""
    from functools import reduce as _fold

    from pinot_tpu.query.reduce import _empty_partial, _finalize, _merge_agg_partials, parts_of

    k = len(node.group_exprs)
    if df.empty:
        if k == 0:
            row = [
                _finalize(
                    a,
                    None if null_on and a.func == "sum" else _empty_partial(a.func, a.extra),
                    null_on,
                )
                for a in node.aggs
            ]
            return pd.DataFrame({i: [v] for i, v in enumerate(row)})
        return _empty_df(len(node.fields))

    # column offsets of each agg's parts
    offs = []
    pos = k
    for a in node.aggs:
        offs.append(pos)
        pos += parts_of(a.func)

    def _merge_rows(sub: pd.DataFrame) -> list:
        out = []
        for a, off in zip(node.aggs, offs):
            if parts_of(a.func) == 2:
                parts = [(row[off], row[off + 1]) for _, row in sub.iterrows()]
            else:
                parts = list(sub[off])
            merged = _fold(lambda x, y, _f=a.func: _merge_agg_partials(_f, x, y, null_on), parts)
            out.append(_finalize(a, merged, null_on))
        return out

    if k == 0:
        return pd.DataFrame({i: [v] for i, v in enumerate(_merge_rows(df))})
    rows = []
    by = list(range(k)) if k > 1 else 0
    # .indices, not .groups — see _exec_partial_aggregate: a NaN group key
    # with dropna=False makes .groups raise in pandas 2.x
    for key, pos in df.groupby(by, dropna=False, sort=False).indices.items():
        key_vals = list(key) if isinstance(key, tuple) else [key]
        rows.append(key_vals + _merge_rows(df.iloc[pos]))
    return pd.DataFrame({i: [r[i] for r in rows] for i in range(len(node.fields))})


def _join_input_dist(node: L.Node, ctx: RunCtx):
    """Distribution that routed a join input's rows to this worker. Project/
    Filter/Rename don't re-route rows, so walk through them to the underlying
    StageInput; a Scan means co-located leaf data (no exchange -> None).
    Anything else (an in-stage Aggregate/Join/...) makes the routing
    indeterminate from here — callers must fail closed on it."""
    while isinstance(node, (L.Project, L.FilterNode, L.Rename)):
        node = node.input
    if isinstance(node, L.StageInput):
        return ctx.stages[node.stage_id].dist
    if isinstance(node, L.Scan):
        return None
    return "indeterminate"


def _exec_join(node: L.Join, ctx: RunCtx) -> pd.DataFrame:
    l = exec_node(node.left, ctx)
    r = exec_node(node.right, ctx)
    nl, nr = len(node.left.fields), len(node.right.fields)
    l.columns = [f"l{i}" for i in range(nl)]
    r.columns = [f"r{i}" for i in range(nr)]
    keys = [f"__k{i}" for i in range(len(node.left_keys))]
    if keys:
        lk = _key_frame(node.left_keys, node.left.fields, l.rename(columns=dict(zip(l.columns, range(nl)))))
        rk = _key_frame(node.right_keys, node.right.fields, r.rename(columns=dict(zip(r.columns, range(nr)))))
        # mixed-type key pair (numeric vs string column): coerce the string
        # side numerically — parseable values compare as numbers (Pinot
        # widens comparisons the same way), unparseable ones become NaN and
        # ride the null-key path below (a NULL key never matches). Coercion
        # is only sound when the rows were NOT routed here by hashing both
        # sides' raw representations: hash(float 5.0) != hash("5"), so a
        # HASH-HASH distributed mixed-type join would drop cross-partition
        # matches silently — fail loudly instead (Calcite rejects the
        # uncasted mixed-type equi-join at validation for the same reason).
        for kc in lk.columns:
            lnum, rnum = lk[kc].dtype.kind == "f", rk[kc].dtype.kind == "f"
            if lnum != rnum:
                ldist = _join_input_dist(node.left, ctx)
                rdist = _join_input_dist(node.right, ctx)
                # an indeterminate input can't be ruled out as hash-routed:
                # treat it as HASH (fail closed) rather than silently coercing
                l_hashy = ldist == L.HASH or ldist == "indeterminate"
                r_hashy = rdist == L.HASH or rdist == "indeterminate"
                if l_hashy and r_hashy:
                    raise L.PlanV2Error(
                        "join key type mismatch (numeric vs string) across hash-"
                        "partitioned inputs; add an explicit CAST on one side"
                    )
                if lnum:
                    rk[kc] = pd.to_numeric(rk[kc], errors="coerce").astype(np.float64)
                else:
                    lk[kc] = pd.to_numeric(lk[kc], errors="coerce").astype(np.float64)
        lk.index = l.index
        rk.index = r.index
        l = pd.concat([l, lk], axis=1)
        r = pd.concat([r, rk], axis=1)
        l_null = lk.isna().any(axis=1).to_numpy() if len(l) else np.zeros(0, bool)
        r_null = rk.isna().any(axis=1).to_numpy() if len(r) else np.zeros(0, bool)
    else:
        keys = ["__cross"]
        l["__cross"] = 1
        r["__cross"] = 1
        l_null = np.zeros(len(l), bool)
        r_null = np.zeros(len(r), bool)

    lcols = [f"l{i}" for i in range(nl)]
    rcols = [f"r{i}" for i in range(nr)]

    def _positional_frame(m: pd.DataFrame) -> pd.DataFrame:
        return m.set_axis(range(m.shape[1]), axis=1).reset_index(drop=True)

    def _positional(m: pd.DataFrame) -> pd.DataFrame:
        return _positional_frame(m[lcols + rcols])

    kind = node.kind if node.kind != "cross" else "inner"

    # -- device path: ANY equi-keyed join (multi-key / string keys ride the
    # joint dense encoding; inner AND outer kinds — HashJoinOperator.java:71
    # parity, executed as device sort + searchsorted range probe) ----------
    if keys[0] != "__cross" and len(l) >= DEVICE_JOIN_MIN and len(r):
        # single plain-numeric key with no nulls: probe the raw values
        # directly — the joint np.unique encode would cost a host sort
        # comparable to the offloaded work (review r4)
        if (
            len(keys) == 1
            and not l_null.any()
            and not r_null.any()
            and l[keys[0]].dtype != object
            and r[keys[0]].dtype != object
            and np.issubdtype(l[keys[0]].dtype, np.number)
            and np.issubdtype(r[keys[0]].dtype, np.number)
        ):
            enc = (l[keys[0]].to_numpy(), r[keys[0]].to_numpy())
        else:
            enc = _encode_join_keys(l[keys], r[keys], l_null, r_null)
        dev = _device_equi_join(enc[0], enc[1]) if enc is not None else None
        if dev is not None:
            lidx, ridx = dev
            lm = l.iloc[lidx]
            rm = r.iloc[ridx]
            rm.index = lm.index
            pairs = pd.concat([lm[lcols], rm[rcols]], axis=1)
            if node.post_filter is not None and len(pairs):
                view = pairs.set_axis(range(nl + nr), axis=1)
                fm = np.asarray(eval_filter(node.post_filter, node.fields, view), bool)
                pairs = pairs[fm]
                lidx = lidx[fm]
                ridx = ridx[fm]
            if kind == "inner":
                return _positional_frame(pairs)
            # outer: append unmatched rows null-extended (the ON residual
            # participated in matching above, so a residual-failed row
            # correctly null-extends instead of dropping)
            parts = [pairs]
            if kind in ("left", "full"):
                lmatched = np.zeros(len(l), dtype=bool)
                lmatched[lidx] = True
                parts.append(l[~lmatched][lcols])
            if kind in ("right", "full"):
                rmatched = np.zeros(len(r), dtype=bool)
                rmatched[ridx] = True
                parts.append(r[~rmatched][rcols])
            return _positional_frame(pd.concat(parts, ignore_index=True)[lcols + rcols])

    # -- pandas fallback (small blocks / unjoinable key dtypes) ------------
    if kind == "inner":
        m = l[~l_null].merge(r[~r_null], how="inner", on=keys)
        out = _positional(m)
        if node.post_filter is not None and len(out):
            out = out[eval_filter(node.post_filter, node.fields, out)].reset_index(drop=True)
        return out

    # outer joins: the ON residual participates in MATCHING (a failed residual
    # null-extends the row, it must not drop it) — so: inner-match with the
    # full ON condition first, then append unmatched rows null-extended.
    l = l.assign(__lid=np.arange(len(l)))
    r = r.assign(__rid=np.arange(len(r)))
    inner = l[~l_null].merge(r[~r_null], how="inner", on=keys)
    if node.post_filter is not None and len(inner):
        view = inner[lcols + rcols].copy()
        view.columns = range(nl + nr)
        inner = inner[eval_filter(node.post_filter, node.fields, view)]
    parts = [inner]
    if kind in ("left", "full"):
        parts.append(l[~l["__lid"].isin(inner["__lid"])])
    if kind in ("right", "full"):
        parts.append(r[~r["__rid"].isin(inner["__rid"])])
    m = pd.concat(parts, ignore_index=True)
    return _positional(m)


_WINDOW_AGGS = {"sum", "min", "max", "avg", "count"}
_WINDOW_RANKS = {"row_number", "rank", "dense_rank"}


def _exec_window(node: L.WindowNode, ctx: RunCtx) -> pd.DataFrame:
    df = exec_node(node.input, ctx)
    infields = node.input.fields
    base_n = len(infields)
    out = df.copy()
    for wi, wf in enumerate(node.windows):
        fname = wf.func.name
        n = len(df)
        if n == 0:
            out[base_n + wi] = pd.Series(dtype=float)
            continue
        pcols = [eval_expr(p, infields, df).reset_index(drop=True) for p in wf.partition_by]
        ocols = [eval_expr(o.expr, infields, df).reset_index(drop=True) for o in wf.order_by]
        odesc = [o.desc for o in wf.order_by]
        wdf = pd.DataFrame(
            {**{f"p{i}": c for i, c in enumerate(pcols)}, **{f"o{i}": c for i, c in enumerate(ocols)}}
        )
        if wf.func.args and not isinstance(wf.func.args[0], ast.Star):
            wdf["v"] = eval_expr(wf.func.args[0], infields, df).reset_index(drop=True)
        pnames = [f"p{i}" for i in range(len(pcols))] or None
        if fname in _WINDOW_AGGS and not ocols:
            if pnames is None:
                if fname == "count":
                    res = pd.Series(np.full(n, int(wdf["v"].notna().sum()) if "v" in wdf else n))
                else:
                    res = pd.Series(np.full(n, _agg_scalar(fname, wdf["v"], ())))
            else:
                g = wdf.groupby(pnames, dropna=False)
                if fname == "count":
                    res = g["v"].transform("count") if "v" in wdf else g["p0"].transform("size")
                else:
                    res = g["v"].transform(fname if fname != "avg" else "mean")
        else:
            onames = [f"o{i}" for i in range(len(ocols))]
            # the sort is the window operator's cost center: shared dispatch
            # (device lexsort above threshold, pandas mergesort otherwise)
            sf = sorted_frame(wdf, (pnames or []) + onames, [False] * len(pcols) + list(odesc))
            if pnames is None:
                sf["__grp"] = 0
                gname = "__grp"
                g = sf.groupby(gname)
            else:
                g = sf.groupby(pnames, dropna=False)
            dres = None
            if fname == "row_number" or fname in _WINDOW_AGGS:
                # the cumulative scan rides the device as one segmented
                # associative scan when the block is large and numeric
                # (NaN/object values fall back inside _device_window_cum)
                _v = sf["v"].to_numpy() if "v" in sf else None
                dres = _device_window_cum(fname, g.ngroup().to_numpy(), _v, len(sf))
            rn = None if dres is not None else g.cumcount() + 1
            if dres is not None:
                res = pd.Series(dres, index=sf.index)
            elif fname == "row_number":
                res = rn
            elif fname in ("rank", "dense_rank"):
                first = rn == 1
                if onames:
                    changed = np.zeros(len(sf), dtype=bool)
                    for o in onames:
                        col = sf[o].to_numpy()
                        prev = np.roll(col, 1)
                        with np.errstate(invalid="ignore"):
                            neq = col != prev
                        both_nan = pd.isna(col) & pd.isna(np.roll(col, 1))
                        changed |= neq & ~both_nan
                    changed[0] = True
                    newkey = first.to_numpy() | changed
                else:
                    newkey = first.to_numpy()
                if fname == "rank":
                    vals = np.where(newkey, rn.to_numpy(), 0)
                    filled = pd.Series(vals, index=sf.index).replace(0, np.nan)
                    grp_keys = g.ngroup()
                    res = filled.groupby(grp_keys.to_numpy()).ffill().astype(np.int64)
                else:
                    grp_keys = g.ngroup().to_numpy()
                    inc = newkey.astype(np.int64)
                    res = pd.Series(inc, index=sf.index).groupby(grp_keys).cumsum()
            elif fname in _WINDOW_AGGS:
                if fname == "count":
                    res = rn if "v" not in sf else sf["v"].notna().astype(np.int64).groupby(g.ngroup().to_numpy()).cumsum()
                elif fname == "avg":
                    gk = g.ngroup().to_numpy()
                    cs = sf["v"].groupby(gk).cumsum()
                    cnt = pd.Series(np.ones(len(sf)), index=sf.index).groupby(gk).cumsum()
                    res = cs / cnt
                else:
                    gk = g.ngroup().to_numpy()
                    if fname == "sum":
                        res = sf["v"].groupby(gk).cumsum()
                    elif fname == "min":
                        res = sf["v"].groupby(gk).cummin()
                    else:
                        res = sf["v"].groupby(gk).cummax()
            else:
                raise L.PlanV2Error(f"unsupported window function {fname}")
            res = res.reindex(df.index)
        out[base_n + wi] = pd.Series(np.asarray(res), index=df.index) if len(res) == n else res
    out.columns = range(out.shape[1])
    return out


# ---------------------------------------------------------------------------
# Stage workers + engine
# ---------------------------------------------------------------------------


def _send_output(df: pd.DataFrame, stage: L.Stage, parent_id: int, parent_par: int, mailbox: MailboxService, worker: int, stats: list | None = None):
    if stage.dist == L.SINGLETON:
        mailbox.send(stage.id, parent_id, 0, df)
    elif stage.dist == L.BROADCAST:
        for w in range(parent_par):
            mailbox.send(stage.id, parent_id, w, df)
    elif stage.dist == L.RANDOM:
        mailbox.send(stage.id, parent_id, worker % parent_par, df)
    elif stage.dist == L.HASH:
        keydf = _key_frame(stage.key_exprs, stage.root.fields, df)
        part = _hash_partition(keydf, parent_par)
        for w in range(parent_par):
            sub = df[part == w]
            if len(sub):
                mailbox.send(stage.id, parent_id, w, sub.reset_index(drop=True))
    else:
        raise L.PlanV2Error(f"unknown distribution {stage.dist}")
    # stats ride the trailing EOS (MultiStageQueryStats parity) — to parent
    # worker 0 ONLY, so a multi-worker parent doesn't relay duplicate copies.
    # That frame goes LAST, and a callable defers its construction to the
    # transport's send attempt, so the shipped trace subtree includes
    # fault/retry span events recorded during the other EOS sends and during
    # its own failed attempts.
    for w in [*range(1, parent_par), 0]:
        if stats and w == 0:
            payload = (lambda: ("__eos__", stats())) if callable(stats) else ("__eos__", stats)
        else:
            payload = _EOS
        mailbox.send(stage.id, parent_id, w, payload)


def run_stage_worker(
    stage: L.Stage,
    w: int,
    mailbox: MailboxService,
    stages: dict[int, L.Stage],
    segments: dict[str, list],
    n_senders: dict[int, int],
    parent_of: dict[int, int],
    scan_local_all: bool = False,
    errors: list | None = None,
    options: dict | None = None,
    trace_out=None,
) -> None:
    """Run ONE (stage, worker) OpChain to completion: execute the stage
    subtree and ship its output (or an error marker) to every parent worker.
    Shared by the in-process engine and the distributed server runtime.

    trace_out: this worker's common.trace.RequestTrace (distributed remote
    workers only). Its span subtree is appended to the trailing-EOS stats
    payload as a TRACE_RECORD_KEY record for the broker to reassemble."""
    from pinot_tpu.common.trace import InvocationScope

    opts = dict(options or {})
    ctx = RunCtx(
        stage, w, mailbox, stages, segments, n_senders,
        scan_local_all=scan_local_all, options=opts,
        stats=StageStatsCollector(stage, w) if stats_enabled(opts) else None,
    )
    parent = parent_of[stage.id]
    parent_par = stages[parent].parallelism
    try:
        with InvocationScope(f"stage{stage.id}:w{w}"):
            df = exec_node(stage.root, ctx)
        stats = ctx.stats.payload() if ctx.stats is not None else None
        if trace_out is not None and stats is not None:
            from pinot_tpu.multistage.stats import TRACE_RECORD_KEY

            base_stats = stats

            def stats_with_subtree():
                # resolved at (re)send time, not here: mailbox fault/retry
                # events recorded DURING the EOS send must make the snapshot
                trace_out.root.duration_ms = trace_out.now_ms()
                return base_stats + [{TRACE_RECORD_KEY: trace_out.subtree()}]

            stats = stats_with_subtree
        _send_output(df, stage, parent, parent_par, mailbox, w, stats=stats)
    except BaseException as e:  # propagate to receivers, error code intact
        from pinot_tpu.common.errors import code_of

        if errors is not None:
            errors.append(e)
        for pw in range(parent_par):
            try:
                mailbox.send(stage.id, parent, pw, ("__err__", repr(e), code_of(e)))
            except Exception:  # pinotlint: disable=deadline-swallow — best-effort marker forwarding; the receiver's own deadline reports the loss
                pass


class MultistageEngine:
    """In-process v2 engine: plans SQL into stages and runs OpChains on
    threads, leaf stages scanning the catalog's segments.

    Reference parity: QueryDispatcher.submitAndReduce
    (pinot-query-runtime/.../QueryDispatcher.java:128) + worker QueryServer.
    """

    def __init__(
        self,
        catalog: dict[str, list],
        n_workers: int = 2,
        schemas: dict[str, list[str]] | None = None,
    ):
        """schemas: optional table -> column names, needed for tables whose
        segment list is empty (a valid empty table must plan, not error)."""
        self.catalog = dict(catalog)
        self.n_workers = n_workers
        self.schemas = dict(schemas) if schemas else {}

    def execute(self, sql: str, stmt=None, deadline=None) -> ResultTable:
        """deadline: optional query.context.Deadline enforced at every
        operator block boundary and mailbox receive."""
        import time

        from pinot_tpu.query.sql import parse_sql

        t0 = time.perf_counter()
        if stmt is None:
            stmt = parse_sql(sql)
        cat = L.Catalog.from_segments(self.catalog, self.schemas)
        plan = L.build_stage_plan(stmt, cat, self.n_workers)
        # singleton-fed stages collapse to one worker BEFORE explain so the
        # reported parallelism matches what actually runs
        for s in plan.stages.values():
            for inp in s.inputs:
                if plan.stages[inp].dist == L.SINGLETON:
                    s.parallelism = 1
        if getattr(stmt, "explain", False):
            # EXPLAIN PLAN FOR: one row per stage in the documented
            # [Operator, Operator_Id, Parent_Id] schema (DataSchema.java:70) —
            # Operator carries the stage plan text with parallelism/dist
            parent_of: dict[int, int] = {}
            for s in plan.stages.values():
                for inp in s.inputs:
                    parent_of[inp] = s.id
            out_rows = [
                [
                    f"[{s.dist or 'root'} x{s.parallelism}] {L._explain(s.root)}",
                    sid,
                    parent_of.get(sid, -1),
                ]
                for sid, s in sorted(plan.stages.items())
            ]
            if plan.rule_stats:
                fired = ", ".join(f"{k}:{v}" for k, v in sorted(plan.rule_stats.items()))
                out_rows.append([f"[rules] {fired}", -1, -1])
            return ResultTable(
                columns=["Operator", "Operator_Id", "Parent_Id"],
                rows=out_rows,
            )
        if getattr(stmt, "explain_analyze", False):
            # EXPLAIN ANALYZE: execute with stats collection forced on, then
            # render the plan tree with the merged runtime stats inline
            plan.options["__collect_stats__"] = True
            _, stats_payload = self._run(plan, deadline=deadline)
            merged = merge_stage_stats(stats_payload or [])
            return ResultTable(
                columns=["Operator", "Operator_Id", "Parent_Id"],
                rows=analyze_rows(plan, merged),
            )
        df, stats_payload = self._run(plan, deadline=deadline)
        df = df.astype(object).where(pd.notna(df), None)
        rows = df.values.tolist()
        total_docs = sum(s.n_docs for segs in self.catalog.values() for s in segs)
        result = ResultTable(
            columns=list(plan.visible_names),
            rows=rows,
            total_docs=total_docs,
            time_used_ms=(time.perf_counter() - t0) * 1e3,
        )
        if stats_payload is not None:
            result.stage_stats = merge_stage_stats(stats_payload)
        return result

    def _run(self, plan: L.StagePlan, deadline=None) -> "tuple[pd.DataFrame, list | None]":
        mailbox = MailboxService()
        mailbox.deadline = deadline
        parent_of: dict[int, int] = {}
        for s in plan.stages.values():
            for inp in s.inputs:
                parent_of[inp] = s.id
        n_senders = {sid: s.parallelism for sid, s in plan.stages.items()}
        errors: list[BaseException] = []
        from pinot_tpu.common.trace import active_trace, run_traced

        trace = active_trace()

        def worker_fn(stage: L.Stage, w: int):
            # in-process workers record straight into the request's trace
            # (plain threads don't inherit the submitting contextvars)
            run_traced(
                trace,
                run_stage_worker,
                stage, w, mailbox, plan.stages, self.catalog, n_senders, parent_of,
                errors=errors, options=plan.options,
            )

        threads = []
        for sid in sorted(plan.stages):
            if sid == 0:
                continue
            s = plan.stages[sid]
            for w in range(s.parallelism):
                t = threading.Thread(target=worker_fn, args=(s, w), daemon=True)
                t.start()
                threads.append(t)
        root = plan.stages[0]
        ctx = RunCtx(
            root, 0, mailbox, plan.stages, self.catalog, n_senders, options=plan.options,
            stats=StageStatsCollector(root, 0) if stats_enabled(plan.options) else None,
        )
        try:
            out = exec_node(root.root, ctx)
        finally:
            for t in threads:
                t.join(timeout=30)
        if errors:
            raise errors[0]
        return out, (ctx.stats.payload() if ctx.stats is not None else None)
