"""QueryEngine: end-to-end SQL execution over a set of segments.

Reference parity: this composes, in-process, what Pinot splits across
ServerQueryExecutorV1Impl (pinot-core/.../query/executor/
ServerQueryExecutorV1Impl.java:141, per-segment plan + execute) and
BrokerReduceService (core/query/reduce/BrokerReduceService.java:61, merge).
Per segment it prefers the compiled device path (plan.py + kernels.py) and
falls back to the host executor per DeviceFallback; partials from either path
merge through one reduce (reduce.py). The distributed layers (scatter/gather
over real server processes) wrap this same engine later.
"""

from __future__ import annotations

import time

import numpy as np
import pandas as pd

from pinot_tpu.common.trace import count
from pinot_tpu.query import ast, host_exec, reduce as reduce_mod
from pinot_tpu.query.context import QueryContext, QueryType
from pinot_tpu.query.kernels import dispatch_plan_packed, wait_packed
from pinot_tpu.query.plan import DeviceFallback, SegmentPlan, mark_device_fallback, plan_segment
from pinot_tpu.query.result import ResultTable
from pinot_tpu.query.sql import parse_sql
from pinot_tpu.segment.segment import DeviceSegment, ImmutableSegment


def _is_compact(plan: SegmentPlan) -> bool:
    """Whether a plan groups under plan.group_spec's "groups_compact"."""
    return plan.spec[0] == "agg" and plan.spec[2] is not None and plan.spec[2][0] == "groups_compact"


def _overflowed(disp) -> bool:
    """Whether a launch under the compact group spec, its vector on the host,
    found more combinations of key values than it has slots."""
    return disp[0] == "dev" and _is_compact(disp[1]) and int(disp[2]()[-1]) > disp[1].spec[2][2]


def _describe_spec(spec: tuple, next_id: int, parent: int) -> list[list]:
    """Flatten a compiled plan spec into [operator, id, parent] rows."""
    rows: list[list] = []
    counter = [next_id]

    def emit(label: str, par: int) -> int:
        oid = counter[0]
        counter[0] += 1
        rows.append([label, oid, par])
        return oid

    def walk_filter(f, par: int) -> None:
        kind = f[0]
        if kind in ("and", "or"):
            oid = emit(f"FILTER_{kind.upper()}", par)
            for c in f[1]:
                walk_filter(c, oid)
        elif kind == "not":
            oid = emit("FILTER_NOT", par)
            walk_filter(f[1], oid)
        elif kind == "const":
            emit(f"FILTER_CONST({f[1]})", par)
        else:
            emit(f"FILTER_{kind.upper()}", par)

    def walk_agg(a, par: int) -> None:
        if a[0] in ("masked", "masked_nan_empty"):
            oid = emit("AGG_FILTERED", par)
            walk_filter(a[1], oid)
            walk_agg(a[2], oid)
        else:
            emit(f"AGGREGATE_{a[0].upper()}", par)

    kind = spec[0]
    if kind == "agg":
        _, fspec, gspec, aggs = spec
        walk_filter(fspec, parent)
        if gspec is not None:
            gid = emit(f"GROUP_BY(keys={list(gspec[1])}, ng={gspec[2]})", parent)
            for a in aggs:
                walk_agg(a, gid)
        else:
            for a in aggs:
                walk_agg(a, parent)
    elif kind == "select":
        emit(f"SELECT(columns={len(spec[2])}, limit={spec[3]})", parent)
        walk_filter(spec[1], parent)
    elif kind == "select_ob":
        emit(f"SELECT_ORDER_BY(columns={len(spec[2])}, limit={spec[5]})", parent)
        walk_filter(spec[1], parent)
    return rows


class QueryEngine:
    def __init__(self, segments: list[ImmutableSegment], fast32: bool = False):
        """fast32=True stages DOUBLE columns as float32 (lossy) for speed."""
        self.segments = list(segments)
        self.fast32 = fast32
        self._device: dict[str, DeviceSegment] = {}
        self._mv_cols = {
            name for seg in self.segments for name, ci in seg.columns.items() if ci.is_mv
        }

    def add_segment(self, seg: ImmutableSegment) -> None:
        self.segments.append(seg)
        self._mv_cols |= {name for name, ci in seg.columns.items() if ci.is_mv}

    def _device_seg(self, seg: ImmutableSegment) -> DeviceSegment:
        if not self.fast32:
            # default staging shares the per-segment cache: every engine
            # instance (including ad-hoc ones the multistage leaf path
            # builds per query) reuses ONE staged copy instead of
            # re-uploading columns to HBM
            return seg.to_device_cached()
        ds = self._device.get(seg.name)
        if ds is None:
            ds = seg.to_device(fast32=self.fast32)
            self._device[seg.name] = ds
        return ds

    # ------------------------------------------------------------------

    def make_context(self, sql: str) -> QueryContext:
        """Parse + resolve a query against this engine's segments."""
        from pinot_tpu.query.optimizer import optimize_filter

        stmt = parse_sql(sql)
        self._expand_star(stmt)
        # filter rewrites (QueryOptimizer parity) run here, where the schema
        # is known: range merging must skip MV columns (any-match semantics)
        stmt.where = optimize_filter(stmt.where, mv_cols=self._mv_cols)
        ctx = QueryContext.from_statement(stmt)
        self._compute_hints(ctx)
        return ctx

    def partials(self, ctx: QueryContext, segments: list[ImmutableSegment] | None = None):
        """Server-side half: (per-segment partials, matched doc count,
        scan-path summary).
        (ServerQueryExecutorV1Impl role; the broker reduce consumes these.)"""
        from pinot_tpu.query import scan_stats

        probes = self._new_probe_sink()
        pend, pruned = self._dispatch_all(ctx, segments, probe_sink=probes)
        out, scanned, summary = self._resolve_partials(ctx, pend, pruned)
        scan_stats.merge_probe_sink(summary, probes)
        return out, scanned, summary

    def _new_probe_sink(self):
        """A dict for index-probe entries recorded during dispatch-time
        pruning (bloom membership, geo grid rejects), or None when scan
        observability is off."""
        from pinot_tpu.query import scan_stats

        if scan_stats.enabled() and getattr(self, "scan_obs_enabled", True):
            return {}
        return None

    def _dispatch_all(self, ctx: QueryContext, segments=None, probe_sink=None):
        """Prune + enqueue every segment's device program (non-blocking for
        the fused path; host fallbacks run inline). The ONE dispatch loop
        shared by partials()/submit()/execute(). Pruning-time index probes
        (bloom/geo) collect into `probe_sink` when given."""
        import contextlib

        from pinot_tpu.common.accounting import default_accountant
        from pinot_tpu.common.faults import FAULTS, InjectedFault
        from pinot_tpu.common import runtime
        from pinot_tpu.common.trace import record_span, span, trace_event
        from pinot_tpu.query import pruner, scan_stats

        pend: list = []
        pruned = 0
        prune_ms = 0.0
        cm = (
            scan_stats.collect_probes(probe_sink)
            if probe_sink is not None
            else contextlib.nullcontext()
        )
        # one span around the query's run of dispatches reads the thread clock for all of them: a pair of
        # reads a query where a pair a launch would be thirty (common/trace.py, the second clock)
        with cm, span("server.dispatch_all", cpu=True):
            for seg in self.segments if segments is None else segments:
                default_accountant.checkpoint()
                if ctx.deadline is not None:
                    ctx.deadline.check(f"segment {seg.name}")
                try:
                    FAULTS.maybe_fail("segment.execute")
                except InjectedFault:
                    trace_event("fault.injected", point="segment.execute", segment=seg.name)
                    raise
                t_prune = time.perf_counter()
                reason = pruner.prune_reason(seg, ctx)
                prune_ms += (time.perf_counter() - t_prune) * 1e3
                if reason is not None:
                    # bloom/min-max/geo pruned: contribute a canonical empty
                    # partial; the reject reason rides along for the per-reason
                    # pruning funnel (numSegmentsPrunedByValue/ByBloom/ByGeo)
                    pend.append((seg, ("pruned", pruner.empty_partial(ctx), reason)))
                    pruned += 1
                else:
                    with span("server.dispatch", segment=seg.name) as sp:
                        compiles = runtime.compile_requests()
                        disp = self._dispatch_segment(seg, ctx)
                        if disp[0] == "dev":
                            sp.set_attr("program", disp[2].program)
                            sp.set_attr("rows", disp[2].rows)
                            sp.set_attr("compiled", runtime.compile_requests() != compiles)
                    pend.append((seg, disp))
            # pruning is microseconds a segment, between the dispatches: one ledger entry a query, no span each
            record_span("server.prune", prune_ms)
        return pend, pruned

    def _resolve_partials(self, ctx: QueryContext, pend: list, pruned: int):
        """Wait once for the query's result vectors (their copies to the host
        started at enqueue), then convert every pending dispatch; per-segment
        accounting checkpoint (the QueryKilledError enforcement point) and
        deadline check in the wait as in the conversions, tracing scope, byte
        sampling, segment meters, and the scan-path/heat fold — the ONE
        resolve loop.  Returns (partials, matched_docs, scan_summary)."""
        from pinot_tpu.common.accounting import default_accountant
        from pinot_tpu.common.metrics import ScanMeter, ServerMeter, server_metrics
        from pinot_tpu.common.segment_heat import HEAT
        from pinot_tpu.common.trace import InvocationScope, span, trace_event
        from pinot_tpu.query import scan_stats

        obs = scan_stats.enabled() and getattr(self, "scan_obs_enabled", True)
        summary = scan_stats.new_scan_summary()
        n_post = len(ctx.post_filter_columns) if obs else 0
        out = []
        scanned = 0
        launched = [(seg, disp[2]) for seg, disp in pend if disp[0] == "dev"]

        def checkpoint(i: int) -> None:
            default_accountant.checkpoint()
            if ctx.deadline is not None:
                ctx.deadline.check(f"segment {launched[i][0].name}")

        wait_packed([res for _, res in launched], checkpoint)
        # the compact launches whose groups passed their slots (the rows decide): enqueued again together under the
        # plan they had before that kind, and waited for once more
        again = [i for i, (_, disp) in enumerate(pend) if _overflowed(disp)]
        if again:
            for i in again:
                seg, disp = pend[i]
                pend[i] = (seg, self._launch_again(seg, ctx, disp))
            wait_packed([pend[i][1][2] for i in again if pend[i][1][0] == "dev"])
        for seg, disp in pend:
            if disp[0] == "pruned":
                out.append(disp[1])  # no scan, no sample
                if obs and len(disp) > 2:
                    scan_stats.fold_prune(summary, disp[2])
                continue
            default_accountant.checkpoint()
            if ctx.deadline is not None:
                ctx.deadline.check(f"segment {seg.name}")
            with InvocationScope(f"segment:{seg.name}") as scope, span("server.unpack", cpu=True, segment=seg.name) as unpack:
                if obs:
                    with scan_stats.collect_probes(summary["indexProbeEntries"]):
                        partial, matched = self._finish_segment(seg, ctx, disp)
                else:
                    partial, matched = self._finish_segment(seg, ctx, disp)
                scope.set_attr("numDocsMatched", int(matched))
            # per-segment CPU attribution (ThreadResourceUsageAccountant
            # sampleThreadCPUTime parity): the span's thread time excludes what
            # this thread spent descheduled or blocked
            default_accountant.sample(
                segments=1,
                allocated_bytes=seg.size_bytes,
                cpu_ns=int(unpack.cpu_ms * 1e6),
            )
            if obs:
                seg_stats = scan_stats.segment_scan_stats(ctx, seg, self._scan_mode(disp), int(matched), n_post)
                scan_stats.fold_segment_stats(summary, seg_stats)
                HEAT.record(
                    ctx.table,
                    seg.name,
                    docs_scanned=int(matched),
                    bytes_touched=seg.size_bytes,
                    device_ms=unpack.ms + (disp[2].wait_ms if disp[0] == "dev" else 0.0),
                )
                if seg_stats["fullScanFallbacks"]:
                    # offender hop for the roofline runbook: which predicate
                    # full-scanned despite a declared usable index
                    trace_event(
                        "scan.fullScan",
                        segment=seg.name,
                        columns=",".join(
                            sorted({f["column"] for f in seg_stats["fullScanFallbacks"]})
                        ),
                    )
            out.append(partial)
            scanned += int(matched)
        m = server_metrics()
        m.meter(ServerMeter.NUM_SEGMENTS_QUERIED).mark(len(pend) - pruned)
        if pruned:
            m.meter(ServerMeter.NUM_SEGMENTS_PRUNED).mark(pruned)
        if obs:
            tbl = ctx.table
            if summary["entriesInFilter"]:
                m.meter(ScanMeter.ENTRIES_IN_FILTER, table=tbl).mark(summary["entriesInFilter"])
            if summary["entriesPostFilter"]:
                m.meter(ScanMeter.ENTRIES_POST_FILTER, table=tbl).mark(
                    summary["entriesPostFilter"]
                )
            by_path: dict[str, int] = {}
            for key, cnt in summary["predicates"].items():
                path = key.rsplit(":", 1)[1]
                by_path[path] = by_path.get(path, 0) + cnt
            for path, cnt in by_path.items():
                m.meter(ScanMeter.PREDICATES, table=tbl, index=path).mark(cnt)
            n_fallback = sum(summary["fullScanFallbacks"].values())
            if n_fallback:
                m.meter(ScanMeter.FULL_SCAN_FALLBACK, table=tbl).mark(n_fallback)
        return out, scanned, summary

    def partials_iter(self, ctx: QueryContext, segments: list[ImmutableSegment] | None = None):
        """Per-segment streaming variant of partials(): yields
        (seg, partial, matched, scan_stats_or_None) as each segment finishes,
        so callers can frame results out incrementally and stop early
        (GrpcQueryServer.submit streaming parity,
        core/transport/grpc/GrpcQueryServer.java:65,165)."""
        from pinot_tpu.common.faults import FAULTS, InjectedFault
        from pinot_tpu.common.segment_heat import HEAT
        from pinot_tpu.common.trace import trace_event
        from pinot_tpu.query import pruner, scan_stats

        obs = scan_stats.enabled() and getattr(self, "scan_obs_enabled", True)
        n_post = len(ctx.post_filter_columns) if obs else 0
        for seg in self.segments if segments is None else segments:
            if ctx.deadline is not None:
                ctx.deadline.check(f"segment {seg.name}")
            try:
                FAULTS.maybe_fail("segment.execute")
            except InjectedFault:
                trace_event("fault.injected", point="segment.execute", segment=seg.name)
                raise
            if not pruner.can_match(seg, ctx):
                continue
            disp = self._dispatch_segment(seg, ctx)
            t_wall = time.perf_counter()
            partial, matched = self._finish_segment(seg, ctx, disp)
            seg_stats = None
            if obs:
                seg_stats = scan_stats.segment_scan_stats(ctx, seg, self._scan_mode(disp), int(matched), n_post)
                HEAT.record(
                    ctx.table,
                    seg.name,
                    docs_scanned=int(matched),
                    bytes_touched=seg.size_bytes,
                    device_ms=(time.perf_counter() - t_wall) * 1e3,
                )
            yield seg, partial, int(matched), seg_stats

    @staticmethod
    def reduce(ctx: QueryContext, partials: list) -> list[list]:
        """Broker-side half: merge partials into final rows."""
        qt = ctx.query_type
        if qt == QueryType.AGGREGATION:
            return reduce_mod.reduce_aggregation(ctx, partials)
        if qt == QueryType.GROUP_BY:
            return reduce_mod.reduce_group_by(ctx, partials)
        if qt == QueryType.DISTINCT:
            return reduce_mod.reduce_distinct(ctx, partials)
        if qt == QueryType.SELECTION_ORDER_BY:
            return reduce_mod.reduce_selection_order_by(ctx, partials)
        return reduce_mod.reduce_selection(ctx, partials)

    def explain(self, ctx: QueryContext) -> ResultTable:
        """EXPLAIN PLAN FOR: the operator tree the query would execute
        (ExplainPlanQueryExecutor parity) as [Operator, Operator_Id,
        Parent_Id] rows, based on the first segment's lowering."""
        rows: list[list] = [["BROKER_REDUCE(" + ctx.query_type.value + ")", 0, -1]]
        if not self.segments:
            return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows)
        seg = self.segments[0]
        st = seg.extras.get("startree")
        from pinot_tpu.query.context import null_handling_enabled

        if (
            st is not None
            and seg.extras.get("valid_docs") is None
            and not (null_handling_enabled(ctx.options) and seg.extras.get("null"))
        ):
            from pinot_tpu.query import startree_exec

            if any(startree_exec.matches(ctx, t) for t in st):
                rows.append(["STARTREE_SWAP(pre-aggregated table scan)", 1, 0])
                rows.extend(self._filter_attribution_rows(ctx, seg, "startree", rows))
                return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows)
        try:
            plan = plan_segment(seg, ctx)
            rows.append(["DEVICE_FUSED_PROGRAM(segment=" + seg.name + ")", 1, 0])
            rows.extend(_describe_spec(plan.spec, next_id=2, parent=1))
            rows.extend(self._filter_attribution_rows(ctx, seg, "device", rows))
        except DeviceFallback as e:
            rows.append([f"HOST_EXECUTOR(reason={e})", 1, 0])
            rows.extend(self._filter_attribution_rows(ctx, seg, "host", rows))
        return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows)

    @staticmethod
    def _filter_attribution_rows(ctx: QueryContext, seg, mode: str, rows: list[list]) -> list[list]:
        """Scan-path attribution lines for EXPLAIN: one FILTER_<PATH>(col)
        row per filter predicate, parented at the execution node (id 1) —
        which index class (or FULL_SCAN) serves each predicate under the
        mode the first segment would execute in."""
        from pinot_tpu.query import scan_stats

        out = []
        nid = max(r[1] for r in rows) + 1
        for leaf in scan_stats.filter_leaves(ctx.filter):
            col, path, _entries = scan_stats.classify_leaf(leaf, seg, mode)
            out.append([f"FILTER_{path}({col})", nid, 1])
            nid += 1
        return out

    def _explain_analyze(self, ctx: QueryContext) -> ResultTable:
        """EXPLAIN ANALYZE: run the query under a private trace and annotate
        the EXPLAIN tree with the runtime stats — the single-stage path
        reuses the per-segment InvocationScope spans instead of a separate
        stats plane."""
        from pinot_tpu.common.trace import start_trace

        base = self.explain(ctx)
        t0 = time.perf_counter()
        with start_trace("explain-analyze") as tr:
            pend, pruned = self._dispatch_all(ctx)
            partials, scanned, scan = self._resolve_partials(ctx, pend, pruned)
            out_rows = self.reduce(ctx, partials)
        wall_ms = (time.perf_counter() - t0) * 1e3
        rows = [list(r) for r in base.rows]
        rows[0][0] += (
            f" (rows={len(out_rows)}, docsScanned={int(scanned)},"
            f" segmentsPruned={pruned},"
            f" entriesInFilter={scan['entriesInFilter']},"
            f" entriesPostFilter={scan['entriesPostFilter']}, timeMs={wall_ms:.2f})"
        )
        # filter-plan attribution rows gain the measured entry counts
        from pinot_tpu.query import scan_stats

        for r in rows:
            label = r[0]
            if label.startswith("FILTER_") and label.endswith(")") and "(" in label:
                path, _, col = label[len("FILTER_") : -1].partition("(")
                if path in scan_stats.ALL_PATHS:
                    entries = scan.get("predicateEntries", {}).get(f"{col}:{path}", 0)
                    r[0] = f"{label} (entries={entries})"
        # per-segment spans become children of the execution root (the
        # DEVICE_FUSED_PROGRAM / HOST_EXECUTOR / STARTREE_SWAP row)
        exec_parent = rows[1][1] if len(rows) > 1 else rows[0][1]
        nid = max(r[1] for r in rows) + 1
        for span in tr.to_dict()["spans"]:
            if not span["name"].startswith("segment:"):
                continue
            matched = span.get("attrs", {}).get("numDocsMatched", 0)
            rows.append(
                [
                    f"SEGMENT_SCAN({span['name'][len('segment:'):]},"
                    f" docsMatched={matched}, wallMs={span['durationMs']})",
                    nid,
                    exec_parent,
                ]
            )
            nid += 1
        return ResultTable(columns=["Operator", "Operator_Id", "Parent_Id"], rows=rows)

    def execute(self, sql: str) -> ResultTable:
        """Synchronous execute = submit + immediate resolve (one code path,
        same per-segment accounting/tracing/meters either way)."""
        return self.submit(sql)()

    def submit(self, sql: str):
        """Asynchronous submit (QueryScheduler.submit ListenableFuture
        parity, core/query/scheduler/QueryScheduler.java): plans the query
        and ENQUEUES every per-segment device program without the
        device->host sync (jax dispatch is non-blocking; each result
        vector's copy to the host starts behind its program, see
        kernels.dispatch_plan_packed), returning a zero-argument resolve()
        that performs the query's one wait, broker reduce, and ResultTable
        build. Dispatching several queries before resolving any overlaps
        their device round trips. execute() is exactly submit()() — one
        path, same instrumentation."""
        t0 = time.perf_counter()
        ctx = self.make_context(sql)
        if getattr(ctx.statement, "explain", False):
            return lambda: self.explain(ctx)
        if getattr(ctx.statement, "explain_analyze", False):
            return lambda: self._explain_analyze(ctx)
        probes = self._new_probe_sink()
        pend, pruned = self._dispatch_all(ctx, probe_sink=probes)

        def resolve() -> ResultTable:
            from pinot_tpu.query import scan_stats

            partials, scanned, scan = self._resolve_partials(ctx, pend, pruned)
            scan_stats.merge_probe_sink(scan, probes)
            rows = self.reduce(ctx, partials)
            by_reason = scan["prunedByReason"]
            return reduce_mod.build_result(
                ctx,
                rows,
                num_docs_scanned=int(scanned),
                total_docs=sum(s.n_docs for s in self.segments),
                num_segments_queried=len(self.segments),
                num_segments_pruned=pruned,
                num_segments_pruned_by_value=by_reason.get("value", 0),
                num_segments_pruned_by_bloom=by_reason.get("bloom", 0),
                num_segments_pruned_by_geo=by_reason.get("geo", 0),
                num_entries_scanned_in_filter=scan["entriesInFilter"],
                num_entries_scanned_post_filter=scan["entriesPostFilter"],
                scan_profile=scan,
                time_used_ms=(time.perf_counter() - t0) * 1e3,
            )

        return resolve

    # ------------------------------------------------------------------

    def _expand_star(self, stmt) -> None:
        from pinot_tpu.query.context import expand_star

        expand_star(stmt, self.segments[0].schema if self.segments else None)

    # ------------------------------------------------------------------

    def _compute_hints(self, ctx: QueryContext) -> None:
        """Cross-segment planning hints: global [min,max] bounds per
        PERCENTILEEST aggregation so all segments build mergeable histograms
        over identical bin edges."""
        for a in ctx.aggregations:
            if a.func != "percentileest" or not isinstance(a.arg, ast.Identifier):
                continue
            col = a.arg.name
            los, his = [], []
            ok = True
            for seg in self.segments:
                ci = seg.columns.get(col)
                if ci is None or not isinstance(ci.stats.min_value, (int, float)):
                    ok = False
                    break
                los.append(float(ci.stats.min_value))
                his.append(float(ci.stats.max_value))
            if ok and los:
                ctx.hints.setdefault("est_bounds", {})[a.name] = (min(los), max(his))

    def _execute_segment(self, seg: ImmutableSegment, ctx: QueryContext):
        """Returns (partial, matched_docs) for one segment."""
        return self._finish_segment(seg, ctx, self._dispatch_segment(seg, ctx))

    def _dispatch_segment(self, seg: ImmutableSegment, ctx: QueryContext):
        """Async half of segment execution: plan + ENQUEUE the fused device
        program without any device->host sync. Returns ("ready", partial,
        matched, mode) when the segment resolved host-side (a host fallback),
        else ("dev", plan, result, vmask, swap) with `result` (a
        kernels.PackedResult) still in flight and on its way to the host —
        _resolve_partials waits for a query's results together,
        _finish_segment for its own where none did. `swap` is None, or the
        star-tree swap (startree_exec.StarSwap) whose program was launched in
        the segment's place: a star-answered segment is enqueued like any
        other. Splitting here is what lets a query enqueue every segment
        before it waits for any."""
        valid = seg.extras.get("valid_docs")
        from pinot_tpu.query.context import null_handling_enabled

        if (
            seg.extras.get("startree")
            and valid is None
            # star-tree pre-agg tables bake null-placeholder rows in; under
            # enableNullHandling the per-doc path must run instead
            and not (null_handling_enabled(ctx.options) and seg.extras.get("null"))
        ):
            # star-tree pre-aggregates over ALL docs; unusable under upsert
            # visibility (invalidated docs are baked into the agg table)
            from pinot_tpu.query import startree_exec

            swap = startree_exec.swap(seg, ctx)
            if swap is not None:
                count("starTreeSegments")
                count("starTreeRecords", swap.seg.n_docs)
                return self._launch(seg, ctx, None, swap)
        # plan_segment threads valid_docs into the kernel as a docmask
        # operand, so upsert tables run the fused device path too
        return self._launch(seg, ctx, valid(seg.n_docs) if valid is not None else None, None)

    def _launch(self, seg: ImmutableSegment, ctx: QueryContext, vmask, swap, compact: bool = True):
        """Plan and enqueue one segment, or the star table of `swap` in its
        place: `_dispatch_segment`'s return. `compact=False` is the second
        launch of a segment whose groups passed the compact slots
        (plan.group_spec's "groups_compact"), under the plan it had before
        that kind."""
        target, tctx = (seg, ctx) if swap is None else (swap.seg, swap.ctx)
        try:
            plan = plan_segment(target, tctx, valid_mask=vmask, compact=compact)
        except DeviceFallback as e:
            # the same meter the multistage leaf marks: a segment that left
            # the device path is counted, whichever engine it ran under
            mark_device_fallback(e, f"segment {target.name}")
            partial, matched = self._host_segment(target, tctx, extra_mask=vmask)
            if swap is None:
                return ("ready", partial, matched, "host")
            # the star table on the host: exact, and still the few records in the raw rows' place;
            # the trailing element = execution mode, for scan-path attribution
            return ("ready", swap.convert(ctx, partial), matched, "startree")
        if _is_compact(plan):
            count("groupCompactSegments")
        return ("dev", plan, dispatch_plan_packed(plan, self._device_seg(target)), vmask, swap)

    def _launch_again(self, seg: ImmutableSegment, ctx: QueryContext, disp):
        """An overflowed compact launch, enqueued again under the plan the
        segment had before that kind: the dense space up to
        plan.MAX_DENSE_GROUPS, the sort-compaction path past it. Exact either
        way; the first launch's result is dropped."""
        count("groupCompactFallbacks")
        return self._launch(seg, ctx, disp[3], disp[4], compact=False)

    @staticmethod
    def _scan_mode(disp) -> str:
        """How a dispatch executed, for scan-path attribution: "device", "host" or "startree"."""
        if disp[0] == "dev":
            return "startree" if disp[4] is not None else "device"
        return disp[3]

    def _finish_segment(self, seg: ImmutableSegment, ctx: QueryContext, disp):
        """Sync half: convert an in-flight dispatch to (partial, matched)."""
        if disp[0] == "ready":
            return disp[1], disp[2]
        _, plan, unpack, vmask, swap = disp
        if swap is not None:
            # the star program's result in the star context, then mapped back to the layout the query asked for
            partial, matched = self._finish_segment(swap.seg, swap.ctx, ("dev", plan, unpack, vmask, None))
            return swap.convert(ctx, partial), matched
        out = unpack()  # waits only where the caller has not waited for the query's vectors already
        qt = ctx.query_type
        if qt == QueryType.AGGREGATION:
            matched, parts = out
            return self._convert_agg(seg, ctx, plan, parts), int(matched)
        if qt in (QueryType.GROUP_BY, QueryType.DISTINCT):
            gspec = plan.spec[2]
            if gspec is not None and gspec[0] in ("groups_sparse", "groups_compact"):
                matched, counts, parts, slot_gids, n_present = out
                if int(n_present) > gspec[2]:
                    # more present groups than slots: the kernel's clipped
                    # slots collided — results unusable. A sparse segment
                    # reruns host; a compact one is launched again (here for
                    # `partials_iter` and `_execute_segment`: a query's
                    # batched path has done so already, `_resolve_partials`)
                    if gspec[0] == "groups_compact":
                        return self._finish_segment(seg, ctx, self._launch_again(seg, ctx, disp))
                    return self._host_segment(seg, ctx, extra_mask=vmask)
                return (
                    self._convert_groups(
                        seg, ctx, plan, np.asarray(counts), parts, dense_gids=np.asarray(slot_gids)
                    ),
                    int(matched),
                )
            matched, counts, parts = out
            return self._convert_groups(seg, ctx, plan, np.asarray(counts), parts), int(matched)
        if qt == QueryType.SELECTION:
            matched, outs = out
            return self._convert_selection(seg, ctx, plan, int(matched), outs), int(matched)
        # SELECTION_ORDER_BY
        matched, keys_out, outs = out
        return (
            self._convert_selection_ob(seg, ctx, plan, int(matched), np.asarray(keys_out), outs),
            int(matched),
        )

    def _host_segment(self, seg: ImmutableSegment, ctx: QueryContext, extra_mask=None):
        from pinot_tpu.query.context import null_handling_enabled

        if null_handling_enabled(ctx.options):
            # three-valued WHERE: predicates over null inputs are UNKNOWN,
            # only definitely-true rows survive (Kleene combination)
            mask = host_exec.filter_mask_null_aware(seg, ctx.filter)
        else:
            mask = host_exec.filter_mask(seg, ctx.filter)
        if extra_mask is not None:
            mask = mask & extra_mask
        matched = int(mask.sum())
        qt = ctx.query_type
        k = ctx.limit + ctx.offset
        if qt == QueryType.AGGREGATION:
            return host_exec.agg_partials(seg, ctx, mask), matched
        if qt == QueryType.GROUP_BY:
            return host_exec.group_frame(seg, ctx, mask), matched
        if qt == QueryType.DISTINCT:
            return host_exec.distinct_frame(seg, ctx, mask), matched
        if qt == QueryType.SELECTION_ORDER_BY:
            return host_exec.selection_ob_frame(seg, ctx, mask, k), matched
        return host_exec.selection_frame(seg, ctx, mask, k), matched

    # -- device output -> host partial conversions ----------------------

    def _convert_agg(self, seg, ctx, plan: SegmentPlan, parts) -> list:
        out = []
        for a, spec_entry, p in zip(ctx.aggregations, plan.spec[3], parts):
            while spec_entry[0] in ("masked", "masked_nan_empty"):  # FILTER(WHERE)/null wrapper
                spec_entry = spec_entry[2]
            if a.func in ("count", "countmv"):
                out.append(int(p))
            elif a.func in ("distinctcount", "distinctcountbitmap", "distinctcountmv"):
                col = spec_entry[1]
                ci = seg.columns[col]
                presence = np.asarray(p)[: ci.cardinality]
                vals = ci.dictionary.values[np.nonzero(presence)[0]]
                out.append(set(vals.tolist()))
            elif a.func in ("funnelcount", "funnelcompletecount"):
                # (K, pad) presence rows -> per-step value sets (the host
                # partial format funnel.merge/finalize consume)
                col = spec_entry[1]
                ci = seg.columns[col]
                pres = np.asarray(p)[:, : ci.cardinality]
                vals = ci.dictionary.values
                out.append(
                    [set(vals[np.nonzero(pres[k])[0]].tolist()) for k in range(pres.shape[0])]
                )
            elif a.func == "distinctcounthll":
                out.append(np.asarray(p))
            elif a.func == "percentileest":
                lo, hi = ctx.hints["est_bounds"][a.name]
                out.append((np.asarray(p), lo, hi))
            elif a.func in ("avg", "avgmv", "minmaxrange"):
                out.append((float(p[0]), int(p[1]) if a.func in ("avg", "avgmv") else float(p[1])))
            else:
                out.append(float(p))
        return out

    def _convert_groups(
        self, seg, ctx, plan: SegmentPlan, counts: np.ndarray, parts, dense_gids=None
    ) -> pd.DataFrame:
        from pinot_tpu.query.plan import group_strides

        pg = np.nonzero(counts)[0]
        cards = [ci.cardinality for _, ci in plan.group_cols]
        strides = group_strides(cards, np.int64)
        # sparse compaction: slot -> its 64-bit dense gid; dense: slot IS gid
        gids = dense_gids[pg] if dense_gids is not None else pg
        data = {}
        for i, (col, ci) in enumerate(plan.group_cols):
            ids = (gids // strides[i]) % max(cards[i], 1)
            vals = ci.dictionary.get_many(ids)
            data[f"k{i}"] = vals.astype(str) if vals.dtype == object else vals
        if ctx.query_type == QueryType.DISTINCT:
            return pd.DataFrame(data)
        aggs_spec = plan.spec[3]
        for i, (a, spec_entry, p) in enumerate(zip(ctx.aggregations, aggs_spec, parts)):
            while spec_entry[0] in ("masked", "masked_nan_empty"):
                spec_entry = spec_entry[2]
            if a.func in ("count", "countmv"):
                data[f"a{i}p0"] = np.asarray(p)[pg]
            elif a.func in ("avg", "avgmv", "minmaxrange"):
                data[f"a{i}p0"] = np.asarray(p[0])[pg]
                data[f"a{i}p1"] = np.asarray(p[1])[pg]
            elif a.func in ("distinctcount", "distinctcountbitmap"):
                # per-group presence rows -> exact value sets (the v1
                # mergeable partial format)
                ci = seg.columns[spec_entry[1]]
                pres = np.asarray(p)[pg][:, : ci.cardinality]
                vals = ci.dictionary.values
                cells = np.empty(len(pg), dtype=object)
                for j in range(len(pg)):
                    cells[j] = set(vals[np.nonzero(pres[j])[0]].tolist())
                data[f"a{i}p0"] = cells
            elif a.func == "distinctcounthll":
                regs = np.asarray(p)[pg]
                cells = np.empty(len(pg), dtype=object)
                for j in range(len(pg)):
                    cells[j] = regs[j]
                data[f"a{i}p0"] = cells
            elif a.func == "percentileest":
                lo, hi = ctx.hints["est_bounds"][a.name]
                hists = np.asarray(p)[pg]
                cells = np.empty(len(pg), dtype=object)
                for j in range(len(pg)):
                    cells[j] = (hists[j].astype(np.int64), lo, hi)
                data[f"a{i}p0"] = cells
            else:
                data[f"a{i}p0"] = np.asarray(p)[pg]
        return pd.DataFrame(data)

    def _convert_selection(self, seg, ctx, plan: SegmentPlan, matched: int, outs) -> pd.DataFrame:
        n = min(matched, plan.spec[3])
        data = {}
        for i, (dec, o) in enumerate(zip(plan.select_decode, outs)):
            v = np.asarray(o)[:n]
            data[f"c{i}"] = self._decode(seg, dec, v)
        return pd.DataFrame(data)

    def _convert_selection_ob(self, seg, ctx, plan: SegmentPlan, matched, keys_out, outs) -> pd.DataFrame:
        n = min(matched, plan.spec[5])
        data = {}
        kspec = plan.spec[3]
        keys = keys_out[:n]
        if plan.ob_decomp:
            # composite rank -> per-key sort values (most significant first)
            comp = keys.astype(np.int64)
            strides = [1] * len(plan.ob_decomp)
            for i in range(len(plan.ob_decomp) - 2, -1, -1):
                strides[i] = strides[i + 1] * plan.ob_decomp[i + 1][1]
            for i, (col, card, desc, kind, off) in enumerate(plan.ob_decomp):
                rank = (comp // strides[i]) % card
                if desc:
                    rank = card - 1 - rank
                if kind == "ids":
                    kv = seg.columns[col].dictionary.get_many(rank)
                    data[f"__key{i}"] = kv.astype(str) if kv.dtype == object else kv
                else:
                    data[f"__key{i}"] = rank + off
        elif kspec[0] == "ids":
            ci = seg.columns[kspec[1]]
            kv = ci.dictionary.get_many(keys.astype(np.int64))
            data["__key0"] = kv.astype(str) if kv.dtype == object else kv
        else:
            data["__key0"] = keys
        for i, (dec, o) in enumerate(zip(plan.select_decode, outs)):
            v = np.asarray(o)[:n]
            data[f"c{i}"] = self._decode(seg, dec, v)
        return pd.DataFrame(data)

    def _decode(self, seg, dec, v: np.ndarray) -> np.ndarray:
        kind = dec[0]
        if kind == "dict":
            ci = seg.columns[dec[1]]
            vals = ci.dictionary.get_many(v.astype(np.int64))
            return vals.astype(str) if vals.dtype == object else vals
        if kind == "virt":
            # virtual columns: v carries the selected doc ids
            if dec[1] == "$docId":
                return v.astype(np.int64)
            if dec[1] == "$segmentName":
                return np.full(len(v), seg.name, dtype=object)
            import socket

            return np.full(len(v), socket.gethostname(), dtype=object)
        return v
