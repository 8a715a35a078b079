"""Multi-device execution: segments sharded over a jax Mesh, partial
aggregates merged via ICI collectives.

Reference parity: this replaces BOTH of Pinot's data-parallel tiers at once —
intra-server combine (BaseCombineOperator.java:92-119 fanning segment plans
across executor threads) and the broker scatter/gather across servers
(QueryRouter.submitQuery, pinot-core/.../transport/QueryRouter.java:89) — for
the single-pod case: segments live stacked and sharded across devices, each
device runs the fused per-segment kernel vmapped over its local segments,
merges partials locally, then psum/pmin/pmax over the `seg` mesh axis replaces
the DataTable network hop. Cross-host scatter/gather over DCN (real broker /
server processes) layers on top of this in the cluster module.

Unlike the per-segment engine (per-segment dictionaries), a ShardedTable uses
TABLE-LEVEL dictionaries so group ids and LUT indices align across devices and
partials combine with pure collectives — the analog of Pinot's partition-aware
replica groups enabling streamlined merges.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pinot_tpu.common.kernel_obs import KERNELS
from pinot_tpu.common.types import Schema
from pinot_tpu.query.context import QueryContext, QueryType
from pinot_tpu.query.kernels import build_fn, plan_columns
from pinot_tpu.query.plan import SegmentPlan, plan_segment
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.segment import ImmutableSegment, padded_len


def make_mesh(devices=None, axis: str = "seg") -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis,))


@dataclass
class ShardedTable:
    """A logical table stacked as (n_segments, padded_docs) device arrays,
    sharded over the mesh 'seg' axis. `proto` is a host-side segment carrying
    the shared table-level dictionaries/stats used for plan lowering."""

    proto: ImmutableSegment
    mesh: Mesh
    arrays: dict[str, Any]  # col -> jax.Array (S, P), sharded over axis 0
    n_docs: Any  # (S,) int32, sharded over axis 0
    n_segments: int
    padded: int
    total_docs: int


def build_sharded_table(
    schema: Schema,
    data: dict[str, np.ndarray],
    mesh: Mesh,
    rows_per_segment: int | None = None,
    table_config=None,
) -> ShardedTable:
    """Split columnar data into equal segments, build ONE table-level
    dictionary set, stack forward arrays and shard them over the mesh."""
    n = len(next(iter(data.values())))
    n_dev = mesh.devices.size
    if rows_per_segment is None:
        # one segment per device by default
        rows_per_segment = (n + n_dev - 1) // n_dev
    n_seg = max(1, (n + rows_per_segment - 1) // rows_per_segment)
    # segments must be a multiple of device count for even sharding
    if n_seg % n_dev:
        n_seg += n_dev - (n_seg % n_dev)
    rows_per_segment = (n + n_seg - 1) // n_seg

    # table-level encoding via one builder pass over the whole table
    proto = SegmentBuilder(schema, table_config).build(data, "proto")
    pad = padded_len(rows_per_segment)
    has_mv = any(ci.is_mv for ci in proto.columns.values())
    if has_mv and pad == rows_per_segment:
        # MV flat-padding positions carry docid pad-1, which must be an
        # ALWAYS-invalid doc slot — guarantee one exists
        pad = padded_len(rows_per_segment + 1)

    arrays = {}
    axis = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(axis, None))
    for col, ci in proto.columns.items():
        if ci.is_mv:
            # flattened-MV staging: per-segment flat id slices + LOCAL
            # owning-doc ids, both padded to one F_pad. Padding docids point
            # at slot pad-1 (invalid in every segment), so padding values
            # can never contribute to a doc mask or an aggregate.
            off = ci.offsets()
            fdoc = ci.flat_docids()
            ids = ci.forward
            seg_bounds = [
                (int(off[min(s * rows_per_segment, n)]), int(off[min((s + 1) * rows_per_segment, n)]))
                for s in range(n_seg)
            ]
            f_pad = padded_len(max(1, max(b - a for a, b in seg_bounds)))
            st_ids = np.zeros((n_seg, f_pad), dtype=ids.dtype)
            st_docs = np.full((n_seg, f_pad), pad - 1, dtype=np.int32)
            for sidx, (a, b) in enumerate(seg_bounds):
                st_ids[sidx, : b - a] = ids[a:b]
                st_docs[sidx, : b - a] = fdoc[a:b] - sidx * rows_per_segment
            arrays[col] = jax.device_put(st_ids, sharding)
            arrays[f"{col}!docs"] = jax.device_put(st_docs, sharding)
            continue
        fwd = ci.forward
        if fwd.dtype == np.int64 and len(fwd):
            # lossless narrowing (DeviceSegment.to_device parity): i64 is
            # software-emulated on TPU, i32 unlocks the native integer paths
            lo, hi = int(fwd.min()), int(fwd.max())
            if np.iinfo(np.int32).min <= lo and hi <= np.iinfo(np.int32).max:
                fwd = fwd.astype(np.int32)
                # keep the proto's dtype in sync: plan-time literal range
                # checks (_raw_compare) consult proto.forward.dtype, and an
                # i64 literal outside i32 range must be statically decided,
                # not silently wrapped by the kernel's o.astype(v.dtype)
                ci.forward = fwd
        stacked = np.zeros((n_seg, pad), dtype=fwd.dtype)
        for s in range(n_seg):
            chunk = fwd[s * rows_per_segment : (s + 1) * rows_per_segment]
            stacked[s, : len(chunk)] = chunk
        arrays[col] = jax.device_put(stacked, sharding)
    n_docs = np.asarray(
        [max(0, min(rows_per_segment, n - s * rows_per_segment)) for s in range(n_seg)],
        dtype=np.int32,
    )
    n_docs = jax.device_put(n_docs, NamedSharding(mesh, P(axis)))
    return ShardedTable(
        proto=proto,
        mesh=mesh,
        arrays=arrays,
        n_docs=n_docs,
        n_segments=n_seg,
        padded=pad,
        total_docs=n,
    )


# ---------------------------------------------------------------------------
# partial combination rules (local reduce over segment axis, then collective)
# ---------------------------------------------------------------------------


def _combine_tree(spec: tuple, matched, counts, parts, axis_name: str | None, local_axis: bool = True):
    """Reduce per-segment partials over the leading axis (when the kernel ran
    vmapped; the flat path sets local_axis=False), then a collective over the
    mesh axis."""

    def red_sum(x):
        y = jnp.sum(x, axis=0) if local_axis else x
        return jax.lax.psum(y, axis_name) if axis_name else y

    # min/max/or collectives ride all_gather + local reduce instead of
    # pmin/pmax: for the emulated f64 these partials are held in, the TPU
    # compiler lowers only Sum all-reduces (libtpu 0.0.34 on four v5e chips:
    # "UNIMPLEMENTED: Supported lowering only of Sum all reduce" for an f64
    # pmin). Partials are small, so gathering then reducing costs about the
    # same ICI bytes as an all-reduce.
    def red_min(x):
        y = jnp.min(x, axis=0) if local_axis else x
        return jnp.min(jax.lax.all_gather(y, axis_name), axis=0) if axis_name else y

    def red_max(x):
        y = jnp.max(x, axis=0) if local_axis else x
        return jnp.max(jax.lax.all_gather(y, axis_name), axis=0) if axis_name else y

    def red_or(x):
        y = jnp.max(x.astype(jnp.int32), axis=0) if local_axis else x.astype(jnp.int32)
        if axis_name:
            y = jnp.max(jax.lax.all_gather(y, axis_name), axis=0)
        return y.astype(bool)

    def red_nansum(x):
        # masked_nan_empty SUM partials: NaN = "no non-null rows on this
        # shard/segment" — skip it in the combine, but keep NaN when EVERY
        # contribution is NaN so the reduce still finalizes to NULL
        seen = (~jnp.isnan(x)).astype(jnp.int32)
        s = jnp.where(jnp.isnan(x), 0.0, x)
        if local_axis:
            s, seen = jnp.sum(s, axis=0), jnp.sum(seen, axis=0)
        if axis_name:
            s, seen = jax.lax.psum(s, axis_name), jax.lax.psum(seen, axis_name)
        return jnp.where(seen == 0, jnp.nan, s)

    aggs = spec[3]
    out_parts = []
    for a, p in zip(aggs, parts):
        kind = a[0]
        nan_empty = False
        while kind in ("masked", "masked_nan_empty"):  # FILTER(WHERE)/null wrapper: combine by inner kind
            nan_empty = nan_empty or kind == "masked_nan_empty"
            a = a[2]
            kind = a[0]
        if kind == "sum" and nan_empty:
            out_parts.append(red_nansum(p))
        elif kind in ("count", "sum", "avg", "mv_count", "mv_sum", "mv_avg"):
            out_parts.append(jax.tree.map(red_sum, p))
        elif kind in ("min", "mv_min"):
            out_parts.append(red_min(p))
        elif kind in ("max", "mv_max"):
            out_parts.append(red_max(p))
        elif kind == "minmaxrange":
            out_parts.append((red_min(p[0]), red_max(p[1])))
        elif kind in ("distinct_ids", "mv_distinct_ids"):
            out_parts.append(red_or(p))
        elif kind == "hll":
            out_parts.append(red_max(p))
        elif kind == "hist":
            out_parts.append(red_sum(p))
        else:
            raise AssertionError(kind)
    m = red_sum(matched)
    c = red_sum(counts) if counts is not None else None
    return m, c, tuple(out_parts)


@lru_cache(maxsize=256)
def _sharded_kernel(spec: tuple, mesh: Mesh, axis: str, doc_pad: int):
    """vmapped per-segment kernel + local reduce + ICI collective, wrapped in
    shard_map over the segment axis and jitted.

    The jitted function returns ONE packed float64 vector holding every
    output leaf (matched count, group counts, agg partials). A query result
    then costs a single device->host transfer instead of one blocking sync
    per output array (the DataTable-bytes-in-one-response analog).

    Returns (jitted_fn, unpack) where unpack(np_vector) restores the
    original (matched[, counts], parts) tree with proper dtypes."""
    from pinot_tpu.query.kernels import build_masked_fn

    base = build_masked_fn(spec)
    gspec = spec[2]
    grouped = gspec is not None
    sparse = grouped and gspec[0] == "groups_sparse"
    pack_meta: dict = {}

    def _flatten_local(cols, n_docs):
        # cols: doc-aligned (S_local, P) plus MV flats (S_local, F_pad).
        # Aggregates are order-independent, so flatten the local segments
        # into ONE doc vector with a per-segment validity mask — one wide
        # kernel call instead of a vmap over segments. MV owning-doc ids
        # shift by each segment's doc offset so they index the flat space.
        s_local = next(iter(cols.values())).shape[0]
        flat = {}
        for k, v in cols.items():
            if k.endswith("!docs"):
                offs = (jnp.arange(s_local, dtype=v.dtype) * v.dtype.type(doc_pad))[:, None]
                flat[k] = (v + offs).reshape(-1)
            else:
                flat[k] = v.reshape(s_local * v.shape[1])
        valid = (
            jnp.arange(doc_pad, dtype=jnp.int32)[None, :] < n_docs[:, None]
        ).reshape(s_local * doc_pad)
        return flat, valid

    def per_shard(cols, ops, n_docs):
        flat, valid = _flatten_local(cols, n_docs)
        out = base(flat, ops, valid)
        if sparse:
            # sort-compaction slots are shard-LOCAL (each shard compacts its
            # own present groups), so partials cannot ride an all-reduce —
            # every shard ships its (counts, parts, uniq) table back and the
            # broker-style reduce merges the <=U-row tables host-side, the
            # per-server DataTable model (BrokerReduceService.java:61).
            return jax.tree.map(lambda x: x[None, ...], out)
        if grouped:
            matched, counts, parts = out
        else:
            matched, parts = out
            counts = None
        # a size-1 mesh axis (the single-chip bench) needs no collective at
        # all — skip them so the program never emits an all-reduce/all-gather
        coll_axis = axis if mesh.shape[axis] > 1 else None
        m, c, p = _combine_tree(spec, matched, counts, parts, coll_axis, local_axis=False)
        return (m, c, p) if grouped else (m, p)

    def run(cols, ops, n_docs):
        col_specs = {k: P(axis, None) for k in cols}
        f = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(col_specs, P(), P(axis)),
            # sparse: per-shard tables concatenate over the mesh axis;
            # dense: partials are replicated after collectives
            out_specs=P(axis) if sparse else P(),
            check_vma=False,
        )
        out = f(cols, ops, n_docs)
        leaves, treedef = jax.tree.flatten(out)
        # output shapes depend only on the plan spec, so the metadata
        # captured at (first) trace time is valid for every call
        pack_meta["treedef"] = treedef  # pinotlint: disable=jit-purity — deliberate trace-time capture; valid for every call of this compiled signature
        pack_meta["leaves"] = [(tuple(l.shape), np.dtype(l.dtype)) for l in leaves]  # pinotlint: disable=jit-purity — same trace-time capture as above
        chunks = []
        for l in leaves:
            flat = jnp.ravel(l)
            if flat.dtype == jnp.int64:
                # hi/lo 32-bit split: sparse gid64 slot tables exceed 2^53
                # and would lose exactness as a plain f64 cast
                chunks.append(jnp.floor_divide(flat, 1 << 32).astype(jnp.float64))
                chunks.append(jnp.remainder(flat, 1 << 32).astype(jnp.float64))
            else:
                chunks.append(flat.astype(jnp.float64))
        return jnp.concatenate(chunks)

    def unpack(vec: np.ndarray):
        out = []
        i = 0
        for shape, dtype in pack_meta["leaves"]:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            if dtype == np.int64:
                hi = vec[i : i + size]
                lo = vec[i + size : i + 2 * size]
                i += 2 * size
                chunk = (hi.astype(np.int64) << 32) + lo.astype(np.int64)
            else:
                chunk = vec[i : i + size]
                i += size
                if dtype != np.float64:
                    chunk = chunk.astype(dtype)
            out.append(chunk.reshape(shape))
        return jax.tree.unflatten(pack_meta["treedef"], out)

    return jax.jit(run), unpack


def _collect_mv_nv_indices(node, out: set) -> None:
    """Operand indices holding MV flat-value counts. In the sharded flat
    space those counts (taken from the whole-table proto) are meaningless —
    validity is enforced by the padding-docid trick instead, so the caller
    neutralizes them to 'all positions valid'."""
    if not isinstance(node, tuple) or not node:
        return
    k = node[0]
    if k == "mv_any":
        out.add(node[3])
    elif k == "mv_count":
        out.add(node[2])
    elif k in ("mv_sum", "mv_min", "mv_max", "mv_avg", "mv_distinct_ids"):
        out.add(node[3])
    elif k == "groups_mv":
        out.add(node[5])
    for c in node:
        if isinstance(c, tuple):
            _collect_mv_nv_indices(c, out)


def execute_sharded(table: ShardedTable, sql: str):
    """Execute an aggregation / group-by query over the sharded table.
    Returns the same device partial structure as the single-segment kernel,
    already merged across all segments and devices."""
    ctx = QueryContext.from_sql(sql)
    if ctx.query_type not in (QueryType.AGGREGATION, QueryType.GROUP_BY):
        raise ValueError("sharded execution currently covers aggregation/group-by queries")
    # global bounds hints from the table-level stats (single shared proto)
    from pinot_tpu.query import ast as _ast

    for a in ctx.aggregations:
        if a.func == "percentileest" and isinstance(a.arg, _ast.Identifier):
            ci = table.proto.columns.get(a.arg.name)
            if ci is not None and isinstance(ci.stats.min_value, (int, float)):
                ctx.hints.setdefault("est_bounds", {})[a.name] = (
                    float(ci.stats.min_value),
                    float(ci.stats.max_value),
                )
    # planned without "groups_compact": its overflow is answered by a second launch, which this path does not have
    plan: SegmentPlan = plan_segment(table.proto, ctx, compact=False)
    gspec = plan.spec[2]
    if gspec is not None and gspec[0] == "groups_mv2":
        # mv2's per-doc offset/length tables index the proto doc space,
        # which the sharded flat layout doesn't have — run on the proto
        raise ProtoFallback("two-MV-key cartesian GROUP BY runs on the proto segment")
    kernel, _unpack = _sharded_kernel(plan.spec, table.mesh, table.mesh.axis_names[0], table.padded)
    cols = plan_columns(plan, table.arrays)
    if not cols:
        cols = {"__shape__": next(iter(table.arrays.values()))}
    operands = list(plan.operands)
    nv_idx: set = set()
    _collect_mv_nv_indices(plan.spec, nv_idx)
    for i in nv_idx:
        # sharded flat positions exceed the proto's table-level flat count
        # whenever a device holds >1 segment; padding positions are already
        # excluded via invalid padding docids, so the count check must pass
        # everywhere (review r4: per-shard flat offsets vs table nv)
        operands[i] = np.int32(np.iinfo(np.int32).max)
    from pinot_tpu.query.kernels import stage_operand

    ops = tuple(stage_operand(o) for o in operands)
    out = kernel(cols, ops, table.n_docs)  # ONE packed f64 vector on device
    return ctx, plan, out


class ProtoFallback(Exception):
    """Raised when a query shape can't ride the sharded kernel; the caller
    re-runs it over the host-side proto segment (which holds the full
    table), preserving the result contract."""


def _run_on_proto(table: ShardedTable, sql: str):
    from pinot_tpu.query.engine import QueryEngine

    return QueryEngine([table.proto]).execute(sql)


def execute_sharded_result(table: ShardedTable, sql: str):
    """execute_sharded + broker-style reduce to a final ResultTable.

    Sparse (high-cardinality) group-bys come back as per-shard compacted
    tables — one <=U-row (counts, parts, uniq) block per device — merged by
    the same reduce that merges per-server DataTables. A shard whose present
    groups overflow its slot budget invalidates the device result; the query
    re-runs on the host-side proto segment."""
    from pinot_tpu.query import reduce as reduce_mod
    from pinot_tpu.query.engine import QueryEngine

    from pinot_tpu.query.plan import DeviceFallback

    try:
        ctx, plan, out = execute_sharded(table, sql)
    except (ProtoFallback, DeviceFallback):
        # proto holds the full host-side table: any shape the sharded kernel
        # can't express (mv2 cartesian, expression group keys, ...) still
        # answers correctly through the per-segment engine's own paths
        return _run_on_proto(table, sql)
    _, unpack = _sharded_kernel(plan.spec, table.mesh, table.mesh.axis_names[0], table.padded)
    # single device->host round trip, fenced + attributed by kernel_obs
    host = unpack(
        np.asarray(
            KERNELS.timed_sync(
                "exchange.sharded",
                lambda: np.asarray(out),
                rows=table.padded,
                cols=max(len(plan.columns), 1),
            )
        )
    )
    e = QueryEngine([])
    gspec = plan.spec[2]
    if ctx.query_type == QueryType.AGGREGATION:
        matched, parts = host
        partial = e._convert_agg(table.proto, ctx, plan, parts)
        rows = reduce_mod.reduce_aggregation(ctx, [partial])
        matched = int(matched)
    elif gspec is not None and gspec[0] == "groups_sparse":
        matched_s, counts_s, parts_s, uniq_s, n_unique_s = host
        u_slots = gspec[2]
        if int(np.max(n_unique_s)) > u_slots:
            # a shard's clipped slots collided — device result unusable
            return _run_on_proto(table, sql)
        frames = []
        for d in range(len(n_unique_s)):
            frames.append(
                e._convert_groups(
                    table.proto,
                    ctx,
                    plan,
                    np.asarray(counts_s[d]),
                    jax.tree.map(lambda x: x[d], parts_s),
                    dense_gids=np.asarray(uniq_s[d]),
                )
            )
        rows = reduce_mod.reduce_group_by(ctx, frames)
        matched = int(np.sum(matched_s))
    else:
        matched, counts, parts = host
        frame = e._convert_groups(table.proto, ctx, plan, np.asarray(counts), parts)
        rows = reduce_mod.reduce_group_by(ctx, [frame])
        matched = int(matched)
    return reduce_mod.build_result(
        ctx,
        rows,
        num_docs_scanned=matched,
        total_docs=table.total_docs,
        num_segments_queried=table.n_segments,
    )


# -- kernel registry: cost model for the roofline report ---------------------


def _sharded_cost(shape: dict) -> tuple[float, float]:
    # same streaming model as the per-segment fused program (each staged
    # column read once at accumulator width), applied to the sharded layout
    rows = max(float(shape.get("rows", 0)), 0.0)
    cols = max(float(shape.get("cols", 1)), 1.0)
    return rows * (cols * 8.0 + 1.0), rows * cols * 4.0


KERNELS.register(
    "exchange.sharded",
    _sharded_kernel,
    cost_model=_sharded_cost,
    description="sharded whole-table program: vmapped fused kernel + ICI partial merge",
)
