"""Spec -> compiled XLA program for per-segment query execution.

Reference parity: this is the TPU-native replacement for Pinot's per-segment
operator chain DocIdSetOperator -> ProjectionOperator -> TransformOperator ->
AggregationOperator/GroupByOperator (call stack SURVEY.md §3.1; key files
core/operator/DocIdSetOperator.java:59, core/operator/ProjectionOperator.java:68,
core/query/aggregation/groupby/DefaultGroupByExecutor.java:191). Instead of
pull-based 10k-doc blocks, the whole segment evaluates as ONE fused program:
filter mask (vector compares + LUT gathers over dict ids), projection
(dictionary-value gathers), aggregation (masked reductions / segment_sum with
dense group ids). XLA fuses the chain; there are no intermediate
materializations in HBM beyond what the compiler chooses.

Compiled programs are cached per spec (plan shape), with literals as dynamic
operands — the analog of Pinot reusing plans across identical query shapes.

Accumulator dtype policy (Pinot parity: SUM/MIN/MAX/AVG return DOUBLE,
COUNT returns LONG): float64 value accumulators, int64 counts. The TPU chip
emulates both; a fast float32 policy is a planned bench option.
"""

from __future__ import annotations

import threading
import weakref
import zlib
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.common.kernel_obs import KERNELS, CacheObserver
from pinot_tpu.common.trace import active_ledger, count, span

_F = jnp.float64
_I = jnp.int64


# ---------------------------------------------------------------------------
# evaluation of value / filter specs (traced)
# ---------------------------------------------------------------------------


def _value(vspec, cols, ops, n_padded):
    """Evaluate a value spec over doc-aligned arrays of length n_padded.
    n_padded is threaded explicitly: cols may also hold MV flat arrays, so
    the doc length cannot be inferred from an arbitrary cols entry."""
    kind = vspec[0]
    if kind == "raw":
        return cols[vspec[1]]
    if kind == "ids":
        return cols[vspec[1]]
    if kind == "docid":
        return jnp.arange(n_padded, dtype=jnp.int32)
    if kind == "dictval":
        return ops[vspec[2]][cols[vspec[1]]]
    if kind == "lit":
        return ops[vspec[1]]
    if kind == "fn":
        from pinot_tpu.query.transforms import DEVICE_FUNCS

        _, fn = DEVICE_FUNCS[vspec[1]]
        args = [_value(a, cols, ops, n_padded) for a in vspec[2]]
        return fn(jnp, *args)
    if kind == "case":
        # reversed fold: first matching WHEN wins
        out = _value(vspec[2], cols, ops, n_padded)
        out = jnp.broadcast_to(out.astype(_F), (n_padded,))
        for fspec, branch in reversed(vspec[1]):
            cond = _filter(fspec, cols, ops, n_padded)
            out = jnp.where(cond, _value(branch, cols, ops, n_padded).astype(_F), out)
        return out
    if kind == "cast_int":
        v = _value(vspec[1], cols, ops, n_padded)
        # truncate toward zero (Pinot CAST AS INT/LONG semantics)
        return jnp.trunc(v.astype(_F)).astype(_I) if jnp.issubdtype(v.dtype, jnp.floating) else v
    if kind == "cast_float":
        return _value(vspec[1], cols, ops, n_padded).astype(_F)
    if kind == "bin":
        op = vspec[1]
        l = _value(vspec[2], cols, ops, n_padded)
        r = _value(vspec[3], cols, ops, n_padded)
        if op == "+":
            return l + r
        if op == "-":
            return l - r
        if op == "*":
            return l * r
        if op == "/":
            # Pinot DIVIDE always returns DOUBLE
            return l.astype(_F) / r.astype(_F)
        if op == "%":
            return jnp.mod(l, r)
        raise AssertionError(op)
    raise AssertionError(vspec)


_CMPS = {
    "EQ": lambda a, b: a == b,
    "NEQ": lambda a, b: a != b,
    "LT": lambda a, b: a < b,
    "LTE": lambda a, b: a <= b,
    "GT": lambda a, b: a > b,
    "GTE": lambda a, b: a >= b,
}


def _filter_k3(fspec, cols, ops, n_padded):
    """Three-valued filter evaluation: returns the (true, unknown) dense
    mask pair. Mirrors host_exec._filter3 exactly (Kleene AND: FALSE
    dominates UNKNOWN; OR: TRUE dominates; NOT(unknown)=unknown)."""
    kind = fspec[0]
    if kind == "k3_and":
        t = jnp.ones((n_padded,), dtype=bool)
        any_u = jnp.zeros((n_padded,), dtype=bool)
        any_false = jnp.zeros((n_padded,), dtype=bool)
        for c in fspec[1]:
            ct, cu = _filter_k3(c, cols, ops, n_padded)
            t = t & ct
            any_u = any_u | cu
            any_false = any_false | (~ct & ~cu)
        return t, any_u & ~any_false
    if kind == "k3_or":
        t = jnp.zeros((n_padded,), dtype=bool)
        any_u = jnp.zeros((n_padded,), dtype=bool)
        for c in fspec[1]:
            ct, cu = _filter_k3(c, cols, ops, n_padded)
            t = t | ct
            any_u = any_u | cu
        return t, any_u & ~t
    if kind == "k3_not":
        ct, cu = _filter_k3(fspec[1], cols, ops, n_padded)
        return ~ct & ~cu, cu
    if kind == "k3_exact":
        return _filter(fspec[1], cols, ops, n_padded), jnp.zeros((n_padded,), dtype=bool)
    if kind == "k3_leaf":
        t = _filter(fspec[1], cols, ops, n_padded)
        nu = ops[fspec[2]]
        return t & ~nu, nu
    raise AssertionError(fspec)


_LUT_WORDS_MAX = 128  # words of 32 entries: the widest boolean table that `_in_lut` reads as bits (4,096 entries)


def _in_lut(lut, ids):
    """`lut[ids]` for a boolean table over a column's dictionary ids (IN, a
    disjunction of equalities, LIKE, a string function's predicate). XLA's
    gather of one element a row is 8.6 ns a row on a v5e from a table of 256
    entries — 36 ms a 4M-row segment and membership test, the whole of SSB
    Q3.3's launch once its group space is compact (PERF.md §6, PR 45) — so a
    table of up to 4,096 entries is packed 32 entries a word on the device
    (it is tiny) and a row picks its word by a compare against every word,
    then its bit: a dense pass of (words, rows) compares, as
    `_compact_key`'s second. A larger table keeps the gather. An id outside
    the table is in no word and reads False."""
    words = -(-lut.shape[0] // 32)
    if words > _LUT_WORDS_MAX:
        return lut[ids]
    ids = ids.astype(jnp.int32)
    bits = jnp.pad(lut, (0, 32 * words - lut.shape[0])).reshape(words, 32).astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(bits, axis=1, dtype=jnp.uint32)
    at = (ids >> 5)[None] == jnp.arange(words, dtype=jnp.int32)[:, None]
    mine = jnp.sum(jnp.where(at, packed[:, None], jnp.uint32(0)), axis=0, dtype=jnp.uint32)
    return (mine >> (ids & 31).astype(jnp.uint32)) & 1 == 1


def _filter(fspec, cols, ops, n_padded):
    kind = fspec[0]
    if kind == "k3root":
        # three-valued WHERE: only definitely-true rows survive
        t, _u = _filter_k3(fspec[1], cols, ops, n_padded)
        return t
    if kind == "const":
        return jnp.full((n_padded,), fspec[1], dtype=bool)
    if kind == "and":
        m = _filter(fspec[1][0], cols, ops, n_padded)
        for c in fspec[1][1:]:
            m = m & _filter(c, cols, ops, n_padded)
        return m
    if kind == "or":
        m = _filter(fspec[1][0], cols, ops, n_padded)
        for c in fspec[1][1:]:
            m = m | _filter(c, cols, ops, n_padded)
        return m
    if kind == "not":
        return ~_filter(fspec[1], cols, ops, n_padded)
    if kind == "range_ids":
        ids = cols[fspec[1]]
        return (ids >= ops[fspec[2]]) & (ids <= ops[fspec[3]])
    if kind == "docmask":
        # host-computed index-probe mask (text/json/vector/null), DMA'd once
        return ops[fspec[1]]
    if kind == "doc_range":
        # sorted-column predicate: [start, end) doc interval, no column read
        i = jnp.arange(n_padded, dtype=jnp.int32)
        return (i >= ops[fspec[1]]) & (i < ops[fspec[2]])
    if kind == "in_lut":
        return _in_lut(ops[fspec[2]], cols[fspec[1]])
    if kind == "lookup_range":
        # a lookUp filter whose passing destination codes are one run (plan.lookup_filter)
        codes = _lookup_codes(fspec[1], cols, ops)
        return (codes >= ops[fspec[2]]) & (codes <= ops[fspec[3]])
    if kind == "lookup_lut":
        return ops[fspec[2]][_lookup_codes(fspec[1], cols, ops)]
    if kind == "cmp_raw":
        v = cols[fspec[2]]
        o = ops[fspec[3]]
        if jnp.issubdtype(v.dtype, jnp.integer) and jnp.issubdtype(o.dtype, jnp.integer):
            # native integer compare: avoids materializing a 64-bit float
            # copy of the column (f64 is software-emulated on TPU)
            return _CMPS[fspec[1]](v, o.astype(v.dtype))
        return _CMPS[fspec[1]](v.astype(_F), o)
    if kind == "cmp_lit":
        v = _value(fspec[2], cols, ops, n_padded)
        return _CMPS[fspec[1]](v.astype(_F), ops[fspec[3]])
    if kind == "cmp2":
        l = _value(fspec[2], cols, ops, n_padded)
        r = _value(fspec[3], cols, ops, n_padded)
        return _CMPS[fspec[1]](l.astype(_F), r.astype(_F))
    if kind == "in_vals":
        v = _value(fspec[1], cols, ops, n_padded).astype(_F)
        vals = ops[fspec[2]]
        return (v[:, None] == vals[None, :]).any(axis=1)
    if kind == "in_sorted":
        # membership via sorted probe: searchsorted + one gather — flat in
        # IN-list length (vals operand is sorted, padded by repeating the max)
        v = _value(fspec[1], cols, ops, n_padded)
        vals = ops[fspec[2]]
        if not (jnp.issubdtype(v.dtype, jnp.integer) and jnp.issubdtype(vals.dtype, jnp.integer)):
            v = v.astype(_F)
            vals = vals.astype(_F)
        elif v.dtype != vals.dtype:
            # widen the narrower side — narrowing the sorted probe list could
            # wrap out-of-range literals and break its ordering
            if jnp.iinfo(vals.dtype).bits > jnp.iinfo(v.dtype).bits:
                v = v.astype(vals.dtype)
            else:
                vals = vals.astype(v.dtype)
        pos = jnp.clip(jnp.searchsorted(vals, v), 0, vals.shape[0] - 1)
        return vals[pos] == v
    if kind == "mv_any":
        # flattened-MV any-match: evaluate the inner predicate over the flat
        # value vector, then scatter-or into doc space (padding docids point
        # past the doc range and are dropped by the scatter)
        _, col, inner, nv_idx = fspec
        flat = cols[col]
        pred = _filter(inner, cols, ops, flat.shape[0])
        pred = pred & (jnp.arange(flat.shape[0], dtype=jnp.int32) < ops[nv_idx])
        docids = cols[f"{col}!docs"]
        return jnp.zeros((n_padded,), dtype=bool).at[docids].max(pred, mode="drop")
    raise AssertionError(fspec)


# ---------------------------------------------------------------------------
# aggregation partials
# ---------------------------------------------------------------------------

# Four forms of a grouped SUM/AVG/MIN/MAX, chosen from what the program can
# see — the value's dtype, the group space and, for the third, the rows'
# exponents — and from nothing else:
#   * int32 values: exact integer summation without 64-bit arithmetic on the
#     hot path. Where the Pallas kernel is on (`_grouped_all`), COUNT and every
#     int32 SUM/AVG ride one byte-plane pass on the MXU; without it docs split
#     into blocks and each value into 16-bit halves, per-block per-group i32
#     partial sums are exact (|half| * BLOCK < 2^31), and only the tiny
#     (n_blocks, ng) second-level reduction runs in f64.
#   * any other value (DOUBLE columns and expressions, LONG past int32) over a
#     dense group space whose real group count the plan states in its group
#     spec, which it does up to plan.DENSE_REDUCE_MAX_GROUPS: a dense masked
#     reduction
#     (`_dense_grouped`), one compare-select-add a (row, slot) pair in the
#     program's emulated f64, fused by XLA into one pass over the rows.
#   * the SUM / AVG of any other value where no real group count is stated
#     (more groups, MV keys' value space, the sort-compaction path's slot
#     budget) and the Pallas kernel is on: fixed-point limbs under the launch's
#     own exponent window, plane rows of the same byte-plane pass
#     (groupby_pallas.limb_planes) — exact, and 8.9 ms where the scatter takes
#     434 at 4.19M rows and 12,032 slots (PERF.md §6, PR 36). The rows decide:
#     where one does not fit the window the same program scatters instead
#     (`_grouped_all`: a lax.cond on the flag the limbs come with).
#   * any other value otherwise (that fallback, MIN / MAX, the kernel off): a
#     scatter, jax.ops.segment_*. On the v5e 4M f64 rows scattered into 256
#     slots take 0.16-0.28 s, a thousand times a masked f64 sum of the same
#     rows (PERF.md §6, PR 28).
# Over which group space they run is the plan's (plan.group_spec): the keys'
# dense product or, where that product reaches plan.COMPACT_MIN_GROUPS, the
# compact space of `_compact_groups` — each key renumbered by the values the
# filter leaves, plan.COMPACT_SLOTS slots whatever the product — where the
# same four forms reduce over a slot id as they do over the sort-compaction
# path's: 2.6 ms a 4M-row launch of SSB Q3.2 where the 437,500 dense groups
# take 99 (PERF.md §6, PR 45).
_BLOCK = 8192


def _blocked(v, block=_BLOCK):
    n = v.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        v = jnp.pad(v, (0, pad))
    return v.reshape(nb, block)


def _exact_int_grouped_sum(v, gid, mask, ng):  # pinotlint: disable=kernel-registry — vmap here is traced inline inside the fused kernel; device time lands under query.fused, not a separate root
    v2 = _blocked(v.astype(jnp.int32))
    g2 = _blocked(gid)
    m2 = _blocked(mask)
    lo = jnp.where(m2, v2 & 0xFFFF, 0)
    hi = jnp.where(m2, v2 >> 16, 0)  # arithmetic shift keeps sign: v = hi*2^16 + lo
    seg = jax.vmap(lambda a, g: jax.ops.segment_sum(a, g, num_segments=ng))
    lo_s = seg(lo, g2)
    hi_s = seg(hi, g2)
    return lo_s.astype(_F).sum(0) + hi_s.astype(_F).sum(0) * 65536.0


def _exact_int_sum(v, mask):
    v2 = _blocked(v.astype(jnp.int32))
    m2 = _blocked(mask)
    lo = jnp.sum(jnp.where(m2, v2 & 0xFFFF, 0), axis=1)
    hi = jnp.sum(jnp.where(m2, v2 >> 16, 0), axis=1)
    return jnp.sum(lo.astype(_F)) + jnp.sum(hi.astype(_F)) * 65536.0


_REDUCTIONS = {
    # kind: (what a row outside the group contributes, dense reduce, scatter);
    # the sum keeps its dtype: jnp.sum alone widens a count's int32 to the emulated int64
    "sum": (0, lambda x, axis: jnp.sum(x, axis=axis, dtype=x.dtype), jax.ops.segment_sum),
    "min": (jnp.inf, jnp.min, jax.ops.segment_min),
    "max": (-jnp.inf, jnp.max, jax.ops.segment_max),
}


def _dense_grouped(kind, v, gid, mask, g):
    """(g,) `kind` of `v` over the masked rows of each group id below `g`:
    every row is compared against the g slots and reduced within blocks of
    rows, then across the blocks — a tree in place of a scatter's order, in
    the same arithmetic (emulated f64 for a DOUBLE, int32 for a count). XLA
    fuses compare, select and reduce: the (g, rows) one-hot never reaches
    HBM."""
    fill, reduce, _ = _REDUCTIONS[kind]
    v2, g2, m2 = _blocked(v), _blocked(gid), _blocked(mask)
    slots = jnp.arange(g, dtype=jnp.int32)[:, None, None]
    hit = m2[None] & (g2[None] == slots)
    return reduce(reduce(jnp.where(hit, v2[None], jnp.asarray(fill, v.dtype)), axis=2), axis=1)


def _grouped_reduce(kind, v, gid, mask, ng, dense_g):
    """Grouped sum / min / max of `v` (f64, or the int32 ones of a count)
    into the (ng,) partial: dense where the plan stated a real group count
    `dense_g` (plan.with_real_groups: a dense group space of few groups), the
    scatter otherwise — for a sum under the Pallas kernel only as the side of
    `_grouped_all`'s cond that rows outside the limbs' window take. Both leave
    the reduction's identity (0, +/-inf) in the slots no row reached."""
    fill, _, scatter = _REDUCTIONS[kind]
    fill = jnp.asarray(fill, v.dtype)
    if dense_g is None:
        return _scatter_grouped(scatter, jnp.where(mask, v, fill), gid, ng)
    r = KERNELS.timed_sync(
        "query.grouped_dense",
        lambda: _dense_grouped(kind, v, gid, mask, dense_g),
        rows=v.shape[0],
        groups=dense_g,
        width=v.dtype.itemsize,
    )
    return jnp.concatenate([r, jnp.full((ng - dense_g,), fill, dtype=v.dtype)])


def _count_grouped(mask, gid, ng, dense_g=None):
    # counts fit i32 (segment docs < 2^31); widen after the reduction
    if dense_g is not None:
        ones = jnp.ones(mask.shape, dtype=jnp.int32)
        return _grouped_reduce("sum", ones, gid, mask, ng, dense_g).astype(_I)
    return jax.ops.segment_sum(mask.astype(jnp.int32), gid, num_segments=ng).astype(_I)


_I32_MAX = np.int32(np.iinfo(np.int32).max)
_I32_MIN = np.int32(np.iinfo(np.int32).min)


def _int_grouped_extreme(v, gid, mask, ng, is_min):
    sentinel = _I32_MAX if is_min else _I32_MIN
    red = jax.ops.segment_min if is_min else jax.ops.segment_max
    r = red(jnp.where(mask, v.astype(jnp.int32), sentinel), gid, num_segments=ng)
    hit = jax.ops.segment_max(mask.astype(jnp.int32), gid, num_segments=ng) > 0
    empty = jnp.inf if is_min else -jnp.inf
    return jnp.where(hit, r.astype(_F), empty)


def _hashes_for(hspec, cols, ops, n_padded):
    from pinot_tpu.query.sketches import jnp_mix32

    if hspec[0] == "gather":
        return ops[hspec[2]][cols[hspec[1]]]
    # ("mix", vspec): hash numeric values by bit pattern. Integers hash by
    # value; floats by their f64 bit pattern split into two u32 words so equal
    # values hash identically across segments.
    v = _value(hspec[1], cols, ops, n_padded)
    if jnp.issubdtype(v.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(v.astype(_F), jnp.uint32)  # (..., 2)
        return jnp_mix32(jnp, bits[..., 0] ^ jnp_mix32(jnp, bits[..., 1]))
    lo = (v & 0xFFFFFFFF).astype(jnp.uint32)
    hi = ((v.astype(_I) >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
    return jnp_mix32(jnp, lo ^ jnp_mix32(jnp, hi))


def _mv_vmask(col, nv_idx, cols, ops, mask):
    """Per-flat-value mask for MV aggregations: the doc mask gathered to each
    value position, ANDed with flat-padding validity. Padding docids point
    past the doc range (gathers clip, but validity zeroes them)."""
    flat = cols[col]
    docids = cols[f"{col}!docs"]
    vvalid = jnp.arange(flat.shape[0], dtype=jnp.int32) < ops[nv_idx]
    return mask[docids] & vvalid


def _agg_scalar(aspec, cols, ops, mask):
    kind = aspec[0]
    if kind == "masked_nan_empty":
        # null-handling SUM: intersect the non-null mask AND every inner
        # FILTER(WHERE) mask, then emit NaN when zero rows survive (the
        # empty-check must see the FULL effective mask, not just the null
        # mask — review r4). NaN finalizes to NULL at reduce.
        m2 = mask & _filter(aspec[1], cols, ops, mask.shape[0])
        inner = aspec[2]
        while inner[0] == "masked":
            m2 = m2 & _filter(inner[1], cols, ops, mask.shape[0])
            inner = inner[2]
        r = _agg_scalar(inner, cols, ops, m2)
        return jnp.where(jnp.any(m2), r.astype(_F), jnp.nan)
    if kind == "masked":
        # FILTER (WHERE ...): intersect the per-agg mask, delegate
        m2 = mask & _filter(aspec[1], cols, ops, mask.shape[0])
        return _agg_scalar(aspec[2], cols, ops, m2)
    if kind == "count":
        return jnp.sum(mask, dtype=jnp.int32).astype(_I)
    if kind == "mv_count":
        vm = _mv_vmask(aspec[1], aspec[2], cols, ops, mask)
        return jnp.sum(vm, dtype=jnp.int32).astype(_I)
    if kind == "mv_distinct_ids":
        col, pad = aspec[1], aspec[2]
        vm = _mv_vmask(col, aspec[3], cols, ops, mask)
        return jnp.zeros((pad,), dtype=bool).at[cols[col]].max(vm)
    if kind in ("mv_sum", "mv_min", "mv_max", "mv_avg"):
        vspec, col, nv_idx = aspec[1], aspec[2], aspec[3]
        vm = _mv_vmask(col, nv_idx, cols, ops, mask)
        inner = {"mv_sum": "sum", "mv_min": "min", "mv_max": "max", "mv_avg": "avg"}[kind]
        return _agg_scalar((inner, vspec), cols, ops, vm)
    if kind == "distinct_ids":
        col, pad = aspec[1], aspec[2]
        presence = jnp.zeros((pad,), dtype=bool).at[cols[col]].max(mask)
        return presence
    if kind == "funnel_steps":
        # un-ordered funnel: per-step presence of correlation ids — K
        # scatter-or rows stacked into one (K, pad) matrix
        col, pad, stepspecs = aspec[1], aspec[2], aspec[3]
        ids = cols[col]
        return jnp.stack(
            [
                jnp.zeros((pad,), dtype=bool)
                .at[ids]
                .max(mask & _filter(s, cols, ops, mask.shape[0]))
                for s in stepspecs
            ]
        )
    if kind == "hll":
        from pinot_tpu.query.sketches import hll_update

        hashes = _hashes_for(aspec[1], cols, ops, mask.shape[0])
        return hll_update(jnp, jax, hashes, mask, aspec[2])
    if kind == "hist":
        v = _value(aspec[1], cols, ops, mask.shape[0]).astype(_F)
        lo, inv_w, nbins = ops[aspec[2]], ops[aspec[3]], aspec[4]
        b = jnp.clip(jnp.floor((v - lo) * inv_w).astype(jnp.int32), 0, nbins - 1)
        return jax.ops.segment_sum(mask.astype(_I), b, num_segments=nbins)
    v_raw = _value(aspec[1], cols, ops, mask.shape[0])
    is_i32 = v_raw.dtype == jnp.int32
    v = v_raw.astype(_F)
    if kind == "sum":
        if is_i32:
            return _exact_int_sum(v_raw, mask)
        return jnp.sum(jnp.where(mask, v, 0.0))
    if kind == "min":
        if is_i32:
            return _int_scalar_extreme(v_raw, mask, True)
        return jnp.min(jnp.where(mask, v, jnp.inf))
    if kind == "max":
        if is_i32:
            return _int_scalar_extreme(v_raw, mask, False)
        return jnp.max(jnp.where(mask, v, -jnp.inf))
    if kind == "avg":
        cnt = jnp.sum(mask, dtype=jnp.int32).astype(_I)
        if is_i32:
            return (_exact_int_sum(v_raw, mask), cnt)
        return (jnp.sum(jnp.where(mask, v, 0.0)), cnt)
    if kind == "minmaxrange":
        if is_i32:
            return (_int_scalar_extreme(v_raw, mask, True), _int_scalar_extreme(v_raw, mask, False))
        return (jnp.min(jnp.where(mask, v, jnp.inf)), jnp.max(jnp.where(mask, v, -jnp.inf)))
    raise AssertionError(aspec)


def _int_scalar_extreme(v, mask, is_min):
    sentinel = _I32_MAX if is_min else _I32_MIN
    r = (jnp.min if is_min else jnp.max)(jnp.where(mask, v.astype(jnp.int32), sentinel))
    empty = jnp.inf if is_min else -jnp.inf
    return jnp.where(jnp.any(mask), r.astype(_F), empty)


def _agg_grouped(aspec, cols, ops, mask, gid, ng, gather=None, doc_pad=None, dense_g=None):
    """gather/doc_pad: MV GROUP BY evaluates in VALUE space — doc-space
    value/filter vectors gather through the owning-doc ids first. dense_g:
    the real group count of a dense group space, where the plan stated one
    (`_grouped_reduce` chooses the form of a non-int32 reduction from it).
    With the Pallas kernel on, a top-level SUM / AVG that is int32, or that no
    real group count is stated for, never arrives here: `_grouped_all` has it."""
    kind = aspec[0]
    if kind == "masked_nan_empty":
        # null-handling SUM: the per-group empty check must see the FULL
        # effective mask (non-null AND every inner FILTER mask — review r4);
        # empty groups emit NaN partials, finalized to NULL at reduce.
        m2 = mask
        node = aspec
        while node[0] in ("masked", "masked_nan_empty"):
            fm = _filter(node[1], cols, ops, doc_pad if gather is not None else mask.shape[0])
            if gather is not None:
                fm = fm[gather]
            m2 = m2 & fm
            node = node[2]
        r = _agg_grouped(node, cols, ops, m2, gid, ng, gather, doc_pad, dense_g)
        cnt = _count_grouped(m2, gid, ng, dense_g)
        return jnp.where(cnt == 0, jnp.nan, r.astype(_F))
    if kind == "masked":
        fm = _filter(aspec[1], cols, ops, doc_pad if gather is not None else mask.shape[0])
        if gather is not None:
            fm = fm[gather]
        return _agg_grouped(aspec[2], cols, ops, mask & fm, gid, ng, gather, doc_pad, dense_g)
    if kind == "count":
        return _count_grouped(mask, gid, ng, dense_g)
    if kind == "distinct_ids":
        # grouped DISTINCTCOUNT: per-group presence matrix via 2-D
        # scatter-or; the plan gates ng*pad under the device budget
        col, pad = aspec[1], aspec[2]
        ids = cols[col] if gather is None else cols[col][gather]
        return jnp.zeros((ng, pad), dtype=bool).at[gid, ids].max(mask)
    if kind == "hll":
        # grouped DISTINCTCOUNTHLL: per-group register matrix
        from pinot_tpu.query.sketches import hll_update_grouped

        hashes = _hashes_for(aspec[1], cols, ops, doc_pad if gather is not None else mask.shape[0])
        if gather is not None:
            hashes = hashes[gather]
        return hll_update_grouped(jnp, jax, hashes, mask, gid, ng, aspec[2])
    if kind == "hist":
        # grouped PERCENTILEEST: per-group fixed-bin histogram matrix
        v = _value(aspec[1], cols, ops, doc_pad if gather is not None else mask.shape[0]).astype(_F)
        if gather is not None:
            v = v[gather]
        lo, inv_w, nbins = ops[aspec[2]], ops[aspec[3]], aspec[4]
        b = jnp.clip(jnp.floor((v - lo) * inv_w).astype(jnp.int32), 0, nbins - 1)
        return jnp.zeros((ng, nbins), dtype=jnp.int32).at[gid, b].add(mask.astype(jnp.int32)).astype(_I)
    if kind == "mv_count":
        col, nv_idx = aspec[1], aspec[2]
        vm = _mv_vmask(col, nv_idx, cols, ops, mask)
        gid_v = gid[cols[f"{col}!docs"]]  # padding positions masked by vm
        return _count_grouped(vm, gid_v, ng)
    if kind in ("mv_sum", "mv_min", "mv_max", "mv_avg"):
        vspec, col, nv_idx = aspec[1], aspec[2], aspec[3]
        vm = _mv_vmask(col, nv_idx, cols, ops, mask)
        gid_v = gid[cols[f"{col}!docs"]]
        inner = {"mv_sum": "sum", "mv_min": "min", "mv_max": "max", "mv_avg": "avg"}[kind]
        return _agg_grouped((inner, vspec), cols, ops, vm, gid_v, ng)
    v_raw = _value(aspec[1], cols, ops, doc_pad if gather is not None else mask.shape[0])
    if gather is not None:
        v_raw = v_raw[gather]
    is_i32 = v_raw.dtype == jnp.int32
    v = v_raw.astype(_F)
    if kind == "sum":
        if is_i32:
            return _exact_int_grouped_sum(v_raw, gid, mask, ng)
        return _grouped_reduce("sum", v, gid, mask, ng, dense_g)
    if kind == "min":
        if is_i32:
            return _int_grouped_extreme(v_raw, gid, mask, ng, True)
        return _grouped_reduce("min", v, gid, mask, ng, dense_g)
    if kind == "max":
        if is_i32:
            return _int_grouped_extreme(v_raw, gid, mask, ng, False)
        return _grouped_reduce("max", v, gid, mask, ng, dense_g)
    if kind == "avg":
        if is_i32:
            s = _exact_int_grouped_sum(v_raw, gid, mask, ng)
        else:
            s = _grouped_reduce("sum", v, gid, mask, ng, dense_g)
        return (s, _count_grouped(mask, gid, ng, dense_g))
    if kind == "minmaxrange":
        if is_i32:
            return (
                _int_grouped_extreme(v_raw, gid, mask, ng, True),
                _int_grouped_extreme(v_raw, gid, mask, ng, False),
            )
        return (
            _grouped_reduce("min", v, gid, mask, ng, dense_g),
            _grouped_reduce("max", v, gid, mask, ng, dense_g),
        )
    raise AssertionError(aspec)


def _compact_key(ids, mask, width):
    """One GROUP BY key renumbered by the values the filter leaves: the rows'
    ranks among the present values, how many are present, and the present
    values in ascending order (then `width`s). Presence is kept 32 values a
    word: a row's value is the bit `id & 31` of the word `id >> 5`, a word of
    the key is the OR over the masked rows that fall into it, and a row's
    rank is the number of set bits under its own — the bits of the words
    before it (a running count, picked with the row's word by a compare
    against every word) and those below its bit in its word. Two dense
    passes of (words, rows) compares, as `_dense_grouped` makes one of
    (values, rows): a thirty-second of it, and no gather of `rank[ids]` at
    2.6 ns a row. A row the mask drops gets the rank of whatever it holds."""
    ids = ids.astype(jnp.int32)
    word, bit = _blocked(ids >> 5), _blocked(jnp.uint32(1) << (ids & 31).astype(jnp.uint32))
    at = word[None] == jnp.arange(-(-width // 32), dtype=jnp.int32)[:, None, None]
    zero = jnp.uint32(0)
    present = jax.lax.reduce(jnp.where(at & _blocked(mask)[None], bit[None], zero), zero, jax.lax.bitwise_or, (1, 2))
    held = jax.lax.population_count(present).astype(jnp.int32)
    before = jnp.cumsum(held) - held
    mine = jnp.sum(jnp.where(at, present[:, None, None], zero), axis=0, dtype=jnp.uint32)  # the presence word each row falls into
    ranks = jnp.sum(jnp.where(at, before[:, None, None], 0), axis=0, dtype=jnp.int32) + jax.lax.population_count(
        mine & (bit - jnp.uint32(1))
    ).astype(jnp.int32)
    values = jnp.arange(width, dtype=jnp.int32)
    is_present = (present[values >> 5] >> (values & 31).astype(jnp.uint32)) & 1 == 1
    return ranks.reshape(-1)[: ids.shape[0]], jnp.sum(held), jnp.sort(jnp.where(is_present, values, width))


def _compact_groups(gcols, widths, slots, dense_strides, cols, ops, mask):
    """The compact group space of plan.group_spec's "groups_compact": (the
    rows' slot ids, (slots,) int64 slot -> dense group id, the number of
    combinations of present key values). A key is renumbered (`_compact_key`,
    one registered call a key) or, where the plan found it too wide,
    carried whole; the compact id is the ranks under row-major strides of the
    present counts, device scalars that saturate one past `slots`. Where the
    combinations pass `slots` the ids are clipped and the caller's result is
    void; a slot past the combinations counts no row."""
    ranks, present, values = [], [], []
    for key, (how, width) in zip(gcols, widths):
        ids = _key_ids(key, cols, ops)
        if how == "whole":
            ranks.append(ids.astype(jnp.int32))
            present.append(jnp.int32(width))
            values.append(jnp.arange(width, dtype=jnp.int32))
            continue
        r, n, v = KERNELS.timed_sync(
            "query.group_compact", lambda: _compact_key(ids, mask, width), rows=ids.shape[0], groups=width
        )
        ranks.append(r)
        present.append(n)
        values.append(v)
    strides, total = [], jnp.int32(1)
    for n in reversed(present):
        strides.insert(0, total)
        total = jnp.minimum(total * n, slots + 1)
    cid = sum(r * s for r, s in zip(ranks, strides))
    slot = jnp.arange(slots, dtype=jnp.int32)
    gids = jnp.zeros((slots,), dtype=jnp.int64)
    for v, n, s, dense in zip(values, present, strides, dense_strides):
        gids = gids + v[(slot // jnp.maximum(s, 1)) % jnp.maximum(n, 1)].astype(jnp.int64) * dense
    return jnp.clip(cid, 0, slots - 1), gids, total


_limb_fallbacks = threading.local()  # .flags: what `_side_counts` collects while a program is traced
_lookup_trace = threading.local()  # .state: a traced program's lookUp codes by node, its gathered words by operand, the codes whose misses it counts


def _side_counts(trace, n_docs):
    """`trace()` and what rides back past its leaves, traced int32 scalars:
    the number of its limb reductions that took the scatter (None where it
    holds no limb reduction), and the number of its rows — of the `n_docs`
    real ones — whose foreign key had no dimension row, summed over its
    (dimension table, foreign key) pairs (None where it holds no lookUp)."""
    _limb_fallbacks.flags = flags = []
    _lookup_trace.state = state = {"codes": {}, "words": {}, "misses": []}
    try:
        out = trace()
    finally:
        _limb_fallbacks.flags = _lookup_trace.state = None
    misses = None
    if state["codes"]:
        misses = jnp.int32(0)
        for codes, miss in state["misses"]:
            real = jnp.arange(codes.shape[0], dtype=jnp.int32) < n_docs
            misses = misses + jnp.sum((codes == miss) & real, dtype=jnp.int32)
    return out, (sum(f.astype(jnp.int32) for f in flags) if flags else None), misses


def _grouped_all(aggs, cols, ops, mask, gid, ng, gather=None, doc_pad=None, dense_g=None):
    """Group counts + every agg partial. On TPU the count, ALL int32 SUM/AVG
    aggs and, where the plan stated no real group count (`dense_g` None), the
    SUM/AVG of every other value (DOUBLE, LONG past int32) fuse into ONE
    pallas byte-plane matmul pass on the MXU, such a value as fixed-point
    limbs (groupby_pallas.limb_planes). Whether its rows fit the limbs' window
    only the rows say: the reduction is a lax.cond on that flag, the limb sums
    on one side and today's scatter on the other, with the pass's own counts
    as an AVG's count on both.
    Every other agg takes its own reduction in `_agg_grouped`: SUM / AVG / MIN
    / MAX / MINMAXRANGE of a value that is not int32 the dense masked reduction
    where the plan stated `dense_g`, the real group count (it does up to
    plan.DENSE_REDUCE_MAX_GROUPS), and MIN / MAX / MINMAXRANGE the scatter
    otherwise; int32 MIN/MAX, HLL, histograms and presence matrices their
    scatters.
    gather/doc_pad: MV GROUP BY (value-space gids) gathers doc-space values
    first."""
    from pinot_tpu.ops import groupby_pallas as gp

    def rest(a):
        return _agg_grouped(a, cols, ops, mask, gid, ng, gather, doc_pad, dense_g)

    if gp.pallas_auto():
        vals, owner = [], {}
        for i, a in enumerate(aggs):
            if a[0] in ("sum", "avg"):
                v_raw = _value(a[1], cols, ops, doc_pad if gather is not None else mask.shape[0])
                if v_raw.dtype == jnp.int32 or dense_g is None:
                    owner[i] = len(vals)
                    vals.append(v_raw if gather is None else v_raw[gather])
        # _blocked splits doc sets past the int32 plane-accumulator bound
        # (SAFE_DOCS) into exact sub-ranges, so big flattened segment sets
        # (16M-row bench) still ride the MXU path
        sums, counts = gp.pallas_grouped_multi_sum_blocked(vals, gid, mask, ng)
        flags = getattr(_limb_fallbacks, "flags", None)
        parts = []
        for i, a in enumerate(aggs):
            if a[0] == "count":
                parts.append(counts)
            elif i in owner:
                s = sums[owner[i]]
                if isinstance(s, tuple):  # limbs: the sum and whether the rows fit its window
                    s, fits = s
                    v = vals[owner[i]].astype(_F)
                    s = jax.lax.cond(fits, lambda s=s: s, lambda v=v: _grouped_reduce("sum", v, gid, mask, ng, None))
                    if flags is not None:
                        flags.append(~fits)
                parts.append(s if a[0] == "sum" else (s, counts))
            else:
                parts.append(rest(a))
        return counts, tuple(parts)
    counts = _count_grouped(mask, gid, ng, dense_g)
    return counts, tuple(rest(a) for a in aggs)


# ---------------------------------------------------------------------------
# kernel construction
# ---------------------------------------------------------------------------


def _agg_eval(fspec, gspec, aggs, cols, ops, valid):
    """The full aggregation program body over an explicit doc-validity mask.
    Shared by build_fn (valid derived from an n_docs scalar) and
    build_masked_fn (the sharded executor's flattened multi-segment space,
    where validity comes per-position). Every group-spec kind — dense,
    MV-key, MV-pair cartesian, compact, sparse sort-compaction — evaluates here, so
    the sharded path supports the same group shapes as the per-segment one
    (groups_mv2 excluded: its per-doc offset/length operand tables index the
    proto's doc space, which does not exist in the sharded flat layout; and
    planned without the compact kind, whose overflow is answered by a second
    launch)."""
    n_padded = valid.shape[0]
    mask = valid & _filter(fspec, cols, ops, n_padded)
    matched = jnp.sum(mask, dtype=jnp.int32).astype(_I)
    if gspec is None:
        return matched, tuple(_agg_scalar(a, cols, ops, mask) for a in aggs)
    if gspec[0] == "groups_mv":
        # one MV group key: gids live in VALUE space — each doc
        # contributes once per value (Pinot MV group-by semantics)
        _, gcols, ng, strides_idx, mv_col, nv_idx = gspec
        docids = cols[f"{mv_col}!docs"]
        vmask = _mv_vmask(mv_col, nv_idx, cols, ops, mask)
        strides = ops[strides_idx]
        gid = jnp.zeros((cols[mv_col].shape[0],), dtype=jnp.int32)
        for i, c in enumerate(gcols):
            ids = cols[c] if c == mv_col else _key_ids(c, cols, ops)[docids]
            gid = gid + ids * strides[i]
        counts, parts = _grouped_all(
            aggs, cols, ops, vmask, gid, ng, gather=docids, doc_pad=n_padded
        )
        return matched, counts, parts
    if gspec[0] == "groups_mv2":
        # two MV keys: dense (base flat values x other max-len) pair
        # space — each pair is one cartesian (a_val, b_val) combination
        # of one doc (Pinot MV group-by cartesian semantics)
        _, gcols, ng, strides_idx, mv_a, nv_a, mv_b, off_idx, len_idx, lb = gspec
        docids = cols[f"{mv_a}!docs"]  # (va,)
        vmask_a = _mv_vmask(mv_a, nv_a, cols, ops, mask)
        d_off = ops[off_idx][docids]  # (va,)
        d_len = ops[len_idx][docids]
        j = jnp.arange(lb, dtype=jnp.int32)
        fidx = d_off[:, None] + j[None, :]  # (va, lb)
        pvalid = vmask_a[:, None] & (j[None, :] < d_len[:, None])
        nb = cols[mv_b].shape[0]
        ids_b = cols[mv_b][jnp.clip(fidx, 0, nb - 1)]
        strides = ops[strides_idx]
        va = docids.shape[0]
        gid2 = jnp.zeros((va, lb), dtype=jnp.int32)
        for i, c in enumerate(gcols):
            if c == mv_a:
                idc = cols[c][:, None]
            elif c == mv_b:
                idc = ids_b
            else:
                idc = _key_ids(c, cols, ops)[docids][:, None]
            gid2 = gid2 + idc * strides[i]
        pair_docids = jnp.broadcast_to(docids[:, None], (va, lb)).reshape(-1)
        counts, parts = _grouped_all(
            aggs,
            cols,
            ops,
            pvalid.reshape(-1),
            gid2.reshape(-1),
            ng,
            gather=pair_docids,
            doc_pad=n_padded,
        )
        return matched, counts, parts
    if gspec[0] == "groups_sparse":
        # high-cardinality product: 64-bit dense gids -> device sort
        # -> run-length compaction into U slots -> aggregate over the
        # compact slot space. The slot table `uniq` rides back so the
        # host can decode keys; n_unique > U is detected host-side
        # and falls back (slot collisions would corrupt results).
        _, gcols, u_slots, strides_idx = gspec
        strides = ops[strides_idx]
        gid64 = jnp.zeros((n_padded,), dtype=jnp.int64)
        for i, c in enumerate(gcols):
            gid64 = gid64 + _key_ids(c, cols, ops).astype(jnp.int64) * strides[i]
        sent = jnp.int64(1) << jnp.int64(62)
        gm = jnp.where(mask, gid64, sent)
        sg = jnp.sort(gm)
        first = jnp.concatenate([jnp.ones((1,), bool), sg[1:] != sg[:-1]]) & (sg < sent)
        n_unique = jnp.sum(first, dtype=jnp.int32)
        slot = jnp.clip(jnp.cumsum(first.astype(jnp.int32)) - 1, 0, u_slots - 1)
        uniq = jnp.full((u_slots,), sent, dtype=jnp.int64).at[slot].min(sg)
        cid = jnp.clip(jnp.searchsorted(uniq, gid64), 0, u_slots - 1).astype(jnp.int32)
        counts, parts = _grouped_all(aggs, cols, ops, mask, cid, u_slots)
        return matched, counts, parts, uniq, n_unique
    if gspec[0] == "groups_compact":
        # a large product of keys: each key renumbered by the values the
        # filter leaves, every aggregate over the compact slot id as over the
        # sort-compaction path's. The slot table and the number of
        # combinations ride back: past the slots the engine throws the
        # result away and launches the segment under the spec it had before
        # this kind (engine._finish_segment)
        _, gcols, slots, strides_idx, widths = gspec
        cid, slot_gids, total = _compact_groups(gcols, widths, slots, ops[strides_idx], cols, ops, mask)
        counts, parts = _grouped_all(aggs, cols, ops, mask, cid, slots)
        return matched, counts, parts, slot_gids, total
    # ("groups", cols, ng, strides[, real groups]): the plan appends the real
    # group count where a non-int32 reduction can use it (plan.group_spec)
    _, gcols, ng, strides_idx, *real = gspec
    strides = ops[strides_idx]
    gid = jnp.zeros((n_padded,), dtype=jnp.int32)
    for i, c in enumerate(gcols):
        gid = gid + _key_ids(c, cols, ops) * strides[i]
    counts, parts = _grouped_all(aggs, cols, ops, mask, gid, ng, dense_g=real[0] if real else None)
    return matched, counts, parts


@lru_cache(maxsize=1024)
def build_fn(spec: tuple):
    """Build the (un-jitted) program for a plan spec. Used directly when
    composing with vmap/shard_map in the sharded executor (parallel/mesh.py);
    plain callers use get_kernel for the jitted form."""

    kind = spec[0]

    if kind == "agg":
        _, fspec, gspec, aggs = spec

        def run(cols, ops, n_docs, n_padded):
            valid = jnp.arange(n_padded, dtype=jnp.int32) < n_docs
            return _agg_eval(fspec, gspec, aggs, cols, ops, valid)

        return run

    if kind == "mask":
        # filter-only program: the multistage leaf Scan's fused filter
        # (plan.plan_filter_mask). Returns the bool doc mask; caller trims
        # the padding tail.
        _, fspec = spec

        def run_mask(cols, ops, n_docs, n_padded):
            valid = jnp.arange(n_padded, dtype=jnp.int32) < n_docs
            return valid & _filter(fspec, cols, ops, n_padded)

        return run_mask

    if kind == "select":
        _, fspec, proj, k = spec

        def run_select(cols, ops, n_docs, n_padded):
            valid = jnp.arange(n_padded, dtype=jnp.int32) < n_docs
            mask = valid & _filter(fspec, cols, ops, n_padded)
            matched = jnp.sum(mask, dtype=_I)
            idx = jnp.nonzero(mask, size=k, fill_value=0)[0]
            outs = tuple(_value(p, cols, ops, n_padded)[idx] for p in proj)
            return matched, outs

        return run_select

    if kind == "select_ob":
        _, fspec, proj, kspec, desc, k = spec

        def run_ob(cols, ops, n_docs, n_padded):
            valid = jnp.arange(n_padded, dtype=jnp.int32) < n_docs
            mask = valid & _filter(fspec, cols, ops, n_padded)
            matched = jnp.sum(mask, dtype=_I)
            key = _value(kspec, cols, ops, n_padded).astype(_F)
            sort_key = jnp.where(mask, key if desc else -key, -jnp.inf)
            kk = min(k, n_padded)
            _, idx = jax.lax.top_k(sort_key, kk)
            outs = tuple(_value(p, cols, ops, n_padded)[idx] for p in proj)
            keys_out = key[idx]
            return matched, keys_out, outs

        return run_ob

    raise AssertionError(spec)


@lru_cache(maxsize=1024)
def build_masked_fn(spec: tuple):
    """Aggregation variant of build_fn taking an explicit validity mask
    instead of an n_docs scalar. Used by the sharded executor, which flattens
    a device's (S_local, P) stacked segments into ONE doc vector — aggregates
    are order-independent, so a single wide kernel call replaces a vmap over
    segments (vmap lowers poorly around pallas_call, and bigger flat ops fuse
    better anyway)."""
    kind = spec[0]
    assert kind == "agg", spec
    _, fspec, gspec, aggs = spec
    # mv2's per-doc offset/length operand tables index the PROTO doc space;
    # the sharded flat layout has no such space — execute_sharded falls back
    assert gspec is None or gspec[0] != "groups_mv2", gspec

    def run(cols, ops, valid):
        # doc length comes from the validity mask: cols may also hold MV
        # flat arrays whose length is the VALUE space, not the doc space
        return _agg_eval(fspec, gspec, aggs, cols, ops, valid)

    return run


def _canonical(x) -> str:
    """A spec as text that is the same in every process: no `hash()`, no set
    order, no numpy scalar's repr."""
    if isinstance(x, (tuple, list)):
        return "(" + ",".join(_canonical(v) for v in x) + ")"
    if isinstance(x, (set, frozenset)):
        return "{" + ",".join(sorted(_canonical(v) for v in x)) + "}"
    if isinstance(x, dict):
        return "{" + ",".join(sorted(f"{_canonical(k)}:{_canonical(v)}" for k, v in x.items())) + "}"
    if isinstance(x, np.generic):
        x = x.item()
    return repr(x)


@lru_cache(maxsize=4096)
def program_name(spec: tuple) -> str:
    """`seg_<kind>_<crc32 of the canonical spec, 8 hex>`: what the fused
    per-segment program of a plan shape is called — in the device trace
    (module `jit_<name>`), in `server.dispatch` spans and in a response's
    `deviceWork` — the same in every process and run of a checkout."""
    kind = spec[0]
    if kind == "agg":
        kind = "agg" if spec[2] is None else ("groupby" if spec[3] else "distinct")
    elif kind == "select_ob":
        kind = "select"
    return f"seg_{kind}_{zlib.crc32(_canonical(spec).encode()):08x}"


def _named(fn, spec: tuple):
    fn.__name__ = fn.__qualname__ = program_name(spec)
    return fn


@lru_cache(maxsize=1024)
def get_kernel(spec: tuple):
    """Jitted program for a plan spec. One compile per (spec, input shapes).
    n_padded (the doc-pad length) is static: cols may contain MV flat arrays,
    so the doc shape cannot be inferred from an arbitrary entry."""
    base = build_fn(spec)

    def run(cols, ops, n_docs, n_padded):
        return base(cols, ops, n_docs, n_padded)

    return jax.jit(_named(run, spec), static_argnums=3)


@lru_cache(maxsize=1024)
def get_packed_kernel(spec: tuple):
    """Jitted program whose outputs ride back in ONE float64 vector.

    A pytree of N output arrays is N device->host copies; packing makes a
    launch's outputs one vector, copied from the enqueue on
    (`dispatch_plan_packed`; a query waits once for its vectors). int64 leaves
    split into hi/lo 32-bit halves (two f64 chunks) so values past 2^53 —
    sparse group gids, raw LONG columns — survive exactly; everything else
    casts to f64 losslessly. A program that holds limb reductions
    (`_grouped_all`) appends one element more: how many of them scattered;
    one that holds lookUp gathers (`_lookup_codes`) another, last: how many of
    its rows had a foreign key without a dimension row.

    Unpack metadata is NOT captured at trace time: output shapes can vary
    with input shapes under one spec (select_ob's k is clipped to n_padded),
    so _packed_meta derives them per input-shape signature via eval_shape."""
    base = build_fn(spec)
    name = program_name(spec)

    def run(cols, ops, n_docs, n_padded):
        # runs when jax traces the program, once per input signature: the
        # registered kernels reached inside leave their static work behind
        with KERNELS.building(name, n_padded):
            out, fallbacks, misses = _side_counts(lambda: base(cols, ops, n_docs, n_padded), n_docs)
        leaves, _ = jax.tree.flatten(out)
        chunks = []
        for l in leaves:
            flat = jnp.ravel(l)
            if flat.dtype == jnp.int64:
                chunks.append(jnp.floor_divide(flat, 1 << 32).astype(jnp.float64))
                chunks.append(jnp.remainder(flat, 1 << 32).astype(jnp.float64))
            else:
                chunks.append(flat.astype(jnp.float64))
        for tail in (fallbacks, misses):  # past the tree's leaves, in this order: `wait_packed` counts them
            if tail is not None:
                chunks.append(tail.astype(jnp.float64)[None])
        if not chunks:
            return jnp.zeros((0,), dtype=jnp.float64)
        return jnp.concatenate(chunks)

    return jax.jit(_named(run, spec), static_argnums=3)


#: compile-cache observability (engine.kernelCache.*{cache=} on /metrics) —
#: the measurement baseline for the shared compile-cache work (ROADMAP 1)
_kernel_cache_obs = CacheObserver(get_kernel, cache="kernel")
_packed_cache_obs = CacheObserver(get_packed_kernel, cache="packed")


def _fused_cost(shape: dict) -> tuple[float, float]:
    """Bytes-moved / FLOPs model for the fused per-segment program: each of
    the plan's staged columns streams once at accumulator width (8 B) plus
    the filter mask, and every row/column pair costs ~4 flops (compare +
    mask + accumulate + combine)."""
    rows = max(float(shape.get("rows", 0)), 0.0)
    cols = max(float(shape.get("cols", 1)), 1.0)
    return rows * (cols * 8.0 + 1.0), rows * cols * 4.0


def _dense_cost(shape: dict) -> tuple[float, float]:
    """One dense grouped reduction: every row's value (8 B a DOUBLE, 4 B a
    count's ones), group id (4 B) and mask (1 B) stream once; a
    compare-select and an add a (row, slot) pair, so flops / (2 x rows) is the
    number of slots reduced over."""
    rows = max(float(shape.get("rows", 0)), 0.0)
    groups = max(float(shape.get("groups", 1)), 1.0)
    return rows * (float(shape.get("width", 8)) + 5.0), rows * groups * 2.0


def _compact_cost(shape: dict) -> tuple[float, float]:
    """One key renumbered: the rows' ids (4 B) and mask (1 B) stream for the
    presence, the ids again for the ranks, which are written (4 B); a
    compare, a select and an OR a (row, word of 32 values) pair for the
    presence, a compare and two selects for the ranks, so flops / (6 x rows)
    is the key's width in words."""
    rows = max(float(shape.get("rows", 0)), 0.0)
    return rows * 13.0, rows * -(-max(int(shape.get("groups", 1)), 1) // 32) * 6.0


KERNELS.register(
    "query.group_compact",
    _compact_key,
    cost_model=_compact_cost,
    description="one GROUP BY key of a large group space renumbered by the values the filter leaves (presence bits 32 values a word, then the rows' ranks: two dense passes over the words); one call a key traced",
)
KERNELS.register(
    "query.grouped_dense",
    _dense_grouped,
    cost_model=_dense_cost,
    description="grouped f64 SUM/MIN/MAX over few real groups as a dense masked reduction; one call a reduction traced",
)


def _key_ids(key, cols, ops):
    """The ids of one GROUP BY key: a dictionary-coded column's own codes, or,
    for an expression key ("remap", column, operand: plan.expr_key), the
    codes gathered through the plan's code -> bucket operand; a lookUp key
    ("lookup_key", node: plan.lookup_key) reads the node a lookUp filter of
    the same call reads."""
    if isinstance(key, str):
        return cols[key]
    return _lookup_codes(key[1], cols, ops) if key[0] == "lookup_key" else _key_gather(ops[key[2]], cols[key[1]])


_GATHER_LANES = 128  # a row of the table as the program views it: one vector register's lanes
_GATHER_BLOCK = 1 << 16  # codes a block: its gathered (block, lanes) int32 rows are 32 MiB


def _gather_rows(table, codes, in_bounds=False):
    """`table[codes]`, bit for bit, for a row-length vector of dictionary
    codes through a resident integer operand. XLA's gather of one element a
    code is a loop of 8.6 ns a row on a v5e whatever the table's size, so the
    table is viewed in the program as (entries / 128, 128) — a reshape of the
    operand, which stays one-dimensional in HBM — the row `code >> 7` is
    gathered and the lane `code & 127` picked by a compare against an iota
    and a sum, in which every lane but one adds an integer 0. The codes are
    walked in blocks of `_GATHER_BLOCK`, so that the gathered rows are a
    block's and never (rows, 128): 2 GiB for a segment of 4M rows. A table
    that is no whole number of rows (an expression key over a few values) is
    padded. `in_bounds` promises that no code lies outside the table; without
    it one that does reads what jnp's indexing reads (a negative code counts
    from the end, what is still outside is clamped)."""
    entries = table.shape[0]
    shift = _GATHER_LANES.bit_length() - 1
    rows = jnp.pad(table, (0, -entries % _GATHER_LANES)).reshape(-1, _GATHER_LANES)
    lanes = jnp.arange(_GATHER_LANES, dtype=jnp.int32)

    def block(c):
        c = c.astype(jnp.int32)  # a column's codes are staged as narrow as its dictionary allows
        if not in_bounds:
            c = jnp.clip(jnp.where(c < 0, c + entries, c), 0, entries - 1)
        hit = (c & (_GATHER_LANES - 1))[:, None] == lanes
        return jnp.sum(jnp.where(hit, rows.at[c >> shift].get(mode="promise_in_bounds"), 0), axis=1, dtype=table.dtype)

    n = codes.shape[0]
    if n <= _GATHER_BLOCK:
        return block(codes)
    return jax.lax.map(block, _blocked(codes, _GATHER_BLOCK)).reshape(-1)[:n]


def _gather_cost(shape: dict) -> tuple[float, float]:
    """One gather of the rows through a resident operand, by what it must
    move at least: every row's code in and word out (4 B each), the operand's
    entries (4 B) once."""
    return max(float(shape.get("rows", 0)), 0.0) * 8.0 + float(shape.get("entries", 0)) * 4.0, 0.0


def _lookup_gather(table, codes):
    """The rows' words through a resident fk code -> destination codes
    operand (`table`, a power of two past the dictionary: no code lies
    outside it), one call a gather traced under the registry name
    `query.lookup_gather` so that a launch's `deviceWork` says how many
    gathers it holds, over how many rows and entries."""
    return KERNELS.timed_sync(
        "query.lookup_gather",
        lambda: _gather_rows(table, codes, in_bounds=True),
        rows=codes.shape[0],
        entries=table.shape[0],
    )


def _key_gather(table, codes):
    """The rows' buckets of an expression key through the plan's code ->
    bucket operand (plan.expr_key), one call a gather traced under the
    registry name `query.key_gather`, as `_lookup_gather` is under its own."""
    return KERNELS.timed_sync(
        "query.key_gather",
        lambda: _gather_rows(table, codes),
        rows=codes.shape[0],
        entries=table.shape[0],
    )


def _lookup_codes(node, cols, ops):
    """The rows' destination codes of a lookUp node (plan.lookup_node):
    ("lookup", fk, operand, shift, mask, miss code, counts misses), the field
    of the word gathered through the operand. Within one traced packed
    program an operand is gathered once however many destinations, filters
    and keys read it, and the first node of a (dimension table, fk) leaves
    its codes for `_side_counts`."""
    state = getattr(_lookup_trace, "state", None)
    if state is not None and node in state["codes"]:
        return state["codes"][node]
    _, fk, operand, shift, mask, miss, counts = node
    words = state["words"].get((fk, operand)) if state is not None else None
    if words is None:
        words = _lookup_gather(ops[operand], cols[fk])
    codes = (words >> ops[shift]) & ops[mask]
    if state is not None:
        state["words"][(fk, operand)] = words
        state["codes"][node] = codes
        if counts:
            state["misses"].append((codes, ops[miss]))
    return codes


KERNELS.register(
    "query.lookup_gather",
    _lookup_gather,
    cost_model=_gather_cost,
    description="a lookUp's rows gathered through the resident foreign-key code -> destination code operand; one call a gather traced",
)
KERNELS.register(
    "query.key_gather",
    _key_gather,
    cost_model=_gather_cost,
    description="an expression GROUP BY key's rows gathered through the plan's code -> bucket operand; one call a gather traced",
)


def _scatter_cost(shape: dict) -> tuple[float, float]:
    """One grouped scatter, by what it must move at least: every row's value
    and group id once, the group table once; an add (or compare) a row."""
    rows = max(float(shape.get("rows", 0)), 0.0)
    width = float(shape.get("width", 8))
    return rows * (width + 4.0) + float(shape.get("groups", 0)) * width, rows


def _scatter_grouped(scatter, v, gid, ng):
    """The scatter side of `_grouped_reduce`: jax.ops.segment_{sum,min,max}
    into `ng` slots, one call a reduction traced under the registry name
    `query.grouped_scatter` so that a launch's `deviceWork` says how many
    scatters it holds, over how many rows and slots."""
    return KERNELS.timed_sync(
        "query.grouped_scatter",
        lambda: scatter(v, gid, num_segments=ng),
        rows=v.shape[0],
        groups=ng,
        width=v.dtype.itemsize,
    )


KERNELS.register(
    "query.grouped_scatter",
    _scatter_grouped,
    cost_model=_scatter_cost,
    description="grouped SUM/MIN/MAX of a value that is not int32 as a scatter (no real group count stated, or more than plan.DENSE_REDUCE_MAX_GROUPS; under the Pallas kernel a SUM's only where a row lies outside its limbs' window); one call a reduction traced",
)
KERNELS.register(
    "query.fused",
    get_kernel,
    cost_model=_fused_cost,
    description="fused filter+project+aggregate segment program (device outputs)",
)
KERNELS.register(
    "query.fused_packed",
    get_packed_kernel,
    cost_model=_fused_cost,
    description="fused segment program, outputs packed into one f64 vector",
)


@lru_cache(maxsize=4096)
def _holds_lookup(spec) -> bool:
    """Whether a plan spec holds a lookUp node, in a filter or a key (plan.lookup_node)."""
    if not isinstance(spec, tuple) or not spec:
        return False
    if spec[0] == "lookup":
        return True
    return any(_holds_lookup(x) for x in spec if isinstance(x, tuple))


@lru_cache(maxsize=4096)
def _packed_meta(spec: tuple, col_sig: tuple, op_sig: tuple, n_padded: int):
    """(treedef, [(shape, dtype)], packed size) of a spec's output tree for one
    input shape signature — abstract evaluation only, no compile. The signatures
    are (name, shape, dtype) a column and (shape, dtype) an operand, read
    off the launch's own arguments: shape tuples and dtype objects."""
    base = build_fn(spec)
    cols = {k: jax.ShapeDtypeStruct(s, d) for k, s, d in col_sig}
    ops = tuple(jax.ShapeDtypeStruct(s, d) for s, d in op_sig)
    out = jax.eval_shape(
        lambda c, o, nd: base(c, o, nd, n_padded),
        cols,
        ops,
        jax.ShapeDtypeStruct((), np.int32),
    )
    leaves, treedef = jax.tree.flatten(out)
    size = sum(l.size * (2 if l.dtype == np.int64 else 1) for l in leaves)
    return treedef, tuple((tuple(l.shape), np.dtype(l.dtype)) for l in leaves), size


#: opt-in device staging cache for operands DECLARED long-lived by their
#: owner (e.g. Dictionary.hll_hash_pad). Per-query operands (literals, LUTs,
#: docmasks) never enter: their id()s don't recur, so caching them would only
#: pin dead host+HBM memory. Entries evict via weakref callback when the host
#: array dies, so the cache is bounded by the owners' lifetimes. The lock
#: covers the server's concurrent scheduler/multistage worker threads.
_OP_CACHE_LOCK = threading.Lock()
_STABLE_OPS: dict[int, "weakref.ref"] = {}
_OP_DEVICE_CACHE: dict[int, tuple] = {}


def _op_cache_drop(key: int) -> None:
    with _OP_CACHE_LOCK:
        _STABLE_OPS.pop(key, None)
        _OP_DEVICE_CACHE.pop(key, None)


def mark_stable_operand(o: np.ndarray) -> np.ndarray:
    """Declare a host array stable (immutable + reused across queries): its
    device copy is staged once and kept until the array is collected."""
    key = id(o)
    with _OP_CACHE_LOCK:
        _STABLE_OPS[key] = weakref.ref(o, lambda _r, k=key: _op_cache_drop(k))
    return o


def stage_operand(o):
    """What a jitted program is handed for one plan operand. A per-query
    operand goes in as the numpy value the plan holds (plan.py `op_idx`
    made it an array of its final dtype): jit's own argument path moves it
    to the device with the launch, so it costs no transfer call of its own.
    Only an array its owner marked stable is staged — once, counted under
    `hostToDeviceTransfers` — and handed in as that device copy: a large
    long-lived array must not cross the link again with every query."""
    if not isinstance(o, np.ndarray):
        return o
    key = id(o)
    with _OP_CACHE_LOCK:
        ref = _STABLE_OPS.get(key)
        if ref is None or ref() is not o:
            return o
        ent = _OP_DEVICE_CACHE.get(key)
    if ent is not None and ent[0]() is o:
        return ent[1]
    dev = jnp.asarray(o)
    count("hostToDeviceTransfers")
    with _OP_CACHE_LOCK:
        _OP_DEVICE_CACHE[key] = (weakref.ref(o), dev)
    return dev


def plan_columns(plan, arrays) -> dict:
    """A program's column arguments out of a staged table's arrays: the plan's
    columns under their names, its raw value columns under their places
    ("@0", "@1", ...: plan._Lowering.raw_value)."""
    cols = {c: arrays[c] for c in plan.columns}
    cols.update((f"@{i}", arrays[c]) for i, c in enumerate(plan.value_columns))
    return cols


def _plan_inputs(plan, device_segment):
    """Device column dict + operand tuple for a plan (shared by run_plan and
    run_plan_packed; owns the no-columns '__shape__' dummy convention)."""
    cols = plan_columns(plan, device_segment.arrays)
    if not cols:
        # query touches no columns (e.g. SELECT COUNT(*) FROM t): feed a
        # dummy array for shape discovery
        any_col = next(iter(device_segment.arrays))
        cols = {"__shape__": device_segment.arrays[any_col]}
    ops = tuple(stage_operand(o) for o in plan.operands)
    return cols, ops


class PackedResult:
    """One enqueued launch of a packed program. Its result vector has been on
    its way to the host since the enqueue; calling the object re-inflates the
    output tree, after `wait_packed` — the caller's, for all launches of a
    query at once, or its own if none was made."""

    __slots__ = ("program", "rows", "wait_ms", "_vec", "_host", "_tree", "_n_cols", "_treedef", "_leaf_meta", "_size", "_lookups")

    def __init__(self, program: str, rows: int, vec, n_cols: int, treedef, leaf_meta, size: int, lookups: bool = False):
        # what was launched, for the caller's `server.dispatch` span
        self.program, self.rows = program, rows
        self.wait_ms = 0.0
        self._vec, self._host, self._tree = vec, None, None
        self._n_cols, self._treedef, self._leaf_meta, self._size = n_cols, treedef, leaf_meta, size
        self._lookups = lookups  # the vector's last element counts lookUp misses (`_holds_lookup`)

    def __call__(self):
        if self._tree is not None:  # asked before: a compact launch's overflow check reads the tree ahead of its conversion
            return self._tree
        if self._host is None:
            wait_packed((self,))
        v = self._host
        out = []
        i = 0
        for shape, dtype in self._leaf_meta:
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            if dtype == np.int64:
                hi = v[i : i + size]
                lo = v[i + size : i + 2 * size]
                i += 2 * size
                chunk = (hi.astype(np.int64) << 32) + lo.astype(np.int64)
            else:
                chunk = v[i : i + size]
                i += size
                if dtype != np.float64:
                    chunk = chunk.astype(dtype)
            out.append(chunk.reshape(shape))
        self._tree = jax.tree.unflatten(self._treedef, out)
        return self._tree


def wait_packed(results, checkpoint=None) -> None:
    """THE device->host wait of a query: one `server.device_wait` span and
    one `deviceReadbackWaits` around the arrival of every result vector not
    yet on the host, `groupedLimbFallbacks`: how many of the launches' limb
    reductions found a row outside their window and scattered, and
    `lookupMisses`: how many of their rows had a foreign key without a dimension
    row (the elements past the tree's leaves, `get_packed_kernel`). The copies were started when the programs were
    enqueued, so the first wait covers what is queued on the device and the
    others find their vector there or on its way; nothing else runs inside
    the span. `checkpoint(i)`, where given, runs before the i-th vector is
    waited for (the caller's deadline and kill checks: a raise leaves the
    rest to arrive unread). Each launch then enters kernel_obs' ledger as
    one call of `query.fused_packed` with its own share of the wait."""
    # imported here: a line more at the top of the file moves the call-site
    # lines in the traced programs' Mosaic payloads, and their cache keys
    import time

    pending = [(i, r) for i, r in enumerate(results) if r._host is None]
    if not pending:
        return
    with span("server.device_wait"):
        t = time.perf_counter()
        for i, r in pending:
            if checkpoint is not None:
                checkpoint(i)
            r._host = np.asarray(r._vec)
            r._vec = None
            now = time.perf_counter()
            r.wait_ms, t = (now - t) * 1e3, now
    count("deviceReadbackWaits")
    # past the leaves: the limb reductions that scattered, where the program holds any, then the lookUp misses likewise
    fallbacks = sum(int(r._host[r._size]) for _, r in pending if r._host.shape[0] - r._lookups > r._size)
    if fallbacks:
        count("groupedLimbFallbacks", fallbacks)
    misses = sum(int(r._host[-1]) for _, r in pending if r._lookups)
    if misses:
        count("lookupMisses", misses)
    if KERNELS.enabled:
        for _, r in pending:
            KERNELS.record("query.fused_packed", r.wait_ms, rows=r.rows, cols=r._n_cols)


def dispatch_plan_packed(plan, device_segment) -> PackedResult:
    """Async half of run_plan_packed: ENQUEUE the packed kernel (jax
    dispatch is non-blocking) with its operands as arguments of the call,
    start the result vector's copy to the host behind it, and return the
    launch's `PackedResult`. One `hostToDeviceTransfers` a launch: columns
    are resident, the operands and the doc count cross with the call. A
    caller dispatches every segment of a query first and waits once
    (`wait_packed`), then unpacks each."""
    cols, ops = _plan_inputs(plan, device_segment)
    kernel = get_packed_kernel(plan.spec)
    _packed_cache_obs.observe()
    rows = device_segment.padded
    name = kernel.__name__  # program_name(plan.spec), without hashing the spec again
    # the one call into the runtime, apart from the planning around it: the operands' transfer, the enqueue
    # and whatever PJRT makes the caller wait for (launches in flight, a staging still on its way)
    with span("server.launch", program=name):
        vec = kernel(cols, ops, np.int32(device_segment.n_docs), rows)
        vec.copy_to_host_async()
    count("hostToDeviceTransfers")
    ledger = active_ledger()
    if ledger is not None:
        ledger.add_device_work(name, rows, KERNELS.program_work(name, rows))
    # the key as the arrays give it, nothing sorted and no string built: segments of one table
    # plan their columns in one order and share the entry; another order would only add one
    treedef, leaf_meta, size = _packed_meta(
        plan.spec,
        tuple((k, v.shape, v.dtype) for k, v in cols.items()),
        tuple((o.shape, o.dtype) for o in ops),
        rows,
    )
    return PackedResult(name, rows, vec, len(cols), treedef, leaf_meta, size, _holds_lookup(plan.spec))


def run_plan_packed(plan, device_segment):
    """run_plan variant returning host numpy outputs via ONE device->host
    transfer (see get_packed_kernel)."""
    return dispatch_plan_packed(plan, device_segment)()


def run_plan(plan, device_segment):
    """Execute a SegmentPlan against a DeviceSegment; returns device outputs."""
    kernel = get_kernel(plan.spec)
    _kernel_cache_obs.observe()
    cols, ops = _plan_inputs(plan, device_segment)
    return kernel(cols, ops, np.int32(device_segment.n_docs), device_segment.padded)
