"""Grouped SUM / AVG / MIN / MAX / MINMAXRANGE of values that are not int32
(DOUBLE columns and expressions, LONG past int32): the dense masked reduction
that a small real group count selects, against the scatter it replaces,
against the host executor on the CPU's true f64; and, where no real group
count is stated and the Pallas kernel is on, a SUM / AVG as fixed-point limbs
on the byte-plane pass, against the scatter, the host and `math.fsum`.

The form is chosen from the value's dtype, the plan's real group count and —
limbs or the scatter — the rows' exponents alone (`kernels._grouped_reduce`,
`kernels._grouped_all`, `plan.with_real_groups`): the scatter leg here is the
same query planned with `DENSE_REDUCE_MAX_GROUPS` patched to 0, which is the
spec — and so the program — PR 28's parent gave it; the limb leg is that spec
with the kernel on (interpreted: `PINOT_TPU_PALLAS=1` on the CPU), which is
what the chip runs past `DENSE_REDUCE_MAX_GROUPS` groups.
"""

import functools
import math

import numpy as np
import pytest

import pinot_tpu  # noqa: F401  (x64 before jax.numpy is touched)
import jax
import jax.numpy as jnp

from pinot_tpu.common import DataType, FieldSpec, Schema
from pinot_tpu.common.kernel_obs import KERNELS
from pinot_tpu.common.trace import request_ledger
from pinot_tpu.ops import groupby_pallas as gp
from pinot_tpu.query import QueryEngine, kernels
from pinot_tpu.query import engine as engine_mod
from pinot_tpu.query import plan as plan_mod
from pinot_tpu.query.plan import DeviceFallback, plan_segment
from pinot_tpu.segment import SegmentBuilder

T = plan_mod.DENSE_REDUCE_MAX_GROUPS
KINDS = ("sum", "avg", "min", "max", "minmaxrange")
GROUPS = (1, 6, 8, T, T + 1, 300)
MASKS = ("all", "none", "one_empty", "nan_empty")
VALUES = {"column": "x", "product": "x * (1 - d)", "long": "w"}
DENSE = "query.grouped_dense"


def bucket(groups: int) -> int:
    """The real group count as the plan states it (plan.with_real_groups)."""
    step = max(8, (1 << (groups - 1).bit_length() if groups > 1 else 1) // 8)
    return min(-(-groups // step) * step, -(-groups // 256) * 256)


def takes_dense(groups: int) -> bool:
    return bucket(groups) <= T


# ---------------------------------------------------------------------------
# through the engine: dense against scatter against the host executor
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def table(groups: int):
    """One segment: key `k` with `groups` values (6 is Q1's 3 x 2 over two
    keys), two DOUBLE metrics, a LONG past int32 and an INT for the filters."""
    rng = np.random.default_rng(groups)
    n = max(2400, 2 * groups)
    k = rng.permutation(np.arange(n, dtype=np.int32) % groups)
    schema = Schema.build(
        "t",
        dimensions=[("k", DataType.INT), ("a", DataType.INT), ("b", DataType.INT)],
        metrics=[("x", DataType.DOUBLE), ("d", DataType.DOUBLE), ("w", DataType.LONG), ("q", DataType.INT)],
    )
    data = {
        "k": k, "a": k // 2, "b": k % 2,
        "x": np.round(rng.random(n) * 1e5, 2) - 2e4,
        "d": rng.integers(0, 11, n) / 100.0,
        "w": rng.integers(1 << 33, 1 << 40, n).astype(np.int64),
        "q": rng.integers(0, 50, n).astype(np.int32),
    }  # fmt: skip
    return SegmentBuilder(schema).build(data, f"t{groups}")


def sql(groups: int, mask: str) -> str:
    keys = "a, b" if groups == 6 else "k"
    # the per-aggregate FILTER empties group 0 and leaves it in the answer: its
    # aggregates show what a reduction leaves where no row arrived
    filt = " FILTER (WHERE k <> 0)" if mask in ("one_empty", "nan_empty") else ""
    aggs = ", ".join(
        f"{fn}({expr}){filt}"
        for expr in VALUES.values()
        for fn in ("SUM", "AVG", "MIN", "MAX", "MINMAXRANGE")
    )
    return (
        ("SET enableNullHandling = true; " if mask == "nan_empty" else "")
        + f"SELECT {keys}, {aggs}, COUNT(*) FROM t"
        + (" WHERE q < 0" if mask == "none" else "")
        + f" GROUP BY {keys} ORDER BY {keys} LIMIT 100000"
    )


def _fallback(*_a, **_k):
    raise DeviceFallback("the host executor is the reference here")


@functools.lru_cache(maxsize=None)
def answers(groups: int, mask: str):
    """(dense rows, scatter rows, host rows, dense deviceWork, scatter
    deviceWork) of one query; an engine a leg, so nothing is shared but the
    segment."""
    seg, q = table(groups), sql(groups, mask)
    with request_ledger("dense") as led:
        dense = QueryEngine([seg]).execute(q).rows
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(plan_mod, "DENSE_REDUCE_MAX_GROUPS", 0)
        with request_ledger("scatter") as led0:
            scatter = QueryEngine([seg]).execute(q).rows
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "plan_segment", _fallback)
        host = QueryEngine([seg]).execute(q).rows
    return dense, scatter, host, led.to_wire()["deviceWork"], led0.to_wire()["deviceWork"]


def same(got, want) -> bool:
    if got is None or want is None:
        return got is None and want is None
    got, want = float(got), float(want)
    if math.isnan(want) or math.isinf(want):
        return (math.isnan(got) and math.isnan(want)) or got == want
    return abs(got - want) <= 1e-12 * max(abs(want), 1e-300)


@pytest.mark.parametrize("value", list(VALUES))
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("kind", KINDS)
def test_dense_scatter_and_host_agree(kind, groups, mask, value):
    dense, scatter, host, _, _ = answers(groups, mask)
    n_keys = 2 if groups == 6 else 1
    col = n_keys + list(VALUES).index(value) * len(KINDS) + KINDS.index(kind)
    assert len(dense) == len(scatter) == len(host) == (0 if mask == "none" else groups)
    for d, s, h in zip(dense, scatter, host):
        assert d[:n_keys] == s[:n_keys] == h[:n_keys]
        assert same(d[col], h[col]) and same(s[col], h[col]), (d[:n_keys], d[col], s[col], h[col])
        assert d[-1] == s[-1] == h[-1]  # COUNT(*)


@pytest.mark.parametrize("mask", ("one_empty", "nan_empty"))
def test_an_emptied_group_reads_what_the_scatter_leaves(mask):
    """0.0 for a sum, +/-inf for the extremes (NULL for all under null
    handling, where the wrapper turns the empty sum into NaN)."""
    dense, scatter, host, _, _ = answers(8, mask)
    first, then = dense[0], dense[1]
    assert first[0] == 0 and first == scatter[0]
    s, _avg, mn, mx, _rng = first[1:6]
    if mask == "nan_empty":
        assert s is None and host[0][1] is None
    else:
        assert s == 0.0 and mn == math.inf and mx == -math.inf
    assert all(v is not None and math.isfinite(v) for v in then[1:6])


@pytest.mark.parametrize("groups", GROUPS)
def test_device_work_names_the_dense_kernel_exactly_when_it_ran(groups):
    _, _, _, work, work0 = answers(groups, "all")
    ((name, w),) = work.items()
    ((name0, w0),) = work0.items()
    assert name.startswith("seg_groupby_") and w["launches"] == w0["launches"] == 1
    assert DENSE not in w0["kernels"]  # the parent's program never names it
    if not takes_dense(groups):
        assert name == name0 and DENSE not in w["kernels"]
        return
    assert name != name0
    k = w["kernels"][DENSE]
    # one call a reduction traced: SUM, AVG's sum and count, MIN, MAX and MINMAXRANGE's
    # two, for each of the three values; COUNT(*) and the group counts, which the Pallas
    # kernel has on the chip (XLA folds the duplicates; the trace counts them)
    assert k["calls"] == 3 * 7 + 2
    rows = w["rows"]
    assert k["flops"] == k["calls"] * rows * bucket(groups) * 2.0
    assert k["bytes"] == rows * (3 * 6 * 13.0 + 5 * 9.0)


# ---------------------------------------------------------------------------
# through the engine: limbs (the kernel interpreted) against scatter and host
# ---------------------------------------------------------------------------

SCATTER = "query.grouped_scatter"
PLANES = "ops.grouped_planes2"


def with_limbs(seg, q: str):
    """(rows, deviceWork, counters) of `q` as the chip runs it where the plan
    states no real group count: the Pallas pass on, interpreted. The programs
    are traced under the patched environment and dropped after it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PINOT_TPU_PALLAS", "1")
        mp.setattr(plan_mod, "DENSE_REDUCE_MAX_GROUPS", 0)
        kernels.get_packed_kernel.cache_clear()
        try:
            with request_ledger("limbs") as led:
                rows = QueryEngine([seg]).execute(q).rows
        finally:
            kernels.get_packed_kernel.cache_clear()
    wire = led.response_fields()
    return rows, wire["deviceWork"], wire["counters"]


@functools.lru_cache(maxsize=None)
def limb_answers(groups: int, mask: str):
    return with_limbs(table(groups), sql(groups, mask))


@pytest.mark.parametrize("value", list(VALUES))
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("kind", KINDS)
def test_limbs_scatter_and_host_agree(kind, groups, mask, value):
    """The program the chip runs past T groups: SUM and AVG on the byte-plane
    pass as limbs, the others on their scatters beside it."""
    _, scatter, host, _, _ = answers(groups, mask)
    limbs, _, counters = limb_answers(groups, mask)
    n_keys = 2 if groups == 6 else 1
    col = n_keys + list(VALUES).index(value) * len(KINDS) + KINDS.index(kind)
    assert len(limbs) == len(host) == (0 if mask == "none" else groups)
    for l, s, h in zip(limbs, scatter, host):
        assert l[:n_keys] == h[:n_keys] and l[-1] == h[-1]
        assert same(l[col], h[col]), (l[:n_keys], l[col], s[col], h[col])
    assert counters["groupedLimbFallbacks"] == 0  # prices, small factors, LONGs under 2^40: all inside 96 bits


@pytest.mark.parametrize("groups", GROUPS)
def test_device_work_of_a_limb_program_still_names_the_scatter_once_a_double_aggregate(groups):
    """What `perfbench/layer_metrics/grouped_double_hbm_share.py` reads: the
    scatter is traced as the other branch of each limb reduction, under its
    registered name, so `calls` is still one a DOUBLE aggregate."""
    seg = table(groups)
    keys = "a, b" if groups == 6 else "k"
    rows, work, counters = with_limbs(seg, f"SELECT {keys}, AVG(x), SUM(x * (1 - d)), SUM(q), COUNT(*) FROM t GROUP BY {keys} LIMIT 100000")
    ((name, w),) = work.items()
    assert name.startswith("seg_groupby_") and w["launches"] == 1 and len(rows) == groups
    assert w["kernels"][SCATTER]["calls"] == 2  # AVG(x) and SUM(x * (1 - d)); AVG's count is the pass's own
    planes = w["kernels"][PLANES]
    ng, r = -(-groups // 256) * 256, 2 * gp.LIMBS + 4 + 1  # two values as limbs, SUM(q)'s four byte planes, the mask
    assert planes["calls"] == 1 and planes["flops"] == -(-w["rows"] // gp.PLANES_CHUNK) * gp.PLANES_CHUNK * ng * 2.0 * r
    assert counters["groupedLimbFallbacks"] == 0


@functools.lru_cache(maxsize=None)
def spread_table():
    """`y` spans 2^-40 .. 2^40 with every significand bit in use: 133 bits,
    past any window of 96; `z` holds an infinity; `x` is a price."""
    rng = np.random.default_rng(36)
    n, groups = 6000, 5000
    schema = Schema.build(
        "s", dimensions=[("k", DataType.INT)],
        metrics=[("x", DataType.DOUBLE), ("y", DataType.DOUBLE), ("z", DataType.DOUBLE)],
    )  # fmt: skip
    z = rng.random(n)
    z[17] = np.inf
    data = {
        "k": rng.permutation(np.arange(n, dtype=np.int32) % groups),
        "x": np.round(rng.random(n) * 1e5, 2),
        "y": rng.normal(0, 1, n) * 2.0 ** rng.integers(-40, 41, n),
        "z": z,
    }
    return SegmentBuilder(schema).build(data, "s0"), data


def test_rows_past_the_window_take_the_scatter_and_are_counted():
    seg, data = spread_table()
    q = "SELECT k, SUM(x), AVG(y), SUM(y), SUM(z) FROM s GROUP BY k ORDER BY k LIMIT 100000"
    rows, work, counters = with_limbs(seg, q)
    assert counters["groupedLimbFallbacks"] == 3  # AVG(y), SUM(y), SUM(z); SUM(x) fits
    ((_, w),) = work.items()
    assert w["kernels"][SCATTER]["calls"] == 4 and w["kernels"][PLANES]["calls"] == 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(plan_mod, "DENSE_REDUCE_MAX_GROUPS", 0)
        scatter = QueryEngine([seg]).execute(q).rows  # the kernel off: every sum the scatter's
    assert len(rows) == len(scatter) == 5000
    for l, s in zip(rows, scatter):
        assert l[0] == s[0] and same(l[1], s[1]) and l[2:] == s[2:], (l, s)  # the same scatter: the same bits
    assert rows[data["k"][17]][4] == math.inf
    k = data["k"]
    assert rows[0][1] == math.fsum(data["x"][k == 0])  # the limbs' sum is the exact one, rounded once


# ---------------------------------------------------------------------------
# the limbs alone: groupby_pallas.pallas_grouped_multi_sum[_blocked] handed a DOUBLE
# ---------------------------------------------------------------------------


def _limb_case(name: str):
    """(values, gid, mask, ng, fits) of one case; `values` a list of arrays."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n, ng = 9000, 40  # three chunks, the last one padded
    gid = rng.integers(0, ng, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    # exponents up to `bits` apart, every significand bit in use: 53 + bits bits of window
    wide = lambda bits: rng.choice([-1.0, 1.0], n) * (1 + rng.random(n)) * 2.0 ** rng.integers(0, bits + 1, n)  # noqa: E731
    fits = True
    if name == "negatives":
        v = [rng.normal(0, 1e5, n)]
    elif name == "exact_zeros":
        v = [np.where(rng.random(n) < 0.5, 0.0, rng.normal(0, 3, n)) * np.where(rng.random(n) < 0.5, -1.0, 1.0)]
    elif name == "clamps":
        v = [np.clip(rng.normal(50, 60, n), 0.0, 100.0)]  # a third of the rows 0.0 or 100.0, as TSBS's walks
    elif name == "spread_inside":
        v = [wide(8 * gp.LIMBS - 53)]  # the widest that fits: 43 bits
    elif name == "spread_past":
        v, fits = [wide(8 * gp.LIMBS - 52)], False  # one bit more
    elif name in ("nan", "inf", "neg_inf"):
        v = [rng.normal(0, 1, n)]
        v[0][np.flatnonzero(mask)[5]] = {"nan": np.nan, "inf": np.inf, "neg_inf": -np.inf}[name]
        fits = False
    elif name == "nan_masked_out":
        v = [rng.normal(0, 1, n)]
        v[0][np.flatnonzero(~mask)[:50]] = np.nan
    elif name == "past_float32":
        v, fits = [rng.normal(0, 1e300, n)], False
    elif name == "under_float32":
        v, fits = [rng.normal(0, 1e-60, n)], False
    elif name == "emptied_group":
        v = [rng.normal(0, 1e3, n)]
        mask = mask & (gid != 3)
    elif name == "all_masked":
        v, mask = [rng.normal(0, 1, n)], np.zeros(n, bool)
    elif name == "long_past_int32":
        v = [rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)]
    elif name == "cancellation":
        v = [np.where(np.arange(n) % 2 == 0, 2.0**60, -(2.0**60)) + np.round(rng.normal(0, 1e3, n))]
    elif name == "five_values":
        v = [np.clip(rng.normal(50, 60, n), 0.0, 100.0) for _ in range(5)] + [rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)]
    elif name == "fits_beside_one_that_does_not":
        v, fits = [rng.normal(0, 1, n) * 2.0 ** rng.integers(-15, 15, n), wide(60)], (True, False)
    else:
        raise AssertionError(name)
    return v, gid, mask, ng, fits


LIMB_CASES = (
    "negatives", "exact_zeros", "clamps", "spread_inside", "spread_past", "nan", "inf", "neg_inf", "nan_masked_out",
    "past_float32", "under_float32", "emptied_group", "all_masked", "long_past_int32", "cancellation", "five_values",
    "fits_beside_one_that_does_not",
)  # fmt: skip


def _fsums(v, gid, mask, ng):
    v = np.asarray(v, np.float64)
    return np.array([math.fsum(v[mask & (gid == g)]) for g in range(ng)])


@pytest.mark.parametrize("blocked", (False, True), ids=("one_block", "safe_docs_crossed"))
@pytest.mark.parametrize("case", LIMB_CASES)
def test_limb_sums_are_the_exact_sum_or_say_that_they_are_not(case, blocked, monkeypatch):
    """Where `fits`, every group's sum is `math.fsum` of its rows bit for bit
    (exact limb sums, one rounding) — never further from the true sum than
    any running f64 sum; where a row lies outside the window, `fits` is False
    and the caller takes the scatter. The count is the mask plane's either way."""
    vals, gid, mask, ng, fits = _limb_case(case)
    if blocked:
        monkeypatch.setattr(gp, "SAFE_DOCS", 4200)  # three blocks of one chunk
    fn = gp.pallas_grouped_multi_sum_blocked if blocked else gp.pallas_grouped_multi_sum
    sums, counts = fn([jnp.asarray(v) for v in vals], jnp.asarray(gid), jnp.asarray(mask), ng)
    assert np.array_equal(np.asarray(counts), np.bincount(gid[mask], minlength=ng))
    fits = fits if isinstance(fits, tuple) else (fits,) * len(vals)
    for v, s, want_fits in zip(vals, sums, fits):
        if v.dtype == np.int32:  # byte planes, as ever
            assert np.array_equal(np.asarray(s), np.bincount(gid[mask], weights=v[mask].astype(np.float64), minlength=ng))
            continue
        s, got_fits = s
        assert bool(got_fits) is want_fits
        if want_fits:
            got, want = np.asarray(s), _fsums(v, gid, mask, ng)
            assert got.dtype == np.float64 and np.array_equal(got, want), np.flatnonzero(got != want)
    if case == "emptied_group":
        assert np.asarray(sums[0][0])[3] == 0.0 and counts[3] == 0


def test_limbs_stay_byte_limbs_and_a_row_is_their_sum():
    """What the kernel's exactness rests on: every limb an integer in
    [-255, 255], zero where the row is masked out, and a row's limbs its value."""
    from fractions import Fraction

    rng = np.random.default_rng(7)
    n = 4096
    v = rng.normal(0, 1, n) * 2.0 ** rng.integers(-8, 8, n)
    mask = rng.random(n) < 0.9
    planes, w0, fits = gp.limb_planes(jnp.asarray(v), jnp.asarray(mask))
    planes, w0 = np.asarray(planes), int(w0)
    assert bool(fits) and planes.shape == (gp.LIMBS, n) and planes.dtype == np.float32
    assert np.array_equal(planes, np.rint(planes)) and np.abs(planes).max() <= 255 and not planes[:, ~mask].any()
    limbs = planes.astype(np.int64)
    for i in np.flatnonzero(mask)[:500]:
        assert Fraction(v[i]) == sum(int(limbs[j, i]) << (8 * j) for j in range(gp.LIMBS)) * Fraction(2) ** w0


def test_the_sharded_executors_program_takes_limbs_too():
    """`build_masked_fn` (parallel/mesh.py) reaches the same `_grouped_all`:
    no collector of fallback flags is open there, and the reduction still
    chooses by its rows."""
    seg, data = spread_table()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PINOT_TPU_PALLAS", "1")
        plan = plan_segment(seg, QueryEngine([seg]).make_context("SELECT k, SUM(x), SUM(y) FROM s GROUP BY k LIMIT 10"))
        cols, ops = kernels._plan_inputs(plan, seg.to_device())
        valid = jnp.arange(seg.to_device().padded) < seg.n_docs
        kernels.build_masked_fn.cache_clear()
        try:
            _, counts, (sx, sy) = jax.jit(kernels.build_masked_fn(plan.spec))(cols, ops, valid)
        finally:
            kernels.build_masked_fn.cache_clear()
    k = data["k"]
    assert np.asarray(counts)[:5000].sum() == 6000
    assert np.asarray(sx)[7] == math.fsum(data["x"][k == 7])  # limbs
    assert same(np.asarray(sy)[7], math.fsum(data["y"][k == 7]))  # the scatter


# ---------------------------------------------------------------------------
# the helper alone
# ---------------------------------------------------------------------------

_NP = {"sum": (np.add, 0.0), "min": (np.minimum, np.inf), "max": (np.maximum, -np.inf)}


@pytest.mark.parametrize("mask_kind", ("all", "none", "one_empty"))
@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("kind", sorted(_NP))
def test_grouped_reduce_fills_every_slot_as_the_scatter_does(kind, groups, mask_kind):
    rng = np.random.default_rng(groups)
    n = 20_000
    v = rng.normal(0, 1e6, n)
    gid = rng.integers(0, groups, n).astype(np.int32)
    mask = {"all": np.ones(n, bool), "none": np.zeros(n, bool), "one_empty": gid != groups - 1}[mask_kind]
    ng = -(-groups // 256) * 256
    ufunc, fill = _NP[kind]
    want = np.full(ng, fill)
    ufunc.at(want, gid[mask], v[mask])
    args = (jnp.asarray(v), jnp.asarray(gid), jnp.asarray(mask))
    with KERNELS.building("test_grouped_reduce", n):
        dense = np.asarray(jax.jit(lambda *a: kernels._grouped_reduce(kind, *a, ng, bucket(groups)))(*args))
    work = KERNELS.program_work("test_grouped_reduce", n)
    scatter = np.asarray(jax.jit(lambda *a: kernels._grouped_reduce(kind, *a, ng, None))(*args))
    assert DENSE in work  # a stated count is what selects the dense form; the plan states none past T
    assert dense.shape == scatter.shape == (ng,) and dense.dtype == np.float64
    np.testing.assert_allclose(dense, want, rtol=1e-12, atol=0)
    np.testing.assert_allclose(scatter, want, rtol=1e-12, atol=0)
    if mask_kind == "one_empty" and groups > 1:
        assert dense[groups - 1] == fill == scatter[groups - 1]
    assert (dense[groups:] == fill).all()


def test_count_goes_with_the_reduction():
    """AVG's own count and the NaN-empty wrapper's are int64 either way."""
    rng = np.random.default_rng(5)
    gid = jnp.asarray(rng.integers(0, 6, 9000).astype(np.int32))
    mask = jnp.asarray(rng.random(9000) < 0.7)
    dense = np.asarray(kernels._count_grouped(mask, gid, 256, 8))
    scatter = np.asarray(kernels._count_grouped(mask, gid, 256))
    assert dense.dtype == scatter.dtype == np.int64 and (dense == scatter).all() and dense.sum() == int(mask.sum())


# ---------------------------------------------------------------------------
# the plan's rule
# ---------------------------------------------------------------------------


def gspec_of(seg, q: str):
    return plan_segment(seg, QueryEngine([seg]).make_context(q)).spec[2]


@pytest.mark.parametrize(
    "groups, aggs, real",
    [
        (6, "SUM(x)", 8),
        (6, "SUM(q), AVG(q), COUNT(*)", None),  # int32 metrics and COUNT: the spec of always
        (6, "SUM(q * q - q)", None),  # +, -, * of int32 stay int32
        (6, "SUM(q * 2)", 8),  # a literal is a DOUBLE
        (6, "MIN(w)", 8),  # a LONG the device keeps as int64
        (6, "MAX(q), MINMAXRANGE(q)", None),
        (6, "COUNT(*) FILTER (WHERE q > 3), SUM(d) FILTER (WHERE q > 3)", 8),
        (300, "AVG(x)", 320),
        (T, "SUM(x)", T),
        (T + 1, "SUM(x)", None),  # past T: the scatter's spec
    ],
)
def test_the_plan_states_the_real_group_count_where_a_wide_reduction_can_use_it(groups, aggs, real):
    keys = "a, b" if groups == 6 else "k"
    gspec = gspec_of(table(groups), f"SELECT {keys}, {aggs} FROM t GROUP BY {keys} LIMIT 10")
    assert gspec[0] == "groups" and gspec[2] == -(-groups // 256) * 256
    assert gspec[4:] == (() if real is None else (real,))
    assert real is None or real == bucket(groups)


def test_a_long_that_fits_int32_is_an_int32_metric():
    schema = Schema.build("n", dimensions=[("k", DataType.INT)], metrics=[("v", DataType.LONG)])
    seg = SegmentBuilder(schema).build(
        {"k": np.arange(100, dtype=np.int32) % 5, "v": np.arange(100, dtype=np.int64)}, "n0"
    )
    assert len(gspec_of(seg, "SELECT k, SUM(v) FROM n GROUP BY k LIMIT 10")) == 4


def test_mv_keys_and_the_sort_compaction_path_keep_their_specs():
    schema = Schema.build(
        "m", dimensions=[("k", DataType.INT), ("hi", DataType.INT), ("hj", DataType.INT)], metrics=[("x", DataType.DOUBLE)]
    )
    schema.add(FieldSpec("tags", DataType.INT, single_value=False))
    n = 3000
    rng = np.random.default_rng(9)
    tags = np.empty(n, dtype=object)
    for i in range(n):
        tags[i] = rng.integers(0, 5, int(rng.integers(1, 4))).astype(np.int32).tolist()
    seg = SegmentBuilder(schema).build(
        {
            "k": np.arange(n, dtype=np.int32) % 4,
            "tags": tags,
            "hi": np.arange(n, dtype=np.int32),
            "hj": (np.arange(n, dtype=np.int32) * 7) % n,
            "x": rng.random(n),
        },
        "m0",
    )
    mv = gspec_of(seg, "SELECT tags, SUM(x) FROM m GROUP BY tags LIMIT 10")
    wide = "SELECT hi, hj, SUM(x) FROM m GROUP BY hi, hj LIMIT 10"
    sparse = plan_segment(seg, QueryEngine([seg]).make_context(wide), compact=False).spec[2]
    assert mv[0] == "groups_mv" and len(mv) == 6
    assert sparse[0] == "groups_sparse" and len(sparse) == 4
    # the same product as the engine first launches it (plan.group_spec): the compact space, its five entries as planned
    assert gspec_of(seg, wide) == ("groups_compact", ("hi", "hj"), plan_mod.COMPACT_SLOTS, 0, (("rank", 3072), ("rank", 3072)))


# ---------------------------------------------------------------------------
# SSB-shaped group-bys keep their programs, name for name
# ---------------------------------------------------------------------------

SSB_PROGRAMS = {
    # flights 2 to 4 over an SSB-shaped table: integer metrics only. The names are
    # program_name() of these plans over this same table as of PR 35, which named every
    # program anew once (a raw value column is "@0" in the spec, not its name: plan.raw_value);
    # before it they were 838d2a87, 5ec0973e, 6728849d, 5d951c51, the same from PR 28's parent on.
    "SELECT d_year, p_brand1, SUM(lo_revenue) FROM lineorder WHERE p_category = 'MFGR#12' AND s_region = 'AMERICA' "
    "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 LIMIT 10": "seg_groupby_25e3d56c",
    "SELECT c_nation, s_nation, d_year, SUM(lo_revenue) FROM lineorder WHERE c_region = 'ASIA' AND s_region = 'ASIA' "
    "AND d_year >= 1992 AND d_year <= 1997 GROUP BY c_nation, s_nation, d_year ORDER BY d_year LIMIT 10": "seg_groupby_e190ae97",
    "SELECT d_year, c_nation, SUM(lo_revenue - lo_supplycost), COUNT(*) FROM lineorder WHERE c_region = 'AMERICA' "
    "GROUP BY d_year, c_nation ORDER BY d_year, c_nation LIMIT 10": "seg_groupby_96dd7fb4",
    "SELECT d_year, SUM(lo_extendedprice * lo_discount), AVG(lo_quantity) FROM lineorder "
    "GROUP BY d_year ORDER BY d_year LIMIT 10": "seg_groupby_89caa50d",
}


@functools.lru_cache(maxsize=None)
def ssb_segment():
    rng = np.random.default_rng(28)
    n = 4000
    names = lambda p, k: np.asarray([f"{p}{i:02d}" for i in range(k)], dtype=object)  # noqa: E731
    schema = Schema.build(
        "lineorder",
        dimensions=[
            ("d_year", DataType.INT), ("p_brand1", DataType.STRING), ("p_category", DataType.STRING),
            ("s_region", DataType.STRING), ("c_region", DataType.STRING), ("c_nation", DataType.STRING),
            ("s_nation", DataType.STRING),
        ],
        metrics=[
            ("lo_revenue", DataType.INT), ("lo_supplycost", DataType.INT), ("lo_extendedprice", DataType.INT),
            ("lo_discount", DataType.INT), ("lo_quantity", DataType.INT),
        ],
    )  # fmt: skip
    regions = np.asarray(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"], dtype=object)
    data = {
        "d_year": (1992 + np.arange(n) % 7).astype(np.int32),
        "p_brand1": names("MFGR#12", 40)[np.arange(n) % 40],
        "p_category": names("MFGR#", 25)[np.arange(n) % 25],
        "s_region": regions[np.arange(n) % 5],
        "c_region": regions[(np.arange(n) // 5) % 5],
        "c_nation": names("N", 25)[np.arange(n) % 25],
        "s_nation": names("N", 25)[(np.arange(n) // 3) % 25],
        "lo_revenue": rng.integers(1, 1 << 22, n).astype(np.int32),
        "lo_supplycost": rng.integers(1, 1 << 16, n).astype(np.int32),
        "lo_extendedprice": rng.integers(1, 1 << 15, n).astype(np.int32),
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
    }
    return SegmentBuilder(schema).build(data, "lo0")


@pytest.mark.parametrize("q", list(SSB_PROGRAMS))
def test_an_int32_only_group_by_is_the_parents_program(q):
    seg = ssb_segment()
    plan = plan_segment(seg, QueryEngine([seg]).make_context(q))
    assert len(plan.spec[2]) == 4  # ("groups", cols, ng, strides): no real count
    assert kernels.program_name(plan.spec) == SSB_PROGRAMS[q]


# ---------------------------------------------------------------------------
# TPC-H Q1 keeps its program too: it states a real group count, so nothing of
# the limb form reaches it
# ---------------------------------------------------------------------------

Q1 = (
    "SELECT l_returnflag, l_linestatus, SUM(l_quantity), SUM(l_extendedprice), "
    "SUM(l_extendedprice * (1 - l_discount)), SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
    "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*) FROM lineitem "
    "WHERE l_shipdate <= '1998-09-02' GROUP BY l_returnflag, l_linestatus "
    "ORDER BY l_returnflag, l_linestatus LIMIT 10"
)
Q1_PROGRAM = "seg_groupby_15c9ddb6"


@functools.lru_cache(maxsize=None)
def lineitem_segment():
    """`tpch-q1q6-closed`'s columns as Q1 reads them (perfbench/datasets/tpch_lineitem.py)."""
    rng = np.random.default_rng(36)
    n = 4000
    schema = Schema.build(
        "lineitem",
        dimensions=[("l_returnflag", DataType.STRING), ("l_linestatus", DataType.STRING), ("l_shipdate", DataType.STRING)],
        metrics=[
            ("l_quantity", DataType.LONG), ("l_extendedprice", DataType.DOUBLE), ("l_discount", DataType.DOUBLE),
            ("l_tax", DataType.DOUBLE),
        ],
    )  # fmt: skip
    days = (np.datetime64("1992-01-01") + np.arange(2500)).astype(str)
    data = {
        "l_returnflag": np.asarray(["A", "N", "R"], dtype=object)[np.arange(n) % 3],
        "l_linestatus": np.asarray(["F", "O"], dtype=object)[(np.arange(n) // 3) % 2],
        "l_shipdate": days[rng.integers(0, 2500, n)].astype(object),
        "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        "l_extendedprice": np.round(rng.random(n) * 1e5, 2),
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
    }
    return SegmentBuilder(schema).build(data, "li0")


@pytest.mark.parametrize("pallas", ("0", "1"))
def test_tpch_q1_is_the_parents_program(pallas, monkeypatch):
    """Spec and name as of PR 35, with the kernel on as with it off: six real
    groups are stated (8), so its DOUBLE sums stay dense masked reductions."""
    monkeypatch.setenv("PINOT_TPU_PALLAS", pallas)
    seg = lineitem_segment()
    plan = plan_segment(seg, QueryEngine([seg]).make_context(Q1))
    assert plan.spec[2][0] == "groups" and plan.spec[2][4:] == (8,)
    assert kernels.program_name(plan.spec) == Q1_PROGRAM
    dev = seg.to_device()
    with KERNELS.building("q1", dev.padded):
        jax.eval_shape(lambda c, o, n: kernels.build_fn(plan.spec)(c, o, n, dev.padded), *kernels._plan_inputs(plan, dev), jnp.int32(seg.n_docs))
    work = KERNELS.program_work("q1", dev.padded)
    assert DENSE in work and SCATTER not in work
    assert (PLANES in work) == (pallas == "1") and work.get(PLANES, {}).get("flops", 0) == (pallas == "1") * 4096 * 256 * 2.0 * 9  # SUM and AVG of l_quantity (a LONG that fits int32) and the mask: no limb
