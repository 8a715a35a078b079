"""Pallas byte-plane group-by kernels (one-hot MXU matmul) vs numpy.

Runs in interpret mode on CPU (tests/conftest.py forces the CPU backend);
the same kernels compile natively on TPU, where `python chip_smoke.py` runs
them at real shapes. Reference semantics: DefaultGroupByExecutor result
holders (SURVEY.md §2.2).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.ops import groupby_pallas as gp


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(42)
    n, ng = 5000, 37  # deliberately not multiples of PLANES_CHUNK / the group tile
    gid = rng.integers(0, ng, n).astype(np.int32)
    vals = rng.integers(-500_000, 500_000, n).astype(np.int32)
    mask = rng.random(n) < 0.7
    return jnp.asarray(gid), jnp.asarray(vals), jnp.asarray(mask), n, ng


def test_interpret_mode_only_on_explicit_cpu():
    """conftest put this process on the CPU explicitly, so the kernels are
    interpreted; a process that merely failed to get a chip would not be."""
    import jax

    assert jax.config.jax_platforms == "cpu" and gp.interpret_mode() is True


def test_grouped_sum_and_count_match_numpy_exactly(data):
    gid, vals, mask, n, ng = data
    sums, counts = gp.pallas_grouped_multi_sum([vals], gid, mask, ng)
    g, v, m = np.asarray(gid), np.asarray(vals), np.asarray(mask)
    want = np.bincount(g[m], weights=v[m].astype(np.float64), minlength=ng)
    assert np.array_equal(np.asarray(sums[0]), want)
    assert np.array_equal(np.asarray(counts), np.bincount(g[m], minlength=ng))


def test_empty_mask_and_group_tile_boundary():
    # ng exactly at every rung of the adaptive tile ladder (gtile_for);
    # all docs masked out — exercises the tile-edge base+iota compare
    for ng in (256, 512, 1024):
        assert gp.gtile_for(ng) == ng  # ng IS the tile boundary
        gid = jnp.arange(2048, dtype=jnp.int32) % ng
        vals = jnp.ones(2048, dtype=jnp.int32)
        mask = jnp.zeros(2048, dtype=bool)
        sums, counts = gp.pallas_grouped_multi_sum([vals], gid, mask, ng)
        assert np.asarray(sums[0]).sum() == 0.0
        assert np.asarray(counts).sum() == 0


def test_large_ng_multiple_tiles():
    rng = np.random.default_rng(0)
    n, ng = 3000, 2500  # 3 group tiles of 1024
    gid = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
    mask = jnp.ones(n, dtype=bool)
    _, counts = gp.pallas_grouped_multi_sum([], gid, mask, ng)
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(np.asarray(gid), minlength=ng))


def test_engine_group_by_with_pallas_path(monkeypatch):
    """End-to-end: the device engine produces identical results with the
    pallas group-by fast path enabled."""
    from pinot_tpu.common import DataType, Schema
    from pinot_tpu.query import kernels
    from pinot_tpu.query.engine import QueryEngine
    from pinot_tpu.segment import SegmentBuilder

    rng = np.random.default_rng(3)
    n = 4000
    schema = Schema.build(
        "t", dimensions=[("k", DataType.STRING)], metrics=[("v", DataType.LONG)]
    )
    data = {
        "k": np.array([f"g{i:02d}" for i in rng.integers(0, 20, n)], dtype=object),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    }
    seg = SegmentBuilder(schema).build(data, "s0")
    sql = "SELECT k, COUNT(*), SUM(v), MIN(v), MAX(v) FROM t WHERE v > 100 GROUP BY k ORDER BY k LIMIT 30"
    baseline = QueryEngine([seg]).execute(sql).rows

    monkeypatch.setenv("PINOT_TPU_PALLAS", "1")
    kernels.build_fn.cache_clear()
    kernels.get_kernel.cache_clear()
    try:
        fast = QueryEngine([seg]).execute(sql).rows
    finally:
        kernels.build_fn.cache_clear()
        kernels.get_kernel.cache_clear()
    assert len(fast) == len(baseline)
    for a, b in zip(fast, baseline):
        assert a[0] == b[0] and a[1] == b[1]
        assert a[2] == pytest.approx(b[2], rel=1e-4)  # f32 accumulation
        assert a[3] == b[3] and a[4] == b[4]


def test_multi_sum_rejects_overflowing_doc_count():
    """The byte-plane int32 accumulator is exact only below SAFE_DOCS; the
    kernel must refuse larger inputs (callers fall back to the XLA path)."""
    from pinot_tpu.ops import groupby_pallas as gp

    n = gp.SAFE_DOCS + 1
    gid = np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="overflows"):
        gp.pallas_grouped_multi_sum([], jnp.asarray(gid), jnp.ones(n, bool), 4)


def test_grouped_all_falls_back_beyond_safe_docs(monkeypatch):
    """kernels._grouped_all must route oversized inputs to the XLA path
    instead of tripping the pallas guard."""
    from pinot_tpu.ops import groupby_pallas as gp
    from pinot_tpu.query import kernels as K

    monkeypatch.setattr(gp, "SAFE_DOCS", 16)  # make 'oversized' cheap
    n, ng = 64, 4
    gid = jnp.asarray(np.arange(n, dtype=np.int32) % ng)
    mask = jnp.ones(n, bool)
    vals = jnp.asarray(np.arange(n, dtype=np.int32))
    aggs = (("sum", ("raw", "v")),)
    counts, parts = K._grouped_all(aggs, {"v": vals}, (), mask, gid, ng)
    truth = np.bincount(np.arange(n) % ng, weights=np.arange(n), minlength=ng)
    np.testing.assert_allclose(np.asarray(parts[0]), truth)


def test_blocked_multi_sum_past_safe_docs(monkeypatch):
    """review r3: doc sets past SAFE_DOCS split into exact blocks instead of
    silently abandoning the pallas path."""
    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp

    monkeypatch.setattr(gp, "SAFE_DOCS", 9000)
    rng = np.random.default_rng(8)
    n, ng = 25_000, 300
    v = jnp.asarray(rng.integers(-500_000, 500_000, n).astype(np.int32))
    g = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
    m = jnp.asarray(rng.random(n) < 0.7)
    sums, counts = gp.pallas_grouped_multi_sum_blocked([v], g, m, ng)
    vm = np.where(np.asarray(m), np.asarray(v, dtype=np.float64), 0.0)
    truth = np.zeros(ng)
    np.add.at(truth, np.asarray(g), vm)
    tc = np.zeros(ng, dtype=np.int64)
    np.add.at(tc, np.asarray(g), np.asarray(m).astype(np.int64))
    assert np.allclose(np.asarray(sums[0]), truth)
    assert np.array_equal(np.asarray(counts), tc)


def test_two_level_planes_kernel_matches_flat(monkeypatch):
    """PINOT_TPU_PALLAS_V2 two-level (hi/lo) byte-plane kernel is exact and
    identical to the flat kernel across group counts that do / don't divide
    G2, including multi-value fusion."""
    import os

    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp

    rng = np.random.default_rng(8)
    for n, ng, k in [(8192, 130, 2), (12288, 3125, 1), (4096, 64, 1)]:
        gid = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
        vals = [jnp.asarray(rng.integers(-50000, 50000, n).astype(np.int32)) for _ in range(k)]
        mask = jnp.asarray(rng.random(n) < 0.8)
        monkeypatch.setenv("PINOT_TPU_PALLAS_V2", "0")
        s1, c1 = gp.pallas_grouped_multi_sum(vals, gid, mask, ng)
        monkeypatch.setenv("PINOT_TPU_PALLAS_V2", "1")
        s2, c2 = gp.pallas_grouped_multi_sum(vals, gid, mask, ng)
        hm, hg = np.asarray(mask), np.asarray(gid)
        for i in range(k):
            want = np.bincount(hg[hm], weights=np.asarray(vals[i])[hm].astype(np.float64), minlength=ng)
            assert np.array_equal(np.asarray(s1[i]), want)
            assert np.array_equal(np.asarray(s2[i]), want)
        assert np.array_equal(np.asarray(c2), np.bincount(hg[hm], minlength=ng))


def test_v2_kernel_failure_propagates(monkeypatch):
    """A kernel the compiler refuses fails the call: nothing substitutes the
    flat kernel behind the caller's back."""
    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setenv("PINOT_TPU_PALLAS_V2", "1")
    monkeypatch.setattr(gp, "_planes2_impl", boom)
    n, ng = 8192, 50
    gid = jnp.asarray(np.arange(n, dtype=np.int32) % ng)
    v = jnp.asarray(np.ones(n, np.int32))
    with pytest.raises(RuntimeError, match="mosaic says no"):
        gp.pallas_grouped_multi_sum([v], gid, jnp.ones(n, bool), ng)
