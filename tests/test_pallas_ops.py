"""Pallas byte-plane group-by kernel (two-level one-hot MXU matmul) vs numpy.

Runs in interpret mode on CPU (tests/conftest.py forces the CPU backend);
the same kernel compiles natively on TPU, where the benchmark's group-by
cells (`python3 -m perfbench.run --workload ssb-groupby-closed`) run it at
real shapes. Reference semantics: DefaultGroupByExecutor result holders
(SURVEY.md §2.2).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.ops import groupby_pallas as gp


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(42)
    n, ng = 5000, 37  # deliberately not multiples of PLANES_CHUNK / the hi and lo widths
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
    # ng exactly at, and one past, the edge of a lo width (G2 steps by 8 per
    # 1024 groups); all docs masked out — exercises the tile-edge compares
    for ng in (1024, 1025, 7168):
        grid = gp.grid_for(ng, 5)
        assert (grid.g2 - 8) * grid.g1_tile < ng <= grid.g2 * grid.g1_tile
        gid = jnp.arange(2048, dtype=jnp.int32) % ng
        vals = jnp.ones(2048, dtype=jnp.int32)
        mask = jnp.zeros(2048, dtype=bool)
        sums, counts = gp.pallas_grouped_multi_sum([vals], gid, mask, ng)
        assert np.asarray(sums[0]).sum() == 0.0
        assert np.asarray(counts).sum() == 0


def test_large_ng_multiple_tiles(monkeypatch):
    """Past the left operand's VMEM budget the hi axis is tiled over the grid
    (437,500 groups at the real budget; made cheap here)."""
    monkeypatch.setattr(gp, "LEFT_BYTES_MAX", 2 * gp.PLANES_CHUNK * 16)
    rng = np.random.default_rng(0)
    n, ng = 3000, 2500
    grid = gp.grid_for(ng, 1)
    assert grid.g2 == 16 and gp._hi_tiles(ng, grid) == 2
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


@pytest.mark.parametrize("k", [0, 1, 2, 3])  # plane rows r = 4k + 1: 1, 5, 9, 13
@pytest.mark.parametrize("ng", [64, 130, 256, 3125, 7000])
def test_exact_on_both_sides_of_the_rule(ng, k):
    """The public entry is exact against np.bincount where the per-step cost
    binds (G2 = 8, up to 1024 groups) and where the MXU does (G2 = 32 and 56),
    at group counts that do and do not divide G2, with no, one and several
    fused value arrays, negative values included."""
    rng = np.random.default_rng([ng, k])
    n = 9000  # three chunks, the last one padded
    gid = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
    vals = [jnp.asarray(rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)) for _ in range(k)]
    mask = jnp.asarray(rng.random(n) < 0.8)
    sums, counts = gp.pallas_grouped_multi_sum(vals, gid, mask, ng)
    hm, hg = np.asarray(mask), np.asarray(gid)
    assert len(sums) == k
    for i in range(k):
        want = np.bincount(hg[hm], weights=np.asarray(vals[i])[hm].astype(np.float64), minlength=ng)
        assert np.array_equal(np.asarray(sums[i]), want)
    assert np.array_equal(np.asarray(counts), np.bincount(hg[hm], minlength=ng))


def test_all_false_mask_gives_zeros_at_a_wide_grid():
    n, ng = 8192, 7000
    gid = jnp.asarray(np.arange(n, dtype=np.int32) % ng)
    v = jnp.asarray(np.full(n, -7, np.int32))
    sums, counts = gp.pallas_grouped_multi_sum([v, v], gid, jnp.zeros(n, bool), ng)
    assert not np.asarray(sums[0]).any() and not np.asarray(sums[1]).any() and not np.asarray(counts).any()


def test_ids_outside_the_group_space_add_to_no_group():
    """What the flat one-hot did by construction: a doc whose dense id is not
    in [0, ng) matches no column, also where hi*G2 + lo would land in the
    grid's padding or lo would wrap."""
    ng = 130
    gid = jnp.asarray(np.array([0, 129, 130, 1023, 1024, 5000, -1, -8, 7], np.int32))
    v = jnp.asarray(np.full(9, 3, np.int32))
    sums, counts = gp.pallas_grouped_multi_sum([v], gid, jnp.ones(9, bool), ng)
    want = np.zeros(ng)
    want[[0, 129, 7]] = 1
    assert np.array_equal(np.asarray(counts), want) and np.array_equal(np.asarray(sums[0]), 3 * want)


@pytest.mark.parametrize(
    "ng, r, g2, tiles",
    [
        (64, 1, 8, 1),  # below 1024 groups: the narrowest lo width, the per-step cost binds
        (256, 5, 8, 1),  # SSB Q4.1, TPC-H Q1
        (1024, 13, 8, 1),
        (4608, 5, 40, 1),  # SSB Q3.1, Q4.2: 4375 groups as the planner rounds them
        (7168, 5, 56, 1),  # SSB Q2.x: 7000 groups, no padding at all
        (7168, 13, 56, 1),
        (40192, 5, 320, 1),  # one tile while r*G2*chunk bf16 fits LEFT_BYTES_MAX
        (40192, 13, 128, 3),  # past it: the widest multiple of 128 that fits, hi tiled
        (437504, 5, 384, 9),  # SSB Q3.2-Q3.4
        (1750016, 5, 384, 36),  # SSB Q4.3
    ],
)
def test_grid_for_picks_the_grid_from_the_shape(ng, r, g2, tiles):
    grid = gp.grid_for(ng, r)
    assert grid == gp.PlanesGrid(g2, gp.G1_TILE, gp.PLANES_CHUNK)
    assert gp._hi_tiles(ng, grid) == tiles
    assert grid.g2 * grid.g1_tile * tiles >= ng  # the grid covers every group
    assert 2 * r * grid.g2 * grid.chunk <= gp.LEFT_BYTES_MAX


def test_cost_model_follows_the_launched_grid():
    """bytes: ids and plane rows once per hi tile of the rule's grid; flops:
    the useful MACs, whatever the grid pads (4608 groups run as 5120)."""
    rows = 4 * gp.PLANES_CHUNK
    assert gp._planes_cost({"rows": rows, "groups": 4608, "planes": 5}) == (rows * 7 * 4.0, rows * 4608 * 10.0)
    assert gp._planes_cost({"rows": rows, "groups": 437504, "planes": 5}) == (rows * 7 * 4.0 * 9, rows * 437504 * 10.0)


def test_refused_kernel_raises(monkeypatch):
    """A kernel the compiler refuses fails the call: there is no other kernel
    to put in its place, and nothing catches the error on the way out."""
    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(gp, "_planes2_impl", boom)
    n, ng = 8192, 50
    gid = jnp.asarray(np.arange(n, dtype=np.int32) % ng)
    v = jnp.asarray(np.ones(n, np.int32))
    with pytest.raises(RuntimeError, match="mosaic says no"):
        gp.pallas_grouped_multi_sum([v], gid, jnp.ones(n, bool), ng)


# -- the chip's compiler, without the chip -----------------------------------
#
# Interpret mode cannot see what Mosaic refuses (a misaligned slice, too much
# VMEM). The TPU compiler is installed wherever jax[tpu] is and compiles for a
# described v5e; nothing runs. The topology is described inside a fixture (one
# process at a time may load libtpu: never at import), in this file only.


@pytest.fixture(scope="module")
def one_v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "ng, r",
    [
        (256, 1),  # TPC-H Q1's count plane: the narrowest left operand, 8 bf16 rows
        (256, 5),  # SSB Q4.1
        (256, 9),  # SSB Q4.1 answered from a star table: two stored sums (revenue, supplycost) and the mask in one pass
        (4608, 9),  # SSB Q4.2 likewise: 7 x 25 x 25 groups
        (7168, 5),  # SSB Q2.x: G2 = 56, not a multiple of the bf16 sublane tile
        (7168, 13),
        (437504, 5),  # SSB Q3.2-Q3.4: nine hi tiles, the widest left operand (15 MB)
    ],
)
def test_mosaic_compiles_the_rules_grid_for_the_v5e(one_v5e_chip, monkeypatch, ng, r):
    import jax

    n = 8 * gp.PLANES_CHUNK
    gid = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_v5e_chip)
    planes = jax.ShapeDtypeStruct((r, n), jnp.float32, sharding=one_v5e_chip)
    grid = gp.grid_for(ng, r)
    # this process is on the CPU, where the package would interpret the kernel;
    # the jitted body is traced afresh under a jit of the test's own
    monkeypatch.setattr(gp, "interpret_mode", lambda: False)
    fn = jax.jit(lambda g, p: gp._planes2_impl.__wrapped__(g, p, ng, grid))
    text = fn.lower(gid, planes).compile().as_text()
    assert "ops_grouped_planes2_impl" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("g", [8, 4096])  # TPC-H Q1's six groups in their bucket; plan.DENSE_REDUCE_MAX_GROUPS
def test_dense_grouped_reduction_is_one_fused_pass_on_the_v5e(one_v5e_chip, g):
    """A grouped DOUBLE SUM over few real groups (query/kernels.py
    `_dense_grouped`) has to fuse: the (g, rows) one-hot of a 4M-row segment
    would be 256 MB at 8 groups. The v5e's compiler gives one reduce fusion of
    emulated-f64 pairs, no scatter, and next to no temporary memory."""
    import jax

    from pinot_tpu.query import kernels, plan

    n = 4096 * 1024
    assert g <= plan.DENSE_REDUCE_MAX_GROUPS

    def arg(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_v5e_chip)

    fn = jax.jit(lambda v, gid, mask: kernels._dense_grouped("sum", v, gid, mask, g))
    compiled = fn.lower(arg(jnp.float64), arg(jnp.int32), arg(jnp.bool_)).compile()
    text = compiled.as_text()
    assert "scatter" not in text and f"f32[{g},{n // kernels._BLOCK}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < n  # the one-hot alone: n * g * 8


def test_an_expression_key_and_the_double_scatter_compile_at_the_tsbs_segment_on_the_v5e(one_v5e_chip):
    """`tsbs-hosthour-closed`'s launch at its real shape: 4.32M rows (padded),
    the hour bucket gathered through the plan's code -> bucket operand
    (`kernels._key_ids`), 4000 hosts x 3 hours in 12,032 slots — past
    plan.DENSE_REDUCE_MAX_GROUPS, so AVG's DOUBLE sum and its count are
    scatters (`kernels._grouped_reduce`). The v5e's compiler takes it, keeps
    the scatters and needs no temporary memory beside the arguments."""
    import jax

    from pinot_tpu.query import kernels, plan
    from pinot_tpu.segment.segment import padded_len

    n, ng = padded_len(4_320_000), 12_032
    assert 4000 * 3 > plan.DENSE_REDUCE_MAX_GROUPS and ng == -(-4000 * 3 // 256) * 256

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def launch(v, host, ts, remap, mask):
        cols, ops = {"hostname": host, "ts": ts}, (remap,)
        gid = kernels._key_ids("hostname", cols, ops) * 3 + kernels._key_ids(("remap", "ts", 0), cols, ops)
        return kernels._grouped_reduce("sum", v, gid, mask, ng, None), kernels._count_grouped(mask, gid, ng)

    compiled = (
        jax.jit(launch)
        .lower(arg((n,), jnp.float64), arg((n,), jnp.int32), arg((n,), jnp.int32), arg((2048,), jnp.int32), arg((n,), jnp.bool_))
        .compile()
    )
    text = compiled.as_text()
    assert text.count("scatter-add") >= 2 and "gather" in text
    # the gather (`kernels._gather_rows`) walks the rows in blocks: no (rows, 128) array, and a block's rows need no HBM
    assert f"[{kernels._GATHER_BLOCK},{kernels._GATHER_LANES}]" in text and f"[{n},{kernels._GATHER_LANES}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < n


def test_a_star_query_compiles_at_the_ssb_segment_on_the_v5e(one_v5e_chip, monkeypatch):
    """`ssbstar-groupby-closed`'s Q2.1 launch at its real shape: 4M rows, four
    lookUps — the part's category and brand out of one gather through the
    resident fk code -> codes operand of a million entries, the supplier's
    region and the year out of one each (`kernels._lookup_codes`) — two of
    them filters by an id range, two the
    keys of 8 years x 1001 brands (a miss bucket each) in 8192 slots on the
    byte-plane kernel, and the launch's count of rows whose key had no
    dimension row. The v5e's compiler takes it with the gathers and
    the kernel in it, and with little memory beside the arguments."""
    import jax

    from pinot_tpu.query import kernels

    n = 4096 * 1024
    ng = -(-8 * 1001 // 256) * 256
    # operands: 0 part's word, 1 supplier's, 2 the dates', then shifts, masks, miss codes, bounds, strides
    part_category, part_brand = ("lookup", "lo_partkey", 0, 3, 4, 5, True), ("lookup", "lo_partkey", 0, 6, 7, 8, False)
    spec = (
        "agg",
        ("and", (("lookup_range", part_category, 9, 10), ("lookup_range", ("lookup", "lo_suppkey", 1, 11, 12, 13, True), 14, 15))),
        ("groups", (("lookup_key", ("lookup", "lo_orderdate", 2, 17, 18, 19, True)), ("lookup_key", part_brand)), ng, 16),
        (("sum", ("raw", "@0")),),
    )  # fmt: skip
    assert kernels._holds_lookup(spec) and not kernels._holds_lookup(("agg", ("const", True), None, (("count",),)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    cols = {c: arg((n,), jnp.int32) for c in ("lo_partkey", "lo_suppkey", "lo_orderdate", "@0")}
    ops = (arg((1 << 20,), jnp.int32), arg((1 << 15,), jnp.int32), arg((4096,), jnp.int32)) + tuple(
        arg((), jnp.int32) for _ in range(13)
    ) + (arg((2,), jnp.int32),) + tuple(arg((), jnp.int32) for _ in range(3))  # fmt: skip
    monkeypatch.setattr(gp, "interpret_mode", lambda: False)
    monkeypatch.setenv("PINOT_TPU_PALLAS", "1")
    kernel = kernels.get_packed_kernel.__wrapped__(spec)
    compiled = kernel.lower(cols, ops, arg((), jnp.int32), n).compile()
    text = compiled.as_text()
    assert text.count(" gather(") == 3 and "tpu_custom_call" in text  # one gather a foreign key
    assert f"[{kernels._GATHER_BLOCK},{kernels._GATHER_LANES}]" in text and f"[{n},{kernels._GATHER_LANES}]" not in text  # a block of rows at a time
    assert compiled.memory_analysis().temp_size_in_bytes < 40 * n  # a few row-sized temporaries, no (rows, groups) one-hot


def test_a_compact_group_space_compiles_at_the_ssb_segment_on_the_v5e(one_v5e_chip, monkeypatch):
    """`ssb-citygroups-closed`'s Q3.3 launch at its real shape: 4M rows, two
    cities a side (membership tables over the cities' dictionaries, read as
    bits: `kernels._in_lut`) and six years out of 250 x 250 x 7 dense
    groups, each key renumbered by the values the filter leaves
    (`kernels._compact_groups`: presence bits 32 values a word, the rows' ranks)
    and the byte-plane kernel over plan.COMPACT_SLOTS slots in one hi tile.
    The v5e's compiler takes it with no gather of the rows, no (values, rows)
    or (rows, slots) array in memory, and a kernel of 160 left rows."""
    import jax

    from pinot_tpu.query import kernels, plan

    n = 4096 * 1024
    spec = (
        "agg",
        ("and", (("in_lut", "c_city", 0), ("in_lut", "s_city", 1), ("range_ids", "d_year", 2, 3))),
        ("groups_compact", ("c_city", "s_city", "d_year"), plan.COMPACT_SLOTS, 4, (("rank", 256), ("rank", 256), ("rank", 8))),
        (("sum", ("raw", "@0")),),
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    cols = {c: arg((n,), jnp.int32) for c in ("d_year", "c_city", "s_city", "@0")}
    ops = (arg((256,), jnp.bool_), arg((256,), jnp.bool_), arg((), jnp.int32), arg((), jnp.int32), arg((3,), jnp.int64))
    monkeypatch.setattr(gp, "interpret_mode", lambda: False)
    monkeypatch.setenv("PINOT_TPU_PALLAS", "1")
    kernel = kernels.get_packed_kernel.__wrapped__(spec)
    compiled = kernel.lower(cols, ops, arg((), jnp.int32), n).compile()
    text = compiled.as_text()
    grid = gp.grid_for(plan.COMPACT_SLOTS, 5)
    assert (grid.g2, gp._hi_tiles(plan.COMPACT_SLOTS, grid)) == (32, 1)
    assert f"s32[{5 * grid.g2},{gp.G1_TILE}]" in text and "tpu_custom_call" in text  # the kernel's output: one hi tile
    assert f"[{n}]" not in "".join(line for line in text.splitlines() if " gather(" in line)  # the slot table's, never the rows'
    assert "popcnt" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * n  # row-sized temporaries; one (256 values, rows) mask alone is 256 * n
