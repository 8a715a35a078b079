"""Pallas TPU kernel for the group-by hot path: segment aggregation as a
two-level one-hot matmul on the MXU.

Reference parity: the inner loops of DefaultGroupByExecutor +
DictionaryBasedGroupKeyGenerator (pinot-core/.../query/aggregation/groupby/
DefaultGroupByExecutor.java:191, DictionaryBasedGroupKeyGenerator.java:119-130)
and the count/sum result holders. On TPU the dense-group-id reduction maps to
the systolic array: with onehot[c, g] = (gid[c] == g),

    out[g] += sum_c masked_values[c] * onehot[c, g]

is a matmul over the doc chunk, and the MXU does the scatter-add. The dense
id is factored gid = hi*G2 + lo so that both operands fill the array (see
"the contraction" below); the grid walks (hi tile, chunk) with the chunk axis
innermost, so each output tile stays resident in VMEM while all chunks
accumulate into it.

The kernel is exact (integer byte planes, see below) and is what the engine's
fused programs call on TPU (kernels._grouped_all via pallas_auto); MIN/MAX and
float aggregates stay on XLA segment reductions.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from pinot_tpu.common.kernel_obs import KERNELS

# Docs a grid step; benchmarks/planes_ab.py sweeps it through `grid_for(..., chunk=)`.
PLANES_CHUNK = 4096


def _check_chunk(chunk: int) -> None:
    """Exactness invariant of the byte-plane SUM: one chunk's plane dot must
    stay below the f32 exact-integer bound. The chunk axis is the lane axis."""
    if chunk * 255 >= 2**24:
        raise ValueError(f"plane chunk {chunk}: CHUNK*255 must stay < 2^24 for lossless sums")
    if chunk % 128:
        raise ValueError(f"plane chunk {chunk}: must be a multiple of 128 (lane tiling)")


def pallas_auto() -> bool:
    """Exact pallas kernels: on by default on TPU, off elsewhere (interpret
    mode works but XLA is faster on CPU). PINOT_TPU_PALLAS=1/0 overrides."""
    env = os.environ.get("PINOT_TPU_PALLAS", "")
    if env == "1":
        return True
    if env == "0":
        return False
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Interpret the kernels only where the process was explicitly put on the
    CPU (JAX_PLATFORMS=cpu / jax_platforms=cpu: tests, CI, rehearsal). A
    process that merely failed to get its chip does not interpret silently:
    the Mosaic lowering then fails on the backend it landed on."""
    return jax.config.jax_platforms == "cpu"


# -- exact integer sum+count: byte planes ------------------------------------
#
# f32 MXU accumulation is inexact past 2^24, so a lossless integer SUM splits
# each int32 value into four signed byte planes (v = b3*2^24 + b2*2^16 +
# b1*2^8 + b0, arithmetic shifts keep the sign in b3). Each chunk's per-plane
# dot product is <= CHUNK*255 < 2^24 (_check_chunk); the cross-chunk
# accumulator is int32 (exact to 2^31 — plane totals stay under it for
# segment sets below ~8M docs, SAFE_DOCS). One pass yields the byte-plane sums
# AND the group count (the mask rides as the last plane); the tiny (r, ng)
# recombination runs in f64 outside the kernel.
#
# -- the contraction: gid = hi*G2 + lo ---------------------------------------
#
# A flat one-hot, (r, chunk) @ (chunk, groups), pushes r <= 13 plane rows
# through a 128-row systolic array and builds a chunk x groups one-hot on the
# VPU for every step: 25 ms a 4M-row launch at 7000 groups on the v5e, a
# tenth of the MXU's peak. The two-level form scales G2 lo-one-hot rows by
# every plane row, L[p*G2 + l, c] = plane_p[c] * (lo[c] == l), and contracts
# with the hi one-hot over the chunk: (r*G2, chunk) x (G1_TILE, chunk)^T — the
# same useful MACs on full rows, an L build that does not grow with the group
# count, and both one-hots compared along the lane axis as the ids arrive (no
# relayout of the id vector). Measured cost is the padded MACs, r*G2 x G1
# tiles of 128, at 80-90 % of the MXU's peak, plus ~0.45 us a grid step: 1.9 ms
# at 7000 groups (sweep table: PERF.md §6, PR 25; benchmarks/planes_ab.py).
# Flat is the G2 = 1 corner of the same kernel, and what the sweep calls so.
# Same exactness invariant: products <= 255, per-chunk dots < 2^24 in f32,
# int32 cross-chunk accumulation.

G1_TILE = 128  # hi one-hot rows a step: one MXU tile. 256 and 512 measured no faster
# budget of the (r*G2, chunk) bf16 left operand a step builds in VMEM; above
# it the hi axis is tiled over the grid and L is rebuilt per tile (a few % of
# a step). 20 MB compiled and ran on the v5e (128 MiB of VMEM)
LEFT_BYTES_MAX = 16 << 20


class PlanesGrid(NamedTuple):
    g2: int  # lo width: rows of the left operand per plane row
    g1_tile: int  # hi one-hot rows a grid step contracts with
    chunk: int  # docs a grid step


def grid_for(ng: int, r: int, chunk: int = PLANES_CHUNK) -> PlanesGrid:
    """The grid for `ng` dense groups and `r` plane rows, from the shape alone.
    The kernel's time follows the padded product r*G2 x tiles*G1_TILE, so: one
    hi tile and the smallest G2 (a multiple of the 8-row sublane tile) that
    covers `ng`, while the left operand fits LEFT_BYTES_MAX; past that the hi
    axis is tiled with the widest G2 that fits, a multiple of 128 (XLA takes
    tens of seconds to compile the untangling of a wide output whose G2 is
    not). Below 1024 groups the per-step cost binds and G2 = 8 is as fast as
    any (PERF.md §6, PR 25)."""
    fit = LEFT_BYTES_MAX // (2 * chunk * r)
    g2 = -(-ng // (G1_TILE * 8)) * 8
    if g2 > fit:
        g2 = fit // 128 * 128 or max(fit // 8 * 8, 8)
    return PlanesGrid(g2, G1_TILE, chunk)


def _hi_tiles(ng: int, grid: PlanesGrid) -> int:
    """Grid steps along the hi axis: ceil(ng / G2) hi values in tiles of g1_tile."""
    g1 = -(-ng // grid.g2)
    return -(-g1 // grid.g1_tile)


@functools.lru_cache(maxsize=None)
def _make_planes2_kernel(r: int, grid: PlanesGrid):
    from jax.experimental import pallas as pl

    g2, g1_tile, chunk = grid

    def kernel(hilo_ref, planes_ref, out_ref, left_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        # ids stay on the lane axis, one-hot rows on the sublane axis
        lo_hit = jax.lax.broadcasted_iota(jnp.int32, (g2, chunk), 0) == hilo_ref[1:2, :]
        for p in range(r):
            # bf16 is exact here: plane bytes are integers in [-128, 255],
            # inside bf16's 2^8 exact-integer range, and the MXU runs bf16 at
            # full rate. The select runs in f32: the v5e's VPU has no bf16
            left_ref[p * g2 : (p + 1) * g2, :] = jnp.where(
                lo_hit, planes_ref[p : p + 1, :], 0.0
            ).astype(jnp.bfloat16)
        base = pl.program_id(0) * g1_tile
        hi_hit = (
            base + jax.lax.broadcasted_iota(jnp.int32, (g1_tile, chunk), 0) == hilo_ref[0:1, :]
        ).astype(jnp.bfloat16)
        # f32 accumulation keeps each chunk's plane dot exact (< 2^24)
        acc = jax.lax.dot_general(
            left_ref[...], hi_hit, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        out_ref[...] += acc.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("ng", "grid"))
def _planes2_impl(gid, planes, ng: int, grid: PlanesGrid):
    """(r, n) pre-masked f32 byte planes, (n,) int32 dense ids -> (r, ng)
    int32 plane sums per group; n a multiple of grid.chunk. Rows whose id is
    outside [0, ng) add to no group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g2, g1_tile, chunk = grid
    r, n_padded = planes.shape
    g1_pad = _hi_tiles(ng, grid) * g1_tile
    hi = gid // g2
    hilo = jnp.stack([hi, gid - hi * g2])
    left_bytes = 2 * r * g2 * chunk
    # the scope names the op's metadata path and `name=` the Mosaic kernel, so
    # a device trace finds the kernel whatever wraps this function
    # (`_planes2_impl` stays a substring of it: perfbench's groupby_kernel_share)
    with jax.named_scope("ops.grouped_planes2"):
        out = pl.pallas_call(
            _make_planes2_kernel(r, grid),
            name="ops_grouped_planes2_impl",
            grid=(g1_pad // g1_tile, n_padded // chunk),
            in_specs=[
                pl.BlockSpec((2, chunk), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
                pl.BlockSpec((r, chunk), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (r * g2, g1_tile), lambda g, c: (jnp.int32(0), g), memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct((r * g2, g1_pad), jnp.int32),
            scratch_shapes=[pltpu.VMEM((r * g2, chunk), jnp.bfloat16)],
            # L, its f32 select and the one-hots of a step, beside the
            # double-buffered blocks: above the 16 MiB default from G2 ~ 64 on
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=3 * left_bytes + (16 << 20)),
            interpret=interpret_mode(),
        )(hilo, planes)
    # out[p*G2 + l, h] holds group h*G2 + l: -> (r, G2, g1_pad) -> (r, ng)
    return jnp.transpose(out.reshape(r, g2, g1_pad), (0, 2, 1)).reshape(r, g1_pad * g2)[:, :ng]


# Byte-plane totals accumulate in int32: a group holding n masked docs can
# reach 255*n per plane, so n must stay below 2^31/255 (~8.42M) for the
# accumulator to be exact. Callers must fall back to the two-level XLA path
# (kernels._exact_int_grouped_sum) beyond this; build_masked_fn flattens ALL
# local segments into one doc vector, so the bound is easy to exceed.
SAFE_DOCS = (2**31 - 2**24) // 255


def pallas_grouped_multi_sum(values_list, gid, mask, ng: int):
    """Fused lossless group-by reduction: byte-plane sums for every int32
    value array plus the group count, in ONE pallas pass. Returns
    ([f64 (ng,) sum per input], i64 (ng,) counts).

    Exactness requires the flat doc count <= SAFE_DOCS (asserted). A kernel
    the compiler refuses raises: nothing substitutes another one."""
    if gid.shape[0] > SAFE_DOCS:  # not assert: must survive python -O
        raise ValueError(
            f"pallas byte-plane accumulator overflows past {SAFE_DOCS} docs; "
            "use the XLA two-level path for larger inputs"
        )
    k = len(values_list)
    r = 4 * k + 1  # four byte planes a value, and the mask: no pad rows, L's rows are r*G2
    grid = grid_for(ng, r)
    pad = (-gid.shape[0]) % grid.chunk
    n_padded = gid.shape[0] + pad
    gid = jnp.pad(gid.astype(jnp.int32), (0, pad))
    mask = jnp.pad(mask, (0, pad))
    rows = []
    for v in values_list:
        v = jnp.pad(v.astype(jnp.int32), (0, n_padded - v.shape[0]))
        v = jnp.where(mask, v, 0)
        rows.extend(
            [
                (v & 0xFF).astype(jnp.float32),
                ((v >> 8) & 0xFF).astype(jnp.float32),
                ((v >> 16) & 0xFF).astype(jnp.float32),
                (v >> 24).astype(jnp.float32),  # signed high byte
            ]
        )
    rows.append(mask.astype(jnp.float32))
    planes = jnp.stack(rows)
    out = KERNELS.timed_sync(
        "ops.grouped_planes2",
        lambda: _planes2_impl(gid, planes, ng, grid),
        rows=n_padded,
        groups=ng,
        planes=r,
    )
    sums = []
    for i in range(k):
        p = out[4 * i : 4 * i + 4].astype(jnp.float64)
        sums.append(p[0] + p[1] * 256.0 + p[2] * 65536.0 + p[3] * 16777216.0)
    counts = out[4 * k].astype(jnp.int64)
    return sums, counts


def pallas_grouped_multi_sum_blocked(values_list, gid, mask, ng: int):
    """SAFE_DOCS-unbounded variant: statically slices the doc axis into
    blocks that each respect the int32 plane-accumulator bound and sums the
    per-block results in f64/i64. Two slices cover 16M docs; per-slice cost
    is one extra kernel launch."""
    n = gid.shape[0]
    if n <= SAFE_DOCS:
        return pallas_grouped_multi_sum(values_list, gid, mask, ng)
    block = (SAFE_DOCS // PLANES_CHUNK) * PLANES_CHUNK
    sums_acc = None
    counts_acc = None
    for start in range(0, n, block):
        end = min(start + block, n)
        s, c = pallas_grouped_multi_sum(
            [v[start:end] for v in values_list], gid[start:end], mask[start:end], ng
        )
        if sums_acc is None:
            sums_acc, counts_acc = list(s), c
        else:
            sums_acc = [a + b for a, b in zip(sums_acc, s)]
            counts_acc = counts_acc + c
    return sums_acc, counts_acc


def pallas_grouped_sum_count_exact(values_i32, gid, mask, ng: int):
    """Lossless (sum, count) per group for one int32 value array."""
    sums, counts = pallas_grouped_multi_sum([values_i32], gid, mask, ng)
    return sums[0], counts


# -- kernel registry: the cost model for the roofline report -----------------
#
# Bytes model what the launched grid streams through VMEM: every doc chunk
# (its hi/lo ids and plane rows) is read once per hi tile (the chunk axis is
# innermost). FLOPs are the useful MACs of the reduction, one (2 flops) per
# (doc, group, plane row) — never the padded MACs of the grid, so a share of
# the MXU's peak built on them cannot be raised by padding, nor pass 100 %.
# The flat one-hot's rows x groups compares are not counted: this form makes
# rows x (G2 + G1_TILE x tiles) of them, on the VPU, and with them a COUNT
# alone over 40,000 groups would read 126 % of the v5e's peak (PERF.md §6).


def _planes_cost(shape: dict) -> tuple[float, float]:
    rows = max(float(shape.get("rows", 0)), 0.0)
    groups = max(int(shape.get("groups", 1)), 1)
    planes = max(int(shape.get("planes", 5)), 1)
    tiles = _hi_tiles(groups, grid_for(groups, planes))
    return rows * (planes + 2.0) * 4.0 * tiles, rows * groups * 2.0 * planes


KERNELS.register(
    "ops.grouped_planes2",
    _planes2_impl,
    cost_model=_planes_cost,
    description="byte-plane exact SUM+COUNT, two-level (hi/lo) one-hot contraction",
)
