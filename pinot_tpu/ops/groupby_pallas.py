"""Pallas TPU kernels for the group-by hot path: segment aggregation as
one-hot matmul on the MXU.

Reference parity: the inner loops of DefaultGroupByExecutor +
DictionaryBasedGroupKeyGenerator (pinot-core/.../query/aggregation/groupby/
DefaultGroupByExecutor.java:191, DictionaryBasedGroupKeyGenerator.java:119-130)
and the count/sum result holders. On TPU the dense-group-id reduction maps to
the systolic array: for a doc chunk of C docs and a group tile of G groups,
the one-hot matrix onehot[c, g] = (gid[c] == g) turns

    out[g] += sum_c masked_values[c] * onehot[c, g]

into a (planes, C) x (C, G) matmul — the MXU does the scatter-add. The grid
walks (group_tile, chunk) with the chunk axis innermost so each output tile
stays resident in VMEM while all chunks accumulate into it.

The kernels here are exact (integer byte planes, see below) and are what the
engine's fused programs call on TPU (kernels._grouped_all via pallas_auto);
MIN/MAX and float aggregates stay on XLA segment reductions.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from pinot_tpu.common.kernel_obs import KERNELS

# Tile geometry. Each grid step carries a fixed dispatch overhead on TPU, so
# for a (chunks x group-tiles) grid the step count — not the MACs — dominates
# at bench shapes (4M docs x 4.4k groups). The one-hot tile is bf16 (plane
# values <=255 are exact in bf16's 8 mantissa bits), so a 4096-doc chunk
# against a 1024-group tile is an 8MB operand. CHUNK*255 < 2^24 keeps the
# per-chunk plane dot exact. Overridable for hardware sweeps
# (benchmarks/pallas_sweep.py).
PLANES_CHUNK = int(os.environ.get("PINOT_TPU_PALLAS_CHUNK_PLANES", "4096"))
_GTILE_ENV = os.environ.get("PINOT_TPU_PALLAS_GTILE", "")


def gtile_for(ng: int) -> int:
    """Group-tile width for a given group count. Wide tiles win at high
    cardinality (per-step overhead amortized over more MXU columns) but a
    small GROUP BY padded to a 1024-wide tile would do 4x the one-hot cell
    work for nothing, so the tile tracks ng."""
    if _GTILE_ENV:
        return int(_GTILE_ENV)
    for t in (256, 512, 1024):
        if ng <= t:
            return t
    return 1024


# exactness invariant of the byte-plane SUM: one chunk's plane dot must stay
# below the f32 exact-integer bound. Fail loudly on bad sweep overrides.
if PLANES_CHUNK * 255 >= 2**24:
    raise ValueError(
        f"PINOT_TPU_PALLAS_CHUNK_PLANES={PLANES_CHUNK}: CHUNK*255 must stay < 2^24 for lossless sums"
    )
if PLANES_CHUNK % 128:
    raise ValueError(
        f"PINOT_TPU_PALLAS_CHUNK_PLANES={PLANES_CHUNK}: must be a multiple of 128 (lane tiling)"
    )
if _GTILE_ENV and int(_GTILE_ENV) % 128:
    raise ValueError("PINOT_TPU_PALLAS_GTILE must be a multiple of 128 (lane tiling)")


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


def _grids(n_padded: int, ng: int, chunk: int):
    gtile = gtile_for(ng)
    ng_pad = max(gtile, ((ng + gtile - 1) // gtile) * gtile)
    return n_padded // chunk, ng_pad // gtile, ng_pad, gtile


# -- exact integer sum+count: byte-plane one-hot matmul ----------------------
#
# f32 MXU accumulation is inexact past 2^24, so a lossless integer SUM splits
# each int32 value into four signed byte planes (v = b3*2^24 + b2*2^16 +
# b1*2^8 + b0, arithmetic shifts keep the sign in b3). Each chunk's per-plane
# dot product is <= CHUNK*255 < 2^24 (enforced at module load); the cross-chunk
# accumulator is int32 (exact to 2^31 — plane totals stay under it for
# segment sets below ~8M docs). One (8, CHUNK) x (CHUNK, GROUP_TILE) matmul
# yields byte-plane sums AND the group count (mask rides as a 5th plane);
# the tiny (5, ng) recombination runs in f64 outside the kernel.

@functools.lru_cache(maxsize=None)
def _make_planes_kernel(r: int, gtile: int, chunk: int):
    from jax.experimental import pallas as pl

    def kernel(gid_ref, planes_ref, out_ref):
        ci = pl.program_id(1)
        gi = pl.program_id(0)

        @pl.when(ci == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        gid = gid_ref[0, :]
        # bf16 is exact here: plane bytes are integers in [-128, 255] and the
        # one-hot is 0/1 — both inside bf16's 2^8 exact-integer range. The
        # one-hot tile is half the size it would be in f32, and the MXU runs
        # bf16 at twice the f32 rate.
        planes = planes_ref[:].astype(jnp.bfloat16)  # (r, chunk), pre-masked
        base = gi * gtile
        onehot = (
            gid[:, None] == (base + jax.lax.broadcasted_iota(jnp.int32, (chunk, gtile), 1))
        ).astype(jnp.bfloat16)
        # f32 accumulation keeps each chunk's plane dot exact (< 2^24)
        acc = jnp.dot(planes, onehot, preferred_element_type=jnp.float32)
        out_ref[:] = out_ref[:] + acc.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("ng", "r"))
def _planes_impl(gid, planes, ng: int, r: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_padded = gid.shape[0]
    n_chunks, n_gtiles, ng_pad, gtile = _grids(n_padded, ng, PLANES_CHUNK)
    # the scope names the op's metadata path and `name=` the Mosaic kernel, so
    # a device trace finds the kernel whatever wraps this function
    # (`_planes_impl` stays a substring of both: perfbench's groupby_kernel_share)
    with jax.named_scope("ops.grouped_planes"):
        return pl.pallas_call(
            _make_planes_kernel(r, gtile, PLANES_CHUNK),
            name="ops_grouped_planes_impl",
            grid=(n_gtiles, n_chunks),
            in_specs=[
                pl.BlockSpec((1, PLANES_CHUNK), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
                pl.BlockSpec((r, PLANES_CHUNK), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, gtile), lambda g, c: (jnp.int32(0), g), memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((r, ng_pad), jnp.int32),
            interpret=interpret_mode(),
        )(gid.reshape(1, n_padded), planes)


# Byte-plane totals accumulate in int32: a group holding n masked docs can
# reach 255*n per plane, so n must stay below 2^31/255 (~8.42M) for the
# accumulator to be exact. Callers must fall back to the two-level XLA path
# (kernels._exact_int_grouped_sum) beyond this; build_masked_fn flattens ALL
# local segments into one doc vector, so the bound is easy to exceed.
SAFE_DOCS = (2**31 - 2**24) // 255


# -- two-level byte-plane kernel: gid = hi*G2 + lo ---------------------------
#
# The flat one-hot kernel's dot is (r x chunk) @ (chunk x gtile): M = r = 8
# plane rows against the MXU's 128-row tile (~6% row utilization). The
# two-level form scales each of G2=128 lo-one-hot rows by every plane row,
# giving L[(p*G2+l), c] = plane_p[c] * (lo[c]==l), then contracts against
# the hi-one-hot: (r*G2 x chunk) @ (chunk x G1) with G1 = ng_pad/G2 — a full
# 1024-row M dimension doing IDENTICAL total MACs. The elementwise build of
# L costs only r*G2*chunk VPU ops per step (no G1 factor), so it does not
# cancel the MXU win. Same exactness invariant: products <= 255, per-chunk
# dots < 2^24 in f32, int32 cross-chunk accumulation.

G2 = 128  # lo-width: one MXU/VPU lane tile


@functools.lru_cache(maxsize=None)
def _make_planes2_kernel(r: int, g1tile: int, chunk: int):
    from jax.experimental import pallas as pl

    def kernel(gid_ref, planes_ref, out_ref):
        ci = pl.program_id(1)
        gi = pl.program_id(0)

        @pl.when(ci == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        gid = gid_ref[0, :]
        lo = gid & (G2 - 1)
        hi = gid >> (G2.bit_length() - 1)
        planes = planes_ref[:].astype(jnp.bfloat16)  # (r, chunk)
        onehot_lo = (
            jax.lax.broadcasted_iota(jnp.int32, (G2, chunk), 0) == lo[None, :]
        ).astype(jnp.bfloat16)
        left = (planes[:, None, :] * onehot_lo[None, :, :]).reshape(r * G2, chunk)
        base = gi * g1tile
        onehot_hi = (
            hi[:, None] == (base + jax.lax.broadcasted_iota(jnp.int32, (chunk, g1tile), 1))
        ).astype(jnp.bfloat16)
        acc = jnp.dot(left, onehot_hi, preferred_element_type=jnp.float32)
        out_ref[:] = out_ref[:] + acc.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("ng", "r"))
def _planes2_impl(gid, planes, ng: int, r: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_padded = gid.shape[0]
    g1 = -(-ng // G2)
    # lane-tile floor: the MXU N dimension is 128-wide — a narrower block
    # pads internally and wastes columns (same constraint the module-load
    # guards enforce on PLANES_CHUNK/GTILE)
    g1tile = min(256, max(128, -(-g1 // 128) * 128))
    g1_pad = -(-g1 // g1tile) * g1tile
    with jax.named_scope("ops.grouped_planes2"):
        out = pl.pallas_call(
            _make_planes2_kernel(r, g1tile, PLANES_CHUNK),
            name="ops_grouped_planes2_impl",
            grid=(g1_pad // g1tile, n_padded // PLANES_CHUNK),
            in_specs=[
                pl.BlockSpec((1, PLANES_CHUNK), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
                pl.BlockSpec((r, PLANES_CHUNK), lambda g, c: (jnp.int32(0), c), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (r * G2, g1tile), lambda g, c: (jnp.int32(0), g), memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct((r * G2, g1_pad), jnp.int32),
            interpret=interpret_mode(),
        )(gid.reshape(1, n_padded), planes)
    # out[(p*G2 + l), h] holds group h*G2+l: -> (r, G2, g1_pad) -> (r, ng)
    cube = out.reshape(r, G2, g1_pad)
    flat = jnp.transpose(cube, (0, 2, 1)).reshape(r, g1_pad * G2)
    return flat[:, :ng]


def planes_v2_enabled() -> bool:
    """Two-level kernel opt-in/out: PINOT_TPU_PALLAS_V2=1 forces on, =0 off.
    Default OFF until an on-chip A/B flips it (the flat kernel is the
    measured-on-hardware baseline)."""
    return os.environ.get("PINOT_TPU_PALLAS_V2", "0") == "1"


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
    pad = (-gid.shape[0]) % PLANES_CHUNK
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
    r = -(-len(rows) // 8) * 8  # pad plane rows to the f32 sublane tile
    while len(rows) < r:
        rows.append(jnp.zeros((n_padded,), jnp.float32))
    planes = jnp.stack(rows)
    name, impl = (
        ("ops.grouped_planes2", _planes2_impl)
        if planes_v2_enabled()
        else ("ops.grouped_planes", _planes_impl)
    )
    out = KERNELS.timed_sync(
        name, lambda: impl(gid, planes, ng, r), rows=n_padded, groups=ng, planes=r
    )
    sums = []
    for i in range(k):
        p = out[4 * i : 4 * i + 4, :ng].astype(jnp.float64)
        sums.append(p[0] + p[1] * 256.0 + p[2] * 65536.0 + p[3] * 16777216.0)
    counts = out[4 * k, :ng].astype(jnp.int64)
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


# -- kernel registry: cost models for the roofline report --------------------
#
# Bytes model what each grid actually streams through VMEM: every doc chunk
# is re-read once per group tile (the chunk axis is innermost), so traffic
# scales with rows x group-tiles, not rows alone. FLOPs count the one-hot
# build (1 compare) + MXU MAC (2) per (doc, group) pair.


def _planes_cost(shape: dict) -> tuple[float, float]:
    rows = max(float(shape.get("rows", 0)), 0.0)
    groups = max(float(shape.get("groups", 1)), 1.0)
    planes = max(float(shape.get("planes", 8)), 1.0)
    gtile = float(gtile_for(int(groups)))
    n_gtiles = max(-(-groups // gtile), 1.0)
    return rows * (planes + 1.0) * 4.0 * n_gtiles, rows * groups * (2.0 * planes + 1.0)


KERNELS.register(
    "ops.grouped_planes",
    _planes_impl,
    cost_model=_planes_cost,
    description="byte-plane exact SUM+COUNT, flat grid",
)
KERNELS.register(
    "ops.grouped_planes2",
    _planes2_impl,
    cost_model=_planes_cost,
    description="byte-plane exact SUM+COUNT, two-level grid (PINOT_TPU_PALLAS_V2)",
)
