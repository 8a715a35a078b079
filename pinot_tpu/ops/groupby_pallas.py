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
fused programs call on TPU (kernels._grouped_all via pallas_auto): the count,
every int32 SUM / AVG as four byte planes and, where the caller passes a value
that is not int32 (a DOUBLE, a LONG past int32), that value's SUM as LIMBS
fixed-point limbs under one exponent window read off the rows ("limbs" below).
MIN / MAX stay on XLA segment reductions, as does the sum of a value whose
rows do not fit the window (the caller's choice, on the flag returned here).
"""

from __future__ import annotations

import functools
import operator
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


# -- a value that is not int32: fixed-point limbs ----------------------------
#
# A DOUBLE (or a LONG past int32, read as one) has no byte planes of its own,
# but the masked rows of one launch share an exponent window: with the largest
# binary exponent among them at the window's top, every value is an integer
# multiple of 2^w0 (the window's bit 0) of at most 8*LIMBS bits, and that
# integer splits into LIMBS signed byte limbs, each in [-255, 255] — exact in
# bf16 and under the same chunk and SAFE_DOCS bounds as an int32's planes. The
# kernel sums limbs as it sums bytes; the (LIMBS, ng) int32 limb sums are
# carried into one sign-magnitude integer per group and rounded to f64 once
# (`_limbs_to_f64`). So where every masked row fits the window the
# result is the correctly rounded true sum; where one does not (`fits` False:
# a spread of exponents past the window, a NaN, an infinity, a value outside
# float32's normal range) the limbs are garbage and the caller reduces another
# way (kernels._grouped_all: the scatter, as the other branch of a lax.cond).
#
# A value is peeled through float32: v = a + b + c with a = f32(v),
# b = f32(v - a), c = f32(v - a - b), each subtraction exact, the three
# significands disjoint (|b| <= ulp(a)/2), so a limb holding bits of two
# pieces of opposite sign still lies in [-255, 255]. Three pieces hold an IEEE
# double's 53 bits; the TPU's f64 is a pair of float32s and c is 0 there.
# Nothing here is assumed of the chip's emulation: the residual, the pieces'
# disjointness and the bits a piece would lose below the window are all read
# off the rows and folded into `fits`. After the three subtractions the work is
# int32 shifts and masks.
#
# 12 limbs: 96 bits hold every 53-bit value whose exponent lies within 43 bits
# of the largest. The kernel's time is linear in its plane rows (PERF.md §6,
# PR 36, has the v5e's readings by LIMBS).

LIMBS = 12


def _f32_fields(p):
    """f32 `p` as sign * m * 2^(field - 150): the 24-bit significand (implicit
    bit included), the biased exponent field (a denormal's is 1) and -1 / 1."""
    u = jax.lax.bitcast_convert_type(p, jnp.int32)
    field = (u >> 23) & 0xFF
    m = (u & 0x7FFFFF) | jnp.where(field != 0, 1 << 23, 0)
    return m, jnp.maximum(field, 1), jnp.where(u < 0, jnp.int32(-1), jnp.int32(1))


def limb_planes(v, mask):
    """((LIMBS, n) f32 limb planes of the masked rows of `v`, w0, fits): where
    `fits`, row i of `v` (0 where masked out) is sum_j planes[j, i] * 2^(8j + w0)
    exactly and every limb lies in [-255, 255]; where not, the planes say nothing."""
    r = jnp.where(mask, v.astype(jnp.float64), 0.0)
    pieces = []
    for _ in range(3):
        p = r.astype(jnp.float32)
        pieces.append(_f32_fields(p))
        r = r - p.astype(jnp.float64)
    fits = jnp.all(r == 0)  # false for a NaN, an infinity and what float32 cannot reach, too
    top = jnp.max(pieces[0][1])
    w0 = top - (126 + 8 * LIMBS)  # the top piece's highest bit, top - 127, is the window's last
    lane = (8 * jnp.arange(LIMBS, dtype=jnp.int32) + 8)[:, None]
    planes, above = 0, None
    for m, field, sign in pieces:
        if above is not None:  # below the piece before: the limbs' bound rests on it
            fits &= jnp.all((m == 0) | (field <= above - 24))
        above = field
        s = field - 150 - w0  # how far above the window's bit 0 the piece's bit 0 lies
        drop = jnp.clip(-s, 0, 24)  # bits of the piece under the window: they must be zeros
        fits &= jnp.all((m & ((1 << drop) - 1)) == 0)
        # the piece at bit 8 of a word: limb j is the byte at bit 8j + 8 - s of it, where there is one
        word = (m >> drop).astype(jnp.uint32) << 8
        sh = lane - jnp.maximum(s, 0)[None, :]
        byte = jnp.where(sh < 32, (word[None, :] >> jnp.clip(sh, 0, 31).astype(jnp.uint32)) & 0xFF, 0)
        planes = planes + sign[None, :] * byte.astype(jnp.int32)
    return planes.astype(jnp.float32), w0, fits


def _scale2(x, k):
    """f64 `x` * 2^k for int32 `k` within +/-300, exactly: three powers of two
    that float32 holds, built from their exponent fields."""
    for _ in range(3):
        step = jnp.clip(k, -100, 100)
        x = x * jax.lax.bitcast_convert_type((step + 127) << 23, jnp.float32).astype(jnp.float64)
        k = k - step
    return x


def _added(parts):
    """The parts' sum without `sum`'s leading 0, which would leave a `0 + x`
    in the HLO of an int32-only program that has one block."""
    return functools.reduce(operator.add, parts)


def _carried(sums):
    """(k, ng) int32 limb sums, |sum| <= 2^31 - 2^24 -> the same number as a
    list of k + 4 int32 digits of 8 bits, all in [0, 255] but the last, which
    keeps the sign. int32 all the way: a carry is under 2^23."""
    digits, carry = [], 0
    for j in range(sums.shape[0] + 3):
        t = carry + (sums[j] if j < sums.shape[0] else 0)
        digits.append(t & 0xFF)
        carry = t >> 8  # arithmetic: the floor, so the digit is never negative
    return digits + [carry]


def _limbs_to_f64(blocks, w0):
    """[(LIMBS, ng) int32 limb sums a block of docs] -> (ng,) f64, the blocks'
    sum_j sums[j] * 2^(8j + w0) rounded once. The limbs are carried into
    digits of 8 bits, sign and magnitude, in int32 (the chip's int64 is a
    pair of words, and this stays off it); the leading six digits of the
    magnitude and the six under them are each an exact f64 of 48 bits, and
    whatever lies below folds into the last bit of the lower one (it is far
    under the rounding point, so it only breaks ties): their sum is the one
    rounding."""
    total = blocks[0]
    if len(blocks) > 1:  # a block's digits are small: they add, and carry once more
        total = _added(jnp.stack(_carried(b)) for b in blocks)
    neg = _carried(total)[-1] < 0  # the digits under the top one are not negative
    d = _carried(jnp.where(neg, -total, total))
    lead = functools.reduce(jnp.maximum, [jnp.where(b != 0, j, 0) for j, b in enumerate(d)])  # the highest digit not 0
    sticky = functools.reduce(jnp.logical_or, [(b != 0) & (j < lead - 11) for j, b in enumerate(d)])
    hi = lo = jnp.zeros(neg.shape, jnp.float64)
    for j, b in enumerate(d):
        b = jnp.where(sticky & (j == lead - 11), b | 1, b)
        term = b.astype(jnp.float64) * 2.0 ** (8 * j - 64)  # centred: a float32's exponent holds both ends
        hi = hi + jnp.where((j <= lead) & (j > lead - 6), term, 0.0)
        lo = lo + jnp.where((j <= lead - 6) & (j > lead - 12), term, 0.0)
    mag = _scale2(hi + lo, w0 + 64)
    return jnp.where(neg, -mag, mag)


def _multi_sum(values_list, gid, mask, ng: int, block):
    """The pass behind both entry points: plane rows of every value and the
    mask, the kernel over each `block` of docs (all of them where None), the
    plane sums recombined."""
    wide = [v.dtype != jnp.int32 for v in values_list]
    r = sum(LIMBS if w else 4 for w in wide) + 1  # and the mask: no pad rows, L's rows are r*G2
    grid = grid_for(ng, r)
    pad = (-gid.shape[0]) % grid.chunk
    n_padded = gid.shape[0] + pad
    gid = jnp.pad(gid.astype(jnp.int32), (0, pad))
    mask = jnp.pad(mask, (0, pad))
    rows, windows = [], []  # a value's (w0, fits) where it goes in as limbs, None where as byte planes
    for v, w in zip(values_list, wide):
        if w:
            planes, w0, fits = limb_planes(jnp.pad(v, (0, n_padded - v.shape[0])), mask)
            rows.extend(planes)
            windows.append((w0, fits))
            continue
        windows.append(None)
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
    outs, step = [], block or n_padded
    for start in range(0, n_padded, step):
        end = min(start + step, n_padded)
        outs.append(
            KERNELS.timed_sync(
                "ops.grouped_planes2",
                lambda: _planes2_impl(gid[start:end], planes[:, start:end], ng, grid),
                rows=end - start,
                groups=ng,
                planes=r,
            )
        )
    sums, at = [], 0
    for window in windows:
        if window is None:
            # a block's byte-plane sums are exact in f64, and so is their sum over the blocks
            planes4 = [o[at : at + 4].astype(jnp.float64) for o in outs]
            sums.append(_added(p[0] + p[1] * 256.0 + p[2] * 65536.0 + p[3] * 16777216.0 for p in planes4))
            at += 4
        else:
            w0, fits = window
            sums.append((_limbs_to_f64([o[at : at + LIMBS] for o in outs], w0), fits))
            at += LIMBS
    return sums, _added(o[at].astype(jnp.int64) for o in outs)


def pallas_grouped_multi_sum(values_list, gid, mask, ng: int):
    """Fused lossless group-by reduction: the sum of every value array and
    the group count, in ONE pallas pass. Returns ([a sum per input], i64 (ng,)
    counts): an int32 input's sum is f64 (ng,), exact; any other input (read
    as f64) gives the pair (f64 (ng,), fits) — the correctly rounded sum where
    the scalar `fits` is True, nothing where it is False (`limb_planes`).

    Exactness requires the flat doc count <= SAFE_DOCS (asserted). A kernel
    the compiler refuses raises: nothing substitutes another one."""
    if gid.shape[0] > SAFE_DOCS:  # not assert: must survive python -O
        raise ValueError(
            f"pallas byte-plane accumulator overflows past {SAFE_DOCS} docs; "
            "use the XLA two-level path for larger inputs"
        )
    return _multi_sum(values_list, gid, mask, ng, None)


def pallas_grouped_multi_sum_blocked(values_list, gid, mask, ng: int):
    """SAFE_DOCS-unbounded variant: statically slices the doc axis into
    blocks that each respect the int32 plane-accumulator bound and adds the
    per-block plane sums in int64 (a wide value's window is the whole
    launch's, so its limbs add across blocks). Two slices cover 16M docs;
    per-slice cost is one extra kernel launch."""
    n = gid.shape[0]
    block = None if n <= SAFE_DOCS else (SAFE_DOCS // PLANES_CHUNK) * PLANES_CHUNK
    return _multi_sum(values_list, gid, mask, ng, block)


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
