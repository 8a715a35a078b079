"""Device-side shuffle: the HASH_DISTRIBUTED exchange tier as ICI
collectives.

Reference parity: Pinot's multistage exchange strategies
(pinot-query-runtime/.../runtime/operator/exchange/BlockExchange.java:41,50-59
— SINGLETON / HASH_DISTRIBUTED / RANDOM_DISTRIBUTED / BROADCAST_DISTRIBUTED)
move DataBlock pages between workers over gRPC mailboxes. For stages that
live on the SAME device mesh, that network hop is redesigned as
`lax.all_to_all` inside `shard_map` (SURVEY §5.8 mapping: shuffle -> ICI
all-to-all): each shard buckets its rows by destination = hash(key) mod D,
packs them into equal-capacity send buffers (static shapes for XLA), and one
collective delivers every bucket. Three exchange shapes:

- `hash_exchange`: row-level HASH exchange of arbitrary column payloads
  (the BlockExchange HASH_DISTRIBUTED analog for join repartition).
- `exchange_group_partials`: dense group-partial repartition — each device
  ends up owning one contiguous range of the group space (the
  partial-aggregate HASH exchange on the group key; block-split rather than
  row-level because dense gid spaces are already the partition function).
- `mesh_equi_join`: repartition both join sides by key, per-shard
  sort+searchsorted probe (LookupJoinOperator-style FK->PK join,
  pinot-query-runtime/.../runtime/operator/LookupJoinOperator.java).

Static-shape discipline: per-destination capacity bounds the send buffers;
overflow is counted on device and surfaces to the caller, which retries
with the safe capacity (= local row count) or falls back host-side.
"""

from __future__ import annotations

import threading
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pinot_tpu.common.kernel_obs import KERNELS

# Multi-device collective launches must not interleave: two host threads
# each enqueueing an all_to_all across the same mesh can order their
# per-device work differently on different devices, and the collective
# deadlocks waiting for peers that are stuck behind the other launch.
# The multistage engine's stage workers call mesh_equi_join concurrently
# (one hash partition per worker), so serialize every launch here.
_COLLECTIVE_LAUNCH_LOCK = threading.Lock()


def _hash64(x):
    """Full-width key hash via the shared murmur3 finalizer (jnp_mix32,
    query/sketches.py): lo32 ^ mix32(hi32) then a final mix. Hashing BOTH
    halves matters — float64-bitcast integer keys carry all their entropy
    in the high word (low mantissa bits are zero), so a low-bits-only hash
    would route every row to one shard."""
    from pinot_tpu.query.sketches import jnp_mix32

    xi = x.astype(jnp.int64)
    lo = (xi & 0xFFFFFFFF).astype(jnp.uint32)
    hi = ((xi >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
    return jnp_mix32(jnp, lo ^ jnp_mix32(jnp, hi))


def _bucket_pack(cols: tuple, key, valid, n_dest: int, capacity: int):
    """Pack rows into (n_dest * capacity) send slots by destination shard.
    Returns (packed_cols, packed_valid, n_dropped). Rows overflowing a
    destination's capacity are dropped and counted.

    Sort-free: the within-bucket rank comes from a one-hot cumsum over the
    (n, D) destination matrix — O(n*D) elementwise work that XLA vectorizes
    well on every backend, vs an argsort whose comparator lowering is the
    dominant cost of the whole exchange (profiled r5: the sort was ~10x the
    rest of the pack)."""
    dest = (_hash64(key) % jnp.uint32(n_dest)).astype(jnp.int32)
    dest = jnp.where(valid, dest, n_dest)
    onehot = (dest[:, None] == jnp.arange(n_dest, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - 1  # (n, D): rank within each bucket
    posn = jnp.sum(jnp.where(onehot > 0, rank, 0), axis=1)
    ok = (dest < n_dest) & (posn < capacity)
    slot = jnp.where(ok, dest * capacity + posn, n_dest * capacity)
    dropped = jnp.sum((dest < n_dest) & (posn >= capacity), dtype=jnp.int32)
    packed = tuple(
        jnp.zeros((n_dest * capacity,), dtype=c.dtype).at[slot].set(c, mode="drop")
        for c in cols
    )
    pvalid = jnp.zeros((n_dest * capacity,), dtype=bool).at[slot].set(ok, mode="drop")
    return packed, pvalid, dropped


def hash_exchange(cols: tuple, key, valid, axis: str, n_dest: int, capacity: int):
    """Row-level HASH_DISTRIBUTED exchange (call inside shard_map).

    Each shard sends every row to shard `hash(key) % D` via ONE
    `lax.all_to_all`. Returns (received_cols, received_valid, total_dropped):
    received arrays are (D * capacity,) — capacity rows from each peer —
    and total_dropped is psum'd so every shard can detect overflow."""
    packed, pvalid, dropped = _bucket_pack(cols, key, valid, n_dest, capacity)

    def ex(buf):
        return jax.lax.all_to_all(
            buf.reshape(n_dest, capacity), axis, split_axis=0, concat_axis=0
        ).reshape(n_dest * capacity)

    out = tuple(ex(c) for c in packed)
    ovalid = ex(pvalid)
    return out, ovalid, jax.lax.psum(dropped, axis)


def exchange_group_partials(partial, axis: str, n_dest: int):
    """Dense group-partial HASH exchange: split the group space into D
    contiguous ranges, all_to_all so device d receives every peer's block
    for range d, reduce locally, then all_gather the owned ranges back to
    the full replicated vector. Equivalent in result to psum, but the
    reduction work and ICI traffic follow the HASH-exchange pattern (each
    device owns a group range — the multistage partial-agg repartition).
    `partial` is (ng,) with ng % n_dest == 0; call inside shard_map."""
    ng = partial.shape[0]
    assert ng % n_dest == 0, (ng, n_dest)
    blocks = partial.reshape(n_dest, ng // n_dest)
    recv = jax.lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0)
    own = jnp.sum(recv, axis=0)  # this shard's group range, fully reduced
    return jax.lax.all_gather(own, axis).reshape(ng)


@lru_cache(maxsize=64)
def _join_kernel(mesh: Mesh, axis: str, lc: int, rc: int, capacity: int, kdt: str):
    """Jitted mesh equi-join: hash-repartition both sides, per-shard
    sorted probe. Right keys must be unique (FK->PK lookup join)."""
    n_dest = mesh.shape[axis]
    kdtype = jnp.dtype(kdt)

    def per_shard(lk, lidx, rk, ridx):
        # shard_map hands each shard its (1, n_local) slice — flatten
        lk, lidx, rk, ridx = (x.reshape(-1) for x in (lk, lidx, rk, ridx))
        (lk2, lidx2), lvalid, ldrop = hash_exchange(
            (lk, lidx), lk, lidx >= 0, axis, n_dest, capacity
        )
        (rk2, ridx2), rvalid, rdrop = hash_exchange(
            (rk, ridx), rk, ridx >= 0, axis, n_dest, capacity
        )
        # per-shard probe: sort received right rows by key. Empty receive
        # slots carry the sentinel key (INT_MAX) — the host wrapper declines
        # inputs containing that value, so the sentinel uniquely marks
        # invalid slots and ONE plain sort suffices (a validity tie-break
        # lexsort doubled the dominant sort cost). Hits still check slot
        # validity so a sentinel-valued LEFT key can't match padding.
        big = jnp.array(jnp.iinfo(kdtype).max, dtype=kdtype)
        rkey_s = jnp.where(rvalid, rk2, big)
        order = jnp.argsort(rkey_s)
        rs = rkey_s[order]
        rv = rvalid[order]
        # duplicate build keys invalidate the unique-right contract; equal
        # keys always hash to the same shard, so a local adjacency check
        # (psum'd) sees every duplicate pair
        dup = jnp.sum((rs[1:] == rs[:-1]) & rv[1:] & rv[:-1], dtype=jnp.int32)
        dup = jax.lax.psum(dup, axis)
        pos = jnp.clip(jnp.searchsorted(rs, lk2), 0, rs.shape[0] - 1)
        hit = (rs[pos] == lk2) & lvalid & rv[pos]
        rmatch = jnp.where(hit, ridx2[order][pos], -1)
        return (
            lidx2[None, :],
            rmatch[None, :],
            hit[None, :],
            (ldrop + rdrop)[None],
            dup[None],
        )

    def run(lk, lidx, rk, ridx):
        f = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis, None)),
            out_specs=P(axis),
            check_vma=False,
        )
        li, ri, hit, drops, dups = f(lk, lidx, rk, ridx)
        return li.reshape(-1), ri.reshape(-1), hit.reshape(-1), jnp.max(drops), jnp.max(dups)

    return jax.jit(run)


def mesh_equi_join(
    lk: np.ndarray, rk: np.ndarray, mesh: Mesh | None = None
) -> "tuple[np.ndarray, np.ndarray] | None":
    """Inner equi-join of two integer key arrays via the mesh all_to_all
    exchange. Returns (l_idx, r_idx) matched-pair index arrays, or None when
    the shape can't ride this path (non-int keys, duplicate right keys,
    single-device mesh, capacity overflow after retry). Contract matches
    multistage.runtime._device_equi_join."""
    if mesh is None:
        from pinot_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(axis="shuf")
    axis = mesh.axis_names[0]
    n_dest = mesh.shape[axis]
    if n_dest < 2:
        return None
    if not (np.issubdtype(lk.dtype, np.integer) and np.issubdtype(rk.dtype, np.integer)):
        return None
    # duplicate build keys (many-to-many) are detected ON DEVICE inside the
    # kernel — a host-side uniqueness sort here would cost as much as the
    # join being offloaded
    kdt = np.promote_types(lk.dtype, rk.dtype)
    if kdt not in (np.dtype(np.int32), np.dtype(np.int64)):
        kdt = np.dtype(np.int64)
    if len(rk) and bool((rk.astype(kdt) == np.iinfo(kdt).max).any()):
        # a build key at the padding sentinel AFTER the kdt cast (including
        # uint64 values that wrap to it) would be indistinguishable from
        # empty receive slots in the sorted probe — rare; decline
        return None

    def shardify(keys: np.ndarray):
        n = len(keys)
        # pow2 bucket: bounds distinct compiled kernels to O(log n) across
        # varying join sizes (review r5) at <2x padding cost
        per = 1 << max(6, int(np.ceil(np.log2(-(-max(n, 1) // n_dest))))) if n else 64
        kp = np.full(n_dest * per, np.iinfo(kdt).max, dtype=kdt)
        ip = np.full(n_dest * per, -1, dtype=np.int32)
        kp[:n] = keys.astype(kdt)
        ip[:n] = np.arange(n, dtype=np.int32)
        sharding = NamedSharding(mesh, P(axis, None))
        return (
            jax.device_put(kp.reshape(n_dest, per), sharding),
            jax.device_put(ip.reshape(n_dest, per), sharding),
            per,
        )

    with _COLLECTIVE_LAUNCH_LOCK:
        lkd, lid, lc = shardify(lk)
        rkd, rid, rc = shardify(rk)
        # worst case one shard receives EVERYTHING both sides hold for one
        # destination: start at balanced-x2, retry once at the safe bound
        # (pow2 capacities keep the compile cache warm across sizes; the
        # received-buffer size D*capacity is what the per-shard probe sorts,
        # so slack directly multiplies the dominant sort cost)
        cap0 = 1 << max(6, int(np.ceil(np.log2(max(1, -(-2 * max(lc, rc) // n_dest))))))
        for capacity in (cap0, max(lc, rc)):
            run = _join_kernel(mesh, axis, lc, rc, int(capacity), str(kdt))
            li, ri, hit, drops, dups = KERNELS.timed_sync(
                "exchange.join",
                lambda: run(lkd, lid, rkd, rid),
                rows=n_dest * int(capacity),
            )
            if int(dups) > 0:
                return None  # many-to-many: single-device range-probe handles
            if int(drops) == 0:
                h = np.asarray(hit)
                return np.asarray(li)[h], np.asarray(ri)[h]
    return None


# -- kernel registry: cost model for the roofline report ---------------------
#
# rows = the exchanged buffer slots (n_dest * capacity). Both sides' key+idx
# columns cross the ICI twice (send + receive), and the per-shard probe is
# sort-dominated: ~2 * rows * log2(rows) compare/moves.


def _join_cost(shape: dict) -> tuple[float, float]:
    rows = max(float(shape.get("rows", 0)), 1.0)
    return rows * (8.0 + 4.0) * 2.0 * 2.0, rows * 2.0 * max(float(np.log2(rows)), 1.0)


KERNELS.register(
    "exchange.join",
    _join_kernel,
    cost_model=_join_cost,
    description="mesh equi-join: hash all_to_all repartition + sorted probe",
)
