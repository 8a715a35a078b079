"""On-chip shape sweep of the byte-plane group-by kernel: ms per launch by group
count, plane rows and grid, each point checked against np.bincount.

    python -m benchmarks.planes_ab                       # the rule's grid at every shape
    python -m benchmarks.planes_ab --ng 7000 --planes 5 --g2 rule 1 32 64 128 --g1-tile 128 256 --chunk 4096 8192
    python -m benchmarks.planes_ab --compact             # a launch over the keys' product against one over the groups a filter leaves

`--g2 rule` is what `groupby_pallas.grid_for` picks for the shape; `--g2 1` is
the flat one-hot (the same kernel with one lo row, `--g1-tile` then being the
group tile). The grid is an argument here and a function of the shape in the
package: no environment variable selects it. One process, which holds the
chip; lines go to stdout and to --out. PERF.md §6 (PR 25) has the v5e's table.

`--compact` is the sweep that sets `plan.COMPACT_MIN_GROUPS`: the fused
program of `SELECT k.., SUM(v), COUNT(*) .. WHERE each key IN (its first
--survive values) GROUP BY k..` (membership tables, SSB Q3.3's filter) at --rows, once under the dense group spec
("groups": the keys' product) and once under "groups_compact" (each key
renumbered by the values that pass, `plan.COMPACT_SLOTS` slots), over products
of 4,096 to 437,500 and two or three keys; the compact answer is checked
against the dense one group by group. PERF.md §6 (PR 45) has the v5e's table.
"""

from __future__ import annotations

import argparse
import itertools
import json
import time
from pathlib import Path

import numpy as np


def _time_ms(fn, args) -> tuple[object, float, int]:
    import jax

    out = jax.block_until_ready(fn(*args))  # compiles
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    once = (time.perf_counter() - t0) * 1e3
    iters = int(max(2, min(20, 300.0 / max(once, 0.1))))
    t0 = time.perf_counter()
    jax.block_until_ready([fn(*args) for _ in range(iters)])
    return out, (time.perf_counter() - t0) * 1e3 / iters, iters


#: key cardinalities of the compact sweep: two and three keys at products of 4,096 .. 437,500 (SSB Q3.2's), then
#: Q4.3's 1,750,000 and the widest key that is renumbered (plan.COMPACT_MAX_KEY_CARD)
COMPACT_SHAPES = ((64, 64), (128, 128), (256, 256), (700, 625), (16, 16, 16), (32, 32, 16), (64, 64, 16), (250, 250, 7), (7, 250, 1000), (4096, 64))


def compact_sweep(cfg, emit) -> None:
    """Dense against compact, a line a shape of COMPACT_SHAPES."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.query import kernels, plan

    rng = np.random.default_rng(cfg.seed)
    n = cfg.rows
    for cards in COMPACT_SHAPES:
        keys = tuple(f"k{i}" for i in range(len(cards)))
        cols_h = {k: rng.integers(0, c, n).astype(np.int32) for k, c in zip(keys, cards)}
        cols_h["@0"] = rng.integers(0, 1 << 20, n).astype(np.int32)
        cols = {k: jnp.asarray(v) for k, v in cols_h.items()}
        product = int(np.prod(cards))
        survive = [min(cfg.survive, c) for c in cards]
        # operands: a membership table a key (over its dictionary, padded to a power of two), then the group spec's strides
        tables = tuple(np.arange(plan._pow2(c)) < s for c, s in zip(cards, survive))
        fspec = ("and", tuple(("in_lut", k, i) for i, k in enumerate(keys)))
        aggs = (("sum", ("raw", "@0")), ("count",))
        dense = ("agg", fspec, ("groups", keys, -(-product // 256) * 256, len(tables)), aggs)
        widths = tuple(("rank", plan._eighths(c)) for c in cards)
        compact = ("agg", fspec, ("groups_compact", keys, plan.COMPACT_SLOTS, len(tables), widths), aggs)
        rec = {"keys": list(cards), "product": product, "left": int(np.prod(survive))}
        got = {}
        for name, spec, strides in (("dense", dense, np.int32), ("compact", compact, np.int64)):
            ops = tables + (plan.group_strides(list(cards), strides),)
            fn = jax.jit(kernels.build_fn(spec), static_argnums=3)
            got[name], rec[f"{name}_ms"], _ = _time_ms(lambda c, o: fn(c, o, np.int32(n), n), (cols, ops))
            rec[f"{name}_ms"] = round(rec[f"{name}_ms"], 3)
        prelude = jax.jit(lambda c, o: kernels._compact_groups(keys, widths, plan.COMPACT_SLOTS, o[-1], c, o, kernels._filter(fspec, c, o, n)))
        _, ms, _ = _time_ms(prelude, (cols, tables + (plan.group_strides(list(cards), np.int64),)))
        rec["compact_prelude_ms"] = round(ms, 3)
        _, d_counts, (d_sum, _) = jax.tree.map(np.asarray, got["dense"])
        _, c_counts, (c_sum, _), gids, total = jax.tree.map(np.asarray, got["compact"])
        at, slot = np.nonzero(d_counts)[0], np.nonzero(c_counts)[0]
        rec["total"] = int(total)
        rec["same"] = bool(
            np.array_equal(gids[slot], at) and np.array_equal(c_counts[slot], d_counts[at]) and np.array_equal(c_sum[slot], d_sum[at])
        )
        emit(rec)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=4096 * 1024, help="docs a launch (a served segment)")
    ap.add_argument("--ng", type=int, nargs="+", default=[175, 256, 1024, 4375, 7000, 40_000, 437_500])
    ap.add_argument("--planes", type=int, nargs="+", default=[1, 5, 9, 13], help="plane rows r = 4 x SUMs + 1")
    ap.add_argument("--g2", nargs="+", default=["rule"], help="lo widths: 'rule', 1 (flat) or a multiple of 8")
    ap.add_argument("--g1-tile", type=int, nargs="+", default=[128])
    ap.add_argument("--chunk", type=int, nargs="+", default=[4096])
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--compact", action="store_true", help="the dense group spec against the compact one (COMPACT_SHAPES)")
    ap.add_argument("--survive", type=int, default=10, help="--compact: values of each key that the filter lets through")
    ap.add_argument("--out", default="chiprun_out/planes_ab/sweep.jsonl")
    cfg = ap.parse_args()

    import pinot_tpu  # noqa: F401  (x64, compile cache)
    import jax
    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp

    dev = jax.devices()[0]
    Path(cfg.out).parent.mkdir(parents=True, exist_ok=True)
    log = open(cfg.out, "a")

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        log.write(line + "\n")
        log.flush()
        print(line, flush=True)

    for chunk in cfg.chunk:
        gp._check_chunk(chunk)
    emit({"device": dev.device_kind, "platform": dev.platform, "rows": cfg.rows})
    if cfg.compact:
        return compact_sweep(cfg, emit)
    rng = np.random.default_rng(cfg.seed)
    for ng, r in itertools.product(cfg.ng, cfg.planes):
        gid_h = rng.integers(0, ng, cfg.rows).astype(np.int32)
        planes_h = rng.integers(-128, 256, (r, cfg.rows)).astype(np.float32)
        planes_h[-1] = rng.random(cfg.rows) < 0.6  # the mask's row
        want = np.stack([np.bincount(gid_h, weights=p, minlength=ng) for p in planes_h]).astype(np.int64)
        gid, planes = jnp.asarray(gid_h), jnp.asarray(planes_h)
        seen = set()
        for g2, g1_tile, chunk in itertools.product(cfg.g2, cfg.g1_tile, cfg.chunk):
            grid = gp.grid_for(ng, r, chunk) if g2 == "rule" else gp.PlanesGrid(int(g2), g1_tile, chunk)
            if grid in seen or cfg.rows % chunk:
                continue
            seen.add(grid)
            rec = {"ng": ng, "planes": r, **grid._asdict(), "hi_tiles": gp._hi_tiles(ng, grid), "rule": g2 == "rule"}
            try:
                out, ms, iters = _time_ms(lambda g, p: gp._planes2_impl(g, p, ng, grid), (gid, planes))
                rec.update(ms=round(ms, 3), iters=iters, exact=bool(np.array_equal(np.asarray(out), want)))
            except Exception as e:  # a grid the compiler refuses is a result of the sweep
                rec["refused"] = str(e).strip().splitlines()[-1][:200]
            emit(rec)


if __name__ == "__main__":
    main()
