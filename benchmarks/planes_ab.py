"""On-chip shape sweep of the byte-plane group-by kernel: ms per launch by group
count, plane rows and grid, each point checked against np.bincount.

    python -m benchmarks.planes_ab                       # the rule's grid at every shape
    python -m benchmarks.planes_ab --ng 7000 --planes 5 --g2 rule 1 32 64 128 --g1-tile 128 256 --chunk 4096 8192

`--g2 rule` is what `groupby_pallas.grid_for` picks for the shape; `--g2 1` is
the flat one-hot (the same kernel with one lo row, `--g1-tile` then being the
group tile). The grid is an argument here and a function of the shape in the
package: no environment variable selects it. One process, which holds the
chip; lines go to stdout and to --out. PERF.md §6 (PR 25) has the v5e's table.
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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=4096 * 1024, help="docs a launch (a served segment)")
    ap.add_argument("--ng", type=int, nargs="+", default=[175, 256, 1024, 4375, 7000, 40_000, 437_500])
    ap.add_argument("--planes", type=int, nargs="+", default=[1, 5, 9, 13], help="plane rows r = 4 x SUMs + 1")
    ap.add_argument("--g2", nargs="+", default=["rule"], help="lo widths: 'rule', 1 (flat) or a multiple of 8")
    ap.add_argument("--g1-tile", type=int, nargs="+", default=[128])
    ap.add_argument("--chunk", type=int, nargs="+", default=[4096])
    ap.add_argument("--seed", type=int, default=25)
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
