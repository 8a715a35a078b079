"""On-chip sweep of a grouped DOUBLE SUM by real group count: the dense masked
reduction against the scatter, ms per launch, each point checked against numpy.

    python -m benchmarks.grouped_dense_ab                      # 4M rows, g = 1 .. 4096
    python -m benchmarks.grouped_dense_ab --groups 6 8 --kind sum min

Both forms are `query/kernels.py`'s own (`_dense_grouped`, and `_grouped_reduce`
with no real group count, which scatters); the form is an argument here and a
function of the plan's group count in the package (`plan.DENSE_REDUCE_MAX_GROUPS`): no
environment variable selects it. One process, which holds the chip; lines go
to stdout and to --out. PERF.md §6 (PR 28) has the v5e's table.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

from benchmarks.planes_ab import _time_ms

_NP = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=4096 * 1024, help="docs a launch (a served segment)")
    ap.add_argument("--groups", type=int, nargs="+", default=[1, 2, 6, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096])
    ap.add_argument("--kind", nargs="+", default=["sum"], choices=sorted(_NP))
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--out", default="chiprun_out/grouped_dense_ab/sweep.jsonl")
    cfg = ap.parse_args()

    import pinot_tpu  # noqa: F401  (x64, compile cache)
    import jax
    import jax.numpy as jnp

    from pinot_tpu.query import kernels

    dev = jax.devices()[0]
    Path(cfg.out).parent.mkdir(parents=True, exist_ok=True)
    log = open(cfg.out, "a")

    def emit(rec: dict) -> None:
        line = json.dumps(rec)
        log.write(line + "\n")
        log.flush()
        print(line, flush=True)

    emit({"device": dev.device_kind, "platform": dev.platform, "rows": cfg.rows, "block": kernels._BLOCK})
    rng = np.random.default_rng(cfg.seed)
    v_h = rng.random(cfg.rows) * 1e5  # a price: the staged DOUBLE is what the cell sums
    mask_h = rng.random(cfg.rows) < 0.9
    v, mask = jnp.asarray(v_h), jnp.asarray(mask_h)
    for g in cfg.groups:
        gid_h = rng.integers(0, g, cfg.rows).astype(np.int32)
        gid = jnp.asarray(gid_h)
        ng = -(-g // 256) * 256  # the plan's padded slot count (plan.group_spec)
        for kind in cfg.kind:
            want = np.full(g, {"sum": 0.0, "min": np.inf, "max": -np.inf}[kind])
            _NP[kind].at(want, gid_h[mask_h], v_h[mask_h])
            forms = {
                "dense": jax.jit(lambda v, gid, mask, kind=kind, g=g: kernels._dense_grouped(kind, v, gid, mask, g)),
                "scatter": jax.jit(
                    lambda v, gid, mask, kind=kind, ng=ng: kernels._grouped_reduce(kind, v, gid, mask, ng, None)
                ),
            }
            for form, fn in forms.items():
                rec = {"groups": g, "ng": ng, "kind": kind, "form": form}
                try:
                    out, ms, iters = _time_ms(fn, (v, gid, mask))
                    got = np.asarray(out)[:g]
                    rec.update(ms=round(ms, 3), iters=iters, max_rel_err=float(np.max(np.abs(got - want) / np.abs(want))))
                except Exception as e:  # a shape the compiler refuses is a result of the sweep
                    rec["refused"] = str(e).strip().splitlines()[-1][:200]
                emit(rec)


if __name__ == "__main__":
    main()
