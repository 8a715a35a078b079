"""On-chip sweep of a grouped DOUBLE SUM by real group count: the dense masked
reduction, the scatter and the fixed-point limbs on the byte-plane kernel, ms
per launch, each point checked against numpy.

    python -m benchmarks.grouped_dense_ab                      # 4M rows, g = 1 .. 65,536
    python -m benchmarks.grouped_dense_ab --groups 6 8 --kind sum min
    python -m benchmarks.grouped_dense_ab --groups 12032 --limbs 8 10 12

All three forms are the package's own (`kernels._dense_grouped`;
`kernels._grouped_reduce` with no real group count, which scatters;
`groupby_pallas.pallas_grouped_multi_sum_blocked` handed a DOUBLE, which peels
limbs); the form is an argument here and a function of the plan's group count,
the value's dtype and the rows' exponents in the package
(`plan.DENSE_REDUCE_MAX_GROUPS`, `kernels._grouped_all`): no environment
variable selects it. Before the sweep the limbs of the chip's own values are
read back and held against the host's integers, bit for bit (`peel_check`).
One process, which holds the chip; lines go to stdout and to --out. PERF.md §6
(PR 28, PR 36) has the v5e's tables.
"""

from __future__ import annotations

import argparse
import json
from fractions import Fraction
from pathlib import Path

import numpy as np

from benchmarks.planes_ab import _time_ms

_NP = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def peel_check(v_h: np.ndarray, mask_h: np.ndarray) -> dict:
    """`groupby_pallas.limb_planes` on this device against exact host integers:
    every row's limbs must add up to the value the device holds (read back, so
    the chip's own f64), each within [-255, 255]."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp

    v = jnp.asarray(v_h)
    planes, w0, fits = jax.jit(gp.limb_planes)(v, jnp.asarray(mask_h))
    planes, w0, held = np.asarray(planes), int(w0), np.asarray(v)
    limbs = planes.astype(np.int64)
    wrong = int((limbs != planes).sum())  # a limb that is no integer
    unit = Fraction(2) ** w0
    checked = np.flatnonzero(mask_h)[:200_000]
    for i in checked:
        total = sum(int(limbs[j, i]) << (8 * j) for j in range(limbs.shape[0]))
        wrong += Fraction(float(held[i])) != total * unit
    return {
        "peel_check": len(checked),
        "rows_not_their_limbs": wrong,
        "rows_the_host_holds_otherwise": int((held != v_h).sum()),
        "max_abs_limb": float(np.abs(planes).max()),
        "masked_rows_not_zero": int(np.abs(planes[:, ~mask_h]).sum()),
        "fits": bool(fits),
        "w0": w0,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=4096 * 1024, help="docs a launch (a served segment)")
    ap.add_argument(
        "--groups", type=int, nargs="+",
        default=[1, 2, 6, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 12032, 16384, 65536],
    )  # fmt: skip
    ap.add_argument("--kind", nargs="+", default=["sum"], choices=sorted(_NP))
    ap.add_argument("--forms", nargs="+", default=["dense", "scatter", "limbs"], choices=["dense", "scatter", "limbs"])
    ap.add_argument("--limbs", type=int, nargs="+", default=None, help="limb counts to time (the package's: one)")
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--out", default="chiprun_out/grouped_dense_ab/sweep.jsonl")
    cfg = ap.parse_args()

    import pinot_tpu  # noqa: F401  (x64, compile cache)
    import jax
    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp
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
    if "limbs" in cfg.forms:
        emit(peel_check(v_h, mask_h))
        signed = np.where(rng.random(cfg.rows) < 0.5, -v_h, v_h) * 2.0 ** rng.integers(-8, 8, cfg.rows)
        emit(peel_check(signed, mask_h))
    v, mask = jnp.asarray(v_h), jnp.asarray(mask_h)
    v_held = np.asarray(v)  # the chip's f64 is narrower than the host's: the limbs' sum is exact in what it holds
    for g in cfg.groups:
        gid_h = rng.integers(0, g, cfg.rows).astype(np.int32)
        gid = jnp.asarray(gid_h)
        ng = -(-g // 256) * 256  # the plan's padded slot count (plan.group_spec)
        for kind in cfg.kind:
            # a sum's reference adds in the host's 80-bit long double: numpy's own running f64 sum is no closer
            # to the true one than the forms it is to judge
            want = np.full(g, {"sum": 0.0, "min": np.inf, "max": -np.inf}[kind], np.longdouble)
            _NP[kind].at(want, gid_h[mask_h], v_held[mask_h].astype(np.longdouble))
            forms = {
                "dense": [(None, jax.jit(lambda v, gid, mask, kind=kind, g=g: kernels._dense_grouped(kind, v, gid, mask, g)))],
                "scatter": [
                    (None, jax.jit(lambda v, gid, mask, kind=kind, ng=ng: kernels._grouped_reduce(kind, v, gid, mask, ng, None)))
                ],
                # the limbs sum and nothing else; jit reads gp.LIMBS when it traces, so each count is its own function
                "limbs": [
                    (p, jax.jit(lambda v, gid, mask, ng=ng, p=p: gp.pallas_grouped_multi_sum_blocked([v], gid, mask, ng)[0][0]))
                    for p in (cfg.limbs or [gp.LIMBS])
                    if kind == "sum"
                ],
            }  # fmt: skip
            for form in cfg.forms:
                for p, fn in forms[form]:
                    rec = {"groups": g, "ng": ng, "kind": kind, "form": form}
                    packaged = gp.LIMBS
                    try:
                        if p is not None:
                            gp.LIMBS = p
                            rec["limbs"] = p
                        out, ms, iters = _time_ms(fn, (v, gid, mask))
                        if form == "limbs":
                            out, fits = out
                            rec["fits"] = bool(fits)
                        got = np.asarray(out)[:g]
                        rec.update(ms=round(ms, 3), iters=iters, max_rel_err=float(np.max(np.abs(got - want) / np.where(want == 0, 1, np.abs(want)))))
                    except Exception as e:  # a shape the compiler refuses is a result of the sweep
                        rec["refused"] = str(e).strip().splitlines()[-1][:200]
                    finally:
                        gp.LIMBS = packaged
                    emit(rec)


if __name__ == "__main__":
    main()
