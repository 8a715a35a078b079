"""On-chip A/B of the flat vs two-level byte-plane group-by kernels.

Run where there is a TPU (the parent never imports jax, so each child can own
the chip in turn):
    python -m benchmarks.planes_ab
Flip the default in ops/groupby_pallas.py (planes_v2_enabled) if v2 wins —
theory says the (r*G2 x chunk) @ (chunk x G1) form lifts MXU row
utilization from r/128 to full, for identical total MACs."""

import json
import os
import subprocess
import sys

_CHILD = r"""
import json, os, sys, time
import numpy as np
import pinot_tpu
import jax, jax.numpy as jnp
from pinot_tpu.ops import groupby_pallas as gp

n, ng = int(sys.argv[1]), int(sys.argv[2])
rng = np.random.default_rng(0)
gid = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
vals = jnp.asarray(rng.integers(-100000, 100000, n).astype(np.int32))
mask = jnp.asarray(rng.random(n) < 0.9)
jax.block_until_ready((gid, vals, mask))

@jax.jit
def run(g, v, m):
    s, c = gp.pallas_grouped_multi_sum_blocked([v], g, m, ng)
    return s[0], c

out = jax.block_until_ready(run(gid, vals, mask))
t0 = time.perf_counter()
outs = [run(gid, vals, mask) for _ in range(20)]
jax.block_until_ready(outs)
dt = (time.perf_counter() - t0) / 20 * 1e3
want = np.bincount(np.asarray(gid)[np.asarray(mask)],
                   weights=np.asarray(vals)[np.asarray(mask)].astype(np.float64), minlength=ng)
ok = bool(np.array_equal(np.asarray(out[0]), want))
print(json.dumps({"v2": os.environ.get("PINOT_TPU_PALLAS_V2", "0"), "n": n, "ng": ng,
                  "ms": round(dt, 2), "exact": ok}))
"""


def main() -> None:
    for n, ng in [(16_000_000, 3125), (60_000_000, 3125), (16_000_000, 40_000)]:
        for v2 in ("0", "1"):
            env = dict(os.environ)
            env["PINOT_TPU_PALLAS_V2"] = v2
            p = subprocess.run(
                [sys.executable, "-c", _CHILD, str(n), str(ng)],
                capture_output=True, text=True, env=env, timeout=900,
            )
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            print(line if line.startswith("{") else json.dumps(
                {"v2": v2, "n": n, "ng": ng, "error": p.stderr.strip()[-200:]}), flush=True)


if __name__ == "__main__":
    main()
