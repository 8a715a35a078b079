"""Tile-geometry sweep for the Pallas group-by kernels on real TPU hardware.

Each (CHUNK, GROUP_TILE) configuration runs in a SUBPROCESS so the env
override re-imports pinot_tpu.ops.groupby_pallas with that geometry. Prints
one JSON line per configuration. The parent never imports jax, so each child
can own the chip in turn; run where there is one:

    python -m benchmarks.pallas_sweep            # default shape set
    PINOT_TPU_SWEEP_DOCS=8000000 python -m benchmarks.pallas_sweep
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

CONFIGS = [(1024, 256), (2048, 256), (4096, 256), (2048, 512), (4096, 128), (8192, 256)]
GROUPS = [256, 1024, 4608]

_CHILD = r"""
import json, os, sys, time
import numpy as np
import pinot_tpu  # noqa: F401
import jax, jax.numpy as jnp
from pinot_tpu.ops.groupby_pallas import PLANES_CHUNK, _grids, gtile_for, pallas_grouped_multi_sum

n = int(os.environ.get("PINOT_TPU_SWEEP_DOCS", 4_000_000))
ng = int(sys.argv[1])
rng = np.random.default_rng(0)
v = jnp.asarray(rng.integers(0, 500_000, n).astype(np.int32))
g = jnp.asarray(rng.integers(0, ng, n).astype(np.int32))
m = jnp.asarray(np.ones(n, dtype=bool))

def run():
    sums, counts = pallas_grouped_multi_sum([v], g, m, ng)
    return np.asarray(sums[0])

out = run()  # compile
# correctness spot check against numpy
truth = np.zeros(ng); np.add.at(truth, np.asarray(g), np.asarray(v, dtype=np.float64))
assert np.allclose(out, truth), "parity failure"
lat = []
for _ in range(7):
    t0 = time.perf_counter(); run(); lat.append((time.perf_counter() - t0) * 1e3)
n_padded = n + ((-n) % PLANES_CHUNK)
n_chunks, n_gtiles, _, _gt = _grids(n_padded, ng, PLANES_CHUNK)
print(json.dumps({
    "chunk": PLANES_CHUNK, "gtile": gtile_for(ng), "ng": ng, "docs": n,
    "p50_ms": round(float(np.percentile(lat, 50)), 2),
    "steps": n_chunks * n_gtiles,
}))
"""


def main() -> None:
    results = []
    for chunk, gtile in CONFIGS:
        for ng in GROUPS:
            env = dict(os.environ)
            env["PINOT_TPU_PALLAS_CHUNK_PLANES"] = str(chunk)
            env["PINOT_TPU_PALLAS_GTILE"] = str(gtile)
            try:
                p = subprocess.run(
                    [sys.executable, "-c", _CHILD, str(ng)],
                    capture_output=True,
                    text=True,
                    env=env,
                    timeout=600,
                )
            except subprocess.TimeoutExpired:
                print(json.dumps({"chunk": chunk, "gtile": gtile, "ng": ng, "error": "timeout"}), flush=True)
                continue
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            if p.returncode == 0 and line.startswith("{"):
                results.append(json.loads(line))
                print(line, flush=True)
            else:
                print(
                    json.dumps(
                        {"chunk": chunk, "gtile": gtile, "ng": ng, "error": p.stderr.strip()[-200:]}
                    ),
                    flush=True,
                )
    if results:
        best = min(results, key=lambda r: r["p50_ms"])
        print(json.dumps({"best": best}))


if __name__ == "__main__":
    main()
