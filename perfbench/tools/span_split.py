"""One run of a cell, then its latency split by the program's spans.

    python -m perfbench.tools.span_split --workload ssb-q1-rate --seed 5 --seconds 45 [run.py arguments]

Not on the driver's path: a diagnosis tool. It is `perfbench.run` with the
load generator's window wrapped, so that what every answered query carried
in `spanTimesMs` / `spanSelfMs` / `counters` (pinot_tpu/common/trace.py) is
kept when the window ends. After run.py's own output it prints, per span
name, the median over the window's queries of the span's total and self
time a query and how often it ran, and writes the same to
`perfbench_out/<cell>/<seed>/span_split.json`.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from perfbench import loadgen
from perfbench import run as bench_run

KEPT: list[dict] = []


def _keeping(run_window):
    def wrapped(*args, **kwargs):
        samples, t0 = run_window(*args, **kwargs)
        # warm-up drives a window of its own first: the last one kept is the measured one
        KEPT[:] = [s.doc for s in samples if s.error is None and isinstance(s.doc, dict) and "spanTimesMs" in s.doc]
        return samples, t0

    return wrapped


def split(docs: list[dict]) -> dict:
    names = sorted({n for d in docs for n in d["spanTimesMs"]}, key=lambda n: -np.median([d["spanTimesMs"].get(n, 0.0) for d in docs]))
    rows = {
        n: {
            "total_ms": float(np.median([d["spanTimesMs"].get(n, 0.0) for d in docs])),
            "self_ms": float(np.median([d["spanSelfMs"].get(n, 0.0) for d in docs])),
            "in_queries": sum(1 for d in docs if n in d["spanTimesMs"]),
        }
        for n in names
    }  # fmt: skip
    counters = {k: float(np.median([d["counters"].get(k, 0) for d in docs])) for k in docs[0]["counters"]}
    return {"queries": len(docs), "time_used_ms": float(np.median([d["timeUsedMs"] for d in docs])), "spans": rows, "counters": counters}


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    loadgen.run_window = _keeping(loadgen.run_window)
    code = bench_run.main(argv)
    if not KEPT:
        print("span_split: no answer of the window carried spanTimesMs", file=sys.stderr)
        return code or 1
    out = split(KEPT)
    args = dict(zip(argv[::2], argv[1::2]))
    path = bench_run.OUT / args.get("--workload", "") / args.get("--seed", "") / "span_split.json"
    if path.parent.is_dir():
        path.write_text(json.dumps(out, indent=1))
    print(f"\n{out['queries']} queries, median timeUsedMs {out['time_used_ms']:.3f}; counters {json.dumps(out['counters'])}")
    print("| span | total ms a query (median) | self ms (median) | in queries |\n| --- | --- | --- | --- |")
    for name, r in out["spans"].items():
        print(f"| `{name}` | {r['total_ms']:.3f} | {r['self_ms']:.3f} | {r['in_queries']} |")
    return code


if __name__ == "__main__":
    sys.exit(main())
