#!/usr/bin/env python3
"""spread.py <lines.jsonl> [...]: per cell and metric, the runs' median and
their spread (interquartile distance over the median, quartiles as
statistics.quantiles(values, n=4) gives them), which is what a bound is set
from: about five times the widest spread of the cells, never under 1 %."""

import json
import statistics
import sys
from collections import defaultdict


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> None:
    for path in paths:
        runs = defaultdict(lambda: defaultdict(list))
        wrong = 0
        with open(path) as f:
            for raw in f:
                rec = json.loads(raw)
                wrong += not rec["line"]["correct"]
                for name, m in rec["line"]["metrics"].items():
                    runs[(rec["cell"], rec["trace"])][name].append(m["value"])
        print(f"{path}: {wrong} runs with correct=false")
        for (cell, trace), metrics in sorted(runs.items()):
            for name, values in sorted(metrics.items()):
                if len(values) >= 2:
                    print(f"  {cell} trace={trace} {name}: n={len(values)} median={statistics.median(values):.6g} "
                          f"spread={100 * spread(values):.2f}% min={min(values):.6g} max={max(values):.6g}")  # fmt: skip


if __name__ == "__main__":
    main(sys.argv[1:])
