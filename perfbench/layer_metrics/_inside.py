"""What the metrics that read inside the widest spans share (PR 37): sums of
several spans of one answer, on either of the ledger's two clocks.

Beside `spanTimesMs` (the clock) an answer carries `spanCpuMs`: the CPU time
of the threads that ran a span, for the spans that read the thread clock. A
program from before a span, or before the second clock, gives these readers
nothing to read: `None`.
"""

from __future__ import annotations

import numpy as np


def sums(
    run, names: tuple[str, ...], field: str = "spanTimesMs", also: tuple[str, ...] = (), without: str | None = None
) -> list[float]:
    """Per answered query of the window, the sum of spans `names` in the
    answer's `field`, and of those of `also` the answer has (a stage only some
    queries run); an answer that lacks one of `names`, or ran the span
    `without`, is passed over."""
    got = []
    for s in run["good"]:
        spans = s.doc.get(field) if isinstance(s.doc, dict) else None
        if not isinstance(spans, dict) or not all(n in spans for n in names):
            continue
        if without is not None and without in (s.doc.get("spanTimesMs") or {}):
            continue
        got.append(sum(spans[n] for n in names) + sum(spans.get(n, 0.0) for n in also))
    return got


def median_sum(run, names: tuple[str, ...], also: tuple[str, ...] = ()):
    got = sums(run, names, also=also)
    return float(np.median(got)) if got else None


def mean_cpu(run, names: tuple[str, ...], without: str | None = None):
    """CPU ms a query: `spanCpuMs` of `names` summed over the window's
    answers, over the answers. The mean and not the median: where the thread
    clock ticks (10 ms on the benchmark's machine) one answer's reading is a
    whole number of ticks, and only the sum over many answers is unbiased. A
    mean has no defence against the few answers that did another thing
    altogether: `without` names the span that marks them."""
    got = sums(run, names, "spanCpuMs", without=without)
    return float(np.mean(got)) if got else None
