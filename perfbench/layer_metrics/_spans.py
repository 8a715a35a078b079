"""What the metrics that read the program's phase ledger share.

Every v1 broker response carries `spanTimesMs` (per span name, total ms of
the request), `counters` and `deviceWork` (pinot_tpu/common/trace.py
`PhaseLedger.response_fields`); `loadgen` keeps the response as `sample.doc`.
A program without them (the parent of the PR that added them) gives every
reader here nothing to read: `None`, and the metric is left out of the line.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())
PROGRAM_PREFIX = "jit_seg_"  # a fused per-segment program in the trace's "XLA Modules" line
KERNEL_PREFIX = "ops.grouped_planes"  # the byte-plane group-by's registered names (also `ops.grouped_planes2`)


def median_difference(run, outer: str, inner: str | None = None):
    """Median over the window's answered queries of span `outer`, less span
    `inner` of the same response; None when no response has them."""
    got = []
    for s in run["good"]:
        spans = s.doc.get("spanTimesMs")
        if not isinstance(spans, dict) or outer not in spans or (inner is not None and inner not in spans):
            continue
        got.append(spans[outer] - (spans[inner] if inner is not None else 0.0))
    return float(np.median(got)) if got else None


def program_of(module: str) -> str | None:
    """`jit_seg_groupby_3f2a9c1e(1234)` -> `seg_groupby_3f2a9c1e`, the key of `deviceWork`."""
    if not module.startswith(PROGRAM_PREFIX):
        return None
    return module.split("(", 1)[0][len("jit_") :]


def kernel_work_per_launch(run, what: str) -> dict[str, float]:
    """Per program: the group-by kernel's static `what` (`flops` or `bytes`)
    of one launch, from the work the window's responses report as dispatched."""
    total: dict[str, float] = {}
    launches: dict[str, int] = {}
    for s in run["good"]:
        work = s.doc.get("deviceWork")
        if not isinstance(work, dict):
            continue
        for program, w in work.items():
            launches[program] = launches.get(program, 0) + int(w.get("launches", 0))
            for kernel, c in w.get("kernels", {}).items():
                if kernel.startswith(KERNEL_PREFIX):
                    total[program] = total.get(program, 0.0) + float(c.get(what, 0.0))
    return {p: total[p] / launches[p] for p in total if launches.get(p)}


def kernel_roof_share(run, what: str, peak_key: str):
    """Work the kernel did in the traced sub-window (the trace's launches of
    each program x that program's static work a launch) over the kernel's own
    device seconds, as a share of the chip's peak, in %."""
    from perfbench.layer_metrics.groupby_kernel_share import KERNEL_MARKS

    t = run["trace"]
    if t is None or len(PEAKS) != 1:
        # `run` does not carry the device kind: run.py refuses a chip that is
        # not in peaks.json, which holds one kind; with more, nothing is read
        return None
    peak = next(iter(PEAKS.values()))[peak_key]
    per_launch = kernel_work_per_launch(run, what)
    done = sum(n * per_launch.get(program_of(name) or "", 0.0) for name, _, n in t["modules"])
    kernel_s = sum(sec for name, sec in t["ops"] if any(m in name for m in KERNEL_MARKS))
    if done <= 0 or kernel_s <= 0:
        return None
    return 100.0 * done / kernel_s / peak
