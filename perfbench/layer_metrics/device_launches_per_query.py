"""Launches of fused per-segment programs (`jit_seg_*` modules) in the traced
sub-window over the queries answered in it."""

from perfbench.layer_metrics._spans import PROGRAM_PREFIX
from perfbench.layer_metrics._traced import queries_in_trace

LAYER = "device: fused per-segment program (query/kernels.py)"
UNIT = "count"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True


def read(run):
    if run["trace"] is None:
        return None
    launches = sum(n for name, _, n in run["trace"]["modules"] if name.startswith(PROGRAM_PREFIX))
    n = queries_in_trace(run)
    return launches / n if launches and n else None
