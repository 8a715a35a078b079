"""Device busy time of the traced sub-window over the queries answered in it."""

from perfbench.layer_metrics._traced import queries_in_trace

LAYER = "device: fused per-segment program (query/kernels.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True


def read(run):
    if run["trace"] is None:
        return None
    n = queries_in_trace(run)
    return run["trace"]["busy_s"] * 1e3 / n if n else None
