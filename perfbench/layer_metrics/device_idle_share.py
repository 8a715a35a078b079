"""Share of the traced sub-window in which no operation ran on the chip."""

LAYER = "device: fused per-segment program (query/kernels.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True


def read(run):
    t = run["trace"]
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])
