"""How late the generator ran: a starved generator is not a fast server."""

import numpy as np

LAYER = "load generator (perfbench/loadgen.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "host_clock"
NEEDS_TRACE = False


def read(run):
    late = [(s.sent - s.due) * 1e3 for s in run["samples"]]
    return float(np.percentile(late, 95)) if late else None
