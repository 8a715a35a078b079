"""Client wall minus the broker's own `timeUsedMs`: HTTP, JSON, the socket."""

import numpy as np

LAYER = "client to broker HTTP (cluster/http.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "host_clock"
NEEDS_TRACE = False


def read(run):
    over = [(s.done - s.sent) * 1e3 - s.doc["timeUsedMs"] for s in run["good"] if "timeUsedMs" in s.doc]
    return float(np.median(over)) if over else None
