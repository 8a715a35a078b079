"""Median `timeUsedMs` of the responses: the broker's parse, plan, route,
scatter and reduce with the server's time inside it. Times two layers from
outside; when the program's spans split it, this one can go."""

import numpy as np

LAYER = "broker with the server inside (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    used = [s.doc["timeUsedMs"] for s in run["good"] if "timeUsedMs" in s.doc]
    return float(np.median(used)) if used else None
