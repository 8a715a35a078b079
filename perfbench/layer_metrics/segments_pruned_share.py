"""Of the table's segments, the share a query's filter rejected before any
row was read: by the broker from the segments' metadata and by the server's
own pruner. `numSegmentsPrunedByServer` in a broker's answer is the sum over
both sites (`Broker._execute` adds its own rejects to the servers'); an
answer without it gives nothing to read. Mean over the window's answers."""

import numpy as np

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    segments = run["config"]["rows"] // run["config"]["segmentRows"]
    got = [
        s.doc["numSegmentsPrunedByServer"]
        for s in run["good"]
        if isinstance(s.doc, dict) and "numSegmentsPrunedByServer" in s.doc
    ]
    return 100.0 * float(np.mean(got)) / segments if got and segments else None
