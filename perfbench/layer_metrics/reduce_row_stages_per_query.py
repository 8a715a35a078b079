"""Stages of an answer's group-by reduce (HAVING, ORDER BY, the select list)
that left the columns for a dict a group or the comparison sort (the counter
`reduceRowStages`, `pinot_tpu/query/reduce.py` `reduce_group_by`): the mean
over the window's answers. 0 while every stage of every answer stays in
columns; a program without the counter gives nothing to read."""

import numpy as np

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "count"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        int(s.doc["counters"]["reduceRowStages"])
        for s in run["good"]
        if isinstance(s.doc, dict) and "reduceRowStages" in (s.doc.get("counters") or {})
    ]
    return float(np.mean(got)) if got else None
