"""What a first touch costs the query that meets it: the span `server.stage`
(the host thread's time handing a segment's columns to the device; of the
query's slowest server, as every `server.*` span of an answer is) of an
answer that staged, all its stagings summed — median over those answers.
How many there were is `segments_staged_in_window`. Nothing to read in a
window with no first touch."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p95_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.stage")
