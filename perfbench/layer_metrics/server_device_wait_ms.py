"""Time a query's server thread blocked on device readbacks
(`server.device_wait`: since PR 34 one wait a query, for all its segments'
result vectors together, where there was one a segment before), median: the
queue on the device plus the programs, as the host sees them."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.device_wait")
