"""The server's host work a query: queue, plan, prune, dispatch, unpack —
`server.execute` less the waits for the device's readbacks, median."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.execute", "server.device_wait")
