"""What a query spends inside the runtime's one call a launch, apart from the
planning around it: the span `server.launch` (the jitted call — operands'
transfer, enqueue, PJRT's wait for launches in flight — and the start of the
result's copy to the host), a query's launches summed, median."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.launch")
