"""The reduce's ORDER BY over the merged groups, before the limit cuts them:
the span `broker.reduce.order`, median; nothing to read where no query orders."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.reduce.order")
