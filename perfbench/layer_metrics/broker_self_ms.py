"""The broker's own time a query: compile, admission, route, reduce and what
lies between them — `broker.request` less `broker.scatter`, median."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.request", "broker.scatter")
