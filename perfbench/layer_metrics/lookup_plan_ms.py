"""What a query spends finding its lookUp operands: the span
`server.plan.lookup` (one a lookUp and launched segment: the resident
fk code -> destination code operand looked up, or built at its first use),
summed over the query's segments, median over the window's answers. A
program without the span (any before PR 41) gives nothing to read."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.plan.lookup")
