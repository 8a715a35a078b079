"""What a query spends lowering its expression GROUP BY key: the span
`server.plan.group_key` (the expression evaluated over each launched
segment's dictionary and the code -> bucket operand built), summed over the
query's segments, median over the window's answers. A program without the
span (any before PR 35) gives nothing to read."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.plan.group_key")
