"""What a star-answered query spends finding its star tables: the span
`server.plan.startree` (one a swapped segment: the match of the query against
the segment's star tables, the rewrite onto the stored pairs and the lookup of
the star table as a segment, wrapped at its first use), summed over the
query's segments, median over the window's star-answered answers. A program
without the span (any before PR 44) gives nothing to read."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "server.plan.startree")
