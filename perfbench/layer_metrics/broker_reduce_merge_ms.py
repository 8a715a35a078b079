"""The first stage of the broker's reduce: the servers' partial frames made
one (`pd.concat`) and merged a group (`groupby().agg` / `.apply`) — the span
`broker.reduce.merge`, median over the window's answers."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.reduce.merge")
