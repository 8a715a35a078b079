"""The reduced rows made the answer's table: `broker.result` around
`build_result` in `Broker._execute` (`ResultTable.__post_init__` makes every
value a plain Python one, row by row), median. Inside `broker.request`, and
after `timeUsedMs` was taken: so it counts in `broker_self_ms` and, not
being in `broker_time_ms`, in `frontend_overhead_ms` too."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.result")
