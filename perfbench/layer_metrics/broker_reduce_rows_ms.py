"""The reduce between the merge and the sort, and after it: the merged groups
made columns (`broker.reduce.rows`: each aggregate finalized as one array over
the groups; since PR 39 no dict a group), the HAVING evaluated a group
(`broker.reduce.having`, where a query has one: the one stage that still reads
a row env) and the kept rows cut to the LIMIT and projected through the select
list (`broker.reduce.project`: the columns taken at the kept positions, a row
made only of what the answer keeps) — their sum, median."""

from perfbench.layer_metrics._inside import median_sum

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_sum(run, ("broker.reduce.rows", "broker.reduce.project"), also=("broker.reduce.having",))
