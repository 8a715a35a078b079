"""The reduce's row-by-row work: the merged groups made row envs
(`broker.reduce.rows`: the columns finalized and a dict built a group), the
HAVING evaluated an env (`broker.reduce.having`, where a query has one) and
the kept rows projected through the select list (`broker.reduce.project`:
the slice and `eval_scalar` an item a row) — their sum, median."""

from perfbench.layer_metrics._inside import median_sum

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_sum(run, ("broker.reduce.rows", "broker.reduce.project"), also=("broker.reduce.having",))
