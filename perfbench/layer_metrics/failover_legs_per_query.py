"""Legs of a query's scatter that the broker sent again to another replica
inside the query, because the first choice was unreachable or answered short
of what it was routed (the answer's own `numLegsFailedOver`): the mean over
the window's answered queries. Above 0 in a window in which a server dies;
0 in one in which none does."""

from perfbench.layer_metrics import _answers, _loss

LAYER = _loss.LAYER_FAILOVER
UNIT = "count"
MOVES = "query_p95_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return _answers.mean_field(run, "numLegsFailedOver")
