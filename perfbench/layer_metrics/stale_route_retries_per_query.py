"""Times a query was routed anew on a fresh snapshot after a server said it
does not host a segment it was routed (the answer's own
`numStaleRouteRetries`): the mean over the window's answered queries. 0 while
the broker asks a server for a segment only once the server has it; it is
the number that moves first if a returning server is routed to too early."""

from perfbench.layer_metrics import _answers, _loss

LAYER = _loss.LAYER_FAILOVER
UNIT = "count"
MOVES = "query_p95_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return _answers.mean_field(run, "numStaleRouteRetries")
