"""The broker's gather on the clock: from the instant the last server's
payload had been read to the end of the scatter (`broker.scatter.tail`: the
last decode, the merge of the servers' ledgers) plus `broker.reduce`, median.
`broker_gather_ms` sums the decodes over the scatter's threads: what the
gather costs the host, which with several servers exceeds what it costs a query."""

from perfbench.layer_metrics._inside import median_sum

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_sum(run, ("broker.scatter.tail", "broker.reduce"))
