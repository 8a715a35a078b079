"""The answer's JSON made by the broker's HTTP handler: `broker.http.encode`,
the result table's rows through `json.dumps` (the envelope around them is
microseconds), median. The part of `frontend_overhead_ms` that the program
spends; the rest of it is the socket and the client's own parse."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "client to broker HTTP (cluster/http.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.http.encode")
