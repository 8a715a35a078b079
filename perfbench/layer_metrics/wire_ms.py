"""Broker to server and back, outside the server's execution: request
encode, HTTP both ways, the server's handler and its encode, response decode
— `broker.scatter` less `server.execute`, median."""

from perfbench.layer_metrics._spans import median_difference

LAYER = "wire broker <-> server (cluster/http.py, common/datatable.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return median_difference(run, "broker.scatter", "server.execute")
