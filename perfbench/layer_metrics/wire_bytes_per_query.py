"""Bytes of a query's scatter on the wire, both ways (request bodies and
DataTable payloads as the broker's client sent and read them), median."""

import numpy as np

LAYER = "wire broker <-> server (cluster/http.py, common/datatable.py)"
UNIT = "bytes"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        c["wireRequestBytes"] + c["wireResponseBytes"]
        for c in (s.doc.get("counters") for s in run["good"])
        if isinstance(c, dict) and "wireRequestBytes" in c and "wireResponseBytes" in c
    ]
    return float(np.median(got)) if got else None
