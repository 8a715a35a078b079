"""Segments staged host -> HBM inside a query of the window (a first touch:
the counter `segmentsStaged`, added over a query's servers), summed over the
window's answers. 0 in a window that meets no first touch; a program without
the counter gives nothing to read."""

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "count"
MOVES = "query_p95_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        int(s.doc["counters"]["segmentsStaged"])
        for s in run["good"]
        if isinstance(s.doc, dict) and "segmentsStaged" in (s.doc.get("counters") or {})
    ]
    return float(sum(got)) if got else None
