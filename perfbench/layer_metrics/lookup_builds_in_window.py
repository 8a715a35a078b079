"""lookUp operands built inside a query of the window (the counter
`lookupOperandBuilds`: a fk code -> destination code table made on the host
and staged to the chip with the query's launches), summed over the window's
answers; expected 0, as `compiles_in_window`: warm-up has read every
(segment, foreign key, destination) the mix reads. A program without the
counter gives nothing to read."""

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "count"
MOVES = "query_p95_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        int(s.doc["counters"]["lookupOperandBuilds"])
        for s in run["good"]
        if isinstance(s.doc, dict) and "lookupOperandBuilds" in (s.doc.get("counters") or {})
    ]
    return float(sum(got)) if got else None
