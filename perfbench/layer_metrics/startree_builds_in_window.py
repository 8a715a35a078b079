"""Star tables wrapped as segments inside a query of the window (the counter
`starTreeBuilds`: a star table's first use, which the dispatch behind it
stages onto the chip), summed over the window's answers; expected 0, as
`compiles_in_window`: warm-up has sent every template that a star table
answers through every segment. A program without the counter gives nothing
to read."""

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "count"
MOVES = "query_p95_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        int(s.doc["counters"]["starTreeBuilds"])
        for s in run["good"]
        if isinstance(s.doc, dict) and "starTreeBuilds" in (s.doc.get("counters") or {})
    ]
    return float(sum(got)) if got else None
