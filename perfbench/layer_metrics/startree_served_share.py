"""The share of the window's launched segments that a star table answered:
the counter `starTreeSegments` (segments of a query whose program was
launched over a star table in their place) over `segmentsDispatched`, both
summed over the window's answers. Where every covered template is matched in
every segment it is the covered templates' share of the traffic (two thirds
under `flights1234-closed4`); it is the first number to fall if a change to
the planner stops a match. A program without the counter (any before PR 44)
gives nothing to read."""

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        (int(s.doc["counters"]["starTreeSegments"]), int(s.doc["counters"].get("segmentsDispatched", 0)))
        for s in run["good"]
        if isinstance(s.doc, dict) and "starTreeSegments" in (s.doc.get("counters") or {})
    ]
    launched = sum(n for _, n in got)
    return 100.0 * sum(star for star, _ in got) / launched if launched else None
