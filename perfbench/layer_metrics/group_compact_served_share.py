"""The share of the window's launched segments that the compact group space
answered: the counter `groupCompactSegments` (segments of a query launched
under the group spec "groups_compact", each key renumbered by the values the
filter leaves) less `groupCompactFallbacks` (those of them whose groups passed
the slots and were launched again under the dense plan), over
`segmentsDispatched`, all summed over the window's answers. 100 % where every
launch is compact and none overflows; a second launch counts in the
denominator, so one overflow in fifteen reads 87.5 %. It is the first number
to fall if the planner stops choosing the compact space or a mix's filters
leave more groups than its slots. A program without the counter (any before
PR 45) gives nothing to read."""

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    got = [
        (
            int(s.doc["counters"]["groupCompactSegments"]) - int(s.doc["counters"].get("groupCompactFallbacks", 0)),
            int(s.doc["counters"].get("segmentsDispatched", 0)),
        )
        for s in run["good"]
        if isinstance(s.doc, dict) and "groupCompactSegments" in (s.doc.get("counters") or {})
    ]
    launched = sum(n for _, n in got)
    return 100.0 * sum(served for served, _ in got) / launched if launched else None
