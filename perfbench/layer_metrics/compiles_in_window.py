"""Compile requests the servers made inside the window; expected 0."""

LAYER = "compile (XLA, persistent cache)"
UNIT = "count"
MOVES = "query_p50_ms"
SOURCE = "program_counter"
NEEDS_TRACE = False


def read(run):
    return run["after"]["compile_requests"] - run["before"]["compile_requests"]
