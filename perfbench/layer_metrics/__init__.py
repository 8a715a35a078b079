"""One module per per-layer metric, found by the name BENCHMARK.json gives.

A module has LAYER, UNIT, MOVES, SOURCE, NEEDS_TRACE and `read(run)`, which
returns the number or None when it finds nothing to read. `run` is what
run.py collected: `samples` (every query issued in the window), `good`
(those that answered), `seconds`, `before` / `after` (the roles' counters
around the window), `trace` (trace_reduce's output, or None) and
`trace_window` (the traced sub-window, seconds from the window's start).
"""
