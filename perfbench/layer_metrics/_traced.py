"""What the trace-reading metrics share."""


def queries_in_trace(run) -> float:
    """Queries answered in the traced sub-window, counting one that was in
    flight across an edge by the share of its time that lay inside."""
    lo, hi = run["trace_window"]
    total = 0.0
    for s in run["good"]:
        span = s.done - s.sent
        inside = min(s.done, hi) - max(s.sent, lo)
        if inside > 0 and span > 0:
            total += inside / span
    return total
