"""What the metrics that read an answer's own top-level fields share (the
broker's `numLegsFailedOver`, `numStaleRouteRetries` beside
`numServersQueried`). A program from before a field gives its reader nothing
to read: `None`, and the metric is left out of the line."""

from __future__ import annotations

import numpy as np


def mean_field(run, field: str):
    """Mean of `field` over the window's answered queries that carry it."""
    got = [s.doc[field] for s in run["good"] if isinstance(s.doc, dict) and field in s.doc]
    return float(np.mean(got)) if got else None
