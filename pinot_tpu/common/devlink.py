"""Device-link profiling: measured RTT + bandwidth of the host<->device path.

The same query engine can run against very different attachments: a
co-located chip (PCIe/HBM, GB/s, sub-millisecond sync) or a remote one (tens
of ms per round trip, MB/s). Size thresholds that are right for one are wrong
by 100x for the other, so operators that ship per-row data (the multistage
device join's index readbacks) gate on THIS measured profile instead of a
static row count — the AdaptiveServerSelector philosophy
(reference: pinot-broker/.../routing/adaptiveserverselector/) applied to the
accelerator link.

The probe runs once per process on first use: one tiny round trip for RTT,
one 4MB round trip for bandwidth. Cost: ~2 RTTs + 8MB of transfer.
"""

from __future__ import annotations

import time

_profile: "tuple[float, float] | None" = None


def link_profile() -> tuple[float, float]:
    """(rtt_seconds, bytes_per_second) of the default-device link, memoized."""
    global _profile
    if _profile is None:
        import jax
        import numpy as np

        tiny = np.zeros(8, np.uint8)
        big = np.zeros(1 << 22, np.uint8)  # 4MB
        np.asarray(jax.device_put(tiny))  # warm the dispatch path
        t0 = time.perf_counter()
        np.asarray(jax.device_put(tiny))
        rtt = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(jax.device_put(big))
        dt = max(time.perf_counter() - t0 - rtt, 1e-9)
        _profile = (rtt, (2 * big.nbytes) / dt)
    return _profile


def transfer_cost_s(n_bytes: int, round_trips: int = 1) -> float:
    """Modeled wall-clock to move n_bytes over the link in round_trips syncs."""
    rtt, bw = link_profile()
    return round_trips * rtt + n_bytes / bw
