"""Device time of the Pallas byte-plane group-by over the chip's busy time.

The kernel is found in the trace's op names by KERNEL_MARKS; a trace in which
no op carries one gives nothing to read, and the metric is left out.
"""

LAYER = "kernel: byte-plane group-by (ops/groupby_pallas.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True

# on the v5e the pallas_call shows up under its jitted wrapper's name: `_planes_impl.1`, `_planes2_impl.1`
KERNEL_MARKS = ("_planes_impl", "_planes2_impl")


def read(run):
    t = run["trace"]
    if t is None:
        return None
    # the share is of one chip's busy time: op seconds are summed over the chips, busy_s is their mean
    kernel = sum(sec for name, sec in t["ops"] if any(m in name for m in KERNEL_MARKS))
    chips = max(len(t["chips"]), 1)
    return 100.0 * kernel / chips / t["busy_s"] if kernel > 0 else None
