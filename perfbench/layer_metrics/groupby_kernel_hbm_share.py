"""The byte-plane group-by's bytes a second over the chip's HBM peak: the
registered cost model's bytes of the launches the trace saw, over the
kernel's own device seconds."""

from perfbench.layer_metrics._spans import kernel_roof_share

LAYER = "kernel: byte-plane group-by (ops/groupby_pallas.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True


def read(run):
    return kernel_roof_share(run, "bytes", "hbm_bytes_per_s")
