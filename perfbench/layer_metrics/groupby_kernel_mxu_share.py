"""The byte-plane group-by's flops a second over the chip's bf16 peak: the
registered cost model's flops of the launches the trace saw, over the
kernel's own device seconds. Near or over 100 % the count is wrong."""

from perfbench.layer_metrics._spans import kernel_roof_share

LAYER = "kernel: byte-plane group-by (ops/groupby_pallas.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True


def read(run):
    return kernel_roof_share(run, "flops", "bf16_flops_per_s")
