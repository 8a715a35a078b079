"""The grouped DOUBLE reduction's bytes a second over the chip's HBM peak.

The work is counted here, from the query and the table alone, so that it is
the same whatever implements the reduction: a launch reads each of its
segment's rows once (the DOUBLE value of every averaged metric and the two
group keys' codes) and writes the group table (hosts x the segment's hours,
a DOUBLE a metric) once.

The time is the device time of the programs that hold the reduction. The
answers say which those are: a program's `deviceWork` names the registered
kernels traced into it, and the scatter side of `kernels._grouped_reduce` is
registered as `query.grouped_scatter` (PR 35); the trace's "XLA Modules" line
has each program's launches and seconds under the same name
(`jit_seg_groupby_<hash>`). The scatter's own op cannot be told by its name:
in a v5e trace it is `fusion.1`, beside `fusion.2` (the count's int32 scatter)
and `fusion` (the gather through the key's operand): XLA names such a fusion
generically, `trace_reduce` keeps an op's instruction name and not its
metadata, and the next change to the program hands the name to another op. So
the share is a floor: of the whole launch, in which the DOUBLE scatter was 82 %
of the device time (PERF.md section 5). A program without the registered name
(any before PR 35), a trace without such a program's launches, or a
configuration without hosts and hours gives nothing to read.
"""

from perfbench.layer_metrics._spans import PEAKS, program_of

LAYER = "device: fused per-segment program (query/kernels.py)"
UNIT = "%"
MOVES = "query_p50_ms"
SOURCE = "device_trace"
NEEDS_TRACE = True

REDUCTION = "query.grouped_scatter"  # the registered name of the reduction in a program's `deviceWork`


def bytes_of_a_launch(config: dict, metrics: float) -> float:
    """What the reduction of one launched segment must move at least."""
    rows, hosts = config["segmentRows"], config["hosts"]
    hours = rows // hosts * config["intervalSeconds"] / 3600.0
    return rows * (8.0 * metrics + 2 * 4.0) + hosts * hours * 8.0 * metrics


def reductions_per_launch(run) -> dict[str, float]:
    """Per program that holds the reduction: its calls in one launch (one a
    DOUBLE aggregate), from the work the window's answers report as dispatched."""
    calls: dict[str, float] = {}
    launches: dict[str, int] = {}
    for s in run["good"]:
        work = s.doc.get("deviceWork") if isinstance(s.doc, dict) else None
        for program, w in (work if isinstance(work, dict) else {}).items():
            n = float(w.get("kernels", {}).get(REDUCTION, {}).get("calls", 0))
            if n > 0:
                calls[program] = calls.get(program, 0.0) + n
                launches[program] = launches.get(program, 0) + int(w.get("launches", 0))
    return {p: calls[p] / launches[p] for p in calls if launches[p]}


def read(run):
    t, config = run["trace"], run["config"]
    if t is None or len(PEAKS) != 1 or not {"hosts", "intervalSeconds"} <= set(config):
        return None
    per_launch = reductions_per_launch(run)
    moved = seconds = 0.0
    for name, sec, n in t["modules"]:
        metrics = per_launch.get(program_of(name) or "")
        if metrics:
            moved += n * bytes_of_a_launch(config, metrics)
            seconds += sec
    if moved <= 0 or seconds <= 0:
        return None
    return 100.0 * moved / seconds / next(iter(PEAKS.values()))["hbm_bytes_per_s"]
