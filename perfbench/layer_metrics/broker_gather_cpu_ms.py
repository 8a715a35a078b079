"""The CPU time of the broker's gather: `spanCpuMs` of `broker.wire.decode`
(summed over the scatter's threads) + `broker.reduce`, mean over the window's
answers. Neither span has I/O inside, so `broker_gather_ms` less this is time
the gather's threads stood runnable and did not run: the interpreter's lock,
or the cores. A mean, because the thread clock ticks at 10 ms on the
benchmark's machine: one answer reads whole ticks."""

from perfbench.layer_metrics._inside import mean_cpu

LAYER = "broker self: compile, admission, route, reduce (cluster/broker.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return mean_cpu(run, ("broker.wire.decode", "broker.reduce"))
