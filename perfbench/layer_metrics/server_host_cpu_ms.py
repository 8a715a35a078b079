"""The CPU time of the server's host work a query: `spanCpuMs` of
`server.dispatch_all` (the query's run of dispatches, one reading around all
of them) + `server.unpack`, of the server with the longest `server.execute`
as `server_host_ms` is, mean over the window's answers that staged nothing
(a first touch copies a segment inside its dispatch, a second or so of CPU
that is `segment_stage_ms`'s to tell and would lift a mean, where
`server_host_ms` is a median). What `server_host_ms` holds beyond it (less
the queue and a plan of under a millisecond) is a thread that waited: for
the interpreter, a core, or inside a launch for the runtime. Not listed for the
two cells whose `server_host_ms` a query is 11 and 31 ms over 450 answers or
fewer: the thread clock ticks at 10 ms on the benchmark's machine."""

from perfbench.layer_metrics._inside import mean_cpu

LAYER = "server host: queue, plan, dispatch, unpack (cluster/server.py, query/engine.py)"
UNIT = "ms"
MOVES = "query_p50_ms"
SOURCE = "program_span"
NEEDS_TRACE = False


def read(run):
    return mean_cpu(run, ("server.dispatch_all", "server.unpack"), without="server.stage")
