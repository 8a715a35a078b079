"""The server under test, with a control thread beside it.

    python -m perfbench.server_main --control-file F [--control fast32] -- StartServer ...

Benchmark code that calls the program's own entry
(`pinot_tpu.tools.admin.main(["StartServer", ...])`, the path every
deployment uses). Only the process that holds the chip can trace it or read
its memory, and the program has no endpoint for either, so before the server
starts this opens a localhost socket (its port is written to the control
file) that answers three requests, one JSON object a line:

    {"cmd": "trace-start", "dir": D}  -> jax.profiler.start_trace(D)
    {"cmd": "trace-stop"}             -> jax.profiler.stop_trace()
    {"cmd": "memstats"}               -> the devices' kind and memory_stats()

A plain run never sends the first two: the server is the same program in
both kinds of run, and the profiler is the only difference.

`--control` is for the controls of the correctness check only
(perfbench/tests, PERF.md), which no benchmark run passes: `fast32` turns on
the program's own float32 staging of DOUBLE columns (the lower precision a
later PR might be tempted by); `corrupt-metrics` adds 1 to every metric
column as it is staged onto the device, so the timed path answers wrongly.
"""

from __future__ import annotations

import argparse
import json
import socketserver
import sys
import threading
from pathlib import Path


def _handle(req: dict) -> dict:
    import jax  # by now the server has initialised the backend it was told to

    cmd = req.get("cmd")
    if cmd == "trace-start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # device planes are what is read; Python frames only slow the host
        opts.host_tracer_level = 1
        jax.profiler.start_trace(req["dir"], profiler_options=opts)
        return {"ok": True}
    if cmd == "trace-stop":
        jax.profiler.stop_trace()
        return {"ok": True}
    if cmd == "memstats":
        return {"ok": True, "devices": [_device_memory(d) for d in jax.local_devices()]}
    return {"ok": False, "error": f"unknown cmd {cmd!r}"}


def _device_memory(d) -> dict:
    stats = d.memory_stats()
    if stats is None and d.platform == "cpu":
        # the CPU backend keeps no device memory statistics: a rehearsal
        # reports the process's peak resident set under the same key
        import resource

        stats = {"peak_bytes_in_use": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    return {"platform": d.platform, "kind": d.device_kind, "id": d.id, "memory_stats": stats or {}}


class _Control(socketserver.StreamRequestHandler):
    def handle(self) -> None:
        for raw in self.rfile:
            try:
                out = _handle(json.loads(raw))
            except Exception as e:  # the launcher decides what a failed request means
                out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            self.wfile.write(json.dumps(out).encode() + b"\n")
            self.wfile.flush()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control-file", required=True)
    ap.add_argument("--control", choices=["fast32", "corrupt-metrics"], default=None)
    ap.add_argument("admin_argv", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    admin_argv = [a for a in args.admin_argv if a != "--"]

    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), _Control)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, name="perfbench-control", daemon=True).start()
    Path(args.control_file).write_text(str(srv.server_address[1]))

    if args.control == "fast32":
        from pinot_tpu.cluster import server as server_mod

        init = server_mod.Server.__init__

        def init_fast32(self, server_id, fast32=False, **kw):
            init(self, server_id, fast32=True, **kw)

        server_mod.Server.__init__ = init_fast32

    if args.control == "corrupt-metrics":
        from pinot_tpu.common.types import FieldType
        from pinot_tpu.segment import segment as segment_mod

        to_device = segment_mod.ImmutableSegment.to_device

        def to_device_corrupt(self, *a, **kw):
            ds = to_device(self, *a, **kw)
            for col, spec in self.schema.fields.items():
                if spec.field_type == FieldType.METRIC and col in ds.arrays:
                    ds.arrays[col] = ds.arrays[col] + 1
            return ds

        segment_mod.ImmutableSegment.to_device = to_device_corrupt

    from pinot_tpu.tools import admin

    return admin.main(admin_argv)


if __name__ == "__main__":
    sys.exit(main())
