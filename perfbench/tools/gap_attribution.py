"""Name the device's idle gaps by what the server's host was doing.

    JAX_PLATFORMS=cpu python -m perfbench.tools.gap_attribution <trace dir or .xplane.pb> [--min-ms 1]

Not on the driver's path: a tool for a trace kept with `run.py --keep-trace`
(`perfbench_out/<cell>/<seed>/trace.xplane.pb`). The program's spans
(pinot_tpu/common/trace.py `span`) are `jax.profiler.TraceAnnotation`s, so
under the profiler they lie in the `/host:CPU` plane on the device plane's
clock. Every gap of at least `--min-ms` between device operations is cut at
the span boundaries inside it; each piece goes to the innermost `server.*`
span that covers it (of several threads', the one opened last), or to
`no request in the server`. A gap is named by the piece-owner with the most
of its time. Prints the ten longest gaps and the idle time by name.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from perfbench.trace_reduce import DEVICE_PLANE_PREFIX, MODULES_LINE, OPS_LINE, _gaps, read_planes

HOST_PLANE_PREFIX = "/host:"
SPAN_PREFIX = "server."
NOBODY = "no request in the server"


def host_spans(planes: list[dict]) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every `server.*` annotation of the host planes."""
    out = []
    for p in planes:
        if not p["name"].startswith(HOST_PLANE_PREFIX):
            continue
        for ln in p["lines"]:
            out += [(s, s + d, name) for name, s, d in ln["events"] if name.startswith(SPAN_PREFIX) and d > 0]
    return sorted(out)


def device_gaps(planes: list[dict], min_ns: float) -> tuple[list[tuple[float, float]], float, float]:
    """Gaps between device operations of at least `min_ns`, over all device
    planes, with the window's idle and whole length in ns."""
    gaps, idle, window = [], 0.0, 0.0
    for p in planes:
        if not p["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        every = [(s, s + d) for ev in lines.values() for _, s, d in ev]
        if not every:
            continue
        busy = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [e for ev in lines.values() for e in ev]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
        found = _gaps([(s, s + d) for _, s, d in busy if d > 0], lo, hi)
        window += hi - lo
        idle += sum(b - a for a, b in found)
        gaps += [(a, b) for a, b in found if b - a >= min_ns]
    return gaps, idle, window


def attribute(gap: tuple[float, float], spans: list[tuple[float, float, str]]) -> dict[str, float]:
    """The gap's time by owner: cut at span boundaries, each piece to the
    covering span that started last (the innermost of one thread's nest)."""
    a, b = gap
    inside = [(s, e, n) for s, e, n in spans if s < b and e > a]
    cuts = sorted({a, b, *(t for s, e, _ in inside for t in (s, e) if a < t < b)})
    owned: dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [(s, n) for s, e, n in inside if s <= lo and e >= hi]
        name = max(cover)[1] if cover else NOBODY
        owned[name] = owned.get(name, 0.0) + (hi - lo)
    return owned


def report(planes: list[dict], min_ms: float = 1.0) -> dict:
    spans = host_spans(planes)
    gaps, idle_ns, window_ns = device_gaps(planes, min_ms * 1e6)
    by_name: dict[str, float] = {}
    named = []
    for gap in gaps:
        owned = attribute(gap, spans)
        for name, ns in owned.items():
            by_name[name] = by_name.get(name, 0.0) + ns
        top = max(owned, key=owned.get)
        named.append({"ms": (gap[1] - gap[0]) / 1e6, "name": top, "share": owned[top] / (gap[1] - gap[0])})
    named.sort(key=lambda g: -g["ms"])
    return {
        "window_ms": window_ns / 1e6,
        "idle_ms": idle_ns / 1e6,
        "gaps": len(gaps),
        "gaps_ms": sum(g["ms"] for g in named),
        "host_spans": len(spans),
        "longest": named[:10],
        "idle_by_name_ms": dict(sorted(((n, ns / 1e6) for n, ns in by_name.items()), key=lambda kv: -kv[1])),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--min-ms", type=float, default=1.0)
    args = ap.parse_args(argv)
    r = report(read_planes(Path(args.path), device_only=False), args.min_ms)
    print(f"device window {r['window_ms']:.1f} ms, idle {r['idle_ms']:.1f} ms; {r['gaps']} gaps of at least "
          f"{args.min_ms:g} ms hold {r['gaps_ms']:.1f} ms; {r['host_spans']} server spans in the host planes")  # fmt: skip
    if not r["host_spans"]:
        print("no `server.*` annotation arrived in a host plane: every gap reads as nobody's")
    print("\n| gap ms | named by | its share of the gap |\n| --- | --- | --- |")
    for g in r["longest"]:
        print(f"| {g['ms']:.2f} | `{g['name']}` | {100 * g['share']:.0f} % |")
    print("\n| idle time by name | ms | share of the gaps' time |\n| --- | --- | --- |")
    for name, ms in r["idle_by_name_ms"].items():
        print(f"| `{name}` | {ms:.1f} | {100 * ms / r['gaps_ms']:.1f} % |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
