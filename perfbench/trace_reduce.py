"""The one reduction from a profiler trace to numbers.

    JAX_PLATFORMS=cpu python -m perfbench.trace_reduce <trace dir or .xplane.pb> [--chips N]

Reading a trace needs jax but no chip, so the launcher runs this in a child
pinned to the CPU and reads the JSON it prints. `reduce_planes` works on plain
tuples, so the tests drive it without a trace file as well.

What a TPU trace looks like (seen by hand on a v5e, PERF.md section 5): one
plane per chip named `/device:TPU:<n>`, with several lines — "XLA Ops" (one
event per HLO op the chip ran), "XLA Modules" (one event per launched
program, spanning its ops), "Steps", and lines for the cores' own units.
Events of different lines overlap in time (a module spans its ops), so busy
time is the *union* of the op intervals, never a sum over lines.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals that may overlap."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def short_op_name(name: str) -> str:
    """An op event is named by its whole HLO line, `%fusion.3 = (...) fusion(...)`:
    the instruction's own name is what a breakdown can carry."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def _gaps(intervals: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def _by_time(seconds: dict[str, float]) -> list[tuple[str, float]]:
    return sorted(seconds.items(), key=lambda kv: -kv[1])


def reduce_planes(planes: list[dict], chips: int | None = None) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}].

    Per device plane, the window runs from the first event's start to the
    last event's end on that plane's clock (the profiler records nothing
    while the chip idles, so the traced sub-window's edges are where the
    launcher's start and stop landed between operations); busy is the union
    of the op intervals clipped to it. Several chips: window and busy are
    averaged over the chips used.
    """
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE_PREFIX)]
    per_chip, op_time, mod_time, mod_count, gaps_all = [], {}, {}, {}, []
    for p in devices:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        every = [(s, s + d) for ev in lines.values() for _, s, d in ev]
        if not every:
            continue
        # ops when the plane has them; a plane with only modules is still a busy chip
        busy_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [e for ev in lines.values() for e in ev]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
        spans = [(max(s, lo), min(s + d, hi)) for _, s, d in busy_events if d > 0]
        busy = union_length(spans)
        per_chip.append({"plane": p["name"], "window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9})
        for name, _, d in lines.get(OPS_LINE, []):
            name = short_op_name(name)
            op_time[name] = op_time.get(name, 0.0) + d / 1e9
        for name, _, d in lines.get(MODULES_LINE, []):
            mod_time[name] = mod_time.get(name, 0.0) + d / 1e9
            mod_count[name] = mod_count.get(name, 0) + 1
        gaps_all += [(b - a) / 1e9 for a, b in _gaps(spans, lo, hi)]
    if chips is not None and len(per_chip) != chips:
        raise ValueError(f"trace has {len(per_chip)} busy device planes {[c['plane'] for c in per_chip]}, cell has {chips} chips")
    if not per_chip:
        raise ValueError(f"no device plane with events among {[p['name'] for p in planes]}")
    n = len(per_chip)
    return {
        "window_s": sum(c["window_s"] for c in per_chip) / n,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "chips": per_chip,
        "ops": [[k, v] for k, v in _by_time(op_time)],
        "modules": [[k, v, mod_count[k]] for k, v in _by_time(mod_time)],
        "idle_gaps_s": sorted(gaps_all, reverse=True)[:10],
        "planes": [{"name": p["name"], "lines": {ln["name"]: len(ln["events"]) for ln in p["lines"]}} for p in planes],
    }


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_planes(path: Path, device_only: bool = True) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(path)))
    planes = []
    for p in data.planes:
        keep = p.name.startswith(DEVICE_PLANE_PREFIX) or not device_only
        planes.append({
            "name": p.name,
            "lines": [
                {"name": ln.name, "events": [(e.name, e.start_ns, e.duration_ns) for e in ln.events]}
                for ln in (p.lines if keep else [])
            ],
        })  # fmt: skip
    return planes


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--chips", type=int, default=None)
    args = ap.parse_args(argv)
    out = reduce_planes(read_planes(Path(args.path)), args.chips)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
