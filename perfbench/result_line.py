"""The result line: one function builds it, one validates it.

`run.py` validates its own line before it prints it; an invalid line is a
non-zero exit with the reason on stderr, never a printed line.
"""

from __future__ import annotations

import json
import math

from perfbench.manifest import metrics_of

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class InvalidLine(ValueError):
    pass


def build(
    manifest: dict, workload: str, trace: bool, *, correct: bool, attempted: int, failed: int,
    values: dict[str, float], device: dict, breakdown: dict | None = None, extra: dict | None = None,
) -> dict:  # fmt: skip
    """The line for this cell: `metrics` holds the cell's end-to-end metrics in
    a plain run and its per-layer metrics in a traced run, each with the unit
    BENCHMARK.json gives it. A per-layer metric whose reader found nothing to
    read is absent from `values` and is left out. `extra` keys ride along
    beside the contract's (the driver ignores them)."""
    section = "per_layer" if trace else "end_to_end"
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in metrics_of(manifest, section, workload)
        if m["name"] in values
    }
    line = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }  # fmt: skip
    if trace and breakdown:
        line["breakdown"] = breakdown
    line.update(extra or {})
    return line


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)


def validate(line: dict, manifest: dict, workload: str, trace: bool, chips: int | None = None) -> str:
    """Raises InvalidLine with the reason; returns the line serialised, with
    no NaN or Infinity anywhere in it."""
    if not isinstance(line, dict):
        raise InvalidLine("the line is not a JSON object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            raise InvalidLine(f"key {key!r} is missing")
    if not isinstance(line["correct"], bool):
        raise InvalidLine("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) or line[key] < 0:
            raise InvalidLine(f"{key} is not a whole number >= 0")
    if line["failed"] > line["attempted"]:
        raise InvalidLine("failed exceeds attempted")
    section = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in metrics_of(manifest, section, workload)}
    metrics = line["metrics"]
    if not isinstance(metrics, dict) or not metrics:
        raise InvalidLine("metrics is empty or not an object")
    for name, got in metrics.items():
        if name not in listed:
            raise InvalidLine(f"metric {name!r} is not a {section} metric of workload {workload!r}")
        if not isinstance(got, dict) or "value" not in got or "unit" not in got:
            raise InvalidLine(f"metric {name!r} is not {{value, unit}}")
        if got["unit"] != listed[name]["unit"]:
            raise InvalidLine(f"metric {name!r} has unit {got['unit']!r}, BENCHMARK.json says {listed[name]['unit']!r}")
        if not _finite(got["value"]):
            raise InvalidLine(f"metric {name!r} has value {got['value']!r}, not a finite number")
    if not trace:
        # an end-to-end metric is measured by the harness itself: none may be missing
        missing = sorted(set(listed) - set(metrics))
        if missing:
            raise InvalidLine(f"end-to-end metrics missing: {missing}")
    device = line["device"]
    if not isinstance(device, dict):
        raise InvalidLine("device is not an object")
    for key in DEVICE_KEYS:
        if key not in device:
            raise InvalidLine(f"device.{key} is missing")
    if not isinstance(device["platform"], str) or not isinstance(device["kind"], str):
        raise InvalidLine("device.platform and device.kind must be strings")
    if not isinstance(device["count"], int) or device["count"] < 1:
        raise InvalidLine("device.count is not a whole number >= 1")
    if chips is not None and device["count"] != chips:
        raise InvalidLine(f"device.count {device['count']} is not the cell's {chips} chips")
    if not _finite(device["memory_peak_bytes"]) or device["memory_peak_bytes"] <= 0:
        raise InvalidLine("device.memory_peak_bytes is not a number above 0")
    if trace:
        for key in ("window_s", "busy_s"):
            if not _finite(device.get(key)):
                raise InvalidLine(f"device.{key} is missing or not a finite number")
        if not 0 < device["busy_s"] <= device["window_s"]:
            raise InvalidLine(
                f"device.busy_s {device['busy_s']} must be above 0 and at most window_s {device['window_s']}"
            )
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key, [])
                if len(rows) > 10 or any(
                    len(r) != 2 or not isinstance(r[0], str) or not _finite(r[1]) for r in rows
                ):
                    raise InvalidLine(f"breakdown.{key} is not at most 10 [name, seconds] pairs")
    try:
        return json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise InvalidLine(f"not serialisable as strict JSON: {e}") from None
