"""BENCHMARK.json and the data files it names: everything a cell is, found by name."""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent  # the checkout
BENCH = ROOT / "perfbench"


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload_entry(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has {[w['name'] for w in manifest['workloads']]}")


def metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of `section` (end_to_end | per_layer) that this cell reports:
    those that list it under `workloads`, and those that list none."""
    return [m for m in manifest[section] if workload in m.get("workloads", [workload])]


def load_cell(manifest: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files read in."""
    entry = workload_entry(manifest, workload)
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "perfbench" / "traffic" / f"{entry['traffic']}.json").read_text())
    return {"entry": entry, "config": config, "traffic": traffic}
