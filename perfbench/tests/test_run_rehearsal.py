"""run.py end to end on the CPU (`--rehearsal`): the plain and the traced run
of every cell end in a line the validator accepts; the controls end in
`correct: false`; a new cell comes as new files only."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import result_line
from perfbench.manifest import load_manifest

ROOT = Path(__file__).resolve().parents[2]


def run_cell(root: Path, workload: str, seed: int, trace: int, *extra: str, seconds: float = 3) -> tuple[int, dict | None, str]:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearsal", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )  # fmt: skip
    lines = p.stdout.strip().splitlines()
    last = None
    if p.returncode == 0:
        last = json.loads(lines[-1])  # the last line of stdout is the result, whatever came before
    return p.returncode, last, p.stdout + p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["ssb-q1-rate", "tpch-q1q6-closed", "ssb-groupby-closed"])
def test_a_rehearsal_ends_in_a_valid_line(workload, trace):
    rc, line, out = run_cell(ROOT, workload, 2_300_000_000 + trace, trace)
    assert rc == 0, out[-3000:]
    manifest = load_manifest(ROOT)
    result_line.validate(line, manifest, workload, bool(trace), chips=1)
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["attempted"] > 0
    if trace:
        # the CPU has no device plane: the rehearsal reduces the trace recorded on the v5e
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert line["breakdown"]["device_ops"]


@pytest.mark.parametrize(
    "workload,control",
    [
        ("ssb-q1-rate", "float32-reference"),  # the reference in the program's place, summed in float32
        ("tpch-q1q6-closed", "fast32"),  # the program's own float32 staging of DOUBLE columns
        ("ssb-q1-rate", "corrupt-metrics"),  # the timed path broken underneath: every staged metric + 1
    ],
)
def test_a_control_comes_out_as_not_correct(workload, control):
    rc, line, out = run_cell(ROOT, workload, 2_300_000_010, 0, "--control", control)
    assert rc == 0, out[-3000:]
    assert line["correct"] is False, out[-3000:]
    assert line["failed"] > 0


def test_without_the_program_there_is_no_line(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    rc, line, out = run_cell(tmp_path, "ssb-q1-rate", 2_300_000_020, 0)
    assert rc != 0 and line is None
    assert not [ln for ln in out.splitlines() if ln.startswith('{"correct"')]


def test_a_new_cell_is_new_files_and_new_entries_only(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric and a
    cell without editing a file: here they are added to a copy, and run."""
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(ROOT / "pinot_tpu", tmp_path / "pinot_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*") if p.is_file()}

    bench = tmp_path / "perfbench"
    cfg = json.loads((bench / "configs" / "tpch-lineitem-1srv.json").read_text())
    cfg.update(name="tpch-lineitem-small", scaleFactor=1, rows=24_000, segmentRows=8_000)
    cfg["rehearsal"] = {"scaleFactor": 1, "rows": 24_000, "segmentRows": 8_000}
    (bench / "configs" / "tpch-lineitem-small.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "q1q6-closed4.json").read_text())
    mix.update(loop={"kind": "open", "arrivals": "uniform", "rate": 5.0, "senders": 4}, templates={"q6": 1})
    (bench / "traffic" / "q6-uniform.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "docs_scanned_per_query.py").write_text(
        'LAYER = "server: scan"\nUNIT = "rows"\nMOVES = "query_p50_ms"\nSOURCE = "program_counter"\n'
        "NEEDS_TRACE = False\n\n\ndef read(run):\n"
        '    docs = [s.doc["numDocsScanned"] for s in run["good"]]\n    return sum(docs) / len(docs) if docs else None\n'
    )
    manifest = load_manifest(ROOT)
    manifest["configs"].append({"name": "tpch-lineitem-small", "source": "test", "reduced": ["scaleFactor"],
                                "file": "perfbench/configs/tpch-lineitem-small.json", "why": "test"})  # fmt: skip
    manifest["workloads"].append({"name": "tpch-q6-uniform", "config": "tpch-lineitem-small",
                                  "traffic": "q6-uniform", "chips": 1, "why": "test"})  # fmt: skip
    manifest["per_layer"].append({"name": "docs_scanned_per_query", "unit": "rows", "better": "lower",
                                  "source": "program_counter", "layer": "server: scan", "moves": "query_p50_ms",
                                  "workloads": ["tpch-q6-uniform"]})  # fmt: skip
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    for trace in (0, 1):
        rc, line, out = run_cell(tmp_path, "tpch-q6-uniform", 2_300_000_030, trace)
        assert rc == 0, out[-3000:]
        result_line.validate(line, manifest, "tpch-q6-uniform", bool(trace), chips=1)
        assert line["correct"] is True
    assert line["metrics"]["docs_scanned_per_query"]["value"] > 0
    assert "queries_per_s" not in line["metrics"] and "groupby_kernel_share" not in line["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file the benchmark already had was edited"
