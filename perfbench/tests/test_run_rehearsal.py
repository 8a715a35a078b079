"""run.py end to end on the CPU (`--rehearsal`): the plain and the traced run
of every cell end in a line the validator accepts; the controls end in
`correct: false`; a new cell comes as new files only. What is particular to
the four-server cell is in `test_four_server_cell.py`, and a server lost under
load in `test_server_loss_cell.py`."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import result_line
from perfbench.manifest import load_cell, load_manifest

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


def forget_seed(config: str, seed: int) -> None:
    """A test of set-up's uploads needs them to happen: without the table an
    earlier run of the test left for the seed, which a run would restart over."""
    shutil.rmtree(ROOT / "perfbench" / ".cache" / f"{config}-rehearsal" / str(seed), ignore_errors=True)


MANIFEST = load_manifest(ROOT)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in MANIFEST["workloads"]])
def test_a_rehearsal_ends_in_a_valid_line(workload, trace):
    rc, line, out = run_cell(ROOT, workload, 2_300_000_000 + trace, trace)
    assert rc == 0, out[-3000:]
    cell = load_cell(MANIFEST, workload, ROOT)
    result_line.validate(line, MANIFEST, workload, bool(trace), chips=cell["entry"]["chips"])
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["attempted"] > 0
    # each number compared stands beside its limit: the line's last key, and stderr's last lines
    assert list(line)[-1] == "compared" and line["compared"]["failed_queries"] == {"value": 0, "limit": line["attempted"] // 100}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    # ... in the line's order: a cell whose traffic has a fault schedule names `recovered_s` last, every other `failed_queries`
    lost = bool(cell["traffic"].get("faults"))
    assert list(line["compared"])[-1] == ("recovered_s" if lost else "failed_queries")
    assert out.rstrip().splitlines()[-1].startswith(f"perfbench: compared {'recovered_s=' if lost else 'failed_queries=0 limit='}")
    assert "perfbench: compared failed_queries=0 limit=" in out
    if trace:
        # the CPU has no device plane: the rehearsal reduces the trace recorded on the v5e
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert line["breakdown"]["device_ops"]
        # every server traced into a directory of its own
        assert trace_files(out) == [f"server_{i}" for i in range(cell["config"]["servers"])]
    if lost:  # what the loss itself has to show is `test_server_loss_cell.py`'s
        assert "[perfbench] fault: killed server_" in out and line["compared"]["not_recovered"] == {"value": 0, "limit": 0}
    else:  # a traffic file without `faults`: no fault thread, no tail, nothing of a loss in the line
        assert "[perfbench] fault:" not in out and "[perfbench] tail:" not in out
        assert not {"fault_at_s", "restart_ready_s", "loss", "tail_queries"} & set(line) and "recovered_s" not in line["metrics"]


def trace_files(out: str) -> list[str]:
    """The servers whose trace file run.py found, from its `trace files:` line."""
    files = json.loads(next(ln for ln in out.splitlines() if "trace files: " in ln).split("trace files: ")[1].replace("'", '"'))
    return [sid for sid, _ in files]


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


def copy_of_the_benchmark(tmp_path: Path) -> dict[Path, bytes]:
    """The benchmark's files copied beside the program, and what each held."""
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(ROOT / "pinot_tpu", tmp_path / "pinot_tpu")
    return {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*") if p.is_file()}


def test_a_new_cell_is_new_files_and_new_entries_only(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric and a
    cell without editing a file: here they are added to a copy, and run."""
    before = copy_of_the_benchmark(tmp_path)

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


LOSES_A_SEGMENT = """
import sys
from perfbench import run

if __name__ == "__main__":
    ideal_state, asked = run.Cluster.ideal_state, []

    def first_answer_lacks_a_segment(self, table=None):
        ideal = ideal_state(self, table)
        asked.append(1)
        if len(asked) == 1:
            del ideal[f"{self.ds.TABLE}_3"]
        return ideal

    run.Cluster.ideal_state = first_answer_lacks_a_segment
    sys.exit(run.main(sys.argv[1:]))
"""


def test_a_segment_the_ideal_state_lacks_is_uploaded_again(tmp_path):
    """Set-up asks the controller what it routes by once the uploads are
    through. Here the first answer lacks a segment, as after the lost update
    of seed 3260000704 (PERF.md, PR 26): that segment is uploaded once more,
    the controller takes it, and the run goes on to a correct line."""
    (tmp_path / "loses_a_segment.py").write_text(LOSES_A_SEGMENT)
    forget_seed("ssb-flat-1srv", 2300000050)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"} | {"PYTHONPATH": str(ROOT)}
    p = subprocess.run(
        [sys.executable, str(tmp_path / "loses_a_segment.py"), "--workload", "ssb-q1-rate", "--seed", "2300000050",
         "--seconds", "3", "--trace", "0", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )  # fmt: skip
    assert p.returncode == 0, (p.stdout + p.stderr)[-3000:]
    assert "the ideal state lacks segments [3] of 6: uploading them again" in p.stdout
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0


REFUSES_AN_UPLOAD = """
import sys
from pathlib import Path
from perfbench import run

datagen_job = run._datagen_job


def refused_once(job):  # runs in a spawned worker, which finds it in this file
    mark = Path(job["out_dir"]) / "refused_once"
    if job["index"] == 2 and not mark.exists():
        mark.parent.mkdir(parents=True, exist_ok=True)
        mark.write_text("")
        raise ConnectionError("no controller reachable and leading after 1 attempts: controller not leading (503): FencedWriteError")
    return datagen_job(job)


if __name__ == "__main__":
    run._datagen_job = refused_once
    sys.exit(run.main(sys.argv[1:]))
"""


def test_an_upload_the_controller_refused_is_sent_again(tmp_path):
    """A write fenced while the controller's 2 s lease ran out under it ends
    an upload with a 503, and the upload's worker with an exception. With one
    attempt that ended the whole run with exit code 1 (seed 3260000902 met it
    on the chip at five uploads; PERF.md, PR 26); now the segment is built and
    sent once more."""
    (tmp_path / "refuses_an_upload.py").write_text(REFUSES_AN_UPLOAD)
    forget_seed("ssb-flat-1srv", 2300000060)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"} | {"PYTHONPATH": str(ROOT)}
    p = subprocess.run(
        [sys.executable, str(tmp_path / "refuses_an_upload.py"), "--workload", "ssb-citygroups-closed", "--seed", "2300000060",
         "--seconds", "3", "--trace", "0", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )  # fmt: skip
    assert p.returncode == 0, (p.stdout + p.stderr)[-3000:]
    assert "segment 2 failed to upload: ConnectionError" in p.stdout and "sending segments [2] again" in p.stdout
    assert '"segments_uploaded_again": 1' in p.stdout
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
