"""The benchmark of record, rehearsed on the CPU: `python -m perfbench.run
--rehearsal` starts controller, broker and server as OS processes, loads a
few thousand rows through the controller, drives the cell's traffic over HTTP
and checks the answers against the benchmark's own independent reference. So
the seams the harness reads the program at (`/health/ready` `runtime`,
`/debug/roofline` `kernels[].calls` of `query.fused_packed`, `/metrics`
`server.deviceFallbacks`, the ledger in every answer, `Server(fast32=)`) are
held here, before a chip run finds them broken. The launcher stays off JAX and
refuses to report a CPU run as a chip run. What only a chip can show — Mosaic
compiles, libtpu's 64-bit handling, every time and share — is the same command
without `--rehearsal` on the TPU machine.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import result_line
from perfbench.manifest import load_cell, load_manifest

ROOT = Path(__file__).resolve().parent.parent
MANIFEST = load_manifest(ROOT)
#: one fixed seed a cell; the refusals get their own, so that no run clears another's logs
SEEDS = {"ssb-q1-rate": 2_900_000_001, "ssb-groupby-closed": 2_900_000_002}
REFUSED_SEED = 2_900_000_010
#: the cell in which a server is killed 10 s into the window and started again: a window that outlasts the
#: kill, the restart and the reload
LOSS_CELL, LOSS_SECONDS, LOSS_SEED = "ssb4-serverloss-closed", 20, 3_300_000_011
#: the cell whose GROUP BY key is an expression (TSBS double-groupby-1)
TIME_CELL, TIME_SEED = "tsbs-hosthour-closed", 3_350_000_011


def _env(**extra):
    """The test's environment without conftest's 8 virtual CPU devices: a
    rehearsal server must see one device, as a server on a chip does."""
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = str(ROOT)
    env.update(extra)
    return env


def _python(*argv, env, timeout=120):
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout
    )


def _perfbench(workload, seed, *extra, env, timeout=600, seconds=3):
    return _python(
        "-m", "perfbench.run", "--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0", *extra,
        env=env, timeout=timeout,
    )  # fmt: skip


def _cold(workload, seed):
    """Without the table an earlier run left for the seed, over which the roles would restart."""
    config = load_cell(MANIFEST, workload, ROOT)["config"]["name"]
    shutil.rmtree(ROOT / "perfbench" / ".cache" / f"{config}-rehearsal" / str(seed), ignore_errors=True)


@pytest.fixture(scope="module")
def rehearse():
    """`rehearse(workload)` -> (the finished process, its result line, its log
    directory); a cell is rehearsed once a module. Each rehearsal starts cold:
    without the table an earlier run left for the seed, over which the roles
    would restart (a restart has a known rare fault, PERF.md §7)."""
    runs = {}

    def run(workload):
        if workload not in runs:
            seed = SEEDS[workload]
            _cold(workload, seed)
            p = _perfbench(workload, seed, "--rehearsal", env=_env(JAX_PLATFORMS="cpu"))
            assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
            line = json.loads(p.stdout.strip().splitlines()[-1])  # the last line of stdout is the result
            runs[workload] = p, line, ROOT / "perfbench_out" / workload / str(seed)
        return runs[workload]

    return run


def test_launcher_import_leaves_jax_out():
    p = _python(
        "-c",
        "import sys, perfbench.run; "
        "assert 'jax' not in sys.modules and 'pinot_tpu' not in sys.modules, sorted(sys.modules)",
        env=_env(),
    )
    assert p.returncode == 0, p.stderr[-2000:]


@pytest.mark.parametrize("workload", list(SEEDS))
def test_rehearsal_ends_in_a_valid_line_and_is_correct(rehearse, workload):
    """`ssb-q1-rate`: filtered SUM in an open loop. `ssb-groupby-closed`:
    multi-key GROUP BY + ORDER BY through the byte-plane kernel (interpreted:
    the harness sets PINOT_TPU_PALLAS=1 in a rehearsal). run.py itself ends
    with no line where a query left the device path, no fused program ran in
    the window (`/debug/roofline`) or a role is on the wrong platform."""
    p, line, _ = rehearse(workload)
    chips = load_cell(MANIFEST, workload, ROOT)["entry"]["chips"]
    result_line.validate(line, MANIFEST, workload, False, chips=chips)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, p.stdout[-3000:]
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert all(c["value"] <= c["limit"] for c in line["compared"].values()), line["compared"]
    assert line["compared"]["max_abs_diff"]["value"] == 0  # integer aggregates: exact


def test_a_time_chart_by_host_and_hour_runs_on_the_device_path():
    """`tsbs-hosthour-closed` at its rehearsal size (40 hosts, 48 hours, 6
    segments), traced, so that the line holds the per-layer metrics: GROUP BY
    hostname, DATETRUNC('hour', ts) through broker, server and the fused
    program (run.py ends with no line where a segment fell back to the host),
    every compared answer complete, in order and within the configuration's
    tolerance of the plain reference, the expression key's plan span in the
    answers' ledgers, and half to two thirds of the segments rejected by the
    12-hour window (3 or 4 of 6 here)."""
    _cold(TIME_CELL, TIME_SEED)
    p = _python(
        "-m", "perfbench.run", "--workload", TIME_CELL, "--seed", str(TIME_SEED), "--seconds", "4", "--trace", "1", "--rehearsal",
        env=_env(JAX_PLATFORMS="cpu"), timeout=600,
    )  # fmt: skip
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    result_line.validate(line, MANIFEST, TIME_CELL, True, chips=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, p.stdout[-3000:]
    compared = line["compared"]
    assert all(c["value"] <= c["limit"] for c in compared.values()), compared
    assert compared["rows_missing_or_extra"]["value"] == 0 and compared["order_violations"]["value"] == 0
    assert 0 < compared["max_rel_err"]["limit"] < 1e-6  # DOUBLE means: the configuration's own tolerance, never exact
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["groupkey_plan_ms"] > 0 and 50.0 <= metrics["segments_pruned_share"] <= 66.7, metrics
    # each query averages a metric drawn of the ten, and the one warm-up query's programs serve them all
    assert metrics["compiles_in_window"] == 0, metrics
    # every compared answer is a row a host and hour of its window: 40 hosts x 12 or 13 hours
    rows = [ln.split("rows=")[1].split(":")[0] for ln in p.stdout.splitlines() if ln.startswith("[perfbench] check #")]
    assert rows and set(rows) <= {"480", "520"}, rows


def test_a_table_kept_twice_survives_the_loss_and_the_return_of_a_server():
    """`ssb4-serverloss-closed` as OS processes, as the program stands (no
    fixture on PYTHONPATH): four servers, the table at replication 2,
    `server_1` SIGKILLed 10 s into the window and started again at once. Every
    answer before, during and after is complete and exact against the plain
    reference (each template from every phase, every query in flight at the
    kill), none fails, and the restarted server is ready, hosts its share
    and serves again, in that order, inside the recovery deadline. (The same
    schedule over the table kept once, `--control no-replica`, must print
    `correct: false`: `perfbench/tests/test_server_loss_cell.py`; one such
    run loads this box for a minute, so tier-1 makes the one.)"""
    _cold(LOSS_CELL, LOSS_SEED)
    p = _perfbench(LOSS_CELL, LOSS_SEED, "--rehearsal", env=_env(JAX_PLATFORMS="cpu"), seconds=LOSS_SECONDS)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    loss = line["loss"]
    assert loss["server"] == "server_1"
    assert 10.0 <= loss["kill_s"] < loss["ready_s"] < loss["hosted_s"] < loss["served_s"], loss
    result_line.validate(line, MANIFEST, LOSS_CELL, False, chips=4)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, p.stderr[-3000:]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values()), line["compared"]
    assert line["compared"]["max_abs_diff"]["value"] == 0 and line["compared"]["not_recovered"]["value"] == 0
    assert loss["recovered_s"] == loss["served_s"]  # no answer sent after it lost a leg again
    assert set(line["servers"]) == {f"server_{i}" for i in range(4)}
    assert all(s["fused_calls"] > 0 for s in line["servers"].values()), line["servers"]  # every server took its share


def test_every_role_of_the_rehearsal_reports_the_cpu_backend(rehearse):
    """Each role says on its start-up line which backend it initialised."""
    _, _, log_dir = rehearse("ssb-q1-rate")
    for role, who in (("controller", "controller"), ("broker", "broker"), ("server_0", "server server_0")):
        assert f"{who} backend: platform=cpu" in (log_dir / f"{role}.stdout.log").read_text()


@pytest.mark.parametrize("platforms", ["cpu", None])
def test_without_rehearsal_a_cpu_only_box_is_refused(platforms):
    """JAX held to the CPU, or left to find a TPU that is not there: either
    way a non-zero exit and no result line."""
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    p = _perfbench("ssb-q1-rate", REFUSED_SEED, env=env, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "Unable to initialize backend 'tpu'" in p.stderr


def test_compile_cache_dir_comes_from_outside_or_is_the_checkouts():
    code = "import pinot_tpu, jax; print(jax.config.jax_compilation_cache_dir); print(pinot_tpu.COMPILE_CACHE_DIR)"
    p = _python("-c", code, env=_env(JAX_PLATFORMS="cpu"))
    assert p.stdout.split() == [str(ROOT / ".jax_cache")] * 2, p.stderr[-2000:]
    p = _python("-c", code, env=_env(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="/tmp/elsewhere"))
    assert p.stdout.split() == ["/tmp/elsewhere"] * 2, p.stderr[-2000:]


def test_server_role_without_a_chip_fails_instead_of_serving_on_cpu(tmp_path):
    """StartServer with JAX_PLATFORMS unset requires a TPU: on this box that
    is a start-up error, never a CPU server that registers and serves."""
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    p = _python(
        "-m", "pinot_tpu.tools.admin", "StartServer", "--controller-url", "http://127.0.0.1:9",
        env=env,
    )
    assert p.returncode != 0
    assert "Unable to initialize backend 'tpu'" in p.stderr and "listening on" not in p.stdout
