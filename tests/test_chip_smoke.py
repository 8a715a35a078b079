"""chip_smoke.py, rehearsed on the CPU: the launcher stays off JAX, refuses to
report a CPU run as a chip run, and its rehearsal walks the whole served path
(controller + server + broker as OS processes, upload through the controller,
every query class checked against pandas). What only a chip can show — Mosaic
compiles, libtpu's 64-bit handling, HBM figures — is `python chip_smoke.py`
on the TPU machine."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


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


@pytest.fixture(scope="module")
def rehearsal():
    p = _python("chip_smoke.py", "--rehearsal", env=_env(JAX_PLATFORMS="cpu"), timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    report = json.loads((ROOT / "chiprun_out" / "chip_smoke" / "report.json").read_text())
    return p, report


def test_launcher_import_leaves_jax_out():
    p = _python(
        "-c",
        "import sys, chip_smoke; "
        "assert 'jax' not in sys.modules and 'pinot_tpu' not in sys.modules, sorted(sys.modules)",
        env=_env(),
    )
    assert p.returncode == 0, p.stderr[-2000:]


def test_rehearsal_runs_end_to_end_and_says_so(rehearsal):
    p, report = rehearsal
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "rehearsal": True,
    }
    assert report["rehearsal"] is True and report["rows"] == 24_000
    leg = report["oneServer"]
    assert leg["load"]["segments"] == 6
    # every query class ran, on the device path, cold and warm
    assert set(leg["queries"]) == {
        "count_eq", "filtered_agg_q2", "groupby_1key", "groupby_q4",
        "distinctcounthll", "select_orderby", "multistage_groupby",
    }
    assert all(q["mode"] == "device" and len(q["steady_warm_s"]) == 3 for q in leg["queries"].values())
    (server,) = leg["servers"].values()
    assert server["platform"] == "cpu" and server["deviceCount"] == 1
    assert server["deviceFallbacks"] == 0
    # Q4 rode the byte-plane kernel inside its fused program (interpreted here)
    assert server["kernelsInlined"]["ops.grouped_planes2"] > 0
    assert server["kernelsCalled"]["query.fused_packed"] > 0
    assert server["native"] == "built" or server["native"].startswith("fallback:")
    # the package's Pallas kernel went through the kernel leg on both sides of its grid rule
    (shapes,) = report["kernelLeg"]["kernels"].values()
    assert [(s["groups"], s["g2"], s["exact"]) for s in shapes] == [(175, 8, True), (4375, 40, True)]


def test_broker_and_controller_report_cpu_backend(rehearsal):
    _, report = rehearsal
    assert report["oneServer"]["controller"] == {"platform": "cpu"}
    assert report["oneServer"]["broker"] == {"platform": "cpu"}
    # and they say so themselves on their start-up line
    out = rehearsal[0].stdout
    assert "controller backend: platform=cpu" in out and "broker backend: platform=cpu" in out


@pytest.mark.parametrize("platforms", ["cpu", None])
def test_without_rehearsal_a_cpu_only_box_is_refused(platforms):
    """JAX held to the CPU, or left to find a TPU that is not there: either
    way a non-zero exit and no result line."""
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    if platforms:
        env["JAX_PLATFORMS"] = platforms
    p = _python("chip_smoke.py", env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_rows_below_the_floor_are_refused():
    p = _python("chip_smoke.py", "--rows", "1000000", env=_env(JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and '"ok"' not in p.stdout


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
