"""The roles of one run as OS processes, and the few HTTP calls the launcher makes.

The pattern is chip_smoke.py's (PR 21), copied so that the benchmark owns its
yardstick: controller and broker pin themselves to the CPU, each server owns
one chip and fails to start without it. Every child's stdout and stderr go
to files under the run's log directory from the moment it is spawned, so
nothing a role prints can follow the result line on the launcher's stdout.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

from perfbench.manifest import ROOT


class RunFailure(Exception):
    """The run cannot give a result: no chip, a role died, a query left the
    device path. `run.py` lets it end the process with a non-zero code and
    no result line."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RunFailure(what)


def http_json(url: str, body: dict | None = None, timeout: float = 120.0):
    req = urllib.request.Request(
        url,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as rsp:
        return json.loads(rsp.read())


def ready_doc(url: str, timeout: float = 600.0) -> dict:
    """A role's /health/ready document, once it is ready: a server answers 503
    while it loads its segments (a restart over a cached seed does, for a while)."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            return http_json(f"{url}/health/ready")
        except urllib.error.HTTPError as e:
            if e.code != 503 or time.monotonic() > deadline:
                raise
        time.sleep(0.25)


def metric_total(url: str, name: str) -> int:
    """A meter's count summed over its label sets, from a role's /metrics JSON."""
    doc = http_json(f"{url}/metrics?format=json")
    return sum(int(m["count"]) for k, m in doc.items() if k == name or k.startswith(name + "{"))


def kernel_calls(server_url: str, kernel: str) -> int:
    roof = http_json(f"{server_url}/debug/roofline")
    return sum(k["calls"] for k in roof["kernels"] if k["kernel"] == kernel)


def chip_pin(chip: int) -> dict:
    """libtpu's variables that give a process exactly one chip of a host with several."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


class Roles:
    def __init__(self, env: dict, log_dir: Path):
        self.env = env
        self.log_dir = log_dir
        self.procs: dict[str, subprocess.Popen] = {}

    def start(self, name: str, argv: list[str], extra_env: dict | None = None, timeout: float = 300.0) -> str:
        """Start `python -m <argv...>`; returns the URL from its "listening on" line."""
        out_path = self.log_dir / f"{name}.stdout.log"
        with open(out_path, "w") as out, open(self.log_dir / f"{name}.stderr.log", "w") as err:
            p = subprocess.Popen(
                [sys.executable, "-m", *argv],
                stdin=subprocess.DEVNULL, stdout=out, stderr=err,
                env={**self.env, **(extra_env or {})}, cwd=ROOT,
            )  # fmt: skip
        self.procs[name] = p
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for line in out_path.read_text().splitlines():
                if "listening on " in line:
                    return line.rsplit(" ", 1)[-1].strip()
            if p.poll() is not None:
                tail = (self.log_dir / f"{name}.stderr.log").read_text()[-3000:]
                raise RunFailure(f"role {name} exited during start-up (rc={p.returncode}):\n{tail}")
            time.sleep(0.05)
        raise RunFailure(f"role {name} never came up")

    def check_alive(self) -> None:
        for name, p in self.procs.items():
            require(p.poll() is None, f"role {name} exited (rc={p.returncode})")

    def stop_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in self.procs.values():
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        self.procs.clear()


class ServerControl:
    """Client of server_main.py's control socket."""

    def __init__(self, control_file: Path, timeout: float = 60.0):
        deadline = time.monotonic() + timeout
        while not (control_file.exists() and control_file.read_text().strip()):
            require(time.monotonic() < deadline, f"server wrote no control port to {control_file}")
            time.sleep(0.05)
        self.port = int(control_file.read_text())

    def ask(self, timeout: float = 300.0, **req) -> dict:
        with socket.create_connection(("127.0.0.1", self.port), timeout=timeout) as s:
            s.sendall(json.dumps(req).encode() + b"\n")
            out = json.loads(s.makefile().readline())
        require(out.get("ok"), f"server control {req.get('cmd')}: {out.get('error')}")
        return out


def child_env(rehearsal: bool) -> dict:
    env = {**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{os.environ.get('PYTHONPATH', '')}"}
    env.pop("BENCH_RUN", None)  # the driver's own; no role may key anything on it
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one CPU device a server, whatever the caller's tests forced
    else:
        env.pop("JAX_PLATFORMS", None)  # a server must get a TPU or fail (runtime.require_device)
    return env
