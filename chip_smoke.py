#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip. Smoke, not a benchmark.

    python chip_smoke.py [--seed N] [--rows N]      # needs a TPU; 60M rows
    python chip_smoke.py --rehearsal                # CPU, a few thousand rows

This process is a launcher: it never imports jax or pinot_tpu, so it can
never hold a chip. It starts the roles a deployment runs, as separate OS
processes through `python -m pinot_tpu.tools.admin`:

    controller (CPU)   broker (CPU)   one server per chip (the chip's owner)

A short-lived child builds the `lineorder` table of bench.py from `--seed` as
real .ptseg segments and uploads them through the controller; the server
downloads, CRC-verifies, loads and stages them itself. The launcher then
sends each query class over the broker's HTTP SQL endpoint, cold then warm,
and checks every answer exactly against a pandas evaluation of the same data
that it computes itself (only the HLL estimate is held to the sketch's error
bound). What ran where is asked of the roles, not assumed: platform, device
kind and ids, HBM in use, kernels called, compile-cache hits, native library.

Order of processes that touch a device (one at a time per chip):
  1. probe + kernel leg child (counts the chips, compiles the Pallas kernel
     at served shapes on both sides of its grid rule, checks it against
     numpy; exits)
  2. the server(s) of the cluster leg(s); on a host with >= 4 chips a second
     leg runs four servers, each pinned to its own chip
  3. on >= 4 chips, after the servers have exited: parallel/mesh.py
     execute_sharded_result for Q4 over all chips

Any failure — no TPU, a role that exits, an answer that differs, a query that
ran on the host executor, a kernel that ran interpreted — ends the run with
a non-zero exit code and no result line. The last line of stdout on success
is one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path

import numpy as np

from bench import Q2_SQL, Q4_SQL, _make_ssb_data  # numpy only: no jax is imported

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"  # data of one run; in .gitignore; removed at the end
LOGS = ROOT / "chiprun_out" / "chip_smoke"  # role logs + report.json; in .gitignore

FULL_ROWS = 60_000_000  # the sf10-class size bench.py calls its scale block
MIN_ROWS = 16_000_000  # bench.py's default table: a cut goes no lower
SEG_ROWS = 4_000_000
MESH_ROWS = 16_000_000  # the mesh leg runs bench.py's default table size
REHEARSAL_ROWS, REHEARSAL_SEG_ROWS = 24_000, 4_000
N_GROUPS = 25 * 25 * 7  # Q4's dense group space: c_nation x p_category x d_year
N_GROUPS_SMALL = 5 * 5 * 7  # SSB Q4.1's: c_region x s_region x d_year
TABLE = "lineorder"
WARM_RUNS = 3
#: relative error allowed on DISTINCTCOUNTHLL: 3 x the published standard
#: error of a 2^12-register HLL, the bound tests/test_sketch_error_bounds.py holds
HLL_BOUND = 3 * 0.0163

Q_COUNT = f"SELECT COUNT(*) FROM {TABLE} WHERE c_nation = 'NATION_07'"
Q3_SQL = (
    f"SELECT d_year, SUM(lo_revenue) FROM {TABLE} "
    "WHERE (c_nation = 'NATION_01' OR c_nation = 'NATION_02') AND lo_quantity < 25 "
    "GROUP BY d_year ORDER BY d_year LIMIT 20"
)
Q_HLL = f"SELECT DISTINCTCOUNTHLL(lo_revenue) FROM {TABLE}"
# every selected column is an ORDER BY key, so tied rows are identical rows
# and the answer is fixed; the composite key rank (600k x 50 x 7) fits int32,
# which is what keeps a multi-key ORDER BY on the device path
Q_SELECT = (
    f"SELECT lo_revenue, lo_quantity, d_year FROM {TABLE} WHERE c_nation = 'NATION_03' "
    "ORDER BY lo_revenue DESC, lo_quantity DESC, d_year DESC LIMIT 10"
)
# the multistage engine on one table: the leaf stage aggregates on the
# servers' device path, the final aggregate and the sort run in the stages
# above it, the last of them in the broker
Q_MULTISTAGE = (
    f"SET useMultistageEngine=true; SELECT c_nation, SUM(lo_revenue), COUNT(*) FROM {TABLE} "
    "WHERE lo_quantity < 25 GROUP BY c_nation ORDER BY SUM(lo_revenue) DESC LIMIT 5"
)
#: name -> (sql, runs on the multistage engine)
QUERIES = {
    "count_eq": (Q_COUNT, False),
    "filtered_agg_q2": (Q2_SQL, False),
    "groupby_1key": (Q3_SQL, False),
    "groupby_q4": (Q4_SQL, False),
    "distinctcounthll": (Q_HLL, False),
    "select_orderby": (Q_SELECT, False),
    "multistage_groupby": (Q_MULTISTAGE, True),
}
#: a cold query stages segments and compiles its plan shape; the deadline is
#: the caller's to grant (the broker's 30 s default is for warm traffic)
QUERY_PREFIX = "SET timeoutMs=900000; "


def say(msg: str) -> None:
    """To stdout, and to LOGS/progress.log for when stdout's tail is all that is kept."""
    print(f"[chip_smoke] {msg}", flush=True)
    if LOGS.is_dir():
        with open(LOGS / "progress.log", "a") as f:
            f.write(msg + "\n")


class SmokeFailure(Exception):
    """A check did not hold. Never caught to carry on: main lets it end the run."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def segment_plan(rows: int, seg_rows: int) -> list[int]:
    """Row count of each segment: equal sizes, so one compile serves all."""
    require(rows % seg_rows == 0, f"rows {rows} not a multiple of {seg_rows}")
    return [seg_rows] * (rows // seg_rows)


def segment_data(seed: int, index: int, n: int) -> dict:
    """Segment `index` of the table: bench.py's lineorder generator, seeded
    per segment so the datagen child and the reference draw the same rows."""
    return _make_ssb_data(np.random.default_rng([seed, index]), n)


# ---------------------------------------------------------------------------
# the reference: pandas over the same data, in this process's own workers
# ---------------------------------------------------------------------------


def reference_partial(seed: int, index: int, n: int) -> dict:
    """Mergeable per-segment partials of every smoke query, in plain pandas."""
    import pandas as pd

    t = pd.DataFrame(segment_data(seed, index, n))
    q2 = t[(t.d_year >= 1994) & (t.d_year <= 1996) & (t.c_nation == "NATION_03")]
    q3 = t[t.c_nation.isin(["NATION_01", "NATION_02"]) & (t.lo_quantity < 25)]
    q4 = t[(t.lo_quantity > 5) & (t.d_year >= 1993) & (t.d_year <= 1997)]
    sel = t[t.c_nation == "NATION_03"]
    cols = ["lo_revenue", "lo_quantity", "d_year"]
    return {
        "count_eq": int((t.c_nation == "NATION_07").sum()),
        "q2": {
            "n": len(q2),
            "sum_rev": int(q2.lo_revenue.sum()),
            "min_qty": int(q2.lo_quantity.min()) if len(q2) else None,
            "max_rev": int(q2.lo_revenue.max()) if len(q2) else None,
            "sum_cost": int(q2.lo_supplycost.sum()),
        },
        "q3": {int(k): int(v) for k, v in q3.groupby("d_year").lo_revenue.sum().items()},
        "q4": {
            k: int(v)
            for k, v in (q4.lo_revenue - q4.lo_supplycost)
            .groupby([q4.d_year, q4.c_nation, q4.p_category])
            .sum()
            .items()
        },
        "distinct_rev": np.unique(t.lo_revenue.to_numpy()),
        "select_top": [
            tuple(int(x) for x in r)
            for r in sel.sort_values(cols, ascending=False)[cols].head(10).itertuples(index=False)
        ],
        "ms": {
            k: (int(r.s), int(r.n))
            for k, r in t[t.lo_quantity < 25]
            .groupby("c_nation")
            .lo_revenue.agg(s="sum", n="count")
            .iterrows()
        },
    }


def merge_reference(partials: list[dict]) -> dict:
    """Expected result rows per query name, from the segments' partials."""

    def add(dicts):
        out: dict = {}
        for d in dicts:
            for k, v in d.items():
                out[k] = out.get(k, 0) + v
        return out

    q2 = [p["q2"] for p in partials if p["q2"]["n"]]
    n2 = sum(p["n"] for p in q2)
    q4 = sorted(add(p["q4"] for p in partials).items(), key=lambda kv: -kv[1])
    # ORDER BY ... LIMIT 10 is only a fixed answer if rank 10 and 11 differ
    require(q4[9][1] != q4[10][1], "reference: Q4 has a tie at the LIMIT boundary")
    ms: dict = {}
    for p in partials:
        for k, (sm, n) in p["ms"].items():
            old = ms.get(k, (0, 0))
            ms[k] = (old[0] + sm, old[1] + n)
    ms_rows = sorted(ms.items(), key=lambda kv: -kv[1][0])
    require(ms_rows[4][1][0] != ms_rows[5][1][0], "reference: multistage query ties at its LIMIT")
    distinct = partials[0]["distinct_rev"]
    for p in partials[1:]:
        distinct = np.union1d(distinct, p["distinct_rev"])
    return {
        "count_eq": [[sum(p["count_eq"] for p in partials)]],
        "filtered_agg_q2": [
            [
                float(sum(p["sum_rev"] for p in q2)),
                float(min(p["min_qty"] for p in q2)),
                float(max(p["max_rev"] for p in q2)),
                sum(p["sum_cost"] for p in q2) / n2,
            ]
        ],
        "groupby_1key": [[y, float(v)] for y, v in sorted(add(p["q3"] for p in partials).items())],
        "groupby_q4": [[int(y), n, c, float(v)] for (y, n, c), v in q4[:10]],
        "distinctcounthll": len(distinct),
        "select_orderby": [
            list(r) for r in sorted((r for p in partials for r in p["select_top"]), reverse=True)[:10]
        ],
        "multistage_groupby": [[k, float(sm), n] for k, (sm, n) in ms_rows[:5]],
    }


def check_rows(name: str, got: list, want) -> None:
    if name == "distinctcounthll":
        err = abs(got[0][0] - want) / want
        require(err <= HLL_BOUND, f"{name}: estimate {got[0][0]} vs exact {want} (rel err {err:.4f})")
        return
    require(got == want, f"{name}: result differs from the reference\n  got  {got}\n  want {want}")


# ---------------------------------------------------------------------------
# HTTP, with the stdlib
# ---------------------------------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 1000.0):
    req = urllib.request.Request(
        url,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as rsp:
        return json.loads(rsp.read())


def run_sql(broker_url: str, sql: str) -> tuple[dict, float]:
    t0 = time.perf_counter()
    doc = http_json(f"{broker_url}/query/sql", {"sql": QUERY_PREFIX + sql})
    wall = time.perf_counter() - t0
    require(not doc.get("exceptions"), f"query failed: {sql}\n  {doc.get('exceptions')}")
    return doc, wall


def metric_total(server_url: str, name: str) -> int:
    """A meter's count summed over its label sets, from /metrics JSON."""
    doc = http_json(f"{server_url}/metrics?format=json")
    return sum(
        int(m["count"]) for k, m in doc.items() if k == name or k.startswith(name + "{")
    )


# ---------------------------------------------------------------------------
# child processes
# ---------------------------------------------------------------------------


class Roles:
    """The OS processes of one run. Every role's stderr goes to a log file
    under LOGS; `stop_all` ends them, and `check_alive` fails the run the
    moment one has exited on its own."""

    def __init__(self, env: dict):
        self.env = env
        self.procs: dict[str, subprocess.Popen] = {}

    def start(self, name: str, argv: list[str], extra_env: dict | None = None) -> str:
        """Start `python -m pinot_tpu.tools.admin <argv>`; returns its URL."""
        with open(LOGS / f"{name}.stderr.log", "w") as err:
            p = subprocess.Popen(
                [sys.executable, "-m", "pinot_tpu.tools.admin", *argv],
                stdout=subprocess.PIPE,
                stderr=err,
                text=True,
                env={**self.env, **(extra_env or {})},
                cwd=ROOT,
            )
        self.procs[name] = p
        deadline = time.time() + 300
        while time.time() < deadline:
            line = p.stdout.readline()
            if not line:
                tail = (LOGS / f"{name}.stderr.log").read_text()[-2000:]
                raise SmokeFailure(f"role {name} exited during start-up (rc={p.wait()}):\n{tail}")
            say(f"{name}: {line.rstrip()}")
            if "listening on " in line:
                return line.rsplit(" ", 1)[-1].strip()
        raise SmokeFailure(f"role {name} never came up")

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


def run_child(env: dict, mode: str, *argv: str, timeout: float = 900.0) -> dict:
    """Run `chip_smoke.py --child <mode>` to its end; its last stdout line is
    its JSON result. A child that fails fails the run."""
    log = LOGS / f"child_{mode}.stderr.log"
    with open(log, "w") as err:
        p = subprocess.run(
            [sys.executable, str(ROOT / "chip_smoke.py"), "--child", mode, *argv],
            stdout=subprocess.PIPE,
            stderr=err,
            text=True,
            env=env,
            cwd=ROOT,
            timeout=timeout,
        )
    if p.returncode != 0:
        tail = log.read_text()[-3000:]
        raise SmokeFailure(f"child {mode} failed (rc={p.returncode}):\n{tail}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def child_kernels(args) -> dict:
    """Probe + kernel leg. Owns the device until it exits: reports what JAX
    sees, then compiles the Pallas kernel of ops/groupby_pallas.py at served
    shapes (one segment x Q4.1's and Q4's group spaces) and checks it is exact."""
    from pinot_tpu.common import runtime

    rt = runtime.require_device()
    if not args.rehearsal and rt["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform {rt['platform']!r}")

    import jax
    import jax.numpy as jnp

    from pinot_tpu.ops import groupby_pallas as gp

    n = args.seg_rows
    rng = np.random.default_rng([args.seed, 999])
    vals = rng.integers(-99_900, 599_950, n).astype(np.int32)  # lo_revenue - lo_supplycost
    mask = rng.random(n) < 0.6
    d_vals, d_mask = jnp.asarray(vals), jnp.asarray(mask)
    shapes = []
    # one shape on each side of gp.grid_for's rule: a group space so small that
    # the per-step cost binds (the narrowest lo width), and Q4's, where the MXU does
    for groups in (N_GROUPS_SMALL, N_GROUPS):
        gid = rng.integers(0, groups, n).astype(np.int32)
        t0 = time.perf_counter()
        sums, counts = gp.pallas_grouped_multi_sum([d_vals], jnp.asarray(gid), d_mask, groups)
        got_sum, got_cnt = np.asarray(sums[0]), np.asarray(counts)
        first_s = time.perf_counter() - t0
        exact = bool(
            np.array_equal(got_sum, np.bincount(gid[mask], weights=vals[mask].astype(np.float64), minlength=groups))
            and np.array_equal(got_cnt, np.bincount(gid[mask], minlength=groups))
        )
        if not exact:
            raise SystemExit(f"kernel ops.grouped_planes2 is not exact at rows={n} groups={groups}")
        grid = gp.grid_for(groups, 5)
        shapes.append({"rows": n, "groups": groups, "g2": grid.g2, "hiTiles": gp._hi_tiles(groups, grid),
                       "exact": exact, "first_call_s": round(first_s, 2)})  # fmt: skip
    kernels = {"ops.grouped_planes2": shapes}
    from pinot_tpu.common.kernel_obs import KERNELS

    called = {k["kernel"] for k in KERNELS.roofline()["kernels"]}
    return {
        "runtime": rt,
        "jaxDeviceCount": len(jax.devices()),
        "kernels": kernels,
        "calledKernels": sorted(called),
    }


def _build_and_upload(job) -> dict:
    """Datagen worker: one segment, generated, built, written as .ptseg and
    pushed through the controller's upload endpoint."""
    seed, index, n, controller_url, out_dir = job
    from pinot_tpu.cluster.http import RemoteControllerClient
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.segment.builder import write_segment

    t0 = time.perf_counter()
    seg = SegmentBuilder(_lineorder_schema()).build(segment_data(seed, index, n), f"{TABLE}_{index}")
    seg_dir = write_segment(seg, out_dir)
    nbytes = sum(f.stat().st_size for f in Path(seg_dir).iterdir())
    RemoteControllerClient(controller_url).upload_segment_dir(TABLE, seg_dir)
    shutil.rmtree(seg_dir)
    return {"segment": seg.name, "rows": n, "fileBytes": nbytes, "seconds": round(time.perf_counter() - t0, 2)}


def _lineorder_schema():
    from pinot_tpu.common import DataType, Schema

    return Schema.build(
        TABLE,
        dimensions=[
            ("d_year", DataType.INT),
            ("c_nation", DataType.STRING),
            ("p_category", DataType.STRING),
        ],
        metrics=[
            ("lo_revenue", DataType.LONG),
            ("lo_supplycost", DataType.LONG),
            ("lo_quantity", DataType.INT),
        ],
    )


def child_datagen(args) -> dict:
    """Create the tables, then build and upload every segment. CPU only."""
    from pinot_tpu.cluster.http import RemoteControllerClient
    from pinot_tpu.common import TableConfig

    rc = RemoteControllerClient(args.controller_url)
    rc.add_schema(_lineorder_schema())
    rc.add_table(TableConfig(TABLE, replication=1))
    out_dir = str(WORK / "built")
    jobs = [
        (args.seed, i, n, args.controller_url, out_dir)
        for i, n in enumerate(segment_plan(args.rows, args.seg_rows))
    ]
    workers = max(1, min(len(jobs), (os.cpu_count() or 2) - 2, 10))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as pool:
        done = list(pool.map(_build_and_upload, jobs))
    return {"segments": done, "workers": workers}


def child_mesh(args) -> dict:
    """parallel/mesh.py over every chip of the host: execute_sharded_result on
    a table sharded over jax.devices() — Q4 (psum combine, Pallas kernel under
    shard_map) and Q2 (pmin/pmax combines)."""
    from pinot_tpu.common import runtime

    rt = runtime.require_device()

    from pinot_tpu.parallel import build_sharded_table, make_mesh
    from pinot_tpu.parallel.mesh import execute_sharded_result

    sizes = segment_plan(args.rows, args.seg_rows)
    parts = [segment_data(args.seed, i, n) for i, n in enumerate(sizes)]
    data = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    mesh = make_mesh()
    t0 = time.perf_counter()
    table = build_sharded_table(_lineorder_schema(), data, mesh, rows_per_segment=args.seg_rows)
    build_s = time.perf_counter() - t0
    out = {
        "runtime": rt,
        "meshDevices": int(mesh.devices.size),
        "rows": args.rows,
        "segments": table.n_segments,
        "build_s": round(build_s, 2),
        "queries": {},
    }
    for name in ("groupby_q4", "filtered_agg_q2"):
        sql = QUERIES[name][0]
        t0 = time.perf_counter()
        rows = execute_sharded_result(table, sql).rows
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        execute_sharded_result(table, sql)
        out["queries"][name] = {
            "result": rows,
            "setup_cold_s": round(cold_s, 3),
            "steady_warm_s": round(time.perf_counter() - t0, 4),
        }
    return out


# ---------------------------------------------------------------------------
# one cluster leg: controller + broker + servers, load, query, check, ask
# ---------------------------------------------------------------------------


def chip_pin(chip: int) -> dict:
    """libtpu's variables that give a process exactly one chip of the host.
    The process then sees that chip as its device 0 at coords (0,0,0), so the
    pin itself is what tells the chips apart; two processes pinned to one
    chip could not both initialise."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def cluster_leg(
    label: str, cfg, env: dict, server_env: dict, chips: list[int | None], reference
) -> dict:
    """Bring a cluster up on `chips` (one server each; None = no pin: the
    host's only chip, or the CPU in rehearsal), load the table, run every
    query cold and warm, check answers and where they ran, tear down.
    `reference()` gives the expected rows; it is first called after the load,
    so the launcher's workers compute it while the cluster comes up."""
    say(f"--- {label}: {len(chips)} server(s) ---")
    leg = WORK / label
    leg.mkdir(parents=True)
    roles = Roles(env)
    report: dict = {"servers": {}, "queries": {}}
    try:
        controller = roles.start(
            f"{label}_controller",
            ["StartController", "--store-dir", str(leg / "store"), "--deep-store", str(leg / "deep")],
        )
        servers = {}
        for i, chip in enumerate(chips):
            sid = f"server_{i}"
            servers[sid] = roles.start(
                f"{label}_{sid}",
                [
                    "StartServer", "--controller-url", controller, "--server-id", sid,
                    "--data-dir", str(leg / f"data_{sid}"),
                ],
                {**server_env, **({} if chip is None else chip_pin(chip))},
            )
        # the broker's result cache would answer the warm runs; this smoke
        # wants every run to reach the device
        broker = roles.start(
            f"{label}_broker",
            ["StartBroker", "--controller-url", controller, "--cache-json", '{"enabled": false}'],
        )

        # -- who runs on what, asked before any data exists -------------------
        for name, url in (("controller", controller), ("broker", broker)):
            rt = http_json(f"{url}/health/ready")["runtime"]
            require(rt["platform"] == "cpu", f"{name} initialised backend {rt['platform']!r}, not cpu")
            report[name] = {"platform": rt["platform"]}
        want_platform = "cpu" if cfg.rehearsal else "tpu"
        for (sid, url), chip in zip(servers.items(), chips):
            rt = http_json(f"{url}/health/ready")["runtime"]
            require(
                rt["platform"] == want_platform,
                f"{sid} runs on {rt['platform']!r}, not {want_platform!r}",
            )
            require(rt["deviceCount"] == 1, f"{sid} sees {rt['deviceCount']} devices, not 1")
            if chip is not None:
                require(rt["visibleChips"] == str(chip), f"{sid} holds chip {rt['visibleChips']}, not {chip}")
            if not cfg.rehearsal:
                require(not rt["pallasInterpret"], f"{sid} would interpret its Pallas kernels")

        # -- load: datagen child builds + uploads; servers fetch, verify, load
        t0 = time.perf_counter()
        gen = run_child(
            {**env, "JAX_PLATFORMS": "cpu"}, "datagen",
            "--seed", str(cfg.seed), "--rows", str(cfg.rows), "--seg-rows", str(cfg.seg_rows),
            "--controller-url", controller,
        )
        n_segments = len(gen["segments"])
        hosted = {sid: http_json(f"{url}/segments/{TABLE}") for sid, url in servers.items()}
        require(
            sorted(s for segs in hosted.values() for s in segs)
            == sorted(g["segment"] for g in gen["segments"]),
            f"servers host {hosted}, expected each of {n_segments} segments once",
        )
        require(all(hosted.values()), f"a server was assigned no segment: {hosted}")
        for sid, url in servers.items():
            st = http_json(f"{url}/debug/storage")
            require(
                len(st["localSegments"]) >= len(hosted[sid]) and not st["quarantined"],
                f"{sid} did not take verified local copies: {st}",
            )
        report["load"] = {
            "rows": cfg.rows,
            "segments": n_segments,
            "segmentFileBytes": sum(g["fileBytes"] for g in gen["segments"]),
            "datagenWorkers": gen["workers"],
            "build_upload_load_s": round(time.perf_counter() - t0, 1),
            "segmentsPerServer": {sid: len(v) for sid, v in hosted.items()},
        }
        say(f"loaded: {json.dumps(report['load'])}")
        roles.check_alive()

        # -- queries: cold + warm runs, each checked for answer and device mode
        expected = reference()

        def fused_runs() -> int:  # segment executions of a fused device program
            return sum(_kernel_calls(u, "query.fused_packed") for u in servers.values())

        def leaf_scans() -> int:  # multistage leaf segments run on the device path
            return sum(metric_total(u, "server.multistageLeafDeviceScans") for u in servers.values())

        for name, (sql, multistage) in QUERIES.items():
            fused_before, leaf_before = fused_runs(), leaf_scans()
            walls = []
            for _ in range(1 + WARM_RUNS):
                doc, wall = run_sql(broker, sql)
                check_rows(name, doc["resultTable"]["rows"], expected[name])
                walls.append(wall)
            if multistage:
                # the leaf stage ran the fused program on the servers; the
                # root stage ran in the broker
                require(leaf_scans() > leaf_before, f"{name}: no leaf stage ran on a server's device path")
            else:
                require(doc["totalDocs"] == cfg.rows, f"{name}: totalDocs {doc['totalDocs']} != {cfg.rows}")
                ran = fused_runs() - fused_before
                require(
                    ran == n_segments * (1 + WARM_RUNS),
                    f"{name}: {ran} device segment executions, expected "
                    f"{n_segments} segments x {1 + WARM_RUNS} runs",
                )
            report["queries"][name] = {
                "mode": "device",
                "setup_cold_s": round(walls[0], 3),
                "steady_warm_s": [round(w, 4) for w in walls[1:]],
                "rows": len(doc["resultTable"]["rows"]),
            }
            say(f"{name}: exact; cold {walls[0]:.2f}s, warm {min(walls[1:]) * 1e3:.1f} ms (smoke, not a benchmark)")
            roles.check_alive()

        # -- what the servers say they ran on ---------------------------------
        for sid, url in servers.items():
            rt = http_json(f"{url}/health/ready")["runtime"]
            roof = http_json(f"{url}/debug/roofline")
            fallbacks = metric_total(url, "server.deviceFallbacks")
            require(fallbacks == 0, f"{sid}: {fallbacks} device fallbacks to the host executor")
            require(roof["platform"] == rt["platform"], f"{sid}: roofline/runtime platform differ")
            if not cfg.rehearsal:
                require(roof["hbm"]["source"] == "device", f"{sid}: HBM figures not from memory_stats()")
                resident = 24 * cfg.seg_rows * len(hosted[sid])  # 6 columns x 4 B staged
                require(
                    roof["hbm"]["liveBytes"] >= resident,
                    f"{sid}: {roof['hbm']['liveBytes']} B in use on device < table's {resident} B",
                )
            require(
                roof["inlined"].get("ops.grouped_planes2", 0) > 0,
                f"{sid}: the Pallas byte-plane kernel was never traced into a fused program",
            )
            report["servers"][sid] = {
                "platform": rt["platform"],
                "deviceKind": rt["deviceKind"],
                "deviceCount": rt["deviceCount"],
                "devices": rt["devices"],
                "visibleChips": rt["visibleChips"],
                "pallasInterpret": rt["pallasInterpret"],
                "hbm": roof["hbm"],
                "hbmPeakGBps": roof["hbmPeakGBps"],
                "kernelsCalled": {k["kernel"]: k["calls"] for k in roof["kernels"]},
                "kernelsInlined": roof["inlined"],
                "compileCache": rt["compileCache"],
                "native": rt["native"],
                "deviceFallbacks": fallbacks,
            }
            say(f"{sid}: {json.dumps(report['servers'][sid])}")
        roles.check_alive()
    finally:
        roles.stop_all()
    return report


def _kernel_calls(server_url: str, kernel: str) -> int:
    roof = http_json(f"{server_url}/debug/roofline")
    return sum(k["calls"] for k in roof["kernels"] if k["kernel"] == kernel)


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=FULL_ROWS, help=f"table rows (>= {MIN_ROWS})")
    ap.add_argument("--rehearsal", action="store_true", help="CPU, tiny table: checks the script, not the chip")
    ap.add_argument("--child", choices=["kernels", "datagen", "mesh"], help=argparse.SUPPRESS)
    ap.add_argument("--seg-rows", type=int, default=SEG_ROWS, help=argparse.SUPPRESS)
    ap.add_argument("--controller-url", help=argparse.SUPPRESS)
    cfg = ap.parse_args(argv)

    if cfg.child:
        out = {"kernels": child_kernels, "datagen": child_datagen, "mesh": child_mesh}[cfg.child](cfg)
        print(json.dumps(out), flush=True)
        return 0

    t_start = time.perf_counter()
    env = {**os.environ, "PYTHONPATH": f"{ROOT}{os.pathsep}{os.environ.get('PYTHONPATH', '')}"}
    server_env: dict = {}
    if cfg.rehearsal:
        cfg.rows, cfg.seg_rows = REHEARSAL_ROWS, REHEARSAL_SEG_ROWS
        # everything on the CPU, said out loud; the servers take the Pallas
        # path anyway (interpreted) so the rehearsal walks the same code
        env["JAX_PLATFORMS"] = "cpu"
        server_env = {"PINOT_TPU_PALLAS": "1"}
    else:
        require(cfg.rows >= MIN_ROWS, f"--rows {cfg.rows} is below the floor of {MIN_ROWS}")
    if cfg.rows != FULL_ROWS:
        say(f"rows cut: {cfg.rows} instead of {FULL_ROWS}")

    for d in (WORK, LOGS):
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True)
    report: dict = {"rehearsal": cfg.rehearsal, "seed": cfg.seed, "rows": cfg.rows}
    sizes = segment_plan(cfg.rows, cfg.seg_rows)
    try:
        # 1. probe + kernel leg: a child that has exited before any server starts
        probe = run_child(
            env, "kernels", "--seed", str(cfg.seed), "--seg-rows", str(cfg.seg_rows),
            *(["--rehearsal"] if cfg.rehearsal else []),
        )
        rt = probe["runtime"]
        device = {"platform": rt["platform"], "kind": rt["deviceKind"], "count": probe["jaxDeviceCount"]}
        say(f"device: {json.dumps(device)}; kernel leg: {json.dumps(probe['kernels'])}")
        if cfg.rehearsal:
            require(device["platform"] == "cpu", f"rehearsal must run on the CPU, got {device}")
        else:
            require(device["platform"] == "tpu", f"no TPU: JAX reports {device}")
            require(not rt["pallasInterpret"], "the Pallas kernels ran interpreted")
        report["device"], report["kernelLeg"] = device, probe

        # the reference runs in this launcher's own workers while the chip works
        n_ref = max(1, min(len(sizes), (os.cpu_count() or 2) // 2, 6))
        with ProcessPoolExecutor(n_ref, mp_context=get_context("spawn")) as pool:
            futures = [pool.submit(reference_partial, cfg.seed, i, n) for i, n in enumerate(sizes)]

            @functools.cache
            def partials() -> list:
                return [f.result() for f in futures]

            def reference() -> dict:
                return merge_reference(partials())

            # 2. the served path on one chip
            multi = not cfg.rehearsal and device["count"] >= 4
            report["oneServer"] = cluster_leg(
                "one", cfg, env, server_env, [0 if multi else None], reference
            )
            if multi:
                # 3. one server per chip, same table, same answers
                report["fourServers"] = cluster_leg(
                    "four", cfg, env, server_env, [0, 1, 2, 3], reference
                )
                # 4. parallel/mesh.py over all chips, after the servers have exited
                mesh = run_child(
                    env, "mesh", "--seed", str(cfg.seed), "--rows", str(MESH_ROWS),
                    "--seg-rows", str(cfg.seg_rows),
                )
                want = merge_reference(partials()[: MESH_ROWS // cfg.seg_rows])
                for name, q in mesh["queries"].items():
                    check_rows(name, q["result"], want[name])
                require(mesh["meshDevices"] == device["count"], f"mesh took {mesh['meshDevices']} devices")
                report["meshLeg"] = mesh
                say(f"mesh leg: exact; {json.dumps({k: v for k, v in mesh.items() if k != 'runtime'})}")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)

    report["wall_s"] = round(time.perf_counter() - t_start, 1)
    (LOGS / "report.json").write_text(json.dumps(report, indent=1))
    say(f"all phases passed in {report['wall_s']} s; full report: {LOGS / 'report.json'}")
    last = {"ok": True, "device": report["device"]}
    if cfg.rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
