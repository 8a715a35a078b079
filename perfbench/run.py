#!/usr/bin/env python3
"""One cell, one run, one result line.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is a launcher: it imports neither jax nor pinot_tpu, so it can
never hold a chip. It starts controller, server(s) and broker as OS
processes, loads the configuration's table for the seed, warms the cell's
own query templates, drives the cell's traffic for `--seconds`, checks the
answers against the plain reference, stops every role, and prints the result
line last. No chip, a role that dies, a query that left the device path or a
role on the wrong platform end the run with a non-zero code and no line.

`--rehearsal` (never passed by the driver) runs the same code on the CPU at
a tiny scale; its line says `"platform": "cpu"`.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from multiprocessing import get_context
from pathlib import Path

if __package__ in (None, ""):  # run as a script: make `perfbench` importable
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from perfbench import check, loadgen, refeval, result_line
from perfbench import loss as loss_mod
from perfbench import tables as tables_mod
from perfbench.cluster import (
    Roles, RunFailure, ServerControl, child_env, chip_pin, http_json, kernel_calls, metric_total, ready_doc, require,
)  # fmt: skip
from perfbench.manifest import BENCH, ROOT, load_cell, load_manifest, metrics_of, with_entries
from perfbench.trace_reduce import find_xplanes  # the standard library only at import: jax stays in the child

OUT = ROOT / "perfbench_out"
CACHE = BENCH / ".cache"
PEAKS = json.loads((BENCH / "peaks.json").read_text())


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


#: worker processes for datagen and for the reference. Each holds one generated
#: segment (over a gigabyte at 4M rows x 30 columns) beside a server that holds
#: the whole table; ten of them met the one-chip machine's 40 GiB (my chip run, PR 23).
#: More would not load a table sooner: the one controller takes a segment every
#: 6-7 s however many uploads it is given (five workers and eighteen kept the same
#: pace, and eighteen starved its lease renewal; PERF.md, PR 26)
MAX_WORKERS = 5


def workers_for(n_jobs: int) -> int:
    return max(1, min(n_jobs, (os.cpu_count() or 2) - 3, MAX_WORKERS))


# ---------------------------------------------------------------------------
# set-up: roles, table, warm-up
# ---------------------------------------------------------------------------


def _datagen_job(job: dict) -> dict:
    from perfbench import datagen  # in a CPU-pinned worker, never in the launcher

    return datagen.build_and_upload(job)


def _create_tables(dataset: str, controller_url: str, declared: list[dict]) -> None:
    from perfbench import datagen

    datagen.create_tables(datagen.dataset_module(dataset), controller_url, declared)


def settle(submitted: list) -> tuple[list[dict], list]:
    """(results, refused) of `(job, future)` pairs: a datagen job that raised
    is no reason yet to end the run: it is said, and handed back with what it raised."""
    done, refused = [], []
    for job, future in submitted:
        try:
            done.append(future.result())
        except BrokenProcessPool:
            raise  # a worker died (out of memory): nothing more can be submitted
        except Exception as e:  # whatever the upload client or the controller's answer raised in the worker
            refused.append((job, f"{type(e).__name__}: {str(e)[:500]}"))
            say(f"segment {job['index']}{'' if job['table']['fact'] else ' of ' + job['table']['name']} failed to upload: {refused[-1][1]}")
    return done, refused


def evict_cache(config_dir: Path, keep: int) -> None:
    """Keep the `keep` newest complete seeds of a configuration: a seed's
    cluster directories are gigabytes, and a check draws many seeds."""
    seeds = sorted((d for d in config_dir.iterdir() if d.is_dir()), key=lambda d: d.stat().st_mtime)
    for d in seeds[: max(len(seeds) - keep, 0)]:
        shutil.rmtree(d, ignore_errors=True)


def hosted_error(hosted: dict[str, list[str]], ideal: dict[str, dict], want: list[str], replication: int) -> str | None:
    """What keeps a table from being loaded as its configuration says, or None:
    every segment of `want` is hosted by exactly `replication` different
    servers, every server hosts its equal share of the replicas (the controller
    gives a replica to the server that hosts fewest) and nothing else, and the
    ideal state names every segment with `replication` servers ONLINE."""
    share = len(want) * replication / len(hosted)
    counts = {sid: len(segs) for sid, segs in hosted.items()}
    twice = {sid: sorted({s for s in segs if segs.count(s) > 1}) for sid, segs in hosted.items()}
    holders = {seg: sum(1 for segs in hosted.values() if seg in segs) for seg in want}
    online = {seg: sum(1 for state in ideal.get(seg, {}).values() if state == "ONLINE") for seg in want}
    strangers = sorted({s for segs in hosted.values() for s in segs} - set(want))
    if (
        not any(twice.values()) and not strangers and set(counts.values()) == {share}
        and set(holders.values()) == {replication} and set(online.values()) == {replication} and len(ideal) == len(want)
    ):  # fmt: skip
        return None
    short = sorted(seg for seg in want if holders[seg] != replication or online[seg] != replication)
    return (
        f"servers host {counts} segments, not {share:g} each of {len(want)} x {replication}; the ideal state names {len(ideal)}; "
        f"{len(short)} segments not on {replication} servers (first {short[:3]}); hosted twice by a server {({k: v for k, v in twice.items() if v})}; "
        f"not of the table {strangers[:3]}"
    )


class Cluster:
    """Roles up over a seed's directories, the table loaded, nothing queried yet."""

    def __init__(self, cell: dict, seed: int, rehearsal: bool, log_dir: Path, control: str | None):
        self.config = cell["config"]
        self.chips = cell["entry"]["chips"]
        self.rehearsal = rehearsal
        self.ds = importlib.import_module(f"perfbench.datasets.{self.config['dataset']}")
        self.env = child_env(rehearsal)
        self.roles = Roles(self.env, log_dir)
        self.log_dir = log_dir
        self.control = control
        # the `no-replica` control loads the same configuration at replication 1: a table of its own in the cache
        cfg_dir = CACHE / (self.config["name"] + ("-no-replica" if control == "no-replica" else "") + ("-rehearsal" if rehearsal else ""))
        cfg_dir.mkdir(parents=True, exist_ok=True)
        self.dir = cfg_dir / str(seed)
        self.seed = seed
        # every table of the deployment, the one the templates query (the fact table) last; `sizes` is its segments
        self.tables = tables_mod.declared(self.config, self.ds)
        self.sizes = tables_mod.segment_sizes(self.tables[-1])
        self.servers: dict[str, str] = {}
        self.controls: dict[str, ServerControl] = {}
        self.control_files: dict[str, Path] = {}
        self.timing: dict[str, float] = {}

    def up(self) -> None:
        t0 = time.perf_counter()
        marker = self.dir / "complete.json"
        cached = marker.exists()
        if not cached:
            shutil.rmtree(self.dir, ignore_errors=True)
            evict_cache(self.dir.parent, int(self.config.get("cacheSeeds", 1)) - 1)
            self.dir.mkdir(parents=True)
        controller = self.roles.start(
            "controller",
            ["pinot_tpu.tools.admin", "StartController", "--store-dir", str(self.dir / "store"),
             "--deep-store", str(self.dir / "deep"), "--ha", *(["--cold-start"] if cached else [])],
        )  # fmt: skip
        server_env = {"PINOT_TPU_PALLAS": "1"} if self.rehearsal else {}
        control_files = self.control_files
        server_control = self.control if self.control in ("fast32", "corrupt-metrics") else None
        starts = {}  # role -> (argv, extra env); servers and broker come up side by side
        for i in range(self.config["servers"]):
            sid = f"server_{i}"
            control_files[sid] = self.log_dir / f"{sid}.control"
            control_files[sid].unlink(missing_ok=True)
            pin = chip_pin(i) if (self.chips > 1 and not self.rehearsal) else {}
            starts[sid] = (
                ["perfbench.server_main", "--control-file", str(control_files[sid]),
                 *(["--control", server_control] if server_control else []), "--",
                 "StartServer", "--controller-url", controller, "--server-id", sid,
                 "--data-dir", str(self.dir / f"data_{sid}")],
                {**server_env, **pin},
            )  # fmt: skip
        cache_json = json.dumps(self.config["broker"]["cache"])
        starts["broker"] = (
            ["pinot_tpu.tools.admin", "StartBroker", "--controller-url", controller, "--cache-json", cache_json], {},
        )
        with ThreadPoolExecutor(len(starts)) as pool:
            urls = dict(zip(starts, pool.map(lambda kv: self.roles.start(kv[0], *kv[1]), starts.items())))
        self.broker = urls.pop("broker")
        self.servers = urls
        self.controls = {sid: ServerControl(f) for sid, f in control_files.items()}
        self.controller = controller
        self.timing["roles_up_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._check_platforms()  # waits out a restarted server's 503 while it loads its segments
        if cached:
            self._wait_hosted(timeout=600)
            self.timing["restart_load_s"] = time.perf_counter() - t0
        else:
            self._generate()
            self._wait_hosted(timeout=60)
            marker.write_text(json.dumps({"rows": self.config["rows"], "segments": len(self.sizes), "tables": self.timing["tables"]}))
            self.timing["generate_upload_load_s"] = time.perf_counter() - t0
        self.cached = cached
        self.roles.check_alive()

    def _check_platforms(self) -> None:
        for name, url in (("controller", self.controller), ("broker", self.broker)):
            rt = ready_doc(url)["runtime"]
            require(rt["platform"] == "cpu", f"{name} initialised backend {rt['platform']!r}, not cpu")
        want = "cpu" if self.rehearsal else "tpu"
        self.runtime = {}
        for sid, url in self.servers.items():
            rt = ready_doc(url)["runtime"]
            require(rt["platform"] == want, f"{sid} runs on {rt['platform']!r}, not {want!r}")
            require(rt["deviceCount"] == 1, f"{sid} sees {rt['deviceCount']} devices, not the one chip it serves from")
            if not self.rehearsal:
                require(not rt["pallasInterpret"], f"{sid} would interpret its Pallas kernels")
                require(rt["deviceKind"] in PEAKS, f"device kind {rt['deviceKind']!r} is not in perfbench/peaks.json")
            self.runtime[sid] = rt

    def _generate(self) -> None:
        env_before = dict(os.environ)
        os.environ.clear()
        os.environ.update({**self.env, "JAX_PLATFORMS": "cpu"})  # what the spawned workers inherit
        done: list[dict] = []
        again = 0
        try:
            with ProcessPoolExecutor(workers_for(len(self.sizes)), mp_context=get_context("spawn")) as pool:
                try:  # every table's schema and table config; a declaration the program cannot read ends the run here
                    pool.submit(_create_tables, self.config["dataset"], self.controller, self.tables).result()
                except ValueError as e:
                    raise RunFailure(f"configuration {self.config['name']}: {e}") from None
                for table in self.tables:  # dimension tables first: the fact table's queries may look them up
                    d, n = self._upload(pool, table)
                    done, again = done + d, again + n
            self.timing["segments_uploaded_again"] = again
        finally:
            os.environ.clear()
            os.environ.update(env_before)
        by_table = {t["name"]: [d for d in done if d["table"] == t["name"]] for t in self.tables}
        fact = by_table[self.tables[-1]["name"]]  # the four older keys stay the fact table's
        for k in ("gen_s", "build_s", "upload_s"):
            self.timing[f"datagen_{k}_per_segment"] = float(np.mean([d[k] for d in fact]))
        self.timing["segment_file_bytes"] = float(sum(d["fileBytes"] for d in fact))
        self.timing["tables"] = {}
        for table in self.tables:
            mine = by_table[table["name"]]
            detail = {"rows": table["rows"], "segments": len(mine), "fileBytes": sum(d["fileBytes"] for d in mine),
                      "index_s_per_segment": float(np.mean([d["index_s"] for d in mine]))}  # fmt: skip
            if any(d["starRecords"] for d in mine):
                detail["starRecords_per_segment"] = float(np.mean([sum(d["starRecords"]) for d in mine]))
            self.timing["tables"][table["name"]] = detail

    def _upload(self, pool: ProcessPoolExecutor, table: dict) -> tuple[list[dict], int]:
        """(each segment's job result, how many were sent a second time) of one table."""
        jobs = [
            {"dataset": self.config["dataset"], "seed": self.seed, "index": i, "rows": n, "table": table,
             "config": self.config, "controller_url": self.controller, "out_dir": str(self.dir / "built")}
            for i, n in enumerate(tables_mod.segment_sizes(table))
        ]  # fmt: skip
        # The controller reads, changes and writes a table's ideal state without a lock, so of two uploads
        # that end in the same moment (a) both can be given the same server and (b) one's entry can be
        # overwritten: the server holds that segment, the broker routes past it, and every answer comes a
        # segment short (14 of 15 queried, seed 3260000704; PERF.md, PR 26). Against (a), with several servers
        # the last round, one a server, goes one upload at a time: the controller gives a segment to the
        # server that hosts fewest. Against (b), a segment the ideal state lacks is uploaded again, alone.
        # An upload that the controller refused (its 2 s lease ran out under it and the write was fenced, or
        # the connection broke) is sent again too, once: the parent's client sent it up to three times.
        last = len(self.servers) if len(self.servers) > 1 else 0
        done, refused = settle([(job, pool.submit(_datagen_job, job)) for job in jobs[: len(jobs) - last]])
        for job in jobs[len(jobs) - last :]:
            d, r = settle([(job, pool.submit(_datagen_job, job))])
            done, refused = done + d, refused + r
        if refused:
            self.roles.check_alive()
            say(f"sending segments {[job['index'] for job, _ in refused]} again")
            d, r = settle([(job, pool.submit(_datagen_job, job)) for job, _ in refused])
            require(not r, f"{len(r)} segments failed to upload twice, first: {r[0][1] if r else ''}")
            done += d
        routed = self.ideal_state(table["name"])
        lost = [job for job in jobs if f"{table['name']}_{job['index']}" not in routed]
        if lost:
            say(f"the ideal state lacks segments {[job['index'] for job in lost]} of {len(jobs)}: uploading them again")
        done += [pool.submit(_datagen_job, job).result() for job in lost]
        return done, len(lost) + len(refused)

    def ideal_state(self, table: str | None = None) -> dict:
        """The controller's ideal state of a table (the fact table's unless named): what the broker routes by."""
        return http_json(f"{self.controller}/tables/{table or self.ds.TABLE}/idealstate")

    def hosted(self, table: str) -> dict[str, list[str]]:
        """The segments of a table that each server says it hosts."""
        return {sid: http_json(f"{url}/segments/{table}") for sid, url in self.servers.items()}

    def _wait_hosted(self, timeout: float) -> None:
        """Until every table is loaded as the configuration says (`hosted_error`):
        one at `"everyServer"` is then on every server, whole."""
        deadline = time.monotonic() + timeout
        for table in self.tables:
            want = sorted(tables_mod.segment_names(table))
            while True:
                wrong = hosted_error(self.hosted(table["name"]), self.ideal_state(table["name"]), want, int(table["replication"]))
                if wrong is None:
                    break
                self.roles.check_alive()
                require(time.monotonic() < deadline, wrong if table["fact"] else f"table {table['name']}: {wrong}")
                time.sleep(0.25)

    def restart_server(self, sid: str, timeout: float) -> None:
        """A killed server started again as a supervisor would: the same argv,
        environment, chip pin, server id and data directory; a control socket of its own."""
        self.control_files[sid].unlink(missing_ok=True)
        self.servers[sid] = self.roles.restart(sid, timeout)
        self.controls[sid] = ServerControl(self.control_files[sid], timeout)

    def share_of(self, sid: str) -> dict[str, set[str]]:
        """The segments the ideal state gives a server, table by table."""
        return {
            t["name"]: {seg for seg, replicas in self.ideal_state(t["name"]).items() if replicas.get(sid) == "ONLINE"}
            for t in self.tables
        }

    def snapshot(self) -> dict[str, dict]:
        """Counters of each server's own endpoints."""
        snap = {}
        for sid, url in self.servers.items():
            snap[sid] = {
                "compile_requests": ready_doc(url)["runtime"]["compileCache"]["requests"],
                "fused_calls": kernel_calls(url, "query.fused_packed"),
                "device_fallbacks": metric_total(url, "server.deviceFallbacks"),
            }
        return snap

    def ask_servers(self, per_server: dict | None = None, **req) -> dict[str, dict]:
        """One request to every server's control socket at the same time, a
        thread a server: `req`, with `per_server[sid]` on top for that server."""
        with ThreadPoolExecutor(len(self.controls)) as pool:
            asked = {sid: pool.submit(ctl.ask, **req, **(per_server or {}).get(sid, {})) for sid, ctl in self.controls.items()}
            return {sid: f.result() for sid, f in asked.items()}

    def trace_start(self, trace_dir: Path) -> dict[str, dict]:
        """Every server starts its profiler, each into `<trace_dir>/<server id>`."""
        return self.ask_servers(cmd="trace-start", per_server={sid: {"dir": str(trace_dir / sid)} for sid in self.controls})

    def trace_stop(self) -> dict[str, dict]:
        return self.ask_servers(cmd="trace-stop")

    def memstats(self) -> dict:
        peak, kinds, platforms = 0, set(), set()
        for reply in self.ask_servers(cmd="memstats").values():
            for d in reply["devices"]:
                kinds.add(d["kind"])
                platforms.add(d["platform"])
                ms = d["memory_stats"]
                peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
        require(len(kinds) == 1 and len(platforms) == 1, f"servers on different devices: {kinds} {platforms}")
        return {"platform": platforms.pop(), "kind": kinds.pop(), "peak": peak}


def warm_up(cluster: Cluster, traffic: dict, seed: int, trace_dir: Path | None = None) -> None:
    """The mix's `warmup.statements` as they stand, where it lists any (for
    a mix whose drawn queries each reach a part of the table: the program
    stages a segment at the first query that reaches it, and statements that
    between them reach every segment keep those first touches out of the
    window); then every template of the mix, with parameters of its own
    stream, one after the other (where every query reaches every segment the
    first of them stages the table onto the chip), then the mix itself for a
    moment through the load generator. A traced
    run (`trace_dir`) takes a first, thrown-away trace over that moment: the
    profiler's first start and stop in a new checkout on a new machine froze
    server, broker and launcher for 4 s and 17 s (PERF.md, PR 23), and
    nothing may warm up inside the window."""
    templates = cluster.ds.TEMPLATES
    rng = np.random.default_rng([seed, 777_001])
    client = loadgen.Client(cluster.broker, int(traffic["warmup"].get("timeoutMs", 900_000)))
    try:
        for i, sql in enumerate(traffic["warmup"].get("statements", [])):
            t0 = time.perf_counter()
            doc = client.send(sql)
            require(not doc.get("exceptions"), f"warm-up statement {i} failed: {doc.get('exceptions')}")
            say(f"warm-up statement {i}: {time.perf_counter() - t0:.2f} s")
        for name in traffic["templates"]:
            for _ in range(int(traffic["warmup"]["runsPerTemplate"])):
                t0 = time.perf_counter()
                doc = client.send(templates[name].render(templates[name].draw(rng)))
                require(not doc.get("exceptions"), f"warm-up of {name} failed: {doc.get('exceptions')}")
                say(f"warm-up {name}: {time.perf_counter() - t0:.2f} s")
    finally:
        client.close()
    ramp = float(traffic["warmup"].get("rampSeconds", 0))
    t0 = time.perf_counter()
    if trace_dir is not None:
        cluster.trace_start(trace_dir)
    if ramp > 0:
        qs = loadgen.draw_queries(templates, traffic["templates"], rng, 10_000)
        ramped, _ = loadgen.run_window(cluster.broker, traffic, qs, ramp, rng)
        bad = [s for s in ramped if s.error]
        require(not bad, f"ramp: {len(bad)} of {len(ramped)} queries failed, first: {bad[0].error if bad else ''}")
        say(f"ramp {ramp:g} s: {len(ramped)} queries, slowest {max(s.latency_ms for s in ramped):.0f} ms")
    if trace_dir is not None:
        t1 = time.perf_counter()
        cluster.trace_stop()
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"thrown-away trace: {t1 - t0:.1f} s open, stop took {time.perf_counter() - t1:.1f} s")


# ---------------------------------------------------------------------------
# the window, the check, the metrics
# ---------------------------------------------------------------------------


class Faults(threading.Thread):
    """Carries out a traffic file's `faults` on the roles' OS processes, each
    at its `atSeconds` from the window's start: `kill` is SIGKILL to a server's
    process, `restart` the same role started again at once, as a supervisor
    would, and watched until it is ready and hosts its share again. Every wait
    ends at the recovery deadline; what went wrong is kept for the launcher
    (`error`), which ends the run with it. In a traced run the first fault
    waits for the tracer to have stopped: a killed server writes no trace."""

    def __init__(self, cluster: Cluster, faults: list[dict], loss: loss_mod.Loss):
        super().__init__(name="faults", daemon=True)
        self.cluster, self.faults, self.loss = cluster, faults, loss
        self.t0: float | None = None
        self.after: threading.Thread | None = None
        self.error: Exception | None = None
        self.over = threading.Event()  # nothing more will happen: done, or failed

    def begin(self, t0: float, after: threading.Thread | None) -> None:
        """The window opened at `t0`; `after` is the tracer's thread in a traced run."""
        self.t0, self.after = t0, after
        self.start()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def left(self) -> float:
        """Seconds to the recovery deadline (from the kill; before it, from its due time)."""
        since = self.loss.kill_s if self.loss.kill_s is not None else min(f["atSeconds"] for f in self.faults)
        return max(since + self.loss.deadline_s - self.now(), 1.0)

    def run(self) -> None:
        try:
            for fault in sorted(self.faults, key=lambda f: f["atSeconds"]):
                time.sleep(max(fault["atSeconds"] - self.now(), 0))
                if self.after is not None:
                    self.after.join(self.left())
                    require(not self.after.is_alive(), "the trace had not stopped by the recovery deadline")
                getattr(self, fault["action"])(f"server_{fault['server']}")
        except Exception as e:  # whatever a role's start, an HTTP call or a requirement raised: the launcher's to report
            self.error = e
        finally:
            self.over.set()

    def kill(self, sid: str) -> None:
        self.loss.kill_s = self.cluster.roles.kill(sid) - self.t0
        say(f"fault: killed {sid} {self.loss.kill_s:.3f} s in, reaped {self.now():.3f} s in")

    def restart(self, sid: str) -> None:
        cluster, loss = self.cluster, self.loss
        loss.restart_s = self.now()
        cluster.restart_server(sid, self.left())
        url = cluster.servers[sid]
        rt = ready_doc(url, self.left())["runtime"]
        loss.ready_s = self.now()
        say(f"fault: {sid} started again {loss.restart_s:.3f} s in, ready {loss.ready_s:.3f} s in at {url} on {rt['platform']}")
        require(rt["platform"] == cluster.runtime[sid]["platform"], f"{sid} came back on {rt['platform']!r}")
        share = cluster.share_of(sid)  # table by table: a dimension table it kept whole comes back whole
        n_share = sum(len(segs) for segs in share.values())
        hosts: dict[str, set[str]] = {}

        def hosts_its_share() -> bool:
            nonlocal hosts
            hosts = {table: set(http_json(f"{url}/segments/{table}")) for table in share}
            return n_share > 0 and all(share[table] <= hosts[table] for table in share)

        if not self.wait_for(hosts_its_share, lambda: f"{sid} hosts {sum(len(h) for h in hosts.values())} of its {n_share} segments"):
            return
        loss.hosted_s = self.now()
        timers = {k: v for k, v in http_json(f"{url}/metrics?format=json").items() if "segmentLoad" in k}
        loss.server_load_timers = timers
        say(f"fault: {sid} hosts its {n_share} segments again {loss.hosted_s:.3f} s in; its load timers {json.dumps(timers)}")
        # its own count of fused device programs, from what it read when it hosted its share (a server that
        # is still loading runs the segments it has of a leg whose answer the broker then throws away)
        base = kernel_calls(url, "query.fused_packed")
        if not self.wait_for(lambda: kernel_calls(url, "query.fused_packed") != base, lambda: f"{sid} has served no query"):
            return
        loss.served_s = self.now()
        say(f"fault: {sid} serves again {loss.served_s:.3f} s in")

    def wait_for(self, done, where) -> bool:
        """Polls `done()` ten times a second. False, with `where()` said, where the recovery deadline
        comes first: not a failed run, but one that is not `correct` and says so in its line."""
        while not done():
            if self.left() <= 1.0:
                say(f"fault: {where()} at the recovery deadline, {self.now():.1f} s in")
                return False
            self.cluster.roles.check_alive()
            time.sleep(0.1)
        return True

    def tail_may_end(self) -> bool:
        """Whether the traffic after the window may stop: the restarted server has
        served again and `confirm_s` of traffic have followed (no later answer may
        lose a leg again); or the recovery deadline has passed; or this thread has given up."""
        now, loss = self.now(), self.loss
        if self.error is not None or (loss.kill_s is not None and now >= loss.kill_s + loss.deadline_s):
            return True
        return loss.served_s is not None and now >= loss.served_s + loss.confirm_s


class StallWatch(threading.Thread):
    """Sleeps 50 ms at a time through the window and keeps by how much each
    sleep overshot: a launcher that was not scheduled for seconds (a frozen
    machine) is not a slow server, and the run's lines should tell them apart."""

    def __init__(self):
        super().__init__(name="stall-watch", daemon=True)
        self.stop, self.worst_ms, self.at = threading.Event(), 0.0, 0.0
        self.t0 = time.perf_counter()

    def run(self) -> None:
        while not self.stop.is_set():
            t = time.perf_counter()
            time.sleep(0.05)
            over = (time.perf_counter() - t - 0.05) * 1e3
            if over > self.worst_ms:
                self.worst_ms, self.at = over, t - self.t0


def percentile(values: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def run_reference(cluster: Cluster, chosen: list, control: bool) -> list[list[list]]:
    """The reference's rows for each chosen query, over every segment, in worker processes."""
    wanted = [(s.template, s.params) for s in chosen]
    cfg = cluster.config
    with ProcessPoolExecutor(workers_for(len(cluster.sizes)), mp_context=get_context("spawn")) as pool:
        futures = [
            pool.submit(check.reference_partials, cfg["dataset"], cluster.seed, i, n, cfg, wanted, control)
            for i, n in enumerate(cluster.sizes)
        ]
        per_segment = [f.result() for f in futures]
    vocabs = cluster.ds.vocabs(cfg)
    out = []
    for j, s in enumerate(chosen):
        merged = refeval.merge([seg[j] for seg in per_segment])
        out.append(refeval.finish(cluster.ds.TEMPLATES[s.template].spec, merged, vocabs))
    return out


def check_answers(cluster: Cluster, samples: list, traffic: dict, seed: int, control: bool,
                  loss: loss_mod.Loss | None = None) -> tuple[bool, int, dict]:  # fmt: skip
    """(correct, failed, compared): shape of every answer (by its phase, where
    a server was lost), full comparison of the sample; `compared` is each
    number's worst over the sample beside its limit."""
    cfg = cluster.config
    failed = 0
    for s in samples:
        if s.error is None:
            s.error = check.shape_error(
                s, cfg["servers"], cfg["rows"], int(traffic.get("limit", 1000)), loss_mod.phase_of(s, loss), int(cfg["replication"])
            )
        if s.error is not None:
            failed += 1
            if failed <= 50:
                say(f"failed #{s.index} {s.template} sent {s.sent:.3f} s in: {s.error}")
    rng = np.random.default_rng([seed, 777_002])
    chosen = check.pick_sample(samples, int(traffic["check"]["perTemplate"]), rng, loss)
    t0 = time.perf_counter()
    wants = run_reference(cluster, chosen, control)
    correct = True
    compared: dict[str, dict] = {}
    for s, want in zip(chosen, wants):
        spec = cluster.ds.TEMPLATES[s.template].spec
        numbers = check.compare_rows(spec, s.doc["resultTable"]["rows"], want)
        ok, lines, limits = check.judge(numbers, spec.exact, float(cfg["guarantees"]["doubleSumRelTolerance"]))
        for k, limit in limits.items():
            compared[k] = {"value": max(compared.get(k, {}).get("value", 0.0), numbers[k]), "limit": limit}
        phase = loss_mod.phase_of(s, loss)
        say(f"check #{s.index} {s.template}{' ' + phase if phase else ''} rows={len(want)}: {' '.join(lines)} -> {'ok' if ok else 'WRONG'}")
        if not ok:
            correct = False
            failed += 1
            meta = {k: v for k, v in s.doc.items() if k != "resultTable"}
            say(f"WRONG #{s.index}: due {s.due:.3f} s, sent {s.sent:.3f}, done {s.done:.3f}; {s.sql}")
            say(f"WRONG #{s.index}: got {json.dumps(s.doc['resultTable']['rows'][:5])} want {json.dumps(want[:5])}; {json.dumps(meta)[:1500]}")
    say(f"checked {len(chosen)} of {len(samples)} answers in full in {time.perf_counter() - t0:.1f} s; worst {compared}")
    if loss is not None:
        inflight = [s for s in samples if loss_mod.phase_of(s, loss) == loss_mod.INFLIGHT]
        compared["inflight_at_kill_not_compared"] = {"value": sum(1 for s in inflight if s.error is None and s not in chosen), "limit": 0}
        say(f"in flight at the kill: {len(inflight)} queries, {sum(1 for s in inflight if s.error is not None)} of them failed, the others compared in full")
    compared["failed_queries"] = {"value": failed, "limit": len(samples) // 100}
    return correct, failed, compared


def reduce_trace(trace_dir: Path, chips: int, log_dir: Path, *more: str) -> dict:
    """trace_reduce in a child pinned to the CPU: reading a trace needs jax, not a chip."""
    env = {**child_env(True), "TPU_LOG_DIR": "disabled"}
    with open(log_dir / "trace_reduce.stderr.log", "w") as err:
        p = subprocess.run(
            [sys.executable, "-m", "perfbench.trace_reduce", str(trace_dir), "--chips", str(chips), *more],
            stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, stderr=err, text=True, env=env, cwd=ROOT, timeout=600,
        )  # fmt: skip
    if p.returncode != 0:
        raise RunFailure(f"trace_reduce failed (rc={p.returncode}): {(log_dir / 'trace_reduce.stderr.log').read_text()[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def sweep(cluster: Cluster, traffic: dict, args) -> None:
    """The knee, once: a window at each rate over one loaded cluster. A rate is
    sustained when the backlog does not grow: what was issued was answered
    inside the window, and the second half's latencies are no worse than the first's."""
    say("sweep: rate issued inside p50_ms p95_ms p50_first_half p50_second_half late_p95_ms")
    for k, rate in enumerate(float(r) for r in args.sweep_rates.split(",")):
        mix = {**traffic, "loop": {**traffic["loop"], "rate": rate}}
        rng = np.random.default_rng([args.seed, 777_100 + k])
        qs = loadgen.draw_queries(cluster.ds.TEMPLATES, mix["templates"], rng, int(mix["maxQueries"]))
        samples, _ = loadgen.run_window(cluster.broker, mix, qs, args.seconds, rng)
        good = [s for s in samples if s.error is None]
        lat = [s.latency_ms for s in good] or [float("nan")]
        first = [s.latency_ms for s in good if s.due < args.seconds / 2] or [float("nan")]
        second = [s.latency_ms for s in good if s.due >= args.seconds / 2] or [float("nan")]
        inside = sum(1 for s in good if s.done <= args.seconds)
        late = percentile([(s.sent - s.due) * 1e3 for s in samples], 95)
        say(f"sweep: {rate:g} {len(samples)} {inside} {percentile(lat, 50):.1f} {percentile(lat, 95):.1f} "
            f"{percentile(first, 50):.1f} {percentile(second, 50):.1f} {late:.2f} failed={len(samples) - len(good)}")  # fmt: skip
        cluster.roles.check_alive()


def main(argv: list[str] | None = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true", help="CPU, tiny scale: checks the harness, not the chip")
    ap.add_argument("--sweep-rates", default=None,
                    help="open-loop cells: a window at each of these rates (comma-separated), a table, no result line")  # fmt: skip
    ap.add_argument("--traffic", default=None, help="a mix of perfbench/traffic/ in place of the cell's (diagnosis; no driver run)")
    ap.add_argument("--check-per-template", type=int, default=None,
                    help="compare this many answers of each template in full, in place of the mix's (diagnosis; no driver run)")  # fmt: skip
    ap.add_argument("--entries", default=None,
                    help="a pending cell's entries (perfbench/pending/<cell>.json) laid over BENCHMARK.json (diagnosis; no driver run)")  # fmt: skip
    ap.add_argument("--keep-trace", action="store_true", help="keep the .xplane.pb in the log directory")
    ap.add_argument("--control", choices=["fast32", "corrupt-metrics", "float32-reference", "no-replica"], default=None,
                    help="correctness controls (PERF.md); a run with one has to print correct=false")  # fmt: skip
    args = ap.parse_args(argv)
    trace = bool(args.trace)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # so that `finally` stops the roles

    manifest = load_manifest()
    if args.entries:
        manifest = with_entries(manifest, json.loads((ROOT / args.entries).read_text()))
    cell = load_cell(manifest, args.workload)
    if args.traffic:
        cell["traffic"] = json.loads((BENCH / "traffic" / f"{args.traffic}.json").read_text())
    traffic = cell["traffic"]
    if args.check_per_template is not None:
        traffic["check"] = {**traffic["check"], "perTemplate": args.check_per_template}
    if args.rehearsal:
        tables_mod.rehearse(cell["config"])
    if args.control == "no-replica":  # the cell's fault schedule over a table that keeps one copy of each segment
        require(bool(traffic.get("faults")), "--control no-replica needs a cell whose traffic has a fault schedule")
        cell["config"]["replication"] = 1
    log_dir = OUT / args.workload / str(args.seed)
    shutil.rmtree(log_dir, ignore_errors=True)
    log_dir.mkdir(parents=True)

    cluster = Cluster(cell, args.seed, args.rehearsal, log_dir, args.control)
    templates = cluster.ds.TEMPLATES
    missing = [t for t in traffic["templates"] if t not in templates]
    require(not missing, f"traffic names templates {missing} that dataset {cell['config']['dataset']} lacks")
    trace_dir = log_dir / "trace"
    trace_state: dict = {}
    try:
        cluster.up()
        warm_up(cluster, traffic, args.seed, log_dir / "trace_warm" if trace else None)
        if args.sweep_rates:
            sweep(cluster, traffic, args)
            return 0
        rng = np.random.default_rng([args.seed, 777_003])
        queries = loadgen.draw_queries(templates, traffic["templates"], rng, int(traffic["maxQueries"]))
        before = cluster.snapshot()
        setup_s = time.perf_counter() - t_start
        say(f"set-up {setup_s:.1f} s: {json.dumps(cluster.timing)} cached={cluster.cached}")

        def tracer(t0: float) -> None:
            """Traces a steady sub-window: starts `after` seconds in, for `seconds`."""
            tw = traffic["trace"]
            after = min(float(tw["afterSeconds"]), args.seconds / 4)
            length = min(float(tw["seconds"]), args.seconds / 2)
            time.sleep(max(t0 + after - time.perf_counter(), 0))
            trace_state["t_start_req"] = time.perf_counter() - t0
            started = cluster.trace_start(trace_dir)
            trace_state["t_on"] = time.perf_counter() - t0
            time.sleep(length)
            trace_state["t_off"] = time.perf_counter() - t0
            stopped = cluster.trace_stop()
            trace_state["t_stopped"] = time.perf_counter() - t0
            # each server's own clock readings (one host, one clock): how far apart the servers' traces began and ended
            for key, replies in (("servers_on_spread_s", started), ("servers_off_spread_s", stopped)):
                at = [r["at"] for r in replies.values()]
                trace_state[key] = max(at) - min(at)

        tracer_thread = None
        # a traffic file with `faults` has a server killed and started again inside the window, and the
        # same traffic go on after it, as a tail, until the cluster has recovered; one without starts neither
        schedule = traffic.get("faults") or []
        loss = faults = None
        if schedule:
            require(len({f["server"] for f in schedule}) == 1, "a fault schedule names one server")
            recovery = traffic["recovery"]
            loss = loss_mod.Loss(
                f"server_{schedule[0]['server']}", float(recovery["deadlineSeconds"]), float(recovery.get("confirmSeconds", loss_mod.Loss.confirm_s))
            )
            faults = Faults(cluster, schedule, loss)

        def on_start(t0: float) -> None:
            nonlocal tracer_thread
            if trace:
                tracer_thread = threading.Thread(target=tracer, args=(t0,), name="tracer", daemon=True)
                tracer_thread.start()
            if faults is not None:
                faults.begin(t0, tracer_thread)

        watch = StallWatch()
        watch.start()
        samples, t0 = loadgen.run_window(cluster.broker, traffic, queries, args.seconds, rng, on_start)
        watch.stop.set()
        say(f"launcher's worst oversleep in the window {watch.worst_ms:.0f} ms, {watch.at:.1f} s in")
        tail: list = []
        if faults is not None:
            if not faults.tail_may_end():
                tail, tail_t0 = loadgen.run_window(cluster.broker, traffic, queries[len(samples) :], faults.left(), rng, until=faults.tail_may_end)
                for s in tail:  # onto the window's clock
                    s.due, s.sent, s.done = (x + tail_t0 - t0 for x in (s.due, s.sent, s.done))
                say(f"tail: {len(tail)} queries more until {faults.now():.1f} s in")
            faults.over.wait(faults.left() + 5)
        with open(log_dir / "samples.jsonl", "w") as f:
            for s in samples + tail:
                doc = s.doc or {}
                f.write(json.dumps({"i": s.index, "t": s.template, "due": s.due, "sent": s.sent, "done": s.done,
                                    "brokerMs": doc.get("timeUsedMs"), "error": s.error,
                                    "servers": [doc.get("numServersQueried"), doc.get("numServersResponded")]}) + "\n")  # fmt: skip
        if faults is not None:
            require(faults.error is None, f"the fault schedule failed: {type(faults.error).__name__}: {faults.error}")
            require(faults.over.is_set(), "the fault schedule had not ended at the recovery deadline")
        if tracer_thread is not None:
            tracer_thread.join(timeout=600)
            require(not tracer_thread.is_alive() and "t_stopped" in trace_state, "the trace never stopped")
        cluster.roles.check_alive()
        after = cluster.snapshot()
        mem = cluster.memstats()
    finally:
        cluster.roles.stop_all()

    # -- the device path was the path, on every server ---------------------------
    # a server the schedule killed counts from nothing after its restart; that it serves again is recovery's to show
    lost = {loss.server} if loss is not None else set()
    require(len(samples) > 0, "no query was issued in the window")
    per_server = {
        sid: {k: after[sid][k] - (0 if sid in lost else before[sid][k]) for k in ("compile_requests", "fused_calls")} for sid in after
    }
    for sid in after:
        require(after[sid]["device_fallbacks"] == 0, f"{after[sid]['device_fallbacks']} queries fell back to the host executor on {sid}")
        if sid not in lost:
            require(per_server[sid]["fused_calls"] > 0, f"no fused device program ran on {sid} in the window; the servers counted {per_server}")
    # what the readers get: the counters summed over the servers, a restarted server's from nothing
    before = {k: sum(v[k] for sid, v in before.items() if sid not in lost) for k in next(iter(before.values()))}
    after = {k: sum(v[k] for v in after.values()) for k in next(iter(after.values()))}
    want_platform = "cpu" if args.rehearsal else "tpu"
    require(mem["platform"] == want_platform, f"servers report platform {mem['platform']!r}")

    # -- answers ----------------------------------------------------------------
    if loss is not None:
        loss.recovered_s = loss_mod.recovery_instant(samples + tail, loss.served_s)
        say(f"loss: {json.dumps({k: v for k, v in vars(loss).items() if k != 'server_load_timers'})}")
    answers_ok, failed, compared = check_answers(cluster, samples + tail, traffic, args.seed, args.control == "float32-reference", loss)
    correct = check.run_correct(answers_ok, failed, len(samples) + len(tail))
    if answers_ok and not correct:
        say(f"not correct: {failed} of {len(samples) + len(tail)} queries failed, more than 1 in 100 (the traffic is chosen so that none does)")
    if loss is not None:
        ends = max(s.done for s in samples + tail)
        took = (loss.recovered_s if loss.recovered_s is not None else ends) - loss.kill_s
        compared["not_recovered"] = {"value": int(loss.recovered_s is None), "limit": 0}
        compared["recovered_s"] = {"value": took, "limit": loss.deadline_s}
        if loss.recovered_s is None or took > loss.deadline_s:
            correct = False
            say(f"not correct: {loss.server} killed {loss.kill_s:.1f} s in, hosting again {loss.hosted_s} s in, serving again "
                f"{loss.served_s} s in, not recovered {ends - loss.kill_s:.1f} s after the kill")  # fmt: skip

    # -- metrics ----------------------------------------------------------------
    good = [s for s in samples if s.error is None]
    require(len(good) > 0, "no query of the window answered")
    lat = [s.latency_ms for s in good]
    in_window = [s for s in good if s.done <= args.seconds]
    values = {
        "setup_s": setup_s,
        "query_p50_ms": percentile(lat, 50),
        "query_p95_ms": percentile(lat, 95),
        "queries_per_s": len(in_window) / args.seconds,
    }
    if loss is not None:
        values["recovered_s"] = took
    say(
        f"window {args.seconds:g} s: issued {len(samples)}, answered {len(good)}, completed inside {len(in_window)}, "
        f"beyond p95 {sum(1 for x in lat if x > values['query_p95_ms'])} samples; "
        f"completed/s {len(in_window) / args.seconds:.3f}; "
        f"generator lateness p95 {percentile([(s.sent - s.due) * 1e3 for s in samples], 95):.3f} ms"
    )
    for name in sorted({s.template for s in good}):
        tl = [s.latency_ms for s in good if s.template == name]
        say(f"  {name}: n={len(tl)} p50={percentile(tl, 50):.1f} ms max={max(tl):.1f} ms")

    run = {
        "samples": samples, "good": good, "seconds": args.seconds, "before": before, "after": after,
        "trace": None, "trace_window": None, "traffic": traffic, "config": cell["config"], "tail": tail, "loss": loss,
    }  # fmt: skip
    device = {"platform": mem["platform"], "kind": mem["kind"], "count": cell["entry"]["chips"],
              "memory_peak_bytes": mem["peak"]}  # fmt: skip
    breakdown = None
    if trace:
        if args.rehearsal:
            # a CPU trace has no device plane: the rehearsal reduces the trace recorded on the chip,
            # and reads its own servers' traces for the program's annotations only
            reduced = reduce_trace(BENCH / "tests" / "fixtures" / "v5e_window.xplane.pb", 1, log_dir, "--spans-from", str(trace_dir))
        else:
            reduced = reduce_trace(trace_dir, cell["entry"]["chips"], log_dir)
        (log_dir / "trace_reduced.json").write_text(json.dumps(reduced, indent=1))
        run["trace"] = reduced
        run["trace_window"] = (trace_state["t_on"], trace_state["t_off"])
        device["window_s"], device["busy_s"] = reduced["window_s"], reduced["busy_s"]
        # a gap is named by the innermost `server.*` span of its own server that held most of it
        # (tools/gap_attribution.py); a trace without the program's annotations leaves its gaps unnamed
        named = reduced.get("named_gaps") or [["unattributed", g] for g in reduced["idle_gaps_s"]]
        breakdown = {"device_ops": [[n, t] for n, t in reduced["ops"][:10]], "idle_gaps": named[:10]}
        say(f"trace: {json.dumps(trace_state)} window_s={reduced['window_s']:.4f} busy_s={reduced['busy_s']:.4f}")
        for chip in reduced["chips"]:
            say(f"  chip {chip['plane']}: window_s={chip['window_s']:.4f} busy_s={chip['busy_s']:.4f}")
        for name, t, n in reduced["modules"][:8]:
            say(f"  module {name}: {t:.4f} s in {n} launches")
        if trace_dir.exists():
            files = find_xplanes(trace_dir)
            say(f"trace files: {[[sid, f.stat().st_size] for sid, f in files]}")
            require(len(files) == len(cluster.controls), f"{len(files)} trace files for {len(cluster.controls)} servers")
            if args.keep_trace:  # one file a server, each under its server's name: what trace_reduce and the tools read
                for sid, f in files:
                    (log_dir / "kept_trace" / sid).mkdir(parents=True)
                    shutil.copy(f, log_dir / "kept_trace" / sid / f.name)
        shutil.rmtree(trace_dir, ignore_errors=True)  # hundreds of MB; the reduction is kept
        for m in metrics_of(manifest, "per_layer", args.workload):
            reader = importlib.import_module(f"perfbench.layer_metrics.{m['name']}")
            got = reader.read(run)
            if got is not None:
                values[m["name"]] = float(got)

    extra = {}
    if trace:  # measured under the profiler: beside the plain run's, the tracing overhead
        extra["end_to_end_under_trace"] = {k: values[k] for k in ("query_p50_ms", "query_p95_ms", "queries_per_s", "setup_s", "recovered_s") if k in values}
    if args.rehearsal:
        extra["rehearsal"] = True
    if args.control:
        extra["control"] = args.control
    if loss is not None:  # the instants the schedule really acted at, seconds from the window's start, and what each server counted
        extra.update(fault_at_s=loss.kill_s, restart_ready_s=loss.ready_s, loss=vars(loss), tail_queries=len(tail), servers=per_server)
    extra["compared"] = compared  # each number of the check beside its limit: the line's last key
    line = result_line.build(
        manifest, args.workload, trace, correct=correct, attempted=len(samples) + len(tail), failed=failed,
        values=values, device=device, breakdown=breakdown, extra=extra,
    )  # fmt: skip
    try:
        text = result_line.validate(line, manifest, args.workload, trace, cell["entry"]["chips"])
    except result_line.InvalidLine as e:
        print(f"perfbench: the result line is invalid: {e}\n{line!r}", file=sys.stderr)
        return 3
    (log_dir / "result.json").write_text(text)
    for name, c in compared.items():  # and stderr's last lines
        print(f"perfbench: compared {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except RunFailure as e:
        print(f"perfbench: run failed: {e}", file=sys.stderr)
        code = 2
    sys.stdout.flush()
    os._exit(code)  # nothing may print after the line: no atexit, no late thread
