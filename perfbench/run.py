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
from multiprocessing import get_context
from pathlib import Path

if __package__ in (None, ""):  # run as a script: make `perfbench` importable
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from perfbench import check, loadgen, refeval, result_line
from perfbench.cluster import (
    Roles, RunFailure, ServerControl, child_env, chip_pin, http_json, kernel_calls, metric_total, ready_doc, require,
)  # fmt: skip
from perfbench.manifest import BENCH, ROOT, load_cell, load_manifest, metrics_of

OUT = ROOT / "perfbench_out"
CACHE = BENCH / ".cache"
PEAKS = json.loads((BENCH / "peaks.json").read_text())


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


#: worker processes for datagen and for the reference. Each holds one generated
#: segment (over a gigabyte at 4M rows x 30 columns) beside a server that holds
#: the whole table; ten of them met the one-chip machine's 40 GiB (my chip run, PR 23)
MAX_WORKERS = 5


def workers_for(n_jobs: int) -> int:
    return max(1, min(n_jobs, (os.cpu_count() or 2) - 3, MAX_WORKERS))


# ---------------------------------------------------------------------------
# set-up: roles, table, warm-up
# ---------------------------------------------------------------------------


def _datagen_job(job: dict) -> dict:
    from perfbench import datagen  # in a CPU-pinned worker, never in the launcher

    return datagen.build_and_upload(job)


def _create_table(dataset: str, controller_url: str, replication: int) -> None:
    from perfbench import datagen

    datagen.create_table(datagen.dataset_module(dataset), controller_url, replication)


def segment_plan(config: dict) -> list[int]:
    rows, seg = config["rows"], config["segmentRows"]
    require(rows % seg == 0, f"rows {rows} not a multiple of segmentRows {seg}")
    return [seg] * (rows // seg)


def evict_cache(config_dir: Path, keep: int) -> None:
    """Keep the `keep` newest complete seeds of a configuration: a seed's
    cluster directories are gigabytes, and a check draws many seeds."""
    seeds = sorted((d for d in config_dir.iterdir() if d.is_dir()), key=lambda d: d.stat().st_mtime)
    for d in seeds[: max(len(seeds) - keep, 0)]:
        shutil.rmtree(d, ignore_errors=True)


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
        cfg_dir = CACHE / (self.config["name"] + ("-rehearsal" if rehearsal else ""))
        cfg_dir.mkdir(parents=True, exist_ok=True)
        self.dir = cfg_dir / str(seed)
        self.seed = seed
        self.sizes = segment_plan(self.config)
        self.servers: dict[str, str] = {}
        self.controls: dict[str, ServerControl] = {}
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
        control_files = {}
        starts = {}  # role -> (argv, extra env); servers and broker come up side by side
        for i in range(self.config["servers"]):
            sid = f"server_{i}"
            control_files[sid] = self.log_dir / f"{sid}.control"
            control_files[sid].unlink(missing_ok=True)
            pin = chip_pin(i) if (self.chips > 1 and not self.rehearsal) else {}
            starts[sid] = (
                ["perfbench.server_main", "--control-file", str(control_files[sid]),
                 *(["--control", self.control] if self.control else []), "--",
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
            marker.write_text(json.dumps({"rows": self.config["rows"], "segments": len(self.sizes)}))
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
        try:
            jobs = [
                {"dataset": self.config["dataset"], "seed": self.seed, "index": i, "rows": n,
                 "config": self.config, "controller_url": self.controller, "out_dir": str(self.dir / "built")}
                for i, n in enumerate(self.sizes)
            ]  # fmt: skip
            with ProcessPoolExecutor(workers_for(len(jobs)), mp_context=get_context("spawn")) as pool:
                pool.submit(_create_table, self.config["dataset"], self.controller, self.config["replication"]).result()
                done = list(pool.map(_datagen_job, jobs))
        finally:
            os.environ.clear()
            os.environ.update(env_before)
        for k in ("gen_s", "build_s", "upload_s"):
            self.timing[f"datagen_{k}_per_segment"] = float(np.mean([d[k] for d in done]))
        self.timing["segment_file_bytes"] = float(sum(d["fileBytes"] for d in done))

    def _wait_hosted(self, timeout: float) -> None:
        want = sorted(f"{self.ds.TABLE}_{i}" for i in range(len(self.sizes)))
        deadline = time.monotonic() + timeout
        while True:
            hosted = sorted(s for url in self.servers.values() for s in http_json(f"{url}/segments/{self.ds.TABLE}"))
            if hosted == want:
                return
            self.roles.check_alive()
            require(time.monotonic() < deadline, f"servers host {len(hosted)} of {len(want)} segments")
            time.sleep(0.25)

    def snapshot(self) -> dict:
        """Counters of the roles' own endpoints, summed over the servers."""
        snap = {"compile_requests": 0, "fused_calls": 0, "device_fallbacks": 0}
        for url in self.servers.values():
            rt = ready_doc(url)["runtime"]
            snap["compile_requests"] += rt["compileCache"]["requests"]
            snap["fused_calls"] += kernel_calls(url, "query.fused_packed")
            snap["device_fallbacks"] += metric_total(url, "server.deviceFallbacks")
        return snap

    def ask_servers(self, **req) -> None:
        """One request to every server's control socket (`trace-start`, `trace-stop`)."""
        for ctl in self.controls.values():
            ctl.ask(**req)

    def memstats(self) -> dict:
        peak, kinds, platforms = 0, set(), set()
        for ctl in self.controls.values():
            for d in ctl.ask(cmd="memstats")["devices"]:
                kinds.add(d["kind"])
                platforms.add(d["platform"])
                ms = d["memory_stats"]
                peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
        require(len(kinds) == 1 and len(platforms) == 1, f"servers on different devices: {kinds} {platforms}")
        return {"platform": platforms.pop(), "kind": kinds.pop(), "peak": peak}


def warm_up(cluster: Cluster, traffic: dict, seed: int, trace_dir: Path | None = None) -> None:
    """Every template of the mix, with parameters of its own stream, one
    after the other (the first query also stages the table onto the chip),
    then the mix itself for a moment through the load generator. A traced
    run (`trace_dir`) takes a first, thrown-away trace over that moment: the
    profiler's first start and stop in a new checkout on a new machine froze
    server, broker and launcher for 4 s and 17 s (PERF.md, PR 23), and
    nothing may warm up inside the window."""
    templates = cluster.ds.TEMPLATES
    rng = np.random.default_rng([seed, 777_001])
    client = loadgen.Client(cluster.broker, int(traffic["warmup"].get("timeoutMs", 900_000)))
    try:
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
        cluster.ask_servers(cmd="trace-start", dir=str(trace_dir))
    if ramp > 0:
        qs = loadgen.draw_queries(templates, traffic["templates"], rng, 10_000)
        ramped, _ = loadgen.run_window(cluster.broker, traffic, qs, ramp, rng)
        bad = [s for s in ramped if s.error]
        require(not bad, f"ramp: {len(bad)} of {len(ramped)} queries failed, first: {bad[0].error if bad else ''}")
        say(f"ramp {ramp:g} s: {len(ramped)} queries, slowest {max(s.latency_ms for s in ramped):.0f} ms")
    if trace_dir is not None:
        t1 = time.perf_counter()
        cluster.ask_servers(cmd="trace-stop")
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"thrown-away trace: {t1 - t0:.1f} s open, stop took {time.perf_counter() - t1:.1f} s")


# ---------------------------------------------------------------------------
# the window, the check, the metrics
# ---------------------------------------------------------------------------


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


def check_answers(cluster: Cluster, samples: list, traffic: dict, seed: int, control: bool) -> tuple[bool, int]:
    """(correct, failed): shape of every answer, full comparison of the sample."""
    cfg = cluster.config
    failed = 0
    for s in samples:
        if s.error is None:
            s.error = check.shape_error(s, cfg["servers"], cfg["rows"], int(traffic.get("limit", 1000)))
        if s.error is not None:
            failed += 1
            say(f"failed #{s.index} {s.template}: {s.error}")
    rng = np.random.default_rng([seed, 777_002])
    chosen = check.pick_sample(samples, int(traffic["check"]["perTemplate"]), rng)
    t0 = time.perf_counter()
    wants = run_reference(cluster, chosen, control)
    correct = True
    worst: dict[str, float] = {}
    for s, want in zip(chosen, wants):
        spec = cluster.ds.TEMPLATES[s.template].spec
        numbers = check.compare_rows(spec, s.doc["resultTable"]["rows"], want)
        ok, lines = check.judge(numbers, spec.exact, float(cfg["guarantees"]["doubleSumRelTolerance"]))
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, 0.0), v)
        say(f"check #{s.index} {s.template} rows={len(want)}: {' '.join(lines)} -> {'ok' if ok else 'WRONG'}")
        if not ok:
            correct = False
            failed += 1
            meta = {k: v for k, v in s.doc.items() if k != "resultTable"}
            say(f"WRONG #{s.index}: due {s.due:.3f} s, sent {s.sent:.3f}, done {s.done:.3f}; {s.sql}")
            say(f"WRONG #{s.index}: got {json.dumps(s.doc['resultTable']['rows'][:5])} want {json.dumps(want[:5])}; {json.dumps(meta)[:1500]}")
    say(f"checked {len(chosen)} of {len(samples)} answers in full in {time.perf_counter() - t0:.1f} s; worst {worst}")
    return correct, failed


def reduce_trace(trace_dir: Path, chips: int, log_dir: Path) -> dict:
    """trace_reduce in a child pinned to the CPU: reading a trace needs jax, not a chip."""
    env = {**child_env(True), "TPU_LOG_DIR": "disabled"}
    with open(log_dir / "trace_reduce.stderr.log", "w") as err:
        p = subprocess.run(
            [sys.executable, "-m", "perfbench.trace_reduce", str(trace_dir), "--chips", str(chips)],
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
    ap.add_argument("--keep-trace", action="store_true", help="keep the .xplane.pb in the log directory")
    ap.add_argument("--control", choices=["fast32", "corrupt-metrics", "float32-reference"], default=None,
                    help="correctness controls (PERF.md); a run with one has to print correct=false")  # fmt: skip
    args = ap.parse_args(argv)
    trace = bool(args.trace)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # so that `finally` stops the roles

    manifest = load_manifest()
    cell = load_cell(manifest, args.workload)
    if args.traffic:
        cell["traffic"] = json.loads((BENCH / "traffic" / f"{args.traffic}.json").read_text())
    traffic = cell["traffic"]
    if args.check_per_template is not None:
        traffic["check"] = {**traffic["check"], "perTemplate": args.check_per_template}
    if args.rehearsal:
        cell["config"].update(cell["config"]["rehearsal"])
    log_dir = OUT / args.workload / str(args.seed)
    shutil.rmtree(log_dir, ignore_errors=True)
    log_dir.mkdir(parents=True)

    cluster = Cluster(cell, args.seed, args.rehearsal, log_dir, args.control if args.control in ("fast32", "corrupt-metrics") else None)
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
            cluster.ask_servers(cmd="trace-start", dir=str(trace_dir))
            trace_state["t_on"] = time.perf_counter() - t0
            time.sleep(length)
            trace_state["t_off"] = time.perf_counter() - t0
            cluster.ask_servers(cmd="trace-stop")
            trace_state["t_stopped"] = time.perf_counter() - t0

        tracer_thread = None

        def on_start(t0: float) -> None:
            nonlocal tracer_thread
            if trace:
                tracer_thread = threading.Thread(target=tracer, args=(t0,), name="tracer", daemon=True)
                tracer_thread.start()

        watch = StallWatch()
        watch.start()
        samples, _ = loadgen.run_window(cluster.broker, traffic, queries, args.seconds, rng, on_start)
        watch.stop.set()
        say(f"launcher's worst oversleep in the window {watch.worst_ms:.0f} ms, {watch.at:.1f} s in")
        with open(log_dir / "samples.jsonl", "w") as f:
            for s in samples:
                f.write(json.dumps({"i": s.index, "t": s.template, "due": s.due, "sent": s.sent, "done": s.done,
                                    "brokerMs": (s.doc or {}).get("timeUsedMs"), "error": s.error}) + "\n")  # fmt: skip
        if tracer_thread is not None:
            tracer_thread.join(timeout=600)
            require(not tracer_thread.is_alive() and "t_stopped" in trace_state, "the trace never stopped")
        cluster.roles.check_alive()
        after = cluster.snapshot()
        mem = cluster.memstats()
    finally:
        cluster.roles.stop_all()

    # -- the device path was the path ---------------------------------------
    require(len(samples) > 0, "no query was issued in the window")
    require(after["device_fallbacks"] == 0, f"{after['device_fallbacks']} queries fell back to the host executor")
    require(after["fused_calls"] > before["fused_calls"], "no fused device program ran in the window")
    want_platform = "cpu" if args.rehearsal else "tpu"
    require(mem["platform"] == want_platform, f"servers report platform {mem['platform']!r}")

    # -- answers ----------------------------------------------------------------
    answers_ok, failed = check_answers(cluster, samples, traffic, args.seed, args.control == "float32-reference")
    correct = check.run_correct(answers_ok, failed, len(samples))
    if answers_ok and not correct:
        say(f"not correct: {failed} of {len(samples)} queries failed, more than 1 in 100 (the traffic is chosen so that none does)")

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
        "trace": None, "trace_window": None, "traffic": traffic, "config": cell["config"],
    }  # fmt: skip
    device = {"platform": mem["platform"], "kind": mem["kind"], "count": cell["entry"]["chips"],
              "memory_peak_bytes": mem["peak"]}  # fmt: skip
    breakdown = None
    if trace:
        if args.rehearsal:
            # a CPU trace has no device plane: the rehearsal reduces the trace recorded on the chip
            reduced = reduce_trace(BENCH / "tests" / "fixtures" / "v5e_window.xplane.pb", 1, log_dir)
        else:
            reduced = reduce_trace(trace_dir, cell["entry"]["chips"], log_dir)
        (log_dir / "trace_reduced.json").write_text(json.dumps(reduced, indent=1))
        run["trace"] = reduced
        run["trace_window"] = (trace_state["t_on"], trace_state["t_off"])
        device["window_s"], device["busy_s"] = reduced["window_s"], reduced["busy_s"]
        breakdown = {
            "device_ops": [[n, t] for n, t in reduced["ops"][:10]],
            # no host span is on the profiler's clock yet (PERF.md, Open questions): gaps go unattributed
            "idle_gaps": [["unattributed", g] for g in reduced["idle_gaps_s"][:10]],
        }
        say(f"trace: {json.dumps(trace_state)} window_s={reduced['window_s']:.4f} busy_s={reduced['busy_s']:.4f}")
        for name, t, n in reduced["modules"][:8]:
            say(f"  module {name}: {t:.4f} s in {n} launches")
        if args.keep_trace and trace_dir.exists():
            from perfbench.trace_reduce import find_xplane  # pathlib only at import

            shutil.copy(find_xplane(trace_dir), log_dir / "trace.xplane.pb")
        shutil.rmtree(trace_dir, ignore_errors=True)  # hundreds of MB; the reduction is kept
        for m in metrics_of(manifest, "per_layer", args.workload):
            reader = importlib.import_module(f"perfbench.layer_metrics.{m['name']}")
            got = reader.read(run)
            if got is not None:
                values[m["name"]] = float(got)

    extra = {}
    if trace:  # measured under the profiler: beside the plain run's, the tracing overhead
        extra["end_to_end_under_trace"] = {k: values[k] for k in ("query_p50_ms", "query_p95_ms", "queries_per_s", "setup_s")}
    if args.rehearsal:
        extra["rehearsal"] = True
    if args.control:
        extra["control"] = args.control
    line = result_line.build(
        manifest, args.workload, trace, correct=correct, attempted=len(samples), failed=failed,
        values=values, device=device, breakdown=breakdown, extra=extra,
    )  # fmt: skip
    try:
        text = result_line.validate(line, manifest, args.workload, trace, cell["entry"]["chips"])
    except result_line.InvalidLine as e:
        print(f"perfbench: the result line is invalid: {e}\n{line!r}", file=sys.stderr)
        return 3
    (log_dir / "result.json").write_text(text)
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
