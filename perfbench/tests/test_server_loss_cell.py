"""A replicated table and the loss of a server: `ssb4-serverloss-closed`, the
cell that waits in `perfbench/pending/` (PERF.md section 7).

The program as it stands cannot run the cell: a server that is killed and
started again never hosts its replicas again, it is routed to all the same,
and in the rehearsal's layout the balanced selector sends every query to the
same half of the servers. So the rehearsals here run under
`fixtures/repaired_program`, which lays the three repairs (a few lines each)
over the controller's and the broker's process: what is tested is the
harness — set-up at replication 2, the fault schedule on OS processes, the
tail, `correct` by phase, `recovered_s` — against a program that can."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import check, result_line, run
from perfbench import loss as loss_mod
from perfbench.manifest import load_cell, load_manifest, metrics_of, with_entries
from perfbench.tests.test_run_rehearsal import ROOT

CELL = "ssb4-serverloss-closed"
ENTRIES = "perfbench/pending/ssb4-serverloss-closed.json"
MANIFEST = with_entries(load_manifest(ROOT), json.loads((ROOT / ENTRIES).read_text()))
REPAIRS = ROOT / "perfbench" / "tests" / "fixtures" / "repaired_program"


def run_loss_cell(seed: int, trace: int, *extra: str, repaired: bool = True, seconds: float = 20) -> tuple[int, dict | None, str]:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    if repaired:
        env["PYTHONPATH"] = f"{REPAIRS}{os.pathsep}{env.get('PYTHONPATH', '')}"
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--entries", ENTRIES, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearsal", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )  # fmt: skip
    line = json.loads(p.stdout.strip().splitlines()[-1]) if p.returncode == 0 else None
    return p.returncode, line, p.stdout + p.stderr


# ---------------------------------------------------------------------------
# the cell, rehearsed as OS processes: four CPU servers, one killed and started again
# ---------------------------------------------------------------------------


def test_the_rehearsal_kills_a_server_and_sees_it_recover():
    rc, line, out = run_loss_cell(3_100_000_101, 0)
    assert rc == 0, out[-3000:]
    result_line.validate(line, MANIFEST, CELL, False, chips=4)
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    due = load_cell(MANIFEST, CELL, ROOT)["traffic"]["faults"][0]["atSeconds"]
    assert abs(line["fault_at_s"] - due) < 0.5
    loss = line["loss"]
    assert loss["kill_s"] <= loss["restart_s"] < loss["ready_s"] < loss["hosted_s"] < loss["served_s"] == loss["recovered_s"]
    assert line["metrics"]["recovered_s"]["value"] == pytest.approx(loss["recovered_s"] - loss["kill_s"])
    assert 0 < line["metrics"]["recovered_s"]["value"] < loss["deadline_s"]
    # every query in flight at the kill was compared in full, and every number stands beside its limit
    assert line["compared"]["inflight_at_kill_not_compared"] == {"value": 0, "limit": 0}
    assert line["compared"]["not_recovered"] == {"value": 0, "limit": 0}
    assert line["compared"]["max_abs_diff"] == {"value": 0.0, "limit": 0.0}
    assert list(line)[-1] == "compared" and all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert "in flight at the kill: 4 queries" in out and " inflight rows=" in out and " degraded rows=" in out and " recovered rows=" in out
    # the survivors ran fused programs all through; the restarted server counts from nothing, and it served
    assert set(line["servers"]) == {f"server_{i}" for i in range(4)} and all(s["fused_calls"] > 0 for s in line["servers"].values())
    assert line["attempted"] > line["tail_queries"] >= 0


def test_the_traced_rehearsal_kills_after_the_trace_and_reads_the_loss_from_the_answers():
    rc, line, out = run_loss_cell(3_100_000_102, 1)
    assert rc == 0, out[-3000:]
    result_line.validate(line, MANIFEST, CELL, True, chips=4)
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    stopped = json.loads(next(ln for ln in out.splitlines() if "[perfbench] trace: {" in ln).split("trace: ")[1].split(" window_s=")[0])["t_stopped"]
    assert line["fault_at_s"] >= stopped  # a killed server writes no trace: the kill waits for the four traces
    m = line["metrics"]
    for name in ("failover_max_ms", "degraded_p50_ms", "healthy_p50_ms", "restart_ready_s", "restart_hosted_s", "restart_first_answer_s"):
        assert m[name]["value"] > 0, name
    legs = m["restart_ready_s"]["value"] + m["restart_hosted_s"]["value"] + m["restart_first_answer_s"]["value"]
    assert legs == pytest.approx(line["end_to_end_under_trace"]["recovered_s"])
    assert m["scatter_skew_ms"]["value"] >= 0 and m["broker_gather_ms"]["value"] > 0 and "device_idle_share" in m


def test_the_no_replica_control_comes_out_as_not_correct():
    """The cell's schedule over the table at replication 1: a quarter of the
    table has no server after the kill, and the check has to see it."""
    rc, line, out = run_loss_cell(3_100_000_103, 0, "--control", "no-replica")
    assert rc == 0, out[-3000:]
    assert line["correct"] is False and line["failed"] > line["attempted"] // 100, out[-3000:]
    assert line["control"] == "no-replica"
    assert "no surviving replica" in out


def test_the_control_needs_a_fault_schedule():
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "ssb-q1-rate", "--seed", "1", "--seconds", "3", "--trace", "0",
         "--rehearsal", "--control", "no-replica"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )  # fmt: skip
    assert p.returncode == 2 and "needs a cell whose traffic has a fault schedule" in p.stderr and not p.stdout.strip().startswith("{")


# ---------------------------------------------------------------------------
# the entries that wait, and the files they name
# ---------------------------------------------------------------------------


def test_the_pending_entries_make_a_manifest_the_contract_takes():
    assert [w["name"] for w in MANIFEST["workloads"]][-1] == CELL and len(MANIFEST["workloads"]) == len(load_manifest(ROOT)["workloads"]) + 1
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= len(MANIFEST["workloads"]) // 2  # at most half of the cells may take four chips
    cell = load_cell(MANIFEST, CELL, ROOT)
    assert cell["config"]["replication"] == 2 and cell["config"]["servers"] == cell["entry"]["chips"] == 4
    twin = json.loads((ROOT / "perfbench" / "traffic" / "flights234-closed4.json").read_text())
    mine = dict(cell["traffic"])
    assert [f["action"] for f in mine.pop("faults")] == ["kill", "restart"] and mine.pop("recovery")["deadlineSeconds"] == 110
    mine.pop("why"), twin.pop("why")
    assert mine == twin  # flights234-closed4 letter for letter, and the schedule
    e2e = {m["name"]: m for m in metrics_of(MANIFEST, "end_to_end", CELL)}
    assert set(e2e) == {"query_p50_ms", "query_p95_ms", "queries_per_s", "setup_s", "recovered_s"}
    assert e2e["recovered_s"]["workloads"] == [CELL] and 0 < e2e["recovered_s"]["bound"] <= 0.25
    mine = {m["name"] for m in metrics_of(MANIFEST, "per_layer", CELL)}
    twin = {m["name"] for m in metrics_of(MANIFEST, "per_layer", "ssb4-groupby-closed")}
    # the pending file's six, and what the cell gained once it stood in BENCHMARK.json: the two counters of PR 33, the two first-staging readers of PR 37
    assert mine - twin == {"failover_max_ms", "degraded_p50_ms", "healthy_p50_ms", "restart_ready_s", "restart_hosted_s", "restart_first_answer_s",
                           "failover_legs_per_query", "stale_route_retries_per_query", "segments_staged_in_window", "segment_stage_ms"}  # fmt: skip
    assert twin <= mine
    for m in MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in e2e
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert all(len(entry[k]) <= 200 for k in ("why", "source") if k in entry), entry["name"]
    # an accepted cell's metrics are what they were
    assert metrics_of(MANIFEST, "end_to_end", "ssb-q1-rate") == metrics_of(load_manifest(ROOT), "end_to_end", "ssb-q1-rate")


# ---------------------------------------------------------------------------
# the rules, without a cluster
# ---------------------------------------------------------------------------

WANT = [f"t_{i}" for i in range(4)]


def ideal_of(hosted: dict[str, list[str]]) -> dict:
    return {seg: {sid: "ONLINE" for sid, segs in hosted.items() if seg in segs} for seg in WANT}


@pytest.mark.parametrize(
    "hosted,replication,wrong",
    [
        ({"a": ["t_0", "t_1"], "b": ["t_2", "t_3"]}, 1, None),  # today's condition: each segment once, an equal share a server
        ({"a": ["t_0", "t_1", "t_2"], "b": ["t_3"]}, 1, "not 2 each"),
        ({"a": ["t_0", "t_1"], "b": ["t_2"]}, 1, "1 segments not on 1 servers"),
        ({"a": WANT, "b": WANT}, 2, None),  # replication 2 over two servers: every segment on both
        ({"a": ["t_0", "t_1"], "b": ["t_1", "t_2"], "c": ["t_2", "t_3"], "d": ["t_3", "t_0"]}, 2, None),
        ({"a": ["t_0", "t_0"], "b": ["t_1", "t_1"], "c": ["t_2", "t_2"], "d": ["t_3", "t_3"]}, 2, "hosted twice by a server"),
        ({"a": ["t_0", "t_1"], "b": ["t_0", "t_1"], "c": ["t_2", "t_3"], "d": ["t_2"]}, 2, "1 segments not on 2 servers"),
        ({"a": ["t_0", "t_1"], "b": ["t_2", "t_3"]}, 2, "not 4 each"),  # loaded at replication 1 where 2 was asked
    ],
)
def test_a_table_is_loaded_when_every_segment_is_on_as_many_different_servers_as_the_configuration_says(hosted, replication, wrong):
    got = run.hosted_error(hosted, ideal_of(hosted), WANT, replication)
    assert (got is None) if wrong is None else (wrong in got), got


def test_an_ideal_state_that_names_fewer_replicas_than_are_hosted_is_not_loaded():
    hosted = {"a": WANT, "b": WANT}
    ideal = ideal_of(hosted)
    del ideal["t_2"]["b"]
    assert "1 segments not on 2 servers" in run.hosted_error(hosted, ideal, WANT, 2)


@pytest.mark.parametrize(
    "queried,responded,phase,replication,ok",
    [
        (4, 4, "", 1, True), (0, 0, "", 1, True), (3, 3, "", 1, False), (4, 3, "", 1, False),  # an accepted cell: as it was
        (2, 2, "", 2, True), (4, 4, "", 2, True), (3, 2, "", 2, False),  # one replica a segment: a query asks some of the servers
        (2, 2, loss_mod.HEALTHY, 2, True), (3, 2, loss_mod.HEALTHY, 2, False),
        (3, 2, loss_mod.INFLIGHT, 2, True), (7, 6, loss_mod.INFLIGHT, 2, True), (2, 3, loss_mod.INFLIGHT, 2, False),  # a lost leg, retried
        (3, 3, loss_mod.DEGRADED, 2, True), (16, 4, loss_mod.DEGRADED, 2, True), (4, 0, loss_mod.DEGRADED, 2, False),  # legs of every attempt
        (3, 2, loss_mod.RECOVERED, 2, False), (4, 4, loss_mod.RECOVERED, 2, True),  # after recovery no leg is lost
    ],
)  # fmt: skip
def test_the_servers_an_answer_may_have_asked_go_by_its_phase(queried, responded, phase, replication, ok):
    assert (check.servers_error(queried, responded, 4, phase, replication) is None) is ok
    doc = {"numServersQueried": queried, "numServersResponded": responded, "totalDocs": 10, "resultTable": {"rows": []}}
    assert (check.shape_error(SimpleNamespace(doc=doc), 4, 10, 1000, phase, replication) is None) is ok
    assert check.shape_error(SimpleNamespace(doc={**doc, "partialResult": True}), 4, 10, 1000, phase, replication) is not None


def sample(i, template, sent, done, servers=(2, 2), error=None):
    return SimpleNamespace(
        index=i, template=template, sent=sent, done=done, due=sent, error=error, latency_ms=(done - sent) * 1e3,
        doc={"numServersQueried": servers[0], "numServersResponded": servers[1]},
    )  # fmt: skip


def a_window(kill=10.12, served=30.0):
    """400 answers of two templates, 0.3 s each, from four callers 50 ms apart; those that span the kill lose a leg."""
    out = []
    for i in range(400):
        sent = (i // 4) * 0.35 + (i % 4) * 0.05
        done = sent + 0.3
        out.append(sample(i, "qa" if i % 2 else "qb", sent, done, (3, 2) if sent < kill <= done else (2, 2)))
    return out, loss_mod.Loss("server_1", 110.0, kill_s=kill, ready_s=15.0, hosted_s=20.0, served_s=served, recovered_s=served)


def test_the_sample_takes_every_query_in_flight_at_the_kill_and_some_of_every_phase():
    samples, loss = a_window()
    inflight = [s for s in samples if loss_mod.phase_of(s, loss) == loss_mod.INFLIGHT]
    assert len(inflight) >= 2
    chosen = check.pick_sample(samples, 2, np.random.default_rng(1), loss)
    assert all(s in chosen for s in inflight)
    by = {}
    for s in chosen:
        by.setdefault((loss_mod.phase_of(s, loss), s.template), []).append(s)
    for phase in (loss_mod.HEALTHY, loss_mod.DEGRADED, loss_mod.RECOVERED):
        assert len(by[(phase, "qa")]) >= 2 and len(by[(phase, "qb")]) >= 2
    first_after = min((s for s in samples if s.sent >= loss.kill_s), key=lambda s: s.done)
    assert first_after in chosen and max(samples, key=lambda s: s.latency_ms) in chosen
    assert [s.index for s in chosen] == sorted(s.index for s in chosen)


def test_without_a_loss_the_sample_is_drawn_as_it_always_was():
    """Per template in the templates' order, then the slowest: the draws of an accepted cell's seed do not move."""
    samples, _ = a_window()
    rng = np.random.default_rng(7)
    want = {}
    for name in ("qa", "qb"):
        group = [s for s in samples if s.template == name]
        for i in rng.choice(len(group), 2, replace=False):
            want[group[i].index] = group[i]
    slowest = max(samples, key=lambda s: s.latency_ms)
    want[slowest.index] = slowest
    assert check.pick_sample(samples, 2, np.random.default_rng(7)) == [want[i] for i in sorted(want)]


def test_recovered_is_when_the_restarted_server_served_unless_a_later_answer_loses_a_leg_again():
    samples, loss = a_window()
    assert loss_mod.recovery_instant(samples, loss.served_s) == loss.served_s
    assert loss_mod.recovery_instant(samples, None) is None  # it never served: not recovered
    samples.append(sample(900, "qa", loss.served_s + 2.0, loss.served_s + 2.5, (3, 2)))  # the server flaps
    assert loss_mod.recovery_instant(samples, loss.served_s) is None
    samples[-1] = sample(900, "qa", loss.served_s - 2.0, loss.served_s + 2.5, (3, 2))  # sent before it was back: no flap
    assert loss_mod.recovery_instant(samples, loss.served_s) == loss.served_s


def test_a_role_that_was_killed_on_purpose_is_not_a_role_that_died(tmp_path):
    from perfbench.cluster import Roles, RunFailure

    roles = Roles(dict(os.environ), tmp_path)
    roles.procs["sleeper"] = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    roles.kill("sleeper")
    assert roles.procs["sleeper"].poll() == -9  # SIGKILL, and reaped
    roles.check_alive()
    roles.killed.discard("sleeper")  # what a start of the same role does
    with pytest.raises(RunFailure, match="role sleeper exited"):
        roles.check_alive()
