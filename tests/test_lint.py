"""Golden-fixture tests for pinotlint (pinot_tpu.devtools.lint).

Each fixture in tests/lint_fixtures/ carries known violations at known
lines plus clean patterns and a suppression demo; the tests pin the exact
(line, check) sets so any checker regression (missed or spurious finding)
fails loudly. The suite ends with the self-run test: the whole pinot_tpu
package must lint clean, including under --require-reason.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from pinot_tpu.devtools.lint import ALL_CHECKERS, lint_paths, make_checkers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
PACKAGE = os.path.join(REPO, "pinot_tpu")


def fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


def findings_for(name: str, checks: list[str] | None = None, **kw):
    return lint_paths([fixture(name)], checks=checks, **kw)


def lines_of(findings, check: str) -> list[int]:
    return sorted(f.line for f in findings if f.check == check)


# ---------------------------------------------------------------------------
# per-checker golden fixtures: exact locations
# ---------------------------------------------------------------------------


def test_race_fixture_findings():
    fs = findings_for("race_fixture.py", checks=["race-discipline"])
    assert lines_of(fs, "race-discipline") == [20, 73]
    by_line = {f.line: f.message for f in fs}
    assert "hits" in by_line[20] and "RacyCounter" in by_line[20]
    assert "last_body" in by_line[73] and "HandlerRacy" in by_line[73]


def test_jit_fixture_findings():
    fs = findings_for("jit_fixture.py", checks=["jit-purity"])
    assert lines_of(fs, "jit-purity") == [15, 28, 42, 54, 61]
    by_line = {f.line: f.message for f in fs}
    assert "time.perf_counter" in by_line[15]
    assert "y" in by_line[28]  # branch on traced parameter
    assert "_cache" in by_line[42]  # closed-over mutation
    assert "print" in by_line[54]
    assert "time.sleep" in by_line[61]  # transitively reached helper


def test_deadline_fixture_findings():
    fs = findings_for("deadline_fixture.py", checks=["deadline-coverage"])
    assert lines_of(fs, "deadline-coverage") == [11]
    assert lines_of(fs, "deadline-swallow") == [33, 56]


def test_errcode_fixture_findings():
    fs = findings_for("errcode_fixture.py", checks=["error-code-registry"])
    assert lines_of(fs, "error-code-registry") == [11, 14, 15, 19]
    assert all("magic error code" in f.message for f in fs)


def test_fault_fixture_findings():
    fs = findings_for("fault_fixture.py", checks=["fault-point-registry"])
    by_line = {f.line: f.message for f in fs}
    assert sorted(by_line) == [7, 19, 20]
    assert "dead.point" in by_line[7]  # declared but never injected
    assert "un.declared" in by_line[19]  # injected but never declared
    assert "literal" in by_line[20]  # non-literal point name


SPAN_FIXTURE = os.path.join("pinot_tpu", "query", "span_fixture.py")


def test_span_fixture_findings():
    fs = findings_for(SPAN_FIXTURE, checks=["fault-span-event"])
    assert lines_of(fs, "fault-span-event") == [12, 27]
    by_line = {f.line: f.message for f in fs}
    assert "no_event" in by_line[12]
    assert "nested_scope_does_not_count" in by_line[27]  # walk_scope stops at inner def


def test_span_checker_ignores_off_query_path():
    # the same violations in a plain fixtures path are out of the rule's scope
    fs = findings_for("fault_fixture.py", checks=["fault-span-event"])
    assert fs == []


def test_atomic_write_fixture_findings():
    fs = findings_for("atomic_write_fixture.py", checks=["atomic-write"])
    assert lines_of(fs, "atomic-write") == [15, 19, 23]
    assert all("durability.atomic_write" in f.message for f in fs)


def test_atomic_write_exempts_durability_module():
    # the helper module itself is the one sanctioned direct writer
    durability = os.path.join(REPO, "pinot_tpu", "common", "durability.py")
    assert lint_paths([durability], checks=["atomic-write"]) == []


KREG_FIXTURE = os.path.join("pinot_tpu", "query", "kernel_registry_fixture.py")


def test_kernel_registry_fixture_findings():
    fs = findings_for(KREG_FIXTURE, checks=["kernel-registry"])
    assert lines_of(fs, "kernel-registry") == [17, 21, 35, 43]
    by_line = {f.line: f.message for f in fs}
    assert "unregistered_root" in by_line[17]  # plain @jax.jit decorator
    assert "plain_fn" in by_line[21]  # jax.jit(f) call form resolves to the def
    assert "pallas_body" in by_line[35]  # handed to a pallas_call wrapper
    assert "<module-level jit>" in by_line[43]  # anonymous lambda root
    # registered_root (by Name), kernel_factory (outermost owner, by string
    # name), and suppressed_root (line 46) must all stay quiet
    for clean in ("registered_root", "kernel_factory", "suppressed_root"):
        assert not any(f"'{clean}'" in f.message for f in fs)


def test_kernel_registry_ignores_off_kernel_path():
    # same rule set, but a fixture outside query/ + ops/ is out of scope
    fs = findings_for("jit_fixture.py", checks=["kernel-registry"])
    assert fs == []


def test_cache_invalidation_fixture_findings():
    fs = findings_for("cache_invalidation_fixture.py", checks=["cache-invalidation"])
    assert lines_of(fs, "cache-invalidation") == [13, 16, 21, 46, 47, 48, 49]
    assert all("`bump=`" in f.message for f in fs)
    by_line = {f.line: f.message for f in fs}
    assert "'idealstate'" in by_line[13]  # idealstate replace without a count
    assert "'/segments/'" in by_line[16]  # segment-metadata update without a count
    assert "in upload_with_bump()" in by_line[21]  # the count as a step of its own leaves a window
    # schema, instance, config and a delete are routing state too (PR 30)
    for line, marker in ((46, "'/schemas/'"), (47, "'/instances/'"), (48, "'/config'"), (49, "'/segments/'")):
        assert marker in by_line[line]
    # writes that name their counter, the bump itself, reads, paths no
    # snapshot holds, non-store receivers, and the suppressed write stay quiet
    for clean in ("write_that_counts", "bump_routing_version", "read_only_paths",
                  "suppressed_write"):
        assert not any(f"in {clean}()" in f.message for f in fs)


def test_cache_invalidation_exempts_metadata_module():
    # the PropertyStore module is the machinery under the rule, not a client
    metadata = os.path.join(REPO, "pinot_tpu", "cluster", "metadata.py")
    assert lint_paths([metadata], checks=["cache-invalidation"]) == []


# ---------------------------------------------------------------------------
# v2 whole-program checkers: lock-order, blocking-under-lock, resource-leak
# ---------------------------------------------------------------------------


def test_lockorder_fixture_findings():
    fs = findings_for("lockorder_fixture.py", checks=["lock-order"])
    assert lines_of(fs, "lock-order") == [17, 25, 37]
    by_line = {f.line: f.message for f in fs}
    # both edges of the A/B cycle, each naming the inverse witness
    assert "LOCK_B" in by_line[17] and ":25" in by_line[17]
    assert "LOCK_A" in by_line[25] and ":17" in by_line[25]
    # line 19 (A->C, no cycle), reentrant RLock, and the suppressed D/E edge
    # at 43 must all stay quiet; the un-suppressed D/E edge reports
    assert "LOCK_E" in by_line[37]


def test_lockorder_cross_module():
    # the X->Y edge exists only through a call into the other module: the
    # exact capability a per-file pass cannot have
    fs = lint_paths(
        [fixture("lockorder_mod_a.py"), fixture("lockorder_mod_b.py")],
        checks=["lock-order"],
    )
    locs = sorted((os.path.basename(f.path), f.line) for f in fs)
    assert locs == [("lockorder_mod_a.py", 10), ("lockorder_mod_b.py", 17)]
    by_file = {os.path.basename(f.path): f.message for f in fs}
    assert "via grab_y()" in by_file["lockorder_mod_a.py"]
    # each file alone shows no cycle
    assert lint_paths([fixture("lockorder_mod_a.py")], checks=["lock-order"]) == []
    assert lint_paths([fixture("lockorder_mod_b.py")], checks=["lock-order"]) == []


def test_blocking_fixture_findings():
    fs = findings_for("blocking_fixture.py", checks=["blocking-under-lock"])
    assert lines_of(fs, "blocking-under-lock") == [24, 28, 37, 41]
    by_line = {f.line: f.message for f in fs}
    assert "time.sleep" in by_line[24]
    # interprocedural: the finding sits at the call, citing the witness
    assert "slow_io" in by_line[28] and "time.sleep" in by_line[28]
    # Condition.wait is legal under its OWN lock (line 32 clean) but line 37
    # still holds _other across the wait
    assert "_other" in by_line[37]
    assert "queue .get" in by_line[41]


def test_resleak_fixture_findings():
    fs = findings_for("resleak_fixture.py", checks=["resource-leak"])
    assert lines_of(fs, "resource-leak") == [15, 20, 22, 27]
    by_line = {f.line: f.message for f in fs}
    assert "thread" in by_line[15] and "join" in by_line[15]
    assert "socket" in by_line[20]
    assert "executor" in by_line[22] and "shutdown" in by_line[22]
    assert "conditional path" in by_line[27]


def test_race_cross_module_attribution():
    # the unlocked write lives in the base-class helper in ANOTHER module;
    # the thread entry that reaches it is spawned by the subclass
    fs = lint_paths(
        [fixture("race_mod_base.py"), fixture("race_mod_sub.py")],
        checks=["race-discipline"],
    )
    assert [(os.path.basename(f.path), f.line) for f in fs] == [("race_mod_base.py", 15)]
    msg = fs[0].message
    assert "Worker._run" in msg and "via _bump()" in msg and "count" in msg
    # _bump_safe's write is call-site locked: no finding for `safe`
    assert not any("safe" in f.message for f in fs)


@pytest.mark.parametrize(
    "name, checks, suppressed_line",
    [
        ("lockorder_fixture.py", ["lock-order"], 43),
        ("blocking_fixture.py", ["blocking-under-lock"], 51),
        ("resleak_fixture.py", ["resource-leak"], 68),
    ],
)
def test_v2_suppressions(name, checks, suppressed_line):
    fs = findings_for(name, checks=checks)
    assert suppressed_line not in {f.line for f in fs}


# ---------------------------------------------------------------------------
# v3 dataflow checkers: fence-discipline, typed-error-boundary,
# event-loop-safety
# ---------------------------------------------------------------------------


def test_fence_fixture_findings():
    fs = findings_for("fence_fixture.py", checks=["fence-discipline"])
    assert lines_of(fs, "fence-discipline") == [32, 35, 54]
    by_line = {f.line: f.message for f in fs}
    assert "omits fence=" in by_line[32]
    assert "unfenced_write" in by_line[32]  # entry witness in the message
    assert "does not flow from the lease epoch" in by_line[35]
    # the interprocedural hop: _apply's fence parameter obligates the caller
    assert "fence parameter 'fence' at its default" in by_line[54]
    # fenced_write, the lease-path write, good_caller, and the non-lead
    # offline_tool must all stay quiet
    assert not any(f.line in (39, 43, 51, 64) for f in fs)


def test_fence_cross_module_obligation():
    # the fence obligation exists only when both halves are in the file set:
    # the sink lives in mod_b, the lead-path entry + the defaulted call in mod_a
    fs = lint_paths(
        [fixture("fence_mod_a.py"), fixture("fence_mod_b.py")],
        checks=["fence-discipline"],
    )
    assert [(os.path.basename(f.path), f.line) for f in fs] == [("fence_mod_a.py", 25)]
    assert "apply_meta()'s fence parameter 'fence'" in fs[0].message
    # each file alone shows nothing: mod_b's helper is not an entry, and
    # mod_a's call into the missing module resolves to no edge
    assert lint_paths([fixture("fence_mod_a.py")], checks=["fence-discipline"]) == []
    assert lint_paths([fixture("fence_mod_b.py")], checks=["fence-discipline"]) == []


def test_typed_error_fixture_findings():
    fs = findings_for("typed_error_fixture.py", checks=["typed-error-boundary"])
    assert lines_of(fs, "typed-error-boundary") == [30, 73]
    by_line = {f.line: f.message for f in fs}
    # the finding lands at the ORIGIN raise, two helpers below the handler
    assert "NakedError" in by_line[30] and "do_GET" in by_line[30]
    assert "via _middle -> _inner" in by_line[30]
    assert "do_DELETE" in by_line[73]
    # registered (TypedError), specifically-caught (CaughtError), and
    # builtin (ValueError) raises must all stay quiet
    for clean in ("TypedError", "CaughtError", "ValueError"):
        assert not any(f"raise {clean}" in f.message for f in fs)


def test_typed_error_silent_without_registry():
    # no `class QueryErrorCode` in the file set -> the checker stays silent
    # (golden fixtures carry their own registry; this one does not)
    fs = findings_for("async_fixture.py", checks=["typed-error-boundary"])
    assert fs == []


def test_async_fixture_findings():
    fs = findings_for("async_fixture.py", checks=["event-loop-safety"])
    assert lines_of(fs, "event-loop-safety") == [16, 20, 24, 44, 45, 57]
    by_line = {f.line: f.message for f in fs}
    assert "time.sleep()" in by_line[16] and "direct_block" in by_line[16]
    # interprocedural: the finding sits at the call, citing the chain
    assert "via sync_slow" in by_line[20]
    assert "subprocess.run()" in by_line[24]  # loop-only blocking set
    assert "threading lock" in by_line[44]
    assert "await while holding" in by_line[45]
    assert "never awaited" in by_line[57] and "background_refresh" in by_line[57]


def test_async_sanctioned_shapes_stay_quiet():
    fs = findings_for("async_fixture.py", checks=["event-loop-safety"])
    # executor hand-offs, asyncio.Lock, and scheduler hand-off are clean
    for clean in ("executor_ok", "to_thread_ok", "async_lock_ok", "scheduled_ok"):
        assert not any(clean in f.message for f in fs)


@pytest.mark.parametrize(
    "name, checks, suppressed_line",
    [
        ("fence_fixture.py", ["fence-discipline"], 57),
        ("typed_error_fixture.py", ["typed-error-boundary"], 53),
        ("async_fixture.py", ["event-loop-safety"], 66),
    ],
)
def test_v3_suppressions(name, checks, suppressed_line):
    fs = findings_for(name, checks=checks)
    assert suppressed_line not in {f.line for f in fs}


def test_v3_checkers_registered():
    for name in ("fence-discipline", "typed-error-boundary", "event-loop-safety"):
        assert name in ALL_CHECKERS


def test_fence_mutation_is_caught(tmp_path):
    # the proof the checker guards the real invariant: copy the package,
    # strip ONE fence= from a real lead-path store call, and the checker
    # must catch exactly that site (the unmutated copy stays clean)
    import shutil

    tree = tmp_path / "pinot_tpu"
    shutil.copytree(PACKAGE, tree)
    assert lint_paths([str(tree)], checks=["fence-discipline"]) == []
    target = tree / "cluster" / "controller.py"
    src = target.read_text()
    fenced = '{"host": host, "port": port}, fence=self.lease_fence())'  # register_broker's write
    assert src.count(fenced) == 1
    mutated = src.replace(fenced, '{"host": host, "port": port})')
    target.write_text(mutated)
    fs = lint_paths([str(tree)], checks=["fence-discipline"])
    assert len(fs) == 1, "\n".join(str(f) for f in fs)
    assert fs[0].path.endswith("controller.py")
    assert "omits fence=" in fs[0].message


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name, checks, suppressed_line",
    [
        ("jit_fixture.py", ["jit-purity"], 48),
        ("deadline_fixture.py", ["deadline-coverage"], 70),
        ("errcode_fixture.py", ["error-code-registry"], 34),
        ("fault_fixture.py", ["fault-point-registry"], 24),
        (os.path.join("pinot_tpu", "query", "span_fixture.py"), ["fault-span-event"], 36),
        (os.path.join("pinot_tpu", "query", "kernel_registry_fixture.py"), ["kernel-registry"], 46),
    ],
)
def test_suppressed_lines_not_reported(name, checks, suppressed_line):
    fs = findings_for(name, checks=checks)
    assert suppressed_line not in {f.line for f in fs}


def test_require_reason_flags_bare_suppressions(tmp_path):
    bare = tmp_path / "bare.py"
    bare.write_text("x = {'errorCode': 1}  # pinotlint: disable=error-code-registry\n")
    fs = lint_paths([str(bare)], require_reason=True)
    assert [f.check for f in fs] == ["suppression-reason"]
    assert fs[0].line == 1
    # fixtures all carry reasons, so --require-reason adds nothing there
    fs = findings_for("errcode_fixture.py", checks=["error-code-registry"], require_reason=True)
    assert not any(f.check == "suppression-reason" for f in fs)


def test_suppression_only_covers_named_check():
    # a disable= for one check must not hide findings from another
    fs = findings_for("deadline_fixture.py", checks=["deadline-coverage"])
    assert 33 in {f.line for f in fs}  # un-suppressed swallow still reported


# ---------------------------------------------------------------------------
# framework behavior
# ---------------------------------------------------------------------------


def test_parse_error_becomes_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    fs = lint_paths([str(bad)])
    assert [f.check for f in fs] == ["parse-error"]


def test_unknown_checker_rejected():
    with pytest.raises(KeyError):
        make_checkers(["no-such-check"])


def test_findings_sorted_and_stringify():
    fs = findings_for("errcode_fixture.py", checks=["error-code-registry"])
    assert fs == sorted(fs, key=lambda f: (f.path, f.line, f.check, f.message))
    s = str(fs[0])
    assert s.endswith(f"[error-code-registry] {fs[0].message}")
    assert f":{fs[0].line}:" in s


# ---------------------------------------------------------------------------
# CLI contract: exit 0 clean / 1 findings / 2 usage
# ---------------------------------------------------------------------------


def _cli(*args: str):
    return subprocess.run(
        [sys.executable, "-m", "pinot_tpu.devtools.lint", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
    )


@pytest.mark.parametrize(
    "name",
    [
        "race_fixture.py",
        "jit_fixture.py",
        "deadline_fixture.py",
        "errcode_fixture.py",
        "fault_fixture.py",
    ],
)
def test_cli_nonzero_on_fixture(name):
    proc = _cli(fixture(name))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert name in proc.stdout


def test_cli_list_checkers():
    proc = _cli("--list")
    assert proc.returncode == 0
    for check in ALL_CHECKERS:
        assert check in proc.stdout


def test_cli_unknown_check_is_usage_error():
    proc = _cli("--check", "bogus", fixture("errcode_fixture.py"))
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# machine-readable output + baseline ("no new findings") workflow
# ---------------------------------------------------------------------------


def test_cli_json_output():
    import json

    proc = _cli("--json", "--check", "resource-leak", fixture("resleak_fixture.py"))
    assert proc.returncode == 1
    findings = json.loads(proc.stdout)
    assert sorted(f["line"] for f in findings) == [15, 20, 22, 27]
    assert all(set(f) == {"check", "path", "line", "message"} for f in findings)
    assert all(f["check"] == "resource-leak" for f in findings)


def test_baseline_roundtrip(tmp_path):
    base = tmp_path / "baseline.json"
    # record today's findings, then the same run is clean against them
    proc = _cli(
        "--check", "resource-leak", "--baseline", str(base), "--update-baseline",
        fixture("resleak_fixture.py"),
    )
    assert proc.returncode == 0, proc.stderr
    proc = _cli("--check", "resource-leak", "--baseline", str(base), fixture("resleak_fixture.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stderr


def test_baseline_catches_new_finding(tmp_path):
    import json

    base = tmp_path / "baseline.json"
    _cli(
        "--check", "resource-leak", "--baseline", str(base), "--update-baseline",
        fixture("resleak_fixture.py"),
    )
    doc = json.loads(base.read_text())
    assert len(doc["findings"]) == 4
    # drop one recorded entry: that finding is now NEW and must fail the run
    doc["findings"] = doc["findings"][1:]
    base.write_text(json.dumps(doc))
    proc = _cli("--check", "resource-leak", "--baseline", str(base), fixture("resleak_fixture.py"))
    assert proc.returncode == 1
    assert "1 new finding" in proc.stderr


def test_baseline_keys_ignore_line_drift(tmp_path):
    import json

    base = tmp_path / "baseline.json"
    src = fixture("resleak_fixture.py")
    shifted = tmp_path / "resleak_fixture.py"
    with open(src) as f:
        original = f.read()
    shifted.write_text(original)
    _cli("--check", "resource-leak", "--baseline", str(base), "--update-baseline", str(shifted))
    # prepend unrelated lines: every finding moves but none is NEW
    shifted.write_text("# drift\n# drift\n" + original)
    proc = _cli("--check", "resource-leak", "--baseline", str(base), str(shifted))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_update_baseline_requires_file():
    proc = _cli("--update-baseline", fixture("resleak_fixture.py"))
    assert proc.returncode == 2


def test_checked_in_baseline_is_empty():
    # the package lints clean, so the CI baseline must tolerate NOTHING —
    # it exists for the mechanism, not to park debt
    import json

    with open(os.path.join(REPO, "pinot_tpu", "devtools", "lint", "baseline.json")) as f:
        doc = json.load(f)
    assert doc == {"version": 1, "findings": []}


# ---------------------------------------------------------------------------
# --diff: whole-program analysis, changed-lines-only reporting
# ---------------------------------------------------------------------------


def _git(cwd, *args: str):
    return subprocess.run(
        ["git", "-C", str(cwd), *args], capture_output=True, text=True
    )


def _diff_repo(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    _git(repo, "init", "-q")
    _git(repo, "config", "user.email", "lint@test")
    _git(repo, "config", "user.name", "lint test")
    return repo


def test_cli_diff_reports_only_changed_lines(tmp_path):
    repo = _diff_repo(tmp_path)
    target = repo / "errcode_fixture.py"
    with open(fixture("errcode_fixture.py")) as f:
        original = f.read()
    target.write_text(original)
    _git(repo, "add", "."), _git(repo, "commit", "-qm", "seed")
    # unmodified tree: every finding is on an unchanged line -> clean
    proc = _cli("--check", "error-code-registry", "--diff", "HEAD", str(target))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # append ONE new violation: only it reports, the four old ones stay out
    mutated = original + "\n\ndef added():\n    return {'errorCode': 250}\n"
    target.write_text(mutated)
    proc = _cli("--check", "error-code-registry", "--diff", "HEAD", str(target))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    lines = [l for l in proc.stdout.splitlines() if "[error-code-registry]" in l]
    assert len(lines) == 1  # exactly the new line; the four old ones stay out
    assert f":{len(mutated.splitlines())}:" in lines[0]  # the appended return line


def test_cli_diff_untracked_file_reports_full(tmp_path):
    repo = _diff_repo(tmp_path)
    (repo / "seed.py").write_text("x = 1\n")
    _git(repo, "add", "."), _git(repo, "commit", "-qm", "seed")
    target = repo / "errcode_fixture.py"
    with open(fixture("errcode_fixture.py")) as f:
        target.write_text(f.read())
    proc = _cli("--check", "error-code-registry", "--diff", "HEAD", str(target))
    assert proc.returncode == 1
    assert len([l for l in proc.stdout.splitlines() if "[error-code-registry]" in l]) == 4


def test_cli_diff_bad_ref_is_usage_error():
    proc = _cli("--check", "error-code-registry", "--diff", "no-such-ref",
                fixture("errcode_fixture.py"))
    assert proc.returncode == 2
    assert "no-such-ref" in proc.stderr


# ---------------------------------------------------------------------------
# the tentpole invariant: the package itself lints clean
# ---------------------------------------------------------------------------


def test_package_lints_clean():
    fs = lint_paths([PACKAGE], require_reason=True)
    assert fs == [], "\n".join(str(f) for f in fs)


def test_cli_clean_on_package():
    proc = _cli("--require-reason", PACKAGE)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stderr
