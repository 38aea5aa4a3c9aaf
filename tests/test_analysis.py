"""jsan static-analyzer tests (PR 3, extended by PRs 15 and 18): one
known-good + known-bad fixture pair per rule, the thread-aware
concurrency rules, the refusal-matrix drift checker, the value-lifetime
rules (view-escape / use-after-recycle / donated-alias-reuse /
torn-publish), the cross-surface contract-drift checker, the --cache
incremental mode, suppression + baseline workflows (including
--prune-baseline / --fail-stale), JSON + SARIF output (now with column
regions), --diff / --explain, the exit-code contract, and the
acceptance gates — the shipped tree is clean with an EMPTY baseline,
and seeding any known-bad snippet into a tree makes the CLI exit
nonzero.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from rlgpuschedule_tpu.analysis import (analyze_paths, apply_baseline,
                                        make_baseline)
from rlgpuschedule_tpu.analysis.engine import (FindingCache, SKIP_DIRS,
                                               analyze_file,
                                               iter_py_files)
from rlgpuschedule_tpu.analysis.rules import rule_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "jsan")

# rule -> (bad fixture, expected finding count in it)
BAD = {
    "donation-discipline": ("bad_donation.py", 2),
    "host-sync": ("bad_host_sync.py", 4),
    "tracer-leak": ("bad_tracer_leak.py", 3),
    "impure-in-jit": ("bad_impure.py", 3),
    "recompile-hazard": ("bad_recompile.py", 2),
    "prng-key-reuse": ("bad_prng_reuse.py", 3),
    "sync-in-loop": ("bad_sync_in_loop.py", 3),
    "unconstrained-intermediate":
        ("bad_unconstrained_intermediate.py", 2),
    "compile-off-thread": ("bad_compile_off_thread.py", 3),
    "device-dispatch-unlocked": ("bad_device_dispatch_unlocked.py", 3),
    "donation-cross-thread": ("bad_donation_cross_thread.py", 1),
    "shared-state-unlocked": ("bad_shared_state_unlocked.py", 2),
    "blocking-under-lock": ("bad_blocking_under_lock.py", 3),
    "hung-future": ("bad_hung_future.py", 3),
    "alloc-in-hot-loop": ("bad_alloc_in_hot_loop.py", 3),
    "refusal-drift": (os.path.join("refusal_bad", "train.py"), 2),
    "view-escape": ("bad_view_escape.py", 4),
    "use-after-recycle": ("bad_use_after_recycle.py", 3),
    "donated-alias-reuse": ("bad_donated_alias_reuse.py", 2),
    "torn-publish": ("bad_torn_publish.py", 2),
    "contract-drift": ("contract_bad", 5),   # directory fixture
}
GOOD = ["good_donation.py", "good_host_sync.py", "good_tracer_leak.py",
        "good_impure.py", "good_recompile.py", "good_prng_reuse.py",
        "good_sync_in_loop.py",
        "good_unconstrained_intermediate.py",
        "good_compile_off_thread.py",
        "good_device_dispatch_unlocked.py",
        "good_donation_cross_thread.py",
        "good_shared_state_unlocked.py",
        "good_blocking_under_lock.py",
        "good_hung_future.py",
        "good_alloc_in_hot_loop.py",
        os.path.join("refusal_good", "configs.py"),
        os.path.join("refusal_good", "train.py"),
        "good_view_escape.py", "good_use_after_recycle.py",
        "good_donated_alias_reuse.py", "good_torn_publish.py",
        "contract_good"]                       # directory fixture


def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "rlgpuschedule_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd,
        env={**os.environ, "PYTHONPATH": REPO})


class TestRules:
    @pytest.mark.parametrize("rule", sorted(BAD))
    def test_bad_fixture_fires_the_rule(self, rule):
        fname, expected = BAD[rule]
        findings = analyze_paths([os.path.join(FIXTURES, fname)])
        assert len(findings) == expected, findings
        assert {f.rule for f in findings} == {rule}, findings

    @pytest.mark.parametrize("fname", GOOD)
    def test_good_fixture_is_clean(self, fname):
        assert analyze_paths([os.path.join(FIXTURES, fname)]) == []

    def test_registry_covers_every_fixture_rule(self):
        assert set(BAD) == set(rule_names())


class TestSuppressions:
    def test_inline_suppression_silences_one_rule(self, tmp_path):
        bad = open(os.path.join(FIXTURES, "bad_prng_reuse.py")).read()
        patched = bad.replace(
            "b = jax.random.uniform(key, (4,))",
            "b = jax.random.uniform(key, (4,))  "
            "# jsan: disable=prng-key-reuse -- test")
        p = tmp_path / "patched.py"
        p.write_text(patched)
        findings = analyze_paths([str(p)])
        assert len(findings) == BAD["prng-key-reuse"][1] - 1

    def test_comment_line_above_suppresses_next_line(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n\n"
            "def f(key):\n"
            "    a = jax.random.normal(key)\n"
            "    # jsan: disable=prng-key-reuse -- deliberate twin draw\n"
            "    b = jax.random.normal(key)\n"
            "    return a, b\n")
        assert analyze_paths([str(p)]) == []

    def test_unrelated_rule_name_does_not_suppress(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n\n"
            "def f(key):\n"
            "    a = jax.random.normal(key)\n"
            "    b = jax.random.normal(key)  # jsan: disable=host-sync\n"
            "    return a, b\n")
        assert [f.rule for f in analyze_paths([str(p)])] \
            == ["prng-key-reuse"]


class TestWalker:
    def test_fixture_dirs_are_skipped_in_tree_walks(self):
        assert "fixtures" in SKIP_DIRS
        walked = list(iter_py_files([os.path.join(REPO, "tests")]))
        assert not any("fixtures" in p for p in walked)
        # but explicit file arguments are always analyzed
        explicit = os.path.join(FIXTURES, "bad_impure.py")
        assert list(iter_py_files([explicit])) == [explicit]


class TestCLI:
    def test_shipped_tree_is_clean(self):
        """Acceptance gate: the analyzer exits 0 over the shipped
        package + top-level scripts (everything fixed or suppressed)."""
        r = _cli("rlgpuschedule_tpu", "chip_smoke.py",
                 "__graft_entry__.py")
        assert r.returncode == 0, r.stdout + r.stderr

    def test_seeded_bad_snippet_fails_the_tree(self, tmp_path):
        """Acceptance gate: seeding any one known-bad fixture into an
        otherwise-clean tree makes the CLI exit nonzero."""
        tree = tmp_path / "pkg"
        tree.mkdir()
        shutil.copy(os.path.join(FIXTURES, "good_donation.py"),
                    tree / "clean.py")
        r = _cli(str(tree), cwd=str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        shutil.copy(os.path.join(FIXTURES, "bad_host_sync.py"),
                    tree / "seeded.py")
        r = _cli(str(tree), cwd=str(tmp_path))
        assert r.returncode == 1
        assert "[host-sync]" in r.stdout

    def test_json_output_is_stable_and_sorted(self):
        paths = [os.path.join(FIXTURES, f) for f, _ in
                 (BAD["prng-key-reuse"], BAD["recompile-hazard"])]
        r1 = _cli(*paths, "--format", "json", "--no-baseline")
        r2 = _cli(*reversed(paths), "--format", "json", "--no-baseline")
        assert r1.returncode == r2.returncode == 1
        out1, out2 = json.loads(r1.stdout), json.loads(r2.stdout)
        assert out1 == out2            # argument order doesn't matter
        keys = [(f["path"], f["line"], f["col"], f["rule"])
                for f in out1["findings"]]
        assert keys == sorted(keys)    # sorted output
        assert out1["count"] == len(out1["findings"])

    def test_list_rules(self):
        r = _cli("--list-rules")
        assert r.returncode == 0
        for name in rule_names():
            assert name in r.stdout


class TestBaseline:
    def test_baseline_round_trips(self, tmp_path):
        """--write-baseline over a dirty tree, then a normal run with
        that baseline, exits 0; and the baseline file itself is stable
        (sorted, deterministic) across regenerations."""
        bad = os.path.join(FIXTURES, "bad_tracer_leak.py")
        base = tmp_path / "baseline.json"
        r = _cli(bad, "--write-baseline", str(base))
        assert r.returncode == 0, r.stdout + r.stderr
        first = base.read_text()
        r = _cli(bad, "--baseline", str(base))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "baselined" in r.stdout
        r = _cli(bad, "--write-baseline", str(base))
        assert base.read_text() == first           # byte-stable
        entries = json.loads(first)["entries"]
        assert entries == sorted(
            entries, key=lambda e: (e["rule"], e["path"], e["snippet"]))

    def test_baseline_survives_line_drift(self, tmp_path):
        """Identity is (rule, path, snippet): inserting lines above a
        grandfathered finding must not resurrect it."""
        src = open(os.path.join(FIXTURES, "bad_recompile.py")).read()
        p = tmp_path / "mod.py"
        p.write_text(src)
        findings = analyze_paths([str(p)])
        assert findings
        baseline = {(f.rule, f.path, f.snippet) for f in findings}
        p.write_text("# pushed\n# down\n# three lines\n" + src)
        drifted = analyze_paths([str(p)])
        assert [f.line for f in drifted] != [f.line for f in findings]
        assert apply_baseline(drifted, baseline) == []

    def test_new_findings_are_not_masked_by_baseline(self, tmp_path):
        findings = analyze_paths(
            [os.path.join(FIXTURES, "bad_impure.py")])
        baseline = {f.baseline_key for f in findings[:1]}
        kept = apply_baseline(findings, baseline)
        assert len(kept) == len(findings) - 1

    def test_make_baseline_matches_engine_format(self):
        findings = analyze_paths(
            [os.path.join(FIXTURES, "bad_donation.py")])
        data = make_baseline(findings)
        assert data["version"] == 1
        assert all(set(e) == {"rule", "path", "snippet"}
                   for e in data["entries"])


class TestRepoBaselineFile:
    def test_committed_baseline_is_valid_and_minimal(self):
        """The committed jsan_baseline.json must parse and contain only
        entries that still match a real finding — stale grandfather
        entries hide future regressions at the same line."""
        path = os.path.join(REPO, "jsan_baseline.json")
        with open(path) as f:
            data = json.load(f)
        assert data["version"] == 1
        current = {f.baseline_key for f in analyze_paths(
            [os.path.join(REPO, "rlgpuschedule_tpu"),
             os.path.join(REPO, "chip_smoke.py"),
             os.path.join(REPO, "__graft_entry__.py")])}
        stale = [e for e in data["entries"]
                 if (e["rule"], e["path"], e["snippet"]) not in current]
        assert stale == [], f"stale baseline entries: {stale}"

    def test_shipped_tree_has_zero_findings_without_baseline(self):
        """PR-15 acceptance: the full package is clean on its own —
        the committed baseline is EMPTY, nothing is grandfathered."""
        findings = analyze_paths(
            [os.path.join(REPO, "rlgpuschedule_tpu"),
             os.path.join(REPO, "chip_smoke.py"),
             os.path.join(REPO, "__graft_entry__.py")])
        assert findings == [], [f"{f.path}:{f.line} [{f.rule}]"
                                for f in findings]
        with open(os.path.join(REPO, "jsan_baseline.json")) as f:
            assert json.load(f)["entries"] == []


class TestConcurrencyRules:
    """Workflow round-trips for the thread-aware rules (the per-rule
    counts live in BAD/GOOD above)."""

    def test_inline_suppression_silences_concurrency_finding(self,
                                                             tmp_path):
        bad = open(os.path.join(
            FIXTURES, "bad_blocking_under_lock.py")).read()
        patched = bad.replace(
            "item = self._q.get()",
            "item = self._q.get()  "
            "# jsan: disable=blocking-under-lock -- test")
        p = tmp_path / "patched.py"
        p.write_text(patched)
        findings = analyze_paths([str(p)])
        assert len(findings) == BAD["blocking-under-lock"][1] - 1
        assert {f.rule for f in findings} == {"blocking-under-lock"}

    def test_baseline_survives_line_drift_for_concurrency_rule(
            self, tmp_path):
        src = open(os.path.join(
            FIXTURES, "bad_shared_state_unlocked.py")).read()
        p = tmp_path / "mod.py"
        p.write_text(src)
        findings = analyze_paths([str(p)])
        assert findings
        baseline = {f.baseline_key for f in findings}
        p.write_text("# pushed\n# down\n" + src)
        drifted = analyze_paths([str(p)])
        assert apply_baseline(drifted, baseline) == []

    def test_condition_alias_counts_as_the_wrapped_lock(self, tmp_path):
        """Dropping the Condition's wrapped-lock argument decouples the
        two regions and the good shared-state fixture goes bad — the
        alias recognition is load-bearing, not decorative."""
        src = open(os.path.join(
            FIXTURES, "good_shared_state_unlocked.py")).read()
        p = tmp_path / "mod.py"
        p.write_text(src.replace("threading.Condition(self._lock)",
                                 "threading.Condition()"))
        findings = analyze_paths([str(p)])
        assert [f.rule for f in findings] == ["shared-state-unlocked"]


class TestRefusalDrift:
    @pytest.mark.parametrize("fname,count,needle", [
        (os.path.join("refusal_bad", "configs.py"), 1,
         "no reachable guard"),
        (os.path.join("refusal_bad", "train.py"), 2, "delta"),
        (os.path.join("refusal_bad", "evaluate.py"), 1,
         "refused pair"),
    ])
    def test_bad_fixture_counts_and_messages(self, fname, count, needle):
        findings = analyze_paths([os.path.join(FIXTURES, fname)])
        assert len(findings) == count, findings
        assert {f.rule for f in findings} == {"refusal-drift"}
        assert any(needle in f.message for f in findings), findings

    def test_adhoc_raise_is_flagged(self):
        findings = analyze_paths(
            [os.path.join(FIXTURES, "refusal_bad", "train.py")])
        assert any("ad-hoc" in f.message for f in findings)

    def test_real_table_rows_are_all_guarded(self):
        """The shipped MODE_REFUSALS table has a guard for every row
        (this is what the PR-15 production fixes bought)."""
        findings = analyze_paths(
            [os.path.join(REPO, "rlgpuschedule_tpu", "configs.py")])
        assert [f for f in findings if f.rule == "refusal-drift"] == []


class TestContractDrift:
    """Cross-surface contract checker: the bad fixture tree drifts in
    all five ways (ghost metric, orphan metric, ghost kind, orphan
    kind, stale wire golden); the good twin exercises the allowlist,
    the f-string registration pattern, and the local-registration
    exemption and stays clean."""

    @pytest.mark.parametrize("needle,tail", [
        ("no code registers it", "ci.sh"),            # ghost metric
        ("'pipe_dropped_total' is registered", "pipeline.py"),  # orphan
        ("no code emits it", "test_gates.py"),        # ghost kind
        ("'debug_tick' is emitted", "pipeline.py"),   # orphan kind
        ("disagree with the frame constants", "test_gates.py"),  # wire
    ])
    def test_bad_tree_drifts_in_each_family(self, needle, tail):
        findings = analyze_paths(
            [os.path.join(FIXTURES, "contract_bad")])
        hits = [f for f in findings if needle in f.message]
        assert len(hits) == 1, findings
        assert hits[0].path.replace(os.sep, "/").endswith(tail)
        assert hits[0].rule == "contract-drift"

    def test_fixture_tree_self_roots_at_its_own_ci_sh(self):
        """The root walk stops at the fixture's own ci.sh — nothing
        from the real repo's surfaces leaks into fixture verdicts."""
        findings = analyze_paths(
            [os.path.join(FIXTURES, "contract_bad")])
        assert findings
        assert all("contract_bad" in f.path for f in findings)

    def test_real_wire_golden_matches_frame_constants(self):
        """The committed TestGoldenBytes pin in tests/test_wire.py is
        the witness the wire direction of the rule checks against."""
        findings = analyze_paths([os.path.join(
            REPO, "rlgpuschedule_tpu", "serve", "wire.py")])
        assert [f for f in findings
                if f.rule == "contract-drift"] == [], findings


class TestCache:
    """--cache DIR incremental mode: entries keyed on (file sha1,
    rule-set hash); cross-file rules are never served from cache."""

    def test_warm_hit_returns_identical_findings(self, tmp_path):
        cache = FindingCache(str(tmp_path / "c"))
        bad = os.path.join(FIXTURES, "bad_prng_reuse.py")
        cold = analyze_file(bad, cache=cache)
        assert cold and cache.misses >= 1 and cache.hits == 0
        warm = analyze_file(bad, cache=cache)
        assert warm == cold
        assert cache.hits >= 1

    def test_warm_second_run_is_faster(self, tmp_path):
        cdir = str(tmp_path / "c")
        pkg = os.path.join(REPO, "rlgpuschedule_tpu", "analysis")
        t0 = time.monotonic()
        cold = analyze_paths([pkg], cache_dir=cdir)
        t_cold = time.monotonic() - t0
        t0 = time.monotonic()
        warm = analyze_paths([pkg], cache_dir=cdir)
        t_warm = time.monotonic() - t0
        assert warm == cold
        assert t_warm < t_cold, (t_warm, t_cold)

    def test_cli_cache_flag_round_trips(self, tmp_path):
        bad = os.path.join(FIXTURES, "bad_host_sync.py")
        cdir = tmp_path / "jc"
        r1 = _cli(bad, "--no-baseline", "--cache", str(cdir))
        r2 = _cli(bad, "--no-baseline", "--cache", str(cdir))
        assert r1.returncode == r2.returncode == 1
        assert r1.stdout == r2.stdout
        assert any(cdir.iterdir())             # entries were written

    def test_corrupt_cache_entry_degrades_to_miss(self, tmp_path):
        cache = FindingCache(str(tmp_path / "c"))
        bad = os.path.join(FIXTURES, "bad_impure.py")
        cold = analyze_file(bad, cache=cache)
        for p in (tmp_path / "c").iterdir():
            p.write_text("not json")
        again = analyze_file(bad, cache=cache)
        assert again == cold

    def test_cross_file_rule_findings_survive_a_warm_run(self, tmp_path):
        """refusal-drift is cross-file: its verdict depends on other
        files, so the warm run re-derives it instead of replaying."""
        bad = os.path.join(FIXTURES, "refusal_bad", "train.py")
        cdir = str(tmp_path / "c")
        cold = analyze_paths([bad], cache_dir=cdir)
        warm = analyze_paths([bad], cache_dir=cdir)
        assert warm == cold
        assert {f.rule for f in warm} == {"refusal-drift"}


class TestSarif:
    def test_sarif_output_is_schema_shaped(self):
        fname, expected = BAD["blocking-under-lock"]
        r = _cli(os.path.join(FIXTURES, fname), "--format", "sarif",
                 "--no-baseline")
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        assert len(doc["runs"]) == 1
        driver = doc["runs"][0]["tool"]["driver"]
        assert driver["name"] == "jsan"
        assert {r_["id"] for r_ in driver["rules"]} == set(rule_names())
        assert all(r_["shortDescription"]["text"] for r_ in driver["rules"])
        results = doc["runs"][0]["results"]
        assert len(results) == expected
        for res in results:
            assert res["ruleId"] in set(rule_names())
            assert res["message"]["text"]
            assert res["partialFingerprints"]["jsanFindingId/v1"]
            loc = res["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"].endswith(".py")
            assert loc["region"]["startLine"] >= 1
            assert loc["region"]["startColumn"] >= 1
            # PR-18: column regions so editors can underline; endColumn
            # is exclusive, so it strictly exceeds startColumn
            assert loc["region"]["endLine"] >= loc["region"]["startLine"]
            assert loc["region"]["endColumn"] \
                > loc["region"]["startColumn"]

    def test_sarif_clean_tree_has_empty_results(self, tmp_path):
        p = tmp_path / "clean.py"
        p.write_text("X = 1\n")
        r = _cli(str(p), "--format", "sarif", cwd=str(tmp_path))
        assert r.returncode == 0
        assert json.loads(r.stdout)["runs"][0]["results"] == []


class TestDiff:
    def _git(self, cwd, *args):
        return subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=cwd, capture_output=True, text=True, check=True)

    def test_diff_restricts_to_changed_files(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        a = tmp_path / "a.py"
        b = tmp_path / "b.py"
        bad = open(os.path.join(FIXTURES, "bad_prng_reuse.py")).read()
        a.write_text("X = 1\n")
        b.write_text(bad)
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-qm", "seed")
        a.write_text(bad)                    # a changes, b does not
        r = _cli(".", "--diff", "HEAD", "--no-baseline",
                 cwd=str(tmp_path))
        assert r.returncode == 1, r.stdout + r.stderr
        assert "a.py" in r.stdout
        assert "b.py" not in r.stdout

    def test_diff_with_no_changes_exits_clean(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        (tmp_path / "a.py").write_text("X = 1\n")
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-qm", "seed")
        r = _cli(".", "--diff", "HEAD", cwd=str(tmp_path))
        assert r.returncode == 0
        assert "no analyzable files changed" in r.stdout

    def test_diff_bad_rev_is_invocation_error(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        (tmp_path / "a.py").write_text("X = 1\n")
        r = _cli(".", "--diff", "no-such-rev", cwd=str(tmp_path))
        assert r.returncode == 2
        assert "git diff" in r.stderr


class TestExplain:
    def test_explain_prints_rule_rationale(self):
        r = _cli("--explain", "refusal-drift")
        assert r.returncode == 0
        assert "MODE_REFUSALS" in r.stdout
        r = _cli("--explain", "compile-off-thread")
        assert r.returncode == 0
        assert "PR-8" in r.stdout or "compile" in r.stdout

    def test_explain_unknown_rule_is_invocation_error(self):
        r = _cli("--explain", "no-such-rule")
        assert r.returncode == 2
        assert "unknown rule" in r.stderr


class TestExitCodeContract:
    def test_findings_exit_1_with_stable_ids(self):
        fname, _ = BAD["shared-state-unlocked"]
        r = _cli(os.path.join(FIXTURES, fname), "--no-baseline")
        assert r.returncode == 1
        assert "id: shared-state-unlocked@" in r.stdout
        r2 = _cli(os.path.join(FIXTURES, fname), "--no-baseline")
        assert r.stdout == r2.stdout       # IDs are deterministic

    def test_unparsable_input_exits_2(self, tmp_path):
        p = tmp_path / "nul.py"
        p.write_bytes(b"x = 1\x00\n")       # ast.parse raises ValueError
        r = _cli(str(p), cwd=str(tmp_path))
        assert r.returncode == 2
        assert "internal error" in r.stderr or "cannot parse" in r.stderr

    def test_missing_path_exits_2(self):
        r = _cli("definitely/not/a/path.py")
        assert r.returncode == 2
        assert "no such path" in r.stderr


class TestBaselineMaintenance:
    def test_fail_stale_flags_dead_entries(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(open(os.path.join(
            FIXTURES, "bad_recompile.py")).read())
        base = tmp_path / "baseline.json"
        r = _cli("bad.py", "--write-baseline", "baseline.json",
                 cwd=str(tmp_path))
        assert r.returncode == 0
        # with live entries, --fail-stale is quiet
        r = _cli("bad.py", "--baseline", "baseline.json", "--fail-stale",
                 cwd=str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        # fix the file: the baseline entries go stale and the gate trips
        bad.write_text("X = 1\n")
        r = _cli("bad.py", "--baseline", "baseline.json", "--fail-stale",
                 cwd=str(tmp_path))
        assert r.returncode == 1
        assert "stale baseline entry" in r.stderr
        assert base.exists()

    def test_prune_baseline_drops_only_stale_entries(self, tmp_path):
        (tmp_path / "bad.py").write_text(open(os.path.join(
            FIXTURES, "bad_recompile.py")).read())
        (tmp_path / "bad2.py").write_text(open(os.path.join(
            FIXTURES, "bad_prng_reuse.py")).read())
        r = _cli("bad.py", "bad2.py", "--write-baseline",
                 "baseline.json", cwd=str(tmp_path))
        assert r.returncode == 0
        (tmp_path / "bad2.py").write_text("X = 1\n")   # half goes stale
        r = _cli("bad.py", "bad2.py", "--baseline", "baseline.json",
                 "--prune-baseline", cwd=str(tmp_path))
        assert r.returncode == 0
        assert "pruned" in r.stdout
        entries = json.loads(
            (tmp_path / "baseline.json").read_text())["entries"]
        assert entries                         # live entries kept
        assert all(e["path"] == "bad.py" for e in entries)
        # after the prune the gate is quiet again
        r = _cli("bad.py", "bad2.py", "--baseline", "baseline.json",
                 "--fail-stale", cwd=str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
