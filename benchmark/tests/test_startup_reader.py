"""Readers ``startup_account`` and ``run_counters`` (ISSUE 46): set-up by
part, and the window's path counters, from the program's own start-up
account. Over a hand-made account, over a tree without one, and in a
traced rehearsal of one cell in a process of its own (the account is the
process's, so its ``t0`` is)."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import common
from benchmark.readers import run_counters
from benchmark.readers import startup_account as reader

METRICS = {
    "setup_import_s.train": "import", "setup_build_s.train": "build",
    "setup_trace_lower_s.train": "trace_lower",
    "setup_compile_s.train": "compile",
    "setup_cache_load_s.train": "cache_load",
    "setup_first_run_s.train": "first_run",
    "setup_outside_program_s.train": "outside_program"}
# metric -> (the counter it reads, the trunk scope it counts layers of,
# the cells whose configuration lists that scope)
COUNTERS = {
    "kda_kernel_layers.train": ("kda_kernel_layers", "attn_kda", ["ling"]),
    "attn_kernel_layers.train": ("attn_kernel_layers", "trunk_attn",
                                 ["trinity", "ling", "ouro"]),
    "moe_short_path_share.train": ("moe_short_path_share", "moe_experts",
                                   ["trinity", "ling"])}


@pytest.fixture
def hand_made(monkeypatch):
    """t0 = 1000. backend 1001-1002, build 1003-1006 (a trace 1004-1005
    inside), two first runs 1006-1008 and 1009-1010 (a cache load
    1006-1006.5 inside the first), the WINDOW's run 1012-1042, and after
    it the check's: a compile 1043-1050 and a span that began before the
    window and closed inside it."""
    import rlgpuschedule_tpu
    from rlgpuschedule_tpu.obs.startup import StartupAccount
    monkeypatch.setattr(rlgpuschedule_tpu, "EARLY_SPANS", [
        ("import", 1000.0, 1001.0), ("backend", 1001.0, 1002.0)])
    a = StartupAccount(1000.0, clock=lambda: 1060.0)
    a.add("build", 1003.0, 1006.0)
    a.add("run", 1006.0, 1008.0)
    a.add("run", 1009.0, 1010.0)
    a.spans[-1]["metrics"] = {"iteration": 0, "kda_kernel_layers": 3.0}
    a.add("build_upload", 1011.0, 1013.0)      # closes after the window began
    a.add("run", 1012.0, 1042.0)
    a.spans[-1].update(iterations=5, metrics={
        "iteration": 4, "total_loss": 0.25, "kda_kernel_layers": 0.0,
        "attn_kernel_layers": 1.0, "moe_short_path_share": 1.0})
    a.compiles.record(1.0, "trace", "init", end=1005.0)
    a.compiles.record(0.5, "cache_load", "train_step", end=1006.5)
    a.compiles.record(7.0, "compile", "reference_update", end=1050.0)
    return a


def test_the_window_is_the_last_run_and_setup_ends_before_it(hand_made):
    found = reader.reduced(hand_made)
    # set-up ends where the last first step ended, 1010: the span that
    # closed inside the window and the check's compile are in no part
    assert found["setup_s"] == 10.0 and found["tail_s"] == 2.0
    assert found["parts"] == {
        "import": 2.0, "build": 2.0, "trace_lower": 1.0, "compile": 0.0,
        "cache_load": 0.5, "first_run": 2.5, "outside_program": 2.0}
    assert sum(found["parts"].values()) == found["setup_s"]
    assert [p["fun"] for p in found["summary"]["programs"]] == [
        "init", "train_step"]
    assert found["summary"]["counts"]["backend_compiles"] == 1
    assert found["listener"]["callbacks"] == 4
    assert found["listener"]["seconds"] == pytest.approx(
        4 * found["listener"]["s_per_callback"])


def test_every_metric_file_reads_its_part_and_one_line_is_logged(
        hand_made, monkeypatch, capfd):
    monkeypatch.setattr(reader, "account", lambda: hand_made)
    probe = {"cache": {}}
    spec = {m["name"]: m for m in common.load_cell(
        "philly512-cnn.train")["spec"]["per_layer"]}
    total = 0.0
    for name, part in METRICS.items():
        m = common.load_json("layer_metrics", name + ".json")
        assert (m["reader"], m["args"], m["drivers"]) == (
            "startup_account", {"part": part}, ["train_loop"])
        assert (m["unit"], m["moves"], m["source"]) == (
            "s", "setup_s", "program_span")
        entry = spec[name]
        assert entry["layer"] == m["layer"] and entry["better"] == "lower"
        assert len(entry["workloads"]) == 4
        total += reader.read(probe, m["args"])
    assert total == 10.0
    lines = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == ["startup_account"]     # once
    assert lines[0]["counts"]["traces"] == 1 and lines[0]["tail_s"] == 2.0
    assert set(reader.PARTS) == set(lines[0]["parts"]) == set(
        METRICS.values())


def test_no_run_yet_reads_nothing(hand_made):
    kept = [s for s in hand_made.spans if s["name"] != "run"]
    hand_made.spans.clear()
    hand_made.spans.extend(kept)
    assert reader.reduced(hand_made) is None


def test_a_tree_without_the_account_reads_none(monkeypatch, capfd):
    # as on the parent commit: the module is not there to import
    monkeypatch.setitem(sys.modules, "rlgpuschedule_tpu.obs.startup", None)
    assert reader.account() is None
    probe = {}
    assert [reader.read(probe, {"part": p}) for p in reader.PARTS] == [
        None] * 7
    assert capfd.readouterr().out == ""


def test_counters_are_the_windows_last_iteration(hand_made, monkeypatch,
                                                 capfd):
    monkeypatch.setattr(run_counters, "account", lambda: hand_made)
    loaded = common.load_cell("philly512-ling.train")
    probe = {"cache": {}, "config": loaded["config"]}
    spec = {m["name"]: m for m in loaded["spec"]["per_layer"]}
    got = {}
    for name, (counter, scope, cells) in COUNTERS.items():
        m = common.load_json("layer_metrics", name + ".json")
        assert (m["reader"], m["args"], m["drivers"]) == (
            "run_counters", {"counter": counter, "scope": scope},
            ["train_loop"])
        entry = spec[name]
        assert (entry["layer"], entry["unit"], entry["source"]) == (
            m["layer"], m["unit"], m["source"])
        assert (entry["moves"], entry["better"]) == (
            "env_steps_per_s", "higher")
        assert entry["workloads"] == [f"philly512-{c}.train" for c in cells]
        got[name] = run_counters.read(probe, m["args"])
    # the window's record (the LAST run), not the first steps': 0 is a
    # value and comes back as one
    assert got == {"kda_kernel_layers.train": 0.0,
                   "attn_kernel_layers.train": 1.0,
                   "moe_short_path_share.train": 1.0}
    assert got["kda_kernel_layers.train"] is not None
    assert run_counters.read(probe, {"counter": "no_such_field",
                                     "scope": "trunk"}) is None
    lines = [json.loads(l) for l in capfd.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == ["run_counters"]          # once
    assert (lines[0]["window_iterations"], lines[0]["total_loss"]) == (
        5, 0.25)


@pytest.mark.parametrize("cell", ["cnn", "trinity", "ling", "ouro"])
def test_a_counter_is_read_where_the_configuration_lists_its_scope(
        cell, hand_made, monkeypatch):
    """A token trunk logs every counter of the family; a cell reports
    those whose layers its own trunk has (``BENCHMARK.json``'s
    ``workloads`` of each), the CNN cell none."""
    monkeypatch.setattr(run_counters, "account", lambda: hand_made)
    probe = {"config": common.load_cell(f"philly512-{cell}.train")["config"]}
    read = {name for name, (counter, scope, _) in COUNTERS.items()
            if run_counters.read(probe, {"counter": counter,
                                         "scope": scope}) is not None}
    assert read == {name for name, (_, _, cells) in COUNTERS.items()
                    if cell in cells}


def test_a_record_without_metrics_or_without_the_field_reads_none(
        hand_made, monkeypatch, capfd):
    monkeypatch.setattr(run_counters, "account", lambda: hand_made)
    config = common.load_cell("philly512-ling.train")["config"]
    args = {"counter": "attn_kernel_layers", "scope": "trunk_attn"}
    read = lambda: run_counters.read({"config": config}, args)
    assert read() == 1.0
    # a policy without a token trunk logs PPOMetrics alone
    hand_made.spans[-1]["metrics"] = {"iteration": 4, "total_loss": 0.25}
    assert read() is None
    # a call that logged nothing keeps nothing
    hand_made.spans[-1]["metrics"] = None
    assert read() is None
    # no run has ended
    hand_made.spans.clear()
    assert read() is None
    # as on the parent commit: no account to import
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "rlgpuschedule_tpu.obs.startup", None)
    assert read() is None
    out = capfd.readouterr().out
    assert [json.loads(l)["phase"] for l in out.splitlines()] == [
        "run_counters"] * 2         # the two records that had metrics


def test_traced_rehearsal_prints_the_seven_the_sum_and_the_counters():
    p = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", "philly512-ling.train", "--seed", str(2 ** 31 + 46),
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, cwd=common.ROOT, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines()]
    metrics = lines[-1]["metrics"]
    assert set(METRICS) <= set(metrics)
    setup = [l for l in lines if l.get("phase") == "setup"][0]
    total = sum(metrics[name]["value"] for name in METRICS)
    assert total == pytest.approx(setup["setup_s"], rel=0.02)
    assert all(metrics[name]["unit"] == "s" for name in METRICS)
    # the counts are the harness's own, and a union never exceeds the sum
    acct = [l for l in lines if l.get("phase") == "startup_account"][0]
    for key in ("traces", "backend_compiles", "cache_hits", "cache_misses"):
        assert acct["counts"][key] == setup[key], key
    compile_side = sum(metrics[f"setup_{part}_s.train"]["value"]
                       for part in ("trace_lower", "compile", "cache_load"))
    assert 0 < compile_side <= setup["compile_s"]
    assert acct["programs"][0]["fun"] in ("train_step", "init")
    # the path counters: no kernel runs on the CPU, and 0 is a value
    assert metrics["kda_kernel_layers.train"] == {"value": 0.0,
                                                  "unit": "layers"}
    assert metrics["attn_kernel_layers.train"]["value"] == 0.0
    assert 0.0 <= metrics["moe_short_path_share.train"]["value"] <= 1.0
    nulls = [l["metric"] for l in lines if l.get("phase") == "per_layer"]
    assert not set(COUNTERS) & set(nulls)
    counters = [l for l in lines if l.get("phase") == "run_counters"]
    assert len(counters) == 1 and "moe_dropped_assignments" in counters[0]
