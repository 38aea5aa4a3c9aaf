"""The start-up account (``obs.startup``): set-up's spans, the compile
intervals by program from the one listener (``analysis.sentinels``), the
exclusive summary, and where an operator sees it (``run_start``, the
report, ``train.py``'s summary). One tiny experiment is built ONCE for
the module; nothing here times anything against a limit."""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

import rlgpuschedule_tpu
from rlgpuschedule_tpu.analysis.sentinels import (KINDS, CompileCounter,
                                                  Coverage, program_name)
from rlgpuschedule_tpu.configs import CONFIGS
from rlgpuschedule_tpu.experiment import Experiment
from rlgpuschedule_tpu.obs import RunTelemetry, read_events, startup
from rlgpuschedule_tpu.obs import report as report_cli
from rlgpuschedule_tpu.obs.startup import ACCOUNT, StartupAccount
from rlgpuschedule_tpu.obs.trace import _union as union

TINY = dataclasses.replace(
    CONFIGS["ppo-mlp-synth64"], n_envs=2, window_jobs=16, horizon=64,
    ppo=dataclasses.replace(CONFIGS["ppo-mlp-synth64"].ppo, n_steps=8,
                            n_epochs=1, n_minibatches=2))
PARTS = ("import_s", "backend_s", "build_s", "trace_lower_s", "compile_s",
         "cache_load_s", "run_s", "unnamed_s")


@pytest.fixture(scope="module")
def built():
    """``(exp, the build's span records)``; the first run call (which
    compiles the step) is made here too."""
    first = ACCOUNT.opened
    exp = Experiment.build(TINY)
    spans = [s for s in ACCOUNT.snapshot()["spans"]
             if s.get("index", -1) >= first]     # (the early spans: none)
    exp.run(iterations=1, log_every=1)
    return exp, spans


def last_run() -> dict:
    return [s for s in ACCOUNT.snapshot()["spans"] if s["name"] == "run"][-1]


def fresh(scale: float):
    """A jitted function no cache has seen: the constant is the clock."""
    c = scale + time.time() % 1.0

    def never_seen_before(v):
        return v * c + 1.0
    return jax.jit(never_seen_before)


# ---- the spans -----------------------------------------------------------

def test_build_children_nest_are_exclusive_and_add_up(built):
    _, spans = built
    build = spans[0]
    assert build["name"] == "build" and build["parent"] is None
    kids = [s for s in spans[1:] if s["parent"] == build["index"]]
    assert [k["name"] for k in kids] == [
        "build_source", "build_windows", "build_upload", "build_policy",
        "build_upload", "build_carry", "build_train_state", "build_step"]
    assert len(kids) == len(spans) - 1         # nothing nests deeper
    for a, b in zip(kids, kids[1:]):
        assert build["start"] <= a["start"] <= a["end"] <= b["start"]
    assert kids[-1]["end"] <= build["end"]
    wall = build["end"] - build["start"]
    assert sum(k["end"] - k["start"] for k in kids) == pytest.approx(
        wall, rel=0.05)
    # counts at the same boundaries: the counters as of any instant
    at = [ACCOUNT.compiles.counts(until=t)["traces"]
          for t in (build["start"], kids[-1]["start"], build["end"])]
    assert at[0] <= at[1] <= at[2] and at[0] < at[2]
    assert set(build) == {"name", "start", "end", "parent", "index"}


def test_build_phases_are_bus_spans_with_telemetry(tmp_path):
    with RunTelemetry(str(tmp_path), rank=0, trace=True) as tel:
        Experiment.build(TINY, telemetry=tel)
    begun = [(e["span"], e["depth"]) for e in read_events(tel.bus.path)
             if e["kind"] == "span_begin"]
    assert begun[0] == ("build", 0)
    assert {name for name, depth in begun[1:] if depth == 1} == {
        "build_source", "build_windows", "build_upload", "build_policy",
        "build_carry", "build_train_state", "build_step"}


def test_run_adds_one_record_and_nothing_an_iteration(built):
    exp, _ = built
    before, events = ACCOUNT.opened, ACCOUNT.compiles.n_events
    exp.run(iterations=3)
    assert ACCOUNT.opened == before + 1
    assert ACCOUNT.compiles.n_events == events     # no compile interval
    run = last_run()
    assert (run["name"], run["iterations"]) == ("run", 3)
    assert list(run["sections"]) == ["step"]        # kept, not thrown away
    # and shown: the summary adds the run calls' sections up
    shown = ACCOUNT.summary()["run_sections"]
    assert shown["step"] >= run["sections"]["step"] > 0
    assert run["metrics"] is None       # nothing was logged: nothing kept
    def size():
        return ACCOUNT.opened + ACCOUNT.compiles.n_events
    before = size()
    out = exp.run(iterations=50, log_every=10)
    assert size() == before + 1     # 50 iterations: the same one record
    # the last logged iteration's metrics, as the call returned them
    assert len(out["history"]) == 6
    assert last_run()["metrics"] == out["history"][-1]
    assert last_run()["metrics"]["iteration"] == 49
    assert set(last_run()) == set(run)      # the same one record's keys


def test_run_with_telemetry_keeps_the_sections_where_they_were(tmp_path,
                                                               built):
    exp, _ = built
    with RunTelemetry(str(tmp_path), rank=0) as tel:
        exp.run(iterations=2, log_every=1, telemetry=tel)
    assert sorted(tel.sections.report()) == ["step", "sync"]
    assert last_run()["sections"] == {}
    start = [e for e in read_events(tel.bus.path)
             if e["kind"] == "run_start"][0]["startup"]
    assert sum(start[k] for k in PARTS) == pytest.approx(
        start["until_s"], abs=1e-4)
    assert start["build_s"] > 0 and start["counts"]["traces"] > 0


def test_async_run_is_one_record_and_reports_its_own_sections(built):
    from rlgpuschedule_tpu.parallel import split_devices
    exp = Experiment.build(TINY)
    before = ACCOUNT.opened
    out = exp.run_async(iterations=2, log_every=1, staleness_bound=0,
                        groups=split_devices(devices=jax.devices()[:1]))
    runs = [s for s in ACCOUNT.snapshot()["spans"]
            if s["name"] == "run" and s["index"] >= before]
    assert len(runs) == 1 and runs[0]["iterations"] == 2
    # this call's seconds, not the process's
    assert sorted(out["phase_seconds"]) == sorted(runs[0]["sections"])
    assert "actor" in runs[0]["sections"]


# ---- the compile listener ------------------------------------------------

def test_fresh_function_is_found_by_name_and_a_second_call_adds_nothing():
    f = fresh(2.0)
    x = jnp.ones((3, 7))
    mark = ACCOUNT.compiles.n_events
    f(x).block_until_ready()
    row = ACCOUNT.compiles.programs_since(mark)["never_seen_before"]
    assert row["trace_s"] > 0 and row["lower_s"] > 0
    assert row["compile_s"] > 0 and row["cache_load_s"] == 0
    assert (row["traces"], row["lowerings"], row["compiles"],
            row["cache_loads"]) == (1, 1, 1, 0)
    after = ACCOUNT.compiles.n_events
    f(x).block_until_ready()
    assert ACCOUNT.compiles.n_events == after


def test_second_compile_after_clear_caches_is_a_cache_load():
    """The suite's conftest keeps every compile in the persistent cache,
    keyed by the call's own stack too: both calls are ONE line's."""
    f = fresh(3.0)
    x = jnp.ones((5, 3))
    with CompileCounter() as c:
        for again in (False, True):
            if again:
                first = c.counts()
                jax.clear_caches()
            f(x).block_until_ready()
    row = c.programs()["never_seen_before"]
    assert (row["compiles"], row["cache_loads"]) == (1, 1)
    assert (first["cache_hits"], first["cache_misses"]) == (0, 1)
    assert (c.cache_hits, c.cache_misses, c.backend_compiles) == (1, 1, 2)
    kinds = [kind for _, _, kind, name in c.events
             if name == "never_seen_before"]
    assert kinds == ["trace", "lower", "compile",
                     "trace", "lower", "cache_load"]
    # counts and the table as of an instant: what had closed by then
    first_end = [e for e in c.events if e[2] == "compile"][0][1]
    assert c.counts(until=first_end) == first
    then = c.programs(until=first_end)["never_seen_before"]
    assert (then["traces"], then["compiles"], then["cache_loads"]) == (
        1, 1, 0)
    assert then["compile_s"] == pytest.approx(row["compile_s"])


def test_nested_traces_are_a_union_not_a_sum():
    @jax.jit
    def inner_fn(v):
        return jnp.tanh(v) * 2.0

    def outer_fn(v):
        return inner_fn(v) + inner_fn(v + 1.0).sum()

    x = jnp.ones((4, 9))        # its own tiny program, outside the scope
    with CompileCounter() as c:
        jax.jit(outer_fn).lower(x)
    traces = [(a, b) for a, b, what, _ in c.events if what == "trace"]
    outer = [(a, b) for a, b, what, name in c.events
             if what == "trace" and name == "outer_fn"]
    assert len(outer) == 1 and len(traces) > 1
    covered = sum(b - a for a, b in union(traces))
    assert covered <= (outer[0][1] - outer[0][0]) + 1e-3
    assert sum(b - a for a, b in traces) > covered      # the sum is longer
    # the counter's own union, kept as the events arrived
    everything = union((a, b) for a, b, _, _ in c.events)
    assert c.covered(0.0, float("inf")) == pytest.approx(
        sum(b - a for a, b in everything))
    assert c.covered(*outer[0]) == pytest.approx(outer[0][1] - outer[0][0])
    assert c.exclusive(0.0, float("inf"))["compile_s"] == 0.0
    assert set(c.programs()) >= {"outer_fn", "inner_fn"}


def test_program_name_is_one_for_a_programs_three_events():
    assert program_name("jit(train_step)") == "train_step"
    assert program_name("train_step") == "train_step"


def test_recompile_alarm_names_the_function_that_recompiled(tmp_path):
    def step_under_alarm(v):
        return v * 2.0

    step = jax.jit(step_under_alarm)
    args = [jnp.ones((4,)), jnp.ones((4,)), jnp.ones((6,))]  # a new shape
    with RunTelemetry(str(tmp_path), rank=0, alarms=True,
                      transfer_guard=False) as tel:
        for i, x in enumerate(args):
            with tel.dispatch(i):
                step(x).block_until_ready()
    events = read_events(tel.bus.path)
    warm = [e for e in events if e["kind"] == "compile"]
    again = [e for e in events if e["kind"] == "recompile"]
    assert [e["iteration"] for e in warm] == [0]
    assert [e["iteration"] for e in again] == [2]
    for e in warm + again:
        named = {p["fun"]: p for p in e["programs"]}
        mine = named["step_under_alarm"]
        assert mine["trace_s"] > 0 and mine["lower_s"] > 0
        assert mine["compile_s"] > 0
        assert set(mine) == {"fun", "trace_s", "lower_s", "compile_s",
                             "cache_hit"}
        assert e["events"] >= 2          # the fields that were there stay


# ---- the summary ---------------------------------------------------------

def test_summary_parts_add_up_to_the_interval(built):
    for until in (None, ACCOUNT.spans[-1]["start"], ACCOUNT.t0 + 0.5):
        s = ACCOUNT.summary(until=until)
        assert sum(s[k] for k in PARTS) == pytest.approx(
            s["until_s"], abs=1e-3)
        assert all(s[k] >= -1e-9 for k in PARTS)     # sums of floats
        assert sum(s["build_by_phase"].values()) == pytest.approx(
            s["build_s"])
    assert s["build_s"] == s["run_s"] == 0      # half a second in: imports
    assert s["import_s"] > 0
    whole = ACCOUNT.summary()
    assert whole["build_s"] > 0 and whole["run_s"] > 0
    assert len(whole["programs"]) == 10
    assert whole["programs"][0]["fun"] in ACCOUNT.compiles.programs()
    assert whole["counts"]["programs"] >= 10


def test_summary_is_exclusive_on_a_hand_made_account(monkeypatch):
    """t0 = 100: import 100-102, backend 102-103, build 104-110 with a
    child 105-108, a trace 105.5-107.5 holding a compile 106-107, a run
    110-115 with a cache load 111-112."""
    a = StartupAccount(100.0, clock=lambda: 120.0)
    build = a._open("build", 104.0)
    a._stack().append(build)
    a.add("build_carry", 105.0, 108.0)
    a._stack().pop()
    build["end"] = 110.0
    a.add("run", 110.0, 115.0)
    a.compiles.record(1.0, "compile", "init", end=107.0)
    a.compiles.record(2.0, "trace", "init", end=107.5)
    a.compiles.record(1.0, "cache_load", "train_step", end=112.0)
    # the early spans: an import inside another is counted once
    monkeypatch.setattr(rlgpuschedule_tpu, "EARLY_SPANS", [
        ("import", 100.0, 102.0), ("import", 100.5, 101.0),
        ("backend", 102.0, 103.0)])
    s = a.summary(until=116.0)
    cut = a.summary(until=106.5)
    assert a.spans[1]["parent"] == build["index"] == 0
    assert (s["import_s"], s["backend_s"]) == (2.0, 1.0)
    assert (s["compile_s"], s["trace_lower_s"], s["cache_load_s"]) == (
        1.0, 1.0, 1.0)
    assert s["build_by_phase"] == {"build_carry": 1.0, "build": 3.0}
    assert (s["build_s"], s["run_s"], s["unnamed_s"]) == (4.0, 4.0, 2.0)
    assert [p["fun"] for p in s["programs"]] == ["init", "train_step"]
    assert s["counts"] == {"traces": 1, "lowerings": 0,
                           "backend_compiles": 2, "cache_hits": 1,
                           "cache_misses": 0, "programs": 2}
    # cut inside the compile: intervals are clipped, events that had not
    # closed are not counted
    assert (cut["compile_s"], cut["trace_lower_s"]) == (0.5, 0.5)
    assert cut["build_by_phase"] == {"build_carry": 0.5, "build": 1.0}
    assert cut["counts"]["backend_compiles"] == 0
    assert cut["until_s"] == 6.5 and cut["unnamed_s"] == 1.0


def test_threads_share_one_account_without_losing_a_record(monkeypatch):
    """More threads than cores, a short switch interval: every span and
    every callback is kept, indices are unique and a span's parent is a
    span of its own thread."""
    import os
    import sys
    import threading
    from rlgpuschedule_tpu.analysis.sentinels import TRACE_EVENT
    n_threads, n_each = 2 * (os.cpu_count() or 4), 200
    monkeypatch.setattr(startup, "MAX_SPANS", 2 * n_threads * n_each)
    a = StartupAccount(0.0)

    def work():
        for _ in range(n_each):
            with a.span("outer"):
                with a.span("inner"):
                    a.compiles._duration(TRACE_EVENT, 1e-6, fun_name="f")
                    a.compiles.covered(0.0, 1e9)        # a reader, meanwhile

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, name=f"w{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    assert len(a.spans) == a.opened == 2 * n_threads * n_each
    assert [s["index"] for s in a.spans] == list(range(len(a.spans)))
    assert a.compiles.traces == len(a.compiles.events) == n_threads * n_each
    assert a.compiles.programs()["f"]["traces"] == n_threads * n_each
    for s in a.spans:
        assert s["end"] is not None
        if s["name"] == "inner":
            parent = a.spans[s["parent"]]
            assert parent["name"] == "outer"
            assert parent["start"] <= s["start"] <= s["end"] <= parent["end"]
        else:
            assert s["parent"] is None


def test_the_account_holds_the_newest_span_records(monkeypatch):
    """Past the cap the oldest records go; the indices keep counting, the
    first run call stays at hand and what went reads as unnamed."""
    monkeypatch.setattr(startup, "MAX_SPANS", 4)
    ticks = iter(range(1000))
    a = StartupAccount(0.0, clock=lambda: float(next(ticks)))
    for _ in range(6):
        with a.span("run", metrics=None):
            pass                        # (0, 1), (2, 3), ... (10, 11)
    assert (len(a.spans), a.opened) == (4, 6)
    assert [s["index"] for s in a.spans] == [2, 3, 4, 5]
    assert (a.first_run["index"], a.first_run["end"]) == (0, 1.0)
    s = a.summary(until=12.0)
    assert (s["run_s"], s["unnamed_s"]) == (4.0, 8.0)


@pytest.mark.parametrize("lo, hi, seconds", [
    (0, 10, 6.0),           # (0.5, 3) + (4, 5) + (6.5, 9)
    (2, 4.5, 1.5),          # both ends inside an interval
    (3, 4, 0.0),            # a gap
    (8.5, 20, 0.5),
    (-1, 0.5, 0.0),
])
def test_coverage_is_the_union_of_what_arrived(lo, hi, seconds):
    c = Coverage()
    for interval in [(1, 2), (1.5, 2.5), (0.5, 3), (4, 5), (6, 6), (7, 8),
                     (6.5, 9)]:         # in the order of their ends
        c.add(*interval)
    assert c.seconds(lo, hi) == seconds
    with pytest.raises(ValueError):
        c.add(0, 1)


def test_the_counter_holds_the_newest_events_and_sums_the_rest(monkeypatch):
    from rlgpuschedule_tpu.analysis import sentinels
    monkeypatch.setattr(sentinels, "RECENT_EVENTS", 8)
    c = CompileCounter()
    for i in range(20):     # one top-level trace a second, a jit inside
        c.record(0.25, "trace", "inner", end=i + 0.5)
        c.record(0.5, "trace", f"outer{i % 2}", end=i + 0.75)
    assert (c.n_events, len(c.events)) == (40, 8)
    assert c.covered(0.0, 100.0) == 10.0            # the outer ones' union
    assert c.programs()["inner"] == {
        "trace_s": 5.0, "lower_s": 0.0, "compile_s": 0.0,
        "cache_load_s": 0.0, "traces": 20, "lowerings": 0, "compiles": 0,
        "cache_loads": 0}
    # as of an instant the held events reach back to: exact
    assert c.counts(until=18.0)["traces"] == 36
    assert c.programs(until=18.0)["outer1"]["traces"] == 9
    assert sorted(c.programs_since(38)) == ["inner", "outer1"]
    # a mark older than what is held: what is held
    assert sum(r["traces"] for r in c.programs_since(0).values()) == 8


def test_kinds_are_the_four_an_interval_is_spent_on():
    assert KINDS == ("trace", "lower", "compile", "cache_load")


# ---- where an operator sees it -------------------------------------------

def test_first_run_summary_ends_with_the_first_run_call(built):
    s = startup.first_run_summary()
    first = ACCOUNT.first_run
    assert first["name"] == "run" and first["index"] == min(
        [r["index"] for r in ACCOUNT.spans if r["name"] == "run"]
        + [first["index"]])
    assert s["until_s"] == pytest.approx(first["end"] - ACCOUNT.t0, abs=1e-5)
    json.dumps(s)                                   # plain data


def test_report_prints_the_startup_table_from_a_recorded_stream(tmp_path,
                                                                built,
                                                                capsys):
    exp, _ = built
    with RunTelemetry(str(tmp_path), rank=0) as tel:
        exp.run(iterations=1, log_every=1, telemetry=tel)
    assert report_cli.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "start-up table (rank 0: process start to run start" in text
    assert text.index("start-up table") < text.index("phase-time table")
    for label in ("import", "backend", "build_train_state", "trace + lower",
                  "cache load", "(unnamed)", "program"):
        assert label in text


def test_chip_smoke_phases_read_the_programs_listener(capsys):
    import chip_smoke
    assert not hasattr(chip_smoke, "CompileMeter")
    smoke = chip_smoke.Smoke()
    assert smoke.compiles is ACCOUNT.compiles
    f = fresh(5.0)
    smoke.run("train", lambda rec: f(jnp.ones((2, 11))).block_until_ready())
    rec = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rec["ok"] and rec["backend_compiles"] >= 1
    assert 0 < rec["compile_s"] <= rec["wall_s"]
    assert rec["run_s"] == pytest.approx(rec["wall_s"] - rec["compile_s"],
                                         abs=2e-3)
    assert rec["cache_hits"] + rec["cache_misses"] <= rec["backend_compiles"]
