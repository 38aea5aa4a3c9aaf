"""The scope and span readers (ISSUE 27), held to a trimmed recording of
PR 27's own traced chip run (``fixtures/trace_scopes.json.gz``: the first
and the last 1,500 device operations of the window with their op_name
paths, and the ``rlsched:`` host events) and to small hand-made traces."""
from __future__ import annotations

import gzip
import json
import os

import pytest

from benchmark.readers import program_spans, scope_time, xplane_scopes as X

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(FIXTURES, "trace_scopes.json.gz"),
                   "rt") as f:
        events = json.load(f)
    with open(os.path.join(FIXTURES, "trace_scopes.expected.json")) as f:
        return events, json.load(f)


def probe_of(events, monkeypatch):
    """A probe whose xplane is ``events``: what ``run.py`` hands a reader,
    with the file's reading replaced."""
    monkeypatch.setattr(X, "newest_xplane", lambda: "recorded")
    monkeypatch.setattr(X, "read_xplane", lambda path: events)
    return {"trace": {"device_planes": sorted(events["devices"])},
            "cache": {}}


def test_tree_is_the_programs():
    from rlgpuschedule_tpu.obs import scopes
    assert X.TREE == scopes.TREE
    assert X.PREFIX == scopes.ANNOTATION_PREFIX
    assert X.ITERATION == scopes.TRAIN_ITERATION


def test_scope_path_strips_wrappers_and_keeps_order():
    assert X.scope_path(
        "jit(train_step)/rollout/while/body/closed_call/env_step/"
        "vmap(observe)/vmap(jit(searchsorted))/gather:") == \
        ("rollout", "env_step", "observe")
    assert X.scope_path("jit(train_step)/update/while/body/closed_call/"
                        "transpose(jvp(loss_grad))/mul:") == \
        ("update", "loss_grad")
    assert X.scope_path("reduce_window_sum:") == ()
    assert X.scope_path("jit(_shuffle)/updates/apply_fn") == ()


def test_recorded_scopes_sum_to_busy_time(recorded):
    events, expected = recorded
    planes = sorted(events["devices"])
    reduced = X.reduce_scopes(events, planes)
    total = sum(reduced["by_path"].values())
    assert total == pytest.approx(reduced["busy_s"], rel=1e-9)
    roots = ["rollout", "advantage", "update"]
    parts = sum(X.scope_seconds(reduced, [r]) for r in roots) \
        + X.outside_seconds(reduced, roots)
    assert parts == pytest.approx(reduced["busy_s"], rel=1e-9)
    line = X.scopes_line(reduced)
    assert line["busy_ms"] == pytest.approx(expected["busy_ms"])
    for path, want in expected["scopes_ms"].items():
        assert line["scopes"][path]["ms"] == pytest.approx(want), path
    assert line["unattributed_ms"] == pytest.approx(
        expected["unattributed_ms"])


def test_recorded_nested_scope_never_exceeds_its_parent(recorded):
    events, _ = recorded
    reduced = X.reduce_scopes(events, sorted(events["devices"]))
    for path in X.TREE:
        if len(path) > 1:
            assert X.scope_seconds(reduced, path) <= \
                X.scope_seconds(reduced, path[:-1]) + 1e-12, path


def test_recorded_heaviest_operation_is_under_one_leaf(recorded):
    """The ledger's 2.64 s operation (PR 26: ``fusion.751``) is in the
    recording and under exactly one leaf: observe."""
    events, _ = recorded
    line = X.scopes_line(X.reduce_scopes(events, sorted(events["devices"])))
    homes = [path for path, ops in line["heaviest_ops"].items()
             if any(op == "%fusion.751" for op, _, _ in ops)]
    assert len(homes) == 1 and homes[0].endswith("observe")
    (src,) = {s for ops in line["heaviest_ops"].values()
              for op, s, _ in ops if op == "%fusion.751"}
    assert src.endswith("rlgpuschedule_tpu/env/obs.py:138")


# no metric any more (ISSUE 29: it read where 2 ms sat), but the reader keeps
# the argument and the ``host_gaps`` line the reading: the case stays
RETIRED = {"idle_explained_share.train": {
    "reader": "program_spans", "args": {"idle_explained": True}}}


def test_recorded_metrics_through_the_readers(recorded, monkeypatch):
    events, expected = recorded
    probe = probe_of(events, monkeypatch)
    for name, want in expected["metrics"].items():
        m = RETIRED.get(name)
        if m is None:
            with open(os.path.join(os.path.dirname(FIXTURES),
                                   "layer_metrics", name + ".json")) as f:
                m = json.load(f)
        reader = {"scope_time": scope_time,
                  "program_spans": program_spans}[m["reader"]]
        assert reader.read(probe, m["args"]) == pytest.approx(want), name


def trace(devices, host):
    return {"devices": {"/device:TPU:0": [
        (op, s, d, op_name, "") for op, s, d, op_name in devices]},
        "host": host}


def test_self_time_goes_to_the_innermost_scope():
    # a while of 100 ns under rollout holds a 30 ns gather under observe
    # and a 20 ns dot under policy_forward; 10 ns of update follow
    events = trace([
        ("%while", 0, 100, "jit(f)/rollout/while:"),
        ("%gather", 10, 30, "jit(f)/rollout/while/body/env_step/"
                            "vmap(observe)/gather:"),
        ("%dot", 50, 20, "jit(f)/rollout/while/body/policy_forward/dot:"),
        ("%adam", 100, 10, "jit(f)/update/apply/add:"),
        ("%copy", 120, 5, ""),
    ], [(X.ITERATION, 0, 200), (X.ITERATION, 200, 10)])
    reduced = X.reduce_scopes(events, ["/device:TPU:0"])
    assert reduced["iterations"] == 2
    per = lambda *scope: X.scope_seconds(reduced, scope) * 1e9 * 2
    assert per("rollout") == pytest.approx(100)
    assert per("observe") == pytest.approx(30)
    assert per("rollout", "policy_forward") == pytest.approx(20)
    assert per("policy_forward", "rollout") == 0          # order matters
    assert per("update") == per("update", "apply") == pytest.approx(10)
    assert X.outside_seconds(reduced, ["rollout", "update"]) * 2e9 == \
        pytest.approx(5)
    assert reduced["busy_s"] * 1e9 == pytest.approx(115)


def test_gap_is_given_to_the_innermost_span_and_iteration_explains_none():
    # device idle 100..200, 300..310 and 320..360; "sync" (140..330)
    # lies inside the iteration (0..400), "step" (90..110) covers 10 ns
    # of the first gap and "sync" 10 ns of the last
    events = trace([("%a", 0, 100, ""), ("%b", 200, 100, ""),
                    ("%c", 310, 10, ""), ("%d", 360, 10, "")],
                   [(X.ITERATION, 0, 400), (X.PREFIX + "step", 90, 20),
                    (X.PREFIX + "sync", 140, 190)])
    spans = X.reduce_spans(events, ["/device:TPU:0"])
    assert spans["gaps"] == [[X.PREFIX + "sync", pytest.approx(100e-9)],
                             [X.ITERATION, pytest.approx(40e-9)],
                             [X.PREFIX + "sync", pytest.approx(10e-9)]]
    assert spans["idle_s"] == pytest.approx(150e-9)
    # 100..110 in step, 140..200, 300..310 and 320..330 in sync; the rest
    # only in the iteration, which explains nothing
    assert spans["explained_s"] == pytest.approx(90e-9)
    assert spans["median_ms"]["step"] == pytest.approx(20e-6)
    only_iteration = trace([("%a", 0, 10, ""), ("%b", 20, 10, "")],
                           [(X.ITERATION, 0, 40)])
    spans = X.reduce_spans(only_iteration, ["/device:TPU:0"])
    assert spans["gaps"] == [[X.ITERATION, pytest.approx(10e-9)]]
    assert spans["explained_s"] == 0


def test_a_program_without_scopes_or_spans_reads_as_nothing(monkeypatch):
    """The parent commit: operations without scope names, no ``rlsched:``
    event. Every reader returns ``None`` and raises nothing; so does a
    run with no device plane or no xplane at all."""
    plain = trace([("%fusion.1", 0, 10, "jit(train_step)/while/body/add:")],
                  [])
    for probe in (probe_of(plain, monkeypatch),
                  {"trace": {"device_planes": []}, "cache": {}},
                  {"trace": None, "cache": {}}):
        assert scope_time.read(probe, {"under": ["rollout"]}) is None
        assert scope_time.read(probe, {"outside": ["rollout"]}) is None
        assert program_spans.read(probe, {"median_ms_of": "step"}) is None
        assert program_spans.read(probe, {"idle_explained": True}) is None
    monkeypatch.setattr(X, "newest_xplane", lambda: None)
    assert scope_time.read({"trace": {"device_planes": ["x"]},
                            "cache": {}}, {"under": ["rollout"]}) is None


def test_op_metadata_reads_the_wire_format(tmp_path):
    """A hand-encoded XSpace: one device plane, stat names 7 -> tf_op and
    8 -> source, one event metadata with both."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def entry(key, message):
        return field(1, key) + field(2, message)

    stat = lambda sid, text: field(5, field(1, sid) + field(5, text))
    ev = field(1, 3) + field(2, b"%fusion.9 = s32[4] fusion()") \
        + stat(7, b"jit(f)/rollout/vmap(observe)/gather:") \
        + stat(8, b"obs.py:138") + stat(9, b"ignored")
    plane = field(2, b"/device:TPU:0") + field(4, entry(3, ev)) \
        + field(5, entry(7, field(1, 7) + field(2, b"tf_op"))) \
        + field(5, entry(8, field(1, 8) + field(2, b"source"))) \
        + field(5, entry(9, field(1, 9) + field(2, b"flops")))
    host = field(2, b"/host:CPU") + field(4, entry(1, field(2, b"x")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert X.op_metadata(str(path)) == {"/device:TPU:0": {
        "%fusion.9 = s32[4] fusion()":
            ("jit(f)/rollout/vmap(observe)/gather:", "obs.py:138")}}
