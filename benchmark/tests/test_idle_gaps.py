"""``trace_reduce.reduce_events`` names an idle gap by the INNERMOST host
span that covers it (ISSUE 29): ``train_run`` covers every gap of the
window, so the first full cover may not win."""
import pytest

from benchmark import trace_reduce


def test_gap_goes_to_the_shortest_of_the_spans_that_cover_most_of_it():
    # device idle 100..200 (inside step, iteration and train_run),
    # 300..340 (sync covers 300..320 only: the iteration is the shortest
    # full cover) and 500..510 (past every rlsched: span)
    events = {
        "devices": {"/device:TPU:0": [
            ["%a", 0.0, 100.0], ["%b", 200.0, 100.0], ["%c", 340.0, 160.0],
            ["%d", 510.0, 10.0]]},
        "host": [["train_run", 0.0, 600.0],
                 ["rlsched:train_iteration", 50.0, 400.0],
                 ["rlsched:step", 90.0, 150.0],
                 ["rlsched:sync", 250.0, 70.0]]}
    gaps = trace_reduce.reduce_events(events)["idle_gaps"]
    assert gaps == [["rlsched:step", pytest.approx(100e-9)],
                    ["rlsched:train_iteration", pytest.approx(40e-9)],
                    ["train_run", pytest.approx(10e-9)]]
    # the order of the host events does not decide
    events["host"].reverse()
    assert trace_reduce.reduce_events(events)["idle_gaps"] == gaps
    events["host"] = []
    assert [g[0] for g in trace_reduce.reduce_events(events)["idle_gaps"]] \
        == ["unattributed"] * 3
