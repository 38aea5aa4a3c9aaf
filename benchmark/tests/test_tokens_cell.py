"""The cell ``philly512-trinity.train`` (ISSUE 30), rehearsed on the CPU at
the configuration's tiny shape through ``run.execute``: every check is
read and holds; a fault planted in the PROGRAM underneath a whole run
(one held expert computing nothing) turns ``correct`` false by a number
the ledger names; the trunk's scope reader takes the program's names from
the configuration's file and leaves its metrics out where there is nothing
to read. The sound rehearsal runs beside a second ``tokens`` configuration
file that has none of this trunk's keys (ISSUE 35): nothing in the harness
looks at a file the cell does not name. That file is written into a copy
of the harness's data under ``tmp_path`` (``common.BENCH_DIR``, where
``load_json`` finds ``configs/``, ``traffic/`` and ``peaks.json``), never
into the checkout."""
import argparse
import json
import os
import shutil

import pytest

from benchmark import common
from benchmark import run as bench_run
from benchmark.readers import trunk_scope_time, xplane_scopes

CELL = "philly512-trinity.train"
# The configuration's limits are readings of the published widths on the
# chip. The rehearsal's trunk is 64 wide and its rows 20 tokens long, so
# bfloat16's rounding is a larger share of every number: the rehearsal is
# held to the same numbers at the tiny shape's own scale (its sound
# readings at seeds 5, 6 and 2147483655: log_prob_gap 2.2e-4 to 4.0e-4,
# loss gaps up to 0.0093, param_change_tree_gap 0.0018 to 0.014).
TINY_LIMITS = {"log_prob_gap": 1.2e-3, "loss_gap_first": 0.05,
               "loss_gap_later": 0.05, "param_change_tree_gap": 0.05}


def _execute(seed: int = 5, trace: int = 0):
    from rlgpuschedule_tpu.utils.platform import device_record
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5,
                              trace=trace, rehearse_cpu=True)
    loaded = common.load_cell(CELL)
    assert set(loaded["config"]["limits"]["train_loop"]) == set(TINY_LIMITS)
    loaded["config"]["limits"]["train_loop"] = dict(TINY_LIMITS)
    line, checks = bench_run.execute(args, loaded, device_record())
    return line, checks


# a later configuration's file, as far as this cell is concerned: the same
# ``obs_kind``, its own reference, none of the ``afmoe`` keys
STRANGER = {"name": "zz-test-tokens-stranger", "obs_kind": "tokens",
            "reference": "forward_of_another_trunk", "hidden_size": 64}


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    data = tmp_path_factory.mktemp("benchmark")
    for name in ("configs", "traffic"):
        shutil.copytree(os.path.join(common.BENCH_DIR, name), data / name)
    shutil.copy(os.path.join(common.BENCH_DIR, "peaks.json"), data)
    with open(data / "configs" / (STRANGER["name"] + ".json"), "w") as f:
        json.dump(STRANGER, f)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(common, "BENCH_DIR", str(data))
        return _execute()


def test_the_cells_files_are_found_by_name():
    loaded = common.load_cell(CELL)
    assert loaded["config"]["obs_kind"] == "tokens"
    assert loaded["config"]["reference"] == "forward_tokens"
    assert loaded["config"]["preset"] == "ppo-trinity-philly512"
    assert loaded["traffic"]["driver"] == "train_loop"
    assert loaded["cell"]["chips"] == 1


def _rows(checks) -> dict:
    return {r["check"]: r for r in checks.rows}


def test_sound_rehearsal_reads_every_check_and_holds_them(sound):
    line, checks = sound
    rows = _rows(checks)
    assert line["correct"] is False            # a CPU rehearsal, always
    assert checks.correct, rows                # the ledger itself holds
    assert set(rows) == {
        "compiles_in_window", "nonfinite_losses", "sim_state_mismatches",
        "masked_actions_taken", "rollout_untied_envs", "log_prob_gap",
        "loss_gap_first", "loss_gap_later", "param_change_tree_gap",
        "sim_time_gap", "sim_reward_gap"}
    assert list(line["checks"]) == [r["check"] for r in checks.rows]
    assert set(line["metrics"]) == {"setup_s", "env_steps_per_s"}


def test_one_held_expert_computing_nothing_is_not_correct(monkeypatch,
                                                          sound):
    """Expert 0 of every expert layer returns zeros in the program (its
    down projection zeroed inside the layer, the parameters as stated):
    the reference, which computes it, disagrees on the first iteration's
    log-probs row by row."""
    from rlgpuschedule_tpu.models import trunk
    whole = trunk.routed_experts

    def without_expert_0(x, gid, weight, w_gate, w_up, w_down):
        return whole(x, gid, weight, w_gate, w_up, w_down.at[0].set(0))

    monkeypatch.setattr(trunk, "routed_experts", without_expert_0)
    _, checks = _execute()
    rows, sound_rows = _rows(checks), _rows(sound[1])
    assert not checks.correct
    assert not rows["log_prob_gap"]["ok"], rows["log_prob_gap"]
    assert rows["log_prob_gap"]["value"] > 3 * sound_rows[
        "log_prob_gap"]["value"]
    # the simulator's half is untouched by it
    assert rows["sim_state_mismatches"]["ok"]
    assert rows["rollout_untied_envs"]["ok"]


def test_traced_rehearsal_reads_the_stages_and_leaves_the_trunk_scopes_out():
    """No device plane on the CPU: the outside-timed stages are read on
    the program's own state put back; every reader of the window's xplane,
    the trunk's among them, returns nothing and raises nothing."""
    line, checks = _execute(seed=6, trace=1)
    assert checks.correct, _rows(checks)
    assert {"rollout_ms.train", "update_ms.train", "advantage_ms.train",
            "resample_ms.train"} <= set(line["metrics"])
    assert not any(name.startswith(("trunk_", "moe_"))
                   for name in line["metrics"])


NAMES = frozenset(common.load_cell(CELL)["config"]["trunk_scopes"])


def test_the_configuration_file_lists_the_programs_names():
    from rlgpuschedule_tpu.obs import scopes
    assert NAMES == {n for path in scopes.TRUNK_TREE for n in path}
    assert not NAMES & {n for path in xplane_scopes.TREE for n in path}


def _events(ops):
    """One device plane, operations back to back, 1 ms each; two
    iterations on the host plane."""
    devices = [(f"%op.{i}", i * 1e6, 1e6, op_name, "trunk.py:1")
               for i, op_name in enumerate(ops)]
    host = [(xplane_scopes.ITERATION, 0.0, 1.0)] * 2
    return {"devices": {"/device:TPU:0": devices}, "host": host}


def _reduce_trunk(events):
    """As ``read`` does: the scope reader's table of operations, and each
    operation's ``op_name`` from the table the events were joined to."""
    plane = "/device:TPU:0"
    return trunk_scope_time.reduce_trunk(
        xplane_scopes.reduce_scopes(events, [plane]),
        {op: op_name for op, _, _, op_name, _ in events["devices"][plane]},
        NAMES)


def test_the_reader_adds_both_forward_passes_of_a_scope():
    fwd = ("jit(train_step)/rollout/while/body/policy_forward/"
           "ActorCritic/encoder/trunk/while/body/closed_call/layer_1/"
           "trunk_attn/attn/attn_sliding/dot_general")
    bwd = ("jit(train_step)/update/while/body/loss_grad/"
           "transpose(jvp(ActorCritic))/encoder/trunk/while/body/"
           "checkpoint/layer_1/trunk_attn/attn/q_proj/dot_general")
    moe = ("jit(train_step)/update/while/body/loss_grad/jvp(ActorCritic)/"
           "encoder/trunk/while/body/layer_2/moe/moe_experts/ragged_dot")
    sim = "jit(train_step)/rollout/while/body/env_step/vmap(observe)/add"
    reduced = _reduce_trunk(_events([fwd, bwd, bwd, moe, sim]))
    ms = lambda *under: xplane_scopes.scope_seconds(reduced, under) * 1e3
    assert reduced["iterations"] == 2
    assert ms("trunk_attn") == pytest.approx(1.5)       # 3 ms over 2
    assert ms("trunk_attn", "attn_sliding") == pytest.approx(0.5)
    assert ms("moe_experts") == pytest.approx(0.5)
    assert ms("trunk") == pytest.approx(2.0)
    assert ms("moe_route") == 0.0


def test_the_reader_returns_nothing_without_a_trunk():
    """A policy with no trunk (the CNN cell), or a commit before this
    one: no operation carries a name, and the metric is left out."""
    sim = "jit(train_step)/rollout/while/body/env_step/vmap(observe)/add"
    assert _reduce_trunk(_events([sim, sim])) is None
    config = {"trunk_scopes": sorted(NAMES)}
    assert trunk_scope_time.read({"trace": None, "config": config},
                                 {"under": ["trunk"]}) is None
    # a configuration that lists no names has no such metric: the trace is
    # not even looked at
    assert trunk_scope_time.read({"config": {}}, {"under": ["trunk"]}) \
        is None
