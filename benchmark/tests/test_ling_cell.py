"""The cell ``philly512-ling.train`` (ISSUE 39), rehearsed on the CPU at
the configuration's tiny shape through ``run.execute``: every check is
read and holds; a fault planted in the PROGRAM underneath a whole run (the
KDA layers' decay left out) turns ``correct`` false by a number the ledger
names; the configuration's file lists the names of the program's scope
tree for this family, and the trunk's scope reader adds up the new
layers' time by them."""
import argparse

import pytest

from benchmark import common
from benchmark import run as bench_run
from benchmark.readers import trunk_scope_time, xplane_scopes

CELL = "philly512-ling.train"
# The configuration's limits are readings of the published widths on the
# chip. The rehearsal's trunk is 64 wide, its rows 20 tokens long and its
# chunks 8, so bfloat16's rounding is a larger share of every number: the
# rehearsal is held to the same numbers at the tiny shape's own scale (its
# sound readings at seeds 5, 6 and 2147483655: log_prob_gap 7.4e-4 to
# 1.4e-3, loss_gap_first up to 0.042, loss_gap_later up to 0.198,
# param_change_tree_gap 0.0046 to 0.046), three times their largest.
TINY_LIMITS = {"log_prob_gap": 4.5e-3, "loss_gap_first": 0.13,
               "loss_gap_later": 0.6, "param_change_tree_gap": 0.14}


def _execute(seed: int = 5, trace: int = 0):
    from rlgpuschedule_tpu.utils.platform import device_record
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.5,
                              trace=trace, rehearse_cpu=True)
    loaded = common.load_cell(CELL)
    assert set(loaded["config"]["limits"]["train_loop"]) == set(TINY_LIMITS)
    loaded["config"]["limits"]["train_loop"] = dict(TINY_LIMITS)
    return bench_run.execute(args, loaded, device_record())


@pytest.fixture(scope="module")
def sound():
    return _execute()


def _rows(checks) -> dict:
    return {r["check"]: r for r in checks.rows}


def test_the_cells_files_are_found_by_name():
    loaded = common.load_cell(CELL)
    assert loaded["config"]["obs_kind"] == "tokens"
    assert loaded["config"]["reference"] == "forward_ling"
    assert loaded["config"]["preset"] == "ppo-ling-philly512"
    assert loaded["config"]["reference_block_rows"] == 1
    assert loaded["traffic"]["driver"] == "train_loop"
    assert loaded["cell"]["chips"] == 1


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


def test_the_decay_left_out_is_not_correct(monkeypatch, sound):
    """The KDA layers of the program keep their state undecayed (``g``
    zeroed on the way into the chunked rule, the parameters as stated):
    the reference, which decays it, disagrees on the first iteration's
    log-probs row by row."""
    from rlgpuschedule_tpu.ops import kda
    whole = kda.chunked_delta_rule

    def undecayed(q, k, v, g, beta, **kw):
        return whole(q, k, v, g * 0.0, beta, **kw)

    monkeypatch.setattr(kda, "chunked_delta_rule", undecayed)
    _, checks = _execute()
    rows, sound_rows = _rows(checks), _rows(sound[1])
    assert not checks.correct
    assert not rows["log_prob_gap"]["ok"], rows["log_prob_gap"]
    assert rows["log_prob_gap"]["value"] > 3 * sound_rows[
        "log_prob_gap"]["value"]
    # the simulator's half is untouched by it
    assert rows["sim_state_mismatches"]["ok"]
    assert rows["rollout_untied_envs"]["ok"]


NAMES = frozenset(common.load_cell(CELL)["config"]["trunk_scopes"])


def test_the_configuration_file_lists_the_programs_names():
    from rlgpuschedule_tpu.obs import scopes
    assert NAMES == {n for path in scopes.LING_TRUNK_TREE for n in path}
    assert not NAMES & {n for path in xplane_scopes.TREE for n in path}
    # the three metrics this cell adds read paths of that tree
    for name in ("attn_kda_scope_ms.train", "kda_scan_scope_ms.train",
                 "attn_mla_scope_ms.train"):
        metric = common.load_json("layer_metrics", name + ".json")
        assert metric["reader"] == "trunk_scope_time"
        assert ("trunk", *metric["args"]["under"]) in scopes.LING_TRUNK_TREE


def test_the_reader_adds_up_the_new_layers_by_the_files_names():
    pre = "jit(train_step)/update/while/body/loss_grad/"
    scan = (pre + "transpose(jvp(ActorCritic))/encoder/trunk/while/body/"
            "checkpoint/layer_1/trunk_attn/attn_kda/attn/kda_scan/while/"
            "body/dot_general")
    conv = ("jit(train_step)/rollout/while/body/policy_forward/ActorCritic/"
            "encoder/trunk/while/body/closed_call/layer_0/trunk_attn/"
            "attn_kda/attn/kda_conv/mul")
    proj = (pre + "jvp(ActorCritic)/encoder/trunk/while/body/layer_2/"
            "trunk_attn/attn_kda/attn/q_proj/dot_general")
    mla = (pre + "jvp(ActorCritic)/encoder/trunk/while/body/layer_5/"
           "trunk_attn/attn_mla/attn/o_proj/dot_general")
    ops = [scan, scan, conv, proj, mla]
    plane = "/device:TPU:0"
    events = {"devices": {plane: [
        (f"%op.{i}", i * 1e6, 1e6, op_name, "trunk.py:1")
        for i, op_name in enumerate(ops)]},
        "host": [(xplane_scopes.ITERATION, 0.0, 1.0)] * 2}
    reduced = trunk_scope_time.reduce_trunk(
        xplane_scopes.reduce_scopes(events, [plane]),
        {op: op_name for op, _, _, op_name, _ in events["devices"][plane]},
        NAMES)
    ms = lambda *under: xplane_scopes.scope_seconds(reduced, under) * 1e3
    assert ms("trunk_attn") == pytest.approx(2.5)           # 5 ms over 2
    assert ms("trunk_attn", "attn_kda") == pytest.approx(2.0)
    assert ms("trunk_attn", "attn_kda", "kda_scan") == pytest.approx(1.0)
    assert ms("trunk_attn", "attn_kda", "kda_conv") == pytest.approx(0.5)
    assert ms("trunk_attn", "attn_mla") == pytest.approx(0.5)
    assert ms("moe_experts") == 0.0
