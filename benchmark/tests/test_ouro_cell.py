"""The cell ``philly512-ouro.train`` (ISSUE 41), rehearsed on the CPU at
the configuration's tiny shape through ``run.execute``: every check is
read and holds; a fault planted in the PROGRAM underneath a whole run (the
loop run one step short; RoPE left off; the closing norm applied once,
after the last step, instead of in every step; half the batch left out of
the update; the update at half the stated step) turns ``correct`` false by
a number the ledger names; the configuration's file lists the names of the
program's scope tree for this family, and the trunk's scope reader adds up
the loop's time by them."""
import argparse
import dataclasses

import pytest

from benchmark import common
from benchmark import run as bench_run
from benchmark.readers import trunk_scope_time, xplane_scopes

CELL = "philly512-ouro.train"
# The configuration's limits are readings of the published widths on the
# chip. The rehearsal's trunk is 32 wide and its rows 20 tokens long, so
# the same three numbers are held at the tiny shape's own scale, each
# between the largest of its sound readings at seeds 5, 6, 7 and 2147483655
# (log_prob_gap 1.1e-4 to 2.3e-4, loss_gap_first 3e-5 to 0.0078,
# param_change_tree_gap 0.0001 to 0.038) and the smallest that half the
# batch left out of the PROGRAM's update reads there (0.114 and 0.219).
# The cell reads loss_gap_later and holds it to nothing (its file says
# why), so the rehearsal does not either (sound up to 0.0067 here).
TINY_LIMITS = {"log_prob_gap": 7e-4, "loss_gap_first": 0.03,
               "param_change_tree_gap": 0.1}


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
    config = loaded["config"]
    assert config["obs_kind"] == "tokens"
    assert config["reference"] == "forward_ouro"
    assert config["preset"] == "ppo-ouro-philly512"
    assert config["reference_block_rows"] == 4
    assert loaded["traffic"]["driver"] == "train_loop"
    assert loaded["cell"]["chips"] == 1
    # the traffic ISSUE 41 fixes: 8 envs x 2 steps, one ROW_BLOCK a minibatch
    cfg = common.resolve_config(config, 1, False)
    assert (cfg.n_envs, cfg.ppo.n_steps, cfg.ppo.n_epochs,
            cfg.ppo.n_minibatches) == (8, 2, 4, 4)
    assert (cfg.window_jobs, cfg.queue_len, cfg.n_nodes,
            cfg.resample_every) == (768, 128, 64, 200)
    assert cfg.trunk == "ouro"
    assert common.resolve_config(config, 1, True).trunk == "ouro-tiny"
    spec = loaded["spec"]
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"trunk_loop_scope_ms.train", "loop_gate_scope_ms.train",
            "trunk_attn_scope_ms.train", "trunk_dense_mlp_scope_ms.train",
            "update_mfu.train"} <= listed
    assert not {n for n in listed if n.startswith(("moe_", "attn_kda",
                                                   "kda_", "attn_mla"))}
    assert len(spec["workloads"]) == 4
    assert all(w["chips"] == 1 for w in spec["workloads"])


def test_sound_rehearsal_reads_every_check_and_holds_them(sound):
    line, checks = sound
    rows = _rows(checks)
    assert line["correct"] is False            # a CPU rehearsal, always
    assert checks.correct, rows                # the ledger itself holds
    assert set(rows) == {
        "compiles_in_window", "nonfinite_losses", "sim_state_mismatches",
        "masked_actions_taken", "rollout_untied_envs", "log_prob_gap",
        "loss_gap_first", "param_change_tree_gap", "sim_time_gap",
        "sim_reward_gap"}
    assert list(line["checks"]) == [r["check"] for r in checks.rows]
    assert set(line["metrics"]) == {"setup_s", "env_steps_per_s"}


def _one_step_short(monkeypatch):
    from rlgpuschedule_tpu.models import trunk
    tiny = trunk.TRUNKS["ouro-tiny"]
    monkeypatch.setitem(trunk.TRUNKS, "ouro-tiny", dataclasses.replace(
        tiny, total_ut_steps=tiny.total_ut_steps - 1))


def _rope_left_off(monkeypatch):
    from rlgpuschedule_tpu.models import trunk
    monkeypatch.setattr(trunk, "rope", lambda x, theta, gain=1.0: x)


def _closing_norm_once(monkeypatch):
    """The steps hand their raw output on, and the norm acts once, on what
    the pool reads (its scale is ones at the seeded weights the first
    iteration's forward runs on)."""
    import jax
    import jax.numpy as jnp

    from rlgpuschedule_tpu.models import trunk
    whole, pool = trunk.RMSNorm.__call__, trunk.pool

    def norm(self, x, gain=1.0):
        y = whole(self, x, gain)
        return x if self.name == "final_norm" else y

    def normed_pool(x, valid):
        x32 = x.astype(jnp.float32)
        return pool(x32 * jax.lax.rsqrt(jnp.mean(
            jnp.square(x32), axis=-1, keepdims=True) + 1e-6), valid)

    monkeypatch.setattr(trunk.RMSNorm, "__call__", norm)
    monkeypatch.setattr(trunk, "pool", normed_pool)


@pytest.mark.parametrize("plant", [_one_step_short, _rope_left_off,
                                   _closing_norm_once])
def test_a_planted_fault_is_not_correct(monkeypatch, sound, plant):
    """The reference, which runs every step, rotates q and k and closes
    every step with the norm, disagrees with the faulty program on the
    first iteration's log-probs row by row."""
    plant(monkeypatch)
    _, checks = _execute()
    rows, sound_rows = _rows(checks), _rows(sound[1])
    assert not checks.correct
    assert not rows["log_prob_gap"]["ok"], rows["log_prob_gap"]
    assert rows["log_prob_gap"]["value"] > 3 * sound_rows[
        "log_prob_gap"]["value"]
    # the simulator's half is untouched by it
    assert rows["sim_state_mismatches"]["ok"]
    assert rows["rollout_untied_envs"]["ok"]


def _half_the_batch(monkeypatch):
    """The update runs over the first half of the batch only, in
    minibatches of the stated size (half the optimizer steps):
    ``reference/ppo.FAULTS``' ``half_batch``, planted in the program."""
    import jax

    from rlgpuschedule_tpu.algos import update
    whole = update.run_minibatch_epochs

    def half(grad_step, state, data, key, *, n_epochs, n_minibatches,
             minibatch_size=None):
        data = jax.tree.map(lambda x: x[:x.shape[0] // 2], data)
        return whole(grad_step, state, data, key, n_epochs=n_epochs,
                     n_minibatches=n_minibatches // 2,
                     minibatch_size=minibatch_size)

    monkeypatch.setattr(update, "run_minibatch_epochs", half)


def _half_the_step(monkeypatch):
    from rlgpuschedule_tpu.experiment import Experiment
    build = Experiment.build
    monkeypatch.setattr(Experiment, "build", staticmethod(
        lambda cfg, *a, **kw: build(dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, lr=cfg.ppo.lr / 2)),
            *a, **kw)))


@pytest.mark.parametrize("plant,failed", [
    (_half_the_batch, ("loss_gap_first", "param_change_tree_gap")),
    (_half_the_step, ("param_change_tree_gap",))])
def test_a_fault_of_the_update_is_not_correct(monkeypatch, sound, plant,
                                              failed):
    """The forward pass on the seeded weights is the sound one (the same
    ``log_prob_gap``, held); the learning numbers are not: half the batch
    fails the first loss gap and the tree's change (1.74 and 0.39 here),
    half the step the tree's change (about 0.5). The cell held no number
    that half the batch failed until its preset's step was set for a
    looped trunk (PERF.md section 2, PR 41)."""
    plant(monkeypatch)
    _, checks = _execute()
    rows, sound_rows = _rows(checks), _rows(sound[1])
    assert not checks.correct
    assert rows["log_prob_gap"]["value"] == sound_rows[
        "log_prob_gap"]["value"] and rows["log_prob_gap"]["ok"]
    for name in failed:
        assert not rows[name]["ok"], rows[name]
        assert rows[name]["value"] > 3 * TINY_LIMITS[name]


NAMES = frozenset(common.load_cell(CELL)["config"]["trunk_scopes"])
NEW = ("trunk_loop_scope_ms.train", "loop_gate_scope_ms.train")


def test_the_configuration_file_lists_the_programs_names():
    from rlgpuschedule_tpu.obs import scopes
    assert NAMES == {n for path in scopes.OURO_TRUNK_TREE for n in path}
    assert not NAMES & {n for path in xplane_scopes.TREE for n in path}
    # the two metrics this cell adds read paths of that tree, on the
    # standing reader, as data alone
    for name in NEW:
        metric = common.load_json("layer_metrics", name + ".json")
        assert metric["reader"] == "trunk_scope_time"
        assert ("trunk", *metric["args"]["under"]) in scopes.OURO_TRUNK_TREE
    # the standing metrics' paths are found under the loop (a subsequence)
    for name in ("trunk_attn_scope_ms.train",
                 "trunk_dense_mlp_scope_ms.train"):
        under = common.load_json("layer_metrics", name + ".json")["args"][
            "under"]
        assert ("trunk", "trunk_loop", *under) in scopes.OURO_TRUNK_TREE


def test_the_reader_adds_up_the_loop_by_the_files_names():
    pre = "jit(train_step)/update/while/body/loss_grad/"
    loop = "encoder/trunk/trunk_loop/while/body/"
    proj = (pre + "jvp(ActorCritic)/" + loop + "checkpoint/layer_3/"
            "trunk_attn/attn/q_proj/dot_general")
    kernel = (pre + "transpose(jvp(ActorCritic))/" + loop + "while/body/"
              "checkpoint/layer_3/trunk_attn/attn/attn_full/pallas_call")
    mlp = ("jit(train_step)/rollout/while/body/policy_forward/ActorCritic/"
           + loop + "while/body/closed_call/layer_0/trunk_dense_mlp/mlp/up/"
           "dot_general")
    gate = (pre + "jvp(ActorCritic)/" + loop + "loop_gate/exit_gate/"
            "dot_general")
    accumulate = pre + "transpose(jvp(ActorCritic))/" + loop + "add_any"
    pool = pre + "jvp(ActorCritic)/encoder/trunk/trunk_pool/reduce_sum"
    ops = [proj, kernel, kernel, mlp, gate, accumulate, pool]
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
    args = lambda name: common.load_json(
        "layer_metrics", name + ".json")["args"]["under"]
    assert ms(*args(NEW[0])) == pytest.approx(3.0)          # 6 ms over 2
    assert ms(*args(NEW[1])) == pytest.approx(0.5)
    assert ms(*args("trunk_attn_scope_ms.train")) == pytest.approx(1.5)
    assert ms(*args("trunk_dense_mlp_scope_ms.train")) == pytest.approx(0.5)
    assert ms("trunk_loop", "trunk_attn", "attn_full") == pytest.approx(1.0)
    assert ms("trunk_pool") == pytest.approx(0.5)
    assert ms("moe_experts") == 0.0
