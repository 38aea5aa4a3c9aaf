"""The looped token trunk (``models.trunk.OuroConfig``) on the normal path,
at its tiny shape on the CPU: the preset trains through ``Experiment.run``
with the loop's counters in every row and the exit gate left where it
was; ``train -> checkpoint -> serve -> evaluate -> select_checkpoint``
take ``--trunk ouro-tiny`` and no other new flag; the train CLI says once
what the trunk fixes. (The mathematics: ``tests/test_trunk_ouro.py``.)
"""
import dataclasses

import jax
import numpy as np

from rlgpuschedule_tpu.configs import CONFIGS


def tiny_experiment(**ppo):
    from rlgpuschedule_tpu.algos import PPOConfig
    from rlgpuschedule_tpu.experiment import Experiment
    cfg = dataclasses.replace(
        CONFIGS["ppo-ouro-philly512"], trunk="ouro-tiny", n_envs=4,
        n_nodes=2, gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
        ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2, **ppo))
    assert cfg.obs_kind == "tokens"
    return Experiment.build(cfg)


def test_preset_trains_and_the_exit_gate_stays_where_it_was():
    """Three iterations through ``Experiment.run``: finite losses, the
    loop's counters in every row and no constant among them; every layer
    moves and the exit gate's two leaves, which no gradient reaches, are
    bit for bit what they were."""
    exp = tiny_experiment()
    before = jax.device_get(exp.train_state.params)["params"]["encoder"]
    out = exp.run(iterations=3, log_every=1)
    after = jax.device_get(exp.train_state.params)["params"]["encoder"]
    assert len(out["history"]) == 3
    for h in out["history"]:
        assert np.isfinite(h["total_loss"])
        assert 0.0 < h["loop_exit_mass_last"] < 1.0
        assert h["loop_last_step_change"] > 0.0
        assert h["attn_kernel_layers"] == 0.0       # a CPU: the plain path
        assert h["moe_assignments_held"] == h["moe_dropped_assignments"] \
            == 0.0
        assert not {"loop_steps", "total_ut_steps", "layers"} & set(h)
    for name in ("kernel", "bias"):
        assert np.array_equal(before["exit_gate"][name],
                              after["exit_gate"][name])
    flat, _ = jax.tree_util.tree_flatten_with_path(
        {k: v for k, v in before.items() if k != "exit_gate"})
    for (path, a), b in zip(flat, jax.tree.leaves(
            {k: v for k, v in after.items() if k != "exit_gate"})):
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)


def test_preset_trains_checkpoints_serves_and_evaluates(tmp_path,
                                                        monkeypatch):
    """``ppo-ouro-philly512`` through ``train -> checkpoint -> serve ->
    evaluate`` as ``chip_smoke.py`` drives the other presets: the same
    three CLIs, phases and checks, at the tiny shape and trunk; then
    ``select_checkpoint`` ranks the checkpoints it left."""
    import chip_smoke
    from rlgpuschedule_tpu import select_checkpoint
    monkeypatch.setattr(chip_smoke, "CONFIG", "ppo-ouro-philly512")
    size = dict(chip_smoke.TINY,
                shape=[*chip_smoke.TINY["shape"], "--trunk", "ouro-tiny"])
    smoke = chip_smoke.Smoke()
    chip_smoke.run_one_chip(smoke, size, str(tmp_path), seed=0)
    assert smoke.ran == ["train", "serve", "evaluate"]
    assert not smoke.failed
    out = select_checkpoint.main(
        ["--config", "ppo-ouro-philly512", *size["shape"],
         "--ckpt-dir", str(tmp_path / "ckpt"), "--val-jobs", "48",
         "--val-seed", "77"])
    assert out["step"] in [s for _, s in out["ranking"]]


def test_train_cli_says_once_what_the_trunk_fixes(tmp_path):
    """The loop count and the layer count are the configuration's: the
    run's summary states them once (``trunk.describe``), and the
    iteration's rows carry the loop's counters, which vary with the data."""
    import csv

    from rlgpuschedule_tpu import train as train_cli
    path = tmp_path / "train.csv"
    summary = train_cli.main([
        "--config", "ppo-ouro-philly512", "--trunk", "ouro-tiny",
        "--n-envs", "4", "--n-nodes", "2", "--gpus-per-node", "4",
        "--window-jobs", "16", "--queue-len", "4", "--horizon", "64",
        "--n-steps", "8", "--n-epochs", "1", "--n-minibatches", "2",
        "--iterations", "2", "--log-every", "1", "--log-csv", str(path)])
    assert summary["trunk"] == {
        "name": "ouro-tiny", "family": "ouro", "layers": 2, "loop_steps": 3,
        "kda_layers": 0, "kda_chunk": 0, "moe_groups": 0,
        "moe_groups_kept": 0}
    with open(path) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2
    masses = [float(row["loop_exit_mass_last"]) for row in rows]
    assert all(0.0 < m < 1.0 for m in masses) and masses[0] != masses[1]
    for row in rows:
        assert float(row["loop_last_step_change"]) > 0.0
        assert "loop_steps" not in row and "total_ut_steps" not in row
