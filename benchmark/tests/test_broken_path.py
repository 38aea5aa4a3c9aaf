"""Drive the rest of a run past the harness's look for a chip, with the
timed path broken underneath, and see ``correct`` come out false - by the
comparison that is there to catch that fault."""
import argparse
import dataclasses

from benchmark import common
from benchmark import run as bench_run

CELL = "philly512-cnn.train"


def _execute(workload: str = CELL, seed: int = 5, trace: int = 0):
    from rlgpuschedule_tpu.utils.platform import device_record
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=trace, rehearse_cpu=True)
    line, checks = bench_run.execute(args, common.load_cell(workload),
                                     device_record())
    return line, {r["check"]: r for r in checks.rows}


def _break_build(monkeypatch, wrap_step=None, wrap_cfg=None):
    """``Experiment.build`` with the configuration or the built train step
    replaced; the benchmark's own resolved configuration stays as stated."""
    from rlgpuschedule_tpu.experiment import Experiment
    build = Experiment.build

    def broken_build(cfg, *a, **kw):
        exp = build(wrap_cfg(cfg) if wrap_cfg else cfg, *a, **kw)
        if wrap_step:
            exp.train_step = wrap_step(exp.train_step)
        return exp

    monkeypatch.setattr(Experiment, "build", staticmethod(broken_build))


def test_sound_train_path_holds_every_check():
    line, rows = _execute()
    assert line["correct"] is False            # a CPU rehearsal, always
    assert all(r["ok"] for r in rows.values()), rows
    for name in ("sim_state_mismatches", "masked_actions_taken",
                 "rollout_untied_envs", "compiles_in_window",
                 "nonfinite_losses", "loss_gap_first",
                 "param_change_tree_gap"):
        assert name in rows


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    """A fault the parameter-change norm is there to catch."""
    def lazy(step):
        def lazy_step(state, carry, traces, key, faults):
            import jax
            import jax.numpy as jnp
            keep = jax.tree.map(jnp.copy, state)
            _, carry, metrics = step(state, carry, traces, key, faults)
            return keep, carry, metrics
        return lazy_step

    _break_build(monkeypatch, wrap_step=lazy)
    _, rows = _execute()
    assert not rows["param_change_tree_gap"]["ok"], rows
    assert rows["param_change_tree_gap"]["value"] >= 0.99


def test_rollout_that_leaves_out_part_of_the_batch(monkeypatch):
    """Half the envs never step: the state the timed step returns no
    longer ties to the rollout, nor (where sampled) to the oracle."""
    def half(step):
        def half_step(state, carry, traces, key, faults):
            import jax
            import jax.numpy as jnp
            old = jax.tree.map(jnp.copy, carry)
            state, new, metrics = step(state, carry, traces, key, faults)
            n = new.obs.shape[0]

            def mix(o, x):
                if x.ndim == 0 or x.shape[0] != n:
                    return x
                return jnp.concatenate([x[:n // 2], o[n // 2:]])

            return state, jax.tree.map(mix, old, new), metrics
        return half_step

    _break_build(monkeypatch, wrap_step=half)
    _, rows = _execute()
    assert not rows["rollout_untied_envs"]["ok"], rows


def test_update_at_half_the_stated_learning_rate(monkeypatch):
    """The other fault the parameter-change norm is there to catch."""
    _break_build(monkeypatch, wrap_cfg=lambda cfg: dataclasses.replace(
        cfg, ppo=dataclasses.replace(cfg.ppo, lr=cfg.ppo.lr / 2)))
    _, rows = _execute()
    assert not rows["param_change_tree_gap"]["ok"], rows
    assert 0.3 < rows["param_change_tree_gap"]["value"] < 0.7


def test_update_that_leaves_out_half_the_batch(monkeypatch):
    """The fault the first iteration's loss is there to catch: the update
    runs over the first half of the batch only, in minibatches of the
    stated size (half the optimizer steps)."""
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
    _, rows = _execute()
    assert not rows["loss_gap_first"]["ok"], rows
    assert rows["loss_gap_first"]["value"] > 0.3
