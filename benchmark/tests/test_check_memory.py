"""What the check holds on the device (ISSUE 29): the program's state is
parked on the host while the references run and put back for the stages,
the follower's update takes its state donated, and one follower is on the
device at a time - with every compared number where it was."""
import jax
import numpy as np
import pytest

from benchmark.common import Reference
from benchmark.drivers import train_loop
from benchmark.reference import ppo as ppo_ref
from benchmark.tests.test_broken_path import _execute
from benchmark.tests.test_control import _reads

CELL = "philly512-cnn.train"

# ``control.py --rehearse-cpu --seeds 3 --variants none,fp8`` on the PARENT's
# tree (PR 28: no parking, no donation, both followers alive), this
# sandbox's CPU backend, jax 0.9.0; the two runs made read the same. Another
# jax or XLA may round otherwise: the test that holds these skips there, and
# the one before it compares the two paths in one process instead
PARENT_JAX = "0.9.0"
PARENT = {
    "none": {"log_prob_gap": 0.00014281272888183594,
             "value_gap": 0.013416807629605088,
             "loss_gap_first": 0.00017796287162010673,
             "loss_gap_later": 0.007618732291341226,
             "param_change_norm_gap": 0.2345444750909177,
             "param_change_tree_gap": 0.03732859887794605,
             "sim_reward_gap": 1.0365398971462191e-07},
    "fp8": {"log_prob_gap": 0.0013245344161987305,
            "value_gap": 0.10098740249965159,
            "loss_gap_first": 0.0032223490938907287,
            "loss_gap_later": 0.005694901358440058,
            "param_change_norm_gap": 0.18770991681043264,
            "param_change_tree_gap": 0.052705563104542365,
            "sim_reward_gap": 1.0365398971462191e-07},
}


def test_no_program_state_on_the_device_while_the_follower_steps(
        monkeypatch):
    seen = {"held": [], "deleted": []}
    park, step = train_loop.TrainCell.park, ppo_ref.Follower.step

    def watched_park(cell):
        seen["leaves"] = jax.tree.leaves(
            (cell.exp.train_state, cell.exp.carry))
        seen["held"].append(cell.program_state_bytes_on_device())
        park(cell)
        seen["held"].append(cell.program_state_bytes_on_device())

    def watched_step(follower, traj, key):
        seen["deleted"].append(all(x.is_deleted() for x in seen["leaves"]))
        return step(follower, traj, key)

    monkeypatch.setattr(train_loop.TrainCell, "park", watched_park)
    monkeypatch.setattr(ppo_ref.Follower, "step", watched_step)
    _, rows = _execute(seed=6)
    assert all(r["ok"] for r in rows.values()), rows
    assert seen["held"][0] > 0 and seen["held"][1] == 0
    assert len(seen["deleted"]) == 2 and all(seen["deleted"])


HYPER = ppo_ref.Hyper(0.99, 0.95, 0.2, 0.5, 0.01, 3e-4, 0.5, 2, 2)
GRID = Reference({"reference": "forward_grid"})


def _tiny_policy_and_trajectory():
    import jax.numpy as jnp
    from rlgpuschedule_tpu.models import make_policy

    from benchmark.reference import weights
    T, E, A = 4, 8, 5
    net = make_policy("grid", A, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    obs = rng.random((T, E, 16, 4, 2), np.float32)
    mask = np.ones((T, E, A), bool)
    params = weights.make_params(
        jax.eval_shape(net.init, jax.random.PRNGKey(0), obs[0, :1],
                       mask[0, :1]), 11)
    return params, {"obs": obs, "mask": mask,
                    "action": rng.integers(0, A, (T, E)).astype(np.int32),
                    "reward": rng.standard_normal((T, E)).astype(np.float32),
                    "done": rng.random((T, E)) < 0.1,
                    "last_obs": obs[-1], "last_mask": mask[-1]}


def test_followers_update_aliases_its_state_and_spares_the_callers():
    """At the parent ``alias_size_in_bytes`` is 0 and arguments and outputs
    are held side by side (24 B a parameter before one temporary)."""
    params, traj = _tiny_policy_and_trajectory()
    fol = ppo_ref.Follower(GRID, HYPER, params, block=8)
    fol.step(traj, jax.random.PRNGKey(1))
    m = fol.memory
    assert m["param_count"] == sum(x.size for x in jax.tree.leaves(params))
    assert m["alias_bytes_per_param"] >= 12
    u = m["update"]
    assert u["peak"] == (u["argument"] + u["output"] + u["temp"]
                         - u["alias"])
    assert m["param_shaped_bytes"] == 16 * m["param_count"] + 4  # + count
    assert m["param_shaped_bytes"] + m["remainder_bytes"] == u["peak"]
    # the caller's start is the caller's still; the follower's own goes
    assert not any(x.is_deleted() for x in jax.tree.leaves(params))
    assert not fol.released
    fol.release()
    assert fol.released


def test_donated_update_reads_what_the_parents_update_read():
    """The parent's path (the update jitted with nothing donated, nothing
    aliased) and this one, in one process on the same trajectories: every
    reading and every parameter bit for bit, whatever the jax."""
    params, traj = _tiny_policy_and_trajectory()
    donated = ppo_ref.Follower(GRID, HYPER, params, block=8)
    plain = ppo_ref.Follower(GRID, HYPER, params, block=8)
    plain._update = jax.jit(ppo_ref.make_update(GRID, HYPER, 8))
    for k in range(2):
        a = donated.step(traj, jax.random.PRNGKey(k))
        b = plain.step(traj, jax.random.PRNGKey(k))
        assert a["loss"] == b["loss"]
        for name in ("log_prob", "value"):
            np.testing.assert_array_equal(a[name], b[name])
        for x, y in zip(jax.tree.leaves((donated.params, donated.adam)),
                        jax.tree.leaves((plain.params, plain.adam))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert plain.memory["update"]["alias"] == 0
    assert donated.memory["update"]["alias"] >= 12 * donated.memory[
        "param_count"]


def test_traced_rehearsal_reads_every_stage_on_the_state_put_back(
        monkeypatch):
    seen = {}
    park, unpark = train_loop.TrainCell.park, train_loop.TrainCell.unpark

    def watched_park(cell):
        park(cell)
        seen["parked"] = jax.tree.leaves(
            (cell.exp.train_state, cell.exp.carry))

    def watched_unpark(cell):
        unpark(cell)
        seen["cell"] = cell
        # put back bit for bit, before the update stage moves it on
        seen["back"] = [np.asarray(x)
                        for x in jax.tree.leaves(cell.exp.train_state)]
        seen["step"] = int(cell.exp.train_state.step)

    monkeypatch.setattr(train_loop.TrainCell, "park", watched_park)
    monkeypatch.setattr(train_loop.TrainCell, "unpark", watched_unpark)
    line, _ = _execute(seed=6, trace=1)
    for stage in ("rollout_ms.train", "advantage_ms.train",
                  "update_ms.train", "resample_ms.train"):
        assert line["metrics"][stage]["value"] > 0, line["metrics"]
    assert all(isinstance(x, np.ndarray) for x in seen["parked"])
    for host, back in zip(seen["parked"], seen["back"]):
        np.testing.assert_array_equal(host, back)
    # ONE copy of the state: the update stage took the program's own,
    # donated, and left the program the live one, moved on
    state = seen["cell"].exp.train_state
    assert all(isinstance(x, jax.Array) and not x.is_deleted()
               for x in jax.tree.leaves(state))
    assert int(state.step) > seen["step"]


@pytest.mark.skipif(jax.__version__ != PARENT_JAX,
                    reason=f"PARENT's literals were read under jax "
                           f"{PARENT_JAX}")
def test_control_readings_equal_the_parents_to_the_last_digit():
    """One follower at a time, state donated, program parked: the same
    operands, so the same numbers as with everything alive at once."""
    reads = _reads(CELL, 3, list(PARENT))
    for variant, want in PARENT.items():
        assert {k: reads[variant][k] for k in want} == want, variant
        for exact in ("sim_state", "untied_envs", "masked_actions"):
            assert reads[variant][exact] == 0
