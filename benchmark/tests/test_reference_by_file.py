"""A cell's plain reference is found by the name its configuration's file
gives and is handed that file's settings (ISSUE 35): two token
configurations that state the SAME hidden size and different layer lists
each follow their own, through ``forward``, ``Follower`` and
``forward_flops_per_row``; every standing configuration names a reference
that is there; the stages are timed in the order the driver lists them."""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.readers import stage_time
from benchmark.reference import forward_tokens, ppo as ppo_ref, weights
from benchmark.reference.forward import forward

T, F, A = 20, 11, 5          # the rehearsal's 4 nodes + 16 jobs
HYPER = ppo_ref.Hyper(0.99, 0.95, 0.2, 0.5, 0.01, 3e-4, 0.5, 2, 2)

# the stand-in the copied configuration names: the same block, and a
# record of the layer lists it was handed
STAND_IN = '''
from benchmark.reference import forward_tokens

HANDED = []


def trunk(encoder, obs, quant, settings):
    HANDED.append(tuple(settings["layer_types"]))
    return forward_tokens.trunk(encoder, obs, quant, settings)


def forward_flops_per_row(params, settings):
    HANDED.append(tuple(settings["layer_types"]))
    return forward_tokens.forward_flops_per_row(params, settings)
'''
OTHER_CUT = ["full_attention", "sliding_attention", "full_attention",
             "full_attention", "sliding_attention"]


def _spec() -> dict:
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def two_references(tmp_path, monkeypatch):
    """The standing token configuration, and a copy of its file with
    another cut of ``layer_types`` and a reference module of its own."""
    path = tmp_path / "forward_standin.py"
    path.write_text(STAND_IN)
    name = "benchmark.reference.forward_standin"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, name, module)
    standing = common.load_cell("philly512-trinity.train")["config"]
    copy = json.loads(json.dumps(standing))
    copy.update(name="copy-of-trinity", reference="forward_standin")
    copy["rehearse_trunk"]["layer_types"] = OTHER_CUT
    (tmp_path / "copy-of-trinity.json").write_text(json.dumps(copy))
    copy = json.loads((tmp_path / "copy-of-trinity.json").read_text())
    return (common.Reference(standing, rehearse=True),
            common.Reference(copy, rehearse=True), module)


def _tiny_policy_and_rows(rows: int = 8):
    from rlgpuschedule_tpu.models import TRUNKS
    from rlgpuschedule_tpu.models import trunk as trunk_lib
    from rlgpuschedule_tpu.models.actor_critic import ActorCritic
    net = ActorCritic(trunk_lib.TokenTrunk(TRUNKS["tiny"],
                                           dtype=jnp.float32), A)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, T, F)), jnp.ones((1, A), bool))
    rng = np.random.default_rng(0)
    obs = rng.uniform(-1, 1, (rows, T, F)).astype(np.float32)
    obs[..., -1] = 1.0                     # every token valid
    return weights.make_params(shapes, 7), obs, np.ones((rows, A), bool)


def test_two_configurations_at_one_hidden_size_follow_their_own_settings(
        two_references):
    standing, copy, stand_in = two_references
    assert standing.module is forward_tokens and copy.module is stand_in
    assert (standing.settings["hidden_size"] == copy.settings["hidden_size"]
            == 64)
    assert standing.settings["layer_types"] != copy.settings["layer_types"]
    params, obs, mask = _tiny_policy_and_rows()
    # forward: 20 tokens against a window of 8, so a sliding layer and a
    # full one differ
    a, _ = forward(standing, params, obs, mask)
    b, _ = forward(copy, params, obs, mask)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4
    for ref, got in ((standing, a), (copy, b)):
        with jax.default_matmul_precision("highest"):
            h = forward_tokens.trunk(params["params"]["encoder"], obs, None,
                                     ref.settings)
        want = h @ params["params"]["policy"]["kernel"] + params["params"][
            "policy"]["bias"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    # the update's FLOPs: four sliding layers of five against two
    assert (standing.forward_flops_per_row(params)
            < copy.forward_flops_per_row(params))
    # Follower: one trajectory, two losses
    rows = obs.reshape(2, 4, T, F)
    traj = {"obs": rows, "mask": mask.reshape(2, 4, A),
            "action": np.zeros((2, 4), np.int32),
            "reward": np.ones((2, 4), np.float32),
            "done": np.zeros((2, 4), bool),
            "last_obs": rows[-1], "last_mask": mask[:4]}
    losses = [ppo_ref.Follower(ref, HYPER, params, block=4).step(
        traj, jax.random.PRNGKey(1))["loss"] for ref in (standing, copy)]
    assert abs(losses[0] - losses[1]) > 1e-6
    # and the stand-in was handed its own file's list every time
    assert stand_in.HANDED and set(stand_in.HANDED) == {tuple(OTHER_CUT)}


def test_the_published_settings_are_the_files_top_level():
    config = common.load_cell("philly512-trinity.train")["config"]
    run, rehearsal = (common.Reference(config, r).settings
                      for r in (False, True))
    assert (run["hidden_size"], run["tokens_per_row"],
            run["sliding_window"]) == (2048, 832, 2048)
    assert (rehearsal["hidden_size"], rehearsal["tokens_per_row"],
            rehearsal["sliding_window"]) == (64, 20, 8)
    # what the rehearsal does not restate is the file's
    assert rehearsal["reference_block_rows"] == run["reference_block_rows"]


@pytest.mark.parametrize("config", [c["name"] for c in _spec()["configs"]])
def test_every_configuration_names_a_reference_that_is_there(config):
    entry = {c["name"]: c for c in _spec()["configs"]}[config]
    with open(os.path.join(common.ROOT, entry["file"])) as f:
        ref = common.Reference(json.load(f))
    assert os.path.exists(os.path.join(
        common.BENCH_DIR, "reference", ref.name + ".py"))
    assert callable(ref.module.trunk)
    assert callable(ref.module.forward_flops_per_row)


def test_a_configuration_without_a_reference_is_refused():
    with pytest.raises(SystemExit, match="names no 'reference'"):
        common.Reference({"name": "nameless", "obs_kind": "tokens"})


def test_a_stage_is_timed_after_those_listed_before_it():
    """The update moves the state the rollout and the advantage read: the
    driver lists it after them, and whichever metric asks first, they are
    timed first."""
    first_call = {}               # stage -> its place in the order
    probe = {"stages": {
        name: (lambda name=name: first_call.setdefault(name,
                                                       len(first_call)))
        for name in ("rollout", "advantage", "update", "resample")}}
    stage_time.time_stage(probe, "update", min_span_s=0.0, repeats=1)
    assert list(first_call) == ["rollout", "advantage", "update"]
    stage_time.time_stage(probe, "rollout")
    assert list(first_call) == ["rollout", "advantage", "update"]
    assert set(probe["cache"]) == set(first_call)        # each timed once
    # a predecessor was timed at the defaults: asking for it at settings
    # of its own afterwards is refused, not answered from the cache
    with pytest.raises(ValueError, match="lists last"):
        stage_time.time_stage(probe, "advantage", min_span_s=0.0, repeats=1)
    # the last stage may carry its own (``resample_ms.train`` does)
    stage_time.time_stage(probe, "resample", min_span_s=0.0, repeats=3)
    assert list(first_call)[-1] == "resample"
