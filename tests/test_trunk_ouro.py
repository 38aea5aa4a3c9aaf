"""The third family of token blocks (``models.trunk.OuroConfig``: one stack
of dense multi-head-attention layers looped with one set of weights, an
exit gate a step) at its tiny shape on the CPU: against the benchmark's
plain reference (``benchmark/reference/forward_ouro.py``) on seeded
weights, values and gradients; a leaf's gradient is the sum over its uses;
the scan equals the loop written out; the compiled loss holds the layers
once; the exit rule; what an invalid token holds is nothing to the policy;
both lowerings of the score product; the configuration file, the
registry and the CLIs' flag agree. (The normal path, through
``Experiment.run`` and the four CLIs: ``tests/test_trunk_ouro_cli.py``,
a file of its own so that the two share no worker's minute.)
"""
import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import forward as ref_forward
from benchmark.reference import forward_ouro as ref
from benchmark.reference import weights
from rlgpuschedule_tpu.configs import CONFIGS, TRUNK_NAMES
from rlgpuschedule_tpu.models import TRUNKS, make_policy
from rlgpuschedule_tpu.models import trunk as trunk_lib
from rlgpuschedule_tpu.models.actor_critic import ActorCritic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = TRUNKS["ouro-tiny"]
R, L = TINY.total_ut_steps, TINY.num_hidden_layers
T, F, A = 20, 11, 5          # the rehearsal's 4 nodes + 16 jobs


def config_file() -> dict:
    return common.load_json("configs", "philly512-ouro.json")


def spec_of(cfg: trunk_lib.OuroConfig, T: int = T) -> dict:
    """What ``forward_ouro`` reads from a configuration file, for a trunk
    the test made itself."""
    return {"head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "total_ut_steps": cfg.total_ut_steps,
            "early_exit_threshold": 1.0,    # the published one: no field
            "tokens_per_row": T}


def observations(key, rows: int, p_valid: float = 0.7, T: int = T):
    """Rows of token features; some job tokens are not valid (all zeros),
    the first four (the nodes) always are."""
    k1, k2 = jax.random.split(key)
    obs = jax.random.uniform(k1, (rows, T, F), minval=-1.0)
    valid = jax.random.bernoulli(k2, p_valid, (rows, T)).at[:, :4].set(True)
    return obs.at[..., -1].set(1.0) * valid[..., None]


def policy(cfg, dtype, seed: int = 7, T: int = T):
    net = ActorCritic(trunk_lib.TokenTrunk(cfg, dtype=dtype), A)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, T, F)), jnp.ones((1, A), bool))
    return net, weights.make_params(shapes, seed)


def heads(params, h, mask):
    p = params["params"]
    logits = ref_forward.dense(h, p["policy"], None)
    value = ref_forward.dense(h, p["value"], None)[..., 0]
    return jnp.where(mask, logits, ref_forward.NEG_INF), value


def reference(params, obs, mask, spec):
    with jax.default_matmul_precision("highest"):
        return heads(params, ref.trunk(params["params"]["encoder"], obs,
                                       None, spec), mask)


def written_out(cfg, dtype, enc, obs, steps=None, norm_every_step=True):
    """The looped trunk as a Python loop over the trunk's OWN modules, no
    scan, no row groups, no remat: ``steps[t]`` holds the layers step ``t``
    applies (``R`` untied copies; by default ``enc``'s, ``R`` times over).
    ``norm_every_step`` False plants the fault of a closing norm applied
    once, after the last step."""
    steps = [enc] * cfg.total_ut_steps if steps is None else steps
    norm = lambda x: trunk_lib.RMSNorm(cfg.rms_norm_eps, dtype).apply(
        {"params": enc["final_norm"]}, x)
    valid = obs[..., -1] > 0.5
    x = nn.Dense(cfg.hidden_size, use_bias=False, dtype=dtype).apply(
        {"params": enc["embed"]}, obs.astype(dtype))
    for layers in steps:
        for i in range(cfg.num_hidden_layers):
            x = trunk_lib.Block(cfg, i, dtype).apply(
                {"params": layers[f"layer_{i}"]}, x, valid)
        if norm_every_step:
            x = norm(x)
    return trunk_lib.pool(x if norm_every_step else norm(x), valid)


def loss_of(forward):
    def of(p):
        logits, value = forward(p)
        return jnp.sum(value ** 2) + jnp.sum(
            jax.nn.log_softmax(logits)[:, 0])
    return of


def leaf_gaps(got, want) -> dict:
    """``{leaf: (largest difference, the reference leaf's largest
    entry)}``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    return {jax.tree_util.keystr(path): (
        float(jnp.max(jnp.abs(a - b))), float(jnp.max(jnp.abs(b))))
        for (path, a), b in zip(flat, jax.tree.leaves(want))}


# ---- the file, the registry, the count ----------------------------------

def test_the_configuration_file_states_both_trunks():
    """The file's top level is ``TRUNKS['ouro']`` and its
    ``rehearse_trunk`` overlay ``TRUNKS['ouro-tiny']``, in every setting
    the reference reads (``common.Reference`` resolves them)."""
    config = config_file()
    for name, tokens, rehearse in (("ouro", 832, False),
                                   ("ouro-tiny", 20, True)):
        got = common.Reference(config, rehearse).settings
        for key, want in spec_of(TRUNKS[name], tokens).items():
            assert got[key] == want, (name, key)


def test_published_widths_are_the_catalogs():
    """The defaults ARE the source's widths and loop count, the file
    states them unchanged, and the cut is depth and the vocabulary;
    411.4M parameters in 8 layer subtrees, 16 B each = 6.58 GB."""
    cfg = config_file()
    c = TRUNKS["ouro"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "intermediate_size", "rope_theta",
                "rms_norm_eps", "num_hidden_layers", "total_ut_steps"):
        assert cfg[key] == getattr(c, key), key
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (
        2048, 5632, 128, 16, 16, 4, 1)
    assert cfg["num_hidden_layers_published"] == len(cfg["layer_types"]) \
        == 48 == cfg["pipeline_stages"] * cfg["num_hidden_layers"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {e["name"]: e for e in json.load(f)["configs"]}[
            "philly512-ouro"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "vocab_size", "trace_source", "chips"}
    assert "vocab_size" not in cfg
    assert "6 stages" in cfg["deployment"]
    assert {"closing_norm", "exit_gate", "attention", "rope"} <= set(
        cfg["assumed"])
    assert cfg["preset"] == "ppo-ouro-philly512"
    assert "--trunk" in cfg["rehearse_overrides"]
    assert "--trunk" not in cfg["overrides"]
    net = make_policy("tokens", 129, trunk="ouro")
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 832, 11)), jnp.ones((1, 129), bool))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg["parameters"] == 411_400_323
    enc = shapes["params"]["encoder"]
    assert sorted(k for k in enc if k.startswith("layer_")) == [
        f"layer_{i}" for i in range(8)]
    layer = sum(int(np.prod(x.shape))
                for x in jax.tree.leaves(enc["layer_0"]))
    assert layer == 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert set(enc["layer_0"]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                           "o_proj"}
    assert enc["exit_gate"]["kernel"].shape == (2048, 1)
    assert enc["exit_gate"]["bias"].shape == (1,)


def test_every_leaf_has_a_sharding_rule_and_a_weights_rule():
    """Each leaf ends in ``kernel``, ``scale`` or ``bias`` (what
    ``benchmark/reference/weights.py`` fills) and is matched by a rule of
    ``RULE_TABLES['tokens']``: all replicated, there is no expert leaf."""
    import re

    from jax.sharding import PartitionSpec as P

    from rlgpuschedule_tpu.parallel.sharding import RULE_TABLES
    rules = RULE_TABLES["tokens"]
    _, params = policy(TINY, jnp.float32)
    flat, _ = jax.tree_util.tree_flatten_with_path(params["params"])
    assert len(flat) == L * 11 + 1 + 1 + 2 + 4
    for path, _ in flat:
        name = "/".join(p.key for p in path)
        assert name.rsplit("/", 1)[-1] in ("kernel", "scale", "bias"), name
        assert next(s for pattern, s in rules
                    if re.search(pattern, name)) == P(), name


def test_make_policy_and_the_clis_name_every_trunk():
    net = make_policy("tokens", A, trunk="ouro-tiny")
    assert isinstance(net.encoder, trunk_lib.TokenTrunk)
    assert net.encoder.cfg == TINY and TINY.family == "ouro"
    assert (R, L) == (3, 2)
    assert CONFIGS["ppo-ouro-philly512"].trunk == "ouro"
    # the Trinity preset but for the trunk and Adam's step, which is
    # divided by the loop count (every weight acts that often a pass)
    ouro, trinity = (CONFIGS[f"ppo-{n}-philly512"] for n in ("ouro",
                                                             "trinity"))
    assert ouro.ppo.lr == trinity.ppo.lr / TRUNKS["ouro"].loop_steps
    assert dataclasses.replace(ouro, name=trinity.name, trunk="published",
                               ppo=trinity.ppo) == trinity
    assert TRUNK_NAMES == tuple(TRUNKS)
    from rlgpuschedule_tpu import evaluate, select_checkpoint, train
    from rlgpuschedule_tpu.serve import __main__ as serve
    for cli in (train, evaluate, select_checkpoint, serve):
        parser = cli.build_parser()
        action = next(a for a in parser._actions if a.dest == "trunk")
        assert list(action.choices) == list(TRUNKS), cli.__name__
        # one name picks family, sizes and loop count: no flag sets a count
        assert not [a.dest for a in parser._actions
                    if "loop" in a.dest or "ut_steps" in a.dest]


@pytest.mark.parametrize("name", list(TRUNKS))
def test_describe_says_what_every_trunk_fixes(name):
    """``describe`` asks no family by name: the loop count is 1 where a
    family states none, and a trunk without expert layers has no groups."""
    c = TRUNKS[name]
    d = trunk_lib.describe(c)
    assert d["family"] == c.family and d["layers"] == c.num_hidden_layers
    assert d["loop_steps"] == (c.total_ut_steps if c.family == "ouro"
                               else 1)
    assert (d["kda_layers"] > 0) == (c.family == "ling") == (
        d["kda_chunk"] > 0)
    assert (d["moe_groups"] == 0) == (c.family == "ouro")


# ---- against the plain reference ----------------------------------------

def test_float32_program_equals_the_plain_reference():
    """Values to 1e-5 and every leaf's gradient to 1e-4 of its largest
    entry: float32 against float32 at ``highest``, the same mathematics in
    another order (a scan over steps and row groups with rematerialised
    blocks against a Python loop; grouped heads against repeated ones), so
    rounding alone parts them. The faults below do not pass. The exit
    gate's two leaves get exactly zero."""
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(1), 6)
    mask = jnp.ones((6, A), bool).at[:, 1].set(False)
    spec = common.Reference(config_file(), True).settings  # by the file
    with jax.default_matmul_precision("highest"):
        logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, spec)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-5
    assert float(jnp.max(jnp.abs(value - r_value))) < 1e-5
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(loss_of(
            lambda p: net.apply(p, obs, mask))))(params)
    want = jax.jit(jax.grad(loss_of(
        lambda p: reference(p, obs, mask, spec))))(params)
    gaps = leaf_gaps(got, want)
    for name, (gap, scale) in gaps.items():
        assert gap <= 1e-4 * max(scale, 1e-3), name
    still = [name for name, (_, scale) in gaps.items() if scale == 0]
    assert sorted(still) == [
        "['params']['encoder']['exit_gate']['bias']",
        "['params']['encoder']['exit_gate']['kernel']"]
    for leaf in jax.tree.leaves(got["params"]["encoder"]["exit_gate"]):
        assert not np.any(np.asarray(leaf))


def reference_written_out(enc, obs, quant, spec):
    """``forward_ouro.steps`` as a Python ``for`` over steps and layers
    over the reference's own plain functions: no scan, no checkpoint."""
    valid = obs[..., -1] > 0.5
    x = ref.matmul(obs.astype(jnp.float32), enc["embed"], quant)
    xs, lam = [], []
    for _ in range(spec["total_ut_steps"]):
        for i in range(L):
            x = ref.block(enc[f"layer_{i}"], x, valid, spec, quant)
        x = ref.rms_norm(x, enc["final_norm"], spec["rms_norm_eps"])
        g = enc["exit_gate"]
        lam.append(jax.nn.sigmoid((ref.matmul(x, g, quant) + g["bias"])[..., 0]))
        xs.append(x)
    return jnp.stack(xs), jnp.stack(lam)


@pytest.mark.parametrize("quant,atol,gtol", [("none", 2e-6, 1e-4),
                                             ("bf16", 0.02, 0.05)])
def test_the_references_scan_over_steps_is_its_loop_written_out(quant, atol,
                                                                gtol):
    """The plain reference runs its steps under one ``lax.scan`` (8 layer
    bodies in its compiled programs where 32 written out passed the chip
    machine's compile cache: PERF.md section 7) with each layer
    application under ``jax.checkpoint``; neither changes a number: every
    step's output and gate, and the gradient of every leaf, equal the loop
    written out to float32 rounding; with the witness's bfloat16 operands
    to a few of THEIR ulps (a float32 sum in another order tips an
    operand's rounding in 3 % of the entries, by 0.004)."""
    _, params = policy(TINY, jnp.float32)
    enc = params["params"]["encoder"]
    obs = observations(jax.random.PRNGKey(3), 5)
    q, spec = ref_forward.QUANT[quant], spec_of(TINY)
    with jax.default_matmul_precision("highest"):
        xs, lam, _ = ref.steps(enc, obs, q, spec)
        w_xs, w_lam = reference_written_out(enc, obs, q, spec)
        np.testing.assert_allclose(np.asarray(xs), np.asarray(w_xs),
                                   atol=atol)
        np.testing.assert_allclose(np.asarray(lam), np.asarray(w_lam),
                                   atol=atol)
        w = jax.random.normal(jax.random.PRNGKey(4), xs.shape[1:])
        loss = lambda f: lambda e: jnp.sum(f(e)[0][-1] * w)
        got = jax.grad(loss(lambda e: ref.steps(e, obs, q, spec)))(enc)
        want = jax.grad(loss(
            lambda e: reference_written_out(e, obs, q, spec)))(enc)
    for name, (gap, scale) in leaf_gaps(got, want).items():
        assert gap <= gtol * max(scale, 1e-3), name


FAULTS = ["a_loop_step_dropped", "closing_norm_once", "rope_left_off",
          "bfloat16_parameters"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_does_not_pass_the_float32_tolerance(fault, monkeypatch):
    """Each of these changes the values by a hundred times the 1e-5 the
    sound program is held to, or more."""
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(1), 6)
    mask = jnp.ones((6, A), bool)
    _, r_value = reference(params, obs, mask, spec_of(TINY))
    enc = params["params"]["encoder"]
    with jax.default_matmul_precision("highest"):
        if fault == "a_loop_step_dropped":
            short = dataclasses.replace(TINY, total_ut_steps=R - 1)
            _, value = ActorCritic(trunk_lib.TokenTrunk(
                short, dtype=jnp.float32), A).apply(params, obs, mask)
        elif fault == "closing_norm_once":
            _, value = heads(params, written_out(
                TINY, jnp.float32, enc, obs, norm_every_step=False), mask)
        elif fault == "rope_left_off":
            monkeypatch.setattr(trunk_lib, "rope",
                                lambda x, theta, gain=1.0: x)
            _, value = net.apply(params, obs, mask)
        else:
            rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                                   .astype(jnp.float32), params)
            _, value = net.apply(rounded, obs, mask)
    assert float(jnp.max(jnp.abs(value - r_value))) > 1e-3, fault


def test_bfloat16_program_stays_near_the_reference():
    """The stated precision against float32. A bfloat16 stream carries 8
    bits, so an activation is off by up to 0.4 % and R x L = 6 blocks in
    a row compound that: values read within 0.014 of the reference's here
    (their spread over rows is 0.33) and logits within 3.3e-4, and are
    held to three times that; a loop step dropped moves the values by
    0.6 and does not pass. Every leaf's gradient within 10 % of its
    largest entry (read: up to 3.1 %)."""
    net, params = policy(TINY, jnp.bfloat16)
    obs = observations(jax.random.PRNGKey(2), 16)
    mask = jnp.ones((16, A), bool)
    logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, spec_of(TINY))
    assert float(jnp.max(jnp.abs(value - r_value))) < 0.04
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-3
    short = {**spec_of(TINY), "total_ut_steps": R - 1}
    _, s_value = reference(params, obs, mask, short)
    assert float(jnp.max(jnp.abs(value - s_value))) > 0.1
    got = jax.jit(jax.grad(loss_of(
        lambda p: net.apply(p, obs, mask))))(params)
    want = jax.jit(jax.grad(loss_of(
        lambda p: reference(p, obs, mask, spec_of(TINY)))))(params)
    for name, (gap, scale) in leaf_gaps(got, want).items():
        assert gap <= 0.1 * scale, (name, gap, scale)


# ---- one set of weights, one compiled body ------------------------------

def test_a_leafs_gradient_is_the_sum_over_its_uses():
    """The scanned program's gradient of a layer's leaf equals the sum of
    the gradients that ``R`` untied copies of the layers get in the loop
    written out (float32 sums in another order: 1e-5 of the leaf's
    largest entry)."""
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(3), 4)
    mask = jnp.ones((4, A), bool)
    enc = params["params"]["encoder"]
    layers = {k: v for k, v in enc.items() if k.startswith("layer_")}

    def untied(steps):
        return loss_of(lambda p: heads(p, written_out(
            TINY, jnp.float32, enc, obs, steps), mask))(params)

    with jax.default_matmul_precision("highest"):
        tied = jax.jit(jax.grad(loss_of(
            lambda p: net.apply(p, obs, mask))))(params)
        copies = jax.jit(jax.grad(untied))([layers] * R)
    assert len(copies) == R
    total = jax.tree.map(lambda *g: sum(g), *copies)
    tied = {k: tied["params"]["encoder"][k] for k in layers}
    for name, (gap, scale) in leaf_gaps(tied, total).items():
        assert scale > 0 and gap <= 1e-5 * scale, name
    # and no copy's share is the whole: each step contributes
    first = leaf_gaps(copies[0], total)
    assert all(gap > 1e-3 * scale for gap, scale in first.values())


@pytest.mark.parametrize("steps", [1, R])
def test_the_scan_equals_the_loop_written_out_bit_for_bit(steps):
    """``nn.scan`` over steps, the row-group scan and ``nn.remat`` change
    the program's form, not one float32 bit of its result: run operation
    by operation (``disable_jit``: the same operations in the same order,
    none fused) it equals the Python loop over the same modules bit for
    bit. Compiled, XLA fuses the two forms differently and they part by
    an ulp: held to 1e-6, at the tiny trunk's ``R`` and at ``R`` = 1,
    which is the plain ``L``-layer stack with its final norm."""
    cfg = dataclasses.replace(TINY, total_ut_steps=steps)
    net, params = policy(cfg, jnp.float32, T=8)
    obs = observations(jax.random.PRNGKey(4), 2, T=8)
    mask = jnp.ones((2, A), bool)
    written = lambda p: heads(p, written_out(
        cfg, jnp.float32, p["params"]["encoder"], obs), mask)
    _, j_value = jax.jit(net.apply)(params, obs, mask)
    _, jw_value = jax.jit(written)(params)
    assert float(jnp.max(jnp.abs(j_value - jw_value))) < 1e-6
    if steps > 1:
        with jax.disable_jit():
            _, value = net.apply(params, obs, mask)
            _, w_value = written(params)
        assert np.array_equal(np.asarray(value), np.asarray(w_value))
        assert float(jnp.max(jnp.abs(j_value - value))) < 1e-6


def count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr inside it
    (scan and remat bodies, custom derivatives)."""
    from jax.extend import core
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(x, (core.Jaxpr,
                                                 core.ClosedJaxpr))):
            if isinstance(sub, core.ClosedJaxpr):
                n += count(sub.jaxpr, primitive)
            elif isinstance(sub, core.Jaxpr):
                n += count(sub, primitive)
    return n


def test_the_loss_holds_the_layers_products_once():
    """The jaxpr of the loss's gradient has as many ``dot_general``s at 2
    loop steps as at 5 (and as many scans): the steps are one traced
    body, so the compiled train step holds the ``L`` layers once whatever
    ``R`` is. The loop written out grows with ``R``."""
    obs = observations(jax.random.PRNGKey(5), 4)
    mask = jnp.ones((4, A), bool)

    def dots(steps, scanned=True):
        cfg = dataclasses.replace(TINY, total_ut_steps=steps)
        net = ActorCritic(trunk_lib.TokenTrunk(cfg, dtype=jnp.bfloat16), A)
        params = jax.eval_shape(net.init, jax.random.PRNGKey(0), obs, mask)
        forward = (lambda p: net.apply(p, obs, mask)) if scanned else (
            lambda p: heads(p, written_out(
                cfg, jnp.bfloat16, p["params"]["encoder"], obs), mask))
        jaxpr = jax.make_jaxpr(jax.grad(loss_of(forward)))(params).jaxpr
        return count(jaxpr, "dot_general"), count(jaxpr, "scan")

    assert dots(2) == dots(5)
    assert dots(2)[0] >= 3 * 7 * L      # forward, recomputation, backward
    assert dots(3, scanned=False)[0] > 2 * dots(1, scanned=False)[0]


# ---- the exit rule -------------------------------------------------------

def test_exit_distribution_sums_to_one_and_exit_step_is_the_first_crossing():
    lam = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6),
                                           (4, 3, 50)) * 2.0)
    p = trunk_lib.exit_distribution(lam)
    assert p.shape == lam.shape and bool(jnp.all(p >= 0))
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(p), np.asarray(
        ref.exit_distribution(lam)), atol=1e-7)
    np.testing.assert_allclose(np.asarray(p[2]), np.asarray(
        lam[2] * (1 - lam[0]) * (1 - lam[1])), atol=1e-7)
    # the published threshold: every step runs, whatever the gates say
    assert bool(jnp.all(ref.exit_step(p, 1) == 4))
    for threshold in (0.3, 0.6, 0.9):
        at = np.asarray(ref.exit_step(p, threshold))
        cum = np.cumsum(np.asarray(p), axis=0)
        want = np.where((cum >= threshold).any(0),
                        (cum >= threshold).argmax(0) + 1, 4)
        want = np.where(cum[2] >= threshold, want, 4)
        assert np.array_equal(at, want)
        assert 1 <= at.min() and at.max() <= 4 and len(set(at.ravel())) > 1
    # a gate wide open at step 1 exits there; gates shut never do
    assert int(ref.exit_step(trunk_lib.exit_distribution(
        jnp.asarray([0.9, 0.1, 0.1])), 0.8)) == 1
    assert int(ref.exit_step(trunk_lib.exit_distribution(
        jnp.zeros((3,))), 0.5)) == 3


def test_the_threshold_is_the_published_constant():
    """Every step runs: the threshold is the family's published 1, which
    the program states nowhere and no field sets (rows leaving the loop at
    different steps is not built: ROADMAP Queue 2 A; the rule for a lower
    threshold is the plain reference's ``exit_step``); a loop of no steps
    is refused."""
    assert not hasattr(TINY, "early_exit_threshold")
    assert config_file()["early_exit_threshold"] == 1
    with pytest.raises(TypeError):
        dataclasses.replace(TINY, early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="at least one step"):
        dataclasses.replace(TINY, total_ut_steps=0)


def test_loop_counters_read_the_gates_and_the_last_step():
    """``loop_exit_mass_last`` is the mean over valid tokens of ``p_R``
    and ``loop_last_step_change`` the relative move of the pooled output
    in the last step, both as the reference's per-step outputs give them;
    the attention counter counts layer APPLICATIONS; a trunk that does
    not loop reads 0."""
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(7), 8)
    _, sown = jax.jit(lambda p: net.apply(
        p, obs, jnp.ones((8, A), bool), mutable=[trunk_lib.COUNTERS]))(params)
    c = {k: float(v) for k, v in trunk_lib.read_counters(
        sown[trunk_lib.COUNTERS]).items()}
    with jax.default_matmul_precision("highest"):
        xs, lam, valid = jax.jit(lambda enc: ref.steps(
            enc, obs, None, spec_of(TINY)))(params["params"]["encoder"])
    p = ref.exit_distribution(lam)
    pooled = [trunk_lib.pool(x, valid) for x in xs]
    size = lambda a: jnp.linalg.norm(a, axis=-1)
    assert c["loop_exit_mass_last"] == pytest.approx(
        float(jnp.sum(p[-1] * valid) / jnp.sum(valid)), abs=1e-5)
    assert 0.0 < c["loop_exit_mass_last"] < 1.0
    assert c["loop_last_step_change"] == pytest.approx(float(jnp.mean(
        size(pooled[-1] - pooled[-2]) / size(pooled[-1]))), rel=1e-4)
    assert c["attn_kernel_layers"] == 0.0           # a CPU: the plain path
    assert c["moe_assignments_held"] == c["moe_short_path_share"] == 0.0
    tiles = sown[trunk_lib.COUNTERS]["encoder"]["layer_0"]["attn"][
        "attn_tiles"][0]
    assert tiles.shape == (R, 8 // trunk_lib.ROW_BLOCK)   # steps x groups


# ---- invalid tokens, both lowerings ------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_what_an_invalid_token_holds_is_nothing_to_the_policy(dtype):
    """The features of a token whose ``valid`` is 0 may be anything:
    logits, values and the loop's counters do not move (its key is masked
    in every application, the pool and the counters leave it out)."""
    net, params = policy(TINY, dtype)
    obs = observations(jax.random.PRNGKey(8), 6)
    mask = jnp.ones((6, A), bool)
    valid = obs[..., -1:] > 0.5
    assert not bool(jnp.all(valid))
    junk = jax.random.normal(jax.random.PRNGKey(9), obs.shape) * 3.0
    other = jnp.where(valid, obs, junk.at[..., -1].set(0.0))
    apply = jax.jit(lambda p, o: net.apply(p, o, mask,
                                           mutable=[trunk_lib.COUNTERS]))
    (logits, value), sown = apply(params, obs)
    (o_logits, o_value), o_sown = apply(params, other)
    assert np.array_equal(np.asarray(logits), np.asarray(o_logits))
    assert np.array_equal(np.asarray(value), np.asarray(o_value))
    for a, b in zip(jax.tree.leaves(sown), jax.tree.leaves(o_sown)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


WIDE = dataclasses.replace(TINY, head_dim=128)  # a head the kernel takes


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5),
                                             (jnp.bfloat16, 0.04)])
def test_kernel_and_plain_paths_agree(monkeypatch, dtype, tolerance):
    """Every one of the ``R x L`` applications on the blocked kernel
    (interpreted here; tiles of 128, so a row of 150 tokens spans two)
    against the plain reference: float32 to the 2e-5 the kernel's tiles
    are held to elsewhere (``tests/test_trunk_ling.py``), bfloat16 to
    the plain path's own tolerance; the counter reads ``R x L``."""
    from rlgpuschedule_tpu.ops import attention
    monkeypatch.setattr(attention, "BLOCK", 128)
    monkeypatch.setattr(trunk_lib, "attention_path",
                        lambda *observed: trunk_lib.KERNEL)
    T = 150
    net, params = policy(WIDE, dtype, T=T)
    obs = observations(jax.random.PRNGKey(10), 1, T=T)
    mask = jnp.ones((1, A), bool)
    with jax.default_matmul_precision("highest"):
        (_, value), sown = net.apply(params, obs, mask,
                                     mutable=[trunk_lib.COUNTERS])
    _, r_value = reference(params, obs, mask, spec_of(WIDE, T))
    assert float(jnp.max(jnp.abs(value - r_value))) < tolerance
    c = trunk_lib.read_counters(sown[trunk_lib.COUNTERS])
    assert float(c["attn_kernel_layers"]) == R * L
    assert 0.0 < float(c["attn_tiles_computed_share"]) < 1.0
