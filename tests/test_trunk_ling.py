"""The second family of token blocks (``models.trunk.LingConfig``: KDA and
MLA layers, group-limited routing) at its tiny shape on the CPU: against
the benchmark's plain reference (``benchmark/reference/forward_ling.py``)
on seeded weights, values and gradients; the chip's shares of an expert
layer add up to the uncut layer; group-limited choice against a sort; MLA
on both lowerings of the score product; what an invalid token holds is
nothing to the policy; the preset trains through ``Experiment.run``; the
configuration file, the registry, the CLIs and the sharding rules agree.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import forward as ref_forward
from benchmark.reference import forward_ling as ref
from benchmark.reference import weights
from rlgpuschedule_tpu.configs import CONFIGS
from rlgpuschedule_tpu.models import TRUNKS, make_policy
from rlgpuschedule_tpu.models import trunk as trunk_lib
from rlgpuschedule_tpu.models.actor_critic import ActorCritic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = TRUNKS["ling-tiny"]
T, F, A = 20, 11, 5          # the rehearsal's 4 nodes + 16 jobs


def config_file() -> dict:
    return common.load_json("configs", "philly512-ling.json")


def spec_of(cfg: trunk_lib.LingConfig, T: int = T) -> dict:
    """What ``forward_ling`` reads from a configuration file, for a trunk
    the test made itself."""
    return {"layer_group_size": cfg.layer_group_size,
            "first_k_dense_replace": cfg.num_dense_layers,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.route_scale,
            "norm_topk_prob": cfg.route_norm, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "kda_lower_bound": cfg.kda_lower_bound,
            "experts_held_first": cfg.experts_held[0], "tokens_per_row": T}


def observations(key, rows: int, p_valid: float = 0.7):
    """Rows of token features; some job tokens are not valid (all zeros),
    the first four (the nodes) always are."""
    k1, k2 = jax.random.split(key)
    obs = jax.random.uniform(k1, (rows, T, F), minval=-1.0)
    valid = jax.random.bernoulli(k2, p_valid, (rows, T)).at[:, :4].set(True)
    return obs.at[..., -1].set(1.0) * valid[..., None]


def policy(cfg, dtype, seed: int = 7):
    net = ActorCritic(trunk_lib.TokenTrunk(cfg, dtype=dtype), A)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, T, F)), jnp.ones((1, A), bool))
    return net, weights.make_params(shapes, seed)


def reference(params, obs, mask, spec):
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        h = ref.trunk(p["encoder"], obs, None, spec)
        logits = ref_forward.dense(h, p["policy"], None)
        value = ref_forward.dense(h, p["value"], None)[..., 0]
    return jnp.where(mask, logits, ref_forward.NEG_INF), value


def test_the_configuration_file_states_both_trunks():
    """The file's top level is ``TRUNKS['ling']`` and its
    ``rehearse_trunk`` overlay ``TRUNKS['ling-tiny']``, in every setting
    the reference reads (``common.Reference`` resolves them)."""
    config = config_file()
    for name, tokens, rehearse in (("ling", 832, False),
                                   ("ling-tiny", 20, True)):
        got = common.Reference(config, rehearse).settings
        for key, want in spec_of(TRUNKS[name], tokens).items():
            assert got[key] == want, (name, key)
        assert got["kda_chunk"] == TRUNKS[name].kda_chunk


def test_float32_program_equals_the_plain_reference():
    """Values to 1e-5 and every leaf's gradient to 1e-4 of its largest
    entry: float32 against float32 at ``highest``, two algorithms (chunks
    and a solve against a token-by-token scan; sorted grouped products
    against dense ones; top-k against a sort), so rounding alone parts
    them. bfloat16 gates or state would not pass
    (``tests/test_kda.py``)."""
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(1), 6)
    mask = jnp.ones((6, A), bool).at[:, 1].set(False)
    spec = common.Reference(config_file(), True).settings  # by the file
    with jax.default_matmul_precision("highest"):
        logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, spec)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-5
    assert float(jnp.max(jnp.abs(value - r_value))) < 1e-5

    def loss(forward):
        def of(p):
            logits, value = forward(p)
            return jnp.sum(value ** 2) + jnp.sum(
                jax.nn.log_softmax(logits)[:, 0])
        return of

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda p: net.apply(p, obs, mask)))(params)
    want = jax.grad(loss(lambda p: reference(p, obs, mask, spec)))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    moved = 0
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * max(scale, 1e-3), \
            jax.tree_util.keystr(path)
        moved += scale > 0
    # every leaf but the expert biases (no gradient reaches them) moves
    biases = sum(1 for path, _ in flat if jax.tree_util.keystr(path)
                 .endswith("['moe']['bias']"))
    assert moved == len(flat) - biases


def test_bfloat16_program_stays_near_the_reference():
    """The stated precision against float32, a liveness bound and no
    more (at 64 channels a bfloat16 stream flips a router choice here and
    there, and each flip is a jump): values within 0.05 on average and
    0.2 at worst (their spread is 0.3), logits (times the policy head's
    0.01 start) within 2e-3."""
    net, params = policy(TINY, jnp.bfloat16)
    obs = observations(jax.random.PRNGKey(2), 16)
    mask = jnp.ones((16, A), bool)
    logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, spec_of(TINY))
    gap = jnp.abs(value - r_value)
    assert float(jnp.mean(gap)) < 0.05 and float(jnp.max(gap)) < 0.2
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 2e-3


def test_gates_pinned_at_their_bound_train_in_bfloat16():
    """Every KDA gate driven to the family's bound (``dt`` = 40 in both
    KDA layers of one period: ``g = kda_lower_bound`` on every channel of every valid token,
    where a trained gate could sit) at a chunk of 64, so that the
    16-token sub-blocks' ``exp(75)`` factors are there (the tiny trunk's
    own chunk, 8, never passes ``exp(35)``): the bfloat16 program's loss
    and every leaf's gradient are finite, and its values stay as near the
    float32 reference as they do at seeded gates."""
    cfg = dataclasses.replace(TINY, kda_chunk=64, num_hidden_layers=3)
    net, params = policy(cfg, jnp.bfloat16)     # one period: KDA, KDA, MLA
    enc = params["params"]["encoder"]
    pinned = 0
    for name, layer in enc.items():
        if name.startswith("layer_") and "dt" in layer["attn"]:
            layer["attn"]["dt"]["bias"] = jnp.full_like(
                layer["attn"]["dt"]["bias"], 40.0)
            pinned += 1
    assert pinned == 2
    obs = observations(jax.random.PRNGKey(4), 4, p_valid=0.9)
    mask = jnp.ones((4, A), bool)

    def loss(p):
        logits, value = net.apply(p, obs, mask)
        return jnp.sum(value ** 2) + jnp.sum(
            jax.nn.log_softmax(logits)[:, 0]), value

    (value_of, value), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)
    assert np.isfinite(float(value_of))
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g))), jax.tree_util.keystr(path)
    _, r_value = reference(params, obs, mask, spec_of(cfg))
    assert float(jnp.max(jnp.abs(value - r_value))) < 0.2


# ---- group-limited choice ----------------------------------------------

def chosen_by_program(choice, cfg):
    idx = trunk_lib.choose_experts(choice, cfg.num_experts_per_tok,
                                   cfg.n_group, cfg.topk_group)
    return np.asarray(jnp.sum(jax.nn.one_hot(idx, choice.shape[-1]),
                              axis=-2))


@pytest.mark.parametrize("case", ["random", "ties", "bias", "flat"])
def test_group_limited_choice_against_a_sort(case):
    """``choose_experts`` (top-k primitives) selects what the reference's
    stable sorts select, ties included (both give the lower index), and
    never an expert outside the groups kept."""
    cfg = dataclasses.replace(TINY, num_experts=32, n_group=4, topk_group=2,
                              num_experts_per_tok=4, experts_held=(0, 8))
    choice = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3),
                                              (50, 32)))
    if case == "ties":      # a few levels only: ties everywhere
        choice = jnp.round(choice * 4) / 4
    elif case == "bias":    # the bias decides: group 3 always kept
        choice = choice.at[:, 24:].add(1.0)
    elif case == "flat":
        cfg = dataclasses.replace(cfg, n_group=1, topk_group=1)
    got = chosen_by_program(choice, cfg)
    want = np.asarray(ref.chosen_experts(choice, spec_of(cfg)))
    assert np.array_equal(got, want)
    assert (got.sum(-1) == 4).all()
    per_group = got.reshape(50, cfg.n_group, -1).sum(-1)
    assert ((per_group > 0).sum(-1) <= cfg.topk_group).all()
    if case == "bias":
        assert (per_group[:, 3] > 0).all()


# ---- the chip's share ---------------------------------------------------

SHARES = dataclasses.replace(TINY, experts_held=(0, 16))


def expert_layer(cfg, params, x):
    layer = trunk_lib.ExpertLayer(cfg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return layer.apply({"params": params}, x)


def whole_layer_params(seed=3):
    layer = trunk_lib.ExpertLayer(SHARES, jnp.float32)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 4, SHARES.hidden_size)))
    return weights.make_params(shapes, seed)["params"]


def share_params(whole, first, count):
    """The leaves chip ``first // count`` holds of the whole layer's."""
    d, f, E = SHARES.hidden_size, SHARES.moe_intermediate_size, \
        SHARES.num_experts
    cut = lambda k, i, o: k.reshape(i, E, o)[:, first:first + count] \
        .reshape(i, count * o)
    out = dict(whole)
    for name, i, o in (("experts_gate", d, f), ("experts_up", d, f),
                       ("experts_down", f, d)):
        out[name] = {"kernel": cut(whole[name]["kernel"], i, o)}
    return out


def test_the_four_shares_add_up_to_the_uncut_layer():
    """At 16 experts in 4 groups: the four chips' routed parts (4 experts
    each, one whole group) plus the shared expert ONCE = the layer with
    every expert held = the uncut reference's layer; and each share is the
    reference's share."""
    whole = whole_layer_params()
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 9, SHARES.hidden_size))
    full = expert_layer(SHARES, whole, x)
    shared = trunk_lib.GatedMLP(SHARES.shared_intermediate_size,
                                SHARES.hidden_size, jnp.float32)
    spec = spec_of(SHARES)
    with jax.default_matmul_precision("highest"):
        once = shared.apply({"params": whole["shared"]}, x)
        total = once
        for first in range(0, 16, 4):
            cfg = dataclasses.replace(SHARES, experts_held=(first, 4))
            part = share_params(whole, first, 4)
            mine = expert_layer(cfg, part, x) - once
            routed = ref.experts(part, x, spec, None, held=(first, 4))
            assert float(jnp.max(jnp.abs(mine - routed))) < 1e-5
            total = total + mine
        r_full = ref.expert_layer(whole, x, spec, None)
    scale = max(float(jnp.max(jnp.abs(full))), 1.0)
    assert float(jnp.max(jnp.abs(total - full))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(total - r_full))) < 1e-5 * scale


# ---- MLA on both lowerings ----------------------------------------------

@pytest.mark.parametrize("path", [trunk_lib.PLAIN, trunk_lib.KERNEL])
def test_mla_against_materialised_scores(monkeypatch, path):
    """The MLA layer through ``attend`` and through the blocked kernel
    (interpreted here; q and k zero-padded from 48 to 128 channels, v left
    at 32) equals the reference's materialised scores. ``plain`` is
    float32 to 1e-5; the kernel's wrapper and tiles are held to 2e-5."""
    monkeypatch.setattr(trunk_lib, "attention_path",
                        lambda backend, head_dim, mesh_bound: path)
    layer = trunk_lib.MLA(TINY, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (3, T, TINY.hidden_size))
    valid = jnp.ones((3, T), bool).at[:, 6].set(False).at[1, 17:].set(False)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u, valid)
    params = weights.make_params(shapes, 11)
    with jax.default_matmul_precision("highest"):
        got, sown = layer.apply(params, u, valid,
                                mutable=[trunk_lib.COUNTERS])
        want = ref.mla(params["params"], u, valid, spec_of(TINY), None)
    tiles = float(sown[trunk_lib.COUNTERS]["attn_tiles"][0])
    assert (tiles > 0) == (path == trunk_lib.KERNEL)
    ok = np.asarray(valid)      # an invalid query's output is nobody's
    err = np.abs(np.asarray(got) - np.asarray(want))[ok]
    assert err.max() < 2e-5, err.max()


# ---- KDA on both lowerings -------------------------------------------------

# the tiny block with heads the kernels take: 128 channels a head, chunks of
# one sub-block
WIDE_HEADS = dataclasses.replace(TINY, num_attention_heads=2, head_dim=128,
                                 kda_chunk=16)


@pytest.mark.parametrize("path", [trunk_lib.kda.PLAIN, trunk_lib.kda.KERNEL])
def test_kda_layer_against_the_token_by_token_reference(monkeypatch, path):
    """A KDA layer through the plain chunks and through the kernel pair
    (interpreted here), 20 tokens in two chunks of 16 with tokens passed
    over, equals the reference's recurrence, float32 to 1e-4 of the
    output's scale (the kernel's solve is three bfloat16 passes); the
    layer's counter says which ran."""
    monkeypatch.setattr(trunk_lib.kda, "delta_rule_path",
                        lambda *build: path)
    layer = trunk_lib.KDA(WIDE_HEADS, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (3, T, TINY.hidden_size))
    valid = jnp.ones((3, T), bool).at[:, 6].set(False).at[1, 15:].set(False)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), u, valid)
    params = weights.make_params(shapes, 11)
    with jax.default_matmul_precision("highest"):
        got, sown = layer.apply(params, u, valid,
                                mutable=[trunk_lib.COUNTERS])
        want = ref.kda(params["params"], u, valid, spec_of(TINY), None)
    assert float(sown[trunk_lib.COUNTERS]["kda_kernel"][0]) == (
        path == trunk_lib.kda.KERNEL)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("backend,layers", [("cpu", 0.0), ("tpu", 2.0)])
def test_the_counter_reads_the_path_functions_answer(monkeypatch, backend,
                                                     layers):
    """``kda_kernel_layers`` is ``ops.kda.delta_rule_path``'s answer summed
    over the KDA layers. The trunk asks ``jax.default_backend()`` and the
    test answers for it: a bfloat16 trunk of three layers, two of them KDA
    with heads the kernels take, reads 0 on this backend and 2 where the
    answer is a TPU (no TPU is attached, so the kernels are made to run
    interpreted whatever the backend is said to be); a float32 build of it
    reads 0 on either."""
    from rlgpuschedule_tpu.ops import kda_kernel
    real = kda_kernel.chunks
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    # the described backend is not attached: interpret whatever it says
    monkeypatch.setattr(kda_kernel, "chunks",
                        lambda *a: real(*a[:-1], True))
    monkeypatch.setattr(trunk_lib, "attention_path",
                        lambda *build: trunk_lib.PLAIN)
    cfg = dataclasses.replace(WIDE_HEADS, num_hidden_layers=3,
                              layer_group_size=3)
    assert cfg.kda_layers == 2
    net = trunk_lib.TokenTrunk(cfg, dtype=jnp.bfloat16)
    obs = observations(jax.random.PRNGKey(3), 2)
    params = net.init(jax.random.PRNGKey(0), obs[:1])
    out, sown = net.apply(params, obs, mutable=[trunk_lib.COUNTERS])
    c = trunk_lib.read_counters(sown[trunk_lib.COUNTERS])
    assert float(c["kda_kernel_layers"]) == layers
    assert bool(jnp.all(jnp.isfinite(out)))
    f32 = trunk_lib.TokenTrunk(cfg, dtype=jnp.float32)
    _, sown = f32.apply(params, obs, mutable=[trunk_lib.COUNTERS])
    assert float(trunk_lib.read_counters(sown[trunk_lib.COUNTERS])[
        "kda_kernel_layers"]) == 0.0


# ---- invalid tokens -----------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_what_an_invalid_token_holds_is_nothing_to_the_policy(dtype):
    """The features of a token whose ``valid`` is 0 may be anything: logits
    and values do not move (the convolutions are fed zeros there, the KDA
    state passes over it, MLA masks its key, the pool leaves it out)."""
    net, params = policy(TINY, dtype)
    obs = observations(jax.random.PRNGKey(8), 6)
    mask = jnp.ones((6, A), bool)
    valid = obs[..., -1:] > 0.5
    assert not bool(jnp.all(valid))
    junk = jax.random.normal(jax.random.PRNGKey(9), obs.shape) * 3.0
    other = jnp.where(valid, obs, junk.at[..., -1].set(0.0))
    apply = jax.jit(net.apply)
    logits, value = apply(params, obs, mask)
    o_logits, o_value = apply(params, other, mask)
    assert np.array_equal(np.asarray(logits), np.asarray(o_logits))
    assert np.array_equal(np.asarray(value), np.asarray(o_value))


# ---- the normal path -----------------------------------------------------

def test_preset_trains_three_iterations_through_experiment_run():
    from rlgpuschedule_tpu.algos import PPOConfig
    from rlgpuschedule_tpu.experiment import Experiment
    cfg = dataclasses.replace(
        CONFIGS["ppo-ling-philly512"], trunk="ling-tiny", n_envs=4,
        n_nodes=2, gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
        ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2))
    assert cfg.obs_kind == "tokens"
    exp = Experiment.build(cfg)
    out = exp.run(iterations=3, log_every=1)
    assert len(out["history"]) == 3
    for h in out["history"]:
        assert np.isfinite(h["total_loss"])
        assert h["moe_dropped_assignments"] == 0.0
        # what the configuration fixes is no counter (trunk.describe)
        assert not {"kda_layers", "kda_chunk", "moe_groups_kept"} & set(h)
        assert h["attn_kernel_layers"] == 0.0       # a CPU: the plain path
        assert h["kda_kernel_layers"] == 0.0        # ... for the rule too
        assert 0 < h["moe_assignments_held"] <= 16 * 18 * 2 * 5
    # the start-up account's record of that call keeps its last logged
    # iteration, path counters and all (benchmark/readers/run_counters.py)
    from rlgpuschedule_tpu.algos.ppo import MOE_COUNTERS
    from rlgpuschedule_tpu.obs.startup import ACCOUNT
    run = [s for s in ACCOUNT.snapshot()["spans"] if s["name"] == "run"][-1]
    assert run["metrics"] == out["history"][-1]
    assert set(MOE_COUNTERS) <= set(run["metrics"])


def test_preset_trains_checkpoints_serves_and_evaluates(tmp_path,
                                                        monkeypatch):
    """``ppo-ling-philly512`` through ``train -> checkpoint -> serve ->
    evaluate`` as ``chip_smoke.py`` drives the CNN and Trinity presets: the
    same three CLIs, phases and checks, at the tiny shape and trunk."""
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "CONFIG", "ppo-ling-philly512")
    size = dict(chip_smoke.TINY,
                shape=[*chip_smoke.TINY["shape"], "--trunk", "ling-tiny"])
    smoke = chip_smoke.Smoke()
    chip_smoke.run_one_chip(smoke, size, str(tmp_path), seed=0)
    assert smoke.ran == ["train", "serve", "evaluate"]
    assert not smoke.failed


def test_make_policy_and_the_clis_name_every_trunk():
    net = make_policy("tokens", A, trunk="ling-tiny")
    assert isinstance(net.encoder, trunk_lib.TokenTrunk)
    assert net.encoder.cfg == TINY and TINY.family == "ling"
    assert TRUNKS["tiny"].family == TRUNKS["published"].family == "afmoe"
    assert CONFIGS["ppo-ling-philly512"].trunk == "ling"
    from rlgpuschedule_tpu import evaluate, select_checkpoint, train
    from rlgpuschedule_tpu.serve import __main__ as serve
    for cli in (train, evaluate, select_checkpoint, serve):
        action = next(a for a in cli.build_parser()._actions
                      if a.dest == "trunk")
        assert list(action.choices) == list(TRUNKS), cli.__name__


def test_published_widths_are_the_catalogs():
    """The defaults ARE the source's widths, the file states them
    unchanged, and the cut is depth, the dense layers, the experts held
    and the vocabulary; 666.8M parameters, 16 B each = 10.67 GB."""
    cfg = config_file()
    c = TRUNKS["ling"]
    for key, attr in (
            ("hidden_size", "hidden_size"),
            ("num_attention_heads", "num_attention_heads"),
            ("head_dim", "head_dim"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("intermediate_size", "intermediate_size"),
            ("moe_intermediate_size", "moe_intermediate_size"),
            ("moe_shared_expert_intermediate_size",
             "shared_intermediate_size"),
            ("num_experts_per_tok", "num_experts_per_tok"),
            ("n_group", "n_group"), ("topk_group", "topk_group"),
            ("routed_scaling_factor", "route_scale"),
            ("norm_topk_prob", "route_norm"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps"),
            ("layer_group_size", "layer_group_size"),
            ("short_conv_kernel_size", "short_conv_kernel_size"),
            ("kda_lower_bound", "kda_lower_bound"),
            ("first_k_dense_replace", "num_dense_layers"),
            ("num_hidden_layers", "num_hidden_layers"),
            ("kda_chunk", "kda_chunk")):
        assert cfg[key] == getattr(c, attr), key
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["kv_lora_rank"], cfg["n_group"]) == (2560, 768, 512, 8)
    assert cfg["num_experts"] == c.experts_held[1] == 8
    assert cfg["num_experts_published"] == c.num_experts == 512
    assert (cfg["num_hidden_layers_published"],
            cfg["first_k_dense_replace_published"]) == (42, 2)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {e["name"]: e for e in json.load(f)["configs"]}[
            "philly512-ling"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "trace_source", "chips"}
    assert "vocab_size" not in cfg
    assert "64 chips share each layer" in cfg["deployment"]
    assert cfg["preset"] == "ppo-ling-philly512"
    assert "--trunk" in cfg["rehearse_overrides"]
    assert "--trunk" not in cfg["overrides"]
    assert len(cfg["guarantees"]) == 3
    net = make_policy("tokens", 129, trunk="ling")
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 832, 11)), jnp.ones((1, 129), bool))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == cfg["parameters"] == 666_772_770
    enc = shapes["params"]["encoder"]
    assert enc["layer_0"]["attn"]["f_proj"]["kernel"].shape == (2560, 4096)
    assert enc["layer_0"]["attn"]["q_conv"]["kernel"].shape == (4, 4096)
    assert enc["layer_5"]["attn"]["q_proj"]["kernel"].shape == (2560, 6144)
    assert enc["layer_5"]["attn"]["kv_b_proj"]["kernel"].shape == (512, 8192)
    assert enc["layer_1"]["moe"]["router"]["kernel"].shape == (2560, 512)
    assert enc["layer_1"]["moe"]["experts_gate"]["kernel"].shape == (
        2560, 8 * 768)
    assert "mlp" in enc["layer_0"] and "moe" not in enc["layer_0"]


def test_every_leaf_has_a_sharding_rule_and_a_weights_rule():
    """Each leaf of the new trunk ends in ``kernel``, ``scale`` or ``bias``
    (what ``benchmark/reference/weights.py`` fills) and is matched by a
    rule of ``RULE_TABLES['tokens']`` before the catch-all; the held
    experts' kernels go on the ``model`` axis, everything else is
    replicated."""
    import re

    from jax.sharding import PartitionSpec as P

    from rlgpuschedule_tpu.parallel.sharding import MODEL_AXIS, RULE_TABLES
    rules = RULE_TABLES["tokens"]
    assert rules[-1][0] == ".*"
    _, params = policy(TINY, jnp.float32)
    flat, _ = jax.tree_util.tree_flatten_with_path(params["params"])
    assert len(flat) > 100
    for path, _ in flat:
        name = "/".join(p.key for p in path)
        assert name.rsplit("/", 1)[-1] in ("kernel", "scale", "bias"), name
        spec = next(s for pattern, s in rules[:-1]
                    if re.search(pattern, name))
        expert = "/experts_" in name
        assert spec == (P(None, MODEL_AXIS) if expert else P()), name


def test_a_mesh_build_meets_no_unknown_leaf():
    """``Experiment.build(mesh=)`` at ``model`` = 2: every leaf of the new
    trunk gets a rule's sharding (the held experts' kernels split, whole
    experts a shard; KDA's and MLA's leaves replicated), the score product
    takes the plain path under the mesh, and the step computes the plain
    step's losses to the rounding a partitioned sum allows."""
    from jax.sharding import PartitionSpec as P

    from rlgpuschedule_tpu.algos import PPOConfig
    from rlgpuschedule_tpu.experiment import Experiment
    from rlgpuschedule_tpu.parallel import MODEL_AXIS, make_unified_mesh
    cfg = dataclasses.replace(
        CONFIGS["ppo-ling-philly512"], trunk="ling-tiny", n_envs=2,
        n_nodes=2, gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
        ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2))
    plain = Experiment.build(cfg).run(iterations=2, log_every=1)["history"]
    mesh = make_unified_mesh(n_model=2, devices=jax.devices()[:2])
    exp = Experiment.build(cfg, mesh=mesh)
    meshed = exp.run(iterations=2, log_every=1)["history"]
    enc = exp.train_state.params["params"]["encoder"]
    gate = enc["layer_1"]["moe"]["experts_gate"]["kernel"]
    assert gate.sharding.spec == P(None, MODEL_AXIS)
    for name in ("q_conv", "f_proj", "A_log", "dt", "o_norm"):
        leaf = jax.tree.leaves(enc["layer_0"]["attn"][name])[0]
        assert leaf.sharding.is_fully_replicated, name
    for leaf in jax.tree.leaves(enc["layer_2"]["attn"]):
        assert leaf.sharding.is_fully_replicated
    np.testing.assert_allclose([h["total_loss"] for h in meshed],
                               [h["total_loss"] for h in plain],
                               rtol=1e-2, atol=1e-3)
    assert all(h["attn_kernel_layers"] == 0.0 for h in meshed)
    assert all(h["kda_kernel_layers"] == 0.0 for h in meshed)


def test_train_cli_says_once_what_the_trunk_fixes(tmp_path):
    """The layers of each kind, the chunk and the expert groups are the
    configuration's: the run's summary states them once
    (``trunk.describe``), and the iteration's rows carry only counters
    that vary with the data."""
    import csv

    from rlgpuschedule_tpu import train as train_cli
    path = tmp_path / "train.csv"
    summary = train_cli.main([
        "--config", "ppo-ling-philly512", "--trunk", "ling-tiny",
        "--n-envs", "4", "--n-nodes", "2", "--gpus-per-node", "4",
        "--window-jobs", "16", "--queue-len", "4", "--horizon", "64",
        "--n-steps", "8", "--n-epochs", "1", "--n-minibatches", "2",
        "--iterations", "2", "--log-every", "1", "--log-csv", str(path)])
    assert summary["trunk"] == {
        "name": "ling-tiny", "family": "ling", "layers": 6, "loop_steps": 1,
        "kda_layers": 4, "kda_chunk": 8, "moe_groups": 4,
        "moe_groups_kept": 2}
    with open(path) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2
    for row in rows:
        assert float(row["moe_dropped_assignments"]) == 0.0
        assert float(row["moe_assignments_held"]) > 0
        assert "kda_chunk" not in row and "moe_groups_kept" not in row
