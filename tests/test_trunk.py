"""The token trunk (``models.trunk``) at its tiny shape on the CPU: against
the benchmark's plain reference (``benchmark/reference/forward_tokens.py``)
on seeded weights; the chip's shares of an expert layer add up to the
uncut layer; routing is dropless and a row's result does not depend on
which rows share its batch; the sliding mask against a brute-force one;
``token_obs`` against a numpy build from the host oracle's state.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import forward as ref_forward
from benchmark.reference import forward_tokens as ref
from benchmark.reference import weights
from rlgpuschedule_tpu.models import TRUNKS, make_policy
from rlgpuschedule_tpu.models import trunk as trunk_lib
from rlgpuschedule_tpu.models.actor_critic import ActorCritic

TINY = TRUNKS["tiny"]
T, F, A = 20, 11, 5          # the rehearsal's 4 nodes + 16 jobs


def spec_of(cfg: trunk_lib.TrunkConfig, T: int = T) -> dict:
    """What ``forward_tokens`` reads from a configuration file, for a
    trunk the test made itself."""
    return {"hidden_size": cfg.hidden_size,
            "layer_types": list(cfg.layer_types),
            "sliding_window": cfg.sliding_window,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "num_dense_layers": cfg.num_dense_layers,
            "experts_held_first": cfg.experts_held[0], "tokens_per_row": T}


def observations(key, rows: int, T: int = T, p_valid: float = 0.7):
    """Rows of token features; some job tokens are not valid (all zeros),
    the first four (the nodes) always are."""
    k1, k2 = jax.random.split(key)
    obs = jax.random.uniform(k1, (rows, T, F), minval=-1.0)
    valid = jax.random.bernoulli(k2, p_valid, (rows, T)).at[:, :4].set(True)
    obs = obs.at[..., -1].set(1.0) * valid[..., None]
    return obs


def policy(cfg: trunk_lib.TrunkConfig, dtype, seed: int = 7, T: int = T):
    net = ActorCritic(trunk_lib.TokenTrunk(cfg, dtype=dtype), A)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, T, F)), jnp.ones((1, A), bool))
    return net, weights.make_params(shapes, seed)


def reference(params, obs, mask, cfg, quant=None):
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        h = ref.trunk(p["encoder"], obs, quant, spec_of(cfg, obs.shape[-2]))
        logits = ref_forward.dense(h, p["policy"], quant)
        value = ref_forward.dense(h, p["value"], quant)[..., 0]
    return jnp.where(mask, logits, ref_forward.NEG_INF), value


def file_settings(rehearse: bool) -> dict:
    """The settings the harness hands ``forward_tokens`` for the standing
    token cell (``common.Reference``: the file's top level in a run,
    overlaid by its ``rehearse_trunk`` in a rehearsal)."""
    from benchmark import common
    config = common.load_json("configs", "philly512-trinity.json")
    return common.Reference(config, rehearse).settings


def test_the_configuration_file_states_the_tiny_trunk():
    """The file's ``rehearse_trunk`` overlay is ``TRUNKS['tiny']`` and its
    top level ``TRUNKS['published']``, in every setting the reference
    reads."""
    for name, tokens, rehearse in (("tiny", 20, True),
                                   ("published", 832, False)):
        want = spec_of(TRUNKS[name], tokens)
        settings = file_settings(rehearse)
        got = {k: settings[k] for k in want}
        n = len(want["layer_types"])
        got["layer_types"] = got["layer_types"][:n]
        assert got == want, name


def test_float32_program_equals_the_plain_reference():
    net, params = policy(TINY, jnp.float32)
    obs = observations(jax.random.PRNGKey(1), 6)
    mask = jnp.ones((6, A), bool).at[:, 1].set(False)
    with jax.default_matmul_precision("highest"):
        logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, TINY)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 1e-5
    assert float(jnp.max(jnp.abs(value - r_value))) < 1e-5
    # and through the file's own settings, as the harness hands them over
    with jax.default_matmul_precision("highest"):
        by_file = ref.trunk(params["params"]["encoder"], obs, None,
                            file_settings(rehearse=True))
        by_spec = ref.trunk(params["params"]["encoder"], obs, None,
                            spec_of(TINY))
    assert np.array_equal(np.asarray(by_file), np.asarray(by_spec))


def top_sets(params, obs, cfg, dtype):
    """Each expert layer's selected experts per token, as sorted index
    sets, from the program's own router on the program's own stream."""
    net = ActorCritic(trunk_lib.TokenTrunk(cfg, dtype=dtype), A)
    _, state = net.apply(params, obs, jnp.ones((obs.shape[0], A), bool),
                         capture_intermediates=lambda m, _: isinstance(
                             m, trunk_lib.RMSNorm)
                         and m.name == "pre_mlp_norm")
    sets = []
    inter = state["intermediates"]["encoder"]
    for i in range(cfg.num_dense_layers, cfg.num_hidden_layers):
        z = inter[f"layer_{i}"]["pre_mlp_norm"]["__call__"][0]
        moe = params["params"]["encoder"][f"layer_{i}"]["moe"]
        s = jax.nn.sigmoid(z.astype(jnp.float32).reshape(-1, z.shape[-1])
                           @ moe["router"]["kernel"])
        _, idx = jax.lax.top_k(s + moe["bias"], cfg.num_experts_per_tok)
        sets.append(np.sort(np.asarray(idx), axis=-1))
    return np.stack(sets)


def test_bfloat16_program_stays_near_the_reference():
    """The stated precision against float32: logits (times the policy
    head's 0.01 start) and values within 0.05 of the values' spread. A
    token whose k-th and (k+1)-th router scores lie within bfloat16's
    rounding of the stream may choose another expert: the share of
    (layer, token) pairs whose chosen set differs is printed and stays a
    minority."""
    net, params = policy(TINY, jnp.bfloat16)
    obs = observations(jax.random.PRNGKey(2), 16)
    mask = jnp.ones((16, A), bool)
    logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, TINY)
    spread = float(jnp.std(r_value))
    assert float(jnp.max(jnp.abs(value - r_value))) < 0.05 * max(spread, 1.0)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 5e-4
    half = top_sets(params, obs, TINY, jnp.bfloat16)
    full = top_sets(params, obs, TINY, jnp.float32)
    differs = float(np.mean(np.any(half != full, axis=-1)))
    print(f"share of (layer, token) pairs whose top-"
          f"{TINY.num_experts_per_tok} set differs in bfloat16: "
          f"{differs:.4f}")
    assert differs < 0.25


SHARES = dataclasses.replace(TINY, num_experts=16, num_experts_per_tok=4,
                             experts_held=(0, 16))


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


@pytest.mark.parametrize("count", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(count):
    """16 / count chips' routed parts + the shared expert ONCE = the
    layer with every expert held, in the program and in the reference."""
    whole = whole_layer_params()
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 9, SHARES.hidden_size))
    full = expert_layer(SHARES, whole, x)
    shared = trunk_lib.GatedMLP(SHARES.moe_intermediate_size,
                                SHARES.hidden_size, jnp.float32)
    with jax.default_matmul_precision("highest"):
        once = shared.apply({"params": whole["shared"]}, x)
        total, r_total = once, once
        spec = spec_of(SHARES)
        for first in range(0, SHARES.num_experts, count):
            cfg = dataclasses.replace(SHARES, experts_held=(first, count))
            part = share_params(whole, first, count)
            total = total + expert_layer(cfg, part, x) - once
            routed = ref.experts(part, x, spec, None, held=(first, count))
            # the program's share and the reference's share, one by one
            assert float(jnp.max(jnp.abs(
                expert_layer(cfg, part, x) - once - routed))) < 1e-5
            r_total = r_total + routed
        r_full = ref.expert_layer(whole, x, spec, None)
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(total - full))) < 1e-5 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(r_total - r_full))) < 1e-5 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(full - r_full))) < 1e-5 * max(scale, 1.0)


def counted(cfg, params, x):
    layer = trunk_lib.ExpertLayer(cfg, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, sown = layer.apply({"params": params}, x,
                              mutable=[trunk_lib.COUNTERS])
    return y, {k: float(v) for k, v in trunk_lib.read_counters(
        sown[trunk_lib.COUNTERS]).items()}


def test_every_token_on_one_held_expert_loses_none():
    """The worst case the buffers are sized for: the router sends every
    token's every choice to experts held here, and one of them gets a
    choice of EVERY token. Nothing is dropped and the result is the
    reference's."""
    cfg = dataclasses.replace(SHARES, experts_held=(4, 4))
    whole = whole_layer_params(seed=5)
    part = share_params(whole, 4, 4)
    # the bias is in the selection only: +10 on experts 4-7 makes them
    # every token's four choices
    part["bias"] = jnp.zeros((16,)).at[4:8].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (5, 7, SHARES.hidden_size))
    y, c = counted(cfg, part, x)
    n = 5 * 7
    assert c["moe_assignments_held"] == n * cfg.num_experts_per_tok
    assert c["moe_dropped_assignments"] == 0
    assert c["moe_expert_load_max_over_mean"] == 1.0   # n on each of four
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(part, x, dict(spec_of(cfg)), None)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))
    # and none held: the shared expert alone, nothing dropped
    part["bias"] = jnp.zeros((16,)).at[8:12].set(10.0)
    y, c = counted(cfg, part, x)
    assert c["moe_assignments_held"] == 0
    assert c["moe_dropped_assignments"] == 0
    shared = trunk_lib.GatedMLP(cfg.moe_intermediate_size, cfg.hidden_size,
                                jnp.float32)
    with jax.default_matmul_precision("highest"):
        once = shared.apply({"params": part["shared"]}, x)
    assert float(jnp.max(jnp.abs(y - once))) < 1e-6


# 640 tokens of 4 choices among 16 experts, 4 of them held: the short
# buffer (``trunk_lib.short_rows``) is 1,536 rows, the worst case 2,560
STEERED = dataclasses.replace(SHARES, experts_held=(4, 4))
N_STEERED = 640
assert trunk_lib.short_rows(N_STEERED, 4) == 1536


def steered_params(seed=11):
    """A share's leaves whose router reads a token's first 16 features,
    one an expert, so that a test chooses each token's experts."""
    part = share_params(whole_layer_params(seed), 4, 4)
    steer = jnp.zeros((STEERED.hidden_size, 16)).at[:16].set(
        2.0 * jnp.eye(16))
    part["router"] = {"kernel": steer}
    return part


def steered_tokens(key, held_of_token):
    """``x[n, d]`` whose token ``t`` chooses ``held_of_token[t]`` of the
    four held experts (4-7) and the rest of its four among the others:
    +1 on a chosen expert's feature, -1 on the others' (the router's
    margin), a little noise so that the weights differ."""
    held_of_token = np.asarray(held_of_token)
    n = len(held_of_token)
    k1, k2 = jax.random.split(key)
    drive = -np.ones((n, 16), np.float32)
    others = [e for e in range(16) if not 4 <= e < 8]
    for t, h in enumerate(held_of_token):
        drive[t, [4 + (t + j) % 4 for j in range(h)]] = 1.0
        drive[t, [others[(t + j) % 12] for j in range(4 - h)]] = 1.0
    drive = drive + 0.3 * jax.random.uniform(k1, (n, 16), minval=-1.0)
    rest = jax.random.normal(k2, (n, STEERED.hidden_size - 16))
    return jnp.concatenate([drive, rest], axis=-1)


def spread(total: int, n: int) -> list[int]:
    """``total`` held assignments over ``n`` tokens, four a token from
    the first token on."""
    return [min(4, max(0, total - 4 * t)) for t in range(n)]


@pytest.mark.parametrize("held", [0, 1, 1535, 1536, 1537, 2560])
def test_both_buffer_lengths_are_the_dense_reference(held):
    """``routed_experts`` at the held counts around its short buffer's
    length (1,536 rows here) and at the worst case: the layer's value
    and its gradients (the tokens, the router, the three expert kernels,
    the shared expert) are the dense reference's on either side of the
    branch, nothing is dropped, and the counter says which length ran."""
    part = steered_params()
    x = steered_tokens(jax.random.PRNGKey(12), spread(held, N_STEERED)
                       ).reshape(5, 128, -1)
    cot = jax.random.normal(jax.random.PRNGKey(13), x.shape)
    y, c = counted(STEERED, part, x)
    assert c["moe_assignments_held"] == held
    assert c["moe_dropped_assignments"] == 0
    assert c["moe_short_path_share"] == float(held <= 1536)
    layer = trunk_lib.ExpertLayer(STEERED, jnp.float32)
    spec = dict(spec_of(STEERED))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(part, x, spec, None)
        got = jax.grad(lambda p, x: jnp.sum(layer.apply(
            {"params": p}, x) * cot), argnums=(0, 1))(part, x)
        wanted = jax.grad(lambda p, x: jnp.sum(ref.expert_layer(
            p, x, spec, None) * cot), argnums=(0, 1))(part, x)
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(b))))
    assert close(y, want)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, leaf), ref_leaf in zip(flat, jax.tree.leaves(wanted)):
        assert close(leaf, ref_leaf), jax.tree_util.keystr(path)
    if held:    # the held experts' kernels did get a gradient
        assert float(jnp.max(jnp.abs(
            got[0]["experts_down"]["kernel"]))) > 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_rows_result_is_the_same_from_either_buffer(dtype):
    """The rows beside a row decide which buffer its block takes: three
    mates whose every choice is a held expert's push the block past the
    short buffer (1,920 + the row's own of 2,560 rows; 1,536 fit), three
    mates with none leave it inside. The row's result is bit for bit the
    same: the same terms, added in the same order, at either length."""
    part = steered_params()
    T = N_STEERED // 4
    row = steered_tokens(jax.random.PRNGKey(14), [t % 3 for t in range(T)])
    mates = lambda h, key: steered_tokens(key, [h] * (3 * T)).reshape(
        3, T, -1)
    layer = trunk_lib.ExpertLayer(STEERED, dtype)
    apply = jax.jit(lambda x: layer.apply(
        {"params": part}, x.astype(dtype), mutable=[trunk_lib.COUNTERS]))
    block = lambda m: jnp.concatenate([m[:1], row[None], m[1:]], axis=0)
    short, c_short = apply(block(mates(0, jax.random.PRNGKey(15))))
    long, c_long = apply(block(mates(4, jax.random.PRNGKey(16))))
    share = lambda c: float(trunk_lib.read_counters(
        c[trunk_lib.COUNTERS])["moe_short_path_share"])
    assert (share(c_short), share(c_long)) == (1.0, 0.0)
    assert float(jnp.max(jnp.abs(short[1].astype(jnp.float32)))) > 0.1
    assert np.array_equal(np.asarray(short[1], np.float32),
                          np.asarray(long[1], np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_rows_logits_do_not_depend_on_its_minibatch(dtype):
    """Token-choice, dropless: PPO's shuffle may put any rows beside a
    row, and its logits stay bit for bit what they were. (The value
    head's ``[B, 1]`` product reassociates with the row's place in the
    batch on some backends, as ``algos.ppo.compute_advantages`` notes of
    every policy here: it is held to an ulp or two, which no dropped or
    re-routed assignment would pass.)"""
    net, params = policy(TINY, dtype)
    obs = observations(jax.random.PRNGKey(8), 12)
    mask = jnp.ones((12, A), bool)
    apply = jax.jit(net.apply)
    logits, value = apply(params, obs, mask)
    perm = jnp.asarray([5, 0, 11, 3, 8, 1, 10, 2, 7, 4, 9, 6])
    p_logits, p_value = apply(params, obs[perm], mask[perm])
    assert np.array_equal(np.asarray(p_logits), np.asarray(logits[perm]))
    np.testing.assert_allclose(np.asarray(p_value, np.float32),
                               np.asarray(value[perm], np.float32),
                               rtol=1e-6, atol=1e-6)
    # beside other rows altogether (the same batch size: one program)
    other = observations(jax.random.PRNGKey(9), 12)
    mixed = other.at[4].set(obs[2])
    m_logits, m_value = apply(params, mixed, mask)
    assert np.array_equal(np.asarray(m_logits[4]), np.asarray(logits[2]))
    np.testing.assert_allclose(np.asarray(m_value[4], np.float32),
                               np.asarray(value[2], np.float32), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("window", [None, 1, 8, 19, 64])
def test_attention_mask_against_brute_force(window):
    valid = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(10), 0.6,
                                            (3, T)))
    got = np.asarray(trunk_lib.attention_mask(jnp.asarray(valid), window))
    for b in range(3):
        for q in range(T):
            for k in range(T):
                want = (k <= q and valid[b, k]
                        and (window is None or q - k < window))
                assert got[b, q, k] == want, (b, q, k)


def test_sliding_layers_see_the_window_only():
    """At window 8 < T a sliding layer's output at position q does not
    move when a key further back than the window changes; a full layer's
    does. Both equal attention written out with the brute-force mask."""
    cfg = TINY
    B, Hq, Hkv, D = 2, cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, T, Hkv, Hq // Hkv, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    valid = jnp.ones((B, T), bool).at[:, 6].set(False)
    with jax.default_matmul_precision("highest"):
        for window in (cfg.sliding_window, None):
            out = trunk_lib.attend(q, k, v, valid, window)
            mask = np.zeros((T, T), bool)
            for a in range(T):
                for b in range(T):
                    mask[a, b] = b <= a and (window is None
                                             or a - b < window) and b != 6
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / np.sqrt(D)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
            assert float(jnp.max(jnp.abs(out - want))) < 1e-5
            moved = trunk_lib.attend(q, k.at[:, 2].add(3.0), v, valid,
                                     window)
            far = float(jnp.max(jnp.abs(moved[:, 15:] - out[:, 15:])))
            assert (far == 0.0) == (window is not None), (window, far)


def test_token_obs_against_the_host_oracles_state():
    """``OracleSim`` (numpy, float64 time) and the env are stepped side by
    side through one episode, action by action; at every step the token
    rows built in numpy from the ORACLE's state (its status, allocation,
    free vector, remaining work, clock and queue order) are what
    ``token_obs`` built on the device."""
    from rlgpuschedule_tpu.env import EnvParams, reset, step
    from rlgpuschedule_tpu.env.obs import TOKEN_FEATURES
    from rlgpuschedule_tpu.sim.core import SimParams, Trace, validate_trace
    from rlgpuschedule_tpu.sim.oracle import (DONE, NOT_ARRIVED, PENDING,
                                              RUNNING, OracleSim)
    from rlgpuschedule_tpu.traces import gen_poisson_trace

    N, G, J, K = 4, 4, 24, 6
    sim = SimParams(n_nodes=N, gpus_per_node=G, max_jobs=J, queue_len=K)
    params = EnvParams(sim=sim, obs_kind="tokens", time_scale=100.0,
                       horizon=200)
    assert params.obs_shape() == (N + J, TOKEN_FEATURES)
    at = validate_trace(sim, gen_poisson_trace(
        rate=0.5, n_jobs=20, seed=3, max_jobs=J, mean_duration=40.0),
        clamp=True)
    trace = Trace.from_array_trace(at, sim)
    oracle = OracleSim(at, N, G)
    squash = lambda t: np.tanh(np.float64(t) / params.time_scale)

    def rows(o: OracleSim) -> np.ndarray:
        out = np.zeros((N + J, TOKEN_FEATURES), np.float64)
        for n in range(N):
            used = G - o.free[n]
            on = [j for j in range(J) if o.alloc[j, n] > 0
                  and o.status[j] == RUNNING]
            mean = sum(o.alloc[j, n] * squash(o.remaining[j])
                       for j in on) / max(used, 1)
            out[n] = [o.free[n] / G, used / G, mean, 1, 1, 0, 0, 0, 1, 0, 1]
        queue = o.pending_jobs()[:K]
        for j in range(J):
            if o.status[j] in (NOT_ARRIVED, DONE):
                continue
            out[N + j] = [
                at.gpus[j] / (N * G), squash(o.clock - at.submit[j]),
                squash(at.duration[j]), squash(o.remaining[j]),
                o.status[j] == PENDING, o.status[j] == RUNNING, j in queue,
                queue.index(j) / K if j in queue else 0.0, 0, 1, 1]
        return out

    state, ts = reset(params, trace)
    jstep = jax.jit(lambda st, a: step(params, st, trace, a))
    rng = np.random.default_rng(0)
    seen = set()
    for _ in range(80):
        np.testing.assert_allclose(np.asarray(ts.obs), rows(oracle),
                                   rtol=1e-5, atol=1e-6)
        seen |= set(oracle.status.tolist())
        action = int(rng.choice(np.flatnonzero(np.asarray(ts.action_mask))))
        oracle.rl_step(action, K)
        state, ts = jstep(state, jnp.int32(action))
        if bool(ts.done):
            break
    assert {PENDING, RUNNING, DONE} <= seen and oracle.done()


def test_make_policy_names_the_kinds_it_has():
    net = make_policy("tokens", A, trunk="tiny")
    assert isinstance(net.encoder, trunk_lib.TokenTrunk)
    assert net.encoder.cfg == TINY
    with pytest.raises(ValueError, match="tokens"):
        make_policy("pixels", A)


def test_published_widths_are_the_catalogs():
    """The defaults ARE the source's widths; the cut is depth and the
    experts held; 401.6M parameters, 16 B each = 6.43 GB."""
    c = TRUNKS["published"]
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.sliding_window, c.intermediate_size,
            c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            c.route_scale) == (2048, 32, 4, 128, 2048, 6144, 1024, 128, 8,
                               2.826)
    assert c.experts_held == (0, 8) and c.num_hidden_layers == 5
    net = make_policy("tokens", 129)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 832, 11)), jnp.ones((1, 129), bool))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 401.5e6 < n < 402.0e6, n
    leaf = shapes["params"]["encoder"]["layer_1"]["moe"]
    assert leaf["experts_gate"]["kernel"].shape == (2048, 8 * 1024)
    assert leaf["experts_down"]["kernel"].shape == (1024, 8 * 2048)
    assert leaf["router"]["kernel"].shape == (2048, 128)


def test_preset_trains_checkpoints_serves_and_evaluates(tmp_path,
                                                        monkeypatch):
    """``ppo-trinity-philly512`` through ``train -> checkpoint -> serve ->
    evaluate`` as ``chip_smoke.py`` drives the CNN preset: the same three
    CLIs, phases and checks, at the tiny shape and the tiny trunk."""
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "CONFIG", "ppo-trinity-philly512")
    size = dict(chip_smoke.TINY,
                shape=[*chip_smoke.TINY["shape"], "--trunk", "tiny"])
    smoke = chip_smoke.Smoke()
    chip_smoke.run_one_chip(smoke, size, str(tmp_path), seed=0)
    assert smoke.ran == ["train", "serve", "evaluate"]
    assert not smoke.failed


def test_train_cli_logs_the_expert_counters(tmp_path):
    """The counters ride the iteration's own metrics: one CSV column
    each, nothing dropped."""
    import csv

    from rlgpuschedule_tpu import train as train_cli
    path = tmp_path / "train.csv"
    train_cli.main(["--config", "ppo-trinity-philly512", "--trunk", "tiny",
                    "--n-envs", "4", "--n-nodes", "2", "--gpus-per-node",
                    "4", "--window-jobs", "16", "--queue-len", "4",
                    "--horizon", "64", "--n-steps", "8", "--n-epochs", "1",
                    "--n-minibatches", "2", "--iterations", "2",
                    "--log-every", "1", "--log-csv", str(path)])
    with open(path) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2
    for row in rows:
        assert float(row["moe_dropped_assignments"]) == 0.0
        # 16 rows x 18 tokens x 2 choices x 4 layers a minibatch, a
        # quarter of the experts held
        assert 0 < float(row["moe_assignments_held"]) <= 16 * 18 * 2 * 4
        assert 1.0 <= float(row["moe_expert_load_max_over_mean"]) <= 2.0
        # four rows of 18 tokens a block: the worst case's 144 rows are
        # fewer than a short buffer's 512, so there is none to take
        assert float(row["moe_short_path_share"]) == 0.0


def test_configuration_file_and_trunk_agree_on_every_width():
    """``benchmark/configs/philly512-trinity.json`` holds the source's
    widths unchanged, and they are ``TRUNKS['published']``'s; the cut is
    depth, the experts held and the vocabulary, and is listed."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "philly512-trinity.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "philly512-trinity"]
    c = TRUNKS["published"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "sliding_window", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "route_scale", "route_norm", "rope_theta", "rms_norm_eps",
                "num_dense_layers", "num_hidden_layers"):
        assert cfg[key] == getattr(c, key), key
    assert (cfg["score_func"], cfg["num_shared_experts"],
            cfg["hidden_act"]) == ("sigmoid", 1, "silu")
    assert cfg["num_experts"] == c.experts_held[1] == 8
    assert cfg["num_experts_published"] == c.num_experts == 128
    assert tuple(cfg["layer_types"][:5]) == c.layer_types
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers_published"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size", "trace_source", "chips"}
    assert "vocab_size" not in cfg
    assert "16 chips share each layer" in cfg["deployment"]
    assert cfg["preset"] == "ppo-trinity-philly512"
    assert "--trunk" in cfg["rehearse_overrides"]
    assert "--trunk" not in cfg["overrides"]
