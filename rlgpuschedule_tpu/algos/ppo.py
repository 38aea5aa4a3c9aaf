"""PPO trainer (L4): clipped surrogate, minibatch epochs, entropy bonus.

Capability parity: SURVEY.md §2 "PPO trainer" and §3.1 — the reference's
rollout→GAE→minibatch-update iteration, lowered end-to-end to XLA: the
whole train step (fused rollout scan + GAE reverse scan + epoch×minibatch
update scans) is ONE jitted function. Data-parallel gradient sync — the
TPU-native replacement for the reference's NCCL allreduce (SURVEY.md §2
"Distributed comm backend") — has two assemblies in ``parallel.dp``:
``shard_train`` jits the ``axis_name=None`` step with GSPMD shardings
(XLA inserts the psum), and ``shard_map_train`` wraps an
``axis_name=DATA_AXIS`` step in ``shard_map`` so the ``lax.pmean`` calls
below bind to the mesh axis explicitly.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from flax.training.train_state import TrainState

from ..env.env import EnvParams
from ..obs import scopes
from ..ops.gae import compute_gae
from . import action_dist
from . import update as update_engine
from . import vtrace as vtrace_ops
from .rollout import PolicyApply, RolloutCarry, Transition, rollout


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    n_steps: int = 128          # rollout length T per iteration
    # update geometry (algos.update.resolve_geometry validates the triple
    # against n_steps * n_envs at build time): minibatch_size, when set,
    # DETERMINES the minibatch count and n_minibatches is ignored — so
    # "fewer, larger minibatches" (the measured MXU-fill lever,
    # BASELINE.md "Where the time goes") is one number away.
    n_epochs: int = 4
    n_minibatches: int = 4
    minibatch_size: int | None = None
    # bf16-compute / fp32-optimizer-state update path (NOT bit-identical
    # to fp32 compute — opt-in): loss + grads evaluated in bfloat16,
    # grads cast back to the param dtype before Adam, so moments stay
    # fp32. The encoders already run bf16 activations; this extends the
    # low precision to the update-path params/grads.
    bf16_update: bool = False
    # off-policy correction for the advantage targets: "none" = GAE on
    # the behavior values (the on-policy path), "vtrace" = IMPALA-style
    # importance-weighted targets (algos.vtrace) against the learner's
    # CURRENT value function — required for deep async staleness bounds,
    # pure overhead when the data is on-policy (ratios ≡ 1 reduces it
    # bit-identically to GAE, so bound-0 async runs stay bitwise equal
    # to sync).
    correction: str = "none"
    rho_bar: float = 1.0       # V-trace TD-error weight clip ρ̄
    c_bar: float = 1.0         # V-trace trace-coefficient clip c̄
    # streaming reward standardization (HEPPO-style): scale rewards by
    # 1/√(running variance) with Welford stats carried in the train
    # state (NormTrainState). Scale-only — no centering, which would
    # change the optimal policy under episodic returns.
    reward_norm: bool = False
    # store normalized advantages/returns in bf16 through the
    # epoch×minibatch engine (HEPPO's compressed-advantage pipeline).
    # NOT bit-identical — opt-in, rides the bf16_update seam.
    bf16_advantages: bool = False
    gamma: float = 0.995
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    lr: float = 3e-4
    max_grad_norm: float = 0.5

    def __post_init__(self):
        if self.correction not in ("none", "vtrace"):
            raise ValueError(
                f"PPOConfig.correction must be 'none' or 'vtrace', "
                f"got {self.correction!r}")


def make_optimizer(config: PPOConfig) -> optax.GradientTransformation:
    return optax.chain(optax.clip_by_global_norm(config.max_grad_norm),
                       optax.adam(config.lr, eps=1e-5))


def masked_entropy(logits: jax.Array) -> jax.Array:
    """Entropy of the masked categorical (−1e9 logits contribute ~0).
    Alias of :func:`action_dist.entropy` kept for the public API."""
    return action_dist.entropy(logits)


class PPOMetrics(NamedTuple):
    total_loss: jax.Array
    pg_loss: jax.Array
    v_loss: jax.Array
    entropy: jax.Array
    approx_kl: jax.Array
    clip_frac: jax.Array
    mean_reward: jax.Array
    mean_value: jax.Array
    # unclipped importance-ratio stats from the advantage pipeline —
    # constant 1.0 on the GAE path, the off-policyness monitor under
    # correction="vtrace" (surfaced as async gauges / run_end fields).
    rho_mean: jax.Array
    rho_max: jax.Array


# :class:`PPOMetrics` and the token trunk's counters (what each counts:
# ``models.trunk.read_counters``: assignments held and dropped, the
# fullest held expert's load, the short buffer's share of calls, the
# attention kernel's layer applications and tiles, the KDA layers on the
# chunked rule's kernels, a looped trunk's exit mass and last step), for
# a policy whose apply has a ``counted`` form; read in the update's own
# loss forward, each reduced over the minibatches as
# ``COUNTER_REDUCTIONS`` says (max where none).
MOE_COUNTERS = ("moe_assignments_held", "moe_expert_load_max_over_mean",
                "moe_dropped_assignments", "moe_short_path_share",
                "attn_kernel_layers", "attn_tiles_computed_share",
                "kda_kernel_layers", "loop_exit_mass_last",
                "loop_last_step_change")
COUNTER_REDUCTIONS = {"moe_assignments_held": jnp.mean,
                      "moe_dropped_assignments": jnp.sum,
                      "moe_short_path_share": jnp.mean,
                      "loop_exit_mass_last": jnp.mean,
                      "loop_last_step_change": jnp.mean}
MoEPPOMetrics = NamedTuple("MoEPPOMetrics", [
    *((f, jax.Array) for f in PPOMetrics._fields + MOE_COUNTERS)])


class RewardNormState(NamedTuple):
    """Welford running moments of the raw reward stream (fp32 scalars),
    carried in :class:`NormTrainState` when ``reward_norm`` is on."""
    count: jax.Array
    mean: jax.Array
    m2: jax.Array


def init_reward_stats() -> RewardNormState:
    # three DISTINCT buffers: aliasing one zeros array across the fields
    # trips XLA's double-donation check once the state is donated
    return RewardNormState(count=jnp.zeros((), jnp.float32),
                           mean=jnp.zeros((), jnp.float32),
                           m2=jnp.zeros((), jnp.float32))


def update_reward_stats(stats: RewardNormState, rewards: jax.Array,
                        axis_name: str | None = None) -> RewardNormState:
    """Streaming (Chan/Welford parallel-combine) update from one rollout
    batch. Batch moments are globally reduced across the mesh axis so DP
    replicas carry identical statistics."""
    r = rewards.astype(jnp.float32)
    batch_count = jnp.asarray(r.size, jnp.float32)
    batch_mean = jnp.mean(r)
    batch_sq = jnp.mean(r * r)
    if axis_name is not None:
        batch_count = jax.lax.psum(batch_count, axis_name)
        batch_mean = jax.lax.pmean(batch_mean, axis_name)
        batch_sq = jax.lax.pmean(batch_sq, axis_name)
    batch_m2 = (batch_sq - batch_mean ** 2) * batch_count
    total = stats.count + batch_count
    delta = batch_mean - stats.mean
    new_mean = stats.mean + delta * batch_count / total
    new_m2 = (stats.m2 + batch_m2
              + delta ** 2 * stats.count * batch_count / total)
    return RewardNormState(count=total, mean=new_mean, m2=new_m2)


def reward_scale(stats: RewardNormState) -> jax.Array:
    """1/√(running variance + ε). Scale-only normalization — rewards are
    NOT centered (subtracting a baseline from per-step rewards changes
    the optimal policy; rescaling does not)."""
    var = stats.m2 / jnp.maximum(stats.count, 1.0)
    return jax.lax.rsqrt(var + 1e-8)


class NormTrainState(TrainState):
    """TrainState + streaming reward moments. Only built when
    ``reward_norm`` is on, so default checkpoints/pytrees are
    unchanged."""
    reward_stats: RewardNormState = None


def ppo_loss(apply_fn: PolicyApply, net_params, batch: Transition,
             advantages: jax.Array, returns: jax.Array, config: PPOConfig,
             clip_eps: jax.Array | float | None = None,
             ent_coef: jax.Array | float | None = None):
    """``clip_eps`` / ``ent_coef`` default to the (static) config values;
    pass traced scalars to make them per-member PBT-explorable
    (``parallel.population``) without recompilation."""
    clip_eps = config.clip_eps if clip_eps is None else clip_eps
    ent_coef = config.ent_coef if ent_coef is None else ent_coef
    counted = getattr(apply_fn, "counted", None)
    if counted is None:
        logits, value = apply_fn(net_params, batch.obs, batch.mask)
        counters = ()
    else:       # the same forward, the expert layers' counters read out
        (logits, value), counters = counted(net_params, batch.obs,
                                            batch.mask)
        counters = (counters,)
    log_prob = action_dist.log_prob(logits, batch.action)
    ratio = jnp.exp(log_prob - batch.log_prob)
    pg1 = ratio * advantages
    pg2 = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * advantages
    pg_loss = -jnp.mean(jnp.minimum(pg1, pg2))
    # clipped value loss (PPO2-style trust region on the critic)
    v_clipped = batch.value + jnp.clip(value - batch.value,
                                       -clip_eps, clip_eps)
    v_loss = 0.5 * jnp.mean(jnp.maximum((value - returns) ** 2,
                                        (v_clipped - returns) ** 2))
    entropy = jnp.mean(action_dist.entropy(logits))
    total = pg_loss + config.vf_coef * v_loss - ent_coef * entropy
    approx_kl = jnp.mean(batch.log_prob - log_prob)
    clip_frac = jnp.mean((jnp.abs(ratio - 1.0) > clip_eps)
                         .astype(jnp.float32))
    return total, (pg_loss, v_loss, entropy, approx_kl, clip_frac,
                   *counters)


def normalize_advantages(advantages: jax.Array,
                         axis_name: str | None = None) -> jax.Array:
    """Normalize over the full batch (global across the mesh axis so DP
    replicas agree on the statistics). Global variance must be
    E[x²] − (E[x])² over globally-reduced moments — a pmean of per-shard
    variances would drop the between-shard term."""
    adv_mean = jnp.mean(advantages)
    adv_sq = jnp.mean(advantages ** 2)
    if axis_name is not None:
        adv_mean = jax.lax.pmean(adv_mean, axis_name)
        adv_sq = jax.lax.pmean(adv_sq, axis_name)
    adv_var = adv_sq - adv_mean ** 2
    return (advantages - adv_mean) / jnp.sqrt(adv_var + 1e-8)


@scopes.scoped(scopes.ADVANTAGE)
def compute_advantages(apply_fn: PolicyApply, config: PPOConfig, state,
                       tr: Transition, last_value: jax.Array,
                       axis_name: str | None = None):
    """The fused advantage pipeline (HEPPO-style): streaming reward
    standardization → GAE or V-trace → global normalization → optional
    bf16 storage, all inside the caller's jitted/donated update dispatch
    so none of it runs as a separate fp32 pass.

    Returns ``(state, advantages, returns, rho_stats)`` where
    ``rho_stats`` is ``(mean, max)`` of the *unclipped* importance
    ratios under ``correction="vtrace"`` and ``None`` on the GAE path.
    With the default config this emits exactly the historical
    ``compute_gae`` + ``normalize_advantages`` ops — bit-identical to
    the pre-fusion path. ``state`` is any struct with ``.params``
    (TrainState or the population's MemberState); it is only replaced
    when ``reward_norm`` updates the Welford stats."""
    rewards = tr.reward
    if config.reward_norm:
        stats = update_reward_stats(state.reward_stats, rewards, axis_name)
        rewards = rewards * reward_scale(stats)
        state = state.replace(reward_stats=stats)
    rho_stats = None
    if config.correction == "vtrace":
        T, E = tr.reward.shape[:2]
        B = T * E
        flat = lambda x: x.reshape(B, *x.shape[2:])
        # One batched apply under the learner's current params. The
        # [T·E] logits (and the log-softmax behind log_prob) are bitwise
        # row-equal to the rollout's per-step [E] applies on the tested
        # backends, so on-policy data yields target_lp == tr.log_prob
        # exactly and ratios ≡ 1.0 exactly. The value HEAD does not share
        # that property (its [B,1] gemm reassociates with batch size), so
        # V-trace bootstraps the stored behavior values like GAE does —
        # the sample-factory/APPO convention, and the choice that keeps
        # the bound-0 path bit-identical.
        logits, _ = apply_fn(_params_of(state), flat(tr.obs),
                             flat(tr.mask))
        target_lp = action_dist.log_prob(
            logits, flat(tr.action)).reshape(T, E)
        rho = vtrace_ops.importance_ratios(tr.log_prob, target_lp)
        advantages, returns = vtrace_ops.compute_vtrace(
            rewards, tr.value, tr.done, last_value, rho,
            config.gamma, config.gae_lambda, config.rho_bar, config.c_bar)
        rho_stats = (jnp.mean(rho), jnp.max(rho))
    else:
        advantages, returns = compute_gae(rewards, tr.value, tr.done,
                                          last_value, config.gamma,
                                          config.gae_lambda)
    advantages = normalize_advantages(advantages, axis_name)
    if config.bf16_advantages:
        advantages = advantages.astype(jnp.bfloat16)
        returns = returns.astype(jnp.bfloat16)
    return state, advantages, returns, rho_stats


def make_ppo_grad_step(apply_fn: PolicyApply, config: PPOConfig,
                       apply_grads, clip_eps=None, ent_coef=None):
    """One clipped-surrogate minibatch update for the fused engine:
    ``(state, (mb, adv, ret)) -> (state, (loss, *aux))``. With
    ``config.bf16_update`` the loss/grad evaluation runs on bf16 casts of
    the params and batch; grads are cast back to each param's dtype so
    the optimizer (and its Adam moments) stays fp32."""

    def grad_step(state, mb_data):
        mb, adv, ret = mb_data
        params = _params_of(state)
        with jax.named_scope(scopes.LOSS_GRAD):
            if config.bf16_update:
                c = lambda t: update_engine.cast_floating(t, jnp.bfloat16)
                (loss, aux), grads = jax.value_and_grad(
                    ppo_loss, argnums=1, has_aux=True)(
                    apply_fn, c(params), c(mb), c(adv), c(ret),
                    config, clip_eps=clip_eps, ent_coef=ent_coef)
                grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, params)
                loss, aux = jax.tree.map(
                    lambda x: x.astype(jnp.float32), (loss, aux))
            else:
                (loss, aux), grads = jax.value_and_grad(
                    ppo_loss, argnums=1, has_aux=True)(
                    apply_fn, params, mb, adv, ret,
                    config, clip_eps=clip_eps, ent_coef=ent_coef)
        with jax.named_scope(scopes.APPLY):
            state = apply_grads(state, grads)
        return state, (loss, *aux)

    return grad_step


@scopes.scoped(scopes.UPDATE)
def run_ppo_epochs(apply_fn: PolicyApply, config: PPOConfig, state,
                   tr: Transition, advantages: jax.Array,
                   returns: jax.Array, key: jax.Array, apply_grads,
                   clip_eps=None, ent_coef=None, rho_stats=None):
    """The PPO update core shared by the single-run trainer and the PBT
    member step: flatten [T, E] → [B], then hand the batch to the fused
    minibatch-geometry engine (:mod:`algos.update`) at the config's
    ``n_epochs × n_minibatches × minibatch_size`` geometry.
    ``apply_grads(state, grads) -> state`` injects the optimizer strategy
    (TrainState vs the population's manual traced-lr update);
    ``clip_eps``/``ent_coef`` optionally override the config with traced
    values. Returns (state, metrics)."""
    B = config.n_steps * tr.reward.shape[1]
    flat = jax.tree.map(lambda x: x.reshape(B, *x.shape[2:]), tr)
    grad_step = make_ppo_grad_step(apply_fn, config, apply_grads,
                                   clip_eps=clip_eps, ent_coef=ent_coef)
    state, stats = update_engine.run_minibatch_epochs(
        grad_step, state, (flat, advantages.reshape(B), returns.reshape(B)),
        key, n_epochs=config.n_epochs, n_minibatches=config.n_minibatches,
        minibatch_size=config.minibatch_size)
    rho_mean, rho_max = (rho_stats if rho_stats is not None
                         else (jnp.asarray(1.0, jnp.float32),
                               jnp.asarray(1.0, jnp.float32)))
    metrics = PPOMetrics(
        total_loss=jnp.mean(stats[0]), pg_loss=jnp.mean(stats[1]),
        v_loss=jnp.mean(stats[2]), entropy=jnp.mean(stats[3]),
        approx_kl=jnp.mean(stats[4]), clip_frac=jnp.mean(stats[5]),
        mean_reward=jnp.mean(tr.reward), mean_value=jnp.mean(tr.value),
        rho_mean=rho_mean, rho_max=rho_max)
    if len(stats) > 6:
        c = stats[6]
        # each counter over the update's minibatches: the mean of the
        # assignments held, the sum of the dropped, the largest of the
        # others (a constant of the trace reads itself)
        metrics = MoEPPOMetrics(*metrics, **{
            f: COUNTER_REDUCTIONS.get(f, jnp.max)(c[f])
            for f in MOE_COUNTERS})
    return state, metrics


def _params_of(state):
    return state.params  # TrainState and population.MemberState both


def make_learn_step(apply_fn: PolicyApply, config: PPOConfig,
                    axis_name: str | None = None):
    """Build the learn half of the PPO iteration:
    (train_state, tr, last_value, key) -> (train_state', metrics).

    GAE + advantage normalization + the fused minibatch-epoch engine —
    everything downstream of the rollout. The fused
    :func:`make_train_step` composes this with :func:`rollout`, and the
    async engine (:mod:`~rlgpuschedule_tpu.async_engine`) jits it alone
    on the learner device group, so both paths run literally the same
    update code (the staleness-bound-0 bit-identity contract)."""

    def apply_grads(state: TrainState, grads):
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
        return state.apply_gradients(grads=grads)

    def learn_step(train_state: TrainState, tr: Transition,
                   last_value: jax.Array, key: jax.Array):
        train_state, advantages, returns, rho_stats = compute_advantages(
            apply_fn, config, train_state, tr, last_value, axis_name)
        return run_ppo_epochs(apply_fn, config, train_state, tr,
                              advantages, returns, key, apply_grads,
                              rho_stats=rho_stats)

    return learn_step


def make_train_step(apply_fn: PolicyApply, env_params: EnvParams,
                    config: PPOConfig, axis_name: str | None = None):
    """Build the jittable PPO iteration:
    (train_state, carry, traces, key) -> (train_state', carry', metrics).

    ``axis_name``: mesh axis for data-parallel gradient pmean (None =
    single-device)."""
    learn_step = make_learn_step(apply_fn, config, axis_name)

    def train_step(train_state: TrainState, carry: RolloutCarry, traces,
                   key: jax.Array, faults=None):
        carry, tr, last_value = rollout(apply_fn, train_state.params,
                                        env_params, traces, carry,
                                        config.n_steps, faults)
        train_state, metrics = learn_step(train_state, tr, last_value, key)
        return train_state, carry, metrics

    return train_step


def make_train_state(net, key: jax.Array, example_obs: jax.Array,
                     example_mask: jax.Array,
                     tx: optax.GradientTransformation,
                     extra_apply_args: tuple = (),
                     reward_norm: bool = False) -> TrainState:
    """Initialize params + optimizer into a flax TrainState.
    ``extra_apply_args`` go between obs and mask (the GNN's adjacency).
    ``reward_norm`` swaps in :class:`NormTrainState` carrying the
    streaming reward moments (different pytree — checkpoints are not
    interchangeable with the default state, by design)."""
    # init under ONE program, not an eager one per operation of the
    # policy's forward pass (which init runs and the compiler drops here:
    # only the leaves come out): the token policy's init was 66 s of a
    # cold start on the chip, eagerly (PERF.md section 6, PR 30). The
    # leaves are bit-equal.
    params = jax.jit(net.init)(key, example_obs, *extra_apply_args,
                               example_mask)
    if reward_norm:
        state = NormTrainState.create(apply_fn=net.apply, params=params,
                                      tx=tx,
                                      reward_stats=init_reward_stats())
    else:
        state = TrainState.create(apply_fn=net.apply, params=params, tx=tx)
    # flax starts ``step`` as the Python int 0 and the first train step
    # hands back an int32 array: on jax 0.9.0 that is a different jit
    # signature, so iteration 1 re-traced the whole train step (a
    # post-warmup recompile alarm, and seconds of host time at config 2)
    return state.replace(step=jnp.zeros((), jnp.int32))
