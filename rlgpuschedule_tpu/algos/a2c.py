"""A2C trainer (L4): synchronous advantage actor-critic.

Capability parity: SURVEY.md §2 "A2C trainer" / config 3 — the same fused
rollout and GAE machinery as PPO, and now the same fused minibatch-update
engine (:mod:`algos.update`): the classic single full-batch
policy-gradient update is the engine's degenerate ``1 × 1`` geometry (the
default, bit-identical to the hand-written full-batch update it
replaces), and minibatched/multi-epoch A2C variants are a config change
rather than a different code path. Multi-actor parallelism is an
env-batch/mesh axis, not processes: more vmapped envs per chip ×
data-parallel chips with pmean gradient sync (SURVEY.md §2 "Multi-actor
runner" rebuild form).
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
from . import ppo as ppo_norm  # shared RewardNormState/Welford helpers
from . import update as update_engine
from .rollout import PolicyApply, RolloutCarry, Transition, rollout


@dataclasses.dataclass(frozen=True)
class A2CConfig:
    n_steps: int = 16           # shorter rollouts, more frequent updates
    # update geometry (same contract as PPOConfig; validated by
    # algos.update.resolve_geometry). The 1 × 1 default IS classic A2C —
    # one full-batch update per iteration, bit-identical to the legacy
    # hand-written path; other geometries run the shared fused engine.
    n_epochs: int = 1
    n_minibatches: int = 1
    minibatch_size: int | None = None
    bf16_update: bool = False   # same contract as PPOConfig.bf16_update
    # fused advantage-pipeline passthrough (same contracts as PPOConfig;
    # A2C has NO correction field — V-trace's clipped-ratio targets are
    # a surrogate-objective correction, and the async engine refuses
    # a2c×vtrace loudly):
    reward_norm: bool = False
    bf16_advantages: bool = False
    gamma: float = 0.995
    gae_lambda: float = 1.0     # plain n-step advantage by default
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    lr: float = 7e-4
    max_grad_norm: float = 0.5


class A2CMetrics(NamedTuple):
    total_loss: jax.Array
    pg_loss: jax.Array
    v_loss: jax.Array
    entropy: jax.Array
    mean_reward: jax.Array
    mean_value: jax.Array


def make_optimizer(config: A2CConfig) -> optax.GradientTransformation:
    return optax.chain(optax.clip_by_global_norm(config.max_grad_norm),
                       optax.rmsprop(config.lr, decay=0.99, eps=1e-5))


def a2c_loss(apply_fn: PolicyApply, net_params, batch: Transition,
             advantages: jax.Array, returns: jax.Array, config: A2CConfig):
    logits, value = apply_fn(net_params, batch.obs, batch.mask)
    log_prob = action_dist.log_prob(logits, batch.action)
    pg_loss = -jnp.mean(log_prob * advantages)
    v_loss = 0.5 * jnp.mean((value - returns) ** 2)
    entropy = jnp.mean(action_dist.entropy(logits))
    total = pg_loss + config.vf_coef * v_loss - config.ent_coef * entropy
    return total, (pg_loss, v_loss, entropy)


def make_a2c_grad_step(apply_fn: PolicyApply, config: A2CConfig,
                       apply_grads):
    """One policy-gradient minibatch update for the fused engine:
    ``(state, (mb, adv, ret)) -> (state, (loss, pg, vl, ent))``. Same
    bf16-compute contract as :func:`ppo.make_ppo_grad_step`."""

    def grad_step(state: TrainState, mb_data):
        mb, adv, ret = mb_data
        with jax.named_scope(scopes.LOSS_GRAD):
            if config.bf16_update:
                c = lambda t: update_engine.cast_floating(t, jnp.bfloat16)
                (loss, aux), grads = jax.value_and_grad(
                    a2c_loss, argnums=1, has_aux=True)(
                    apply_fn, c(state.params), c(mb), c(adv), c(ret),
                    config)
                grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, state.params)
                loss, aux = jax.tree.map(
                    lambda x: x.astype(jnp.float32), (loss, aux))
            else:
                (loss, aux), grads = jax.value_and_grad(
                    a2c_loss, argnums=1, has_aux=True)(
                    apply_fn, state.params, mb, adv, ret, config)
        with jax.named_scope(scopes.APPLY):
            state = apply_grads(state, grads)
        return state, (loss, *aux)

    return grad_step


@scopes.scoped(scopes.UPDATE)
def run_a2c_update(apply_fn: PolicyApply, config: A2CConfig,
                   state: TrainState, tr: Transition,
                   advantages: jax.Array, returns: jax.Array,
                   key: jax.Array, apply_grads):
    """A2C's update through the fused minibatch-geometry engine: flatten
    [T, E] → [B] and run the config geometry (default 1 × 1 = classic
    full-batch A2C, bit-identical to the legacy direct update). Returns
    (state, metrics)."""
    B = config.n_steps * tr.reward.shape[1]
    flat = jax.tree.map(lambda x: x.reshape(B, *x.shape[2:]), tr)
    grad_step = make_a2c_grad_step(apply_fn, config, apply_grads)
    state, stats = update_engine.run_minibatch_epochs(
        grad_step, state, (flat, advantages.reshape(B), returns.reshape(B)),
        key, n_epochs=config.n_epochs, n_minibatches=config.n_minibatches,
        minibatch_size=config.minibatch_size)
    metrics = A2CMetrics(
        total_loss=jnp.mean(stats[0]), pg_loss=jnp.mean(stats[1]),
        v_loss=jnp.mean(stats[2]), entropy=jnp.mean(stats[3]),
        mean_reward=jnp.mean(tr.reward), mean_value=jnp.mean(tr.value))
    return state, metrics


def make_learn_step(apply_fn: PolicyApply, config: A2CConfig,
                    axis_name: str | None = None):
    """Build the learn half of the A2C iteration:
    (train_state, tr, last_value, key) -> (train_state', metrics).
    Same factoring contract as :func:`ppo.make_learn_step` — the fused
    train step and the async learner loop compose/jit this identical
    code (no advantage normalization in A2C, matching the legacy path)."""

    def apply_grads(state: TrainState, grads):
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
        return state.apply_gradients(grads=grads)

    def learn_step(train_state: TrainState, tr: Transition,
                   last_value: jax.Array, key: jax.Array):
        with jax.named_scope(scopes.ADVANTAGE):
            rewards = tr.reward
            if config.reward_norm:
                stats = ppo_norm.update_reward_stats(
                    train_state.reward_stats, rewards, axis_name)
                rewards = rewards * ppo_norm.reward_scale(stats)
                train_state = train_state.replace(reward_stats=stats)
            advantages, returns = compute_gae(rewards, tr.value, tr.done,
                                              last_value, config.gamma,
                                              config.gae_lambda)
            if config.bf16_advantages:
                advantages = advantages.astype(jnp.bfloat16)
                returns = returns.astype(jnp.bfloat16)
        return run_a2c_update(apply_fn, config, train_state, tr,
                              advantages, returns, key, apply_grads)

    return learn_step


def make_train_step(apply_fn: PolicyApply, env_params: EnvParams,
                    config: A2CConfig, axis_name: str | None = None):
    """(train_state, carry, traces, key) -> (train_state', carry', metrics).
    Action sampling draws from carry.key (advanced inside the rollout);
    ``key`` feeds the update engine's per-epoch minibatch shuffles and is
    untouched at the default 1 × 1 geometry (which consumes no
    randomness), preserving the legacy signature contract."""
    learn_step = make_learn_step(apply_fn, config, axis_name)

    def train_step(train_state: TrainState, carry: RolloutCarry, traces,
                   key: jax.Array, faults=None):
        carry, tr, last_value = rollout(apply_fn, train_state.params,
                                        env_params, traces, carry,
                                        config.n_steps, faults)
        train_state, metrics = learn_step(train_state, tr, last_value, key)
        return train_state, carry, metrics

    return train_step
