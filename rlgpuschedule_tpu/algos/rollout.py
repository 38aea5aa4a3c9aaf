"""Fused policy+env rollout collection (L4).

Capability parity: SURVEY.md §2 "Rollout buffer" / "Multi-actor runner" and
§3.1 HOT LOOP #1. The reference alternates host-side env stepping with
device policy inference per step; here the policy forward, action sampling,
and the vmapped env step fuse into ONE ``lax.scan`` that never leaves the
device — the Podracer/Anakin pattern (SURVEY.md §7 step 5 `[P: Podracer]`),
which removes the per-step host↔device sync that bottlenecks the reference
(SURVEY.md §7 hard part (d)).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax

from ..env import env as env_lib
from ..env.env import EnvParams, EnvState
from ..obs import scopes
from . import action_dist

# (net_params, obs, mask) -> (masked_logits, value[E]). obs/mask/logits may
# each be a single array or a pytree (multi-head policies — see
# algos.action_dist); the rollout is agnostic.
PolicyApply = Callable[[Any, Any, Any], tuple[Any, jax.Array]]


class Transition(NamedTuple):
    """One scan slice of the rollout buffer; stacked to [T, E, ...].
    ``obs``/``action``/``mask`` are arrays for single-head policies and
    pytrees for multi-head (hierarchical) ones; ``log_prob`` is always the
    joint [E] log-prob under the BEHAVIOR params the rollout ran with —
    PPO's surrogate ratio and V-trace's importance ratios
    (``algos.vtrace``) both divide the target policy by exactly this
    stored quantity, so it must never be recomputed post-hoc."""
    obs: Any
    action: Any
    log_prob: jax.Array
    value: jax.Array
    reward: jax.Array
    done: jax.Array
    mask: Any
    env_steps_dt: jax.Array  # simulated seconds advanced (metrics)


class RolloutCarry(NamedTuple):
    env_state: EnvState
    obs: jax.Array
    mask: jax.Array
    key: jax.Array


def init_carry(params: EnvParams, traces, key: jax.Array,
               faults=None) -> RolloutCarry:
    env_state, ts = env_lib.vec_reset(params, traces, faults)
    return RolloutCarry(env_state, ts.obs, ts.action_mask, key)


def validate_rollout_geometry(n_steps: int, n_envs: int,
                              n_devices: int = 1) -> None:
    """Validate the rollout phase's batch geometry on its own terms —
    decoupled from the update phase's minibatch constraints
    (:func:`..algos.update.validate_update_geometry`), because the async
    actor–learner engine runs the two phases on *different* device
    groups: the env batch must tile the actor group; whether the
    flattened [T·E] batch tiles the update's minibatch geometry is the
    learner group's problem."""
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    if n_envs < 1:
        raise ValueError(f"n_envs must be >= 1, got {n_envs}")
    if n_devices > 1 and n_envs % n_devices:
        raise ValueError(
            f"n_envs={n_envs} must be divisible by the rollout device "
            f"group size ({n_devices}) to shard the env batch evenly")


def make_rollout_step(apply_fn: PolicyApply, env_params: EnvParams,
                      n_steps: int):
    """Build the jittable rollout half of an iteration:
    (net_params, carry, traces, faults) -> (carry', tr, last_value).

    The fused ``make_train_step`` inlines :func:`rollout` directly; the
    async engine jits this factory's product alone on the actor device
    group, so the collection program is byte-for-byte the same scan in
    both paths."""

    def rollout_step(net_params, carry: RolloutCarry, traces, faults=None):
        return rollout(apply_fn, net_params, env_params, traces, carry,
                       n_steps, faults)

    return rollout_step


@scopes.scoped(scopes.ROLLOUT)
def rollout(apply_fn: PolicyApply, net_params, env_params: EnvParams,
            traces, carry: RolloutCarry, n_steps: int, faults=None,
            ) -> tuple[RolloutCarry, Transition, jax.Array]:
    """Collect ``n_steps`` transitions from the vectorized envs in one scan.
    Returns (carry', transitions [T,E,...], last_value [E]).

    ``faults``: batched per-env FaultSchedule threaded next to the traces
    (auto-reset restarts an episode under the SAME schedule); None =
    healthy cluster, the bit-identical pre-chaos program."""
    # the auto-reset bundle depends only on the traces (and the fault
    # schedules): build it once here (a scan constant) instead of
    # re-running a full reset every step
    fresh = env_lib.vec_reset(env_params, traces, faults)

    def step(c: RolloutCarry, _):
        with jax.named_scope(scopes.POLICY_FORWARD):
            logits, value = apply_fn(net_params, c.obs, c.mask)
            key, sub = jax.random.split(c.key)
            action, log_prob = action_dist.sample(sub, logits)
        with jax.named_scope(scopes.ENV_STEP):
            env_state, ts = env_lib.vec_step(env_params, c.env_state,
                                             traces, action, fresh, faults)
        t = Transition(obs=c.obs, action=action, log_prob=log_prob,
                       value=value, reward=ts.reward, done=ts.done,
                       mask=c.mask, env_steps_dt=ts.info.dt)
        return RolloutCarry(env_state, ts.obs, ts.action_mask, key), t

    carry, transitions = jax.lax.scan(step, carry, None, length=n_steps)
    # Pin the trajectory stack's env axis to the mesh's data axis before it
    # feeds GAE + the minibatch update: without the constraint GSPMD is free
    # to replicate the [T, E, ...] buffer on every device, which is exactly
    # the memory ceiling the partition-rule mesh exists to lift. Identity
    # when no mesh is bound (single-device / legacy dp paths).
    from ..parallel.sharding import DATA_AXIS, constrain_tree
    transitions = constrain_tree(transitions, None, DATA_AXIS)
    with jax.named_scope(scopes.POLICY_FORWARD):
        _, last_value = apply_fn(net_params, carry.obs, carry.mask)
    return carry, transitions, last_value
