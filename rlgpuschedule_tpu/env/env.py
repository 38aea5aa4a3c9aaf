"""Gym-style pure-functional cluster environment (L2).

Capability parity: SURVEY.md §2 "Gym-style env wrapper" / "Vectorized env":
``reset/step`` over the jitted simulator, an episode = one trace-window
replay, action masking for infeasible placements, and vectorization via
``jax.vmap`` over a batched Trace pytree (the reference's subprocess/serial
VecEnv becomes a vmap axis — SURVEY.md §2 "rebuild: vmap").

Everything is pure: ``step`` is (params, state, action) → (state', timestep),
so the whole interaction loop fuses into one ``lax.scan`` with the policy
(Anakin pattern, SURVEY.md §7 step 5).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Literal, NamedTuple

import jax
import jax.numpy as jnp

from ..obs import scopes
from ..sim import core
from ..sim.core import SimParams, SimState, Trace, StepInfo
from ..sim.faults import FaultRegime, FaultSchedule
from ..traces.records import ArrayTrace
from . import obs as obs_lib
from . import rewards as reward_lib


@dataclasses.dataclass(frozen=True)
class EnvParams:
    """Static env configuration (hashable; closed over by jit)."""
    sim: SimParams
    obs_kind: Literal["flat", "grid", "graph", "tokens"] = "flat"
    reward_kind: Literal["jct", "fair"] = "jct"
    n_tenants: int = 1
    time_scale: float = 600.0     # normalizes times in observations
    reward_scale: float = 1000.0  # divides reward magnitudes
    place_bonus: float = 0.0      # potential-based shaping (rewards.py)
    preempt_cost: float = 0.0     # anti-stall preemption charge (rewards.py)
    horizon: int = 512            # max decision steps per episode
    # cluster fault process (sim.faults): the static DISTRIBUTION the
    # env's fault schedules are drawn from (the sampled FaultSchedule is
    # per-env data threaded next to the trace). None = permanently
    # healthy — the pre-chaos program, bit-identical.
    fault_process: FaultRegime | None = None
    # append a per-node health channel (1/slowdown while up, 0 while
    # drained) to the observation so the policy can LEARN to route
    # around drains. Flat observations only (the grid/graph encoders pin
    # their channel/feature counts); checked in __post_init__.
    fault_obs: bool = False
    # domain randomization (domains.schedule): the static DISTRIBUTION
    # cluster geometry / hardware speed / arrival knobs are drawn from
    # (the sampled DomainSchedule is per-env data riding the faults
    # slot). None = the fixed-cluster program, bit-identical.
    domain_process: Any = None
    # append a per-node geometry channel (capacity / gpus_per_node) so
    # the policy can tell a shrunken node from a busy one. Flat only,
    # like fault_obs; checked in __post_init__.
    domain_obs: bool = False

    def __post_init__(self):
        if self.fault_obs and self.obs_kind != "flat":
            raise ValueError(
                f"fault_obs appends per-node health to the FLAT "
                f"observation; obs_kind={self.obs_kind!r} pins its "
                f"feature layout (train grid/graph fault policies "
                f"without health visibility, or use flat)")
        if self.domain_obs and self.obs_kind != "flat":
            raise ValueError(
                f"domain_obs appends per-node geometry to the FLAT "
                f"observation; obs_kind={self.obs_kind!r} pins its "
                f"feature layout")

    @property
    def n_actions(self) -> int:
        return self.sim.n_actions

    def obs_shape(self) -> tuple[int, ...]:
        s, k, r = self.sim, self.sim.queue_len, self.sim.preempt_len
        if self.obs_kind == "flat":
            n_health = s.n_nodes if self.fault_obs else 0
            n_geom = s.n_nodes if self.domain_obs else 0
            return (s.n_nodes + 4 * k + 4 * r + 2 + n_health + n_geom,)
        if self.obs_kind == "grid":
            return (s.n_nodes + k + r, s.gpus_per_node, 2)
        if self.obs_kind == "tokens":
            return (s.n_nodes + s.max_jobs, obs_lib.TOKEN_FEATURES)
        return (s.n_nodes + k + r, obs_lib.GRAPH_FEATURES)


class EnvState(NamedTuple):
    sim: SimState
    t: jax.Array  # i32 decision-step counter within the episode


class TimeStep(NamedTuple):
    obs: jax.Array
    reward: jax.Array
    done: jax.Array
    action_mask: jax.Array
    info: StepInfo


def build_obs(params: EnvParams, sim: SimState, trace: Trace,
              queue: jax.Array | None = None,
              run_queue: jax.Array | None = None,
              faults: FaultSchedule | None = None) -> jax.Array:
    if params.obs_kind == "tokens":     # node rows carry health, geometry
        return obs_lib.token_obs(params.sim, sim, trace, params.time_scale,
                                 queue, run_queue, faults)
    fn = {"flat": obs_lib.flat_obs, "grid": obs_lib.grid_obs,
          "graph": obs_lib.graph_obs}[params.obs_kind]
    obs = fn(params.sim, sim, trace, params.time_scale, queue, run_queue)
    if params.fault_obs:
        # health appended LAST so the fault-free feature prefix is laid
        # out identically to the pre-chaos observation; faults=None (a
        # fault-trained policy replayed on a clean cluster) reads as
        # every node healthy at full speed
        obs = jnp.concatenate(
            [obs, obs_lib.node_health(params.sim, sim, faults)])
    if params.domain_obs:
        # geometry after health, same append-only contract: the prefix
        # stays laid out identically to the fixed-cluster observation
        obs = jnp.concatenate(
            [obs, obs_lib.node_geometry(params.sim, faults)])
    return obs


@scopes.scoped(scopes.OBSERVE)
def _observe(params: EnvParams, sim: SimState, trace: Trace,
             faults: FaultSchedule | None = None,
             ) -> tuple[jax.Array, jax.Array]:
    """(obs, action_mask) for ``sim``, computing the pending (and, for
    preemptive configs, running) queue once and sharing them between the
    two (VERDICT r1 weak #2)."""
    queue = core.pending_queue(params.sim, sim)
    run_queue = (core.running_queue(params.sim, sim, trace)
                 if params.sim.preempt_len else None)
    return (build_obs(params, sim, trace, queue, run_queue, faults),
            core.action_mask(params.sim, sim, trace, queue, run_queue,
                             faults))


def reset(params: EnvParams, trace: Trace,
          faults: FaultSchedule | None = None) -> tuple[EnvState, TimeStep]:
    # the schedule seeds init_state too: a DomainSchedule's per-node
    # capacity IS the initial free vector (plain FaultSchedule/None keep
    # the static full cluster, bit-identical)
    sim = core.init_state(params.sim, trace, faults)
    state = EnvState(sim=sim, t=jnp.int32(0))
    obs, mask = _observe(params, sim, trace, faults)
    ts = TimeStep(
        obs=obs,
        reward=jnp.float32(0.0),
        done=jnp.bool_(False),
        action_mask=mask,
        info=StepInfo(placed=jnp.bool_(False), dt=jnp.float32(0.0),
                      in_system_before=core.in_system(sim),
                      done=jnp.bool_(False), preempted=jnp.bool_(False),
                      first_placed=jnp.bool_(False)),
    )
    return state, ts


def step(params: EnvParams, state: EnvState, trace: Trace,
         action: jax.Array,
         faults: FaultSchedule | None = None) -> tuple[EnvState, TimeStep]:
    sim_before = state.sim
    sim, info = core.rl_step(params.sim, sim_before, trace, action, faults)
    with jax.named_scope(scopes.REWARD):
        if params.reward_kind == "fair":
            reward = reward_lib.reward_fair(sim_before, trace, info,
                                            params.n_tenants,
                                            params.reward_scale)
        else:
            reward = reward_lib.reward_jct(info, params.reward_scale,
                                           params.place_bonus)
        # the anti-stall preemption charge is a property of the ACTION
        # SPACE (any preemptive config can generate zero-dt actions
        # forever — the pause-the-game exploit, rewards.preempt_charge),
        # not of one reward function, so it applies after whichever
        # reward branch ran
        if params.preempt_cost:
            reward = reward + reward_lib.preempt_charge(info,
                                                        params.preempt_cost)
    t = state.t + 1
    done = info.done | (t >= params.horizon)
    new_state = EnvState(sim=sim, t=t)
    obs, mask = _observe(params, sim, trace, faults)
    ts = TimeStep(obs=obs, reward=reward, done=done, action_mask=mask,
                  info=info)
    return new_state, ts


@scopes.scoped(scopes.AUTO_RESET)
def auto_reset(stepped_state, ts: TimeStep, fresh_state, fresh_ts: TimeStep,
               ) -> tuple[Any, TimeStep]:
    """Blend a stepped (state, timestep) with a fresh reset on episode end
    (obs/mask from the fresh episode, reward/done from the finished one) —
    the standard fused auto-reset so rollouts never leave the device.
    obs/mask may be pytrees (hierarchical env)."""
    pick = lambda a, b: jax.tree.map(
        lambda x, y: jnp.where(ts.done, x, y), a, b)
    new_state = pick(fresh_state, stepped_state)
    obs = pick(fresh_ts.obs, ts.obs)
    mask = pick(fresh_ts.action_mask, ts.action_mask)
    return new_state, ts._replace(obs=obs, action_mask=mask)


def auto_reset_step(params: EnvParams, state: EnvState, trace: Trace,
                    action: jax.Array, fresh=None,
                    faults: FaultSchedule | None = None,
                    ) -> tuple[EnvState, TimeStep]:
    """Step + fused auto-reset. The reset bundle depends only on the trace
    (and fault schedule), so callers stepping in a loop should compute
    ``fresh = reset(params, trace, faults)`` ONCE outside it and pass it
    here — recomputing a full reset (init + obs + mask) every step was
    round 1's single largest hot-loop redundancy (VERDICT r1 weak #2).
    A mid-episode fault episode auto-resets the same way: the fresh
    episode restarts at clock 0 under the SAME schedule (fault times are
    episode-relative, like submits)."""
    stepped, ts = step(params, state, trace, action, faults)
    fresh_state, fresh_ts = (reset(params, trace, faults)
                             if fresh is None else fresh)
    return auto_reset(stepped, ts, fresh_state, fresh_ts)


# ---- vectorization ----------------------------------------------------------

def stack_traces(traces: list[ArrayTrace],
                 params: EnvParams | SimParams | None = None) -> Trace:
    """Stack per-env trace windows into a batched Trace (leading axis E).
    All windows must share max_jobs (pad at construction). Pass ``params``
    to validate gang sizes against cluster capacity (see
    ``sim.core.validate_trace``)."""
    sim_params = params.sim if isinstance(params, EnvParams) else params
    devs = [Trace.from_array_trace(t, sim_params) for t in traces]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *devs)


@functools.singledispatch
def vec_reset(params, traces: Trace, faults=None) -> tuple[Any, TimeStep]:
    """Vectorized reset, dispatched on the params type (EnvParams here;
    env.hier registers HierParams) so the rollout/algorithms layer is
    env-agnostic. ``faults``: batched per-env FaultSchedule (leading axis
    E, ``sim.faults.stack_fault_schedules``), or None = healthy."""
    raise TypeError(f"no env registered for params type {type(params)}")


@functools.singledispatch
def vec_step(params, state, traces: Trace, actions,
             fresh=None, faults=None) -> tuple[Any, TimeStep]:
    """Vectorized auto-reset step, dispatched on the params type. Pass
    ``fresh = vec_reset(params, traces, faults)`` when stepping in a loop
    so the trace-constant reset bundle is built once, not per step."""
    raise TypeError(f"no env registered for params type {type(params)}")


@vec_reset.register
def _(params: EnvParams, traces: Trace,
      faults=None) -> tuple[EnvState, TimeStep]:
    if faults is None:
        return jax.vmap(lambda tr: reset(params, tr))(traces)
    return jax.vmap(lambda tr, f: reset(params, tr, f))(traces, faults)


@vec_step.register
def _(params: EnvParams, state: EnvState, traces: Trace,
      actions: jax.Array, fresh=None,
      faults=None) -> tuple[EnvState, TimeStep]:
    if faults is None:
        if fresh is None:
            return jax.vmap(lambda s, tr, a: auto_reset_step(params, s, tr, a)
                            )(state, traces, actions)
        return jax.vmap(lambda s, tr, a, f: auto_reset_step(params, s, tr, a, f)
                        )(state, traces, actions, fresh)
    if fresh is None:
        return jax.vmap(
            lambda s, tr, a, fl: auto_reset_step(params, s, tr, a,
                                                 faults=fl)
        )(state, traces, actions, faults)
    return jax.vmap(
        lambda s, tr, a, f, fl: auto_reset_step(params, s, tr, a, f, fl)
    )(state, traces, actions, fresh, faults)
