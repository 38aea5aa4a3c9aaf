"""Hierarchical multi-pod environment (L2/L5) — config 5's workload.

Capability parity: SURVEY.md §2 "Hierarchical multi-agent" / §3.5 — a
scheduler-of-schedulers over ``n_pods`` simulated pods: a **top-level
router** assigns each arriving job to one pod; **per-pod placement agents**
(shared weights, one action per pod per step) schedule their own pod's
queue. The reference runs these as communicating agents across processes;
here the whole hierarchy is one pure-functional step over a pytree —
per-pod simulators are ONE stacked :class:`~..sim.core.SimState` with a
leading pod axis driven by ``vmap``, clocks held in lockstep by advancing
every pod to the same global next-event time.

Joint-action semantics per decision step (mirrors ``sim.core.rl_step``'s
branchless pattern):

1. the router action (``action["top"]``: pod index or no-op) routes the
   HEAD arrived-but-unassigned job into that pod's queue;
2. every pod's action (``action["pods"][p]``: queue-slot×placement or
   no-op) gang-places within its pod, all at the same virtual time;
3. iff nothing was routed or placed, time advances to the next global
   event (earliest trace arrival or pod completion); with no event left,
   forced progress (route head to freest pod, else pack pod queue heads)
   guarantees liveness, as in the flat env.

Jobs live in exactly one pod: pods are initialized with every job inert
(status DONE, the sim's "not mine" sentinel — completions/queues/events
all ignore it) and routing flips the job to PENDING in the chosen pod
only. Global metrics (JCT, done) therefore reduce over the pod axis:
``finish[j] = min_p pods.finish[p, j]``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import scopes
from ..sim import core
from ..sim.core import (DONE, INF, PACK, PENDING, RUNNING, SimParams,
                        SimState, Trace)
from ..traces.records import ArrayTrace
from . import env as env_lib
from . import obs as obs_lib
from . import rewards as reward_lib
from .env import TimeStep
from ..sim.core import StepInfo


@dataclasses.dataclass(frozen=True)
class HierParams:
    """Static hierarchical-env configuration. ``pod_sim`` describes ONE
    pod's geometry (nodes per pod × GPUs); the cluster is
    ``n_pods × pod_sim.n_nodes`` nodes."""
    n_pods: int
    pod_sim: SimParams
    time_scale: float = 600.0
    reward_scale: float = 10_000.0
    place_bonus: float = 0.0    # shaping per progress step (rewards.py)
    horizon: int = 512

    @property
    def n_top_actions(self) -> int:
        return self.n_pods + 1          # route-to-pod p | no-op

    @property
    def pod_capacity(self) -> int:
        return self.pod_sim.capacity

    # top-level observation: per-pod summaries + head-job features + globals
    POD_SUMMARY_FEATURES = 3
    HEAD_FEATURES = 4

    def top_obs_dim(self) -> int:
        return (self.n_pods * self.POD_SUMMARY_FEATURES
                + self.HEAD_FEATURES + 2)

    def obs_shape(self) -> dict:
        pod = self.pod_sim
        return {"top": (self.top_obs_dim(),),
                "pods": (self.n_pods, pod.n_nodes + 4 * pod.queue_len + 2)}


class HierState(NamedTuple):
    pods: SimState        # stacked [P, ...]
    assignment: jax.Array  # i32[J]; -1 = not yet routed
    t: jax.Array           # i32 decision-step counter


def validate_hier_trace(params: HierParams, tr: ArrayTrace,
                        clamp: bool = False) -> ArrayTrace:
    """A job demanding more GPUs than ONE POD holds can never be placed
    (gangs do not span pods); mirror sim.core.validate_trace at pod
    granularity."""
    return core.validate_trace(params.pod_sim, tr, clamp=clamp)


def pod_init(params: HierParams, trace: Trace) -> SimState:
    """One pod's initial state: every job inert (DONE) until routed in."""
    J, N = params.pod_sim.max_jobs, params.pod_sim.n_nodes
    return SimState(
        clock=jnp.float32(0.0),
        status=jnp.full((J,), DONE, jnp.int32),
        remaining=jnp.array(trace.duration, jnp.float32, copy=True),
        start=jnp.full((J,), INF, jnp.float32),
        finish=jnp.full((J,), INF, jnp.float32),
        alloc=jnp.zeros((J, N), jnp.int32),
        free=jnp.full((N,), params.pod_sim.gpus_per_node, jnp.int32),
    )


# ---- global queries ---------------------------------------------------------

def global_clock(state: HierState) -> jax.Array:
    return state.pods.clock[0]          # pods advance in lockstep


def finished_mask(state: HierState, trace: Trace) -> jax.Array:
    """bool[J]: job completed in whichever pod ran it."""
    return trace.valid & (jnp.min(state.pods.finish, axis=0) < INF)


def arrived_mask(state: HierState, trace: Trace,
                 clock: jax.Array | None = None) -> jax.Array:
    clock = global_clock(state) if clock is None else clock
    return trace.valid & (trace.submit <= clock)


def unassigned_mask(state: HierState, trace: Trace) -> jax.Array:
    return arrived_mask(state, trace) & (state.assignment < 0)


def head_unassigned(state: HierState, trace: Trace,
                    ) -> tuple[jax.Array, jax.Array]:
    """(row index of the earliest-submitted arrived-unassigned job, exists).
    Trace rows are submit-sorted, so argmax of the mask is the head."""
    mask = unassigned_mask(state, trace)
    return jnp.argmax(mask).astype(jnp.int32), jnp.any(mask)


def in_system(state: HierState, trace: Trace) -> jax.Array:
    """Arrived and not finished — counts jobs still waiting in the router,
    so leaving work unrouted is penalized exactly like leaving it queued."""
    return jnp.sum(arrived_mask(state, trace)
                   & ~finished_mask(state, trace))


def all_done(state: HierState, trace: Trace) -> jax.Array:
    return jnp.all(jnp.where(trace.valid, finished_mask(state, trace), True))


def jct_stats(state: HierState, trace: Trace) -> dict[str, jax.Array]:
    finish = jnp.min(state.pods.finish, axis=0)
    done = finished_mask(state, trace)
    jct = jnp.where(done, finish - trace.submit, 0.0)
    n = jnp.maximum(jnp.sum(done), 1)
    return {"avg_jct": jnp.sum(jct) / n,
            "max_jct": jnp.max(jnp.where(done, jct, -INF)),
            "n_done": jnp.sum(done)}


# ---- state transforms -------------------------------------------------------

def apply_route(params: HierParams, state: HierState, trace: Trace,
                pod: jax.Array, j: jax.Array, ok: jax.Array) -> HierState:
    """Route job row ``j`` into ``pod``'s queue (PENDING there); masked
    no-op unless ``ok``."""
    row = (jax.nn.one_hot(j, params.pod_sim.max_jobs, dtype=jnp.int32)
           * ok.astype(jnp.int32)).astype(bool)          # [J]
    pod_row = (jax.nn.one_hot(pod, params.n_pods, dtype=jnp.int32)
               * ok.astype(jnp.int32)).astype(bool)      # [P]
    hit = pod_row[:, None] & row[None, :]                # [P, J]
    return HierState(
        pods=state.pods._replace(
            status=jnp.where(hit, PENDING, state.pods.status)),
        assignment=jnp.where(row, pod.astype(jnp.int32), state.assignment),
        t=state.t)


def pod_place(params: HierParams, pod_state: SimState, trace: Trace,
              action: jax.Array) -> tuple[SimState, jax.Array]:
    """One pod's placement action (queue-slot × placement | no-op), the
    action-decode + try_place half of ``core.rl_step`` (no time advance —
    the hierarchy advances time globally)."""
    sp = params.pod_sim
    K, Pl = sp.queue_len, sp.n_placements
    queue = core.pending_queue(sp, pod_state)
    is_noop = action >= K * Pl
    k = jnp.clip(action // Pl, 0, K - 1)
    mode = action % Pl
    j = jnp.where(is_noop, -1, queue[k])
    return core.try_place(sp, pod_state, trace, j, mode)


def _vmap_pods(fn, pods: SimState, *args):
    return jax.vmap(lambda ps, *a: fn(ps, *a))(pods, *args)


def _pod_queues(params: HierParams, pods: SimState) -> jax.Array:
    """Every pod's pending queue, [P, K]."""
    return _vmap_pods(lambda ps: core.pending_queue(params.pod_sim, ps),
                      pods)


def next_event_time(state: HierState, trace: Trace) -> jax.Array:
    """Earliest future trace arrival or any-pod completion (+inf if none)."""
    clock = global_clock(state)
    t_arr = jnp.min(jnp.where(trace.valid & (trace.submit > clock),
                              trace.submit, INF))
    pod_next = _vmap_pods(lambda ps: core.next_event_time(ps, trace),
                          state.pods)
    return jnp.minimum(t_arr, jnp.min(pod_next))


def advance_all(state: HierState, trace: Trace, t: jax.Array) -> HierState:
    pods = _vmap_pods(lambda ps: core.advance_to(ps, trace, t), state.pods)
    return state._replace(pods=pods)


def forced_progress(params: HierParams, state: HierState, trace: Trace,
                    ) -> tuple[HierState, jax.Array]:
    """Liveness fallback when agents no-op with no event left: route the
    head unassigned job to the pod with the most free GPUs; with nothing to
    route, pack-place every pod's queue head (mirrors ``core.rl_step``'s
    forced placement; validate_hier_trace guarantees head demands fit an
    empty pod)."""
    j, exists = head_unassigned(state, trace)
    pod_free = jnp.sum(state.pods.free, axis=1)              # [P]
    best = jnp.argmax(pod_free).astype(jnp.int32)
    routed = apply_route(params, state, trace, best, j, exists)

    def head_place(ps: SimState) -> tuple[SimState, jax.Array]:
        queue = core.pending_queue(params.pod_sim, ps)
        return core.try_place(params.pod_sim, ps, trace, queue[0],
                              jnp.int32(PACK))

    placed_pods, placed_ok = _vmap_pods(head_place, state.pods)
    placed = state._replace(pods=placed_pods)
    pick = lambda a, b: jax.tree.map(
        lambda x, y: jnp.where(exists, x, y), a, b)
    return pick(routed, placed), exists | jnp.any(placed_ok)


# ---- observations / masks ---------------------------------------------------

def build_obs(params: HierParams, state: HierState, trace: Trace,
              queues: jax.Array | None = None) -> dict:
    sp = params.pod_sim
    clock = global_clock(state)
    # per-pod flat observations (shared-weight pod agents), [P, D_pod]
    if queues is None:
        queues = _pod_queues(params, state.pods)
    pod_obs = _vmap_pods(
        lambda ps, q: obs_lib.flat_obs(sp, ps, trace, params.time_scale, q),
        state.pods, queues)
    # router observation: per-pod summaries + head job + global load
    free_frac = jnp.sum(state.pods.free, axis=1) / sp.capacity       # [P]
    pending = jnp.sum(state.pods.status == PENDING, axis=1)          # [P]
    running = jnp.sum(state.pods.status == RUNNING, axis=1)          # [P]
    summary = jnp.stack([free_frac,
                         pending / sp.queue_len,
                         running / sp.capacity], axis=1)             # [P, 3]
    j, exists = head_unassigned(state, trace)
    e = exists.astype(jnp.float32)
    head = jnp.stack([
        e,
        trace.gpus[j].astype(jnp.float32) / sp.capacity * e,
        jnp.tanh(jnp.where(exists, clock - trace.submit[j], 0.0)
                 / params.time_scale),
        jnp.tanh(jnp.where(exists, trace.duration[j], 0.0)
                 / params.time_scale)])
    n_unassigned = jnp.sum(unassigned_mask(state, trace))
    globals_ = jnp.stack([n_unassigned / sp.max_jobs,
                          in_system(state, trace) / sp.max_jobs])
    top = jnp.concatenate([summary.reshape(-1), head, globals_]
                          ).astype(jnp.float32)
    return {"top": top, "pods": pod_obs}


def action_mask(params: HierParams, state: HierState, trace: Trace,
                queues: jax.Array | None = None) -> dict:
    j, exists = head_unassigned(state, trace)
    fits = trace.gpus[j] <= params.pod_capacity
    route_ok = jnp.broadcast_to(exists & fits, (params.n_pods,))
    top = jnp.concatenate([route_ok, jnp.ones((1,), bool)])
    if queues is None:
        queues = _pod_queues(params, state.pods)
    pod_masks = _vmap_pods(
        lambda ps, q: core.action_mask(params.pod_sim, ps, trace, q),
        state.pods, queues)
    return {"top": top, "pods": pod_masks}


@scopes.scoped(scopes.OBSERVE)
def _observe(params: HierParams, state: HierState, trace: Trace,
             ) -> tuple[dict, dict]:
    """(obs, mask), computing each pod's pending queue once and sharing it
    between the observation builder and the action mask."""
    queues = _pod_queues(params, state.pods)
    return (build_obs(params, state, trace, queues),
            action_mask(params, state, trace, queues))


# ---- reset / step -----------------------------------------------------------

def reset(params: HierParams, trace: Trace) -> tuple[HierState, TimeStep]:
    pods = jax.vmap(lambda _: pod_init(params, trace)
                    )(jnp.arange(params.n_pods))
    state = HierState(pods=pods,
                      assignment=jnp.full((params.pod_sim.max_jobs,), -1,
                                          jnp.int32),
                      t=jnp.int32(0))
    info = StepInfo(placed=jnp.bool_(False), dt=jnp.float32(0.0),
                    in_system_before=in_system(state, trace),
                    done=jnp.bool_(False), preempted=jnp.bool_(False),
                    first_placed=jnp.bool_(False))
    obs, mask = _observe(params, state, trace)
    ts = TimeStep(obs=obs, reward=jnp.float32(0.0), done=jnp.bool_(False),
                  action_mask=mask, info=info)
    return state, ts


def step(params: HierParams, state: HierState, trace: Trace,
         action: dict) -> tuple[HierState, TimeStep]:
    """One joint decision step; see module docstring for semantics.
    ``action = {"top": i32, "pods": i32[P]}``."""
    clock = global_clock(state)
    n_before = in_system(state, trace)

    # 1. route (top head)
    top = action["top"]
    j, exists = head_unassigned(state, trace)
    is_route = top < params.n_pods
    pod_choice = jnp.clip(top, 0, params.n_pods - 1).astype(jnp.int32)
    fits = trace.gpus[j] <= params.pod_capacity
    route_ok = is_route & exists & fits
    routed = apply_route(params, state, trace, pod_choice, j, route_ok)

    # 2. pod placements (on the post-routing pods, same virtual time)
    pods2, placed = _vmap_pods(
        lambda ps, a: pod_place(params, ps, trace, a),
        routed.pods, action["pods"])
    acted = routed._replace(pods=pods2)
    progress = route_ok | jnp.any(placed)
    # a failed route / failed placements leave the state bit-identical, so
    # the advance/forced candidates below start from `acted` in every case

    # 3. advance time — or forced progress when the event horizon is empty
    t_next = next_event_time(acted, trace)
    has_event = jnp.isfinite(t_next)
    advanced = advance_all(acted, trace, t_next)
    forced, forced_ok = forced_progress(params, acted, trace)

    def pick(a, b, c):  # progress ? a : (has_event ? b : c)
        return jnp.where(progress, a, jnp.where(has_event, b, c))

    new_state = jax.tree.map(pick, acted, advanced, forced)
    new_state = new_state._replace(t=state.t + 1)
    dt = jnp.where(progress | ~has_event, 0.0, t_next - clock)
    acted_ok = progress | (~progress & ~has_event & forced_ok)
    # no preemption in the hierarchy, so every progress step is "first"
    # (a job routes once and places once — the bonus stays bounded)
    info = StepInfo(placed=acted_ok, dt=dt, in_system_before=n_before,
                    done=all_done(new_state, trace),
                    preempted=jnp.bool_(False), first_placed=acted_ok)
    # same JCT integrand + placement shaping as the flat env (ADVICE r1:
    # place_bonus was silently dropped for hierarchical configs)
    with jax.named_scope(scopes.REWARD):
        reward = reward_lib.reward_jct(info, params.reward_scale,
                                       params.place_bonus)
    done = info.done | (new_state.t >= params.horizon)
    obs, mask = _observe(params, new_state, trace)
    ts = TimeStep(obs=obs, reward=reward, done=done, action_mask=mask,
                  info=info)
    return new_state, ts


def auto_reset_step(params: HierParams, state: HierState, trace: Trace,
                    action: dict, fresh=None) -> tuple[HierState, TimeStep]:
    """Step + fused auto-reset; pass a precomputed ``fresh = reset(params,
    trace)`` when stepping in a loop (see env.auto_reset_step)."""
    stepped, ts = step(params, state, trace, action)
    fresh_state, fresh_ts = (reset(params, trace) if fresh is None
                             else fresh)
    return env_lib.auto_reset(stepped, ts, fresh_state, fresh_ts)


# ---- vectorization (rollout integration via singledispatch) -----------------

@env_lib.vec_reset.register
def _(params: HierParams, traces: Trace,
      faults=None) -> tuple[HierState, TimeStep]:
    if faults is not None:
        raise ValueError("the hierarchical env has no fault-process "
                         "support; cluster chaos (sim.faults) is a flat-"
                         "config feature for now")
    return jax.vmap(lambda tr: reset(params, tr))(traces)


@env_lib.vec_step.register
def _(params: HierParams, state: HierState, traces: Trace,
      actions: dict, fresh=None, faults=None) -> tuple[HierState, TimeStep]:
    if faults is not None:
        raise ValueError("the hierarchical env has no fault-process "
                         "support; cluster chaos (sim.faults) is a flat-"
                         "config feature for now")
    if fresh is None:
        return jax.vmap(lambda s, tr, a: auto_reset_step(params, s, tr, a)
                        )(state, traces, actions)
    return jax.vmap(lambda s, tr, a, f: auto_reset_step(params, s, tr, a, f)
                    )(state, traces, actions, fresh)
