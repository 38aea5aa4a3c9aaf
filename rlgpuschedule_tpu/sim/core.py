"""Pure-functional, jit/vmap-able cluster simulator (L1) — the TPU hot path.

Capability parity: SURVEY.md §1 L1/L2 TPU restatement — "the discrete-event
GPU-cluster simulator becomes a jit-compiled, vmapped environment". This is
the central rebuild challenge (SURVEY.md §7 step 2 and "hard parts" (a)):

- State is a pytree of **fixed-shape** arrays (static shapes for XLA): a job
  table ``[J]`` with status masks, a per-job allocation matrix ``[J, N]``,
  a free-GPU vector ``[N]``, and a scalar clock.
- The reference's Python priority queue is replaced by **masked argmin over
  next-event times** — O(J) but fully vectorized, which is the idiomatic
  TPU trade (SURVEY.md §7 step 2).
- Every function here is a pure ``state -> state`` map built from
  ``jnp.where`` masks — no data-dependent Python control flow, so the whole
  step jits once and ``vmap``s over an env batch.

Semantics are specified by ``sim.oracle.OracleSim`` and enforced by the
property tests in ``tests/test_sim_core.py`` (bit-identical schedules on
integer-valued traces).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import scopes
from ..traces.records import ArrayTrace
from .faults import (FaultSchedule, effective_free, job_stretch, next_transition,
                     node_up, validate_fault_schedule)
from .oracle import NOT_ARRIVED, PENDING, RUNNING, DONE, PACK, SPREAD

INF = jnp.inf
_EPS = 1e-5  # completion tolerance in float32 virtual time


@dataclasses.dataclass(frozen=True)
class SimParams:
    """Static simulator configuration (hashable; closed over by jit)."""
    n_nodes: int
    gpus_per_node: int
    max_jobs: int          # J: rows in the (padded) job table
    queue_len: int = 16    # K: pending-queue slots visible to the agent
    n_placements: int = 1  # P: 1 = pack only; 2 = pack|spread factored action
    preempt_len: int = 0   # R: running-job slots the agent may preempt
    #                        (0 = non-preemptive action space, the default)

    @property
    def capacity(self) -> int:
        return self.n_nodes * self.gpus_per_node

    @property
    def n_actions(self) -> int:
        # [K*P placements][R preemptions][no-op] — see rl_step
        return self.queue_len * self.n_placements + self.preempt_len + 1


class Trace(NamedTuple):
    """Device-side trace (rows sorted by submit; padding has submit=+inf)."""
    submit: jax.Array    # f32[J]
    duration: jax.Array  # f32[J]
    gpus: jax.Array      # i32[J]
    tenant: jax.Array    # i32[J]
    valid: jax.Array     # bool[J]

    @staticmethod
    def from_array_trace(tr: ArrayTrace, params: "SimParams | None" = None,
                         ) -> "Trace":
        """Upload a host trace; pass ``params`` to validate gang sizes
        against cluster capacity (recommended — see :func:`validate_trace`)."""
        if params is not None:
            tr = validate_trace(params, tr)
        return Trace(jnp.asarray(tr.submit), jnp.asarray(tr.duration),
                     jnp.asarray(tr.gpus), jnp.asarray(tr.tenant),
                     jnp.asarray(tr.valid))


def validate_trace(params: SimParams, tr: ArrayTrace, clamp: bool = False,
                   faults: "FaultSchedule | None" = None) -> ArrayTrace:
    """Host-side guard mirroring OracleSim's constructor check: a valid job
    demanding more GPUs than the cluster has can never be placed, and inside
    the jitted sim that surfaces as a silently frozen episode (no exception
    can be raised from traced code). Raise here instead — or, with
    ``clamp=True``, cap demands at capacity (useful when replaying a big
    production trace on a small debug cluster).

    ``faults``: also validate a fault schedule against the cluster shape
    (drain windows sorted, durations positive, node count matching — see
    :func:`~.faults.validate_fault_schedule`), so the trace and its chaos
    script are vetted at the same ingest point."""
    if faults is not None:
        validate_fault_schedule(params.n_nodes, faults)
    over = tr.valid & (tr.gpus > params.capacity)
    if not over.any():
        return tr
    if not clamp:
        raise ValueError(
            f"{int(over.sum())} job(s) demand more than the cluster's "
            f"{params.capacity} GPUs (max demand {int(tr.gpus[tr.valid].max())}); "
            f"pass clamp=True to cap demands at capacity")
    gpus = np.minimum(tr.gpus, params.capacity)
    return dataclasses.replace(tr, gpus=gpus)


class SimState(NamedTuple):
    """Dynamic simulator state — a pytree of fixed-shape arrays."""
    clock: jax.Array      # f32 scalar
    status: jax.Array     # i32[J]
    remaining: jax.Array  # f32[J]
    start: jax.Array      # f32[J] (+inf until started)
    finish: jax.Array     # f32[J] (+inf until done)
    alloc: jax.Array      # i32[J, N]
    free: jax.Array       # i32[N]


class StepInfo(NamedTuple):
    """Per-step outcomes consumed by rewards/metrics."""
    placed: jax.Array           # bool — action placed a job this step
    dt: jax.Array               # f32 — simulated time advanced
    in_system_before: jax.Array # i32 — arrived-not-done count during [t, t+dt)
    done: jax.Array             # bool — all valid jobs DONE
    preempted: jax.Array        # bool — action preempted a running job
    first_placed: jax.Array     # bool — placed a job that had NEVER run
    #   (drives place_bonus: re-placing a preempted job earns nothing, so
    #    the shaping potential Φ = bonus·#{ever-started} still telescopes
    #    and a preempt→re-place cycle cannot farm reward)


# ---- lifecycle --------------------------------------------------------------

def init_state(params: SimParams, trace: Trace,
               faults: "FaultSchedule | None" = None) -> SimState:
    J, N = params.max_jobs, params.n_nodes
    # a DomainSchedule (domains.schedule) carries per-node GPU capacity as
    # data — geometry randomization without retracing; a plain
    # FaultSchedule (or None) has no capacity attribute and the free
    # vector stays the bit-identical static full cluster
    cap = getattr(faults, "capacity", None)
    free = (jnp.full((N,), params.gpus_per_node, jnp.int32) if cap is None
            # copy=True for the same donation-aliasing reason as remaining
            else jnp.array(cap, jnp.int32, copy=True))
    state = SimState(
        clock=jnp.float32(0.0),
        status=jnp.where(trace.valid, NOT_ARRIVED, DONE).astype(jnp.int32),
        # copy=True: .astype on an already-f32 array aliases the trace
        # buffer, and a donated sim state must never share buffers with the
        # (non-donated) trace — XLA rejects `f(donate(a), a)`
        remaining=jnp.array(trace.duration, jnp.float32, copy=True),
        start=jnp.full((J,), INF, jnp.float32),
        finish=jnp.full((J,), INF, jnp.float32),
        alloc=jnp.zeros((J, N), jnp.int32),
        free=free,
    )
    return _process_arrivals(state, trace)


def _process_arrivals(state: SimState, trace: Trace) -> SimState:
    arrived = (state.status == NOT_ARRIVED) & (trace.submit <= state.clock)
    return state._replace(
        status=jnp.where(arrived, PENDING, state.status))


# ---- events -----------------------------------------------------------------

def next_event_time(state: SimState, trace: Trace,
                    faults: "FaultSchedule | None" = None) -> jax.Array:
    """Earliest future arrival, completion, or fault transition; +inf if
    none (masked min — the vectorized replacement for the oracle's
    priority queue). With ``faults``, completions are slowdown-stretched
    (a gang finishes at ``clock + remaining × stretch``) and every drain
    start / node return is an event, so the decision loop stops AT each
    transition and :func:`advance_to` never integrates across one."""
    arrival = jnp.min(jnp.where(state.status == NOT_ARRIVED, trace.submit, INF))
    running = state.status == RUNNING
    if faults is None:
        eta = state.clock + state.remaining
    else:
        eta = state.clock + state.remaining * job_stretch(faults, state.alloc)
    completion = jnp.min(jnp.where(running, eta, INF))
    t = jnp.minimum(arrival, completion)
    if faults is not None:
        t = jnp.minimum(t, next_transition(faults, state.clock))
    return t


def advance_to(state: SimState, trace: Trace, t: jax.Array,
               faults: "FaultSchedule | None" = None) -> SimState:
    """Advance the clock to ``t`` (caller guarantees t ≤ next event; +inf is
    a no-op). Completions at ``t`` are processed before arrivals, matching
    ``OracleSim.advance_to``.

    With ``faults``: running work progresses at ``1/stretch`` (straggler
    nodes stretch remaining service; ``next_event_time`` uses the same
    stretched expression, so the completion-tolerance argument below is
    unchanged), and — after completions, before arrivals — every job still
    holding an allocation on a node that is down at ``t`` is killed back
    to PENDING with its attained service preserved (checkpointed
    preemption; the job is never lost). The caller contract "t ≤ next
    event" now also means "never advance across a fault transition":
    ``next_event_time`` includes transitions, so ``rl_step`` stops at the
    drain instant and the kill happens exactly there."""
    finite = jnp.isfinite(t)
    t = jnp.where(finite, t, state.clock)
    dt = t - state.clock
    running = state.status == RUNNING
    if faults is None:
        progressed = state.remaining - dt
        eta = state.clock + state.remaining
    else:
        stretch = job_stretch(faults, state.alloc)
        progressed = state.remaining - dt / stretch
        eta = state.clock + state.remaining * stretch
    remaining = jnp.where(running, jnp.maximum(progressed, 0.0),
                          state.remaining)
    # Completion test on absolute completion time with an ulp-scaled
    # tolerance: at large clocks the f32 spacing of ``clock + remaining``
    # exceeds any absolute epsilon, so ``remaining - dt`` can round to a
    # small positive value while next_event_time rounds to the current
    # clock — a dt=0 deadlock. A few ulps of ``t`` covers the worst-case
    # rounding of the sum without opening an early-completion window wider
    # than f32 time resolution itself (1e-5·|t| would complete jobs seconds
    # early on Philly-scale clocks).
    tol = _EPS + 4.0 * jnp.spacing(t)
    completed = running & (eta <= t + tol)
    released = jnp.sum(state.alloc * completed[:, None].astype(jnp.int32), axis=0)
    state = SimState(
        clock=t,
        status=jnp.where(completed, DONE, state.status),
        remaining=jnp.where(completed, 0.0, remaining),
        start=state.start,
        finish=jnp.where(completed, t, state.finish),
        alloc=jnp.where(completed[:, None], 0, state.alloc),
        free=state.free + released,
    )
    if faults is not None:
        state = _kill_drained(state, faults)
    return _process_arrivals(state, trace)


def _kill_drained(state: SimState, faults: FaultSchedule) -> SimState:
    """RUNNING → PENDING for every job holding an allocation on a node
    that is down at ``state.clock``; GPUs return to ``free`` so the
    per-node conservation invariant (free + allocated == capacity) holds
    at every instant. Idempotent and branch-free: a pure mask over
    (alloc, node_up) — re-applying it at a later step while the node is
    still down is a no-op because killed jobs hold no allocation."""
    up = node_up(faults, state.clock)
    killed = (state.status == RUNNING) & jnp.any(
        (state.alloc > 0) & ~up[None, :], axis=1)
    released = jnp.sum(state.alloc * killed[:, None].astype(jnp.int32),
                       axis=0)
    return state._replace(
        status=jnp.where(killed, PENDING, state.status),
        alloc=jnp.where(killed[:, None], 0, state.alloc),
        free=state.free + released,
    )


# ---- placement (matches oracle.pack_placement / spread_placement) ----------

def pack_placement(free: jax.Array, demand: jax.Array,
                   ) -> tuple[jax.Array, jax.Array]:
    """Fill freest nodes first (ties → lowest node id). Returns (alloc[N],
    feasible). jnp.argsort is stable, so argsort(-free) reproduces the
    oracle's (free desc, id asc) order."""
    feasible = demand <= jnp.sum(free)
    order = jnp.argsort(-free)
    sorted_free = free[order]
    before = jnp.cumsum(sorted_free) - sorted_free
    take = jnp.clip(demand - before, 0, sorted_free)
    alloc = jnp.zeros_like(free).at[order].set(take)
    return jnp.where(feasible, alloc, 0), feasible


def spread_placement(free: jax.Array, demand: jax.Array, gpus_per_node: int,
                     ) -> tuple[jax.Array, jax.Array]:
    """Water-filling: smallest level t with Σ min(free, t) ≥ demand;
    excess trimmed from the highest node ids allocated exactly t."""
    feasible = demand <= jnp.sum(free)
    levels = jnp.arange(gpus_per_node + 1)                      # [G+1]
    supply = jnp.sum(jnp.minimum(free[None, :], levels[:, None]), axis=1)
    t = jnp.argmax(supply >= demand)                            # first true
    alloc = jnp.minimum(free, t)
    excess = jnp.sum(alloc) - demand
    at_t = alloc == t
    # rank 1.. from the highest node id among nodes at level t
    rank_from_top = jnp.cumsum(at_t[::-1].astype(jnp.int32))[::-1]
    trim = at_t & (rank_from_top <= excess)
    alloc = jnp.where(trim, alloc - 1, alloc)
    return jnp.where(feasible, alloc, 0), feasible


def placement(free: jax.Array, demand: jax.Array, mode: jax.Array,
              gpus_per_node: int, n_placements: int = 2,
              ) -> tuple[jax.Array, jax.Array]:
    """Traced-mode dispatch between pack (0) and spread (1). When the action
    space has a single placement (``n_placements == 1``, a static Python
    int), the spread branch is dropped at trace time — no dead water-filling
    compute in the jitted hot path."""
    pa, pf = pack_placement(free, demand)
    if n_placements == 1:
        return pa, pf
    sa, sf = spread_placement(free, demand, gpus_per_node)
    spread = mode == SPREAD
    return jnp.where(spread, sa, pa), jnp.where(spread, sf, pf)


# ---- scheduling actions -----------------------------------------------------

def try_place(params: SimParams, state: SimState, trace: Trace,
              j: jax.Array, mode: jax.Array,
              faults: "FaultSchedule | None" = None,
              ) -> tuple[SimState, jax.Array]:
    """Gang-place job row ``j`` (traced index; -1 = invalid). Returns
    (state', success). All-or-nothing: infeasible → state unchanged.
    With ``faults``, placement sees drained nodes as zero free capacity
    (:func:`~.faults.effective_free`), so a gang can never land on a
    down node."""
    jc = jnp.clip(j, 0, params.max_jobs - 1)
    pending = (j >= 0) & (state.status[jc] == PENDING)
    demand = trace.gpus[jc]
    free = effective_free(faults, state.free, state.clock)
    alloc, feasible = placement(free, demand, mode, params.gpus_per_node,
                                params.n_placements)
    ok = pending & feasible
    allocd = jnp.where(ok, alloc, 0)
    row = jax.nn.one_hot(jc, params.max_jobs, dtype=jnp.int32) * ok.astype(jnp.int32)
    return SimState(
        clock=state.clock,
        status=jnp.where(row.astype(bool), RUNNING, state.status),
        remaining=state.remaining,
        start=jnp.where(row.astype(bool),
                        jnp.minimum(state.start, state.clock), state.start),
        finish=state.finish,
        alloc=state.alloc + row[:, None] * allocd[None, :],
        free=state.free - allocd,
    ), ok


def preempt(state: SimState, j: jax.Array, max_jobs: int
            ) -> tuple[SimState, jax.Array]:
    """RUNNING → PENDING for job row ``j``; attained service preserved."""
    jc = jnp.clip(j, 0, max_jobs - 1)
    ok = (j >= 0) & (state.status[jc] == RUNNING)
    row = (jax.nn.one_hot(jc, max_jobs, dtype=jnp.int32) * ok.astype(jnp.int32)
           ).astype(bool)
    released = jnp.sum(state.alloc * row[:, None].astype(jnp.int32), axis=0)
    return state._replace(
        status=jnp.where(row, PENDING, state.status),
        alloc=jnp.where(row[:, None], 0, state.alloc),
        free=state.free + released,
    ), ok


# ---- queue & queries --------------------------------------------------------

def pending_queue(params: SimParams, state: SimState) -> jax.Array:
    """Row indices of the first K pending jobs, -1 padded. Trace rows are
    submit-sorted at construction, so row order IS the oracle's
    (submit asc, id asc) queue order.

    Selected densely: slot k takes the pending job whose rank among the
    pending jobs is k, as a ``[K, J]`` compare and a masked max. At most
    one job has a given rank, so the max IS that job's row; a slot no job
    ranks at keeps -1, and a job of rank >= K matches no slot. No scatter:
    the chip writes scattered int32 elements at ~10 ns each (1.1 ms a call
    at 320 envs x 129 slots), where this compare-select-reduce fuses into
    one pass that also keeps its ``named_scope`` (PERF.md section 6,
    PR 34)."""
    K = params.queue_len
    pending = state.status == PENDING
    rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
    rows = jnp.arange(params.max_jobs, dtype=jnp.int32)
    slots = jnp.arange(K, dtype=jnp.int32)
    sel = pending[None, :] & (rank[None, :] == slots[:, None])    # [K,J]
    return jnp.max(jnp.where(sel, rows[None, :], -1), axis=1)


def queue_rows(field: jax.Array, queue: jax.Array) -> jax.Array:
    """``field[J]`` at the rows a queue view ``[K]`` names; an empty slot
    (-1) reads 0, so callers mask by ``queue >= 0`` as they always did.
    Dense like :func:`pending_queue` and for the same reason (an element
    gather costs the chip ~10 ns an element): a ``[K, J]`` compare of each
    slot's row against the job index and a masked sum of at most one term,
    so a float comes through exactly. ``where``, not a product: a padding
    row's ``submit`` is +inf."""
    rows = jnp.arange(field.shape[0], dtype=queue.dtype)
    sel = queue[:, None] == rows[None, :]                         # [K,J]
    return jnp.sum(jnp.where(sel, field[None, :], 0), axis=1)


def running_queue(params: SimParams, state: SimState, trace: Trace,
                  ) -> jax.Array:
    """Row indices of the R running jobs with the MOST attained GPU-service
    (ties → lowest row id), -1 padded — the slots the preemptive action
    space indexes into. Most-served-first is the Tiresias demotion order:
    preempting slot 0 evicts the long-runner to make room for short work
    (attained service is preserved, so nothing is lost)."""
    R = params.preempt_len
    running = state.status == RUNNING
    key = jnp.where(running, attained_service(state, trace), -INF)
    order = jnp.argsort(-key)                  # stable: ties → row asc
    rows = order[:R].astype(jnp.int32)
    # NOTE: the sort key is f32 (device state) while OracleSim.running_queue
    # sorts in f64; the bit-identical-equivalence contract therefore holds
    # on integer-valued traces (where f32 time is exact — the property-test
    # regime, tests/test_sim_core.py), not on arbitrary float traces where
    # two attained-service values may tie in f32 but differ in f64.
    return jnp.where(running[rows], rows, -1)


def in_system(state: SimState) -> jax.Array:
    return jnp.sum((state.status == PENDING) | (state.status == RUNNING))


def all_done(state: SimState, trace: Trace) -> jax.Array:
    return jnp.all(jnp.where(trace.valid, state.status == DONE, True))


def attained_service(state: SimState, trace: Trace) -> jax.Array:
    """Per-job attained GPU-seconds (Tiresias priority key)."""
    executed = trace.duration - state.remaining
    return executed * trace.gpus.astype(jnp.float32)


def action_mask(params: SimParams, state: SimState, trace: Trace,
                queue: jax.Array | None = None,
                run_queue: jax.Array | None = None,
                faults: "FaultSchedule | None" = None) -> jax.Array:
    """bool[n_actions]: queue-slot actions valid iff the slot holds a pending
    job whose gang fits in the free GPUs (pack and spread share feasibility:
    jobs may span nodes); preempt slots valid iff they hold a running job;
    no-op is always valid. Pass precomputed ``pending_queue`` /
    ``running_queue`` to share them with the observation builder. With
    ``faults``, feasibility counts only up nodes' free GPUs — the mask and
    :func:`try_place` always agree on what fits. The slots' demands are
    read through :func:`queue_rows` (dense; no gather of K elements)."""
    if queue is None:
        queue = pending_queue(params, state)                   # [K]
    demand = queue_rows(trace.gpus, queue)
    free = effective_free(faults, state.free, state.clock)
    ok = (queue >= 0) & (demand <= jnp.sum(free))              # [K]
    slots = jnp.repeat(ok, params.n_placements)                # [K*P]
    parts = [slots]
    if params.preempt_len:
        if run_queue is None:
            run_queue = running_queue(params, state, trace)    # [R]
        parts.append(run_queue >= 0)
    parts.append(jnp.ones((1,), bool))
    return jnp.concatenate(parts)


# ---- the RL decision-point step --------------------------------------------

@scopes.scoped(scopes.SIM_STEP)
def rl_step(params: SimParams, state: SimState, trace: Trace,
            action: jax.Array, faults: "FaultSchedule | None" = None,
            ) -> tuple[SimState, StepInfo]:
    """One decision-point step; exact jit/vmap analogue of
    ``OracleSim.rl_step`` (see its docstring for the semantics). Branchless:
    every outcome (placement vs preemption vs time-advance) is computed and
    masked — the idiomatic XLA trade against host control flow.

    Action layout: ``[K*P placements][R preemptions][no-op]``. Placements
    and preemptions cost no simulated time (the agent acts again at the
    same instant); preemption targets ``running_queue`` slots. The R block
    exists only when ``params.preempt_len > 0``, so non-preemptive configs
    trace the exact same XLA program as before.

    ``faults`` (a :class:`~.faults.FaultSchedule`, or None = permanently
    healthy) threads the cluster fault process through placement
    feasibility, event selection, progress stretching, and drain kills —
    it is DATA: stepping under a different schedule of the same shape
    reuses the compiled program (CompileCounter-asserted)."""
    K, P, R = params.queue_len, params.n_placements, params.preempt_len
    n_place = K * P
    with jax.named_scope(scopes.SIM_QUEUE):
        queue = pending_queue(params, state)
    is_place = action < n_place
    k = jnp.clip(action // P, 0, K - 1)
    mode = action % P
    j = jnp.where(is_place, queue[k], -1)

    with jax.named_scope(scopes.SIM_PLACE):
        placed_state, placed = try_place(params, state, trace, j, mode,
                                         faults)

    if R:
        with jax.named_scope(scopes.SIM_QUEUE):
            run_q = running_queue(params, state, trace)
        is_pre = ~is_place & (action < n_place + R)
        r = jnp.clip(action - n_place, 0, R - 1)
        with jax.named_scope(scopes.SIM_PLACE):
            pre_state, preempted = preempt(
                state, jnp.where(is_pre, run_q[r], -1), params.max_jobs)
    else:
        preempted = jnp.bool_(False)
    progress = placed | preempted

    # no progress → advance to next event, or force-place queue head if the
    # event horizon is empty (nothing running ⇒ cluster free ⇒ feasible for
    # any job with demand ≤ capacity — validate_trace enforces that on host;
    # an over-capacity job would make forced_ok False and the episode can
    # only end via the env horizon). Under faults an exhausted event
    # horizon additionally implies no transition is pending, so any still-
    # drained node is drained FOREVER; a job that no longer fits the
    # surviving capacity makes forced_ok False the same way.
    with jax.named_scope(scopes.SIM_ADVANCE):
        t_next = next_event_time(state, trace, faults)
        has_event = jnp.isfinite(t_next)
        n_before = in_system(state)
        advanced_state = advance_to(state, trace, t_next, faults)
    with jax.named_scope(scopes.SIM_PLACE):
        forced_state, forced_ok = try_place(params, state, trace, queue[0],
                                            jnp.int32(PACK), faults)

    with jax.named_scope(scopes.SIM_SELECT):
        if R:
            def pick(a, p, b, c):
                # placed ? a : preempted ? p : (has_event ? b : c)
                return jnp.where(placed, a, jnp.where(
                    preempted, p, jnp.where(has_event, b, c)))

            new_state = jax.tree.map(pick, placed_state, pre_state,
                                     advanced_state, forced_state)
        else:
            def pick(a, b, c):  # placed ? a : (has_event ? b : c)
                return jnp.where(placed, a, jnp.where(has_event, b, c))

            new_state = jax.tree.map(pick, placed_state, advanced_state,
                                     forced_state)
        dt = jnp.where(progress | ~has_event, 0.0, t_next - state.clock)
        # "first" = the job had never run before this step (start still
        # +inf); try_place keeps the original start on re-placement, so
        # this reads the pre-step state
        never_ran = ~jnp.isfinite(state.start)
        first_sel = never_ran[jnp.clip(j, 0, params.max_jobs - 1)]
        first_head = never_ran[jnp.clip(queue[0], 0, params.max_jobs - 1)]
        forced_fire = ~progress & ~has_event & forced_ok
        info = StepInfo(placed=placed | forced_fire,
                        dt=dt, in_system_before=n_before,
                        done=all_done(new_state, trace),
                        preempted=preempted,
                        first_placed=(placed & first_sel)
                        | (forced_fire & first_head))
    return new_state, info


# ---- metrics ----------------------------------------------------------------

def jct_stats(state: SimState, trace: Trace) -> dict[str, jax.Array]:
    """Avg/max JCT over completed valid jobs (masked)."""
    done = trace.valid & (state.status == DONE)
    jct = jnp.where(done, state.finish - trace.submit, 0.0)
    n = jnp.maximum(jnp.sum(done), 1)
    return {"avg_jct": jnp.sum(jct) / n,
            "max_jct": jnp.max(jnp.where(done, jct, -INF)),
            "n_done": jnp.sum(done)}


def utilization(params: SimParams, state: SimState) -> jax.Array:
    return 1.0 - jnp.sum(state.free) / params.capacity


def np_state(state: SimState) -> SimState:
    """Host copy for debugging/tests."""
    return jax.tree.map(np.asarray, state)
