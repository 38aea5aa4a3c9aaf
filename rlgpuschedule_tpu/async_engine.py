"""Async actor–learner engine (L6): Sebulba-style overlapped
rollout/update (PAPERS.md: arXiv 2104.06272).

The synchronous loop alternates rollout and update on the same devices,
idling each phase's silicon during the other. This engine splits the
device set into an ACTOR group (collects fixed-shape trajectory batches
with the fused rollout scan) and a LEARNER group (runs the fused
minibatch-update engine), overlapped through a bounded device-side
queue:

- **actor thread**: gates on the staleness bound, runs the jitted
  rollout on the actor mesh, ``device_put``s the batch onto the learner
  mesh (an EXPLICIT transfer — the hot path stays clean under
  ``jax.transfer_guard("disallow")``), and blocks when the queue is
  full (backpressure, never drops).
- **learner loop** (the CALLER's thread, so exceptions/logging/ckpt
  hooks behave exactly like ``Experiment.run``): pops batch ``i``,
  enforces the staleness invariant, splits the learner RNG in the same
  per-iteration order as the sync loop, runs the jitted
  ``make_learn_step`` program, and publishes the fresh params back to
  the actor mesh.

**Staleness semantics.** Batches are indexed ``i = 0, 1, ...`` and
batch ``i`` feeds update ``i``; after update ``i`` the published
version is ``i+1``. The actor may not START collecting batch ``i``
until ``published_version >= i - bound``, and always uses the FRESHEST
published params (so ``staleness(i) = i - version_used(i) <= bound`` —
the learner asserts it defensively). ``bound = 0`` is lock-step: every
batch is collected with fully-fresh params, which — because the split
rollout/learn programs compose literally the same functions as the
fused step, and the learner replicates the sync loop's key-split
order — reproduces ``Experiment.run`` BIT-IDENTICALLY
(tests/test_async.py pins this).

**Barriers.** Checkpoints and window resamples need a drained queue
(the carry and traces are shared mutable state). Both loops compute the
same barrier set from the cadences up front; at a barrier iteration the
actor parks after collecting that batch, the learner drains/updates
through it, performs the ckpt/resample, then releases the actor — so
checkpoints always capture a consistent (state, key, carry) triple and
resume is deterministic given the drained queue.

A single-device rig runs both roles on the same device
(``DeviceGroups.shared``): phases overlap only at the host level, but
every queue/staleness/barrier semantic — and the bound-0 bit-identity —
is identical, which is what most in-process tests exercise.

**Bit-identity scope.** The bound-0 guarantee holds when the learner
group has the same device count as the sync baseline's placement (the
update's batch reductions keep their float summation order). A WIDER
learner group shards those reductions — allclose, not bitwise, exactly
like ``parallel.dp`` data-parallel vs single-device.

**Compile-once execution.** Both programs are AOT-compiled at
construction (``jit(...).lower(...).compile()``) on the caller thread:
the loops call execute-only Compiled objects, so no jit dispatch-cache
or persistent compile-cache traffic ever happens on the actor thread
(the compile cache's file IO is not thread-safe against a concurrently
dispatching peer), and a geometry change raises a shape error instead
of silently recompiling mid-run.

**CPU host platform caveat.** XLA:CPU's client is not robust against a
second execute thread: concurrent execute calls intermittently crash
(and collective-bearing multi-device programs deadlock), and buffer
DONATION frees inputs at execute time in a way that races the peer
thread (heap corruption). On the CPU platform the runner therefore
serializes device dispatch behind a lock and disables donation — phase
spans still overlap at the host level (queue/staleness/backpressure
all behave), but compute does not. Real overlap needs separate non-CPU
device groups, where the lock is a no-op and donation is on.

**Deep staleness.** The queue depth worth running is bounded by the
learner's tolerance for off-policy data, not by the engine: with the
default clip-only PPO loss, bounds past ~1 visibly bias the surrogate.
``cfg.ppo.correction = "vtrace"`` (``algos.vtrace``) re-weights the
advantage targets by clipped importance ratios so bounds >= 4 train
without that bias — the per-batch mean/max ratios surface on the
``rlsched_async_importance_ratio_*`` gauges and in ``async_info()`` so
a drifting ratio is visible before it is a reward regression.

:class:`AsyncPopulationRunner` extends the same engine to the PBT
population: the vmapped member rollout/learn halves run on the group
meshes, PBT exploit/explore fires at drained-queue barriers predicted
from the controller window, and staleness is tracked per member.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .algos import (init_carry, validate_rollout_geometry,
                    validate_update_geometry)
from .algos.a2c import make_learn_step as make_a2c_learn_step
from .algos.ppo import make_learn_step as make_ppo_learn_step
from .algos.rollout import make_rollout_step
from .analysis.sentinels import no_implicit_transfers
from .obs import startup
from .obs.telemetry import AsyncGauges, OverlapMeter
from .obs.scopes import TRAIN_ITERATION
from .obs.trace import tracer_of
from .parallel.dp import put_carry
from .parallel.groups import DeviceGroups
from .parallel.sharding import put_global
from .utils.profiling import SectionTimer

# every blocking wait re-checks abort/progress at this period, and gives
# up (a clear RuntimeError instead of a silent hang) after stall_timeout_s
_WAIT_TICK_S = 0.2


class StalenessError(RuntimeError):
    """The learner was handed a batch older than the configured bound —
    an engine invariant violation (the actor gate should make this
    impossible), never a user error."""


class _Aborted(Exception):
    """Internal: unwind a loop after the other loop failed."""


@dataclasses.dataclass
class _QueueItem:
    index: int      # global batch index (== the update that consumes it)
    version: int    # policy version the batch was collected with
    batch: Any      # (transitions, last_value) on the LEARNER mesh


class TrajectoryQueue:
    """Bounded blocking FIFO between the actor and learner loops.

    ``put`` blocks while the queue is at capacity (backpressure — a
    full queue slows the actor down, it never drops a batch); ``get``
    blocks while empty. ``abort(exc)`` wakes every waiter: blocked
    ``put``/``get`` calls raise ``_Aborted`` so a failure in either
    loop unwinds the other instead of deadlocking it. Items hold
    device arrays (the batch already lives on the learner mesh), so
    the queue itself never copies — it is depth bookkeeping plus
    blocking semantics."""

    def __init__(self, capacity: int,
                 clock: Callable[[], float] = time.monotonic,
                 stall_timeout_s: float = 300.0):
        if capacity < 1:
            raise ValueError(f"queue capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock
        self._stall_timeout_s = stall_timeout_s
        self._items: list[_QueueItem] = []
        self._cv = threading.Condition()
        self._abort_exc: BaseException | None = None

    def __len__(self) -> int:
        with self._cv:
            return len(self._items)

    def abort(self, exc: BaseException) -> None:
        with self._cv:
            if self._abort_exc is None:
                self._abort_exc = exc
            self._cv.notify_all()

    def _wait(self, ready: Callable[[], bool], what: str) -> float:
        """Wait until ``ready()`` under the held condition; returns the
        seconds spent blocked."""
        t0 = self._clock()
        while not ready():
            if self._abort_exc is not None:
                raise _Aborted() from self._abort_exc
            if self._clock() - t0 > self._stall_timeout_s:
                raise RuntimeError(
                    f"TrajectoryQueue.{what} stalled for more than "
                    f"{self._stall_timeout_s}s (deadlocked peer loop?)")
            self._cv.wait(_WAIT_TICK_S)
        if self._abort_exc is not None:
            raise _Aborted() from self._abort_exc
        return self._clock() - t0

    def put(self, item: _QueueItem) -> float:
        """Blocking append; returns seconds spent in backpressure."""
        with self._cv:
            waited = self._wait(
                lambda: len(self._items) < self.capacity, "put")
            self._items.append(item)
            self._cv.notify_all()
            return waited

    def get(self) -> tuple[_QueueItem, float]:
        """Blocking pop; returns (item, seconds spent waiting)."""
        with self._cv:
            waited = self._wait(lambda: len(self._items) > 0, "get")
            item = self._items.pop(0)
            self._cv.notify_all()
            return item, waited


class _ParamSlot:
    """The published-params mailbox: the learner publishes
    ``(params_on_actor_mesh, version)``; the actor waits for a minimum
    version and always reads the freshest publication."""

    def __init__(self, params: Any, version: int,
                 clock: Callable[[], float] = time.monotonic,
                 stall_timeout_s: float = 300.0):
        self._params = params
        self._version = version
        self._clock = clock
        self._stall_timeout_s = stall_timeout_s
        self._cv = threading.Condition()
        self._abort = False

    @property
    def version(self) -> int:
        with self._cv:
            return self._version

    def abort(self) -> None:
        with self._cv:
            self._abort = True
            self._cv.notify_all()

    def publish(self, params: Any, version: int) -> None:
        with self._cv:
            self._params = params
            self._version = version
            self._cv.notify_all()

    def wait_for(self, min_version: int) -> tuple[Any, int, float]:
        """Block until ``version >= min_version``; returns
        (freshest params, their version, seconds spent gated)."""
        t0 = self._clock()
        with self._cv:
            while self._version < min_version:
                if self._abort:
                    raise _Aborted()
                if self._clock() - t0 > self._stall_timeout_s:
                    raise RuntimeError(
                        f"staleness gate stalled waiting for version "
                        f">= {min_version} (have {self._version})")
                self._cv.wait(_WAIT_TICK_S)
            if self._abort:
                raise _Aborted()
            return self._params, self._version, self._clock() - t0


class AsyncRunner:
    """The assembled async engine over one :class:`~.experiment.Experiment`.

    Construction ADOPTS the experiment onto the group meshes: traces +
    rollout carry move to the actor mesh, train state + learner RNG key
    to the learner mesh (all explicit placements). ``run()`` may be
    called repeatedly — programs stay compiled, version/batch counters
    continue — which is how the no-post-warmup-recompile contract is
    tested.

    ``staleness_bound``: max policy-versions a consumed batch may be
    behind (0 = lock-step sync twin). ``queue_capacity``: bounded
    batches in flight past the gate (backpressure blocks the actor
    when full)."""

    def __init__(self, exp, groups: DeviceGroups | None = None,
                 staleness_bound: int = 1, queue_capacity: int = 2,
                 stall_timeout_s: float = 300.0):
        if staleness_bound < 0:
            raise ValueError(f"staleness_bound must be >= 0, got "
                             f"{staleness_bound}")
        cfg = exp.cfg
        algo_cfg = cfg.ppo if cfg.algo == "ppo" else cfg.a2c
        if groups is None:
            # default split carved from the shared unified mesh (same
            # device walk as every other entry point), so actor/learner
            # groups are submeshes of the ONE Mesh(pop × data × model)
            from .parallel.groups import split_mesh
            from .parallel.mesh import unified_mesh
            groups = split_mesh(unified_mesh())
        # decoupled per-phase geometry validation: each phase against
        # ITS device group (the whole point of splitting the check)
        validate_rollout_geometry(algo_cfg.n_steps, cfg.n_envs,
                                  len(groups.actor))
        validate_update_geometry(algo_cfg.n_epochs, algo_cfg.n_minibatches,
                                 algo_cfg.minibatch_size,
                                 n_steps=algo_cfg.n_steps,
                                 n_envs=cfg.n_envs,
                                 n_devices=len(groups.learner))
        # XLA:CPU's client intermittently segfaults (and, for
        # collective-bearing multi-device programs, deadlocks) when two
        # threads execute concurrently, so serialize device dispatch on
        # the CPU platform. Phase spans still overlap at the host level
        # — the same accounting the shared-group mode reports — but
        # real compute overlap needs a non-CPU platform, where the lock
        # is a no-op.
        on_cpu = groups.actor[0].platform == "cpu"
        self._dispatch_lock: Any = (
            threading.Lock() if on_cpu else contextlib.nullcontext())
        self.exp = exp
        self.groups = groups
        self.staleness_bound = staleness_bound
        self.queue_capacity = queue_capacity
        self._stall_timeout_s = stall_timeout_s
        self._clock = time.monotonic

        make_learn = (make_ppo_learn_step if cfg.algo == "ppo"
                      else make_a2c_learn_step)

        # adopt the experiment's state onto the group meshes (explicit
        # placements; the experiment object stays the canonical holder
        # so save/restore_checkpoint work unchanged)
        self._arep = groups.actor_replicated()
        self._aenv = groups.actor_env()
        self._lrep = groups.learner_replicated()
        self._lenv = groups.learner_env()
        self._ltraj = groups.learner_traj()
        exp.traces = put_global(exp.traces, self._aenv)
        exp.carry = put_carry(groups.actor_mesh, exp.carry)
        exp.train_state = put_global(exp.train_state, self._lrep)
        exp.key = jax.device_put(exp.key, self._lrep)
        self._faults = (put_global(exp.faults, self._aenv)
                        if exp.faults is not None else None)
        exp.faults = self._faults

        # AOT-compile BOTH programs on the construction thread
        # (``jit(...).lower(...).compile()``): the loops call execute-only
        # Compiled objects, so neither the jit dispatch machinery nor the
        # persistent compilation cache — whose file IO is not safe to
        # drive from the actor thread while the caller thread dispatches —
        # is ever touched off this thread, and a geometry change raises a
        # shape error instead of silently recompiling mid-run.
        # axis_name stays None on both programs: GSPMD derives the
        # gradient psum / global advantage moments from the shardings,
        # exactly like parallel.dp.shard_train
        # donation frees the consumed input buffers at execute time, and
        # on XLA:CPU that deallocation races the peer loop's thread
        # (heap corruption — intermittent SIGSEGV/SIGABRT at ~30% per
        # run on the 8-virtual-device rig, clean with donation off), so
        # the engine donates only off-CPU; the lock-step bit-identity
        # does not depend on aliasing
        rollout_donate = () if on_cpu else (1,)   # the carry
        learn_donate = () if on_cpu else (0,)     # the train state
        params_a = jax.device_put(exp.train_state.params, self._arep)
        rollout_jit = jax.jit(
            make_rollout_step(exp.apply_fn, exp.env_params,
                              algo_cfg.n_steps),
            donate_argnums=rollout_donate)
        self._rollout = rollout_jit.lower(
            params_a, exp.carry, exp.traces, self._faults).compile()
        # the learner program needs a trajectory batch to lower against;
        # shape it from the rollout's output avals (zeros, freed after)
        _, tr_s, lv_s = jax.eval_shape(rollout_jit, params_a, exp.carry,
                                       exp.traces, self._faults)
        tr0 = jax.device_put(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), tr_s), self._ltraj)
        lv0 = jax.device_put(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), lv_s), self._lenv)
        # donate the state only (off-CPU): the trajectory leaves go
        # through a [T, E] -> [B] flatten, so XLA can't alias them
        # anyway (donating them just warns)
        self._learn = jax.jit(
            make_learn(exp.apply_fn, algo_cfg),
            donate_argnums=learn_donate).lower(
                exp.train_state, tr0, lv0, exp.key).compile()
        del tr0, lv0

        # loop state shared across run() calls
        self._iterations_done = 0
        self._slot = _ParamSlot(
            params_a, version=0,
            clock=self._clock, stall_timeout_s=stall_timeout_s)
        self.queue = TrajectoryQueue(queue_capacity, clock=self._clock,
                                     stall_timeout_s=stall_timeout_s)
        self.overlap = OverlapMeter(clock=self._clock)
        self._bar_cv = threading.Condition()
        self._barriers: list[int] = []     # global iteration indices
        self._barriers_done = 0
        self._failure: BaseException | None = None
        # actor-thread-owned accounting, read by the learner at log points
        self._actor_idle_s = 0.0
        self._learner_idle_s = 0.0
        self._staleness_last = 0
        self._staleness_max = 0
        self._staleness_sum = 0
        self._consumed = 0
        # importance-ratio monitor, fed from the metrics already fetched
        # at log points (ZERO extra host syncs): 1.0 is the on-policy
        # neutral value the GAE path reports
        self._rho_last = 1.0
        self._rho_max_seen = 1.0

    # -- barrier plumbing --------------------------------------------------

    def _wait_barriers_before(self, i: int) -> float:
        """Actor side: park until every barrier < global iteration ``i``
        has been completed by the learner. Returns seconds parked."""
        t0 = self._clock()
        with self._bar_cv:
            need = bisect.bisect_left(self._barriers, i)
            while self._barriers_done < need:
                if self._failure is not None:
                    raise _Aborted()
                if self._clock() - t0 > self._stall_timeout_s:
                    raise RuntimeError(
                        f"actor stalled at barrier before iteration {i}")
                self._bar_cv.wait(_WAIT_TICK_S)
        return self._clock() - t0

    def _complete_barrier(self) -> None:
        with self._bar_cv:
            self._barriers_done += 1
            self._bar_cv.notify_all()

    def _abort(self, exc: BaseException) -> None:
        # publish the failure under the barrier Condition: the learner
        # reads it there, and an unlocked write could be seen torn
        # against the notify
        with self._bar_cv:
            self._failure = exc
            self._bar_cv.notify_all()
        self.queue.abort(exc)
        self._slot.abort()

    # -- the actor loop (background thread) --------------------------------

    def _actor_loop(self, base: int, iterations: int,
                    sections: SectionTimer, tracer) -> None:
        exp = self.exp
        carry = exp.carry
        try:
            for k in range(iterations):
                i = base + k
                # the flight recorder's actor track: the two wait spans
                # (barrier park + staleness gate) and the push-side
                # backpressure are the idle gaps the occupancy timeline
                # exists to show; the "actor" span is the busy lane the
                # measured-overlap summary unions against "learner"
                with tracer.span("actor_barrier_wait"):
                    self._actor_idle_s += self._wait_barriers_before(i)
                # staleness gate: may not collect batch i until the
                # learner is within `bound` versions; always take the
                # freshest publication (ISSUE: "refresh actor params
                # from the learner at each publish")
                with tracer.span("actor_gate_wait"):
                    params, version, gated = self._slot.wait_for(
                        i - self.staleness_bound)
                self._actor_idle_s += gated
                # barrier-park may have replaced the carry (resample)
                carry = exp.carry
                with tracer.phase(sections, "actor", meter=self.overlap,
                                  iteration=i), \
                        no_implicit_transfers(), self._dispatch_lock:
                    carry, tr, last_value = self._rollout(
                        params, carry, exp.traces, self._faults)
                    # explicit hop onto the learner mesh: the queue is
                    # device-side, the learner pops ready-to-consume
                    # buffers
                    batch = (jax.device_put(tr, self._ltraj),
                             jax.device_put(last_value, self._lenv))
                    jax.block_until_ready(batch)
                exp.carry = carry
                with tracer.span("queue_push_wait"):
                    self._actor_idle_s += self.queue.put(
                        _QueueItem(index=i, version=version, batch=batch))
        except _Aborted:
            pass
        except BaseException as e:  # surface in the learner thread
            self._abort(e)

    # -- the learner loop (caller thread) -----------------------------------

    @startup.recorded_run
    def run(self, iterations: int | None = None, log_every: int = 0,
            logger: Callable[[int, dict], None] | None = None,
            ckpt=None, ckpt_every: int = 0,
            eval_every: int = 0,
            eval_fn: "Callable[[int], dict] | None" = None,
            eval_logger: Callable[[int, dict], None] | None = None,
            telemetry=None) -> dict:
        """Run ``iterations`` overlapped actor/learner iterations; the
        hook surface (log/ckpt/eval cadences, telemetry protocol,
        summary dict) mirrors :meth:`Experiment.run`. Window streaming
        (``cfg.resample_every``) and checkpoints run at drained-queue
        barriers."""
        exp = self.exp
        cfg = exp.cfg
        iterations = iterations or cfg.iterations
        base = self._iterations_done
        history: list[dict] = []
        eval_history: list[dict] = []
        sections = startup.sections_of(telemetry)
        gauges = (AsyncGauges(telemetry.registry)
                  if telemetry is not None else None)
        tracer = tracer_of(telemetry)

        def is_ckpt(b: int) -> bool:
            return bool(ckpt is not None and ckpt_every
                        and ((b + 1) % ckpt_every == 0
                             or b == iterations - 1))

        def is_resample(b: int) -> bool:
            return bool(cfg.resample_every
                        and (b + 1) % cfg.resample_every == 0
                        and b != iterations - 1)

        local_barriers = sorted(b for b in range(iterations)
                                if is_ckpt(b) or is_resample(b))
        with self._bar_cv:
            self._barriers = [base + b for b in local_barriers]
            self._barriers_done = 0
            self._failure = None

        if telemetry is not None:
            telemetry.run_start(
                loop="async-experiment", config=cfg.name, algo=cfg.algo,
                iterations=iterations, n_envs=cfg.n_envs,
                steps_per_iteration=exp.steps_per_iteration,
                staleness_bound=self.staleness_bound,
                queue_capacity=self.queue_capacity,
                actor_devices=[d.id for d in self.groups.actor],
                learner_devices=[d.id for d in self.groups.learner],
                shared_group=self.groups.shared)

        t0 = time.monotonic()
        actor = threading.Thread(
            target=self._actor_loop,
            args=(base, iterations, sections, tracer),
            name="async-actor", daemon=True)
        actor.start()
        try:
            for k in range(iterations):
                b = k  # hook-facing iteration index, as in Experiment.run
                i = base + k
                with jax.profiler.StepTraceAnnotation(
                        TRAIN_ITERATION, step_num=b):
                    if telemetry is not None:
                        telemetry.begin_iteration(b)
                    with tracer.phase(sections, "queue_wait",
                                      span="queue_pop_wait"):
                        item, waited = self.queue.get()  # jsan: disable=hung-future -- TrajectoryQueue.get is bounded by construction (stall timeout + abort wakes every waiter)
                    self._learner_idle_s += waited
                    if item.index != i:
                        raise RuntimeError(
                            f"queue order violation: expected batch {i}, "
                            f"got {item.index}")
                    staleness = item.index - item.version
                    if staleness > self.staleness_bound:
                        raise StalenessError(
                            f"batch {item.index} was collected at policy "
                            f"version {item.version} — {staleness} versions "
                            f"behind, bound is {self.staleness_bound}")
                    self._staleness_last = staleness
                    self._staleness_max = max(self._staleness_max, staleness)
                    self._staleness_sum += staleness
                    self._consumed += 1
                    guard = (telemetry.dispatch(b) if telemetry is not None
                             else contextlib.nullcontext())
                    tr, last_value = item.batch
                    with tracer.phase(sections, "learner",
                                      meter=self.overlap, iteration=b), \
                            guard, self._dispatch_lock:
                        # the sync loop's per-iteration split, same order
                        exp.key, sub = jax.random.split(exp.key)
                        state, metrics = self._learn(exp.train_state, tr,
                                                     last_value, sub)
                        params_a = jax.device_put(state.params, self._arep)
                        jax.block_until_ready(params_a)
                    exp.train_state = state
                    self._slot.publish(params_a, i + 1)

                    want_log = bool(log_every) and (b % log_every == 0
                                                    or b == iterations - 1)
                    m = None
                    if want_log:
                        with tracer.phase(sections, "sync"), \
                                self._dispatch_lock:
                            m = {k2: float(v) for k2, v in
                                 jax.device_get(metrics)._asdict().items()}
                        if "rho_mean" in m:
                            self._rho_last = m["rho_mean"]
                            self._rho_max_seen = max(self._rho_max_seen,
                                                     m["rho_max"])
                        history.append({"iteration": b, **m})
                        if logger is not None:
                            logger(b, m)
                        if gauges is not None:
                            gauges.publish(
                                queue_depth=len(self.queue),
                                staleness=self._staleness_last,
                                actor_idle_s=self._actor_idle_s,
                                learner_idle_s=self._learner_idle_s,
                                overlap_s=self.overlap.overlap_s,
                                importance_ratio_mean=self._rho_last,
                                importance_ratio_max=self._rho_max_seen)
                    if eval_fn is not None and eval_every and \
                            ((b + 1) % eval_every == 0 or b == iterations - 1):
                        with tracer.phase(sections, "eval"), \
                                self._dispatch_lock:
                            em = dict(eval_fn(b))
                        eval_history.append({"iteration": b, **em})
                        if eval_logger is not None:
                            eval_logger(b, em)
                    # drained-queue barrier work (actor is parked past i)
                    if is_ckpt(b):
                        with tracer.phase(sections, "ckpt"):
                            exp.save_checkpoint(
                                ckpt, meta={"iteration": b,
                                            "async_iteration": i,
                                            "staleness_bound":
                                                self.staleness_bound})
                    if is_resample(b):
                        with tracer.phase(sections, "resample"):
                            self._resample()
                    if is_ckpt(b) or is_resample(b):
                        self._complete_barrier()
                    if telemetry is not None:
                        telemetry.end_iteration(
                            b, m if want_log else None,
                            exp.steps_per_iteration)
                    if self._failure is not None:
                        raise self._failure
        except BaseException as e:
            self._abort(e)
            actor.join(timeout=30)
            raise
        actor.join(timeout=self._stall_timeout_s)
        if actor.is_alive():
            exc = RuntimeError("actor thread failed to drain")
            self._abort(exc)
            raise exc
        if self._failure is not None:
            raise self._failure
        jax.block_until_ready(exp.train_state.params)
        self._iterations_done = base + iterations
        wall = time.monotonic() - t0
        total_env_steps = iterations * exp.steps_per_iteration
        async_info = self.async_info()
        out = {"wall_s": wall, "iterations": iterations,
               "env_steps": total_env_steps,
               "env_steps_per_sec": total_env_steps / wall,
               "window_cursor": exp.window_cursor,
               "history": history,
               "phase_seconds": {k: round(v, 6)
                                 for k, v in sections.report().items()},
               "async": async_info}
        if eval_history:
            out["eval_history"] = eval_history
        if telemetry is not None:
            if gauges is not None:
                gauges.publish(queue_depth=len(self.queue),
                               staleness=self._staleness_last,
                               actor_idle_s=self._actor_idle_s,
                               learner_idle_s=self._learner_idle_s,
                               overlap_s=self.overlap.overlap_s,
                               importance_ratio_mean=self._rho_last,
                               importance_ratio_max=self._rho_max_seen)
            telemetry.run_end(
                iterations=iterations, wall_s=round(wall, 6),
                env_steps=total_env_steps,
                env_steps_per_sec=round(out["env_steps_per_sec"], 3),
                **{f"async_{k2}": v for k2, v in async_info.items()
                   if not isinstance(v, (list, dict))})
        return out

    def async_info(self) -> dict:
        """The engine's overlap/staleness accounting so far."""
        snap = self.overlap.snapshot()
        return {
            "staleness_bound": self.staleness_bound,
            "queue_capacity": self.queue_capacity,
            "actor_devices": [d.id for d in self.groups.actor],
            "learner_devices": [d.id for d in self.groups.learner],
            "shared_group": self.groups.shared,
            "overlap_s": snap["overlap_s"],
            "actor_busy_s": snap.get("busy_actor_s", 0.0),
            "learner_busy_s": snap.get("busy_learner_s", 0.0),
            "actor_idle_s": round(self._actor_idle_s, 6),
            "learner_idle_s": round(self._learner_idle_s, 6),
            "staleness_max": self._staleness_max,
            "staleness_mean": (self._staleness_sum / self._consumed
                               if self._consumed else 0.0),
            "importance_ratio_mean": self._rho_last,
            "importance_ratio_max": self._rho_max_seen,
        }

    def _resample(self) -> None:
        """Window streaming at a drained-queue barrier: re-cut the env
        windows and re-init the carry, keeping every placement on its
        group mesh (the sync twin is ``Experiment.advance_windows``,
        which assumes a single placement domain)."""
        exp = self.exp
        exp._cut_windows(exp.window_cursor + exp.cfg.n_envs)
        exp.key, carry_key = jax.random.split(exp.key)
        carry_key = jax.device_put(carry_key, self._arep)
        carry = init_carry(exp.env_params, exp.traces, carry_key,
                           self._faults)
        exp.carry = jax.tree.map(
            lambda new, old: jax.device_put(new, old.sharding),
            carry, exp.carry)


def _make_pop_rollout(apply_fn, env_params, n_steps,
                      with_faults: bool = False):
    """The actor half of the population step: vmap the SAME rollout the
    fused ``make_population_step`` vmaps — member params/carries mapped,
    traces broadcast (``in_axes=None``, one shared env-window set for
    fitness comparability). Per-member [P, E] fault-schedule stacks map
    over the member axis like the carries (``with_faults``)."""
    from .algos.rollout import rollout as rollout_fn

    if with_faults:
        def pop_rollout_faulty(params, carries, traces, faults):
            return jax.vmap(
                lambda p, c, t, f: rollout_fn(apply_fn, p, env_params, t,
                                              c, n_steps, f),
                in_axes=(0, 0, None, 0))(params, carries, traces, faults)

        return pop_rollout_faulty

    def pop_rollout(params, carries, traces):
        return jax.vmap(
            lambda p, c, t: rollout_fn(apply_fn, p, env_params, t, c,
                                       n_steps),
            in_axes=(0, 0, None))(params, carries, traces)

    return pop_rollout


class AsyncPopulationRunner:
    """The async engine over a :class:`~.experiment.PopulationExperiment`:
    the vmapped member ROLLOUT half runs on the actor group, the vmapped
    member LEARN half (``parallel.population.make_member_learn_step``,
    traced per-member hyperparameters and all) on the learner group,
    overlapped through the same bounded queue / staleness gate /
    barrier machinery as :class:`AsyncRunner`.

    **Why V-trace makes this row legal.** The refusal this class deletes
    (``MODE_REFUSALS`` ``async x pbt``) existed because PBT's host-side
    exploit/explore interleaves between steps AND because stale batches
    bias each member differently, corrupting the fitness comparison the
    controller ranks on. Both are now handled: exploit rounds fire at
    drained-queue BARRIERS predicted from the controller window (both
    loops agree on the schedule up front, so the actor is parked and the
    weight copy is race-free), and ``correction="vtrace"`` re-weights
    every member's targets by its own importance ratios so staleness
    shifts no member's fitness estimate.

    **Placement (v1).** Member stacks are REPLICATED on their group
    meshes (``actor_replicated`` / ``learner_replicated``); build the
    population with ``mesh=None`` and let the runner own placement.
    Sharding the member stack over a ``pop`` axis *within* each async
    group is an open end (ROADMAP) — it needs per-group meshes with a
    pop dimension plus a sharded exploit gather, and the bound-0
    bit-identity contract below is defined against the unsharded sync
    twin anyway.

    **Bound-0 contract.** ``staleness_bound=0`` reproduces the non-mesh
    ``PopulationExperiment.run`` loop bit-identically: same key-split
    program and order, same member program composition (the split
    rollout/learn halves vmap the same functions the fused
    ``make_population_step`` vmaps), same exploit schedule (the barrier
    prediction is exact, and the runner raises if the controller ever
    fires off-schedule).

    **Per-member staleness.** Batches are stacked, so every member in
    queue item ``i`` shares the item's version lag; the bookkeeping is
    still tracked per member because exploit RESETS the exploited
    members' effective lag (they restart from just-published donor
    weights). ``async_info()`` reports both the scalar aggregates and
    the per-member last/max vectors."""

    def __init__(self, pexp, groups: DeviceGroups | None = None,
                 staleness_bound: int = 1, queue_capacity: int = 2,
                 stall_timeout_s: float = 300.0):
        from .parallel.population import make_member_learn_step
        if staleness_bound < 0:
            raise ValueError(f"staleness_bound must be >= 0, got "
                             f"{staleness_bound}")
        cfg = pexp.cfg
        if pexp.mesh is not None:
            raise ValueError(
                "AsyncPopulationRunner owns device placement (member "
                "stacks replicated on the actor/learner group meshes); "
                "build the population with mesh=None. Sharding the pop "
                "axis within async groups is an open end (ROADMAP)")
        if groups is None:
            from .parallel.groups import split_mesh
            from .parallel.mesh import unified_mesh
            groups = split_mesh(unified_mesh())
        # v1 replicates both member stacks on their group meshes, so the
        # per-phase geometry checks run against a single placement domain
        validate_rollout_geometry(cfg.ppo.n_steps, cfg.n_envs, 1)
        validate_update_geometry(cfg.ppo.n_epochs, cfg.ppo.n_minibatches,
                                 cfg.ppo.minibatch_size,
                                 n_steps=cfg.ppo.n_steps,
                                 n_envs=cfg.n_envs, n_devices=1)
        on_cpu = groups.actor[0].platform == "cpu"
        self._dispatch_lock: Any = (
            threading.Lock() if on_cpu else contextlib.nullcontext())
        self.pexp = pexp
        self.groups = groups
        self.staleness_bound = staleness_bound
        self.queue_capacity = queue_capacity
        self._stall_timeout_s = stall_timeout_s
        self._clock = time.monotonic

        # adopt the population onto the group meshes (explicit placements;
        # the experiment object stays the canonical holder so
        # save/restore_checkpoint and member_eval_view work unchanged)
        self._arep = groups.actor_replicated()
        self._lrep = groups.learner_replicated()
        pexp.traces = put_global(pexp.traces, self._arep)
        pexp.carries = put_global(pexp.carries, self._arep)
        pexp.states = put_global(pexp.states, self._lrep)
        pexp.keys = jax.device_put(pexp.keys, self._lrep)
        pexp.hparams = put_global(pexp.hparams, self._lrep)
        if pexp.faults is not None:
            # the [P, E] member schedule stacks are actor-side data, like
            # the traces
            pexp.faults = put_global(pexp.faults, self._arep)

        apply_fn = pexp.apply_fn
        pop_learn = jax.vmap(make_member_learn_step(apply_fn, cfg.ppo),
                             in_axes=(0, 0, 0, 0, 0))

        # same AOT-compile + CPU donation-off reasoning as AsyncRunner
        rollout_donate = () if on_cpu else (1,)   # the carry stack
        learn_donate = () if on_cpu else (0,)     # the member-state stack
        params_a = jax.device_put(pexp.states.params, self._arep)
        rollout_jit = jax.jit(
            _make_pop_rollout(apply_fn, pexp.env_params, cfg.ppo.n_steps,
                              with_faults=pexp.faults is not None),
            donate_argnums=rollout_donate)
        rollout_args = (params_a, pexp.carries, pexp.traces)
        if pexp.faults is not None:
            rollout_args = rollout_args + (pexp.faults,)
        self._rollout = rollout_jit.lower(*rollout_args).compile()
        _, tr_s, lv_s = jax.eval_shape(rollout_jit, *rollout_args)
        tr0 = jax.device_put(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), tr_s), self._lrep)
        lv0 = jax.device_put(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), lv_s), self._lrep)
        subs0 = jax.device_put(
            jnp.zeros(pexp.keys.shape, pexp.keys.dtype), self._lrep)
        self._learn = jax.jit(
            pop_learn, donate_argnums=learn_donate).lower(
                pexp.states, tr0, lv0, subs0, pexp.hparams).compile()
        del tr0, lv0, subs0
        # the sync population loop's per-iteration key split — the SAME
        # jit(vmap(split)) program in the same order, for bound-0 parity
        self._split_all = jax.jit(jax.vmap(lambda k: jax.random.split(k)))

        # loop state shared across run() calls
        self._iterations_done = 0
        self._slot = _ParamSlot(
            params_a, version=0,
            clock=self._clock, stall_timeout_s=stall_timeout_s)
        self.queue = TrajectoryQueue(queue_capacity, clock=self._clock,
                                     stall_timeout_s=stall_timeout_s)
        self.overlap = OverlapMeter(clock=self._clock)
        self._bar_cv = threading.Condition()
        self._barriers: list[int] = []
        self._barriers_done = 0
        self._failure: BaseException | None = None
        self._actor_idle_s = 0.0
        self._learner_idle_s = 0.0
        self._staleness_last = 0
        self._staleness_max = 0
        self._staleness_sum = 0
        self._consumed = 0
        # per-member lag vectors: uniform per stacked item, but exploit
        # resets the exploited members' LAST lag (fresh donor weights)
        self._stale_last_pm = [0] * pexp.n_pop
        self._stale_max_pm = [0] * pexp.n_pop
        self._rho_last = 1.0
        self._rho_max_seen = 1.0

    # -- barrier plumbing (same protocol as AsyncRunner) --------------------

    def _wait_barriers_before(self, i: int) -> float:
        t0 = self._clock()
        with self._bar_cv:
            need = bisect.bisect_left(self._barriers, i)
            while self._barriers_done < need:
                if self._failure is not None:
                    raise _Aborted()
                if self._clock() - t0 > self._stall_timeout_s:
                    raise RuntimeError(
                        f"actor stalled at barrier before iteration {i}")
                self._bar_cv.wait(_WAIT_TICK_S)
        return self._clock() - t0

    def _complete_barrier(self) -> None:
        with self._bar_cv:
            self._barriers_done += 1
            self._bar_cv.notify_all()

    def _abort(self, exc: BaseException) -> None:
        # publish the failure under the barrier Condition: the learner
        # reads it there, and an unlocked write could be seen torn
        # against the notify
        with self._bar_cv:
            self._failure = exc
            self._bar_cv.notify_all()
        self.queue.abort(exc)
        self._slot.abort()

    # -- the actor loop (background thread) ---------------------------------

    def _actor_loop(self, base: int, iterations: int,
                    sections: SectionTimer, tracer) -> None:
        pexp = self.pexp
        carries = pexp.carries
        try:
            for k in range(iterations):
                i = base + k
                with tracer.span("actor_barrier_wait"):
                    self._actor_idle_s += self._wait_barriers_before(i)
                with tracer.span("actor_gate_wait"):
                    params, version, gated = self._slot.wait_for(
                        i - self.staleness_bound)
                self._actor_idle_s += gated
                carries = pexp.carries
                roll_args = (params, carries, pexp.traces)
                if pexp.faults is not None:
                    roll_args = roll_args + (pexp.faults,)
                with tracer.phase(sections, "actor", meter=self.overlap,
                                  iteration=i), \
                        no_implicit_transfers(), self._dispatch_lock:
                    carries, tr, last_value = self._rollout(*roll_args)
                    batch = (jax.device_put(tr, self._lrep),
                             jax.device_put(last_value, self._lrep))
                    jax.block_until_ready(batch)
                pexp.carries = carries
                with tracer.span("queue_push_wait"):
                    self._actor_idle_s += self.queue.put(
                        _QueueItem(index=i, version=version, batch=batch))
        except _Aborted:
            pass
        except BaseException as e:
            self._abort(e)

    # -- the learner loop (caller thread) -----------------------------------

    @startup.recorded_run
    def run(self, iterations: int | None = None, log_every: int = 0,
            logger: Callable[[int, dict], None] | None = None,
            ckpt=None, ckpt_every: int = 0,
            eval_every: int = 0,
            eval_fn: "Callable[[int], dict] | None" = None,
            eval_logger: Callable[[int, dict], None] | None = None,
            telemetry=None) -> dict:
        """Run ``iterations`` overlapped population iterations; the hook
        surface mirrors :meth:`PopulationExperiment.run` minus
        watchdog/injector (chaos drills stay on the sync loop). PBT
        exploit/explore and checkpoints run at drained-queue barriers."""
        pexp = self.pexp
        cfg = pexp.cfg
        ctrl = pexp.controller
        iterations = iterations or cfg.iterations
        base = self._iterations_done
        history: list[dict] = []
        eval_history: list[dict] = []
        sections = startup.sections_of(telemetry)
        gauges = (AsyncGauges(telemetry.registry)
                  if telemetry is not None else None)
        tracer = tracer_of(telemetry)

        def is_ckpt(b: int) -> bool:
            return bool(ckpt is not None and ckpt_every
                        and ((b + 1) % ckpt_every == 0
                             or b == iterations - 1))

        # predict the controller's exploit iterations so both loops agree
        # on the barrier set up front: maybe_update consults ONLY its
        # recorded-window count (never the iteration number) and resets
        # the window on fire, so with `window` records carried in from
        # earlier run() calls, local iteration b fires exactly when
        # (window + b + 1) % ready_iters == 0
        window = ctrl._fitness_n + len(ctrl._pending)
        ready = ctrl.cfg.ready_iters

        def is_exploit(b: int) -> bool:
            return (window + b + 1) % ready == 0

        local_barriers = sorted(b for b in range(iterations)
                                if is_ckpt(b) or is_exploit(b))
        with self._bar_cv:
            self._barriers = [base + b for b in local_barriers]
            self._barriers_done = 0
            self._failure = None

        if telemetry is not None:
            telemetry.run_start(
                loop="async-population", config=cfg.name,
                n_pop=pexp.n_pop, iterations=iterations,
                n_envs=cfg.n_envs,
                steps_per_iteration=pexp.steps_per_iteration,
                staleness_bound=self.staleness_bound,
                queue_capacity=self.queue_capacity,
                actor_devices=[d.id for d in self.groups.actor],
                learner_devices=[d.id for d in self.groups.learner],
                shared_group=self.groups.shared)

        t0 = time.monotonic()
        actor = threading.Thread(
            target=self._actor_loop,
            args=(base, iterations, sections, tracer),
            name="async-pop-actor", daemon=True)
        actor.start()
        try:
            for k in range(iterations):
                b = k
                i = base + k
                with jax.profiler.StepTraceAnnotation(
                        TRAIN_ITERATION, step_num=b):
                    if telemetry is not None:
                        telemetry.begin_iteration(b)
                    with tracer.phase(sections, "queue_wait",
                                      span="queue_pop_wait"):
                        item, waited = self.queue.get()  # jsan: disable=hung-future -- TrajectoryQueue.get is bounded by construction (stall timeout + abort wakes every waiter)
                    self._learner_idle_s += waited
                    if item.index != i:
                        raise RuntimeError(
                            f"queue order violation: expected batch {i}, "
                            f"got {item.index}")
                    staleness = item.index - item.version
                    if staleness > self.staleness_bound:
                        raise StalenessError(
                            f"batch {item.index} was collected at policy "
                            f"version {item.version} — {staleness} versions "
                            f"behind, bound is {self.staleness_bound}")
                    self._staleness_last = staleness
                    self._staleness_max = max(self._staleness_max, staleness)
                    self._staleness_sum += staleness
                    self._consumed += 1
                    for p in range(pexp.n_pop):
                        self._stale_last_pm[p] = staleness
                        self._stale_max_pm[p] = max(self._stale_max_pm[p],
                                                    staleness)
                    guard = (telemetry.dispatch(b) if telemetry is not None
                             else contextlib.nullcontext())
                    tr, last_value = item.batch
                    with tracer.phase(sections, "learner",
                                      meter=self.overlap, iteration=b), \
                            guard, self._dispatch_lock:
                        # the sync population loop's per-iteration split,
                        # same program and order
                        both = self._split_all(pexp.keys)
                        keys2, subs = both[:, 0], both[:, 1]
                        states, metrics = self._learn(
                            pexp.states, tr, last_value, subs, pexp.hparams)
                        params_a = jax.device_put(states.params, self._arep)
                        jax.block_until_ready(params_a)
                    pexp.keys = keys2
                    pexp.states = states
                    self._slot.publish(params_a, i + 1)

                    # PBT bookkeeping every iteration, as in the sync loop:
                    # record is a device-array append (no sync), maybe_update
                    # fires only at the barrier-predicted iterations — if it
                    # ever fires off-schedule the actor is NOT parked, so
                    # fail loudly rather than race the weight copy
                    ctrl.record(metrics.mean_reward)
                    out = ctrl.maybe_update(i, pexp.states, pexp.hparams)
                    if (out is not None) != is_exploit(b):
                        raise RuntimeError(
                            f"PBT exploit fired off the predicted barrier "
                            f"schedule at iteration {b} (window={window}, "
                            f"ready_iters={ready}) — controller state was "
                            f"mutated outside the runner")
                    if out is not None:
                        states2, hparams2, decision = out
                        with tracer.phase(sections, "pbt",
                                          span="pbt_exploit"), \
                                self._dispatch_lock:
                            # the exploit gather pins its outputs to the
                            # input (learner) shardings; the host-side
                            # explore hands back fresh uncommitted arrays
                            pexp.states = states2
                            pexp.hparams = put_global(hparams2, self._lrep)
                            params_a = jax.device_put(pexp.states.params,
                                                      self._arep)
                            jax.block_until_ready(params_a)
                        # re-publish the exploited weights under the SAME
                        # version: the parked actor then collects batch i+1
                        # with post-exploit params, exactly like the sync loop
                        self._slot.publish(params_a, i + 1)
                        exploited = [bool(x) for x in decision.exploited]
                        for p, ex in enumerate(exploited):
                            if ex:
                                self._stale_last_pm[p] = 0
                        if telemetry is not None:
                            telemetry.emit(
                                "pbt_exploit", iteration=b,
                                exploited=int(sum(exploited)),
                                src=[int(s) for s in decision.src])

                    want_log = bool(log_every) and (b % log_every == 0
                                                    or b == iterations - 1)
                    m = None
                    if want_log:
                        # ONE batched device_get for the whole [P]-metrics
                        # tuple, flattened to suffixed scalar columns + _mean
                        # (same CSV schema as the sync population loop)
                        m = {}
                        with tracer.phase(sections, "sync"), \
                                self._dispatch_lock:
                            got = jax.device_get(metrics)._asdict()
                        for k2, v in got.items():
                            vals = [float(x) for x in v]
                            m.update({f"{k2}_{p}": x
                                      for p, x in enumerate(vals)})
                            m[f"{k2}_mean"] = sum(vals) / len(vals)
                        if "rho_mean_mean" in m:
                            self._rho_last = m["rho_mean_mean"]
                            self._rho_max_seen = max(
                                self._rho_max_seen,
                                max(float(x) for x in got["rho_max"]))
                        history.append({"iteration": b, **m})
                        if logger is not None:
                            logger(b, m)
                        if gauges is not None:
                            gauges.publish(
                                queue_depth=len(self.queue),
                                staleness=self._staleness_last,
                                actor_idle_s=self._actor_idle_s,
                                learner_idle_s=self._learner_idle_s,
                                overlap_s=self.overlap.overlap_s,
                                importance_ratio_mean=self._rho_last,
                                importance_ratio_max=self._rho_max_seen)
                    if eval_fn is not None and eval_every and \
                            ((b + 1) % eval_every == 0 or b == iterations - 1):
                        with tracer.phase(sections, "eval"), \
                                self._dispatch_lock:
                            em = dict(eval_fn(b))
                        eval_history.append({"iteration": b, **em})
                        if eval_logger is not None:
                            eval_logger(b, em)
                    if is_ckpt(b):
                        with tracer.phase(sections, "ckpt"):
                            pexp.save_checkpoint(
                                ckpt, meta={"iteration": b,
                                            "async_iteration": i,
                                            "staleness_bound":
                                                self.staleness_bound})
                    if is_ckpt(b) or is_exploit(b):
                        self._complete_barrier()
                    if telemetry is not None:
                        telemetry.end_iteration(
                            b, m if want_log else None,
                            pexp.steps_per_iteration)
                    if self._failure is not None:
                        raise self._failure
        except BaseException as e:
            self._abort(e)
            actor.join(timeout=30)
            raise
        actor.join(timeout=self._stall_timeout_s)
        if actor.is_alive():
            exc = RuntimeError("actor thread failed to drain")
            self._abort(exc)
            raise exc
        if self._failure is not None:
            raise self._failure
        jax.block_until_ready(pexp.states.params)
        self._iterations_done = base + iterations
        wall = time.monotonic() - t0
        total_env_steps = iterations * pexp.steps_per_iteration
        async_info = self.async_info()
        out = {"wall_s": wall, "iterations": iterations,
               "env_steps": total_env_steps,
               "env_steps_per_sec": total_env_steps / wall,
               "final_fitness": [float(f) for f in ctrl.mean_fitness],
               "pbt_events": len(ctrl.history),
               "history": history,
               "phase_seconds": {k2: round(v, 6)
                                 for k2, v in sections.report().items()},
               "async": async_info}
        if eval_history:
            out["eval_history"] = eval_history
        if telemetry is not None:
            if gauges is not None:
                gauges.publish(queue_depth=len(self.queue),
                               staleness=self._staleness_last,
                               actor_idle_s=self._actor_idle_s,
                               learner_idle_s=self._learner_idle_s,
                               overlap_s=self.overlap.overlap_s,
                               importance_ratio_mean=self._rho_last,
                               importance_ratio_max=self._rho_max_seen)
            telemetry.run_end(
                iterations=iterations, wall_s=round(wall, 6),
                env_steps=total_env_steps,
                env_steps_per_sec=round(out["env_steps_per_sec"], 3),
                pbt_events=len(ctrl.history),
                **{f"async_{k2}": v for k2, v in async_info.items()
                   if not isinstance(v, (list, dict))})
        return out

    def async_info(self) -> dict:
        """Overlap/staleness accounting, including the per-member lag
        vectors (uniform per stacked batch; exploit resets the exploited
        members' LAST lag)."""
        snap = self.overlap.snapshot()
        return {
            "staleness_bound": self.staleness_bound,
            "queue_capacity": self.queue_capacity,
            "actor_devices": [d.id for d in self.groups.actor],
            "learner_devices": [d.id for d in self.groups.learner],
            "shared_group": self.groups.shared,
            "overlap_s": snap["overlap_s"],
            "actor_busy_s": snap.get("busy_actor_s", 0.0),
            "learner_busy_s": snap.get("busy_learner_s", 0.0),
            "actor_idle_s": round(self._actor_idle_s, 6),
            "learner_idle_s": round(self._learner_idle_s, 6),
            "staleness_max": self._staleness_max,
            "staleness_mean": (self._staleness_sum / self._consumed
                               if self._consumed else 0.0),
            "staleness_last_per_member": list(self._stale_last_pm),
            "staleness_max_per_member": list(self._stale_max_pm),
            "importance_ratio_mean": self._rho_last,
            "importance_ratio_max": self._rho_max_seen,
        }
