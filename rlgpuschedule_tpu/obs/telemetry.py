"""Run-loop telemetry: iteration spans + production alarms.

:class:`RunTelemetry` is what the train loops hold — one object owning
the event bus (:mod:`.events`), the counters/gauges registry
(:mod:`.metrics`), the host-side phase timer
(``utils.profiling.SectionTimer``) and, opt-in, the :class:`Alarms`.

Host-sync discipline (the whole design constraint): telemetry never
touches device values. Phase timings are host clocks; the ``iteration``
event is emitted only at logged iterations, carrying the metrics dict
the run loop ALREADY materialized through its single batched
``device_get`` — so an instrumented run performs exactly the same
host↔device syncs as a bare one (asserted in tests/test_obs.py).

:class:`Alarms` promotes PR 3's test-only sentinels to production:

- **recompile** — the process's ``CompileCounter`` (jax.monitoring
  listeners; the start-up account's, ``obs.startup``) is read around each
  dispatch; any trace/compile activity observed during a post-warmup
  dispatch emits a ``recompile`` event and bumps a counter instead of
  only failing a sanitize test. Legitimate re-traces (warmup, the
  watchdog's LR-rescale rollback) are granted amnesty via
  :meth:`Alarms.expect_recompile` and land as ``compile`` events. Both
  kinds name the programs the dispatch traced, lowered, compiled or
  loaded, with each one's seconds (``programs``).
- **transfer** — post-warmup dispatches run under
  ``jax.transfer_guard("disallow")``: an implicit host↔device transfer
  in the hot path emits a ``transfer`` event and raises
  :class:`AlarmError` (fail fast WITH telemetry — the buffer-donating
  dispatch cannot be safely retried after a mid-trace abort).
- **slow_iteration** — optionally, an iteration whose wall time exceeds
  ``slow_iter_s`` emits the event and arms a one-shot ``jax.profiler``
  trace capture of the NEXT iteration (profiling the slow iteration
  itself is impossible — it already happened).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable, Iterator, Mapping

from ..analysis.sentinels import no_implicit_transfers
from ..utils.profiling import SectionTimer
from . import startup
from .events import EventBus
from .metrics import Registry
from .trace import Tracer

PROM_SNAPSHOT = "metrics.prom"


class AlarmError(RuntimeError):
    """A production alarm that cannot be survived in place (an implicit
    transfer inside a buffer-donating dispatch)."""


class Alarms:
    """Production alarm scope. Use as a context manager spanning the run;
    wrap each jitted dispatch in :meth:`dispatch`.

    ``warmup_iters`` dispatches are exempt (the first iteration MUST
    compile); compile activity inside them is still recorded, as
    ``compile`` events, and every ``compile`` / ``recompile`` event
    carries ``programs``: ``[{fun, trace_s, lower_s, compile_s,
    cache_hit}]`` for the dispatch it belongs to (``compile_s`` is the
    backend's interval, a load where ``cache_hit``), heaviest first, so
    the post-mortem shows where compile time went and WHICH step
    recompiled. ``expect_recompile(reason)`` grants the next dispatch the
    same amnesty — the run loop calls it after a watchdog rollback, whose
    LR rescale legitimately re-traces the step.
    """

    # a dispatch's event names at most this many programs (a train step
    # traces hundreds of inner functions; its own events close LAST)
    MAX_PROGRAMS = 16

    def __init__(self, bus: EventBus, registry: Registry | None = None,
                 warmup_iters: int = 1, transfer_guard: bool = True,
                 slow_iter_s: float | None = None,
                 profile_dir: str | None = None):
        if warmup_iters < 0:
            raise ValueError(f"warmup_iters must be >= 0, got "
                             f"{warmup_iters}")
        self.bus = bus
        self.registry = registry if registry is not None else Registry()
        self.warmup_iters = warmup_iters
        self.transfer_guard = transfer_guard
        self.slow_iter_s = slow_iter_s
        self.profile_dir = profile_dir
        self._counter = None     # the compile listener, inside the scope
        self._dispatches = 0
        self._amnesty: str | None = None
        self._profile_pending = False
        self._profile_active = False
        self._profile_done = False
        self._recompiles = self.registry.counter(
            "rlsched_recompile_alarms_total",
            "post-warmup dispatches that traced or compiled")
        self._transfers = self.registry.counter(
            "rlsched_transfer_alarms_total",
            "implicit host-device transfers caught in the hot path")
        self._slow = self.registry.counter(
            "rlsched_slow_iteration_alarms_total",
            "iterations slower than the slow_iter_s threshold")

    def __enter__(self) -> "Alarms":
        # the process's one listener (the start-up account's), read by a
        # mark before and after each dispatch
        self._counter = startup.ACCOUNT.compiles
        return self

    def __exit__(self, *exc) -> None:
        self.stop_profile()
        self._counter = None

    def expect_recompile(self, reason: str) -> None:
        """Grant the NEXT dispatch compile amnesty (e.g. a rollback's LR
        rescale rebinds the optimizer and re-traces legitimately)."""
        self._amnesty = reason

    @contextlib.contextmanager
    def dispatch(self, iteration: int) -> Iterator[None]:
        """Wrap one jitted dispatch: count compile activity attributable
        to it and (post-warmup) forbid implicit transfers."""
        if self._counter is None:
            raise ValueError("Alarms.dispatch outside the context "
                             "(enter the Alarms scope first)")
        warm = self._dispatches < self.warmup_iters
        amnesty, self._amnesty = self._amnesty, None
        self._dispatches += 1
        t0 = self._counter.total
        mark = self._counter.n_events
        guard = (no_implicit_transfers()
                 if self.transfer_guard and not warm and amnesty is None
                 else contextlib.nullcontext())
        try:
            with guard:
                yield
        except Exception as e:
            msg = str(e)
            if "disallow" in msg.lower() or "transfer" in msg.lower():
                self._transfers.inc()
                self.bus.emit("transfer", iteration=iteration,
                              error=msg[:500])
                raise AlarmError(
                    f"implicit host<->device transfer in the iteration-"
                    f"{iteration} dispatch (transfer alarm): {msg}") from e
            raise
        compiles = self._counter.total - t0
        if compiles <= 0:
            return
        programs = self._programs_since(mark)
        if warm or amnesty is not None:
            self.bus.emit("compile", iteration=iteration, events=compiles,
                          warmup=warm, expected=amnesty, programs=programs)
        else:
            self._recompiles.inc()
            self.bus.emit("recompile", iteration=iteration,
                          events=compiles, programs=programs)

    def _programs_since(self, mark: int) -> list[dict]:
        """What the counter's events from ``mark`` on were spent on, by
        program, most seconds first."""
        rows = [{"fun": fun, "trace_s": round(r["trace_s"], 6),
                 "lower_s": round(r["lower_s"], 6),
                 "compile_s": round(r["compile_s"] + r["cache_load_s"], 6),
                 "cache_hit": bool(r["cache_loads"])}
                for fun, r in self._counter.programs_since(mark).items()]
        rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"]
                                  + r["compile_s"]))
        return rows[:self.MAX_PROGRAMS]

    def observe_wall(self, iteration: int, wall_s: float) -> None:
        """Slow-iteration trigger: emit the alarm and arm a one-shot
        profiler capture of the next iteration."""
        if self.slow_iter_s is None or wall_s <= self.slow_iter_s:
            return
        self._slow.inc()
        self.bus.emit("slow_iteration", iteration=iteration,
                      wall_s=round(wall_s, 6),
                      threshold_s=self.slow_iter_s)
        if self.profile_dir is not None and not self._profile_done:
            self._profile_pending = True

    def maybe_start_profile(self) -> None:
        if not self._profile_pending or self._profile_active:
            return
        import jax
        jax.profiler.start_trace(self.profile_dir)
        self._profile_pending = False
        self._profile_active = True

    def stop_profile(self, iteration: int | None = None) -> None:
        if not self._profile_active:
            return
        import jax
        jax.profiler.stop_trace()
        self._profile_active = False
        self._profile_done = True   # one capture per run
        self.bus.emit("profile_captured", iteration=iteration,
                      profile_dir=self.profile_dir)


class RunTelemetry:
    """Everything a run loop needs, in one handle.

    >>> with RunTelemetry(obs_dir, alarms=True) as tel:
    ...     exp.run(iterations=100, log_every=10, telemetry=tel)

    The loop protocol (``Experiment.run`` / ``PopulationExperiment.run``
    implement it): ``run_start`` once; per iteration ``begin_iteration``
    → ``dispatch`` around the jitted call → phase work under
    ``sections(name)`` → ``end_iteration`` (metrics dict only when the
    loop materialized one — logged iterations); ``iteration_aborted`` on
    a rollback retry; ``run_end`` once. Everything is host-side; no
    device value is ever touched here.
    """

    def __init__(self, obs_dir: str, rank: int = 0, alarms: bool = False,
                 warmup_iters: int = 1, transfer_guard: bool = True,
                 slow_iter_s: float | None = None,
                 name: str | None = None, trace: bool = False,
                 clock: Callable[[], float] = time.monotonic):
        self.obs_dir = obs_dir
        self.bus = EventBus(obs_dir, rank=rank, name=name)
        self.registry = Registry()
        self.sections = SectionTimer()
        # the span-tracing flight recorder (obs.trace): disabled, a span
        # is only the profiler's annotation — the run loops thread it
        # unconditionally, so --trace costs nothing when off
        self.tracer = Tracer(self.bus, enabled=trace)
        self._clock = clock
        self.alarms = (Alarms(self.bus, self.registry,
                              warmup_iters=warmup_iters,
                              transfer_guard=transfer_guard,
                              slow_iter_s=slow_iter_s,
                              profile_dir=os.path.join(obs_dir, "profile"))
                       if alarms else None)
        self._iterations = self.registry.counter(
            "rlsched_iterations_total", "train iterations completed")
        self._env_steps = self.registry.counter(
            "rlsched_env_steps_total", "environment steps completed")
        self._steps_per_sec = self.registry.gauge(
            "rlsched_env_steps_per_sec",
            "cumulative env-steps/sec over the run (monotonic clock)")
        self._t_run = clock()
        self._t_iter: float | None = None
        self._iter_span: Any = None
        self._last_sections: dict[str, float] = {}
        self.prom_path = os.path.join(obs_dir, PROM_SNAPSHOT)

    # -- lifecycle ---------------------------------------------------------
    def __enter__(self) -> "RunTelemetry":
        if self.alarms is not None:
            self.alarms.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self.alarms is not None:
            self.alarms.__exit__(*exc)
        self.close()

    def close(self) -> None:
        self.registry.write(self.prom_path)
        self.bus.close()

    def emit(self, kind: str, **fields: Any) -> None:
        self.bus.emit(kind, **fields)

    def run_start(self, **info: Any) -> None:
        """``startup``: the start-up account's summary as the run begins
        (import, backend, build, compile-side seconds so far)."""
        self.bus.emit("run_start", startup=startup.rounded(
            startup.ACCOUNT.summary()), **info)

    def run_end(self, **info: Any) -> None:
        self.bus.emit("run_end", phase_seconds=self._rounded_sections(),
                      **info)
        self.registry.write(self.prom_path)

    # -- per-iteration protocol -------------------------------------------
    def begin_iteration(self, iteration: int) -> None:
        self._t_iter = self._clock()
        if self.tracer.enabled:
            # the per-iteration flight-recorder span: phase spans the
            # loop opens (step/sync/eval/ckpt) nest under it
            self._iter_span = self.tracer.span("iteration",
                                               iteration=iteration)
            self._iter_span.__enter__()
        if self.alarms is not None:
            self.alarms.maybe_start_profile()

    def _close_iter_span(self) -> None:
        if self._iter_span is not None:
            self._iter_span.__exit__(None, None, None)
            self._iter_span = None

    @contextlib.contextmanager
    def dispatch(self, iteration: int) -> Iterator[None]:
        if self.alarms is None:
            yield
            return
        with self.alarms.dispatch(iteration):
            yield

    def end_iteration(self, iteration: int,
                      metrics: Mapping[str, Any] | None = None,
                      env_steps: int = 0) -> None:
        """Close the span opened by :meth:`begin_iteration`. ``metrics``
        is the ALREADY-materialized host dict of a logged iteration (or
        None between log points — no event, no sync, just bookkeeping)."""
        wall = (self._clock() - self._t_iter
                if self._t_iter is not None else 0.0)
        self._t_iter = None
        self._close_iter_span()
        self._iterations.inc()
        self._env_steps.inc(env_steps)
        dt = self._clock() - self._t_run
        if dt > 0:
            self._steps_per_sec.set(self._env_steps.value / dt)
        if self.alarms is not None:
            self.alarms.stop_profile(iteration)
            self.alarms.observe_wall(iteration, wall)
        if metrics is None:
            return
        self.bus.emit("iteration", iteration=iteration,
                      wall_s=round(wall, 6), phases=self._section_delta(),
                      steps_per_sec=round(self._steps_per_sec.value, 3),
                      metrics={k: v for k, v in metrics.items()})
        self.registry.write(self.prom_path)

    def iteration_aborted(self, iteration: int, reason: str) -> None:
        """A rollback retry abandoned this iteration: settle the span
        without an event (the watchdog emits its own ``rollback``) and
        grant the retry's re-trace amnesty."""
        self._t_iter = None
        self._close_iter_span()
        if self.alarms is not None:
            self.alarms.stop_profile(iteration)
            self.alarms.expect_recompile(reason)

    def expect_recompile(self, reason: str) -> None:
        if self.alarms is not None:
            self.alarms.expect_recompile(reason)

    # -- internals ---------------------------------------------------------
    def _rounded_sections(self) -> dict[str, float]:
        return {k: round(v, 6) for k, v in self.sections.report().items()}

    def _section_delta(self) -> dict[str, float]:
        """Per-phase seconds since the previous ``iteration`` event (the
        span breakdown), from the cumulative SectionTimer."""
        now = self.sections.report()
        delta = {k: round(v - self._last_sections.get(k, 0.0), 6)
                 for k, v in now.items()}
        self._last_sections = now
        for phase, secs in delta.items():
            self.registry.counter(
                f"rlsched_phase_{phase}_seconds_total",
                f"host wall seconds spent in the {phase} phase").inc(
                max(secs, 0.0))
        return delta


class OverlapMeter:
    """Online wall-clock overlap between two busy lanes (the async
    engine's actor and learner threads).

    Each lane opens/closes spans via :meth:`span`; the meter credits the
    intersection of concurrently-open spans to ``overlap_s``, each
    overlapping interval exactly once: when a span ENDS, it claims the
    intersection with the other lane's open span and advances that
    lane's credit frontier past the claimed interval, so the other
    lane's own end event cannot re-claim it. Thread-safe (one lock;
    span bookkeeping is O(1)) and clock-injectable for tests.

    This is the CI smoke stage's "nonzero overlap" evidence: even on a
    single core, the two threads' spans interleave around device waits,
    so a genuinely overlapped engine shows ``overlap_s > 0`` while a
    serialized one shows ~0.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._open: dict[str, float] = {}      # lane -> actual span start
        self._frontier: dict[str, float] = {}  # lane -> uncredited start
        self.busy_s: dict[str, float] = {}
        self.overlap_s = 0.0

    @contextlib.contextmanager
    def span(self, lane: str) -> Iterator[None]:
        t0 = self._clock()
        with self._lock:
            self._open[lane] = t0
            self._frontier[lane] = t0
        try:
            yield
        finally:
            t1 = self._clock()
            with self._lock:
                start = self._open.pop(lane, t1)
                self.busy_s[lane] = (self.busy_s.get(lane, 0.0)
                                     + (t1 - start))
                mine = self._frontier.pop(lane, start)
                for other in self._open:
                    lo = max(mine, self._frontier[other])
                    if t1 > lo:
                        self.overlap_s += t1 - lo
                        self._frontier[other] = t1

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            out = {f"busy_{k}_s": round(v, 6)
                   for k, v in self.busy_s.items()}
            out["overlap_s"] = round(self.overlap_s, 6)
            return out


class AsyncGauges:
    """The async engine's metric surface on a :class:`.metrics.Registry`
    (ISSUE 9 names the quartet): ``queue_depth``, ``param_staleness``,
    ``actor_idle_s``, ``learner_idle_s``, plus the overlap headline.
    Only the learner (caller) thread writes these — the actor thread
    hands its numbers over through the engine's lock-protected state, so
    the Registry never sees concurrent writers."""

    def __init__(self, registry: Registry):
        self.queue_depth = registry.gauge(
            "rlsched_async_queue_depth",
            "trajectory batches waiting in the actor->learner queue")
        self.param_staleness = registry.gauge(
            "rlsched_async_param_staleness",
            "policy-versions behind of the last consumed batch")
        self.actor_idle = registry.gauge(
            "rlsched_async_actor_idle_s",
            "cumulative seconds the actor spent blocked (staleness gate "
            "+ full-queue backpressure)")
        self.learner_idle = registry.gauge(
            "rlsched_async_learner_idle_s",
            "cumulative seconds the learner spent waiting on an empty "
            "queue")
        self.overlap = registry.gauge(
            "rlsched_async_overlap_s",
            "cumulative wall seconds actor and learner were busy "
            "simultaneously")
        self.rho_mean = registry.gauge(
            "rlsched_async_importance_ratio_mean",
            "mean unclipped importance ratio of the last logged update "
            "(1.0 = on-policy; the V-trace off-policyness monitor)")
        self.rho_max = registry.gauge(
            "rlsched_async_importance_ratio_max",
            "max unclipped importance ratio seen at any logged update "
            "this run")

    def publish(self, *, queue_depth: int, staleness: int,
                actor_idle_s: float, learner_idle_s: float,
                overlap_s: float, importance_ratio_mean: float = 1.0,
                importance_ratio_max: float = 1.0) -> None:
        self.queue_depth.set(queue_depth)
        self.param_staleness.set(staleness)
        self.actor_idle.set(round(actor_idle_s, 6))
        self.learner_idle.set(round(learner_idle_s, 6))
        self.overlap.set(round(overlap_s, 6))
        self.rho_mean.set(round(importance_ratio_mean, 6))
        self.rho_max.set(round(importance_ratio_max, 6))
