"""Unified telemetry layer (L6 aux): event bus, metrics registry,
run-loop spans, production alarms, multihost merge + post-mortem.

Podracer's core observability argument — scalable RL stacks live or die
by cheap, always-on throughput/health telemetry — applied to this
codebase's production machinery: when a multihost run restarts, rolls
back, or silently recompiles, this package is what ties *what happened*
to *when and on which rank*.

- :mod:`.events` — the structured event bus: append-only JSONL, one
  stream per rank, every event stamped ``(v, kind, rank, pid, seq,
  mono, wall)``; reader tolerates a crashed writer's torn last line;
  :func:`merge_dir` orders interleaved per-rank streams into one
  timeline by the shared monotonic clock.
- :mod:`.metrics` — counters/gauges registry with an atomic
  Prometheus-text snapshot file (``metrics.prom``) and a live stdlib
  HTTP scrape endpoint (:func:`serve_http` — what the serving CLI's
  ``--metrics-port`` exposes).
- :mod:`.slo` — declarative SLO specs evaluated as multi-window burn
  rates over cumulative SLIs (ISSUE 20), refreshed by the registry's
  pre-scrape collector hook: ``slo_burn_rate`` /
  ``slo_error_budget_remaining`` gauges (the budget recovers as the
  window slides past an incident) and edge-triggered bus alerts.
- :mod:`.telemetry` — :class:`RunTelemetry` (what ``Experiment.run`` /
  ``PopulationExperiment.run`` hold: iteration spans with a
  rollout+update/sync/eval/ckpt phase breakdown, zero added host syncs)
  and :class:`Alarms` (``CompileCounter`` + transfer-guard promoted
  from test-only sentinels to production: ``recompile``/``transfer``
  events, optional slow-iteration ``jax.profiler`` auto-capture).
- :mod:`.startup` — the start-up account (:data:`.startup.ACCOUNT`):
  always on and in memory, where the process's time went before and
  while its first iterations ran — ``import`` / ``backend`` / ``build``
  (by phase) / ``run`` spans and every program's trace, lowering,
  compile and cache-load intervals from the ONE ``CompileCounter`` it
  installs for the process's life; :meth:`~.startup.StartupAccount.summary`
  reduces it to exclusive seconds. ``train.py``'s closing summary and
  each ``run_start`` event carry it (``startup``), the report prints it,
  and the benchmark's ``setup_*_s.train`` metrics read it.
- :mod:`.report` — ``python -m rlgpuschedule_tpu.obs.report <dir>``:
  merged timeline post-mortem (start-up table, phase-time table, span
  tree, restart/rollback history, steps/s curve, alarm summary;
  ``--strict-alarms`` for CI, ``--trace-out`` for the Perfetto export).
- :mod:`.trace` — the span-tracing flight recorder: nestable,
  thread-aware :meth:`Tracer.span` extents on the same bus (track =
  ``(rank, thread)``), plus :func:`to_chrome_trace` so any run opens in
  Perfetto / ``chrome://tracing``.
- :mod:`.skew` — the cross-host clock-skew handshake: ranks stamp
  ``(wall, mono)`` offset samples; :func:`correct_events` rewrites a
  merged timeline onto one corrected monotonic axis with a residual-
  uncertainty annotation.

Event kinds by emitter:

== run loops (``experiment.py``): ``run_start`` (its ``startup`` field:
   the start-up account's summary as the run begins), ``iteration``,
   ``run_end``, ``pbt_exploit``
== tracer (any layer, ``--trace``): ``span_begin``, ``span_end``,
   ``span_point`` (the run loops' phases, and ``build`` with its
   ``build_*`` phases where ``build`` was handed the telemetry)
== alarms: ``compile`` (warmup/expected), ``recompile`` (both with
   ``programs``: ``[{fun, trace_s, lower_s, compile_s, cache_hit}]`` of
   the dispatch), ``transfer``, ``slow_iteration``, ``profile_captured``
== checkpoint: ``ckpt_save``, ``ckpt_restore``, ``ckpt_reject``,
   ``ckpt_crc_reject``, ``ckpt_elastic_restore``
== resilience: ``rollback`` (watchdog), ``fault`` (injector)
== supervisor: ``gang_launch``, ``rank_failure``, ``gang_restart``,
   ``gang_shrink``, ``supervisor_done``
== multihost worker: ``worker_start``, ``worker_resumed``,
   ``worker_step``, ``worker_done``, ``clock_skew``
== data flywheel (``flywheel/``): ``flywheel_shard_seal`` (flight-log
   writer), ``promote_blocked`` (canary gate), ``promote_apply`` (serve
   CLI promotion driver), ``promote_rollback`` (SLO watchdog) — none
   are alarm kinds, so a healthy promotion keeps ``--strict-alarms``
   green
== SLO engine (:mod:`.slo`): ``slo_burn_alert`` (every burn window of a
   spec over threshold — rising edge) and ``slo_burn_clear`` (falling
   edge, budget recovering) — deliberately not alarm kinds either:
   ``--strict-alarms`` stays a compile/transfer contract while SLO
   health alerts on its own channel
"""
from .events import (EventBus, SCHEMA_VERSION, event_streams, merge_dir,
                     merge_events, read_events)
from .metrics import (Counter, Gauge, Histogram, MetricsHTTPServer,
                      Registry, serve_http)
from .skew import (RankSkew, correct_events, learn_offsets,
                   merge_dir_corrected)
from .slo import DEFAULT_WINDOWS, SLOEngine, SLOSpec, histogram_sli
from .telemetry import AlarmError, Alarms, RunTelemetry
from .trace import (NULL_TRACER, Tracer, async_overlap_summary,
                    build_span_tree, to_chrome_trace, tracer_of)

__all__ = [
    "EventBus", "SCHEMA_VERSION", "event_streams", "merge_dir",
    "merge_events", "read_events",
    "Counter", "Gauge", "Histogram", "MetricsHTTPServer", "Registry",
    "serve_http",
    "AlarmError", "Alarms", "RunTelemetry",
    "NULL_TRACER", "Tracer", "async_overlap_summary", "build_span_tree",
    "to_chrome_trace", "tracer_of",
    "RankSkew", "correct_events", "learn_offsets", "merge_dir_corrected",
    "DEFAULT_WINDOWS", "SLOEngine", "SLOSpec", "histogram_sli",
]
