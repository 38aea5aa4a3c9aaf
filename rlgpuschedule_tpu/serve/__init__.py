"""Fleet-scale serving (L6): continuous batching + vmapped fleet replay.

The ROADMAP's "millions of users" entry point (PR 7): the trained
scheduler policy served as a batched inference system instead of a
one-at-a-time evaluation.

- :mod:`.engine` — :class:`InferenceEngine`: the stateless jit'd
  ``policy_step(obs_batch) -> actions``, compiled once per power-of-two
  batch bucket with donated request buffers, sharing the greedy/masked
  decision rule with ``eval.replay`` (:mod:`..decision`) and policed by
  the jsan runtime sentinels — post-warmup recompiles and implicit
  host syncs are production alarms, not silent slowdowns.
- :mod:`.batching` — the continuous-batching front end:
  :class:`PolicyServer` request queue (coalesce to the next bucket,
  pad, dispatch, scatter in FIFO order), deadline-aware adaptive
  batching + load shedding (typed :class:`DeadlineSheddedError`
  rejections, ``serve_shed_total``), and the SLO metric surface
  (p50/p99 decision latency, decisions/s/chip, queue depth, batch
  occupancy) through the ``obs`` registry.
- :mod:`.router` — multi-engine scale-out (PR 13):
  :class:`EngineRouter` resolves one engine per data-axis device of
  the unified mesh and dispatches least-loaded;
  :class:`AutoscaleAdvisor` turns the SLO gauges into a desired-engine
  count the router applies live.
- :mod:`.fleet` — vmapped fleet replay: one checkpoint vs N seeded
  simulated clusters (optionally under ``sim.faults`` regimes) in a
  single fused-scan dispatch, bit-identical to N sequential
  ``eval.replay`` runs.
- :mod:`.bench` — the ``serve --bench`` driver: deterministic request
  streams, zero-recompile steady-state assertion; ``run_chaos_soak``
  paces the fitted trace arrival process through a fleet under
  injected engine faults and reports the conservation invariant.
- :mod:`.frontend` — the network front door (PR 16, rebuilt PR 17):
  :class:`ServeFrontend`, an asyncio listener speaking keep-alive
  HTTP/1.1 *and* the length-prefixed binary frame dialect
  (:mod:`.wire`) on one port, with zero-copy request decoding, wire
  deadline propagation (503 + learned clamped ``Retry-After`` on
  shed), queue-depth connection backpressure, and graceful SIGTERM
  drain (typed :class:`ServerClosedError` for late submits — never a
  hung future).
- :mod:`.wire` — the framed transport: 24-byte prefix (magic,
  version, kind, lengths, metadata) + dtype/shape descriptor header +
  raw row bytes; ``np.frombuffer`` is the only decode.
- ``python -m rlgpuschedule_tpu.serve`` — the CLI (``--bench``,
  ``--fleet N``, ``--metrics-port`` live Prometheus scrape endpoint,
  ``--chaos-faults`` engine-fault chaos soak, ``--frontend-port``).
"""
from . import wire
from .batching import (DeadlineSheddedError, Ewma, PolicyServer, Reservoir,
                       ServeResult, ServerClosedError, next_bucket,
                       pad_batch, scatter_results, stack_requests)
from .engine import InferenceEngine
from .fleet import fleet_replay, fleet_windows, sample_fleet_faults
from .frontend import FrontendHandle, ServeFrontend, start_frontend
from .router import (SERVE_FAULT_KINDS, AutoscaleAdvisor, EngineRouter,
                     EngineStats, InjectedEngineFault, ServeFaultInjector,
                     ServeFaultSpec, parse_serve_fault)

__all__ = [
    "InferenceEngine", "PolicyServer", "Reservoir", "ServeResult",
    "DeadlineSheddedError", "ServerClosedError", "Ewma",
    "EngineRouter", "AutoscaleAdvisor", "EngineStats",
    "SERVE_FAULT_KINDS", "ServeFaultSpec", "ServeFaultInjector",
    "InjectedEngineFault", "parse_serve_fault",
    "ServeFrontend", "FrontendHandle", "start_frontend", "wire",
    "next_bucket", "pad_batch", "scatter_results", "stack_requests",
    "fleet_replay", "fleet_windows", "sample_fleet_faults",
]
