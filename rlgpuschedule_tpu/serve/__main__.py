"""Serving CLI: ``python -m rlgpuschedule_tpu.serve``.

Five modes, composable in one invocation:

- ``--bench``: drive a deterministic synthetic request stream through
  the continuous-batching policy server and report the SLO table —
  p50/p99 decision latency, decisions/s(/chip), batch occupancy, and
  the steady-state contract (zero post-warmup recompiles across
  distinct request sizes within one bucket, CompileCounter-verified).
- ``--soak SECONDS``: sustained paced load through live dispatcher
  threads (``--rate``, ``--deadline-ms`` shedding, ``--adaptive-wait``
  learned batching, ``--autoscale`` advisor loop) reporting p99 drift
  + shed rate — the ci.sh soak-lite surface.
- ``--scaleout``: decisions/s + shed rate, 1 engine vs ``--engines``
  routed engines on the same stream (honest CPU caveat included).
- ``--fleet N``: vmapped fleet replay — the checkpoint vs N seeded
  simulated clusters in one dispatch (optionally under a
  ``sim.faults`` regime), reporting fleet mean JCT / completion /
  decisions/s.
- ``--flight-log DIR`` / ``--promote``: the data flywheel (ISSUE 19) —
  record served decisions into crc-sidecar'd shards during ``--soak``
  (exactly-once: ``rows_logged == served``), then canary-gate a
  candidate checkpoint against the logged window and promote it live
  (``swap_params`` + blessed re-warm) under an SLO watchdog that rolls
  back automatically; the whole lineage lands in the promotion ledger.

``--engines N`` serves every mode through the mesh-resolved
:class:`~.router.EngineRouter` (one engine per data-axis device,
least-loaded dispatch, per-engine labeled sentinel series).

``--metrics-port`` exposes the live Prometheus scrape endpoint
(``obs.serve_http``); ``--obs-dir`` writes the event stream (blessed
``compile`` / alarm ``recompile`` events) + a ``metrics.prom``
snapshot. The JSON on stdout carries the same reproducibility tuple
``evaluate`` emits (``configs.repro_tuple``: config/seed/.../ckpt_dir/
RESOLVED ckpt_step), so serving numbers are regenerable exactly.

Examples::

    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
        --ckpt-dir out/ckpt --bench --bucket 16
    python -m rlgpuschedule_tpu.serve --config ppo-mlp-synth64 \
        --fleet 512 --fleet-regime storm --metrics-port 9090
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    from ..configs import TRUNK_NAMES
    p = argparse.ArgumentParser(
        prog="rlgpuschedule_tpu.serve",
        description="Fleet-scale policy serving: continuous-batching "
                    "bench + vmapped fleet replay.")
    p.add_argument("--config", default="ppo-mlp-synth64")
    p.add_argument("--ckpt-dir", default=None,
                   help="restore the served policy from this checkpoint "
                        "dir (omit = untrained init weights; pick the "
                        "step with select_checkpoint)")
    p.add_argument("--ckpt-step", type=int, default=None)
    # cluster-shape overrides — MUST match the training run when
    # restoring a checkpoint (same contract as evaluate)
    p.add_argument("--trace", default=None,
                   choices=["synthetic", "philly", "pai", "philly-proxy",
                            "pai-proxy"])
    p.add_argument("--trace-path", default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--n-envs", type=int, default=None)
    p.add_argument("--n-nodes", type=int, default=None)
    p.add_argument("--gpus-per-node", type=int, default=None)
    p.add_argument("--window-jobs", type=int, default=None)
    p.add_argument("--queue-len", type=int, default=None)
    p.add_argument("--horizon", type=int, default=None)
    p.add_argument("--obs-kind", default=None,
                   choices=["flat", "grid", "graph", "tokens"])
    p.add_argument("--trunk", default=None, choices=TRUNK_NAMES,
                   help="obs-kind tokens: the trunk sizes the checkpoint "
                        "was trained with (train --trunk)")
    # bench mode
    p.add_argument("--bench", action="store_true",
                   help="latency bench: deterministic request stream "
                        "through the continuous-batching server; "
                        "asserts the zero-recompile steady state")
    p.add_argument("--bucket", type=int, default=8,
                   help="largest power-of-two batch bucket the engine "
                        "compiles (bench default request sizes live in "
                        "(bucket/2, bucket])")
    p.add_argument("--rounds", type=int, default=24,
                   help="bench: coalesced dispatches to serve")
    p.add_argument("--request-sizes", default=None, metavar="A,B,...",
                   help="bench: request counts to cycle per round "
                        "(default: three distinct sizes inside the "
                        "--bucket bucket)")
    p.add_argument("--pool-steps", type=int, default=4,
                   help="bench: env decision steps used to materialize "
                        "the request pool")
    # multi-engine scale-out (PR 13)
    p.add_argument("--engines", type=int, default=1,
                   help="serve through N routed per-device engines (one "
                        "per data-axis device of the unified mesh; "
                        "least-loaded dispatch; N=1 keeps the single "
                        "engine). Refused for hierarchical configs")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request latency SLO for --soak/--scaleout "
                        "submissions; requests whose deadline cannot be "
                        "met are shed with a typed rejection "
                        "(serve_shed_total)")
    p.add_argument("--adaptive-wait", action="store_true",
                   help="learn the partial-bucket hold time from the "
                        "observed arrival rate (streaming estimator) "
                        "instead of a fixed max-wait; dispatches early "
                        "when the head-of-line deadline approaches")
    p.add_argument("--soak", type=float, default=None, metavar="SECONDS",
                   help="sustained-load soak: pace --rate requests/s "
                        "through live dispatcher threads for this long; "
                        "reports first-half vs second-half p99 drift, "
                        "shed rate, per-engine rows/recompiles")
    p.add_argument("--rate", type=float, default=None, metavar="HZ",
                   help="soak arrival rate (default 200/s)")
    p.add_argument("--autoscale", action="store_true",
                   help="with --soak: run the AutoscaleAdvisor loop "
                        "(SLO gauges -> desired engine count, applied "
                        "live by the router with hysteresis)")
    p.add_argument("--chaos-faults", default=None,
                   metavar="SPEC[,SPEC...]",
                   help="with --soak: inject engine faults mid-run "
                        "(kind@N[:engine=E], kind in engine-raise / "
                        "engine-hang / engine-slow; N = router dispatch "
                        "sequence, fires on the target engine's first "
                        "dispatch >= N). The soak paces arrivals by the "
                        "config's fitted trace arrival process and "
                        "gates on exact request conservation; needs "
                        "--engines >= 2 so the retry hedge has a "
                        "healthy engine to land on")
    p.add_argument("--frontend-port", type=int, default=None,
                   metavar="PORT",
                   help="with --soak: run the asyncio HTTP front door "
                        "on this port (0 = ephemeral) and self-check "
                        "the wire contract after the soak (200 decide, "
                        "graceful drain, typed late-submit refusal)")
    p.add_argument("--scaleout", action="store_true",
                   help="decisions/s + shed rate vs engine count: "
                        "isolated 1-engine and --engines-engine arms "
                        "serving the same stream (CPU caveat: dispatch "
                        "is serialized there)")
    # fleet mode
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="fleet replay: evaluate the checkpoint against "
                        "N seeded simulated clusters in one dispatch")
    p.add_argument("--fleet-regime", default=None, metavar="REGIME",
                   help="with --fleet: replay every cluster under this "
                        "seeded fault regime (sim.faults.FAULT_REGIMES; "
                        "flat configs)")
    p.add_argument("--fleet-seed", type=int, default=0,
                   help="with --fleet-regime: base seed of the fault "
                        "draws (cluster e draws (seed, e))")
    p.add_argument("--max-steps", type=int, default=None,
                   help="fleet: cap decision steps per cluster "
                        "(default: the env horizon)")
    # observability
    p.add_argument("--metrics-port", type=int, default=None,
                   help="expose the live Prometheus scrape endpoint on "
                        "this port (0 = ephemeral; the bound port and a "
                        "self-scrape check land in the JSON)")
    p.add_argument("--obs-dir", default=None,
                   help="emit serve events (JSONL bus) + a metrics.prom "
                        "snapshot under this directory")
    p.add_argument("--trace-spans", action="store_true",
                   help="flight recorder: record the request lifecycle "
                        "(enqueue/bucket_wait/pad/dispatch/scatter) as "
                        "nested spans on the event bus; requires "
                        "--obs-dir (spans ride the JSONL stream). NOT "
                        "--trace, which picks the workload trace source")
    # data flywheel (ISSUE 19): flight log + canary-gated promotion
    p.add_argument("--flight-log", default=None, metavar="DIR",
                   help="with --soak: record every served decision "
                        "(obs/mask/action/behavior log-prob/value/"
                        "stall/deadline outcome) into crc-sidecar'd "
                        "shards under DIR; with --promote*: the logged "
                        "window the canary replays. Recording switches "
                        "the engine to capture mode (same compiled "
                        "program, extra outputs — zero-recompile "
                        "contract intact)")
    p.add_argument("--flight-capacity", type=int, default=512,
                   help="flight log rows per sealed shard")
    p.add_argument("--durable-log", action="store_true",
                   help="fsync flight-log shards + promotion-ledger "
                        "lines on seal (power-loss durability; default "
                        "is flush-only — see obs.events for the "
                        "overhead stance)")
    p.add_argument("--promote", default=None, metavar="CKPTDIR",
                   help="canary-gated promotion: load the candidate "
                        "policy from this checkpoint dir, replay the "
                        "--flight-log window under candidate vs "
                        "incumbent through the shared decision rule, "
                        "and only swap the serving weights if the "
                        "hysteresis gate clears; post-swap SLO "
                        "watchdog rolls back automatically")
    p.add_argument("--promote-step", type=int, default=None,
                   help="candidate checkpoint step (default: latest)")
    p.add_argument("--promote-noise", type=float, default=None,
                   metavar="SIGMA",
                   help="synthesize the candidate by perturbing the "
                        "incumbent with seeded N(0, SIGMA) noise "
                        "(alone: the candidate IS the perturbed "
                        "incumbent; with --promote: noise on top of "
                        "the loaded candidate). Large SIGMA is the "
                        "ci.sh seeded-regressed candidate the gate "
                        "must block")
    p.add_argument("--promote-fault", action="store_true",
                   help="inject a post-swap SLO regression (the "
                        "watchdog's observed p99 is inflated 10x) to "
                        "prove automatic rollback restores the "
                        "incumbent bit-identically")
    p.add_argument("--canary-slices", type=int, default=8,
                   help="held-out window slices the hysteresis gate "
                        "scores")
    p.add_argument("--canary-tol", type=float, default=0.02,
                   help="per-slice agreement regression tolerance")
    p.add_argument("--canary-hysteresis", type=int, default=2,
                   help="consecutive regressed slices that block "
                        "promotion")
    return p


def main(argv: "list[str] | None" = None) -> dict:
    args = build_parser().parse_args(argv)
    from ..configs import CONFIGS, repro_tuple
    if args.config not in CONFIGS:
        sys.exit(f"unknown config {args.config!r}")
    promote_mode = (args.promote is not None
                    or args.promote_noise is not None)
    if (not args.bench and args.fleet is None and args.soak is None
            and not args.scaleout and not promote_mode):
        sys.exit("nothing to do: pass --bench, --soak S, --scaleout, "
                 "--promote/--promote-noise, and/or --fleet N")
    if args.fleet is not None and args.fleet <= 0:
        sys.exit("--fleet must be a positive cluster count")
    if args.bucket <= 0 or (args.bucket & (args.bucket - 1)):
        sys.exit("--bucket must be a positive power of two")
    if args.engines < 1:
        sys.exit("--engines must be >= 1")
    if args.scaleout and args.engines < 2:
        sys.exit("--scaleout compares 1 engine vs --engines; pass "
                 "--engines >= 2 with it")
    if args.soak is not None and args.soak <= 0:
        sys.exit("--soak must be a positive duration in seconds")
    if args.rate is not None and args.soak is None:
        sys.exit("--rate paces --soak submissions; pass --soak S with "
                 "it (refusing the silent no-op)")
    if args.rate is not None and args.rate <= 0:
        sys.exit("--rate must be positive requests/s")
    if args.autoscale and args.soak is None:
        sys.exit("--autoscale runs the advisor loop during --soak; "
                 "pass --soak S with it (refusing the silent no-op)")
    if args.autoscale and args.engines < 2:
        sys.exit("--autoscale resizes a multi-engine router; pass "
                 "--engines >= 2 with it (one engine cannot scale)")
    chaos_specs = None
    if args.chaos_faults is not None:
        if args.soak is None:
            sys.exit("--chaos-faults injects engine faults during "
                     "--soak; pass --soak S with it (refusing the "
                     "silent no-op)")
        if args.engines < 2:
            sys.exit("--chaos-faults needs --engines >= 2: the retry "
                     "hedge moves a failed dispatch to a DIFFERENT "
                     "healthy engine (one engine has nowhere to go)")
        if args.autoscale:
            sys.exit("--chaos-faults runs the chaos soak, which does "
                     "not drive the autoscale loop; drop --autoscale "
                     "(refusing the silent no-op)")
        from .router import parse_serve_fault
        try:
            chaos_specs = [parse_serve_fault(s)
                           for s in args.chaos_faults.split(",") if s]
        except ValueError as e:
            sys.exit(str(e))
        if not chaos_specs:
            sys.exit("--chaos-faults got no specs")
        bad_engine = [s for s in chaos_specs
                      if not 0 <= s.engine < args.engines]
        if bad_engine:
            sys.exit(f"--chaos-faults targets engine(s) "
                     f"{sorted({s.engine for s in bad_engine})} outside "
                     f"[0, {args.engines})")
    if args.frontend_port is not None and args.soak is None:
        sys.exit("--frontend-port runs the HTTP front door around "
                 "--soak; pass --soak S with it (refusing the silent "
                 "no-op)")
    if args.frontend_port is not None and args.frontend_port < 0:
        sys.exit("--frontend-port must be >= 0 (0 = ephemeral)")
    if args.deadline_ms is not None and args.deadline_ms <= 0:
        sys.exit("--deadline-ms must be positive")
    if (args.deadline_ms is not None and args.soak is None
            and not args.scaleout):
        sys.exit("--deadline-ms attaches SLOs to --soak/--scaleout "
                 "submissions; pass one of them (refusing the silent "
                 "no-op)")
    if args.fleet_regime is not None and args.fleet is None:
        sys.exit("--fleet-regime configures --fleet replay; pass "
                 "--fleet N with it (refusing the silent no-op)")
    sizes = None
    if args.request_sizes is not None:
        if not args.bench:
            sys.exit("--request-sizes configures --bench (refusing the "
                     "silent no-op)")
        try:
            sizes = tuple(int(s) for s in args.request_sizes.split(",")
                          if s)
        except ValueError:
            sys.exit(f"bad --request-sizes {args.request_sizes!r}")
        if not sizes or any(s <= 0 for s in sizes):
            sys.exit("--request-sizes must be positive integers")
        too_big = [s for s in sizes if s > args.bucket]
        if too_big:
            sys.exit(f"--request-sizes {too_big} exceed --bucket "
                     f"{args.bucket}")
    if args.trace_spans and not args.obs_dir:
        sys.exit("--trace-spans records spans on the event bus; pass "
                 "--obs-dir with it (refusing the silent no-op)")
    if args.flight_log is not None and args.soak is None \
            and not promote_mode:
        sys.exit("--flight-log records --soak traffic or feeds "
                 "--promote replay; pass one of them (refusing the "
                 "silent no-op)")
    if promote_mode and args.flight_log is None:
        sys.exit("promotion replays a logged window; pass "
                 "--flight-log DIR with --promote/--promote-noise")
    if args.flight_capacity <= 0:
        sys.exit("--flight-capacity must be a positive row count")
    if args.promote_step is not None and args.promote is None:
        sys.exit("--promote-step picks the --promote candidate step; "
                 "pass --promote CKPTDIR with it (refusing the silent "
                 "no-op)")
    if args.promote_noise is not None and args.promote_noise <= 0:
        sys.exit("--promote-noise must be a positive sigma")
    if args.promote_fault and not promote_mode:
        sys.exit("--promote-fault injects a post-swap SLO regression; "
                 "pass --promote/--promote-noise with it (refusing "
                 "the silent no-op)")
    if args.canary_slices < 1:
        sys.exit("--canary-slices must be >= 1")
    if args.canary_tol < 0:
        sys.exit("--canary-tol must be >= 0")
    if args.canary_hysteresis < 1:
        sys.exit("--canary-hysteresis must be >= 1")
    if args.durable_log and args.flight_log is None:
        sys.exit("--durable-log hardens the --flight-log shards and "
                 "ledger; pass --flight-log DIR with it (refusing the "
                 "silent no-op)")
    if args.fleet_regime is not None:
        from ..sim.faults import FAULT_REGIMES
        if args.fleet_regime not in FAULT_REGIMES:
            sys.exit(f"unknown --fleet-regime {args.fleet_regime!r}; "
                     f"known: {sorted(FAULT_REGIMES)}")

    cfg = CONFIGS[args.config]
    over = {k: v for k, v in
            {"trace": args.trace, "trace_path": args.trace_path,
             "seed": args.seed, "n_envs": args.n_envs,
             "n_nodes": args.n_nodes,
             "gpus_per_node": args.gpus_per_node,
             "window_jobs": args.window_jobs,
             "queue_len": args.queue_len, "horizon": args.horizon,
             "obs_kind": args.obs_kind,
             "trunk": args.trunk}.items() if v is not None}
    cfg = dataclasses.replace(cfg, **over)
    from ..configs import ModeCombinationError, validate_mode_combination
    try:
        validate_mode_combination({"router": args.engines > 1,
                                   "hier": cfg.n_pods > 1})
    except ModeCombinationError as e:
        sys.exit(str(e))

    import os

    from ..experiment import Experiment
    from ..obs import EventBus, Registry
    from ..obs.trace import NULL_TRACER, Tracer
    from ..utils.platform import enable_compile_cache
    from .batching import PolicyServer
    from .bench import (build_request_pool, run_bench, run_scaleout,
                        run_soak)
    from .engine import InferenceEngine
    from .fleet import fleet_replay, fleet_windows, sample_fleet_faults
    from .router import AutoscaleAdvisor, EngineRouter

    enable_compile_cache()
    repro = repro_tuple(cfg, ckpt_dir=args.ckpt_dir)

    exp = Experiment.build(cfg)
    if args.ckpt_dir:
        from ..checkpoint import Checkpointer
        with Checkpointer(os.path.abspath(args.ckpt_dir)) as ckpt:
            exp.restore_checkpoint(ckpt, step=args.ckpt_step)
            # resolved, not requested: the integrity fallback may
            # restore an older retained step than asked for
            repro["ckpt_step"] = ckpt.last_restored_step
        print(f"policy restored from {args.ckpt_dir} "
              f"(step {repro['ckpt_step']})", file=sys.stderr)
    else:
        print("note: no --ckpt-dir; serving untrained init weights",
              file=sys.stderr)

    registry = Registry()
    bus = None
    if args.obs_dir:
        bus = EventBus(os.path.abspath(args.obs_dir), rank=0,
                       name="serve")
    tracer = (Tracer(bus, enabled=True)
              if args.trace_spans else NULL_TRACER)
    scraper = None
    report: dict = {"repro": repro}
    try:
        if args.metrics_port is not None:
            from ..obs import serve_http
            scraper = serve_http(registry, port=args.metrics_port)
            print(f"metrics scrape endpoint: {scraper.url}",
                  file=sys.stderr)
        injector = None
        if chaos_specs is not None:
            from .router import ServeFaultInjector
            injector = ServeFaultInjector(chaos_specs, bus=bus)
        # flight-log recording and canary replay both need the engine's
        # capture outputs (behavior log-prob/value from the SAME
        # compiled decision program — never a post-hoc recompute)
        capture = args.flight_log is not None
        if args.engines > 1:
            from ..parallel.mesh import serve_devices
            avail = len(serve_devices())
            if args.engines > avail:
                sys.exit(f"--engines {args.engines} exceeds the "
                         f"{avail} data-axis device(s) of the unified "
                         f"mesh (one engine per device)")
            engine = EngineRouter(exp.apply_fn, exp.train_state.params,
                                  exp.env_params, max_bucket=args.bucket,
                                  registry=registry, bus=bus,
                                  tracer=tracer, n_engines=args.engines,
                                  fault_injector=injector,
                                  capture=capture)
            print(f"engine router: {args.engines} engines on "
                  f"{[str(e.device) for e in engine.engines]}"
                  + (" (CPU: dispatch serialized)"
                     if engine.serialized_dispatch() else ""),
                  file=sys.stderr)
        else:
            engine = InferenceEngine(exp.apply_fn,
                                     exp.train_state.params,
                                     exp.env_params,
                                     max_bucket=args.bucket,
                                     registry=registry, bus=bus,
                                     tracer=tracer, capture=capture)
        pool = None
        if (args.bench or args.soak is not None or args.scaleout
                or promote_mode):
            pool = build_request_pool(exp.apply_fn,
                                      exp.train_state.params,
                                      exp.env_params, exp.traces,
                                      steps=args.pool_steps,
                                      faults=exp.faults)
        flight_writer = None
        if args.flight_log is not None and args.soak is not None:
            from ..flywheel import FlightLogWriter
            flight_writer = FlightLogWriter(
                os.path.abspath(args.flight_log),
                capacity=args.flight_capacity,
                policy_step=int(exp.train_state.step),
                registry=registry, bus=bus,
                durable=args.durable_log)
        deadline_s = (args.deadline_ms / 1e3
                      if args.deadline_ms is not None else None)
        if args.bench:
            server = PolicyServer(engine, registry=registry,
                                  tracer=tracer, bus=bus,
                                  adaptive_wait=args.adaptive_wait)
            report["bench"] = run_bench(engine, server, pool,
                                        rounds=args.rounds,
                                        request_sizes=sizes)
            b = report["bench"]
            print(f"bench: {b['requests']} decisions over "
                  f"{b['rounds']} dispatches (sizes "
                  f"{b['request_sizes']} -> buckets {b['buckets']}), "
                  f"p50 {b['latency_p50_ms']:.2f} ms, "
                  f"p99 {b['latency_p99_ms']:.2f} ms, "
                  f"{b['decisions_per_s']:.0f} decisions/s "
                  f"({b['decisions_per_s_per_chip']:.0f}/chip), "
                  f"post-warmup recompiles: "
                  f"{b['post_warmup_recompiles']}", file=sys.stderr)
        if args.soak is not None:
            obs0, mask0 = pool[0]
            engine.warmup(obs0, mask0)   # every bucket pre-paid
            server = PolicyServer(engine, registry=registry,
                                  tracer=tracer, bus=bus,
                                  adaptive_wait=args.adaptive_wait,
                                  flight_log=flight_writer)
            advisor = None
            if args.autoscale:
                advisor = AutoscaleAdvisor(registry,
                                           n_max=args.engines,
                                           initial=args.engines)
            router = engine if args.engines > 1 else None
            server.start(dispatchers=args.engines)
            fe_handle = None
            try:
                if args.frontend_port is not None:
                    from .frontend import start_frontend
                    fe_handle = start_frontend(server, obs0, mask0,
                                               port=args.frontend_port)
                    fe_handle.install_sigterm()
                    print(f"http front door: {fe_handle.url} "
                          f"(SIGTERM drains gracefully)",
                          file=sys.stderr)
                if injector is not None:
                    from ..traces.fit import domain_fit
                    from .bench import run_chaos_soak
                    soak = run_chaos_soak(
                        server, pool, fit=domain_fit(cfg),
                        duration_s=args.soak,
                        rate_hz=(args.rate if args.rate is not None
                                 else 150.0),
                        deadline_s=deadline_s, router=router,
                        seed=cfg.seed)
                else:
                    soak = run_soak(
                        server, pool, duration_s=args.soak,
                        rate_hz=(args.rate if args.rate is not None
                                 else 200.0),
                        deadline_s=deadline_s, router=router,
                        advisor=(advisor if router is not None
                                 else None))
                if fe_handle is not None:
                    report["frontend"] = _frontend_selfcheck(
                        fe_handle, obs0, mask0)
            finally:
                if fe_handle is not None:
                    fe_handle.close()   # drain: also closes the server
                else:
                    server.stop()
            # no manual slo_snapshot() here: the registry collector
            # hook refreshes the gauges at every collect/render — the
            # metrics.prom write below scrapes fresh values (ISSUE 20)
            soak["post_warmup_recompiles"] = \
                engine.post_warmup_recompiles
            report["soak"] = soak
            if flight_writer is not None:
                flight_writer.close()   # seal the tail shard
                # exactly-once accounting: every dispatched row was
                # logged, every shed row was not (shed requests never
                # reach the engine, so they never reach the log)
                # the frontend selfcheck (if it ran) served one more
                # request through the same server after the soak loop
                fe_rows = (1 if report.get("frontend", {})
                           .get("decide_status") == 200 else 0)
                fl = {"dir": os.path.abspath(args.flight_log),
                      "rows_logged": flight_writer.rows_logged,
                      "served": soak["served"] + fe_rows,
                      "conservation_ok":
                          flight_writer.rows_logged
                          == soak["served"] + fe_rows}
                report["flight_log"] = fl
                print(f"flight log: {fl['rows_logged']} rows sealed "
                      f"under {fl['dir']}, conservation "
                      + ("ok" if fl["conservation_ok"] else "VIOLATED"),
                      file=sys.stderr)
            drift = soak["p99_drift"]
            print(f"soak: {soak['requests']} requests over "
                  f"{soak['duration_s']:.1f}s at {soak['rate_hz']:.0f}/s"
                  f", shed {soak['shed']} "
                  f"({soak['shed_rate']:.1%}), p99 "
                  f"{soak['p99_first_half_ms']} -> "
                  f"{soak['p99_second_half_ms']} ms (drift "
                  + (f"{drift:.2f}x" if drift is not None else "n/a")
                  + f"), post-warmup recompiles: "
                  f"{soak['post_warmup_recompiles']}", file=sys.stderr)
            if injector is not None:
                fs = soak["fault_stats"]
                fired = sum(s.fired for s in chaos_specs)
                soak["chaos_faults"] = args.chaos_faults
                soak["faults_fired"] = int(fired)
                conserved = (soak["conservation_ok"]
                             and soak["failed"] == 0)
                print(f"chaos: {fired}/{len(chaos_specs)} faults fired, "
                      f"engine failures {fs['failures']}, ejections "
                      f"{fs['ejections']}, readmissions "
                      f"{fs['readmissions']}, retry hedges "
                      f"{fs['retry_hedges']}, conservation "
                      + ("ok" if conserved else "VIOLATED"),
                      file=sys.stderr)
        if promote_mode:
            report["promote"] = _run_promotion(
                args, cfg, exp, engine, pool, registry, bus,
                warmed=args.soak is not None)
        if args.scaleout:
            report["scaleout"] = run_scaleout(
                exp.apply_fn, exp.train_state.params, exp.env_params,
                pool, max_bucket=args.bucket, rounds=args.rounds,
                request_sizes=sizes,
                engine_counts=(1, args.engines),
                deadline_s=deadline_s)
            for arm in report["scaleout"]["arms"]:
                print(f"scaleout[{arm['engines']} engine(s)]: "
                      f"{arm['decisions_per_s']:.0f} decisions/s, "
                      f"shed {arm['shed_rate']:.1%}, rows/engine "
                      f"{arm['per_engine_rows']}, recompiles "
                      f"{arm['per_engine_recompiles']}",
                      file=sys.stderr)
        if args.fleet is not None:
            windows, traces = fleet_windows(cfg, args.fleet,
                                            source=exp.source)
            faults = None
            if args.fleet_regime is not None:
                faults = sample_fleet_faults(
                    cfg.n_nodes, args.fleet_regime, args.fleet_seed,
                    args.fleet, windows)
            fl = fleet_replay(exp.apply_fn, exp.train_state.params,
                              exp.env_params, traces, faults=faults,
                              max_steps=args.max_steps)
            fl["regime"] = args.fleet_regime
            fl["fleet_seed"] = (args.fleet_seed
                                if args.fleet_regime else None)
            registry.gauge("serve_fleet_mean_jct",
                           "fleet replay pooled mean JCT").set(
                fl["mean_jct"])
            registry.gauge("serve_fleet_completion",
                           "fleet replay completed fraction").set(
                fl["completion"])
            registry.gauge("serve_fleet_decisions_per_s",
                           "fleet replay decision throughput").set(
                fl["decisions_per_s"])
            report["fleet"] = fl
            print(f"fleet: {fl['n_clusters']} clusters"
                  + (f" under {args.fleet_regime!r} faults"
                     if args.fleet_regime else "")
                  + f", mean JCT {fl['mean_jct']:.1f} s, completion "
                  f"{fl['completion']:.1%}, {fl['decisions']} decisions "
                  f"in {fl['wall_s']:.2f} s "
                  f"({fl['decisions_per_s']:.0f}/s)", file=sys.stderr)
        if scraper is not None:
            report["scrape"] = _self_scrape(scraper)
        if args.obs_dir:
            registry.write(os.path.join(os.path.abspath(args.obs_dir),
                                        "metrics.prom"))
    finally:
        if scraper is not None:
            scraper.close()
        if bus is not None:
            bus.close()
    print(json.dumps(report))
    return report


def _swap_weights(engine, params) -> "tuple[int, ...]":
    """Live swap + blessed re-warm through whichever serving surface is
    up: the router swaps every engine under its device lock; a single
    engine swaps in place. Both re-drive the warmed buckets so a shape/
    dtype drift surfaces HERE as a recompile alarm, not on live traffic."""
    if hasattr(engine, "swap_params"):
        return engine.swap_params(params)
    engine.set_params(params)
    return engine.rewarm()


def _run_promotion(args, cfg, exp, engine, pool, registry, bus,
                   warmed: bool) -> dict:
    """``serve --promote``: canary-gate the candidate on the logged
    window, swap only if the gate clears, then watch the post-swap SLOs
    and roll back automatically on regression.

    The candidate comes from ``--promote CKPTDIR`` (a real checkpoint,
    e.g. the continual retrain's output) and/or ``--promote-noise``
    (seeded perturbation — the ci.sh regressed-candidate arm).
    ``--promote-fault`` inflates the watchdog's observed p99 10x after
    the swap: an injected SLO regression exercising the rollback path
    end-to-end (the rollback itself is real — weights swap back and the
    probe must match the pre-promotion decisions bit-identically)."""
    import os
    import time

    import jax
    import numpy as np

    from ..flywheel import (PromotionLedger, SLOWatchdog, read_flight_log,
                            run_canary, unflatten_like)

    flight_dir = os.path.abspath(args.flight_log)
    data = read_flight_log(flight_dir)
    if not data.shards:
        sys.exit(f"--promote: no verified flight-log shards under "
                 f"{flight_dir}"
                 + (f" (torn tail: {data.torn_reason})"
                    if data.torn_tail else ""))
    window = data.concat()
    obs0, mask0 = pool[0]
    incumbent = exp.train_state.params

    candidate = incumbent
    source = "incumbent"
    if args.promote is not None:
        from ..checkpoint import Checkpointer
        with Checkpointer(os.path.abspath(args.promote)) as cckpt:
            cand_state, _, _, _ = cckpt.restore(
                exp.train_state, step=args.promote_step)
            source = (f"{os.path.abspath(args.promote)}"
                      f"@{cckpt.last_restored_step}")
        candidate = cand_state.params
    if args.promote_noise is not None:
        rng = np.random.default_rng(cfg.seed)
        candidate = jax.tree.map(
            lambda l: (np.asarray(l) + rng.normal(
                0.0, args.promote_noise, np.shape(l)
            ).astype(np.asarray(l).dtype))
            if np.issubdtype(np.asarray(l).dtype, np.floating) else l,
            candidate)
        source += f"+noise(sigma={args.promote_noise:g},seed={cfg.seed})"

    rep = run_canary(exp.apply_fn, incumbent, candidate, window,
                     obs0, mask0, env_params=exp.env_params,
                     slices=args.canary_slices, tol=args.canary_tol,
                     hysteresis=args.canary_hysteresis,
                     registry=registry, bus=bus)
    ledger = PromotionLedger(flight_dir, durable=args.durable_log)
    lineage = {"candidate": source,
               "incumbent_step": int(exp.train_state.step),
               "window_rows": window.rows,
               "verdict": rep.verdict,
               "incumbent_agreement": rep.incumbent_agreement,
               "candidate_agreement": rep.candidate_agreement}
    out = {"candidate": source, "verdict": rep.verdict,
           "canary": rep.to_json(), "promoted": False,
           "rollback": False, "ledger_entries": 1}
    if rep.verdict != "promote":
        ledger.append(dict(lineage, action="blocked",
                           regress_streak=rep.max_regress_streak))
        print(f"promotion BLOCKED: candidate agreement "
              f"{rep.candidate_agreement:.3f} vs incumbent "
              f"{rep.incumbent_agreement:.3f} on the logged window "
              f"(regressed streak {rep.max_regress_streak} >= "
              f"{args.canary_hysteresis})", file=sys.stderr)
        return out

    # gate cleared: pre-promotion probe -> swap -> watchdog
    if not warmed:
        engine.warmup(obs0, mask0)
    k = min(args.bucket, window.rows)
    probe_obs = unflatten_like(obs0, [l[:k] for l in window.obs_leaves])
    probe_mask = unflatten_like(mask0,
                                [l[:k] for l in window.mask_leaves])
    probe_stall = window.stall[:k]

    def probe() -> "tuple[list, float]":
        t0 = time.perf_counter()
        dec, _ = engine.decide(probe_obs, probe_mask, probe_stall)
        # capture triple: [0] is the action tree (promote mode always
        # serves a capture engine — --flight-log is required)
        acts = [np.asarray(a) for a in jax.tree.leaves(
            jax.device_get(dec[0]))]
        return acts, (time.perf_counter() - t0) * 1e3

    g_p99 = registry.gauge("serve_decision_latency_p99_ms")
    wd = SLOWatchdog(registry, engine=engine, breach_after=2, bus=bus)
    pre_acts: list = []
    for _ in range(4):
        pre_acts, ms = probe()
        g_p99.set(ms)
        wd.sample_baseline()
    recomp_before = int(engine.post_warmup_recompiles)
    driven = _swap_weights(engine, candidate)
    wd.arm()
    swap_recompiles = int(engine.post_warmup_recompiles) - recomp_before
    if bus is not None:
        bus.emit("promote_apply", candidate=source,
                 rewarmed_buckets=list(driven),
                 swap_recompiles=swap_recompiles)
    ledger.append(dict(lineage, action="promote",
                       rewarmed_buckets=list(driven),
                       swap_recompiles=swap_recompiles))
    out.update(promoted=True, rewarmed_buckets=list(driven),
               swap_recompiles=swap_recompiles, ledger_entries=2)
    print(f"promoted {source}: canary agreement "
          f"{rep.candidate_agreement:.3f}, re-warmed buckets "
          f"{tuple(driven)}, swap recompiles {swap_recompiles}",
          file=sys.stderr)

    ticks, breach = [], None
    for _ in range(max(3, args.canary_hysteresis + 1)):
        _, ms = probe()
        if args.promote_fault:
            ms *= 10.0        # injected post-swap SLO regression
        g_p99.set(ms)
        tick = wd.observe()
        ticks.append({k_: tick[k_] for k_ in
                      ("rollback", "reasons", "streak", "p99_ms",
                       "baseline_p99_ms")})
        if tick["rollback"]:
            breach = tick
            break
    out["watchdog_ticks"] = ticks
    if breach is not None:
        _swap_weights(engine, incumbent)
        post_acts, _ = probe()
        bit = (len(pre_acts) == len(post_acts)
               and all(np.array_equal(a, b)
                       for a, b in zip(pre_acts, post_acts)))
        ledger.append(dict(lineage, action="rollback",
                           reasons=breach["reasons"],
                           bit_identical=bool(bit)))
        out.update(rollback=True, rollback_reasons=breach["reasons"],
                   probe_bit_identical=bool(bit), ledger_entries=3)
        print(f"ROLLBACK: {breach['reasons']}; incumbent restored, "
              f"probe decisions bit-identical: {bit}", file=sys.stderr)
    out["post_warmup_recompiles"] = int(engine.post_warmup_recompiles)
    return out


def _frontend_selfcheck(handle, obs0, mask0) -> dict:
    """Prove the wire contract on the live front door: one real POST
    decide must answer 200 with an action (no deadline attached — a
    cold or loaded server still serves), then a graceful drain, after
    which a late submit gets the typed :class:`ServerClosedError` (the
    never-a-hung-future half of the drain contract) and new connections
    are refused outright."""
    import urllib.error
    import urllib.request

    import numpy as np

    from .batching import ServerClosedError

    body = (np.ascontiguousarray(obs0).tobytes()
            + np.ascontiguousarray(mask0).tobytes())
    req = urllib.request.Request(handle.url + "/v1/decide", data=body,
                                 method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        decide_status = resp.status
        payload = json.loads(resp.read().decode())
    handle.drain()
    try:
        handle.frontend.server.submit(obs0, mask0)
        late_submit = "accepted"          # contract violation
    except ServerClosedError:
        late_submit = "server-closed"
    try:
        urllib.request.urlopen(
            urllib.request.Request(handle.url + "/v1/decide", data=body,
                                   method="POST"), timeout=5)
        post_drain_connect = "accepted"   # contract violation
    except (urllib.error.URLError, ConnectionError):
        post_drain_connect = "refused"
    return {"url": handle.url, "port": handle.port,
            "decide_status": decide_status,
            "decide_has_action": "action" in payload,
            "drained": True, "late_submit": late_submit,
            "post_drain_connect": post_drain_connect}


def _self_scrape(scraper) -> dict:
    """GET the live endpoint once and validate the exposition is
    well-formed — the smoke proof that a fleet scraper would accept it."""
    import urllib.request
    with urllib.request.urlopen(scraper.url, timeout=10) as resp:
        body = resp.read().decode("utf-8")
        status = resp.status
        ctype = resp.headers.get("Content-Type", "")
    lines = [ln for ln in body.splitlines() if ln]
    sample_lines = [ln for ln in lines if not ln.startswith("#")]
    well_formed = (
        status == 200 and ctype.startswith("text/plain")
        and all(ln.startswith(("# HELP ", "# TYPE "))
                or len(ln.split()) == 2 for ln in lines)
        and any(ln.startswith("serve_") for ln in sample_lines))
    return {"url": scraper.url, "port": scraper.port, "status": status,
            "content_type": ctype, "metric_lines": len(sample_lines),
            "well_formed": bool(well_formed)}


if __name__ == "__main__":
    main()
