"""Evaluation CLI (L6): ``python -m rlgpuschedule_tpu.evaluate``.

Capability parity: SURVEY.md §3.4 — "run trained policy (or baseline) over
full trace, report JCT table" (the eval/replay script of §2 "Eval / trace
replay"). Loads a config (+ optional checkpoint), replays the trace windows
under the greedy policy and the oracle baselines, and prints the avg-JCT
comparison table — north-star metric #2's harness.

Examples::

    python -m rlgpuschedule_tpu.evaluate --config ppo-mlp-synth64
    python -m rlgpuschedule_tpu.evaluate --config ppo-cnn-philly512 \
        --trace-path philly.csv --ckpt-dir out/ckpt
    python -m rlgpuschedule_tpu.evaluate --config ppo-mlp-synth64 \
        --baselines-only
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys

# tail-latency columns --percentiles adds (keep the flag's help in sync)
PERCENTILES = (50, 90, 99)


def build_parser() -> argparse.ArgumentParser:
    from .configs import TRUNK_NAMES
    p = argparse.ArgumentParser(
        prog="rlgpuschedule_tpu.evaluate",
        description="JCT evaluation: trained policy vs baseline schedulers.")
    p.add_argument("--config", default="ppo-mlp-synth64")
    p.add_argument("--trace", default=None,
                   choices=["synthetic", "philly", "pai", "philly-proxy",
                            "pai-proxy"],
                   help="trace source override (same contract as train)")
    p.add_argument("--trace-path", default=None)
    p.add_argument("--trace-load", type=float, default=None,
                   help="proxy traces: offered-load target of the "
                        "EVALUATION stream (a replay-time knob, not part "
                        "of the checkpointed policy — e.g. evaluate a "
                        "load-1.1-trained policy on a load-1.6 overload "
                        "stream)")
    p.add_argument("--source-jobs", type=int, default=None,
                   help="generated traces: pin the evaluation source "
                        "trace size in jobs (e.g. a 100k-job held-out "
                        "stream for --full-trace)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--n-envs", type=int, default=None)
    # cluster-shape overrides — MUST match the training run when restoring
    # a checkpoint (shapes are part of the saved state)
    p.add_argument("--n-nodes", type=int, default=None)
    p.add_argument("--gpus-per-node", type=int, default=None)
    p.add_argument("--window-jobs", type=int, default=None)
    p.add_argument("--queue-len", type=int, default=None)
    p.add_argument("--horizon", type=int, default=None)
    p.add_argument("--obs-kind", default=None,
                   choices=["flat", "grid", "graph", "tokens"],
                   help="must match the training run when restoring a "
                        "checkpoint (same contract as the cluster-shape "
                        "overrides)")
    p.add_argument("--trunk", default=None, choices=TRUNK_NAMES,
                   help="obs-kind tokens: the trunk sizes the checkpoint "
                        "was trained with (train --trunk)")
    p.add_argument("--drain-frac", type=float, default=None,
                   help="evaluate on backlog-drain copies of this fraction "
                        "of the windows (all jobs at t=0) — the regime the "
                        "drain curriculum trains on; use 1.0 to reproduce "
                        "the BASELINE.md drain tables")
    p.add_argument("--faults", default=None, metavar="REGIME",
                   help="config override matching a --faults TRAINING run "
                        "(the health channel is part of the checkpointed "
                        "observation space — same contract as the "
                        "cluster-shape overrides). Evaluation itself "
                        "stays clean unless --chaos is passed")
    p.add_argument("--domains", default=None, metavar="REGIME",
                   help="config override matching a --domains TRAINING "
                        "run (the geometry/health channels are part of "
                        "the checkpointed observation space — same "
                        "contract as --faults). Evaluation itself stays "
                        "on the fixed cluster unless --matrix is passed")
    p.add_argument("--matrix", action="store_true",
                   help="generalization matrix: replay the policy (plus "
                        "any --matrix-ckpt rows) AND the oracle "
                        "baselines under identical seeded DOMAIN draws "
                        "— randomized geometry, heterogeneous speeds, "
                        "arrival regimes up to 1.6× overload — and "
                        "report per-cell avg JCT, completion, and "
                        "DEGRADATION vs the fixed-cluster control — "
                        "flat configs")
    p.add_argument("--matrix-regimes", default=None, metavar="A,B,...",
                   help="with --matrix: comma-separated eval-regime "
                        "subset (domains.DOMAIN_REGIMES); the "
                        "fixed-cluster 'none' control is always included")
    p.add_argument("--matrix-baselines", default="sjf,tiresias",
                   metavar="A,B,...",
                   help="with --matrix: baseline scheduler rows next to "
                        "the policy (sim.schedulers.BASELINES)")
    p.add_argument("--matrix-seed", type=int, default=0,
                   help="with --matrix: base seed of the domain draws "
                        "and generated windows (env e draws (seed, e)); "
                        "recorded in the JSON repro tuple")
    p.add_argument("--matrix-ckpt", action="append", default=None,
                   metavar="REGIME=DIR",
                   help="with --matrix: add a policy row restored from "
                        "DIR, trained under --domains REGIME (use "
                        "'clean' for a checkpoint trained without "
                        "domains). Repeatable — the train-regime × "
                        "eval-regime cross table. Cluster shape must "
                        "match the --config")
    p.add_argument("--alarms", action="store_true",
                   help="with --matrix --obs-dir: production alarm scope "
                        "over the jitted matrix cells — a post-warmup "
                        "recompile or implicit transfer becomes an alarm "
                        "event (obs.report --strict-alarms gates on "
                        "them); the zero-retrace-across-domains contract, "
                        "enforced in CI")
    p.add_argument("--stitch-faults", default=None, metavar="REGIME",
                   help="with --full-trace: run the WHOLE stitched table "
                        "(policy rows and baselines) under one seeded "
                        "global-time fault schedule of this regime "
                        "(sim.faults.FAULT_REGIMES)")
    p.add_argument("--stitch-domain", default=None, metavar="REGIME",
                   help="with --full-trace: run the whole stitched table "
                        "on one seeded domain draw of this regime "
                        "(domains.DOMAIN_REGIMES) — heterogeneous "
                        "speeds / shrunken geometry; composes with "
                        "--stitch-faults (worst slowdown wins per node)")
    p.add_argument("--stitch-seed", type=int, default=0,
                   help="with --stitch-faults/--stitch-domain: seed of "
                        "the schedule draw; recorded in the repro tuple")
    p.add_argument("--chaos", action="store_true",
                   help="chaos evaluation matrix: replay the policy AND "
                        "the oracle baselines under identical seeded "
                        "fault schedules across regimes (none/sporadic "
                        "drains/drain storms/stragglers) and report "
                        "per-regime avg JCT, completion, and DEGRADATION "
                        "vs the clean regime — flat configs")
    p.add_argument("--chaos-regimes", default=None, metavar="A,B,...",
                   help="with --chaos: comma-separated regime subset "
                        "(sim.faults.FAULT_REGIMES); the clean 'none' "
                        "control is always included")
    p.add_argument("--chaos-baselines", default="sjf,tiresias",
                   metavar="A,B,...",
                   help="with --chaos: baseline scheduler columns next "
                        "to the policy (sim.schedulers.BASELINES)")
    p.add_argument("--chaos-seed", type=int, default=0,
                   help="with --chaos: base seed of the fault-schedule "
                        "draws (env e draws (seed, e)); recorded in the "
                        "JSON repro tuple")
    p.add_argument("--obs-dir", default=None,
                   help="with --chaos/--matrix: emit per-cell events "
                        "(env_fault / domain_cell, JSONL event bus) and "
                        "chaos_*/matrix_* gauges (metrics.prom) under "
                        "this directory so obs.report can tell the "
                        "story")
    p.add_argument("--trace-spans", action="store_true",
                   help="with --chaos --obs-dir: flight recorder — "
                        "record each regime row as nested "
                        "chaos_regime/policy_replay/baseline spans on "
                        "the event bus (export via obs.report "
                        "--trace-out). NOT --trace, which would be the "
                        "workload trace source")
    p.add_argument("--ckpt-dir", default=None,
                   help="restore the trained policy from this checkpoint "
                        "dir (omit = untrained init weights)")
    p.add_argument("--ckpt-step", type=int, default=None)
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument("--eval-windows", type=int, default=None,
                   help="evaluate on this many windows instead of --n-envs. "
                        "--n-envs must still match the TRAINING run (the "
                        "checkpoint's rollout carry restores into it), but "
                        "the replay itself has no batch-size constraint — "
                        "use a small value to evaluate a large-batch TPU "
                        "checkpoint on a CPU host")
    p.add_argument("--percentiles", action="store_true",
                   help="add p50/p90/p99 JCT tail-latency columns per "
                        "scheduler to the table (flat configs)")
    p.add_argument("--pbt", action="store_true",
                   help="evaluate a PBT population checkpoint (config 5): "
                        "restores the population from --ckpt-dir and "
                        "replays one member")
    p.add_argument("--n-pop", type=int, default=4,
                   help="with --pbt: population size of the training run")
    p.add_argument("--member", type=int, default=None,
                   help="with --pbt: member index to evaluate (default: "
                        "fittest by the controller's windowed fitness)")
    p.add_argument("--baselines-only", action="store_true")
    p.add_argument("--no-random", action="store_true",
                   help="skip the random-policy column")
    p.add_argument("--fairness", action="store_true",
                   help="multi-tenant fairness table: per-tenant avg JCT "
                        "+ Jain index, policy vs baselines (config 3)")
    p.add_argument("--full-trace", action="store_true",
                   help="evaluate over the ENTIRE source trace: policy via "
                        "sequential windowed replay with residual carry, "
                        "baselines via the native engine on the same trace")
    p.add_argument("--max-jobs", type=int, default=None,
                   help="with --full-trace: cap the source trace at the "
                        "first N jobs")
    p.add_argument("--stitch-window-jobs", type=int, default=None,
                   help="with --full-trace: stitch-replay through a "
                        "job-table of this size instead of the training "
                        "window_jobs — the policy nets are max_jobs-"
                        "independent, so a deeper stitch window widens "
                        "the backlog held between seams")
    p.add_argument("--stitch-drain-jobs", type=int, default=1,
                   help="with --full-trace: in deep-backlog mode, free "
                        "this many job-table rows per stitched window "
                        "instead of 1 before ingesting fresh jobs. The "
                        "default reproduces the recorded tables exactly "
                        "but makes window count linear in the backlog "
                        "excess — set ~max_jobs/8 for sustained-overload "
                        "streams of 10^5 jobs (fewer seams, same carry "
                        "approximation)")
    p.add_argument("--backlog-gate", type=int, default=0,
                   help="evaluate the backlog-gated HYBRID scheduler: "
                        "when fewer than N jobs are pending, play FIFO "
                        "(place the oldest job if it fits) instead of "
                        "the policy. A drain-trained policy adds "
                        "ordering delay on underloaded streams where "
                        "placing immediately is optimal (measured, "
                        "BASELINE.md config 4); the gate recovers the "
                        "FIFO tie there and keeps the learned policy "
                        "where backlogs are deep. Flat configs, policy "
                        "row only")
    p.add_argument("--stall-guard", dest="stall_guard", default=True,
                   action="store_true",
                   help="break eval-time place<->preempt argmax cycles by "
                        "masking preempt actions after the legitimate "
                        "zero-dt activity bound (preemptive configs; "
                        "default ON — the measured config-1p drain "
                        "deadlock, BASELINE.md)")
    p.add_argument("--no-stall-guard", dest="stall_guard",
                   action="store_false",
                   help="disable the guard (A/B the raw argmax replay; a "
                        "preemptive policy may then deadlock at <100% "
                        "completion — the completion guard will flag it)")
    return p


def main(argv: list[str] | None = None) -> dict:
    args = build_parser().parse_args(argv)
    from .configs import CONFIGS
    if args.config not in CONFIGS:
        sys.exit(f"unknown config {args.config!r}")
    cfg = CONFIGS[args.config]
    over = {k: v for k, v in
            {"trace": args.trace, "trace_path": args.trace_path,
             "trace_load": args.trace_load, "seed": args.seed,
             "source_jobs": args.source_jobs,
             "n_envs": args.n_envs, "n_nodes": args.n_nodes,
             "gpus_per_node": args.gpus_per_node,
             "window_jobs": args.window_jobs, "queue_len": args.queue_len,
             "horizon": args.horizon, "obs_kind": args.obs_kind,
             "trunk": args.trunk, "drain_frac": args.drain_frac,
             "faults": args.faults,
             "domains": args.domains}.items() if v is not None}
    cfg = dataclasses.replace(cfg, **over)

    from .configs import ModeCombinationError, validate_mode_combination
    try:
        validate_mode_combination({
            "pbt": args.pbt,
            "faults": args.faults is not None,
            "domains": args.domains is not None,
        })
    except ModeCombinationError as e:
        sys.exit(str(e))

    if args.source_jobs is not None:
        if args.source_jobs <= 0:
            sys.exit("--source-jobs must be positive")
        if cfg.trace in ("philly", "pai"):
            sys.exit("--source-jobs sizes GENERATED traces; a CSV trace "
                     "is its file's own size (refusing the silent no-op)")

    from .eval import (baseline_jct_table, fairness_report, format_fairness,
                       format_report, full_trace_report, jct_report)
    from .experiment import Experiment, build_stack
    from .utils.platform import enable_compile_cache

    enable_compile_cache()

    if args.chaos:
        if (args.pbt or args.fairness or args.full_trace
                or args.baselines_only or args.percentiles
                or args.backlog_gate or cfg.n_pods > 1):
            sys.exit("--chaos is its own regime × scheduler matrix over "
                     "the window batch (flat configs): no --pbt/"
                     "--fairness/--full-trace/--baselines-only/"
                     "--percentiles/--backlog-gate")
        if args.eval_windows is not None:
            sys.exit("--chaos replays the experiment's window batch; "
                     "size it with --n-envs")
        from .sim.faults import FAULT_REGIMES
        from .sim.schedulers import BASELINES
        regimes = (tuple(s for s in args.chaos_regimes.split(",") if s)
                   if args.chaos_regimes else None)
        chaos_baselines = tuple(
            s for s in args.chaos_baselines.split(",") if s)
        bad = [r for r in (regimes or ()) if r not in FAULT_REGIMES]
        if bad:
            sys.exit(f"unknown --chaos-regimes {bad}; known: "
                     f"{sorted(FAULT_REGIMES)}")
        bad = [b for b in chaos_baselines if b not in BASELINES]
        if bad:
            sys.exit(f"unknown --chaos-baselines {bad}; known: "
                     f"{sorted(BASELINES)}")
    elif args.chaos_regimes is not None:
        sys.exit("--chaos-regimes configures the --chaos matrix; pass "
                 "--chaos with it (refusing the silent no-op)")
    if args.obs_dir and not (args.chaos or args.matrix):
        sys.exit("--obs-dir serves the --chaos and --matrix flows; pass "
                 "one of them with it (refusing the silent no-op)")
    if args.trace_spans and not (args.chaos and args.obs_dir):
        sys.exit("--trace-spans records spans on the chaos event bus; "
                 "pass --chaos and --obs-dir with it (refusing the "
                 "silent no-op)")

    if args.matrix:
        if (args.chaos or args.pbt or args.fairness or args.full_trace
                or args.baselines_only or args.percentiles
                or args.backlog_gate or cfg.n_pods > 1):
            sys.exit("--matrix is its own train-regime × eval-regime "
                     "table over generated domain windows (flat "
                     "configs): no --chaos/--pbt/--fairness/"
                     "--full-trace/--baselines-only/--percentiles/"
                     "--backlog-gate")
        if args.eval_windows is not None:
            sys.exit("--matrix generates its own window batch per "
                     "regime; size it with --n-envs")
        from .domains import DOMAIN_REGIMES
        from .sim.schedulers import BASELINES
        matrix_regimes = (tuple(s for s in args.matrix_regimes.split(",")
                                if s)
                          if args.matrix_regimes else None)
        matrix_baselines = tuple(
            s for s in args.matrix_baselines.split(",") if s)
        bad = [r for r in (matrix_regimes or ()) if r not in
               DOMAIN_REGIMES]
        if bad:
            sys.exit(f"unknown --matrix-regimes {bad}; known: "
                     f"{sorted(DOMAIN_REGIMES)}")
        bad = [b for b in matrix_baselines if b not in BASELINES]
        if bad:
            sys.exit(f"unknown --matrix-baselines {bad}; known: "
                     f"{sorted(BASELINES)}")
        matrix_ckpts = []
        for spec in args.matrix_ckpt or []:
            regime, sep, path = spec.partition("=")
            if not sep or not path or (regime != "clean" and
                                       regime not in DOMAIN_REGIMES):
                sys.exit(f"--matrix-ckpt wants REGIME=DIR with REGIME "
                         f"in {sorted(DOMAIN_REGIMES)} or 'clean' "
                         f"(got {spec!r})")
            matrix_ckpts.append((regime, path))
    elif (args.matrix_regimes is not None or args.matrix_ckpt
          or args.matrix_seed != 0 or args.alarms):
        sys.exit("--matrix-regimes/--matrix-ckpt/--matrix-seed/--alarms "
                 "configure the --matrix table; pass --matrix with them "
                 "(refusing the silent no-op)")
    if args.alarms and not args.obs_dir:
        sys.exit("--alarms raises its events on the --obs-dir bus; pass "
                 "--obs-dir with it")

    if (args.stitch_faults or args.stitch_domain) and not args.full_trace:
        sys.exit("--stitch-faults/--stitch-domain degrade the "
                 "--full-trace stitched replay; pass --full-trace with "
                 "them (refusing the silent no-op)")
    if args.stitch_seed != 0 and not (args.stitch_faults or
                                      args.stitch_domain):
        sys.exit("--stitch-seed seeds the --stitch-faults/--stitch-domain "
                 "draw; pass one of them with it")
    if args.stitch_faults is not None:
        from .sim.faults import FAULT_REGIMES
        if args.stitch_faults not in FAULT_REGIMES:
            sys.exit(f"unknown --stitch-faults {args.stitch_faults!r}; "
                     f"known: {sorted(FAULT_REGIMES)}")
    if args.stitch_domain is not None:
        from .domains import DOMAIN_REGIMES
        if args.stitch_domain not in DOMAIN_REGIMES:
            sys.exit(f"unknown --stitch-domain {args.stitch_domain!r}; "
                     f"known: {sorted(DOMAIN_REGIMES)}")

    # the full reproducibility tuple every evaluate JSON carries: enough
    # to regenerate any row (chaos-matrix rows included) exactly —
    # resolved checkpoint step filled in by restore() below. The tuple's
    # shape is shared with the serve CLI (configs.repro_tuple), so
    # serving numbers reproduce the same way evaluation numbers do
    from .configs import repro_tuple
    repro = repro_tuple(cfg, ckpt_dir=args.ckpt_dir)

    if args.percentiles and (args.fairness or args.baselines_only
                             or args.pbt):
        sys.exit("--percentiles applies to the per-window and --full-trace "
                 "JCT tables (flat configs, no --fairness/"
                 "--baselines-only/--pbt)")
    if args.eval_windows is not None and (args.pbt or args.fairness or
                                          args.full_trace or
                                          args.baselines_only):
        sys.exit("--eval-windows applies to the plain per-window JCT "
                 "table (population views carry no source trace; the "
                 "other modes define their own window batch)")
    if args.stitch_window_jobs is not None and not args.full_trace:
        sys.exit("--stitch-window-jobs applies to --full-trace stitched "
                 "replay only")
    if args.stitch_drain_jobs != 1 and not args.full_trace:
        sys.exit("--stitch-drain-jobs applies to --full-trace stitched "
                 "replay only")
    if args.stitch_drain_jobs < 1:
        sys.exit("--stitch-drain-jobs must be >= 1 (each deep-backlog "
                 "window must free at least one job-table row)")
    if args.backlog_gate < 0:
        sys.exit("--backlog-gate must be >= 0 (a negative gate would "
                 "silently run ungated)")
    if args.backlog_gate and (args.pbt or args.fairness or
                              args.baselines_only or cfg.n_pods > 1):
        sys.exit("--backlog-gate applies to the flat per-window and "
                 "--full-trace policy tables (the hierarchical action "
                 "space has no single FIFO fall-through action; "
                 "--baselines-only has no policy row)")
    if not args.stall_guard and (args.baselines_only or args.fairness
                                 or cfg.n_pods > 1
                                 or cfg.preempt_len == 0):
        sys.exit("--no-stall-guard applies to flat PREEMPTIVE configs' "
                 "policy rows (per-window, --full-trace, and flat --pbt "
                 "members): the guard only ever masks preempt actions, "
                 "so it is a no-op elsewhere, and the fairness path "
                 "does not plumb it; refusing beats silently changing "
                 "nothing)")

    if args.baselines_only:
        _, windows, _, _, _, _, _ = build_stack(cfg)
        report = baseline_jct_table(windows, cfg.n_nodes, cfg.gpus_per_node)
        print(format_report(report), file=sys.stderr)
        print(json.dumps({**report, "repro": repro}))
        return report

    def restore(target, label: str) -> None:
        if args.ckpt_dir:
            from .checkpoint import Checkpointer
            import os
            with Checkpointer(os.path.abspath(args.ckpt_dir)) as ckpt:
                target.restore_checkpoint(ckpt, step=args.ckpt_step)
                # resolved, not requested: the integrity fallback may
                # restore an older retained step than asked for
                repro["ckpt_step"] = ckpt.last_restored_step
            print(f"{label} restored from {args.ckpt_dir}", file=sys.stderr)
        else:
            print("note: no --ckpt-dir; evaluating untrained init weights",
                  file=sys.stderr)

    if args.pbt:
        if args.fairness or args.full_trace:
            sys.exit("--pbt supports the per-window JCT table "
                     "(hierarchical members replay per-window)")
        from .experiment import PopulationExperiment
        pop = PopulationExperiment.build(cfg, n_pop=args.n_pop)
        restore(pop, "population")
        # untrained populations have no fitness record to rank by
        member = args.member if args.member is not None else \
            (None if args.ckpt_dir else 0)
        exp = pop.member_eval_view(member)
        print(f"evaluating member {exp.member} of {args.n_pop}",
              file=sys.stderr)
    else:
        exp = Experiment.build(cfg)
        restore(exp, "policy")
    if args.chaos:
        import os

        from .eval import CHAOS_REGIMES, chaos_report, format_chaos
        bus = registry = tracer = None
        if args.obs_dir:
            from .obs import EventBus, Registry
            bus = EventBus(os.path.abspath(args.obs_dir), rank=0,
                           name="chaos")
            registry = Registry()
            if args.trace_spans:
                from .obs.trace import Tracer
                tracer = Tracer(bus, enabled=True)
        try:
            report = chaos_report(
                exp, regimes=regimes or CHAOS_REGIMES,
                baselines=chaos_baselines, max_steps=args.max_steps,
                seed=args.chaos_seed, bus=bus, registry=registry,
                tracer=tracer)
        finally:
            if bus is not None:
                bus.close()
        if registry is not None:
            registry.write(os.path.join(os.path.abspath(args.obs_dir),
                                        "metrics.prom"))
        print(format_chaos(report), file=sys.stderr)
        report["repro"] = dict(repro, chaos_seed=args.chaos_seed,
                               chaos_regimes=report["chaos_regimes"],
                               chaos_baselines=list(chaos_baselines))
        print(json.dumps(report))
        return report
    if args.matrix:
        import os

        from .eval import MATRIX_REGIMES, format_matrix, matrix_report
        # the experiment's own row, labeled by its training regime
        own = cfg.domains or "clean"
        policies = {own: (exp.apply_fn, exp.train_state.params,
                          exp.env_params)}
        for regime, path in matrix_ckpts:
            label = regime if regime not in policies else \
                f"{regime}@{len(policies)}"
            rcfg = dataclasses.replace(
                cfg, domains=None if regime == "clean" else regime)
            rexp = Experiment.build(rcfg)
            from .checkpoint import Checkpointer
            with Checkpointer(os.path.abspath(path)) as ck:
                rexp.restore_checkpoint(ck, step=None)
            print(f"matrix row {label!r} restored from {path}",
                  file=sys.stderr)
            policies[label] = (rexp.apply_fn, rexp.train_state.params,
                               rexp.env_params)
        bus = registry = alarms = None
        if args.obs_dir:
            from .obs import EventBus, Registry
            bus = EventBus(os.path.abspath(args.obs_dir), rank=0,
                           name="matrix")
            registry = Registry()
            if args.alarms:
                from .obs import Alarms
                alarms = Alarms(bus, registry, warmup_iters=1,
                                transfer_guard=True)
        try:
            with (alarms if alarms is not None
                  else contextlib.nullcontext()):
                report = matrix_report(
                    exp, regimes=matrix_regimes or MATRIX_REGIMES,
                    baselines=matrix_baselines, policies=policies,
                    max_steps=args.max_steps, seed=args.matrix_seed,
                    bus=bus, registry=registry, alarms=alarms)
        finally:
            if bus is not None:
                bus.close()
        if registry is not None:
            registry.write(os.path.join(os.path.abspath(args.obs_dir),
                                        "metrics.prom"))
        print(format_matrix(report), file=sys.stderr)
        report["repro"] = dict(
            repro, matrix_seed=args.matrix_seed,
            matrix_regimes=report["matrix_regimes"],
            matrix_baselines=list(matrix_baselines),
            matrix_ckpts=[f"{r}={p}" for r, p in matrix_ckpts])
        print(json.dumps(report))
        return report
    if args.fairness:
        report = fairness_report(exp, max_steps=args.max_steps)
        print(format_fairness(report), file=sys.stderr)
        import math

        # NaN is the deliberate nothing-completed sentinel, but bare NaN
        # tokens are invalid JSON — emit null so strict parsers (jq etc.)
        # can consume the CLI output
        def _json_safe(v):
            if isinstance(v, float) and not math.isfinite(v):
                return None
            if isinstance(v, dict):
                return {k: _json_safe(x) for k, x in v.items()}
            if isinstance(v, list):
                return [_json_safe(x) for x in v]
            return v
        print(json.dumps(_json_safe({**report, "repro": repro})))
        return report
    if args.full_trace:
        stitch_params = None
        if args.stitch_window_jobs is not None:
            if cfg.n_pods > 1:
                sys.exit("--stitch-window-jobs applies to flat configs "
                         "(full-trace evaluation has no hierarchical "
                         "form)")
            stitch_params = dataclasses.replace(
                exp.env_params, sim=dataclasses.replace(
                    exp.env_params.sim,
                    max_jobs=args.stitch_window_jobs))
        stitch_schedule = None
        if args.stitch_faults or args.stitch_domain:
            from .sim.faults import (fault_horizon, resolve_regime,
                                     sample_fault_schedule)
            if args.stitch_faults:
                stitch_schedule = sample_fault_schedule(
                    cfg.n_nodes, resolve_regime(args.stitch_faults),
                    (args.stitch_seed,), fault_horizon([exp.source]))
            if args.stitch_domain:
                from .domains import (domain_schedule, domain_stats,
                                      resolve_domain, sample_domain)
                draw = sample_domain(resolve_domain(args.stitch_domain),
                                     cfg.n_nodes, cfg.gpus_per_node,
                                     (args.stitch_seed,))
                stitch_schedule = domain_schedule(draw, stitch_schedule)
                repro["stitch_domain_draw"] = domain_stats(draw)
            repro["stitch_faults"] = args.stitch_faults
            repro["stitch_domain"] = args.stitch_domain
            repro["stitch_seed"] = args.stitch_seed
        report = full_trace_report(exp, max_jobs=args.max_jobs,
                                   include_random=not args.no_random,
                                   percentiles=PERCENTILES
                                   if args.percentiles else None,
                                   env_params=stitch_params,
                                   backlog_gate=args.backlog_gate,
                                   stall_guard=args.stall_guard,
                                   drain_completions=args.stitch_drain_jobs,
                                   faults=stitch_schedule)
    else:
        eval_windows = None
        if args.eval_windows is not None and \
                args.eval_windows != cfg.n_envs:
            # re-cut the evaluation window batch at the requested size,
            # keeping the checkpoint's restored tiling cursor so a
            # resized batch replays the same part of the trace the
            # default path would; the restored params have no batch
            # dimension, so only the restore template above needed the
            # training n_envs
            from .experiment import make_env_windows
            eval_windows = make_env_windows(
                dataclasses.replace(cfg, n_envs=args.eval_windows),
                exp.source, start=exp.window_cursor)
        report = jct_report(exp, windows=eval_windows,
                            max_steps=args.max_steps,
                            include_random=not args.no_random,
                            percentiles=PERCENTILES if args.percentiles
                            else None,
                            backlog_gate=args.backlog_gate,
                            stall_guard=args.stall_guard)
    print(format_report(report), file=sys.stderr)
    out = {k: v for k, v in report.items() if isinstance(v, (int, float))}
    if "percentiles" in report:
        out["percentiles"] = report["percentiles"]
    out["repro"] = repro
    print(json.dumps(out))
    return report


if __name__ == "__main__":
    main()
