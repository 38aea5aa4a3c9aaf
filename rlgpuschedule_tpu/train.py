"""Training CLI (L6): ``python -m rlgpuschedule_tpu.train --config <name>``.

Capability parity: SURVEY.md §2 "Config/flags" and §3.1 "cli main (parse
flags, seed, build trace)" — entry script selecting trace, cluster size,
algorithm, encoder, env count, seeds; checkpointing; metric logging. The
five driver capability configs are the named presets (``--list-configs``);
every preset axis can be overridden from the command line.

Examples::

    python -m rlgpuschedule_tpu.train --config ppo-mlp-synth64
    python -m rlgpuschedule_tpu.train --config ppo-cnn-philly512 \
        --trace philly --trace-path philly.csv --iterations 200 \
        --ckpt-dir out/ckpt --log-csv out/metrics.csv --log-every 10 --report
    python -m rlgpuschedule_tpu.train --config hier-pbt-member \
        --pbt --n-pop 4 --pbt-ready 10            # config 5: PBT population
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys

from .configs import (CONFIGS, TRUNK_NAMES, ExperimentConfig,
                      ModeCombinationError, validate_mode_combination)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="rlgpuschedule_tpu.train",
        description="Train an RL GPU-cluster scheduling policy (TPU-native).")
    p.add_argument("--config", default="ppo-mlp-synth64",
                   help="named preset (see --list-configs)")
    p.add_argument("--list-configs", action="store_true")
    # config overrides (None = keep preset value)
    p.add_argument("--iterations", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--n-envs", type=int, default=None)
    p.add_argument("--n-nodes", type=int, default=None)
    p.add_argument("--gpus-per-node", type=int, default=None)
    p.add_argument("--window-jobs", type=int, default=None)
    p.add_argument("--queue-len", type=int, default=None,
                   help="pending-queue slots the agent sees/acts on (the "
                        "policy's visibility into the backlog)")
    p.add_argument("--horizon", type=int, default=None)
    p.add_argument("--obs-kind", default=None,
                   choices=["flat", "grid", "graph", "tokens"],
                   help="override the preset's observation/encoder family "
                        "(e.g. train config 2's cluster on the flat MLP "
                        "encoder on a CPU host)")
    p.add_argument("--trunk", default=None, choices=TRUNK_NAMES,
                   help="obs-kind tokens: the token trunk's family and "
                        "whole set of sizes (models.trunk.TRUNKS): a "
                        "source model's published widths (published: "
                        "afmoe blocks; ling: linear-attention blocks; "
                        "ouro: looped dense blocks), or its tiny shape "
                        "for a CPU host (tiny; ling-tiny; ouro-tiny)")
    p.add_argument("--trace", default=None,
                   choices=["synthetic", "philly", "pai", "philly-proxy",
                            "pai-proxy"],
                   help="trace source (e.g. switch a -proxy preset to the "
                        "real CSV loader)")
    p.add_argument("--trace-path", default=None,
                   help="CSV path for philly/pai traces")
    p.add_argument("--trace-load", type=float, default=None,
                   help="proxy traces: offered-load target (default 1.1)")
    p.add_argument("--source-jobs", type=int, default=None,
                   help="generated traces: pin the source trace size in "
                        "jobs (default: one window-streaming pass over "
                        "the env batch). The north-star full-Philly run "
                        "pins 100k+ explicitly")
    p.add_argument("--resample-every", type=int, default=None,
                   help="window streaming: rotate env windows over the "
                        "source trace every N iterations (0 = static)")
    p.add_argument("--drain-frac", type=float, default=None,
                   help="backlog-drain curriculum: fraction of envs that "
                        "train on drained copies of their windows (all "
                        "jobs at t=0)")
    p.add_argument("--faults", default=None, metavar="REGIME",
                   help="cluster chaos: train on a seeded in-simulator "
                        "fault distribution — per-env node-drain/"
                        "straggler schedules (sim.faults.FAULT_REGIMES: "
                        "none/sporadic/storm/straggler) threaded through "
                        "the rollout next to the traces; flat configs "
                        "also expose per-node health in the observation. "
                        "Evaluate the result with evaluate --chaos")
    p.add_argument("--domains", default=None, metavar="REGIME",
                   help="domain randomization: train ONE policy across a "
                        "seeded distribution of clusters — randomized "
                        "geometry (per-node capacity), heterogeneous "
                        "hardware speeds, and arrival regimes up to "
                        "sustained overload (domains.DOMAIN_REGIMES: "
                        "none/baseline/geom/hetero/overload/flash/"
                        "mixed); per-env draws ride the fault-schedule "
                        "slot, windows are GENERATED from the trace's "
                        "fitted job mix against each draw's actual "
                        "capacity. Composes with --faults (worst "
                        "slowdown wins per node). Evaluate the result "
                        "with evaluate --matrix")
    # algorithm hyperparameter overrides (apply to the active algo's
    # config — cfg.ppo or cfg.a2c; None = keep preset value). Large-batch
    # TPU runs typically want a higher --lr than the preset 3e-4, which
    # was tuned at config-1 batch sizes.
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--ent-coef", type=float, default=None)
    p.add_argument("--n-steps", type=int, default=None,
                   help="rollout length T per iteration")
    # update geometry (algos.update): n_epochs x n_minibatches x
    # minibatch_size, validated against n_steps * n_envs at build time.
    # Applies to BOTH algorithms — A2C's default 1x1 is the classic
    # full-batch update; any other geometry runs the same fused engine.
    p.add_argument("--n-epochs", type=int, default=None,
                   help="update epochs per iteration")
    p.add_argument("--n-minibatches", type=int, default=None,
                   help="minibatches per update epoch")
    p.add_argument("--minibatch-size", type=int, default=None,
                   help="explicit minibatch size (overrides "
                        "--n-minibatches; must tile n_steps * n_envs — "
                        "the fewer-larger-minibatch throughput lever: "
                        "what it buys on the chip is for the benchmark "
                        "to say, PERF.md)")
    p.add_argument("--bf16-update", action="store_true", default=None,
                   help="bf16-compute / fp32-optimizer-state update path "
                        "(NOT bit-identical to the fp32 default)")
    # fused advantage pipeline (ISSUE 12): off-policy correction +
    # streaming reward normalization + compact advantage storage
    p.add_argument("--correction", default=None,
                   choices=["none", "vtrace"],
                   help="off-policy advantage correction (PPO only). "
                        "'vtrace' re-weights the advantage scan by "
                        "rho/c-clipped importance ratios (algos.vtrace) "
                        "so deep --staleness-bound queues train without "
                        "bias; requires --async (on-policy ratios are "
                        "identically 1 and the correction reduces "
                        "bit-identically to the GAE path, so the sync "
                        "combination is refused as a silent no-op)")
    p.add_argument("--reward-norm", action="store_true", default=None,
                   help="streaming reward standardization: scale rewards "
                        "by a running inverse-std (Welford moments "
                        "carried in the train state, scale-only — no "
                        "centering, so sparse-reward signs survive) "
                        "before the advantage scan")
    p.add_argument("--bf16-advantages", action="store_true", default=None,
                   help="store advantage/return targets in bfloat16 "
                        "between the advantage scan and the minibatch "
                        "epochs (halves the target buffer; NOT "
                        "bit-identical — loss math upcasts to fp32)")
    # async actor-learner split (async_engine; opt-in)
    p.add_argument("--async", dest="async_run", action="store_true",
                   help="overlapped actor-learner engine: rollout "
                        "collection on one device group overlaps the "
                        "minibatch update on another, coupled by a "
                        "bounded device-side trajectory queue "
                        "(Sebulba split). Single-run configs only; "
                        "--staleness-bound 0 reproduces the sync loop "
                        "bit-identically")
    p.add_argument("--actor-devices", default=None, metavar="N|I,J,..",
                   help="with --async: actor group as a device COUNT "
                        "(taken from the front of the visible list) or "
                        "explicit comma-separated device indices. "
                        "Default: first half (one device: shared group)")
    p.add_argument("--learner-devices", default=None, metavar="N|I,J,..",
                   help="with --async: learner group (count from the "
                        "back, or explicit indices; must be disjoint "
                        "from the actor group unless identical)")
    p.add_argument("--staleness-bound", type=int, default=1,
                   help="with --async: max update-steps the policy that "
                        "collected a batch may lag the learner at "
                        "consume time (0 = lock-step, bit-identical to "
                        "the sync path; default 1). Bounds >= 4 run the "
                        "queue deep enough to hide slow actors but bias "
                        "the clip-only surrogate — pair them with "
                        "--correction vtrace")
    p.add_argument("--queue-capacity", type=int, default=2,
                   help="with --async: trajectory-queue slots; a full "
                        "queue blocks the actor (backpressure, no drops)")
    p.add_argument("--mesh", default="off", metavar="off|auto|PxDxM",
                   help="rule-table sharding for the single-run path: "
                        "build the unified Mesh(pop x data x model) and "
                        "jit the train step with in/out shardings "
                        "resolved from the model family's partition-rule "
                        "table (parallel.sharding). 'auto' picks the "
                        "largest data axis dividing both n_envs and the "
                        "device count (model axis 1 — bit-identical "
                        "layout to replication); an explicit PxDxM "
                        "triple (e.g. 1x2x2) also engages the model "
                        "axis. 'off' (default) is the plain jit path")
    # population / PBT (config 5)
    p.add_argument("--pbt", action="store_true",
                   help="train a PBT population instead of a single run")
    p.add_argument("--n-pop", type=int, default=4)
    p.add_argument("--pbt-ready", type=int, default=10,
                   help="iterations between exploit/explore rounds")
    # logging / checkpointing / profiling
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--eval-every", type=int, default=0,
                   help="every N iterations, replay the policy greedily on "
                        "a small HELD-OUT window batch and log avg JCT + "
                        "vs_tiresias (the in-training quality probe; "
                        "single-run configs). Rows go to <log-csv>.eval.csv")
    p.add_argument("--eval-windows", type=int, default=4,
                   help="held-out windows per --eval-every probe")
    p.add_argument("--eval-seed", type=int, default=None,
                   help="seed of the held-out eval trace (default: "
                        "training seed + 1000)")
    p.add_argument("--eval-probe", default="auto",
                   choices=["auto", "drain", "stream"],
                   help="probe regime: auto = drain for drain-curriculum "
                        "configs else streaming. Use 'stream' when the "
                        "deliverable is a streaming/full-trace table — "
                        "measured: drain-probe checkpoint selection does "
                        "not rank streaming quality")
    p.add_argument("--keep-best", action="store_true",
                   help="with --eval-every and --ckpt-dir: whenever the "
                        "held-out probe's avg JCT improves (at full "
                        "completion), save a checkpoint under "
                        "<ckpt-dir>/best — automated model selection "
                        "against late-training collapse")
    p.add_argument("--log-csv", default=None)
    p.add_argument("--tb-dir", default=None,
                   help="also write scalar curves as a TensorBoard event "
                        "file under this directory")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--ckpt-keep", type=int, default=None,
                   help="retain the last N periodic checkpoints (default "
                        "3). Measured round 5: per-window probes do not "
                        "rank full-trace quality, so keep a SERIES and "
                        "select post-hoc with select_checkpoint against "
                        "a held-out validation stream instead of "
                        "trusting the probe's single best")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest checkpoint from --ckpt-dir")
    # continual training from served traffic (ISSUE 19 data flywheel)
    p.add_argument("--continual", default=None, metavar="LOGDIR",
                   help="continual-training mode: instead of simulator "
                        "rollouts, ingest the crc-verified served-traffic "
                        "flight log under LOGDIR (serve --flight-log) and "
                        "run --iterations V-trace-corrected updates over "
                        "its pseudo-trajectories (flywheel.continual; "
                        "default 1 iteration). Policy lag is measured per "
                        "shard (staleness + importance-ratio gauges) and "
                        "shards outside the trust region are refused. "
                        "Composes with --ckpt-dir/--resume (restore the "
                        "incumbent, retrain, save the candidate)")
    p.add_argument("--continual-trust", type=float, default=2.0,
                   help="ingest trust region: refuse shards whose mean "
                        "importance ratio leaves [1/T, T]")
    p.add_argument("--continual-rho-max", type=float, default=8.0,
                   help="ingest trust region: refuse shards whose max "
                        "importance ratio exceeds this")
    p.add_argument("--fused-chunk", type=int, default=1,
                   help="dispatch N train steps as one on-device scan "
                        "between hook boundaries (every active log/eval/"
                        "ckpt/resample cadence must be a multiple of N). "
                        "Chunking amortizes per-dispatch latency. "
                        "Single-run configs only (--pbt is refused: its "
                        "exploit/explore interleaves host-side between "
                        "steps)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the run")
    # observability (obs/): structured event bus + metrics snapshot +
    # production alarms — the run's post-mortem surface
    p.add_argument("--obs-dir", default=None,
                   help="unified telemetry: append structured events "
                        "(JSONL event bus, schema-versioned, rank/pid/"
                        "monotonic-stamped) and a Prometheus-text "
                        "metrics snapshot (metrics.prom) under this "
                        "directory; post-mortem via "
                        "python -m rlgpuschedule_tpu.obs.report <dir>")
    p.add_argument("--alarms", action="store_true",
                   help="production alarms (requires --obs-dir): a "
                        "post-warmup dispatch that traces/compiles emits "
                        "a recompile event (the silent throughput killer "
                        "the test-only CompileCounter gate catches only "
                        "in CI), and an implicit host<->device transfer "
                        "in the dispatch emits a transfer event and "
                        "fails fast")
    p.add_argument("--alarm-slow-iter", type=float, default=None,
                   metavar="SECONDS",
                   help="with --alarms: an iteration slower than this "
                        "emits a slow_iteration event and auto-captures "
                        "a one-shot jax.profiler trace of the NEXT "
                        "iteration under <obs-dir>/profile")
    p.add_argument("--trace-spans", action="store_true",
                   help="flight recorder (requires --obs-dir): record "
                        "nested phase spans (iteration/step/sync/... and "
                        "the async engine's actor/learner/queue-wait "
                        "lanes) on the event bus; export with "
                        "obs.report --trace-out trace.json (Perfetto). "
                        "NOT --trace, which picks the workload trace "
                        "source")
    p.add_argument("--debug-nans", action="store_true",
                   help="run under jax_debug_nans (sanitizer hook — the "
                        "functional design has no data races to detect, so "
                        "NaN-poisoning is the remaining numeric hazard; "
                        "fails fast with a traceback at the first NaN)")
    # resilience (SURVEY.md §5 "Failure detection"): the divergence
    # watchdog + deterministic fault injection, demonstrable end to end
    p.add_argument("--max-rollbacks", type=int, default=None,
                   help="attach the divergence watchdog: a non-finite or "
                        "exploding iteration rolls the run back to the "
                        "last good checkpoint with a decayed LR, giving "
                        "up cleanly after N rollbacks (requires "
                        "--ckpt-dir; see resilience.DivergenceWatchdog)")
    p.add_argument("--fault", action="append", default=None,
                   metavar="KIND@N[:rank=R]",
                   help="deterministic fault injection (repeatable): "
                        "nan-grad@K poisons params+metrics at iteration "
                        "K (PBT: rank=M selects the member), "
                        "corrupt-ckpt@K truncates the checkpoint saved "
                        "at iteration K. kill-rank/lose-rank are refused "
                        "here (multihost only — drive them with "
                        "__graft_entry__.dryrun_multihost_supervised / "
                        "dryrun_multihost_elastic)")
    p.add_argument("--report", action="store_true",
                   help="print the JCT-vs-baselines table after training "
                        "(single-run, non-hierarchical configs)")
    return p


def apply_overrides(cfg: ExperimentConfig,
                    args: argparse.Namespace) -> ExperimentConfig:
    fields = {"iterations": args.iterations, "seed": args.seed,
              "n_envs": args.n_envs, "n_nodes": args.n_nodes,
              "gpus_per_node": args.gpus_per_node,
              "window_jobs": args.window_jobs, "horizon": args.horizon,
              "queue_len": args.queue_len, "obs_kind": args.obs_kind,
              "trunk": args.trunk,
              "trace": args.trace, "trace_path": args.trace_path,
              "trace_load": args.trace_load,
              "source_jobs": args.source_jobs,
              "resample_every": args.resample_every,
              "drain_frac": args.drain_frac, "faults": args.faults,
              "domains": args.domains}
    cfg = dataclasses.replace(
        cfg, **{k: v for k, v in fields.items() if v is not None})
    algo_fields = {"lr": args.lr, "ent_coef": args.ent_coef,
                   "n_steps": args.n_steps,
                   # both algorithms run the shared minibatch-geometry
                   # engine (algos.update); A2C's preset 1x1 geometry is
                   # the classic full-batch update
                   "n_epochs": args.n_epochs,
                   "n_minibatches": args.n_minibatches,
                   "minibatch_size": args.minibatch_size,
                   "bf16_update": args.bf16_update,
                   # both algo configs carry the fused-pipeline knobs...
                   "reward_norm": args.reward_norm,
                   "bf16_advantages": args.bf16_advantages}
    over = {k: v for k, v in algo_fields.items() if v is not None}
    # ...but only PPO has an off-policy correction (A2C's single-epoch
    # full-batch update consumes each batch once, at its own policy)
    if args.correction is not None:
        if cfg.algo != "ppo":
            sys.exit("--correction selects the PPO advantage pipeline "
                     "(algos.vtrace); the A2C update has no importance-"
                     "corrected variant")
        over["correction"] = args.correction
    if over:
        algo = "ppo" if cfg.algo == "ppo" else "a2c"
        cfg = dataclasses.replace(
            cfg, **{algo: dataclasses.replace(getattr(cfg, algo), **over)})
    return cfg


def make_eval_probe(cfg: ExperimentConfig, exp, n_windows: int,
                    eval_seed: int | None, regime: str = "auto"):
    """The --eval-every in-training quality probe: a greedy replay on a
    held-out window batch (fresh trace seed, so never trained on), scored
    against oracle baselines computed ONCE. Returns ``eval_fn(i) -> dict``
    for :meth:`Experiment.run`. The replay program compiles on the first
    probe and is reused after (fixed shapes).

    ``regime``: "auto" probes all-drain for drain-curriculum configs and
    all-streaming otherwise; "drain"/"stream" force one. Measured round 5:
    a drain-probe-selected config-1 "best" checkpoint read 1.08 vs
    Tiresias on the STREAMING full-trace where round 3's comparable run
    read 0.80 — drain quality does not rank streaming quality, so a run
    whose deliverable is the full-trace table must probe (and keep-best
    on) the streaming regime it will be judged in."""
    from . import eval as eval_lib
    from .env import env as env_lib
    from .experiment import load_source_trace, make_env_windows
    from .sim.core import validate_trace

    import sys

    if cfg.trace in ("philly", "pai"):
        # CSV loaders take no seed: there is no second trace to hold out,
        # so the probe replays leading windows of the TRAINING csv —
        # on-distribution, not held-out. Refuse a seed that would
        # otherwise be a silent no-op, and say what the number means.
        if eval_seed is not None:
            sys.exit("--eval-seed has no effect for csv traces "
                     "(philly/pai load a file, not a seeded generator)")
        print("note: --eval-every probe windows come from the training "
              "CSV (csv traces have no held-out seed); treat the curve "
              "as on-distribution quality, not generalization",
              file=sys.stderr)
    seed = cfg.seed + 1000 if eval_seed is None else eval_seed
    # probe one regime, not a mix: a fractional drain_frac would pool two
    # incomparable regimes into one number
    if regime == "auto":
        regime = "drain" if cfg.drain_frac > 0 else "stream"
    if regime not in ("drain", "stream"):
        raise ValueError(f"unknown probe regime {regime!r}")
    # source_jobs=None: the probe's trace is sized to its own window
    # batch — inheriting a pinned 100k-job source would generate and
    # validate the whole thing just to cut n_windows leading windows
    ecfg = dataclasses.replace(cfg, n_envs=n_windows, seed=seed,
                               source_jobs=None,
                               drain_frac=1.0 if regime == "drain"
                               else 0.0)
    sim_params = (exp.env_params.sim
                  if hasattr(exp.env_params, "sim") else
                  exp.env_params.pod_sim)
    source = validate_trace(sim_params, load_source_trace(ecfg),
                            clamp=True)
    windows = make_env_windows(ecfg, source)
    traces = env_lib.stack_traces(windows, sim_params)
    baselines = eval_lib.baseline_jct_table(
        windows, cfg.n_nodes, cfg.gpus_per_node,
        names=("fifo", "tiresias"))

    def eval_fn(_i: int) -> dict:
        res = eval_lib.replay(exp.apply_fn, exp.train_state.params,
                              exp.env_params, traces)
        jct, completion = eval_lib.pooled_avg_jct(res)
        out = {"eval_avg_jct": jct, "eval_completion": completion,
               **{f"eval_{k}": v for k, v in baselines.items()}}
        if baselines.get("tiresias"):
            out["eval_vs_tiresias"] = jct / baselines["tiresias"]
        return out

    return eval_fn


class FittestMemberView:
    """Experiment-like adapter over a :class:`PopulationExperiment` for
    :func:`make_eval_probe`: ``train_state.params`` resolves to the
    FITTEST member's params at probe time (the controller has recorded
    fitness by then — the population run fires eval hooks after the
    iteration's record), so the in-training probe and ``--keep-best``
    track the population's best member rather than a fixed index. The
    population-drift failure mode this closes has cost a best-population
    twice (VERDICT r5 weak #2)."""

    def __init__(self, pop):
        self._pop = pop

    @property
    def env_params(self):
        return self._pop.env_params

    @property
    def apply_fn(self):
        return self._pop.apply_fn

    @property
    def train_state(self):
        return self._pop.member_eval_view().train_state


def make_pop_mesh(n_pop: int):
    """Best unified mesh for a population run: the largest pop axis that
    divides both the population and the device count (1 device → no
    mesh), remaining devices on the data axis, model axis free at 1.
    Built through the SAME ``make_unified_mesh`` every other entry point
    resolves placements from."""
    import jax
    from .parallel import make_unified_mesh
    n_dev = jax.device_count()
    if n_dev == 1:
        return None
    pop_axis = 1
    for c in range(min(n_pop, n_dev), 0, -1):
        if n_pop % c == 0 and n_dev % c == 0:
            pop_axis = c
            break
    return make_unified_mesh(n_pop=pop_axis)


def make_run_mesh(spec: str, n_envs: int):
    """Resolve ``--mesh`` into a unified mesh (or None for the plain
    path). ``auto`` puts the largest data axis that divides both the env
    batch and the device count, model axis 1; an explicit ``PxDxM``
    triple engages exactly P*D*M devices."""
    import jax
    from .parallel import make_unified_mesh
    if spec == "off":
        return None
    devices = jax.devices()
    if spec == "auto":
        n_dev = len(devices)
        data = 1
        for c in range(min(n_envs, n_dev), 0, -1):
            if n_envs % c == 0 and n_dev % c == 0:
                data = c
                break
        if data == 1 and n_dev == 1:
            return None
        return make_unified_mesh(devices=devices[:data])
    p, d, m = (int(x) for x in spec.split("x"))
    if p * d * m == 0:
        sys.exit(f"bad --mesh {spec!r}: every axis must be >= 1")
    if p * d * m > len(devices):
        sys.exit(f"--mesh {spec} asks for {p * d * m} devices but only "
                 f"{len(devices)} are visible")
    if n_envs % d:
        sys.exit(f"--mesh {spec}: data axis {d} does not divide "
                 f"n_envs={n_envs}")
    return make_unified_mesh(n_pop=p, n_model=m,
                             devices=devices[:p * d * m])


def main(argv: list[str] | None = None) -> dict:
    args = build_parser().parse_args(argv)
    if args.list_configs:
        for name, c in CONFIGS.items():
            print(f"{name:20s} algo={c.algo} obs={c.obs_kind} "
                  f"cluster={c.n_nodes}x{c.gpus_per_node} trace={c.trace}"
                  f"{' pods=' + str(c.n_pods) if c.n_pods > 1 else ''}")
        return {}
    if args.config not in CONFIGS:
        sys.exit(f"unknown config {args.config!r}; try --list-configs")
    if args.keep_best and not (args.eval_every and args.ckpt_dir):
        sys.exit("--keep-best requires --eval-every (the probe that "
                 "defines 'best') and --ckpt-dir (where best/ lives)")
    if args.eval_probe != "auto" and not args.eval_every:
        sys.exit("--eval-probe selects the --eval-every probe's regime; "
                 "without --eval-every no probe runs and the flag would "
                 "be a silent no-op")
    if args.ckpt_keep is not None:
        if args.ckpt_keep < 1:
            sys.exit("--ckpt-keep must be >= 1")
        if not args.ckpt_dir:
            sys.exit("--ckpt-keep requires --ckpt-dir (nothing is "
                     "retained without one)")
    faults = []
    if args.fault:
        from .resilience import parse_fault
        try:
            faults = [parse_fault(s) for s in args.fault]
        except ValueError as e:
            sys.exit(str(e))
        if any(f.kind in ("kill-rank", "lose-rank") for f in faults):
            sys.exit("kill-rank/lose-rank are multihost faults and this "
                     "CLI is one process; drive them with __graft_entry__"
                     ".dryrun_multihost_supervised / "
                     "dryrun_multihost_elastic")
        if any(f.kind == "corrupt-ckpt" for f in faults) \
                and not args.ckpt_dir:
            sys.exit("--fault corrupt-ckpt requires --ckpt-dir (no "
                     "checkpoint is ever written without one)")
    if args.max_rollbacks is not None:
        if args.max_rollbacks < 0:
            sys.exit("--max-rollbacks must be >= 0")
        if not args.ckpt_dir:
            sys.exit("--max-rollbacks requires --ckpt-dir (rollback "
                     "restores the last good checkpoint)")
    if args.faults is not None:
        from .sim.faults import FAULT_REGIMES
        if args.faults not in FAULT_REGIMES:
            sys.exit(f"unknown --faults regime {args.faults!r}; known: "
                     f"{sorted(FAULT_REGIMES)}")
    if args.domains is not None:
        from .domains import DOMAIN_REGIMES
        if args.domains not in DOMAIN_REGIMES:
            sys.exit(f"unknown --domains regime {args.domains!r}; known: "
                     f"{sorted(DOMAIN_REGIMES)}")
    if args.mesh != "off" and args.mesh != "auto" \
            and not re.fullmatch(r"\d+x\d+x\d+", args.mesh):
        sys.exit(f"bad --mesh {args.mesh!r}: expected off, auto, or an "
                 f"explicit PxDxM axis triple like 1x2x1")
    if not args.async_run:
        for flag, val, default in (("--actor-devices",
                                    args.actor_devices, None),
                                   ("--learner-devices",
                                    args.learner_devices, None),
                                   ("--staleness-bound",
                                    args.staleness_bound, 1),
                                   ("--queue-capacity",
                                    args.queue_capacity, 2)):
            if val != default:
                sys.exit(f"{flag} configures the async engine; pass "
                         f"--async with it (refusing the silent no-op)")
    else:
        if args.staleness_bound < 0:
            sys.exit("--staleness-bound must be >= 0")
        if args.queue_capacity < 1:
            sys.exit("--queue-capacity must be >= 1")
    if args.continual is None:
        for flag, val, default in (
                ("--continual-trust", args.continual_trust, 2.0),
                ("--continual-rho-max", args.continual_rho_max, 8.0)):
            if val != default:
                sys.exit(f"{flag} tunes the --continual ingest trust "
                         f"region; pass --continual LOGDIR with it "
                         f"(refusing the silent no-op)")
    else:
        if args.continual_trust < 1.0:
            sys.exit("--continual-trust must be >= 1.0 (the region is "
                     "[1/T, T])")
        if args.continual_rho_max <= 0:
            sys.exit("--continual-rho-max must be positive")
    if args.alarms and not args.obs_dir:
        sys.exit("--alarms requires --obs-dir (alarm events need an "
                 "event stream to land in)")
    if args.trace_spans and not args.obs_dir:
        sys.exit("--trace-spans requires --obs-dir (span events need an "
                 "event stream to land in)")
    if args.alarm_slow_iter is not None:
        if not args.alarms:
            sys.exit("--alarm-slow-iter is an alarm trigger; pass "
                     "--alarms (and --obs-dir) with it")
        if args.alarm_slow_iter <= 0:
            sys.exit("--alarm-slow-iter must be positive")
    cfg = apply_overrides(CONFIGS[args.config], args)
    # the ONE mode-combination gate: every pairwise refusal lives in
    # configs.MODE_REFUSALS (one validated table, one error format)
    # instead of per-flag checks scattered through this function
    try:
        validate_mode_combination({
            "async": args.async_run,
            "pbt": args.pbt,
            "faults": args.faults is not None,
            "domains": cfg.domains is not None,
            "fault_injection": bool(faults),
            "fused_chunk": args.fused_chunk > 1,
            "rollbacks": args.max_rollbacks is not None,
            "hier": cfg.n_pods > 1,
            "mesh": args.mesh != "off",
            # resolved AFTER overrides so a preset with
            # correction="vtrace" is gated the same as the flag
            "vtrace": cfg.algo == "ppo" and cfg.ppo.correction == "vtrace",
            "sync": not args.async_run,
            # NOT the "vtrace" flag: continual FORCES the correction
            # internally against measured serving lag, which is exactly
            # the case the vtrace x sync refusal (ratios == 1 on-policy)
            # does not cover
            "continual": args.continual is not None,
        })
    except ModeCombinationError as e:
        sys.exit(str(e))
    if args.continual is not None and cfg.algo != "ppo":
        sys.exit("--continual retrains through the V-trace-corrected "
                 "PPO pipeline; the A2C update has no importance-"
                 "corrected variant")
    if args.source_jobs is not None:
        if args.source_jobs <= 0:
            sys.exit("--source-jobs must be positive")
        if cfg.trace in ("philly", "pai"):
            sys.exit("--source-jobs sizes GENERATED traces; a CSV trace "
                     "is its file's own size (refusing the silent no-op)")

    import contextlib

    from .utils import MetricsLogger, profiling
    from .utils.platform import enable_compile_cache

    enable_compile_cache()

    with contextlib.ExitStack() as stack:
        # telemetry first: its event bus threads through the checkpoint
        # store, watchdog and injector below (and the ExitStack closes
        # it LAST, so their teardown events still have a live bus)
        telemetry = None
        bus = None
        if args.obs_dir:
            import os

            from .obs import RunTelemetry
            telemetry = stack.enter_context(RunTelemetry(
                os.path.abspath(args.obs_dir), rank=0,
                alarms=args.alarms, slow_iter_s=args.alarm_slow_iter,
                trace=args.trace_spans))
            bus = telemetry.bus
        ckpt = None
        if args.ckpt_dir:
            from .checkpoint import Checkpointer
            import os
            ckpt = Checkpointer(os.path.abspath(args.ckpt_dir),
                                max_to_keep=args.ckpt_keep or 3, bus=bus)
        # --resume APPENDS to the existing metrics CSV (header re-read +
        # schema-validated) instead of truncating the history a relaunch
        # is trying to continue
        csv_logger = stack.enter_context(
            MetricsLogger(args.log_csv, echo=args.log_every > 0,
                          append=args.resume))
        logger = csv_logger
        if args.tb_dir:
            from .utils import TensorBoardWriter
            tb = stack.enter_context(TensorBoardWriter(args.tb_dir))

            def logger(i, m, _csv=csv_logger, _tb=tb):
                _csv(i, m)
                _tb(i, m)
        if args.profile_dir:
            stack.enter_context(profiling.trace(args.profile_dir))
        if args.debug_nans:
            stack.enter_context(profiling.debug_checks())
        if ckpt is not None:
            stack.enter_context(ckpt)

        run_mesh = None
        if args.pbt:
            from .experiment import PopulationExperiment
            from .parallel import PBTConfig
            # the async population runner owns placement (member stacks
            # replicated on the actor/learner group meshes), so the
            # unified pop mesh stays a sync-path construct
            run_mesh = None if args.async_run else make_pop_mesh(args.n_pop)
            exp = PopulationExperiment.build(
                cfg, n_pop=args.n_pop, mesh=run_mesh,
                pbt_cfg=PBTConfig(ready_iters=args.pbt_ready,
                                  seed=cfg.seed),
                telemetry=telemetry)
        else:
            from .experiment import Experiment
            run_mesh = make_run_mesh(args.mesh, cfg.n_envs)
            exp = Experiment.build(cfg, mesh=run_mesh, telemetry=telemetry)
        if run_mesh is not None:
            from .parallel import rule_table_hash, rules_for
            print(f"mesh: {dict(run_mesh.shape)} rules="
                  f"{rule_table_hash(rules_for(cfg))}", file=sys.stderr)
        trunk_record = None
        if cfg.obs_kind == "tokens":
            # what the trunk's configuration fixes (layers of each kind,
            # chunk, expert groups): said once, not logged an iteration
            from .models.trunk import TRUNKS, describe
            trunk_record = {"name": cfg.trunk, **describe(TRUNKS[cfg.trunk])}
            print(f"trunk: {json.dumps(trunk_record)}", file=sys.stderr)
        if args.resume:
            if ckpt is None:
                sys.exit("--resume requires --ckpt-dir")
            meta = exp.restore_checkpoint(ckpt)
            # last_restored_step, not latest_step: the integrity fallback
            # may have restored an older retained step than the newest dir
            print(f"resumed from step {ckpt.last_restored_step} ({meta})",
                  file=sys.stderr)

        if args.continual is not None:
            import os

            from .flywheel import FlightLogError, run_continual
            from .obs import Registry
            registry = (telemetry.registry if telemetry is not None
                        else Registry())
            try:
                summary = run_continual(
                    exp, os.path.abspath(args.continual),
                    iterations=(args.iterations
                                if args.iterations is not None else 1),
                    trust=args.continual_trust,
                    rho_max_cap=args.continual_rho_max,
                    registry=registry, ckpt=ckpt)
            except FlightLogError as e:
                sys.exit(f"continual ingest refused: {e}")
            print(f"continual: {summary['shards_accepted']}/"
                  f"{summary['shards_seen']} shards admitted "
                  f"({summary['shards_refused']} refused by the trust "
                  f"region), {summary['rows_trained']} rows as "
                  f"{summary['pseudo_steps']} pseudo-steps x "
                  f"{summary['iterations']} iterations -> step "
                  f"{summary['final_step']}", file=sys.stderr)
            print(json.dumps(summary))
            return summary

        eval_kw = {}
        if args.eval_every:
            probe_exp = FittestMemberView(exp) if args.pbt else exp
            probe = make_eval_probe(cfg, probe_exp, args.eval_windows,
                                    args.eval_seed, regime=args.eval_probe)
            if args.keep_best:
                from .checkpoint import Checkpointer
                import os
                best_ckpt = stack.enter_context(Checkpointer(
                    os.path.join(os.path.abspath(args.ckpt_dir), "best"),
                    max_to_keep=1, bus=bus))
                best = {"jct": float("inf")}
                if best_ckpt.latest_step() is not None:
                    # a resumed run must not rotate out a prior run's
                    # genuinely-best checkpoint with its own first probe:
                    # recover the bar from the saved meta
                    best["jct"] = float(best_ckpt.read_meta().get(
                        "eval_avg_jct", float("inf")))
                    print(f"keep-best: prior best eval_avg_jct="
                          f"{best['jct']:.1f}", file=sys.stderr)

                def probe(i, _inner=probe):
                    m = dict(_inner(i))
                    improved = (m["eval_completion"] >= 1.0 and
                                m["eval_avg_jct"] < best["jct"])
                    if improved:
                        # force: a resumed run can revisit a step number
                        # the best dir already holds; a silently-skipped
                        # save would leave stale params labeled with the
                        # new probe result
                        exp.save_checkpoint(
                            best_ckpt,
                            meta={"iteration": i,
                                  "eval_avg_jct": m["eval_avg_jct"]},
                            force=True)
                        best["jct"] = m["eval_avg_jct"]
                    m["eval_is_best"] = float(improved)
                    return m
            eval_kw = dict(
                eval_every=args.eval_every, eval_fn=probe,
                eval_logger=stack.enter_context(
                    MetricsLogger(args.log_csv + ".eval.csv"
                                  if args.log_csv else None, echo=True,
                                  append=args.resume)))

        run_kw = {}
        if args.fused_chunk > 1:
            run_kw["fused_chunk"] = args.fused_chunk
        if args.max_rollbacks is not None:
            from .resilience import DivergenceWatchdog
            run_kw["watchdog"] = DivergenceWatchdog(
                max_rollbacks=args.max_rollbacks, bus=bus)
        if faults:
            from .resilience import FaultInjector
            run_kw["injector"] = FaultInjector(faults, bus=bus)
        if telemetry is not None:
            run_kw["telemetry"] = telemetry
        from .resilience import DivergenceError
        try:
            if args.async_run:
                from .parallel import split_devices
                groups = split_devices(actor=args.actor_devices,
                                       learner=args.learner_devices)
                print(f"async actor-learner: {groups.describe()} "
                      f"staleness_bound={args.staleness_bound} "
                      f"queue_capacity={args.queue_capacity}",
                      file=sys.stderr)
                out = exp.run_async(
                    groups=groups, staleness_bound=args.staleness_bound,
                    queue_capacity=args.queue_capacity,
                    log_every=args.log_every, logger=logger,
                    ckpt=ckpt, ckpt_every=args.ckpt_every, **eval_kw,
                    **run_kw)
            else:
                out = exp.run(log_every=args.log_every, logger=logger,
                              ckpt=ckpt, ckpt_every=args.ckpt_every,
                              **eval_kw, **run_kw)
        except DivergenceError as e:
            # the watchdog's clean give-up: budget exhausted, state rolled
            # back — a non-zero exit with the reason, not a traceback
            sys.exit(f"divergence watchdog gave up: {e}")

        summary = {k: v for k, v in out.items() if k != "history"}
        # from the process's start to the end of its first run call, by
        # part (import, backend, build, tracing and lowering, compiling,
        # cache loads, running): obs.startup
        from .obs import startup
        summary["startup"] = startup.first_run_summary()
        if trunk_record is not None:
            summary["trunk"] = trunk_record
        if run_mesh is not None:
            from .parallel import rule_table_hash, rules_for
            summary["mesh"] = {
                "shape": {k: int(v) for k, v in run_mesh.shape.items()},
                "rule_table_hash": rule_table_hash(rules_for(cfg))}
        if args.report and not args.pbt and cfg.n_pods == 1:
            from .eval import format_report, jct_report
            report = jct_report(exp)
            print(format_report(report), file=sys.stderr)
            summary["jct_report"] = {k: v for k, v in report.items()
                                     if isinstance(v, (int, float))}
        print(json.dumps(summary))
        return summary


if __name__ == "__main__":
    main()
