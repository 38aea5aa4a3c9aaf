"""Named experiment configs (L6).

Capability parity: SURVEY.md §2 "Config/flags" and §0 — dataclass configs
with named presets matching the five driver-specified capability configs
exactly (SURVEY.md §5 "Config / flag system").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Literal

_t_import = time.monotonic()
from .algos.a2c import A2CConfig
from .algos.ppo import PPOConfig
from .models.trunk import TRUNKS

from . import stamp

# where the CLIs pay for jax, optax and flax (obs.startup)
stamp("import", _t_import)

# the token trunks by name, for every CLI's --trunk (one list: the
# registry's own)
TRUNK_NAMES = tuple(TRUNKS)


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    name: str
    algo: Literal["ppo", "a2c"] = "ppo"
    # cluster
    n_nodes: int = 8
    gpus_per_node: int = 8
    # trace source. "philly"/"pai" parse a real CSV at trace_path;
    # "philly-proxy"/"pai-proxy" generate a seeded trace with the published
    # Philly/PAI workload statistics (traces/philly_proxy.py) so the
    # large-cluster configs run end-to-end with no external file
    # (VERDICT r2 missing #3).
    trace: Literal["synthetic", "philly", "pai",
                   "philly-proxy", "pai-proxy"] = "synthetic"
    trace_path: str | None = None
    trace_load: float = 1.1             # proxy traces: offered load target
    # generated traces (synthetic / *-proxy): pin the SOURCE trace size in
    # jobs. None = sized to one window-streaming pass over the env batch
    # (window_jobs * max(n_envs, 8), floored at 1024/4096). The north-star
    # full-Philly run pins this at 100k+ so "the whole trace" is explicit
    # rather than implied by the batch geometry.
    source_jobs: int | None = None
    arrival_rate: float = 0.08          # synthetic: jobs/sec
    mean_duration: float = 600.0        # synthetic: log-normal mean
    window_jobs: int = 64               # jobs per episode window (max_jobs)
    # env
    n_envs: int = 4
    queue_len: int = 8
    n_placements: int = 1
    preempt_len: int = 0                # >0 = preemptive RL action space
    n_pods: int = 1                     # >1 = hierarchical env (config 5)
    obs_kind: Literal["flat", "grid", "graph", "tokens"] = "flat"
    # obs_kind "tokens": which family of blocks at which whole set of
    # sizes, one of TRUNK_NAMES (models.trunk.TRUNKS): "published" /
    # "tiny" the afmoe blocks at the source model's widths / the CPU
    # tests' shape, "ling" / "ling-tiny" the linear-attention blocks and
    # "ouro" / "ouro-tiny" the looped dense blocks likewise. No flag sets
    # a single width or a loop count.
    trunk: str = "published"
    reward_kind: Literal["jct", "fair"] = "jct"
    n_tenants: int = 1
    nodes_per_rack: int | None = None   # graph topology granularity
    horizon: int = 512
    time_scale: float = 600.0
    reward_scale: float = 10_000.0
    place_bonus: float = 0.05   # shaping vs the idle local optimum (rewards.py)
    # preemptive configs: reward charge per preemption AND per
    # re-placement. Without it the agent can stall the clock forever in
    # a zero-dt place<->preempt cycle (the pause-the-game exploit,
    # measured: a 3000-iteration preempt run completed ZERO jobs at
    # replay); an under-priced charge (0.05) measurably left stalling
    # return-optimal under discounting — see rewards.preempt_charge for
    # the magnitude analysis behind 0.25.
    preempt_cost: float = 0.25
    # training
    ppo: PPOConfig = PPOConfig()
    a2c: A2CConfig = A2CConfig()
    iterations: int = 100
    seed: int = 0
    # window streaming: every N iterations rotate every env onto the next
    # windows of the source-trace tiling (and reset episodes), so a long
    # run trains on the WHOLE trace instead of replaying the first
    # n_envs windows forever. 0 = static windows (round-1 behavior).
    resample_every: int = 0
    # backlog-drain curriculum: this fraction of the env batch trains on
    # DRAINED copies of its windows (every submit zeroed, so the episode
    # is "drain a full backlog"). Ordering/packing decisions carry the
    # whole JCT signal there — measured in round 3, a drain-trained
    # config-1 policy beats oracle SJF on drain episodes and transfers to
    # streaming windows (vs_tiresias 0.81), while pure streaming training
    # plateaus at random-order quality (credit assignment: a placement's
    # JCT consequence lands hundreds of steps later).
    drain_frac: float = 0.0
    # cluster chaos (sim.faults): train on a seeded in-simulator fault
    # distribution — per-env FaultSchedules (node drains, drain storms,
    # stragglers) sampled from this named regime (FAULT_REGIMES) and
    # threaded through the rollout next to the traces. Flat configs also
    # expose per-node health in the observation so the policy can LEARN
    # to route around drains. None = permanently healthy cluster.
    faults: str | None = None
    # domain randomization (domains.schedule): train across a named
    # scenario DISTRIBUTION (DOMAIN_REGIMES) — per-env cluster geometry,
    # hardware speed, and arrival-process draws threaded through the
    # rollout as data next to the traces, composing with cfg.faults.
    # None = the single fixed cluster, bit-identical.
    domains: str | None = None

    @property
    def total_gpus(self) -> int:
        return self.n_nodes * self.gpus_per_node


# The five driver-specified capability configs (SURVEY.md §0, `[B]`).
CONFIGS: dict[str, ExperimentConfig] = {}


def _register(cfg: ExperimentConfig) -> ExperimentConfig:
    CONFIGS[cfg.name] = cfg
    return cfg


# 1. PPO-MLP scheduler, 64-GPU synthetic Poisson trace, 4 vectorized envs.
PPO_MLP_SYNTH64 = _register(ExperimentConfig(
    name="ppo-mlp-synth64", algo="ppo", n_nodes=8, gpus_per_node=8,
    trace="synthetic", n_envs=4, obs_kind="flat"))

# 2. PPO-CNN on Microsoft Philly trace, 512-GPU simulated cluster.
# Ships on the Philly-statistics proxy so it runs with no external CSV
# (none can exist on this machine); pass --trace philly --trace-path x.csv
# to train on the real trace instead.
PPO_CNN_PHILLY512 = _register(ExperimentConfig(
    name="ppo-cnn-philly512", algo="ppo", n_nodes=64, gpus_per_node=8,
    trace="philly-proxy", n_envs=8, obs_kind="grid", window_jobs=128,
    queue_len=16, horizon=1024))

# Config 2's cluster, trace, reward and PPO numbers under a token policy:
# one token per node and per job of the window through sparse-expert
# transformer blocks at a public model's widths (models.trunk; one chip's
# share of a 16-chip expert layout). The policy reads the whole backlog,
# not its queue_len oldest jobs; the action space is still the queue
# view's. Train on a CPU host with --trunk tiny.
PPO_TRINITY_PHILLY512 = _register(dataclasses.replace(
    PPO_CNN_PHILLY512, name="ppo-trinity-philly512", obs_kind="tokens"))

# The same again under the second family of token blocks: five
# linear-attention (KDA) layers and one latent-attention (MLA) layer a
# period, 512 experts chosen by groups (one chip's share of a 64-chip
# expert layout). Train on a CPU host with --trunk ling-tiny.
PPO_LING_PHILLY512 = _register(dataclasses.replace(
    PPO_TRINITY_PHILLY512, name="ppo-ling-philly512", trunk="ling"))

# And under the third: one stack of dense multi-head-attention layers
# applied several times with the same weights, an exit gate a step (one
# stage of a six-stage pipeline). Train on a CPU host with --trunk
# ouro-tiny. Its Adam step is the other token presets' over the loop
# count: Adam moves a weight by about lr a step whatever its gradient,
# and here every weight acts four times a pass, so at 3e-4 one
# iteration moved the policy by an approximate KL of 0.2 with three
# quarters of the ratios clipped (PERF.md section 6, PR 41).
PPO_OURO_PHILLY512 = _register(dataclasses.replace(
    PPO_TRINITY_PHILLY512, name="ppo-ouro-philly512", trunk="ouro",
    ppo=dataclasses.replace(PPO_TRINITY_PHILLY512.ppo,
                            lr=PPO_TRINITY_PHILLY512.ppo.lr / 4)))

# 3. A2C multi-actor on Alibaba PAI trace, multi-tenant fairness reward.
# Same proxy arrangement as config 2 (PAI-statistics preset).
A2C_PAI_FAIR = _register(ExperimentConfig(
    name="a2c-pai-fair", algo="a2c", n_nodes=16, gpus_per_node=8,
    trace="pai-proxy", n_envs=16, obs_kind="flat", reward_kind="fair",
    n_tenants=8, window_jobs=96))

# 4. GNN policy over cluster topology, gang-scheduling + placement actions.
GNN_GANG_PLACE = _register(ExperimentConfig(
    name="gnn-gang-place", algo="ppo", n_nodes=16, gpus_per_node=8,
    trace="synthetic", n_envs=4, obs_kind="graph", n_placements=2,
    nodes_per_rack=4, window_jobs=64))

# Preemptive variant of config 1: the agent can also evict the R most-
# attained running jobs (sim.core.running_queue), like Tiresias' demotions
# but learned (VERDICT r1 missing #5 — Tiresias preempts, so a policy that
# cannot is handicapped on overloaded traces).
PPO_MLP_PREEMPT = _register(ExperimentConfig(
    name="ppo-mlp-preempt", algo="ppo", n_nodes=8, gpus_per_node=8,
    trace="synthetic", n_envs=4, obs_kind="flat", preempt_len=4))

# 5. Hierarchical multi-agent across 4 pods + PBT: each population member
# IS a hierarchical agent (top-level router + shared per-pod placers) over
# a 4-pod cluster; PopulationExperiment runs a PBT population of these
# (parallel.population / parallel.pbt).
HIER_PBT_MEMBER = _register(ExperimentConfig(
    name="hier-pbt-member", algo="ppo", n_nodes=16, gpus_per_node=8,
    n_pods=4, trace="synthetic", n_envs=4, obs_kind="flat",
    window_jobs=64))


class ModeCombinationError(ValueError):
    """Two requested run modes are mutually unsupported (the single
    refusal format `train` reports — see :data:`MODE_REFUSALS`)."""


# How each mode name is spelled to the user in refusal messages.
MODE_FLAGS: dict[str, str] = {
    "async": "--async",
    "pbt": "--pbt",
    "faults": "--faults",
    "domains": "--domains",
    "fault_injection": "--fault",
    "fused_chunk": "--fused-chunk",
    "rollbacks": "--max-rollbacks",
    "hier": "hierarchical config (n_pods > 1)",
    "shard_map": "shard_map/axis_name build",
    "mesh": "--mesh",
    "vtrace": "--correction vtrace",
    "sync": "the synchronous loop (no --async)",
    "router": "--engines > 1 (multi-engine serving router)",
    "continual": "--continual LOGDIR (flight-log retraining)",
}

# THE mode-combination refusal matrix — every pairwise refusal `train`
# (or a programmatic caller) enforces, in one place with one error
# format, instead of the per-flag sys.exit checks that used to be
# scattered through train.main. Order within a pair is cosmetic; the
# check is symmetric. Each entry: (mode_a, mode_b, why-it-refuses).
MODE_REFUSALS: tuple[tuple[str, str, str], ...] = (
    # async x pbt was refused here until ISSUE 12: AsyncPopulationRunner
    # now runs PBT exploit/explore at drained-queue barriers, with
    # V-trace keeping stale batches from skewing the fitness ranking
    ("vtrace", "sync",
     "importance correction divides the target policy by the behavior "
     "policy; the sync loop collects every batch on-policy (ratios are "
     "identically 1), so --correction vtrace without --async would only "
     "buy the extra forward pass — the bit-identity contract makes this "
     "a no-op, refuse it loudly instead"),
    ("vtrace", "hier",
     "the hierarchical joint log-prob sums router+placer heads; the "
     "V-trace ratio recompute has not been validated against the "
     "multi-head action distribution yet"),
    ("async", "fused_chunk",
     "the async engine already overlaps phases — pick one"),
    ("async", "rollbacks",
     "the divergence watchdog is sync-path-only for now"),
    ("async", "fault_injection",
     "fault injection hooks the sync loop's iteration boundary"),
    ("async", "mesh",
     "the async engine resolves its own actor/learner submeshes from "
     "the unified mesh"),
    # pbt x faults was refused here until ISSUE 14: the population step
    # now threads per-member [P, E] fault schedules (seeded (seed,
    # member, env)) through the vmapped member rollout
    ("pbt", "domains",
     "per-member domain draws would need member-indexed trace windows "
     "through the population stack; sample domain diversity across "
     "single-run seeds instead"),
    ("hier", "domains",
     "domain schedules carry per-node capacity through the flat sim "
     "path only; the pod-sharded hierarchical env has no geometry "
     "threading yet"),
    ("pbt", "fused_chunk",
     "the PBT loop interleaves host-side exploit/explore between steps"),
    ("pbt", "mesh",
     "--pbt builds the population mesh from the unified mesh "
     "automatically"),
    ("hier", "faults",
     "sim faults thread per-node health through flat observations only"),
    ("shard_map", "pbt",
     "the population step is a GSPMD vmap, not an axis-name program"),
    ("shard_map", "async",
     "the async engine jits per-group GSPMD programs, not shard_map"),
    ("shard_map", "fused_chunk",
     "run_fused jits the raw step; an axis-name step needs "
     "dp.shard_map_train"),
    ("shard_map", "mesh",
     "rule-table shardings are GSPMD in/out_shardings; the axis-name "
     "path wires its own specs in dp.shard_map_train"),
    ("router", "hier",
     "the engine router resolves one single-device engine per data-axis "
     "device; a hierarchical (n_pods > 1) policy's router+placer heads "
     "have not been validated under per-engine replicated serving — "
     "serve hierarchical configs single-engine until they are"),
    # continual mode (ISSUE 19 flywheel) replaces simulator rollouts
    # with logged served traffic: the data source IS the mode, so every
    # combination that reshapes the rollout/update loop is refused
    ("continual", "pbt",
     "continual ingest folds ONE flight log into one learner's "
     "pseudo-trajectories; a population would train every member on "
     "the same behavior stream (no per-member exploration signal)"),
    ("continual", "async",
     "the async engine overlaps simulator rollout collection with the "
     "update; continual mode has no rollout to overlap — the flight "
     "log is read once up front"),
    ("continual", "hier",
     "logged rows carry the flat policy's action heads; the "
     "hierarchical joint log-prob has not been validated against "
     "flight-log replay (same gap as vtrace x hier)"),
    ("continual", "fused_chunk",
     "run_fused scans the simulator train step; continual updates run "
     "their own jitted learn step over a fixed ingested batch"),
)


def _validate_refusal_table() -> None:
    """The table is validated at import: a typo'd mode name would
    otherwise silently never refuse anything."""
    for a, b, why in MODE_REFUSALS:
        for m in (a, b):
            if m not in MODE_FLAGS:
                raise AssertionError(
                    f"MODE_REFUSALS names unknown mode {m!r} (known: "
                    f"{sorted(MODE_FLAGS)})")
        if a == b or not why:
            raise AssertionError(f"malformed refusal entry {(a, b, why)!r}")


_validate_refusal_table()


def validate_mode_combination(active: dict[str, bool]) -> None:
    """Raise :class:`ModeCombinationError` if any two ACTIVE modes are a
    refused pair. ``active`` maps mode names (:data:`MODE_FLAGS` keys) to
    whether the run requests them; unknown names raise (fail-loud — a
    misspelled key would otherwise never be checked)."""
    unknown = set(active) - set(MODE_FLAGS)
    if unknown:
        raise KeyError(f"unknown mode name(s) {sorted(unknown)}; known: "
                       f"{sorted(MODE_FLAGS)}")
    for a, b, why in MODE_REFUSALS:
        if active.get(a) and active.get(b):
            raise ModeCombinationError(
                f"unsupported mode combination: {MODE_FLAGS[a]} × "
                f"{MODE_FLAGS[b]} — {why}")


def repro_tuple(cfg: ExperimentConfig, ckpt_dir: str | None = None,
                ckpt_step: int | None = None) -> dict:
    """The reproducibility tuple every evaluate/serve JSON carries: the
    resolved config fields that determine a replay plus the checkpoint
    provenance — enough to regenerate any reported row exactly. ONE
    definition shared by ``evaluate`` and ``serve`` so serving numbers
    are reproducible the same way evaluation numbers are (PR 7).

    ``ckpt_step`` must be the RESOLVED restored step
    (``Checkpointer.last_restored_step``), not the requested one: the
    integrity fallback may restore an older retained step than asked
    for, and the tuple exists to name what actually ran."""
    return {"config": cfg.name, "seed": cfg.seed, "trace": cfg.trace,
            "trace_path": cfg.trace_path, "trace_load": cfg.trace_load,
            "source_jobs": cfg.source_jobs, "n_envs": cfg.n_envs,
            "n_nodes": cfg.n_nodes, "gpus_per_node": cfg.gpus_per_node,
            "window_jobs": cfg.window_jobs, "queue_len": cfg.queue_len,
            "horizon": cfg.horizon, "obs_kind": cfg.obs_kind,
            "trunk": cfg.trunk,
            "drain_frac": cfg.drain_frac, "faults": cfg.faults,
            "domains": cfg.domains,
            "ckpt_dir": ckpt_dir, "ckpt_step": ckpt_step}
