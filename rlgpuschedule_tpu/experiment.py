"""Experiment assembly (L6): config -> traces + env + policy + train loop.

Capability parity: SURVEY.md §3.1 — the `train()` call stack: build trace,
make vectorized envs, build policy, run the trainer loop, log metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

_t_import = time.monotonic()
import jax
import jax.numpy as jnp

from .algos import (init_carry, make_a2c_step, make_ppo_step,
                    make_train_state, resolve_geometry)
from .algos.rollout import RolloutCarry
from .algos.ppo import make_optimizer
from .configs import ExperimentConfig
from .env import EnvParams, build_adjacency, stack_traces
from .models import make_policy
from .domains import (domain_schedule, resolve_domain, sample_env_domains,
                      stack_domain_schedules, validate_domain_schedule)
from .sim.core import SimParams, validate_trace
from .sim.faults import (fault_horizon, resolve_regime,
                         sample_env_fault_schedules, sample_fault_schedule)
from .traces import (ArrayTrace, gen_domain_window, gen_poisson_trace,
                     load_pai, load_philly)
from .traces.fit import domain_fit
from flax.training.train_state import TrainState

from . import stamp
from .obs import startup

# where a library caller pays for jax, flax, optax and every layer's module
stamp("import", _t_import)
phase = startup.ACCOUNT.phase    # a set-up phase of the start-up account


def build_env_params(cfg: ExperimentConfig) -> EnvParams:
    sim = SimParams(n_nodes=cfg.n_nodes, gpus_per_node=cfg.gpus_per_node,
                    max_jobs=cfg.window_jobs, queue_len=cfg.queue_len,
                    n_placements=cfg.n_placements,
                    preempt_len=cfg.preempt_len)
    fault_process = resolve_regime(cfg.faults) if cfg.faults else None
    domain_process = resolve_domain(cfg.domains) if cfg.domains else None
    return EnvParams(sim=sim, obs_kind=cfg.obs_kind,
                     reward_kind=cfg.reward_kind, n_tenants=cfg.n_tenants,
                     time_scale=cfg.time_scale, reward_scale=cfg.reward_scale,
                     place_bonus=cfg.place_bonus,
                     preempt_cost=cfg.preempt_cost, horizon=cfg.horizon,
                     fault_process=fault_process,
                     # per-node health rides the FLAT observation only
                     # (grid/graph pin their feature layouts); those
                     # encoders still train on fault dynamics, blind to
                     # which node is sick. Domain runs always carry a
                     # DomainSchedule (with a possibly-heterogeneous
                     # slowdown) so they get the health channel too
                     fault_obs=((fault_process is not None
                                 or domain_process is not None)
                                and cfg.obs_kind == "flat"),
                     domain_process=domain_process,
                     domain_obs=(domain_process is not None
                                 and cfg.obs_kind == "flat"))


def load_source_trace(cfg: ExperimentConfig, n_jobs: int | None = None,
                      seed: int | None = None) -> ArrayTrace:
    """The full source trace this experiment schedules."""
    seed = cfg.seed if seed is None else seed
    if cfg.trace in ("synthetic", "philly-proxy", "pai-proxy"):
        # cfg.source_jobs pins GENERATED traces only (its documented
        # scope); a CSV load is the file's own size (n_jobs caps it)
        n_jobs = n_jobs or cfg.source_jobs
    if cfg.trace == "synthetic":
        n = n_jobs or max(cfg.window_jobs * max(cfg.n_envs, 8), 1024)
        return gen_poisson_trace(cfg.arrival_rate, n, seed,
                                 mean_duration=cfg.mean_duration,
                                 n_tenants=max(cfg.n_tenants, 1))
    if cfg.trace in ("philly-proxy", "pai-proxy"):
        from .traces import gen_pai_proxy_trace, gen_philly_proxy_trace
        n = n_jobs or max(cfg.window_jobs * max(cfg.n_envs, 8), 4096)
        gen = (gen_philly_proxy_trace if cfg.trace == "philly-proxy"
               else gen_pai_proxy_trace)
        kw = {}
        if cfg.n_tenants:       # keep tenant ids inside the env's bins
            kw["n_tenants"] = cfg.n_tenants
        return gen(n, seed, n_gpus=cfg.total_gpus, load=cfg.trace_load,
                   max_gang=cfg.total_gpus, **kw)
    if cfg.trace_path is None:
        raise ValueError(
            f"config {cfg.name!r} uses trace={cfg.trace!r} but has no "
            f"trace_path; pass one (CSV) or use trace='synthetic'")
    loader = load_philly if cfg.trace == "philly" else load_pai
    return loader(cfg.trace_path, max_jobs=n_jobs)


def build_stack(cfg: ExperimentConfig):
    """Shared assembly for single-run and population experiments: trace
    load/validate/window/stack + policy net + (obs, mask) apply closure.
    Returns (env_params, windows, traces [E, ...], net, apply_fn, extra,
    source) where ``extra`` are the apply args between obs and mask (the
    GNN's adjacency) and ``source`` is the full validated source trace
    (window streaming re-cuts windows from it). ``cfg.n_pods > 1`` selects
    the hierarchical env + policy (config 5) — env_params is then a
    ``env.hier.HierParams``."""
    if cfg.n_pods > 1:
        from .env import hier as hier_lib   # registers the vec dispatch
        from .models.hier import HierActorCritic
        if cfg.faults:
            raise ValueError(
                "hierarchical configs have no fault-process support yet "
                "(sim.faults is a flat-config feature); unset faults")
        if cfg.domains:
            raise ValueError(
                "hierarchical configs have no domain-randomization "
                "support yet (domain schedules carry per-node capacity "
                "through the flat sim path only); unset domains")
        if cfg.n_nodes % cfg.n_pods != 0:
            raise ValueError(f"n_nodes={cfg.n_nodes} not divisible by "
                             f"n_pods={cfg.n_pods}")
        if cfg.obs_kind != "flat" or cfg.reward_kind != "jct":
            raise ValueError(
                f"hierarchical configs use flat pod observations and the "
                f"JCT reward; got obs_kind={cfg.obs_kind!r}, "
                f"reward_kind={cfg.reward_kind!r}")
        if cfg.preempt_len:
            raise ValueError(
                "hierarchical configs do not support the preemptive action "
                "space (pod actions are queue-slot×placement + no-op); set "
                "preempt_len=0")
        pod_sim = SimParams(n_nodes=cfg.n_nodes // cfg.n_pods,
                            gpus_per_node=cfg.gpus_per_node,
                            max_jobs=cfg.window_jobs,
                            queue_len=cfg.queue_len,
                            n_placements=cfg.n_placements)
        env_params = hier_lib.HierParams(
            n_pods=cfg.n_pods, pod_sim=pod_sim, time_scale=cfg.time_scale,
            reward_scale=cfg.reward_scale, place_bonus=cfg.place_bonus,
            horizon=cfg.horizon)
        with phase("build_source"):
            source = validate_trace(pod_sim, load_source_trace(cfg),
                                    clamp=True)
        with phase("build_windows"):
            windows = make_env_windows(cfg, source)
        with phase("build_upload"):
            traces = stack_traces(windows, pod_sim)
        with phase("build_policy"):
            net = HierActorCritic(n_top_actions=env_params.n_top_actions,
                                  n_pod_actions=pod_sim.n_actions)
        apply_fn = lambda p, obs, mask: net.apply(p, obs, mask)
        return env_params, windows, traces, net, apply_fn, (), source

    env_params = build_env_params(cfg)
    with phase("build_source"):
        source = validate_trace(env_params.sim, load_source_trace(cfg),
                                clamp=True)
    with phase("build_windows"):
        if env_params.domain_process is not None:
            # domain windows are GENERATED per env from the config's
            # fitted job mix under each env's seeded domain draw (arrival
            # knobs + that draw's actual capacity), not cut from the
            # source — the source stays loaded so --full-trace/window
            # accounting on the same config keep working
            draws = sample_env_domains(
                env_params.domain_process, cfg.n_nodes, cfg.gpus_per_node,
                cfg.seed, cfg.n_envs)
            windows = make_domain_windows(cfg, draws)
        else:
            windows = make_env_windows(cfg, source)
    with phase("build_upload"):
        traces = stack_traces(windows, env_params)
    with phase("build_policy"):
        net = make_policy(cfg.obs_kind, env_params.n_actions,
                          n_cluster_nodes=cfg.n_nodes,
                          queue_len=cfg.queue_len,
                          n_placements=cfg.n_placements,
                          preempt_len=cfg.preempt_len, trunk=cfg.trunk)
    if cfg.obs_kind == "graph":
        adj = jnp.asarray(build_adjacency(cfg.n_nodes, cfg.queue_len,
                                          cfg.nodes_per_rack,
                                          cfg.preempt_len))
        apply_fn = lambda p, obs, mask: net.apply(p, obs, adj, mask)
        extra = (adj,)
    else:
        apply_fn = lambda p, obs, mask: net.apply(p, obs, mask)
        extra = ()
    if cfg.obs_kind == "tokens":
        # the same forward with the expert layers' counters read out:
        # the update's loss takes this one (algos.ppo.ppo_loss), so the
        # counters ride the iteration's metrics at no extra pass
        from .models.trunk import COUNTERS, read_counters

        def counted(p, obs, mask):
            out, sown = net.apply(p, obs, mask, mutable=[COUNTERS])
            return out, read_counters(sown[COUNTERS])

        apply_fn.counted = counted
    return env_params, windows, traces, net, apply_fn, extra, source


def windows_per_pass(total_jobs: int, window_jobs: int) -> int:
    """Windows in one full tiling pass over the trace (the last window is
    the final ``window_jobs`` jobs, so every job appears in some window)."""
    return max(-(-total_jobs // window_jobs), 1)


def drain_window(w: ArrayTrace) -> ArrayTrace:
    """A backlog-drain copy of a window: every job submitted at t=0, so
    the episode is purely "drain this backlog" — the regime where the
    ordering/packing decision carries the whole JCT signal (see
    ``ExperimentConfig.drain_frac``)."""
    import numpy as np
    return dataclasses.replace(
        w, submit=np.where(w.valid, 0.0, np.inf).astype(np.float32))


def make_env_windows(cfg: ExperimentConfig, source: ArrayTrace,
                     start: int = 0) -> list[ArrayTrace]:
    """Cut n_envs episode windows out of the source trace: windows
    ``start+e`` (e < n_envs) of a tiling of the trace by ``window_jobs``,
    wrapping around at the end of the trace. Advancing ``start`` by
    ``n_envs`` per resample therefore sweeps the ENTIRE trace every
    ``windows_per_pass / n_envs`` resamples — round 1 trained forever on
    the first n_envs windows (VERDICT r1 missing #3). Windows are
    demand-clamped by stack_traces at upload.

    With ``cfg.drain_frac > 0`` the LAST ``round(n_envs * drain_frac)``
    envs train on drained copies of their windows (the backlog-drain
    curriculum); streaming resamples keep the same envs drained."""
    total = source.num_jobs
    if total < cfg.window_jobs:
        raise ValueError(f"source trace has {total} jobs < window "
                         f"{cfg.window_jobs}")
    per_pass = windows_per_pass(total, cfg.window_jobs)
    windows = []
    for e in range(cfg.n_envs):
        k = (start + e) % per_pass
        off = min(k * cfg.window_jobs, total - cfg.window_jobs)
        windows.append(source.slice(off, cfg.window_jobs))
    n_drain = int(round(cfg.n_envs * cfg.drain_frac))
    for e in range(cfg.n_envs - n_drain, cfg.n_envs):
        windows[e] = drain_window(windows[e])
    return windows


def make_domain_windows(cfg: ExperimentConfig, draws, start: int = 0,
                        ) -> list[ArrayTrace]:
    """The domain-randomized twin of :func:`make_env_windows`: one
    GENERATED window per env from the config's fitted job mix
    (``traces.fit.domain_fit``) under that env's :class:`DomainDraw` —
    offered load against the draw's ACTUAL capacity, duration scaling,
    diurnal/burst arrivals, gang mix renormalized to what the shrunken
    cluster can place. ``start`` is the window-streaming cursor: window
    seeds are ``(cfg.seed, env, start)``, so advancing the cursor draws
    fresh windows of identical shape (no recompilation), and a
    checkpoint restore at a cursor regenerates bit-identical windows.
    The drain-curriculum tail works exactly like the env-window path."""
    fit = domain_fit(cfg)
    windows = []
    for e, d in enumerate(draws):
        total = d.total_gpus
        windows.append(gen_domain_window(
            fit, cfg.window_jobs, (cfg.seed, e, start), n_gpus=total,
            load=d.load, duration_scale=d.duration_scale,
            burst_frac=d.burst_frac, diurnal=d.diurnal, max_gang=total,
            n_tenants=max(cfg.n_tenants, 1)))
    n = len(windows)     # the matrix evaluates draw batches != n_envs
    n_drain = int(round(n * cfg.drain_frac))
    for e in range(n - n_drain, n):
        windows[e] = drain_window(windows[e])
    return windows


@dataclasses.dataclass
class Experiment:
    """Assembled experiment: jitted train step + host loop."""
    cfg: ExperimentConfig
    env_params: EnvParams
    windows: list            # host ArrayTrace windows (reused by eval)
    traces: Any              # batched device Trace [E, ...]
    net: Any
    apply_fn: Callable
    train_state: TrainState
    train_step: Callable     # jitted
    carry: Any
    key: jax.Array
    source: Any = None       # full source ArrayTrace (window streaming)
    window_cursor: int = 0   # first window index of the current env batch
    train_step_raw: Callable | None = None   # unjitted (for run_fused)
    _fused_jit: Callable | None = None       # lazy; jit caches per length
    # batched per-env sim.faults.FaultSchedule [E, ...] (cfg.faults), or
    # None = healthy cluster. DATA like the traces: threaded through the
    # jitted step as an argument, never closed over, so schedules can
    # change without recompiling. Under cfg.domains this slot holds the
    # batched domains.DomainSchedule instead (a strict superset the
    # fault consumers read field-by-field), composing any cfg.faults
    # draw into its windows/slowdown
    faults: Any = None
    # host list[domains.DomainDraw] (cfg.domains), or None: the per-env
    # draws behind self.faults, kept so window streaming can regenerate
    # windows under the SAME cluster draws at a new cursor
    domains: Any = None
    # unified Mesh(pop × data × model) the step was rule-sharded against
    # (parallel.sharding), or None = plain single-program jit
    mesh: Any = None

    @staticmethod
    @startup.recorded_build
    def build(cfg: ExperimentConfig, axis_name: str | None = None,
              jit: bool = True, mesh=None, *,
              telemetry=None) -> "Experiment":
        """Assemble the experiment. Its phases (``build_source`` ...
        ``build_step``, ``obs.startup``) go to the start-up account and,
        as ``rlsched:<phase>``, to a profile that is running; with
        ``telemetry`` (:class:`obs.RunTelemetry`, as :meth:`run` takes it)
        they are spans on its bus too."""
        env_params, windows, traces, net, apply_fn, extra, source = \
            build_stack(cfg)
        faults = None
        domains = None
        fp = getattr(env_params, "fault_process", None)
        with phase("build_upload"):    # the fault and domain schedules
            if getattr(env_params, "domain_process", None) is not None:
                # the SAME seeded draws build_stack generated windows from
                # (host sampling is deterministic in (seed, env)); the
                # device data is one batched DomainSchedule riding the
                # faults slot, composing any cfg.faults draw per env
                domains = sample_env_domains(
                    env_params.domain_process, cfg.n_nodes,
                    cfg.gpus_per_node, cfg.seed, cfg.n_envs)
                horizon_s = fault_horizon(windows)
                schedules = []
                for e, d in enumerate(domains):
                    f = (sample_fault_schedule(cfg.n_nodes, fp,
                                               (cfg.seed, e), horizon_s)
                         if fp is not None else None)
                    schedules.append(validate_domain_schedule(
                        cfg.n_nodes, cfg.gpus_per_node,
                        domain_schedule(d, f)))
                faults = stack_domain_schedules(schedules)
            elif fp is not None:
                # seeded per-env draws over the window batch's time span,
                # so drain windows intersect live episodes at every trace
                # scale
                faults = sample_env_fault_schedules(
                    cfg.n_nodes, fp, cfg.seed, cfg.n_envs,
                    fault_horizon(windows))
        algo_cfg = cfg.ppo if cfg.algo == "ppo" else cfg.a2c
        # fail fast on a geometry that cannot tile the rollout batch —
        # inside the jitted step the same check would surface as an
        # opaque reshape trace error
        resolve_geometry(algo_cfg.n_epochs, algo_cfg.n_minibatches,
                         algo_cfg.minibatch_size,
                         algo_cfg.n_steps * cfg.n_envs)
        if cfg.algo == "ppo":
            tx = make_optimizer(algo_cfg)
            step_fn = make_ppo_step(apply_fn, env_params, algo_cfg, axis_name)
        else:
            from .algos.a2c import make_optimizer as a2c_opt
            tx = a2c_opt(algo_cfg)
            step_fn = make_a2c_step(apply_fn, env_params, algo_cfg, axis_name)
        with phase("build_carry"):     # the keys' eager programs too
            key = jax.random.PRNGKey(cfg.seed)
            key, init_key, carry_key = jax.random.split(key, 3)
            carry = init_carry(env_params, traces, carry_key, faults)
        with phase("build_train_state"):
            ex_obs, ex_mask = jax.tree.map(lambda x: x[:1],
                                           (carry.obs, carry.mask))
            train_state = make_train_state(
                net, init_key, ex_obs, ex_mask, tx, extra,
                reward_norm=algo_cfg.reward_norm)
        # the step jitted and, under a mesh, everything it takes placed
        with phase("build_step"):
            if jit:
                if axis_name is not None:
                    # pmean(axis_name) is unbound under plain jit — the
                    # explicit-collective assembly lives in
                    # parallel.dp.shard_map_train: build with jit=False and
                    # hand the returned step to it (module docstring there)
                    raise ValueError(
                        "axis_name requires jit=False: hand the returned "
                        "train_step to parallel.dp.shard_map_train, which "
                        "wraps it in shard_map over the mesh axis")
                if mesh is not None:
                    # rule-sharded single program: params/opt-state laid out
                    # by the model family's partition-rule table, env batch
                    # over data, and the step traced with the mesh bound so
                    # rollout's with_sharding_constraint pins the trajectory
                    from .parallel import sharding as shardlib
                    from .parallel.dp import carry_sharding_prefix
                    from .parallel.mesh import (DATA_AXIS, env_sharded,
                                                replicated)
                    if cfg.n_envs % mesh.shape[DATA_AXIS]:
                        raise ValueError(
                            f"n_envs={cfg.n_envs} not divisible by the "
                            f"mesh's data axis size {mesh.shape[DATA_AXIS]}")
                    rules = shardlib.rules_for(cfg)
                    state_sh = shardlib.tree_shardings(train_state, rules,
                                                       mesh)
                    env = env_sharded(mesh)
                    rep = replicated(mesh)
                    carry_sh = carry_sharding_prefix(mesh)
                    jit_step = jax.jit(
                        shardlib.bind_mesh(step_fn, mesh),
                        in_shardings=(state_sh, carry_sh, env, rep, env),
                        out_shardings=(state_sh, carry_sh, rep),
                        donate_argnums=(0, 1))
                    train_state = shardlib.put_tree(train_state, state_sh)
                    carry = RolloutCarry(
                        env_state=shardlib.put_global(carry.env_state, env),
                        obs=shardlib.put_global(carry.obs, env),
                        mask=shardlib.put_global(carry.mask, env),
                        key=shardlib.put_global(carry.key, rep))
                    traces = shardlib.put_global(traces, env)
                    if faults is not None:
                        faults = shardlib.put_global(faults, env)
                else:
                    # state and carry are replaced every iteration in run(),
                    # so donating them halves live copies in the benchmarked
                    # hot loop
                    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
            else:
                if mesh is not None:
                    raise ValueError("mesh requires jit=True (the rule-table "
                                     "shardings are jit in/out_shardings)")
                jit_step = step_fn
        return Experiment(cfg=cfg, env_params=env_params, windows=windows,
                          traces=traces, net=net, apply_fn=apply_fn,
                          train_state=train_state, train_step=jit_step,
                          carry=carry, key=key, source=source,
                          train_step_raw=step_fn, faults=faults,
                          domains=domains, mesh=mesh)

    @property
    def steps_per_iteration(self) -> int:
        algo_cfg = self.cfg.ppo if self.cfg.algo == "ppo" else self.cfg.a2c
        return algo_cfg.n_steps * self.cfg.n_envs

    def run_fused(self, iterations: int):
        """Run ``iterations`` train steps as ONE on-device program — a
        ``lax.scan`` over the train step, the Podracer outer loop taken
        all the way (SURVEY.md §7 hard part (d): per-step host↔device
        sync at zero). The per-iteration host loop of :meth:`run` pays
        one dispatch per train step, which bounds a small step's
        sustained throughput by dispatch latency, not chip time; one
        fused dispatch removes that bound (no benchmark cell runs it:
        the cells time :meth:`run`, PERF.md). No logging / eval /
        checkpoint / window-streaming hooks run inside — use :meth:`run`
        when you need them. Returns the LAST iteration's metrics.

        RNG: ONE split of ``self.key`` is fanned out into ``iterations``
        subkeys up front, whereas :meth:`run`'s per-step loop splits
        ``self.key`` sequentially every iteration — the two derive
        DIFFERENT key streams. A fused (or ``fused_chunk > 1``) run is
        therefore deterministic and reproducible, but NOT bit-identical
        to the same-seed per-step run."""
        if self._fused_jit is None:
            step = self.train_step_raw
            if step is None:
                raise ValueError("run_fused needs the raw step "
                                 "(Experiment.build stores it)")
            if self.train_step is self.train_step_raw:
                # built with jit=False — the shard_map/axis_name path
                # (build() directs that path to dp.shard_map_train);
                # jitting the raw step here would hit an unbound
                # collective axis at trace time with an opaque error
                raise ValueError(
                    "run_fused supports the plain jitted single-program "
                    "build; a jit=False/axis_name experiment runs its "
                    "step under parallel.dp.shard_map_train instead")

            def many(state, carry, traces, keys, faults):
                def body(c, sk):
                    s, ca = c
                    s, ca, _ = step(s, ca, traces, sk, faults)
                    return (s, ca), None

                (state, carry), _ = jax.lax.scan(
                    body, (state, carry), keys[:-1])
                # final step outside the scan returns its metrics without
                # stacking [k] metric arrays for the whole run
                state, carry, metrics = step(state, carry, traces,
                                             keys[-1], faults)
                return state, carry, metrics

            # one wrapper; jax.jit itself caches one compile per distinct
            # keys length — no second cache layer needed
            if self.mesh is not None:
                # fused-under-mesh rides the SAME partition-rule table
                # as the per-step build (not input-inferred shardings,
                # which would silently fall back to whatever layout the
                # donated buffers happened to carry): params/opt-state
                # by the model family's rules, env batch over data,
                # fanned-out keys replicated — one sharding authority
                # for both step cadences (ROADMAP residual from PR 9)
                from .parallel import sharding as shardlib
                from .parallel.dp import carry_sharding_prefix
                from .parallel.mesh import env_sharded, replicated
                rules = shardlib.rules_for(self.cfg)
                state_sh = shardlib.tree_shardings(self.train_state,
                                                   rules, self.mesh)
                env = env_sharded(self.mesh)
                rep = replicated(self.mesh)
                carry_sh = carry_sharding_prefix(self.mesh)
                self._fused_jit = jax.jit(
                    shardlib.bind_mesh(many, self.mesh),
                    in_shardings=(state_sh, carry_sh, env, rep, env),
                    out_shardings=(state_sh, carry_sh, rep),
                    donate_argnums=(0, 1))
            else:
                self._fused_jit = jax.jit(many, donate_argnums=(0, 1))
        self.key, sub = jax.random.split(self.key)
        keys = jax.random.split(sub, iterations)
        self.train_state, self.carry, metrics = self._fused_jit(
            self.train_state, self.carry, self.traces, keys, self.faults)
        return metrics

    def _cut_windows(self, cursor: int) -> None:
        """Re-cut the env windows at tiling position ``cursor`` (same
        shapes → NO recompilation; the jitted step takes traces as an
        argument). Sharding of the previous traces is preserved so DP runs
        stay sharded."""
        self.window_cursor = cursor
        windows = (make_domain_windows(self.cfg, self.domains, cursor)
                   if self.domains is not None
                   else make_env_windows(self.cfg, self.source, cursor))
        sim_params = (self.env_params.sim
                      if isinstance(self.env_params, EnvParams)
                      else self.env_params.pod_sim)
        traces = stack_traces(windows, sim_params)
        self.traces = jax.tree.map(
            lambda new, old: jax.device_put(new, old.sharding),
            traces, self.traces)
        self.windows = windows

    def advance_windows(self) -> None:
        """Rotate every env onto the next ``n_envs`` windows of the source
        tiling and reset episodes (window streaming — a long run covers
        the whole trace, VERDICT r1 missing #3). Fault schedules are
        window-independent (episode-relative times) and stay fixed: a
        streaming run sees every window under its env's draw of the
        fault distribution."""
        self._cut_windows(self.window_cursor + self.cfg.n_envs)
        self.key, carry_key = jax.random.split(self.key)
        carry = init_carry(self.env_params, self.traces, carry_key,
                           self.faults)
        self.carry = jax.tree.map(
            lambda new, old: jax.device_put(new, old.sharding),
            carry, self.carry)

    def save_checkpoint(self, ckpt, step: int | None = None,
                        meta: dict | None = None, force: bool = False) -> bool:
        """Persist train state + rollout PRNG key + rollout carry
        (``checkpoint.Checkpointer``). Pass ``force=True`` to overwrite an
        existing checkpoint at the same step (e.g. a PBT exploit that copies
        weights without advancing the optimizer)."""
        step = int(self.train_state.step) if step is None else step
        meta = dict(meta or {}, window_cursor=self.window_cursor)
        return ckpt.save(step, self.train_state, key=self.key,
                         extra=self.carry, meta=meta, force=force)

    def restore_checkpoint(self, ckpt, step: int | None = None) -> dict:
        """Restore train state + key + rollout carry in place; returns the
        checkpoint meta. With the carry (and, for streaming runs, the
        window cursor) restored, a resumed ``run()`` reproduces the
        uninterrupted run exactly. The experiment must be built from the
        same config (shapes must match)."""
        self.train_state, key, carry, meta = ckpt.restore(
            self.train_state, self.key, self.carry, step)
        if key is not None:
            self.key = key
        if carry is not None:
            self.carry = carry
        cursor = int((meta or {}).get("window_cursor", 0))
        if cursor != self.window_cursor:
            self._cut_windows(cursor)
        return meta

    def scale_lr(self, scale: float) -> None:
        """Swap the optimizer for one at ``scale`` × the config LR (the
        watchdog's deterministic rollback decay). Rebinding ``tx`` changes
        the TrainState's static treedef, so the next step re-traces — an
        acceptable cost bounded by ``max_rollbacks``. Adam's moment state
        is LR-independent, so the restored opt_state carries over."""
        algo_cfg = self.cfg.ppo if self.cfg.algo == "ppo" else self.cfg.a2c
        scaled = dataclasses.replace(algo_cfg, lr=algo_cfg.lr * scale)
        if self.cfg.algo == "ppo":
            tx = make_optimizer(scaled)
        else:
            from .algos.a2c import make_optimizer as a2c_opt
            tx = a2c_opt(scaled)
        self.train_state = self.train_state.replace(tx=tx)

    def fold_key(self, n: int) -> None:
        """Deterministically diverge the rollout RNG stream (watchdog
        retry: replaying the restored key bit-exactly would re-sample the
        trajectory that just diverged)."""
        self.key = jax.random.fold_in(self.key, n)

    @startup.recorded_run
    def run(self, iterations: int | None = None, log_every: int = 0,
            logger: Callable[[int, dict], None] | None = None,
            ckpt=None, ckpt_every: int = 0,
            eval_every: int = 0,
            eval_fn: "Callable[[int], dict] | None" = None,
            eval_logger: Callable[[int, dict], None] | None = None,
            fused_chunk: int = 1, watchdog=None, injector=None,
            telemetry=None) -> dict:
        """Run the host training loop; returns summary metrics. Pass a
        ``checkpoint.Checkpointer`` + cadence to persist while training.

        ``telemetry`` (:class:`obs.RunTelemetry`) span-traces the loop:
        per-iteration phase breakdown (step dispatch / sync / eval /
        ckpt / resample via its ``SectionTimer``), an ``iteration``
        event at every LOGGED iteration carrying the metrics dict this
        loop already materialized — telemetry adds ZERO host syncs of
        its own — and, when its alarms are armed, the jitted dispatch
        runs under the recompile/transfer production alarms (a rollback
        retry's LR-rescale re-trace is granted amnesty).

        ``eval_fn(i) -> dict`` runs every ``eval_every`` iterations (and at
        the last one) — the in-training quality probe (e.g. a held-out JCT
        replay); its rows go to ``eval_logger`` (NOT ``logger``: eval rows
        have a different schema than train rows and MetricsLogger pins one
        schema per stream) and into the summary's ``eval_history``.

        ``fused_chunk > 1`` dispatches that many train steps as ONE
        on-device :meth:`run_fused` program between hook boundaries
        (the chunk amortizes per-dispatch latency). Every log/eval/ckpt/
        resample cadence must be a multiple of the chunk, so hooks fire
        exactly as in the per-step loop; metrics logged at a boundary are
        the boundary ITERATION's.
        NOTE: chunked and per-step runs derive their rollout RNG keys
        DIFFERENTLY (see :meth:`run_fused`), so a ``fused_chunk > 1`` run
        is deterministic but NOT bit-identical to the same-seed per-step
        run.

        ``watchdog`` (:class:`resilience.DivergenceWatchdog`, requires
        ``ckpt``) checks each materialized iteration's metrics and rolls
        back to the last good checkpoint on divergence — after a rollback
        the replayed iterations are re-logged, so the history/CSV shows
        the retry honestly. With ``fused_chunk > 1`` only chunk-boundary
        metrics exist to check. ``injector``
        (:class:`resilience.FaultInjector`) drives the fault-injection
        hooks (``nan-grad`` after the matching iteration's step,
        ``corrupt-ckpt`` after the matching iteration's save). A
        :class:`resilience.DivergenceError` propagates to the caller once
        the watchdog's rollback budget is exhausted."""
        iterations = iterations or self.cfg.iterations
        if watchdog is not None and ckpt is None:
            raise ValueError(
                "watchdog rollback needs a checkpoint store; pass ckpt= "
                "(and a ckpt_every cadence so rollbacks stay short)")
        if fused_chunk > 1:
            cadences = {"log_every": log_every,
                        # ckpt_every is only a live cadence when a
                        # checkpointer is attached (the CLI default is 50
                        # even without --ckpt-dir)
                        "ckpt_every": ckpt_every if ckpt is not None else 0,
                        "eval_every": eval_every if eval_fn is not None
                        else 0,
                        "resample_every": self.cfg.resample_every,
                        "iterations": iterations}
            bad = {k: v for k, v in cadences.items()
                   if v and v % fused_chunk}
            if bad:
                raise ValueError(
                    f"fused_chunk={fused_chunk} must divide every active "
                    f"cadence and the iteration count; offending: {bad}")
        history = []
        eval_history = []
        t0 = time.monotonic()
        stride = fused_chunk if fused_chunk > 1 else 1
        # the sections' sink: the telemetry's timer, else this call's
        # ``run`` record in the start-up account, so the section sites
        # stay branch-free (two perf_counter reads a section: noise next
        # to a dispatch) and the totals are kept either way
        from .obs.scopes import TRAIN_ITERATION
        from .obs.trace import tracer_of
        sections = startup.sections_of(telemetry)
        tracer = tracer_of(telemetry)
        if telemetry is not None:
            telemetry.run_start(
                loop="experiment", config=self.cfg.name,
                algo=self.cfg.algo, iterations=iterations,
                n_envs=self.cfg.n_envs,
                steps_per_iteration=self.steps_per_iteration,
                fused_chunk=fused_chunk)
        if watchdog is not None and ckpt.latest_step() is None:
            # guarantee a rollback target before the first periodic save
            self.save_checkpoint(ckpt, meta={"iteration": -1})
        # under a mesh build the step pins its key argument to the
        # replicated sharding; a freshly split subkey is committed to the
        # default device, so the jit would replicate it with an implicit
        # device-to-device copy INSIDE the guarded dispatch (a transfer
        # alarm). Place it explicitly here, outside the guard, like every
        # other input placed at build time.
        key_rep = None
        if self.mesh is not None:
            from .parallel.mesh import replicated
            key_rep = replicated(self.mesh)
        i = 0
        while i < iterations:
            # hooks see the chunk's last iteration (== i when unchunked);
            # chunked boundaries sit at b = k*chunk - 1, so the phase-0
            # cadence form (b % L == 0) would never fire there; the (b+1)
            # form is the same cadence shifted to boundary-aligned phase
            b = i + stride - 1
            with jax.profiler.StepTraceAnnotation(TRAIN_ITERATION,
                                                  step_num=b):
                if telemetry is not None:
                    telemetry.begin_iteration(b)
                guard = (telemetry.dispatch(b) if telemetry is not None
                         else contextlib.nullcontext())
                # "step" is the async dispatch only — the device work it
                # enqueues materializes in the "sync" span's device_get
                if fused_chunk > 1:
                    with tracer.phase(sections, "step"), guard:
                        metrics = self.run_fused(fused_chunk)
                else:
                    self.key, sub = jax.random.split(self.key)
                    if key_rep is not None:
                        sub = jax.device_put(sub, key_rep)
                    with tracer.phase(sections, "step"), guard:
                        self.train_state, self.carry, metrics = \
                            self.train_step(
                                self.train_state, self.carry, self.traces,
                                sub, self.faults)
                if injector is not None:
                    metrics = injector.poison_nan(self, b, metrics)
                log_hit = log_every and (
                    (b + 1) % log_every == 0 if fused_chunk > 1
                    else b % log_every == 0)
                want_log = bool(log_every) and (log_hit or b == iterations - 1)
                # host consumers (watchdog + logger + telemetry) share ONE
                # batched device_get: per-field float() is a separate
                # blocking transfer each, and the watchdog path pays it every
                # iteration (jsan host-sync review, PR 3)
                m = None
                if watchdog is not None or want_log:
                    with tracer.phase(sections, "sync"):
                        m = {k: float(v) for k, v in
                             jax.device_get(metrics)._asdict().items()}
                if watchdog is not None:
                    reason = watchdog.check(m)
                    if reason is not None:
                        event = watchdog.rollback(self, ckpt, b, reason)
                        if telemetry is not None:
                            # the retry's LR rescale rebinds tx and re-traces
                            # the step — a legitimate compile, not an alarm
                            telemetry.iteration_aborted(
                                b, f"rollback: {reason}")
                        i = event.resume_iteration
                        continue
                if want_log:
                    history.append({"iteration": b, **m})
                    if logger is not None:
                        logger(b, m)
                if eval_fn is not None and eval_every and \
                        ((b + 1) % eval_every == 0 or b == iterations - 1):
                    with tracer.phase(sections, "eval"):
                        em = dict(eval_fn(b))
                    eval_history.append({"iteration": b, **em})
                    if eval_logger is not None:
                        eval_logger(b, em)
                if ckpt is not None and ckpt_every and \
                        ((b + 1) % ckpt_every == 0 or b == iterations - 1):
                    with tracer.phase(sections, "ckpt"):
                        self.save_checkpoint(ckpt, meta={"iteration": b})
                    if injector is not None:
                        injector.corrupt_after_save(ckpt, b)
                if self.cfg.resample_every and \
                        (b + 1) % self.cfg.resample_every == 0 and \
                        b != iterations - 1:
                    with tracer.phase(sections, "resample"):
                        self.advance_windows()
                if telemetry is not None:
                    telemetry.end_iteration(
                        b, m if want_log else None,
                        stride * self.steps_per_iteration)
                i += stride
        jax.block_until_ready(self.train_state.params)
        wall = time.monotonic() - t0
        total_env_steps = iterations * self.steps_per_iteration
        out = {"wall_s": wall, "iterations": iterations,
               "env_steps": total_env_steps,
               "env_steps_per_sec": total_env_steps / wall,
               "window_cursor": self.window_cursor,
               "history": history}
        if watchdog is not None:
            out["rollbacks"] = watchdog.n_rollbacks
            out["rollback_events"] = [e.as_dict() for e in watchdog.events]
        if eval_history:
            out["eval_history"] = eval_history
        if telemetry is not None:
            telemetry.run_end(
                iterations=iterations, wall_s=round(wall, 6),
                env_steps=total_env_steps,
                env_steps_per_sec=round(out["env_steps_per_sec"], 3),
                rollbacks=(watchdog.n_rollbacks
                           if watchdog is not None else 0))
        return out

    def run_async(self, iterations: int | None = None, *,
                  groups=None, staleness_bound: int = 1,
                  queue_capacity: int = 2, log_every: int = 0,
                  logger: Callable[[int, dict], None] | None = None,
                  ckpt=None, ckpt_every: int = 0, eval_every: int = 0,
                  eval_fn: "Callable[[int], dict] | None" = None,
                  eval_logger: Callable[[int, dict], None] | None = None,
                  telemetry=None) -> dict:
        """Opt-in async actor–learner loop (:mod:`.async_engine`):
        rollout collection on the actor device group overlaps the
        minibatch update on the learner group, coupled by a bounded
        device-side trajectory queue under an explicit staleness bound
        (``staleness_bound=0`` reproduces :meth:`run` bit-identically).
        The hook surface matches :meth:`run`; checkpoints and window
        resamples run at drained-queue barriers so :meth:`restore_checkpoint`
        + a resumed ``run_async`` stays deterministic. ``groups`` is a
        :class:`~.parallel.groups.DeviceGroups` (default: split the
        visible devices). NOTE: construction moves this experiment's
        state onto the group meshes; reuse the runner (or rebuild) rather
        than mixing with :meth:`run` afterwards. Watchdog/injector
        resilience hooks and ``fused_chunk`` are sync-path-only."""
        from .async_engine import AsyncRunner
        runner = AsyncRunner(self, groups=groups,
                             staleness_bound=staleness_bound,
                             queue_capacity=queue_capacity)
        return runner.run(iterations, log_every=log_every, logger=logger,
                          ckpt=ckpt, ckpt_every=ckpt_every,
                          eval_every=eval_every, eval_fn=eval_fn,
                          eval_logger=eval_logger, telemetry=telemetry)


@dataclasses.dataclass
class PopulationExperiment:
    """Config 5 assembly: a population of PPO members trained as one
    vmapped+pop-sharded program, with host-side PBT exploit/explore
    (SURVEY.md §3.5). Each member runs the per-member config ``cfg`` (for
    the driver's config 5 that is the hierarchical 4-pod agent,
    ``configs.HIER_PBT_MEMBER``)."""
    cfg: ExperimentConfig
    n_pop: int
    env_params: EnvParams
    traces: Any              # [E, ...] batched device Trace (shared)
    apply_fn: Callable
    states: Any              # stacked MemberState [P, ...]
    carries: Any             # stacked RolloutCarry [P, ...]
    hparams: Any             # HParams stacked [P]
    keys: jax.Array          # [P, 2] per-member rollout keys
    pop_step: Callable       # jitted
    controller: Any          # PBTController
    windows: list = None     # host ArrayTrace windows (shared; eval reuse)
    mesh: Any = None         # unified Mesh when members ride the pop axis
    state_sharding: Any = None    # rule-resolved member-stack layout
    hparam_sharding: Any = None   # [P] hparam layout (pop axis)
    # batched per-member per-env FaultSchedule [P, E, ...] (cfg.faults),
    # or None: each member draws its own seeded (seed, member, env)
    # schedules, so the population covers the regime P×E-wide. Not
    # checkpointed — deterministically regenerated from cfg at build
    faults: Any = None

    @staticmethod
    @startup.recorded_build
    def build(cfg: ExperimentConfig, n_pop: int = 4, mesh=None,
              pbt_cfg=None, *, telemetry=None) -> "PopulationExperiment":
        """Assemble the population; phases and ``telemetry`` as
        :meth:`Experiment.build` has them (a member's carry and state are
        a ``build_carry`` and a ``build_train_state`` each)."""
        from .parallel.pbt import PBTConfig, PBTController
        from .parallel.population import (init_member, jit_population_step,
                                          make_population_step,
                                          sample_hparams, stack_members)
        if cfg.algo != "ppo":
            raise ValueError(
                f"PopulationExperiment trains PPO members (PBT explores "
                f"PPO hyperparameters); config {cfg.name!r} has "
                f"algo={cfg.algo!r}")
        if cfg.domains:
            # configs.MODE_REFUSALS carries the pbt×domains row for the
            # CLI; programmatic builders must refuse just as loudly
            raise ValueError(
                "PopulationExperiment does not thread domain schedules: "
                "per-member domain draws would need member-indexed trace "
                "windows through the population stack (cfg.domains=None; "
                "cfg.faults is supported)")
        pbt_cfg = pbt_cfg or PBTConfig(seed=cfg.seed)
        resolve_geometry(cfg.ppo.n_epochs, cfg.ppo.n_minibatches,
                         cfg.ppo.minibatch_size,
                         cfg.ppo.n_steps * cfg.n_envs)
        env_params, windows, traces, net, apply_fn, extra, _source = \
            build_stack(cfg)
        # traces stay unstacked [E, ...]: every member trains on the same
        # env windows (PBT fitness comparability) and the vmapped step
        # broadcasts them (in_axes=None) instead of holding n_pop copies

        # per-member per-env fault schedules [P, E, ...]: member p's env e
        # draws from (seed, p, e), so the population covers the regime
        # P×E-wide while every member trains on the SAME trace windows
        # (fitness stays comparable in expectation — same regime,
        # independent draws)
        member_faults = None
        fp = getattr(env_params, "fault_process", None)
        if fp is not None:
            from .sim.faults import stack_fault_schedules
            horizon_s = fault_horizon(windows)
            with phase("build_upload"):
                member_faults = [
                    stack_fault_schedules(
                        [sample_fault_schedule(cfg.n_nodes, fp,
                                               (cfg.seed, p, e), horizon_s)
                         for e in range(cfg.n_envs)])
                    for p in range(n_pop)]

        with phase("build_carry"):     # the keys' eager programs
            key = jax.random.PRNGKey(cfg.seed)
            member_keys = jax.random.split(key, n_pop * 3).reshape(
                n_pop, 3, 2)
        members, carries = [], []
        for p in range(n_pop):
            with phase("build_carry"):
                carry = init_carry(
                    env_params, traces, member_keys[p, 1],
                    member_faults[p] if member_faults is not None else None)
            with phase("build_train_state"):
                ex_obs, ex_mask = jax.tree.map(lambda x: x[:1],
                                               (carry.obs, carry.mask))
                members.append(init_member(net, member_keys[p, 0], ex_obs,
                                           ex_mask, cfg.ppo, extra))
            carries.append(carry)
        with phase("build_train_state"):
            states = stack_members(members)
            stacked_carries = stack_members(carries)
            hparams = sample_hparams(cfg.ppo, n_pop, cfg.seed)
            keys = member_keys[:, 2]
            faults = (stack_members(member_faults)
                      if member_faults is not None else None)
        with phase("build_step"):
            pop_step = make_population_step(apply_fn, env_params, cfg.ppo,
                                            with_faults=faults is not None)
            if mesh is not None:
                if n_pop % mesh.shape["pop"] != 0:
                    raise ValueError(f"n_pop={n_pop} not divisible by pop "
                                     f"axis size {mesh.shape['pop']}")
                if cfg.n_envs % mesh.shape["data"] != 0:
                    raise ValueError(f"n_envs={cfg.n_envs} not divisible by "
                                     f"data axis size {mesh.shape['data']}")
                # member-state layout resolved per-leaf from the same
                # partition-rule table the single-run path uses: pop axis on
                # the member stack, model axis on kernels within each member
                from .parallel import sharding as shardlib
                from .parallel.population import population_shardings
                rules = shardlib.rules_for(cfg)
                jitted = jit_population_step(mesh, pop_step, states=states,
                                             rules=rules,
                                             with_faults=faults is not None)
                st_sh, ca_sh, tr_sh, key_sh, hp_sh = population_shardings(
                    mesh, states=states, rules=rules)
                states = jax.device_put(states, st_sh)
                stacked_carries = jax.device_put(stacked_carries, ca_sh)
                traces = jax.device_put(traces, tr_sh)
                keys = jax.device_put(keys, key_sh)
                hparams = jax.device_put(hparams, hp_sh)
                if faults is not None:
                    from .parallel.mesh import pop_env_sharded
                    faults = jax.device_put(faults, pop_env_sharded(mesh))
                return PopulationExperiment(
                    cfg=cfg, n_pop=n_pop, env_params=env_params,
                    traces=traces, apply_fn=apply_fn, states=states,
                    carries=stacked_carries, hparams=hparams, keys=keys,
                    pop_step=jitted,
                    controller=PBTController(n_pop, pbt_cfg),
                    windows=windows, mesh=mesh, state_sharding=st_sh,
                    hparam_sharding=hp_sh, faults=faults)
            jitted = jax.jit(pop_step, donate_argnums=(0, 1))
            return PopulationExperiment(
                cfg=cfg, n_pop=n_pop, env_params=env_params, traces=traces,
                apply_fn=apply_fn, states=states, carries=stacked_carries,
                hparams=hparams, keys=keys, pop_step=jitted,
                controller=PBTController(n_pop, pbt_cfg), windows=windows,
                faults=faults)

    @property
    def steps_per_iteration(self) -> int:
        return self.cfg.ppo.n_steps * self.cfg.n_envs * self.n_pop

    def best_member(self) -> int:
        """Index of the fittest member by windowed mean fitness (NaN ranks
        worst — same ordering PBT exploit uses). Raises when the controller
        holds no recorded fitness (e.g. a population checkpoint saved
        before controller state was persisted): argmax over the all-zero
        default would silently crown member 0."""
        import numpy as np
        if self.controller._fitness_n == 0 and not self.controller.history:
            raise ValueError(
                "population has no recorded fitness (pre-controller-state "
                "checkpoint, or no training iterations ran); pass an "
                "explicit member index instead")
        f = np.asarray(self.controller.mean_fitness, np.float64)
        return int(np.nanargmax(np.where(np.isnan(f), -np.inf, f)))

    def member_eval_view(self, m: int | None = None):
        """Experiment-like view of one population member for the eval
        harness (``eval.jct_report(pop.member_eval_view())``): the member's
        params indexed out of the stacked MemberState (materialized on the
        default device — the eval replay is unsharded), sharing the
        population's windows/traces/env_params. Default: fittest member."""
        import types
        m = self.best_member() if m is None else m
        if not 0 <= m < self.n_pop:
            raise ValueError(f"member {m} out of range [0, {self.n_pop})")
        params = jax.tree.map(
            lambda x: jax.device_put(x[m], jax.devices()[0]),
            self.states.params)
        return types.SimpleNamespace(
            cfg=self.cfg, env_params=self.env_params, windows=self.windows,
            traces=self.traces, apply_fn=self.apply_fn,
            train_state=types.SimpleNamespace(params=params), member=m)

    def save_checkpoint(self, ckpt, step: int | None = None,
                        meta: dict | None = None, force: bool = False) -> bool:
        """Persist the whole population (member stack + carries + hparams +
        rollout keys) in one checkpoint, plus the full PBT controller state
        (RNG, fitness window, decision history) in meta — so a resumed run
        reproduces the uninterrupted run's exploit decisions bit-for-bit
        (VERDICT r2 weak #2)."""
        import numpy as np
        extra = {"carries": self.carries, "keys": self.keys,
                 "hparams": self.hparams}
        step = (int(np.max(np.asarray(self.states.step)))
                if step is None else step)
        meta = dict(meta or {}, pbt_events=len(self.controller.history),
                    pbt_controller=self.controller.state_dict())
        return ckpt.save(step, self.states, extra=extra, meta=meta,
                         force=force)

    def restore_checkpoint(self, ckpt, step: int | None = None) -> dict:
        extra_t = {"carries": self.carries, "keys": self.keys,
                   "hparams": self.hparams}
        self.states, _key, extra, meta = ckpt.restore(
            self.states, None, extra_t, step)
        if extra is not None:
            # structures restore into the template's treedefs, so these are
            # already RolloutCarry / HParams
            self.carries = extra["carries"]
            self.keys = extra["keys"]
            self.hparams = extra["hparams"]
        self.controller.load_state_dict((meta or {}).get("pbt_controller"))
        return meta

    def scale_lr(self, scale: float) -> None:
        """Watchdog rollback decay for the population: per-member LRs live
        in the traced :class:`~parallel.population.HParams` (not the
        optimizer), so the decay is one array multiply — no re-trace."""
        self.hparams = self.hparams._replace(lr=self.hparams.lr * scale)

    def run_async(self, iterations: int | None = None, *,
                  groups=None, staleness_bound: int = 1,
                  queue_capacity: int = 2, log_every: int = 0,
                  logger: Callable[[int, dict], None] | None = None,
                  ckpt=None, ckpt_every: int = 0, eval_every: int = 0,
                  eval_fn: "Callable[[int], dict] | None" = None,
                  eval_logger: Callable[[int, dict], None] | None = None,
                  telemetry=None) -> dict:
        """Opt-in async actor–learner loop over the whole population
        (:class:`~.async_engine.AsyncPopulationRunner`): the vmapped
        member rollout overlaps the vmapped member update, PBT
        exploit/explore fires at drained-queue barriers, and
        ``staleness_bound=0`` reproduces :meth:`run` bit-identically
        (non-mesh build — construction requires ``mesh=None`` and places
        member stacks on the group meshes itself). Deep bounds want
        ``cfg.ppo.correction="vtrace"`` so stale batches do not skew the
        cross-member fitness ranking. Watchdog/injector chaos drills are
        sync-path-only."""
        from .async_engine import AsyncPopulationRunner
        runner = AsyncPopulationRunner(self, groups=groups,
                                       staleness_bound=staleness_bound,
                                       queue_capacity=queue_capacity)
        return runner.run(iterations, log_every=log_every, logger=logger,
                          ckpt=ckpt, ckpt_every=ckpt_every,
                          eval_every=eval_every, eval_fn=eval_fn,
                          eval_logger=eval_logger, telemetry=telemetry)

    def fold_key(self, n: int) -> None:
        """Deterministically diverge every member's rollout RNG stream
        (watchdog retry — same contract as :meth:`Experiment.fold_key`)."""
        self.keys = jax.vmap(lambda k: jax.random.fold_in(k, n))(self.keys)

    @startup.recorded_run
    def run(self, iterations: int | None = None, log_every: int = 0,
            logger: Callable[[int, dict], None] | None = None,
            ckpt=None, ckpt_every: int = 0,
            eval_every: int = 0,
            eval_fn: "Callable[[int], dict] | None" = None,
            eval_logger: Callable[[int, dict], None] | None = None,
            watchdog=None, injector=None, telemetry=None) -> dict:
        """Train the population; PBT exploit/explore fires every
        ``controller.cfg.ready_iters`` iterations. Returns summary metrics
        including per-member final fitness and the PBT event log.

        ``eval_fn(i) -> dict`` runs every ``eval_every`` iterations (and
        at the last one), AFTER the iteration's fitness is recorded — so
        a probe may rank members via :meth:`best_member` (the in-training
        quality probe behind the PBT ``--keep-best`` path: the
        population-drift failure mode has cost a best-population twice,
        VERDICT r5 weak #2). Rows go to ``eval_logger`` and the summary's
        ``eval_history`` — same contract as :meth:`Experiment.run`.

        ``watchdog`` (requires ``ckpt``) handles only the CATASTROPHIC
        divergence case — every member non-finite, nobody left to re-seed
        from — by rolling the whole population back to the last good
        checkpoint; a single diverged member is PBT's job (exploit treats
        non-finite fitness as dead and re-seeds it from the best member).
        ``injector`` drives ``nan-grad`` (member poisoning; spec
        ``rank`` = member index) and ``corrupt-ckpt`` faults."""
        iterations = iterations or self.cfg.iterations
        if watchdog is not None and ckpt is None:
            raise ValueError(
                "watchdog rollback needs a checkpoint store; pass ckpt= "
                "(and a ckpt_every cadence so rollbacks stay short)")
        split_all = jax.jit(jax.vmap(lambda k: jax.random.split(k)))
        history = []
        eval_history = []
        t0 = time.monotonic()
        from .obs.scopes import TRAIN_ITERATION
        from .obs.trace import tracer_of
        sections = startup.sections_of(telemetry)
        tracer = tracer_of(telemetry)
        if telemetry is not None:
            telemetry.run_start(
                loop="population", config=self.cfg.name,
                n_pop=self.n_pop, iterations=iterations,
                n_envs=self.cfg.n_envs,
                steps_per_iteration=self.steps_per_iteration)
        if watchdog is not None and ckpt.latest_step() is None:
            self.save_checkpoint(ckpt, meta={"iteration": -1})
        i = 0
        while i < iterations:
            with jax.profiler.StepTraceAnnotation(TRAIN_ITERATION,
                                                  step_num=i):
                if telemetry is not None:
                    telemetry.begin_iteration(i)
                guard = (telemetry.dispatch(i) if telemetry is not None
                         else contextlib.nullcontext())
                both = split_all(self.keys)
                self.keys, subs = both[:, 0], both[:, 1]
                step_args = (self.states, self.carries, self.traces, subs,
                             self.hparams)
                if self.faults is not None:
                    step_args = step_args + (self.faults,)
                with tracer.phase(sections, "step"), guard:
                    self.states, self.carries, metrics = self.pop_step(
                        *step_args)
                if injector is not None:
                    metrics = injector.poison_nan_member(self, i, metrics)
                fitness = metrics.mean_reward
                if watchdog is not None:
                    reason = watchdog.check_population(fitness)
                    if reason is not None:
                        event = watchdog.rollback(self, ckpt, i, reason)
                        if telemetry is not None:
                            telemetry.iteration_aborted(
                                i, f"rollback: {reason}")
                        i = event.resume_iteration
                        continue
                self.controller.record(fitness)
                out = self.controller.maybe_update(i, self.states,
                                                   self.hparams)
                if out is not None:
                    self.states, self.hparams, decision = out
                    if self.mesh is not None:
                        # the exploit gather + host-side explore hand
                        # back arrays without the pop-axis commitment;
                        # re-pin them HERE — outside the next dispatch's
                        # transfer guard — or the jit replicates them with
                        # an implicit device-to-device copy (transfer alarm)
                        self.states = jax.device_put(self.states,
                                                     self.state_sharding)
                        self.hparams = jax.device_put(self.hparams,
                                                      self.hparam_sharding)
                    if telemetry is not None:
                        telemetry.emit(
                            "pbt_exploit", iteration=i,
                            exploited=int(decision.exploited.sum()),
                            src=[int(s) for s in decision.src])
                m = None
                if log_every and (i % log_every == 0 or i == iterations - 1):
                    # flatten per-member values to suffixed scalar columns so
                    # the CSV stays pandas/TensorBoard-ingestible (ADVICE r1).
                    # ONE batched device_get for the whole [P]-metrics tuple:
                    # per-element float() was n_fields x P separate blocking
                    # transfers per logged iteration (jsan host-sync review)
                    m = {}
                    with tracer.phase(sections, "sync"):
                        got = jax.device_get(metrics)._asdict()
                    for k, v in got.items():
                        vals = [float(x) for x in v]
                        m.update({f"{k}_{p}": x for p, x in enumerate(vals)})
                        m[f"{k}_mean"] = sum(vals) / len(vals)
                    history.append({"iteration": i, **m})
                    if logger is not None:
                        logger(i, m)
                if eval_fn is not None and eval_every and \
                        ((i + 1) % eval_every == 0 or i == iterations - 1):
                    with tracer.phase(sections, "eval"):
                        em = dict(eval_fn(i))
                    eval_history.append({"iteration": i, **em})
                    if eval_logger is not None:
                        eval_logger(i, em)
                if ckpt is not None and ckpt_every and \
                        ((i + 1) % ckpt_every == 0 or i == iterations - 1):
                    with tracer.phase(sections, "ckpt"):
                        self.save_checkpoint(ckpt, meta={"iteration": i})
                    if injector is not None:
                        injector.corrupt_after_save(ckpt, i)
                if telemetry is not None:
                    telemetry.end_iteration(i, m, self.steps_per_iteration)
                i += 1
        jax.block_until_ready(self.states.params)
        wall = time.monotonic() - t0
        total_env_steps = iterations * self.steps_per_iteration
        out = {"wall_s": wall, "iterations": iterations,
               "env_steps": total_env_steps,
               "env_steps_per_sec": total_env_steps / wall,
               "final_fitness": [float(f) for f in
                                 self.controller.mean_fitness],
               "pbt_events": len(self.controller.history),
               "history": history}
        if watchdog is not None:
            out["rollbacks"] = watchdog.n_rollbacks
            out["rollback_events"] = [e.as_dict() for e in watchdog.events]
        if eval_history:
            out["eval_history"] = eval_history
        if telemetry is not None:
            telemetry.run_end(
                iterations=iterations, wall_s=round(wall, 6),
                env_steps=total_env_steps,
                env_steps_per_sec=round(out["env_steps_per_sec"], 3),
                pbt_events=len(self.controller.history),
                rollbacks=(watchdog.n_rollbacks
                           if watchdog is not None else 0))
        return out
