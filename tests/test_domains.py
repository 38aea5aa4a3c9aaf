"""Domain-randomization engine + generalization matrix (ISSUE 14).

Five contracts pin the tentpole:

1. **Samplers are seeded data with fail-fast validation** — draws are
   bit-deterministic in (seed, regime), capacities respect the
   [0, gpus_per_node] bound, and malformed specs/schedules are refused
   loudly (never a silently-wrong cluster).
2. **Oracle parity under heterogeneous speeds + drawn geometry** — the
   jitted sim under a :class:`DomainSchedule` (per-node capacity AND
   dyadic speed factors) reproduces ``OracleSim`` trajectory-for-
   trajectory, f32-exact — same regime as tests/test_sim_faults.py.
3. **Conservation under geometry randomization** — at every step of
   random action sequences, each node's ``free + allocated`` equals its
   DRAWN capacity and no valid job leaves the lifecycle.
4. **Domains are data, not code** — stepping under draws from different
   regimes must not retrace (CompileCounter), and a whole second
   ``matrix_report`` over fresh draws compiles NOTHING.
5. **The matrix** — shape, degradation-vs-none, conservation, obs bus
   events/gauges, and CLI refusals for the mode combinations that have
   no domain threading.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from rlgpuschedule_tpu import domains as D
from rlgpuschedule_tpu.sim import core as C
from rlgpuschedule_tpu.sim import faults as F
from rlgpuschedule_tpu.sim import oracle as O
from rlgpuschedule_tpu.traces import JobRecord, to_array_trace
from rlgpuschedule_tpu.traces.fit import TraceFit, fit_jobs, gen_domain_window

from tests.test_sim_faults import int_faults, int_trace


def device_schedule(ds):
    return jax.tree.map(jnp.asarray, ds)


def dyadic_draw(rng, n_nodes, gpus_per_node):
    """Hand-built draw with dyadic slowdowns (f32-exact stretch — the
    oracle-parity regime) and random but non-empty geometry."""
    cap = rng.integers(0, gpus_per_node + 1, size=n_nodes).astype(np.int32)
    if cap.sum() == 0:
        cap[0] = gpus_per_node
    slow = rng.choice([1.0, 2.0, 4.0], size=n_nodes).astype(np.float32)
    return D.DomainDraw(spec_name="test", capacity=cap, slowdown=slow,
                        load=1.0, duration_scale=1.0, burst_frac=0.0,
                        diurnal=False)


class TestSamplers:
    def test_spec_range_fail_fasts(self):
        with pytest.raises(ValueError, match="capacity_min_frac"):
            D.DomainSpec("x", capacity_min_frac=0.0)
        with pytest.raises(ValueError, match="p_node_off"):
            D.DomainSpec("x", p_node_off=1.5)
        with pytest.raises(ValueError, match="slowdown_min"):
            D.DomainSpec("x", slowdown_min=0.5)
        with pytest.raises(ValueError, match="load_min"):
            D.DomainSpec("x", load_min=1.2, load_max=0.8)
        with pytest.raises(ValueError, match="duration_scale"):
            D.DomainSpec("x", duration_scale_min=0.0)

    def test_unknown_regime_rejected(self):
        with pytest.raises(ValueError, match="unknown domain regime"):
            D.resolve_domain("meteor")

    def test_draws_seed_deterministically_per_regime(self):
        for name in D.DOMAIN_REGIMES:
            a = D.sample_domain(name, 4, 8, (7, 0))
            b = D.sample_domain(name, 4, 8, (7, 0))
            np.testing.assert_array_equal(a.capacity, b.capacity)
            np.testing.assert_array_equal(a.slowdown, b.slowdown)
            assert (a.load, a.duration_scale) == (b.load, b.duration_scale)
        # the regime name is folded into the entropy: same seed, distinct
        # regimes must not alias onto one cluster
        caps = {tuple(D.sample_domain(n, 16, 8, 0).capacity)
                for n in ("geom", "mixed")}
        loads = {D.sample_domain(n, 16, 8, 0).load
                 for n in ("baseline", "mixed")}
        assert len(caps) == 2 or len(loads) == 2

    def test_draw_capacity_bounds_and_nonempty(self):
        for e in range(50):
            d = D.sample_domain("mixed", 6, 4, (3, e))
            assert d.capacity.dtype == np.int32
            assert (d.capacity >= 0).all() and (d.capacity <= 4).all()
            assert d.total_gpus >= 1
            assert (d.slowdown >= 1.0).all()
            assert d.load > 0 and d.duration_scale > 0

    def test_overload_regime_pins_the_weakness_load(self):
        d = D.sample_domain("overload", 4, 8, (0, 0))
        assert d.load == pytest.approx(1.6)
        assert d.total_gpus == 32    # overload is a LOAD shift only

    def test_validate_schedule_fail_fasts(self):
        good = D.domain_schedule(dyadic_draw(np.random.default_rng(0),
                                             3, 4))
        D.validate_domain_schedule(3, 4, good)   # ok
        bad = good._replace(capacity=good.capacity[:2])
        with pytest.raises(ValueError, match="shape"):
            D.validate_domain_schedule(3, 4, bad)
        bad = good._replace(capacity=good.capacity.astype(np.float32))
        with pytest.raises(ValueError, match="integral"):
            D.validate_domain_schedule(3, 4, bad)
        bad = good._replace(capacity=np.array([9, 1, 1], np.int32))
        with pytest.raises(ValueError, match=r"\[0, 4\]"):
            D.validate_domain_schedule(3, 4, bad)
        bad = good._replace(capacity=np.zeros(3, np.int32))
        with pytest.raises(ValueError, match="zero GPUs"):
            D.validate_domain_schedule(3, 4, bad)

    def test_schedule_composes_worst_slowdown_with_faults(self):
        draw = D.DomainDraw("test", np.array([4, 4], np.int32),
                            np.array([1.0, 4.0], np.float32),
                            1.0, 1.0, 0.0, False)
        fs = F.no_faults(2, 1)
        fs.slowdown[:] = [2.0, 2.0]
        ds = D.domain_schedule(draw, F.validate_fault_schedule(2, fs))
        # elementwise max: the worst factor wins, never the product
        np.testing.assert_array_equal(ds.slowdown, [2.0, 4.0])
        with pytest.raises(ValueError, match="node"):
            D.domain_schedule(draw, F.no_faults(3, 1))


class TestFitAndWindows:
    def _jobs(self, rng, n=200):
        return [JobRecord(i, float(rng.uniform(0, 1000)),
                          float(rng.lognormal(5.0, 1.0)),
                          int(rng.choice([1, 2, 4, 8])),
                          int(rng.integers(0, 3)))
                for i in range(n)]

    def test_fit_jobs_recovers_the_mix(self):
        rng = np.random.default_rng(0)
        fit = fit_jobs(self._jobs(rng), name="t")
        assert fit.median_duration_s > 0 and 0.5 < fit.sigma < 2.0
        assert set(fit.gpu_sizes) == {1, 2, 4, 8}
        assert abs(sum(fit.gpu_probs) - 1.0) < 1e-6
        assert fit.n_tenants == 3

    def test_gen_window_fail_fasts(self):
        fit = TraceFit("t", 100.0, 1.0, (1, 2), (0.5, 0.5))
        with pytest.raises(ValueError, match="n_jobs"):
            gen_domain_window(fit, 0, 0, n_gpus=8, load=1.0)
        with pytest.raises(ValueError, match="load"):
            gen_domain_window(fit, 8, 0, n_gpus=8, load=0.0)
        with pytest.raises(ValueError, match="n_gpus"):
            gen_domain_window(fit, 8, 0, n_gpus=0, load=1.0)

    def test_gen_window_deterministic_and_gang_renormalized(self):
        fit = TraceFit("t", 100.0, 1.0, (1, 2, 4, 8),
                       (0.4, 0.3, 0.2, 0.1))
        a = gen_domain_window(fit, 32, (5, 0), n_gpus=4, load=1.0,
                              max_gang=2)
        b = gen_domain_window(fit, 32, (5, 0), n_gpus=4, load=1.0,
                              max_gang=2)
        np.testing.assert_array_equal(a.submit, b.submit)
        np.testing.assert_array_equal(a.gpus, b.gpus)
        # a shrunken cluster never receives a gang it cannot place
        assert np.asarray(a.gpus)[np.asarray(a.valid)].max() <= 2
        assert (np.asarray(a.duration)[np.asarray(a.valid)] >= 1.0).all()

    def test_offered_load_scales_arrivals(self):
        fit = TraceFit("t", 100.0, 1.0, (1,), (1.0,))
        lo = gen_domain_window(fit, 64, 1, n_gpus=8, load=0.5)
        hi = gen_domain_window(fit, 64, 1, n_gpus=8, load=2.0)
        span = lambda w: float(np.asarray(w.submit)[np.asarray(w.valid)]
                               .max())
        # 4x the offered load packs the same jobs into ~1/4 the span
        assert span(hi) < span(lo) / 2

    def test_flash_crowd_concentrates_arrivals(self):
        fit = TraceFit("t", 100.0, 1.0, (1,), (1.0,))
        flash = gen_domain_window(fit, 64, 2, n_gpus=8, load=1.0,
                                  burst_frac=0.5)
        sub = np.sort(np.asarray(flash.submit)[np.asarray(flash.valid)])
        gaps = np.diff(sub)
        # half the window lands on one instant: many near-zero gaps
        assert (gaps < 1e-3).sum() >= 16


def run_pair_domain(trace, ds, n_nodes, gpus_per_node, actions, queue_len,
                    n_placements=2, preempt_len=0):
    """Oracle and JAX sim under the same DomainSchedule (drawn capacity +
    hetero speed + drains); full-trajectory comparison after every step.
    The twin of test_sim_faults.run_pair_faulty with geometry as data:
    init_state seeds the free vector from the schedule."""
    params = C.SimParams(n_nodes=n_nodes, gpus_per_node=gpus_per_node,
                         max_jobs=trace.max_jobs, queue_len=queue_len,
                         n_placements=n_placements, preempt_len=preempt_len)
    osim = O.OracleSim(trace, n_nodes, gpus_per_node, faults=ds)
    np.testing.assert_array_equal(osim.node_capacity, ds.capacity)
    tr = C.Trace.from_array_trace(trace)
    dsd = device_schedule(ds)
    jstate = C.init_state(params, tr, dsd)
    step = jax.jit(lambda s, f, a: C.rl_step(params, s, tr, a, f))
    for i, a in enumerate(actions):
        oinfo = osim.rl_step(int(a), queue_len, n_placements, preempt_len)
        jstate, jinfo = step(jstate, dsd, jnp.int32(a))
        s = C.np_state(jstate)
        ctx = f"step {i} action {a}"
        np.testing.assert_allclose(s.clock, osim.clock, atol=1e-3,
                                   err_msg=ctx)
        np.testing.assert_array_equal(s.status, osim.status, err_msg=ctx)
        np.testing.assert_allclose(s.remaining, osim.remaining, atol=1e-3,
                                   err_msg=ctx)
        np.testing.assert_array_equal(s.alloc, osim.alloc, err_msg=ctx)
        np.testing.assert_array_equal(s.free, osim.free, err_msg=ctx)
        assert bool(jinfo.placed) == oinfo["placed"], ctx
        assert bool(jinfo.done) == oinfo["done"], ctx
        # conservation against the DRAWN capacity at every step
        np.testing.assert_array_equal(s.alloc.sum(axis=0) + s.free,
                                      ds.capacity, err_msg=ctx)
    assert osim.gpus_consistent()


class TestOracleParityHeteroGeometry:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_actions_random_domains(self, seed):
        rng = np.random.default_rng(seed)
        n_nodes, g = int(rng.integers(2, 5)), int(rng.integers(2, 5))
        draw = dyadic_draw(rng, n_nodes, g)
        # widest valid gang = the drawn total, not the static one
        trace = int_trace(rng, 12, max(draw.total_gpus // 2, 1),
                          max_jobs=16)
        ds = D.validate_domain_schedule(
            n_nodes, g, D.domain_schedule(draw, int_faults(rng, n_nodes)))
        actions = rng.integers(
            0, C.SimParams(n_nodes, g, 16, 4, 2, 2).n_actions, size=60)
        run_pair_domain(trace, ds, n_nodes, g, actions, queue_len=4,
                        preempt_len=2)

    def test_half_speed_node_doubles_service(self):
        trace = to_array_trace([JobRecord(0, 0.0, 10.0, 2)], max_jobs=2)
        params = C.SimParams(2, 2, max_jobs=2, queue_len=2, n_placements=1)
        tr = C.Trace.from_array_trace(trace)
        draw = D.DomainDraw("test", np.array([2, 2], np.int32),
                            np.array([2.0, 1.0], np.float32),
                            1.0, 1.0, 0.0, False)
        ds = device_schedule(D.domain_schedule(draw))
        state = C.init_state(params, tr, ds)
        state, info = C.rl_step(params, state, tr, jnp.int32(0), ds)
        assert bool(info.placed)
        state, info = C.rl_step(params, state, tr,
                                jnp.int32(params.n_actions - 1), ds)
        # placed on the x2 node: 10s of work completes at t=20
        assert float(state.clock) == 20.0 and bool(info.done)

    def test_absent_node_is_never_allocated(self):
        rng = np.random.default_rng(4)
        draw = D.DomainDraw("test", np.array([0, 4], np.int32),
                            np.ones(2, np.float32), 1.0, 1.0, 0.0, False)
        trace = int_trace(rng, 8, 3, max_jobs=8)
        params = C.SimParams(2, 4, max_jobs=8, queue_len=4, n_placements=2)
        tr = C.Trace.from_array_trace(trace)
        ds = device_schedule(D.domain_schedule(draw))
        state = C.init_state(params, tr, ds)
        step = jax.jit(lambda s, a: C.rl_step(params, s, tr, a, ds))
        for a in rng.integers(0, params.n_actions, size=40):
            state, _ = step(state, jnp.int32(a))
            s = C.np_state(state)
            assert s.alloc[:, 0].sum() == 0 and s.free[0] == 0


class TestCompileOnceAcrossDomains:
    def test_step_zero_retrace_across_regime_draws(self):
        rng = np.random.default_rng(0)
        trace = int_trace(rng, 10, 2, max_jobs=12)
        params = C.SimParams(3, 4, max_jobs=12, queue_len=4,
                             n_placements=1, preempt_len=2)
        tr = C.Trace.from_array_trace(trace)
        schedules = [device_schedule(D.validate_domain_schedule(
            3, 4, D.domain_schedule(D.sample_domain(name, 3, 4, (s, 0)))))
            for s, name in enumerate(D.DOMAIN_REGIMES)]
        # the step's OWN traces: its Python body runs once per trace and
        # never on a cache hit. A process-wide compile counter also sees
        # the eager ``init_state`` / ``jnp.int32`` below, whose one-op
        # programs a long-lived worker may evict and trace again.
        step_traces = []

        def rl_step(s, f, a):
            step_traces.append(jax.tree.map(jnp.shape, (s, f, a)))
            return C.rl_step(params, s, tr, a, f)

        step = jax.jit(rl_step)
        for ds in schedules:
            st = C.init_state(params, tr, ds)
            for a in rng.integers(0, params.n_actions, size=4):
                st, _ = step(st, ds, jnp.int32(a))
        jax.block_until_ready(st.clock)
        assert len(step_traces) == 1, step_traces

    def test_matrix_report_second_sweep_compiles_nothing(self):
        """A whole second matrix (fresh seed -> fresh draws, fresh
        generated windows, every regime) must reuse the first sweep's
        compiled cell — the ISSUE 14 acceptance gate: one compiled step
        serves the entire domain distribution."""
        from rlgpuschedule_tpu.analysis.sentinels import CompileCounter
        from rlgpuschedule_tpu.eval import matrix_report
        from rlgpuschedule_tpu.experiment import Experiment
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=16, queue_len=4, horizon=256)
        exp = Experiment.build(cfg)
        kw = dict(regimes=("geom", "overload"), baselines=("sjf",),
                  max_steps=192)
        matrix_report(exp, seed=0, **kw)                       # warmup
        with CompileCounter() as counter:
            report = matrix_report(exp, seed=1, **kw)
        assert counter.total == 0, counter.events
        assert report["jobs_lost"] == 0


class TestEnvAndTrainingWiring:
    def _cfg(self, **kw):
        from rlgpuschedule_tpu.configs import CONFIGS
        base = dict(n_envs=2, n_nodes=2, gpus_per_node=4, window_jobs=16,
                    queue_len=4, horizon=64, iterations=2,
                    domains="mixed")
        return dataclasses.replace(CONFIGS["ppo-mlp-synth64"],
                                   **{**base, **kw})

    def test_domain_obs_shape_and_geometry_values(self):
        from rlgpuschedule_tpu.env import env as env_lib
        params = C.SimParams(2, 4, max_jobs=4, queue_len=2, n_placements=1)
        ep = env_lib.EnvParams(sim=params,
                               domain_process=D.resolve_domain("mixed"),
                               domain_obs=True)
        base = env_lib.EnvParams(sim=params)
        assert ep.obs_shape()[0] == base.obs_shape()[0] + 2
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 1)], max_jobs=4)
        tr = C.Trace.from_array_trace(trace)
        draw = D.DomainDraw("test", np.array([2, 4], np.int32),
                            np.ones(2, np.float32), 1.0, 1.0, 0.0, False)
        ds = device_schedule(D.domain_schedule(draw))
        _, ts = env_lib.reset(ep, tr, ds)
        # geometry channel: capacity / gpus_per_node, appended LAST
        np.testing.assert_allclose(np.asarray(ts.obs[-2:]), [0.5, 1.0])
        # schedule=None replay reads as the full fixed cluster
        _, ts = env_lib.reset(ep, tr)
        np.testing.assert_allclose(np.asarray(ts.obs[-2:]), [1.0, 1.0])

    def test_domain_obs_refused_for_grid(self):
        from rlgpuschedule_tpu.env import env as env_lib
        params = C.SimParams(2, 2, max_jobs=4, queue_len=2)
        with pytest.raises(ValueError, match="FLAT"):
            env_lib.EnvParams(sim=params, obs_kind="grid",
                              domain_obs=True)

    def test_domains_none_is_bit_identical(self):
        # the pre-domains program: no schedule -> static full cluster,
        # and a full-capacity no-fault DomainSchedule is the SAME state
        rng = np.random.default_rng(0)
        trace = int_trace(rng, 6, 4, max_jobs=8)
        params = C.SimParams(2, 4, max_jobs=8, queue_len=4)
        tr = C.Trace.from_array_trace(trace)
        clean = C.init_state(params, tr)
        np.testing.assert_array_equal(np.asarray(clean.free), [4, 4])
        draw = D.DomainDraw("test", np.array([4, 4], np.int32),
                            np.ones(2, np.float32), 1.0, 1.0, 0.0, False)
        ds = device_schedule(D.domain_schedule(draw))
        seeded = C.init_state(params, tr, ds)
        for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(seeded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_experiment_trains_under_domains(self):
        from rlgpuschedule_tpu.experiment import Experiment
        exp = Experiment.build(self._cfg())
        assert exp.domains is not None and len(exp.domains) == 2
        assert exp.env_params.domain_obs and exp.env_params.fault_obs
        assert isinstance(exp.faults, D.DomainSchedule)
        # windows were generated against each draw's ACTUAL capacity
        for w, d in zip(exp.windows, exp.domains):
            gpus = np.asarray(w.gpus)[np.asarray(w.valid)]
            assert gpus.max() <= d.total_gpus
        out = exp.run(log_every=1)
        assert np.isfinite(out["history"][-1]["total_loss"])

    def test_window_streaming_regenerates_domain_windows(self):
        from rlgpuschedule_tpu.experiment import Experiment
        exp = Experiment.build(self._cfg(resample_every=1))
        first = [np.asarray(w.submit).copy() for w in exp.windows]
        exp.run(log_every=1)
        assert exp.window_cursor > 0
        changed = any(not np.array_equal(a, np.asarray(w.submit))
                      for a, w in zip(first, exp.windows))
        assert changed    # fresh draws of the arrival process, same shape

    def test_mode_table_rows(self):
        from rlgpuschedule_tpu.configs import MODE_REFUSALS
        pairs = {frozenset((a, b)) for a, b, _ in MODE_REFUSALS}
        assert frozenset(("pbt", "faults")) not in pairs   # ISSUE 14 sat 1
        assert frozenset(("pbt", "domains")) in pairs
        assert frozenset(("hier", "domains")) in pairs

    def test_hier_and_pbt_refuse_domains(self):
        from rlgpuschedule_tpu.experiment import (Experiment,
                                                  PopulationExperiment)
        with pytest.raises(ValueError, match="domain"):
            Experiment.build(self._cfg(n_pods=2, n_nodes=4))
        with pytest.raises(ValueError, match="domain"):
            PopulationExperiment.build(self._cfg(), n_pop=2)


class TestMatrixReport:
    def _exp(self):
        from rlgpuschedule_tpu.experiment import Experiment
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=16, queue_len=4, horizon=256)
        return Experiment.build(cfg)

    def test_matrix_shape_degradation_conservation_and_bus(self, tmp_path):
        from rlgpuschedule_tpu.eval import matrix_report
        from rlgpuschedule_tpu.obs import EventBus, Registry, read_events
        exp = self._exp()
        bus = EventBus(str(tmp_path), rank=0, name="matrix")
        registry = Registry()
        report = matrix_report(exp, regimes=("geom",), baselines=("sjf",),
                               seed=0, max_steps=192, bus=bus,
                               registry=registry)
        bus.close()
        assert set(report["cells"]) == {"none", "geom"}
        for cols in report["cells"].values():
            assert set(cols) == {"policy", "sjf"}
            for row in cols.values():
                assert {"avg_jct", "completion", "degradation"} <= set(row)
        assert report["cells"]["none"]["policy"]["degradation"] == 1.0
        assert report["jobs_lost"] == 0
        assert report["domain_stats"]["geom"]["mean_total_gpus"] <= 8.0
        events = read_events(str(tmp_path / "events.matrix.jsonl"))
        cells = [e for e in events if e["kind"] == "domain_cell"]
        assert {(e["regime"], e["scheduler"]) for e in cells} == {
            ("none", "policy"), ("none", "sjf"),
            ("geom", "policy"), ("geom", "sjf")}
        assert "matrix_none_policy_avg_jct" in registry.render()

    def test_matrix_refuses_mismatched_row_geometry(self):
        from rlgpuschedule_tpu.eval import matrix_report
        exp = self._exp()
        other = dataclasses.replace(
            exp.env_params, sim=dataclasses.replace(exp.env_params.sim,
                                                    gpus_per_node=8))
        with pytest.raises(ValueError, match="sim geometry"):
            matrix_report(exp, regimes=("geom",), policies={
                "a": (exp.apply_fn, exp.train_state.params,
                      exp.env_params),
                "b": (exp.apply_fn, exp.train_state.params, other)})


class TestFullTraceSchedules:
    def test_shift_schedule_rebase(self):
        from rlgpuschedule_tpu.eval import _shift_schedule
        fs = F.no_faults(1, 3)
        fs.down_start[0] = [10.0, 50.0, 90.0]
        fs.down_end[0] = [20.0, 60.0, 100.0]
        out = _shift_schedule(F.validate_fault_schedule(1, fs), 55.0)
        # past window -> never-active; straddling -> active from local 0;
        # future -> shifted left
        np.testing.assert_allclose(out.down_start[0], [np.inf, 0.0, 35.0])
        np.testing.assert_allclose(out.down_end[0], [np.inf, 5.0, 45.0])
        draw = D.DomainDraw("test", np.array([3], np.int32),
                            np.array([2.0], np.float32),
                            1.0, 1.0, 0.0, False)
        ds = D.domain_schedule(draw, F.validate_fault_schedule(1, fs))
        out = _shift_schedule(ds, 55.0)
        assert isinstance(out, D.DomainSchedule)   # type survives rebase
        np.testing.assert_array_equal(out.capacity, [3])
        np.testing.assert_array_equal(out.slowdown, [2.0])

    def test_stitched_replay_feels_hetero_slowdown(self):
        from rlgpuschedule_tpu.eval import full_trace_report
        from rlgpuschedule_tpu.experiment import Experiment
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
            source_jobs=24)
        exp = Experiment.build(cfg)
        draw = D.DomainDraw("test", np.array([4, 4], np.int32),
                            np.array([2.0, 2.0], np.float32),
                            1.0, 1.0, 0.0, False)
        ds = D.domain_schedule(draw)
        slow = full_trace_report(exp, include_random=False,
                                 baselines=("sjf",), faults=ds)
        clean = full_trace_report(exp, include_random=False,
                                  baselines=("sjf",))
        assert slow["faulty_cluster"] is True
        # every node at half speed: strictly worse JCT for everyone
        assert slow["policy"] > clean["policy"]
        assert slow["sjf"] > clean["sjf"]

    def test_demand_check_uses_drawn_capacity(self):
        from rlgpuschedule_tpu.eval import full_trace_replay
        from rlgpuschedule_tpu.experiment import Experiment
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
            source_jobs=24)
        exp = Experiment.build(cfg)
        draw = D.DomainDraw("test", np.array([1, 0], np.int32),
                            np.ones(2, np.float32), 1.0, 1.0, 0.0, False)
        with pytest.raises(ValueError, match="drawn cluster has 1"):
            full_trace_replay(exp.apply_fn, exp.train_state.params,
                              exp.env_params, exp.source,
                              faults=D.domain_schedule(draw))


class TestCLIRefusals:
    def test_matrix_flag_refusals(self):
        from rlgpuschedule_tpu import evaluate
        for argv in (["--matrix", "--chaos"],
                     ["--matrix-regimes", "geom"],
                     ["--matrix", "--matrix-regimes", "meteor"],
                     ["--matrix", "--eval-windows", "4"],
                     ["--matrix", "--matrix-ckpt", "nodir"],
                     ["--stitch-domain", "hetero"],
                     ["--obs-dir", "/tmp/x"]):
            with pytest.raises(SystemExit):
                evaluate.main(argv)
