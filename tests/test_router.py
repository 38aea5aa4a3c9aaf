"""Multi-engine serving router tests (ISSUE 13): routed-fleet-vs-single
bit-identity under the recompile sentinel, least-loaded dispatch
fairness, deadline-aware shedding (a shed request is NEVER a silent
drop — its future resolves with a typed rejection), the adaptive
batching estimators, and autoscale-advisor hysteresis (no flapping on
a steady load)."""
import numpy as np
import pytest

from rlgpuschedule_tpu.configs import (ModeCombinationError,
                                       validate_mode_combination)
from rlgpuschedule_tpu.obs import Registry
from rlgpuschedule_tpu.parallel.mesh import serve_devices
from rlgpuschedule_tpu.serve import (AutoscaleAdvisor, DeadlineSheddedError,
                                     EngineRouter, Ewma, InferenceEngine,
                                     InjectedEngineFault, PolicyServer,
                                     ServeFaultInjector, ServeFaultSpec,
                                     ServeResult, ServerClosedError,
                                     next_bucket, parse_serve_fault)

OBS_D, ACT_D = 6, 9


def linear_apply(params, obs, mask):
    """Row-wise linear policy head — batch-composition invariant by
    construction, so per-request actions are comparable no matter how
    the router coalesced them."""
    return obs @ params["w"], None


def make_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((OBS_D, ACT_D)).astype(np.float32)}


def make_batch(rng, n):
    obs = rng.standard_normal((n, OBS_D)).astype(np.float32)
    mask = rng.integers(0, 2, (n, ACT_D)).astype(bool)
    mask[:, 0] = True           # at least one legal action per row
    return obs, mask


def make_router(n_engines=2, max_bucket=8, registry=None, **kw):
    return EngineRouter(linear_apply, make_params(), max_bucket=max_bucket,
                        registry=registry, stall_gate=False,
                        n_engines=n_engines, **kw)


class FakeEngine:
    """Host-only engine stand-in for batching-policy tests: every
    dispatch advances the shared fake clock by ``cost_s``, so the
    server's service-time estimator learns an exact, deterministic
    value (no real timing in the deadline tests)."""

    def __init__(self, clock_cell, max_bucket=8, cost_s=0.05):
        self.max_bucket = max_bucket
        self.cost_s = cost_s
        self.dispatches = 0
        self._t = clock_cell

    def bucket_for(self, n):
        return next_bucket(n, self.max_bucket)

    def decide(self, obs, mask, stall=None):
        n = int(np.asarray(obs).shape[0])
        self._t[0] += self.cost_s
        self.dispatches += 1
        return np.asarray(obs), self.bucket_for(n)


def fake_server(max_bucket=8, cost_s=0.05, **kw):
    t = [0.0]
    reg = Registry()
    server = PolicyServer(FakeEngine(t, max_bucket, cost_s), registry=reg,
                          clock=lambda: t[0], **kw)
    return server, t, reg


def row(rng):
    return (rng.standard_normal(OBS_D).astype(np.float32),
            np.ones(ACT_D, bool))


class TestRoutedBitIdentity:
    """The tentpole contract: a routed fleet of N engines is bit-identical
    to ONE engine fed the same request stream, with zero post-warmup
    recompiles PER ENGINE (CompileCounter-gated via the per-engine
    labeled sentinel counters)."""

    def test_fleet_matches_single_engine_bitwise(self):
        assert len(serve_devices()) >= 2, \
            "conftest forces 8 virtual CPU devices"
        params = make_params()
        router = EngineRouter(linear_apply, params, max_bucket=8,
                              registry=Registry(), stall_gate=False,
                              n_engines=2)
        single = InferenceEngine(linear_apply, params, max_bucket=8,
                                 registry=Registry(), stall_gate=False)
        rng = np.random.default_rng(0)
        batches = [make_batch(rng, int(rng.integers(1, 9)))
                   for _ in range(12)]
        obs0, mask0 = batches[0]
        router.warmup(obs0[0], mask0[0])
        single.warmup(obs0[0], mask0[0])
        for obs, mask in batches:
            a_r, b_r = router.decide(obs, mask)
            a_s, b_s = single.decide(obs, mask)
            assert b_r == b_s
            assert np.array_equal(np.asarray(a_r), np.asarray(a_s))
        # the zero-recompile contract is per engine, not fleet-aggregate
        assert router.per_engine_recompiles() == [0, 0]
        assert single.post_warmup_recompiles == 0
        rows = [s.rows for s in router.stats()]
        assert all(r > 0 for r in rows), \
            f"both engines must actually serve, got rows={rows}"
        assert sum(rows) == sum(o.shape[0] for o, _ in batches)

    def test_threaded_fleet_matches_rowwise_reference(self):
        """End-to-end through the PolicyServer with 2 live dispatcher
        threads: whatever batches the router coalesced, every request's
        action equals the single-engine answer for its own row."""
        params = make_params()
        reg = Registry()
        router = make_router(registry=reg)
        single = InferenceEngine(linear_apply, params, max_bucket=8,
                                 registry=Registry(), stall_gate=False)
        rng = np.random.default_rng(1)
        rows = [row(rng) for _ in range(60)]
        router.warmup(*rows[0])
        single.warmup(*rows[0])
        server = PolicyServer(router, registry=reg)
        server.start(dispatchers=2)
        try:
            futs = [server.submit(o, m) for o, m in rows]
            got = [f.result(timeout=60).action for f in futs]
        finally:
            server.stop()
        for (o, m), a in zip(rows, got):
            ref, _ = single.decide(o[None], m[None])
            assert np.array_equal(np.asarray(a), np.asarray(ref)[0])
        assert router.per_engine_recompiles() == [0, 0]

    def test_per_engine_labeled_series_in_scrape(self):
        reg = Registry()
        router = make_router(registry=reg)
        rng = np.random.default_rng(2)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0], buckets=(4,))
        router.decide(obs, mask)
        router.decide(obs, mask)
        text = reg.render()
        for i in (0, 1):
            assert f'serve_engine_rows_total{{engine="{i}"}}' in text
            assert f'serve_recompile_alarms_total{{engine="{i}"}}' in text
        assert "serve_engines_total 2" in text
        assert "serve_engines_active 2" in text


class TestLeastLoaded:
    def test_equal_batches_split_evenly(self):
        router = make_router(max_bucket=4)
        rng = np.random.default_rng(3)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0], buckets=(4,))
        for _ in range(6):
            router.decide(obs, mask)
        stats = router.stats()
        assert [s.dispatches for s in stats] == [3, 3]
        assert [s.rows for s in stats] == [12, 12]

    def test_fewest_rows_breaks_ties(self):
        """Sequential dispatches (inflight always 0 at pick time) route
        by lifetime rows: after a big batch lands on engine 0, the
        smaller ones pile onto engine 1 until it catches up."""
        router = make_router(max_bucket=8)
        rng = np.random.default_rng(4)
        o8, m8 = make_batch(rng, 8)
        o1, m1 = make_batch(rng, 1)
        router.warmup(o8[0], m8[0], buckets=(1, 8))
        router.decide(o8, m8)           # engine 0: 8 rows
        for _ in range(8):
            router.decide(o1, m1)       # all catch-up goes to engine 1
        stats = router.stats()
        assert stats[0].rows == 8
        assert stats[1].rows == 8

    def test_inflight_preferred_over_rows(self):
        router = make_router()
        assert router._acquire() == 0
        assert router._acquire() == 1   # engine 0 is busy
        router._release(0, 0, None)     # aborted dispatch: no rows booked
        assert router._acquire() == 0   # free again, beats busy engine 1
        router._release(0, 0, None)
        router._release(1, 0, None)
        assert all(s.inflight == 0 for s in router.stats())

    def test_set_active_drains_and_reactivates(self):
        router = make_router(max_bucket=4)
        rng = np.random.default_rng(5)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0], buckets=(4,))
        assert router.set_active(1) == 1
        for _ in range(4):
            router.decide(obs, mask)
        stats = router.stats()
        assert stats[0].dispatches == 4 and stats[1].dispatches == 0
        assert not stats[1].active
        assert router.set_active(2) == 2
        router.decide(obs, mask)        # least-loaded: engine 1 next
        assert router.stats()[1].dispatches == 1
        assert router.per_engine_recompiles() == [0, 0]

    def test_spinup_warms_cold_engine_before_traffic(self):
        """An engine activated AFTER warmup gets its blessed compiles
        from the stored example before it takes traffic — so its
        recompile counter stays 0 through live dispatches."""
        router = make_router(max_bucket=4)
        rng = np.random.default_rng(6)
        obs, mask = make_batch(rng, 4)
        router.set_active(1)
        router.warmup(obs[0], mask[0])          # engine 1 inactive: cold
        assert router.engines[1].warmed_buckets == ()
        router.set_active(2)
        assert router.engines[1].warmed_buckets != ()
        for _ in range(4):
            router.decide(obs, mask)
        assert router.per_engine_recompiles() == [0, 0]
        assert router.stats()[1].rows > 0

    def test_set_active_clamps(self):
        router = make_router()
        assert router.set_active(0) == 1        # never below one engine
        assert router.set_active(99) == 2       # never above the fleet

    def test_set_active_fires_rewarm_listeners_on_change_only(self):
        """ISSUE 17 satellite: a fleet change (spin-up warm or active-
        count change) notifies re-warm listeners — the PolicyServer
        resets its service-time Ewma off this hook — while a no-op
        ``set_active`` stays silent (no estimator churn on the advisor's
        steady-state ticks)."""
        router = make_router(max_bucket=4)
        fired = []
        router.add_rewarm_listener(lambda: fired.append(1))
        assert router.set_active(2) == 2        # already 2: no change
        assert fired == []
        assert router.set_active(1) == 1
        assert len(fired) == 1
        assert router.set_active(1) == 1        # steady: still silent
        assert len(fired) == 1
        rng = np.random.default_rng(7)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0])          # engine 1 inactive: cold
        router.set_active(2)                    # spin-up warm => fires
        assert len(fired) == 2

    def test_policy_server_resets_estimator_on_router_rewarm(self):
        """End-to-end wiring: PolicyServer registers on the router at
        construction; a set_active fleet change wipes the learned
        service time (back to cold-admit until relearned)."""
        router = make_router(max_bucket=4)
        rng = np.random.default_rng(8)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0])
        server = PolicyServer(router, example_obs=obs[0],
                              example_mask=mask[0])
        for i in range(4):
            server.submit(obs[i], mask[i])
        assert server.pump() == 4
        assert server.service_time_s() is not None
        router.set_active(1)                    # fleet changed
        assert server.service_time_s() is None  # estimator reset
        server.close()

    def test_n_engines_validation(self):
        with pytest.raises(ValueError, match="n_engines"):
            make_router(n_engines=0)
        with pytest.raises(ValueError, match="n_engines"):
            make_router(n_engines=len(serve_devices()) + 1)

    def test_serialized_dispatch_honesty_bit_on_cpu(self):
        assert make_router().serialized_dispatch() is True

    def test_router_hier_combination_refused(self):
        with pytest.raises(ModeCombinationError, match="router"):
            validate_mode_combination({"router": True, "hier": True})
        validate_mode_combination({"router": True, "hier": False})
        validate_mode_combination({"router": False, "hier": True})


class TestDeadlineShedding:
    def test_expired_request_resolves_with_typed_rejection(self):
        server, t, reg = fake_server()
        rng = np.random.default_rng(7)
        fut = server.submit(*row(rng), deadline_s=0.5)
        t[0] += 1.0
        assert server.pump() == 0       # nothing left to serve
        assert fut.done()
        with pytest.raises(DeadlineSheddedError) as ei:
            fut.result()
        assert ei.value.reason == "expired"
        assert ei.value.waited_s == pytest.approx(1.0)
        assert reg.counter("serve_shed_total").value == 1

    def test_admission_shed_uses_learned_service_time(self):
        server, t, reg = fake_server(cost_s=0.05)
        rng = np.random.default_rng(8)
        ok = server.submit(*row(rng))
        server.pump()                   # learns service time = 0.05
        assert isinstance(ok.result(), ServeResult)
        fut = server.submit(*row(rng), deadline_s=0.01)
        assert fut.done()               # rejected at the door, no queue
        with pytest.raises(DeadlineSheddedError) as ei:
            fut.result()
        assert ei.value.reason == "admission"
        assert ei.value.predicted_wait_s == pytest.approx(0.05)
        assert reg.counter("serve_shed_total").value == 1
        assert server.pump() == 0       # the shed request never queued

    def test_cold_server_admits_rather_than_guessing(self):
        server, t, _ = fake_server()
        rng = np.random.default_rng(9)
        fut = server.submit(*row(rng), deadline_s=1e-9)
        assert not fut.done()           # no service estimate yet: admit
        assert server.pump() == 1       # clock hasn't moved: still fresh
        assert isinstance(fut.result(), ServeResult)

    def test_mid_queue_expiry_not_masked_by_generous_head(self):
        """Deadlines are per-request: an expired TAIL request sheds even
        when the queue head has no deadline at all (full-scan, not
        head-only)."""
        server, t, reg = fake_server()
        rng = np.random.default_rng(10)
        head = server.submit(*row(rng))
        tail = server.submit(*row(rng), deadline_s=0.1)
        t[0] += 0.2
        assert server.pump() == 1
        assert isinstance(head.result(), ServeResult)
        with pytest.raises(DeadlineSheddedError):
            tail.result()
        assert reg.counter("serve_shed_total").value == 1

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_no_request_is_ever_silently_dropped(self, seed):
        """Property: for a random stream of deadlined and deadline-free
        requests under a randomly advancing clock, EVERY future
        resolves — to a ServeResult or a DeadlineSheddedError — and the
        shed counter equals exactly the number of typed rejections."""
        server, t, reg = fake_server(max_bucket=4, cost_s=0.02)
        rng = np.random.default_rng(seed)
        futs = []
        for _ in range(40):
            deadline = (None if rng.random() < 0.4
                        else float(rng.uniform(0.005, 0.2)))
            futs.append(server.submit(*row(rng), deadline_s=deadline))
            t[0] += float(rng.uniform(0.0, 0.05))
            if rng.random() < 0.3:
                server.pump()
        while server.queue_depth():
            server.pump()
        shed = 0
        for f in futs:
            assert f.done(), "a submitted request's future never resolved"
            try:
                assert isinstance(f.result(), ServeResult)
            except DeadlineSheddedError:
                shed += 1
        assert reg.counter("serve_shed_total").value == shed
        assert shed + sum(1 for f in futs
                          if not f.exception()) == len(futs)


class TestAdaptiveWait:
    def test_static_mode_returns_the_knob(self):
        server, t, _ = fake_server(max_wait_s=0.02)
        rng = np.random.default_rng(11)
        server.submit(*row(rng))
        assert server._effective_wait() == 0.02

    def test_adaptive_holds_for_estimated_fill_time(self):
        server, t, _ = fake_server(max_bucket=8, adaptive_wait=True)
        rng = np.random.default_rng(12)
        server.submit(*row(rng))
        assert server._effective_wait() is None     # nothing learned yet
        t[0] += 0.1
        server.submit(*row(rng))
        t[0] += 0.1
        server.submit(*row(rng))                    # arrival gap -> 0.1
        # 3 pending of 8: hold ~= gap x free slots = 0.1 * 5
        assert server._effective_wait() == pytest.approx(0.5)

    def test_deadline_slack_clips_the_hold(self):
        server, t, _ = fake_server(max_bucket=8, cost_s=0.05,
                                   adaptive_wait=True)
        rng = np.random.default_rng(13)
        f = server.submit(*row(rng))
        server.pump()                               # learn service 0.05
        f.result()
        server.submit(*row(rng), deadline_s=0.08)
        # slack 0.08 minus one service time in hand = 0.03, well under
        # any fill estimate — the head sheds nothing, it dispatches early
        assert server._effective_wait() == pytest.approx(0.03)

    def test_expired_slack_floors_at_zero(self):
        server, t, _ = fake_server(max_bucket=8, cost_s=0.05,
                                   adaptive_wait=True)
        rng = np.random.default_rng(14)
        f = server.submit(*row(rng))
        server.pump()
        f.result()
        server.submit(*row(rng), deadline_s=0.06)   # admitted: 0.05 fits
        t[0] += 0.1                                 # ...then the SLO dies
        assert server._effective_wait() == 0.0


class TestEwma:
    def test_unlearned_is_none(self):
        assert Ewma().value is None

    def test_update_math(self):
        e = Ewma(alpha=0.2)
        assert e.update(1.0) == pytest.approx(1.0)
        assert e.update(2.0) == pytest.approx(0.2 * 2.0 + 0.8 * 1.0)
        assert e.count == 2

    def test_alpha_validation(self):
        for bad in (0.0, -0.1, 1.5):
            with pytest.raises(ValueError, match="alpha"):
                Ewma(alpha=bad)


def advisor_reg(p99=10.0, depth=0, occ=0.6, shed=0):
    """Registry primed with a healthy steady-state SLO surface; override
    one signal per test."""
    reg = Registry()
    reg.gauge("serve_decision_latency_p99_ms").set(p99)
    reg.gauge("serve_queue_depth").set(depth)
    reg.gauge("serve_batch_occupancy").set(occ)
    if shed:
        reg.counter("serve_shed_total").inc(shed)
    return reg


class TestAutoscaleHysteresis:
    def test_steady_load_never_flaps(self):
        """The headline property: a healthy steady load holds the fleet
        size forever — zero resizes over many ticks."""
        reg = advisor_reg()
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=3)
        for _ in range(20):
            assert adv.observe() == 2
        assert reg.counter("serve_autoscale_resizes_total").value == 0
        assert reg.gauge("serve_autoscale_desired_engines").value == 2

    def test_scale_up_needs_consecutive_votes(self):
        reg = advisor_reg(depth=100)
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=3,
                               queue_high=64)
        assert adv.observe() == 2
        assert adv.observe() == 2
        assert adv.observe() == 3       # third consecutive up vote lands
        assert reg.counter("serve_autoscale_resizes_total").value == 1

    def test_mixed_votes_reset_the_streak(self):
        reg = advisor_reg(depth=100)
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=3)
        adv.observe(); adv.observe()                    # two up votes
        reg.gauge("serve_queue_depth").set(0)           # healthy: hold
        assert adv.observe() == 2                       # streak reset
        reg.gauge("serve_queue_depth").set(100)
        adv.observe(); adv.observe()
        assert adv.desired == 2                         # needs a fresh 3
        assert adv.observe() == 3

    def test_scale_down_on_idle_clamps_at_n_min(self):
        reg = advisor_reg(p99=5.0, occ=0.1)
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=2)
        adv.observe()
        assert adv.observe() == 1
        for _ in range(6):
            assert adv.observe() == 1   # clamped, no further resizes
        assert reg.counter("serve_autoscale_resizes_total").value == 1

    def test_shedding_is_an_up_vote(self):
        reg = advisor_reg()
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=1)
        assert adv.observe() == 2                       # no shed delta
        reg.counter("serve_shed_total").inc(3)
        assert adv.observe() == 3                       # delta observed
        assert adv.observe() == 3                       # delta consumed

    def test_p99_over_target_is_an_up_vote(self):
        reg = advisor_reg(p99=80.0)
        adv = AutoscaleAdvisor(reg, n_max=4, initial=2, hysteresis=1,
                               p99_target_ms=50.0)
        assert adv.observe() == 3

    def test_unset_gauges_never_scale_up(self):
        """A fresh registry reads all-zero: that can only ever look like
        idleness, never pressure — the advisor must not invent load."""
        adv = AutoscaleAdvisor(Registry(), n_max=4, initial=2,
                               hysteresis=1)
        for _ in range(5):
            assert adv.observe() <= 2

    def test_router_applies_votes_live(self):
        reg = advisor_reg(p99=5.0, occ=0.1)
        router = make_router(max_bucket=4, registry=reg)
        rng = np.random.default_rng(15)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0], buckets=(4,))
        adv = AutoscaleAdvisor(reg, n_max=2, initial=2, hysteresis=1)
        assert router.apply_autoscale(adv) == 1         # idle: drain
        reg.gauge("serve_queue_depth").set(100)
        assert router.apply_autoscale(adv) == 2         # pressure: grow
        router.decide(obs, mask)
        assert router.per_engine_recompiles() == [0, 0]

    def test_validation(self):
        with pytest.raises(ValueError, match="n_min"):
            AutoscaleAdvisor(Registry(), n_max=0)
        with pytest.raises(ValueError, match="hysteresis"):
            AutoscaleAdvisor(Registry(), n_max=2, hysteresis=0)


# ---- ISSUE 16: engine fault tolerance ---------------------------------

class _Bus:
    """Event-bus stand-in recording (kind, fields) tuples."""

    def __init__(self):
        self.events = []

    def emit(self, kind, **fields):
        self.events.append((kind, fields))

    def kinds(self):
        return [k for k, _ in self.events]


def health_router(specs, injector_kw=None, bus=None, **kw):
    """2-engine router with a fake monotonic clock (cell-advanced) and
    an armed fault injector, for deterministic ejection/backoff tests."""
    now = [100.0]
    inj = ServeFaultInjector(specs, bus=bus, **(injector_kw or {}))
    router = make_router(registry=Registry(), fault_injector=inj, bus=bus,
                         probe_backoff_s=0.5, clock=lambda: now[0], **kw)
    return router, now


class TestServeFaultSpecs:
    def test_parse_round_trip(self):
        s = parse_serve_fault("engine-hang@10:engine=1")
        assert (s.kind, s.at, s.engine, s.fired) == \
            ("engine-hang", 10, 1, False)
        assert parse_serve_fault(" engine-raise@3 ").engine == 0

    @pytest.mark.parametrize("bad", [
        "engine-raise", "nope@3", "engine-raise@x",
        "engine-raise@3:rank=1", "engine-raise@3:engine=x"])
    def test_parse_rejects_with_the_offending_spec(self, bad):
        with pytest.raises(ValueError, match="serve-fault"):
            parse_serve_fault(bad)

    def test_ge_semantics_fire_exactly_once(self):
        """A spec fires on the FIRST dispatch with seq >= at landing on
        its engine (exact-match would lose the race to the other pump
        thread forever), and never again."""
        inj = ServeFaultInjector([ServeFaultSpec("engine-raise", at=2,
                                                 engine=1)])
        inj.on_dispatch(1, 0)                   # below at: no-op
        inj.on_dispatch(0, 5)                   # wrong engine: no-op
        with pytest.raises(InjectedEngineFault):
            inj.on_dispatch(1, 5)               # >= at: fires
        inj.on_dispatch(1, 6)                   # spent: no-op
        assert inj.specs[0].fired

    def test_slow_returns_hang_raises(self):
        inj = ServeFaultInjector(
            [ServeFaultSpec("engine-slow", at=0),
             ServeFaultSpec("engine-hang", at=1)],
            slow_s=0.0, hang_s=0.0)
        inj.on_dispatch(0, 0)                   # brownout: succeeds
        with pytest.raises(InjectedEngineFault, match="hung"):
            inj.on_dispatch(0, 1)


class TestEngineHealth:
    def test_consecutive_failures_eject_then_backoff_readmits(self):
        router, now = health_router(
            [ServeFaultSpec("engine-raise", at=0),
             ServeFaultSpec("engine-raise", at=0)])
        rng = np.random.default_rng(20)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0])
        router.decide(obs, mask)        # fail 1 on engine 0 -> hedge
        router.decide(obs, mask)        # fail 2 -> EJECT -> hedge
        fs = router.fault_stats()
        assert fs == {"failures": 2, "ejections": 1, "readmissions": 0,
                      "retry_hedges": 2, "engines_ejected": 1}
        st = router.stats()
        assert st[0].ejected and not st[1].ejected
        assert st[0].consecutive_failures == 2
        router.decide(obs, mask)        # backoff not elapsed: no probe
        assert router.stats()[0].dispatches == 0
        now[0] += 1.0                   # past the 0.5s backoff
        router.decide(obs, mask)        # probe passes -> readmitted
        fs = router.fault_stats()
        assert fs["readmissions"] == 1 and fs["engines_ejected"] == 0
        st = router.stats()
        assert not st[0].ejected and st[0].consecutive_failures == 0
        assert st[0].dispatches >= 1    # taking traffic again
        assert router.per_engine_recompiles() == [0, 0]

    def test_single_transient_failure_never_ejects(self):
        router, _ = health_router([ServeFaultSpec("engine-raise", at=0)])
        rng = np.random.default_rng(21)
        obs, mask = make_batch(rng, 2)
        router.warmup(obs[0], mask[0])
        a, b = router.decide(obs, mask)         # hedged transparently
        assert np.asarray(a).shape[0] == 2 and b == 2
        router.decide(obs, mask)                # success resets streak
        fs = router.fault_stats()
        assert fs["failures"] == 1 and fs["ejections"] == 0
        assert all(s.consecutive_failures == 0 for s in router.stats())

    def test_slow_engine_is_not_ejected(self):
        """Brownout discipline: a slow dispatch SUCCEEDS — health
        tracking must not drain capacity over latency alone."""
        router, _ = health_router([ServeFaultSpec("engine-slow", at=0)],
                                  injector_kw={"slow_s": 0.0})
        rng = np.random.default_rng(22)
        obs, mask = make_batch(rng, 2)
        router.warmup(obs[0], mask[0])
        router.decide(obs, mask)
        fs = router.fault_stats()
        assert fs["failures"] == 0 and fs["retry_hedges"] == 0

    def test_failed_probe_doubles_backoff_until_fault_clears(self):
        router, now = health_router(
            [ServeFaultSpec("engine-raise", at=0),
             ServeFaultSpec("engine-raise", at=0),
             ServeFaultSpec("engine-raise", at=0)])
        rng = np.random.default_rng(23)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0])
        router.decide(obs, mask)        # fail 1
        router.decide(obs, mask)        # fail 2 -> eject, probe at +0.5
        now[0] += 0.6
        router.decide(obs, mask)        # probe fires spec 3 -> FAILS
        fs = router.fault_stats()
        assert fs["failures"] == 3 and fs["readmissions"] == 0
        assert router.stats()[0].ejected
        now[0] += 0.5                   # inside the DOUBLED (1s) backoff
        router.decide(obs, mask)
        assert router.fault_stats()["readmissions"] == 0
        now[0] += 1.0                   # past it; fault set exhausted
        router.decide(obs, mask)
        fs = router.fault_stats()
        assert fs["readmissions"] == 1 and fs["engines_ejected"] == 0

    def test_total_engine_loss_raises_then_recovers(self):
        router, now = health_router(
            [ServeFaultSpec("engine-raise", at=0, engine=0),
             ServeFaultSpec("engine-raise", at=0, engine=1)],
            eject_after=1)
        rng = np.random.default_rng(24)
        obs, mask = make_batch(rng, 2)
        router.warmup(obs[0], mask[0])
        with pytest.raises(InjectedEngineFault):
            router.decide(obs, mask)    # both engines eject, loudly
        fs = router.fault_stats()
        assert fs["engines_ejected"] == 2 and fs["retry_hedges"] == 1
        with pytest.raises(RuntimeError, match="no active healthy"):
            router.decide(obs, mask)    # nothing to serve with
        now[0] += 1.0                   # probes pass (faults spent)
        a, b = router.decide(obs, mask)
        assert b == 2
        assert router.fault_stats()["readmissions"] == 2

    def test_lifecycle_lands_on_the_event_bus(self):
        bus = _Bus()
        router, now = health_router(
            [ServeFaultSpec("engine-raise", at=0),
             ServeFaultSpec("engine-raise", at=0)], bus=bus)
        rng = np.random.default_rng(25)
        obs, mask = make_batch(rng, 2)
        router.warmup(obs[0], mask[0])
        router.decide(obs, mask)
        router.decide(obs, mask)
        now[0] += 1.0
        router.decide(obs, mask)
        kinds = bus.kinds()
        for want in ("serve_fault", "serve_retry", "engine_eject",
                     "engine_readmit"):
            assert want in kinds, kinds
        eject = dict(bus.events)["engine_eject"]
        assert eject["engine"] == 0
        assert eject["consecutive_failures"] == 2
        assert eject["error"] == "InjectedEngineFault"

    def test_hedged_batch_is_bit_identical_to_healthy_fleet(self):
        """The retry hedge must not change ANSWERS: a faulted fleet's
        output equals a healthy single engine's for the same rows."""
        router, _ = health_router([ServeFaultSpec("engine-raise", at=0)])
        single = InferenceEngine(linear_apply, make_params(),
                                 max_bucket=8, registry=Registry(),
                                 stall_gate=False)
        rng = np.random.default_rng(26)
        obs, mask = make_batch(rng, 4)
        router.warmup(obs[0], mask[0])
        single.warmup(obs[0], mask[0])
        a_r, b_r = router.decide(obs, mask)     # served via the hedge
        a_s, b_s = single.decide(obs, mask)
        assert b_r == b_s
        assert np.array_equal(np.asarray(a_r), np.asarray(a_s))


# ---- ISSUE 16: drain contract + exactly-once shed accounting ----------

class TestServerClosed:
    def test_close_refuses_submit_and_start_forever(self):
        server, t, reg = fake_server()
        rng = np.random.default_rng(30)
        fut = server.submit(*row(rng))
        server.close()
        assert isinstance(fut.result(timeout=10), ServeResult), \
            "close() must flush already-accepted work"
        assert server.closed
        with pytest.raises(ServerClosedError, match="closed"):
            server.submit(*row(rng))
        with pytest.raises(ServerClosedError):
            server.start()
        server.close()                          # idempotent

    def test_stop_is_not_terminal_close_is(self):
        server, t, reg = fake_server()
        rng = np.random.default_rng(31)
        server.start()
        server.stop()
        fut = server.submit(*row(rng))          # back in inline mode
        assert server.pump() == 1
        assert isinstance(fut.result(timeout=10), ServeResult)
        server.close()
        with pytest.raises(ServerClosedError):
            server.submit(*row(rng))

    def test_submit_refused_while_drain_in_flight(self):
        server, t, reg = fake_server()
        rng = np.random.default_rng(32)
        with server._wake:                      # freeze mid-drain state
            server._stopped = True
        with pytest.raises(ServerClosedError, match="drain in flight"):
            server.submit(*row(rng))
        with server._wake:
            server._stopped = False
        server.submit(*row(rng))
        assert server.pump() == 1

    def test_close_resolves_queued_futures_even_on_engine_failure(self):
        class DeadEngine:
            max_bucket = 8

            def bucket_for(self, n):
                return next_bucket(n, 8)

            def decide(self, obs, mask, stall=None):
                raise RuntimeError("device lost")

        reg = Registry()
        server = PolicyServer(DeadEngine(), registry=reg)
        rng = np.random.default_rng(33)
        futs = [server.submit(*row(rng)) for _ in range(3)]
        server.close()                          # must not hang or strand
        for f in futs:
            with pytest.raises(RuntimeError, match="device lost"):
                f.result(timeout=10)
        assert reg.counter("serve_dispatch_errors_total").value == 1


class TestShedAccounting:
    def test_cancelled_future_is_not_counted_as_shed(self):
        """The exactly-once invariant: a client that walked away
        (Future.cancel) is not double-counted by the expiry scan —
        ``serve_shed_total`` counts only rejections someone can SEE."""
        server, t, reg = fake_server()
        rng = np.random.default_rng(34)
        fut = server.submit(*row(rng), deadline_s=0.5)
        assert fut.cancel()
        t[0] += 1.0
        assert server.pump() == 0               # expiry scan drops it
        assert reg.counter("serve_shed_total").value == 0

    def test_multi_dispatcher_shed_counted_exactly_once(self):
        """4 dispatcher threads race the same expiry scans and admission
        path under real time; conservation must hold exactly:
        submitted == served + shed, and the counter == typed
        rejections observed (no double-count, no silent drop)."""
        import time as _time

        class SleepyEngine:
            max_bucket = 1

            def bucket_for(self, n):
                return next_bucket(n, 1)

            def decide(self, obs, mask, stall=None):
                _time.sleep(0.002)
                return np.asarray(obs), 1

        reg = Registry()
        server = PolicyServer(SleepyEngine(), registry=reg)
        rng = np.random.default_rng(35)
        o, m = row(rng)
        server.start(dispatchers=4)
        try:
            futs = [server.submit(o, m, deadline_s=0.004)
                    for _ in range(120)]
        finally:
            server.stop()                       # drains before stopping
        served = shed = 0
        for f in futs:
            try:
                assert isinstance(f.result(timeout=30), ServeResult)
                served += 1
            except DeadlineSheddedError:
                shed += 1
        assert served + shed == len(futs) == 120
        assert reg.counter("serve_shed_total").value == shed
        assert reg.counter("serve_requests_total").value == 120
        assert shed > 0, "the race was never exercised"


class TestDispatcherSurvival:
    def test_dispatcher_outlives_a_failed_dispatch(self):
        """A pump exception resolves ITS batch exceptionally and the
        dispatcher keeps serving — a dead dispatcher would strand every
        later request as a hung future."""
        class FlakyEngine:
            max_bucket = 1

            def __init__(self):
                self.fails_left = 1

            def bucket_for(self, n):
                return next_bucket(n, 1)

            def decide(self, obs, mask, stall=None):
                if self.fails_left:
                    self.fails_left -= 1
                    raise RuntimeError("transient XLA error")
                return np.asarray(obs), 1

        reg = Registry()
        server = PolicyServer(FlakyEngine(), registry=reg)
        rng = np.random.default_rng(36)
        server.start()
        try:
            f1 = server.submit(*row(rng))
            with pytest.raises(RuntimeError, match="transient"):
                f1.result(timeout=30)
            f2 = server.submit(*row(rng))       # same dispatcher thread
            assert isinstance(f2.result(timeout=30), ServeResult)
        finally:
            server.stop()
        assert reg.counter("serve_dispatch_errors_total").value == 1
