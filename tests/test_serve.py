"""Serving subsystem tests (ISSUE 7): bucket coalescing properties,
compile-once-per-bucket sentinel gates, fleet-vs-sequential bit parity,
the eval↔serve shared-decision refactor guard, the scrape endpoint, and
the serve CLI."""
import dataclasses
import json
import urllib.request

import jax
import numpy as np
import pytest

from rlgpuschedule_tpu import decision
from rlgpuschedule_tpu import eval as eval_lib
from rlgpuschedule_tpu.algos import PPOConfig
from rlgpuschedule_tpu.analysis.sentinels import (CompileCounter,
                                                  RecompileSentinelError,
                                                  assert_no_recompiles)
from rlgpuschedule_tpu.configs import CONFIGS, repro_tuple
from rlgpuschedule_tpu.env import env as env_lib
from rlgpuschedule_tpu.eval import EvalResult, pooled_avg_jct
from rlgpuschedule_tpu.experiment import Experiment, make_env_windows
from rlgpuschedule_tpu.obs import Registry, serve_http
from rlgpuschedule_tpu.serve import (InferenceEngine, PolicyServer,
                                     fleet_replay, fleet_windows,
                                     next_bucket, pad_batch,
                                     sample_fleet_faults, scatter_results,
                                     stack_requests)
from rlgpuschedule_tpu.serve import __main__ as serve_cli
from rlgpuschedule_tpu.serve.bench import (build_request_pool,
                                           default_request_sizes,
                                           run_bench)


def small_cfg(**kw):
    return dataclasses.replace(
        CONFIGS["ppo-mlp-synth64"], n_envs=2, window_jobs=12, horizon=96,
        n_nodes=4, gpus_per_node=4, queue_len=4,
        ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2), **kw)


@pytest.fixture(scope="module")
def exp():
    return Experiment.build(small_cfg())


@pytest.fixture(scope="module")
def exp_pre():
    """Preemptive action space — exercises the served stall gate."""
    return Experiment.build(
        dataclasses.replace(small_cfg(), name="pre", preempt_len=2))


def host_requests(exp, n=None):
    """First reset's per-env (obs, mask) request rows as host arrays."""
    _state, ts = env_lib.vec_reset(exp.env_params, exp.traces)
    obs = np.asarray(jax.device_get(ts.obs))
    mask = np.asarray(jax.device_get(ts.action_mask))
    n = obs.shape[0] if n is None else n
    return obs[:n], mask[:n]


class TestBucketing:
    def test_next_bucket_rounds_to_power_of_two(self):
        assert [next_bucket(n, 16) for n in (1, 2, 3, 5, 8, 9, 16)] == \
            [1, 2, 4, 8, 8, 16, 16]

    def test_next_bucket_refuses_bad_inputs(self):
        with pytest.raises(ValueError):
            next_bucket(0, 16)
        with pytest.raises(ValueError):
            next_bucket(17, 16)
        with pytest.raises(ValueError):
            next_bucket(3, 12)      # max_bucket not a power of two

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_pad_scatter_roundtrips_request_order(self, seed):
        """Property (satellite): for random request batches, stacking +
        padding + scattering returns every request's own row, in FIFO
        order, regardless of bucket slack."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 16))
        bucket = next_bucket(n, 16)
        rows = [(rng.standard_normal(7).astype(np.float32),
                 rng.integers(0, 2, 9).astype(bool))
                for _ in range(n)]
        obs = stack_requests([r[0] for r in rows])
        mask = stack_requests([r[1] for r in rows])
        obs_p = pad_batch(obs, bucket)
        mask_p = pad_batch(mask, bucket, fill_mask_true=True)
        assert obs_p.shape[0] == mask_p.shape[0] == bucket
        # padded mask rows are all-legal (finite-logits contract)
        assert mask_p[n:].all()
        assert (obs_p[n:] == 0).all()
        # identity "dispatch": scatter returns each request's own row
        back = scatter_results(obs_p, n)
        for i in range(n):
            np.testing.assert_array_equal(back[i], rows[i][0])

    def test_pad_batch_refuses_overfull(self):
        with pytest.raises(ValueError):
            pad_batch(np.zeros((5, 2)), 4)

    def test_pad_fill_constants_hoisted_and_dtype_stable(self):
        """ISSUE 17 satellite: the pad-fill constant is built once per
        (rows, tail, dtype, mask) key, shared immutably across batches,
        and padding can never promote a leaf's dtype."""
        from rlgpuschedule_tpu.serve.batching import _pad_fill
        for dtype in (np.float32, np.float64, np.int32, np.bool_):
            x = np.ones((3, 2), dtype)
            out = pad_batch(x, 8)
            assert out.dtype == x.dtype, dtype       # never promotes
            assert out.shape == (8, 2)
        f1 = _pad_fill(5, (2,), np.dtype(np.float32), False)
        f2 = _pad_fill(5, (2,), np.dtype(np.float32), False)
        assert f1 is f2                              # hoisted, not rebuilt
        with pytest.raises((ValueError, RuntimeError)):
            f1[0] = 1.0                              # shared => immutable
        # bool + fill_mask_true pads all-legal; bool otherwise pads False
        m = pad_batch(np.zeros((2, 3), bool), 4, fill_mask_true=True)
        assert m[2:].all() and m.dtype == np.bool_
        z = pad_batch(np.ones((2, 3), bool), 4)
        assert not z[2:].any()
        # fill_mask_true on a float leaf still pads ZEROS (the flag only
        # flips boolean mask leaves)
        f = pad_batch(np.ones((2, 3), np.float32), 4, fill_mask_true=True)
        assert (f[2:] == 0).all() and f.dtype == np.float32

    def test_default_request_sizes_share_one_bucket(self):
        for bucket in (8, 16, 64):
            sizes = default_request_sizes(bucket)
            assert len(set(sizes)) == 3
            assert {next_bucket(s, bucket) for s in sizes} == {bucket}
        with pytest.raises(ValueError):
            default_request_sizes(4)


class TestSharedDecision:
    """Satellite 1 guard: the extracted decision helpers are bit-identical
    to the pre-refactor inline logic of eval.replay."""

    def test_policy_decision_is_inline_masked_argmax(self, exp):
        obs, mask = host_requests(exp)
        got = decision.policy_decision(
            exp.apply_fn, exp.train_state.params, obs, mask)
        logits, _ = exp.apply_fn(exp.train_state.params, obs, mask)
        want = jax.tree.map(lambda lg: np.argmax(np.asarray(lg), -1),
                            logits)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_gate_stalled_matches_pre_refactor_formulas(self, exp_pre):
        pre = decision.preempt_slice(exp_pre.env_params)
        thresh = decision.stall_threshold(exp_pre.env_params)
        assert pre is not None and int(np.asarray(pre).sum()) == 2
        rng = np.random.default_rng(0)
        A = exp_pre.env_params.n_actions
        mask_b = rng.integers(0, 2, (3, A)).astype(bool)
        stall_b = np.asarray([0, thresh, thresh + 3], np.int32)
        # the exact expressions replay()/full_trace_replay() inlined
        want_b = mask_b & ~((stall_b >= thresh)[:, None]
                            & np.asarray(pre)[None, :])
        got_b = decision.gate_stalled(mask_b, stall_b, thresh, pre)
        np.testing.assert_array_equal(np.asarray(got_b), want_b)
        mask_1 = mask_b[0]
        for s in (0, thresh):
            want_1 = mask_1 & ~((np.int32(s) >= thresh) & np.asarray(pre))
            got_1 = decision.gate_stalled(mask_1, np.int32(s), thresh, pre)
            np.testing.assert_array_equal(np.asarray(got_1), want_1)

    def test_eval_replay_still_deterministic_after_refactor(self, exp):
        r1 = eval_lib.replay(exp.apply_fn, exp.train_state.params,
                             exp.env_params, exp.traces)
        r2 = eval_lib.replay(exp.apply_fn, exp.train_state.params,
                             exp.env_params, exp.traces)
        np.testing.assert_array_equal(np.asarray(r1.avg_jct),
                                      np.asarray(r2.avg_jct))
        assert (np.asarray(r1.n_done) == np.asarray(r1.n_valid)).all()


class TestInferenceEngine:
    def test_served_actions_match_eval_decision(self, exp):
        """serve↔eval no-drift: the engine's dispatched action for an
        observation is bit-identical to what eval's decision rule
        produces for the same observation."""
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        actions, bucket = engine.decide(obs, mask)
        assert bucket == 2
        want = decision.policy_decision(
            exp.apply_fn, exp.train_state.params, obs, mask)
        np.testing.assert_array_equal(np.asarray(actions),
                                      np.asarray(want))

    def test_batch_composition_invariance(self, exp):
        """A request's action does not depend on who it was batched
        with (padding rows included)."""
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        together, _ = engine.decide(obs, mask)
        for i in range(obs.shape[0]):
            alone, _ = engine.decide(obs[i:i + 1], mask[i:i + 1])
            np.testing.assert_array_equal(np.asarray(alone)[0],
                                          np.asarray(together)[i])

    def test_stall_gate_masks_preempts_when_served(self, exp_pre):
        obs, mask = host_requests(exp_pre)
        mask = np.ones_like(mask)       # every action legal
        engine = InferenceEngine(exp_pre.apply_fn,
                                 exp_pre.train_state.params,
                                 exp_pre.env_params, max_bucket=8)
        thresh = decision.stall_threshold(exp_pre.env_params)
        pre = np.asarray(decision.preempt_slice(exp_pre.env_params))
        stalled = np.full(obs.shape[0], thresh, np.int32)
        actions, _ = engine.decide(obs, mask, stalled)
        assert not pre[np.asarray(actions)].any(), \
            "stalled requests must never be served a preempt action"
        # control: the same requests un-stalled see the ungated mask
        calm, _ = engine.decide(obs, mask, np.zeros_like(stalled))
        want = decision.policy_decision(
            exp_pre.apply_fn, exp_pre.train_state.params, obs, mask)
        np.testing.assert_array_equal(np.asarray(calm), np.asarray(want))

    def test_compile_once_per_bucket(self, exp):
        """The sentinel gate (satellite): two+ loads of the same bucket
        size must not retrace — across DIFFERENT request counts."""
        obs, mask = host_requests(exp)
        pool_obs = np.concatenate([obs] * 4)     # 8 rows to draw from
        pool_mask = np.concatenate([mask] * 4)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        engine.warmup(obs[0], mask[0], buckets=(8,))
        with assert_no_recompiles("warmed serve bucket"):
            for n in (5, 7, 8, 6, 5):
                engine.decide(pool_obs[:n], pool_mask[:n])
        assert engine.post_warmup_recompiles == 0

    def test_new_bucket_compiles_and_is_blessed(self, exp):
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        engine.warmup(obs[0], mask[0], buckets=(2,))
        with CompileCounter() as c:
            engine.decide(np.concatenate([obs] * 2),
                          np.concatenate([mask] * 2))  # bucket 4: first use
        assert c.total > 0
        assert engine.post_warmup_recompiles == 0      # blessed warmup
        assert set(engine.warmed_buckets) == {2, 4}

    def test_recompile_on_warmed_bucket_raises_when_strict(self, exp):
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8,
                                 strict=True)
        # claim bucket 4 is warm without ever compiling it: the next
        # dispatch at 4 MUST trace -> the alarm path fires
        engine._warmed.add(4)
        with pytest.raises(RecompileSentinelError):
            engine.decide(np.concatenate([obs] * 2),
                          np.concatenate([mask] * 2))
        assert engine.post_warmup_recompiles == 1

    def test_warmup_all_buckets_covers_every_size(self, exp):
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=4)
        done = engine.warmup(obs[0], mask[0])
        assert done == (1, 2, 4)
        with assert_no_recompiles("fully warmed engine"):
            for n in (1, 2):
                engine.decide(obs[:n], mask[:n])


class TestPolicyServer:
    def test_submit_pump_scatters_in_fifo_order(self, exp):
        obs, mask = host_requests(exp)
        registry = Registry()
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8,
                                 registry=registry)
        server = PolicyServer(engine, registry=registry)
        futs = [server.submit(obs[i % obs.shape[0]],
                              mask[i % mask.shape[0]]) for i in range(5)]
        assert server.pump() == 5
        want, _ = engine.decide(
            np.stack([obs[i % obs.shape[0]] for i in range(5)]),
            np.stack([mask[i % mask.shape[0]] for i in range(5)]))
        for i, f in enumerate(futs):
            res = f.result(timeout=10)
            np.testing.assert_array_equal(np.asarray(res.action),
                                          np.asarray(want)[i])
            assert res.latency_s > 0
        assert server.pump() == 0           # queue drained
        snap = server.slo_snapshot()
        assert snap["requests"] == 5 and snap["dispatches"] == 1
        assert snap["latency_p50_ms"] > 0
        assert snap["batch_occupancy_mean"] == pytest.approx(5 / 8)
        rendered = registry.render()
        assert "serve_requests_total 5" in rendered
        assert "serve_decision_latency_p99_ms" in rendered

    def test_pump_max_wait_dispatches_partial_after_deadline(self, exp):
        import time
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        engine.warmup(obs[0], mask[0], buckets=(2, 8))
        server = PolicyServer(engine)
        futs = [server.submit(obs[i], mask[i]) for i in range(2)]
        t0 = time.perf_counter()
        assert server.pump(max_wait_s=0.2) == 2   # partial bucket, held
        waited = time.perf_counter() - t0
        assert waited >= 0.15                      # sat out the deadline
        assert all(f.result(timeout=10) for f in futs)
        # a FULL bucket never waits on the deadline
        futs = [server.submit(obs[i % obs.shape[0]],
                              mask[i % mask.shape[0]]) for i in range(8)]
        t0 = time.perf_counter()
        assert server.pump(max_wait_s=30.0) == 8
        assert time.perf_counter() - t0 < 5.0
        assert all(f.result(timeout=10) for f in futs)

    def test_pump_max_wait_cut_short_when_bucket_fills(self, exp):
        import threading
        import time
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=2)
        engine.warmup(obs[0], mask[0], buckets=(1, 2))
        server = PolicyServer(engine)
        server.submit(obs[0], mask[0])
        late = threading.Timer(0.1, server.submit, (obs[1], mask[1]))
        late.start()
        try:
            t0 = time.perf_counter()
            assert server.pump(max_wait_s=60.0) == 2   # filled mid-wait
            assert time.perf_counter() - t0 < 30.0
        finally:
            late.cancel()
        assert server.pump() == 0

    def test_max_wait_ctor_knob_validates_and_reaches_pump(self, exp):
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        with pytest.raises(ValueError, match="max_wait_s"):
            PolicyServer(engine, max_wait_s=-1.0)
        server = PolicyServer(engine, max_wait_s=0.0)   # explicit no-wait
        server.submit(obs[0], mask[0])
        assert server.pump() == 1                       # ctor default used

    def test_background_dispatcher_serves_and_stops(self, exp):
        obs, mask = host_requests(exp)
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8)
        engine.warmup(obs[0], mask[0], buckets=(1, 2, 4, 8))
        server = PolicyServer(engine)
        server.start()
        try:
            futs = [server.submit(obs[i % 2], mask[i % 2])
                    for i in range(12)]
            results = [f.result(timeout=30) for f in futs]
            assert len(results) == 12
        finally:
            server.stop()
        # a stopped server is back in inline mode — submit+pump works
        fut = server.submit(obs[0], mask[0])
        assert server.pump() == 1
        assert fut.result(timeout=10) is not None


class ArgmaxEngine:
    """Deterministic host-only engine: per-row argmax over obs. Returns
    a FRESH array per dispatch (so plane-parity is a real comparison,
    not view aliasing)."""

    def __init__(self, max_bucket=8):
        self.max_bucket = max_bucket
        self.post_warmup_recompiles = 0

    def bucket_for(self, n):
        return next_bucket(n, self.max_bucket)

    def decide(self, obs, mask, stall=None):
        a = np.argmax(np.asarray(obs), axis=-1).astype(np.int32)
        return a, self.bucket_for(a.shape[0])


class RewarmEngine(ArgmaxEngine):
    """ArgmaxEngine exposing the router's re-warm listener hook."""

    def __init__(self, max_bucket=8):
        super().__init__(max_bucket)
        self.listeners = []

    def add_rewarm_listener(self, cb):
        self.listeners.append(cb)


def request_rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(6).astype(np.float32),
             rng.integers(0, 2, 9).astype(bool) | True)
            for _ in range(n)]


class TestArenaDataPlane:
    """ISSUE 17 tentpole: the preallocated batch arena — zero
    steady-state ndarray construction, plane parity, zero-copy scatter
    views, shape policing at the door, and the estimator re-warm
    reset."""

    @pytest.mark.parametrize("stream", [
        # fills its buckets exactly: no tail to neutralise
        dict(n=16, bucket=8),
        # leaves a partial bucket, in a slab that still holds the first
        # round's rows: 5 live rows, 3 neutral ones
        dict(n=21, bucket=8, arena_blocks=2, neutral=3),
        # outgrows the ring's first slabs: 64 rows through 3 slabs of 4,
        # each recycled over stale rows, producers held back meanwhile
        dict(n=64, bucket=4, arena_blocks=2, dispatchers=1, ring_rows=12),
        # two producers against two dispatchers
        dict(n=80, bucket=8, producers=2, dispatchers=2),
        # float64 rows are cast to the arena's float32 at the door
        dict(n=11, bucket=8, dtype=np.float64, neutral=1),
        # deadline-shed rows in the middle become padding, the live rows
        # close up over them: 8 claimed - 2 shed -> 6 (+2 neutral), then
        # 4 claimed - 1 shed -> 3 (+1 neutral)
        dict(n=12, bucket=8, shed=(3, 4, 9), neutral=3),
    ], ids=["exact", "partial", "outgrow", "threads", "float64", "shed"])
    def test_served_actions_match_row_wise_reference(self, stream):
        """What the arena serves is what ``engine.decide`` returns for
        each request ALONE, and no row the engine is handed is anything
        but a submitted row or a neutral one (zero obs, every action
        legal): coalescing, sealing, compaction and slab reuse change
        nothing and leak nothing."""
        import threading
        from rlgpuschedule_tpu.serve.batching import DeadlineSheddedError
        n, bucket = stream["n"], stream["bucket"]
        shed = set(stream.get("shed", ()))
        rng = np.random.default_rng(n)
        rows = []
        for _ in range(n):
            mask = rng.integers(0, 2, 9).astype(bool)
            mask[0] = True
            rows.append((rng.standard_normal(6).astype(
                stream.get("dtype", np.float32)), mask))
        alone = ArgmaxEngine(1)
        want = [alone.decide(o.astype(np.float32)[None], m[None])[0][0]
                for o, m in rows]

        seen = []                       # copies of every dispatched batch

        class RecordingEngine(ArgmaxEngine):
            def decide(self, obs, mask, stall=None):
                seen.append((np.array(obs), np.array(mask)))  # GIL-atomic
                return super().decide(obs, mask, stall)

        t = [0.0]
        server = PolicyServer(
            RecordingEngine(bucket), clock=lambda: t[0],
            example_obs=rows[0][0].astype(np.float32),
            example_mask=rows[0][1],
            arena_blocks=stream.get("arena_blocks"))
        futs = [None] * n

        def produce(idx):
            for i in idx:
                futs[i] = server.submit(
                    *rows[i], deadline_s=0.01 if i in shed else None)

        if "dispatchers" in stream:
            server.start(dispatchers=stream["dispatchers"])
            k = stream.get("producers", 1)
            threads = [threading.Thread(target=produce,
                                        args=(range(j, n, k),))
                       for j in range(k)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
        else:
            for i in range(n):
                produce([i])
                if not shed and (i + 1) % bucket == 0:
                    assert server.pump() == bucket
            t[0] = 1.0                  # every deadline has run out
            while server.pump():
                pass
        for i, f in enumerate(futs):
            if i in shed:
                with pytest.raises(DeadlineSheddedError) as ei:
                    f.result(timeout=30)
                assert ei.value.reason == "expired"
            else:
                assert f.result(timeout=30).action == want[i]
        if "ring_rows" in stream:       # the stream is longer than the ring
            assert server.arena_stats()["rows"] == stream["ring_rows"] < n
        server.close()

        # every live row reached the engine exactly once (a stale copy
        # in a reused slab would be a second time), and nothing else did
        # but neutral rows
        unseen = {o.astype(np.float32).tobytes() + m.tobytes()
                  for i, (o, m) in enumerate(rows) if i not in shed}
        assert len(unseen) == n - len(shed)
        neutral = 0
        for obs, mask in seen:
            assert obs.dtype == np.float32 and mask.dtype == np.bool_
            for o, m in zip(obs, mask):
                key = o.tobytes() + m.tobytes()
                if key in unseen:
                    unseen.remove(key)
                else:
                    assert not o.any() and m.all()
                    neutral += 1
        assert not unseen
        if "neutral" in stream:
            assert neutral == stream["neutral"]

    @pytest.mark.parametrize("bucket", [1, 8, 64])
    def test_zero_steady_state_allocations(self, bucket):
        """THE perf contract: after warmup, a full-bucket round calls
        none of the numpy batch constructors and allocates no new
        slabs, at the smallest bucket, a middling one and the largest
        the CLI's default ring is sized for."""
        from rlgpuschedule_tpu.serve.bench import StubEngine, _AllocCounter
        rows = request_rows(16)
        server = PolicyServer(StubEngine(bucket), registry=Registry(),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])

        def one_round():
            for i in range(bucket):
                server.submit(*rows[i % len(rows)])
            return server.pump()

        for _ in range(4):                          # warmup: ring growth
            one_round()
        slabs_before = server.arena_stats()["slab_allocs"]
        served = 0
        with _AllocCounter() as counter:
            for _ in range(32):
                served += one_round()
        assert served == 32 * bucket                # conservation
        assert counter.calls == 0
        assert server.arena_stats()["slab_allocs"] == slabs_before
        server.close()
        with _AllocCounter() as probe:              # the counter sees through
            stack_requests([rows[0][0], rows[1][0]])
        assert probe.calls > 0

    def test_scatter_returns_views_into_actions_buffer(self):
        """Zero-copy tail: when the engine's actions don't alias the
        request slabs (the device-fetch shape) and rows are non-scalar,
        scatter hands back VIEWS of the actions buffer, not per-row
        copies. (Scalar-per-request actions degenerate to numpy scalars
        — there is no 0-d view to take.)"""
        class VecActionEngine(ArgmaxEngine):
            def __init__(self, max_bucket=8):
                super().__init__(max_bucket)
                self.buf = np.zeros((max_bucket, 2), np.int32)

            def decide(self, obs, mask, stall=None):
                n = np.asarray(obs).shape[0]
                return self.buf[:n], self.bucket_for(n)

        rows = request_rows(8)
        engine = VecActionEngine(8)
        server = PolicyServer(engine,
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        futs = [server.submit(o, m) for o, m in rows]
        assert server.pump() == 8
        for f in futs:
            action = np.asarray(f.result(timeout=10).action)
            assert action.shape == (2,)
            assert np.may_share_memory(action, engine.buf)
        server.close()

    def test_submit_rejects_wrong_row_shape_at_the_door(self):
        rows = request_rows(2)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        with pytest.raises(ValueError):
            server.submit(np.zeros(7, np.float32), rows[0][1])
        with pytest.raises(ValueError):
            server.submit(rows[0][0], np.ones(4, bool))
        # the arena survives the rejections: a good row still serves
        fut = server.submit(*rows[1])
        assert server.pump() == 1
        assert fut.result(timeout=10) is not None
        server.close()

    def test_arena_stats_surface(self):
        rows = request_rows(1)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        stats = server.arena_stats()
        assert set(stats) == {"blocks", "rows", "slab_allocs"}
        assert stats["blocks"] >= 1
        assert stats["rows"] == stats["blocks"] * 8
        # one counted allocation per slab array: obs leaves + mask
        # leaves + the stall vector + the req-id lane, per block
        assert stats["slab_allocs"] == stats["blocks"] * 4
        server.close()
        # without examples the ring is sized by the first request
        lazy = PolicyServer(ArgmaxEngine(8))
        assert lazy.arena_stats() == {"blocks": 0, "rows": 0,
                                      "slab_allocs": 0}
        lazy.submit(*rows[0])
        assert lazy.arena_stats()["blocks"] >= 1
        lazy.close()

    def test_rewarm_listener_resets_service_time_estimator(self):
        """ISSUE 17 satellite: a fleet re-warm (weight swap /
        set_active) resets the learned service time — admission returns
        to cold-admit instead of shedding on the stale estimate."""
        rows = request_rows(8)
        engine = RewarmEngine(8)
        server = PolicyServer(engine,
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        assert len(engine.listeners) == 1            # hook registered
        for o, m in rows:
            server.submit(o, m)
        assert server.pump() == 8
        assert server.service_time_s() is not None   # learned
        engine.listeners[0]()                        # fleet re-warmed
        assert server.service_time_s() is None       # forgotten
        server.close()


class TestRequestCausality:
    """ISSUE 20 tentpole: the 64-bit request id threads submit ->
    arena slot -> dispatch -> scatter -> result, and every submitted id
    resolves exactly once as served, shed, or failed."""

    def test_minted_ids_unique_salted_and_on_results(self):
        rows = request_rows(8)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        futs = [server.submit(o, m) for o, m in rows]
        assert server.pump() == 8
        ids = [f.result(timeout=10).req_id for f in futs]
        assert len(set(ids)) == 8
        salts = {i >> 40 for i in ids}
        assert len(salts) == 1                   # same rank+pid salt
        assert all(0 < i < (1 << 63) for i in ids)   # int64-safe
        server.close()

    def test_explicit_id_round_trips(self):
        rows = request_rows(1)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        fut = server.submit(*rows[0], req_id=0x123456789ABCDEF)
        server.pump()
        assert fut.result(timeout=10).req_id == 0x123456789ABCDEF
        server.close()

    def test_conservation_every_id_resolves_exactly_once(self, tmp_path):
        """The property the ci.sh chaos gate asserts at scale: over a
        run with served, failed, and in-queue-expired requests, the
        merged instant stream resolves every enqueued id exactly once
        as served | shed | dispatch_failed."""
        from rlgpuschedule_tpu.obs import EventBus, Tracer
        from rlgpuschedule_tpu.obs.events import merge_dir

        class FlakyEngine(ArgmaxEngine):
            def __init__(self, max_bucket=8):
                super().__init__(max_bucket)
                self.dispatches = 0

            def decide(self, obs, mask, stall=None):
                self.dispatches += 1
                if self.dispatches == 2:
                    raise RuntimeError("injected fault")
                return super().decide(obs, mask, stall)

        bus = EventBus(str(tmp_path), rank=0, name="serve")
        server = PolicyServer(FlakyEngine(8),
                              example_obs=request_rows(1)[0][0],
                              example_mask=request_rows(1)[0][1],
                              tracer=Tracer(bus, enabled=True))
        rows = request_rows(24)
        futs = [server.submit(o, m) for o, m in rows[:8]]
        assert server.pump() == 8                    # dispatch 1: served
        futs += [server.submit(o, m) for o, m in rows[8:16]]
        with pytest.raises(RuntimeError):
            server.pump()                            # dispatch 2: fails
        for f in futs[8:16]:
            with pytest.raises(RuntimeError):
                f.result(timeout=10)
        # round 3: half shed at admission (deadline below any predicted
        # wait), half admitted but left to expire in the queue
        futs += [server.submit(o, m, deadline_s=1e-9)
                 for o, m in rows[16:20]]
        futs += [server.submit(o, m, deadline_s=0.01)
                 for o, m in rows[20:24]]
        import time as _time
        _time.sleep(0.05)
        server.pump()                                # expire the admitted ones
        from rlgpuschedule_tpu.serve.batching import DeadlineSheddedError
        for f in futs[16:]:
            with pytest.raises(DeadlineSheddedError):
                f.result(timeout=10)
        server.close()
        bus.close()

        pts = [e for e in merge_dir(str(tmp_path))
               if e.get("kind") == "span_point"]
        enq = [e["attrs"]["req_id"] for e in pts
               if e.get("span") == "enqueue"]
        served = [r for e in pts if e.get("span") == "served"
                  for r in e["attrs"]["req_ids"]]
        shed = [(e["attrs"]["req_id"], e["attrs"]["reason"])
                for e in pts if e.get("span") == "shed"]
        failed = [r for e in pts if e.get("span") == "dispatch_failed"
                  for r in e["attrs"]["req_ids"]]
        # the ci.sh gate's ledger: submitted = enqueued + admission-shed
        # (admission sheds never reach the queue so never emit enqueue);
        # resolved = served + shed (any reason) + dispatch_failed
        submitted = enq + [r for r, why in shed if why == "admission"]
        resolved = served + failed + [r for r, _ in shed]
        assert len(submitted) == len(set(submitted)) == 24
        assert sorted(resolved) == sorted(submitted)  # exactly once each
        assert (len(served), len(failed), len(shed)) == (8, 8, 8)
        reasons = {why for _, why in shed}
        assert reasons == {"admission", "expired"}    # both shed paths hit

    def test_shed_exception_and_instant_carry_req_id(self, tmp_path):
        from rlgpuschedule_tpu.obs import EventBus, Tracer
        from rlgpuschedule_tpu.obs.events import merge_dir
        from rlgpuschedule_tpu.serve.batching import DeadlineSheddedError
        bus = EventBus(str(tmp_path), rank=0, name="serve")
        rows = request_rows(2)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1],
                              tracer=Tracer(bus, enabled=True))
        fut = server.submit(*rows[0], deadline_s=1e-6, req_id=777)
        import time as _time
        _time.sleep(0.005)
        server.pump()
        with pytest.raises(DeadlineSheddedError) as ei:
            fut.result(timeout=10)
        assert ei.value.req_id == 777
        server.close()
        bus.close()
        sheds = [e for e in merge_dir(str(tmp_path))
                 if e.get("kind") == "span_point"
                 and e.get("span") == "shed"]
        assert [e["attrs"]["req_id"] for e in sheds] == [777]

    def test_p99_exemplar_rides_snapshot(self):
        rows = request_rows(16)
        server = PolicyServer(ArgmaxEngine(8),
                              example_obs=rows[0][0],
                              example_mask=rows[0][1])
        futs = [server.submit(o, m) for o, m in rows]
        while server.pump():
            pass
        ids = {f.result(timeout=10).req_id for f in futs}
        snap = server.slo_snapshot()
        assert snap["latency_p99_exemplar_req_id"] in ids
        assert "slo" in snap                     # engine status attached
        server.close()


class TestBench:
    def test_run_bench_zero_recompiles_across_sizes(self, exp):
        registry = Registry()
        engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                                 exp.env_params, max_bucket=8,
                                 registry=registry)
        server = PolicyServer(engine, registry=registry)
        pool = build_request_pool(exp.apply_fn, exp.train_state.params,
                                  exp.env_params, exp.traces, steps=2)
        assert len(pool) == 3 * exp.cfg.n_envs
        report = run_bench(engine, server, pool, rounds=6,
                           request_sizes=(5, 7, 8))
        assert report["post_warmup_recompiles"] == 0
        assert report["buckets"] == [8]
        assert report["requests"] == 2 * (5 + 7 + 8)
        # same pool, same stream -> the same served actions
        again = run_bench(engine, server, pool, rounds=6,
                          request_sizes=(5, 7, 8))
        assert len(report["actions_crc32"]) == 8
        assert again["actions_crc32"] == report["actions_crc32"]
        assert report["decisions_per_s"] > 0
        assert report["latency_p50_ms"] > 0
        assert report["latency_p99_ms"] >= report["latency_p50_ms"]


class TestFleetReplay:
    def test_fleet_matches_sequential_replay_bit_for_bit(self, exp):
        """ISSUE 7 acceptance: fleet replay of N seeded clusters ==
        N sequential eval.replay runs, mean JCT/completion bit-for-bit
        on CPU."""
        fleet = fleet_replay(exp.apply_fn, exp.train_state.params,
                             exp.env_params, exp.traces)
        n = fleet["n_clusters"]
        assert n == exp.cfg.n_envs
        seq = []
        for i in range(n):
            ti = jax.tree.map(lambda x: x[i:i + 1], exp.traces)
            seq.append(eval_lib.replay(exp.apply_fn,
                                       exp.train_state.params,
                                       exp.env_params, ti))
        pooled = EvalResult(*[np.concatenate([np.asarray(getattr(r, f))
                                              for r in seq])
                              for f in EvalResult._fields])
        want_jct, want_completion = pooled_avg_jct(pooled)
        assert fleet["mean_jct"] == want_jct
        assert fleet["completion"] == want_completion
        np.testing.assert_array_equal(
            np.asarray(fleet["per_cluster"]["avg_jct"], np.float32),
            np.asarray(pooled.avg_jct, np.float32))

    def test_fleet_under_faults_matches_sequential(self, exp):
        windows, traces = fleet_windows(exp.cfg, 2, source=exp.source)
        faults = sample_fleet_faults(exp.cfg.n_nodes, "sporadic", 0, 2,
                                     windows)
        fleet = fleet_replay(exp.apply_fn, exp.train_state.params,
                             exp.env_params, traces, faults=faults,
                             max_steps=96)
        seq_jct = []
        for i in range(2):
            ti = jax.tree.map(lambda x: x[i:i + 1], traces)
            fi = jax.tree.map(lambda x: x[i:i + 1], faults)
            r = eval_lib.replay(exp.apply_fn, exp.train_state.params,
                                exp.env_params, ti, max_steps=96,
                                faults=fi)
            seq_jct.append(float(np.asarray(r.avg_jct)[0]))
        np.testing.assert_array_equal(
            np.asarray(fleet["per_cluster"]["avg_jct"], np.float32),
            np.asarray(seq_jct, np.float32))

    def test_fleet_windows_are_the_eval_tiling(self, exp):
        windows, traces = fleet_windows(exp.cfg, 3, source=exp.source)
        want = make_env_windows(dataclasses.replace(exp.cfg, n_envs=3),
                                exp.source)
        assert len(windows) == 3
        for w, v in zip(windows, want):
            np.testing.assert_array_equal(w.submit, v.submit)
            np.testing.assert_array_equal(w.gpus, v.gpus)

    def test_fleet_reports_throughput(self, exp):
        fleet = fleet_replay(exp.apply_fn, exp.train_state.params,
                             exp.env_params, exp.traces)
        assert fleet["decisions"] > 0
        assert fleet["decisions_per_s"] > 0
        assert fleet["wall_s"] > 0


class TestScrapeEndpoint:
    def test_scrape_serves_live_exposition(self):
        registry = Registry()
        registry.counter("serve_requests_total", "n").inc(3)
        with serve_http(registry, port=0) as srv:
            with urllib.request.urlopen(srv.url, timeout=10) as resp:
                body = resp.read().decode()
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
            assert body == registry.render()
            assert "serve_requests_total 3" in body
            # live: a scrape observes updates without restart
            registry.gauge("serve_queue_depth", "d").set(7)
            with urllib.request.urlopen(srv.url, timeout=10) as resp:
                assert "serve_queue_depth 7" in resp.read().decode()
            # root alias works, anything else 404s
            root = srv.url.rsplit("/", 1)[0] + "/"
            with urllib.request.urlopen(root, timeout=10) as resp:
                assert resp.status == 200
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope", timeout=10)

    def test_close_releases_the_port(self):
        registry = Registry()
        srv = serve_http(registry, port=0)
        port = srv.port
        srv.close()
        srv2 = serve_http(registry, port=port)   # re-bindable after close
        assert srv2.port == port
        srv2.close()


SERVE_FAST = ["--config", "ppo-mlp-synth64", "--n-envs", "2",
              "--n-nodes", "2", "--gpus-per-node", "4",
              "--window-jobs", "12", "--queue-len", "4",
              "--horizon", "64"]


class TestServeCLI:
    def test_bench_reports_slo_and_repro(self, capsys):
        report = serve_cli.main(
            SERVE_FAST + ["--bench", "--bucket", "8", "--rounds", "6",
                          "--max-steps", "64", "--pool-steps", "2"])
        b = report["bench"]
        assert b["post_warmup_recompiles"] == 0
        assert len(set(b["request_sizes"])) >= 3
        assert b["buckets"] == [8]
        assert b["decisions_per_s"] > 0
        assert b["latency_p50_ms"] > 0 and b["latency_p99_ms"] > 0
        # the same repro tuple evaluate emits (shared constructor)
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=12, queue_len=4, horizon=64)
        assert report["repro"] == repro_tuple(cfg)
        out = capsys.readouterr().out
        assert json.loads(out.strip().splitlines()[-1])["bench"][
            "post_warmup_recompiles"] == 0

    def test_fleet_mode_and_metrics_port(self):
        report = serve_cli.main(
            SERVE_FAST + ["--fleet", "2", "--max-steps", "96",
                          "--metrics-port", "0"])
        fl = report["fleet"]
        assert fl["n_clusters"] == 2
        assert fl["completion"] > 0
        assert np.isfinite(fl["mean_jct"])
        scrape = report["scrape"]
        assert scrape["well_formed"] and scrape["status"] == 200
        assert scrape["metric_lines"] > 0

    def test_bench_resolved_ckpt_step_in_repro(self, tmp_path):
        from rlgpuschedule_tpu.checkpoint import Checkpointer
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, n_nodes=2,
            gpus_per_node=4, window_jobs=12, queue_len=4, horizon=64,
            ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2))
        exp = Experiment.build(cfg)
        with Checkpointer(str(tmp_path / "ckpt")) as ckpt:
            exp.save_checkpoint(ckpt, step=3)
        report = serve_cli.main(
            SERVE_FAST + ["--bench", "--bucket", "8", "--rounds", "3",
                          "--pool-steps", "1",
                          "--ckpt-dir", str(tmp_path / "ckpt")])
        assert report["repro"]["ckpt_step"] == 3
        assert report["repro"]["ckpt_dir"] == str(tmp_path / "ckpt")

    def test_refusals(self):
        with pytest.raises(SystemExit):
            serve_cli.main(SERVE_FAST)                     # no mode
        with pytest.raises(SystemExit):
            serve_cli.main(SERVE_FAST + ["--bench", "--bucket", "6"])
        with pytest.raises(SystemExit):
            serve_cli.main(SERVE_FAST + ["--fleet", "0"])
        with pytest.raises(SystemExit):                    # silent no-op
            serve_cli.main(SERVE_FAST + ["--fleet-regime", "storm",
                                         "--bench"])
        with pytest.raises(SystemExit):
            serve_cli.main(SERVE_FAST + ["--request-sizes", "2,4",
                                         "--fleet", "1"])
        with pytest.raises(SystemExit):                    # > bucket
            serve_cli.main(SERVE_FAST + ["--bench", "--bucket", "8",
                                         "--request-sizes", "9"])
        with pytest.raises(SystemExit):
            serve_cli.main(SERVE_FAST + ["--fleet", "1",
                                         "--fleet-regime", "nope"])
