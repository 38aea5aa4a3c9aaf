"""Property tests: the jit/vmap JAX simulator must reproduce the oracle
exactly (SURVEY.md §7 step 2 — "property-test against a slow Python oracle
sim written first as executable spec"). Integer-valued traces keep float32
virtual time exact, so comparisons are bit-meaningful."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from rlgpuschedule_tpu.sim import oracle as O
from rlgpuschedule_tpu.sim import core as C
from rlgpuschedule_tpu.traces import JobRecord, to_array_trace


def int_trace(rng, n_jobs, max_gpus, max_jobs=None):
    """Random integer-valued trace (exact in float32)."""
    jobs = []
    t = 0
    for i in range(n_jobs):
        t += int(rng.integers(0, 30))
        jobs.append(JobRecord(i, float(t), float(rng.integers(1, 50)),
                              int(rng.integers(1, max_gpus + 1)),
                              int(rng.integers(0, 3))))
    return to_array_trace(jobs, max_jobs=max_jobs)


class TestPlacementEquivalence:
    @pytest.mark.parametrize("seed", range(5))
    def test_pack_matches_oracle(self, seed):
        rng = np.random.default_rng(seed)
        for _ in range(50):
            free = rng.integers(0, 9, size=6).astype(np.int32)
            demand = int(rng.integers(1, 20))
            want = O.pack_placement(free, demand)
            got, feasible = C.pack_placement(jnp.asarray(free), jnp.asarray(demand))
            if want is None:
                assert not bool(feasible)
            else:
                assert bool(feasible)
                np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("seed", range(5))
    def test_spread_matches_oracle(self, seed):
        rng = np.random.default_rng(seed)
        for _ in range(50):
            free = rng.integers(0, 9, size=6).astype(np.int32)
            demand = int(rng.integers(1, 20))
            want = O.spread_placement(free, demand)
            got, feasible = C.spread_placement(jnp.asarray(free),
                                               jnp.asarray(demand), 8)
            if want is None:
                assert not bool(feasible)
            else:
                assert bool(feasible)
                np.testing.assert_array_equal(np.asarray(got), want)


class TestQueueAndMask:
    def test_pending_queue_order_and_padding(self):
        trace = to_array_trace([JobRecord(i, float(i), 5.0, 1) for i in range(6)],
                               max_jobs=8)
        params = C.SimParams(n_nodes=1, gpus_per_node=2, max_jobs=8, queue_len=4)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        state = C.advance_to(state, tr, jnp.float32(3.0))  # jobs 0..3 pending
        q = np.asarray(C.pending_queue(params, state))
        np.testing.assert_array_equal(q, [0, 1, 2, 3])
        # place job 0 → queue shifts, tail pads with next pending
        state, ok = C.try_place(params, state, tr, jnp.int32(0), jnp.int32(0))
        assert bool(ok)
        q = np.asarray(C.pending_queue(params, state))
        np.testing.assert_array_equal(q, [1, 2, 3, -1])

    def test_action_mask(self):
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 2), JobRecord(1, 0.0, 5.0, 4)],
                               max_jobs=4)
        params = C.SimParams(n_nodes=1, gpus_per_node=4, max_jobs=4,
                             queue_len=3, n_placements=2)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        mask = np.asarray(C.action_mask(params, state, tr))
        # both jobs feasible on empty cluster; slot 2 empty; noop valid
        np.testing.assert_array_equal(mask, [1, 1, 1, 1, 0, 0, 1])
        state, ok = C.try_place(params, state, tr, jnp.int32(0), jnp.int32(0))
        mask = np.asarray(C.action_mask(params, state, tr))
        # 2 free left: job 1 (4 gpus) infeasible now
        np.testing.assert_array_equal(mask, [0, 0, 0, 0, 0, 0, 1])


def scatter_pending_queue(params, state):
    """The scatter form ``pending_queue`` had up to PR 33, kept here as the
    reference the dense selection is held to, element for element."""
    K = params.queue_len
    pending = state.status == C.PENDING
    rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
    rows = jnp.arange(params.max_jobs, dtype=jnp.int32)
    target = jnp.where(pending & (rank < K), rank, K)  # K = scatter-drop slot
    return jnp.full((K + 1,), -1, jnp.int32).at[target].set(
        jnp.where(pending & (rank < K), rows, -1), mode="drop")[:K]


QV_J, QV_K = 24, 6


def _queue_view_status(case):
    """int32[J] job statuses for one named case of the queue view."""
    rng = np.random.default_rng(7)
    status = np.full(QV_J, C.DONE, np.int32)
    if case == "none":
        status[::2] = C.RUNNING
        status[1::4] = C.NOT_ARRIVED
    elif case == "fewer":
        status[[2, 9, 17]] = C.PENDING
    elif case == "exactly_k":
        status[rng.choice(QV_J, QV_K, replace=False)] = C.PENDING
    elif case == "more":
        status[rng.choice(QV_J, QV_K + 4, replace=False)] = C.PENDING
    elif case == "k_plus_one_tail":
        # the job past the view is the LAST row: the drop slot's corner
        status[:QV_K] = C.PENDING
        status[QV_J - 1] = C.PENDING
    elif case == "all":
        status[:] = C.PENDING
    elif case == "interleaved":
        status = rng.choice(
            np.array([C.NOT_ARRIVED, C.PENDING, C.RUNNING, C.DONE], np.int32),
            QV_J).astype(np.int32)
    else:
        raise ValueError(case)
    return status


def _queue_view_state(status):
    status = jnp.asarray(status, jnp.int32)
    J = status.shape[-1]
    lead = status.shape[:-1]
    z = jnp.zeros(lead + (J,), jnp.float32)
    return C.SimState(clock=jnp.zeros(lead, jnp.float32), status=status,
                      remaining=z, start=z, finish=z,
                      alloc=jnp.zeros(lead + (J, 2), jnp.int32),
                      free=jnp.zeros(lead + (2,), jnp.int32))


QV_CASES = ["none", "fewer", "exactly_k", "more", "k_plus_one_tail", "all",
            "interleaved"]


class TestDenseQueueView:
    """PR 34: the K-slot queue view is a dense [K, J] selection; the scatter
    and the element gathers it replaced live on only as references here."""
    params = C.SimParams(n_nodes=2, gpus_per_node=4, max_jobs=QV_J,
                         queue_len=QV_K)

    @pytest.mark.parametrize("case", QV_CASES)
    def test_pending_queue_matches_scatter_form(self, case):
        status = _queue_view_status(case)
        state = _queue_view_state(status)
        got = np.asarray(jax.jit(
            lambda s: C.pending_queue(self.params, s))(state))
        want = np.asarray(scatter_pending_queue(self.params, state))
        assert got.dtype == np.int32 and got.shape == (QV_K,)
        np.testing.assert_array_equal(got, want)
        # and against the plain definition
        rows = np.flatnonzero(status == C.PENDING)[:QV_K]
        np.testing.assert_array_equal(got[:len(rows)], rows)
        assert (got[len(rows):] == -1).all()

    @pytest.mark.parametrize("first", [0, 2, 4])
    def test_pending_queue_vmapped_matches_scatter_form(self, first):
        names = (QV_CASES + QV_CASES)[first:first + 4]
        state = _queue_view_state(
            np.stack([_queue_view_status(c) for c in names]))
        got = jax.jit(jax.vmap(
            lambda s: C.pending_queue(self.params, s)))(state)
        want = jax.vmap(
            lambda s: scatter_pending_queue(self.params, s))(state)
        assert got.shape == (4, QV_K)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("field", ["gpus_int32", "duration_float32",
                                       "submit_inf_padding"])
    @pytest.mark.parametrize("case", ["none", "fewer", "more", "all"])
    def test_queue_rows_matches_masked_gather(self, case, field):
        rng = np.random.default_rng(11)
        if field == "gpus_int32":
            f = rng.integers(1, 33, QV_J).astype(np.int32)
        elif field == "duration_float32":
            # values a float32 sum must bring through to the last bit
            f = (rng.random(QV_J) * 1e5 + 1e-3).astype(np.float32)
        else:
            f = np.cumsum(rng.random(QV_J)).astype(np.float32)
            f[-5:] = np.inf                    # padding rows
        f = jnp.asarray(f)
        state = _queue_view_state(_queue_view_status(case))
        queue = C.pending_queue(self.params, state)
        got = jax.jit(C.queue_rows)(f, queue)
        occupied = np.asarray(queue) >= 0
        want = np.where(occupied,
                        np.asarray(f)[np.clip(np.asarray(queue), 0, QV_J - 1)],
                        0)
        assert got.dtype == f.dtype and got.shape == (QV_K,)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert np.isfinite(np.asarray(got)[~occupied]).all()

    def test_queue_rows_vmapped(self):
        rng = np.random.default_rng(3)
        f = jnp.asarray(rng.random((4, QV_J)).astype(np.float32))
        state = _queue_view_state(
            np.stack([_queue_view_status(c) for c in QV_CASES[:4]]))
        queues = jax.vmap(lambda s: C.pending_queue(self.params, s))(state)
        got = np.asarray(jax.jit(jax.vmap(C.queue_rows))(f, queues))
        q = np.asarray(queues)
        want = np.take_along_axis(np.asarray(f), np.clip(q, 0, QV_J - 1),
                                  axis=1) * (q >= 0)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("what", ["pending_queue", "queue_rows",
                                      "action_mask", "grid_obs",
                                      "queue_features"])
    def test_lowered_queue_view_has_no_scatter_or_gather(self, what):
        """Structural guard: nothing that selects or reads the queue view
        lowers to a scatter or a gather (vmapped over envs, as the rollout
        runs it). ``grid_obs`` and ``action_mask`` are lowered whole, with
        the queue passed in: the waterfall and ``jnp.repeat`` lower to
        neither."""
        from rlgpuschedule_tpu.env import obs as obs_lib
        params = self.params
        E = 3
        state = _queue_view_state(
            np.stack([_queue_view_status(c) for c in QV_CASES[:E]]))
        f32 = jnp.ones((E, QV_J), jnp.float32)
        trace = C.Trace(submit=f32, duration=f32,
                        gpus=jnp.ones((E, QV_J), jnp.int32),
                        tenant=jnp.zeros((E, QV_J), jnp.int32),
                        valid=jnp.ones((E, QV_J), bool))
        queues = jnp.zeros((E, QV_K), jnp.int32)
        fn, args = {
            "pending_queue": (lambda s: C.pending_queue(params, s), (state,)),
            "queue_rows": (C.queue_rows, (f32, queues)),
            "action_mask": (lambda s, t, q: C.action_mask(params, s, t, q),
                            (state, trace, queues)),
            "grid_obs": (lambda s, t, q: obs_lib.grid_obs(
                params, s, t, 100.0, q), (state, trace, queues)),
            "queue_features": (lambda s, t, q: obs_lib.queue_features(
                params, s, t, q), (state, trace, queues)),
        }[what]
        text = jax.jit(jax.vmap(fn)).lower(*args).as_text()
        # the words looked for are the ones the old forms lower to
        old_forms = jax.jit(jax.vmap(lambda s, f, q: (
            scatter_pending_queue(params, s), f[jnp.clip(q, 0, QV_J - 1)]))
            ).lower(state, f32, queues).as_text()
        assert "scatter" in old_forms and "gather" in old_forms
        assert "scatter" not in text
        assert "gather" not in text


def run_pair(trace, n_nodes, gpus_per_node, actions, queue_len,
             n_placements=2, preempt_len=0):
    """Drive oracle and JAX sim with the same action sequence; compare
    trajectories after every step."""
    params = C.SimParams(n_nodes=n_nodes, gpus_per_node=gpus_per_node,
                         max_jobs=trace.max_jobs, queue_len=queue_len,
                         n_placements=n_placements, preempt_len=preempt_len)
    osim = O.OracleSim(trace, n_nodes, gpus_per_node)
    tr = C.Trace.from_array_trace(trace)
    jstate = C.init_state(params, tr)
    step = jax.jit(lambda s, a: C.rl_step(params, s, tr, a))
    for i, a in enumerate(actions):
        oinfo = osim.rl_step(int(a), queue_len, n_placements, preempt_len)
        jstate, jinfo = step(jstate, jnp.int32(a))
        s = C.np_state(jstate)
        ctx = f"step {i} action {a}"
        np.testing.assert_allclose(s.clock, osim.clock, atol=1e-3, err_msg=ctx)
        np.testing.assert_array_equal(s.status, osim.status, err_msg=ctx)
        np.testing.assert_allclose(s.remaining, osim.remaining, atol=1e-3,
                                   err_msg=ctx)
        np.testing.assert_array_equal(s.alloc, osim.alloc, err_msg=ctx)
        np.testing.assert_array_equal(s.free, osim.free, err_msg=ctx)
        assert bool(jinfo.placed) == oinfo["placed"], ctx
        assert bool(jinfo.preempted) == oinfo["preempted"], ctx
        assert bool(jinfo.first_placed) == oinfo["first_placed"], ctx
        np.testing.assert_allclose(float(jinfo.dt), oinfo["dt"], atol=1e-3,
                                   err_msg=ctx)
        assert int(jinfo.in_system_before) == oinfo["in_system_before"], ctx
        assert bool(jinfo.done) == oinfo["done"], ctx
        if oinfo["done"]:
            break
    return osim, jstate, params


class TestRLStepEquivalence:
    @pytest.mark.parametrize("seed", range(8))
    def test_random_actions_match_oracle(self, seed):
        rng = np.random.default_rng(seed)
        trace = int_trace(rng, n_jobs=20, max_gpus=4, max_jobs=24)
        queue_len, n_placements = 5, 2
        actions = rng.integers(0, queue_len * n_placements + 1, size=400)
        osim, jstate, params = run_pair(trace, n_nodes=3, gpus_per_node=2,
                                        actions=actions, queue_len=queue_len)

    def test_greedy_head_completes_trace_and_matches_jct(self):
        rng = np.random.default_rng(42)
        trace = int_trace(rng, n_jobs=15, max_gpus=4, max_jobs=16)
        # always try queue head with pack; falls through to time advance
        actions = [0] * 600
        osim, jstate, params = run_pair(trace, 2, 4, actions, queue_len=4)
        assert osim.done()
        tr = C.Trace.from_array_trace(trace)
        stats = C.jct_stats(jstate, tr)
        np.testing.assert_allclose(float(stats["avg_jct"]), osim.avg_jct(),
                                   rtol=1e-5)
        assert int(stats["n_done"]) == 15

    @pytest.mark.parametrize("seed", range(8))
    def test_random_actions_with_preemption_match_oracle(self, seed):
        """Bit-identical trajectories when the action space includes the
        preempt block (VERDICT r1 missing #5). The overloaded trace keeps
        many jobs running+pending so preempt actions actually fire."""
        rng = np.random.default_rng(100 + seed)
        trace = int_trace(rng, n_jobs=20, max_gpus=4, max_jobs=24)
        queue_len, n_placements, preempt_len = 4, 2, 3
        n_actions = queue_len * n_placements + preempt_len + 1
        actions = rng.integers(0, n_actions, size=500)
        run_pair(trace, n_nodes=3, gpus_per_node=2, actions=actions,
                 queue_len=queue_len, preempt_len=preempt_len)

    def test_running_queue_order_and_mask(self):
        """Slot 0 = most attained GPU-service; preempt mask tracks slot
        occupancy; preempting returns the job to the pending queue."""
        trace = to_array_trace(
            [JobRecord(0, 0.0, 50.0, 1), JobRecord(1, 0.0, 50.0, 2)],
            max_jobs=4)
        params = C.SimParams(1, 4, max_jobs=4, queue_len=2, n_placements=1,
                             preempt_len=2)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        state, _ = C.try_place(params, state, tr, jnp.int32(0), jnp.int32(0))
        state, _ = C.try_place(params, state, tr, jnp.int32(1), jnp.int32(0))
        state = C.advance_to(state, tr, jnp.float32(10.0))
        # attained: job0 = 10·1 = 10, job1 = 10·2 = 20 → slot 0 is job 1
        rq = np.asarray(C.running_queue(params, state, tr))
        np.testing.assert_array_equal(rq, [1, 0])
        mask = np.asarray(C.action_mask(params, state, tr))
        # layout [K=2 slots][R=2 preempt][noop]: queue empty, both running
        np.testing.assert_array_equal(mask, [0, 0, 1, 1, 1])
        # preempt slot 0 → job 1 back to PENDING with service preserved
        state, info = C.rl_step(params, state, tr,
                                jnp.int32(params.queue_len))
        assert bool(info.preempted) and not bool(info.placed)
        assert float(info.dt) == 0.0
        s = C.np_state(state)
        assert s.status[1] == O.PENDING and s.remaining[1] == 40.0
        assert s.free.sum() == 3

    def test_replace_after_preempt_is_not_first(self):
        """A preempt→re-place cycle must not farm place_bonus: the
        re-placement reports first_placed=False (shaping potential
        Φ = bonus·#{ever-started} never pays twice)."""
        trace = to_array_trace([JobRecord(0, 0.0, 50.0, 2)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2, n_placements=1,
                             preempt_len=1)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        state, info = C.rl_step(params, state, tr, jnp.int32(0))  # place
        assert bool(info.first_placed)
        state, info = C.rl_step(params, state, tr, jnp.int32(2))  # preempt
        assert bool(info.preempted)
        state, info = C.rl_step(params, state, tr, jnp.int32(0))  # re-place
        assert bool(info.placed) and not bool(info.first_placed)

    def test_preempt_len_zero_mask_unchanged(self):
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 1)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2, n_placements=1)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        assert params.n_actions == 3
        assert C.action_mask(params, state, tr).shape == (3,)

    def test_force_place_on_empty_event_horizon(self):
        # single job, agent always noops: the sim must force-place to
        # guarantee progress (oracle docstring semantics).
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 1)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2, n_placements=1)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        noop = jnp.int32(params.n_actions - 1)
        state, info = C.rl_step(params, state, tr, noop)   # force-place
        assert bool(info.placed) and float(info.dt) == 0.0
        state, info = C.rl_step(params, state, tr, noop)   # advance to done
        assert bool(info.done) and float(state.clock) == 5.0

    def test_preempt(self):
        trace = to_array_trace([JobRecord(0, 0.0, 10.0, 2)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        state, ok = C.try_place(params, state, tr, jnp.int32(0), jnp.int32(0))
        state = C.advance_to(state, tr, jnp.float32(4.0))
        state, ok = C.preempt(state, jnp.int32(0), params.max_jobs)
        assert bool(ok)
        s = C.np_state(state)
        assert s.status[0] == O.PENDING and s.free.sum() == 2
        assert s.remaining[0] == 6.0
        att = np.asarray(C.attained_service(state, tr))
        assert att[0] == 8.0  # 4s × 2 gpus, matches oracle.attained_service


class TestValidateTrace:
    def test_over_capacity_raises_on_host(self):
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 8)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2)
        with pytest.raises(ValueError, match="more than the cluster"):
            C.Trace.from_array_trace(trace, params)

    def test_clamp(self):
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 8)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2)
        clamped = C.validate_trace(params, trace, clamp=True)
        assert clamped.gpus[0] == 2

    def test_over_capacity_step_does_not_lie(self):
        # if an unvalidated over-capacity job sneaks in, rl_step must not
        # report placed=True (regression: forced-place success flag)
        trace = to_array_trace([JobRecord(0, 0.0, 5.0, 8)], max_jobs=2)
        params = C.SimParams(1, 2, max_jobs=2, queue_len=2, n_placements=1)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        state, info = C.rl_step(params, state, tr, jnp.int32(0))
        assert not bool(info.placed) and not bool(info.done)


class TestVmap:
    def test_vmapped_step_matches_single(self):
        rng = np.random.default_rng(0)
        traces = [int_trace(np.random.default_rng(s), 10, 2, max_jobs=12)
                  for s in range(4)]
        params = C.SimParams(2, 2, max_jobs=12, queue_len=4, n_placements=1)
        trs = [C.Trace.from_array_trace(t) for t in traces]
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *trs)
        states = jax.vmap(lambda tr: C.init_state(params, tr))(batched)
        actions = jnp.asarray(rng.integers(0, params.n_actions, size=(20, 4)),
                              jnp.int32)
        vstep = jax.jit(jax.vmap(lambda s, tr, a: C.rl_step(params, s, tr, a)))
        sstep = jax.jit(lambda s, tr, a: C.rl_step(params, s, tr, a))
        single_states = [jax.tree.map(lambda x: x[i], states) for i in range(4)]
        for t in range(20):
            states, infos = vstep(states, batched, actions[t])
            for i in range(4):
                single_states[i], _ = sstep(single_states[i], trs[i], actions[t][i])
                got = jax.tree.map(lambda x: np.asarray(x[i]), states)
                want = C.np_state(single_states[i])
                for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                    np.testing.assert_allclose(g, w, atol=1e-4)


class TestFloat32Tolerance:
    def test_completion_fires_at_large_clock(self):
        """Regression: a job placed at a large f32 clock must still complete.

        With an absolute completion epsilon, ``remaining - dt`` can round to
        a small positive value at clocks where f32 spacing > epsilon, while
        next_event_time rounds to the current clock — advancing dt=0 forever.
        """
        # chosen so f32(1288.741577… + 1720.452392…) rounds DOWN a half-ulp:
        # the advance target then undershoots the completion time and the old
        # absolute-epsilon test left remaining ≈ 1.2e-4 > eps forever
        trace = to_array_trace([
            JobRecord(0, 0.0, 1288.7415771484375, 1),
            JobRecord(1, 0.1, 1720.4523925781250, 1),
        ])
        params = C.SimParams(1, 1, max_jobs=2, queue_len=2, n_placements=1)
        tr = C.Trace.from_array_trace(trace)
        state = C.init_state(params, tr)
        step = jax.jit(lambda s, a: C.rl_step(params, s, tr, a))
        # run jobs back-to-back: place head, advance, place, advance
        for _ in range(8):
            state, info = step(state, jnp.int32(0))
            if bool(info.done):
                break
        assert bool(C.all_done(state, tr)), C.np_state(state)
