"""Partition-rule sharding engine (parallel.sharding): rule matching,
per-family coverage, and the two bit-identity acceptance gates — the
rule-sharded train step vs the hand-wired dp path, and the PBT
population as a mesh axis vs the per-member Python loop — both on
forced-CPU virtual devices with a zero-post-warmup-recompile
CompileCounter gate."""

import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from flax.training.train_state import TrainState

from rlgpuschedule_tpu.algos import PPOConfig, init_carry, make_ppo_step
from rlgpuschedule_tpu.algos.ppo import make_optimizer
from rlgpuschedule_tpu.analysis.sentinels import CompileCounter
from rlgpuschedule_tpu.env import EnvParams, stack_traces
from rlgpuschedule_tpu.models import HierActorCritic, make_policy
from rlgpuschedule_tpu.parallel import (DATA_AXIS, MODEL_AXIS, POP_AXIS,
                                        make_unified_mesh)
from rlgpuschedule_tpu.parallel import sharding as shardlib
from rlgpuschedule_tpu.parallel.dp import carry_sharding_prefix, put_carry
from rlgpuschedule_tpu.parallel.mesh import env_sharded, replicated
from rlgpuschedule_tpu.sim.core import SimParams
from rlgpuschedule_tpu.traces import gen_poisson_trace


def build(n_envs=8, dtype=jnp.float32):
    env_params = EnvParams(sim=SimParams(2, 4, max_jobs=16, queue_len=4),
                           obs_kind="flat", horizon=64, time_scale=100.0,
                           reward_scale=1000.0)
    windows = [gen_poisson_trace(0.05, 12, seed=s, max_jobs=16,
                                 mean_duration=60.0, gpu_sizes=(1, 2),
                                 gpu_probs=(0.7, 0.3))
               for s in range(n_envs)]
    traces = stack_traces(windows, env_params)
    net = make_policy("flat", env_params.n_actions, dtype=dtype)
    apply_fn = lambda p, o, m: net.apply(p, o, m)
    cfg = PPOConfig(n_steps=8, n_epochs=2, n_minibatches=2)
    key = jax.random.PRNGKey(0)
    carry = init_carry(env_params, traces, key)
    params = net.init(key, carry.obs[:1], carry.mask[:1])
    state = TrainState.create(apply_fn=net.apply, params=params,
                              tx=make_optimizer(cfg))
    step = make_ppo_step(apply_fn, env_params, cfg)
    return env_params, traces, state, carry, step


class TestRuleMatching:
    def test_scalar_and_size1_short_circuit(self):
        specs = shardlib.match_partition_rules(
            [], {"step": jnp.int32(0), "ema": jnp.ones((1,))})
        assert specs["step"] == P() and specs["ema"] == P()

    def test_first_match_wins(self):
        rules = [(r"kernel$", P(None, MODEL_AXIS)), (r".*", P())]
        got = shardlib.match_rule(rules, "params/Dense_0/kernel")
        assert got == P(None, MODEL_AXIS)
        # reversed order: the catch-all shadows the kernel rule
        got = shardlib.match_rule(list(reversed(rules)),
                                  "params/Dense_0/kernel")
        assert got == P()

    def test_unmatched_leaf_is_a_hard_error(self):
        with pytest.raises(ValueError, match="Partition rule not found"):
            shardlib.match_partition_rules(
                [(r"kernel$", P())], {"weird": jnp.ones((4, 4))})

    def test_rule_table_hash_is_stable_and_order_sensitive(self):
        h1 = shardlib.rule_table_hash(shardlib.FLAT_RULES)
        assert h1 == shardlib.rule_table_hash(list(shardlib.FLAT_RULES))
        h2 = shardlib.rule_table_hash(list(reversed(shardlib.FLAT_RULES)))
        assert h1 != h2

    def test_prune_spec_drops_axes_the_mesh_lacks(self):
        # a legacy pop x data mesh (no model axis) must not hard-error on
        # the unified tables' model-axis specs — those dims replicate
        import numpy as _np
        from jax.sharding import Mesh as JMesh
        legacy = JMesh(_np.array(jax.devices()[:1]).reshape(1, 1),
                       (POP_AXIS, DATA_AXIS))
        assert shardlib.prune_spec(
            P(POP_AXIS, None, MODEL_AXIS), legacy) == P(POP_AXIS)
        assert shardlib.prune_spec(
            P((POP_AXIS, MODEL_AXIS), DATA_AXIS), legacy) == \
            P(POP_AXIS, DATA_AXIS)
        sh = shardlib.tree_shardings(
            {"dense/kernel": jnp.ones((4, 4))},
            [(r"kernel$", P(DATA_AXIS, MODEL_AXIS))], legacy)
        assert sh["dense/kernel"].spec == P(DATA_AXIS)


class TestFamilyCoverage:
    """Every family's params are fully covered BEFORE the catch-all,
    and at least one kernel per family actually lands on ``model``."""

    def _covered(self, rules, params):
        specs = shardlib.match_partition_rules(rules[:-1], params)
        flat = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert any(MODEL_AXIS in (s or ()) for spec in flat
                   for s in spec), "no leaf sharded over model"

    def test_flat(self):
        net = make_policy("flat", 5, dtype=jnp.float32)
        params = net.init(jax.random.PRNGKey(0), jnp.ones((1, 24)),
                          jnp.ones((1, 5), bool))
        self._covered(shardlib.FLAT_RULES, params)

    def test_grid(self):
        net = make_policy("grid", 5, dtype=jnp.float32)
        params = net.init(jax.random.PRNGKey(0), jnp.ones((1, 8, 8, 3)),
                          jnp.ones((1, 5), bool))
        self._covered(shardlib.GRID_RULES, params)
        specs = shardlib.match_partition_rules(shardlib.GRID_RULES, params)
        conv = [s for n, s in zip(shardlib.tree_leaf_names(params),
                                  jax.tree.leaves(
                                      specs,
                                      is_leaf=lambda x: isinstance(x, P)))
                if "Conv_0/kernel" in n]
        assert conv == [P(None, None, None, MODEL_AXIS)]

    def test_graph(self):
        net = make_policy("graph", 5, n_cluster_nodes=2, queue_len=4,
                          dtype=jnp.float32)
        V = 2 + 4 + 1
        params = net.init(jax.random.PRNGKey(0), jnp.ones((1, V, 6)),
                          jnp.ones((V, V)), jnp.ones((1, 5), bool))
        self._covered(shardlib.GRAPH_RULES, params)

    def test_hier(self):
        net = HierActorCritic(n_top_actions=5, n_pod_actions=7,
                              dtype=jnp.float32)
        obs = {"top": jnp.ones((1, 16)), "pods": jnp.ones((1, 4, 16))}
        mask = {"top": jnp.ones((1, 5), bool),
                "pods": jnp.ones((1, 4, 7), bool)}
        params = net.init(jax.random.PRNGKey(0), obs, mask)
        self._covered(shardlib.HIER_RULES, params)

    def test_tokens(self):
        """The held experts' three kernels a layer go on ``model`` by
        their last axis (whole experts a shard); every other leaf is
        replicated by an explicit rule."""
        net = make_policy("tokens", 5, trunk="tiny", dtype=jnp.float32)
        params = net.init(jax.random.PRNGKey(0), jnp.ones((1, 12, 11)),
                          jnp.ones((1, 5), bool))
        self._covered(shardlib.TOKENS_RULES, params)
        specs = shardlib.match_partition_rules(shardlib.TOKENS_RULES, params)
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        sharded = {n for n, s in zip(shardlib.tree_leaf_names(params), flat)
                   if s != P()}
        assert sharded == {
            f"params/encoder/layer_{i}/moe/experts_{w}/kernel"
            for i in range(1, 5) for w in ("gate", "up", "down")}
        assert all(s == P(None, MODEL_AXIS) for s in flat if s != P())
        assert shardlib.RULE_TABLES["tokens"] is shardlib.TOKENS_RULES

    def test_opt_state_shards_with_the_same_table(self):
        # Adam moments mirror param paths, so the SAME rules cover the
        # full TrainState — the zero-extra-configuration property the
        # re.search matching exists for
        _, _, state, _, _ = build(n_envs=2)
        shardlib.match_partition_rules(shardlib.FLAT_RULES, state)


class TestBitIdentityVsDP:
    """Rule-resolved in/out_shardings + bind_mesh constraints vs the
    hand-wired dp.shard_train path: same 2-device mesh, same seeds —
    params must be BITWISE identical, and the rule path must not
    recompile after warmup."""

    def _run_dp(self, iters):
        from rlgpuschedule_tpu.parallel.dp import shard_train
        _, traces, state, carry, step = build()
        mesh = make_unified_mesh(devices=jax.devices()[:2])
        jstep, state, carry, traces = shard_train(mesh, step, state,
                                                  carry, traces)
        for i in range(iters):
            state, carry, m = jstep(state, carry, traces,
                                    jax.random.PRNGKey(i))
        return state, m

    def _run_rules(self, iters):
        _, traces, state, carry, step = build()
        mesh = make_unified_mesh(devices=jax.devices()[:2])
        rules = shardlib.FLAT_RULES
        state_sh = shardlib.tree_shardings(state, rules, mesh)
        env, rep = env_sharded(mesh), replicated(mesh)
        carry_sh = carry_sharding_prefix(mesh)
        jstep = jax.jit(shardlib.bind_mesh(step, mesh),
                        in_shardings=(state_sh, carry_sh, env, rep),
                        out_shardings=(state_sh, carry_sh, rep),
                        donate_argnums=(0, 1))
        state = shardlib.put_tree(state, state_sh)
        carry = put_carry(mesh, carry)
        traces = shardlib.put_global(traces, env)
        counted = 0
        for i in range(iters):
            if i == 1:
                cc = CompileCounter()
                cc.__enter__()
                counted = 1
            state, carry, m = jstep(state, carry, traces,
                                    jax.random.PRNGKey(i))
        if counted:
            jax.block_until_ready(jax.tree.leaves(state.params))
            cc.__exit__(None, None, None)
            assert cc.total == 0, (
                f"rule-sharded step recompiled after warmup: "
                f"{cc.traces} traces, {cc.backend_compiles} compiles")
        return state, m

    def test_rule_path_matches_dp_bitwise(self):
        assert len(jax.devices()) >= 2
        dstate, _ = self._run_dp(3)
        rstate, _ = self._run_rules(3)
        for name, d, r in zip(shardlib.tree_leaf_names(dstate.params),
                              jax.tree.leaves(jax.device_get(
                                  dstate.params)),
                              jax.tree.leaves(jax.device_get(
                                  rstate.params))):
            assert np.array_equal(np.asarray(d), np.asarray(r)), (
                f"param {name} diverged between dp and rule paths")


class TestBitIdentityPBT:
    """The population as a ``pop`` mesh axis (ONE dispatch) vs a Python
    loop of per-member steps: member params identical to last-ulp
    tolerance, zero post-warmup recompiles."""

    N_POP = 2
    ITERS = 2

    def _init_population(self):
        from rlgpuschedule_tpu.parallel.population import (
            init_member, sample_hparams, stack_members)
        env_params, traces, _, _, _ = build(n_envs=4)
        net = make_policy("flat", env_params.n_actions, dtype=jnp.float32)
        apply_fn = lambda p, o, m: net.apply(p, o, m)
        cfg = PPOConfig(n_steps=8, n_epochs=2, n_minibatches=2)
        members, carries = [], []
        for i in range(self.N_POP):
            key = jax.random.PRNGKey(100 + i)
            carry = init_carry(env_params, traces, key)
            members.append(init_member(net, key, carry.obs[:1],
                                       carry.mask[:1], cfg))
            carries.append(carry)
        hp = sample_hparams(cfg, self.N_POP, seed=0)
        keys = jnp.stack([jax.random.PRNGKey(500 + i)
                          for i in range(self.ITERS)])
        return (env_params, traces, apply_fn, cfg, members, carries, hp,
                keys, stack_members)

    def test_mesh_axis_matches_python_loop_bitwise(self):
        from rlgpuschedule_tpu.parallel.population import (
            jit_population_step, make_member_step, make_population_step)
        (env_params, traces, apply_fn, cfg, members, carries, hp, keys,
         stack_members) = self._init_population()

        # --- reference: per-member jitted step in a Python loop
        member = jax.jit(make_member_step(apply_fn, env_params, cfg))
        loop_states = [m for m in members]
        loop_carries = [c for c in carries]
        for t in range(self.ITERS):
            mkeys = jax.random.split(keys[t], self.N_POP)
            for i in range(self.N_POP):
                hp_i = jax.tree.map(lambda x: x[i], hp)
                loop_states[i], loop_carries[i], _ = member(
                    loop_states[i], loop_carries[i], traces, mkeys[i],
                    hp_i)

        # --- mesh path: stacked members, pop axis, one dispatch/iter
        mesh = make_unified_mesh(n_pop=self.N_POP,
                                 devices=jax.devices()[:self.N_POP])
        states = stack_members(members)
        carry = stack_members(carries)
        pop_step = make_population_step(apply_fn, env_params, cfg)
        jstep = jit_population_step(mesh, pop_step, states=states,
                                    rules=shardlib.FLAT_RULES)
        cc = None
        for t in range(self.ITERS):
            mkeys = jax.random.split(keys[t], self.N_POP)
            if t == 1:
                cc = CompileCounter()
                cc.__enter__()
            states, carry, _ = jstep(states, carry, traces, mkeys, hp)
        jax.block_until_ready(jax.tree.leaves(states.params))
        if cc is not None:
            cc.__exit__(None, None, None)
            assert cc.total == 0, (
                f"population step recompiled after warmup: {cc.traces} "
                f"traces, {cc.backend_compiles} compiles")

        # last-ulp tolerance, not bitwise: XLA:CPU emits different dot
        # kernels for the batched (vmapped) and unbatched member shapes,
        # so loop/vmap/partitioned-vmap all differ in the final float32
        # bit after a few updates. Anything beyond ulp noise (a wrong
        # sharding, a member mixup, hp misalignment) is an O(1)
        # divergence this still catches.
        stacked = jax.device_get(states.params)
        for i in range(self.N_POP):
            got = jax.tree.map(lambda x: x[i], stacked)
            want = jax.device_get(loop_states[i].params)
            for name, g, w in zip(shardlib.tree_leaf_names(want),
                                  jax.tree.leaves(got),
                                  jax.tree.leaves(want)):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(w), rtol=2e-5, atol=1e-7,
                    err_msg=(f"member {i} param {name} diverged between "
                             f"mesh and loop paths"))


class TestElasticByRule:
    def test_key_leaf_is_protected_by_name(self):
        # a PRNG key whose length coincides with old_n_envs: the rule
        # path keeps it whole, the deprecated dim heuristic slices it
        old_n_envs, old_world = 8, 4
        tree = {"obs": np.arange(8 * 3, dtype=np.float32).reshape(8, 3),
                "done": np.zeros(8, bool),
                "key": np.arange(8, dtype=np.uint32)}
        out = shardlib.shrink_env_rows_by_rule(
            tree, shardlib.ELASTIC_EXTRA_RULES, old_n_envs=old_n_envs,
            old_world=old_world, surviving_ranks=[0, 2])
        assert out["obs"].shape == (4, 3)
        assert out["done"].shape == (4,)
        assert out["key"].shape == (8,)          # preserved by name
        np.testing.assert_array_equal(out["key"], tree["key"])
        np.testing.assert_array_equal(out["obs"],
                                      tree["obs"][[0, 1, 4, 5]])

    def test_dp_shim_warns_and_keeps_dim_keyed_behavior(self):
        from rlgpuschedule_tpu.parallel import dp
        tree = {"key": np.arange(8, dtype=np.uint32)}
        with pytest.warns(DeprecationWarning, match="shrink_env_rows"):
            out = dp.shrink_env_rows(tree, old_n_envs=8, old_world=4,
                                     surviving_ranks=[0, 2])
        assert out["key"].shape == (4,)          # the old caveat, exactly

    def test_put_global_shim_warns_and_places(self):
        from rlgpuschedule_tpu.parallel import dp
        mesh = make_unified_mesh(devices=jax.devices()[:2])
        with pytest.warns(DeprecationWarning, match="put_global"):
            out = dp.put_global(jnp.ones((4, 2)), env_sharded(mesh))
        assert out.sharding.mesh.shape[DATA_AXIS] == 2

    def test_invalid_survivors_raise(self):
        with pytest.raises(ValueError, match="surviving_ranks"):
            shardlib.shrink_env_rows_by_rule(
                {"a": np.zeros((8,))}, shardlib.ELASTIC_EXTRA_RULES,
                old_n_envs=8, old_world=4, surviving_ranks=[0, 7])


class TestUnifiedMesh:
    def test_three_axis_shape_and_validation(self):
        m = make_unified_mesh(n_pop=2, n_model=2)
        assert (m.shape[POP_AXIS], m.shape[DATA_AXIS],
                m.shape[MODEL_AXIS]) == (2, 2, 2)
        with pytest.raises(ValueError):
            make_unified_mesh(n_pop=3)

    def test_split_mesh_partitions_devices(self):
        from rlgpuschedule_tpu.parallel import split_mesh
        groups = split_mesh(make_unified_mesh(), actor=2)
        assert len(groups.actor) == 2
        assert len(groups.learner) == len(jax.devices()) - 2


class TestModeTable:
    def test_every_refusal_names_known_modes(self):
        from rlgpuschedule_tpu.configs import MODE_FLAGS, MODE_REFUSALS
        for a, b, why in MODE_REFUSALS:
            assert a in MODE_FLAGS and b in MODE_FLAGS and why

    def test_error_format_carries_both_flag_spellings(self):
        from rlgpuschedule_tpu.configs import (MODE_FLAGS, MODE_REFUSALS,
                                               ModeCombinationError,
                                               validate_mode_combination)
        for a, b, _ in MODE_REFUSALS:
            with pytest.raises(ModeCombinationError) as ei:
                validate_mode_combination({a: True, b: True})
            assert MODE_FLAGS[a] in str(ei.value)
            assert MODE_FLAGS[b] in str(ei.value)

    def test_inactive_and_unknown_modes(self):
        from rlgpuschedule_tpu.configs import validate_mode_combination
        validate_mode_combination({"async": True, "pbt": False})
        with pytest.raises(KeyError, match="unknown mode"):
            validate_mode_combination({"warp_drive": True})


class TestFusedUnderMesh:
    """run_fused under the unified mesh (ISSUE 13 satellite): the fused
    scan's in/out_shardings come from the SAME partition-rule table as
    the per-step build — not input-inferred shardings — so the fused
    path is bit-identical to the per-step rule path given the same key
    stream, keeps the rule-table NamedSharding layout on its outputs,
    and never recompiles on a repeated fused length.

    Collected only inside the clean-interpreter subprocess spawned by
    :func:`test_fused_under_mesh_isolated` (the ``__test__`` gate below):
    compiling the fused MULTI-device SPMD program on the forced-8-device
    CPU backend after a long heap-churning session (anything after
    test_serve) SIGABRT/SIGSEGVs the whole pytest process on jax 0.4.37
    — it reproduces on a pristine checkout, with the persistent compile
    cache on OR off, and MALLOC_CHECK_ heisenbugs it away, i.e. latent
    native heap damage surfacing at the biggest multi-device compile. A
    fresh interpreter running just this class is deterministically
    green, so that is the only supported way to run it in-suite."""

    __test__ = os.environ.get("RLGS_FUSED_MESH_INPROC") == "1"

    ITERS = 3

    @pytest.fixture(autouse=True)
    def _no_persistent_cache(self):
        # independent of the in-process crash above, the persistent
        # compile cache's multi-device executable ROUND-TRIP is itself
        # flaky on this backend (the jax 0.4.37 bug ci.sh works around
        # with JAX_ENABLE_COMPILATION_CACHE=false on its mesh smokes) —
        # pay the recompile instead of betting the run on a deserialize
        import jax as _jax
        prev = _jax.config.jax_enable_compilation_cache
        _jax.config.update("jax_enable_compilation_cache", False)
        yield
        _jax.config.update("jax_enable_compilation_cache", prev)

    def _build(self):
        import dataclasses
        from rlgpuschedule_tpu.configs import CONFIGS
        from rlgpuschedule_tpu.experiment import Experiment
        cfg = dataclasses.replace(
            CONFIGS["ppo-mlp-synth64"], n_envs=2, window_jobs=16,
            horizon=64, iterations=2,
            ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2))
        mesh = make_unified_mesh(devices=jax.devices()[:2])
        return Experiment.build(cfg, mesh=mesh), mesh

    def test_fused_matches_perstep_rule_path_bitwise(self):
        exp_f, mesh = self._build()
        exp_s, _ = self._build()
        # replay run_fused's exact key stream through the per-step jit
        key, sub = jax.random.split(exp_s.key)
        keys = jax.random.split(sub, self.ITERS)
        state, carry = exp_s.train_state, exp_s.carry
        for i in range(self.ITERS):
            state, carry, _ = exp_s.train_step(state, carry, exp_s.traces,
                                               keys[i], exp_s.faults)
        metrics = exp_f.run_fused(self.ITERS)
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree.leaves(jax.device_get(metrics)))
        for name, f, s in zip(
                shardlib.tree_leaf_names(exp_f.train_state.params),
                jax.tree.leaves(jax.device_get(exp_f.train_state.params)),
                jax.tree.leaves(jax.device_get(state.params))):
            assert np.array_equal(np.asarray(f), np.asarray(s)), (
                f"param {name} diverged between fused-under-mesh and "
                f"the per-step rule path")

    def test_fused_outputs_keep_rule_shardings_and_stay_warm(self):
        exp, mesh = self._build()
        exp.run_fused(self.ITERS)       # warmup: blessed compile
        for leaf in jax.tree.leaves(exp.train_state.params):
            sh = leaf.sharding
            assert isinstance(sh, jax.sharding.NamedSharding), (
                f"fused output fell back to {type(sh).__name__}: the "
                f"rule-table out_shardings were not applied")
            assert sh.mesh.shape == mesh.shape
        with CompileCounter() as cc:
            exp.run_fused(self.ITERS)   # same length: cached program
            jax.block_until_ready(jax.tree.leaves(exp.train_state.params))
        assert cc.total == 0, (
            f"fused-under-mesh recompiled on a repeated length: "
            f"{cc.traces} traces, {cc.backend_compiles} compiles")


def test_fused_under_mesh_isolated():
    """Run :class:`TestFusedUnderMesh` in a fresh interpreter (see its
    docstring for why in-process is not survivable on jax 0.4.37) and
    fail with its full output if anything inside fails. One retry, ONLY
    on a signal death (negative returncode): the fresh process dodges
    the heap-state trigger but the underlying XLA:CPU bug is still
    nondeterministic native code — a genuine test failure (rc > 0) is
    never retried."""
    env = dict(os.environ,
               RLGS_FUSED_MESH_INPROC="1",
               JAX_ENABLE_COMPILATION_CACHE="false")
    cmd = [sys.executable, "-m", "pytest",
           f"{__file__}::TestFusedUnderMesh", "-q", "-m", "not slow",
           "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    for attempt in (1, 2):
        res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=420)
        if res.returncode == 0:
            # rc 0 with nothing collected would be a silent coverage
            # hole (e.g. the __test__ gate broke); pytest exits 5 on
            # "no tests ran", but belt-and-braces the success line
            assert " passed" in res.stdout, res.stdout
            return
        if res.returncode > 0:
            break                       # real failure inside the class
    pytest.fail(
        f"isolated fused-under-mesh run failed (rc {res.returncode}, "
        f"attempt {attempt}):\n{res.stdout[-4000:]}\n{res.stderr[-4000:]}")


class TestTokensModelAxis:
    """The token policy's rule table on the unified mesh: at ``model`` = 1
    the layout is exact replication and the mesh-built step is the plain
    step bit for bit; at ``model`` = 2 each shard holds whole experts and
    the step computes the same update."""

    def _cfg(self):
        import dataclasses
        from rlgpuschedule_tpu.configs import CONFIGS
        return dataclasses.replace(
            CONFIGS["ppo-trinity-philly512"], trunk="tiny", n_envs=2,
            n_nodes=2, gpus_per_node=4, window_jobs=16, queue_len=4,
            horizon=64, ppo=PPOConfig(n_steps=8, n_epochs=1,
                                      n_minibatches=2))

    def _run(self, mesh, iters=2):
        from rlgpuschedule_tpu.experiment import Experiment
        exp = Experiment.build(self._cfg(), mesh=mesh)
        out = exp.run(iterations=iters, log_every=1)
        return exp, out["history"]

    def test_model_axis_1_is_exact_replication(self):
        plain, h_plain = self._run(None)
        mesh = make_unified_mesh(devices=jax.devices()[:1])
        assert dict(mesh.shape) == {POP_AXIS: 1, DATA_AXIS: 1,
                                    MODEL_AXIS: 1}
        meshed, h_mesh = self._run(mesh)
        for x in jax.tree.leaves(meshed.train_state.params):
            assert x.sharding.is_fully_replicated
        for name, a, b in zip(
                shardlib.tree_leaf_names(plain.train_state.params),
                jax.tree.leaves(jax.device_get(plain.train_state.params)),
                jax.tree.leaves(jax.device_get(meshed.train_state.params))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert [h["total_loss"] for h in h_plain] == [
            h["total_loss"] for h in h_mesh]

    def test_model_axis_2_holds_whole_experts_a_shard(self):
        plain, h_plain = self._run(None)
        mesh = make_unified_mesh(n_model=2, devices=jax.devices()[:2])
        meshed, h_mesh = self._run(mesh)
        moe = meshed.train_state.params["params"]["encoder"]["layer_1"]["moe"]
        gate = moe["experts_gate"]["kernel"]       # [d, 2 experts * f]
        assert gate.sharding.spec == P(None, MODEL_AXIS)
        assert {s.data.shape for s in gate.addressable_shards} == {
            (gate.shape[0], gate.shape[1] // 2)}
        assert moe["router"]["kernel"].sharding.is_fully_replicated
        np.testing.assert_allclose(
            [h["total_loss"] for h in h_mesh],
            [h["total_loss"] for h in h_plain], rtol=1e-2, atol=1e-3)
        assert all(h["moe_dropped_assignments"] == 0.0 for h in h_mesh)
