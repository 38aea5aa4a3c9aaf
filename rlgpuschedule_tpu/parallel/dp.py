"""Data-parallel training over a device mesh (L5).

Capability parity: SURVEY.md §2 "Distributed comm backend" / §7 step 6 —
the reference's actor-learner gradient sync (NCCL allreduce driven from
torch.distributed) becomes XLA collectives over the mesh, via either of
two equivalent assemblies:

1. **GSPMD** (:func:`shard_train`, the default production path): sharding
   annotations on ONE jitted train step — params/optimizer replicated
   (P()), env batch (traces, rollout carry) sharded over the ``data``
   axis — and GSPMD auto-partitions the fused rollout scan and inserts
   the gradient all-reduce (psum over ICI) where sharded-batch gradients
   meet replicated params. The carry's PRNG key is replicated: action
   sampling is per batch row, so replicas compute identical updates and
   DP matches single-device training bit-for-bit
   (tests/test_parallel.py).
2. **Explicit collectives** (:func:`shard_map_train`): the same step built
   with ``axis_name=DATA_AXIS`` (``lax.pmean`` on gradients and advantage
   moments — algos.ppo/a2c) wrapped in ``shard_map``, the hand-written
   twin of what GSPMD derives. Each shard rolls out its local envs under
   a per-shard PRNG key (decorrelated exploration noise), so this path is
   NOT bit-identical to single-device training — it is the multi-process
   form that generalizes to multi-host meshes where a single GSPMD
   program spans hosts but explicit per-shard control is wanted.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..algos.rollout import RolloutCarry
from .mesh import DATA_AXIS, env_sharded, replicated
from .sharding import put_global as _put_global
from .sharding import shrink_env_rows_by_rule as _shrink_by_rule


def put_global(tree: Any, sharding: NamedSharding) -> Any:
    """DEPRECATED shim: the implementation moved to
    ``parallel.sharding.put_global`` (the rule engine owns placement).
    Delegates and warns; external callers keep working for one
    release."""
    import warnings

    warnings.warn(
        "parallel.dp.put_global is deprecated; use "
        "parallel.sharding.put_global",
        DeprecationWarning, stacklevel=2)
    return _put_global(tree, sharding)


def carry_sharding_prefix(mesh: Mesh) -> RolloutCarry:
    """RolloutCarry sharding prefix-tree: PRNG key replicated, everything
    env-batched split over ``data``."""
    env = env_sharded(mesh)
    return RolloutCarry(env_state=env, obs=env, mask=env,
                        key=replicated(mesh))


def put_carry(mesh: Mesh, carry: RolloutCarry,
              key_sharding: NamedSharding | None = None) -> RolloutCarry:
    """Mesh-place a carry: env-batched fields over ``data``; the key
    replicated (GSPMD path) unless ``key_sharding`` overrides it (the
    shard_map path stacks per-shard keys over ``data``)."""
    env = env_sharded(mesh)
    return RolloutCarry(
        env_state=_put_global(carry.env_state, env),
        obs=_put_global(carry.obs, env),
        mask=_put_global(carry.mask, env),
        key=_put_global(carry.key, key_sharding or replicated(mesh)))


def shrink_env_rows(tree: Any, *, old_n_envs: int, old_world: int,
                    surviving_ranks) -> Any:
    """DEPRECATED shim: elastic shrink-to-fit moved to
    ``parallel.sharding.shrink_env_rows_by_rule``, which decides per-leaf
    by partition RULE instead of this shim's leading-dim heuristic (the
    documented key-length collision caveat is fixed there by keying PRNG
    keys by name). The shim reproduces the old dim-keyed behavior
    exactly — every leaf treated as data-axis-resident, sliced iff its
    leading dim equals ``old_n_envs`` — and warns."""
    import warnings

    from jax.sharding import PartitionSpec

    warnings.warn(
        "parallel.dp.shrink_env_rows is deprecated; use "
        "parallel.sharding.shrink_env_rows_by_rule with a rule table",
        DeprecationWarning, stacklevel=2)
    return _shrink_by_rule(tree, [(r".*", PartitionSpec(DATA_AXIS))],
                           old_n_envs=old_n_envs, old_world=old_world,
                           surviving_ranks=surviving_ranks)


def _check_env_divisible(mesh: Mesh, traces) -> None:
    n_data = mesh.shape[DATA_AXIS]
    n_envs = int(traces.submit.shape[0])
    if n_envs % n_data != 0:
        raise ValueError(f"n_envs={n_envs} not divisible by data axis "
                         f"size {n_data}")


def shard_train(mesh: Mesh, train_step: Callable, train_state, carry,
                traces) -> tuple[Callable, Any, RolloutCarry, Any]:
    """Place (state, carry, traces) on the mesh and wrap ``train_step``
    (an UNjitted step from algos.ppo/a2c, axis_name=None) in a jit with
    explicit in/out shardings. Returns (jitted_step, state, carry, traces)
    for the host loop. n_envs must be divisible by the ``data`` axis."""
    _check_env_divisible(mesh, traces)
    env = env_sharded(mesh)
    rep = replicated(mesh)
    carry_sh = carry_sharding_prefix(mesh)
    jitted = jax.jit(train_step,
                     in_shardings=(rep, carry_sh, env, rep),
                     out_shardings=(rep, carry_sh, rep),
                     donate_argnums=(0, 1))
    return (jitted,
            _put_global(train_state, rep),
            put_carry(mesh, carry),
            _put_global(traces, env))


def shard_map_train(mesh: Mesh, train_step_axis: Callable, train_state,
                    carry, traces) -> tuple[Callable, Any, RolloutCarry, Any]:
    """Explicit-collective twin of :func:`shard_train` (module docstring
    path 2). ``train_step_axis`` must be built with
    ``axis_name=DATA_AXIS`` (``make_ppo_step``/``make_a2c_step``) so its
    gradient/advantage ``lax.pmean`` calls bind to the mesh axis here.

    The rollout carry's key becomes a per-shard key stack ``[n_data, 2]``
    (split from the original): each shard rolls out under its own key, so
    exploration noise decorrelates across shards instead of repeating the
    replicated key's draws on every shard. Metrics are pmean'd before
    leaving the shard so the host sees one replicated value, same as the
    GSPMD path."""
    from ..configs import validate_mode_combination
    # shard_map is a build-path mode with no CLI flag, so its refusal
    # rows are enforced here, at the mode's activation site. The
    # companion modes are False by construction on this path: the
    # shard_map build is the synchronous single-policy loop (no async
    # engine, no PBT controller), takes the whole train step (no fused
    # chunk), and IS the explicit-collective alternative to the GSPMD
    # --mesh build.
    validate_mode_combination({"shard_map": True, "pbt": False,
                               "async": False, "fused_chunk": False,
                               "mesh": False})
    _check_env_divisible(mesh, traces)
    n_data = mesh.shape[DATA_AXIS]

    env_spec, rep_spec = P(DATA_AXIS), P()
    carry_spec = RolloutCarry(env_state=env_spec, obs=env_spec,
                              mask=env_spec, key=env_spec)

    def wrapped(state, carry_in, tr, key):
        local = carry_in._replace(key=carry_in.key[0])
        state, local, metrics = train_step_axis(state, local, tr, key)
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m, DATA_AXIS), metrics)
        return state, local._replace(key=local.key[None]), metrics

    jitted = jax.jit(jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(rep_spec, carry_spec, env_spec, rep_spec),
        out_specs=(rep_spec, carry_spec, rep_spec),
        check_vma=False), donate_argnums=(0, 1))

    keys = jax.random.split(jnp.asarray(carry.key), n_data)
    carry = carry._replace(key=keys)
    carry_sh = put_carry(mesh, carry,
                         key_sharding=NamedSharding(mesh, P(DATA_AXIS)))
    return (jitted, jax.device_put(train_state, replicated(mesh)), carry_sh,
            jax.device_put(traces, env_sharded(mesh)))
