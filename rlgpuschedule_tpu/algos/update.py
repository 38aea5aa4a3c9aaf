"""Fused minibatch-update engine (L4): ONE geometry-configurable
epoch × minibatch ``lax.scan`` shared by PPO, A2C, and the PBT member step.

Motivation (BASELINE.md "Where the time goes"): the minibatch update is
76.7% of the fused train step and its small matmuls underfill the MXU, so
minibatch geometry — ``n_epochs × n_minibatches × minibatch_size`` — is
the first throughput lever. This module makes that geometry an explicit,
validated, sweepable property instead of a hard-coded split:

- :func:`resolve_geometry` validates the triple against the rollout batch
  (``minibatch_size``, when set, *determines* the minibatch count —
  "fewer, larger minibatches" is one number away).
- :func:`run_minibatch_epochs` is the engine: an epoch scan carrying
  ``(state, key)`` whose body gathers ONE whole-batch permutation and
  scans a ``grad_step`` over contiguous minibatch blocks. At the trivial
  ``1 × 1`` geometry it calls ``grad_step`` on the whole batch directly
  (no permutation, no scan machinery) so A2C's classic full-batch update
  is the same engine at the degenerate geometry, bit-identically. At
  ``n_minibatches == 1`` the permutation gather is skipped entirely (a
  full-batch epoch sees every sample regardless of order), which is
  exactly the swept fewer-larger-minibatch fast path.
- :func:`cast_floating` backs the optional bf16-compute path: loss +
  grads evaluated in bfloat16, gradients cast back to the parameter
  dtype so the optimizer state (Adam moments) stays fp32. Behind a flag
  (``bf16_update``) because it is NOT bit-identical to fp32 compute.

Buffer discipline: inside the fused train step the engine is one jitted
region — XLA's scan carries the optimizer state in place and the rollout
batch is consumed without copies. For a *standalone* update dispatch
(stage profiling, the minibatch sweep), :func:`make_update_step` jits the
engine with the state donated, so repeated calls reuse the
parameter/optimizer buffers instead of allocating fresh ones per call.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..obs import scopes

# grad_step(state, minibatch_data) -> (state, stats): one optimizer
# update on one minibatch. ``stats`` is any pytree of scalars; the engine
# stacks it to [n_epochs, n_minibatches, ...].
GradStep = Callable[[Any, Any], tuple[Any, Any]]


def resolve_geometry(n_epochs: int, n_minibatches: int,
                     minibatch_size: int | None,
                     batch_size: int) -> tuple[int, int, int]:
    """Validate the update geometry against the flattened rollout batch.

    Returns the resolved ``(n_epochs, n_minibatches, minibatch_size)``
    triple. ``minibatch_size``, when set, takes precedence: it determines
    the minibatch count (``batch_size // minibatch_size``) and the
    configured ``n_minibatches`` is required to either agree or be left
    at any value (it is ignored) — so "fewer, larger minibatches" needs
    only one number. Everything must tile the batch exactly: a silently
    dropped remainder would train on less data than configured."""
    if n_epochs < 1:
        raise ValueError(f"n_epochs must be >= 1, got {n_epochs}")
    if minibatch_size is not None:
        if minibatch_size < 1:
            raise ValueError(
                f"minibatch_size must be >= 1, got {minibatch_size}")
        if batch_size % minibatch_size:
            raise ValueError(
                f"minibatch_size={minibatch_size} must divide the rollout "
                f"batch (n_steps * n_envs = {batch_size}); a remainder "
                f"minibatch would change shapes mid-scan")
        n_minibatches = batch_size // minibatch_size
    else:
        if n_minibatches < 1:
            raise ValueError(
                f"n_minibatches must be >= 1, got {n_minibatches}")
        if batch_size % n_minibatches:
            raise ValueError(
                f"n_steps * n_envs = {batch_size} must be divisible by "
                f"n_minibatches={n_minibatches}")
        minibatch_size = batch_size // n_minibatches
    return n_epochs, n_minibatches, minibatch_size


def validate_update_geometry(n_epochs: int, n_minibatches: int,
                             minibatch_size: int | None, *, n_steps: int,
                             n_envs: int, n_devices: int = 1
                             ) -> tuple[int, int, int]:
    """Validate the update phase's geometry on its own terms — the
    counterpart of ``algos.rollout.validate_rollout_geometry`` for the
    async split, where the update runs on a learner device group that
    need not match the actor group. Checks that the trajectory batch
    tiles the learner group (the [T, E] env axis is what's sharded) and
    resolves the minibatch triple against the flattened T·E batch.
    Returns the resolved ``(n_epochs, n_minibatches, minibatch_size)``."""
    if n_devices > 1 and n_envs % n_devices:
        raise ValueError(
            f"n_envs={n_envs} must be divisible by the update device "
            f"group size ({n_devices}) to shard the trajectory batch")
    return resolve_geometry(n_epochs, n_minibatches, minibatch_size,
                            n_steps * n_envs)


def cast_floating(tree: Any, dtype) -> Any:
    """Cast every floating leaf of ``tree`` to ``dtype`` (bool/int leaves
    — action ids, masks, done flags — pass through untouched)."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, tree)


def _batch_size(data: Any) -> int:
    leaves = jax.tree.leaves(data)
    if not leaves:
        raise ValueError("update engine got an empty data pytree")
    return leaves[0].shape[0]


def run_minibatch_epochs(grad_step: GradStep, state: Any, data: Any,
                         key: jax.Array, *, n_epochs: int = 1,
                         n_minibatches: int = 1,
                         minibatch_size: int | None = None
                         ) -> tuple[Any, Any]:
    """The fused update engine: run ``grad_step`` over ``n_epochs``
    shuffled passes of ``data`` split into ``n_minibatches`` contiguous
    blocks. ``data`` is any pytree of ``[B, ...]`` arrays (B = flattened
    rollout batch). Returns ``(state, stats)`` with stats stacked
    ``[n_epochs, n_minibatches, ...]``.

    Numerics contract (pinned by tests/test_algos.py): at any geometry
    this is bit-identical to the legacy per-minibatch Python loop with
    the same key — one ``jax.random.split`` per epoch, one whole-batch
    ``jax.random.permutation`` gather per epoch, minibatches read as
    contiguous blocks of the shuffled batch. At the degenerate ``1 × 1``
    geometry the batch is passed to ``grad_step`` whole, unpermuted —
    bit-identical to a classic single full-batch update (A2C's default).
    """
    B = _batch_size(data)
    n_epochs, n_mb, _mb = resolve_geometry(n_epochs, n_minibatches,
                                           minibatch_size, B)
    if n_epochs == 1 and n_mb == 1:
        # degenerate geometry: one full-batch update, no permutation, no
        # scan machinery, no key consumed (A2C's classic update)
        state, stats = grad_step(state, data)
        return state, jax.tree.map(lambda s: jnp.asarray(s)[None, None],
                                   stats)

    def epoch(state_and_key, _):
        state, key = state_and_key
        key, sub = jax.random.split(key)
        if n_mb > 1:
            # ONE whole-batch gather per epoch, then scan over contiguous
            # [n_mb, mb, ...] blocks — identical minibatch contents to
            # gathering x[perm[i]] inside the scan body (same perm, same
            # row order), but the inner loop reads each minibatch as a
            # contiguous dynamic-slice instead of issuing a fresh
            # row-gather per minibatch (the update scan is the measured
            # hot stage — BASELINE.md "where the time goes").
            with jax.named_scope(scopes.SHUFFLE):
                perm = jax.random.permutation(sub, B)
                blocks = jax.tree.map(
                    lambda x: x[perm].reshape(n_mb, _mb, *x.shape[1:]),
                    data)
        else:
            # full-batch epochs: a permutation would only reorder a mean —
            # skip the gather (the swept fewer-larger-minibatch fast path)
            blocks = jax.tree.map(lambda x: x[None], data)
        state, stats = jax.lax.scan(grad_step, state, blocks)
        return (state, key), stats

    (state, _), stats = jax.lax.scan(epoch, (state, key), None,
                                     length=n_epochs)
    return state, stats


def make_update_step(run_update: Callable, donate: bool = True) -> Callable:
    """Jit a standalone update dispatch ``run_update(state, *batch_args)
    -> (state, metrics)`` with the state donated (parameter + optimizer
    buffers reused across calls instead of re-allocated — the
    "allocation-free across epochs" contract at the dispatch boundary;
    inside the fused train step the same engine is one scan and needs no
    donation). Callers must thread the returned state back in and treat
    the donated input as dead."""
    return jax.jit(run_update, donate_argnums=(0,) if donate else ())
