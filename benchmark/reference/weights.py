"""Seeded weights, made by the benchmark and handed to the program.

The program's module fixes only the NAMES and SHAPES of the parameter tree
(``jax.eval_shape`` of its ``init``); every value comes from here, from
``--seed``, in one jitted call on the device. The plain forward passes in
this directory read the same tree by the same names.

Rule per leaf, by its last path key: ``kernel`` is normal with variance
1/fan_in (fan_in = product of all but the last axis), the ``policy``
head's kernel scaled by a further 0.01 (a near-uniform initial policy, as
the program's own initialiser gives) ; ``scale`` is ones; ``bias`` zeros.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

POLICY_HEAD_SCALE = 0.01


def _leaf(path, shape_dtype, key):
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    shape, dtype = shape_dtype.shape, shape_dtype.dtype
    last = names[-1]
    if last == "kernel":
        fan_in = math.prod(shape[:-1])
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        if "policy" in names[-2]:
            w = w * POLICY_HEAD_SCALE
        return w.astype(dtype)
    if last == "scale":
        return jnp.ones(shape, dtype)
    if last == "bias":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"weights.py has no rule for parameter leaf {names}")


def make_params(shapes, seed: int):
    """``shapes``: pytree of ShapeDtypeStruct (the program's tree).
    Returns the filled tree; one jitted program."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(p, s, k) for (p, s), k in zip(leaves, keys)])

    # fold the seed in two 31-bit halves: --seed may exceed int32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.jit(build)(key)
