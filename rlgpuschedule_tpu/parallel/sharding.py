"""Regex-keyed partition-rule sharding engine (L5).

One rule table per model family maps parameter *names* (the '/'-joined
pytree path, flax-style: ``params/params/Dense_0/kernel``) to
``PartitionSpec``s over the unified ``Mesh(pop × data × model)``
(:func:`mesh.make_unified_mesh`). The style is the battle-tested
EasyLM/levanter idiom (SNIPPETS.md [1]/[3]):

- :func:`match_partition_rules` walks any pytree, names each leaf by its
  path, and returns the first-matching rule's spec (``re.search``, order
  matters). Scalars and size-1 leaves short-circuit to ``P()`` —
  optimizer step counters never need a rule. A leaf no rule matches is a
  hard error, so a new parameter cannot silently default to the wrong
  layout. The shipped tables end in an explicit ``(".*", P())``
  replicate catch-all; tests assert each family's params are fully
  covered *before* the catch-all.
- Because optimizer state mirrors parameter paths (``opt_state/1/mu/
  params/Dense_0/kernel``), the same rules shard Adam moments with zero
  extra configuration — that is why matching uses ``re.search`` rather
  than full-path equality.
- :func:`make_shard_and_gather_fns` turns a spec tree into per-leaf
  place/fetch callables for checkpoint restore paths that must not
  materialize the full tree on one device.

Constraint helpers: jax 0.4 has no ambient-mesh context for
``with_sharding_constraint``, so :func:`bind_mesh` wraps a step function
and installs the mesh for the duration of its *trace*; :func:`constrain`
is then an identity outside any bound mesh and a
``lax.with_sharding_constraint`` inside one. Library code (e.g. the
rollout's trajectory stack) calls ``constrain`` unconditionally and
mesh-free callers pay nothing.

Elastic restore: :func:`shrink_env_rows_by_rule` replaces
``dp.shrink_env_rows``'s leading-dim heuristic — leaves are shrunk iff
their *rule* puts them on the data axis, so a PRNG key whose length
happens to equal ``old_n_envs`` can no longer be mis-sliced (the caveat
documented on the old path is fixed by construction).
"""
from __future__ import annotations

import hashlib
import re
import threading
from contextlib import contextmanager
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, MODEL_AXIS, POP_AXIS, data_shard_slices

# A rule table: ordered (regex, PartitionSpec) pairs, first re.search
# match wins. Specs name axes of the unified mesh.
Rules = list[tuple[str, P]]


# --------------------------------------------------------------------------
# Named tree walking
# --------------------------------------------------------------------------

def _key_name(k) -> str:
    """One path entry -> its bare name (dict key, attr name, or index)."""
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "name"):
        return str(k.name)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any,
                   sep: str = "/") -> Any:
    """``jax.tree.map`` with the leaf's '/'-joined path as first arg."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [fn(sep.join(_key_name(k) for k in path), leaf)
           for path, leaf in paths_leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_leaf_names(tree: Any, sep: str = "/") -> list[str]:
    """The '/'-joined path of every leaf, in flatten order."""
    paths_leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [sep.join(_key_name(k) for k in path) for path, _ in paths_leaves]


# --------------------------------------------------------------------------
# Rule matching
# --------------------------------------------------------------------------

def match_rule(rules: Rules, name: str) -> P:
    """First rule whose regex ``re.search``-matches ``name``. Raises if
    none does — a silent default is how a new param ends up replicated
    when it should be sharded (or vice versa)."""
    for pattern, spec in rules:
        if re.search(pattern, name):
            return spec
    raise ValueError(f"Partition rule not found for param: {name!r}")


def match_partition_rules(rules: Rules, tree: Any) -> Any:
    """Resolve a PartitionSpec for every leaf of ``tree`` by name.
    Scalars and size-1 leaves (step counters, EMA scalars) get ``P()``
    without consulting the table."""
    def get_spec(name: str, leaf: Any) -> P:
        ndim = getattr(leaf, "ndim", np.ndim(leaf))
        size = getattr(leaf, "size", np.size(leaf))
        if ndim == 0 or size == 1:
            return P()
        return match_rule(rules, name)
    return named_tree_map(get_spec, tree)


def prune_spec(spec: P, mesh: Mesh) -> P:
    """Drop axis names ``mesh`` does not carry. The rule tables name all
    three unified axes; a caller-supplied legacy mesh (e.g. a bare
    pop x data test mesh) then gets those dims replicated instead of a
    hard "resource axis not found" error — on such a mesh that is the
    same layout the wholesale pre-rule shardings produced."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.shape)
            if not kept:
                return None
            return kept[0] if len(kept) == 1 else kept
        return entry if entry in mesh.shape else None
    kept = [keep(e) for e in spec]
    while kept and kept[-1] is None:   # P('pop', None, None) == P('pop')
        kept.pop()
    return P(*kept)


def tree_shardings(tree: Any, rules: Rules, mesh: Mesh) -> Any:
    """Rule-resolved ``NamedSharding`` tree for ``tree`` on ``mesh``."""
    specs = match_partition_rules(rules, tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, prune_spec(s, mesh)),
                        specs)


def rule_table_hash(rules: Rules) -> str:
    """Stable short fingerprint of a rule table — recorded with a run so
    two results are comparable only when their layouts were."""
    text = "|".join(f"{pat}=>{tuple(spec)}" for pat, spec in rules)
    return hashlib.sha256(text.encode()).hexdigest()[:12]


# --------------------------------------------------------------------------
# Per-model-family rule tables
# --------------------------------------------------------------------------
# Head kernels [hidden, n_actions] shard the *input* dim on model (the
# output dim is tiny: n_actions or 1); encoder Dense kernels [in, out]
# shard the output dim (megatron column split); Conv kernels [H, W, Cin,
# Cout] shard output channels. Biases / LayerNorm scales are small —
# replicate. On a model axis of size 1 all of this degrades to exact
# replication (the bit-identity tests pin that).

_HEADS = r"(^|/)((slot_|preempt_|noop_|top_|pod_)?policy|value)/kernel$"

FLAT_RULES: Rules = [
    (_HEADS, P(MODEL_AXIS, None)),
    (r"Dense_\d+/kernel$", P(None, MODEL_AXIS)),
    (r"LayerNorm_\d+/(scale|bias)$", P()),
    (r"(^|/)bias$", P()),
    (r".*", P()),
]

GRID_RULES: Rules = [
    (_HEADS, P(MODEL_AXIS, None)),
    (r"Conv_\d+/kernel$", P(None, None, None, MODEL_AXIS)),
    (r"Dense_\d+/kernel$", P(None, MODEL_AXIS)),
    (r"LayerNorm_\d+/(scale|bias)$", P()),
    (r"(^|/)bias$", P()),
    (r".*", P()),
]

# GNN encoder is Dense+LayerNorm message passing; hier is two MLP trunks
# + three Dense heads — both are the flat table's patterns.
GRAPH_RULES: Rules = FLAT_RULES
HIER_RULES: Rules = FLAT_RULES

# Token trunk (models.trunk): the held experts' three kernels a layer are
# [d, count*f] / [f, count*d], experts contiguous along the LAST axis, so
# that axis on ``model`` gives each shard whole experts (expert parallel);
# everything else (attention of every kind: the afmoe blocks' GQA, the
# ling blocks' KDA projections, convolutions, decay leaves and MLA
# projections; router, shared expert, norms, heads) is replicated, as each
# chip of the stated deployment holds it whole.
TOKENS_RULES: Rules = [
    (r"experts_(gate|up|down)/kernel$", P(None, MODEL_AXIS)),
    (r"(^|/)kernel$", P()),
    (r"(^|/)(scale|bias)$", P()),
    (r".*", P()),
]

RULE_TABLES: dict[str, Rules] = {
    "flat": FLAT_RULES,
    "grid": GRID_RULES,
    "graph": GRAPH_RULES,
    "hier": HIER_RULES,
    "tokens": TOKENS_RULES,
}


def rules_for(cfg) -> Rules:
    """The rule table for an ExperimentConfig's model family."""
    if getattr(cfg, "n_pods", 1) > 1:
        return RULE_TABLES["hier"]
    return RULE_TABLES[cfg.obs_kind]


# --------------------------------------------------------------------------
# Placement (subsumes dp.put_global)
# --------------------------------------------------------------------------

def put_global(tree: Any, sharding: NamedSharding) -> Any:
    """``device_put`` every leaf of ``tree`` onto ``sharding``, including
    in MULTI-CONTROLLER runs. Plain ``jax.device_put`` refuses a host
    value destined for a sharding that spans non-addressable devices (the
    multihost mesh); there each process instead contributes its
    addressable shards of its local copy via
    ``jax.make_array_from_process_local_data``. Leaves that are already
    global (non-fully-addressable) jax.Arrays — e.g. traces assembled by
    ``multihost.global_traces`` — pass through untouched, since their
    shards cannot be re-placed host-side."""
    def put(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return x
        if sharding.is_fully_addressable:
            return jax.device_put(x, sharding)
        arr = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sharding, arr, arr.shape)

    return jax.tree.map(put, tree)


def put_tree(tree: Any, shardings: Any) -> Any:
    """Per-leaf :func:`put_global` against a matching tree of
    ``NamedSharding``s (what :func:`tree_shardings` returns)."""
    return jax.tree.map(put_global, tree, shardings)


def make_shard_and_gather_fns(specs: Any, mesh: Mesh
                              ) -> tuple[Any, Any]:
    """Per-leaf (shard_fn, gather_fn) trees for a spec tree: ``shard_fn``
    places a host leaf on its rule-resolved sharding (multihost-safe);
    ``gather_fn`` fetches a placed leaf back to one host numpy array.
    Restore paths apply shard_fns leaf-by-leaf so a big tree never has to
    exist fully replicated on one device."""
    def make_shard(spec):
        sh = NamedSharding(mesh, spec)
        return lambda x: jax.tree.leaves(put_global(x, sh))[0]

    def make_gather(_spec):
        return lambda x: np.asarray(jax.device_get(x))  # jsan: disable=host-sync -- gather_fns ARE the host materialization step (checkpoint save path), never traced

    shard_fns = jax.tree.map(make_shard, specs)
    gather_fns = jax.tree.map(make_gather, specs)
    return shard_fns, gather_fns


# --------------------------------------------------------------------------
# with_sharding_constraint helpers (trace-scoped ambient mesh)
# --------------------------------------------------------------------------

_ACTIVE = threading.local()


def active_mesh() -> Mesh | None:
    """The mesh bound by the innermost :func:`use_mesh`/:func:`bind_mesh`
    on this thread, or None."""
    return getattr(_ACTIVE, "mesh", None)


@contextmanager
def use_mesh(mesh: Mesh | None):
    prev = active_mesh()
    _ACTIVE.mesh = mesh
    try:
        yield mesh
    finally:
        _ACTIVE.mesh = prev


def bind_mesh(fn: Callable, mesh: Mesh) -> Callable:
    """Wrap ``fn`` so the mesh is active while it runs. Under ``jax.jit``
    the wrapper body executes at TRACE time, which is exactly when
    :func:`constrain` needs the mesh — so only steps built against a mesh
    get constraints baked into their jaxpr, deterministically."""
    def bound(*args, **kwargs):
        with use_mesh(mesh):
            return fn(*args, **kwargs)
    return bound


def constrain(x: Any, *axes) -> Any:
    """``with_sharding_constraint`` against the active mesh, or identity
    when no mesh is bound (single-device and legacy dp paths trace the
    very same code with zero overhead). ``axes`` are PartitionSpec
    entries for the leading dims; trailing dims are unconstrained."""
    mesh = active_mesh()
    if mesh is None:
        return x
    sh = NamedSharding(mesh, P(*axes))
    return jax.lax.with_sharding_constraint(x, sh)


def constrain_tree(tree: Any, *axes) -> Any:
    """:func:`constrain` every leaf of a pytree with the same spec."""
    return jax.tree.map(lambda x: constrain(x, *axes), tree)


# --------------------------------------------------------------------------
# Elastic restore by rule (subsumes dp.shrink_env_rows)
# --------------------------------------------------------------------------

# What lives in an elastic checkpoint's "extra" tree: rollout carry +
# trajectory leaves are env-batched; PRNG keys are replicated state and
# MUST NOT be row-sliced — keyed by NAME, not by a leading-dim
# coincidence.
ELASTIC_EXTRA_RULES: Rules = [
    (r"(^|/)keys?$", P()),
    (r".*", P(DATA_AXIS)),
]


def shrink_env_rows_by_rule(tree: Any, rules: Rules, *, old_n_envs: int,
                            old_world: int, surviving_ranks) -> Any:
    """Shrink-to-fit an env-batched pytree to the surviving data shards,
    deciding per-leaf by RULE: a leaf is sliced iff its matched spec puts
    the leading dim on the data axis AND the leading dim equals
    ``old_n_envs`` (geometry sanity; replicated-by-rule leaves pass
    through whole regardless of shape). Row blocks follow
    ``mesh.data_shard_slices`` — the same contiguous layout
    ``env_sharded`` places, which is what makes "rows that lived on
    surviving ranks" well-defined. Host-side numpy; the caller re-places
    the shrunk tree on the new mesh (:func:`put_global`)."""
    surv = sorted(set(int(r) for r in surviving_ranks))
    if not surv:
        raise ValueError("shrink_env_rows_by_rule: no surviving ranks")
    if surv[0] < 0 or surv[-1] >= old_world:
        raise ValueError(f"surviving_ranks {surv} outside the saved world "
                         f"range [0, {old_world})")
    slices = data_shard_slices(old_n_envs, old_world)
    specs = match_partition_rules(rules, tree)

    def shrink(spec, x):
        arr = np.asarray(x)
        on_data = len(spec) > 0 and spec[0] == DATA_AXIS
        if on_data and arr.ndim >= 1 and arr.shape[0] == old_n_envs:
            return np.concatenate([arr[slices[r]] for r in surv], axis=0)
        return arr

    return jax.tree.map(shrink, specs, tree)
