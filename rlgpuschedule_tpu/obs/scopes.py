"""The names a profile of this program is read by, in one place.

Two kinds. **Device scopes** are ``jax.named_scope`` names put on the
jitted program's own layer boundaries by the function that owns each
boundary; they reach the compiled program as metadata only (the HLO
``op_name`` path of every operation traced under them), so the code the
compiler emits is the same with or without them
(tests/test_scopes.py). **Host annotations** are the obs tracer's span
names as they appear in the profiler's trace: ``ANNOTATION_PREFIX`` +
the span name (:mod:`.trace`), and one step annotation per train
iteration.

A reader matches a scope as a path component, so the ``vmap(...)`` and
``while/body`` wrappers jax adds around them do not matter. The tree::

    rollout                 algos.rollout.rollout
      policy_forward        apply_fn + action_dist.sample
      env_step              env.vec_step
        sim_step            sim.core.rl_step
          sim_queue         pending_queue / running_queue
          sim_place         try_place (selected and forced), preempt
          sim_advance       next_event_time, advance_to
          sim_select        the branchless pick, StepInfo
        reward              env.rewards.*
        observe             env._observe: build_obs + action_mask
        auto_reset          env.auto_reset
    advantage               ppo.compute_advantages / a2c's GAE
    update                  ppo.run_ppo_epochs / a2c.run_a2c_update
      shuffle               permutation and minibatch gather
      loss_grad             value_and_grad of the loss
      apply                 clipping and the optimizer step

A token policy (``models.trunk``) names its own layers wherever its
forward pass is traced, so the same subtree hangs under both
``rollout/policy_forward`` and ``update/loss_grad`` (``TRUNK_TREE``)::

    trunk                   models.trunk.TokenTrunk
      trunk_embed           features -> hidden
      trunk_attn            norms, projections, gate, o_proj
        attn_sliding        scores, mask, softmax, values (window + RoPE)
        attn_full           the same on a full-attention layer
      trunk_dense_mlp       a leading dense layer's MLP
      moe_route             router scores, top-k, the sort by expert
      moe_experts           dispatch, the grouped products, combine
      moe_shared            the shared expert
      trunk_pool            final norm, mean over valid tokens

The second family of blocks (``models.trunk.LingConfig``) has a tree of
its own (``LING_TRUNK_TREE``): the names above that it shares, and under
``trunk_attn`` its two kinds of layer in place of the two windows::

      trunk_attn            the input norm and the layer
        attn_kda            a linear-attention layer, projections to o_proj
          kda_conv          the three causal convolutions and their silu
          kda_gates         decays, write strengths, q/k normalisation
          kda_scan          the gated delta rule in chunks (ops.kda)
        attn_mla            a latent-attention layer, projections to o_proj

The third family (``models.trunk.OuroConfig``) applies one stack of dense
layers several times, so its layers hang under the loop
(``OURO_TRUNK_TREE``; an operation under ``trunk_attn`` runs once a layer
APPLICATION, steps x layers a pass)::

    trunk
      trunk_embed
      trunk_loop            the scan over loop steps and all inside it
        trunk_attn          norms, projections, RoPE, o_proj
          attn_full         scores, mask, softmax, values
        trunk_dense_mlp     every layer's MLP
        loop_gate           a step's closing norm, the exit gate, p
      trunk_pool            mean over valid tokens of the last step's output
"""
from __future__ import annotations

import functools

import jax

ROLLOUT = "rollout"
POLICY_FORWARD = "policy_forward"
ENV_STEP = "env_step"
SIM_STEP = "sim_step"
SIM_QUEUE = "sim_queue"
SIM_PLACE = "sim_place"
SIM_ADVANCE = "sim_advance"
SIM_SELECT = "sim_select"
REWARD = "reward"
OBSERVE = "observe"
AUTO_RESET = "auto_reset"
ADVANTAGE = "advantage"
UPDATE = "update"
SHUFFLE = "shuffle"
LOSS_GRAD = "loss_grad"
APPLY = "apply"

TRUNK = "trunk"
TRUNK_EMBED = "trunk_embed"
TRUNK_ATTN = "trunk_attn"
ATTN_SLIDING = "attn_sliding"
ATTN_FULL = "attn_full"
TRUNK_DENSE_MLP = "trunk_dense_mlp"
MOE_ROUTE = "moe_route"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
TRUNK_POOL = "trunk_pool"
ATTN_KDA = "attn_kda"
KDA_CONV = "kda_conv"
KDA_GATES = "kda_gates"
KDA_SCAN = "kda_scan"
ATTN_MLA = "attn_mla"
TRUNK_LOOP = "trunk_loop"
LOOP_GATE = "loop_gate"

# every scope as its path from the program's top, parents first
TREE = (
    (ROLLOUT,),
    (ROLLOUT, POLICY_FORWARD),
    (ROLLOUT, ENV_STEP),
    (ROLLOUT, ENV_STEP, SIM_STEP),
    (ROLLOUT, ENV_STEP, SIM_STEP, SIM_QUEUE),
    (ROLLOUT, ENV_STEP, SIM_STEP, SIM_PLACE),
    (ROLLOUT, ENV_STEP, SIM_STEP, SIM_ADVANCE),
    (ROLLOUT, ENV_STEP, SIM_STEP, SIM_SELECT),
    (ROLLOUT, ENV_STEP, REWARD),
    (ROLLOUT, ENV_STEP, OBSERVE),
    (ROLLOUT, ENV_STEP, AUTO_RESET),
    (ADVANTAGE,),
    (UPDATE,),
    (UPDATE, SHUFFLE),
    (UPDATE, LOSS_GRAD),
    (UPDATE, APPLY),
)

# a token policy's scopes, as paths from wherever its forward is traced
TRUNK_TREE = (
    (TRUNK,),
    (TRUNK, TRUNK_EMBED),
    (TRUNK, TRUNK_ATTN),
    (TRUNK, TRUNK_ATTN, ATTN_SLIDING),
    (TRUNK, TRUNK_ATTN, ATTN_FULL),
    (TRUNK, TRUNK_DENSE_MLP),
    (TRUNK, MOE_ROUTE),
    (TRUNK, MOE_EXPERTS),
    (TRUNK, MOE_SHARED),
    (TRUNK, TRUNK_POOL),
)
# the second family's (module docstring): what it shares, and its layers
LING_TRUNK_TREE = (
    (TRUNK,),
    (TRUNK, TRUNK_EMBED),
    (TRUNK, TRUNK_ATTN),
    (TRUNK, TRUNK_ATTN, ATTN_KDA),
    (TRUNK, TRUNK_ATTN, ATTN_KDA, KDA_CONV),
    (TRUNK, TRUNK_ATTN, ATTN_KDA, KDA_GATES),
    (TRUNK, TRUNK_ATTN, ATTN_KDA, KDA_SCAN),
    (TRUNK, TRUNK_ATTN, ATTN_MLA),
    (TRUNK, TRUNK_DENSE_MLP),
    (TRUNK, MOE_ROUTE),
    (TRUNK, MOE_EXPERTS),
    (TRUNK, MOE_SHARED),
    (TRUNK, TRUNK_POOL),
)
# the third family's: its layers under the loop over steps
OURO_TRUNK_TREE = (
    (TRUNK,),
    (TRUNK, TRUNK_EMBED),
    (TRUNK, TRUNK_LOOP),
    (TRUNK, TRUNK_LOOP, TRUNK_ATTN),
    (TRUNK, TRUNK_LOOP, TRUNK_ATTN, ATTN_FULL),
    (TRUNK, TRUNK_LOOP, TRUNK_DENSE_MLP),
    (TRUNK, TRUNK_LOOP, LOOP_GATE),
    (TRUNK, TRUNK_POOL),
)
TRUNK_PARENTS = ((ROLLOUT, POLICY_FORWARD), (UPDATE, LOSS_GRAD))

# host side: the profiler's trace shows span "step" as "rlsched:step"
ANNOTATION_PREFIX = "rlsched:"
TRAIN_ITERATION = ANNOTATION_PREFIX + "train_iteration"


def scoped(name: str):
    """Decorator: trace the function's body under
    ``jax.named_scope(name)``, a fresh context each call
    (``jax.named_scope`` used as a decorator keeps ONE context object
    for every call, and its saved state is not per thread: the actor
    and an evaluator tracing the same function at once would swap
    name stacks)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
