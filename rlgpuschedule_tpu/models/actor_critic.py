"""Actor-critic heads (L3).

Capability parity: SURVEY.md §2 "Actor/critic heads" — action logits over
the scheduling action space (job-select × placement + no-op) and a value
head, with infeasible actions masked to -inf before sampling (SURVEY.md §7
step 4 "action masking via -inf logits").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import flax.linen as nn

from .encoders import MLPEncoder, CNNEncoder, GNNEncoder
from .trunk import TRUNKS, TokenTrunk

NEG_INF = -1e9


def mask_logits(logits: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.where(mask, logits, NEG_INF)


class ActorCritic(nn.Module):
    """Pooled-trunk actor-critic (MLP and CNN encoders).

    ``apply(params, obs, mask) -> (masked_logits f32, value f32)``."""
    encoder: nn.Module
    n_actions: int

    @nn.compact
    def __call__(self, obs: jax.Array, mask: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
        h = self.encoder(obs)
        logits = nn.Dense(self.n_actions, dtype=jnp.float32,
                          kernel_init=nn.initializers.orthogonal(0.01),
                          name="policy")(h)
        value = nn.Dense(1, dtype=jnp.float32,
                         kernel_init=nn.initializers.orthogonal(1.0),
                         name="value")(h)
        return mask_logits(logits.astype(jnp.float32), mask), value.squeeze(-1)


class GNNActorCritic(nn.Module):
    """Graph actor-critic (config 4): per-queue-slot logits come from each
    slot's own node embedding (slots are graph nodes N..N+K-1), so the
    policy is equivariant over queue slots; with ``n_placements`` > 1 each
    slot head emits pack/spread logits (the factored gang-scheduling +
    placement action space). With ``preempt_len`` > 0, per-running-slot
    preempt logits come from the running-slot nodes N+K..N+K+R-1 the same
    way. The no-op logit and value come from the pooled graph embedding."""
    encoder: GNNEncoder
    n_cluster_nodes: int
    queue_len: int
    n_placements: int = 1
    preempt_len: int = 0

    @nn.compact
    def __call__(self, obs: jax.Array, adj: jax.Array, mask: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
        h = self.encoder(obs, adj)                       # [..., V, D]
        pooled = h.mean(axis=-2)
        slots = h[..., self.n_cluster_nodes:
                  self.n_cluster_nodes + self.queue_len, :]  # [..., K, D]
        slot_logits = nn.Dense(self.n_placements, dtype=jnp.float32,
                               kernel_init=nn.initializers.orthogonal(0.01),
                               name="slot_policy")(slots)
        parts = [slot_logits.reshape(*slot_logits.shape[:-2], -1)]  # [..., K*P]
        if self.preempt_len:
            run0 = self.n_cluster_nodes + self.queue_len
            runs = h[..., run0:run0 + self.preempt_len, :]   # [..., R, D]
            pre = nn.Dense(1, dtype=jnp.float32,
                           kernel_init=nn.initializers.orthogonal(0.01),
                           name="preempt_policy")(runs)
            parts.append(pre.squeeze(-1))                    # [..., R]
        noop = nn.Dense(1, dtype=jnp.float32,
                        kernel_init=nn.initializers.orthogonal(0.01),
                        name="noop_policy")(pooled)
        parts.append(noop)
        logits = jnp.concatenate(parts, axis=-1)
        value = nn.Dense(1, dtype=jnp.float32,
                         kernel_init=nn.initializers.orthogonal(1.0),
                         name="value")(pooled)
        return mask_logits(logits.astype(jnp.float32), mask), value.squeeze(-1)


def make_policy(obs_kind: str, n_actions: int, *, n_cluster_nodes: int = 0,
                queue_len: int = 0, n_placements: int = 1,
                preempt_len: int = 0, trunk: str = "published",
                dtype=jnp.bfloat16) -> nn.Module:
    """Encoder-selection factory matching EnvParams.obs_kind. ``trunk``
    names the token trunk's family and sizes (``models.trunk.TRUNKS``)."""
    if obs_kind == "flat":
        return ActorCritic(MLPEncoder(dtype=dtype), n_actions)
    if obs_kind == "grid":
        return ActorCritic(CNNEncoder(dtype=dtype), n_actions)
    if obs_kind == "graph":
        return GNNActorCritic(GNNEncoder(dtype=dtype), n_cluster_nodes,
                              queue_len, n_placements, preempt_len)
    if obs_kind == "tokens":
        return ActorCritic(TokenTrunk(TRUNKS[trunk], dtype=dtype), n_actions)
    raise ValueError(f"unknown obs_kind {obs_kind!r} (have flat, grid, "
                     f"graph, tokens)")
