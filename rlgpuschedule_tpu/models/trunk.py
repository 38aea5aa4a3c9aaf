"""Token trunk (L3): transformer blocks over one token per cluster node
and per job of the window (``env.obs.token_obs``), of three public
families. ``TRUNKS`` names each whole set of sizes AND its family:
``published`` / ``tiny`` the ``afmoe`` blocks below (:class:`TrunkConfig`),
``ling`` / ``ling-tiny`` the linear-attention blocks further down
(:class:`LingConfig`), ``ouro`` / ``ouro-tiny`` the looped dense blocks
after them (:class:`OuroConfig`). **What they share:** the token
observation, :class:`TokenTrunk` (the embedding's linear map, blocks
rematerialised and taken ``ROW_BLOCK`` rows at a time, the final norm and
the mean over valid tokens), :class:`RMSNorm`, :class:`GatedMLP`,
:func:`attend` / ``ops.attention.blocked_attend`` for a softmax score
product, the counters and ``ActorCritic``'s heads; the two sparse families
also :class:`ExpertLayer` with :func:`routed_experts`, :class:`SortedRows`
and :func:`sum_rows` (an expert layer told which experts it holds);
``afmoe`` and ``ouro`` also :class:`Block` and :class:`Attention`.

**The ``afmoe`` block** is the public ``afmoe`` family's (arcee-ai
Trinity; widths and ``layer_types`` as the family's ``config.json`` keys
them, the rest after ``transformers``' ``models/afmoe``). Per layer
``i``, ``x`` ``[T, d]``::

    h = x + post_attn_norm(Attn_i(input_norm(x)))
    y = h + post_mlp_norm(MLP_i(pre_mlp_norm(h)))          all RMSNorm

``Attn``: no-bias q/k/v projections to ``Hq``/``Hkv``/``Hkv`` heads of
``D``; RMSNorm over the head dim on q and k; RoPE (position = token
index) on q and k ONLY where ``layer_types[i]`` is ``sliding_attention``;
causal mask, cut to the last ``sliding_window`` positions on sliding
layers; keys of ``valid = 0`` tokens masked; softmax(q k^T / sqrt(D)) v
with each KV head serving ``Hq/Hkv`` query heads (two lowerings of it,
below); the result times ``sigmoid(gate_proj(input))``; ``o_proj``.
``MLP`` of the leading dense layers: ``down(silu(gate(x)) * up(x))``.
``MLP`` of the rest:
``shared(x) + sum_e w_e expert_e(x)`` with ``s = sigmoid(router(x))`` in
float32 over ALL published experts, selection = top-k of ``s +
expert_bias`` (a ``bias`` leaf no gradient reaches: zeros), ``w`` = the
selected ``s`` over their sum over all k selected (``route_norm``) times
``route_scale``. After the last layer: final RMSNorm, mean over valid
tokens. Where a policy departs from the language model: no token ids, so
a linear map of each token's features (times sqrt(d), as the family's
``mup_enabled`` scales its embedding) stands for the embedding, and
``ActorCritic``'s heads for the output head.

**The ``ling`` block** (inclusionAI Ling-3.0-flash's language model: its
``config.json`` keys; what they do not settle follows Kimi Linear,
arXiv:2510.26692, flash-linear-attention's ``kda`` and ``bailing_moe_v2``,
and is listed under ``assumed`` in
``benchmark/configs/philly512-ling.json``). Pre-norm, ``u = norm(x)``::

    h = x + Attn_i(input_norm(x));  y = h + MLP_i(pre_mlp_norm(h))

Layers ``i % layer_group_size != layer_group_size - 1`` are **KDA**
(:class:`KDA`): ``q~, k~, v = silu(conv4(u W))`` for three projections to
``H`` heads of ``D`` (``conv4``: depthwise, causal, no bias, over tokens);
``q = q~ / |q~| / sqrt(D)``, ``k = k~ / |k~|`` a head; a log-decay a
channel ``g = kda_lower_bound * sigmoid(exp(A_log) * (u W_f + dt_bias))``
and a write strength a head ``beta = sigmoid(u W_beta)``; the gated delta
rule over a ``[D, D]`` state a head (``ops.kda``: in chunks, never token by
token; as Pallas kernels where ``ops.kda.delta_rule_path`` says a build
gets them, plain ``jax.numpy`` elsewhere, and ``kda_kernel_layers`` says
which ran); ``(RMSNorm_head(o) * sigmoid(u W_g)) W_o``. No RoPE. A token
with ``valid = 0`` feeds zeros to the convolutions and leaves the state as
it was (``beta`` 0, ``g`` 0): the policy's departure, like the pool. The
last layer of a period is **MLA** (:class:`MLA`): ``[c, r] = u W_kva``;
``[k_nope, v]_h = RMSNorm(c) W_kvb``; one rope key ``RoPE(RMSNorm(r))`` a
token for all heads; ``[q_nope, q_rope]_h = RMSNorm_head(u W_q)``, RoPE on
``q_rope``; causal softmax of ``q . [k_nope, k_rope] / sqrt(dn + dr)``
over valid keys; each head's output times one scalar ``sigmoid(u
W_gate)_h``; ``W_o``. Its score product is the shared one: q and k have
``dn + dr`` = 192 channels and v 128, so the kernel path zero-pads q and k
to 256 (exact). The expert layers choose **groups before experts**
(:func:`choose_experts`) and have a shared expert of its own width.

**The ``ouro`` block** (ByteDance Ouro's looped language model: its
``config.json`` keys; what they do not settle follows "Scaling Latent
Reasoning via Looped Language Models" and is listed under ``assumed`` in
``benchmark/configs/philly512-ouro.json``). ONE stack of ``L`` layers is
applied ``R = total_ut_steps`` times to its own output, ``x^(0)`` the
embedding::

    for t = 1..R:  z = x^(t-1);  for i = 0..L-1: z = Block_i(z)   the SAME blocks
                   x^(t) = final_norm(z)          closes every step, feeds the next
                   lambda_t = sigmoid(x^(t) w_g + b_g)            a token
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j);  p_R = the rest

``Block_i`` has the ``afmoe`` block's sandwich form above (and is the same
class), every layer's MLP dense; its ``Attn`` is plain multi-head (as many
KV heads as query heads), with RoPE on q and k in EVERY layer and no q/k
norm, no output gate and no window. The exit rule hands on ``x^(t*)``,
``t*`` the first step whose cumulated ``p`` reaches ``early_exit_threshold``;
at the published threshold 1 that is ``R``, every step runs, and the pool
reads ``x^(R)`` (the rule for a lower threshold is the plain reference's
``exit_step``; nothing here regroups a batch between steps yet: ROADMAP
Queue 2 A). The gate and ``p`` are computed at every
step and feed counters only (the family trains the gate by a loss over each
step's vocabulary logits, which a policy does not have), so no gradient
reaches ``exit_gate``'s two leaves. **One compiled body:** the steps are a
scan (``nn.scan``, parameters broadcast), so the parameter tree holds the
``L`` layers once, the program holds their bodies once whatever ``R`` is,
and a leaf's gradient is the sum over its ``R`` uses.

**Two lowerings of the score product**, chosen by
:func:`attention_path` from what the build can observe and from nothing a
user sets. On a TPU, at a head size the kernel's tiles take, in a program
whose every device sees whole rows (one device, or a shard inside
``parallel.dp.shard_map_train``): ``ops.attention.blocked_attend``, Pallas
kernels that keep a tile of scores in VMEM, forward and backward, so no
``[rows, heads, T, T]`` array exists. Everywhere else (another backend, the
``tiny`` trunk's head size, a GSPMD build over a mesh, where XLA partitions
no custom call): :func:`attend`, the mathematics written out, and the
kernel's oracle in the tests. The counters say which ran.

**The chip's share.** An expert layer is TOLD which experts it holds
(``experts_held = (first, count)``), routes over all ``num_experts``
and adds its own experts' terms only; nothing stands in for the absent
ones (``(0, num_experts)`` is the whole layer). Token-choice and
dropless: every assignment to a held expert is computed. Assignments are
sorted by expert (non-held ones last) and each projection is ONE grouped
matrix product (``jax.lax.ragged_dot``: on a TPU XLA's own grouped-matmul
kernel, whose work follows the group sizes). **What is sized for what:**
the sorted ORDER has an entry for every assignment (``tokens x k``
integers); the BUFFER of rows the products run on is sized for the
assignments that are held: :func:`routed_experts` works on the first
``L`` entries of the order, at ``L`` = :func:`short_rows` (two choices a
token held here) when the held count fits that, and at ``L = tokens x
k``, the worst case (every token's k choices held here), when it does
not, one routine at two static lengths behind a ``jax.lax.cond`` on the
held count. Each row's result times its router weight is summed into its
token (:func:`sum_rows`: a token's own terms and exact zeros, at either
length). So ``moe_dropped_assignments`` is 0 whatever the router does, a
row's result cannot depend on which rows share its batch, and what a chip
with a small share of the experts pays follows what it holds; a chip
whose experts get a deployment's load takes the long buffer every time.
The counters say which ran (``moe_short_path_share``).

**Parameter leaves** end in ``kernel``, ``scale`` or ``bias``. The held
experts' weights are three leaves a layer, laid out so that a kernel's
fan-in is the expert's own: ``experts_gate``/``experts_up``
``[d, count*f]`` and ``experts_down`` ``[f, count*d]``, viewed as
``[count, d, f]``/``[count, f, d]`` in the layer; their last axis is the
one ``parallel.sharding`` puts on the ``model`` mesh axis.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import scopes
from ..ops import kda

SLIDING, FULL = "sliding_attention", "full_attention"
COUNTERS = "counters"       # the flax collection the expert layers sow
# Rows of a batch that pass a block together: their activations (the
# sorted expert buffers, the projections; on the plain path the float32
# attention scores too, which the kernel path never holds) are what is
# live at once. Memory, not mathematics: rows do not see each other. At
# the published widths on the plain path 4 rows leave the update 6.5 GB
# of temporaries (2 rows 5.8, all 16 of a minibatch 13.1; compiler
# estimates, PERF.md section 4): the most that fits one chip beside TWO
# train states (a traced benchmark run keeps a copy), and half as many
# passes of a block, so half as many device operations in an iteration,
# as 2 rows.
ROW_BLOCK = 4
KERNEL, PLAIN = "kernel", "plain"       # attention_path's two answers


@dataclasses.dataclass(frozen=True)
class TrunkConfig:
    """One trunk: the defaults are the published widths (Trinity-Mini's
    ``config.json``) at this repo's cut: the first ``num_hidden_layers``
    of ``layer_types`` (one leading dense layer, then one whole 3 : 1
    period), experts 0-7 of 128 held (one chip of 16)."""
    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    route_norm: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_dense_layers: int = 1
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL, SLIDING)
    experts_held: tuple[int, int] = (0, 8)      # (first, count)

    family = "afmoe"
    loop_steps = 1              # the layers run once a pass
    kda_layers = 0
    # what :class:`Attention` does for this family beside the products
    qk_norm = attn_gate = True
    rope_full = False           # RoPE on sliding layers only

    def __post_init__(self):
        _check_experts(self)
        _check_heads(self)
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"unknown layer type in {self.layer_types}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def shared_intermediate_size(self) -> int:
        return self.moe_intermediate_size

    @property
    def embed_scale(self) -> float:     # as ``mup_enabled`` scales it
        return math.sqrt(self.hidden_size)


def _check_heads(c) -> None:
    if c.num_attention_heads % c.num_key_value_heads:
        raise ValueError("query heads must be a multiple of KV heads")


def _check_experts(c) -> None:
    first, count = c.experts_held
    if not (0 <= first and count >= 1 and first + count <= c.num_experts):
        raise ValueError(f"experts_held={c.experts_held} is not a "
                         f"range of the {c.num_experts} experts")
    groups, kept = expert_groups(c)
    if c.num_experts % groups or not 1 <= kept <= groups:
        raise ValueError(f"{kept} of {groups} groups do not divide "
                         f"{c.num_experts} experts")
    if kept * (c.num_experts // groups) < c.num_experts_per_tok:
        raise ValueError("the groups kept hold fewer experts than a token "
                         "chooses")


def expert_groups(c) -> tuple[int, int]:
    """``(n_group, topk_group)`` of a trunk's router; a family that states
    neither (``afmoe``) has one group: a flat top-k."""
    return getattr(c, "n_group", 1), getattr(c, "topk_group", 1)


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """The second family (module docstring): the defaults are
    Ling-3.0-flash's published widths at this repo's cut: one whole period
    (five KDA layers, one MLA layer), the first of them dense, experts 0-7
    of 512 held (one chip of 64). Field names are ``TrunkConfig``'s where
    the two mean the same; the file
    ``benchmark/configs/philly512-ling.json`` has the source's keys."""
    hidden_size: int = 2560
    num_attention_heads: int = 32
    head_dim: int = 128                 # KDA's q, k and v
    kv_lora_rank: int = 512             # MLA
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    route_scale: float = 2.5            # routed_scaling_factor
    route_norm: bool = True             # norm_topk_prob
    rope_theta: float = 6000000.0
    rms_norm_eps: float = 1e-6
    num_dense_layers: int = 1           # first_k_dense_replace, cut
    num_hidden_layers: int = 6
    layer_group_size: int = 6
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    experts_held: tuple[int, int] = (0, 8)

    family = "ling"
    loop_steps = 1
    embed_scale = 1.0

    def __post_init__(self):
        _check_experts(self)
        if not 0 <= -self.kda_lower_bound * kda.SUB <= kda.MAX_LOG_DECAY:
            raise ValueError(
                f"kda_lower_bound {self.kda_lower_bound} lets {kda.SUB} "
                f"tokens decay by more than exp({kda.MAX_LOG_DECAY}): "
                f"ops.kda's sub-block products would overflow")

    def is_mla(self, layer: int) -> bool:
        return layer % self.layer_group_size == self.layer_group_size - 1

    @property
    def kda_layers(self) -> int:
        return sum(not self.is_mla(i) for i in range(self.num_hidden_layers))


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The third family (module docstring): the defaults are Ouro-2.6B's
    published widths and loop count at this repo's cut: the first 8 of
    its 48 layers, stage 0 of a six-stage pipeline, looped four times.
    Field names are ``TrunkConfig``'s where the two mean the same (its
    :class:`Block` and :class:`Attention` serve both); the file
    ``benchmark/configs/philly512-ouro.json`` has the source's keys."""
    hidden_size: int = 2048
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    num_hidden_layers: int = 8
    total_ut_steps: int = 4

    family = "ouro"
    embed_scale = 1.0
    qk_norm = attn_gate = False
    rope_full = True            # RoPE on every layer
    sliding_window = None
    kda_layers = 0
    n_group = topk_group = 0    # no router

    def __post_init__(self):
        _check_heads(self)
        if self.total_ut_steps < 1:
            raise ValueError("a looped trunk takes at least one step")

    @property
    def loop_steps(self) -> int:
        return self.total_ut_steps

    @property
    def layer_types(self) -> tuple[str, ...]:
        return (FULL,) * self.num_hidden_layers

    @property
    def num_dense_layers(self) -> int:          # every layer's MLP
        return self.num_hidden_layers


TrunkConfigs = TrunkConfig | LingConfig | OuroConfig

TRUNKS: dict[str, TrunkConfigs] = {
    "published": TrunkConfig(),
    # the CPU tests' and rehearsals' shape: the window (8) is shorter
    # than any observation, so the sliding mask bites
    "tiny": TrunkConfig(hidden_size=64, num_attention_heads=2,
                        num_key_value_heads=1, head_dim=32,
                        sliding_window=8, intermediate_size=96,
                        moe_intermediate_size=32, num_experts=8,
                        num_experts_per_tok=2, experts_held=(0, 2)),
    "ling": LingConfig(),
    # two periods of three, so that both kinds of layer come twice; 16
    # experts in 4 groups of 4, 2 groups kept; the chunk (8) is shorter
    # than any observation, so the state crosses chunks
    "ling-tiny": LingConfig(
        hidden_size=64, num_attention_heads=2, head_dim=32, kv_lora_rank=16,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        intermediate_size=96, moe_intermediate_size=32,
        shared_intermediate_size=24, num_experts=16, num_experts_per_tok=2,
        n_group=4, topk_group=2, num_hidden_layers=6, layer_group_size=3,
        kda_chunk=8, experts_held=(0, 4)),
    "ouro": OuroConfig(),
    # two layers looped three times: neither count is 1 and they differ,
    # so a test can tell a step from a layer
    "ouro-tiny": OuroConfig(hidden_size=32, num_attention_heads=2,
                            num_key_value_heads=2, head_dim=16,
                            intermediate_size=64, num_hidden_layers=2,
                            total_ut_steps=3),
}


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array, gain: float = 1.0) -> jax.Array:
        """``gain``: a constant factor applied in float32, before the one
        cast to ``dtype``."""
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        y = y * scale
        return (y if gain == 1.0 else y * gain).astype(self.dtype)


class Kernel(nn.Module):
    """One ``kernel`` leaf of the given shape, variance 1 / shape[0]."""
    shape: tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape, jnp.float32)


def rope(x: jax.Array, theta: float, gain: float = 1.0) -> jax.Array:
    """Rotary embedding over ``x[..., T, H, D]``, position = token index,
    halves rotated (the ``rotate_half`` convention), in float32; times
    the constant ``gain`` there, before the one cast back."""
    T, D = x.shape[-3], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    y = x32 * cos + rot * sin
    return (y if gain == 1.0 else y * gain).astype(x.dtype)


def attention_mask(valid: jax.Array, window: int | None) -> jax.Array:
    """bool[..., T, T]: query q sees key k iff k <= q, q - k < window (on
    a sliding layer) and key k is a valid token."""
    T = valid.shape[-1]
    q = jnp.arange(T)[:, None]
    k = jnp.arange(T)[None, :]
    seen = k <= q
    if window is not None:
        seen &= (q - k) < window
    return seen & valid[..., None, :]


def attend(q, k, v, valid, window):
    """q ``[b, T, Hkv, G, D]``, k/v ``[b, T, Hkv, D]``, valid ``[b, T]``
    -> ``[b, T, Hkv, G, D]``; scores and softmax in float32."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(q.shape[-1]))
    mask = attention_mask(valid, window)[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def attention_path(backend: str, head_dim: int, mesh_bound: bool) -> str:
    """Which lowering of the score product a build gets (module
    docstring): ``KERNEL`` iff the backend is a TPU, the head size is a
    multiple of the 128 lanes the kernel's tiles are made of, and the
    program is not one GSPMD partitions over a mesh (``mesh_bound``: the
    step is traced under ``parallel.sharding.bind_mesh``; a Mosaic
    custom call is not partitioned for us). Any T fits: the kernel's
    wrapper pads to whole tiles."""
    fits = backend == "tpu" and head_dim % 128 == 0 and not mesh_bound
    return KERNEL if fits else PLAIN


class Attention(nn.Module):
    """Softmax attention of the ``afmoe`` and ``ouro`` blocks (module
    docstring); the family's class attributes say whether q and k are
    normed, the output gated, and full layers rotated."""
    cfg: "TrunkConfig | OuroConfig"
    sliding: bool
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array, valid: jax.Array) -> jax.Array:
        from ..parallel.sharding import active_mesh
        c = self.cfg
        B, T, _ = x.shape
        Hq, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        window = c.sliding_window if self.sliding else None
        kernel = attention_path(jax.default_backend(), D,
                                active_mesh() is not None) == KERNEL
        # the kernel takes no scale: 1/sqrt(D) goes onto q inside q_norm
        # (or, in a family without one, inside rope), where q is still
        # float32, so that it costs q no rounding of its own (rope is
        # linear); attend scales the scores itself
        pre = 1.0 / math.sqrt(D) if kernel else 1.0
        proj = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                        name=name)(x)
        q = proj(Hq * D, "q_proj").reshape(B, T, Hq, D)
        k = proj(Hkv * D, "k_proj").reshape(B, T, Hkv, D)
        v = proj(Hkv * D, "v_proj").reshape(B, T, Hkv, D)
        if c.attn_gate:
            gate = proj(Hq * D, "gate_proj")
        if c.qk_norm:
            q = RMSNorm(c.rms_norm_eps, self.dtype, name="q_norm")(q, pre)
            k = RMSNorm(c.rms_norm_eps, self.dtype, name="k_norm")(k)
            pre = 1.0
        if self.sliding or c.rope_full:
            q, k = rope(q, c.rope_theta, pre), rope(k, c.rope_theta)
        q = q.reshape(B, T, Hkv, Hq // Hkv, D)
        tiles = 0.0
        with jax.named_scope(scopes.ATTN_SLIDING if self.sliding
                             else scopes.ATTN_FULL):
            if kernel:
                from ..ops import attention     # Pallas: this path only
                out = attention.blocked_attend(q, k, v, valid, window)
                tiles = attention.tiles_computed_share(T, window)
            else:
                out = attend(q, k, v, valid, window)
        if not self.is_initializing():      # init's tree is params only
            self.sow(COUNTERS, "attn_tiles", jnp.float32(tiles))
        out = out.reshape(B, T, Hq * D)
        if c.attn_gate:
            out = out * jax.nn.sigmoid(gate)
        return nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                        name="o_proj")(out)


class GatedMLP(nn.Module):
    width: int
    out: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dense = lambda n, name: nn.Dense(n, use_bias=False,
                                         dtype=self.dtype, name=name)
        h = nn.silu(dense(self.width, "gate")(x)) * dense(self.width,
                                                          "up")(x)
        return dense(self.out, "down")(h)


def sum_rows(y, index, n: int):
    """``[n, d]`` whose row ``i`` is the sum of the rows ``y[j]`` with
    ``index[j] == i`` (zeros where there is none): ONE product of the
    0/1 matrix ``[n, L]`` with ``y``, accumulated in float32 and rounded
    once (the matrix unit's work for a few thousand rows is a fraction of
    a scatter-add's, which serialises: PERF.md section 6, PR 40). A row
    of the result is its own terms and exact zeros."""
    hot = (index[:, None] == jnp.arange(n)[None, :]).astype(y.dtype)
    return jnp.einsum("ln,ld->nd", hot, y,
                      precision=jax.lax.Precision.HIGHEST,   # float32 rows
                      preferred_element_type=jnp.float32).astype(y.dtype)


def short_rows(n: int, k: int) -> int:
    """Rows of the short expert buffer for ``n`` tokens of ``k`` choices:
    two choices a token held here, in whole 512s, and never more than
    the worst case ``n * k``. A static function of the call's shapes.
    Why two: at seeded weights every token of a block makes much the same
    choices, so a layer's held count comes in steps of the block's valid
    tokens (0, about ``n``, about ``2n``: PERF.md section 6, PR 40); one
    choice a token would send one layer in twelve the long way."""
    return min(-(-2 * n // 512) * 512, n * k)


class SortedRows:
    """:func:`routed_experts`' ONE routine over the first ``L`` of the
    sorted assignments ``order``, forward and backward: what a length
    needs of the call's integers."""

    def __init__(self, L: int, order, sizes, k: int, dtype):
        self.at = order[:L]                             # assignment a row
        self.token = self.at // k
        self.in_group = (jnp.arange(L) < jnp.sum(sizes))[:, None]
        self.grouped = lambda a, b: jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=dtype)

    def forward(self, x, weight, w_gate, w_up, w_down):
        """The weighted sum ``[n, d]`` and the rows the backward pass
        reads."""
        xs = jnp.where(self.in_group, x[self.token], 0)
        gate, up = self.grouped(xs, w_gate), self.grouped(xs, w_up)
        ys = jnp.where(self.in_group,
                       self.grouped(nn.silu(gate) * up, w_down), 0)
        w = weight.reshape(-1)[self.at].astype(x.dtype)
        return (sum_rows(ys * w[:, None], self.token, x.shape[0]),
                (xs, gate, up, ys))

    def backward(self, kept, g, x, weight, w_gate, w_up, w_down):
        """The operands' cotangents from ``forward``'s rows and the
        result's cotangent ``g``: the transpose, step by step (a gather
        of ``g``'s rows, the grouped products' own transposes, one
        :func:`sum_rows` into the tokens)."""
        xs, gate, up, ys = kept
        g_rows = g[self.token]
        w = weight.reshape(-1)[self.at].astype(x.dtype)
        d_ys = jnp.where(self.in_group, g_rows * w[:, None], 0)
        hid, pull_hid = jax.vjp(lambda a, b: nn.silu(a) * b, gate, up)
        d_hid, d_down = jax.vjp(self.grouped, hid, w_down)[1](d_ys)
        d_gate, d_up = pull_hid(d_hid)
        d_xs_gate, d_w_gate = jax.vjp(self.grouped, xs, w_gate)[1](d_gate)
        d_xs_up, d_w_up = jax.vjp(self.grouped, xs, w_up)[1](d_up)
        d_xs = jnp.where(self.in_group, d_xs_gate + d_xs_up, 0)
        d_w = jnp.sum(g_rows * ys, axis=-1, dtype=weight.dtype)
        d_weight = jnp.zeros((weight.size,), weight.dtype).at[self.at].set(
            d_w, unique_indices=True).reshape(weight.shape)
        return (sum_rows(d_xs, self.token, x.shape[0]), d_weight, d_w_gate,
                d_w_up, d_down)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(short: int, order, sizes, *operands):
    return _routed_fwd(short, order, sizes, *operands)[0]


def _routed_fwd(short, order, sizes, *operands):
    """The short length when the held assignments fit it, else the long
    one, whose rows are not handed on (the backward pass makes them
    again: the worst case needs no speed, and a ``cond`` gives both
    branches' results one shape); no branch where the two are one."""
    x, weight = operands[:2]
    at = lambda L: SortedRows(L, order, sizes, weight.shape[1], x.dtype)
    if short == weight.size:
        out, kept = at(short).forward(*operands)
    else:
        spare = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                             jax.eval_shape(at(short).forward, *operands)[1])
        out, kept = jax.lax.cond(
            jnp.sum(sizes) <= short, at(short).forward,
            lambda *o: (at(weight.size).forward(*o)[0], spare), *operands)
    return out, (order, sizes, kept, operands)


def _routed_bwd(short, passed, g):
    order, sizes, kept, operands = passed
    x, weight = operands[:2]
    at = lambda L: SortedRows(L, order, sizes, weight.shape[1], x.dtype)
    if short == weight.size:
        return None, None, *at(short).backward(kept, g, *operands)
    long = at(weight.size)
    return None, None, *jax.lax.cond(
        jnp.sum(sizes) <= short, at(short).backward,
        lambda _, g, *o: long.backward(long.forward(*o)[1], g, *o),
        kept, g, *operands)


_routed.defvjp(_routed_fwd, _routed_bwd)


def routed_experts(x, gid, weight, w_gate, w_up, w_down):
    """The held experts' part of an expert layer for tokens ``x[n, d]``:
    ``gid[n * k]`` is each assignment's expert (0 .. count - 1 held here,
    ``count`` = not held), ``weight[n, k]`` its router weight (0 where not
    held), the kernels ``[count, d, f]`` / ``[count, f, d]``. Returns the
    weighted sum ``[n, d]`` and the held experts' loads ``[count]``.

    Assignments are sorted by expert, non-held ones last, so the held
    ones are the first ``sum(loads)`` of the order. ONE routine
    (:class:`SortedRows`) works on the first ``L`` of them: it gathers
    their tokens' rows, zeroes the rows behind the last group (a grouped
    product leaves them unwritten; zeroed on the way in, which zeroes
    their gradient too, and on the way out), runs each projection as one
    grouped product and sums every row's result times its router weight
    into its token (:func:`sum_rows`). It is called at ``L`` =
    :func:`short_rows` when the held assignments fit that and at ``L = n
    * k``, a row for EVERY assignment, when they do not
    (``jax.lax.cond``; where the short length is the long one there is
    one call and no branch): none can be dropped, and a token's terms
    are the same rows' at either length. Only integers are ever ``n *
    k`` long on the short path. The backward pass is the routine's own
    transpose at the length the forward pass took, written out
    (:meth:`SortedRows.backward`) and chosen again by the same ``cond``:
    differentiated through, a ``cond`` hands back both branches'
    residuals, zeros the length of the worst case on every short call
    (PERF.md section 6, PR 40)."""
    n, k = weight.shape
    count = w_gate.shape[0]
    order = jnp.argsort(gid, stable=True).astype(jnp.int32)
    sizes = jnp.sum(gid[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return _routed(short_rows(n, k), order, sizes, x, weight, w_gate, w_up,
                   w_down), sizes


def largest(x: jax.Array, k: int) -> jax.Array:
    """Indices ``[..., k]`` of the ``k`` largest of ``x[..., E]``, largest
    first, ties to the lower index: ``k`` passes of a ``max`` and the
    ``min`` of the positions that hold it. For the few an expert layer
    wants of its hundreds these are cheap reductions where ``lax.top_k``
    sorts (on the chip the group-limited choice over 512 experts took
    1.1 s an iteration by ``top_k``), and two plain reductions a pass
    compile in a third of the time of one ``argmax`` over (value, index)
    pairs (PERF.md section 6, PR 39)."""
    E = x.shape[-1]
    at = jnp.arange(E, dtype=jnp.int32)
    out = []
    for _ in range(k):
        top = jnp.max(x, axis=-1, keepdims=True)
        i = jnp.min(jnp.where(x == top, at, E), axis=-1)
        out.append(i)
        x = jnp.where(at == i[..., None], -jnp.inf, x)
    return jnp.stack(out, axis=-1)


def choose_experts(choice: jax.Array, k: int, n_group: int,
                   topk_group: int) -> jax.Array:
    """Indices ``[..., k]`` of the ``k`` largest of ``choice[..., E]``
    among the experts of the ``topk_group`` best of ``n_group`` groups of
    ``E / n_group`` neighbours; a group's score is the sum of its two
    largest entries (``bailing_moe_v2`` / DeepSeek-V3's group-limited
    routing). One group: a flat top-k. Ties go to the lower index."""
    if n_group == 1:
        return jax.lax.top_k(choice, k)[1]
    E = choice.shape[-1]
    grouped = choice.reshape(*choice.shape[:-1], n_group, E // n_group)
    two = jnp.take_along_axis(grouped, largest(grouped, 2), axis=-1)
    best = largest(jnp.sum(two, axis=-1), topk_group)       # [..., kept]
    kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)
    inside = jnp.where(kept[..., None], grouped, -jnp.inf)
    return largest(inside.reshape(choice.shape), k)


class ExpertLayer(nn.Module):
    """``shared(x) + sum over this chip's experts`` (module docstring),
    for ``x[B, T, d]``; sows each held expert's load."""
    cfg: "TrunkConfig | LingConfig"
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = self.cfg
        d, f, k = c.hidden_size, c.moe_intermediate_size, \
            c.num_experts_per_tok
        first, count = c.experts_held
        B, T, _ = x.shape
        router = Kernel((d, c.num_experts), name="router")()
        bias = self.param("bias", nn.initializers.zeros, (c.num_experts,),
                          jnp.float32)
        with jax.named_scope(scopes.MOE_ROUTE):
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))           # [B,T,E]
            idx = choose_experts(jax.lax.stop_gradient(scores) + bias, k,
                                 *expert_groups(c))             # [B,T,k]
            w = jnp.take_along_axis(scores, idx, axis=-1)
            if c.route_norm:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            local = idx - first
            held = (local >= 0) & (local < count)
            w = jnp.where(held, w * c.route_scale, 0.0)
            # non-held assignments sort behind every held expert's
            gid = jnp.where(held, local, count)
        with jax.named_scope(scopes.MOE_EXPERTS):
            view = lambda name, i, o: Kernel((i, count * o), name=name)() \
                .astype(self.dtype).reshape(i, count, o).transpose(1, 0, 2)
            kernels = (view("experts_gate", d, f), view("experts_up", d, f),
                       view("experts_down", f, d))
            routed, sizes = routed_experts(
                x.reshape(B * T, d).astype(self.dtype), gid.reshape(-1),
                w.reshape(-1, k), *kernels)
            routed = routed.reshape(B, T, d)
        with jax.named_scope(scopes.MOE_SHARED):
            shared = GatedMLP(c.shared_intermediate_size, d, self.dtype,
                              name="shared")(x)
        if not self.is_initializing():      # init's tree is params only
            short = short_rows(B * T, k)
            self.sow(COUNTERS, "held", jnp.sum(held, dtype=jnp.int32))
            self.sow(COUNTERS, "load", sizes)
            self.sow(COUNTERS, "short", jnp.float32(short < B * T * k) * (
                jnp.sum(sizes) <= short))
        return shared + routed


class Block(nn.Module):
    cfg: "TrunkConfig | OuroConfig"
    index: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array, valid: jax.Array) -> jax.Array:
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        with jax.named_scope(scopes.TRUNK_ATTN):
            a = Attention(c, c.layer_types[self.index] == SLIDING,
                          self.dtype, name="attn")(norm("input_norm")(x),
                                                   valid)
            h = x + norm("post_attn_norm")(a)
        z = norm("pre_mlp_norm")(h)
        if self.index < c.num_dense_layers:
            with jax.named_scope(scopes.TRUNK_DENSE_MLP):
                m = GatedMLP(c.intermediate_size, c.hidden_size, self.dtype,
                             name="mlp")(z)
        else:
            m = ExpertLayer(c, self.dtype, name="moe")(z)
        return h + norm("post_mlp_norm")(m)


class Bias(nn.Module):
    """One ``bias`` leaf of the given shape, zeros."""
    shape: tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("bias", nn.initializers.zeros, self.shape,
                          jnp.float32)


def unit(x: jax.Array, gain: float = 1.0) -> jax.Array:
    """``x / |x|`` over the last axis (flash-linear-attention's
    ``l2norm``: eps 1e-6 under the root), times ``gain``; float32."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                      keepdims=True) + 1e-6) * gain)


class KDA(nn.Module):
    """A linear-attention layer of the ``ling`` block (module docstring)
    for ``u[B, T, d]``, ``valid[B, T]``."""
    cfg: LingConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: jax.Array, valid: jax.Array) -> jax.Array:
        from ..parallel.sharding import active_mesh
        c = self.cfg
        B, T, _ = u.shape
        H, D = c.num_attention_heads, c.head_dim
        there = valid[..., None]
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)(u)
        heads = lambda a: a.reshape(B, T, H, D)

        def conv(name):
            x = jnp.where(there, dense(H * D, f"{name}_proj"), 0)
            w = Kernel((c.short_conv_kernel_size, H * D),
                       name=f"{name}_conv")()
            with jax.named_scope(scopes.KDA_CONV):
                return heads(nn.silu(kda.causal_conv(x, w)))

        q, k, v = conv("q"), conv("k"), conv("v")
        f = dense(H * D, "f_proj")
        b = dense(H, "b_proj")
        a_log, dt = Bias((H,), name="A_log")(), Bias((H * D,), name="dt")()
        with jax.named_scope(scopes.KDA_GATES):
            rate = jnp.exp(a_log)[:, None] * heads(
                f.astype(jnp.float32) + dt)
            g = jnp.where(there[..., None],
                          c.kda_lower_bound * jax.nn.sigmoid(rate), 0.0)
            beta = jnp.where(there, jax.nn.sigmoid(b.astype(jnp.float32)),
                             0.0)
            q = unit(q, 1.0 / math.sqrt(D)).astype(self.dtype)
            k = unit(k).astype(self.dtype)
        path = kda.delta_rule_path(jax.default_backend(), D, D, c.kda_chunk,
                                   self.dtype, active_mesh() is not None)
        with jax.named_scope(scopes.KDA_SCAN):
            o = kda.chunked_delta_rule(q, k, v, g, beta, chunk=c.kda_chunk,
                                       dtype=self.dtype, path=path)
        if not self.is_initializing():      # init's tree is params only
            self.sow(COUNTERS, "kda_kernel", jnp.float32(path == kda.KERNEL))
        o = RMSNorm(c.rms_norm_eps, self.dtype, name="o_norm")(o)
        out = o.reshape(B, T, H * D) * jax.nn.sigmoid(dense(H * D, "g_proj"))
        return nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                        name="o_proj")(out)


class MLA(nn.Module):
    """A latent-attention layer of the ``ling`` block (module docstring)
    for ``u[B, T, d]``, ``valid[B, T]``."""
    cfg: LingConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, u: jax.Array, valid: jax.Array) -> jax.Array:
        from ..parallel.sharding import active_mesh
        c = self.cfg
        B, T, _ = u.shape
        H, dn, dr, dv = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim, c.v_head_dim
        wide = -(-(dn + dr) // 128) * 128       # q and k, zero-padded
        kernel = attention_path(jax.default_backend(), wide,
                                active_mesh() is not None) == KERNEL
        pre = 1.0 / math.sqrt(dn + dr) if kernel else 1.0   # as Attention
        dense = lambda n, name, x=u: nn.Dense(
            n, use_bias=False, dtype=self.dtype, name=name)(x)
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        kva = dense(c.kv_lora_rank + dr, "kv_a_proj")
        latent = norm("kv_a_norm")(kva[..., :c.kv_lora_rank])
        kvb = dense(H * (dn + dv), "kv_b_proj", latent).reshape(
            B, T, H, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        k_rope = rope(norm("k_rope_norm")(kva[..., c.kv_lora_rank:])[
            :, :, None, :], c.rope_theta)                   # [B, T, 1, dr]
        q = norm("q_norm")(dense(H * (dn + dr), "q_proj").reshape(
            B, T, H, dn + dr), pre)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], c.rope_theta)],
                            axis=-1)
        k = jnp.concatenate([norm("k_norm")(k_nope),
                             jnp.broadcast_to(k_rope, (B, T, H, dr))],
                            axis=-1)
        gate = dense(H, "gate_proj")
        q = q[:, :, :, None, :]             # every head its own keys
        tiles = 0.0
        if kernel:
            from ..ops import attention     # Pallas: this path only
            widen = lambda a: jnp.pad(
                a, [(0, 0)] * (a.ndim - 1) + [(0, wide - dn - dr)])
            out = attention.blocked_attend(widen(q), widen(k), v, valid,
                                           None)
            tiles = attention.tiles_computed_share(T, None)
        else:
            out = attend(q, k, v, valid, None)
        if not self.is_initializing():      # init's tree is params only
            self.sow(COUNTERS, "attn_tiles", jnp.float32(tiles))
        out = out.reshape(B, T, H, dv) * jax.nn.sigmoid(gate)[..., None]
        return nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                        name="o_proj")(out.reshape(B, T, H * dv))


class LingBlock(nn.Module):
    cfg: LingConfig
    index: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array, valid: jax.Array) -> jax.Array:
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        mla = c.is_mla(self.index)
        with jax.named_scope(scopes.TRUNK_ATTN):
            u = norm("input_norm")(x)
            with jax.named_scope(scopes.ATTN_MLA if mla
                                 else scopes.ATTN_KDA):
                layer = (MLA if mla else KDA)(c, self.dtype, name="attn")
                h = x + layer(u, valid)
        z = norm("pre_mlp_norm")(h)
        if self.index < c.num_dense_layers:
            with jax.named_scope(scopes.TRUNK_DENSE_MLP):
                m = GatedMLP(c.intermediate_size, c.hidden_size, self.dtype,
                             name="mlp")(z)
        else:
            m = ExpertLayer(c, self.dtype, name="moe")(z)
        return h + m


BLOCKS = {"afmoe": Block, "ling": LingBlock, "ouro": Block}


def exit_distribution(lam: jax.Array) -> jax.Array:
    """``p[R, ...]`` from the exit gates ``lam[R, ...]`` of a looped
    trunk's steps: ``p_t = lam_t prod_{j<t}(1 - lam_j)``, and the last
    step takes what is left, so ``p`` sums to 1 (``lam_R`` is not read)."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)      # mass not yet exited
    ones = jnp.ones_like(lam[:1])
    return jnp.concatenate([lam[:-1], ones]) * jnp.concatenate([ones, stay])


def pool(x: jax.Array, valid: jax.Array) -> jax.Array:
    """float32 mean of ``x[B, T, d]`` over each row's valid tokens."""
    m = valid[..., None].astype(jnp.float32)
    return jnp.sum(x * m, axis=-2) / jnp.maximum(jnp.sum(m, axis=-2), 1.0)


class TokenTrunk(nn.Module):
    """``obs[..., T, F]`` (last feature: ``valid``) -> float32 ``[..., d]``.
    Each block takes the batch ``ROW_BLOCK`` rows at a time and is
    rematerialised in the backward pass: what a minibatch keeps is each
    block's input (of every application, where the trunk loops), and what
    is live is one group of rows' activations."""
    cfg: TrunkConfigs = TrunkConfig()
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, obs: jax.Array) -> jax.Array:
        c = self.cfg
        Layer = BLOCKS[c.family]
        looped = c.family == "ouro"
        final_norm = lambda trunk, dtype: RMSNorm(
            c.rms_norm_eps, dtype, parent=trunk, name="final_norm")
        with jax.named_scope(scopes.TRUNK):
            lead = obs.shape[:-2]
            obs = obs.reshape(-1, *obs.shape[-2:])
            B, T, _ = obs.shape
            valid = obs[..., -1] > 0.5
            with jax.named_scope(scopes.TRUNK_EMBED):
                x = nn.Dense(c.hidden_size, use_bias=False,
                             dtype=self.dtype, name="embed")(
                    obs.astype(self.dtype))
                if c.embed_scale != 1.0:
                    x = x * jnp.asarray(c.embed_scale, self.dtype)
            r = max(b for b in range(1, min(ROW_BLOCK, B) + 1)
                    if B % b == 0)
            groups = lambda a: a.reshape(B // r, r, *a.shape[1:])
            scan = partial(
                nn.scan, variable_broadcast="params",
                variable_axes={COUNTERS: 0, "intermediates": 0},
                split_rngs={"params": False})
            over_groups = scan(lambda block, carry, xv: (carry, block(*xv)))

            def layers(trunk, x):
                """The ``L`` blocks, one after the other."""
                for i in range(c.num_hidden_layers):
                    # inside a scan nothing can merge the recomputation
                    # with the forward pass, so the barriers against it
                    # are left out
                    block = nn.remat(Layer, prevent_cse=False)(
                        c, i, self.dtype, parent=trunk, name=f"layer_{i}")
                    _, x = over_groups(block, None,
                                       (groups(x), groups(valid)))
                    x = x.reshape(B, T, c.hidden_size)
                return x

            def step(trunk, carry, _):
                """One pass of a looped trunk: the blocks, the closing
                norm (the next step's input) and the exit gate."""
                x = layers(trunk, carry[0])
                with jax.named_scope(scopes.LOOP_GATE):
                    y = final_norm(trunk, self.dtype)(x)
                    lam = jax.nn.sigmoid(nn.Dense(
                        1, dtype=self.dtype, parent=trunk,
                        name="exit_gate")(y)[..., 0].astype(jnp.float32))
                return (y, carry[0]), lam

            if looped:
                with jax.named_scope(scopes.TRUNK_LOOP):
                    (x, before), lam = scan(step, length=c.loop_steps)(
                        self, (x, x), None)
                    with jax.named_scope(scopes.LOOP_GATE):
                        p = exit_distribution(lam)
            else:
                x = layers(self, x)
            with jax.named_scope(scopes.TRUNK_POOL):
                if not looped:          # a loop's last step has normed it
                    x = final_norm(self, jnp.float32)(x)
                pooled = pool(x, valid)
                if looped and not self.is_initializing():   # params only
                    size = lambda a: jnp.linalg.norm(a, axis=-1)
                    self.sow(COUNTERS, "loop_exit_mass_last",
                             jnp.sum(p[-1] * valid) / jnp.maximum(
                                 jnp.sum(valid), 1))
                    self.sow(COUNTERS, "loop_last_step_change", jnp.mean(
                        size(pooled - pool(before, valid))
                        / jnp.maximum(size(pooled), 1e-30)))
            return pooled.reshape(*lead, c.hidden_size)


def describe(c: TrunkConfigs) -> dict:
    """What a trunk's configuration fixes about its layers, for a run's
    own record (``train.py`` prints it once, at build): constants of the
    build, so no counter carries them through every iteration."""
    groups, kept = expert_groups(c)
    return {"family": c.family, "layers": c.num_hidden_layers,
            "loop_steps": c.loop_steps, "kda_layers": c.kda_layers,
            "kda_chunk": c.kda_chunk if c.kda_layers else 0,
            "moe_groups": groups, "moe_groups_kept": kept}


def read_counters(collection: dict) -> dict:
    """The counters of one forward pass from what its layers sowed, one
    entry a group of rows (the last axis) and, where the trunk loops, a
    loop step (the first). The expert layers' (each: assignments to held
    experts, every held expert's load, and whether the call took
    ``routed_experts``' short buffer): assignments held and assignments
    dropped, each summed over the layers; the fullest held expert's load
    over the mean held load, the largest of the layers'; the share of the
    calls (layers x groups of rows) that took the short buffer, 0 where
    the shapes leave no shorter one than the worst case's; all 0 in a
    trunk without expert layers.
    The attention layers' (each: the share of its padded grid's tiles
    that the kernel computes, a constant of the trace; 0 where the score
    product took the plain path): the layer APPLICATIONS on the kernel
    (a looped trunk applies each layer once a step), and the share's mean
    over them (0 where none is).
    The KDA layers' (each: 1 where its chunks took the kernel pair,
    ``ops.kda.delta_rule_path``'s answer, a constant of the trace): the
    layer applications on it; 0 in a trunk without KDA layers.
    A looped trunk's own: the share of exit mass its gates leave to the
    last step (``p_R``, mean over valid tokens), and how far the last
    step still moves the pooled output, ``|pool(x^(R)) - pool(x^(R-1))| /
    |pool(x^(R))|``, mean over rows; 0 in a trunk that does not loop."""
    held, ratio, computed, tiles, short, kernels = [], [], [], [], [], []
    loop = {"loop_exit_mass_last": 0.0, "loop_last_step_change": 0.0}
    paths, _ = jax.tree_util.tree_flatten_with_path(collection)
    for path, leaf in paths:
        name = [p.key for p in path if hasattr(p, "key")][-1]
        if name == "held":
            held.append(jnp.sum(leaf))
        elif name == "attn_tiles":
            tiles.append(jnp.max(jnp.atleast_1d(leaf),
                                 axis=-1).reshape(-1))
        elif name == "short":
            short.append(jnp.mean(leaf))
        elif name == "kda_kernel":
            kernels.append(jnp.max(leaf))
        elif name in loop:
            loop[name] = leaf
        else:
            load = jnp.sum(leaf.reshape(-1, leaf.shape[-1]), axis=0)
            computed.append(jnp.sum(load))
            ratio.append(jnp.max(load) / jnp.maximum(jnp.mean(
                load.astype(jnp.float32)), 1.0 / load.shape[0]))
    held, computed = sum(held), sum(computed)
    largest = lambda a: jnp.max(jnp.stack(a)) if a else 0.0
    tiles = jnp.concatenate([*tiles, jnp.zeros((0,), jnp.float32)])
    layers = jnp.sum(tiles > 0, dtype=jnp.float32)
    return jax.tree.map(jnp.float32, {
        "moe_assignments_held": held,
        "moe_expert_load_max_over_mean": largest(ratio),
        "moe_dropped_assignments": held - computed,
        "moe_short_path_share": jnp.mean(jnp.stack(short)) if short else 0.0,
        "attn_kernel_layers": layers, "kda_kernel_layers": sum(kernels),
        "attn_tiles_computed_share": jnp.sum(tiles) / jnp.maximum(
            layers, 1.0), **loop})
