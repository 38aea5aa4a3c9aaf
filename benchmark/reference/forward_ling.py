"""Plain float32 ``ling`` trunk over the token observation (see
``forward.py``): Ling-3.0-flash's language block (five KDA layers and one
MLA layer a period, group-limited sigmoid routing, a shared expert) over
one token per cluster node and per job, and the FLOPs its forward pass
needs per row. ``jax.numpy`` / ``jax.lax`` only: no flax, no program
import, no chunked form, no top-k primitive, no grouped product, no
kernel. RMSNorm, the dense product, RoPE and the gated MLP are
``forward_tokens``'s: the same plain code serves both token trunks.

Pre-norm blocks, ``x`` ``[T, d]``, every norm an RMSNorm
(``y = x * rsqrt(mean(x^2) + eps) * scale``)::

    h = x + Attn_i(input_norm(x));   y = h + MLP_i(pre_mlp_norm(h))

**KDA** (``i % layer_group_size != layer_group_size - 1``), ``u`` the
normed input: ``q~, k~, v = silu(conv(u W_q)), silu(conv(u W_k)),
silu(conv(u W_v))``, ``H`` heads of ``D``; ``conv`` depthwise and causal
over tokens, ``y_t = sum_j w[j] x_{t-(W-1)+j}``, no bias; ``q = q~ /
sqrt(|q~|^2 + 1e-6) / sqrt(D)``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``;
``g = kda_lower_bound * sigmoid(exp(A_log)_h * (u W_f + dt_bias))`` a
channel; ``beta = sigmoid(u W_beta)`` a head; **token by token**, a head's
state ``S`` ``[D, D]`` from zero::

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then ``(RMSNorm_head(o) * sigmoid(u W_g)) W_o``. A token whose ``valid`` is
0 feeds zeros to the convolutions and leaves the state as it was (``beta``
0, ``g`` 0). **MLA** (the period's last layer): ``[c, r] = u W_kva``;
``[k_nope, v]_h = RMSNorm(c) W_kvb``; ``k_rope = RoPE(RMSNorm(r))``, one a
token for all heads; ``[q_nope, q_rope]_h = RMSNorm_h(u W_q)`` (one norm
over the head's ``dn + dr`` channels), RoPE on ``q_rope`` (theta, position
= token index, halves rotated); ``k_nope`` RMSNormed a head; scores ``(q .
[k_nope, k_rope]) / sqrt(dn + dr)`` materialised, causal, keys of invalid
tokens masked; softmax; times ``v``; each head times the scalar
``sigmoid(u W_gate)_h``; ``W_o``. **MLP** of a leading dense layer:
``down(silu(gate(x)) * up(x))``; of the others ``shared(x) + sum over the
experts HELD HERE of w_e expert_e(x)``: ``s = sigmoid(router(x))`` over all
published experts; choice scores ``s + expert_bias``; ``n_group`` groups of
neighbours, a group's score the sum of its two largest choice scores, the
``topk_group`` best groups kept (by SORTING; ties to the lower index), the
``k`` largest choice scores inside them chosen; ``w`` = the chosen ``s``
over their sum, times ``routed_scaling_factor``; every held expert computed
densely for every token. After the last layer: final RMSNorm, mean over
valid tokens. Input: the token features through ``embed`` (no scale).

**Memory is designed, not found.** The recurrence's backward pass would
keep a ``[H, D, D]`` state a token (1.74 GB a row a layer at the published
widths), so the scan is nested: an outer scan over runs of
``SCAN_INNER`` tokens whose body is under ``jax.checkpoint``, an inner one
over tokens; and every layer is under ``jax.checkpoint``.
Rematerialisation changes no number.

What no leaf's shape says comes in ``settings`` (the configuration file's
top-level keys, overlaid by its ``rehearse_trunk`` in a rehearsal:
``benchmark.common.Reference``). This module looks at no file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .forward import _q
from .forward_tokens import gated_mlp, matmul, rms_norm, rope

SCAN_INNER = 32         # tokens an inner scan: sqrt(832) or so


def causal_conv(x, p, quant):
    """``x[B, T, C]``, ``kernel[W, C]``: the written-out sum."""
    w = _q(p["kernel"], quant)
    x = _q(x, quant)
    W, T = w.shape[0], x.shape[1]
    xp = jnp.concatenate([jnp.zeros_like(x[:, :W - 1]), x], axis=1)
    y = jnp.zeros_like(x)
    for j in range(W):
        y = y + w[j] * xp[:, j:j + T]
    return y


def delta_rule(q, k, v, g, beta, inner: int = SCAN_INNER):
    """The recurrence, token by token: ``q, k, g`` ``[B, T, H, K]``, ``v``
    ``[B, T, H, V]``, ``beta`` ``[B, T, H]`` -> ``o`` ``[B, T, H, V]``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    n = -(-T // inner)
    pad = n * inner - T     # padded tokens leave the state alone

    def runs(z):            # [B, T, ...] -> [n, inner, B, ...]
        z = jnp.concatenate([z, jnp.zeros((B, pad, *z.shape[2:]), z.dtype)],
                            axis=1)
        return jnp.moveaxis(z, 1, 0).reshape(n, inner, B, *z.shape[2:])

    def token(S, x):
        q, k, v, g, b = x
        # sums written out, not products on the matrix unit: float32
        # arithmetic throughout (6.1 ms a row and layer forward on the
        # chip, 24.4 with its gradient)
        S = jnp.exp(g)[..., None] * S                       # [B, H, K, V]
        u = b[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.sum(S * q[..., None], axis=-2)

    run = jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs))
    _, o = jax.lax.scan(run, jnp.zeros((B, H, K, V), jnp.float32),
                        tuple(runs(z) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(n * inner, B, H, V), 0, 1)[:, :T]


def kda(p, u, valid, spec: dict, quant):
    B, T, _ = u.shape
    H = p["A_log"]["bias"].shape[0]
    D = p["o_norm"]["scale"].shape[0]
    there = valid[..., None]
    heads = lambda a: a.reshape(B, T, H, D)
    conv = lambda n: heads(jax.nn.silu(causal_conv(
        jnp.where(there, matmul(u, p[f"{n}_proj"], quant), 0.0),
        p[f"{n}_conv"], quant)))
    q, k, v = conv("q"), conv("k"), conv("v")
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                               keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(D), unit(k)
    rate = jnp.exp(p["A_log"]["bias"])[:, None] * heads(
        matmul(u, p["f_proj"], quant) + p["dt"]["bias"])
    g = jnp.where(there[..., None],
                  spec["kda_lower_bound"] * jax.nn.sigmoid(rate), 0.0)
    beta = jnp.where(there, jax.nn.sigmoid(matmul(u, p["b_proj"], quant)),
                     0.0)
    o = delta_rule(_q(q, quant), _q(k, quant), _q(v, quant), g, beta)
    o = rms_norm(o, p["o_norm"], spec["rms_norm_eps"]).reshape(B, T, H * D)
    return matmul(o * jax.nn.sigmoid(matmul(u, p["g_proj"], quant)),
                  p["o_proj"], quant)


def mla(p, u, valid, spec: dict, quant):
    B, T, _ = u.shape
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    rank = p["kv_a_norm"]["scale"].shape[0]
    dn = p["k_norm"]["scale"].shape[0]
    dr = p["k_rope_norm"]["scale"].shape[0]
    H = p["gate_proj"]["kernel"].shape[-1]
    kva = matmul(u, p["kv_a_proj"], quant)
    latent = rms_norm(kva[..., :rank], p["kv_a_norm"], eps)
    kvb = matmul(latent, p["kv_b_proj"], quant).reshape(B, T, H, -1)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    k_rope = rope(rms_norm(kva[..., rank:], p["k_rope_norm"],
                           eps)[:, :, None, :], theta)
    q = rms_norm(matmul(u, p["q_proj"], quant).reshape(B, T, H, dn + dr),
                 p["q_norm"], eps)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([rms_norm(k_nope, p["k_norm"], eps),
                         jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant))
    s = s / math.sqrt(dn + dr)
    pos = jnp.arange(T)
    mask = (pos[None, :] <= pos[:, None])[None] & valid[:, None, :]
    w = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(w, quant), _q(v, quant))
    o = o * jax.nn.sigmoid(matmul(u, p["gate_proj"], quant))[..., None]
    return matmul(o.reshape(B, T, -1), p["o_proj"], quant)


def chosen_experts(choice, spec: dict):
    """0/1 ``[..., E]``: the ``k`` experts group-limited choice selects,
    by sorting (stable, descending: ties to the lower index)."""
    E = choice.shape[-1]
    n, kept, k = spec["n_group"], spec["topk_group"], \
        spec["num_experts_per_tok"]
    rank = lambda x: jnp.argsort(jnp.argsort(-x, axis=-1, stable=True),
                                 axis=-1)      # 0 = the largest
    grouped = choice.reshape(*choice.shape[:-1], n, E // n)
    score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)
    inside = jnp.where((rank(score) < kept)[..., None], grouped, -jnp.inf)
    return (rank(inside.reshape(choice.shape)) < k).astype(choice.dtype)


def route(p, x, spec: dict):
    """``w[..., E]``: each token's weight on every published expert (0
    where it was not chosen). Router scores in float32, never quantised
    (the configuration states them so)."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    w = s * chosen_experts(s + p["bias"], spec)
    if spec["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * spec["routed_scaling_factor"]


def experts(p, x, spec: dict, quant, held=None):
    """The part of the expert layer's result that the experts held here
    give (``held = (first, count)``; the count is the leaves' own)."""
    d = x.shape[-1]
    f = p["experts_down"]["kernel"].shape[0]
    count = p["experts_gate"]["kernel"].shape[-1] // f
    first = spec["experts_held_first"] if held is None else held[0]
    view = lambda name, i, o: p[name]["kernel"].reshape(i, count, o)
    w = route(p, x, spec)[..., first:first + count]          # [..., count]
    xq = _q(x, quant)
    g = jnp.einsum("...d,def->...ef", xq, _q(view("experts_gate", d, f),
                                             quant))
    u = jnp.einsum("...d,def->...ef", xq, _q(view("experts_up", d, f),
                                             quant))
    y = jnp.einsum("...ef,fed->...ed", _q(jax.nn.silu(g) * u, quant),
                   _q(view("experts_down", f, d), quant))
    return jnp.sum(y * w[..., None], axis=-2)


def expert_layer(p, x, spec: dict, quant):
    return gated_mlp(p["shared"], x, quant) + experts(p, x, spec, quant)


def is_mla(i: int, spec: dict) -> bool:
    return i % spec["layer_group_size"] == spec["layer_group_size"] - 1


def trunk(enc, obs, quant, settings: dict):
    spec, eps = settings, settings["rms_norm_eps"]
    obs = obs.astype(jnp.float32)
    valid = obs[..., -1] > 0.5
    x = matmul(obs, enc["embed"], quant)

    def layer(i):
        def apply(p, x):
            u = rms_norm(x, p["input_norm"], eps)
            h = x + (mla if is_mla(i, spec) else kda)(p["attn"], u, valid,
                                                     spec, quant)
            z = rms_norm(h, p["pre_mlp_norm"], eps)
            return h + (gated_mlp(p["mlp"], z, quant)
                        if i < spec["first_k_dense_replace"]
                        else expert_layer(p["moe"], z, spec, quant))
        return jax.checkpoint(apply)

    i = 0
    while f"layer_{i}" in enc:
        x = layer(i)(enc[f"layer_{i}"], x)
        i += 1
    x = rms_norm(x, enc["final_norm"], eps)
    m = valid[..., None].astype(jnp.float32)
    return jnp.sum(x * m, axis=-2) / jnp.maximum(jnp.sum(m, axis=-2), 1.0)


def forward_flops_per_row(params, settings: dict) -> float:
    """FLOPs of one observation row's forward pass (a multiply-add is 2),
    from shapes and the tokens a row holds (T). Every projection, both
    heads and the convolutions' taps at 2 a parameter a token. The
    recurrence AS WRITTEN above, per token and head of ``K`` x ``V``
    state: the decay ``K V``, the rank-one correction's read ``2 K V`` and
    ``2 V``, the write ``2 K V``, the read-out ``2 K V`` (the chunked form
    the program runs does more arithmetic than this; it is not counted).
    MLA's scores and their product with the values over the causal half
    of the (query, key) pairs. The routed experts at the expected share of
    assignments (T x k x held / published), not the worst case the
    buffers are sized for. ``params`` may be shapes."""
    p = params["params"]
    enc = p["encoder"]
    T = settings["tokens_per_row"]
    size = lambda leaf: math.prod(leaf.shape)
    kernels = lambda tree, names: sum(size(tree[n]["kernel"]) for n in names)
    per_token = size(enc["embed"]["kernel"])    # parameters a token passes
    extra = 0.0                                 # FLOPs a row, not in those
    i = 0
    while f"layer_{i}" in enc:
        lp = enc[f"layer_{i}"]
        a = lp["attn"]
        if is_mla(i, settings):
            per_token += kernels(a, ("kv_a_proj", "kv_b_proj", "q_proj",
                                     "gate_proj", "o_proj"))
            H = a["gate_proj"]["kernel"].shape[-1]
            qk = a["q_proj"]["kernel"].shape[-1] // H
            dv = a["o_proj"]["kernel"].shape[0] // H
            extra += 2.0 * H * (qk + dv) * (T * (T + 1) / 2)
        else:
            per_token += kernels(a, (
                "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv",
                "f_proj", "b_proj", "g_proj", "o_proj"))
            H = a["A_log"]["bias"].shape[0]
            K = V = a["o_norm"]["scale"].shape[0]
            extra += T * H * (7.0 * K * V + 2.0 * V)
        if i < settings["first_k_dense_replace"]:
            per_token += kernels(lp["mlp"], ("gate", "up", "down"))
        else:
            m = lp["moe"]
            per_token += size(m["router"]["kernel"]) + kernels(
                m["shared"], ("gate", "up", "down"))
            held = kernels(m, ("experts_gate", "experts_up", "experts_down"))
            # an assignment passes one expert: held / count parameters;
            # a token makes k * count / published of them here
            per_token += held * settings["num_experts_per_tok"] / \
                m["router"]["kernel"].shape[-1]
        i += 1
    heads = size(p["policy"]["kernel"]) + size(p["value"]["kernel"])
    return 2.0 * (T * per_token + heads) + extra
