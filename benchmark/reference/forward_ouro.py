"""Plain float32 ``ouro`` trunk over the token observation (see
``forward.py``): Ouro's looped language block (one stack of dense
multi-head-attention layers applied ``total_ut_steps`` times with the same
weights, an exit gate a step) over one token per cluster node and per job,
and the FLOPs its forward pass needs per row. ``jax.numpy`` / ``jax.lax``
only: no flax, no program import, no kernel. The layers of a step are a
Python ``for``; the steps are ONE ``lax.scan`` (below). RMSNorm, the dense
product, RoPE and the gated MLP are ``forward_tokens``'s: the same plain
code serves every token trunk.

``x^(0)`` = the token features through ``embed`` (no scale), ``[T, d]``;
``R`` = ``total_ut_steps``; every norm an RMSNorm (``y = x * rsqrt(mean(x^2)
+ eps) * scale``)::

    for t = 1..R:  z = x^(t-1);  for i = 0..L-1: z = Block_i(z)   the SAME L blocks
                   x^(t) = final_norm(z)        closes every step; the next step's input
                   lambda_t = sigmoid(x^(t) w_g + b_g)            a token
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j) (1 < t < R)
    p_R = prod_{j<R}(1 - lambda_j)                                sums to 1
    output = x^(t*),  t* = the first t with p_1 + .. + p_t >= early_exit_threshold

    Block_i(x):  h = x + post_attn_norm(Attn(input_norm(x)))
                 y = h + post_mlp_norm(MLP(pre_mlp_norm(h)))

``Attn``: q, k, v = no-bias projections to H heads of D each (as many KV
heads as query heads); RoPE (theta, position = token index, halves rotated)
on q and k in EVERY layer; query q sees key k iff k <= q and key k's token
is valid (the observation's last feature); softmax(q k^T / sqrt(D)) v,
scores materialised; ``o_proj``. No q/k norm, no output gate, no window.
``MLP``: ``down(silu(gate(x)) * up(x))``. After the loop: the mean over
valid tokens of ``x^(t*)``. At the published threshold 1 the cumulated
``p`` reaches it only at ``R`` (and there only up to rounding), so every
step runs and ``t* = R`` for every token: taken as such, not left to a
float32 sum that may or may not round to 1.0. The gate and ``p`` are
computed at every step all the same.

**Memory and program size are designed, not found.** Every layer
application is under ``jax.checkpoint``: a follower's backward pass keeps
one ``[rows, T, d]`` input an application (R x L of them) and one
application's activations. The steps run under one ``lax.scan``, so a
compiled follower holds the ``L`` float32 layer bodies once and not ``R``
times: written out, the 32 applications made programs of 209 and 110 MB,
more than the chip machine's compile cache keeps (201 MB), and every
check compiled for 165-190 s; scanned they are 56 and 27 MB. Neither
changes a number (``tests/test_trunk_ouro.py`` holds this module to its
own loop written out, values and gradients).

What no leaf's shape says (the loop count, theta, eps, the threshold, the
tokens a row holds) comes in ``settings`` (the configuration file's
top-level keys, overlaid by its ``rehearse_trunk`` in a rehearsal:
``benchmark.common.Reference``). This module looks at no file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .forward import _q
from .forward_tokens import gated_mlp, matmul, rms_norm, rope


def attention(p, u, valid, spec: dict, quant):
    B, T, _ = u.shape
    D = spec["head_dim"]
    heads = lambda name: matmul(u, p[name], quant).reshape(B, T, -1, D)
    q = rope(heads("q_proj"), spec["rope_theta"])
    k = rope(heads("k_proj"), spec["rope_theta"])
    v = heads("v_proj")
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant))
    s = s / math.sqrt(D)
    pos = jnp.arange(T)
    mask = (pos[None, :] <= pos[:, None])[None] & valid[:, None, :]
    w = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(w, quant), _q(v, quant))
    return matmul(o.reshape(B, T, -1), p["o_proj"], quant)


def block(p, x, valid, spec: dict, quant):
    eps = spec["rms_norm_eps"]
    a = attention(p["attn"], rms_norm(x, p["input_norm"], eps), valid, spec,
                  quant)
    h = x + rms_norm(a, p["post_attn_norm"], eps)
    m = gated_mlp(p["mlp"], rms_norm(h, p["pre_mlp_norm"], eps), quant)
    return h + rms_norm(m, p["post_mlp_norm"], eps)


def exit_distribution(lam):
    """``p`` ``[R, ...]`` from the steps' gates ``lam`` ``[R, ...]``."""
    p, left = [], jnp.ones_like(lam[0])
    for t in range(len(lam) - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack([*p, left])


def exit_step(p, threshold: float):
    """1-based step each token leaves the loop at (module docstring)."""
    R = len(p)
    if threshold >= 1:
        return jnp.full(p[0].shape, R, jnp.int32)
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0),
                     1 + jnp.argmax(reached, axis=0), R).astype(jnp.int32)


def steps(enc, obs, quant, settings: dict):
    """``(x[R, B, T, d], lam[R, B, T], valid)``: every step's output and
    gate."""
    spec = settings
    obs = obs.astype(jnp.float32)
    valid = obs[..., -1] > 0.5
    apply = jax.checkpoint(lambda p, x: block(p, x, valid, spec, quant))
    layers = []
    while f"layer_{len(layers)}" in enc:
        layers.append(enc[f"layer_{len(layers)}"])

    def step(x, _):
        for p in layers:
            x = apply(p, x)
        x = rms_norm(x, enc["final_norm"], spec["rms_norm_eps"])
        g = enc["exit_gate"]
        lam = jax.nn.sigmoid((matmul(x, g, quant) + g["bias"])[..., 0])
        return x, (x, lam)

    _, (xs, lam) = jax.lax.scan(step, matmul(obs, enc["embed"], quant),
                                None, length=spec["total_ut_steps"])
    return xs, lam, valid


def trunk(enc, obs, quant, settings: dict):
    xs, lam, valid = steps(enc, obs, quant, settings)
    at = exit_step(exit_distribution(lam), settings["early_exit_threshold"])
    x = jnp.take_along_axis(xs, (at - 1)[None, ..., None], axis=0)[0]
    m = valid[..., None].astype(jnp.float32)
    return jnp.sum(x * m, axis=-2) / jnp.maximum(jnp.sum(m, axis=-2), 1.0)


def forward_flops_per_row(params, settings: dict) -> float:
    """FLOPs of one observation row's forward pass (a multiply-add is 2),
    from shapes, the loop count and the tokens a row holds (T): the
    embedding and both heads once; ``total_ut_steps`` TIMES the ``L``
    layers' seven products, their scores and the scores' product with the
    values over the causal half of the (query, key) pairs, and the gate's
    ``[T, d] x [d, 1]``. ``params`` may be shapes."""
    p = params["params"]
    enc = p["encoder"]
    T, R = settings["tokens_per_row"], settings["total_ut_steps"]
    size = lambda leaf: math.prod(leaf.shape)
    kernels = lambda tree, names: sum(size(tree[n]["kernel"]) for n in names)
    per_token = size(enc["exit_gate"]["kernel"])    # parameters a step
    pairs = 0.0                                     # score FLOPs a step
    i = 0
    while f"layer_{i}" in enc:
        lp = enc[f"layer_{i}"]
        per_token += kernels(lp["attn"], ("q_proj", "k_proj", "v_proj",
                                          "o_proj"))
        per_token += kernels(lp["mlp"], ("gate", "up", "down"))
        width = lp["attn"]["q_proj"]["kernel"].shape[-1]        # H * D
        pairs += 2.0 * width * (T * (T + 1) / 2)    # scores, values
        i += 1
    once = T * size(enc["embed"]["kernel"]) + size(
        p["policy"]["kernel"]) + size(p["value"]["kernel"])
    return 2.0 * (R * (T * per_token + pairs) + once)
