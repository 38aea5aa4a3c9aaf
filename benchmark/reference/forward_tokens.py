"""Plain float32 ``afmoe`` trunk over the token observation (see
``forward.py``): the block over one token per cluster node and per job,
and the FLOPs its forward pass needs per row. ``jax.numpy`` only: no flax, no program
import, no sort, no grouped product, no kernel.

Per layer ``i``, ``x`` ``[T, d]``, every norm an RMSNorm (eps from the
configuration, ``y = x * rsqrt(mean(x^2) + eps) * scale``)::

    h = x + post_attn_norm(Attn_i(input_norm(x)))
    y = h + post_mlp_norm(MLP_i(pre_mlp_norm(h)))

``Attn``: q, k, v = no-bias projections to Hq / Hkv / Hkv heads of D;
RMSNorm over D on q and k; RoPE (theta, position = token index, halves
rotated) on q and k only where ``layer_types[i]`` is
``sliding_attention``; query q sees key k iff k <= q, q - k <
``sliding_window`` on a sliding layer, and key k's token is valid (the
observation's last feature); softmax(q k^T / sqrt(D)) v, KV head h
serving query heads h*G .. h*G+G-1; times sigmoid(gate_proj(input));
``o_proj``. ``MLP`` of a leading dense layer: down(silu(gate(x)) * up(x)).
``MLP`` of the others: shared(x) + sum over the experts HELD HERE of w_e
expert_e(x), with s = sigmoid(router(x)) over all published experts,
selection = top-k of s + expert_bias, w = the selected s over their sum
over all k selected, times ``route_scale``: every held expert is computed
densely for every token and weighted (w_e = 0 where e was not selected);
what the absent experts would add is left out, as in the program. After
the last layer: final RMSNorm, mean over valid tokens. Input: the token
features through ``embed`` times sqrt(d).

What no leaf's shape says (layer types, window, k, route scale and norm,
theta, eps, the count of leading dense layers, the first held expert, and
the tokens a row holds) comes in ``settings``, which the harness resolves
from the configuration's file (``benchmark.common.Reference``: the file's
top-level keys in a run, overlaid by its ``rehearse_trunk`` in a
rehearsal) and hands to ``trunk`` and ``forward_flops_per_row``. But for
the shim at its end this module looks at no file, and at no other
configuration ever.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .forward import _q

SLIDING = "sliding_attention"


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * p["scale"]


def matmul(x, p, quant):
    return _q(x, quant) @ _q(p["kernel"], quant)


def rope(x, theta):
    """``x[..., T, H, D]``."""
    T, D = x.shape[-3], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def seen(T: int, window) -> jax.Array:
    """bool[T, T] by position alone (the valid keys come on top)."""
    q, k = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = k <= q
    return ok if window is None else ok & (q - k < window)


def attention(p, x, valid, sliding: bool, spec: dict, quant):
    B, T, _ = x.shape
    D = p["q_norm"]["scale"].shape[0]
    Hq = p["q_proj"]["kernel"].shape[-1] // D
    Hkv = p["k_proj"]["kernel"].shape[-1] // D
    q = matmul(x, p["q_proj"], quant).reshape(B, T, Hq, D)
    k = matmul(x, p["k_proj"], quant).reshape(B, T, Hkv, D)
    v = matmul(x, p["v_proj"], quant).reshape(B, T, Hkv, D)
    q = rms_norm(q, p["q_norm"], spec["rms_norm_eps"])
    k = rms_norm(k, p["k_norm"], spec["rms_norm_eps"])
    if sliding:
        q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant))
    s = s / math.sqrt(D)
    mask = (seen(T, spec["sliding_window"] if sliding else None)[None]
            & valid[:, None, :])[:, None]
    w = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _q(w, quant), _q(v, quant))
    o = o.reshape(B, T, Hq * D) * jax.nn.sigmoid(
        matmul(x, p["gate_proj"], quant))
    return matmul(o, p["o_proj"], quant)


def gated_mlp(p, x, quant):
    h = jax.nn.silu(matmul(x, p["gate"], quant)) * matmul(x, p["up"], quant)
    return matmul(h, p["down"], quant)


def route(p, x, spec: dict):
    """``w[..., E]``: each token's weight on every published expert (0
    where it was not selected). Router scores in float32, never
    quantised (the configuration states them so)."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    k = spec["num_experts_per_tok"]
    _, idx = jax.lax.top_k(s + p["bias"], k)
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype),
                     axis=-2)
    w = s * chosen
    if spec["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * spec["route_scale"]


def experts(p, x, spec: dict, quant, held=None):
    """The part of the expert layer's result that the experts held here
    give (``held = (first, count)``; the count is the leaves' own)."""
    d = x.shape[-1]
    f = p["shared"]["gate"]["kernel"].shape[-1]
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


def trunk(enc, obs, quant, settings: "dict | None" = None):
    spec = legacy_settings(enc) if settings is None else settings
    eps = spec["rms_norm_eps"]
    obs = obs.astype(jnp.float32)
    valid = obs[..., -1] > 0.5
    d = enc["embed"]["kernel"].shape[-1]
    x = matmul(obs, enc["embed"], quant) * math.sqrt(d)
    for i, kind in enumerate(spec["layer_types"]):
        if f"layer_{i}" not in enc:
            break           # the cut: the first layers of the published list
        p = enc[f"layer_{i}"]
        a = attention(p["attn"], rms_norm(x, p["input_norm"], eps), valid,
                      kind == SLIDING, spec, quant)
        h = x + rms_norm(a, p["post_attn_norm"], eps)
        z = rms_norm(h, p["pre_mlp_norm"], eps)
        m = (gated_mlp(p["mlp"], z, quant) if i < spec["num_dense_layers"]
             else expert_layer(p["moe"], z, spec, quant))
        x = h + rms_norm(m, p["post_mlp_norm"], eps)
    x = rms_norm(x, enc["final_norm"], eps)
    m = valid[..., None].astype(jnp.float32)
    return jnp.sum(x * m, axis=-2) / jnp.maximum(jnp.sum(m, axis=-2), 1.0)


def forward_flops_per_row(params, settings: dict) -> float:
    """Multiply-adds x2 of one observation row's forward pass, from
    shapes, the stated window and the tokens a row holds (T): every
    projection and both heads; attention scores and their product with
    the values over the (query, key) pairs the causal and windowed masks
    leave; the routed experts at the expected share of assignments (T x k
    x held / published: with random routing an expert held here gets that
    many), not the worst case the buffers are sized for. ``params`` may
    be shapes."""
    p = params["params"]
    enc = p["encoder"]
    T = settings["tokens_per_row"]
    size = lambda leaf: math.prod(leaf.shape)
    per_token = size(enc["embed"]["kernel"])
    pairs = 0.0
    n_layers = sum(1 for k in enc if k.startswith("layer_"))
    for i in range(n_layers):
        lp = enc[f"layer_{i}"]
        a = lp["attn"]
        per_token += sum(size(a[n]["kernel"]) for n in
                         ("q_proj", "k_proj", "v_proj", "gate_proj",
                          "o_proj"))
        width = a["q_proj"]["kernel"].shape[-1]          # Hq * D
        window = (settings["sliding_window"]
                  if settings["layer_types"][i] == SLIDING else T)
        seen_pairs = sum(min(q + 1, window) for q in range(T))
        pairs += 2.0 * width * seen_pairs                # scores, values
        if i < settings["num_dense_layers"]:
            per_token += sum(size(lp["mlp"][n]["kernel"])
                             for n in ("gate", "up", "down"))
        else:
            m = lp["moe"]
            per_token += size(m["router"]["kernel"]) + sum(
                size(m["shared"][n]["kernel"]) for n in ("gate", "up",
                                                         "down"))
            n_published = m["router"]["kernel"].shape[-1]
            held = sum(size(m[n]["kernel"]) for n in
                       ("experts_gate", "experts_up", "experts_down"))
            # an assignment passes one expert: held / count parameters;
            # a token makes k * count / published of them here
            per_token += held * settings["num_experts_per_tok"] / n_published
    heads = size(p["policy"]["kernel"]) + size(p["value"]["kernel"])
    return 2.0 * (T * per_token + pairs + heads)


# ---- kept for tests/test_trunk.py alone ------------------------------
# Two tier-1 tests there still call ``specs()`` and ``trunk(enc, obs,
# quant)`` without settings, and a ``benchmark`` PR may not edit
# ``tests/`` (ISSUE 35; PERF.md section 7). Nothing under ``benchmark/``
# calls either: the harness hands the settings over. The shim names the
# ONE file this reference was written for, so no other configuration's
# file can break it; it goes when those two tests are rewritten.
KEYS = ("hidden_size", "layer_types", "sliding_window",
        "num_experts_per_tok", "route_scale", "route_norm", "rope_theta",
        "rms_norm_eps", "num_dense_layers", "experts_held_first",
        "tokens_per_row")


def specs() -> tuple:
    from benchmark.common import Reference, load_json
    config = load_json("configs", "philly512-trinity.json")
    return tuple({k: Reference(config, rehearse).settings[k] for k in KEYS}
                 for rehearse in (False, True))


def legacy_settings(encoder) -> dict:
    d = encoder["embed"]["kernel"].shape[-1]
    return next(s for s in specs() if s["hidden_size"] == d)
