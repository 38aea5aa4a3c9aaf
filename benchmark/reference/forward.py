"""Plain float32 forward passes of the policy networks, one trunk module
per reference a configuration's file names (``forward_<name>.py``: its
``trunk(encoder, obs, quant, settings)`` and ``forward_flops_per_row(params,
settings)`` get the file's settings as an argument); ``jax.numpy``/
``jax.lax`` only, no flax, no program import. ``ref`` below is that
reference as ``benchmark.common.Reference`` resolved it, once, from the
file. Called under ``jax.default_matmul_precision("highest")`` by
:func:`forward` (on a TPU a float32 matmul is otherwise one bf16 pass).

``quant`` is the lower-precision control's hook: it is applied to every
operand of every matmul/convolution (activations and weights). ``None``
is the reference itself.

Published layer equations (the repo's ``models/``): per block
``silu(LayerNorm_eps1e-6(op(x) + b))`` with LayerNorm over the channel
axis; grid trunk = conv3x3(32, stride 1) -> conv3x3(64, stride (2,1)) ->
conv3x3(64, stride (2,1)), SAME padding, flatten, dense 256 block; flat
trunk = two dense-256 blocks; heads = dense(n_actions) and dense(1) on the
trunk output; infeasible actions get logit -1e9.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9
LN_EPS = 1e-6


def fp8_quant(x):
    """Per-tensor scaled float8 (e4m3) fake quantisation, straight-through
    in the backward pass: the most careful 8-bit float a later PR could
    swap in for the configuration's bf16 activations."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def bf16_quant(x):
    """The configuration's own stated precision (bf16 operands), for
    reading how far stated-precision rounding alone moves each number."""
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


QUANT = {"none": None, "fp8": fp8_quant, "bf16": bf16_quant}


def _q(x, quant):
    return x if quant is None else quant(x)


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(jnp.maximum(var, 0.0) + LN_EPS)
    return y * p["scale"] + p["bias"]


def dense(x, p, quant):
    return _q(x, quant) @ _q(p["kernel"], quant) + p["bias"]


def conv(x, p, strides, quant):
    y = jax.lax.conv_general_dilated(
        _q(x, quant), _q(p["kernel"], quant), window_strides=strides,
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def forward(ref, params, obs, mask, quant=None):
    """``(masked_logits f32[B, A], value f32[B])`` for rows ``obs[B, ...]``."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        h = ref.trunk(p["encoder"], obs, quant)
        logits = dense(h, p["policy"], quant)
        value = dense(h, p["value"], quant)[..., 0]
    return jnp.where(mask, logits, NEG_INF), value


def log_prob(logits, action):
    logp = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(logp, action[..., None], axis=-1)[..., 0]


def entropy(logits):
    logp = jax.nn.log_softmax(logits)
    p = jnp.exp(logp)
    return -jnp.sum(p * jnp.where(p > 0, logp, 0.0), axis=-1)


def forward_blocks(ref, params, obs, mask, block, quant=None):
    """Forward over many rows in blocks of ``block`` (one ``lax.map``), so
    float32 activations of a block, not of the whole set, are live."""
    n = obs.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        obs = jnp.concatenate([obs, jnp.zeros((pad, *obs.shape[1:]),
                                              obs.dtype)])
        mask = jnp.concatenate([mask, jnp.ones((pad, *mask.shape[1:]),
                                               mask.dtype)])
    obs = obs.reshape(nb, block, *obs.shape[1:])
    mask = mask.reshape(nb, block, *mask.shape[1:])
    logits, value = jax.lax.map(
        lambda om: forward(ref, params, om[0], om[1], quant),
        (obs, mask))
    return (logits.reshape(nb * block, -1)[:n], value.reshape(-1)[:n])
