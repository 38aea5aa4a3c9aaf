"""Blocked attention for the token trunk (``models.trunk.Attention``'s
kernel path): ``softmax(mask(q k^T)) v`` as JAX's splash-attention Pallas
TPU kernels (``jax.experimental.pallas.ops.tpu.splash_attention``), which
hold one tile of scores in VMEM at a time. No ``[.., T, T]`` array is an
HLO value, forward or backward: the backward pass recomputes each tile
from q, k, v and the saved log-sum-exp.

This module imports Pallas, so ``models.trunk`` imports it only where the
kernel path is taken. ``models.trunk.attend`` is the same mathematics
written out, the other platforms' path and this one's oracle
(tests/test_attention_kernel.py).

**Precision.** q and k enter in their own dtype (bfloat16 in the trunk),
scores accumulate in float32, the mask, max, exp and sum are float32. The
kernels take no scale: the caller folds 1/sqrt(D) into q where q is still
float32. Forward, the probabilities stay float32 into the product with v
(splash's own choice; ``attend`` rounds them to v's dtype), the output
accumulates in float32 and is cast once; backward, p and ds are cast to
the cotangent's dtype for their products, as ``attend``'s transpose does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

# One tile size for q and kv, forward and backward (multiples of the 128
# lanes). On the v5e a grid step's fixed cost outweighs the tiles a finer
# grid skips: 128 ran a call in 6.1 ms, 256 in 3.4, 512 in 2.1; one
# 896-tile ties at T = 832 and skips nothing (PERF.md section 6, PR 31).
BLOCK = 512


def tile_size(T: int) -> int:
    """The tile for ``T`` tokens: ``BLOCK``, or ``T`` rounded up to the
    128 lanes where that is smaller (a short row is one tile)."""
    return min(BLOCK, -(-T // 128) * 128)


def padded_length(T: int) -> int:
    block = tile_size(T)
    return -(-T // block) * block


@functools.lru_cache(maxsize=None)
def _kernel(padded: int, window: int | None, heads: int, block: int,
            interpret: bool) -> splash.SplashAttentionKernel:
    """The MQA kernel of one ``(T, window)``: ``heads`` query heads over
    one KV head. Its mask information (which tiles are empty, partial or
    full) is host numpy made here once, not a layer call's work, and is
    kept as numpy so that no trace's values outlive the trace."""
    mask = masks.CausalMask((padded, padded))
    if window is not None and window < padded:
        # LocalMask keeps |q - k| <= left: q - k < window is left = w - 1
        mask &= masks.LocalMask((padded, padded), (window - 1, None), 0)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)       # dq with dk and dv: 5 products
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mqa_single_device(
            masks.MultiHeadMask([mask] * heads), block_sizes=sizes,
            interpret=interpret)
    return jax.tree.map(np.asarray, kernel)


def tiles_computed_share(T: int, window: int | None) -> float:
    """Tiles the forward kernel computes over tiles in the padded grid,
    from the kernel's own mask information: a tile no (q, k) pair of
    which passes the causal and window masks is skipped. Key padding is
    data, not structure: it empties no tile."""
    padded = padded_length(T)
    info = _kernel(padded, window, 1, tile_size(T), False).fwd_mask_info
    return float(np.count_nonzero(info.block_mask)) / (
        info.block_mask.shape[0] * (padded // tile_size(T)) ** 2)


def blocked_attend(q, k, v, valid, window, *, interpret=None):
    """``models.trunk.attend``'s contract through the kernel: q ``[b, T,
    Hkv, G, D]`` (ALREADY times 1/sqrt(D)), k/v ``[b, T, Hkv, D]``, valid
    ``[b, T]`` -> ``[b, T, Hkv, G, D]``. Key k is seen by query q iff ``k
    <= q``, ``q - k < window`` (``window`` not None) and ``valid[k]``,
    for every query, valid or not.

    The ``G`` query heads of a KV head go through one call against that
    head's k and v, which are not repeated. ``T`` is padded to whole
    tiles: padded keys are masked like invalid ones, padded queries
    sliced off. Key padding rides the kernel's segment ids: every query
    carries id 1 and key k carries ``valid[k]``.

    **A query with no visible key** (only an invalid token can have
    none) gets the mean of v over the keys of the tiles the kernel
    computed for it, a finite value, where ``attend`` gives the mean over
    all T keys; its gradient is as finite. Neither is meaningful, and
    nothing downstream weighs an invalid token's state.

    ``interpret`` None: interpret the kernel where the backend is not a
    TPU (tests), run it where it is."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, G = q.shape[1], q.shape[3]
    padded = padded_length(T)
    kernel = _kernel(padded, window, G, tile_size(T), interpret)
    return run_kernel(kernel, padded, q, k, v, valid)


def run_kernel(kernel, padded: int, q, k, v, valid):
    """``blocked_attend`` given its kernel and padded length (the chip
    sweep's way in: ``chip_attention.py`` brings other tiles)."""
    b, T = valid.shape
    pad = lambda a: jnp.pad(
        a, [(0, 0)] * (a.ndim - 2) + [(0, padded - T), (0, 0)])
    qh = pad(q.transpose(0, 2, 3, 1, 4))              # [b, Hkv, G, T', D]
    kh = pad(k.transpose(0, 2, 1, 3))                 # [b, Hkv, T', D]
    vh = pad(v.transpose(0, 2, 1, 3))
    ids = splash.SegmentIds(
        q=jnp.ones((b, padded), jnp.int32),
        kv=jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, padded - T))))
    over_kv_heads = jax.vmap(
        lambda q, k, v, ids: kernel(q, k, v, segment_ids=ids),
        in_axes=(0, 0, 0, None))
    out = jax.vmap(over_kv_heads)(qh, kh, vh, ids)    # [b, Hkv, G, T', D]
    return out[..., :T, :].transpose(0, 3, 1, 2, 4)
