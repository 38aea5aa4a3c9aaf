"""The chunks of :func:`ops.kda.chunked_delta_rule` as a pair of Pallas TPU
kernels (forward and backward, joined by a ``jax.custom_vjp``): a head's
state and the current chunk's tiles stay in VMEM while the grid walks the
chunks, where the plain path (``ops.kda.plain_chunks``) makes each
chunk-local factor an HBM array of ``[64, 64]`` and ``[16, 16]`` tiles and
sends the state to HBM and back at every step of a ``lax.scan``.

The mathematics, the sub-block form of the exponents and the precision
are ``ops.kda``'s docstring's, factor for factor: the decayed products
through each sub-block's first token, ``(I + A)^-1`` from Neumann products
inside diagonal sub-blocks joined two by two, ``[U0, Wm] = (I + A)^-1
[beta V, beta (K * exp G)]``, then::

    U = U0 - Wm S;   O = (Q * exp G) S + B U;   S <- keep * S + k_out^T U

**The grid** is ``(rows, heads / HEADS, chunks)``: rows and heads
``parallel``, chunks ``arbitrary`` and innermost. The body handles ONE
chunk of ``HEADS`` heads as batched products (the heads' chains of small
products are independent, which is what keeps the matrix unit busy); its
size does not depend on ``T``, rows or heads, and the only loop in it is
the static one over a chunk's sub-blocks. The state is a float32 VMEM
scratch, zeroed at a head's first chunk (walking back: the state's
cotangent, at its last). It is held transposed, ``[V, K]``, so that a row
of ``K`` decays multiplies it along the lanes.

**Backward.** The forward kernel also writes what the backward one reads
again (the inverse, ``[U0, Wm]``, the two decayed products, ``U`` and the
state each chunk started from: 184 KB a head and chunk, which is cheaper
to move than to make twice); the exponents and the products' operands are
made again. The backward kernel walks the chunks from the last to the
first with the state's cotangent in VMEM. The inverse's cotangent is the
closed form ``dA = -(T^T dX) X^T``, not the transpose of the Neumann
products.

**Precision.** ``G``, every ``exp``, ``beta``, the state, its cotangent
and every sum float32. The solve's products (the inverse and what it
multiplies, forward and backward) are three bfloat16 passes with float32
accumulation (:func:`_mm3`: XLA's ``high``, which Mosaic does not take by
name). The tile products take their operands in the inputs' dtype and
accumulate in float32, and such an operand's cotangent is rounded to that
dtype, as the transpose of a product rounds it.

**Set-up.** Each pass is ONE module-level jitted function, so a program
that applies the rule in several layers lowers each kernel body once and
calls it (a ``pallas_call`` is lowered to a Mosaic module where it
stands, site by site, otherwise). Nothing runs at import or at a first
call but the trace; the kernels' names are fixed strings.

This module imports Pallas, so ``ops.kda`` imports it only where the
kernel path is taken.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import SUB

# products of a block's heads, one batch dimension in front
NN = (((2,), (1,)), ((0,), (0,)))       # a b
NT = (((2,), (2,)), ((0,), (0,)))       # a b^T
TN = (((1,), (1,)), ((0,), (0,)))       # a^T b
HEADS = 8       # heads a grid step takes (a block's leading dimension)
_F32, _BF16 = jnp.float32, jnp.bfloat16


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _mm3(a, b, dims):
    """``a b`` for float32 ``a``, ``b`` in three bfloat16 passes (the high
    and low halves of each; the low x low term is left out)."""
    halves = lambda x: (x.astype(_BF16),
                        (x - x.astype(_BF16).astype(_F32)).astype(_BF16))
    (ah, al), (bh, bl) = halves(a), halves(b)
    return _dot(ah, bh, dims) + _dot(al, bh, dims) + _dot(ah, bl, dims)


def _indices(C):
    """Row and column indices of a ``[1, C, C]`` tile."""
    at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (1, C, C), axis)
    return at(1), at(2)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A`` ``[h, C, C]`` strictly lower triangular,
    as ``ops.kda.unit_lower_inverse`` forms it, on whole ``[C, C]`` tiles:
    the Neumann product of the diagonal sub-blocks (a block-diagonal
    matrix's powers stay block-diagonal; a doubling is ONE product, ``[inv;
    power] power``: the inverse's next term and the next power together),
    then the joins, ``(D + L)^-1 = D^-1 - D^-1 L D^-1`` for ``L`` the
    blocks under a level's diagonal."""
    C = A.shape[-1]
    sub = min(SUB, C)
    rows, cols = _indices(C)
    # same diagonal block of `size` (a power of two)
    block = lambda size: (rows >> (size.bit_length() - 1)
                          == cols >> (size.bit_length() - 1))
    N = jnp.where(block(sub), -A, 0.0)
    inv, reach = jnp.where(rows == cols, 1.0, 0.0) + N, 2
    power = _mm3(N, N, NN) if sub > 2 else N            # N^reach
    while reach < sub:      # inv = sum of N^j, j < reach
        if 2 * reach < sub:
            both = _mm3(jnp.concatenate([inv, power], axis=1), power, NN)
            inv, power = inv + both[:, :C], both[:, C:]
        else:
            inv = inv + _mm3(inv, power, NN)
        reach *= 2
    size = sub
    while size < C:
        low = jnp.where(block(2 * size) & ~block(size), A, 0.0)
        inv = inv - _mm3(_mm3(inv, low, NN), inv, NN)
        size *= 2
    return inv


def _column(row):
    """``[h, 1, C]`` -> ``[h, C, 1]`` without a transpose."""
    rows, cols = _indices(row.shape[-1])
    return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=2, keepdims=True)


def _row(column):
    """``[h, C, 1]`` -> ``[h, 1, C]``."""
    rows, cols = _indices(column.shape[-2])
    return jnp.sum(jnp.where(rows == cols, column, 0.0), axis=1,
                   keepdims=True)


def _sub_block(R, sub, kf, qf, G, dtype):
    """Sub-block ``R``'s two operands of the decayed products (``ops.kda``'s
    docstring): its rows of k and q decayed from its first token ``n``,
    ``[h, 2 sub, K]``, and every key up to its end decayed TO ``n``, ``[h,
    C, K]`` (keys behind it are masked BEFORE the exp); with the two
    exponentials, which the backward pass multiplies by again."""
    C = G.shape[1]
    lo, hi = R * sub, (R + 1) * sub
    Gn = G[:, lo:lo + 1]
    EL = jnp.exp(G[:, lo:hi] - Gn)
    left = jnp.concatenate([kf[:, lo:hi] * EL, qf[:, lo:hi] * EL], axis=1)
    upto = jax.lax.broadcasted_iota(jnp.int32, (1, C, 1), 1) < hi
    ER = jnp.where(upto, jnp.exp(jnp.where(upto, Gn - G, 0.0)), 0.0)
    return left.astype(dtype), (kf * ER).astype(dtype), EL, ER


def _decays(G):
    """A token's decay from the chunk's start and to its end, and the
    state's over the whole chunk."""
    last = G[:, -1:]
    return jnp.exp(G), jnp.exp(last - G), jnp.exp(last)


def _forward_body(q, k, v, G_ref, beta, o, T_ref, X_ref, Mkk_ref, B_ref,
                  U_ref, start, state):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    dtype = q.dtype
    C, V = v.shape[1], v.shape[2]
    sub = min(SUB, C)
    kf, qf, G = k[...].astype(_F32), q[...].astype(_F32), G_ref[...]
    b = _column(beta[...])                              # [h, C, 1]
    rows, cols = _indices(C)
    products = []
    for R in range(C // sub):
        left, right, _, _ = _sub_block(R, sub, kf, qf, G, dtype)
        products.append(_dot(left, right, NT))          # [h, 2 sub, C]
    Mkk = jnp.concatenate([m[:, :sub] for m in products], axis=1)
    Bm = jnp.where(rows >= cols, jnp.concatenate(
        [m[:, sub:] for m in products], axis=1), 0.0).astype(dtype)
    T = _unit_lower_inverse(jnp.where(rows > cols, b * Mkk, 0.0))
    decay, to_end, keep = _decays(G)
    X = _mm3(T, jnp.concatenate([b * v[...].astype(_F32),
                                 b * (kf * decay)], axis=-1), NN)
    S = state[...]                                      # [h, V, K]
    Sd = S.astype(dtype)
    U = (X[..., :V] - _dot(X[..., V:].astype(dtype), Sd, NT)).astype(dtype)
    o[...] = _dot((qf * decay).astype(dtype), Sd, NT) + _dot(Bm, U, NN)
    state[...] = keep * S + _dot(U, (kf * to_end).astype(dtype), TN)
    T_ref[...], X_ref[...], Mkk_ref[...] = T, X, Mkk
    B_ref[...], U_ref[...], start[...] = Bm, U, S


def _backward_body(q, k, v, G_ref, beta, do, T_ref, X_ref, Mkk_ref, B_ref,
                   U_ref, start, dq, dk, dv, dG_ref, dbeta, dstate):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    dtype = q.dtype
    C, V = v.shape[1], v.shape[2]
    sub = min(SUB, C)
    rounded = lambda x: x.astype(dtype).astype(_F32)
    kf, qf, G = k[...].astype(_F32), q[...].astype(_F32), G_ref[...]
    vf = v[...].astype(_F32)
    b = _column(beta[...])
    rows, cols = _indices(C)
    T, X, Mkk, Bm, U = T_ref[...], X_ref[...], Mkk_ref[...], B_ref[...], \
        U_ref[...]
    decay, to_end, keep = _decays(G)
    Wm, q_in = X[..., V:].astype(dtype), (qf * decay).astype(dtype)
    k_out = (kf * to_end).astype(dtype)
    # the walk, transposed
    dS, S = dstate[...], start[...]     # dS: of the state this chunk LEFT
    dSd, Sd, dO = dS.astype(dtype), S.astype(dtype), do[...].astype(dtype)
    dU = _dot(Bm, dO, TN) + _dot(k_out, dSd, NT)
    dUd = dU.astype(dtype)
    dBm = rounded(_dot(dO, U, NT))
    dk_out = rounded(_dot(U, dSd, NN))
    dWm = rounded(-_dot(dUd, Sd, NN))
    dq_in = rounded(_dot(dO, Sd, NN))
    dkeep = jnp.sum(dS * S, axis=1, keepdims=True)      # [h, 1, K]
    dstate[...] = (keep * dS + _dot(dO, q_in, TN) - _dot(dUd, Wm, TN))
    # the solve: X = T rhs, T = (I + A)^-1
    drhs = _mm3(T, jnp.concatenate([dU, dWm], axis=-1), TN)
    dA = jnp.where(rows > cols, -_mm3(drhs, X, NT), 0.0)
    KD = kf * decay
    db = (jnp.sum(dA * Mkk, axis=2, keepdims=True)
          + jnp.sum(drhs[..., :V] * vf, axis=2, keepdims=True)
          + jnp.sum(drhs[..., V:] * KD, axis=2, keepdims=True))
    dbeta[...] = _row(db)
    dv[...] = (b * drhs[..., :V]).astype(dv.dtype)
    dKD = b * drhs[..., V:]
    # the decays from the chunk's start and to its end
    dkf = dKD * decay + dk_out * to_end
    dqf = dq_in * decay
    to_end_log = dk_out * kf * to_end
    dG = (dKD * kf + dq_in * qf) * decay - to_end_log
    at_row = lambda r, x: jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, C, 1), 1) == r, x, 0.0)
    dG = dG + at_row(C - 1, jnp.sum(to_end_log, axis=1, keepdims=True)
                     + dkeep * keep)
    # the decayed products, sub-block by sub-block
    dM = jnp.concatenate([b * dA, jnp.where(rows >= cols, dBm, 0.0)],
                         axis=1).astype(dtype)          # [h, 2 C, C]
    dk_rows, dq_rows, dG_rows = [], [], []
    for R in range(C // sub):
        lo, hi = R * sub, (R + 1) * sub
        left, right, EL, ER = _sub_block(R, sub, kf, qf, G, dtype)
        dM_R = jnp.concatenate([dM[:, lo:hi], dM[:, C + lo:C + hi]], axis=1)
        dleft = rounded(_dot(dM_R, right, NN))          # [h, 2 sub, K]
        dright = rounded(_dot(dM_R, left, TN))          # [h, C, K]
        dk_rows.append(dleft[:, :sub] * EL)
        dq_rows.append(dleft[:, sub:] * EL)
        from_first = (dleft[:, :sub] * kf[:, lo:hi]
                      + dleft[:, sub:] * qf[:, lo:hi]) * EL
        dG_rows.append(from_first)
        to_first = dright * kf * ER
        dkf = dkf + dright * ER
        dG = dG - to_first + at_row(lo, jnp.sum(
            to_first, axis=1, keepdims=True) - jnp.sum(
                from_first, axis=1, keepdims=True))
    dk[...] = (dkf + jnp.concatenate(dk_rows, axis=1)).astype(dk.dtype)
    dq[...] = (dqf + jnp.concatenate(dq_rows, axis=1)).astype(dq.dtype)
    dG_ref[...] = dG + jnp.concatenate(dG_rows, axis=1)


def _specs(shapes, heads, chunk_of):
    """One ``[rows, heads, chunks]`` grid point's block of each array
    ``[N, B, H, x, y]``: ``heads`` whole ``[x, y]`` tiles of chunk
    ``chunk_of(n)``."""
    at = lambda b, h, n: (chunk_of(n), b, h, 0, 0)
    return [pl.BlockSpec((None, None, heads, *s[-2:]), at) for s in shapes]


def _call(body, name, ins, outs, chunk_of, interpret):
    N, B, H, C, K = ins[0].shape
    V = ins[2].shape[-1]
    h = max(h for h in range(1, min(HEADS, H) + 1) if H % h == 0)
    return pl.pallas_call(
        body, grid=(B, H // h, N),
        in_specs=_specs([x.shape for x in ins], h, chunk_of),
        out_specs=_specs([x.shape for x in outs], h, chunk_of),
        out_shape=outs, scratch_shapes=[pltpu.VMEM((h, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name, interpret=interpret)(*ins)


@functools.partial(jax.jit, static_argnames="interpret")
def _forward(q, k, v, G, beta, interpret=False):
    N, B, H, C, K = q.shape
    V = v.shape[-1]
    out = lambda x, y, dtype=_F32: jax.ShapeDtypeStruct((N, B, H, x, y),
                                                        dtype)
    outs = (out(C, V), out(C, C), out(C, V + K), out(C, C),     # O T X Mkk
            out(C, C, q.dtype), out(C, V, q.dtype), out(V, K))  # Bm U states
    return _call(_forward_body, "kda_scan_forward", (q, k, v, G, beta),
                 outs, lambda n: n, interpret)


@functools.partial(jax.jit, static_argnames="interpret")
def _backward(q, k, v, G, beta, dO, kept, interpret=False):
    N = q.shape[0]
    outs = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                 for x in (q, k, v, G, beta))
    return _call(_backward_body, "kda_scan_backward",
                 (q, k, v, G, beta, dO, *kept), outs,
                 lambda n: N - 1 - n, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunks(q, k, v, G, beta, interpret):
    """``O`` float32 ``[N, B, H, C, V]`` from a zero state: a head's ``N``
    chunks in order, for ``q, k`` ``[N, B, H, C, K]`` and ``v`` ``[.., C,
    V]`` in the tile products' dtype, the log-decays' running sums ``G``
    ``[.., C, K]`` and ``beta`` ``[.., 1, C]`` float32
    (``ops.kda.plain_chunks``' contract, with ``beta`` as a row).
    ``interpret``: run the kernels interpreted (the tests' way, off the
    chip)."""
    return _forward(q, k, v, G, beta, interpret=interpret)[0]


def _chunks_fwd(q, k, v, G, beta, interpret):
    O, *kept = _forward(q, k, v, G, beta, interpret=interpret)
    return O, (q, k, v, G, beta, tuple(kept))


def _chunks_bwd(interpret, res, dO):
    *ins, kept = res
    return _backward(*ins, dO, kept, interpret=interpret)


chunks.defvjp(_chunks_fwd, _chunks_bwd)
