"""The gated delta rule of a KDA layer (Kimi Delta Attention: a linear
attention whose state is a matrix a head, decayed channel by channel),
computed in chunks, in two lowerings of one mathematics
(:func:`delta_rule_path` says which a build gets): on a TPU, at the
published block's shapes, a pair of Pallas kernels that keep a head's state
and a chunk's tiles in VMEM (``ops.kda_kernel``: forward, and a backward
pass written out); everywhere else plain ``jax.numpy``
(:func:`plain_chunks`), whose backward pass is autodiff of the chunked form
and which is the kernels' oracle in the tests.

A head's state ``S`` is ``[K, V]`` (key channels x value channels), zero
before the first token. Token ``t`` brings ``q_t, k_t`` ``[K]``, ``v_t``
``[V]``, a log-decay a key channel ``g_t <= 0`` ``[K]`` and a write
strength ``beta_t`` in [0, 1]::

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A token with ``beta_t = 0`` and ``g_t = 0`` leaves the state as it was
(the caller's way of passing over a token that is not there).

**In chunks** of ``C`` tokens (``G_r`` = the sum of ``g`` over the chunk's
tokens up to and including ``r``, in float32; ``S_0`` the state the chunk
starts from). With ``u_r = beta_r (v_r - (diag(exp g_r) S_{r-1})^T k_r)``,
the value token ``r`` really writes, the recurrence unrolls to::

    (I + A) U = beta * (V - (K * exp G) S_0)
    O = (Q * exp G) S_0 + B U
    S_C = diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

with ``A[r, i] = beta_r <k_r, k_i>_{r,i}`` for ``i < r``, ``B[r, i] = <q_r,
k_i>_{r,i}`` for ``i <= r`` (0 elsewhere), and ``<a, b>_{r,i} = sum_c a[c]
b[c] exp(G_r[c] - G_i[c])``. ``A`` is
strictly lower triangular, so ``U`` is one unit-lower-triangular solve a
head and chunk, against the two right-hand sides ``beta V`` and ``beta (K
* exp G)``; everything else is a matrix product over ``[C, K]`` tiles, and
the state crosses chunks in a scan of ``T / C`` steps.

**Exponents.** ``exp(G_r - G_i)`` does not factor into ``exp(G_r)
exp(-G_i)`` safely (64 tokens at ``g = -5`` is ``exp(320)``), and pair by
pair it is a ``[C, C, K]`` array a head and chunk. So ``<a, b>`` goes
through the first token ``n`` of ``r``'s sub-block of ``SUB`` tokens, as
flash-linear-attention's ``kda`` does: ``(a_r exp(G_r - G_n)) . (b_i
exp(G_n - G_i))``, ONE batched product of ``[SUB, K] x [K, C]`` tiles a
sub-block. The first exponent is never positive; the second is never
positive for a key in an earlier sub-block, and for a key inside ``r``'s
own it is at most ``SUB x max|g|``: 80 at the family's ``kda_lower_bound``
-5, where float32 and bfloat16 alike hold ``exp`` up to 88. **That bound
is the gate's** (``kda_safe_gate``: ``g`` in ``(kda_lower_bound, 0)`` by
construction); :class:`models.trunk.LingConfig` refuses a bound it does
not cover. Every other exponent here (from a chunk's start, to a chunk's
end) is of a non-positive number. Keeping EVERY exponent non-positive
(halving down to sub-blocks, pair by pair inside them) is possible and
was measured: its ``[SUB, SUB, K]`` arrays cost 8.0 s of a 17.7 s
iteration on the chip against 3.7 of 12.4 this way (PERF.md section 6,
PR 39).

**The solve** is ``U = (I + A)^-1 [beta V, beta (K * exp G)]`` with the
inverse formed explicitly (:func:`unit_lower_inverse`): the finite Neumann
product inside ``SUB``-token diagonal blocks (exact, ``A`` being
nilpotent; at most ``SUB - 1`` = 15 factors of entries under 1 in
magnitude, so no growth to speak of), then blocks joined two by two by the
block-triangular inverse. Products of ``[16, 16]`` to ``[64, 64]`` tiles
at ``high`` precision (three bfloat16 passes, 16 bits of mantissa: ``U``
is rounded to ``dtype`` as the next product's operand), where XLA's
``triangular_solve`` ran a row-by-row inversion a head and chunk that
took 40 % of the scope on the chip (PERF.md section 6, PR 39).

**Precision.** ``g``, its sums, every ``exp``, ``beta``, the solve and the
carried state are float32. The tile products take their operands in
``dtype`` (bfloat16 in the trunk, float32 in a float32 build) and
accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64      # tokens a chunk: one [64, 64] solve a head
SUB = 16        # tokens a sub-block: SUB x max|g| must stay under 88
MAX_LOG_DECAY = 80.0    # what a sub-block's keys may decay by, in all
KERNEL, PLAIN = "kernel", "plain"       # delta_rule_path's two answers


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution over tokens, no bias: ``x[..., T, C]``,
    ``w[W, C]`` -> ``y_t = sum_j w[j] x_{t - (W - 1) + j}`` (``w[W - 1]``
    multiplies the token itself; tokens before the first are zeros)."""
    W, T = w.shape[0], x.shape[-2]
    pad = [(0, 0)] * (x.ndim - 2) + [(W - 1, 0), (0, 0)]
    xp = jnp.pad(x, pad)
    return sum(xp[..., j:j + T, :] * w[j].astype(x.dtype) for j in range(W))


def decayed_products(a, b, G, dtype, sub: int = SUB):
    """``M[.., x, r, i] = sum_c a[.., x, r, c] b[.., i, c] exp(G[.., r, c] -
    G[.., i, c])`` for ``i <= r``, 0 elsewhere: ``a`` ``[.., X, C, K]`` (X
    left-hand sides against one ``b``), ``b``, ``G`` ``[.., C, K]``, ``G``
    non-increasing along ``C``. Float32 ``[.., X, C, C]``: one batched
    product through each sub-block's first token (module docstring)."""
    *lead, X, C, K = a.shape
    f32 = jnp.float32
    sub = min(sub, C)
    s = C // sub
    blocks = lambda z: z.reshape(*z.shape[:-2], s, sub, K)
    Gb = blocks(G)
    Gn = Gb[..., :1, :]                                 # [.., s, 1, K]
    left = blocks(a) * jnp.exp(Gb - Gn)[..., None, :, :, :]
    # key i against the rows of sub-block R: through R's first token n.
    # G_n - G_i <= 0 before R and in [0, sub * max|g|] inside it; keys
    # behind R are masked BEFORE the exp (theirs would grow without bound)
    upto = (jnp.arange(C)[None, :]
            < ((jnp.arange(s) + 1) * sub)[:, None])[..., None]  # [s, C, 1]
    gap = jnp.where(upto, Gn - G[..., None, :, :], 0.0)         # [.., s, C, K]
    right = jnp.where(upto, b[..., None, :, :] * jnp.exp(gap), 0.0)
    M = jnp.einsum("...xsrk,...sik->...xsri", left.astype(dtype),
                   right.astype(dtype), preferred_element_type=f32)
    return jnp.where(jnp.tril(jnp.ones((C, C), bool)),
                     M.reshape(*lead, X, C, C), 0.0)


def unit_lower_inverse(A, sub: int = SUB):
    """``(I + A)^-1`` for ``A`` ``[.., C, C]`` strictly lower triangular,
    float32, ``C`` and ``sub`` powers of two. Inside diagonal blocks of
    ``sub`` the finite Neumann product ``(I + N)(I + N^2)(I + N^4)...``,
    ``N = -A`` (exact: ``N^sub = 0``); then blocks are joined two by two,
    ``[[P, 0], [L, Q]]^-1 = [[P', 0], [-Q' L P', Q']]``. Every product at
    ``high`` precision: this is the solve, not a tile product."""
    *lead, C, _ = A.shape
    sub = min(sub, C)
    mm = lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGH)

    def diagonal_blocks(size):      # [.., C / size, size, size] of A
        # one masked sum, not n slices stacked: with those the compiled
        # train step was five times the size (PERF.md section 6, PR 39)
        n = C // size
        own = jnp.eye(n, dtype=A.dtype)[:, None, :, None]
        return jnp.sum(A.reshape(*lead, n, size, n, size) * own, axis=-2)

    N = -diagonal_blocks(sub)
    eye = jnp.eye(sub, dtype=A.dtype)
    inv, power, reach = eye + N, N, 2
    while reach < sub:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        reach *= 2
    h = sub
    while h < C:
        first, second = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = diagonal_blocks(2 * h)[..., h:, :h]
        join = -mm(second, mm(low, first))
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([join, second], axis=-1)], axis=-2)
        h *= 2
    return inv[..., 0, :, :]


def delta_rule_path(backend: str, K: int, V: int, chunk: int, dtype,
                    mesh_bound: bool) -> str:
    """Which lowering of the chunks a build gets (module docstring):
    ``KERNEL`` iff the backend is a TPU, a head's state is made of whole
    128-lane tiles both ways, a chunk is whole sub-blocks (whose bfloat16
    tiles fill their 16 sublanes), the products' operands are bfloat16 (a
    float32 build wants products the kernel does not make) and the
    program is not one GSPMD partitions over a mesh (a Mosaic custom call
    is not partitioned for us). Any ``T``, any number of rows and heads:
    the grid runs over them."""
    fits = (backend == "tpu" and K % 128 == 0 and V % 128 == 0
            and chunk % SUB == 0 and jnp.dtype(dtype) == jnp.bfloat16
            and not mesh_bound)
    return KERNEL if fits else PLAIN


def plain_chunks(q, k, v, G, beta, dtype):
    """The chunks in order, written out (module docstring), for ``q, k``
    ``[N, B, H, C, K]`` and ``v`` ``[.., C, V]`` in ``dtype``, the decays'
    running sums ``G`` ``[.., C, K]`` and ``beta`` ``[.., C, 1]`` float32;
    ``O`` float32 ``[N, B, H, C, V]``. ``ops.kda_kernel.chunks``'
    contract, and its oracle."""
    _, B, H, C, K = q.shape
    V = v.shape[-1]
    f32 = jnp.float32
    M = decayed_products(jnp.stack([k, q], axis=-3), k, G, dtype)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict, beta * M[..., 0, :, :], 0.0)
    Bm = M[..., 1, :, :]
    decay = jnp.exp(G)                              # from the chunk's start
    rhs = jnp.concatenate([beta * v.astype(f32),
                           beta * (k.astype(f32) * decay)], axis=-1)
    X = jnp.matmul(unit_lower_inverse(A), rhs,
                   precision=jax.lax.Precision.HIGH)
    U0, Wm = X[..., :V], X[..., V:]                 # [.., C, V], [.., C, K]
    q_in = (q.astype(f32) * decay).astype(dtype)
    last = G[..., -1:, :]                               # [.., 1, K]
    k_out = (k.astype(f32) * jnp.exp(last - G)).astype(dtype)
    keep = jnp.exp(last)[..., 0, :, None]               # [.., K, 1]
    mm = lambda eq, x, y: jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                                     preferred_element_type=f32)

    def step(S, c):
        U0, Wm, Bm, q_in, k_out, keep = c
        U = U0 - mm("bhck,bhkv->bhcv", Wm, S)
        O = mm("bhck,bhkv->bhcv", q_in, S) + mm("bhci,bhiv->bhcv", Bm, U)
        S = keep * S + mm("bhck,bhcv->bhkv", k_out, U)
        return S, O

    return jax.lax.scan(step, jnp.zeros((B, H, K, V), f32),
                        (U0, Wm, Bm, q_in, k_out, keep))[1]


def chunked_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                       dtype=jnp.float32, path: str = PLAIN,
                       interpret: bool | None = None):
    """The recurrence of the module docstring for ``q, k, g`` ``[B, T, H,
    K]``, ``v`` ``[B, T, H, V]``, ``beta`` ``[B, T, H]``, from a zero
    state; returns ``o`` float32 ``[B, T, H, V]``. ``T`` is padded to
    whole chunks with tokens that leave the state alone. ``path``: the
    chunks' lowering, :func:`delta_rule_path`'s answer for this build
    (``interpret`` None: interpret the kernels where the backend is not a
    TPU, the tests' way; run them where it is)."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    C = chunk
    if C < 1 or C & (C - 1):
        raise ValueError(f"chunk {C} is not a power of two")
    # g is the caller's promise (the gate bounds it); nothing here checks
    # a traced value
    N = -(-T // C)
    f32 = jnp.float32

    def tiles(z):       # [B, T, H, X] -> [N, B, H, C, X], zero padded
        z = jnp.pad(z, ((0, 0), (0, N * C - T), (0, 0), (0, 0)))
        return z.reshape(B, N, C, H, -1).transpose(1, 0, 3, 2, 4)

    q, k, v = tiles(q), tiles(k), tiles(v)
    G = jnp.cumsum(tiles(g.astype(f32)), axis=-2)       # [N, B, H, C, K]
    beta = tiles(beta.astype(f32)[..., None])           # [N, B, H, C, 1]
    if path == KERNEL:
        from . import kda_kernel        # Pallas: this path only
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        O = kda_kernel.chunks(q.astype(dtype), k.astype(dtype),
                              v.astype(dtype), G,
                              jnp.swapaxes(beta, -1, -2), interpret)
    else:
        O = plain_chunks(q, k, v, G, beta, dtype)
    return O.transpose(1, 0, 3, 2, 4).reshape(B, N * C, H, V)[:, :T]
