"""``ops.kda``: the gated delta rule in chunks against the recurrence
written token by token, values and gradients, in float32; the decayed
inner products against the pairwise sum; the causal convolution against a
written-out sum; and that bfloat16 decays or state would not pass. The
kernel pair (``ops.kda_kernel``, interpreted here) against the plain path
and the recurrence at the published head size, values and gradients, and
the table of which build gets which.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlgpuschedule_tpu.ops import kda

B, H, K, V = 2, 3, 8, 8


def recurrence(q, k, v, g, beta, state_dtype=jnp.float32):
    """Token by token, the module docstring's two lines."""
    def token(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = (S + k[..., None] * u[..., None, :]).astype(state_dtype)
        return S, jnp.einsum("bhkv,bhk->bhv", S.astype(jnp.float32), q)

    xs = tuple(z.swapaxes(0, 1) for z in (q, k, v, g, beta))
    S0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                   state_dtype)
    return jax.lax.scan(token, S0, xs)[1].swapaxes(0, 1)


def inputs(T: int, invalid=(), seed: int = 0, lower: float = -5.0,
           B: int = B, H: int = H, K: int = K, V: int = V):
    """A layer's tensors as the trunk makes them: unit k, q of norm
    K^-1/2, g in (lower, 0), beta in (0, 1); tokens in ``invalid`` leave
    the state alone (beta 0, g 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) / np.sqrt(K)
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = lower * jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    there = jnp.ones((T,), bool).at[jnp.asarray(invalid, int)].set(False)
    g = jnp.where(there[None, :, None, None], g, 0.0)
    beta = jnp.where(there[None, :, None], beta, 0.0)
    return q, k, v, g, beta


# chunk sizes that divide T and that do not; tokens passed over at the
# front, inside (across a chunk's edge) and at the end
CASES = [
    (1, 5, ()), (2, 16, ()), (8, 16, ()), (8, 20, ()), (16, 37, ()),
    (64, 37, ()), (8, 24, (0, 1, 2)), (8, 24, (7, 8, 9, 15)),
    (8, 24, (22, 23)), (4, 19, (0, 5, 6, 7, 8, 18)),
]


@pytest.mark.parametrize("chunk,T,invalid", CASES)
def test_chunked_rule_equals_the_recurrence(chunk, T, invalid):
    """Float32 against float32: the two differ by rounding alone (sums in
    another order, a triangular solve for a chain of rank-one updates), so
    1e-5 of the outputs' scale (about 2) holds with room; a wrong decay on
    one chunk edge moves an output by 1e-2 or more."""
    args = inputs(T, invalid)
    with jax.default_matmul_precision("highest"):
        got = kda.chunked_delta_rule(*args, chunk=chunk)
        want = recurrence(*args)
    assert got.shape == want.shape == (B, T, H, V)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("chunk,T,invalid", [CASES[3], CASES[7], CASES[9]])
def test_chunked_rule_has_the_recurrences_gradients(chunk, T, invalid):
    """Autodiff of the chunked form against autodiff of the scan, for all
    five inputs, to 2e-5 of each gradient's largest entry."""
    args = inputs(T, invalid, seed=1)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *a: kda.chunked_delta_rule(
            *a, chunk=chunk)), argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * scale, name


def test_a_token_passed_over_changes_no_later_output():
    """What an invalid token holds in q, k and v is nothing to the tokens
    after it (beta 0 and g 0 there), bit for bit."""
    q, k, v, g, beta = inputs(24, (7, 8, 9, 15))
    other = inputs(24, (), seed=5)
    hole = jnp.zeros((24,), bool).at[jnp.asarray([7, 8, 9, 15])].set(True)
    swap = lambda a, b: jnp.where(hole[None, :, None, None], b, a)
    a = kda.chunked_delta_rule(q, k, v, g, beta, chunk=8)
    b = kda.chunked_delta_rule(swap(q, other[0]), swap(k, other[1]),
                               swap(v, other[2]), g, beta, chunk=8)
    keep = ~np.asarray(hole)
    assert np.array_equal(np.asarray(a)[:, keep], np.asarray(b)[:, keep])


@pytest.mark.parametrize("where", ["every_token", "every_other_token"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_decays_at_the_gates_bound_stay_finite(dtype, tol, where):
    """Every channel at the family's bound, ``g = -5``, on every token or
    on every other one (the widest swings between neighbours): a
    sub-block's keys then decay by up to ``exp(-80)`` in all, the most the
    gate lets them, and a chunk's by ``exp(-320)``, where ``exp(-G)``
    overflows float32. The chunked form still equals the recurrence,
    values AND gradients, with float32 tiles and with the trunk's
    bfloat16 ones (whose ``exp(80)`` factors meet ``exp(-75)`` ones in the
    backward pass's products too): each within ``tol`` of the largest
    entry, the five gradients on their common scale, since bfloat16's
    error in ``dg`` is as large here as at usual decays (4e-3 of 5.9) while
    ``dg`` itself is forty times smaller."""
    T = 150                                             # three chunks
    q, k, v, g, beta = inputs(T, (), seed=2)
    at_bound = jnp.full_like(g, -kda.MAX_LOG_DECAY / kda.SUB)
    g = at_bound if where == "every_token" else jnp.where(
        (jnp.arange(T) % 2 == 0)[None, :, None, None], at_bound, g)
    rule = lambda *a: kda.chunked_delta_rule(*a, chunk=64, dtype=dtype)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    with jax.default_matmul_precision("highest"):
        got = rule(q, k, v, g, beta)
        want = recurrence(q, k, v, g, beta)
        grads = jax.grad(loss(rule), (0, 1, 2, 3, 4))(q, k, v, g, beta)
        wants = jax.grad(loss(recurrence), (0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < tol * float(
        jnp.max(jnp.abs(want)))
    scale = max(float(jnp.max(jnp.abs(x))) for x in wants)
    for name, a, b in zip("qkvgb", grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) < tol * scale, name


def test_a_bound_the_sub_blocks_do_not_cover_is_refused():
    import dataclasses

    from rlgpuschedule_tpu.models.trunk import TRUNKS
    with pytest.raises(ValueError, match="overflow"):
        dataclasses.replace(TRUNKS["ling-tiny"], kda_lower_bound=-6.0)


@pytest.mark.parametrize("C,sub", [(1, 1), (2, 1), (8, 2), (32, 4),
                                   (32, 16), (64, 16), (8, 16)])
def test_decayed_products_against_the_pairwise_sum(C, sub):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(ks[0], (2, 3, C, K))
    b = jax.random.normal(ks[1], (3, C, K))
    G = jnp.cumsum(-3.0 * jax.random.uniform(ks[2], (3, C, K)), axis=-2)
    with jax.default_matmul_precision("highest"):
        got = kda.decayed_products(a.transpose(1, 0, 2, 3), b, G,
                                   jnp.float32, sub=sub)
    pair = jnp.exp(jnp.minimum(G[:, :, None, :] - G[:, None, :, :], 0.0))
    want = jnp.einsum("xhrk,hik,hrik->hxri", a, b, pair)
    want = jnp.where(jnp.tril(jnp.ones((C, C), bool)), want, 0.0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("C,sub", [(1, 1), (4, 1), (16, 16), (64, 16),
                                   (32, 2), (8, 16)])
def test_unit_lower_inverse_against_numpy(C, sub):
    """``(I + A)^-1`` to 1e-5 for strictly lower ``A`` with entries up to
    1 in magnitude (unit keys, no decay: the largest the layer can make),
    against numpy's float64 inverse."""
    A = np.tril(np.asarray(jax.random.uniform(
        jax.random.PRNGKey(6), (3, 2, C, C), minval=-1.0)), -1)
    A = A * (np.arange(C)[:, None] - np.arange(C)[None, :] <= 6)
    got = np.asarray(kda.unit_lower_inverse(jnp.asarray(A), sub=sub))
    want = np.linalg.inv(np.eye(C) + A.astype(np.float64))
    assert np.abs(got - want).max() < 1e-5 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("what", ["decays", "state"])
def test_bfloat16_decays_or_state_would_not_pass(what):
    """The tolerance above is tight enough to see the precision the
    configuration forbids: log-decays rounded to bfloat16, or the state
    kept in bfloat16 between tokens, move an output (of scale 0.1-2) by
    ten times 1e-5 or more."""
    q, k, v, g, beta = inputs(37)
    want = recurrence(q, k, v, g, beta)
    if what == "decays":
        got = kda.chunked_delta_rule(
            q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta,
            chunk=16)
    else:
        got = recurrence(q, k, v, g, beta, state_dtype=jnp.bfloat16)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4


@pytest.mark.parametrize("W", [1, 4])
def test_causal_convolution_against_the_written_out_sum(W):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = np.asarray(jax.random.normal(ks[0], (2, 9, 5)))
    w = np.asarray(jax.random.normal(ks[1], (W, 5)))
    got = np.asarray(kda.causal_conv(jnp.asarray(x), jnp.asarray(w)))
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(W):
            src = t - (W - 1) + j
            if src >= 0:
                want[:, t] += w[j] * x[:, src]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_chunk_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        kda.chunked_delta_rule(*inputs(8), chunk=12)


# ---- the kernel pair (interpreted), at the published head size ------------

D = 128
# T, rows, heads, tokens passed over: 1, 2 and 13 chunks of 64; T no
# multiple of the chunk; 1, 3 and 4 rows (a block of heads is the largest
# divisor of H up to ops.kda_kernel.HEADS: 1, 2 and 3 here); a run of
# tokens passed over across a chunk's edge, and one at the end
KERNEL_CASES = [
    (64, 1, 1, ()), (128, 3, 2, ()), (832, 1, 1, ()), (100, 4, 2, ()),
    (150, 2, 3, tuple(range(58, 75))), (150, 1, 2, tuple(range(120, 150))),
]
FIVE = (0, 1, 2, 3, 4)


def kernel_and(other, dtype):
    rule = lambda path: lambda *a: kda.chunked_delta_rule(
        *a, chunk=64, dtype=dtype, path=path, interpret=True)
    return rule(kda.KERNEL), (recurrence if other == "recurrence"
                              else rule(kda.PLAIN))


def values_and_gradients(fn, args):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a)))
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args), jax.jit(jax.grad(loss, FIVE))(*args)


@pytest.mark.parametrize("other", ["recurrence", "plain"])
@pytest.mark.parametrize("T,rows,heads,invalid", KERNEL_CASES)
def test_kernel_pair_in_float32(T, rows, heads, invalid, other):
    """The kernels on float32 tiles against the token-by-token recurrence
    and against the plain path: outputs and the gradients of all five
    inputs. What separates them is the solve's three bfloat16 passes (16
    bits of mantissa, as the plain path's on a TPU; float32 on this
    backend), so 1e-4 of each one's largest entry where the plain path
    holds 2e-5; a wrong decay on one edge moves an output by 1e-2."""
    args = inputs(T, invalid, seed=3, B=rows, H=heads, K=D, V=D)
    kernel, fn = kernel_and(other, jnp.float32)
    got, grads = values_and_gradients(kernel, args)
    want, wants = values_and_gradients(fn, args)
    assert got.shape == want.shape == (rows, T, heads, D)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    for name, a, b in zip("qkvgb", grads, wants):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
            jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("where", ["usual", "every_token",
                                   "every_other_token"])
@pytest.mark.parametrize("other", ["recurrence", "plain"])
def test_kernel_pair_with_bfloat16_tiles(other, where):
    """The trunk's call: q, k, v and the tile products in bfloat16, at
    usual decays and with every gate at the family's bound ``g = -5`` (on
    every token, or on every other one), where a sub-block's keys decay by
    ``exp(-80)`` and the factors ``exp(80)`` meet ``exp(-75)`` ones in the
    backward products too. Against the plain path on the same bfloat16
    tiles and against the float32 recurrence: finite, and within 1e-2 of
    the largest entry (the five gradients on their common scale), the
    plain path's own tolerance at the bound."""
    T = 150
    q, k, v, g, beta = inputs(T, (40, 41, 42), seed=2, B=2, H=2, K=D, V=D)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    at_bound = jnp.full_like(g, -kda.MAX_LOG_DECAY / kda.SUB)
    there = beta[..., None] > 0
    if where == "every_token":
        g = jnp.where(there, at_bound, 0.0)
    elif where == "every_other_token":
        g = jnp.where(there & (jnp.arange(T) % 2 == 0)[None, :, None, None],
                      at_bound, g)
    kernel, fn = kernel_and(other, jnp.bfloat16)
    if other == "recurrence":
        fn = lambda *a, f=fn: f(*(x.astype(jnp.float32) for x in a))
    got, grads = values_and_gradients(kernel, (q, k, v, g, beta))
    want, wants = values_and_gradients(fn, (q, k, v, g, beta))
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-2 * float(
        jnp.max(jnp.abs(want)))
    f32 = lambda x: x.astype(jnp.float32)
    scale = max(float(jnp.max(jnp.abs(f32(x)))) for x in wants)
    for name, a, b in zip("qkvgb", grads, wants):
        assert a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(f32(a)))), name
        assert float(jnp.max(jnp.abs(f32(a) - f32(b)))) < 1e-2 * scale, name


def test_kernel_passes_over_a_token_as_the_plain_path_does():
    """What a token passed over holds in q, k and v reaches no later
    output through the kernels either, bit for bit."""
    hole = (58, 59, 63, 64, 65, 149)
    q, k, v, g, beta = inputs(150, hole, B=1, H=2, K=D, V=D)
    other = inputs(150, (), seed=5, B=1, H=2, K=D, V=D)
    at = jnp.zeros((150,), bool).at[jnp.asarray(hole)].set(True)
    swap = lambda a, b: jnp.where(at[None, :, None, None], b, a)
    rule = jax.jit(kernel_and("plain", jnp.float32)[0])
    a = rule(q, k, v, g, beta)
    b = rule(swap(q, other[0]), swap(k, other[1]), swap(v, other[2]), g,
             beta)
    keep = ~np.asarray(at)
    assert np.array_equal(np.asarray(a)[:, keep], np.asarray(b)[:, keep])


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("build,path", [
    # backend, K, V, chunk, dtype, mesh bound
    (("tpu", 128, 128, 64, BF16, False), kda.KERNEL),   # the ling cell
    (("tpu", 128, 128, 16, BF16, False), kda.KERNEL),   # one sub-block
    (("tpu", 128, 256, 64, BF16, False), kda.KERNEL),   # whole lane tiles
    (("cpu", 128, 128, 64, BF16, False), kda.PLAIN),    # any other backend
    (("gpu", 128, 128, 64, BF16, False), kda.PLAIN),
    (("tpu", 128, 128, 64, F32, False), kda.PLAIN),     # a float32 build
    (("tpu", 128, 128, 64, BF16, True), kda.PLAIN),     # GSPMD over a mesh
    (("tpu", 16, 16, 8, BF16, False), kda.PLAIN),       # the tiny preset
    (("tpu", 64, 128, 64, BF16, False), kda.PLAIN),     # half a lane tile
    (("tpu", 128, 64, 64, BF16, False), kda.PLAIN),
    (("tpu", 128, 128, 8, BF16, False), kda.PLAIN),     # under a sub-block
])
def test_which_build_gets_the_kernels(build, path):
    assert kda.delta_rule_path(*build) == path


def test_the_published_block_gets_the_kernels_and_the_tiny_one_does_not():
    from rlgpuschedule_tpu.models.trunk import TRUNKS
    answer = lambda c, backend: kda.delta_rule_path(
        backend, c.head_dim, c.head_dim, c.kda_chunk, BF16, False)
    assert answer(TRUNKS["ling"], "tpu") == kda.KERNEL
    assert answer(TRUNKS["ling"], "cpu") == kda.PLAIN
    assert answer(TRUNKS["ling-tiny"], "tpu") == kda.PLAIN
