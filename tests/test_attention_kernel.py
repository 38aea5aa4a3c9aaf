"""The token trunk's blocked attention (``ops.attention``), the Pallas
kernels in interpret mode on the CPU, held to ``models.trunk.attend``:
the same mask for every query, the same output and gradients; which
build takes which path; what the counters say of it.

What only the chip sees: the Mosaic compile of the kernels (rehearsed for
a described v5e in tests/test_tpu_compile.py) and their bfloat16 products
on the MXU (``chip_attention.py``, on the chip).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import weights
from rlgpuschedule_tpu.models import TRUNKS
from rlgpuschedule_tpu.models import trunk as trunk_lib
from rlgpuschedule_tpu.models.actor_critic import ActorCritic
from rlgpuschedule_tpu.ops import attention
from tests.test_trunk import A, F, observations, reference

D = 128                       # the published head size: the kernel's tiles
# the tiny trunk at a head size the kernel takes; window 8 < any T here
WIDE = dataclasses.replace(TRUNKS["tiny"], head_dim=D)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128, so that a CPU-sized T spans several and the mask
    empties some (the production tile is chosen for the chip's speed)."""
    monkeypatch.setattr(attention, "BLOCK", 128)


@pytest.fixture
def kernel_path(monkeypatch):
    """Every build takes the kernel path, as on a TPU (the kernels are
    interpreted where the backend is not one)."""
    monkeypatch.setattr(trunk_lib, "attention_path",
                        lambda *observed: trunk_lib.KERNEL)


def qkv(T: int, G: int, dtype=jnp.float32, b: int = 2, Hkv: int = 2,
        seed: int = 0, p_valid: float = 0.6):
    """Inputs as ``Attention`` hands them over, and a ``valid`` with
    invalid tokens anywhere, the first ones (the nodes) included."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, T, Hkv, G, D), dtype)
    k = jax.random.normal(ks[1], (b, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (b, T, Hkv, D), dtype)
    valid = jax.random.bernoulli(ks[3], p_valid, (b, T))
    w = jax.random.normal(ks[4], (b, T, Hkv, G, D))
    return q, k, v, valid, w


def both_paths(q, k, v, valid, w, window):
    """(output, gradients to q, k, v) of ``attend`` and of the kernel,
    under a loss that weighs every query that sees a key."""
    scale = 1.0 / math.sqrt(D)
    sees = jnp.any(trunk_lib.attention_mask(valid, window), axis=-1)
    w = w * sees[:, :, None, None, None]

    def of(attn):
        def loss(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return out, grads

    with jax.default_matmul_precision("highest"):
        plain = of(lambda q, k, v: trunk_lib.attend(q, k, v, valid, window))
        kernel = of(lambda q, k, v: attention.blocked_attend(
            (q * scale).astype(q.dtype), k, v, valid, window))
    return plain, kernel, sees


def gap(a, b, queries=None):
    """Largest difference, over the ``queries`` ``[b, T]`` if given."""
    d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
    if queries is not None:
        d = d * queries[:, :, None, None, None]
    return float(jnp.max(d))


# tile 128: T 200 is not whole tiles (padded to 256), T 256 is; windows
# that cut inside a tile, across tiles, and not at all; tile 512 (the
# production one): T 640 pads to 1,024, a 2 x 2 grid
CASES = [(128, T, G, window)
         for T in (200, 256) for G in (1, 8)
         for window in (None, 8, 64, 4096)] + [
    (512, 640, 8, None), (512, 640, 8, 64), (512, 1024, 1, 600)]


@pytest.mark.parametrize("block,T,G,window", CASES)
def test_kernel_equals_attend_in_float32(monkeypatch, block, T, G, window):
    """Output (every query that sees a key, valid or not) and the
    gradients to q, k and v within 1e-4 of ``attend`` at float32
    ``highest``; a query with no visible key stays finite."""
    monkeypatch.setattr(attention, "BLOCK", block)
    q, k, v, valid, w = qkv(T, G, seed=T + G)
    (out, grads), (k_out, k_grads), sees = both_paths(q, k, v, valid, w,
                                                      window)
    assert attention.padded_length(T) % block == 0
    assert bool(jnp.all(jnp.isfinite(k_out)))
    assert gap(k_out, out, sees) < 1e-4
    for name, a, b in zip("qkv", k_grads, grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert gap(a, b) < 1e-4, name


def test_a_query_with_no_visible_key_stays_finite(small_tiles):
    """The first tokens invalid: their queries see nothing (and nothing
    sees them). Output and all three gradients stay finite under a loss
    that weighs EVERY query, and the queries that do see a key read what
    ``attend`` reads."""
    T = 200
    q, k, v, valid, w = qkv(T, 2, seed=5)
    valid = valid.at[:, :9].set(False)
    scale = 1.0 / math.sqrt(D)
    for window in (None, 8):
        sees = jnp.any(trunk_lib.attention_mask(valid, window), axis=-1)
        assert not bool(jnp.any(sees[:, :9]))

        def loss(q, k, v):
            out = attention.blocked_attend(q * scale, k, v, valid, window)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in (out, *grads))
        with jax.default_matmul_precision("highest"):
            want = trunk_lib.attend(q, k, v, valid, window)
        assert gap(out, want, sees) < 1e-4


def test_kernel_sliding_layers_see_the_window_only(small_tiles):
    """The kernel's twin of test_trunk's: at window 8 a key further back
    than the window leaves a sliding layer's output bit-equal and moves a
    full layer's."""
    T = 200
    q, k, v, valid, _ = qkv(T, 2, seed=11, p_valid=1.0)
    valid = valid.at[:, 6].set(False)
    q = q / math.sqrt(D)
    for window in (8, None):
        out = attention.blocked_attend(q, k, v, valid, window)
        moved = attention.blocked_attend(q, k.at[:, 2].add(3.0), v, valid,
                                         window)
        far = float(jnp.max(jnp.abs(moved[:, 15:] - out[:, 15:])))
        assert (far == 0.0) == (window is not None), (window, far)
        # an invalid key moves nothing, whatever the window
        hidden = attention.blocked_attend(q, k.at[:, 6].add(3.0),
                                          v.at[:, 6].add(3.0), valid, window)
        assert float(jnp.max(jnp.abs(hidden - out))) == 0.0


def wide_policy(dtype, T: int, seed: int = 7):
    net = ActorCritic(trunk_lib.TokenTrunk(WIDE, dtype=dtype), A)
    shapes = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, T, F)), jnp.ones((1, A), bool))
    return net, weights.make_params(shapes, seed)


def test_bfloat16_trunk_on_the_kernel_stays_near_the_reference(
        kernel_path, small_tiles):
    """The whole bfloat16 policy with every attention layer on the kernel
    against the benchmark's float32 reference, by the tolerances
    ``test_bfloat16_program_stays_near_the_reference`` holds the plain
    path to."""
    T = 150
    net, params = wide_policy(jnp.bfloat16, T)
    obs = observations(jax.random.PRNGKey(2), 8, T)
    mask = jnp.ones((8, A), bool)
    logits, value = net.apply(params, obs, mask)
    r_logits, r_value = reference(params, obs, mask, WIDE)
    spread = float(jnp.std(r_value))
    assert float(jnp.max(jnp.abs(value - r_value))) < 0.05 * max(spread, 1.0)
    assert float(jnp.max(jnp.abs(logits - r_logits))) < 5e-4


PATHS = [  # backend, head size, mesh bound -> path
    ("tpu", 128, False, trunk_lib.KERNEL),
    ("tpu", 256, False, trunk_lib.KERNEL),
    ("tpu", 128, True, trunk_lib.PLAIN),       # GSPMD over a mesh
    ("tpu", 32, False, trunk_lib.PLAIN),       # the tiny trunk
    ("tpu", 64, False, trunk_lib.PLAIN),
    ("tpu", 192, False, trunk_lib.PLAIN),
    ("cpu", 128, False, trunk_lib.PLAIN),
    ("gpu", 128, False, trunk_lib.PLAIN),
]


@pytest.mark.parametrize("backend,head_dim,mesh_bound,path", PATHS)
def test_attention_path_table(backend, head_dim, mesh_bound, path):
    assert trunk_lib.attention_path(backend, head_dim, mesh_bound) == path


def test_this_backend_takes_the_plain_path_and_a_bound_mesh_is_seen():
    """What ``Attention`` hands ``attention_path``: this backend, and
    whether the step is traced under ``parallel.sharding.bind_mesh``."""
    from rlgpuschedule_tpu.parallel.sharding import active_mesh, bind_mesh
    assert trunk_lib.attention_path(jax.default_backend(), D,
                                    active_mesh() is not None) \
        == trunk_lib.PLAIN
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    assert bind_mesh(lambda: active_mesh() is not None, mesh)()
    assert active_mesh() is None


def brute_force_share(T: int, window, block: int) -> float:
    """Tiles of the padded grid that hold a (q, k) pair ``attention_mask``
    lets through (all keys valid), over all tiles."""
    padded = -(-T // block) * block
    seen = np.asarray(trunk_lib.attention_mask(jnp.ones((padded,), bool),
                                               window))
    n = padded // block
    tiles = seen.reshape(n, block, n, block).any(axis=(1, 3))
    return float(tiles.sum()) / n ** 2


@pytest.mark.parametrize("T,window", [(300, None), (300, 8), (300, 130),
                                      (640, 256), (128, None), (90, 8)])
def test_tiles_computed_share_is_the_masks_own_count(small_tiles, T, window):
    assert attention.tiles_computed_share(T, window) == pytest.approx(
        brute_force_share(T, window, attention.tile_size(T)))
    assert attention.padded_length(T) - T < attention.tile_size(T)


def counters_of(T: int) -> dict:
    net = trunk_lib.TokenTrunk(WIDE, dtype=jnp.float32)
    obs = observations(jax.random.PRNGKey(3), 4, T)
    params = net.init(jax.random.PRNGKey(0), obs[:1])
    _, sown = net.apply(params, obs, mutable=[trunk_lib.COUNTERS])
    return {k: float(v) for k, v in trunk_lib.read_counters(
        sown[trunk_lib.COUNTERS]).items()}


def test_counters_read_zero_on_the_plain_path():
    c = counters_of(40)
    assert c["attn_kernel_layers"] == 0.0
    assert c["attn_tiles_computed_share"] == 0.0
    assert c["moe_dropped_assignments"] == 0.0


def test_counters_read_the_masks_count_on_the_kernel_path(kernel_path,
                                                          small_tiles):
    """Five attention layers, four of them sliding (window 8): the
    share is the mean of the layers' own."""
    T = 300
    c = counters_of(T)
    assert c["attn_kernel_layers"] == 5.0
    sliding = brute_force_share(T, WIDE.sliding_window, 128)
    full = brute_force_share(T, None, 128)
    assert sliding < full < 1.0
    assert c["attn_tiles_computed_share"] == pytest.approx(
        (4 * sliding + full) / 5)
