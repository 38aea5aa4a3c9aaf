"""The main path's jitted programs compile for the chip, without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED topology
(``v5e:2x2`` -> 4 x "TPU v5 lite"), so these guard every later PR at no
chip time: a program the chip's compiler would refuse, or one that no
longer fits 16 GB of HBM, fails here first. Shapes are the real ones
``chip_smoke.py`` runs (config 2, 512 GPUs, 256 envs x 768-job windows,
queue 128, 128-step rollouts). Nothing executes, so nothing here says
anything about results or speed.

These are not two-second kernels: a whole train step costs ~35 s to
compile whatever the batch (the sim's control flow, not the shapes) on top
of ~15 s of host-side ``Experiment.build``, so exactly one is kept, next
to the serve engine's ``policy_step``. The update stage alone is not
cheap (26 s) and the train step contains it, so it is not kept. Each test
has its own time limit; the limit cannot interrupt a compile, it fails
the test that overran. The four-chip mesh compile stays a builder's
rehearsal (CHANGES.md, PR 24).
"""
import contextlib
import dataclasses
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from rlgpuschedule_tpu.experiment import Experiment

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip


@contextlib.contextmanager
def time_limit(seconds: float):
    t0 = time.monotonic()
    yield
    took = time.monotonic() - t0
    assert took <= seconds, f"took {took:.0f}s, limit {seconds}s"


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip as a sharding; skips where the
    topology cannot be described. The persistent compile cache is off
    around the module: a described-topology executable is written to it
    but can never be read back without a chip, so every later run would
    warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    assert {d.device_kind for d in topo.devices} == {"TPU v5 lite"}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def exp():
    cfg = chip_smoke.smoke_cfg(chip_smoke.FULL)    # exactly what it runs
    assert cfg.name == "ppo-cnn-philly512" and cfg.total_gpus == 512
    assert cfg.ppo.n_steps * cfg.n_envs == 32_768
    return Experiment.build(cfg, jit=False)


def shapes(tree, sharding):
    """The tree as ShapeDtypeStructs on the described chip (there is no
    device to hold an array, so programs are lowered from shapes)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_config2_train_step_compiles_and_fits_hbm(one_chip, exp):
    """Rollout scan (the sim's while_loop event advance inside), GAE and
    the epoch x minibatch update as the ONE donated program train runs."""
    with time_limit(150):
        compiled = jax.jit(exp.train_step_raw, donate_argnums=(0, 1)).lower(
            shapes(exp.train_state, one_chip), shapes(exp.carry, one_chip),
            shapes(exp.traces, one_chip),
            shapes(jax.random.PRNGKey(0), one_chip), None).compile()
    assert device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


# the actions output is smaller than the donated request buffers; the
# engine filters the same compile-time note at its warm-up dispatch
@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_config2_serve_policy_step_compiles(one_chip, exp):
    """The serve engine's donated ``policy_step`` at bucket 16."""
    from rlgpuschedule_tpu.serve.engine import InferenceEngine
    engine = InferenceEngine(exp.apply_fn, exp.train_state.params,
                             exp.env_params, max_bucket=16)
    bucket = lambda x: jax.ShapeDtypeStruct((16,) + x.shape[1:], x.dtype,
                                            sharding=one_chip)
    obs, mask = bucket(exp.carry.obs), bucket(exp.carry.mask)
    assert obs.shape == (16, 192, 8, 2) and mask.shape == (16, 129)
    with time_limit(30):
        compiled = engine._step.lower(
            shapes(exp.train_state.params, one_chip), obs, mask).compile()
    assert "fusion" in compiled.as_text()
    assert device_bytes(compiled) < HBM_BYTES


def test_blocked_attention_compiles_at_the_cells_call_shape(one_chip):
    """The token trunk's attention kernels (``ops.attention``), forward
    and gradient, at one call of the benchmark cell: ``ROW_BLOCK`` rows of
    832 tokens, 4 KV heads of 8 query heads of 128, bfloat16. Mosaic
    takes the tiles, and no ``[.., T, T]`` array is left in the program."""
    from rlgpuschedule_tpu.models.trunk import ROW_BLOCK
    from rlgpuschedule_tpu.ops import attention
    b, T, Hkv, G, D = ROW_BLOCK, 832, 4, 8, 128
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    loss = lambda q, k, v, valid: jnp.sum(attention.blocked_attend(
        q, k, v, valid, None, interpret=False).astype(jnp.float32))
    with time_limit(30):
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            sds(b, T, Hkv, G, D), sds(b, T, Hkv, D), sds(b, T, Hkv, D),
            sds(b, T, dtype=jnp.bool_)).compile().as_text()
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text
    padded = attention.padded_length(T)
    assert f"{T},{T}]" not in text and f"{padded},{padded}]" not in text


def test_mla_score_product_compiles_with_q_and_k_padded_to_256(one_chip):
    """The ``ling`` trunk's MLA layer on the kernel path: 32 heads, each
    with its own keys, q and k of 192 channels zero-padded to 256, v of
    128: the kernels take the two widths, forward and gradient."""
    from rlgpuschedule_tpu.models.trunk import ROW_BLOCK
    from rlgpuschedule_tpu.ops import attention
    b, T, H = ROW_BLOCK, 832, 32
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    loss = lambda q, k, v, valid: jnp.sum(attention.blocked_attend(
        q, k, v, valid, None, interpret=False).astype(jnp.float32))
    with time_limit(60):
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            sds(b, T, H, 1, 256), sds(b, T, H, 256), sds(b, T, H, 128),
            sds(b, T, dtype=jnp.bool_)).compile().as_text()
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text
    assert f"{T},{T}]" not in text


def test_chunked_delta_rule_compiles_at_the_cells_call_shape(one_chip):
    """``ops.kda`` forward and gradient at one call of the ``ling`` cell:
    ``ROW_BLOCK`` rows of 832 tokens, 32 heads of 128 x 128 state, chunks
    of 64, bfloat16 tiles. The compiler takes it (a ``jnp.diagonal`` in
    the solve once tripped its algebraic simplifier), no array of a pair
    of tokens by channel is kept (``[.., 16, 16, 128]`` over a call is
    1.7 GB: it lives inside one fusion), and the temporaries (2.22 GB as
    this was written: two dozen arrays of 55-109 MB, the levels' factors
    and the scan's residuals) stay under 2.5 GB."""
    from rlgpuschedule_tpu.models.trunk import ROW_BLOCK
    from rlgpuschedule_tpu.ops import kda
    b, T, H, D = ROW_BLOCK, 832, 32, 128
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    loss = lambda q, k, v, g, beta: jnp.sum(kda.chunked_delta_rule(
        q, k, v, g, beta, chunk=64, dtype=jnp.bfloat16))
    with time_limit(120):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
            sds(b, T, H, D), sds(b, T, H, D), sds(b, T, H, D),
            sds(b, T, H, D, dtype=jnp.float32),
            sds(b, T, H, dtype=jnp.float32)).compile()
    assert "64,64,128]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def kda_call(one_chip, tokens: int = 832):
    """One call of ``ops.kda.chunked_delta_rule`` on the kernel path as
    the ``ling`` cell makes it (``ROW_BLOCK`` rows of 832 tokens, 32 heads
    of 128, chunks of 64, bfloat16 tiles), and its arguments as shapes on
    the described chip."""
    from rlgpuschedule_tpu.models.trunk import ROW_BLOCK
    from rlgpuschedule_tpu.ops import kda
    b, H, D = ROW_BLOCK, 32, 128
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    rule = lambda q, k, v, g, beta: kda.chunked_delta_rule(
        q, k, v, g, beta, chunk=64, dtype=jnp.bfloat16, path=kda.KERNEL,
        interpret=False)
    return rule, (sds(b, tokens, H, D), sds(b, tokens, H, D),
                  sds(b, tokens, H, D),
                  sds(b, tokens, H, D, dtype=jnp.float32),
                  sds(b, tokens, H, dtype=jnp.float32))


def lowered_gradient(fn, args):
    """The gradient of ``sum(fn(q, k, v, g, beta))`` in all five, lowered
    for the described chip."""
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32))
    return jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args)


def kernel_bodies(text: str) -> list[str]:
    """The serialized Mosaic module of every ``tpu_custom_call`` in a
    lowered program's StableHLO text, in order."""
    return re.findall(r'\\22body\\22: \\22([^\\]+)\\22', text)


def test_delta_rule_kernels_compile_at_the_cells_call_shape(one_chip):
    """``ops.kda_kernel``, forward and gradient, at one call of the
    ``ling`` cell: Mosaic takes both bodies (the batched products with a
    transposed operand, the sub-block slices, the VMEM they ask for), the
    program holds the two kernels by their fixed names, and what it keeps
    (the kernel's residuals: 184 KB a head and chunk, 0.31 GB; the plain
    path's temporaries at this call are 2.2 GB) stays under 0.6 GB."""
    with time_limit(60):
        compiled = lowered_gradient(*kda_call(one_chip)).compile()
    text = compiled.as_text()
    assert "kda_scan_forward" in text and "kda_scan_backward" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_delta_rule_kernel_bodies_do_not_grow_with_the_chunks(one_chip):
    """Rule 1 of the set-up budget: the body that is lowered handles ONE
    chunk, so a row of 2 chunks and a row of 13 lower Mosaic modules of
    one size, forward and backward (the grid's extent is a number in the
    module, not a loop written out)."""
    sizes = {}
    for tokens in (128, 832):
        with time_limit(30):
            text = lowered_gradient(*kda_call(one_chip, tokens)).as_text()
        bodies = kernel_bodies(text)
        assert len(bodies) == 2, len(bodies)        # forward, backward
        sizes[tokens] = [len(b) for b in bodies]
    for short, long in zip(sizes[128], sizes[832]):
        # the grid's extents and the arrays' types are written into the
        # module: 2 % as this was written; 13 chunks written out are 6x
        assert abs(short - long) <= 0.05 * long, sizes
    assert max(sizes[832]) < 40_000, sizes  # 16 and 21 KB of base64 now


def test_five_layers_lower_each_delta_rule_kernel_once(one_chip):
    """Rule 2: the five KDA layers call the same shapes, and each pass is
    ONE module-level jitted function, so a program that applies the rule
    five times under ``jax.checkpoint`` and differentiates it holds the
    forward body twice (the forward pass and its recomputation) and the
    backward body once, and calls them; site by site it would hold 15."""
    rule, args = kda_call(one_chip)

    @jax.checkpoint
    def layer(q, k, v, g, beta):
        return rule(q, k, v, g, beta).astype(q.dtype)

    def layers(q, k, v, g, beta):
        for _ in range(5):
            q = layer(q, k, v, g, beta)
        return q

    with time_limit(60):
        text = lowered_gradient(layers, args).as_text()
    assert len(kernel_bodies(text)) == 3, len(kernel_bodies(text))
    assert text.count("call @") >= 15       # ... and fifteen calls


def test_delta_rule_kernels_lower_to_the_same_text_twice(one_chip):
    """Rule 4: nothing in the lowered module differs between two
    lowerings of one program (no counter, address or temporary name in a
    kernel's name, metadata or serialized body), so a second process finds
    the first one's executable in the persistent cache."""
    texts = []
    for _ in range(2):
        jax.clear_caches()
        with time_limit(30):
            texts.append(lowered_gradient(*kda_call(one_chip)).as_text(
                debug_info=True))
    assert texts[0] == texts[1]
    assert "kda_scan_forward" in texts[0]


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_token_policy_update_compiles_and_leaves_room_for_a_second_state(
        one_chip, monkeypatch, path):
    """``ppo-trinity-philly512`` at the published widths and the
    benchmark cell's minibatch (8 steps x 8 envs of 832 tokens, PPO 4 x 4
    minibatches of 16 rows): the update the train step runs, donated, as
    the benchmark's traced run jits it alone. That run keeps the
    program's train state on the device beside the copy the update
    consumes, so what must fit one chip's 16.9e9 bytes is the update's
    arguments and temporaries plus a second 12 B a parameter (ISSUE 30).
    Built from shapes: no parameter is materialised here. ``plain`` is
    what this backend traces; ``kernel`` what a TPU traces (the trunk asks
    ``jax.default_backend()``, which sees the CPU here, so the test
    answers for it): the attention kernels inside the whole update, and
    no score tensor anywhere in it."""
    if path == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from rlgpuschedule_tpu.algos.ppo import (make_optimizer,
                                              make_train_state,
                                              run_ppo_epochs)
    from rlgpuschedule_tpu.algos.rollout import Transition
    from rlgpuschedule_tpu.algos.update import make_update_step
    from rlgpuschedule_tpu.configs import CONFIGS
    from rlgpuschedule_tpu.models import make_policy
    from rlgpuschedule_tpu.models.trunk import COUNTERS, read_counters

    cfg = CONFIGS["ppo-trinity-philly512"]
    ppo = dataclasses.replace(cfg.ppo, n_steps=8)
    from rlgpuschedule_tpu.env.obs import TOKEN_FEATURES as F
    T, E, tokens, A = ppo.n_steps, 8, cfg.n_nodes + 768, 129
    assert cfg.trunk == "published" and tokens == 832
    net = make_policy(cfg.obs_kind, A, trunk=cfg.trunk)
    state = jax.eval_shape(lambda: make_train_state(
        net, jax.random.PRNGKey(0), jnp.zeros((1, tokens, F)),
        jnp.ones((1, A), bool), make_optimizer(ppo)))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    assert 401.5e6 < n_params < 402.5e6

    def apply_fn(p, obs, mask):
        return net.apply(p, obs, mask)

    def counted(p, obs, mask):
        out, sown = net.apply(p, obs, mask, mutable=[COUNTERS])
        return out, read_counters(sown[COUNTERS])

    apply_fn.counted = counted          # as experiment.build_stack does
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    f32 = lambda *shape: sds(shape, jnp.float32)
    tr = Transition(obs=f32(T, E, tokens, F), action=sds((T, E), jnp.int32),
                    log_prob=f32(T, E), value=f32(T, E), reward=f32(T, E),
                    done=sds((T, E), jnp.bool_),
                    mask=sds((T, E, A), jnp.bool_), env_steps_dt=f32(T, E))
    update = make_update_step(
        lambda s, tr, adv, ret, key: run_ppo_epochs(
            apply_fn, ppo, s, tr, adv, ret, key,
            lambda s, g: s.apply_gradients(grads=g)))
    with time_limit(300):
        compiled = update.lower(
            shapes(state, one_chip), tr, f32(T, E), f32(T, E),
            shapes(jax.random.PRNGKey(0), one_chip)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 12 * n_params       # state in place
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + 12 * n_params)
    assert held < 16.9e9, (held, m)
    # XLA's own grouped-matmul kernel serves the experts' products
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert ("splash_mqa" in text) == (path == "kernel")
    assert (f"{tokens},{tokens}]" in text) == (path == "plain")


def test_looped_trunk_gradient_compiles_with_the_layers_once(one_chip,
                                                             monkeypatch):
    """``TRUNKS['ouro']`` at the published widths on the kernel path: the
    gradient of one minibatch of the ``philly512-ouro.train`` cell (one
    ``ROW_BLOCK`` of 832-token rows) through the scan over four loop
    steps, the rematerialised blocks and the attention kernels. The
    compiled program holds the eight layers' kernels once for the forward
    pass and once each for the recomputation and the backward pass,
    whatever the loop count (written out it would hold 32 of each), and
    what it keeps beside the parameters and their gradient stays under
    3 GB (each application's input, 32 x 27 MB, and one block's
    activations)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from rlgpuschedule_tpu.env.obs import TOKEN_FEATURES as F
    from rlgpuschedule_tpu.models import TRUNKS, make_policy
    from rlgpuschedule_tpu.models.trunk import ROW_BLOCK
    c, tokens, A = TRUNKS["ouro"], 832, 129
    net = make_policy("tokens", A, trunk="ouro")
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, tokens, F)), jnp.ones((1, A), bool))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert n_params == 411_400_323

    def loss(p, obs, mask):
        logits, value = net.apply(p, obs, mask)
        return jnp.sum(value) + jnp.sum(jax.nn.log_softmax(logits)[:, 0])

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    with time_limit(120):
        compiled = jax.jit(jax.grad(loss)).lower(
            shapes(params, one_chip), sds((ROW_BLOCK, tokens, F),
                                          jnp.float32),
            sds((ROW_BLOCK, A), jnp.bool_)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "splash_mqa" in line]
    forward = sum("splash_mqa_fwd" in line for line in calls)
    assert forward == 2 * c.num_hidden_layers       # forward, recomputation
    assert len(calls) - forward >= c.num_hidden_layers      # backward
    assert len(calls) < 2 * c.total_ut_steps * c.num_hidden_layers
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes - 4 * n_params < 3e9, m
