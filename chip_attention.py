#!/usr/bin/env python
"""Chip check of the token trunk's blocked attention at the benchmark
cell's own call shape: parity against ``models.trunk.attend`` and time,
forward + gradient, one call = one group of ``ROW_BLOCK`` rows of one
layer (q ``[4, 832, 4, 8, 128]``, k/v ``[4, 832, 4, 128]`` bfloat16).

    python chip_attention.py            # parity, time, the counters
    python chip_attention.py --sweep    # and every tile size once

There is no CPU fallback: without a TPU it exits non-zero. stdout is
JSON lines, each gap beside the limit it was held to, LAST
``{"ok": ..., "device": ...}``.

The limits: ``attend`` in float32 at ``highest`` precision is the truth;
``attend`` in bfloat16 (what the kernel replaces) has an error of its own
against it, from the same roundings of q, k, v and of the output. The
kernel's error against the truth may be ``ROOM`` times that and no more,
for the output and for each of the three gradients.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

ROOM = 2.0
ROWS, T, HKV, G, D = 4, 832, 4, 8, 128
NODES = 64              # the cell's node tokens: always valid
REPS = 20


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_attention.py")
    ap.add_argument("--sweep", action="store_true",
                    help="time every tile size, not only the one kept")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from rlgpuschedule_tpu.utils.platform import require_tpu
    device = require_tpu("chip_attention.py")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    from rlgpuschedule_tpu.models import trunk
    from rlgpuschedule_tpu.ops import attention

    emit = lambda **kw: print(json.dumps(kw), flush=True)
    rng = np.random.default_rng(args.seed)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    # q and k as q_norm and k_norm leave them (unit RMS over the head),
    # v as a projection of a normed row, the cotangent as the gate's
    q32, k32 = normal(ROWS, T, HKV, G, D), normal(ROWS, T, HKV, D)
    v32, w = normal(ROWS, T, HKV, D), normal(ROWS, T, HKV, G, D)
    # the nodes' tokens valid, four jobs in five: a draining backlog
    valid = jnp.asarray(np.concatenate(
        [np.ones((ROWS, NODES), bool),
         rng.random((ROWS, T - NODES)) < 0.8], axis=1))
    scale = 1.0 / math.sqrt(D)
    window = None       # 832 < 2048: the cell's sliding layers mask no more

    def loss_of(attn, dtype, prescale):
        def loss(q, k, v):
            q = (q * scale if prescale else q).astype(dtype)
            out = attn(q, k.astype(dtype), v.astype(dtype))
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    def timed(fn):
        for _ in range(3):
            jax.block_until_ready(fn(q32, k32, v32))
        t0 = time.perf_counter()
        for _ in range(REPS):
            r = fn(q32, k32, v32)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / REPS * 1e3, r

    plain = lambda q, k, v: trunk.attend(q, k, v, valid, window)
    with jax.default_matmul_precision("highest"):
        (_, o_true), g_true = loss_of(plain, jnp.float32, False)(
            q32, k32, v32)
    ms_plain, ((_, o_plain), g_plain) = timed(
        loss_of(plain, jnp.bfloat16, False))
    gap = lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
    plain_gaps = [gap(o_plain, o_true)] + [
        gap(a, b) for a, b in zip(g_plain, g_true)]
    emit(phase="attend_bfloat16", ms_forward_and_gradient=ms_plain,
         gaps_to_float32=dict(zip(("out", "dq", "dk", "dv"), plain_gaps)))

    ok = True

    def run(name, attn):
        nonlocal ok
        ms, ((_, o), g) = timed(loss_of(attn, jnp.bfloat16, True))
        ms_fwd, _ = timed(jax.jit(lambda q, k, v: attn(
            (q * scale).astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16))))
        gaps = [gap(o, o_true)] + [gap(a, b) for a, b in zip(g, g_true)]
        limits = [ROOM * p for p in plain_gaps]
        held = all(x <= lim for x, lim in zip(gaps, limits))
        ok &= held
        emit(phase=name, ms_forward_and_gradient=ms, ms_forward=ms_fwd,
             gaps_to_float32=dict(zip(("out", "dq", "dk", "dv"), gaps)),
             limits=dict(zip(("out", "dq", "dk", "dv"), limits)),
             gap_to_attend_bfloat16=gap(o, o_plain), within_limits=held,
             finite=bool(all(jnp.all(jnp.isfinite(x.astype(jnp.float32)))
                             for x in (o, *g))))

    run(f"kernel_block_{attention.BLOCK}",
        lambda q, k, v: attention.blocked_attend(q, k, v, valid, window))
    emit(phase="tiles", padded=attention.padded_length(T),
         tile=attention.tile_size(T),
         computed_share=attention.tiles_computed_share(T, window))

    if args.sweep:
        def variant(bq, bkv, bkvc, padded, fused):
            sizes = splash.BlockSizes(
                block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
                block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkvc,
                block_q_dq=None if fused else bq,
                block_kv_dq=None if fused else bkv,
                use_fused_bwd_kernel=fused)
            kernel = splash.make_splash_mqa_single_device(
                masks.MultiHeadMask([masks.CausalMask((padded, padded))] * G),
                block_sizes=sizes)
            return lambda q, k, v: attention.run_kernel(kernel, padded, q,
                                                        k, v, valid)
        for bq, bkv, bkvc, padded, fused in [
                (128, 128, 128, 896, True), (256, 256, 256, 1024, True),
                (512, 512, 512, 1024, True), (512, 512, 256, 1024, True),
                (512, 256, 256, 1024, True), (256, 512, 256, 1024, True),
                (896, 896, 128, 896, True), (1024, 1024, 512, 1024, True),
                (512, 1024, 512, 1024, True), (1024, 512, 512, 1024, True),
                (512, 512, 512, 1024, False), (256, 256, 256, 1024, False)]:
            name = (f"sweep_q{bq}_kv{bkv}_c{bkvc}_"
                    + ("fused" if fused else "dq+dkv"))
            try:
                run(name, variant(bq, bkv, bkvc, padded, fused))
            except Exception as e:      # a tile the compiler refuses
                emit(phase=name, refused=f"{type(e).__name__}: {e}"[:300])

    # the counters through one attention layer at the published widths
    layer = trunk.Attention(trunk.TRUNKS["published"], True, jnp.bfloat16)
    x = normal(ROWS, T, 2048).astype(jnp.bfloat16)
    params = jax.jit(layer.init)(jax.random.PRNGKey(args.seed), x, valid)
    _, sown = jax.jit(lambda p, x: layer.apply(
        p, x, valid, mutable=[trunk.COUNTERS]))(params, x)
    counters = {name: float(leaf[0]) for name, leaf in
                sown[trunk.COUNTERS].items()}
    ok &= counters["attn_tiles"] == attention.tiles_computed_share(
        T, trunk.TRUNKS["published"].sliding_window)
    emit(phase="counters_one_layer", **counters)
    emit(ok=bool(ok), device=device)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
