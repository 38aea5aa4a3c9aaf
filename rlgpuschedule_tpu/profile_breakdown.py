"""Where-the-time-goes profiling of the headline bench workload (L6 aux).

Capability parity: SURVEY.md §5 "Tracing / profiling" and §7 hard part (d)
("keeping per-step host↔device sync at zero"); VERDICT r2 missing #4 /
next-round #6 — one steps/s number says nothing about WHERE the time goes,
so this CLI decomposes the fused PPO train step into its three stages and
measures the host gap:

- **rollout**: the fused policy+env ``lax.scan`` (HOT LOOP #1),
- **gae**: the bare reverse-scan advantage computation (reference row),
- **advantage**: the production fused advantage pipeline
  (``algos.ppo.compute_advantages``: optional streaming reward
  standardization → GAE or V-trace → global normalization → optional
  bf16 storage). With default flags this is the gae row plus
  normalization; ``--correction vtrace`` prices the batched
  target-policy recompute the off-policy path adds on top,
- **update**: epoch × minibatch clipped-surrogate updates (HOT LOOP #2),
- **fused_loop**: the production one-jit step (rollout+gae+update
  together — XLA may fuse across stages, so fused ≤ sum(parts) is
  expected) timed as a pipelined driver loop (block only at the end),
- **fused_step_blocked**: the same step with a device sync after EVERY
  call — the un-pipelined latency,
- **pipeline_overlap**: blocked − pipelined = how much host work (Python
  dispatch, PRNG splits) async dispatch hides. True device time needs the
  profiler trace (``--trace-dir``); wall-minus-parts is NOT it, because
  cross-stage fusion makes sum(parts) an overestimate of the fused step.

Each stage is jitted separately, warmed, then timed as median-of-N
(the same noise discipline as bench.py). Optionally captures a
``jax.profiler`` trace (Perfetto/TensorBoard) of the fused loop.

Usage::

    python -m rlgpuschedule_tpu.profile_breakdown [--cpu] [--repeats 5]
        [--trace-dir /tmp/jax-trace] [--n-envs 512] [--n-steps 128]
        [--n-epochs 2] [--n-minibatches 8 | --minibatch-size N]
        [--bf16-update] [--correction vtrace] [--reward-norm]
        [--bf16-advantages]
    python -m rlgpuschedule_tpu.profile_breakdown [--cpu] \
        --sweep-minibatch [--sweep-out sweep.json]
    python -m rlgpuschedule_tpu.profile_breakdown [--cpu] \
        --async [--staleness-bound 1] [--async-out async.json]

``--async`` swaps the stage breakdown for a sync-vs-async PHASE table:
the same workload is run through the per-iteration sync loop and through
the overlapped actor-learner engine (``async_engine.AsyncRunner``), and
the artifact reports seconds/iteration for both plus the engine's own
phase accounting — actor / learner busy seconds, queue-wait (the actor's
staleness-gate stall + the learner's pop stall), and the overlap-ceiling
projection ``(actor + learner) / max(actor, learner)`` that bounds the
achievable speedup on hardware with enough cores to truly overlap. With
``--cpu`` this mode pins TWO virtual CPU devices (the split needs
disjoint actor/learner groups; the plain breakdown pins one).

Prints one JSON object with per-stage seconds/iteration, the stage shares,
an env-steps/s figure, and a model-FLOPs/s estimate (policy fwd+bwd FLOPs
from param count — the MXU utilization proxy; the env scan does almost no
matmul work, so "MFU" here is meaningful for the update stage only).

``--sweep-minibatch`` is the automated minibatch-geometry lever sweep
(BASELINE.md named it "the first lever the next TPU session should
profile"): one rollout+GAE is materialized, then the update stage alone is
timed at every power-of-two minibatch count that tiles the batch, on
whatever backend jax picked — the same artifact schema on CPU and TPU
(``mfu_update`` is null off-chip where no bf16 peak is known). The output
is a RANKED JSON artifact (fastest geometry first, ``best`` duplicated at
the top level); feed it to ``bench.py --sweep`` so the headline number
reflects the lever. The update step is timed exactly as production runs
it: optimizer/param buffers donated and threaded call-to-call
(``algos.update.make_update_step``), so no per-call state reallocation
pollutes the measurement.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import time


def _median_time(fn, repeats: int) -> float:
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


# MFU pricing: the chip's bf16 matmul peak (the networks run bf16
# compute), keyed on device_kind — platform == "tpu" alone would price
# every generation at the v5e's peak. This is the measured replacement
# for the "dispatch/HBM-bound" assertion (VERDICT r4 missing #4):
# mfu_total over the whole fused step, and mfu_update over the update
# stage alone (the only stage whose matmuls could fill the MXU — the
# env scan does no matmul work). Public bf16 peaks per chip, keyed by
# the lower-cased ``device_kind`` without its "TPU " prefix.
BF16_PEAK = {"v4": 275e12, "v5 lite": 197e12, "v5e": 197e12,
             "v5p": 459e12, "v5": 459e12, "v6 lite": 918e12,
             "v6e": 918e12}


def bf16_peak(platform: str, device_kind: str) -> "float | None":
    """Peak bf16 FLOP/s of the attached chip; ``None`` off-TPU (no MFU
    is priced there). The match is EXACT on the kind — a substring match
    would price ``"TPU v5 lite"`` (v5e) at the ``"v5"`` (v5p) row — and a
    TPU kind that is not in the table is an error, not a silently-null
    MFU."""
    if platform != "tpu":
        return None
    kind = device_kind.lower().removeprefix("tpu").strip()
    if kind not in BF16_PEAK:
        raise SystemExit(
            f"profile_breakdown: no bf16 peak recorded for TPU "
            f"device_kind {device_kind!r}; add it to BF16_PEAK (known: "
            f"{sorted(BF16_PEAK)})")
    return BF16_PEAK[kind]


def _sweep_minibatch(args, ppo, platform, kind, peak, B, n_params,
                     timed_update, state, tr, adv, ret, key, n,
                     t_adv) -> dict:
    """Time the update stage over the geometry grid — epochs in
    ``{1, configured}`` × every power-of-two minibatch count that tiles
    the batch (plus the configured default) — and rank the geometries
    fastest-first. All three axes of the ``n_epochs × n_minibatches ×
    minibatch_size`` triple are covered (minibatch_size is the derived
    ``B / n_minibatches``); ``n_epochs`` scales the update's FLOPs
    linearly, so same-epoch rows compare pure geometry overhead/MXU fill
    while the 1-epoch rows price the fused single-pass recipe. Same
    artifact on CPU and TPU; ``mfu_update`` is null where no bf16 peak is
    known (non-TPU backends)."""
    import dataclasses as _dc

    from rlgpuschedule_tpu.algos import resolve_geometry

    _, default_mb, _sz = resolve_geometry(ppo.n_epochs, ppo.n_minibatches,
                                          ppo.minibatch_size, B)
    mbs = sorted({m for m in (2 ** p for p in range(0, 8))
                  if m <= B and B % m == 0} | {default_mb})
    results = []
    for e in sorted({1, ppo.n_epochs}):
        upd_evals = e * B                        # fwd+bwd per sample
        upd_flops = 2 * n_params * 3 * upd_evals
        for m in mbs:
            geom = _dc.replace(ppo, n_epochs=e, n_minibatches=m,
                               minibatch_size=None)
            t = timed_update(geom, state, tr, adv, ret, key, n)
            results.append({
                "n_epochs": e, "n_minibatches": m,
                "minibatch_size": B // m,
                "update_s_per_iteration": round(t, 5),
                "update_env_steps_per_sec": round(B / t, 1),
                "model_flops_per_sec": round(upd_flops / t, 1),
                "mfu_update": round(upd_flops / t / peak, 6)
                if peak is not None else None,
            })
    default = next(r for r in results
                   if r["n_epochs"] == ppo.n_epochs
                   and r["n_minibatches"] == default_mb)
    t_default = default["update_s_per_iteration"]
    for r in results:
        r["speedup_vs_default"] = round(
            t_default / r["update_s_per_iteration"], 3)
    results.sort(key=lambda r: r["update_s_per_iteration"])
    out = {
        "sweep": "minibatch-geometry",
        "platform": platform,
        "device_kind": kind or None,
        "n_envs": tr.reward.shape[1], "n_steps": ppo.n_steps,
        "batch_per_iteration": B,
        "bf16_update": ppo.bf16_update,
        "advantage_pipeline": {"correction": ppo.correction,
                               "reward_norm": ppo.reward_norm,
                               "bf16_advantages": ppo.bf16_advantages},
        # the advantage phase is geometry-invariant (it runs once per
        # iteration, before the epoch×minibatch grid) — one row
        # contextualizes every geometry's update time against it
        "advantage_s_per_iteration": round(t_adv, 5),
        "policy_params": int(n_params),
        "assumed_bf16_peak_flops": peak,
        "default_geometry": {"n_epochs": ppo.n_epochs,
                             "n_minibatches": default_mb},
        "results": results,            # ranked fastest-first
        "best": results[0],
    }
    return out


def _profile_async(args, cfg, platform) -> dict:
    """Sync-vs-async phase table on one workload.

    Times the per-iteration sync loop and the overlapped engine
    (median-of-N, same noise discipline as the stage breakdown), then
    folds in the engine's own accounting: per-phase host seconds from the
    run's SectionTimer (``actor``/``learner``/``queue_wait``/``sync``)
    and the cumulative overlap/staleness counters from ``async_info()``.
    ``projected_overlap_speedup`` is the phase-time ceiling
    ``(actor + learner) / max(actor, learner)`` — what perfect overlap
    would buy on hardware with spare host cores; the measured ``speedup``
    is what THIS host delivers (≈1.0 or below on a single core, where the
    CPU dispatch lock serializes the two loops by design)."""
    import os

    from rlgpuschedule_tpu.async_engine import AsyncRunner
    from rlgpuschedule_tpu.experiment import Experiment

    n = args.iters_per_repeat
    sync_exp = Experiment.build(cfg)
    sync_exp.run(iterations=1)                     # compile + warm
    t_sync = _median_time(lambda: sync_exp.run(iterations=n),
                          args.repeats) / n

    async_exp = Experiment.build(cfg)
    runner = AsyncRunner(async_exp, staleness_bound=args.staleness_bound)
    runner.run(iterations=1)                       # warm the engine path
    last: dict = {}

    def timed():
        last.update(runner.run(iterations=n))

    t_async = _median_time(timed, args.repeats) / n
    phases = last["phase_seconds"]                 # last timed run only
    info = last["async"]                           # cumulative counters
    busy_a = phases.get("actor", 0.0)
    busy_l = phases.get("learner", 0.0)
    parts = busy_a + busy_l
    return {
        "profile": "async-phase-table",
        "platform": platform,
        "cores": os.cpu_count(),
        "n_envs": cfg.n_envs, "n_steps": cfg.ppo.n_steps,
        "iters_per_repeat": n, "repeats": args.repeats,
        "staleness_bound": args.staleness_bound,
        "groups": runner.groups.describe(),
        "seconds_per_iteration": {
            "sync_loop": round(t_sync, 5),
            "async_loop": round(t_async, 5)},
        "speedup": round(t_sync / t_async, 3),
        "async_phase_seconds_per_iteration": {
            k: round(v / n, 5) for k, v in sorted(phases.items())},
        "async_phase_share_of_busy": {
            "actor": round(busy_a / parts, 3) if parts else None,
            "learner": round(busy_l / parts, 3) if parts else None},
        "projected_overlap_speedup": round(
            parts / max(busy_a, busy_l), 3) if parts else None,
        "queue_wait_s_cumulative": {
            "actor_idle": info["actor_idle_s"],
            "learner_idle": info["learner_idle_s"]},
        "staleness": {"max": info["staleness_max"],
                      "mean": info["staleness_mean"]},
        "overlap_s_cumulative": info["overlap_s"],
        "note": "phase seconds are the last timed run's SectionTimer; "
                "queue_wait/overlap/staleness counters are cumulative "
                "over warmup + all repeats",
    }


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(prog="rlgpuschedule_tpu.profile_breakdown")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU platform (default: whatever jax picks)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--iters-per-repeat", type=int, default=3)
    ap.add_argument("--n-envs", type=int, default=None,
                    help="default: 512 on TPU, 32 on CPU")
    ap.add_argument("--n-steps", type=int, default=None,
                    help="default: 128 on TPU, 64 on CPU")
    ap.add_argument("--n-epochs", type=int, default=2,
                    help="update geometry: PPO epochs over the batch")
    ap.add_argument("--n-minibatches", type=int, default=8,
                    help="update geometry: minibatch count per epoch "
                         "(profile the swept-best with e.g. 1)")
    ap.add_argument("--minibatch-size", type=int, default=None,
                    help="update geometry: explicit minibatch size; "
                         "overrides --n-minibatches (algos.update "
                         "resolve_geometry contract)")
    ap.add_argument("--bf16-update", action="store_true",
                    help="profile the bf16-compute / fp32-optimizer "
                         "update path")
    ap.add_argument("--correction", choices=["none", "vtrace"],
                    default="none",
                    help="advantage pipeline: V-trace importance-corrected "
                         "targets instead of plain GAE — the advantage row "
                         "then prices the batched target-policy recompute "
                         "the off-policy path adds")
    ap.add_argument("--reward-norm", action="store_true",
                    help="advantage pipeline: streaming Welford reward "
                         "standardization before the target scan")
    ap.add_argument("--bf16-advantages", action="store_true",
                    help="advantage pipeline: store advantages/returns in "
                         "bf16 (halves the tensors' HBM traffic; the "
                         "update still computes fp32)")
    ap.add_argument("--sweep-minibatch", action="store_true",
                    help="time the update stage over a grid of minibatch "
                         "geometries and emit a ranked JSON artifact "
                         "(steps/s + mfu_update) instead of the stage "
                         "breakdown")
    ap.add_argument("--sweep-out", default=None,
                    help="with --sweep-minibatch: also write the ranked "
                         "artifact to this path (bench.py --sweep reads "
                         "it)")
    ap.add_argument("--trace-dir", default=None,
                    help="also capture a jax.profiler trace of the fused "
                         "loop here")
    ap.add_argument("--async", dest="async_run", action="store_true",
                    help="profile the overlapped actor-learner engine "
                         "against the sync loop (phase table) instead of "
                         "the stage breakdown")
    ap.add_argument("--staleness-bound", type=int, default=1,
                    help="with --async: the engine's staleness bound")
    ap.add_argument("--async-out", default=None,
                    help="with --async: also write the phase-table "
                         "artifact to this path")
    args = ap.parse_args(argv)
    if args.sweep_out and not args.sweep_minibatch:
        ap.error("--sweep-out only applies with --sweep-minibatch")
    if args.async_out and not args.async_run:
        ap.error("--async-out only applies with --async")
    if args.async_run and (args.sweep_minibatch or args.trace_dir):
        ap.error("--async is exclusive with --sweep-minibatch/--trace-dir")

    if args.cpu:
        from rlgpuschedule_tpu.utils.platform import force_cpu
        # the async split needs disjoint actor/learner device groups
        force_cpu(2 if args.async_run else 1)
    from rlgpuschedule_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from rlgpuschedule_tpu.algos import PPOConfig, resolve_geometry
    from rlgpuschedule_tpu.algos.ppo import (compute_advantages,
                                             normalize_advantages,
                                             run_ppo_epochs)
    from rlgpuschedule_tpu.algos.rollout import rollout
    from rlgpuschedule_tpu.algos.update import make_update_step
    from rlgpuschedule_tpu.configs import PPO_MLP_SYNTH64
    from rlgpuschedule_tpu.experiment import Experiment
    from rlgpuschedule_tpu.ops.gae import compute_gae
    from rlgpuschedule_tpu.utils import profiling

    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"
    n_envs = args.n_envs or (32 if on_cpu else 512)
    n_steps = args.n_steps or (64 if on_cpu else 128)
    ppo = PPOConfig(n_steps=n_steps, n_epochs=args.n_epochs,
                    n_minibatches=args.n_minibatches,
                    minibatch_size=args.minibatch_size,
                    bf16_update=args.bf16_update,
                    correction=args.correction,
                    reward_norm=args.reward_norm,
                    bf16_advantages=args.bf16_advantages)
    cfg = dataclasses.replace(PPO_MLP_SYNTH64, n_envs=n_envs, ppo=ppo)
    if args.async_run:
        out = _profile_async(args, cfg, platform)
        print(json.dumps(out))
        if args.async_out:
            with open(args.async_out, "w") as f:
                json.dump(out, f, indent=1)
        return out
    exp = Experiment.build(cfg)
    env_params, apply_fn = exp.env_params, exp.apply_fn
    state, carry, traces = exp.train_state, exp.carry, exp.traces
    # one key per consumer: the sweep, the standalone update timing, and
    # the fused-step warmup each get their own stream (jsan
    # prng-key-reuse: handing two consumers the same key makes their
    # draws bit-identical); the fused timing loop splits `key` itself
    key = jax.random.PRNGKey(0)
    key, k_sweep, k_upd, k_warm = jax.random.split(key, 4)
    B = n_steps * n_envs
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    kind = jax.devices()[0].device_kind.lower()
    peak = bf16_peak(platform, kind)

    # ---- stage jits (batch inputs are reused across repeats, so only the
    # update's state — the buffers production donates — is donated and
    # threaded call-to-call) ----------------------------------------------
    @jax.jit
    def rollout_only(params, carry):
        return rollout(apply_fn, params, env_params, traces, carry, n_steps)

    @jax.jit
    def gae_only(tr, last_value):
        adv, ret = compute_gae(tr.reward, tr.value, tr.done, last_value,
                               ppo.gamma, ppo.gae_lambda)
        return normalize_advantages(adv), ret

    @jax.jit
    def advantage_only(state, tr, last_value):
        # the production pipeline (reward-norm → GAE/V-trace → normalize
        # → bf16 store); with default flags it lowers to gae_only's ops
        _st, a, r, _rho = compute_advantages(apply_fn, ppo, state, tr,
                                             last_value)
        return a, r

    # ONE jitted copy program shared by every _timed_update call: the
    # sweep times a dozen geometries, and a fresh jax.jit(lambda) per
    # call would recompile the copy once per geometry (jsan
    # recompile-hazard, PR 3 first-run finding). Can't live at module
    # scope — jax is imported lazily so --cpu can pin the platform first.
    copy_state = jax.jit(  # jsan: disable=recompile-hazard -- built once per process; jax import is deferred
        lambda t: jax.tree.map(jnp.copy, t))

    def _timed_update(ppo_g, state0, tr, adv, ret, key, n):
        """Median seconds/iteration of the donated update step at geometry
        ``ppo_g``, threading the donated state like the production loop."""
        upd = make_update_step(
            lambda s, t, a, r, k: run_ppo_epochs(
                apply_fn, ppo_g, s, t, a, r, k,
                lambda st, g: st.apply_gradients(grads=g)))
        cell = {"s": copy_state(state0)}
        cell["s"], _ = jax.block_until_ready(
            upd(cell["s"], tr, adv, ret, key))         # compile + warm

        def run_n():
            for _ in range(n):
                cell["s"], _m = upd(cell["s"], tr, adv, ret, key)
            jax.block_until_ready(cell["s"].params)

        return _median_time(run_n, args.repeats) / n

    _, tr, last_value = jax.block_until_ready(
        rollout_only(state.params, carry))
    jax.block_until_ready(gae_only(tr, last_value))        # compile + warm
    # the update/sweep timings consume the PRODUCTION pipeline's outputs
    # (bf16 storage changes the tensors the update reads)
    adv, ret = jax.block_until_ready(advantage_only(state, tr, last_value))

    n = args.iters_per_repeat
    t_adv = _median_time(
        lambda: jax.block_until_ready(
            [advantage_only(state, tr, last_value) for _ in range(n)]),
        args.repeats) / n
    if args.sweep_minibatch:
        out = _sweep_minibatch(args, ppo, platform, kind, peak, B, n_params,
                               _timed_update, state, tr, adv, ret, k_sweep,
                               n, t_adv)
        print(json.dumps(out))
        if args.sweep_out:
            with open(args.sweep_out, "w") as f:
                json.dump(out, f, indent=1)
        return out

    t_upd = _timed_update(ppo, state, tr, adv, ret, k_upd, n)

    fused = exp.train_step     # the production jit (donates; returns fresh)
    state2, carry2, _ = fused(state, carry, traces, k_warm)
    jax.block_until_ready(state2.params)
    state, carry = state2, carry2   # donated originals are dead now

    t_roll = _median_time(
        lambda: jax.block_until_ready(
            [rollout_only(state.params, carry) for _ in range(n)]),
        args.repeats) / n
    t_gae = _median_time(
        lambda: jax.block_until_ready(
            [gae_only(tr, last_value) for _ in range(n)]),
        args.repeats) / n

    def fused_loop(block_every: bool = False):
        nonlocal state, carry, key
        for _ in range(n):
            key, sub = jax.random.split(key)
            state, carry, _m = fused(state, carry, traces, sub)
            if block_every:
                jax.block_until_ready(state.params)
        jax.block_until_ready(state.params)

    t_loop = _median_time(fused_loop, args.repeats) / n
    t_blocked = _median_time(lambda: fused_loop(True), args.repeats) / n

    if args.trace_dir:
        with profiling.trace(args.trace_dir):
            fused_loop()

    # parts = the production decomposition (rollout → advantage pipeline
    # → update); the bare gae row stays as the pre-fusion reference
    t_parts = t_roll + t_adv + t_upd
    pipeline_overlap = max(t_blocked - t_loop, 0.0)

    # model-FLOPs proxy: 2*params per fwd MAC, 3x for fwd+bwd, over every
    # policy evaluation (T rollout steps + 1 bootstrap + epochs*B updates)
    fwd_evals = B + n_envs                      # rollout + bootstrap value
    upd_evals = ppo.n_epochs * B                # fwd+bwd per sample
    flops = 2 * n_params * (fwd_evals + 3 * upd_evals)
    upd_flops = 2 * n_params * 3 * upd_evals
    _, n_mb, mb = resolve_geometry(ppo.n_epochs, ppo.n_minibatches,
                                   ppo.minibatch_size, B)
    out = {
        "platform": platform,
        "n_envs": n_envs, "n_steps": n_steps,
        "geometry": {"n_epochs": ppo.n_epochs, "n_minibatches": n_mb,
                     "minibatch_size": mb,
                     "bf16_update": ppo.bf16_update},
        "advantage_pipeline": {"correction": ppo.correction,
                               "reward_norm": ppo.reward_norm,
                               "bf16_advantages": ppo.bf16_advantages},
        "seconds_per_iteration": {
            "rollout": round(t_roll, 5), "gae": round(t_gae, 5),
            "advantage": round(t_adv, 5),
            "update": round(t_upd, 5), "fused_loop": round(t_loop, 5),
            "fused_step_blocked": round(t_blocked, 5),
            "pipeline_overlap": round(pipeline_overlap, 5)},
        "stage_share_of_parts": {
            "rollout": round(t_roll / t_parts, 3),
            "advantage": round(t_adv / t_parts, 3),
            "update": round(t_upd / t_parts, 3)},
        "env_steps_per_sec": round(B / t_loop, 1),
        "policy_params": int(n_params),
        "model_flops_per_sec": round(flops / t_loop, 1),
    }
    if peak is not None:
        out["assumed_bf16_peak_flops"] = peak
        out["device_kind"] = kind
        out["mfu_total"] = round(flops / t_loop / peak, 6)
        out["mfu_update"] = round(upd_flops / t_upd / peak, 6)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
