#!/usr/bin/env python
"""Chip smoke: train -> checkpoint -> serve -> evaluate on the TPU, in one
process, through the entry points a user calls.

    python chip_smoke.py            # one chip, the 512-GPU config-2 shape
    python chip_smoke.py --chips 4  # the multi-chip paths and nothing else
    python chip_smoke.py --tiny     # CPU rehearsal: same phases, small shape

Default (one chip), at ``ppo-cnn-philly512`` (64 nodes x 8 GPUs, grid-CNN,
Philly-statistics proxy trace) with 256 envs x 768-job drain windows,
queue view 128, 128-step rollouts (32,768 env-steps per iteration):

1. ``train.main``     3 PPO iterations, a checkpoint per iteration;
2. ``serve.main``     the continuous-batching server restores that
                      checkpoint and answers 95 decision requests in
                      bucket 16 with zero post-warmup recompiles;
3. ``evaluate.main``  restore-and-replay of 2 windows to 100 % completion,
                      its JCT held against the host oracle's baselines.

``--chips 4`` runs only what exists across chips: 2 iterations under
``--mesh 1x4x1`` against the same 2 iterations unsharded from the same
seed, and an ``EngineRouter`` with one engine per chip whose answers must
equal engine 0's.

There is no CPU fallback: without a TPU the script exits non-zero before
doing any work and prints no result. ``--tiny`` relaxes that check for the
rehearsal only, and its last line still says ``"ok": false`` off-TPU.
Everything runs in this one process (a parent that touched jax would hold
the chip), nothing is spawned, and every compile goes through
``utils.platform.enable_compile_cache``.

stdout is JSON lines: one object per phase (wall seconds split into
compile vs run, compile-cache hits/misses, the phase's own numbers), and
LAST the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
The CLIs' own output goes to stderr.
"""
from __future__ import annotations

import argparse
import contextlib
import csv
import faulthandler
import hashlib
import json
import math
import os
import shutil
import sys
import threading
import time
import traceback

CONFIG = "ppo-cnn-philly512"
# cluster/observation shape: shared by train, serve and evaluate (it is
# part of the checkpoint). FULL is the one shape this repo has itself
# trained on a chip (BASELINE.md "config 2 at scale").
FULL = {"shape": ["--n-envs", "256", "--window-jobs", "768",
                  "--queue-len", "128"],
        "train": ["--n-steps", "128"],
        "bucket": 16, "eval_horizon": 2048}
TINY = {"shape": ["--n-envs", "4", "--n-nodes", "2", "--gpus-per-node", "4",
                  "--window-jobs", "16", "--queue-len", "4",
                  "--horizon", "64"],
        "train": ["--n-steps", "8", "--n-epochs", "1",
                  "--n-minibatches", "2"],
        "bucket": 8, "eval_horizon": 64}
# generous but finite: ~3x what a cold run took on a v5e (my chip run,
# PR 24: train 123 s, serve 183 s, evaluate 75 s, mesh_train 319 s; most
# of it ~0.5 s compiles of a few hundred eager one-op programs). A phase
# past its limit dumps every thread's stack and ends the process loudly
# instead of hanging the chip.
PHASE_LIMIT_S = {"train": 600, "serve": 480, "evaluate": 360,
                 "mesh_train": 900, "router": 600}
# --chips 4 tolerances, sharded vs unsharded after 2 iterations from one
# seed. The two are one program semantically; they differ by the order of
# the gradient all-reduce's float32 sums, which Adam's normalisation can
# amplify to a few learning rates (3e-4) on isolated parameters.
LOSS_RTOL, LOSS_ATOL = 5e-2, 1e-3
PARAMS_REL_L2 = 1e-2


class SmokeFailure(RuntimeError):
    """A phase ran but its result is wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Smoke:
    """Runs phases in order, prints one JSON line each, and skips what
    follows a failure (later phases consume earlier ones' output)."""

    def __init__(self):
        # the program's own compile listener (one for the process's life)
        from rlgpuschedule_tpu.obs.startup import ACCOUNT
        self.compiles = ACCOUNT.compiles
        self.failed: list[str] = []
        self.ran: list[str] = []

    def run(self, name: str, fn) -> None:
        """``fn(rec)`` does the phase, fills ``rec`` with what is worth
        printing and raises on a wrong result."""
        rec: dict = {"phase": name}
        if self.failed:
            rec.update(ok=False, skipped=f"after failed {self.failed[0]}")
            self.failed.append(name)
            print(json.dumps(rec), flush=True)
            return
        limit = PHASE_LIMIT_S[name]
        timer = threading.Timer(limit, _phase_timeout, (name, limit))
        timer.daemon = True
        before = self.compiles.counts()
        t0 = time.monotonic()
        timer.start()
        try:
            # the CLIs print their own summaries on stdout; ours stays a
            # clean JSON-lines stream
            with contextlib.redirect_stdout(sys.stderr):
                fn(rec)
            rec["ok"] = True
        except (Exception, SystemExit) as e:   # a CLI's sys.exit included
            traceback.print_exc()
            rec.update(ok=False, error=f"{type(e).__name__}: {e}")
            self.failed.append(name)
        finally:
            timer.cancel()
        wall = time.monotonic() - t0
        # the UNION of the phase's trace, lowering, compile and cache-load
        # intervals (compiles are synchronous, so wall - compile is run
        # time; a sum would count an inner jit's trace twice)
        compile_s = self.compiles.covered(t0, t0 + wall)
        during = {k: v - before[k]
                  for k, v in self.compiles.counts().items()}
        rec.update(
            wall_s=round(wall, 3), compile_s=round(compile_s, 3),
            run_s=round(wall - compile_s, 3),
            backend_compiles=during["backend_compiles"],
            cache_hits=during["cache_hits"],
            cache_misses=during["cache_misses"])
        self.ran.append(name)
        print(json.dumps(rec), flush=True)


def _phase_timeout(name: str, limit: float) -> None:
    """Timer thread: a phase outlived its limit. The main thread may be
    inside XLA, so end the process from here; no result line is printed."""
    print(f"chip_smoke: phase {name!r} exceeded its {limit}s limit",
          file=sys.stderr, flush=True)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    os._exit(4)


def note(msg: str) -> None:
    """Progress on stderr, so a phase that overruns says how far it got."""
    print(f"chip_smoke [{time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _loss_rows(path: str) -> list[float]:
    with open(path) as f:
        return [float(r["total_loss"]) for r in csv.DictReader(f)]


def _ckpt_steps(ckpt_dir: str) -> list[int]:
    return sorted(int(d) for d in os.listdir(ckpt_dir) if d.isdigit())


def smoke_cfg(size: dict, seed: int = 0):
    """The ExperimentConfig the train CLI resolves for ``size`` — for the
    phases (and tests/test_tpu_compile.py) that need the Experiment
    object itself rather than a CLI's summary."""
    from rlgpuschedule_tpu import train as train_cli
    from rlgpuschedule_tpu.configs import CONFIGS
    args = train_cli.build_parser().parse_args(
        ["--config", CONFIG, "--seed", str(seed), *size["shape"],
         *size["train"], "--drain-frac", "1.0"])
    return train_cli.apply_overrides(CONFIGS[CONFIG], args)


def run_one_chip(smoke: Smoke, size: dict, out: str, seed: int) -> None:
    from rlgpuschedule_tpu import evaluate as evaluate_cli
    from rlgpuschedule_tpu import train as train_cli
    from rlgpuschedule_tpu.serve.__main__ import main as serve_main
    from rlgpuschedule_tpu.serve.bench import default_request_sizes

    base = ["--config", CONFIG, "--seed", str(seed), *size["shape"]]
    ckpt_dir = os.path.join(out, "ckpt")
    csv_path = os.path.join(out, "train.csv")
    iterations = 3
    seen: dict = {}     # what the digest line compares between two runs

    def train(rec: dict) -> None:
        summary = train_cli.main(
            [*base, *size["train"], "--drain-frac", "1.0",
             "--iterations", str(iterations), "--ckpt-dir", ckpt_dir,
             "--ckpt-every", "1", "--log-every", "1",
             "--log-csv", csv_path])
        losses = _loss_rows(csv_path)
        steps = _ckpt_steps(ckpt_dir)
        rec.update(iterations=summary["iterations"],
                   env_steps_per_iteration=(summary["env_steps"]
                                            // iterations),
                   loss=losses, ckpt_steps=steps)
        check(summary["iterations"] == iterations, "iteration count")
        check(len(losses) == iterations
              and all(math.isfinite(x) for x in losses),
              f"non-finite or missing loss rows: {losses}")
        check(len(steps) == iterations,
              f"expected {iterations} checkpoints on disk, got {steps}")
        seen["loss"] = losses

    def serve(rec: dict) -> None:
        rounds = 8
        report = serve_main(
            [*base, "--ckpt-dir", ckpt_dir, "--bench",
             "--bucket", str(size["bucket"]), "--rounds", str(rounds)])
        b = report["bench"]
        sizes = default_request_sizes(size["bucket"])
        want = sum(sizes[r % len(sizes)] for r in range(rounds))
        rec.update(requests=b["requests"], buckets=b["buckets"],
                   post_warmup_recompiles=b["post_warmup_recompiles"],
                   restored_step=report["repro"]["ckpt_step"],
                   latency_p50_ms=b["latency_p50_ms"],
                   latency_p99_ms=b["latency_p99_ms"],
                   actions_crc32=b["actions_crc32"])
        check(report["repro"]["ckpt_step"] == _ckpt_steps(ckpt_dir)[-1],
              "server did not restore the trainer's last checkpoint")
        check(b["requests"] == want,
              f"served {b['requests']} of {want} requests")
        check(b["post_warmup_recompiles"] == 0,
              "steady-state contract broken: post-warmup recompiles")
        seen["actions_crc32"] = b["actions_crc32"]

    def evaluate(rec: dict) -> None:
        report = evaluate_cli.main(
            [*base, "--drain-frac", "1.0", "--ckpt-dir", ckpt_dir,
             "--eval-windows", "2", "--no-random",
             "--horizon", str(size["eval_horizon"])])
        oracle = {k: report[k] for k in ("fifo", "sjf", "srtf", "tiresias")}
        rec.update(policy_completion=report["policy_completion"],
                   policy_jct=report["policy"], oracle_jct=oracle,
                   vs_tiresias=report.get("vs_tiresias"))
        check(report["policy_completion"] >= 1.0,
              f"replay completed {report['policy_completion']:.1%}")
        # the replay ran the on-device simulator, the baselines the
        # independent host oracle, on the same windows: any scheduler
        # that finishes a drain window lands within a small factor of
        # them unless one side's clock or job accounting is wrong
        lo, hi = 0.5 * min(oracle.values()), 2.0 * max(oracle.values())
        check(math.isfinite(report["policy"])
              and lo <= report["policy"] <= hi,
              f"policy JCT {report['policy']} outside [{lo}, {hi}] of "
              f"the host oracle's baselines")

    smoke.run("train", train)
    smoke.run("serve", serve)
    smoke.run("evaluate", evaluate)
    if len(seen) == 2:
        # compare a cold and a cache-warm run by eye: same seed -> same
        # digest, unless a cache-loaded executable computes differently
        digest = hashlib.sha256(repr(sorted(seen.items())).encode())
        print(json.dumps({"phase": "digest", **seen,
                          "digest": digest.hexdigest()[:12]}), flush=True)


def run_four_chips(smoke: Smoke, size: dict, seed: int) -> None:
    import jax
    import numpy as np

    from rlgpuschedule_tpu.experiment import Experiment
    from rlgpuschedule_tpu.train import make_run_mesh

    cfg = smoke_cfg(size, seed)
    iterations = 2
    kept: dict = {}     # the unsharded experiment, served by the router

    def train(mesh):
        exp = Experiment.build(cfg, mesh=mesh)
        hist = exp.run(iterations=iterations, log_every=1)["history"]
        flat = np.concatenate([np.asarray(x, np.float64).ravel() for x in
                               jax.tree.leaves(exp.train_state.params)])
        return exp, [h["total_loss"] for h in hist], flat

    def mesh_train(rec: dict) -> None:
        mesh = make_run_mesh("1x4x1", cfg.n_envs)
        exp, loss_m, flat_m = train(mesh)
        note(f"mesh run done, loss {loss_m}")
        shards = exp.carry.obs.addressable_shards
        devs = {s.device for s in shards}
        rows = sorted({s.data.shape[0] for s in shards})
        del exp, shards
        kept["exp"], loss_s, flat_s = train(None)
        rel = float(np.linalg.norm(flat_m - flat_s)
                    / np.linalg.norm(flat_s))
        rec.update(mesh={k: int(v) for k, v in mesh.shape.items()},
                   carry_shard_devices=len(devs), carry_shard_rows=rows,
                   loss_mesh=loss_m, loss_single=loss_s,
                   params_rel_l2=rel,
                   params_max_abs=float(np.abs(flat_m - flat_s).max()),
                   tolerance={"loss_rtol": LOSS_RTOL,
                              "loss_atol": LOSS_ATOL,
                              "params_rel_l2": PARAMS_REL_L2})
        check(len(devs) == 4 and rows == [cfg.n_envs // 4],
              f"env-batched carry not split over 4 chips: {len(devs)} "
              f"devices, shard rows {rows}")
        check(all(math.isfinite(x) for x in loss_m + loss_s),
              "non-finite loss")
        check(np.allclose(loss_m, loss_s, rtol=LOSS_RTOL, atol=LOSS_ATOL),
              f"sharded loss {loss_m} != unsharded {loss_s}")
        check(rel <= PARAMS_REL_L2,
              f"sharded params differ from unsharded: rel L2 {rel}")

    def router(rec: dict) -> None:
        from rlgpuschedule_tpu.obs import Registry
        from rlgpuschedule_tpu.serve.batching import (PolicyServer,
                                                      stack_requests)
        from rlgpuschedule_tpu.serve.bench import build_request_pool
        from rlgpuschedule_tpu.serve.router import EngineRouter

        exp = kept["exp"]
        params = exp.train_state.params
        pool = build_request_pool(exp.apply_fn, params, exp.env_params,
                                  exp.traces, steps=1, faults=exp.faults)
        n_req, bucket = 64, size["bucket"]
        reqs = [pool[(i * 7) % len(pool)] for i in range(n_req)]
        note(f"request pool built ({len(pool)} rows)")
        registry = Registry()
        fleet = EngineRouter(exp.apply_fn, params, exp.env_params,
                             max_bucket=bucket, registry=registry,
                             n_engines=4)
        fleet.warmup(*reqs[0])
        note(f"4 engines warmed, buckets {fleet.warmed_buckets}")
        param_devs = [{d for leaf in jax.tree.leaves(e._params)
                       for d in leaf.devices()} for e in fleet.engines]
        # the reference: engine 0 alone, bucket by bucket
        want = []
        for i in range(0, n_req, bucket):
            batch = reqs[i:i + bucket]
            acts, _ = fleet.engines[0].decide(
                stack_requests([o for o, _ in batch]),
                stack_requests([m for _, m in batch]))
            want.extend(np.asarray(acts))
        # 4 live dispatcher threads: on the chip device work is
        # concurrent (the dispatch lock is CPU-only)
        note("engine 0 answered the reference batches")
        server = PolicyServer(fleet, registry=registry)
        server.start(dispatchers=4)
        try:
            futures = [server.submit(o, m) for o, m in reqs]
            got = [np.asarray(f.result(timeout=120).action)
                   for f in futures]
        finally:
            server.stop()
        rows = [s.rows for s in fleet.stats()]
        faults = fleet.fault_stats()
        rec.update(requests=len(got),
                   engine_devices=[str(e.device) for e in fleet.engines],
                   per_engine_rows=rows,
                   per_engine_recompiles=fleet.per_engine_recompiles(),
                   engine_failures=faults["failures"],
                   serialized_dispatch=fleet.serialized_dispatch())
        check(all(len(d) == 1 for d in param_devs)
              and len(set().union(*param_devs)) == 4,
              f"engines' params not on 4 distinct devices: {param_devs}")
        check(all(np.array_equal(g, w) for g, w in zip(got, want)),
              "a routed answer differs from engine 0's")
        check(all(r > 0 for r in rows),
              f"an engine served nothing: {rows}")
        check(faults["failures"] == 0 and faults["retry_hedges"] == 0,
              f"engine failures behind the retry hedge: {faults}")
        check(sum(fleet.per_engine_recompiles()) == 0,
              "post-warmup recompiles in the routed fleet")

    smoke.run("mesh_train", mesh_train)
    smoke.run("router", router)


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = run only the multi-chip paths (mesh train vs "
                         "unsharded, 4-engine router)")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal: same phases at a small shape, any "
                         "platform; the last line says ok=false off-TPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="work dir for checkpoints/logs (default: "
                         "out/chip_smoke next to this script, which git "
                         "ignores); emptied at start")
    args = ap.parse_args(argv)

    try:
        from rlgpuschedule_tpu import native
        from rlgpuschedule_tpu.utils.platform import (device_record,
                                                      enable_compile_cache,
                                                      require_tpu)
    except ImportError as e:
        print(f"chip_smoke.py: the rlgpuschedule_tpu package is not "
              f"importable from here ({e})", file=sys.stderr)
        return 5
    # before any work: no TPU, no run (--tiny is the rehearsal's way in)
    device = (device_record() if args.tiny
              else require_tpu("chip_smoke.py"))
    if device["count"] < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but jax sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 5
    cache = enable_compile_cache()
    import jax

    root = os.path.dirname(os.path.abspath(__file__))
    out = os.path.abspath(args.out or os.path.join(
        root, "out", "chip_smoke"))
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    size = TINY if args.tiny else FULL
    print(json.dumps({
        "phase": "setup", "device": device, "chips": args.chips,
        "tiny": args.tiny, "seed": args.seed, "config": CONFIG,
        "shape": size["shape"] + size["train"],
        "compile_cache_dir": cache,
        # not -1: whoever placed the directory capped it, which puts it in
        # jax's LRU layout (a -cache/-atime pair per entry)
        "compile_cache_max_size": jax.config.jax_compilation_cache_max_size,
        "compile_cache_entries_at_start":
            (sum(n.endswith("-cache") for n in os.listdir(cache))
             if os.path.isdir(cache) else 0),
        # evaluate's baselines: the built C++ oracle, or (no compiler on
        # this machine) the Python one
        "native_oracle": native.available(),
        "native_build_error": native.build_error()}), flush=True)

    smoke = Smoke()
    if args.chips == 4:
        run_four_chips(smoke, size, args.seed)
    else:
        run_one_chip(smoke, size, out, args.seed)

    phases_ok = not smoke.failed
    print(json.dumps({"phase": "summary", "phases_ok": phases_ok,
                      "ran": smoke.ran, "failed": smoke.failed}),
          flush=True)
    print(json.dumps({"ok": phases_ok and device["platform"] == "tpu",
                      "device": device}), flush=True)
    return 0 if phases_ok else 1


if __name__ == "__main__":
    sys.exit(main())
