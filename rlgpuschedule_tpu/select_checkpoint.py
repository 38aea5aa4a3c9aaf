"""Post-hoc checkpoint selection on a held-out VALIDATION stream (L6).

``python -m rlgpuschedule_tpu.select_checkpoint --ckpt-dir out/run ...``

Round-5 measurement: neither the drain probe nor the streaming probe
reliably ranks stitched full-trace quality (drain-probe best read 1.08 vs
Tiresias on the test stream, streaming-probe best 1.28, while an
unselected mid-series checkpoint read 0.96 on validation) — per-window
probe JCT and full-trace JCT are different functionals of the same
policy. The honest selector is therefore the DELIVERABLE's own metric
(full-trace stitched replay) on a validation stream that is neither the
training trace nor the test stream: sweep every retained checkpoint
(``train --ckpt-keep N`` retains a series), score each, emit the argmin.
The test stream is then run ONCE with the chosen step
(``evaluate --ckpt-step``), keeping selection and measurement disjoint.

Prints one JSON line: {"dir", "step", "val_ratio", "val_tiresias",
"ranking": [[ratio, step], ...]}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    from .configs import TRUNK_NAMES
    p = argparse.ArgumentParser(
        prog="rlgpuschedule_tpu.select_checkpoint",
        description="Rank retained checkpoints by full-trace JCT on a "
                    "held-out validation stream.")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--config", default="ppo-mlp-synth64")
    p.add_argument("--seed", type=int, default=None,
                   help="the TRAINING seed the checkpointed run used "
                        "(train --seed); the val-seed guard checks "
                        "against this, not just the preset default")
    p.add_argument("--val-seed", type=int, default=2000,
                   help="seed of the VALIDATION stream (must differ from "
                        "the training seed, from training seed + 1000 — "
                        "the --eval-every probe's default held-out "
                        "stream — and from the test seed)")
    p.add_argument("--test-seed", type=int, default=None,
                   help="seed of the TEST stream the chosen step will be "
                        "measured on (evaluate's stream); pass it so the "
                        "validation/test disjointness this selector "
                        "promises is actually enforced, not assumed")
    p.add_argument("--val-jobs", type=int, default=1024,
                   help="validation stream length in jobs")
    p.add_argument("--stitch-drain-jobs", type=int, default=8,
                   help="deep-backlog batching for the sweep (selection "
                        "only ranks checkpoints, so a coarse fast stitch "
                        "is fine; the test run chooses its own)")
    # the same shape overrides the training run used (must match the
    # checkpoints' shapes)
    p.add_argument("--n-envs", type=int, default=None)
    p.add_argument("--n-nodes", type=int, default=None)
    p.add_argument("--gpus-per-node", type=int, default=None)
    p.add_argument("--window-jobs", type=int, default=None)
    p.add_argument("--queue-len", type=int, default=None)
    p.add_argument("--horizon", type=int, default=None)
    p.add_argument("--obs-kind", default=None,
                   choices=["flat", "grid", "graph", "tokens"])
    p.add_argument("--trunk", default=None, choices=TRUNK_NAMES,
                   help="obs-kind tokens: the trunk sizes the checkpoint "
                        "was trained with (train --trunk)")
    p.add_argument("--trace-load", type=float, default=None,
                   help="proxy traces: offered load of the validation "
                        "stream — match the TEST stream's load (round-5 "
                        "measurement: a load-1.1-trained policy reads "
                        "7.4x Tiresias on a 1.6x-overload 100k stream; "
                        "selection must happen in the deliverable's "
                        "regime)")
    return p


def main(argv: list[str] | None = None) -> dict:
    args = build_parser().parse_args(argv)
    from .configs import CONFIGS
    if args.config not in CONFIGS:
        sys.exit(f"unknown config {args.config!r}")
    over = {k: v for k, v in
            {"seed": args.seed, "n_envs": args.n_envs,
             "n_nodes": args.n_nodes,
             "gpus_per_node": args.gpus_per_node,
             "window_jobs": args.window_jobs, "queue_len": args.queue_len,
             "horizon": args.horizon, "obs_kind": args.obs_kind,
             "trunk": args.trunk, "trace_load": args.trace_load}.items()
            if v is not None}
    cfg = dataclasses.replace(CONFIGS[args.config], **over)
    if cfg.trace in ("philly", "pai"):
        sys.exit("csv traces have no seeded held-out stream (the loader "
                 "would silently re-read the training csv — the same "
                 "no-op train.py refuses for --eval-seed); select "
                 "against a generated validation stream or split the "
                 "csv yourself")
    if args.val_seed == cfg.seed:
        sys.exit("--val-seed equals the config's training seed; selection "
                 "on the training distribution is not validation")
    if args.val_seed == cfg.seed + 1000:
        sys.exit("--val-seed equals training seed + 1000, the in-training "
                 "--eval-every probe's default held-out seed; a --keep-best "
                 "run already optimized checkpoint choice against that "
                 "stream, so selecting on it is not validation either")
    if args.test_seed is not None:
        if args.test_seed == args.val_seed:
            sys.exit("--test-seed equals --val-seed; selection and "
                     "measurement must run on disjoint streams")
        if args.test_seed == cfg.seed:
            sys.exit("--test-seed equals the config's training seed; "
                     "measuring on the training distribution is not a "
                     "test")

    import os

    from . import eval as eval_lib
    from .checkpoint import Checkpointer
    from .experiment import Experiment, load_source_trace
    from .sim.core import validate_trace
    from .sim.schedulers import run_baseline
    from .utils.platform import enable_compile_cache

    enable_compile_cache()
    exp = Experiment.build(cfg)
    val = validate_trace(
        exp.env_params.sim,
        load_source_trace(cfg, n_jobs=args.val_jobs, seed=args.val_seed),
        clamp=True)
    tiresias = run_baseline(val, cfg.n_nodes, cfg.gpus_per_node,
                            "tiresias").avg_jct()
    rows = []
    with Checkpointer(os.path.abspath(args.ckpt_dir)) as ck:
        steps = ck.all_steps()
        if not steps:
            sys.exit(f"no checkpoints under {args.ckpt_dir}")
        for step in sorted(steps):
            exp.restore_checkpoint(ck, step=step)
            out = eval_lib.full_trace_replay(
                exp.apply_fn, exp.train_state.params, exp.env_params, val,
                drain_completions=args.stitch_drain_jobs)
            ratio = out["avg_jct"] / tiresias
            rows.append((round(ratio, 4), step))
            print(f"step {step}: {out['avg_jct']:.1f} ratio {ratio:.4f}",
                  file=sys.stderr, flush=True)
    best = min(rows)
    result = {"dir": args.ckpt_dir, "step": best[1], "val_ratio": best[0],
              "val_tiresias": round(tiresias, 1), "ranking": sorted(rows)}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
