#!/usr/bin/env python3
"""What the check's follower costs on the device as the policy grows: the
plain PPO reference (``reference/ppo.Follower``) ALONE, at a cell's own
rows, minibatches and blocks, on a seeded trajectory of the cell's shapes,
with the policy's trunk widened to ``--dense-width``. No program runs: the
program's module gives the names and shapes of the parameter tree only.

    python3 benchmark/follower_memory.py --workload <cell> --dense-width 4096

One width a process (a process's peak never falls). Prints one JSON line:
the device's ``bytes_in_use`` and ``peak_bytes_in_use`` after each phase of
one followed step as ``TrainCell.check`` makes it (the start on the host,
the follower built, the behaviour pass, the donated update, the
parameters' change against the start), and the follower's ``memory`` as the
``check_memory`` line has it. Two widths give the slope (bytes a parameter)
and the intercept (what does not grow with the policy); PERF.md section 4
keeps both. The benchmark's own runs never run this. Needs the TPU like
``run.py`` (``--rehearse-cpu`` for the tiny shape, where the device has no
memory statistics).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# (module, leaf) -> the axis that is the pooled vector's width
WIDENED = {("Dense_0", "kernel"): 1, ("Dense_0", "bias"): 0,
           ("LayerNorm_3", "scale"): 0, ("LayerNorm_3", "bias"): 0,
           ("policy", "kernel"): 0, ("value", "kernel"): 0}


def widen(shapes, width: int):
    """The tree of shapes with the trunk's pooled vector ``width`` wide:
    ``Dense_0``'s outputs, ``LayerNorm_3`` and both heads' inputs."""
    import jax

    def leaf(path, s):
        names = [getattr(k, "key", str(k)) for k in path]
        axis = WIDENED.get((names[-2], names[-1]))
        if axis is None:
            return s
        return jax.ShapeDtypeStruct(
            tuple(width if i == axis else d for i, d in enumerate(s.shape)),
            s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/follower_memory.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--dense-width", type=int, default=0,
                    help="0: the configuration's own")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import common
    from benchmark.reference import ppo as ppo_ref
    from benchmark.reference import weights
    from rlgpuschedule_tpu.experiment import build_env_params
    from rlgpuschedule_tpu.models import make_policy
    from rlgpuschedule_tpu.utils.platform import (device_record,
                                                  enable_compile_cache,
                                                  require_tpu)
    device = (device_record() if args.rehearse_cpu
              else require_tpu("benchmark follower_memory"))
    enable_compile_cache()
    loaded = common.load_cell(args.workload)
    cfg = common.resolve_config(loaded["config"], args.seed,
                                args.rehearse_cpu)
    env = build_env_params(cfg)
    T, E, A = cfg.ppo.n_steps, cfg.n_envs, env.n_actions
    net = make_policy(cfg.obs_kind, A, n_cluster_nodes=cfg.n_nodes,
                      queue_len=cfg.queue_len,
                      n_placements=cfg.n_placements,
                      preempt_len=cfg.preempt_len)
    shapes = jax.eval_shape(
        net.init, jax.random.PRNGKey(0),
        jnp.zeros((1, *env.obs_shape()), jnp.float32),
        jnp.ones((1, A), bool))
    if args.dense_width:
        shapes = widen(shapes, args.dense_width)

    phases: dict = {}

    def read(phase: str) -> None:
        stats = jax.local_devices()[0].memory_stats() or {}
        phases[phase] = {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")}

    read("start")
    params0 = jax.device_get(weights.make_params(shapes, args.seed))
    read("start_on_host")

    def trajectory(key):
        k = jax.random.split(key, 6)
        obs = jax.random.uniform(k[0], (T, E, *env.obs_shape()), jnp.float32)
        mask = jax.random.bernoulli(k[1], 0.5, (T, E, A)).at[..., -1].set(
            True)
        logits = jnp.where(mask, jax.random.normal(k[2], (T, E, A)), -1e30)
        return {"obs": obs, "mask": mask,
                "action": jnp.argmax(logits, -1).astype(jnp.int32),
                "reward": jax.random.normal(k[3], (T, E), jnp.float32),
                "done": jax.random.bernoulli(k[4], 0.01, (T, E)),
                "last_obs": jax.random.uniform(
                    k[5], (E, *env.obs_shape()), jnp.float32),
                "last_mask": mask[-1]}

    traj = jax.block_until_ready(jax.jit(trajectory)(
        jax.random.PRNGKey(args.seed & 0x7FFFFFFF)))
    read("trajectory")
    p = cfg.ppo
    hyper = ppo_ref.Hyper(
        gamma=p.gamma, gae_lambda=p.gae_lambda, clip_eps=p.clip_eps,
        vf_coef=p.vf_coef, ent_coef=p.ent_coef, lr=p.lr,
        max_grad_norm=p.max_grad_norm, n_epochs=p.n_epochs,
        n_minibatches=p.n_minibatches)
    follower = ppo_ref.Follower(
        common.Reference(loaded["config"], args.rehearse_cpu), hyper,
        params0, int(loaded["config"]["reference_block_rows"]))
    jax.block_until_ready(follower.params)
    read("follower_built")
    out = follower.step(traj, jax.random.PRNGKey(7))
    jax.block_until_ready(follower.params)
    read("first_update")
    # as TrainCell.check reads it: the difference, then its leaves' norms
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(x)))
                                  for x in jax.tree.leaves(tree)])
    change = np.asarray(norms(delta(follower.params, params0)), np.float64)
    read("change_read")
    print(json.dumps({
        "dense_width": args.dense_width, "rows": T * E,
        "block_rows": int(loaded["config"]["reference_block_rows"]),
        "loss": out["loss"], "change_tree": float(np.sqrt(
            np.sum(change ** 2))),
        "memory": follower.memory, "phases": phases, "device": device},
        default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
