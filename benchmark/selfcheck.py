#!/usr/bin/env python3
"""The benchmark's self-check, on the CPU, no chip needed:

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py

1. the trace reducer against ``fixtures/``' small recorded trace, whose
   busy time, window and top operation are known (and recomputed here by
   brute force);
2. each plain reference against the program at a tiny shape: the forward
   pass of ``reference/forward_grid.py`` against the program's module in
   float32 (``forward_tokens.py``: ``tests/test_trunk.py``), GAE against its scan,
   one whole PPO iteration's learning half (loss, Adam average, parameters)
   against its learn step in float32;
3. ``run.py --rehearse-cpu`` for every cell of ``BENCHMARK.json``, both
   ``--trace`` values: a well-formed last line, ``correct: false``, the
   CPU named, and a ``check_memory`` line that shows none of the program's
   state on the device while the references ran.

Prints one line per check and ``SELFCHECK ok`` / ``SELFCHECK FAILED``; no
part of the repo's tier-1 count.
"""
from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAILED: list[str] = []


def report(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'ok  ' if ok else 'FAIL'} {name} {detail}", flush=True)
    if not ok:
        FAILED.append(name)


def check_trace_reducer() -> None:
    from benchmark import trace_reduce
    with gzip.open(os.path.join(BENCH_DIR, "fixtures",
                                "trace_small.json.gz"), "rt") as f:
        events = json.load(f)
    with open(os.path.join(BENCH_DIR, "fixtures",
                           "trace_small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_events(events, chips=1)
    # brute force: sweep the sorted interval ends of the one device plane
    evs = events["devices"][sorted(events["devices"])[0]]
    marks = sorted({t for _, s, d in evs for t in (s, s + d)})
    busy = 0.0
    starts = sorted((s, s + d) for _, s, d in evs)
    for a, b in zip(marks, marks[1:]):
        mid = (a + b) / 2
        busy += (b - a) * any(s <= mid < e for s, e in starts
                              if s <= mid)
    report("trace_reduce.busy_s", abs(got["busy_s"] - busy * 1e-9) < 1e-9
           and abs(got["busy_s"] - want["busy_s"]) < 1e-9,
           f"{got['busy_s']} (brute force {busy * 1e-9}, recorded "
           f"{want['busy_s']})")
    report("trace_reduce.window_s",
           abs(got["window_s"] - want["window_s"]) < 1e-9,
           str(got["window_s"]))
    report("trace_reduce.top_op",
           got["device_ops"][0][0] == want["top_op"],
           got["device_ops"][0][0][:60])
    report("trace_reduce.idle_share",
           0.0 < 1 - got["busy_s"] / got["window_s"] < 1.0,
           f"{100 * (1 - got['busy_s'] / got['window_s']):.3f} %")


def check_references() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from rlgpuschedule_tpu.algos.ppo import PPOConfig, make_learn_step
    from rlgpuschedule_tpu.algos.ppo import make_optimizer
    from rlgpuschedule_tpu.algos.rollout import Transition
    from rlgpuschedule_tpu.models import make_policy
    from rlgpuschedule_tpu.ops.gae import compute_gae
    from flax.training.train_state import TrainState

    from benchmark.common import Reference
    from benchmark.reference import gae as gae_ref
    from benchmark.reference import ppo as ppo_ref
    from benchmark.reference import weights
    from benchmark.reference.forward import forward

    T, E, A = 8, 16, 9
    key = jax.random.PRNGKey(3)
    # a tiny observation, the program's policy for it, and the reference
    # a configuration's file would name for it
    for kind, shape in (("grid", (16, 4, 2)),):
        ref = Reference({"reference": f"forward_{kind}"})
        net = make_policy(kind, A, dtype=jnp.float32)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        obs = jax.random.uniform(k1, (T, E, *shape))
        mask = jax.random.bernoulli(k2, 0.7, (T, E, A)).at[..., -1].set(True)
        shapes = jax.eval_shape(net.init, key, obs[0, :1], mask[0, :1])
        params = weights.make_params(shapes, 11)
        apply_fn = lambda p, o, m: net.apply(p, o, m)
        with jax.default_matmul_precision("highest"):
            lg, v = apply_fn(params, obs[0], mask[0])
        lr, vr = forward(ref, params, obs[0], mask[0])
        gap = float(jnp.max(jnp.abs(jnp.where(mask[0], lg - lr, 0.0))))
        report(f"forward_{kind}.logits", gap < 1e-5, f"gap {gap:.2e}")
        report(f"forward_{kind}.value",
               float(jnp.max(jnp.abs(v - vr))) < 1e-5)
        # one whole learning half, program in float32 against the reference
        cfg = PPOConfig(n_steps=T, n_epochs=2, n_minibatches=4)
        action = jax.random.randint(k3, (T, E), 0, A)
        action = jnp.where(jnp.take_along_axis(
            mask, action[..., None], -1)[..., 0], action, A - 1)
        reward = jax.random.normal(k4, (T, E))
        done = jax.random.bernoulli(k5, 0.1, (T, E))
        with jax.default_matmul_precision("highest"):
            flat = lambda x: x.reshape(T * E, *x.shape[2:])
            lg, val = apply_fn(params, flat(obs), flat(mask))
            lp = ppo_ref.log_prob(lg, flat(action)).reshape(T, E)
            val = val.reshape(T, E)
            _, last_v = apply_fn(params, obs[-1], mask[-1])
            tr = Transition(obs=obs, action=action, log_prob=lp, value=val,
                            reward=reward, done=done, mask=mask,
                            env_steps_dt=jnp.zeros((T, E)))
            state = TrainState.create(apply_fn=net.apply, params=params,
                                      tx=make_optimizer(cfg))
            state2, metrics = jax.jit(make_learn_step(apply_fn, cfg))(
                state, tr, last_v, key)
        adv_p, ret_p = compute_gae(reward, val, done, last_v, cfg.gamma,
                                   cfg.gae_lambda)
        adv_r, ret_r = gae_ref.gae(reward, val, done, last_v, cfg.gamma,
                                   cfg.gae_lambda)
        report(f"gae_{kind}", float(np.max(np.abs(
            np.asarray(adv_p) - adv_r))) < 1e-5)
        hp = ppo_ref.Hyper(cfg.gamma, cfg.gae_lambda, cfg.clip_eps,
                           cfg.vf_coef, cfg.ent_coef, cfg.lr,
                           cfg.max_grad_norm, cfg.n_epochs,
                           cfg.n_minibatches)
        fol = ppo_ref.Follower(ref, hp, params, block=8)
        ref = fol.step({"obs": obs, "mask": mask, "action": action,
                        "reward": reward, "done": done,
                        "last_obs": obs[-1], "last_mask": mask[-1]}, key)
        dloss = abs(ref["loss"] - float(metrics.total_loss))
        report(f"ppo_{kind}.loss", dloss < 1e-4 * max(1, abs(ref["loss"])),
               f"reference {ref['loss']:.6f} program "
               f"{float(metrics.total_loss):.6f}")
        mu_p = state2.opt_state[1][0].mu
        dmu = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(mu_p), jax.tree.leaves(fol.adam.mu)))
        dpar = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(state2.params), jax.tree.leaves(fol.params)))
        report(f"ppo_{kind}.adam_average", dmu < 1e-5, f"gap {dmu:.2e}")
        # Adam divides by sqrt(nu): rounding noise on all-but-zero
        # gradients is amplified up to one learning rate per step
        report(f"ppo_{kind}.parameters", dpar < 8 * cfg.lr,
               f"gap {dpar:.2e}")


def check_rehearsals() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in cells:
        for trace in (0, 1):
            p = subprocess.run(
                [sys.executable, os.path.join(BENCH_DIR, "run.py"),
                 "--workload", cell, "--seed", str(2 ** 31 + 7 + trace),
                 "--seconds", "1", "--trace", str(trace), "--rehearse-cpu"],
                capture_output=True, text=True, env=env, cwd=ROOT,
                timeout=900)
            name = f"rehearse {cell} --trace {trace}"
            if p.returncode != 0:
                report(name, False, p.stderr[-400:])
                continue
            try:
                line = json.loads(p.stdout.strip().splitlines()[-1])
                ok = (set(line) - {"breakdown"} == {
                    "correct", "attempted", "failed", "metrics", "device",
                    "checks"} and list(line)[-1] == "checks"
                    and line["correct"] is False
                    and line["device"]["platform"] == "cpu"
                    and line["attempted"] > 0 and line["metrics"]
                    and all(set(m) == {"value", "unit"}
                            for m in line["metrics"].values()))
                failing = [json.loads(l)["check"]
                           for l in p.stdout.splitlines()
                           if l.startswith('{"check"')
                           and not json.loads(l)["ok"]]
                report(name, ok, f"metrics {sorted(line['metrics'])}"
                       + (f" checks not holding at the tiny shape: "
                          f"{failing}" if failing else ""))
                memory = [json.loads(l) for l in p.stdout.splitlines()
                          if l.startswith('{"phase": "check_memory"')]
                report(f"{name}: the references ran with the program's "
                       f"state parked", bool(memory) and all(
                           m["program_state_bytes_on_device"] == 0
                           and m["followers_alive"] == 1 for m in memory),
                       f"check_memory lines {len(memory)}")
            except (ValueError, KeyError, IndexError) as e:
                report(name, False, f"bad last line: {e}")


def main() -> int:
    check_trace_reducer()
    check_references()
    check_rehearsals()
    print("SELFCHECK FAILED: " + ", ".join(FAILED) if FAILED
          else "SELFCHECK ok", flush=True)
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
