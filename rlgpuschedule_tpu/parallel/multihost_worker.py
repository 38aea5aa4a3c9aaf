"""One process of the multi-host CPU dryrun (SURVEY.md §4 "Distributed
without a real cluster"; VERDICT r2 next-round #4).

Run as ``python -m rlgpuschedule_tpu.parallel.multihost_worker --coordinator
127.0.0.1:PORT --num-procs 2 --proc-id K --devices-per-proc 4`` — normally
via ``__graft_entry__.dryrun_multihost`` (plain gate) or
``__graft_entry__.dryrun_multihost_supervised`` (failure-recovery gate),
which spawn all ranks and check their reports agree. Each rank:

1. ``multihost.initialize`` (jax.distributed + gloo CPU collectives,
   retry-with-backoff on the coordinator connect),
2. builds the global (pop, data) mesh spanning both processes,
3. cuts ONLY its own env windows of a config-1-style trace
   (per-host trace sharding) and assembles the global Trace with
   ``multihost.global_traces``,
4. runs ``--steps`` GSPMD DP train steps (gradient psum crosses the
   process boundary) and prints a params fingerprint — identical across
   ranks iff the cross-process allreduce works,
5. runs a PBT exploit gather over a pop axis that spans the two processes
   (the cross-host weight copy, DCN-analog) and prints its fingerprint
   (skippable with ``--no-pbt-check``).

Resilience surface (the supervised dryrun drives all of it):

- ``--heartbeat-dir`` — beat a per-rank file before every step
  (``resilience.HeartbeatWriter``); the supervisor's timeout watchdog
  reads them.
- ``--ckpt-dir`` — after every completed step, atomically persist this
  rank's params + opt_state to a PER-STEP ``rank<r>.step<k>.npz`` (+ a
  ``rank<r>.step`` latest-step sidecar the supervisor can read without
  numpy; last ``_CKPT_KEEP`` step files retained). Plain npz, not Orbax:
  each rank saves only its own replicated copy, so no cross-process
  checkpoint barrier can deadlock a gang that is already dying.
- ``--resume-step S`` — restore ``rank<r>.step<S>.npz`` and continue
  from step S (the supervisor passes the minimum completed step across
  ranks; a rank that durably got further must restore the OLDER state,
  or the gang resumes from divergent replicated params).
- ``--restore-rank R`` — restore RANK R's checkpoint file instead of
  this rank's own (default). This is the shrink-to-fit hook: after a
  permanent rank loss the supervisor relaunches the gang at the
  surviving world size, and new rank i restores surviving old rank
  ``restore_ranks[i]``'s file. Sound because the persisted state is
  replicated (params + optimizer moments) — every rank's file at step S
  holds the same state, so any surviving rank's copy re-seeds the
  shrunk gang at ANY world size (``--num-procs`` is free to differ from
  the world the checkpoint was written at; the update geometry is
  re-validated against the shrunk global batch before anything
  compiles).
- ``--fault kill-rank@T:rank=R | lose-rank@T:rank=R`` — rank R dies
  un-gracefully right before step T, i.e. before entering the step's
  collective, so every rank's last durable checkpoint is step T-1 or
  later. ``kill-rank`` exits restartable (``faults.KILL_RANK_EXIT``);
  ``lose-rank`` exits ``faults.LOSE_RANK_EXIT``, the permanent-loss
  signature the supervisor answers with a shrink instead of a respawn.

Per-step rollout keys are ``PRNGKey(i)`` — a restarted rank replays the
same key sequence from its resume step, so all ranks (including the
respawned one) converge to identical fingerprints; a SHRUNK gang runs a
smaller env batch (fewer global devices), so its fingerprints differ
from the old world's, but they must still AGREE across the surviving
ranks — the cross-rank contract holds at every world size.
"""
from __future__ import annotations

import argparse
import os


_CKPT_KEEP = 4   # per-rank retained step files (bounds disk, >= any lag)


def _save_rank_ckpt(ckpt_dir: str, rank: int, state, completed: int) -> None:
    """Persist this rank's state as a PER-STEP file plus a latest-step
    sidecar. Per-step files are load-bearing: when a rank dies mid-step,
    its PEERS may have durably completed one step more, so the supervisor
    resumes the gang from the MINIMUM completed step — and a rank that is
    ahead must restore that older state, not its own newest (restoring
    divergent per-rank states into a replicated-params DP program
    assembles garbage global arrays; measured as NaN metrics two steps
    after a resume)."""
    import glob
    import jax
    import numpy as np
    leaves = [np.asarray(x) for x in
              jax.tree.leaves((state.params, state.opt_state))]
    path = os.path.join(ckpt_dir, f"rank{rank}.step{completed}.npz")
    tmp = path + ".tmp.npz"
    np.savez(tmp, completed=completed,
             **{f"leaf{j}": l for j, l in enumerate(leaves)})
    os.replace(tmp, path)
    side = os.path.join(ckpt_dir, f"rank{rank}.step")
    with open(side + ".tmp", "w") as f:
        f.write(str(completed))
    os.replace(side + ".tmp", side)
    kept = sorted(glob.glob(os.path.join(ckpt_dir, f"rank{rank}.step*.npz")),
                  key=lambda p: int(p.rsplit("step", 1)[1].split(".")[0]))
    for old in kept[:-_CKPT_KEEP]:
        os.remove(old)


def _load_rank_ckpt(ckpt_dir: str, rank: int, state, step: int):
    """Restore this rank's state AT exactly ``step`` (the gang-wide
    minimum the supervisor chose)."""
    import jax
    import numpy as np
    path = os.path.join(ckpt_dir, f"rank{rank}.step{step}.npz")
    data = np.load(path)
    template = (state.params, state.opt_state)
    treedef = jax.tree.structure(template)
    leaves = [data[f"leaf{j}"] for j in range(treedef.num_leaves)]
    params, opt_state = jax.tree.unflatten(treedef, leaves)
    return state.replace(params=params, opt_state=opt_state)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-procs", type=int, required=True)
    ap.add_argument("--proc-id", type=int, required=True)
    ap.add_argument("--devices-per-proc", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--heartbeat-dir", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume-step", type=int, default=-1,
                    help=">= 0: restore rank<r>.npz from --ckpt-dir and "
                         "continue from this step")
    ap.add_argument("--restore-rank", type=int, default=-1,
                    help=">= 0: with --resume-step, restore THIS rank's "
                         "checkpoint file instead of our own (shrink-to-"
                         "fit: a surviving old rank's replicated state "
                         "re-seeds the shrunk gang)")
    ap.add_argument("--fault", action="append", default=None,
                    help="kill-rank@T:rank=R | lose-rank@T:rank=R "
                         "(resilience.parse_fault)")
    ap.add_argument("--obs-dir", default=None,
                    help="append this rank's telemetry events "
                         "(obs.EventBus JSONL stream) under this "
                         "directory; the supervisor's report CLI merges "
                         "all ranks into one timeline")
    ap.add_argument("--no-pbt-check", action="store_true",
                    help="skip the PBT exploit-gather section (the "
                         "supervised dryrun tests recovery, not PBT)")
    args = ap.parse_args(argv)

    # platform pins must precede ANY jax device access. The env var alone
    # is NOT enough here: ``python -m`` imports the package __init__s
    # (which import jax) before main() runs, and jax snapshots
    # JAX_PLATFORMS at import — so mutate the live config too. Measured
    # without it (2026-08-04): with the rig's libtpu importable, the
    # first device access probed the TPU plugin through minutes of
    # metadata-fetch retries on ONE rank, desyncing the gang past gloo's
    # ~30s rendezvous window.
    os.environ["JAX_PLATFORMS"] = "cpu"   # for any subprocess readers
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{args.devices_per_proc}").strip()

    import jax
    jax.config.update("jax_platforms", "cpu")
    # no persistent compile cache in multi-controller workers: RELOADING
    # a serialized gloo-collective executable segfaults the rank on this
    # jax (measured; __graft_entry__'s spawners drop
    # JAX_COMPILATION_CACHE_DIR too, but any other launcher may pass it)
    jax.config.update("jax_compilation_cache_dir", None)
    from rlgpuschedule_tpu.parallel import multihost
    from rlgpuschedule_tpu.resilience import (FaultInjector, HeartbeatWriter,
                                              parse_fault)

    bus = None
    from rlgpuschedule_tpu.obs.trace import NULL_TRACER, Tracer
    tracer = NULL_TRACER
    if args.obs_dir:
        from rlgpuschedule_tpu.obs import EventBus
        from rlgpuschedule_tpu.obs import skew as skew_lib
        bus = EventBus(args.obs_dir, rank=args.proc_id)
        bus.emit("worker_start", world=args.num_procs,
                 devices_per_proc=args.devices_per_proc, steps=args.steps,
                 resume_step=(args.resume_step
                              if args.resume_step >= 0 else None),
                 restore_rank=(args.restore_rank
                               if args.restore_rank >= 0 else None))
        # clock-skew handshake: a dedicated (wall, mono) offset sample at
        # start and each step, so the report CLI can rewrite all ranks'
        # timelines onto one corrected monotonic axis
        skew_lib.stamp(bus, source="worker_start")
        tracer = Tracer(bus, enabled=True)
    injector = FaultInjector([parse_fault(s) for s in args.fault or []],
                             bus=bus)
    hb = (HeartbeatWriter(args.heartbeat_dir, args.proc_id)
          if args.heartbeat_dir else None)
    if hb is not None:
        # beat BEFORE the first jax import: startup (backend init +
        # distributed connect + XLA compiles) is the longest beat-free
        # stretch of the whole run, and without this the supervisor's
        # missing-file grace window has to cover all of it
        hb.beat(-1)
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)

    multihost.initialize(args.coordinator, args.num_procs, args.proc_id)
    n_global = args.num_procs * args.devices_per_proc
    assert len(jax.devices()) == n_global, \
        f"expected {n_global} global devices, got {len(jax.devices())}"
    multihost.warmup_collectives()

    import jax.numpy as jnp
    import numpy as np
    from flax.training.train_state import TrainState

    from rlgpuschedule_tpu.algos import (PPOConfig, init_carry,
                                         make_ppo_step)
    from rlgpuschedule_tpu.algos.ppo import make_optimizer
    from rlgpuschedule_tpu.env import EnvParams, stack_traces
    from rlgpuschedule_tpu.models import make_policy
    from rlgpuschedule_tpu.parallel import dp, mesh as mesh_lib, pbt
    from rlgpuschedule_tpu.sim.core import SimParams
    from rlgpuschedule_tpu.traces import gen_poisson_trace

    # ---- DP across processes (config-1 shape, tiny) ----------------------
    mesh = multihost.global_mesh()
    n_envs = 2 * n_global
    cfg = PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2)
    # elastic fail-fast: the world size may differ from the one the
    # checkpoint was written at (shrink-to-fit relaunch) — re-validate
    # the update geometry against THIS world's global batch before any
    # mesh/compile work, so an untileable shrink dies with a clear error
    # instead of a shape error mid-step
    from rlgpuschedule_tpu.algos import resolve_geometry
    try:
        resolve_geometry(cfg.n_epochs, cfg.n_minibatches,
                         cfg.minibatch_size, cfg.n_steps * n_envs)
    except ValueError as e:
        raise SystemExit(
            f"elastic geometry: world size {args.num_procs} "
            f"({n_global} devices, global batch {cfg.n_steps}x{n_envs}) "
            f"does not tile the update geometry: {e}") from e
    env_params = EnvParams(
        sim=SimParams(n_nodes=4, gpus_per_node=4, max_jobs=12, queue_len=4),
        obs_kind="flat", horizon=32, time_scale=60.0, reward_scale=100.0)

    # per-host trace sharding: cut ONLY the windows this process owns
    sl = multihost.process_env_slice(mesh, n_envs)
    local_windows = [gen_poisson_trace(0.1, 8, seed=e, max_jobs=12,
                                       mean_duration=30.0, gpu_sizes=(1, 2),
                                       gpu_probs=(0.7, 0.3))
                     for e in range(n_envs)[sl]]
    local_traces = stack_traces(local_windows, env_params)
    traces = multihost.global_traces(
        mesh, jax.tree.map(np.asarray, local_traces), n_envs)

    net = make_policy("flat", env_params.n_actions)
    apply_fn = lambda p, o, m: net.apply(p, o, m)
    # distinct streams for the rollout carry and the param init (jsan
    # prng-key-reuse, PR 3 first-run finding: the same PRNGKey(0) fed the
    # carry, the global carry assembly, AND net.init — action sampling
    # and weight draws shared one stream). Every rank computes the same
    # split, so the cross-rank fingerprint contract is untouched.
    carry_key, init_key = jax.random.split(jax.random.PRNGKey(0))
    # carry init needs a local-shape trace: init on the local shard, then
    # assemble the global carry the same way the traces were assembled
    local_carry = init_carry(env_params, local_traces, carry_key)
    carry = dp.RolloutCarry(
        env_state=multihost.global_traces(
            mesh, jax.tree.map(np.asarray, local_carry.env_state), n_envs),
        obs=multihost.global_traces(
            mesh, np.asarray(local_carry.obs), n_envs),
        mask=multihost.global_traces(
            mesh, np.asarray(local_carry.mask), n_envs),
        key=local_carry.key)
    params = net.init(init_key, np.asarray(local_carry.obs[:1]),
                      np.asarray(local_carry.mask[:1]))
    state = TrainState.create(apply_fn=net.apply, params=params,
                              tx=make_optimizer(cfg))
    start = 0
    if args.ckpt_dir and args.resume_step >= 0:
        start = args.resume_step
        src = args.restore_rank if args.restore_rank >= 0 else args.proc_id
        state = _load_rank_ckpt(args.ckpt_dir, src, state, start)
        if bus is not None:
            bus.emit("worker_resumed", step=start, from_rank=src,
                     world=args.num_procs)
        print(f"MULTIHOST_RESUMED proc={args.proc_id} step={start} "
              f"from_rank={src}", flush=True)
    step, state, carry, traces = dp.shard_train(
        mesh, make_ppo_step(apply_fn, env_params, cfg), state, carry, traces)
    for i in range(start, args.steps):
        injector.maybe_exit_rank(args.proc_id, i)
        if hb is not None:
            hb.beat(i)
        # per-rank iteration span (a named ROADMAP residual): every rank
        # records its own step extent, so the merged skew-corrected
        # timeline shows the gang's lockstep (or a straggler's lag)
        with tracer.span("iteration", iteration=i):
            state, carry, metrics = step(state, carry, traces,
                                         jax.random.PRNGKey(i))
            if args.ckpt_dir:
                jax.block_until_ready(state.params)
                with tracer.span("ckpt"):
                    _save_rank_ckpt(args.ckpt_dir, args.proc_id, state,
                                    i + 1)
        if bus is not None:
            bus.emit("worker_step", step=i, completed=i + 1)
            skew_lib.stamp(bus, source="step", step=i)
    jax.block_until_ready(state.params)
    assert all(bool(jnp.isfinite(v)) for v in metrics), metrics
    # replicated-params fingerprint: identical across ranks iff the
    # cross-process gradient psum worked
    fp = float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                   for l in jax.tree.leaves(state.params)))
    if bus is not None:
        bus.emit("worker_done", world=args.num_procs,
                 fingerprint=round(fp, 6))
        bus.close()
    print(f"MULTIHOST_DP_OK proc={args.proc_id} fingerprint={fp:.6f}",
          flush=True)

    if args.no_pbt_check:
        return

    # ---- PBT exploit gather across the process boundary ------------------
    pop_mesh = multihost.global_mesh(n_pop=args.num_procs)
    pop_sh = mesh_lib.pop_sharded(pop_mesh)
    vals = np.arange(args.num_procs * 4, dtype=np.float32) \
        .reshape(args.num_procs, 4)
    # each process contributes ONLY its own member row (the member stack
    # lives pop-sharded across hosts; exploit must move weights between
    # them — the DCN-analog transfer)
    w = jax.make_array_from_process_local_data(
        pop_sh, vals[args.proc_id:args.proc_id + 1], vals.shape)
    src = np.full((args.num_procs,), args.num_procs - 1, np.int64)
    gathered = pbt.gather_members({"w": w}, src)  # all copy the LAST member
    # verify THIS process's shards now hold the last member's row — data
    # that lived on the other process before the gather (for every rank
    # but the last)
    for shard in gathered["w"].addressable_shards:
        rows = np.asarray(shard.data)
        np.testing.assert_array_equal(
            rows, np.tile(vals[-1], (rows.shape[0], 1)))
    print(f"MULTIHOST_PBT_OK proc={args.proc_id} "
          f"gathered_row={vals[-1].tolist()}", flush=True)


if __name__ == "__main__":
    main()
