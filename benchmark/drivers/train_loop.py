"""Driver ``train_loop``: the window is ONE call of ``Experiment.run`` as
``train.py`` makes it by default (``fused_chunk`` 1, log every 10th
iteration, no checkpoint directory, no eval).

Set-up builds ONE ``Experiment``, gives it weights made by the benchmark
from ``--seed``, drives it through its first ``check_steps`` iterations
through the window's own call (``exp.run``), keeps what each left behind,
calibrates the iteration count and hands the same object to the window.
After the window the plain references follow those first iterations
(``check``): the host oracle replays sampled clusters into the state the
timed step itself returned, and the float32 PPO reference follows the
loss and the parameters' change. What set-up keeps for them it keeps on
the HOST, so that the device holds the program's buffers only; and once
the window's memory is read the program's own state waits on the host too
(``park``), so that the references have the chip to themselves: one
follower at a time, its state donated to its update. A traced run puts the
state back for the stages.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark.common import Checks, limits_of, log, note, resolve_config
from benchmark.reference import oracle as oracle_ref
from benchmark.reference import ppo as ppo_ref
from benchmark.reference import weights
from benchmark.reference.forward import QUANT


class TrainCell:
    """Everything one run of a train cell holds."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from rlgpuschedule_tpu.experiment import Experiment

        self.ctx = ctx
        self.jax, self.jnp = jax, jnp
        t = ctx.traffic
        self.cfg = resolve_config(ctx.config, ctx.seed, ctx.rehearse)
        if self.cfg.algo != "ppo":
            raise SystemExit("train_loop: the PPO reference is the only "
                             "learning reference the benchmark has")
        self.exp = exp = Experiment.build(self.cfg)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            exp.train_state.params)
        self.copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        params0 = weights.make_params(shapes, ctx.seed)
        self.params0 = jax.device_get(params0)     # the reference's start
        exp.train_state = exp.train_state.replace(params=params0)
        self.steps: list[dict] = []
        self.log_every = int(t.get("log_every", 10))
        self.n_check = int(t["check_steps"])

    # ---- set-up ------------------------------------------------------

    def drive_first_steps(self) -> None:
        """The first iterations, each through the window's own call, on
        the one object the window will drive; what each consumed and left
        is kept on the host for the references."""
        jax, exp = self.jax, self.exp
        carry = jax.device_get(exp.carry)
        for k in range(self.n_check):
            key = np.asarray(exp.key)
            out = exp.run(iterations=1, log_every=1)
            after = jax.device_get(exp.carry)
            self.steps.append({
                "carry": carry, "key": key, "carry_after": after,
                "loss": out["history"][0]["total_loss"],
                "wall_s": out["wall_s"],
                "params": jax.device_get(exp.train_state.params)})
            carry = after
            note(f"first step {k}: loss {self.steps[-1]['loss']:.6f}")

    def calibrate(self, seconds: float) -> int:
        """Iterations that fill ``seconds``: from the last first step's
        wall time where an iteration is a second or more, else from one
        short call of the loop as the window makes it."""
        exp = self.exp
        n_cal, wall = 1, self.steps[-1]["wall_s"]
        if wall < 1.0:         # short iterations: time a second's worth
            n_cal = math.ceil(1.0 / max(wall, 1e-4))
            wall = exp.run(iterations=n_cal,
                           log_every=self.log_every)["wall_s"]
        per = wall / n_cal
        n = max(1, round(seconds / per))
        if self.cfg.resample_every and n >= self.cfg.resample_every:
            exp.advance_windows()      # its programs, before the window
        log(phase="calibrate", iterations_timed=n_cal, wall_s=wall,
            s_per_iteration=per, window_iterations=n)
        return n

    # ---- the window --------------------------------------------------

    def window(self, iterations: int) -> dict:
        import jax
        with jax.profiler.TraceAnnotation("train_run"):
            out = self.exp.run(iterations=iterations,
                               log_every=self.log_every)
        losses = [h["total_loss"] for h in out["history"]]
        return {"wall_s": out["wall_s"], "iterations": iterations,
                "env_steps": out["env_steps"], "losses": losses}

    # ---- the check ---------------------------------------------------

    PARKED = ("train_state", "carry")    # ``check`` reads neither

    def park(self) -> None:
        """Copy the program's train state and rollout carry to the host
        and free their device buffers, so that the references have the
        chip to themselves; ``unpark`` puts them back where they were."""
        jax, exp = self.jax, self.exp
        self._shardings = {}
        for name in self.PARKED:
            tree = getattr(exp, name)
            self._shardings[name] = jax.tree.map(lambda x: x.sharding, tree)
            setattr(exp, name, jax.device_get(tree))
            for x in jax.tree.leaves(tree):
                x.delete()

    def unpark(self) -> None:
        for name, shardings in self._shardings.items():
            setattr(self.exp, name, self.jax.device_put(
                getattr(self.exp, name), shardings))
        self._shardings = {}

    def program_state_bytes_on_device(self) -> int:
        """Bytes of the parked trees still alive on the device."""
        return sum(x.nbytes for name in self.PARKED
                   for x in self.jax.tree.leaves(getattr(self.exp, name))
                   if isinstance(x, self.jax.Array) and not x.is_deleted())

    def _before_first_update(self, followers) -> dict:
        """What the chip holds besides the follower about to step."""
        return {"program_state_bytes_on_device":
                self.program_state_bytes_on_device(),
                "followers_alive": sum(not f.released for f in followers)}

    def _say_memory(self, role: str, before: dict, follower) -> None:
        """The ``check_memory`` line of one follower, with the process's
        peaks so far: the check's own where they pass the window's (the
        ``memory`` line has those)."""
        stats = self.jax.local_devices()[0].memory_stats() or {}
        log(phase="check_memory", follower=role, **before,
            **follower.memory,
            **{k: stats.get(k) for k in ("peak_bytes_in_use",
                                         "peak_bytes_reserved")})

    def rollout_alone(self):
        """The program's rollout, jitted alone at the cell's shape: reads
        out the trajectory an iteration consumed (it is tied to the timed
        step by the state that step itself returned) and is the
        ``rollout_ms`` stage."""
        if not hasattr(self, "_rollout"):
            from rlgpuschedule_tpu.algos.rollout import make_rollout_step
            self._rollout = self.jax.jit(make_rollout_step(
                self.exp.apply_fn, self.exp.env_params,
                self.cfg.ppo.n_steps))
        return self._rollout

    def hyper(self) -> ppo_ref.Hyper:
        p = self.cfg.ppo
        return ppo_ref.Hyper(
            gamma=p.gamma, gae_lambda=p.gae_lambda, clip_eps=p.clip_eps,
            vf_coef=p.vf_coef, ent_coef=p.ent_coef, lr=p.lr,
            max_grad_norm=p.max_grad_norm, n_epochs=p.n_epochs,
            n_minibatches=p.n_minibatches)

    def check(self, checks: Checks, variant: str = "none") -> dict:
        """Follow the first iterations with the plain references and add
        every number compared to ``checks``. ``variant`` other than
        ``none`` puts the reference in the program's place, computed in
        that lower precision (``forward.QUANT``: the control) or with that
        fault planted (``ppo.FAULTS``): its loss, log-probs, values and
        parameter change are read instead of the program's."""
        jax, jnp, exp, cfg = self.jax, self.jnp, self.exp, self.cfg
        lim = limits_of(self.ctx.traffic, self.ctx.config)
        t = self.ctx.traffic
        block = int(self.ctx.config["reference_block_rows"])
        E, T = cfg.n_envs, cfg.ppo.n_steps
        sim = exp.env_params.sim
        rng = np.random.default_rng(self.ctx.seed)
        sample = sorted(rng.choice(E, size=min(int(t["oracle_envs"]), E),
                                   replace=False).tolist())
        episodes = {}
        for e in sample:
            w = exp.windows[e]      # env e's jobs as the program cut them
            episodes[e] = oracle_ref.Episode(
                oracle_ref.Cluster(w.submit, w.duration, w.gpus, w.valid,
                                   sim.n_nodes, sim.gpus_per_node),
                sim.queue_len, exp.env_params.horizon,
                exp.env_params.reward_scale, exp.env_params.place_bonus)
        build = lambda **kw: ppo_ref.Follower(
            self.ctx.reference, self.hyper(), self.params0, block, **kw)
        follower = build()
        norms = jax.jit(lambda tree: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)])
        delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
        change = lambda params: np.asarray(
            norms(delta(params, self.params0)), np.float64)
        read = {"log_prob_gap": 0.0, "value_gap": 0.0, "sim_state": 0,
                "sim_time_gap": 0.0, "sim_reward_gap": 0.0,
                "untied_envs": 0, "masked_actions": 0}
        refs, gots, kept = [], [], []
        params_before = self.params0
        for k, s in enumerate(self.steps):
            carry2, tr, _ = self.rollout_alone()(
                params_before, s["carry"], exp.traces, exp.faults)
            sub = jax.random.split(jnp.asarray(s["key"]))[1]
            host = jax.device_get({
                "action": tr.action, "reward": tr.reward, "done": tr.done,
                "dt": tr.env_steps_dt, "log_prob": tr.log_prob,
                "value": tr.value,
                "alone": _sim_fields(carry2.env_state),
                "timed": _sim_fields(s["carry_after"].env_state)})
            # tie: the rollout read out alone ends in the state the timed
            # step itself returned, env by env
            untied = _untied(host["alone"], host["timed"])
            read["untied_envs"] += int(untied.sum())
            # every action taken was feasible under its own mask
            taken = jnp.take_along_axis(tr.mask, tr.action[..., None],
                                        axis=-1)
            read["masked_actions"] += int(jnp.sum(~taken))
            # oracle: replay the sampled clusters' actions on the host
            for e in sample:
                ep = episodes[e]
                for step in range(T):
                    o = ep.step(host["action"][step, e])
                    read["sim_state"] += int(
                        bool(o["done"]) != bool(host["done"][step, e]))
                    read["sim_reward_gap"] = max(
                        read["sim_reward_gap"], _rel(
                            o["reward"], host["reward"][step, e]))
                    read["sim_time_gap"] = max(
                        read["sim_time_gap"],
                        _rel(o["dt"], host["dt"][step, e]))
                timed = {f: v[e] for f, v in host["timed"].items()}
                c = ep.c
                read["sim_state"] += int(
                    (timed["status"] != c.status).sum()
                    + (timed["alloc"] != c.alloc).sum()
                    + (timed["free"] != c.free).sum()
                    + int(timed["t"] != ep.t))
                read["sim_time_gap"] = max(
                    read["sim_time_gap"], _rel(c.clock, timed["clock"]),
                    float(np.max(np.abs(c.remaining - timed["remaining"])
                                 / np.maximum(np.abs(c.remaining), 1.0))))
            # the float32 reference follows the learning half
            traj = {"obs": tr.obs, "mask": tr.mask, "action": tr.action,
                    "reward": tr.reward, "done": tr.done,
                    "last_obs": carry2.obs, "last_mask": carry2.mask}
            if k == 0:
                before = self._before_first_update([follower])
            refs.append(dict(follower.step(traj, sub),
                             untied_envs=int(untied.sum()),
                             change=change(follower.params)))
            gots.append({"loss": s["loss"], "log_prob": host["log_prob"],
                         "value": host["value"]})
            if variant != "none":   # the control's turn comes later
                kept.append((jax.device_get(traj), sub))
            params_before = s["params"]
        follower.release()
        self._say_memory("reference", before, follower)
        if variant == "none":
            for got, s in zip(gots, self.steps):
                got["change"] = change(s["params"])
        else:
            # one follower on the chip at a time: the reference has gone
            control = build(quant=QUANT.get(variant), fault=variant
                            if variant in ppo_ref.FAULTS else None)
            before = self._before_first_update([follower, control])
            gots = [dict(control.step(traj, sub),
                         change=change(control.params))
                    for traj, sub in kept]
            control.release()
            self._say_memory("control", before, control)
        for k, (got, ref) in enumerate(zip(gots, refs)):
            lgap = abs(got["loss"] - ref["loss"]) / max(
                abs(ref["loss"]), float(lim["loss_floor"]))
            which = "loss_gap_first" if k == 0 else "loss_gap_later"
            read[which] = max(read.get(which, 0.0), lgap)
            if k == 0:
                # the first iteration's forward runs on the very weights
                # the benchmark made; later ones have drifted apart by the
                # optimizer's amplification of rounding, which the norms
                # below tolerate and a row-by-row difference does not
                read["log_prob_gap"] = float(np.max(
                    np.abs(got["log_prob"] - ref["log_prob"])))
                vscale = max(float(np.std(ref["value"])), 1e-6)
                read["value_gap"] = float(np.sqrt(np.mean(np.square(
                    got["value"] - ref["value"])))) / vscale
            log(phase="check_step", step=k, loss_program=got["loss"],
                loss_reference=ref["loss"], untied_envs=ref["untied_envs"],
                change_program=got["change"].tolist(),
                change_reference=ref["change"].tolist())
        mine, theirs = gots[-1]["change"], refs[-1]["change"]
        # held to: the change over the WHOLE tree, which sees a wrong step
        # size (0.51 and up), a step not taken (1.0) and a wrong update of
        # any leaf that carries weight. Read, not held to: the worst leaf
        # (0.003 to 0.78 over sound seeds) and the median leaf (steadier
        # from seed to seed, blind to a fault in a minority of leaves);
        # PERF.md section 2 has the readings
        gaps = ppo_ref.leaf_gaps(mine, theirs)
        read["param_change_median_leaf_gap"] = float(np.median(gaps))
        read["param_change_norm_gap"] = float(np.max(gaps))
        tree = float(np.sqrt(np.sum(theirs ** 2)))
        read["param_change_tree_gap"] = abs(
            float(np.sqrt(np.sum(mine ** 2))) - tree) / max(tree, 1e-30)
        # exact comparisons have the limit 0
        checks.add("sim_state_mismatches", read["sim_state"], 0,
                   envs=sample, steps=len(self.steps) * T)
        checks.add("masked_actions_taken", read["masked_actions"], 0)
        checks.add("rollout_untied_envs", read["untied_envs"], 0)
        for name in sorted(set(lim) & set(read)):
            checks.add(name, read[name], lim[name])
        return read


def _sim_fields(env_state) -> dict:
    s = env_state.sim
    return {"status": s.status, "alloc": s.alloc, "free": s.free,
            "clock": s.clock, "remaining": s.remaining, "t": env_state.t}


def _untied(a: dict, b: dict) -> np.ndarray:
    """bool[E]: envs whose two end states differ in any field."""
    bad = np.zeros(a["t"].shape[0], bool)
    for f in a:
        diff = a[f] != b[f]
        bad |= diff.reshape(diff.shape[0], -1).any(axis=1)
    return bad


def _rel(ref, got) -> float:
    ref, got = float(ref), float(got)
    return abs(ref - got) / max(abs(ref), 1.0)


def control(ctx, variants) -> dict:
    """Readings of the program (``none``) and of the reference put in its
    place in each lower precision or with each fault planted, on one build
    (no window)."""
    cell = TrainCell(ctx)
    cell.drive_first_steps()
    cell.park()
    return {v: cell.check(Checks(), v) for v in variants}


def run(ctx) -> dict:
    cell = TrainCell(ctx)
    cell.drive_first_steps()
    n_iter = cell.calibrate(ctx.trace_seconds if ctx.trace else ctx.seconds)
    ctx.window_opens()
    with ctx.profile():
        win = cell.window(n_iter)
    ctx.window_closes()
    nonfinite = sum(not math.isfinite(x) for x in win["losses"])
    checks = Checks()
    checks.add("compiles_in_window", ctx.window_compiles, 0)
    checks.add("nonfinite_losses", nonfinite, 0)
    t0 = time.monotonic()
    cell.park()         # after the memory was read: the peak stays the window's
    cell.check(checks)
    log(phase="check_time", seconds=time.monotonic() - t0)
    if ctx.trace:
        cell.unpark()   # the stages run on the program's own state
    chips = ctx.cell["chips"]
    return {
        "attempted": win["iterations"], "failed": nonfinite,
        "checks": checks,
        "end_to_end": {"env_steps_per_s":
                       win["env_steps"] / win["wall_s"] / chips},
        "window_s": win["wall_s"],
        "probe": stages(cell) if ctx.trace else {},
    }


def stages(cell: TrainCell) -> dict:
    """What the per-layer readers read in a traced run: the program's
    rollout, advantage and update, each jitted alone at the cell's shape
    (``profile_breakdown``'s pattern), a resample, and the update's FLOPs
    from shapes (counted by the configuration's own reference).

    ONE copy of the train state: the update takes the program's own,
    donated and threaded (the box and ``exp`` always hold the live one),
    and ``rollout`` and ``advantage`` read the state the box holds. The
    stages are listed in the order they are to be timed in
    (``readers/stage_time`` times a stage's predecessors before it):
    ``rollout`` and ``advantage`` on the state the window left, before the
    first ``update`` moves it; ``resample`` last, since it re-cuts the
    windows (the rollout keeps the window's own carry and traces)."""
    import jax
    from rlgpuschedule_tpu.algos.ppo import (compute_advantages,
                                              run_ppo_epochs)
    from rlgpuschedule_tpu.algos.update import make_update_step

    exp, cfg = cell.exp, cell.cfg
    box = {"state": exp.train_state}
    carry, traces = cell.copy(exp.carry), exp.traces
    rollout = cell.rollout_alone()
    _, tr, last_value = jax.block_until_ready(
        rollout(box["state"].params, carry, traces, exp.faults))
    adv_jit = jax.jit(lambda state, tr, lv: compute_advantages(
        exp.apply_fn, cfg.ppo, state, tr, lv)[1:3])
    adv, ret = jax.block_until_ready(adv_jit(box["state"], tr, last_value))
    upd = make_update_step(lambda state, tr, adv, ret, key: run_ppo_epochs(
        exp.apply_fn, cfg.ppo, state, tr, adv, ret, key,
        lambda s, g: s.apply_gradients(grads=g)))
    key = jax.random.PRNGKey(0)

    def update():
        box["state"], m = upd(box["state"], tr, adv, ret, key)
        exp.train_state = box["state"]      # the donated one is dead
        jax.block_until_ready(m)

    rows = cfg.ppo.n_steps * cfg.n_envs
    fwd = cell.ctx.reference.forward_flops_per_row(box["state"].params)
    return {
        "stages": {
            "rollout": lambda: jax.block_until_ready(
                rollout(box["state"].params, carry, traces, exp.faults)),
            "advantage": lambda: jax.block_until_ready(
                adv_jit(box["state"], tr, last_value)),
            "update": update,
            "resample": lambda: (exp.advance_windows(),
                                 jax.block_until_ready(exp.carry)),
        },
        # forward + backward (2x forward) over every row, every epoch
        "flops": {"update": 3.0 * fwd * rows * cfg.ppo.n_epochs},
    }
