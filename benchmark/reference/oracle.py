"""Host oracle of the cluster simulator's decision step: numpy, Python
control flow, one cluster at a time. A copy of the semantics of the
repo's ``sim/oracle.py`` ``OracleSim.rl_step`` path (no faults, no
preemption: neither configuration uses them), kept here so that later PRs
cannot move the yardstick, and importing nothing of the program.

Semantics (the configuration's stated guarantees):

- ``n_nodes`` x ``gpus_per_node`` GPUs; gang all-or-nothing; PACK placement
  fills the freest node first, ties to the lowest node id.
- NOT_ARRIVED -> PENDING (clock >= submit) -> RUNNING -> DONE.
- Time moves only on a no-op / infeasible action, to the next event
  min(next arrival, next completion); completions before arrivals at one
  instant; with no future event the queue head is force-placed.
- The pending queue is ordered by (submit, row); action ``k < K`` places
  slot k; anything else is a no-op.

Where this copy departs from the repo's float64 oracle: the system keeps
time in float32 and states its completion rule in float32 ("a job running
at the advance completes when clock + remaining <= t + 1e-5 + 4 ulp(t)").
That rule is part of the semantics, not an accident of rounding - two jobs
whose completions are closer than float32 resolves DO complete together -
so the oracle keeps float32 times and applies the same rule. States
(status, allocation, free vector, flags) must then agree exactly, and
times to float32 rounding.
"""
from __future__ import annotations

import numpy as np

NOT_ARRIVED, PENDING, RUNNING, DONE = 0, 1, 2, 3
F = np.float32
_EPS = F(1e-5)


def pack_placement(free: np.ndarray, demand: int):
    if demand > int(free.sum()):
        return None
    order = np.lexsort((np.arange(len(free)), -free))   # free desc, id asc
    alloc = np.zeros_like(free)
    left = demand
    for n in order:
        take = min(int(free[n]), left)
        alloc[n] = take
        left -= take
        if left == 0:
            break
    return alloc


class Cluster:
    """One cluster replaying one window of jobs."""

    def __init__(self, submit, duration, gpus, valid, n_nodes: int,
                 gpus_per_node: int):
        self.submit = np.asarray(submit, F)
        self.duration = np.asarray(duration, F)
        self.gpus = np.asarray(gpus, np.int64)
        self.valid = np.asarray(valid, bool)
        self.n_nodes, self.gpus_per_node = n_nodes, gpus_per_node
        self.reset()

    def reset(self):
        J = len(self.submit)
        self.clock = F(0.0)
        self.status = np.where(self.valid, NOT_ARRIVED, DONE).astype(np.int32)
        self.remaining = self.duration.copy()
        self.started = np.zeros(J, bool)
        self.alloc = np.zeros((J, self.n_nodes), np.int32)
        self.free = np.full(self.n_nodes, self.gpus_per_node, np.int32)
        self._arrivals()

    def _arrivals(self):
        self.status[(self.status == NOT_ARRIVED)
                    & (self.submit <= self.clock)] = PENDING

    def next_event_time(self) -> np.float32:
        t = F(np.inf)
        na = self.status == NOT_ARRIVED
        if na.any():
            t = min(t, self.submit[na].min())
        run = self.status == RUNNING
        if run.any():
            t = min(t, (self.clock + self.remaining[run]).min())
        return F(t)

    def advance_to(self, t: np.float32) -> np.float32:
        dt = F(t - self.clock)
        run = self.status == RUNNING
        eta = self.clock + self.remaining
        tol = F(_EPS + F(4.0) * np.spacing(t))
        completed = run & (eta <= F(t + tol))
        self.remaining[run] = np.maximum(self.remaining[run] - dt, F(0.0))
        self.clock = F(t)
        for j in np.flatnonzero(completed):
            self.status[j] = DONE
            self.remaining[j] = F(0.0)
            self.free += self.alloc[j]
            self.alloc[j] = 0
        self._arrivals()
        return dt

    def pending_jobs(self) -> list:
        pend = np.flatnonzero(self.status == PENDING)
        return sorted(pend, key=lambda j: (self.submit[j], j))

    def try_place(self, j: int) -> bool:
        if self.status[j] != PENDING:
            return False
        place = pack_placement(self.free, int(self.gpus[j]))
        if place is None:
            return False
        self.alloc[j] = place
        self.free -= place
        self.status[j] = RUNNING
        self.started[j] = True
        return True

    def in_system(self) -> int:
        return int(((self.status == PENDING)
                    | (self.status == RUNNING)).sum())

    def all_done(self) -> bool:
        return bool((self.status[self.valid] == DONE).all())

    def rl_step(self, action: int, queue_len: int) -> dict:
        queue = self.pending_jobs()[:queue_len]
        placed = first_placed = False
        if action < queue_len and action < len(queue):
            first = not self.started[queue[action]]
            placed = self.try_place(queue[action])
            first_placed = placed and first
        dt, n_before = F(0.0), self.in_system()
        if not placed:
            t = self.next_event_time()
            if np.isfinite(t):
                dt = self.advance_to(t)
            elif queue:
                first = not self.started[queue[0]]
                placed = self.try_place(queue[0])
                first_placed = placed and first
        return {"placed": placed, "dt": dt, "in_system_before": n_before,
                "done": self.all_done(), "first_placed": first_placed}


class Episode:
    """The environment around :class:`Cluster`: the JCT reward
    ``-dt * n_in_system / reward_scale + place_bonus * first_placed``, the
    episode end (all jobs done, or ``horizon`` decisions) and the
    auto-reset onto the same window that follows it."""

    def __init__(self, cluster: Cluster, queue_len: int, horizon: int,
                 reward_scale: float, place_bonus: float):
        self.c, self.queue_len, self.horizon = cluster, queue_len, horizon
        self.reward_scale, self.place_bonus = F(reward_scale), F(place_bonus)
        self.t = 0

    def step(self, action: int) -> dict:
        info = self.c.rl_step(int(action), self.queue_len)
        reward = F(-(info["dt"] * F(info["in_system_before"]))
                   / self.reward_scale)
        if self.place_bonus:
            reward = F(reward + self.place_bonus * F(info["first_placed"]))
        self.t += 1
        done = info["done"] or self.t >= self.horizon
        if done:
            self.c.reset()
            self.t = 0
        return {"reward": reward, "done": done, "dt": info["dt"]}
