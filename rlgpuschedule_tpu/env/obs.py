"""Observation builders (L2): flat / occupancy-grid / topology-graph.

Capability parity: SURVEY.md §2 "Observation builders" — node×GPU occupancy
grid (image-like, CNN config 2), flat features (MLP config 1), topology graph
+ node features (GNN config 4). All are fixed-shape pure functions of
(SimState, Trace) so they live inside the jitted rollout.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..sim.core import (SimParams, SimState, Trace, pending_queue,
                        queue_rows, running_queue, PENDING, RUNNING,
                        in_system, utilization)
from ..sim.faults import FaultSchedule, node_up


def node_health(params: SimParams, state: SimState,
                faults: FaultSchedule | None = None) -> jax.Array:
    """Per-node effective-speed feature [N]: 1.0 = healthy full speed,
    ``1/slowdown`` = straggling, 0.0 = drained at the current clock — the
    single channel a policy needs to route around sick nodes. With
    ``faults=None`` (clean replay of a fault-trained policy) every node
    reads healthy."""
    if faults is None:
        return jnp.ones((params.n_nodes,), jnp.float32)
    up = node_up(faults, state.clock)
    return jnp.where(up, 1.0 / faults.slowdown, 0.0).astype(jnp.float32)


def node_geometry(params: SimParams, faults=None) -> jax.Array:
    """Per-node capacity feature [N]: usable GPUs / gpus_per_node — the
    geometry channel a domain-randomized policy needs to tell a shrunken
    (or absent) node from a merely busy one. Reads the ``capacity``
    carried by a ``domains.DomainSchedule`` in the faults slot; a plain
    FaultSchedule or ``faults=None`` (clean replay) reads as a full
    homogeneous cluster."""
    cap = getattr(faults, "capacity", None)
    if cap is None:
        return jnp.ones((params.n_nodes,), jnp.float32)
    return jnp.asarray(cap, jnp.float32) / params.gpus_per_node


def queue_features(params: SimParams, state: SimState, trace: Trace,
                   queue: jax.Array | None = None) -> jax.Array:
    """Per-queue-slot features [K, 4]: demand/capacity, waiting time,
    service demand (both in units of ``time_scale`` via the caller), valid.
    Pass a precomputed ``pending_queue`` to share it with the action mask
    (the env step computes it once — VERDICT r1 weak #2). The trace fields
    are read at the queue's rows through ``core.queue_rows`` (dense)."""
    if queue is None:
        queue = pending_queue(params, state)               # [K]
    occupied = queue >= 0
    valid = occupied.astype(jnp.float32)
    demand = (queue_rows(trace.gpus, queue).astype(jnp.float32)
              / params.capacity * valid)
    wait = jnp.where(occupied,
                     state.clock - queue_rows(trace.submit, queue), 0.0)
    service = jnp.where(occupied, queue_rows(trace.duration, queue), 0.0)
    return jnp.stack([demand, wait, service, valid], axis=1)


def run_features(params: SimParams, state: SimState, trace: Trace,
                 time_scale: float, run_queue: jax.Array | None = None,
                 ) -> jax.Array:
    """Per-preempt-slot features [R, 4] over :func:`running_queue` (most
    attained GPU-service first): demand/capacity, executed seconds,
    remaining seconds (both tanh-squashed by ``time_scale``), valid — what
    the agent needs to judge a demotion."""
    if run_queue is None:
        run_queue = running_queue(params, state, trace)     # [R]
    jc = jnp.clip(run_queue, 0, params.max_jobs - 1)
    occupied = run_queue >= 0
    valid = occupied.astype(jnp.float32)
    demand = trace.gpus[jc].astype(jnp.float32) / params.capacity * valid
    executed = jnp.where(occupied,
                         trace.duration[jc] - state.remaining[jc], 0.0)
    remaining = jnp.where(occupied, state.remaining[jc], 0.0)
    return jnp.stack([demand, jnp.tanh(executed / time_scale),
                      jnp.tanh(remaining / time_scale), valid], axis=1)


def flat_obs(params: SimParams, state: SimState, trace: Trace,
             time_scale: float, queue: jax.Array | None = None,
             run_queue: jax.Array | None = None) -> jax.Array:
    """[N + 4K + 4R + 2] vector: per-node free fraction, queue features,
    running-job features (preemptive configs, R = preempt_len),
    utilization, normalized in-system count."""
    free_frac = state.free.astype(jnp.float32) / params.gpus_per_node
    qf = queue_features(params, state, trace, queue)
    qf = qf.at[:, 1].set(jnp.tanh(qf[:, 1] / time_scale))
    qf = qf.at[:, 2].set(jnp.tanh(qf[:, 2] / time_scale))
    util = utilization(params, state)
    n_insys = in_system(state) / params.max_jobs
    parts = [free_frac, qf.reshape(-1)]
    if params.preempt_len:
        parts.append(run_features(params, state, trace, time_scale,
                                  run_queue).reshape(-1))
    parts.append(jnp.stack([util, n_insys]))
    return jnp.concatenate(parts).astype(jnp.float32)


def grid_obs(params: SimParams, state: SimState, trace: Trace,
             time_scale: float, queue: jax.Array | None = None,
             run_queue: jax.Array | None = None) -> jax.Array:
    """Occupancy image [N + K (+ R), G, 2] (the reference's CNN input shape
    class — cluster occupancy stacked over queue-demand rows, SURVEY.md §2):

    cluster rows n<N:  ch0 = GPU slot occupied; ch1 = PER-SLOT normalized
                       remaining service: each job's remaining painted on
                       the slots it holds, slots sorted longest-remaining
                       first within a node (a canonical waterfall — GPU
                       slots are fungible, so sorting removes a spurious
                       permutation symmetry). VERDICT r4 weak #5: the
                       earlier node-AVERAGE hid per-job boundaries within
                       a node; the waterfall strictly generalizes it (mean-
                       pooling ch1 recovers the average) while exposing
                       how many distinct jobs a node hosts and how skewed
                       their remaining work is — what drain-regime packing
                       decisions actually need.
    queue rows:        ch0 = demand bar (capped at G); ch1 = normalized
                       service demand painted on the bar.
    preempt rows (preemptive configs): ch0 = demand bar of running-queue
                       slots; ch1 = normalized remaining service on the bar.

    The waterfall is built densely, in G rounds over the ``[J, N]``
    allocation table (a job holds at least one GPU on a node it is on, so
    a node hosts at most G jobs and G rounds reach them all). Round r
    finds on every node the largest remaining-value below round r-1's (a
    masked max over J), adds up the GPUs of the jobs that hold exactly
    that value (a masked sum over J) and paints the value on that many
    slots after those already painted. Two reductions over ``[J, N]`` a
    round: O(G·J·N) compares and selects, linear in J, nothing kept
    beyond one ``[J, N]`` table, and no sort, search or data-dependent
    gather, which a TPU runs three orders of magnitude slower than dense
    compare-and-reduce at this size (PERF.md §6, PR 28). Jobs with EQUAL
    values on a node are taken in the same round and paint the same value
    on the sum of their GPUs, so the image cannot depend on how ties
    would be ordered.

    The queue rows read ``trace.gpus`` and ``trace.duration`` at the
    queue's K rows through ``core.queue_rows``: the same trade, a
    ``[K, J]`` compare and masked sum in place of two gathers of K single
    elements (~10 ns an element on the chip: 107 ms an iteration of the
    CNN cell before PR 34).
    """
    N, G, K = params.n_nodes, params.gpus_per_node, params.queue_len
    used = (params.gpus_per_node - state.free).astype(jnp.float32)    # [N]
    slots = jnp.arange(G, dtype=jnp.float32)                          # [G]
    occ = (slots[None, :] < used[:, None]).astype(jnp.float32)        # [N,G]
    running = (state.status == RUNNING).astype(jnp.float32)
    val = running * jnp.tanh(state.remaining / time_scale)            # [J]
    alloc = state.alloc                                               # [J,N]
    # -inf where a job holds nothing on the node: below every value, and
    # equal to a round's max only once a node's jobs are all painted
    key = jnp.where(alloc > 0, val[:, None], -jnp.inf)                # [J,N]
    islots = jnp.arange(G, dtype=jnp.int32)[None, :]                  # [1,G]
    rem_img = jnp.zeros((N, G), jnp.float32)
    painted = jnp.zeros((N,), jnp.int32)
    top = jnp.full((N,), jnp.inf, jnp.float32)
    for _ in range(G):
        top = jnp.max(jnp.where(key < top[None, :], key, -jnp.inf), axis=0)
        upto = painted + jnp.sum(
            jnp.where(key == top[None, :], alloc, 0), axis=0)         # [N]
        turn = (islots >= painted[:, None]) & (islots < upto[:, None])
        rem_img = jnp.where(turn, top[:, None], rem_img)              # [N,G]
        painted = upto
    cluster = jnp.stack([occ, occ * rem_img], axis=-1)                # [N,G,2]

    if queue is None:
        queue = pending_queue(params, state)
    valid = (queue >= 0).astype(jnp.float32)
    demand = (jnp.minimum(queue_rows(trace.gpus, queue), G)
              .astype(jnp.float32) * valid)
    bar = (slots[None, :] < demand[:, None]).astype(jnp.float32)      # [K,G]
    service = jnp.tanh(queue_rows(trace.duration, queue) / time_scale) * valid
    qimg = jnp.stack([bar, bar * service[:, None]], axis=-1)          # [K,G,2]
    parts = [cluster, qimg]
    if params.preempt_len:
        if run_queue is None:
            run_queue = running_queue(params, state, trace)
        rc = jnp.clip(run_queue, 0, params.max_jobs - 1)
        rvalid = (run_queue >= 0).astype(jnp.float32)
        rdemand = jnp.minimum(trace.gpus[rc], G).astype(jnp.float32) * rvalid
        rbar = (slots[None, :] < rdemand[:, None]).astype(jnp.float32)
        rrem = jnp.tanh(state.remaining[rc] / time_scale) * rvalid
        parts.append(jnp.stack([rbar, rbar * rrem[:, None]], axis=-1))
    return jnp.concatenate(parts, axis=0)                     # [N+K+R,G,2]


def build_adjacency(n_nodes: int, queue_len: int,
                    nodes_per_rack: int | None = None,
                    preempt_len: int = 0) -> np.ndarray:
    """Static topology adjacency [V, V], V = N + K + R: cluster nodes
    connected within a rack (all-to-all if ``nodes_per_rack`` is None),
    every queue slot and every running (preempt) slot connected to every
    cluster node (placement / eviction candidates), self-loops. Static
    because cluster topology never changes — only features do."""
    V = n_nodes + queue_len + preempt_len
    a = np.zeros((V, V), np.float32)
    if nodes_per_rack is None:
        a[:n_nodes, :n_nodes] = 1.0
    else:
        for r0 in range(0, n_nodes, nodes_per_rack):
            r1 = min(r0 + nodes_per_rack, n_nodes)
            a[r0:r1, r0:r1] = 1.0
    a[:n_nodes, n_nodes:] = 1.0   # node ↔ {queue, running} bipartite
    a[n_nodes:, :n_nodes] = 1.0
    np.fill_diagonal(a, 1.0)
    return a


GRAPH_FEATURES = 5


def graph_obs(params: SimParams, state: SimState, trace: Trace,
              time_scale: float, queue: jax.Array | None = None,
              run_queue: jax.Array | None = None) -> jax.Array:
    """Node-feature matrix [N + K (+ R), 5] over the static topology graph:
    cluster rows: [free_frac, used_frac, avg_remaining, 1, 0];
    queue rows:   [demand/capacity, wait, service, 0, 1] (times tanh-squashed);
    preempt rows: [demand/capacity, executed, remaining, 0, 0] (type flags
    both 0 distinguish running slots from cluster/queue rows).
    The adjacency comes from :func:`build_adjacency` (static)."""
    N, G = params.n_nodes, params.gpus_per_node
    free_frac = state.free.astype(jnp.float32) / G
    used = (G - state.free).astype(jnp.float32)
    running = (state.status == RUNNING).astype(jnp.float32)
    rem_n = jnp.einsum("jn,j->n", state.alloc.astype(jnp.float32),
                       running * jnp.tanh(state.remaining / time_scale))
    rem_avg = rem_n / jnp.maximum(used, 1.0)
    ones = jnp.ones((N,), jnp.float32)
    cluster = jnp.stack([free_frac, 1.0 - free_frac, rem_avg,
                         ones, 0.0 * ones], axis=1)            # [N,5]
    qf = queue_features(params, state, trace, queue)           # [K,4]
    wait = jnp.tanh(qf[:, 1] / time_scale)
    service = jnp.tanh(qf[:, 2] / time_scale)
    zeros = jnp.zeros((params.queue_len,), jnp.float32)
    queue = jnp.stack([qf[:, 0], wait, service, zeros, qf[:, 3]], axis=1)
    parts = [cluster, queue]
    if params.preempt_len:
        rf = run_features(params, state, trace, time_scale, run_queue)
        rzeros = jnp.zeros((params.preempt_len,), jnp.float32)
        parts.append(jnp.stack([rf[:, 0], rf[:, 1], rf[:, 2],
                                rzeros, rzeros], axis=1))
    return jnp.concatenate(parts, axis=0)                      # [N+K+R,5]


TOKEN_FEATURES = 11


def token_obs(params: SimParams, state: SimState, trace: Trace,
              time_scale: float, queue: jax.Array | None = None,
              run_queue: jax.Array | None = None,
              faults: FaultSchedule | None = None) -> jax.Array:
    """One token per cluster node and per job of the window,
    ``[N + J, 11]``: the N node rows first, then the J job rows in
    job-index order (the window's submit order), so a causal trunk reads
    the cluster before the backlog and older jobs before newer ones.

    node rows: [free fraction, used fraction, mean remaining of what runs
               there, health, geometry, 0, 0, 0, 1, 0, 1]
    job rows:  [demand / capacity, waited, service, remaining, pending,
               running, in the K-slot queue view, its slot / K, 0, 1,
               valid]

    Times are tanh-squashed by ``time_scale``. A job is ``valid`` while
    it is in the system (pending or running): one that has not arrived
    yet is not the scheduler's to know, one that is done is nobody's, and
    both rows are all zeros. The last column is what the trunk masks keys
    and pools by. The action space is the queue view's (K slots and a
    no-op), so each job row says whether it is in the view and where.

    Job tokens built this way are nearly collinear (the flags they share
    outweigh what tells them apart), so at seeded weights every token of
    a row picks the same few experts: PERF.md section 6, PR 30, has what
    that does to a chip's share of the load, and what a position code
    among the features did about it (tried, measured, not kept).

    Dense arithmetic over ``[J]``, ``[J, N]`` and ``[J, K]`` only: the
    slot of a job is a compare of the job index against the K queue
    entries and a masked sum, not a scatter (PERF.md section 6, PR 28);
    the queue itself is the one ``pending_queue`` the env step already
    builds for the mask."""
    N, G, K, J = (params.n_nodes, params.gpus_per_node, params.queue_len,
                  params.max_jobs)
    squash = lambda t: jnp.tanh(t / time_scale)
    free_frac = state.free.astype(jnp.float32) / G
    used = (G - state.free).astype(jnp.float32)
    running = state.status == RUNNING
    pending = state.status == PENDING
    rem_n = jnp.einsum("jn,j->n", state.alloc.astype(jnp.float32),
                       running * squash(state.remaining))
    zeros, ones = jnp.zeros((N,), jnp.float32), jnp.ones((N,), jnp.float32)
    nodes = jnp.stack([free_frac, 1.0 - free_frac,
                       rem_n / jnp.maximum(used, 1.0),
                       node_health(params, state, faults),
                       node_geometry(params, faults),
                       zeros, zeros, zeros, ones, zeros, ones], axis=1)

    if queue is None:
        queue = pending_queue(params, state)
    at = queue[None, :] == jnp.arange(J, dtype=queue.dtype)[:, None]  # [J,K]
    in_view = jnp.any(at, axis=1)
    slot = jnp.sum(at * jnp.arange(K, dtype=jnp.float32)[None, :], axis=1)
    valid = pending | running
    f = lambda x: jnp.where(valid, x, 0.0).astype(jnp.float32)
    # where, not a product: a padding row's submit is +inf
    jobs = jnp.stack([
        f(trace.gpus.astype(jnp.float32) / params.capacity),
        f(squash(jnp.where(valid, state.clock - trace.submit, 0.0))),
        f(squash(trace.duration)), f(squash(state.remaining)),
        f(pending), f(running), f(in_view), f(slot / K),
        jnp.zeros((J,), jnp.float32), f(1.0), f(1.0)], axis=1)
    return jnp.concatenate([nodes, jobs], axis=0)               # [N+J,11]
