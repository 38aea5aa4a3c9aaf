"""Continuous-batching front end: queue -> coalesce -> pad -> scatter.

The TF-Agents batched-environment insight (PAPERS.md: arXiv 1709.02878)
applied to serving: many independent decision streams become ONE
dispatch when their observations are stacked along a batch axis. The
front end's whole job is managing that axis on the host side:

- **coalesce**: pending requests are drained FIFO and rounded up to the
  next power-of-two *bucket* (``next_bucket``), so the jitted policy
  step compiles once per bucket instead of once per request count;
- **pad**: the tail of the bucket is filled with neutral rows (zero
  observations, all-actions-legal masks — a padded row must never
  produce ``-inf``-everywhere logits or NaNs, its action is discarded
  anyway);
- **scatter**: the batched action array is split back to the submitting
  requests in FIFO order.

The hot path is the **arena data plane** (ISSUE 17): requests land
directly in preallocated bucket-sized slabs (one memcpy from the wire
bytes into the slot row — ``submit`` IS the stack), ``pump`` seals a
slab in place (tail rows neutralized by slice assignment, no
``np.concatenate``) and dispatches a contiguous view, and ``scatter``
hands back views into the single device-fetched actions buffer. Steady
state allocates ZERO new host ndarrays per batch (asserted by test;
``serve_arena_allocs_total`` counts slab allocations and must stay flat
after warmup). The handoff is **lock-light**: producers take one tiny
O(1) critical section to reserve a sequence-numbered slot (CPython's
GIL rules out a true CAS loop, so "lock-free reservation" is not
expressible — the honest version is a lock held for a handful of
bytecodes, never across a copy or a dispatch), the row memcpy and the
publish flag happen outside any lock, and the consumer side never holds
the producers' lock during its O(batch) accounting work.

``stack_requests``/``pad_batch`` are the public stacking and padding
utilities for callers off the hot path (router probes, engine warmup).

Everything operates on HOST pytrees (numpy leaves, leading request
axis); device placement is the engine's job, so the queue never holds
device buffers hostage.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import random
import threading
import time
from concurrent.futures import Future
from typing import Any

import numpy as np

from ..obs.trace import NULL_TRACER


class Reservoir:
    """Bounded uniform sample of an unbounded stream (Vitter's
    Algorithm R): the first ``capacity`` observations are kept verbatim,
    after which each new observation replaces a random kept one with
    probability ``capacity / count``. Memory stays flat forever while
    every observation ever made has EQUAL probability of being in the
    sample — unlike a ``deque(maxlen=)`` ring, whose percentiles only
    describe the last ``capacity`` observations of a long soak run.
    Seeded so two servers replaying one workload keep identical samples.

    Sequence protocol (``len``/indexing/iteration) so ``np.asarray``
    and ``np.percentile`` consume it directly; ``count`` is the total
    number of observations ever offered.
    """

    def __init__(self, capacity: int, seed: int = 0):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self.count = 0
        self._rng = random.Random(seed)
        self._samples: list[float] = []

    def append(self, v: float) -> None:
        self.count += 1
        if len(self._samples) < self.capacity:
            self._samples.append(v)
            return
        j = self._rng.randrange(self.count)
        if j < self.capacity:
            self._samples[j] = v

    def __len__(self) -> int:
        return len(self._samples)

    def __getitem__(self, i):
        return self._samples[i]

    def __iter__(self):
        return iter(self._samples)


def next_bucket(n: int, max_bucket: int) -> int:
    """The power-of-two batch bucket for ``n`` requests (smallest power
    of two >= n, capped by ``max_bucket``). Compiling one executable per
    bucket bounds the jit cache at log2(max_bucket)+1 entries while
    wasting at most half a batch of padding."""
    if n <= 0:
        raise ValueError(f"need at least one request, got {n}")
    if max_bucket <= 0 or (max_bucket & (max_bucket - 1)):
        raise ValueError(f"max_bucket must be a positive power of two, "
                         f"got {max_bucket}")
    if n > max_bucket:
        raise ValueError(f"{n} requests exceed max_bucket={max_bucket}; "
                         f"drain in max_bucket-sized dispatches")
    return 1 << (n - 1).bit_length()


def stack_requests(rows: "list[Any]") -> Any:
    """Stack per-request pytrees (no leading axis) into one batched host
    pytree (leading axis = len(rows), FIFO order preserved). Probe
    utility: the server never stacks — rows are written into the slab
    at submit time."""
    import jax

    def stack(*xs):
        return np.stack([np.asarray(x) for x in xs])

    return jax.tree.map(stack, *rows)


# Padding fill constants, hoisted out of the per-call path (ISSUE 17
# satellite): keyed by (pad rows, row tail shape, dtype, mask fill), so
# the bool-mask-pads-True / everything-else-pads-zero branch and the
# constant construction happen ONCE per bucket shape instead of per
# call, and the fill dtype is the leaf dtype by construction — padding
# can never promote (pinned by a dtype-stability test). The cache is
# bounded by the number of distinct (bucket, leaf) shapes a process
# serves — a handful.
_PAD_FILL_CACHE: "dict[tuple, np.ndarray]" = {}


def _pad_fill(rows: int, tail: tuple, dtype: np.dtype,
              mask_true: bool) -> np.ndarray:
    key = (rows, tail, dtype, bool(mask_true))
    fill = _PAD_FILL_CACHE.get(key)
    if fill is None:
        value = True if (mask_true and dtype == np.bool_) else 0
        fill = np.full((rows,) + tail, value, dtype)
        fill.setflags(write=False)      # shared across batches: immutable
        _PAD_FILL_CACHE[key] = fill
    return fill


def pad_batch(batch: Any, bucket: int, fill_mask_true: bool = False) -> Any:
    """Pad a batched host pytree from n rows up to ``bucket`` rows.

    Padding rows are zeros, EXCEPT boolean leaves when
    ``fill_mask_true``: action masks pad with every action legal, so the
    padded rows' logits stay finite under the ``-inf`` masking scheme
    (an all-masked row is the degenerate case the models never see in
    training). A full bucket (n == bucket) returns the input unchanged —
    the server relies on this no-op to dispatch slab views without a
    copy."""
    import jax

    def pad(x):
        x = np.asarray(x)
        n = x.shape[0]
        if n > bucket:
            raise ValueError(f"batch of {n} rows exceeds bucket {bucket}")
        if n == bucket:
            return x
        fill = _pad_fill(bucket - n, x.shape[1:], x.dtype, fill_mask_true)
        return np.concatenate([x, fill])

    return jax.tree.map(pad, batch)


def scatter_results(actions: Any, n: int) -> "list[Any]":
    """Split a batched action pytree back into ``n`` per-request pytrees
    in submission order, dropping the padding tail."""
    import jax
    return [jax.tree.map(lambda x: np.asarray(x)[i], actions)
            for i in range(n)]


@dataclasses.dataclass
class ServeResult:
    """What a request's future resolves to."""
    action: Any            # per-request action pytree (numpy)
    latency_s: float       # submit -> result, queue wait included
    req_id: int = 0        # request-causality id (ISSUE 20); 0 = unassigned


class DeadlineSheddedError(RuntimeError):
    """Typed rejection a shed request's future resolves with.

    Shedding is NEVER a silent drop: the future completes exceptionally
    with this error, carrying why (``reason``: ``"admission"`` — the
    predicted wait at submit already exceeded the deadline — or
    ``"expired"`` — the deadline passed while queued) and the numbers
    behind the verdict, so a client can retry elsewhere, relax its
    deadline, or back off — the load-shedding contract from the lost-
    computation accounting school: reject loudly at the door rather
    than time out quietly inside."""

    def __init__(self, reason: str, deadline_s: float, waited_s: float,
                 predicted_wait_s: "float | None" = None, req_id: int = 0):
        self.reason = reason
        self.deadline_s = float(deadline_s)
        self.waited_s = float(waited_s)
        self.predicted_wait_s = predicted_wait_s
        self.req_id = int(req_id)   # causality id, echoed on shed replies
        pred = (f", predicted wait {predicted_wait_s * 1e3:.1f}ms"
                if predicted_wait_s is not None else "")
        super().__init__(
            f"request shed ({reason}): deadline {deadline_s * 1e3:.1f}ms"
            f", waited {waited_s * 1e3:.1f}ms{pred}")


class ServerClosedError(RuntimeError):
    """Typed refusal for submits against a stopped or closed server.

    Raised by :meth:`PolicyServer.submit` while a :meth:`PolicyServer.stop`
    drain is in flight and forever after :meth:`PolicyServer.close` — the
    drain half of the no-silent-drop contract: a client racing a shutdown
    gets a typed, catchable refusal at the door instead of a future that
    no dispatcher will ever resolve. Distinguishable from
    :class:`DeadlineSheddedError` (overload, retry later with backoff)
    and from a bare ``RuntimeError`` (a bug): closed means *this server
    is going away — re-resolve and connect elsewhere*."""


class Ewma:
    """Streaming exponentially-weighted mean — the arrival-rate /
    service-time estimator behind adaptive batching. O(1) memory, no
    sample window to size; ``alpha`` is the forgetting factor (higher =
    faster tracking, noisier). ``value`` is ``None`` until the first
    observation — callers must not act on an unlearned estimate."""

    def __init__(self, alpha: float = 0.2):
        if not (0.0 < alpha <= 1.0):
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = float(alpha)
        self.value: "float | None" = None
        self.count = 0

    def update(self, x: float) -> float:
        x = float(x)
        # jsan: disable=shared-state-unlocked -- every Ewma instance is written under exactly one lock (arrival gap: the producers' ring/queue lock; service time: the dispatchers' server lock); the per-class model cannot split instances
        self.count += 1
        # jsan: disable=shared-state-unlocked -- same per-instance single-lock discipline as above
        self.value = (x if self.value is None
                      else self.alpha * x + (1 - self.alpha) * self.value)
        return self.value

    def reset(self) -> None:
        """Forget the learned estimate, returning to the cold state
        (``value is None``). Used when the world the estimate described
        is gone — e.g. a ``set_active`` weight-swap re-warm invalidates
        the learned per-dispatch service time, and acting on the stale
        value would mis-shed / mis-advertise Retry-After."""
        self.value = None
        self.count = 0


class _ArenaBlock:
    """One bucket-sized slab of the request ring: per-leaf preallocated
    host arrays (leading axis = ``capacity`` slots) plus parallel
    per-slot metadata lists. Slots are claimed in order (``claimed`` is
    the reservation high-water mark); ``published[i]`` flips True — a
    GIL-atomic list store, no lock — only after slot ``i``'s rows and
    metadata are fully written, so a consumer never reads a torn row."""

    __slots__ = ("obs", "mask", "stall", "req", "futures", "t_submit",
                 "deadline", "published", "dead", "claimed", "n_dead",
                 "n_deadlined")

    def __init__(self, obs_leaves, mask_leaves, capacity: int):
        self.obs = [np.zeros((capacity,) + l.shape, l.dtype)
                    for l in obs_leaves]
        self.mask = [np.zeros((capacity,) + l.shape, l.dtype)
                     for l in mask_leaves]
        self.stall = np.zeros(capacity, np.int32)
        # request-causality sidecar lane (ISSUE 20): the 64-bit request
        # id rides the slab next to the row it describes, so dispatch/
        # scatter/flight-log all read it as one more preallocated
        # column — zero per-request allocations, like the stall lane
        self.req = np.zeros(capacity, np.int64)
        self.futures: "list[Future | None]" = [None] * capacity
        self.t_submit = [0.0] * capacity
        self.deadline: "list[float | None]" = [None] * capacity
        self.published = [False] * capacity
        self.dead = [False] * capacity
        self.claimed = 0
        self.n_dead = 0
        self.n_deadlined = 0

    def reset(self) -> None:
        """Return the block to the empty state for recycling. Slab
        contents are NOT zeroed — the dispatch path neutralizes exactly
        the tail rows it pads with, so stale rows are never read."""
        for i in range(self.claimed):
            self.futures[i] = None
            self.deadline[i] = None
            self.published[i] = False
            self.dead[i] = False
        self.claimed = 0
        self.n_dead = 0
        self.n_deadlined = 0


class _ArenaRing:
    """Fixed-capacity MPSC ring of :class:`_ArenaBlock` slabs.

    Producers reserve a slot under ``lock`` — an O(1) critical section
    (sequence bump; on block rollover, one deque rotation) — then write
    the row and publish OUTSIDE the lock. The consumer takes whole
    blocks (FIFO: sealed blocks first, else it force-seals the current
    one) and recycles them after scatter; a full ring back-pressures
    producers on ``cond`` until a block frees (the bounded-memory
    contract)."""

    def __init__(self, obs_leaves, mask_leaves, bucket: int,
                 n_blocks: int, alloc_counter=None):
        self.bucket = int(bucket)
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self._obs_leaves = obs_leaves
        self._mask_leaves = mask_leaves
        self._alloc_counter = alloc_counter
        self.n_blocks = 0
        self.depth = 0              # live (not shed) slots not yet taken
        self.sealed: "collections.deque[_ArenaBlock]" = collections.deque()
        self.free: "collections.deque[_ArenaBlock]" = collections.deque()
        self.cur = self._new_block()
        for _ in range(max(2, n_blocks) - 1):
            self.free.append(self._new_block())

    def _new_block(self) -> _ArenaBlock:
        blk = _ArenaBlock(self._obs_leaves, self._mask_leaves, self.bucket)
        self.n_blocks += 1
        if self._alloc_counter is not None:
            # slabs + the stall and req-id lanes; metadata lists are
            # not ndarrays
            self._alloc_counter.inc(
                len(self._obs_leaves) + len(self._mask_leaves) + 2)
        return blk

    def grow(self, n_blocks: int) -> None:
        """Ensure at least ``n_blocks`` blocks exist (construction /
        ``start()`` time only — never on the steady-state path)."""
        with self.lock:
            while self.n_blocks < n_blocks:
                self.free.append(self._new_block())
            self.cond.notify_all()

    def blocks(self) -> "list[_ArenaBlock]":
        """Ring-resident blocks in FIFO order (caller holds ``lock``)."""
        return [*self.sealed, self.cur]

    def take_block(self) -> "_ArenaBlock | None":
        """Remove and return the oldest block with claimed slots (the
        current block is force-sealed when nothing older is waiting), or
        None when the ring is empty. Once taken, a block is invisible to
        producers and shed scans until :meth:`recycle`."""
        with self.lock:
            if self.sealed:
                blk = self.sealed.popleft()
            elif self.cur.claimed > 0 and self.free:
                blk = self.cur
                self.cur = self.free.popleft()
            else:
                return None
            self.depth -= blk.claimed - blk.n_dead
            return blk

    def recycle(self, blk: _ArenaBlock) -> None:
        blk.reset()
        with self.lock:
            self.free.append(blk)
            self.cond.notify_all()

    def head_t_submit(self) -> "float | None":
        """Submit time of the oldest live published slot (the static
        hold-wait anchor). Lock-free racy read: a concurrent take makes
        the anchor momentarily stale, which only shortens a hold."""
        for blk in (self.sealed[0] if self.sealed else self.cur,):
            for i in range(blk.claimed):
                if blk.published[i] and not blk.dead[i]:
                    return blk.t_submit[i]
        return None

    def deadlines_due(self) -> "list[float]":
        """When each live pending slot's deadline falls due (submit time
        + deadline), for the adaptive hold's slack scan."""
        with self.lock:
            return [blk.t_submit[i] + blk.deadline[i]
                    for blk in self.blocks() for i in range(blk.claimed)
                    if blk.published[i] and not blk.dead[i]
                    and blk.deadline[i] is not None]


class PolicyServer:
    """The continuous-batching request queue over one
    :class:`~.engine.InferenceEngine`.

    ``submit`` enqueues a request and returns a
    :class:`concurrent.futures.Future` resolving to :class:`ServeResult`;
    ``pump`` drains up to ``engine.max_bucket`` pending requests into
    one coalesced dispatch. Drive it either inline (submit-then-pump —
    deterministic batch composition; what ``serve --bench`` does so its
    measured dispatch sizes are exactly the request sizes) or via the
    background dispatcher thread (:meth:`start` / :meth:`stop`) for live
    continuous batching, where a dispatch grabs whatever is pending the
    moment the previous one finishes.

    **The arena data plane** (ISSUE 17) is a zero-copy hot path:
    ``submit`` memcpys the request row straight into a preallocated
    slab slot (reserved under a tiny O(1) ring lock, the
    copy itself outside any lock), ``pump`` seals and dispatches slab
    views, and steady state allocates no host ndarrays per batch. Slabs
    are sized from ``example_obs``/``example_mask`` at construction when
    given, else lazily from the first submitted request (row shapes and
    dtypes are then FIXED: later submits must match, and float inputs
    are cast to the arena dtype instead of silently promoting the
    batch).

    SLO surface (the ``registry`` gauges/counters, re-rendered by both
    the ``metrics.prom`` snapshot and the live scrape endpoint):
    ``serve_requests_total``, ``serve_dispatches_total``,
    ``serve_queue_depth``, ``serve_batch_occupancy`` (real rows /
    bucket, last dispatch), the ``serve_decision_latency_seconds``
    histogram (observed per request at scatter — the aggregatable
    latency surface; scrape-side ``histogram_quantile`` beats exporting
    pre-computed percentiles), ``serve_latency_sample_window`` (live
    reservoir size), ``serve_decision_latency_p50_ms`` / ``_p99_ms``
    and ``serve_decisions_per_s`` (+ ``_per_chip``) via
    :meth:`slo_snapshot`, and ``serve_arena_allocs_total`` (host
    ndarrays allocated by the arena — warmup/ring-growth only; a moving
    value in steady state is a regression, gated by test). Since
    ISSUE 20 the percentile/throughput gauges are refreshed by a
    registry pre-scrape collector hook (scrapes are never stale),
    ``serve_queue_wait_seconds`` buckets the submit->dispatch wait
    separately from service time, and
    ``self.slo`` is an :class:`~..obs.slo.SLOEngine` evaluating
    availability / queue-latency / engine-health burn rates
    (``slo_burn_rate``, ``slo_error_budget_remaining``,
    ``slo_burn_alert`` bus events) on every collect.

    **Request causality** (ISSUE 20): every submit carries a 64-bit
    ``req_id`` (caller-supplied or minted here) that rides an int64
    sidecar lane of the arena slab — same zero-steady-state-allocation
    contract as the data lanes — and is stamped on the
    enqueue/shed/served instants, the latency exemplar reservoir, the
    flight log's ``req_id`` column, and the resolved
    :class:`ServeResult`.

    With a ``tracer`` attached (``serve --trace-spans``) the request
    lifecycle lands on the flight recorder: an ``enqueue`` instant per
    submit, then ``bucket_wait`` -> ``serve_batch`` (``arena_seal`` ->
    engine ``pad``/``dispatch`` -> ``scatter``) per pump.

    When the engine exposes ``add_rewarm_listener`` (the router does),
    the server registers a callback that RESETS the learned service-time
    Ewma on weight-swap re-warm: the estimate described the old fleet
    shape/weights, and stale values would mis-shed admissions and
    mis-advertise ``Retry-After``.
    """

    def __init__(self, engine, registry=None, latency_window: int = 8192,
                 clock=time.perf_counter, max_wait_s: float | None = None,
                 tracer=None, sample_seed: int = 0,
                 adaptive_wait: bool = False,
                 example_obs: Any = None, example_mask: Any = None,
                 arena_blocks: "int | None" = None, flight_log=None,
                 bus=None):
        from ..obs import Registry
        from ..obs.slo import SLOEngine, SLOSpec, histogram_sli
        self.engine = engine
        # data-flywheel tap: a capture-mode engine returns
        # (actions, behavior log-prob, value) per dispatch; the server
        # unpacks the triple and, when a flight log is attached, appends
        # every SERVED row (shed rows never dispatch, so rows_logged ==
        # served is structural, not best-effort)
        self._capture = bool(getattr(engine, "capture", False))
        self._flight_log = flight_log
        if flight_log is not None and not self._capture:
            raise ValueError(
                "flight_log requires a capture-mode engine "
                "(capture=True): the log's behavior log-prob and value "
                "columns come out of the engine's compiled decision "
                "program, never a post-hoc recompute")
        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.bus = bus
        # request-causality ids (ISSUE 20): 64 bits = [1 zero bit]
        # [7 rank][16 pid][40 seq] — collision-free across ranks and
        # processes without coordination, and the sign bit stays clear
        # so an id survives the int64 flight-log column round trip.
        # seq starts at 1: id 0 means "unassigned" (v1 wire frames).
        rank = int(getattr(bus, "rank", 0) or 0)
        self._req_salt = (((rank & 0x7F) << 56)
                          | ((os.getpid() & 0xFFFF) << 40))
        self._req_seq = itertools.count(1)
        if max_wait_s is not None and max_wait_s < 0:
            raise ValueError(f"max_wait_s must be >= 0, got {max_wait_s}")
        if arena_blocks is not None and arena_blocks < 2:
            raise ValueError(f"arena_blocks must be >= 2, "
                             f"got {arena_blocks}")
        self.max_wait_s = max_wait_s
        self.adaptive_wait = bool(adaptive_wait)
        self._clock = clock
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._sleepers = 0          # consumers parked on _wake (under _lock)
        self._shed_lock = threading.Lock()   # serializes shed counting
        self._ring: "_ArenaRing | None" = None
        self._min_blocks = (arena_blocks if arena_blocks is not None
                            else max(4, min(128, 1024
                                            // int(engine.max_bucket))))
        # lifetime-uniform reservoirs, not rings: a soak run's p99 must
        # describe the whole run, not its trailing window
        self._latencies = Reservoir(latency_window, seed=sample_seed)
        self._occupancies = Reservoir(latency_window, seed=sample_seed + 1)
        # exemplar lane: same capacity AND seed as _latencies, appended
        # in lockstep -> Algorithm R draws identical replacement
        # indices, so sample i's request id is _latency_req_ids[i] —
        # ids can't ride float gauges (the salt exceeds 2**53), so the
        # p99 exemplar surfaces through slo_snapshot()'s dict instead
        self._latency_req_ids = Reservoir(latency_window, seed=sample_seed)
        self._threads: list[threading.Thread] = []
        self._stopped = False
        self._closed = False
        self._served = 0
        self._t_first: float | None = None
        self._t_last: float | None = None
        # streaming estimators feeding adaptive batching + admission:
        # inter-arrival gap (how long a bucket slot takes to fill) and
        # per-dispatch service time (how long a queued dispatch costs)
        self._arrival_gap = Ewma(alpha=0.2)
        self._service_time = Ewma(alpha=0.2)
        self._t_prev_submit: "float | None" = None
        self._requests = self.registry.counter(
            "serve_requests_total", "scheduling requests submitted")
        self._shed = self.registry.counter(
            "serve_shed_total",
            "requests rejected with a typed deadline rejection "
            "(admission + in-queue expiry)")
        self._dispatches = self.registry.counter(
            "serve_dispatches_total", "coalesced batch dispatches")
        self._padded = self.registry.counter(
            "serve_padded_slots_total",
            "bucket slots filled with padding instead of requests")
        self._depth = self.registry.gauge(
            "serve_queue_depth", "requests waiting after the last drain")
        self._occupancy = self.registry.gauge(
            "serve_batch_occupancy",
            "real rows / bucket rows of the last dispatch")
        self._sample_window = self.registry.gauge(
            "serve_latency_sample_window",
            "latency samples currently held by the reservoir")
        self._latency_hist = self.registry.histogram(
            "serve_decision_latency_seconds",
            "submit->result decision latency (cumulative histogram; "
            "aggregatable across ranks/restarts, unlike percentile "
            "gauges)")
        self._queue_wait_hist = self.registry.histogram(
            "serve_queue_wait_seconds",
            "submit->dispatch queue wait (the shed-or-scale half of "
            "decision latency: service time is the other half, and "
            "only the split says which knob to turn)")
        self._dispatch_errors = self.registry.counter(
            "serve_dispatch_errors_total",
            "background pumps that raised after resolving their batch's "
            "futures exceptionally (the dispatcher survives and keeps "
            "serving)")
        self._arena_allocs = self.registry.counter(
            "serve_arena_allocs_total",
            "host ndarrays allocated by the arena data plane (slab "
            "construction and ring growth; steady state must stay flat)")
        if (example_obs is None) != (example_mask is None):
            raise ValueError("example_obs and example_mask must be given "
                             "together (the arena is sized from both)")
        if example_obs is not None:
            self.ensure_arena(example_obs, example_mask)
        add_listener = getattr(engine, "add_rewarm_listener", None)
        if callable(add_listener):
            add_listener(self._on_engine_rewarm)
        # the hedge counter is the router's, shared through the common
        # registry (re-registration returns the same object); over a
        # plain engine it simply never moves
        self._hedges = self.registry.counter(
            "serve_retry_hedges_total",
            "dispatches retried on a sibling engine after a failure")
        # declarative SLOs (ISSUE 20): burn rates re-evaluated by the
        # registry's pre-scrape collector hook, never hand-refreshed.
        # Windows are soak-scale (seconds, not SRE-handbook hours)
        # because this process's serving lifetime IS the soak.
        self.slo = SLOEngine(self.registry, bus=bus)
        self.slo.watch(SLOSpec(
            "availability", objective=0.99,
            windows=((5.0, 2.0), (30.0, 1.0)), budget_window_s=30.0,
            description="fraction of admitted requests neither shed "
                        "nor failed"), self._availability_sli)
        self.slo.watch(SLOSpec(
            "queue-latency", objective=0.95,
            windows=((5.0, 2.0), (30.0, 1.0)), budget_window_s=30.0,
            description="fraction of requests dispatched within 250ms "
                        "of submit"),
            histogram_sli(self._queue_wait_hist, 0.25))
        # short windows + a rolling 3s budget: a hedge burst (a sick
        # engine) trips the alert within one collect and the budget
        # gauge visibly recovers seconds after the fault clears — the
        # chaos-soak CI gate pins exactly that cycle
        self.slo.watch(SLOSpec(
            "engine-health", objective=0.999,
            windows=((1.0, 1.0), (3.0, 1.0)), budget_window_s=3.0,
            description="fraction of dispatches served without a "
                        "hedge or failure"), self._engine_health_sli)
        # the percentile/throughput gauges ride the same hook, retiring
        # the manual slo_snapshot() refresh calls (a scrape between
        # refreshes used to read stale gauges)
        self.registry.add_collector(self._refresh_slo_gauges)

    # ---- estimator lifecycle -----------------------------------------

    def _on_engine_rewarm(self) -> None:
        """Engine/router weight-swap re-warm callback: the learned
        per-dispatch service time described the PREVIOUS fleet, so
        forget it (admission goes back to cold-admit until relearned,
        and the frontend's Retry-After falls back to its floor)."""
        with self._lock:
            self._service_time.reset()

    # ---- request-causality ids ---------------------------------------

    def mint_request_id(self) -> int:
        """Next request-causality id. Thread-safe without a lock:
        ``itertools.count.__next__`` is atomic under the GIL, and the
        rank/pid salt makes ids from different processes disjoint. The
        frontend calls this when a client didn't supply an
        ``X-Request-Id`` (or sent a v1 frame), so it knows the id it
        must echo on the response."""
        return self._req_salt | (next(self._req_seq) & 0xFFFFFFFFFF)

    # ---- SLI plumbing ------------------------------------------------

    def _availability_sli(self) -> "tuple[float, float]":
        """(bad, total) for the availability SLO: bad = typed sheds
        plus failed dispatches (a failed pump fails every row it
        carried; counting it once is the cheap conservative floor),
        total = requests admitted at the door."""
        return (self._shed.value + self._dispatch_errors.value,
                self._requests.value)

    def _engine_health_sli(self) -> "tuple[float, float]":
        """(bad, total) for the engine-health SLO: bad = retry hedges
        (each one is a dispatch an engine failed before the hedge
        rescued it) plus dispatches that failed outright, total =
        dispatches attempted."""
        return (self._hedges.value + self._dispatch_errors.value,
                self._dispatches.value + self._dispatch_errors.value)

    def _refresh_slo_gauges(self) -> None:
        """Pre-scrape collector hook: recompute the percentile and
        throughput gauges at render time — the replacement for the
        manual ``slo_snapshot()`` refresh calls the CLIs used to
        sprinkle before every write."""
        self.slo_snapshot()

    # ---- arena construction ------------------------------------------

    def _row_leaves(self, tree: Any) -> "list[np.ndarray]":
        import jax
        return [np.asarray(l) for l in jax.tree.leaves(tree)]

    def ensure_arena(self, example_obs: Any, example_mask: Any) -> None:
        """Build the slab ring from one example request row (no leading
        batch axis). Called from the constructor when examples are
        given, else lazily by the first :meth:`submit`; idempotent.
        Row shapes and dtypes are fixed from the example."""
        if self._ring is not None:
            return
        import jax
        with self._lock:
            if self._ring is not None:
                return
            obs_leaves = self._row_leaves(example_obs)
            mask_leaves = self._row_leaves(example_mask)
            self._obs_treedef = jax.tree.structure(example_obs)
            self._mask_treedef = jax.tree.structure(example_mask)
            self._obs_is_leaf = (self._obs_treedef.num_leaves == 1
                                 and isinstance(example_obs, np.ndarray))
            self._mask_is_leaf = (self._mask_treedef.num_leaves == 1
                                  and isinstance(example_mask, np.ndarray))
            self._obs_row_shapes = [l.shape for l in obs_leaves]
            self._mask_row_shapes = [l.shape for l in mask_leaves]
            # single-ndarray-leaf rows take a no-loop submit fast path
            self._fast_rows = self._obs_is_leaf and self._mask_is_leaf
            self._ring = _ArenaRing(
                obs_leaves, mask_leaves, int(self.engine.max_bucket),
                self._min_blocks, alloc_counter=self._arena_allocs)

    def arena_stats(self) -> dict:
        """Arena occupancy/allocation surface for benches and CI gates."""
        ring = self._ring
        return {
            "blocks": ring.n_blocks if ring is not None else 0,
            "rows": (ring.n_blocks * ring.bucket
                     if ring is not None else 0),
            "slab_allocs": int(self._arena_allocs.value),
        }

    # ---- shed plumbing -----------------------------------------------

    def _reject(self, fut: Future, exc: DeadlineSheddedError,
                reason: str) -> None:
        """Resolve ``fut`` with a typed shed rejection and count it in
        ``serve_shed_total`` — counting gated on WINNING the future's
        state transition, so a request raced by two dispatchers' expiry
        scans (or abandoned via ``Future.cancel``) is counted at most
        once, and only when someone will actually observe the rejection.
        Conservation (submitted == resolved + shed) is structural, not
        best-effort. The counter bump takes its own tiny lock: rejects
        fire from producer threads (admission) and dispatcher threads
        (expiry) which no longer share a queue lock."""
        try:
            fut.set_exception(exc)
        except BaseException:   # cancelled, or already resolved elsewhere
            return
        with self._shed_lock:
            self._shed.inc()
        self.tracer.instant("shed", reason=reason, req_id=exc.req_id)

    # ---- submit ------------------------------------------------------

    def submit(self, obs: Any, mask: Any, stall: int = 0,
               deadline_s: "float | None" = None,
               req_id: "int | None" = None) -> Future:
        """Enqueue one scheduling request (host pytrees, NO leading batch
        axis). ``stall`` is the client's consecutive-zero-dt count for
        the stall gate (preemptive configs; 0 = gate disengaged).

        ``req_id`` is the request-causality key (ISSUE 20): minted here
        when the caller didn't bring one (``None``/0 — the frontend
        mints eagerly instead, so it can echo the id even on a shed).
        The id rides the arena sidecar lane through dispatch and
        scatter, is stamped on the enqueue/shed/served instants and the
        latency exemplars, lands in the flight log's ``req_id`` column,
        and comes back on the resolved :class:`ServeResult` — one key
        joining every observation of this request's life.

        ``deadline_s`` is the request's latency SLO, relative to submit.
        A deadlined request is subject to **load shedding**: if the
        predicted queue wait at submit time (queued dispatches ahead ×
        learned service time) already exceeds the deadline, or the
        deadline expires while queued, the returned future resolves
        exceptionally with :class:`DeadlineSheddedError` — typed, never
        a silent drop — and ``serve_shed_total`` counts it. Admission
        only rejects once the service-time estimator has observations
        (a cold server admits everything rather than guessing).

        This call performs the ONE host copy of the request's life: the
        row lands directly in the current slab slot (wire bytes -> arena
        when called from the frontend's ``np.frombuffer`` views). Rows
        that don't match the arena's fixed shapes raise ``ValueError``
        here, at the door."""
        req_id = self.mint_request_id() if not req_id else int(req_id)
        if self._ring is None:
            self.ensure_arena(obs, mask)     # lazy sizing, first request
        ring = self._ring
        now = self._clock()
        fut: Future = Future()
        deadline_s = None if deadline_s is None else float(deadline_s)
        shed_exc = None
        with ring.lock:
            if self._closed:
                raise ServerClosedError(
                    "PolicyServer is closed (drained for shutdown)")
            if self._stopped:
                raise ServerClosedError(
                    "PolicyServer is stopped (drain in flight)")
            self._requests.inc()
            if self._t_prev_submit is not None:
                self._arrival_gap.update(now - self._t_prev_submit)
            self._t_prev_submit = now
            svc = self._service_time.value
            if deadline_s is not None and svc is not None:
                # dispatches ahead of this request if it joins the queue,
                # itself included — each costs ~one learned service time
                ahead = -(-(ring.depth + 1) // self.engine.max_bucket)
                predicted = ahead * svc
                if predicted > deadline_s:
                    shed_exc = DeadlineSheddedError(
                        "admission", deadline_s, waited_s=0.0,
                        predicted_wait_s=predicted, req_id=req_id)
            if shed_exc is None:
                # common case inlined: current block has a free slot
                blk = ring.cur
                i = blk.claimed
                if i < ring.bucket:
                    blk.claimed = i + 1
                    ring.depth += 1
                else:
                    blk, i = self._reserve_slot_locked(ring)
        if shed_exc is not None:
            self._reject(fut, shed_exc, reason="admission")
            return fut
        # outside every lock: the row copy and the publish store
        try:
            # single-leaf fast path inlined: this is the per-request hot
            # path the host bench measures, and the generic tree walk in
            # _write_row costs more than the memcpy itself
            if (self._fast_rows and type(obs) is np.ndarray
                    and type(mask) is np.ndarray
                    and obs.shape == self._obs_row_shapes[0]
                    and mask.shape == self._mask_row_shapes[0]):
                blk.obs[0][i] = obs
                blk.mask[0][i] = mask
                blk.stall[i] = stall
            else:
                self._write_row(blk, i, obs, mask, int(stall))
        except BaseException:
            # the slot is already reserved — kill it in place (typed
            # error to the CALLER; there is no future holder to strand)
            with ring.lock:
                blk.dead[i] = True
                blk.n_dead += 1
                ring.depth -= 1
            blk.published[i] = True
            raise
        blk.req[i] = req_id          # sidecar lane: one int64 store
        blk.t_submit[i] = now
        blk.deadline[i] = deadline_s
        blk.futures[i] = fut
        if deadline_s is not None:
            blk.n_deadlined += 1
        blk.published[i] = True      # GIL-atomic store: slot now visible
        if self._sleepers:           # wake a parked consumer (rare in
            with self._wake:         # steady state: dispatchers stay hot)
                self._wake.notify_all()
        if self.tracer is not NULL_TRACER:
            self.tracer.instant("enqueue", stall=int(stall),
                                req_id=req_id)
        return fut

    def _write_row(self, blk: _ArenaBlock, i: int, obs, mask,
                   stall: int) -> None:
        """The one memcpy: request row -> slab slot ``i``. Shape
        mismatches raise before any slab write (no torn rows)."""
        if self._obs_is_leaf and isinstance(obs, np.ndarray):
            obs_leaves = (obs,)
        else:
            import jax
            obs_leaves = jax.tree.leaves(obs)
        if self._mask_is_leaf and isinstance(mask, np.ndarray):
            mask_leaves = (mask,)
        else:
            import jax
            mask_leaves = jax.tree.leaves(mask)
        if len(obs_leaves) != len(blk.obs):
            raise ValueError(
                f"obs has {len(obs_leaves)} leaves, arena expects "
                f"{len(blk.obs)}")
        if len(mask_leaves) != len(blk.mask):
            raise ValueError(
                f"mask has {len(mask_leaves)} leaves, arena expects "
                f"{len(blk.mask)}")
        for j, leaf in enumerate(obs_leaves):
            if np.shape(leaf) != self._obs_row_shapes[j]:
                raise ValueError(
                    f"obs leaf {j} has shape {np.shape(leaf)}, arena row "
                    f"is {self._obs_row_shapes[j]}")
            blk.obs[j][i] = leaf
        for j, leaf in enumerate(mask_leaves):
            if np.shape(leaf) != self._mask_row_shapes[j]:
                raise ValueError(
                    f"mask leaf {j} has shape {np.shape(leaf)}, arena row "
                    f"is {self._mask_row_shapes[j]}")
            blk.mask[j][i] = leaf
        blk.stall[i] = stall

    def _reserve_slot_locked(self, ring: _ArenaRing):
        """Claim the next slot (caller holds ``ring.lock``). Rolls the
        current block over when full; a completely full ring waits for
        the consumer to recycle a block (bounded slices so a close()
        during the wait raises instead of hanging)."""
        while True:
            blk = ring.cur
            i = blk.claimed
            if i < ring.bucket:
                blk.claimed = i + 1
                ring.depth += 1
                return blk, i
            if ring.free:               # rollover: seal, swap in a free
                ring.sealed.append(blk)
                ring.cur = ring.free.popleft()
                continue
            # ring full: producer backpressure until a block recycles
            ring.cond.wait(timeout=0.05)
            if self._closed or self._stopped:
                raise ServerClosedError(
                    "PolicyServer is closing (arena ring drained for "
                    "shutdown)")

    # ---- expiry ------------------------------------------------------

    def _shed_expired(self, now: float) -> None:
        """Expiry: slots whose deadline already passed are marked dead
        IN PLACE (their slab rows become padding at dispatch) instead of
        being removed from a queue; the typed rejections fire outside
        the ring lock. A head-first scan is NOT enough: deadlines are
        per-request, so a generous-deadline head can hide an expired
        tail."""
        ring = self._ring
        if ring is None:
            return
        expired: "list[tuple[Future, float, float, int]]" = []
        with ring.lock:
            blocks = ring.blocks()
            if not any(b.n_deadlined for b in blocks):
                return
            for blk in blocks:
                for i in range(blk.claimed):
                    if not blk.published[i] or blk.dead[i]:
                        continue
                    d = blk.deadline[i]
                    if d is None:
                        continue
                    waited = now - blk.t_submit[i]
                    if waited > d:
                        blk.dead[i] = True
                        blk.n_dead += 1
                        blk.n_deadlined -= 1
                        ring.depth -= 1
                        expired.append((blk.futures[i], d, waited,
                                        int(blk.req[i])))
                        blk.futures[i] = None
        for fut, d, waited, rid in expired:
            self._reject(fut, DeadlineSheddedError(
                "expired", d, waited_s=waited, req_id=rid),
                reason="expired")

    # ---- adaptive hold -----------------------------------------------

    def _effective_wait(self) -> "float | None":
        """The partial-bucket hold time for THIS pump (called under
        ``self._lock``, queue non-empty). Static mode returns the
        constructor knob. Adaptive mode learns it: hold for the
        estimated time to FILL the bucket at the observed arrival rate
        (waiting longer than that buys nothing), clipped to the
        head-of-line deadline slack (dispatch a partial bucket rather
        than shed the head), and capped by ``max_wait_s`` when given."""
        if not self.adaptive_wait:
            return self.max_wait_s
        waits = []
        if self.max_wait_s is not None:
            waits.append(self.max_wait_s)
        ring = self._ring
        gap = self._arrival_gap.value
        if gap is not None:
            free = max(self.engine.max_bucket - ring.depth, 0)
            waits.append(gap * free)
        now = self._clock()
        due = ring.deadlines_due()
        if due:
            # keep one learned service time in hand for the dispatch
            svc = self._service_time.value or 0.0
            waits.append(max(min(due) - now - svc, 0.0))
        return min(waits) if waits else None

    # ---- pump --------------------------------------------------------

    def _hold_for_bucket(self, ring: _ArenaRing,
                         max_wait_s: "float | None") -> None:
        """The partial-bucket hold loop (caller holds ``self._lock``).
        The sleep re-checks depth AFTER advertising itself in
        ``_sleepers`` — with producers publishing outside this lock,
        that ordering (producer: publish then read ``_sleepers``;
        consumer: increment then re-check) is what makes the wakeup
        race-free without a per-submit lock."""
        wait = (max_wait_s if max_wait_s is not None
                else self._effective_wait())
        if wait is None:
            return
        # static mode anchors at the head's submit time (total head wait
        # bounded by the knob); adaptive mode anchors NOW — its estimate
        # already folds in the head's remaining slack
        if max_wait_s is None and self.adaptive_wait:
            anchor = self._clock()
        else:
            head = ring.head_t_submit()
            anchor = head if head is not None else self._clock()
        deadline = anchor + wait
        with self.tracer.span("bucket_wait"):
            while (ring.depth < self.engine.max_bucket
                   and not self._stopped):
                remaining = deadline - self._clock()
                if remaining <= 0:
                    break
                self._sleepers += 1
                try:
                    if (ring.depth < self.engine.max_bucket
                            and not self._stopped):
                        self._wake.wait(timeout=remaining)
                finally:
                    self._sleepers -= 1

    def _split_capture(self, out):
        """Unpack one engine dispatch output: a capture engine returns
        the ``(actions, behavior log-prob, value)`` triple, a plain
        engine just actions (then log-prob/value are ``None``)."""
        if self._capture:
            actions, blp, bval = out
            return actions, blp, bval
        return out, None, None

    def _log_rows(self, obs, mask, stall, actions, blp, bval, n: int,
                  lats: "list[float]", deads, req_ids) -> None:
        """Append this dispatch's ``n`` SERVED rows to the flight log.
        Deadline outcome per row: 0 = no deadline, 1 = met, 2 = served
        late (resolved past its SLO but not shed). Shed rows never reach
        a dispatch, so the log's row count equals ``serve_dispatches``'
        served total exactly — the flywheel's conservation contract."""
        import jax
        # per-call outcome buffer, NOT a shared scratch: N dispatcher
        # threads reach here concurrently outside self._lock, and the
        # flight log only copies rows under ITS lock — a shared slab
        # would let one thread's fill interleave with another's copy
        # jsan: disable=alloc-in-hot-loop -- n int8s per dispatch (noise next to this call's obs/mask slab memcpys); a shared scratch raced across dispatcher threads
        outcome = np.zeros(n, np.int8)
        for i, d in enumerate(deads):
            if d is not None:
                outcome[i] = 1 if lats[i] <= d else 2
        self._flight_log.append_batch(
            jax.tree.map(lambda l: np.asarray(l)[:n], obs),
            jax.tree.map(lambda l: np.asarray(l)[:n], mask),
            jax.tree.map(lambda l: np.asarray(l)[:n], actions),
            np.asarray(blp)[:n], np.asarray(bval)[:n],
            np.asarray(stall)[:n], outcome,
            req_id=np.asarray(req_ids, np.int64)[:n])

    def _seal_block(self, blk: _ArenaBlock):
        """Turn a taken block into a dispatchable contiguous prefix:
        wait out in-flight row copies (bounded by one memcpy — the
        producer published its reservation before we took the block),
        compact live rows over dead ones (shed slots become padding),
        and neutralize the pad tail IN PLACE (zero obs, all-legal bool
        masks, zero stall, zero req id) — pure slice assignment, no
        allocation. Returns ``(n_live, bucket, futures, t_submits,
        deadlines, req_ids)`` — ``req_ids`` is a view into the slab's
        sidecar lane, valid until the block recycles."""
        spin_deadline = time.monotonic() + 5.0
        while not all(blk.published[:blk.claimed]):
            if time.monotonic() > spin_deadline:
                # a producer died mid-copy (interpreter teardown); its
                # slot has no future holder — treat it as dead padding
                for i in range(blk.claimed):
                    if not blk.published[i]:
                        blk.published[i] = True
                        blk.dead[i] = True
                        blk.n_dead += 1
                break
            time.sleep(50e-6)
        live = [i for i in range(blk.claimed) if not blk.dead[i]]
        n_live = len(live)
        if n_live == 0:
            return 0, 0, [], [], [], []
        if n_live != blk.claimed:
            # compact: shift live rows down over dead ones (dst <= src,
            # so in-place row moves are safe); rare — shed path only
            for dst, src in enumerate(live):
                if dst == src:
                    continue
                for leaf in blk.obs:
                    leaf[dst] = leaf[src]
                for leaf in blk.mask:
                    leaf[dst] = leaf[src]
                blk.stall[dst] = blk.stall[src]
                blk.req[dst] = blk.req[src]
                blk.futures[dst] = blk.futures[src]
                blk.t_submit[dst] = blk.t_submit[src]
                blk.deadline[dst] = blk.deadline[src]
        bucket = next_bucket(n_live, self.engine.max_bucket)
        if n_live < bucket:
            for leaf in blk.obs:
                leaf[n_live:bucket] = 0
            for leaf in blk.mask:
                leaf[n_live:bucket] = (True if leaf.dtype == np.bool_
                                       else 0)
            blk.stall[n_live:bucket] = 0
            blk.req[n_live:bucket] = 0
        return (n_live, bucket, blk.futures[:n_live],
                blk.t_submit[:n_live], blk.deadline[:n_live],
                blk.req[:n_live])

    def _arena_views(self, blk: _ArenaBlock, bucket: int):
        """Contiguous ``[:bucket]`` views of the slab, re-assembled into
        the caller's pytree structure (views, never copies)."""
        if self._obs_is_leaf:
            obs = blk.obs[0][:bucket]
        else:
            import jax
            obs = jax.tree.unflatten(
                self._obs_treedef, [l[:bucket] for l in blk.obs])
        if self._mask_is_leaf:
            mask = blk.mask[0][:bucket]
        else:
            import jax
            mask = jax.tree.unflatten(
                self._mask_treedef, [l[:bucket] for l in blk.mask])
        return obs, mask, blk.stall[:bucket]

    def _scatter(self, blk: _ArenaBlock, actions: Any, n_live: int):
        """Per-request action views into the single device-fetched
        actions buffer. If the engine echoed its INPUT back (host-stub
        engines do), the buffer aliases the slab we are about to
        recycle — detected with a bounds-only overlap check and copied
        once, so resolved results can never be corrupted by slab
        reuse."""
        import jax
        leaves = [np.asarray(l) for l in jax.tree.leaves(actions)]
        slabs = blk.obs + blk.mask + [blk.stall]
        safe = []
        for leaf in leaves:
            if any(np.may_share_memory(leaf, s) for s in slabs):
                leaf = leaf.copy()
            safe.append(leaf)
        if len(safe) == 1 and isinstance(actions, np.ndarray):
            buf = safe[0]
            return [buf[i] for i in range(n_live)]
        treedef = jax.tree.structure(actions)
        return [jax.tree.unflatten(treedef, [l[i] for l in safe])
                for i in range(n_live)]

    def pump(self, max_wait_s: float | None = None) -> int:
        """Drain one coalesced batch: take up to ``engine.max_bucket``
        pending requests (FIFO), dispatch, scatter results to their
        futures. Returns the number of requests served (0 = queue was
        empty). The "batch" is one slab: tail slots are neutralized in
        place and the engine sees a contiguous full-bucket view — no
        stacking, no padding copies.

        ``max_wait_s`` (default: the constructor's policy; ``None`` = no
        wait) is the batching deadline: a PARTIAL bucket holds off
        dispatching until either the bucket fills or the batching
        deadline passes — trading a bounded latency floor for occupancy
        (the classic continuous-batching knob). ``0`` keeps the
        dispatch-whatever-is-pending behavior while still being
        explicit about it. With ``adaptive_wait`` the hold time is
        LEARNED per pump (:meth:`_effective_wait`): the estimated
        bucket fill time at the observed arrival rate, cut short when
        the head-of-line deadline slack runs out — the deadline-aware
        partial-bucket dispatch. Expired deadlines shed before and
        after the hold (:meth:`_shed_expired`). A :meth:`stop` drain
        cuts the wait short so shutdown never hangs on a sparse
        queue."""
        ring = self._ring
        if ring is None:
            return 0
        with self._lock:
            self._shed_expired(self._clock())
            if ring.depth > 0:
                self._hold_for_bucket(ring, max_wait_s)
                self._shed_expired(self._clock())
            blk = ring.take_block()
            self._depth.set(ring.depth)
        if blk is None:
            return 0
        t_disp = self._clock()
        try:
            n_live, bucket, futs, t_subs, deads, rids = \
                self._seal_block(blk)
        except BaseException:
            ring.recycle(blk)
            raise
        if n_live == 0:
            ring.recycle(blk)
            return 0
        try:
            # with no tracer attached each span is the profiler's bare
            # annotation: six a batch, ~2 us of a 1.66 ms dispatch
            with self.tracer.span("serve_batch", n=n_live):
                with self.tracer.span("arena_seal"):
                    obs, mask, stall = self._arena_views(blk, bucket)
                out, bucket = self.engine.decide(obs, mask, stall)
                actions, blp, bval = self._split_capture(out)
                now = self._clock()
                with self.tracer.span("scatter"):
                    per_req = self._scatter(blk, actions, n_live)
            lats = [now - t for t in t_subs]
            if self._flight_log is not None:
                # tap point: the slab views stay valid until ring.recycle
                # below (donation consumed the DEVICE copies, not these
                # host slabs), and the flight log copies rows into its
                # own recycled shard buffer before returning. Inside the
                # try: a failing append must resolve this batch's
                # futures with the exception (the dispatcher loop's
                # no-silent-drop invariant), never strand them
                self._log_rows(obs, mask, stall, actions, blp, bval,
                               n_live, lats, deads, rids)
        except BaseException as e:
            for fut in futs:
                if not fut.cancelled():
                    fut.set_exception(e)
            if self.tracer is not NULL_TRACER:
                self.tracer.instant("dispatch_failed",
                                    req_ids=[int(r) for r in rids],
                                    error=type(e).__name__)
            ring.recycle(blk)
            raise
        self._account_dispatch(now, t_disp, n_live, bucket, lats,
                               t_subs, rids)
        for fut, a, lat, rid in zip(futs, per_req, lats, rids):
            try:
                fut.set_result(ServeResult(action=a, latency_s=lat,
                                           req_id=int(rid)))
            except BaseException:   # cancelled while in flight
                pass
        if self.tracer is not NULL_TRACER:
            # one instant per DISPATCH, not per request: the causality
            # record for n_live requests costs one bus write
            self.tracer.instant(
                "served", bucket=bucket,
                req_ids=[int(r) for r in rids],
                wait_ms=[round((t_disp - t) * 1e3, 3) for t in t_subs],
                lat_ms=[round(l * 1e3, 3) for l in lats])
        ring.recycle(blk)
        return n_live

    def _account_dispatch(self, now: float, t_disp: float, n: int,
                          bucket: int, lats: "list[float]",
                          t_subs, req_ids) -> None:
        """Per-dispatch accounting under the consumer lock: concurrent
        dispatcher threads (start(dispatchers=N) over a router) share
        every reservoir, counter, and estimator below. Producers never
        take this lock — that is the lock-light contract."""
        with self._lock:
            self._service_time.update(now - t_disp)
            self._dispatches.inc()
            self._padded.inc(bucket - n)
            self._occupancy.set(n / bucket)
            self._occupancies.append(n / bucket)
            if self._t_first is None:
                self._t_first = min(t_subs)
            self._t_last = now if self._t_last is None else max(
                self._t_last, now)
            self._served += n
            for lat, t_sub, rid in zip(lats, t_subs, req_ids):
                self._latencies.append(lat)
                self._latency_req_ids.append(int(rid))   # exemplar lane
                self._latency_hist.observe(lat)
                self._queue_wait_hist.observe(max(t_disp - t_sub, 0.0))
            self._sample_window.set(len(self._latencies))

    # ---- live dispatcher thread --------------------------------------

    def _has_work(self) -> bool:
        ring = self._ring
        return ring is not None and ring.depth > 0

    def start(self, dispatchers: int = 1) -> None:
        """Start the background dispatchers: pump whenever requests are
        pending (continuous batching — each dispatch coalesces whatever
        arrived while the previous one ran). ``dispatchers > 1`` keeps
        that many pumps in flight at once so a multi-engine router can
        run its engines concurrently; over a single engine extra
        dispatchers only shrink batch occupancy (and the router is the
        layer that owns device-level thread safety — see
        ``serve.router.EngineRouter``)."""
        if self._threads:
            raise RuntimeError("dispatcher already running")
        if self._closed:
            raise ServerClosedError("PolicyServer is closed")
        if dispatchers < 1:
            raise ValueError(f"dispatchers must be >= 1, got {dispatchers}")
        # every in-flight dispatcher can hold one block while another is
        # current and one stays free — guarantee the ring never wedges
        self._min_blocks = max(self._min_blocks, dispatchers + 2)
        if self._ring is not None:
            self._ring.grow(self._min_blocks)
        self._stopped = False

        def loop():
            while True:
                with self._wake:
                    while not self._has_work() and not self._stopped:
                        self._sleepers += 1
                        try:
                            if not self._has_work() and not self._stopped:
                                self._wake.wait()
                        finally:
                            self._sleepers -= 1
                    if self._stopped and not self._has_work():
                        return
                try:
                    self.pump()
                except Exception:
                    # the pump already resolved its batch's futures with
                    # the exception (no silent drop); a dead dispatcher
                    # would strand every LATER request as a hung future,
                    # so survive the failed dispatch and keep draining
                    self._dispatch_errors.inc()

        for i in range(dispatchers):
            t = threading.Thread(target=loop,
                                 name=f"serve-dispatcher-{i}",
                                 daemon=True)
            self._threads.append(t)
            t.start()

    def stop(self) -> None:
        """Stop the dispatchers after draining the queue. Submits are
        refused while the drain is in flight; once stopped the server
        is back in inline mode (submit-then-:meth:`pump`) and
        :meth:`start` may be called again."""
        with self._wake:
            self._stopped = True
            self._wake.notify_all()
        for t in self._threads:
            t.join(timeout=30)
        self._threads = []
        with self._wake:
            # a close() drain is terminal; a stop() drain returns the
            # server to inline mode
            self._stopped = self._closed

    def close(self) -> None:
        """Permanent :meth:`stop`: drain the queue, stop the dispatchers,
        then refuse every later :meth:`submit` (and :meth:`start`) with
        :class:`ServerClosedError` forever. The terminal half of the
        frontend's graceful-drain contract — after ``close`` returns,
        every future ever handed out has resolved (result, shed, or
        dispatch error) and no future will ever be created that can't.
        Idempotent."""
        with self._wake:
            self._closed = True
        self.stop()
        # inline-mode close: no dispatcher drained the queue, so flush it
        # here — every already-accepted future must resolve (each pump
        # consumes its batch even when the dispatch raises, so this
        # terminates)
        while True:
            try:
                if not self.pump():
                    break
            except Exception:
                self._dispatch_errors.inc()
        # one final refresh, then detach from the scrape surface: a
        # scrape after close reads the last computed SLO values instead
        # of running collectors against a dead server
        self.registry.collect()
        self.registry.remove_collector(self._refresh_slo_gauges)
        self.slo.close()

    @property
    def closed(self) -> bool:
        return self._closed

    def queue_depth(self) -> int:
        """Requests currently queued (the frontend's backpressure
        signal — sampled, so momentarily stale values are fine)."""
        ring = self._ring
        return ring.depth if ring is not None else 0

    def service_time_s(self) -> "float | None":
        """The learned per-dispatch service time (Ewma), ``None`` until
        the first dispatch — what the frontend derives ``Retry-After``
        from for shed responses."""
        with self._lock:
            return self._service_time.value

    # ---- SLO surface -------------------------------------------------

    def slo_snapshot(self) -> dict:
        """Compute and publish the SLO numbers: p50/p99 decision latency
        (ms), decisions/s and decisions/s/chip over the serving span,
        mean batch occupancy. Also writes the latency/throughput gauges
        into the registry so a scrape observes them."""
        import jax
        lats = np.asarray(self._latencies, np.float64)
        span = ((self._t_last - self._t_first)
                if self._served and self._t_last is not None
                and self._t_first is not None else 0.0)
        n_chips = max(jax.local_device_count(), 1)
        dps = self._served / span if span > 0 else 0.0
        snap = {
            "requests": int(self._served),
            "dispatches": int(self._dispatches.value),
            "latency_p50_ms": (float(np.percentile(lats, 50)) * 1e3
                               if lats.size else None),
            "latency_p99_ms": (float(np.percentile(lats, 99)) * 1e3
                               if lats.size else None),
            "decisions_per_s": dps,
            "decisions_per_s_per_chip": dps / n_chips,
            "n_chips": n_chips,
            "batch_occupancy_mean": (float(np.mean(self._occupancies))
                                     if self._occupancies else None),
            "serving_span_s": span,
            "slo": self.slo.status(),
        }
        if lats.size and len(self._latency_req_ids) == lats.size:
            # exemplar: the request id of the sample nearest the p99 —
            # the concrete request a p99 regression points at (ids
            # exceed a float gauge's 2**53 precision, so the exemplar
            # only rides this dict, never the registry)
            p99 = float(np.percentile(lats, 99))
            snap["latency_p99_exemplar_req_id"] = int(
                self._latency_req_ids[int(np.argmin(np.abs(lats - p99)))])
        if lats.size:
            self.registry.gauge(
                "serve_decision_latency_p50_ms",
                "median submit->result decision latency").set(
                snap["latency_p50_ms"])
            self.registry.gauge(
                "serve_decision_latency_p99_ms",
                "p99 submit->result decision latency").set(
                snap["latency_p99_ms"])
        self.registry.gauge(
            "serve_decisions_per_s",
            "scheduling decisions served per second").set(dps)
        self.registry.gauge(
            "serve_decisions_per_s_per_chip",
            "decisions/s divided by local device count").set(
            dps / n_chips)
        return snap
