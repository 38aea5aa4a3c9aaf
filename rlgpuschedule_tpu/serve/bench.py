"""`serve --bench`: the latency half of the serving SLO story.

Drives a deterministic synthetic request stream through the full
engine + continuous-batching stack and reports decision-latency
percentiles, decisions/s(/chip), occupancy, and — the steady-state
contract — the post-warmup recompile count, which must be ZERO across
distinct request batch sizes inside one bucket (ISSUE 7 acceptance;
ci.sh asserts it).

Requests are real observations: a pool is built by resetting the
config's env windows and stepping them a few decisions under the same
greedy policy being served, so the benched batches look like live
cluster snapshots, not zeros.

PR 13 adds the scale-out half: :func:`run_scaleout` measures
decisions/s + shed rate vs engine count (1 vs N routed engines, each
arm an isolated router + registry), and :func:`run_soak` drives a
sustained paced request stream through a live dispatcher fleet — the
p99-drift / zero-torn-span / zero-recompile surface the ci.sh
soak-lite stage asserts on. Both carry the
``serialized_dispatch_cpu`` honesty bit: on the CPU backend the router
serializes device work (XLA:CPU thread-safety), so decisions/s does
NOT scale with engines there — the numbers prove the routing and
accounting, not CPU wall-clock scaling.
"""
from __future__ import annotations

import os
import time
import zlib
from typing import Any

import jax
import numpy as np

from ..decision import policy_decision
from ..env import env as env_lib


def default_request_sizes(bucket: int) -> "tuple[int, ...]":
    """Three distinct request counts that all coalesce to ``bucket``
    (i.e. in ``(bucket/2, bucket]``) — the acceptance shape: one
    compiled program must serve all of them without retracing. Needs
    ``bucket >= 8`` for three distinct sizes to exist comfortably."""
    if bucket < 8:
        raise ValueError(f"default request sizes need bucket >= 8 for "
                         f"three distinct sizes in (bucket/2, bucket]; "
                         f"got {bucket} — pass explicit sizes")
    return (bucket // 2 + 1, (3 * bucket) // 4, bucket)


def build_request_pool(apply_fn, net_params: Any, env_params: Any,
                       traces: Any, steps: int = 4,
                       faults: Any = None) -> "list[tuple[Any, Any]]":
    """Materialize a pool of (obs, mask) request rows by stepping the
    env batch ``steps`` decisions under the greedy policy — every pool
    entry is a cluster state the policy actually reaches. Host pytrees,
    no leading axis; pool order is (step, env) row-major."""
    state, ts = env_lib.vec_reset(env_params, traces, faults)
    obs, mask = ts.obs, ts.action_mask
    pool: list[tuple[Any, Any]] = []

    def rows(o, m):
        o, m = jax.device_get((o, m))
        n = jax.tree.leaves(o)[0].shape[0]
        for i in range(n):
            pool.append((jax.tree.map(lambda x: np.asarray(x)[i], o),
                         jax.tree.map(lambda x: np.asarray(x)[i], m)))

    rows(obs, mask)
    for _ in range(max(steps, 0)):
        actions = policy_decision(apply_fn, net_params, obs, mask)
        state, ts = env_lib.vec_step(env_params, state, traces, actions,
                                     faults=faults)
        obs, mask = ts.obs, ts.action_mask
        rows(obs, mask)
    return pool


def run_bench(engine, server, pool: "list[tuple[Any, Any]]",
              rounds: int = 24,
              request_sizes: "tuple[int, ...] | None" = None) -> dict:
    """Serve ``rounds`` coalesced dispatches, cycling the request sizes
    and the pool deterministically, inline-pumped so every dispatch's
    composition is exactly the round's request size. Returns the SLO
    report (and leaves the same numbers in the server's registry for
    the scrape endpoint / .prom snapshot)."""
    if rounds <= 0:
        raise ValueError(f"rounds must be positive, got {rounds}")
    if not pool:
        raise ValueError("empty request pool")
    if request_sizes is None:
        request_sizes = default_request_sizes(engine.max_bucket)
    request_sizes = tuple(int(s) for s in request_sizes)
    if any(s <= 0 for s in request_sizes):
        raise ValueError(f"request sizes must be positive: "
                         f"{request_sizes}")
    buckets = sorted({engine.bucket_for(s) for s in request_sizes})

    # pre-pay the per-bucket compiles so the measured rounds are pure
    # steady state — after this, ANY compile is an alarm
    obs0, mask0 = pool[0]
    engine.warmup(obs0, mask0, buckets=tuple(buckets))
    warm_recompiles = engine.post_warmup_recompiles

    cursor = 0
    futures = []
    for r in range(rounds):
        k = request_sizes[r % len(request_sizes)]
        for _ in range(k):
            obs, mask = pool[cursor % len(pool)]
            futures.append(server.submit(obs, mask))
            cursor += 1
        server.pump()
    results = [f.result(timeout=60) for f in futures]
    # digest of every served action in submit order: two runs of the same
    # checkpoint and stream (a cold and a cache-warm one, say) must agree
    crc = 0
    for res in results:
        for leaf in jax.tree.leaves(res.action):
            crc = zlib.crc32(np.asarray(leaf).tobytes(), crc)

    # a DATA site, not a gauge refresh: the snapshot dict is the bench
    # report (gauge freshness is the registry collector hook's job now)
    snap = server.slo_snapshot()
    return {
        "rounds": rounds,
        "request_sizes": list(request_sizes),
        "buckets": [int(b) for b in buckets],
        "pool_size": len(pool),
        "post_warmup_recompiles":
            engine.post_warmup_recompiles - warm_recompiles,
        "warmed_buckets": [int(b) for b in engine.warmed_buckets],
        **snap,
        "requests": len(results),
        "actions_crc32": f"{crc:08x}",
    }


def run_scaleout(apply_fn, net_params: Any, env_params: Any,
                 pool: "list[tuple[Any, Any]]", *, max_bucket: int,
                 rounds: int = 24,
                 request_sizes: "tuple[int, ...] | None" = None,
                 engine_counts: "tuple[int, ...]" = (1, 2),
                 deadline_s: "float | None" = None) -> dict:
    """Decisions/s + shed rate vs engine count: one isolated arm per
    count in ``engine_counts`` (fresh router + registry + server, so
    arms share nothing), each serving the SAME deterministic request
    stream through ``engines`` live dispatcher threads. Per-arm output
    carries per-engine row shares and recompile counts; the top level
    carries the CPU-serialization caveat (module docstring)."""
    from ..obs import Registry
    from .batching import DeadlineSheddedError, PolicyServer
    from .router import EngineRouter

    if request_sizes is None:
        request_sizes = default_request_sizes(max_bucket)
    request_sizes = tuple(int(s) for s in request_sizes)
    obs0, mask0 = pool[0]
    arms = []
    serialized = None
    for k in engine_counts:
        reg = Registry()
        router = EngineRouter(apply_fn, net_params, env_params,
                              max_bucket=max_bucket, registry=reg,
                              n_engines=int(k))
        serialized = router.serialized_dispatch()
        buckets = tuple(sorted({router.bucket_for(s)
                                for s in request_sizes}))
        router.warmup(obs0, mask0, buckets=buckets)
        server = PolicyServer(router, registry=reg)
        server.start(dispatchers=int(k))
        futures, shed, cursor = [], 0, 0
        t0 = time.perf_counter()
        for r in range(rounds):
            for _ in range(request_sizes[r % len(request_sizes)]):
                obs, mask = pool[cursor % len(pool)]
                futures.append(server.submit(obs, mask,
                                             deadline_s=deadline_s))
                cursor += 1
        for f in futures:
            try:
                f.result(timeout=120)
            except DeadlineSheddedError:
                shed += 1
        wall = time.perf_counter() - t0
        server.stop()
        total_rows = sum(s.rows for s in router.stats()) or 1
        arms.append({
            "engines": int(k),
            "requests": len(futures),
            "served": len(futures) - shed,
            "shed": shed,
            "shed_rate": shed / len(futures),
            "decisions_per_s": (len(futures) - shed) / wall,
            "wall_s": wall,
            "per_engine_rows": [s.rows for s in router.stats()],
            "per_engine_row_share": [s.rows / total_rows
                                     for s in router.stats()],
            "per_engine_dispatches": [s.dispatches
                                      for s in router.stats()],
            "per_engine_occupancy": [s.occupancy
                                     for s in router.stats()],
            "per_engine_recompiles": router.per_engine_recompiles(),
        })
    return {
        "engine_counts": [int(k) for k in engine_counts],
        "rounds": rounds,
        "request_sizes": list(request_sizes),
        "deadline_s": deadline_s,
        "serialized_dispatch_cpu": bool(serialized),
        "caveat": ("CPU backend serializes device dispatch behind one "
                   "lock (XLA:CPU thread-safety) — decisions/s does not "
                   "scale with engines here; routing/occupancy/shed "
                   "accounting is what this measures"
                   if serialized else None),
        "arms": arms,
    }


def run_soak(server, pool: "list[tuple[Any, Any]]", *,
             duration_s: float = 6.0, rate_hz: float = 200.0,
             deadline_s: "float | None" = None, router=None,
             advisor=None, advisor_every_s: float = 0.5) -> dict:
    """Sustained-load soak through a RUNNING server (caller started the
    dispatchers): pace submissions at ``rate_hz`` for ``duration_s``,
    optionally attaching a per-request ``deadline_s`` (shedding active)
    and an autoscale loop (every ``advisor_every_s``: let ``advisor``
    vote — its tick refreshes the SLO gauges through the registry
    collector hook — and apply to ``router``). Reports
    first-half vs second-half p99 — the drift surface the soak-lite CI
    stage bounds (an unbounded queue or a leak shows up as second-half
    p99 runaway)."""
    from .batching import DeadlineSheddedError

    if advisor is not None and router is None:
        raise ValueError("autoscale soak needs the router to apply "
                         "advisor votes to")
    interval = 1.0 / float(rate_hz)
    futures = []
    cursor = 0
    resizes = 0
    t_start = time.perf_counter()
    next_t = t_start
    next_tick = t_start + advisor_every_s
    while time.perf_counter() - t_start < duration_s:
        obs, mask = pool[cursor % len(pool)]
        futures.append(server.submit(obs, mask, deadline_s=deadline_s))
        cursor += 1
        now = time.perf_counter()
        if advisor is not None and now >= next_tick:
            before = advisor.desired
            router.apply_autoscale(advisor)
            resizes += int(advisor.desired != before)
            next_tick += advisor_every_s
        next_t += interval
        sleep = next_t - time.perf_counter()
        if sleep > 0:
            time.sleep(sleep)
    lat_s: "list[float | None]" = []
    shed = 0
    for f in futures:
        try:
            lat_s.append(f.result(timeout=120).latency_s)
        except DeadlineSheddedError:
            shed += 1
            lat_s.append(None)
    wall = time.perf_counter() - t_start

    def p99_ms(xs):
        xs = [x for x in xs if x is not None]
        return (float(np.percentile(np.asarray(xs), 99) * 1e3)
                if xs else None)

    half = len(lat_s) // 2
    p99_a, p99_b = p99_ms(lat_s[:half]), p99_ms(lat_s[half:])
    out = {
        "requests": len(futures),
        "served": len(futures) - shed,
        "shed": shed,
        "shed_rate": shed / max(len(futures), 1),
        "duration_s": wall,
        "rate_hz": rate_hz,
        "deadline_s": deadline_s,
        "p99_first_half_ms": p99_a,
        "p99_second_half_ms": p99_b,
        "p99_drift": (p99_b / p99_a
                      if p99_a and p99_b and p99_a > 0 else None),
        "autoscale_resizes": resizes if advisor is not None else None,
    }
    if router is not None:
        out["per_engine_rows"] = [s.rows for s in router.stats()]
        out["per_engine_occupancy"] = [s.occupancy
                                       for s in router.stats()]
        out["per_engine_recompiles"] = router.per_engine_recompiles()
        out["engines_active"] = router.n_active
        out["serialized_dispatch_cpu"] = router.serialized_dispatch()
    return out


class StubEngine:
    """Zero-device-work engine for the arena's allocation gate:
    ``decide`` returns a view of ONE preallocated action buffer (never
    a fresh ndarray, never an alias of the caller's obs/mask — so the
    arena's zero-copy scatter needs no defensive copy and the
    steady-state allocation count is the data plane's alone)."""

    def __init__(self, max_bucket: int = 8):
        self.max_bucket = int(max_bucket)
        self._actions = np.zeros(self.max_bucket, dtype=np.int32)

    def bucket_for(self, n: int) -> int:
        from .batching import next_bucket
        return next_bucket(n, self.max_bucket)

    def decide(self, obs: Any, mask: Any, stall=None):
        n = int(np.asarray(jax.tree.leaves(obs)[0]).shape[0])
        return self._actions[:n], self.bucket_for(n)


class _AllocCounter:
    """Context manager counting calls to the numpy batch constructors
    the hot path must not touch in steady state (the same four the jsan
    ``alloc-in-hot-loop`` rule polices). Wraps the module-level
    functions, so every caller in-process is counted."""

    TRACKED = ("zeros", "empty", "concatenate", "stack")

    def __init__(self):
        self.calls = 0
        self._orig: dict = {}

    def __enter__(self):
        def counted(fn):
            def inner(*a, **k):
                self.calls += 1
                return fn(*a, **k)
            return inner
        for name in self.TRACKED:
            self._orig[name] = getattr(np, name)
            setattr(np, name, counted(self._orig[name]))
        return self

    def __exit__(self, *exc):
        for name, fn in self._orig.items():
            setattr(np, name, fn)
        self._orig.clear()
        return False


def fit_paced_gaps(fit, n: int, seed, rate_hz: float) -> np.ndarray:
    """Inter-arrival gaps carrying a fitted workload's arrival SHAPE at
    a chosen offered rate: realize one seeded window from ``fit``
    (:func:`~..traces.fit.gen_domain_window` — the same arrival process
    the simulator replays), take its inter-arrival gaps, and rescale
    them so the mean gap is exactly ``1/rate_hz``. The soak then pounds
    the server with the trace's burstiness, not a metronome — idle
    stretches and pile-ups included — while the offered load stays the
    configured number. Deterministic per (fit, seed)."""
    from ..traces.fit import gen_domain_window

    if n < 1:
        raise ValueError(f"need at least one gap, got n={n}")
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be positive, got {rate_hz}")
    win = gen_domain_window(fit, n_jobs=n + 1, seed=seed, n_gpus=8,
                            load=1.0)
    gaps = np.maximum(np.diff(win.submit.astype(np.float64)), 0.0)
    mean = float(gaps.mean())
    if mean <= 0:       # degenerate window (all-burst); fall back flat
        return np.full(n, 1.0 / rate_hz)
    return gaps * ((1.0 / rate_hz) / mean)


def _rss_bytes() -> "int | None":
    """Resident-set size from ``/proc/self/statm`` (no psutil dep);
    None where procfs is absent (non-Linux). Used by the chaos soak's
    heap-drift gate: a steady-state serving plane recycling arena slabs
    must not grow its RSS materially under sustained load + faults."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def run_chaos_soak(server, pool: "list[tuple[Any, Any]]", *, fit,
                   duration_s: float = 6.0, rate_hz: float = 150.0,
                   deadline_s: "float | None" = None, router=None,
                   seed: int = 0) -> dict:
    """:func:`run_soak` graduated to chaos: replay-paced load
    (:func:`fit_paced_gaps` — the fitted trace's arrival process, not a
    fixed interval) through a RUNNING dispatcher fleet while a
    :class:`~.router.ServeFaultInjector` (attached to the router by the
    caller) fails engines mid-run. Every future is awaited with a bound
    and bucketed into exactly one of served / shed / failed, so the
    report carries the conservation invariant directly::

        submitted == served + shed + failed      (failed must be 0:
        the retry hedge absorbs injected engine faults)

    plus the exactly-once counter cross-check (``registry_shed_total``
    must equal the shed futures actually observed) and the router's
    ejection/readmission/hedge story (:meth:`~.router.EngineRouter.
    fault_stats`).

    The pacing loop calls ``registry.collect()`` twice a second, so the
    SLO engine's burn windows advance DURING the fault window (a burn
    alert must fire while the bleeding happens, not at the post-mortem
    scrape), and after the last future resolves the soak keeps
    collecting until every SLO stops alerting (bounded) — the report's
    ``slo`` section shows the recovered budget."""
    from .batching import DeadlineSheddedError

    n_gaps = max(int(duration_s * rate_hz * 2) + 16, 1)
    gaps = fit_paced_gaps(fit, n_gaps, seed=(seed, 0xC7A05),
                          rate_hz=rate_hz)
    reg = server.registry
    rss_start = _rss_bytes()
    futures = []
    cursor = 0
    t_start = time.perf_counter()
    next_t = t_start
    # pre-incident baseline sample: burn is measured between samples,
    # so a fault that fires before the FIRST collect would be invisible
    # (baked into the initial cumulative reading) without this
    reg.collect()
    next_collect = t_start + 0.5
    while time.perf_counter() - t_start < duration_s:
        obs, mask = pool[cursor % len(pool)]
        futures.append(server.submit(obs, mask, deadline_s=deadline_s))
        next_t += gaps[cursor % len(gaps)]
        cursor += 1
        if time.perf_counter() >= next_collect:
            reg.collect()
            next_collect += 0.5
        sleep = next_t - time.perf_counter()
        if sleep > 0:
            time.sleep(sleep)
    lat_s: "list[float | None]" = []
    shed = 0
    failed = 0
    failure_kinds: dict[str, int] = {}
    for f in futures:
        try:
            lat_s.append(f.result(timeout=30).latency_s)
        except DeadlineSheddedError:
            shed += 1
            lat_s.append(None)
        except Exception as e:   # incl. a hung future's TimeoutError
            failed += 1
            kind = type(e).__name__
            failure_kinds[kind] = failure_kinds.get(kind, 0) + 1
            lat_s.append(None)
    wall = time.perf_counter() - t_start
    served = len(futures) - shed - failed

    # settle: keep the burn windows sliding until every SLO clears (the
    # 1s engine-health window un-trips ~1s after the last hedge, the 3s
    # budget window recovers shortly after), bounded so a genuinely
    # still-burning SLO reports alerting=True instead of hanging
    slo_status: dict = {}
    if getattr(server, "slo", None) is not None:
        settle_by = time.perf_counter() + 4.0
        while True:
            reg.collect()
            slo_status = server.slo.status()
            settled = not any(s["alerting"] for s in slo_status.values())
            # ...and let SHORT budget windows slide fully past the
            # incident, so the report shows the recovered budget rather
            # than the mid-bleed snapshot (long windows would outlast
            # the settle bound — leave those to the dashboards)
            settled = settled and all(
                s["budget_remaining"] >= 1.0
                for s in slo_status.values()
                if s["alerts_total"] and s["budget_window_s"] <= 3.0)
            if settled or time.perf_counter() >= settle_by:
                break
            time.sleep(0.2)

    def p99_ms(xs):
        xs = [x for x in xs if x is not None]
        return (float(np.percentile(np.asarray(xs), 99) * 1e3)
                if xs else None)

    half = len(lat_s) // 2
    p99_a, p99_b = p99_ms(lat_s[:half]), p99_ms(lat_s[half:])
    out = {
        "requests": len(futures),
        "served": served,
        "shed": shed,
        "failed": failed,
        "failure_kinds": failure_kinds,
        "conservation_ok": len(futures) == served + shed + failed,
        "registry_requests_total": int(
            reg.counter("serve_requests_total").value),
        "registry_shed_total": int(reg.counter("serve_shed_total").value),
        "shed_rate": shed / max(len(futures), 1),
        "duration_s": wall,
        "rate_hz": rate_hz,
        "arrival_fit": fit.name,
        "deadline_s": deadline_s,
        "p99_first_half_ms": p99_a,
        "p99_second_half_ms": p99_b,
        "p99_drift": (p99_b / p99_a
                      if p99_a and p99_b and p99_a > 0 else None),
        "slo": slo_status,
    }
    # heap-drift gate inputs: RSS before the first submit vs after the
    # last future resolved (all recycled slabs back in the ring)
    rss_end = _rss_bytes()
    out["rss_start_bytes"] = rss_start
    out["rss_end_bytes"] = rss_end
    out["rss_growth_bytes"] = (rss_end - rss_start
                               if rss_start is not None
                               and rss_end is not None else None)
    out["rss_growth_frac"] = ((rss_end - rss_start) / rss_start
                              if rss_start else None)
    if router is not None:
        out["fault_stats"] = router.fault_stats()
        out["per_engine_rows"] = [s.rows for s in router.stats()]
        out["per_engine_recompiles"] = router.per_engine_recompiles()
        out["engines_active"] = router.n_active
        out["serialized_dispatch_cpu"] = router.serialized_dispatch()
    return out
