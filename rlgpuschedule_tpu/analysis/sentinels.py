"""Runtime performance sentinels: the dynamic half of jsan.

The static rules (:mod:`.rules`) catch what local evidence can prove;
these two sentinels catch what it can't:

- :class:`CompileCounter` — counts XLA traces and backend compiles via
  ``jax.monitoring`` event listeners. The contract it enforces
  (tests/test_sentinels.py): the fused update step compiles **exactly
  once** across geometry-stable iterations. A shape-unstable argument,
  an unhashable closure capture, or a rebuilt function object all show
  up here as steady-state compiles — the recompile-per-step failure
  mode that erases a bench win without failing a test. It also keeps
  WHICH program each event belonged to and its seconds (jax 0.9 hands
  listeners ``fun_name``), and whether a backend compile was a load from
  the persistent cache: the one listener the start-up account
  (``obs.startup``), the production alarms and ``chip_smoke.py`` read.
- :func:`no_implicit_transfers` — ``jax.transfer_guard("disallow")``
  scoped as a context: inside it, any *implicit* host↔device transfer
  raises. Wrapped around a hot loop it proves the loop is device-
  resident (explicit ``jax.device_put``/``device_get`` remain allowed,
  so deliberate materialization at loop boundaries still works).

Both are cheap enough for the ``sanitize`` tier-1 subset — neither
re-executes programs the way ``jax_debug_nans`` does.
"""
from __future__ import annotations

import array
import bisect
import collections
import contextlib
import itertools
import sys
import threading
import time

import jax

# every XLA backend compile fires this duration event; every jaxpr trace
# fires the trace event even when the *persistent* compilation cache
# serves the executable (conftest enables that cache, so a warm CI run
# may legitimately see traces without backend compiles — steady-state
# assertions must require BOTH to be zero, which assert_no_recompiles
# does)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# fired INSIDE a backend-compile interval, on its thread: the persistent
# cache served the executable (hit), or took a freshly compiled one (miss)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# what an interval was spent on; a backend-compile interval inside which
# the cache hit is a ``cache_load``, else a ``compile``
KINDS = ("trace", "lower", "compile", "cache_load")
_KIND_OF = {TRACE_EVENT: "trace", LOWER_EVENT: "lower"}
_PLURAL = {"trace": "traces", "lower": "lowerings", "compile": "compiles",
           "cache_load": "cache_loads"}
_SECONDS = {kind: kind + "_s" for kind in KINDS}
# the events a counter holds one by one: the newest; older ones stay in
# its sums (a process that compiles for days holds no more than this, some
# 30 MB; the benchmark's largest cell makes 45,000 in a whole run)
RECENT_EVENTS = 1 << 18


class RecompileSentinelError(AssertionError):
    """A region that must be compile-free traced or compiled."""


def program_name(fun_name: str) -> str:
    """One name for a program's three events: jax names the trace by the
    function (``train_step``) and the lowering and the backend compile by
    the module (``jit(train_step)``)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class Coverage:
    """The UNION of intervals that arrive in the order of their ends (as a
    listener's do: it is called as an interval closes), kept disjoint with
    a running length, so the seconds of any ``[lo, hi]`` that they cover
    are two bisections. An inner ``jit`` traced inside an outer one closes
    first and is swallowed when the outer interval arrives: the union
    holds one interval a top-level compile, not one an event."""

    def __init__(self):
        self._lo = array.array("d")
        self._hi = array.array("d")
        self._cum = array.array("d")    # length of the intervals up to each

    def add(self, lo: float, hi: float) -> None:
        if self._hi and hi < self._hi[-1]:
            raise ValueError("intervals arrive in the order of their ends")
        while self._hi and self._hi[-1] >= lo:
            lo = min(lo, self._lo.pop())
            self._hi.pop()
            self._cum.pop()
        if hi > lo:
            self._lo.append(lo)
            self._hi.append(hi)
            self._cum.append((self._cum[-1] if self._cum else 0.0) + hi - lo)

    def seconds(self, lo: float, hi: float) -> float:
        """Seconds of ``[lo, hi]`` inside the union."""
        i = bisect.bisect_right(self._hi, lo)   # the first that ends after lo
        j = bisect.bisect_left(self._lo, hi)    # the first that starts after
        if i >= j:
            return 0.0
        inside = self._cum[j - 1] - (self._cum[i - 1] if i else 0.0)
        return (inside - max(lo - self._lo[i], 0.0)
                - max(self._hi[j - 1] - hi, 0.0))


def _empty_row() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_load_s": 0.0, "traces": 0, "lowerings": 0,
            "compiles": 0, "cache_loads": 0}


def _fold(table: dict, event: tuple, sign: int = 1) -> None:
    """Add an event to (or, ``sign=-1``, take it out of) a by-program
    table."""
    start, end, kind, name = event
    row = table.get(name)
    if row is None:
        row = table[name] = _empty_row()
    row[_SECONDS[kind]] += sign * (end - start)
    row[_PLURAL[kind]] += sign


class CompileCounter:
    """Context manager recording jax's compile activity in its scope:
    what was traced, lowered, compiled or loaded from the persistent
    cache, BY PROGRAM and with its seconds.

    Usage (the geometry-stable contract)::

        step(state, batch)                 # warmup: compiles once
        with CompileCounter() as c:
            for _ in range(n):
                state, _ = step(state, batch)
        assert c.total == 0, c.events

    jax hands every duration listener the program's ``fun_name``, and a
    listener is called as the interval closes, so an event is
    ``(start, end, kind, program)`` on ``time.monotonic()`` (``end`` is
    the clock at the callback, ``start = end - duration``). Each one is
    folded, as it arrives, into what is read: the counters, the by-program
    table (:meth:`programs`: sums) and the unions (:meth:`covered`,
    :meth:`exclusive`: an inner ``jit`` traced inside an outer one lies
    inside the outer's interval, so a sum would count it twice).
    ``events`` holds the newest :data:`RECENT_EVENTS` one by one, for
    :meth:`programs_since` (what one dispatch compiled) and for reading
    the table and the counters as of an earlier instant (``until``).

    The events are the process's (jax.monitoring knows no scope), so
    keep input construction — ``jnp.ones``, key splits, anything that
    dispatches its own tiny program — outside the scope. ONE counter is
    installed for the process's life by ``obs.startup`` (the start-up
    account; the production alarms and ``chip_smoke.py`` read that one by
    a mark before and after); a scoped one costs a second callback a
    compile event while it is open. Nothing is called where nothing
    compiles.
    """

    def __init__(self):
        self.backend_compiles = 0
        self.traces = 0
        self.lowerings = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.events: collections.deque = collections.deque(
            maxlen=RECENT_EVENTS)
        self._missed: collections.deque = collections.deque(
            maxlen=RECENT_EVENTS)        # when each miss was written
        self._table: dict[str, dict] = {}
        # nested unions, so that the differences are exclusive seconds:
        # cache loads; those and compiles; those and tracing and lowering
        self._loads = Coverage()
        self._backend = Coverage()
        self._all = Coverage()
        self._lock = threading.Lock()
        self._hit = threading.local()    # a hit waits for its interval

    @property
    def total(self) -> int:
        return self.backend_compiles + self.traces

    @property
    def n_events(self) -> int:
        """Events recorded so far: a mark for :meth:`programs_since`."""
        return self.traces + self.lowerings + self.backend_compiles

    # -- the two listeners --------------------------------------------------
    def _duration(self, event: str, duration: float, fun_name: str = "",
                  **_kw) -> None:
        kind = _KIND_OF.get(event)
        if kind is None:
            if event != BACKEND_COMPILE_EVENT:
                return
            kind = ("cache_load" if getattr(self._hit, "seen", False)
                    else "compile")
            self._hit.seen = False
        # one string a program, however many events
        self.record(duration, kind, sys.intern(program_name(fun_name)))

    def record(self, duration: float, kind: str, program: str,
               end: float | None = None) -> None:
        """An interval of ``kind`` that closes now (at ``end``, for an
        account made by hand: in the order of the ends)."""
        with self._lock:
            if end is None:     # under the lock: the ends arrive in order
                end = time.monotonic()
            start = end - duration
            if kind == "trace":
                self.traces += 1
            elif kind == "lower":
                self.lowerings += 1
            else:
                self.backend_compiles += 1
                self._backend.add(start, end)
                if kind == "cache_load":
                    self.cache_hits += 1
                    self._loads.add(start, end)
            self._all.add(start, end)
            event = (start, end, kind, program)
            _fold(self._table, event)
            self.events.append(event)

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:    # counted as its interval closes
            self._hit.seen = True
        elif event == CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1
                self._missed.append(time.monotonic())

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    # -- reading ------------------------------------------------------------
    def _after(self, until: float) -> list:
        """The held events that closed after ``until`` (call under the
        lock). Where more than :data:`RECENT_EVENTS` did, the oldest of
        them are no longer held, and a reading "as of ``until``" is one
        as of the oldest event held."""
        after = []
        for event in reversed(self.events):
            if event[1] <= until:
                break
            after.append(event)
        return after

    def counts(self, until: float | None = None) -> dict:
        """The counters (names as the benchmark's line has them): as they
        stand, or over what had closed by ``until``."""
        with self._lock:
            out = {"traces": self.traces, "lowerings": self.lowerings,
                   "backend_compiles": self.backend_compiles,
                   "cache_hits": self.cache_hits,
                   "cache_misses": self.cache_misses}
            if until is None:
                return out
            for _, _, kind, _ in self._after(until):
                if kind == "trace":
                    out["traces"] -= 1
                elif kind == "lower":
                    out["lowerings"] -= 1
                else:
                    out["backend_compiles"] -= 1
                    out["cache_hits"] -= kind == "cache_load"
            out["cache_misses"] -= sum(t > until for t in self._missed)
        return out

    def programs(self, until: float | None = None) -> dict:
        """``{program: {trace_s, lower_s, compile_s, cache_load_s,
        traces, lowerings, compiles, cache_loads}}``: SUMS by program (a
        program's own events do not nest in each other), over the
        process's life or over what had closed by ``until``."""
        with self._lock:
            table = {name: dict(row) for name, row in self._table.items()}
            after = [] if until is None else self._after(until)
        for event in after:
            _fold(table, event, -1)
        return {name: row for name, row in table.items()
                if any(row[_PLURAL[k]] for k in KINDS)}

    def programs_since(self, mark: int) -> dict:
        """:meth:`programs` over the events recorded since ``n_events``
        read ``mark`` (the newest :data:`RECENT_EVENTS` of them)."""
        with self._lock:
            skip = max(mark - (self.n_events - len(self.events)), 0)
            events = list(itertools.islice(self.events, skip, None))
        table: dict[str, dict] = {}
        for event in events:
            _fold(table, event)
        return table

    def covered(self, lo: float, hi: float) -> float:
        """Seconds of ``[lo, hi]`` inside a trace, lowering, compile or
        cache-load interval of any thread."""
        with self._lock:
            return self._all.seconds(lo, hi)

    def exclusive(self, lo: float, hi: float) -> dict:
        """:meth:`covered`, split so that every instant counts once:
        ``cache_load_s``, then ``compile_s``, then ``trace_lower_s`` (an
        eager compile inside a trace is the compile's)."""
        with self._lock:
            loads = self._loads.seconds(lo, hi)
            backend = self._backend.seconds(lo, hi)
            return {"cache_load_s": loads, "compile_s": backend - loads,
                    "trace_lower_s": self._all.seconds(lo, hi) - backend}


@contextlib.contextmanager
def assert_no_recompiles(what: str = "region"):
    """Assert a region neither traces nor compiles (post-warmup steady
    state). Raises :class:`RecompileSentinelError` naming the events."""
    with CompileCounter() as counter:
        yield counter
    if counter.total > 0:
        raise RecompileSentinelError(
            f"{what} expected zero compilation activity but saw "
            f"{counter.traces} trace(s) and {counter.backend_compiles} "
            f"backend compile(s): a geometry-stable hot loop is "
            f"recompiling (shape-unstable args, rebuilt function object, "
            f"or unhashable static capture)")


def no_implicit_transfers():
    """``jax.transfer_guard("disallow")`` as a readable name: inside,
    implicit host↔device transfers raise; explicit device_put/device_get
    stay legal. Wrap hot loops in perf/sanitize tests to prove device
    residency."""
    return jax.transfer_guard("disallow")
