"""The start-up account: where a process's time went before (and while)
its first iterations ran, kept by the program itself.

``setup_s`` is one of the benchmark's two end-to-end metrics and was the
one with no layer under it: the profile covers the window only, and the
only account of set-up was the harness's one sum over every program's
tracing, lowering and compiling, read from outside. This module is the
inside view, built on what was there (:meth:`.trace.Tracer.phase` is the
boundary primitive; ``analysis.sentinels.CompileCounter`` the one compile
listener) and always on: a record a set-up phase, a callback a compile
event, NOTHING an iteration. Importing it installs the listener
(``experiment`` does, so every build is heard from its first trace).

What :data:`ACCOUNT` keeps, all on ``time.monotonic()`` (the bus's
``mono``), from ``t0`` = the package's import (``rlgpuschedule_tpu.T0``):

== spans (name, start, end, parent = the span open on that thread when it
   began)
   ``import``   the module bodies that pay for jax, flax and optax,
                whichever an entry point reaches first (``utils``,
                ``configs``, ``experiment``), as the process paid them
   ``backend``  ``jax.devices()``: the TPU client's start. These two are
                stamped on the package itself (``EARLY_SPANS``), by
                modules that this one's imports would reach
   ``build``    ``Experiment.build`` / ``PopulationExperiment.build``, and
                under it ``build_source`` (load + validate the trace),
                ``build_windows`` (cut or generate the env windows),
                ``build_upload`` (``stack_traces``, the fault and domain
                schedules), ``build_policy``, ``build_carry``
                (``init_carry``), ``build_train_state`` (the jitted
                ``init`` and the optimizer's eager zeros), ``build_step``
                (``make_*_step`` + ``jax.jit`` + the mesh placement).
                These go through ``Tracer.phase``: each is also an
                ``rlsched:<name>`` profiler annotation, and with
                ``build(..., telemetry=tel)`` a bus span
   ``run``      one a CALL of a run loop (``Experiment.run``,
                ``PopulationExperiment.run``, the async runners'): start,
                end, iterations, and the loop's own sections (``step``,
                ``sync``, ``eval``, ``ckpt``, ``resample``: the totals of
                the ``SectionTimer`` a run without telemetry used to throw
                away; the summary's ``run_sections``), and ``metrics``:
                the last logged iteration's, as the call returned them in
                ``history[-1]`` (None where it logged none). They were on
                the host already; a token trunk's carry its path counters
                (``algos.ppo.MOE_COUNTERS``), so a reader of the account
                can tell which code the window ran. Account only: no
                annotation, no bus event
== compile intervals, by program (``ACCOUNT.compiles``): ``trace``,
   ``lower``, ``compile``, ``cache_load`` (a backend compile the
   persistent cache served), folded as they arrive into unions and a
   by-program table; the newest are held one by one
== counts: traces, lowerings, backend compiles, cache hits and misses,
   programs (distinct names), as they stand or as of any boundary
   (``summary(until=...)``)

:meth:`StartupAccount.summary` reduces it to EXCLUSIVE seconds that add up
to ``until - t0``: every instant inside a compile interval goes to it (a
cache load, else a compile, else tracing or lowering; nested traces are a
union, never a sum), every other instant to the deepest span that covers
it, else to ``unnamed_s``. A span's self time is therefore its duration
less what its children and the compile intervals inside it cover.

Bounded: a small record a build phase and a ``run`` call, the newest
:data:`MAX_SPANS` of them (and the first ``run`` call's, for
:func:`first_run_summary`); of the compile side one interval a top-level
compile and one row a program for good, and the newest
``sentinels.RECENT_EVENTS`` single events.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from typing import Any, Iterator

import rlgpuschedule_tpu

from ..analysis.sentinels import KINDS, CompileCounter
from ..utils.profiling import SectionTimer
from .trace import NULL_TRACER, Tracer, _union, tracer_of

TOP_PROGRAMS = 10
# the span records held: the newest (a process that calls ``run`` for days
# holds no more than this, some 4 MB with every record's metrics; an older
# record's seconds then read as ``unnamed_s``)
MAX_SPANS = 1024


class StartupAccount:
    """Process-wide, thread-safe, in memory. See the module docstring."""

    def __init__(self, t0: float, clock=time.monotonic):
        self.t0 = t0
        self.spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
        # the first ``run`` record, also once the deque has let it go
        self.first_run: dict | None = None
        self.compiles = CompileCounter()
        self.opened = 0     # records opened so far: the next one's index
        self._clock = clock
        self._lock = threading.Lock()
        self._local = threading.local()     # open spans, attached tracer

    # -- recording ----------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, name: str, start: float, **fields: Any) -> dict:
        stack = self._stack()
        rec = {"name": name, "start": start, "end": None,
               "parent": stack[-1]["index"] if stack else None, **fields}
        with self._lock:
            rec["index"] = self.opened
            self.opened += 1
            self.spans.append(rec)
            if name == "run" and self.first_run is None:
                self.first_run = rec
        return rec

    @contextlib.contextmanager
    def span(self, name: str, **fields: Any) -> Iterator[dict]:
        """One span on this thread, nested under the one open on it. The
        shape ``Tracer.phase`` wants of its ``sections``, so a build
        phase names its boundary once for the account, the profiler and
        the bus."""
        rec = self._open(name, self._clock(), **fields)
        stack = self._stack()
        stack.append(rec)
        try:
            yield rec
        finally:
            stack.pop()
            rec["end"] = self._clock()

    def add(self, name: str, start: float, end: float) -> None:
        """A span with its times given (an account made by hand)."""
        self._open(name, start)["end"] = end

    def _all_spans(self) -> list:
        """The recorded spans, and after them the package's early spans
        (``import``, ``backend``: stamped by modules below this one), each
        name's made disjoint (``experiment``'s body imports
        ``configs``')."""
        with self._lock:
            spans = [dict(s) for s in self.spans]
        early: dict[str, list] = {}
        for name, start, end in list(rlgpuschedule_tpu.EARLY_SPANS):
            early.setdefault(name, []).append((start, end))
        return spans + [
            {"name": name, "start": start, "end": end, "parent": None}
            for name, intervals in early.items()
            for start, end in _union(intervals)]

    @contextlib.contextmanager
    def building(self, telemetry=None) -> Iterator[None]:
        """The ``build`` span; the phases opened on this thread inside it
        are bus spans too where ``telemetry`` traces."""
        previous = getattr(self._local, "tracer", NULL_TRACER)
        self._local.tracer = tracer_of(telemetry)
        try:
            with self.phase("build"):
                yield
        finally:
            self._local.tracer = previous

    def phase(self, name: str):
        """A set-up phase: the account's span, the ``rlsched:<name>``
        annotation, and the bus span of the tracer :meth:`building`
        attached (none outside it)."""
        tracer: Tracer = getattr(self._local, "tracer", NULL_TRACER)
        return tracer.phase(self.span, name)

    def current_run(self) -> "dict | None":
        """The ``run`` record open on this thread."""
        for rec in reversed(self._stack()):
            if rec["name"] == "run":
                return rec
        return None

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> dict:
        """The account as plain data: every span (a run's sections as
        their totals) and the compile events held one by one."""
        spans = self._all_spans()
        for s in spans:
            if "sections" in s:
                s["sections"] = s["sections"].report()
        return {"t0": self.t0, "spans": spans,
                "events": list(self.compiles.events)}

    def summary(self, until: float | None = None) -> dict:
        """Exclusive seconds of ``[t0, until]`` (now, by default) by part:
        ``import_s``, ``backend_s``, ``build_s`` (by phase under
        ``build_by_phase``), ``trace_lower_s``, ``compile_s``,
        ``cache_load_s``, ``run_s`` (the run calls less the compile
        intervals inside them: executing and syncing), ``unnamed_s`` (under
        no span of the program's); they add up to ``until_s``. Beside
        them ``run_sections`` (the loops' own section totals over the run
        calls begun by ``until``: wall seconds, a first step's compiles
        included), the counts and the programs with most compile-side
        seconds, both as of ``until``. Spans open on two threads at once
        are each counted (no entry point opens them so)."""
        until = self._clock() if until is None else until
        comp = self.compiles
        out = {"until_s": until - self.t0, **comp.exclusive(self.t0, until)}
        # a span's wall and compile-side seconds inside [t0, until], and
        # what its children take of both
        own: list = []
        kids: dict[int, list] = {}
        sections: dict[str, float] = {}
        for rec in self._all_spans():
            lo = max(rec["start"], self.t0)
            hi = until if rec["end"] is None else min(rec["end"], until)
            if hi <= lo:
                continue
            wall, inside = hi - lo, comp.covered(lo, hi)
            own.append((rec, wall, inside))
            if rec["parent"] is not None:
                taken = kids.setdefault(rec["parent"], [0.0, 0.0])
                taken[0] += wall
                taken[1] += inside
            for name, secs in (rec["sections"].report().items()
                               if "sections" in rec else ()):
                sections[name] = sections.get(name, 0.0) + secs
        by_name: dict[str, float] = {}
        for rec, wall, inside in own:
            k_wall, k_inside = kids.get(rec.get("index"), (0.0, 0.0))
            by_name[rec["name"]] = (by_name.get(rec["name"], 0.0)
                                    + (wall - k_wall) - (inside - k_inside))
        build = {k: v for k, v in by_name.items() if k.startswith("build")}
        out.update(import_s=by_name.get("import", 0.0),
                   backend_s=by_name.get("backend", 0.0),
                   build_s=sum(build.values()), build_by_phase=build,
                   run_s=by_name.get("run", 0.0), run_sections=sections)
        out["unnamed_s"] = out["until_s"] - sum(
            out[k] for k in ("cache_load_s", "compile_s", "trace_lower_s",
                             "import_s", "backend_s", "build_s", "run_s"))
        programs = comp.programs(until=until)
        out["counts"] = dict(comp.counts(until), programs=len(programs))
        heaviest = sorted(programs.items(), reverse=True, key=lambda kv: sum(
            kv[1][k + "_s"] for k in KINDS))[:TOP_PROGRAMS]
        out["programs"] = [{"fun": name, **row} for name, row in heaviest]
        return out


ACCOUNT = StartupAccount(rlgpuschedule_tpu.T0)
ACCOUNT.compiles.__enter__()    # listens for the rest of the process


def recorded_build(fn):
    """A ``build``: the ``build`` span around it, its phases
    (:meth:`StartupAccount.phase`) bus spans of the ``telemetry`` it was
    handed by keyword."""
    @functools.wraps(fn)
    def build(*args, **kwargs):
        with ACCOUNT.building(kwargs.get("telemetry")):
            return fn(*args, **kwargs)
    return build


def recorded_run(fn):
    """A run loop's method: one ``run`` record a call (start, end,
    iterations and the last logged iteration's metrics from the summary
    it returns: already on the host, so no sync and nothing inside the
    loop). The loop takes its sections from :func:`sections_of`."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with ACCOUNT.span("run", iterations=None, sections=SectionTimer(),
                          metrics=None) as rec:
            out = fn(*args, **kwargs)
            rec["iterations"] = out.get("iterations")
            if out.get("history"):
                rec["metrics"] = dict(out["history"][-1])
            return out
    return run


def sections_of(telemetry) -> SectionTimer:
    """Where a run loop's phase seconds accumulate: the telemetry's timer
    (the ``iteration`` events' phases read it), else the open ``run``
    record's own."""
    if telemetry is not None:
        return telemetry.sections
    return ACCOUNT.current_run()["sections"]


def first_run_summary() -> "dict | None":
    """:meth:`StartupAccount.summary` up to the first run call's end (now,
    while it is open): from the process's start to the end of its first
    run call, by part. None before any run."""
    rec = ACCOUNT.first_run
    if rec is None:
        return None
    return rounded(ACCOUNT.summary(until=rec["end"]))


def rounded(summary: dict, digits: int = 6) -> dict:
    """A summary as an event or a JSON line carries it."""
    def r(v):
        if isinstance(v, float):
            return round(v, digits)
        if isinstance(v, dict):
            return {k: r(x) for k, x in v.items()}
        if isinstance(v, list):
            return [r(x) for x in v]
        return v
    return r(summary)
