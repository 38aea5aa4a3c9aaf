"""Span-tracing flight recorder on the event bus (Perfetto-ready).

The event bus records *points* ("this happened at t"); this module
records *extents with causality*: a :class:`Tracer` opens nestable,
thread-aware spans (``with tracer.span("actor", iteration=i):``) that
land on the SAME JSONL stream as every other event — paired
``span_begin`` / ``span_end`` records whose track is ``(rank, thread)``.
Because spans ride the bus, they merge, skew-correct
(:mod:`.skew`) and post-mortem (:mod:`.report`) exactly like any other
event, and one exporter (:func:`to_chrome_trace`) turns any run into a
Chrome-trace JSON that Perfetto / ``chrome://tracing`` opens directly.

Design constraints, in order:

- **Zero host syncs.** Span emission touches host clocks and a file
  only — never a device value. The device_get-counting test in
  tests/test_obs.py runs with tracing ON and still counts exactly one
  batched ``device_get`` per *logged* iteration.
- **One clock with the device.** Every span entry point — enabled or
  not, thread track or lane — also opens a
  ``jax.profiler.TraceAnnotation`` named ``rlsched:<span>``
  (:data:`.scopes.ANNOTATION_PREFIX`), so a profile of the normal path
  (``--profile-dir`` with no ``--obs-dir``) shows the host's phases on
  the profiler's own timeline, where an idle gap of the device can be
  laid against them. The profiler being active is the only switch;
  with it off an annotation is a ~0.3 us C++ no-op. No attributes go
  to the annotation (an attribute costs more than the span on the
  serve path).
- **Near-zero overhead when disabled.** ``span()`` on a disabled tracer
  returns the bare annotation — no bus write, no lock, no Python-level
  context. Run loops thread a :data:`NULL_TRACER` when no telemetry is
  attached, so the hot path never branches on ``None``.
- **Thread-aware.** The async engine's actor thread and the learner
  (caller) thread emit on one rank's bus concurrently; the bus write is
  serialized by :class:`.events.EventBus`'s emit lock, and each thread
  gets a stable small ``tid`` so stack discipline (B/E pairing) holds
  *per track*, which is exactly the Chrome trace format's contract.

A crash mid-span leaves a ``span_begin`` with no ``span_end`` (a *torn*
span): :func:`build_span_tree` renders it as an open span (counted,
flagged) instead of corrupting the tree, and :func:`to_chrome_trace`
closes it at the track's last timestamp with ``"torn": true``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterable, Iterator

from jax.profiler import TraceAnnotation

from .events import RESERVED_FIELDS, EventBus, merge_events
from .scopes import ANNOTATION_PREFIX

# the bus kinds the tracer owns
SPAN_BEGIN = "span_begin"
SPAN_END = "span_end"
SPAN_POINT = "span_point"
SPAN_KINDS = (SPAN_BEGIN, SPAN_END, SPAN_POINT)


def _annotation(name: str) -> TraceAnnotation:
    """The span as the profiler's trace shows it: ``rlsched:<name>``."""
    return TraceAnnotation(ANNOTATION_PREFIX + name)


class _Span:
    """One live span of an enabled tracer or lane (``owner``): the
    profiler's annotation, then begin on enter; end on exit. Exceptions
    propagate (the end event still lands — a failed span is still an
    extent)."""

    __slots__ = ("_owner", "_name", "_attrs", "_annotation")

    def __init__(self, owner: "Tracer | TracerLane", name: str,
                 attrs: dict):
        self._owner = owner
        self._name = name
        self._attrs = attrs
        self._annotation = _annotation(name)

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self._owner._begin(self._name, self._attrs)
        return self

    def __exit__(self, *exc) -> None:
        self._owner._end(self._name)
        self._annotation.__exit__(*exc)


class Tracer:
    """Thread-aware span emitter over one rank's :class:`EventBus`.

    >>> tracer = Tracer(bus, enabled=True)
    >>> with tracer.span("iteration", iteration=3):
    ...     with tracer.span("step"):
    ...         ...

    ``tid`` is a small per-process thread index (0 = first emitting
    thread), stamped on every span event so the merged timeline keeps
    one B/E stack per ``(rank, tid)`` track; the thread's *name* rides
    the begin event for Perfetto track labels. Attrs must be
    JSON-serializable and are carried under one ``attrs`` key so they
    can never shadow the bus's stamp fields.
    """

    def __init__(self, bus: EventBus | None, enabled: bool = True):
        self.bus = bus
        self.enabled = bool(enabled) and bus is not None
        self._lock = threading.Lock()          # protects _tids only
        self._tids: dict[int, int] = {}
        self._local = threading.local()

    def _track(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _depth(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs: Any) -> Any:
        """Context manager for one span: always the profiler's
        ``rlsched:<name>`` annotation, and the bus's begin/end pair when
        the tracer is enabled."""
        if not self.enabled:
            return _annotation(name)
        return _Span(self, name, attrs)

    @contextlib.contextmanager
    def phase(self, sections, name: str, *, span: str | None = None,
              meter=None, **attrs: Any) -> Iterator[None]:
        """One boundary of a run loop or of set-up, named once: its wall
        time goes to ``sections(name)`` (a run loop's ``SectionTimer``,
        which ``RunTelemetry._section_delta`` and the report's phase
        table read; for a set-up phase the start-up account's
        ``StartupAccount.span``), it is a span (named ``span`` where the
        stream's name for the boundary differs from the section's), and
        with ``meter`` (an ``OverlapMeter``) it is that meter's busy lane
        ``name``."""
        with sections(name), self.span(span or name, **attrs):
            if meter is None:
                yield
            else:
                with meter.span(name):
                    yield

    def instant(self, name: str, **attrs: Any) -> None:
        """A zero-duration mark on this thread's track (Chrome ``i``
        event) — e.g. a serve request's enqueue point."""
        if not self.enabled:
            return
        assert self.bus is not None
        self.bus.emit(SPAN_POINT, span=name, tid=self._track(),
                      **({"attrs": attrs} if attrs else {}))

    def _begin(self, name: str, attrs: dict) -> None:
        assert self.bus is not None
        stack = self._depth()
        self.bus.emit(SPAN_BEGIN, span=name, tid=self._track(),
                      depth=len(stack),
                      thread=threading.current_thread().name,
                      **({"attrs": attrs} if attrs else {}))
        stack.append(name)

    def _end(self, name: str) -> None:
        assert self.bus is not None
        stack = self._depth()
        if stack and stack[-1] == name:
            stack.pop()
        self.bus.emit(SPAN_END, span=name, tid=self._track(),
                      depth=len(stack))

    def lane(self, label: str) -> "TracerLane":
        """A named VIRTUAL track on this tracer — a dedicated ``tid``
        that is not any OS thread's, labeled ``label`` in Perfetto.

        The router gives every inference engine its own lane (PR 13):
        engine spans (``pad``/``dispatch``) land on per-engine tracks,
        so a routed timeline shows which chip served which batch even
        though the dispatching happens from whichever pump thread won
        the request — exactly the track-per-resource (not
        track-per-thread) layout GPU rows use in Chrome traces. Each
        call returns a NEW lane (one per engine, allocated at router
        construction, never per dispatch — tids must stay stable).
        Disabled tracers return the shared no-op lane."""
        if not self.enabled:
            return NULL_LANE
        with self._lock:
            # virtual lanes share the tid space with real threads; the
            # key can never collide with threading.get_ident() values
            tid = len(self._tids)
            self._tids[("lane", label, tid)] = tid
        return TracerLane(self, label, tid)


class TracerLane:
    """One virtual track of a :class:`Tracer` (see :meth:`Tracer.lane`).

    Mirrors the ``span``/``instant`` API; B/E pairing discipline holds
    per lane via the lane's own depth stack (lock-guarded — concurrent
    pump threads may dispatch on one engine's lane under queue
    pressure)."""

    def __init__(self, tracer: Tracer, label: str, tid: int):
        self._tracer = tracer
        self.label = label
        self.tid = tid
        self._stack: list[str] = []
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self._tracer.enabled

    def span(self, name: str, **attrs: Any) -> Any:
        if not self._tracer.enabled:
            return _annotation(name)
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs: Any) -> None:
        if not self._tracer.enabled:
            return
        assert self._tracer.bus is not None
        self._tracer.bus.emit(SPAN_POINT, span=name, tid=self.tid,
                              **({"attrs": attrs} if attrs else {}))

    def _begin(self, name: str, attrs: dict) -> None:
        assert self._tracer.bus is not None
        with self._lock:
            depth = len(self._stack)
            self._stack.append(name)
        self._tracer.bus.emit(SPAN_BEGIN, span=name, tid=self.tid,
                              depth=depth, thread=self.label,
                              **({"attrs": attrs} if attrs else {}))

    def _end(self, name: str) -> None:
        assert self._tracer.bus is not None
        with self._lock:
            if self._stack and self._stack[-1] == name:
                self._stack.pop()
            depth = len(self._stack)
        self._tracer.bus.emit(SPAN_END, span=name, tid=self.tid,
                              depth=depth)


class _NullLane:
    """Shared no-op lane for disabled tracers."""

    __slots__ = ()
    enabled = False
    label = ""
    tid = 0

    def span(self, name: str, **attrs: Any) -> Any:
        return _annotation(name)

    def instant(self, name: str, **attrs: Any) -> None:
        pass


NULL_LANE = _NullLane()


# the always-available disabled tracer: run loops hold it when no
# telemetry (or no --trace) is attached, so call sites never branch
NULL_TRACER = Tracer(None, enabled=False)


def tracer_of(telemetry: Any) -> Tracer:
    """The run loops' one accessor: ``telemetry.tracer`` when present,
    :data:`NULL_TRACER` otherwise (bare runs, legacy fakes)."""
    t = getattr(telemetry, "tracer", None)
    return t if isinstance(t, Tracer) else NULL_TRACER


# -- post-processing: span tree --------------------------------------------

def build_span_tree(events: Iterable[dict]) -> list[dict]:
    """Aggregate span events into a preorder tree of phase rows.

    Each row: ``{"path": "iteration/step", "name", "depth", "count",
    "total_s", "self_s", "open"}`` — ``self_s`` is total minus child
    time, ``open`` counts torn spans (begin, no end), which are closed
    at their track's last seen timestamp instead of corrupting the
    tree. Pairing is per ``(rank, tid)`` track, so concurrent threads
    cannot steal each other's ends.
    """
    nodes: dict[tuple, dict] = {}
    stacks: dict[tuple, list] = {}     # track -> [(path, t_begin), ...]
    last_ts: dict[tuple, float] = {}

    def node(path: tuple) -> dict:
        n = nodes.get(path)
        if n is None:
            n = nodes[path] = {"path": "/".join(path), "name": path[-1],
                               "depth": len(path) - 1, "count": 0,
                               "total_s": 0.0, "child_s": 0.0, "open": 0}
        return n

    def close(track: tuple, path: tuple, t0: float, t1: float,
              torn: bool) -> None:
        n = node(path)
        n["count"] += 1
        n["total_s"] += max(t1 - t0, 0.0)
        if torn:
            n["open"] += 1
        if len(path) > 1:
            node(path[:-1])["child_s"] += max(t1 - t0, 0.0)

    for e in merge_events(events):
        kind = e.get("kind")
        if kind not in (SPAN_BEGIN, SPAN_END) or "mono" not in e:
            continue
        track = (e.get("rank", 0), e.get("tid", 0))
        ts = e["mono"]
        last_ts[track] = ts
        stack = stacks.setdefault(track, [])
        if kind == SPAN_BEGIN:
            parent = stack[-1][0] if stack else ()
            stack.append((parent + (str(e.get("span")),), ts))
        else:
            # pop to the matching name: a torn INNER span is closed at
            # the outer end's timestamp rather than poisoning the stack;
            # an end whose begin was lost entirely is ignored
            name = str(e.get("span"))
            if not any(path[-1] == name for path, _ in stack):
                continue
            while stack:
                path, t0 = stack.pop()
                if path[-1] == name:
                    close(track, path, t0, ts, torn=False)
                    break
                close(track, path, t0, ts, torn=True)
    for track, stack in stacks.items():
        t1 = last_ts.get(track, 0.0)
        while stack:                       # crash mid-span: open spans
            path, t0 = stack.pop()
            close(track, path, t0, t1, torn=True)

    out = [nodes[p] for p in sorted(nodes)]
    for n in out:
        n["total_s"] = round(n["total_s"], 6)
        n["self_s"] = round(n["total_s"] - n.pop("child_s"), 6)
    return out


# -- post-processing: measured async overlap -------------------------------

def _lane_intervals(events: Iterable[dict],
                    lanes: tuple[str, ...]) -> dict[str, list]:
    opened: dict[tuple, float] = {}
    iv: dict[str, list] = {lane: [] for lane in lanes}
    for e in merge_events(events):
        name = e.get("span")
        if e.get("kind") not in (SPAN_BEGIN, SPAN_END) or name not in iv:
            continue
        key = (e.get("rank", 0), e.get("tid", 0), name)
        if e["kind"] == SPAN_BEGIN:
            opened[key] = e.get("mono", 0.0)
        elif key in opened:
            iv[name].append((opened.pop(key), e.get("mono", 0.0)))
    return iv


def _union(intervals: list) -> list:
    merged: list = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _total(intervals: list) -> float:
    return sum(hi - lo for lo, hi in intervals)


def async_overlap_summary(events: Iterable[dict]) -> dict | None:
    """Measured actor/learner occupancy from the span timeline — the
    number PR 8 could only project from phase sums. Over the window
    spanned by actor∪learner spans: ``busy`` is the union of the two
    lanes' spans, ``idle = window - busy``, ``concurrent`` the lanes'
    intersection, and ``async_overlap_measured = 1 - idle/window`` (the
    occupancy of the actor∪learner timeline). None when either lane
    recorded no spans (not an async traced run)."""
    iv = _lane_intervals(events, ("actor", "learner"))
    if not iv["actor"] or not iv["learner"]:
        return None
    actor, learner = _union(iv["actor"]), _union(iv["learner"])
    both = _union(actor + learner)
    window = (max(hi for _, hi in both) - min(lo for lo, _ in both))
    busy = _total(both)
    concurrent = _total(actor) + _total(learner) - busy
    idle = max(window - busy, 0.0)
    return {
        "async_overlap_measured": round(1.0 - idle / window, 6)
        if window > 0 else 1.0,
        "window_s": round(window, 6),
        "actor_busy_s": round(_total(actor), 6),
        "learner_busy_s": round(_total(learner), 6),
        "concurrent_s": round(concurrent, 6),
        "idle_s": round(idle, 6),
    }


# -- Chrome trace export ---------------------------------------------------

def to_chrome_trace(events: Iterable[dict]) -> dict:
    """Chrome Trace Event Format JSON (the Perfetto/chrome://tracing
    lingua franca): spans become paired ``B``/``E`` duration events on
    ``pid=rank, tid=thread`` tracks, ``span_point`` marks and every
    non-span bus event become ``i`` instants, and metadata events name
    each rank/thread. Timestamps are the (possibly skew-corrected)
    ``mono`` clock in microseconds. Torn spans are closed at their
    track's last timestamp with ``args.torn = true``."""
    trace: list[dict] = []
    stacks: dict[tuple, list] = {}
    last_ts: dict[tuple, float] = {}
    named_procs: set = set()
    named_threads: set = set()
    for e in merge_events(events):
        if "mono" not in e:
            continue
        kind = e.get("kind")
        pid = e.get("rank", 0)
        ts = e["mono"] * 1e6
        if pid not in named_procs:
            named_procs.add(pid)
            trace.append({"ph": "M", "name": "process_name", "pid": pid,
                          "tid": 0, "args": {"name": f"rank {pid}"}})
        if kind in (SPAN_BEGIN, SPAN_END, SPAN_POINT):
            tid = e.get("tid", 0)
            track = (pid, tid)
            last_ts[track] = ts
            if e.get("thread") and track not in named_threads:
                named_threads.add(track)
                trace.append({"ph": "M", "name": "thread_name",
                              "pid": pid, "tid": tid,
                              "args": {"name": e["thread"]}})
            name = str(e.get("span"))
            if kind == SPAN_BEGIN:
                stacks.setdefault(track, []).append(name)
                trace.append({"ph": "B", "name": name, "cat": "span",
                              "pid": pid, "tid": tid, "ts": ts,
                              "args": e.get("attrs") or {}})
            elif kind == SPAN_END:
                stack = stacks.get(track) or []
                if not stack:
                    continue           # torn end (begin lost): drop
                stack.pop()
                trace.append({"ph": "E", "name": name, "cat": "span",
                              "pid": pid, "tid": tid, "ts": ts})
            else:
                trace.append({"ph": "i", "name": name, "cat": "span",
                              "pid": pid, "tid": tid, "ts": ts, "s": "t",
                              "args": e.get("attrs") or {}})
        else:
            args = {k: v for k, v in e.items()
                    if k not in RESERVED_FIELDS}
            trace.append({"ph": "i", "name": str(kind), "cat": "event",
                          "pid": pid, "tid": 0, "ts": ts, "s": "p",
                          "args": args})
    for (pid, tid), stack in stacks.items():
        ts = last_ts.get((pid, tid), 0.0)
        while stack:                   # close torn spans at track end
            trace.append({"ph": "E", "name": stack.pop(), "cat": "span",
                          "pid": pid, "tid": tid, "ts": ts,
                          "args": {"torn": True}})
    return {"traceEvents": trace, "displayTimeUnit": "ms"}
