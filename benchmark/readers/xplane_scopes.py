"""What the scope and span readers share: find the traced window's own
xplane, read it once, and reduce it by the program's own names.

The probe carries the reduced trace but not the file, so the newest
``.benchmark_trace/*/plugins/profile/*/*.xplane.pb`` is taken (``run.py``
clears the cell's directory before each traced run). Read once into
``probe["cache"]``. Where the trace has no device plane (``--rehearse-cpu``)
or the program has no scopes or spans (a commit before ISSUE 27), every
reader here returns ``None`` and its metric is left out.

**Device side.** One event per executed operation on each device plane's
``XLA Ops`` line (``jax.profiler.ProfileData``, as ``trace_reduce`` reads
it). Where the scope path comes from on this jax (0.9.0), looked at by
hand in PR 27's first traced chip run: NOT the event's own stats
(``device_offset_ps``, ``device_duration_ps`` and a time scale, nothing
else), and the raw xplane has no name-scope line (that one is made by the
profile viewer). It is the ``tf_op`` stat of the event's METADATA
(``XEventMetadata.stats``), which holds the HLO ``op_name``
(``jit(train_step)/rollout/while/body/closed_call/env_step/vmap(observe)/
.../gather:``) beside ``source`` (file:line). ``ProfileData`` does not
show metadata stats, so :func:`op_metadata` reads those two maps off the
file's protobuf wire format itself (four message types, no schema
package; tensorflow's ``xplane_pb2`` would work and costs an ``import
tensorflow``), and events are joined to them by name. The components are
stripped of the wrappers a transformation puts around a name
(``vmap(observe)`` -> ``observe``). An operation's time is its SELF time
(``trace_reduce.self_times``: its span minus the spans nested in it), so
a ``while`` counts its own overhead, not its body's. A scope's time is
the sum over operations whose path holds the scope's own path as a
subsequence, over the device planes, over the iterations in the window
(the count of ``rlsched:train_iteration`` steps on the host plane; 1
where there is none).

**Host side.** The host plane's events named ``rlsched:<span>``: the obs
tracer's spans (``obs/trace.py``) on the profiler's clock. A device idle
gap is given to the innermost span over it: of the spans that cover more
than half of the gap, the shortest.
"""
from __future__ import annotations

import glob
import os
import re
import statistics

from benchmark.common import ROOT, log
from benchmark.trace_reduce import (DEVICE_PREFIX, OPS_LINE, self_times,
                                    union_and_gaps)

PREFIX = "rlsched:"
ITERATION = PREFIX + "train_iteration"
# the stats of a device event's metadata that are read: the HLO op_name
# and the source line
OP_NAME_STAT, SOURCE_STAT = "tf_op", "source"
# the program's scope tree (rlgpuschedule_tpu/obs/scopes.py TREE);
# benchmark/tests/test_scope_readers.py holds the two equal
TREE = (
    ("rollout",),
    ("rollout", "policy_forward"),
    ("rollout", "env_step"),
    ("rollout", "env_step", "sim_step"),
    ("rollout", "env_step", "sim_step", "sim_queue"),
    ("rollout", "env_step", "sim_step", "sim_place"),
    ("rollout", "env_step", "sim_step", "sim_advance"),
    ("rollout", "env_step", "sim_step", "sim_select"),
    ("rollout", "env_step", "reward"),
    ("rollout", "env_step", "observe"),
    ("rollout", "env_step", "auto_reset"),
    ("advantage",),
    ("update",),
    ("update", "shuffle"),
    ("update", "loss_grad"),
    ("update", "apply"),
)
_SCOPES = frozenset(name for path in TREE for name in path)


def newest_xplane() -> "str | None":
    found = glob.glob(os.path.join(ROOT, ".benchmark_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def scope_path(op_name: str) -> tuple:
    """The tree's scope names an ``op_name`` path carries, outermost
    first: ``.../rollout/while/body/env_step/vmap(observe)/gather`` ->
    ``("rollout", "env_step", "observe")``."""
    out = []
    for component in op_name.split("/"):
        words = re.findall(r"[\w.]+", component)
        if words and words[-1] in _SCOPES:
            out.append(words[-1])
    return tuple(out)


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield tag >> 3, value


def _map_entry(buf) -> tuple:
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def op_metadata(path: str) -> dict:
    """``{plane: {event name: (op_name, source)}}`` for the device planes,
    from ``XSpace.planes[].event_metadata[].stats`` (tsl's
    ``xplane.proto``: XSpace.planes = 1; XPlane.name = 2, .event_metadata
    = 4, .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, v in _fields(plane):
            if field == 2:
                name = bytes(v).decode()
            elif field == 4:
                events.append(_map_entry(v)[1])
            elif field == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for f, x in _fields(meta) if f == 2),
                    "")
        if not name.startswith(DEVICE_PREFIX):
            continue
        wanted = {k for k, n in stat_names.items()
                  if n in (OP_NAME_STAT, SOURCE_STAT)}
        table = out.setdefault(name, {})
        for meta in events:
            ev_name, found = "", {}
            for field, v in _fields(meta):
                if field == 2:
                    ev_name = bytes(v).decode()
                elif field == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in wanted and 5 in stat:
                        found[stat_names[stat[1]]] = bytes(stat[5]).decode()
            table[ev_name] = (found.get(OP_NAME_STAT, ""),
                              found.get(SOURCE_STAT, ""))
    return out


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: [(op, start_ns, dur_ns, op_name, source)]},
    "host": [(name, start_ns, dur_ns)]}``."""
    from jax.profiler import ProfileData

    meta = op_metadata(path)
    devices: dict = {}
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            table = meta.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (ev.name, float(ev.start_ns),
                         float(ev.duration_ns),
                         *table.get(ev.name, ("", "")))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, float(ev.start_ns),
                             float(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith(PREFIX))
    return {"devices": devices, "host": host}


def holds(path: tuple, scope: tuple) -> bool:
    """``scope`` is a subsequence of ``path``."""
    it = iter(path)
    return all(name in it for name in scope)


def reduce_scopes(events: dict, planes) -> "dict | None":
    """Self time by scope path over ``planes``, in seconds a plane:
    ``{"by_path": {path: s}, "busy_s", "iterations", "ops": {path: {op:
    s}}}``; ``None`` where no operation carries a scope."""
    planes = [p for p in planes if events["devices"].get(p)]
    if not planes:
        return None
    by_path: dict = {}
    ops: dict = {}
    busy = 0.0
    for p in planes:
        evs = events["devices"][p]
        keyed = [((scope_path(op_name), op, source), s, d)
                 for op, s, d, op_name, source in evs]
        for (path, op, source), t in self_times(keyed).items():
            by_path[path] = by_path.get(path, 0.0) + t
            ops.setdefault(path, {})
            ops[path][op, source] = ops[path].get((op, source), 0.0) + t
        busy += union_and_gaps([(e[1], e[1] + e[2]) for e in evs])[0]
    if not any(by_path):
        return None
    n = len(planes) * 1e9
    steps = sum(1 for name, _, _ in events["host"] if name == ITERATION)
    return {"by_path": {k: v / n for k, v in by_path.items()},
            "ops": {k: {o: t / n for o, t in v.items()}
                    for k, v in ops.items()},
            "busy_s": busy / n, "iterations": max(1, steps)}


def scope_seconds(reduced: dict, scope) -> float:
    """Seconds an iteration under ``scope`` (a path of names)."""
    scope = tuple(scope)
    return sum(t for path, t in reduced["by_path"].items()
               if holds(path, scope)) / reduced["iterations"]


def outside_seconds(reduced: dict, roots) -> float:
    """Seconds an iteration of operations under none of ``roots``."""
    return sum(t for path, t in reduced["by_path"].items()
               if not any(r in path for r in roots)) / reduced["iterations"]


def device_gaps(events: dict, planes) -> list:
    """``[(start_ns, end_ns)]``: where, between its first and its last
    operation, a device plane ran nothing."""
    gaps = []
    for p in planes:
        evs = events["devices"].get(p) or []
        gaps.extend(union_and_gaps([(e[1], e[1] + e[2]) for e in evs])[1])
    return gaps


def innermost(spans, gap) -> "str | None":
    """The shortest of the spans that cover more than half of ``gap``."""
    gs, ge = gap
    over = [(d, name) for name, s, d in spans
            if min(ge, s + d) - max(gs, s) > (ge - gs) / 2]
    return min(over)[1] if over else None


def reduce_spans(events: dict, planes) -> "dict | None":
    """``{"median_ms": {span: ms}, "idle_s", "explained_s", "gaps":
    [[innermost span or "unattributed", seconds]] longest first}``;
    ``None`` where the host plane holds no ``rlsched:`` event."""
    if not events["host"]:
        return None
    durations: dict = {}
    for name, _, d in events["host"]:
        durations.setdefault(name[len(PREFIX):], []).append(d * 1e-6)
    gaps = device_gaps(events, planes)
    inner = [(s, s + d) for name, s, d in events["host"]
             if name != ITERATION]
    idle = sum(ge - gs for gs, ge in gaps)
    # of each gap, the part some span other than the iteration's covers
    explained = sum(
        union_and_gaps([(max(s, gs), min(e, ge)) for s, e in inner
                        if s < ge and e > gs])[0]
        for gs, ge in gaps)
    rows = [[innermost(events["host"], g) or "unattributed",
             (g[1] - g[0]) * 1e-9]
            for g in sorted(gaps, key=lambda g: g[0] - g[1])[:10]]
    return {"median_ms": {k: statistics.median(v)
                          for k, v in durations.items()},
            "count": {k: len(v) for k, v in durations.items()},
            "idle_s": idle * 1e-9, "explained_s": explained * 1e-9,
            "gaps": rows}


def parsed(probe: dict) -> "dict | None":
    """The window's xplane, read and reduced once a run (memoised in
    ``probe["cache"]``); logs the ``scopes`` and ``host_gaps`` lines."""
    cache = probe.setdefault("cache", {})
    if "xplane_scopes" in cache:
        return cache["xplane_scopes"]
    cache["xplane_scopes"] = None
    trace = probe.get("trace")
    path = newest_xplane()
    if not trace or not trace.get("device_planes") or path is None:
        return None
    events = read_xplane(path)
    planes = trace["device_planes"]
    out = {"scopes": reduce_scopes(events, planes),
           "spans": reduce_spans(events, planes)}
    cache["xplane_scopes"] = out
    if out["scopes"] is not None:
        log(phase="scopes", **scopes_line(out["scopes"]))
    if out["spans"] is not None:
        log(phase="host_gaps", **out["spans"])
    return out


def scopes_line(reduced: dict) -> dict:
    """Every scope of the tree with its ms an iteration and its share of
    busy time, the unattributed rest with its largest operations, and
    for each LEAF path that took time its largest operation."""
    busy = reduced["busy_s"] / reduced["iterations"]
    table = {}
    for scope in TREE:
        s = scope_seconds(reduced, scope)
        table["/".join(scope)] = {"ms": s * 1e3,
                                  "share": s / busy if busy else 0.0}
    rest = outside_seconds(reduced, [p[0] for p in TREE if len(p) == 1])
    heaviest = {
        "/".join(path) or "unattributed":
            [[op.split(" = ")[0], source, t / reduced["iterations"]]
             for (op, source), t in
             sorted(ops.items(), key=lambda kv: -kv[1])[:3]]
        for path, ops in reduced["ops"].items()}
    return {"iterations": reduced["iterations"], "busy_ms": busy * 1e3,
            "scopes": table, "unattributed_ms": rest * 1e3,
            "heaviest_ops": heaviest}


def main(argv=None) -> int:
    """``python3 benchmark/readers/xplane_scopes.py <xplane.pb> [--events
    out.json.gz --max-events N]``: print both reductions; optionally keep
    N device events of each plane (the first N/3, the last N/3, and N/3
    around the first operation under ``advantage``, where one stage hands
    over to the next; operations by their instruction name alone) and the
    host spans as a small recorded trace."""
    import argparse
    import gzip
    import json

    ap = argparse.ArgumentParser(prog="benchmark/readers/xplane_scopes.py")
    ap.add_argument("xplane")
    ap.add_argument("--events", default=None)
    ap.add_argument("--max-events", type=int, default=3000)
    args = ap.parse_args(argv)
    events = read_xplane(args.xplane)
    planes = sorted(events["devices"])
    scopes = reduce_scopes(events, planes)
    print(json.dumps({"scopes": scopes and scopes_line(scopes),
                      "spans": reduce_spans(events, planes)}))
    if args.events:
        third = args.max_events // 3
        small = {"devices": {}, "host": events["host"]}
        for plane, evs in events["devices"].items():
            evs = sorted(evs, key=lambda e: e[1])
            if len(evs) > 3 * third:
                mid = next((i for i, e in enumerate(evs)
                            if "advantage" in scope_path(e[3])),
                           len(evs) // 2)
                lo = min(max(third, mid - third // 2), len(evs) - 2 * third)
                evs = evs[:third] + evs[lo:lo + third] + evs[-third:]
            small["devices"][plane] = [
                (op.split(" = ")[0], *rest) for op, *rest in evs]
        with gzip.open(args.events, "wt") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
