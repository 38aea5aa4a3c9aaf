"""Profiler trace (``.xplane.pb``) -> device busy/idle, the device
operations that took most time, and the longest idle gaps with what the
host was doing in each. Two things only (ISSUE 26); per-scope kernel time
waits for scopes inside the program.

A device plane is one whose name starts with ``/device:TPU:``; its line
``XLA Ops`` holds one event per executed operation (a ``while`` spans its
body's operations, which are events of their own). Busy time is the UNION
of those intervals, so nesting and overlap count once; an operation's time
in the table is its SELF time (its span minus the spans of operations
nested in it). The window is the span from the first to the last device
event of the chips used. Host spans come from the benchmark's own
``jax.profiler.TraceAnnotation`` names and the program's ``rlsched:`` spans
(the traffic file's ``host_spans``) on the host plane's lines; a gap is
attributed to the INNERMOST span that covers it: of the spans that cover
most of it, the shortest.
"""
from __future__ import annotations

import collections

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def read_events(path: str, host_spans=()) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "host": [...]}``
    with nothing but jax."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_spans:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"devices": devices, "host": host}


def union_and_gaps(intervals):
    """Sorted ``(start, end)`` -> (covered length, [(gap_start, gap_end)])."""
    covered, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def self_times(events) -> dict:
    """Self time per operation name: span minus the spans nested in it."""
    out = collections.defaultdict(float)
    stack = []            # (name, end, children_time)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            n, _, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, end, dur])
    while stack:
        n, _, own = stack.pop()
        out[n] += own
    return out


def reduce_events(events: dict, chips: int = 1) -> dict:
    planes = sorted(events["devices"])[:chips]
    busy, window, ops = [], [], collections.defaultdict(float)
    gaps_all = []
    for p in planes:
        evs = events["devices"][p]
        if not evs:
            continue
        t0 = min(s for _, s, _ in evs)
        t1 = max(s + d for _, s, d in evs)
        covered, gaps = union_and_gaps([(s, s + d) for _, s, d in evs])
        busy.append(covered)
        window.append(t1 - t0)
        for name, t in self_times(evs).items():
            ops[name] += t / len(planes)
        gaps_all.extend(gaps)
    n = max(len(busy), 1)
    gap_rows = []
    for gs, ge in sorted(gaps_all, key=lambda g: g[0] - g[1])[:10]:
        # (cover, -duration): the largest cover, then the shortest span
        best = max(((min(ge, s + d) - max(gs, s), -d, name)
                    for name, s, d in events["host"]),
                   default=(0.0, 0.0, "unattributed"))
        gap_rows.append([best[2] if best[0] > 0 else "unattributed",
                         (ge - gs) * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n * 1e-9,
            "window_s": sum(window) / n * 1e-9,
            "device_planes": planes,
            "device_events": sum(len(events["devices"][p]) for p in planes),
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": gap_rows}


def reduce_file(path: str, chips: int = 1, host_spans=()) -> dict:
    return reduce_events(read_events(path, host_spans), chips)


def main(argv=None) -> int:
    """``python3 benchmark/trace_reduce.py <xplane.pb> [--events out.json.gz
    --max-events N]``: print the reduction; optionally keep the first N
    device events (and the host spans) as a small recorded trace."""
    import argparse
    import gzip
    import json

    ap = argparse.ArgumentParser(prog="benchmark/trace_reduce.py")
    ap.add_argument("xplane")
    ap.add_argument("--events", default=None)
    ap.add_argument("--max-events", type=int, default=3000)
    ap.add_argument("--host-spans", default="train_run")
    args = ap.parse_args(argv)
    events = read_events(args.xplane, args.host_spans.split(","))
    print(json.dumps(reduce_events(events)))
    if args.events:
        small = {"devices": {}, "host": events["host"]}
        for plane, evs in events["devices"].items():
            evs = sorted(evs, key=lambda e: e[1])[:args.max_events]
            small["devices"][plane] = evs
        with gzip.open(args.events, "wt") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
