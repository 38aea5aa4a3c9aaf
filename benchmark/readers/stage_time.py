"""Reader ``stage_time``: median wall time in ms of one call of a stage
the driver exposes (``probe["stages"][stage]``, a callable that returns
only when the device work is done). Any timing by the host's clock is off
by some half a millisecond, so each timing spans ``min_span_s`` (250 ms)
or more: as many calls together as that takes."""
from __future__ import annotations

import math
import statistics
import time


def time_stage(probe: dict, stage: str, min_span_s: float = 0.25,
               repeats: int = 5) -> "float | None":
    """Seconds per call (median over ``repeats`` timings); memoised in
    ``probe["cache"]`` so that two metrics of one stage time it once."""
    fn = probe.get("stages", {}).get(stage)
    if fn is None:
        return None
    cache = probe.setdefault("cache", {})
    if stage not in cache:
        fn()                                    # warm: compiles here
        t0 = time.perf_counter()
        fn()
        one = max(time.perf_counter() - t0, 1e-6)
        calls = max(1, math.ceil(min_span_s / one))
        spans = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(calls):
                fn()
            spans.append((time.perf_counter() - t0) / calls)
        cache[stage] = statistics.median(spans)
    return cache[stage]


def read(probe: dict, args: dict) -> "float | None":
    s = time_stage(probe, args["stage"], args.get("min_span_s", 0.25),
                   args.get("repeats", 5))
    return None if s is None else s * 1e3
