"""Reader ``stage_time``: median wall time in ms of one call of a stage
the driver exposes (``probe["stages"][stage]``, a callable that returns
only when the device work is done). Any timing by the host's clock is off
by some half a millisecond, so each timing spans ``min_span_s`` (250 ms)
or more: as many calls together as that takes.

The driver lists its stages in the order they are to be timed in (a stage
may move what an earlier one reads, as an update moves the train state):
before a stage is timed, every stage listed before it is, whichever
metric asks first. Such a predecessor is timed at the defaults, so only
a stage that no metric of another stage waits for (the one the driver
lists last: ``resample``) may carry ``min_span_s`` or ``repeats`` of its
own; a stage asked for at other settings than it was timed at is an
error, never a silent second answer."""
from __future__ import annotations

import math
import statistics
import time


def time_stage(probe: dict, stage: str, min_span_s: float = 0.25,
               repeats: int = 5) -> "float | None":
    """Seconds per call (median over ``repeats`` timings); memoised in
    ``probe["cache"]`` so that two metrics of one stage time it once."""
    stages = probe.get("stages", {})
    fn = stages.get(stage)
    if fn is None:
        return None
    cache = probe.setdefault("cache", {})
    if stage not in cache:
        for earlier in stages:
            if earlier == stage:
                break
            if earlier not in cache:
                time_stage(probe, earlier)      # at the defaults
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
        cache[stage] = (statistics.median(spans), (min_span_s, repeats))
    seconds, timed_at = cache[stage]
    if timed_at != (min_span_s, repeats):
        raise ValueError(
            f"stage {stage!r} was timed at (min_span_s, repeats) = "
            f"{timed_at} and is asked for at {(min_span_s, repeats)}: only "
            f"the stage the driver lists last may carry its own")
    return seconds


def read(probe: dict, args: dict) -> "float | None":
    s = time_stage(probe, args["stage"], args.get("min_span_s", 0.25),
                   args.get("repeats", 5))
    return None if s is None else s * 1e3
