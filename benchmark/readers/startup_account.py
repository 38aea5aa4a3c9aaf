"""Reader ``startup_account``: set-up by part, in seconds, from the
program's own start-up account (``rlgpuschedule_tpu/obs/startup.py``: its
``import`` / ``backend`` / ``build`` / ``run`` spans and every program's
trace, lowering, compile and cache-load intervals). ``args["part"]`` is
one of :data:`PARTS`; the seven are exclusive and add up to set-up's
length.

The window is the LAST closed ``run`` record: the driver's window is one
call of ``Experiment.run``, and the check and the stages that follow call
none (the harness hands a reader neither ``window_opens`` nor the window's
iteration count to hold the choice to; a run call after the window would
show as seven parts that add up to far more than the line's ``setup_s``).
Set-up is ``[t0, the end of the last span or compile interval of the
program that closed before the window's run began]``: what lies between
that end and the window (the harness's last host copies before
``window_opens``, and the profiler's own start after it) is in no part and
is printed as ``tail_s``, so the seven cover ``setup_s`` less ``tail_s``
less ``t0_after_process_s``. On a tree without the account every metric
of this reader is None.

One ``phase="startup_account"`` line a run (``common.log``): the whole
summary, the counts as of set-up's end, the ten programs with most
compile-side seconds, and what the listener itself cost (callbacks x
seconds a callback, timed here on a scratch counter)."""
from __future__ import annotations

import sys
import time

from benchmark.common import log

# part -> the summary's exclusive seconds it adds up
PARTS = {
    "import": ("import_s", "backend_s"),
    "build": ("build_s",),
    "trace_lower": ("trace_lower_s",),
    "compile": ("compile_s",),
    "cache_load": ("cache_load_s",),
    "first_run": ("run_s",),
    "outside_program": ("unnamed_s",),
}


def account():
    """The program's account, or None on a tree that has none."""
    try:
        from rlgpuschedule_tpu.obs.startup import ACCOUNT
    except ImportError:
        return None
    return ACCOUNT


def window_run(spans: list) -> "dict | None":
    """The window's run record: the LAST closed one (``run_counters``
    reads its metrics), or None where no run has ended."""
    runs = [s for s in spans if s["name"] == "run" and s["end"] is not None]
    return runs[-1] if runs else None


def setup_end(spans: list, events: list) -> "tuple[dict, float] | None":
    """``(the window's run record, set-up's end)`` from the account's
    span records and compile events, or None where no run has ended."""
    window = window_run(spans)
    if window is None:
        return None
    ends = [s["end"] for s in spans
            if s["end"] is not None and s["end"] <= window["start"]]
    ends += [end for _, end, _, _ in events if end <= window["start"]]
    return window, max(ends, default=window["start"])


def seconds_a_callback(counter, calls: int = 2000) -> float:
    """What one callback of the program's listener costs: a trace event
    handed, as jax hands it, to a scratch counter of its class that
    listens to nothing."""
    scratch = type(counter)()
    t0 = time.perf_counter()
    for _ in range(calls):
        scratch._duration("/jax/core/compile/jaxpr_trace_duration", 1e-6,
                          fun_name="scratch")
    return (time.perf_counter() - t0) / calls


def reduced(acct) -> "dict | None":
    """Set-up by part, with what the line prints beside it."""
    snap = acct.snapshot()
    found = setup_end(snap["spans"], snap["events"])
    if found is None:
        return None
    window, until = found
    summary = acct.summary(until=until)
    parts = {part: sum(summary[k] for k in keys)
             for part, keys in PARTS.items()}
    callbacks = (acct.compiles.n_events + acct.compiles.cache_hits
                 + acct.compiles.cache_misses)
    each = seconds_a_callback(acct.compiles)
    # run.py stamps its own start before it imports the package
    process = getattr(sys.modules.get("__main__"), "_T_PROCESS", None)
    return {
        "parts": parts, "summary": summary,
        "setup_s": summary["until_s"],
        "tail_s": window["start"] - until,
        "t0_after_process_s": (None if process is None
                               else acct.t0 - process),
        "window_iterations": window.get("iterations"),
        "listener": {"callbacks": callbacks, "s_per_callback": each,
                     "seconds": callbacks * each},
    }


def read(probe: dict, args: dict) -> "float | None":
    cache = probe.setdefault("cache", {})
    if "startup_account" not in cache:
        acct = account()
        found = None if acct is None else reduced(acct)
        cache["startup_account"] = found
        if found is not None:
            log(phase="startup_account", **{k: v for k, v in found.items()
                                            if k != "summary"},
                **found["summary"])
    found = cache["startup_account"]
    return None if found is None else found["parts"][args["part"]]
