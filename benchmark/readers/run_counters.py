"""Reader ``run_counters``: one field of the window's LAST logged
iteration's metrics (``args["counter"]``), from the program's own start-up
account (``rlgpuschedule_tpu/obs/startup.py``): every ``run`` record keeps
what its call returned as ``history[-1]``, and the window is the last
closed one (``startup_account.window_run``). ``Experiment.run`` always
logs a call's last iteration, so the record has the window's; the values
were on the host already (no sync, nothing inside the loop).

None where the tree has no account, the record no metrics, or the metrics
no such field (a policy without a token trunk logs ``PPOMetrics`` alone):
the metric is left out there. So it is where the cell's configuration
file lists no ``args["scope"]`` under ``trunk_scopes``, the trunk scope
whose layers the counter counts (a token trunk logs all of its family's
counters, and one without KDA layers has no kernel to fall back from).
Anywhere else a counter that reads 0 IS a value: a build that fell back
from a kernel to the plain path prints 0 beside the ledger's 5.

One ``phase="run_counters"`` line a run (``common.log``): the record's
iterations and every field of its metrics, so the counters that no
metric reads are on a traced run's output too."""
from __future__ import annotations

from benchmark.common import log
from benchmark.readers.startup_account import account, window_run


def window_metrics(acct) -> dict:
    """The window's last logged iteration, ``{}`` where there is none."""
    window = window_run(acct.snapshot()["spans"])
    if window is None or not window.get("metrics"):
        return {}
    log(phase="run_counters", window_iterations=window.get("iterations"),
        **window["metrics"])
    return window["metrics"]


def read(probe: dict, args: dict) -> "float | None":
    if args.get("scope") not in probe.get("config", {}).get(
            "trunk_scopes", ()):
        return None
    cache = probe.setdefault("cache", {})
    if "run_counters" not in cache:
        acct = account()
        cache["run_counters"] = {} if acct is None else window_metrics(acct)
    return cache["run_counters"].get(args["counter"])
