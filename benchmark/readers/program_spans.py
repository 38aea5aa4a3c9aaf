"""Reader ``program_spans``: the program's own host spans
(``rlsched:<span>``, ``obs/trace.py``) in the traced window's xplane
(``xplane_scopes``). ``args["median_ms_of"]`` (a span name) gives the
median duration of that span in ms; ``args["idle_explained"]`` gives the
share in % of the device's idle time in the window that lies inside a
span other than the iteration's own."""
from __future__ import annotations

from benchmark.readers import xplane_scopes


def read(probe: dict, args: dict) -> "float | None":
    parsed = xplane_scopes.parsed(probe)
    spans = parsed and parsed["spans"]
    if not spans:
        return None
    if "median_ms_of" in args:
        return spans["median_ms"].get(args["median_ms_of"])
    if spans["idle_s"] <= 0:
        return None
    return 100.0 * spans["explained_s"] / spans["idle_s"]
