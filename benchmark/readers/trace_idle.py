"""Reader ``trace_idle``: the device's idle share in % over the traced
window: 1 - union of device-operation intervals over the window, from
``trace_reduce`` (averaged over the chips used)."""
from __future__ import annotations


def read(probe: dict, args: dict) -> "float | None":
    tr = probe.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
