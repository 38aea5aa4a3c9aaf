"""Reader ``memory``: what the fullest chip held in GiB, as the result
line's ``memory_peak_bytes`` has it (``run.py``: the buffers live when the
window closed plus the largest reservation a program made for its
temporaries, from ``memory_stats()``)."""
from __future__ import annotations


def read(probe: dict, args: dict) -> "float | None":
    peak = probe.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
