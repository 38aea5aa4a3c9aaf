"""Reader ``flops_share``: model FLOP/s utilisation of one stage in % -
the FLOPs the stage needs (from shapes, ``probe["flops"][flops]``, counted
by the configuration's own ``reference/forward_<name>.py``) over the stage's measured time,
over the device's peak from ``peaks.json``. Not a roofline share of a
kernel: it is named ``mfu``."""
from __future__ import annotations

from benchmark.common import peaks_for
from benchmark.readers.stage_time import time_stage


def read(probe: dict, args: dict) -> "float | None":
    flops = probe.get("flops", {}).get(args["flops"])
    seconds = time_stage(probe, args["stage"])
    if flops is None or seconds is None:
        return None
    if probe["device"]["platform"] != "tpu":
        return None          # a CPU run has no device peak to stand against
    peak = peaks_for(probe["device"]["kind"])[args["peak"]]
    return 100.0 * flops / seconds / peak
