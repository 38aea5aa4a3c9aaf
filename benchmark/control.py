#!/usr/bin/env python3
"""The control of ``correct``: for each seed, the numbers the check
compares, once read from the program (``none``) and once from the plain
reference put in the program's place, computed in a lower precision
(``fp8``) or with a fault planted (``half_lr``, ``half_rows``).

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--variants none,fp8,half_lr,half_rows]

One process for all seeds of a cell (set-up is most of the cost). A limit
is sound when the control's SMALLEST reading of a number is at least three
times the program's LARGEST; ``PERF.md`` keeps both and the limit set
between them. The benchmark's own runs never run this. Needs the TPU like
``run.py`` (``--rehearse-cpu`` for the tiny shape).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="none,fp8")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import common
    from benchmark.run import context_for
    from rlgpuschedule_tpu.utils.platform import (device_record,
                                                  enable_compile_cache,
                                                  require_tpu)
    device = (device_record() if args.rehearse_cpu
              else require_tpu("benchmark control"))
    enable_compile_cache()
    variants = args.variants.split(",")
    worst: dict = {v: {} for v in variants}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = context_for(args.workload, seed, args.seconds,
                          args.rehearse_cpu, device)
        driver = common.load_module("drivers", ctx.traffic["driver"])
        reads = driver.control(ctx, variants)
        for q, read in reads.items():
            print(json.dumps({"seed": seed, "variant": q, "device": device,
                              **read}, default=float), flush=True)
            for k, v in read.items():
                if isinstance(v, (int, float)):
                    lo, hi = worst[q].get(k, (v, v))
                    worst[q][k] = (min(lo, v), max(hi, v))
    print(json.dumps({"summary_min_max": worst}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
