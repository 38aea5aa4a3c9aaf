#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: find the cell's files by the names in ``BENCHMARK.json``,
require the TPU (no CPU fallback), place the compile cache, build and warm
the cell's own shapes (set-up), measure for ``--seconds``, check what the
timed path produced against the plain references, print. The LAST line of
stdout is the contract's JSON object, with every number compared beside
its limit under ``checks``, its last key (they are the last lines of stderr
too); everything else (compile split, the check's memory, each comparison's
detail) is on earlier lines, one JSON object each.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` a short
profiled window and the cell's per-layer metrics (each read by the reader
its ``layer_metrics/<name>.json`` names).

``--rehearse-cpu`` runs the same phases at the configuration's tiny shape
on whatever platform jax has, names that device and reports
``correct: false``: a rehearsal, never a measurement.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
import time

_T_PROCESS = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Context:
    """What a driver gets: the cell, its files, the run's arguments, and
    the window's bookkeeping (set-up ends where the window opens)."""

    def __init__(self, loaded: dict, args, device: dict, meter):
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.spec = loaded["spec"]
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse_cpu
        from benchmark.common import Reference
        # resolved once, from this cell's own file
        self.reference = Reference(self.config, self.rehearse)
        self.trace_seconds = min(
            args.seconds, float(self.traffic.get("trace_seconds", 2.0)))
        self.device = device
        self.meter = meter
        self.setup_s = None
        self.setup_compile = None
        self.window_compiles = None
        self.memory_peak_bytes = 0
        self.memory_stats: dict = {}
        self.trace_dir = os.path.join(
            ROOT, ".benchmark_trace", self.cell["name"])

    def window_opens(self) -> None:
        self.setup_s = time.monotonic() - _T_PROCESS
        self.setup_compile = self.meter.snapshot()
        self.meter.reset()

    def window_closes(self) -> None:
        import jax
        snap = self.meter.snapshot()
        self.window_compiles = snap["traces"] + snap["backend_compiles"]
        self.meter.reset()          # from here on: the check's programs
        peak = 0
        for d in jax.local_devices()[:self.cell["chips"]]:
            stats = d.memory_stats() or {}
            # what the chip holds while the window's largest program
            # runs: the buffers live as the window closes (the program's
            # state; the drivers keep their own copies on the host) plus
            # the largest reservation a program has made for its
            # temporaries, which the TPU runtime counts apart
            # (``peak_bytes_reserved``; ``peak_bytes_in_use`` leaves
            # temporaries out)
            peak = max(peak, int(stats.get("bytes_in_use", 0))
                       + int(stats.get("peak_bytes_reserved", 0)))
        # read here, before the references run: the peak stays the
        # program's
        self.memory_peak_bytes = peak
        self.memory_stats = stats

    @contextlib.contextmanager
    def profile(self):
        """The profiler around the window of a ``--trace 1`` run; nothing
        in a ``--trace 0`` run."""
        import shutil

        import jax
        if not self.trace:
            yield
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        with jax.profiler.trace(self.trace_dir):
            yield

    def xplane(self) -> "str | None":
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def context_for(workload: str, seed: int, seconds: float, rehearse: bool,
                device: dict) -> Context:
    """A driver's context outside ``main`` (``control.py``,
    ``benchmark/tests``)."""
    from benchmark import common
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              rehearse_cpu=rehearse)
    return Context(common.load_cell(workload), args, device,
                   common.CompileMeter())


def per_layer(ctx: Context, result: dict, reduced: "dict | None") -> dict:
    """Every ``layer_metrics/<name>.json`` whose ``drivers`` name this
    cell's driver, read by the reader it names; a reader that finds
    nothing to read returns nothing and the metric is left out."""
    from benchmark.common import load_module, log
    probe = dict(result.get("probe", {}))
    probe.update(trace=reduced, memory_peak_bytes=ctx.memory_peak_bytes,
                 device=ctx.device, config=ctx.config, cache={})
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if ctx.traffic["driver"] not in m["drivers"]:
            continue
        value = load_module("readers", m["reader"]).read(
            probe, m.get("args", {}))
        if value is None:
            log(phase="per_layer", metric=m["name"], value=None)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse_args(argv: "list[str] | None" = None):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


def main(argv: "list[str] | None" = None) -> int:
    args = parse_args(argv)
    try:
        from benchmark import common
        from rlgpuschedule_tpu.utils.platform import (device_record,
                                                      require_tpu)
    except ImportError as e:
        print(f"benchmark: the system under test is not importable from "
              f"this checkout ({e})", file=sys.stderr)
        return 5
    loaded = common.load_cell(args.workload)
    # before any work: no TPU, no run (--rehearse-cpu is the rehearsal's
    # way in)
    device = (device_record() if args.rehearse_cpu
              else require_tpu("benchmark"))
    chips = loaded["cell"]["chips"]
    if device["count"] < chips:
        print(f"benchmark: cell {args.workload!r} needs {chips} chip(s) "
              f"but jax sees {device['count']}", file=sys.stderr)
        return 5
    line, _ = execute(args, loaded, device)
    print(json.dumps(line), file=sys.__stdout__, flush=True)
    # the last lines of stderr: every number compared beside its limit
    for name, c in line["checks"].items():
        print(f"benchmark: compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr, flush=True)
    return 0


def execute(args, loaded: dict, device: dict) -> "tuple[dict, object]":
    """Everything after the look for a chip: returns the result line and
    the check ledger (``benchmark/tests`` drive this with the timed path
    broken underneath)."""
    from benchmark import common
    from rlgpuschedule_tpu.utils.platform import enable_compile_cache
    chips = loaded["cell"]["chips"]
    cache = enable_compile_cache()
    meter = common.CompileMeter()
    ctx = Context(loaded, args, device, meter)
    common.log(phase="start", workload=args.workload, seed=args.seed,
               seconds=args.seconds, trace=args.trace, device=device,
               rehearse=args.rehearse_cpu, compile_cache_dir=cache)

    driver = common.load_module("drivers", ctx.traffic["driver"])
    # the CLIs' and the program's own prints go to stderr; stdout stays
    # this program's JSON lines
    with contextlib.redirect_stdout(sys.stderr):
        result = driver.run(ctx)
    common.log(phase="setup", setup_s=ctx.setup_s, **ctx.setup_compile)
    common.log(phase="after_window", **meter.snapshot())
    common.log(phase="memory", **ctx.memory_stats)
    checks = result["checks"]
    checks.emit()

    metrics = {"setup_s": {"value": ctx.setup_s, "unit": "s"}}
    units = {m["name"]: m["unit"] for m in ctx.spec["end_to_end"]}
    for name, value in result["end_to_end"].items():
        metrics[name] = {"value": float(value), "unit": units[name]}
    out_device = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    line = {"correct": checks.correct and device["platform"] == "tpu"
            and not args.rehearse_cpu,
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": out_device}
    if ctx.trace:
        from benchmark import trace_reduce
        reduced = None
        xplane = ctx.xplane()
        if xplane is not None:
            reduced = trace_reduce.reduce_file(
                xplane, chips=chips,
                host_spans=ctx.traffic.get("host_spans", ()))
            common.log(phase="trace", xplane_bytes=os.path.getsize(xplane),
                       **{k: v for k, v in reduced.items()
                          if k not in ("device_ops", "idle_gaps")})
            out_device.update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
            line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
        line["metrics"] = per_layer(ctx, result, reduced)
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        # the traced run's largest moment, the stages' included (the
        # ``memory`` line above is the window's)
        common.log(phase="memory_after_stages", **{
            k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "peak_bytes_reserved")})
    line["checks"] = checks.compared()      # last in the line
    return line, checks


if __name__ == "__main__":
    sys.exit(main())
