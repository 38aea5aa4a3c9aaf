"""What every driver and reader shares: finding a cell's files by the
names in ``BENCHMARK.json``, the compile meter, the peaks table, and the
check ledger that decides ``correct``."""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry, its configuration file and its traffic file,
    each found by the name the entry gives."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return {"spec": spec, "cell": cell, "config": config,
            "traffic": traffic}


def load_module(kind: str, name: str):
    """``drivers/<name>.py``, ``readers/<name>.py`` or
    ``reference/<name>.py``, by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


class Reference:
    """A configuration's plain reference, resolved ONCE from its file: the
    module ``reference/<name>.py`` that the file's ``reference`` key names,
    and the settings that module is handed with every call: the file's
    top-level keys in a run, overlaid by its ``rehearse_trunk`` in a
    rehearsal. Which keys a reference reads is that reference's business;
    the harness requires none of them, looks at no other configuration's
    file and asks the program nothing."""

    def __init__(self, config: dict, rehearse: bool = False):
        if "reference" not in config:
            raise SystemExit(
                f"benchmark: configuration {config.get('name')!r} names no "
                f"'reference' (a module of benchmark/reference/)")
        self.name = config["reference"]
        self.module = load_module("reference", self.name)
        self.settings = dict(config)
        if rehearse:
            self.settings.update(config.get("rehearse_trunk", {}))

    def trunk(self, encoder, obs, quant=None):
        """One pooled vector a row from the encoder's leaves."""
        return self.module.trunk(encoder, obs, quant, self.settings)

    def forward_flops_per_row(self, params) -> float:
        return self.module.forward_flops_per_row(params, self.settings)


def log(**fields) -> None:
    """An earlier line of stdout (never the last): one JSON object."""
    print(json.dumps(fields, default=float), file=sys.__stdout__, flush=True)


def note(msg: str) -> None:
    print(f"benchmark [{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def peaks_for(device_kind: str) -> dict:
    """The row of ``peaks.json`` for exactly this ``device_kind``; a device
    that is not in the table is an error, never a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not "
                         f"in benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


class CompileMeter:
    """Seconds spent tracing/lowering/compiling, backend compiles and
    persistent-cache hits/misses since the last :meth:`reset`, from jax's
    public monitoring events (a copy of ``chip_smoke.CompileMeter``)."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.compile_s = 0.0
        self.traces = 0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "traces": self.traces,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event in self.DURATIONS:
            self.compile_s += duration
            self.traces += event == self.DURATIONS[0]
            self.backend_compiles += event == self.DURATIONS[-1]

    def _event(self, event: str, **_kw) -> None:
        self.hits += event == self.HIT
        self.misses += event == self.MISS


class Checks:
    """Every number compared, beside its limit; ``correct`` is all of them
    holding. ``limit`` is an upper bound on ``value`` (an exact comparison
    has the limit 0)."""

    def __init__(self):
        self.rows: list[dict] = []

    def add(self, name: str, value: float, limit: float, **detail) -> None:
        value = float(value)
        ok = value <= limit        # a NaN compares false: not correct
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": bool(ok), **detail})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def compared(self) -> dict:
        """``{name: {"value", "limit"}}``, in the order compared."""
        return {r["check"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def emit(self) -> None:
        for r in self.rows:
            log(**r)


def limits_of(traffic: dict, config: dict) -> dict:
    """Limits of the comparisons: the traffic mix's ``limits`` (what holds
    for every configuration), overlaid by the configuration's own
    ``limits`` for this traffic mix's driver."""
    out = dict(traffic.get("limits", {}))
    out.update(config.get("limits", {}).get(traffic["driver"], {}))
    return out


def resolve_config(config: dict, seed: int, rehearse: bool):
    """The program's ``ExperimentConfig`` for this configuration file,
    resolved the way its train CLI resolves flags."""
    from rlgpuschedule_tpu import train as train_cli
    from rlgpuschedule_tpu.configs import CONFIGS
    over = config["rehearse_overrides" if rehearse else "overrides"]
    # the program's seed is an int32 inside jax.random.PRNGKey and numpy's
    # default_rng: fold a wide --seed down, keeping runs distinct
    seed31 = (seed ^ (seed >> 31)) & 0x7FFFFFFF
    args = train_cli.build_parser().parse_args(
        ["--config", config["preset"], "--seed", str(seed31), *over])
    return train_cli.apply_overrides(CONFIGS[config["preset"]], args)
