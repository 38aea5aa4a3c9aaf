"""Native runtime (L1): compile-on-demand C++ baseline engine + ctypes
binding.

Capability parity: SURVEY.md §2 "Native components" — the reference keeps
its native code in dependencies (PyTorch CUDA kernels, NCCL); this
framework's TPU compute path is XLA-compiled JAX, and the host-side
runtime piece that IS performance-critical — full-production-trace
baseline scheduling for the JCT comparison tables (SURVEY.md §3.4) — is
implemented natively here (``fast_oracle.cpp``) and cross-validated
against the Python oracle property-by-property.

The shared library is built on first use with the system ``g++`` (no build
system, no pybind11 — plain C ABI via ctypes) from the committed
``fast_oracle.cpp`` alone, cached keyed by source hash in the same
directory as the jax compile cache (``utils.platform.cache_dir``: inside
the checkout unless the environment places it), and every entry point
degrades gracefully to the Python oracle when no toolchain is present
(``available()`` gates it; ``chip_smoke.py`` prints which one ran).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "fast_oracle.cpp")
_POLICIES = {"fifo": 0, "sjf": 1, "srtf": 2, "tiresias": 3}
_TIRESIAS_THRESHOLDS = (3600.0, 36000.0)   # sim/schedulers.py::tiresias

_lib: ctypes.CDLL | None = None
_build_error: str | None = None


def _so_path() -> str:
    # 0700 and never the shared tmp dir: a predictable world-writable
    # path could be pre-seeded by another local user and dlopen runs
    # arbitrary constructors
    from ..utils.platform import cache_dir
    d = os.path.join(cache_dir(), "native")
    os.makedirs(d, mode=0o700, exist_ok=True)
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(d, f"fast_oracle_{tag}.so")


def _load() -> ctypes.CDLL | None:
    global _lib, _build_error
    if _lib is not None or _build_error is not None:
        return _lib
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        _build_error = "no C++ compiler on PATH"
        return None
    so = _so_path()
    if not os.path.exists(so):
        tmp = so + f".tmp{os.getpid()}"
        cmd = [cxx, "-O2", "-std=c++17", "-shared", "-fPIC", _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True,
                           timeout=120)
            os.replace(tmp, so)  # atomic: concurrent builders race safely
        except (subprocess.SubprocessError, OSError) as e:
            _build_error = f"build failed: {getattr(e, 'stderr', e)}"
            return None
    lib = ctypes.CDLL(so)
    f = lib.run_baseline_native
    f.restype = ctypes.c_int64
    f.argtypes = [
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ctypes.c_int, ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.c_int,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
    ]
    _lib = lib
    return _lib


def available() -> bool:
    """True iff the native engine can be built/loaded on this machine."""
    return _load() is not None


def build_error() -> str | None:
    _load()
    return _build_error


def run_baseline_native(trace, n_nodes: int, gpus_per_node: int, name: str,
                        thresholds=_TIRESIAS_THRESHOLDS,
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Run one named baseline natively over an ArrayTrace; returns per-row
    ``(finish, start)`` times [max_jobs] (+inf on padding — all valid jobs
    complete, as in the oracle; ``start`` is the FIRST start, preserved
    across preemptions, mirroring ``OracleSim.start``). Raises RuntimeError
    if the engine is unavailable or the trace is infeasible."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native engine unavailable: {_build_error}")
    if name not in _POLICIES:
        raise ValueError(f"unknown baseline {name!r}")
    valid = np.flatnonzero(trace.valid)
    submit = np.ascontiguousarray(trace.submit[valid], np.float64)
    duration = np.ascontiguousarray(trace.duration[valid], np.float64)
    gpus = np.ascontiguousarray(trace.gpus[valid], np.int32)
    th = np.ascontiguousarray(sorted(thresholds), np.float64)
    finish = np.full(len(valid), np.inf, np.float64)
    start = np.full(len(valid), np.inf, np.float64)
    rc = lib.run_baseline_native(
        len(valid), submit, duration, gpus, n_nodes * gpus_per_node,
        _POLICIES[name], th, len(th), finish, start)
    if rc < 0:
        reasons = {-1: "invalid input (zero/oversized gang or duration)",
                   -2: "scheduler deadlock", -3: "no progress",
                   -4: "max_events exceeded"}
        raise RuntimeError(f"native {name} failed: "
                           f"{reasons.get(int(rc), rc)}")
    finish_out = np.full(trace.max_jobs, np.inf, np.float64)
    start_out = np.full(trace.max_jobs, np.inf, np.float64)
    finish_out[valid] = finish
    start_out[valid] = start
    return finish_out, start_out


class NativeSimResult:
    """Finished-run shim exposing the OracleSim result surface the eval
    harness and downstream tools read: ``finish`` / ``start`` / ``status``
    / ``jcts()`` / ``avg_jct()`` / ``trace`` (the ``sim.schedulers
    .BaselineResult`` protocol). ``status`` mirrors the oracle's finished
    state exactly: all rows DONE — valid jobs because the engine runs the
    trace to completion, padding rows because ``OracleSim.__init__`` marks
    them DONE from the start (oracle.py:95)."""

    def __init__(self, trace, finish: np.ndarray, start: np.ndarray):
        from ..sim.oracle import DONE

        self.trace = trace
        self.finish = np.where(np.isfinite(finish), finish, np.nan)
        self.start = np.where(np.isfinite(start), start, np.nan)
        self.status = np.full(trace.max_jobs, DONE, np.int32)

    def jcts(self) -> np.ndarray:
        v = self.trace.valid & np.isfinite(self.finish)
        return (self.finish[v] - self.trace.submit[v]).astype(np.float64)

    def avg_jct(self) -> float:
        j = self.jcts()
        return float(j.mean()) if len(j) else float("nan")
