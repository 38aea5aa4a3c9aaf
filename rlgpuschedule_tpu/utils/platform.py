"""Device selection and compile-cache placement, one helper each.

``force_cpu`` pins N virtual CPU devices before any jax backend
initializes: the test suite (tests/conftest.py) and
``__graft_entry__.dryrun_multichip``
need it (SURVEY.md §4 "Distributed without a real cluster").
``require_tpu`` is its opposite for the chip entry points: no TPU is an
error, never a quiet CPU run. ``enable_compile_cache`` places jax's
persistent compilation cache where whoever runs the program said, else at
one fixed path inside the checkout.
"""
from __future__ import annotations

import os
import time

from .. import stamp

# <repo>/.jax_cache, resolved from this file's own location: the cache
# path is part of what a caller must be able to predict and carry, so it
# never depends on $HOME, a temp dir, a pid or the clock
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """Where compiled artifacts live: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it (and then nowhere else), otherwise
    ``<repo>/.jax_cache``. The native oracle's built ``.so`` shares it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at :func:`cache_dir` and
    cache every compile (floor 0). Every entry point that compiles calls
    this before its first compile — the CLIs, ``benchmark/run.py``,
    ``chip_smoke.py`` and the test conftest — so all of them write ONE
    on-disk format into a shared directory. It sets the directory and the
    compile-time floor and nothing else: in particular no size cap, which
    would switch jax's file cache to its LRU layout (``-cache`` +
    ``-atime`` pairs) and make a directory shared with any process that
    did not set the same cap unwritable. Whoever places the directory
    bounds it. Returns the directory.

    The cache key includes each operation's metadata. jax's default key
    strips it, so a program that differs from a cached one only in its
    ``jax.named_scope`` names (``obs.scopes``) would be handed the
    cached executable with the OTHER program's ``op_name`` paths, and a
    profile of it would show none of its own scopes. The price: an
    entry is good for one version of the traced source lines, not for
    every program with the same arithmetic."""
    directory = cache_dir()
    import jax

    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return directory


def device_record() -> dict:
    """``{platform, kind, count}`` as jax reports the default backend —
    the provenance every benchmark/smoke JSON line carries, so a CPU-sized
    run can never be read as the chip's."""
    import jax

    start = time.monotonic()
    devices = jax.devices()     # the first call starts the backend's client
    stamp("backend", start)     # a span of the start-up account
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_tpu(what: str) -> dict:
    """Fail (``SystemExit``, one line) unless jax's default backend is a
    TPU; returns :func:`device_record` otherwise. The chip entry points
    call this before doing any work: there is no fallback platform."""
    try:
        device = device_record()
    except RuntimeError as e:
        raise SystemExit(f"{what}: no TPU (jax backend init failed: "
                         f"{str(e).splitlines()[0]})") from e
    if device["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU but jax's default backend is "
            f"{device['platform']!r} ({device['kind']}); there is no "
            f"fallback platform")
    return device


def force_cpu(n_devices: int = 8) -> list:
    """Pin jax to the CPU platform with ``n_devices`` virtual devices and
    return them.

    jax may already be imported, but as long as its backends are still
    lazy the pin works: flip ``jax_platforms`` to cpu and set
    ``--xla_force_host_platform_device_count`` before first device access.
    If backends already initialized as CPU this is a no-op that returns
    the existing devices; if they initialized as anything else, raises
    with an actionable message (the fix is a fresh process) instead of
    the opaque backend errors that follow otherwise.

    The env-var mutations are reverted before returning: in-process the
    pin lives in the initialized backend, and leaking ``JAX_PLATFORMS=cpu``
    into the environment would silently force later-spawned subprocesses
    onto CPU.
    """
    prev = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    try:
        import jax

        # jax 0.9.0 has no public "are backends initialized" probe
        # (jax.extend.backend.backends() initializes them as a side
        # effect), so this one private call stays
        if not jax._src.xla_bridge.backends_are_initialized():
            jax.config.update("jax_platforms", "cpu")
        try:
            devices = jax.devices("cpu")
        except RuntimeError as e:
            raise RuntimeError(
                f"cannot obtain CPU devices: jax backends were already "
                f"initialized (default backend "
                f"{jax.default_backend()!r}) before force_cpu could pin "
                f"the platform — run the CPU-mesh program in a fresh "
                f"process") from e
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not devices or any(d.platform != "cpu" for d in devices):
        raise RuntimeError(f"force_cpu got non-CPU devices: {devices}")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"force_cpu({n_devices}) got only {len(devices)} CPU devices — "
            f"either the CPU backend initialized before this call, or "
            f"XLA_FLAGS already pins a smaller "
            f"xla_force_host_platform_device_count; a multichip program "
            f"must not silently degrade to {len(devices)} device(s)")
    return devices
