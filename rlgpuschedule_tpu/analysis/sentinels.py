"""Runtime performance sentinels: the dynamic half of jsan.

The static rules (:mod:`.rules`) catch what local evidence can prove;
these two sentinels catch what it can't:

- :class:`CompileCounter` — counts XLA traces and backend compiles via
  ``jax.monitoring`` event listeners. The contract it enforces
  (tests/test_sentinels.py): the fused update step compiles **exactly
  once** across geometry-stable iterations. A shape-unstable argument,
  an unhashable closure capture, or a rebuilt function object all show
  up here as steady-state compiles — the recompile-per-step failure
  mode that erases a bench win without failing a test.
- :func:`no_implicit_transfers` — ``jax.transfer_guard("disallow")``
  scoped as a context: inside it, any *implicit* host↔device transfer
  raises. Wrapped around a hot loop it proves the loop is device-
  resident (explicit ``jax.device_put``/``device_get`` remain allowed,
  so deliberate materialization at loop boundaries still works).

Both are cheap enough for the ``sanitize`` tier-1 subset — neither
re-executes programs the way ``jax_debug_nans`` does.
"""
from __future__ import annotations

import contextlib

import jax

# every XLA backend compile fires this duration event; every jaxpr trace
# fires the trace event even when the *persistent* compilation cache
# serves the executable (conftest enables that cache, so a warm CI run
# may legitimately see traces without backend compiles — steady-state
# assertions must require BOTH to be zero, which assert_no_recompiles
# does)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class RecompileSentinelError(AssertionError):
    """A region that must be compile-free traced or compiled."""


class CompileCounter:
    """Context manager counting traces + backend compiles in its scope.

    Usage (the geometry-stable contract)::

        step(state, batch)                 # warmup: compiles once
        with CompileCounter() as c:
            for _ in range(n):
                state, _ = step(state, batch)
        assert c.total == 0, c.events

    Counts are global to the process (jax.monitoring has no per-program
    attribution), so keep input construction — ``jnp.ones``, key splits,
    anything that dispatches its own tiny program — outside the scope.
    """

    def __init__(self):
        self.backend_compiles = 0
        self.traces = 0
        self.events: list[str] = []
        self._listener = None

    @property
    def total(self) -> int:
        return self.backend_compiles + self.traces

    def __enter__(self) -> "CompileCounter":
        def listener(event: str, duration: float, **kwargs) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.backend_compiles += 1
                self.events.append(event)
            elif event == TRACE_EVENT:
                self.traces += 1
                self.events.append(event)

        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listener)
        self._listener = None


@contextlib.contextmanager
def assert_no_recompiles(what: str = "region"):
    """Assert a region neither traces nor compiles (post-warmup steady
    state). Raises :class:`RecompileSentinelError` naming the events."""
    with CompileCounter() as counter:
        yield counter
    if counter.total > 0:
        raise RecompileSentinelError(
            f"{what} expected zero compilation activity but saw "
            f"{counter.traces} trace(s) and {counter.backend_compiles} "
            f"backend compile(s): a geometry-stable hot loop is "
            f"recompiling (shape-unstable args, rebuilt function object, "
            f"or unhashable static capture)")


def no_implicit_transfers():
    """``jax.transfer_guard("disallow")`` as a readable name: inside,
    implicit host↔device transfers raise; explicit device_put/device_get
    stay legal. Wrap hot loops in perf/sanitize tests to prove device
    residency."""
    return jax.transfer_guard("disallow")
