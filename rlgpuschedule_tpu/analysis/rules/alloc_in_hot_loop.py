"""alloc-in-hot-loop: fresh ndarray construction on dispatcher paths.

The arena data plane's whole contract (ISSUE 17) is that serving's
steady state allocates ZERO new host ndarrays per batch: requests land
in preallocated slabs, padding is slice assignment into the slab tail,
and scatter returns views into the one device-fetched actions buffer.
An ``np.zeros``/``np.empty``/``np.concatenate``/``np.stack`` that
creeps into code reachable from a dispatcher loop quietly reintroduces
per-batch allocation churn — the regression the arena exists
to prevent — long before any benchmark notices.

Fires on those four constructors inside any function reachable (via the
module's call graph) from a thread root the concurrency model knows:
``threading.Thread`` targets, executor-submitted callables, and the
``loop``/``*_loop``/``*_worker`` dispatcher convention. Main-thread-only
helpers (warmup, benches, construction-time sizing) never fire — slab
construction is exactly where those calls belong.

A deliberate allocation on a hot path (a cold-path branch, a
rare-rollover grow) is a one-line suppression with the reason inline::

    slab = np.zeros(shape)  # jsan: disable=alloc-in-hot-loop -- ring growth, amortized
"""
from __future__ import annotations

import ast

from . import Rule
from ..concurrency import model_for
from ..engine import Finding, ModuleContext, SourceFile

_ALLOC_CALLS = {"numpy.zeros", "numpy.empty", "numpy.concatenate",
                "numpy.stack"}


def _check(src: SourceFile, ctx: ModuleContext) -> list[Finding]:
    model = model_for(ctx)
    if not model.thread_roots:
        return []
    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = ctx.resolve_call(node)
        if name not in _ALLOC_CALLS:
            continue
        roots = model.roots_reaching(node)
        if not roots:
            continue
        labels = ", ".join(model.root_labels(roots))
        short = name.split(".")[-1]
        findings.append(src.finding(
            node, RULE.name,
            f"np.{short}() allocates a fresh ndarray on a path "
            f"reachable from {labels}: dispatcher hot paths must reuse "
            f"preallocated slabs (write into an arena slot / slice-"
            f"assign the tail) — or suppress with the reason the "
            f"allocation is cold or amortized"))
    return findings


RULE = Rule(
    name="alloc-in-hot-loop",
    summary="np.zeros/empty/concatenate/stack in functions reachable "
            "from dispatcher loops",
    check=_check)
