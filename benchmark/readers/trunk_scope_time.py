"""Reader ``trunk_scope_time``: device time under a policy trunk's own
scopes, from the traced window's xplane. The names are data: the cell's
configuration file lists them under ``trunk_scopes`` (the driver hands
the file over as ``probe["config"]``; for the program's token trunk they
are ``rlgpuschedule_tpu/obs/scopes.py`` ``TRUNK_TREE``'s, and
``benchmark/tests/test_tokens_cell.py`` holds the two equal).
``scope_time`` keeps to the names of the program's main tree
(``xplane_scopes.TREE``) when it cuts an ``op_name`` down to a scope
path, so the trunk's names never reach it. Its reduction keeps every
operation's self time by name (``reduce_scopes``' ``ops``),
so this reader reads no event a second time: it takes that table, looks
each operation's ``op_name`` up in the file's metadata
(``xplane_scopes.op_metadata``, the table the events were joined to) and
adds the times up over the trunk's names. ``args["under"]`` is a path of
those names, outermost first; a trunk scope is traced under both
``rollout/policy_forward`` and ``update/loss_grad``, and the value is the
sum of both, in ms an iteration.

Returns nothing where the configuration lists no names, or the window's
operations carry none of them (a policy without a trunk, a commit before
ISSUE 30, a rehearsal without a device plane): the metric is then left
out.
"""
from __future__ import annotations

import re

from benchmark.readers import xplane_scopes


def trunk_path(op_name: str, names) -> tuple:
    """Those of ``names`` an ``op_name`` carries, outermost first."""
    out = []
    for component in op_name.split("/"):
        words = re.findall(r"[\w.]+", component)
        if words and words[-1] in names:
            out.append(words[-1])
    return tuple(out)


def reduce_trunk(scopes: dict, op_names: dict, names) -> "dict | None":
    """``{"by_path": {path: seconds a plane}, "iterations"}`` over the
    trunk's ``names``, from ``xplane_scopes.reduce_scopes``' result and
    ``{operation: op_name}``; ``None`` where no operation carries one."""
    by_path: dict = {}
    for ops in scopes["ops"].values():
        for (op, _), t in ops.items():
            path = trunk_path(op_names.get(op, ""), names)
            by_path[path] = by_path.get(path, 0.0) + t
    if not any(by_path):        # no path but the empty one
        return None
    return {"by_path": by_path, "iterations": scopes["iterations"]}


def read(probe: dict, args: dict) -> "float | None":
    names = frozenset(probe.get("config", {}).get("trunk_scopes", ()))
    if not names:
        return None
    cache = probe.setdefault("cache", {})
    if "trunk_scope_time" not in cache:
        cache["trunk_scope_time"] = None
        parsed = xplane_scopes.parsed(probe)
        path = xplane_scopes.newest_xplane()
        if parsed and parsed["scopes"] and path is not None:
            meta = xplane_scopes.op_metadata(path)
            op_names = {op: paths[0]
                        for plane in probe["trace"]["device_planes"]
                        for op, paths in meta.get(plane, {}).items()}
            cache["trunk_scope_time"] = reduce_trunk(parsed["scopes"],
                                                     op_names, names)
    reduced = cache["trunk_scope_time"]
    if reduced is None:
        return None
    return xplane_scopes.scope_seconds(reduced, args["under"]) * 1e3
