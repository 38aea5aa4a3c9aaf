"""Reader ``scope_time``: device time by the program's own
``jax.named_scope`` names, from the traced window's xplane
(``xplane_scopes``). ``args["under"]`` (a path of scope names, outermost
first) gives the ms an iteration of operations under that path;
``args["outside"]`` (scope names) gives the share in % of busy time spent
in operations under none of them."""
from __future__ import annotations

from benchmark.readers import xplane_scopes


def read(probe: dict, args: dict) -> "float | None":
    parsed = xplane_scopes.parsed(probe)
    reduced = parsed and parsed["scopes"]
    if not reduced:
        return None
    if "under" in args:
        return xplane_scopes.scope_seconds(reduced, args["under"]) * 1e3
    busy = reduced["busy_s"] / reduced["iterations"]
    return 100.0 * xplane_scopes.outside_seconds(
        reduced, args["outside"]) / busy
