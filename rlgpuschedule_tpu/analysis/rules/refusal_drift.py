"""refusal-drift: the mode-refusal table and the CLI guards, in lockstep.

``configs.MODE_REFUSALS`` is THE pairwise mode-combination contract
(one table, one error format — PR 9), and the ROADMAP's refusal-matrix
burn-down depends on it describing what the code actually refuses.
Nothing enforced that until now: a row nobody guards is dead weight
that reads as a live constraint, and a CLI that exposes two refusable
mode flags without calling ``validate_mode_combination`` silently runs
(or silently ignores) a combination the table says must refuse — both
drift classes existed in this tree when the rule first ran (the
shard_map rows had no guard; ``evaluate`` and a root-level script
exposed refusable pairs unguarded).

Both directions are checked, each finding landing in the file whose
edit fixes it:

**Analyzing the defining module** (the file assigning ``MODE_REFUSALS``
and ``MODE_FLAGS``): every refusal row ``(a, b, why)`` must have at
least one guard — a ``validate_mode_combination({...})`` call in the
package tree around it whose literal dict keys cover both ``a`` and
``b``. A row with no such guard fires on the row.

**Analyzing a CLI/caller module** (locating the defining ``configs.py``
next to it — same directory, a parent, or an immediate subdirectory):

- every literal key passed to ``validate_mode_combination`` must be a
  ``MODE_FLAGS`` mode (a typo'd key would KeyError at runtime — flag it
  at lint time);
- ``raise ModeCombinationError(...)`` outside the defining module is an
  ad-hoc refusal that bypasses the table's single error format;
- a module that ``add_argument``-exposes BOTH flags of a refused pair
  (matching ``MODE_FLAGS`` values' leading ``--token``) must have a
  guard covering that pair — otherwise the refused combination parses
  and runs unchecked.

Everything is literal-extracted (``ast.literal_eval`` on the table,
dict-literal keys on the guards) — no imports, keeping the lint stage's
no-JAX contract.
"""
from __future__ import annotations

import ast
import os

from . import Rule
from ..engine import Finding, ModuleContext, SourceFile, iter_py_files

_GUARD = "validate_mode_combination"
_ERROR = "ModeCombinationError"
_TABLE = "MODE_REFUSALS"
_FLAGS = "MODE_FLAGS"


def _assigned_literal(tree: ast.AST, name: str):
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            if any(isinstance(t, ast.Name) and t.id == name
                   for t in node.targets):
                try:
                    return ast.literal_eval(node.value), node.value
                except ValueError:
                    return None, None
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name) and node.target.id == name:
                try:
                    return ast.literal_eval(node.value), node.value
                except ValueError:
                    return None, None
    return None, None


def _call_name(func: ast.AST) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _guard_key_sets(tree: ast.AST) -> list[tuple[ast.Call, set[str]]]:
    """Every validate_mode_combination call with its literal dict keys."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node.func) == _GUARD \
                and node.args and isinstance(node.args[0], ast.Dict):
            keys = {k.value for k in node.args[0].keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
            out.append((node, keys))
    return out


def _defines_table(tree: ast.AST) -> bool:
    return _assigned_literal(tree, _TABLE)[0] is not None


def _parse_sibling(path: str) -> ast.AST | None:
    try:
        with open(path, encoding="utf-8") as f:
            return ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError, ValueError):
        return None


def _find_configs(path: str) -> ast.AST | None:
    """The defining module near ``path``: ``configs.py`` in the file's
    directory, up to two parents, or an immediate subdirectory (covers
    package modules, ``serve/__main__.py``, and repo-root
    ``chip_smoke.py``)."""
    d = os.path.dirname(os.path.abspath(path))
    candidates = [os.path.join(d, "configs.py"),
                  os.path.join(d, os.pardir, "configs.py"),
                  os.path.join(d, os.pardir, os.pardir, "configs.py")]
    try:
        candidates += sorted(
            os.path.join(d, sub, "configs.py")
            for sub in os.listdir(d)
            if os.path.isdir(os.path.join(d, sub)))
    except OSError:
        pass
    for cand in candidates:
        if os.path.isfile(cand):
            tree = _parse_sibling(cand)
            if tree is not None and _defines_table(tree):
                return tree
    return None


def _check_defining_module(src: SourceFile,
                           ctx: ModuleContext) -> list[Finding]:
    refusals, table_node = _assigned_literal(ctx.tree, _TABLE)
    if not isinstance(refusals, tuple) or table_node is None:
        return []
    # collect every guard's key set from the package tree around the
    # defining module (the defining module itself contributes none —
    # its only mention of the guard is the def)
    own = os.path.abspath(src.path)
    key_sets: list[set[str]] = []
    for path in iter_py_files([os.path.dirname(own) or "."]):
        if os.path.abspath(path) == own:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        if _GUARD not in text:
            continue
        tree = _parse_sibling(path)
        if tree is not None:
            key_sets.extend(keys for _, keys in _guard_key_sets(tree))
    findings: list[Finding] = []
    rows = [elt for elt in table_node.elts
            if isinstance(elt, ast.Tuple)] \
        if isinstance(table_node, ast.Tuple) else []
    for row in rows:
        lits = [e.value for e in row.elts[:2]
                if isinstance(e, ast.Constant)]
        if len(lits) != 2:
            continue
        a, b = lits
        if not any({a, b} <= keys for keys in key_sets):
            findings.append(src.finding(
                row, RULE.name,
                f"refusal row ({a!r}, {b!r}) has no reachable guard: no "
                f"{_GUARD} call in the package covers both modes, so "
                f"the table claims a refusal the code never enforces — "
                f"add the pair to a CLI/entry-point guard or delete "
                f"the row"))
    return findings


def _exposed_flags(tree: ast.AST) -> dict[str, ast.Call]:
    """--flag -> its add_argument call, for every literal option."""
    out: dict[str, ast.Call] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _call_name(node.func) == "add_argument" \
                and node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and node.args[0].value.startswith("--"):
            out.setdefault(node.args[0].value, node)
    return out


def _check_caller_module(src: SourceFile,
                         ctx: ModuleContext) -> list[Finding]:
    findings: list[Finding] = []
    guards = _guard_key_sets(ctx.tree)
    defines_error = any(isinstance(n, ast.ClassDef) and n.name == _ERROR
                        for n in ast.walk(ctx.tree))
    # ad-hoc refusals bypass the table's single error format
    if not defines_error:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Raise) \
                    and isinstance(node.exc, ast.Call) \
                    and _call_name(node.exc.func) == _ERROR:
                findings.append(src.finding(
                    node, RULE.name,
                    f"ad-hoc raise of {_ERROR} outside the defining "
                    f"module: refusals must come from {_GUARD} so the "
                    f"table stays the single source of truth — add a "
                    f"row to {_TABLE} and call the guard"))
    flags_exposed = _exposed_flags(ctx.tree)
    if not guards and not flags_exposed:
        return findings
    configs = _find_configs(src.path)
    if configs is None:
        return findings
    mode_flags, _ = _assigned_literal(configs, _FLAGS)
    refusals, _ = _assigned_literal(configs, _TABLE)
    if not isinstance(mode_flags, dict) or not isinstance(refusals, tuple):
        return findings
    for call, keys in guards:
        unknown = sorted(keys - set(mode_flags))
        if unknown:
            findings.append(src.finding(
                call, RULE.name,
                f"guard passes unknown mode name(s) {unknown}: not in "
                f"{_FLAGS} (this raises KeyError at runtime — fix the "
                f"key or add the mode to the table)"))
    # a CLI exposing both flags of a refused pair must guard the pair
    mode_by_flag = {spelling.split()[0]: mode
                    for mode, spelling in mode_flags.items()
                    if isinstance(spelling, str)
                    and spelling.startswith("--")}
    exposed_modes = {mode_by_flag[f] for f in flags_exposed
                     if f in mode_by_flag}
    for row in refusals:
        if not (isinstance(row, tuple) and len(row) >= 2):
            continue
        a, b = row[0], row[1]
        if a not in exposed_modes or b not in exposed_modes:
            continue
        if any({a, b} <= keys for _, keys in guards):
            continue
        anchor = flags_exposed[mode_flags[a].split()[0]]
        findings.append(src.finding(
            anchor, RULE.name,
            f"CLI exposes {mode_flags[a].split()[0]} and "
            f"{mode_flags[b].split()[0]} but no {_GUARD} call covers "
            f"the refused pair ({a!r}, {b!r}): the combination parses "
            f"and runs unchecked — add both modes to this module's "
            f"guard dict"))
    return findings


def _check(src: SourceFile, ctx: ModuleContext) -> list[Finding]:
    if _defines_table(ctx.tree):
        return _check_defining_module(src, ctx)
    return _check_caller_module(src, ctx)


RULE = Rule(
    name="refusal-drift",
    summary="MODE_REFUSALS rows without a reachable guard; CLI guards "
            "with unknown modes, ad-hoc refusals, or unguarded "
            "refusable flag pairs",
    check=_check,
    cross_file=True)
