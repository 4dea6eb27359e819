"""R4 — jax.experimental access must go through ``repro.compat``.

``shard_map`` has lived in more than one place in jax
(``jax.experimental.shard_map``, then top-level), and so has
``axis_size``.  ``src/repro/compat.py`` is the one module that names
them; everything else imports them from it, so a move in jax's surface
is a one-file change.  This rule flags:

* ``import jax.experimental.shard_map`` / ``from jax.experimental[.x]
  import shard_map`` anywhere outside ``compat.py``;
* ``from jax.experimental import ...`` of the shimmed names generally;
* attribute chains ``jax.experimental.shard_map...`` /
  ``jax.lax.axis_size`` / ``lax.axis_size`` used directly.

Scope: the whole repo (``src/``, ``scripts/``, ``tests/``, ``examples/``)
minus ``src/repro/compat.py`` itself.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from repro.analysis.engine import Finding, ModuleContext, rule

#: Names whose only sanctioned import site is repro.compat.
SHIMMED_NAMES = {"shard_map", "axis_size"}

_EXEMPT = ("src/repro/compat.py",)


def _in_scope(rel_path: str) -> bool:
    return rel_path not in _EXEMPT


def _attr_chain(node: ast.Attribute) -> List[str]:
    parts: List[str] = []
    cur: ast.AST = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return list(reversed(parts))


def _mentions_shimmed(dotted: str) -> bool:
    return any(part in SHIMMED_NAMES for part in dotted.split("."))


@rule("R4", "shard-map-via-compat",
      "shard_map/axis_size must come from repro.compat, never "
      "jax.experimental / jax.lax directly", _in_scope)
def check_compat_imports(ctx: ModuleContext) -> Iterable[Finding]:
    findings = []
    flagged_lines = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if (alias.name.startswith("jax.experimental")
                        and _mentions_shimmed(alias.name)):
                    findings.append(ctx.finding(
                        "R4", node,
                        f"direct `import {alias.name}` — shard_map's home "
                        "moves between jax versions; import the shim from "
                        "repro.compat instead"))
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if not (mod == "jax.experimental"
                    or mod.startswith("jax.experimental.")):
                continue
            bad = [a.name for a in node.names
                   if a.name in SHIMMED_NAMES] if not _mentions_shimmed(
                       mod) else [a.name for a in node.names]
            if bad:
                findings.append(ctx.finding(
                    "R4", node,
                    f"`from {mod} import {', '.join(bad)}` bypasses "
                    "repro.compat — the only sanctioned import site for "
                    f"{sorted(SHIMMED_NAMES)}"))
        elif isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if node.lineno in flagged_lines:
                continue
            if (len(chain) >= 3 and chain[:2] == ["jax", "experimental"]
                    and any(p in SHIMMED_NAMES for p in chain[2:])):
                flagged_lines.add(node.lineno)
                findings.append(ctx.finding(
                    "R4", node,
                    f"direct attribute access `{'.'.join(chain)}` — use "
                    "the repro.compat shim so jax version bumps stay a "
                    "one-file change"))
            elif (node.attr == "axis_size" and len(chain) >= 2
                  and chain[-2] == "lax"):
                flagged_lines.add(node.lineno)
                findings.append(ctx.finding(
                    "R4", node,
                    f"`{'.'.join(chain)}` used directly — use "
                    "repro.compat.axis_size"))
    return findings
