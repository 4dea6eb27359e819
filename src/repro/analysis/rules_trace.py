"""R1/R2 — trace-safety rules for the step bodies that run under lax.scan.

Scope resolution (shared by both rules): within each module under
``src/repro/core/`` or ``src/repro/optim/``, the *traced set* is

* every function whose whole body IS a traced round (``_flecs_round``), and
* every function def nested inside a step factory (``make_*_step`` /
  ``make_*_sweep_step``) or a method's async-hooks factory
  (``make_*_async_hooks``) — the closures those factories return are
  exactly the step/scan bodies ``driver.run_experiment`` compiles, and
* every module-level function transitively *called* from either of the
  above (per-module resolution: cross-module calls such as
  ``driver.masked_mean`` are linted when their own module is linted).

The factory's own top-level statements are NOT traced (they run once at
build time — ``hp = hparams_from_config(cfg)`` may call ``float``/``int``
freely); only the nested defs are.

R1 forbids Python ``for``/``while`` inside the traced set: an unrolled
round/worker loop compiles O(iters·n) copies of the step and silently
breaks the one-compile-per-figure invariant — rounds belong to lax.scan,
workers to vmap.  ``TRACED_LOOP_ALLOWLIST`` carries the deliberate
exceptions with their justifications (currently ``dl_flecs.py``: loops
over pytree leaves and sketch columns unroll over *static model
structure*, never over rounds or workers).

R2 forbids host synchronization on traced values inside the traced set:
``float()``/``int()``/``bool()`` casts, ``.item()``, and
``np.asarray``/``np.array`` all force a device sync (or a
ConcretizationTypeError under jit) — a single one inside a scan body
serializes the whole program.  Constructor/config paths
(``make_spec`` and friends) are outside the traced set and stay
allowed.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from repro.analysis.engine import Finding, ModuleContext, rule

#: Factory / round-function names whose closures form the traced set.
TRACED_ROOT_RE = re.compile(r"^(make_\w+_(step|hooks)|_flecs_round)$")

#: (file basename, root function name) -> justification.  Loops inside
#: these roots' traced closures are deliberate and safe.
TRACED_LOOP_ALLOWLIST: Dict[Tuple[str, str], str] = {
    ("dl_flecs.py", "make_flecs_train_step"):
        "per-tensor and sketch-column loops unroll over the STATIC pytree "
        "structure / m sketch columns of the model — never over rounds or "
        "workers (those stay in the trainer's scan/mesh axes)",
}

_LOOP_NODES = (ast.For, ast.While, ast.AsyncFor)
_HOST_CASTS = {"float", "int", "bool"}
_NUMPY_ALIASES = {"np", "numpy"}
_NUMPY_SYNC_FNS = {"asarray", "array"}


def _in_scope(rel_path: str) -> bool:
    return rel_path.startswith(("src/repro/core/", "src/repro/optim/"))


def _module_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _called_names(node: ast.AST) -> Set[str]:
    return {c.func.id for c in ast.walk(node)
            if isinstance(c, ast.Call) and isinstance(c.func, ast.Name)}


def _nested_defs(fn: ast.AST) -> List[ast.AST]:
    return [sub for sub in ast.walk(fn)
            if sub is not fn and isinstance(
                sub, (ast.FunctionDef, ast.AsyncFunctionDef))]


def traced_scopes(ctx: ModuleContext):
    """Yield (root_name, function_node) for every function in the module's
    traced set (see the module docstring for the definition).  A function
    and each of its nested defs are separate entries, so rules can treat
    every def as its own scope without double-reporting."""
    functions = _module_functions(ctx.tree)
    seeds: List[Tuple[str, ast.AST]] = []
    for name, fn in functions.items():
        if not TRACED_ROOT_RE.match(name):
            continue
        if name.startswith("make_"):
            # factory: the traced parts are its nested function defs
            seeds.extend((name, sub) for sub in _nested_defs(fn))
        else:
            # a round function: it and its nested defs are all traced
            seeds.append((name, fn))
            seeds.extend((name, sub) for sub in _nested_defs(fn))

    # transitively pull in module-level helpers called from traced code
    visited = {id(node) for _, node in seeds}
    claimed = {node.name for _, node in seeds if hasattr(node, "name")}
    frontier = list(seeds)
    while frontier:
        root, node = frontier.pop()
        for callee in sorted(_called_names(node)):
            target = functions.get(callee)
            if target is None or id(target) in visited:
                continue
            if callee in claimed or TRACED_ROOT_RE.match(callee):
                continue
            claimed.add(callee)
            for new in [target] + _nested_defs(target):
                if id(new) in visited:
                    continue
                visited.add(id(new))
                entry = (root, new)
                seeds.append(entry)
                frontier.append(entry)
    return seeds


@rule("R1", "no-python-loops-in-traced-step",
      "traced step/scan bodies must not loop over rounds/workers in "
      "Python (lax.scan / vmap instead)", _in_scope)
def check_python_loops(ctx: ModuleContext) -> Iterable[Finding]:
    findings = []
    for root, fn in traced_scopes(ctx):
        if (ctx.name, root) in TRACED_LOOP_ALLOWLIST:
            continue
        nested = {id(sub) for sub in ast.walk(fn)
                  if sub is not fn and isinstance(
                      sub, (ast.FunctionDef, ast.AsyncFunctionDef))}

        def local_walk(node):
            # stay inside THIS function: nested defs are their own scopes
            # (they are separate traced_scopes entries)
            for child in ast.iter_child_nodes(node):
                if id(child) in nested:
                    continue
                yield child
                yield from local_walk(child)

        for sub in local_walk(fn):
            if isinstance(sub, _LOOP_NODES):
                kind = "while" if isinstance(sub, ast.While) else "for"
                findings.append(ctx.finding(
                    "R1", sub,
                    f"Python `{kind}` loop inside traced step body "
                    f"{fn.name!r} (reached from {root!r}) — rounds belong "
                    "to lax.scan, workers to vmap; a deliberate "
                    "static-structure unroll needs an entry in "
                    "TRACED_LOOP_ALLOWLIST or a justified suppression"))
    return findings


def _is_numpy_sync(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr in _NUMPY_SYNC_FNS
            and isinstance(f.value, ast.Name)
            and f.value.id in _NUMPY_ALIASES)


@rule("R2", "no-host-sync-in-traced-step",
      "traced step bodies must not host-sync traced values "
      "(float()/int()/.item()/np.asarray)", _in_scope)
def check_host_sync(ctx: ModuleContext) -> Iterable[Finding]:
    findings = []
    seen: Set[int] = set()
    for root, fn in traced_scopes(ctx):
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or id(sub) in seen:
                continue
            seen.add(id(sub))
            what = None
            if isinstance(sub.func, ast.Name) and sub.func.id in _HOST_CASTS:
                what = f"`{sub.func.id}()` cast"
            elif (isinstance(sub.func, ast.Attribute)
                  and sub.func.attr == "item"):
                what = "`.item()`"
            elif _is_numpy_sync(sub):
                what = f"`{sub.func.value.id}.{sub.func.attr}()`"
            if what is not None:
                findings.append(ctx.finding(
                    "R2", sub,
                    f"{what} inside traced step body {fn.name!r} (reached "
                    f"from {root!r}) forces a host sync / concretization "
                    "under jit — keep traced values on device (jnp casts, "
                    "lax.cond) or move the conversion to the constructor "
                    "path"))
    return findings
