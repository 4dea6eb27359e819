"""Layer 2 — semantic consistency checks (imports jax, compiles NOTHING).

Three checkers, each returning a list of human-readable failure strings
(empty = pass):

* :func:`check_switch_tables` — the compressor family registry vs the
  branch tables in ``compressors.py``: the FAMILY_* ids must be exactly
  0..N-1 (a switch clamps out-of-range indices SILENTLY, so a gap or
  duplicate would route a family to the wrong branch), and each of
  ``compress`` / ``spec_bits`` / ``spec_omega`` must hand its dispatch
  (``lax.switch``, or ``_family_switch``, which narrows the table to the
  spec's family set) exactly N branches (checked on the AST — a
  forgotten branch after adding a family is the regression this
  guards).
* :func:`check_round_bits` — every registered :class:`MethodSpec` prices a
  toy problem consistently: grid-shaped output, finite and positive,
  per-point slices agree with the full-grid query (the
  ``spec_bits_many`` vmap path vs its scalar path), and the price matches
  the method's documented wire formula recomputed from ``spec_bits_many``
  directly.
* :func:`check_jaxpr` — ``jax.make_jaxpr`` on every method's sweep step
  and 2-round sweep program at toy shapes (host-side tracing only; no
  device compile): no side-effecting primitives anywhere in the scan
  bodies, every ``bits``-named output leaf carries ``bits_dtype()``, the
  grid axis survives to every output leaf, and every declared hparam leaf
  is actually consumed by the step (a declared-but-dead sweep axis means
  the figure's axis labels lie) — except a compressor-spec leaf that the
  compressor algebra itself never reads for the spec's family set.

:func:`run_semantic_checks` runs all three — the CLI's ``--layer 2``.
"""
from __future__ import annotations

import ast
import inspect
from typing import Dict, List

#: Toy problem shapes — big enough to make every code path real (top-k
#: keeps >= 1 of 12; the sketch m=1 column is non-trivial), small enough
#: that host-side tracing is instant.
TOY = dict(d=12, n_workers=3, r=4)

#: Grid axes exercised per method (2 points each, varying the wire price).
METHOD_GRIDS = {
    "flecs": dict(hess_levels=(16.0, 64.0)),
    "flecs_cgd": dict(hess_levels=(16.0, 64.0)),
    "diana": dict(levels=(16.0, 64.0)),
    "fednl": dict(fracs=(0.25, 0.5)),
    "gd": dict(alphas=(1.0, 2.0)),
}

_SWITCH_FNS = ("compress", "spec_bits", "spec_omega")

#: The calls that dispatch on the family id, each taking the full branch
#: table second: ``lax.switch``, and ``compressors._family_switch``, which
#: switches over the table's entries for the spec's family set only.
_DISPATCH_CALLS = ("switch", "_family_switch")


def _toy_problem():
    from repro.data.logreg import make_problem
    return make_problem(**TOY)


def _method_grid(name: str, spec):
    return spec.grid(**METHOD_GRIDS.get(name, {}))


# ---------------------------------------------------------------------------
# switch tables
# ---------------------------------------------------------------------------

def _switch_branch_counts(source: str) -> Dict[str, List[int]]:
    """{function name: [branch counts of each family dispatch call in
    it]} for the spec-dispatched entry points."""
    tree = ast.parse(source)
    out: Dict[str, List[int]] = {}
    for fn in tree.body:
        if not isinstance(fn, ast.FunctionDef) or fn.name not in _SWITCH_FNS:
            continue
        counts = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = (node.func.attr if isinstance(node.func, ast.Attribute)
                      else getattr(node.func, "id", None))
            if callee not in _DISPATCH_CALLS:
                continue
            if len(node.args) < 2:
                counts.append(-1)
            elif isinstance(node.args[1], (ast.Tuple, ast.List)):
                counts.append(len(node.args[1].elts))
            else:
                counts.append(-1)   # non-literal branch table: opaque
        out[fn.name] = counts
    return out


def check_switch_tables() -> List[str]:
    from repro.core import compressors

    problems: List[str] = []
    families = {name: getattr(compressors, name)
                for name in dir(compressors) if name.startswith("FAMILY_")}
    if not families:
        return ["compressors.py defines no FAMILY_* ids"]
    ids = sorted(families.values())
    n = len(families)
    if ids != list(range(n)):
        problems.append(
            f"FAMILY_* ids must be exactly 0..{n - 1} (lax.switch clamps "
            f"out-of-range ids silently); got {families}")

    source = inspect.getsource(compressors)
    counts = _switch_branch_counts(source)
    for fn in _SWITCH_FNS:
        got = counts.get(fn)
        if not got:
            problems.append(
                f"compressors.{fn} has no family dispatch (lax.switch or "
                "_family_switch) — the family registry and its branch "
                "table have diverged")
        elif any(c != n for c in got):
            problems.append(
                f"compressors.{fn}: branch table size {got} != "
                f"{n} registered families {sorted(families)} — every "
                "family needs exactly one branch in every table")
    return problems


# ---------------------------------------------------------------------------
# round_bits price queries
# ---------------------------------------------------------------------------

def _expected_prices(name: str, prob, cfg, hp):
    """The documented wire formula of each method, recomputed directly
    from ``spec_bits_many`` — the consistency target for ``round_bits``."""
    import jax.numpy as jnp

    from repro.core.compressors import spec_bits_many

    d = prob.d
    if name in ("flecs", "flecs_cgd"):
        return (spec_bits_many(hp.grad_spec, d)
                + spec_bits_many(hp.hess_spec, d * cfg.m)
                + 32.0 * cfg.m * cfg.m)
    if name == "diana":
        return spec_bits_many(hp.spec, d)
    if name == "fednl":
        return 32.0 * d + spec_bits_many(hp.spec, d * d)
    if name == "gd":
        return jnp.broadcast_to(jnp.float32(32.0 * d), jnp.shape(hp.alpha))
    return None


def check_round_bits() -> List[str]:
    import jax
    import numpy as np

    from repro.core.api import get_method, method_names

    problems: List[str] = []
    prob = _toy_problem()
    for name in method_names():
        spec = get_method(name)
        if spec.round_bits is None:
            problems.append(f"{name}: MethodSpec.round_bits is None — "
                            "budget-fair plans cannot price this method")
            continue
        cfg = spec.default_config()
        hp = _method_grid(name, spec)
        G = jax.tree.leaves(hp)[0].shape[0]
        prices = np.asarray(spec.round_bits(prob, cfg, hp), float)
        if prices.shape != (G,):
            problems.append(
                f"{name}: round_bits shape {prices.shape} != grid ({G},)")
            continue
        if not np.all(np.isfinite(prices)) or not np.all(prices > 0):
            problems.append(
                f"{name}: round_bits must be finite and positive, got "
                f"{prices}")
            continue
        # grid query vs per-point slices: the spec_bits_many vmap path
        # must agree with its scalar path at every grid point
        for g in range(G):
            hp_g = jax.tree.map(lambda a: a[g:g + 1], hp)
            p_g = float(np.asarray(spec.round_bits(prob, cfg, hp_g))[0])
            if not np.isclose(p_g, prices[g], rtol=1e-6):
                problems.append(
                    f"{name}: grid point {g} prices {prices[g]} in the "
                    f"full grid but {p_g} as a [1] slice — "
                    "spec_bits_many's vmap and scalar paths disagree")
        expected = _expected_prices(name, prob, cfg, hp)
        if expected is not None and not np.allclose(
                prices, np.asarray(expected, float), rtol=1e-6):
            problems.append(
                f"{name}: round_bits {prices} != documented wire formula "
                f"{np.asarray(expected, float)} recomputed from "
                "spec_bits_many")
    return problems


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr nested in its eqn params
    (scan/cond/switch bodies, custom_jvp internals, ...)."""
    import jax.extend.core as jex_core
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for v in vals:
                inner = getattr(v, "jaxpr", v)
                if isinstance(inner, jex_core.Jaxpr):
                    yield from _iter_jaxprs(inner)


def _side_effecting(prim_name: str) -> bool:
    return ("callback" in prim_name or "infeed" in prim_name
            or "outfeed" in prim_name or prim_name == "debug_print")


def _leaf_paths(tree_value):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree_value)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _consumed(closed) -> List[bool]:
    """Per input of a closed jaxpr: does an equation or output read it?"""
    used = set()
    for eqn in closed.jaxpr.eqns:
        used.update(map(id, eqn.invars))
    used.update(map(id, closed.jaxpr.outvars))
    return [id(v) in used for v in closed.jaxpr.invars]


def _spec_leaves_unread(hp) -> set:
    """Paths of the compressor-spec leaves in ``hp`` that ``compress``,
    ``spec_bits`` and ``spec_omega`` never read for the spec's family set.
    ``spec_bits`` keeps its full switch and reads ``family``, ``s``,
    ``frac``, ``width`` and ``depth`` of every spec, so in practice these
    are the sketch slots only the count-sketch branches read, such as
    ``hh_frac`` of a spec without count sketch.  The set pins them, so a
    step that does not consume them drops no sweep axis."""
    import jax
    import jax.numpy as jnp

    from repro.core.compressors import (CompressorSpec, compress,
                                        spec_bits, spec_omega)

    d = TOY["d"]
    x = jnp.zeros((d,), jnp.float32)

    def algebra(spec):
        return (compress(spec, jax.random.key(0), x), spec_bits(spec, d),
                spec_omega(spec, d))

    unread = set()
    nodes, _ = jax.tree_util.tree_flatten_with_path(
        hp, is_leaf=lambda v: isinstance(v, CompressorSpec))
    for path, spec in nodes:
        if not isinstance(spec, CompressorSpec):
            continue
        flat, _ = jax.tree_util.tree_flatten_with_path(spec)
        read = _consumed(jax.make_jaxpr(algebra)(spec))
        unread.update(jax.tree_util.keystr(path + sub)
                      for (sub, _), r in zip(flat, read) if not r)
    return unread


def check_jaxpr() -> List[str]:
    import jax
    import numpy as np

    from repro.core.api import get_method, method_names
    from repro.core.driver import bits_dtype, sweep_keys, sweep_program

    problems: List[str] = []
    prob = _toy_problem()
    n = prob.n_workers
    iters = 2
    for name in method_names():
        spec = get_method(name)
        cfg = spec.default_config()
        hp = _method_grid(name, spec)
        G = jax.tree.leaves(hp)[0].shape[0]
        state = spec.init(prob, n, cfg)
        step = spec.sweep_step(prob, cfg)

        # (a) one step at one grid point: every declared hparam leaf must
        # be consumed (a dead sweep axis mislabels the figure)
        hp0 = jax.tree.map(lambda a: a[0], hp)
        closed = jax.make_jaxpr(step)(hp0, state, jax.random.key(0))
        pinned = _spec_leaves_unread(hp0)
        hp_names = [p for p, _ in _leaf_paths(hp0)]
        for leaf_name, used in zip(hp_names, _consumed(closed)):
            if not used and leaf_name not in pinned:
                problems.append(
                    f"{name}: declared hparam leaf {leaf_name} is never "
                    "consumed by the step — the sweep axis is dead and "
                    "its grid labels lie")

        # (b) the full 2-round sweep program: no side-effecting
        # primitives anywhere (a stray debug callback inside the scan
        # body would serialize — or under jit, crash — every figure)
        prog = sweep_program(step, iters)
        keys = sweep_keys(jax.random.key(0), G, iters)
        closed_prog = jax.make_jaxpr(prog)(hp, state, keys)
        if closed_prog.effects:
            problems.append(
                f"{name}: sweep program carries jax effects "
                f"{closed_prog.effects} — scan bodies must be pure")
        for sub in _iter_jaxprs(closed_prog.jaxpr):
            for eqn in sub.eqns:
                if _side_effecting(eqn.primitive.name):
                    problems.append(
                        f"{name}: side-effecting primitive "
                        f"{eqn.primitive.name!r} inside the traced "
                        "program")

        # (c) output contracts via eval_shape (no device work): bits
        # ledgers keep bits_dtype(), and the [G] grid axis reaches every
        # output leaf
        out = jax.eval_shape(prog, hp, state, keys)
        want = np.dtype(bits_dtype())
        for path, leaf in _leaf_paths(out):
            if "bits" in path and leaf.dtype != want:
                problems.append(
                    f"{name}: output leaf {path} has dtype {leaf.dtype}, "
                    f"ledgers must carry bits_dtype()={want}")
            if leaf.ndim == 0 or leaf.shape[0] != G:
                problems.append(
                    f"{name}: output leaf {path} shape {leaf.shape} lost "
                    f"the [{G}] grid axis")
    return problems


def run_semantic_checks() -> List[str]:
    """All layer-2 checks; list of failures (empty = pass)."""
    problems = []
    for check in (check_switch_tables, check_round_bits, check_jaxpr):
        try:
            problems.extend(check())
        except Exception as e:   # a crashed checker is itself a finding
            problems.append(f"{check.__name__} raised "
                            f"{type(e).__name__}: {e}")
    return problems


__all__ = ["check_switch_tables", "check_round_bits", "check_jaxpr",
           "run_semantic_checks", "TOY", "METHOD_GRIDS"]
