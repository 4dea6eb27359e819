"""The phases of a federated round are named in the compiled program.

Every phase opens a ``jax.named_scope`` (``driver.ROUND_SCOPES``) and every
compressor family's implementation one of its own
(``compressors.COMPRESS_SCOPES``).  A scope is metadata: it must reach the
optimized HLO as part of the instructions' ``op_name`` even under the
grid's ``vmap`` over a traced family id, where every branch of the family
switch over the spec's family set runs, and the branches of families the
set leaves out are not in the program at all.  Also here: the compile
clock of ``compile_cache`` and the host seconds of ``run_plan``.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import api, compressors, driver
from repro.core.compressors import stack_specs
from repro.core.flecs import FlecsConfig
from repro.data.logreg import make_problem
from repro.launch import compile_cache
from repro.optim.baselines import DianaConfig, DianaHParams

FAMILIES = ("identity", "dither16", "natural", "topk0.25", "count_sketch8",
            "minmax0.25")


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _has(names, scope: str) -> bool:
    return any(re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)", n)
               for n in names)


def _compile(method: str, cfg, hp, prob):
    spec = api.get_method(method)
    state = spec.init(prob, prob.A.shape[0], cfg)
    G = jax.tree.leaves(hp)[0].shape[0]
    keys = driver.sweep_keys(jax.random.key(0), G, 2)
    fn = driver.sweep_program(spec.sweep_step(prob, cfg), 2,
                              record=lambda st: prob.metrics(st.w))
    return jax.jit(fn).lower(hp, state, keys).compile()


@pytest.fixture(scope="module")
def prob():
    return make_problem(d=24, n_workers=3, r=8, seed=0)


def test_round_scope_names():
    assert driver.ROUND_SCOPES == ("fed.oracle", "fed.compress.grad",
                                   "fed.compress.hess", "fed.curvature",
                                   "fed.server", "fed.record")
    ids = (compressors.FAMILY_IDENTITY, compressors.FAMILY_DITHER,
           compressors.FAMILY_NATURAL, compressors.FAMILY_TOPK,
           compressors.FAMILY_COUNT_SKETCH, compressors.FAMILY_MINMAX)
    assert [compressors.COMPRESS_SCOPES[i] for i in ids] == [
        "compress.identity", "compress.dither", "compress.natural",
        "compress.topk", "compress.count_sketch", "compress.minmax"]


def test_flecs_cgd_program_names_every_phase_and_family(prob):
    cfg = FlecsConfig(m=2)
    hp = api.get_method("flecs_cgd").grid(
        grad_specs=stack_specs(*FAMILIES), hess_specs=stack_specs(*FAMILIES))
    names = _op_names(_compile("flecs_cgd", cfg, hp, prob))
    for scope in driver.ROUND_SCOPES:
        assert _has(names, scope), scope
    for scope in compressors.COMPRESS_SCOPES[1:]:     # identity has no ops
        for msg in ("fed.compress.grad", "fed.compress.hess"):
            assert any(msg in n and scope in n for n in names), (msg, scope)


@pytest.mark.parametrize("extra, present", [
    ((), ("compress.dither", "compress.topk")),
    (("count_sketch64",), ("compress.dither", "compress.topk",
                           "compress.count_sketch")),
], ids=["cell_axis", "with_count_sketch"])
def test_family_axis_compiles_only_its_branches(prob, extra, present):
    """The benchmark cell's plan: gradient family axis {dither64,
    topk0.1}, Hessian messages dither64 at every point.  Its optimized
    program holds the branches of those families only; a family added
    to the axis brings its branch back."""
    cfg = FlecsConfig(m=2, hessian_update="direct", direction="fedsonia",
                      use_kernel=True)
    hp = api.get_method("flecs_cgd").grid(
        alphas=(0.5,), grad_specs=stack_specs("dither64", "topk0.1", *extra))
    names = _op_names(_compile("flecs_cgd", cfg, hp, prob))
    for scope in compressors.COMPRESS_SCOPES[1:]:
        assert _has(names, scope) == (scope in present), scope
    hess = {n for n in names if "fed.compress.hess" in n}
    assert any("compress.dither" in n for n in hess)
    for scope in ("compress.topk", "compress.count_sketch"):
        assert not any(scope in n for n in hess), scope


def _computations(text) -> dict:
    """HLO text by computation: name -> its instruction lines."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif name and line.startswith(" "):
            comps[name].append(line)
    return comps


def _while_body(text) -> list:
    """The instructions of every while body and of what it calls."""
    comps = _computations(text)
    todo = [c for lines in comps.values() for line in lines
            for c in re.findall(r"\bbody=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"\b(?:calls|to_apply|body|condition)="
                               r"%([\w.\-]+)", line)
            for group in re.findall(r"\b(?:branch|called)_computations="
                                    r"\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return [line for c in seen for line in comps[c]]


def _cell_plan(prob):
    cfg = FlecsConfig(m=2, hessian_update="direct", direction="fedsonia",
                      use_kernel=True)
    hp = api.get_method("flecs_cgd").grid(
        alphas=(0.5,), grad_specs=stack_specs("dither64", "topk0.1"))
    return cfg, hp


def test_curvature_update_neither_copies_nor_transposes_B(prob):
    """The benchmark cell's plan updates B in one pass: no instruction of
    the round loop copies or transposes a whole [G, n, d, d] state."""
    cfg, hp = _cell_plan(prob)
    (n, _, d), G = prob.A.shape, 2
    text = _compile("flecs_cgd", cfg, hp, prob).as_text()
    body = _while_body(text)
    assert any(f"f32[{G},{n},{d},{d}]" in line for line in body)
    whole_B = re.compile(rf"= f32\[{G},{n},{d},{d}\]\{{[^}}]*\}} "
                         r"(copy|transpose)\(")
    assert not [line for line in body if whole_B.search(line)]


def test_engine_B_stays_symmetric(prob):
    """B is symmetrised only through the update's m×m factor: after 30
    rounds every worker's B is still symmetric to float32 rounding."""
    cfg, hp = _cell_plan(prob)
    spec, T = api.get_method("flecs_cgd"), 30
    state = spec.init(prob, prob.A.shape[0], cfg)
    keys = driver.sweep_keys(jax.random.key(1), 2, T)
    fn = driver.sweep_program(spec.sweep_step(prob, cfg), T)
    B = jax.jit(fn)(hp, state, keys)[0].B                  # [G, n, d, d]
    asym = jnp.linalg.norm(B - B.swapaxes(-1, -2), axis=(-2, -1))
    assert float(jnp.min(jnp.linalg.norm(B, axis=(-2, -1)))) > 0
    assert float(jnp.max(asym / jnp.linalg.norm(B, axis=(-2, -1)))) <= 1e-6


def test_diana_program_names_its_phases_and_families(prob):
    G = len(FAMILIES)
    hp = DianaHParams(jnp.full((G,), 0.5), jnp.full((G,), 0.5),
                      stack_specs(*FAMILIES))
    names = _op_names(_compile("diana", DianaConfig(), hp, prob))
    for scope in ("fed.oracle", "fed.compress.grad", "fed.server",
                  "fed.record"):
        assert _has(names, scope), scope
    for scope in ("fed.compress.hess", "fed.curvature"):
        assert not _has(names, scope), scope
    for scope in compressors.COMPRESS_SCOPES[1:]:
        assert _has(names, scope), scope


def test_scopes_are_metadata_only(prob):
    """The same round with the scopes taken away compiles to the same
    instructions: strip the metadata and the instruction numbering."""
    cfg = FlecsConfig(m=2)
    hp = api.get_method("flecs_cgd").grid(
        grad_specs=stack_specs("dither16", "topk0.25"))

    def plain(text):
        body = text[text.index("\n%"):]           # the computations
        body = re.sub(r", metadata=\{[^}]*\}", "", body)
        seen = {}
        return re.sub(r"%([\w.\-]+)", lambda m: "%v" + str(
            seen.setdefault(m.group(1), len(seen))), body)

    scoped = _compile("flecs_cgd", cfg, hp, prob).as_text()
    # the round's ``with`` scopes open nothing; the family scopes,
    # decorators applied at import, stay
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        unscoped = _compile("flecs_cgd", cfg, hp, prob).as_text()
    assert "fed.oracle" in scoped and "fed.oracle" not in unscoped
    assert plain(scoped) == plain(unscoped)


def _dl_step_text(m):
    """The DL trainer's FLECS-CGD step for a small published-form mamba2
    (conv bias, tied embedding unscaled), compiled on one CPU device."""
    import dataclasses

    import numpy as np
    from repro.configs import get_config
    from repro.core.dl_flecs import FlecsDLConfig, make_flecs_train_step
    from repro.launch.sharding import batch_specs, named_shardings
    from repro.models.context import ModelContext
    from repro.models.model import init_params
    cfg = dataclasses.replace(get_config("mamba2-1.3b", smoke=True),
                              tie_embeddings=True, embed_multiplier=1.0)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    ctx = ModelContext(mesh=mesh, data_axes=("data",), remat=True)
    pa = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0),
                                            jnp.float32))
    ba = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
          for k in ("tokens", "labels")}
    step = make_flecs_train_step(cfg, ctx, FlecsDLConfig(m=m))
    return step(pa, ba, named_shardings(pa, mesh), named_shardings(
        ba, mesh, batch_specs(ba, mesh, ("data",)))).compile().as_text()


def test_dl_step_names_every_phase():
    """One DL step is one FLECS-CGD round: its phases carry the round
    scopes, the quantizer ``compress.dither``, the SSD scan ``ssm.ssd``;
    taking the ``with`` scopes away changes nothing but metadata."""
    scoped = _dl_step_text(1)
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for scope in ("fed.oracle", "fed.compress.grad", "fed.compress.hess",
                  "fed.server", "ssm.ssd"):
        assert _has(names, scope), scope
    for msg in ("fed.compress.grad", "fed.compress.hess"):
        assert any(msg in n and "compress.dither" in n for n in names), msg
    assert any("fed.oracle" in n and "ssm.ssd" in n for n in names)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        unscoped = _dl_step_text(1)
    assert "fed.oracle" not in unscoped

    def plain(text):
        body = text[text.index("\n%"):]
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    assert plain(scoped) == plain(unscoped)


def test_compile_clock_counts_nested_spans_once():
    trace, lower, backend = compile_cache.COMPILE_EVENTS
    got = compile_cache.span_seconds([
        (trace, 100.0, 104.0), (trace, 101.0, 102.0),   # traced inside
        (lower, 104.0, 104.5), (backend, 104.5, 106.0),
        (backend, 110.0, 111.0)])
    assert got == {trace: 5.0, lower: 0.5, backend: 2.5, "total": 7.0}


def test_compile_clock_sees_a_jit_compile():
    compile_cache.start_compile_clock()
    before = compile_cache.compile_seconds()["total"]
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25).lower(
        jnp.ones((7, 5))).compile()
    assert compile_cache.compile_seconds()["total"] > before


def test_run_plan_times_compile_and_run_apart(prob):
    plan = api.ExperimentPlan(problem=prob, runs=(api.MethodRun("diana"),),
                              iters=3, seed=0)
    res = api.run_plan(plan)
    assert res.compile_s > 0 and res.run_s > 0
    assert res.seconds == pytest.approx(res.compile_s + res.run_s)
