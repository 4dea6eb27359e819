"""``correct`` in the cross-silo cells: true on a sound run, false with a
fault under the timed path, and false for the control."""
import pytest

from bench.tests import tiny
from bench.tests.control import control_fails

CELLS = ("xsilo.flecs-cgd", "xsilo.diana")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = tiny.run(cell)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0
    assert r["compared"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("fault", ("state_unchanged", "half_batch",
                                   "answer_altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault):
    r = tiny.run(cell, fault=fault)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("fault", ("curvature_stale",
                                   "curvature_half_beta"))
def test_curvature_fault_is_caught(fault):
    r = tiny.run("xsilo.flecs-cgd", fault=fault)
    assert not r["correct"], r["compared"]
    assert r["compared"]["B_rel"]["value"] > r["compared"]["B_rel"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    assert control_fails(cell)


@pytest.mark.parametrize("cell", CELLS)
def test_timed_program_matches_run_plan(cell):
    """The cell's program, with run_plan's key stream, gives what
    ``api.run_plan`` gives for the same one-run plan."""
    import jax
    import numpy as np
    from bench import harness, spec as bspec
    from repro.core.api import ExperimentPlan, MethodRun, run_plan
    from repro.core.driver import sweep_keys
    from repro.data.logreg import make_problem

    jax.config.update("jax_enable_compilation_cache", False)
    config, traffic = tiny.SIZES[cell]
    seed = 3
    c, _ = harness.build_cell(tiny.spec_with_open_cells(), cell, seed,
                              jax.devices()[:1], config, traffic)
    c.setup()
    spec, cfg, hp = c._plan()
    keys = sweep_keys(jax.random.fold_in(jax.random.key(seed), 0),
                      len(c.points), c.T)
    mod = bspec.load_module(bspec.config_module_path("xsilo-gisette-n20"),
                            "xsilo_keys")
    np.testing.assert_array_equal(                  # the harness's copy
        jax.random.key_data(mod.make_keys(jax.random.fold_in(
            jax.random.key(seed), 0), len(c.points), c.T)),
        jax.random.key_data(keys))
    st, tr = c.compiled(*c.args[:-1], keys)
    prob = make_problem(d=c.d, n_workers=c.n, r=c.r, mu=c.mu, seed=seed)
    ref = run_plan(ExperimentPlan(problem=prob, runs=(MethodRun(
        spec.name, cfg=cfg, hparams=hp),), iters=c.T, seed=seed))
    st_p, tr_p = ref[spec.name]
    np.testing.assert_allclose(tr["F"], tr_p["F"], rtol=1e-6)
    np.testing.assert_allclose(st.w, st_p.w, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(st.bits_per_node, st_p.bits_per_node)
