"""The invariant linter (repro.analysis): every rule fires on its
known-bad fixture (and ONLY its rule), suppressions and the R0 meta-rule
behave, the real tree is clean, and the layer-2 semantic checkers pass on
all five registered methods.
"""
import textwrap

import pytest

from repro.analysis import lint_source
from repro.analysis.engine import META_RULE
from repro.analysis.rules_pytree import hparam_classes, load_snapshot

CORE = "src/repro/core/_fixture.py"   # virtual path inside R1/R2 scope


def rules_fired(findings, include_suppressed=False):
    return {f.rule for f in findings
            if include_suppressed or not f.suppressed}


# One known-bad snippet per rule.  Each must fire EXACTLY its rule —
# cross-firing fixtures would mean the rules' scopes overlap confusingly.
RULE_FIXTURES = {
    "R1": (CORE, """
        import jax

        def make_demo_step(cfg):
            def step(hp, state, key):
                for i in range(3):
                    state = state + i
                return state, {}
            return step
        """),
    "R2": (CORE, """
        import jax.numpy as jnp

        def make_demo_step(cfg):
            def step(hp, state, key):
                lr = float(hp.alpha)
                return state - lr * state, {"lr": lr}
            return step
        """),
    "R3": (CORE, """
        import jax.numpy as jnp

        def init(n):
            bits_per_node = jnp.zeros((n,), jnp.float32)
            return bits_per_node
        """),
    "R4": (CORE, """
        from jax.experimental.shard_map import shard_map
        """),
    "R5": (CORE, """
        from typing import NamedTuple

        class DemoHParams(NamedTuple):
            alpha: float
        """),
    # a cohort-engine scan body allocating a full-population intermediate
    "R7": (CORE, """
        import jax
        import jax.numpy as jnp

        def make_demo_cohort_sweep_step(cfg, n_total, cohort):
            def step(hp, state, key):
                noise = jax.random.uniform(key, (n_total,))
                return state, {"noise": jnp.sum(noise)}
            return step
        """),
    # a traced step materializing a rate schedule from a Python loop
    # (list comprehensions are not R1's loop statements, and jnp calls
    # are not R2's host syncs — the fixture fires R8 alone)
    "R8": (CORE, """
        import jax.numpy as jnp

        def make_demo_traffic_sweep_step(cfg):
            def step(hp, state, key):
                rate_table = jnp.stack(
                    [hp.rate * (t % 24) for t in range(24)])
                return state, {"r": jnp.sum(rate_table)}
            return step
        """),
    # a kernel launcher in a package with no ref.py oracle (the demo/
    # package does not exist on disk, so the pairing probe fails)
    "R6": ("src/repro/kernels/demo/demo.py", """
        import jax
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x):
            return pl.pallas_call(
                _kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """),
}


@pytest.mark.parametrize("rule_id", sorted(RULE_FIXTURES))
def test_rule_fires_on_its_fixture_only(rule_id):
    path, src = RULE_FIXTURES[rule_id]
    findings = lint_source(textwrap.dedent(src), path)
    assert rules_fired(findings) == {rule_id}, [f.format() for f in findings]


def test_r1_loop_fixture_names_the_root():
    path, src = RULE_FIXTURES["R1"]
    (f,) = lint_source(textwrap.dedent(src), path)
    assert "make_demo_step" in f.message and f.rule == "R1"


def test_r1_ignores_factory_build_time_and_out_of_scope_paths():
    src = textwrap.dedent("""
        def make_demo_step(cfg):
            specs = [make_spec(n) for n in cfg.names]
            table = {}
            for name in cfg.names:          # build-time: runs once
                table[name] = 1

            def step(hp, state, key):
                return state, {}
            return step
        """)
    assert lint_source(src, CORE) == []
    # same loop INSIDE the step, but outside core/optim scope: not R1's job
    path, bad = RULE_FIXTURES["R1"]
    assert lint_source(textwrap.dedent(bad), "src/repro/launch/x.py") == []


def test_r2_allows_constructor_paths():
    src = textwrap.dedent("""
        def spec_from_name(name):
            return float(name[4:])

        def make_demo_step(cfg):
            spec = spec_from_name(cfg.name)   # build-time call is fine

            def step(hp, state, key):
                return state, {}
            return step
        """)
    assert lint_source(src, CORE) == []


def test_r2_follows_transitive_helpers_and_nested_defs():
    src = textwrap.dedent("""
        def _helper(x):
            def inner(v):
                return v.item()
            return inner(x)

        def make_demo_step(cfg):
            def step(hp, state, key):
                return _helper(state), {}
            return step
        """)
    findings = lint_source(src, CORE)
    assert rules_fired(findings) == {"R2"}
    assert ".item()" in findings[0].message


def test_r3_accepts_bits_dtype_and_ledger_dtype_inheritance():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        from repro.core.driver import bits_dtype

        def init(n, state):
            bits_per_node = jnp.zeros((n,), bits_dtype())
            bit_budget = jnp.zeros((n,), state.bits_per_node.dtype)
            other = jnp.zeros((n,), jnp.float32)   # not a ledger name
            return bits_per_node, bit_budget, other
        """)
    assert lint_source(src, CORE) == []


def test_r3_sees_positional_namedtuple_construction():
    src = textwrap.dedent("""
        from typing import NamedTuple
        import jax.numpy as jnp

        class State(NamedTuple):
            w: jnp.ndarray
            bits_per_node: jnp.ndarray

        def init(n):
            return State(jnp.zeros((3,)), jnp.zeros((n,)))
        """)
    findings = lint_source(src, CORE)
    assert rules_fired(findings) == {"R3"}
    assert "bits_per_node" in findings[0].message


def test_r4_flags_only_shimmed_names():
    ok = "from jax.experimental import pallas as pl\n"
    assert lint_source(ok, "src/repro/kernels/demo.py") == []
    # ... including at kernel-package depth, where R6 also applies: a
    # pallas IMPORT alone (no pallas_call launch) trips neither rule
    assert lint_source(ok, "src/repro/kernels/demo/demo.py") == []
    bad = "import jax\nsm = jax.experimental.shard_map.shard_map\n"
    assert rules_fired(lint_source(bad, CORE)) == {"R4"}
    bad2 = "import jax\nn = jax.lax.axis_size('data')\n"
    assert rules_fired(lint_source(bad2, CORE)) == {"R4"}
    # compat.py itself is the sanctioned probe site
    exempt = "from jax.experimental.shard_map import shard_map\n"
    assert lint_source(exempt, "src/repro/compat.py") == []


def test_r6_missing_ref_fires_once_and_names_the_oracle():
    path, src = RULE_FIXTURES["R6"]
    findings = lint_source(textwrap.dedent(src), path)
    assert [f.rule for f in findings] == ["R6"]          # exactly once
    assert "ref.py" in findings[0].message


def test_r6_registration_branch(tmp_path, monkeypatch):
    """With the oracle present, R6 checks the differential-test registry:
    a kernel package not mentioned in tests/test_kernels.py fires; a
    mentioned one is clean; an absent registry file skips the check."""
    from repro.analysis import rules_kernels
    pkg = tmp_path / "src" / "repro" / "kernels" / "demo"
    pkg.mkdir(parents=True)
    (pkg / "ref.py").write_text("def launch_ref(x):\n    return x\n")
    registry = tmp_path / "tests" / "test_kernels.py"
    registry.parent.mkdir()
    registry.write_text("from repro.kernels.other.ops import thing\n")
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(rules_kernels, "TEST_FILE",
                        registry.relative_to(tmp_path))
    path, src = RULE_FIXTURES["R6"]
    findings = lint_source(textwrap.dedent(src), path)
    assert rules_fired(findings) == {"R6"}
    assert "differential test" in findings[0].message
    # registering the package (any mention of repro.kernels.demo) clears it
    registry.write_text("from repro.kernels.demo.ops import launch\n")
    assert lint_source(textwrap.dedent(src), path) == []
    # no registry file at all: pairing check only (vendored-subtree mode)
    registry.unlink()
    assert lint_source(textwrap.dedent(src), path) == []


def test_r6_real_kernel_packages_are_paired(repo_root):
    """Every shipped kernel package passes R6 from the repo root: the
    kernel/ops/ref triple exists and test_kernels.py registers it."""
    from repro.analysis import lint_paths
    findings = lint_paths([str(repo_root / "src" / "repro" / "kernels")],
                          root=repo_root, only=["R6"])
    assert [f.format() for f in findings] == []


def test_r7_scope_is_cohort_only_and_split_exempt():
    """R7 ignores the same allocation under a non-cohort root (the dense
    engines legitimately build [n] arrays), and `split` stays exempt
    (the sharded key-gather idiom)."""
    path, src = RULE_FIXTURES["R7"]
    dense = textwrap.dedent(src).replace("make_demo_cohort_sweep_step",
                                         "make_demo_sweep_step")
    assert lint_source(dense, path) == []
    keyed = textwrap.dedent(src).replace(
        "jax.random.uniform(key, (n_total,))",
        "jax.random.split(key, n_total)[:cohort]")
    assert lint_source(keyed, path) == []
    # init-time [N] state is outside the traced set: the ledger contract
    init = textwrap.dedent("""
        import jax.numpy as jnp
        from repro.core.driver import bits_dtype

        def init_cohort_state(w0, n_total):
            return jnp.zeros((n_total,), bits_dtype())
        """)
    assert lint_source(init, path) == []


def test_r8_scope_is_traffic_named_and_trace_time_only():
    """R8 leaves factory build-time schedule construction alone (that is
    exactly where `traffic_hparams` builds tables), and ignores literal
    arrays in traced steps that carry no traffic-named identifier."""
    path, src = RULE_FIXTURES["R8"]
    build_time = textwrap.dedent("""
        import jax.numpy as jnp

        def make_demo_traffic_sweep_step(cfg):
            rate_table = jnp.stack(
                [cfg.rate * (t % 24) for t in range(24)])

            def step(hp, state, key):
                return state, {"r": rate_table[state % 24]}
            return step
        """)
    assert lint_source(build_time, path) == []
    unrelated = textwrap.dedent(src).replace("rate_table", "sign_mask") \
                                    .replace("hp.rate", "hp.alpha")
    assert lint_source(unrelated, path) == []


def test_r5_snapshot_matches_tree_and_detects_drift():
    snapshot = load_snapshot()
    assert any(k.endswith("::FlecsHParams") for k in snapshot)
    key = next(k for k in snapshot if k.endswith("::GDHParams"))
    path = key.split("::")[0]

    def gd_findings(src):
        # the fixture module only defines GDHParams, so its siblings in
        # the real baselines.py show up as (expected) stale-entry
        # findings — keep only the messages about GDHParams itself
        return [f for f in lint_source(textwrap.dedent(src), path)
                if f.rule == "R5" and "GDHParams" in f.message
                and "snapshot entry" not in f.message]

    # a reorder of committed fields must fire R5
    reordered = """
        from typing import NamedTuple

        class GDHParams(NamedTuple):
            p: object = None
            alpha: object = None
        """
    findings = gd_findings(reordered)
    assert findings and "reorders" in findings[0].message
    # trailing defaulted growth is the sanctioned evolution
    grown = """
        from typing import NamedTuple

        class GDHParams(NamedTuple):
            alpha: object
            p: object = None
            bit_budget: object = None
            new_knob: object = None
        """
    assert gd_findings(grown) == []
    # ... but an undefaulted trailing field is not
    required = grown.replace("new_knob: object = None", "new_knob: object")
    findings = gd_findings(required)
    assert findings and "no default" in findings[0].message


def test_hparam_classes_extractor():
    import ast
    tree = ast.parse(textwrap.dedent("""
        from typing import NamedTuple

        class FooHParams(NamedTuple):
            a: float
            b: float = 1.0

        class NotTracked:
            pass
        """))
    assert hparam_classes(tree) == {"FooHParams": [("a", False),
                                                   ("b", True)]}


def test_suppression_and_r0_meta_rule():
    path, src = RULE_FIXTURES["R3"]
    ok = textwrap.dedent(src).replace(
        "jnp.float32)",
        "jnp.float32)  # repro-lint: disable=R3 -- fixture: exercising "
        "the suppression path")
    findings = lint_source(ok, path)
    assert rules_fired(findings) == set()               # live set empty
    assert rules_fired(findings, include_suppressed=True) == {"R3"}
    # an unjustified disable is itself a finding (R0)
    bare = textwrap.dedent(src).replace(
        "jnp.float32)", "jnp.float32)  # repro-lint: disable=R3")
    assert rules_fired(lint_source(bare, path)) == {META_RULE}
    # a disable for a DIFFERENT rule does not cover the finding
    wrong = textwrap.dedent(src).replace(
        "jnp.float32)",
        "jnp.float32)  # repro-lint: disable=R1 -- wrong rule id")
    assert "R3" in rules_fired(lint_source(wrong, path))


def test_syntax_errors_are_reported_not_raised():
    findings = lint_source("def broken(:\n", CORE)
    assert [f.rule for f in findings] == ["E9"]


def test_clean_corpus_core_and_optim(repo_root):
    from repro.analysis import lint_paths
    findings = lint_paths([str(repo_root / "src" / "repro")],
                          root=repo_root)
    live = [f.format() for f in findings if not f.suppressed]
    assert live == []


def test_layer1_import_is_jax_free(repo_root):
    import subprocess
    import sys
    code = ("import sys; sys.path.insert(0, 'src'); import repro.analysis; "
            "banned = [m for m in ('jax', 'numpy') if m in sys.modules]; "
            "assert not banned, banned")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=repo_root)


@pytest.fixture(scope="module")
def repo_root():
    from pathlib import Path
    return Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# layer 2
# ---------------------------------------------------------------------------

def test_semantic_switch_tables_clean():
    from repro.analysis.semantic import check_switch_tables
    assert check_switch_tables() == []


def test_semantic_switch_arity_is_six_families():
    # the auto-counted FAMILY_* registry drives the required lax.switch
    # arity — the six-family algebra (identity/dither/natural/topk/
    # count_sketch/minmax) must be contiguous 0..5 so every dispatch
    # table is checked at exactly 6 literal branches
    from repro.core import compressors
    fams = sorted(getattr(compressors, n) for n in dir(compressors)
                  if n.startswith("FAMILY_"))
    assert fams == [0, 1, 2, 3, 4, 5]
    assert compressors.FAMILY_COUNT_SKETCH == 4
    assert compressors.FAMILY_MINMAX == 5


def test_semantic_switch_branch_counter_sees_missing_branch():
    from repro.analysis.semantic import _switch_branch_counts
    src = textwrap.dedent("""
        import jax

        def compress(spec, key, x):
            return jax.lax.switch(spec.family, (lambda: x, lambda: -x))

        def spec_bits(spec, d):
            return jax.lax.switch(
                spec.family,
                (lambda: d, lambda: d, lambda: d, lambda: d))
        """)
    assert _switch_branch_counts(src) == {"compress": [2],
                                          "spec_bits": [4]}


def test_semantic_switch_branch_counter_sees_family_switch_tables():
    # the narrowing dispatch takes the full table too: a table handed to
    # _family_switch is counted like a lax.switch table
    from repro.analysis.semantic import _switch_branch_counts
    src = textwrap.dedent("""
        def compress(spec, key, x):
            return _family_switch(spec, (lambda: x, lambda: -x))

        def spec_omega(spec, d):
            return _family_switch(
                spec, (lambda: d, lambda: d, lambda: d, lambda: d,
                       lambda: d, lambda: d))
        """)
    assert _switch_branch_counts(src) == {"compress": [2],
                                          "spec_omega": [6]}


def test_semantic_round_bits_all_methods():
    from repro.analysis.semantic import METHOD_GRIDS, check_round_bits
    from repro.core.api import method_names
    assert set(method_names()) == set(METHOD_GRIDS)
    assert check_round_bits() == []


def test_semantic_jaxpr_all_methods():
    from repro.analysis.semantic import check_jaxpr
    assert check_jaxpr() == []


def test_semantic_jaxpr_catches_dead_hparam_axis():
    """A method whose step ignores a declared hparam leaf must be caught
    by the dead-axis walk (registered temporarily, then removed)."""
    import dataclasses

    import jax.numpy as jnp

    from repro.analysis.semantic import check_jaxpr
    from repro.core import api
    from repro.optim import baselines

    def dead_alpha_step(prob, cfg):
        inner = baselines.make_gd_sweep_step(cfg, prob.make_oracles()[0],
                                             prob.n_workers)

        def step(hp, state, key):
            # alpha is declared in the grid but pinned here: a dead axis
            fixed = hp._replace(alpha=jnp.float32(1.0))
            return inner(fixed, state, key)

        return step

    bad = dataclasses.replace(
        api.get_method("gd"), name="_bad_gd", sweep_step=dead_alpha_step,
        grid=lambda **kw: baselines.GDHParams(jnp.asarray([1.0, 2.0])))
    api._REGISTRY["_bad_gd"] = bad
    try:
        problems = [p for p in check_jaxpr() if p.startswith("_bad_gd")]
    finally:
        del api._REGISTRY["_bad_gd"]
    assert problems and "never consumed" in problems[0]


def test_semantic_jaxpr_catches_dead_spec_level_axis():
    """A one-family spec leaves its id unread by the compressor algebra,
    but not its level: a step that pins the dithering level of a
    level-only grid is still caught."""
    import dataclasses

    import jax.numpy as jnp

    from repro.analysis.semantic import check_jaxpr
    from repro.core import api
    from repro.optim import baselines

    def dead_level_step(prob, cfg):
        inner = baselines.make_diana_sweep_step(cfg,
                                                prob.make_oracles()[0])

        def step(hp, state, key):
            fixed = hp._replace(spec=hp.spec._replace(s=jnp.float32(64.0)))
            return inner(fixed, state, key)

        return step

    good = api.get_method("diana")
    bad = dataclasses.replace(good, name="_bad_diana",
                              sweep_step=dead_level_step,
                              grid=lambda **kw: good.grid(
                                  levels=(16.0, 64.0)))
    api._REGISTRY["_bad_diana"] = bad
    try:
        problems = [p for p in check_jaxpr()
                    if p.startswith("_bad_diana")]
    finally:
        del api._REGISTRY["_bad_diana"]
    assert len(problems) == 1 and ".spec.s " in problems[0]
    assert "never consumed" in problems[0]


def test_run_semantic_checks_aggregates():
    from repro.analysis.semantic import run_semantic_checks
    assert run_semantic_checks() == []


def test_cli_strict_clean_and_bad_path(repo_root, tmp_path, capsys):
    from repro.analysis.__main__ import main
    assert main(["--strict", str(repo_root / "src" / "repro" / "core")]) == 0
    capsys.readouterr()
    bad = tmp_path / "bad.py"
    bad.write_text(RULE_FIXTURES["R4"][1].strip() + "\n")
    assert main(["--strict", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "R4" in out
