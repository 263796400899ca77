"""graft-lint self-tests: every catalog rule fires exactly once on its
fixture with the right location; clean code stays silent; suppression,
baseline, enforcement modes, and the CLI contract all hold."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import (ERROR, INFO, RULES, WARNING, ProgramSpec,
                                 analyze_program, enforce_import,
                                 filter_baseline, lint_file, lint_source,
                                 load_baseline, save_baseline)
from paddle_tpu.core.enforce import AnalysisError
from paddle_tpu.core.flags import set_flags

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
_FIX = os.path.join(_HERE, "fixtures", "graftlint")
_CLI = os.path.join(_REPO, "tools", "analysis", "graftlint.py")

sds = jax.ShapeDtypeStruct


def _lint_fix(name):
    return lint_file(os.path.join(_FIX, name), root=_REPO)


# ---------------------------------------------------------------------------
# AST rules: one fixture, one finding, right location
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture,rule,line,func,severity", [
    ("fix_numpy_in_jit.py", "numpy-in-jit", 8, "root", ERROR),
    ("fix_host_sync.py", "host-sync-in-jit", 7, "root", ERROR),
    ("fix_tracer_branch.py", "tracer-branch", 7, "root", ERROR),
    ("fix_mutable_default.py", "mutable-default-arg", 4, "helper", WARNING),
    ("fix_unkeyed_jit.py", "unkeyed-jit", 6, "call", ERROR),
    (os.path.join("inference", "fix_attention_budget.py"),
     "attention-program-budget", 18, "decode_step", ERROR),
    (os.path.join("inference", "fix_attention_budget_kinds.py"),
     "attention-program-budget", 21, "decode_attention_step", ERROR),
    (os.path.join("inference", "fix_quantized_kv.py"),
     "quantized-kv-float32-page", 10, "build_pools", WARNING),
    (os.path.join("inference", "fix_weight_matmul.py"),
     "f32-weight-matmul-in-quantized-engine", 10, "project", WARNING),
    (os.path.join("inference", "fix_swallowed_exception.py"),
     "swallowed-exception", 9, "release_pages", ERROR),
    (os.path.join("inference", "fix_collective_outside_shard_map.py"),
     "collective-outside-shard-map", 11, "gather_logits", ERROR),
    (os.path.join("inference", "fix_wallclock_timing.py"),
     "wallclock-in-timing-path", 8, "measure_step", WARNING),
    (os.path.join("inference", "fix_host_sync_dispatch.py"),
     "host-sync-in-dispatch-path", 12, "dispatch_step", WARNING),
    (os.path.join("inference", "fix_host_copy_step_path.py"),
     "host-copy-in-step-path", 11, "dispatch_restore", WARNING),
    (os.path.join("inference", "fix_host_sync_window.py"),
     "per-token-host-sync-in-decode-window", 23,
     "DecodeEngine._commit", WARNING),
    (os.path.join("inference", "fix_unbounded_buffer.py"),
     "unbounded-observability-buffer", 14, "StepStatsLog.record", WARNING),
    (os.path.join("pallas", "fix_untuned_launch.py"),
     "untuned-pallas-launch", 15, "hardcoded_launch", WARNING),
    (os.path.join("sim", "fix_nondeterministic_sim.py"),
     "nondeterministic-sim", 10, "step_cost", WARNING),
])
def test_ast_fixture_fires_exactly_once(fixture, rule, line, func, severity):
    findings = _lint_fix(fixture)
    assert len(findings) == 1, [str(f.location) for f in findings]
    f = findings[0]
    assert f.rule == rule
    assert f.severity == severity
    assert f.location.line == line
    assert f.location.func == func
    assert f.location.file.endswith(fixture)


def test_clean_fixture_is_silent():
    assert _lint_fix("fix_clean.py") == []


def test_serving_engine_within_attention_program_budget():
    """The shipped engine holds the contract the budget rule guards:
    exactly one attention-bearing compiled program KIND (the ragged
    step; its float32 and quantized-int8 dtype variants share the kind
    — an engine only ever compiles one).  And its quantized branch
    allocates int8 pages, so the float32-page rule stays silent too."""
    findings = lint_file(os.path.join(_REPO, "paddle_tpu", "inference",
                                      "serving.py"), root=_REPO)
    assert [f for f in findings
            if f.rule == "attention-program-budget"] == []
    # the budget is by the layers' attention kinds, which the engine
    # declares as a literal the rule can read
    from paddle_tpu.inference import layer_stack, serving
    assert serving.ATTENTION_KINDS == tuple(layer_stack.ATTENTION)
    assert [f for f in findings
            if f.rule == "quantized-kv-float32-page"] == []
    assert [f for f in findings
            if f.rule == "f32-weight-matmul-in-quantized-engine"] == []


def test_mutable_default_is_error_in_compiled_path():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def root(x, acc=[]):
            return x
    """)
    (f,) = lint_source(src, "m.py")
    assert f.rule == "mutable-default-arg" and f.severity == ERROR


def test_unkeyed_jit_in_loop_fires():
    src = textwrap.dedent("""
        import jax

        fns = [lambda v: v]
        for fn in fns:
            prog = jax.jit(fn)
    """)
    (f,) = lint_source(src, "m.py")
    assert f.rule == "unkeyed-jit" and "loop" in f.message


def test_coercion_on_traced_param_fires():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def root(x):
            return float(x)
    """)
    (f,) = lint_source(src, "m.py")
    assert f.rule == "host-sync-in-jit" and "float" in f.message


def test_static_argnames_params_do_not_count_as_traced():
    src = textwrap.dedent("""
        import jax

        def step(x, causal):
            if causal:
                return x
            return -x

        prog = jax.jit(step, static_argnames=("causal",))
    """)
    assert lint_source(src, "m.py") == []


def test_suppression_same_line_def_line_and_next_line():
    base = textwrap.dedent("""
        import jax

        @jax.jit
        def root(x):
            return x.item(){same}
    """)
    dirty = base.format(same="")
    assert len(lint_source(dirty, "m.py")) == 1
    same = base.format(same="  # graftlint: disable=host-sync-in-jit")
    assert lint_source(same, "m.py") == []
    nxt = textwrap.dedent("""
        import jax

        @jax.jit
        def root(x):
            # graftlint: disable-next=host-sync-in-jit
            return x.item()
    """)
    assert lint_source(nxt, "m.py") == []
    deco = textwrap.dedent("""
        import jax

        @jax.jit
        def root(x):  # graftlint: disable=host-sync-in-jit
            return x.item()
    """)
    assert lint_source(deco, "m.py") == []


def test_skip_file_suppresses_everything():
    src = "# graftlint: skip-file\nimport jax\n\n@jax.jit\n" \
          "def root(x):\n    return x.item()\n"
    assert lint_source(src, "m.py") == []


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------

_BIG = sds((1 << 18,), jnp.float32)            # 1 MiB
_SMALL = sds((8,), jnp.float32)


def test_undonated_buffer_fires_and_donation_clears_it():
    def f(buf):
        return buf * 2.0

    spec = ProgramSpec("p", f, (_BIG,))
    (finding,) = analyze_program(spec)
    assert finding.rule == "undonated-buffer"
    assert finding.severity == ERROR
    assert "donate_argnums" in finding.message

    donated = ProgramSpec("p", f, (_BIG,), donate_argnums=(0,))
    assert analyze_program(donated) == []


def test_undonated_buffer_ignores_small_and_passthrough():
    def f(buf, small):
        return buf, small + 1.0                 # buf passes through

    spec = ProgramSpec("p", f, (_BIG, _SMALL))
    rules = {x.rule for x in analyze_program(spec)}
    assert "undonated-buffer" not in rules
    assert "passthrough-output" in rules        # INFO on buf


def test_host_callback_fires_with_trail():
    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a), sds(x.shape, x.dtype), x)
        return y + 1.0

    spec = ProgramSpec("p", f, (_SMALL,))
    findings = [x for x in analyze_program(spec)
                if x.rule == "host-callback"]
    assert len(findings) == 1
    assert findings[0].severity == ERROR
    assert findings[0].trail                    # user source frames


def test_dtype_promotion_fires_only_when_declared_low_precision():
    def f(x):
        return (x.astype(jnp.float32) * 2.0).astype(jnp.bfloat16)

    bf16 = sds((16,), jnp.bfloat16)
    spec = ProgramSpec("p", f, (bf16,), declared_dtype=jnp.bfloat16)
    proms = [x for x in analyze_program(spec)
             if x.rule == "dtype-promotion"]
    assert len(proms) == 1 and proms[0].severity == WARNING
    assert "bfloat16" in proms[0].message and proms[0].trail

    undeclared = ProgramSpec("p", f, (bf16,))
    assert [x for x in analyze_program(undeclared)
            if x.rule == "dtype-promotion"] == []


def test_dead_code_and_dead_input():
    def f(a, b):
        unused = a * 3.0                       # noqa: F841  dead eqn
        return a + 1.0

    spec = ProgramSpec("p", f, (_SMALL, _SMALL))
    rules = {}
    for x in analyze_program(spec):
        rules.setdefault(x.rule, []).append(x)
    assert len(rules["dead-code"]) == 1
    (di,) = rules["dead-input"]
    assert di.severity == WARNING and "arg1" in di.message

    big_spec = ProgramSpec("p", f, (_SMALL, _BIG))
    (di_big,) = [x for x in analyze_program(big_spec)
                 if x.rule == "dead-input"]
    assert di_big.severity == ERROR            # large dead input escalates


def test_every_catalog_rule_is_exercised():
    """Each RULES entry must be covered by a firing assertion — AST and
    jaxpr rules in this file, race rules by the fixture parametrization
    in test_race_rules.py (fixtures under tests/fixtures/graftlint/races)
    — this meta-check catches a rule added to the catalog without a
    test."""
    covered = {
        "numpy-in-jit", "host-sync-in-jit", "tracer-branch",
        "mutable-default-arg", "unkeyed-jit", "attention-program-budget",
        "quantized-kv-float32-page", "swallowed-exception",
        "f32-weight-matmul-in-quantized-engine",
        "collective-outside-shard-map", "untuned-pallas-launch",
        "wallclock-in-timing-path", "host-sync-in-dispatch-path",
        "per-token-host-sync-in-decode-window", "host-copy-in-step-path",
        "unbounded-observability-buffer", "nondeterministic-sim",
        "undonated-buffer", "host-callback", "dtype-promotion",
        "dead-code", "dead-input", "passthrough-output",
        # race front end — firing fixtures asserted in test_race_rules.py
        "unguarded-shared-state", "non-atomic-shared-rmw",
        "callback-under-lock", "blocking-call-in-event-loop",
    }
    assert covered == set(RULES)
    # every race-tagged rule must ship a firing fixture AND an assertion
    # naming it in test_race_rules.py
    race_fixture = {
        "unguarded-shared-state": "fix_unguarded_shared_state.py",
        "non-atomic-shared-rmw": "fix_non_atomic_rmw.py",
        "callback-under-lock": "fix_callback_under_lock.py",
        "blocking-call-in-event-loop": "fix_blocking_in_event_loop.py",
    }
    race_rules = {r for r, (_s, tag, _d) in RULES.items() if tag == "race"}
    assert race_rules == set(race_fixture)
    race_tests = open(os.path.join(_HERE, "test_race_rules.py")).read()
    for rule, fixture in race_fixture.items():
        assert f'"{rule}"' in race_tests, f"{rule}: no firing assertion"
        assert os.path.exists(os.path.join(_FIX, "races", fixture)), fixture


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_line_drift(tmp_path):
    findings = _lint_fix("fix_host_sync.py")
    path = tmp_path / "baseline.json"
    save_baseline(str(path), findings, reason="known")
    accepted = load_baseline(str(path))
    assert filter_baseline(findings, accepted) == []
    # fingerprints ignore line numbers: shifting the finding down two
    # lines must not resurrect it
    src = open(os.path.join(_FIX, "fix_host_sync.py")).read()
    shifted = "# pad\n# pad\n" + src
    moved = lint_source(shifted, "tests/fixtures/graftlint/fix_host_sync.py")
    assert moved[0].location.line != findings[0].location.line
    assert filter_baseline(moved, accepted) == []


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == set()


# ---------------------------------------------------------------------------
# enforcement modes (PT_ANALYSIS / FLAGS_analysis_mode)
# ---------------------------------------------------------------------------

@pytest.fixture
def analysis_mode():
    def set_mode(mode):
        set_flags({"analysis_mode": mode})
    yield set_mode
    set_flags({"analysis_mode": "off"})


def test_enforce_import_off_is_free(analysis_mode):
    analysis_mode("off")
    assert enforce_import("fix", os.path.join(_FIX, "fix_host_sync.py")) == []


def test_enforce_import_strict_raises(analysis_mode):
    analysis_mode("strict")
    with pytest.raises(AnalysisError, match="host-sync-in-jit"):
        enforce_import("fix", os.path.join(_FIX, "fix_host_sync.py"))


def test_enforce_import_warn_warns(analysis_mode):
    analysis_mode("warn")
    with pytest.warns(UserWarning, match="host-sync-in-jit"):
        errors = enforce_import("fix",
                                os.path.join(_FIX, "fix_host_sync.py"))
    assert len(errors) == 1


def test_enforce_import_strict_passes_clean_file(analysis_mode):
    analysis_mode("strict")
    assert enforce_import("fix", os.path.join(_FIX, "fix_clean.py")) == []


def test_strict_import_of_engine_module_raises_on_seeded_violation(
        tmp_path, analysis_mode):
    """End-to-end: the hook at the bottom of serving.py/step.py raises at
    import time under strict when the module has a non-baselined ERROR."""
    bad = tmp_path / "engine_like.py"
    bad.write_text("import jax\n\n@jax.jit\ndef step(x):\n"
                   "    return x.tolist()\n")
    analysis_mode("strict")
    with pytest.raises(AnalysisError):
        enforce_import("engine_like", str(bad))


# ---------------------------------------------------------------------------
# CLI + repo-tree contract
# ---------------------------------------------------------------------------

def _run_cli(*args):
    return subprocess.run([sys.executable, _CLI, *args],
                          capture_output=True, text=True, cwd=_REPO,
                          timeout=120)


def test_cli_nonzero_on_fixture_tree_json():
    r = _run_cli(_FIX, "--format", "json", "--no-default-baseline")
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["counts"]["ERROR"] == 8          # one per ERROR fixture
    rules = {f["rule"] for f in doc["findings"]}
    assert {"numpy-in-jit", "host-sync-in-jit", "tracer-branch",
            "unkeyed-jit", "attention-program-budget",
            "swallowed-exception", "collective-outside-shard-map"} <= rules


def test_cli_exit_zero_on_shipped_tree():
    r = _run_cli(os.path.join(_REPO, "paddle_tpu"))
    assert r.returncode == 0, r.stdout + r.stderr


def test_repo_tree_has_no_new_error_findings():
    """Tier-1 smoke: the shipped paddle_tpu tree AST-lints clean against
    the committed baseline (the pytest plugin enforces the same thing
    session-wide; this keeps the guarantee visible as a named test)."""
    from paddle_tpu.analysis import default_baseline_path, lint_paths
    findings = filter_baseline(
        lint_paths([os.path.join(_REPO, "paddle_tpu")], root=_REPO),
        load_baseline(default_baseline_path()))
    errors = [f for f in findings if f.severity == ERROR]
    assert errors == [], [str(f.location) for f in errors]
