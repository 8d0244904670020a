"""Tests for the ``repro.analysis`` static checker: every lint rule fires on
its planted fixture and stays quiet on the clean twin, the jaxpr passes
detect what they claim to detect, the scheme validator flags planted
violations, and -- the meta-test -- the live repo itself passes the full
CLI under ``--strict``.

The lint fixtures live in ``tests/analysis_fixtures/`` laid out like the
real package so the default ``LintConfig`` path rules apply verbatim.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

from repro.analysis.findings import ERROR, WARNING, Finding, Report
from repro.analysis.lint import LintConfig, lint_source, run_lint

FIXTURES = Path(__file__).parent / "analysis_fixtures"
REPO = Path(__file__).resolve().parent.parent


# --------------------------------- findings ----------------------------------

def test_report_exit_codes():
    ok = Report(checked={"lint": 3})
    assert ok.exit_code() == 0 and ok.exit_code(strict=True) == 0
    warn = Report(findings=[Finding("r", WARNING, "f.py", 1, "m", "lint")],
                  checked={"lint": 3})
    assert warn.exit_code() == 0
    assert warn.exit_code(strict=True) == 1
    err = Report(findings=[Finding("r", ERROR, "f.py", 1, "m", "lint")],
                 checked={"lint": 3})
    assert err.exit_code() == 1
    vacuous = Report(checked={"jaxpr": 0})
    assert vacuous.exit_code() == 2  # checked nothing must not read as a pass


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError, match="severity"):
        Finding("r", "fatal", "f.py", 1, "m", "lint")


# ------------------------------- lint fixtures -------------------------------

def test_fixture_tree_findings_match_plants_exactly():
    findings, files = run_lint(FIXTURES)
    assert files == 11
    got = sorted((f.path, f.line, f.rule) for f in findings)
    assert got == [
        ("bad_compat.py", 3, "compat-boundary"),
        ("bad_compat.py", 10, "compat-boundary"),
        ("bad_deprecated.py", 4, "no-deprecated-surface"),
        ("bad_deprecated.py", 8, "no-deprecated-surface"),
        ("bad_unused_waiver.py", 7, "unused-waiver"),
        ("coded/config.py", 4, "jax-free-module"),
        ("runtime/bad_rank.py", 7, "matrix-rank-hot-path"),
    ]
    assert all(f.severity == ERROR for f in findings)
    # a tree with planted violations fails the aggregate report
    assert Report(findings=list(findings),
                  checked={"lint": files}).exit_code() == 1


@pytest.mark.parametrize("rel", [
    "ok_compat.py", "compat.py", "kernels/fused.py", "core/encoder.py",
    "runtime/ok_rank.py", "ok_deprecated.py",
])
def test_clean_twins_stay_clean(rel):
    assert lint_source(rel, (FIXTURES / rel).read_text()) == []


@pytest.mark.parametrize("rel", [
    "bad_compat.py", "coded/config.py", "runtime/bad_rank.py",
    "bad_unused_waiver.py", "bad_deprecated.py",
])
def test_each_planted_fixture_fires(rel):
    assert lint_source(rel, (FIXTURES / rel).read_text())


def test_pallas_only_allowed_under_kernels():
    src = (FIXTURES / "kernels/fused.py").read_text()
    findings = lint_source("runtime/fused.py", src)
    assert {f.rule for f in findings} == {"compat-boundary"}


def test_waiver_trailing_and_above_line_both_work():
    above = ("import numpy as np\n"
             "# repro: allow(matrix-rank-hot-path)\n"
             "r = np.linalg.matrix_rank(M)\n")
    trailing = ("import numpy as np\n"
                "r = np.linalg.matrix_rank(M)"
                "  # repro: allow(matrix-rank-hot-path)\n")
    for src in (above, trailing):
        assert lint_source("runtime/x.py", src) == []


def test_waiver_for_wrong_rule_is_unused_and_does_not_suppress():
    src = ("import numpy as np\n"
           "# repro: allow(compat-boundary)\n"
           "r = np.linalg.matrix_rank(M)\n")
    rules = sorted(f.rule for f in lint_source("runtime/x.py", src))
    assert rules == ["matrix-rank-hot-path", "unused-waiver"]


def test_live_repo_waiver_is_used():
    # the sanctioned one-shot rank check in the registry: waived, not silent
    src = (REPO / "src/repro/coded/registry.py").read_text()
    assert "repro: allow(matrix-rank-hot-path)" in src
    assert lint_source("coded/registry.py", src) == []


def test_lint_flags_unparseable_source():
    findings = lint_source("x.py", "def broken(:\n")
    assert [f.rule for f in findings] == ["syntax"]


# ------------------------------- jaxpr passes --------------------------------

def test_stacked_detector_and_sensitivity_probe():
    jax = pytest.importorskip("jax")
    from repro.analysis.jaxpr_check import (
        assert_detector_sensitivity,
        legacy_stacked_gather,
        stacked_intermediates,
    )
    import jax.numpy as jnp

    L, s, n, bt = 5, 16, 2, 8
    closed = jax.make_jaxpr(
        lambda b: legacy_stacked_gather(b, L, s, n, bt))(
            jnp.ones((s, n * bt), jnp.float32))
    assert stacked_intermediates(closed.jaxpr, L * s)
    assert_detector_sensitivity(L, s, n, bt)  # must not raise
    clean = jax.make_jaxpr(lambda b: b @ b.T)(jnp.ones((s, n * bt)))
    assert stacked_intermediates(clean.jaxpr, L * s) == []


def test_collective_axis_pass():
    jax = pytest.importorskip("jax")
    from repro.analysis.jaxpr_check import (
        collective_axis_offenders,
        collective_prims,
    )
    import jax.numpy as jnp

    # an AbstractMesh stages a real 8-way shard_map without any devices
    # (a 1-device mesh's psum would be elided at trace time, and vmap
    # resolves axis names positionally)
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from repro import compat

    mesh = AbstractMesh((8,), ("model",))
    f = compat.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                         in_specs=P("model"), out_specs=P())
    closed = jax.make_jaxpr(f)(jnp.ones((8, 4), jnp.float32))
    assert collective_prims(closed.jaxpr) == ["psum_invariant"]
    assert collective_axis_offenders(closed.jaxpr, "model") == []
    assert collective_axis_offenders(closed.jaxpr, "data") == [
        ("psum_invariant", ("model",))]


def test_float64_pass():
    jax = pytest.importorskip("jax")
    from repro.analysis.jaxpr_check import float64_offenders
    import jax.numpy as jnp

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: jnp.sum(x * 2.0))(np.ones((4,), np.float64))
        assert float64_offenders(closed.jaxpr)
    clean = jax.make_jaxpr(
        lambda x: jnp.sum(x * 2.0))(np.ones((4,), np.float32))
    assert float64_offenders(clean.jaxpr) == []


def test_peak_bytes_pass():
    jax = pytest.importorskip("jax")
    from repro.analysis.jaxpr_check import peak_equation_bytes
    import jax.numpy as jnp

    closed = jax.make_jaxpr(
        lambda a, b: a @ b)(jnp.ones((8, 4), jnp.float32),
                            jnp.ones((4, 2), jnp.float32))
    peak, prim, shapes = peak_equation_bytes(closed.jaxpr)
    assert prim == "dot_general"
    assert peak == 4 * (8 * 4 + 4 * 2 + 8 * 2)


# ------------------------------ scheme validator -----------------------------

def test_scheme_validator_clean_on_builtin():
    from repro.analysis.schemes import validate_scheme

    assert validate_scheme("sparse_code") == []


def test_scheme_validator_flags_false_exactness_claim():
    from repro.analysis.schemes import validate_scheme
    from repro.coded import registry
    from repro.core import schemes as schemes_lib
    from repro.core.schemes import SchemeInvariants

    name = "bad_exact_claim"
    registry.register_scheme(
        name,
        lambda m, n, N, *, seed=0: schemes_lib.sparse_code(m, n, N, seed=seed),
        invariants=SchemeInvariants(exact=True, mean_overhead=0.0,
                                    max_overhead=0.0))
    try:
        rules = {f.rule for f in validate_scheme(name)}
        assert "recovery-threshold" in rules
    finally:
        registry._REGISTRY.pop(name, None)


def test_scheme_validator_flags_empty_generator_rows():
    from repro.analysis.schemes import validate_scheme
    from repro.coded import registry
    from repro.core.schemes import CodeInstance

    def degenerate(m, n, N, *, seed=0):
        # N workers but only mn useful rows: the rest are EMPTY
        M = sp.csr_matrix(np.eye(N, m * n))
        return CodeInstance(name="degenerate", M=M,
                            worker_rows=[[k] for k in range(N)],
                            cost_factor=np.ones(N), decode_kind="hybrid")

    name = "bad_empty_rows"
    registry.register_scheme(name, degenerate)
    try:
        rules = {f.rule for f in validate_scheme(name)}
        assert "degree-sanity" in rules
    finally:
        registry._REGISTRY.pop(name, None)


def test_scheme_validator_findings_anchor_at_builder():
    from repro.analysis.schemes import validate_scheme
    from repro.coded import registry
    from repro.core.schemes import SchemeInvariants

    name = "bad_anchored"
    registry.register_scheme(
        name,
        lambda m, n, N, *, seed=0: registry.get_scheme(
            "sparse_code").instance(m, n, N, seed=seed),
        invariants=SchemeInvariants(exact=True, mean_overhead=0.0,
                                    max_overhead=0.0))
    try:
        findings = validate_scheme(name)
        assert findings
        # the anchor is THIS test file (where the builder lambda lives)
        assert all(f.path.endswith("test_analysis.py") for f in findings)
        assert all(f.line > 0 for f in findings)
    finally:
        registry._REGISTRY.pop(name, None)


# --------------------------------- meta-test ---------------------------------

def test_live_repo_passes_strict_cli(tmp_path):
    """The acceptance gate itself: the full CLI, exactly as CI invokes it,
    exits 0 on this repo with every layer reporting real coverage."""
    out = tmp_path / "findings.json"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict",
         "--json", str(out)],
        capture_output=True, text=True, timeout=560, env=env, cwd=str(REPO))
    assert proc.returncode == 0, (
        f"repo fails its own strict analysis gate:\n{proc.stdout}\n"
        f"{proc.stderr}")
    report = json.loads(out.read_text())
    assert report["errors"] == 0 and report["warnings"] == 0
    checked = report["checked"]
    assert checked["lint"] >= 60       # the whole src/repro tree
    assert checked["schemes"] == 7     # every registered scheme
    assert checked["jaxpr"] >= 20      # both backends x layouts x schemes


def test_cli_only_lint_is_fast_and_scoped(tmp_path):
    out = tmp_path / "findings.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--only", "lint",
         "--json", str(out)],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    checked = json.loads(out.read_text())["checked"]
    assert set(checked) == {"lint"}
