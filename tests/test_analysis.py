"""Tests for the static-analysis subsystem (repro.analysis).

Two halves:

* **seeded violations** — each pass is aimed at a deliberately-broken
  fixture (an over-budget configuration, an index map that walks off the
  operand, a float64 leak, a host callback, a collective under a nosync
  schedule, run signatures that drop ``handle_dangling``) and must flag it
  with the matching check key;
* **clean run** — the real kernel family and the full real registry must
  produce zero *unsuppressed* findings, and the documented suppressions
  must actually fire (a suppression matching nothing is stale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import apply_suppressions, unsuppressed
from repro.analysis.contracts import (
    audit_dangling_flow, audit_metadata, audit_registry,
)
from repro.analysis.jaxpr_lint import lint_jaxpr
from repro.analysis.vmem import (
    SYMBOLS, analyze_grid_spec, analyze_kernels, capture_grid_spec,
)


def _checks(findings):
    return {f.check for f in findings}


# ---------------------------------------------------------------------------
# vmem pass
# ---------------------------------------------------------------------------


def test_vmem_real_family_clean_and_budget_matches_docs():
    reps = analyze_kernels()
    assert set(reps) == {"spmv_blocked", "spmv_gs_pass", "spmv_gs_pass_multi"}
    assert all(not r.findings for r in reps.values())
    gs = reps["spmv_gs_pass"]
    # the docs/KERNELS.md whole-state budget, now computed: 6 f32 operands
    assert gs.per_vertex_bytes() == 24.0
    # ... and the ~600-700k vertices/core claim as an asserted number
    assert 600_000 <= gs.max_vertices_per_core() <= 700_000
    # Jacobi kernel streams everything: no whole-state residency cap
    assert reps["spmv_blocked"].max_vertices_per_core() is None
    # multi-vector budget is linear in the batch: 2 shared + 3 per-row f32
    multi = reps["spmv_gs_pass_multi"]
    assert multi.per_vertex_bytes(b=1) == 20.0
    assert multi.per_vertex_bytes(b=8) == 8 + 12 * 8


def test_vmem_flags_over_budget_configuration():
    gs = analyze_kernels()["spmv_gs_pass"]
    over = gs.max_vertices_per_core() + 1_000_000
    findings = gs.check_budget(over)
    assert _checks(findings) == {"budget-overflow"}
    assert not gs.check_budget(gs.max_vertices_per_core())


def _broken_index_map_spec():
    """A kernel whose streamed operand's index map runs one block past the
    end of the operand on the last grid step."""
    T, cap = SYMBOLS["T"], SYMBOLS["cap"]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[pl.BlockSpec((1, cap), lambda t, sb, db: (t + 1, 0))],
        out_specs=pl.BlockSpec((1, cap), lambda t, sb, db: (t, 0)),
    )
    shapes = [((T,), np.int32), ((T,), np.int32), ((T, cap), np.float32)]
    return grid_spec, shapes


def test_vmem_flags_out_of_range_index_map():
    grid_spec, shapes = _broken_index_map_spec()
    out = jax.ShapeDtypeStruct((SYMBOLS["T"], SYMBOLS["cap"]), np.float32)
    rep = analyze_grid_spec(grid_spec, shapes, ["sb", "db", "tiles", "out"],
                            kernel="broken", out_shape=out)
    assert _checks(rep.findings) == {"index-map-out-of-range"}
    assert any("tiles" in f.message for f in rep.findings)


def test_vmem_flags_operand_count_drift():
    T, cap = SYMBOLS["T"], SYMBOLS["cap"]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T,),
        in_specs=[pl.BlockSpec((1, cap), lambda t, sb, db: (t, 0))],
        out_specs=pl.BlockSpec((1, cap), lambda t, sb, db: (t, 0)),
    )
    shapes = [((T,), np.int32), ((T,), np.int32), ((T, cap), np.float32)]
    out = jax.ShapeDtypeStruct((T, cap), np.float32)
    rep = analyze_grid_spec(grid_spec, shapes, ["sb", "db", "tiles"],
                            kernel="drifted", out_shape=out)
    assert "operand-count-drift" in _checks(rep.findings)


def test_capture_records_grid_without_executing():
    ran = []

    def fake_kernel(n, *, interpret=False):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(4,),
            in_specs=[pl.BlockSpec((1,), lambda t: (t,))],
            out_specs=pl.BlockSpec((1,), lambda t: (t,)),
        )
        ran.append(True)
        return pl.pallas_call(lambda x_ref, o_ref: None, grid_spec=grid_spec,
                              out_shape=jax.ShapeDtypeStruct((4,), np.float32),
                              interpret=interpret)(n)

    gs, out_shape, operands = capture_grid_spec(
        fake_kernel, [jax.ShapeDtypeStruct((4,), np.float32)])
    assert tuple(gs.grid) == (4,)
    assert out_shape.shape == (4,)
    assert [shape for shape, _ in operands] == [(4,)]
    assert ran  # the wrapper body ran; the kernel itself never compiled
    assert pl.pallas_call is not None  # monkeypatch restored


# ---------------------------------------------------------------------------
# jaxpr pass
# ---------------------------------------------------------------------------


def test_jaxpr_flags_float64_leak():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: jnp.sum(x.astype(jnp.float64)))(jnp.ones(4, jnp.float32))
    findings = lint_jaxpr(jaxpr, target="fixture")
    assert _checks(findings) == {"float64-leak"}


def test_jaxpr_flags_host_callback():
    def leaky(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    findings = lint_jaxpr(jax.make_jaxpr(leaky)(jnp.ones(3)),
                          target="fixture")
    assert _checks(findings) == {"host-callback"}


def test_jaxpr_flags_collective_only_under_nosync():
    jaxpr = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                           axis_env=[("i", 2)])(jnp.ones(3))
    nosync = lint_jaxpr(jaxpr, target="fixture", schedule="nosync")
    assert _checks(nosync) == {"collective-in-nosync"}
    # the same program under a barrier schedule is fine — the collective IS
    # the barrier the metadata declares
    assert not lint_jaxpr(jaxpr, target="fixture", schedule="barrier")


def test_jaxpr_finds_collectives_in_nested_jaxprs():
    def solve(x):
        def body(i, v):
            return v + jax.lax.psum(v, "i")

        return jax.lax.fori_loop(0, 3, body, x)

    jaxpr = jax.make_jaxpr(solve, axis_env=[("i", 2)])(jnp.ones(3))
    assert _checks(lint_jaxpr(jaxpr, target="fixture", schedule="nosync")) \
        == {"collective-in-nosync"}


# ---------------------------------------------------------------------------
# contracts pass
# ---------------------------------------------------------------------------


def _result(pr):
    from repro.core.solver import PageRankResult

    return PageRankResult(pr, 0, 0.0)


def test_contracts_flags_run_that_cannot_receive_dangling():
    def run(bundle, *, threshold=1e-8, max_iter=100):
        return _result(bundle)

    findings = audit_dangling_flow(run, target="fixture")
    assert _checks(findings) == {"dangling-flow"}
    assert "cannot receive" in findings[0].message


def test_contracts_flags_run_that_drops_explicit_dangling():
    def run(bundle, *, handle_dangling=False, **kw):
        return _result(bundle)  # accepts the flag, ignores it — PR-2 bug

    findings = audit_dangling_flow(run, target="fixture")
    assert _checks(findings) == {"dangling-flow"}
    assert "never reads it" in findings[0].message


def test_contracts_flags_kw_never_forwarded():
    def run(bundle, **kw):
        return _result(bundle)

    findings = audit_dangling_flow(run, target="fixture")
    assert _checks(findings) == {"dangling-flow"}
    assert "never" in findings[0].message


def test_contracts_accepts_real_plumbing_shapes():
    def explicit(bundle, *, handle_dangling=False, **kw):
        return _result(bundle if not handle_dangling else bundle)

    def forwards(bundle, **kw):
        return explicit(bundle, **kw)

    def _filter(kw):
        return {k: v for k, v in kw.items() if k == "handle_dangling"}

    helper = lambda b, **kw: explicit(b, **_filter(kw))  # noqa: E731

    for run in (explicit, forwards, helper):
        assert not audit_dangling_flow(run, target="fixture"), run


def test_contracts_metadata_vocabulary():
    import dataclasses

    from repro.core.solver import get_variant

    good = get_variant("nosync")
    assert not audit_metadata(good)
    bad = dataclasses.replace(good, schedule="async", description="")
    checks = _checks(audit_metadata(bad))
    assert checks == {"metadata-empty", "metadata-vocabulary"}


def test_register_variant_rejects_bad_metadata_at_registration():
    from repro.core.solver import _REGISTRY, register_variant

    with pytest.raises(ValueError, match="description"):
        register_variant("bad_fixture", build=lambda g, **_: g,
                         run=lambda b, **kw: None,
                         description="", layout="host",
                         backend="numpy", schedule="sequential")
    with pytest.raises(ValueError, match="backend"):
        register_variant("bad_fixture", build=lambda g, **_: g,
                         run=lambda b, **kw: None,
                         description="x", layout="host",
                         backend="tpu", schedule="sequential")
    assert "bad_fixture" not in _REGISTRY  # failed registration left no trace


# keep the original registry test's guarantee here too: the import-time
# validation in register_variant is what enforces it, this is the regression
# guard that the validation stays wired
def test_registry_metadata_still_validated():
    from repro.core.solver import BACKENDS, SCHEDULES, get_variant, list_variants

    for name in list_variants():
        v = get_variant(name)
        assert v.description and v.layout
        assert v.backend in BACKENDS and v.schedule in SCHEDULES


# ---------------------------------------------------------------------------
# clean run over the real registry (slowest test: traces every variant)
# ---------------------------------------------------------------------------


def test_markers_pass_clean_on_repo():
    from repro.analysis.markers import marker_findings, registered_markers

    assert {"tier1", "slow", "subprocess"} <= registered_markers()
    assert marker_findings() == [], \
        [f.to_dict() for f in marker_findings()]


def test_markers_pass_flags_violations(tmp_path):
    from repro.analysis.markers import marker_findings

    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    tier1: gate\n    slow: slow tier\n"
        "    subprocess: spawns workers\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_bad.py").write_text(
        "import subprocess\n"
        "import pytest\n"
        "import sys\n"
        "@pytest.mark.tier1\n"          # conftest owns tier1
        "@pytest.mark.sloow\n"          # typo'd, unregistered
        "def test_a():\n"
        "    subprocess.run([sys.executable, '-V'])\n"  # unmarked spawn
        "@pytest.mark.subprocess\n"     # subprocess without slow
        "def test_b():\n"
        "    pass\n")
    checks = {f.check for f in marker_findings(tmp_path)}
    assert checks == {"unregistered-marker", "explicit-tier1",
                      "unmarked-subprocess", "subprocess-not-slow"}
    # a missing pytest.ini is itself a finding, not a crash
    (tmp_path / "pytest.ini").unlink()
    assert "missing-config" in {f.check for f in marker_findings(tmp_path)}


def test_markers_module_pytestmark_counts(tmp_path):
    from repro.analysis.markers import marker_findings

    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    tier1: a\n    slow: b\n    subprocess: c\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # module-level pytestmark satisfies both the spawn rule and slow⊆rule
    (tests / "test_mod.py").write_text(
        "import subprocess\n"
        "import pytest\n"
        "import sys\n"
        "pytestmark = [pytest.mark.slow, pytest.mark.subprocess]\n"
        "def test_a():\n"
        "    subprocess.run([sys.executable, '-V'])\n")
    assert marker_findings(tmp_path) == []


def test_full_registry_runs_clean_and_suppressions_fire():
    from repro.analysis import run_all

    findings = run_all()
    assert unsuppressed(findings) == [], [f.to_dict() for f in findings]
    # the documented suppressions must fire — a suppression that matches
    # nothing is stale and should be deleted
    fired = {(f.target, f.check) for f in findings if f.suppressed}
    assert ("distributed_stale", "collective-in-nosync") in fired
    assert ("distributed_topk", "collective-in-nosync") in fired


def test_contract_audit_clean_per_variant():
    audit = audit_registry()
    assert all(not fs for fs in audit.values()), \
        {k: [f.to_dict() for f in v] for k, v in audit.items() if v}


def test_suppressions_do_not_hide_new_findings():
    from repro.analysis.findings import Finding

    fresh = Finding("jaxpr", "distributed_stale", "float64-leak", "fixture")
    known = Finding("jaxpr", "distributed_stale", "collective-in-nosync", "x")
    out = apply_suppressions([fresh, known])
    assert not fresh.suppressed  # triple match only — no blanket suppression
    assert known.suppressed and known.reason
    assert unsuppressed(out) == [fresh]
