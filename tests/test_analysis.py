"""The static checker suite: schema, registry, seeded-bug detection for
every pass family, and the live tree staying clean.

The seeded-bug tests are the acceptance contract: a weak-typed loop carry
(the PR 5 ShuffleStats class), an out-of-bounds BlockSpec index map, and
an overlapping-output-window kernel must each be caught *statically* —
no kernel executes anywhere in this file — with the right rule id.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import (
    FAMILIES,
    RULES,
    AnalysisContext,
    Finding,
    diff_against_baseline,
    iter_passes,
    load_report,
    register_pass,
    sort_findings,
    write_report,
)
from repro.analysis.jaxpr_passes import analyze_jaxpr
from repro.analysis.kernel_passes import check_case, kernel_analysis_cases
from repro.analysis.lint import iter_bare_asserts

REPO = Path(__file__).resolve().parent.parent


class TestFindingsSchema:
    def f(self, **kw):
        base = dict(rule="JX002", severity="error", target="t",
                    location="loc", message="m", fix_hint="h")
        base.update(kw)
        return Finding(**base)

    def test_key_excludes_message(self):
        a = self.f(message="run 1: 42 bytes")
        b = self.f(message="run 2: 99 bytes")
        assert a.key == b.key == "JX002::t::loc"

    def test_bad_severity_rejected(self):
        with pytest.raises(ValueError, match="severity"):
            self.f(severity="catastrophic")

    def test_report_roundtrip(self, tmp_path):
        fs = [self.f(), self.f(rule="PK004", severity="warning",
                               location="out0")]
        path = tmp_path / "report.json"
        write_report(fs, path)
        loaded = load_report(path)
        assert loaded == sort_findings(fs)
        # errors sort before warnings regardless of insert order
        assert [x.severity for x in loaded] == ["error", "warning"]

    def test_load_missing_is_empty(self, tmp_path):
        assert load_report(tmp_path / "nope.json") == []

    def test_load_rejects_unknown_schema(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"schema": "other/9", "findings": []}))
        with pytest.raises(ValueError, match="schema"):
            load_report(path)

    def test_baseline_diff(self):
        old = [self.f(location="kept"), self.f(location="gone")]
        cur = [self.f(location="kept"), self.f(location="fresh")]
        diff = diff_against_baseline(cur, old)
        assert [x.location for x in diff.new] == ["fresh"]
        assert [x.location for x in diff.known] == ["kept"]
        assert diff.fixed == ("JX002::t::gone",)
        assert diff.gate_failed
        assert not diff_against_baseline(old, old).gate_failed


class TestRegistry:
    def test_every_pass_rule_documented(self):
        for p in iter_passes():
            for rule in p.rules:
                assert rule in RULES, (p.name, rule)

    def test_all_families_covered(self):
        assert {p.family for p in iter_passes()} == set(FAMILIES)

    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown analysis families"):
            iter_passes(["jaxprs"])

    def test_undocumented_rule_rejected_at_registration(self):
        with pytest.raises(ValueError, match="undocumented"):
            register_pass("bogus", "lint", ("XX999",))(lambda ctx: [])

    def test_duplicate_name_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            register_pass("lint-asserts", "lint", ("LN001",))(lambda c: [])


class TestJaxprPasses:
    def test_seeded_weak_carry_fires_jx002(self):
        """The PR 5 bug class: a bare Python-int carry leg stays weak."""
        def buggy(x):
            def body(c):
                total, n = c
                return total + x.sum(), n + 1
            return jax.lax.while_loop(lambda c: c[1] < 3, body,
                                      (jnp.zeros((), jnp.int32), 0))

        closed = jax.make_jaxpr(buggy)(jax.ShapeDtypeStruct((4,), jnp.int32))
        found = analyze_jaxpr(closed, "seeded")
        assert [(f.rule, f.location) for f in found] == \
            [("JX002", "while#0/carry[1]")]
        assert found[0].severity == "error"

    def test_fixed_carry_is_clean(self):
        def fixed(x):
            def body(c):
                total, n = c
                return total + x.sum(), n + jnp.int32(1)
            return jax.lax.while_loop(lambda c: c[1] < 3, body,
                                      (jnp.zeros((), jnp.int32),
                                       jnp.int32(0)))

        closed = jax.make_jaxpr(fixed)(jax.ShapeDtypeStruct((4,), jnp.int32))
        assert analyze_jaxpr(closed, "seeded") == []

    def test_seeded_weak_scan_carry_fires_jx002(self):
        def buggy(xs):
            def step(c, x):
                return c + 1, x * 2
            return jax.lax.scan(step, 0, xs)

        closed = jax.make_jaxpr(buggy)(jax.ShapeDtypeStruct((4,), jnp.int32))
        rules = {(f.rule, f.location) for f in analyze_jaxpr(closed, "s")}
        assert ("JX002", "scan#0/carry[0]") in rules

    def test_seeded_host_callback_fires_jx004(self):
        def chatty(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        closed = jax.make_jaxpr(chatty)(jax.ShapeDtypeStruct((4,), jnp.int32))
        found = analyze_jaxpr(closed, "seeded")
        assert [f.rule for f in found] == ["JX004"]
        assert "debug_print" in found[0].location

    def test_concretization_raises_the_type_jx001_catches(self):
        """trace_pass converts jax.errors.JAXTypeError into JX001; prove
        the classic concretization bug raises exactly that family."""
        def buggy(x):
            if x.sum() > 0:  # traced bool in Python control flow
                return x
            return -x

        with pytest.raises(jax.errors.JAXTypeError):
            jax.make_jaxpr(buggy)(jax.ShapeDtypeStruct((4,), jnp.int32))

    def test_live_drivers_trace_clean(self):
        """Every engine x backend driver stages without JX001/JX002/JX004
        findings (JX003 depends on the platform and is baseline-gated via
        the CLI instead)."""
        from repro.analysis.jaxpr_passes import structure_pass, trace_pass

        ctx = AnalysisContext()
        assert trace_pass(ctx) == []
        assert structure_pass(ctx) == []
        # 2+2 engines x 3 backends, plus the resident-service programs:
        # serve_ingest x 3 backends, serve_snapshot, serve_query
        assert len(ctx.cache["traced_drivers"]) == 17


class TestKernelPasses:
    SDS = staticmethod(jax.ShapeDtypeStruct)

    def _case(self, entry, in_shape=(4, 128), accumulate=None):
        return {"name": "seeded", "accumulate": accumulate or {},
                "stage": lambda: jax.eval_shape(
                    entry, self.SDS(in_shape, jnp.float32))}

    def test_seeded_oob_index_map_fires_pk002(self):
        def entry(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i + 1, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=self.SDS((4, 128), jnp.float32))(x)

        found = check_case(self._case(entry), AnalysisContext())
        assert [(f.rule, f.location) for f in found] == \
            [("PK002", "<lambda>/in0")]

    def test_seeded_overlapping_windows_fire_pk004(self):
        def entry(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i // 2, 0)),
                out_shape=self.SDS((2, 128), jnp.float32))(x)

        found = check_case(self._case(entry), AnalysisContext())
        assert [(f.rule, f.location) for f in found] == \
            [("PK004", "<lambda>/out0")]

    def test_declared_accumulation_silences_pk004(self):
        def entry(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
                out_shape=self.SDS((1, 128), jnp.float32))(x)

        case = self._case(entry, accumulate={"<lambda>": {0}})
        assert check_case(case, AnalysisContext()) == []

    def test_seeded_ragged_block_fires_pk001(self):
        def entry(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(3,),
                in_specs=[pl.BlockSpec((1, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=self.SDS((3, 128), jnp.float32))(x)

        found = check_case(self._case(entry, in_shape=(3, 128)),
                           AnalysisContext())
        assert [(f.rule, f.location) for f in found] == \
            [("PK001", "<lambda>/in0[dim1]")]

    def test_seeded_vmem_blowout_fires_pk003(self):
        big = 8192
        def entry(x):
            return pl.pallas_call(
                lambda x_ref, o_ref: None,
                grid=(1,),
                in_specs=[pl.BlockSpec((big, big), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((big, big), lambda i: (0, 0)),
                out_shape=self.SDS((big, big), jnp.float32))(x)

        found = check_case(self._case(entry, in_shape=(big, big)),
                           AnalysisContext())
        assert [f.rule for f in found] == ["PK003"]

    def test_all_four_kernel_packages_export_cases(self):
        names = {c["name"].split("/")[0] for c in kernel_analysis_cases()}
        assert names == {"segment_hist", "count_scatter", "powerlaw_sample",
                         "windowed_ratio"}

    def test_live_kernels_clean(self):
        """All four kernel packages pass PK001-PK004 at production-preset
        geometry — statically, with pallas_call patched out (nothing
        executes; this also proves count_scatter's declared OR-scatter
        revisit is audited rather than special-cased)."""
        from repro.analysis.kernel_passes import kernels_pass

        ctx = AnalysisContext()
        assert kernels_pass(ctx) == []


class TestLintPass:
    def test_kernel_tiling_asserts_are_gone(self):
        src = REPO / "src" / "repro"
        kernel_asserts = [
            (rel, line) for rel, line, _ in iter_bare_asserts(src)
            if rel.startswith("repro/kernels/")]
        assert kernel_asserts == []

    def test_seeded_assert_fires_ln001(self, tmp_path):
        pkg = tmp_path / "repro_fake"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            "def f(x):\n    assert x > 0, x\n    return x\n")
        from repro.analysis.lint import asserts_pass

        ctx = AnalysisContext(src_root=str(pkg))
        found = asserts_pass(ctx)
        assert [(f.rule, f.location) for f in found] == [("LN001", "L2")]
        assert "assert x > 0" in found[0].message


class TestBaselineFile:
    def test_committed_baseline_loads_and_covers_live_lint(self):
        baseline = load_report(REPO / "results" / "analysis_baseline.json")
        assert baseline, "committed baseline missing or empty"
        # every grandfathered finding uses a documented rule
        for f in baseline:
            assert f.rule in RULES
        # the lint pass findings must all be grandfathered (anything new
        # would fail the CI gate)
        from repro.analysis.lint import asserts_pass

        keys = {f.key for f in baseline}
        for f in asserts_pass(AnalysisContext()):
            assert f.key in keys, f"ungrandfathered bare assert: {f.key}"


@pytest.mark.slow
def test_cli_target_all_exits_zero():
    """The acceptance gate: ``python -m repro.analysis --target all`` on
    the live repo, with the forced multi-device platform, exits 0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--target", "all"],
        capture_output=True, text=True, timeout=580, env=env, cwd=REPO)
    assert proc.returncode == 0, (
        f"analysis gate failed\nSTDOUT:\n{proc.stdout}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
    assert "gate: OK" in proc.stdout
