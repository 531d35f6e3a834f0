"""hkv-lint's own suite: every checker flags its known-bad fixture, the
shipped tree is clean, and the findings model (waivers, formats) behaves.

The fixture tests are the teeth of the analyzer — a checker that never
fires is indistinguishable from a correct tree, so each rule is proven
against a deliberately broken input before the cleanliness assertions
are trusted.
"""

import pathlib
import subprocess
import sys

import pytest

from repro import analysis
from repro.analysis import findings as findings_mod
from repro.analysis import kernel_contracts as kc
from repro.analysis import oracle_coupling as oc
from repro.analysis import registry
from repro.analysis import roles as roles_checker
from repro.analysis import telemetry as tel_checker
from repro.analysis.fixtures import bad_kernels, bad_ops

REPO = pathlib.Path(__file__).resolve().parents[1]


def rules(fs):
    return sorted({f.rule for f in fs})


# ---------------------------------------------------------------------------
# fixtures: each checker demonstrably fires
# ---------------------------------------------------------------------------

class TestKernelFixtures:
    def test_unpaired_dma_flagged(self):
        fs = kc.check_traced_kernel(
            "fixture_unpaired_dma", "fixture", bad_kernels.trace_unpaired_dma())
        assert "dma-unpaired" in rules(fs)

    def test_unpaired_ring_slot_dma_flagged(self):
        """A copy started into a ring slot to be retired later, with no
        wait on its semaphore anywhere in the body."""
        fs = kc.check_traced_kernel(
            "fixture_unpaired_ring_dma", "fixture",
            bad_kernels.trace_unpaired_ring_dma())
        assert "dma-unpaired" in rules(fs)

    def test_unmasked_store_flagged(self):
        fs = kc.check_traced_kernel(
            "fixture_unmasked_store", "fixture",
            bad_kernels.trace_unmasked_store())
        assert rules(fs) == ["unmasked-store"]

    def test_direct_hbm_read_flagged(self):
        fs = kc.check_traced_kernel(
            "fixture_direct_hbm", "fixture", bad_kernels.trace_direct_hbm())
        assert "memory-space" in rules(fs)

    def test_trace_failure_is_a_finding(self):
        spec = registry.KernelSpec(
            "boom", "fixture", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        fs = kc.check_kernels([spec])
        assert rules(fs) == ["trace-failed"]


class TestRolesFixture:
    def test_unannotated_op_flagged(self):
        fs = roles_checker.check_annotations(bad_ops, path="fixture")
        assert [(f.rule, f.subject) for f in fs] == \
            [("unannotated-op", "mystery_op")]

    def test_annotated_and_non_ops_not_flagged(self):
        subjects = {f.subject
                    for f in roles_checker.check_annotations(bad_ops)}
        assert "annotated_op" not in subjects
        assert "free_function" not in subjects
        assert "_private_helper" not in subjects


class TestTelemetryFixture:
    def test_missing_seam_flagged(self):
        fs = tel_checker.check_telemetry(bad_ops, path="fixture", exempt={})
        assert [(f.rule, f.subject) for f in fs] == \
            [("missing-telemetry-seam", "annotated_op")]

    def test_seamed_unannotated_and_exempt_not_flagged(self):
        # telemetered_op threads the seam; mystery_op has no role (the
        # roles checker owns that); an exemption silences annotated_op
        fs = tel_checker.check_telemetry(
            bad_ops, path="fixture",
            exempt={"annotated_op": "fixture rationale"})
        assert fs == []

    def test_stale_exemptions_flagged(self):
        fs = tel_checker.check_telemetry(
            bad_ops, path="fixture",
            exempt={"annotated_op": "ok",
                    "ghost_op": "no longer exists",
                    "telemetered_op": "grew the seam"})
        assert [(f.rule, f.subject) for f in fs] == \
            [("stale-exemption", "ghost_op"),
             ("stale-exemption", "telemetered_op")]


class TestForkFixture:
    FIXTURE = REPO / "src/repro/analysis/fixtures/bad_fork.py"

    def test_inline_match_formula_flagged(self):
        fs = oc.scan_source(self.FIXTURE.read_text(), "fixtures/bad_fork.py")
        assert rules(fs) == ["match-formula-fork"]
        assert len(fs) == 1, "control conjunction must not be flagged"

    def test_forked_definition_flagged(self):
        src = "def match_lanes(a, b, c, d):\n    return (a == c) & (b == d)\n"

        class FakePath:
            def __init__(self, text):
                self._t = text

            def read_text(self):
                return self._t

        files = [("repro/core/find.py", FakePath(src)),
                 ("repro/kernels/evil.py", FakePath(src))]
        fs = oc.check_multiplicity(files)
        assert "oracle-multiplicity" in rules(fs)


class TestCompileCacheAudit:
    def test_recompile_detected(self, monkeypatch):
        import repro.analysis.compile_cache as cc

        monkeypatch.setattr(cc, "scenarios", lambda: [
            cc.Scenario("hot", 1, lambda: 3),
            cc.Scenario("under", 2, lambda: 1),
            cc.Scenario("boom", 1,
                        lambda: (_ for _ in ()).throw(ValueError("x"))),
        ])
        fs = cc.check_compile_cache()
        assert rules(fs) == ["audit-error", "recompile", "under-exercised"]


# ---------------------------------------------------------------------------
# cleanliness: the shipped tree passes every checker
# ---------------------------------------------------------------------------

class TestShippedTreeClean:
    def test_kernel_contracts_clean(self):
        assert kc.check_kernels() == []

    def test_hmem_seam_clean(self):
        assert kc.check_hmem_seam() == []

    def test_roles_clean(self):
        assert roles_checker.check_roles() == []

    def test_oracle_coupling_clean(self):
        assert oc.check_oracle_coupling() == []

    def test_telemetry_clean(self):
        # every @roles-annotated op threads telemetry= or carries a
        # reviewed TELEMETRY_EXEMPT rationale, and no exemption is stale
        assert tel_checker.check_telemetry() == []

    def test_registry_covers_every_pallas_file(self):
        assert registry.unregistered_kernel_files() == []

    @pytest.mark.slow
    def test_compile_cache_clean(self):
        from repro.analysis.compile_cache import check_compile_cache
        assert check_compile_cache() == []


# ---------------------------------------------------------------------------
# findings model: waivers and output formats
# ---------------------------------------------------------------------------

def _finding(rule="unmasked-store", subject="k1", sev="error"):
    return findings_mod.Finding("kernel-contracts", rule, subject,
                                "msg with % and\nnewline",
                                path="src/x.py", line=3, severity=sev)


class TestFindingsModel:
    def test_waiver_glob_matches_and_annotates(self):
        w = findings_mod.Waiver("kernel-contracts", "unmasked-store", "k*",
                                "known benign: sentinel fill")
        out = findings_mod.apply_waivers([_finding()], (w,))
        assert out[0].waived and "sentinel" in out[0].waiver_reason
        assert findings_mod.unwaived(out) == []

    def test_waiver_requires_all_three_axes(self):
        w = findings_mod.Waiver("roles", "unmasked-store", "k*", "no")
        out = findings_mod.apply_waivers([_finding()], (w,))
        assert not out[0].waived
        assert findings_mod.unwaived(out) == out

    def test_warning_severity_not_fatal(self):
        out = findings_mod.apply_waivers([_finding(sev="warning")], ())
        assert findings_mod.unwaived(out) == []

    def test_text_format_summary_line(self):
        txt = findings_mod.format_text([_finding()])
        assert "hkv-lint: 1 finding(s), 1 fatal, 0 waived" in txt
        assert "src/x.py:3" in txt

    def test_github_format_escapes_workflow_commands(self):
        gh = findings_mod.format_github([_finding()])
        line = gh.splitlines()[0]
        assert line.startswith("::error file=src/x.py,line=3")
        assert "%25" in line and "%0A" in line

    def test_no_shipped_waivers(self):
        # satellite 1: the shipped tree is clean WITHOUT waivers; any
        # future waiver must come with a reviewed rationale here.
        assert findings_mod.WAIVERS == ()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def test_run_all_subset_unknown_checker(self):
        with pytest.raises(SystemExit):
            analysis.run_all(only=["nope"])

    @pytest.mark.slow
    def test_cli_clean_exit_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis",
             "--checker", "oracle-coupling", "--checker", "roles"],
            capture_output=True, text=True,
            cwd=REPO, env={"PYTHONPATH": str(REPO / "src"),
                           "PATH": "/usr/bin:/bin:/usr/local/bin",
                           "HOME": "/tmp"})
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 fatal" in proc.stdout
