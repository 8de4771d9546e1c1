"""SPMD-lint layer 1 (jaxpr/HLO rules) against tests/lint_corpus/."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.analysis import (LintConfig, SuppressionIndex,
                            dtype_conversion_table, lint_hlo_text,
                            lint_lowerable, scan_suppressions,
                            tlr_dense_frac)

CORPUS = os.path.join(os.path.dirname(__file__), "lint_corpus")
_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _corpus(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CORPUS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lint(case, **extra):
    fn, specs, kw = case()
    kw.update(extra)
    return lint_lowerable(fn, specs, **kw)


def _live(report, rule, min_severity="warning"):
    order = {"info": 0, "warning": 1, "error": 2}
    return [f for f in report.findings
            if f.rule == rule and not f.suppressed
            and order[f.severity] >= order[min_severity]]


# ---------------------------------------------------------------------------
# Rule-by-rule corpus pairs
# ---------------------------------------------------------------------------


def test_r2a_dead_undonated_pair():
    mod = _corpus("r2_dead_undonated")
    bad = _lint(mod.make_bad)
    hits = _live(bad, "R2")
    assert len(hits) == 2, bad.findings
    assert all("not donated" in f.message for f in hits)
    assert bad.summary["undonated_dead_bytes"] == 2 * mod.M * mod.M * 4
    good = _lint(mod.make_good)
    assert not _live(good, "R2"), good.findings
    assert good.summary["undonated_dead_bytes"] == 0


def test_r2b_failed_donation_pair():
    mod = _corpus("r2_failed_donation")
    bad = _lint(mod.make_bad)
    hits = [f for f in _live(bad, "R2") if f.op == "donate_argnums"]
    assert hits and hits[0].severity == "error", bad.findings
    assert "no matching outputs" in hits[0].message
    # R2b (a donation mistake, not a missing donation) stays out of the
    # undonated_dead_bytes bench gate.
    assert bad.summary["undonated_dead_bytes"] == 0
    good = _lint(mod.make_good)
    assert not _live(good, "R2"), good.findings


def test_r3_dense_sigma_pair():
    mod = _corpus("r3_dense_sigma")
    bad = _lint(mod.make_bad)
    hits = _live(bad, "R3", "error")
    assert hits, bad.findings
    assert any("dense Sigma must never be formed" in f.message for f in hits)
    good = _lint(mod.make_good)
    assert not _live(good, "R3", "info"), good.findings


def test_r4_convert_churn_pair():
    mod = _corpus("r4_convert_churn")
    bad = _lint(mod.make_bad)
    hits = _live(bad, "R4")
    assert hits, bad.findings
    assert any("inside a scan/while body" in f.message for f in hits)
    rows = dtype_conversion_table(bad.findings)
    assert any(r["in_loop"] and r["bytes"] > 0 for r in rows)
    good = _lint(mod.make_good)
    assert not _live(good, "R4", "info"), good.findings


def test_r5_dynamic_while_pair():
    mod = _corpus("r5_dynamic_while")
    bad = _lint(mod.make_bad)
    hits = _live(bad, "R5", "error")
    assert hits, bad.findings
    assert "s64" in hits[0].message
    good = _lint(mod.make_good)
    assert not _live(good, "R5", "info"), good.findings


def test_r1_replicated_qr_pair_multidevice():
    """R1 needs a multi-device mesh: run the corpus pair on 8 fake CPUs."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {_SRC!r})
        import importlib.util
        import jax
        jax.config.update("jax_enable_x64", True)
        spec = importlib.util.spec_from_file_location(
            "r1", os.path.join({CORPUS!r}, "r1_replicated_qr.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        from repro.analysis import lint_lowerable
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((8,), ("data",))
        fn, specs, kw = mod.make_bad(mesh)
        rep = lint_lowerable(fn, specs, mesh=mesh, **kw)
        bad = [f for f in rep.findings if f.rule == "R1" and not f.suppressed]
        assert bad, rep.findings
        assert rep.summary["replicated_temp_bytes"] > 0, rep.summary
        assert any("PER DEVICE" in f.message for f in bad)
        fn, specs, kw = mod.make_good(mesh)
        rep = lint_lowerable(fn, specs, mesh=mesh, **kw)
        good = [f for f in rep.findings if f.rule == "R1" and not f.suppressed]
        assert not good, good
        assert rep.summary["replicated_temp_bytes"] == 0, rep.summary
        print("R1-PAIR-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "R1-PAIR-OK" in out.stdout


# ---------------------------------------------------------------------------
# R1 HLO-text unit behaviour (no devices needed)
# ---------------------------------------------------------------------------

_HLO_LINE = ('  %qr = (f32[512,64,64], f32[512,64]) custom-call(%x), '
             'custom_call_target="lapack_sgeqrf", '
             'metadata={{op_name="{op}" '
             'source_file="/tmp/corpus_x.py" source_line=7}}')


def test_r1_hlo_text_unsharded_vs_shmap():
    unsharded = _HLO_LINE.format(op="jit(fn)/qr")
    fs = lint_hlo_text(unsharded, n_devices=8)
    assert len(fs) == 1 and fs[0].rule == "R1"
    assert "GSPMD has no partitioning rule" in fs[0].message
    # under shard_map the same bytes only warn, with the per-device message
    sharded = _HLO_LINE.format(op="jit(fn)/jit(shmap_body)/qr")
    fs = lint_hlo_text(sharded, n_devices=8)
    assert len(fs) == 1 and fs[0].severity == "warning"
    assert "shard_map" in fs[0].message
    # huge unsharded batches escalate to error
    big = unsharded.replace("f32[512,64,64]", "f32[65536,64,64]")
    fs = lint_hlo_text(big, n_devices=8)
    assert fs and fs[0].severity == "error"
    # single device: replication is impossible, rule disarmed
    assert lint_hlo_text(unsharded, n_devices=1) == []


def test_r1_suppression_via_source_comment(tmp_path):
    src = tmp_path / "lowering.py"
    src.write_text("# spmdlint: ignore[R1] tiny panel head on purpose\n"
                   "q = qr(x)\n")
    line = _HLO_LINE.format(op="jit(fn)/qr").replace(
        "/tmp/corpus_x.py", str(src)).replace("source_line=7",
                                              "source_line=2")
    idx = SuppressionIndex()
    fs = idx.apply(lint_hlo_text(line, n_devices=8))
    assert fs[0].suppressed
    assert "tiny panel head" in fs[0].suppress_reason


def test_scan_suppressions_and_reach():
    table = scan_suppressions(
        "x = 1\n# spmdlint: ignore[R1,R5] two rules\ny = 2\n")
    assert table[2][0] == {"R1", "R5"}
    assert table[2][1] == "two rules"
    idx = SuppressionIndex()
    idx.add_source("f.py", "# spmdlint: ignore[R3] above\na = 1\nb = 2\n")
    assert idx.lookup("R3", "f.py", 3) == "above"       # reach 2 lines up
    assert idx.lookup("R3", "f.py", 4) is None          # out of reach
    assert idx.lookup("R1", "f.py", 3) is None          # wrong rule


def test_tlr_dense_frac_geometry():
    # production geometry (kmax/nb = 1/16) keeps the strict default bar
    assert tlr_dense_frac(2048, 128) == 0.25
    # fat dev tiles scale the bar past the legitimate 4 kmax/nb storage
    assert tlr_dense_frac(64, 16) == 1.0                # reduced() config
    assert tlr_dense_frac(256, 32) == 0.5
    # the cap: the dense Sigma itself (m^2 elements) is always caught
    assert tlr_dense_frac(64, 64) == 1.0


def test_lint_config_thresholds_respected():
    """Raising donation_min_bytes above the corpus input size disarms R2a."""
    mod = _corpus("r2_dead_undonated")
    rep = _lint(mod.make_bad,
                config=LintConfig(donation_min_bytes=1 << 30))
    assert not _live(rep, "R2"), rep.findings


# ---------------------------------------------------------------------------
# Integration: the shipped TLR pipeline lowerable lints clean
# ---------------------------------------------------------------------------


def test_pipeline_lowerable_lints_clean_multidevice():
    """The acceptance gate as a test: the production pipeline lowerable has
    zero >= error findings on a multi-device mesh (the CLI exits 0)."""
    env = dict(os.environ, PYTHONPATH=_SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis",
         "--target", "dist_tlr_pipeline_lowerable",
         "--mesh", "cpu8", "--shape", "mle_4k"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "summary" in out.stdout


def test_cli_flags_bad_lowerable(tmp_path):
    """The CLI exit code is the gate: --ast on a tree with a seeded A3
    violation fails, and the same tree with the fix passes."""
    pkg = tmp_path / "core"
    pkg.mkdir()
    bad = open(os.path.join(CORPUS, "a3_host_linalg_bad.py")).read()
    (pkg / "mod.py").write_text(bad)
    env = dict(os.environ, PYTHONPATH=_SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--ast",
         "--ast-root", str(tmp_path), "--fail-on", "error"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 1, out.stdout
    assert "A3" in out.stdout
    good = open(os.path.join(CORPUS, "a3_host_linalg_good.py")).read()
    (pkg / "mod.py").write_text(good)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--ast",
         "--ast-root", str(tmp_path), "--fail-on", "error"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stdout
