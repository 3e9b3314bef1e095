"""repro.analyze: rule-family fixtures, suppressions, baseline, CLI, bench.

Pure-AST tests — nothing here traces jax.  Each committed bad-snippet
fixture under ``tests/analyze_fixtures/`` must trip its rule family
(exit 1 through the CLI), the good/suppressed twins must not, and the live
repo tree must be clean under ``--strict`` — that last test is the same
gate CI runs ahead of pytest.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from repro.analyze import ALL_RULES, BY_FAMILY, analyze_paths
from repro.analyze import bench
from repro.analyze.__main__ import main as analyze_main
from repro.analyze.core import Finding, baselined

ROOT = Path(__file__).resolve().parents[1]
FIX = ROOT / "tests" / "analyze_fixtures"


def codes_of(path, rules=None):
    findings, _ = analyze_paths([path], ROOT, rules)
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# One test per rule family: the committed bad snippet must trip it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fixture,expected", [
    ("bad_clock.py", {"CLK001"}),
    ("bad_host_sync.py", {"SYNC001"}),
    ("bad_jit_cache.py", {"JIT001"}),
    ("bad_jit_static.py", {"JIT002"}),
    ("bad_jit_module_state.py", {"JIT003"}),
    ("bad_pallas_grid.py", {"PAL001"}),
    ("bad_pallas_arity.py", {"PAL002"}),
    ("bad_pallas_effect.py", {"PAL003"}),
    ("bad_pallas_vmem.py", {"PAL004"}),
    ("bad_pallas_divis.py", {"PAL005"}),
    ("bad_trace.py", {"TRACE001", "TRACE002", "TRACE003"}),
    ("bad_deprecated.py", {"DEP001"}),
])
def test_bad_fixture_trips_rule(fixture, expected):
    got = codes_of(FIX / fixture)
    assert expected <= got, f"{fixture}: wanted {expected}, got {got}"


@pytest.mark.parametrize("fixture,expected", [
    ("bad_clock.py", 1),
    ("bad_host_sync.py", 1),
    ("good_host_sync.py", 0),
])
def test_cli_exit_codes(fixture, expected, capsys):
    rc = analyze_main([str(FIX / fixture), "--root", str(ROOT)])
    assert rc == expected, capsys.readouterr().out


@pytest.mark.parametrize("fixture", ["good_host_sync.py",
                                     "good_pallas_grid_spec.py"])
def test_good_fixture_is_clean(fixture):
    assert codes_of(FIX / fixture) == set()


def test_inline_allow_suppresses_and_is_counted():
    findings, suppressed = analyze_paths([FIX / "suppressed_sync.py"], ROOT)
    assert not findings
    assert {f.rule for f in suppressed} == {"SYNC001"}


def test_bad_dispatch_tree_flags_every_missing_leg():
    tree = FIX / "bad_dispatch_tree"
    findings, _ = analyze_paths(
        [tree / "src"], tree, [BY_FAMILY["dispatch-registry"]])
    got = {f.rule for f in findings}
    assert {"DISP001", "DISP002", "DISP003", "DISP004", "DISP005",
            "DISP006", "DISP007", "DISP008"} <= got, got


def test_at_least_six_rule_families():
    assert len(ALL_RULES) >= 6
    for mod in ALL_RULES:
        assert mod.FAMILY and mod.CODES and callable(mod.check)


def test_findings_carry_location_and_hint():
    findings, _ = analyze_paths([FIX / "bad_clock.py"], ROOT)
    f = findings[0]
    assert f.path.endswith("bad_clock.py") and f.line > 0
    assert "perf_counter" in f.hint
    rendered = f.render()
    assert f"{f.path}:{f.line}" in rendered and f.rule in rendered


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------
def test_baseline_grandfathers_by_rule_and_path(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [
        {"rule": "wall-clock", "path": "tests/analyze_fixtures/bad_clock.py"},
    ]}))
    rc = analyze_main([str(FIX / "bad_clock.py"), "--root", str(ROOT),
                       "--baseline", str(bl)])
    assert rc == 0


def test_strict_fails_on_stale_baseline_entry(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [
        {"rule": "CLK001", "path": "no/such/file.py"},
    ]}))
    rc = analyze_main([str(FIX / "good_host_sync.py"), "--root", str(ROOT),
                       "--baseline", str(bl), "--strict"])
    assert rc == 1


def test_baselined_matching_semantics():
    f = Finding("CLK001", "wall-clock", "src/a/b.py", 3, 0, "time.time() x")
    assert baselined(f, [{"rule": "*", "path": "src/**"}])
    assert baselined(f, [{"rule": "wall-clock", "path": "src/a/*.py"}])
    assert baselined(f, [{"rule": "CLK001", "path": "src/a/b.py",
                          "message": "time.time()"}])
    assert not baselined(f, [{"rule": "CLK001", "path": "tests/*"}])
    assert not baselined(f, [{"rule": "SYNC001", "path": "src/a/b.py"}])


# ---------------------------------------------------------------------------
# The CI gates: live tree clean under --strict; BENCH reports valid
# ---------------------------------------------------------------------------
def test_live_tree_clean_under_strict(capsys):
    rc = analyze_main(["--strict", "--root", str(ROOT)])
    out = capsys.readouterr().out
    assert rc == 0, f"live tree has findings:\n{out}"
    assert "0 finding(s)" in out


def test_bench_reports_all_valid():
    errors = bench.check_all(ROOT, report=lambda *_: None)
    assert errors == []


def test_bench_checker_catches_breakage():
    rec = json.loads((ROOT / "BENCH_kernels.json").read_text())
    rec.pop("mode")
    rec["rows"][0]["max_rel_err"] = 0.5
    del rec["rows"][1]["kind"]
    errors = bench.check_report("kernels", rec)
    assert any("missing top-level key 'mode'" in e for e in errors)
    assert any("max_rel_err" in e for e in errors)
    assert any("missing field 'kind'" in e for e in errors)


def test_bench_cli_exit_code():
    assert analyze_main(["--bench", "--root", str(ROOT)]) == 0


def test_bench_missing_file_is_an_error(tmp_path):
    errs = bench.check_file("kernels", tmp_path / "BENCH_kernels.json")
    assert errs and "does not exist" in errs[0]
