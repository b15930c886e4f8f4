"""Layer-1 lint: rule engine, fixture corpus, suppressions, baseline.

The fixture corpus under tests/analysis_fixtures/ is the executable rule
spec: every rule has a must-flag file (reproducing the originating bug —
RA001 is the seed's `jnp.maximum.accumulate` line, RA002 is PR 6's
unguarded `donate_argnums`) and a must-pass file (the sanctioned
spelling the repo actually uses).  The engine itself is stdlib-only, so
none of these tests import jax.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis.baseline import Baseline, line_hash
from repro.analysis.engine import analyze_paths, iter_py_files, suppressed_rules_for_line
from repro.analysis.rules import RULES, check_source

FIXTURES = Path(__file__).parent / "analysis_fixtures"
REPO = Path(__file__).resolve().parent.parent

ALL_RULES = sorted(RULES)


def _check_fixture(name: str):
    path = FIXTURES / name
    return check_source(path.read_text(), str(path))


# ---------------------------------------------------------------------------
# per-rule must-flag / must-pass corpora
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_must_flag(rule):
    findings = _check_fixture(f"{rule.lower()}_flag.py")
    assert any(f.rule == rule for f in findings), f"{rule} missed its must-flag fixture"


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_must_pass(rule):
    findings = _check_fixture(f"{rule.lower()}_pass.py")
    assert findings == [], f"{rule} false positives: {[f.format() for f in findings]}"


def test_ra001_reproduces_seed_bug():
    """The historical proof: the literal seed line trips RA001."""
    path = FIXTURES / "ra001_flag.py"
    src = path.read_text()
    assert "jnp.maximum.accumulate" in src  # the seed's segmentation bug, verbatim
    flagged_lines = {f.line for f in _check_fixture("ra001_flag.py") if f.rule == "RA001"}
    bug_line = next(
        i
        for i, l in enumerate(src.splitlines(), 1)
        if "return jnp.maximum.accumulate" in l
    )
    assert bug_line in flagged_lines


def test_ra002_reproduces_pr6_bug():
    """The historical proof: unguarded donate_argnums trips RA002, the
    trainer's default_backend() guard does not."""
    assert any(f.rule == "RA002" for f in _check_fixture("ra002_flag.py"))
    assert not _check_fixture("ra002_pass.py")
    # the real guarded site ships clean
    trainer = REPO / "src" / "repro" / "train" / "trainer.py"
    findings = check_source(trainer.read_text(), str(trainer))
    assert not [f for f in findings if f.rule == "RA002"]


def test_ra005_flags_every_module():
    """No module is exempt: the experimental context is gone everywhere,
    and the supported ``jax.enable_x64`` passes."""
    src = "from jax.experimental import enable_x64\n"
    for path in ("src/repro/sim/device_timeline.py", "src/repro/sim/cluster.py"):
        assert [f.rule for f in check_source(src, path)] == ["RA005"]
    assert check_source("import jax\nwith jax.enable_x64(True):\n    pass\n", "src/repro/x.py") == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_comment_parsing():
    assert suppressed_rules_for_line("x = f()") is None
    assert suppressed_rules_for_line("x = f()  # ra: ignore") == {"*"}
    assert suppressed_rules_for_line("x = f()  # ra: ignore[RA001]") == {"RA001"}
    assert suppressed_rules_for_line("x = f()  # RA: Ignore[ra003, RA006]") == {
        "RA003",
        "RA006",
    }


def test_suppressions_fixture():
    result = analyze_paths([FIXTURES / "suppressions.py"])
    # targeted + blanket ignores suppress; the wrong-rule ignore does not
    assert [f.rule for f in result.active] == ["RA001"]
    assert len(result.suppressed) == 2
    # the surviving finding is the one whose ignore names the wrong rule
    assert "ra: ignore[RA003]" in result.active[0].source_line


# ---------------------------------------------------------------------------
# engine: walking, exclusions, errors
# ---------------------------------------------------------------------------


def test_fixture_dir_excluded_from_directory_walk():
    files = iter_py_files([REPO / "tests"])
    assert not any("analysis_fixtures" in str(f) for f in files)
    # but explicit file arguments always analyze
    explicit = iter_py_files([FIXTURES / "ra001_flag.py"])
    assert len(explicit) == 1


def test_syntax_error_reported_not_raised(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(:\n")
    result = analyze_paths([bad])
    assert result.errors and not result.ok


def test_missing_path_raises():
    with pytest.raises(FileNotFoundError):
        analyze_paths([REPO / "no_such_dir_xyz"])


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_line_drift(tmp_path):
    fixture = FIXTURES / "ra001_flag.py"
    findings = analyze_paths([fixture]).active
    assert findings

    bl_path = tmp_path / "baseline.json"
    Baseline.from_findings(findings).save(bl_path)
    bl = Baseline.load(bl_path)

    clean = analyze_paths([fixture], baseline=bl)
    assert clean.active == [] and len(clean.baselined) == len(findings)

    # line drift: same line content at a new line number still matches
    drifted = tmp_path / "drifted.py"
    drifted.write_text("# new leading comment\n\n" + fixture.read_text())
    res = analyze_paths([drifted], baseline=bl)
    # paths differ -> nothing matches; rebuild keyed on the drifted path
    bl2 = Baseline.from_findings(res.active)
    res2 = analyze_paths([drifted], baseline=bl2)
    assert res2.active == []
    # now shift the lines again: hash is content-keyed, so still baselined
    drifted.write_text("# another comment\n" + drifted.read_text())
    res3 = analyze_paths([drifted], baseline=bl2)
    assert res3.active == [] and not res3.stale_baseline


def test_baseline_stale_entries_surface(tmp_path):
    bl = Baseline.from_findings([])
    bl.entries[("RA001", "gone.py", line_hash("x = 1"))] = 1
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    res = analyze_paths([clean], baseline=bl)
    assert res.active == [] and len(res.stale_baseline) == 1


def test_baseline_count_consumption(tmp_path):
    dup = tmp_path / "dup.py"
    dup.write_text(
        "import jax.numpy as jnp\n"
        "def a(v):\n"
        "    return jnp.maximum.accumulate(v)\n"
        "def b(v):\n"
        "    return jnp.maximum.accumulate(v)\n"
    )
    findings = analyze_paths([dup]).active
    assert len(findings) == 2
    # baseline only ONE occurrence: the identical second line stays active
    bl = Baseline.from_findings(findings[:1])
    res = analyze_paths([dup], baseline=bl)
    assert len(res.active) == 1 and len(res.baselined) == 1


# ---------------------------------------------------------------------------
# CLI + the tree itself
# ---------------------------------------------------------------------------


def _run_cli(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )


def test_cli_flags_fixture_and_passes_tree():
    bad = _run_cli("tests/analysis_fixtures/ra001_flag.py")
    assert bad.returncode == 1 and "RA001" in bad.stdout

    good = _run_cli("src", "benchmarks", "tests")
    assert good.returncode == 0, good.stdout + good.stderr


def test_cli_json_and_list_rules():
    out = _run_cli("--list-rules")
    assert out.returncode == 0
    for rule in ALL_RULES:
        assert rule in out.stdout

    js = _run_cli("--json", "tests/analysis_fixtures/ra002_flag.py")
    payload = json.loads(js.stdout)
    assert payload["ok"] is False
    assert [f["rule"] for f in payload["active"]] == ["RA002"]


def test_cli_usage_errors():
    assert _run_cli().returncode == 2
    assert _run_cli("--rule", "RA999", "src").returncode == 2
    assert _run_cli("no/such/path").returncode == 2


def test_tree_is_clean_in_process():
    """The acceptance invariant: zero unsuppressed findings on the tree."""
    result = analyze_paths([REPO / "src", REPO / "benchmarks", REPO / "tests"])
    assert result.ok, [f.format() for f in result.active] + result.errors
    assert result.files_checked > 50
