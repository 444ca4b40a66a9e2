"""palplint framework tests: every rule against its positive/negative
fixtures, suppression semantics, CLI exit codes + output formats, the
``--fix`` rewrites, the result cache, and the zero-violation sweep of
the real tree (the CI gate, run here so a violation fails tests too).
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from tools.palplint import RULES, run_rule
from tools.palplint.diagnostics import Suppressions
from tools.palplint.engine import (
    ResultCache,
    fix_file,
    iter_python_files,
    lint_file,
    lint_paths,
)
from tools.palplint.registry import load_rules

pytestmark = pytest.mark.tier1

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "palplint_fixtures"
ALL_CODES = ["PALP001", "PALP002", "PALP003",
             "PALP101", "PALP102", "PALP103", "PALP104",
             "PALP201", "PALP202", "PALP203",
             "PALP301"]


def fixture(name: str) -> str:
    return str(FIXTURES / name)


# ------------------------------------------------------------ rule set

def test_at_least_eight_active_rules():
    load_rules()
    assert len(RULES) >= 8
    assert sorted(RULES) == ALL_CODES
    families = {r.family for r in RULES.values()}
    assert families == {"determinism", "futures", "tracer",
                        "observability"}


# ---------------------------------------------- positive/negative pairs

@pytest.mark.parametrize("code", ALL_CODES)
def test_rule_fires_on_positive_fixture(code):
    diags = run_rule(code, fixture(f"{code.lower()}_bad.py"))
    assert any(d.code == code for d in diags), diags


@pytest.mark.parametrize("code", ALL_CODES)
def test_rule_quiet_on_negative_fixture(code):
    diags = run_rule(code, fixture(f"{code.lower()}_good.py"))
    assert not [d for d in diags if d.code == code], diags


def test_positive_counts_and_lines_are_stable():
    """Pin the exact per-fixture hit counts so a rule that silently
    broadens or narrows shows up as a diff here, not just in CI noise."""
    expect = {"PALP001": 6, "PALP002": 6, "PALP003": 6,
              "PALP101": 3, "PALP102": 2, "PALP103": 2, "PALP104": 2,
              "PALP201": 3, "PALP202": 3, "PALP203": 2,
              "PALP301": 7}
    for code, n in sorted(expect.items()):
        diags = [d for d in run_rule(code, fixture(f"{code.lower()}_bad.py"))
                 if d.code == code]
        assert len(diags) == n, (code, [d.format() for d in diags])
        assert all(d.line > 0 and d.col > 0 for d in diags)


def test_alias_imports_do_not_dodge_rules():
    d1 = [d.line for d in run_rule("PALP001", fixture("palp001_bad.py"))]
    # `_t.monotonic()` and `from time import perf_counter` sites
    assert len(d1) >= 4
    d2 = [d for d in run_rule("PALP002", fixture("palp002_bad.py"))
          if "alias" not in d.message]
    assert d2


# ------------------------------------------------------- suppressions

def test_justified_suppression_silences_rule():
    diags = lint_file(fixture("suppressed_ok.py"),
                      select={"PALP001"}, force_scope=True)
    assert diags == []


def test_unjustified_suppression_is_inert_and_reported():
    diags = lint_file(fixture("suppressed_bad.py"),
                      select={"PALP001"}, force_scope=True)
    codes = sorted(d.code for d in diags)
    assert codes == ["PALP000", "PALP001"]


def test_own_line_suppression_covers_next_statement():
    src = ("def f(t):\n"
           "    # palplint: disable=PALP001 -- why not\n"
           "    return t\n")
    sup = Suppressions.parse(src)
    assert sup.is_suppressed("PALP001", 2)
    assert sup.is_suppressed("PALP001", 3)
    assert not sup.is_suppressed("PALP001", 1)
    assert not sup.is_suppressed("PALP002", 3)


def test_disable_file_suppression():
    src = ("# palplint: disable-file=PALP003 -- order-free module\n"
           "x = 1\n")
    sup = Suppressions.parse(src)
    assert sup.is_suppressed("PALP003", 99)
    assert not sup.is_suppressed("PALP001", 99)


# -------------------------------------------------------------- engine

def test_fixture_dir_excluded_from_directory_walks():
    files = iter_python_files([str(REPO / "tests")])
    assert not any("palplint_fixtures" in f for f in files)
    # explicitly named files are linted regardless
    files = iter_python_files([fixture("palp001_bad.py")])
    assert len(files) == 1


def test_syntax_error_reported_not_raised(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    diags = lint_file(str(p))
    assert [d.code for d in diags] == ["PALP999"]


def test_repo_tree_is_clean(monkeypatch):
    """The ratcheted-to-zero baseline: the real tree has no violations.
    (This is the same invocation CI gates on.)"""
    monkeypatch.chdir(REPO)
    diags, n_files = lint_paths(["src", "benchmarks", "tools", "tests"])
    assert n_files > 80
    assert diags == [], [d.format() for d in diags]


def test_result_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.chdir(REPO)
    cache_path = str(tmp_path / "cache.json")
    target = ["src/repro/core/mining.py", "src/repro/core/cluster.py"]
    d1, n1 = lint_paths(target, cache=ResultCache(cache_path))
    assert os.path.exists(cache_path)
    warm = ResultCache(cache_path)
    assert warm.get(target[0]) == []
    d2, n2 = lint_paths(target, cache=warm)
    assert (d1, n1) == (d2, n2)
    # a rules-digest mismatch invalidates wholesale
    data = json.loads(Path(cache_path).read_text())
    data["digest"] = "stale"
    Path(cache_path).write_text(json.dumps(data))
    assert ResultCache(cache_path).get(target[0]) is None


# ----------------------------------------------------------------- CLI

def run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.palplint", *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_exit_zero_on_clean_tree():
    proc = run_cli("src", "benchmarks", "tools")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_fails_on_seeded_fixture_violation():
    """The CI-gate demonstration: pointing the linter at a violating
    fixture exits non-zero with the diagnostic on stdout."""
    proc = run_cli("--select", "PALP001", "--force-scope",
                   "tests/palplint_fixtures/palp001_bad.py")
    assert proc.returncode == 1
    assert "PALP001" in proc.stdout
    assert "palp001_bad.py" in proc.stdout


def test_cli_json_format():
    proc = run_cli("--select", "PALP002", "--force-scope", "--format",
                   "json", "tests/palplint_fixtures/palp002_bad.py")
    assert proc.returncode == 1
    out = json.loads(proc.stdout)
    assert out["ok"] is False
    assert out["counts"]["PALP002"] == 6
    assert all({"path", "line", "col", "code", "message"}
               <= set(d) for d in out["diagnostics"])


def test_cli_usage_errors():
    assert run_cli("--select", "PALP777").returncode == 2
    assert run_cli("no/such/path").returncode == 2
    assert run_cli("--force-scope", "src").returncode == 2


def test_cli_list_rules():
    proc = run_cli("--list-rules")
    assert proc.returncode == 0
    for code in ALL_CODES:
        assert code in proc.stdout


def test_cli_github_summary(tmp_path):
    summary = tmp_path / "summary.md"
    env = dict(os.environ, GITHUB_STEP_SUMMARY=str(summary))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.palplint", "src", "tools",
         "--github-summary"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert proc.returncode == 0
    text = summary.read_text()
    assert "## palplint" in text and "✅" in text
    for code in ALL_CODES:
        assert code in text


# ----------------------------------------------------------------- fix

def test_fix_rewrites_wall_clock_in_benchmarks(tmp_path, monkeypatch):
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    f = bench / "bench_toy.py"
    f.write_text(
        "import time\n"
        "\n"
        "\n"
        "def timed(fn):\n"
        "    t0 = time.perf_counter()\n"
        "    fn()\n"
        "    return time.perf_counter() - t0\n")
    monkeypatch.chdir(tmp_path)
    assert fix_file(str(f)) > 0
    out = f.read_text()
    assert "time.perf_counter()" not in out
    assert "wall_clock()" in out
    assert "from .common import wall_clock" in out


def test_fix_rewrites_unseeded_numpy_rng(tmp_path, monkeypatch):
    core = tmp_path / "src" / "repro" / "core"
    core.mkdir(parents=True)
    f = core / "toy.py"
    f.write_text(
        "import numpy as np\n"
        "\n"
        "\n"
        "def draws():\n"
        "    a = np.random.randint(0, 10, size=4)\n"
        "    b = np.random.rand(3, 4)\n"
        "    return a, b\n")
    monkeypatch.chdir(tmp_path)
    assert fix_file(str(f)) > 0
    out = f.read_text()
    assert "np.random.default_rng(0).integers(0, 10, size=4)" in out
    assert "np.random.default_rng(0).standard_normal" not in out
    assert "np.random.default_rng(0).random((3, 4,))" in out
    # the rewritten file is PALP002-clean and still valid python
    compile(out, str(f), "exec")
    assert not [d for d in lint_file(str(f)) if d.code == "PALP002"]


def test_fix_roundtrip_on_fixture_copy(tmp_path, monkeypatch):
    """--fix over a copied bad fixture leaves mechanically-fixable
    PALP002 sites clean without touching anything else."""
    core = tmp_path / "src" / "repro" / "core"
    core.mkdir(parents=True)
    dst = core / "palp002_bad.py"
    shutil.copy(fixture("palp002_bad.py"), dst)
    monkeypatch.chdir(tmp_path)
    before = [d for d in lint_file(str(dst)) if d.code == "PALP002"]
    assert before
    fix_file(str(dst))
    compile(dst.read_text(), str(dst), "exec")
    after = [d for d in lint_file(str(dst)) if d.code == "PALP002"]
    # seed/no-arg-default_rng sites are design decisions, not mechanical
    assert len(after) < len(before)


# ------------------------------------------- swept-behavior regressions

def test_wall_clock_accessor_monotone():
    from benchmarks.common import wall_clock

    t0 = wall_clock()
    t1 = wall_clock()
    assert isinstance(t0, float) and t1 >= t0
