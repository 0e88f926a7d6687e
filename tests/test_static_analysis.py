"""Tier-1 gate for the repo-native static analyzer (``pathway_tpu lint``).

Three properties, each load-bearing:

* **The golden corpus proves every rule fires** — one known-bad snippet
  per rule under ``tests/lint_corpus/``, with the expected finding
  pinned to an exact ``file:line`` by ``# EXPECT:`` markers in the
  corpus source itself (``# EXPECT-BELOW:`` for findings on suppression
  comment lines, where a trailing marker would parse as the reason).
  A rule that silently stops firing turns the clean-package assertion
  vacuous; this suite is what keeps it honest.

* **The package is clean** — ``pathway_tpu/`` + ``tests/`` lint to zero
  unsuppressed findings, and the suppression count is pinned (the
  ratchet: adding a suppression is a reviewed, counted event).

* **The gate is cheap and deterministic** — the full-tree run must fit
  the tier-1 budget (< 20 s, measured here, on the 2-core rig) and two
  runs must render byte-identically.
"""

from __future__ import annotations

import json
import os
import re
import time

import pytest

from pathway_tpu.analysis import RULES, report_to_text, run_lint

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CORPUS = os.path.join(HERE, "lint_corpus")

# the ratchet: every suppression in the real tree is a counted, reviewed
# exception.  If you add one, justify it in the PR and bump this number.
EXPECTED_SUPPRESSIONS = 1

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([a-z\-,]+)")
_EXPECT_BELOW_RE = re.compile(r"#\s*EXPECT-BELOW:\s*([a-z\-,]+)")


def _corpus_files() -> list[str]:
    """Every corpus .py, recursively — path-scoped rules (e.g.
    ``jit-outside-executor`` firing only under ``xpacks``/``stdlib``
    segments) need their known-bad snippets in matching subtrees."""
    out: list[str] = []
    for dirpath, dirnames, filenames in os.walk(CORPUS):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return out


def _expected_findings() -> set[tuple[str, int, str]]:
    """(basename, line, rule) for every EXPECT marker in the corpus."""
    expected: set[tuple[str, int, str]] = set()
    for path in _corpus_files():
        name = os.path.basename(path)
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, start=1):
                m = _EXPECT_BELOW_RE.search(line)
                if m is not None:
                    for rule in m.group(1).split(","):
                        expected.add((name, lineno + 1, rule.strip()))
                    continue
                m = _EXPECT_RE.search(line)
                if m is not None:
                    for rule in m.group(1).split(","):
                        expected.add((name, lineno, rule.strip()))
    return expected


@pytest.fixture(scope="module")
def corpus_report():
    return run_lint([CORPUS])


def test_golden_corpus_every_rule_fires(corpus_report):
    got = {
        (os.path.basename(f.path), f.line, f.rule)
        for f in corpus_report.findings
    }
    expected = _expected_findings()
    missing = expected - got
    surplus = got - expected
    assert not missing and not surplus, (
        f"corpus drift:\n  missing (marked but did not fire): "
        f"{sorted(missing)}\n  surplus (fired but unmarked): "
        f"{sorted(surplus)}"
    )
    # every non-meta rule must be exercised by at least one marker; the
    # meta rules the corpus can't or needn't hold: env-docs-stale gets a
    # dedicated fake-tree test below
    covered = {rule for _, _, rule in expected}
    uncoverable = {"env-docs-stale"}
    assert covered >= (set(RULES) - uncoverable), (
        f"rules with no corpus proof: {sorted(set(RULES) - uncoverable - covered)}"
    )


def test_golden_corpus_suppression_semantics(corpus_report):
    # the valid suppression silenced its finding (and only its finding)
    silenced = {
        (os.path.basename(f.path), f.rule) for f in corpus_report.suppressed
    }
    assert ("suppression_rules.py", "ctx-blocking-call") in silenced


def test_corpus_determinism():
    a = run_lint([CORPUS])
    b = run_lint([CORPUS])
    assert report_to_text(a) == report_to_text(b)
    assert report_to_text(a, as_json=True) == report_to_text(b, as_json=True)


def test_package_tree_is_clean_within_budget():
    # the process's own CPU time: beside five other xdist workers the wall
    # clock measures the machine, not the analyzer
    t0 = time.process_time()
    report = run_lint(
        [os.path.join(REPO, "pathway_tpu"), os.path.join(REPO, "tests")]
    )
    elapsed = time.process_time() - t0
    assert not report.findings, (
        "unsuppressed lint findings in the package tree:\n"
        + report_to_text(report)
    )
    # the ratchet: suppressions are counted, not free
    assert len(report.suppressions) == EXPECTED_SUPPRESSIONS, (
        f"suppression count changed ({len(report.suppressions)} != "
        f"{EXPECTED_SUPPRESSIONS}): "
        + "; ".join(f"{s.path}:{s.line} [{','.join(s.rules)}] {s.reason}"
                    for s in report.suppressions)
        + " — if deliberate, justify it in the PR and bump "
        "EXPECTED_SUPPRESSIONS"
    )
    # every suppression that exists must be in use (the audit guarantees
    # this via unused-suppression, but assert the invariant directly)
    assert len(report.suppressed) >= len(report.suppressions)
    # the tier-1 budget: the analyzer must never dominate the gate
    assert elapsed < 20.0, (
        f"lint over the full tree took {elapsed:.1f}s of CPU (budget 20s) — "
        "profile the call-graph passes before landing this"
    )


def test_env_docs_stale_fires_on_fake_tree(tmp_path):
    # a fake package root whose docs/configuration.md is missing, then
    # wrong: the rule must fire in both shapes (the real repo's in-sync
    # state is covered by test_package_tree_is_clean_within_budget)
    pkg = tmp_path / "pathway_tpu" / "internals"
    pkg.mkdir(parents=True)
    (pkg / "config.py").write_text("X = 1\n", encoding="utf-8")
    report = run_lint([str(tmp_path)], rules=["env-docs-stale"])
    assert [f.rule for f in report.findings] == ["env-docs-stale"]
    assert "missing" in report.findings[0].message

    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "configuration.md").write_text("hand-edited\n", encoding="utf-8")
    report = run_lint([str(tmp_path)], rules=["env-docs-stale"])
    assert [f.rule for f in report.findings] == ["env-docs-stale"]
    assert "does not match" in report.findings[0].message


def test_generated_config_docs_in_sync():
    # belt and braces: the exact byte-equality the rule enforces, stated
    # directly so a failure names the regeneration command
    from pathway_tpu.internals.config import render_env_docs

    path = os.path.join(REPO, "docs", "configuration.md")
    with open(path, encoding="utf-8") as f:
        actual = f.read()
    assert actual == render_env_docs(), (
        "docs/configuration.md is out of sync with "
        "internals/config.py:ENV_KNOBS — run "
        "`pathway_tpu lint --update-config-docs`"
    )


def test_unknown_rule_id_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint([CORPUS], rules=["no-such-rule"])


def test_cli_lint_corpus_and_flags():
    from click.testing import CliRunner

    from pathway_tpu.cli import cli

    runner = CliRunner()
    # corpus: findings -> exit 1, --json parses and carries file:line+rule
    result = runner.invoke(cli, ["lint", "--json", CORPUS])
    assert result.exit_code == 1
    payload = json.loads(result.stdout)
    assert payload["ok"] is False
    assert all(
        {"rule", "path", "line", "message"} <= set(f) for f in payload["findings"]
    )
    # a clean single file -> exit 0
    clean = os.path.join(REPO, "pathway_tpu", "analysis", "chaos.py")
    result = runner.invoke(cli, ["lint", clean])
    assert result.exit_code == 0, result.stdout
    # --list-rules names every registered rule
    result = runner.invoke(cli, ["lint", "--list-rules"])
    assert result.exit_code == 0
    for rule_id in RULES:
        assert rule_id in result.stdout
    # unknown rule id -> distinct exit code
    result = runner.invoke(cli, ["lint", "--rules", "bogus", CORPUS])
    assert result.exit_code == 2
