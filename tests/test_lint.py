"""graftlint: fixture goldens per rule + the tier-1 live-tree gate.

Fixture convention (tests/fixtures/graftlint/): every rule has a
`*_pos.py` with `# EXPECT` markers on each line that must be flagged,
and a `*_neg.py` of near-misses that must stay clean. The live-tree
test IS the CI gate: `deeplearning4j_tpu/ + tools/ + bench.py +
chip_smoke.py` must have zero unsuppressed findings, so every future PR (including the
GSPMD-mesh refactor) walks through the analyzer.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")
sys.path.insert(0, REPO)

from deeplearning4j_tpu import analysis
from deeplearning4j_tpu.analysis import core as lint_core
from deeplearning4j_tpu.analysis.rules.telemetry import (
    MetricFamilyRegistrationRule,
)

RULES_BY_NAME = {r.name: r for r in analysis.ALL_RULES}


def expect_lines(path):
    with open(path, encoding="utf-8") as fh:
        return {i for i, line in enumerate(fh.read().splitlines(), 1)
                if "# EXPECT" in line}


def run_rule(rule_name, fixture, rule=None):
    rule = rule or RULES_BY_NAME[rule_name]
    mod = lint_core.load_module(os.path.join(FIXTURES, fixture))
    assert mod is not None, f"fixture {fixture} failed to parse"
    if isinstance(rule, analysis.ProjectRule):
        findings = rule.check_project(lint_core.Project([mod]))
    else:
        findings = rule.check(mod)
    return sorted(f.line for f in findings)


FIXTURE_MATRIX = [
    ("donated-aliasing", "donated_aliasing_pos.py"),
    ("donated-aliasing", "donated_aliasing_pr3_pos.py"),
    ("donated-aliasing", "donated_aliasing_neg.py"),
    ("unlaundered-restore-placement", "restore_placement_pos.py"),
    ("unlaundered-restore-placement", "restore_placement_neg.py"),
    ("host-sync-in-hot-path", "host_sync_pos.py"),
    ("host-sync-in-hot-path", "host_sync_neg.py"),
    ("recompile-hazard", "recompile_hazard_pos.py"),
    ("recompile-hazard", "recompile_hazard_neg.py"),
    ("env-knob-contract", "env_knob_pos.py"),
    ("env-knob-contract", "env_knob_neg.py"),
    ("blocking-under-lock", "blocking_under_lock_pos.py"),
    ("blocking-under-lock", "blocking_under_lock_neg.py"),
    ("telemetry-zero-cost", "telemetry_zero_cost_pos.py"),
    ("telemetry-zero-cost", "telemetry_zero_cost_neg.py"),
    ("bare-except-swallow", os.path.join("parallel", "bare_except_pos.py")),
    ("bare-except-swallow", os.path.join("parallel", "bare_except_neg.py")),
    ("lock-order-inversion", "lock_order_pos.py"),
    ("lock-order-inversion", "lock_order_neg.py"),
    ("transitive-blocking-under-lock", "transitive_blocking_pos.py"),
    ("transitive-blocking-under-lock", "transitive_blocking_neg.py"),
    ("thread-lifecycle", "thread_lifecycle_pos.py"),
    ("thread-lifecycle", "thread_lifecycle_neg.py"),
    ("resource-pairing", "resource_pairing_pos.py"),
    ("resource-pairing", "resource_pairing_neg.py"),
]


def test_pr8_and_pr11_shapes_invisible_to_lexical_rules():
    """THE acceptance pin: the literal PR-8 transitive-blocking and
    PR-11 silent-thread-death regression shapes are caught ONLY by the
    new interprocedural rules — every pre-PR lexical rule reports
    nothing on those fixtures."""
    lexical = [r for r in analysis.ALL_RULES
               if not isinstance(r, analysis.ProjectRule)
               and r.name != "resource-pairing"]
    for fixture in ("transitive_blocking_pos.py",
                    "thread_lifecycle_pos.py"):
        mod = lint_core.load_module(os.path.join(FIXTURES, fixture))
        for rule in lexical:
            hits = list(rule.check(mod))
            assert hits == [], (
                f"{rule.name} unexpectedly fires on {fixture}: {hits}")
    # ...and the new rules DO catch them (the fixture goldens pin the
    # exact lines; this is the cross-check that both halves exist)
    assert run_rule("transitive-blocking-under-lock",
                    "transitive_blocking_pos.py")
    assert run_rule("thread-lifecycle", "thread_lifecycle_pos.py")


@pytest.mark.parametrize("rule_name,fixture", FIXTURE_MATRIX,
                         ids=[f"{r}:{os.path.basename(f)}"
                              for r, f in FIXTURE_MATRIX])
def test_fixture_golden(rule_name, fixture):
    """Each `# EXPECT` line is flagged; nothing else is. Positives prove
    the rule catches the shipped bug shape (incl. the PR-3 donated-
    aliasing resume and the PR-8 launch-under-tick-lock); negatives
    prove the near-misses stay clean."""
    path = os.path.join(FIXTURES, fixture)
    assert run_rule(rule_name, fixture) == sorted(expect_lines(path))


def test_metric_family_rule_against_fixture_catalog():
    rule = MetricFamilyRegistrationRule(
        catalog_path=os.path.join(FIXTURES, "fixture_catalog.md"))
    pos = os.path.join(FIXTURES, "metric_family_pos.py")
    assert run_rule(None, "metric_family_pos.py", rule=rule) == \
        sorted(expect_lines(pos))
    assert run_rule(None, "metric_family_neg.py", rule=rule) == []


def test_metric_family_extraction_is_shared_source_of_truth():
    """telemetry_smoke.py consumes this exact extraction — the static
    catalog check and the live-scrape check must agree on what the tree
    emits."""
    fams = analysis.extract_metric_families(
        [os.path.join(REPO, "deeplearning4j_tpu")])
    for expected in ("train_iterations_total", "etl_fetch_wait_seconds",
                     "serving_requests_total",
                     "serving_fleet_restarts_total",
                     "xla_analysis_unavailable_total"):
        assert expected in fams, f"extraction lost {expected}"
    # every extraction hit carries (path, line) provenance
    path, line = fams["train_iterations_total"][0]
    assert path.endswith(".py") and line > 0


# ------------------------------------------------------------- framework
def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body), encoding="utf-8")
    return str(p)


def test_pragma_suppresses_with_justification(tmp_path):
    p = _write(tmp_path, "m.py", """\
        import os
        # graftlint: disable=env-knob-contract -- fixture: recorded decision
        v = os.environ.get("DL4J_TPU_X")
        """)
    res = analysis.run([p])
    assert res.findings == [] and res.pragma_findings == []
    assert len(res.suppressed) == 1


def test_pragma_without_justification_is_a_finding(tmp_path):
    p = _write(tmp_path, "m.py", """\
        import os
        v = os.environ.get("DL4J_TPU_X")  # graftlint: disable=env-knob-contract
        """)
    res = analysis.run([p])
    assert any(f.rule == analysis.PRAGMA_RULE and "justification"
               in f.message for f in res.pragma_findings)
    # an unjustified pragma does NOT suppress
    assert any(f.rule == "env-knob-contract" for f in res.findings)


def test_stale_and_unknown_pragmas_are_findings(tmp_path):
    p = _write(tmp_path, "m.py", """\
        x = 1  # graftlint: disable=env-knob-contract -- suppresses nothing
        y = 2  # graftlint: disable=not-a-rule -- bogus rule name
        """)
    res = analysis.run([p])
    msgs = [f.message for f in res.pragma_findings]
    assert any("suppresses nothing" in m for m in msgs)
    assert any("unknown rule" in m for m in msgs)


# ------------------------------------------------------------------- CLI
def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
         *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cli_exit_2_and_json_on_findings(tmp_path):
    p = _write(tmp_path, "dirty.py", """\
        import os
        v = os.environ.get("DL4J_TPU_X")
        """)
    r = _cli("--json", p)
    assert r.returncode == 2, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["files"] == 1
    assert [f["rule"] for f in payload["findings"]] == ["env-knob-contract"]


def test_unparseable_file_is_a_finding_not_clean(tmp_path):
    p = _write(tmp_path, "broken.py", "def oops(:\n")
    res = analysis.run([p])
    assert [f.rule for f in res.findings] == ["parse-error"]
    r = _cli(p)
    assert r.returncode == 2 and "parse-error" in r.stdout


def test_cli_refuses_empty_path_glob(tmp_path):
    """A typo'd path must not read as a permanently-green gate."""
    r = _cli(str(tmp_path / "no_such_dir"))
    assert r.returncode == 1
    assert "nothing was linted" in r.stderr


def test_cli_exit_0_on_clean(tmp_path):
    p = _write(tmp_path, "clean.py", "x = 1\n")
    r = _cli(p)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_baseline_burn_down_workflow(tmp_path):
    """A new rule lands with --write-baseline; the gate then passes on
    old debt, fails on NEW findings, and reports stale entries when debt
    is paid down."""
    p = _write(tmp_path, "legacy.py", """\
        import os
        v = os.environ.get("DL4J_TPU_OLD")
        """)
    base = str(tmp_path / "baseline.json")
    assert _cli("--write-baseline", base, p).returncode == 0
    assert _cli("--baseline", base, p).returncode == 0     # old debt passes
    _write(tmp_path, "legacy.py", """\
        import os
        v = os.environ.get("DL4J_TPU_OLD")
        w = os.environ.get("DL4J_TPU_NEW")
        """)
    r = _cli("--json", "--baseline", base, p)
    assert r.returncode == 2                               # new finding gates
    payload = json.loads(r.stdout)
    assert len(payload["findings"]) == 1
    assert "DL4J_TPU_NEW" in payload["findings"][0]["message"]
    _write(tmp_path, "legacy.py", "x = 1\n")
    r = _cli("--json", "--baseline", base, p)
    assert r.returncode == 0                               # debt paid
    assert json.loads(r.stdout)["stale_baseline_entries"]  # ...and visible


def test_cli_list_rules_names_all_thirteen():
    r = _cli("--list-rules")
    assert r.returncode == 0
    for name in RULES_BY_NAME:
        assert name in r.stdout
    assert len(RULES_BY_NAME) == 13
    for new in ("lock-order-inversion", "transitive-blocking-under-lock",
                "thread-lifecycle", "resource-pairing"):
        assert new in RULES_BY_NAME


# --------------------------------------------------------- the tier-1 gate
def test_live_tree_is_clean():
    """THE gate: zero unsuppressed findings over the shipped tree. If
    this fails, either fix the finding or suppress it with a justified
    `# graftlint: disable=<rule> -- <why>` pragma."""
    # the gate runs the FULL registry — including the PR-15
    # interprocedural concurrency rules (a select= or trimmed registry
    # would silently narrow the invariant)
    active = {r.name for r in analysis.ALL_RULES}
    for required in ("lock-order-inversion",
                     "transitive-blocking-under-lock",
                     "thread-lifecycle", "resource-pairing"):
        assert required in active
    res = analysis.run([os.path.join(REPO, "deeplearning4j_tpu"),
                        os.path.join(REPO, "tools"),
                        os.path.join(REPO, "bench.py"),
                        os.path.join(REPO, "chip_smoke.py")])
    rendered = "\n".join(f.render(REPO) for f in res.all_unsuppressed)
    assert not res.all_unsuppressed, f"graftlint findings:\n{rendered}"
    # the suite actually ran over the tree (not an empty glob) and the
    # suppression machinery engaged (a count pin would punish future
    # PRs for legitimately deleting suppressed code)
    assert res.files > 100
    assert len(res.suppressed) >= 1
