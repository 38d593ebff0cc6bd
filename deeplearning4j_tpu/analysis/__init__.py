"""graftlint — project-native static analysis for the bug classes this
repo actually shipped.

Six PRs of review rounds kept finding the same defect families: donated
numpy-aliased buffers (the PR-3 serde-resume segfault), hand-rolled env
kill-switch truthiness (re-fixed in PRs 5/7/8), blocking calls held
under supervisor/router locks (fixed twice in PR 8), host syncs and
recompile hazards inside the compiled step (the PERF.md tax). Every one
is visible in the AST — this package turns that review knowledge into a
machine-enforced invariant.

Entry points:

- CLI: ``python tools/graftlint.py deeplearning4j_tpu tools bench.py chip_smoke.py``
  (human, ``--json``, ``--baseline`` burn-down; exit 2 on unsuppressed
  findings) — wired into tier-1 via tests/test_lint.py.
- Library: `run(paths)` -> RunResult; `ALL_RULES`;
  `extract_metric_families` (shared with tools/telemetry_smoke.py).
- Suppression: ``# graftlint: disable=<rule> -- <justification>`` —
  the justification is mandatory and checked.

Rule catalog + how to add a rule: docs/STATIC_ANALYSIS.md.
"""
from deeplearning4j_tpu.analysis.core import (
    Finding, ModuleInfo, PRAGMA_RULE, Project, ProjectRule, Rule,
    RunResult, apply_baseline, iter_py_files, load_module, run as _run,
    write_baseline,
)
from deeplearning4j_tpu.analysis.rules import ALL_RULES
from deeplearning4j_tpu.analysis.rules.telemetry import (
    extract_metric_families, metric_families_in,
)


def run(paths, rules=None, select=None, module_findings=None) -> RunResult:
    """Run the full registered suite (or `rules`) over `paths`.
    `module_findings` feeds the CLI's multiprocess per-module pass
    (core.run docstring)."""
    return _run(paths, ALL_RULES if rules is None else rules,
                select=select, module_findings=module_findings)


__all__ = [
    "ALL_RULES", "Finding", "ModuleInfo", "PRAGMA_RULE", "Project",
    "ProjectRule", "Rule", "RunResult", "apply_baseline",
    "extract_metric_families", "iter_py_files", "load_module",
    "metric_families_in", "run", "write_baseline",
]
