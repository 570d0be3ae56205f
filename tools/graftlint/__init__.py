"""graftlint — the repo-native static-analysis suite.

Six PRs of runtime conventions (zero hot-loop syncs, bit-identical
replay, flag/faultpoint/metric registries mirrored in docs, lock
discipline across the threaded pipeline) become machine-checked
invariants: five AST passes over ``paddlebox_tpu/`` and ``tools/``,
stdlib-only, no jax import, runs in tier-1.

    python -m tools.graftlint                  # human-readable, exit 1 on new
    python -m tools.graftlint --json           # findings as JSON
    python -m tools.graftlint --summary s.json # trend-tracking counts
    python -m tools.graftlint --write-baseline # adopt current findings

See STATIC_ANALYSIS.md for the pass catalog, pragma syntax and the
baseline workflow.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

from tools.graftlint.config import Config, default_config, fixture_config
from tools.graftlint.findings import (Baseline, Finding, RunResult,
                                      SEV_ERROR, SEV_WARN)
from tools.graftlint.project import Project

__all__ = [
    "Config", "default_config", "fixture_config", "Baseline",
    "Finding", "RunResult", "Project", "run_passes", "SEV_ERROR",
    "SEV_WARN", "DEFAULT_BASELINE",
]

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                "baseline.json")


def run_passes(cfg: Config,
               only: Optional[Sequence[str]] = None) -> RunResult:
    """Parse the tree once, run the (selected) passes, return findings
    with pragmas already applied — baseline application is the
    caller's move (CLI / tests decide which baseline file)."""
    from tools.graftlint.passes import ALL_PASSES
    proj = Project(cfg.root, cfg.roots, cfg.exclude)
    selected = list(only) if only else list(ALL_PASSES)
    unknown = [p for p in selected if p not in ALL_PASSES]
    if unknown:
        raise ValueError(f"unknown pass(es): {unknown}; "
                         f"available: {sorted(ALL_PASSES)}")
    findings = []
    for pid in selected:
        findings.extend(ALL_PASSES[pid](proj, cfg))
    findings.sort(key=lambda f: (f.path, f.lineno, f.code, f.key))
    return RunResult(findings, cfg.root,
                     files_scanned=len(proj.modules),
                     pass_ids=selected)
