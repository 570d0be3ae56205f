"""``python -m tools.graftlint`` — the CLI.

Exit codes: 0 = clean (no failing findings under --fail-on), 1 =
findings failed the gate, 2 = usage / internal error. Pure stdlib, no
jax — milliseconds over the full tree, safe anywhere (CI, pre-commit,
the tier-1 suite via tests/test_graftlint.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from tools.graftlint import (Baseline, DEFAULT_BASELINE, default_config,
                             run_passes)
from tools.graftlint.config import Config


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="repo-native static analysis: hot-path sync, flag "
                    "hygiene, registry drift, lock discipline, replay "
                    "purity (see STATIC_ANALYSIS.md)")
    ap.add_argument("paths", nargs="*",
                    help="roots to analyze, relative to --root "
                         "(default: paddlebox_tpu tools)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: the parent of tools/)")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON on stdout")
    ap.add_argument("--summary", metavar="PATH",
                    help="write the trend-tracking summary JSON "
                         "(findings_total / baselined / new / per-pass) "
                         "— feed it to tools/perf_gate.py")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help=f"suppression baseline (default: "
                         f"{os.path.relpath(DEFAULT_BASELINE)})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="record current findings into the baseline "
                         "(keeps existing reasons) and exit 0")
    ap.add_argument("--fail-on", choices=("new", "any", "none"),
                    default="new",
                    help="what fails the run: 'new' (default — "
                         "non-baselined errors), 'any' (every error, "
                         "baselined or not), 'none' (report only)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass ids to run")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    cfg = default_config(root)
    if args.paths:
        cfg = Config(root=cfg.root, roots=tuple(args.paths))
    only = args.passes.split(",") if args.passes else None

    try:
        result = run_passes(cfg, only)
    except ValueError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 2

    baseline_path = args.baseline or DEFAULT_BASELINE
    baseline = Baseline.load(baseline_path)

    if args.write_baseline:
        entries = {}
        for f in result.active:
            fp = f.fingerprint(result.root)
            entries[fp] = baseline.entries.get(
                fp, "baselined at adoption — REVIEW AND REPLACE with a "
                    "real reason (STATIC_ANALYSIS.md)")
        Baseline(entries).save(baseline_path)
        print(f"graftlint: wrote {len(entries)} baseline entries to "
              f"{baseline_path}")
        return 0

    result.apply_baseline(baseline)
    failures = result.failures(args.fail_on)
    summary = result.summary()

    if args.summary:
        with open(args.summary, "w", encoding="utf-8") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")

    if args.json:
        print(json.dumps({
            "summary": summary,
            "findings": [f.to_dict(result.root) for f in result.findings],
        }, indent=2))
    else:
        for f in sorted(result.new, key=lambda f: (f.path, f.lineno)):
            rel = os.path.relpath(f.path, result.root)
            print(f"{rel}:{f.lineno}: [{f.pass_id}/{f.code}] "
                  f"{f.severity}: {f.message}")
        print(f"graftlint: {summary['findings_total']} findings "
              f"({summary['new']} new, {summary['baselined']} baselined, "
              f"{summary['allowed']} pragma-allowed) over "
              f"{summary['files_scanned']} files")
    if failures:
        print(f"graftlint: FAILED — {len(failures)} finding(s) not "
              f"covered by {os.path.relpath(baseline_path)} "
              "(fix, pragma with a reason, or --write-baseline)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
