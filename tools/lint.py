#!/usr/bin/env python3
"""arroyolint CLI — project-specific static analysis for arroyo_tpu.

Usage:
    python tools/lint.py                  # lint arroyo_tpu/, tools/, bench.py,
                                          #   chip_smoke.py
    python tools/lint.py --strict         # CI mode: findings OR a stale /
                                          #   unjustified baseline fail (exit 1)
    python tools/lint.py --changed-only   # only files touched vs git HEAD
    python tools/lint.py --json           # machine-readable findings
    python tools/lint.py --list-rules     # registered rules + descriptions
    python tools/lint.py --config-table   # resolved config key/default table
    python tools/lint.py --call-graph     # RACE rules' async call graph as
                                          #   JSON (roots, locksets, accesses)
    python tools/lint.py --update-baseline  # grandfather current findings
                                            # (each entry then needs a
                                            #  human-written justification)

Suppressions: `# arroyolint: disable=RULE` on the offending line,
`# arroyolint: disable-file=RULE` within the first 10 lines of a file.
Exit codes: 0 clean, 1 findings (or strict-mode baseline problems),
2 internal error.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from arroyo_tpu.analysis import Baseline, all_rules, run_lint  # noqa: E402
from arroyo_tpu.analysis.baseline import DEFAULT_BASELINE  # noqa: E402
from arroyo_tpu.analysis.engine import DEFAULT_ROOTS  # noqa: E402
from arroyo_tpu.analysis.reporters import (  # noqa: E402
    report_json,
    report_sarif,
    report_text,
)
from arroyo_tpu.analysis.rules_jax_config import config_key_table  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="lint.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("paths", nargs="*",
                    help=f"roots to lint (default: {', '.join(DEFAULT_ROOTS)})")
    ap.add_argument("--root", default=str(REPO_ROOT),
                    help="project root the paths are relative to")
    ap.add_argument("--strict", action="store_true",
                    help="fail on findings, stale baseline entries, and "
                         "unjustified baseline entries")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="JSON report on stdout")
    ap.add_argument("--sarif", metavar="FILE", default=None,
                    help="also write a SARIF 2.1.0 report (use '-' for "
                         "stdout); CI uploads it so findings annotate PRs")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="include rule descriptions under each finding")
    ap.add_argument("--changed-only", action="store_true",
                    help="report findings only for files changed vs git HEAD")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: <root>/{DEFAULT_BASELINE})")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write all current findings into the baseline")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--config-table", action="store_true",
                    help="print the declared config key/default table")
    ap.add_argument("--call-graph", action="store_true",
                    help="dump the async call graph the RACE rules analyze "
                         "as JSON: task roots -> reachable functions -> "
                         "shared-field accesses with locksets")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            print(f"{rule.id}  {rule.name}")
            print(f"      {rule.description}")
        return 0

    root = Path(args.root)
    roots = tuple(args.paths) or DEFAULT_ROOTS
    baseline_path = Path(args.baseline) if args.baseline else root / DEFAULT_BASELINE

    if args.config_table:
        from arroyo_tpu.analysis.engine import collect_files, parse_project

        project = parse_project(root, collect_files(root, roots))
        table = config_key_table(project)
        width = max((len(k) for k, _ in table), default=0)
        for key, default in table:
            print(f"{key:<{width}}  {default}")
        print(f"{len(table)} declared config keys")
        return 0

    if args.call_graph:
        import json as _json

        from arroyo_tpu.analysis.engine import collect_files, parse_project
        from arroyo_tpu.analysis.races import callgraph

        project = parse_project(root, collect_files(root, roots))
        _json.dump(callgraph.build(project).to_debug_json(), sys.stdout,
                   indent=1, sort_keys=True)
        print()
        return 0

    rules = None
    if args.rules:
        wanted = {r.strip() for r in args.rules.split(",") if r.strip()}
        rules = [r for r in all_rules() if r.id in wanted or r.name in wanted]
        missing = wanted - {r.id for r in rules} - {r.name for r in rules}
        if missing:
            print(f"unknown rule(s): {', '.join(sorted(missing))}", file=sys.stderr)
            return 2

    baseline = Baseline.load(baseline_path)
    try:
        result = run_lint(
            root,
            rules=rules,
            roots=roots,
            baseline=baseline,
            changed_only=args.changed_only,
        )
    except Exception as e:  # noqa: BLE001 - CLI boundary
        print(f"arroyolint internal error: {e!r}", file=sys.stderr)
        return 2

    if args.update_baseline:
        merged = Baseline.from_findings(result.findings)
        # keep still-matching grandfathered entries (and their justifications)
        matched = {(f.rule, f.path, f.message) for f in result.grandfathered}
        merged.entries.extend(
            e for e in baseline.entries
            if (e["rule"], e["path"], e["message"]) in matched
        )
        merged.save(baseline_path)
        print(f"baseline updated: {len(merged.entries)} entries -> "
              f"{baseline_path}")
        print("every new entry needs a human-written `justification` before "
              "--strict accepts it")
        return 0

    if args.sarif:
        if args.sarif == "-":
            report_sarif(result, sys.stdout)
        else:
            with open(args.sarif, "w") as f:
                report_sarif(result, f)
            print(f"sarif report written to {args.sarif}", file=sys.stderr)

    if args.as_json:
        report_json(result, sys.stdout)
    elif args.sarif != "-":  # '-' owns stdout: SARIF must stay parseable
        report_text(result, sys.stdout, verbose=args.verbose)

    if args.strict:
        if baseline.unjustified():
            print(f"--strict: {len(baseline.unjustified())} baseline "
                  "entry(ies) lack a justification", file=sys.stderr)
        return 0 if result.strict_ok(baseline) else 1
    return 0 if result.clean else 1


if __name__ == "__main__":
    sys.exit(main())
