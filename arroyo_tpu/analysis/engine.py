"""Lint engine: file collection, the per-file/project rule pipeline, and
baseline application. `run_lint` is the single entry point used by the CLI
(tools/lint.py) and the tier-1 test (tests/test_lint.py)."""

from __future__ import annotations

import dataclasses
import subprocess
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

from .baseline import Baseline
from .core import (
    FileContext,
    Finding,
    Project,
    Rule,
    all_rules,
    sorted_findings,
)

# what a default run covers, relative to the lint root
DEFAULT_ROOTS = ("arroyo_tpu", "tools", "bench.py", "chip_smoke.py")
EXCLUDED_PARTS = {"__pycache__", "lint_fixtures", ".git", "node_modules"}


def collect_files(root: Path, roots: Sequence[str] = DEFAULT_ROOTS) -> List[Path]:
    root = Path(root)
    out: List[Path] = []
    for entry in roots:
        p = root / entry
        if p.is_file() and p.suffix == ".py":
            out.append(p)
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                # exclusions apply below the lint root only (a fixture tree
                # lives UNDER an excluded dir but lints fine as a root)
                if not EXCLUDED_PARTS.intersection(f.relative_to(root).parts):
                    out.append(f)
    return out


def parse_project(root: Path, files: Iterable[Path]) -> Project:
    root = Path(root)
    ctxs: Dict[str, FileContext] = {}
    errors: List[Finding] = []
    for f in files:
        rel = f.relative_to(root).as_posix()
        try:
            source = f.read_text()
            ctxs[rel] = FileContext(root, rel, source)
        except (SyntaxError, UnicodeDecodeError) as e:
            errors.append(
                Finding(
                    rule="LINT000",
                    path=rel,
                    line=getattr(e, "lineno", 1) or 1,
                    col=0,
                    message=f"file does not parse: {e.msg if hasattr(e, 'msg') else e}",
                )
            )
    return Project(root, ctxs, errors)


def changed_paths(root: Path) -> Optional[set]:
    """Repo-relative paths touched vs HEAD (staged, unstaged, untracked).
    None when git is unavailable — callers fall back to a full run."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            cwd=root, capture_output=True, text=True, timeout=30,
        )
        status = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=root, capture_output=True, text=True, timeout=30,
        )
        if diff.returncode != 0 or status.returncode != 0:
            return None
    except (OSError, subprocess.SubprocessError):
        return None
    out = {l.strip() for l in diff.stdout.splitlines() if l.strip()}
    for line in status.stdout.splitlines():
        if len(line) > 3:
            out.add(line[3:].split(" -> ")[-1].strip())
    return out


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]       # new findings (not grandfathered)
    grandfathered: List[Finding]  # matched a baseline entry
    stale_baseline: List[dict]    # baseline entries matching nothing
    errors: List[Finding]         # unparseable files
    n_files: int
    n_rules: int

    @property
    def clean(self) -> bool:
        return not self.findings and not self.errors

    def strict_ok(self, baseline: Baseline) -> bool:
        """--strict: no new findings, no parse errors, every grandfathered
        entry justified, and no stale entries rotting in the baseline."""
        return (
            self.clean
            and not self.stale_baseline
            and not baseline.unjustified()
        )


def run_lint(
    root,
    rules: Optional[Sequence[Rule]] = None,
    roots: Sequence[str] = DEFAULT_ROOTS,
    baseline: Optional[Baseline] = None,
    changed_only: bool = False,
) -> LintResult:
    root = Path(root)
    rules = list(rules) if rules is not None else all_rules()
    project = parse_project(root, collect_files(root, roots))
    findings: List[Finding] = []
    for rule in rules:
        if rule.scope == "project":
            found = rule.check_project(project)
            for f in found:
                ctx = project.get(f.path)
                if ctx is None or not ctx.suppressed(f.rule, f.line):
                    findings.append(f)
        else:
            for ctx in project:
                for f in rule.check_file(ctx):
                    if not ctx.suppressed(f.rule, f.line):
                        findings.append(f)
    errors = list(project.errors)
    if changed_only:
        changed = changed_paths(root)
        if changed is not None:
            findings = [f for f in findings if f.path in changed]
            errors = [f for f in errors if f.path in changed]
    baseline = baseline or Baseline()
    new, old, stale = baseline.split(sorted_findings(findings))
    return LintResult(
        findings=new,
        grandfathered=old,
        stale_baseline=stale,
        errors=sorted_findings(errors),
        n_files=len(project.files),
        n_rules=len(rules),
    )
