"""graftlint driver + reporters.

`run_lint` parses the product tree (never importing it), runs the rule
registry, applies suppressions, and returns sorted findings. The text
reporter mirrors the compiler-style `file:line:col: CODE message` shape;
the json reporter feeds CI and the tier-1 enforcement test.
"""

from __future__ import annotations

import json
import os
import subprocess
from typing import Iterable, List, Optional, Sequence

from open_simulator_tpu.analysis.findings import LintError, LintFinding
from open_simulator_tpu.analysis.rules import RULES, LintContext, Rule
from open_simulator_tpu.analysis.walker import Module, iter_py_files

# What `simon-tpu lint` checks by default: the product tree. Tests and
# examples are exercised by pytest itself; fixtures under tests/fixtures/
# are deliberately-broken lint corpora.
DEFAULT_PATHS = ("open_simulator_tpu", "tools", "__graft_entry__.py")


def repo_root() -> str:
    """The repository root: two levels above this package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _parse_one(args) -> Module:
    """Top-level so ProcessPoolExecutor can pickle it."""
    path, root = args
    return Module.parse(path, root)


def load_modules(root: Optional[str] = None,
                 paths: Optional[Sequence[str]] = None,
                 jobs: int = 0) -> List[Module]:
    """Parse the lint set. `jobs` > 1 fans the (embarrassingly parallel)
    per-file parse across a process pool; rule evaluation stays in the
    parent because the interprocedural rules need the whole module set.
    Falls back to serial parsing when the pool can't be used."""
    root = root or repo_root()
    subpaths = tuple(paths) if paths else DEFAULT_PATHS
    files = list(iter_py_files(root, subpaths))
    if jobs > 1 and len(files) > 1:
        try:
            from concurrent.futures import ProcessPoolExecutor
            with ProcessPoolExecutor(max_workers=jobs) as pool:
                return list(pool.map(_parse_one,
                                     [(fp, root) for fp in files],
                                     chunksize=8))
        except Exception:  # pool unavailable (sandbox, pickling): serial
            pass
    return [Module.parse(fp, root) for fp in files]


def changed_files(root: Optional[str] = None,
                  ref: str = "HEAD") -> Optional[List[str]]:
    """Repo-relative .py files changed vs `ref` (diff + untracked),
    restricted to the default lint scope. Returns None when git is
    unavailable or errors — callers fall back to the full tree."""
    root = root or repo_root()
    names: List[str] = []
    try:
        for cmd in (["git", "diff", "--name-only", ref, "--"],
                    ["git", "ls-files", "--others", "--exclude-standard"]):
            proc = subprocess.run(cmd, cwd=root, capture_output=True,
                                  text=True, timeout=30)
            if proc.returncode != 0:
                return None
            names.extend(proc.stdout.splitlines())
    except (OSError, subprocess.SubprocessError):
        return None
    scope_dirs = tuple(p + "/" for p in DEFAULT_PATHS)
    out = []
    for n in sorted(set(names)):
        if not n.endswith(".py"):
            continue
        if not (n in DEFAULT_PATHS or n.startswith(scope_dirs)):
            continue
        if os.path.isfile(os.path.join(root, n)):
            out.append(n)
    return out


def apply_suppressions(modules: Iterable[Module],
                       findings: Iterable[LintFinding]) -> List[LintFinding]:
    by_rel = {m.rel: m for m in modules}
    out = []
    for f in findings:
        m = by_rel.get(f.path)
        if m is not None:
            if m.file_suppressed(f.code):
                continue
            if f.line in m.suppressed_lines(f.code):
                continue
        out.append(f)
    return out


def run_lint(root: Optional[str] = None,
             paths: Optional[Sequence[str]] = None,
             rules: Optional[Sequence[Rule]] = None,
             codes: Optional[Sequence[str]] = None,
             jobs: int = 0,
             report_paths: Optional[Sequence[str]] = None) -> List[LintFinding]:
    """Lint `paths` (repo-relative files/dirs) under `root`; returns the
    surviving findings sorted by (path, line, code).

    `report_paths` narrows the REPORT without narrowing the ANALYSIS:
    the whole tree in `paths` is parsed and resolved (so interprocedural
    facts — fault-domain callees, lock tokens, the metric registry —
    stay accurate), but only findings in the listed files survive. This
    is how `--changed` avoids partial-scope false positives."""
    root = root or repo_root()
    full_tree = paths is None or tuple(paths) == DEFAULT_PATHS
    modules = load_modules(root, paths, jobs=jobs)
    ctx = LintContext(modules=modules, root=root, full_tree=full_tree)
    active = list(rules) if rules is not None else list(RULES)
    if codes:
        wanted = set(codes)
        active = [r for r in active if r.code in wanted]
    findings: List[LintFinding] = []
    for rule in active:
        findings.extend(rule.check(ctx))
    out = sorted(apply_suppressions(modules, findings))
    if report_paths is not None:
        wanted_paths = set(report_paths)
        out = [f for f in out if f.path in wanted_paths]
    return out


def assert_clean(root: Optional[str] = None,
                 paths: Optional[Sequence[str]] = None,
                 rules: Optional[Sequence[Rule]] = None,
                 codes: Optional[Sequence[str]] = None,
                 jobs: int = 0,
                 report_paths: Optional[Sequence[str]] = None) -> None:
    """run_lint with exception semantics: raises LintError (code E_LINT,
    structured findings payload) unless the tree is clean. The CLI exits
    through this so lint failures ride the same structured-error path as
    every other SimulationError surface."""
    findings = run_lint(root=root, paths=paths, rules=rules, codes=codes,
                        jobs=jobs, report_paths=report_paths)
    if findings:
        raise LintError(findings)


def format_text(findings: Sequence[LintFinding]) -> str:
    if not findings:
        return "graftlint: clean (0 findings)"
    lines = [f.format() for f in findings]
    lines.append(f"graftlint: {len(findings)} finding(s)")
    return "\n".join(lines)


def format_json(findings: Sequence[LintFinding]) -> str:
    return json.dumps({
        "findings": [f.to_dict() for f in findings],
        "count": len(findings),
        "clean": not findings,
    }, indent=2)


def format_sarif(findings: Sequence[LintFinding]) -> str:
    """SARIF 2.1.0 for code-scanning UIs (GitHub, VS Code). One run,
    one driver (`graftlint`), the full rule catalog, one result per
    finding with a physical location + region."""
    results = []
    for f in findings:
        region = {"startLine": f.line, "startColumn": f.col}
        if f.end_line:
            region["endLine"] = f.end_line
        if f.end_col:
            region["endColumn"] = f.end_col
        message = f.message if not f.hint else f"{f.message} (hint: {f.hint})"
        results.append({
            "ruleId": f.code,
            "level": "error",
            "message": {"text": f"[{f.symbol}] {message}"},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": region,
                },
            }],
        })
    return json.dumps({
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "informationUri": "ARCHITECTURE.md",
                "rules": [{
                    "id": r.code,
                    "name": r.name,
                    "shortDescription": {"text": r.summary},
                } for r in RULES],
            }},
            "results": results,
        }],
    }, indent=2)


def format_rules() -> str:
    lines = ["graftlint rules:"]
    for r in RULES:
        lines.append(f"  {r.code}  {r.name:<24} {r.summary}")
    return "\n".join(lines)
