"""``detectmate-lint``: run the dmlint analyzer suite and gate on the result.

Usage::

    detectmate-lint                       # whole repo, baseline applied
    detectmate-lint path/to/file.py ...   # per-file rules on those files only
    detectmate-lint --list-rules          # rule catalog
    detectmate-lint --format json         # machine-readable findings
    detectmate-lint --format sarif        # SARIF 2.1.0 (CI code-scanning)
    detectmate-lint --changed origin/main # findings in changed files only
    detectmate-lint --write-baseline      # snapshot current findings
    detectmate-lint --no-baseline         # raw findings, suppressions off

Exit codes: 0 clean (every finding suppressed with a justification),
1 unsuppressed findings, 2 usage/internal error.

``--changed <ref>`` runs the FULL analysis (whole-program rules like the
DM-A affinity graph and the cross-artifact contracts need the whole tree)
and then reports only findings anchored in files ``git diff --name-only
<ref>`` lists — the fail-fast PR mode. CI runs the unfiltered gate on main;
a finding a diff filter hides never survives past merge.

Also runnable as ``python -m detectmateservice_tpu.analysis.cli`` (what the
CI fail-fast step, the pre-commit local hook, and the
``scripts/static_check.py`` shim do — the entry-point script requires an
installed package, the sandbox has none).

The gate is stdlib-only by construction; analyzers that read YAML artifacts
degrade gracefully when PyYAML is missing (it is a declared runtime dep, so
only a hermetic toolchain image would lack it).
"""
from __future__ import annotations

import argparse
import ast
import json
import py_compile
import subprocess
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

from . import (
    affinity,
    basic,
    contracts,
    durability,
    hotloop,
    locks,
    markers,
    robustness,
)
from .findings import (
    BASELINE_NAME,
    Finding,
    load_baseline,
    scan_pragmas,
    write_baseline,
)

CHECK_DIRS = ("detectmateservice_tpu", "scripts", "tests")
TOP_FILES = ("bench.py", "chip_smoke.py", "__graft_entry__.py")
SKIP_PARTS = {"__pycache__", "_native"}
SKIP_FILES = {"schemas_pb2.py"}  # generated by protoc

RULES = {
    "DM-B001": "mutable default argument",
    "DM-B002": "bare except:",
    "DM-B003": "== None / != None comparison",
    "DM-B004": "tab in indentation",
    "DM-B005": "syntax error",
    "DM-B006": "committed YAML artifact does not parse",
    "DM-L001": "unguarded access to a lock-guarded shared attribute",
    "DM-L002": "blocking call while holding a lock",
    "DM-L003": "lock-acquisition-order cycle (potential deadlock)",
    "DM-H001": "per-iteration metric construction in a hot loop",
    "DM-H002": "INFO-level logging in a hot loop",
    "DM-H003": "re.compile in a hot loop",
    "DM-H004": "blocking call on a hot loop's steady-state path",
    "DM-C001": "alert/panel references an undeclared series",
    "DM-C002": "declared series has no Grafana panel",
    "DM-C003": "declared series undocumented in docs/prometheus.md",
    "DM-C004": "health/SLO series has no alert rule",
    "DM-C005": "settings field undocumented in docs/configuration.md",
    "DM-C006": "example settings YAML uses a rejected key",
    "DM-C007": "admin route undocumented in docs/usage.md",
    "DM-C008": "docs/usage.md documents an undeclared admin route",
    "DM-C009": "soak scenario undocumented in docs/benchmarks.md",
    "DM-A001": "call into a method owned by a different thread",
    "DM-A002": "unguarded attribute shared across affinity domains",
    "DM-A003": "socket/spool write path reachable off the engine thread",
    "DM-D001": "bare write to a non-temp final path (not crash-atomic)",
    "DM-D002": "os.rename/os.replace with no fsync (atomic but not durable)",
    "DM-D003": "buffered append handle on a WAL segment path",
    "DM-R001": "broad except swallows the exception silently",
    "DM-E001": "emitted event kind not declared in EVENT_KINDS",
    "DM-E002": "registered event kind emitted nowhere",
    "DM-E003": "registered event kind undocumented in docs/prometheus.md",
    "DM-E004": "soak-gated event kind never emitted",
    "DM-T001": "pytest marker not registered in pyproject.toml",
    "DM-X000": "baseline file unreadable/malformed",
    "DM-X001": "suppression without a justification",
    "DM-X002": "stale baseline entry (no matching finding)",
}


def default_repo_root() -> Path:
    # analysis/cli.py → analysis → detectmateservice_tpu → repo root
    return Path(__file__).resolve().parent.parent.parent


def iter_py_files(repo: Path) -> Iterable[Path]:
    for name in TOP_FILES:
        path = repo / name
        if path.exists():
            yield path
    for base in CHECK_DIRS:
        root = repo / base
        if not root.is_dir():
            continue
        for path in sorted(root.rglob("*.py")):
            if SKIP_PARTS & set(path.parts) or path.name in SKIP_FILES:
                continue
            yield path


def check_file(repo: Path, path: Path) -> List[Finding]:
    """Run every per-file analyzer over one source file."""
    rel = path.resolve().relative_to(repo).as_posix()
    findings: List[Finding] = []
    try:
        py_compile.compile(str(path), doraise=True)
    except py_compile.PyCompileError as exc:
        findings.append(Finding(
            "DM-B005", rel, getattr(exc.exc_value, "lineno", 1) or 1,
            f"does not byte-compile: {exc.exc_value}", key="compile"))
        return findings
    source = path.read_text(encoding="utf-8")
    pragmas = scan_pragmas(source)
    for line in pragmas.bare_ignores:
        findings.append(Finding(
            "DM-X001", rel, line,
            "dmlint ignore pragma without a justification",
            hint="append one line of reasoning after the bracket",
            key=f"pragma:L{line}"))
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:  # pragma: no cover - py_compile catches first
        findings.append(Finding(
            "DM-B005", rel, exc.lineno or 1, f"syntax error: {exc.msg}",
            key="syntax"))
        return findings
    findings.extend(basic.check_source(rel, source, tree))
    findings.extend(locks.check_module(rel, source, tree, pragmas))
    findings.extend(hotloop.check_module(rel, source, tree, pragmas))
    findings.extend(durability.check_module(rel, source, tree, pragmas))
    findings.extend(robustness.check_module(rel, source, tree, pragmas))
    return findings


def run(repo: Path, paths: Optional[Sequence[Path]] = None,
        use_baseline: bool = True,
        baseline_path: Optional[Path] = None) -> Dict[str, object]:
    """Analyze and partition findings → {'active', 'suppressed', 'files'}."""
    repo = repo.resolve()
    files = list(paths) if paths else list(iter_py_files(repo))
    findings: List[Finding] = []
    for path in files:
        findings.extend(check_file(repo, path))
    if paths is None:
        # repo-wide checks only make sense for whole-repo runs (the DM-A
        # affinity graph is a whole-program property: ownership pragmas and
        # receiver types cross file boundaries)
        findings.extend(basic.check_yaml_artifacts(repo))
        findings.extend(contracts.check_all(repo))
        findings.extend(markers.check_markers(repo))
        findings.extend(affinity.check_repo(repo, files))

    # de-duplicate by fingerprint (nested hot loops etc.)
    unique: Dict[str, Finding] = {}
    for finding in findings:
        unique.setdefault(finding.fingerprint, finding)
    findings = list(unique.values())

    baseline_path = baseline_path or repo / BASELINE_NAME
    baseline: Dict[str, str] = {}
    meta: List[Finding] = []
    if use_baseline:
        baseline, meta = load_baseline(baseline_path)
    active: List[Finding] = list(meta)
    suppressed: List[Finding] = []
    matched: set = set()
    for finding in findings:
        if finding.fingerprint in baseline:
            matched.add(finding.fingerprint)
            suppressed.append(finding)
        else:
            active.append(finding)
    if use_baseline and paths is None:
        for fingerprint in sorted(set(baseline) - matched):
            active.append(Finding(
                "DM-X002", BASELINE_NAME, 1,
                f"stale suppression {fingerprint!r} matches no finding",
                hint="delete the entry (the debt was paid — keep it paid)",
                key=fingerprint))
    active.sort(key=lambda f: (f.file, f.line, f.rule))
    return {"active": active, "suppressed": suppressed,
            "all": findings, "files": len(files), "baseline": baseline}


def changed_files(repo: Path, ref: str) -> Optional[Set[str]]:
    """Repo-relative posix paths ``git diff --name-only <ref>`` reports
    (committed, staged, and working-tree changes vs the ref), or None when
    git cannot answer (not a checkout, unknown ref) — the caller falls back
    to the unfiltered run rather than silently passing."""
    try:
        proc = subprocess.run(
            ["git", "-C", str(repo), "diff", "--name-only", ref, "--"],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        return None
    return {line.strip() for line in proc.stdout.splitlines() if line.strip()}


def to_sarif(findings: Sequence[Finding],
             suppressed: Sequence[Finding] = ()) -> Dict[str, object]:
    """Render findings as a SARIF 2.1.0 log (the schema GitHub code
    scanning ingests via ``upload-sarif``). Fingerprints ride along as
    ``partialFingerprints`` so annotations stay stable across pushes, the
    same identity contract the baseline uses."""
    def result(finding: Finding, suppressed_: bool) -> Dict[str, object]:
        message = finding.message
        if finding.hint:
            message += f" (fix: {finding.hint})"
        doc: Dict[str, object] = {
            "ruleId": finding.rule,
            "level": "error",
            "message": {"text": message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": finding.file,
                                         "uriBaseId": "SRCROOT"},
                    "region": {"startLine": max(1, finding.line)},
                },
            }],
            "partialFingerprints": {
                "dmlintFingerprint/v1": finding.fingerprint},
        }
        if suppressed_:
            doc["suppressions"] = [{"kind": "external",
                                    "justification": "dmlint-baseline.json"}]
        return doc

    rules = [{"id": rule,
              "shortDescription": {"text": summary},
              "helpUri": "docs/static_analysis.md"}
             for rule, summary in sorted(RULES.items())]
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                    "master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "detectmate-lint",
                "informationUri": "docs/static_analysis.md",
                "rules": rules,
            }},
            "results": ([result(f, False) for f in findings]
                        + [result(f, True) for f in suppressed]),
        }],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="detectmate-lint",
        description="codebase-aware static analysis (lock discipline, "
                    "hot-loop purity, cross-artifact contracts)")
    parser.add_argument("paths", nargs="*", help="files to check "
                        "(default: the whole repo, incl. repo-wide contracts)")
    parser.add_argument("--root", default=None,
                        help="repo root (default: derived from the package)")
    parser.add_argument("--baseline", default=None,
                        help=f"baseline file (default: <root>/{BASELINE_NAME})")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report raw findings, ignoring the baseline")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write current findings to the baseline "
                             "(existing justifications kept, new entries TODO)")
    parser.add_argument("--format", choices=("text", "json", "sarif"),
                        default="text")
    parser.add_argument("--changed", metavar="REF", default=None,
                        help="report only findings in files git diff REF "
                             "lists (full analysis still runs; the "
                             "fail-fast PR / pre-commit mode)")
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule, summary in sorted(RULES.items()):
            print(f"{rule}  {summary}")
        return 0

    repo = Path(args.root).resolve() if args.root else default_repo_root()
    baseline_path = (Path(args.baseline).resolve() if args.baseline
                     else repo / BASELINE_NAME)
    paths: Optional[List[Path]] = None
    if args.paths:
        paths = []
        for raw in args.paths:
            path = Path(raw).resolve()
            if path.is_dir():
                paths.extend(p for p in sorted(path.rglob("*.py"))
                             if not (SKIP_PARTS & set(p.parts))
                             and p.name not in SKIP_FILES)
            elif path.suffix == ".py":
                paths.append(path)
    try:
        result = run(repo, paths, use_baseline=not args.no_baseline,
                     baseline_path=baseline_path)
    except Exception as exc:  # noqa: BLE001 — analyzer crash = usage error, not "clean"
        print(f"detectmate-lint: internal error: {exc!r}", file=sys.stderr)
        return 2

    active: List[Finding] = result["active"]  # type: ignore[assignment]
    if args.changed is not None:
        changed = changed_files(repo, args.changed)
        if changed is None:
            print(f"detectmate-lint: cannot diff against {args.changed!r}; "
                  "reporting unfiltered findings", file=sys.stderr)
        else:
            # baseline-hygiene findings (DM-X*) stay only when the baseline
            # itself changed — they gate baseline edits, not code edits
            active = [f for f in active
                      if f.file in changed
                      or (f.rule.startswith("DM-X")
                          and BASELINE_NAME in changed)]
    if args.write_baseline:
        keep, _ = load_baseline(baseline_path)
        write_baseline(baseline_path,
                       [f for f in active if not f.rule.startswith("DM-X")],
                       keep=keep)
        print(f"baseline written: {baseline_path}", file=sys.stderr)
        return 0

    if args.format == "sarif":
        print(json.dumps(to_sarif(active, result["suppressed"]),  # type: ignore[arg-type]
                         indent=2))
    elif args.format == "json":
        print(json.dumps({
            "findings": [f.as_dict() for f in active],
            "suppressed": len(result["suppressed"]),  # type: ignore[arg-type]
            "files": result["files"],
        }, indent=2))
    else:
        for finding in active:
            print(finding.render())
    print(
        f"detectmate-lint: {result['files']} files, "
        f"{len(active)} finding(s), "
        f"{len(result['suppressed'])} suppressed",  # type: ignore[arg-type]
        file=sys.stderr)
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
