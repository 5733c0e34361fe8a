"""Drive the rules over files/trees and produce findings + reports.

v2 two-phase sweep: parse *every* file first, build one
:class:`~.callgraph.Project` (module set + call graph) over the lot,
then run each rule per module through ``Rule.project_check`` — so
flow-aware rules see cross-module structure while single-module rules
(the default ``project_check`` delegates to ``check``) are untouched.
"""
import gc
import os
from typing import Dict, Iterable, Iterator, List, Optional, Sequence

from .callgraph import Project
from .core import Finding, ModuleCache, ParsedModule, Rule
from .rules import all_rules

_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", "node_modules"}


def iter_python_files(paths: Sequence[str]) -> Iterator[str]:
    """Expand files/directories into .py files, deterministic order."""
    for path in paths:
        if os.path.isfile(path):
            if path.endswith(".py"):
                yield path
        elif os.path.isdir(path):
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def _rel(path: str, root: Optional[str]) -> str:
    if root is not None:
        try:
            rel = os.path.relpath(path, root)
            if not rel.startswith(".."):
                return rel.replace(os.sep, "/")
        except ValueError:
            pass  # different drive on windows
    return path.replace(os.sep, "/")


def _run_project(modules: Sequence[ParsedModule],
                 rules: Sequence[Rule]) -> List[Finding]:
    project = Project(modules={m.path: m for m in modules})
    findings: List[Finding] = []
    for module in modules:
        for rule in rules:
            findings.extend(rule.project_check(module, project))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def run_paths(paths: Sequence[str],
              rules: Optional[Sequence[Rule]] = None,
              root: Optional[str] = None,
              cache: Optional[ModuleCache] = None) -> List[Finding]:
    """Analyze all .py files under `paths`; findings carry paths relative
    to `root` (so baselines are checkout-location independent). Inline
    noqa suppressions are already applied; baseline filtering is the
    caller's job (the CLI/gate owns the baseline)."""
    rules = list(rules) if rules is not None else all_rules()
    cache = cache or ModuleCache()
    modules: List[ParsedModule] = []
    seen = set()
    # a sweep builds some million tree nodes that all live to its end:
    # the cycle collector's passes over them find nothing, and were a
    # third of the sweep's CPU (more in a process with a large heap)
    collecting = gc.isenabled()
    gc.disable()
    try:
        for filename in iter_python_files(paths):
            module = cache.parse_file(filename, _rel(filename, root))
            if module is None or module.path in seen:
                continue
            seen.add(module.path)
            modules.append(module)
        return _run_project(modules, rules)
    finally:
        if collecting:
            gc.enable()


def run_source(source: str, path: str = "<memory>",
               rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    """Analyze one in-memory snippet (the fixture-test entry point):
    a single-module project, so flow-aware rules run too."""
    rules = list(rules) if rules is not None else all_rules()
    cache = ModuleCache()
    module = cache.parse_source(source, path)
    return _run_project([module], rules)


def report_json(findings: Sequence[Finding],
                baselined: Sequence[Finding] = (),
                stale: Sequence[dict] = (),
                errors: Optional[Dict[str, str]] = None,
                sweep_seconds: Optional[float] = None) -> dict:
    """Machine-readable report (`tools/graftlint.py --format json`).

    `by_rule` counts *all* findings (unbaselined + baselined) per rule —
    rule activity, not just new debt."""
    by_rule: Dict[str, int] = {}
    for f in list(findings) + list(baselined):
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    report = {
        "unbaselined": [f.to_json() for f in findings],
        "unbaselined_count": len(findings),
        "baselined_count": len(baselined),
        "stale_baseline_count": len(stale),
        "by_rule": dict(sorted(by_rule.items())),
        "parse_errors": dict(errors or {}),
        "clean": not findings and not (errors or {}),
    }
    if sweep_seconds is not None:
        report["sweep_seconds"] = round(sweep_seconds, 4)
    return report


_SARIF_SCHEMA = ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                 "master/Schemata/sarif-schema-2.1.0.json")


def report_sarif(findings: Sequence[Finding],
                 rules: Optional[Sequence[Rule]] = None) -> dict:
    """SARIF 2.1.0 document for CI annotation UIs.

    One run, one driver ("graftlint"); every reported rule appears in
    the driver's rule table; each result carries the graftlint
    fingerprint as a partialFingerprint so SARIF consumers dedupe
    across line drift exactly like the baseline does."""
    rules = list(rules) if rules is not None else all_rules()
    rule_ids = [r.name for r in rules]
    index_of = {name: i for i, name in enumerate(rule_ids)}
    results = []
    for f in findings:
        result = {
            "ruleId": f.rule,
            "level": "warning",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path,
                                         "uriBaseId": "SRCROOT"},
                    "region": {"startLine": f.line,
                               "snippet": {"text": f.snippet}},
                },
            }],
            "partialFingerprints": {"graftlint/v1": f.fingerprint},
        }
        if f.rule in index_of:
            result["ruleIndex"] = index_of[f.rule]
        results.append(result)
    return {
        "$schema": _SARIF_SCHEMA,
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "rules": [{
                    "id": r.name,
                    "shortDescription": {"text": r.description},
                } for r in rules],
            }},
            "originalUriBaseIds": {"SRCROOT": {"uri": "file:///"}},
            "results": results,
        }],
    }
