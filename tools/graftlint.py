#!/usr/bin/env python3
"""graftlint CLI — run the repo's AST hazard rules and gate on the baseline.

    python tools/graftlint.py paddle_tpu                 # the tier-1 gate
    python tools/graftlint.py paddle_tpu --format json   # machine-readable
    python tools/graftlint.py --rule SWALLOWED-API serving/engine.py
    python tools/graftlint.py paddle_tpu --baseline-update

Exit codes: 0 clean (no unbaselined findings, no parse errors), 1 findings
or parse errors, 2 usage error.

The analysis package is pure stdlib; this entry point loads it WITHOUT
importing `paddle_tpu` (which would pull in jax) so linting stays
backend-free and cheap enough for the fast lane.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO_ROOT, "tools", "graftlint_baseline.json")

# loaded under a private top-level name so nothing touches the real
# `paddle_tpu` package namespace (no stub parents poisoning sys.modules,
# no breakage for a later full `import paddle_tpu` in the same process)
_PKG_NAME = "_graftlint_analysis"


def load_analysis():
    """Load paddle_tpu/analysis as a standalone stdlib-only package."""
    if "paddle_tpu" in sys.modules:  # already paid for; reuse the real one
        import paddle_tpu.analysis
        return paddle_tpu.analysis
    mod = sys.modules.get(_PKG_NAME)
    if mod is not None:
        return mod
    pkg_dir = os.path.join(REPO_ROOT, "paddle_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        _PKG_NAME, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[_PKG_NAME] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(_PKG_NAME, None)
        raise
    return mod


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="graftlint",
        description="AST-based JAX-hazard static analyzer for this repo")
    p.add_argument("paths", nargs="*", default=["paddle_tpu"],
                   help="files/directories to analyze (default: paddle_tpu)")
    p.add_argument("--rule", action="append", default=None, metavar="NAME",
                   help="run only this rule (repeatable; accepts aliases "
                        "like BLE001)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE, metavar="PATH",
                   help="baseline file (default: tools/graftlint_baseline"
                        ".json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the baseline")
    p.add_argument("--baseline-update", action="store_true",
                   help="rewrite the baseline from current findings, "
                        "keeping reasons for surviving fingerprints and "
                        "preserving stale entries (add --prune-stale to "
                        "drop them)")
    p.add_argument("--prune-stale", action="store_true",
                   help="drop baseline entries whose fingerprint no "
                        "longer matches any finding, printing each "
                        "pruned entry; combines with --baseline-update "
                        "or rewrites the baseline in place on its own")
    p.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule set and exit")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    analysis = load_analysis()

    if args.list_rules:
        for rule in analysis.all_rules():
            codes = ", ".join(rule.codes)
            print(f"{codes}\n    {rule.description}")
        return 0

    try:
        rules = ([analysis.get_rule(n) for n in args.rule]
                 if args.rule else None)
    except KeyError as e:
        print(f"graftlint: {e.args[0]}", file=sys.stderr)
        return 2

    paths = []
    for p in (args.paths or ["paddle_tpu"]):
        paths.append(p if os.path.exists(p) else os.path.join(REPO_ROOT, p))
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"graftlint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2

    cache = analysis.ModuleCache()
    t0 = time.monotonic()
    findings = analysis.run_paths(paths, rules=rules, root=REPO_ROOT,
                                  cache=cache)
    sweep_seconds = time.monotonic() - t0

    baseline_path = None if args.no_baseline else args.baseline
    baseline = analysis.load_baseline(baseline_path)

    if args.baseline_update:
        new = analysis.Baseline.from_findings(
            findings, default_reason="TODO: justify or fix")
        new.carry_reasons_from(baseline)
        if args.prune_stale:
            for e in baseline.stale_entries(findings):
                print(f"graftlint: pruned stale {e['rule']} "
                      f"{e['path']}:{e.get('line', '?')} "
                      f"[{e['fingerprint']}]")
        else:
            new.adopt_missing_from(baseline)
        new.dump(args.baseline)
        print(f"graftlint: wrote {len(new)} entries to {args.baseline}")
        return 0

    if args.prune_stale:
        if args.no_baseline:
            print("graftlint: --prune-stale needs a baseline "
                  "(--no-baseline given)", file=sys.stderr)
            return 2
        pruned = baseline.prune_stale(findings)
        for e in pruned:
            print(f"graftlint: pruned stale {e['rule']} "
                  f"{e['path']}:{e.get('line', '?')} "
                  f"[{e['fingerprint']}]")
        baseline.dump(args.baseline)
        print(f"graftlint: pruned {len(pruned)} entr"
              f"{'y' if len(pruned) == 1 else 'ies'}, "
              f"{len(baseline)} remain in {args.baseline}")
        return 0

    fresh, known = baseline.split(findings)
    stale = baseline.stale_entries(findings)

    if args.format == "json":
        report = analysis.runner.report_json(
            fresh, baselined=known, stale=stale, errors=cache.errors,
            sweep_seconds=sweep_seconds)
        report["stale_baseline"] = stale
        json.dump(report, sys.stdout, indent=2)
        print()
    elif args.format == "sarif":
        rules_for_table = rules if rules is not None \
            else analysis.all_rules()
        json.dump(analysis.report_sarif(fresh, rules=rules_for_table),
                  sys.stdout, indent=2)
        print()
    else:
        for f in fresh:
            print(f.render())
        for path, err in sorted(cache.errors.items()):
            print(f"{path}: PARSE-ERROR: {err}")
        summary = (f"graftlint: {len(fresh)} unbaselined finding(s), "
                   f"{len(known)} baselined, {len(stale)} stale baseline "
                   f"entr{'y' if len(stale) == 1 else 'ies'}")
        print(summary)
        for e in stale:
            print(f"  stale: {e['rule']} {e['path']}:{e.get('line', '?')} "
                  f"(fixed? delete the entry)")
    return 1 if (fresh or cache.errors) else 0


if __name__ == "__main__":
    sys.exit(main())
