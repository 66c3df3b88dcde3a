#!/usr/bin/env python
"""racecheck: static race & lock-discipline analyzer CLI (tpurace).

Whole-repo AST pass over spark_tpu/ (no jax import, no device work; safe
inside the tier-1 budget). Rules: shared-mutation, lock-order,
bare-submit, worker-reinit — see spark_tpu/analysis/race_lint.py. The
runtime half is utils/lockwatch.py, cross-checked by
tests/test_race_lint.py.

Usage:
  python dev/racecheck.py [paths...] [--baseline dev/race_baseline.json]
                          [--write-baseline] [--rule RULE]
                          [--format text|json] [--dump-model]

Exit codes: 0 clean (or all violations baselined), 1 new violations,
2 usage error. The baseline counts violations per (file, rule) bucket —
same workflow as tpulint: existing debt doesn't block CI, NEW debt does.
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Import the analyzer directly off its file path: `import spark_tpu`
# pulls in the whole engine (and jax); the AST pass must stay light
# enough for CI's tier-1 budget.
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "racecheck_impl",
    os.path.join(_ROOT, "spark_tpu", "analysis", "race_lint.py"))
rlint = importlib.util.module_from_spec(_spec)
sys.modules["racecheck_impl"] = rlint
_spec.loader.exec_module(rlint)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="racecheck", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(_ROOT, "spark_tpu")])
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON; violations beyond its per-bucket "
                         "counts fail the run")
    ap.add_argument("--write-baseline", action="store_true",
                    help="(re)write the baseline from the current state "
                         "and exit 0")
    ap.add_argument("--rule", action="append", default=None,
                    choices=list(rlint.RULES),
                    help="restrict to specific rule(s)")
    ap.add_argument("--format", default="text", choices=("text", "json"))
    ap.add_argument("--dump-model", action="store_true",
                    help="print the repo concurrency model (locks, "
                         "states, nesting edges, annotations) as JSON — "
                         "the surface the --race dynamic gate consumes")
    args = ap.parse_args(argv)
    if args.write_baseline and args.rule:
        ap.error("--write-baseline with --rule would drop every other "
                 "rule's buckets from the baseline; run it unfiltered")

    paths = [p if os.path.isabs(p) else os.path.join(os.getcwd(), p)
             for p in args.paths]
    model = rlint.build_model(paths, repo_root=_ROOT)
    violations = model.violations
    if args.rule:
        violations = [v for v in violations if v.rule in set(args.rule)]

    if args.dump_model:
        print(json.dumps(model.to_dict(), indent=1))
        return 0

    if args.write_baseline:
        target = args.baseline or os.path.join(_HERE, "race_baseline.json")
        rlint.write_baseline(target, violations)
        print(f"racecheck: baseline written to {target} "
              f"({len(violations)} violations over "
              f"{len(rlint.baseline_counts(violations))} buckets)")
        return 0

    if args.baseline:
        baseline = rlint.load_baseline(args.baseline)
        offending = rlint.new_violations(violations, baseline)
        label = "new violation(s) beyond baseline"
    else:
        baseline = {}
        offending = violations
        label = "violation(s)"

    if args.format == "json":
        print(json.dumps({
            "total": len(violations),
            "new": [v.__dict__ for v in offending],
        }, indent=1))
    else:
        for v in offending:
            print(v)
        by_rule = {}
        for v in violations:
            by_rule[v.rule] = by_rule.get(v.rule, 0) + 1
        summary = ", ".join(f"{r}:{n}" for r, n in sorted(by_rule.items()))
        print(f"racecheck: {len(violations)} total "
              f"({summary or 'clean'}); {len(offending)} {label}")
    return 1 if offending else 0


if __name__ == "__main__":
    sys.exit(main())
