#!/usr/bin/env python
"""perfcheck: deterministic perf-regression gate over bench smoke profiles.

Wall-clock on the CI box is noise, but kernel launches by kind, compile
counts, and retry attempts are DETERMINISTIC — plan_lint predicts them
exactly, and the query flight recorder (spark_tpu/obs/history.py) now
persists them per plan fingerprint. This gate closes the loop across
commits:

  1. run `bench.py --smoke --profile` (tiny scales, forced CPU) with the
     flight recorder pointed at a scratch directory;
  2. collapse each query key's profiles to its STEADY-STATE deterministic
     counters (min launches per kind across runs — the warm run; max of
     the retry/fault counters — which must stay zero on a healthy run);
  3. diff against the committed `dev/perf_baseline.json` and exit
     non-zero on ANY counter increase, new launch kind, or vanished
     query key.

A legitimate engine change that shifts launch counts (a new fusion rule,
a tier-chooser change) must refresh the baseline CONSCIOUSLY:

  python dev/perfcheck.py --write-baseline

Exit codes: 0 clean, 1 regression (or missing baseline), 2 usage/bench
failure.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

DEFAULT_BASELINE = os.path.join(_HERE, "perf_baseline.json")

# counters gated here (max across a key's profiles — healthy smoke runs
# must not retry); mirrors obs/history.DETERMINISTIC_COUNTERS
from spark_tpu.obs.history import DETERMINISTIC_COUNTERS, ProfileStore  # noqa: E402

# persistent-cache steady-state counters (exec/persist_cache.py): gated
# the same increase-only way — compile.disk_miss going up means the XLA
# disk cache stopped hitting for a known plan, result_cache.miss going
# up means a repeated query stopped answering from the result cache.
# With the caches off (the default bench --smoke run) both stay 0 and
# the gate is inert; a cache-enabled profile run locks them in.
PERSIST_COUNTERS = ("compile.disk_miss", "result_cache.miss")
GATED_COUNTERS = tuple(DETERMINISTIC_COUNTERS) + PERSIST_COUNTERS


def collect_profiles(profile_dir: str) -> dict:
    """Collapse a profile directory into the gate's shape:
    {query_key: {detail, launches (min per kind), compiles_steady (min),
    counters (max per deterministic counter), runs}}. Min-per-kind is
    the steady state — cold runs legitimately launch memo probes and
    compile; the WARM run is the deterministic quantity."""
    store = ProfileStore(profile_dir)
    out: dict = {}
    for qk in store.query_keys():
        # deltas are scope-exact (per-query kernel ledger, PR 15) —
        # every stored profile gates, including ones recorded under
        # concurrent load
        profs = store.profiles(qk)
        if not profs:
            continue
        launches: dict = {}
        for p in profs:
            for kind, n in (p.get("launches_by_kind") or {}).items():
                cur = launches.get(kind)
                launches[kind] = n if cur is None else min(cur, n)
        counters = {}
        for key in GATED_COUNTERS:
            v = max((p.get("counters") or {}).get(key, 0) for p in profs)
            if v:
                counters[key] = v
        out[qk] = {
            "detail": profs[-1].get("detail", "")[:120],
            "launches": {k: int(v) for k, v in sorted(launches.items())},
            "compiles_steady": int(min(p.get("compiles", 0)
                                       for p in profs)),
            "counters": counters,
            "runs": len(profs),
        }
    return out


def compare(fresh: dict, baseline: dict) -> tuple[list, list]:
    """Diff fresh steady-state counters against the committed baseline.
    Returns (regressions, notes): regressions fail the gate; notes are
    improvements/new queries that suggest a conscious baseline refresh."""
    regressions: list[str] = []
    notes: list[str] = []
    base_q = baseline.get("queries", {})
    for qk, b in sorted(base_q.items()):
        f = fresh.get(qk)
        tag = f"{qk} [{b.get('detail', '')[:60]}]"
        if f is None:
            regressions.append(
                f"{tag}: query key missing from the fresh run — the plan "
                "structure (or its fingerprinting) changed; if "
                "intentional, refresh with --write-baseline")
            continue
        kinds = set(b.get("launches", {})) | set(f.get("launches", {}))
        for kind in sorted(kinds):
            bv = b.get("launches", {}).get(kind, 0)
            fv = f.get("launches", {}).get(kind, 0)
            if fv > bv:
                regressions.append(
                    f"{tag}: steady-state launches '{kind}' {fv} > "
                    f"baseline {bv}")
            elif fv < bv:
                notes.append(
                    f"{tag}: launches '{kind}' improved {bv} -> {fv} "
                    "(refresh the baseline to lock it in)")
        bv = b.get("compiles_steady", 0)
        fv = f.get("compiles_steady", 0)
        if fv > bv:
            regressions.append(
                f"{tag}: steady-state compiles {fv} > baseline {bv} — a "
                "kernel cache key stopped hitting across runs")
        for key in GATED_COUNTERS:
            bv = b.get("counters", {}).get(key, 0)
            fv = f.get("counters", {}).get(key, 0)
            if fv > bv:
                regressions.append(
                    f"{tag}: counter {key} = {fv} > baseline {bv}")
    for qk in sorted(set(fresh) - set(base_q)):
        notes.append(f"{qk} [{fresh[qk].get('detail', '')[:60]}]: new "
                     "query key (not in baseline — add with "
                     "--write-baseline)")
    return regressions, notes


def run_bench_smoke(profile_dir: str) -> int:
    """Run the bench smoke configs with the flight recorder on, in a
    child process (bench.py owns its own session/device lifecycle)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_TPU_PROFILE_DIR"] = profile_dir
    cmd = [sys.executable, os.path.join(_ROOT, "bench.py"),
           "--smoke", "--profile"]
    print(f"perfcheck: running {' '.join(cmd)}")
    proc = subprocess.run(cmd, env=env, cwd=_ROOT,
                          stdout=subprocess.PIPE, text=True)
    sys.stdout.write(proc.stdout[-2000:])
    return proc.returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfcheck", description=__doc__)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--write-baseline", action="store_true",
                    help="(re)write the committed baseline from this "
                         "run's profiles and exit 0")
    ap.add_argument("--profiles", default=None,
                    help="use an existing profile directory instead of "
                         "running bench --smoke --profile")
    args = ap.parse_args(argv)

    if args.profiles:
        profile_dir = args.profiles
    else:
        profile_dir = tempfile.mkdtemp(prefix="perfcheck_profiles_")
        rc = run_bench_smoke(profile_dir)
        if rc != 0:
            print(f"perfcheck: FAIL — bench smoke run exited {rc}")
            return 2
    fresh = collect_profiles(profile_dir)
    if not fresh:
        print(f"perfcheck: FAIL — no profiles recorded in {profile_dir}")
        return 2

    if args.write_baseline:
        doc = {"version": 1,
               "note": "steady-state deterministic counters of the bench "
                       "smoke configs, keyed by structural query key "
                       "(spark_tpu/obs/history.py); regenerate with "
                       "`python dev/perfcheck.py --write-baseline`",
               "queries": fresh}
        with open(args.baseline, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"perfcheck: baseline written to {args.baseline} "
              f"({len(fresh)} query keys)")
        return 0

    if not os.path.isfile(args.baseline):
        print(f"perfcheck: FAIL — no baseline at {args.baseline} (create "
              "one with --write-baseline)")
        return 1
    with open(args.baseline) as f:
        baseline = json.load(f)
    regressions, notes = compare(fresh, baseline)
    for n in notes:
        print(f"perfcheck: note — {n}")
    if regressions:
        for r in regressions:
            print(f"perfcheck: REGRESSION — {r}")
        print(f"perfcheck: FAIL — {len(regressions)} deterministic-counter "
              f"regression(s) vs {args.baseline}")
        return 1
    print(f"perfcheck: OK — {len(fresh)} query keys, steady-state "
          "launches/compiles/retries all within baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
