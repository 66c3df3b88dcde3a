#!/usr/bin/env python3
"""How a bound in BENCHMARK.json is set: the rule, applied to recorded runs.

    python3 perfbench/bounds.py perfbench/bounds/<file>.jsonl

Every line of the file is one fresh-process run on the chip: `set` (two
sets, the same seeds in both), `seed`, and the run's end-to-end `metrics`
as its result line gave them. For every metric but `setup_s`:

- a set's spread is the range of its values once the one farthest from
  their median is left out, over the median (the check's own rule for
  "cannot tell", ledger PR 27);
- the bound is twice the wider of the two sets' spreads, rounded up to a
  step of 0.005, never under 0.01; a metric that would need more than 0.1
  is not steady yet, and no bound is set wider than this gives.

A cell measured later is a further file, with two sets of its own
(`python3 perfbench/bounds.py <first> <later>...`): the same rule on its
runs, and a metric's bound is the widest that any recorded cell gives it
(`fit`). So a cell's runs can widen a bound and never tighten another
cell's.

`setup_s` stands at 0.1: the check judges it by its median alone. Beside
each bound the two readings the driver holds it to are printed (each set's
interquartile spread by `statistics.quantiles(n=4)`, its farthest run left
out, averaged, against half the bound; the bound against eight times the
widest interquartile spread), and whether the second set, taken as a change
against the first, comes out unchanged: its median no worse by more than
the bound, and neither set's spread wider than the bound.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
STEP, FLOOR, CEILING, SETUP_BOUND = 0.005, 0.01, 0.1, 0.1


def less_farthest(values: list) -> list:
    m = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - m))[:-1]


def spread(values: list) -> float:
    kept = less_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def quartile_spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def bound_from(spreads: list) -> float:
    """Twice the widest spread, up to the next step, never under the floor."""
    steps = math.ceil(round(2 * max(spreads) / STEP, 9))
    b = max(FLOOR, round(steps * STEP, 6))
    if b > CEILING:
        raise ValueError(f"a spread of {max(spreads):.4f} needs a bound of "
                         f"{b}, over {CEILING}: the metric is not steady")
    return b


def fit(judged: list) -> dict:
    """Every metric's bound over the recorded cells (`judge_file`'s
    results): the widest that any of them gives it."""
    return {name: max(file[name]["bound"] for file in judged)
            for name in judged[0]}


def judge(name: str, first: list, second: list, better: str) -> dict:
    """One metric from its two sets of values."""
    spreads = [spread(first), spread(second)]
    bound = SETUP_BOUND if name == "setup_s" else bound_from(spreads)
    m1, m2 = statistics.median(first), statistics.median(second)
    worse = (m2 - m1) / m1 * (1 if better == "lower" else -1)
    tightness = statistics.mean(
        quartile_spread(less_farthest(v)) for v in (first, second))
    widest = max(quartile_spread(first), quartile_spread(second))
    return {"bound": bound, "spreads": spreads, "medians": [m1, m2],
            "second_worse_by": worse,
            "unchanged": worse <= bound and (
                name == "setup_s" or max(spreads) <= bound),
            "too_tight": name != "setup_s" and tightness > bound / 2,
            "too_loose": name != "setup_s" and bound > FLOOR
            and bound > 8 * widest,
            "quartile_spread_less_farthest_mean": tightness,
            "quartile_spread_widest": widest}


def judge_file(path: str) -> dict:
    with open(path) as f:
        runs = [json.loads(line) for line in f if line.strip()]
    sets = list(dict.fromkeys(r["set"] for r in runs))
    if len(sets) != 2:
        raise ValueError(f"two sets are needed, {path} has {sets}")
    by_set = [[r for r in runs if r["set"] == s] for s in sets]
    if sorted(r["seed"] for r in by_set[0]) \
            != sorted(r["seed"] for r in by_set[1]):
        raise ValueError("the two sets do not have the same seeds")
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        better = {m["name"]: m["better"] for m in json.load(f)["end_to_end"]}
    return {name: judge(name, *([r["metrics"][name] for r in rs]
                                for rs in by_set), better[name])
            for name in by_set[0][0]["metrics"]}


def main(argv) -> int:
    judged = [judge_file(path) for path in argv[1:]]
    for path, file in zip(argv[1:], judged):
        print(path)
        report(file)
    print("bounds:", json.dumps(fit(judged)))
    return 0


def report(judged: dict) -> None:
    for name, j in judged.items():
        print(f"{name}: bound {j['bound']}; spreads "
              f"{j['spreads'][0]:.5f} {j['spreads'][1]:.5f}; medians "
              f"{j['medians'][0]:.6g} {j['medians'][1]:.6g}, the second "
              f"worse by {j['second_worse_by']:+.5f}: "
              f"{'unchanged' if j['unchanged'] else 'NOT unchanged'}; "
              f"the driver's readings "
              f"{j['quartile_spread_less_farthest_mean']:.5f} "
              f"(at most {j['bound'] / 2}), "
              f"{j['quartile_spread_widest']:.5f} "
              f"(at least {j['bound'] / 8:.5f})"
              f"{' TOO TIGHT' if j['too_tight'] else ''}"
              f"{' TOO LOOSE' if j['too_loose'] else ''}")


if __name__ == "__main__":
    sys.exit(main(sys.argv))
