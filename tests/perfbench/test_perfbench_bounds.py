"""How a bound is set (perfbench/bounds.py): the rule on planted values,
and BENCHMARK.json's bounds against the recorded runs they came from."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import bounds  # noqa: E402

RUNS = os.path.join(REPO, "perfbench", "bounds", "pr28.jsonl")
# two sets of six runs of the two cells whose traffic PR 36 changed
LATER = {"tpcds_sf10_window.dev2": os.path.join(
             REPO, "perfbench", "bounds", "pr36_dev2.jsonl"),
         "tpcds_sf10_server.tenants2": os.path.join(
             REPO, "perfbench", "bounds", "pr36_tenants2.jsonl")}


def test_a_spread_leaves_out_the_run_farthest_from_the_median():
    calm = [100.0, 100.2, 99.9, 100.1, 100.0, 99.8]
    assert bounds.spread(calm + [104.0]) == pytest.approx(0.004)
    # one far-off run does no harm, two do
    assert bounds.spread(calm + [104.0, 104.0]) > 0.04
    assert bounds.less_farthest([1.0, 2.0, 9.0]) == [2.0, 1.0]


@pytest.mark.parametrize("widest,bound", [
    (0.0005, 0.01), (0.005, 0.01), (0.0051, 0.015), (0.0064, 0.015),
    (0.0075, 0.015), (0.0076, 0.02), (0.0086, 0.02), (0.0499, 0.1)])
def test_a_bound_is_twice_the_widest_spread_up_to_the_next_step(
        widest, bound):
    assert bounds.bound_from([0.0001, widest]) == bound


def test_a_metric_that_needs_more_than_the_ceiling_is_not_steady():
    with pytest.raises(ValueError, match="not steady"):
        bounds.bound_from([0.0501, 0.01])


@pytest.mark.parametrize("second,better,bound,unchanged", [
    ([100.5, 100.6, 100.4, 100.5, 100.7, 100.5, 100.3], "lower", 0.01, True),
    ([102.5, 102.6, 102.4, 102.5, 102.7, 102.5, 102.3], "lower", 0.01, False),
    ([102.5, 102.6, 102.4, 102.5, 102.7, 102.5, 102.3], "higher", 0.01, True),
    ([100.0, 101.6, 99.0, 100.5, 101.8, 100.5, 98.9], "lower", 0.06, True)])
def test_the_second_set_against_the_first(second, better, bound, unchanged):
    """One tree, so nothing moved: a second median worse by more than
    the bound would be a loss, a better one never is; the bound follows
    the wider set."""
    first = [100.0, 100.1, 99.9, 100.0, 100.2, 100.0, 99.8]
    j = bounds.judge("query_s.p50", first, second, better)
    assert j["bound"] == bound and j["unchanged"] == unchanged
    assert max(j["spreads"]) <= j["bound"] / 2 + 1e-12


def test_setup_s_stands_at_a_tenth_and_is_judged_by_its_median_alone():
    first = [43.0, 44.5, 42.1, 43.6, 45.0, 43.2, 42.8]
    j = bounds.judge("setup_s", first, [v + 2.0 for v in first], "lower")
    assert j["bound"] == 0.1 and j["unchanged"] and not j["too_tight"]
    j = bounds.judge("setup_s", first, [v + 5.0 for v in first], "lower")
    assert not j["unchanged"]


@pytest.mark.parametrize("later,bound", [
    ([0.01], 0.015), ([0.015], 0.015), ([0.02], 0.02),
    ([0.01, 0.025], 0.025), ([0.05, 0.01], 0.05)])
def test_a_bound_is_the_widest_that_any_recorded_cell_gives_it(
        later, bound):
    """A cell measured later has the same rule on its own two sets; its
    runs can widen a bound and never tighten another cell's."""
    files = [{"m": {"bound": 0.015}, "setup_s": {"bound": 0.1}}] + [
        {"m": {"bound": b}, "setup_s": {"bound": 0.1}} for b in later]
    assert bounds.fit(files) == {"m": bound, "setup_s": 0.1}


def test_benchmark_jsons_bounds_are_the_rules_on_the_recorded_runs():
    """Two sets of seven runs at the same seven seeds (PR 28, on the
    v5e): every bound is what the rule gives, every metric comes out
    unchanged between the sets, every window held whole rounds. The
    cells whose traffic PR 36 changed (two sets of six runs each, the same
    six seeds) are held to the same rule, and a bound is the widest that
    any of the three gives: dev2's rate and tail, tenants2's median."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        written = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    judged = bounds.judge_file(RUNS)
    later = {cell: bounds.judge_file(path) for cell, path in LATER.items()}
    assert bounds.fit([judged] + list(later.values())) == written
    assert written == {"fact_rows_per_s": 0.02, "query_s.p50": 0.025,
                       "query_s.p95": 0.05, "setup_s": 0.1}
    dev2, tenants2 = (later[c] for c in sorted(LATER, reverse=True))
    assert dev2["fact_rows_per_s"]["bound"] == 0.02
    assert dev2["query_s.p95"]["bound"] == 0.05
    assert tenants2["query_s.p50"]["bound"] == 0.025
    for file in later.values():
        for name, j in file.items():
            assert j["unchanged"], name
            assert j["second_worse_by"] <= written[name], name
            # the check's reading for "too tight", against what is written
            assert j["quartile_spread_less_farthest_mean"] \
                <= written[name] / 2 or name == "setup_s", name
    for name, j in judged.items():
        assert j["unchanged"], name
        assert max(j["spreads"]) <= j["bound"] / 2 or name == "setup_s", name
    with open(RUNS) as f:
        runs = [json.loads(line) for line in f]
    assert len(runs) == 14 and len({r["seed"] for r in runs}) == 7
    for r in runs:
        assert r["correct"] is True
        assert r["window"]["queries"]["q3"] == r["window"]["queries"]["q7"] \
            == r["window"]["rounds"][0]
        assert r["window"]["seconds"] <= 52.0


@pytest.mark.parametrize("cell", sorted(LATER))
def test_the_later_cells_recorded_runs_are_whole_and_correct(cell):
    from perfbench import spec

    traffic = spec.cell(cell)["traffic"]
    with open(LATER[cell]) as f:
        runs = [json.loads(line) for line in f]
    assert len(runs) == 12 and len({r["seed"] for r in runs}) == 6
    assert sorted(r["set"] for r in runs) == ["A"] * 6 + ["B"] * 6
    for r in runs:
        assert r["correct"] is True
        assert r["window"]["rounds"] \
            == [traffic["rounds_at_most"]] * len(traffic["streams"])
        assert r["window"]["seconds"] <= 51.0
