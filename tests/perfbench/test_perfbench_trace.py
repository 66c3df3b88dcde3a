"""The reduction from a profiler trace to busy time, top operations and
idle gaps: on hand-made planes whose answers are known, and on a small
trace recorded on a v5e and kept beside the reduction."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import spec  # noqa: E402
from perfbench.trace import reduce as tr  # noqa: E402

SAMPLE = os.path.join(REPO, "perfbench", "trace", "sample_planes.json")
MS = 1_000_000


def planes(ops, spans, modules=()):
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": list(modules)},
            "/host:CPU": {"python": [("pb:window", 0, 100 * MS)] + spans}}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = tr.reduce_planes(planes(
        ops=[("fusion.1", 10 * MS, 20 * MS), ("sort.2", 20 * MS, 30 * MS),
             ("fusion.1", 70 * MS, 10 * MS),
             ("copy.3", 95 * MS, 10 * MS)],        # runs past the window
        spans=[("pb:toArrow", 5 * MS, 60 * MS)]))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.040 + 0.010 + 0.005)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    assert [n for n, _ in red["device_ops"]] == ["fusion.1", "sort.2",
                                                  "copy.3"]


def test_idle_gaps_are_named_after_the_span_that_covers_them():
    red = tr.reduce_planes(planes(
        ops=[("a", 10 * MS, 20 * MS), ("b", 60 * MS, 30 * MS)],
        spans=[("pb:session.sql", 0, 10 * MS),
               ("pb:toArrow", 10 * MS, 80 * MS)]))
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["pb:session.sql"] == pytest.approx(0.010)   # 0..10
    assert gaps["pb:toArrow"] == pytest.approx(0.030)       # 30..60
    assert gaps["no_span"] == pytest.approx(0.010)          # 90..100
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(
        red["window_s"])


def test_two_streams_share_a_gap_and_four_chips_average():
    p = planes(ops=[("a", 0, 50 * MS)], spans=[("pb:execute", 40 * MS,
                                                 60 * MS)])
    p["/host:CPU"]["pb-stream-1"] = [("pb:fetchall", 50 * MS, 50 * MS)]
    for d in (1, 2, 3):
        p[f"/device:TPU:{d}"] = {"XLA Ops": [("a", 0, 100 * MS)]}
    red = tr.reduce_planes(p)
    assert red["devices"] == 4
    assert red["busy_s"] == pytest.approx((0.050 + 3 * 0.100) / 4)
    assert red["idle_gaps"][0][0] == "pb:execute+pb:fetchall"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="pb:window"):
        tr.reduce_planes({"/device:TPU:0": {"XLA Ops": [("a", 0, 1)]}})


@pytest.mark.parametrize("name", [
    "hbm_roofline_pct", "device_idle_pct", "device_s_per_query", "plan_ms",
    "programs_per_query"])
def test_reader_returns_nothing_where_there_is_nothing_to_read(name):
    run = {"trace": None, "records": [], "latencies": [],
           "before": {"counters": {"by_kind": {}}},
           "after": {"counters": {"by_kind": {}}}}
    assert spec.metric_reader(name).read(run) is None


def test_the_recorded_v5e_trace_reduces_to_what_was_recorded():
    with open(SAMPLE) as f:
        sample = json.load(f)
    red = tr.reduce_planes(sample["planes"])
    want = sample["reduced"]
    assert red["devices"] == want["devices"] == 1
    for k in ("window_s", "busy_s"):
        assert red[k] == pytest.approx(want[k], rel=1e-12)
    assert [n for n, _ in red["device_ops"]] \
        == [n for n, _ in want["device_ops"]]
    # what has to hold of any trace
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = sum(s for _n, s in red["idle_gaps"])
    assert gaps + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    names = {n for n, _ in red["idle_gaps"]}
    assert names & {"pb:session.sql", "pb:toArrow"}


def test_the_recorded_xplane_file_loads_to_the_recorded_planes():
    """Through jax.profiler.ProfileData, as a run reads its own trace."""
    with open(SAMPLE) as f:
        sample = json.load(f)
    path = os.path.join(REPO, "perfbench", "trace", "sample.xplane.pb")
    loaded = tr.load_planes(path)
    assert set(loaded) == set(sample["planes"])
    for plane, lines in sample["planes"].items():
        for line, events in lines.items():
            assert [tuple(e) for e in events] == loaded[plane][line]
    assert tr.reduce_file(path)["busy_s"] == pytest.approx(
        sample["reduced"]["busy_s"], rel=1e-12)
