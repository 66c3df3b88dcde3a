"""`perfbench/queue_sim.py`: the reckoning behind tenants2's
`start_offsets_s`. With both tenants started at once it has the two
levels of `query_s.p50` that the chip showed (PR 36: 4.53 and 4.76 s);
with the second tenant as late as the traffic file says it has one, the
one the chip then read (4.71-4.85 s, `query_s.p95` 7.30-7.37 s); and the
file's offset sits in the middle of a stretch that reads the same."""

import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import queue_sim as qs, spec  # noqa: E402

TRAFFIC = spec.cell("tpcds_sf10_server.tenants2")["traffic"]
STREAMS, ROUNDS = TRAFFIC["streams"], TRAFFIC["rounds_at_most"]


def test_started_together_the_streams_fall_into_one_of_two_orders():
    p50 = sorted(qs.window(STREAMS, ROUNDS, [0.0, 0.0],
                           random.Random(i))[1]["query_s.p50"]
                 for i in range(100))
    low = [v for v in p50 if v < 4.65]
    high = [v for v in p50 if v > 4.65]
    assert len(low) >= 20 and len(high) >= 20
    assert 4.45 < min(low) and max(low) < 4.62      # the chip: 4.50-4.58
    assert 4.72 < min(high) and max(high) < 4.90    # the chip: 4.69-4.83


@pytest.mark.parametrize("late", [0.9, 1.0, 1.25, 1.5, 1.8])
def test_the_files_offset_is_in_the_middle_of_one_order(late):
    assert TRAFFIC["start_offsets_s"] == [0.0, 1.25]
    mine = qs.spread(STREAMS, ROUNDS, [0.0, 1.25], runs=100)
    near = qs.spread(STREAMS, ROUNDS, [0.0, late], runs=100)
    for name, (median, whole) in near.items():
        assert median == pytest.approx(mine[name][0], rel=2e-3), name
        assert whole < 0.025, name        # the host's own draws, no step
    assert mine["query_s.p50"][0] == pytest.approx(4.81, abs=0.05)
    assert mine["query_s.p95"][0] == pytest.approx(7.33, abs=0.05)


@pytest.mark.parametrize("host_after_q7,programs", [
    (-0.2, 1.0), (-0.1, 1.0), (0.1, 1.0), (0.2, 1.0),
    (0.0, 0.8), (0.0, 0.9), (0.0, 1.1), (0.0, 1.2)])
def test_the_order_holds_when_the_hosts_or_the_devices_times_move(
        host_after_q7, programs):
    """No step nearby: the numbers move with the times, in proportion,
    and the spread stays the host's own."""
    lo, hi = qs.HOST["answer"]["q7"]
    host = {**qs.HOST, "answer": {**qs.HOST["answer"], "q7": (
        lo + host_after_q7, hi + host_after_q7)}}
    progs = {q: (a * programs, b * programs)
             for q, (a, b) in qs.PROGRAMS.items()}
    got = qs.spread(STREAMS, ROUNDS, TRAFFIC["start_offsets_s"], runs=100,
                    programs=progs, host=host)
    for name, (_, whole) in got.items():
        assert whole < 0.03, name


def test_a_window_is_whole_rounds_of_every_stream_and_the_tool_prints(
        capsys):
    records, values = qs.window(STREAMS, ROUNDS, [0.0, 1.25],
                                random.Random(7))
    assert sorted((s, q) for s, q, _, _ in records) == sorted(
        (s, q) for s in (0, 1) for q in STREAMS[s] * ROUNDS)
    assert min(t for s, _, t, _ in records if s == 1) == 1.25
    assert values["query_s.p50"] in [r[3] for r in records]
    path = os.path.join(REPO, "perfbench", "traffic", "tenants2.json")
    assert qs.main(["queue_sim.py", path]) == 0
    out = capsys.readouterr().out
    assert "as written, offsets [0.0, 1.25]" in out
    assert "second stream 3.0 s late" in out
