"""The window's rule and what `setup_s` counts, on fake clients and a fake
clock: whole rounds only, the same count of every template whatever the
rounds' length, a rate that does not move when the count flips, the two
percentiles at ranks k and 2k, over all the window's queries; and a
planted wait for the generator left out of `setup_s` and reported beside
it."""

import argparse
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import gen, run as pb  # noqa: E402

SESSION = "tpcds_sf10_session.power2"
ROWS = 28_800_991
SHARE = {"q3": 0.37, "q7": 0.63}     # of a round, as on the chip


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


class FakeGate:
    def __init__(self, clock):
        self.t0 = clock()

    def wait(self):
        pass


class FakeClient:
    """A query takes its template's share of `round_s`; within a round
    the shares are shifted by a little that differs from round to round,
    so that no two latencies are equal and every round is `round_s`."""

    def __init__(self, clock, queries, round_s, stall=None):
        self.clock, self.queries, self.round_s = clock, queries, round_s
        self.calls, self.stall = 0, stall

    def run(self, text, annotate):
        n = len(self.queries)
        pos, rnd = self.calls % n, self.calls // n
        took = self.round_s * SHARE[text] / sum(
            SHARE[q] for q in self.queries)
        if n > 1 and pos < 2:       # the first gives, the second takes
            took += (1 if pos else -1) * 1e-3 * ((rnd * 7) % 5 + 1)
        if self.stall == self.calls:
            took += 0.12
        self.calls += 1
        self.clock.now += took
        return None, {}


def window(streams, round_s, seconds, stall=None, at_most=None,
           offsets=None):
    records = []
    for i, (queries, length) in enumerate(zip(streams, round_s)):
        clock = FakeClock()

        def sleep(s, clock=clock):
            clock.now += s

        pb.stream_loop(i, FakeClient(clock, queries, length, stall), queries,
                       {q: q for q in queries}, seconds, FakeGate(clock),
                       pb.no_span, records, at_most, clock=clock,
                       offset=(offsets or [0.0] * len(streams))[i],
                       sleep=sleep)
    return records


@pytest.mark.parametrize("round_s,rounds", [
    (8.40, 6), (8.49, 6), (8.51, 5), (8.60, 5), (10.19, 5), (10.21, 4)])
def test_a_window_is_whole_rounds_and_the_rate_does_not_see_the_count(
        round_s, rounds):
    records = window([["q3", "q7"]], [round_s], 51.0)
    assert [r["query"] for r in records] == ["q3", "q7"] * rounds
    assert [r["round"] for r in records] == [
        i // 2 for i in range(2 * rounds)]
    values = pb.window_values(records, ROWS)
    # it ends at or before --seconds, and the rate is that of one round:
    # rows and seconds grow together
    assert values["window_s"] == pytest.approx(rounds * round_s)
    assert values["window_s"] <= 51.0
    assert values["fact_rows_per_s"] * round_s / (2 * ROWS) \
        == pytest.approx(1.0, abs=1e-3)
    # nearest rank over all 2k queries: p50 is rank k, the slowest of the
    # cheap template; p95 is rank 2k, the slowest query (k <= 9)
    ranked = sorted(values["latencies"])
    cheap = sorted(r["t_done"] - r["t_submit"] for r in records
                   if r["query"] == "q3")
    assert values["query_s.p50"] == ranked[rounds - 1] == cheap[-1]
    assert values["query_s.p95"] == ranked[2 * rounds - 1]


@pytest.mark.parametrize("round_s,rounds", [
    (3.0, 5), (8.40, 5), (8.49, 5), (8.60, 5), (10.21, 4), (60.0, 1)])
def test_the_traffic_files_cap_holds_the_count_when_rounds_get_shorter(
        round_s, rounds):
    """power2.json's `rounds_at_most`: the cell sits 0.4 % from the flip
    between five rounds and six, and a change that small must not alter
    the sample; a slower engine still loses whole rounds to --seconds."""
    cap = pb.spec.cell(SESSION)["traffic"]["rounds_at_most"]
    assert cap == 5
    records = window([["q3", "q7"]], [round_s], 51.0, at_most=cap)
    assert [r["query"] for r in records] == ["q3", "q7"] * rounds
    assert pb.window_values(records, ROWS)["fact_rows_per_s"] * round_s \
        / (2 * ROWS) == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("round_s,seconds", [
    (8.6, 0.0), (8.6, 8.0), (60.0, 51.0), (200.0, 51.0)])
def test_the_first_round_always_starts_and_is_finished(round_s, seconds):
    records = window([["q3", "q7"]], [round_s], seconds)
    assert [r["query"] for r in records] == ["q3", "q7"]
    assert pb.window_values(records, ROWS)["window_s"] \
        == pytest.approx(round_s)


@pytest.mark.parametrize("round_s", [(8.49, 12.7), (8.51, 12.8),
                                     (10.3, 17.1), (3.0, 30.0)])
def test_two_streams_of_unequal_lists_each_keep_whole_rounds(round_s):
    streams = [["q3", "q7"], ["q7", "q3", "q3"]]
    records = window(streams, round_s, 51.0)
    for i, (queries, length) in enumerate(zip(streams, round_s)):
        mine = [r for r in records if r["stream"] == i]
        k = len(mine) // len(queries)
        assert [r["query"] for r in mine] == queries * k
        # the rule's own arithmetic: the last round began with room for
        # one more of its length, the next would not have had it
        assert k == 1 or k * length < 51.0
        assert (k + 1) * length >= 51.0
        for q in set(queries):
            assert sum(r["query"] == q for r in mine) == k * queries.count(q)
    # a window over both streams: first submit to last completion
    values = pb.window_values(records, ROWS)
    assert values["window_s"] == pytest.approx(max(
        (len([r for r in records if r["stream"] == i]) // len(q)) * length
        for i, (q, length) in enumerate(zip(streams, round_s))))
    assert values["fact_rows_per_s"] == pytest.approx(
        ROWS * len(records) / values["window_s"])


@pytest.mark.parametrize("offset,rounds", [
    (0.0, 3), (1.25, 3), (13.9, 3), (14.1, 2), (40.0, 1), (60.0, 1)])
def test_a_stream_that_arrives_later_starts_later_and_the_window_does_not(
        offset, rounds):
    """tenants2.json's `start_offsets_s`: the second tenant's first
    statement comes that long after the window opened. The window's
    clock is the first stream's; the late stream's rounds are whole, its
    first always runs, and the time it arrived late counts against its
    further rounds as any other time passed does."""
    streams = [["q3", "q7"], ["q3", "q7"]]
    records = window(streams, [12.3, 12.3], 51.0, at_most=3,
                     offsets=[0.0, offset])
    first = [r for r in records if r["stream"] == 0]
    late = [r for r in records if r["stream"] == 1]
    assert [r["query"] for r in first] == ["q3", "q7"] * 3
    assert [r["query"] for r in late] == ["q3", "q7"] * rounds
    assert first[0]["t_submit"] == 1000.0
    assert late[0]["t_submit"] == pytest.approx(1000.0 + offset)
    values = pb.window_values(records, ROWS)
    assert values["window_s"] == pytest.approx(
        max(3 * 12.3, offset + rounds * 12.3))
    assert values["fact_rows_per_s"] == pytest.approx(
        ROWS * len(records) / values["window_s"])


@pytest.mark.parametrize("stalled", [0, 4, 5, 8])
def test_one_stalled_query_is_in_the_percentile_of_its_template(stalled):
    """Five rounds; one query waits 120 ms more for the device's answer
    (the kind met on the chip, PR 28). An even call is a q3, an odd one
    a q7. Both percentiles are over all the window's queries, so they are
    maxima here: a stalled q3 is the median of the mix, a stalled q7 its
    tail, and the rate loses the stall's share of the window."""
    calm = pb.window_values(window([["q3", "q7"]], [8.53], 51.0), ROWS)
    records = window([["q3", "q7"]], [8.53], 51.0, stall=stalled)
    values = pb.window_values(records, ROWS)
    assert len(records) == 10
    assert values["query_s.p50"] in values["latencies"]
    assert values["query_s.p95"] in values["latencies"]
    q3 = stalled % 2 == 0
    assert (values["query_s.p50"] > calm["query_s.p50"] + 0.1) == q3
    assert (values["query_s.p95"] > calm["query_s.p95"] + 0.1) == (not q3)
    assert values["fact_rows_per_s"] == pytest.approx(
        calm["fact_rows_per_s"] * 42.65 / 42.77, rel=1e-4)


def test_a_query_that_failed_is_in_the_percentiles_and_not_in_the_rate():
    records = window([["q3", "q7"]], [8.6], 51.0)
    records[3]["error"] = "RuntimeError: planted"
    values = pb.window_values(records, ROWS)
    assert len(values["latencies"]) == 10
    assert values["fact_rows_per_s"] == pytest.approx(ROWS * 9 / 43.0)


class ThreadClient:
    """Says which thread ran what."""

    def __init__(self, log):
        self.log = log

    def run(self, text, annotate):
        self.log.append((threading.current_thread().name, text))
        return None, {}


def test_the_first_stream_runs_in_the_callers_thread_the_others_in_theirs():
    streams = [["q3", "q7"], ["q7", "q3", "q3"]]
    log = []
    records = pb.run_window([ThreadClient(log), ThreadClient(log)], streams,
                            {"q3": "q3", "q7": "q7"}, 0.0, None, pb.no_span)
    names = {0: threading.current_thread().name, 1: "pb-stream-1"}
    assert sorted(log) == sorted(
        (names[r["stream"]], r["query"]) for r in records)
    assert len(records) == 5       # seconds = 0: the first round, whole
    assert [r["query"] for r in records if r["stream"] == 1] == streams[1]


def test_run_window_hands_each_stream_its_offset_on_the_real_clock():
    streams = [["q3"], ["q3"]]
    records = pb.run_window([ThreadClient([]), ThreadClient([])], streams,
                            {"q3": "q3"}, 0.0, None, pb.no_span,
                            offsets=[0.0, 0.2])
    by = {r["stream"]: r for r in records}
    assert 0.2 <= by[1]["t_submit"] - by[0]["t_submit"] < 0.3


def test_setup_s_leaves_out_the_wait_for_the_generator():
    marks = {"backend up": 11.0, "generator wait": 13.5, "tables made": 24.5,
             "session up": 25.1, "warm": 55.0, "window opens": 55.2}
    assert pb.setup_seconds(marks) == pytest.approx(41.7)


def test_a_planted_generator_wait_is_reported_and_not_counted(monkeypatch):
    """The whole run at the rehearsal's scale, with a generator that
    sleeps: the wait is in `set_up`, `setup_s` is short of the window's
    start by it, and the window the run reports is whole rounds."""
    generate = gen.generate

    def slow(config, seed, scale):
        time.sleep(2.0)
        return generate(config, seed, scale)

    monkeypatch.setattr(gen, "generate", slow)
    args = argparse.Namespace(workload=SESSION, seed=2 ** 31 + 28,
                              seconds=2.5, trace=0, rehearse=True)
    out = pb.run(args)
    marks = out["set_up"]
    assert out["correct"] is True and out["metrics"] == {}
    # the CPU's backend is up at once, so all of the sleep is waited for
    assert marks["generator wait"] >= 1.5
    assert marks["generator wait"] == pytest.approx(
        marks["tables made"] - marks["backend up"], abs=0.05)
    assert pb.setup_seconds(marks) == pytest.approx(
        marks["window opens"] - marks["generator wait"])
    assert pb.setup_seconds(marks) < marks["window opens"] - 1.5
    w = out["window"]
    assert w["queries"]["q3"] == w["queries"]["q7"] == w["rounds"][0]
    assert out["attempted"] == 2 * w["rounds"][0]
    assert list(out)[-1] == "compared"
