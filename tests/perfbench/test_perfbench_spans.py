"""The five readers of the engine's spans (PR 25): each on a planted
window and span list, each silent where there is nothing to read and
where the program has no `recorded_spans`, and all five on a real query.
`discarded_program_s_per_query` is read in every cell, so where the
program keeps spans and none is a discarded attempt it reads 0."""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import spec  # noqa: E402

# two queries in a window from 100 s to 140 s; set-up before it
RECORDS = [{"t_submit": 100.0, "t_done": 120.0, "error": None},
           {"t_submit": 120.5, "t_done": 140.0, "error": None}]


def _span(name, ts, dur_ms, **args):
    return {"name": name, "cat": "x", "ts": ts, "dur_ms": dur_ms,
            "thread": "t", **({"args": args} if args else {})}


SPANS = [
    _span("ingest.h2d", 10.0, 16000.0, bytes=1, planes=1),
    _span("ingest.h2d", 30.0, 500.0, bytes=1, planes=1),
    _span("kernel.cost_capture", 40.0, 2000.0, kind="whole_query"),
    _span("kernel.first_launch", 42.0, 5000.0, kind="whole_query"),
    _span("whole_query.attempt", 50.0, 900.0, discarded=True),   # warm-up
    _span("whole_query.attempt", 100.1, 700.0, discarded=True),
    _span("whole_query.lower", 100.1, 30.0),
    _span("whole_query.launch", 100.2, 10.0),
    _span("whole_query.attempt", 100.9, 19000.0, discarded=False),
    _span("whole_query.lower", 100.9, 20.0),
    _span("whole_query.launch", 101.0, 4.0),
    _span("collect", 119.9, 90.0),
    _span("collect.d2h", 119.9, 60.0),
    _span("whole_query.attempt", 120.6, 1100.0, discarded=True),
    _span("whole_query.lower", 120.6, 25.0),
    _span("whole_query.launch", 120.7, 11.0),
    _span("whole_query.attempt", 121.8, 18000.0, discarded=False),
    _span("collect", 139.8, 110.0),
    _span("kernel.first_launch", 150.0, 7000.0, kind="later"),
]
EXPECTED = {
    "discarded_program_s_per_query": (0.7 + 1.1) / 2,
    "dispatch_ms": (30 + 10 + 20 + 4 + 25 + 11) / 2,
    "collect_ms": (90 + 110) / 2,
    "setup_h2d_s": 16.5,
    "setup_program_load_s": 7.0,
}
READERS = sorted(EXPECTED)


def _plant(monkeypatch, spans):
    import spark_tpu.obs.tracing as tracing

    monkeypatch.setattr(
        tracing, "recorded_spans",
        lambda t_from, t_to: [s for s in spans if t_from <= s["ts"] < t_to])


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    value = spec.metric_reader(name).read({"records": RECORDS})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_nothing_is_recorded(name, monkeypatch):
    _plant(monkeypatch, [])
    nothing = 0.0 if name == "discarded_program_s_per_query" else None
    assert spec.metric_reader(name).read({"records": RECORDS}) == nothing
    _plant(monkeypatch, SPANS)
    assert spec.metric_reader(name).read({"records": []}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_on_a_program_without_recorded_spans(
        name, monkeypatch):
    """The parent commit of PR 25 has no `recorded_spans`: the traced run
    of its checkout leaves the metric out and does not raise."""
    import spark_tpu.obs.tracing as tracing

    monkeypatch.delattr(tracing, "recorded_spans")
    assert spec.metric_reader(name).read({"records": RECORDS}) is None


def test_no_discarded_attempt_reads_zero(monkeypatch):
    _plant(monkeypatch, [s for s in SPANS
                         if not s.get("args", {}).get("discarded")])
    reader = spec.metric_reader("discarded_program_s_per_query")
    assert reader.read({"records": RECORDS}) == 0.0


def test_failed_queries_do_not_count(monkeypatch):
    _plant(monkeypatch, SPANS)
    records = [dict(RECORDS[0]), dict(RECORDS[1], error="Boom: no")]
    assert spec.metric_reader("collect_ms").read({"records": records}) \
        == pytest.approx(200.0)


def _discarded_holds(records, holds_first_execution):
    """The reader against the spans it sums: the `whole_query.attempt`
    spans of the records' window that say `discarded`, over the queries.
    Never under 0; over 0 only where the records hold the session's
    first execution of the text, which has no capacities to start from:
    an engine that remembers them reads 0 afterwards."""
    from spark_tpu.obs.tracing import recorded_spans

    found = recorded_spans(min(r["t_submit"] for r in records),
                           max(r["t_done"] for r in records))
    thrown = sum(s["dur_ms"] for s in found
                 if s["name"] == "whole_query.attempt"
                 and s.get("args", {}).get("discarded")) / 1000.0
    got = spec.metric_reader("discarded_program_s_per_query").read(
        {"records": records})
    assert got == pytest.approx(thrown / len(records))
    assert got >= 0
    if holds_first_execution:
        assert got > 0
    return got


# three executions of one text in one session, as the real query below:
# today's engine replays the capacity ladder in each; one that remembers
# the final capacities in-process (ROADMAP S3) discards in the first only
THREE = [{"t_submit": 100.0, "t_done": 103.0, "error": None},
         {"t_submit": 103.0, "t_done": 105.0, "error": None},
         {"t_submit": 105.0, "t_done": 107.0, "error": None}]
ENGINES = {
    "replays": [_span("whole_query.attempt", 100.1, 700.0, discarded=True),
                _span("whole_query.attempt", 100.9, 1900.0, discarded=False),
                _span("whole_query.attempt", 103.1, 600.0, discarded=True),
                _span("whole_query.attempt", 103.8, 1100.0, discarded=False),
                _span("whole_query.attempt", 105.1, 600.0, discarded=True),
                _span("whole_query.attempt", 105.8, 1100.0, discarded=False)],
    "remembers": [_span("whole_query.attempt", 100.1, 700.0, discarded=True),
                  _span("whole_query.attempt", 100.9, 1900.0,
                        discarded=False),
                  _span("whole_query.attempt", 103.1, 1100.0,
                        discarded=False),
                  _span("whole_query.attempt", 105.1, 1100.0,
                        discarded=False)]}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_what_the_real_query_holds_the_reader_to(engine, monkeypatch):
    _plant(monkeypatch, ENGINES[engine])
    assert _discarded_holds(THREE, True) == pytest.approx(
        {"replays": 1.9 / 3, "remembers": 0.7 / 3}[engine])
    assert _discarded_holds(THREE[1:], False) == pytest.approx(
        {"replays": 0.6, "remembers": 0.0}[engine])


def test_the_five_readers_on_a_real_query():
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession

    rng = np.random.default_rng(5)
    s = TpuSession("pb-spans", {"spark.sql.shuffle.partitions": 4,
                                "spark.tpu.compile.tier": "whole"})
    try:
        s.createDataFrame(pa.table({
            "k": rng.integers(0, 9, 4000), "v": rng.integers(0, 99, 4000),
        })).createOrReplaceTempView("pbs_t")
        s.createDataFrame(pa.table({
            "k": np.repeat(np.arange(9), 3), "tag": np.arange(27),
        })).createOrReplaceTempView("pbs_d")
        text = ("select t.k, sum(v) sv, count(*) n from pbs_t t join pbs_d d "
                "on t.k = d.k group by t.k order by t.k")
        records = []            # the first is the set-up, the rest the window
        for _ in range(3):
            rec = {"t_submit": time.perf_counter(), "error": None}
            s.sql(text).toArrow()
            rec["t_done"] = time.perf_counter()
            records.append(rec)
        _discarded_holds(records, True)
        thrown = _discarded_holds(records[1:], False)
        got = {name: spec.metric_reader(name).read({"records": records[1:]})
               for name in READERS}
    finally:
        s.stop()
    window = records[-1]["t_done"] - records[1]["t_submit"]
    assert got["discarded_program_s_per_query"] == thrown < window / 2
    assert 0 < got["dispatch_ms"] < 1000 * window / 2
    assert 0 < got["collect_ms"] < 1000 * window / 2
    assert got["setup_h2d_s"] > 0 and got["setup_program_load_s"] > 0
