"""The five readers of the engine's spans (PR 25): each on a planted
window and span list, each silent where nothing is recorded and where the
program has no `recorded_spans`, and all five on a real query."""

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
    assert spec.metric_reader(name).read({"records": RECORDS}) is None
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
        s.sql(text).toArrow()                       # the set-up
        records = []
        for _ in range(2):
            rec = {"t_submit": time.perf_counter(), "error": None}
            s.sql(text).toArrow()
            rec["t_done"] = time.perf_counter()
            records.append(rec)
        got = {name: spec.metric_reader(name).read({"records": records})
               for name in READERS}
    finally:
        s.stop()
    window = records[-1]["t_done"] - records[0]["t_submit"]
    assert 0 < got["discarded_program_s_per_query"] < window / 2
    assert 0 < got["dispatch_ms"] < 1000 * window / 2
    assert 0 < got["collect_ms"] < 1000 * window / 2
    assert got["setup_h2d_s"] > 0 and got["setup_program_load_s"] > 0
