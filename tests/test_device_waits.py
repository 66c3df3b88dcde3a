"""Why the device waits, seen from inside the engine (PR 39): every
blocking device→host read is a `sync` span through one door
(`utils/device_memo.device_read`), the launch/sync account
(`obs/tracing.DeviceAccount`) turns the stretches with nothing in flight
into `device.gap` spans, the collector's pauses are `py.gc` spans, and
the `pc` arg of each `st:` annotation lays them on the profiler's
clock."""

import contextlib
import gc
import glob
import io
import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.obs import tracing as T

# four joins under one whole-query program: the verdict reads four
# `needed` scalars, the build spans and the guards
QUERY4 = ("select d1.a, sum(f.v) s from dw_fact f "
          "join dw_d1 d1 on f.k1 = d1.k1 join dw_d2 d2 on f.k2 = d2.k2 "
          "join dw_d3 d3 on f.k3 = d3.k3 join dw_d4 d4 on f.k4 = d4.k4 "
          "where d2.b < 40 and d3.c < 30 and d4.d < 20 "
          "group by d1.a order by d1.a")


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession

    s = TpuSession("device-waits", {
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.compile.tier": "whole",
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cache.result.enabled": "false",
    })
    rng = np.random.default_rng(5)
    n = 4000
    tables = {
        "dw_fact": pa.table({f"k{i}": rng.integers(0, 50, n)
                             for i in range(1, 5)}
                            | {"v": rng.integers(0, 1000, n)}),
        # two rows a key: the first join expands past its first capacity
        "dw_d1": pa.table({"k1": np.repeat(np.arange(50), 2),
                           "a": np.arange(100) % 7}),
        "dw_d2": pa.table({"k2": np.arange(50), "b": np.arange(50)}),
        "dw_d3": pa.table({"k3": np.arange(50), "c": np.arange(50)}),
        "dw_d4": pa.table({"k4": np.arange(50), "d": np.arange(50)}),
    }
    for name, t in tables.items():
        s.createDataFrame(t).createOrReplaceTempView(name)
    yield s
    s.stop()


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


@pytest.fixture
def scoped():
    """A fresh account on a fake clock, and an enabled tracer in scope."""
    clock = _Clock()
    tracer = T.Tracer(enabled=True)
    token = T.push_query("q-gap", tracer)
    yield T.DeviceAccount(clock=clock), clock, tracer
    T.pop_query(token)


def _gaps(tracer):
    return [s for s in tracer.spans_for("q-gap") if s["name"] == T.GAP_SPAN]


# ---------------------------------------------------------------------------
# the account on a fake clock
# ---------------------------------------------------------------------------

def test_a_sync_with_nothing_in_flight_opens_a_gap_the_next_launch_closes(
        scoped):
    acc, clock, tracer = scoped
    acc.launch("whole_query")
    clock.now = 1.0
    begun = acc.sync_begin()
    clock.now = 3.0
    acc.sync_end(begun, "whole_query.verdict")
    clock.now = 3.5
    acc.sync_end(acc.sync_begin(), "collect.d2h")   # the gap stays the first
    clock.now = 7.0
    acc.launch("pipeline")
    gap, = _gaps(tracer)
    assert gap["ts"] == 3.0 and gap["dur_ms"] == 4000.0
    assert gap["cat"] == "gap" and gap["thread"] == T.GAP_TRACK
    assert gap["args"] == {"after": "whole_query.verdict",
                           "before": "pipeline"}
    acc.launch("pipeline")                          # nothing open: no span
    assert len(_gaps(tracer)) == 1


def test_a_launch_during_a_sync_leaves_the_device_busy(scoped):
    acc, clock, tracer = scoped
    begun = acc.sync_begin()
    acc.launch("pipeline")           # another program queued meanwhile
    clock.now = 2.0
    acc.sync_end(begun, "join.needed")
    assert acc.gap is None
    clock.now = 5.0
    acc.launch("pipeline")
    assert _gaps(tracer) == []


def test_two_threads_a_gap_opens_only_when_every_launch_drained(scoped):
    acc, clock, tracer = scoped
    acc.launch("a")
    begun_a = acc.sync_begin()       # thread A waits on launch 1
    launched, go = threading.Event(), threading.Event()
    got = {}

    def other():
        acc.launch("b")              # thread B launches 2 and waits on it
        got["begun"] = acc.sync_begin()
        launched.set()
        go.wait(5)
        clock.now = 4.0
        acc.sync_end(got["begun"], "b.read")

    t = threading.Thread(target=other)
    t.start()
    launched.wait(5)
    clock.now = 2.0
    acc.sync_end(begun_a, "a.read")  # launch 2 may still run: no gap
    assert acc.gap is None
    go.set()
    t.join(5)
    assert acc.gap == (4.0, "b.read")
    # a launch from a thread outside any query scope closes it on the
    # tracer the last query scope was entered with
    clock.now = 6.0
    closer = threading.Thread(target=acc.launch, args=("c",))
    closer.start()
    closer.join(5)
    gap, = [T.Tracer._span_dict(s) for s in tracer.spans()
            if s[0] == T.GAP_SPAN]
    assert gap["ts"] == 4.0 and gap["dur_ms"] == 2000.0
    assert gap["args"] == {"after": "b.read", "before": "c"}


def test_a_tenants_read_leaves_the_other_tenants_program_running(scoped):
    """Tenant A reads its program's answer while tenant B's program,
    launched after A's, still runs: A's sync drains A's launch only."""
    acc, clock, tracer = scoped
    acc.launch("whole_query")                        # A's program (q-gap)
    other = T.push_query("q-other", tracer)
    try:
        acc.launch("whole_query")                    # B's, queued behind
        begun_b = acc.sync_begin()
    finally:
        T.pop_query(other)
    clock.now = 1.0
    acc.sync_end(acc.sync_begin(), "whole_query.verdict")   # A's answer
    assert acc.gap is None
    clock.now = 2.0
    acc.sync_end(begun_b, "whole_query.verdict")     # B's: nothing left
    assert acc.gap == (2.0, "whole_query.verdict")


def test_a_gap_open_when_spans_are_read_counts_up_to_then(scoped):
    acc, clock, tracer = scoped
    acc.sync_end(acc.sync_begin(), "collect.d2h")
    clock.now = 2.5
    span = acc.open_gap()
    assert span[0] == T.GAP_SPAN and span[2] == 0.0 and span[3] == 2.5
    assert span[6] == {"after": "collect.d2h", "before": ""}
    tracer._enabled = False
    assert acc.open_gap() is None    # tracing off: nothing to report


def test_recorded_spans_report_the_gap_after_a_query(session):
    """The verdict is a warm query's last wait on the device: the gap it
    opens (collect's read finds it open) is there, still open, when the
    spans are read."""
    import time

    session.sql(QUERY4).toArrow()
    t0 = time.perf_counter()
    session.sql(QUERY4).toArrow()
    spans = T.recorded_spans(t0)
    gap = [s for s in spans if s["name"] == T.GAP_SPAN][-1]
    assert gap["thread"] == T.GAP_TRACK
    assert gap["args"] == {"after": "whole_query.verdict", "before": ""}
    verdict, = [s for s in spans if s["name"] == "whole_query.verdict"]
    assert gap["ts"] >= verdict["ts"] + verdict["dur_ms"] / 1000.0


# ---------------------------------------------------------------------------
# the one door
# ---------------------------------------------------------------------------

def test_device_read_is_one_transfer_and_one_sync_span(monkeypatch):
    import jax
    import jax.numpy as jnp

    from spark_tpu.utils.device_memo import device_read

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(x) or real(x))
    a, b = jnp.arange(8, dtype=jnp.int32) * 2, jnp.ones(4, jnp.float32)
    on = T.Tracer(enabled=True)
    token = T.push_query("q-read", on)
    try:
        ha, (hb, none) = device_read("test.site", a, (b, None))
    finally:
        T.pop_query(token)
    assert len(calls) == 1
    assert isinstance(ha, np.ndarray) and ha.tolist() == list(range(0, 16, 2))
    assert hb.tolist() == [1.0] * 4 and none is None
    sync, = [s for s in on.spans_for("q-read") if s["cat"] == "sync"]
    assert sync["name"] == "test.site"
    assert sync["args"] == {"site": "test.site", "bytes": 32 + 16}
    off = T.Tracer(enabled=False)
    token = T.push_query("q-off", off)
    try:
        device_read("test.site", a)
    finally:
        T.pop_query(token)
    assert off.spans() == []


def test_trace_disabled_session_records_no_sync_span(session):
    import time

    session.conf.set("spark.tpu.trace.enabled", "false")
    try:
        t0 = time.perf_counter()
        session.sql(QUERY4).toArrow()
        assert [s for s in T.recorded_spans(t0)
                if s["cat"] in ("sync", "gap", "gc")] == []
    finally:
        session.conf.set("spark.tpu.trace.enabled", "true")


def test_a_four_join_verdict_is_one_sync_and_the_query_makes_two(
        session, monkeypatch):
    """A warm whole-query execution waits on the device twice: its
    verdict and its collect, which also counts the operators' rows."""
    import time

    import jax

    session.sql(QUERY4).toArrow()    # climbs the capacity ladder
    reads = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: reads.append(x) or real(x))
    t0 = time.perf_counter()
    df = session.sql(QUERY4)
    out = df.toArrow()
    spans = T.recorded_spans(t0)
    syncs = [s for s in spans if s["cat"] == "sync"]
    assert [s["name"] for s in syncs] == ["whole_query.verdict",
                                          "collect.d2h"]
    assert len(reads) == 2
    verdict = syncs[0]
    # four `needed` scalars and more (guards, spans) in that one read
    assert verdict["args"]["bytes"] >= 4 * 4
    attempt, = [s for s in spans if s["name"] == "whole_query.attempt"]
    assert attempt["args"]["discarded"] is False
    # no span of the same name nests inside the sync span
    assert sum(s["name"] == "whole_query.verdict" for s in spans) == 1
    # the plan's row counts come from collect's own read of the result
    root = df.query_execution.plan_graph()[0]
    assert root["rows"] == out.num_rows and root["rows_exact"]


# ---------------------------------------------------------------------------
# the collector
# ---------------------------------------------------------------------------

def test_a_full_collection_in_a_query_scope_is_one_py_gc_span():
    tracer = T.Tracer(enabled=True)
    token = T.push_query("q-gc", tracer)
    try:
        gc.collect()
    finally:
        T.pop_query(token)
    full = [s for s in tracer.spans_for("q-gc")
            if s["name"] == T.GC_SPAN and s["args"]["generation"] == 2]
    assert len(full) == 1
    assert full[0]["cat"] == "gc" and full[0]["args"]["collected"] >= 0
    assert full[0]["dur_ms"] >= 0


def test_a_young_collection_under_a_millisecond_is_no_span():
    tracer = T.Tracer(enabled=True)
    token = T.push_query("q-young", tracer)
    try:
        T._gc_callback("start", {"generation": 0})
        T._gc_callback("stop", {"generation": 0, "collected": 3})
    finally:
        T.pop_query(token)
    assert [s for s in tracer.spans_for("q-young")
            if s["name"] == T.GC_SPAN] == []


# ---------------------------------------------------------------------------
# the profiler's clock, and explain(mode="device")
# ---------------------------------------------------------------------------

def _st_events(path):
    from jax.profiler import ProfileData

    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(T.ANNOTATION_PREFIX):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return found


def test_pc_places_the_gap_between_the_st_spans_around_it(session,
                                                          tmp_path):
    """The first execution climbs the capacity ladder: the discarded
    attempt's verdict opens a gap that the next attempt's launch closes.
    Laid on the profiler's clock by the `pc` args, the gap lies between
    those two annotations."""
    import time

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    t0 = time.perf_counter()
    try:
        session.sql(QUERY4).toArrow()
    finally:
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = _st_events(path)
    offs = sorted(e[0] - float(e[2]["pc"]) * 1e9
                  for evs in found.values() for e in evs)
    off = offs[len(offs) // 2]             # one offset, give or take
    assert offs[len(offs) // 4] > off - 1e6 and offs[len(offs) // 4 * 3] \
        < off + 1e6                        # the thread's switches
    verdicts = sorted(found["st:whole_query.verdict"])
    launches = sorted(found["st:whole_query.launch"])
    assert len(verdicts) >= 2 and len(launches) == len(verdicts)
    gap = next(s for s in T.recorded_spans(t0, t1)
               if s["name"] == T.GAP_SPAN
               and s["args"]["after"] == "whole_query.verdict"
               and s["args"]["before"] == "whole_query")
    lo = gap["ts"] * 1e9 + off
    hi = lo + gap["dur_ms"] * 1e6
    slack = 1e6                 # 1 ms: the offset's own spread
    assert verdicts[0][1] - slack <= lo
    assert launches[1][0] - slack <= hi <= launches[1][1] + slack


def test_explain_device_ends_with_the_gaps(session):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        session.sql(QUERY4).explain(mode="device")
    text = buf.getvalue()
    i = text.index("device gaps the engine saw in the traced run")
    assert "the trace's idle over the same run" in text[i:]
    rows = text[i:].splitlines()[1:]
    assert rows and all(r.strip().endswith("%") for r in rows)


def test_gap_table_gives_each_instant_to_the_innermost_span():
    from spark_tpu.obs.device_profile import OUTSIDE, gap_table

    def span(name, ts, dur_s):
        return {"name": name, "ts": ts, "dur_ms": dur_s * 1000.0}

    spans = [span("collect", 10.0, 4.0), span("collect.arrow", 11.0, 1.0),
             span(T.GAP_SPAN, 9.0, 4.0), span(T.GAP_SPAN, 20.0, 2.0)]
    got = gap_table(spans, 0.0, 21.0)
    assert got == pytest.approx({OUTSIDE: 1.0 + 1.0, "collect": 2.0,
                                 "collect.arrow": 1.0})
