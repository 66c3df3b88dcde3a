"""The three readers of why the device waits (PR 39) —
`device_gap_s_per_query`, `device_syncs_per_query`,
`gc_pause_ms_per_query` — on a planted window, silent on a program
without the engine's hooks; and the cells' own query shapes run on small
tables with every read of a device array's value fenced to the one door,
`utils/device_memo.device_read`."""

import os
import sys
import traceback
import weakref

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import run as pb, spec  # noqa: E402

READERS = ("device_gap_s_per_query", "device_syncs_per_query",
           "gc_pause_ms_per_query")
# two queries in a window from 100 s to 140 s
RECORDS = [{"t_submit": 100.0, "t_done": 120.0, "error": None},
           {"t_submit": 120.5, "t_done": 140.0, "error": None}]


def _span(name, cat, ts, dur_ms, **args):
    return {"name": name, "cat": cat, "ts": ts, "dur_ms": dur_ms,
            "thread": "t", **({"args": args} if args else {})}


SPANS = [
    _span("device.gap", "gap", 90.0, 12000.0),     # set-up into the window
    _span("whole_query.verdict", "sync", 95.0, 10.0),
    _span("py.gc", "gc", 96.0, 50.0, generation=2, collected=1),
    _span("whole_query.verdict", "sync", 110.0, 300.0),
    _span("device.gap", "gap", 110.3, 700.0),
    _span("py.gc", "gc", 110.5, 12.0, generation=2, collected=4),
    _span("collect.d2h", "sync", 119.0, 50.0),
    _span("whole_query.verdict", "sync", 130.0, 200.0),
    _span("dense.range", "sync", 131.0, 1.0),
    _span("py.gc", "gc", 132.0, 3.0, generation=0, collected=9),
    _span("collect.d2h", "sync", 139.0, 40.0),
    _span("device.gap", "gap", 139.5, 4000.0),     # open past the window
]
EXPECTED = {
    "device_gap_s_per_query": (2.0 + 0.7 + 0.5) / 2,
    "device_syncs_per_query": 5 / 2,
    "gc_pause_ms_per_query": (12.0 + 3.0) / 2,
}


def _plant(monkeypatch, spans):
    import spark_tpu.obs.tracing as tracing

    monkeypatch.setattr(
        tracing, "recorded_spans",
        lambda t_from, t_to: [s for s in spans if t_from <= s["ts"] < t_to])


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    assert spec.metric_reader(name).read({"records": RECORDS}) \
        == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_zero_where_the_engine_saw_nothing(name, monkeypatch):
    _plant(monkeypatch, [])
    assert spec.metric_reader(name).read({"records": RECORDS}) == 0.0
    assert spec.metric_reader(name).read({"records": []}) is None


@pytest.mark.parametrize("name,module,attr", [
    ("device_gap_s_per_query", "spark_tpu.obs.tracing", "DEVICE"),
    ("device_syncs_per_query", "spark_tpu.utils.device_memo",
     "device_read"),
    ("gc_pause_ms_per_query", "spark_tpu.obs.tracing", "GC_SPAN")])
def test_reader_is_silent_on_a_program_without_the_hook(name, module, attr,
                                                         monkeypatch):
    """The parent commit of PR 39 has spans but none of these hooks: its
    traced run leaves the metric out, and does not raise."""
    import importlib

    _plant(monkeypatch, SPANS)
    monkeypatch.delattr(importlib.import_module(module), attr)
    assert spec.metric_reader(name).read({"records": RECORDS}) is None


def test_failed_queries_do_not_count(monkeypatch):
    _plant(monkeypatch, SPANS)
    records = [dict(RECORDS[0]), dict(RECORDS[1], error="Boom: no")]
    assert spec.metric_reader("device_syncs_per_query").read(
        {"records": records}) == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# the fence: no device array's value is read outside device_read
# ---------------------------------------------------------------------------

@pytest.fixture
def fence(monkeypatch):
    """Every read of a device array's value (`ArrayImpl._value`, and the
    numpy conversions that the CPU backend serves by the buffer protocol
    without it) outside `device_read` is noted with the engine's frames.
    An array the door has read is host data afterwards: on a TPU it keeps
    its copy (`_npy_value`); on the CPU the copy is a view, so the fence
    remembers it."""
    import jax
    import numpy
    from jax._src.array import ArrayImpl

    value = ArrayImpl._value
    read = {}
    outside = []

    def check(a):
        r = read.get(id(a))
        if a._npy_value is not None or (r is not None and r() is a):
            return
        stack = traceback.extract_stack()[:-2]
        if any(f.name == "device_read" for f in stack):
            read[id(a)] = weakref.ref(a)
            return
        outside.append(" <- ".join(
            f"{f.filename.rsplit('/spark_tpu/', 1)[-1]}:{f.lineno}"
            for f in stack[::-1] if "/spark_tpu/" in f.filename)
            or "".join(traceback.format_list(stack[-3:])))

    def guarded(self):
        check(self)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(guarded))
    for name in ("asarray", "array", "asanyarray", "ascontiguousarray"):
        real = getattr(numpy, name)

        def conv(a, *args, _real=real, **kw):
            if isinstance(a, jax.Array):
                check(a)
            return _real(a, *args, **kw)

        monkeypatch.setattr(numpy, name, conv)
    return outside


# q3 q7 (whole-query star joins), q89 q47 (windows, a CTE materialised),
# q28 q88 (the stage tier: dense joins, host exchanges)
CELLS = {"tpcds_sf10_session.power2": 2.0,
         "tpcds_sf10_window.dev2": None,
         "tpcds_sf10_onerow.onerow2": None}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_the_cells_queries_read_the_device_through_one_door(
        workload, fence, monkeypatch):
    windows, kept = [], []
    real = pb.run_window

    def window(*a, **k):
        windows.append(real(*a, **k))
        return windows[-1]

    monkeypatch.setattr(pb, "run_window", window)
    args = pb.argparse.Namespace(workload=workload, seed=2 ** 31 + 39,
                                 seconds=2.0, trace=0, rehearse=True)
    out = pb.run(args, break_path=lambda e, s, t: kept.append(s))
    assert out["correct"] is True, out["compared"]
    assert fence == [], "\n".join(sorted(set(fence)))
    run = {"records": windows[0]}
    syncs = spec.metric_reader("device_syncs_per_query").read(run)
    # power2's whole-query execution: the verdict and collect's read
    want = CELLS[workload]
    assert syncs == want if want is not None else syncs >= 2
    gap = spec.metric_reader("device_gap_s_per_query").read(run)
    t0 = min(r["t_submit"] for r in windows[0])
    t1 = max(r["t_done"] for r in windows[0])
    assert 0 < gap <= (t1 - t0) / len(windows[0])
    assert spec.metric_reader("gc_pause_ms_per_query").read(run) >= 0
