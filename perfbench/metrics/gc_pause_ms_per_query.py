"""Milliseconds a query of the window spends in the interpreter's
collector: the `py.gc` spans (`spark_tpu/obs/tracing`'s `gc.callbacks`
hook: every collection of the oldest generation, and any of 1 ms or
more) that started in the window, over the queries. The reference's
per-task JVM GC time. A program without the hook has nothing to read."""

from perfbench import spans

LAYER = "host interpreter"
SOURCE = "program_span"
MOVES = "query_s.p95"
UNIT = "ms"


def read(run):
    try:
        from spark_tpu.obs.tracing import GC_SPAN
    except ImportError:
        return None
    found = spans.in_window(run)
    if found is None:
        return None
    per = spans.per_query(run, sum(s["dur_ms"] for s in found
                                   if s["name"] == GC_SPAN) / 1000.0)
    return None if per is None else 1000.0 * per
