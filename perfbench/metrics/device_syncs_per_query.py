"""Blocking device→host reads a query of the window makes: the spans of
category `sync` (`spark_tpu/utils/device_memo.device_read`, one a site:
`whole_query.verdict`, `collect.d2h`, `dense.range`, `shuffle.pull`,
...) that started in the window, over the queries. Each is a wait on
the device and a chance of a late wake-up. A program without the one
door has nothing to read."""

from perfbench import spans

LAYER = "device"
SOURCE = "program_span"
MOVES = "query_s.p95"
UNIT = "count"


def read(run):
    try:
        from spark_tpu.utils.device_memo import device_read  # noqa: F401
    except ImportError:
        return None
    found = spans.in_window(run)
    if found is None:
        return None
    return spans.per_query(run, sum(s["cat"] == "sync" for s in found))
