"""Seconds a statement holds its slot: the `serve.execute` span of
`serve/service.QueryService.collect` (grant to release: the tenant's
`toArrow()`), over the queries. Against `device_s_per_query` it is the
wait behind the other tenants' programs on the one device queue, and
the host's part. A program without the span has nothing to read."""

from perfbench import spans

LAYER = "serving"
SOURCE = "program_span"
MOVES = "query_s.p95"
UNIT = "s"


def read(run):
    return spans.per_query(
        run, spans.seconds(spans.in_window(run), ("serve.execute",)))
