"""Host milliseconds a query spends bringing its result home: the
`collect` span of `QueryExecution.to_arrow` (exec/query_execution.py),
which holds `collect.d2h` (the result planes copied to the host) and
`collect.arrow` (the Arrow table assembled from them)."""

from perfbench import spans

LAYER = "collect"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    per = spans.per_query(
        run, spans.seconds(spans.in_window(run), ("collect",)))
    return None if per is None else 1000.0 * per
