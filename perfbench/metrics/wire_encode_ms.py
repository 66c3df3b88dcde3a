"""Host milliseconds a result set takes onto the wire: the
`endpoint.encode` span of `connect/sql_endpoint.SQLEndpoint` (the Arrow
table to Python rows to one JSON line, written and flushed; `rows`,
`bytes`), over the queries. It runs under the interpreter lock, so it
is also time the other tenant's dispatch may wait. A program without the
span has nothing to read."""

from perfbench import spans

LAYER = "wire"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    per = spans.per_query(
        run, spans.seconds(spans.in_window(run), ("endpoint.encode",)))
    return None if per is None else 1000.0 * per
