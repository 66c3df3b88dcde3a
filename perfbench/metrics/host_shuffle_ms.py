"""Host milliseconds a query spends in exchanges between stages: the
`shuffle.host` spans (physical/exchange.py over exec/shuffle.py: one an
exchange, its input partitions brought to the host, sliced by partition
and rebuilt as device batches; `bytes_d2h`, `bytes_h2d`, `partitions`),
summed, over the queries. A program without the span has nothing to
read."""

from perfbench import spans

LAYER = "host shuffle"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    per = spans.per_query(run, spans.seconds(
        spans.in_window(run), ("shuffle.host",)))
    return None if per is None else 1000.0 * per
