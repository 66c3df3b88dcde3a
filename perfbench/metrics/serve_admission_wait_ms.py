"""Host milliseconds a statement waits for its slot: the
`serve.admission` span of `serve/service.QueryService.collect` (submit
to `serve/pools.FairScheduler` until the grant), over the queries. With
fewer tenants than `spark.tpu.serve.maxConcurrent` it is the price of
the bookkeeping; once tenants outnumber slots it is the queue. A program
without the span has nothing to read."""

from perfbench import spans

LAYER = "serving"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    per = spans.per_query(
        run, spans.seconds(spans.in_window(run), ("serve.admission",)))
    return None if per is None else 1000.0 * per
