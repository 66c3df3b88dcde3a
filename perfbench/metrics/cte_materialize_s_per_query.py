"""Seconds a query spends before its outer plan exists: the
`cte.materialize` spans (api/session.py `_materialize_ctes`: a CTE's
body executed, collected to Arrow and spliced back in as an in-memory
relation, all inside `session.sql()`), over the queries. Here it is
q47's `v1`, and q47 is the cell's `query_s.p95`. A program without the
span has nothing to read."""

from perfbench import spans

LAYER = "entry and plan"
SOURCE = "program_span"
MOVES = "query_s.p95"
UNIT = "s"


def read(run):
    return spans.per_query(run, spans.seconds(
        spans.in_window(run), ("cte.materialize",)))
