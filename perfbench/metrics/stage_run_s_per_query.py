"""Seconds a query spends running stages of the stage tier: the
`stage.run` spans (exec/scheduler.py: one a stage, from its root's
`execute` to its materialised partitions; `stage`, `operators`,
`launches`, `tiles`, `rows_out`), summed, over the queries. A stage's
exchange runs inside it, so `host_shuffle_ms` is a part of this. A plan
on the whole tier runs no stage, and a program without the span has
nothing to read."""

from perfbench import spans

LAYER = "stage tier"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "s"


def read(run):
    return spans.per_query(run, spans.seconds(
        spans.in_window(run), ("stage.run",)))
