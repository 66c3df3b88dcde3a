"""Seconds a query spends in whole-query programs that hold a set
operation's join and whose result stands: the `whole_query.attempt`
spans (physical/whole_query.py: lowering to the verdict's blocking read)
that are not `discarded` and whose `setop_members` (the program's semi
and anti joins, as INTERSECT and EXCEPT are rewritten) is over 0, over
the queries. A program from before the attempt span said
`setop_members` has nothing to read, and the line leaves the metric
out."""

from perfbench import spans

LAYER = "whole-query program"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "s"


def _holds_a_set_operation(span):
    args = span.get("args", {})
    return args.get("setop_members", 0) > 0 and not args.get("discarded")


def read(run):
    return spans.per_query(run, spans.seconds(
        spans.in_window(run), ("whole_query.attempt",),
        _holds_a_set_operation))
