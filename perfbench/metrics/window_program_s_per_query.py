"""Seconds a query spends in whole-query programs that hold a window
operator and whose result stands: the `whole_query.attempt` spans
(physical/whole_query.py: lowering to the verdict's blocking read) that
are not `discarded` and whose `window_members` is over 0, over the
queries. A program from before the window had a lowering says no
`window_members`, and the line leaves the metric out."""

from perfbench import spans

LAYER = "whole-query program"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "s"


def _holds_a_window(span):
    args = span.get("args", {})
    return args.get("window_members", 0) > 0 and not args.get("discarded")


def read(run):
    return spans.per_query(run, spans.seconds(
        spans.in_window(run), ("whole_query.attempt",), _holds_a_window))
