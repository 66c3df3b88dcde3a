"""Output slots a query's semi and anti joins allocate in the whole-query
programs whose result stands: the `setop_slots` of the window's
`whole_query.attempt` spans that are not `discarded`, summed, over the
queries: the join's capacity, which a join that decides existence sizes
to the probe rows it may keep (one slot a row) and one that expands to
its matches. A program from before the attempt span said `setop_slots`
has nothing to read, and the line leaves the metric out."""

from perfbench import spans

LAYER = "whole-query program"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "slots"


def read(run):
    found = [s["args"]["setop_slots"] for s in spans.in_window(run) or ()
             if s["name"] == "whole_query.attempt"
             and "setop_slots" in s.get("args", {})
             and not s["args"].get("discarded")]
    return spans.per_query(run, float(sum(found))) if found else None
