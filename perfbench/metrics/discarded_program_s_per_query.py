"""Seconds a query spends in whole-query programs whose result is thrown
away: the attempts whose verdict bumped a join capacity or tripped a
dense-probe guard, so that the program was built and run again. Each
`whole_query.attempt` span (physical/whole_query.py) runs from the
lowering to the verdict's blocking read and says `discarded` when it
ends. 0 when every program's first attempt stood, and where the window
ran no whole-query program at all; nothing only where the program
keeps no spans to read."""

from perfbench import spans

LAYER = "whole-query program"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "s"


def read(run):
    found = spans.in_window(run)
    if found is None:
        return None
    thrown = spans.seconds(found, ("whole_query.attempt",),
                           lambda s: s.get("args", {}).get("discarded"))
    return spans.per_query(run, thrown or 0.0)
