"""Whole-query programs launched per query of the window. 1 means no
replay of the capacity ladder; each launch beyond it is an undersized
program run and thrown away; 0 where every plan of the window ran on
another tier (`stage_launches_per_query` counts those launches)."""

LAYER = "whole-query program"
SOURCE = "program_counter"
MOVES = "fact_rows_per_s"
UNIT = "count"


def read(run):
    b = run["before"]["counters"]["by_kind"].get("whole_query", 0)
    a = run["after"]["counters"]["by_kind"].get("whole_query", 0)
    done = sum(r["error"] is None for r in run["records"])
    return (a - b) / done if done else None
