"""Launches per query of the window that are not a whole-query or
mesh-whole program: the per-stage, per-operator and shuffle kernels of
the tiers under the whole tier (KernelCache's `launches_by_kind`, every
kind but those two). 0 while every plan of the cell runs as whole-query
programs. In `tpcds_sf10_window` it watches q47's two plans, the CTE
`v1` and the self-join of its result: it rises the day one of them
falls off that tier (a window the lowering refuses, or a self-join
under the tier's volume floor, which then runs by stages). q89 it
cannot watch there: entry `session_whole` ends such a run at set-up."""

LAYER = "whole-query program"
SOURCE = "program_counter"
MOVES = "fact_rows_per_s"
UNIT = "count"

WHOLE = ("whole_query", "mesh_whole")


def read(run):
    b = run["before"]["counters"].get("by_kind")
    a = run["after"]["counters"].get("by_kind")
    done = sum(r["error"] is None for r in run["records"])
    if a is None or b is None or not done:
        return None
    other = sum(v - b.get(k, 0) for k, v in a.items() if k not in WHOLE)
    return other / done
