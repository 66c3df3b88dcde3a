"""Host time from SQL text to a physical plan with its tier chosen: the
mean over the window's queries of every phase of
`QueryExecution.phase_times` but `execution`, in milliseconds. Only an
entry that hands the DataFrame back can read it (`entries/session.py`
and what is built on it). A query that plans twice counts its outer
plan alone: `session.sql()` plans, runs and splices in q47's CTE `v1`
before the DataFrame and its `QueryExecution` exist, so v1's parse is
in no phase, its analysis, optimisation and planning are inside
`cte_materialize_s_per_query`'s span, and what is summed here for q47
is the analysis, optimisation and planning of the self-join over the
spliced relation."""

LAYER = "entry and plan"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    times = [sum(v for k, v in r["info"]["phase_times"].items()
                 if k != "execution")
             for r in run["records"] if r["info"].get("phase_times")]
    return 1000.0 * sum(times) / len(times) if times else None
