"""Host time from SQL text to a physical plan with its tier chosen: the
mean over the window's queries of every phase of
`QueryExecution.phase_times` but `execution`, in milliseconds. Only an
entry that hands the DataFrame back can read it."""

LAYER = "entry and plan"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    times = [sum(v for k, v in r["info"]["phase_times"].items()
                 if k != "execution")
             for r in run["records"] if r["info"].get("phase_times")]
    return 1000.0 * sum(times) / len(times) if times else None
