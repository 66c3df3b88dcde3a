"""Statements the server turned away inside the window: the counters
`serve.rejected_full` + `serve.rejected_timeout` (`serve/service.py`:
the pool's queue was full, or the wait for a slot ran out) +
`endpoint.auth_refused` (`connect/sql_endpoint.py`: a connection
without the token), summed over the server's and every tenant's session,
from the last note the entry took before the window's first submit to
the first it took after the last completion (`entries/endpoint.py`,
`NOTES`). A run through another door has no note, and nothing to
read."""

LAYER = "serving"
SOURCE = "program_counter"
MOVES = "fact_rows_per_s"
UNIT = "count"

SHED = ("serve.rejected_full", "serve.rejected_timeout",
        "endpoint.auth_refused")


def read(run):
    from perfbench.entries import endpoint

    records = run["records"]
    if not records:
        return None
    t_first = min(r["t_submit"] for r in records)
    t_last = max(r["t_done"] for r in records)
    before = [c for t, c in endpoint.NOTES if t <= t_first]
    after = [c for t, c in endpoint.NOTES if t >= t_last]
    if not before or not after:
        return None
    return float(sum(after[0][k] - before[-1][k] for k in SHED))
