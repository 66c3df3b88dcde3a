"""Share of the chip's memory bandwidth that the window's queries would
need if each plane they read, and each result, crossed HBM once: the
least time at the peak over the device's busy time in the trace. The
bytes are the query's (perfbench/bytes_model.py), so the number means the
same whatever programs implement the query."""

from perfbench.bytes_model import query_bytes
from perfbench.reference import load

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "fact_rows_per_s"
UNIT = "%"


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    total = 0
    for r in run["records"]:
        if r["error"] is None:
            want = run["want"][r["query"]]
            total += query_bytes(load(r["query"]).READS, run["data"],
                                 len(want), len(want[0]) if want else 0)
    least_s = total / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"] if total else None
