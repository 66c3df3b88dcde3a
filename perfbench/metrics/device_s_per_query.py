"""Device busy seconds per completed query of the window, from the
trace: the device's side of a query with the host taken out, steadier
than the host clock's latencies."""

LAYER = "device"
SOURCE = "device_trace"
MOVES = "fact_rows_per_s"
UNIT = "s"


def read(run):
    trace = run["trace"]
    done = sum(r["error"] is None for r in run["records"])
    if not trace or not trace["busy_s"] or not done:
        return None
    return trace["busy_s"] / done
