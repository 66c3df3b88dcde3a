"""Share of the window in which no operation ran on the device: 1 minus
the union of the device-op intervals over the window, from the trace."""

LAYER = "device"
SOURCE = "device_trace"
MOVES = "fact_rows_per_s"
UNIT = "%"


def read(run):
    trace = run["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
