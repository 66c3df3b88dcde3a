"""Seconds a query of the window leaves the device with nothing to run,
as the engine itself sees it: the `device.gap` spans of
`spark_tpu/obs/tracing.DEVICE` (a gap opens when a blocking read returns
with nothing launched since it began, and the next KernelCache launch
closes it), clipped to the window, over the queries. A gap open when the
spans are read counts up to then. Blind to device work outside the
KernelCache (eager `jnp` calls), to a launch's own dispatch and to the
device's wake-up: `device_idle_pct` reads the trace's idle beside it. A
program without the account has nothing to read."""

from perfbench import spans

LAYER = "device"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "s"


def read(run):
    try:
        from spark_tpu.obs.tracing import DEVICE  # noqa: F401
    except ImportError:
        return None
    records = run["records"]
    if not records:
        return None
    t0 = min(r["t_submit"] for r in records)
    t1 = max(r["t_done"] for r in records)
    total = sum(max(0.0, min(s["ts"] + s["dur_ms"] / 1000.0, t1)
                    - max(s["ts"], t0))
                for s in spans.recorded(float("-inf"), t1) or ()
                if s["name"] == "device.gap")
    return spans.per_query(run, total)
