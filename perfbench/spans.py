"""The engine's own spans, for the per-layer readers that read them.

The program keeps its spans in memory (`spark_tpu/obs/tracing.py`) on
`time.perf_counter()`, the clock of a record's `t_submit` and `t_done`,
and `recorded_spans(t_from, t_to)` hands out those of every tracer of the
process. A program from before that function has nothing to read: every
reader then returns None and the result line leaves its metric out.
"""

from __future__ import annotations


def recorded(t_from: float, t_to: float):
    """The spans that started in [t_from, t_to), or None where the
    program cannot say."""
    try:
        from spark_tpu.obs.tracing import recorded_spans
    except ImportError:
        return None
    return recorded_spans(t_from, t_to)


def in_window(run):
    """Spans of the window: first submit to last completion."""
    records = run["records"]
    if not records:
        return None
    return recorded(min(r["t_submit"] for r in records),
                    max(r["t_done"] for r in records))


def before_window(run):
    """Spans of the set-up: process start to the first submit."""
    records = run["records"]
    if not records:
        return None
    return recorded(float("-inf"), min(r["t_submit"] for r in records))


def seconds(spans, names, where=lambda s: True):
    """Summed duration of the spans so named, or None if there is none."""
    found = [s["dur_ms"] for s in spans or ()
             if s["name"] in names and where(s)]
    return sum(found) / 1000.0 if found else None


def per_query(run, total_s):
    """`total_s` over the queries the window completed."""
    done = sum(r["error"] is None for r in run["records"])
    return total_s / done if done and total_s is not None else None
