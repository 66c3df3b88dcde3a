"""Programs built or compiled inside the window: new entries of the
KernelCache plus misses of the XLA disk cache. A warm window has none."""

LAYER = "compile"
SOURCE = "program_counter"
MOVES = "query_s.p95"
UNIT = "count"


def read(run):
    b, a = run["before"]["counters"], run["after"]["counters"]
    return float(sum(a[k] - b[k] for k in ("kernel_cache.misses",
                                           "compile.disk_miss")))
