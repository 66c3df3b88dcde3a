"""Host milliseconds a query spends getting its programs onto the device:
`whole_query.lower` (the builder's host pass over the plan, the leaves'
cached planes, the cache key) plus `whole_query.launch` (KernelCache
lookup and the call that returns futures), summed over the query's
attempts. The device is idle for as long as the first of them lasts."""

from perfbench import spans

LAYER = "whole-query program"
SOURCE = "program_span"
MOVES = "query_s.p50"
UNIT = "ms"


def read(run):
    total = spans.seconds(spans.in_window(run),
                          ("whole_query.lower", "whole_query.launch"))
    per = spans.per_query(run, total)
    return None if per is None else 1000.0 * per
