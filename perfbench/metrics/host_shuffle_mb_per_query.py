"""Megabytes a query moves through the host between stages: what the
`shuffle.host` spans carry as `bytes_d2h` (the exchanged planes brought
to the host) and `bytes_h2d` (the partitions rebuilt on the device),
both directions added, over the queries. A program without the span has
nothing to read."""

from perfbench import spans

LAYER = "host shuffle"
SOURCE = "program_span"
MOVES = "fact_rows_per_s"
UNIT = "MB"


def read(run):
    found = [s.get("args", {}) for s in spans.in_window(run) or ()
             if s["name"] == "shuffle.host"]
    if not found:
        return None
    moved = sum(int(a.get("bytes_d2h", 0)) + int(a.get("bytes_h2d", 0))
                for a in found)
    return spans.per_query(run, moved / 1e6)
