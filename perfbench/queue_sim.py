#!/usr/bin/env python3
"""Closed-loop streams on one device queue, reckoned: why a traffic file
starts a stream late, and where a cell's latencies move by steps.

    python3 perfbench/queue_sim.py perfbench/traffic/tenants2.json

A statement is two programs (the first discarded, the second final) with
the host between them: submit to the first enqueue, verdict to the second
enqueue, last program's end to the answer in the client's hands. The
device runs programs in the order they were enqueued, one at a time. Two
streams that enqueue within some tens of milliseconds of each other fall
into one order or the other, and in a closed loop the order stays: a
cell's `query_s.p50` then has two levels (tenants2 before PR 36: 4.53 and
4.76 s). The tool draws the host's times from their measured ranges, runs
the window many times and prints how far the end-to-end numbers spread,
for the file's `start_offsets_s` and for a scan of others: an offset is
sound where the spread is the host's own (about 1 %) and its neighbours
read the same.

`PROGRAMS` and `HOST` are tenants2's, measured on the v5e (PERF.md §5,
§6, PR 34 and 36): a PR that moves a program's or the host's time by
some tenths of a second should put its numbers here first and see which
order the cell lands in. It is no measurement: nothing it prints is a
device's number.
"""

from __future__ import annotations

import heapq
import json
import random
import statistics
import sys

# device seconds of a statement's two programs (discarded, final)
PROGRAMS = {"q3": (0.648, 1.2466), "q7": (0.9251, 2.569)}
# host seconds, (least, most): submit to first enqueue, verdict to second
# enqueue, last program's end to the answer (by query)
HOST = {"submit": (0.02, 0.05), "between": (0.008, 0.035),
        "answer": {"q3": (0.04, 0.06), "q7": (0.58, 0.68)}}


def percentile(values: list, p: float) -> float:
    v = sorted(values)
    return v[min(len(v) - 1, max(0, -(-len(v) * p // 100) - 1))]


def window(streams, rounds, offsets, rng, programs=PROGRAMS, host=HOST):
    """One window: the records (stream, query, submit, seconds) and the
    three end-to-end numbers, the rate in queries a second."""
    events, order, free = [], 0, 0.0
    at = [{"i": 0, "round": 0, "submit": 0.0} for _ in streams]
    records = []

    def push(t, stream, what):
        nonlocal order
        heapq.heappush(events, (t, order, stream, what))
        order += 1

    for s, offset in enumerate(offsets):
        push(offset, s, "submit")
    while events:
        t, _, s, what = heapq.heappop(events)
        q = streams[s][at[s]["i"]]
        if what == "submit":
            at[s]["submit"] = t
            push(t + rng.uniform(*host["submit"]), s, "first")
        elif what == "first":
            free = max(free, t) + programs[q][0]
            push(free + rng.uniform(*host["between"]), s, "second")
        elif what == "second":
            free = max(free, t) + programs[q][1]
            push(free + rng.uniform(*host["answer"][q]), s, "answer")
        else:
            records.append((s, q, at[s]["submit"], t - at[s]["submit"]))
            at[s]["i"] += 1
            if at[s]["i"] == len(streams[s]):
                at[s]["i"], at[s]["round"] = 0, at[s]["round"] + 1
            if at[s]["round"] < rounds:
                push(t, s, "submit")
    latencies = [r[3] for r in records]
    seconds = max(r[2] + r[3] for r in records) - min(r[2] for r in records)
    return records, {"query_s.p50": percentile(latencies, 50),
                     "query_s.p95": percentile(latencies, 95),
                     "queries_per_s": len(records) / seconds}


def spread(streams, rounds, offsets, runs=200, **kw) -> dict:
    """Median and whole range over the median, of each number, over
    `runs` windows that differ in the host's draws alone."""
    out = [window(streams, rounds, offsets, random.Random(i), **kw)[1]
           for i in range(runs)]
    return {name: (statistics.median(v := [o[name] for o in out]),
                   (max(v) - min(v)) / statistics.median(v))
            for name in out[0]}


def main(argv) -> int:
    with open(argv[1]) as f:
        traffic = json.load(f)
    streams, rounds = traffic["streams"], traffic["rounds_at_most"]
    mine = traffic.get("start_offsets_s", [0.0] * len(streams))

    def line(offsets):
        return "; ".join(f"{n} {m:.3f} +-{100 * r / 2:.2f} %" for n, (m, r)
                         in spread(streams, rounds, offsets).items())

    print(f"{argv[1]} as written, offsets {mine}: {line(mine)}")
    if len(streams) == 2:
        for tenth in range(0, 31, 2):
            print(f"  second stream {tenth / 10:.1f} s late: "
                  f"{line([0.0, tenth / 10])}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
