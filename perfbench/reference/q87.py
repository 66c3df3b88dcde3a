"""TPC-DS q87: how many (last name, first name, date) triples of customers
who bought in the store on that day and in neither of the other channels,
in the twelve months from d_month_seq 1200 (the year 2000): the store's
distinct triples EXCEPT the catalog's EXCEPT the web's, NULL equal to NULL
as in any set operation (q38's triples, `q38.channel_triples`). One row:
the count."""

import numpy as np

from perfbench.reference.q38 import READS, channel_triples  # noqa: F401

KEY_COLUMNS = ()                  # one row: nothing tells rows apart


def count(t, null_equal: bool = True) -> int:
    """The query's count; `null_equal=False` is what an equality that
    takes NULL as unknown would give: a store triple with a NULL name is
    never taken away."""
    (s, sn), (c, cn), (w, wn) = channel_triples(t)
    if null_equal:
        left = np.setdiff1d(np.setdiff1d(s, c, assume_unique=True), w,
                            assume_unique=True)
        return int(len(left))
    kept = np.setdiff1d(np.setdiff1d(s[~sn], c[~cn], assume_unique=True),
                        w[~wn], assume_unique=True)
    return int(len(kept) + np.count_nonzero(sn))


def run(t, arith):
    """`arith` is not read: a count is exact in any arithmetic."""
    return [(count(t),)]


def order_key(row):
    """No ORDER BY: one row."""
    return ()
