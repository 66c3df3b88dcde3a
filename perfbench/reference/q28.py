"""TPC-DS q28: six buckets of store_sales by quantity, each the average,
the count and the distinct count of the list prices of the rows that
pass one of three decimal ranges; one row, the buckets side by side."""

import numpy as np

from perfbench.reference import valid

READS = {"store_sales": ["ss_quantity", "ss_list_price", "ss_coupon_amt",
                         "ss_wholesale_cost"]}
KEY_COLUMNS = ()                       # one row: nothing tells rows apart
AVG_COLUMNS = (0, 3, 6, 9, 12, 15)     # B<n>_LP: a division, not exact

# (quantity from, list price from, coupon from, wholesale cost from): the
# qualification substitutions; the ranges are 5 quantities, 10, 1000 and
# 20 dollars wide, ends included
BUCKETS = ((0, 8, 459, 57), (6, 90, 2323, 31), (11, 142, 12214, 79),
           (16, 135, 6071, 38), (21, 122, 836, 17), (26, 154, 7326, 7))


def _between(col, lo, hi) -> np.ndarray:
    """`col BETWEEN lo AND hi`, true: a NULL is not."""
    unit = 10 ** (col.scale or 0)
    return valid(col) & (col.values >= lo * unit) & (col.values <= hi * unit)


def run(t, arith):
    ss = t["store_sales"]
    qty, price = ss["ss_quantity"], ss["ss_list_price"]
    coupon, cost = ss["ss_coupon_amt"], ss["ss_wholesale_cost"]
    row = []
    for q, p, c, w in BUCKETS:
        keep = _between(qty, q, q + (5 if q == 0 else 4)) & (
            _between(price, p, p + 10) | _between(coupon, c, c + 1000)
            | _between(cost, w, w + 20))
        rows = np.flatnonzero(keep)
        prices = price.take(rows)
        zeros = np.zeros(len(rows), np.int64)
        priced = prices.values[valid(prices)]
        row += [arith.avg_decimal(zeros, prices, 1)[0], int(len(priced)),
                int(len(np.unique(priced)))]
    return [tuple(row)]


def order_key(row):
    """No ORDER BY: one row."""
    return ()
