"""TPC-DS q3: sales of one manufacturer's brands in November, by year."""

import numpy as np

from perfbench.reference import group, position, valid

READS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                         "ss_ext_sales_price"],
         "date_dim": ["d_date_sk", "d_year", "d_moy"],
         "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"]}
KEY_COLUMNS = (0, 1, 2)      # d_year, brand_id, brand


def run(t, arith):
    ss, d, i = t["store_sales"], t["date_dim"], t["item"]
    dpos = position(ss["ss_sold_date_sk"], d["d_date_sk"])
    ipos = position(ss["ss_item_sk"], i["i_item_sk"])
    keep = valid(ss["ss_sold_date_sk"]) & valid(ss["ss_item_sk"]) \
        & (d["d_moy"].values == 11)[dpos] \
        & (i["i_manufact_id"].values == 128)[ipos]
    rows = np.flatnonzero(keep)
    dpos, ipos = dpos[rows], ipos[rows]
    uniq, inv = group(d["d_year"].values[dpos], i["i_brand"].values[ipos],
                      i["i_brand_id"].values[ipos])
    sums = arith.sum_decimal(inv, ss["ss_ext_sales_price"].take(rows),
                             len(uniq))
    brands = i["i_brand"].pool
    out = [(int(y), int(bid), brands[b], s)
           for (y, b, bid), s in zip(uniq.tolist(), sums)]
    return sorted(out, key=order_key)


def order_key(row):
    """ORDER BY d_year, sum_agg DESC, brand_id (a NULL sum sorts last
    when descending)."""
    return (row[0], row[3] is None, -(row[3] or 0), row[1])
