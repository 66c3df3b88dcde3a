"""TPC-DS q47: a brand's monthly sales by store in one year beside the
year's average month, with the month before and the month after; the
months more than a tenth away from the average."""

import numpy as np

from perfbench.reference import group
from perfbench.reference.q89 import (by_string, far_from_average, star,
                                     window_avg)

READS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                         "ss_sales_price"],
         "date_dim": ["d_date_sk", "d_year", "d_moy"],
         "item": ["i_item_sk", "i_category", "i_brand"],
         "store": ["s_store_sk", "s_store_name", "s_company_name"]}
KEY_COLUMNS = (0, 1, 2, 3, 4, 5)    # category, brand, store, company,
#                                     d_year, d_moy
AVG_COLUMNS = (6,)                  # avg_monthly_sales: a division
YEAR = 1999


def run(t, arith):
    ss, d, i, s = t["store_sales"], t["date_dim"], t["item"], t["store"]
    year, moy = d["d_year"].values, d["d_moy"].values
    date_ok = (year == YEAR) | ((year == YEAR - 1) & (moy == 12)) \
        | ((year == YEAR + 1) & (moy == 1))
    rows, dpos, ipos, spos = star(
        t, date_ok, np.ones(len(i["i_item_sk"].values), bool))
    brands, brand = by_string(i["i_brand"])
    stores, store = by_string(s["s_store_name"])
    companies, company = by_string(s["s_company_name"])
    # v1: one row a (category, brand, store, company, year, month); `group`
    # returns them sorted by those keys, so a partition of `rn`'s window
    # is a run of rows already in its ORDER BY d_year, d_moy
    v1, inv = group(i["i_category"].values[ipos], brand[ipos], store[spos],
                    company[spos], year[dpos], moy[dpos])
    sums = arith.sum_decimal(inv, ss["ss_sales_price"].take(rows), len(v1))
    by_year, part_year = group(v1[:, 0], v1[:, 1], v1[:, 2], v1[:, 3],
                               v1[:, 4])
    avgs = window_avg(arith, part_year, sums, len(by_year))
    _parts, part = group(v1[:, 0], v1[:, 1], v1[:, 2], v1[:, 3])
    # rank() OVER (PARTITION BY the four ORDER BY d_year, d_moy): the
    # order keys are group keys, so no ties: 1 + rows before in the run
    start = np.flatnonzero(np.diff(part, prepend=-1))
    rn = np.arange(len(part)) - start[part] + 1
    size = np.bincount(part)[part]
    categories = i["i_category"].pool
    out = []
    for j, (c, b, st, co, y, m) in enumerate(v1.tolist()):
        # v1 JOIN v1_lag ON rn = lag.rn + 1 JOIN v1_lead ON rn = lead.rn - 1
        # within the four keys: inner joins, so a partition's first and
        # last months have no row
        if y != YEAR or rn[j] == 1 or rn[j] == size[j]:
            continue
        if not far_from_average(sums[j], avgs[j]):
            continue
        out.append((categories[c], brands[b], stores[st], companies[co],
                    int(y), int(m), avgs[j], sums[j], sums[j - 1],
                    sums[j + 1]))
    return sorted(out, key=order_key)


def order_key(row):
    """ORDER BY sum_sales - avg_monthly_sales, 3 (s_store_name)."""
    return (row[7] - row[6], row[2])
