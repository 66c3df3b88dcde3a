"""TPC-DS q89: monthly sales of a class's brands by store in one year,
beside the brand's average month in that store; the months more than a
tenth away from it."""

from decimal import Decimal

import numpy as np

from perfbench.gen import Col
from perfbench.reference import group, position, valid

READS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                         "ss_sales_price"],
         "date_dim": ["d_date_sk", "d_year", "d_moy"],
         "item": ["i_item_sk", "i_category", "i_class", "i_brand"],
         "store": ["s_store_sk", "s_store_name", "s_company_name"]}
KEY_COLUMNS = (0, 1, 2, 3, 4, 5)    # category, class, brand, store,
#                                     company, d_moy
AVG_COLUMNS = (7,)                  # avg_monthly_sales: a division

PAIRS = ((("Books", "Electronics", "Sports"),
          ("computers", "stereo", "football")),
         (("Men", "Jewelry", "Women"), ("shirts", "birdal", "dresses")))
TENTH = Decimal("0.1")


def _in(col, values) -> np.ndarray:
    return np.isin(col.strings(), values)


def by_string(col):
    """(the column's distinct strings in order, each row's index among
    them): a pool may hold one string under two codes (`i_brand` is coded
    by brand id), and SQL groups by the string."""
    names, of_code = np.unique(np.asarray(col.pool, dtype=object),
                               return_inverse=True)
    return names.tolist(), of_code.reshape(-1)[col.values]


def star(t, date_ok, item_ok):
    """Rows of store_sales that join item, date_dim and store (inner
    joins: a null key joins nothing) and pass the dimensions' filters,
    with their positions in the three dimensions."""
    ss = t["store_sales"]
    dpos = position(ss["ss_sold_date_sk"], t["date_dim"]["d_date_sk"])
    ipos = position(ss["ss_item_sk"], t["item"]["i_item_sk"])
    spos = position(ss["ss_store_sk"], t["store"]["s_store_sk"])
    keep = valid(ss["ss_sold_date_sk"]) & valid(ss["ss_item_sk"]) \
        & valid(ss["ss_store_sk"]) & date_ok[dpos] & item_ok[ipos]
    rows = np.flatnonzero(keep)
    return rows, dpos[rows], ipos[rows], spos[rows]


def window_avg(arith, partition, sums, npartitions, scale=2):
    """avg(sum_sales) OVER (PARTITION BY ...): over the partition's
    non-null monthly sums, to AVG's decimal type; one value a row."""
    col = Col(np.asarray([0 if s is None else int(s.scaleb(scale))
                          for s in sums], np.int64),
              np.asarray([s is not None for s in sums], bool),
              scale=scale, precision=17)
    per = arith.avg_decimal(partition, col, npartitions)
    return [per[p] for p in partition.tolist()]


def far_from_average(total, average) -> bool:
    """CASE WHEN avg > 0 THEN abs(sum - avg) / avg END > 0.1, exactly; a
    NULL on either side keeps no row. (Sales prices are not negative, so
    q89's `avg <> 0` and q47's `avg > 0` are one test.)"""
    if total is None or average is None or average <= 0:
        return False
    return abs(total - average) > TENTH * average


def run(t, arith):
    ss, d, i, s = t["store_sales"], t["date_dim"], t["item"], t["store"]
    item_ok = np.zeros(len(i["i_item_sk"].values), bool)
    for categories, classes in PAIRS:
        item_ok |= _in(i["i_category"], categories) \
            & _in(i["i_class"], classes)
    rows, dpos, ipos, spos = star(t, d["d_year"].values == 1999, item_ok)
    brands, brand = by_string(i["i_brand"])
    stores, store = by_string(s["s_store_name"])
    companies, company = by_string(s["s_company_name"])
    uniq, inv = group(i["i_category"].values[ipos],
                      i["i_class"].values[ipos], brand[ipos], store[spos],
                      company[spos], d["d_moy"].values[dpos])
    sums = arith.sum_decimal(inv, ss["ss_sales_price"].take(rows), len(uniq))
    parts, part = group(uniq[:, 0], uniq[:, 2], uniq[:, 3], uniq[:, 4])
    avgs = window_avg(arith, part, sums, len(parts))
    categories, classes = i["i_category"].pool, i["i_class"].pool
    out = [(categories[c], classes[k], brands[b], stores[st], companies[co],
            int(moy), total, average)
           for (c, k, b, st, co, moy), total, average
           in zip(uniq.tolist(), sums, avgs)
           if far_from_average(total, average)]
    return sorted(out, key=order_key)


def order_key(row):
    """ORDER BY sum_sales - avg_monthly_sales, s_store_name."""
    return (row[6] - row[7], row[3])
