"""TPC-DS q7: average quantity and prices by item for one demographic
group, promotions not both on email and at events, in one year."""

import numpy as np

from perfbench.reference import group, position, valid

READS = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                         "ss_promo_sk", "ss_quantity", "ss_list_price",
                         "ss_coupon_amt", "ss_sales_price"],
         "customer_demographics": ["cd_demo_sk", "cd_gender",
                                   "cd_marital_status",
                                   "cd_education_status"],
         "date_dim": ["d_date_sk", "d_year"],
         "item": ["i_item_sk", "i_item_id"],
         "promotion": ["p_promo_sk", "p_channel_email", "p_channel_event"]}
KEY_COLUMNS = (0,)           # i_item_id
AVG_COLUMNS = (2, 3, 4)      # decimal averages: a division, not exact


def _is(col, value) -> np.ndarray:
    return col.values == col.pool.index(value)


def run(t, arith):
    ss, cd, d = t["store_sales"], t["customer_demographics"], t["date_dim"]
    i, p = t["item"], t["promotion"]
    dpos = position(ss["ss_sold_date_sk"], d["d_date_sk"])
    ipos = position(ss["ss_item_sk"], i["i_item_sk"])
    cpos = position(ss["ss_cdemo_sk"], cd["cd_demo_sk"])
    ppos = position(ss["ss_promo_sk"], p["p_promo_sk"])
    cd_ok = _is(cd["cd_gender"], "M") & _is(cd["cd_marital_status"], "S") \
        & _is(cd["cd_education_status"], "College")
    p_ok = _is(p["p_channel_email"], "N") | _is(p["p_channel_event"], "N")
    keep = valid(ss["ss_sold_date_sk"]) & valid(ss["ss_item_sk"]) \
        & valid(ss["ss_cdemo_sk"]) & valid(ss["ss_promo_sk"]) \
        & (d["d_year"].values == 2000)[dpos] & cd_ok[cpos] & p_ok[ppos]
    rows = np.flatnonzero(keep)
    uniq, inv = group(i["i_item_id"].values[ipos[rows]])
    n = len(uniq)
    ids = i["i_item_id"].pool
    a1 = arith.avg_int(inv, ss["ss_quantity"].take(rows), n)
    a2 = arith.avg_decimal(inv, ss["ss_list_price"].take(rows), n)
    a3 = arith.avg_decimal(inv, ss["ss_coupon_amt"].take(rows), n)
    a4 = arith.avg_decimal(inv, ss["ss_sales_price"].take(rows), n)
    out = [(ids[c], *aggs)
           for (c,), *aggs in zip(uniq.tolist(), a1, a2, a3, a4)]
    return sorted(out, key=order_key)


def order_key(row):
    """ORDER BY i_item_id."""
    return (row[0],)
