"""TPC-DS q38: how many (last name, first name, date) triples of customers
who bought on that day in all three channels, store, catalog and web, in
the twelve months from d_month_seq 1200, which are the year 2000: each
channel's distinct triples, joined by INTERSECT. The day is the row of
date_dim, here its key (one d_date a key). A set operation takes NULL as
equal to NULL, so a triple with a NULL name is one triple and meets its
like in another channel. The names are compared as strings, not as the
generator's codes. One row: the count."""

import numpy as np

from perfbench.reference import position, valid

YEAR = 2000                       # d_month_seq 1200..1211 (DMS = 1200)
CHANNELS = (("store_sales", "ss_sold_date_sk", "ss_customer_sk"),
            ("catalog_sales", "cs_sold_date_sk", "cs_bill_customer_sk"),
            ("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk"))
READS = {**{table: [date, cust] for table, date, cust in CHANNELS},
         "date_dim": ["d_date_sk", "d_year"],
         "customer": ["c_customer_sk", "c_first_name", "c_last_name"]}
KEY_COLUMNS = ()                  # one row: nothing tells rows apart


def _name_ids(col) -> tuple:
    """(an id per row by its string's place among the column's distinct
    strings, -1 where NULL; how many distinct strings)."""
    ok = valid(col)
    strings = np.asarray(col.pool)[col.values[ok]]
    uniq, inverse = np.unique(strings, return_inverse=True)
    ids = np.full(len(col.values), -1, np.int64)
    ids[ok] = inverse.reshape(-1)
    return ids, len(uniq)


def channel_triples(t) -> list:
    """Per channel, in CHANNELS' order: (its distinct triples as int64
    keys, ascending; whether each holds a NULL name). A sale whose date or
    customer is NULL, or out of the twelve months, joins nothing."""
    d, c = t["date_dim"], t["customer"]
    in_months = d["d_year"].values == YEAR
    last, _ = _name_ids(c["c_last_name"])
    first, n_first = _name_ids(c["c_first_name"])
    day = d["d_date_sk"].values.astype(np.int64) & 0xFFFFFFFF
    out = []
    for table, date_col, cust_col in CHANNELS:
        f = t[table]
        dk, ck = f[date_col], f[cust_col]
        dpos = position(dk, d["d_date_sk"])
        cpos = position(ck, c["c_customer_sk"])
        rows = np.flatnonzero(valid(dk) & valid(ck) & in_months[dpos])
        lid, fid = last[cpos[rows]], first[cpos[rows]]
        names = (lid + 1) * (n_first + 1) + (fid + 1)
        keys = np.unique((names << 32) | day[dpos[rows]])
        held = keys >> 32
        null = ((held // (n_first + 1)) == 0) | ((held % (n_first + 1)) == 0)
        out.append((keys, null))
    return out


def count(t, null_equal: bool = True) -> int:
    """The query's count; `null_equal=False` is what an equality that
    takes NULL as unknown would give (no triple with a NULL name meets
    another), the semantics q38 does not have."""
    (s, sn), (c, cn), (w, wn) = channel_triples(t)
    if not null_equal:
        s, c, w = s[~sn], c[~cn], w[~wn]
    return int(len(np.intersect1d(np.intersect1d(s, c, assume_unique=True),
                                  w, assume_unique=True)))


def run(t, arith):
    """`arith` is not read: a count is exact in any arithmetic."""
    return [(count(t),)]


def order_key(row):
    """No ORDER BY: one row."""
    return ()
