"""catalog_sales as dsdgen makes it (tools v2.13.0, `w_catalog_sales.c`,
`join.c`, `nulls.c`; recalled, not at hand), of its 34 columns the two
that TPC-DS q38 and q87 read: the sale's date and the bill customer.

`mk_w_catalog_sales`: an order is several line items (4 to 14, uniform,
as recalled); `mk_master` draws the order's date (`date_join`: the year
uniform in 1998..2002, the day by the calendar's sales zones, as
store_sales draws a ticket's) and its bill customer (uniform over
`customer`) once, and every line of the order reads them. `nullSet`: 9 %
of the rows draw a random bitmap, and a column whose bit is set is null
(the not-null map names the item and the order number only), so each of
the two columns is null in 4.5 % of the rows. Rows are in order order.
web_sales is made by the same mechanism (`web_sales.py`).
"""

import numpy as np

from perfbench.gen import Col, rng_for
from perfbench.gen.tables.store_sales import (
    NULL_ROWS_IN_10000, YEAR_MAX, YEAR_MIN, _year_tables,
)

LINES_MIN, LINES_MAX = 4, 14
# the column's place among the table's 34, which picks its null bit
BITS = {"cs_sold_date_sk": 0, "cs_bill_customer_sk": 3}
DATE, CUSTOMER = "cs_sold_date_sk", "cs_bill_customer_sk"


def orders(seed, table, rows, columns, sizes, lines, bits, date, customer):
    """{column: Col} of a channel's fact table whose rows are the line
    items of orders of `lines` (min, max) rows: `date` and `customer`
    drawn once an order, each column's nulls by its bit in `bits`."""
    def rng(name):
        return rng_for(seed, table, name)

    lo, hi = lines
    size = rng("_order").integers(lo, hi + 1, rows // lo + 1)
    ends = np.cumsum(size)
    n = int(np.searchsorted(ends, rows)) + 1
    first = ends[:n] - size[:n]
    order = np.repeat(np.arange(n, dtype=np.int32),
                      np.diff(first, append=rows))

    r = rng("_nulls")
    hit = r.integers(0, 10000, rows, dtype=np.int32) < NULL_ROWS_IN_10000
    nulls = r.integers(1, 2 ** 31 - 1, rows, dtype=np.int32, endpoint=True)
    nulls[~hit] = 0

    def key(name, per_order):
        return Col(np.ascontiguousarray(per_order[order], dtype=np.int32),
                   (nulls & np.int32(1 << bits[name])) == 0)

    def order_dates():
        r = rng(date)
        year = r.integers(0, YEAR_MAX - YEAR_MIN + 1, n)
        u = r.random(n)
        first_sk, cum = _year_tables()
        out = np.empty(n, np.int32)
        for y in range(len(first_sk)):
            m = year == y
            out[m] = first_sk[y] + np.searchsorted(cum[y], u[m], side="right")
        return out

    makers = {
        date: lambda: key(date, order_dates()),
        customer: lambda: key(customer, rng(customer).integers(
            1, sizes["customer"] + 1, n, dtype=np.int32)),
    }
    return {c: makers[c]() for c in columns if c in makers}


def generate(seed, rows, columns, sizes):
    return orders(seed, "catalog_sales", rows, columns, sizes,
                  (LINES_MIN, LINES_MAX), BITS, DATE, CUSTOMER)
