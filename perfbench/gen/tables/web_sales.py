"""web_sales as dsdgen makes it (tools v2.13.0, `w_web_sales.c`; recalled,
not at hand), of its 34 columns the two that TPC-DS q38 and q87 read, by
catalog_sales' mechanism (`catalog_sales.orders`): an order of 8 to 16
line items (uniform, as recalled), its date and bill customer drawn once
an order, nullSet's 4.5 % nulls a column."""

from perfbench.gen.tables.catalog_sales import orders

LINES_MIN, LINES_MAX = 8, 16
BITS = {"ws_sold_date_sk": 0, "ws_bill_customer_sk": 4}


def generate(seed, rows, columns, sizes):
    return orders(seed, "web_sales", rows, columns, sizes,
                  (LINES_MIN, LINES_MAX), BITS, "ws_sold_date_sk",
                  "ws_bill_customer_sk")
