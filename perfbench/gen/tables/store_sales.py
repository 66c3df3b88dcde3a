"""store_sales as dsdgen makes it (tools v2.13.0, `w_store_sales.c`,
`pricing.c`, `nulls.c`, `scd.c`, `join.c`; the files were not at hand in
the PR that wrote this, so each rule is as its author recalled it, and the
configuration's `reduced.distributions` names what could not be recalled),
in integer cents and without a Python loop. Only the columns asked for are made.

`mk_w_store_sales`: a ticket is 8 to 16 line items, uniform. `mk_master`
draws the ticket's date, time, store, customer, demographics and address
once; `mk_detail` draws each line's item, promotion and pricing. A
ticket's items are consecutive entries of one random permutation of the
item ids, from a start drawn per ticket, so they are distinct, and the id
is turned into the surrogate key of the item revision in force on the
ticket's date (`matchSCDSK`). `nullSet`: 9 % of the rows (`nNullPct` 900)
draw a random bitmap, and a column whose bit is set is null unless the
table's not-null map (0x204: ss_item_sk, ss_ticket_number) names it, so
each other column is null in 4.5 % of the rows.
"""

import numpy as np

from perfbench.gen import Col, rng_for
from perfbench.gen.tables.date_dim import FIRST_DAY, FIRST_SK
from perfbench.gen.tables.item import id_count, match_scd_sk

COLUMNS = ("ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk",
           "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk",
           "ss_store_sk", "ss_promo_sk", "ss_ticket_number", "ss_quantity",
           "ss_wholesale_cost", "ss_list_price", "ss_sales_price",
           "ss_ext_discount_amt", "ss_ext_sales_price",
           "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
           "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
           "ss_net_profit")
NOT_NULL = ("ss_item_sk", "ss_ticket_number")        # 0x204
NULL_ROWS_IN_10000 = 900
ITEMS_MIN, ITEMS_MAX = 8, 16
YEAR_MIN, YEAR_MAX = 1998, 2002
# `date_join`: the year uniform, the day of the year by the `calendar`
# distribution's `sales` weights: three zones, January to July low, August
# to October medium, November and December high (Nambiar and Poess, "The
# Making of TPC-DS", VLDB 2006, 3.2). The weights of the zones are assumed.
ZONE_WEIGHT_BY_MONTH = (1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 4, 4)
COUPON_ROWS_IN_100 = 20      # pricing.c: "20% of sales employ a coupon"

# (column, dimension it points into) of the ticket-level keys
TICKET_KEYS = {"ss_customer_sk": "customer",
               "ss_cdemo_sk": "customer_demographics",
               "ss_hdemo_sk": "household_demographics",
               "ss_addr_sk": "customer_address",
               "ss_store_sk": "store"}


def _sk(day) -> int:
    return FIRST_SK + int((day - FIRST_DAY).astype(np.int64))


def _year_tables():
    """For each sales year: the d_date_sk of its 1 January, and the
    cumulative sales weight of its days."""
    first, cum = [], []
    for y in range(YEAR_MIN, YEAR_MAX + 1):
        days = np.arange(np.datetime64(f"{y}-01-01"),
                         np.datetime64(f"{y + 1}-01-01"))
        month = days.astype("datetime64[M]").astype(np.int64) % 12
        w = np.asarray(ZONE_WEIGHT_BY_MONTH, np.float64)[month]
        first.append(_sk(days[0]))
        cum.append(np.cumsum(w) / w.sum())
    return first, cum


class _Lazy:
    """Each shared array is made once, when a column first needs it."""

    def __init__(self, seed, rows, sizes):
        self.seed, self.rows, self.sizes = seed, rows, sizes
        self._memo = {}

    def rng(self, name):
        return rng_for(self.seed, "store_sales", name)

    def get(self, name):
        if name not in self._memo:
            self._memo[name] = getattr(self, "_" + name)()
        return self._memo[name]

    def _first_row(self):
        """First row of each ticket: tickets of 8 to 16 rows, the last one
        cut where the table's row count ends."""
        sizes = self.rng("ss_ticket_number").integers(
            ITEMS_MIN, ITEMS_MAX + 1, self.rows // ITEMS_MIN + 1)
        ends = np.cumsum(sizes)
        n = int(np.searchsorted(ends, self.rows)) + 1
        return ends[:n] - sizes[:n]

    def _tickets(self):
        return len(self.get("first_row"))

    def _ticket(self):
        """Ticket of each row, 0-based."""
        first = self.get("first_row")
        sizes = np.diff(first, append=self.rows)
        return np.repeat(np.arange(len(first), dtype=np.int32), sizes)

    def _ticket_date(self):
        """d_date_sk of each ticket."""
        n = self.get("tickets")
        rng = self.rng("ss_sold_date_sk")
        year = rng.integers(0, YEAR_MAX - YEAR_MIN + 1, n)
        u = rng.random(n)
        first, cum = _year_tables()
        out = np.empty(n, np.int32)
        for y in range(len(first)):
            m = year == y
            out[m] = first[y] + np.searchsorted(cum[y], u[m], side="right")
        return out

    def _null_bits(self):
        """0 for a row with no null; else the row's random bitmap."""
        rng = self.rng("_nulls")
        hit = rng.integers(0, 10000, self.rows, dtype=np.int32) \
            < NULL_ROWS_IN_10000
        bits = rng.integers(1, 2 ** 31 - 1, self.rows, dtype=np.int32,
                            endpoint=True)
        bits[~hit] = 0
        return bits

    def valid(self, column):
        if column in NOT_NULL:
            return None
        bit = np.int32(1 << COLUMNS.index(column))
        return (self.get("null_bits") & bit) == 0

    def _item(self):
        n_ids = id_count(self.sizes["item"])
        perm = (self.rng("ss_item_sk.permutation").permutation(n_ids) + 1) \
            .astype(np.int32)
        start = self.rng("ss_item_sk").integers(
            0, n_ids, self.get("tickets"), dtype=np.int32)
        t = self.get("ticket")
        # the row's place in the permutation: the ticket's start, plus
        # the row's place in the ticket (from 1), around the end
        at = np.arange(1, self.rows + 1, dtype=np.int32)
        at += (start - self.get("first_row").astype(np.int32))[t]
        at[at >= n_ids] -= np.int32(n_ids)
        return match_scd_sk(perm[at], self.get("ticket_date")[t],
                            self.sizes["item"])

    # pricing.c, set_pricing(SS_PRICING): quantity 1..100, wholesale cost
    # 1.00..100.00, markup 0.00..1.00, discount 0.00..1.00, all uniform;
    # products are cut to the cent
    def _qty(self):
        return self.rng("ss_quantity").integers(1, 101, self.rows,
                                                dtype=np.int32)

    def _wholesale(self):
        return self.rng("ss_wholesale_cost").integers(
            100, 10001, self.rows, dtype=np.int32).astype(np.int64)

    def _list(self):
        markup = self.rng("ss_list_price").integers(0, 101, self.rows,
                                                    dtype=np.int16)
        return self.get("wholesale") * (100 + markup) // 100

    def _sales(self):
        discount = self.rng("ss_sales_price").integers(
            0, 101, self.rows, dtype=np.int8)
        return self.get("list") * (100 - discount) // 100

    def _ext_sales(self):
        return self.get("qty") * self.get("sales")

    def _ext_wholesale(self):
        return self.get("qty") * self.get("wholesale")

    def _ext_list(self):
        return self.get("qty") * self.get("list")

    def _coupon(self):
        rng = self.rng("ss_coupon_amt")
        used = rng.integers(1, 101, self.rows, dtype=np.int8) \
            <= COUPON_ROWS_IN_100
        share = rng.integers(0, 101, self.rows, dtype=np.int8)
        share[~used] = 0
        return self.get("ext_sales") * share // 100

    def _net_paid(self):
        return self.get("ext_sales") - self.get("coupon")

    def _ext_tax(self):
        pct = self.rng("ss_ext_tax").integers(0, 10, self.rows,
                                              dtype=np.int8)
        return self.get("net_paid") * pct // 100


def generate(seed, rows, columns, sizes):
    z = _Lazy(seed, rows, sizes)

    def key(name, values):
        return Col(np.ascontiguousarray(values, dtype=np.int32),
                   z.valid(name))

    def money(name, cents):
        return Col(np.ascontiguousarray(cents, dtype=np.int64),
                   z.valid(name), scale=2, precision=7)

    def per_ticket(name, lo, hi):
        """A value drawn once a ticket, read by each of its line items."""
        return z.rng(name).integers(lo, hi, z.get("tickets"),
                                    dtype=np.int32)[z.get("ticket")]

    makers = {
        "ss_sold_date_sk": lambda: key(
            "ss_sold_date_sk", z.get("ticket_date")[z.get("ticket")]),
        "ss_sold_time_sk": lambda: key("ss_sold_time_sk", per_ticket(
            "ss_sold_time_sk", 0, 86400)),
        "ss_item_sk": lambda: key("ss_item_sk", z.get("item")),
        "ss_promo_sk": lambda: key("ss_promo_sk", z.rng(
            "ss_promo_sk").integers(1, sizes["promotion"] + 1, rows,
                                    dtype=np.int32)),
        "ss_ticket_number": lambda: key("ss_ticket_number",
                                        z.get("ticket") + 1),
        "ss_quantity": lambda: key("ss_quantity", z.get("qty")),
        "ss_wholesale_cost": lambda: money("ss_wholesale_cost",
                                           z.get("wholesale")),
        "ss_list_price": lambda: money("ss_list_price", z.get("list")),
        "ss_sales_price": lambda: money("ss_sales_price", z.get("sales")),
        "ss_ext_discount_amt": lambda: money(
            "ss_ext_discount_amt", z.get("ext_list") - z.get("ext_sales")),
        "ss_ext_sales_price": lambda: money("ss_ext_sales_price",
                                            z.get("ext_sales")),
        "ss_ext_wholesale_cost": lambda: money("ss_ext_wholesale_cost",
                                               z.get("ext_wholesale")),
        "ss_ext_list_price": lambda: money("ss_ext_list_price",
                                           z.get("ext_list")),
        "ss_ext_tax": lambda: money("ss_ext_tax", z.get("ext_tax")),
        "ss_coupon_amt": lambda: money("ss_coupon_amt", z.get("coupon")),
        "ss_net_paid": lambda: money("ss_net_paid", z.get("net_paid")),
        "ss_net_paid_inc_tax": lambda: money(
            "ss_net_paid_inc_tax", z.get("net_paid") + z.get("ext_tax")),
        "ss_net_profit": lambda: money(
            "ss_net_profit", z.get("net_paid") - z.get("ext_wholesale")),
    }
    for name, dim in TICKET_KEYS.items():
        makers[name] = (lambda name=name, dim=dim: key(
            name, per_ticket(name, 1, sizes[dim] + 1)))
    return {c: makers[c]() for c in columns if c in makers}
