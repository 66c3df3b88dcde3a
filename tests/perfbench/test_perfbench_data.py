"""The generator and the numpy references: the same seed gives the same
tables, a column does not depend on which others are kept, Arrow holds
what numpy made, and the references agree with the sqlite oracle of
tests/tpcds on the same tiny tables — while the float32 control does not
pass the comparison that decides `correct`."""

import copy
import importlib
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402

CONFIG = spec.cell("tpcds_sf10_session.power2")["config"]
SCALE = 0.002
BIG_SEED = 2 ** 31 + 12345       # more than 32 signed bits hold
QUERIES = ("q3", "q7")


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, BIG_SEED, SCALE)


@pytest.fixture(scope="module")
def tables(data):
    return gen.arrow_tables(data)


def test_same_seed_same_tables_other_seed_other_tables(data):
    again = gen.generate(CONFIG, BIG_SEED, SCALE)
    other = gen.generate(CONFIG, BIG_SEED + 1, SCALE)
    differs = False
    for t, cols in data.items():
        for c, col in cols.items():
            assert np.array_equal(col.values, again[t][c].values), (t, c)
            differs |= not np.array_equal(col.values, other[t][c].values)
    assert differs


def generate_of_pr35(config, seed, scale=1.0):
    """`gen.generate` as it stood before a table's columns could come
    from column modules (the parent of PR 36), word for word."""
    sizes = gen.table_rows(config, scale)
    seeding = config["seeding"]
    seeds = gen.Seeds(int(seed), int(seeding["structure_seed"]),
                      frozenset(seeding["from_the_run_seed"]))
    data = {}
    for spec in config["tables"]:
        name = spec["name"]
        mod = importlib.import_module(f"perfbench.gen.tables.{name}")
        cols = mod.generate(seeds, sizes[name], list(spec["columns"]), sizes)
        missing = [c for c in spec["columns"] if c not in cols]
        if missing:
            raise KeyError(f"{name}: generator made no column {missing}")
        data[name] = {c: cols[c] for c in spec["columns"]}
    return data


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.benchmark()["workloads"]])
def test_the_accepted_tables_are_the_parents_value_for_value(cell):
    """At the rehearsal's scale: every column of every table of the
    cell's configuration, values, masks, pools and decimal types."""
    config = spec.cell(cell)["config"]
    scale = float(config["rehearsal"]["scale"])
    made = gen.generate(config, BIG_SEED, scale)
    parents = generate_of_pr35(config, BIG_SEED, scale)
    assert {t: list(c) for t, c in made.items()} \
        == {t: list(c) for t, c in parents.items()} \
        == {t["name"]: t["columns"] for t in config["tables"]}
    for t, cols in parents.items():
        for c, want in cols.items():
            col = made[t][c]
            assert col.values.dtype == want.values.dtype, (t, c)
            assert np.array_equal(col.values, want.values), (t, c)
            assert (col.valid is None) == (want.valid is None), (t, c)
            assert col.valid is None \
                or np.array_equal(col.valid, want.valid), (t, c)
            assert (col.pool, col.scale, col.precision) \
                == (want.pool, want.scale, want.precision), (t, c)


def test_a_column_does_not_depend_on_the_columns_kept(data):
    fewer = copy.deepcopy(CONFIG)
    for t in fewer["tables"]:
        if t["name"] == "store_sales":
            t["columns"] = ["ss_ext_sales_price", "ss_item_sk"]
    small = gen.generate(fewer, BIG_SEED, SCALE)["store_sales"]
    for c, col in small.items():
        assert np.array_equal(col.values, data["store_sales"][c].values)
        assert np.array_equal(col.valid, data["store_sales"][c].valid) \
            or col.valid is None


def test_row_counts_are_the_configurations(data):
    full = gen.table_rows(CONFIG)
    # dsdgen -scale 10
    made = {t["name"]: full[t["name"]] for t in CONFIG["tables"]}
    assert made == {"store_sales": 28_800_991, "item": 102_000,
                    "customer_demographics": 1_920_800, "date_dim": 73_049,
                    "promotion": 500}
    assert full["customer"] == 500_000 and full["store"] == 102
    small = gen.table_rows(CONFIG, SCALE)
    assert small["store_sales"] == int(28_800_991 * SCALE)
    assert small["date_dim"] == 73_049          # fixed domains stay whole
    for t, cols in data.items():
        for col in cols.values():
            assert len(col.values) == small[t]
    assert len(data["store_sales"]) == 23


def test_arrow_holds_what_numpy_made(data, tables):
    ss, t = data["store_sales"], tables["store_sales"]
    price = t.column("ss_ext_sales_price").to_pylist()[:500]
    col = ss["ss_ext_sales_price"]
    assert price == [Decimal(int(v)).scaleb(-2) if ok else None
                     for v, ok in zip(col.values[:500], col.valid[:500])]
    assert str(t.schema.field("ss_ext_sales_price").type) \
        == "decimal128(7, 2)"
    for name in ("ss_promo_sk", "ss_quantity"):
        key = ss[name]
        got = t.column(name).to_pylist()
        assert got == [int(v) if ok else None
                       for v, ok in zip(key.values, key.valid)]
    assert t.column("ss_ext_sales_price").null_count \
        == int((~ss["ss_ext_sales_price"].valid).sum()) > 0
    assert t.column("ss_item_sk").null_count == 0
    item = tables["item"].column("i_brand").to_pylist()
    assert item == list(data["item"]["i_brand"].strings())
    for tab in tables.values():
        tab.validate(full=True)


@pytest.fixture(scope="module")
def wide():
    """The tables at 0.4 M fact rows, for the shape tests."""
    return gen.generate(CONFIG, 7, 0.014)


def test_pricing_is_dsdgens_arithmetic(wide):
    ss = {c: col.values for c, col in wide["store_sales"].items()}
    assert np.array_equal(ss["ss_quantity"] * ss["ss_sales_price"],
                          ss["ss_ext_sales_price"])
    assert np.array_equal(ss["ss_quantity"] * ss["ss_list_price"],
                          ss["ss_ext_list_price"])
    assert np.array_equal(ss["ss_ext_list_price"] - ss["ss_ext_sales_price"],
                          ss["ss_ext_discount_amt"])
    assert np.array_equal(ss["ss_ext_sales_price"] - ss["ss_coupon_amt"],
                          ss["ss_net_paid"])
    assert np.array_equal(ss["ss_net_paid"] - ss["ss_ext_wholesale_cost"],
                          ss["ss_net_profit"])
    assert ss["ss_quantity"].min() == 1 and ss["ss_quantity"].max() == 100
    assert ss["ss_wholesale_cost"].min() == 100
    assert ss["ss_wholesale_cost"].max() == 10000
    # markup 0..100 %, discount 0..100 %
    assert np.all(ss["ss_list_price"] >= ss["ss_wholesale_cost"])
    assert np.all(ss["ss_list_price"] <= 2 * ss["ss_wholesale_cost"])
    assert np.all(ss["ss_sales_price"] <= ss["ss_list_price"])
    assert ss["ss_sales_price"].min() == 0
    assert ss["ss_ext_list_price"].max() < 10 ** 7      # DECIMAL(7,2)
    # "20% of sales employ a coupon", of at most the extended price
    share = np.mean(ss["ss_coupon_amt"] > 0)
    assert 0.17 < share < 0.2
    assert np.all(ss["ss_coupon_amt"] <= ss["ss_ext_sales_price"])


def test_tickets_are_8_to_16_distinct_items_in_ticket_order(wide):
    ss = wide["store_sales"]
    ticket = ss["ss_ticket_number"].values
    assert np.all(np.diff(ticket) >= 0) and ticket[0] == 1
    sizes = np.bincount(ticket)[1:]
    assert sizes[:-1].min() == 8 and sizes[:-1].max() == 16
    assert 1 <= sizes[-1] <= 16            # cut where the row count ends
    assert abs(sizes[:-1].mean() - 12) < 0.1
    # a ticket's items are distinct, its date and demographics shared
    pairs = ticket.astype(np.int64) * 2 ** 20 + ss["ss_item_sk"].values
    assert len(np.unique(pairs)) == len(pairs)
    first = np.flatnonzero(np.diff(ticket, prepend=0))
    for name in ("ss_sold_date_sk", "ss_sold_time_sk", "ss_customer_sk",
                 "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk"):
        v = ss[name].values
        assert np.array_equal(v, v[first][ticket - 1])
    promo = ss["ss_promo_sk"].values          # drawn per line, not per ticket
    assert not np.array_equal(promo, promo[first][ticket - 1])


def test_nulls_are_nullsets(wide):
    """9 % of the rows draw a bitmap; a nullable column is null in half
    of those; ss_item_sk and ss_ticket_number never."""
    ss = wide["store_sales"]
    assert ss["ss_item_sk"].valid is None
    assert ss["ss_ticket_number"].valid is None
    nullable = [c for c in ss
                if c not in ("ss_item_sk", "ss_ticket_number")]
    assert len(nullable) == 21
    any_null = np.zeros(len(ss["ss_item_sk"].values), bool)
    for c in nullable:
        frac = 1 - ss[c].valid.mean()
        assert 0.042 < frac < 0.048, (c, frac)
        any_null |= ~ss[c].valid
    assert 0.088 < any_null.mean() < 0.092       # 9 %, sd 0.045 % here
    # two columns are null together far more often than by chance
    both = (~ss["ss_quantity"].valid & ~ss["ss_promo_sk"].valid).mean()
    assert 0.02 < both < 0.025


def test_sales_dates_follow_the_calendars_three_zones(wide):
    ss, d = wide["store_sales"], wide["date_dim"]
    pos = ss["ss_sold_date_sk"].values - d["d_date_sk"].values[0]
    years = d["d_year"].values[pos]
    assert years.min() == 1998 and years.max() == 2002
    share = np.bincount(years)[1998:] / len(years)
    assert np.all(np.abs(share - 0.2) < 0.01)
    by_month = np.bincount(d["d_moy"].values[pos], minlength=13)[1:]
    per_day = by_month / np.asarray([31, 28.2, 31, 30, 31, 30, 31, 31, 30,
                                     31, 30, 31])
    low, mid, high = per_day[:7].mean(), per_day[7:10].mean(), \
        per_day[10:].mean()
    assert 1.8 < mid / low < 2.2 and 3.7 < high / low < 4.3


def test_item_is_a_slowly_changing_dimension(wide):
    from perfbench.gen.tables import item as it

    rows = 102_000
    assert it.id_count(rows) == 51_000
    assert [int(it.id_of_row(r)) for r in range(1, 8)] \
        == [1, 2, 2, 3, 3, 3, 4]
    ids = wide["item"]["i_item_id"]
    assert len(ids.pool) == 51_000 and len(set(ids.pool)) == 51_000
    assert ids.pool[0] == "AAAAAAAABAAAAAAA"          # mk_bkey(1)
    assert ids.pool[16] == "AAAAAAAABBAAAAAA"         # mk_bkey(17)
    assert np.array_equal(ids.values + 1, it.id_of_row(np.arange(1, rows + 1)))
    # matchSCDSK: the revision in force on the date, by the cuts in scd.c
    early, late = it.THIRD_1, it.THIRD_2 + 1
    assert it.match_scd_sk([1, 1], [early, late], rows).tolist() == [1, 1]
    assert it.match_scd_sk([2, 2], [it.HALF, it.HALF + 1], rows).tolist() \
        == [2, 3]
    assert it.match_scd_sk([3, 3, 3], [early, early + 1, late],
                           rows).tolist() == [4, 5, 6]
    assert it.match_scd_sk([51_000], [late], rows).tolist() == [rows]
    # every sale's item is a revision of an id, in force on its date
    ss = wide["store_sales"]
    sk = ss["ss_item_sk"].values
    assert sk.min() >= 1 and sk.max() <= rows
    again = it.match_scd_sk(it.id_of_row(sk), ss["ss_sold_date_sk"].values,
                            rows)
    assert np.array_equal(again, sk)
    brand_id = wide["item"]["i_brand_id"].values
    assert brand_id.min() >= 1_001_001 and brand_id.max() <= 10_016_017
    m = wide["item"]["i_manufact_id"].values
    assert m.min() == 1 and m.max() == 1000


def test_promotion_flags_are_dsdgens():
    full = copy.deepcopy(CONFIG)
    for t in full["tables"]:
        if t["name"] == "promotion":
            t["columns"] = ["p_promo_sk", "p_channel_dmail",
                            "p_channel_email", "p_channel_event"]
    p = gen.generate(full, 3, 0.001)["promotion"]
    assert set(p["p_channel_email"].strings()) == {"N"}
    assert set(p["p_channel_event"].strings()) == {"N"}
    assert set(p["p_channel_dmail"].strings()) == {"N", "Y"}


@pytest.mark.parametrize("arith", ["Exact", "Float32"])
def test_aggregates_skip_nulls_and_an_empty_group_is_null(arith):
    a = getattr(reference, arith)()
    col = gen.Col(np.asarray([100, 250, 999, 7], np.int64),
                  np.asarray([True, True, False, False]), scale=2,
                  precision=7)
    inv = np.asarray([0, 0, 0, 1])
    assert a.sum_decimal(inv, col, 2) == [Decimal("3.50"), None]
    assert a.avg_decimal(inv, col, 2) == [Decimal("1.750000"), None]
    qty = gen.Col(np.asarray([3, 4, 9, 9], np.int32), col.valid)
    assert a.avg_int(inv, qty, 2) == [3.5, None]
    q3 = reference.load("q3")
    rows = [(2000, 2, "b", None), (2000, 1, "a", Decimal("1.00")),
            (1999, 3, "c", None)]
    assert sorted(rows, key=q3.order_key) == [rows[2], rows[1], rows[0]]


@pytest.fixture(scope="module")
def sqlite_rows(tables):
    from tests.tpcds.oracle import load_sqlite, rewrite_for_sqlite

    conn = load_sqlite(tables)
    try:
        return {q: conn.execute(rewrite_for_sqlite(
            spec.query_text(q), q)).fetchall() for q in QUERIES}
    finally:
        conn.close()


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_sqlite_oracle(q, data, sqlite_rows):
    from tests.tpcds.oracle import compare_rows

    want = reference.load(q).run(data, reference.Exact())
    assert want, "no rows at this scale: the comparison proves nothing"
    ok, msg = compare_rows(want, sqlite_rows[q])
    assert ok, msg
    # and in the order the query asks for
    keys = [reference.load(q).order_key(r) for r in want]
    assert keys == sorted(keys)


@pytest.mark.parametrize("q", QUERIES)
def test_reference_passes_its_own_comparison(q, data):
    want = reference.load(q).run(data, reference.Exact())
    n = check.compare_rows(list(want), want, reference.load(q))
    assert not any(n.values())


def test_float32_control_comes_out_not_correct():
    """The control: the reference itself in float32, in the program's
    place. It fails through q7's averages (limits.json). At a scale
    where some of q7's groups hold several rows, as the cell's do."""
    data = gen.generate(CONFIG, BIG_SEED, 0.05)
    total = {"unanswered": 0, "tier_mismatch": 0,
             "hidden_counters_moved": 0}
    for q in QUERIES:
        ref = reference.load(q)
        check.merge(total, check.compare_rows(
            ref.run(data, reference.Float32()),
            ref.run(data, reference.Exact()), ref))
    ok, compared = check.verdict(total)
    assert not ok
    assert compared["decimal_avg_max_abs_units"]["value"] \
        > compared["decimal_avg_max_abs_units"]["limit"]
    assert compared["double_max_rel"]["value"] > 1e-9
    assert compared["rows_wrong"]["value"] == 0


@pytest.mark.parametrize("fault,number", [
    ("drop", "rows_wrong"), ("extra", "rows_wrong"), ("swap", "order_breaks"),
    ("cent", "decimal_sum_max_abs_units"), ("key", "rows_wrong"),
    ("retype", "rows_wrong")])
def test_comparison_sees_each_kind_of_wrong_answer(fault, number, data):
    ref = reference.load("q3")
    want = ref.run(data, reference.Exact())
    got = list(want)
    if fault == "drop":
        got.pop(len(got) // 2)
    elif fault == "extra":
        got.append((1990, 1, "nobody", Decimal("1.00")))
    elif fault == "swap":
        got[0], got[-1] = got[-1], got[0]
    elif fault == "cent":
        y, b, n, s = got[3]
        got[3] = (y, b, n, s + Decimal("0.01"))
    elif fault == "key":
        y, b, n, s = got[3]
        got[3] = (y, b + 1, n, s)
    elif fault == "retype":
        y, b, n, s = got[3]
        got[3] = (y, b, n, float(s))
    n = check.compare_rows(got, want, ref)
    assert n[number] >= 1
    ok, _ = check.verdict({**n, "unanswered": 0, "tier_mismatch": 0,
                           "hidden_counters_moved": 0})
    assert not ok


def test_a_number_never_read_is_not_correct():
    ok, compared = check.verdict({"rows_wrong": 0})
    assert not ok and compared["unanswered"]["value"] is None


def test_the_run_seed_moves_the_measures_and_leaves_the_work_alone():
    """The configuration's `seeding`: two run seeds give the same keys,
    dates, tickets and nulls (so the same joins, filters and groups) and
    other quantities and prices (so other answers)."""
    one = gen.generate(CONFIG, BIG_SEED, SCALE)
    two = gen.generate(CONFIG, BIG_SEED + 1, SCALE)
    measures = {"ss_quantity"} | {
        c for c, col in one["store_sales"].items() if col.kind == "decimal"}
    assert len(measures) == 13
    for t, cols in one.items():
        for c, col in cols.items():
            other = two[t][c]
            assert (col.valid is None and other.valid is None) \
                or np.array_equal(col.valid, other.valid), (t, c)
            same = np.array_equal(col.values, other.values)
            assert same == (c not in measures), (t, c)
    for q in QUERIES:
        ref = reference.load(q)
        a = ref.run(one, reference.Exact())
        b = ref.run(two, reference.Exact())
        keys = len(ref.KEY_COLUMNS)   # the same groups, in the sums' order
        assert sorted(r[:keys] for r in a) == sorted(r[:keys] for r in b)
        assert a != b
    for stream in CONFIG["seeding"]["from_the_run_seed"]:
        table, column = stream.split(".")
        assert column in one[table], stream
