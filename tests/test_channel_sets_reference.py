"""TPC-DS's cross-channel set reports (q38 INTERSECT, q87 EXCEPT) through
the engine, on the whole-query tier and on the stage tier, against the
benchmark's plain numpy references (perfbench/reference/q38.py, q87.py)
on seeded tables at a small size on the CPU, by the comparison that
decides the cell's `correct`. The customer domain is cut to 3 000 so that
the channels meet, and every seed's tables have planted buckets: triples
bought in all three channels, and three customers of one first name and
a NULL last name, one a channel, on one day, who are one triple only
where NULL equals NULL. With web_sales empty, q87 is the store's triples
less the catalog's."""

import copy
import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402
from perfbench.reference import q38  # noqa: E402

CONFIG = copy.deepcopy(spec.cell("tpcds_sf10_channels.sets2")["config"])
for _t in CONFIG["tables"]:
    if _t["name"] == "customer":
        _t["rows"] = 3000
SCALE = 0.002                       # 57 601 store, 28 802 catalog rows
SEEDS = (2 ** 31 + 41, 20261015, 9)
SHARED = 60                         # store triples copied to the others
NULL_DAY_SK = 2451711               # 2000-06-15
QUERIES = ("q38", "q87")


def _planted(seed, empty_web=False):
    data = gen.generate(CONFIG, seed, SCALE)
    ss, cs, ws = (data[t] for t in ("store_sales", "catalog_sales",
                                    "web_sales"))
    d = data["date_dim"]
    in_2000 = set(d["d_date_sk"].values[d["d_year"].values == 2000].tolist())
    date, cust = ss["ss_sold_date_sk"], ss["ss_customer_sk"]
    ok = date.valid & cust.valid & np.isin(date.values, list(in_2000))
    rows = np.flatnonzero(ok)[::97][:SHARED]
    # the store's first customers' triples bought in the other channels
    for table, (dc, cc), n in ((cs, ("cs_sold_date_sk", "cs_bill_customer_sk"),
                                SHARED),
                               (ws, ("ws_sold_date_sk", "ws_bill_customer_sk"),
                                SHARED // 2)):
        for col, src in ((dc, date), (cc, cust)):
            values, valid = table[col].values.copy(), table[col].valid.copy()
            values[:n], valid[:n] = src.values[rows[:n]], True
            table[col] = gen.Col(values, valid)
    # customers 1, 2 and 3: one first name, no last name, each in one
    # channel on one day
    c = data["customer"]
    last, first = c["c_last_name"], c["c_first_name"]
    lv = last.valid.copy()
    lv[:3] = False
    fvalues, fv = first.values.copy(), first.valid.copy()
    fvalues[:3], fv[:3] = fvalues[0], True
    c["c_last_name"] = gen.Col(last.values, lv, pool=last.pool)
    c["c_first_name"] = gen.Col(fvalues, fv, pool=first.pool)
    for k, (table, dc, cc) in enumerate(q38.CHANNELS, start=1):
        f = data[table]
        at = len(f[dc].values) - 1
        for col, value in ((dc, NULL_DAY_SK), (cc, k)):
            values, valid = f[col].values.copy(), f[col].valid.copy()
            values[at], valid[at] = value, True
            f[col] = gen.Col(values, valid)
    if empty_web:
        data["web_sales"] = {k: col.take(np.zeros(0, np.int64))
                             for k, col in ws.items()}
    return data


@functools.lru_cache(maxsize=None)
def _case(seed, empty_web=False):
    data = _planted(seed, empty_web)
    return data, gen.arrow_tables(data)


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession

    # the configuration's conf, but tiles of 16 Ki rows: four a scan
    conf = {**CONFIG["session_conf"], "spark.tpu.batch.capacity": 1 << 14}
    s = TpuSession("channel-sets-reference", conf)
    yield s
    s.stop()


def _agrees(session, tier, seed, query, empty_web=False):
    data, tables = _case(seed, empty_web)
    for name, table in tables.items():
        session.createDataFrame(table).createOrReplaceTempView(name)
    session.conf.set("spark.tpu.compile.tier", tier)
    got = session.sql(spec.query_text(query)).toArrow()
    rows = list(zip(*[c.to_pylist() for c in got.columns]))
    ref = reference.load(query)
    want = ref.run(data, reference.Exact())
    numbers = check.compare_rows(rows, want, ref)
    assert check.over(numbers) == [], (numbers, rows, want)
    assert numbers["rows_wrong"] == 0 and len(rows) == 1
    return want[0][0]


@pytest.mark.parametrize("tier", ["whole", "stage"])
@pytest.mark.parametrize("query", QUERIES)
@pytest.mark.parametrize("seed", SEEDS)
def test_engine_agrees_with_the_plain_reference(session, seed, query, tier):
    count = _agrees(session, tier, seed, query)
    assert count >= (SHARED // 4 if query == "q38" else 500)


@pytest.mark.parametrize("tier", ["whole", "stage"])
def test_with_no_web_sale_q87_is_the_store_less_the_catalog(session, tier):
    seed = SEEDS[0]
    assert _agrees(session, tier, seed, "q38", empty_web=True) == 0
    count = _agrees(session, tier, seed, "q87", empty_web=True)
    data, _tables = _case(seed, True)
    (s, _), (c, _), (w, _) = q38.channel_triples(data)
    assert len(w) == 0
    assert count == len(np.setdiff1d(s, c)) > 500


@pytest.mark.parametrize("seed", SEEDS)
def test_the_planted_buckets_read_as_planted(seed):
    """What the reference itself says of them: the shared triples are in
    q38's count, and the NULL-named triple is one triple of all three
    channels only where NULL equals NULL."""
    data, _tables = _case(seed)
    triples = q38.channel_triples(data)
    for keys, null in triples:
        # the planted NULL-named triple is each channel's, once
        assert np.count_nonzero(null) >= 1 and len(np.unique(keys)) == \
            len(keys)
    both = q38.count(data)
    assert both >= SHARED // 4 + 1
    assert q38.count(data, null_equal=False) < both
    q87 = reference.load("q87")
    assert q87.count(data, null_equal=False) > q87.count(data)
