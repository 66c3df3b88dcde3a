"""TPC-DS's one-row reports (q28, q88) through the engine against the
benchmark's plain numpy references (perfbench/reference/q28.py, q88.py)
on seeded tables at a small size on the CPU, by the comparison that
decides the cell's `correct`. Every seed's fact table has three planted
buckets of q28: one no row falls into, one whose list prices are all
NULL, one with a single list price many times over."""

import functools
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402

CONFIG = spec.cell("tpcds_sf10_onerow.onerow2")["config"]
SCALE = 0.002                       # 57 601 fact rows
SEEDS = (2 ** 31 + 37, 20261004, 7)
ONE_PRICE = 13000                   # 130.00: inside bucket 5's 122..132


def _planted(seed):
    """The configuration's tables with bucket 3 (quantity 11..15) empty,
    bucket 4's (16..20) list prices NULL and bucket 5's (21..25) all
    130.00."""
    data = gen.generate(CONFIG, seed, SCALE)
    ss = data["store_sales"]
    qty, price = ss["ss_quantity"], ss["ss_list_price"]
    q = qty.values.copy()
    q[(q >= 11) & (q <= 15)] = 50
    values, valid = price.values.copy(), price.valid.copy()
    valid[(q >= 16) & (q <= 20)] = False
    values[(q >= 21) & (q <= 25)] = ONE_PRICE
    ss["ss_quantity"] = gen.Col(q, qty.valid)
    ss["ss_list_price"] = gen.Col(values, valid, scale=price.scale,
                                  precision=price.precision)
    return data


@functools.lru_cache(maxsize=None)
def _case(seed):
    data = _planted(seed)
    return data, gen.arrow_tables(data)


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession

    # the configuration's conf, but tiles of 16 Ki rows: four a scan
    conf = {**CONFIG["session_conf"], "spark.tpu.batch.capacity": 1 << 14}
    s = TpuSession("onerow-reference", conf)
    yield s
    s.stop()


@pytest.mark.parametrize("query", CONFIG["query_templates"])
@pytest.mark.parametrize("seed", SEEDS)
def test_engine_agrees_with_the_plain_reference(session, seed, query):
    data, tables = _case(seed)
    for name, table in tables.items():
        session.createDataFrame(table).createOrReplaceTempView(name)
    got = session.sql(spec.query_text(query)).toArrow()
    rows = list(zip(*[c.to_pylist() for c in got.columns]))
    ref = reference.load(query)
    want = ref.run(data, reference.Exact())
    assert len(rows) == 1 == len(want)
    numbers = check.compare_rows(rows, want, ref)
    assert check.over(numbers) == [], (numbers, rows, want)
    # counts are compared exactly, so nothing hides in a limit
    assert numbers["rows_wrong"] == 0 == numbers["order_breaks"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_planted_buckets_read_as_planted(seed):
    """What the reference itself says of them: the comparison above is
    worth what its right-hand side is."""
    data, _tables = _case(seed)
    row = reference.load("q28").run(data, reference.Exact())[0]
    b = [row[3 * i:3 * i + 3] for i in range(6)]
    assert b[2] == (None, 0, 0)                      # no row
    assert b[3] == (None, 0, 0)                      # rows, no price
    lp, cnt, cntd = b[4]                             # one price
    assert lp == Decimal("130.000000") and cnt > 100 and cntd == 1
    for lp, cnt, cntd in (b[0], b[1], b[5]):         # as generated
        assert lp is not None and cnt > cntd > 1
    # bucket 4 is not empty: rows pass its coupon and wholesale ranges
    ss = data["store_sales"]
    q = ss["ss_quantity"]
    in4 = q.valid & (q.values >= 16) & (q.values <= 20)
    cost = ss["ss_wholesale_cost"]
    assert np.count_nonzero(in4 & cost.valid & (cost.values >= 3800)
                            & (cost.values <= 5800)) > 100
    counts = reference.load("q88").run(data, reference.Exact())[0]
    assert len(counts) == 8 and all(c > 0 for c in counts)


def test_the_float32_control_is_not_correct():
    """The same reports with sums and averages carried in float32 fail
    the comparison, by the averages' limit alone: q88 is counts."""
    data, _tables = _case(SEEDS[0])
    total = {}
    for query in CONFIG["query_templates"]:
        ref = reference.load(query)
        check.merge(total, check.compare_rows(
            ref.run(data, reference.Float32()),
            ref.run(data, reference.Exact()), ref))
    assert check.over(total) == ["decimal_avg_max_abs_units"], total
