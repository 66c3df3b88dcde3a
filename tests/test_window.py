"""Window function tests (reference: sql/core window suites /
DataFrameWindowFunctionsSuite)."""

import pyarrow as pa
import pytest

import spark_tpu.api.functions as F
from spark_tpu.api.window import Window


@pytest.fixture()
def sales(spark):
    df = spark.createDataFrame(pa.table({
        "dept": ["a", "a", "a", "b", "b", "c"],
        "emp": ["e1", "e2", "e3", "e4", "e5", "e6"],
        "sal": [100, 200, 200, 50, 75, 10],
    }))
    df.createOrReplaceTempView("emp_sales")
    return df


def _d(df):
    return df.toArrow().to_pydict()


def test_row_number_rank_dense(sales):
    w = Window.partitionBy("dept").orderBy(F.col("sal").desc())
    out = _d(sales.select(
        "dept", "emp", "sal",
        F.row_number().over(w).alias("rn"),
        F.rank().over(w).alias("rk"),
        F.dense_rank().over(w).alias("dr"),
    ).orderBy("dept", "sal", "emp"))
    # dept a sorted desc by sal: e2(200), e3(200), e1(100)
    rows = {(d, e): (rn, rk, dr) for d, e, rn, rk, dr in
            zip(out["dept"], out["emp"], out["rn"], out["rk"], out["dr"])}
    assert rows[("a", "e1")] == (3, 3, 2)
    assert rows[("a", "e2")][1:] == (1, 1)   # rank/dense of a 200 row
    assert rows[("a", "e3")][1:] == (1, 1)
    assert sorted([rows[("a", "e2")][0], rows[("a", "e3")][0]]) == [1, 2]
    assert rows[("b", "e5")] == (1, 1, 1)
    assert rows[("b", "e4")] == (2, 2, 2)
    assert rows[("c", "e6")] == (1, 1, 1)


def test_running_sum(sales):
    w = Window.partitionBy("dept").orderBy("sal")
    out = _d(sales.select(
        "dept", "sal", F.sum("sal").over(w).alias("rs"),
    ).orderBy("dept", "sal"))
    assert out["rs"][:3] == [100, 500, 500]  # peers (200,200) share total
    assert out["rs"][3:5] == [50, 125]
    assert out["rs"][5] == [10][0]


def test_partition_total(sales):
    w = Window.partitionBy("dept")
    out = _d(sales.select("dept",
                          F.sum("sal").over(w).alias("total"))
             .distinct().orderBy("dept"))
    assert out["total"] == [500, 125, 10]


def test_lag_lead(sales):
    w = Window.partitionBy("dept").orderBy("sal")
    out = _d(sales.select(
        "dept", "sal",
        F.lag("sal").over(w).alias("prev"),
        F.lead("sal").over(w).alias("next"),
    ).orderBy("dept", "sal", "emp"))
    assert out["prev"][:3] == [None, 100, 200]
    assert out["next"][2] is None or out["next"][1] is not None


def test_window_sql(sales, spark):
    out = _d(spark.sql("""
        SELECT dept, emp, sal,
               row_number() OVER (PARTITION BY dept ORDER BY sal DESC) AS rn,
               sum(sal) OVER (PARTITION BY dept) AS total
        FROM emp_sales ORDER BY dept, rn"""))
    assert out["rn"][:3] == [1, 2, 3]
    assert out["total"][:3] == [500, 500, 500]
    assert out["total"][3:5] == [125, 125]


def test_ntile_percent_rank(spark):
    df = spark.createDataFrame(pa.table({"v": list(range(1, 9))}))
    w = Window.orderBy("v")
    out = _d(df.select("v",
                       F.ntile(4).over(w).alias("q"),
                       F.percent_rank().over(w).alias("pr"))
             .orderBy("v"))
    assert out["q"] == [1, 1, 2, 2, 3, 3, 4, 4]
    assert out["pr"][0] == 0.0
    assert abs(out["pr"][-1] - 1.0) < 1e-12


def test_window_after_join_shuffle(spark):
    a = spark.createDataFrame(pa.table({
        "k": [1, 1, 2, 2, 3], "v": [10, 20, 30, 40, 50]}))
    w = Window.partitionBy("k").orderBy("v")
    out = _d(a.repartition(4).select(
        "k", "v", F.row_number().over(w).alias("rn")).orderBy("k", "v"))
    assert out["rn"] == [1, 2, 1, 2, 1]


def test_rows_frame_moving_average(spark):
    import pyarrow as pa
    from spark_tpu.api.window import Window

    df = spark.createDataFrame(pa.table({
        "g": ["a"] * 5, "t": [1, 2, 3, 4, 5],
        "v": [10.0, 20.0, 30.0, 40.0, 50.0]}))
    w = Window.partitionBy("g").orderBy("t").rowsBetween(-1, 1)
    out = _d(df.select("t", F.sum("v").over(w).alias("ms"),
                       F.avg("v").over(w).alias("ma")).orderBy("t"))
    assert out["ms"] == [30.0, 60.0, 90.0, 120.0, 90.0]
    assert out["ma"] == [15.0, 20.0, 30.0, 40.0, 45.0]


def test_rows_frame_sql(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "t": [1, 2, 3, 4], "v": [1, 2, 3, 4]})) \
        .createOrReplaceTempView("wf")
    out = spark.sql("""
        SELECT t, sum(v) OVER (ORDER BY t
            ROWS BETWEEN 2 PRECEDING AND CURRENT ROW) AS s
        FROM wf ORDER BY t""").toArrow().to_pydict()
    assert out["s"] == [1, 3, 6, 9]


def test_rows_frame_unbounded_following(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({"t": [1, 2, 3], "v": [5, 6, 7]})) \
        .createOrReplaceTempView("wf2")
    out = spark.sql("""
        SELECT t, sum(v) OVER (ORDER BY t
            ROWS BETWEEN CURRENT ROW AND UNBOUNDED FOLLOWING) AS s
        FROM wf2 ORDER BY t""").toArrow().to_pydict()
    assert out["s"] == [18, 13, 7]


def test_window_over_aggregate_single_query(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "store": [1, 1, 1, 2, 2],
        "item": [10, 11, 12, 10, 11],
        "rev": [5.0, 9.0, 7.0, 4.0, 8.0]})) \
        .createOrReplaceTempView("woa")
    out = spark.sql("""
        SELECT * FROM (
          SELECT store, item, SUM(rev) AS r,
                 rank() OVER (PARTITION BY store ORDER BY SUM(rev) DESC) AS rnk
          FROM woa GROUP BY store, item) t
        WHERE rnk <= 2 ORDER BY store, rnk""").toArrow().to_pydict()
    assert out["store"] == [1, 1, 2, 2]
    assert out["item"] == [11, 12, 11, 10]
    assert out["rnk"] == [1, 2, 1, 2]


def test_value_range_frame(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "g": ["a"] * 5, "t": [1, 2, 4, 7, 8], "v": [10, 20, 30, 40, 50]})) \
        .createOrReplaceTempView("vr")
    out = spark.sql("""
        SELECT t, sum(v) OVER (PARTITION BY g ORDER BY t
            RANGE BETWEEN 2 PRECEDING AND CURRENT ROW) AS s
        FROM vr ORDER BY t""").toArrow().to_pydict()
    # t=1:[1] → 10; t=2:[1,2] → 30; t=4:[2,4] → 50; t=7:[7] → 40; t=8:[7,8]
    assert out["s"] == [10, 30, 50, 40, 90]


def test_value_range_frame_api(spark):
    import pyarrow as pa
    from spark_tpu.api.window import Window

    df = spark.createDataFrame(pa.table({
        "t": [0, 5, 10, 30], "v": [1.0, 2.0, 4.0, 8.0]}))
    w = Window.orderBy("t").rangeBetween(-10, 10)
    out = df.select("t", F.avg("v").over(w).alias("a")) \
        .orderBy("t").toArrow().to_pydict()
    # t=0: window [−10,10] → {0,5,10} avg 7/3; t=30: only itself
    assert abs(out["a"][0] - 7 / 3) < 1e-9
    assert out["a"][3] == 8.0


def test_rows_frame_min_max(spark):
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from spark_tpu.api.window import Window

    rng = np.random.default_rng(7)
    n = 200
    pdf = pd.DataFrame({
        "g": rng.integers(0, 5, n),
        "t": np.arange(n),
        "v": rng.integers(-50, 50, n).astype("int64"),
    })
    df = spark.createDataFrame(pa.table(pdf))
    w = Window.partitionBy("g").orderBy("t").rowsBetween(-3, 2)
    out = _d(df.select("g", "t",
                       F.min("v").over(w).alias("lo"),
                       F.max("v").over(w).alias("hi")).orderBy("g", "t"))
    ordered = pdf.sort_values(["g", "t"])
    exp_lo, exp_hi = [], []  # brute-force oracle
    for _, grp in ordered.groupby("g"):
        vs = grp["v"].tolist()
        for i in range(len(vs)):
            win = vs[max(0, i - 3): i + 3]
            exp_lo.append(min(win))
            exp_hi.append(max(win))
    assert out["lo"] == exp_lo
    assert out["hi"] == exp_hi


def test_range_value_frame_min(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "t": [1, 2, 5, 6, 10], "v": [9, 3, 7, 1, 5]})) \
        .createOrReplaceTempView("wrv")
    out = spark.sql("""
        SELECT t, min(v) OVER (ORDER BY t
            RANGE BETWEEN 2 PRECEDING AND CURRENT ROW) AS m
        FROM wrv ORDER BY t""").toArrow().to_pydict()
    # windows by VALUE of t: t=1→{9}; t=2→{9,3}; t=5→{7}; t=6→{7,1}; t=10→{5}
    assert out["m"] == [9, 3, 7, 1, 5]


@pytest.mark.parametrize("frame,whole_partition", [
    ("", True),
    ("order by t", False),
    ("order by t rows between unbounded preceding and unbounded following",
     True),
    ("order by t rows between unbounded preceding and current row", False)],
    ids=["whole_partition", "running", "explicit_unbounded", "rows_to_here"])
@pytest.mark.parametrize("value,arrow", [
    ("vdec", "decimal128(18, 6)"), ("vi", "double"), ("vf", "double")])
def test_window_avg_is_the_aggregates_avg(spark, frame, whole_partition,
                                          value, arrow):
    """A window's AVG finishes through the aggregate's own expression
    (`aggregates.lower_aggregate_function`): over a whole partition it is
    the GROUP BY's average, value and type, AVG over DECIMAL included;
    over a running frame it is the average of the rows so far; a frame
    with no non-null value is NULL."""
    from decimal import Decimal

    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(3)
    n = 120
    g = rng.integers(0, 6, n)
    cents = rng.integers(-99999, 9999999, n)
    dead = (g == 4) | (rng.random(n) < 0.15)   # group 4 has no value
    spark.createDataFrame(pa.table({
        "g": g, "t": np.arange(n),
        "vdec": pa.array([None if d else Decimal(int(c)).scaleb(-2)
                          for c, d in zip(cents, dead)],
                         pa.decimal128(17, 2)),
        "vi": pa.array([None if d else int(c) for c, d in zip(cents, dead)],
                       pa.int64()),
        "vf": pa.array([None if d else c / 7.0 for c, d in zip(cents, dead)],
                       pa.float64()),
    })).createOrReplaceTempView("wavg")
    over = f"partition by g {frame}"
    out = spark.sql(f"select g, t, avg({value}) over ({over}) a from wavg "
                    "order by g, t").toArrow()
    assert str(out.schema.field("a").type) == arrow
    got = out.to_pydict()
    by_group = spark.sql(f"select g, avg({value}) a from wavg group by g") \
        .toArrow()
    assert by_group.schema.field("a").type == out.schema.field("a").type
    want = dict(zip(*by_group.to_pydict().values()))
    assert want[4] is None
    if value == "vf":       # a double's sum depends on the order of adding
        want = {k: v if v is None else pytest.approx(v, rel=1e-12)
                for k, v in want.items()}
    last = {}
    for gi, ti, a in zip(got["g"], got["t"], got["a"]):
        last[gi] = a
        if whole_partition:
            assert a == want[gi], (gi, ti)
    # the running frames reach the whole partition at its last row
    assert last == want


@pytest.mark.parametrize("tier", ["stage", "operator"])
def test_window_over_aggregate_same_on_both_segment_paths(spark, tier,
                                                          monkeypatch):
    """A report of q89's shape (windows over a GROUP BY on a string and
    an integer key, decimal, integral and float sums, a group and a
    partition with no value) below the whole tier: the per-partition
    window kernel and the aggregate kernels trace `ops/`'s bodies by the
    same rule, and with a sort's fixed cost taken away they take the scans
    and the sorts, same table."""
    from decimal import Decimal

    import numpy as np

    from spark_tpu.ops import grouping as G
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    rng = np.random.default_rng(30)
    n = 900
    store = rng.integers(0, 5, n)
    month = rng.integers(1, 13, n)
    cents = rng.integers(-99999, 9999999, n)
    dead = (store == 3) | ((store == 1) & (month == 2)) \
        | (rng.random(n) < 0.1)
    spark.createDataFrame(pa.table({
        "store": pa.array([None if s == 4 and m < 3 else f"s{s}"
                           for s, m in zip(store, month)]),
        "moy": month,
        "price": pa.array([None if d else Decimal(int(c)).scaleb(-2)
                           for c, d in zip(cents, dead)],
                          pa.decimal128(7, 2)),
        "qty": pa.array([None if d else int(c) % 100
                         for c, d in zip(cents, dead)], pa.int64()),
        "w": pa.array([None if d else c / 7.0 for c, d in zip(cents, dead)],
                      pa.float64()),
    })).createOrReplaceTempView("seg_sales")
    q = ("select store, moy, sp, sq, sw, n, "
         "avg(sp) over (partition by store) ap, "
         "sum(sq) over (partition by store) tq, "
         "count(sw) over (partition by store) cw, "
         "sum(sw) over (partition by store) tw, "
         "rank() over (partition by store order by sp desc, moy) r "
         "from (select store, moy, sum(price) sp, sum(qty) sq, sum(w) sw, "
         "count(*) n from seg_sales group by store, moy) t "
         "order by store, moy")
    old = spark.conf.get("spark.tpu.compile.tier")
    spark.conf.set("spark.tpu.compile.tier", tier)
    try:
        assert G.segment_path(1 << 12) == "scatter"
        plain = spark.sql(q).toArrow()
        monkeypatch.setattr(G, "SORT_FIXED_S", 0.0)
        assert G.segment_path(1 << 12) == "scan"
        built = KC.misses
        forced = spark.sql(q).toArrow()
        # the aggregate's and the window's kernels are other ones (the
        # operator tier finds those that the stage tier's run built)
        assert KC.misses >= built + (2 if tier == "stage" else 0)
    finally:
        spark.conf.set("spark.tpu.compile.tier", old)
    assert plain.num_rows == 60 and forced.equals(plain)
    got = plain.to_pydict()
    assert got["sp"][got["store"].index("s3")] is None
    assert set(a for s, a in zip(got["store"], got["ap"]) if s == "s3") \
        == {None}
