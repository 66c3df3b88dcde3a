"""SQL layer tests (role of the reference's SQLQueryTestSuite golden files —
inline expected results here; golden-file harness in test_golden.py)."""

import pyarrow as pa
import pytest

from spark_tpu.errors import AnalysisException, ParseException


@pytest.fixture()
def store(spark):
    sales = spark.createDataFrame(pa.table({
        "item": [1, 2, 3, 1, 2, 1, 4],
        "qty": [10, 20, 30, 40, 50, 60, 5],
        "price": [1.5, 2.0, 0.5, 1.5, 2.0, 1.5, 9.9],
    }))
    items = spark.createDataFrame(pa.table({
        "id": [1, 2, 3],
        "name": ["apple", "banana", "cherry"],
    }))
    sales.createOrReplaceTempView("sales")
    items.createOrReplaceTempView("items")
    return spark


def q(spark, text):
    return spark.sql(text).toArrow().to_pydict()


def test_basic_select(store):
    out = q(store, "SELECT item, qty FROM sales WHERE qty >= 30 ORDER BY qty")
    assert out["item"] == [3, 1, 2, 1]
    assert out["qty"] == [30, 40, 50, 60]


def test_join_agg_having(store):
    out = q(store, """
        SELECT i.name, SUM(s.qty * s.price) AS revenue, COUNT(*) AS n
        FROM sales s JOIN items i ON s.item = i.id
        GROUP BY i.name HAVING SUM(s.qty) > 40
        ORDER BY revenue DESC""")
    assert out["name"] == ["apple", "banana"]
    assert out["revenue"] == [165.0, 140.0]
    assert out["n"] == [3, 2]


def test_left_join_nulls(store):
    out = q(store, """SELECT s.item, i.name FROM sales s
                      LEFT JOIN items i ON s.item = i.id
                      WHERE s.qty = 5""")
    assert out["name"] == [None]


def test_semi_anti(store):
    out = q(store, """SELECT item FROM sales s LEFT ANTI JOIN items i
                      ON s.item = i.id""")
    assert out["item"] == [4]
    out2 = q(store, """SELECT DISTINCT item FROM sales s LEFT SEMI JOIN items i
                       ON s.item = i.id ORDER BY item""")
    assert out2["item"] == [1, 2, 3]


def test_union_distinct_and_all(store):
    out = q(store, "SELECT item FROM sales UNION SELECT id FROM items "
                   "ORDER BY item")
    assert out["item"] == [1, 2, 3, 4]
    out2 = q(store, "SELECT item FROM sales UNION ALL SELECT id FROM items")
    assert len(out2["item"]) == 10


def test_cte(store):
    out = q(store, """WITH big AS (SELECT * FROM sales WHERE qty >= 30)
                      SELECT count(*) AS c, min(qty) AS mn FROM big""")
    assert out["c"] == [4]
    assert out["mn"] == [30]


def test_subquery_in_from(store):
    out = q(store, """SELECT t.s FROM
                      (SELECT item, sum(qty) AS s FROM sales GROUP BY item) t
                      WHERE t.s > 50 ORDER BY t.s""")
    assert out["s"] == [70, 110]


def test_case_expressions(store):
    out = q(store, """SELECT item,
                        CASE WHEN qty < 20 THEN 'low'
                             WHEN qty < 50 THEN 'mid'
                             ELSE 'high' END AS band
                      FROM sales ORDER BY item, qty""")
    assert out["band"] == ["low", "mid", "high", "mid", "high", "mid", "low"]


def test_simple_case(store):
    out = q(store, "SELECT CASE item WHEN 1 THEN 'one' ELSE 'other' END AS c "
                   "FROM sales WHERE qty = 10")
    assert out["c"] == ["one"]


def test_in_between_like(store):
    assert q(store, "SELECT count(*) AS c FROM sales WHERE item IN (1, 3)")["c"] == [4]
    assert q(store, "SELECT count(*) AS c FROM sales WHERE qty BETWEEN 20 AND 50")["c"] == [4]
    assert q(store, "SELECT count(*) AS c FROM items WHERE name LIKE '%an%'")["c"] == [1]


def test_arithmetic_and_functions(store):
    out = q(store, """SELECT abs(-3) AS a, round(2.567, 2) AS r,
                             floor(2.7) AS f, ceil(2.1) AS c,
                             power(2, 10) AS p""")
    assert out["a"] == [3]
    assert abs(out["r"][0] - 2.57) < 1e-9
    assert out["f"] == [2]
    assert out["c"] == [3]
    assert out["p"] == [1024.0]


def test_division_by_zero_null(store):
    out = q(store, "SELECT 1 / 0 AS d, 5 % 0 AS m")
    assert out["d"] == [None]
    assert out["m"] == [None]


def test_values_clause(spark):
    out = q(spark, "SELECT col1 + col2 AS s FROM (VALUES (1, 2), (3, 4))")
    assert out["s"] == [3, 7]


def test_select_without_from(spark):
    out = q(spark, "SELECT 1 + 1 AS two, 'x' AS s")
    assert out["two"] == [2]
    assert out["s"] == ["x"]


def test_order_by_ordinal_and_group_by_ordinal(store):
    out = q(store, "SELECT item, sum(qty) FROM sales GROUP BY 1 ORDER BY 1")
    assert out["item"] == [1, 2, 3, 4]


@pytest.mark.parametrize("query,qty", [
    ("SELECT * FROM sales ORDER BY 1 DESC, 2", [5, 30, 20, 50, 10, 40, 60]),
    ("SELECT * FROM (SELECT item, qty FROM sales) t WHERE qty > 5 "
     "ORDER BY qty - item * 100, 2", [30, 20, 50, 10, 40, 60]),
    ("SELECT * FROM sales ORDER BY price, 2 DESC",
     [30, 60, 40, 10, 50, 20, 5]),
    # a literal outside the list is a constant, as it was
    ("SELECT * FROM sales ORDER BY qty, 9", [5, 10, 20, 30, 40, 50, 60])],
    ids=["star", "star_over_subquery", "after_a_name", "out_of_range"])
def test_order_by_ordinal_under_select_star(store, query, qty):
    """An ordinal names a column of the expanded star (q47's
    `SELECT * FROM v2 ... ORDER BY sum_sales - avg_monthly_sales, 3`)."""
    assert q(store, query)["qty"] == qty


def test_date_literal(spark):
    out = q(spark, "SELECT year(DATE '2021-03-15') AS y, "
                   "month(DATE '2021-03-15') AS m")
    assert out["y"] == [2021]
    assert out["m"] == [3]


def test_cast_syntax(spark):
    out = q(spark, "SELECT CAST('42' AS INT) AS i, CAST(3.9 AS INT) AS t, "
                   "CAST('2020-01-02' AS DATE) AS d")
    assert out["i"] == [42]
    assert out["t"] == [3]
    assert str(out["d"][0]) == "2020-01-02"


def test_parse_error(spark):
    with pytest.raises(ParseException):
        spark.sql("SELEC 1")


def test_unresolved_column_error(store):
    with pytest.raises(AnalysisException):
        store.sql("SELECT nope FROM sales").toArrow()


def test_missing_aggregation_error(store):
    with pytest.raises(AnalysisException):
        store.sql("SELECT item, qty FROM sales GROUP BY item").toArrow()


def test_string_comparison_lt(store):
    out = q(store, "SELECT name FROM items WHERE name < 'b' ORDER BY name")
    assert out["name"] == ["apple"]


def test_concat_pipe(store):
    out = q(store, "SELECT 'x' || name AS n FROM items ORDER BY n")
    assert out["n"] == ["xapple", "xbanana", "xcherry"]


def test_nested_subquery_aliasing(store):
    out = q(store, """
      SELECT a.name, a.total FROM (
        SELECT i.name AS name, SUM(s.qty) AS total
        FROM sales s JOIN items i ON s.item = i.id GROUP BY i.name
      ) a WHERE a.total >= 70 ORDER BY a.total""")
    assert out["name"] == ["banana", "apple"]
    assert out["total"] == [70, 110]


def test_non_equi_inner_join(store):
    out = q(store, """SELECT count(*) AS c FROM items a JOIN items b
                      ON a.id < b.id""")
    assert out["c"] == [3]  # (1,2),(1,3),(2,3)


def test_mixed_equi_and_residual_join(store):
    out = q(store, """SELECT s.item, s.qty FROM sales s JOIN items i
                      ON s.item = i.id AND s.qty > 25
                      ORDER BY s.item, s.qty""")
    assert out["qty"] == [40, 60, 50, 30]


def test_empty_relation_propagation(store):
    # WHERE false collapses to an empty relation; joins/unions fold away
    out = q(store, """SELECT s.item FROM sales s
                      JOIN (SELECT id FROM items WHERE false) t
                      ON s.item = t.id""")
    assert out["item"] == []
    out2 = q(store, "SELECT item FROM sales WHERE false "
                    "UNION ALL SELECT id FROM items ORDER BY item")
    assert out2["item"] == [1, 2, 3]


def test_nested_union_flattening(store):
    out = q(store, """SELECT 1 AS v UNION ALL SELECT 2
                      UNION ALL SELECT 3 UNION ALL SELECT 4""")
    assert sorted(out["v"]) == [1, 2, 3, 4]


def test_non_equi_left_outer_join(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({"x": [1, 5, 9]})) \
        .createOrReplaceTempView("neq_a")
    spark.createDataFrame(pa.table({"y": [3, 6]})) \
        .createOrReplaceTempView("neq_b")
    out = spark.sql("""
        SELECT x, y FROM neq_a LEFT JOIN neq_b ON x < y
        ORDER BY x, y""").toArrow().to_pydict()
    assert list(zip(out["x"], out["y"])) == \
        [(1, 3), (1, 6), (5, 6), (9, None)]


def test_left_outer_join_with_residual(spark):
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "k": [1, 1, 2], "v": [10, 20, 30]})) \
        .createOrReplaceTempView("res_a")
    spark.createDataFrame(pa.table({
        "k": [1, 2], "w": [15, 25]})) \
        .createOrReplaceTempView("res_b")
    out = spark.sql("""
        SELECT v, w FROM res_a LEFT JOIN res_b
        ON res_a.k = res_b.k AND v < w
        ORDER BY v""").toArrow().to_pydict()
    # v=10 matches (k=1, w=15); v=20 has no qualifying row; v=30 neither
    assert list(zip(out["v"], out["w"])) == \
        [(10, 15), (20, None), (30, None)]


def test_join_reorder_star_schema(spark):
    import numpy as np
    import pyarrow as pa

    n = 1000
    spark.createDataFrame(pa.table({
        "fk1": np.arange(n) % 10, "fk2": np.arange(n) % 5,
        "v": np.ones(n)})).createOrReplaceTempView("ro_fact")
    spark.createDataFrame(pa.table({
        "k1": np.arange(10), "n1": [f"a{i}" for i in range(10)]})) \
        .createOrReplaceTempView("ro_d1")
    spark.createDataFrame(pa.table({
        "k2": np.arange(5), "n2": [f"b{i}" for i in range(5)]})) \
        .createOrReplaceTempView("ro_d2")
    df = spark.sql("""SELECT n1, n2, sum(v) AS sv FROM ro_fact, ro_d1, ro_d2
                      WHERE fk1 = k1 AND fk2 = k2 GROUP BY n1, n2""")
    out = df.toArrow().to_pydict()
    assert len(out["sv"]) == 10  # 10 (k1 mod) × joint with k2 mod 5 pairs
    assert sum(out["sv"]) == n
    # the smallest relation (ro_d2, 5 rows) must seed the join chain
    txt = df.query_execution.optimized.tree_string()
    join_lines = [l for l in txt.splitlines() if "Join" in l
                  or "LocalRelation" in l]
    assert any("Join" in l for l in join_lines)


def test_join_runtime_filter_correctness(spark):
    import numpy as np
    import pyarrow as pa

    spark.conf.set("spark.tpu.join.runtimeFilter", True)
    spark.conf.set("spark.tpu.join.runtimeFilter.minCapacity", 1)
    try:
        rng = np.random.default_rng(3)
        n = 3000
        spark.createDataFrame(pa.table({
            "k": rng.integers(0, 3_000_000, n), "v": np.ones(n)})) \
            .createOrReplaceTempView("rf_f")
        # sparse keys over a wide span: forces the sort-probe path so the
        # range filter actually runs (dense spans use direct addressing)
        spark.createDataFrame(pa.table({
            "k2": 1000 + 99991 * np.arange(30), "w": np.arange(30.0)})) \
            .createOrReplaceTempView("rf_d")
        q = "SELECT count(*) AS c, sum(w) AS s FROM rf_f JOIN rf_d ON k = k2"
        on = spark.sql(q).collect()
        spark.conf.set("spark.tpu.join.runtimeFilter", False)
        off = spark.sql(q).collect()
        assert tuple(on[0].values()) == tuple(off[0].values())
        # semi join path
        spark.conf.set("spark.tpu.join.runtimeFilter", True)
        q2 = ("SELECT count(*) AS c FROM rf_f "
              "WHERE k IN (SELECT k2 FROM rf_d)")
        on2 = spark.sql(q2).collect()
        spark.conf.set("spark.tpu.join.runtimeFilter", False)
        off2 = spark.sql(q2).collect()
        assert tuple(on2[0].values()) == tuple(off2[0].values())
    finally:
        spark.conf.set("spark.tpu.join.runtimeFilter", False)
        spark.conf.set("spark.tpu.join.runtimeFilter.minCapacity", 1 << 20)


def test_ctas_with_materialized_cte(spark):
    """CREATE TABLE/VIEW AS with a multiply-instantiated expensive CTE:
    the command path must resolve WithCTE materializations exactly like
    session.sql does (r4 regression — placeholder relations leaked)."""
    import pyarrow as pa

    spark.createDataFrame(pa.table({
        "k": list(range(20)), "v": [1.0] * 20})) \
        .createOrReplaceTempView("ctas_src")
    spark.sql("""
        CREATE OR REPLACE TEMP VIEW ctas_out AS
        WITH big AS (SELECT a.k, sum(a.v) s FROM ctas_src a
                     JOIN ctas_src b ON a.k = b.k
                     JOIN ctas_src c ON a.k = c.k GROUP BY a.k)
        SELECT count(*) AS c FROM big x JOIN big y ON x.k = y.k""")
    assert spark.sql("SELECT * FROM ctas_out").toArrow() \
        .column("c")[0].as_py() == 20


def test_session_variables(spark):
    """DECLARE/SET/DROP VARIABLE with column-wins resolution
    (reference: SQL session variables, CreateVariable/ResolveSetVariable)."""
    import pyarrow as pa

    spark.sql("DECLARE VARIABLE sv_threshold INT DEFAULT 25")
    spark.createDataFrame(pa.table({"age": [20, 30, 40]})) \
        .createOrReplaceTempView("sv_people")
    q = "SELECT count(*) c FROM sv_people WHERE age > sv_threshold"
    assert spark.sql(q).toArrow().column("c")[0].as_py() == 2
    spark.sql("SET VARIABLE sv_threshold = 35")
    assert spark.sql(q).toArrow().column("c")[0].as_py() == 1
    # subquery assignment
    spark.sql("SET VAR sv_threshold = (SELECT max(age) FROM sv_people)")
    assert spark.sql("SELECT sv_threshold AS t").toArrow() \
        .column("t")[0].as_py() == 40
    # a real column with the variable's name wins over the variable
    spark.createDataFrame(pa.table({"sv_threshold": [7]})) \
        .createOrReplaceTempView("sv_shadow")
    assert spark.sql("SELECT sv_threshold AS t FROM sv_shadow").toArrow() \
        .column("t")[0].as_py() == 7
    spark.sql("DROP TEMPORARY VARIABLE sv_threshold")
    import pytest as _pytest

    with _pytest.raises(Exception, match="sv_threshold"):
        spark.sql("SELECT sv_threshold AS t").toArrow()


@pytest.fixture()
def ratios(spark):
    """a DECIMAL(17,2), b DECIMAL(18,6), n and d BIGINT, x DOUBLE: rows on
    both sides of a tenth of b and exactly on it, a negative and a zero
    divisor, a NULL, and a row too large for 64-bit products."""
    from decimal import Decimal as D

    a = ["1150.47", "1150.46", "1150.48", "1406.13", "1406.14", "-127.83",
         None, "5.00", "900000000000.00"]
    b = ["1278.300000"] * 5 + ["-1278.300000", "1.000000", "0.000000",
                               "300000.000000"]
    spark.createDataFrame(pa.table({
        "a": pa.array([None if v is None else D(v) for v in a],
                      pa.decimal128(17, 2)),
        "b": pa.array([D(v) for v in b], pa.decimal128(18, 6)),
        "n": pa.array([1, 2, 3, -1, 7, 10, 0, 5, 2 ** 62], pa.int64()),
        "d": pa.array([10, 20, 29, -10, 70, 99, 3, 0, 7], pa.int64()),
        "x": pa.array([0.1] * 9, pa.float64()),
    })).createOrReplaceTempView("ratios")
    return spark


@pytest.mark.parametrize("predicate,want,exact", [
    ("CASE WHEN b > 0 THEN abs(a - b) / b ELSE NULL END > 0.1",
     [False, True, False, False, True, None, None, None, True], True),
    ("abs(a - b) / b >= 0.1",
     [True, True, False, True, True, False, None, None, True], True),
    ("0.1 < abs(a - b) / b",
     [False, True, False, False, True, False, None, None, True], True),
    ("if(b <> 0, a / b, NULL) = 0.1",
     [False, False, False, False, False, True, None, None, False], True),
    ("a / b != 0.9",
     [False, True, True, True, True, True, None, None, True], True),
    ("n / d <= 0.1",
     [True, True, False, True, True, False, True, None, False], True),
    ("n / d > 1",
     [False, False, False, False, False, False, False, None, True], True),
    # not a quotient of exact operands, or no literal: as it was
    ("n / d > x",
     [False, False, True, False, False, True, False, None, True], False),
    ("x / d < 0.1",
     [True, True, True, True, True, True, True, None, True], False)],
    ids=["case_guard", "ge", "mirrored", "if_eq_negative_divisor", "ne",
         "integral", "integral_literal", "column_other", "double_operand"])
def test_decimal_quotient_compared_with_a_literal_is_decided_exactly(
        ratios, predicate, want, exact):
    """q89's and q47's `CASE WHEN avg > 0 THEN abs(sum - avg) / avg END >
    0.1`: 127.83 / 1278.3 is a tenth and not more, whatever the
    platform's float64 makes of it; NULL where the quotient is."""
    from spark_tpu.expr.expressions import QuotientComparison

    df = ratios.sql(f"SELECT {predicate} AS p FROM ratios")
    rewritten = any(
        isinstance(n, QuotientComparison)
        for e in df.query_execution.optimized.expressions()
        for n in e.iter_nodes())
    assert rewritten == exact
    assert df.toArrow().to_pydict()["p"] == want
    kept = ratios.sql(f"SELECT a FROM ratios WHERE {predicate}").toArrow()
    assert kept.num_rows == sum(w is True for w in want)
