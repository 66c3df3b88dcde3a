"""Connect remote API tests (reference: Spark Connect —
SparkConnectServiceSuite, python/pyspark/sql/tests/connect/). The core
contracts: (1) a THIN client with zero engine imports drives the server
from another process; (2) remote results are identical to in-process
execution, TPC-DS q3 included; (3) sessions are isolated."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def connect():
    """In-process server + client pair (fast path for API tests)."""
    from spark_tpu.connect.client import ConnectSession
    from spark_tpu.connect.server import ConnectServer

    server = ConnectServer({"spark.sql.shuffle.partitions": 2})
    addr = server.start()
    session = ConnectSession(addr, server.token)
    yield server, session
    session.close()
    server.stop()


def test_sql_roundtrip(connect):
    _, s = connect
    t = pa.table({"k": [1, 2, 1, 3], "v": [1.0, 2.0, 3.0, 4.0]})
    s.createDataFrame(t, "ct")
    rows = s.sql(
        "SELECT k, sum(v) AS s FROM ct GROUP BY k ORDER BY k").collect()
    assert rows == [{"k": 1, "s": 4.0}, {"k": 2, "s": 2.0},
                    {"k": 3, "s": 4.0}]


def test_dataframe_ops_build_remote_plan(connect):
    _, s = connect
    t = pa.table({"x": list(range(100))})
    df = s.createDataFrame(t)
    out = df.filter("x % 10 = 3").selectExpr("x", "x * 2 AS y").limit(4)
    got = out.collect()
    assert got == [{"x": 3, "y": 6}, {"x": 13, "y": 26},
                   {"x": 23, "y": 46}, {"x": 33, "y": 66}]
    assert df.count() == 100


def test_schema_and_explain(connect, capsys):
    _, s = connect
    df = s.sql("SELECT 1 AS a, 'x' AS b")
    fields = df.schema()
    assert [f[0] for f in fields] == ["a", "b"]
    df.explain()
    assert "Physical Plan" in capsys.readouterr().out


def test_create_view_from_plan(connect):
    _, s = connect
    s.createDataFrame(pa.table({"n": [1, 2, 3, 4]}), "cv_src")
    s.table("cv_src").filter("n > 2").createOrReplaceTempView("cv_big")
    assert s.sql("SELECT count(*) AS c FROM cv_big").collect() == [{"c": 2}]


def test_analysis_error_carries_server_detail(connect):
    from spark_tpu.connect.client import ConnectError

    _, s = connect
    with pytest.raises(ConnectError, match="nonexistent_table_xyz"):
        s.sql("SELECT * FROM nonexistent_table_xyz").collect()


def test_session_isolation(connect):
    from spark_tpu.connect.client import ConnectSession

    server, s1 = connect
    s2 = ConnectSession(server.address, server.token)
    try:
        s1.createDataFrame(pa.table({"z": [1]}), "iso_t")
        assert s1.sql("SELECT * FROM iso_t").collect() == [{"z": 1}]
        from spark_tpu.connect.client import ConnectError

        with pytest.raises(ConnectError, match="iso_t"):
            s2.sql("SELECT * FROM iso_t").collect()
    finally:
        s2.close()


# ---------------------------------------------------------------------------
# The headline contract: separate client process, zero engine imports,
# TPC-DS q3 identical to in-process execution.
# ---------------------------------------------------------------------------

_CLIENT_SCRIPT = r"""
import json, sys
sys.path.insert(0, {repo!r})
from spark_tpu.connect.client import ConnectSession

addr, token, data_dir, q3 = sys.argv[1:5]
import pyarrow.parquet as pq
import os
s = ConnectSession(addr, token)
for name in ("date_dim", "store_sales", "item"):
    t = pq.read_table(os.path.join(data_dir, name + ".parquet"))
    s.createDataFrame(t, name)
out = s.sql(open(q3).read()).toArrow()
print(json.dumps(out.to_pylist(), default=str))

# the purity pin: a Connect client process must never load the engine
engine_mods = [m for m in sys.modules
               if m.startswith(("jax", "spark_tpu.api", "spark_tpu.plan",
                                "spark_tpu.physical", "spark_tpu.expr",
                                "spark_tpu.sql", "spark_tpu.exec"))]
assert not engine_mods, f"engine leaked into thin client: {{engine_mods}}"
s.close()
"""


def test_q3_client_process_matches_inprocess(tmp_path, spark):
    import pyarrow.parquet as pq

    from spark_tpu.connect.server import ConnectServer
    from tests.tpcds.datagen import _Gen
    from tests.tpcds.oracle import strip_trailing_limit

    g = _Gen(0.25, 17)
    for t in ("date_dim", "time_dim", "item", "customer_address",
              "customer_demographics", "household_demographics",
              "income_band", "customer", "store", "warehouse",
              "ship_mode", "reason", "call_center", "catalog_page",
              "web_site", "web_page", "promotion", "store_sales"):
        getattr(g, t)()
    data_dir = tmp_path / "tpcds"
    data_dir.mkdir()
    for name in ("date_dim", "store_sales", "item"):
        pq.write_table(g.tables[name], str(data_dir / f"{name}.parquet"))
    qfile = tmp_path / "q3.sql"
    qfile.write_text(strip_trailing_limit(
        open(os.path.join(REPO, "tests", "tpcds", "queries",
                          "q3.sql")).read()))

    # in-process oracle run
    for name in ("date_dim", "store_sales", "item"):
        spark.createDataFrame(g.tables[name]).createOrReplaceTempView(name)
    expected = spark.sql(qfile.read_text()).toArrow().to_pylist()

    server = ConnectServer({"spark.sql.shuffle.partitions": 2})
    addr = server.start()
    try:
        script = tmp_path / "client.py"
        script.write_text(_CLIENT_SCRIPT.format(repo=REPO))
        r = subprocess.run(
            [sys.executable, str(script), addr, server.token,
             str(data_dir), str(qfile)],
            capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        got = json.loads(r.stdout.strip().splitlines()[-1])
    finally:
        server.stop()

    def norm(rows):
        return [tuple(str(v) for v in row.values()) for row in rows]

    assert norm(got) == norm(expected)
    assert len(got) > 0
