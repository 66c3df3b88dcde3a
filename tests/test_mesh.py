"""Mesh / collectives tests over the 8-virtual-device CPU mesh
(SURVEY.md §4: the local-cluster analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_tpu.parallel.mesh import get_mesh, replicated_sharding, shard_rows
from spark_tpu.parallel.mesh_agg import make_distributed_groupby_sum


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return get_mesh(8)


def test_distributed_groupby_matches_oracle(mesh):
    n = 8 * 128
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 23, n).astype(np.int64)
    vals = rng.integers(-50, 100, n).astype(np.int64)
    mask = np.ones(n, bool)
    mask[::13] = False

    f = make_distributed_groupby_sum(mesh)
    ok, osum, ocnt, om = f(shard_rows(jnp.asarray(keys), mesh),
                           shard_rows(jnp.asarray(vals), mesh),
                           shard_rows(jnp.asarray(mask), mesh))
    ok, osum, ocnt, om = map(np.asarray, (ok, osum, ocnt, om))

    got = {}
    for kk, ss, cc in zip(ok[om], osum[om], ocnt[om]):
        assert int(kk) not in got, "key owned by two shards"
        got[int(kk)] = (int(ss), int(cc))
    want = {}
    for kk, vv, mm in zip(keys, vals, mask):
        if mm:
            s, c = want.get(int(kk), (0, 0))
            want[int(kk)] = (s + int(vv), c + 1)
    assert got == want


def test_keys_land_on_owner_shard(mesh):
    """Each distinct key must end up on exactly one shard — the clustering
    contract the final aggregation relies on."""
    n = 8 * 64
    keys = np.arange(n, dtype=np.int64) % 11
    vals = np.ones(n, dtype=np.int64)
    mask = np.ones(n, bool)
    f = make_distributed_groupby_sum(mesh)
    ok, osum, ocnt, om = f(shard_rows(jnp.asarray(keys), mesh),
                           shard_rows(jnp.asarray(vals), mesh),
                           shard_rows(jnp.asarray(mask), mesh))
    ok, om = np.asarray(ok), np.asarray(om)
    per_shard = ok.shape[0] // 8
    owners = {}
    for shard in range(8):
        sl = slice(shard * per_shard, (shard + 1) * per_shard)
        for kk in ok[sl][om[sl]]:
            assert int(kk) not in owners
            owners[int(kk)] = shard
    assert len(owners) == 11


# ---------------------------------------------------------------------------
# Planner-integrated mesh execution: whole SQL queries through the mesh
# exchange (spark_tpu/parallel/mesh_exchange.py), results bit-identical to
# the host shuffle path.
# ---------------------------------------------------------------------------

def _rows(df):
    out = [tuple(r) for r in df.collect()]
    return sorted(out, key=lambda t: tuple((x is None, x) for x in t))


@pytest.fixture()
def mesh_session():
    from spark_tpu import TpuSession

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    s = TpuSession("mesh-sql", {"spark.sql.shuffle.partitions": 8,
                                "spark.tpu.batch.capacity": 1 << 10})
    yield s
    s.stop()


def _mk_tables(s, seed=11, n=3000):
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    t1 = pa.table({
        "k": rng.integers(0, 40, n),
        "g": rng.choice(["a", "b", "c", None], n).tolist(),
        "v": rng.standard_normal(n),
    })
    t2 = pa.table({
        "k": rng.integers(0, 60, n // 2),
        "w": rng.integers(-5, 5, n // 2),
    })
    # repartition: LocalRelation scans are single-partition, which would
    # satisfy every clustering requirement and elide the exchange under test
    s.createDataFrame(t1).repartition(8).createOrReplaceTempView("t1")
    s.createDataFrame(t2).repartition(8).createOrReplaceTempView("t2")


def _run_both(mesh_session, sql):
    """Run once with the mesh exchange, once with the host shuffle."""
    _mk_tables(mesh_session)
    mesh_session.conf.set("spark.tpu.mesh.enabled", "true")
    got_mesh = _rows(mesh_session.sql(sql))
    mesh_session.conf.set("spark.tpu.mesh.enabled", "false")
    got_host = _rows(mesh_session.sql(sql))
    mesh_session.conf.set("spark.tpu.mesh.enabled", "true")
    assert got_mesh == got_host, sql
    return got_mesh


def test_mesh_sql_groupby_agg(mesh_session):
    out = _run_both(mesh_session,
                    "SELECT k, g, count(*) c, sum(v) s, min(v) mn "
                    "FROM t1 GROUP BY k, g")
    assert len(out) > 40


def test_mesh_sql_join(mesh_session):
    out = _run_both(mesh_session,
                    "SELECT t1.k, count(*) c, sum(t2.w) sw FROM t1 "
                    "JOIN t2 ON t1.k = t2.k GROUP BY t1.k ORDER BY t1.k")
    assert len(out) > 10


def test_mesh_sql_distinct_and_semi(mesh_session):
    _run_both(mesh_session, "SELECT DISTINCT g, k % 7 FROM t1")
    _run_both(mesh_session,
              "SELECT k, g FROM t1 WHERE k IN (SELECT k FROM t2 WHERE w > 0)")


def test_mesh_exchange_fires(mesh_session):
    """The metric proves the ICI path actually ran (not the host fallback)."""
    _mk_tables(mesh_session)
    df = mesh_session.sql("SELECT k, sum(v) FROM t1 GROUP BY k")
    df.collect()
    m = mesh_session._metrics.snapshot()["counters"]
    assert m.get("exchange.mesh", 0) >= 1
