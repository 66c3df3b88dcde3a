"""Mesh-native SPMD stage fusion (parallel/mesh_fusion.py +
mesh_exchange.py): MULTICHIP differential tests against the unfused mesh
path and the host shuffle oracle, the one-dispatch-per-stage regression
guard, the donated-send-buffer HBM watermark, and obs attribution under
shard_map.

The tier-1 harness runs 8 virtual CPU devices (conftest), so the
8-device tests run in CI; they skip gracefully on smaller device counts
while the 2-device variant keeps coverage."""

import gc

import jax
import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.api.functions as F
from spark_tpu.obs.resources import GLOBAL_LEDGER
from spark_tpu.parallel import mesh_fusion as MF
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


@pytest.fixture()
def mesh_spark(spark):
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    yield spark
    for k in ("spark.tpu.fusion.enabled", "spark.tpu.fusion.minRows",
              "spark.tpu.fusion.mesh", "spark.tpu.mesh.enabled"):
        spark.conf.unset(k)


@pytest.fixture()
def mdata(mesh_spark):
    spark = mesh_spark
    rng = np.random.default_rng(17)
    n = 6000
    v = rng.integers(-50, 100, n)
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": v,
        # nullable column: validity planes must survive the all-to-all
        "nv": pa.array([None if i % 7 == 0 else int(x)
                        for i, x in enumerate(v)], type=pa.int64()),
        "s": [f"cat{i % 5}" for i in range(n)],
    })).createOrReplaceTempView("mf_t")
    spark.createDataFrame(pa.table({
        "dk": np.arange(13, dtype=np.int64),
        "label": [f"lab{i % 3}" for i in range(13)],
    })).createOrReplaceTempView("mf_dim")
    return spark


def _modes(spark, build, sort_cols):
    """The same query in four modes: mesh-fused, mesh-legacy
    (materialize-then-collective), fusion-off mesh, and the host shuffle
    oracle — all must agree row-for-row."""
    outs = {}
    for mode, confs in (
            ("mesh_fused", {}),
            ("mesh_legacy", {"spark.tpu.fusion.mesh": "false"}),
            ("mesh_unfused", {"spark.tpu.fusion.enabled": "false"}),
            ("host", {"spark.tpu.mesh.enabled": "false"})):
        for k, val in confs.items():
            spark.conf.set(k, val)
        try:
            outs[mode] = (build().toPandas().sort_values(sort_cols)
                          .reset_index(drop=True))
        finally:
            for k in confs:
                spark.conf.unset(k)
    want = outs.pop("mesh_fused")
    for mode, got in outs.items():
        assert want.equals(got), f"{mode} diverged from mesh_fused"
    return want


# ---------------------------------------------------------------------------
# differentials: fused mesh vs unfused mesh vs host oracle
# ---------------------------------------------------------------------------

def test_mesh_fused_agg_differential(mdata):
    _need_devices(8)
    spark = mdata
    out = _modes(
        spark,
        lambda: (spark.sql("select k, v * 2 as v2, nv, s from mf_t "
                           "where v > 0")
                 .repartition(8, "k").groupBy("k")
                 .agg(F.sum("v2").alias("sv"), F.count("*").alias("c"),
                      F.sum("nv").alias("snv"))),
        ["k"])
    assert len(out) == 13


def test_mesh_fused_join_differential(mdata):
    """Shuffled hash join: BOTH sides redistribute over mesh exchanges
    (broadcast disabled) and the reduce-side join build/probe consumes
    the shard-resident exchange output."""
    _need_devices(4)
    spark = mdata
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", "-1")
    try:
        _modes(
            spark,
            lambda: spark.sql(
                "select label, sum(v) sv, count(*) c from mf_t "
                "join mf_dim on k = dk where v > 10 group by label"),
            ["label"])
    finally:
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")


def test_mesh_fused_tpcds_q3_sharded_differential(mesh_spark, spark):
    """Sharded TPC-DS mini q3: the fact table redistributes over the
    8-device mesh before the join spine (the acceptance query)."""
    _need_devices(8)
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.sql("select * from store_sales") \
        .repartition(8, "ss_item_sk") \
        .createOrReplaceTempView("mf_store_sales")
    q3 = """
        SELECT dt.d_year, item.i_brand_id AS brand_id,
               SUM(ss_ext_sales_price) AS sum_agg
        FROM date_dim dt, mf_store_sales store_sales, item
        WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
          AND store_sales.ss_item_sk = item.i_item_sk
          AND item.i_manufact_id = 28 AND dt.d_moy = 11
        GROUP BY dt.d_year, item.i_brand_id"""
    out = _modes(spark, lambda: spark.sql(q3), ["d_year", "brand_id"])
    assert len(out) > 0


def test_mesh_two_device_variant(mdata):
    """2-device CPU-mesh variant: the smallest mesh keeps tier-1
    coverage even when the harness runs under 8 devices."""
    _need_devices(2)
    spark = mdata
    _modes(
        spark,
        lambda: (spark.sql("select k, v + 1 as v1, s from mf_t "
                           "where v != 7")
                 .repartition(2, "k").groupBy("k")
                 .agg(F.sum("v1").alias("sv"))),
        ["k"])


# ---------------------------------------------------------------------------
# one sharded dispatch per stage per step
# ---------------------------------------------------------------------------

def _kind_delta(run):
    before = dict(KC.launches_by_kind)
    run()
    after = dict(KC.launches_by_kind)
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_mesh_single_dispatch_per_stage(mdata):
    """Acceptance: a scan→filter→project→mesh-shuffle stage executes as
    ONE SPMD dispatch per step — regardless of the input batch count —
    with no separate pipeline launch and no per-batch partition kernel."""
    _need_devices(4)
    spark = mdata
    q = lambda: (spark.sql("select k, v * 3 as v3 from mf_t "  # noqa: E731
                           "where v > 25").repartition(4, "k").toArrow())
    q()  # warm: compile the stage program, device-cache the scan
    delta = _kind_delta(q)
    assert delta.get("mesh_stage", 0) == 1, delta
    assert delta.get("pipeline", 0) == 0, delta
    assert sum(delta.values()) == 1, delta

    # the legacy composition pays a pipeline dispatch per map batch on
    # top of the collective (6000 rows / 4096-capacity tiles = 2)
    spark.conf.set("spark.tpu.fusion.mesh", "false")
    q()  # warm the legacy kernels
    legacy = _kind_delta(q)
    assert legacy.get("mesh_stage", 0) == 1, legacy
    assert legacy.get("pipeline", 0) == 2, legacy


def test_mesh_quota_retry_counts_as_extra_dispatch(mesh_spark, spark):
    """Pathological skew overflows the per-(src,dst) quota: the stage
    re-dispatches with a doubled quota and the KernelCache counts every
    attempt (the plan analyzer predicts the same count — see
    test_plan_analysis.test_mesh_exchange_prediction_exact)."""
    _need_devices(4)
    n = 6000
    spark.createDataFrame(pa.table({
        "k": np.ones(n, np.int64) * 5,  # every live row → one reducer
        "v": np.arange(n, dtype=np.int64),
    })).createOrReplaceTempView("mf_skew")
    q = lambda: (spark.sql("select k, v from mf_skew")  # noqa: E731
                 .repartition(4, "k").toArrow())
    q()
    delta = _kind_delta(q)
    report = (spark.sql("select k, v from mf_skew").repartition(4, "k")
              .query_execution.analysis_report())
    assert delta.get("mesh_stage", 0) >= 2, delta
    assert report.predicted_launches.get("mesh_stage") == \
        delta["mesh_stage"], (report.predicted_launches, delta)


# ---------------------------------------------------------------------------
# donated send buffers: the DeviceLedger watermark is the scoreboard
# ---------------------------------------------------------------------------

def test_mesh_stage_program_donates_send_buffers(mdata, monkeypatch):
    """donate_argnums rides the mesh stage program (cache key carries the
    donation flag) and the donated run's per-window HBM watermark sits
    BELOW the undonated oracle's: donated staging buffers release at
    dispatch (the arrays are invalidated), undonated ones overlap the
    received output tiles."""
    _need_devices(4)
    spark = mdata
    rng = np.random.default_rng(23)
    n = 40000
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 1 << 12, n),
        "v": rng.integers(0, 1000, n),
    })).createOrReplaceTempView("mf_big")
    q = lambda: (spark.sql("select k, v * 2 as v2 from mf_big "  # noqa: E731
                           "where v > 10").repartition(4, "k").toArrow())

    q()  # warm donated program
    donated_keys = [k for k in KC._cache
                    if k and k[0] == "mesh_stage" and k[-1] is True]
    assert donated_keys, "no mesh stage program compiled with donation"

    monkeypatch.setattr(MF, "DONATE_DEFAULT", False)
    q()  # warm undonated program
    undonated_keys = [k for k in KC._cache
                      if k and k[0] == "mesh_stage" and k[-1] is False]
    assert undonated_keys, "undonated oracle program never compiled"

    def window_peak():
        gc.collect()
        GLOBAL_LEDGER.begin_window()
        q()
        return GLOBAL_LEDGER.window_peak()

    peak_undonated = window_peak()
    monkeypatch.setattr(MF, "DONATE_DEFAULT", True)
    peak_donated = window_peak()
    # staged send planes: 2 int64 columns + mask over ≥P*shard_cap slots
    assert peak_undonated - peak_donated >= 1 << 19, \
        (peak_undonated, peak_donated)


# ---------------------------------------------------------------------------
# obs: the single SPMD dispatch attributes like the single-device path
# ---------------------------------------------------------------------------

def test_mesh_dispatch_attribution_total_matches_counter(mdata):
    """The mesh stage's launches re-bucket to the dispatching exchange
    (fused_members re-attribution included) and the per-operator
    attribution total equals the global KernelCache delta — no dispatch
    escapes the operator scope under shard_map."""
    _need_devices(4)
    spark = mdata

    def build():
        return (spark.sql("select k, v * 2 as v2 from mf_t where v > 0")
                .repartition(4, "k").groupBy("k")
                .agg(F.sum("v2").alias("sv")))

    build().toArrow()  # warm
    before = KC.launches
    df = build()
    df.toArrow()
    global_delta = KC.launches - before
    graph = df.query_execution.plan_graph()
    attributed = sum(v for nd in graph
                     for v in (nd.get("launches") or {}).values())
    assert attributed == global_delta
    mesh_attr = [nd for nd in graph
                 if (nd.get("launches") or {}).get("mesh_stage")]
    assert mesh_attr, "mesh_stage dispatch not attributed to any operator"


def test_mesh_stage_explain_analyze_reconciles_and_trace_nests(mdata):
    """EXPLAIN ANALYZE over a fused repartition + aggregate on the mesh:
    the mesh_stage dispatches are the predicted ones, nothing drifts
    unexplained, every launch has an operator, the spans recorded around
    the shard_map dispatch still nest, and the ledger balances once the
    donated send buffers are gone."""
    from test_observability import _assert_well_formed

    _need_devices(8)
    spark = mdata
    spark.conf.set("spark.sql.shuffle.partitions", "8")
    try:
        df = (spark.sql("select k, v * 2 as v2 from mf_t where v > 0")
              .repartition(8, "k").groupBy("k").agg(F.sum("v2").alias("s")))
        report = df.query_execution.analyzed_report()
    finally:
        spark.conf.unset("spark.sql.shuffle.partitions")
    assert not report.has_unexplained_drift, report.render()
    assert report.measured.get("mesh_stage", 0) >= 1, dict(report.measured)
    assert report.predicted.get("mesh_stage") == \
        report.measured["mesh_stage"], report.render()
    attributed = sum(v for nd in report.nodes
                     for v in (nd.get("launches") or {}).values())
    assert attributed == sum(report.measured.values()), report.render()
    complete = _assert_well_formed(spark.tracer.to_chrome_trace())
    assert any((e.get("args") or {}).get("launches", 0) > 0
               for e in complete), "no span carries kernel attribution"
    assert GLOBAL_LEDGER.verify() == []


def test_mesh_zero_launch_obs_overhead(mdata):
    """The obs contract holds under shard_map: metrics + tracing add
    ZERO kernel launches to a mesh-fused query."""
    _need_devices(4)
    spark = mdata
    q = lambda: (spark.sql("select k, v * 2 as v2 from mf_t "  # noqa: E731
                           "where v > 0").repartition(4, "k").toArrow())

    def delta():
        q()  # warm
        return _kind_delta(q)

    spark.conf.set("spark.tpu.ui.operatorMetrics", "true")
    spark.conf.set("spark.tpu.trace.enabled", "true")
    try:
        with_obs = delta()
        spark.conf.set("spark.tpu.ui.operatorMetrics", "false")
        spark.conf.set("spark.tpu.trace.enabled", "false")
        without = delta()
        assert with_obs == without
    finally:
        spark.conf.unset("spark.tpu.ui.operatorMetrics")
        spark.conf.unset("spark.tpu.trace.enabled")
