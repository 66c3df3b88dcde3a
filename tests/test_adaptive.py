"""AQE partition-coalescing tests (reference: CoalesceShufflePartitionsSuite)."""

import pyarrow as pa
import pytest

import spark_tpu.api.functions as F
from spark_tpu.physical.adaptive import plan_merge_groups


def test_plan_merge_groups():
    assert plan_merge_groups([1, 1, 1, 10, 1], 3) == [[0, 1, 2], [3], [4]]
    assert plan_merge_groups([5, 5], 3) == [[0], [1]]
    assert plan_merge_groups([0, 0, 0], 3) == [[0, 1, 2]]


def test_coalesced_agg_correct(spark):
    # tiny shuffle partitions → coalesced into one, results unchanged
    spark.conf.set("spark.sql.adaptive.advisoryPartitionSizeInBytes",
                   1 << 30)
    try:
        df = spark.range(0, 1000, 1, 8)
        out = (df.groupBy((F.col("id") % 5).alias("m"))
               .agg(F.count("*").alias("c")).orderBy("m")
               .toArrow().to_pydict())
        assert out["c"] == [200] * 5
        snap = spark._metrics.snapshot()
        assert snap["counters"].get("aqe.partitions_coalesced", 0) > 0
    finally:
        spark.conf.unset("spark.sql.adaptive.advisoryPartitionSizeInBytes")


def test_coalesced_join_correct(spark):
    spark.conf.set("spark.sql.adaptive.advisoryPartitionSizeInBytes",
                   1 << 30)
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", -1)  # force shuffle
    try:
        a = spark.createDataFrame(pa.table({
            "k": list(range(50)), "v": list(range(50))}))
        b = spark.createDataFrame(pa.table({
            "k": list(range(0, 100, 2)), "w": list(range(50))}))
        out = a.join(b, on="k").agg(F.count("*").alias("c")) \
            .toArrow().to_pydict()
        assert out["c"] == [25]
    finally:
        spark.conf.unset("spark.sql.adaptive.advisoryPartitionSizeInBytes")
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")


def test_full_outer_join_never_broadcast(spark):
    """A replicated build side is unsound for full_outer (unmatched build
    rows would re-emit per probe partition) — the planner must pick the
    shuffled path however small the right side is."""
    l = spark.createDataFrame(pa.table({
        "k": [1, 2, 3, 4, 5, 6, 7, 8], "a": [1] * 8})).repartition(4)
    r = spark.createDataFrame(pa.table({"k": [1, 9], "b": [100, 900]}))
    l.createOrReplaceTempView("fo_l")
    r.createOrReplaceTempView("fo_r")
    out = spark.sql(
        "SELECT b FROM fo_l FULL OUTER JOIN fo_r ON fo_l.k = fo_r.k "
        "ORDER BY b NULLS LAST").toArrow().to_pydict()
    assert out["b"] == [100, 900] + [None] * 7


def test_aqe_broadcast_demotion(spark):
    """Initial plan picks a shuffled join (stats over threshold); runtime
    size of the filtered build side demotes it to broadcast and elides the
    probe-side shuffle (role of AdaptiveSparkPlanExec re-optimization +
    local shuffle read)."""
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", 200)
    try:
        a = spark.createDataFrame(pa.table({
            "k": list(range(1000)), "v": list(range(1000))})).repartition(4)
        b = spark.createDataFrame(pa.table({
            "k": list(range(0, 2000, 2)),
            "w": list(range(1000))})).repartition(4)
        a.createOrReplaceTempView("aqe_a")
        b.createOrReplaceTempView("aqe_b")
        out = spark.sql(
            "SELECT count(*) AS c FROM aqe_a JOIN "
            "(SELECT k, w FROM aqe_b WHERE w < 3) sb "
            "ON aqe_a.k = sb.k").toArrow().to_pydict()
        assert out["c"] == [3]
        snap = spark._metrics.snapshot()["counters"]
        assert snap.get("aqe.broadcast_demotions", 0) >= 1
        assert snap.get("aqe.probe_shuffles_elided", 0) >= 1
    finally:
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")


def test_aqe_demotion_disabled_when_adaptive_off(spark):
    spark.conf.set("spark.sql.adaptive.enabled", "false")
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", 200)
    before = spark._metrics.snapshot()["counters"].get(
        "aqe.broadcast_demotions", 0)
    try:
        a = spark.createDataFrame(pa.table({
            "k": list(range(100)), "v": list(range(100))})).repartition(4)
        b = spark.createDataFrame(pa.table({
            "k": list(range(0, 200, 2)), "w": list(range(100))}))
        out = a.join(b.filter("w < 3"), on="k") \
            .agg(F.count("*").alias("c")).toArrow().to_pydict()
        assert out["c"] == [3]
        snap = spark._metrics.snapshot()["counters"]
        assert snap.get("aqe.broadcast_demotions", 0) == before
    finally:
        spark.conf.unset("spark.sql.adaptive.enabled")
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")


def test_aqe_demotion_preserves_partitioning_dependent_agg(spark):
    """Probe-shuffle elision must NOT fire when an operator above the join
    relies on the join's hash partitioning (per-key agg over the join
    keys) — role of the reference's ValidateRequirements after AQE
    re-optimization. Results must stay correct either way."""
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", 200)
    try:
        a = spark.createDataFrame(pa.table({
            "k": [1, 2, 3, 4] * 250, "v": list(range(1000))})).repartition(4)
        b = spark.createDataFrame(pa.table({
            "k": list(range(0, 2000, 2)),
            "w": list(range(1000))})).repartition(4)
        a.createOrReplaceTempView("aqe_pk_a")
        b.createOrReplaceTempView("aqe_pk_b")
        out = spark.sql(
            "SELECT aqe_pk_a.k, count(*) c FROM aqe_pk_a JOIN "
            "(SELECT k FROM aqe_pk_b WHERE w < 3) sb "
            "ON aqe_pk_a.k = sb.k GROUP BY aqe_pk_a.k "
            "ORDER BY aqe_pk_a.k").toArrow().to_pydict()
        assert out["k"] == [2, 4] and out["c"] == [250, 250]
    finally:
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")


# ---------------------------------------------------------------------------
# Runtime-adaptive execution: runtime join filters, stage-boundary
# re-admission, parquet-stats whole-tier admission, skew re-partitioning
# (reference: dynamic partition pruning / runtime filters in
# sqlx/dynamicpruning + AQEShuffleReadExec skew handling, recast for the
# eager-exchange TPU pipeline: the build side's key domain is harvested
# HOST-SIDE from already-synced state and pushed into the not-yet-run
# probe shuffle). Differentials run fresh sessions per leg so metric
# counters isolate the adaptive layer's effect.
# ---------------------------------------------------------------------------

import os
import tempfile

import numpy as np

from spark_tpu import TpuSession


def _session(name, extra=None):
    conf = {"spark.sql.shuffle.partitions": 4,
            "spark.sql.autoBroadcastJoinThreshold": -1}
    conf.update(extra or {})
    return TpuSession(name, conf)


def _counters(s, *prefixes):
    snap = s._metrics.snapshot()["counters"]
    return {k: v for k, v in snap.items()
            if any(k.startswith(p) for p in prefixes)}


def _rf_join_leg(name, adaptive, build_query):
    s = _session(f"{name}-{adaptive}",
                 {"spark.tpu.adaptive.runtimeFilter":
                  "true" if adaptive else "false"})
    try:
        out = build_query(s)
        return out, _counters(s, "adaptive.", "shuffle.bytes_shipped",
                              "kernel.launches")
    finally:
        s.stop()


def test_runtime_filter_join_differential():
    """A selective build side ([5,6,7] vs a 2000-key probe) installs a
    range filter on the probe shuffle: identical results, measurably
    fewer shuffled bytes, rows pruned before the exchange."""
    def q(s):
        a = s.createDataFrame(pa.table({
            "k": list(range(2000)), "v": list(range(2000))})).repartition(4)
        b = s.createDataFrame(pa.table({
            "k": [5, 6, 7], "w": [50, 60, 70]})).repartition(2)
        return a.join(b, on="k").orderBy("k").toArrow().to_pydict()

    off, m_off = _rf_join_leg("rf-join", False, q)
    on, m_on = _rf_join_leg("rf-join", True, q)
    assert off == on
    assert on["k"] == [5, 6, 7]
    assert m_on.get("adaptive.runtime_filters_installed", 0) >= 1
    assert m_on.get("adaptive.filter_rows_pruned", 0) >= 1000
    # host shuffles ship fewer bytes; the mesh path prunes before
    # staging instead (bytes_shipped counts host transfers only)
    assert m_on["shuffle.bytes_shipped"] <= m_off["shuffle.bytes_shipped"]
    assert "adaptive.runtime_filters_installed" not in m_off


def test_runtime_filter_join_agg_differential():
    def q(s):
        a = s.createDataFrame(pa.table({
            "k": [i % 40 for i in range(4000)],
            "v": list(range(4000))})).repartition(4)
        b = s.createDataFrame(pa.table({
            "k": [3, 4, 5], "w": [30, 40, 50]})).repartition(2)
        return (a.join(b, on="k").groupBy("k")
                .agg(F.count("*").alias("c"), F.sum("v").alias("sv"))
                .orderBy("k").toArrow().to_pydict())

    off, m_off = _rf_join_leg("rf-agg", False, q)
    on, m_on = _rf_join_leg("rf-agg", True, q)
    assert off == on
    assert on["c"] == [100, 100, 100]
    assert m_on.get("adaptive.runtime_filters_installed", 0) >= 1
    assert m_on.get("adaptive.filter_rows_pruned", 0) > 0


def test_runtime_filter_string_keys_differential():
    """Dict-encoded string keys: the build side's StringDict values form
    the filter domain; probe rows prune through a code-level lookup table
    (no string comparisons on device)."""
    def q(s):
        a = s.createDataFrame(pa.table({
            "k": [f"u{i % 50:03d}" for i in range(2000)],
            "v": list(range(2000))})).repartition(4)
        b = s.createDataFrame(pa.table({
            "k": ["u005", "u006"], "w": [1, 2]})).repartition(2)
        return a.join(b, on="k").orderBy("v").toArrow().to_pydict()

    off, m_off = _rf_join_leg("rf-str", False, q)
    on, m_on = _rf_join_leg("rf-str", True, q)
    assert off == on
    assert len(on["v"]) == 80
    assert m_on.get("adaptive.runtime_filters_installed", 0) >= 1
    assert m_on.get("adaptive.filter_rows_pruned", 0) == 1920
    assert m_on["shuffle.bytes_shipped"] <= m_off["shuffle.bytes_shipped"]


def test_runtime_filter_tpcds_q3_differential():
    """TPC-DS mini q3 with broadcast disabled: the dimension filters
    (i_manufact_id=28, d_moy=11) make both build sides selective —
    results identical with the filter layer installed."""
    from test_whole_query import Q3_SORTED
    from tpcds_mini import gen_tpcds

    tabs = gen_tpcds()
    outs = {}
    for adaptive in (False, True):
        s = _session(f"rf-q3-{adaptive}",
                     {"spark.tpu.adaptive.runtimeFilter":
                      "true" if adaptive else "false"})
        try:
            # register pre-partitioned views so the joins actually
            # shuffle (single-partition local tables co-locate and the
            # plan collapses to one stage with nothing to filter)
            for name, t in tabs.items():
                (s.createDataFrame(t).repartition(4)
                 .createOrReplaceTempView(name))
            outs[adaptive] = s.sql(Q3_SORTED).toArrow().to_pydict()
            if adaptive:
                m = _counters(s, "adaptive.")
                assert m.get("adaptive.runtime_filters_installed", 0) >= 1
        finally:
            s.stop()
    assert outs[False] == outs[True]
    assert len(outs[True]["sum_agg"]) > 0


def test_runtime_filter_cluster_differential():
    """2-worker cluster leg: adaptive on/off must agree when map stages
    ship to workers (the filter layer must never corrupt a cluster
    shuffle, whether or not it engages on this path)."""
    from spark_tpu.exec.cluster import LocalCluster

    rng = np.random.default_rng(20)
    t = pa.table({"k": rng.integers(0, 500, 4000),
                  "v": rng.integers(-20, 80, 4000)})
    dim = pa.table({"k": [7, 8, 9], "w": [70, 80, 90]})
    outs = {}
    for adaptive in (False, True):
        s = _session(f"rf-cluster-{adaptive}",
                     {"spark.tpu.adaptive.runtimeFilter":
                      "true" if adaptive else "false"})
        cluster = LocalCluster(num_workers=2)
        s.attachSqlCluster(cluster)
        try:
            a = s.createDataFrame(t).repartition(4)
            b = s.createDataFrame(dim).repartition(2)
            df = (a.join(b, on="k").groupBy("k")
                  .agg(F.count("*").alias("c"), F.sum("v").alias("sv"))
                  .orderBy("k"))
            outs[adaptive] = df.toArrow().to_pydict()
        finally:
            s.stop()
    assert outs[False] == outs[True]


def test_runtime_filter_zero_launch_identity():
    """Obs contract: arming the adaptive layer on a FILTER-FREE plan
    (no shuffled hash join → nothing to harvest) must not add a single
    kernel launch — the harvest reads only already-synced host state."""
    def q(s):
        df = s.createDataFrame(pa.table({
            "k": [i % 7 for i in range(3000)],
            "v": list(range(3000))})).repartition(4)
        return (df.groupBy("k").agg(F.sum("v").alias("sv"))
                .orderBy("k").toArrow().to_pydict())

    off, m_off = _rf_join_leg("rf-zero", False, q)
    on, m_on = _rf_join_leg("rf-zero", True, q)
    assert off == on
    assert m_on["kernel.launches"] == m_off["kernel.launches"]
    assert "adaptive.runtime_filters_installed" not in m_on
    assert "adaptive.filter_rows_pruned" not in m_on


# -- stage-boundary re-admission --------------------------------------------

def _csv_fixture(tmp_path):
    csv = str(tmp_path / "re_t.csv")
    with open(csv, "w") as f:
        f.write("k,v\n")
        for i in range(500):
            f.write(f"{i % 10},{i}\n")
    return csv


def _readmission_leg(name, csv, extra):
    conf = {"spark.tpu.compile.whole.minRows": 1}
    conf.update(extra)
    s = _session(name, conf)
    try:
        a = (s.read.option("header", "true").option("inferSchema", "true")
             .csv(csv).repartition(4))
        b = s.createDataFrame(pa.table({
            "k": [5, 6, 7], "w": [50, 60, 70]})).repartition(2)
        df = (a.join(b, on="k").groupBy("k")
              .agg(F.count("*").alias("c")).orderBy("k"))
        out = df.toArrow().to_pydict()
        ctx = getattr(df.query_execution, "_last_ctx", None)
        dec = getattr(ctx, "readmission_decision", None)
        spans = [d for d in s.tracer.since(0)
                 if d.get("name") == "adaptive.readmission"]
        return out, _counters(s, "adaptive."), dec, spans
    finally:
        s.stop()


def test_readmission_tier_flip(tmp_path):
    """An external scan (rows unknown at plan time) keeps the initial
    plan on the stage tier; once the scan stage materializes, the
    measured sizes re-admit the remainder to the whole tier — asserted
    via the TierDecision the re-planner recorded AND its trace span."""
    csv = _csv_fixture(tmp_path)
    off = _readmission_leg("readmit-off", csv,
                           {"spark.tpu.adaptive.readmission": "false"})
    on = _readmission_leg("readmit-on", csv,
                          {"spark.tpu.adaptive.readmission": "true"})
    assert off[0] == on[0]
    assert on[0]["c"] == [50, 50, 50]
    assert "adaptive.readmissions" not in off[1]
    assert on[1].get("adaptive.readmissions", 0) >= 1
    dec = on[2]
    assert dec is not None and dec.tier == "whole"
    assert dec.details.get("readmitted") is True
    assert on[3], "adaptive.readmission span missing from the trace"
    assert on[3][0]["args"]["tier"] == "whole"


def test_readmission_history_replan(tmp_path):
    """Recurring queries skip the mid-query flip: the warm-start manifest
    records the first run's observed sizes, and the SECOND run re-plans
    to the whole tier from history before the first batch executes."""
    csv = _csv_fixture(tmp_path)
    conf = {"spark.tpu.adaptive.readmission": "true",
            "spark.tpu.cache.dir": str(tmp_path / "cache"),
            "spark.tpu.cache.result.enabled": "false"}
    out1, m1, _, _ = _readmission_leg("readmit-h1", csv, conf)
    out2, m2, _, _ = _readmission_leg("readmit-h2", csv, conf)
    assert out1 == out2
    assert m1.get("adaptive.readmissions", 0) >= 1
    assert m2.get("adaptive.history_replans", 0) >= 1


# -- parquet footer-statistics admission ------------------------------------

def test_parquet_stats_whole_tier_admission(tmp_path):
    """Footer row-group counts admit an external parquet scan to the
    whole tier AT PLAN TIME (no stage ever executes host-side); with the
    stats feed disabled the same plan stays stage-at-a-time."""
    import pyarrow.parquet as pq

    pqf = str(tmp_path / "adm_t.parquet")
    pq.write_table(pa.table({"k": [i % 10 for i in range(500)],
                             "v": list(range(500))}), pqf)
    outs, metrics = {}, {}
    for stats_on in (False, True):
        s = _session(f"pq-adm-{stats_on}", {
            "spark.tpu.compile.whole.minRows": 1,
            "spark.tpu.adaptive.parquetStats":
                "true" if stats_on else "false"})
        try:
            a = s.read.parquet(pqf).repartition(4)
            b = s.createDataFrame(pa.table({
                "k": [5, 6, 7], "w": [50, 60, 70]})).repartition(2)
            df = (a.join(b, on="k").groupBy("k")
                  .agg(F.count("*").alias("c")).orderBy("k"))
            outs[stats_on] = df.toArrow().to_pydict()
            metrics[stats_on] = _counters(s, "whole_query.")
        finally:
            s.stop()
    assert outs[False] == outs[True]
    assert outs[True]["c"] == [50, 50, 50]
    assert metrics[True].get("whole_query.dispatches", 0) >= 1
    assert metrics[False].get("whole_query.dispatches", 0) == 0


# -- mesh skew re-partitioning ----------------------------------------------

def test_skew_split_replans_on_mesh(monkeypatch):
    """When quota-ladder retries exhaust on a hot key, the adaptive layer
    splits the batch set and re-plans each half ON the mesh instead of
    abandoning the whole exchange to the host-shuffle fallback."""
    import jax

    import spark_tpu.parallel.mesh_exchange as ME

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    # first quota overflow exhausts the ladder → fallback decision point
    monkeypatch.setattr(ME, "_MAX_QUOTA_RETRIES", 1)
    rng = np.random.default_rng(11)
    t = pa.table({"k": np.zeros(4000, dtype=np.int64),
                  "v": rng.integers(0, 1000, 4000)})
    outs, metrics = {}, {}
    for skew_on in (False, True):
        s = TpuSession(f"skew-{skew_on}", {
            "spark.sql.shuffle.partitions": 8,
            "spark.tpu.batch.capacity": 1 << 10,
            "spark.tpu.mesh.enabled": "true",
            "spark.tpu.adaptive.skewRepartition":
                "true" if skew_on else "false"})
        try:
            df = s.createDataFrame(t).repartition(8)
            outs[skew_on] = sorted(
                tuple(r) for r in df.repartition(8, "k").collect())
            metrics[skew_on] = _counters(s, "adaptive.", "exchange.")
        finally:
            s.stop()
    assert outs[False] == outs[True]
    assert metrics[False].get("exchange.mesh_fallback", 0) >= 1
    assert metrics[True].get("adaptive.skew_repartitions", 0) >= 1
    assert metrics[True].get("exchange.mesh_fallback", 0) == 0


# -- plan_lint honesty ------------------------------------------------------

def test_plan_lint_runtime_filter_degrades_honestly(spark):
    """With the filter layer armed, a shuffled single-key join's launch
    prediction is runtime-dependent — the report degrades to exact=False
    with the adaptive reason named (never silently wrong)."""
    spark.conf.set("spark.sql.autoBroadcastJoinThreshold", -1)
    spark.conf.set("spark.tpu.adaptive.runtimeFilter", "true")
    try:
        a = spark.createDataFrame(pa.table({
            "k": list(range(100)), "v": list(range(100))})).repartition(4)
        b = spark.createDataFrame(pa.table({
            "k": [1, 2], "w": [10, 20]})).repartition(2)
        report = a.join(b, on="k").query_execution.analysis_report()
        assert not report.exact
        assert any("adaptive runtime join filter" in r
                   for r in report.inexact_reasons), report.inexact_reasons
    finally:
        spark.conf.unset("spark.sql.autoBroadcastJoinThreshold")
        spark.conf.unset("spark.tpu.adaptive.runtimeFilter")


def test_runtime_filter_install_is_a_span_and_explain_analyze_classifies_it():
    """With the filter armed the launch model says it is inexact; EXPLAIN
    ANALYZE then classifies the difference and reports no error. The
    install itself is on the timeline, and the ledger balances after."""
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    s = _session("rf-analyze", {"spark.tpu.adaptive.runtimeFilter": "true",
                                "spark.tpu.batch.capacity": 1 << 12})
    try:
        a = s.createDataFrame(pa.table({
            "k": list(range(2000)), "v": list(range(2000))})).repartition(4)
        b = s.createDataFrame(pa.table({
            "k": [5, 6, 7], "w": [50, 60, 70]})).repartition(2)
        df = (a.join(b, on="k").groupBy("k").agg(F.sum("v").alias("sv"))
              .orderBy("k"))
        report = df.query_execution.analyzed_report()
        assert not report.has_unexplained_drift, report.render()
        m = _counters(s, "adaptive.")
        assert m.get("adaptive.runtime_filters_installed", 0) >= 1
        assert m.get("adaptive.filter_rows_pruned", 0) >= 1000
        installs = [d for d in s.tracer.since(0)
                    if d["name"] == "adaptive.runtime_filter"]
        assert installs, "the filter's install left no span"
        assert GLOBAL_LEDGER.verify() == []
    finally:
        s.stop()


def test_plan_lint_broadcast_join_stays_exact_with_adaptive(spark):
    """Exactness case: a broadcast join never takes a runtime filter
    (the build side is already local), so arming the layer must NOT
    degrade its analysis."""
    spark.conf.set("spark.tpu.adaptive.runtimeFilter", "true")
    try:
        a = spark.createDataFrame(pa.table({
            "k": list(range(100)), "v": list(range(100))})).repartition(4)
        b = spark.createDataFrame(pa.table({"k": [1, 2], "w": [10, 20]}))
        report = a.join(b, on="k").query_execution.analysis_report()
        assert report.exact, report.inexact_reasons
    finally:
        spark.conf.unset("spark.tpu.adaptive.runtimeFilter")


def test_plan_lint_readmission_named(spark):
    """Re-admission honesty: any staged plan may collapse mid-query with
    the re-admission layer armed — the analyzer names that, and an
    exchange-free plan stays exact (nothing to re-admit)."""
    spark.conf.set("spark.tpu.adaptive.readmission", "true")
    try:
        df = spark.createDataFrame(pa.table({
            "k": [i % 5 for i in range(100)],
            "v": list(range(100))})).repartition(4).groupBy("k").count()
        report = df.query_execution.analysis_report()
        assert not report.exact
        assert any("adaptive re-admission" in r
                   for r in report.inexact_reasons), report.inexact_reasons
        flat = spark.createDataFrame(pa.table({
            "k": [1, 2, 3]})).select((F.col("k") + 1).alias("k1"))
        flat_report = flat.query_execution.analysis_report()
        assert flat_report.exact, flat_report.inexact_reasons
    finally:
        spark.conf.unset("spark.tpu.adaptive.readmission")
