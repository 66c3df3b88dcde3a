"""Plan/trace analyzer (spark_tpu/analysis/plan_lint.py).

Acceptance gate: on the fusion differential suite (agg, join+agg, limit,
TPC-DS mini q3/q7), `explain("analysis")`'s predicted per-kind kernel
launch counts must equal the measured KernelCache launch counters EXACTLY
— fusion on and off. The prediction models one warm execution; the test
warms once (compiles + device-cached scans + memo priming) and measures a
second run, the same steady-state discipline the fusion dispatch tests
use (the reference gates EXPLAIN CODEGEN with codegen-metrics checks the
same way)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


@pytest.fixture()
def fusion_conf(spark):
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    yield spark
    spark.conf.unset("spark.tpu.fusion.enabled")
    spark.conf.unset("spark.tpu.fusion.minRows")


@pytest.fixture()
def data(spark):
    rng = np.random.default_rng(7)
    n = 5000
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": rng.integers(-50, 100, n),
        "f": rng.random(n),
        "s": [f"cat{i % 5}" for i in range(n)],
    })).createOrReplaceTempView("an_t")
    dim = pa.table({
        "dk": np.arange(13, dtype=np.int64),
        "label": [f"lab{i % 3}" for i in range(13)],
    })
    spark.createDataFrame(dim).createOrReplaceTempView("an_dim")
    return spark


Q_AGG = ("select k, sum(v * 2) sv, count(*) c, min(v) mn, max(v+1) mx, "
         "avg(f) af from an_t where v > 0 group by k")
Q_JOIN_AGG = ("select label, sum(v) sv, count(*) c from an_t "
              "join an_dim on k = dk where v > 10 group by label")
Q_LIMIT = ("select k + v * 100 as key2 from an_t where v > 95 "
           "order by key2 limit 17")
Q3 = """
    SELECT dt.d_year, item.i_brand_id AS brand_id,
           SUM(ss_ext_sales_price) AS sum_agg
    FROM date_dim dt, store_sales, item
    WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
      AND store_sales.ss_item_sk = item.i_item_sk
      AND item.i_manufact_id = 28 AND dt.d_moy = 11
    GROUP BY dt.d_year, item.i_brand_id"""
Q7 = """
    SELECT i.i_category, AVG(ss_quantity) AS agg1, COUNT(*) AS cnt
    FROM store_sales ss
    JOIN item i ON ss.ss_item_sk = i.i_item_sk
    JOIN date_dim d ON ss.ss_sold_date_sk = d.d_date_sk
    WHERE d.d_year = 1999
    GROUP BY i.i_category"""


def _predicted_vs_measured_df(build):
    """(analysis report, measured by-kind launch delta of one warm run)
    for a DataFrame builder (fresh DataFrame per run)."""
    df = build()
    report = df.query_execution.analysis_report()
    df.toArrow()  # warm: compile kernels, device-cache scans, prime memos
    before = dict(KC.launches_by_kind)
    build().toArrow()
    after = dict(KC.launches_by_kind)
    measured = {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}
    return report, measured


def _predicted_vs_measured(spark, sql):
    return _predicted_vs_measured_df(lambda: spark.sql(sql))


def _whole_tier_cold_then_warm(build):
    """The whole tier's two predictions, each beside the execution it is
    of: (report, measured launches) in a process that has not run the
    plan (tests/conftest.py empties the process's memory before every
    test), where the capacity ladder is climbed, and again after that
    execution, which starts from the capacities the first ended with."""
    out = []
    for _ in range(2):
        report = build().query_execution.analysis_report()
        before = dict(KC.launches_by_kind)
        build().toArrow()
        out.append((report, {
            k: v - before.get(k, 0) for k, v in KC.launches_by_kind.items()
            if v != before.get(k, 0)}))
    return out


def _assert_exact_df(build):
    report, measured = _predicted_vs_measured_df(build)
    assert report.exact, report.inexact_reasons
    assert report.predicted_launches == measured, (
        f"predicted {dict(sorted(report.predicted_launches.items()))} != "
        f"measured {dict(sorted(measured.items()))}\n{report.render()}")


def _assert_exact(spark, sql):
    _assert_exact_df(lambda: spark.sql(sql))


# ---------------------------------------------------------------------------
# acceptance: predicted == measured, fusion on AND off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", ["true", "false"])
def test_agg_launch_prediction_exact(fusion_conf, data, enabled):
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(data, Q_AGG)


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_join_agg_launch_prediction_exact(fusion_conf, data, enabled):
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(data, Q_JOIN_AGG)


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_limit_launch_prediction_exact(fusion_conf, data, enabled):
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(data, Q_LIMIT)


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_tpcds_q3_q7_launch_prediction_exact(fusion_conf, spark, enabled):
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(spark, Q3)
    _assert_exact(spark, Q7)


def test_total_matches_kernel_launch_metric(fusion_conf, data):
    """The report's total equals the per-query kernel.launches SQLMetric
    delta the scheduler records (same ground truth, metric plumbing)."""
    data.conf.set("spark.tpu.fusion.enabled", "true")
    df = data.sql(Q_AGG)
    report = df.query_execution.analysis_report()
    df.toArrow()  # warm
    before = data._metrics.snapshot()["counters"].get("kernel.launches", 0)
    data.sql(Q_AGG).toArrow()
    after = data._metrics.snapshot()["counters"].get("kernel.launches", 0)
    assert report.total == after - before


# ---------------------------------------------------------------------------
# minRows runtime gate: fused PLAN, unfused runtime kernels — still exact
# ---------------------------------------------------------------------------

def test_min_rows_gate_prediction_exact(spark, data):
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    try:
        # default minRows (128k tile rows) far exceeds the 5k-row table:
        # the analyzer must predict the UNFUSED runtime kernels under the
        # fused plan, and say why
        report, measured = _predicted_vs_measured(spark, Q_AGG)
        assert report.exact, report.inexact_reasons
        assert report.predicted_launches == measured, report.render()
        assert "fused_agg" not in report.predicted_launches
        assert any("minRows" in n for s in report.stages
                   for n in s["notes"])
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")


# ---------------------------------------------------------------------------
# explain("analysis") surface + boundary explanations + hazards
# ---------------------------------------------------------------------------

def test_explain_analysis_renders(fusion_conf, data, capsys):
    data.conf.set("spark.tpu.fusion.enabled", "true")
    data.sql(Q_AGG).explain("analysis")
    out = capsys.readouterr().out
    assert "== Plan Analysis ==" in out
    assert "predicted launches" in out
    assert "FUSED" in out
    assert "minRows" in out          # the runtime gate is explained
    assert "fused_agg" in out


def test_sort_consume_boundary_explained(fusion_conf, data):
    data.conf.set("spark.tpu.fusion.enabled", "true")
    report = data.sql(Q_LIMIT).query_execution.analysis_report()
    assert any("Sort" in b and "UNFUSED" in b
               for b in report.fusion_boundaries), report.fusion_boundaries


def test_fusion_off_boundary_explained(fusion_conf, data):
    data.conf.set("spark.tpu.fusion.enabled", "false")
    report = data.sql(Q_AGG).query_execution.analysis_report()
    assert any("spark.tpu.fusion.enabled=false" in b
               for b in report.fusion_boundaries)


def test_string_probe_key_fuses_with_encoding(fusion_conf, data):
    """Compressed execution retires the string-key unfused probe
    fallback: the probe pipeline fuses (padded dictionary-hash lut as a
    kernel aux input), the prediction stays exact, and turning encoding
    OFF restores the historical boundary + reason."""
    data.conf.set("spark.tpu.fusion.enabled", "true")
    sdim = pa.table({"sk": [f"cat{i}" for i in range(5)],
                     "w": np.arange(5, dtype=np.int64)})
    data.createDataFrame(sdim).createOrReplaceTempView("an_sdim")
    q = ("select s, w from an_t join an_sdim on s = sk where v > 0")
    report = data.sql(q).query_execution.analysis_report()
    assert any("FUSED probe" in b for b in report.fusion_boundaries), \
        report.fusion_boundaries
    assert not any("UNFUSED probe" in b for b in report.fusion_boundaries)
    _assert_exact(data, q)
    data.conf.set("spark.tpu.encoding.enabled", "false")
    try:
        report = data.sql(q).query_execution.analysis_report()
        assert any("UNFUSED probe" in b and "string" in b
                   for b in report.fusion_boundaries), \
            report.fusion_boundaries
    finally:
        data.conf.unset("spark.tpu.encoding.enabled")


def test_overflow_risk_flagged_for_int_sum(fusion_conf, data):
    report = data.sql(Q_AGG).query_execution.analysis_report()
    assert any("SUM(" in r and "int64" in r
               for r in report.overflow_risks), report.overflow_risks


def test_dense_recompile_hazard_flagged(fusion_conf, data):
    data.conf.set("spark.tpu.fusion.enabled", "false")
    report = data.sql(Q_AGG).query_execution.analysis_report()
    assert any("value-dependent" in h
               for h in report.recompile_hazards), report.recompile_hazards


def test_report_dict_shape(fusion_conf, data):
    d = data.sql(Q_AGG).query_execution.analysis_report().to_dict()
    for key in ("stages", "predicted_launches", "predicted_total", "exact",
                "fusion_boundaries", "recompile_hazards", "overflow_risks"):
        assert key in d
    assert d["predicted_total"] == sum(d["predicted_launches"].values())


def test_sample_offset_arg_no_recompile_storm(spark):
    """SampleExec keys its kernel by (capacity, seed, fraction) and feeds
    the per-(partition,batch) position base as a kernel ARGUMENT: 12
    batches across 4 partitions compile at most one kernel per capacity
    bucket (the historical per-batch cache key compiled 12), launches
    stay 1/batch, the analyzer predicts them exactly, and the recompile
    hazard is gone from the report."""

    def q():
        return spark.range(0, 40000, 1, 4).sample(0.5, seed=31)

    report = q().query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    assert report.predicted_launches == {"sample": 12}, \
        report.predicted_launches
    assert not any("SampleExec" in h for h in report.recompile_hazards), \
        report.recompile_hazards
    assert any("kernel argument" in n for s in report.stages
               for n in s["notes"])

    before = KC.counters()
    before_kinds = dict(KC.launches_by_kind)
    q().toArrow()  # cold: compiles happen here
    mid = KC.counters()
    # 10000 rows/partition at 4096-capacity tiles → per partition
    # [4096, 4096, 2048] caps: two distinct buckets → ≤ 2 compiles
    assert mid["kernel_cache.misses"] - before["kernel_cache.misses"] <= 2
    assert KC.launches_by_kind["sample"] \
        - before_kinds.get("sample", 0) == 12

    warm = dict(KC.launches_by_kind)
    q().toArrow()  # warm: predicted == measured, zero further compiles
    after = KC.counters()
    measured = {k: v - warm.get(k, 0) for k, v in
                KC.launches_by_kind.items() if v != warm.get(k, 0)}
    assert measured == report.predicted_launches
    assert after["kernel_cache.misses"] == mid["kernel_cache.misses"]


def test_rr_offset_arg_no_recompile_storm(spark):
    """shuffle_rr keys its kernel by (capacity, num_out) and feeds the
    running row offset as a kernel ARGUMENT: a multi-batch round-robin
    repartition compiles at most one kernel per capacity bucket (the
    historical per-offset cache key compiled one per batch position),
    launches stay 1/batch, and the analyzer's recompile hazard is gone
    — replaced by the kernel-argument note."""

    def rr_keys():
        return [k for k in KC._cache if k and k[0] == "shuffle_rr"]

    def q():
        return spark.range(0, 40000, 1, 4).repartition(3)

    report = q().query_execution.analysis_report()
    assert not any("round-robin" in h for h in report.recompile_hazards), \
        report.recompile_hazards
    assert any("kernel argument" in n for s in report.stages
               for n in s["notes"] if "round-robin" in n), \
        [n for s in report.stages for n in s["notes"]]

    before_keys = set(rr_keys())
    before_kinds = dict(KC.launches_by_kind)
    q().toArrow()  # cold: compiles happen here
    new_keys = set(rr_keys()) - before_keys
    # 10000 rows/partition at 4096-capacity tiles → per partition caps
    # [4096, 4096, 2048]: two distinct buckets → ≤ 2 compiled kernels,
    # each keyed WITHOUT the running offset
    assert len(new_keys) <= 2, new_keys
    assert all(len(k) == 3 for k in new_keys), new_keys
    assert KC.launches_by_kind["shuffle_rr"] \
        - before_kinds.get("shuffle_rr", 0) == 12

    warm_keys = set(rr_keys())
    q().toArrow()  # warm: zero further shuffle_rr compiles
    assert set(rr_keys()) == warm_keys


def test_rr_shuffle_rows_survive_offset_argument(spark):
    """Round-robin output stays balanced and complete with the offset as
    a kernel argument (the offset still advances across batches)."""
    out = spark.range(0, 9999, 1, 4).repartition(3)
    parts = out.query_execution.execute()
    sizes = [sum(b.num_rows() for b in p) for p in parts]
    assert sum(sizes) == 9999
    assert max(sizes) - min(sizes) <= 1, sizes  # strict round-robin


def test_inexact_degrades_honestly(fusion_conf, data):
    """A MESH hash exchange whose key values the analyzer cannot trace
    (a COMPUTED string key — only pass-through columns trace) has
    data-dependent quota retries: the analyzer must NOT claim exactness,
    and must say why. (Traced keys — integers AND plain string columns,
    whose eq-lanes ride the dictionary hashes — now simulate the staging
    + retry loop exactly.)"""
    data.conf.set("spark.tpu.fusion.enabled", "true")
    df = (data.sql("select upper(s) as u, v from an_t")
          .repartition(4, "u").groupBy("u").count())
    report = df.query_execution.analysis_report()
    assert not report.exact
    assert report.inexact_reasons
    assert any("untraced" in r for r in report.inexact_reasons), \
        report.inexact_reasons
    # the mesh stage dispatch itself is still predicted
    assert report.predicted_launches.get("mesh_stage", 0) >= 1, \
        report.predicted_launches


# ---------------------------------------------------------------------------
# multi-stage shuffle plans: host-side hash of traced keys → EXACT
# ---------------------------------------------------------------------------
# Partition counts are non-powers-of-two so the exchanges stay on the host
# shuffle path (the 8-virtual-device env would otherwise go mesh).

@pytest.mark.parametrize("enabled", ["true", "false"])
def test_repartition_agg_prediction_exact(fusion_conf, data, enabled):
    """Acceptance: the value model flows THROUGH the hash exchange
    (host-side splitmix64 of the traced keys decides per-reducer rows and
    values), so repartition+agg predicts exactly — krange3 probes, dense
    vs sorted decisions, and per-batch launches included — fusion on and
    off."""
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact_df(lambda: (data.sql("select * from an_t")
                              .repartition(5, "k").groupBy("k").count()))


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_fused_exchange_prediction_exact(fusion_conf, data, enabled):
    """A shuffle-map stage with a nontrivial pipeline: fused (ONE
    fused_shuffle dispatch per map batch) and unfused (pipeline + shuffle
    kind) launch models both predict exactly, through the downstream
    aggregate."""
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact_df(lambda: (
        data.sql("select k, v * 2 as v2 from an_t where v > 0")
        .repartition(5, "k")))
    _assert_exact_df(lambda: (
        data.sql("select k, v * 2 as v2 from an_t where v > 0")
        .repartition(5, "k").groupBy("k").count()))
    # round-robin keeps its offset-as-kernel-argument model when fused
    _assert_exact_df(lambda: (
        data.sql("select k, v from an_t where v > 0").repartition(3)))


def test_fused_exchange_boundary_and_kind(fusion_conf, data):
    data.conf.set("spark.tpu.fusion.enabled", "true")
    df = (data.sql("select k, v * 2 as v2 from an_t where v > 0")
          .repartition(5, "k"))
    report = df.query_execution.analysis_report()
    assert "fused_shuffle" in report.predicted_launches, \
        report.predicted_launches
    assert any("FUSED map side" in b for b in report.fusion_boundaries), \
        report.fusion_boundaries


def test_string_exchange_key_fuses_with_encoding(fusion_conf, data):
    """Compressed execution fuses string hash-partition keys into the
    map-side program (dict-hash lut aux input): fused_shuffle is
    predicted exactly; encoding off restores the historical boundary."""
    data.conf.set("spark.tpu.fusion.enabled", "true")

    def q():
        return (data.sql("select s, v * 2 as v2 from an_t where v > 0")
                .repartition(5, "s"))

    report = q().query_execution.analysis_report()
    assert "fused_shuffle" in report.predicted_launches, \
        report.predicted_launches
    assert any("FUSED map side" in b for b in report.fusion_boundaries), \
        report.fusion_boundaries
    _assert_exact_df(q)
    data.conf.set("spark.tpu.encoding.enabled", "false")
    try:
        report = q().query_execution.analysis_report()
        assert "fused_shuffle" not in report.predicted_launches, \
            report.predicted_launches
        assert any("UNFUSED exchange" in b and "string" in b
                   for b in report.fusion_boundaries), \
            report.fusion_boundaries
    finally:
        data.conf.unset("spark.tpu.encoding.enabled")


# ---------------------------------------------------------------------------
# mesh SPMD stage: staging + quota-retry simulation → EXACT
# ---------------------------------------------------------------------------
# Partition counts are powers of two on the 8-virtual-device env, so these
# exchanges take the mesh stage program (ONE sharded dispatch per step).

@pytest.mark.parametrize("enabled", ["true", "false"])
def test_mesh_exchange_prediction_exact(fusion_conf, data, enabled):
    """Acceptance: the mesh stage model simulates the staging geometry,
    the splitmix64 partition ids, and the quota-retry loop host-side, so
    mesh-path plans predict EXACTLY — one mesh_stage dispatch per step
    (no per-batch pipeline when fused), krange3/dense decisions on the
    shard-resident reduce tiles included — fusion on and off."""
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact_df(lambda: (
        data.sql("select k, v * 2 as v2 from an_t where v > 0")
        .repartition(4, "k")))
    _assert_exact_df(lambda: (
        data.sql("select k, v * 2 as v2 from an_t where v > 0")
        .repartition(4, "k").groupBy("k").count()))


def test_mesh_fused_single_dispatch_predicted(fusion_conf, data):
    data.conf.set("spark.tpu.fusion.enabled", "true")
    df = (data.sql("select k, v * 2 as v2 from an_t where v > 0")
          .repartition(4, "k"))
    report = df.query_execution.analysis_report()
    assert report.predicted_launches.get("mesh_stage") == 1, \
        report.predicted_launches
    assert "pipeline" not in report.predicted_launches, \
        report.predicted_launches
    assert any("FUSED mesh stage" in n for s in report.stages
               for n in s["notes"]), \
        [n for s in report.stages for n in s["notes"]]


def test_mesh_legacy_mode_prediction_exact(fusion_conf, data):
    """spark.tpu.fusion.mesh=false: the pipeline materializes per batch
    before the collective — the model mirrors that too."""
    data.conf.set("spark.tpu.fusion.enabled", "true")
    data.conf.set("spark.tpu.fusion.mesh", "false")
    try:
        _assert_exact_df(lambda: (
            data.sql("select k, v * 2 as v2 from an_t where v > 0")
            .repartition(4, "k")))
    finally:
        data.conf.unset("spark.tpu.fusion.mesh")


def test_mesh_quota_retry_prediction_exact(fusion_conf, spark):
    """Skewed keys overflow the per-(src,dst) quota: the simulation
    predicts the retry dispatches exactly."""
    n = 6000
    spark.createDataFrame(pa.table({
        "k": np.full(n, 5, np.int64),
        "v": np.arange(n, dtype=np.int64),
    })).createOrReplaceTempView("an_skew")
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    try:
        df = spark.sql("select k, v from an_skew").repartition(4, "k")
        report = df.query_execution.analysis_report()
        assert report.predicted_launches.get("mesh_stage", 0) >= 2, \
            report.predicted_launches
        _assert_exact_df(
            lambda: spark.sql("select k, v from an_skew")
            .repartition(4, "k"))
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_mesh_sharded_q3_prediction_exact(fusion_conf, spark, enabled):
    """The acceptance query: sharded TPC-DS mini q3 — fact table
    redistributed over the mesh, broadcast join spine, fused partial
    aggregate — predicts exactly, fusion on and off (the join value
    model rides the per-partition mesh reduce traces)."""
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.sql("select * from store_sales") \
        .repartition(4, "ss_item_sk") \
        .createOrReplaceTempView("an_store_sales_sharded")
    spark.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(spark, """
        SELECT dt.d_year, item.i_brand_id AS brand_id,
               SUM(ss_ext_sales_price) AS sum_agg
        FROM date_dim dt, an_store_sales_sharded store_sales, item
        WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
          AND store_sales.ss_item_sk = item.i_item_sk
          AND item.i_manufact_id = 28 AND dt.d_moy = 11
        GROUP BY dt.d_year, item.i_brand_id""")


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_string_minmax_fused_prediction_exact(fusion_conf, data, enabled):
    """String MIN/MAX now rides the fused aggregate kernel (rank-space
    reduce, inverse-rank lut as aux input) — and the launch model stays
    exact fusion on and off."""
    data.conf.set("spark.tpu.fusion.enabled", enabled)
    _assert_exact(data, "select k, min(s) mn, max(s) mx, count(*) c "
                        "from an_t where v > 0 group by k")


@pytest.mark.parametrize("query,ladder", [(Q7, True), (Q_JOIN_AGG, False)],
                         ids=["overflowing", "fitting"])
def test_whole_tier_prediction_exact_cold_then_remembered(fusion_conf, data,
                                                          query, ladder):
    """On the whole tier the mirror reads what the executor reads
    (persist_cache.plan_seed): in a process that has not run the plan it
    predicts the capacity ladder and the first execution climbs it; after
    that execution it predicts the one program the second one launches.
    A plan whose joins fit their first capacities is one program both
    times."""
    from tpcds_mini import register_tpcds

    register_tpcds(data)
    data.conf.set("spark.tpu.compile.tier", "whole")
    try:
        (cold, first), (warm, second) = _whole_tier_cold_then_warm(
            lambda: data.sql(query))
        assert cold.exact and warm.exact, (cold.inexact_reasons,
                                           warm.inexact_reasons)
        assert cold.predicted_launches == first, cold.render()
        assert (first["whole_query"] >= 2) == ladder, first
        assert warm.predicted_launches == second == {"whole_query": 1}, \
            warm.render()
    finally:
        data.conf.unset("spark.tpu.compile.tier")


Q_WINDOW = ("select label, k, sv, avg(sv) over (partition by label) a, "
            "rank() over (partition by label order by sv desc) r from "
            "(select label, k, sum(v) sv from an_t join an_dim on k = dk "
            "group by label, k) t")


@pytest.mark.parametrize("query,windows", [
    (Q_WINDOW, 2),
    (Q_WINDOW.replace("avg(sv)", "lag(sv)")
     .replace("(partition by label) a",
              "(partition by label order by k) a"), 0)],
    ids=["lowered", "refused"])
def test_window_whole_tier_prediction_exact(fusion_conf, data, query,
                                            windows):
    """A window over a join and an aggregate: with a lowering
    (whole_query._lower_window) the mirror counts the one program and the
    window's planes; refused by name (lag), the tier decision says so."""
    data.conf.set("spark.tpu.compile.tier", "whole")
    try:
        report, measured = _predicted_vs_measured(data, query)
        tier = report.tier or {}
        if windows:
            assert report.exact, report.inexact_reasons
            assert report.predicted_launches == measured \
                == {"whole_query": 1}, (report.predicted_launches, measured)
            assert tier.get("tier") == "whole"
            assert report.predicted_peak_hbm > 0
        else:
            assert tier.get("tier") == "stage"
            assert "window function lag" in tier.get("reason", ""), tier
            assert "whole_query" not in measured
    finally:
        data.conf.unset("spark.tpu.compile.tier")
