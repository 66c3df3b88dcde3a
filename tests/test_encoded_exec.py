"""Compressed execution (ROADMAP direction 3): dictionary/RLE-native
kernels, encoded scans, and the code-shipping shuffle.

Differential suite against the decoded oracle
(spark.tpu.encoding.enabled=false): the encoded path — dense-on-codes
aggregation, fused string-key join probes / exchanges (padded dict-hash
aux luts), sorted-run (RLE) segment reduce, dictionary-preserving cluster
IPC — must produce byte-identical results on agg/join/sort/shuffle, local
+ cluster + mesh, nullable and high-cardinality dictionaries, with
≤1-launch-per-batch regression guards and exact plan_lint predictions
fusion on AND off."""

import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.api.functions as F
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


@pytest.fixture()
def enc_spark(spark):
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    yield spark
    for k in ("spark.tpu.fusion.enabled", "spark.tpu.fusion.minRows",
              "spark.tpu.encoding.enabled"):
        spark.conf.unset(k)


@pytest.fixture()
def edata(enc_spark):
    rng = np.random.default_rng(23)
    n = 5000
    s = [None if i % 37 == 0 else f"cat{i % 17}" for i in range(n)]
    hc = [f"val{rng.integers(0, 2000):04d}" for _ in range(n)]
    enc_spark.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": rng.integers(-50, 100, n),
        "s": s,
        "hc": hc,
    })).createOrReplaceTempView("enc_t")
    sdim = pa.table({
        "sk": [f"cat{i}" for i in range(17)],
        "w": np.arange(17, dtype=np.int64),
    })
    enc_spark.createDataFrame(sdim).createOrReplaceTempView("enc_dim")
    return enc_spark


def _encoding_differential(spark, build_query, sort_cols):
    """Run the same query encoded and decoded; compare row-for-row."""
    outs = {}
    for enabled in (True, False):
        spark.conf.set("spark.tpu.encoding.enabled",
                       str(enabled).lower())
        outs[enabled] = build_query().toPandas() \
            .sort_values(sort_cols).reset_index(drop=True)
    spark.conf.unset("spark.tpu.encoding.enabled")
    got, want = outs[True], outs[False]
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want), f"{len(got)} vs {len(want)} rows"
    for c in got.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if np.issubdtype(np.asarray(w).dtype, np.floating):
            np.testing.assert_allclose(g.astype(float), w.astype(float),
                                       rtol=1e-12, atol=1e-12)
        else:
            assert list(g) == list(w), f"column {c} differs"


def _kind_delta(run):
    before = dict(KC.launches_by_kind)
    run()
    after = dict(KC.launches_by_kind)
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _assert_exact(spark, build):
    df = build()
    report = df.query_execution.analysis_report()
    df.toArrow()  # warm
    before = dict(KC.launches_by_kind)
    build().toArrow()
    after = dict(KC.launches_by_kind)
    measured = {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}
    assert report.exact, report.inexact_reasons
    assert report.predicted_launches == measured, (
        f"predicted {dict(sorted(report.predicted_launches.items()))} != "
        f"measured {dict(sorted(measured.items()))}\n{report.render()}")


# ---------------------------------------------------------------------------
# differentials: encoded vs decoded oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fusion", ["true", "false"])
def test_dict_groupby_differential(edata, fusion):
    """Nullable dictionary grouping key: dense-on-codes vs the decoded
    sort path, fusion on and off (the null-key group rides the dense
    table's parking slot)."""
    edata.conf.set("spark.tpu.fusion.enabled", fusion)
    _encoding_differential(
        edata,
        lambda: edata.sql("select s, count(*) c, sum(v) sv, min(v) mn "
                          "from enc_t where v > 0 group by s"),
        ["s"])


def test_high_cardinality_dict_groupby_differential(edata):
    _encoding_differential(
        edata,
        lambda: edata.sql("select hc, count(*) c, max(v) mx from enc_t "
                          "group by hc"),
        ["hc"])


def test_string_minmax_over_dict_key_differential(edata):
    """String values reduced (rank space) under a string key grouped on
    codes — both encodings of the same batch cooperate."""
    _encoding_differential(
        edata,
        lambda: edata.sql("select s, min(hc) mn, max(hc) mx, count(*) c "
                          "from enc_t group by s"),
        ["s"])


def test_string_join_differential(edata):
    """String-key join: fused probe via the padded dict-hash lut vs the
    decoded unfused probe."""
    _encoding_differential(
        edata,
        lambda: edata.sql("select s, w, v from enc_t join enc_dim "
                          "on s = sk where v > 5"),
        ["s", "w", "v"])


def test_string_join_agg_differential(edata):
    _encoding_differential(
        edata,
        lambda: edata.sql("select w, count(*) c, sum(v) sv from enc_t "
                          "join enc_dim on s = sk group by w"),
        ["w"])


def test_string_sort_differential(edata):
    _encoding_differential(
        edata,
        lambda: edata.sql("select s, v from enc_t where v > 90 "
                          "order by s, v"),
        ["s", "v"])


def test_string_repartition_differential_host(edata):
    """Non-power-of-two partition count keeps the exchange on the host
    shuffle path: the fused map dispatch computes string pids in-kernel
    via the dict-hash lut."""
    _encoding_differential(
        edata,
        lambda: (edata.sql("select s, v * 2 as v2 from enc_t "
                           "where v > 0").repartition(5, "s")),
        ["s", "v2"])


def test_string_repartition_differential_mesh(edata):
    """Power-of-two partition count takes the mesh path (8 virtual
    devices): string keys ride staged eq-key planes after the pipeline
    materializes."""
    _encoding_differential(
        edata,
        lambda: (edata.sql("select s, v from enc_t where v != 7")
                 .repartition(4, "s").groupBy("s").count()),
        ["s"])


def test_sorted_run_agg_differential(enc_spark):
    """RLE fast path: a SORTED sparse integral key (dense span check
    fails) reduces per run boundary — results match the sorting oracle
    and the decoded oracle."""
    rng = np.random.default_rng(29)
    n = 3000
    sk = np.cumsum(rng.integers(5, 60, n)).astype(np.int64)  # sorted,
    # span ~100k >> 4*4096: the dense-range path declines
    enc_spark.createDataFrame(pa.table({
        "sk": sk, "v": rng.integers(0, 50, n),
    })).createOrReplaceTempView("enc_sorted")
    _encoding_differential(
        enc_spark,
        lambda: enc_spark.sql("select sk, count(*) c, sum(v) sv "
                              "from enc_sorted group by sk"),
        ["sk"])


# ---------------------------------------------------------------------------
# dispatch-count guards + exact predictions
# ---------------------------------------------------------------------------

def test_dict_groupby_single_dispatch_no_probe(enc_spark):
    """≤1 launch per batch for the fused string-key aggregate, and ZERO
    krange3 probes: the code domain is known host-side (len(dict))."""
    cap = 1 << 12
    n_batches = 4
    rng = np.random.default_rng(31)
    t = pa.table({"g": [f"g{int(x)}" for x in rng.integers(0, 11,
                                                           cap * n_batches)],
                  "v": rng.integers(0, 100, cap * n_batches)})
    df = enc_spark.createDataFrame(t)
    q = lambda: (df.filter(F.col("v") > 25)  # noqa: E731
                 .groupBy("g").agg(F.sum("v").alias("sv")).toArrow())
    q()  # warm
    delta = _kind_delta(q)
    assert delta.get("fused_agg", 0) == n_batches, delta
    assert delta.get("krange3", 0) == 0, delta
    assert delta.get("gagg", 0) == 0, delta
    total = sum(delta.values())
    assert total <= n_batches + 4, delta


def test_sorted_run_agg_kind_and_exact(enc_spark):
    """The sorted-run chunk dispatches ONE ragg kernel (no sort-path
    gagg, no dense dagg) and the analyzer predicts it exactly."""
    rng = np.random.default_rng(37)
    n = 3000
    t = pa.table({"sk": np.cumsum(rng.integers(5, 60, n)).astype(np.int64),
                  "v": rng.integers(0, 50, n)})
    df = enc_spark.createDataFrame(t)

    def q():
        return df.groupBy("sk").agg(F.count("*").alias("c"))

    _assert_exact(enc_spark, q)
    delta = _kind_delta(lambda: q().toArrow())
    assert delta.get("ragg", 0) == 1, delta
    assert delta.get("gagg", 0) == 0, delta
    assert delta.get("dagg", 0) == 0, delta
    report = q().query_execution.analysis_report()
    assert any("sorted-run" in nn for s in report.stages
               for nn in s["notes"]), report.render()


@pytest.mark.parametrize("fusion", ["true", "false"])
def test_dict_agg_prediction_exact(edata, fusion):
    edata.conf.set("spark.tpu.fusion.enabled", fusion)
    _assert_exact(edata, lambda: edata.sql(
        "select s, count(*) c, sum(v) sv from enc_t where v > 0 "
        "group by s"))


@pytest.mark.parametrize("fusion", ["true", "false"])
def test_string_shuffle_agg_prediction_exact(edata, fusion):
    """String-keyed repartition + group-by: the reduce layout rides the
    dictionary-hash eq lanes host-side, the reduce tiles carry merged
    dictionary domains, and the whole plan predicts exactly."""
    edata.conf.set("spark.tpu.fusion.enabled", fusion)
    _assert_exact(edata, lambda: (
        edata.sql("select s, v from enc_t where v > 0")
        .repartition(5, "s").groupBy("s").count()))


@pytest.mark.parametrize("fusion", ["true", "false"])
def test_string_shuffle_agg_explain_analyze_no_drift(edata, fusion):
    """EXPLAIN ANALYZE over the encoded path: what the dictionary-native
    kernels launch for a string-keyed repartition + group-by is what the
    launch model said, so no finding is an error."""
    edata.conf.set("spark.tpu.fusion.enabled", fusion)
    report = (edata.sql("select s, v from enc_t where v > 0")
              .repartition(5, "s").groupBy("s").agg(F.sum("v").alias("sv"))
              .query_execution.analyzed_report())
    assert not report.has_unexplained_drift, report.render()
    assert sum(report.measured.values()) > 0
    assert not report.measured.get("krange3"), dict(report.measured)
    assert not report.measured.get("gagg"), dict(report.measured)


def test_string_probe_single_dispatch(edata):
    """Fused string probe: one dispatch per probe batch, no separate
    pipeline launch (the dict-hash lut rides as an aux input)."""
    q = lambda: edata.sql(  # noqa: E731
        "select s, w from enc_t join enc_dim on s = sk "
        "where v > 0").toArrow()
    q()  # warm
    delta = _kind_delta(q)
    assert delta.get("fused_probe", 0) >= 1, delta
    assert delta.get("join_probe", 0) == 0, delta  # unfused path retired
    # the only pipeline launch left is the BUILD side's own filter
    assert delta.get("pipeline", 0) <= 1, delta


def test_dict_ingest_seeds_range_memo(enc_spark):
    """Satellite: dictionary cardinality seeds the dense-range memo at
    ingest — a dense-range read of a CODE column never launches the
    krange3 probe, even cold."""
    from spark_tpu.physical.operators import dense_range_stats

    t = pa.table({"c": ["a", "b", "a", "c", None, "b"]})
    df = enc_spark.createDataFrame(t)
    parts = df.query_execution.execute()
    before = KC.launches_by_kind.get("krange3", 0)
    for part in parts:
        for b in part:
            col = b.columns[0]
            kmin, kmax, any_live = dense_range_stats(
                col, b.row_mask, b.capacity)
            assert (kmin, kmax) == (0, len(col.dictionary) - 1)
            assert any_live
    assert KC.launches_by_kind.get("krange3", 0) == before


# ---------------------------------------------------------------------------
# code-shipping shuffle: encoded IPC + dictionary identity
# ---------------------------------------------------------------------------

def test_encoded_ipc_roundtrip_shares_dictionaries(enc_spark):
    """The encoded wire format ships codes + dictionaries (never decoded
    values); equal dictionary tokens rebuild to ONE shared StringDict
    across blocks (identity remap, no re-encode)."""
    from spark_tpu.exec.cluster_sql import (
        _ipc_to_partition, _partition_to_ipc_encoded,
    )
    from spark_tpu.physical.operators import attrs_schema

    df = enc_spark.createDataFrame(pa.table({
        "s": [f"x{i % 7}" for i in range(6000)],
        "v": np.arange(6000, dtype=np.int64),
    }))
    parts = df.query_execution.execute()
    part = [b for p in parts for b in p]
    assert len(part) >= 2  # 6000 rows at 4096-capacity tiles
    payload, tokens = _partition_to_ipc_encoded(part)
    assert payload[0] == "enc1"
    assert 0 in tokens and len(tokens[0]) == len(part)
    schema = attrs_schema(df.query_execution.physical.output)
    cache: dict = {}
    # tokens travel on the MapStatus (dict_ids), not in the payload —
    # the reduce side hands them back in alongside the intern cache
    rebuilt = _ipc_to_partition(payload, schema, dict_cache=cache,
                                dict_tokens=tokens)
    assert len(rebuilt) == len(part)
    dicts = [b.columns[0].dictionary for b in rebuilt]
    # equal tokens -> the SAME StringDict object (identity fast path)
    tok_to_dict = {}
    for tok, sd in zip(tokens[0], dicts):
        if tok in tok_to_dict:
            assert sd is tok_to_dict[tok]
        tok_to_dict[tok] = sd
    # values decode identically to the source
    src = pa.concat_tables([b.to_arrow() for b in part])
    got = pa.concat_tables([b.to_arrow() for b in rebuilt])
    assert src.equals(got)


def test_cluster_encoded_differential_and_bytes(enc_spark):
    """Cluster shuffle ships codes + one dictionary per map task:
    encoded and decoded cluster runs agree, the MapStatus carries the
    dictionary identity, and the encoded payload moves measurably fewer
    bytes for a dictionary-heavy table."""
    from spark_tpu.api.session import TpuSession
    from spark_tpu.exec.cluster import LocalCluster

    rng = np.random.default_rng(41)
    n = 6000
    t = pa.table({
        # long repeated strings: the decoded wire format pays them per row
        "s": [f"category-with-a-rather-long-name-{int(x):02d}"
              for x in rng.integers(0, 12, n)],
        "v": rng.integers(-20, 80, n),
    })
    outs, bytes_written = {}, {}
    for enabled in ("true", "false"):
        s = TpuSession(f"enc-cluster-{enabled}", {
            "spark.sql.shuffle.partitions": "3",
            "spark.tpu.batch.capacity": 1 << 12,
            "spark.sql.adaptive.enabled": "false",
            "spark.tpu.fusion.enabled": "true",
            "spark.tpu.fusion.minRows": "0",
            "spark.tpu.encoding.enabled": enabled,
        })
        cluster = LocalCluster(num_workers=2)
        s.attachSqlCluster(cluster)
        try:
            s.createDataFrame(t).createOrReplaceTempView("ec_t")
            df = (s.sql("select s, v from ec_t where v > 0")
                  .repartition(3, "s").groupBy("s")
                  .agg(F.sum("v").alias("sv")))
            outs[enabled] = (df.toPandas().sort_values("s")
                             .reset_index(drop=True))
            snap = s._metrics.snapshot()["counters"]
            assert snap.get("scheduler.stages_remote", 0) >= 1
            bytes_written[enabled] = snap.get("shuffle.bytes_written", 0)
        finally:
            s.stop()
    assert outs["true"].equals(outs["false"])
    assert bytes_written["true"] > 0 and bytes_written["false"] > 0
    # codes + one dict per map task beat decoded row values on the wire
    assert bytes_written["true"] < bytes_written["false"], bytes_written


def test_local_shuffle_bytes_encoded_smaller(edata):
    """Local host shuffle: the shipped host planes are int32 codes +
    shared dictionary references either way — the counter exists and the
    encoded fused path moves no MORE bytes than the decoded oracle."""
    def run():
        (edata.sql("select s, v from enc_t where v > 0")
         .repartition(5, "s").toArrow())

    sizes = {}
    for enabled in ("true", "false"):
        edata.conf.set("spark.tpu.encoding.enabled", enabled)
        before = edata._metrics.snapshot()["counters"].get(
            "shuffle.bytes_shipped", 0)
        run()
        after = edata._metrics.snapshot()["counters"].get(
            "shuffle.bytes_shipped", 0)
        sizes[enabled] = after - before
    edata.conf.unset("spark.tpu.encoding.enabled")
    assert sizes["true"] > 0
    assert sizes["true"] <= sizes["false"], sizes
