"""Whole-query compilation (physical/whole_query.py) + compile-tier model.

Acceptance gates:
  * whole / stage / operator tiers produce IDENTICAL results on the
    differential suite (agg, join+agg, repartition+agg, sorted q3);
  * the whole tier executes as ONE jitted dispatch per step (warm run:
    {"whole_query": 1}) with zero host shuffle round-trips;
  * plan_lint's launch model predicts EXACTLY for all three tiers, with
    the tier decision and fallback reason surfaced in explain("analysis");
  * the tier chooser launches nothing and falls back tier-by-tier (HBM
    budget exceeded / unsupported operators -> stage);
  * obs contract: attributed launch totals == global counters under the
    whole-query program, zero extra launches from the chooser.

Satellites covered here: dictionary-domain UDF evaluation (once per
distinct value, mapped over codes), RunInfo propagation through
pass-through pipeline outputs (ragg on filter->agg chains), and the mesh
quota-retry restaging fix (retries reuse device-resident base planes).
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


@pytest.fixture()
def tiers(spark):
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    yield spark
    for k in ("spark.tpu.compile.tier", "spark.tpu.fusion.minRows",
              "spark.tpu.compile.whole.minRows", "spark.tpu.memory.budget",
              "spark.tpu.fusion.enabled"):
        spark.conf.unset(k)


@pytest.fixture()
def data(spark):
    rng = np.random.default_rng(11)
    n = 5000
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": rng.integers(-50, 100, n),
        "f": rng.random(n),
        "s": [f"cat{i % 5}" for i in range(n)],
    })).createOrReplaceTempView("wq_t")
    dim = pa.table({
        "dk": np.arange(13, dtype=np.int64),
        "label": [f"lab{i % 3}" for i in range(13)],
    })
    spark.createDataFrame(dim).createOrReplaceTempView("wq_dim")
    return spark


Q_AGG = ("select k, sum(v * 2) sv, count(*) c, min(v) mn, max(v+1) mx, "
         "avg(f) af from wq_t where v > 0 group by k")
Q_JOIN_AGG = ("select label, sum(v) sv, count(*) c from wq_t "
              "join wq_dim on k = dk where v > 10 group by label")
Q3 = """
    SELECT dt.d_year, item.i_brand_id AS brand_id,
           SUM(ss_ext_sales_price) AS sum_agg
    FROM date_dim dt, store_sales, item
    WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
      AND store_sales.ss_item_sk = item.i_item_sk
      AND item.i_manufact_id = 28 AND dt.d_moy = 11
    GROUP BY dt.d_year, item.i_brand_id"""
Q3_SORTED = Q3 + "\n    ORDER BY d_year, brand_id"


def _rows(df, by):
    t = df.toArrow().to_pandas()
    return t.sort_values(by).reset_index(drop=True)


def _measured(build):
    build().toArrow()  # warm
    before = dict(KC.launches_by_kind)
    build().toArrow()
    return {k: v - before.get(k, 0) for k, v in KC.launches_by_kind.items()
            if v != before.get(k, 0)}


# ---------------------------------------------------------------------------
# differential suite: identical results across the three tiers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("query,by", [
    (Q_AGG, ["k"]),
    (Q_JOIN_AGG, ["label"]),
])
def test_tier_differential(tiers, data, query, by):
    import pandas as pd

    data.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(data.sql(query), by)
    for tier in ("whole", "operator"):
        data.conf.set("spark.tpu.compile.tier", tier)
        out = _rows(data.sql(query), by)
        pd.testing.assert_frame_equal(ref, out, check_dtype=False)


def test_tier_differential_repartition_agg(tiers, data):
    import pandas as pd

    def q():
        return (data.sql("select * from wq_t").repartition(5, "k")
                .groupBy("k").count())

    data.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(q(), ["k"])
    for tier in ("whole", "operator"):
        data.conf.set("spark.tpu.compile.tier", tier)
        pd.testing.assert_frame_equal(ref, _rows(q(), ["k"]),
                                      check_dtype=False)


def test_tier_differential_sorted_q3(tiers, spark):
    """Sorted q3: broadcast-join spine + group agg + range-exchange sort,
    ALL lowered into one program under the whole tier — results identical
    INCLUDING the total order (the in-program gather + global sort
    replaces range partitioning + per-partition sorts)."""
    import pandas as pd

    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(Q3_SORTED).toArrow().to_pandas().reset_index(drop=True)
    for tier in ("whole", "operator"):
        spark.conf.set("spark.tpu.compile.tier", tier)
        out = spark.sql(Q3_SORTED).toArrow().to_pandas() \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(ref, out, check_dtype=False)


# ---------------------------------------------------------------------------
# one dispatch per step + exact predictions for every tier
# ---------------------------------------------------------------------------

def test_whole_tier_single_dispatch_per_step(tiers, spark):
    """Acceptance: TPC-DS mini q3 under the whole tier is ONE jitted
    dispatch per step — no host shuffle round-trip, no per-stage kernels
    of any kind on the warm run."""
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    measured = _measured(lambda: spark.sql(Q3))
    assert measured == {"whole_query": 1}, measured


@pytest.mark.parametrize("tier", ["whole", "stage", "operator"])
def test_prediction_exact_all_tiers(tiers, data, tier):
    data.conf.set("spark.tpu.compile.tier", tier)
    for q in (Q_AGG, Q_JOIN_AGG):
        df = data.sql(q)
        report = df.query_execution.analysis_report()
        assert report.exact, report.inexact_reasons
        measured = _measured(lambda: data.sql(q))
        assert report.predicted_launches == measured, (
            tier, report.predicted_launches, measured)
        assert (report.tier or {}).get("tier") == tier, report.tier


@pytest.mark.parametrize("tier", ["whole", "stage", "operator"])
def test_q3_prediction_exact_all_tiers(tiers, spark, tier):
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.compile.tier", tier)
    df = spark.sql(Q3)
    report = df.query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    measured = _measured(lambda: spark.sql(Q3))
    assert report.predicted_launches == measured, (
        tier, report.predicted_launches, measured)


def test_whole_tier_join_retry_predicted(tiers, spark):
    """q7's fact-probe joins overflow the initial output buckets: the
    program re-dispatches with bumped capacities and the analyzer's
    round-by-round mirror (truncated upstream traces included) predicts
    the retry dispatches EXACTLY."""
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    q7 = """SELECT i.i_category, AVG(ss_quantity) AS agg1, COUNT(*) AS cnt
        FROM store_sales ss JOIN item i ON ss.ss_item_sk = i.i_item_sk
        JOIN date_dim d ON ss.ss_sold_date_sk = d.d_date_sk
        WHERE d.d_year = 1999 GROUP BY i.i_category"""
    report = spark.sql(q7).query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    assert report.predicted_launches.get("whole_query", 0) >= 2, \
        report.predicted_launches
    measured = _measured(lambda: spark.sql(q7))
    assert report.predicted_launches == measured


def test_join_rank_paths_counted_and_shown(tiers, spark, monkeypatch):
    """Each sorted join asks `ops/joining.rank_path` at its three call
    sites (two ranks in `probe`, one in `expand`): the lowering counts the
    answers, writes them into the join's members row, and the traced body
    takes the same ones. Mini shapes keep the binary search; with the
    merge's fixed cost taken away they take the merge, same rows."""
    import pandas as pd

    from spark_tpu.ops import joining as J
    from spark_tpu.physical.compile import capture_programs
    from tpcds_mini import register_tpcds

    register_tpcds(spark)

    def counts():
        c = spark._metrics.snapshot()["counters"]
        return {p: c.get(f"join.rank_{p}", 0) for p in ("merge", "search")}

    def run(query):
        before = counts()
        with capture_programs() as programs:
            out = spark.sql(query).toArrow().to_pandas()
        delta = {p: n - before[p] for p, n in counts().items()}
        return out, delta, programs

    # a month no other test asks for: the programs are built here
    query = Q3_SORTED.replace("d_moy = 11", "d_moy = 12")
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(query).toArrow().to_pandas()
    assert len(ref)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    for fixed, path, other in ((0.0, "merge", "search"),
                               (J.MERGE_FIXED_S, "search", "merge")):
        monkeypatch.setattr(J, "MERGE_FIXED_S", fixed)
        out, delta, programs = run(query if path == "merge" else Q3_SORTED)
        assert programs and delta[other] == 0
        # two joins, three call sites each, every program lowered
        assert delta[path] == 6 * len(programs), (delta, len(programs))
        rec = programs[-1]
        joins = [m for s, m in zip(rec["scopes"], rec["members"])
                 if s and s.endswith(".HashJoin")]
        assert len(joins) == 2
        note = f"rank[probe={path},expand={path}]"
        assert all(m.endswith(note) for m in joins), joins
        text = rec["kernel"]._kernel.lower(*rec["args"]).as_text(
            debug_info=True)
        assert f"probe/rank_{path}" in text and f"expand/rank_{path}" in text
        assert f"rank_{other}" not in text
        if path == "merge":
            pd.testing.assert_frame_equal(ref, out, check_dtype=False)
            shown = spark.sql(query).query_execution.explain_string("device")
            assert note in shown, shown


# ---------------------------------------------------------------------------
# tier chooser: fallbacks + obs contract
# ---------------------------------------------------------------------------

def test_tier_fallback_hbm_budget(tiers, data):
    """Forced whole tier still respects the memory admission: a budget the
    fully-resident working set exceeds (but the per-stage peak fits)
    falls back to the stage tier with the reason surfaced in
    explain('analysis'), and the query still runs there."""
    from spark_tpu.physical.whole_query import _estimate_resident_bytes

    data.conf.set("spark.tpu.compile.tier", "stage")
    qe = data.sql(Q_AGG).query_execution
    stage_peak = qe.analysis_report().predicted_peak_hbm
    whole_est = _estimate_resident_bytes(qe.physical, data.conf)
    assert stage_peak and whole_est and stage_peak < whole_est, (
        stage_peak, whole_est)
    budget = (stage_peak + whole_est) // 2
    data.conf.set("spark.tpu.compile.tier", "whole")
    data.conf.set("spark.tpu.memory.budget", str(budget))
    df = data.sql(Q_AGG)
    phys = df.query_execution.physical
    assert type(phys).__name__ != "WholeQueryExec"
    report = df.query_execution.analysis_report()
    assert (report.tier or {}).get("tier") == "stage", report.tier
    assert "memory.budget" in (report.tier or {}).get("reason", ""), \
        report.tier
    # still runs correctly on the fallback tier
    assert df.toArrow().num_rows > 0


def test_tier_fallback_unsupported_operator(tiers, data):
    """A plan with an operator outside the whole-query lowering set
    (SampleExec: per-batch position-dependent) falls back to stage with
    the structural reason recorded."""
    data.conf.set("spark.tpu.compile.tier", "whole")
    df = data.sql("select * from wq_t").sample(0.5, seed=3)
    phys = df.query_execution.physical
    assert type(phys).__name__ != "WholeQueryExec"
    report = df.query_execution.analysis_report()
    assert (report.tier or {}).get("tier") == "stage", report.tier
    assert "whole-query fallback" in (report.tier or {}).get("reason", "")


def test_fusion_off_never_whole(tiers, data):
    """spark.tpu.fusion.enabled=false is the operator-at-a-time
    differential oracle: the tier chooser must never collapse the plan
    into a whole-query program there (even forced), or fusion-on/off
    differentials would compare whole vs whole."""
    data.conf.set("spark.tpu.fusion.enabled", "false")
    for tier in ("auto", "whole"):
        data.conf.set("spark.tpu.compile.tier", tier)
        data.conf.set("spark.tpu.compile.whole.minRows", "0")
        df = (data.sql("select * from wq_t").repartition(5, "k")
              .groupBy("k").count())
        assert type(df.query_execution.physical).__name__ != \
            "WholeQueryExec", tier
        report = df.query_execution.analysis_report()
        assert "fusion.enabled" in (report.tier or {}).get("reason", ""), \
            report.tier


def test_auto_tier_volume_floor(tiers, data):
    """auto keeps small queries on the stage tier (the compile-
    amortization floor, the whole-query generalization of minRows) and
    flips to whole when the floor admits a plan WITH exchange
    round-trips to eliminate; exchange-free plans always stay staged
    (stage fusion is already one dispatch per batch there)."""
    data.conf.set("spark.tpu.compile.tier", "auto")

    def q():
        return (data.sql("select * from wq_t").repartition(5, "k")
                .groupBy("k").count())

    df = q()
    assert type(df.query_execution.physical).__name__ != "WholeQueryExec"
    report = df.query_execution.analysis_report()
    assert "floor" in (report.tier or {}).get("reason", ""), report.tier
    data.conf.set("spark.tpu.compile.whole.minRows", "0")
    df = q()
    assert type(df.query_execution.physical).__name__ == "WholeQueryExec"
    report = df.query_execution.analysis_report()
    assert (report.tier or {}).get("tier") == "whole"
    # exchange-free plan: auto declines whole even with the floor at 0
    df = data.sql(Q_AGG)
    assert type(df.query_execution.physical).__name__ != "WholeQueryExec"
    report = df.query_execution.analysis_report()
    assert "no exchange round-trips" in (report.tier or {}).get(
        "reason", ""), report.tier


def test_tier_chooser_launches_nothing(tiers, data):
    """The cost model is pure host metadata: planning + analysis under
    any tier dispatches zero kernels and performs no device sync."""
    for tier in ("auto", "whole", "stage", "operator"):
        data.conf.set("spark.tpu.compile.tier", tier)
        before = KC.launches
        df = data.sql(Q_AGG)
        df.query_execution.physical       # plan (tier decision included)
        df.query_execution.analysis_report()
        assert KC.launches == before, tier


def test_whole_tier_attribution_matches_global(tiers, data):
    """obs contract: the whole program's single dispatch attributes to
    WholeQueryExec (re-attributed to members via fused_members), and the
    attributed total equals the global launch counter delta."""
    data.conf.set("spark.tpu.compile.tier", "whole")
    data.sql(Q_AGG).toArrow()  # warm
    before = KC.launches
    df = data.sql(Q_AGG)
    df.toArrow()
    global_delta = KC.launches - before
    graph = df.query_execution.plan_graph()
    attributed = sum(v for nd in graph
                     for v in (nd.get("launches") or {}).values())
    assert attributed == global_delta
    assert global_delta == 1
    fused = [nd for nd in graph if nd.get("fused")]
    assert fused and any("HashAggregate" in m or "Aggregate" in m
                         for nd in fused for m in nd["fused"]), graph


def test_whole_tier_explain_surfaces_decision(tiers, data, capsys):
    data.conf.set("spark.tpu.compile.tier", "whole")
    data.sql(Q_AGG).explain("analysis")
    out = capsys.readouterr().out
    assert "compilation tier: whole" in out
    assert "WHOLE-QUERY program" in out
    assert "whole_query" in out


def test_operator_tier_boundary_explained(tiers, data):
    data.conf.set("spark.tpu.compile.tier", "operator")
    report = data.sql(Q_AGG).query_execution.analysis_report()
    assert any("OPERATOR" in b for b in report.fusion_boundaries), \
        report.fusion_boundaries


def test_whole_tier_memory_model_bounds_measured(tiers, data):
    """The whole-query memory model (fully-resident sum) upper-bounds the
    measured per-query ledger watermark."""
    data.conf.set("spark.tpu.compile.tier", "whole")
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    df = data.sql(Q_AGG)
    report = df.query_execution.analysis_report()
    assert report.predicted_peak_hbm and report.predicted_peak_hbm > 0
    df.toArrow()
    qrec = GLOBAL_LEDGER.query_record(
        getattr(df.query_execution._last_ctx, "query_id", None))
    if qrec and qrec.get("peak_bytes"):
        assert report.predicted_peak_hbm >= qrec["peak_bytes"] // 4, (
            report.predicted_peak_hbm, qrec)


# ---------------------------------------------------------------------------
# satellite: dictionary-domain UDF evaluation
# ---------------------------------------------------------------------------

def test_udf_dict_domain_filter(tiers, data):
    """A non-host-evaluable predicate (a Python UDF) over a dictionary-
    encoded string column evaluates once per DISTINCT value and maps over
    codes: |dict| calls, not |rows|; encoding off restores the per-row
    oracle with identical results."""
    from spark_tpu.api import functions as F

    calls = [0]

    def is_even_cat(v):
        calls[0] += 1
        return v is not None and int(v[3:]) % 2 == 0

    from spark_tpu.types import boolean

    pred = F.udf(is_even_cat, boolean)
    df = data.table("wq_t")
    q = df.filter(pred(F.col("s"))).select("k", "v", "s")
    base = data._metrics.snapshot()["counters"].get(
        "udf.dict_domain_evals", 0)
    out = q.toArrow().to_pandas().sort_values(["k", "v"]) \
        .reset_index(drop=True)
    n_calls_encoded = calls[0]
    assert data._metrics.snapshot()["counters"].get(
        "udf.dict_domain_evals", 0) > base
    # 5 distinct values per batch, a handful of batches — nowhere near
    # the ~5000 per-row calls
    assert n_calls_encoded <= 5 * 4, n_calls_encoded

    calls[0] = 0
    data.conf.set("spark.tpu.encoding.enabled", "false")
    try:
        df2 = data.table("wq_t")
        ref = df2.filter(pred(F.col("s"))).select("k", "v", "s").toArrow() \
            .to_pandas().sort_values(["k", "v"]).reset_index(drop=True)
        assert calls[0] >= len(ref)  # per-row oracle
    finally:
        data.conf.unset("spark.tpu.encoding.enabled")
    import pandas as pd

    pd.testing.assert_frame_equal(ref, out, check_dtype=False)


def test_udf_dict_domain_skips_filtered_values(tiers, spark):
    """The lane evaluates the LIVE distinct codes only: a dictionary
    value that exists solely in rows an upstream filter dropped must
    never reach the UDF (a partial UDF guarded by that filter would
    crash on it under the full-dictionary domain)."""
    from spark_tpu.api import functions as F
    from spark_tpu.types import float64

    t = pa.table({"s": (["aa", "bbb", ""] * 200)})
    spark.createDataFrame(t).createOrReplaceTempView("wq_guard")
    inv_len = F.udf(lambda v: 1.0 / len(v), float64)
    df = spark.table("wq_guard").filter("length(s) > 0")
    out = df.select(inv_len(F.col("s")).alias("r")).toArrow().to_pandas()
    assert len(out) == 400
    assert sorted(set(round(x, 4) for x in out["r"])) == [
        round(1 / 3, 4), 0.5]


def test_udf_dict_domain_null_lane(tiers, spark):
    """Invalid rows take the dedicated null lane (the UDF sees None once),
    matching per-row semantics."""
    from spark_tpu.api import functions as F
    from spark_tpu.types import string

    t = pa.table({"s": pa.array(["a", None, "b", "a", None]),
                  "i": pa.array(np.arange(5, dtype=np.int64))})
    spark.createDataFrame(t).createOrReplaceTempView("wq_nulls")

    def tag(v):
        return "NULL" if v is None else v.upper() + "!"

    u = F.udf(tag, string)
    df = spark.table("wq_nulls")
    out = df.select(F.col("i"), u(F.col("s")).alias("t")).toArrow().to_pandas() \
        .sort_values("i")["t"].tolist()
    assert out == ["A!", "NULL", "B!", "A!", "NULL"]


def test_udf_plan_model_exact_with_dict_lane(tiers, data):
    """plan_lint models PythonEvalExec: one argument-pipeline dispatch per
    batch per UDF, layout/value model passing through — predictions stay
    EXACT, with the per-distinct lane noted."""
    from spark_tpu.api import functions as F
    from spark_tpu.types import boolean

    pred = F.udf(lambda v: v is not None and v.endswith("1"), boolean)

    def q():
        df = data.table("wq_t")
        return df.select(F.col("k"), F.col("s"),
                         pred(F.col("s")).alias("hit")) \
            .groupBy("k").count()

    report = q().query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    assert any("dictionary-domain lane" in n
               for s in report.stages for n in s["notes"]), \
        [n for s in report.stages for n in s["notes"]]
    measured = _measured(q)
    assert report.predicted_launches == measured, (
        report.predicted_launches, measured)
    # a FILTER on the UDF output is value-opaque: the model must degrade
    # honestly, never claim exactness over an untraced span
    flt = (data.table("wq_t")
           .select(F.col("k"), pred(F.col("s")).alias("hit"))
           .filter("hit").groupBy("k").count())
    rep2 = flt.query_execution.analysis_report()
    assert not rep2.exact and rep2.inexact_reasons


# ---------------------------------------------------------------------------
# satellite: RunInfo through pass-through pipeline outputs
# ---------------------------------------------------------------------------

def test_ragg_fires_through_filter_pipeline(tiers, spark):
    """A sorted sparse key aggregated through a filter/project chain takes
    the sorted-run (ragg) kernel — pass-through outputs inherit ingest
    RunInfo — and the analyzer predicts it exactly (gated stage tier:
    default minRows routes to the shared kernels where ragg lives)."""
    spark.conf.unset("spark.tpu.fusion.minRows")  # default gate ON
    n = 3000
    k = np.sort(np.random.default_rng(5).integers(0, 10 ** 9, n))
    v = np.arange(n, dtype=np.int64)
    spark.createDataFrame(pa.table({"k": k, "v": v})) \
        .createOrReplaceTempView("wq_sorted")
    q = ("select k, sum(v) sv, count(*) c from wq_sorted "
         "where v > 100 group by k")
    report = spark.sql(q).query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    assert report.predicted_launches.get("ragg", 0) >= 1, \
        report.predicted_launches
    measured = _measured(lambda: spark.sql(q))
    assert report.predicted_launches == measured
    # the decoded oracle agrees on values
    import pandas as pd

    got = spark.sql(q).toArrow().to_pandas().sort_values("k") \
        .reset_index(drop=True)
    spark.conf.set("spark.tpu.encoding.enabled", "false")
    try:
        ref = spark.sql(q).toArrow().to_pandas().sort_values("k") \
            .reset_index(drop=True)
    finally:
        spark.conf.unset("spark.tpu.encoding.enabled")
    pd.testing.assert_frame_equal(ref, got, check_dtype=False)


# ---------------------------------------------------------------------------
# satellite: mesh quota-retry restaging
# ---------------------------------------------------------------------------

def test_mesh_quota_retry_reuses_staged_planes(tiers, spark, monkeypatch):
    """A skewed mesh exchange overflows its quota: the retry reuses the
    device-resident base planes (one base staging at first overflow,
    ZERO further host->device restages), the ledger stays balanced, and
    the launch prediction stays exact — retries included."""
    import spark_tpu.parallel.mesh_exchange as ME

    n = 6000
    spark.createDataFrame(pa.table({
        "k": np.full(n, 5, np.int64),
        "v": np.arange(n, dtype=np.int64),
    })).createOrReplaceTempView("wq_skew")

    pad_calls = [0]
    base_calls = [0]
    orig_pad = ME._pad_shards
    orig_base = ME._pad_base

    def count_pad(*a, **k):
        pad_calls[0] += 1
        return orig_pad(*a, **k)

    def count_base(*a, **k):
        base_calls[0] += 1
        return orig_base(*a, **k)

    monkeypatch.setattr(ME, "_pad_shards", count_pad)
    monkeypatch.setattr(ME, "_pad_base", count_base)

    def q():
        return spark.sql("select k, v from wq_skew").repartition(4, "k")

    report = q().query_execution.analysis_report()
    attempts = report.predicted_launches.get("mesh_stage", 0)
    assert attempts >= 2, report.predicted_launches  # quota retried
    out = q().toArrow()
    assert out.num_rows == n
    # host-side padding ran for attempt 1 only; every retry embedded the
    # persisted base planes in-program
    first_attempt_pads = pad_calls[0]
    assert base_calls[0] >= 1, "base planes never staged"
    pad_calls[0] = 0
    base_calls[0] = 0
    measured = _measured(q)
    assert report.predicted_launches == measured, (
        report.predicted_launches, measured)
    # warm runs still pad only the first attempt (two runs in _measured)
    assert pad_calls[0] <= first_attempt_pads * 2
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    assert GLOBAL_LEDGER.verify() == [], \
        "device ledger unbalanced after retry"
