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


def _executed(session, build):
    """One execution: its launches by kind, and the session's counters it
    moved."""
    before_k = dict(KC.launches_by_kind)
    before_c = dict(session._metrics.snapshot()["counters"])
    build().toArrow()
    after_c = session._metrics.snapshot()["counters"]
    return ({k: v - before_k.get(k, 0)
             for k, v in KC.launches_by_kind.items()
             if v != before_k.get(k, 0)},
            {k: v - before_c.get(k, 0) for k, v in after_c.items()
             if v != before_c.get(k, 0)})


def test_whole_tier_join_retry_predicted(tiers, spark):
    """q7's fact-probe joins overflow the initial output buckets. In a
    process that has not run the plan, the program re-dispatches with
    bumped capacities and the analyzer's round-by-round mirror (truncated
    upstream traces included) predicts the FIRST execution's dispatches
    EXACTLY; the process then remembers the capacities the ladder ended
    with, so a report taken after that execution predicts one launch and
    the second execution measures one."""
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    q7 = """SELECT i.i_category, AVG(ss_quantity) AS agg1, COUNT(*) AS cnt
        FROM store_sales ss JOIN item i ON ss.ss_item_sk = i.i_item_sk
        JOIN date_dim d ON ss.ss_sold_date_sk = d.d_date_sk
        WHERE d.d_year = 1999 GROUP BY i.i_category"""
    cold = spark.sql(q7).query_execution.analysis_report()
    assert cold.exact, cold.inexact_reasons
    ladder = cold.predicted_launches.get("whole_query", 0)
    assert ladder >= 2, cold.predicted_launches
    kinds, counters = _executed(spark, lambda: spark.sql(q7))
    assert kinds.get("whole_query") == ladder, (kinds, ladder)
    assert counters.get("whole_query.dispatches") == ladder
    assert counters.get("whole_query.capacity_retries") == ladder - 1
    assert "cache.capacity_seeded" not in counters
    warm = spark.sql(q7).query_execution.analysis_report()
    assert warm.exact, warm.inexact_reasons
    assert warm.predicted_launches == {"whole_query": 1}
    kinds, counters = _executed(spark, lambda: spark.sql(q7))
    assert kinds == warm.predicted_launches
    assert counters.get("whole_query.dispatches") == 1
    assert "whole_query.capacity_retries" not in counters
    assert counters.get("cache.capacity_remembered") == 1


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
        note = f"rank[probe={path},expand={path}] src=gather key=exact"
        assert all(m.endswith(note) for m in joins), joins
        text = rec["kernel"]._kernel.lower(*rec["args"]).as_text(
            debug_info=True)
        assert f"probe/rank_{path}" in text and f"expand/rank_{path}" in text
        assert f"rank_{other}" not in text
        if path == "merge":
            pd.testing.assert_frame_equal(ref, out, check_dtype=False)
            shown = spark.sql(query).query_execution.explain_string("device")
            assert note in shown, shown


def test_join_src_paths_counted_and_shown(tiers, spark, monkeypatch):
    """Each sorted join asks `ops/joining.src_path` how `_expand` has a
    probe row's values at the output's slots: the lowering counts the
    answer, ends the join's members row with it, and the traced body takes
    the same one. Mini shapes keep the gathers, in the very text `_expand`
    lowered to before the rule was there; forced to fill they rank nothing
    in `expand`, carry their validity planes as one word, same rows."""
    import jax
    import pandas as pd

    from join_reference import expand_of_pr31
    from spark_tpu.ops import joining as J
    from spark_tpu.physical.compile import capture_programs
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    names = ("join.src_fill", "join.src_gather", "join.rank_search",
             "join.rank_merge")

    def counts():
        c = spark._metrics.snapshot()["counters"]
        return {n: c.get(n, 0) for n in names}

    def run(query):
        before = counts()
        with capture_programs() as programs:
            out = spark.sql(query).toArrow().to_pandas()
        assert programs
        delta = {n: v - before[n] for n, v in counts().items()}
        rec = programs[-1]
        joins = [m for s, m in zip(rec["scopes"], rec["members"])
                 if s and s.endswith(".HashJoin")]
        assert len(joins) == 2
        return out, delta, len(programs), rec, joins

    def lowered(rec, scopes=True):
        # a function of its own each time: nothing traced before is reused
        fn = rec["kernel"]._kernel.__wrapped__
        return jax.jit(lambda *a: fn(*a)).lower(*rec["args"]).as_text(
            debug_info=scopes)

    # the rule as it stands: two joins a lowering, each gathers, and
    # `_expand` lowers to what PR 31's did
    spark.conf.set("spark.tpu.compile.tier", "whole")
    out, delta, n, rec, joins = run(Q3_SORTED)
    assert delta == {"join.src_fill": 0, "join.src_gather": 2 * n,
                     "join.rank_search": 6 * n, "join.rank_merge": 0}
    note = "rank[probe=search,expand=search] src=gather key=exact"
    assert all(m.endswith(note) for m in joins), joins
    text = lowered(rec)
    assert "expand/rank_search" in text and "src_fill" not in text
    text = lowered(rec, scopes=False)
    with monkeypatch.context() as m:
        m.setattr(J, "_expand", expand_of_pr31)
        assert lowered(rec, scopes=False) == text

    # a month no other test asks for: the programs are built here
    query = Q3_SORTED.replace("d_moy = 11", "d_moy = 10")
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(query).toArrow().to_pandas()
    assert len(ref)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    monkeypatch.setattr(J, "src_path", lambda pcap, out_cap: "fill")
    out, delta, n, rec, joins = run(query)
    assert delta == {"join.src_fill": 2 * n, "join.src_gather": 0,
                     "join.rank_search": 4 * n, "join.rank_merge": 0}
    note = "rank[probe=search,expand=none] src=fill key=exact"
    assert all(m.endswith(note) for m in joins), joins
    text = lowered(rec)
    assert "expand/src_fill" in text and "gather/src_fill" in text
    assert "expand/rank_" not in text
    # the build side's validity byte left the joins: each build column is
    # gathered by its reader, one plane of an index plane each here
    assert "gather/pack_valid" not in text and "/late_gather/" in text
    pd.testing.assert_frame_equal(ref, out, check_dtype=False)
    shown = spark.sql(query).query_execution.explain_string("device")
    assert note in shown, shown


def test_join_key_paths_counted_and_shown(tiers, spark, monkeypatch):
    """Each sorted join asks `ops/joining.key_path` what its build side is
    indexed on: the lowering counts the answer and ends the join's members
    row with it. One 32-bit integer key is its own index: against the same
    join sent to the hash, the program has one `sort` less (the span's
    observation reads the index) and no gather of a key or of a key's
    validity, same rows. Two keys, or one 64-bit key, keep the hash, in
    the very text they lowered to before the rule was there."""
    import jax
    import pandas as pd

    from join_reference import build_index_of_pr33, probe_join_of_pr33
    from spark_tpu.ops import joining as J
    from spark_tpu.physical.compile import capture_programs

    rng = np.random.default_rng(34)
    n, dims = 4000, 40
    k = rng.integers(-2, dims + 2, n)
    spark.createDataFrame(pa.table({
        "k": pa.array(k.astype(np.int32), mask=rng.random(n) < 0.05),
        "k2": (k % 7).astype(np.int32), "w": k.astype(np.int64),
        "v": rng.integers(-50, 100, n),
    })).createOrReplaceTempView("kp_fact")
    d = np.arange(dims)
    spark.createDataFrame(pa.table({
        "dk": pa.array(d.astype(np.int32), mask=d == 3),
        "dk2": (d % 7).astype(np.int32), "dw": d.astype(np.int64),
        "label": [f"lab{i % 5}" for i in d],
    })).createOrReplaceTempView("kp_dim")
    shape = ("select label, sum(v) sv, count(*) c from kp_fact join kp_dim "
             "on {on} where v > {v} group by label order by label")
    names = ("join.key_exact", "join.key_hash")

    def counts():
        c = spark._metrics.snapshot()["counters"]
        return {x: c.get(x, 0) for x in names}

    def run(on, v=10):
        query = shape.format(on=on, v=v)
        spark.conf.set("spark.tpu.compile.tier", "stage")
        ref = spark.sql(query).toArrow().to_pandas()
        assert len(ref) == 5
        spark.conf.set("spark.tpu.compile.tier", "whole")
        before = counts()
        with capture_programs() as programs:
            out = spark.sql(query).toArrow().to_pandas()
        assert programs
        pd.testing.assert_frame_equal(ref, out, check_dtype=False)
        delta = {x: c - before[x] for x, c in counts().items()}
        rec = programs[-1]
        row, = [m for sc, m in zip(rec["scopes"], rec["members"])
                if sc and sc.endswith(".HashJoin")]
        return query, delta, len(programs), rec, row

    def lowered(rec, scopes=True):
        # a function of its own each time: nothing traced before is reused
        fn = rec["kernel"]._kernel.__wrapped__
        return jax.jit(lambda *a: fn(*a)).lower(*rec["args"]).as_text(
            debug_info=scopes)

    def instructions(text, op):
        # a `jnp.take` lowers to a call of a private `_take`, one body for
        # every call of one signature: the calls are the gathers
        return sum(op in ln.split(" loc(")[0] for ln in text.splitlines())

    # one int32 key: exact; the same join sent to the hash is the parent's
    query, delta, progs, rec, row = run("k = dk")
    assert delta == {"join.key_exact": progs, "join.key_hash": 0}
    assert row.endswith(" src=gather key=exact"), row
    exact = lowered(rec)
    shown = spark.sql(query).query_execution.explain_string("device")
    assert "src=gather key=exact" in shown, shown
    with monkeypatch.context() as m:
        m.setattr(J, "key_path", lambda build, probe: "hash")
        _, delta, progs, rec, row = run("k = dk", v=11)   # lowered anew
        assert delta == {"join.key_exact": 0, "join.key_hash": progs}
        assert row.endswith(" src=gather key=hash"), row
        hashed = lowered(rec)
    assert "span_observe" in exact and "span_observe" in hashed
    assert instructions(exact, "stablehlo.sort") == \
        instructions(hashed, "stablehlo.sort") - 1
    # the build key and its validity by the build row, the probe key and
    # its validity by `src`
    assert instructions(exact, "call @_take") == \
        instructions(hashed, "call @_take") - 4

    # two keys, and one 64-bit key (whose span is observed as it was)
    for on, observed in (("k = dk and k2 = dk2", False), ("w = dw", True)):
        _, delta, progs, rec, row = run(on)
        assert delta == {"join.key_exact": 0, "join.key_hash": progs}
        assert row.endswith(" src=gather key=hash"), row
        assert ("span_observe" in lowered(rec)) == observed
        text = lowered(rec, scopes=False)
        with monkeypatch.context() as m:
            m.setattr(J, "build_index", build_index_of_pr33)
            m.setattr(J, "probe_join", probe_join_of_pr33)
            assert lowered(rec, scopes=False) == text


# ---------------------------------------------------------------------------
# late materialisation: a join hands its build side's columns on as row
# numbers, and each is gathered by the first reader of its values
# ---------------------------------------------------------------------------

_DATE_FACT = ("from date_dim d join store_sales ss "
              "on d.d_date_sk = ss.ss_sold_date_sk ")
# name -> (query, the join kinds its program holds, where deferred columns
# are gathered: `mNN.<Kind>/late_gather` of these kinds)
LATE_CASES = {
    # a fact payload read only after the last of three joins
    "star": ("select i.i_category, d.d_year, sum(ss.ss_ext_sales_price) s, "
             "avg(ss.ss_quantity) q, count(*) c " + _DATE_FACT +
             "join item i on ss.ss_item_sk = i.i_item_sk "
             "join store st on ss.ss_store_sk = st.s_store_sk "
             "where d.d_moy = 11 and st.s_state = 'CA' "
             "group by i.i_category, d.d_year",
             {"inner"}, {"HashJoin", "HashAggregate"}),
    # a deferred column is a later join's key on its probe side
    "key_probe": ("select i.i_brand, sum(ss.ss_net_profit) p " + _DATE_FACT +
                  "join item i on ss.ss_item_sk = i.i_item_sk "
                  "where d.d_moy = 3 group by i.i_brand",
                  {"inner"}, {"HashJoin", "HashAggregate"}),
    # ... and on its build side, where an outer join null-extends it
    "key_build": ("select i.i_brand, count(*) c, sum(x.ss_net_profit) p "
                  "from item i left outer join (select ss.ss_item_sk, "
                  "ss.ss_net_profit " + _DATE_FACT + "where d.d_moy = 3) x "
                  "on i.i_item_sk = x.ss_item_sk where i.i_manufact_id < 20 "
                  "group by i.i_brand",
                  {"inner", "left_outer"}, {"HashJoin", "HashAggregate"}),
    # an outer join's null-extended build columns cross a further join
    "outer": ("select i.i_brand, d.d_year, count(*) c, sum(ss.ss_quantity) q "
              "from store_sales ss left outer join (select * from item "
              "where i_manufact_id < 20) i on ss.ss_item_sk = i.i_item_sk "
              "join date_dim d on ss.ss_sold_date_sk = d.d_date_sk "
              "where d.d_moy = 5 group by i.i_brand, d.d_year",
              {"inner", "left_outer"}, {"HashAggregate"}),
    "semi_anti": ("select d.d_year, count(*) c, sum(ss.ss_quantity) q " +
                  _DATE_FACT + "left semi join (select i_item_sk from item "
                  "where i_manufact_id < 10) i on ss.ss_item_sk = i.i_item_sk "
                  "left anti join (select s_store_sk from store where "
                  "s_state = 'CA') st on ss.ss_store_sk = st.s_store_sk "
                  "where d.d_moy = 4 group by d.d_year",
                  {"inner", "left_semi", "left_anti"},
                  {"HashJoin", "HashAggregate"}),
    # dictionary-encoded strings deferred across a join, then grouped on
    "strings": ("select i.i_brand, st.s_state, count(*) c " + _DATE_FACT +
                "join item i on ss.ss_item_sk = i.i_item_sk "
                "join store st on ss.ss_store_sk = st.s_store_sk "
                "where d.d_moy = 2 group by i.i_brand, st.s_state",
                {"inner"}, {"HashJoin", "HashAggregate"}),
    # a deferred column read by a filter the next join's probe side fuses
    "probe_filter": ("select d.d_year, count(*) c, sum(ss.ss_sales_price) p "
                     + _DATE_FACT + "join item i on ss.ss_item_sk = "
                     "i.i_item_sk where ss.ss_quantity > i.i_manufact_id "
                     "and d.d_moy < 7 group by d.d_year",
                     {"inner"}, {"HashJoin", "HashAggregate"}),
}


def _lowered(rec, scopes=True):
    """A captured program's text, traced by a function of its own so
    that nothing traced before is reused."""
    import jax

    fn = rec["kernel"]._kernel.__wrapped__
    return jax.jit(lambda *a: fn(*a)).lower(*rec["args"]).as_text(
        debug_info=scopes)


@pytest.mark.parametrize("case", list(LATE_CASES))
def test_late_columns_give_the_stage_tiers_rows(tiers, spark, case):
    """Every shape a deferred column takes through a program gives the
    stage tier's rows: read after the last join, a later join's key on
    either side, null-extended by an outer join, through semi and anti
    joins, a string, read by a filter fused into a join's probe side. The
    program shows where the deferred columns were gathered."""
    import re

    import pandas as pd

    from spark_tpu.physical.compile import capture_programs
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    query, kinds, readers = LATE_CASES[case]
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(query).toArrow().to_pandas()
    assert len(ref)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    before = spark._metrics.snapshot()["counters"].get(
        "join.build_deferred", 0)
    df = spark.sql(query)
    with capture_programs() as programs:
        out = df.toArrow().to_pandas()
    assert spark._metrics.snapshot()["counters"].get(
        "join.build_deferred", 0) > before
    by = list(ref.columns)
    pd.testing.assert_frame_equal(
        ref.sort_values(by).reset_index(drop=True),
        out.sort_values(by).reset_index(drop=True), check_dtype=False)
    joins = [n for n in df.query_execution.physical.plan.iter_nodes()
             if type(n).__name__ == "HashJoinExec"]
    assert {j.join_type for j in joins} == kinds
    if case == "probe_filter":
        assert any(j.probe_fusion is not None and j.probe_fusion[0]
                   for j in joins)
    rec = programs[-1]
    rows = [m for s, m in zip(rec["scopes"], rec["members"])
            if s and s.endswith(".HashJoin")]
    assert any(" late=" in m for m in rows), rows
    found = set(re.findall(r"m\d+\.(\w+)/late_gather", _lowered(rec)))
    assert found == readers, found


def test_join_hands_build_columns_on_as_row_numbers(tiers, spark):
    """Two joins: the first hands the fact table's columns on as its build
    row numbers and the second carries that one plane for them, so no
    payload is gathered at the first join's output capacity; the payload
    is gathered once, from the fact table, at the aggregate's capacity,
    its two validity planes as one byte. Counted per program built and
    shown in the join's row, before its rank note, and in explain."""
    import re

    import pandas as pd

    from spark_tpu.exec.persist_cache import PLAN_MEMORY
    from spark_tpu.physical.compile import capture_programs

    rng = np.random.default_rng(40)
    n = 6000
    spark.createDataFrame(pa.table({
        "k1": rng.integers(0, 50, n).astype(np.int32),
        "k2": rng.integers(0, 40, n).astype(np.int32),
        "a": pa.array(rng.random(n), mask=rng.random(n) < 0.1),
        "b": pa.array(rng.integers(-50, 100, n), mask=rng.random(n) < 0.1),
    })).createOrReplaceTempView("lf_fact")
    spark.createDataFrame(pa.table({
        "dk1": np.arange(50, dtype=np.int32),
        "flag": np.arange(50) % 5 == 0,
    })).createOrReplaceTempView("lf_d1")
    spark.createDataFrame(pa.table({
        "dk2": np.arange(40, dtype=np.int32),
        "y": [f"y{i % 4}" for i in range(40)],
    })).createOrReplaceTempView("lf_d2")
    query = ("select d2.y, sum(f.a) sa, sum(f.b) sb, count(*) c from lf_d1 d1 "
             "join lf_fact f on d1.dk1 = f.k1 join lf_d2 d2 on f.k2 = d2.dk2 "
             "where d1.flag and d2.dk2 < 8 group by d2.y order by d2.y")
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(query).toArrow().to_pandas()
    assert len(ref) == 4
    spark.conf.set("spark.tpu.compile.tier", "whole")
    names = ("join.build_deferred", "join.build_gathered")

    def counts():
        c = spark._metrics.snapshot()["counters"]
        return {x: c.get(x, 0) for x in names}

    before = counts()
    df = spark.sql(query)
    with capture_programs() as programs:
        out = df.toArrow().to_pandas()
    pd.testing.assert_frame_equal(ref, out, check_dtype=False)
    progs = len(programs)
    # the fact table's join hands on its four columns and its consumer,
    # the next join, gathers one (its key); the next join hands on lf_d1's
    assert {x: c - before[x] for x, c in counts().items()} == {
        "join.build_deferred": 4 * progs, "join.build_gathered": progs}
    rec = programs[-1]
    rows = [m for s, m in zip(rec["scopes"], rec["members"])
            if s and s.endswith(".HashJoin")]
    assert len(rows) == 2
    assert re.search(r" late=3/4 rank\[.*\] src=\w+ key=exact$", rows[1])
    assert re.search(r" late=1/1 rank\[.*\] src=\w+ key=exact$", rows[0])
    first, second = PLAN_MEMORY.get(
        df.query_execution.plan_fingerprint()["fingerprint"])
    assert first != second
    takes = re.findall(r"call @_take\w*\(.*\) : \(tensor<(\d+)x(\w+)>, "
                       r"tensor<\d+xi32>\) -> tensor<(\d+)x", _lowered(rec,
                                                                 False))
    payload = [(int(src), int(to)) for src, dt, to in takes
               if dt in ("f64", "i64")]
    assert payload and all(to != first for _src, to in payload), payload
    assert any(src > first and to == second for src, to in payload)
    assert "late_gather/pack_valid" in _lowered(rec)
    shown = spark.sql(query).query_execution.explain_string("device")
    assert " late=3/4 rank[" in shown, shown


def test_is_null_gathers_the_validity_alone(tiers, spark, monkeypatch):
    """q89's shape: the join key a later join needs is checked for NULL in
    the probe side of the join before it (the inferred IS NOT NULL). That
    reads the key's validity alone: the validity is gathered there, and
    the key's values stay deferred to the join that reads them."""
    import pandas as pd

    from spark_tpu.physical import whole_query as WQ
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    query = ("select i.i_category, st.s_state, d.d_moy, "
             "sum(ss.ss_sales_price) p from store st join store_sales ss "
             "on st.s_store_sk = ss.ss_store_sk join item i "
             "on ss.ss_item_sk = i.i_item_sk join date_dim d "
             "on ss.ss_sold_date_sk = d.d_date_sk where d.d_year = 1999 "
             "and i.i_category in ('Books', 'Music') "
             "group by i.i_category, st.s_state, d.d_moy")
    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = spark.sql(query).toArrow().to_pandas()
    assert len(ref)
    flags_alone = []
    take = WQ._late_take

    def spy(datas, valids, cols, valid_cols=()):
        flags_alone.extend(i for i in valid_cols
                           if isinstance(datas[i], WQ._Late)
                           and isinstance(valids[i], WQ._Late))
        return take(datas, valids, cols, valid_cols)

    monkeypatch.setattr(WQ, "_late_take", spy)
    spark.conf.set("spark.tpu.compile.tier", "whole")
    df = spark.sql(query)
    out = df.toArrow().to_pandas()
    by = list(ref.columns)
    pd.testing.assert_frame_equal(
        ref.sort_values(by).reset_index(drop=True),
        out.sort_values(by).reset_index(drop=True), check_dtype=False)
    fused = [str(f) for n in df.query_execution.physical.plan.iter_nodes()
             if type(n).__name__ == "HashJoinExec" and n.probe_fusion
             for f in n.probe_fusion[0]]
    assert any(f.startswith("isnotnull(ss_sold_date_sk") for f in fused)
    assert flags_alone


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


def test_whole_tier_decision_rides_the_span_and_explain_analyze_agrees(
        tiers, data):
    """The tier decision is an argument of the `whole_query.program`
    span, and EXPLAIN ANALYZE over the whole tier measures the one
    predicted dispatch: no finding is an error."""
    data.conf.set("spark.tpu.compile.tier", "whole")
    mark = data.tracer.mark()
    report = data.sql(Q_JOIN_AGG).query_execution.analyzed_report()
    assert not report.has_unexplained_drift, report.render()
    assert set(report.measured) == {"whole_query"}, dict(report.measured)
    assert dict(report.predicted) == dict(report.measured)
    programs = [d for d in data.tracer.since(mark)
                if d["name"] == "whole_query.program"]
    assert programs and all(
        (d.get("args") or {}).get("tier") == "whole" for d in programs), \
        programs


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


# ---------------------------------------------------------------------------
# window functions in the whole-query program (physical/whole_query.py
# _lower_window over physical/window.trace_window)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wdata(spark):
    """Seeded rows with NULL keys, NULL values, ties, a partition whose
    values are all NULL, and key columns of every admitted type."""
    import datetime
    from decimal import Decimal

    rng = np.random.default_rng(29)
    n = 700

    def nulls(values, frac, typ=None):
        mask = rng.random(n) < frac
        return pa.array([None if m else v for v, m in zip(values, mask)],
                        typ)

    pi = rng.integers(0, 9, n)
    day0 = datetime.date(1999, 1, 1)
    vi = rng.integers(-40, 90, n).tolist()
    vdec = [Decimal(int(x)).scaleb(-2) for x in rng.integers(-9999, 99999, n)]
    for i in range(n):               # partition 3: no value at all
        if pi[i] == 3:
            vi[i] = None
            vdec[i] = None
    table = pa.table({
        "uid": pa.array(np.arange(n, dtype=np.int32)),
        "pi": nulls(pi.astype(np.int32).tolist(), 0.05, pa.int32()),
        "pd": nulls([day0 + datetime.timedelta(int(x) % 5) for x in pi],
                    0.05, pa.date32()),
        "pdec": nulls([Decimal(int(x) % 4).scaleb(-1) for x in pi], 0.05,
                      pa.decimal128(5, 1)),
        "ps": nulls([f"store{x % 6}" for x in pi], 0.05, pa.string()),
        "ps2": pa.array([f"co{x % 2}" for x in range(n)]),
        "oi": nulls(rng.integers(0, 12, n).astype(np.int32).tolist(), 0.05,
                    pa.int32()),
        "od": nulls([day0 + datetime.timedelta(int(x))
                     for x in rng.integers(0, 12, n)], 0.05, pa.date32()),
        "odec": nulls([Decimal(int(x)).scaleb(-2)
                       for x in rng.integers(0, 12, n)], 0.05,
                      pa.decimal128(7, 2)),
        "vi": nulls(vi, 0.1, pa.int32()),
        "vf": nulls(rng.random(n).tolist(), 0.1, pa.float64()),
        "vdec": nulls(vdec, 0.1, pa.decimal128(7, 2)),
    })
    spark.createDataFrame(table).createOrReplaceTempView("wq_w")
    return spark


def _window_rows(spark, tier, select, where=""):
    spark.conf.set("spark.tpu.compile.tier", tier)
    df = spark.sql(f"select uid, {select} from wq_w {where}")
    phys = df.query_execution.physical
    dec = getattr(phys, "decision", None) \
        or getattr(phys, "_tier_decision", None)
    t = df.toArrow()
    rows = sorted(zip(*[c.to_pylist() for c in t.columns]))
    return dec, rows, t.schema


_AGGS = ", ".join(f"{fn}({v}) over (partition by {{pk}}) {fn}_{v}"
                  for fn in ("sum", "avg", "min", "max", "count")
                  for v in ("vi", "vf", "vdec")) \
    + ", count(*) over (partition by {pk}) n"
_RANKS = ", ".join(f"{fn}() over (partition by {{pk}} order by {{ok}}) {fn}_"
                   for fn in ("rank", "dense_rank")) \
    + ", row_number() over (partition by {pk} order by {ok}, uid) rn"
_RUNNING = ", ".join(f"{fn}({v}) over (partition by {{pk}} order by {{ok}}) "
                     f"{fn}_{v}"
                     for fn in ("sum", "avg", "min", "max", "count")
                     for v in ("vi", "vdec"))

WINDOW_ADMITTED = (
    [(f"whole_partition-by_{pk.replace(', ', '_')}", _AGGS.format(pk=pk))
     for pk in ("pi", "pd", "pdec", "ps", "ps, ps2, pi")]
    + [(f"ranks-by_{pk}-order_{ok.split()[0]}",
        _RANKS.format(pk=pk, ok=ok))
       for pk, ok in (("pi", "oi"), ("ps", "od desc"), ("pd", "odec"),
                      ("ps, ps2", "oi desc nulls last, od"))]
    + [(f"running-by_{pk}-order_{ok}", _RUNNING.format(pk=pk, ok=ok))
       for pk, ok in (("pi", "oi"), ("ps", "od"), ("pdec", "odec"))]
    + [("no_partition_key", "sum(vi) over () s, rank() over (order by oi) r")])


@pytest.mark.parametrize("where", ["", "where uid < 0"],
                         ids=["rows", "empty_input"])
@pytest.mark.parametrize("select", [s for _n, s in WINDOW_ADMITTED],
                         ids=[n for n, _s in WINDOW_ADMITTED])
def test_window_whole_tier_gives_the_operator_tiers_rows(tiers, wdata,
                                                         select, where):
    """Every admitted function x frame x key type: the whole tier runs
    the window inside its one program and returns the operator tier's
    rows and types, NULL keys (one partition), NULL values (skipped; a
    partition with none is NULL), ties and an empty input included."""
    dec, rows, schema = _window_rows(wdata, "whole", select, where)
    assert dec.tier == "whole", dec
    before, misses = dict(KC.launches_by_kind), KC.misses
    _dec, again, _s = _window_rows(wdata, "whole", select, where)
    kinds = {k: v - before.get(k, 0) for k, v in KC.launches_by_kind.items()
             if v != before.get(k, 0)}
    assert kinds == {"whole_query": 1}, kinds
    assert KC.misses == misses, "the same query built its program again"
    _dec, want, want_schema = _window_rows(wdata, "operator", select, where)
    assert schema == want_schema
    assert rows == want == again
    assert bool(rows) != bool(where)


WINDOW_REFUSED = [
    ("lag(vi) over (partition by pi order by oi, uid) x", "function lag"),
    ("lead(vi, 2) over (partition by pi order by oi, uid) x",
     "function lead"),
    ("ntile(3) over (partition by pi order by oi, uid) x", "function ntile"),
    ("nth_value(vi, 2) over (partition by pi order by oi, uid) x",
     "function nthvalue"),
    ("first_value(vi) over (partition by pi order by oi, uid) x",
     "function firstvalue"),
    ("percent_rank() over (partition by pi order by oi) x",
     "function percentrank"),
    ("sum(vi) over (partition by pi order by oi, uid rows between 1 "
     "preceding and 1 following) x", "frame ROWS BETWEEN -1 AND 1 of sum"),
    ("avg(vdec) over (partition by pi order by oi, uid rows between "
     "unbounded preceding and current row) x",
     "frame ROWS BETWEEN None AND 0 of average"),
    ("sum(vi) over (partition by pi order by uid range between 2 preceding "
     "and current row) x", "frame VRANGE BETWEEN -2 AND 0 of sum"),
    ("rank() over (partition by pi order by ps) x",
     "order key ps is a string"),
    ("min(ps) over (partition by pi) x",
     "function min over the dictionary-encoded ps"),
]


@pytest.mark.parametrize("select,reason", WINDOW_REFUSED,
                         ids=[r.replace(" ", "_") for _s, r in
                              WINDOW_REFUSED])
def test_window_refused_by_name_runs_on_the_stage_tier(tiers, wdata, select,
                                                       reason):
    """What `_lower_window` does not trace is refused with a reason that
    names the function, frame or key, and answers on the stage tier."""
    dec, rows, _schema = _window_rows(wdata, "whole", select)
    assert dec.tier == "stage", dec
    assert "whole-query fallback: window" in dec.reason, dec.reason
    assert reason in dec.reason, dec.reason
    _dec, want, _s = _window_rows(wdata, "stage", select)
    assert rows == want and rows


def test_window_member_scopes_and_prediction(tiers, wdata):
    """The window is a member of the program under `mNN.Window` with its
    three phases as scopes; the attempt span says how many Window members
    the program holds; plan_lint predicts the one launch."""
    import time

    from spark_tpu.obs.tracing import recorded_spans
    from spark_tpu.physical.compile import capture_programs

    wdata.conf.set("spark.tpu.compile.tier", "whole")
    q = ("select uid, avg(vdec) over (partition by ps) a, rank() over "
         "(partition by ps order by oi) r from wq_w where uid >= 0")
    df = wdata.sql(q)
    report = df.query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    assert (report.tier or {}).get("tier") == "whole"
    t0 = time.perf_counter()
    with capture_programs() as programs:
        df.toArrow()
    assert report.predicted_launches == _measured(lambda: wdata.sql(q))
    rec = programs[-1]
    windows = [s for s in rec["scopes"] if s and s.endswith(".Window")]
    assert len(windows) == 2, rec["scopes"]
    text = rec["kernel"]._kernel.lower(*rec["args"]).as_text(debug_info=True)
    for phase in ("layout_sort", "frame", "scatter_back"):
        assert f"{windows[0]}/{phase}" in text, phase
    attempts = [s for s in recorded_spans(t0)
                if s["name"] == "whole_query.attempt"]
    assert attempts and attempts[-1]["args"]["window_members"] == 2


def test_window_counts_in_the_resident_estimate(tiers, wdata):
    from spark_tpu.physical.whole_query import _estimate_resident_bytes

    wdata.conf.set("spark.tpu.compile.tier", "stage")
    plain = wdata.sql("select uid, ps, oi, vi from wq_w")
    win = wdata.sql("select uid, ps, oi, vi, rank() over (partition by ps "
                    "order by oi) r from wq_w")
    a = _estimate_resident_bytes(plain.query_execution.physical, wdata.conf)
    b = _estimate_resident_bytes(win.query_execution.physical, wdata.conf)
    cap = 1024                     # 700 rows in one bucket
    # the window's own output tile, the sort's operands, the layout
    assert b - a >= cap * (4 + 12 * 2 + 8 + 36), (a, b)


def test_mesh_whole_refuses_a_window_by_name(tiers, wdata):
    from spark_tpu.physical.whole_query import supported_mesh_whole

    wdata.conf.set("spark.tpu.compile.tier", "stage")
    df = wdata.sql("select ps, sum(vi) over (partition by ps) s from wq_w") \
        .repartition(4, "ps")
    ok, why, _det = supported_mesh_whole(df.query_execution.physical,
                                         wdata.conf)
    assert not ok and "WindowExec has no mesh-whole lowering" in why, why
    wdata.conf.set("spark.tpu.compile.tier", "mesh-whole")
    df = wdata.sql("select ps, sum(vi) over (partition by ps) s from wq_w") \
        .repartition(4, "ps")
    dec = df.query_execution.physical.decision
    assert dec.tier == "whole" and "WindowExec" in dec.reason, dec


@pytest.fixture(scope="module")
def wtpcds():
    """tests/tpcds's tables in a session of their own (the module's other
    tests register tpcds_mini's under the same names)."""
    from spark_tpu import TpuSession
    from tests.tpcds.datagen import gen_tpcds_full

    s = TpuSession("wq-tpcds", {"spark.sql.shuffle.partitions": 4,
                                "spark.tpu.batch.capacity": 1 << 12,
                                "spark.tpu.compile.tier": "whole"})
    for name, tab in gen_tpcds_full(scale=0.1).items():
        s.createDataFrame(tab).createOrReplaceTempView(name)
    yield s
    s.stop()


@pytest.mark.parametrize("qname", ["q89", "q47", "q57", "q98", "q12", "q20"])
def test_tpcds_window_reports_whole_tier_match_the_oracle(wtpcds, qname):
    """The monthly-deviation reports and their siblings, windows and all,
    as whole-query programs against the sqlite oracle's golden rows."""
    import json
    import os

    from test_tpcds_full import GOLDEN_DIR, QUERY_DIR, _norm_rows
    from tests.tpcds.oracle import compare_rows, strip_trailing_limit

    sql = strip_trailing_limit(
        open(os.path.join(QUERY_DIR, f"{qname}.sql")).read())
    before = dict(KC.launches_by_kind)
    df = wtpcds.sql(sql)
    assert type(df.query_execution.physical).__name__ == "WholeQueryExec"
    rows = _norm_rows(df.toArrow())
    kinds = {k for k, v in KC.launches_by_kind.items()
             if v != before.get(k, 0)}
    assert kinds == {"whole_query"}, kinds
    golden = json.load(open(os.path.join(GOLDEN_DIR, f"{qname}.json")))
    assert golden["tier"] == "oracle"
    ok, msg = compare_rows(rows, [tuple(r) for r in golden["rows"]])
    assert ok, msg


@pytest.mark.parametrize("qname", ["q89", "q47"])
def test_tpcds_window_reports_same_on_both_segment_paths(wtpcds, qname,
                                                         monkeypatch):
    """The aggregate and the windows of the monthly-deviation reports by
    scatters (what `ops/grouping.segment_path` picks at a few thousand
    slots) and by scans and sorts (what it picks at the benchmark's 1 Mi
    and 8 Mi, here with a sort's fixed cost taken away): equal tables,
    the counters and the members rows say which body each member traced,
    and the scopes keep their names."""
    import os

    from spark_tpu.ops import grouping as G
    from spark_tpu.physical.compile import capture_programs
    from test_tpcds_full import QUERY_DIR
    from tests.tpcds.oracle import strip_trailing_limit

    sql = strip_trailing_limit(
        open(os.path.join(QUERY_DIR, f"{qname}.sql")).read())
    names = ("agg.segment_scan", "agg.segment_scatter",
             "window.unpermute_sort", "window.unpermute_scatter")

    def run():
        c = wtpcds._metrics.snapshot()["counters"]
        before = {k: c.get(k, 0) for k in names}
        with capture_programs() as programs:
            table = wtpcds.sql(sql).toArrow()
        c = wtpcds._metrics.snapshot()["counters"]
        rec = next(r for r in programs
                   if any(s and s.endswith(".Window") for s in r["scopes"]))
        rows = {s.split(".")[1]: m for s, m in zip(rec["scopes"],
                                                   rec["members"])
                if s and s.endswith((".Window", ".HashAggregate"))}
        return table, {k: c.get(k, 0) - before[k] for k in names}, rec, rows

    plain, delta, _rec, rows = run()
    assert delta["agg.segment_scan"] == 0 == delta["window.unpermute_sort"]
    assert delta["agg.segment_scatter"] and delta["window.unpermute_scatter"]
    assert rows["HashAggregate"].endswith(" segments[scatter]"), rows
    assert rows["Window"].endswith("unpermute=scatter]"), rows
    monkeypatch.setattr(G, "SORT_FIXED_S", 0.0)
    forced, delta, rec, rows = run()
    assert delta["agg.segment_scatter"] == 0 \
        == delta["window.unpermute_scatter"]
    assert delta["agg.segment_scan"] and delta["window.unpermute_sort"]
    assert forced.num_rows and forced.equals(plain)
    assert rows["HashAggregate"].endswith(" segments[scan]"), rows
    assert rows["Window"].endswith(("segments[frame=scan,unpermute=sort]",
                                    "segments[unpermute=sort]")), rows
    text = rec["kernel"]._kernel.lower(*rec["args"]).as_text(debug_info=True)
    for scope, phases in (("HashAggregate", ("group_sort", "group_keys",
                                            "segment_reduce")),
                          ("Window", ("layout_sort", "frame",
                                      "scatter_back"))):
        member = next(s for s in rec["scopes"] if s and s.endswith(scope))
        for phase in phases:
            assert f"{member}/{phase}" in text, (member, phase)
    if qname == "q89":
        shown = wtpcds.sql(sql).query_execution.explain_string("device")
        assert "segments[scan]" in shown \
            and "segments[frame=scan,unpermute=sort]" in shown, shown


@pytest.mark.parametrize("sizes,want", [
    ((10, 20, 850, 10, 1), (((0, 4), (1, 5), (2, 10), (3, 4), (4, 1)),)),
    ((850,), ()),                           # a lone string key gains nothing
    ((0, 3), (((0, 1), (1, 2)),)),          # an empty dictionary: all NULL
    ((2 ** 30,) * 3, (((0, 31), (1, 31)),)),  # 62 bits a pack; the third
    #                                           key is left as it is
], ids=["q89s_group_by", "one_key", "empty_dictionary", "over_62_bits"])
def test_string_keys_pack_into_one_sort_key(sizes, want):
    """Grouping and partition keys that are dictionary codes travel as
    bit fields of one integer (code + 1, 0 for NULL): equal packed keys
    are equal tuples, NULL = NULL included, and keys of other types stay
    behind the packs, with their validity."""
    import jax.numpy as jnp

    from spark_tpu.physical.whole_query import (
        _MCol, _pack_keys, _plan_key_packs,
    )
    from spark_tpu.types import StringType, int32

    class Dict(list):
        def __init__(self, n):
            self.n = n

        def __len__(self):
            return self.n

    metas = [_MCol(int32, True, None)] \
        + [_MCol(StringType(), True, Dict(n)) for n in sizes]
    packs = _plan_key_packs(metas[1:])
    assert packs == want
    packs = _plan_key_packs(metas)           # positions count every key
    assert packs == tuple(tuple((j + 1, b) for j, b in p) for p in want)
    rng = np.random.default_rng(1)
    n = 400
    codes = [rng.integers(0, 3, n)] + [
        rng.integers(0, max(1, min(s, 4)), n) for s in sizes]
    valids = [rng.random(n) < 0.8 for _ in codes]
    keys, kvalids = _pack_keys(
        packs, [jnp.asarray(c, jnp.int32) for c in codes],
        [jnp.asarray(v) for v in valids])
    packed = {j for p in packs for j, _b in p}
    assert len(keys) == len(codes) - len(packed) + len(packs)
    assert [v is None for v in kvalids[:len(packs)]] == [True] * len(packs)

    def tuples(ks, vs):
        cols = [np.where(np.asarray(v), np.asarray(k), -1) if v is not None
                else np.asarray(k) for k, v in zip(ks, vs)]
        return list(zip(*[c.tolist() for c in cols]))

    a, b = tuples(keys, kvalids), tuples(codes, valids)
    for i in range(0, n, 7):
        for j in range(i, n, 13):
            assert (a[i] == a[j]) == (b[i] == b[j]), (i, j)


def test_materialised_cte_is_on_the_timeline(wtpcds):
    """q47's `v1` runs inside `session.sql()`, before the outer plan has a
    QueryExecution: a `cte.materialize` span names it and the rows it
    spliced back, and holds the body's own program."""
    import os
    import time

    from spark_tpu.obs.tracing import recorded_spans
    from test_tpcds_full import QUERY_DIR

    t0 = time.perf_counter()
    df = wtpcds.sql(open(os.path.join(QUERY_DIR, "q47.sql")).read())
    spans = recorded_spans(t0)       # sql() alone: nothing collected yet
    cte = [s for s in spans if s["name"] == "cte.materialize"]
    assert len(cte) == 1 and cte[0]["args"]["cte"] == "v1"
    assert cte[0]["args"]["rows"] > 0
    inside = [s for s in spans if s["name"] == "whole_query.attempt"
              and cte[0]["ts"] <= s["ts"] <= cte[0]["ts"]
              + cte[0]["dur_ms"] / 1000]
    assert inside and inside[-1]["args"]["window_members"] == 2
    assert df.toArrow().num_rows > 0


def test_case_when_query_builds_its_program_once(tiers, data):
    """`canonical_key` leaves out CaseWhen's `branches` (a second view of
    its children, whose text carries attribute ids): the same text parsed
    again finds the program it built, on every tier. (q89's and q47's
    filters are CASE WHEN ... END > 0.1; each execution compiled anew.)"""
    q = ("select k, sv from (select k, sum(v) sv from wq_t group by k) t "
         "where case when sv <> 0 then abs(sv - 10) / sv else null end "
         "> 0.1")
    for tier in ("whole", "stage", "operator"):
        data.conf.set("spark.tpu.compile.tier", tier)
        first = data.sql(q).toArrow()
        misses = KC.misses
        assert data.sql(q).toArrow().equals(first)
        assert KC.misses == misses, tier
