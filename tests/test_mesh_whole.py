"""Mesh whole-query compilation (physical/mesh_whole.py).

Acceptance gates:
  * mesh-whole / whole / stage tiers produce IDENTICAL results on the
    differential suite (repartition+agg, shuffled join+agg, string and
    nullable keys);
  * the mesh tier executes the ENTIRE sharded plan as ONE shard_map
    dispatch per retry round (warm run: {"mesh_whole": 1});
  * plan_lint's mesh mirror predicts the per-kind launch counts EXACTLY,
    including quota-doubling, join-capacity and dense-guard retry rounds,
    fusion on AND off;
  * the warm-start manifest collapses retries across restarts (quota
    seeds) and compiles the dense direct-address probe up front (span
    seeds), with the in-program guard catching seeded-span drift;
  * chaos: a gang fault retries the whole program as a unit, reusing the
    undonated base planes (never re-staging from host), and the device
    ledger stays balanced.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.utils import faults


def _need_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


@pytest.fixture()
def tiers(spark):
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    yield spark
    for k in ("spark.tpu.compile.tier", "spark.tpu.fusion.minRows",
              "spark.tpu.fusion.enabled", "spark.tpu.faults.enabled",
              "spark.tpu.faults.points"):
        spark.conf.unset(k)
    faults.reset()


@pytest.fixture()
def data(spark):
    rng = np.random.default_rng(11)
    n = 5000
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": rng.integers(-50, 100, n),
        "f": rng.random(n),
        "s": [f"cat{i % 5}" for i in range(n)],
    })).createOrReplaceTempView("mw_t")
    spark.createDataFrame(pa.table({
        "dk": np.arange(13, dtype=np.int64),
        "label": [f"lab{i % 3}" for i in range(13)],
    })).createOrReplaceTempView("mw_dim")
    return spark


def _rows(df, by):
    t = df.toArrow().to_pandas()
    return t.sort_values(by).reset_index(drop=True)


def _measured(build):
    build().toArrow()  # warm
    before = dict(KC.launches_by_kind)
    build().toArrow()
    return {k: v - before.get(k, 0) for k, v in KC.launches_by_kind.items()
            if v != before.get(k, 0)}


def _counters(session) -> dict:
    return dict(session._metrics.snapshot()["counters"])


def _q_agg(s):
    return (s.sql("select * from mw_t").repartition(4, "k")
            .groupBy("k").count())


def _q_join_agg(s):
    return (s.sql("select mw_t.k k, v, label from mw_t "
                  "join mw_dim on k = dk where v > 10")
            .repartition(4, "k").groupBy("label").count())


def _q_str(s):
    return (s.sql("select * from mw_t").repartition(4, "s")
            .groupBy("s").count())


QUERIES = [("agg", _q_agg, ["k"]),
           ("join_agg", _q_join_agg, ["label"]),
           ("str_key", _q_str, ["s"])]


# ---------------------------------------------------------------------------
# differential suite: identical results across the tiers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,q,by", QUERIES,
                         ids=[n for n, _q, _b in QUERIES])
def test_mesh_tier_differential(tiers, data, name, q, by):
    import pandas as pd

    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(q(data), by)
    for tier in ("whole", "mesh-whole"):
        data.conf.set("spark.tpu.compile.tier", tier)
        pd.testing.assert_frame_equal(ref, _rows(q(data), by),
                                      check_dtype=False)
    from spark_tpu.physical.mesh_whole import MeshWholeQueryExec

    assert isinstance(q(data).query_execution.physical,
                      MeshWholeQueryExec)


def test_mesh_tier_differential_nullable_key(tiers, data):
    """Nullable join/partition key: null rows hash by the null tag
    through the collective and join to nothing — identical to the
    host-shuffle oracle."""
    import pandas as pd

    _need_devices(4)
    rng = np.random.default_rng(5)
    k = rng.integers(0, 13, 800).astype(object)
    k[::7] = None
    data.createDataFrame(pa.table({
        "nk": pa.array(list(k), type=pa.int64()),
        "nv": np.arange(800),
    })).createOrReplaceTempView("mw_null")

    def q(s):
        return (s.sql("select nk, nv, label from mw_null "
                      "left outer join mw_dim on nk = dk")
                .repartition(4, "nk").groupBy("label").count())

    data.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(q(data), ["label"])
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    pd.testing.assert_frame_equal(ref, _rows(q(data), ["label"]),
                                  check_dtype=False)


# ---------------------------------------------------------------------------
# ONE dispatch per retry round + exact lint predictions
# ---------------------------------------------------------------------------

def test_mesh_whole_single_dispatch_warm(tiers, data):
    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    assert _measured(lambda: _q_agg(data)) == {"mesh_whole": 1}


def test_mesh_whole_decision_is_on_the_report_and_the_span(tiers, data):
    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    df = _q_join_agg(data)
    report = df.query_execution.analysis_report()
    assert (report.tier or {}).get("tier") == "mesh-whole", report.tier
    mark = data.tracer.mark()
    df.toArrow()
    programs = [d for d in data.tracer.since(mark)
                if d["name"] == "whole_query.program"]
    assert programs and all(
        (d.get("args") or {}).get("tier") == "mesh-whole"
        for d in programs), programs


@pytest.mark.parametrize("name,q,by", QUERIES,
                         ids=[n for n, _q, _b in QUERIES])
def test_mesh_lint_exact(tiers, data, name, q, by):
    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    data.conf.set("spark.tpu.fusion.enabled", "true")
    df = q(data)
    report = df.query_execution.analysis_report()
    assert report.exact, report.inexact_reasons
    measured = _measured(lambda: q(data))
    assert report.predicted_launches == measured, (
        f"predicted {dict(sorted(report.predicted_launches.items()))} != "
        f"measured {dict(sorted(measured.items()))}\n{report.render()}")


@pytest.mark.parametrize("name,q,by", QUERIES,
                         ids=[n for n, _q, _b in QUERIES])
def test_mesh_lint_fusion_off_fallback(tiers, data, name, q, by):
    """Fusion disabled: the whole tiers cannot fuse the plan into one
    program, so mesh-whole falls back tier-by-tier. The analyzer follows
    the same chooser — ZERO mesh_whole launches predicted AND measured —
    and the fallback plan returns identical rows."""
    import pandas as pd

    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    ref = _rows(q(data), by)
    data.conf.set("spark.tpu.fusion.enabled", "false")
    from spark_tpu.physical.mesh_whole import MeshWholeQueryExec

    df = q(data)
    assert not isinstance(df.query_execution.physical, MeshWholeQueryExec)
    report = df.query_execution.analysis_report()
    measured = _measured(lambda: q(data))
    assert report.predicted_launches.get("mesh_whole", 0) == 0
    assert measured.get("mesh_whole", 0) == 0
    pd.testing.assert_frame_equal(ref, _rows(q(data), by),
                                  check_dtype=False)


def test_mesh_quota_retry_exact(tiers, spark):
    """A skewed key sends nearly every row to one destination shard: the
    psum'd overflow scalar doubles that exchange's quota and the WHOLE
    program re-dispatches — 2 mesh_whole dispatches, predicted exactly."""
    _need_devices(4)
    skew = np.zeros(4000, dtype=np.int64)
    skew[:32] = np.arange(32)
    spark.createDataFrame(pa.table({"sk": skew, "sv": np.arange(4000)})) \
        .createOrReplaceTempView("mw_skew")
    spark.conf.set("spark.tpu.compile.tier", "mesh-whole")

    def q():
        return (spark.sql("select * from mw_skew").repartition(4, "sk")
                .groupBy("sk").count())

    report = q().query_execution.analysis_report()
    assert report.predicted_launches.get("mesh_whole", 0) >= 2, \
        report.predicted_launches
    before = _counters(spark)
    out = dict(zip(*(c.to_pylist()
                     for c in q().toArrow().columns)))
    after = _counters(spark)
    assert out[0] == 4000 - 31 and out[5] == 1
    assert after.get("mesh_whole.quota_retries", 0) \
        > before.get("mesh_whole.quota_retries", 0)
    measured = _measured(q)
    assert report.predicted_launches == measured, (
        report.predicted_launches, measured, report.render())


def test_mesh_join_cap_retry_exact(tiers, spark):
    """An expanding inner join (8 build rows per key) overflows the
    default join output bucket inside the program: the pmax'd `needed`
    bumps the capacity and the whole program re-dispatches."""
    import pandas as pd

    _need_devices(4)
    rng = np.random.default_rng(3)
    spark.createDataFrame(pa.table({
        "fk": rng.integers(0, 8, 3000),
        "fv": rng.integers(0, 50, 3000),
    })).createOrReplaceTempView("mw_fact")
    spark.createDataFrame(pa.table({
        "bk": np.repeat(np.arange(8, dtype=np.int64), 8),
        "bl": [f"b{i}" for i in range(64)],
    })).createOrReplaceTempView("mw_dup")

    def q(s):
        return (s.sql("select fk, fv, bl from mw_fact "
                      "join mw_dup on fk = bk")
                .repartition(4, "fk").groupBy("fk").count())

    spark.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(q(spark), ["fk"])
    spark.conf.set("spark.tpu.compile.tier", "mesh-whole")
    def executed():
        """One execution, its rows checked: (launches by kind, the
        session's counters it moved)."""
        before, before_k = _counters(spark), dict(KC.launches_by_kind)
        pd.testing.assert_frame_equal(ref, _rows(q(spark), ["fk"]),
                                      check_dtype=False)
        after = _counters(spark)
        return ({k: v - before_k.get(k, 0)
                 for k, v in KC.launches_by_kind.items()
                 if v != before_k.get(k, 0)},
                {k: v - before.get(k, 0) for k, v in after.items()
                 if v != before.get(k, 0)})

    # a process that has not run the plan: the mirror predicts the ladder
    # and the first execution climbs it
    cold = q(spark).query_execution.analysis_report()
    ladder = cold.predicted_launches.get("mesh_whole", 0)
    assert ladder >= 2, cold.predicted_launches
    kinds, moved = executed()
    assert kinds.get("mesh_whole") == ladder, (kinds, cold.render())
    assert moved.get("mesh_whole.dispatches") == ladder
    # the process remembers the capacities the ladder ended with: a
    # report taken now predicts one launch, the next execution measures it
    warm = q(spark).query_execution.analysis_report()
    assert warm.predicted_launches == {"mesh_whole": 1}, warm.render()
    kinds, moved = executed()
    assert kinds == warm.predicted_launches
    assert moved.get("cache.capacity_remembered") == 1


# ---------------------------------------------------------------------------
# admission + obs contract
# ---------------------------------------------------------------------------

def test_mesh_admission_fallbacks(tiers, data):
    """Inadmissible shapes fall back to the whole tier with the reason on
    the decision: non-power-of-two partition counts and plans without a
    hash exchange never reach the mesh builder."""
    from spark_tpu.physical.mesh_whole import MeshWholeQueryExec
    from spark_tpu.physical.whole_query import WholeQueryExec

    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    # 3 partitions: not a power of two
    p = (data.sql("select * from mw_t").repartition(3, "k")
         .groupBy("k").count()).query_execution.physical
    assert isinstance(p, WholeQueryExec) \
        and not isinstance(p, MeshWholeQueryExec)
    assert "mesh-whole fallback" in p.decision.reason, p.decision.reason
    # single-partition collapse: no hash exchange anywhere in the plan
    p = data.sql("select k, count(*) c from mw_t group by k") \
        .query_execution.physical
    assert isinstance(p, WholeQueryExec) \
        and not isinstance(p, MeshWholeQueryExec)
    assert "mesh-whole fallback" in p.decision.reason, p.decision.reason


def test_mesh_attribution_matches_global(tiers, data):
    """obs contract: the single sharded dispatch attributes to
    MeshWholeQueryExec (re-attributed to members via fused_members) and
    the attributed total equals the global launch counter delta."""
    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    _q_agg(data).toArrow()  # warm
    before = KC.launches
    df = _q_agg(data)
    df.toArrow()
    global_delta = KC.launches - before
    graph = df.query_execution.plan_graph()
    attributed = sum(v for nd in graph
                     for v in (nd.get("launches") or {}).values())
    assert attributed == global_delta
    assert global_delta == 1
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    assert GLOBAL_LEDGER.verify() == [], \
        "device ledger unbalanced after mesh whole-query runs"


# ---------------------------------------------------------------------------
# warm-start manifest: quota seeds, dense span seeds, drift guard
# ---------------------------------------------------------------------------

def _session(name, tmp_path):
    from spark_tpu import TpuSession

    return TpuSession(name, {
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.compile.tier": "mesh-whole",
        "spark.tpu.cache.dir": str(tmp_path),
        # the manifest tests measure real dispatches on the second run;
        # a result-cache hit would answer with zero launches
        "spark.tpu.cache.result.enabled": "false",
    })


def _seed_skew(s):
    skew = np.zeros(4000, dtype=np.int64)
    skew[:32] = np.arange(32)
    s.createDataFrame(pa.table({"sk": skew, "sv": np.arange(4000)})) \
        .createOrReplaceTempView("pm_skew")
    return lambda: (s.sql("select * from pm_skew").repartition(4, "sk")
                    .groupBy("sk").count())


def test_warm_manifest_collapses_quota_retries(tiers, tmp_path):
    """Run 1 learns the doubled quota (2 dispatches) and records it in
    the manifest; a fresh restart seeds it and dispatches ONCE — and the
    analyzer, reading the same manifest, predicts both runs exactly."""
    _need_devices(4)
    s = _session("mw-manifest", tmp_path)
    try:
        q = _seed_skew(s)
        r1 = q()
        assert r1.query_execution.analysis_report() \
                 .predicted_launches == {"mesh_whole": 2}
        first = r1.toArrow()
    finally:
        s.stop()
    s = _session("mw-manifest2", tmp_path)
    try:
        q = _seed_skew(s)
        report = q().query_execution.analysis_report()
        assert report.predicted_launches == {"mesh_whole": 1}, \
            report.render()
        before = _counters(s)
        again = q().toArrow()
        after = _counters(s)
        assert sorted(zip(*(c.to_pylist() for c in again.columns))) \
            == sorted(zip(*(c.to_pylist() for c in first.columns)))
        assert after.get("cache.mesh_quota_seeded", 0) \
            > before.get("cache.mesh_quota_seeded", 0)
        assert _measured(q) == {"mesh_whole": 1}
    finally:
        s.stop()


def _seed_join(s):
    rng = np.random.default_rng(11)
    n = 5000
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 13, n),
        "v": rng.integers(-50, 100, n),
    })).createOrReplaceTempView("pm_t")
    s.createDataFrame(pa.table({
        "dk": np.arange(13, dtype=np.int64),
        "label": [f"lab{i % 3}" for i in range(13)],
    })).createOrReplaceTempView("pm_dim")
    return lambda: (s.sql("select pm_t.k k, v, label from pm_t "
                          "join pm_dim on k = dk where v > 10")
                    .repartition(4, "k").groupBy("label").count())


def test_warm_manifest_dense_probe(tiers, tmp_path):
    """Run 1 observes the build-side key span (dense + unique) through
    the sorted probe; run 2 compiles the dense direct-address probe
    INSIDE the mesh program from the span seed — same results, one
    dispatch, predicted exactly."""
    _need_devices(4)
    s = _session("mw-dense", tmp_path)
    try:
        q = _seed_join(s)
        first = q().toArrow()
        before = _counters(s)
        report = q().query_execution.analysis_report()
        assert report.predicted_launches == {"mesh_whole": 1}
        again = q().toArrow()
        after = _counters(s)
        assert after.get("join.dense_fast_path", 0) \
            > before.get("join.dense_fast_path", 0), \
            "span seed never compiled the dense probe"
        assert after.get("whole_query.dense_probe", 0) \
            > before.get("whole_query.dense_probe", 0)
        assert sorted(zip(*(c.to_pylist() for c in again.columns))) \
            == sorted(zip(*(c.to_pylist() for c in first.columns)))
    finally:
        s.stop()


def test_dense_guard_catches_span_drift(tiers, tmp_path):
    """A manifest span that no longer covers the build keys (data drift
    stand-in: a doctored record) makes the in-program guard fire: the
    round is discarded, dense is disabled for the join, and the retry
    returns the correct result — one extra dispatch, predicted exactly
    by the analyzer reading the SAME lying manifest."""
    import spark_tpu.exec.persist_cache as pc

    _need_devices(4)
    s = _session("mw-drift", tmp_path)
    try:
        q = _seed_join(s)
        oracle = sorted(zip(*(c.to_pylist()
                              for c in q().toArrow().columns)))
        s.stop()
        s = _session("mw-drift2", tmp_path)
        q = _seed_join(s)
        fp = q().query_execution.plan_fingerprint()["fingerprint"]
        rec = pc.manifest_seed(s.conf, fp)
        assert rec and rec.get("join_spans"), \
            "run 1 never recorded a span — dense seeding is dead"
        lying = dict(rec)
        lying["join_spans"] = [[2, 6, 1]] \
            + list(rec["join_spans"][1:])
        pc._manifest(s.conf).append(lying)
        report = q().query_execution.analysis_report()
        assert report.predicted_launches == {"mesh_whole": 2}, \
            report.render()
        before = _counters(s)
        before_k = dict(KC.launches_by_kind)
        got = sorted(zip(*(c.to_pylist()
                           for c in q().toArrow().columns)))
        after = _counters(s)
        delta = {k: v - before_k.get(k, 0)
                 for k, v in KC.launches_by_kind.items()
                 if v != before_k.get(k, 0)}
        assert got == oracle
        assert delta == {"mesh_whole": 2}, delta
        assert after.get("whole_query.dense_guard_retries", 0) \
            > before.get("whole_query.dense_guard_retries", 0)
        # the guarded run re-records the HONEST observed span at close:
        # the manifest self-heals, so the next run (and the analyzer
        # reading the healed record) is back to one dense dispatch
        report = q().query_execution.analysis_report()
        assert report.predicted_launches == {"mesh_whole": 1}, \
            report.render()
        assert _measured(q) == {"mesh_whole": 1}
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# chaos: gang retry reuses the undonated base planes
# ---------------------------------------------------------------------------

def test_mesh_gang_retry_reuses_base_planes(tiers, spark):
    """A runtime fault on the retry round's dispatch (after the base
    planes staged) gang-retries the WHOLE program as a unit: the rebuilt
    program proves the undonated base planes resident and reuses them —
    no host restage — and the device ledger stays balanced. Faulted
    dispatches never count, so the launch prediction still holds."""
    _need_devices(4)
    skew = np.zeros(4000, dtype=np.int64)
    skew[:32] = np.arange(32)
    spark.createDataFrame(pa.table({"gk": skew, "gv": np.arange(4000)})) \
        .createOrReplaceTempView("mw_gang")
    spark.conf.set("spark.tpu.compile.tier", "mesh-whole")

    def q():
        return (spark.sql("select * from mw_gang").repartition(4, "gk")
                .groupBy("gk").count())

    q().toArrow()  # warm both retry-round programs, healthy
    spark.conf.set("spark.tpu.faults.enabled", "true")
    spark.conf.set("spark.tpu.faults.points",
                   "kernel.dispatch=nth:2@mesh_whole")
    faults.configure(spark.conf)
    before = _counters(spark)
    out = dict(zip(*(c.to_pylist() for c in q().toArrow().columns)))
    after = _counters(spark)
    spark.conf.set("spark.tpu.faults.enabled", "false")
    spark.conf.unset("spark.tpu.faults.points")
    faults.configure(spark.conf)
    assert out[0] == 4000 - 31
    assert after.get("whole_query.mesh_gang_retries", 0) \
        - before.get("whole_query.mesh_gang_retries", 0) == 1
    assert after.get("whole_query.mesh_gang_base_reused", 0) \
        > before.get("whole_query.mesh_gang_base_reused", 0), \
        "gang retry restaged from host instead of reusing base planes"
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    assert GLOBAL_LEDGER.verify() == [], \
        "device ledger unbalanced after the gang retry"


def test_deferred_column_is_gathered_before_the_exchange(tiers, data,
                                                         monkeypatch):
    """A join hands its build side on as row numbers; those number the
    rows of the shard's own source, so a deferred column that meets an
    in-program all_to_all is gathered before it and crosses as values,
    same rows as the stage tier's."""
    import pandas as pd

    from spark_tpu.physical import mesh_whole as MW
    from spark_tpu.physical.whole_query import _Late

    _need_devices(4)
    gathered = []
    take = MW._late_take

    def spy(datas, valids, cols):
        gathered.extend(i for i in cols if isinstance(datas[i], _Late))
        return take(datas, valids, cols)

    monkeypatch.setattr(MW, "_late_take", spy)

    def q(s):   # a filter no other test uses: the program is traced here
        return (s.sql("select mw_t.k k, v, label from mw_t "
                      "join mw_dim on k = dk where v > 17")
                .repartition(4, "k").groupBy("label").count())

    data.conf.set("spark.tpu.compile.tier", "stage")
    ref = _rows(q(data), ["label"])
    data.conf.set("spark.tpu.compile.tier", "mesh-whole")
    before = _counters(data).get("join.build_deferred", 0)
    pd.testing.assert_frame_equal(ref, _rows(q(data), ["label"]),
                                  check_dtype=False)
    assert isinstance(q(data).query_execution.physical,
                      MW.MeshWholeQueryExec)
    assert _counters(data).get("join.build_deferred", 0) > before
    assert gathered, "no deferred column reached the exchange"


# ---------------------------------------------------------------------------
# per-stage carry-over: dict-encoded keys fuse into the stage collective
# ---------------------------------------------------------------------------

def test_stage_mesh_fused_string_keys(tiers, data):
    """PR 9 encoding carry-over on the per-stage mesh path: a fused
    filter+shuffle with a dict-encoded partition key ships padded
    codes→value-hash luts as replicated aux planes and hashes inside the
    shard_map — the pipeline no longer materializes before the
    collective, and the launch prediction stays exact."""
    import pandas as pd

    _need_devices(4)
    data.conf.set("spark.tpu.compile.tier", "stage")

    def q():
        return (data.sql("select k, v, s from mw_t where v > 10")
                .repartition(4, "s"))

    ref = _rows(q(), ["k", "v", "s"])
    fused_keys = [k for k in KC._cache
                  if k and k[0] == "mesh_stage" and k[1] == "f"
                  and isinstance(k[-3], tuple) and len(k[-3]) > 0]
    assert fused_keys, \
        "string-key exchange never compiled the fused mesh program"
    data.conf.set("spark.tpu.fusion.enabled", "false")
    pd.testing.assert_frame_equal(ref, _rows(q(), ["k", "v", "s"]),
                                  check_dtype=False)
    data.conf.unset("spark.tpu.fusion.enabled")
    report = q().query_execution.analysis_report()
    measured = _measured(q)
    assert report.predicted_launches == measured, (
        report.predicted_launches, measured, report.render())
