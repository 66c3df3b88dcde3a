"""Query flight recorder (spark_tpu/obs/history.py): plan fingerprints,
persistent run profiles, deterministic perf-regression detection — plus
the PR's satellites (chaos obs salvage, degrade-path attribution).

Contract under test: the recorder is pure close-time host work (zero
kernel launches, fusion on or off), fingerprints are stable across runs
of the same query and sensitive to literals/schemas/tiers, the store
round-trips and stays bounded, and regression findings fire exactly when
a deterministic counter EXCEEDS the stored baseline — never on a warm
re-run of an identical query."""

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.obs.history import (
    ProfileStore, detect_regressions, plan_fingerprint, query_key,
)
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


def _session(name, extra=None):
    from spark_tpu import TpuSession

    conf = {"spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 12,
            "spark.tpu.fusion.minRows": "0"}
    conf.update(extra or {})
    return TpuSession(name, conf)


def _seed_table(s, view="fr_t", n=4000):
    rng = np.random.default_rng(3)
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 9, n),
        "v": rng.integers(-20, 80, n),
    })).createOrReplaceTempView(view)


Q = "select k, sum(v) s from fr_t where v > 0 group by k"


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_stability_and_sensitivity():
    s = _session("fp-test")
    try:
        _seed_table(s)

        def fp(sql):
            return s.sql(sql).query_execution.plan_fingerprint()

        a = fp(Q)
        b = fp(Q)
        assert a["fingerprint"] == b["fingerprint"], \
            "same query twice must fingerprint identically"
        assert a["stages"] and all(st["fingerprint"]
                                   for st in a["stages"]), \
            "per-stage sub-fingerprints missing"
        # literal sensitivity
        c = fp("select k, sum(v) s from fr_t where v > 1 group by k")
        assert c["fingerprint"] != a["fingerprint"]
        # schema sensitivity (different input column type)
        s.createDataFrame(pa.table({
            "k": np.arange(40, dtype=np.int64),
            "v": np.arange(40).astype(np.float64),
        })).createOrReplaceTempView("fr_f")
        d = fp("select k, sum(v) s from fr_f where v > 0 group by k")
        assert d["fingerprint"] != a["fingerprint"]
        # tier sensitivity: the FULL fingerprint flips with the tier
        # (compile-cache key), the structural query key does NOT
        # (regression baselines survive strategy changes)
        qk_a = query_key(s.sql(Q).query_execution.optimized, s.conf)
        s.conf.set("spark.tpu.compile.tier", "operator")
        e = fp(Q)
        qk_e = query_key(s.sql(Q).query_execution.optimized, s.conf)
        s.conf.unset("spark.tpu.compile.tier")
        assert e["fingerprint"] != a["fingerprint"]
        assert qk_e == qk_a, "query key must be tier-insensitive"
    finally:
        s.stop()


def test_fingerprint_capacity_is_part_of_the_key():
    s = _session("fp-cap")
    try:
        _seed_table(s)
        a = s.sql(Q).query_execution.plan_fingerprint()
        s.conf.set("spark.tpu.batch.capacity", 1 << 13)
        b = s.sql(Q).query_execution.plan_fingerprint()
        s.conf.set("spark.tpu.batch.capacity", 1 << 12)
        assert a["fingerprint"] != b["fingerprint"]
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# profile round-trip + store bounds
# ---------------------------------------------------------------------------

def test_profile_roundtrip_through_store(tmp_path):
    s = _session("fr-store", {"spark.tpu.obs.profileDir": str(tmp_path)})
    try:
        _seed_table(s)
        s.sql(Q).toArrow()
        df = s.sql(Q)
        df.toArrow()
        qe = df.query_execution
        assert qe._last_profile is not None
        assert qe._last_regressions == [], \
            "identical warm re-run must not regress"
        store = ProfileStore(str(tmp_path))
        qk = qe._last_profile["query_key"]
        profs = store.profiles(qk)
        assert len(profs) == 2
        assert {p["fingerprint"] for p in profs} == \
            {qe._last_profile["fingerprint"]}
        cold, warm = profs
        assert cold["launches_by_kind"], "profile lost its launch deltas"
        assert warm["launches_by_kind"] == \
            qe._last_profile["launches_by_kind"]
        assert cold["compiles"] > 0 and warm["compiles"] == 0, \
            "cold/warm compile deltas inverted"
        assert warm["ops"] and any(op["rows"] for op in warm["ops"]), \
            "per-operator records missing from the profile"
        assert warm["wall_ms"] > 0 and "execution" in warm["phases"]
        assert warm["hbm"].get("peak", 0) > 0
        assert (warm.get("tier") or {}).get("tier") in (
            "whole", "stage", "operator")
        # reader APIs: one fingerprint, resolvable back to its profiles
        fps = store.fingerprints()
        assert len(fps) == 1
        fp = next(iter(fps))
        assert fps[fp]["profiles"] == 2
        assert len(store.profiles_for_fingerprint(fp)) == 2
    finally:
        s.stop()


def test_store_ring_stays_bounded(tmp_path):
    store = ProfileStore(str(tmp_path), ring=4)
    for i in range(11):
        store.append({"query_key": "qk1", "fingerprint": "fp1",
                      "ts": float(i), "wall_ms": 1.0})
    profs = store.profiles("qk1")
    assert len(profs) <= 8, "ring never compacted"
    assert profs[-1]["ts"] == 10.0, "compaction dropped the newest"
    # newest-N survive: the oldest entries are the ones evicted
    assert min(p["ts"] for p in profs) > 0.0


# ---------------------------------------------------------------------------
# regression detection
# ---------------------------------------------------------------------------

def _prof(kinds=None, compiles=0, counters=None, wall=10.0, hbm=1000):
    return {"launches_by_kind": kinds or {"pipeline": 2, "fused_agg": 1},
            "compiles": compiles, "counters": counters or {},
            "wall_ms": wall, "hbm": {"peak": hbm}}


def test_detect_regressions_unit():
    base = [_prof(compiles=3), _prof()]  # cold then warm
    # identical warm run: silent
    assert detect_regressions(_prof(), base) == []
    # fewer launches (improvement): silent
    assert detect_regressions(
        _prof(kinds={"pipeline": 1, "fused_agg": 1}), base) == []
    # launch increase + new kind: error findings, one per kind
    regs = detect_regressions(
        _prof(kinds={"pipeline": 4, "fused_agg": 1, "gagg": 2}), base)
    assert {f["severity"] for f in regs} == {"error"}
    assert {f["kind"] for f in regs} == {"obs.regression"}
    assert len(regs) == 2
    # retry counter consumed: error
    regs = detect_regressions(
        _prof(counters={"scheduler.stage_retries": 1}), base)
    assert any("stage_retries" in f["metric"] for f in regs)
    assert all(f["severity"] == "error" for f in regs)
    # wall drift: advisory info, never error
    regs = detect_regressions(_prof(wall=100.0), base)
    assert regs and all(f["severity"] == "info" for f in regs)
    # empty history: nothing to compare
    assert detect_regressions(_prof(wall=9e9), []) == []
    # profiles recorded under concurrent load are baseline-eligible
    # (PR 15: deltas are scope-exact per-query ledger values, so there
    # is no contamination to quarantine — even a legacy profile still
    # carrying the retired `overlapped` mark enters the baseline)
    legacy = [dict(_prof(kinds={"pipeline": 99}), overlapped=True)]
    regs = detect_regressions(_prof(kinds={"pipeline": 100}), legacy)
    assert regs and all(f["severity"] == "error" for f in regs)
    assert detect_regressions(_prof(kinds={"pipeline": 99}), legacy) == []


def test_sanitizer_keeps_decimal_literals():
    from spark_tpu.obs.history import _sanitize

    # 13-digit epoch-millis literal is query identity — must survive
    assert "1700000000000" in _sanitize("Filter(ts > lit(1700000000000))")
    # hex ids (uuid fragments) and expr ids are volatile — must not
    s = _sanitize("scan cache-9f86d081884c k#12 ids=(3, 4) at 0x7f01")
    assert "9f86d081884c" not in s and "#12" not in s
    assert "ids=(3, 4)" not in s and "0x7f01" not in s


def test_regression_fires_on_forced_tier_flip(tmp_path):
    s = _session("fr-flip", {"spark.tpu.obs.profileDir": str(tmp_path)})
    try:
        _seed_table(s)
        s.sql(Q).toArrow()
        s.sql(Q).toArrow()
        s.conf.set("spark.tpu.compile.tier", "operator")
        df = s.sql(Q)
        df.toArrow()
        s.conf.unset("spark.tpu.compile.tier")
        regs = df.query_execution._last_regressions
        errors = [f for f in regs if f["severity"] == "error"]
        assert errors, f"tier flip raised no error regression: {regs}"
        assert any("launches" in f["metric"] for f in errors)
        # findings reached the live store (EXPLAIN ANALYZE's source)
        live = s.live_obs.findings_for(
            df.query_execution._last_ctx.query_id)
        assert any(f.get("kind") == "obs.regression" for f in live)
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# obs contract: the recorder adds zero kernel launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fusion", ["true", "false"])
def test_recorder_zero_launch_overhead(tmp_path, fusion):
    s = _session("fr-overhead", {"spark.tpu.fusion.enabled": fusion})
    try:
        _seed_table(s)

        def delta():
            s.sql(Q).toArrow()  # warm
            before = dict(KC.launches_by_kind)
            s.sql(Q).toArrow()
            return {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}

        without = delta()
        s.conf.set("spark.tpu.obs.profileDir", str(tmp_path))
        with_recorder = delta()
        s.conf.unset("spark.tpu.obs.profileDir")
        assert with_recorder == without, (
            f"flight recorder changed kernel dispatches (fusion={fusion}): "
            f"{with_recorder} vs {without}")
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# cluster: merged profile equals the local shape; chaos salvage
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_session(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fr_cluster_profiles")
    s = _session("fr-cluster", {
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.obs.profileDir": str(tmp),
    })
    _seed_table(s)
    yield s, str(tmp)
    s.stop()


def _agg_df(s):
    import spark_tpu.api.functions as F

    return (s.table("fr_t").repartition(2).groupBy("k")
            .agg(F.sum("v").alias("s")))


def test_cluster_profile_merges_worker_obs(cluster_session, tmp_path):
    s, profile_dir = cluster_session
    _agg_df(s).toArrow()
    df = _agg_df(s)
    df.toArrow()
    cluster_prof = df.query_execution._last_profile
    assert cluster_prof is not None and cluster_prof["cluster"] is True
    assert cluster_prof["launches_by_kind"], \
        "cluster profile lost the merged driver+worker launch deltas"
    assert df.query_execution._last_regressions == []
    # same query in a LOCAL session: the merged cluster profile must
    # have the local profile's shape — same structural query key, same
    # record fields, per-operator rows present both sides
    local = _session("fr-local", {
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.obs.profileDir": str(tmp_path)})
    try:
        _seed_table(local)
        ldf = _agg_df(local)
        ldf.toArrow()
        local_prof = ldf.query_execution._last_profile
    finally:
        local.stop()
    assert cluster_prof["query_key"] == local_prof["query_key"], \
        "cluster planning changed the structural query identity"
    assert set(cluster_prof) >= set(local_prof) - {"wasted", "findings"}
    root_rows = {p["ops"][0]["rows"] for p in (cluster_prof, local_prof)
                 if p["ops"]}
    assert len(root_rows) == 1, \
        f"merged per-operator rows diverge from local: {root_rows}"


def test_failed_attempt_obs_salvaged(cluster_session):
    from spark_tpu.utils import faults

    s, profile_dir = cluster_session
    df0 = s.table("fr_t").repartition(2)
    df0.collect()  # warm (and a clean baseline profile)
    s.conf.set("spark.tpu.faults.enabled", "true")
    s.conf.set("spark.tpu.faults.seed", "7")
    s.conf.set("spark.tpu.faults.points", "worker.task=once")
    faults.configure(s.conf)
    try:
        df = s.table("fr_t").repartition(2)
        rows = df.collect()
        assert len(rows) == 4000  # failover produced the right answer
        ctx = df.query_execution._last_ctx
        assert ctx.failed_attempt_obs, \
            "failed attempt's obs was discarded with the error"
        entry = ctx.failed_attempt_obs[0]
        assert entry["executor"] and "INJECTED" in entry["error"].upper() \
            or "worker.task" in entry["error"]
        assert "kernel_kinds" in entry and "spans" in entry
        # the wasted work reached the profile and the live findings
        prof = df.query_execution._last_profile
        assert prof.get("wasted"), "profile lost the wasted-attempt record"
        live = s.live_obs.findings_for(ctx.query_id)
        assert any(f.get("kind") == "obs.wasted-work" for f in live)
        # salvage counter is a deterministic-counter regression signal
        regs = df.query_execution._last_regressions
        assert any("task_failures_salvaged" in str(f.get("metric"))
                   for f in regs)
    finally:
        faults.reset()
        s.conf.set("spark.tpu.faults.enabled", "false")
        s.conf.unset("spark.tpu.faults.points")
        faults.configure(s.conf)
        s._sql_cluster.health.reset()


# ---------------------------------------------------------------------------
# degrade-path attribution (PR 11 follow-on (d))
# ---------------------------------------------------------------------------

def test_degraded_whole_tier_renders_member_attribution(tmp_path):
    import spark_tpu.api.functions as F
    from spark_tpu.utils import faults

    s = _session("fr-degrade", {
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.compile.tier": "whole",
        "spark.tpu.obs.profileDir": str(tmp_path),
    })
    try:
        _seed_table(s)

        def q():
            return (s.table("fr_t").repartition(2).groupBy("k")
                    .agg(F.sum("v").alias("s")))

        healthy = q()
        healthy.toArrow()
        healthy_graph = healthy.query_execution.plan_graph()
        # healthy whole run: single wrapper node owns the dispatch and
        # re-attributes through fused members (no inner child rows)
        wq = [nd for nd in healthy_graph if nd["op"] == "WholeQueryExec"]
        assert wq and wq[0].get("fused"), \
            "healthy whole-tier run lost its fused-member view"
        s.conf.set("spark.tpu.faults.enabled", "true")
        s.conf.set("spark.tpu.faults.points",
                   "kernel.dispatch=once@whole_query")
        faults.configure(s.conf)
        df = q()
        df.toArrow()
        faults.reset()
        graph = df.query_execution.plan_graph()
        inner = [nd for nd in graph
                 if nd["op"] not in ("WholeQueryExec", "AQE")]
        assert inner, "degraded run did not render the inner plan"
        assert any(nd["rows"] for nd in inner), \
            "inner operators carry no measured rows after degrade"
        assert any(nd.get("launches") for nd in inner), \
            "inner operators carry no attributed launches after degrade"
        wq = [nd for nd in graph if nd["op"] == "WholeQueryExec"]
        assert wq and not wq[0].get("fused"), \
            "degraded wrapper still renders fused members (duplication)"
        # the profile records the degrade and the per-member records
        prof = df.query_execution._last_profile
        assert (prof.get("tier") or {}).get("degraded") is True
        assert "runtime_degraded" in str(
            (prof.get("tier") or {}).get("details"))
        assert len(prof["ops"]) > 1, \
            "degraded profile is not comparable to a stage-tier profile"
    finally:
        faults.reset()
        s.stop()
