"""Chaos suite: deterministic fault injection end to end.

Every scenario drives a REAL failure mode through the regular conf
surface (spark.tpu.faults.*, utils/faults.py) and asserts the hardening
the fault proves out: bounded RPC/fetch retry absorbing transient flaps
with ZERO stage regenerations, FetchFailed regeneration still producing
correct results, worker death mid-task retried on surviving executors,
window-based executor exclusion with timed re-inclusion
(excludeOnFailure), heartbeat blackout flagged as a straggler and
rescued by speculation, whole-tier runtime faults degrading to the
stage tier with identical results, mesh gang failures retrying then
falling back to the host shuffle, and failed queries releasing their
shuffle state.

Chaos assertions are measured (KernelCache deltas, metrics counters,
result equality against a healthy oracle) — never plan predictions:
healthy-path launch behavior is UNCHANGED and tests/test_plan_analysis
keeps asserting exact counts with the fault layer present but idle.
"""

import pickle
import time

import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.api.functions as F
from spark_tpu import TpuSession
from spark_tpu.config import SQLConf
from spark_tpu.exec.cluster import LocalCluster
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.utils import faults


# ---------------------------------------------------------------------------
# helpers / fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    faults.reset()


def _set_faults(session, points: str, seed: int = 7) -> None:
    session.conf.set("spark.tpu.faults.enabled", "true")
    session.conf.set("spark.tpu.faults.seed", str(seed))
    session.conf.set("spark.tpu.faults.points", points)
    faults.configure(session.conf)


def _clear_faults(session) -> None:
    session.conf.set("spark.tpu.faults.enabled", "false")
    session.conf.unset("spark.tpu.faults.points")
    faults.configure(session.conf)


def _counters(session) -> dict:
    return dict(session._metrics.snapshot()["counters"])


def _delta(after: dict, before: dict, key: str) -> int:
    return after.get(key, 0) - before.get(key, 0)


def _expected_sums(keys, vals) -> dict:
    exp: dict = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        exp[k] = exp.get(k, 0) + v
    return exp


def _assert_sums(df, exp: dict) -> None:
    got = {r["k"]: r["s"] for r in df.collect()}
    assert set(got) == set(exp)
    for k in exp:
        assert abs(got[k] - exp[k]) < 1e-6, (k, got[k], exp[k])


@pytest.fixture(scope="module")
def chaos_spark():
    s = TpuSession("chaos", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.heartbeat.interval": "0.2",
    })
    rng = np.random.default_rng(11)
    n = 6000
    keys = rng.integers(0, 32, n)
    vals = rng.integers(-50, 100, n)
    s.createDataFrame(pa.table({"k": keys, "v": vals})) \
        .createOrReplaceTempView("chaos_t")
    s._chaos_exp = _expected_sums(keys, vals)
    s._chaos_rows = sorted(zip(keys.tolist(), vals.tolist()))
    yield s
    s.stop()


def _agg_df(s):
    return (s.table("chaos_t").repartition(2)
            .groupBy("k").agg(F.sum("v").alias("s")))


def _shuffle_df(s):
    # EXACTLY one exchange → two stages: a remote map stage (shuffle
    # write, no fetches) and a driver-side result stage whose Fetch
    # leaf pulls the blocks — block.fetch rules fire in the DRIVER
    # process only, keeping fetch-path scenarios deterministic
    return s.table("chaos_t").repartition(2)


def _assert_rows(df, s) -> None:
    got = sorted((r["k"], r["v"]) for r in df.collect())
    assert got == s._chaos_rows


# ---------------------------------------------------------------------------
# registry unit behavior
# ---------------------------------------------------------------------------

def test_fault_rules_unit():
    conf = SQLConf({
        "spark.tpu.faults.enabled": "true",
        "spark.tpu.faults.seed": "13",
        "spark.tpu.faults.points":
            "a.nth=nth:2;b.first=first:2;c.after=after:2;"
            "d.prob=prob:0.5;e.scoped=always@nowhere;f.sleep=always:sleep:0",
    })
    faults.configure(conf)
    assert faults.ENABLED

    def fires(point, n, detail=""):
        out = []
        for _ in range(n):
            try:
                faults.maybe_fail(point, detail=detail)
                out.append(False)
            except faults.InjectedFault:
                out.append(True)
        return out

    assert fires("a.nth", 4) == [False, True, False, False]
    assert fires("b.first", 4) == [True, True, False, False]
    assert fires("c.after", 5) == [False, False, True, True, True]
    # scope neither matches the driver host label nor the detail
    assert fires("e.scoped", 3) == [False, False, False]
    assert fires("e.scoped", 1, detail="x/nowhere/y") == [True]
    # seeded prob: identical schedule on reinstall with the same seed
    sched1 = fires("d.prob", 16)
    faults.reset()
    faults.configure(conf)
    assert fires("d.prob", 16) == sched1
    assert any(sched1) and not all(sched1)
    # sleep action returns instead of raising
    faults.maybe_fail("f.sleep")
    # disabled registry short-circuits
    faults.reset()
    faults.maybe_fail("a.nth")


def test_rpc_call_retry_absorbs_flap():
    """Transient UNAVAILABLE on an idempotent control-plane call is
    absorbed by RpcClient's bounded backoff; without a policy the same
    flap surfaces immediately."""
    from spark_tpu.net.transport import (
        RETRY_STATS, RetryPolicy, RpcClient, RpcServer,
        RpcUnavailableError,
    )

    server = RpcServer("tok")
    server.register("echo", lambda p: p)
    addr = server.start()
    try:
        c = RpcClient(addr, "tok")
        conf = SQLConf({"spark.tpu.faults.enabled": "true",
                        "spark.tpu.faults.points": "rpc.call=first:1"})
        faults.configure(conf)
        with pytest.raises(RpcUnavailableError):
            c.call("echo", b"x")          # no policy → flap surfaces
        faults.reset()
        faults.configure(conf)            # fresh first:1
        before = RETRY_STATS["absorbed"]
        out = c.call("echo", b"y",
                     retry=RetryPolicy(attempts=3, base_ms=1.0,
                                       deadline_s=5.0))
        assert out == b"y"
        assert RETRY_STATS["absorbed"] > before
        c.close()
    finally:
        server.stop()


def test_fault_layer_idle_zero_overhead(chaos_spark):
    """Fault layer compiled in but IDLE (enabled with a never-hit
    point): identical measured kernel-launch count as the healthy run —
    the acceptance guard that healthy-path launch behavior is
    unchanged."""
    s = chaos_spark
    _agg_df(s).toArrow()                      # warm
    before = KC.launches
    _agg_df(s).toArrow()
    healthy = KC.launches - before
    _set_faults(s, "never.hit=always")
    before = KC.launches
    _agg_df(s).toArrow()
    idle = KC.launches - before
    _clear_faults(s)
    assert idle == healthy, (idle, healthy)


# ---------------------------------------------------------------------------
# fetch retry / FetchFailed regeneration / regen cap
# ---------------------------------------------------------------------------

def test_rpc_flap_absorbed_by_fetch_retry_zero_regens(chaos_spark):
    """A transient block-fetch flap is absorbed by the bounded fetch
    retry: the query completes correctly with ZERO stage
    regenerations (no FetchFailed ever reaches the scheduler)."""
    s = chaos_spark
    _set_faults(s, "block.fetch=first:2")
    before = _counters(s)
    _assert_rows(_shuffle_df(s), s)
    after = _counters(s)
    fired = faults.fire_counts().get("block.fetch")
    _clear_faults(s)
    assert _delta(after, before, "scheduler.fetch_failures") == 0
    assert _delta(after, before, "scheduler.stage_retries") == 0
    assert _delta(after, before, "shuffle.fetch_retries") >= 1
    assert fired == 2


def test_fetch_exhaustion_regenerates_stage_correctly(chaos_spark):
    """With the fetch retry budget at zero, a lost block surfaces as
    FetchFailed and the scheduler regenerates the map stage from
    lineage — the result is still correct."""
    s = chaos_spark
    s.conf.set("spark.tpu.shuffle.fetch.maxRetries", "0")
    _set_faults(s, "block.fetch=first:1")
    before = _counters(s)
    try:
        _assert_rows(_shuffle_df(s), s)
    finally:
        s.conf.unset("spark.tpu.shuffle.fetch.maxRetries")
        _clear_faults(s)
        s._sql_cluster.health.reset()   # the regen counted a failure
    after = _counters(s)
    assert _delta(after, before, "scheduler.fetch_failures") >= 1


def test_stage_regen_cap_is_classified_and_state_freed(chaos_spark):
    """An executor set that keeps losing map outputs terminates in the
    CLASSIFIED StageRegenerationLimitError (never an infinite
    FetchFailed loop), and the failed query leaves zero shuffle blocks
    on any worker and a balanced device ledger."""
    from spark_tpu.errors import StageRegenerationLimitError
    from spark_tpu.net.transport import RpcClient
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    s = chaos_spark
    s.conf.set("spark.tpu.shuffle.fetch.maxRetries", "0")
    s.conf.set("spark.tpu.scheduler.maxStageRegens", "1")
    # this test targets the regen CAP — keep exclusion out of the way
    # (each regen legitimately counts a failure against the producer)
    s.conf.set("spark.tpu.excludeOnFailure.maxFailures", "100")
    _set_faults(s, "block.fetch=first:100")
    try:
        with pytest.raises(StageRegenerationLimitError) as ei:
            _shuffle_df(s).toArrow()
        assert ei.value.error_class == "STAGE_REGENERATION_LIMIT"
    finally:
        s.conf.unset("spark.tpu.shuffle.fetch.maxRetries")
        s.conf.unset("spark.tpu.scheduler.maxStageRegens")
        s.conf.unset("spark.tpu.excludeOnFailure.maxFailures")
        _clear_faults(s)
        # the repeated FetchFaileds legitimately counted against the
        # producing executors — reset so later tests start clean
        s._sql_cluster.health.reset()
    cluster = s._sql_cluster
    for w in cluster.alive_workers():
        with RpcClient(w.client.addr, cluster.authkey_hex) as c:
            stats = pickle.loads(c.call("block_stats", timeout=10))
        assert stats["blocks"] == 0, \
            f"{w.executor_id} leaked {stats['blocks']} blocks"
    assert GLOBAL_LEDGER.verify() == []
    # the cluster is still healthy for the next query
    _assert_rows(_shuffle_df(s), s)


# ---------------------------------------------------------------------------
# worker death / transient task failures / exclusion
# ---------------------------------------------------------------------------

def test_worker_kill_mid_map_retries_on_survivors():
    """A worker process hard-dying mid-task (kill action) is detected
    as executor loss; the task retries on a survivor, the query is
    correct, and the failure is recorded against the dead executor."""
    s = TpuSession("chaos_kill", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    cluster = LocalCluster(num_workers=2)
    s.attachSqlCluster(cluster)
    try:
        cluster.add_worker("chaoshost")
        rng = np.random.default_rng(3)
        keys = rng.integers(0, 16, 3000)
        vals = rng.integers(0, 50, 3000)
        s.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("kill_t")
        exp = _expected_sums(keys, vals)
        _set_faults(s, "worker.task=always:kill@chaoshost")
        for _ in range(6):   # round-robin eventually offers chaoshost
            df = (s.table("kill_t").repartition(2)
                  .groupBy("k").agg(F.sum("v").alias("s")))
            _assert_sums(df, exp)
            if cluster.stats.get("executor_losses", 0) >= 1:
                break
        assert cluster.stats.get("executor_losses", 0) >= 1, \
            "chaoshost never received (and died on) a task"
        assert cluster.num_alive() == 2   # survivors only
        _clear_faults(s)
    finally:
        s.stop()


def test_flaky_executor_excluded_then_reincluded():
    """excludeOnFailure end to end: an alive-but-flaky executor that
    keeps failing tasks transiently is retried around (queries stay
    correct), accumulates failures in the window, gets EXCLUDED from
    scheduling, surfaces in live status + findings, and rejoins after
    the timed re-inclusion horizon."""
    s = TpuSession("chaos_flaky", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.excludeOnFailure.maxFailures": "2",
        "spark.tpu.excludeOnFailure.windowSecs": "60",
        "spark.tpu.excludeOnFailure.timeoutSecs": "1.0",
    })
    cluster = LocalCluster(num_workers=2)
    s.attachSqlCluster(cluster)
    try:
        cluster.add_worker("flakyhost")
        flaky_eid = next(w.executor_id
                         for w in cluster._workers.values()
                         if w.host == "flakyhost")
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 16, 2000)
        vals = rng.integers(0, 50, 2000)
        s.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("flaky_t")
        exp = _expected_sums(keys, vals)
        qids = []
        s.listener_bus.register(lambda ev: qids.append(ev.query_id))
        _set_faults(s, "worker.task=always@flakyhost")
        excluded_at = None
        for _ in range(10):
            df = (s.table("flaky_t").repartition(2)
                  .groupBy("k").agg(F.sum("v").alias("s")))
            _assert_sums(df, exp)   # transient failures retried around
            if flaky_eid in cluster.health.excluded():
                excluded_at = time.time()
                break
        assert excluded_at is not None, \
            f"flaky executor never excluded " \
            f"(failures={cluster.health.failure_count(flaky_eid)})"
        assert cluster.health.failure_count(flaky_eid) >= 2
        # excluded from scheduling NOW
        assert flaky_eid not in [e.executor_id
                                 for e in cluster.registry.alive()]
        # surfaced: live executor row + a query finding
        util = s.live_obs.executor_utilization()
        assert util.get(flaky_eid, {}).get("excluded") is True
        s.listener_bus.wait_empty()
        found = [f for q in qids
                 for f in (s.live_obs.query_progress(q)
                           or {"findings": []})["findings"]
                 if f.get("kind") == "exec.excluded"]
        assert found, "no exec.excluded finding surfaced"
        _clear_faults(s)
        # timed re-inclusion: past the horizon the executor is offered
        # tasks again
        deadline = excluded_at + 1.0
        time.sleep(max(0.0, deadline - time.time()) + 0.3)
        assert flaky_eid in [e.executor_id
                             for e in cluster.registry.alive()]
        _assert_sums(s.table("flaky_t").repartition(2)
                     .groupBy("k").agg(F.sum("v").alias("s")), exp)
    finally:
        s.stop()


def test_shuffle_write_fault_is_transient_task_failure(chaos_spark):
    """An injected shuffle-write failure fails the map task; the driver
    classifies it TRANSIENT (marker), retries on another executor, and
    the query completes correctly."""
    s = chaos_spark
    cluster = s._sql_cluster
    before_t = cluster.stats.get("transient_task_failures", 0)
    _set_faults(s, "shuffle.write=once")
    try:
        _assert_rows(_shuffle_df(s), s)
    finally:
        _clear_faults(s)
        cluster.health.reset()
    assert cluster.stats.get("transient_task_failures", 0) > before_t


def test_worker_task_failover_keeps_attribution_exact(chaos_spark):
    """A map task that fails once and runs again on another executor is
    still accounted once: the launches attributed to operators equal
    the driver's KernelCache delta plus what the workers shipped."""
    s = chaos_spark
    cluster = s._sql_cluster
    _assert_rows(_shuffle_df(s), s)          # warm both workers
    before_t = cluster.stats.get("transient_task_failures", 0)
    _set_faults(s, "worker.task=once")
    before = KC.launches
    try:
        df = _shuffle_df(s)
        _assert_rows(df, s)
        driver_delta = KC.launches - before
    finally:
        _clear_faults(s)
        cluster.health.reset()
    assert cluster.stats.get("transient_task_failures", 0) > before_t
    worker = sum((df.query_execution._last_ctx.worker_kernel_kinds
                  or {}).values())
    attributed = sum(v for nd in df.query_execution.plan_graph()
                     for v in (nd.get("launches") or {}).values())
    assert worker > 0 and attributed == driver_delta + worker, (
        attributed, driver_delta, worker)


# ---------------------------------------------------------------------------
# heartbeat: telemetry error counting, blackout → straggler + speculation
# ---------------------------------------------------------------------------

def test_heartbeat_telemetry_errors_counted(chaos_spark):
    """A throwing heartbeat sink must never fail a liveness beat — but
    every swallowed exception is COUNTED (cluster stats + the sink
    owner's telemetry_errors) instead of disappearing into a bare
    except."""
    s = chaos_spark
    cluster = s._sql_cluster

    class Boom:
        telemetry_errors = 0

        def sink(self, *a, **k):
            raise RuntimeError("sink bug")

    boom = Boom()
    saved = cluster.obs_sink
    cluster.obs_sink = boom.sink
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline and \
                cluster.stats.get("heartbeat.telemetry_errors", 0) == 0:
            time.sleep(0.1)
    finally:
        cluster.obs_sink = saved
    assert cluster.stats.get("heartbeat.telemetry_errors", 0) >= 1
    assert boom.telemetry_errors >= 1
    # the workers are still registered: the beat returned ok
    assert cluster.num_alive() >= 2


def test_heartbeat_blackout_straggler_and_speculation_win():
    """Heartbeat blackout mid-task: the driver flags the silent task as
    a straggler (silence deadline), the speculation signal launches a
    backup on the healthy executor, and the backup's result wins while
    the stalled primary is still asleep."""
    s = TpuSession("chaos_hb", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
        "spark.speculation": "true",
        "spark.tpu.straggler.minSeconds": "0.1",
        "spark.tpu.straggler.heartbeatDeadline": "0.35",
    })
    cluster = LocalCluster(num_workers=1, heartbeat_interval=0.1)
    s.attachSqlCluster(cluster)
    try:
        cluster.add_worker("slowhost")
        rng = np.random.default_rng(9)
        keys = rng.integers(0, 8, 2000)
        vals = rng.integers(0, 40, 2000)
        s.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("hb_t")
        exp = _expected_sums(keys, vals)
        qids = []
        s.listener_bus.register(lambda ev: qids.append(ev.query_id))
        # slowhost: task stalls 2.5s AND its busy-phase beats black out
        # after the first two (the entry must exist before it can go
        # silent) — the driver sees a live task fall silent mid-stage
        _set_faults(s, "worker.task=always:sleep:2.5@slowhost;"
                       "heartbeat.flush=after:2@busy")
        t0 = time.time()
        straggled = False
        for _ in range(4):   # round-robin until the primary lands slow
            df = (s.table("hb_t").repartition(2)
                  .groupBy("k").agg(F.sum("v").alias("s")))
            _assert_sums(df, exp)
            s.listener_bus.wait_empty()
            straggled = any(
                f.get("kind") == "obs.straggler"
                for q in qids
                for f in (s.live_obs.query_progress(q)
                          or {"findings": []})["findings"])
            if straggled and cluster.stats.get("speculative_wins", 0):
                break
        _clear_faults(s)
        assert straggled, "blackout never produced a straggler finding"
        assert cluster.stats.get("speculative_launched", 0) >= 1
        assert cluster.stats.get("speculative_wins", 0) >= 1, \
            f"speculation never won (stats={cluster.stats}, " \
            f"{time.time() - t0:.1f}s)"
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# runtime tier degradation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def local_spark():
    s = TpuSession("chaos_local", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    rng = np.random.default_rng(21)
    n = 5000
    keys = rng.integers(0, 24, n)
    vals = rng.integers(-30, 80, n)
    s.createDataFrame(pa.table({"k": keys, "v": vals})) \
        .createOrReplaceTempView("deg_t")
    s._chaos_exp = _expected_sums(keys, vals)
    yield s
    s.stop()


def test_whole_tier_dispatch_fault_degrades_to_stage(local_spark):
    """An XLA-runtime-shaped fault at the whole-query program's single
    dispatch degrades the query to the STAGE tier and re-executes with
    identical results; the reason lands on the tier decision. Measured
    KernelCache deltas (not plan predictions) prove the degraded run
    took the stage tier."""
    from spark_tpu.physical.whole_query import WholeQueryExec

    s = local_spark
    s.conf.set("spark.tpu.compile.tier", "whole")

    def q():
        return (s.table("deg_t").repartition(2)
                .groupBy("k").agg(F.sum("v").alias("s")))

    try:
        q().toArrow()                      # warm the whole program
        before_kinds = dict(KC.launches_by_kind)
        _assert_sums(q(), s._chaos_exp)    # healthy whole run
        healthy_kinds = {k: v - before_kinds.get(k, 0)
                         for k, v in KC.launches_by_kind.items()
                         if v != before_kinds.get(k, 0)}
        assert healthy_kinds.get("whole_query", 0) >= 1, healthy_kinds

        _set_faults(s, "kernel.dispatch=once@whole_query")
        before = _counters(s)
        before_kinds = dict(KC.launches_by_kind)
        df = q()
        _assert_sums(df, s._chaos_exp)     # identical results, degraded
        after = _counters(s)
        deg_kinds = {k: v - before_kinds.get(k, 0)
                     for k, v in KC.launches_by_kind.items()
                     if v != before_kinds.get(k, 0)}
        _clear_faults(s)
        assert _delta(after, before, "whole_query.runtime_degraded") == 1
        # the faulted dispatch never counted; the stage tier did the work
        assert deg_kinds.get("whole_query", 0) == 0, deg_kinds
        assert sum(deg_kinds.values()) > 0, deg_kinds
        plan = df.query_execution.physical
        assert isinstance(plan, WholeQueryExec)
        assert "runtime_degraded" in plan.decision.details
        # consumed `once` rule: the next run is whole again
        _assert_sums(q(), s._chaos_exp)
    finally:
        s.conf.unset("spark.tpu.compile.tier")
        _clear_faults(s)


def test_kernel_compile_fault_absorbed_by_stage_retry(local_spark):
    """A one-shot compile-time fault fails the stage attempt; the DAG
    scheduler's deterministic stage retry recompiles and the query
    completes correctly."""
    s = local_spark
    _set_faults(s, "kernel.compile=once")
    before = _counters(s)
    try:
        # a fresh expression structure forces at least one cache miss
        df = (s.table("deg_t")
              .withColumn("w", (F.col("v") * 13 + F.col("k") * 7) % 11)
              .groupBy("k").agg(F.sum("w").alias("s")))
        got = {r["k"]: r["s"] for r in df.collect()}
        fired = faults.fire_counts().get("kernel.compile")
    finally:
        _clear_faults(s)
    after = _counters(s)
    assert fired == 1, "compile fault never fired (no cache miss?)"
    assert _delta(after, before, "scheduler.stage_retries") >= 1
    exp: dict = {}
    for k, v in zip(*(c.to_pylist() for c in
                      s.table("deg_t").toArrow().columns)):
        # engine % is C-style (sign follows the dividend), unlike Python's
        exp[k] = exp.get(k, 0) + int(np.fmod(v * 13 + k * 7, 11))
    assert got == exp


def test_mesh_gang_failure_retries_then_falls_back(local_spark):
    """Mesh gang semantics at runtime: one injected dispatch fault →
    the whole sharded stage retries as a unit and succeeds; repeated
    faults → the exchange degrades to the host shuffle. Results match
    the healthy oracle in both regimes."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    s = local_spark

    def q():
        return (s.table("deg_t").repartition(8, "k")
                .groupBy("k").agg(F.sum("v").alias("s")))

    q().toArrow()                          # warm, healthy
    before = _counters(s)
    _assert_sums(q(), s._chaos_exp)
    after = _counters(s)
    assert _delta(after, before, "exchange.mesh") >= 1, \
        "query did not take the mesh path — test setup is wrong"

    # one gang failure: retry as a unit, still mesh, same results
    _set_faults(s, "kernel.dispatch=once@mesh_stage")
    before = _counters(s)
    _assert_sums(q(), s._chaos_exp)
    after = _counters(s)
    assert _delta(after, before, "exchange.mesh_gang_retries") == 1
    assert _delta(after, before, "exchange.mesh") >= 1
    assert _delta(after, before, "exchange.mesh_runtime_fallback") == 0

    # gang keeps dying: degrade to the host shuffle, same results
    _set_faults(s, "kernel.dispatch=first:2@mesh_stage", seed=8)
    before = _counters(s)
    _assert_sums(q(), s._chaos_exp)
    after = _counters(s)
    _clear_faults(s)
    assert _delta(after, before, "exchange.mesh_runtime_fallback") == 1
    assert _delta(after, before, "exchange.mesh") == 0
