"""Live telemetry (spark_tpu/obs/live.py + worker_main heartbeat flush).

The contract under test: worker stage tasks stream incremental obs
partials on the executor heartbeat BEFORE any task returns; the driver's
LiveObs merges them monotonically (final task-return record supersedes,
late heartbeats drop); the straggler detector flags slowed tasks in live
status AND EXPLAIN ANALYZE; and the whole layer preserves the obs
invariants — zero extra kernel launches, no mid-query device syncs,
contextvars into every new flush thread."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.obs.live import (
    ConsoleProgressReporter, LiveObs, start_query_flusher,
)
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


def _delta(qid="q1", stage="s.1.1", task=0, seq=1, rows=0, batches=0,
           launches=0, **kw):
    return {"query": qid, "stage": stage, "task": task, "seq": seq,
            "rows": rows, "batches": batches, "launches": launches,
            "compile_ms": 0.0, "kernel_kinds": kw.pop("kernel_kinds", {}),
            "op_records": kw.pop("op_records", {}),
            "spans_closed": kw.pop("spans_closed", []),
            "open_spans": kw.pop("open_spans", []), **kw}


# ---------------------------------------------------------------------------
# merge semantics: monotonic partials, final supersedes, late drops
# ---------------------------------------------------------------------------

def test_partials_merge_monotonically_and_final_supersedes():
    live = LiveObs()
    live.on_heartbeat("exec-a", [_delta(seq=1, rows=10, batches=1)])
    live.on_heartbeat("exec-a", [_delta(seq=3, rows=30, batches=3,
                                        launches=5)])
    # stale/reordered snapshot must not regress the counters
    live.on_heartbeat("exec-a", [_delta(seq=2, rows=20, batches=2)])
    t = live.task_record("q1", "s.1.1", 0)
    assert t["rows"] == 30 and t["batches"] == 3 and t["launches"] == 5
    assert t["partials"] == 2 and not t["done"]
    assert live.partials_seen == 2

    final = {"op_records": {7: {"rows": 44, "rows_exact": True,
                                "batches": 4}},
             "kernel_launches": 6, "kernel_compile_ms": 1.5,
             "kernel_kinds": {"pipeline": 6}}
    live.task_finished("q1", "s.1.1", 0, final)
    t = live.task_record("q1", "s.1.1", 0)
    assert t["done"] and t["rows"] == 44 and t["launches"] == 6
    assert t["kernel_kinds"] == {"pipeline": 6}
    # partials arrived and the final extends them monotonically
    assert t["reconciled"] is True

    # a late heartbeat after completion is DROPPED, not merged
    live.on_heartbeat("exec-a", [_delta(seq=9, rows=999)])
    t = live.task_record("q1", "s.1.1", 0)
    assert t["rows"] == 44 and live.late_dropped == 1


def test_query_progress_rolls_up_stages_and_heartbeat_age():
    live = LiveObs()
    live.on_heartbeat("e1", [_delta(task=0, seq=1, rows=5, batches=1),
                             _delta(task=1, seq=1, rows=7, batches=2)])
    live.task_finished("q1", "s.1.1", 1, None, rows=7)
    p = live.query_progress("q1")
    st = p["stages"]["s.1.1"]
    assert st["tasks_total"] == 2 and st["tasks_done"] == 1
    assert st["rows"] == 12 and st["partials"] == 2
    assert st["tasks"][0]["heartbeat_age_s"] >= 0
    assert st["tasks"][1]["done"]


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------

class _Conf:
    """Minimal conf shim (LiveObs only calls .get(entry))."""

    def __init__(self, **over):
        self.over = over

    def get(self, entry):
        return self.over.get(entry.key, entry.default)


def test_straggler_rate_detection_and_healthy_runs_stay_clean():
    conf = _Conf(**{"spark.tpu.straggler.minSeconds": 0.05,
                    "spark.tpu.straggler.rateFraction": 0.5})
    live = LiveObs(conf=conf)
    # fast peer completes with a healthy rate; slow task makes no progress
    live.on_heartbeat("e1", [_delta(task=0, seq=1, rows=0),
                             _delta(task=1, seq=1, rows=500, batches=4)])
    live.task_finished("q1", "s.1.1", 1, None, rows=500)
    time.sleep(0.1)
    live.on_heartbeat("e1", [_delta(task=0, seq=2, rows=0)])
    active = live.check_stragglers()
    assert [(f["stage"], f["task"]) for f in active] == [("s.1.1", 0)]
    assert all(f["kind"] == "obs.straggler" and f["severity"] == "warning"
               for f in active)
    # findings persist for the query (EXPLAIN ANALYZE reads them later)
    assert live.findings_for("q1")
    assert live.active_stragglers() == [("q1", "s.1.1", 0)]

    # healthy: equal-progress peers never flag
    live2 = LiveObs(conf=conf)
    live2.on_heartbeat("e1", [_delta(qid="q2", task=0, seq=1, rows=100),
                              _delta(qid="q2", task=1, seq=1, rows=110)])
    time.sleep(0.1)
    live2.on_heartbeat("e1", [_delta(qid="q2", task=0, seq=2, rows=200),
                              _delta(qid="q2", task=1, seq=2, rows=210)])
    assert live2.check_stragglers() == []
    assert live2.findings_for("q2") == []


def test_straggler_silence_detection():
    conf = _Conf(**{"spark.tpu.straggler.heartbeatDeadline": 0.05,
                    "spark.tpu.straggler.minSeconds": 10_000})
    live = LiveObs(conf=conf)
    live.on_heartbeat("e1", [_delta(task=0, seq=1, rows=5)])
    time.sleep(0.12)
    active = live.check_stragglers()
    assert active and "silent" in active[0]["msg"]
    # a finished query stops being scanned
    live.query_finished("q1")
    assert live.check_stragglers() == []


def test_fast_task_without_partials_gets_real_duration():
    """A task can finish before its first heartbeat ever reaches the
    driver; without the scheduler-provided start time its duration would
    collapse to ~0 and its completed-peer rate would explode, flagging
    every healthy sibling as a straggler."""
    conf = _Conf(**{"spark.tpu.straggler.minSeconds": 0.05,
                    "spark.tpu.straggler.rateFraction": 0.5})
    live = LiveObs(conf=conf)
    # sibling still running, healthy progress
    live.on_heartbeat("e1", [_delta(task=0, seq=1, rows=90)])
    # peer finishes WITHOUT any partials; the scheduler knows it started
    # 1s ago → rate ~100 rows/s, same ballpark as the running sibling
    live.task_finished("q1", "s.1.1", 1, None, rows=100,
                       started=time.time() - 1.0)
    t = live.task_record("q1", "s.1.1", 1)
    assert t["duration"] >= 0.9          # real duration, not ~0
    time.sleep(0.1)
    live.on_heartbeat("e1", [_delta(task=0, seq=2, rows=110)])
    assert live.check_stragglers() == [] # healthy sibling stays clean


def test_stage_abandoned_drops_failed_attempt_entries():
    """A failed stage attempt retries under a new shuffle id; its live
    entries must not sit open forever tripping the heartbeat-silence
    deadline (a permanently-truthy straggler signal)."""
    conf = _Conf(**{"spark.tpu.straggler.heartbeatDeadline": 0.05,
                    "spark.tpu.straggler.minSeconds": 10_000})
    live = LiveObs(conf=conf)
    live.on_heartbeat("e1", [_delta(stage="run.1.1", task=0, seq=1,
                                    rows=5)])
    live.stage_abandoned("q1", "run.1.1")
    # a heartbeat straggling in AFTER abandonment must not resurrect
    # the entry (nothing would ever close it again)
    live.on_heartbeat("e1", [_delta(stage="run.1.1", task=0, seq=2,
                                    rows=9)])
    # nor may a late final record of the failed attempt
    live.task_finished("q1", "run.1.1", 0, None, rows=9)
    time.sleep(0.12)                     # past the silence deadline
    assert live.check_stragglers() == []
    assert live.active_stragglers() == []
    p = live.query_progress("q1")
    assert p is not None and "run.1.1" not in p["stages"]
    assert live.late_dropped >= 1


def test_speculative_copies_merge_per_executor():
    """Speculation races two copies of one task on the same key, each
    with an independent seq counter: per-executor seq tracking accepts
    both streams (no interleave-drops), the further-along copy owns the
    displayed counters, and reconciliation compares the final record
    against the WINNING copy's own partials."""
    live = LiveObs()
    live.on_heartbeat("e1", [_delta(seq=1, rows=100, batches=2)])
    live.on_heartbeat("e2", [_delta(seq=1, rows=10, batches=1)])
    t = live.task_record("q1", "s.1.1", 0)
    assert t["partials"] == 2            # laggard's stream not dropped
    assert t["rows"] == 100 and t["executor"] == "e1"  # leader displays
    # the laggard catches up past the leader and takes over the display
    live.on_heartbeat("e2", [_delta(seq=2, rows=300, batches=4)])
    t = live.task_record("q1", "s.1.1", 0)
    assert t["rows"] == 300 and t["executor"] == "e2"
    assert t["rows_by"] == {"e1": 100, "e2": 300}
    # e1 wins the race: reconciliation is against e1's OWN partials
    # (100 <= 120), not the displayed 300 from the losing copy
    live.task_finished("q1", "s.1.1", 0, None, rows=120, executor="e1")
    t = live.task_record("q1", "s.1.1", 0)
    assert t["reconciled"] is True and t["executor"] == "e1"


def test_straggler_signal_scoped_to_flagged_task():
    """The live straggler signal is the hook the speculative-execution
    path consumes — polled during the wait for the primary, SCOPED to
    the waiting task's key, so one flagged straggler launches ITS
    backup immediately without collapsing the speculation threshold for
    every other in-flight task."""
    from spark_tpu.exec.cluster import LocalCluster

    c = LocalCluster.__new__(LocalCluster)     # no worker spawn
    c.speculation_interval = None
    c.speculation_multiplier = 1.5
    c._durations = []
    c._lock = threading.Lock()
    c.speculation_signal = None
    assert c._speculation_threshold() is None  # no history, no interval
    assert c._signal_flags(("s.1", 0)) is False

    flagged = [("q1", "s.1", 0)]               # active_stragglers() shape
    c.speculation_signal = (
        lambda key=None: any(key is None or (f[1], f[2]) == key
                             for f in flagged))
    assert c._signal_flags(("s.1", 0)) is True   # this task is flagged
    assert c._signal_flags(("s.1", 1)) is False  # siblings unaffected
    # a KEYLESS task never consumes the signal — 'any straggler
    # anywhere' would double-launch every unrelated task
    assert c._signal_flags(None) is False
    # bare (no-arg) signals keep the legacy any-straggler semantics
    c.speculation_signal = lambda: True
    assert c._signal_flags(("s.9", 3)) is True
    # the duration-history threshold itself no longer consults the
    # signal — the poll inside _run_speculative owns that decision
    assert c._speculation_threshold() is None


# ---------------------------------------------------------------------------
# no-sync guard: partial export never touches a device array
# ---------------------------------------------------------------------------

def test_partial_export_leaves_parked_masks_parked():
    from spark_tpu.obs import metrics as OM

    class Grenade:
        """Parked mask stand-in: ANY array access mid-query is a sync."""

        def __array__(self, *a, **k):
            raise AssertionError("live flush resolved a parked mask")

        @property
        def nbytes(self):
            raise AssertionError("live flush touched a parked mask")

    rec = {}
    ent = rec[1] = OM.new_op_record()
    ent["rows"] = 7
    ent["batches"] = 2
    ent["pending"].append(Grenade())
    snap = OM.export_op_records_partial(rec)
    # host counters ship; the pending mask is untouched and still parked
    assert snap[1]["rows"] == 7 and snap[1]["batches"] == 2
    assert snap[1]["rows_exact"] is False      # lower bound until task end
    assert len(ent["pending"]) == 1
    assert "pending" not in snap[1]


def test_worker_collect_live_obs_is_pure_host(spark):
    """collect_live_obs over a registered recorder launches nothing and
    ships cumulative snapshots with monotonic seq + incremental spans."""
    from spark_tpu.config import SQLConf
    from spark_tpu.exec import worker_main as WM
    from spark_tpu.obs.metrics import new_op_record

    conf = SQLConf({})
    state = WM.begin_stage_obs(conf, query_id="qx", stage_id="st.1.1",
                               task_id=2)
    try:
        assert state is not None
        tracer = state["tracer"]
        state["rec"][5] = new_op_record()
        state["rec"][5]["rows"] = 11
        with tracer.span("op-a", cat="operator"):
            pass
        before = KC.launches
        d1 = WM.collect_live_obs()
        # the heartbeat carrying d1 FAILED: spans must be re-sent, not
        # silently lost from the live stream
        d_retry = WM.collect_live_obs()
        WM.ack_live_obs()                      # this beat reached the driver
        d2 = WM.collect_live_obs()
        assert KC.launches == before
        mine = [d for d in d1 if d["query"] == "qx"]
        assert len(mine) == 1 and mine[0]["task"] == 2
        assert mine[0]["rows"] == 11
        assert any(s["name"] == "op-a" for s in mine[0]["spans_closed"])
        retry = [d for d in d_retry if d["query"] == "qx"][0]
        assert any(s["name"] == "op-a" for s in retry["spans_closed"]), \
            "unacked closed spans dropped from the live stream"
        mine2 = [d for d in d2 if d["query"] == "qx"][0]
        assert mine2["seq"] == mine[0]["seq"] + 2
        assert mine2["spans_closed"] == []     # acked: shipped exactly once
    finally:
        WM.finish_stage_obs(state)
    assert all(d.get("query") != "qx" for d in WM.collect_live_obs()), \
        "finished task still registered for live flushing"


def test_open_spans_visible_while_in_flight(spark):
    from spark_tpu.obs.tracing import Tracer

    t = Tracer(enabled=True)
    with t.span("long-running", cat="operator"):
        open_now = t.open_spans()
        assert any(s["name"] == "long-running" and s["elapsed_ms"] >= 0
                   for s in open_now)
    assert all(s["name"] != "long-running" for s in t.open_spans())


# ---------------------------------------------------------------------------
# flush-thread contextvar propagation (satellite regression)
# ---------------------------------------------------------------------------

def test_flush_thread_carries_query_scope_via_scoped_submit():
    """start_query_flusher hands its loop to the pool through
    scoped_submit: the flush thread sees the caller's query scope and
    publishes under the right qid. A bare pool.submit (negative
    control) starts from an empty context and would publish untagged."""
    from concurrent.futures import ThreadPoolExecutor

    from spark_tpu.exec.context import ExecContext
    from spark_tpu.obs import metrics as OM
    from spark_tpu.obs.tracing import current_query, pop_query, push_query

    live = LiveObs()
    ctx = ExecContext()
    ctx.plan_metrics = {3: OM.new_op_record()}
    ctx.plan_metrics[3]["rows"] = 42
    tok = push_query("q-flush")
    try:
        stop = start_query_flusher(live, ctx, interval=0.02)
        time.sleep(0.1)
        stop()
        with ThreadPoolExecutor(1) as pool:
            bare_qid = pool.submit(current_query).result()
    finally:
        pop_query(tok)
    assert bare_qid is None         # the hazard scoped_submit prevents
    p = live.query_progress("q-flush")
    assert p is not None, "flush thread lost the query scope"
    st = p["stages"]["local"]
    assert st["rows"] == 42 and st["partials"] >= 1


# ---------------------------------------------------------------------------
# zero-launch guard: live telemetry (flusher + console) adds no dispatch
# ---------------------------------------------------------------------------

def test_local_live_telemetry_zero_launch_overhead(spark):
    import io

    rng = np.random.default_rng(5)
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 9, 4000),
        "v": rng.integers(-10, 50, 4000)})) \
        .createOrReplaceTempView("live_t")
    sql = "select k, sum(v) s, count(*) c from live_t where v > 0 group by k"

    def delta():
        spark.sql(sql).toArrow()   # warm
        before = dict(KC.launches_by_kind)
        spark.sql(sql).toArrow()
        after = dict(KC.launches_by_kind)
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}

    baseline = delta()
    # console progress ON routes every query through the live flusher +
    # reporter; pre-install a reporter on a throwaway stream so the test
    # terminal stays clean
    spark._progress_reporter = ConsoleProgressReporter(
        spark.live_obs, stream=io.StringIO(), interval=0.02).start()
    spark.conf.set("spark.tpu.progress.console", "true")
    try:
        with_live = delta()
    finally:
        spark.conf.unset("spark.tpu.progress.console")
        spark._progress_reporter.stop()
        spark._progress_reporter = None
    assert with_live == baseline, (
        f"live telemetry changed dispatches: {with_live} vs {baseline}")


def test_console_reporter_renders_stage_bars():
    import io

    live = LiveObs()
    live.on_heartbeat("e1", [_delta(task=0, seq=1, rows=100, launches=3),
                             _delta(task=1, seq=1, rows=50)])
    live.task_finished("q1", "s.1.1", 1, None, rows=50)
    rep = ConsoleProgressReporter(live, stream=io.StringIO())
    line = rep.render_line()
    assert "1/2 tasks" in line and "rows=150" in line
    assert "launches=3" in line


# ---------------------------------------------------------------------------
# cluster integration: a deliberately slow worker streams partials
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_cluster_spark():
    """2-worker cluster heartbeating every 0.1s — slow stage tasks emit
    several live deltas before returning."""
    from spark_tpu.api.session import TpuSession
    from spark_tpu.exec.cluster import LocalCluster

    s = TpuSession("live-cluster", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    cluster = LocalCluster(num_workers=2, heartbeat_interval=0.1)
    s.attachSqlCluster(cluster)
    rng = np.random.default_rng(17)
    n = 4000
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 8, n),
        "v": rng.integers(-20, 60, n)})) \
        .createOrReplaceTempView("lc_t")
    yield s
    s.stop()


def _slow_df(spark, sleep_s=0.25, slow_key=None):
    """Map stage containing a sleeping UDF: slow_key=None sleeps every
    batch; an int sleeps only in batches containing that key (after the
    hash repartition, exactly the map task holding that key's partition
    stalls)."""
    import spark_tpu.api.functions as F
    from spark_tpu.types import int64

    @F.udf(returnType=int64)
    def crawl(k):
        if slow_key is None or (np.asarray(k) == slow_key).any():
            time.sleep(sleep_s)
        return k * 2

    base = spark.table("lc_t")
    if slow_key is not None:
        base = base.repartition(2, "k")
    return base.withColumn("kk", crawl("k")).repartition(2)


def test_slow_worker_streams_partials_before_any_task_returns(
        live_cluster_spark):
    spark = live_cluster_spark
    live = spark.live_obs
    df = _slow_df(spark, sleep_s=0.3)
    base_partials = live.partials_seen

    seen_running = []
    done = threading.Event()

    def poll():
        while not done.is_set():
            snap = live.snapshot()
            for qid, q in snap["running"].items():
                for stage, st in q["stages"].items():
                    if stage != "local" and st["partials"] > 0 and \
                            st["tasks_done"] < st["tasks_total"]:
                        seen_running.append((qid, stage, dict(st)))
            time.sleep(0.05)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        df.toArrow()
    finally:
        done.set()
        poller.join(5)
    # acceptance: incremental worker deltas were visible on the driver
    # BEFORE the map task returned
    assert seen_running, "no mid-stage heartbeat partial reached the driver"
    assert live.partials_seen > base_partials
    qid, stage, st = seen_running[-1]
    # after completion the final record superseded and reconciled
    p = live.query_progress(qid)
    final_st = p["stages"][stage]
    assert final_st["tasks_done"] == final_st["tasks_total"]
    for t in final_st["tasks"].values():
        assert t["done"] and t["reconciled"] is True
    # healthy run: zero straggler findings
    assert p["findings"] == []


def test_cluster_attribution_intact_with_live_telemetry(live_cluster_spark):
    """Streaming partials must not perturb the ground truth: attributed
    per-operator launches still equal driver + worker measured totals
    (the PR 4 invariant) with heartbeat obs flowing."""
    import spark_tpu.api.functions as F

    spark = live_cluster_spark

    def q():
        return (spark.table("lc_t").repartition(2)
                .groupBy("k").agg(F.sum("v").alias("s")))

    q().toArrow()   # warm worker caches
    before = KC.launches
    df = q()
    df.toArrow()
    driver_delta = KC.launches - before
    ctx = df.query_execution._last_ctx
    worker_kinds = ctx.worker_kernel_kinds or {}
    assert worker_kinds, "workers shipped no kernel deltas"
    graph = df.query_execution.plan_graph()
    attributed = sum(v for nd in graph
                     for v in (nd.get("launches") or {}).values())
    assert attributed == driver_delta + sum(worker_kinds.values())


def test_straggler_flagged_in_live_status_and_explain_analyze(
        live_cluster_spark):
    """Acceptance: an artificially slowed map task (sleeping UDF pinned
    to one hash partition, 2 map tasks racing) is flagged while running
    and the obs.straggler finding surfaces in live status and EXPLAIN
    ANALYZE."""
    spark = live_cluster_spark
    spark.conf.set("spark.tpu.shuffle.mapParallelism", "2")
    spark.conf.set("spark.tpu.straggler.minSeconds", "0.3")
    spark.conf.set("spark.tpu.straggler.rateFraction", "0.5")
    qids = []
    listener = lambda ev: qids.append(ev.query_id)  # noqa: E731
    spark.listener_bus.register(listener)
    try:
        # the stall must dominate the task: completed peers now carry
        # REAL durations (scheduler start time), so the bar is a
        # realistic rate, not the inflated ~0-duration artifact —
        # a marginal slowdown would make this assertion timing-flaky
        df = _slow_df(spark, sleep_s=3.0, slow_key=3)
        report = df.query_execution.analyzed_report()
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(listener)
        spark.conf.unset("spark.tpu.shuffle.mapParallelism")
        spark.conf.unset("spark.tpu.straggler.minSeconds")
        spark.conf.unset("spark.tpu.straggler.rateFraction")
    stragglers = [f for f in report.findings
                  if f.get("kind") == "obs.straggler"]
    assert stragglers, \
        f"no straggler finding in EXPLAIN ANALYZE: {report.findings}"
    # and the same finding lives in the query's live status
    flagged_q = stragglers[0]["query"]
    assert flagged_q in qids
    p = spark.live_obs.query_progress(flagged_q)
    assert p is not None and any(f["kind"] == "obs.straggler"
                                 for f in p["findings"])
    # drift gates stay green: stragglers are warnings, not errors
    assert not report.has_unexplained_drift, report.render()


def test_live_ui_summary_includes_live_snapshot(live_cluster_spark):
    from spark_tpu.exec.ui import LiveStatusStore

    spark = live_cluster_spark
    store = LiveStatusStore("live-ui", live_obs=spark.live_obs)
    spark.listener_bus.register(store)
    try:
        _slow_df(spark, sleep_s=0.05).toArrow()
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(store)
    s = store.summary("live-ui")
    assert "live" in s
    assert s["live"]["partials_seen"] > 0


# ---------------------------------------------------------------------------
# push-merge flow arrows (satellite): merged chunks have a producing span
# ---------------------------------------------------------------------------

def test_push_merge_exchange_edges_flow_through_merge_span():
    from spark_tpu.api.session import TpuSession
    from spark_tpu.exec.cluster import LocalCluster
    from tests.test_observability import _flow_edges

    s = TpuSession("push-flow", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    try:
        cluster = LocalCluster(num_workers=2, push_shuffle=True)
        s.attachSqlCluster(cluster)
        rng = np.random.default_rng(3)
        s.createDataFrame(pa.table({
            "k": rng.integers(0, 5, 3000),
            "v": rng.integers(0, 40, 3000)})) \
            .createOrReplaceTempView("pm_t")
        import spark_tpu.api.functions as F

        (s.table("pm_t").repartition(2)
         .groupBy("k").agg(F.sum("v").alias("sv"))).toArrow()
        merged = s._metrics.snapshot()["counters"].get(
            "shuffle.merged_chunks_fetched", 0)
        assert merged > 0, "query never consumed a push-merged chunk"
        doc = s.tracer.to_chrome_trace()
    finally:
        s.stop()
    evs = doc["traceEvents"]
    complete = [e for e in evs if e.get("ph") == "X"]
    merge_spans = [e for e in complete if e["name"].startswith("merge[")]
    assert merge_spans, "push-merge finalize recorded no producing span"
    assert all((e.get("args") or {}).get("flow_id", "").endswith("#merged")
               for e in merge_spans)
    # every arrow is one start and one finish (`_flow_edges` asserts it)
    # and both land inside a span; at least one lands merge span →
    # reduce-side fetch: the exchange edge no longer stops at the fetch
    edges = _flow_edges(doc)
    assert edges and all(srd is not None and dst is not None for srd, dst in edges)
    assert any(srd["name"].startswith("merge[")
               and dst["name"].startswith("fetch[")
               for srd, dst in edges), \
        "no merge → reduce-fetch flow arrow"
    # and a map task feeds the merge span (map → merge → fetch chain)
    assert any(srd["cat"] == "worker" and dst["name"].startswith("merge[")
               for srd, dst in edges), "no map-task → merge flow arrow"


# ---------------------------------------------------------------------------
# map-side stat restriction (satellite): only plan-reachable candidates
# ---------------------------------------------------------------------------

def test_exchange_stat_cols_restricted_to_dense_candidates(spark):
    rng = np.random.default_rng(9)
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 7, 3000),
        "v": rng.integers(0, 100, 3000),
        "w": rng.integers(0, 100, 3000)})) \
        .createOrReplaceTempView("sc_t")
    from spark_tpu.physical.exchange import ShuffleExchangeExec

    # k is a downstream single-int grouping key → the exchange
    # accumulates stats ONLY for k, not for v/w (historically every
    # integral column paid the per-append host min/max)
    df = (spark.table("sc_t").repartition(3, "k")
          .groupBy("k").count())
    plan = df.query_execution.physical
    ex = [n for n in plan.iter_nodes()
          if isinstance(n, ShuffleExchangeExec)]
    assert ex
    kpos = [i for i, a in enumerate(ex[0].output) if a.name == "k"]
    assert ex[0].stat_cols == kpos, ex[0].stat_cols
    df.toArrow()
    stats = ex[0].last_col_stats
    assert stats and all(set(cols) <= set(kpos)
                         for cols in stats.values()), stats

    # no downstream dense consumer → no stat accumulation at all
    df2 = spark.table("sc_t").repartition(3)
    plan2 = df2.query_execution.physical
    ex2 = [n for n in plan2.iter_nodes()
           if isinstance(n, ShuffleExchangeExec)]
    assert ex2 and ex2[0].stat_cols == []
    df2.toArrow()
    assert all(cols == {} for cols in ex2[0].last_col_stats.values())
