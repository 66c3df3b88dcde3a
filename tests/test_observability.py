"""Observability layer (spark_tpu/obs/): always-on tracing + per-operator
metrics with kernel attribution + EXPLAIN ANALYZE drift detection.

The hard constraint under test: collection adds ZERO kernel launches —
metrics/tracing on (the default) must measure identical KernelCache
launch deltas to metrics/tracing off, fusion on and off."""

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC


@pytest.fixture()
def data(spark):
    rng = np.random.default_rng(23)
    n = 5000
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 11, n),
        "v": rng.integers(-40, 90, n),
    })).createOrReplaceTempView("obs_t")
    dim = pa.table({"dk": np.arange(11, dtype=np.int64),
                    "label": [f"l{i % 3}" for i in range(11)]})
    spark.createDataFrame(dim).createOrReplaceTempView("obs_dim")
    return spark


Q_AGG = "select k, sum(v) sv, count(*) c from obs_t where v > 0 group by k"
Q_JOIN = ("select label, sum(v) sv from obs_t join obs_dim on k = dk "
          "where v > 5 group by label")


def _launch_delta(spark, sql):
    spark.sql(sql).toArrow()  # warm: compiles + caches + memos
    before = dict(KC.launches_by_kind)
    spark.sql(sql).toArrow()
    after = dict(KC.launches_by_kind)
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# ---------------------------------------------------------------------------
# overhead guard: metrics + tracing add ZERO kernel launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fusion", ["true", "false"])
@pytest.mark.parametrize("sql", [Q_AGG, Q_JOIN], ids=["agg", "join+agg"])
def test_metrics_and_tracing_zero_launch_overhead(data, fusion, sql):
    spark = data
    spark.conf.set("spark.tpu.fusion.enabled", fusion)
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    try:
        spark.conf.set("spark.tpu.ui.operatorMetrics", "true")
        spark.conf.set("spark.tpu.trace.enabled", "true")
        with_obs = _launch_delta(spark, sql)
        spark.conf.set("spark.tpu.ui.operatorMetrics", "false")
        spark.conf.set("spark.tpu.trace.enabled", "false")
        without = _launch_delta(spark, sql)
        assert with_obs == without, (
            f"observability changed kernel dispatches: {with_obs} vs "
            f"{without}")
    finally:
        for k in ("spark.tpu.fusion.enabled", "spark.tpu.fusion.minRows",
                  "spark.tpu.ui.operatorMetrics", "spark.tpu.trace.enabled"):
            spark.conf.unset(k)


# ---------------------------------------------------------------------------
# per-operator kernel attribution
# ---------------------------------------------------------------------------

def test_plan_graph_attributes_launches_per_operator(data):
    spark = data
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    try:
        spark.sql(Q_AGG).toArrow()  # warm
        df = spark.sql(Q_AGG)
        df.toArrow()
        graph = df.query_execution.plan_graph()
        launched = {nd["op"]: nd["launches"] for nd in graph
                    if nd.get("launches")}
        assert launched, "no operator carries attributed launches"
        # the fused partial aggregate owns its fused_agg dispatches
        agg = [l for op, l in launched.items()
               if "HashAggregate" in op]
        assert agg and any("fused_agg" in l or "dagg" in l or "gagg" in l
                           for l in agg), launched
        # attributed per-op totals == the global measured delta shape
        total = sum(v for l in launched.values() for v in l.values())
        assert total > 0
        # fused member re-attribution rides the graph
        fused_nodes = [nd for nd in graph if nd.get("fused")]
        assert fused_nodes and any(
            "HashAggregate[partial]" in m
            for nd in fused_nodes for m in nd["fused"])
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")
        spark.conf.unset("spark.tpu.fusion.minRows")


def test_attribution_total_matches_global_counter(data):
    """Sum of per-operator attributed launches == global per-query delta
    (no dispatch escapes the operator scope on the local scheduler)."""
    spark = data
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    try:
        spark.sql(Q_AGG).toArrow()  # warm
        before = KC.launches
        df = spark.sql(Q_AGG)
        df.toArrow()
        global_delta = KC.launches - before
        graph = df.query_execution.plan_graph()
        attributed = sum(v for nd in graph
                         for v in (nd.get("launches") or {}).values())
        assert attributed == global_delta
    finally:
        spark.conf.unset("spark.tpu.fusion.minRows")


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_query_lifecycle_spans_and_chrome_export(data):
    spark = data
    mark = spark.tracer.mark()
    df = spark.sql("select k + 1 kk, v from obs_t where v > 10")
    df.toArrow()
    spans = spark.tracer.since(mark)
    cats = {s["cat"] for s in spans}
    names = {s["name"] for s in spans}
    assert "phase" in cats and "operator" in cats and "stage" in cats
    assert {"parse", "analysis", "planning", "execution",
            "collect"} <= names, names
    # multi-partition operator work records per-partition lane spans
    mark2 = spark.tracer.mark()
    spark.sql("select v from obs_t").repartition(4) \
        .filter("v > 0").toArrow()
    cats2 = {s["cat"] for s in spark.tracer.since(mark2)}
    assert "partition" in cats2, cats2
    # chrome export: metadata + complete events, nested, with kernel
    # attribution args on dispatching operator spans
    doc = spark.tracer.to_chrome_trace()
    evs = doc["traceEvents"]
    complete = [e for e in evs if e.get("ph") == "X"]
    assert complete and all("ts" in e and "dur" in e for e in complete)
    assert any((e.get("args") or {}).get("launches", 0) > 0
               for e in complete), "no span carries kernel attribution"


def test_tracer_ring_keeps_latest_spans_and_marks_survive_eviction():
    """Long-lived sessions must never go permanently dark: the buffer is
    a ring of the latest maxSpans, and mark()/since() sequence numbers
    stay correct across eviction."""
    from spark_tpu.obs.tracing import Tracer

    t = Tracer(enabled=True, max_spans=5)
    for i in range(8):
        with t.span(f"s{i}"):
            pass
    assert [s[0] for s in t.spans()] == [f"s{i}" for i in range(3, 8)]
    assert t.dropped == 3
    m = t.mark()
    with t.span("tail"):
        pass
    assert [d["name"] for d in t.since(m)] == ["tail"]


def test_chrome_trace_tracks_keyed_by_ident_and_name():
    """Python reuses thread idents for ephemeral lane threads — tracks
    must not merge two differently-named threads onto one label."""
    from spark_tpu.obs.tracing import to_chrome_trace

    spans = [("a", "c", 0.0, 1.0, 99, "lane-0", None),
             ("b", "c", 2.0, 1.0, 99, "lane-1", None)]  # reused ident
    doc = to_chrome_trace(spans)
    meta = [e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {m["args"]["name"] for m in meta} == {"lane-0", "lane-1"}
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(tids) == 2


def test_tracing_disable_stops_span_collection(data):
    spark = data
    spark.conf.set("spark.tpu.trace.enabled", "false")
    try:
        mark = spark.tracer.mark()
        spark.sql("select v from obs_t where v > 0").toArrow()
        assert spark.tracer.since(mark) == []
    finally:
        spark.conf.unset("spark.tpu.trace.enabled")


# ---------------------------------------------------------------------------
# event-log round-trip: metrics + spans → HistoryReader.summary
# ---------------------------------------------------------------------------

def test_event_log_roundtrip_surfaces_kernel_and_operator_totals(
        data, tmp_path):
    from spark_tpu.exec.listener import EventLoggingListener, HistoryReader

    spark = data
    log_dir = str(tmp_path / "events")
    el = EventLoggingListener(log_dir, app_id="obsapp")
    spark.listener_bus.register(el)
    try:
        spark.sql(Q_AGG).toArrow()
        spark.sql(Q_AGG).toArrow()
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(el)
    h = HistoryReader(log_dir)
    app = h.applications()[0]
    s = h.summary(app)
    assert s["queries"] >= 2
    # kernel.* counters replayed from the log
    assert s["kernel"].get("kernel.launches", 0) > 0, s["kernel"]
    assert "kernel_cache.launches" in s["kernel"]
    # per-operator totals aggregated over plan graphs
    assert any("HashAggregate" in op for op in s["operators"]), \
        s["operators"]
    agg = next(v for op, v in s["operators"].items()
               if "HashAggregate" in op)
    assert agg["rows"] > 0 and agg["launches"] > 0
    # spans rode the event log and replay into the summary
    assert s["span_count"] > 0 and s["span_total_ms"] > 0
    events = h.load(app)
    done = [e for e in events if e["event"] == "querySucceeded"]
    assert all("spans" in e for e in done)
    span_names = {sp["name"] for e in done for sp in e["spans"]}
    # the full lifecycle rides the event: parse (recorded in session.sql
    # before the QueryExecution exists) through execution and collect
    assert {"parse", "execution", "collect"} <= span_names, span_names


def test_parse_span_emitted_once_per_parse(data, tmp_path):
    """Re-collecting a DataFrame must not re-report a parse that never
    ran: the parse span rides the FIRST collect's event only."""
    from spark_tpu.exec.listener import EventLoggingListener, HistoryReader

    spark = data
    log_dir = str(tmp_path / "events")
    el = EventLoggingListener(log_dir, app_id="reparse")
    spark.listener_bus.register(el)
    try:
        df = spark.sql("select count(*) c from obs_t")
        df.toArrow()
        df.toArrow()
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(el)
    h = HistoryReader(log_dir)
    done = [e for e in h.load(h.applications()[0])
            if e["event"] == "querySucceeded"]
    assert len(done) == 2
    counts = [sum(1 for sp in e["spans"] if sp["name"] == "parse")
              for e in done]
    assert counts == [1, 0], counts


def test_parse_span_consumed_even_when_tracing_off_at_collect(
        data, tmp_path):
    """Parse spans attach at sql() time; an untraced first collect must
    still consume them so a later re-traced collect cannot mis-report a
    stale parse."""
    from spark_tpu.exec.listener import EventLoggingListener, HistoryReader

    spark = data
    df = spark.sql("select count(*) c from obs_t")   # tracing on: attach
    spark.conf.set("spark.tpu.trace.enabled", "false")
    try:
        df.toArrow()                                 # untraced collect
    finally:
        spark.conf.unset("spark.tpu.trace.enabled")
    log_dir = str(tmp_path / "events")
    el = EventLoggingListener(log_dir, app_id="stale")
    spark.listener_bus.register(el)
    try:
        df.toArrow()                                 # re-traced collect
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(el)
    h = HistoryReader(log_dir)
    done = [e for e in h.load(h.applications()[0])
            if e["event"] == "querySucceeded"]
    assert not any(sp["name"] == "parse"
                   for e in done for sp in e["spans"])


def test_live_ui_summary_matches_history_shape(data):
    from spark_tpu.exec.ui import LiveStatusStore

    spark = data
    store = LiveStatusStore("obs-live")
    spark.listener_bus.register(store)
    try:
        spark.sql(Q_AGG).toArrow()
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(store)
    s = store.summary("obs-live")
    assert s["queries"] >= 1 and "kernel" in s and "operators" in s
    assert "running" in s


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

def test_explain_analyze_renders_measured_vs_predicted(data, capsys):
    spark = data
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    try:
        spark.sql(Q_AGG).explain("analyze")
        out = capsys.readouterr().out
        assert "EXPLAIN ANALYZE" in out
        assert "predicted vs measured" in out
        assert "rows=" in out and "launches=" in out
        assert "fused:" in out          # member re-attribution rendered
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")
        spark.conf.unset("spark.tpu.fusion.minRows")


@pytest.mark.parametrize("enabled", ["true", "false"])
def test_explain_analyze_tpcds_mini_zero_unexplained_drift(spark, enabled):
    """Acceptance: q3/q7 show per-operator rows/wall-ms/attributed
    launches (including inside fused stages) with zero unexplained
    drift, fusion on and off."""
    from tests.test_plan_analysis import Q3, Q7
    from tpcds_mini import register_tpcds

    register_tpcds(spark)
    spark.conf.set("spark.tpu.fusion.enabled", enabled)
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    try:
        for sql in (Q3, Q7):
            report = spark.sql(sql).query_execution.analyzed_report()
            assert not report.has_unexplained_drift, report.render()
            assert report.prediction_exact
            assert report.predicted == report.measured
            # every executed operator carries rows + wall-ms
            executed = [nd for nd in report.nodes if nd["ms"] is not None]
            assert executed
            assert all(nd["rows"] is not None for nd in executed)
            # kernel attribution reached inside the plan
            assert any(nd["launches"] for nd in report.nodes)
            if enabled == "true":
                fused = [nd for nd in report.nodes if nd["fused"]]
                assert fused, "no fused operators on TPC-DS mini plan"
                assert all(nd["launches"] for nd in fused)
            d = report.to_dict()
            assert d["prediction_exact"] and d["measured"] == d["predicted"]
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")
        spark.conf.unset("spark.tpu.fusion.minRows")


def test_explain_analyze_forces_metrics_when_disabled(data):
    """EXPLAIN ANALYZE drives its own runs — it must annotate operators
    even in sessions that disable operatorMetrics (bench-style), and
    restore the setting afterwards."""
    spark = data
    spark.conf.set("spark.tpu.ui.operatorMetrics", "false")
    spark.conf.set("spark.tpu.metrics.kernelAttribution", "false")
    try:
        report = spark.sql(Q_AGG).query_execution.analyzed_report()
        assert any(nd["ms"] is not None for nd in report.nodes)
        assert any(nd["launches"] for nd in report.nodes)
        assert spark.conf.get("spark.tpu.ui.operatorMetrics") is False
        assert spark.conf.get("spark.tpu.metrics.kernelAttribution") is False
    finally:
        spark.conf.unset("spark.tpu.ui.operatorMetrics")
        spark.conf.unset("spark.tpu.metrics.kernelAttribution")


def test_explain_analyze_flags_min_rows_gate(spark, data):
    """Default minRows (≫ 5k rows) routes a fused plan to the unfused
    kernels at runtime — EXPLAIN ANALYZE must surface the gate decision
    as a first-class finding, with zero unexplained drift."""
    spark.conf.set("spark.tpu.fusion.enabled", "true")
    try:
        report = spark.sql(Q_AGG).query_execution.analyzed_report()
        assert not report.has_unexplained_drift, report.render()
        assert any(f["kind"] == "minRows-gate" for f in report.findings), \
            report.findings
    finally:
        spark.conf.unset("spark.tpu.fusion.enabled")


# ---------------------------------------------------------------------------
# per-query span scoping (concurrency-safe replacement for mark/since)
# ---------------------------------------------------------------------------

def test_query_scope_tags_spans_disjointly():
    from spark_tpu.obs.tracing import Tracer, pop_query, push_query

    t = Tracer(enabled=True)
    tok = push_query("qA")
    try:
        with t.span("a1"):
            with t.span("a2"):
                pass
    finally:
        pop_query(tok)
    tok = push_query("qB")
    try:
        with t.span("b1"):
            pass
    finally:
        pop_query(tok)
    with t.span("untagged"):
        pass
    assert {s["name"] for s in t.spans_for("qA")} == {"a1", "a2"}
    assert {s["name"] for s in t.spans_for("qB")} == {"b1"}
    assert all(s["query"] == "qA" for s in t.spans_for("qA"))


def test_concurrent_collects_get_disjoint_query_spans(data):
    """Two collects racing on ONE shared session must not cross-attribute
    event spans: each querySucceeded event carries exactly its own
    lifecycle (one collect span) and none of the other query's operator
    spans — the failure mode of the old buffer-offset mark()/since()
    slicing."""
    import threading

    spark = data
    events = []
    spark.listener_bus.register(events.append)
    barrier = threading.Barrier(2)
    errors = []

    def run(sql):
        try:
            barrier.wait(timeout=30)
            for _ in range(3):
                spark.sql(sql).toArrow()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    q_plain = "select v from obs_t where v > 10"
    threads = [threading.Thread(target=run, args=(s,))
               for s in (Q_AGG, q_plain)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        spark.listener_bus.wait_empty()
    finally:
        spark.listener_bus.unregister(events.append)
    assert not errors, errors
    done = [e for e in events if e.event == "querySucceeded"]
    assert len(done) == 6
    for e in done:
        names = [sp["name"] for sp in e.spans]
        assert names.count("collect") == 1, (e.query_id, names)
        assert names.count("execution") == 1, (e.query_id, names)
        is_agg = "HashAggregate" in (e.plan or "")
        agg_spans = [n for n in names if "HashAggregate" in n]
        if is_agg:
            assert agg_spans, names
        else:
            assert not agg_spans, (e.query_id, names)


def test_scoped_submit_preserves_attribution_and_query_scope():
    """Satellite regression: obs scope must follow work into thread
    POOLS via a copied contextvars Context per submit — a bare submit
    silently re-buckets launches to 'unattributed' and drops the query
    tag (pool threads start with an empty context)."""
    from concurrent.futures import ThreadPoolExecutor

    from spark_tpu.obs import metrics as OM
    from spark_tpu.obs.tracing import current_query, pop_query, push_query

    rec = OM.new_op_record()
    op_token = OM.push_op(rec, "PoolOp")
    q_token = push_query("q-pool")
    try:
        with ThreadPoolExecutor(2) as pool:
            futs = [OM.scoped_submit(pool, OM.record_kernel_launch, "probe")
                    for _ in range(3)]
            for f in futs:
                f.result()
            scoped_op = OM.scoped_submit(pool, OM.current_op_name).result()
            scoped_q = OM.scoped_submit(pool, current_query).result()
            bare_op = pool.submit(OM.current_op_name).result()
    finally:
        pop_query(q_token)
        OM.pop_op(op_token)
    assert rec["kinds"] == {"probe": 3} and rec["launch_total"] == 3
    assert scoped_op == "PoolOp" and scoped_q == "q-pool"
    assert bare_op is None  # the hazard scoped_submit exists to prevent


# ---------------------------------------------------------------------------
# Perfetto flow events: phase → stage → partition-lane arrows
# ---------------------------------------------------------------------------

def _flow_edges(doc):
    """(source complete event, dest complete event) per exported flow."""
    evs = doc["traceEvents"]
    complete = [e for e in evs if e.get("ph") == "X"]

    def enclosing(fe):
        best = None
        for sp in complete:
            if sp["pid"] == fe["pid"] and sp["tid"] == fe["tid"] and \
                    sp["ts"] - 1 <= fe["ts"] <= sp["ts"] + sp["dur"] + 1:
                if best is None or sp["dur"] < best["dur"]:
                    best = sp
        return best

    # every arrow is exactly one "s" and one "f": a repeated or a
    # stepped ("t") endpoint renders as an arrow from or to nowhere
    phases = {}
    for e in evs:
        if e.get("ph") in ("s", "t", "f"):
            phases.setdefault(e["id"], []).append(e["ph"])
    broken = {i: p for i, p in phases.items() if sorted(p) != ["f", "s"]}
    assert not broken, f"broken flow arrows: {broken}"
    starts = {e["id"]: e for e in evs if e.get("ph") == "s"}
    ends = {e["id"]: e for e in evs if e.get("ph") == "f"}
    return [(enclosing(starts[i]), enclosing(ends[i])) for i in starts]


def _assert_well_formed(doc):
    """What a trace viewer needs of an exported trace: complete events
    with every field and no negative time, and on each thread track
    spans that nest or are disjoint (1 us of slack for float rounding).
    Returns the complete events."""
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    complete = [e for e in evs if e.get("ph") == "X"]
    assert complete, "no complete span events"
    for e in complete:
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e), e
        assert e["ts"] >= 0 and e["dur"] >= 0, e
    tracks = {}
    for e in complete:
        tracks.setdefault((e["pid"], e["tid"]), []).append(e)
    for track, spans in tracks.items():
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in spans:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - 1:
                stack.pop()
            if stack:
                outer = stack[-1]
                assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1, (
                    f"span {e['name']!r} partially overlaps "
                    f"{outer['name']!r} on track {track}")
            stack.append(e)
    return complete


def test_exported_trace_is_well_formed_and_spans_nest(data, tmp_path):
    import json

    spark = data
    spark.sql(Q_JOIN).toArrow()
    spark.sql("select v from obs_t").repartition(4) \
        .filter("v > 0").toArrow()
    with open(spark.tracer.write_chrome_trace(
            str(tmp_path / "trace.json"))) as f:
        complete = _assert_well_formed(json.load(f))
    # lanes ran beside the main thread, so nesting was checked on several
    assert len({(e["pid"], e["tid"]) for e in complete}) > 1


def test_flow_events_link_execution_stage_and_lanes(data):
    spark = data
    spark.sql("select v from obs_t").repartition(4) \
        .filter("v > 0").toArrow()
    doc = spark.tracer.to_chrome_trace()
    edges = _flow_edges(doc)
    assert edges, "no flow arrows exported"
    assert all(src is not None and dst is not None for src, dst in edges), \
        "flow endpoint does not land inside a span"
    kinds = {(src["name"].split("[")[0].split("-")[0], dst["cat"])
             for src, dst in edges}
    # execution phase → stage arrows and stage → partition-lane arrows
    assert any(src["name"] == "execution" and
               dst["name"].startswith("stage-")
               for src, dst in edges), kinds
    assert any(dst["cat"] == "partition" for _, dst in edges), kinds


# ---------------------------------------------------------------------------
# cluster mode: worker-side metric/span shipping round trip
# ---------------------------------------------------------------------------

def _cq(spark):
    """Shuffle+agg over the cluster: the explicit repartition keeps a
    round-robin map stage and a hash-exchange map stage in the plan even
    on single-partition input (a partial-only aggregate would collapse
    to one local stage and never ship)."""
    import spark_tpu.api.functions as F

    return (spark.sql("select k, v from cobs_t").repartition(3)
            .groupBy("k").agg(F.sum("v").alias("sv"),
                              F.count("k").alias("c")))


def _cobs_table():
    rng = np.random.default_rng(41)
    n = 6000
    return pa.table({"k": rng.integers(0, 7, n),
                     "v": rng.integers(-30, 70, n)})


@pytest.fixture(scope="module")
def cluster_spark():
    """Session over a 2-worker local process cluster (shuffle+agg plans
    ship their map stages into worker processes). AQE off so local and
    cluster runs execute the identical static plan."""
    from spark_tpu.api.session import TpuSession
    from spark_tpu.exec.cluster import LocalCluster

    s = TpuSession("obs-cluster", {
        "spark.sql.shuffle.partitions": "3",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    cluster = LocalCluster(num_workers=2)
    s.attachSqlCluster(cluster)
    s.createDataFrame(_cobs_table()).createOrReplaceTempView("cobs_t")
    yield s
    s.stop()


def _rollup(graph):
    """plan_graph → {(metric id, op): (rows, batches)} for executed ops."""
    return {(nd["id"], nd["op"]): (nd["rows"], nd.get("batches"))
            for nd in graph if nd.get("rows") is not None}


def test_cluster_metrics_merge_matches_local_rollup(cluster_spark):
    """Worker-shipped per-operator records must merge to the SAME rollup
    the purely-local scheduler measures: identical plan → identical
    per-node rows/batches, metric-id for metric-id."""
    from spark_tpu.api.session import TpuSession

    df = _cq(cluster_spark)
    df.toArrow()
    remote = cluster_spark._metrics.snapshot()["counters"].get(
        "scheduler.stages_remote", 0)
    assert remote >= 1, "query never shipped a stage to a worker"
    cluster_rollup = _rollup(df.query_execution.plan_graph())
    assert cluster_rollup, "cluster plan graph carries no operator rows"

    local = TpuSession("obs-local-ref", {
        "spark.sql.shuffle.partitions": "3",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
    })
    try:
        local.createDataFrame(_cobs_table()) \
            .createOrReplaceTempView("cobs_t")
        ldf = _cq(local)
        ldf.toArrow()
        local_rollup = _rollup(ldf.query_execution.plan_graph())
    finally:
        local.stop()
    assert cluster_rollup == local_rollup, (
        f"cluster rollup {cluster_rollup} != local {local_rollup}")


def test_cluster_spans_include_worker_tracks(cluster_spark):
    spark = cluster_spark
    mark = spark.tracer.mark()
    _cq(spark).toArrow()
    spans = spark.tracer.since(mark)
    worker = [s for s in spans
              if str(s.get("thread", "")).startswith("worker:")]
    assert worker, f"no worker-track spans in {sorted({s['thread'] for s in spans})}"
    cats = {s["cat"] for s in worker}
    # the task root span and the operator spans inside it both shipped
    assert "worker" in cats and "operator" in cats, cats
    # worker spans re-tagged to the driver's query scope
    assert all("query" in s for s in worker), worker[:3]


def test_cluster_attribution_total_matches_driver_plus_worker(cluster_spark):
    """No dispatch escapes attribution across the process boundary: the
    per-operator attributed-launch total equals the driver KernelCache
    delta plus the worker-shipped launch deltas."""
    spark = cluster_spark
    _cq(spark).toArrow()  # warm both worker processes' caches
    before = KC.launches
    df = _cq(spark)
    df.toArrow()
    driver_delta = KC.launches - before
    ctx = df.query_execution._last_ctx
    worker_kinds = ctx.worker_kernel_kinds or {}
    assert worker_kinds, "workers shipped no kernel-launch deltas"
    graph = df.query_execution.plan_graph()
    attributed = sum(v for nd in graph
                     for v in (nd.get("launches") or {}).values())
    assert attributed == driver_delta + sum(worker_kinds.values()), (
        f"attributed {attributed} != driver {driver_delta} + worker "
        f"{worker_kinds}")


def test_cluster_explain_analyze_no_unexplained_drift(cluster_spark):
    """Acceptance: cluster-mode EXPLAIN ANALYZE reports non-empty
    per-operator metrics, zero unexplained drift, and an attributed
    total equal to the measured driver+worker launch total."""
    report = _cq(cluster_spark).query_execution.analyzed_report()
    assert not report.has_unexplained_drift, report.render()
    executed = [nd for nd in report.nodes if nd["ms"] is not None]
    assert executed and any(nd["launches"] for nd in report.nodes), \
        report.render()
    attributed = sum(v for nd in report.nodes
                     for v in (nd.get("launches") or {}).values())
    assert attributed == sum(report.measured.values()), report.render()


def test_cluster_trace_exports_cross_process_flow_arrows(cluster_spark):
    """The exported trace draws arrows across the process boundary:
    stage → worker task (shipped flow parent) and map task →
    reduce-side fetch (deterministic shuffle-derived flow ids)."""
    spark = cluster_spark
    _cq(spark).toArrow()
    doc = spark.tracer.to_chrome_trace()
    edges = [(s, d) for s, d in _flow_edges(doc)
             if s is not None and d is not None]
    assert any(d["cat"] == "worker" for _, d in edges), \
        "no stage → worker-task flow arrow"
    assert any(d["name"].startswith("fetch[") and s["cat"] == "worker"
               for s, d in edges), "no map-task → reduce-fetch flow arrow"


def test_cluster_trace_is_well_formed_with_worker_tracks(cluster_spark):
    """Spans shipped from the worker processes keep the viewer's rules:
    they land on thread tracks of their own, named `worker:<id>/...`, and
    nest there like the driver's."""
    spark = cluster_spark
    _cq(spark).toArrow()
    doc = spark.tracer.to_chrome_trace()
    complete = _assert_well_formed(doc)
    worker_tids = {m["tid"] for m in doc["traceEvents"]
                   if m.get("ph") == "M" and m.get("name") == "thread_name"
                   and str(m["args"]["name"]).startswith("worker:")}
    assert worker_tids, "no worker thread track in the exported trace"
    assert any(e["tid"] in worker_tids for e in complete)
