#!/usr/bin/env python
"""CI gate for the observability layer (dev/run_all.sh).

Four checks, all hard failures:

1. Trace validation — the Chrome-trace JSON emitted by `bench.py --smoke
   --trace` must be well-formed (a non-empty `traceEvents` list of
   complete/metadata events with sane fields), spans must nest properly
   per thread track (stack discipline: no partial overlap), at least
   one span must carry non-empty kernel attribution (`args.launches`) —
   proving the KernelCache→operator attribution path is live end to end
   — and every Perfetto flow arrow must have referential integrity:
   each flow id resolves to exactly one "s" and one "f" event, each
   anchored inside a complete span on its thread track. With --cluster
   (the `bench.py --smoke --trace --cluster` leg), the trace must also
   contain at least one worker-track span (`worker:<id>/...` thread
   name), proving worker-side span shipping crossed the process
   boundary.

2. Drift gate — EXPLAIN ANALYZE on a representative fused aggregation
   runs predicted-vs-measured reconciliation; any finding of severity
   `error` (unexplained drift between analysis/plan_lint.py's launch
   model and the execution layer) fails the build. With --cluster the
   gate query runs under ClusterDAGScheduler and additionally requires
   non-empty per-operator metrics whose attributed-launch total equals
   the measured (driver + worker) launch total.

3. Resource gate — after the drift-gate query, the device ledger
   (obs/resources.py) must verify internally: non-negative balances
   everywhere (global, per-query, per-operator), the identity table
   reconciling with the byte counter, attribution sums never exceeding
   the global ledger; the KernelCache's captured cost table must be
   non-empty with positive cumulative bytes accessed; and the gate
   query's HBM record must show a positive measured watermark with
   per-operator attribution.

4. Live-telemetry gate (--live) — a cluster smoke run with a fast
   executor heartbeat must surface at least one MID-STAGE obs delta on
   the driver before any task returns (the reference's periodic
   Heartbeater streaming accumulator updates), and after completion the
   merged live records must reconcile with the final task-return
   records (monotonic merge converged: every task done, partial
   counters superseded exactly, zero straggler findings on the healthy
   run).

5. Mesh gate (--mesh) — on a virtual 8-device CPU mesh, a fused
   power-of-two repartition+agg must run its shuffle stage as
   mesh_stage dispatches that plan_lint predicts EXACTLY, with zero
   unexplained drift, attribution totals matching the measured
   launches under shard_map, span nesting holding on the exported
   trace, a donated (donate_argnums) stage program in the kernel
   cache, and a balanced device ledger afterwards. Self-contained:
   `validate_trace.py --mesh` with no trace path runs only this gate.

6. Encoded gate (--encoded) — compressed execution: a dictionary-heavy
   string-keyed repartition + group-by must produce byte-identical
   results encoded vs decoded (spark.tpu.encoding.enabled
   differential), predict launch counts exactly on the encoded path
   (dense-on-codes, zero krange3 probes) fusion on and off, and show
   zero unexplained EXPLAIN ANALYZE drift. Self-contained:
   `validate_trace.py --encoded` with no trace path runs only this
   gate.

7. Chaos gate (--chaos) — deterministic fault injection under a fixed
   seed: a transient block-fetch flap must be absorbed by the bounded
   fetch retry with zero stage regenerations; exhausted fetch retries
   must regenerate from lineage correctly and an unbounded failure
   stream must terminate in the classified StageRegenerationLimitError
   with zero leaked shuffle blocks; a transient worker-task fault must
   fail over to another executor with per-operator kernel attribution
   still equal to driver+worker totals; a whole-tier runtime dispatch
   fault must degrade to the stage tier with identical results. Every
   scenario runs under a watchdog (a hang fails the gate) and the
   device ledger must verify balanced afterwards. Self-contained:
   `validate_trace.py --chaos` with no trace path runs only this gate.

8. Profile gate (--profile) — query flight recorder end to end: two
   identical smoke queries must yield ONE plan fingerprint, two stored
   profiles, and zero obs.regression findings; a forced
   spark.tpu.compile.tier=operator flip must land on the SAME
   structural query key, a DIFFERENT fingerprint, and raise a
   deterministic-counter regression finding (severity error); and
   dev/perfcheck.py's comparator must flag the same delta against a
   baseline built from the healthy runs. Self-contained:
   `validate_trace.py --profile` with no trace path runs only this
   gate.

10. Serve gate (--serve) — multi-tenant serving (spark_tpu/serve/):
    the weighted fair scheduler must grant contended slots in exact
    2:1 proportion under a deterministic submit/release schedule;
    scheduler-level HBM admission must hold a query back until the
    in-flight reservation frees budget (and an over-budget plan must
    reject plan-time through check_memory_budget); a REAL concurrent
    load (8 cloned sessions, 2 pools) must complete with every
    query's attributed launch total summing exactly to the global
    KernelCache delta, zero `overlapped` profiles, and a
    contention-fairness ratio within 25% of the configured weights;
    and graceful drain must reject new queries with SERVER_DRAINING,
    finish in-flight work, and leave the admission ledger balanced
    (no leaked slots or HBM reservations) with the device ledger
    verifying clean. Self-contained: `validate_trace.py --serve`
    with no trace path runs only this gate.

11. Mesh whole-query gate (--mesh-whole) — on a virtual 8-device CPU
    mesh, a repartitioned join+agg under spark.tpu.compile.tier=
    mesh-whole must execute the ENTIRE sharded plan as ONE shard_map
    dispatch per step (exchanges as in-program all-to-alls, join and
    aggregate folded behind the collectives), agree with the whole and
    stage tiers, have its mesh_whole launch count — including a skew-
    driven quota-retry round — predicted EXACTLY by plan_lint, surface
    the tier decision on report and span, and leave the device ledger
    balanced. Self-contained: `validate_trace.py --mesh-whole` with no
    trace path runs only this gate.

12. Race gate (--race) — runtime lock-discipline validation: the
    8-session serve load and a 2-worker cluster chaos leg (transient
    block-fetch flap plus a deterministic transport-retry exercise) run
    under utils/lockwatch.py with every registered lock watched. Every
    instrumented guard must be HELD where the static race_lint model
    claims (zero guard violations, the RETRY_STATS counter actually
    exercised), the union of the statically inferred lock-nesting graph
    and the runtime-observed acquisition-order edges must stay acyclic
    (an observed order the static model missed that closes a cycle is a
    latent deadlock), the registered watch slots must all exist in the
    static lock inventory, attribution must stay scope-exact under
    watching, and disable() must restore raw locks (the structural
    zero-overhead-when-idle claim). Self-contained:
    `validate_trace.py --race` with no trace path runs only this gate.

13. Adaptive gate (--adaptive) — runtime-adaptive execution: a
    selective shuffled hash join must produce identical results with
    spark.tpu.adaptive.runtimeFilter on vs off, install at least one
    runtime join filter that prunes probe rows before the shuffle (the
    install event visible as an adaptive.runtime_filter span), degrade
    the launch model honestly (exact=False with a named runtimeFilter
    reason, zero unexplained EXPLAIN ANALYZE drift), and leave the
    device ledger balanced. Self-contained: `validate_trace.py
    --adaptive` with no trace path runs only this gate.

Usage: python dev/validate_trace.py [--cluster] [--live] [--mesh]
       [--encoded] [--adaptive] [--whole-query] [--mesh-whole]
       [--chaos] [--profile] [--serve] [--race] [<trace.json>]
"""

import json
import os
import sys

# runs as `python dev/validate_trace.py` — spark_tpu lives one level up
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"validate_trace: FAIL — {msg}")
    sys.exit(1)


def _check_flows(events: list, complete: list) -> int:
    """Flow-event referential integrity: every flow id has exactly one
    "s" and one "f" endpoint, and each endpoint lands inside a complete
    span on its (pid, tid) track (Perfetto binds arrows to the enclosing
    slice — a dangling endpoint renders as an arrow from/to nowhere)."""
    fuzz = 1.0
    flows = [e for e in events if e.get("ph") in ("s", "t", "f")]
    by_id: dict = {}
    for e in flows:
        if "id" not in e:
            fail(f"flow event missing id: {e}")
        by_id.setdefault(e["id"], []).append(e)
    spans_by_track: dict = {}
    for e in complete:
        spans_by_track.setdefault((e["pid"], e["tid"]), []).append(e)
    for fid, evs in by_id.items():
        phs = sorted(e["ph"] for e in evs)
        if phs != ["f", "s"]:
            fail(f"flow id {fid} endpoints are {phs}, want one 's' + "
                 "one 'f' (broken arrow)")
        for e in evs:
            track = spans_by_track.get((e["pid"], e["tid"]), [])
            if not any(sp["ts"] - fuzz <= e["ts"] <= sp["ts"] + sp["dur"]
                       + fuzz for sp in track):
                fail(f"flow endpoint {e} does not land inside any span "
                     f"on track {(e['pid'], e['tid'])} — the flow id "
                     "does not resolve to an endpoint span")
    return len(by_id)


def validate_trace(path: str, cluster: bool = False) -> None:
    if not os.path.isfile(path):
        fail(f"trace file {path} does not exist")
    with open(path) as f:
        try:
            doc = json.load(f)
        except json.JSONDecodeError as e:
            fail(f"trace file is not valid JSON: {e}")
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        fail("traceEvents missing or empty")
    complete = [e for e in events if e.get("ph") == "X"]
    if not complete:
        fail("no complete ('ph': 'X') span events")
    for e in complete:
        for k in ("name", "ts", "dur", "pid", "tid"):
            if k not in e:
                fail(f"span event missing field {k!r}: {e}")
        if e["dur"] < 0 or e["ts"] < 0:
            fail(f"negative ts/dur: {e}")

    # nesting: per tid, spans must obey stack discipline — any two spans
    # either nest or are disjoint (1 µs fuzz for float rounding)
    fuzz = 1.0
    by_tid: dict = {}
    for e in complete:
        by_tid.setdefault(e["tid"], []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] \
                    - fuzz:
                stack.pop()
            if stack:
                parent = stack[-1]
                if e["ts"] + e["dur"] > parent["ts"] + parent["dur"] + fuzz:
                    fail(f"span {e['name']!r} partially overlaps "
                         f"{parent['name']!r} on tid {tid} "
                         "(broken nesting)")
            stack.append(e)

    attributed = [e for e in complete
                  if (e.get("args") or {}).get("launches", 0) > 0]
    if not attributed:
        fail("no span carries kernel attribution (args.launches > 0) — "
             "the KernelCache→operator attribution scope is dead")

    n_flows = _check_flows(events, complete)
    if not n_flows:
        fail("no flow events — the query→stage→lane/worker flow "
             "linkage is dead (spans carry no resolvable flow ids)")

    worker_tracks = {m["args"]["name"] for m in events
                     if m.get("ph") == "M"
                     and m.get("name") == "thread_name"
                     and str(m.get("args", {}).get("name", ""))
                     .startswith("worker:")}
    if cluster and not worker_tracks:
        fail("--cluster: no worker-track span (thread name 'worker:…') — "
             "worker-side span shipping never crossed the process "
             "boundary")
    cats = {e.get("cat") for e in complete}
    print(f"validate_trace: trace OK — {len(complete)} spans, "
          f"{len(by_tid)} thread tracks ({len(worker_tracks)} worker), "
          f"{len(attributed)} with kernel attribution, {n_flows} flow "
          f"arrows, categories={sorted(c for c in cats if c)}")


def drift_gate(cluster: bool = False) -> None:
    """EXPLAIN ANALYZE a fused aggregation; severity-error drift findings
    (launch-model divergence) fail the gate. With --cluster the query
    runs under ClusterDAGScheduler: worker-shipped attribution must be
    non-empty and reconcile with the driver+worker measured total."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession

    conf = {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.fusion.minRows": "0",
    }
    if cluster:
        conf["spark.tpu.cluster.enabled"] = "true"
        conf["spark.tpu.cluster.workers"] = "2"
    session = TpuSession("trace-gate", conf)
    try:
        rng = np.random.default_rng(11)
        n = 4000
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 9, n),
            "v": rng.integers(-20, 80, n),
        })).createOrReplaceTempView("gate_t")
        if cluster:
            # the explicit repartition keeps shuffle map stages in the
            # plan (a single-partition partial agg never ships) — the
            # gate must exercise worker-side attribution, not just the
            # driver path
            import spark_tpu.api.functions as F

            df = (session.sql("select k, v from gate_t where v > 0")
                  .repartition(2).groupBy("k")
                  .agg(F.sum("v").alias("s"), F.count("k").alias("c")))
        else:
            df = session.sql(
                "select k, sum(v) s, count(*) c from gate_t where v > 0 "
                "group by k")
        report = df.query_execution.analyzed_report()
        errors = [f for f in report.findings if f["severity"] == "error"]
        if errors:
            print(report.render())
            fail("EXPLAIN ANALYZE reported unexplained drift: "
                 + "; ".join(f["msg"] for f in errors))
        if cluster:
            remote = session._metrics.snapshot()["counters"].get(
                "scheduler.stages_remote", 0)
            if remote < 1:
                fail("--cluster: gate query never shipped a map stage "
                     "to a worker process")
            attributed = sum(v for nd in report.nodes
                             for v in (nd.get("launches") or {}).values())
            measured = sum(report.measured.values())
            if not attributed:
                fail("--cluster: EXPLAIN ANALYZE per-operator metrics "
                     "empty — worker-side attribution never shipped")
            if attributed != measured:
                fail(f"--cluster: attributed launches ({attributed}) != "
                     f"measured driver+worker total ({measured}) — a "
                     "dispatch escaped cross-process attribution")
        print("validate_trace: drift gate OK — predicted "
              f"{sum(report.predicted.values())} == measured "
              f"{sum(report.measured.values())} launches, "
              f"{len(report.findings)} non-error findings"
              + (" [cluster]" if cluster else ""))
    finally:
        session.stop()


def resource_gate() -> None:
    """Device-resource accounting must balance at query end: the ledger
    verifies internally (non-negative balances, identity table ==
    counter, attribution <= global), the kernel cost table is non-empty
    with positive bytes accessed, and the gate query's HBM record shows
    a positive per-operator-attributed watermark that EXPLAIN ANALYZE's
    memory section reconciles against the plan analyzer's prediction."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    session = TpuSession("resource-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
    })
    try:
        rng = np.random.default_rng(5)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 7, 3000),
            "v": rng.integers(-10, 90, 3000),
        })).createOrReplaceTempView("res_t")
        df = session.sql("select k, sum(v) s from res_t where v > 0 "
                         "group by k")
        report = df.query_execution.analyzed_report()
        issues = GLOBAL_LEDGER.verify()
        if issues:
            fail("resource gate: ledger failed verification — "
                 + "; ".join(issues))
        if not KC.cost_by_kind:
            fail("resource gate: kernel cost table empty — cost capture "
                 "never ran (spark.tpu.metrics.kernelCost path broken)")
        if not KC.bytes_total > 0:
            fail("resource gate: cumulative kernel bytes accessed is 0 — "
                 "neither XLA cost_analysis nor the metadata fallback "
                 "captured anything")
        mem = report.memory
        if not mem.get("measured_peak"):
            fail("resource gate: EXPLAIN ANALYZE memory section has no "
                 "measured HBM watermark for the gate query")
        if not mem.get("predicted_peak"):
            fail("resource gate: plan analyzer produced no predicted "
                 "peak HBM for the gate query")
        if not any(st.get("measured") for st in mem.get("per_stage", ())):
            fail("resource gate: no per-operator HBM attribution reached "
                 "the memory section (scope propagation broken)")
        print("validate_trace: resource gate OK — ledger balanced "
              f"({GLOBAL_LEDGER.bytes} B live), "
              f"{len(KC.cost_by_kind)} kernel kinds costed "
              f"({KC.bytes_total:.0f} B accessed), query watermark "
              f"{mem['measured_peak']} B vs predicted {mem['predicted_peak']} B")
    finally:
        session.stop()


def live_gate() -> None:
    """Heartbeat-streamed telemetry must be operational, not post-mortem:
    run a deliberately slow map stage on a 2-worker cluster heartbeating
    every 0.1s, require ≥1 mid-stage obs delta on the driver BEFORE the
    task-return record lands, then require the live store to have
    converged to the task-return truth (every cluster task done and
    reconciled) with zero straggler findings on the healthy run."""
    import time

    import numpy as np
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu import TpuSession
    from spark_tpu.types import int64

    session = TpuSession("live-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 2,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.heartbeat.interval": "0.1",
    })
    try:
        rng = np.random.default_rng(23)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 8, 4000),
            "v": rng.integers(-20, 60, 4000),
        })).createOrReplaceTempView("live_t")

        @F.udf(returnType=int64)
        def crawl(k):
            time.sleep(0.4)   # several 0.1s heartbeats per map batch
            return k * 2

        qids = []
        session.listener_bus.register(lambda ev: qids.append(ev.query_id))
        live = session.live_obs
        base = live.partials_seen
        (session.table("live_t").withColumn("kk", crawl("k"))
         .repartition(2).groupBy("k").agg(F.sum("v").alias("s"))).toArrow()
        session.listener_bus.wait_empty()
        if live.partials_seen <= base:
            fail("--live: no mid-stage heartbeat obs delta reached the "
                 "driver before task return")
        if not qids:
            fail("--live: query event never fired (no query id to check)")
        progress = live.query_progress(qids[-1])
        if progress is None:
            fail("--live: live store has no record of the gate query")
        streamed = 0
        for stage, st in progress["stages"].items():
            if stage == "local":
                continue
            if st["tasks_done"] != st["tasks_total"]:
                fail(f"--live: stage {stage} never closed in the live "
                     f"store ({st['tasks_done']}/{st['tasks_total']})")
            for task, t in st["tasks"].items():
                if t["partials"] > 0:
                    streamed += 1
                    if t["reconciled"] is not True:
                        fail(f"--live: task {task} of stage {stage} "
                             "streamed partials that do NOT reconcile "
                             "with its final task-return record")
        if streamed < 1:
            fail("--live: no cluster task streamed a mid-stage partial "
                 "for the gate query")
        stragglers = [f for f in progress["findings"]
                      if f.get("kind") == "obs.straggler"]
        if stragglers:
            fail("--live: healthy run raised straggler findings: "
                 + "; ".join(f["msg"] for f in stragglers))
        print(f"validate_trace: live gate OK — {live.partials_seen - base} "
              f"heartbeat deltas, {streamed} task(s) streamed partials "
              "and reconciled, 0 stragglers")
    finally:
        session.stop()


def mesh_gate() -> None:
    """Mesh SPMD stage gate (--mesh, virtual 8-device CPU mesh): a
    power-of-two fused repartition+agg must execute its shuffle stage as
    mesh_stage dispatches predicted EXACTLY by plan_lint (one per step
    plus quota retries), EXPLAIN ANALYZE must show zero unexplained
    drift, per-operator attribution must equal the measured total (no
    dispatch escapes the operator scope under shard_map), span nesting
    must hold on the exported trace, and the device ledger must stay
    balanced after the donated send buffers release."""
    import jax

    if len(jax.devices()) < 8:
        fail("--mesh: needs 8 virtual devices (run with JAX_PLATFORMS="
             "cpu XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    import json as _json
    import tempfile

    import numpy as np
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu import TpuSession
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    session = TpuSession("mesh-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 8,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.trace.enabled": "true",
        "spark.tpu.ui.operatorMetrics": "true",
    })
    try:
        rng = np.random.default_rng(29)
        n = 6000
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 11, n),
            "v": rng.integers(-20, 80, n),
        })).createOrReplaceTempView("mesh_t")

        def q():
            return (session.sql("select k, v * 2 as v2 from mesh_t "
                                "where v > 0")
                    .repartition(8, "k").groupBy("k")
                    .agg(F.sum("v2").alias("s")))

        report = q().query_execution.analyzed_report()
        errors = [f for f in report.findings if f["severity"] == "error"]
        if errors:
            print(report.render())
            fail("--mesh: EXPLAIN ANALYZE reported unexplained drift "
                 "under shard_map: " + "; ".join(f["msg"] for f in errors))
        if report.measured.get("mesh_stage", 0) < 1:
            fail("--mesh: gate query never dispatched a mesh stage "
                 f"program (measured {dict(report.measured)})")
        if report.predicted.get("mesh_stage") != \
                report.measured.get("mesh_stage"):
            fail("--mesh: plan_lint mesh_stage prediction "
                 f"{report.predicted.get('mesh_stage')} != measured "
                 f"{report.measured.get('mesh_stage')}")
        attributed = sum(v for nd in report.nodes
                         for v in (nd.get("launches") or {}).values())
        measured = sum(report.measured.values())
        if attributed != measured:
            fail(f"--mesh: attributed launches ({attributed}) != "
                 f"measured total ({measured}) — a shard_map dispatch "
                 "escaped operator attribution")
        # span nesting + attribution args hold on the exported trace
        from spark_tpu.obs.tracing import to_chrome_trace

        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            _json.dump(to_chrome_trace(session.tracer.spans(),
                                       process_name="mesh-gate"), f)
            path = f.name
        validate_trace(path)
        os.unlink(path)
        donated = [k for k in KC._cache
                   if k and k[0] == "mesh_stage" and k[-1] is True]
        if not donated:
            fail("--mesh: no mesh stage program compiled with donated "
                 "send buffers (donate_argnums)")
        from spark_tpu.obs.resources import GLOBAL_LEDGER

        issues = GLOBAL_LEDGER.verify()
        if issues:
            fail("--mesh: device ledger failed verification after the "
                 "donated stage: " + "; ".join(issues))
        print("validate_trace: mesh gate OK — "
              f"{report.measured.get('mesh_stage')} mesh_stage "
              f"dispatch(es) predicted exactly, {attributed} launches "
              "attributed, ledger balanced")
    finally:
        session.stop()


def encoded_gate() -> None:
    """Compressed-execution drift gate (--encoded): a dictionary-heavy
    string-keyed repartition + group-by must (1) produce byte-identical
    results encoded vs decoded (spark.tpu.encoding.enabled differential),
    (2) predict its launch counts EXACTLY on the encoded path — dense-on-
    codes aggregation with ZERO krange3 probes, fused string pids —
    fusion on AND off, and (3) show zero unexplained EXPLAIN ANALYZE
    drift. Self-contained: no trace path required."""
    import numpy as np
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu import TpuSession
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    session = TpuSession("encoded-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 5,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.ui.operatorMetrics": "true",
    })
    try:
        rng = np.random.default_rng(31)
        n = 6000
        session.createDataFrame(pa.table({
            "s": [None if i % 29 == 0 else f"cat{i % 23}"
                  for i in range(n)],
            "v": rng.integers(-20, 80, n),
        })).createOrReplaceTempView("enc_gate_t")

        def q():
            return (session.sql("select s, v from enc_gate_t where v > 0")
                    .repartition(5, "s").groupBy("s")
                    .agg(F.sum("v").alias("sv")))

        outs = {}
        for flag in ("true", "false"):
            session.conf.set("spark.tpu.encoding.enabled", flag)
            outs[flag] = (q().toPandas().sort_values("s", na_position="last")
                          .reset_index(drop=True))
        session.conf.unset("spark.tpu.encoding.enabled")
        if not outs["true"].equals(outs["false"]):
            fail("--encoded: encoded results differ from the decoded "
                 "oracle (dictionary-native kernels changed answers)")

        for fusion in ("true", "false"):
            session.conf.set("spark.tpu.fusion.enabled", fusion)
            report = q().query_execution.analysis_report()
            if not report.exact:
                fail(f"--encoded: plan not exactly predicted (fusion="
                     f"{fusion}): {report.inexact_reasons}")
            if report.predicted_launches.get("krange3"):
                fail("--encoded: dictionary grouping key predicted a "
                     "krange3 probe — the code-domain decision regressed")
            q().toArrow()  # warm
            before = dict(KC.launches_by_kind)
            q().toArrow()
            measured = {k: v - before.get(k, 0)
                        for k, v in KC.launches_by_kind.items()
                        if v != before.get(k, 0)}
            if report.predicted_launches != measured:
                fail(f"--encoded: predicted {report.predicted_launches} "
                     f"!= measured {measured} (fusion={fusion})")
            if measured.get("gagg"):
                fail("--encoded: string group-by took the sort path "
                     f"(fusion={fusion}): {measured} — dense-on-codes "
                     "regressed")
        session.conf.unset("spark.tpu.fusion.enabled")

        report = q().query_execution.analyzed_report()
        errors = [f for f in report.findings if f["severity"] == "error"]
        if errors:
            print(report.render())
            fail("--encoded: EXPLAIN ANALYZE reported unexplained drift "
                 "on the encoded path: "
                 + "; ".join(f["msg"] for f in errors))
        print("validate_trace: encoded gate OK — encoded == decoded, "
              f"{sum(report.measured.values())} launches predicted "
              "exactly fusion on/off, 0 krange3 probes on the "
              "dictionary key")
    finally:
        session.stop()


def adaptive_gate() -> None:
    """Runtime-adaptive execution gate (--adaptive): a selective shuffled
    hash join (2000-key probe ⋈ [5,6,7] build) must (1) produce results
    identical with spark.tpu.adaptive.runtimeFilter on vs off — the
    differential identity, (2) install at least one runtime join filter
    that prunes probe rows before the shuffle, with the install event
    visible in the trace (adaptive.runtime_filter span), (3) degrade the
    launch model HONESTLY (exact=False with a named runtimeFilter
    reason) and show zero unexplained EXPLAIN ANALYZE drift with the
    adaptive layer armed, and (4) leave the device ledger balanced.
    Self-contained: no trace path required."""
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu import TpuSession
    from spark_tpu.obs.resources import GLOBAL_LEDGER

    session = TpuSession("adaptive-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 4,
        "spark.sql.autoBroadcastJoinThreshold": -1,
        "spark.tpu.ui.operatorMetrics": "true",
    })
    try:
        def q():
            a = session.createDataFrame(pa.table({
                "k": list(range(2000)),
                "v": list(range(2000))})).repartition(4)
            b = session.createDataFrame(pa.table({
                "k": [5, 6, 7], "w": [50, 60, 70]})).repartition(2)
            return (a.join(b, on="k").groupBy("k")
                    .agg(F.sum("v").alias("sv")).orderBy("k"))

        outs = {}
        for flag in ("false", "true"):
            session.conf.set("spark.tpu.adaptive.runtimeFilter", flag)
            outs[flag] = q().toArrow().to_pydict()
        if outs["true"] != outs["false"]:
            fail("--adaptive: results differ with the runtime filter on "
                 "vs off (probe pruning changed answers)")

        c = session._metrics.snapshot()["counters"]
        if not c.get("adaptive.runtime_filters_installed"):
            fail("--adaptive: no runtime filter installed on the "
                 "selective join (harvest/install path regressed)")
        if not c.get("adaptive.filter_rows_pruned"):
            fail("--adaptive: filter installed but zero probe rows "
                 "pruned (the exchange never applied it)")
        rf_spans = [s for s in session.tracer.spans()
                    if s and s[0] == "adaptive.runtime_filter"]
        if not rf_spans:
            fail("--adaptive: filter install not visible in the trace "
                 "(no adaptive.runtime_filter span)")

        # the launch model must degrade honestly, not silently: armed
        # adaptive execution is a named inexactness, and EXPLAIN ANALYZE
        # reconciliation must classify the drift rather than error
        report = q().query_execution.analysis_report()
        if report.exact:
            fail("--adaptive: plan_lint claims exact launch counts with "
                 "the runtime filter armed — the model is lying")
        if not any("runtimeFilter" in r for r in report.inexact_reasons):
            fail("--adaptive: inexactness lacks a named runtimeFilter "
                 f"reason: {report.inexact_reasons}")
        report = q().query_execution.analyzed_report()
        errors = [f for f in report.findings if f["severity"] == "error"]
        if errors:
            print(report.render())
            fail("--adaptive: EXPLAIN ANALYZE reported unexplained drift "
                 "with the adaptive layer armed: "
                 + "; ".join(f["msg"] for f in errors))
        session.conf.unset("spark.tpu.adaptive.runtimeFilter")

        issues = GLOBAL_LEDGER.verify()
        if issues:
            fail("--adaptive: device ledger failed verification after "
                 "the adaptive run — " + "; ".join(issues))
        print("validate_trace: adaptive gate OK — on == off, "
              f"{c.get('adaptive.filter_rows_pruned')} probe rows pruned "
              f"by {c.get('adaptive.runtime_filters_installed')} "
              "filter(s), drift classified, ledger balanced")
    finally:
        session.stop()


def whole_query_gate() -> None:
    """Whole-query compilation gate (--whole-query): a q3-shaped star
    join + group-by under spark.tpu.compile.tier=whole must (1) produce
    results identical to the per-stage and operator tiers, (2) execute
    as EXACTLY the predicted whole_query dispatch count per step (one
    plus any predicted join-capacity retries; zero per-stage kernels of
    any kind), (3) show zero unexplained EXPLAIN ANALYZE drift, and (4)
    surface the tier decision in the analysis report (tier + reason).
    Self-contained: no trace path required."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    session = TpuSession("whole-query-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 5,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.ui.operatorMetrics": "true",
    })
    try:
        rng = np.random.default_rng(41)
        n, nd = 9000, 700
        session.createDataFrame(pa.table({
            "date_sk": rng.integers(0, nd, n),
            "item_sk": rng.integers(0, nd, n),
            "price": rng.integers(0, 1000, n),
        })).createOrReplaceTempView("wqg_fact")
        session.createDataFrame(pa.table({
            "d_date_sk": np.arange(nd, dtype=np.int64),
            "d_year": (1998 + np.arange(nd) // 366),
            "d_moy": (1 + np.arange(nd) % 12),
        })).createOrReplaceTempView("wqg_dates")
        session.createDataFrame(pa.table({
            "i_item_sk": np.arange(nd, dtype=np.int64),
            "i_brand_id": (np.arange(nd) % 37),
            "i_manufact_id": (np.arange(nd) % 50),
        })).createOrReplaceTempView("wqg_items")
        sql = ("select d_year, i_brand_id, sum(price) s from wqg_fact "
               "join wqg_dates on date_sk = d_date_sk "
               "join wqg_items on item_sk = i_item_sk "
               "where d_moy = 11 and i_manufact_id = 28 "
               "group by d_year, i_brand_id")

        def q():
            return session.sql(sql)

        outs = {}
        for tier in ("whole", "stage", "operator"):
            session.conf.set("spark.tpu.compile.tier", tier)
            outs[tier] = (q().toPandas()
                          .sort_values(["d_year", "i_brand_id"])
                          .reset_index(drop=True))
        for tier in ("stage", "operator"):
            if not outs["whole"].equals(outs[tier]):
                fail(f"--whole-query: whole-tier results differ from the "
                     f"{tier} tier (in-program lowering changed answers)")

        session.conf.set("spark.tpu.compile.tier", "whole")
        report = q().query_execution.analysis_report()
        if not report.exact:
            fail("--whole-query: whole tier not exactly predicted: "
                 f"{report.inexact_reasons}")
        if (report.tier or {}).get("tier") != "whole":
            fail("--whole-query: tier decision missing from the analysis "
                 f"report: {report.tier}")
        expected = report.predicted_launches
        if set(expected) != {"whole_query"}:
            fail(f"--whole-query: predicted kinds {expected} — per-stage "
                 "kernels leaked into the whole-query program")
        q().toArrow()  # warm
        before = dict(KC.launches_by_kind)
        q().toArrow()
        measured = {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}
        if measured != expected:
            fail(f"--whole-query: measured {measured} != predicted "
                 f"{expected} — the one-dispatch-per-step guarantee "
                 "regressed")

        # the tier decision rides the execution span (obs contract)
        tier_spans = [s for s in session.tracer.spans()
                      if s and s[0] == "whole_query.program"
                      and (s[6] or {}).get("tier") == "whole"]
        if not tier_spans:
            fail("--whole-query: tier decision not visible in spans "
                 "(no whole_query.program span with args.tier=whole)")

        report = q().query_execution.analyzed_report()
        errors = [f for f in report.findings if f["severity"] == "error"]
        if errors:
            print(report.render())
            fail("--whole-query: EXPLAIN ANALYZE reported unexplained "
                 "drift under the whole tier: "
                 + "; ".join(f["msg"] for f in errors))
        session.conf.unset("spark.tpu.compile.tier")
        print("validate_trace: whole-query gate OK — 3 tiers agree, "
              f"{sum(expected.values())} dispatch(es) per step predicted "
              "exactly, tier decision surfaced, zero drift")
    finally:
        session.stop()


def mesh_whole_gate() -> None:
    """Mesh whole-query gate (--mesh-whole, virtual 8-device CPU mesh):
    the ENTIRE sharded join+agg plan — leaves, in-program all-to-alls,
    join build+probe, partial and final aggregate — must execute as ONE
    shard_map dispatch per step under spark.tpu.compile.tier=mesh-whole,
    with (1) results identical to the whole and stage tiers, (2) the
    mesh_whole launch count predicted EXACTLY by plan_lint including a
    quota-doubling retry round on a skewed key, (3) the tier decision
    surfaced on the report and the execution span, and (4) the device
    ledger balanced. Self-contained: no trace path required."""
    import jax
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    if len(jax.devices()) < 4:
        fail("--mesh-whole: needs >=4 virtual devices (run with "
             "JAX_PLATFORMS=cpu "
             "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    session = TpuSession("mesh-whole-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.fusion.minRows": "0",
    })
    try:
        rng = np.random.default_rng(41)
        n, nd = 9000, 700
        session.createDataFrame(pa.table({
            "item_sk": rng.integers(0, nd, n),
            "price": rng.integers(0, 1000, n),
        })).createOrReplaceTempView("mwg_fact")
        session.createDataFrame(pa.table({
            "i_item_sk": np.arange(nd, dtype=np.int64),
            "i_brand_id": (np.arange(nd) % 37),
        })).createOrReplaceTempView("mwg_items")

        def q():
            return (session.sql(
                "select item_sk, price, i_brand_id from mwg_fact "
                "join mwg_items on item_sk = i_item_sk "
                "where price > 100")
                .repartition(4, "i_brand_id")
                .groupBy("i_brand_id").count())

        outs = {}
        for tier in ("mesh-whole", "whole", "stage"):
            session.conf.set("spark.tpu.compile.tier", tier)
            outs[tier] = (q().toPandas().sort_values("i_brand_id")
                          .reset_index(drop=True))
        for tier in ("whole", "stage"):
            if not outs["mesh-whole"].equals(outs[tier]):
                fail(f"--mesh-whole: mesh-tier results differ from the "
                     f"{tier} tier (sharded lowering changed answers)")

        session.conf.set("spark.tpu.compile.tier", "mesh-whole")
        report = q().query_execution.analysis_report()
        if not report.exact:
            fail("--mesh-whole: mesh tier not exactly predicted: "
                 f"{report.inexact_reasons}")
        if (report.tier or {}).get("tier") != "mesh-whole":
            fail("--mesh-whole: tier decision missing from the analysis "
                 f"report: {report.tier}")
        expected = report.predicted_launches
        if set(expected) != {"mesh_whole"}:
            fail(f"--mesh-whole: predicted kinds {expected} — per-stage "
                 "kernels leaked out of the single sharded program")
        q().toArrow()  # warm
        before = dict(KC.launches_by_kind)
        q().toArrow()
        measured = {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}
        if measured != expected:
            fail(f"--mesh-whole: measured {measured} != predicted "
                 f"{expected} — the one-dispatch-per-step guarantee "
                 "regressed")

        # skewed key: one destination shard overflows its exchange quota
        # — the in-program overflow scalar doubles it and the WHOLE
        # program re-dispatches, and the analyzer mirrors the round
        skew = np.zeros(4000, dtype=np.int64)
        skew[:32] = np.arange(32)
        session.createDataFrame(pa.table({
            "sk": skew, "sv": np.arange(4000),
        })).createOrReplaceTempView("mwg_skew")

        def qs():
            return (session.sql("select * from mwg_skew")
                    .repartition(4, "sk").groupBy("sk").count())

        rep_s = qs().query_execution.analysis_report()
        if rep_s.predicted_launches.get("mesh_whole", 0) < 2:
            fail("--mesh-whole: the analyzer never predicted the skew "
                 f"quota-retry round: {rep_s.predicted_launches}")
        qs().toArrow()  # warm (retry rounds recur per fresh execution)
        before = dict(KC.launches_by_kind)
        qs().toArrow()
        measured = {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}
        if measured != rep_s.predicted_launches:
            fail(f"--mesh-whole: skew retry measured {measured} != "
                 f"predicted {rep_s.predicted_launches}")

        tier_spans = [s for s in session.tracer.spans()
                      if s and s[0] == "whole_query.program"
                      and (s[6] or {}).get("tier") == "mesh-whole"]
        if not tier_spans:
            fail("--mesh-whole: tier decision not visible in spans (no "
                 "whole_query.program span with args.tier=mesh-whole)")
        bad = GLOBAL_LEDGER.verify()
        if bad:
            fail("--mesh-whole: device ledger failed verification after "
                 f"the mesh whole-query runs: {bad[:3]}")
        session.conf.unset("spark.tpu.compile.tier")
        print("validate_trace: mesh-whole gate OK — 3 tiers agree, "
              f"{sum(expected.values())} sharded dispatch(es) per step "
              "and the skew retry round predicted exactly, ledger "
              "balanced")
    finally:
        session.stop()


def chaos_gate() -> None:
    """Chaos gate (--chaos, self-contained, fixed seed): deterministic
    fault injection through the regular conf surface must always
    TERMINATE — every injected fault class ends in a correct query
    result or a CLASSIFIED error under a watchdog timeout, never a
    hang. Scenarios: (1) transient block-fetch flap absorbed by the
    bounded fetch retry with ZERO stage regenerations; (2) fetch-retry
    budget exhausted → FetchFailed regeneration still correct, and an
    unbounded failure stream terminates in StageRegenerationLimitError
    with zero leaked shuffle blocks on any worker; (3) transient
    worker-task fault retried on another executor with per-operator
    kernel attribution still equal to driver+worker measured totals
    AFTER the failover; (4) a whole-tier runtime dispatch fault
    degrading to the stage tier with identical results. The device
    ledger must verify balanced at the end."""
    import pickle
    import threading

    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.errors import StageRegenerationLimitError
    from spark_tpu.net.transport import RpcClient
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.utils import faults

    def watchdog(name, fn, timeout_s=120.0):
        """Every injected fault must terminate — run the scenario under
        a hard wall-clock bound so a hang fails the gate instead of
        wedging CI."""
        out: dict = {}

        def run():
            try:
                out["result"] = fn()
            except BaseException as e:   # re-raised on the gate thread
                out["error"] = e

        t = threading.Thread(target=run, daemon=True, name=f"chaos-{name}")
        t.start()
        t.join(timeout_s)
        if t.is_alive():
            fail(f"--chaos: scenario {name!r} HUNG past {timeout_s}s "
                 "(injected faults must terminate in a result or a "
                 "classified error)")
        if "error" in out:
            raise out["error"]
        return out.get("result")

    session = TpuSession("chaos-gate", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
    })
    try:
        rng = np.random.default_rng(7)    # fixed seed end to end
        keys = rng.integers(0, 24, 5000)
        vals = rng.integers(-40, 90, 5000)
        session.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("cg_t")
        rows = sorted(zip(keys.tolist(), vals.tolist()))

        def set_faults(points):
            session.conf.set("spark.tpu.faults.enabled", "true")
            session.conf.set("spark.tpu.faults.seed", "7")
            session.conf.set("spark.tpu.faults.points", points)
            faults.configure(session.conf)

        def clear_faults():
            session.conf.set("spark.tpu.faults.enabled", "false")
            session.conf.unset("spark.tpu.faults.points")
            faults.configure(session.conf)

        def counters():
            return dict(session._metrics.snapshot()["counters"])

        def shuffle_q():
            return session.table("cg_t").repartition(2)

        def check_rows(df):
            got = sorted((r["k"], r["v"]) for r in df.collect())
            if got != rows:
                fail("--chaos: faulted query returned WRONG rows")

        def scenario_flap():
            set_faults("block.fetch=first:2")
            before = counters()
            check_rows(shuffle_q())
            after = counters()
            clear_faults()
            regens = after.get("scheduler.fetch_failures", 0) \
                - before.get("scheduler.fetch_failures", 0)
            if regens != 0:
                fail(f"--chaos: transient fetch flap cost {regens} stage "
                     "regeneration(s) — the bounded retry did not absorb")
            retries = after.get("shuffle.fetch_retries", 0) \
                - before.get("shuffle.fetch_retries", 0)
            if retries < 1:
                fail("--chaos: fetch flap injected but no retry recorded")

        def scenario_regen_and_cap():
            session.conf.set("spark.tpu.shuffle.fetch.maxRetries", "0")
            set_faults("block.fetch=first:1")
            check_rows(shuffle_q())          # regen path still correct
            session.conf.set("spark.tpu.scheduler.maxStageRegens", "1")
            session.conf.set("spark.tpu.excludeOnFailure.maxFailures",
                             "100")
            set_faults("block.fetch=first:1000")
            try:
                shuffle_q().toArrow()
                fail("--chaos: unbounded fetch failures did NOT raise "
                     "the classified regen-limit error")
            except StageRegenerationLimitError as e:
                if e.error_class != "STAGE_REGENERATION_LIMIT":
                    fail(f"--chaos: wrong error class {e.error_class}")
            finally:
                session.conf.unset("spark.tpu.shuffle.fetch.maxRetries")
                session.conf.unset("spark.tpu.scheduler.maxStageRegens")
                session.conf.unset(
                    "spark.tpu.excludeOnFailure.maxFailures")
                clear_faults()
                session._sql_cluster.health.reset()
            cluster = session._sql_cluster
            for w in cluster.alive_workers():
                with RpcClient(w.client.addr, cluster.authkey_hex) as c:
                    stats = pickle.loads(c.call("block_stats", timeout=10))
                if stats["blocks"]:
                    fail(f"--chaos: failed query leaked {stats['blocks']} "
                         f"shuffle block(s) on {w.executor_id}")

        def scenario_failover_attribution():
            check_rows(shuffle_q())          # warm
            set_faults("worker.task=once")
            before = KC.launches
            df = shuffle_q()
            check_rows(df)
            driver_delta = KC.launches - before
            clear_faults()
            session._sql_cluster.health.reset()
            ctx = df.query_execution._last_ctx
            worker = sum((ctx.worker_kernel_kinds or {}).values())
            graph = df.query_execution.plan_graph()
            attributed = sum(v for nd in graph
                             for v in (nd.get("launches") or {}).values())
            if attributed != driver_delta + worker:
                fail("--chaos: attribution total after failover "
                     f"({attributed}) != driver+worker measured "
                     f"({driver_delta}+{worker})")

        def scenario_tier_degrade():
            local = TpuSession("chaos-gate-local", {
                "spark.sql.shuffle.partitions": "2",
                "spark.tpu.batch.capacity": 1 << 12,
                "spark.sql.adaptive.enabled": "false",
                "spark.tpu.compile.tier": "whole",
            })
            try:
                local.createDataFrame(pa.table({"k": keys, "v": vals})) \
                    .createOrReplaceTempView("cg_t")
                import spark_tpu.api.functions as F

                def q():
                    return (local.table("cg_t").repartition(2)
                            .groupBy("k").agg(F.sum("v").alias("s")))

                healthy = {r["k"]: r["s"] for r in q().collect()}
                local.conf.set("spark.tpu.faults.enabled", "true")
                local.conf.set("spark.tpu.faults.points",
                               "kernel.dispatch=once@whole_query")
                faults.configure(local.conf)
                before = dict(local._metrics.snapshot()["counters"])
                degraded = {r["k"]: r["s"] for r in q().collect()}
                after = dict(local._metrics.snapshot()["counters"])
                if degraded != healthy:
                    fail("--chaos: tier-degraded run returned different "
                         "results from the whole-tier run")
                d = after.get("whole_query.runtime_degraded", 0) \
                    - before.get("whole_query.runtime_degraded", 0)
                if d != 1:
                    fail("--chaos: whole-tier dispatch fault did not "
                         f"degrade to the stage tier (counter delta {d})")
            finally:
                faults.reset()
                local.stop()

        watchdog("flap", scenario_flap)
        watchdog("regen+cap", scenario_regen_and_cap)
        watchdog("failover-attribution", scenario_failover_attribution)
        watchdog("tier-degrade", scenario_tier_degrade)
        issues = GLOBAL_LEDGER.verify()
        if issues:
            fail("--chaos: device ledger unbalanced after chaos run: "
                 + "; ".join(issues))
        print("validate_trace: chaos gate OK — flap absorbed with 0 "
              "regens, regen limit classified + state freed, failover "
              "attribution intact, whole→stage degrade identical, "
              "ledger balanced")
    finally:
        faults.reset()
        session.stop()


def profile_gate() -> None:
    """Query flight recorder gate (--profile, self-contained): the
    fingerprint/store/regression loop must hold end to end. Two
    identical runs ⇒ one fingerprint, two stored profiles, zero
    obs.regression findings (warm runs never regress against their own
    cold baseline); a forced tier flip ⇒ same structural query key,
    different fingerprint, and a severity-error deterministic-counter
    regression finding in both the close hook and the live store; and
    dev/perfcheck.py's comparator flags the same delta against a
    baseline built from the healthy profiles."""
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs.history import ProfileStore

    tmp = tempfile.mkdtemp(prefix="profile_gate_")
    session = TpuSession("profile-gate", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.obs.profileDir": tmp,
    })
    try:
        rng = np.random.default_rng(13)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 9, 4000),
            "v": rng.integers(-20, 80, 4000),
        })).createOrReplaceTempView("pg_t")

        def q():
            return session.sql("select k, sum(v) s from pg_t "
                               "where v > 0 group by k")

        first = q()
        first.toArrow()
        second = q()
        second.toArrow()
        qe = second.query_execution
        if qe._last_profile is None:
            fail("--profile: flight recorder never recorded a profile")
        store = ProfileStore(tmp)
        qk = qe._last_profile["query_key"]
        profs = store.profiles(qk)
        if len(profs) != 2:
            fail(f"--profile: expected 2 stored profiles for the query "
                 f"key, found {len(profs)}")
        fps = {p["fingerprint"] for p in profs}
        if len(fps) != 1:
            fail(f"--profile: identical runs produced {len(fps)} distinct "
                 f"fingerprints ({fps}) — canonicalization is unstable")
        if qe._last_regressions:
            fail("--profile: identical re-run raised regression findings: "
                 + "; ".join(f["msg"] for f in qe._last_regressions))
        # perfcheck comparator: healthy baseline vs itself must be clean
        import importlib.util as _ilu

        spec = _ilu.spec_from_file_location(
            "perfcheck", os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "perfcheck.py"))
        perfcheck = _ilu.module_from_spec(spec)
        spec.loader.exec_module(perfcheck)
        healthy = perfcheck.collect_profiles(tmp)
        regs, _notes = perfcheck.compare(healthy, {"queries": healthy})
        if regs:
            fail("--profile: perfcheck flagged a healthy run against its "
                 "own baseline: " + "; ".join(regs))
        # forced tier flip: same query key, new fingerprint, counter
        # drift detected as a severity-error finding
        session.conf.set("spark.tpu.compile.tier", "operator")
        flipped = q()
        flipped.toArrow()
        session.conf.unset("spark.tpu.compile.tier")
        fqe = flipped.query_execution
        fprof = fqe._last_profile
        if fprof["query_key"] != qk:
            fail("--profile: tier flip changed the structural query key — "
                 "regression detection lost its baseline")
        if fprof["fingerprint"] in fps:
            fail("--profile: tier flip did NOT change the full plan "
                 "fingerprint (compile-cache key is tier-blind)")
        errors = [f for f in fqe._last_regressions
                  if f["severity"] == "error"]
        if not errors:
            fail("--profile: forced tier flip raised no deterministic-"
                 f"counter regression (findings: {fqe._last_regressions})")
        live = session.live_obs.findings_for(
            fqe._last_ctx.query_id)
        if not any(f.get("kind") == "obs.regression" for f in live):
            fail("--profile: regression finding never reached the live "
                 "store (EXPLAIN ANALYZE/live status would miss it)")
        # the same delta must trip perfcheck's cross-commit comparator
        flipped_counters = perfcheck.collect_profiles(tmp)
        regs, _notes = perfcheck.compare(flipped_counters,
                                         {"queries": healthy})
        if not regs:
            fail("--profile: perfcheck comparator missed the tier-flip "
                 "counter delta")
        print("validate_trace: profile gate OK — 1 fingerprint / 2 "
              "profiles / 0 regressions on identical runs; tier flip "
              f"kept query key, changed fingerprint, raised {len(errors)} "
              f"error finding(s) and {len(regs)} perfcheck regression(s)")
    finally:
        session.stop()


# one persist-gate child leg: runs in a REAL subprocess (the warm
# restart must be a fresh process) against the shared cache dir passed
# as argv[1]. Prints one PERSIST json line the parent asserts on.
_PERSIST_LEG = r'''
import json, os, sys
import numpy as np, pyarrow as pa

cache = sys.argv[1]
from spark_tpu import TpuSession
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
import spark_tpu.exec.persist_cache as pc

session = TpuSession("persist-gate", {
    "spark.tpu.cache.dir": cache,
    "spark.tpu.cache.result.enabled": "false",
    "spark.sql.shuffle.partitions": 2,
    "spark.tpu.batch.capacity": 1 << 12,
    "spark.tpu.fusion.minRows": "0",
    "spark.sql.adaptive.enabled": "false",
    "spark.tpu.obs.profileDir": os.path.join(cache, "profiles"),
})
rng = np.random.default_rng(21)
session.createDataFrame(pa.table({
    "k": rng.integers(0, 9, 4000), "v": rng.integers(-50, 90, 4000),
})).createOrReplaceTempView("pg")
session.createDataFrame(pa.table({
    "k": np.repeat(np.arange(9), 3), "tag": np.arange(27),
})).createOrReplaceTempView("pg_dim")

# leg 1 — compile-cache proof (result cache OFF so queries execute).
# The FIRST run is the one that compiles (and, warm, hits disk): its
# profile must carry the disk-hit attribution.
q = lambda: session.sql(
    "select k, sum(v) s, count(*) c from pg where v > 0 group by k")
df1 = q()
out1 = df1.toArrow()
fp = df1.query_execution.plan_fingerprint()["fingerprint"]
prof = df1.query_execution._last_profile or {}

# leg 2 — whole-tier capacity-retry seeding: the 3x-expanding join
# overflows its output bucket cold; a warm restart's manifest seed must
# collapse the retry (1 dispatch, 0 capacity retries)
session.conf.set("spark.tpu.compile.tier", "whole")
jq = lambda: session.sql(
    "select p.k, count(*) n from pg p join pg_dim d on p.k = d.k "
    "group by p.k")
jrep = jq().query_execution.analysis_report()
c0 = dict(session._metrics.snapshot()["counters"])
jout = jq().toArrow()
c1 = dict(session._metrics.snapshot()["counters"])
session.conf.unset("spark.tpu.compile.tier")
wq = {"predicted": jrep.predicted_launches.get("whole_query"),
      "exact": jrep.exact,
      "dispatches": c1.get("whole_query.dispatches", 0)
      - c0.get("whole_query.dispatches", 0),
      "retries": c1.get("whole_query.capacity_retries", 0)
      - c0.get("whole_query.capacity_retries", 0),
      "rows": jout.num_rows}

# leg 3 — result cache: populate, then the analyzer must predict the
# zero-launch hit path exactly and the repeat must launch nothing
session.conf.set("spark.tpu.cache.result.enabled", "true")
a1 = q().toArrow()
rep = q().query_execution.analysis_report()
l0 = KC.launches
a2 = q().toArrow()
counters = session._metrics.snapshot()["counters"]
print("PERSIST " + json.dumps({
    "fingerprint": fp,
    "compiles": KC.misses,
    "disk_hit_compiles": KC.disk_hit_compiles,
    "disk": pc.disk_counters(),
    "profile_compiles": prof.get("compiles"),
    "profile_disk_hit": prof.get("compiles_disk_hit"),
    "profile_counters": prof.get("counters") or {},
    "wq": wq,
    "rc_predicted": rep.predicted_launches,
    "rc_exact": rep.exact,
    "rc_repeat_launches": KC.launches - l0,
    "rc_hits": int(counters.get("result_cache.hit", 0)),
    "rc_equal": a1.equals(a2),
    "rows": out1.num_rows,
}), flush=True)
'''


def persist_gate() -> None:
    """Persistent-cache gate (--persist, self-contained): the warm-
    restart story must hold across two REAL processes sharing one
    spark.tpu.cache.dir. Cold leg: XLA disk misses populate the cache,
    the whole-tier join pays its capacity retry, the result cache
    populates and answers the repeat with zero launches (plan_lint
    predicting the hit path exactly). Warm leg (fresh process): the
    SAME fingerprints resolve (stability across processes), ZERO XLA
    disk misses with every engine compile disk-served (per-query
    profiles attribute disk-hit vs cold), the manifest seed collapses
    the whole-tier capacity retry to one dispatch (plan_lint mirroring
    the seeded prediction), and the result cache hits cross-process."""
    import subprocess
    import tempfile

    cache = tempfile.mkdtemp(prefix="persist_gate_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def leg(name: str) -> dict:
        proc = subprocess.run(
            [sys.executable, "-c", _PERSIST_LEG, cache],
            env=env, cwd=root, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, timeout=600)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("PERSIST ")]
        if proc.returncode != 0 or not lines:
            fail(f"--persist: {name} leg failed rc={proc.returncode}: "
                 f"{proc.stderr[-800:]}")
        return json.loads(lines[-1][len("PERSIST "):])

    cold = leg("cold")
    # cold-leg invariants: disk cache populated, retry paid, result
    # cache exact on the hit path
    if cold["disk"]["compile.disk_miss"] < 1:
        fail("--persist: cold leg recorded no XLA disk-cache misses — "
             "the persistent compile cache never engaged")
    if cold["wq"]["retries"] < 1 or cold["wq"]["dispatches"] < 2:
        fail(f"--persist: cold whole-tier join did not pay a capacity "
             f"retry ({cold['wq']}) — the warm-start seed has nothing "
             "to prove")
    if cold["wq"]["predicted"] != cold["wq"]["dispatches"] \
            or not cold["wq"]["exact"]:
        fail(f"--persist: cold whole-query prediction "
             f"{cold['wq']['predicted']} != measured dispatches "
             f"{cold['wq']['dispatches']}")
    for c in (cold,):
        if c["rc_predicted"] != {} or not c["rc_exact"]:
            fail(f"--persist: plan_lint did not predict the zero-launch "
                 f"result-cache hit path ({c['rc_predicted']})")
        if c["rc_repeat_launches"] != 0:
            fail(f"--persist: repeated query launched "
                 f"{c['rc_repeat_launches']} kernels through the result "
                 "cache")
        if not c["rc_equal"]:
            fail("--persist: result-cache answer differs from the "
                 "executed answer")
    warm = leg("warm")
    if warm["fingerprint"] != cold["fingerprint"]:
        fail("--persist: plan fingerprint is not stable across "
             f"processes ({cold['fingerprint']} vs "
             f"{warm['fingerprint']}) — every persistent key is dead")
    if warm["disk"]["compile.disk_miss"] != 0:
        fail(f"--persist: warm restart paid "
             f"{warm['disk']['compile.disk_miss']} TRUE cold XLA "
             "compile(s) — the persistent compile cache missed")
    if warm["disk"]["compile.disk_hit"] < 1:
        fail("--persist: warm restart recorded no XLA disk-cache hits")
    if warm["disk_hit_compiles"] < 1:
        fail("--persist: KernelCache attributed no disk-served compiles "
             "on the warm restart")
    if warm["profile_disk_hit"] is None \
            or warm["profile_disk_hit"] < 1 \
            or not any(k == "compile.disk_hit"
                       for k in warm["profile_counters"]):
        fail("--persist: the warm query profile does not attribute "
             f"disk-hit compiles ({warm['profile_disk_hit']}, "
             f"{sorted(warm['profile_counters'])})")
    if warm["wq"]["retries"] != 0 or warm["wq"]["dispatches"] != 1:
        fail(f"--persist: warm whole-tier join replayed the capacity "
             f"ladder ({warm['wq']}) — the manifest seed did not take")
    if warm["wq"]["predicted"] != 1 or not warm["wq"]["exact"]:
        fail(f"--persist: plan_lint did not mirror the seeded "
             f"whole-query attempt count ({warm['wq']['predicted']})")
    if warm["rc_hits"] < 1 or warm["rc_repeat_launches"] != 0 \
            or not warm["rc_equal"]:
        fail(f"--persist: cross-process result-cache hit failed "
             f"(hits={warm['rc_hits']}, "
             f"launches={warm['rc_repeat_launches']})")
    print("validate_trace: persist gate OK — fingerprints stable across "
          f"processes; warm restart: 0 true cold XLA compiles "
          f"({warm['disk']['compile.disk_hit']} disk hits, "
          f"{warm['disk_hit_compiles']} kernels attributed), capacity "
          "retry collapsed 2→1 dispatches via the manifest seed, "
          "repeated query answered with 0 launches (predicted exactly)")


def serve_gate() -> None:
    """Serving gate (--serve, self-contained): deterministic weighted
    fairness, HBM admission, a real concurrent load with scope-exact
    attribution, and graceful drain (see module docstring #10)."""
    import tempfile
    import threading
    import time

    from spark_tpu import TpuSession
    from spark_tpu.config import SQLConf
    from spark_tpu.errors import (
        AdmissionTimeout, ServerDraining,
    )
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.obs.resources import GLOBAL_LEDGER, MemoryBudgetExceeded
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import FairScheduler, QueryService
    from spark_tpu.serve.loadgen import run_serve_load

    # -- 1: deterministic weighted fairness (no timing, no threads) ------
    conf = SQLConf({"spark.tpu.scheduler.pools": "a:2,b:1",
                    "spark.tpu.serve.maxConcurrent": 1})
    sched = FairScheduler(conf)
    tickets = []
    for _ in range(12):
        tickets.append(sched.submit("a"))
        tickets.append(sched.submit("b"))
    for _ in range(len(tickets)):
        running = [t for t in tickets if t.granted and not t.released]
        if len(running) != 1:
            fail(f"--serve: maxConcurrent=1 but {len(running)} tickets "
                 "hold slots")
        sched.release(running[0])
    grants = sched.contended_grants()
    if grants.get("a", 0) + grants.get("b", 0) < 12:
        fail(f"--serve: too few contended grants to judge fairness "
             f"({grants})")
    ratio = sched.fairness_ratio()
    if ratio is None or ratio > 1.20:
        fail(f"--serve: deterministic stride fairness broken — "
             f"contended grants {grants} (weights 2:1), "
             f"normalized ratio {ratio}")
    if not sched.balanced():
        fail("--serve: scheduler ledger unbalanced after the "
             "deterministic schedule drained")

    # -- 2: HBM admission — reservation blocks, release unblocks ---------
    conf = SQLConf({"spark.tpu.memory.budget": 100})
    sched = FairScheduler(conf)
    big = sched.submit("default", hbm=70)
    sched.wait(big, timeout=1.0)
    small = sched.submit("default", hbm=50)
    try:
        sched.wait(small, timeout=0.05)
        fail("--serve: 50B reservation admitted next to 70B in-flight "
             "under a 100B budget")
    except AdmissionTimeout:
        pass
    small = sched.submit("default", hbm=50)
    sched.release(big)
    sched.wait(small, timeout=1.0)
    sched.release(small)
    if not sched.balanced():
        fail("--serve: HBM reservations leaked through the "
             "admit/timeout/release cycle")

    # -- 3: real concurrent load (8 cloned sessions, 2 pools 2:1) --------
    profile_dir = tempfile.mkdtemp(prefix="serve_gate_prof_")
    session = TpuSession("serve-gate", {
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.obs.profileDir": profile_dir,
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.serve.maxConcurrent": 2,
    })
    try:
        import numpy as np
        import pyarrow as pa

        rng = np.random.default_rng(5)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 16, 4000).astype(np.int64),
            "v": rng.integers(-50, 150, 4000).astype(np.int64),
        })).createOrReplaceTempView("serve_gate_t")
        service = QueryService(session)
        launches_before = KC.launches
        report = run_serve_load(
            service,
            ["select k, sum(v) s from serve_gate_t group by k",
             "select k, v from serve_gate_t where v > 0 "
             "order by v limit 16"],
            sessions=8, reps=3, pools=("dash", "batch"))
        if report["errors"]:
            fail(f"--serve: load queries failed: {report['errors']}")
        kc_delta = KC.launches - launches_before
        store = ProfileStore(profile_dir)
        attributed = 0
        overlapped = 0
        for qk in store.query_keys():
            for p in store.profiles(qk):
                attributed += int(p.get("launch_total", 0))
                if p.get("overlapped"):
                    overlapped += 1
        if overlapped:
            fail(f"--serve: {overlapped} profiles marked overlapped — "
                 "scope-exact per-query deltas regressed to the PR 12 "
                 "overlap guard")
        if attributed != kc_delta:
            fail(f"--serve: per-query attributed launch totals "
                 f"({attributed}) != global KernelCache delta "
                 f"({kc_delta}) — the query ledger leaks or double-"
                 "counts under concurrency")
        ratio = report["fairness_ratio"]
        grants = report["contended_grants"]
        total_contended = sum(grants.values()) if grants else 0
        # judge the live-load ratio only on a real contended sample —
        # with few contended grants the ±1 stride rounding dominates
        # (the deterministic schedule above is the exact 2:1 assertion)
        if len(grants) >= 2 and total_contended >= 12:
            if ratio is None or ratio > 1.25:
                fail(f"--serve: contention fairness ratio {ratio} "
                     f"outside 25% of the 2:1 weights ({grants})")
        # -- 4: over-budget plan rejects PLAN-TIME, never queues ---------
        s2 = service.open_session()
        s2.conf.set("spark.tpu.memory.budget", 1024)
        try:
            service.execute_sql(
                s2, "select k, sum(v) s from serve_gate_t group by k")
            fail("--serve: over-budget plan was admitted (expected "
                 "MemoryBudgetExceeded from the plan-time pre-flight)")
        except MemoryBudgetExceeded:
            pass
        # -- 5: graceful drain -------------------------------------------
        slow = service.scheduler.submit("dash")     # a held in-flight slot
        service.scheduler.wait(slow, timeout=1.0)
        done = {"v": None}

        def _drain():
            done["v"] = service.drain(timeout=10.0)

        th = threading.Thread(target=_drain, daemon=True)
        th.start()
        deadline = time.monotonic() + 2.0
        while not service.scheduler.draining \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        try:
            service.execute_sql(
                service.session, "select count(*) c from serve_gate_t")
            fail("--serve: draining server accepted a new query")
        except ServerDraining:
            pass
        service.scheduler.release(slow)             # in-flight finishes
        th.join(10.0)
        if done["v"] is not True:
            fail(f"--serve: drain did not quiesce ({done['v']})")
        if not service.scheduler.balanced():
            fail("--serve: admission ledger unbalanced after drain "
                 "(leaked slots or HBM reservations)")
        problems = GLOBAL_LEDGER.verify()
        if problems:
            fail(f"--serve: device ledger inconsistent after drain: "
                 f"{problems[:3]}")
    finally:
        session.stop()
    print("validate_trace: serve gate OK — stride fairness 2:1 "
          "(deterministic), HBM admission holds/releases reservations, "
          f"concurrent load attribution exact ({attributed} launches, "
          "0 overlapped profiles), over-budget plans reject plan-time, "
          "drain quiesced with a balanced ledger")


def race_gate() -> None:
    """Race gate (--race, self-contained): runtime validation of the
    static race_lint concurrency model (see module docstring #12). Runs
    the two real concurrent loads CI already trusts — the 8-session
    serve load and a 2-worker cluster leg with a transient block-fetch
    flap plus a deterministic transport-retry exercise — with
    utils/lockwatch.py watching every registered lock, then cross-checks
    the observations against the static model built by
    analysis/race_lint.py."""
    import tempfile
    import threading

    # Watch BEFORE any session exists so module-level registered locks
    # swap to proxies, and export the env var so spawned cluster workers
    # inherit watching through their environment.
    os.environ["SPARK_TPU_LOCKWATCH"] = "1"
    from spark_tpu.utils import faults, lockwatch
    lockwatch.enable()
    lockwatch.reset_observations()

    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.config import SQLConf
    from spark_tpu.net.transport import (
        RETRY_STATS, RetryPolicy, RpcClient, RpcServer,
    )
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import QueryService
    from spark_tpu.serve.loadgen import run_serve_load

    def watchdog(name, fn, timeout_s=120.0):
        out: dict = {}

        def run():
            try:
                out["result"] = fn()
            except BaseException as e:   # re-raised on the gate thread
                out["error"] = e

        t = threading.Thread(target=run, daemon=True, name=f"race-{name}")
        t.start()
        t.join(timeout_s)
        if t.is_alive():
            fail(f"--race: leg {name!r} HUNG past {timeout_s}s under "
                 "lockwatch — watching must never introduce a deadlock")
        if "error" in out:
            raise out["error"]
        return out.get("result")

    def leg_serve():
        """The serve-gate concurrent load (8 cloned sessions, 2 pools)
        run under watching; attribution must stay scope-exact, proving
        the proxies perturb nothing the obs layer measures."""
        profile_dir = tempfile.mkdtemp(prefix="race_gate_prof_")
        session = TpuSession("race-gate-serve", {
            "spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 12,
            "spark.tpu.fusion.minRows": "0",
            "spark.tpu.obs.profileDir": profile_dir,
            "spark.tpu.scheduler.pools": "dash:2,batch:1",
            "spark.tpu.serve.maxConcurrent": 2,
        })
        try:
            rng = np.random.default_rng(11)
            session.createDataFrame(pa.table({
                "k": rng.integers(0, 16, 4000).astype(np.int64),
                "v": rng.integers(-50, 150, 4000).astype(np.int64),
            })).createOrReplaceTempView("race_gate_t")
            service = QueryService(session)
            before = KC.launches
            report = run_serve_load(
                service,
                ["select k, sum(v) s from race_gate_t group by k",
                 "select k, v from race_gate_t where v > 0 "
                 "order by v limit 16"],
                sessions=8, reps=2, pools=("dash", "batch"))
            if report["errors"]:
                fail(f"--race: serve load failed under lockwatch: "
                     f"{report['errors']}")
            kc_delta = KC.launches - before
            store = ProfileStore(profile_dir)
            attributed = sum(int(p.get("launch_total", 0))
                             for qk in store.query_keys()
                             for p in store.profiles(qk))
            if attributed != kc_delta:
                fail(f"--race: watched serve load attribution "
                     f"({attributed}) != KernelCache delta ({kc_delta}) "
                     "— lockwatch perturbed the obs scope machinery")
        finally:
            session.stop()

    def leg_cluster():
        """2-worker cluster chaos leg: a transient block-fetch flap must
        still return correct rows with watching live in driver AND
        workers (inherited env), then a deterministic rpc.call flap
        drives the RETRY_STATS locked-counter bump so its guard check
        fires on record. Returns each worker's own lockwatch
        observations (the lockwatch_edges RPC) so the cross-checks
        below cover executor processes, not just the driver."""
        session = TpuSession("race-gate-cluster", {
            "spark.sql.shuffle.partitions": "2",
            "spark.tpu.batch.capacity": 1 << 12,
            "spark.sql.adaptive.enabled": "false",
            "spark.tpu.cluster.enabled": "true",
            "spark.tpu.cluster.workers": "2",
        })
        worker_lw: dict = {}
        try:
            rng = np.random.default_rng(13)
            keys = rng.integers(0, 24, 4000)
            vals = rng.integers(-40, 90, 4000)
            session.createDataFrame(pa.table({"k": keys, "v": vals})) \
                .createOrReplaceTempView("rg_t")
            rows = sorted(zip(keys.tolist(), vals.tolist()))
            session.conf.set("spark.tpu.faults.enabled", "true")
            session.conf.set("spark.tpu.faults.seed", "13")
            session.conf.set("spark.tpu.faults.points",
                             "block.fetch=first:2")
            faults.configure(session.conf)
            got = sorted(
                (r["k"], r["v"]) for r in
                session.table("rg_t").repartition(2).collect())
            if got != rows:
                fail("--race: cluster flap query returned WRONG rows "
                     "under lockwatch")
            # pull each worker's lock observations BEFORE teardown —
            # the executor half of cross-check 2
            cluster = getattr(session, "_sql_cluster", None)
            if cluster is not None:
                worker_lw = cluster.lockwatch_edges()
        finally:
            faults.reset()
            session.stop()

        server = RpcServer("rg")
        server.register("echo", lambda p: p)
        addr = server.start()
        try:
            c = RpcClient(addr, "rg")
            faults.configure(SQLConf({
                "spark.tpu.faults.enabled": "true",
                "spark.tpu.faults.points": "rpc.call=first:1"}))
            before = RETRY_STATS["absorbed"]
            out = c.call("echo", b"y",
                         retry=RetryPolicy(attempts=3, base_ms=1.0,
                                           deadline_s=5.0))
            if out != b"y" or RETRY_STATS["absorbed"] <= before:
                fail("--race: transport retry exercise did not absorb "
                     "the injected flap")
            c.close()
        finally:
            faults.reset()
            server.stop()
        return worker_lw

    try:
        watchdog("serve-load", leg_serve)
        worker_lw = watchdog("cluster-chaos", leg_cluster) or {}

        # -- cross-check 1: every claimed guard was HELD where claimed --
        viol = lockwatch.violations()
        if viol:
            fail(f"--race: {len(viol)} guard check(s) found the claimed "
                 f"lock NOT held at a flagged mutation site, e.g. "
                 f"{viol[0]}")
        checks = lockwatch.guard_checks()
        if not any(site.startswith("net.transport.RETRY_STATS")
                   for site, _lock in checks):
            fail("--race: the RETRY_STATS guard was never exercised — "
                 "the retry leg did not drive the instrumented counter")
        acq = lockwatch.acquire_counts()
        if not acq:
            fail("--race: no watched-lock acquisitions recorded — "
                 "lockwatch was not live during the load")

        # -- cross-check 2: the static and runtime halves share one
        # lock namespace, and their union stays acyclic ----------------
        from spark_tpu.analysis import race_lint
        model = race_lint.build_model(
            [os.path.join(_ROOT, "spark_tpu")], repo_root=_ROOT)
        static_locks = set(model.locks)
        unknown = [n for n in lockwatch.registered_names()
                   if not n.startswith("counter.")
                   and n not in static_locks]
        if unknown:
            fail(f"--race: registered watch slots unknown to the static "
                 f"model: {unknown} — the two halves drifted apart")
        observed = set(lockwatch.order_edges())

        # -- cross-check 2b: the EXECUTOR processes, via the
        # lockwatch_edges RPC the cluster leg collected — workers must
        # have watched (inherited env), reported no guard violations,
        # registered only slots the static model knows, and their
        # acquisition edges fold into the same cycle check -------------
        if not worker_lw:
            fail("--race: no worker answered the lockwatch_edges RPC — "
                 "executor-side lock discipline went unchecked")
        for eid, wp in sorted(worker_lw.items()):
            if not wp.get("enabled"):
                fail(f"--race: worker {eid} ran with lockwatch OFF — "
                     "the env inheritance into executors broke")
            if wp.get("violations"):
                fail(f"--race: worker {eid} recorded guard violations: "
                     f"{wp['violations'][:2]}")
            unknown_w = [n for n in wp.get("names", ())
                         if not n.startswith("counter.")
                         and n not in static_locks]
            if unknown_w:
                fail(f"--race: worker {eid} registered watch slots "
                     f"unknown to the static model: {unknown_w}")
            observed |= {(a, b) for a, b, _n in wp.get("edges", ())}

        static_edges = {tuple(e) for e in model.lock_edges}
        cyc = lockwatch.find_cycle(observed | static_edges)
        if cyc:
            fail("--race: observed acquisition orders close a lock-order "
                 f"cycle the static model missed: {' -> '.join(cyc)}")

        problems = GLOBAL_LEDGER.verify()
        if problems:
            fail(f"--race: device ledger inconsistent after watched "
                 f"run: {problems[:3]}")
    finally:
        lockwatch.disable()
        os.environ.pop("SPARK_TPU_LOCKWATCH", None)

    # disable() must restore RAW locks in every registered slot — the
    # zero-overhead-when-idle claim is structural, so verify structure
    import threading as _threading
    raw_lock_type = type(_threading.Lock())
    if not isinstance(RETRY_STATS._lock, raw_lock_type):
        fail("--race: disable() left a WatchedLock proxy installed — "
             "idle runs would pay the watching overhead")
    print("validate_trace: race gate OK — serve load (8 sessions) and "
          "2-worker chaos leg ran watched with exact attribution, "
          f"{len(checks)} guard site(s) held where claimed, 0 guard "
          f"violations (driver + {len(worker_lw)} workers via the "
          f"lockwatch_edges RPC), {len(observed)} observed acquisition "
          "edge(s) union the static nesting graph acyclic, raw locks "
          "restored on disable")


def metrics_gate() -> None:
    """Metrics gate (--metrics, self-contained): the service metrics
    plane's acceptance identities under a real serve load —

      1. the new lockwatch slots are registered;
      2. structural zero overhead: the kernel-launch delta of the same
         query is IDENTICAL with export on and off;
      3. under a concurrent load with export on: the Prometheus scrape
         parses, the per-pool e2e histogram counts sum EXACTLY to the
         queries the service admitted, per-query attribution stays
         scope-exact, and the drain snapshot froze a non-empty ring;
      4. the static race model still matches its baseline (the new
         locks/threads are modeled, not baselined away).
    """
    import subprocess
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs import export as mx
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import QueryService
    from spark_tpu.serve.loadgen import run_serve_load
    from spark_tpu.utils import lockwatch

    # -- 1: the metrics plane's locks are lockwatch-registered -----------
    names = set(lockwatch.registered_names())
    for slot in ("obs.export.MetricsRegistry._lock",
                 "obs.export._TS_LOCK"):
        if slot not in names:
            fail(f"--metrics: lock slot {slot!r} is not "
                 "lockwatch-registered — the metrics plane left the "
                 "runtime discipline net")

    # hermetic registry: earlier gates in the same process may have
    # bound sources over their (now-stopped) sessions
    mx.REGISTRY.reset()

    base = {
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.cache.result.enabled": "false",
    }

    # -- 2: zero overhead — launch delta export on == export off ---------
    session = TpuSession("metrics-gate-overhead", dict(base))
    try:
        rng = np.random.default_rng(17)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 16, 4000).astype(np.int64),
            "v": rng.integers(-50, 150, 4000).astype(np.int64),
        })).createOrReplaceTempView("mg_t")
        probe = "select k, sum(v) s from mg_t group by k"
        session.sql(probe).collect()            # compile warmup
        l0 = KC.launches
        session.sql(probe).collect()
        delta_off = KC.launches - l0
        session.conf.set("spark.tpu.metrics.export", "true")
        mx.configure(session.conf)
        mx.register_default_sources(session=session)
        l0 = KC.launches
        session.sql(probe).collect()
        delta_on = KC.launches - l0
        if delta_off <= 0:
            fail("--metrics: overhead probe launched nothing — the "
                 "comparison is vacuous")
        if delta_on != delta_off:
            fail(f"--metrics: export flipped the kernel-launch count "
                 f"({delta_off} off -> {delta_on} on) — the metrics "
                 "plane touched the device path")
    finally:
        session.stop()

    # -- 3: serve load with export on --------------------------------
    profile_dir = tempfile.mkdtemp(prefix="metrics_gate_prof_")
    session = TpuSession("metrics-gate-serve", {
        **base,
        "spark.tpu.obs.profileDir": profile_dir,
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.serve.maxConcurrent": 2,
        "spark.tpu.metrics.export": "true",
        "spark.tpu.metrics.tickInterval": "0.1",
    })
    try:
        rng = np.random.default_rng(19)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 16, 4000).astype(np.int64),
            "v": rng.integers(-50, 150, 4000).astype(np.int64),
        })).createOrReplaceTempView("mg_serve_t")
        queries = ["select k, sum(v) s from mg_serve_t group by k",
                   "select k, v from mg_serve_t where v > 0 "
                   "order by v limit 16"]
        service = QueryService(session)
        launches_before = KC.launches
        warmup = service.open_session()
        for q in queries:
            service.execute_sql(warmup, q)
        sessions_n, reps = 6, 2
        report = run_serve_load(service, queries, sessions=sessions_n,
                                reps=reps, pools=("dash", "batch"))
        if report["errors"]:
            fail(f"--metrics: load queries failed: {report['errors']}")
        # the acceptance identity: every admitted collect — warmup plus
        # the whole load — released through exactly one pool histogram
        expected = len(queries) * (1 + sessions_n * reps)
        try:
            parsed = mx.parse_prometheus(mx.render_prometheus())
        except ValueError as e:
            fail(f"--metrics: /metrics scrape does not parse: {e}")
        e2e_total = sum(
            v for (name, _lbl), v in parsed["samples"].items()
            if name == "spark_tpu_serve_pool_e2e_ms_count")
        if int(e2e_total) != expected:
            fail(f"--metrics: per-pool e2e histogram counts sum to "
                 f"{int(e2e_total)}, expected {expected} admitted "
                 "queries — the admission path leaks or double-counts "
                 "observations")
        if "spark_tpu_kernel_launches" not in parsed["types"]:
            fail("--metrics: scrape is missing the kernel.launches "
                 "series — default sources not wired")
        # attribution must stay scope-exact with the plane live
        kc_delta = KC.launches - launches_before
        store = ProfileStore(profile_dir)
        attributed = sum(int(p.get("launch_total", 0))
                         for qk in store.query_keys()
                         for p in store.profiles(qk))
        if attributed != kc_delta:
            fail(f"--metrics: attributed launches ({attributed}) != "
                 f"KernelCache delta ({kc_delta}) under the metrics "
                 "plane — export perturbed scope attribution")
        service.drain()
        snap = service.drain_snapshot or {}
        if not snap.get("series"):
            fail("--metrics: drain froze an EMPTY time-series ring — "
                 "the ticker never sampled")
    finally:
        session.stop()

    # -- 4: the static race model still matches its baseline ----------
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "dev", "racecheck.py"),
         "spark_tpu", "--baseline",
         os.path.join(_ROOT, "dev", "race_baseline.json")],
        cwd=_ROOT, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        fail("--metrics: racecheck regressed against its baseline — "
             "the metrics plane introduced unmodeled concurrency:\n"
             + proc.stdout[-800:] + proc.stderr[-400:])

    print("validate_trace: metrics gate OK — scrape parses, per-pool "
          f"e2e histogram counts == {expected} admitted queries, "
          f"attribution exact ({attributed} launches), launch delta "
          f"identical export on/off ({delta_on}), drain snapshot "
          f"{len(snap['series'])} series, racecheck baseline clean")


def bundles_gate() -> None:
    """Black-box gate (--bundles, self-contained): the diagnostic
    bundle layer's acceptance identities —

      1. structural hygiene: the capture layer's lock is
         lockwatch-registered, and with spark.tpu.obs.bundles off the
         module bool stays False (no registry, no scans);
      2. zero-overhead identity: the kernel-launch delta of the same
         query is IDENTICAL armed-but-untriggered vs off, and a healthy
         armed run captures ZERO bundles;
      3. chaos-seeded SLO breach on a 2-worker cluster ⇒ exactly one
         complete self-contained bundle: manifest + trace + plan
         reports + metrics scrape on disk, pulled worker diagnostic
         state (executor-labeled spans, fault-registry counts) inside,
         profile with embedded same-key history, and dev/diagnose.py
         renders the postmortem from the bundle directory alone;
      4. the retention ring prunes to its bound.
    """
    import json as _json
    import subprocess
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs import blackbox
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import QueryService
    from spark_tpu.utils import lockwatch

    # -- 1: structural hygiene -------------------------------------------
    if "obs.blackbox._LOCK" not in set(lockwatch.registered_names()):
        fail("--bundles: obs.blackbox._LOCK is not lockwatch-registered "
             "— the capture layer left the runtime discipline net")

    base = {
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.cache.result.enabled": "false",
    }
    blackbox.reset()
    bundle_dir = tempfile.mkdtemp(prefix="bundles_gate_")

    # -- 2: zero overhead — launch delta armed == off, healthy ⇒ 0 -------
    session = TpuSession("bundles-gate-overhead", dict(base))
    try:
        if blackbox.ENABLED:
            fail("--bundles: capture layer armed with "
                 "spark.tpu.obs.bundles at its default (off)")
        rng = np.random.default_rng(23)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 16, 4000).astype(np.int64),
            "v": rng.integers(-50, 150, 4000).astype(np.int64),
        })).createOrReplaceTempView("bg_t")
        probe = "select k, sum(v) s from bg_t group by k"
        session.sql(probe).collect()            # compile warmup
        l0 = KC.launches
        session.sql(probe).collect()
        delta_off = KC.launches - l0
        session.conf.set("spark.tpu.obs.bundles", "true")
        session.conf.set("spark.tpu.obs.bundleDir", bundle_dir)
        blackbox.configure(session.conf)
        if not blackbox.ENABLED:
            fail("--bundles: configure() left the layer unarmed with "
                 "bundles on and a bundle dir set")
        l0 = KC.launches
        session.sql(probe).collect()
        delta_on = KC.launches - l0
        if delta_off <= 0:
            fail("--bundles: overhead probe launched nothing — the "
                 "comparison is vacuous")
        if delta_on != delta_off:
            fail(f"--bundles: arming flipped the kernel-launch count "
                 f"({delta_off} off -> {delta_on} armed) — capture is "
                 "not pull-on-anomaly")
        if blackbox.list_bundles(bundle_dir):
            fail("--bundles: a HEALTHY armed run captured a bundle — "
                 "the trigger predicate fires on non-anomalies")
    finally:
        session.stop()
        blackbox.reset()

    # -- 3: chaos-seeded SLO breach on a 2-worker cluster ----------------
    profile_dir = tempfile.mkdtemp(prefix="bundles_gate_prof_")
    session = TpuSession("bundles-gate-cluster", {
        **base,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.obs.bundles": "true",
        "spark.tpu.obs.bundleDir": bundle_dir,
        "spark.tpu.obs.profileDir": profile_dir,
        "spark.tpu.metrics.export": "true",
        "spark.tpu.serve.sloMs": "50",
        # deterministic breach: every worker stage task sleeps well past
        # the pool SLO (host-side sleep — results stay exact)
        "spark.tpu.faults.enabled": "true",
        "spark.tpu.faults.seed": "7",
        "spark.tpu.faults.points": "worker.task=always:sleep:0.2",
    })
    try:
        rng = np.random.default_rng(29)
        keys = rng.integers(0, 24, 5000).astype(np.int64)
        vals = rng.integers(-40, 90, 5000).astype(np.int64)
        session.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("bg_c")
        service = QueryService(session)
        # explicit repartition: the query MUST run worker map tasks (the
        # chaos gate's worker.task seam) for the pull leg to mean anything
        df = session.table("bg_c").repartition(2)
        table = service.collect(session, df)
        got = sorted(zip(table.column("k").to_pylist(),
                         table.column("v").to_pylist()))
        if got != sorted(zip(keys.tolist(), vals.tolist())):
            fail("--bundles: chaos-seeded query returned wrong rows — "
                 "the breach scenario corrupted results")
        entries = blackbox.list_bundles(bundle_dir)
        if len(entries) != 1:
            fail(f"--bundles: SLO breach captured {len(entries)} "
                 "bundle(s), expected exactly one")
        ent = entries[0]
        if ent.get("trigger_kind") != "obs.slo":
            fail(f"--bundles: bundle trigger is {ent.get('trigger_kind')!r},"
                 " expected obs.slo")
        bid = ent["id"]
        bdir = os.path.join(bundle_dir, f"bundle-{bid}")
        for fname in ("bundle.json", "trace.json", "explain_simple.txt",
                      "explain_analysis.txt", "explain_analyze.txt",
                      "metrics.prom"):
            if not os.path.isfile(os.path.join(bdir, fname)):
                fail(f"--bundles: bundle is missing {fname} — not "
                     "self-contained")
        with open(os.path.join(bdir, "bundle.json")) as f:
            manifest = _json.load(f)
        workers = manifest.get("workers") or {}
        if not workers:
            fail("--bundles: diagnostic_state pull landed NO worker "
                 "state in the bundle")
        ring_tasks = [t for w in workers.values()
                      for t in (w.get("tasks") or [])]
        if not ring_tasks:
            fail("--bundles: pulled worker rings are empty — "
                 "finish_stage_obs did not retain post-task state")
        if not any(t.get("spans") for t in ring_tasks):
            fail("--bundles: pulled worker rings carry no spans")
        if not any((w.get("faults") or {}).get("fired")
                   for w in workers.values()):
            fail("--bundles: no worker fault-registry state in the "
                 "bundle (the injected worker.task rule fired)")
        with open(os.path.join(bdir, "trace.json")) as f:
            trace = _json.load(f)
        procs = {e.get("args", {}).get("name")
                 for e in trace.get("traceEvents", [])
                 if e.get("name") == "process_name"}
        if not any(str(p).startswith("executor ") for p in procs):
            fail(f"--bundles: trace.json has no executor-labeled "
                 f"process track (got {sorted(map(str, procs))})")
        if manifest.get("profile") is None:
            fail("--bundles: bundle carries no query profile — the "
                 "flight recorder section is missing")
        # postmortem renders from the bundle dir alone, out of process
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "dev", "diagnose.py"),
             bundle_dir, bid],
            cwd=_ROOT, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            fail("--bundles: dev/diagnose.py failed on the bundle:\n"
                 + proc.stdout[-400:] + proc.stderr[-400:])
        for marker in ("Trigger timeline", "obs.slo",
                       "Per-executor straggler / HBM map"):
            if marker not in proc.stdout:
                fail(f"--bundles: postmortem report is missing "
                     f"{marker!r}")

        # -- 4: retention ring prunes to its bound -----------------------
        session.conf.set("spark.tpu.obs.bundle.ring", "2")
        blackbox.configure(session.conf)
        for _ in range(4):
            if session.capture_diagnostics(df) is None:
                fail("--bundles: explicit capture_diagnostics returned "
                     "no bundle id")
        left = blackbox.list_bundles(bundle_dir)
        dirs = [d for d in os.listdir(bundle_dir)
                if d.startswith("bundle-")]
        if len(left) > 2 or len(dirs) > 2:
            fail(f"--bundles: retention ring bound 2 violated "
                 f"({len(left)} index entries, {len(dirs)} dirs)")
    finally:
        session.stop()
        blackbox.reset()

    print("validate_trace: bundles gate OK — launch delta identical "
          f"armed/off ({delta_on}), healthy run zero bundles, SLO "
          "breach on the 2-worker cluster captured exactly one "
          f"self-contained bundle ({len(ring_tasks)} pulled worker "
          "task(s), executor trace tracks, fault-registry state), "
          "diagnose.py rendered it offline, retention ring pruned to "
          "bound")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cluster = "--cluster" in argv
    live = "--live" in argv
    mesh = "--mesh" in argv
    encoded = "--encoded" in argv
    adaptive = "--adaptive" in argv
    whole = "--whole-query" in argv
    mesh_whole = "--mesh-whole" in argv
    chaos = "--chaos" in argv
    profile = "--profile" in argv
    persist = "--persist" in argv
    serve = "--serve" in argv
    race = "--race" in argv
    metrics = "--metrics" in argv
    bundles = "--bundles" in argv
    argv = [a for a in argv if a not in ("--cluster", "--live", "--mesh",
                                         "--encoded", "--adaptive",
                                         "--whole-query",
                                         "--mesh-whole",
                                         "--chaos", "--profile",
                                         "--persist", "--serve",
                                         "--race", "--metrics",
                                         "--bundles")]
    if (mesh or encoded or adaptive or whole or mesh_whole or chaos
            or profile or persist or serve or race or metrics
            or bundles) and not argv:
        # self-contained legs: these gates generate and validate their
        # own state (dev/run_all.sh runs them without a trace file)
        if mesh:
            mesh_gate()
        if encoded:
            encoded_gate()
        if adaptive:
            adaptive_gate()
        if whole:
            whole_query_gate()
        if mesh_whole:
            mesh_whole_gate()
        if chaos:
            chaos_gate()
        if profile:
            profile_gate()
        if persist:
            persist_gate()
        if serve:
            serve_gate()
        if metrics:
            metrics_gate()
        if bundles:
            bundles_gate()
        if race:
            race_gate()
        print("validate_trace: PASS")
        return 0
    if len(argv) != 1:
        print(__doc__)
        return 2
    validate_trace(argv[0], cluster=cluster)
    drift_gate(cluster=cluster)
    resource_gate()
    if live:
        live_gate()
    if mesh:
        mesh_gate()
    if encoded:
        encoded_gate()
    if adaptive:
        adaptive_gate()
    if whole:
        whole_query_gate()
    if mesh_whole:
        mesh_whole_gate()
    if chaos:
        chaos_gate()
    if profile:
        profile_gate()
    if persist:
        persist_gate()
    if serve:
        serve_gate()
    if metrics:
        metrics_gate()
    if bundles:
        bundles_gate()
    if race:
        race_gate()
    print("validate_trace: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
