"""QueryExecution: the lazy phase pipeline.

Role of the reference's QueryExecution (sqlx/QueryExecution.scala —
lazyAnalyzed:192 → withCachedData → lazyOptimizedPlan:311 → lazySparkPlan:335
→ lazyExecutedPlan:353 → toRdd), with a QueryPlanningTracker-style per-phase
timing record (sqlcat/QueryPlanningTracker.scala).
"""

from __future__ import annotations

import time
from functools import cached_property

import pyarrow as pa

from ..columnar.ops import concat_batches
from ..config import MAX_RESULT_ROWS
from ..exec.context import ExecContext
from ..plan.logical import LogicalPlan
from ..physical.operators import PhysicalPlan, attrs_schema
from ..utils.device_memo import device_read


def _unconvert(value, dt):
    """arrow python value → Literal-compatible value."""
    import datetime
    import decimal

    if isinstance(value, decimal.Decimal):
        return value
    return value


def _whole_program_joins(plan) -> bool:
    """A whole-program plan (one chip's or the mesh's) that lowers a
    join: its first attempt has capacities to start from."""
    from ..physical.operators import HashJoinExec
    from ..physical.whole_query import WholeQueryExec

    return isinstance(plan, WholeQueryExec) and any(
        isinstance(n, HashJoinExec) for n in plan.plan.iter_nodes())


class QueryPlanningTracker:
    """Per-query rule/phase timing (reference:
    sqlcat/QueryPlanningTracker.scala — phases via measurePhase, rules
    via RuleExecutor.executeAndTrack; dumpTimeSpent role filled by
    top_rules)."""

    def __init__(self):
        self.rules: dict[str, float] = {}
        self.rule_hits: dict[str, int] = {}

    def record_rule(self, name: str, seconds: float) -> None:
        self.rules[name] = self.rules.get(name, 0.0) + seconds
        self.rule_hits[name] = self.rule_hits.get(name, 0) + 1

    def top_rules(self, n: int = 10) -> list[tuple[str, float, int]]:
        return sorted(((k, v, self.rule_hits[k])
                       for k, v in self.rules.items()),
                      key=lambda t: -t[1])[:n]


def _planes_to_host(batches) -> dict:
    """Copy every plane of the result to the host in one transfer (the
    sync `collect.d2h`), in the order `ColumnarBatch.to_arrow` reads
    them; returns {id(plane): host copy}. A jax array keeps its host
    copy, so the Arrow assembly that follows finds it there: the
    device→host time and the assembly time come apart, and nothing is
    copied twice."""
    planes = [a for b in batches
              for a in [b.row_mask] + [x for c in b.columns
                                       for x in (c.data, c.validity)]
              if a is not None]
    return dict(zip(map(id, planes), device_read("collect.d2h", *planes)))


class QueryExecution:
    # flight-recorder close results (obs/history.py): populated by
    # execute() when spark.tpu.obs.profileDir is set; class defaults so
    # probes on a recorder-off (or failed-close) query read None/empty
    # instead of AttributeError
    _last_profile: dict | None = None
    _last_regressions: tuple = ()
    # black-box close result (obs/blackbox.py): bundle id captured for
    # this execution, None when nothing triggered / bundles off
    _last_bundle: str | None = None

    def __init__(self, session, logical: LogicalPlan):
        self.session = session
        self.logical = logical
        self.phase_times: dict[str, float] = {}
        self.tracker = QueryPlanningTracker()

    @property
    def _tracer(self):
        t = getattr(self.session, "tracer", None)
        return t if (t is not None and t.enabled) else None

    def _timed(self, name: str, fn):
        tracer = self._tracer
        t0 = time.perf_counter()
        if tracer is not None:
            # the execution phase roots the query's flow graph: stage →
            # partition-lane/worker spans draw arrows back to it
            with tracer.span(name, cat="phase", flow=(name == "execution")):
                out = fn()
        else:
            out = fn()
        self.phase_times[name] = time.perf_counter() - t0
        return out

    @cached_property
    def analyzed(self) -> LogicalPlan:
        return self._timed("analysis",
                           lambda: self.session._analyzer.execute(
                               self.logical, tracker=self.tracker))

    @cached_property
    def with_cached_data(self) -> LogicalPlan:
        """Cached-fragment substitution (reference: QueryExecution
        withCachedData → CacheManager.useCachedData)."""
        analyzed = self.analyzed
        use = getattr(self.session, "_use_cached", None)
        return use(analyzed) if use else analyzed

    @cached_property
    def optimized(self) -> LogicalPlan:
        plan = self.with_cached_data
        out = self._timed("optimization",
                          lambda: self.session._optimizer.execute(
                              plan, tracker=self.tracker))
        return self._materialize_scalar_subqueries(out)

    def _materialize_scalar_subqueries(self, plan: LogicalPlan) -> LogicalPlan:
        """Execute remaining (uncorrelated) scalar subqueries once and
        substitute literals (role of the reference's SubqueryExec
        materialization before the main query runs)."""
        from ..plan.subquery import ScalarSubquery
        from ..expr.expressions import Literal

        has = any(isinstance(x, ScalarSubquery)
                  for n in plan.iter_nodes()
                  for e in n.expressions()
                  for x in e.iter_nodes())
        if not has:
            return plan

        def fix_expr(e):
            if isinstance(e, ScalarSubquery):
                sub_qe = QueryExecution(self.session, e.plan)
                table = sub_qe.to_arrow()
                if table.num_rows > 1:
                    raise RuntimeError(
                        "scalar subquery returned more than one row")
                value = table.column(0)[0].as_py() if table.num_rows else None
                dt = e.dtype
                return Literal(_unconvert(value, dt), dt) \
                    if value is not None else Literal(None, dt)
            return e

        def rule(node):
            return node.transform_expressions(fix_expr)

        return plan.transform_up(rule)

    @cached_property
    def physical(self) -> PhysicalPlan:
        optimized = self.optimized
        return self._timed("planning",
                           lambda: self.session._planner().plan(optimized))

    def _history_replan(self, plan):
        """Re-enter the compile-tier chooser with a recorded prior run's
        observed shuffle volume (warm-start manifest "observed_rows").
        Returns the whole-tier wrapped plan, or None to keep `plan`
        unchanged. Recurring queries over external sources — whose
        plan-time leaf statistics are unknown — reach the whole tier
        before their first batch moves."""
        from ..config import ADAPTIVE_READMISSION

        if not self.session.conf.get(ADAPTIVE_READMISSION):
            return None
        if getattr(self.session, "_sql_cluster", None) is not None:
            return None
        from ..exec import persist_cache as _persist

        if not _persist.cache_root(self.session.conf):
            return None
        from ..physical.mesh_whole import MeshWholeQueryExec
        from ..physical.whole_query import WholeQueryExec, choose_tier

        if isinstance(plan, (WholeQueryExec, MeshWholeQueryExec)):
            return None
        try:
            fp = self.plan_fingerprint()["fingerprint"]
            seed = _persist.manifest_seed(self.session.conf, fp) or {}
        except Exception:
            return None
        observed = seed.get("observed_rows")
        if not observed:
            return None
        dec = choose_tier(plan, self.session.conf,
                          observed_rows=int(observed))
        if dec.tier != "whole":
            return None
        dec.details["history_replanned"] = True
        return WholeQueryExec(plan, dec)

    def execute(self, finalize_rows: bool = True) -> list:
        """Run the plan; the partitions of its result batches. With
        `finalize_rows` False the operators' parked row masks stay
        unread unless the flight recorder needs them: a collect counts
        them from its own read of the result planes."""
        from ..config import (KERNEL_ATTRIBUTION, PROGRESS_CONSOLE,
                              PROGRESS_UPDATE_INTERVAL,
                              UI_OPERATOR_METRICS)
        from ..obs.metrics import discard_pending, finalize_plan_metrics
        from ..obs.tracing import current_query, pop_query, push_query
        from .scheduler import DAGScheduler

        plan = self.physical
        from ..physical.exchange import annotate_exchange_stat_cols

        # map-side shuffle stat accumulation is restricted to columns a
        # downstream dense decision can actually consult (the plan
        # analyzer mirrors the same reachability rule)
        annotate_exchange_stat_cols(plan)
        # recurring-query history re-planning (spark.tpu.adaptive.
        # readmission): a prior same-fingerprint run recorded its
        # observed shuffle volume in the warm-start manifest; a plan the
        # tier chooser refused for lack of plan-time statistics re-enters
        # choose_tier with the OBSERVED volume before the first batch
        # moves. Pure host work; no-op without a cache dir or history.
        history_replanned = self._history_replan(plan)
        if history_replanned is not None:
            plan = history_replanned
            self.__dict__["physical"] = plan
        # HBM admission control: with spark.tpu.memory.budget set, the
        # analyzer's memory model pre-flights predicted peak HBM and an
        # over-budget plan fails HERE — named stage, nothing dispatched —
        # instead of as an opaque XLA OOM mid-query (obs/resources.py)
        from ..obs.resources import check_memory_budget

        # the serving layer's admission pre-flight (serve/service.py)
        # already analyzed this plan — reuse its report instead of
        # paying a second whole-plan analysis on the serving hot path
        check_memory_budget(
            plan, self.session.conf,
            # a history re-plan changed the tier after the serving-layer
            # pre-flight: its report modeled the OLD plan — re-analyze
            report=None if history_replanned is not None
            else getattr(self, "_preflight_report", None),
            cluster=getattr(self.session, "_sql_cluster", None) is not None)
        # execution always runs under a query scope: collects push one in
        # to_arrow, but direct execute() callers (tests) would
        # otherwise stream worker heartbeat deltas with no
        # query key — phantom entries the live store could never close
        qid = current_query()
        eph_token = None
        if qid is None:
            import uuid

            qid = uuid.uuid4().hex[:12]
            eph_token = push_query(qid, self._tracer)
        from .context import ScopedMetrics

        # ScopedMetrics: every counter this query adds lands on the
        # session totals (unchanged) AND a query-local copy — profiles
        # and EXPLAIN ANALYZE then read scope-exact per-query deltas
        # that concurrent collects cannot contaminate
        ctx = ExecContext(conf=self.session.conf,
                          metrics=ScopedMetrics(self.session._metrics),
                          block_manager=getattr(
                              self.session, "block_manager", None),
                          tracer=self._tracer,
                          live_obs=getattr(self.session, "live_obs",
                                           None),
                          query_id=qid)
        # conf values are host data — bool() here never touches device
        if bool(self.session.conf.get(  # tpulint: ignore[host-sync]
                UI_OPERATOR_METRICS)):
            ctx.plan_metrics = {}
            ctx.kernel_attribution = bool(  # tpulint: ignore[host-sync]
                self.session.conf.get(KERNEL_ATTRIBUTION))
            # stable metric keys BEFORE execution: the stage builder
            # copies exchanges and their ancestors (with_new_children),
            # and copies share __dict__, so a pre-assigned id survives
            # into the executed objects where id() would not. The walk
            # descends through a whole-query wrapper into its inner
            # plan: a runtime tier degrade re-executes the inner
            # operators directly, and their records must land under
            # keys the plan graph can render (PR 11 follow-on (d))
            from ..obs.metrics import iter_metric_nodes

            for i, n in enumerate(iter_metric_nodes(self.physical)):
                n._metric_id = i
            # AQE annotations are per-QUERY: baseline the session-level
            # adaptive counters so plan_graph reports the delta
            self._adaptive_baseline = {
                k: v for k, v in ctx.metrics.snapshot()["counters"].items()
                if k.startswith("adaptive.")}
        self._last_ctx = ctx
        if history_replanned is not None:
            ctx.metrics.add("adaptive.history_replans")
        # query flight recorder (obs/history.py): with a profile dir
        # configured, snapshot the process counters the close-time
        # profile deltas against. One conf read when off; the snapshot
        # itself is a few dict copies — pure host bookkeeping
        from ..config import OBS_PROFILE_DIR

        recorder = None
        if str(self.session.conf.get(  # tpulint: ignore[host-sync]
                OBS_PROFILE_DIR) or ""):
            # close-time deltas come from the per-query kernel ledger
            # and ScopedMetrics (scope-exact under concurrency); the
            # snapshots here remain only as the fallback for contexts
            # without a ledger, plus the wall-clock anchor
            from ..physical.compile import GLOBAL_KERNEL_CACHE as _KC

            recorder = {
                "kinds": dict(_KC.launches_by_kind),
                "misses": _KC.misses,
                "compile_ms": _KC.compile_ms,
                "disk_hit_compiles": _KC.disk_hit_compiles,
                "counters": dict(
                    self.session._metrics.snapshot()["counters"]),
                "t0": time.perf_counter()}
        # warm start (exec/persist_cache.py): seed this query's
        # capacity-retry state from what its plan learned before — the
        # newest same-fingerprint manifest record with a cache dir
        # configured, else the join capacities this process remembers
        # (so a whole-program plan with a join is fingerprinted, cache
        # dir or not: under a millisecond against the attempt it saves)
        # — and, with a cache dir, snapshot the XLA disk-cache traffic
        # so the per-query compile.disk_* metric deltas below attribute
        # disk-served vs true cold compiles. Pure host work.
        from ..exec import persist_cache as _persist

        persist_on = bool(  # tpulint: ignore[host-sync]
            _persist.cache_root(self.session.conf))
        disk_before = _persist.disk_counters() if persist_on else None
        plan_fp = None
        if persist_on or _whole_program_joins(plan):
            try:
                plan_fp = self.plan_fingerprint()["fingerprint"]
                ctx.persist_seed = _persist.plan_seed(self.session.conf,
                                                      plan_fp)
            except Exception:
                ctx.persist_seed = None
        if getattr(self, "_rc_miss_pending", False):
            # the result-cache probe in to_arrow ran BEFORE the recorder
            # baseline above: counting the miss here (after it) lands it
            # in this run's profile counter deltas, so the executed
            # profile attributes its own result-cache miss
            self._rc_miss_pending = False
            ctx.metrics.add("result_cache.miss")
        bus = getattr(self.session, "listener_bus", None)
        cluster = getattr(self.session, "_sql_cluster", None)
        if cluster is not None:
            from .cluster_sql import ClusterDAGScheduler

            sched = ClusterDAGScheduler(
                ctx, cluster, self.session.conf.overrides(),
                listener_bus=bus)
        else:
            sched = DAGScheduler(ctx, listener_bus=bus)
        # live progress: local stages get the same in-flight feed
        # cluster tasks stream over heartbeats — a flush thread (spawned
        # through scoped_submit so the query scope rides along) samples
        # the driver-side plan_metrics into the live store while the
        # console reporter renders bars from it
        stop_flusher = None
        live = ctx.live_obs
        console_on = bool(self.session.conf.get(  # tpulint: ignore[host-sync]
            PROGRESS_CONSOLE))
        if live is not None and console_on:
            from ..obs.live import start_query_flusher

            self.session._ensure_progress_reporter()
            if ctx.plan_metrics is not None:
                stop_flusher = start_query_flusher(
                    live, ctx,
                    interval=float(  # tpulint: ignore[host-sync]
                        self.session.conf.get(PROGRESS_UPDATE_INTERVAL)))
        # per-query kernel ledger: KernelCache launch/compile events of
        # this execution window accumulate here through the query-scope
        # contextvar (copied into par_map lanes / scoped_submit pools),
        # so concurrent collects on one process read disjoint deltas
        from ..obs.metrics import (
            QueryKernelLedger, pop_query_ledger, push_query_ledger,
        )

        ctx.kernel_ledger = QueryKernelLedger()
        led_token = push_query_ledger(ctx.kernel_ledger)
        try:
            out = self._timed("execution", lambda: sched.run(plan))
        except Exception as exec_err:
            discard_pending(ctx.plan_metrics)
            # black box: a fatal execution error (chaos retry
            # exhaustion, stage-regeneration limit, ...) bundles the
            # partial evidence before the error propagates. One module
            # bool read when off; a capture failure never masks the
            # query error.
            from ..obs import blackbox

            if blackbox.ENABLED:
                try:
                    self._last_bundle = blackbox.capture_failure(
                        self, ctx, exec_err)
                except Exception:
                    ctx.metrics.add("obs.bundle_errors")
            raise
        finally:
            pop_query_ledger(led_token)
            if stop_flusher is not None:
                stop_flusher()
            if live is not None:
                live.query_finished(ctx.query_id)
            if eph_token is not None:
                pop_query(eph_token)
        # query end: resolve row counts parked during sync-free collection
        # (one host read of the distinct masks — the only device read the
        # metrics layer performs, after the last dispatch)
        if finalize_rows or recorder is not None:
            finalize_plan_metrics(ctx.plan_metrics)
        if plan_fp and ctx.persist_join_caps:
            # the process's own memory of this plan's final capacities:
            # its next execution, from any session, starts from them
            _persist.PLAN_MEMORY.put(plan_fp, ctx.persist_join_caps)
        if persist_on:
            # per-query XLA disk-cache traffic + the warm-start manifest
            # write (capacity outcomes of this run, keyed by the full
            # plan fingerprint). Never fails the query. The traffic
            # deltas come from THIS query's kernel ledger (the monitor
            # listener fires on the compiling thread, inside the query
            # scope) — scope-exact under concurrent collects; the
            # process-snapshot diff remains only as the fallback.
            try:
                snap = ctx.kernel_ledger.snapshot() \
                    if ctx.kernel_ledger is not None else None
                if snap is not None:
                    deltas = {"compile.disk_hit": snap["disk_hits"],
                              "compile.disk_miss": snap["disk_misses"]}
                else:
                    disk_after = _persist.disk_counters()
                    deltas = {key: disk_after[key] - disk_before[key]
                              for key in ("compile.disk_hit",
                                          "compile.disk_miss")}
                for key, d in deltas.items():
                    if d:
                        ctx.metrics.add(key, d)
                # measured shuffle volume of this run (adaptive history
                # re-planning food): host-side per-reducer counters the
                # map side already accumulated — zero device reads
                from ..physical.exchange import ShuffleExchangeExec

                observed = sum(
                    sum(n.last_stats.values())
                    for n in self.physical.iter_nodes()
                    if isinstance(n, ShuffleExchangeExec))
                _persist.record_manifest(
                    self.session.conf, self.plan_fingerprint(),
                    tier=getattr(self.physical, "decision", None)
                    and self.physical.decision.to_dict(),
                    join_caps=getattr(ctx, "persist_join_caps", None),
                    mesh_quotas=getattr(ctx, "persist_mesh_quotas", None),
                    prior=getattr(ctx, "persist_seed", None),
                    join_spans=getattr(ctx, "persist_join_spans", None),
                    observed_rows=observed or None)
            except Exception:
                ctx.metrics.add("cache.manifest_errors")
        if recorder is not None:
            # flight recorder close: assemble the QueryProfile, persist
            # it fingerprint-keyed, and regression-check against the
            # stored baseline. Runs AFTER the query's last device
            # interaction; a recorder failure must never fail the query
            from ..obs.history import close_query_profile

            try:
                self._last_profile, self._last_regressions = \
                    close_query_profile(self, ctx, recorder)
            except Exception:
                ctx.metrics.add("obs.profile_errors")
        # black-box close sweep (obs/blackbox.py): register this
        # execution for post-close triggers (the SLO verdict lands on
        # ticket release) and capture a diagnostic bundle if any trigger
        # finding was raised during the run. Runs AFTER the flight
        # recorder so the bundle embeds the fresh profile; one module
        # bool read when off, zero kernel launches always.
        from ..obs import blackbox

        if blackbox.ENABLED:
            try:
                self._last_bundle = blackbox.maybe_capture(self, ctx)
            except Exception:
                ctx.metrics.add("obs.bundle_errors")
        return out

    def plan_fingerprint(self) -> dict:
        """Canonical structural fingerprint of the executed physical
        plan (obs/history.py): the full hash + per-stage
        sub-fingerprints the persistent compile/result caches key by.
        Pure host work; memoized per QueryExecution (the physical plan
        is cached, so the fingerprint cannot drift under it)."""
        fp = getattr(self, "_plan_fingerprint", None)
        if fp is None:
            from ..obs.history import plan_fingerprint

            fp = self._plan_fingerprint = plan_fingerprint(
                self.physical, self.session.conf)
        return fp

    def to_arrow(self) -> pa.Table:
        import uuid

        from ..obs.tracing import pop_query, push_query
        from .listener import QueryEvent

        qid = uuid.uuid4().hex[:12]
        bus = getattr(self.session, "listener_bus", None)
        tracer = self._tracer
        # query-scope tag (NOT a buffer offset): every span this collect
        # records — on this thread, in par_map lanes (copied contexts),
        # or in cluster workers (tag ships with the task) — is stamped
        # with qid, so concurrent collects on one shared session produce
        # disjoint span sets
        qtoken = push_query(qid, tracer)
        t0 = time.perf_counter()
        if bus is not None:
            bus.post(QueryEvent("queryStarted", qid, time.time()))
        # persistent result cache (exec/persist_cache.py): a repeated
        # identical query — same plan fingerprint, same leaf data
        # versions — answers straight from the on-disk Arrow payload
        # with ZERO kernel launches (planning above is host-only work).
        # Shared across sessions, processes, and the cluster driver; the
        # plan analyzer's launch model mirrors this hit path exactly.
        from ..exec import persist_cache as _persist

        result_cache = None
        result_cache_key = None
        result_deps: list = []
        try:
            result_cache = _persist.result_cache_for(self.session.conf)
            if result_cache is not None:
                result_cache_key, result_deps = _persist.result_key(
                    self.physical, self.session.conf,
                    fingerprint=self.plan_fingerprint())
        except Exception:
            result_cache = None
        if result_cache is not None and result_cache_key is not None:
            cached = result_cache.lookup(result_cache_key)
            if cached is not None:
                # the executed path enforces the limit after collect;
                # the hit path must enforce it too (maxRows is NOT part
                # of the cache key — a lowered limit after the store
                # must still reject the oversized answer)
                limit = int(self.session.conf.get(  # tpulint: ignore[host-sync]
                    MAX_RESULT_ROWS))
                if cached.num_rows > limit:
                    err = RuntimeError(
                        f"result has {cached.num_rows} rows > "
                        "spark.tpu.collect.maxRows")
                    if bus is not None:
                        # the executed path's rejection posts queryFailed
                        # from its except handler — a started query must
                        # never be left without a terminal event
                        bus.post(QueryEvent(
                            "queryFailed", qid, time.time(),
                            duration_ms=(time.perf_counter() - t0) * 1000,
                            error=f"RuntimeError: {err}"))
                    pop_query(qtoken)
                    raise err
                metrics = self.session._metrics
                metrics.add("result_cache.hit")
                metrics.add("result_cache.hit_bytes",
                            int(cached.nbytes))  # tpulint: ignore[host-sync]
                if tracer is not None:
                    with tracer.span("result_cache.hit", cat="phase",
                                     args={"key": result_cache_key,
                                           "rows": cached.num_rows}):
                        pass
                parse_spans = self._consume_parse_spans()
                if bus is not None:
                    bus.post(QueryEvent(
                        "querySucceeded", qid, time.time(),
                        duration_ms=(time.perf_counter() - t0) * 1000,
                        phases=dict(self.phase_times),
                        plan=self.physical.tree_string(),
                        metrics={"result_cache.hit": 1},
                        plan_graph=[],
                        spans=(parse_spans + tracer.spans_for(qid))
                        if tracer is not None else []))
                pop_query(qtoken)
                return cached
            # counted inside execute() AFTER the recorder baseline, so
            # the executed run's profile attributes its own miss
            self._rc_miss_pending = True
        try:
            from ..obs.tracing import span_here

            parts = self.execute(finalize_rows=False)
            with span_here("collect", cat="phase"):
                batches = [b for p in parts for b in p]
                schema = attrs_schema(self.physical.output)
                if not batches:
                    from ..columnar.batch import ColumnarBatch

                    batches = [ColumnarBatch.empty(schema)]
                host = _planes_to_host(batches)
                with span_here("collect.arrow", cat="phase") as sp:
                    from ..columnar.batch import BY_VALUE_TYPES

                    tables = [b.to_arrow() for b in batches]
                    # the answer's columns built a Python value at a time
                    by_value = sum(isinstance(f.dataType, BY_VALUE_TYPES)
                                   for f in schema.fields)
                    sp.set_args({"rows": sum(t.num_rows for t in tables),
                                 "by_value": by_value})
                    if by_value:
                        self._last_ctx.metrics.add(
                            "collect.columns_by_value", by_value)
                    try:
                        # identical schemas concat fine even with
                        # duplicate output names (legal, as in the
                        # reference); permissive unify (which rejects
                        # duplicates) only for promotions
                        out = pa.concat_tables(tables)
                    except pa.lib.ArrowInvalid:
                        out = pa.concat_tables(
                            tables, promote_options="permissive")
            # the operators' parked row masks, counted from the result's
            # host copies where they are result planes
            from ..obs.metrics import finalize_plan_metrics

            finalize_plan_metrics(self._last_ctx.plan_metrics, host)
            limit = int(self.session.conf.get(MAX_RESULT_ROWS))
            if out.num_rows > limit:
                raise RuntimeError(
                    f"result has {out.num_rows} rows > "
                    "spark.tpu.collect.maxRows")
            if result_cache is not None and result_cache_key is not None:
                # populate the result cache (host-side IPC write; the
                # flock-safe LRU evicts past maxBytes). A store failure
                # must never fail the query.
                try:
                    if result_cache.store(result_cache_key, out,
                                          result_deps):
                        self.session._metrics.add("result_cache.store")
                        self.session._metrics.add(
                            "result_cache.bytes",
                            int(out.nbytes))  # tpulint: ignore[host-sync]
                except Exception:
                    self.session._metrics.add("result_cache.errors")
            # consume parse spans on first collect even with tracing off
            # NOW — a later traced collect must not re-report them
            parse_spans = self._consume_parse_spans()
            if bus is not None:
                from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

                counters = dict(
                    self.session._metrics.snapshot()["counters"])
                # process-absolute kernel cache/dispatch counters (the
                # per-query deltas live under kernel.* via the scheduler)
                counters.update(KC.counters())
                counters.update(
                    {f"rule.{name}_ms": round(sec * 1000, 3)
                     for name, sec, _ in self.tracker.top_rules(5)})
                bus.post(QueryEvent(
                    "querySucceeded", qid, time.time(),
                    duration_ms=(time.perf_counter() - t0) * 1000,
                    phases=dict(self.phase_times),
                    plan=self.physical.tree_string(),
                    metrics=counters,
                    plan_graph=self.plan_graph(),
                    spans=(parse_spans + tracer.spans_for(qid))
                    if tracer is not None else []))
            return out
        except Exception as e:
            if bus is not None:
                bus.post(QueryEvent(
                    "queryFailed", qid, time.time(),
                    duration_ms=(time.perf_counter() - t0) * 1000,
                    error=f"{type(e).__name__}: {e}"))
            raise
        finally:
            pop_query(qtoken)

    def _consume_parse_spans(self) -> list:
        """Parse spans ride the parsed plan (session.sql records them
        before this QueryExecution exists); consume ON FIRST COLLECT so a
        re-collected DataFrame does not re-report a parse that never
        ran."""
        spans = getattr(self.logical, "_parse_spans", None)
        if spans is None:
            return []
        try:
            del self.logical._parse_spans
        except AttributeError:
            pass
        return spans

    def plan_graph(self) -> list:
        """The executed plan as a node list with per-operator SQLMetrics
        (rows / inclusive ms / batches / attributed kernel launches and
        compile-ms) and AQE annotations (role of sqlx/execution/ui/
        SparkPlanGraph.scala — the UI renders this instead of re-parsing
        plan text)."""
        from ..obs.metrics import (
            finalize_plan_metrics, fused_members, iter_plan_metrics,
            metric_children, metric_key,
        )

        ctx = getattr(self, "_last_ctx", None)
        rec = getattr(ctx, "plan_metrics", None) or {}
        finalize_plan_metrics(rec)  # resolve any parked row masks
        nodes = []
        for node, depth, key, fields in iter_plan_metrics(self.physical,
                                                          rec):
            nodes.append({
                "id": key,
                "depth": depth,
                "op": node.graph_name()
                if hasattr(node, "graph_name") else type(node).__name__,
                "detail": node.simple_string()
                if hasattr(node, "simple_string") else "",
                **fields,
                "fused": fused_members(node) or None,
                "children": [metric_key(c) for c in metric_children(node)],
            })
        # AQE re-plan annotations: THIS query's delta over the session
        # counters (they are cumulative across queries)
        annotations = []
        if ctx is not None:
            base = getattr(self, "_adaptive_baseline", {})
            for k, v in ctx.metrics.snapshot()["counters"].items():
                if k.startswith("adaptive."):
                    d = v - base.get(k, 0)
                    if d:
                        annotations.append(f"{k} = {d}")
        if annotations:
            nodes.append({"id": 0, "depth": 0, "op": "AQE",
                          "detail": "; ".join(annotations),
                          "rows": None, "ms": None, "children": []})
        return nodes

    def analysis_report(self):
        """Static plan/trace analysis of the optimized physical plan:
        predicted kernel launches per batch per stage, fusion-boundary
        explanations, recompile and dtype-overflow hazards (role of the
        reference's debugCodegen, sqlx/execution/debug/package.scala).
        Pure host work — nothing executes on device."""
        from ..analysis.plan_lint import analyze_plan

        return analyze_plan(
            self.physical, self.session.conf,
            cluster=getattr(self.session, "_sql_cluster", None) is not None)

    def analyzed_report(self, warm: bool = True):
        """EXPLAIN ANALYZE: execute the query and annotate the physical
        plan with MEASURED per-operator metrics (rows, inclusive wall-ms,
        batches, attributed kernel launches + compile-ms — including
        inside whole-stage fused operators, whose single dispatch is
        re-attributed to the FuseStages members), side by side with the
        static analyzer's predictions. Drift between the two (measured
        launches ≠ predicted, runtime minRows gate decisions, capacity
        retries) is surfaced as first-class findings.

        The static model predicts one WARM run (kernels compiled,
        device-cached scans resident, device-scalar memos primed), so by
        default the query executes once to warm and the SECOND run is
        measured — the same steady-state discipline as
        tests/test_plan_analysis.py. Pass warm=False to measure the cold
        run (compile misses then show up as drift findings)."""
        from ..config import KERNEL_ATTRIBUTION, UI_OPERATOR_METRICS
        from ..obs.metrics import build_analyzed_report
        from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

        # the report's whole point is per-operator annotation: force
        # metrics collection AND launch attribution for the runs EXPLAIN
        # ANALYZE itself drives, even in sessions that disable them,
        # then restore the session's settings
        conf = self.session.conf
        forced = (UI_OPERATOR_METRICS, KERNEL_ATTRIBUTION)
        saved = {e.key: conf.overrides().get(e.key)
                 for e in forced if e.key in conf.overrides()}
        for e in forced:
            conf.set(e, True)
        prev_ctx = getattr(self, "_last_ctx", None)
        try:
            if warm:
                QueryExecution(self.session, self.logical).to_arrow()
            # prediction AFTER the warm run: with the persistent result
            # cache on, the warm run populates the entry the measured
            # run will hit, and the analyzer's result-probe mirror must
            # see the same cache state the measured run does (predicted
            # zero-launch hit == measured zero launches). Cache off:
            # ordering is irrelevant — the analysis is pure plan work.
            prediction = self.analysis_report()
            before_kinds = dict(KC.launches_by_kind)
            before_counters = dict(
                self.session._metrics.snapshot()["counters"])
            t0 = time.perf_counter()
            self.to_arrow()
            wall_ms = (time.perf_counter() - t0) * 1000
        finally:
            for e in forced:
                if e.key in saved:
                    conf.set(e, saved[e.key])
                else:
                    conf.unset(e)
        ctx = getattr(self, "_last_ctx", None)
        # a result-cache hit answers without executing: _last_ctx is then
        # stale (the warm run's, or absent) and the measured deltas fall
        # back to the zero-launch process snapshot
        fresh = ctx is not None and ctx is not prev_ctx
        ledger = getattr(ctx, "kernel_ledger", None) if fresh else None
        if ledger is not None:
            # scope-exact per-query deltas: concurrent collects on this
            # process (a serving workload) cannot contaminate them
            measured = {k: v for k, v in ledger.snapshot()["kinds"].items()
                        if v}
        else:
            after_kinds = dict(KC.launches_by_kind)
            measured = {k: v - before_kinds.get(k, 0)
                        for k, v in after_kinds.items()
                        if v != before_kinds.get(k, 0)}
        # cluster mode: the measured run's worker processes shipped their
        # own KernelCache deltas back with the stage results — measured
        # launches are DRIVER + WORKER totals, same ground truth the
        # per-operator attribution merge uses
        wkinds = getattr(ctx, "worker_kernel_kinds", None) if fresh \
            else None
        if wkinds:
            for k, v in wkinds.items():
                measured[k] = measured.get(k, 0) + v
        scoped = getattr(getattr(ctx, "metrics", None), "local_counters",
                         None) if fresh else None
        if scoped is not None:
            counter_deltas = {k: v for k, v in scoped().items() if v}
        else:
            after_counters = dict(
                self.session._metrics.snapshot()["counters"])
            counter_deltas = {k: v - before_counters.get(k, 0)
                              for k, v in after_counters.items()
                              if v != before_counters.get(k, 0)}
        # device-resource view of the measured run: the ledger's
        # per-query record (driver watermarks + worker peaks merged from
        # the shipped task obs) reconciles against the analyzer's
        # per-stage memory model inside the report
        from ..obs.resources import GLOBAL_LEDGER, device_peak_gbps

        resources = GLOBAL_LEDGER.query_record(
            getattr(ctx, "query_id", None))
        report = build_analyzed_report(
            self.physical, getattr(ctx, "plan_metrics", None),
            prediction, measured, counter_deltas, wall_ms,
            resources=resources,
            peak_gbps=device_peak_gbps(self.session.conf))
        # straggler findings the live telemetry raised during the
        # measured run surface as first-class EXPLAIN ANALYZE findings
        live = getattr(ctx, "live_obs", None)
        if live is not None:
            report.findings.extend(
                live.findings_for(getattr(ctx, "query_id", None)))
        return report

    def explain_string(self, mode: str = "formatted") -> str:
        if mode == "analysis":
            return "\n".join([
                "== Physical Plan ==", self.physical.tree_string(),
                self.analysis_report().render(),
            ])
        if mode == "analyze":
            return self.analyzed_report().render()
        if mode == "device":
            from ..obs import device_profile

            return device_profile.explain(self)
        parts = [
            "== Analyzed Logical Plan ==", self.analyzed.tree_string(),
            "== Optimized Logical Plan ==", self.optimized.tree_string(),
            "== Physical Plan ==", self.physical.tree_string(),
        ]
        return "\n".join(parts)
