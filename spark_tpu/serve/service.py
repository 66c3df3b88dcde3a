"""Multi-tenant query service: session routing + fair-pool admission.

The serving brain behind the SQL endpoint (connect/sql_endpoint.py).
Role of the reference's
SparkSQLSessionManager + SparkSQLOperationManager over a shared
SparkContext (sql/hive-thriftserver): many logical sessions, one
engine process — here with weighted fair-scheduler pools and plan-time
HBM admission layered in front of execution.

One QueryService wraps one long-lived "server" TpuSession:

  * `open_session()` clones a per-connection session
    (TpuSession.newSession — connection-local SET/temp views, shared
    KernelCache/warehouse/persistent caches/cluster) or hands back the
    shared server session when `spark.tpu.serve.sessionMode=shared`
    (or the caller asks for "shared").

  * `execute_sql()` / `collect()` run a statement: parsing, analysis,
    planning and the admission decision happen on the calling thread
    (pure host work, zero launches), then the collect executes inside
    the session's fair-scheduler pool slot. With an HBM budget
    configured the plan analyzer's predicted peak is pre-flighted
    through the existing `check_memory_budget` path AND reserved
    against the aggregate in-flight budget — an over-budget query
    fails plan-time, a momentarily-unfittable one queues. Admitted
    queries execute exactly as they would without the serving layer.

  * `drain()` starts graceful shutdown: new statements raise
    ServerDraining, in-flight (and already-queued) queries finish and
    flush their query profiles, then the call returns.

Tracing (obs/tracing.py), on the statement's session: span
`serve.admission` (submit to grant; `pool`) and span `serve.execute`
(grant to release; `pool`, `query`); counters `serve.granted`,
`serve.rejected_full`, `serve.rejected_timeout` and `serve.running_peak`
(the most statements that held a slot at one of this session's grants).
"""

from __future__ import annotations

import threading

from ..config import (
    MEMORY_BUDGET, SERVE_DRAIN_TIMEOUT, SERVE_POOL, SERVE_SESSION_MODE,
)
from ..errors import AdmissionTimeout, PoolQueueFull, ServerDraining
from .pools import FairScheduler, pool_configs

__all__ = ["QueryService"]


class QueryService:
    def __init__(self, session):
        self.session = session
        self.scheduler = FairScheduler(session.conf)
        self._lock = threading.Lock()
        self.sessions_opened = 0
        self._open: list = []       # one entry a connection, until closed
        self.drain_snapshot = None
        # service metrics plane (spark.tpu.metrics.export): wire the
        # scrape sources over this service's pools/session and start
        # the time-series ticker — structurally nothing when off
        from ..obs import export as _export

        _export.configure(session.conf)
        if _export.ENABLED:
            _export.register_default_sources(session=session,
                                             scheduler=self.scheduler)
            _export.start_ticker()

    # -- sessions ---------------------------------------------------------
    def open_session(self, mode: str | None = None):
        """A session for one connection/tenant: a clone by default, the
        shared server session when the server (or this caller) opts
        into 'shared'."""
        if self.scheduler.draining:
            raise ServerDraining()
        mode = mode or str(self.session.conf.get(SERVE_SESSION_MODE))
        opened = self.session if mode == "shared" \
            else self.session.newSession()
        with self._lock:
            self.sessions_opened += 1
            self._open.append(opened)
        return opened

    def close_session(self, session) -> None:
        """A connection's end: its session leaves `sessions()`. Nothing
        is stopped: a clone owns no service, and the shared session is
        the server's."""
        with self._lock:
            for i, s in enumerate(self._open):
                if s is session:
                    del self._open[i]
                    return

    def sessions(self) -> list:
        """The sessions opened and not closed, each once."""
        with self._lock:
            return list({id(s): s for s in self._open}.values())

    # -- execution --------------------------------------------------------
    def _predicted_hbm(self, qe, conf) -> int:
        """Plan-time HBM reservation for admission (zero launches). Only
        computed when some budget is configured — otherwise the analyzer
        is skipped entirely and admission is slot-only."""
        budget = int(conf.get(MEMORY_BUDGET))
        if budget <= 0 and not any(p.hbm_budget
                                   for p in pool_configs(conf).values()):
            return 0
        report = qe.analysis_report()
        # same pre-flight execute() would run — but HERE, before the
        # query ever queues, so an over-budget plan rejects immediately
        # with the named stage instead of waiting out a queue slot
        from ..obs.resources import check_memory_budget

        check_memory_budget(
            qe.physical, conf, report=report,
            cluster=getattr(qe.session, "_sql_cluster", None) is not None)
        # hand the report to execute()'s own pre-flight so the serving
        # hot path analyzes each plan ONCE, not twice
        qe._preflight_report = report
        return int(report.predicted_peak_hbm or 0)

    def collect(self, session, df, pool: str | None = None,
                timeout: float | None = None):
        """Admit one DataFrame collect through the session's pool."""
        if self.scheduler.draining:
            raise ServerDraining()
        qe = df.query_execution
        conf = session.conf
        hbm = self._predicted_hbm(qe, conf)
        if pool is None:
            pool = str(conf.get(SERVE_POOL) or "default")
        metrics, tracer = session._metrics, session.tracer
        try:
            with tracer.span("serve.admission", cat="serve",
                             args={"pool": pool}):
                ticket = self.scheduler.submit(pool, hbm=hbm)
                self.scheduler.wait(ticket, timeout=timeout)
        except Exception as admission_err:
            if isinstance(admission_err, PoolQueueFull):
                metrics.add("serve.rejected_full")
            elif isinstance(admission_err, AdmissionTimeout):
                metrics.add("serve.rejected_timeout")
            # black box: an admission rejection (queue full / timeout)
            # bundles the serving/metrics state that explains it
            # (rate-limited; never masks the rejection itself)
            from ..obs import blackbox

            if blackbox.ENABLED:
                try:
                    blackbox.record_rejection(self.session, admission_err,
                                              pool=pool, qe=qe)
                except Exception:
                    pass
            raise
        metrics.add("serve.granted")
        metrics.peak("serve.running_peak", ticket.running_at_grant)
        with tracer.span("serve.execute", cat="serve",
                         args={"pool": pool}) as span:
            try:
                table = df.toArrow()
                ctx = getattr(qe, "_last_ctx", None)
                if ctx is not None:
                    qid = getattr(ctx, "query_id", None)
                    self.scheduler.note_query(ticket, qid)
                    span.set_args({"query": qid})
                return table
            finally:
                # an SLO breach at release becomes an obs.slo finding on
                # the query's live record — the list EXPLAIN ANALYZE and
                # pool status already surface
                finding = self.scheduler.release(ticket)
                if finding is not None:
                    live = getattr(self.session, "live_obs", None)
                    if live is not None:
                        live.add_finding(ticket.query_id, finding)

    def execute_sql(self, session, sql: str):
        """One SQL statement for one session. Commands and other
        host-only statements (their result is a bare local relation —
        SET, DDL, SHOW) return without admission; real queries collect
        inside the session's pool slot."""
        if self.scheduler.draining:
            raise ServerDraining()
        out = session.sql(sql)
        if out is None or not hasattr(out, "toArrow"):
            return out
        from ..plan.logical import LocalRelation

        if isinstance(getattr(out, "plan", None), LocalRelation):
            # command result: already materialized host metadata
            return out.toArrow()
        # per-statement /*+ POOL(x) */ hint (session.sql validated it
        # against the declared pools and stamped the DataFrame)
        return self.collect(session, out,
                            pool=getattr(out, "_pool_hint", None))

    # -- lifecycle / status -----------------------------------------------
    def drain(self, timeout: float | None = None) -> bool:
        """Graceful shutdown: reject new queries (ServerDraining), let
        in-flight and already-queued queries finish — their close-time
        query profiles flush as part of normal query close — and
        return True when everything quiesced inside the timeout."""
        if timeout is None:
            timeout = float(self.session.conf.get(SERVE_DRAIN_TIMEOUT))
        self.scheduler.drain()
        ok = self.scheduler.quiesce(timeout)
        from ..obs import export as _export

        if _export.ENABLED:
            # drain-time snapshot: one last tick so the ring's tail is
            # the quiesced state, then freeze the time series
            _export.tick_once()
            self.drain_snapshot = _export.timeseries_snapshot()
            _export.stop_ticker()
        return ok

    def status(self) -> dict:
        """Per-pool live serving status incl. SLO findings from the
        live store (stragglers/regressions of each pool's recent
        queries) and — with the metrics plane on — sparkline series
        from the time-series ring."""
        st = self.scheduler.status(
            live_obs=getattr(self.session, "live_obs", None))
        st["sessions_opened"] = self.sessions_opened
        from ..obs import export as _export

        if _export.ENABLED:
            st["sparklines"] = _export.sparklines()
            if self.drain_snapshot is not None:
                st["drain_timeseries"] = self.drain_snapshot
        from ..obs import blackbox

        if blackbox.ENABLED:
            from ..config import OBS_BUNDLE_DIR

            bdir = str(self.session.conf.get(OBS_BUNDLE_DIR) or "")
            if bdir:
                st["bundles"] = blackbox.list_bundles(bdir)[:8]
        return st
