"""TpuSession — the SparkSession equivalent.

Role of the reference's SparkSession (sql/api .../SparkSession.scala; classic
impl sql/core/.../classic/SparkSession.scala) + the SparkContext/SparkEnv
bootstrap (core/SparkContext.scala, core/SparkEnv.scala:587): wires conf,
catalog, analyzer, optimizer, planner, and the JAX device runtime.
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Iterable, Sequence

import numpy as np
import pyarrow as pa

from ..config import SQLConf
from ..exec.context import Metrics
from ..plan.analyzer import Analyzer
from ..plan.catalog import Catalog
from ..plan.logical import LocalRelation, RangeRelation
from ..plan.optimizer import Optimizer
from ..expr.expressions import AttributeReference
from ..types import StructType, from_arrow_type, int64

_jax_initialized = False
_init_lock = threading.Lock()

# per-statement fair-scheduler pool hint: /*+ POOL(x) */ anywhere in the
# statement text (the reference's ResolveHints COALESCE/REPARTITION hint
# comment syntax, applied to serving admission)
_POOL_HINT_RE = re.compile(
    r"/\*\+\s*POOL\s*\(\s*([A-Za-z0-9_.\-]+)\s*\)\s*\*/", re.IGNORECASE)


def _init_jax():
    """Enable x64 (int64 sums/hashes; XLA emulates on TPU with int32 pairs —
    SURVEY.md §7 'Hard parts' (6)) exactly once, before any tracing. Also
    raises the recursion limit — expression-tree recursion uses several
    frames per node (the reference raises JVM stack size for Catalyst for
    the same reason)."""
    global _jax_initialized
    import sys

    if sys.getrecursionlimit() < 20000:
        sys.setrecursionlimit(20000)
    with _init_lock:
        if _jax_initialized:
            return
        import jax

        jax.config.update("jax_enable_x64", True)
        _jax_initialized = True


class SessionBuilder:
    def __init__(self):
        self._conf: dict[str, Any] = {}
        self._name = "spark-tpu"

    def appName(self, name: str) -> "SessionBuilder":
        self._name = name
        return self

    def master(self, master: str) -> "SessionBuilder":
        # accepted for API compatibility; local[n] sets default parallelism
        if master.startswith("local[") and master.endswith("]"):
            n = master[6:-1]
            if n != "*":
                self._conf["spark.default.parallelism"] = int(n)
        return self

    def config(self, key=None, value=None, **kw) -> "SessionBuilder":
        if key is not None:
            self._conf[key] = value
        self._conf.update(kw)
        return self

    def getOrCreate(self) -> "TpuSession":
        if TpuSession._active is not None:
            for k, v in self._conf.items():
                TpuSession._active.conf.set(k, v)
            return TpuSession._active
        return TpuSession(self._name, self._conf)


class TpuSession:
    _active: "TpuSession | None" = None

    builder = None  # replaced below by property-like helper

    def __init__(self, name: str = "spark-tpu",
                 conf: dict[str, Any] | None = None):
        _init_jax()
        self.name = name
        self.conf = SQLConf(conf)
        self.catalog_ = Catalog(self.conf.case_sensitive)
        wh_dir = self.conf.get("spark.sql.warehouse.dir")
        if wh_dir:
            from ..exec import persist_cache as _pc
            from ..plan.warehouse import Warehouse

            # every catalog write (save/append/overwrite/drop) drops the
            # persistent result-cache entries depending on the table —
            # a no-op while spark.tpu.cache.dir is unset
            self.catalog_.external = Warehouse(
                str(wh_dir),
                on_write=lambda p, _c=self.conf:
                _pc.invalidate_path(_c, p))
        self._analyzer = Analyzer(self.catalog_, self.conf.case_sensitive)
        self._optimizer = Optimizer()
        self._metrics = Metrics()
        self._table_stats: dict[str, Any] = {}  # ANALYZE TABLE output
        self._cached: dict[int, Any] = {}
        self._streams: list = []
        from ..exec.listener import EventLoggingListener, ListenerBus
        from ..obs.tracing import Tracer

        # always-on span tracing (spark.tpu.trace.enabled flips it live);
        # pure host bookkeeping — see obs/tracing.py
        self.tracer = Tracer(conf=self.conf)
        from ..obs import resources as _resources

        # device-resource ledger + kernel cost capture switches
        # (spark.tpu.memory.ledger / spark.tpu.metrics.kernelCost) —
        # process-global like the KernelCache, configured per session
        _resources.configure(self.conf)
        from ..columnar import encoding as _encoding

        # compressed-execution ingest harvest (spark.tpu.encoding.enabled)
        _encoding.configure(self.conf)
        from ..utils import faults as _faults

        # deterministic fault injection (spark.tpu.faults.*) — off by
        # default; chaos runs flip it per session and the rules ship to
        # workers with the rest of the conf
        _faults.configure(self.conf)
        from ..utils import lockwatch as _lockwatch

        # runtime lock-discipline watching (spark.tpu.lockwatch.enabled)
        # — off by default: raw unwrapped locks, zero overhead; on per
        # session by the option, or by SPARK_TPU_LOCKWATCH=1
        _lockwatch.configure(self.conf)
        from ..exec import persist_cache as _persist

        # persistent XLA compile cache: placed by
        # JAX_COMPILATION_CACHE_DIR, else <spark.tpu.cache.dir>/xla,
        # else the fixed in-checkout .cache/xla; installs the
        # disk-hit/miss event counters. Conf ships to workers, whose
        # begin_stage_obs makes the same call.
        _persist.configure(self.conf)
        from ..obs import export as _export

        # service metrics plane (spark.tpu.metrics.export) — off by
        # default: no registry sampling, no ticker thread, Prometheus
        # endpoints report disabled. QueryService wires the scrape
        # sources; here the switch itself is applied session-wide.
        _export.configure(self.conf)
        from ..obs.live import LiveObs

        # live telemetry store: heartbeat-streamed worker obs partials,
        # in-flight stage progress, straggler findings (obs/live.py) —
        # created BEFORE the conf-driven cluster attach so the cluster's
        # heartbeat handler has a sink from its first beat
        self.live_obs = LiveObs(conf=self.conf)
        from ..obs import blackbox as _blackbox

        # query black box (spark.tpu.obs.bundles): anomaly-triggered
        # diagnostic bundle capture. Off by default — configure() leaves
        # the module bool False and every call site stays one attribute
        # read. The live store's finding sink routes POST-CLOSE trigger
        # findings (the SLO verdict lands on ticket release) into the
        # capture layer; the sink itself no-ops unless armed.
        _blackbox.configure(self.conf)
        self.live_obs.finding_sink = (
            lambda qid, f, _s=self: _blackbox.on_finding(_s, qid, f))
        self._progress_reporter = None
        self.listener_bus = ListenerBus()
        if str(self.conf.get("spark.eventLog.enabled", "false")).lower() \
                == "true":
            log_dir = self.conf.get("spark.eventLog.dir", "/tmp/spark-events")
            self.listener_bus.register(EventLoggingListener(log_dir))
        self._maybe_attach_conf_cluster()
        TpuSession._active = self

    def _maybe_attach_conf_cluster(self) -> None:
        """Conf-driven cluster attach (the spark-submit --master flow):
        spark.tpu.master=grpc://host:port joins a standalone master
        (deploy/standalone.py); spark.tpu.cluster.enabled=true spawns a
        local process cluster (the reference's local-cluster mode)."""
        import os

        master = str(self.conf.get("spark.tpu.master", "") or "")
        push = str(self.conf.get("spark.tpu.shuffle.push",
                                 "false")).lower() == "true"
        if master.startswith(("grpc://", "spark://")):
            from ..deploy.standalone import StandaloneCluster

            secret = (self.conf.get("spark.tpu.master.secret")
                      or os.environ.get("SPARK_TPU_MASTER_SECRET"))
            if not secret:
                raise ValueError(
                    "spark.tpu.master set but no secret: provide "
                    "spark.tpu.master.secret or SPARK_TPU_MASTER_SECRET")
            from ..config import HEARTBEAT_INTERVAL

            self._sql_cluster = StandaloneCluster(
                master, str(secret),
                int(self.conf.get("spark.executor.instances", 2)),
                app_name=self.name, push_shuffle=push,
                heartbeat_interval=float(self.conf.get(
                    HEARTBEAT_INTERVAL)))
        elif str(self.conf.get("spark.tpu.cluster.enabled",
                               "false")).lower() == "true":
            from ..config import HEARTBEAT_INTERVAL
            from ..exec.cluster import LocalCluster

            self._sql_cluster = LocalCluster(
                num_workers=int(self.conf.get("spark.tpu.cluster.workers",
                                              2)),
                push_shuffle=push,
                heartbeat_interval=float(self.conf.get(
                    HEARTBEAT_INTERVAL)))
        if getattr(self, "_sql_cluster", None) is not None:
            self._wire_cluster_obs(self._sql_cluster)

    def _wire_cluster_obs(self, cluster) -> None:
        """Point the cluster's heartbeat telemetry at this session's
        live store (executor heartbeats carry per-task obs partials)."""
        if hasattr(cluster, "obs_sink"):
            cluster.obs_sink = self.live_obs.on_heartbeat

    def newSession(self) -> "TpuSession":
        """Per-connection session clone (reference: SparkSession
        .newSession + the thriftserver's session-per-connection model).

        The clone gets its OWN conf (seeded from this session's current
        overrides — SET stays connection-local), its own temp-view
        catalog and SQL variables (reading THROUGH to this session's:
        views registered on the server session stay visible, views the
        clone registers stay local), and its own metrics/tracer/
        listener bus. It SHARES everything expensive and process-wide:
        the KernelCache (module-global), the warehouse catalog with its
        result-cache invalidation hook, the persistent caches under
        spark.tpu.cache.dir, the live-obs store, the block manager, and
        any attached cluster. stop() on a clone never tears the shared
        services down."""
        import collections

        from ..exec.listener import ListenerBus
        from ..obs.tracing import Tracer

        clone = object.__new__(TpuSession)
        clone.name = self.name
        clone.conf = SQLConf(self.conf.overrides())
        clone.catalog_ = Catalog(clone.conf.case_sensitive)
        clone.catalog_.external = self.catalog_.external
        # read-through temp views/variables: clone registrations land in
        # the first map (connection-local), parent registrations stay
        # visible; dropping a parent view from a clone is a no-op
        clone.catalog_._tables = collections.ChainMap(
            {}, self.catalog_._tables)
        clone.catalog_.variables = collections.ChainMap(
            {}, self.catalog_.variables)
        clone._analyzer = Analyzer(clone.catalog_,
                                   clone.conf.case_sensitive)
        clone._optimizer = Optimizer()
        clone._metrics = Metrics()
        clone._table_stats = self._table_stats      # shared ANALYZE stats
        clone._cached = self._cached                # shared cached plans
        clone._streams = []
        clone.tracer = Tracer(conf=clone.conf)
        clone.live_obs = self.live_obs              # one live store
        clone._progress_reporter = None
        clone.listener_bus = ListenerBus()
        cl = getattr(self, "_sql_cluster", None)
        if cl is not None:
            clone._sql_cluster = cl
        clone._block_manager = self.block_manager   # shared pin budgets
        clone._shared_services = True
        return clone

    @property
    def listenerManager(self):
        return self.listener_bus

    # ------------------------------------------------------------------
    def _planner(self):
        from ..physical.planner import Planner

        return Planner(
            self.conf,
            cluster=getattr(self, "_sql_cluster", None) is not None)

    # ------------------------------------------------------------------
    @property
    def read(self):
        from .readwriter import DataFrameReader

        return DataFrameReader(self)

    def table(self, name: str):
        from .dataframe import DataFrame
        from ..plan.logical import UnresolvedRelation

        return DataFrame(self, UnresolvedRelation(name.split(".")))

    def sql(self, query: str, **kwargs):
        from ..plan.commands import Command, run_command
        from ..plan.logical import WithCTE
        from ..sql.parser import parse_sql
        from .dataframe import DataFrame

        from ..sql.scripting import execute_script, is_script

        if is_script(query):
            return execute_script(self, query)
        # per-statement pool hint: /*+ POOL(x) */ routes THIS statement
        # to the named fair-scheduler pool (serve/pools.py). Validated
        # here — an unknown pool is a typed error naming the declared
        # pools, not a silent fallback to 'default'. The hint is
        # stripped before parse and stamped on the DataFrame for the
        # serving layer's admission call.
        pool_hint = None
        m = _POOL_HINT_RE.search(query)
        if m is not None:
            pool_hint = m.group(1)
            query = query[:m.start()] + query[m.end():]
            from ..errors import UnknownPoolError
            from ..serve.pools import pool_configs

            valid = list(pool_configs(self.conf))
            if pool_hint not in valid:
                raise UnknownPoolError(pool_hint, valid)
        import uuid as _uuid

        from ..obs.tracing import pop_query, push_query

        # parse predates the collect's query id — tag its spans with a
        # private scope so concurrent sql() calls on a shared session
        # can't capture each other's parse work (the old mark()/since()
        # buffer slice could)
        pqid = f"parse-{_uuid.uuid4().hex[:8]}"
        qtoken = push_query(pqid)
        try:
            with self.tracer.span("parse", cat="phase"):  # no-op when off
                plan = parse_sql(query)
        finally:
            pop_query(qtoken)
        if isinstance(plan, Command):
            return run_command(self, plan)
        if isinstance(plan, WithCTE):
            plan = self._materialize_ctes(plan)
        # the parse span predates the QueryExecution — ride it on the
        # parsed plan so to_arrow's event includes the full lifecycle
        parse_spans = self.tracer.spans_for(pqid)
        if parse_spans:
            try:
                plan._parse_spans = parse_spans
            except Exception:
                pass
        df = DataFrame(self, plan)
        if pool_hint is not None:
            df._pool_hint = pool_hint
        return df

    def _materialize_ctes(self, wplan):
        """Execute each multiply-referenced CTE once and splice the
        result into every call site as an in-memory relation (WithCTE /
        CTERelationRef role — see plan/logical.py WithCTE). Every splice
        site gets FRESH attribute ids over the SHARED source: a
        correlated subquery referencing the same CTE as its outer query
        (q1/q30's ctr1/ctr2) must see distinct ids or decorrelation
        cannot tell inner from outer."""
        from .dataframe import DataFrame

        mapping = {}
        for uniq, body in wplan.materializations:
            # this part of a query runs inside sql(), before the outer
            # plan has a QueryExecution: the span puts it on the timeline
            # (the body's own execution has its spans under it); `uniq`
            # is sql/parser._apply_ctes's `__cte_mat_<name>_<8 hex>`
            name = uniq.removeprefix("__cte_mat_").rsplit("_", 1)[0]
            with self.tracer.span("cte.materialize", cat="phase",
                                  args={"cte": name}) as sp:
                body = self._splice_relations(body, mapping)
                table = DataFrame(self, body).toArrow()
                rel = self.createDataFrame(table).plan
                sp.set_args({"rows": table.num_rows})
            mapping[uniq.lower()] = rel
        return self._splice_relations(wplan.child, mapping)

    def _splice_relations(self, plan, mapping):
        from ..expr.expressions import AttributeReference
        from ..plan import logical as L
        from ..plan.subquery import SubqueryExpression

        def fresh(rel):
            attrs = [AttributeReference(a.name, a.dtype, a.nullable)
                     for a in rel.output]
            if isinstance(rel, L.LocalRelation):
                return L.LocalRelation(attrs, rel.table)
            return L.LogicalRelation(rel.source, attrs, rel.name)

        def fix_expr(ex):
            if isinstance(ex, SubqueryExpression):
                return ex.copy(plan=self._splice_relations(ex.plan, mapping))
            return ex

        def rule(node):
            if isinstance(node, L.UnresolvedRelation):
                rel = mapping.get(node.name.lower())
                if rel is not None:
                    return fresh(rel)
            return node.map_expressions(lambda e: e.transform_up(fix_expr))

        return plan.transform_up(rule)

    def range(self, start: int, end: int | None = None, step: int = 1,
              numPartitions: int | None = None):
        from .dataframe import DataFrame

        if end is None:
            start, end = 0, start
        n = numPartitions or int(self.conf.get("spark.default.parallelism", 8))
        return DataFrame(self, RangeRelation(start, end, step, n))

    def createDataFrame(self, data, schema=None):
        from .dataframe import DataFrame

        table = _to_arrow_table(data, schema)
        attrs = [AttributeReference(f.name, from_arrow_type(f.type),
                                    f.nullable)
                 for f in table.schema]
        return DataFrame(self, LocalRelation(attrs, table))

    # ------------------------------------------------------------------
    @property
    def readStream(self):
        from ..streaming.api import DataStreamReader

        return DataStreamReader(self)

    @property
    def streams(self):
        return _StreamsApi(self)

    def memory_stream(self, schema=None):
        """Create a MemoryStream + its DataFrame (test helper; reference:
        MemoryStream[T].toDF)."""
        from ..streaming.query import StreamingRelation
        from ..streaming.sources import MemoryStream
        from .dataframe import DataFrame

        src = MemoryStream(schema)
        if schema is None:
            raise ValueError("memory_stream requires a pyarrow schema")
        return src, DataFrame(self, StreamingRelation(src))

    # ------------------------------------------------------------------
    @property
    def catalog(self):
        return _CatalogApi(self)

    def startUI(self, port: int = 0):
        """Start the live web UI (core/ui/SparkUI.scala role); returns
        the SparkUI with `.url`."""
        from ..exec.ui import SparkUI

        self._ui = SparkUI(self, port=port).start()
        return self._ui

    def attachSqlCluster(self, cluster) -> "TpuSession":
        """Route non-result SQL stages to a process cluster
        (exec/cluster_sql.py — the multi-host stage execution contract)."""
        self._sql_cluster = cluster
        self._wire_cluster_obs(cluster)
        return self

    def _ensure_progress_reporter(self):
        """Start the console progress reporter on first use
        (spark.tpu.progress.console — ConsoleProgressBar role); lives
        until session stop."""
        if self._progress_reporter is None:
            from ..obs.live import ConsoleProgressReporter

            self._progress_reporter = ConsoleProgressReporter(
                self.live_obs, conf=self.conf).start()
        return self._progress_reporter

    def detachSqlCluster(self) -> "TpuSession":
        self._sql_cluster = None
        return self

    def capture_diagnostics(self, df=None) -> str | None:
        """Explicitly capture a diagnostic bundle (obs/blackbox.py) —
        the operator's on-demand black-box pull. With a DataFrame, the
        bundle covers its last execution (plan reports, recorded
        metrics, profile + history); without one, the most recently
        closed query if the capture layer is armed, else a
        session-level bundle (serving/metrics/fleet state only).
        Requires spark.tpu.obs.bundleDir; works with the anomaly
        trigger (spark.tpu.obs.bundles) off. Returns the bundle id, or
        None when no bundle dir is configured."""
        from ..obs import blackbox

        qe = ctx = None
        if df is not None:
            qe = df.query_execution
            ctx = getattr(qe, "_last_ctx", None)
        else:
            recent = blackbox.most_recent()
            if recent is not None:
                qe, ctx = recent
        return blackbox.capture(self, qe=qe, ctx=ctx, reason="manual")

    def stop(self) -> None:
        # a newSession() clone shares the cluster/block manager with its
        # parent — stopping the clone must not tear those down
        shared = getattr(self, "_shared_services", False)
        pr = getattr(self, "_progress_reporter", None)
        if pr is not None:
            try:
                pr.stop()
            except Exception:
                pass
            self._progress_reporter = None
        for q in self._streams:
            try:
                q.stop()
            except Exception:
                pass
        self._streams.clear()
        rc = getattr(self, "_rdd_context", None)
        if rc is not None:
            rc.stop()
        ui = getattr(self, "_ui", None)
        if ui is not None:
            try:
                ui.stop()
            except Exception:
                pass
            self._ui = None
        cl = getattr(self, "_sql_cluster", None)
        if cl is not None:
            if not shared:
                try:
                    cl.stop()
                except Exception:
                    pass
            self._sql_cluster = None
        bm = getattr(self, "_block_manager", None)
        if bm is not None:
            if not shared:
                try:
                    bm.clear()
                except Exception:
                    pass
            self._block_manager = None
        if TpuSession._active is self:
            TpuSession._active = None

    @property
    def block_manager(self):
        """Session block store: cached tables live here under tiered
        budgets (device pins / host RAM / disk) with LRU eviction —
        role of core/storage/BlockManager.scala + MemoryStore/DiskStore."""
        bm = getattr(self, "_block_manager", None)
        if bm is None:
            from ..exec.block_store import BlockManager

            spill = str(self.conf.get("spark.local.dir", "") or "") or None
            bm = self._block_manager = BlockManager(
                self.conf, spill_dir=spill, metrics=self._metrics)
        return bm

    @staticmethod
    def _table_to_ipc(table) -> bytes:
        import pyarrow as pa

        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        return sink.getvalue().to_pybytes()

    def _cache_df(self, df):
        """Materialize once and register (analyzed plan → block id): ANY
        later query containing a semantically equal subtree is rewritten
        to scan the cached block (role of CacheManager.useCachedData,
        sqlx/columnar/CacheManager.scala + QueryExecution
        withCachedData). The bytes live in the tiered block store, so a
        cache bigger than the memory budget degrades to disk and then to
        recompute-from-lineage — it never pins unbounded RAM."""
        import uuid

        analyzed = df.query_execution.analyzed
        for plan, _attrs, _bid in self._cached.values():
            if plan.fast_equals(analyzed):
                return df
        table = df.toArrow()
        block_id = f"cache-{uuid.uuid4().hex[:12]}"
        self.block_manager.put(block_id, self._table_to_ipc(table))
        # unique token key (id(df) recycles after GC and would silently
        # evict an unrelated entry)
        self._cached[object()] = (analyzed, list(analyzed.output), block_id)
        return df

    def _uncache_df(self, df):
        analyzed = df.query_execution.analyzed
        for k, (plan, _attrs, bid) in list(self._cached.items()):
            if plan.fast_equals(analyzed):
                self.block_manager.remove(bid)
                del self._cached[k]
        return df

    def _cached_relation(self, analyzed, attrs, block_id):
        """Block bytes → LocalRelation; a dropped block re-materializes
        from lineage (the RDD recompute-on-miss contract,
        BlockManager.getOrElseUpdate role) and re-enters the store."""
        import pyarrow as pa

        from .dataframe import DataFrame

        data = self.block_manager.get(block_id)
        if data is None:
            guard = getattr(self, "_recomputing", None)
            if guard is None:
                guard = self._recomputing = set()
            if block_id in guard:
                return None     # already rebuilding below us — compute raw
            guard.add(block_id)
            try:
                table = DataFrame(self, analyzed).toArrow()
            finally:
                guard.discard(block_id)
            self._metrics.add("cache.recomputed_from_lineage")
            self.block_manager.put(block_id, self._table_to_ipc(table))
        else:
            table = pa.ipc.open_stream(pa.BufferReader(data)).read_all()
        return LocalRelation(attrs, table)

    def _use_cached(self, plan):
        """Substitute cached fragments into an analyzed plan. One
        relation per block per call (memo): a self-join of a cached
        frame shares a single deserialized table instead of two."""
        if not self._cached:
            return plan
        entries = list(self._cached.values())
        memo: dict = {}

        def rule(node):
            for cached_plan, attrs, block_id in entries:
                if node.fast_equals(cached_plan):
                    if block_id not in memo:
                        memo[block_id] = self._cached_relation(
                            cached_plan, attrs, block_id)
                    if memo[block_id] is not None:
                        return memo[block_id]
            return node

        return plan.transform_up(rule)

    def version(self) -> str:
        from .. import __version__

        return __version__


class _StreamsApi:
    def __init__(self, session):
        self.s = session

    @property
    def active(self):
        return [q for q in self.s._streams if q.isActive]

    def awaitAnyTermination(self, timeout=None):
        for q in list(self.s._streams):
            q.awaitTermination(timeout)


class _CatalogApi:
    def __init__(self, session: TpuSession):
        self.s = session

    def listTables(self):
        return self.s.catalog_.list_tables()

    def dropTempView(self, name: str) -> bool:
        return self.s.catalog_.drop(name)

    def tableExists(self, name: str) -> bool:
        try:
            self.s.catalog_.lookup(name.split("."))
            return True
        except Exception:
            return False

    def listColumns(self, table: str):
        """Column name/type/nullable rows for a table (pyspark
        Catalog.listColumns shape)."""
        plan = self.s.catalog_.lookup(table.split("."))
        from ..exec.query_execution import QueryExecution

        analyzed = QueryExecution(self.s, plan).analyzed
        return [{"name": a.name, "dataType": str(a.dtype),
                 "nullable": bool(a.nullable)} for a in analyzed.output]

    def listFunctions(self, pattern: str | None = None):
        """Registered SQL function names (Catalog.listFunctions role)."""
        from ..expr.registry import filter_names

        return filter_names(pattern)

    def functionExists(self, name: str) -> bool:
        from ..expr.registry import function_exists

        return function_exists(name)

    def cacheTable(self, name: str) -> None:
        # command layer directly: an f-string SQL round trip would break
        # on names that aren't lexable identifiers
        from ..plan.commands import CacheTableCommand, run_command

        run_command(self.s, CacheTableCommand(name))

    def uncacheTable(self, name: str) -> None:
        from ..plan.commands import CacheTableCommand, run_command

        run_command(self.s, CacheTableCommand(name, uncache=True))


def _to_arrow_table(data, schema) -> pa.Table:
    from ..types import StructType as ST, to_arrow_type

    if isinstance(data, pa.Table):
        return data
    try:
        import pandas as pd

        if isinstance(data, pd.DataFrame):
            return pa.Table.from_pandas(data, preserve_index=False)
    except ImportError:
        pass
    if isinstance(data, dict):
        return pa.table(data)
    if isinstance(data, (list, tuple)):
        if not data:
            raise ValueError("cannot infer schema from empty data")
        first = data[0]
        if isinstance(first, dict):
            names = list(first.keys())
            cols = {n: [r.get(n) for r in data] for n in names}
            return pa.table(cols)
        if isinstance(first, (list, tuple)):
            if schema is None:
                raise ValueError("schema required for list-of-tuples")
            if isinstance(schema, ST):
                names = schema.names
                arrays = []
                for i, f in enumerate(schema.fields):
                    arrays.append(pa.array([r[i] for r in data],
                                           type=to_arrow_type(f.dataType)))
                return pa.table(arrays, names=names)
            names = list(schema)
            cols = {n: [r[i] for r in data] for i, n in enumerate(names)}
            return pa.table(cols)
    raise TypeError(f"cannot create DataFrame from {type(data)}")


class _Builder:
    def __get__(self, obj, objtype=None):
        return SessionBuilder()


TpuSession.builder = _Builder()

# Spark-compatible alias
SparkSession = TpuSession
