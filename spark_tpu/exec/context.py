"""Execution context shared across a query run.

Role of the reference's TaskContext + SQLMetrics plumbing (core/TaskContext,
sqlx/metric/SQLMetrics.scala:35): carries session conf and accumulates
per-operator metrics.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field

from ..config import SQLConf


class Metrics:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: dict[str, int] = defaultdict(int)
        self.timers: dict[str, float] = defaultdict(float)

    def add(self, name: str, v: int = 1) -> None:
        with self._lock:
            self.counters[name] += v

    def peak(self, name: str, v: int) -> None:
        """A counter that keeps the most it was shown."""
        with self._lock:
            if v > self.counters[name]:
                self.counters[name] = v

    def time(self, name: str):
        return _Timer(self, name)

    def snapshot(self) -> dict:
        with self._lock:
            return {"counters": dict(self.counters),
                    "timers": dict(self.timers)}


class ScopedMetrics(Metrics):
    """Per-query view over the session Metrics.

    Every add() lands on BOTH the session-global counters (unchanged
    behavior: listeners and tests keep reading cumulative
    session totals) and a query-local copy, so close-time consumers
    (query profiles, EXPLAIN ANALYZE counter deltas) read scope-exact
    per-query deltas instead of process-snapshot differences that
    concurrent queries on one session would contaminate.
    snapshot() deliberately stays the SESSION view — existing callers
    (plan_graph's adaptive baseline) diff session-cumulative counters."""

    def __init__(self, base: Metrics):
        super().__init__()
        self.base = base

    def add(self, name: str, v: int = 1) -> None:
        self.base.add(name, v)
        super().add(name, v)

    def time(self, name: str):
        return self.base.time(name)

    def snapshot(self) -> dict:
        return self.base.snapshot()

    def local_counters(self) -> dict:
        """This query's own counter increments (scope-exact)."""
        with self._lock:
            return dict(self.counters)


class _Timer:
    def __init__(self, m: Metrics, name: str):
        self.m = m
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        with self.m._lock:
            self.m.timers[self.name] += time.perf_counter() - self.t0
        return False


@dataclass
class ExecContext:
    conf: SQLConf = field(default_factory=SQLConf)
    metrics: Metrics = field(default_factory=Metrics)
    _memory: object = field(default=None, repr=False)
    # session BlockManager when the query runs under one (device-pin
    # budget for scan caches; None in bare contexts/workers)
    block_manager: object = field(default=None, repr=False)
    # id(physical node) → obs.metrics op record (rows/ms/batches/launch
    # attribution) when per-operator SQLMetrics collection is on
    # (ui/SparkPlanGraph role); None = no profiling
    plan_metrics: dict | None = field(default=None, repr=False)
    # session Tracer when span tracing is on (obs/tracing.py); None = off
    tracer: object = field(default=None, repr=False)
    # attribute KernelCache launches to the executing operator
    # (spark.tpu.metrics.kernelAttribution, resolved once per query)
    kernel_attribution: bool = field(default=True, repr=False)
    # cluster mode: per-kind kernel-launch deltas shipped back from
    # worker processes this query (ClusterDAGScheduler._merge_task_obs);
    # EXPLAIN ANALYZE reconciles measured launches as driver + this
    worker_kernel_kinds: dict | None = field(default=None, repr=False)
    # session LiveObs (obs/live.py) when live telemetry is wired: the
    # cluster scheduler closes task records against it and the straggler
    # detector reads it; None = no live store
    live_obs: object = field(default=None, repr=False)
    # query-scope tag of the collect driving this execution (set by
    # QueryExecution.execute from the tracing contextvar) — keys the
    # live store and EXPLAIN ANALYZE's straggler-finding lookup
    query_id: str | None = field(default=None, repr=False)
    # warm start (exec/persist_cache.plan_seed): what a prior run of
    # this query's plan fingerprint learned — the newest manifest record
    # (join/mesh capacity outcomes) when spark.tpu.cache.dir is
    # configured, else the join capacities the process remembers — set
    # by QueryExecution; executors of capacity-retry loops read their
    # seed from it and stash this run's outcomes below for the
    # close-time write to the manifest and the process's memory
    persist_seed: dict | None = field(default=None, repr=False)
    persist_join_caps: list | None = field(default=None, repr=False)
    persist_mesh_quotas: dict | None = field(default=None, repr=False)
    # per-join build-side key spans ([lo, hi, unique] or None, aligned
    # with persist_join_caps) observed by the whole-program tiers — the
    # manifest carries them so a warm restart compiles the dense
    # direct-address probe variant directly
    persist_join_spans: list | None = field(default=None, repr=False)
    # per-query kernel ledger (obs/metrics.QueryKernelLedger) installed
    # by QueryExecution.execute for the execution window: scope-exact
    # launch/compile deltas under concurrent collects (the contextvar
    # copy rides into par_map lanes and scoped_submit pools); profiles
    # and EXPLAIN ANALYZE read this instead of process-snapshot deltas
    kernel_ledger: object = field(default=None, repr=False)
    # chaos salvage (cluster mode): wasted-work records of failed task
    # attempts whose worker-side obs rode the error payload back
    # (ClusterDAGScheduler._record_failed_attempt) — kept SEPARATE from
    # plan_metrics/worker_kernel_kinds so launch reconciliation still
    # counts only work that contributed to the result; the query
    # profile and EXPLAIN ANALYZE findings surface it as waste
    failed_attempt_obs: list | None = field(default=None, repr=False)

    @property
    def memory(self):
        """Per-query MemoryManager (UnifiedMemoryManager role)."""
        if self._memory is None:
            from .memory import MemoryManager

            self._memory = MemoryManager(self.conf, self.metrics)
        return self._memory

    @property
    def partition_parallelism(self) -> int:
        """Concurrent partition-dispatch lanes for operator execution
        (spark.tpu.exec.partitionParallelism; 0 = auto)."""
        n = int(self.conf.get("spark.tpu.exec.partitionParallelism", 0))
        if n <= 0:
            import os

            n = min(4, os.cpu_count() or 1)
        return n

    def par_map(self, fn, items: list) -> list:
        """Dispatch independent partitions concurrently (async pipelining
        across partitions; see exec/scheduler.par_map). `fn` must be pure
        per-item device/host work — it must not recurse into plan
        execution. With tracing on, each partition records its own span
        from its lane thread (distinct trace tracks), so the async
        pipeline's overlap is visible in the exported timeline."""
        from .scheduler import par_map

        items = list(items)
        tracer = self.tracer
        if tracer is not None and tracer.enabled and len(items) > 1:
            from ..obs.metrics import current_op_name

            op = current_op_name() or "partition"

            def traced(pair, _fn=fn, _op=op):
                i, item = pair
                # flow=True: the lane span parents to the enclosing flow
                # span (stage/worker task) — the lane context is a copy
                # of the dispatching thread's, so the parent id is visible
                with tracer.span(f"{_op}[p{i}]", cat="partition",
                                 flow=True):
                    return _fn(item)

            return par_map(traced, list(enumerate(items)),
                           self.partition_parallelism)
        return par_map(fn, items, self.partition_parallelism)
