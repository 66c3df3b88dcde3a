"""Stage-DAG scheduler and control plane.

Role of the reference's scheduling stack (SURVEY.md §2.1):
  * DAGScheduler (core/scheduler/DAGScheduler.scala:648 createShuffleMapStage,
    :1614 submitStage, :1831 submitMissingTasks): the plan DAG is cut into
    stages at exchange boundaries; parents run before children; a failed
    stage retries up to spark.stage.maxAttempts.
  * TaskScheduler/TaskSetManager (core/scheduler/TaskSchedulerImpl.scala,
    TaskSetManager.scala): per-stage task sets with per-task retry.
  * Executor registry + HeartbeatReceiver (core/HeartbeatReceiver.scala) and
    HealthTracker (core/scheduler/HealthTracker.scala:52): failure detection
    and excludelists for the multi-host backend.
  * BarrierCoordinator (core/BarrierCoordinator.scala): gang-sync for SPMD
    stages — on a TPU mesh every pjit program is already gang-scheduled, so
    the barrier is only needed for host-side phases.

Local mode runs stages in-process (a stage = the maximal exchange-free
physical subtree; partitions already execute as device programs inside it).
The control-plane classes are the contract for the multi-host DCN backend.
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..physical.operators import PhysicalPlan
from .context import ExecContext


# ---------------------------------------------------------------------------
# Concurrent partition dispatch
# ---------------------------------------------------------------------------

def par_map(fn: Callable, items: list, workers: int) -> list:
    """Run `fn` over `items` on up to `workers` threads, preserving order.

    The async dispatch plane for partition-granular operator work: XLA
    dispatch is asynchronous, so a Python thread per partition keeps the
    device queue fed across partitions instead of round-tripping host →
    device → host between every launch (role of the reference's task-slot
    parallelism inside one executor). Threads are ephemeral daemons striding
    over the item list — no pool to leak, deterministic output order, first
    exception re-raised like the serial loop would. Each lane runs inside
    a copy of the caller's contextvars context so the obs/ kernel-
    attribution scope (the operator that called par_map) follows the
    work onto the lane threads."""
    n = len(items)
    if n <= 1 or workers <= 1:
        return [fn(x) for x in items]
    import contextvars

    w = min(workers, n)
    out: list = [None] * n
    errors: list = []

    def run(lane: int) -> None:
        for i in range(lane, n, w):
            if errors:
                return
            try:
                out[i] = fn(items[i])
            except BaseException as e:  # propagate to caller, stop lanes
                errors.append(e)
                return

    # one context copy per lane: a Context cannot be entered concurrently
    contexts = [contextvars.copy_context() for _ in range(w)]
    threads = [threading.Thread(target=contexts[k].run, args=(run, k),
                                daemon=True, name=f"tpu-dispatch-{k}")
               for k in range(w)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


# ---------------------------------------------------------------------------
# Stage graph
# ---------------------------------------------------------------------------

@dataclass
class Stage:
    stage_id: int
    root: PhysicalPlan           # subtree with exchanges as leaves
    parents: list["Stage"] = field(default_factory=list)
    attempts: int = 0
    result: list | None = None   # materialized partitions

    def __hash__(self):
        return self.stage_id


def build_stage_graph(plan: PhysicalPlan) -> tuple[Stage, list[Stage]]:
    """Cut the physical plan at exchange boundaries
    (DAGScheduler.createShuffleMapStage role). Each stage's root is an
    exchange (shuffle/broadcast "map stage") or the result subtree; nested
    exchanges become _StageOutput leaves wired to parent stages."""
    from ..physical.exchange import BroadcastExchangeExec, ShuffleExchangeExec

    counter = [0]
    stages: list[Stage] = []

    def convert(node: PhysicalPlan, parent_list: list[Stage]) -> PhysicalPlan:
        if isinstance(node, (ShuffleExchangeExec, BroadcastExchangeExec)):
            sub_parents: list[Stage] = []
            new_child = convert(node.child, sub_parents)
            counter[0] += 1
            st = Stage(counter[0], node.with_new_children([new_child]),
                       sub_parents)
            stages.append(st)
            parent_list.append(st)
            return _StageOutput(st, node.output)
        return node.map_children(lambda c: convert(c, parent_list))

    root_parents: list[Stage] = []
    root_plan = convert(plan, root_parents)
    counter[0] += 1
    result_stage = Stage(counter[0], root_plan, root_parents)
    stages.append(result_stage)
    return result_stage, stages


class _StageOutput(PhysicalPlan):
    """Leaf standing for a parent stage's materialized output."""

    child_fields = ()

    def __init__(self, stage: Stage, attrs):
        self.stage = stage
        self.attrs = attrs

    @property
    def output(self):
        return self.attrs

    def output_partitioning(self):
        from ..physical.partitioning import UnknownPartitioning

        n = len(self.stage.result) if self.stage.result is not None else 1
        return UnknownPartitioning(n)

    def execute(self, ctx):
        assert self.stage.result is not None, \
            f"parent stage {self.stage.stage_id} not materialized"
        return self.stage.result

    def simple_string(self):
        return f"StageOutput(#{self.stage.stage_id})"


def _stage_leaves(root: PhysicalPlan) -> list["_StageOutput"]:
    return [n for n in root.iter_nodes() if isinstance(n, _StageOutput)]


def _reachable_stages(result_stage: Stage) -> list[Stage]:
    """Stages transitively referenced from the result stage via
    _StageOutput leaves (replanning can orphan stages; orphans never run)."""
    seen: dict[int, Stage] = {}
    work = [result_stage]
    while work:
        st = work.pop()
        if st.stage_id in seen:
            continue
        seen[st.stage_id] = st
        for leaf in _stage_leaves(st.root):
            work.append(leaf.stage)
    return list(seen.values())


def _build_side_stage_ids(stages: list[Stage], done: set[int]) -> set[int]:
    """Stage ids feeding the build (right) side of a not-yet-broadcast
    hash join — materializing those first gives AQE demotion its shot."""
    from ..physical.operators import HashJoinExec

    build: list[Stage] = []
    for st in stages:
        if st.stage_id in done:
            continue
        for n in st.root.iter_nodes():
            if isinstance(n, HashJoinExec) and not n.is_broadcast and \
                    isinstance(n.right, _StageOutput):
                build.append(n.right.stage)
    # close over ancestors: the whole build-side chain runs before any
    # probe-side shuffle
    out: set[int] = set()
    while build:
        st = build.pop()
        if st.stage_id in out:
            continue
        out.add(st.stage_id)
        build.extend(leaf.stage for leaf in _stage_leaves(st.root))
    return out


def _stage_args(stage: Stage) -> dict:
    """What span `stage.run` says of a stage before it runs: its id, the
    attempt, and the kinds of its operators from the root down (a parent
    stage's output is a leaf and has none)."""
    kinds = [type(n).__name__.removesuffix("Exec")
             for n in stage.root.iter_nodes()
             if not isinstance(n, _StageOutput)]
    return {"stage": stage.stage_id, "attempt": stage.attempts,
            "operators": ",".join(kinds)}


def _result_args(result: list, launches: int) -> dict:
    """And once it has: the kernels it launched, the tiles it left and
    their live rows where every tile knows its count on the host (-1
    otherwise: reading one off the device would be a sync)."""
    tiles = [b for part in result for b in part]
    known = all(b._num_rows is not None for b in tiles)
    return {"launches": launches, "tiles": len(tiles),
            "rows_out": sum(b._num_rows for b in tiles) if known else -1}


class DAGScheduler:
    """Runs a stage graph with per-stage retry (stage = unit of recovery;
    deterministic re-execution replays the subtree, the lineage property
    the reference relies on)."""

    def __init__(self, ctx: ExecContext, max_attempts: int = 2,
                 listener_bus=None):
        self.ctx = ctx
        self.max_attempts = max_attempts
        self.bus = listener_bus

    # KernelCache counters that the query's own ledger (obs/metrics.py
    # QueryKernelLedger) counts too
    _LEDGER_KEYS = (("kernel_cache.launches", "launches"),
                    ("kernel_cache.misses", "compiles"),
                    ("kernel_cache.compile_ms", "compile_ms"),
                    ("kernel_cache.disk_hit_compiles", "disk_hit_compiles"))

    def run(self, plan: PhysicalPlan) -> list:
        from ..physical.compile import GLOBAL_KERNEL_CACHE

        ledger = getattr(self.ctx, "kernel_ledger", None)
        kc_before = GLOBAL_KERNEL_CACHE.counters()
        led_before = ledger.snapshot() if ledger is not None else None
        try:
            return self._run(plan)
        finally:
            # per-run kernel dispatch/cache deltas into the query metrics
            # (satellite of SQLMetrics: dispatch-count regressions surface
            # in listener snapshots and BENCH output)
            deltas = {k: v - kc_before.get(k, 0)
                      for k, v in GLOBAL_KERNEL_CACHE.counters().items()}
            if ledger is not None:
                # the process's counters also move with every other
                # query in flight (a server's other tenants): what the
                # query's ledger counts is taken from it
                led_after = ledger.snapshot()
                for k, name in self._LEDGER_KEYS:
                    deltas[k] = led_after[name] - led_before[name]
            for k, v in deltas.items():
                d = round(v)
                if d:
                    self.ctx.metrics.add(f"kernel.{k.split('.', 1)[1]}", d)

    def _run(self, plan: PhysicalPlan) -> list:
        result_stage, stages = build_stage_graph(plan)
        done: set[int] = set()

        tracer = getattr(self.ctx, "tracer", None)

        def run_stage(stage: Stage) -> None:
            last_err: Exception | None = None
            for attempt in range(self.max_attempts):
                stage.attempts = attempt + 1
                try:
                    self._post("stageSubmitted", stage)
                    t0 = time.perf_counter()
                    if tracer is None:
                        stage.result = stage.root.execute(self.ctx)
                    else:
                        # flow=True links execution phase → stage → lane
                        # spans as Perfetto flow arrows in the export
                        with tracer.span(f"stage-{stage.stage_id}",
                                         cat="stage",
                                         args={"attempt": attempt + 1},
                                         flow=True):
                            self._execute_stage(stage, tracer)
                    from ..columnar.validate import maybe_validate

                    maybe_validate(stage.result, self.ctx,
                                   f"stage-{stage.stage_id}")
                    self.ctx.metrics.add("scheduler.stages_completed")
                    self._post("stageCompleted", stage,
                               dur=(time.perf_counter() - t0) * 1000)
                    done.add(stage.stage_id)
                    return
                except Exception as e:  # deterministic retry (lineage)
                    last_err = e
                    self.ctx.metrics.add("scheduler.stage_retries")
                    self._post("stageFailed", stage, error=str(e))
            raise last_err  # noqa: B904

        from ..physical.adaptive import (
            aqe_replanning_enabled, install_runtime_filters, maybe_readmit,
            replan_stages,
        )

        adaptive = aqe_replanning_enabled(self.ctx)

        # iterative ready-set loop (AdaptiveSparkPlanExec.scala:301 role):
        # materialize one ready stage at a time, re-plan the remainder with
        # observed sizes after each completion; stages the re-plan inlined
        # or replaced drop out of the reachable set and never run
        while result_stage.stage_id not in done:
            needed = _reachable_stages(result_stage)
            ready = [st for st in needed
                     if st.stage_id not in done
                     and all(leaf.stage.stage_id in done
                             for leaf in _stage_leaves(st.root))]
            if not ready:
                raise RuntimeError("stage graph stalled (cycle?)")
            # potential broadcast build sides first so a small side can
            # demote the join before the probe shuffle runs
            if adaptive:
                build_ids = _build_side_stage_ids(needed, done)
                ready.sort(key=lambda s: (s.stage_id not in build_ids,
                                          s.stage_id))
            st = ready[0]
            run_stage(st)
            if st is not result_stage:
                if adaptive:
                    replan_stages(needed, done, self.ctx)
                # spark.tpu.adaptive.* family (each self-gating): push
                # materialized build-side key domains into unrun probe
                # shuffles, then try to collapse the remaining plan into
                # one whole-tier program with the observed sizes
                install_runtime_filters(needed, done, self.ctx)
                maybe_readmit(result_stage, done, self.ctx)
        return result_stage.result

    def _execute_stage(self, stage: Stage, tracer) -> None:
        """`stage.root.execute` under span `stage.run`. A whole-query
        program is no stage of the stage tier though the scheduler runs
        it as its one stage: it has the `whole_query.*` spans, and if it
        degrades at run time the scheduler it starts spans its stages."""
        from ..physical.whole_query import WholeQueryExec

        if isinstance(stage.root, WholeQueryExec):
            stage.result = stage.root.execute(self.ctx)
            return
        with tracer.span("stage.run", cat="stage",
                         args=_stage_args(stage)) as sp:
            l0 = self._launches()
            stage.result = stage.root.execute(self.ctx)
            sp.set_args(_result_args(stage.result, self._launches() - l0))

    def _launches(self) -> int:
        """Kernel launches so far: the query's own where it has a ledger
        (other queries in flight move the process's counter too)."""
        ledger = getattr(self.ctx, "kernel_ledger", None)
        if ledger is not None:
            return ledger.launches
        from ..physical.compile import GLOBAL_KERNEL_CACHE

        return GLOBAL_KERNEL_CACHE.launches

    def _post(self, kind: str, stage: Stage, dur=None, error=None):
        if self.bus is None:
            return
        from .listener import QueryEvent

        self.bus.post(QueryEvent(
            kind, f"stage-{stage.stage_id}", time.time(),
            duration_ms=dur, error=error,
            metrics={"attempt": stage.attempts}))


# ---------------------------------------------------------------------------
# Control plane (multi-host contract)
# ---------------------------------------------------------------------------

@dataclass
class ExecutorInfo:
    executor_id: str
    host: str
    slots: int
    last_heartbeat: float = field(default_factory=time.time)
    failures: int = 0            # lifetime task-failure total (surfaced)
    excluded: bool = False       # permanent exclusion (legacy/manual)
    excluded_until: float = 0.0  # timed exclusion (excludeOnFailure)

    def is_excluded(self, now: float | None = None) -> bool:
        return self.excluded or \
            self.excluded_until > (time.time() if now is None else now)


class ExecutorRegistry:
    """Executor registration + heartbeat expiry
    (CoarseGrainedSchedulerBackend + HeartbeatReceiver roles)."""

    def __init__(self, heartbeat_timeout_s: float = 120.0):
        self.timeout = heartbeat_timeout_s
        self._executors: dict[str, ExecutorInfo] = {}
        self._lock = threading.Lock()

    def register(self, host: str, slots: int = 1) -> str:
        eid = f"exec-{uuid.uuid4().hex[:8]}"
        with self._lock:
            self._executors[eid] = ExecutorInfo(eid, host, slots)
        return eid

    def heartbeat(self, executor_id: str) -> bool:
        with self._lock:
            e = self._executors.get(executor_id)
            if e is None:
                return False  # reference: executor told to re-register
            e.last_heartbeat = time.time()
            return True

    def remove(self, executor_id: str) -> None:
        """Executor lost (process death / connection drop) — immediate
        deregistration (reference: CoarseGrainedSchedulerBackend
        RemoveExecutor)."""
        with self._lock:
            self._executors.pop(executor_id, None)

    def expire_dead(self) -> list[str]:
        now = time.time()
        dead = []
        with self._lock:
            for eid, e in list(self._executors.items()):
                if now - e.last_heartbeat > self.timeout:
                    dead.append(eid)
                    del self._executors[eid]
        return dead

    def alive(self) -> list[ExecutorInfo]:
        now = time.time()
        with self._lock:
            return [e for e in self._executors.values()
                    if not e.is_excluded(now)]

    def registered(self) -> list[ExecutorInfo]:
        """All registered executors INCLUDING excluded ones — the
        last-resort scheduling pool when exclusion would otherwise
        starve the cluster."""
        with self._lock:
            return list(self._executors.values())


class HealthTracker:
    """Executor excludelist on repeated failures (the reference's
    HealthTracker.scala:52 + TaskSetExcludelist): failures are counted
    per executor inside a sliding window; crossing `max_failures` inside
    `window_s` excludes the executor from scheduling for `exclude_s`
    seconds (timed re-inclusion — a transiently-sick executor rejoins,
    a permanently-sick one re-excludes on its next failures). Failure
    history lives here (not on ExecutorInfo), so counters survive an
    executor being removed and re-registered and are reportable after
    loss."""

    def __init__(self, registry: ExecutorRegistry,
                 max_failures: int = 2, window_s: float = 60.0,
                 exclude_s: float = 0.0, enabled: bool = True):
        self.registry = registry
        self.max_failures = max_failures
        self.window_s = window_s
        # 0.0 keeps the legacy permanent-exclusion semantics (tests and
        # callers that never configure a timeout)
        self.exclude_s = exclude_s
        self.enabled = enabled
        self._lock = threading.Lock()
        self._failures: dict[str, list[float]] = {}
        self._totals: dict[str, int] = {}
        self._excluded_until: dict[str, float] = {}
        # host-granular exclusion: when EVERY executor on one host has
        # tripped the failure window, the box itself is suspect (NIC,
        # PCIe link, thermal) — the host is excluded as a unit with the
        # same timed re-inclusion horizon as its members
        self._host_excluded_until: dict[str, float] = {}
        # on_exclude(eid, until, failures) — the cluster scheduler hooks
        # this to surface exclusion in live status / EXPLAIN ANALYZE
        self.on_exclude = None
        # on_exclude_host(host, until, eids) — fired once per host trip
        self.on_exclude_host = None

    def configure(self, enabled: bool | None = None,
                  max_failures: int | None = None,
                  window_s: float | None = None,
                  exclude_s: float | None = None) -> None:
        if enabled is not None:
            self.enabled = enabled
        if max_failures is not None:
            self.max_failures = max_failures
        if window_s is not None:
            self.window_s = window_s
        if exclude_s is not None:
            self.exclude_s = exclude_s

    def record_failure(self, executor_id: str) -> bool:
        """Count one task failure against the executor. Returns True if
        the executor is now (or already) excluded."""
        if not self.enabled:
            return False
        now = time.time()
        with self._lock:
            times = self._failures.setdefault(executor_id, [])
            times.append(now)
            times[:] = [t for t in times if now - t <= self.window_s]
            self._totals[executor_id] = \
                self._totals.get(executor_id, 0) + 1
            total = self._totals[executor_id]
            trip = len(times) >= self.max_failures
            if trip:
                until = (now + self.exclude_s) if self.exclude_s > 0 \
                    else float("inf")
                self._excluded_until[executor_id] = until
                # the window restarts after an exclusion: re-inclusion
                # gives the executor a clean slate to prove itself
                times.clear()
        host_trip = None
        with self.registry._lock:
            e = self.registry._executors.get(executor_id)
            if e is None:
                # executor already deregistered (process death) — the
                # failure still counts toward its history
                excluded = True
            else:
                e.failures = total
                if trip:
                    if self.exclude_s > 0:
                        e.excluded_until = until
                    else:
                        e.excluded = True
                excluded = e.is_excluded()
                if trip:
                    # host-granular escalation: every executor on this
                    # host now excluded → exclude the host as a unit
                    peers = [p for p in self.registry._executors.values()
                             if p.host == e.host]
                    if peers and all(p.is_excluded(now) for p in peers):
                        horizon = until
                        for p in peers:
                            if not p.excluded:
                                # synchronized re-inclusion: the whole
                                # host rejoins at once, or not at all
                                p.excluded_until = max(
                                    p.excluded_until, horizon)
                        host_trip = (e.host, horizon,
                                     [p.executor_id for p in peers])
        if host_trip is not None:
            host, horizon, eids = host_trip
            with self._lock:
                # one event per trip: an already-excluded host extending
                # its horizon re-fires only past the prior horizon
                if self._host_excluded_until.get(host, 0.0) >= horizon:
                    host_trip = None
                else:
                    self._host_excluded_until[host] = horizon
        if trip and self.on_exclude is not None:
            try:
                self.on_exclude(executor_id,
                                self._excluded_until[executor_id], total)
            except Exception:
                pass    # surfacing must never fail the scheduling path
        if host_trip is not None and self.on_exclude_host is not None:
            try:
                self.on_exclude_host(*host_trip)
            except Exception:
                pass
        return excluded

    def failure_count(self, executor_id: str) -> int:
        with self._lock:
            return self._totals.get(executor_id, 0)

    def reset(self) -> None:
        """Clear all failure history and lift every exclusion (the
        operator's 'clear the excludelist' action)."""
        with self._lock:
            self._failures.clear()
            self._totals.clear()
            self._excluded_until.clear()
            self._host_excluded_until.clear()
        with self.registry._lock:
            for e in self.registry._executors.values():
                e.excluded = False
                e.excluded_until = 0.0
                e.failures = 0

    def excluded(self) -> dict[str, float]:
        """Currently-excluded executors → re-inclusion time."""
        now = time.time()
        with self._lock:
            return {eid: until
                    for eid, until in self._excluded_until.items()
                    if until > now}

    def excluded_hosts(self) -> dict[str, float]:
        """Currently-excluded hosts → re-inclusion time."""
        now = time.time()
        with self._lock:
            return {host: until
                    for host, until in self._host_excluded_until.items()
                    if until > now}


class BarrierCoordinator:
    """allGather/barrier for gang-scheduled host phases
    (core/BarrierTaskContext.scala barrier()/allGather())."""

    def __init__(self, num_tasks: int):
        self.num_tasks = num_tasks
        self._barrier = threading.Barrier(num_tasks)
        self._messages: dict[int, object] = {}
        self._lock = threading.Lock()

    def barrier(self, task_id: int, timeout: float = 60.0) -> None:
        self._barrier.wait(timeout)

    def all_gather(self, task_id: int, message,
                   timeout: float = 60.0) -> list:
        with self._lock:
            self._messages[task_id] = message
        self._barrier.wait(timeout)
        with self._lock:
            out = [self._messages[i] for i in sorted(self._messages)]
        self._barrier.wait(timeout)
        with self._lock:
            self._messages.pop(task_id, None)
        return out
