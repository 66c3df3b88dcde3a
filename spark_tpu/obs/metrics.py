"""Per-operator SQLMetrics with kernel-launch attribution + EXPLAIN ANALYZE.

Role of the reference's SQLMetrics + the SQL tab's per-node metric
annotations (sqlx/metric/SQLMetrics.scala, SparkPlanGraph), with the two
pieces a fusing TPU engine needs that Spark does not:

  * kernel attribution — the process-global KernelCache counts launches
    and compile-ms; a contextvar scoped to the EXECUTING operator (pushed
    by the PhysicalPlan execute wrapper, propagated into par_map lanes)
    re-buckets every launch to the physical node that dispatched it. A
    whole-stage fused operator owns its single dispatch; `fused_members`
    re-attributes that dispatch to the constituent operators the
    `FuseStages` rewrite collapsed (Flare's lesson: once a stage compiles
    to one program, per-operator attribution must be rebuilt
    deliberately).

  * sync-free row counts — output rows come from host-side batch
    metadata (`_num_rows`); batches whose live count is only on device
    park their row-mask array (bounded by a per-query byte budget) and
    are resolved ONCE per distinct mask identity at query end. Collection
    never launches a kernel and never blocks mid-query.

`AnalyzedReport` is the EXPLAIN ANALYZE surface: the executed plan
annotated with measured metrics side by side with the static analyzer's
predictions (analysis/plan_lint.py), with drift between them surfaced as
first-class findings.
"""

from __future__ import annotations

import contextlib
import contextvars
import sys
import threading
from dataclasses import dataclass, field

import numpy as np

from ..utils import lockwatch

__all__ = ["AnalyzedReport", "QueryKernelLedger", "batch_cost_scope",
           "current_op_name", "current_op_row", "current_query_ledger",
           "export_op_records", "export_op_records_partial",
           "finalize_plan_metrics", "fused_members",
           "get_or_create_op_record", "iter_metric_nodes",
           "merge_op_records", "metric_children", "new_op_record",
           "pop_op", "pop_query_ledger", "push_op", "push_query_ledger",
           "record_compile_disk_event", "record_kernel_launch",
           "record_kernel_compile", "record_kernel_disk_hit",
           "record_kernel_miss", "scoped_submit"]


# ---------------------------------------------------------------------------
# Attribution scope: which operator is executing on this thread/lane
# ---------------------------------------------------------------------------

# (record dict | None, operator name). contextvars (not thread-locals) so
# exec/scheduler.par_map can copy the context into its lane threads and
# kernels dispatched from a lane still attribute to the dispatching node.
_SCOPE: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_op_scope", default=None)

# per-record Counter updates are read-modify-write; lanes of one operator
# share its record, so serialize the tiny increments
_ATTR_LOCK = threading.Lock()
lockwatch.register("obs.metrics._ATTR_LOCK",
                   sys.modules[__name__], "_ATTR_LOCK")

# live-row fraction of the batch currently dispatching. Captured kernel
# costs are per-kernel-identity CONSTANTS (first-invocation lowering), so
# shape buckets whose batches carry very different live row counts would
# overstate per-operator bytes/flops — and EXPLAIN ANALYZE's achieved
# GB/s — on sparse batches. Dispatch sites that know the live count
# host-side (ExprPipeline.run, the fused stage kernels) scope this
# fraction around the kernel call and record_kernel_launch scales the
# cost multiplied onto the OPERATOR record. The process-wide KernelCache
# counters stay unscaled: they mirror the cost model's per-launch bytes.
_BATCH_FRACTION: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_batch_fraction", default=None)


# ---------------------------------------------------------------------------
# Per-query kernel ledger: scope-exact launch/compile deltas
# ---------------------------------------------------------------------------

# The KernelCache counters are PROCESS-global: two queries collecting
# concurrently on one process read each other's launches into any
# snapshot-delta they take (the PR 12 `overlapped` limitation). The
# ledger fixes that at the source: QueryExecution installs one
# QueryKernelLedger in this contextvar for the execution window, the
# contextvar follows the work into par_map lanes (copied contexts) and
# scoped_submit pools, and every KernelCache launch/compile event also
# lands on the CURRENT query's ledger — so racing queries get disjoint,
# exact deltas and profiles/EXPLAIN ANALYZE stop needing an overlap
# guard. Cluster-worker launches are NOT in the ledger (separate
# processes); they keep shipping per-task deltas that the driver folds
# per query (ctx.worker_kernel_kinds).
_QUERY_LEDGER: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_query_ledger", default=None)


class QueryKernelLedger:
    """Per-query accumulator of kernel events (launches by kind, engine
    compiles, compile wall-ms, disk-served compiles). Pure host
    bookkeeping; thread-safe because one query's launches arrive from
    several par_map lanes."""

    __slots__ = ("_lock", "kinds", "launches", "compiles", "compile_ms",
                 "disk_hit_compiles", "disk_hits", "disk_misses")

    def __init__(self):
        self._lock = threading.Lock()
        self.kinds: dict = {}
        self.launches = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self.disk_hit_compiles = 0
        # raw XLA persistent-cache traffic of THIS query's compiles
        # (exec/persist_cache._on_monitor_event) — distinct from
        # disk_hit_compiles, which counts KERNELS whose first
        # invocation was disk-served
        self.disk_hits = 0
        self.disk_misses = 0

    def _launch(self, kind) -> None:
        with self._lock:
            self.kinds[kind] = self.kinds.get(kind, 0) + 1
            self.launches += 1

    def _compile(self, ms: float) -> None:
        with self._lock:
            self.compile_ms += ms

    def _miss(self) -> None:
        with self._lock:
            self.compiles += 1

    def _disk_hit(self) -> None:
        with self._lock:
            self.disk_hit_compiles += 1

    def _disk_event(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.disk_hits += 1
            else:
                self.disk_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"kinds": dict(self.kinds),
                    "launches": self.launches,
                    "compiles": self.compiles,
                    "compile_ms": self.compile_ms,
                    "disk_hit_compiles": self.disk_hit_compiles,
                    "disk_hits": self.disk_hits,
                    "disk_misses": self.disk_misses}


def push_query_ledger(ledger: "QueryKernelLedger"):
    """Enter a query's kernel-ledger scope; returns the reset token."""
    return _QUERY_LEDGER.set(ledger)


def pop_query_ledger(token) -> None:
    _QUERY_LEDGER.reset(token)


def current_query_ledger() -> "QueryKernelLedger | None":
    return _QUERY_LEDGER.get()


def record_kernel_miss(kind) -> None:
    """Called by KernelCache on every cache miss (= one engine compile:
    trace + jit). The ledger's `compiles` mirrors what a process-level
    KC.misses delta would read on a serial run."""
    led = _QUERY_LEDGER.get()
    if led is not None:
        led._miss()


def record_kernel_disk_hit(kind) -> None:
    """Called by KernelCache when a kernel's first invocation was served
    by the persistent XLA disk cache (exec/persist_cache.py)."""
    led = _QUERY_LEDGER.get()
    if led is not None:
        led._disk_hit()


def record_compile_disk_event(hit: bool) -> None:
    """Called by persist_cache's jax monitoring listener per raw XLA
    disk-cache hit/miss — the compile runs on the dispatching thread,
    so the event lands on the compiling query's ledger (scope-exact
    per-query compile.disk_* deltas under concurrency)."""
    led = _QUERY_LEDGER.get()
    if led is not None:
        led._disk_event(hit)


@contextlib.contextmanager
def batch_cost_scope(batch):
    """Context manager scoping the live-row fraction of `batch` (host
    metadata only — an unknown live count scales nothing). Runs once per
    kernel dispatch: module-level contextmanager, no per-call closure."""
    rows = batch._num_rows
    cap = batch.capacity
    frac = None
    if rows is not None and cap and rows < cap:
        frac = max(int(rows), 1) / cap
    token = _BATCH_FRACTION.set(frac)
    try:
        yield
    finally:
        _BATCH_FRACTION.reset(token)


def new_op_record() -> dict:
    return {"rows": 0, "rows_exact": True, "batches": 0, "ms": 0.0,
            "calls": 0, "kinds": {}, "launch_total": 0, "compile_ms": 0.0,
            "flops": 0.0, "bytes": 0.0, "pending": []}


def get_or_create_op_record(rec: dict, key) -> dict:
    """Insert-if-absent under the attribution lock. The plan_metrics
    dict is iterated under `_ATTR_LOCK` by the live-telemetry partial
    export (heartbeat thread) while operator threads create records for
    nodes reaching their first batch — an unlocked `rec[key] = ...`
    there can blow up the iterator with "dict changed size during
    iteration". Every insertion into a plan_metrics dict goes through
    here or `merge_op_records`."""
    ent = rec.get(key)
    if ent is None:
        with _ATTR_LOCK:
            ent = rec.get(key)
            if ent is None:
                ent = rec[key] = new_op_record()
    return ent


def push_op(record: dict | None, name: str, row=None):
    """Enter an operator's attribution scope; returns the reset token.
    `row` is the operator's key in the query's plan_metrics (its
    `_metric_id`: the node's place in `iter_metric_nodes`)."""
    return _SCOPE.set((record, name, row))


def pop_op(token) -> None:
    _SCOPE.reset(token)


def current_op_name() -> str | None:
    scope = _SCOPE.get()
    return scope[1] if scope is not None else None


def current_op_row() -> tuple | None:
    """(row, name) of the executing operator, or None outside one."""
    scope = _SCOPE.get()
    return None if scope is None else (scope[2], scope[1])


def record_kernel_launch(kind, cost: dict | None = None) -> None:
    """Called by KernelCache on every kernel invocation (pure host
    bookkeeping — never a launch or sync itself). `cost` is the kernel's
    captured per-launch cost (flops / bytes accessed — physical/compile.
    _capture_kernel_cost), multiplied out onto the executing operator's
    record so EXPLAIN ANALYZE can render per-operator FLOPs, bytes and
    achieved GB/s. Also lands the launch on the current query's kernel
    ledger (scope-exact per-query deltas under concurrent collects)."""
    led = _QUERY_LEDGER.get()
    if led is not None:
        led._launch(kind)
    scope = _SCOPE.get()
    if scope is None or scope[0] is None:
        return
    rec = scope[0]
    frac = _BATCH_FRACTION.get() if cost is not None else None
    with _ATTR_LOCK:
        rec["kinds"][kind] = rec["kinds"].get(kind, 0) + 1
        rec["launch_total"] += 1
        if cost is not None:
            if frac is not None:
                # scale the per-identity constant cost by the dispatching
                # batch's live-row fraction (PR 7 follow-on: sparse
                # batches no longer overstate achieved GB/s)
                rec["flops"] += cost["flops"] * frac
                rec["bytes"] += cost["bytes"] * frac
            else:
                rec["flops"] += cost["flops"]
                rec["bytes"] += cost["bytes"]


def record_kernel_compile(kind, ms: float) -> None:
    """Called by KernelCache for builder time and first-invocation (XLA
    lazy compile) time."""
    led = _QUERY_LEDGER.get()
    if led is not None:
        led._compile(ms)
    scope = _SCOPE.get()
    if scope is None or scope[0] is None:
        return
    rec = scope[0]
    with _ATTR_LOCK:
        rec["compile_ms"] += ms


def scoped_submit(pool, fn, *args):
    """Submit `fn` to a concurrent.futures pool under a COPY of the
    caller's contextvars Context, taken at submit time — the same
    discipline `exec/scheduler.par_map` applies to its lane threads.
    Pool worker threads start with an empty context, so a bare
    `pool.submit` silently re-buckets every kernel launch the task
    dispatches to "unattributed" and drops its spans' query tag; this is
    the one sanctioned way to hand obs-scoped work to a thread pool.
    One Context copy per submit (a Context cannot be entered
    concurrently)."""
    ctx = contextvars.copy_context()
    return pool.submit(ctx.run, fn, *args)


# ---------------------------------------------------------------------------
# Sync-free row accounting
# ---------------------------------------------------------------------------

# Device-memory ceiling for row masks parked until query end. Parking
# holds a strong reference (the mask cannot be freed mid-query), so the
# budget bounds the extra HBM metrics-on can pin on huge queries: beyond
# it, rows degrade to a lower bound (rows_exact=False) instead of
# risking an OOM a metrics-off run would not hit. The special "_parked"
# key in the plan_metrics dict carries the query's remaining budget.
PARKED_MASK_BUDGET_BYTES = 64 << 20
_PARKED_KEY = "_parked"


def count_batch(rec: dict, record: dict, batch) -> None:
    """Account one output batch against an operator record using only
    host-side metadata. Device masks are parked (within the per-query
    byte budget) for query-end resolution — never pulled here."""
    record["batches"] += 1
    n = getattr(batch, "_num_rows", None)
    if n is not None:
        record["rows"] += n
        return
    mask = getattr(batch, "row_mask", None)
    if mask is None:
        record["rows_exact"] = False
        return
    if isinstance(mask, np.ndarray):  # already host data — free to count
        record["rows"] += int(mask.sum())
        return
    budget = rec.get(_PARKED_KEY)
    if budget is None:
        # locked insert — the live-telemetry flush iterates this dict
        # under _ATTR_LOCK (export_op_records_partial) concurrently
        with _ATTR_LOCK:
            budget = rec.get(_PARKED_KEY)
            if budget is None:
                budget = rec[_PARKED_KEY] = \
                    [PARKED_MASK_BUDGET_BYTES, set()]
    remaining, charged = budget
    if id(mask) in charged:
        # already pinned by another operator's park this query: sharing
        # a mask costs one pull and one ref — charge the budget once
        record["pending"].append(mask)
        return
    nbytes = int(getattr(mask, "nbytes", 0) or 0)
    if remaining - nbytes < 0:
        record["rows_exact"] = False  # budget spent: lower bound only
        return
    budget[0] = remaining - nbytes
    charged.add(id(mask))
    record["pending"].append(mask)


def _op_records(rec: dict):
    return (ent for k, ent in rec.items() if k != _PARKED_KEY)


def metric_key(node) -> int:
    """Stable metric-record key: the pre-assigned `_metric_id` (survives
    the stage builder's exchange copies) or the object id."""
    k = getattr(node, "_metric_id", None)
    return id(node) if k is None else k


def iter_metric_nodes(physical):
    """Every node that can own a metric record, INCLUDING a whole-query
    wrapper's inner plan (its child_fields=() hides the inner tree from
    the schedulable walk, but a runtime tier degrade executes those
    operators directly — they need pre-assigned metric ids so the
    records land under keys the renderers know)."""
    def walk(node):
        yield node
        for c in metric_children(node, degraded_only=False):
            yield from walk(c)

    yield from walk(physical)


def metric_children(node, degraded_only: bool = True) -> list:
    """A node's children for metric/graph rendering. A whole-query
    wrapper that DEGRADED to the stage tier at runtime contributes its
    inner plan as a rendered child (per-member attribution through the
    wrapper — degraded profiles read like stage-tier profiles); a
    healthy wrapper keeps its single-dispatch fused_members view.
    `degraded_only=False` (metric-id assignment) always descends: the
    ids must exist BEFORE execution decides whether to degrade."""
    kids = list(node.children)
    inner = getattr(node, "degraded_inner", None)
    if inner is not None:
        inner_plan = inner() if degraded_only else inner(always=True)
        if inner_plan is not None:
            kids = [inner_plan] + kids
    return kids


def iter_plan_metrics(physical, rec: dict):
    """Depth-first (node, depth, key, metric-fields) over the executed
    plan — the single walker both plan_graph and EXPLAIN ANALYZE consume,
    so a new metric field reaches every renderer at once. Descends into
    a runtime-degraded whole-query wrapper's inner plan (see
    metric_children)."""
    out = []

    def walk(node, depth):
        key = metric_key(node)
        out.append((node, depth, key, op_metric_fields(rec.get(key))))
        for c in metric_children(node):
            walk(c, depth + 1)

    walk(physical, 0)
    return out


def op_metric_fields(ent: dict | None) -> dict:
    """One operator record → the per-node metric fields every renderer
    shares (plan_graph, EXPLAIN ANALYZE, history server). Single place to
    extend when records grow new counters — the walkers only add their
    own identity/topology fields around this."""
    if not ent:
        return {"rows": None, "rows_exact": True, "ms": None,
                "batches": None, "launches": None, "compile_ms": None,
                "flops": None, "bytes": None, "gbps": None}
    ms = ent["ms"]
    by = ent.get("bytes") or 0.0
    return {"rows": ent["rows"], "rows_exact": ent["rows_exact"],
            "ms": round(ms, 3),
            "batches": ent["batches"] or None,
            "launches": dict(ent["kinds"]) if ent["kinds"] else None,
            "compile_ms": round(ent["compile_ms"], 3)
            if ent["compile_ms"] else None,
            "flops": round(ent.get("flops") or 0.0, 1) or None,
            "bytes": round(by, 1) or None,
            # achieved device bandwidth: captured bytes over INCLUSIVE
            # wall-ms (an understatement for parents that time their
            # children — still the roofline-facing number per leaf/stage)
            "gbps": round(by / (ms / 1000.0) / 1e9, 3)
            if by and ms > 0 else None}


def finalize_plan_metrics(rec: dict | None, host: dict | None = None) -> None:
    """Resolve parked row masks at query end: one host copy per DISTINCT
    mask identity, deduped QUERY-LOCALLY so masks shared across operators
    (reorder projections, rewrapped union batches) count once. `host`
    maps id(mask) to host copies already made (a collect's read of the
    result planes); the rest come home in ONE read, the sync
    `metrics.rows`. A local dict — not the bounded utils/device_memo
    LRU — because parked masks are per-query temporaries: pushing them
    through the shared memo could evict the dense-range seeds and cause
    real kernel re-launches. This is the only device read the metrics
    layer performs, and it happens after the query's last dispatch."""
    if not rec:
        return
    host = dict(host or ())
    pending = {id(m): m for ent in _op_records(rec)
               for m in ent.get("pending") or () if id(m) not in host}
    if pending:
        from ..utils.device_memo import device_read

        try:
            host.update(zip(pending, device_read("metrics.rows",
                                                 *pending.values())))
        except Exception:
            pass  # the masks stay unread: their rows are a lower bound
    counts: dict[int, int] = {}  # id(mask) -> live rows, this query only
    for ent in _op_records(rec):
        masks, ent["pending"] = ent.get("pending"), []
        for mask in masks or ():
            got = host.get(id(mask))
            if got is None:
                ent["rows_exact"] = False
                continue
            n = counts.get(id(mask))
            if n is None:
                n = counts[id(mask)] = int(np.asarray(got).sum())
            ent["rows"] += n
    with _ATTR_LOCK:  # size-changing pop vs the live flush's iteration
        rec.pop(_PARKED_KEY, None)


def discard_pending(rec: dict | None) -> None:
    """Drop parked masks without resolving (failed queries)."""
    if not rec:
        return
    for ent in _op_records(rec):
        if ent.get("pending"):
            ent["pending"] = []
            ent["rows_exact"] = False
    with _ATTR_LOCK:  # size-changing pop vs the live flush's iteration
        rec.pop(_PARKED_KEY, None)


# ---------------------------------------------------------------------------
# Cross-process shipping (cluster workers → driver)
# ---------------------------------------------------------------------------

def export_op_records(rec: dict | None) -> dict:
    """Worker-side: resolve parked masks and strip device references so
    the per-operator records can ride the stage-task result back to the
    driver. Keys are the plan nodes' pre-assigned `_metric_id`s, which
    survive cloudpickle into the worker — the driver merges by the same
    key. Resolving here adds no extra sync: the task result path has
    already pulled every output batch to the host for Arrow-IPC block
    storage, so the stage's last dispatch is long done."""
    if not rec:
        return {}
    finalize_plan_metrics(rec)
    return {key: {f: v for f, v in ent.items() if f != "pending"}
            for key, ent in rec.items() if key != _PARKED_KEY}


def export_op_records_partial(rec: dict | None) -> dict:
    """Live-telemetry snapshot of in-flight per-operator records: host
    counters only, parked row-masks STAY PARKED (resolving them is a
    device sync the mid-query contract forbids — they resolve once at
    task end). Rows with pending masks degrade to a lower bound
    (rows_exact=False) in the snapshot; the final task-return record
    supersedes with exact values. Never touches a device array."""
    if not rec:
        return {}
    out = {}
    with _ATTR_LOCK:
        for key, ent in rec.items():
            if key == _PARKED_KEY:
                continue
            out[key] = {
                "rows": ent["rows"],
                "rows_exact": ent["rows_exact"] and not ent["pending"],
                "batches": ent["batches"], "ms": round(ent["ms"], 3),
                "calls": ent["calls"], "kinds": dict(ent["kinds"]),
                "launch_total": ent["launch_total"],
                "compile_ms": round(ent["compile_ms"], 3),
                "flops": round(ent.get("flops", 0.0), 1),
                "bytes": round(ent.get("bytes", 0.0), 1)}
    return out


def merge_op_records(dst: dict, shipped: dict) -> None:
    """Driver-side: fold a worker's shipped per-operator records into the
    query's plan_metrics dict (same key space — `_metric_id`). Counters
    accumulate; rows_exact degrades monotonically. Lanes of one query
    may merge from several map tasks concurrently, so the increments
    serialize on the shared attribution lock."""
    with _ATTR_LOCK:
        for key, src in shipped.items():
            ent = dst.get(key)
            if ent is None:
                ent = dst[key] = new_op_record()
            ent["rows"] += src.get("rows", 0)
            ent["rows_exact"] = ent["rows_exact"] and \
                src.get("rows_exact", True)
            ent["batches"] += src.get("batches", 0)
            ent["ms"] += src.get("ms", 0.0)
            ent["calls"] += src.get("calls", 0)
            ent["launch_total"] += src.get("launch_total", 0)
            ent["compile_ms"] += src.get("compile_ms", 0.0)
            ent["flops"] += src.get("flops", 0.0)
            ent["bytes"] += src.get("bytes", 0.0)
            for kind, n in (src.get("kinds") or {}).items():
                ent["kinds"][kind] = ent["kinds"].get(kind, 0) + n


# ---------------------------------------------------------------------------
# Fused-stage re-attribution (the FuseStages mapping, inverted)
# ---------------------------------------------------------------------------

def pipeline_member_names(filters, outputs) -> list[str]:
    """Filter/Project member descriptions of a fused pipeline (shared by
    the fused operators' `fused_members` implementations)."""
    out = []
    if filters:
        out.append("Filter[" + " AND ".join(
            f.simple_string() for f in filters)[:80] + "]")
    out.append("Project[" + ", ".join(
        o.simple_string() for o in outputs)[:80] + "]")
    return out


def fused_members(node) -> list[str]:
    """Constituent operators a whole-stage fused node subsumes, in
    produce→consume order — the single fused dispatch per batch is
    re-attributed to these (the reference renders member operators inside
    their WholeStageCodegen cluster). Fused nodes expose the FuseStages
    mapping via their `fused_members()` method; anything else has none."""
    fn = getattr(node, "fused_members", None)
    return fn() if fn is not None else []


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE report
# ---------------------------------------------------------------------------

@dataclass
class AnalyzedReport:
    """Measured steady-state execution annotated onto the physical plan,
    reconciled against the static analyzer's predictions."""

    nodes: list = field(default_factory=list)       # rendered rows
    predicted: dict = field(default_factory=dict)   # kind -> launches
    measured: dict = field(default_factory=dict)    # kind -> launches
    prediction_exact: bool = True
    findings: list = field(default_factory=list)    # {severity, kind?, msg}
    counter_deltas: dict = field(default_factory=dict)
    wall_ms: float = 0.0
    # HBM accounting: predicted per-stage peaks (plan_lint memory model)
    # reconciled against the device ledger's measured watermarks
    # (obs/resources.py) — {"predicted_peak", "measured_peak",
    # "per_stage": [...], "remote": {executor: peak}, "peak_gbps"}
    memory: dict = field(default_factory=dict)

    @property
    def drift_kinds(self) -> list[str]:
        kinds = set(self.predicted) | set(self.measured)
        return sorted(k for k in kinds
                      if self.predicted.get(k, 0) != self.measured.get(k, 0))

    @property
    def has_unexplained_drift(self) -> bool:
        return any(f["severity"] == "error" for f in self.findings)

    def to_dict(self) -> dict:
        return {"nodes": list(self.nodes),
                "predicted": dict(self.predicted),
                "measured": dict(self.measured),
                "prediction_exact": self.prediction_exact,
                "findings": list(self.findings),
                "counter_deltas": dict(self.counter_deltas),
                "wall_ms": round(self.wall_ms, 3),
                "memory": dict(self.memory)}

    def render(self) -> str:
        out = ["== EXPLAIN ANALYZE (measured steady-state run, "
               f"{self.wall_ms:.1f} ms) =="]
        for nd in self.nodes:
            pad = "  " * nd["depth"]
            rows = nd["rows"]
            rows_s = "?" if rows is None else (
                str(rows) if nd.get("rows_exact", True) else f">={rows}")
            kinds = nd.get("launches") or {}
            ks = ",".join(f"{k}:{v}" for k, v in sorted(kinds.items()))
            peak_gbps = self.memory.get("peak_gbps")
            gbps = nd.get("gbps")
            gbps_s = ""
            if gbps is not None:
                gbps_s = f", {gbps:g} GB/s"
                if peak_gbps:
                    gbps_s += f" ({100.0 * gbps / peak_gbps:.0f}% of peak)"
            line = (f"{pad}{nd['detail']}  "
                    f"[rows={rows_s}"
                    + (f", {nd['ms']:.2f} ms" if nd["ms"] is not None else "")
                    + (f", batches={nd['batches']}" if nd.get("batches")
                       else "")
                    + (f", launches={{{ks}}}" if ks else "")
                    + (f", flops={nd['flops']:g}" if nd.get("flops")
                       else "")
                    + (f", bytes={_fmt_bytes(nd['bytes'])}"
                       if nd.get("bytes") else "")
                    + gbps_s
                    + (f", hbm_peak={_fmt_bytes(nd['hbm_peak'])}"
                       if nd.get("hbm_peak") else "")
                    + (f", compile={nd['compile_ms']:.1f} ms"
                       if nd.get("compile_ms") else "")
                    + "]")
            out.append(line)
            for m in nd.get("fused", ()):
                out.append(f"{pad}  + fused: {m} (shares the stage's "
                           "single dispatch per batch)")
        out.append("-- kernel launches: predicted vs measured "
                   + ("(prediction EXACT) --" if self.prediction_exact
                      else "(prediction approximate) --"))
        kinds = sorted(set(self.predicted) | set(self.measured))
        for k in kinds:
            p, m = self.predicted.get(k, 0), self.measured.get(k, 0)
            mark = "ok" if p == m else "DRIFT"
            out.append(f"  {k:<18} predicted={p:<5} measured={m:<5} {mark}")
        out.append(f"  {'total':<18} predicted="
                   f"{sum(self.predicted.values()):<5} measured="
                   f"{sum(self.measured.values()):<5}")
        mem = self.memory
        if mem:
            pred = mem.get("predicted_peak")
            meas = mem.get("measured_peak")
            out.append("-- memory (HBM, per-stage peaks) --")
            out.append(
                "  query peak: predicted~"
                + (_fmt_bytes(pred) if pred is not None else "?")
                + "  measured watermark="
                + (_fmt_bytes(meas) if meas is not None else "?")
                + ("" if not mem.get("remote") else
                   "  workers={"
                   + ", ".join(f"{e}:{_fmt_bytes(v.get('peak', 0))}"
                               for e, v in sorted(mem["remote"].items()))
                   + "}"))
            for st in mem.get("per_stage", ()):
                tag = st["op"] if st.get("instances", 1) == 1 \
                    else f"{st['op']} ×{st['instances']}"
                out.append(f"  {tag:<22} predicted~"
                           f"{_fmt_bytes(st['predicted'])}"
                           + (f"  measured peak="
                              f"{_fmt_bytes(st['measured'])}"
                              if st.get("measured") is not None else ""))
            if mem.get("xla_temp_peak"):
                out.append("  xla temp scratch (peak per dispatch): "
                           + _fmt_bytes(mem["xla_temp_peak"])
                           + " — outside the engine-tile ledger")
        if self.findings:
            out.append("-- findings --")
            for f in self.findings:
                out.append(f"  [{f['severity']}] {f['msg']}")
        else:
            out.append("-- findings: none (zero drift) --")
        return "\n".join(out)


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if n >= div:
            return f"{n / div:.1f}{unit}"
    return f"{n:.0f}B"


def _memory_section(physical, prediction, resources: dict | None,
                    peak_gbps: float | None, nodes: list,
                    findings: list) -> dict:
    """Reconcile the analyzer's per-stage predicted HBM against the
    device ledger's measured watermarks: annotate nodes with their
    operator's measured peak, build the report's memory dict, and raise
    drift findings when a measured watermark exceeds the model (the
    model is an upper bound on engine-held tiles — overshooting it means
    the model and the execution layer diverged)."""
    mem: dict = {}
    pred_stages = [s for s in getattr(prediction, "stages", ())
                   if s.get("hbm_bytes") is not None]
    measured_ops = (resources or {}).get("ops") or {}
    if pred_stages:
        # the ledger buckets by creator-operator CLASS, so a measured
        # watermark covers every instance of that class in the query —
        # compare it against the class-summed prediction, not a single
        # instance's (two ComputeExec stages ≠ each one doubling the
        # model)
        by_cls: dict = {}
        per_stage = []
        for s in pred_stages:
            ent = by_cls.get(s["op"])
            if ent is None:
                ent = by_cls[s["op"]] = {
                    "op": s["op"], "detail": s["detail"][:80],
                    "predicted": 0, "instances": 0}
                per_stage.append(ent)
            ent["predicted"] += s["hbm_bytes"]
            ent["instances"] += 1
        for ent in per_stage:
            ent["measured"] = measured_ops.get(ent["op"], {}).get("peak")
        mem["per_stage"] = per_stage
        mem["predicted_peak"] = getattr(prediction, "predicted_peak_hbm",
                                        None)
    if resources is not None:
        mem["measured_peak"] = resources.get("peak")
        if resources.get("remote"):
            mem["remote"] = resources["remote"]
    if peak_gbps:
        mem["peak_gbps"] = peak_gbps
    # per-node annotation: the creator-op's measured HBM watermark
    by_name: dict[str, int] = {}
    for nd in nodes:
        op = nd["op"]
        m = measured_ops.get(op)
        if m is not None and op not in by_name:
            by_name[op] = 1
            nd["hbm_peak"] = m.get("peak")
    pred = mem.get("predicted_peak")
    meas = mem.get("measured_peak")
    if pred and meas is not None and meas > pred:
        exact = getattr(prediction, "memory_exact", False)
        findings.append({
            "severity": "warning" if exact else "info",
            "kind": "hbm-drift",
            "msg": f"measured HBM watermark {_fmt_bytes(meas)} exceeds "
                   f"the memory model's predicted peak {_fmt_bytes(pred)}"
                   + ("" if exact else
                      " (model approximate: "
                      + "; ".join(getattr(prediction, "memory_notes",
                                          [])[:2]) + ")")})
    return mem


def _xla_temp_section(measured: dict, mem: dict,
                      findings: list) -> None:
    """Fold captured XLA temp (scratch) bytes into the memory
    reconciliation (PR 7 follow-on): the device ledger tracks
    engine-held tiles only, so a fused kernel's scratch is invisible to
    both the predicted and the measured watermark — with
    spark.tpu.metrics.kernelMemory on, the cost table's
    memory_analysis() capture names that headroom explicitly instead of
    leaving it as unexplained drift (and as surprise OOM room under
    spark.tpu.memory.budget). Scratch lives only inside one kernel, so
    the concurrent peak is the max over the kinds this query launched."""
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    per_kind = {}
    for kind in measured:
        tb = (KC.cost_by_kind.get(kind) or {}).get("temp_bytes")
        if tb:
            per_kind[kind] = int(tb)
    if not per_kind:
        return
    peak = max(per_kind.values())
    mem["xla_temp_by_kind"] = per_kind
    mem["xla_temp_peak"] = peak
    pred = mem.get("predicted_peak")
    meas = mem.get("measured_peak")
    if pred and meas is not None and meas <= pred and meas + peak > pred:
        findings.append({
            "severity": "info", "kind": "xla-temp",
            "msg": f"XLA kernel scratch (up to {_fmt_bytes(peak)} of "
                   "temp per dispatch, memory_analysis capture) pushes "
                   "true peak HBM past the engine-tile model's "
                   f"{_fmt_bytes(pred)} — the ledger only sees "
                   "engine-held tiles, so this headroom is real but "
                   "invisible to the measured watermark"})


def build_analyzed_report(physical, plan_metrics: dict | None,
                          prediction, measured: dict,
                          counter_deltas: dict,
                          wall_ms: float,
                          resources: dict | None = None,
                          peak_gbps: float | None = None) -> AnalyzedReport:
    """Assemble the EXPLAIN ANALYZE report from the executed plan's
    per-operator records, the measured per-kind launch deltas, the
    static analyzer's AnalysisReport, and the device ledger's HBM
    accounting for the measured query (`resources` — obs/resources.py
    query_record)."""
    rec = plan_metrics or {}
    finalize_plan_metrics(rec)
    nodes = []
    for node, depth, _key, fields in iter_plan_metrics(physical, rec):
        detail = node.simple_string() if hasattr(node, "simple_string") \
            else type(node).__name__
        detail = " ".join(detail.split())  # multi-line details flatten
        nodes.append({"op": type(node).__name__, "detail": detail[:140],
                      "depth": depth, **fields,
                      "fused": fused_members(node)})

    predicted = dict(prediction.predicted_launches)
    findings: list[dict] = []
    kinds = sorted(set(predicted) | set(measured))
    for k in kinds:
        p, m = predicted.get(k, 0), measured.get(k, 0)
        if p == m:
            continue
        if prediction.exact:
            findings.append({
                "severity": "error", "kind": k,
                "msg": f"unexplained drift on kernel kind '{k}': analyzer "
                       f"predicted {p} launches (and claimed exactness), "
                       f"measured {m} — the plan_lint launch model and the "
                       "execution layer have diverged"})
        else:
            findings.append({
                "severity": "info", "kind": k,
                "msg": f"drift on kernel kind '{k}' (predicted {p}, "
                       f"measured {m}) — analyzer declared itself "
                       "approximate: "
                       + "; ".join(prediction.inexact_reasons[:3])})
    # runtime minRows gate decisions are first-class findings
    gate_notes = {n for s in prediction.stages for n in s.get("notes", ())
                  if "minRows" in n}
    for n in sorted(gate_notes):
        findings.append({"severity": "info", "kind": "minRows-gate",
                         "msg": f"runtime fusion gate: {n}"})
    retries = counter_deltas.get("join.capacity_retry", 0)
    if retries:
        findings.append({
            "severity": "warning", "kind": "capacity-retry",
            "msg": f"{retries} join probe capacity retr"
                   f"{'y' if retries == 1 else 'ies'}: the probe kernel "
                   "re-launched with a doubled output bucket "
                   "(value-dependent cache key — extra dispatch + compile)"})
    stage_retries = counter_deltas.get("scheduler.stage_retries", 0)
    if stage_retries:
        findings.append({
            "severity": "warning", "kind": "stage-retry",
            "msg": f"{stage_retries} stage retr"
                   f"{'y' if stage_retries == 1 else 'ies'} during the "
                   "measured run (lineage re-execution inflates measured "
                   "launches)"})
    memory = _memory_section(physical, prediction, resources, peak_gbps,
                             nodes, findings)
    _xla_temp_section(measured, memory, findings)
    return AnalyzedReport(nodes=nodes, predicted=predicted,
                          measured=dict(measured),
                          prediction_exact=prediction.exact,
                          findings=findings,
                          counter_deltas=dict(counter_deltas),
                          wall_ms=wall_ms, memory=memory)
