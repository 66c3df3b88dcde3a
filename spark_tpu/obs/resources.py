"""Device-resource ledger: HBM occupancy, kernel cost, memory budgets.

Role of the reference's task memory accounting — UnifiedMemoryManager's
ExecutionMemoryPool/StorageMemoryPool bookkeeping (core/memory/
ExecutionMemoryPool.scala acquireMemory/releaseMemory) plus the
peak-execution-memory task metric the UI renders — re-shaped for the XLA
allocation model. XLA owns the actual HBM allocator, so the engine does
not *reserve* bytes here; it keeps an attributed shadow ledger of every
device buffer the ENGINE holds (columnar batches: column data + validity
planes + row masks — which is also what shuffle reduce tiles, join build
inputs and agg state are made of), so queries, operators and executors
can be charged for the HBM they pin.

Contract (same as the rest of obs/): everything in this module is pure
host bookkeeping — ZERO kernel launches, no device syncs. Sizes come
from array shape/dtype metadata (`.shape`/`.dtype`/`.nbytes` never touch
device data), attribution comes from the existing contextvar scopes
(obs.tracing query scope, obs.metrics operator scope — both of which
already propagate into par_map lanes, scoped_submit pools and cluster
worker tasks), and deregistration rides weakref finalizers so the ledger
never extends a buffer's lifetime.

Three public legs:

  * `GLOBAL_LEDGER` — process-global `DeviceLedger`. Buffers register by
    ARRAY IDENTITY with a refcount, so ten ColumnarBatch wrappers over
    one device column charge the ledger once; per-query and per-operator
    buckets track live bytes and watermarks (peaks). Worker processes
    run their own instance; their per-task peaks ship back with the
    stage obs payload and on the executor heartbeat (exec/worker_main),
    so cluster live status shows HBM per executor.

  * kernel cost — the KernelCache (physical/compile.py) captures each
    compiled kernel's XLA `cost_analysis()` (flops, bytes accessed) at
    first invocation via the *lowering* (no second backend compile) with
    an argument/output-metadata fallback, and feeds it to the operator
    attribution scope per launch. EXPLAIN ANALYZE and plan_graph render
    per-operator flops/bytes and achieved GB/s against
    `device_peak_gbps()`.

  * memory budget — `check_memory_budget` pre-flights the plan
    analyzer's memory model (analysis/plan_lint.py) against
    `spark.tpu.memory.budget` BEFORE dispatch and raises
    `MemoryBudgetExceeded` naming the offending stage, instead of
    letting XLA OOM opaquely mid-query — the admission-control primitive
    the serving direction needs.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict

__all__ = ["DeviceLedger", "GLOBAL_LEDGER", "MemoryBudgetExceeded",
           "check_memory_budget", "configure", "device_peak_gbps",
           "kernel_cost_enabled", "kernel_memory_enabled",
           "ledger_enabled"]

_MAX_QUERIES = 64   # retained per-query records (ring, matches LiveObs)


# ---------------------------------------------------------------------------
# process-wide switches (config-driven; flipped by configure())
# ---------------------------------------------------------------------------

# module flags rather than per-call conf reads: registration runs on the
# ColumnarBatch constructor and kernel cost capture on the KernelCache
# first-invocation path — both too hot for a conf dict lookup + parse
_LEDGER_ON = True
_KERNEL_COST_ON = True
_KERNEL_MEMORY_ON = False


def configure(conf) -> None:
    """Apply a session/worker conf to the process-global switches
    (spark.tpu.memory.ledger, spark.tpu.metrics.kernelCost/kernelMemory).
    Called by TpuSession.__init__ and the worker-side begin_stage_obs —
    the ledger itself stays process-global like the KernelCache."""
    global _LEDGER_ON, _KERNEL_COST_ON, _KERNEL_MEMORY_ON

    from ..config import KERNEL_COST, KERNEL_MEMORY, MEMORY_LEDGER

    # conf values are host data — bool() here never touches device
    _LEDGER_ON = bool(conf.get(MEMORY_LEDGER))  # tpulint: ignore[host-sync]
    _KERNEL_COST_ON = bool(conf.get(  # tpulint: ignore[host-sync]
        KERNEL_COST))
    _KERNEL_MEMORY_ON = bool(conf.get(  # tpulint: ignore[host-sync]
        KERNEL_MEMORY))


def ledger_enabled() -> bool:
    return _LEDGER_ON


def kernel_cost_enabled() -> bool:
    return _KERNEL_COST_ON


def kernel_memory_enabled() -> bool:
    """XLA memory_analysis() temp-bytes capture (off by default: the AOT
    lowering compile it needs is not shared with the dispatch path on
    this jax version — one extra backend compile per distinct kernel)."""
    return _KERNEL_MEMORY_ON


# ---------------------------------------------------------------------------
# peak-bandwidth reference (achieved-vs-peak GB/s in EXPLAIN ANALYZE)
# ---------------------------------------------------------------------------

# published HBM bandwidth in GB/s, keyed by the exact `device_kind` the
# chip reports; the conf override spark.tpu.memory.peakGbps wins when
# set (>0). A device that is not in the table is an error, not a default.
_PEAK_GBPS_BY_KIND = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e at 819 GB/s per
    # chip; kind string as reported by the chip (PR 21 chip run)
    "TPU v5 lite": 819.0,
}


def device_peak_gbps(conf=None) -> float | None:
    """Peak HBM GB/s of the local accelerator. None on a CPU backend
    (no meaningful HBM roofline); an accelerator whose device_kind is
    not in the table raises. Reads only the jax device *descriptor* —
    never device memory."""
    if conf is not None:
        from ..config import MEMORY_PEAK_GBPS

        v = float(conf.get(MEMORY_PEAK_GBPS))  # tpulint: ignore[host-sync]
        if v > 0:
            return v
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        return _PEAK_GBPS_BY_KIND[dev.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak HBM bandwidth for device_kind "
            f"{dev.device_kind!r}: add it to obs/resources."
            "_PEAK_GBPS_BY_KIND with its source, or set "
            "spark.tpu.memory.peakGbps") from None


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def _new_bucket() -> dict:
    return {"bytes": 0, "peak": 0, "registered": 0, "released": 0}


class DeviceLedger:
    """Attributed shadow ledger of engine-held device bytes.

    Registration is by array identity with a refcount: wrappers sharing
    device arrays (rewrapped batches, trivial projections, shared row
    masks) charge once, and the charge releases when the LAST owner is
    garbage-collected. Each identity is charged to the (query, operator)
    scope active at first registration — the creator owns the buffer,
    the reference's per-task peak-execution-memory discipline.

    Thread-safe; every operation is O(arrays in one batch) dict work.
    """

    def __init__(self):
        # REENTRANT: a GC cycle can run a batch's _release finalizer on
        # whatever thread happens to allocate — including one already
        # inside a ledger method holding this lock (dict growth inside
        # _register can trigger collection). A plain Lock would deadlock
        # that thread against itself; with an RLock the nested release
        # runs as a complete, consistent sequence.
        self._lock = threading.RLock()
        # id(array) -> [nbytes, refs, qid, op]
        self._arrays: dict[int, list] = {}
        self.bytes = 0              # live engine-held device bytes
        self.peak = 0               # process-lifetime watermark
        self.registered_total = 0   # cumulative bytes ever charged
        self.released_total = 0     # cumulative bytes ever released
        self._win_peak = 0          # window watermark (begin_window)
        # qid (None = unattributed) -> bucket + per-op buckets + remote
        self._queries: "OrderedDict" = OrderedDict()

    # -- buckets ----------------------------------------------------------
    def _qrec(self, qid) -> dict:
        q = self._queries.get(qid)
        if q is None:
            q = self._queries[qid] = {**_new_bucket(), "ops": {},
                                      "remote": {}}
            while len(self._queries) > _MAX_QUERIES:
                self._queries.popitem(last=False)
        return q

    # -- writes -----------------------------------------------------------
    def register_batch(self, batch) -> None:
        """Charge one ColumnarBatch's device planes (column data,
        validity masks, row mask) to the current query/operator scope and
        arm a finalizer that releases the charge when the batch dies.
        Metadata only — never reads device data."""
        if not _LEDGER_ON:
            return
        pairs = []
        rm = batch.row_mask
        if rm is not None and hasattr(rm, "shape"):
            pairs.append((rm, int(rm.size)))     # bool plane: 1 B/row
        for c in batch.columns:
            d = getattr(c, "data", None)
            if d is not None and hasattr(d, "dtype"):
                pairs.append((d, int(d.size) * d.dtype.itemsize))
            v = getattr(c, "validity", None)
            if v is not None and hasattr(v, "shape"):
                pairs.append((v, int(v.size)))
        if pairs:
            self._register(pairs, batch)

    def charge_arrays(self, arrays) -> list:
        """Explicitly charge raw device arrays (mesh staging send
        buffers — parallel/mesh_fusion.py) to the current scope. Returns
        the token for `release_arrays`; the caller owns the lifetime
        (donated buffers release at dispatch, undonated ones after the
        outputs register). Metadata only — never reads device data."""
        if not _LEDGER_ON:
            return []
        pairs = []
        for a in arrays:
            if a is None or not hasattr(a, "dtype"):
                continue
            pairs.append((a, int(a.size) * a.dtype.itemsize))
        return self._charge(pairs) if pairs else []

    def release_arrays(self, token: list) -> None:
        """Release a `charge_arrays` token (idempotent per token use)."""
        if token:
            self._release(token)

    def _register(self, pairs, owner) -> None:
        keys = self._charge(pairs)
        # the finalizer closes over ids + the ledger only — it must not
        # keep the arrays (or the batch) alive
        weakref.finalize(owner, self._release, keys)

    def _charge(self, pairs) -> list:
        from .metrics import current_op_name
        from .tracing import current_query

        qid = current_query()
        op = current_op_name()
        keys = []
        with self._lock:
            for obj, nb in pairs:
                key = id(obj)
                keys.append(key)
                ent = self._arrays.get(key)
                if ent is not None:
                    ent[1] += 1           # shared plane: one charge
                    continue
                self._arrays[key] = [nb, 1, qid, op]
                self.bytes += nb
                self.registered_total += nb
                if self.bytes > self.peak:
                    self.peak = self.bytes
                if self.bytes > self._win_peak:
                    self._win_peak = self.bytes
                q = self._qrec(qid)
                q["bytes"] += nb
                q["registered"] += nb
                if q["bytes"] > q["peak"]:
                    q["peak"] = q["bytes"]
                if op is not None:
                    o = q["ops"].get(op)
                    if o is None:
                        o = q["ops"][op] = _new_bucket()
                    o["bytes"] += nb
                    o["registered"] += nb
                    if o["bytes"] > o["peak"]:
                        o["peak"] = o["bytes"]
        return keys

    def _release(self, keys) -> None:
        with self._lock:
            for key in keys:
                ent = self._arrays.get(key)
                if ent is None:
                    continue
                ent[1] -= 1
                if ent[1] > 0:
                    continue
                nb, _, qid, op = self._arrays.pop(key)
                self.bytes -= nb
                self.released_total += nb
                q = self._queries.get(qid)
                if q is None:
                    continue
                q["bytes"] -= nb
                q["released"] += nb
                if op is not None and op in q["ops"]:
                    q["ops"][op]["bytes"] -= nb
                    q["ops"][op]["released"] += nb

    def merge_remote(self, qid, executor: str, shipped: dict) -> None:
        """Fold a worker task's shipped HBM accounting into the query
        record (worker HBM is a DIFFERENT device's memory — it reports
        side by side with the driver's, never summed into `bytes`)."""
        if not shipped:
            return
        with self._lock:
            rem = self._qrec(qid)["remote"]
            cur = rem.get(executor)
            if cur is None:
                rem[executor] = dict(shipped)
            else:
                cur["peak"] = max(cur.get("peak", 0),
                                  shipped.get("peak", 0))
                cur["bytes"] = shipped.get("bytes", cur.get("bytes", 0))

    # -- windows (bench measurement) --------------------------------------
    def begin_window(self) -> None:
        """Reset the window watermark to the current occupancy; read it
        back with window_peak() after the measured region."""
        with self._lock:
            self._win_peak = self.bytes

    def window_peak(self) -> int:
        with self._lock:
            return self._win_peak

    # -- reads ------------------------------------------------------------
    def snapshot(self) -> dict:
        """Executor-level occupancy (rides the heartbeat payload)."""
        with self._lock:
            return {"bytes": self.bytes, "peak": self.peak,
                    "arrays": len(self._arrays)}

    def query_record(self, qid) -> dict | None:
        """Deep-ish copy of one query's HBM accounting: live bytes,
        watermark, per-operator buckets, per-executor remote peaks."""
        with self._lock:
            q = self._queries.get(qid)
            if q is None:
                return None
            return {"bytes": q["bytes"], "peak": q["peak"],
                    "registered": q["registered"],
                    "released": q["released"],
                    "ops": {k: dict(v) for k, v in q["ops"].items()},
                    "remote": {k: dict(v) for k, v in q["remote"].items()}}

    def verify(self) -> list[str]:
        """Internal-consistency check (tests/test_resource_obs.py):
        non-negative balances everywhere, attribution sums never
        exceeding the global ledger, identity table reconciling with the
        byte counter."""
        issues = []
        with self._lock:
            if self.bytes < 0:
                issues.append(f"global balance negative: {self.bytes}")
            table = sum(e[0] for e in self._arrays.values())
            if table != self.bytes:
                issues.append(f"identity table {table} B != balance "
                              f"{self.bytes} B")
            if self.registered_total - self.released_total != self.bytes:
                issues.append("registered - released != balance")
            attributed = 0
            for qid, q in self._queries.items():
                if q["bytes"] < 0:
                    issues.append(f"query {qid} balance negative: "
                                  f"{q['bytes']}")
                attributed += max(q["bytes"], 0)
                for op, o in q["ops"].items():
                    if o["bytes"] < 0:
                        issues.append(
                            f"op {op} of query {qid} negative: "
                            f"{o['bytes']}")
            # evicted query records release against the global counter
            # but not their popped bucket — attribution can only be <=
            if attributed > self.bytes:
                issues.append(f"attributed {attributed} B > global "
                              f"{self.bytes} B")
        return issues


GLOBAL_LEDGER = DeviceLedger()


# ---------------------------------------------------------------------------
# memory budget pre-flight (admission control)
# ---------------------------------------------------------------------------

class MemoryBudgetExceeded(RuntimeError):
    """The plan analyzer's memory model predicts peak HBM above
    spark.tpu.memory.budget — raised BEFORE any dispatch, naming the
    offending stage, instead of an opaque XLA OOM mid-query."""


def check_memory_budget(physical, conf, report=None,
                        cluster: bool = False) -> None:
    """Pre-flight the memory model against spark.tpu.memory.budget
    (0 = unlimited). Pure host work — nothing executes on device."""
    from ..config import MEMORY_BUDGET

    budget = int(conf.get(MEMORY_BUDGET))
    if budget <= 0:
        return
    if report is None:
        from ..analysis.plan_lint import analyze_plan

        report = analyze_plan(physical, conf, cluster=cluster)
    peak = report.predicted_peak_hbm
    if peak is None or peak <= budget:
        return
    staged = [s for s in report.stages if s.get("hbm_bytes")]
    worst = max(staged, key=lambda s: s["hbm_bytes"]) if staged else None
    where = (f"largest stage: {worst['op']} "
             f"[{worst['detail'][:80]}] holding "
             f"~{worst['hbm_bytes'] / (1 << 20):.1f} MiB"
             if worst else "no per-stage breakdown available")
    raise MemoryBudgetExceeded(
        f"query predicted peak HBM ~{peak / (1 << 20):.1f} MiB exceeds "
        f"spark.tpu.memory.budget={budget} bytes "
        f"({budget / (1 << 20):.1f} MiB); {where}. Raise the budget, "
        "lower spark.tpu.batch.capacity, or repartition so less of the "
        "plan is resident at once (nothing was dispatched).")
