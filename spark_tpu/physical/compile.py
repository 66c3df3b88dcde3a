"""Kernel compilation: expression trees → cached jitted batch functions.

Role of the reference's WholeStageCodegen + CodeGenerator
(sqlx/WholeStageCodegenExec.scala:673 doCodeGen; sqlcat/.../codegen/
CodeGenerator.scala:1557 Janino compile + cache). Here the "generated code"
is a traced JAX function per (expression structure, input signature,
capacity, aux signature); XLA performs the operator fusion the reference
hand-rolls with produce/consume. The cache is keyed STRUCTURALLY (attribute
ids normalized to input positions) so repeated queries reuse compiled
kernels across plan instances.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import hashlib
import threading
from typing import Any, Callable, Sequence

import numpy as np

from ..columnar.batch import Column, ColumnarBatch
from ..expr.eval import HostCtx, TraceCtx, Val
from ..obs.metrics import (
    batch_cost_scope,
    current_op_row as _obs_op_row,
    record_kernel_compile as _obs_compile,
    record_kernel_disk_hit as _obs_disk_hit,
    record_kernel_launch as _obs_launch,
    record_kernel_miss as _obs_miss,
)
from ..obs.tracing import DEVICE as _DEVICE, span_here as _span_here
from ..expr.expressions import (
    Alias, AttributeReference, Expression, Literal, SortOrder,
)
from ..types import ArrayType, DataType, StringType, StructField, StructType
from ..utils import faults as _faults

__all__ = ["canonical_key", "KernelCache", "ExprPipeline", "bind_inputs",
            "broadcast_to_cap", "trace_pipeline", "pipeline_host_pass",
            "pipeline_signature", "pipeline_columns", "named_jit",
            "stage_jit", "capture_programs", "note_program", "module_name"]


# ---------------------------------------------------------------------------
# Stable program names
# ---------------------------------------------------------------------------

# Part of every named program's hash. jax's persistent-cache key leaves op
# metadata out (jax_compilation_cache_include_metadata_in_key), so a
# program whose `jax.named_scope` labels changed is a cache HIT on the old
# executable with the old `op_name`s. Bump this when the scopes inside the
# kernel bodies (ops/sorting, ops/joining, ops/grouping, the whole-query
# emits) are renamed, so that the next run compiles afresh.
SCOPES_VERSION = 1


def named_jit(kind: str, key, fn, labels: Sequence = (), **jit_kwargs):
    """`jax.jit(fn)` under the name `<kind>_<sha1 of (SCOPES_VERSION, key,
    labels)>[:10]`, so that the XLA module is `jit_<kind>_<hash>` in a
    profiler trace and in the compiled text. `key` is the program's
    KernelCache key and `labels` its scope labels (the operator rows):
    both are tuples of strings and numbers whose `repr` is the same in
    every process — no `hash()`, no `id()` — because XLA's disk-cache key
    includes the module name, and a name that moved would recompile
    every program on every start."""
    import jax

    digest = hashlib.sha1(repr(
        (SCOPES_VERSION, key, tuple(labels))).encode()).hexdigest()[:10]
    fn.__name__ = fn.__qualname__ = f"{kind}_{digest}"
    return jax.jit(fn, **jit_kwargs)  # tpulint: ignore[raw-jit]


# the key `KernelCache.get_or_build` is building under, for stage_jit
_BUILDING: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_kernel_building", default=None)


def stage_jit(fn):
    """`named_jit` for a kernel of the tiers under the whole tier, called
    inside the builder that `KernelCache.get_or_build` runs: the kind is
    the key's first word (`pipeline`, `join_probe`, `gagg`, ...: what
    `launches_by_kind` and `kernel.first_launch` call it) and the key the
    one it is cached under, so the XLA module is `jit_<kind>_<hash>` and a
    trace says which kernel held the chip. A kernel is shared by every
    operator whose structure gives its key, so an operator's row is not
    part of the name: `capture_programs` notes the launching operator of
    each launch instead."""
    key = _BUILDING.get()
    if key is None:
        raise RuntimeError("stage_jit outside a KernelCache.get_or_build "
                           "builder: there is no key to name the kernel by")
    return named_jit(str(key[0]), key, fn)


def module_name(kernel) -> str | None:
    """The XLA module a KernelCache kernel runs as (`jit_<name>`), which
    is how a profiler trace names it."""
    name = getattr(getattr(kernel, "_kernel", kernel), "__name__", None)
    return None if name is None else "jit_" + name


# programs launched while a capture is open: explain("device") and the
# tests ask which programs a query ran, with what to compile them again
_CAPTURE: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_program_capture", default=None)


class _Captured(list):
    """What `capture_programs` yields: the `note_program` records, and in
    `launches` one (module name, kind, operator row) for every launch of
    a KernelCache kernel, in launch order."""

    def __init__(self):
        super().__init__()
        self.launches: list = []


@contextlib.contextmanager
def capture_programs():
    """Collect a record of every named program launched in this context:
    {program, members, scopes, kernel, args} with `args` reduced to
    shapes, so that `kernel._kernel.lower(*args)` gives the program's
    text again without holding a plane. Its `launches` lists every
    KernelCache launch with the operator that made it
    (obs/metrics.current_op_row): the stage tier's kernels are shared
    between operators, so the launch and not the program says whose
    device time a run is."""
    got = _Captured()
    token = _CAPTURE.set(got)
    try:
        yield got
    finally:
        _CAPTURE.reset(token)


def note_program(kernel, args: tuple, members: list, scopes: list) -> None:
    """One launch for an open capture; a contextvar read otherwise."""
    got = _CAPTURE.get()
    if got is None:
        return
    import jax

    # a plane on one device goes in as the call gives it, with no
    # sharding: the lowering is then the one the call compiled, and
    # compiling it again is a cache hit and not a second compile
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if len(a.sharding.device_set) > 1
            else None), args)
    got.append({"program": module_name(kernel), "members": list(members),
                "scopes": list(scopes), "kernel": kernel, "args": shapes})


# ---------------------------------------------------------------------------
# Structural canonicalization
# ---------------------------------------------------------------------------

def canonical_key(e: Expression, id_to_pos: dict[int, int]) -> tuple:
    """Hashable structural key with attribute ids replaced by input positions
    (so two queries with identical shapes share kernels)."""
    if isinstance(e, AttributeReference):
        return ("attr", id_to_pos.get(e.expr_id, -1), str(e.dtype))
    if isinstance(e, Alias):
        return ("alias", canonical_key(e.child, id_to_pos))
    if isinstance(e, Literal):
        return ("lit", e.value if not isinstance(e.value, (list, dict)) else str(e.value),
                str(e.dtype))
    if isinstance(e, SortOrder):
        return ("sort", canonical_key(e.child, id_to_pos), e.ascending,
                e.nulls_first)
    data = []
    for k, v in sorted(e.__dict__.items()):
        if k in e.child_fields or k.startswith("_") or isinstance(v, Expression):
            continue
        if k in e.equality_excluded_fields:
            # a second view of the children (CaseWhen.branches pairs up
            # branch_exprs): its text carries the attribute ids, and a key
            # with them in it never matches the same query parsed again
            continue
        if isinstance(v, (list, tuple)) and any(isinstance(x, Expression) for x in v):
            continue
        if isinstance(v, DataType):
            v = str(v)
        try:
            hash(v)
        except TypeError:
            v = str(v)
        data.append((k, v))
    return (type(e).__name__, tuple(data),
            tuple(canonical_key(c, id_to_pos) for c in e.children
                  if isinstance(c, Expression)))


# ---------------------------------------------------------------------------
# Kernel cache
# ---------------------------------------------------------------------------

def _tree_nbytes(x, depth: int = 0) -> int:
    """Sum .nbytes over array leaves of a (nested) argument structure —
    shape/dtype metadata only, never touches device data."""
    if depth > 4:
        return 0
    nb = getattr(x, "nbytes", None)
    if nb is not None and not isinstance(x, (bytes, str)):
        return int(nb)
    if isinstance(x, (list, tuple)):
        return sum(_tree_nbytes(i, depth + 1) for i in x)
    if isinstance(x, dict):
        return sum(_tree_nbytes(v, depth + 1) for v in x.values())
    return 0


def _capture_kernel_cost(f, args, kwargs) -> dict | None:
    """Per-launch cost of one compiled kernel, captured once at first
    invocation: XLA's HLO cost analysis via the LOWERING (tracing only —
    no second backend compile; jax.stages.Lowered.cost_analysis) with a
    metadata fallback (argument bytes) when lowering is unavailable.
    Gated by spark.tpu.metrics.kernelCost. With
    spark.tpu.metrics.kernelMemory additionally on, the lowering is
    also COMPILED once to read memory_analysis() temp (scratch) bytes —
    the per-dispatch HBM the engine-tile ledger cannot see; that AOT
    compile is not shared with the dispatch path, hence the separate
    opt-in."""
    from ..obs.resources import kernel_cost_enabled, kernel_memory_enabled

    if not kernel_cost_enabled():
        return None
    cost = {"flops": 0.0, "bytes": float(_tree_nbytes(args)
                                         + _tree_nbytes(kwargs)),
            "source": "metadata"}
    lower = getattr(f, "lower", None)
    if lower is not None:
        try:
            lowered = lower(*args, **kwargs)
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0) or 0.0)
            ba = float(ca.get("bytes accessed", 0.0) or 0.0)
            if ba > 0.0:
                cost = {"flops": flops, "bytes": ba, "source": "xla"}
            elif flops > 0.0:
                cost["flops"] = flops
            if kernel_memory_enabled():
                try:
                    ma = lowered.compile().memory_analysis()
                    tb = getattr(ma, "temp_size_in_bytes", None)
                    if tb is not None:
                        cost["temp_bytes"] = int(tb)
                except Exception:
                    pass  # memory capture must never fail a dispatch
        except Exception:
            pass  # cost capture must never fail a dispatch
    return cost


class KernelCache:
    """Process-global LRU of jitted kernels.

    Besides hit/miss bookkeeping the cache counts kernel LAUNCHES — every
    invocation of a cached kernel is one device dispatch, so the counters
    are the ground truth for "one dispatch per batch per stage" regression
    tests (the reference's analog is WholeStageCodegen's generated-class
    instantiation count). `launches_by_kind` buckets by the cache key's
    leading tag ("pipeline", "fused_agg", "gagg", ...). `compile_ms`
    accumulates builder time plus each kernel's first invocation (XLA
    compiles lazily on first call).

    Resource accounting (obs/resources.py): the first invocation also
    captures the kernel's per-launch cost (XLA cost_analysis flops /
    bytes accessed via the lowering), after which every launch adds it
    to the process counters (`flops_total`, `bytes_total`), the per-kind
    cost table (`cost_by_kind`), and the executing operator's record —
    launch attribution multiplied out to FLOPs and bytes."""

    def __init__(self, max_size: int = 1024):
        self._cache: "collections.OrderedDict[tuple, Any]" = collections.OrderedDict()
        self.max_size = max_size
        self.hits = 0
        self.misses = 0
        self.launches = 0
        self.compile_ms = 0.0
        self.launches_by_kind: "collections.Counter" = collections.Counter()
        # engine compiles (misses) whose XLA backend compile was served
        # from the persistent disk cache (exec/persist_cache.py): a warm
        # restart re-traces and re-jits every kernel (misses count them)
        # but the expensive XLA compile hits disk — distinct counters so
        # the obs layer tells disk-served compiles from true cold ones
        self.disk_hit_compiles = 0
        self.flops_total = 0.0      # cumulative captured flops dispatched
        self.bytes_total = 0.0      # cumulative captured bytes accessed
        # kind -> {"flops","bytes","kernels","launches"} aggregate of the
        # captured per-launch costs (the resource gate's cost table)
        self.cost_by_kind: dict = {}
        # scheduler stages run in threads; OrderedDict mutation is not
        # thread-safe (builder() itself runs unlocked — duplicate builds of
        # the same key are benign, a torn dict is not)
        self._lock = threading.Lock()

    def _wrap(self, key: tuple, f):
        if not callable(f):
            return f
        kind = key[0] if isinstance(key, tuple) and key else "?"
        # "ran": some invocation has returned — until then an XLA error
        # out of this kernel is its (lazy, first-call) compile
        state = {"first": True, "ran": False, "cost": None,
                 "capturing": False}

        def call(*args, **kwargs):
            if state["ran"]:
                return f(*args, **kwargs)
            try:
                out = f(*args, **kwargs)
            except Exception as e:
                if _faults.is_runtime_fault(e) \
                        and not isinstance(e, _faults.InjectedFault):
                    raise _faults.KernelCompileError(
                        f"kernel '{kind}' failed before its first "
                        f"completed launch — compile refusal: "
                        f"{type(e).__name__}: {e}") from e
                raise
            state["ran"] = True
            return out

        def launch(*args, **kwargs):
            if _faults.ENABLED:
                # chaos seam: an injected dispatch fault stands in for
                # an XLA runtime error the pre-flight could not predict
                # (RESOURCE_EXHAUSTED at launch). Raised BEFORE counting
                # — a launch that never dispatched must not count.
                # Idle cost: one module-bool read per launch.
                _faults.maybe_fail("kernel.dispatch", detail=str(kind))
            with self._lock:
                self.launches += 1
                self.launches_by_kind[kind] += 1
                first = state["first"]
                state["first"] = False
                # one capturer at a time; retried while unset (a capture
                # under kernelCost=off yields None, so flipping it on
                # later still costs this kernel), concurrent launches
                # during the capture window just skip cost accounting
                cost = state["cost"]
                capture = cost is None and not state["capturing"]
                if capture:
                    state["capturing"] = True
                elif cost is not None:
                    # steady state: cost accounting rides the same
                    # critical section as the launch counters
                    self.flops_total += cost["flops"]
                    self.bytes_total += cost["bytes"]
                    ent = self.cost_by_kind.get(kind)
                    if ent is not None:
                        ent["flops"] += cost["flops"]
                        ent["bytes"] += cost["bytes"]
                        ent["launches"] += 1
            if capture:
                # BEFORE the dispatch so even the first launch
                # attributes cost (host-side trace/lower only — no
                # kernel launch, no device sync)
                with _span_here("kernel.cost_capture", "compile",
                                {"kind": str(kind),
                                 "program": module_name(f)}):
                    cost = _capture_kernel_cost(f, args, kwargs)
                with self._lock:
                    state["cost"] = cost
                    state["capturing"] = False
                    if cost is not None:
                        ent = self.cost_by_kind.setdefault(
                            kind, {"flops": 0.0, "bytes": 0.0,
                                   "kernels": 0, "launches": 0})
                        ent["kernels"] += 1
                        ent["flops"] += cost["flops"]
                        ent["bytes"] += cost["bytes"]
                        ent["launches"] += 1
                        tb = cost.get("temp_bytes")
                        if tb:
                            # scratch is per-dispatch, not cumulative —
                            # the kind's entry keeps the worst kernel
                            ent["temp_bytes"] = max(
                                ent.get("temp_bytes", 0), tb)
                        self.flops_total += cost["flops"]
                        self.bytes_total += cost["bytes"]
            # per-operator attribution (obs/metrics contextvar scope):
            # host bookkeeping only — no dispatch, no sync
            _obs_launch(kind, cost)
            got = _CAPTURE.get()
            if got is not None:
                got.launches.append((module_name(f), str(kind),
                                     _obs_op_row()))
            # the device has work from here: closes a gap a sync opened
            _DEVICE.launch(kind)
            if first:
                import time as _time

                # persistent compile cache (exec/persist_cache.py): the
                # disk-traffic counter delta across the first invocation
                # classifies THIS kernel's XLA compile as disk-served vs
                # true cold. Module-int reads — no overhead when the
                # cache is off (both counters stay 0). Concurrent first
                # invocations on other threads can in principle blur one
                # delta; the counters are process telemetry, not a gate
                # on correctness.
                from ..exec import persist_cache as _pc

                d0 = _pc.DISK_HITS
                t0 = _time.perf_counter()
                with _span_here("kernel.first_launch", "compile",
                                {"kind": str(kind),
                                 "program": module_name(f)}) as sp:
                    out = call(*args, **kwargs)
                    disk_hit = _pc.DISK_HITS > d0
                    sp.set_args({"disk_hit": disk_hit})
                dt = (_time.perf_counter() - t0) * 1000
                with self._lock:
                    self.compile_ms += dt
                    if disk_hit:
                        self.disk_hit_compiles += 1
                if disk_hit:
                    _obs_disk_hit(kind)
                _obs_compile(kind, dt)
                return out
            return call(*args, **kwargs)

        launch._kernel = f
        return launch

    def get_or_build(self, key: tuple, builder: Callable[[], Any]):
        with self._lock:
            f = self._cache.get(key)
            if f is not None:
                self.hits += 1
                self._cache.move_to_end(key)
                return f
            self.misses += 1
        # per-query ledger: one engine compile attributed to the query
        # whose dispatch built this kernel (obs/metrics.py)
        _obs_miss(key[0] if isinstance(key, tuple) and key else "?")
        if _faults.ENABLED:
            # chaos seam: a compile-time failure (trace/lower bug, XLA
            # compiler fault) — fired on the MISS path only, cached
            # kernels never re-compile
            _faults.maybe_fail(
                "kernel.compile",
                detail=str(key[0]) if isinstance(key, tuple) and key
                else "?")
        import time as _time

        t0 = _time.perf_counter()
        token = _BUILDING.set(key)
        try:
            f = self._wrap(key, builder())
        finally:
            _BUILDING.reset(token)
        dt = (_time.perf_counter() - t0) * 1000
        with self._lock:
            self.compile_ms += dt
            f = self._cache.setdefault(key, f)
            while len(self._cache) > self.max_size:
                self._cache.popitem(last=False)
        _obs_compile(key[0] if isinstance(key, tuple) and key else "?", dt)
        return f

    def counters(self) -> dict:
        """Snapshot for metrics/listener plumbing. Deliberately does NOT
        splat persist_cache.disk_counters() in: the compile.disk_* keys
        already ride the session metrics as per-query deltas (worker
        traffic folded in by the cluster scheduler), and process-absolute
        values under the same names would clobber them in the
        querySucceeded payload — one fact, one metric family. Callers
        that want the raw process-global XLA disk traffic read
        persist_cache.disk_counters() directly (the tests do)."""
        with self._lock:
            return {
                "kernel_cache.hits": self.hits,
                "kernel_cache.misses": self.misses,
                "kernel_cache.launches": self.launches,
                "kernel_cache.compile_ms": round(self.compile_ms, 3),
                "kernel_cache.disk_hit_compiles": self.disk_hit_compiles,
                "kernel_cache.flops": round(self.flops_total, 1),
                "kernel_cache.bytes_accessed": round(self.bytes_total, 1),
            }


GLOBAL_KERNEL_CACHE = KernelCache()

# the singleton's counter lock is process-global state worth watching:
# every par_map lane and serve session bumps launch tallies through it
from ..utils import lockwatch as _lockwatch  # noqa: E402

_lockwatch.register("physical.compile.KernelCache._lock",
                    GLOBAL_KERNEL_CACHE, "_lock")


# ---------------------------------------------------------------------------
# Input binding
# ---------------------------------------------------------------------------

def bind_inputs(input_attrs: Sequence[AttributeReference]) -> dict[int, int]:
    return {a.expr_id: i for i, a in enumerate(input_attrs)}


def _host_inputs(batch: ColumnarBatch,
                 input_attrs: Sequence[AttributeReference]) -> dict[int, Val]:
    out = {}
    for a, col in zip(input_attrs, batch.columns):
        out[a.expr_id] = Val(a.dtype, None,
                             True if col.validity is not None else None,
                             col.dictionary)
    return out


def broadcast_to_cap(x, cap: int):
    import jax.numpy as jnp

    if x is None:
        return None
    x = jnp.asarray(x)
    if x.ndim == 0:
        return jnp.broadcast_to(x, (cap,))
    return x


def pipeline_host_pass(input_attrs: Sequence[AttributeReference],
                       filters: Sequence[Expression],
                       outputs: Sequence[Expression],
                       batch: ColumnarBatch):
    """Per-batch host shadow pass for a (possibly fused) pipeline kernel:
    harvests aux lookup tables and output metadata (dtype/validity
    presence/dictionaries) without touching row data. Returns
    (hctx, host_outs, aux device arrays)."""
    import jax.numpy as jnp

    hctx = HostCtx(_host_inputs(batch, input_attrs))
    for f in filters:
        hctx.eval(f)
    host_outs = [hctx.eval(o) for o in outputs]
    aux = [jnp.asarray(a) for a in hctx.aux_arrays]
    return hctx, host_outs, aux


def pipeline_signature(batch: ColumnarBatch) -> tuple:
    """Input dtype/validity signature — part of every fused kernel key."""
    return tuple((str(c.data.dtype), c.validity is not None)
                 for c in batch.columns)


def pipeline_columns(fields, host_outs, out_datas, out_valids) -> list:
    """Rebuild output Columns from a pipeline kernel's results, attaching
    each dict-encoded column's host dictionary."""
    from ..types import dict_encoded

    cols = []
    for f, hv, d, v in zip(fields, host_outs, out_datas, out_valids):
        sdict = hv.sdict if dict_encoded(f.dataType) else None
        cols.append(Column(f.dataType, d, v, sdict))
    return cols


def trace_pipeline(input_attrs: Sequence[AttributeReference],
                   filters: Sequence[Expression],
                   outputs: Sequence[Expression],
                   datas, valids, row_mask, aux, cap: int):
    """Trace the filter+project pipeline body inside a jitted kernel.

    Shared consume-side prelude: ExprPipeline wraps it alone; fused-stage
    kernels (physical/fusion.py) run it and feed the projected columns
    straight into their terminal operator's consume code — the produce/
    consume splice of the reference's WholeStageCodegen, done by tracing.
    Returns (out_datas, out_valids, out_mask) broadcast to capacity."""
    inputs = {}
    for a, d, v in zip(input_attrs, datas, valids):
        inputs[a.expr_id] = Val(a.dtype, d, v, None)
    tctx = TraceCtx(inputs, aux, cap, row_mask)
    mask = row_mask
    for f in filters:
        fv = tctx.eval(f)
        pd = fv.data
        if fv.validity is not None:
            pd = pd & fv.validity
        mask = mask & broadcast_to_cap(pd, cap)
    out_datas = []
    out_valids = []
    for o in outputs:
        ov = tctx.eval(o)
        out_datas.append(broadcast_to_cap(ov.data, cap))
        out_valids.append(broadcast_to_cap(ov.validity, cap))
    return out_datas, out_valids, mask


# ---------------------------------------------------------------------------
# ExprPipeline: N filters + M output expressions in one kernel
# ---------------------------------------------------------------------------

class ExprPipeline:
    """Compiles `filters` (conjunctive predicates) and `outputs` (named
    expressions) over a fixed input attribute list into one jitted kernel.

    Per batch: a host pass harvests dictionaries/aux tables and output
    metadata, then the cached kernel runs on device."""

    def __init__(self, input_attrs: Sequence[AttributeReference],
                 filters: Sequence[Expression],
                 outputs: Sequence[Expression],
                 out_schema: StructType):
        self.input_attrs = list(input_attrs)
        self.filters = list(filters)
        self.outputs = list(outputs)
        self.out_schema = out_schema
        self.id_to_pos = bind_inputs(self.input_attrs)
        self._struct_key = (
            tuple(canonical_key(f, self.id_to_pos) for f in self.filters),
            tuple(canonical_key(o, self.id_to_pos) for o in self.outputs),
        )

    def run(self, batch: ColumnarBatch) -> ColumnarBatch:
        cap = batch.capacity
        hctx, host_outs, aux = pipeline_host_pass(
            self.input_attrs, self.filters, self.outputs, batch)
        key = ("pipeline", self._struct_key, cap, pipeline_signature(batch),
               hctx.signature())

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            key, lambda: self._build_kernel(cap))

        datas = [c.data for c in batch.columns]
        valids = [c.validity for c in batch.columns]
        with batch_cost_scope(batch):
            out_datas, out_valids, new_mask = kernel(datas, valids,
                                                     batch.row_mask, aux)
        cols = pipeline_columns(self.out_schema.fields, host_outs, out_datas,
                                out_valids)
        cols = self._propagate_runs(batch, cols)
        # a projection keeps its input's rows: a count the host already
        # has stays known (a one-row aggregate's, through to a cross join)
        return ColumnarBatch(self.out_schema, cols, new_mask,
                             num_rows=None if self.filters
                             else batch._num_rows)

    def _propagate_runs(self, batch: ColumnarBatch, cols: list) -> list:
        """Pass-through outputs inherit the input column's ingest RunInfo:
        the kernel emits a FRESH array, but a pure attribute reference
        carries the same values row-for-row and mask-only filters never
        reorder rows, so sortedness metadata harvested at ingest still
        describes the output plane — the sorted-run (ragg) aggregate
        stays reachable on filter/project→agg chains, not just direct
        scan→agg (compressed execution; plan_lint mirrors via
        _Batch.ingest pass-through sets)."""
        from dataclasses import replace as _replace

        from ..expr.expressions import Alias as _Alias

        any_runs = any(c.runs is not None for c in batch.columns)
        if not any_runs:
            return cols
        in_pos = {a.expr_id: i for i, a in enumerate(self.input_attrs)}
        out = []
        for o, col in zip(self.outputs, cols):
            target = o.child if isinstance(o, _Alias) else o
            if isinstance(target, AttributeReference):
                i = in_pos.get(target.expr_id)
                if i is not None and batch.columns[i].runs is not None:
                    col = _replace(col, runs=batch.columns[i].runs)
            out.append(col)
        return out

    def _build_kernel(self, cap: int):
        input_attrs = self.input_attrs
        filters = self.filters
        outputs = self.outputs

        def kernel(datas, valids, row_mask, aux):
            return trace_pipeline(input_attrs, filters, outputs,
                                  datas, valids, row_mask, aux, cap)

        return stage_jit(kernel)
