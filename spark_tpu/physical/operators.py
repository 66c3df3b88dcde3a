"""Physical operators.

Role of the reference's SparkPlan hierarchy (sqlx/SparkPlan.scala:343
doExecute / :359 doExecuteColumnar and the exec nodes under sqlx/). Every
operator here is columnar-only (the reference's ColumnarRule path,
sqlx/Columnar.scala:47, made the default): execute() returns a list of
partitions, each a list of device ColumnarBatches. Blocking operators
(aggregate/sort/join-build) concatenate their partition's batches and run one
fused kernel; XLA plays the role of WholeStageCodegen.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence

import numpy as np

from ..columnar.batch import Column, ColumnarBatch, bucket_capacity
from ..columnar.ops import concat_batches, gather_batch
from ..errors import CapacityOverflowError, ExecutionError, UnsupportedOperationError
from ..exec.context import ExecContext
from ..expr.eval import HostCtx, TraceCtx, Val
from ..expr.expressions import (
    Alias, AttributeReference, Expression, SortOrder,
)
from ..plan.tree import TreeNode
from ..types import (
    BooleanType, StringType, StructField, StructType, int64,
)
from .aggregates import PARTIAL_TO_MERGE, AggSpec
from .compile import (
    GLOBAL_KERNEL_CACHE, ExprPipeline, broadcast_to_cap, canonical_key,
    stage_jit,
)
from .partitioning import (
    AllTuples, BroadcastDistribution, BroadcastPartitioning,
    ClusteredDistribution, Distribution, HashPartitioning, OrderedDistribution,
    Partitioning, RangePartitioning, SinglePartition, UnknownPartitioning,
    UnspecifiedDistribution,
)

Partition = list  # list[ColumnarBatch]


def _jnp():
    import jax.numpy as jnp

    return jnp


def attrs_schema(attrs: Sequence[AttributeReference]) -> StructType:
    return StructType([StructField(a.name, a.dtype, a.nullable) for a in attrs])


class PhysicalPlan(TreeNode):
    """Base physical operator.

    Every subclass's `execute` is wrapped ONCE at class-creation time
    with per-operator instrumentation (role of SQLMetrics,
    sqlx/metric/SQLMetrics.scala: each SparkPlan carries rows/time
    metrics the UI's plan graph renders) plus the observability layer
    (obs/): a tracer span per operator execute, and a kernel-attribution
    scope so KernelCache launches/compile-ms bucket to the dispatching
    node. The wrapper is a no-op unless the ExecContext carries a
    `plan_metrics` dict or an enabled tracer, so bare runs pay two
    attribute lookups. Collection is sync-free: row counts come from
    host-side batch metadata; device masks are parked and resolved once
    per identity at query end (obs.metrics.finalize_plan_metrics)."""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        fn = cls.__dict__.get("execute")
        if fn is None or getattr(fn, "_sql_metrics_wrapped", False):
            return

        import functools
        import time as _time

        from ..obs import metrics as _OM

        @functools.wraps(fn)
        def traced(self, ctx, *a, _orig=fn, **k):
            rec = getattr(ctx, "plan_metrics", None)
            tracer = getattr(ctx, "tracer", None)
            if rec is None and tracer is None:
                return _orig(self, ctx, *a, **k)
            name = self.graph_name()
            ent = None
            token = None
            if rec is not None:
                key = getattr(self, "_metric_id", None)
                if key is None:
                    key = id(self)
                # locked insert: the heartbeat flush iterates this dict
                # under the attribution lock (export_op_records_partial)
                ent = _OM.get_or_create_op_record(rec, key)
                if getattr(ctx, "kernel_attribution", True):
                    token = _OM.push_op(ent, name, key)
            sp = tracer.span(name, cat="operator") if tracer is not None \
                else None
            l0 = ent["launch_total"] if ent is not None else 0
            t0 = _time.perf_counter()
            try:
                if sp is not None:
                    sp.__enter__()
                try:
                    out = _orig(self, ctx, *a, **k)
                finally:
                    if sp is not None:
                        if ent is not None:
                            launched = ent["launch_total"] - l0
                            if launched:
                                sp.set_args({"launches": launched})
                        sp.__exit__(None, None, None)
            finally:
                if token is not None:
                    _OM.pop_op(token)
            if ent is not None:
                ent["ms"] += (_time.perf_counter() - t0) * 1000  # inclusive
                ent["calls"] += 1
                try:
                    for p in out:
                        for b in p:
                            _OM.count_batch(rec, ent, b)
                except Exception:
                    pass                    # non-standard result shape
            return out

        traced._sql_metrics_wrapped = True
        cls.execute = traced

    @property
    def output(self) -> list[AttributeReference]:
        raise NotImplementedError

    def graph_name(self) -> str:
        """Operator-role name the plan graph/UI groups by. Whole-stage
        fused operators report the operator they implement (the reference
        renders the member operators inside a WholeStageCodegen cluster)."""
        return type(self).__name__

    def output_partitioning(self) -> Partitioning:
        ch = self.children
        if ch:
            return ch[0].output_partitioning()
        return UnknownPartitioning(1)

    def required_child_distribution(self) -> list[Distribution]:
        return [UnspecifiedDistribution() for _ in self.children]

    def execute(self, ctx: ExecContext) -> list[Partition]:
        raise NotImplementedError

    def schema(self) -> StructType:
        return attrs_schema(self.output)


# ---------------------------------------------------------------------------
# Scans
# ---------------------------------------------------------------------------

class ScanExec(PhysicalPlan):
    """Columnar scan over a DataSource (role of FileSourceScanExec,
    sqlx/DataSourceScanExec.scala:719, vectorized path)."""

    child_fields = ()

    def __init__(self, source, attrs: list[AttributeReference], name: str = ""):
        self.source = source
        self.attrs = attrs
        self.name = name
        # (partition column name, allowed values) installed at runtime by a
        # joining operator before this scan executes — dynamic partition
        # pruning (reference: sqlx/dynamicpruning/PartitionPruning.scala)
        self.runtime_split_filter = None

    @property
    def output(self):
        return self.attrs

    def output_partitioning(self):
        return UnknownPartitioning(self.source.num_partitions())

    def _split_pruned(self, i: int) -> bool:
        """True if split i cannot contain rows passing the runtime filter.
        Partition count stays stable — pruned splits read as empty."""
        if self.runtime_split_filter is None:
            return False
        from ..io.sources import UNKNOWN_PARTITION_VALUE

        col, allowed = self.runtime_split_filter
        pv = self.source.split_partition_value(i, col)
        if pv is UNKNOWN_PARTITION_VALUE:
            return False  # conservative: value not derivable from layout
        return pv is None or pv not in allowed  # null never equals a key

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from ..columnar.arrow import table_to_batches

        cols = [a.name for a in self.attrs]
        cap = ctx.conf.batch_capacity
        cache = getattr(self.source, "_device_cache", None)
        if cache is None and getattr(self.source, "cache_device_batches", False):
            cache = self.source._device_cache = {}
        schema = attrs_schema(self.attrs)
        out: list[Partition] = []
        for i in range(self.source.num_partitions()):
            if self._split_pruned(i):
                ctx.metrics.add("scan.dpp_pruned_splits")
                out.append([ColumnarBatch.empty(schema)])
                continue
            key = (i, tuple(cols), cap)
            bm = getattr(ctx, "block_manager", None)
            # block id covers the FULL cache key: the same partition
            # projected differently is a distinct pinned entry
            bid = f"scan-{id(self.source)}-{i}-{hash(key) & 0xffffffff:x}"
            if cache is not None and key in cache:
                if bm is not None:
                    bm.touch_device(bid)
                out.append(cache[key])
                continue
            table = self.source.read_partition(i, cols)
            batches = list(table_to_batches(table, cap, schema))
            ctx.metrics.add(f"scan.{self.name}.rows", table.num_rows)
            if cache is not None:
                cache[key] = batches
                if bm is not None:
                    # device-tier governance: LRU-unpin over budget
                    nbytes = sum(b.device_nbytes() for b in batches)
                    bm.pin_device(bid, cache, key, nbytes)
            out.append(batches)
        return out

    def simple_string(self):
        return f"Scan[{self.name}]({', '.join(a.name for a in self.attrs)})"


_LOCAL_TABLE_CACHE: "weakref.WeakKeyDictionary" = None
# two sessions of one process (a server's tenants) may scan one table
# for the first time at the same moment: the lock makes the table's
# entry once, and the entry's own lock copies a set of columns to the
# device once
_LOCAL_TABLE_LOCK = threading.Lock()


class LocalTableScanExec(PhysicalPlan):
    child_fields = ()

    def __init__(self, attrs: list[AttributeReference], table):
        self.attrs = attrs
        self.table = table  # pyarrow.Table

    @property
    def output(self):
        return self.attrs

    def output_partitioning(self):
        return SinglePartition()

    def execute(self, ctx: ExecContext) -> list[Partition]:
        import weakref

        global _LOCAL_TABLE_CACHE
        if _LOCAL_TABLE_CACHE is None:
            _LOCAL_TABLE_CACHE = {}

        # pa.Table is unhashable: key by id with a weakref finalizer so the
        # device batches die with the table. id() values recycle after GC
        # (and weakref callbacks can be skipped when the referent dies in a
        # collected cycle), so a hit must prove the entry still belongs to
        # THIS table — a stale entry here once served another test's batches.
        tid = id(self.table)
        with _LOCAL_TABLE_LOCK:
            entry = _LOCAL_TABLE_CACHE.get(tid)
            if entry is not None:
                ref = entry.get("ref")
                if ref is None or ref() is not self.table:
                    entry = None
            if entry is None:
                try:
                    ref = weakref.ref(self.table,
                                      lambda _r, t=tid:
                                      _LOCAL_TABLE_CACHE.pop(t, None))
                except TypeError:
                    ref = None
                entry = {"ref": ref, "batches": {},
                         "lock": threading.Lock()}
                _LOCAL_TABLE_CACHE[tid] = entry

        names = tuple(a.name for a in self.attrs)
        key = (names, ctx.conf.batch_capacity)
        with entry["lock"]:
            hit = entry["batches"].get(key)
            if hit is None:
                hit = entry["batches"][key] = self._to_device(ctx, names)
        return [hit]

    def _to_device(self, ctx: ExecContext, names: tuple) -> list:
        """The table's planes go to the device here, once: host
        conversion, padding and the enqueue of each copy (the copies
        themselves are asynchronous; the first program waits for them)."""
        from ..columnar.arrow import table_to_batches
        from ..obs.tracing import span_here

        tbl = self.table.select(list(names)) if self.table.num_columns \
            else self.table
        with span_here("ingest.h2d", cat="operator") as sp:
            batches = list(table_to_batches(tbl, ctx.conf.batch_capacity,
                                            attrs_schema(self.attrs)))
            sp.set_args({
                "bytes": sum(b.device_nbytes() for b in batches),
                "planes": sum(1 + sum(1 + (c.validity is not None)
                                      for c in b.columns)
                              for b in batches)})
        return batches


class RangeExec(PhysicalPlan):
    child_fields = ()

    def __init__(self, start: int, end: int, step: int, num_partitions: int,
                 attr: AttributeReference):
        self.start = start
        self.end = end
        self.step = step
        self.num_partitions = max(1, num_partitions)
        self.attr = attr

    @property
    def output(self):
        return [self.attr]

    def output_partitioning(self):
        return UnknownPartitioning(self.num_partitions)

    def execute(self, ctx: ExecContext) -> list[Partition]:
        jnp = _jnp()
        total = max(0, -(-(self.end - self.start) // self.step)) if self.step > 0 \
            else max(0, -(-(self.start - self.end) // -self.step))
        per = -(-total // self.num_partitions)
        parts: list[Partition] = []
        schema = attrs_schema([self.attr])
        tile = ctx.conf.batch_capacity
        for p in range(self.num_partitions):
            lo = min(p * per, total)
            hi = min(lo + per, total)
            batches = []
            for s in range(lo, hi, tile):
                e = min(s + tile, hi)
                n = e - s
                cap = bucket_capacity(n)
                idx = jnp.arange(cap, dtype=jnp.int64)
                data = self.start + (s + idx) * self.step
                mask = idx < n
                batches.append(ColumnarBatch(
                    schema, [Column(self.attr.dtype, data, None, None)],
                    mask, num_rows=n))
            if not batches:
                batches = [ColumnarBatch.empty(schema)]
            parts.append(batches)
        return parts


# ---------------------------------------------------------------------------
# Compute (fused filter+project)
# ---------------------------------------------------------------------------

class ComputeExec(PhysicalPlan):
    """Fused conjunctive filters + projections — one XLA kernel per batch
    (the WholeStageCodegen pipeline analog for narrow operators)."""

    child_fields = ("child",)

    def __init__(self, filters: Sequence[Expression],
                 outputs: Sequence[Expression], child: PhysicalPlan):
        self.filters = list(filters)
        self.outputs = list(outputs)  # Alias | AttributeReference
        self.child = child
        self._pipeline: ExprPipeline | None = None

    @property
    def output(self):
        out = []
        for e in self.outputs:
            if isinstance(e, Alias):
                out.append(e.to_attribute())
            else:
                out.append(e)
        return out

    def output_partitioning(self):
        p = self.child.output_partitioning()
        if isinstance(p, (HashPartitioning, RangePartitioning)):
            out_ids = {a.expr_id for a in self.output}
            exprs = p.exprs if isinstance(p, HashPartitioning) else \
                [o.child for o in p.orders]
            for e in exprs:
                if not (e.references() <= out_ids):
                    return UnknownPartitioning(p.num_partitions)
        return p

    def _get_pipeline(self) -> ExprPipeline:
        if self._pipeline is None:
            self._pipeline = ExprPipeline(
                self.child.output, self.filters, self.outputs,
                attrs_schema(self.output))
        return self._pipeline

    def execute(self, ctx: ExecContext) -> list[Partition]:
        parts = self.child.execute(ctx)
        if not self.filters:
            # pure column reorder/prune: share the child's arrays instead of
            # launching an identity kernel — a computed copy would also be
            # re-staged per downstream dispatch on transfer-bound transports
            pos = {a.expr_id: i for i, a in enumerate(self.child.output)}
            if all(isinstance(e, AttributeReference) and e.expr_id in pos
                   for e in self.outputs):
                schema = attrs_schema(self.output)
                idx = [pos[e.expr_id] for e in self.outputs]

                def reorder(b):
                    nb = ColumnarBatch(schema, [b.columns[i] for i in idx],
                                       b.row_mask, num_rows=b._num_rows)
                    # column objects are shared, so id-keyed per-batch
                    # caches (bloom bitsets) stay valid — keep them; the
                    # dense-range memo is identity-keyed and global
                    nb._stats = b._stats
                    return nb

                return [[reorder(b) for b in part] for part in parts]
        pipe = self._get_pipeline()
        return ctx.par_map(lambda part: [pipe.run(b) for b in part], parts)

    def simple_string(self):
        f = " AND ".join(x.simple_string() for x in self.filters)
        o = ", ".join(x.simple_string() for x in self.outputs)
        s = f"Compute[{o}]"
        if f:
            s += f" WHERE {f}"
        return s


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

def _batch_stats_cache(batch: ColumnarBatch) -> dict:
    if batch._stats is None:
        batch._stats = {}
    return batch._stats


# Process-global memo of host-synced scalars derived from device arrays,
# keyed by the arrays' identities. Unlike the per-batch `_stats` dict this
# survives re-wrapping the same device columns into fresh ColumnarBatches
# (device-cached scans re-executed per query, reorder projections, repeated
# broadcast probes), so the dense-range decision syncs its two scalars ONCE
# per distinct (column, mask) pair instead of once per batch per run —
# per-batch dispatches then pipeline without a host round-trip in between.
# Implementation lives in utils/device_memo (also used by exchange/sort
# sampling and columnar ingest seeding).
from ..utils.device_memo import (
    DENSE_RANGE_KIND, device_read,
    memo_device_scalars as _memo_device_scalars,
)


def dense_range_stats(kc: Column, row_mask, cap: int):
    """(kmin, kmax, any_live) of an integral key column under `row_mask`,
    memoized across batches sharing the same device arrays (the
    physical/operators dense fast-path decision; one kernel + one two-scalar
    host sync per distinct column/mask identity)."""

    jnp = _jnp()

    def compute():
        rkey = ("krange3", cap, str(kc.data.dtype), kc.validity is not None)

        def build_range():
            def kr(k, v, m):
                k = k.astype(jnp.int64)  # cast inside (transport cost)
                if v is not None:
                    m = m & v
                big = jnp.iinfo(jnp.int64).max
                small = jnp.iinfo(jnp.int64).min
                return (jnp.min(jnp.where(m, k, big)),
                        jnp.max(jnp.where(m, k, small)),
                        jnp.any(m))
            return stage_jit(kr)

        kmin, kmax, anyl = device_read(
            "dense.range", *GLOBAL_KERNEL_CACHE.get_or_build(
                rkey, build_range)(kc.data, kc.validity, row_mask))
        return (int(kmin), int(kmax), bool(anyl))

    return _memo_device_scalars(DENSE_RANGE_KIND,
                                (kc.data, kc.validity, row_mask), compute)


def _group_kernel(num_keys: int, ops: tuple[str, ...], cap: int,
                  key_valid_sig: tuple[bool, ...],
                  val_valid_sig: tuple[bool, ...]):
    """Build the jitted grouped-aggregation kernel (SURVEY.md §7 step 2)."""

    from ..ops import grouping as G

    def kernel(key_eqs, key_outs, key_valids, val_datas, val_valids, row_mask):
        return G.group_aggregate(key_eqs, key_valids, key_outs, row_mask,
                                 ops, val_datas, val_valids)

    return stage_jit(kernel)


def _dense_group_kernel(ops: tuple[str, ...], cap: int, out_cap: int,
                        has_key_valid: bool):
    """Dense-range fast path: a single integral key whose value range fits a
    capacity bucket aggregates by DIRECT scatter-add (`segment_sum` keyed by
    `key - min`) — no sort at all. This is the analog of the reference's
    vectorized hashmap fast path (AggregateBenchmark 'vectorized hashmap'
    rows) and the main bench configuration's hot kernel. NULL keys get the
    last slot."""
    import jax
    from jax import lax

    from ..ops import grouping as G

    def kernel(key, key_valid, kmin, val_datas, val_valids, row_mask):
        jnp = _jnp()
        # cast INSIDE the program: an eager astype outside it would be a
        # dispatch of its own and a second resident copy of the key
        key = key.astype(jnp.int64)
        seg = (key - kmin).astype(jnp.int32)
        if has_key_valid:
            seg = jnp.where(key_valid, seg, out_cap - 1)
        seg = jnp.where(row_mask, seg, out_cap - 1)
        w_all = row_mask

        present = jax.ops.segment_sum(
            jnp.where(row_mask, 1, 0), seg, num_segments=out_cap)
        # rows parked in the null/inactive slot: count actual nulls there
        if has_key_valid:
            null_rows = jnp.sum((row_mask & ~key_valid).astype(jnp.int64))
        else:
            null_rows = jnp.int64(0)

        bufs = G.apply_dense_ops(seg, out_cap, cap, ops, val_datas,
                                 val_valids, w_all)

        out_keys = kmin + lax.iota(jnp.int64, out_cap)
        out_mask = present > 0
        # the parking slot is a real group only for actual null keys
        out_mask = out_mask.at[out_cap - 1].set(null_rows > 0)
        key_validity = jnp.ones(out_cap, dtype=bool).at[out_cap - 1].set(False)
        return out_keys, key_validity, bufs, out_mask

    return stage_jit(kernel)


def _run_group_kernel(ops: tuple[str, ...], cap: int):
    """RLE-aware grouped aggregation: the key column is already sorted
    (ingest RunInfo), so segments come from run boundaries
    (ops/grouping.group_rows_presorted) and `lax.sort` is skipped — the
    reduce visits each run once. Compiled only when sorted-run metadata
    is actually present (encoded-operand cache-key discipline)."""

    from ..ops import grouping as G

    def kernel(key, val_datas, val_valids, row_mask):
        layout = G.group_rows_presorted(key, row_mask)
        out_key = G.scatter_group_keys(layout, key, None)
        bufs = G.apply_group_ops(layout, ops, val_datas, val_valids)
        out_mask = G.group_output_mask(layout)
        return out_key, bufs, out_mask, layout.num_groups

    return stage_jit(kernel)


def _ungrouped_kernel(ops: tuple[str, ...], cap: int,
                      val_valid_sig: tuple[bool, ...], out_cap: int = 8):
    from ..ops import grouping as G

    def kernel(val_datas, val_valids, row_mask):
        jnp = _jnp()
        outs = G.apply_global_ops(ops, val_datas, val_valids, row_mask)
        # materialize as 1-row arrays of capacity out_cap
        datas = []
        valids = []
        for d, v in outs:
            arr = jnp.zeros((out_cap,), dtype=d.dtype).at[0].set(d)
            datas.append(arr)
            if v is None:
                valids.append(None)
            else:
                varr = jnp.zeros((out_cap,), dtype=bool).at[0].set(v)
                valids.append(varr)
        mask = jnp.zeros((out_cap,), dtype=bool).at[0].set(True)
        return datas, valids, mask

    return stage_jit(kernel)


class HashAggregateExec(PhysicalPlan):
    """Grouped aggregation via the sort/segment kernel (role of
    HashAggregateExec, sqlx/aggregate/HashAggregateExec.scala:50; the
    lax.sort design replaces UnsafeFixedWidthAggregationMap).

    mode 'partial': values come from spec.input_expr attributes.
    mode 'final':   values are the buffer attrs; ops are merge ops.
    Output (both modes): grouping attrs ++ flattened buffer attrs."""

    child_fields = ("child",)

    def __init__(self, grouping: Sequence[AttributeReference],
                 specs: Sequence[AggSpec], mode: str, child: PhysicalPlan):
        assert mode in ("partial", "final")
        self.grouping = list(grouping)
        self.specs = list(specs)
        self.mode = mode
        self.child = child

    @property
    def output(self):
        out = list(self.grouping)
        for s in self.specs:
            out.extend(s.buffer_attrs)
        return out

    def required_child_distribution(self):
        if self.mode == "partial":
            return [UnspecifiedDistribution()]
        if not self.grouping:
            return [AllTuples()]
        return [ClusteredDistribution(list(self.grouping))]

    def output_partitioning(self):
        return self.child.output_partitioning()

    def _plan_values(self):
        """(op, input attr, param) per buffer column."""
        out = []
        for s in self.specs:
            for i, op in enumerate(s.ops):
                if self.mode == "partial":
                    attr = s.input_expr if op != "countstar" else None
                    out.append((op, attr, s.param))
                else:
                    out.append((PARTIAL_TO_MERGE[op], s.buffer_attrs[i],
                                s.param))
        return out

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from .adaptive import coalesce_after_exchange

        parts = self.child.execute(ctx)
        if self.mode == "final":
            parts = coalesce_after_exchange(self.child, parts, ctx,
                                            self.child.output)
        return ctx.par_map(
            lambda part: [self._aggregate_partition(part, ctx)], parts)

    def _aggregate_partition(self, part: Partition, ctx) -> ColumnarBatch:
        """Aggregate one partition. Partitions larger than the blockwise
        threshold fold incrementally — partial-agg each chunk, then agg the
        accumulated partials — bounding HBM like the reference's
        sort-based spill fallback (TungstenAggregationIterator), but with
        associative merges instead of disk (SURVEY.md §7 'Hard parts' (3))."""
        max_rows = int(ctx.conf.get("spark.tpu.agg.blockRows", 1 << 22))
        if len(part) > 1 and sum(b.capacity for b in part) > max_rows \
                and self.grouping and all(s.mergeable for s in self.specs):
            acc: list[ColumnarBatch] = []
            chunk: list[ColumnarBatch] = []
            cap_sum = 0
            for b in part:
                chunk.append(b)
                cap_sum += b.capacity
                if cap_sum >= max_rows:
                    acc.append(self._aggregate_chunk(chunk, ctx))
                    chunk, cap_sum = [], 0
            if chunk:
                acc.append(self._aggregate_chunk(chunk, ctx))
            # merge accumulated partials (buffer schema) with final-mode ops
            merger = HashAggregateExec(self.grouping, self.specs, "final",
                                       _SchemaOnly(self.output))
            return merger._aggregate_chunk(acc, ctx)
        return self._aggregate_chunk(part, ctx)

    def _aggregate_chunk(self, part: Partition, ctx) -> ColumnarBatch:
        jnp = _jnp()
        batch = concat_batches(part, attrs_schema(self.child.output))
        cap = batch.capacity
        pos = {a.expr_id: i for i, a in enumerate(self.child.output)}

        vals = self._plan_values()
        percentiles: dict[int, tuple] = {}  # buffer idx → (column, q)
        collects: dict[int, tuple] = {}     # buffer idx → (column, dedupe)
        main_vals = []
        for bi, (op, attr, param) in enumerate(vals):
            if op == "percentile":
                percentiles[bi] = (batch.columns[pos[attr.expr_id]], param)
                main_vals.append(("first", attr))  # placeholder, overwritten
            elif op == "collect":
                collects[bi] = (batch.columns[pos[attr.expr_id]],
                                param >= 0.5)
                main_vals.append(("first", attr))  # placeholder, overwritten
            else:
                main_vals.append((op, attr))
        ops = tuple(op for op, _ in main_vals)
        val_datas = []
        val_valids = []
        string_minmax: dict[int, Column] = {}  # buffer idx → source column
        for bi, (op, attr) in enumerate(main_vals):
            if attr is None:
                val_datas.append(batch.row_mask)  # dummy
                val_valids.append(None)
                continue
            c = batch.columns[pos[attr.expr_id]]
            if op in ("min", "max") and c.is_string:
                # strings reduce in RANK space (lexicographic); the winning
                # rank maps back to a dictionary code afterwards
                val_datas.append(c.sort_keys())
                string_minmax[bi] = c
            else:
                val_datas.append(c.data)
            val_valids.append(c.validity)

        out_schema = attrs_schema(self.output)

        if not self.grouping:
            key = ("uagg", ops, cap,
                   tuple(v is not None for v in val_valids),
                   tuple(str(d.dtype) for d in val_datas))
            kernel = GLOBAL_KERNEL_CACHE.get_or_build(
                key, lambda: _ungrouped_kernel(
                    ops, cap, tuple(v is not None for v in val_valids)))
            datas, valids, mask = kernel(val_datas, val_valids, batch.row_mask)
            datas, valids = list(datas), list(valids)
            for bi, (pc, q) in percentiles.items():
                datas[bi], valids[bi] = self._ungrouped_percentile(
                    batch, pc, q, datas[bi].shape[0])
            collect_cols = {
                bi: self._ungrouped_collect(batch, vc, dd,
                                            datas[bi].shape[0],
                                            out_schema.fields[bi].dataType)
                for bi, (vc, dd) in collects.items()}
            cols = [self._finish_buffer(bi, d, v, f, string_minmax,
                                        collect_cols)
                    for bi, (f, d, v) in enumerate(
                        zip(out_schema.fields, datas, valids))]
            return ColumnarBatch(out_schema, cols, mask, num_rows=1)

        key_cols = [batch.columns[pos[g.expr_id]] for g in self.grouping]
        key_eqs = [c.eq_keys() for c in key_cols]
        # None where the key is its own output (all but strings, booleans)
        key_outs = [None if c.data is e else c.data
                    for c, e in zip(key_cols, key_eqs)]
        key_valids = [c.validity for c in key_cols]

        if not percentiles and not collects:
            dense = self._try_dense(batch, key_cols, ops, val_datas,
                                    val_valids, out_schema, ctx,
                                    string_minmax)
            if dense is not None:
                return dense
            rle = self._try_run_sorted(batch, key_cols, ops, val_datas,
                                       val_valids, out_schema, ctx,
                                       string_minmax)
            if rle is not None:
                return rle

        from ..ops.grouping import segment_path

        kkey = ("gagg", len(key_cols), ops, cap, segment_path(cap),
                tuple(v is not None for v in key_valids),
                tuple(v is not None for v in val_valids),
                tuple(str(d.dtype) for d in key_eqs),
                tuple(str(d.dtype) for d in val_datas))
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            kkey, lambda: _group_kernel(
                len(key_cols), ops, cap,
                tuple(v is not None for v in key_valids),
                tuple(v is not None for v in val_valids)))
        out_keys, bufs, out_mask, _ng = kernel(
            key_eqs, key_outs, key_valids, val_datas, val_valids, batch.row_mask)

        bufs = list(bufs)
        for bi, (pc, q) in percentiles.items():
            from ..ops.grouping import group_percentile

            pkey = ("gperc", batch.capacity, len(key_cols), float(q),
                    tuple(str(k.dtype) for k in key_eqs),
                    tuple(v is not None for v in key_valids),
                    str(pc.data.dtype), pc.validity is not None)

            def build_p(q=q):
                return stage_jit(lambda ke, kv, vd, vv, m:
                                 group_percentile(ke, kv, vd, vv, m, q))

            pk = GLOBAL_KERNEL_CACHE.get_or_build(pkey, build_p)
            pvals, phas = pk(key_eqs, key_valids, pc.data, pc.validity,
                             batch.row_mask)
            bufs[bi] = (pvals, phas)
        collect_cols = {
            bi: self._group_collect(
                batch, key_cols, out_keys, out_mask, vc, dd,
                out_schema.fields[len(key_cols) + bi].dataType)
            for bi, (vc, dd) in collects.items()}
        cols = []
        for (kd, kv), kc, f in zip(out_keys, key_cols,
                                   out_schema.fields[: len(key_cols)]):
            cols.append(Column(f.dataType, kd, kv, kc.dictionary))
        for bi, ((bd, bv), f) in enumerate(
                zip(bufs, out_schema.fields[len(key_cols):])):
            cols.append(self._finish_buffer(bi, bd, bv, f, string_minmax,
                                            collect_cols))
        return ColumnarBatch(out_schema, cols, out_mask, num_rows=None)

    def _ungrouped_percentile(self, batch, pc: Column, q: float,
                              out_cap: int):
        from ..ops.grouping import masked_percentile

        jnp = _jnp()
        key = ("uperc", batch.capacity, float(q), str(pc.data.dtype),
               pc.validity is not None, out_cap)

        def build(q=q):
            def kernel(vd, vv, m):
                v, has = masked_percentile(vd, m, vv, q)
                arr = jnp.zeros((out_cap,), dtype=v.dtype).at[0].set(v)
                hv = jnp.zeros((out_cap,), dtype=bool).at[0].set(has)
                return arr, hv

            return stage_jit(kernel)

        k = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
        return k(pc.data, pc.validity, batch.row_mask)

    def _ungrouped_collect(self, batch, vc: Column, dedupe: bool,
                           out_cap: int, out_dtype):
        """collect_list/set with no grouping: one list over all valid rows
        (list order = input row order; reference leaves it unspecified)."""
        jnp = _jnp()
        sel = batch.selection_indices()
        vals = [v for v in vc.to_numpy(sel) if v is not None]
        if dedupe:
            vals = list(dict.fromkeys(vals))
        from ..columnar.batch import StringDict

        return Column(out_dtype, jnp.zeros(out_cap, jnp.int32), None,
                      StringDict([vals]))

    def _group_collect(self, batch, key_cols, out_keys, out_mask,
                       vc: Column, dedupe: bool, out_dtype):
        """Grouped collect: the group structure comes from the device
        kernel; lists are built host-side and matched to the kernel's
        group rows by key tuple (same raw key domain on both sides)."""
        jnp = _jnp()

        def key_tuples(cols, selection):
            arrs = []
            for kd, kv in cols:
                d = np.asarray(kd)[selection]
                v = None if kv is None else np.asarray(kv)[selection]
                arrs.append((d, v))
            return [tuple(None if (v is not None and not v[i])
                          else d[i].item() for d, v in arrs)
                    for i in range(len(selection))]

        sel = batch.selection_indices()
        vals = vc.to_numpy(sel)
        groups: dict[tuple, list] = {}
        for kt, v in zip(key_tuples(
                [(c.data, c.validity) for c in key_cols], sel), vals):
            if v is not None:
                groups.setdefault(kt, []).append(v)

        gm = np.asarray(out_mask)
        gsel = np.nonzero(gm)[0]
        codes = np.zeros(gm.shape[0], np.int32)
        values: list[list] = []
        out_tuples = key_tuples(out_keys, gsel)
        for g, kt in zip(gsel, out_tuples):
            lst = groups.get(kt, [])
            if dedupe:
                lst = list(dict.fromkeys(lst))
            codes[g] = len(values)
            values.append(lst)
        from ..columnar.batch import StringDict

        return Column(out_dtype, jnp.asarray(codes), None,
                      StringDict(values or [[]]))

    def _finish_buffer(self, bi, bd, bv, f, string_minmax,
                       collect_cols=None):
        if collect_cols and bi in collect_cols:
            return collect_cols[bi]
        jnp = _jnp()
        if bi in string_minmax:
            from ..columnar.batch import EMPTY_DICT

            c = string_minmax[bi]
            sd = c.dictionary or EMPTY_DICT
            inv = sd.device_rank_to_code()
            codes = jnp.take(inv, jnp.clip(bd.astype(jnp.int32), 0,
                                           inv.shape[0] - 1))
            return Column(f.dataType, codes, bv, sd)
        want = f.dataType.device_dtype
        if str(bd.dtype) != str(want):
            bd = bd.astype(want)
        return Column(f.dataType, bd, bv, None)

    def _try_dense(self, batch: ColumnarBatch, key_cols, ops, val_datas,
                   val_valids, out_schema, ctx, string_minmax):
        """Dense-range fast path dispatch: single integral key whose value
        span fits a capacity bucket (host syncs two scalars to decide),
        OR a single dictionary-encoded string key — its int32 codes ARE a
        dense domain [0, len(dict)) with the span known host-side
        (len(dictionary)), so the decision never launches the range
        probe and the dictionary decodes the output keys (compressed
        execution: the aggregate groups directly on codes)."""

        from ..types import DateType, IntegralType

        jnp = _jnp()
        if len(key_cols) != 1:
            return None
        kc = key_cols[0]
        cap = batch.capacity
        key_dict = None
        if kc.is_string:
            from ..columnar.batch import EMPTY_DICT
            from ..columnar.encoding import encoding_enabled

            if not encoding_enabled(ctx.conf):
                return None
            key_dict = kc.dictionary or EMPTY_DICT
            kmin, span = 0, len(key_dict)
            if span + 1 > min(4 * cap, 1 << 23):
                return None  # mega-dictionary — sort path handles it
            ctx.metrics.add("agg.dict_code_fast_path")
        elif isinstance(kc.dtype, (IntegralType, DateType)):
            kmin, kmax, any_live = dense_range_stats(kc, batch.row_mask,
                                                     cap)
            if not any_live:
                return None
            span = kmax - kmin + 1
            if span + 1 > min(4 * cap, 1 << 23):
                return None  # sparse keys — sort path handles it
        else:
            return None

        out_cap = bucket_capacity(span + 1)
        dkey = ("dagg", ops, cap, out_cap, kc.validity is not None,
                str(kc.data.dtype),
                tuple(str(d.dtype) for d in val_datas),
                tuple(v is not None for v in val_valids))
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            dkey, lambda: _dense_group_kernel(
                ops, cap, out_cap, kc.validity is not None))
        out_keys, key_validity, bufs, out_mask = kernel(
            kc.data, kc.validity, jnp.int64(kmin), val_datas, val_valids,
            batch.row_mask)
        ctx.metrics.add("agg.dense_fast_path")

        cols = []
        kf = out_schema.fields[0]
        kdata = out_keys.astype(kf.dataType.device_dtype)
        kv = key_validity if kc.validity is not None else None
        cols.append(Column(kf.dataType, kdata, kv, key_dict))
        for bi, ((bd, bv), f) in enumerate(zip(bufs, out_schema.fields[1:])):
            cols.append(self._finish_buffer(bi, bd, bv, f, string_minmax))
        return ColumnarBatch(out_schema, cols, out_mask, num_rows=None)

    def _try_run_sorted(self, batch: ColumnarBatch, key_cols, ops,
                        val_datas, val_valids, out_schema, ctx,
                        string_minmax):
        """RLE fast path: a single integral key whose ingest RunInfo says
        the live rows are already sorted (no validity plane) reduces per
        RUN BOUNDARY — no grouping sort, no dense table. Reached only
        when the dense-range path declined (sparse span), so clustered
        sparse keys (sorted file reads, post-sort streams) keep a
        sort-free aggregate. Metadata-only decision: zero launches."""
        from ..columnar.encoding import encoding_enabled

        if len(key_cols) != 1:
            return None
        kc = key_cols[0]
        runs = getattr(kc, "runs", None)
        if runs is None or not runs.is_sorted or kc.validity is not None:
            return None
        if not encoding_enabled(ctx.conf):
            return None
        cap = batch.capacity
        rkey = ("ragg", ops, cap, str(kc.data.dtype),
                tuple(str(d.dtype) for d in val_datas),
                tuple(v is not None for v in val_valids))
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            rkey, lambda: _run_group_kernel(ops, cap))
        (out_key, out_kv), bufs, out_mask, _ng = kernel(
            kc.data, val_datas, val_valids, batch.row_mask)
        ctx.metrics.add("agg.run_sorted_fast_path")
        cols = [Column(out_schema.fields[0].dataType, out_key, None,
                       kc.dictionary)]
        for bi, ((bd, bv), f) in enumerate(zip(bufs, out_schema.fields[1:])):
            cols.append(self._finish_buffer(bi, bd, bv, f, string_minmax))
        return ColumnarBatch(out_schema, cols, out_mask, num_rows=None)

    def simple_string(self):
        g = ", ".join(a.name for a in self.grouping)
        fns = ", ".join(type(s.func).__name__ for s in self.specs)
        return f"HashAggregate[{self.mode}](keys=[{g}], fns=[{fns}])"


# ---------------------------------------------------------------------------
# Sort / Limit
# ---------------------------------------------------------------------------

class _SchemaOnly(PhysicalPlan):
    """Placeholder child carrying only an output schema (blockwise-agg
    merge step)."""

    child_fields = ()

    def __init__(self, attrs):
        self.attrs = list(attrs)

    @property
    def output(self):
        return self.attrs


class SortExec(PhysicalPlan):
    """In-partition sort (role of sqlx/SortExec.scala:39). Orders must be
    over child output attributes (planner pre-projects complex keys)."""

    child_fields = ("child",)

    def __init__(self, orders: Sequence[SortOrder], child: PhysicalPlan):
        self.orders = list(orders)
        self.child = child
        for o in self.orders:
            assert isinstance(o.child, AttributeReference), \
                "planner must bind sort keys to attributes"

    @property
    def output(self):
        return self.child.output

    def required_child_distribution(self):
        return [UnspecifiedDistribution()]

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from .adaptive import coalesce_after_exchange

        parts = self.child.execute(ctx)
        parts = coalesce_after_exchange(self.child, parts, ctx,
                                        self.child.output)
        return [self._sort_partition(p, ctx) if p else [] for p in parts]

    def _sort_partition(self, part: Partition, ctx) -> Partition:
        """Budget dispatch: a partition that fits the device budget sorts
        as one tile; a larger one takes the external range-bucketed
        multi-pass (physical/external_sort.py, the UnsafeExternalSorter
        role)."""
        schema = attrs_schema(self.child.output)
        budget = ctx.memory.tile_rows(schema, amplification=3)
        if sum(b.capacity for b in part) <= budget:
            return [self._sort_single(part)]
        from .external_sort import external_sort

        return external_sort(part, self.orders, schema, self.child.output,
                             ctx, budget, self._sort_single)

    def _sort_single(self, part: Partition) -> ColumnarBatch:
        from ..ops.sorting import SortKeySpec, sort_permutation

        jnp = _jnp()
        batch = concat_batches(part, attrs_schema(self.child.output))
        pos = {a.expr_id: i for i, a in enumerate(self.child.output)}
        keys = []
        valids = []
        specs = []
        for o in self.orders:
            c = batch.columns[pos[o.child.expr_id]]
            keys.append(c.sort_keys())
            valids.append(c.validity)
            specs.append(SortKeySpec(o.ascending, o.nulls_first))

        cap = batch.capacity
        skey = ("sort", cap, tuple((s.ascending, s.nulls_first) for s in specs),
                tuple(str(k.dtype) for k in keys),
                tuple(v is not None for v in valids),
                tuple((str(c.data.dtype), c.validity is not None)
                      for c in batch.columns))

        def build():
            def kernel(keys, valids, datas, dvalids, row_mask):
                perm = sort_permutation(keys, valids, specs, row_mask)
                out_d = [jnp.take(d, perm) for d in datas]
                out_v = [None if v is None else jnp.take(v, perm)
                         for v in dvalids]
                return out_d, out_v, jnp.take(row_mask, perm)

            return stage_jit(kernel)

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(skey, build)
        datas = [c.data for c in batch.columns]
        dvalids = [c.validity for c in batch.columns]
        out_d, out_v, out_mask = kernel(keys, valids, datas, dvalids,
                                        batch.row_mask)
        cols = [Column(c.dtype, d, v, c.dictionary)
                for c, d, v in zip(batch.columns, out_d, out_v)]
        return ColumnarBatch(batch.schema, cols, out_mask, batch._num_rows)

    def simple_string(self):
        o = ", ".join(
            f"{x.child.simple_string()} {'ASC' if x.ascending else 'DESC'}"
            for x in self.orders)
        return f"Sort[{o}]"


class LimitExec(PhysicalPlan):
    """Keep first n live rows per partition (LocalLimit); with a single
    child partition this is GlobalLimit (reference: sqlx/limit.scala)."""

    child_fields = ("child",)

    def __init__(self, n: int, child: PhysicalPlan, offset: int = 0,
                 is_global: bool = False):
        self.n = n
        self.offset = offset
        self.is_global = is_global
        self.child = child

    @property
    def output(self):
        return self.child.output

    def required_child_distribution(self):
        return [AllTuples()] if self.is_global else [UnspecifiedDistribution()]

    def execute(self, ctx: ExecContext) -> list[Partition]:
        return [self._limit_partition(part, ctx)
                for part in self.child.execute(ctx)]

    def _limit_partition(self, part: Partition, ctx) -> Partition:
        jnp = _jnp()
        if not part:
            return []
        batch = concat_batches(part, attrs_schema(self.output))
        cap = batch.capacity
        key = ("limit", cap, self.n, self.offset)

        def build():
            def kernel(mask):
                rank = jnp.cumsum(mask.astype(jnp.int64))
                keep = mask & (rank > self.offset) & \
                    (rank <= self.offset + self.n)
                return keep

            return stage_jit(kernel)

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
        new_mask = kernel(batch.row_mask)
        limited = ColumnarBatch(batch.schema, batch.columns, new_mask,
                                num_rows=None)
        # a local limit leaves ≤ n live rows in a full-capacity tile;
        # compact so the gather exchange and downstream sort touch only
        # the kept rows (the TakeOrderedAndProject shrink)
        if not self.is_global and self.n * 4 <= cap:
            from ..columnar.ops import compact_batch

            limited = compact_batch(limited)
        return [limited]


# ---------------------------------------------------------------------------
# Joins
# ---------------------------------------------------------------------------

class HashJoinExec(PhysicalPlan):
    """Equi-join via the sorted-probe kernel (role of ShuffledHashJoinExec /
    BroadcastHashJoinExec, sqlx/joins/). The right side is the build side;
    the planner flips right-joins into left joins over swapped children."""

    child_fields = ("left", "right")

    def __init__(self, left_keys: Sequence[AttributeReference],
                 right_keys: Sequence[AttributeReference], join_type: str,
                 left: PhysicalPlan, right: PhysicalPlan,
                 is_broadcast: bool = False):
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type  # inner/left_outer/left_semi/left_anti/full_outer
        self.left = left
        self.right = right
        self.is_broadcast = is_broadcast
        # [(ScanExec, key index)] injected by the planner: probe-side scans
        # whose partition column is a join key — executing the build side
        # first lets those scans skip whole splits (DPP)
        self.dpp_targets: list = []
        # whole-stage fusion splice (physical/fusion.py FuseStages): when a
        # filter/project pipeline fed this join's probe side, its
        # (filters, outputs) trace inside the probe kernel and `left` is the
        # pipeline's child. probe_attrs = the pipeline's output attributes —
        # the join's probe-side schema from the outside.
        self.probe_fusion: tuple | None = None
        self.probe_attrs: list | None = None
        self._probe_pipe_cache: ExprPipeline | None = None

    @property
    def _left_attrs(self) -> list:
        """Probe-side output attributes as consumers see them (after the
        fused pipeline when one is spliced in)."""
        return self.probe_attrs if self.probe_fusion is not None \
            else self.left.output

    def _probe_pipeline(self) -> "ExprPipeline | None":
        if self.probe_fusion is None:
            return None
        if self._probe_pipe_cache is None:
            filters, outputs = self.probe_fusion
            self._probe_pipe_cache = ExprPipeline(
                self.left.output, filters, outputs,
                attrs_schema(self.probe_attrs))
        return self._probe_pipe_cache

    @property
    def output(self):
        if self.join_type in ("left_semi", "left_anti"):
            return self._left_attrs
        ro = self.right.output
        lo = self._left_attrs
        if self.join_type in ("left_outer", "full_outer"):
            ro = [a.with_nullability(True) for a in ro]
        if self.join_type == "full_outer":
            lo = [a.with_nullability(True) for a in lo]
        return lo + ro

    def required_child_distribution(self):
        if self.is_broadcast:
            return [UnspecifiedDistribution(), BroadcastDistribution()]
        return [ClusteredDistribution(list(self.left_keys)),
                ClusteredDistribution(list(self.right_keys))]

    def output_partitioning(self):
        return self.left.output_partitioning()

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from .adaptive import coalesce_join_inputs

        if self.dpp_targets:
            # build first; its distinct keys prune probe-side splits
            right_parts = self.right.execute(ctx)
            self._install_dpp_filters(right_parts, ctx)
            left_parts = self.left.execute(ctx)
        else:
            left_parts = self.left.execute(ctx)
            right_parts = self.right.execute(ctx)
        if self.is_broadcast:
            # broadcast exchange produced one partition; replicate
            bp = right_parts[0]
            right_parts = [bp for _ in left_parts]
        else:
            from .adaptive import split_skewed_join_inputs

            left_parts, right_parts = coalesce_join_inputs(
                self.left, self.right, left_parts, right_parts, ctx,
                self.left.output, self.right.output)
            left_parts, right_parts = split_skewed_join_inputs(
                left_parts, right_parts, ctx, self.join_type)
        if len(left_parts) != len(right_parts):
            raise ExecutionError(
                f"join children partition counts differ: "
                f"{len(left_parts)} vs {len(right_parts)}")
        probe_pipe = self._probe_pipeline()
        if probe_pipe is not None and (
                self.join_type == "full_outer"
                or ctx.conf.get("spark.tpu.join.runtimeFilter", False)
                or ctx.conf.get("spark.tpu.join.runtimeFilter.bloom",
                                False)):
            # paths that read probe key columns outside the probe kernel
            # (anti-join of build vs probe keys, runtime filters):
            # materialize the pipeline up front and join as if unfused
            left_parts = [[probe_pipe.run(b) for b in p]
                          for p in left_parts]
            probe_pipe = None
        rschema = attrs_schema(self.right.output)
        if self.builds_the_larger_side(
                [[b.capacity for b in p] for p in left_parts],
                [[b.capacity for b in p] for p in right_parts]):
            return [self._join_built_on_the_left(
                left_parts[0], right_parts[0], rschema, ctx, probe_pipe)]
        lschema = attrs_schema(self.left.output if probe_pipe is not None
                               else self._left_attrs)
        return ctx.par_map(
            lambda pair: self._join_partition(pair[0], pair[1], lschema,
                                              rschema, ctx,
                                              probe_pipe=probe_pipe),
            list(zip(left_parts, right_parts)))

    # an inner join's build side is the planner's right; where the left
    # holds this many times fewer slots, the join is built on the left.
    # Not under 4 Mi slots on the right: there the larger side's sort is
    # a few ms (24 ms at 8 Mi, 123 ms at 32 Mi: PERF.md 7), no more than
    # the launches the turn adds (a probe a tile of the larger side),
    # and the plan analyzer's launch model stays exact
    BUILD_LEFT_RATIO = 4
    BUILD_LEFT_MIN_SLOTS = 1 << 22

    def builds_the_larger_side(self, left_caps, right_caps) -> bool:
        """Whether this inner join, as planned, would index the larger of
        its two sides, each given as its partitions' tile capacities (the
        plan analyzer asks with the capacities it predicts). `ReorderJoins` grows a join chain from its smallest
        relation, so a filtered dimension comes out on the left and the
        fact table's flow on the right, the build side: on the chip the
        index of a 32 Mi-slot flow is a 0.12 s sort and a 0.4 s scatter
        before a single row is probed (PR 37, TPC-DS q88 by stages: 15.0 s
        a query, eight such joins). A build costs by its slots and a probe
        by the probing side's, so the smaller side is the one to build.
        Decided from the capacities of the batches at hand: host numbers,
        no sync. One partition a side only: a broadcast build side is one
        partition for every probe partition, and which side that is does
        not turn around."""
        if self.join_type != "inner" or self.dpp_targets \
                or len(left_caps) != 1 or len(right_caps) != 1:
            return False
        small, large = sum(left_caps[0]), sum(right_caps[0])
        return large >= self.BUILD_LEFT_MIN_SLOTS \
            and 0 < small * self.BUILD_LEFT_RATIO <= large

    def _join_built_on_the_left(self, lp: Partition, rp: Partition, rschema,
                                ctx, probe_pipe) -> Partition:
        """The same inner join with the sides' roles turned around: the
        right side's tiles probe an index of the left. A twin join does
        it, over the two sides as they are already executed; its rows are
        the right side's columns and then the left's, so each batch's
        columns are put back in this join's order. Which row comes first
        is no part of an inner join's result."""
        if probe_pipe is not None:
            lp = [probe_pipe.run(b) for b in lp]
        lattrs = self._left_attrs
        twin = HashJoinExec(self.right_keys, self.left_keys, "inner",
                            _SchemaOnly(self.right.output),
                            _SchemaOnly(lattrs))
        ctx.metrics.add("join.build_swapped")
        out = twin._join_partition(rp, lp, rschema, attrs_schema(lattrs),
                                   ctx)
        schema = attrs_schema(self.output)
        nr = len(self.right.output)
        return [ColumnarBatch(schema, b.columns[nr:] + b.columns[:nr],
                              b.row_mask, num_rows=b._num_rows)
                for b in out]

    def _install_dpp_filters(self, right_parts, ctx) -> None:
        """Distinct build-side key values → runtime split filters on the
        probe scans (reference: PartitionPruning's duplicated build
        subquery; here the materialized build side IS the value source, so
        nothing is executed twice)."""
        from ..config import DPP_BUILD_THRESHOLD

        max_rows = int(ctx.conf.get(DPP_BUILD_THRESHOLD))
        total = sum(b.num_rows() for p in right_parts for b in p)
        rpos = {a.expr_id: i for i, a in enumerate(self.right.output)}
        values_by_key: dict[int, set] = {}
        for scan, key_idx in self.dpp_targets:
            if total > max_rows:
                scan.runtime_split_filter = None
                continue
            values = values_by_key.get(key_idx)
            if values is None:
                ci = rpos[self.right_keys[key_idx].expr_id]
                values = set()
                for part in right_parts:
                    for b in part:
                        arr = b.columns[ci].to_numpy(b.selection_indices())
                        if arr.dtype == object:
                            arr = np.array([v for v in arr if v is not None],
                                           dtype=object)
                        if len(arr):
                            values.update(
                                v.item() if hasattr(v, "item") else v
                                for v in np.unique(arr))
                values_by_key[key_idx] = values
            col_name = scan.attrs[self._dpp_attr_index(scan, key_idx)].name
            scan.runtime_split_filter = (col_name, values)

    def _dpp_attr_index(self, scan, key_idx: int) -> int:
        target = self.left_keys[key_idx].expr_id
        for i, a in enumerate(scan.attrs):
            if a.expr_id == target:
                return i
        raise KeyError(target)

    def _join_partition(self, lp: Partition, rp: Partition, lschema, rschema,
                        ctx, _depth: int = 0, probe_pipe=None) -> Partition:

        from ..ops import joining as J

        jnp = _jnp()
        if probe_pipe is not None:
            from ..config import FUSION_MIN_ROWS

            if sum(b.capacity for b in lp) < int(ctx.conf.get(
                    FUSION_MIN_ROWS)):
                # partition too small to amortize a per-structure fused
                # probe compile: run the shared pipeline + probe kernels
                lp = [probe_pipe.run(b) for b in lp]
                lschema = attrs_schema(self._left_attrs)
                probe_pipe = None
        # Grace hash join (memory discipline): a build side over the device
        # budget is hash-fragmented together with its probe side — same key
        # hash, same fragment — and each fragment joins independently
        # (role of the reference's spillable HashedRelation fallback;
        # exec/memory.py is the budget authority). Depth guard: one level —
        # re-hashing with the same function cannot split further.
        if rp and _depth == 0:
            budget = ctx.memory.tile_rows(rschema, amplification=4)
            build_cap = sum(b.capacity for b in rp)
            if build_cap > budget:
                if probe_pipe is not None:
                    # grace fragments by computed key columns: materialize
                    lp = [probe_pipe.run(b) for b in lp]
                    lschema = attrs_schema(self._left_attrs)
                return self._grace_join(lp, rp, lschema, rschema, ctx,
                                        budget, build_cap)
        build = concat_batches(rp, rschema) if rp else ColumnarBatch.empty(rschema)
        # mesh partitions are committed to their device; the build side and
        # every probe batch must share one before a kernel can see both
        # (broadcast batch vs mesh partition, AQE-coalesced neighbours)
        from ..columnar.ops import _device_of, batch_to_device

        bdev = _device_of(build.row_mask)
        if bdev is not None and lp:
            lp = [pb if _device_of(pb.row_mask) in (None, bdev)
                  else batch_to_device(pb, bdev) for pb in lp]
        rpos = {a.expr_id: i for i, a in enumerate(self.right.output)}
        lpos = {a.expr_id: i for i, a in enumerate(self._left_attrs)}
        bkeys = [build.columns[rpos[k.expr_id]] for k in self.right_keys]

        dense = self._try_dense_build(build, bkeys, ctx)
        if dense is not None:
            out_batches = [
                self._dense_probe_batch(pb, build, dense, lpos, ctx,
                                        probe_pipe)
                for pb in (lp or [ColumnarBatch.empty(lschema)])]
            if self.join_type == "full_outer":
                out_batches.append(
                    self._unmatched_build_rows(lp, build, lschema, ctx))
            return out_batches

        bkey_eqs = [c.eq_keys() for c in bkeys]
        bkey_valids = [c.validity for c in bkeys]

        from ..types import DateType, DecimalType, IntegralType

        if self.join_type in ("inner", "left_semi") and len(bkeys) == 1 \
                and isinstance(bkeys[0].dtype,
                               (IntegralType, DateType, DecimalType)) \
                and ctx.conf.get("spark.tpu.join.runtimeFilter", False):
            lp = self._range_filter_probe(lp, build, bkeys, bkey_valids,
                                          lpos, ctx)
        if self.join_type in ("inner", "left_semi") \
                and ctx.conf.get("spark.tpu.join.runtimeFilter.bloom", False):
            lp = self._bloom_filter_probe(lp, build, bkeys, bkey_valids,
                                          lpos, ctx)

        from ..columnar.batch import eq_key_dtype

        # asked once: the index and every probe of it take the same answer
        kpath = J.key_path(bkey_eqs, [eq_key_dtype(k.dtype)
                                      for k in self.left_keys])
        bi_key = ("join_build", build.capacity, len(bkeys),
                  tuple(str(k.dtype) for k in bkey_eqs),
                  tuple(v is not None for v in bkey_valids), kpath)

        def build_bi():
            return stage_jit(lambda eqs, valids, mask: J.build_index(
                eqs, valids, mask, kpath))

        bi_kernel = GLOBAL_KERNEL_CACHE.get_or_build(bi_key, build_bi)
        bindex = bi_kernel(bkey_eqs, bkey_valids, build.row_mask)

        out_batches = []
        for pb in (lp or [ColumnarBatch.empty(lschema)]):
            out_batches.append(
                self._probe_batch(pb, build, bindex, bkey_eqs, bkey_valids,
                                  lpos, ctx, kpath, probe_pipe))
        if self.join_type == "full_outer":
            out_batches.append(
                self._unmatched_build_rows(lp, build, lschema, ctx))
        return out_batches

    def _range_filter_probe(self, lp, build, bkeys, bkey_valids, lpos, ctx):
        """Runtime min-max join filter (reference: InjectRuntimeFilter /
        bloom pushdown, simplified to a range): probe rows outside the
        build key range can't match an inner/semi join, so they drop
        BEFORE the O(cap log cap) sort-probe; batches that shrink enough
        compact to a smaller capacity bucket. Default OFF: on the 2-core
        CPU VM the filter+sync overhead beats the smaller sort; benchmark
        on a live chip (where lax.sort dominates) before enabling."""

        from ..columnar.ops import compact_batch

        jnp = _jnp()
        bc = bkeys[0]
        rkey = ("join_rf_range", build.capacity, str(bc.data.dtype),
                bc.validity is not None)

        def build_range():
            def kr(k, v, m):
                k64 = k.astype(jnp.int64)
                live = m if v is None else (m & v)
                big = jnp.iinfo(jnp.int64).max
                small = jnp.iinfo(jnp.int64).min
                return (jnp.min(jnp.where(live, k64, big)),
                        jnp.max(jnp.where(live, k64, small)))

            return stage_jit(kr)

        kr = GLOBAL_KERNEL_CACHE.get_or_build(rkey, build_range)
        bmin, bmax = kr(bc.data, bc.validity, build.row_mask)

        min_cap = int(ctx.conf.get(
            "spark.tpu.join.runtimeFilter.minCapacity", 1 << 20))
        out = []
        for pb in (lp or []):
            if pb.capacity < min_cap:
                out.append(pb)  # small batch: the sort-probe is cheap
                continue
            pc = pb.columns[lpos[self.left_keys[0].expr_id]]
            fkey = ("join_rf_mask", pb.capacity, str(pc.data.dtype),
                    pc.validity is not None)

            def build_mask():
                def km(k, v, m, lo, hi):
                    k64 = k.astype(jnp.int64)
                    keep = (k64 >= lo) & (k64 <= hi)
                    if v is not None:
                        keep = keep & v
                    nm = m & keep
                    return nm, jnp.sum(nm)

                return stage_jit(km)

            km = GLOBAL_KERNEL_CACHE.get_or_build(fkey, build_mask)
            nm, live = km(pc.data, pc.validity, pb.row_mask, bmin, bmax)
            live = int(live)
            nb = ColumnarBatch(pb.schema, pb.columns, nm, num_rows=live)
            if bucket_capacity(max(live, 1)) <= pb.capacity // 16:
                nb = compact_batch(nb)
                ctx.metrics.add("join.runtime_filter_compactions")
            out.append(nb)
        return out

    def _bloom_filter_probe(self, lp, build, bkeys, bkey_valids, lpos, ctx):
        """Runtime bloom join filter (reference: InjectRuntimeFilter.scala
        bloom branch + BloomFilterImpl): a device bitset of build-key hashes
        drops probe rows that cannot match an inner/semi join before the
        sort-probe. Works for any key arity/type (the hash domain is
        hash_columns), unlike the single-integral-key min-max filter. The
        bitset is two scatter-sets at build + two gathers at probe — all
        inside XLA; k=2 with ≥8 bits/row keeps the false-positive rate
        under ~5%."""

        from ..columnar.ops import compact_batch
        from ..ops.hashing import hash_columns, mix64

        jnp = _jnp()
        nbits = min(1 << 24, bucket_capacity(max(build.capacity, 1) * 8))
        bkey_eqs = [c.eq_keys() for c in bkeys]

        bkey2 = ("join_rf_bloom_build", build.capacity, nbits, len(bkeys),
                 tuple(str(k.dtype) for k in bkey_eqs),
                 tuple(v is not None for v in bkey_valids))

        from ..utils.sketch import bloom_position_offsets

        off0, off1 = bloom_position_offsets(2)

        def build_bloom():
            def kb(eqs, valids, mask):
                h = hash_columns(eqs, list(valids))
                p1 = mix64(h + jnp.int64(off0)) & (nbits - 1)
                p2 = mix64(h + jnp.int64(off1)) & (nbits - 1)
                p1 = jnp.where(mask, p1, nbits)
                p2 = jnp.where(mask, p2, nbits)
                bits = jnp.zeros(nbits, dtype=bool)
                bits = bits.at[p1].set(True, mode="drop")
                bits = bits.at[p2].set(True, mode="drop")
                return bits

            return stage_jit(kb)

        # a broadcast join probes the SAME build batch once per partition —
        # memoize the bitset on the batch so the scatter-build runs once
        bstats = _batch_stats_cache(build)
        mkey = ("bloom_bits", nbits,
                tuple(k.expr_id for k in self.right_keys))
        bits = bstats.get(mkey)
        if bits is None:
            bits = GLOBAL_KERNEL_CACHE.get_or_build(bkey2, build_bloom)(
                bkey_eqs, bkey_valids, build.row_mask)
            bstats[mkey] = bits

        out = []
        for pb in (lp or []):
            pkeys = [pb.columns[lpos[k.expr_id]] for k in self.left_keys]
            pkey_eqs = [c.eq_keys() for c in pkeys]
            pkey_valids = [c.validity for c in pkeys]
            fkey = ("join_rf_bloom_probe", pb.capacity, nbits, len(pkeys),
                    tuple(str(k.dtype) for k in pkey_eqs),
                    tuple(v is not None for v in pkey_valids))

            def probe_bloom():
                def kp(bits, eqs, valids, mask):
                    h = hash_columns(eqs, list(valids))
                    keep = jnp.take(bits, mix64(h + jnp.int64(off0))
                                    & (nbits - 1)) \
                        & jnp.take(bits, mix64(h + jnp.int64(off1))
                                   & (nbits - 1))
                    nm = mask & keep
                    return nm, jnp.sum(nm)

                return stage_jit(kp)

            nm, live = GLOBAL_KERNEL_CACHE.get_or_build(fkey, probe_bloom)(
                bits, pkey_eqs, pkey_valids, pb.row_mask)
            before = pb.num_rows()
            live = int(live)
            ctx.metrics.add("join.bloom_filtered_rows", before - live)
            nb = ColumnarBatch(pb.schema, pb.columns, nm, num_rows=live)
            if bucket_capacity(max(live, 1)) <= pb.capacity // 16:
                nb = compact_batch(nb)
                ctx.metrics.add("join.runtime_filter_compactions")
            out.append(nb)
        return out

    def _probe_batch(self, pb: ColumnarBatch, build: ColumnarBatch, bindex,
                     bkey_eqs, bkey_valids, lpos, ctx, kpath: str,
                     probe_pipe=None) -> ColumnarBatch:

        from ..ops import joining as J

        jnp = _jnp()
        jt = self.join_type if self.join_type != "full_outer" else "left_outer"
        if probe_pipe is not None:
            pb, r, expand = self._fused_probe(pb, bindex, bkey_eqs,
                                              bkey_valids, ctx, jt, kpath)
        else:
            pkeys = [pb.columns[lpos[k.expr_id]] for k in self.left_keys]
            pkey_eqs = [c.eq_keys() for c in pkeys]
            pkey_valids = [c.validity for c in pkeys]

            out_cap = max(pb.capacity, 1 << 10)
            expand = False
            while True:
                key = ("join_probe", jt, pb.capacity, build.capacity, out_cap,
                       len(pkey_eqs), tuple(str(k.dtype) for k in pkey_eqs),
                       tuple(v is not None for v in pkey_valids),
                       tuple(v is not None for v in bkey_valids), kpath) + (
                           ("expand",) if expand else ())

                def build_kernel(oc=out_cap, ex=expand):
                    def kernel(bidx_sorted, bidx_perm, beqs, bvalids, peqs,
                               pvalids, pmask):
                        bi = J.BuildSide(bidx_sorted, bidx_perm)
                        return J.probe_join(bi, beqs, bvalids, peqs, pvalids,
                                            pmask, oc, jt, kpath, expand=ex)

                    return stage_jit(kernel)

                kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build_kernel)
                r = kernel(bindex.sorted_hash, bindex.perm, bkey_eqs,
                           bkey_valids, pkey_eqs, pkey_valids, pb.row_mask)
                # a semi/anti join's hash index may leave rows undecided:
                # then the batch is probed again on the expansion
                needed, unsure = device_read("join.needed", r.needed,
                                             r.unsure)
                if unsure is not None and int(unsure):
                    expand = True
                    continue
                needed = int(needed)
                if needed <= out_cap:
                    break
                out_cap = bucket_capacity(needed)
                ctx.metrics.add("join.capacity_retry")

        self._count_setop(jt, expand, ctx)
        probe_out = gather_batch(pb, r.probe_idx, r.out_mask)
        if self.join_type in ("left_semi", "left_anti"):
            return probe_out
        null_build = ~r.matched
        build_out = gather_batch(build, r.build_idx, r.out_mask,
                                 extra_invalid=null_build)
        schema = attrs_schema(self.output)
        cols = probe_out.columns + build_out.columns
        return ColumnarBatch(schema, cols, r.out_mask, num_rows=None)

    def _fused_probe(self, pb: ColumnarBatch, bindex, bkey_eqs, bkey_valids,
                     ctx, jt, kpath: str):
        """Whole-stage fused probe: the probe-side filter/project pipeline
        traces INSIDE the probe kernel — one dispatch computes the projected
        columns, derives the join keys, and probes the build index (the
        consume splice of the reference's codegen'd
        BroadcastHashJoinExec.doConsume). Returns the COMPUTED probe batch
        plus the probe result; the caller's gathers read the computed
        columns."""

        from ..ops import joining as J
        from .compile import (
            pipeline_columns, pipeline_host_pass, pipeline_signature,
            trace_pipeline,
        )
        from ..types import BooleanType

        jnp = _jnp()
        from ..columnar.batch import EMPTY_DICT
        from ..types import StringType

        filters, outputs = self.probe_fusion
        input_attrs = self.left.output
        pipe = self._probe_pipeline()
        cap = pb.capacity
        hctx, host_outs, aux = pipeline_host_pass(input_attrs, filters,
                                                  outputs, pb)
        opos = {a.expr_id: i for i, a in enumerate(self.probe_attrs)}
        kidx = tuple(opos[k.expr_id] for k in self.left_keys)
        key_bool = tuple(isinstance(self.probe_attrs[i].dtype, BooleanType)
                         for i in kidx)
        # string probe keys: padded dictionary-hash luts ride as kernel
        # aux inputs so eq_keys (codes → stable value hashes) computes
        # INSIDE the trace — the former unfused string-probe fallback is
        # retired (compressed execution)
        dict_pos = {i: j for j, i in enumerate(
            i for i in kidx
            if isinstance(self.probe_attrs[i].dtype, StringType))}
        kluts = [(host_outs[i].sdict or EMPTY_DICT).device_hash_lut()
                 for i in dict_pos]
        in_sig = pipeline_signature(pb)

        out_cap = max(cap, 1 << 10)
        expand = False
        while True:
            kkey = ("fused_probe", jt, pipe._struct_key, cap,
                    bindex.perm.shape[0], out_cap, kidx, in_sig,
                    hctx.signature(), tuple(v is not None
                                            for v in bkey_valids),
                    tuple(sorted(dict_pos)),
                    tuple(int(l.shape[0])  # tpulint: ignore[host-sync]
                          for l in kluts), kpath) + (
                        ("expand",) if expand else ())

            def build_kernel(oc=out_cap, ex=expand):
                def kernel(bidx_sorted, bidx_perm, beqs, bvalids, datas,
                           valids, pmask, aux, kluts):
                    out_datas, out_valids, mask = trace_pipeline(
                        input_attrs, filters, outputs, datas, valids, pmask,
                        aux, cap)
                    peqs = []
                    pvalids = []
                    for i, is_bool in zip(kidx, key_bool):
                        kd = out_datas[i]
                        if is_bool:
                            kd = kd.astype(jnp.int32)
                        if i in dict_pos:
                            lut = kluts[dict_pos[i]]
                            kd = jnp.take(lut, jnp.clip(
                                kd.astype(jnp.int32), 0,
                                lut.shape[0] - 1))
                        peqs.append(kd)
                        pvalids.append(out_valids[i])
                    bi = J.BuildSide(bidx_sorted, bidx_perm)
                    r = J.probe_join(bi, beqs, bvalids, peqs, pvalids,
                                     mask, oc, jt, kpath, expand=ex)
                    return r, out_datas, out_valids, mask

                return stage_jit(kernel)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(kkey, build_kernel)
            r, out_datas, out_valids, mask = kernel(
                bindex.sorted_hash, bindex.perm, bkey_eqs, bkey_valids,
                [c.data for c in pb.columns],
                [c.validity for c in pb.columns], pb.row_mask, aux, kluts)
            needed, unsure = device_read("join.needed", r.needed, r.unsure)
            if unsure is not None and int(unsure):
                expand = True
                continue
            needed = int(needed)
            if needed <= out_cap:
                break
            out_cap = bucket_capacity(needed)
            ctx.metrics.add("join.capacity_retry")

        pschema = attrs_schema(self.probe_attrs)
        cols = pipeline_columns(pschema.fields, host_outs, out_datas,
                                out_valids)
        computed = ColumnarBatch(pschema, cols, mask, num_rows=None)
        return computed, r, expand

    @staticmethod
    def _count_setop(jt: str, expand: bool, ctx) -> None:
        """`join.semi_exists`/`join.anti_exists` for a semi/anti probe that
        decided existence, `join.setop_expanded` for one that expanded."""
        if jt in ("left_semi", "left_anti"):
            ctx.metrics.add("join.setop_expanded" if expand else
                            f"join.{jt.removeprefix('left_')}_exists")

    def _grace_join(self, lp: Partition, rp: Partition, lschema, rschema,
                    ctx, budget_rows: int, build_cap: int) -> Partition:
        """Fragment both sides by join-key hash and join fragment-wise.
        Equal keys co-locate, so every join type distributes over the
        fragments (full_outer's unmatched-build emission runs per
        fragment against that fragment's probe rows only)."""
        from ..exec import shuffle as S

        nfrag = -(-build_cap // max(budget_rows, 1))
        nfrag = min(256, 1 << max(1, (nfrag - 1).bit_length()))
        rpos = {a.expr_id: i for i, a in enumerate(self.right.output)}
        lpos = {a.expr_id: i for i, a in enumerate(self._left_attrs)}
        rk = [rpos[k.expr_id] for k in self.right_keys]
        lk = [lpos[k.expr_id] for k in self.left_keys]
        # distinct seed: the inputs are already hash-partitioned on these
        # keys with the exchange's default seed — reusing it would send the
        # whole partition to one fragment (h % nfrag constant)
        r_frags = S.shuffle_hash([rp], rk, nfrag, rschema, ctx,
                                 seed=0x9E3779B9)
        l_frags = S.shuffle_hash([lp], lk, nfrag, lschema, ctx,
                                 seed=0x9E3779B9)
        ctx.memory.count("join.grace.fragments", nfrag)
        out: Partition = []
        for lf, rf in zip(l_frags, r_frags):
            out.extend(self._join_partition(lf, rf, lschema, rschema, ctx,
                                            _depth=1))
        return out

    def _try_dense_build(self, build: ColumnarBatch, bkeys, ctx):
        """Dense unique-key build fast path (TPC-DS dimension tables: dense
        integral primary keys): the 'hash table' is a direct-address row
        index, the probe a single gather — no sort, no searchsorted, no
        expansion (probe output is 1:1). Falls back when keys are multi,
        non-integral, sparse, or duplicated."""

        from ..types import DateType, IntegralType

        jnp = _jnp()
        if len(bkeys) != 1:
            return None
        kc = bkeys[0]
        if not isinstance(kc.dtype, (IntegralType, DateType)):
            return None
        cap = build.capacity

        kmin, kmax, any_live = dense_range_stats(kc, build.row_mask, cap)
        if not any_live:
            return None
        span = kmax - kmin + 1
        if span > min(8 * cap, 1 << 23):
            return None

        tcap = bucket_capacity(span)
        tkey = ("djoin_build", cap, tcap, str(kc.data.dtype),
                kc.validity is not None)

        def build_table():
            from jax import lax

            def kt(k, v, rm, kmin_s):
                k = k.astype(jnp.int64)  # cast inside (transport cost)
                m = rm if v is None else (rm & v)
                slot = jnp.where(m, k - kmin_s, tcap)
                # -1 where no build row has the key: the probe reads one
                # table, a row and whether there is one (a gather is 7 ns
                # an element on a v5e: 30 ms a 4 Mi tile)
                rowidx = jnp.full((tcap,), -1, jnp.int32).at[slot].set(
                    lax.iota(jnp.int32, cap), mode="drop")
                cnt = jnp.zeros((tcap,), jnp.int32).at[slot].add(
                    1, mode="drop")
                return rowidx, jnp.max(cnt)

            return stage_jit(kt)

        rowidx, maxc_d = GLOBAL_KERNEL_CACHE.get_or_build(
            tkey, build_table)(kc.data, kc.validity, build.row_mask,
                               jnp.int64(kmin))
        # the duplicate-key verdict is one scalar: memoize it per build
        # column identity so a broadcast build probed from many partitions
        # syncs once, not once per partition
        maxc = _memo_device_scalars(
            ("djoin_maxc", tcap), (kc.data, kc.validity, build.row_mask),
            lambda: int(device_read("dense.dup", maxc_d)[0]))
        if maxc > 1:
            return None  # duplicate build keys → sorted-probe path
        ctx.metrics.add("join.dense_fast_path")
        return {"rowidx": rowidx, "kmin": kmin, "tcap": tcap}

    def _dense_probe_batch(self, pb: ColumnarBatch, build: ColumnarBatch,
                           dense, lpos, ctx, probe_pipe=None) -> ColumnarBatch:

        jnp = _jnp()
        cap = pb.capacity
        tcap = dense["tcap"]
        jt = self.join_type if self.join_type != "full_outer" else "left_outer"

        def probe_body(k64, pvalid, pmask, rowidx, kmin_s):
            k = k64 - kmin_s
            in_range = (k >= 0) & (k < tcap)
            slot = jnp.clip(k, 0, tcap - 1)
            usable = pmask & in_range
            if pvalid is not None:
                usable = usable & pvalid
            bidx = jnp.take(rowidx, slot)
            matched = usable & (bidx >= 0)
            bidx = jnp.maximum(bidx, 0)
            if jt == "inner":
                out_mask = matched
            elif jt == "left_outer":
                out_mask = pmask
            elif jt == "left_semi":
                out_mask = matched
            else:  # left_anti
                out_mask = pmask & ~matched
            return bidx, matched, out_mask

        if probe_pipe is not None:
            # fused: the probe-side pipeline traces inside the dense-probe
            # kernel; the computed batch comes back with the probe result
            from .compile import (
                pipeline_columns, pipeline_host_pass, pipeline_signature,
                trace_pipeline,
            )

            filters, outputs = self.probe_fusion
            input_attrs = self.left.output
            hctx, host_outs, aux = pipeline_host_pass(input_attrs, filters,
                                                      outputs, pb)
            opos = {a.expr_id: i for i, a in enumerate(self.probe_attrs)}
            ki = opos[self.left_keys[0].expr_id]
            pipe = self._probe_pipeline()
            key = ("fused_djoin_probe", jt, pipe._struct_key, cap, tcap, ki,
                   pipeline_signature(pb), hctx.signature())

            def build_fused():
                def kp(datas, valids, pmask, aux, rowidx, kmin_s):
                    out_datas, out_valids, mask = trace_pipeline(
                        input_attrs, filters, outputs, datas, valids, pmask,
                        aux, cap)
                    k64 = out_datas[ki].astype(jnp.int64)
                    bidx, matched, out_mask = probe_body(
                        k64, out_valids[ki], mask, rowidx, kmin_s)
                    return bidx, matched, out_mask, out_datas, out_valids

                return stage_jit(kp)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build_fused)
            bidx, matched, out_mask, out_datas, out_valids = kernel(
                [c.data for c in pb.columns],
                [c.validity for c in pb.columns], pb.row_mask, aux,
                dense["rowidx"], jnp.int64(dense["kmin"]))
            pschema = attrs_schema(self.probe_attrs)
            cols = pipeline_columns(pschema.fields, host_outs, out_datas,
                                    out_valids)
            pb = ColumnarBatch(pschema, cols, out_mask, num_rows=None)
        else:
            kc = pb.columns[lpos[self.left_keys[0].expr_id]]
            key = ("djoin_probe", jt, cap, tcap, str(kc.data.dtype),
                   kc.validity is not None)

            def build_kernel():
                def kp(pkey, pvalid, pmask, rowidx, kmin_s):
                    return probe_body(pkey.astype(jnp.int64), pvalid, pmask,
                                      rowidx, kmin_s)

                return stage_jit(kp)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build_kernel)
            bidx, matched, out_mask = kernel(
                kc.data, kc.validity, pb.row_mask, dense["rowidx"],
                jnp.int64(dense["kmin"]))

        if self.join_type in ("left_semi", "left_anti"):
            return ColumnarBatch(pb.schema, pb.columns, out_mask,
                                 num_rows=None)
        schema = attrs_schema(self.output)
        cols = pb.columns + self._dense_build_columns(
            pb, build, bidx, matched, out_mask, lpos)
        return ColumnarBatch(schema, cols, out_mask, num_rows=None)

    def _dense_build_columns(self, pb, build, bidx, matched, out_mask,
                             lpos) -> list:
        """The build side's columns at the probe's rows. Its key column
        is not fetched: a matched row's build key is its probe key (one
        integral key, equal by value), and an unmatched row reads NULL
        either way. A dimension filtered down to its key, which is what
        a star join's pipelines leave of most of them, then costs the
        probe no gather by the build row at all (TPC-DS q88 by stages:
        24 such joins a query, 4.6 of its 14.4 s, PR 37)."""
        rpos = {a.expr_id: i for i, a in enumerate(self.right.output)}
        ki = rpos[self.right_keys[0].expr_id]
        bkey = build.columns[ki]
        pkey = pb.columns[lpos[self.left_keys[0].expr_id]]
        cols = [None] * len(build.columns)
        cols[ki] = Column(bkey.dtype, pkey.data.astype(bkey.data.dtype),
                          matched, None)
        rest = [i for i in range(len(cols)) if i != ki]
        if rest:
            fields = build.schema.fields
            fetched = gather_batch(
                ColumnarBatch(StructType([fields[i] for i in rest]),
                              [build.columns[i] for i in rest],
                              build.row_mask, num_rows=None),
                bidx, out_mask, extra_invalid=~matched)
            for i, c in zip(rest, fetched.columns):
                cols[i] = c
        return cols

    def _unmatched_build_rows(self, lp: Partition, build: ColumnarBatch,
                              lschema, ctx) -> ColumnarBatch:
        """full_outer extension: anti-join build side against probe keys."""

        from ..ops import joining as J

        jnp = _jnp()
        probe_all = concat_batches(lp, lschema) if lp \
            else ColumnarBatch.empty(lschema)
        lpos = {a.expr_id: i for i, a in enumerate(self._left_attrs)}
        pkeys = [probe_all.columns[lpos[k.expr_id]] for k in self.left_keys]
        pkey_eqs = [c.eq_keys() for c in pkeys]
        pkey_valids = [c.validity for c in pkeys]
        rpos = {a.expr_id: i for i, a in enumerate(self.right.output)}
        bkeys = [build.columns[rpos[k.expr_id]] for k in self.right_keys]
        bkey_eqs = [c.eq_keys() for c in bkeys]
        bkey_valids = [c.validity for c in bkeys]

        # swap: probe = build side, build = probe side; left_anti
        kpath = J.key_path(pkey_eqs, bkey_eqs)
        pi = J.build_index(pkey_eqs, pkey_valids, probe_all.row_mask, kpath)
        out_cap = build.capacity
        r = J.probe_join(pi, pkey_eqs, pkey_valids, bkey_eqs, bkey_valids,
                         build.row_mask, out_cap, "left_anti", kpath)
        expand = r.unsure is not None \
            and int(device_read("join.unsure", r.unsure)[0]) > 0
        if expand:
            r = J.probe_join(pi, pkey_eqs, pkey_valids, bkey_eqs,
                             bkey_valids, build.row_mask, out_cap,
                             "left_anti", kpath, expand=True)
        self._count_setop("left_anti", expand, ctx)
        build_rows = gather_batch(build, r.probe_idx, r.out_mask)
        schema = attrs_schema(self.output)
        nl = len(self._left_attrs)
        from ..columnar.batch import EMPTY_DICT

        jnpmod = _jnp()
        cap = r.out_mask.shape[0]
        left_cols = [
            Column(f.dataType,
                   jnpmod.zeros(cap, dtype=f.dataType.device_dtype),
                   jnpmod.zeros(cap, dtype=bool),
                   EMPTY_DICT if isinstance(f.dataType, StringType) else None)
            for f in schema.fields[:nl]]
        cols = left_cols + build_rows.columns
        return ColumnarBatch(schema, cols, r.out_mask, num_rows=None)

    def fused_members(self) -> list:
        """FuseStages probe-splice mapping for obs/ re-attribution: the
        probe-side pipeline shares this join's probe dispatch."""
        if self.probe_fusion is None:
            return []
        from ..obs.metrics import pipeline_member_names

        filters, outputs = self.probe_fusion
        return pipeline_member_names(filters, outputs) + [
            f"HashJoin[{self.join_type}] probe"]

    def simple_string(self):
        k = ", ".join(f"{l.name}={r.name}"
                      for l, r in zip(self.left_keys, self.right_keys))
        b = "Broadcast" if self.is_broadcast else "Shuffled"
        s = f"{b}HashJoin[{self.join_type}]({k})"
        if self.probe_fusion is not None:
            filters, outputs = self.probe_fusion
            o = ", ".join(x.simple_string() for x in outputs)
            s += f" FUSED-PROBE[{o}]"
            if filters:
                s += " WHERE " + " AND ".join(x.simple_string()
                                              for x in filters)
        return s


class NestedLoopJoinExec(PhysicalPlan):
    """Cartesian product + optional condition (role of
    BroadcastNestedLoopJoinExec / CartesianProductExec). Build side (right)
    is broadcast."""

    child_fields = ("left", "right")

    def __init__(self, condition: Expression | None, join_type: str,
                 left: PhysicalPlan, right: PhysicalPlan):
        if join_type not in ("inner", "cross", "left_semi", "left_anti",
                             "left_outer"):
            raise UnsupportedOperationError(
                f"nested-loop {join_type} join not supported yet")
        self.condition = condition
        self.join_type = join_type
        self.left = left
        self.right = right
        self._cond_pipeline: ExprPipeline | None = None

    @property
    def output(self):
        if self.join_type in ("left_semi", "left_anti"):
            return list(self.left.output)
        return self.left.output + self.right.output

    def required_child_distribution(self):
        return [UnspecifiedDistribution(), BroadcastDistribution()]

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from ..ops.joining import cross_join

        jnp = _jnp()
        left_parts = self.left.execute(ctx)
        build = self.right.execute(ctx)[0]
        rschema = attrs_schema(self.right.output)
        lschema = attrs_schema(self.left.output)
        bbatch = concat_batches(build, rschema) if build \
            else ColumnarBatch.empty(rschema)
        nb = bbatch.num_rows()
        pair_attrs = list(self.left.output) + list(self.right.output)
        pair_schema = attrs_schema(pair_attrs)
        semi_anti = self.join_type in ("left_semi", "left_anti")

        cond_pipe = None
        if self.condition is not None:
            cond_pipe = ExprPipeline(pair_attrs, [self.condition],
                                     pair_attrs, pair_schema)

        out = []
        for part in left_parts:
            obatches = []
            for pb in (part or [ColumnarBatch.empty(lschema)]):
                # both counts are the host's, so the product is the
                # pairs' exact number: the capacity holds them, and no
                # verdict is read back (a read waits for every kernel
                # queued before it: thirteen such waits a TPC-DS q28)
                np_rows = pb.num_rows()
                pairs = np_rows * nb
                r = cross_join(pb.row_mask, bbatch.row_mask,
                               bucket_capacity(max(pairs, 1)))
                probe_out = gather_batch(pb, r.probe_idx, r.out_mask)
                build_out = gather_batch(bbatch, r.build_idx, r.out_mask)
                joined = ColumnarBatch(pair_schema,
                                       probe_out.columns + build_out.columns,
                                       r.out_mask, num_rows=pairs)
                if cond_pipe is not None:
                    joined = cond_pipe.run(joined)
                if semi_anti:
                    # fold pair matches back onto probe rows: a probe row
                    # matches iff ANY surviving pair points at it
                    matched = jnp.zeros(pb.capacity, bool) \
                        .at[r.probe_idx].max(joined.row_mask)
                    keep = pb.row_mask & (
                        matched if self.join_type == "left_semi"
                        else ~matched)
                    obatches.append(ColumnarBatch(
                        pb.schema, pb.columns, keep, num_rows=None))
                elif self.join_type == "left_outer":
                    obatches.append(joined)
                    # null-extend unmatched probe rows as a second batch
                    matched = jnp.zeros(pb.capacity, bool) \
                        .at[r.probe_idx].max(joined.row_mask)
                    from ..columnar.batch import EMPTY_DICT
                    from ..types import dict_encoded

                    null_cols = []
                    for f in rschema.fields:
                        null_cols.append(Column(
                            f.dataType,
                            jnp.zeros(pb.capacity, f.dataType.device_dtype),
                            jnp.zeros(pb.capacity, bool),
                            EMPTY_DICT if dict_encoded(f.dataType)
                            else None))
                    obatches.append(ColumnarBatch(
                        pair_schema, list(pb.columns) + null_cols,
                        pb.row_mask & ~matched, num_rows=None))
                else:
                    obatches.append(joined)
            out.append(obatches)
        return out


# ---------------------------------------------------------------------------
# Union / Coalesce
# ---------------------------------------------------------------------------

class SampleExec(PhysicalPlan):
    """Bernoulli sampling via a hash of the row's global position —
    deterministic for a given seed (role of BasicOperators' SampleExec)."""

    child_fields = ("child",)

    def __init__(self, fraction: float, seed: int, child: PhysicalPlan):
        self.fraction = fraction
        self.seed = seed
        self.child = child

    @property
    def output(self):
        return self.child.output

    def execute(self, ctx: ExecContext) -> list[Partition]:
        from ..ops.hashing import mix64

        jnp = _jnp()
        threshold = int(self.fraction * (1 << 30))
        out = []
        for pi, part in enumerate(self.child.execute(ctx)):
            obatches = []
            for bi, b in enumerate(part):
                cap = b.capacity
                # the per-(partition,batch) global position base is a
                # KERNEL INPUT, not part of the cache key: one compiled
                # kernel per capacity bucket serves every batch position
                # (keying by (pi, bi) compiled a kernel per batch — the
                # recompile storm plan_lint/ROADMAP flagged)
                key = ("sample", cap, self.seed, threshold)

                def build():
                    def kernel(mask, base):
                        pos = jnp.arange(cap, dtype=jnp.int64) + base
                        h = mix64(pos + self.seed)
                        keep = (h.view(jnp.uint64) >> jnp.uint64(34)) \
                            .astype(jnp.int64) < threshold
                        return mask & keep

                    return stage_jit(kernel)

                kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
                base = jnp.int64((pi << 40) + (bi << 28))
                obatches.append(ColumnarBatch(
                    b.schema, b.columns, kernel(b.row_mask, base),
                    num_rows=None))
            out.append(obatches)
        return out


class UnionExec(PhysicalPlan):
    child_fields = ("children_plans",)

    def __init__(self, children_plans: Sequence[PhysicalPlan],
                 attrs: list[AttributeReference]):
        self.children_plans = list(children_plans)
        self.attrs = attrs

    @property
    def output(self):
        return self.attrs

    def output_partitioning(self):
        n = sum(c.output_partitioning().num_partitions
                for c in self.children_plans)
        return UnknownPartitioning(n)

    def execute(self, ctx: ExecContext) -> list[Partition]:
        out: list[Partition] = []
        schema = attrs_schema(self.attrs)
        for c in self.children_plans:
            for part in c.execute(ctx):
                # rewrap batches under union output schema (names may differ)
                out.append([ColumnarBatch(schema, b.columns, b.row_mask,
                                          b._num_rows) for b in part])
        return out


class CoalescePartitionsExec(PhysicalPlan):
    child_fields = ("child",)

    def __init__(self, num_partitions: int, child: PhysicalPlan):
        self.num_partitions = max(1, num_partitions)
        self.child = child

    @property
    def output(self):
        return self.child.output

    def output_partitioning(self):
        if self.num_partitions == 1:
            return SinglePartition()
        return UnknownPartitioning(self.num_partitions)

    def execute(self, ctx: ExecContext) -> list[Partition]:
        parts = self.child.execute(ctx)
        n = self.num_partitions
        out: list[Partition] = [[] for _ in range(min(n, max(len(parts), 1)))]
        for i, p in enumerate(parts):
            out[i % len(out)].extend(p)
        return out
