"""Exchange operators.

Role of the reference's ShuffleExchangeExec (sqlx/exchange/
ShuffleExchangeExec.scala:190) and BroadcastExchangeExec (:61
relationFuture + torrent broadcast). Broadcast here is a replicated
concatenated batch (on a mesh: an ICI all-gather — SURVEY.md §2.5).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..columnar.batch import ColumnarBatch
from ..columnar.ops import concat_batches
from ..errors import UnsupportedOperationError
from ..exec import shuffle as S
from ..exec.context import ExecContext
from ..expr.expressions import AttributeReference, SortOrder
from ..types import StringType
from .operators import PhysicalPlan, attrs_schema
from .partitioning import (
    BroadcastPartitioning, HashPartitioning, Partitioning, RangePartitioning,
    SinglePartition, UnknownPartitioning,
)


class ShuffleExchangeExec(PhysicalPlan):
    child_fields = ("child",)

    def __init__(self, partitioning: Partitioning, child: PhysicalPlan):
        self.partitioning = partitioning
        self.child = child
        self.last_stats: dict[int, int] = {}
        # map-side per-reduce-partition integral column stats (satellite
        # of the fused write: seeds the dense-range memo locally and
        # rides the MapStatus payload in cluster mode)
        self.last_col_stats: dict[int, dict] = {}
        # set by FuseStages (physical/fusion.py): (filters, outputs) of
        # the producing pipeline traced into the partition-id kernel
        self.pipe_fusion: tuple | None = None
        self.pipe_attrs: list | None = None
        # output column positions whose min/max the map-side write
        # accumulates (annotate_exchange_stat_cols: only plan-reachable
        # dense candidates); None = every integral column (bare plans)
        self.stat_cols: list | None = None
        # runtime join filter (physical/adaptive.install_runtime_filters):
        # a materialized build side's key domain, applied to map batches
        # before they are shuffled — whole-batch skip via the seeded
        # dense-range memo, row-level pruning inside the fused map kernel
        self.runtime_filter: dict | None = None

    @property
    def output(self):
        if self.pipe_attrs is not None:
            return self.pipe_attrs
        return self.child.output

    def output_partitioning(self):
        return self.partitioning

    def fused_members(self) -> list:
        """FuseStages mapping for obs/ dispatch re-attribution: the
        pipeline members share this exchange's single map-side dispatch
        per batch (the partition-id kernel rides the same program)."""
        if self.pipe_fusion is None:
            return []
        from ..obs.metrics import pipeline_member_names

        filters, outputs = self.pipe_fusion
        return pipeline_member_names(filters, outputs) + [
            f"Exchange[{type(self.partitioning).__name__}] partition-ids"]

    def _fusion(self):
        """Fresh ExchangeFusion per execute (it carries the partitioning
        binding); the jitted kernels live in the global KernelCache, so
        rebuilding the binder costs no compile."""
        from .fusion import ExchangeFusion

        filters, outputs = self.pipe_fusion
        return ExchangeFusion(filters, outputs, self.child.output)

    def execute(self, ctx: ExecContext) -> list:
        parts = self.child.execute(ctx)
        if self.runtime_filter is not None:
            parts = self._runtime_filter_skip(parts, ctx)
        schema = attrs_schema(self.output)
        p = self.partitioning
        # cleared IN PLACE: stage-builder/AQE copies share this node's
        # __dict__ values (TreeNode.copy), so mutating the same dicts
        # keeps runtime stats visible on the pre-copy plan the user
        # inspects (EXPLAIN, tests); rebinding would strand them on the
        # executing copy
        self.last_stats.clear()
        self.last_col_stats.clear()
        fusion = self._fusion() if self.pipe_fusion is not None else None
        with ctx.metrics.time("shuffle"):
            if isinstance(p, SinglePartition):
                with self._span(ctx, "exchange.gather", p):
                    return S.gather_single(parts, ctx)
            if isinstance(p, HashPartitioning):
                pos = {a.expr_id: i for i, a in enumerate(self.output)}
                key_positions = []
                for e in p.exprs:
                    assert isinstance(e, AttributeReference), \
                        "exchange keys must be attributes (planner contract)"
                    key_positions.append(pos[e.expr_id])
                from ..parallel import mesh_exchange as ME

                mesh = ME.mesh_for(p.num_partitions, ctx.conf, schema)
                if mesh is not None:
                    # the whole stage — pipeline, partition ids,
                    # all-to-all — is ONE SPMD dispatch per step when the
                    # map side is fused (spark.tpu.fusion.mesh); the
                    # legacy materialize-then-collective composition sits
                    # behind that flag
                    if self.runtime_filter is not None:
                        # the mesh program stages whole host arrays, so
                        # the filter cannot ride it as aux operands —
                        # prune rows per batch BEFORE staging (one tiny
                        # mask dispatch each; fewer live rows also eases
                        # the quota ladder)
                        parts = self._runtime_filter_rows(parts, ctx)
                    with self._span(ctx, "exchange.mesh_all_to_all", p):
                        return ME.mesh_shuffle_hash(
                            parts, key_positions, p.num_partitions, schema,
                            ctx, self.last_stats, mesh,
                            fusion=None if fusion is None else
                            fusion.bind_hash(key_positions,
                                             p.num_partitions),
                            col_stats=self.last_col_stats,
                            stat_cols=self.stat_cols)
                with self._span(ctx, "exchange.hash", p):
                    if fusion is not None:
                        bound = fusion.bind_hash(key_positions,
                                                 p.num_partitions)
                        if self.runtime_filter is not None:
                            # row-level pruning rides the SAME fused map
                            # kernel as aux operands — no extra dispatch
                            bound.bind_runtime_filter(self.runtime_filter)
                        out = S.shuffle_fused(
                            parts, bound,
                            p.num_partitions, schema, ctx, self.last_stats,
                            self.last_col_stats, self.stat_cols)
                        if fusion.rf_pruned:
                            ctx.metrics.add("adaptive.filter_rows_pruned",
                                            fusion.rf_pruned)
                        return out
                    return S.shuffle_hash(parts, key_positions,
                                          p.num_partitions, schema, ctx,
                                          self.last_stats,
                                          col_stats=self.last_col_stats,
                                          stat_cols=self.stat_cols)
            if isinstance(p, RangePartitioning):
                with self._span(ctx, "exchange.range", p):
                    return self._range_shuffle(parts, p, schema, ctx,
                                               fusion)
            if isinstance(p, UnknownPartitioning):
                with self._span(ctx, "exchange.round_robin", p):
                    if fusion is not None:
                        return S.shuffle_fused(
                            parts, fusion.bind_rr(p.num_partitions),
                            p.num_partitions, schema, ctx, self.last_stats,
                            self.last_col_stats, self.stat_cols)
                    return S.shuffle_round_robin(
                        parts, p.num_partitions, schema, ctx,
                        self.last_stats, col_stats=self.last_col_stats,
                        stat_cols=self.stat_cols)
        raise UnsupportedOperationError(f"exchange for {p}")

    def _runtime_filter_skip(self, parts: list, ctx: ExecContext) -> list:
        """Whole-batch pruning against the build-side key domain using
        ONLY already-synced state: the seeded dense-range memo for
        integral keys (peek — a miss never computes) and the host-side
        StringDict code domain for encoded string keys. A batch whose
        key range/domain misses the build domain cannot produce a join
        match and never enters the shuffle. Zero kernels, zero syncs."""
        rf = self.runtime_filter
        cp = rf.get("child_pos")
        if cp is None:
            return parts    # computed key: no pre-pipeline column
        from ..utils.device_memo import peek_dense_range

        kind = rf["kind"]
        kept, skipped = [], 0
        for part in parts:
            keep_part = []
            for b in part:
                drop = False
                col = b.columns[cp]
                if kind == "range":
                    hit = peek_dense_range(col, b.row_mask)
                    if hit is not None:
                        kmin, kmax, any_live = hit
                        drop = (not any_live) or kmax < rf["lo"] \
                            or kmin > rf["hi"]
                else:
                    d = col.dictionary
                    if d is not None:
                        dom = rf["domain"]
                        drop = not any(v in dom for v in d.values)
                if drop:
                    skipped += 1
                else:
                    keep_part.append(b)
            kept.append(keep_part)
        if skipped:
            ctx.metrics.add("adaptive.filter_batches_skipped", skipped)
        return kept

    def _runtime_filter_rows(self, parts: list, ctx: ExecContext) -> list:
        """Row-level pruning ahead of the mesh path: batches are the
        CHILD's output here (any map pipeline runs inside the mesh
        program), so the filter applies at the pre-pipeline key position.
        One shared mask-update kernel per batch (physical/fusion.
        runtime_filter_batch)."""
        from .fusion import runtime_filter_batch

        rf = self.runtime_filter
        cp = rf.get("child_pos")
        if cp is None:
            return parts    # computed key: no pre-pipeline column
        pruned = 0
        out = []
        for part in parts:
            new_part = []
            for b in part:
                nb, drop = runtime_filter_batch(rf, None, b, cp)
                pruned += drop
                new_part.append(nb)
            out.append(new_part)
        if pruned:
            ctx.metrics.add("adaptive.filter_rows_pruned", pruned)
        return out

    @staticmethod
    def _span(ctx, name: str, p):
        """Shuffle-kind span INSIDE the operator span, so the trace
        timeline separates redistribution work from child execution (the
        shuffle write/read lane of the reference's stage timeline)."""
        tracer = getattr(ctx, "tracer", None)
        if tracer is None:
            from contextlib import nullcontext

            return nullcontext()
        return tracer.span(name, cat="exchange",
                           args={"partitions": p.num_partitions})

    def _range_shuffle(self, parts, p: RangePartitioning, schema, ctx,
                       fusion=None):
        order = p.orders[0]
        pos = {a.expr_id: i for i, a in enumerate(self.output)}
        assert isinstance(order.child, AttributeReference)
        kpos = pos[order.child.expr_id]
        if fusion is not None:
            # bounds sample the POST-pipeline key column: the pipeline
            # materializes for ≤3 sampled batches per partition — spread
            # first/middle/last so ordered domains (range scans) are
            # covered end to end — and selective filters no longer skew
            # partition balance; COMPUTED sort keys fuse too (the
            # pre-pipeline input-column sampling was a pre-filter
            # superset — sound but uneven, and it required a
            # pass-through key)
            def picks(part):
                if len(part) <= 3:
                    return list(part)
                return [part[0], part[len(part) // 2], part[-1]]

            sample_parts = [[fusion.run_pipeline(b) for b in picks(part)]
                            for part in parts]
            bounds = _sample_bounds(sample_parts, kpos, schema,
                                    p.num_partitions, all_batches=True)
            if bounds is None or len(bounds) == 0:
                return S.gather_single(
                    [[fusion.run_pipeline(b) for b in part]
                     for part in parts], ctx)
            return S.shuffle_fused(
                parts,
                fusion.bind_range(kpos, bounds, not order.ascending,
                                  p.num_partitions),
                p.num_partitions, schema, ctx, self.last_stats,
                self.last_col_stats, self.stat_cols)
        bounds = _sample_bounds(parts, kpos, schema, p.num_partitions)
        if bounds is None or len(bounds) == 0:
            return S.gather_single(parts, ctx)
        return S.shuffle_range(parts, kpos, bounds, not order.ascending,
                               p.num_partitions, schema, ctx,
                               self.last_stats,
                               col_stats=self.last_col_stats,
                               stat_cols=self.stat_cols)

    def simple_string(self):
        s = f"Exchange[{type(self.partitioning).__name__}" \
            f"({self.partitioning.num_partitions})]"
        if self.runtime_filter is not None:
            s += f" RUNTIME-FILTER[{self.runtime_filter['kind']}]"
        if self.pipe_fusion is not None:
            filters, outputs = self.pipe_fusion
            o = ", ".join(x.simple_string() for x in outputs)
            s += f" FUSED-MAP[{o}]"
            if filters:
                s += " WHERE " + " AND ".join(x.simple_string()
                                              for x in filters)
        return s


def _batch_key_samples(batch: ColumnarBatch, kpos: int, f,
                       per_part_sample: int) -> tuple:
    """Up to `per_part_sample` live non-null key values of one batch as an
    immutable tuple. The device→host pull is memoized per (data, validity,
    mask) identity (utils/device_memo.memo_device_scalars): repeated
    range exchanges over device-cached scan batches sync once, not once
    per batch per query."""
    from ..utils.device_memo import device_read, memo_device_scalars

    col = batch.columns[kpos]

    def compute():
        mask, data, valid = device_read("exchange.sample", batch.row_mask,
                                        col.data, col.validity)
        mask = np.asarray(mask, dtype=bool)
        if isinstance(f.dataType, StringType):
            vals = col.to_numpy(np.nonzero(mask)[0][:per_part_sample])
            return tuple(v for v in vals if v is not None)
        data = data[mask][:per_part_sample]
        if valid is not None:
            data = data[valid[mask][:per_part_sample][: len(data)]]
        return tuple(data.tolist())

    return memo_device_scalars(
        ("range_sample", kpos, per_part_sample, str(f.dataType)),
        (col.data, col.validity, batch.row_mask), compute)


def _sample_bounds(parts, kpos: int, schema, num_out: int,
                   per_part_sample: int = 4096,
                   all_batches: bool = False):
    """Sample the sort key to derive range bounds (role of the reference's
    RangePartitioner sampling job, core/Partitioner.scala:388).
    `all_batches` samples every batch handed in — the fused exchange
    pre-selects a spread of materialized pipeline outputs instead of
    relying on the first-2 heuristic."""
    f = schema.fields[kpos]
    samples = []
    for part in parts:
        for batch in (part if all_batches else part[:2]):
            samples.extend(_batch_key_samples(batch, kpos, f,
                                              per_part_sample))
    if not samples:
        return None
    if isinstance(f.dataType, StringType):
        s = sorted(set(samples))
    else:
        # host math over already-pulled (memoized) sample tuples
        s = np.unique(np.asarray(samples))  # tpulint: ignore[host-sync]
    if len(s) <= 1:
        return None
    qs = [int(round(i * (len(s) - 1) / num_out))  # tpulint: ignore[host-sync]
          for i in range(1, num_out)]
    if isinstance(f.dataType, StringType):
        bounds = sorted(set(s[q] for q in qs))
    else:
        bounds = np.unique(s[qs])
    return bounds


def dense_stat_candidate_ids(plan: PhysicalPlan) -> set:
    """Expr ids whose value RANGE some downstream dense decision can
    consult: the single integral/date grouping key of a hash aggregate
    (dense-scatter vs sorted-segment, operators._try_dense and
    fusion._dense_decision) and the single integral/date keys of a hash
    join (dense direct-address build, operators._try_dense_build; both
    sides listed — AQE may re-side the build). Pass-through projections
    preserve expr ids, so membership at an exchange's output is exactly
    'a consumer above can read this column's range'. Aliased/computed
    keys produce FRESH device arrays whose identity the memo can never
    hit, so excluding them loses nothing."""
    from ..types import DateType, IntegralType
    from .operators import HashAggregateExec, HashJoinExec

    def single_int(keys) -> bool:
        return len(keys) == 1 and isinstance(
            keys[0].dtype, (IntegralType, DateType))

    out: set = set()
    for node in plan.iter_nodes():
        if isinstance(node, HashAggregateExec):  # FusedAggregate too
            if single_int(node.grouping):
                out.add(node.grouping[0].expr_id)
        if isinstance(node, HashJoinExec):
            for keys in (node.left_keys, node.right_keys):
                if single_int(keys):
                    out.add(keys[0].expr_id)
    return out


def annotate_exchange_stat_cols(plan: PhysicalPlan) -> None:
    """Restrict every shuffle exchange's map-side stat accumulation
    (exec/shuffle._OutBuffer) to plan-reachable dense candidates: the
    historical behavior ran host min/max over EVERY integral column per
    appended slice even when no downstream consumer makes a dense
    decision. Idempotent; runs at plan time (Planner.plan) so the
    annotation rides stage-builder copies (shared __dict__) and
    cloudpickle into cluster map tasks, and the plan analyzer reads the
    SAME annotation for its krange3 launch model."""
    exchanges = [n for n in plan.iter_nodes()
                 if isinstance(n, ShuffleExchangeExec)]
    # planner-annotated plans reach execute() already done (stat_cols
    # defaults to None until annotated) — skip the candidate recompute;
    # any exchange an adaptive rewrite introduced un-annotated re-runs it
    if all(n.stat_cols is not None for n in exchanges):
        return
    cands = dense_stat_candidate_ids(plan)
    for node in exchanges:
        node.stat_cols = [
            i for i, a in enumerate(node.output)
            if a.expr_id in cands]


class BroadcastExchangeExec(PhysicalPlan):
    child_fields = ("child",)

    def __init__(self, child: PhysicalPlan):
        self.child = child

    @property
    def output(self):
        return self.child.output

    def output_partitioning(self):
        return BroadcastPartitioning()

    def execute(self, ctx: ExecContext) -> list:
        parts = self.child.execute(ctx)
        merged = []
        for p in parts:
            merged.extend(p)
        schema = attrs_schema(self.output)
        if not merged:
            return [[ColumnarBatch.empty(schema)]]
        with S.host_exchange(ctx, "broadcast", 1):
            batch = concat_batches(merged, schema)
        if batch._num_rows is not None:
            # counted where the host has the count: nothing reads this
            # metric, and reading a count off the device waits for every
            # kernel queued before it
            ctx.metrics.add("broadcast.rows", batch._num_rows)
        return [[batch]]

    def simple_string(self):
        return "BroadcastExchange"
