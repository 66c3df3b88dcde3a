"""Whole-stage kernel fusion: one XLA dispatch per batch per stage.

Role of the reference's WholeStageCodegen (sqlx/WholeStageCodegenExec.scala:673
doCodeGen + CollapseCodegenStages): Spark splices produce/consume Java code so
a stage's operators run as one loop; here the splice is a TRACE — the
filter/project pipeline body (physical/compile.trace_pipeline) is traced
inside the terminal operator's kernel (partial hash aggregate, hash-join
probe, limit mask) and `jax.jit` compiles the whole stage consume as ONE
program per (structure, input signature, capacity), cached in the
structurally-keyed GLOBAL_KERNEL_CACHE. XLA then performs the operator
fusion the reference hand-rolls.

`FuseStages` runs after stage-boundary insertion (exchanges are already
placed), so each rewrite stays inside one exchange-free chain:

  * ComputeExec(ComputeExec)              -> one ComputeExec (CollapseProject
    /CollapseCodegenStages analog; the substitution is shared with the
    planner's construction-time fusion)
  * HashAggregateExec[partial](ComputeExec) -> FusedAggregateExec
  * LimitExec(ComputeExec)                -> FusedLimitExec
  * HashJoinExec(left=ComputeExec)        -> probe pipeline spliced into the
    probe kernel (operators.HashJoinExec._fused_probe)

The unfused operator-at-a-time path stays intact behind
spark.tpu.fusion.enabled=false as the differential-testing oracle.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..config import (
    ENCODING_ENABLED, FUSION_DENSE_KEYS, FUSION_EXCHANGE, FUSION_MIN_ROWS,
    SQLConf,
)
from ..expr.expressions import Alias, AttributeReference, Expression
from ..types import (
    BooleanType, DateType, IntegralType, StringType, dict_encoded,
)
from ..columnar.batch import Column, ColumnarBatch, bucket_capacity
from ..obs.metrics import batch_cost_scope
from ..utils.device_memo import device_read
from .aggregates import FUSABLE_OPS
from .compile import (
    GLOBAL_KERNEL_CACHE, bind_inputs, canonical_key, pipeline_columns,
    pipeline_host_pass, pipeline_signature, stage_jit, trace_pipeline,
)
from .operators import (
    ComputeExec, HashAggregateExec, HashJoinExec, LimitExec, PhysicalPlan,
    _SchemaOnly, attrs_schema, dense_range_stats,
)

__all__ = ["FusedAggregateExec", "FusedLimitExec", "ExchangeFusion",
           "fuse_stages", "collapse_computes", "merge_into_compute"]


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# ComputeExec collapsing (shared with the planner's construction-time path)
# ---------------------------------------------------------------------------

def merge_into_compute(filters: Sequence[Expression],
                       outputs: Sequence[Expression],
                       child: ComputeExec) -> ComputeExec:
    """Fuse a filter/project layer into an existing ComputeExec child by
    substituting the child's output expressions (the CollapseCodegenStages
    analog; all expressions are deterministic and XLA CSEs duplicated
    subtrees, so inlining is always sound)."""
    from ..plan.optimizer import substitute_attrs

    m: dict[int, Expression] = {}
    for e in child.outputs:
        if isinstance(e, Alias):
            m[e.expr_id] = e.child
        elif isinstance(e, AttributeReference):
            m[e.expr_id] = e
    new_filters = [substitute_attrs(f, m) for f in filters]
    new_outputs: list[Expression] = []
    for o in outputs:
        if isinstance(o, Alias):
            new_outputs.append(
                Alias(substitute_attrs(o.child, m), o.name, o.expr_id))
            continue
        sub = m.get(o.expr_id)
        if sub is None or (isinstance(sub, AttributeReference)
                           and sub.expr_id == o.expr_id):
            new_outputs.append(o)
        else:
            new_outputs.append(Alias(sub, o.name, o.expr_id))
    return ComputeExec(child.filters + new_filters, new_outputs, child.child)


def collapse_computes(plan: PhysicalPlan) -> PhysicalPlan:
    """Collapse adjacent ComputeExec nodes anywhere in the physical tree —
    a ComputeExec over a ComputeExec would launch two kernels per batch."""

    def rule(node):
        if isinstance(node, ComputeExec) and isinstance(node.child,
                                                        ComputeExec):
            return merge_into_compute(node.filters, node.outputs, node.child)
        return node

    return plan.transform_up(rule)


# ---------------------------------------------------------------------------
# Shared fused-kernel plumbing
# ---------------------------------------------------------------------------

def _pipe_attrs(outputs: Sequence[Expression]) -> list[AttributeReference]:
    return [o.to_attribute() if isinstance(o, Alias) else o for o in outputs]


def _compute_nontrivial(c: ComputeExec) -> bool:
    """A pure column reorder/prune launches no kernel — nothing to fuse."""
    return bool(c.filters) or any(not isinstance(o, AttributeReference)
                                  for o in c.outputs)


# ---------------------------------------------------------------------------
# FusedAggregateExec
# ---------------------------------------------------------------------------

class FusedAggregateExec(HashAggregateExec):
    """Partial hash aggregate with its feeding filter/project pipeline
    traced into the aggregation kernel: per input batch, ONE jitted program
    filters, projects, and partially aggregates (dense-range scatter,
    sorted-segment, or whole-tile reduce). Per-batch partials then merge
    with the associative final-mode ops — dispatches across batches and
    partitions pipeline asynchronously with no host sync in between (the
    dense-range range decision is memoized per column identity)."""

    child_fields = ("child",)

    def __init__(self, grouping, specs, filters, outputs, child):
        super().__init__(grouping, specs, "partial", child)
        self.filters = list(filters)
        self.pipe_outputs = list(outputs)
        self.pipe_attrs = _pipe_attrs(self.pipe_outputs)
        self._unfused_cache = None
        id_to_pos = bind_inputs(child.output)
        self._struct_key = (
            tuple(canonical_key(f, id_to_pos) for f in self.filters),
            tuple(canonical_key(o, id_to_pos) for o in self.pipe_outputs),
        )

    def graph_name(self) -> str:
        # the plan graph groups by operator role (the reference renders the
        # aggregate node inside its WholeStageCodegen cluster)
        return "HashAggregateExec"

    def fused_members(self) -> list:
        """The FuseStages mapping, inverted: constituent operators whose
        work rides this node's single dispatch per batch (obs/ EXPLAIN
        ANALYZE re-attributes the fused launch to these)."""
        from ..obs.metrics import pipeline_member_names

        return pipeline_member_names(self.filters, self.pipe_outputs) + [
            "HashAggregate[partial](keys=[%s])"
            % ", ".join(a.name for a in self.grouping)]

    def execute(self, ctx) -> list:
        parts = self.child.execute(ctx)
        return ctx.par_map(
            lambda part: [self._fused_partition(part, ctx)], parts)

    # ------------------------------------------------------------------
    def _unfused(self):
        """Operator-at-a-time fallback for partitions under
        spark.tpu.fusion.minRows: the shared (structure-agnostic) agg
        kernels beat a fresh per-structure fused compile on small inputs."""
        if self._unfused_cache is None:
            from .compile import ExprPipeline

            pipe = ExprPipeline(self.child.output, self.filters,
                                self.pipe_outputs,
                                attrs_schema(self.pipe_attrs))
            inner = HashAggregateExec(self.grouping, self.specs, "partial",
                                      _SchemaOnly(self.pipe_attrs))
            self._unfused_cache = (pipe, inner)
        return self._unfused_cache

    def _fused_partition(self, part, ctx) -> ColumnarBatch:
        if not part:
            part = [ColumnarBatch.empty(attrs_schema(self.child.output))]
        if sum(b.capacity for b in part) < int(ctx.conf.get(FUSION_MIN_ROWS)):
            pipe, inner = self._unfused()
            return inner._aggregate_partition(
                [pipe.run(b) for b in part], ctx)
        partials = [self._fused_batch(b, ctx) for b in part]
        if len(partials) == 1:
            return partials[0]
        merger = HashAggregateExec(self.grouping, self.specs, "final",
                                   _SchemaOnly(self.output))
        return merger._aggregate_partition(partials, ctx)

    def _fused_batch(self, batch: ColumnarBatch, ctx) -> ColumnarBatch:
        import jax

        from ..columnar.batch import EMPTY_DICT

        jnp = _jnp()
        cap = batch.capacity
        input_attrs = self.child.output
        filters, outputs = self.filters, self.pipe_outputs
        hctx, host_outs, aux = pipeline_host_pass(input_attrs, filters,
                                                  outputs, batch)
        opos = {a.expr_id: i for i, a in enumerate(self.pipe_attrs)}
        vals = self._plan_values()
        ops = tuple(op for op, _, _ in vals)
        val_idx = tuple(opos[attr.expr_id] if attr is not None else -1
                        for _, attr, _ in vals)
        key_idx = tuple(opos[g.expr_id] for g in self.grouping)
        out_schema = attrs_schema(self.output)
        # string MIN/MAX reduces in RANK space inside the trace: the
        # rank lut (codes→lexicographic rank) and its inverse (winning
        # rank→code) ride as kernel aux inputs, so the whole aggregate
        # stays in the single fused dispatch (no unfused fallback)
        smm_idx = tuple(bi for bi, (op, attr, _p) in enumerate(vals)
                        if op in ("min", "max") and attr is not None
                        and dict_encoded(attr.dtype))
        smm_dicts = [host_outs[val_idx[bi]].sdict or EMPTY_DICT
                     for bi in smm_idx]
        rank_luts = [sd.device_ranks() for sd in smm_dicts]
        inv_luts = [sd.device_rank_to_code() for sd in smm_dicts]
        base_key = (self._struct_key, ops, val_idx, key_idx, cap,
                    smm_idx, tuple(int(r.shape[0]) for r in rank_luts),
                    pipeline_signature(batch), hctx.signature())
        datas = [c.data for c in batch.columns]
        valids = [c.validity for c in batch.columns]
        smm_pos = {bi: j for j, bi in enumerate(smm_idx)}

        def pipe_vals(out_datas, out_valids, mask, rluts):
            vd = []
            for bi, i in enumerate(val_idx):
                d = out_datas[i] if i >= 0 else mask
                if bi in smm_pos:
                    r = rluts[smm_pos[bi]]
                    d = jnp.take(r, jnp.clip(d.astype(jnp.int32), 0,
                                             r.shape[0] - 1))
                vd.append(d)
            vv = [out_valids[i] if i >= 0 else None for i in val_idx]
            return vd, vv

        def rank_to_code(bufs, iluts):
            """Map winning ranks of string min/max buffers back to codes
            (inside the trace; masked/empty groups clip harmlessly — their
            validity is already False)."""
            out = []
            for bi, (bd, bv) in enumerate(bufs):
                if bi in smm_pos:
                    inv = iluts[smm_pos[bi]]
                    bd = jnp.take(inv, jnp.clip(bd.astype(jnp.int32), 0,
                                                inv.shape[0] - 1))
                out.append((bd, bv))
            return out

        # ---- ungrouped -------------------------------------------------
        if not self.grouping:
            out_cap = 8

            def build_ungrouped():
                from ..ops import grouping as G

                def kernel(datas, valids, row_mask, aux, rluts, iluts):
                    out_datas, out_valids, mask = trace_pipeline(
                        input_attrs, filters, outputs, datas, valids,
                        row_mask, aux, cap)
                    vd, vv = pipe_vals(out_datas, out_valids, mask, rluts)
                    outs = G.apply_global_ops(ops, vd, vv, mask)
                    outs = rank_to_code(outs, iluts)
                    bufs_d, bufs_v = [], []
                    for d, v in outs:
                        bufs_d.append(jnp.zeros((out_cap,), dtype=d.dtype)
                                      .at[0].set(d))
                        bufs_v.append(None if v is None else
                                      jnp.zeros((out_cap,), dtype=bool)
                                      .at[0].set(v))
                    m = jnp.zeros((out_cap,), dtype=bool).at[0].set(True)
                    return bufs_d, bufs_v, m

                return stage_jit(kernel)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(
                ("fused_agg", "u") + base_key, build_ungrouped)
            with batch_cost_scope(batch):
                bufs_d, bufs_v, m = kernel(datas, valids, batch.row_mask,
                                           aux, rank_luts, inv_luts)
            cols = self._fused_cols(
                list(zip(bufs_d, bufs_v)), out_schema.fields, host_outs,
                val_idx, 0)
            return ColumnarBatch(out_schema, cols, m, num_rows=1)

        # ---- grouped: dense-range direct scatter -----------------------
        # dictionary-encoded single keys are ALWAYS dense candidates: the
        # int32 code domain is [0, len(dict)) with the span known from
        # the host pass's output dictionary — no range probe, no sync
        # (compressed execution; the dictionary decodes the output keys)
        dense = None
        key_dict = None
        if len(key_idx) == 1 and ctx.conf.get(FUSION_DENSE_KEYS) \
                and isinstance(self.pipe_attrs[key_idx[0]].dtype,
                               StringType):
            from ..columnar.encoding import encoding_enabled

            if encoding_enabled(ctx.conf):
                from ..columnar.batch import EMPTY_DICT as _ED

                sdk = host_outs[key_idx[0]].sdict or _ED
                if len(sdk) + 1 <= min(4 * cap, 1 << 23):
                    key_dict = sdk
                    dense = (0, bucket_capacity(len(sdk) + 1),
                             host_outs[key_idx[0]].validity is not None)
                    ctx.metrics.add("agg.dict_code_fast_path")
        if dense is None:
            dense = self._dense_decision(batch, key_idx, ctx)
        if dense is not None:
            kmin, out_cap, has_kv = dense
            kpos = key_idx[0]
            kf = out_schema.fields[0]
            kdt = kf.dataType.device_dtype

            def build_dense():
                from jax import lax

                from ..ops import grouping as G

                def kernel(datas, valids, row_mask, aux, kmin_s, rluts,
                           iluts):
                    out_datas, out_valids, mask = trace_pipeline(
                        input_attrs, filters, outputs, datas, valids,
                        row_mask, aux, cap)
                    key = out_datas[kpos].astype(jnp.int64)
                    kvalid = out_valids[kpos]
                    seg = (key - kmin_s).astype(jnp.int32)
                    if kvalid is not None:
                        seg = jnp.where(kvalid, seg, out_cap - 1)
                    seg = jnp.where(mask, seg, out_cap - 1)
                    present = jax.ops.segment_sum(
                        jnp.where(mask, 1, 0), seg, num_segments=out_cap)
                    if kvalid is not None:
                        null_rows = jnp.sum(
                            (mask & ~kvalid).astype(jnp.int64))
                    else:
                        null_rows = jnp.int64(0)
                    vd, vv = pipe_vals(out_datas, out_valids, mask, rluts)
                    bufs = G.apply_dense_ops(seg, out_cap, cap, ops, vd, vv,
                                             mask)
                    bufs = rank_to_code(bufs, iluts)
                    out_keys = (kmin_s +
                                lax.iota(jnp.int64, out_cap)).astype(kdt)
                    out_mask = (present > 0).at[out_cap - 1].set(
                        null_rows > 0)
                    key_validity = jnp.ones(out_cap, dtype=bool) \
                        .at[out_cap - 1].set(False)
                    return out_keys, key_validity, bufs, out_mask

                return stage_jit(kernel)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(
                ("fused_agg", "d", out_cap) + base_key, build_dense)
            with batch_cost_scope(batch):
                out_keys, key_validity, bufs, out_mask = kernel(
                    datas, valids, batch.row_mask, aux, jnp.int64(kmin),
                    rank_luts, inv_luts)
            ctx.metrics.add("agg.dense_fast_path")
            cols = [Column(kf.dataType, out_keys,
                           key_validity if has_kv else None, key_dict)]
            cols += self._fused_cols(bufs, out_schema.fields[1:], host_outs,
                                     val_idx, 0)
            return ColumnarBatch(out_schema, cols, out_mask, num_rows=None)

        # ---- grouped: sorted-segment -----------------------------------
        key_bool = tuple(isinstance(self.pipe_attrs[i].dtype, BooleanType)
                         for i in key_idx)

        def build_grouped():
            from ..ops import grouping as G

            def kernel(datas, valids, row_mask, aux, rluts, iluts):
                out_datas, out_valids, mask = trace_pipeline(
                    input_attrs, filters, outputs, datas, valids, row_mask,
                    aux, cap)
                key_eqs = []
                for i, is_bool in zip(key_idx, key_bool):
                    kd = out_datas[i]
                    if is_bool:
                        kd = kd.astype(jnp.int32)
                    key_eqs.append(kd)
                key_valids = [out_valids[i] for i in key_idx]
                vd, vv = pipe_vals(out_datas, out_valids, mask, rluts)
                # a key is its own output, but for a boolean's type
                out_keys, bufs, out_mask, _ng = G.group_aggregate(
                    key_eqs, key_valids,
                    [out_datas[i] if is_bool else None
                     for i, is_bool in zip(key_idx, key_bool)],
                    mask, ops, vd, vv)
                return out_keys, rank_to_code(bufs, iluts), out_mask

            return stage_jit(kernel)

        from ..ops.grouping import segment_path

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            ("fused_agg", "g", segment_path(cap)) + base_key, build_grouped)
        with batch_cost_scope(batch):
            out_keys, bufs, out_mask = kernel(datas, valids,
                                              batch.row_mask, aux,
                                              rank_luts, inv_luts)
        cols = []
        nk = len(key_idx)
        for (kd, kv), ki, f in zip(out_keys, key_idx,
                                   out_schema.fields[:nk]):
            sdict = host_outs[ki].sdict if dict_encoded(f.dataType) else None
            cols.append(Column(f.dataType, kd, kv, sdict))
        cols += self._fused_cols(bufs, out_schema.fields[nk:], host_outs,
                                 val_idx, nk)
        return ColumnarBatch(out_schema, cols, out_mask, num_rows=None)

    def _fused_cols(self, bufs, fields, host_outs, val_idx, key_count):
        """Finish buffer columns (dtype casts) and re-attach dictionaries of
        dict-encoded passthrough buffers (e.g. first(string): codes travel,
        the batch's dictionary decodes them)."""
        cols = []
        for bi, ((bd, bv), f) in enumerate(zip(bufs, fields)):
            col = self._finish_buffer(bi, bd, bv, f, {})
            if dict_encoded(f.dataType) and col.dictionary is None:
                vi = val_idx[bi]
                if vi >= 0 and host_outs[vi].sdict is not None:
                    col = Column(f.dataType, col.data, col.validity,
                                 host_outs[vi].sdict)
            cols.append(col)
        return cols

    def _dense_decision(self, batch: ColumnarBatch, key_idx, ctx):
        """(kmin, out_cap, key_has_validity) when the single grouping key is
        a pass-through integral input column whose value range (memoized per
        column identity — the satellite fix for the per-batch two-scalar
        host sync) fits a capacity bucket. The range is measured under the
        PRE-filter row mask: a superset of the post-filter range, so the
        dense table stays sound, merely (rarely) wider."""
        if len(key_idx) != 1:
            return None
        if not ctx.conf.get(FUSION_DENSE_KEYS):
            return None
        kexpr = self.pipe_outputs[key_idx[0]]
        if not isinstance(kexpr, AttributeReference):
            return None
        in_pos = None
        for i, a in enumerate(self.child.output):
            if a.expr_id == kexpr.expr_id:
                in_pos = i
                break
        if in_pos is None:
            return None
        kc = batch.columns[in_pos]
        if not isinstance(kc.dtype, (IntegralType, DateType)):
            return None
        cap = batch.capacity
        kmin, kmax, any_live = dense_range_stats(kc, batch.row_mask, cap)
        if not any_live:
            return None
        span = kmax - kmin + 1
        if span + 1 > min(4 * cap, 1 << 23):
            return None  # sparse keys — sort path handles it
        return kmin, bucket_capacity(span + 1), kc.validity is not None

    def simple_string(self):
        g = ", ".join(a.name for a in self.grouping)
        fns = ", ".join(type(s.func).__name__ for s in self.specs)
        f = " AND ".join(x.simple_string() for x in self.filters)
        s = f"FusedHashAggregate[partial](keys=[{g}], fns=[{fns}])"
        if f:
            s += f" WHERE {f}"
        return s


# ---------------------------------------------------------------------------
# FusedLimitExec
# ---------------------------------------------------------------------------

class FusedLimitExec(LimitExec):
    """Limit with its feeding filter/project pipeline traced into the limit
    kernel: one program per partition computes the pipeline, ranks live rows
    (cumsum), and masks past-limit rows."""

    child_fields = ("child",)

    def __init__(self, n, filters, outputs, child, offset: int = 0,
                 is_global: bool = False):
        super().__init__(n, child, offset=offset, is_global=is_global)
        self.filters = list(filters)
        self.pipe_outputs = list(outputs)
        self.pipe_attrs = _pipe_attrs(self.pipe_outputs)
        self._unfused_cache = None
        id_to_pos = bind_inputs(child.output)
        self._struct_key = (
            tuple(canonical_key(f, id_to_pos) for f in self.filters),
            tuple(canonical_key(o, id_to_pos) for o in self.pipe_outputs),
        )

    @property
    def output(self):
        return self.pipe_attrs

    def graph_name(self) -> str:
        return "LimitExec"

    def fused_members(self) -> list:
        """FuseStages mapping for obs/ dispatch re-attribution."""
        from ..obs.metrics import pipeline_member_names

        return pipeline_member_names(self.filters, self.pipe_outputs) + [
            f"Limit[n={self.n}]"]

    def execute(self, ctx) -> list:
        parts = self.child.execute(ctx)
        return ctx.par_map(lambda part: self._fused_partition(part, ctx),
                           parts)

    def _unfused(self):
        """Operator-at-a-time fallback under spark.tpu.fusion.minRows."""
        if self._unfused_cache is None:
            from .compile import ExprPipeline

            pipe = ExprPipeline(self.child.output, self.filters,
                                self.pipe_outputs,
                                attrs_schema(self.pipe_attrs))
            inner = LimitExec(self.n, _SchemaOnly(self.pipe_attrs),
                              offset=self.offset, is_global=self.is_global)
            self._unfused_cache = (pipe, inner)
        return self._unfused_cache

    def _fused_partition(self, part, ctx) -> list:
        from ..columnar.ops import concat_batches

        jnp = _jnp()
        if not part:
            return []
        if sum(b.capacity for b in part) < \
                int(ctx.conf.get(FUSION_MIN_ROWS)):  # tpulint: ignore[host-sync]
            pipe, inner = self._unfused()
            return inner._limit_partition([pipe.run(b) for b in part], ctx)
        batch = concat_batches(part, attrs_schema(self.child.output))
        cap = batch.capacity
        input_attrs = self.child.output
        filters, outputs = self.filters, self.pipe_outputs
        hctx, host_outs, aux = pipeline_host_pass(input_attrs, filters,
                                                  outputs, batch)
        key = ("fused_limit", self._struct_key, cap, self.n, self.offset,
               pipeline_signature(batch), hctx.signature())

        def build():
            def kernel(datas, valids, row_mask, aux):
                out_datas, out_valids, mask = trace_pipeline(
                    input_attrs, filters, outputs, datas, valids, row_mask,
                    aux, cap)
                rank = jnp.cumsum(mask.astype(jnp.int64))
                keep = mask & (rank > self.offset) & \
                    (rank <= self.offset + self.n)
                return out_datas, out_valids, keep

            return stage_jit(kernel)

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
        with batch_cost_scope(batch):
            out_datas, out_valids, keep = kernel(
                [c.data for c in batch.columns],
                [c.validity for c in batch.columns], batch.row_mask, aux)
        schema = attrs_schema(self.output)
        cols = pipeline_columns(schema.fields, host_outs, out_datas,
                                out_valids)
        limited = ColumnarBatch(schema, cols, keep, num_rows=None)
        if not self.is_global and self.n * 4 <= cap:
            from ..columnar.ops import compact_batch

            limited = compact_batch(limited)
        return [limited]

    def simple_string(self):
        o = ", ".join(x.simple_string() for x in self.pipe_outputs)
        f = " AND ".join(x.simple_string() for x in self.filters)
        s = f"FusedLimit[n={self.n}]({o})"
        if f:
            s += f" WHERE {f}"
        return s


# ---------------------------------------------------------------------------
# ExchangeFusion: shuffle writes consume straight from the fused stage
# ---------------------------------------------------------------------------

class ExchangeFusion:
    """The map side of a shuffle exchange fused with its producing
    pipeline: per input batch, ONE jitted program filters, projects,
    computes the partition id of every live row (hash / range /
    round-robin), groups rows by pid with `lax.sort`, and gathers the
    pipeline OUTPUT columns into pid order — the shuffle write
    (exec/shuffle.shuffle_fused) slices the grouped host columns straight
    into the reduce buffers. No intermediate materialized batch and no
    separate partition-id dispatch: <=1 XLA dispatch per map batch (the
    round-robin running offset stays an int32 kernel argument, so the
    cache key is position-independent)."""

    def __init__(self, filters: Sequence[Expression],
                 outputs: Sequence[Expression], input_attrs):
        self.filters = list(filters)
        self.pipe_outputs = list(outputs)
        self.pipe_attrs = _pipe_attrs(self.pipe_outputs)
        self.input_attrs = list(input_attrs)
        self._pipe_cache = None
        id_to_pos = bind_inputs(self.input_attrs)
        self._struct_key = (
            tuple(canonical_key(f, id_to_pos) for f in self.filters),
            tuple(canonical_key(o, id_to_pos) for o in self.pipe_outputs),
        )
        # partitioning binding (set by bind_*): mode + operands
        self._mode = None
        self._num_out = None
        self._key_idx = ()
        self._seed = 42
        self._descending = False
        self._bounds_host = None
        self._bounds_dev = None
        self._range_pos = None
        # runtime join filter (physical/adaptive): build-side key domain
        # pruning probe rows inside the SAME fused kernel — the domain is
        # an aux operand (range bounds / per-batch dict-code LUT), never
        # a separate dispatch. rf_pruned accumulates the pruned-row count
        # that rides the counts transfer (no extra sync).
        self._rf = None
        self._rf_dev = None
        self.rf_pruned = 0

    # -- partitioning binding (one ExchangeFusion serves one execute) ------
    def bind_hash(self, key_positions, num_out: int, seed: int = 42):
        self._mode, self._num_out = "h", num_out
        self._key_idx, self._seed = tuple(key_positions), seed
        return self

    def bind_rr(self, num_out: int):
        self._mode, self._num_out = "rr", num_out
        return self

    def bind_runtime_filter(self, rf: dict):
        """Arm the runtime join filter. The cache key grows an element
        ONLY when armed, so filter-off runs keep byte-identical kernel
        keys (the launch-delta identity the obs gate proves); the range
        bounds stay kernel operands, so different domains reuse one
        compiled kernel."""
        import jax.numpy as jnp

        self._rf = dict(rf)
        if rf["kind"] == "range":
            self._rf_dev = jnp.asarray(  # tpulint: ignore[host-sync]
                np.asarray(  # tpulint: ignore[host-sync] host bounds
                    [rf["lo"], rf["hi"]], dtype=np.int64))
        return self

    def bind_range(self, key_position: int, bounds, descending: bool,
                   num_out: int):
        import jax.numpy as jnp

        self._mode, self._num_out = "rg", num_out
        self._range_pos = key_position
        self._descending = descending
        self._bounds_host = bounds
        # host sample bounds → device, once per exchange execute
        self._bounds_dev = jnp.asarray(np.asarray(bounds))  # tpulint: ignore[host-sync]
        return self

    # -- unfused fallback (spark.tpu.fusion.minRows gate) ------------------
    def _pipeline(self):
        if self._pipe_cache is None:
            from .compile import ExprPipeline

            self._pipe_cache = ExprPipeline(
                self.input_attrs, self.filters, self.pipe_outputs,
                attrs_schema(self.pipe_attrs))
        return self._pipe_cache

    def run_pipeline(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Materialize the pipeline only (mesh fallback + size gate)."""
        return self._pipeline().run(batch)

    def partition_unfused(self, batch: ColumnarBatch, start: int):
        """Shared operator-at-a-time kernels for undersized partitions:
        one pipeline dispatch + one shuffle-kind dispatch per batch."""
        from ..exec import shuffle as S

        b = self.run_pipeline(batch)
        if self._rf is not None:
            b = self._apply_rf_unfused(b)
        if self._mode == "h":
            return S.hash_partition_batch(b, self._key_idx, self._num_out,
                                          self._seed)
        if self._mode == "rr":
            return S.rr_partition_batch(b, self._num_out, start)
        return S.range_partition_batch(b, self._range_pos,
                                       self._bounds_host, self._descending,
                                       self._num_out, string_key=False)

    def _apply_rf_unfused(self, b: ColumnarBatch) -> ColumnarBatch:
        """Runtime join filter on the size-gated unfused path: one tiny
        mask-update dispatch (the fused path folds it into the map kernel
        instead). The pruned count rides the partition-kernel counts this
        path already materializes, except here we pull the scalar beside
        them — the path syncs per batch regardless."""
        b, drop = runtime_filter_batch(self._rf, self._rf_dev, b,
                                       self._rf["out_pos"])
        self.rf_pruned += drop
        return b

    # -- the fused kernel --------------------------------------------------
    def partition_batch(self, batch: ColumnarBatch, start: int):
        """One dispatch: (grouped host columns, per-partition counts)."""

        jnp = _jnp()
        cap = batch.capacity
        num_out = self._num_out
        input_attrs = self.input_attrs
        filters, outputs = self.filters, self.pipe_outputs
        hctx, host_outs, aux = pipeline_host_pass(input_attrs, filters,
                                                  outputs, batch)
        key_idx = self._key_idx
        key_bool = tuple(isinstance(self.pipe_attrs[i].dtype, BooleanType)
                         for i in key_idx)
        # string partition keys: eq_keys computes inside the trace via
        # padded dictionary-hash aux luts (compressed execution — the
        # fused map dispatch ships codes, never decoded values)
        from ..columnar.batch import EMPTY_DICT as _ED

        dict_pos = {i: j for j, i in enumerate(
            i for i in key_idx
            if isinstance(self.pipe_attrs[i].dtype, StringType))}
        kluts = [(host_outs[i].sdict or _ED).device_hash_lut()
                 for i in dict_pos]
        mode, seed, descending = self._mode, self._seed, self._descending
        rpos = self._range_pos
        # runtime join filter operands (bind_runtime_filter): range
        # bounds ride as a device scalar pair; dict domains become a
        # per-batch bool LUT over the batch's OWN code space (host set
        # membership over StringDict values — no decode, no sync)
        rf = self._rf
        rf_kind = None if rf is None else rf["kind"]
        rf_pos = None if rf is None else rf["out_pos"]
        rf_arg = self._rf_dev
        if rf_kind == "dict":
            sd = host_outs[rf_pos].sdict
            if sd is None:
                rf_kind = rf_pos = rf_arg = None  # undecodable: unfiltered
            else:
                dom = rf["domain"]
                lut = np.fromiter((v in dom for v in sd.values),
                                  dtype=bool, count=len(sd.values))
                if lut.size == 0:
                    lut = np.zeros(1, dtype=bool)
                rf_arg = jnp.asarray(lut)
        key = ("fused_shuffle", mode, self._struct_key, cap, num_out,
               key_idx, seed, descending, rpos,
               None if self._bounds_dev is None
               else (str(self._bounds_dev.dtype), len(self._bounds_host)),
               pipeline_signature(batch), hctx.signature(),
               tuple(sorted(dict_pos)),
               tuple(int(l.shape[0])  # tpulint: ignore[host-sync]
                     for l in kluts))
        if rf_kind is not None:
            # appended ONLY when armed: filter-off cache keys stay
            # byte-identical (zero launch-delta with the layer enabled
            # on a filter-free plan)
            key = key + (("rf", rf_kind, rf_pos,
                          None if rf_kind != "dict"
                          # static shape, not a device scalar
                          else int(rf_arg.shape[0])),)  # tpulint: ignore[host-sync]

        def build():
            from ..ops.hashing import hash_columns, partition_ids
            from ..ops.partition import _group_by_pid

            def kernel(datas, valids, row_mask, aux, start_s, bounds,
                       kluts, rf_op):
                out_datas, out_valids, mask = trace_pipeline(
                    input_attrs, filters, outputs, datas, valids, row_mask,
                    aux, cap)
                rf_drop = None
                if rf_kind is not None:
                    kd = out_datas[rf_pos]
                    kv = out_valids[rf_pos]
                    if rf_kind == "range":
                        k64 = kd.astype(jnp.int64)
                        ok = (k64 >= rf_op[0]) & (k64 <= rf_op[1])
                    else:
                        codes = jnp.clip(kd.astype(jnp.int32), 0,
                                         rf_op.shape[0] - 1)
                        ok = jnp.take(rf_op, codes)
                    if kv is not None:
                        # null keys never match but never mis-route:
                        # keep them (conservative) — the join drops them
                        ok = ok | ~kv
                    rf_drop = jnp.sum(mask & ~ok)
                    mask = mask & ok
                if mode == "h":
                    eqs = []
                    for i, is_bool in zip(key_idx, key_bool):
                        kd = out_datas[i]
                        if is_bool:
                            kd = kd.astype(jnp.int32)
                        if i in dict_pos:
                            lut = kluts[dict_pos[i]]
                            kd = jnp.take(lut, jnp.clip(
                                kd.astype(jnp.int32), 0,
                                lut.shape[0] - 1))
                        eqs.append(kd)
                    kvs = [out_valids[i] for i in key_idx]
                    pids = partition_ids(
                        hash_columns(eqs, kvs, seed=seed), num_out)
                elif mode == "rr":
                    live_rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
                    pids = ((live_rank + start_s) % num_out) \
                        .astype(jnp.int32)
                else:  # range over sampled bounds (numeric key domain)
                    keys64 = out_datas[rpos].astype(bounds.dtype)
                    pids = jnp.searchsorted(bounds, keys64, side="right") \
                        .astype(jnp.int32)
                    if descending:
                        pids = (num_out - 1) - pids
                pr = _group_by_pid(pids, mask, num_out)
                g_datas = [jnp.take(d, pr.perm) for d in out_datas]
                g_valids = [None if v is None else jnp.take(v, pr.perm)
                            for v in out_valids]
                counts = pr.counts
                if rf_drop is not None:
                    # the pruned-row count rides the counts transfer —
                    # one appended lane, not a second sync
                    counts = jnp.concatenate(
                        [counts, rf_drop.astype(counts.dtype)[None]])
                return g_datas, g_valids, counts

            return stage_jit(kernel)

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
        with batch_cost_scope(batch):
            g_datas, g_valids, counts = kernel(
                [c.data for c in batch.columns],
                [c.validity for c in batch.columns], batch.row_mask, aux,
                np.int32(start % num_out), self._bounds_dev, kluts,
                rf_arg if rf_kind is not None else None)
        fields = attrs_schema(self.pipe_attrs).fields
        # the shuffle write's ONE intended sync point: map output lands
        # in host buffers for IPC/reduce-buffer slicing
        g_datas, g_valids, counts = device_read(
            "shuffle.pull", g_datas, g_valids, counts)
        gathered = [(g_datas[i], g_valids[i],
                     host_outs[i].sdict if dict_encoded(f.dataType)
                     else None)
                    for i, f in enumerate(fields)]
        if rf_kind is not None:
            self.rf_pruned += int(counts[-1])
            counts = counts[:-1]
        return gathered, counts


# ---------------------------------------------------------------------------
# FuseStages planner rule
# ---------------------------------------------------------------------------

def runtime_filter_batch(rf: dict, rf_dev, b: ColumnarBatch,
                         pos: int) -> tuple:
    """One mask-update dispatch applying a runtime join filter to a
    batch's key column `pos` (the shared kernel behind the size-gated
    unfused path AND the mesh pre-pass, where the filter cannot ride a
    fused map kernel). Null keys are kept conservatively — the join
    drops them. Returns (filtered batch, pruned-row count)."""

    jnp = _jnp()
    col = b.columns[pos]
    if rf["kind"] == "dict":
        sd = col.dictionary
        if sd is None:
            return b, 0    # undecodable codes: pass through unfiltered
        dom = rf["domain"]
        lut = np.fromiter((v in dom for v in sd.values), dtype=bool,
                          count=len(sd.values))
        if lut.size == 0:
            lut = np.zeros(1, dtype=bool)
        op = jnp.asarray(lut)
    elif rf_dev is not None:
        op = rf_dev
    else:
        op = jnp.asarray(  # tpulint: ignore[host-sync]
            np.asarray(  # tpulint: ignore[host-sync] host bounds
                [rf["lo"], rf["hi"]], dtype=np.int64))
    kind = rf["kind"]
    key = ("rf_mask", kind, str(col.data.dtype),
           col.validity is not None, b.capacity,
           # static shape, not a device scalar
           None if kind != "dict" else int(op.shape[0]))  # tpulint: ignore[host-sync]

    def build():
        def kernel(kd, kv, mask, opnd):
            if kind == "range":
                k64 = kd.astype(jnp.int64)
                ok = (k64 >= opnd[0]) & (k64 <= opnd[1])
            else:
                codes = jnp.clip(kd.astype(jnp.int32), 0,
                                 opnd.shape[0] - 1)
                ok = jnp.take(opnd, codes)
            if kv is not None:
                ok = ok | ~kv
            new_mask = mask & ok
            return new_mask, jnp.sum(mask & ~ok)

        return stage_jit(kernel)

    kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
    new_mask, drop = kernel(col.data, col.validity, b.row_mask, op)
    drop, = device_read("rf.pruned", drop)
    return ColumnarBatch(b.schema, b.columns, new_mask), int(drop)


def _aggregate_fusable(agg: HashAggregateExec, compute: ComputeExec) -> bool:
    if not _compute_nontrivial(compute):
        return False
    if not all(s.mergeable for s in agg.specs):
        return False
    out_ids = {a.expr_id for a in compute.output}
    if any(g.expr_id not in out_ids for g in agg.grouping):
        return False
    for op, attr, _param in agg._plan_values():
        if op not in FUSABLE_OPS:
            return False
        if attr is not None and attr.expr_id not in out_ids:
            return False
        # string min/max fuses too: the reduce runs in rank space with
        # the rank + inverse-rank luts as kernel aux inputs
    return True


def _exchange_fusable(exch, compute: ComputeExec, conf: SQLConf) -> bool:
    from .partitioning import (
        HashPartitioning, RangePartitioning, UnknownPartitioning,
    )

    if not conf.get(FUSION_EXCHANGE):
        return False
    if not _compute_nontrivial(compute):
        return False
    p = exch.partitioning
    out_by_id = {a.expr_id: a for a in compute.output}
    if isinstance(p, HashPartitioning):
        for e in p.exprs:
            if not isinstance(e, AttributeReference):
                return False
            a = out_by_id.get(e.expr_id)
            if a is None:
                return False
            if isinstance(a.dtype, StringType):
                # string eq-keys compute inside the trace via padded
                # dictionary-hash aux luts (compressed execution)
                if not conf.get(ENCODING_ENABLED):
                    return False
            elif dict_encoded(a.dtype):
                # nested types: raw codes are not a cross-dictionary
                # equality domain — unfused path handles them
                return False
        return True
    if isinstance(p, UnknownPartitioning):
        return True  # round-robin: no keys; offset is a kernel argument
    if isinstance(p, RangePartitioning):
        if len(p.orders) != 1:
            return False
        oc = p.orders[0].child
        if not isinstance(oc, AttributeReference):
            return False
        a = out_by_id.get(oc.expr_id)
        if a is None or isinstance(a.dtype, StringType) \
                or dict_encoded(a.dtype):
            # string pids ride a host rank→pid lut per dictionary
            return False
        # computed sort keys fuse too: bounds sample the POST-pipeline
        # key column (physical/exchange._range_shuffle materializes the
        # pipeline for the sampled batches only)
        return True
    return False  # SinglePartition gathers without kernels


def _probe_fusable(join: HashJoinExec, compute: ComputeExec,
                   conf: SQLConf) -> bool:
    if not _compute_nontrivial(compute):
        return False
    out_by_id = {a.expr_id: a for a in compute.output}
    for k in join.left_keys:
        a = out_by_id.get(k.expr_id)
        if a is None:
            return False
        if isinstance(a.dtype, StringType):
            # string probe keys fuse: eq_keys (codes → value hashes)
            # computes inside the probe kernel via the padded
            # dictionary-hash lut aux input (compressed execution)
            if not conf.get(ENCODING_ENABLED):
                return False
        elif dict_encoded(a.dtype):
            # nested types: codes are not a cross-dictionary eq domain
            return False
    return True


def fuse_stages(plan: PhysicalPlan, conf: SQLConf) -> PhysicalPlan:
    """Collapse each maximal exchange-free chain of fusable operators into
    whole-stage fused operators (run by the planner after EnsureRequirements
    — the CollapseCodegenStages slot in the reference's preparation rules)."""
    plan = collapse_computes(plan)

    def rule(node):
        if isinstance(node, HashAggregateExec) \
                and not isinstance(node, FusedAggregateExec) \
                and node.mode == "partial" \
                and isinstance(node.child, ComputeExec) \
                and _aggregate_fusable(node, node.child):
            c = node.child
            return FusedAggregateExec(node.grouping, node.specs, c.filters,
                                      c.outputs, c.child)
        if isinstance(node, LimitExec) \
                and not isinstance(node, FusedLimitExec) \
                and isinstance(node.child, ComputeExec) \
                and _compute_nontrivial(node.child):
            c = node.child
            return FusedLimitExec(node.n, c.filters, c.outputs, c.child,
                                  offset=node.offset,
                                  is_global=node.is_global)
        if isinstance(node, HashJoinExec) and node.probe_fusion is None \
                and isinstance(node.left, ComputeExec) \
                and _probe_fusable(node, node.left, conf):
            c = node.left
            node.probe_fusion = (list(c.filters), list(c.outputs))
            node.probe_attrs = list(c.output)
            node.left = c.child
            node._probe_pipe_cache = None
            return node
        from .exchange import ShuffleExchangeExec

        if isinstance(node, ShuffleExchangeExec) \
                and node.pipe_fusion is None \
                and isinstance(node.child, ComputeExec) \
                and _exchange_fusable(node, node.child, conf):
            # the exchange terminal consumes straight from the fused
            # stage: the partition-id kernel traces into the pipeline
            # program (ExchangeFusion) and shuffle writes read its
            # pid-grouped output — no materialized intermediate batch
            c = node.child
            node.pipe_fusion = (list(c.filters), list(c.outputs))
            node.pipe_attrs = list(c.output)
            node.child = c.child
            return node
        return node

    return plan.transform_up(rule)
