"""WindowExec.

Role of the reference's sqlx/window/WindowExec.scala — but frame evaluation
is the sort/segment kernel in ops/window.py (no row-at-a-time frame
iterators), and results scatter back to the original row order so the
operator is order-preserving like the reference's."""

from __future__ import annotations

from typing import Sequence

from ..columnar.batch import Column, ColumnarBatch
from ..columnar.ops import concat_batches
from ..errors import UnsupportedOperationError
from ..exec.context import ExecContext
from ..expr.expressions import (
    AggregateFunction, Alias, AttributeReference, Average, Count, Literal,
    Max, Min, SortOrder, Sum,
)
from ..expr.window import (
    CumeDist, DenseRank, FirstValue, Lag, LastValue, Lead, NthValue, NTile,
    PercentRank, Rank, RowNumber, WindowExpression,
)
from ..types import StringType, float64, int32, int64
from .compile import GLOBAL_KERNEL_CACHE, stage_jit
from .operators import PhysicalPlan, attrs_schema
from .partitioning import AllTuples, ClusteredDistribution, UnspecifiedDistribution


def _jnp():
    import jax.numpy as jnp

    return jnp


def trace_window(plans, ospecs, finish, pkeys, pvalids, okeys, ovalids,
                 vdatas, vvalids, row_mask, kmin: int = 0, band: int = 0):
    """The operator's traced body: one layout sort, each expression's
    frame computation over the sorted layout, the scatter back to the
    input's row order, and the cast to the expression's type. Every tier
    traces THIS (the per-partition kernel below, the whole-query
    program's `_lower_window`): there is no second copy of the frame
    logic. `plans` is `WindowExec._plans()`, `finish` its `_finish()`;
    returns one (data, validity | None) per expression, at the input's
    capacity and in its row order."""
    import jax

    from ..ops import window as W

    lo = W.build_layout(pkeys, pvalids, okeys, ovalids, ospecs, row_mask)
    outs = []
    for (kind, param, _), (want, avg, _sig), vd, vv in zip(
            plans, finish, vdatas, vvalids):
        with jax.named_scope("frame"):
            if kind == "row_number":
                sv, svalid = W.w_row_number(lo), None
            elif kind == "rank":
                sv, svalid = W.w_rank(lo), None
            elif kind == "dense_rank":
                sv, svalid = W.w_dense_rank(lo), None
            elif kind == "percent_rank":
                sv, svalid = W.w_percent_rank(lo), None
            elif kind == "cume_dist":
                sv, svalid = W.w_cume_dist(lo), None
            elif kind == "ntile":
                sv, svalid = W.w_ntile(lo, param), None
            elif kind == "shift":
                sv, svalid = W.w_shift(lo, vd, vv, param)
            elif kind == "first_value":
                sv, svalid = W.w_first_value(lo, vd, vv)
            elif kind == "last_value":
                sv, svalid = W.w_last_value(lo, vd, vv,
                                            whole=param == "partition")
            elif kind == "nth_value":
                sv, svalid = W.w_nth_value(lo, vd, vv, param[0],
                                           whole=param[1] == "partition")
            elif kind.startswith("agg_vrange_"):
                sv, svalid = W.w_agg_value_range(
                    lo, okeys[0], vd, vv, kind.split("_")[-1],
                    param[0], param[1], kmin, band, avg=avg)
            elif kind.startswith("agg_rows_"):
                sv, svalid = W.w_agg_rows(lo, vd, vv, kind.split("_")[-1],
                                          param[0], param[1], avg=avg)
            elif kind.startswith("agg_running_"):
                sv, svalid = W.w_agg_running(lo, vd, vv,
                                             kind.split("_")[-1], avg=avg)
            elif kind.startswith("agg_unbounded_"):
                sv, svalid = W.w_agg_unbounded(lo, vd, vv,
                                               kind.split("_")[-1], avg=avg)
            else:
                raise ValueError(kind)
            if str(sv.dtype) != want:
                sv = sv.astype(want)
        outs.append(W.scatter_back(lo, sv, svalid))
    return outs


def _average_finish(fn: Average, name: str, expr_id: int):
    """AVG's (total, count) -> (data, validity) as the aggregate finishes
    it: `aggregates.lower_aggregate_function`'s result expression (sum /
    count, cast to AVG's type; NULL over no value) traced over the
    frame's total and count. So a window's average and a GROUP BY's are
    one computation to one type, AVG over DECIMAL included."""
    from .aggregates import lower_aggregate_function
    from .compile import trace_pipeline

    spec = lower_aggregate_function(fn, name, expr_id)

    def avg(total, cnt):
        jnp = _jnp()
        cap = total.shape[0]
        d, v, _m = trace_pipeline(spec.buffer_attrs, [], [spec.result_alias],
                                  [total, cnt], [None, None],
                                  jnp.ones((cap,), dtype=bool), [], cap)
        return d[0], v[0]

    return avg


class WindowExec(PhysicalPlan):
    """window_exprs: Alias(WindowExpression) whose function args, partition
    keys, and order keys are bound to child attributes by the planner."""

    child_fields = ("child",)

    def __init__(self, window_exprs: Sequence[Alias],
                 partition_keys: Sequence[AttributeReference],
                 order_keys: Sequence[SortOrder], child: PhysicalPlan):
        self.window_exprs = list(window_exprs)
        self.partition_keys = list(partition_keys)
        self.order_keys = list(order_keys)
        self.child = child

    @property
    def output(self):
        return self.child.output + [a.to_attribute() for a in self.window_exprs]

    def required_child_distribution(self):
        if not self.partition_keys:
            return [AllTuples()]
        return [ClusteredDistribution(list(self.partition_keys))]

    def output_partitioning(self):
        return self.child.output_partitioning()

    def _plans(self):
        """(kind, params) per window expr — static kernel config."""
        out = []
        has_order = bool(self.order_keys)
        for al in self.window_exprs:
            w: WindowExpression = al.child
            f = w.function
            if isinstance(f, RowNumber):
                out.append(("row_number", None, None))
            elif isinstance(f, Rank):
                out.append(("rank", None, None))
            elif isinstance(f, DenseRank):
                out.append(("dense_rank", None, None))
            elif isinstance(f, PercentRank):
                out.append(("percent_rank", None, None))
            elif isinstance(f, CumeDist):
                out.append(("cume_dist", None, None))
            elif isinstance(f, NTile):
                out.append(("ntile", f.n, None))
            elif isinstance(f, (Lag, Lead)):
                off = f.offset if isinstance(f, Lag) else -f.offset
                out.append(("shift", off, f.child))
            elif isinstance(f, (NthValue, FirstValue)):
                # default frame = running-to-current-peers; explicit
                # UNBOUNDED..UNBOUNDED = whole partition; anything else
                # is unsupported rather than silently wrong
                frame = w.frame
                if frame is None:
                    scope = "peers"
                elif (frame[1], frame[2]) == (None, None):
                    scope = "partition"
                else:
                    raise UnsupportedOperationError(
                        f"{type(f).__name__} over a bounded frame is "
                        "not supported yet")
                if isinstance(f, NthValue):
                    out.append(("nth_value", (f.n, scope), f.child))
                elif isinstance(f, LastValue):  # FirstValue subclass
                    out.append(("last_value", scope, f.child))
                else:
                    out.append(("first_value", scope, f.child))
            elif isinstance(f, (Sum, Count, Min, Max, Average)):
                kind = {Sum: "sum", Count: "count", Min: "min", Max: "max",
                        Average: "avg"}[type(f)]
                frame = w.frame
                if frame is not None:
                    ftype, lo, hi = frame
                    if (lo, hi) == (None, None):
                        out.append((f"agg_unbounded_{kind}", None, f.child))
                    elif kind not in ("sum", "count", "avg", "min", "max"):
                        raise UnsupportedOperationError(
                            f"{kind} over a bounded frame is not "
                            "supported yet")
                    elif ftype == "vrange":
                        if len(self.order_keys) != 1:
                            raise UnsupportedOperationError(
                                "RANGE value frames need exactly one "
                                "ORDER BY key")
                        out.append((f"agg_vrange_{kind}", (lo, hi), f.child))
                    else:
                        out.append((f"agg_rows_{kind}", (lo, hi), f.child))
                else:
                    mode = "running" if has_order else "unbounded"
                    out.append((f"agg_{mode}_{kind}", None, f.child))
            else:
                raise UnsupportedOperationError(
                    f"window function {type(f).__name__}")
        return out

    def _finish(self):
        """(device dtype, AVG's finishing | None, key fragment) per window
        expr: how `trace_window` turns a frame's value into the column."""
        out = []
        for al in self.window_exprs:
            fn = al.child.function
            want = str(al.child.dtype.device_dtype)
            avg = None
            sig = (want,)
            if isinstance(fn, Average):
                avg = _average_finish(fn, al.name, al.expr_id)
                sig = (want, str(fn.child.dtype))
            out.append((want, avg, sig))
        return out

    def execute(self, ctx: ExecContext):
        from .adaptive import coalesce_after_exchange

        parts = self.child.execute(ctx)
        parts = coalesce_after_exchange(self.child, parts, ctx,
                                        self.child.output)
        return [[self._run_partition(p)] if p else [] for p in parts]

    def _run_partition(self, part) -> ColumnarBatch:
        from ..ops.sorting import SortKeySpec

        jnp = _jnp()
        batch = concat_batches(part, attrs_schema(self.child.output))
        pos = {a.expr_id: i for i, a in enumerate(self.child.output)}
        cap = batch.capacity

        pcols = [batch.columns[pos[k.expr_id]] for k in self.partition_keys]
        ocols = [batch.columns[pos[o.child.expr_id]] for o in self.order_keys]
        ospecs = [SortKeySpec(o.ascending, o.nulls_first)
                  for o in self.order_keys]

        plans = self._plans()
        finish = self._finish()
        vcols = []
        for kind, param, arg in plans:
            if arg is not None:
                vcols.append(batch.columns[pos[arg.expr_id]])
            elif kind.endswith("_count"):
                # count(*) over a window: count frame rows — an all-valid
                # ones column makes the count kernels row-counting
                vcols.append("ones")
            else:
                vcols.append(None)

        # value-RANGE frames: band the single integral order key per
        # partition (host syncs min/max; band is baked into the kernel)
        kmin = band = 0
        if any(k.startswith("agg_vrange_") for k, _, _ in plans):
            import jax
            from ..types import DateType, IntegralType

            oc = ocols[0]
            if not isinstance(oc.dtype, (IntegralType, DateType)) or \
                    oc.validity is not None:
                raise UnsupportedOperationError(
                    "RANGE value frames need a non-null integral/date "
                    "ORDER BY key")
            if not ospecs[0].ascending:
                raise UnsupportedOperationError(
                    "RANGE value frames need an ascending ORDER BY")
            jnp2 = _jnp()
            k64 = oc.data.astype(jnp2.int64)
            big = jnp2.iinfo(jnp2.int64).max
            small = jnp2.iinfo(jnp2.int64).min
            kmin = int(jnp2.min(jnp2.where(batch.row_mask, k64, big)))
            kmax = int(jnp2.max(jnp2.where(batch.row_mask, k64, small)))
            max_off = max(abs(p[0] or 0) if p else 0 for _, p, _ in plans
                          if p) + max(abs(p[1] or 0) if p else 0
                                      for _, p, _ in plans if p) + 1
            span = max(kmax - kmin + 1 + 2 * max_off, 8)
            band = 1
            while band < span:
                band <<= 1
            if cap * band >= (1 << 62):
                raise UnsupportedOperationError(
                    "RANGE frame key span too large to band")

        from ..ops.grouping import segment_path

        key = ("window", cap, segment_path(cap), kmin, band,
               tuple((str(c.eq_keys().dtype), c.validity is not None)
                     for c in pcols),
               tuple((str(c.sort_keys().dtype), c.validity is not None,
                      s.ascending, s.nulls_first)
                     for c, s in zip(ocols, ospecs)),
               tuple((k, p, "ones" if isinstance(v, str) else
                      None if v is None else
                      (str(v.data.dtype), v.validity is not None))
                     for (k, p, _), v in zip(plans, vcols)),
               tuple(sig for _want, _avg, sig in finish))

        def build():
            def kernel(pkeys, pvalids, okeys, ovalids, vdatas, vvalids,
                       row_mask):
                return trace_window(plans, ospecs, finish, pkeys, pvalids,
                                    okeys, ovalids, vdatas, vvalids,
                                    row_mask, kmin, band)

            return stage_jit(kernel)

        kernel = GLOBAL_KERNEL_CACHE.get_or_build(key, build)
        ones = jnp.ones((cap,), jnp.int32)
        outs = kernel([c.eq_keys() for c in pcols],
                      [c.validity for c in pcols],
                      [c.sort_keys() for c in ocols],
                      [c.validity for c in ocols],
                      [ones if isinstance(v, str) else
                       None if v is None else v.data for v in vcols],
                      [None if v is None or isinstance(v, str)
                       else v.validity for v in vcols],
                      batch.row_mask)

        schema = attrs_schema(self.output)
        new_cols = list(batch.columns)
        for (d, v), al in zip(outs, self.window_exprs):
            dt = al.child.dtype
            sdict = None
            if isinstance(dt, StringType):
                # shift over strings keeps the source dictionary
                arg = al.child.function.child
                sdict = batch.columns[pos[arg.expr_id]].dictionary
            new_cols.append(Column(dt, d, v, sdict))
        return ColumnarBatch(schema, new_cols, batch.row_mask,
                             batch._num_rows)

    def simple_string(self):
        fns = ", ".join(a.child.function.sql_name()
                        for a in self.window_exprs)
        return f"Window[{fns}]"
