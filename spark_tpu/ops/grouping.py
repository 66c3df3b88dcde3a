"""Sort-based grouped aggregation kernel.

Role of the reference's HashAggregateExec + UnsafeFixedWidthAggregationMap
(sqlx/aggregate/HashAggregateExec.scala:50, corej/unsafe/map/BytesToBytesMap.java)
and its sort-based fallback (TungstenAggregationIterator). TPU-native design:
no hash table at all — `lax.sort` (bitonic/radix, MXU-adjacent, fully
data-parallel) groups equal keys adjacently, then `segment_sum`-family ops
reduce each run. Static shapes throughout: output has the same capacity as
input (worst case all rows distinct) with a row mask for live groups.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax


class GroupLayout(NamedTuple):
    """Result of grouping rows by key columns."""

    perm: jnp.ndarray        # int32[cap] permutation sorting rows (inactive last)
    seg_ids: jnp.ndarray     # int32[cap] segment id per SORTED row (0-based)
    start_flag: jnp.ndarray  # bool[cap] first-row-of-group flag per sorted row
    active: jnp.ndarray      # bool[cap] row_mask per sorted row
    num_groups: jnp.ndarray  # int32 scalar — number of live groups


@jax.named_scope("group_sort")
def group_rows(key_cols: Sequence[jnp.ndarray],
               key_valids: Sequence[jnp.ndarray | None],
               row_mask: jnp.ndarray) -> GroupLayout:
    """Sort rows so equal keys (SQL semantics: null == null, inactive rows
    last) are adjacent; derive segment structure."""
    cap = row_mask.shape[0]
    inactive = (~row_mask).astype(jnp.int32)
    operands = [inactive]
    for c, v in zip(key_cols, key_valids):
        if v is not None:
            operands.append((~v).astype(jnp.int32))  # nulls group together
            operands.append(jnp.where(v, c, jnp.zeros_like(c)))
        else:
            operands.append(c)
    num_keys = len(operands)
    operands.append(lax.iota(jnp.int32, cap))
    sorted_ops = lax.sort(tuple(operands), num_keys=num_keys, is_stable=True)
    perm = sorted_ops[-1]
    skeys = sorted_ops[:num_keys]
    active = jnp.take(row_mask, perm)

    changed = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for k in skeys:
        diff = jnp.concatenate([jnp.ones(1, dtype=bool), k[1:] != k[:-1]])
        changed = changed | diff
    start_flag = changed & active
    seg_ids = jnp.cumsum(start_flag.astype(jnp.int32)) - 1
    seg_ids = jnp.maximum(seg_ids, 0)
    num_groups = jnp.sum(start_flag.astype(jnp.int32))
    return GroupLayout(perm, seg_ids, start_flag, active, num_groups)


@jax.named_scope("group_runs")
def group_rows_presorted(key: jnp.ndarray, row_mask: jnp.ndarray
                         ) -> GroupLayout:
    """GroupLayout for a single key column whose values are ALREADY
    non-decreasing (ingest RunInfo.is_sorted metadata, no validity plane):
    the RLE-aware segment reduce. Equal keys are contiguous by
    construction, so the segment structure derives from run BOUNDARIES
    (one adjacent-difference + a per-run first-live scatter) and the
    O(cap log cap) grouping sort is skipped entirely — the reduce visits
    each run once instead of re-discovering it. Mask-only filters never
    reorder rows, so sortedness established at ingest survives them;
    masked rows inside a run contribute nothing (weights), and runs with
    no live rows produce no group."""
    cap = row_mask.shape[0]
    pos = lax.iota(jnp.int32, cap)
    changed = jnp.concatenate([jnp.ones(1, dtype=bool),
                               key[1:] != key[:-1]])
    run_id = jnp.cumsum(changed.astype(jnp.int32)) - 1
    # first LIVE row of each value run opens its group: a masked row
    # between two live rows of one run must not split the group
    p = jnp.where(row_mask, pos, cap)
    first_live = jax.ops.segment_min(p, run_id, num_segments=cap)
    start_flag = row_mask & (pos == jnp.take(first_live, run_id))
    seg_ids = jnp.maximum(jnp.cumsum(start_flag.astype(jnp.int32)) - 1, 0)
    num_groups = jnp.sum(start_flag.astype(jnp.int32))
    return GroupLayout(pos, seg_ids, start_flag, row_mask, num_groups)


@jax.named_scope("group_keys")
def scatter_group_keys(layout: GroupLayout, key_col: jnp.ndarray,
                       key_valid: jnp.ndarray | None):
    """Gather each group's key value into output slot seg_id.

    Returns (data[cap], validity[cap] | None) in group-output order."""
    cap = layout.perm.shape[0]
    sorted_vals = jnp.take(key_col, layout.perm)
    idx = jnp.where(layout.start_flag, layout.seg_ids, cap)  # drop non-starts
    out = jnp.zeros(cap, dtype=key_col.dtype).at[idx].set(sorted_vals, mode="drop")
    out_valid = None
    if key_valid is not None:
        sv = jnp.take(key_valid, layout.perm)
        out_valid = jnp.zeros(cap, dtype=bool).at[idx].set(sv, mode="drop")
    return out, out_valid


def group_output_mask(layout: GroupLayout):
    cap = layout.perm.shape[0]
    return lax.iota(jnp.int32, cap) < layout.num_groups


# --- segment aggregation primitives ---------------------------------------

def _weights(layout: GroupLayout, valid: jnp.ndarray | None):
    w = layout.active
    if valid is not None:
        w = w & jnp.take(valid, layout.perm)
    return w


def seg_sum(layout: GroupLayout, values: jnp.ndarray, valid=None):
    cap = values.shape[0]
    v = jnp.take(values, layout.perm)
    w = _weights(layout, valid)
    acc_dtype = jnp.float64 if jnp.issubdtype(v.dtype, jnp.floating) else jnp.int64
    vv = jnp.where(w, v.astype(acc_dtype), jnp.zeros((), acc_dtype))
    total = jax.ops.segment_sum(vv, layout.seg_ids, num_segments=cap)
    cnt = jax.ops.segment_sum(w.astype(jnp.int64), layout.seg_ids, num_segments=cap)
    return total, cnt  # caller derives sum validity: cnt > 0


def seg_count(layout: GroupLayout, valid=None):
    cap = layout.perm.shape[0]
    w = _weights(layout, valid)
    return jax.ops.segment_sum(w.astype(jnp.int64), layout.seg_ids, num_segments=cap)


def seg_min(layout: GroupLayout, values: jnp.ndarray, valid=None):
    cap = values.shape[0]
    v = jnp.take(values, layout.perm)
    w = _weights(layout, valid)
    big = _max_ident(v.dtype)
    vv = jnp.where(w, v, big)
    m = jax.ops.segment_min(vv, layout.seg_ids, num_segments=cap)
    cnt = jax.ops.segment_sum(w.astype(jnp.int32), layout.seg_ids, num_segments=cap)
    return m, cnt > 0


def seg_max(layout: GroupLayout, values: jnp.ndarray, valid=None):
    cap = values.shape[0]
    v = jnp.take(values, layout.perm)
    w = _weights(layout, valid)
    small = _min_ident(v.dtype)
    vv = jnp.where(w, v, small)
    m = jax.ops.segment_max(vv, layout.seg_ids, num_segments=cap)
    cnt = jax.ops.segment_sum(w.astype(jnp.int32), layout.seg_ids, num_segments=cap)
    return m, cnt > 0


def bitplane_reduce(values: jnp.ndarray, weights: jnp.ndarray,
                    seg_ids: jnp.ndarray, num_segments: int, kind: str):
    """bit_and / bit_or / bit_xor per segment (reference:
    sqlcat/expressions/aggregate/bitwiseAggregates.scala). jax has no
    bitwise segment reduce, so decompose into 64 bit PLANES and ride
    ONE [cap, 64] segment_sum — then OR = plane sum > 0, AND = plane
    sum == segment count, XOR = plane sum parity. Arithmetic shift on
    int64 keeps two's-complement bit patterns exact for negatives.
    Planes are int32 (counts < 2^31), halving the HBM transient vs a
    naive int64 matrix. Shared by the sorted-segment, dense-range, and
    ungrouped kernels."""
    v = values.astype(jnp.int64)
    shifts = jnp.arange(64, dtype=jnp.int64)
    bits = ((v[:, None] >> shifts[None, :]) & 1).astype(jnp.int32)
    bits = jnp.where(weights[:, None], bits, jnp.int32(0))
    sums = jax.ops.segment_sum(bits, seg_ids, num_segments=num_segments)
    cnt = jax.ops.segment_sum(weights.astype(jnp.int32), seg_ids,
                              num_segments=num_segments)
    if kind == "and":
        plane = (sums == cnt[:, None]) & (cnt[:, None] > 0)
    elif kind == "xor":
        plane = (sums & 1) == 1
    else:
        plane = sums > 0
    out = (plane.astype(jnp.int64) << shifts[None, :]).sum(axis=1)
    return out, cnt > 0


def seg_bitreduce(layout: GroupLayout, values: jnp.ndarray, valid=None,
                  kind: str = "or"):
    cap = values.shape[0]
    v = jnp.take(values, layout.perm)
    w = _weights(layout, valid)
    return bitplane_reduce(v, w, layout.seg_ids, cap, kind)


def seg_first(layout: GroupLayout, values: jnp.ndarray, valid=None):
    """First value per group in sorted order (the reference's First agg is
    also order-dependent)."""
    cap = values.shape[0]
    v = jnp.take(values, layout.perm)
    w = _weights(layout, valid)
    # first row of each group where weight holds: use segment_min over
    # (position if w else cap)
    pos = lax.iota(jnp.int32, cap)
    p = jnp.where(w, pos, cap)
    first_pos = jax.ops.segment_min(p, layout.seg_ids, num_segments=cap)
    has = first_pos < cap
    fp = jnp.minimum(first_pos, cap - 1)
    return jnp.take(v, fp), has


# --- primitive-op dispatch tables ------------------------------------------
# One traced consume loop per aggregation layout, shared by the standalone
# HashAggregateExec kernels and the whole-stage fused kernels
# (physical/fusion.py) so both paths reduce with byte-identical op code.

@jax.named_scope("segment_reduce")
def apply_group_ops(layout: GroupLayout, ops: Sequence[str], val_datas,
                    val_valids):
    """Sorted-segment reduce of each (op, values, validity) triple over a
    GroupLayout. Returns [(buffer, validity | None)] per op."""
    bufs = []
    for op, vd, vv in zip(ops, val_datas, val_valids):
        if op in ("count", "countstar"):
            cnt = seg_count(layout, vv if op == "count" else None)
            bufs.append((cnt, None))
        elif op == "sum":
            total, cnt = seg_sum(layout, vd, vv)
            bufs.append((total, cnt > 0))
        elif op == "sumsq":
            x = vd.astype(jnp.float64)
            total, cnt = seg_sum(layout, x * x, vv)
            bufs.append((total, cnt > 0))
        elif op == "min":
            m, has = seg_min(layout, vd, vv)
            bufs.append((m, has))
        elif op == "max":
            m, has = seg_max(layout, vd, vv)
            bufs.append((m, has))
        elif op == "first":
            f, has = seg_first(layout, vd, vv)
            bufs.append((f, has))
        elif op in ("bitand", "bitor", "bitxor"):
            r, has = seg_bitreduce(layout, vd, vv, kind=op[3:])
            bufs.append((r, has))
        else:
            raise ValueError(op)
    return bufs


@jax.named_scope("dense_reduce")
def apply_dense_ops(seg, out_cap: int, cap: int, ops: Sequence[str],
                    val_datas, val_valids, live_mask):
    """Direct scatter reduce keyed by precomputed segment ids (dense-range
    fast path; `live_mask` is the row mask after filters). Returns
    [(buffer, validity | None)] per op."""
    bufs = []
    for op, vd, vv in zip(ops, val_datas, val_valids):
        w = live_mask if vv is None else (live_mask & vv)
        if op in ("count", "countstar"):
            ww = live_mask if op == "countstar" else w
            cnt = jax.ops.segment_sum(
                ww.astype(jnp.int64), seg, num_segments=out_cap)
            bufs.append((cnt, None))
        elif op in ("sum", "sumsq"):
            acc = jnp.float64 if jnp.issubdtype(vd.dtype, jnp.floating) \
                else jnp.int64
            x = vd.astype(acc)
            if op == "sumsq":
                x = vd.astype(jnp.float64)
                x = x * x
            total = jax.ops.segment_sum(
                jnp.where(w, x, jnp.zeros((), x.dtype)), seg,
                num_segments=out_cap)
            cnt = jax.ops.segment_sum(w.astype(jnp.int64), seg,
                                      num_segments=out_cap)
            bufs.append((total, cnt > 0))
        elif op == "min":
            big = _max_ident(vd.dtype)
            m = jax.ops.segment_min(jnp.where(w, vd, big), seg,
                                    num_segments=out_cap)
            cnt = jax.ops.segment_sum(w.astype(jnp.int32), seg,
                                      num_segments=out_cap)
            bufs.append((m, cnt > 0))
        elif op == "max":
            small = _min_ident(vd.dtype)
            m = jax.ops.segment_max(jnp.where(w, vd, small), seg,
                                    num_segments=out_cap)
            cnt = jax.ops.segment_sum(w.astype(jnp.int32), seg,
                                      num_segments=out_cap)
            bufs.append((m, cnt > 0))
        elif op == "first":
            pos = lax.iota(jnp.int32, cap)
            p = jnp.where(w, pos, cap)
            fp = jax.ops.segment_min(p, seg, num_segments=out_cap)
            has = fp < cap
            bufs.append((jnp.take(vd, jnp.minimum(fp, cap - 1)), has))
        elif op in ("bitand", "bitor", "bitxor"):
            r, has = bitplane_reduce(vd, w, seg, out_cap, op[3:])
            bufs.append((r, has))
        else:
            raise ValueError(op)
    return bufs


@jax.named_scope("global_reduce")
def apply_global_ops(ops: Sequence[str], val_datas, val_valids, row_mask):
    """Whole-tile (ungrouped) reduce. Returns [(scalar, has | None)]."""
    outs = []
    for op, vd, vv in zip(ops, val_datas, val_valids):
        if op in ("count", "countstar"):
            w = row_mask if (vv is None or op == "countstar") \
                else (row_mask & vv)
            outs.append((jnp.sum(w.astype(jnp.int64)), None))
        elif op == "sum":
            s, c = masked_sum(vd, row_mask, vv)
            outs.append((s, c > 0))
        elif op == "sumsq":
            x = vd.astype(jnp.float64)
            s, c = masked_sum(x * x, row_mask, vv)
            outs.append((s, c > 0))
        elif op == "min":
            m, has = masked_min(vd, row_mask, vv)
            outs.append((m, has))
        elif op == "max":
            m, has = masked_max(vd, row_mask, vv)
            outs.append((m, has))
        elif op == "first":
            w = row_mask if vv is None else (row_mask & vv)
            pos = jnp.argmax(w)  # first True (0 if none)
            has = jnp.any(w)
            outs.append((vd[pos], has))
        elif op in ("bitand", "bitor", "bitxor"):
            w = row_mask if vv is None else (row_mask & vv)
            seg0 = jnp.zeros(vd.shape[0], dtype=jnp.int32)
            r, has = bitplane_reduce(vd, w, seg0, 1, op[3:])
            outs.append((r[0], has[0]))
        else:
            raise ValueError(op)
    return outs


def _max_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(True)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _min_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(False)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


@jax.named_scope("percentile")
def group_percentile(key_cols, key_valids, values, value_valid, row_mask,
                     q: float):
    """Exact per-group percentile: one sort by (keys, value) makes each
    group's values contiguous and ordered; the q-th element is a gather at
    seg_start + floor(q·(n_valid−1)). Non-mergeable across partitions (the
    planner gathers to one partition first). Returns (vals, has) in the
    same group order as group_rows over the same keys."""
    cap = row_mask.shape[0]
    w = row_mask if value_valid is None else (row_mask & value_valid)
    operands = [(~row_mask).astype(jnp.int32)]
    for c, v in zip(key_cols, key_valids):
        if v is not None:
            operands.append((~v).astype(jnp.int32))
            operands.append(jnp.where(v, c, jnp.zeros_like(c)))
        else:
            operands.append(c)
    n_keys = len(operands)
    operands.append((~w).astype(jnp.int32))  # null/masked values last
    operands.append(values)
    operands.append(lax.iota(jnp.int32, cap))
    out = lax.sort(tuple(operands), num_keys=n_keys + 2, is_stable=True)
    perm = out[-1]
    skeys = out[:n_keys]
    svals = out[-2]
    active = jnp.take(row_mask, perm)
    sw = jnp.take(w, perm)

    changed = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for k in skeys:
        changed = changed | jnp.concatenate(
            [jnp.ones(1, dtype=bool), k[1:] != k[:-1]])
    start_flag = changed & active
    seg_ids = jnp.maximum(jnp.cumsum(start_flag.astype(jnp.int32)) - 1, 0)

    pos = lax.iota(jnp.int32, cap)
    seg_start = jnp.full((cap,), 0, jnp.int32).at[
        jnp.where(start_flag, seg_ids, cap)].set(pos, mode="drop")
    n_valid = jax.ops.segment_sum(sw.astype(jnp.int32), seg_ids,
                                  num_segments=cap)
    idx = seg_start + jnp.floor(
        q * jnp.maximum(n_valid - 1, 0)).astype(jnp.int32)
    vals = jnp.take(svals, jnp.clip(idx, 0, cap - 1))
    return vals, n_valid > 0


def masked_percentile(values, row_mask, valid, q: float):
    """Global exact percentile via one sort."""
    cap = values.shape[0]
    w = row_mask if valid is None else (row_mask & valid)
    big = _max_ident(values.dtype)
    sv = jnp.sort(jnp.where(w, values, big))
    n = jnp.sum(w.astype(jnp.int32))
    idx = jnp.floor(q * jnp.maximum(n - 1, 0)).astype(jnp.int32)
    return jnp.take(sv, jnp.clip(idx, 0, cap - 1)), n > 0


# --- ungrouped (global) aggregation ---------------------------------------

def masked_sum(values, row_mask, valid=None):
    w = row_mask if valid is None else (row_mask & valid)
    acc_dtype = jnp.float64 if jnp.issubdtype(values.dtype, jnp.floating) else jnp.int64
    s = jnp.sum(jnp.where(w, values.astype(acc_dtype), jnp.zeros((), acc_dtype)))
    c = jnp.sum(w.astype(jnp.int64))
    return s, c


def masked_min(values, row_mask, valid=None):
    w = row_mask if valid is None else (row_mask & valid)
    m = jnp.min(jnp.where(w, values, _max_ident(values.dtype)))
    return m, jnp.any(w)


def masked_max(values, row_mask, valid=None):
    w = row_mask if valid is None else (row_mask & valid)
    m = jnp.max(jnp.where(w, values, _min_ident(values.dtype)))
    return m, jnp.any(w)
