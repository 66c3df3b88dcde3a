"""Window function kernels.

Role of the reference's WindowExec + window function frames
(sqlx/window/WindowExec.scala, sqlcat/expressions/windowExpressions.scala).
TPU-native design: one `lax.sort` by (partition keys, order keys) makes
partitions and peer groups contiguous; every ranking/frame computation is
then a cumsum/segment-op over the sorted layout, and results go back to the
original row order. No per-row loops, no frame iterators.

Two steps have two bodies, picked by `ops/grouping.segment_path` from the
static capacity as the aggregate's are (a TPU scatters and gathers scalars
one at a time; scans and sorts run at memory speed; a `lax.sort` costs the
TPU compiler 20-60 s, so small capacities keep the plain bodies):

  a partition's count/sum/avg (`w_agg_unbounded`) — `segment_sum` into
    `cap` segments and a gather back by `seg_id`, or an inclusive `cumsum`
    whose value at the partition's last row, less its value before the
    first, is carried to every row of the partition by `cummax` scans
    (`_carried`). Exact for the 64-bit integer accumulators, modulo 2^64
    as the scatter-add is; a floating sum would be a difference of running
    sums, so it keeps the scatter-add;
  the way back (`scatter_back`) — `zeros.at[perm].set`, or a sort on
    `perm`: a permutation's inverse is a sort on it.

Default frames (Spark semantics):
  ranking fns — whole partition by definition;
  aggregates with ORDER BY — RANGE UNBOUNDED PRECEDING..CURRENT ROW
    (peer rows share the value);
  aggregates without ORDER BY — whole partition.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .grouping import segment_path
from .sorting import SortKeySpec, _directional


class WindowLayout(NamedTuple):
    perm: jnp.ndarray        # sorted-row → original-row index
    active: jnp.ndarray      # bool per sorted row
    pos: jnp.ndarray         # int32 global position
    seg_start: jnp.ndarray   # int32 per sorted row: position of partition start
    seg_id: jnp.ndarray      # int32 partition id per sorted row
    peer_id: jnp.ndarray     # int32 peer-group id per sorted row
    peer_first: jnp.ndarray  # position of first row of the peer group
    peer_last: jnp.ndarray   # position of last row of the peer group
    seg_size: jnp.ndarray    # int32 rows in the partition
    seg_first: jnp.ndarray   # bool per sorted row: first row of its partition


# Scopes, by the pattern of ops/joining.py: the one sort, the frame
# computations and the scatter each carry a `jax.named_scope`, so that a
# profile of a program that traced them (the per-partition kernel, the
# whole-query program's `mNN.Window`) says where a window's time goes.

@jax.named_scope("layout_sort")
def build_layout(part_keys: Sequence[jnp.ndarray],
                 part_valids: Sequence[jnp.ndarray | None],
                 order_keys: Sequence[jnp.ndarray],
                 order_valids: Sequence[jnp.ndarray | None],
                 order_specs: Sequence[SortKeySpec],
                 row_mask: jnp.ndarray) -> WindowLayout:
    cap = row_mask.shape[0]
    # one flags operand for all the partition keys: dead rows last, and a
    # bit a key that is null there. The order of the partitions among
    # themselves is nobody's business, so the null flags need no place of
    # their own between the keys, and every sort key less is minutes of
    # the TPU compiler's time (a 13-key sort compiles five times as long
    # as a 6-key one)
    ft = jnp.int32 if len(part_keys) < 31 else jnp.int64
    flags = (~row_mask).astype(ft) << len(part_keys)
    operands: list[jnp.ndarray] = [flags]
    for i, (k, v) in enumerate(zip(part_keys, part_valids)):
        if v is not None:
            flags = flags | ((~v).astype(ft) << i)
            k = jnp.where(v, k, jnp.zeros_like(k))
        operands.append(k)
    operands[0] = flags
    n_pkeys_ops = len(part_keys)
    for k, v, s in zip(order_keys, order_valids, order_specs):
        if v is not None:
            nf = s.nulls_first_effective
            operands.append((v if nf else ~v).astype(jnp.int32))
            k = jnp.where(v, k, jnp.zeros_like(k))
        operands.append(_directional(k, s.ascending))
    nk = len(operands)
    operands.append(lax.iota(jnp.int32, cap))
    out = lax.sort(tuple(operands), num_keys=nk, is_stable=True)
    perm = out[-1]
    sorted_keys = out[:nk]
    active = jnp.take(row_mask, perm)
    pos = lax.iota(jnp.int32, cap)

    def change_flag(keys):
        flag = jnp.zeros(cap, dtype=bool).at[0].set(True)
        for k in keys:
            flag = flag | jnp.concatenate(
                [jnp.ones(1, dtype=bool), k[1:] != k[:-1]])
        return flag

    pchange = change_flag(sorted_keys[: 1 + n_pkeys_ops])
    ochange = pchange | change_flag(sorted_keys)  # any key change

    seg_id = jnp.cumsum(pchange.astype(jnp.int32)) - 1
    peer_id = jnp.cumsum(ochange.astype(jnp.int32)) - 1

    seg_start_by_id = jnp.full((cap,), 0, jnp.int32).at[
        jnp.where(pchange, seg_id, cap)].set(pos, mode="drop")
    seg_start = jnp.take(seg_start_by_id, seg_id)
    peer_first_by_id = jnp.full((cap,), 0, jnp.int32).at[
        jnp.where(ochange, peer_id, cap)].set(pos, mode="drop")
    peer_first = jnp.take(peer_first_by_id, peer_id)
    peer_last_by_id = jax.ops.segment_max(pos, peer_id, num_segments=cap)
    peer_last = jnp.take(peer_last_by_id, peer_id)
    seg_size = jax.ops.segment_sum(active.astype(jnp.int32), seg_id,
                                   num_segments=cap)
    seg_size = jnp.take(seg_size, seg_id)
    return WindowLayout(perm, active, pos, seg_start, seg_id, peer_id,
                        peer_first, peer_last, seg_size, pchange)


# --- per-function computations (all return values in SORTED order) ---------

def w_row_number(lo: WindowLayout):
    return (lo.pos - lo.seg_start + 1).astype(jnp.int32)


def w_rank(lo: WindowLayout):
    return (lo.peer_first - lo.seg_start + 1).astype(jnp.int32)


def w_dense_rank(lo: WindowLayout):
    start_peer = jnp.take(lo.peer_id, lo.seg_start)
    return (lo.peer_id - start_peer + 1).astype(jnp.int32)


def w_percent_rank(lo: WindowLayout):
    denom = jnp.maximum(lo.seg_size - 1, 1)
    return (w_rank(lo) - 1).astype(jnp.float64) / denom


def w_cume_dist(lo: WindowLayout):
    return (lo.peer_last - lo.seg_start + 1).astype(jnp.float64) / \
        jnp.maximum(lo.seg_size, 1)


def w_ntile(lo: WindowLayout, n: int):
    rn0 = (lo.pos - lo.seg_start).astype(jnp.int64)
    return (rn0 * n // jnp.maximum(lo.seg_size, 1) + 1).astype(jnp.int32)


def _float_avg(total, cnt):
    """AVG as the float64 quotient; NULL over no value. The operator
    passes its own `avg` to the frame kernels (physical/window.py: the
    aggregate's finishing expression), so that a window's average and a
    GROUP BY's are one computation, to one type."""
    return total.astype(jnp.float64) / jnp.maximum(cnt, 1), cnt > 0


def _agg_result(kind: str, total, cnt, avg):
    """count / sum / avg of a frame from its total and its count of
    non-null values (None for min/max, which the caller finishes)."""
    if kind == "count":
        return cnt, None
    if kind == "sum":
        return total, cnt > 0
    if kind == "avg":
        return (avg or _float_avg)(total, cnt)
    return None


def _sorted_vals(lo: WindowLayout, values, valid):
    v = jnp.take(values, lo.perm)
    w = lo.active if valid is None else (lo.active & jnp.take(valid, lo.perm))
    return v, w


def unbounded_path(kind: str, value_dtype, cap: int) -> str | None:
    """The body `w_agg_unbounded` takes for the frame kind
    `agg_unbounded_<fn>` over values of `value_dtype`: `"scan"` or
    `"scatter"`; None for a frame that has no such choice."""
    if kind not in ("agg_unbounded_count", "agg_unbounded_sum",
                    "agg_unbounded_avg"):
        return None
    return segment_path(cap, _acc_dtype(value_dtype))


def _acc_dtype(dtype):
    return jnp.float64 if jnp.issubdtype(dtype, jnp.floating) else jnp.int64


def w_agg_unbounded(lo: WindowLayout, values, valid, kind: str, avg=None,
                    path: str | None = None):
    """sum/count/min/max/avg over the whole partition, broadcast to rows.
    `path` forces a body of count/sum/avg, for the tests; callers leave it
    to `segment_path`."""
    cap = values.shape[0]
    v, w = _sorted_vals(lo, values, valid)
    acc = _acc_dtype(v.dtype)
    if kind in ("count", "sum", "avg"):
        if (path or segment_path(cap, acc)) == "scan":
            c = _partition_total(lo, w.astype(jnp.int32)).astype(jnp.int64)
            s = c if kind == "count" else _partition_total(
                lo, jnp.where(w, v.astype(acc), 0))
            return _agg_result(kind, s, c, avg)     # per row already
        c = jax.ops.segment_sum(w.astype(jnp.int64), lo.seg_id, cap)
        s = c if kind == "count" else jax.ops.segment_sum(
            jnp.where(w, v.astype(acc), 0), lo.seg_id, cap)
        d, dv = _agg_result(kind, s, c, avg)    # per partition, then spread
        return jnp.take(d, lo.seg_id), \
            None if dv is None else jnp.take(dv, lo.seg_id)
    from .grouping import _max_ident, _min_ident

    if kind == "min":
        m = jax.ops.segment_min(jnp.where(w, v, _max_ident(v.dtype)),
                                lo.seg_id, cap)
    else:
        m = jax.ops.segment_max(jnp.where(w, v, _min_ident(v.dtype)),
                                lo.seg_id, cap)
    c = jax.ops.segment_sum(w.astype(jnp.int32), lo.seg_id, cap)
    return jnp.take(m, lo.seg_id), jnp.take(c, lo.seg_id) > 0


def _partition_total(lo: WindowLayout, x):
    """Each row's partition's sum of the integers `x`, by scans alone: the
    running sum at the partition's last row, less the running sum before its
    first, each carried to the partition's other rows. Exact modulo 2^64."""
    run = jnp.cumsum(x)
    last = jnp.concatenate([lo.seg_first[1:], jnp.ones(1, dtype=bool)])
    return _carried(last, run, reverse=True) - _carried(lo.seg_first, run - x)


def _carried(flag, x, reverse: bool = False):
    """`x` at the nearest flagged slot at or before each slot (at or after
    it, `reverse`d); slot 0 (the last slot) is flagged. A cummax carries a
    value along only if the values never fall, so each half of `x` rides
    below its slot's number, which never does."""
    cap = x.shape[0]
    rank = lax.iota(jnp.int64, cap)
    if reverse:
        rank = cap - 1 - rank
    wide = x.astype(jnp.int64)
    halves = []
    for half in (wide & 0xFFFFFFFF, (wide >> 32) & 0xFFFFFFFF):
        key = jnp.where(flag, (rank << 32) | half, -1)
        halves.append(lax.cummax(key, axis=0, reverse=reverse) & 0xFFFFFFFF)
    return (halves[0] | (halves[1] << 32)).astype(x.dtype)


def w_agg_running(lo: WindowLayout, values, valid, kind: str, avg=None):
    """RANGE UNBOUNDED PRECEDING..CURRENT ROW (peers share the value)."""
    cap = values.shape[0]
    v, w = _sorted_vals(lo, values, valid)
    acc = _acc_dtype(v.dtype)
    vv = jnp.where(w, v.astype(acc), 0)
    csum = jnp.cumsum(vv)
    ccnt = jnp.cumsum(w.astype(jnp.int64))
    before_seg_sum = jnp.where(lo.seg_start > 0,
                               jnp.take(csum, jnp.maximum(lo.seg_start - 1, 0)),
                               0)
    before_seg_cnt = jnp.where(lo.seg_start > 0,
                               jnp.take(ccnt, jnp.maximum(lo.seg_start - 1, 0)),
                               0)
    run_sum = jnp.take(csum, lo.peer_last) - before_seg_sum
    run_cnt = jnp.take(ccnt, lo.peer_last) - before_seg_cnt
    if kind in ("count", "sum", "avg"):
        return _agg_result(kind, run_sum, run_cnt, avg)
    # running min/max via cummin/cummax reset at segment start: use
    # associative_scan over (value, seg_id) pairs
    big = jnp.where(w, v, _ident(kind, v.dtype))

    def combine(a, b):
        av, aseg = a
        bv, bseg = b
        same = aseg == bseg
        if kind == "min":
            m = jnp.minimum(av, bv)
        else:
            m = jnp.maximum(av, bv)
        return jnp.where(same, m, bv), bseg

    scanned, _ = lax.associative_scan(combine, (big, lo.seg_id))
    run = jnp.take(scanned, lo.peer_last)
    return run, run_cnt > 0


def w_agg_rows(lo: WindowLayout, values, valid, kind: str,
               lo_off, hi_off, avg=None):
    """ROWS BETWEEN <lo_off> AND <hi_off> frame for sum/count/avg, via
    segment-clipped cumulative sums. Offsets are row deltas relative to the
    current row; None means unbounded on that side."""
    import jax

    cap = values.shape[0]
    v, w = _sorted_vals(lo, values, valid)
    acc = _acc_dtype(v.dtype)
    vv = jnp.where(w, v.astype(acc), 0)
    csum = jnp.cumsum(vv)
    ccnt = jnp.cumsum(w.astype(jnp.int64))
    seg_end = lo.seg_start + lo.seg_size - 1

    lo_idx = lo.seg_start if lo_off is None else \
        jnp.maximum(lo.pos + lo_off, lo.seg_start)
    hi_idx = seg_end if hi_off is None else \
        jnp.minimum(lo.pos + hi_off, seg_end)
    empty = hi_idx < lo_idx

    def rng(c):
        hi_v = jnp.take(c, jnp.clip(hi_idx, 0, cap - 1))
        lo_m1 = lo_idx - 1
        lo_v = jnp.where(lo_m1 >= 0,
                         jnp.take(c, jnp.clip(lo_m1, 0, cap - 1)), 0)
        return jnp.where(empty, 0, hi_v - lo_v)

    total = rng(csum)
    cnt = rng(ccnt)
    if kind in ("count", "sum", "avg"):
        return _agg_result(kind, total, cnt, avg)
    if kind in ("min", "max"):
        return _range_minmax(v, w, lo_idx, hi_idx, empty, kind), cnt > 0
    raise ValueError(kind)


def w_agg_value_range(lo: WindowLayout, order_key, values, valid, kind: str,
                      lo_off, hi_off, kmin: int, band: int, avg=None):
    """RANGE BETWEEN <lo_off> AND <hi_off> with VALUE offsets over a single
    integral order key. Keys are banded per partition —
    enc = seg_id·band + (key − kmin) — so one global `searchsorted` finds
    each row's value-window inside its own partition (band exceeds the key
    span plus the largest offset, so queries never cross partitions)."""
    import jax

    cap = values.shape[0]
    k = jnp.take(order_key, lo.perm).astype(jnp.int64)
    enc = lo.seg_id.astype(jnp.int64) * band + (k - kmin)
    lo_q = enc + (lo_off if lo_off is not None else -(band - 1))
    hi_q = enc + (hi_off if hi_off is not None else (band - 1))
    lo_idx = jnp.searchsorted(enc, lo_q, side="left").astype(jnp.int32)
    hi_idx = (jnp.searchsorted(enc, hi_q, side="right") - 1).astype(jnp.int32)
    seg_end = lo.seg_start + lo.seg_size - 1
    lo_idx = jnp.maximum(lo_idx, lo.seg_start)
    hi_idx = jnp.minimum(hi_idx, seg_end)
    empty = hi_idx < lo_idx

    v, w = _sorted_vals(lo, values, valid)
    acc = _acc_dtype(v.dtype)
    csum = jnp.cumsum(jnp.where(w, v.astype(acc), 0))
    ccnt = jnp.cumsum(w.astype(jnp.int64))

    def rng(c):
        hi_v = jnp.take(c, jnp.clip(hi_idx, 0, cap - 1))
        lo_m1 = lo_idx - 1
        lo_v = jnp.where(lo_m1 >= 0,
                         jnp.take(c, jnp.clip(lo_m1, 0, cap - 1)), 0)
        return jnp.where(empty, 0, hi_v - lo_v)

    total = rng(csum)
    cnt = rng(ccnt)
    if kind in ("count", "sum", "avg"):
        return _agg_result(kind, total, cnt, avg)
    if kind in ("min", "max"):
        return _range_minmax(v, w, lo_idx, hi_idx, empty, kind), cnt > 0
    raise ValueError(kind)


def _ident(kind, dtype):
    from .grouping import _max_ident, _min_ident

    return _max_ident(dtype) if kind == "min" else _min_ident(dtype)


def _range_minmax(v, w, lo_idx, hi_idx, empty, kind):
    """min/max over per-row index ranges [lo_idx, hi_idx] of the sorted
    value array, via a sparse table (doubling): level j holds the reduce
    of windows of length 2^j — O(n log n) fully vectorized build, O(1)
    two-window query per row. This is the TPU analog of the reference's
    per-row frame scan (sqlx/window/WindowFunctionFrame SlidingWindow)."""
    cap = v.shape[0]
    ident = _ident(kind, v.dtype)
    op = jnp.minimum if kind == "min" else jnp.maximum
    a = jnp.where(w, v, ident)
    levels = [a]
    step = 1
    while step < cap:
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[step:], jnp.full((step,), ident, prev.dtype)])
        levels.append(op(prev, shifted))
        step <<= 1
    sp = jnp.stack(levels)  # [L, cap]
    length = jnp.maximum(hi_idx - lo_idx + 1, 1)
    k = jnp.floor(
        jnp.log2(length.astype(jnp.float64))).astype(jnp.int32)
    # integer-exact guard against float log sloppiness: need 2^k <= length
    k = jnp.clip(jnp.where((1 << k) > length, k - 1, k),
                 0, len(levels) - 1)
    p1 = sp[k, jnp.clip(lo_idx, 0, cap - 1)]
    p2_at = jnp.clip(hi_idx - (1 << k) + 1, 0, cap - 1)
    return jnp.where(empty, ident, op(p1, sp[k, p2_at]))


def w_shift(lo: WindowLayout, values, valid, offset: int,
            default_data=None):
    """lag (offset>0) / lead (offset<0) within the partition."""
    cap = values.shape[0]
    v = jnp.take(values, lo.perm)
    src = lo.pos - offset
    seg_end = lo.seg_start + lo.seg_size - 1
    in_seg = (src >= lo.seg_start) & (src <= seg_end)
    srcc = jnp.clip(src, 0, cap - 1)
    out = jnp.take(v, srcc)
    out_valid = in_seg
    if valid is not None:
        sv = jnp.take(valid, lo.perm)
        out_valid = out_valid & jnp.take(sv, srcc)
    if default_data is not None:
        out = jnp.where(in_seg, out, default_data)
        out_valid = None if valid is None else (out_valid | ~in_seg)
    return out, out_valid


def w_first_value(lo: WindowLayout, values, valid):
    """first_value: the frame's first row — default running frame starts
    at the partition start."""
    v = jnp.take(values, lo.perm)
    out = jnp.take(v, lo.seg_start)
    out_valid = None
    if valid is not None:
        sv = jnp.take(valid, lo.perm)
        out_valid = jnp.take(sv, lo.seg_start)
    return out, out_valid


def w_last_value(lo: WindowLayout, values, valid, whole: bool = False):
    """last_value: the frame's last row — default frame ends at the
    current PEER GROUP's last row; whole=True (explicit
    UNBOUNDED..UNBOUNDED) uses the partition's last row."""
    v = jnp.take(values, lo.perm)
    end = (lo.seg_start + lo.seg_size - 1) if whole else lo.peer_last
    out = jnp.take(v, end)
    out_valid = None
    if valid is not None:
        sv = jnp.take(valid, lo.perm)
        out_valid = jnp.take(sv, end)
    return out, out_valid


def w_nth_value(lo: WindowLayout, values, valid, n: int,
                whole: bool = False):
    """nth_value(x, n): NULL until the frame reaches n rows."""
    cap = values.shape[0]
    v = jnp.take(values, lo.perm)
    idx = lo.seg_start + (n - 1)
    end = (lo.seg_start + lo.seg_size - 1) if whole else lo.peer_last
    exists = idx <= end
    idxc = jnp.clip(idx, 0, cap - 1)
    out = jnp.take(v, idxc)
    out_valid = exists
    if valid is not None:
        sv = jnp.take(valid, lo.perm)
        out_valid = out_valid & jnp.take(sv, idxc)
    return out, out_valid


@jax.named_scope("scatter_back")
def scatter_back(lo: WindowLayout, sorted_vals, sorted_valid=None,
                 path: str | None = None):
    """Sorted-order results → original row order: a scatter by `perm`, or
    a sort on it (a permutation's inverse is a sort on it). `path` forces
    a body, for the tests; callers leave it to `segment_path`."""
    cap = sorted_vals.shape[0]
    if (path or segment_path(cap)) == "scan":
        cols = (sorted_vals,) if sorted_valid is None \
            else (sorted_vals, sorted_valid)
        back = lax.sort((lo.perm, *cols), num_keys=1, is_stable=False)[1:]
        return back[0], None if sorted_valid is None else back[1]
    out = jnp.zeros(cap, dtype=sorted_vals.dtype).at[lo.perm].set(sorted_vals)
    ov = None
    if sorted_valid is not None:
        ov = jnp.zeros(cap, dtype=bool).at[lo.perm].set(sorted_valid)
    return out, ov
