"""Sort-based grouped aggregation kernel.

Role of the reference's HashAggregateExec + UnsafeFixedWidthAggregationMap
(sqlx/aggregate/HashAggregateExec.scala:50, corej/unsafe/map/BytesToBytesMap.java)
and its sort-based fallback (TungstenAggregationIterator). TPU-native design:
no hash table at all — `lax.sort` (bitonic/radix, MXU-adjacent, fully
data-parallel) groups equal keys adjacently, then each run of equal keys is
reduced. Static shapes throughout: output has the same capacity as input
(worst case all rows distinct) with a row mask for live groups.

What follows the sort has two bodies, and `group_aggregate` is the one door
to both. The *scatter* body is the plain one: gather every column into
sorted order by `perm`, `segment_sum` into `cap` segments, write each group's
key to slot `seg_id` with `.at[].set`. Every step of it is a per-element
gather or scatter of `cap` scalars, and a TPU moves scalars one at a time:
at 8 Mi slots on a v5e one sum and its count were 2.28 s and one key with
its validity 0.25 s, and the hints that are true there
(`indices_are_sorted`, `unique_indices`) bought nothing. The *scan* body
never addresses by a computed index (the same sum and count in 0.04 s).
The values travel through the group sort as operands, so they come out in
sorted order, as the keys do; a sum or a count is an inclusive `cumsum`,
whose value before each group's first row is carried, with the group's
keys, to slot `seg_id` by ONE single-key `lax.sort` on "the slot, for a
first row"; a group's total is then the difference of two adjacent slots.
For 64-bit integer accumulators that difference is exact modulo 2^64
whatever the running sum did, which is all a scatter-add promises too. A
floating sum must not be a difference of running sums (cancellation), so
it keeps the scatter-add, as min/max/first/bit ops do. `segment_path` picks
the body from the static capacity: each `lax.sort` costs the TPU compiler
20-60 s and more with every operand, so small capacities keep the scatter
body, and the scan body's sorts are kept narrow (validity planes as the
bits of one word, no key sent twice).
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


def group_rows(key_cols: Sequence[jnp.ndarray],
               key_valids: Sequence[jnp.ndarray | None],
               row_mask: jnp.ndarray) -> GroupLayout:
    """Sort rows so equal keys (SQL semantics: null == null, inactive rows
    last) are adjacent; derive segment structure."""
    return _group_sort(key_cols, key_valids, row_mask, None)[0]


@jax.named_scope("group_sort")
def _group_sort(key_cols, key_valids, row_mask, carry):
    """`group_rows`, with the arrays of `carry` sent through the sort as
    operands: (layout, the keys as sorted [(data, validity | None)] with
    zeros under a NULL, `carry` as sorted). With None for a `carry` this
    is the scatter body's sort, which gathers what it needs by `perm`."""
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
    sorted_ops = lax.sort((*operands, *(carry or ())), num_keys=num_keys,
                          is_stable=True)
    perm = sorted_ops[num_keys]
    skeys = sorted_ops[:num_keys]
    # the sorted flag is the mask in sorted order; the scatter body keeps
    # its gather, so that its programs stay the ones that are compiled
    active = jnp.take(row_mask, perm) if carry is None else skeys[0] == 0

    changed = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for k in skeys:
        diff = jnp.concatenate([jnp.ones(1, dtype=bool), k[1:] != k[:-1]])
        changed = changed | diff
    start_flag = changed & active
    seg_ids = jnp.cumsum(start_flag.astype(jnp.int32)) - 1
    seg_ids = jnp.maximum(seg_ids, 0)
    num_groups = jnp.sum(start_flag.astype(jnp.int32))
    keys, at = [], 1
    for v in key_valids:
        keys.append((skeys[at], None) if v is None
                    else (skeys[at + 1], skeys[at] == 0))
        at += 1 if v is None else 2
    return (GroupLayout(perm, seg_ids, start_flag, active, num_groups),
            keys, list(sorted_ops[num_keys + 1:]))


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


# What each body costs on a TPU v5e, in seconds (PERF.md §6, PR 30; the
# probe is `.probe/segment_probe.py`, git-ignored as `.probe/` is).
# SCATTER_S: a slot through one scatter-add of the scatter body, or one
# scatter by a permutation (77-80 ns at 1 Mi slots, 136-142 ns at 8 Mi; a
# group key's gather and scatter are 29 ns). SCAN_S: a slot through the
# scans and the sort that stand in their place (4.5 ns for a sum and its
# count, 3 ns for a permutation undone). SORT_FIXED_S: what the scan body
# must save a run to be worth a `lax.sort`, which costs the TPU compiler
# 19-40 s and 15-35 s more for each operand beyond three; as
# `ops/joining.MERGE_FIXED_S` is. The crossover is at 660 k slots: 1 Mi and
# 8 Mi (the window reports' flows) scan, 128 Ki (q3's and q7's) does not.
SCATTER_S = 80e-9
SCAN_S = 5e-9
SORT_FIXED_S = 0.05


def segment_path(cap: int, acc_dtype=None) -> str:
    """`"scan"` or `"scatter"`: the cheaper body for one step over a
    sorted-segment layout of `cap` slots (a reduce, a group's keys, a
    window's frame, a permutation undone), known at trace time. A
    floating accumulator never takes the scan: its totals would be
    differences of a running sum."""
    if acc_dtype is not None and jnp.issubdtype(acc_dtype, jnp.floating):
        return "scatter"
    scatter = cap * SCATTER_S
    scan = SORT_FIXED_S + cap * SCAN_S
    return "scan" if scan < scatter else "scatter"


def _scan_reduces(op: str, values) -> bool:
    """Whether the scan body reduces `op` itself: counts, and sums into the
    integer accumulator. The other ops keep `apply_group_ops`."""
    return op in ("count", "countstar") or (
        op == "sum" and not jnp.issubdtype(values.dtype, jnp.floating))


def _place(items: list, x) -> int:
    """`x`'s place in `items` (the very array, not an equal one), at the
    end if it is not there yet."""
    for at, have in enumerate(items):
        if have is x:
            return at
    items.append(x)
    return len(items) - 1


class _Operands(list):
    """Arrays bound for a sort's operands, each once. A `lax.sort` costs
    the TPU compiler more than in proportion to its operands, so the
    validity planes travel as the bits of one word."""

    def __init__(self):
        super().__init__()
        self.planes: list = []

    def place(self, x) -> int:
        return _place(self, x)

    def place_plane(self, x) -> int | None:
        return None if x is None else _place(self.planes, x)

    def words(self) -> list:
        """The planes, 31 to an int32."""
        out = []
        for at in range(0, len(self.planes), 31):
            word = jnp.int32(0)
            for bit, plane in enumerate(self.planes[at:at + 31]):
                word = word | (plane.astype(jnp.int32) << bit)
            out.append(word)
        return out

    @staticmethod
    def plane(words, at: int):
        return (words[at // 31] >> (at % 31)) & 1 == 1


def group_aggregate(key_eqs, key_valids, key_outs, row_mask,
                    ops: Sequence[str], val_datas, val_valids,
                    path: str | None = None):
    """GROUP BY in one call: rows grouped by `key_eqs` (with `key_valids`,
    null == null), each group's `key_outs` (None: the key it was grouped
    by; with that key's validity, and anything under a NULL) and its
    reduction of every (op, values, validity) triple in output slot
    `seg_id`. Returns ([(key, validity | None)], [(buffer, validity |
    None)], mask of live groups, number of groups). `path` forces a body,
    for the tests; callers leave it to `segment_path`."""
    cap = row_mask.shape[0]
    if (path or segment_path(cap)) == "scatter":
        layout = group_rows(key_eqs, key_valids, row_mask)
        out_keys = [scatter_group_keys(layout, ke if ko is None else ko, kv)
                    for ko, ke, kv in zip(key_outs, key_eqs, key_valids)]
        bufs = apply_group_ops(layout, ops, val_datas, val_valids)
        return out_keys, bufs, group_output_mask(layout), layout.num_groups

    # what the scans read goes through the group sort, and an output key
    # that is not its own sort key (the sort's output has those)
    carry = _Operands()
    scanned = [_scan_reduces(op, vd) for op, vd in zip(ops, val_datas)]
    val_at = [(carry.place(vd) if op == "sum" else None,
               carry.place_plane(vv) if op != "countstar" else None)
              for op, vd, vv, scans in zip(ops, val_datas, val_valids,
                                           scanned) if scans]
    sent_key_at = [None if ko is None else carry.place(ko)
                   for ko in key_outs]
    layout, skeys, carried = _group_sort(key_eqs, key_valids, row_mask,
                                         carry + carry.words())
    planes = carried[len(carry):]
    out_mask = group_output_mask(layout)

    with jax.named_scope("segment_reduce"):
        # running sums, each taken BEFORE its row: at a group's first row
        # that is the sum over every group before it
        before, grand = [], []
        count_of: dict = {}    # a validity plane -> its count's place
        sums = []              # per scanned op: (sum's place | None, count's)
        for di, vi in val_at:
            w = layout.active if vi is None \
                else layout.active & carry.plane(planes, vi)
            if vi not in count_of:
                c = jnp.cumsum(w, dtype=jnp.int32)
                count_of[vi] = len(before)
                before.append(c - w)
                grand.append(c[cap - 1])
            if di is None:
                sums.append((None, count_of[vi]))
                continue
            x = jnp.where(w, carried[di].astype(jnp.int64), jnp.int64(0))
            c = jnp.cumsum(x)
            sums.append((len(before), count_of[vi]))
            before.append(c - x)
            grand.append(c[cap - 1])
        rest = [i for i, scans in enumerate(scanned) if not scans]
        rest_bufs = apply_group_ops(
            layout, [ops[i] for i in rest], [val_datas[i] for i in rest],
            [val_valids[i] for i in rest])

    # ONE sort brings every group's first row to slot seg_id (the other
    # rows go behind them, in no order: a stable sort compiles for twice as
    # long); the running sums and the group's keys ride along
    with jax.named_scope("group_keys"):
        keys = _Operands()
        key_at = [(keys.place(sk if at is None else carried[at]),
                   keys.place_plane(sv))
                  for at, (sk, sv) in zip(sent_key_at, skeys)]
        firsts = lax.sort(
            (jnp.where(layout.start_flag, layout.seg_ids, cap), *before,
             *keys, *keys.words()), num_keys=1, is_stable=False)[1:]
        key_cols = firsts[len(before):]
        null_words = key_cols[len(keys):]
        out_keys = [
            (jnp.where(out_mask, key_cols[di],
                       jnp.zeros((), key_cols[di].dtype)),
             None if vi is None else out_mask & keys.plane(null_words, vi))
            for di, vi in key_at]

    with jax.named_scope("segment_reduce"):
        # a group's total: what came before the next group's first row (the
        # grand total for the last group), less what came before its own
        slot = lax.iota(jnp.int32, cap)
        totals = []
        for first, whole in zip(firsts, grand):
            nxt = jnp.concatenate([first[1:], first[:1]])
            nxt = jnp.where(slot + 1 == layout.num_groups, whole, nxt)
            totals.append(jnp.where(out_mask, nxt - first,
                                    jnp.zeros((), first.dtype)))
        bufs, sums, rest_bufs = [], iter(sums), iter(rest_bufs)
        for scans in scanned:
            if not scans:
                bufs.append(next(rest_bufs))
                continue
            si, ci = next(sums)
            bufs.append((totals[ci].astype(jnp.int64), None) if si is None
                        else (totals[si], totals[ci] > 0))
    return out_keys, bufs, out_mask, layout.num_groups


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
