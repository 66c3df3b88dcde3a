"""External (memory-bounded) sort: range-bucket multi-pass.

Role of the reference's UnsafeExternalSorter + SortExec spill path
(corej/util/collection/unsafe/sort/UnsafeExternalSorter.java,
sqlx/SortExec.scala) — redesigned for the TPU memory model. Disk is not
the scarce resource here, HBM is: instead of run-merge (k-way merges are
control-flow-hostile on a systolic machine), the partition is
range-bucketed by the leading sort key — the same device kernel as the
range exchange (ops/partition.range_partition) — into host buffers, and
each bucket (which fits the device budget) is sorted independently with
the full multi-key kernel. Equal leading keys always share a bucket
(searchsorted), so bucket order × in-bucket order = total order, and no
merge pass exists at all.

Null leading keys route to the first/last bucket per nulls_first, NaNs
follow the same IEEE placement the in-tile kernel uses, and a bucket that
still exceeds the budget (pathological leading-key skew) is sorted whole
with a metrics flag rather than failing.
"""

from __future__ import annotations

import numpy as np

from ..columnar.batch import ColumnarBatch, bucket_capacity
from ..exec.shuffle import _OutBuffer, _pull_sorted, _slice_into
from ..types import StringType

_SAMPLE_PER_BATCH = 4096
_MAX_BUCKETS = 1 << 10


def _jnp():
    import jax.numpy as jnp

    return jnp


def _batch_numeric_samples(b: ColumnarBatch, kpos: int) -> np.ndarray:
    """Leading-sort-key samples for one batch, memoized per device-array
    identity (utils/device_memo.memo_device_scalars): repeated external
    sorts over device-cached batches pull samples to host once, not once
    per batch per pass. Treat the returned array as immutable."""
    from ..utils.device_memo import device_read, memo_device_scalars

    col = b.columns[kpos]

    def compute():
        mask, keys, valid = device_read("sort.sample", b.row_mask,
                                        col.sort_keys(), col.validity)
        mask = np.asarray(mask, dtype=bool)
        keys = keys[mask]
        if valid is not None:
            keys = keys[valid[mask]]
        if keys.dtype.kind == "f":
            keys = keys[~np.isnan(keys)]
        return keys[:_SAMPLE_PER_BATCH]

    return memo_device_scalars(("extsort_sample", kpos),
                                (col.data, col.validity, b.row_mask),
                                compute)


def _sample_numeric_bounds(part, kpos: int, num_buckets: int):
    """Quantile bounds in the sort-key domain from per-batch samples."""
    samples = [_batch_numeric_samples(b, kpos) for b in part]
    allv = np.concatenate(samples) if samples else np.zeros(0)
    if allv.size == 0:
        return None
    s = np.sort(allv)
    qs = (np.arange(1, num_buckets) * len(s)) // num_buckets
    return np.unique(s[qs])


def _batch_string_samples(b: ColumnarBatch, kpos: int) -> tuple:
    """Live non-null string samples for one batch, memoized like the
    numeric path (selection_indices syncs the mask otherwise)."""
    from ..utils.device_memo import device_read, memo_device_scalars

    col = b.columns[kpos]

    def compute():
        # one transfer; the host copies are what the two reads below see
        device_read("sort.sample", b.row_mask, col.data, col.validity)
        sel = b.selection_indices()[:_SAMPLE_PER_BATCH]
        vals = col.to_numpy(sel)
        return tuple(v for v in vals if v is not None)

    return memo_device_scalars(("extsort_sample_str", kpos),
                                (col.data, col.validity, b.row_mask),
                                compute)


def _sample_string_bounds(part, kpos: int, num_buckets: int):
    samples: list = []
    for b in part:
        samples.extend(_batch_string_samples(b, kpos))
    if not samples:
        return None
    s = sorted(samples)
    qs = (np.arange(1, num_buckets) * len(s)) // num_buckets
    return sorted(set(s[q] for q in qs))


def external_sort(part, orders, schema, child_output, ctx,
                  budget_rows: int, sort_single):
    """Sort one partition whose total capacity exceeds ``budget_rows``.

    Returns an ordered list of sorted ColumnarBatches (bucket order).
    ``sort_single(list_of_batches) -> ColumnarBatch`` is the in-budget
    single-tile sort (SortExec's kernel)."""

    from ..ops.partition import _group_by_pid
    from .compile import GLOBAL_KERNEL_CACHE, stage_jit

    jnp = _jnp()
    total_cap = sum(b.capacity for b in part)
    num_buckets = min(_MAX_BUCKETS,
                      2 * max(2, -(-total_cap // max(budget_rows, 1))))
    first = orders[0]
    kpos = next(i for i, a in enumerate(child_output)
                if a.expr_id == first.child.expr_id)
    string_key = isinstance(schema.fields[kpos].dataType, StringType)

    bounds = (_sample_string_bounds(part, kpos, num_buckets) if string_key
              else _sample_numeric_bounds(part, kpos, num_buckets))
    if bounds is None or len(bounds) == 0:
        # all-null / empty leading key: one bucket == plain sort
        return [sort_single(part)]
    B = len(bounds) + 1
    null_pid = 0 if first.nulls_first else B - 1
    descending = not first.ascending

    bufs = [_OutBuffer(schema, spill_bytes=ctx.memory.spill_bytes,
                       spill_dir=ctx.memory.spill_dir, metrics=ctx.metrics)
            for _ in range(B)]
    for batch in part:
        col = batch.columns[kpos]
        cap = batch.capacity
        has_valid = col.validity is not None
        if string_key:
            sd_vals = np.array(list(col.dictionary.values)
                               if col.dictionary else [], dtype=object)
            lut = np.searchsorted(np.array(bounds, dtype=object), sd_vals,
                                  side="right").astype(np.int32)
            if descending:
                lut = (B - 1) - lut
            if len(lut) == 0:
                lut = np.zeros(1, np.int32)
            lut_d = jnp.asarray(lut)
            kkey = ("extsort_pid_str", cap, B, has_valid, null_pid)

            def build_str():
                def kernel(lut_d, codes, valid, mask):
                    pids = jnp.take(lut_d,
                                    jnp.clip(codes, 0, lut_d.shape[0] - 1))
                    if has_valid:
                        pids = jnp.where(valid, pids, null_pid)
                    return _group_by_pid(pids, mask, B)

                return stage_jit(kernel)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(kkey, build_str)
            pr = kernel(lut_d, col.data,
                        col.validity if has_valid else jnp.zeros(0, bool),
                        batch.row_mask)
        else:
            keys = col.sort_keys()
            kkey = ("extsort_pid", cap, B, str(keys.dtype), has_valid,
                    null_pid, descending)

            def build_num():
                def kernel(bounds_d, keys, valid, mask):
                    pids = jnp.searchsorted(
                        bounds_d, keys, side="right").astype(jnp.int32)
                    if descending:
                        pids = (B - 1) - pids
                    if has_valid:
                        pids = jnp.where(valid, pids, null_pid)
                    return _group_by_pid(pids, mask, B)

                return stage_jit(kernel)

            kernel = GLOBAL_KERNEL_CACHE.get_or_build(kkey, build_num)
            pr = kernel(jnp.asarray(bounds), keys,
                        col.validity if has_valid else jnp.zeros(0, bool),
                        batch.row_mask)
        gathered, counts = _pull_sorted(batch, pr.perm, pr.counts)
        _slice_into(bufs, gathered, counts)

    ctx.memory.count("sort.external.passes")
    tile = bucket_capacity(max(budget_rows, 1))
    out = []
    for buf in bufs:
        if buf.rows == 0:
            continue
        if buf.rows > budget_rows:
            ctx.memory.count("sort.external.oversizedBucket")
        out.append(sort_single(buf.build(tile)))
    if not out:
        out.append(ColumnarBatch.empty(schema))
    return out
