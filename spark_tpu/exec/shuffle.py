"""Shuffle: redistribute rows across partitions.

Role of the reference's sort-based shuffle stack — ShuffleExchangeExec
partition-id computation (sqlx/exchange/ShuffleExchangeExec.scala:344),
SortShuffleManager write paths (core/shuffle/sort/SortShuffleManager.scala:73),
and BlockStoreShuffleReader (core/shuffle/BlockStoreShuffleReader.scala:72).

TPU-native design (SURVEY.md §2.5, §7 step 6): partition ids are computed on
device for a whole batch (hash kernel), rows are grouped by pid with one
`lax.sort`, and the grouped columns cross to the host in a single contiguous
transfer — the host then slices per-partition runs (the "shuffle files") and
rebuilds device batches per reducer. Within a real TPU slice the same kernel
output feeds an ICI all-to-all instead (parallel/collectives.py); this module
is the host/DCN path and the local-mode fallback. String columns travel as
dictionary codes + host dictionaries; reducers merge dictionaries on rebuild.
"""

from __future__ import annotations

import contextlib
from typing import Sequence

import numpy as np

from ..columnar.batch import (Column, ColumnarBatch, EMPTY_DICT,
                              StringDict, bucket_capacity)
from ..exec.context import ExecContext
from ..types import StringType, StructType, dict_encoded
from ..utils.device_memo import device_read

Partition = list


def _jnp():
    import jax.numpy as jnp

    return jnp


class _OutBuffer:
    """Accumulates host-side row slices for one reducer partition.

    Memory discipline (UnsafeExternalSorter.java role): past
    ``spill_bytes`` of accumulated host arrays, the live chunks are
    written to one .npz spill file (dictionaries stay in RAM — they are
    shared references, not copies) and dropped; build() streams spills
    back one file at a time, so peak host memory is
    O(spill_bytes + one tile), not O(partition).

    While the rows are host-side anyway, append() keeps a running
    (min, max, any_valid) per stat column — the map-side column stats.
    build() seeds the dense-range device-scalar memo with them, and in
    cluster mode they ride the MapStatus payload so the reduce side
    seeds the same values after the IPC rebuild: post-shuffle dense
    agg/join decisions never launch the krange3 probe.

    ``stat_cols`` restricts accumulation to the PLAN-REACHABLE dense
    candidates (columns some downstream single-integral-key aggregate or
    join can actually consult — physical/exchange.
    annotate_exchange_stat_cols); None keeps the historical behavior of
    every integral column (bare plans built without the planner). Either
    way the set intersects with integral non-dictionary columns, the
    only ones dense_range_stats reads."""

    def __init__(self, schema: StructType, spill_bytes: int | None = None,
                 spill_dir: str | None = None, metrics=None,
                 stat_cols: list | None = None):
        self.schema = schema
        self.chunks: list[list] = []  # per append: [(data, validity, sdict), ...]
        self.rows = 0
        self.spill_bytes = spill_bytes
        self.spill_dir = spill_dir
        self.metrics = metrics
        self._chunk_rows: list[int] = []
        self._live_bytes = 0
        self.bytes_in = 0      # host bytes appended (codes + validity)
        # per spill: (path, [per-chunk [sdict per col]], [per-chunk rows])
        self._spills: list[tuple] = []
        integral = [
            i for i, f in enumerate(schema.fields)
            if np.dtype(f.dataType.device_dtype).kind == "i"
            and not dict_encoded(f.dataType)]
        self._stat_cols = integral if stat_cols is None else \
            [i for i in integral if i in set(stat_cols)]
        # col index -> (kmin, kmax, any_valid) over every appended row
        self.col_stats: dict[int, tuple] = {
            i: (0, 0, False) for i in self._stat_cols}

    def append(self, cols: list, n: int):
        if not n:
            return
        self.chunks.append(cols)
        self._chunk_rows.append(n)
        self.rows += n
        # bytes moved through the shuffle write (codes + validity
        # planes; dictionaries ride by reference): what compressed
        # execution saves (tests/test_encoded_exec.py compares it)
        shipped = sum(d.nbytes + (v.nbytes if v is not None else 0)
                      for d, v, _ in cols)
        self.bytes_in += shipped
        if self.metrics is not None:
            self.metrics.add("shuffle.bytes_shipped", shipped)
        for i in self._stat_cols:
            d, v, _ = cols[i]
            live = d if v is None else d[v]
            if len(live):
                lo, hi = int(live.min()), int(live.max())
                plo, phi, seen = self.col_stats[i]
                self.col_stats[i] = ((min(plo, lo), max(phi, hi), True)
                                     if seen else (lo, hi, True))
        if self.spill_bytes is not None:
            self._live_bytes += shipped
            if self._live_bytes > self.spill_bytes:
                self._spill()

    def seed_stats(self, batch: ColumnarBatch) -> None:
        """Seed the dense-range memo of one built tile with this
        partition's column stats. The seeded range may be a SUPERSET of
        the tile's own (partition-wide vs per-tile) — sound for the dense
        fast-path decision: kmin only offsets the scatter base and a wider
        span merely widens the table. Partition-wide is deliberate: the
        reduce side of a cluster shuffle seeds the same partition-wide
        values from the MapStatus payload, so local and cluster runs make
        identical dense decisions (the plan analyzer mirrors this)."""
        from ..utils.device_memo import seed_dense_range_memo

        for i, st in self.col_stats.items():
            seed_dense_range_memo(batch.columns[i], batch.row_mask, st)

    def _spill(self):
        import os
        import tempfile

        fd, path = tempfile.mkstemp(suffix=".sparktpu-spill.npz",
                                    dir=self.spill_dir or None)
        os.close(fd)
        arrays = {}
        dicts = []
        for ci, chunk in enumerate(self.chunks):
            dicts.append([sd for _, _, sd in chunk])
            for i, (d, v, _) in enumerate(chunk):
                arrays[f"d{ci}_{i}"] = d
                if v is not None:
                    arrays[f"v{ci}_{i}"] = v
        np.savez(path, **arrays)
        self._spills.append((path, dicts, list(self._chunk_rows)))
        if self.metrics is not None:
            self.metrics.add("shuffle.spill.files")
            self.metrics.add("shuffle.spill.bytes", self._live_bytes)
        self.chunks, self._chunk_rows, self._live_bytes = [], [], 0

    def _iter_chunks(self):
        """Yield (chunk_cols, nrows) in append order, loading spill files
        one at a time."""
        import os

        ncols = len(self.schema.fields)
        for path, dicts, chunk_rows in self._spills:
            with np.load(path, allow_pickle=False) as z:
                for ci, n in enumerate(chunk_rows):
                    chunk = []
                    for i in range(ncols):
                        d = z[f"d{ci}_{i}"]
                        v = (z[f"v{ci}_{i}"] if f"v{ci}_{i}" in z.files
                             else None)
                        chunk.append((d, v, dicts[ci][i]))
                    yield chunk, n
            try:
                os.unlink(path)
            except OSError:
                pass
        for chunk, n in zip(self.chunks, self._chunk_rows):
            yield chunk, n

    def _build_tile(self, chunks: list[list]) -> ColumnarBatch:
        """Merge a group of chunks into one device batch."""
        arrays = []
        validities = []
        dicts = []
        for i, f in enumerate(self.schema.fields):
            datas = [c[i][0] for c in chunks]
            valids = [c[i][1] for c in chunks]
            if dict_encoded(f.dataType):
                sdicts = [c[i][2] for c in chunks]
                merged, recoded = _merge_dict_chunks(sdicts, datas)
                data = (np.concatenate(recoded) if recoded
                        else np.zeros(0, np.int32))
                sd = merged
            else:
                data = np.concatenate(datas) if datas else np.zeros(0)
                sd = None
            if any(v is not None for v in valids):
                vs = [v if v is not None else np.ones(len(d), bool)
                      for v, d in zip(valids, datas)]
                validity = np.concatenate(vs)
            else:
                validity = None
            arrays.append(data)
            validities.append(validity)
            dicts.append(sd)
        return ColumnarBatch.from_numpy(
            self.schema, arrays, dictionaries=dicts, validities=validities)

    def build(self, tile_capacity: int) -> Partition:
        """Rebuild device batches (≤ tile_capacity rows each), streaming
        spilled chunks so peak host memory stays bounded. Chunks are split
        at exact tile boundaries — an overshooting tile would round up to
        the next capacity bucket and break the memory bound."""
        if not self.chunks and not self._spills:
            empty = ColumnarBatch.empty(self.schema)
            self.seed_stats(empty)
            return [empty]
        batches: Partition = []
        pend: list[list] = []
        pend_rows = 0
        for chunk, n in self._iter_chunks():
            off = 0
            while n - off > 0:
                take = min(n - off, tile_capacity - pend_rows)
                if off == 0 and take == n:
                    pend.append(chunk)
                else:
                    pend.append([
                        (d[off:off + take],
                         None if v is None else v[off:off + take], sd)
                        for d, v, sd in chunk])
                pend_rows += take
                off += take
                if pend_rows >= tile_capacity:
                    batches.append(self._build_tile(pend))
                    pend, pend_rows = [], 0
        if pend or not batches:
            batches.append(self._build_tile(pend))
        self._spills = []
        for b in batches:
            self.seed_stats(b)
        return batches


def _merge_dict_chunks(sdicts: list, datas: list):
    from ..columnar.batch import merge_string_dicts

    dicts = [sd or EMPTY_DICT for sd in sdicts]
    if all(d is dicts[0] for d in dicts):
        return dicts[0], [np.asarray(c) for c in datas]
    merged, luts = merge_string_dicts(dicts)
    recoded = [lut[np.clip(codes, 0, len(lut) - 1)]
               for lut, codes in zip(luts, datas)]
    return merged, recoded


def _pull_sorted(batch: ColumnarBatch, perm, counts) -> tuple[list, np.ndarray]:
    """Gather columns by perm on device, transfer to host once."""
    jnp = _jnp()

    planes, counts = device_read(
        "shuffle.pull",
        [(jnp.take(c.data, perm),
          None if c.validity is None else jnp.take(c.validity, perm))
         for c in batch.columns], counts)
    return ([(d, v, c.dictionary) for (d, v), c in zip(planes,
                                                        batch.columns)],
            counts)


@contextlib.contextmanager
def host_exchange(ctx: ExecContext, kind: str, num_out: int):
    """Span `shuffle.host` around one exchange between stages. Yields
    the dict the exchange adds what it moved to, which the span carries
    when it closes: `bytes_d2h`, the exchanged columns as they lie on
    the host for the reducers' buffers, and `bytes_h2d`, the partitions
    rebuilt as device batches. An exchange that stays on the device (a
    broadcast of a stage's batches, a gather into one partition) moves 0
    and 0. Nothing here reads the device."""
    moved = {"bytes_d2h": 0, "bytes_h2d": 0}
    tracer = getattr(ctx, "tracer", None)
    if tracer is None:
        yield moved
        return
    with tracer.span("shuffle.host", cat="exchange",
                     args={"kind": kind, "partitions": num_out}) as sp:
        yield moved
        sp.set_args(moved)


def _out_buffers(num_out: int, schema: StructType, ctx: ExecContext,
                 stat_cols: list | None = None) -> list[_OutBuffer]:
    return [_OutBuffer(schema, spill_bytes=ctx.memory.spill_bytes,
                       spill_dir=ctx.memory.spill_dir, metrics=ctx.metrics,
                       stat_cols=stat_cols)
            for _ in range(num_out)]


def hash_partition_batch(batch: ColumnarBatch,
                         key_positions: Sequence[int], num_out: int,
                         seed: int) -> tuple[list, np.ndarray]:
    """Partition ONE materialized batch by key hash; returns the
    pid-grouped host columns + per-partition counts (the shared
    operator-at-a-time kernels — the fused exchange write in
    physical/fusion.py produces the same shape from one fused dispatch)."""

    from ..ops.hashing import hash_columns, partition_ids
    from ..ops.partition import hash_partition
    from ..physical.compile import GLOBAL_KERNEL_CACHE, stage_jit

    try:
        from ..utils.native import radix_partition as native_radix
        has_native = True
    except Exception:
        has_native = False

    jnp = _jnp()
    keys = [batch.columns[i] for i in key_positions]
    key_eqs = [c.eq_keys() for c in keys]
    key_valids = [c.validity for c in keys]
    cap = batch.capacity
    if has_native:
        # fast path: device computes only the pid per row (cheap
        # hash kernel); the C++ counting sort groups rows host-side
        # (native/sparktpu_native.cpp, the RadixSort role) — no
        # device sort, no device gather
        kkey = ("shuffle_pids", cap, num_out, len(keys), seed,
                tuple(str(k.dtype) for k in key_eqs),
                tuple(v is not None for v in key_valids))
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            kkey, lambda: stage_jit(
                lambda eqs, valids, mask: jnp.where(
                    mask,
                    partition_ids(hash_columns(eqs, list(valids),
                                               seed=seed),
                                  num_out),
                    num_out)))
        # the pids and the columns they group, in one transfer
        pids, planes = device_read(
            "shuffle.pull", kernel(key_eqs, key_valids, batch.row_mask),
            [(c.data, c.validity) for c in batch.columns])
        try:
            order, counts = native_radix(pids, num_out)
        except Exception:
            order = np.argsort(pids, kind="stable")
            counts = np.bincount(
                pids[pids < num_out], minlength=num_out)
        order = order[: int(counts.sum())]
        gathered = [(d[order], None if v is None else v[order],
                     c.dictionary)
                    for (d, v), c in zip(planes, batch.columns)]
        return gathered, counts.astype(np.int64)
    kkey = ("shuffle_hash", cap, num_out, len(keys), seed,
            tuple(str(k.dtype) for k in key_eqs),
            tuple(v is not None for v in key_valids))
    kernel = GLOBAL_KERNEL_CACHE.get_or_build(
        kkey, lambda: stage_jit(
            lambda eqs, valids, mask: hash_partition(
                eqs, valids, mask, num_out, seed=seed)))
    pr = kernel(key_eqs, key_valids, batch.row_mask)
    return _pull_sorted(batch, pr.perm, pr.counts)


def rr_partition_batch(batch: ColumnarBatch, num_out: int,
                       start: int) -> tuple[list, np.ndarray]:
    """Round-robin-partition one batch. The running row offset is a
    kernel ARGUMENT (an int32 device scalar), not part of the cache key:
    one compiled kernel per (capacity, num_out) serves every batch
    position (the historical key embedded start % num_out and compiled
    once per batch — the SampleExec storm shape)."""

    from ..ops.partition import round_robin_partition
    from ..physical.compile import GLOBAL_KERNEL_CACHE, stage_jit

    kkey = ("shuffle_rr", batch.capacity, num_out)
    kernel = GLOBAL_KERNEL_CACHE.get_or_build(
        kkey, lambda: stage_jit(
            lambda mask, s: round_robin_partition(mask, num_out, s)))
    pr = kernel(batch.row_mask, np.int32(start % num_out))
    return _pull_sorted(batch, pr.perm, pr.counts)


def range_partition_batch(batch: ColumnarBatch, key_position: int,
                          bounds, descending: bool, num_out: int,
                          string_key: bool) -> tuple[list, np.ndarray]:
    """Range-partition one batch against sampled bounds."""

    from ..ops.partition import range_partition, _group_by_pid
    from ..physical.compile import GLOBAL_KERNEL_CACHE, stage_jit

    jnp = _jnp()
    col = batch.columns[key_position]
    cap = batch.capacity
    if string_key:
        # host: dict value → pid lut; device: take + group
        sd = col.dictionary or StringDict([""])
        lut = np.searchsorted(bounds, np.array(sd.values or [""],
                                               dtype=object),
                              side="right").astype(np.int32)
        if descending:
            lut = (num_out - 1) - lut
        lut_d = jnp.asarray(lut)
        pids = jnp.take(lut_d, jnp.clip(col.data, 0, len(lut) - 1))
        kkey = ("shuffle_range_str", cap, num_out)
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            kkey, lambda: stage_jit(
                lambda p, m: _group_by_pid(p, m, num_out)))
        pr = kernel(pids, batch.row_mask)
    else:
        barr = jnp.asarray(np.asarray(bounds))
        kkey = ("shuffle_range", cap, num_out, descending,
                str(col.data.dtype), len(bounds))
        kernel = GLOBAL_KERNEL_CACHE.get_or_build(
            kkey, lambda: stage_jit(
                lambda keys, b, mask: range_partition(
                    keys, b, mask, num_out, descending)))
        pr = kernel(col.sort_keys().astype(barr.dtype), barr,
                    batch.row_mask)
    return _pull_sorted(batch, pr.perm, pr.counts)


def shuffle_hash(partitions: list[Partition], key_positions: Sequence[int],
                 num_out: int, schema: StructType, ctx: ExecContext,
                 stats: dict | None = None,
                 seed: int = 42,
                 col_stats: dict | None = None,
                 stat_cols: list | None = None) -> list[Partition]:
    """Hash-repartition. ``seed`` must differ from the upstream exchange's
    when re-splitting already-hash-partitioned data (grace join): reusing
    the seed makes h %% nfrag constant within a partition whenever nfrag
    divides the exchange's partition count — a degenerate split."""
    with host_exchange(ctx, "hash", num_out) as moved:
        bufs = _out_buffers(num_out, schema, ctx, stat_cols)
        for part in partitions:
            for batch in part:
                gathered, counts = hash_partition_batch(
                    batch, key_positions, num_out, seed)
                _slice_into(bufs, gathered, counts)
        return _finish(bufs, ctx, stats, col_stats, moved)


def shuffle_round_robin(partitions: list[Partition], num_out: int,
                        schema: StructType, ctx: ExecContext,
                        stats: dict | None = None,
                        col_stats: dict | None = None,
                        stat_cols: list | None = None) -> list[Partition]:
    with host_exchange(ctx, "round_robin", num_out) as moved:
        bufs = _out_buffers(num_out, schema, ctx, stat_cols)
        start = 0
        for part in partitions:
            for batch in part:
                gathered, counts = rr_partition_batch(batch, num_out, start)
                _slice_into(bufs, gathered, counts)
                start += int(counts.sum())
        return _finish(bufs, ctx, stats, col_stats, moved)


def shuffle_range(partitions: list[Partition], key_position: int,
                  bounds, descending: bool, num_out: int, schema: StructType,
                  ctx: ExecContext, stats: dict | None = None,
                  col_stats: dict | None = None,
                  stat_cols: list | None = None) -> list[Partition]:
    """Range shuffle for global sort. `bounds` is a host list of boundary
    values in the sort-key domain (numeric) or raw strings."""
    f = schema.fields[key_position]
    string_key = isinstance(f.dataType, StringType)
    with host_exchange(ctx, "range", num_out) as moved:
        bufs = _out_buffers(num_out, schema, ctx, stat_cols)
        for part in partitions:
            for batch in part:
                gathered, counts = range_partition_batch(
                    batch, key_position, bounds, descending, num_out,
                    string_key)
                _slice_into(bufs, gathered, counts)
        return _finish(bufs, ctx, stats, col_stats, moved)


def shuffle_fused(partitions: list[Partition], writer, num_out: int,
                  schema: StructType, ctx: ExecContext,
                  stats: dict | None = None,
                  col_stats: dict | None = None,
                  stat_cols: list | None = None) -> list[Partition]:
    """Fused exchange map side: `writer` (physical/fusion.ExchangeFusion
    bound to a partitioning) runs ONE jitted kernel per input batch —
    pipeline trace + partition ids + pid-grouped gather — and this loop
    consumes the grouped host columns directly into the reduce buffers:
    no intermediate materialized batch between the stage pipeline and the
    shuffle write. Partitions under spark.tpu.fusion.minRows take the
    shared unfused kernels instead (pipeline + shuffle kind), matching
    the other fused operators' size gate."""
    from ..config import FUSION_MIN_ROWS

    min_rows = int(ctx.conf.get(FUSION_MIN_ROWS))  # tpulint: ignore[host-sync]
    with host_exchange(ctx, "fused", num_out) as moved:
        bufs = _out_buffers(num_out, schema, ctx, stat_cols)
        start = 0  # running live-row offset (round-robin positioning)
        for part in partitions:
            fused = sum(b.capacity for b in part) >= min_rows
            for batch in part:
                if fused:
                    gathered, counts = writer.partition_batch(batch, start)
                else:
                    gathered, counts = writer.partition_unfused(batch,
                                                                start)
                _slice_into(bufs, gathered, counts)
                # counts is host numpy (materialized by the map-side
                # write)
                start += int(counts.sum())  # tpulint: ignore[host-sync]
        return _finish(bufs, ctx, stats, col_stats, moved)


def gather_single(partitions: list[Partition],
                  ctx: ExecContext | None = None) -> list[Partition]:
    """AllTuples: concatenate every partition into one. The batches stay
    where they are, so the exchange's span carries no bytes."""
    with host_exchange(ctx, "gather", 1):
        merged: Partition = []
        for p in partitions:
            merged.extend(p)
        return [merged]


def _slice_into(bufs: list[_OutBuffer], gathered: list, counts: np.ndarray):
    offsets = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    for p in range(len(bufs)):
        lo, hi = int(offsets[p]), int(offsets[p + 1])  # tpulint: ignore[host-sync]
        if hi <= lo:
            continue
        cols = []
        for data, validity, sd in gathered:
            cols.append((data[lo:hi],
                         None if validity is None else validity[lo:hi], sd))
        bufs[p].append(cols, hi - lo)


def _finish(bufs: list[_OutBuffer], ctx: ExecContext,
            stats: dict | None,
            col_stats: dict | None = None,
            moved: dict | None = None) -> list[Partition]:
    tile = ctx.conf.batch_capacity
    out = []
    for i, b in enumerate(bufs):
        if stats is not None:
            stats[i] = b.rows
        if col_stats is not None:
            col_stats[i] = dict(b.col_stats)
        out.append(b.build(tile))
    if moved is not None:
        moved["bytes_d2h"] += sum(b.bytes_in for b in bufs)
        moved["bytes_h2d"] += sum(t.device_nbytes()
                                  for part in out for t in part)
    return out
