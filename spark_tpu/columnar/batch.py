"""Columnar batch substrate: fixed-capacity device tiles.

Role of the reference's vectorized layer — ColumnVector/ColumnarBatch
(sqlcatj/vectorized/{ColumnVector,ColumnarBatch}.java) and the writable
On/OffHeapColumnVector (sqlxj/vectorized/OffHeapColumnVector.java) — re-designed
for XLA:

  * Every batch has a STATIC power-of-two `capacity`; the number of live rows
    is carried as a boolean `row_mask` device array, so filters/joins never
    change array shapes (no XLA recompilation per cardinality; SURVEY.md §7
    'Hard parts' (1)).
  * A column is a device array in the type's device representation plus an
    optional validity (null) mask. Strings/binary are dictionary-encoded:
    int32 codes on device, UTF-8 values host-side in a StringDict (the
    reference keeps UTF8String bytes in UnsafeRow; on TPU bytes stay on host
    and comparisons ride hashes/ranks — SURVEY.md §2.5).
  * Selection is mask-based (the reference's selection-vector idea); host
    materialization compacts.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field, replace
from functools import cached_property
from typing import Any, Iterable, Sequence

import numpy as np

from ..types import (
    ArrayType,
    BooleanType,
    DataType,
    DecimalType,
    MapType,
    NullType,
    StringType,
    StructField,
    StructType,
    dict_encoded,
    from_arrow_type,
    to_arrow_type,
)

__all__ = ["StringDict", "Column", "ColumnarBatch", "bucket_capacity", "EMPTY_DICT"]


def bucket_capacity(n: int, minimum: int = 1 << 10) -> int:
    """Round row count up to a power-of-two capacity bucket so jitted kernels
    are reused across batches (bounded recompile cache; the reference instead
    re-JITs Janino code per plan — codegen/CodeGenerator.scala:1557)."""
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


def _hash_str(s: str) -> int:
    """Deterministic 64-bit hash of a UTF-8 string (signed int64).

    Per-dictionary-entry only — row-level hashing happens on device via code
    lookup. (Native murmur3 path lives in native/; this is the fallback.)
    """
    d = hashlib.blake2b(s.encode("utf-8"), digest_size=8).digest()
    return int.from_bytes(d, "little", signed=True)


class StringDict:
    """Host-side dictionary for a string column: unique UTF-8 values.

    Device-side derivatives (lazily cached):
      * hashes: int64[n_values] stable hash per value — the cross-dictionary
        equality domain used by joins/group-bys over string keys.
      * ranks:  int32[n_values] lexicographic rank — the ORDER BY key domain.
    """

    __slots__ = ("values", "_index", "_hashes", "_ranks", "_device_hashes",
                 "_device_ranks", "_hash_luts", "_token")

    def __init__(self, values: Sequence[str]):
        self.values: list[str] = list(values)
        self._index: dict[str, int] | None = None
        self._hashes: np.ndarray | None = None
        self._ranks: np.ndarray | None = None
        self._device_hashes = None
        self._device_ranks = None
        self._hash_luts: dict | None = None  # pow2 bucket -> device lut
        self._token: str | None = None

    def __len__(self) -> int:
        return len(self.values)

    # The lazy caches below (_index/_device_hashes/_device_ranks/
    # _hash_luts/_token) are pure functions of `values`, which is frozen
    # at construction: par_map lanes racing on first touch recompute the
    # SAME value and last-writer-wins is safe (wasted work, never a
    # wrong answer). A lock here would serialize jnp.asarray uploads.
    @property
    def index(self) -> dict[str, int]:
        if self._index is None:
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index

    def code_of(self, value: str) -> int | None:
        return self.index.get(value)

    @property
    def hashes(self) -> np.ndarray:
        if self._hashes is None:
            try:
                from ..utils.native import hash_strings
                self._hashes = hash_strings(self.values)
            except Exception:
                self._hashes = np.array(
                    [_hash_str(v) for v in self.values], dtype=np.int64)
        return self._hashes

    @property
    def ranks(self) -> np.ndarray:
        if self._ranks is None:
            order = np.argsort(np.array(self.values, dtype=object), kind="stable")
            r = np.empty(len(self.values), dtype=np.int32)
            r[order] = np.arange(len(self.values), dtype=np.int32)
            self._ranks = r
        return self._ranks

    def device_hashes(self):
        if self._device_hashes is None:
            import jax.numpy as jnp

            h = self.hashes if len(self.values) else np.zeros(1, np.int64)
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            self._device_hashes = jnp.asarray(h)
        return self._device_hashes

    def device_ranks(self):
        if self._device_ranks is None:
            import jax.numpy as jnp

            r = self.ranks if len(self.values) else np.zeros(1, np.int32)
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            self._device_ranks = jnp.asarray(r)
        return self._device_ranks

    def device_hash_lut(self, minimum: int = 8):
        """Padded codes→value-hash lut as a kernel AUX input: the eq-key
        domain (joins/exchanges on string keys) computed INSIDE a traced
        stage kernel via one `take`. Padded to a power-of-two bucket so
        the kernel cache key depends on the BUCKET, not the exact
        dictionary size — dictionaries that drift a few entries between
        batches reuse one compiled kernel. Pad entries are zeros: live
        valid codes are always < len(values), so padding is never read
        by a row that matters. Cached per bucket on the dictionary."""
        import jax.numpy as jnp

        n = max(len(self.values), 1)
        bucket = bucket_capacity(n, minimum=minimum)
        if self._hash_luts is None:
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            self._hash_luts = {}
        lut = self._hash_luts.get(bucket)
        if lut is None:
            h = np.zeros(bucket, dtype=np.int64)
            if len(self.values):
                h[: len(self.values)] = self.hashes
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            lut = self._hash_luts[bucket] = jnp.asarray(h)
        return lut

    def token(self) -> str:
        """Stable content fingerprint — the dictionary IDENTITY shipped on
        MapStatus/shuffle payloads so reduce sides recognize equal
        dictionaries across map tasks and remap by reference instead of
        re-merging (cluster shuffle ships codes + ONE dictionary per map
        task; equal tokens rebuild to one shared StringDict object)."""
        if self._token is None:
            h = hashlib.blake2b(digest_size=16)
            h.update(str(len(self.values)).encode())
            for v in self.values:
                s = v if isinstance(v, str) else repr(canon_value(v))
                h.update(s.encode("utf-8", "surrogatepass"))
                h.update(b"\x00")
            # race-lint: ignore[shared-mutation] — idempotent lazy memo
            self._token = h.hexdigest()
        return self._token

    def device_rank_to_code(self):
        """Inverse of ranks: rank → dictionary code (string MIN/MAX
        aggregation: reduce in rank space, map the winner back to a code)."""
        import jax.numpy as jnp

        r = self.ranks if len(self.values) else np.zeros(1, np.int32)
        inv = np.empty(len(r), dtype=np.int32)
        inv[r] = np.arange(len(r), dtype=np.int32)
        return jnp.asarray(inv)

    def map_values(self, fn) -> "StringDict":
        """Apply a host string→string function to every dictionary entry —
        how upper/lower/substr/concat-literal execute in O(|dict|) instead of
        O(rows) (no reference analog; enabled by dictionary encoding)."""
        return StringDict([fn(v) for v in self.values])

    @staticmethod
    def merged(a: "StringDict", b: "StringDict"):
        """Union two dictionaries; returns (merged, recode_a, recode_b) where
        recode_x maps old codes → merged codes."""
        md, (ra, rb) = merge_string_dicts([a, b])
        return md, ra, rb


def eq_key_dtype(dt: DataType) -> np.dtype:
    """The dtype of a column's equality key (`Column.eq_keys`; the whole
    tier's `eqs_of`), from its type: a string compares by its 64-bit value
    hash (any dictionary-encoded type is given 64 bits here: the stage tier
    compares a nested value's int32 codes, and nothing should read those
    as values), a boolean as int32, anything else as it lies on the device.
    What `ops/joining.key_path` asks before either side's array is there."""
    if dict_encoded(dt):
        return np.dtype(np.int64)
    if isinstance(dt, BooleanType):
        return np.dtype(np.int32)
    return np.dtype(dt.device_dtype)


def canon_value(v):
    """Hashable canonical form for a dictionary value. Dict items are
    SORTED: maps are unordered (two insertion orders are the same map);
    for structs the field order is schema-fixed so sorting is harmless."""
    if isinstance(v, dict):
        return tuple(sorted((k, canon_value(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple, np.ndarray)):
        return tuple(canon_value(x) for x in v)
    return v


def encode_values(values, codes: np.ndarray | None = None):
    """Dictionary-encode a sequence of python values (None → code 0,
    caller tracks validity separately). Returns (unique values, codes)."""
    n = len(values)
    if codes is None:
        codes = np.zeros(n, np.int32)
    uniq: list = []
    index: dict = {}
    for i, v in enumerate(values):
        if v is None:
            continue
        k = canon_value(v)
        j = index.get(k)
        if j is None:
            j = len(uniq)
            uniq.append(v)
            index[k] = j
        codes[i] = j
    return uniq, codes


def merge_string_dicts(dicts: Sequence["StringDict"]):
    """Union several dictionaries; returns (merged StringDict,
    [recode int32 array per dict]). Uses the C++ open-addressing merge
    (native/sparktpu_native.cpp spark_tpu_merge_dicts) when built; nested
    values (lists/dicts) take the canonical-key python path. Dictionaries
    are type-homogeneous per column, so the first value decides the path."""
    all_str = all(isinstance(d.values[0], str)
                  for d in dicts if d.values)
    if all_str:
        try:
            from ..utils.native import merge_dicts

            merged_vals, recodes = merge_dicts([d.values for d in dicts])
            recodes = [r if len(r) else np.zeros(1, np.int32)
                       for r in recodes]
            return StringDict(merged_vals), recodes
        except Exception:
            pass
    merged: list = []
    idx: dict = {}
    recodes = []
    for d in dicts:
        # empty dictionaries contribute nothing; their (all-masked/invalid)
        # rows keep code 0, which decoding treats as the type default. Never
        # pad with "" here — for map/array/struct dictionaries a stray str
        # corrupts decoding (v.items() on "").
        lut = np.zeros(max(len(d.values), 1), dtype=np.int32)
        for i, v in enumerate(d.values):
            k = canon_value(v)
            j = idx.get(k)
            if j is None:
                j = len(merged)
                merged.append(v)
                idx[k] = j
            lut[i] = j
        recodes.append(lut)
    return StringDict(merged), recodes


EMPTY_DICT = StringDict([])


@dataclass(frozen=True)
class Column:
    """One column of a batch: device data + optional validity mask.

    data: device array [capacity] in dtype.device_dtype
    validity: device bool array [capacity] or None (= no nulls)
    dictionary: StringDict for string-like columns
    runs: host-side RunInfo (columnar/encoding.py) harvested at ingest —
        run-length/sortedness metadata licensing encoding-native kernel
        variants; dropped whenever the data plane is replaced
    """

    dtype: DataType
    data: Any
    validity: Any = None
    dictionary: StringDict | None = None
    runs: Any = None

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, StringType)

    def with_data(self, data, validity="__keep__") -> "Column":
        v = self.validity if validity == "__keep__" else validity
        # fresh data plane: ingest-time run metadata no longer describes it
        return replace(self, data=data, validity=v, runs=None)

    # --- device key domains ----------------------------------------------
    def eq_keys(self):
        """Device array usable as an equality-comparison key (joins, group-by,
        distinct). Strings map codes → stable 64-bit value hashes so columns
        with different dictionaries compare correctly."""
        if self.is_string:
            import jax.numpy as jnp

            codes = jnp.clip(self.data, 0, max(len(self.dictionary) - 1, 0))
            return jnp.take(self.dictionary.device_hashes(), codes)
        if isinstance(self.dtype, BooleanType):
            return self.data.astype(np.int32)
        return self.data

    def sort_keys(self):
        """Device array whose numeric order == SQL ORDER BY order."""
        if self.is_string:
            import jax.numpy as jnp

            codes = jnp.clip(self.data, 0, max(len(self.dictionary) - 1, 0))
            return jnp.take(self.dictionary.device_ranks(), codes)
        if isinstance(self.dtype, BooleanType):
            return self.data.astype(np.int32)
        return self.data

    # --- host materialization --------------------------------------------
    def to_numpy(self, selection: np.ndarray | None = None) -> np.ndarray:
        """Materialize (optionally selecting rows) into a host array of
        Python-level values (strings decoded, decimals scaled)."""
        data = np.asarray(self.data)
        valid = None if self.validity is None else np.asarray(self.validity)
        if selection is not None:
            data = data[selection]
            valid = valid[selection] if valid is not None else None
        if self.is_string or isinstance(self.dtype,
                                        (ArrayType, MapType, StructType)):
            # explicit fill: np.array() would make ragged equal-length
            # lists into a 2-D array
            vals = np.empty(len(self.dictionary.values) + 1, dtype=object)
            for i, v in enumerate(self.dictionary.values):
                vals[i] = v
            vals[-1] = [] if isinstance(self.dtype, ArrayType) else \
                {} if isinstance(self.dtype, (MapType, StructType)) else ""
            codes = np.clip(data, 0, len(self.dictionary.values))
            out = vals[codes] if len(self.dictionary) else \
                vals[np.full(len(data), -1)]
            out = np.asarray(out, dtype=object)
        elif isinstance(self.dtype, DecimalType):
            out = data.astype(np.float64) / (10 ** self.dtype.scale)
        else:
            out = data
        if valid is not None:
            out = np.asarray(out, dtype=object) if out.dtype != object else out
            out = out.copy()
            out[~valid] = None
        return out


class ColumnarBatch:
    """A fixed-capacity tile of rows (SURVEY.md §7 step 1).

    columns are positional; `schema` names them. `row_mask` marks live rows.
    `num_rows` is the host-known live count when available (None after a
    device-side filter until counted)."""

    # __weakref__: the device-resource ledger (obs/resources.py) arms a
    # weakref finalizer per batch to release its HBM charge on GC
    __slots__ = ("schema", "columns", "row_mask", "_num_rows", "_stats",
                 "__weakref__")

    def __init__(self, schema: StructType, columns: Sequence[Column], row_mask,
                 num_rows: int | None = None):
        assert len(schema.fields) == len(columns), (len(schema.fields), len(columns))
        self.schema = schema
        self.columns = list(columns)
        self.row_mask = row_mask
        self._num_rows = num_rows
        self._stats = None  # lazy per-batch kernel-result cache (dense agg
        # range etc.) so repeated executions over a cached batch skip the
        # host round-trip of re-syncing the same scalars
        # HBM ledger registration: charge this tile's device planes to
        # the current query/operator scope (array-identity refcounted, so
        # rewraps over shared columns charge once; shape/dtype metadata
        # only — zero launches, no sync)
        from ..obs.resources import GLOBAL_LEDGER, ledger_enabled

        if ledger_enabled():
            GLOBAL_LEDGER.register_batch(self)

    @property
    def capacity(self) -> int:
        return int(self.row_mask.shape[0])

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> Column:
        return self.columns[i]

    def column_by_name(self, name: str) -> Column:
        for f, c in zip(self.schema.fields, self.columns):
            if f.name == name:
                return c
        raise KeyError(name)

    def num_rows(self) -> int:
        """Live row count; syncs with device if unknown."""
        if self._num_rows is None:
            self._num_rows = int(np.asarray(self.row_mask).sum())
        return self._num_rows

    def device_nbytes(self) -> int:
        """Device bytes this tile holds (column data + validity planes +
        row mask) — the block store's device-pin accounting unit."""
        total = self.row_mask.size * 1
        for c in self.columns:
            data = getattr(c, "data", None)
            if data is not None:
                total += data.size * data.dtype.itemsize
            valid = getattr(c, "validity", None)
            if valid is not None:
                total += valid.size * 1
        return int(total)

    def with_columns(self, schema: StructType, columns: Sequence[Column],
                     row_mask=None, num_rows: int | None = None) -> "ColumnarBatch":
        return ColumnarBatch(
            schema, columns,
            self.row_mask if row_mask is None else row_mask,
            num_rows if row_mask is not None else (num_rows or self._num_rows))

    # --- construction ------------------------------------------------------
    @staticmethod
    def from_numpy(schema: StructType, arrays: Sequence[np.ndarray],
                   dictionaries: Sequence[StringDict | None] | None = None,
                   validities: Sequence[np.ndarray | None] | None = None,
                   capacity: int | None = None) -> "ColumnarBatch":
        import jax.numpy as jnp

        n = int(arrays[0].shape[0]) if arrays else 0
        cap = capacity or bucket_capacity(max(n, 1))
        cols = []
        dictionaries = dictionaries or [None] * len(arrays)
        validities = validities or [None] * len(arrays)
        for f, arr, d, v in zip(schema.fields, arrays, dictionaries, validities):
            dd = f.dataType.device_dtype
            pad = np.zeros(cap, dtype=dd)
            pad[:n] = np.asarray(arr, dtype=dd)[:cap]
            vv = None
            if v is not None:
                vm = np.zeros(cap, dtype=bool)
                vm[:n] = v[:cap]
                vv = jnp.asarray(vm)
            runs = None
            if v is None and not dict_encoded(f.dataType) \
                    and pad.dtype.kind == "i":
                # run/sortedness metadata while the plane is still host
                # numpy (columnar/encoding.py): licenses the sort-free
                # run-boundary aggregate downstream, zero device work;
                # skipped entirely under the decoded oracle
                from .encoding import column_runs, runs_harvest_enabled

                if runs_harvest_enabled():
                    runs = column_runs(pad, min(n, cap))
            cols.append(Column(f.dataType, jnp.asarray(pad), vv,
                               d if dict_encoded(f.dataType) else None,
                               runs=runs))
        mask = np.zeros(cap, dtype=bool)
        mask[:n] = True
        return ColumnarBatch(schema, cols, jnp.asarray(mask), num_rows=n)

    @staticmethod
    def empty(schema: StructType, capacity: int = 1 << 10) -> "ColumnarBatch":
        return ColumnarBatch.from_numpy(
            schema,
            [np.zeros(0, dtype=f.dataType.device_dtype) for f in schema.fields],
            dictionaries=[EMPTY_DICT if dict_encoded(f.dataType) else None
                          for f in schema.fields],
            capacity=capacity)

    # --- host materialization ---------------------------------------------
    def selection_indices(self) -> np.ndarray:
        mask = np.asarray(self.row_mask)
        return np.nonzero(mask)[0]

    def to_pydict(self) -> dict[str, np.ndarray]:
        sel = self.selection_indices()
        return {f.name: c.to_numpy(sel)
                for f, c in zip(self.schema.fields, self.columns)}

    def to_arrow(self, encoded: bool = False):
        """Arrow materialization. `encoded=True` keeps StringType columns
        DICTIONARY-ENCODED (int32 codes + the dictionary values, i.e.
        pa.DictionaryArray) instead of decoding every row — the cluster
        shuffle wire format: codes cross the IPC boundary and the reduce
        side rebuilds code columns without re-encoding (compressed
        execution; the decoded path remains the user-facing collect
        format and the encoding-off oracle)."""
        import pyarrow as pa

        sel = self.selection_indices()
        arrays = []
        for f, c in zip(self.schema.fields, self.columns):
            if encoded and isinstance(f.dataType, StringType):
                sd = c.dictionary or EMPTY_DICT
                codes = np.asarray(c.data)[sel]  # tpulint: ignore[host-sync]
                codes = np.clip(codes, 0, max(len(sd) - 1, 0)) \
                    .astype(np.int32)
                mask = None
                if c.validity is not None:
                    mask = ~np.asarray(c.validity)[sel]  # tpulint: ignore[host-sync]
                arrays.append(pa.DictionaryArray.from_arrays(
                    pa.array(codes, mask=mask),
                    pa.array(list(sd.values) or [""], type=pa.string())))
                continue
            arrays.append(_arrow_by_value(f.dataType, c, sel)
                          if isinstance(f.dataType, BY_VALUE_TYPES)
                          else _arrow_from_planes(f.dataType, c, sel))
        return pa.table(arrays, names=self.schema.names)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"ColumnarBatch(cap={self.capacity}, rows={self._num_rows}, "
                f"schema={self.schema.simple_string()})")


# the types whose answer columns `to_arrow` builds a Python value at a
# time; every other column is built from its host planes as they lie
BY_VALUE_TYPES = (ArrayType, MapType, StructType)


def _arrow_from_planes(dt: DataType, c: Column, sel: np.ndarray):
    """One column of a flat type as an Arrow array built from its host
    planes (data, validity) at `sel`, with no Python object a value."""
    import pyarrow as pa

    if isinstance(dt, NullType):
        return pa.nulls(len(sel))
    data = np.asarray(c.data)  # tpulint: ignore[host-sync]
    if data.dtype.kind not in "biuf":
        raise TypeError(f"a {dt.simple_string()} column holds "
                        f"{data.dtype} data, not a numeric plane")
    data = data[sel]
    valid = None if c.validity is None \
        else np.asarray(c.validity)[sel]  # tpulint: ignore[host-sync]
    at = to_arrow_type(dt)
    if isinstance(dt, StringType):
        # the dictionary once, with the sentinel `Column.to_numpy` appends
        # for codes past its end (all of them when it is empty)
        values = c.dictionary.values
        codes = np.clip(data, 0, len(values))
        return pa.array(list(values) + [""], type=at).take(
            pa.array(codes, mask=None if valid is None else ~valid))
    if isinstance(dt, DecimalType):
        # decimal128 is two little-endian 64-bit words a value: the
        # unscaled integer and its sign
        live = data if valid is None else data[valid]
        bound = 10 ** dt.precision
        if ((live >= bound) | (live <= -bound)).any():
            raise pa.ArrowInvalid(
                f"a value of {dt.simple_string()} does not fit into "
                f"precision {dt.precision}")
        words = np.empty((len(data), 2), dtype="<i8")
        words[:, 0] = data
        words[:, 1] = data >> 63
        bitmap = None if valid is None or valid.all() \
            else pa.py_buffer(np.packbits(valid, bitorder="little"))
        return pa.Array.from_buffers(at, len(data),
                                     [bitmap, pa.py_buffer(words)])
    return pa.array(data, type=at, mask=None if valid is None else ~valid)


def _arrow_by_value(dt: DataType, c: Column, sel: np.ndarray):
    """One column of an array, map or struct type as an Arrow array built
    a Python value at a time."""
    import pyarrow as pa

    vals = c.to_numpy(sel)
    at = to_arrow_type(dt)
    if isinstance(dt, MapType):
        return pa.array([None if v is None else list(v.items())
                         for v in vals], type=at)
    return pa.array(list(vals), type=at)
