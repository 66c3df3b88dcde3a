"""Device/host memory discipline for blocking operators.

Role of the reference's UnifiedMemoryManager
(core/memory/UnifiedMemoryManager.scala:491) and its spilling consumers
(corej/util/collection/unsafe/sort/UnsafeExternalSorter.java,
TungstenAggregationIterator's sort-based fallback) — redesigned for the
XLA allocation model. JAX/XLA owns the actual HBM allocator, so a
byte-for-byte reservation ledger would double-book what the runtime
already tracks; what the engine must govern is *operator policy*:

- how many rows a blocking operator (sort, join build, aggregation) may
  materialize as one device tile before it must switch to its multi-pass
  path (external range-bucketed sort, grace hash join, blockwise fold);
- when host-side shuffle buffers spill their accumulated chunks to disk
  (UnsafeExternalSorter role — exec/shuffle._OutBuffer calls back here).

Budget resolution order: explicit conf > live device memory stats
(bytes_limit × safety fraction) > conservative default. The same
MemoryManager instance travels with the ExecContext for one query, so
its counters land in the query's SQLMetrics snapshot.
"""

from __future__ import annotations

import numpy as np

from ..config import ConfigEntry, _register
from ..types import dict_encoded

DEVICE_BUDGET = _register(ConfigEntry(
    "spark.tpu.memory.deviceBudgetBytes", 0,
    "Device-memory budget (bytes) a single blocking operator may "
    "materialize as one tile. 0 = auto: live device bytes_limit × 0.5 "
    "(a CPU backend, which reports none: 4 GiB). (Role of spark.memory.fraction over the unified region, "
    "core/memory/UnifiedMemoryManager.scala.)", int))

SPILL_BYTES = _register(ConfigEntry(
    "spark.tpu.shuffle.spillBytes", 1 << 28,
    "Host bytes one shuffle reducer buffer may hold before spilling its "
    "chunks to disk (UnsafeExternalSorter.java role).", int))

SPILL_DIR = _register(ConfigEntry(
    "spark.local.dir", "",
    "Directory for shuffle spill files; '' = the system temp dir "
    "(role of spark.local.dir).", str))

_MIN_TILE_ROWS = 1 << 14


def schema_row_bytes(schema) -> int:
    """Device bytes per row: column data (dict-encoded = int32 codes) +
    validity planes + the row mask."""
    total = 1  # row mask
    for f in schema.fields:
        if dict_encoded(f.dataType):
            total += 4
        else:
            total += np.dtype(f.dataType.device_dtype).itemsize
        total += 1  # validity (may be absent; budget conservatively)
    return total


def _auto_budget() -> int:
    """Half the device's memory limit. A CPU backend reports none and
    gets 4 GB (tests); an accelerator that reports none is an error —
    tiling against a guessed HBM size hides the device."""
    import jax

    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit // 2
    if dev.platform == "cpu":
        return 4 << 30
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no "
        "memory_stats()['bytes_limit']: set "
        "spark.tpu.memory.deviceBudgetBytes explicitly")


class MemoryManager:
    """Per-query policy object; see module docstring."""

    def __init__(self, conf, metrics=None):
        explicit = int(conf.get(DEVICE_BUDGET))
        self.device_budget = explicit if explicit > 0 else _auto_budget()
        # an explicit budget is a deliberate cap (tests, constrained
        # slices) and may push tiles below the auto-mode floor
        self._floor = (1 << 10) if explicit > 0 else _MIN_TILE_ROWS
        self.spill_bytes = int(conf.get(SPILL_BYTES))
        self.spill_dir = str(conf.get(SPILL_DIR)) or None
        self.metrics = metrics

    def tile_rows(self, schema, amplification: int = 3) -> int:
        """Max rows a blocking operator may hold in one device tile.

        `amplification` models the operator's working set on top of the
        input tile (sort: keys + permutation + gathered output ≈ 3×;
        join build: build + probe + outputs ≈ 4×)."""
        per_row = schema_row_bytes(schema) * max(1, amplification)
        rows = self.device_budget // per_row
        return max(self._floor, int(rows))

    def count(self, name: str, v: int = 1) -> None:
        if self.metrics is not None:
            self.metrics.add(name, v)
