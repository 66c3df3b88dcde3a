"""Process-global memo of host-synced scalars derived from device arrays.

One device→host sync stalls the async dispatch pipeline; on
transfer-bound transports each is a permanent tax. This memo, keyed by
the IDENTITY of the source device arrays, makes such reads once-per-array
instead of once-per-batch-per-run: identity survives re-wrapping the same
device columns into fresh ColumnarBatches (device-cached scans re-executed
per query, reorder projections, repeated broadcast probes). Entries hold
weakrefs and verify identity: id() values recycle after GC, and serving
another array's cached value would silently corrupt results.

Users: the dense-range aggregate/join fast-path decision
(physical/operators.dense_range_stats), the dense-join duplicate-key
verdict, range-exchange and external-sort key sampling. dev/tpulint.py's
host-sync rule sanctions reads wrapped in this helper.

`device_read` is the one door of every blocking
device→host read on the engine's paths, the memo's computes included:
one transfer a site, one span of category `sync` named by the site
(obs/tracing), and the launch/sync account that finds the device's
gaps (`obs/tracing.DEVICE`). The lint sanctions it as it does the
memo.
"""

from __future__ import annotations

import collections
import sys
import threading

from . import lockwatch

__all__ = ["device_read", "memo_device_scalars",
           "seed_dense_range_memo", "peek_dense_range", "DENSE_RANGE_KIND"]

_MEMO: "collections.OrderedDict" = collections.OrderedDict()
_LOCK = threading.Lock()
lockwatch.register("utils.device_memo._LOCK",
                   sys.modules[__name__], "_LOCK")
_MAX = 4096

# cache-key kind shared by dense_range_stats and the arrow-ingest seeding
DENSE_RANGE_KIND = ("dense_range",)


def device_read(site: str, *arrays) -> tuple:
    """The host copies of `arrays` (pytrees of arrays; None entries
    allowed), in one explicit transfer: the sync span `site` (args `site`, `bytes`) on
    the tracer in scope, and the device account's drain. A jax array
    keeps its host copy, so a later `np.asarray` of it reads no device."""
    import jax

    from ..obs.tracing import DEVICE, span_here

    begun = DEVICE.sync_begin()
    nbytes = sum(int(getattr(a, "nbytes", 0) or 0)
                 for a in jax.tree_util.tree_leaves(arrays))
    with span_here(site, "sync", {"site": site, "bytes": nbytes}):
        host = jax.device_get(arrays)
    DEVICE.sync_end(begun, site)
    return host


def memo_device_scalars(kind: tuple, arrays: tuple, compute):
    """Memoized `compute()` keyed by `kind` + identity of `arrays` (None
    entries allowed). Falls back to plain computation when an array does
    not support weakrefs. Treat returned values as immutable."""
    import weakref

    live = tuple(a for a in arrays if a is not None)
    key = (kind, tuple(id(a) if a is not None else None for a in arrays))
    with _LOCK:
        ent = _MEMO.get(key)
        if ent is not None:
            refs, value = ent
            if all(r() is a for r, a in zip(refs, live)):
                _MEMO.move_to_end(key)
                return value
            del _MEMO[key]
    value = compute()
    try:
        refs = tuple(weakref.ref(a) for a in live)
    except TypeError:
        return value
    with _LOCK:
        _MEMO[key] = (refs, value)
        while len(_MEMO) > _MAX:
            _MEMO.popitem(last=False)
    return value


def peek_dense_range(col, row_mask):
    """Memo lookup WITHOUT compute: the seeded (kmin, kmax, any_live)
    for this column under this row mask, or None on a miss. Never
    launches a kernel and never syncs — callers that only want to act
    when the answer is already free (runtime-filter batch skip) use
    this instead of memo_device_scalars."""
    arrays = (col.data, col.validity, row_mask)
    live = tuple(a for a in arrays if a is not None)
    key = (DENSE_RANGE_KIND,
           tuple(id(a) if a is not None else None for a in arrays))
    with _LOCK:
        ent = _MEMO.get(key)
        if ent is None:
            return None
        refs, value = ent
        if all(r() is a for r, a in zip(refs, live)):
            _MEMO.move_to_end(key)
            return value
        del _MEMO[key]
        return None


def seed_dense_range_memo(col, row_mask, value: tuple) -> None:
    """Pre-populate the dense-range memo from stats computed host-side
    while the column was still a numpy array (scan ingest,
    columnar/arrow.record_batch_to_columnar): the dense aggregate/join
    fast-path decision then never launches its range-probe kernel nor
    syncs, even on a cold first run. `value` = (kmin, kmax, any_live)
    under the batch's row mask ∧ validity — the dense_range_stats
    contract."""
    memo_device_scalars(DENSE_RANGE_KIND,
                        (col.data, col.validity, row_mask), lambda: value)
