"""Sort kernels.

Role of the reference's SortExec / UnsafeExternalRowSorter / RadixSort
(sqlx/SortExec.scala:39, corej/util/collection/unsafe/sort/RadixSort.java).
TPU-native: `lax.sort` over multiple key operands (XLA lowers to an on-device
sorting network) with order-preserving key transforms for DESC and null
placement; payload columns ride along via a permutation gather.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax


class SortKeySpec(NamedTuple):
    ascending: bool = True
    nulls_first: bool | None = None  # None => Spark default (first if asc)

    @property
    def nulls_first_effective(self) -> bool:
        if self.nulls_first is None:
            return self.ascending
        return self.nulls_first


def _directional(key: jnp.ndarray, ascending: bool) -> jnp.ndarray:
    """Transform key so ascending lax.sort yields the requested order.

    Signed ints: bitwise NOT is an exact order reversal (~x = -x-1, total,
    no overflow — the trick the reference's PrefixComparators play with
    unsigned prefixes). Floats: negate after NaN-normalization (SQL: NaN
    sorts greatest)."""
    if ascending:
        if jnp.issubdtype(key.dtype, jnp.floating):
            return jnp.where(jnp.isnan(key), jnp.asarray(jnp.inf, key.dtype), key)
        return key
    if key.dtype == jnp.bool_:
        return ~key
    if jnp.issubdtype(key.dtype, jnp.floating):
        k = jnp.where(jnp.isnan(key), jnp.asarray(jnp.inf, key.dtype), key)
        return -k
    return ~key


@jax.named_scope("sort")
def sort_permutation(keys: Sequence[jnp.ndarray],
                     valids: Sequence[jnp.ndarray | None],
                     specs: Sequence[SortKeySpec],
                     row_mask: jnp.ndarray) -> jnp.ndarray:
    """Permutation ordering live rows by the sort spec; inactive rows last.

    keys are in the numeric sort-key domain (Column.sort_keys())."""
    cap = row_mask.shape[0]
    operands: list[jnp.ndarray] = [(~row_mask).astype(jnp.int32)]
    for key, valid, spec in zip(keys, valids, specs):
        if valid is not None:
            nf = spec.nulls_first_effective
            null_key = (valid if nf else ~valid).astype(jnp.int32)
            operands.append(null_key)
            key = jnp.where(valid, key, jnp.zeros_like(key))
        operands.append(_directional(key, spec.ascending))
    nk = len(operands)
    operands.append(lax.iota(jnp.int32, cap))
    out = lax.sort(tuple(operands), num_keys=nk, is_stable=True)
    return out[-1]


def take_rows(arrays: Sequence[jnp.ndarray], perm: jnp.ndarray):
    return [jnp.take(a, perm) for a in arrays]


def limit_mask(row_mask_sorted: jnp.ndarray, n: int) -> jnp.ndarray:
    """Keep the first n live rows (post-sort): LocalLimit/GlobalLimit kernel
    (reference: sqlx/limit.scala)."""
    live_rank = jnp.cumsum(row_mask_sorted.astype(jnp.int32))
    return row_mask_sorted & (live_rank <= n)
