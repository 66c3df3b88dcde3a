"""Fully on-device distributed group-by over a mesh: the flagship SPMD step.

This is the TPU-native replacement for the reference's whole
partial-agg → shuffle → final-agg stage pipeline (HashAggregateExec +
ShuffleExchangeExec + HashAggregateExec, SURVEY.md §3.2/§3.3) compiled into
ONE XLA program over a jax.sharding.Mesh:

  1. each shard partially aggregates its rows (sort + segment_sum),
  2. partial groups are exchanged by key hash with `lax.all_to_all`
     (ICI, no host involvement),
  3. each shard merges the groups it owns.

Used by __graft_entry__.dryrun_multichip and (future) the mesh execution
backend of the planner.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import grouping as G
from ..ops.hashing import hash_columns, partition_ids
from .collectives import _bucket_local


def make_distributed_groupby_sum(mesh, axis_name: str = "data",
                                 quota: int | None = None):
    """Returns jitted fn(keys, values, row_mask) -> (out_keys, out_sums,
    out_counts, out_mask), all row-sharded over `axis_name`.

    keys int64[n], values float64/int64[n], row_mask bool[n]; n divisible by
    mesh size. Per-shard group count is bounded by shard capacity, so the
    exchange quota defaults to shard_cap // P (retryable upward by caller)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n_part = mesh.shape[axis_name]

    def local_fn(keys, values, row_mask):
        cap = row_mask.shape[0]
        q = quota or max(cap // n_part, 8)

        # --- 1. local partial aggregation ---
        layout = G.group_rows([keys], [None], row_mask)
        sums, cnts = G.seg_sum(layout, values)
        gkeys, _ = G.scatter_group_keys(layout, keys, None)
        gmask = G.group_output_mask(layout)

        # --- 2. exchange partial groups by hash(key) ---
        gather_idx, slot_valid, _overflow = _bucket_local(
            [gkeys], [None], gmask, n_part, q)

        def xchg(arr):
            blocks = jnp.take(arr, gather_idx).reshape(n_part, q)
            recv = lax.all_to_all(blocks, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
            return recv.reshape(n_part * q)

        rkeys = xchg(gkeys)
        rsums = xchg(sums)
        rcnts = xchg(cnts)
        rmask = lax.all_to_all(slot_valid, axis_name, split_axis=0,
                               concat_axis=0, tiled=False).reshape(n_part * q)

        # --- 3. merge: group again, sum the partial sums/counts ---
        mlayout = G.group_rows([rkeys], [None], rmask)
        msums, _ = G.seg_sum(mlayout, rsums)
        mcnts, _ = G.seg_sum(mlayout, rcnts)
        mkeys, _ = G.scatter_group_keys(mlayout, rkeys, None)
        mmask = G.group_output_mask(mlayout)
        return mkeys, msums, mcnts, mmask

    def sharded(keys, values, row_mask):
        f = shard_map(local_fn, mesh=mesh,
                      in_specs=(P(axis_name), P(axis_name), P(axis_name)),
                      out_specs=(P(axis_name), P(axis_name), P(axis_name),
                                 P(axis_name)),
                      check_vma=False)
        return f(keys, values, row_mask)

    return jax.jit(sharded)
