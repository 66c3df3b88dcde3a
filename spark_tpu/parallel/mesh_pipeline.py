"""Composable mesh-resident query pipeline.

The round-2 building block for planner-level mesh execution: a full
filter → project → partial-aggregate → ICI all-to-all → final-merge pipeline
compiled as ONE XLA program over a jax.sharding.Mesh, with the quota-retry
discipline the host engine uses for capacity overflows (SURVEY.md §7
'Hard parts' (1)) applied to the exchange: the program reports dropped rows
via psum, and the host retries with a doubled quota — same contract as the
join kernel's `needed` scalar.
"""

from __future__ import annotations

from typing import Callable

import numpy as np


def make_mesh_groupby_pipeline(mesh, axis_name: str = "data"):
    """Returns run(keys, values, row_mask, *, filter_fn=None,
    project_fn=None, quota=None) executing

        filter → project → local partial group-sum → all-to-all by key hash
        → final merge

    entirely on the mesh. filter_fn(keys, values)->bool mask and
    project_fn(values)->values trace into the same program. Overflowing
    exchange quotas retry doubled (host loop, fresh compile per quota
    bucket)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from ..ops import grouping as G
    from .collectives import _bucket_local

    n_part = mesh.shape[axis_name]

    def build(quota: int, filter_fn, project_fn):
        def local_fn(keys, values, row_mask):
            mask = row_mask
            if filter_fn is not None:
                mask = mask & filter_fn(keys, values)
            vals = project_fn(values) if project_fn is not None else values

            layout = G.group_rows([keys], [None], mask)
            sums, _ = G.seg_sum(layout, vals)
            cnts = G.seg_count(layout)
            gkeys, _ = G.scatter_group_keys(layout, keys, None)
            gmask = G.group_output_mask(layout)

            gather_idx, slot_valid, overflow = _bucket_local(
                [gkeys], [None], gmask, n_part, quota)

            def xchg(arr):
                blocks = jnp.take(arr, gather_idx).reshape(n_part, quota)
                recv = lax.all_to_all(blocks, axis_name, split_axis=0,
                                      concat_axis=0, tiled=False)
                return recv.reshape(n_part * quota)

            rkeys = xchg(gkeys)
            rsums = xchg(sums)
            rcnts = xchg(cnts)
            rmask = lax.all_to_all(slot_valid, axis_name, split_axis=0,
                                   concat_axis=0,
                                   tiled=False).reshape(n_part * quota)
            total_overflow = lax.psum(overflow, axis_name)

            mlayout = G.group_rows([rkeys], [None], rmask)
            msums, _ = G.seg_sum(mlayout, rsums)
            mcnts, _ = G.seg_sum(mlayout, rcnts)
            mkeys, _ = G.scatter_group_keys(mlayout, rkeys, None)
            mmask = G.group_output_mask(mlayout)
            return mkeys, msums, mcnts, mmask, total_overflow

        def sharded(keys, values, row_mask):
            f = shard_map(
                local_fn, mesh=mesh,
                in_specs=(P(axis_name), P(axis_name), P(axis_name)),
                out_specs=(P(axis_name), P(axis_name), P(axis_name),
                           P(axis_name), P()),
                check_vma=False)
            return f(keys, values, row_mask)

        return jax.jit(sharded)

    compiled: dict = {}

    def run(keys, values, row_mask, *, filter_fn=None, project_fn=None,
            quota: int | None = None, max_retries: int = 8):
        per_shard = keys.shape[0] // n_part
        q = quota or max(per_shard // n_part, 8)
        for _ in range(max_retries):
            key = (q, id(filter_fn), id(project_fn))
            fn = compiled.get(key)
            if fn is None:
                fn = compiled[key] = build(q, filter_fn, project_fn)
            mk, ms, mc, mm, overflow = fn(keys, values, row_mask)
            if int(overflow) == 0:
                return mk, ms, mc, mm
            q *= 2  # exchange quota too small — retry doubled
        raise RuntimeError(
            f"mesh exchange quota still overflowing at {q}")

    return run
