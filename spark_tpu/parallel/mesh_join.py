"""On-device distributed broadcast hash join over a mesh.

The second flagship SPMD step (with mesh_agg's distributed group-by): the
probe side stays row-sharded over the 'data' axis, the build side is
REPLICATED (the BroadcastExchangeExec pattern — on real hardware the
all-gather rides ICI), and every shard probes its rows against the dense
build table in one program. SURVEY.md §2.5 'Broadcast replication'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def make_broadcast_join_sum(mesh, axis_name: str = "data"):
    """Returns jitted fn(probe_keys, probe_vals, probe_mask,
                         build_keys, build_vals, build_mask)
    -> (matched_mask, joined_vals) both row-sharded like the probe side.

    Semantics: inner equi join probe.key = build.key (unique build keys),
    joined_vals = probe_val * build_val for matched rows — the
    scan→broadcast-join→project spine of a TPC-DS star query."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local_fn(pk, pv, pm, bk, bv, bm):
        # build side is replicated: dense direct-address table per shard
        bcap = bk.shape[0]
        tcap = bcap * 2
        big = jnp.iinfo(jnp.int64).max
        kmin = jnp.min(jnp.where(bm, bk, big))
        slot = jnp.where(bm, (bk - kmin), tcap)
        rowidx = jnp.full((tcap,), 0, jnp.int32).at[slot].set(
            lax.iota(jnp.int32, bcap), mode="drop")
        present = jnp.zeros((tcap,), bool).at[slot].set(True, mode="drop")

        k = pk - kmin
        in_range = (k >= 0) & (k < tcap)
        s = jnp.clip(k, 0, tcap - 1)
        matched = pm & in_range & jnp.take(present, s)
        bval = jnp.take(bv, jnp.take(rowidx, s))
        joined = jnp.where(matched, pv * bval, jnp.zeros_like(pv))
        return matched, joined

    def sharded(pk, pv, pm, bk, bv, bm):
        f = shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name), P(axis_name),
                      P(), P(), P()),
            out_specs=(P(axis_name), P(axis_name)),
            check_vma=False)
        return f(pk, pv, pm, bk, bv, bm)

    return jax.jit(sharded)
