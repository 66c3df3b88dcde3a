"""The sorted join's expansion as it was before `ops/joining.src_path`: the
tests' reference for the arrays of both bodies, and for the lowered text of
the one that keeps the gathers."""

import jax.numpy as jnp
from jax import lax

from spark_tpu.ops.joining import JoinResult, rank_sorted


def expand_of_pr31(build, build_key_cols, build_key_valids, probe_key_cols,
                   probe_key_valids, probe_mask, oc, join_type, pcap, lo,
                   counts):
    """`ops/joining._expand` as it was before `src_path` (PR 31), word for
    word: every fetch by `src` a gather. The tests' reference for both
    bodies' arrays, and for the lowered text of the gathering one."""
    if join_type in ("left_semi", "left_anti", "left_outer"):
        ecounts = jnp.maximum(counts, jnp.where(probe_mask, 1, 0))
    else:
        ecounts = counts

    offsets = jnp.cumsum(ecounts)
    total = offsets[pcap - 1] if pcap > 0 else jnp.int64(0)

    j = lax.iota(jnp.int64, oc)
    src = jnp.minimum(rank_sorted(offsets, j, "right"), pcap - 1)
    base = offsets[src] - ecounts[src]
    within = (j - base).astype(jnp.int32)
    in_range = j < total

    has_build = within < counts[src]
    bpos = jnp.minimum(build.perm.shape[0] - 1, lo[src] + within)
    bidx = jnp.take(build.perm, bpos)

    pair_ok = has_build
    for bc, bv, pc_, pv in zip(build_key_cols, build_key_valids,
                               probe_key_cols, probe_key_valids):
        b_val = jnp.take(bc, bidx)
        p_val = jnp.take(pc_, src)
        eq = b_val == p_val
        if bv is not None:
            eq = eq & jnp.take(bv, bidx)
        if pv is not None:
            eq = eq & jnp.take(pv, src)
        pair_ok = pair_ok & eq

    live_probe = jnp.take(probe_mask, src)

    if join_type == "inner":
        out_mask = in_range & live_probe & pair_ok
        return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64))

    vmatch = jnp.zeros(pcap, dtype=jnp.int32).at[src].add(
        (in_range & pair_ok).astype(jnp.int32), mode="drop")

    if join_type == "left_semi":
        first_slot = within == 0
        out_mask = in_range & live_probe & first_slot & (jnp.take(vmatch, src) > 0)
        return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64))

    if join_type == "left_anti":
        first_slot = within == 0
        out_mask = in_range & live_probe & first_slot & (jnp.take(vmatch, src) == 0)
        return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64))

    no_match = jnp.take(vmatch, src) == 0
    null_row = no_match & (within == 0)
    out_mask = in_range & live_probe & (pair_ok | null_row)
    return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64))
