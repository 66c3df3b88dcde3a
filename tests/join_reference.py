"""The sorted join's expansion as it was before `ops/joining.src_path`: the
tests' reference for the arrays of both bodies, and for the lowered text of
the one that keeps the gathers. And the index and the probe as they were
before `ops/joining.key_path`: the reference for the text of a join that
keeps the hash, and a numpy oracle for what any join must give."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_tpu.ops import joining as J
from spark_tpu.ops.hashing import hash_columns
from spark_tpu.ops.joining import I64_MAX, BuildSide, JoinResult, rank_sorted


@jax.named_scope("build_sort")
def build_index_of_pr33(key_cols, key_valids, row_mask, key="hash"):
    """`ops/joining.build_index` as it was before `key_path` (PR 33), word
    for word; `key` is taken and not read."""
    h = hash_columns(key_cols, list(key_valids))
    # null join keys never match (SQL equi-join); drop them from the index
    usable = row_mask
    for v in key_valids:
        if v is not None:
            usable = usable & v
    hh = jnp.where(usable, h, I64_MAX)
    cap = row_mask.shape[0]
    sh, perm = lax.sort((hh, lax.iota(jnp.int32, cap)), num_keys=1, is_stable=True)
    return BuildSide(sh, perm)


def probe_join_of_pr33(build, build_key_cols, build_key_valids,
                       probe_key_cols, probe_key_valids, probe_mask,
                       out_capacity, join_type="inner", key="hash",
                       expand=True):
    """`ops/joining.probe_join` as it was before `key_path` (PR 33), word
    for word (it ends in today's `_expand`, whose body that PR left as it
    was); `key` and `expand` (a semi or anti join here always expands) are
    taken and not read."""
    pcap = probe_mask.shape[0]
    oc = out_capacity

    with jax.named_scope("probe"):
        ph = hash_columns(probe_key_cols, list(probe_key_valids))
        usable = probe_mask
        for v in probe_key_valids:
            if v is not None:
                usable = usable & v
        ph = jnp.where(usable, ph, I64_MAX - 1)  # sentinel: matches nothing

        lo, hi = rank_sorted(build.sorted_hash, ph, "both")
        counts = jnp.where(usable, hi - lo, 0)
    return J._expand(build, build_key_cols, build_key_valids, probe_key_cols,
                     probe_key_valids, probe_mask, oc, join_type, pcap, lo,
                     counts)


def join_oracle(bk, bvalid, bmask, pk, pvalid, pmask, join_type):
    """What a join on one key must emit, by loops over numpy arrays: the
    (probe row, build row) pairs in the output's order, build row -1 for a
    row that stands alone (null-extended, or a semi/anti row)."""
    bk, bvalid, bmask, pk, pvalid, pmask = (
        np.asarray(x) for x in (bk, bvalid, bmask, pk, pvalid, pmask))
    rows_of = {}
    for b in np.nonzero(bmask & bvalid)[0]:
        rows_of.setdefault(int(bk[b]), []).append(int(b))
    out = []
    for p in np.nonzero(pmask)[0]:
        found = rows_of.get(int(pk[p]), []) if pvalid[p] else []
        if join_type in ("inner", "left_outer"):
            out += [(int(p), b) for b in found]
            if join_type == "left_outer" and not found:
                out.append((int(p), -1))
        elif (join_type == "left_semi") == bool(found):
            out.append((int(p), -1))
    return out


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
