"""Equi-join kernel: sorted build side + ranked probe + cumsum expansion.

Role of the reference's hash joins — BroadcastHashJoinExec / ShuffledHashJoinExec
over HashedRelation (sqlx/joins/ShuffledHashJoinExec.scala:38, buildHashedRelation
:103, sqlx/joins/HashedRelation.scala) and SortMergeJoinExec (:39). TPU-native
design: pointer-chasing hash tables don't vectorize; instead the build side is
sorted by a combined 64-bit key hash (`lax.sort`), each probe row finds its
match range as two ranks in the sorted hashes (`rank_sorted`, left and
right), and the variable-fanout output is flattened into a STATIC-capacity
batch by a cumsum of the counts and the rank of each output slot in it. Hash
false-positives are eliminated by gathering and comparing the actual key
columns (so 64-bit hashing is a grouping accelerator, not a correctness
assumption).

`rank_sorted` is `jnp.searchsorted` with two bodies. The binary search is a
loop of ceil(log2(len(a)+1)) dependent steps, each one scalar gather per
query, and a TPU gathers scalars one at a time: 8 Mi queries into 131 072
keys, both sides, were 7.05 s on a v5e. The merge ranks the queries by sorting
them together with the keys, counting the keys before each with a cumsum and
sorting the counts back: sorts and scans, which run at memory speed (the same
ranks in 0.067 s). `rank_path` picks between them from the two static lengths.

Output capacity overflow is reported via a scalar (`needed`) that the host
checks to retry at the next capacity bucket (SURVEY.md §7 'Hard parts' (1)).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .hashing import hash_columns

I64_MAX = jnp.iinfo(jnp.int64).max

# What each body of `rank_sorted` costs on a TPU v5e, in seconds (PERF.md §6,
# PR 26). GATHER_S: one query's one step of the binary search, a scalar
# gather (14.6 ns with 131 072 queries, where the choice is close; 23-32 ns
# with 8 Mi). MERGE_S: one element of keys + queries through the merge, two
# sorts and the scans (6.9 ns). MERGE_FIXED_S: what a merge must save a run
# to be worth its sorts, which add 22-49 s to the program's compile where
# the search adds 0.2-1.3 s: at 50 ms a program repays them in a thousand
# runs, and a join of small inputs never pays them.
GATHER_S = 15e-9
MERGE_S = 7e-9
MERGE_FIXED_S = 0.05


def rank_path(n_sorted: int, n_queries: int) -> str:
    """`"merge"` or `"search"`: the cheaper body of `rank_sorted` for
    `n_queries` queries into `n_sorted` keys, both known at trace time."""
    steps = int(n_sorted).bit_length()        # = ceil(log2(n_sorted + 1))
    search = n_queries * steps * GATHER_S
    merge = MERGE_FIXED_S + (n_sorted + n_queries) * MERGE_S
    return "merge" if merge < search else "search"


def rank_sorted(a: jnp.ndarray, v: jnp.ndarray, side: str,
                path: str | None = None):
    """`jnp.searchsorted(a, v, side=side)` as int32: for each query in `v`
    the number of keys in the sorted `a` that are < it (`"left"`) or <= it
    (`"right"`); `side="both"` gives the pair (left, right) for the price
    of one. `path` forces a body, for the tests; callers leave it to
    `rank_path`."""
    n, m = a.shape[0], v.shape[0]
    path = path or rank_path(n, m)
    sides = ("left", "right") if side == "both" else (side,)
    with jax.named_scope(f"rank_{path}"):
        if path == "search":
            out = [jnp.searchsorted(a, v, side=s).astype(jnp.int32)
                   for s in sides]
        else:
            out = _merge_ranks(a, v, sides)
    return tuple(out) if side == "both" else out[0]


def _merge_ranks(a, v, sides) -> list:
    """Ranks by sorts and scans, with no gather. A stable sort of keys then
    queries leaves each query behind the keys equal to it, so the count of
    keys up to its slot is its right rank; its left rank is the count of
    keys before its run of equal values, carried along the run from the
    run's first slot by a cummax (counts never fall). A second sort, on
    where each element came from, puts the ranks back in query order."""
    n = a.shape[0]
    origin = lax.iota(jnp.int32, n + v.shape[0])
    keys, origin = lax.sort((jnp.concatenate([a, v]), origin), num_keys=1,
                            is_stable=True)
    is_key = origin < n
    upto = jnp.cumsum(is_key, dtype=jnp.int32)
    ranks = []
    for side in sides:
        if side == "right":
            ranks.append(upto)
        else:
            run_start = jnp.concatenate([jnp.ones(1, dtype=bool),
                                         keys[1:] != keys[:-1]])
            ranks.append(lax.cummax(
                jnp.where(run_start, upto - is_key, 0), axis=0))
    back = lax.sort((origin, *ranks), num_keys=1)
    return [r[n:] for r in back[1:]]


class BuildSide(NamedTuple):
    """Build-side index: key-hash-sorted."""

    sorted_hash: jnp.ndarray  # int64[Bcap], inactive rows pushed to +inf
    perm: jnp.ndarray         # int32[Bcap] original row index per sorted slot


# Scopes: each separate loop of the join carries its own `jax.named_scope`
# (`build_sort`, `probe`, `expand`), so that a profile of a program that
# traced these bodies says which of them the device is in.

@jax.named_scope("build_sort")
def build_index(key_cols: Sequence[jnp.ndarray],
                key_valids: Sequence[jnp.ndarray | None],
                row_mask: jnp.ndarray) -> BuildSide:
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


class JoinResult(NamedTuple):
    probe_idx: jnp.ndarray   # int32[OC] source probe-row index per output row
    build_idx: jnp.ndarray   # int32[OC] source build-row index (clipped when unmatched)
    matched: jnp.ndarray     # bool[OC] true => real build match (false => null-extended)
    out_mask: jnp.ndarray    # bool[OC] live output rows
    needed: jnp.ndarray      # int32 scalar: total rows the join wanted to emit


def probe_join(build: BuildSide,
               build_key_cols: Sequence[jnp.ndarray],
               build_key_valids: Sequence[jnp.ndarray | None],
               probe_key_cols: Sequence[jnp.ndarray],
               probe_key_valids: Sequence[jnp.ndarray | None],
               probe_mask: jnp.ndarray,
               out_capacity: int,
               join_type: str = "inner") -> JoinResult:
    """join_type: inner | left_outer | left_semi | left_anti.

    'left' always refers to the probe side; the planner flips sides for
    right joins (as the reference's planner does for build-side selection,
    sqlx/SparkStrategies.scala join selection)."""
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
    return _expand(build, build_key_cols, build_key_valids, probe_key_cols,
                   probe_key_valids, probe_mask, oc, join_type, pcap, lo,
                   counts)


@jax.named_scope("expand")
def _expand(build, build_key_cols, build_key_valids, probe_key_cols,
            probe_key_valids, probe_mask, oc, join_type, pcap, lo,
            counts) -> JoinResult:
    """probe_join's second loop: the match ranges flattened into the
    static-capacity output, each pair verified on the true keys."""

    # --- verify hash ranges by comparing true keys, count real matches ----
    # For semi/anti we must not rely on hash ranges alone. Verified counts
    # also matter for left_outer's null-extension decision. We verify during
    # expansion (cheap: one gather per key col) and fix the semi/anti/outer
    # masks after expansion via a max-scatter back to probe rows.

    if join_type in ("left_semi", "left_anti", "left_outer"):
        ecounts = jnp.maximum(counts, jnp.where(probe_mask, 1, 0))
    else:
        ecounts = counts

    offsets = jnp.cumsum(ecounts)  # inclusive, int64 under x64
    total = offsets[pcap - 1] if pcap > 0 else jnp.int64(0)

    j = lax.iota(jnp.int64, oc)
    src = jnp.minimum(rank_sorted(offsets, j, "right"), pcap - 1)
    base = offsets[src] - ecounts[src]
    within = (j - base).astype(jnp.int32)
    in_range = j < total

    has_build = within < counts[src]
    bpos = jnp.minimum(build.perm.shape[0] - 1, lo[src] + within)
    bidx = jnp.take(build.perm, bpos)

    # verify true key equality (null keys already excluded via sentinels)
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

    # count of VERIFIED matches per probe row (scatter-add over output rows)
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

    if join_type == "left_outer":
        # matched rows pass; unmatched probe rows emit exactly one null-extended
        # row in their first slot
        no_match = jnp.take(vmatch, src) == 0
        null_row = no_match & (within == 0)
        out_mask = in_range & live_probe & (pair_ok | null_row)
        return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64))

    raise ValueError(f"unsupported join type {join_type}")


@jax.named_scope("cross")
def cross_join(probe_mask: jnp.ndarray, build_mask: jnp.ndarray,
               out_capacity: int) -> JoinResult:
    """Cartesian product (reference: CartesianProductExec). Build side is
    compacted first so output is probe-major."""
    pcap = probe_mask.shape[0]
    bcap = build_mask.shape[0]
    nb = jnp.sum(build_mask.astype(jnp.int32))
    # compact build row ids
    order = jnp.argsort(~build_mask, stable=True).astype(jnp.int32)
    counts = jnp.where(probe_mask, nb, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[pcap - 1]
    j = lax.iota(jnp.int64, out_capacity)
    src = jnp.minimum(rank_sorted(offsets, j, "right"), pcap - 1)
    within = (j - (offsets[src] - counts[src])).astype(jnp.int32)
    bidx = jnp.take(order, jnp.minimum(within, bcap - 1))
    out_mask = (j < total) & jnp.take(probe_mask, src)
    return JoinResult(src, bidx, jnp.ones_like(out_mask), out_mask,
                      total.astype(jnp.int64))
