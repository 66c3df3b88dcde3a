"""Equi-join kernel: sorted build side + ranked probe + cumsum expansion.

Role of the reference's hash joins — BroadcastHashJoinExec / ShuffledHashJoinExec
over HashedRelation (sqlx/joins/ShuffledHashJoinExec.scala:38, buildHashedRelation
:103, sqlx/joins/HashedRelation.scala) and SortMergeJoinExec (:39). TPU-native
design: pointer-chasing hash tables don't vectorize; instead the build side is
sorted by a combined 64-bit key hash (`lax.sort`), each probe row finds its
match range as two ranks in the sorted hashes (`rank_sorted`, left and
right), and the variable-fanout output is flattened into a STATIC-capacity
batch by a cumsum of the counts and each output slot's place in it. Hash
false-positives are eliminated by gathering and comparing the actual key
columns (so 64-bit hashing is a grouping accelerator, not a correctness
assumption).

Where the join has one key and both sides hold it as an integer of at most
32 bits (`key_path`: every TPC-DS surrogate key, a date, a boolean), the
hash is a detour and the index is *exact*: the build side is sorted on the
key itself, widened to int64, with the two sentinels outside the 32-bit
range. The two ranks then bound just the live build rows whose key is the
probe's, in their original order (the sort is stable), so `_expand` has
nothing to verify: it is handed no keys, and the gathers of the build key
and its validity by the build row (and of the probe key and its validity by
`src`, where `src` is gathered) leave the program, with the hash's three
`mix64` on each side. The sorted keys are also what the dense join's span
wants to know (`observe_span`: first, last, two neighbours equal), so no
second sort of the build keys is made for it. A collision of the hash left
a dead slot that the check cleared; the exact index has none, and is
otherwise the hash's result slot for slot.

`rank_sorted` is `jnp.searchsorted` with two bodies. The binary search is a
loop of ceil(log2(len(a)+1)) dependent steps, each one scalar gather per
query, and a TPU gathers scalars one at a time: 8 Mi queries into 131 072
keys, both sides, were 7.05 s on a v5e. The merge ranks the queries by sorting
them together with the keys, counting the keys before each with a cumsum and
sorting the counts back: sorts and scans, which run at memory speed (the same
ranks in 0.067 s). `rank_path` picks between them from the two static lengths.

`_expand` flattens the match ranges: probe row i owns the output slots
[offsets[i] - ecounts[i], offsets[i]), and slot j's row `src` never falls as
j grows. What a slot needs of its probe row (where its run began, where the
run lies in the sorted build side, the probe key, and for `take_probe` any
probe column) it has by one of two bodies. The *gather* body ranks every
slot in the offsets and gathers each value by `src`: 8 Mi slots fetching
from 131 072 probe rows paid 8 Mi scalar gathers a value, six or seven
values a join. The *fill* body sends each owner's value to the owner's first
slot (`pcap` scattered scalars) and carries it along the run with a scan:
`src` is a `cummax` of the scattered row numbers, so nothing is ranked and
the rank's two sorts leave the program, and any other value is a `cumsum` of
the steps from one owner's value to the next, exact in wrapping integers. A
dead or null-keyed probe row owns no slot, so the fill fetches no validity
at all. `src_path` picks between them from the two static lengths: a small
side probing a large one fills, a flow probing a dimension (`pcap` >=
`out_cap`) keeps its gathers, whose scatters would cost more. What is still
gathered (by the build row: a permutation's order) takes its validity planes
along as the bits of one byte (`take_planes`): a gathered byte costs what a
gathered bool costs.

A semi or anti join asks only whether a probe row has a match, as the
reference's LeftSemi/LeftAnti hash joins do, so it expands no match
(`_exists`): a row it may keep has one output slot, and `needed` counts
those rows. The probe side of a set operation is often a sparse flow (an
aggregate's output keeps its input's capacity: 468 000 rows in 8 Mi slots
in TPC-DS q38 and q87 at SF10), so the output, at the capacity those rows
fill, is also what every later operator runs at (kept at the probe's
slots under a mask, q38 took 7.09 s a query on a TPU v5e; in the capacity
its rows fill, as the expansion had it, 4.37 s). On an exact index a row
has a match
where its range is not empty. On a hash index the range's first build row
is compared on the true keys; a range of more than one row whose first row
is not the probe's key (two keys of one 64-bit hash) leaves the row
undecided, and the join counts it (`unsure`) so that the caller runs it on
the expansion instead (`expand=True`), which verifies every pair.

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

# What a probe row's value at every output slot costs `_expand`, per body
# (PERF.md §6, PR 32: the probe, chip call 1, device ms, the least of 5).
# By a gather by `src`, from 131 072 rows into 8 Mi slots: an int32 60.6 ms,
# a bool 68.4, an int64 249-252 (7.2, 8.2 and 30 ns a slot; from 1 Ki rows
# into 32 Mi slots 227, 272 and 481 ms): GATHER_S above stands for the mix.
# By the fill: SCATTER_S, a scalar sent to its owner's first slot, is 11 ns
# for 131 072 int32 at rising places (1.44 ms; 0.9 ms for 1 Ki), but 71-142
# ns for 8 Mi scalars by a permutation (PR 30), and where `pcap` nears
# `out_cap` the fill's scatters are of that kind, so the rule takes the dear
# end. SCAN_S, a slot through the `cumsum` that carries a value along its
# run: 0.27-0.5 ns for 32 bits (2.3 ms at 8 Mi, 8.0 at 32 Mi; a `cummax`
# 3.8 and 13.8), so 1 ns for a 64-bit value's two halves. Whole fills: `src`
# 4.5 ms at 8 Mi and 14.1-14.8 at 32 Mi, an int32 4.0 and 8.4-10.0; an int64
# as one 64-bit scatter and scan was 20.5-21.4 and 40.4-58.3 and compiled for
# 37-42 s at 32 Mi where an int32 takes 6 (hence the halves), and a 64-bit
# `cummax` carrying `src` with 32 bits of payload 22.4-23.1 and 53-70, for
# 75-204 s of compile (hence the `cumsum` of steps). The fill adds no
# `lax.sort` and takes the rank's two out.
SCATTER_S = 80e-9
SCAN_S = 1e-9

# What the exact index (`key_path`) takes out of a program, a run, on a v5e
# (PERF.md §5-§6, PR 34: `explain("device")` at the cells' size). With the
# 32 Mi-slot fact table as the build side and 8 Mi output slots (q3's and
# q7's date join): `expand` 463 -> 176 ms (the key's gather by the build row
# 149.6, its validity's 134.5; what stays is the permutation's gather, 175),
# `span_observe` 118 -> 1.07 ms (a `jnp.sort` of the keys against reads of
# the index), `build_sort` 117.6 -> 117.2 (the hash was never the cost: the
# sort is). At 32 Mi output slots (q89's store join) the two gathers were
# 587 and 270 ms. The search of 131 072 probe keys in the 32 Mi index took
# 262, 328, 257 and 302 ms in power2's four programs where it took 252, 293,
# 329 and 255 on hashes: the same scalar gathers land nearer each other on
# sorted keys, 10-47 ms dearer in three programs and 72 cheaper in one.
# An index in the key's own 32 bits is not built (PERF.md §7 (c): sorting
# int32 + row number at 32 Mi takes 81.6 ms where int64 takes 123.4, the two
# ranks of 131 072 queries 99.0 ms where 190.8, and the sort compiles in
# 21 s where 45; its sentinels would have to be the live count's, not values).


def rank_path(n_sorted: int, n_queries: int) -> str:
    """`"merge"` or `"search"`: the cheaper body of `rank_sorted` for
    `n_queries` queries into `n_sorted` keys, both known at trace time."""
    steps = int(n_sorted).bit_length()        # = ceil(log2(n_sorted + 1))
    search = n_queries * steps * GATHER_S
    merge = MERGE_FIXED_S + (n_sorted + n_queries) * MERGE_S
    return "merge" if merge < search else "search"


def src_path(pcap: int, out_cap: int) -> str:
    """`"fill"` or `"gather"`: the cheaper way for `_expand` to have, at
    each of `out_cap` output slots, a value of the probe row `src` that
    owns the slot, both lengths known at trace time."""
    fill = pcap * SCATTER_S + out_cap * SCAN_S
    return "fill" if fill < out_cap * GATHER_S else "gather"


def key_path(build_key_cols: Sequence, probe_key_cols: Sequence) -> str:
    """`"exact"` or `"hash"`: what the build index is sorted on, from the
    two sides' equality keys (arrays or dtypes), known at trace time. One
    pair of integers of at most 32 bits is its own index: widened to int64
    no live key can equal a sentinel. Everything else keeps the hash: many
    keys, a 64-bit integer (it could), a string (its equality key is a
    64-bit hash already), a float. A caller asks once and hands the answer
    to `build_index` and `probe_join` both."""
    if len(build_key_cols) != 1 or len(probe_key_cols) != 1:
        return "hash"
    for col in (build_key_cols[0], probe_key_cols[0]):
        dt = jnp.dtype(getattr(col, "dtype", col))
        if not jnp.issubdtype(dt, jnp.integer) or dt.itemsize > 4:
            return "hash"
    return "exact"


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
    """Build-side index: sorted by the key's hash or, where `key_path` says
    "exact", by the key itself."""

    sorted_hash: jnp.ndarray  # int64[Bcap], inactive rows pushed to +inf
    perm: jnp.ndarray         # int32[Bcap] original row index per sorted slot


# Scopes: each separate loop of the join carries its own `jax.named_scope`
# (`build_sort`, `probe`, `expand`), so that a profile of a program that
# traced these bodies says which of them the device is in; inside them
# `rank_<path>`, `src_fill` and `pack_valid` say which body ran.

def _index_keys(key_cols, key_valids, key: str) -> jnp.ndarray:
    """What one side's rows are ranked by: the keys' combined hash, or on
    an exact index the one key, widened."""
    if key == "hash":
        return hash_columns(key_cols, list(key_valids))
    if key != "exact" or key_path(key_cols, key_cols) != "exact":
        raise ValueError(f"no {key} index over keys of "
                         f"{[str(c.dtype) for c in key_cols]}")
    return key_cols[0].astype(jnp.int64)


@jax.named_scope("build_sort")
def build_index(key_cols: Sequence[jnp.ndarray],
                key_valids: Sequence[jnp.ndarray | None],
                row_mask: jnp.ndarray, key: str = "hash") -> BuildSide:
    """`key`: `key_path`'s answer for the join, the same `probe_join` is
    given."""
    h = _index_keys(key_cols, key_valids, key)
    # null join keys never match (SQL equi-join); drop them from the index
    usable = row_mask
    for v in key_valids:
        if v is not None:
            usable = usable & v
    hh = jnp.where(usable, h, I64_MAX)
    cap = row_mask.shape[0]
    sh, perm = lax.sort((hh, lax.iota(jnp.int32, cap)), num_keys=1, is_stable=True)
    return BuildSide(sh, perm)


def observe_span(build: BuildSide) -> tuple:
    """(lo, hi, dup) of an exact index's live keys: the first, the last,
    and whether two neighbours are equal (int32), read off the sorted keys.
    An index with no live key gives (2**62, -2**62, 0), as a `min` and a
    `max` over nothing live would."""
    sh = build.sorted_hash
    big = jnp.int64(1) << 62
    live = sh != I64_MAX
    n = jnp.sum(live, dtype=jnp.int32)
    lo = jnp.where(n > 0, sh[0], big)
    hi = jnp.where(n > 0, sh[jnp.maximum(n - 1, 0)], -big)
    dup = jnp.any((sh[1:] == sh[:-1]) & live[1:])
    return lo, hi, dup.astype(jnp.int32)


class SrcRuns(NamedTuple):
    """The output's runs, for the fill body: probe row i's slots are
    [start[i], start[i] + ecounts[i])."""

    start: jnp.ndarray   # int32[pcap] the row's first output slot; out_cap
    #                      where it owns none (or none below out_cap)
    prev: jnp.ndarray    # int32[pcap] the nearest owner before the row, -1:
    #                      none


class JoinResult(NamedTuple):
    probe_idx: jnp.ndarray   # int32[OC] source probe-row index per output row
    build_idx: jnp.ndarray   # int32[OC] source build-row index (clipped when unmatched)
    matched: jnp.ndarray     # bool[OC] true => real build match (false => null-extended)
    out_mask: jnp.ndarray    # bool[OC] live output rows
    needed: jnp.ndarray      # int32 scalar: total rows the join wanted to emit
    runs: SrcRuns | None = None  # where `src_path` said "fill": take_probe's
    unsure: jnp.ndarray | None = None  # a semi/anti join on a hash index:
    #   int32, the probe rows `_exists` could not decide (nonzero: run the
    #   join again with `expand=True`)


def take_probe(r: JoinResult, x: jnp.ndarray) -> jnp.ndarray:
    """`x[r.probe_idx]`, a probe-side column at every output slot, on the
    body `src_path` picked for the join."""
    if r.runs is None:
        return jnp.take(x, r.probe_idx)
    return _fill(r.runs, x, r.probe_idx.shape[0])


def _fill(runs: SrcRuns, x: jnp.ndarray, oc: int) -> jnp.ndarray:
    """`x[src]` with no gather at the output's size. `src` never falls
    from one slot to the next, so the value at a slot is the sum of the
    steps between consecutive owners up to it: each owner's step from the
    owner before it goes to its first slot (`pcap` scattered scalars), and
    one `cumsum` carries them along the runs. Exact for any bits: the sum
    telescopes in wrapping int32, which a 64-bit value rides as two halves
    (an int64 scatter and scan cost the chip five times an int32's, and its
    compiler eight times)."""
    dt = x.dtype
    if dt.itemsize == 8:
        wide = x if dt == jnp.int64 else lax.bitcast_convert_type(
            x, jnp.int64)
        low = _fill(runs, wide.astype(jnp.int32), oc)
        high = _fill(runs, (wide >> 32).astype(jnp.int32), oc)
        wide = (high.astype(jnp.int64) << 32) | (
            low.astype(jnp.int64) & 0xFFFFFFFF)
        return wide if dt == jnp.int64 else lax.bitcast_convert_type(wide, dt)
    whole = dt == jnp.bool_ or jnp.issubdtype(dt, jnp.integer)
    bits = x.astype(jnp.int32) if whole else lax.bitcast_convert_type(
        x, jnp.int32)                  # the engine's narrowest float is 32
    with jax.named_scope("src_fill"):
        before = jnp.where(runs.prev >= 0,
                           jnp.take(bits, jnp.maximum(runs.prev, 0)), 0)
        steps = jnp.zeros(oc, dtype=jnp.int32).at[runs.start].set(
            bits - before, mode="drop")
        out = jnp.cumsum(steps, dtype=jnp.int32)
    return out.astype(dt) if whole else lax.bitcast_convert_type(out, dt)


def take_planes(planes: Sequence[jnp.ndarray | None], fetch) -> list:
    """Each bool plane of one side at the output's slots (`None` stays
    `None`), for one fetch per eight planes: they ride as the bits of uint8
    words, packed at the side's own capacity, and `fetch` (a gather by the
    side's row index, or `take_probe`) moves the words. A gathered byte
    costs what a gathered bool costs, whatever the table's size (an int32
    word would cost up to twice that from a 32 Mi table); a side with one
    plane fetches it as it is."""
    live = [p for p in planes if p is not None]
    if len(live) < 2:
        return [None if p is None else fetch(p) for p in planes]
    with jax.named_scope("pack_valid"):
        words = []
        for at in range(0, len(live), 8):
            word = jnp.uint8(0)
            for bit, plane in enumerate(live[at:at + 8]):
                word = word | (plane.astype(jnp.uint8) << bit)
            words.append(fetch(word))
        out, at = [], 0
        for p in planes:
            out.append(None if p is None else
                       (words[at // 8] >> (at % 8)) & 1 == 1)
            at += p is not None
        return out


def probe_join(build: BuildSide,
               build_key_cols: Sequence[jnp.ndarray],
               build_key_valids: Sequence[jnp.ndarray | None],
               probe_key_cols: Sequence[jnp.ndarray],
               probe_key_valids: Sequence[jnp.ndarray | None],
               probe_mask: jnp.ndarray,
               out_capacity: int,
               join_type: str = "inner", key: str = "hash",
               expand: bool = False) -> JoinResult:
    """join_type: inner | left_outer | left_semi | left_anti.

    'left' always refers to the probe side; the planner flips sides for
    right joins (as the reference's planner does for build-side selection,
    sqlx/SparkStrategies.scala join selection). `key` is what `build` was
    indexed on (`key_path`). A semi or anti join decides existence, one
    slot a row it may keep (`_exists`), unless `expand` asks for the
    expansion, which a caller does where `_exists` left rows `unsure`."""
    pcap = probe_mask.shape[0]
    oc = out_capacity

    with jax.named_scope("probe"):
        ph = _index_keys(probe_key_cols, probe_key_valids, key)
        usable = probe_mask
        for v in probe_key_valids:
            if v is not None:
                usable = usable & v
        ph = jnp.where(usable, ph, I64_MAX - 1)  # sentinel: matches nothing

        lo, hi = rank_sorted(build.sorted_hash, ph, "both")
        counts = jnp.where(usable, hi - lo, 0)
    if join_type in ("left_semi", "left_anti") and not expand:
        if key == "exact":
            return _exists(build, (), (), (), probe_mask, oc, join_type,
                           pcap, lo, counts)
        return _exists(build, build_key_cols, build_key_valids,
                       probe_key_cols, probe_mask, oc, join_type, pcap, lo,
                       counts)
    if key == "exact":
        # the ranges hold the probe's key and nothing else: no key to check
        return _expand(build, (), (), (), (), probe_mask, oc, join_type,
                       pcap, lo, counts)
    return _expand(build, build_key_cols, build_key_valids, probe_key_cols,
                   probe_key_valids, probe_mask, oc, join_type, pcap, lo,
                   counts)


@jax.named_scope("exists")
def _exists(build, build_key_cols, build_key_valids, probe_key_cols,
            probe_mask, oc, join_type, pcap, lo, counts) -> JoinResult:
    """probe_join's second loop for a semi or anti join: whether each probe
    row has a match, one output slot a row that can be kept (a semi join's
    rows whose range is not empty, an anti join's live rows), in probe
    order, so `needed` counts those rows and the join's capacity is what
    they fill, not the probe side's. A slot reaches its row as `_expand`'s
    do (`src_path`). An exact index hands in no keys: a range holds the
    probe's key alone. On a hash index the range's first build row is
    checked on the true keys (the build's null keys are out of the index
    already): a range of one row that fails holds no match; one of more
    rows whose first row fails (keys of one hash, interleaved by the
    stable sort) is `unsure`. `build_idx` is the range's first row."""
    semi = join_type == "left_semi"
    ecounts = ((counts > 0) if semi else probe_mask).astype(jnp.int32)
    offsets = jnp.cumsum(ecounts)
    total = offsets[pcap - 1] if pcap > 0 else jnp.int32(0)
    if src_path(pcap, oc) == "fill":
        runs, src, _ = _src_runs(offsets, ecounts, oc)

        def by_src(x):
            return _fill(runs, x, oc)
    else:
        runs = None
        src = jnp.minimum(rank_sorted(offsets, lax.iota(jnp.int64, oc),
                                      "right"), pcap - 1)

        def by_src(x):
            return jnp.take(x, src)
    # a slot below `total` is a candidate row's, and that row is live
    in_range = lax.iota(jnp.int32, oc) < total
    n = by_src(counts)
    bidx = jnp.take(build.perm, jnp.minimum(by_src(lo),
                                            build.perm.shape[0] - 1))
    found = n > 0
    unsure = None
    if build_key_cols:
        first = found
        for bc, bv, pc_ in zip(build_key_cols, build_key_valids,
                               probe_key_cols):
            eq = jnp.take(bc, bidx) == by_src(pc_)
            if bv is not None:
                eq = eq & jnp.take(bv, bidx)
            first = first & eq
        unsure = jnp.sum(in_range & (n > 1) & ~first, dtype=jnp.int32)
        found = first
    keep = found if semi else ~found
    return JoinResult(src, bidx, found, in_range & keep,
                      total.astype(jnp.int64), runs, unsure)


@jax.named_scope("src_fill")
def _src_runs(offsets, ecounts, oc: int) -> tuple:
    """The fill body's bookkeeping: (`SrcRuns`, `src`, each row's first
    slot). Probe row i owns the slots [offsets[i] - ecounts[i], offsets[i])
    if it has any; the last row also owns what lies beyond the last offset,
    as the gather body's clipped `src` has it, so every slot has an owner
    and both bodies give the same arrays, dead slots included."""
    pcap = offsets.shape[0]
    row = lax.iota(jnp.int32, pcap)
    start = offsets - ecounts
    owns = ((ecounts > 0) | (row == pcap - 1)) & (start < oc)
    at = jnp.where(owns, start, oc)
    last = lax.cummax(jnp.where(owns, row, -1), axis=0)
    prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), last[:-1]])
    src = lax.cummax(jnp.zeros(oc, jnp.int32).at[at].set(row, mode="drop"),
                     axis=0)
    return SrcRuns(at, prev), src, start


@jax.named_scope("expand")
def _expand(build, build_key_cols, build_key_valids, probe_key_cols,
            probe_key_valids, probe_mask, oc, join_type, pcap, lo,
            counts) -> JoinResult:
    """probe_join's second loop: the match ranges flattened into the
    static-capacity output, each pair verified on the true keys. An exact
    index (`key_path`) hands in no keys: its ranges are the matches."""

    # --- verify hash ranges by comparing true keys, count real matches ----
    # For semi/anti we must not rely on hash ranges alone. Verified counts
    # also matter for left_outer's null-extension decision. We verify during
    # expansion (cheap: one gather per key col) and fix the semi/anti/outer
    # masks after expansion via a max-scatter back to probe rows.

    if join_type not in ("inner", "left_semi", "left_anti", "left_outer"):
        raise ValueError(f"unsupported join type {join_type}")
    if join_type in ("left_semi", "left_anti", "left_outer"):
        ecounts = jnp.maximum(counts, jnp.where(probe_mask, 1, 0))
    else:
        ecounts = counts

    offsets = jnp.cumsum(ecounts)  # inclusive; int32, as the counts are
    total = offsets[pcap - 1] if pcap > 0 else jnp.int64(0)

    j = lax.iota(jnp.int64, oc)
    bcap = build.perm.shape[0]
    fill = src_path(pcap, oc) == "fill"
    if fill:
        runs, src, start = _src_runs(offsets, ecounts, oc)

        def by_src(x):
            return _fill(runs, x, oc)

        in_range = j < total
        # A row owns slots only if it is live with a usable key, or live
        # (the outer kinds' one slot): no slot below `total` reads a dead
        # probe row, and none with a build row behind it a null probe key,
        # so neither mask is fetched.
        j32 = lax.iota(jnp.int32, oc)
        if join_type == "inner":
            within = None
            has_build = in_range          # every slot owned is a pair
        else:
            within = j32 - by_src(start)
            has_build = within < by_src(counts)
        # lo[src] + within, the slot's place in the sorted build side
        bpos = jnp.minimum(bcap - 1, j32 + by_src(lo - start))
    else:
        runs = None
        src = jnp.minimum(rank_sorted(offsets, j, "right"), pcap - 1)

        def by_src(x):
            return jnp.take(x, src)

        base = offsets[src] - ecounts[src]
        within = (j - base).astype(jnp.int32)
        in_range = j < total

        has_build = within < counts[src]
        bpos = jnp.minimum(bcap - 1, lo[src] + within)
    bidx = jnp.take(build.perm, bpos)

    # verify true key equality (null keys already excluded via sentinels)
    pair_ok = has_build
    for bc, bv, pc_, pv in zip(build_key_cols, build_key_valids,
                               probe_key_cols, probe_key_valids):
        b_val = jnp.take(bc, bidx)
        p_val = by_src(pc_)
        eq = b_val == p_val
        if bv is not None:
            eq = eq & jnp.take(bv, bidx)
        if pv is not None and not fill:
            eq = eq & jnp.take(pv, src)
        pair_ok = pair_ok & eq

    live_probe = None if fill else jnp.take(probe_mask, src)

    def alive():
        return in_range if fill else in_range & live_probe

    if join_type == "inner":
        out_mask = alive() & pair_ok
        return JoinResult(src, bidx, pair_ok, out_mask,
                          total.astype(jnp.int64), runs)

    # count of VERIFIED matches per probe row (scatter-add over output rows)
    vmatch = jnp.zeros(pcap, dtype=jnp.int32).at[src].add(
        (in_range & pair_ok).astype(jnp.int32), mode="drop")

    if join_type == "left_semi":
        first_slot = within == 0
        out_mask = alive() & first_slot & (by_src(vmatch) > 0)
    elif join_type == "left_anti":
        first_slot = within == 0
        out_mask = alive() & first_slot & (by_src(vmatch) == 0)
    else:
        # left_outer: matched rows pass; unmatched probe rows emit exactly
        # one null-extended row in their first slot
        no_match = by_src(vmatch) == 0
        null_row = no_match & (within == 0)
        out_mask = alive() & (pair_ok | null_row)
    return JoinResult(src, bidx, pair_ok, out_mask, total.astype(jnp.int64),
                      runs)


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
