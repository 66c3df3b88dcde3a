"""Kernel unit tests (the reference tests expression eval both interpreted
and codegen'd — here numpy is the oracle for every jitted kernel;
SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from join_reference import (
    build_index_of_pr33, expand_of_pr31, join_oracle, probe_join_of_pr33,
)
from spark_tpu.ops import (
    SortKeySpec, build_index, cross_join, group_rows, group_output_mask,
    hash_columns, hash_partition, limit_mask, mix64, partition_ids,
    probe_join, scatter_group_keys, seg_count, seg_first, seg_max, seg_min,
    seg_sum, sort_permutation,
)


def test_mix64_deterministic_and_spread():
    x = jnp.arange(1000, dtype=jnp.int64)
    h1 = np.asarray(mix64(x))
    h2 = np.asarray(mix64(x))
    assert (h1 == h2).all()
    assert len(np.unique(h1)) == 1000
    # partition balance
    pids = np.asarray(partition_ids(jnp.asarray(h1), 8))
    counts = np.bincount(pids, minlength=8)
    assert counts.min() > 60  # roughly uniform


def test_group_rows_numpy_oracle():
    rng = np.random.default_rng(0)
    n, cap = 900, 1024
    keys = rng.integers(0, 50, n)
    vals = rng.integers(-100, 100, n)
    k = np.zeros(cap, np.int64)
    v = np.zeros(cap, np.int64)
    k[:n] = keys
    v[:n] = vals
    mask = np.arange(cap) < n

    layout = group_rows([jnp.asarray(k)], [None], jnp.asarray(mask))
    sums, cnts = seg_sum(layout, jnp.asarray(v))
    out_k, _ = scatter_group_keys(layout, jnp.asarray(k), None)
    om = np.asarray(group_output_mask(layout))

    got = {}
    for kk, s in zip(np.asarray(out_k)[om], np.asarray(sums)[om]):
        got[int(kk)] = int(s)
    want = {}
    for kk, vv in zip(keys, vals):
        want[int(kk)] = want.get(int(kk), 0) + int(vv)
    assert got == want

    mins, has = seg_min(layout, jnp.asarray(v))
    gotm = {int(kk): int(m) for kk, m in
            zip(np.asarray(out_k)[om], np.asarray(mins)[om])}
    wantm = {}
    for kk, vv in zip(keys, vals):
        wantm[int(kk)] = min(wantm.get(int(kk), 10**9), int(vv))
    assert gotm == wantm


def test_group_rows_null_keys_group_together():
    k = jnp.asarray([1, 2, 1, 99, 99], dtype=jnp.int64)
    valid = jnp.asarray([True, True, True, False, False])
    mask = jnp.ones(5, dtype=bool)
    layout = group_rows([k], [valid], mask)
    assert int(layout.num_groups) == 3  # {1}, {2}, {null}


def test_sort_permutation_desc_nulls():
    k = jnp.asarray([3, 1, 2, 0, 0], dtype=jnp.int64)
    valid = jnp.asarray([True, True, True, False, True])
    mask = jnp.asarray([True, True, True, True, False])
    perm = sort_permutation([k], [valid], [SortKeySpec(ascending=False)], mask)
    out = np.asarray(jnp.take(k, perm))
    vout = np.asarray(jnp.take(valid, perm))
    mout = np.asarray(jnp.take(mask, perm))
    # live rows: 3,2,1 then null last (desc → nulls last by default)
    assert list(out[mout][:3]) == [3, 2, 1]
    assert not vout[mout][3]


def test_sort_stability():
    k = jnp.asarray([1, 1, 1, 1], dtype=jnp.int64)
    mask = jnp.ones(4, dtype=bool)
    perm = sort_permutation([k], [None], [SortKeySpec()], mask)
    assert list(np.asarray(perm)) == [0, 1, 2, 3]


def test_join_inner_oracle():
    rng = np.random.default_rng(1)
    bn, pn = 300, 500
    bcap, pcap = 512, 512
    bk = np.zeros(bcap, np.int64)
    pk = np.zeros(pcap, np.int64)
    bk[:bn] = rng.integers(0, 100, bn)
    pk[:pn] = rng.integers(0, 100, pn)
    bmask = np.arange(bcap) < bn
    pmask = np.arange(pcap) < pn

    bi = build_index([jnp.asarray(bk)], [None], jnp.asarray(bmask))
    r = probe_join(bi, [jnp.asarray(bk)], [None], [jnp.asarray(pk)], [None],
                   jnp.asarray(pmask), out_capacity=1 << 14)
    om = np.asarray(r.out_mask)
    pi = np.asarray(r.probe_idx)[om]
    bi_idx = np.asarray(r.build_idx)[om]
    got = sorted(zip(pi.tolist(), bi_idx.tolist()))
    want = sorted((i, j) for i in range(pn) for j in range(bn)
                  if pk[i] == bk[j])
    assert got == want


def test_join_left_outer_and_anti():
    bk = jnp.asarray([1, 2, 0, 0], dtype=jnp.int64)
    bmask = jnp.asarray([True, True, False, False])
    pk = jnp.asarray([1, 5, 2, 2], dtype=jnp.int64)
    pmask = jnp.ones(4, dtype=bool)
    bi = build_index([bk], [None], bmask)
    r = probe_join(bi, [bk], [None], [pk], [None], pmask, 16, "left_outer")
    om = np.asarray(r.out_mask)
    rows = sorted(zip(np.asarray(r.probe_idx)[om].tolist(),
                      np.asarray(r.matched)[om].tolist()))
    assert rows == [(0, True), (1, False), (2, True), (3, True)]
    r2 = probe_join(bi, [bk], [None], [pk], [None], pmask, 16, "left_anti")
    om2 = np.asarray(r2.out_mask)
    assert np.asarray(r2.probe_idx)[om2].tolist() == [1]


def test_join_null_keys_never_match():
    bk = jnp.asarray([1, 1], dtype=jnp.int64)
    bvalid = jnp.asarray([True, False])
    bmask = jnp.ones(2, dtype=bool)
    pk = jnp.asarray([1], dtype=jnp.int64)
    pvalid = jnp.asarray([False])
    pmask = jnp.ones(1, dtype=bool)
    bi = build_index([bk], [bvalid], bmask)
    r = probe_join(bi, [bk], [bvalid], [pk], [pvalid], pmask, 8, "inner")
    assert int(np.asarray(r.out_mask).sum()) == 0


def test_join_overflow_reports_needed():
    bk = jnp.zeros(8, dtype=jnp.int64)
    bmask = jnp.ones(8, dtype=bool)
    pk = jnp.zeros(8, dtype=jnp.int64)
    pmask = jnp.ones(8, dtype=bool)
    bi = build_index([bk], [None], bmask)
    r = probe_join(bi, [bk], [None], [pk], [None], pmask, out_capacity=16)
    assert int(r.needed) == 64  # 8x8 matches, capacity 16 → host must retry


_I64 = np.iinfo(np.int64)
_RANK_RNG = np.random.default_rng(26)


def _rank_case(name):
    """(sorted keys, queries) for one shape `rank_sorted` must get right."""
    r = _RANK_RNG
    if name == "duplicate_runs":
        return np.sort(r.integers(0, 9, 200)), r.integers(-2, 11, 300)
    if name == "join_sentinels":
        # build hashes padded with I64_MAX (dead rows), probes carrying
        # I64_MAX - 1 (unusable: must land before the padding, match none)
        a = np.sort(np.concatenate([r.integers(_I64.min, _I64.max - 1, 40),
                                    np.full(24, _I64.max)]))
        v = np.concatenate([a[:50:3], np.full(9, _I64.max - 1),
                            r.integers(_I64.min, _I64.max - 1, 20)])
        return a, v
    if name == "int64_extremes":
        a = np.array([_I64.min, _I64.min, -1, 0, 0, _I64.max - 1, _I64.max,
                      _I64.max])
        return a, np.array([_I64.max, _I64.min, 0, _I64.max - 1, -1, 1,
                            _I64.min + 1])
    if name == "all_below":
        return np.arange(100, 164), np.arange(-30, 0)
    if name == "all_above":
        return np.arange(100, 164), np.arange(500, 530)
    if name == "iota_queries":
        # _expand's shape: inclusive offsets with empty slots, slots 0..n-1
        return np.cumsum(r.integers(0, 4, 120)), np.arange(256)
    if name == "keys_far_more":
        return np.sort(r.integers(-1000, 1000, 5000)), r.integers(-1100,
                                                                  1100, 3)
    if name == "queries_far_more":
        return np.sort(r.integers(-50, 50, 3)), r.integers(-60, 60, 5000)
    if name == "one_key":
        return np.array([7]), np.array([6, 7, 8, 7])
    if name == "one_query":
        return np.array([1, 3, 3, 5]), np.array([3])
    assert name == "one_each"
    return np.array([4]), np.array([4])


@pytest.mark.parametrize("path", ["merge", "search"])
@pytest.mark.parametrize("side", ["left", "right", "both"])
@pytest.mark.parametrize("case", [
    "duplicate_runs", "join_sentinels", "int64_extremes", "all_below",
    "all_above", "iota_queries", "keys_far_more", "queries_far_more",
    "one_key", "one_query", "one_each"])
def test_rank_sorted_is_searchsorted(case, side, path):
    from spark_tpu.ops.joining import rank_sorted

    a, v = (x.astype(np.int64) for x in _rank_case(case))
    got = rank_sorted(jnp.asarray(a), jnp.asarray(v), side, path)
    sides = ("left", "right") if side == "both" else (side,)
    for g, s in zip(got if side == "both" else (got,), sides):
        assert g.dtype == jnp.int32 and g.shape == v.shape
        assert np.asarray(g).tolist() == \
            np.searchsorted(a, v, side=s).tolist(), (case, s, path)


Mi = 1 << 20


@pytest.mark.parametrize("n_sorted,n_queries,path", [
    (131072, 8 * Mi, "merge"),     # q3's item probe: 18 steps, 8 Mi queries
    (2 * Mi, 8 * Mi, "merge"),     # q7's customer_demographics probe
    (131072, 8 * Mi, "merge"),     # the date join's _expand: 8 Mi slots
    (32 * Mi, 131072, "search"),   # date_dim probes the fact table
    (8 * Mi, 131072, "search"),    # q3's item _expand: 131 072 slots
    (131072, 131072, "search"),    # a discarded first program's joins
    (1024, 1024, "search"),        # small inputs never pay for the sorts
    (1024, 4 * Mi, "merge"),       # a stage-tier batch probing a small build
    (0, 16, "search"), (16, 0, "search"),
])
def test_rank_path_is_a_rule_of_two_lengths(n_sorted, n_queries, path):
    from spark_tpu.ops.joining import rank_path

    assert rank_path(n_sorted, n_queries) == path


JOIN_TYPES = ["inner", "left_outer", "left_semi", "left_anti"]


def _join_case(name):
    """(build key, its validity, build mask, probe key, its validity, probe
    mask, out capacity) for one shape of join."""
    rng = np.random.default_rng(7)
    bcap, pcap, oc, keys = 64, 128, 1 << 11, 12
    if name in ("fan_out", "overflow"):
        # every key many times on both sides; `overflow` wants more
        # slots than the capacity has
        bcap, keys, oc = 256, 6, (1 << 13 if name == "fan_out" else 1 << 10)
    elif name == "fill_unforced":
        bcap, pcap, oc, keys = 4096, 1024, 1 << 16, 300
    bk = rng.integers(0, keys, bcap).astype(np.int64)
    pk = rng.integers(-3, keys + 3, pcap).astype(np.int64)
    bvalid = rng.random(bcap) > 0.15
    pvalid = rng.random(pcap) > 0.15
    bmask = np.arange(bcap) < bcap * 3 // 4
    pmask = rng.random(pcap) > 0.1
    if name == "no_usable_probe":      # every probe row dead or null-keyed
        pvalid &= ~pmask
    return tuple(jnp.asarray(x) for x in (bk, bvalid, bmask, pk, pvalid,
                                          pmask)) + (oc,)


def _joined(case, join_type):
    """The join on the expansion, which a semi or anti join takes where
    its existence test leaves rows undecided (tests/test_existence_joins.py
    holds that test to it)."""
    bk, bvalid, bmask, pk, pvalid, pmask, oc = case
    bi = build_index([bk], [bvalid], bmask)
    return probe_join(bi, [bk], [bvalid], [pk], [pvalid], pmask, oc,
                      join_type, expand=True)


def _same_arrays(a, b, what):
    a, b = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(a) == len(b), what
    for at, (x, y) in enumerate(zip(a, b)):
        assert x.dtype == y.dtype and x.shape == y.shape, (what, at)
        assert np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=True), (what, at)


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_probe_join_same_on_both_rank_paths(join_type, monkeypatch):
    """Hash-equal runs (duplicate keys on both sides), null keys, dead
    rows, probes that match nothing: the two bodies of `rank_sorted` hand
    `_expand` the same ranks, so every array of the result is the same."""
    from spark_tpu.ops import joining as J

    monkeypatch.setattr(J, "src_path", lambda p, o: "gather")
    results = {}
    for path in ("merge", "search"):
        monkeypatch.setattr(J, "rank_path", lambda n, m, _p=path: _p)
        results[path] = _joined(_join_case("as_is"), join_type)
    assert int(results["merge"].out_mask.sum()) > 0
    _same_arrays(results["merge"], results["search"], join_type)


@pytest.mark.parametrize("join_type", JOIN_TYPES)
@pytest.mark.parametrize("case", ["as_is", "fan_out", "overflow",
                                  "no_usable_probe", "fill_unforced"])
def test_probe_join_same_on_both_src_paths(case, join_type, monkeypatch):
    """What `_expand` fetches by the non-decreasing `src` it may fill by
    position instead (`src_path`): every array of the result, dead slots
    and an overflowing `needed` included, and every probe column taken
    "by `src`" is the same, element for element, on both bodies and in the
    body PR 31 had."""
    from spark_tpu.ops import joining as J

    data = _join_case(case)
    pcap, oc = data[3].shape[0], data[6]
    rng = np.random.default_rng(32)
    reals = rng.normal(size=pcap)
    reals[::7] = np.nan
    columns = [data[3], data[4], jnp.asarray(reals),
               jnp.asarray(reals.astype(np.float32)),
               jnp.asarray(rng.integers(-128, 127, pcap).astype(np.int8)),
               jnp.asarray(rng.integers(-2**31, 2**31 - 1, pcap)
                           .astype(np.int32))]
    got = {}
    for path in ("fill", "gather", "pr31"):
        if path == "pr31":
            monkeypatch.setattr(J, "_expand", expand_of_pr31)
        elif case != "fill_unforced" or path == "gather":
            monkeypatch.setattr(J, "src_path", lambda p, o, _p=path: _p)
        else:
            assert J.src_path(pcap, oc) == "fill"
        r = _joined(data, join_type)
        assert (r.runs is not None) == (path == "fill")
        taken = [J.take_probe(r, x) for x in columns]
        assert all(t.dtype == x.dtype for t, x in zip(taken, columns))
        got[path] = (tuple(r[:5]), taken)
    needed = int(got["gather"][0][4])
    assert (needed > oc) == (case == "overflow")
    assert (needed == 0) == (case == "no_usable_probe"
                             and join_type == "inner")
    _same_arrays(got["gather"], got["pr31"], (case, join_type, "gather"))
    _same_arrays(got["fill"], got["pr31"], (case, join_type, "fill"))


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_gathering_expand_lowers_to_the_text_of_pr31(join_type, monkeypatch):
    """Where the rule says "gather" (every small join: 1 Ki slots from
    1 Ki probe rows here, the rule unforced) the program is, byte for
    byte, the one it was."""
    from spark_tpu.ops import joining as J

    cap = 1 << 10
    assert J.src_path(cap, cap) == "gather"

    def program(bk, bvalid, bmask, pk, pvalid, pmask):
        return tuple(_joined((bk, bvalid, bmask, pk, pvalid, pmask, cap),
                             join_type))[:5]

    shapes = [jax.ShapeDtypeStruct((cap,), t)
              for t in (jnp.int64, bool, bool, jnp.int64, bool, bool)]
    now = jax.jit(program).lower(*shapes).as_text()
    monkeypatch.setattr(J, "_expand", expand_of_pr31)
    assert jax.jit(program).lower(*shapes).as_text() == now


@pytest.mark.parametrize("pcap,out_cap,path", [
    (131072, 8 * Mi, "fill"),      # q3 m01, q7 m03, v1 m02: date_dim probes
    (1024, 32 * Mi, "fill"),       # q89 m02: store probes the fact table
    (8 * Mi, 8 * Mi, "gather"),    # v1's item and store joins
    (32 * Mi, 4 * Mi, "gather"),   # q89's item join
    (4 * Mi, 1 * Mi, "gather"),    # q89's date join
    (131072, 131072, "gather"),    # a discarded first program's joins
    (1024, 1024, "gather"),        # a small join, as the tests' are
    (1024, 64 * 1024, "fill"),
])
def test_src_path_is_a_rule_of_two_lengths(pcap, out_cap, path):
    from spark_tpu.ops.joining import src_path

    assert src_path(pcap, out_cap) == path


I32 = np.iinfo(np.int32)


@pytest.mark.parametrize("build,probe,path", [
    ([jnp.int32], [jnp.int32], "exact"),     # a surrogate key, a date
    ([jnp.int8], [jnp.int8], "exact"),
    ([jnp.int16], [jnp.int16], "exact"),
    ([jnp.int16], [jnp.int32], "exact"),     # widened, both fit
    ([jnp.uint32], [jnp.int32], "exact"),
    ([jnp.int64], [jnp.int64], "hash"),      # a live key could be a sentinel
    ([jnp.int32], [jnp.int64], "hash"),      # mixed widths, either way
    ([jnp.int64], [jnp.int32], "hash"),
    ([jnp.uint64], [jnp.uint64], "hash"),
    ([jnp.float32], [jnp.float32], "hash"),
    ([jnp.float64], [jnp.float64], "hash"),
    ([jnp.bool_], [jnp.bool_], "hash"),      # callers hand a boolean as int32
    ([jnp.int32, jnp.int32], [jnp.int32, jnp.int32], "hash"),
    ([jnp.int32, jnp.int64], [jnp.int32, jnp.int64], "hash"),
    ([], [], "hash"),
])
def test_key_path_is_a_rule_of_the_keys(build, probe, path):
    """One pair of integers of at most 32 bits indexes itself; the rule
    reads dtypes, or arrays' (a string's equality key is the int64 of its
    LUT hash, so it is the int64 case)."""
    from spark_tpu.ops.joining import key_path

    assert key_path(build, probe) == path
    assert key_path([jnp.zeros(4, dt) for dt in build],
                    [np.dtype(dt) for dt in probe]) == path


def _key_case(name):
    """(build key, its validity, build mask, probe key, its validity, probe
    mask, out capacity), the keys integers of at most 32 bits."""
    rng = np.random.default_rng(34)
    bcap, pcap, oc, dt = 96, 128, 1 << 11, np.int32
    if name == "fan_out":
        bk, pk = rng.integers(0, 5, bcap), rng.integers(-1, 6, pcap)
    elif name == "extremes":
        pool = np.array([I32.min, I32.min + 1, -1, 0, 1, I32.max - 1,
                         I32.max])
        bk, pk = rng.choice(pool, bcap), rng.choice(pool, pcap)
    elif name == "absent":             # no probe key is on the build side
        bk, pk = rng.integers(0, 40, bcap) * 2, rng.integers(0, 40, pcap) * 2 + 1
    elif name == "int8":
        dt = np.int8
        bk, pk = rng.integers(-128, 128, bcap), rng.integers(-128, 128, pcap)
    elif name == "int16_into_int32":   # each side its own width
        dt = np.int16
        bk, pk = rng.integers(-300, 300, bcap), rng.integers(-300, 300, pcap)
    elif name == "one_build_row":
        bcap = 1
        bk, pk = np.array([7]), rng.integers(5, 9, pcap)
    else:
        bk, pk = rng.integers(0, 30, bcap), rng.integers(-3, 33, pcap)
    bvalid = rng.random(bcap) > 0.15
    pvalid = rng.random(pcap) > 0.15
    bmask = rng.random(bcap) > 0.2
    pmask = rng.random(pcap) > 0.1
    if name == "empty_build":
        bmask[:] = False
    elif name == "all_null_build":
        bvalid[:] = False
    elif name == "one_build_row":
        bvalid[:], bmask[:] = True, True
    pk = pk.astype(np.int32 if name == "int16_into_int32" else dt)
    return tuple(jnp.asarray(x) for x in (bk.astype(dt), bvalid, bmask, pk,
                                          pvalid, pmask)) + (oc,)


KEY_CASES = ["as_is", "fan_out", "extremes", "absent", "empty_build",
             "all_null_build", "one_build_row", "int8", "int16_into_int32"]


@pytest.mark.parametrize("src", ["gather", "fill"])
@pytest.mark.parametrize("join_type", JOIN_TYPES)
@pytest.mark.parametrize("case", KEY_CASES)
def test_probe_join_same_on_both_key_paths(case, join_type, src, monkeypatch):
    """An index on the key itself and one on its hash (`key_path`, forced
    either way) give the same join on both bodies of `src_path`: `needed`,
    the live slots, each slot's probe row and whether it has a match are
    equal everywhere, the build row wherever there is one (a slot with
    none points at whatever row lies where its key would), and both are
    what loops over the rows give."""
    from spark_tpu.ops import joining as J

    monkeypatch.setattr(J, "src_path", lambda p, o: src)
    bk, bvalid, bmask, pk, pvalid, pmask, oc = data = _key_case(case)
    assert J.key_path([bk], [pk]) == "exact"
    got = {}
    for key in ("exact", "hash"):
        bi = build_index([bk], [bvalid], bmask, key)
        got[key] = probe_join(bi, [bk], [bvalid], [pk], [pvalid], pmask, oc,
                              join_type, key, expand=True)
        assert (got[key].runs is not None) == (src == "fill")
    exact, hashed = got["exact"], got["hash"]
    _same_arrays((exact.probe_idx, exact.matched, exact.out_mask,
                  exact.needed, exact.runs),
                 (hashed.probe_idx, hashed.matched, hashed.out_mask,
                  hashed.needed, hashed.runs), (case, join_type, src))
    paired = np.asarray(exact.matched)
    assert np.array_equal(np.asarray(exact.build_idx)[paired],
                          np.asarray(hashed.build_idx)[paired])
    live = np.asarray(exact.out_mask)
    assert not (live & ~paired).any() or join_type != "inner"
    rows = [(int(p), int(b) if m and join_type in ("inner", "left_outer")
             else -1)
            for p, b, m in zip(np.asarray(exact.probe_idx)[live],
                               np.asarray(exact.build_idx)[live],
                               paired[live])]
    assert rows == join_oracle(*data[:6], join_type), (case, join_type)
    assert int(exact.needed) <= oc


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.float32, jnp.uint64])
def test_exact_index_refuses_what_the_rule_would(dtype):
    """A caller that hands `build_index` or `probe_join` "exact" for keys
    the rule sends to the hash is told so at trace time."""
    k = jnp.zeros(8, dtype)
    mask = jnp.ones(8, bool)
    with pytest.raises(ValueError, match="no exact index"):
        build_index([k], [None], mask, "exact")
    bi = build_index([k], [None], mask)
    with pytest.raises(ValueError, match="no exact index"):
        probe_join(bi, [k], [None], [k], [None], mask, 16, "inner", "exact")


@pytest.mark.parametrize("keys", ["int64", "two_int32", "float64",
                                  "int32_forced"])
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_hash_keyed_join_lowers_to_the_text_of_pr33(join_type, keys):
    """A join the rule sends to the hash (a 64-bit key, two keys, a float;
    or a 32-bit key with "hash" handed in) is, byte for byte, the program
    it was before the rule: index, probe and expansion."""
    from spark_tpu.ops import joining as J

    cap = 1 << 10
    dts = {"int64": [jnp.int64], "two_int32": [jnp.int32, jnp.int32],
           "float64": [jnp.float64], "int32_forced": [jnp.int32]}[keys]
    n = len(dts)

    def program(index, probe, *cols):
        bk, bv, pk, pv = (list(cols[at * n:(at + 1) * n]) for at in range(4))
        bmask, pmask = cols[4 * n:]
        key = "hash" if keys == "int32_forced" else J.key_path(bk, pk)
        bi = index(bk, bv, bmask, key)
        return tuple(probe(bi, bk, bv, pk, pv, pmask, cap, join_type,
                           key, expand=True))[:5]

    shapes = [jax.ShapeDtypeStruct((cap,), t)
              for t in dts + [bool] * n + dts + [bool] * n + [bool, bool]]
    now = jax.jit(lambda *c: program(build_index, probe_join, *c)).lower(
        *shapes).as_text()
    then = jax.jit(lambda *c: program(
        build_index_of_pr33, probe_join_of_pr33, *c)).lower(*shapes).as_text()
    assert "stablehlo.sort" in now and then == now


def _span_case(name):
    rng = np.random.default_rng(5)
    cap = 64
    keys = rng.permutation(np.arange(-20, 44))
    valid, mask = rng.random(cap) > 0.2, rng.random(cap) > 0.2
    if name == "repeated":
        keys = rng.integers(0, 9, cap)
    elif name == "repeat_is_dead":     # the only repeat is of a dead row
        keys[5], mask[5] = keys[9], False
        mask[9] = valid[9] = True
    elif name == "repeat_is_null":
        keys[5], valid[5] = keys[9], False
        mask[9] = valid[9] = True
    elif name == "all_dead":
        mask[:] = False
    elif name == "all_null":
        valid[:] = False
    elif name == "one_row":
        keys, valid, mask = keys[:1], np.array([True]), np.array([True])
    elif name == "one_live_of_many":
        mask[:] = False
        mask[17] = valid[17] = True
    elif name == "extremes":
        keys[:4] = [I32.min, I32.max, I32.min, 0]
        valid[:4] = mask[:4] = True
    elif name == "no_validity":
        valid = None
    else:
        assert name == "unique"
    return keys.astype(np.int32), valid, mask


@pytest.mark.parametrize("case", [
    "unique", "repeated", "repeat_is_dead", "repeat_is_null", "all_dead",
    "all_null", "one_row", "one_live_of_many", "extremes", "no_validity"])
def test_observe_span_reads_the_sorted_keys(case):
    """First live key, last live key, any live key twice: off an exact
    index, what a `min`, a `max` and a sort of the live keys give (and
    2**62, -2**62, 0 where no key is live, as those would)."""
    from spark_tpu.ops.joining import observe_span

    keys, valid, mask = _span_case(case)
    bi = build_index([jnp.asarray(keys)],
                     [None if valid is None else jnp.asarray(valid)],
                     jnp.asarray(mask), "exact")
    lo, hi, dup = jax.jit(observe_span)(bi)
    assert (lo.dtype, hi.dtype, dup.dtype) == (jnp.int64, jnp.int64,
                                               jnp.int32)
    live = keys[mask if valid is None else mask & valid].astype(np.int64)
    want = (live.min(), live.max(), int(len(np.unique(live)) < len(live))) \
        if len(live) else (1 << 62, -(1 << 62), 0)
    assert (int(lo), int(hi), int(dup)) == want, case


@pytest.mark.parametrize("planes", [1, 2, 7, 8, 9, 31, 33])
def test_take_planes_is_a_gather_of_each(planes):
    """Validity planes ride through a fetch as the bits of uint8 words,
    eight to a word: each comes back as its own gather would have it, a
    `None` plane stays `None`, and a lone plane is fetched as it is."""
    from spark_tpu.ops.joining import take_planes

    rng = np.random.default_rng(planes)
    cap, oc = 512, 2048
    side = [jnp.asarray(rng.random(cap) > 0.3) for _ in range(planes)]
    for at in range(1, planes + 3, 4):
        side.insert(at, None)
    idx = jnp.asarray(rng.integers(0, cap, oc).astype(np.int32))
    fetched = []

    def fetch(word):
        assert word.shape == (cap,)
        fetched.append(word.dtype)
        return jnp.take(word, idx)

    got = take_planes(side, fetch)
    assert fetched == ([jnp.bool_] if planes == 1
                       else [jnp.uint8] * -(-planes // 8))
    assert len(got) == len(side)
    for plane, g in zip(side, got):
        if plane is None:
            assert g is None
            continue
        assert g.dtype == jnp.bool_
        assert np.array_equal(np.asarray(g),
                              np.asarray(plane)[np.asarray(idx)])
    assert take_planes([None, None], fetch) == [None, None]


def test_hash_partition_counts():
    k = jnp.arange(1000, dtype=jnp.int64)
    mask = jnp.ones(1000, dtype=bool)
    pr = hash_partition([k], [None], mask, 7)
    counts = np.asarray(pr.counts)
    assert counts.sum() == 1000
    pids = np.asarray(pr.pids)
    # grouped ascending
    live = pids[pids < 7]
    assert (np.diff(live) >= 0).all()


def test_limit_mask():
    mask = jnp.asarray([True, False, True, True, True])
    out = np.asarray(limit_mask(mask, 2))
    assert out.tolist() == [True, False, True, False, False]


def test_cross_join():
    pmask = jnp.asarray([True, True, False])
    bmask = jnp.asarray([True, False, True])
    r = cross_join(pmask, bmask, 16)
    om = np.asarray(r.out_mask)
    assert int(om.sum()) == 4  # 2 live probe x 2 live build


def test_batch_validation_mode(spark):
    import pyarrow as pa

    spark.conf.set("spark.tpu.debug.validateBatches", "true")
    try:
        df = spark.createDataFrame(pa.table({
            "k": ["a", "b", "a"], "v": [1, 2, 3]}))
        import spark_tpu.api.functions as F

        out = (df.repartition(3).groupBy("k")
               .agg(F.sum("v").alias("s")).orderBy("k")
               .toArrow().to_pydict())
        assert out["s"] == [4, 2]
    finally:
        spark.conf.unset("spark.tpu.debug.validateBatches")


def test_validate_batch_catches_bad_codes():
    import jax.numpy as jnp
    import pytest as _pt

    from spark_tpu.columnar.batch import Column, ColumnarBatch, StringDict
    from spark_tpu.columnar.validate import validate_batch
    from spark_tpu.errors import ExecutionError
    from spark_tpu.types import StructField, StructType, string

    schema = StructType([StructField("s", string, False)])
    bad = ColumnarBatch(
        schema,
        [Column(string, jnp.asarray(np.array([5, 0], np.int32)), None,
                StringDict(["only"]))],
        jnp.asarray(np.array([True, True])), num_rows=2)
    with _pt.raises(ExecutionError, match="out of range"):
        validate_batch(bad, "test")
