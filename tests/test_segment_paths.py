"""The two bodies over a sorted-segment layout (`ops/grouping.py`,
`ops/window.py`): the scatter body and the scan body give the same arrays,
bit for bit, and `segment_path` is a rule of the static capacity and the
accumulator's type."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_tpu.ops import grouping as G
from spark_tpu.ops import window as W

_I64 = np.iinfo(np.int64)
CAP = 96


def _case(name):
    """(keys, key valid, values, value valid, row mask) of one shape the
    two bodies must agree on, as numpy arrays of CAP rows."""
    r = np.random.default_rng(sum(map(ord, name)))
    keys = r.integers(0, 7, CAP).astype(np.int32)
    kvalid = r.random(CAP) > 0.15
    vals = r.integers(-1000, 1000, CAP).astype(np.int64)
    vvalid = r.random(CAP) > 0.2
    mask = r.random(CAP) > 0.25           # dead rows between live ones
    if name == "int32_values":
        vals = vals.astype(np.int32)
    elif name == "decimal_negatives":
        vals = r.integers(-10**17, 10**17, CAP).astype(np.int64)
    elif name == "running_sum_wraps":
        # the running sum passes 2^63 and comes back; no group's own does:
        # each group holds as many +2^62 as -2^62, and the +2^62 come first
        keys = np.repeat(np.arange(8, dtype=np.int32), CAP // 8)
        kvalid[:] = vvalid[:] = mask[:] = True
        big = np.where(np.arange(CAP) % (CAP // 8) < CAP // 16, 2**62,
                       -2**62).astype(np.int64)
        vals = big + r.integers(-5, 5, CAP)
        keys = np.sort(keys)[::-1].copy()  # the sort sees the -2^62 last
    elif name == "dead_rows_after":
        mask = np.arange(CAP) < 60
    elif name == "group_of_nulls":
        vvalid = vvalid & (keys != 3)      # group 3 has no non-null value
    elif name == "one_group":
        keys[:] = 5
        kvalid[:] = True
    elif name == "cap_groups":
        keys = r.permutation(CAP).astype(np.int32)
        kvalid[:] = mask[:] = True
    elif name == "empty":
        mask[:] = False
    elif name == "all_null_keys":
        kvalid[:] = False
    else:
        assert name == "mixed"
    return keys, kvalid, vals, vvalid, mask


CASES = ["mixed", "int32_values", "decimal_negatives", "running_sum_wraps",
         "dead_rows_after", "group_of_nulls", "one_group", "cap_groups",
         "empty", "all_null_keys"]


def _agg(result):
    """`group_aggregate`'s result with zeros under a NULL key: what a key's
    data is there is nobody's business, and the bodies differ in it."""
    out_keys, *rest = result
    return ([(k if v is None else jnp.where(v, k, 0), v)
             for k, v in out_keys], *rest)


def _same(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for i, (x, y) in enumerate(zip(la, lb)):
        assert x.dtype == y.dtype and x.shape == y.shape, (what, i)
        assert np.asarray(x).tolist() == np.asarray(y).tolist(), (what, i)


@pytest.mark.parametrize("case", CASES)
def test_group_aggregate_same_on_both_paths(case):
    """Group keys, buffers, validities, the mask and the number of groups:
    every array the same from both bodies, the ops that never scan (a
    float sum, min, first) among the ones that do."""
    keys, kvalid, vals, vvalid, mask = map(jnp.asarray, _case(case))
    k2 = (keys.astype(jnp.int64) * 3) % 5         # a second key, no nulls
    fvals = vals.astype(jnp.float64) / 7
    ops = ("sum", "count", "countstar", "sum", "sum", "min", "first", "sum")
    vd = [vals, vals, mask, fvals, keys, vals, vals, vals]
    vv = [vvalid, vvalid, None, vvalid, None, vvalid, None, kvalid]
    hashed = keys.astype(jnp.int64) * 1000003 + 17    # as a string's
    got = {path: _agg(G.group_aggregate(
        [hashed, k2], [kvalid, None], [keys, None], mask, ops, vd, vv,
        path=path)) for path in ("scatter", "scan")}
    _same(got["scatter"], got["scan"], case)
    own = _agg(G.group_aggregate([keys, k2], [kvalid, None], [None, None],
                                 mask, ops, vd, vv, path="scan"))
    if case != "all_null_keys":    # hashes and keys sort alike here
        _same(own, got["scan"], (case, "the sort's own keys"))
    out_keys, bufs, out_mask, n = got["scan"]
    # against numpy, so that the two are not the same wrong answer
    live = np.asarray(mask)
    k = np.where(np.asarray(kvalid), np.asarray(keys), -1)[live]
    groups = sorted(set(zip(k.tolist(), np.asarray(k2)[live].tolist())))
    assert int(n) == len(groups) == int(np.asarray(out_mask).sum())
    x = np.asarray(vals).astype(np.int64)[live]
    ok = np.asarray(vvalid)[live]
    want = {}
    with np.errstate(over="ignore"):
        for g in groups:
            rows = (k == g[0]) & (np.asarray(k2)[live] == g[1])
            want[g] = (int(x[rows & ok].sum()), int((rows & ok).sum()),
                       int(rows.sum()))
    gk = np.where(np.asarray(out_keys[0][1]), np.asarray(out_keys[0][0]), -1)
    have = {(int(gk[i]), int(out_keys[1][0][i])):
            (int(bufs[0][0][i]), int(bufs[1][0][i]), int(bufs[2][0][i]))
            for i in range(len(groups))}
    assert have == want, case
    for i, g in enumerate(sorted(have, key=list(have).index)):
        assert bool(bufs[0][1][i]) == (want[g][1] > 0), (case, g)


def test_group_aggregate_presents_sorted_keys_once():
    """An array that is a key and a value, or two ops over one column, is
    sent through the group sort once: the scan body's sort is no wider
    than its distinct arrays."""
    keys, kvalid, vals, vvalid, mask = map(jnp.asarray, _case("mixed"))

    def f(path):
        return jax.jit(lambda k, kv, x, xv, m: G.group_aggregate(
            [k], [kv], [None], m, ("sum", "count", "sum"), [x, x, k],
            [xv, xv, kv], path=path)).lower(keys, kvalid, vals, vvalid,
                                            mask).as_text()

    text = f("scan")
    sorts = [ln for ln in text.splitlines() if "stablehlo.sort" in ln]
    # the group sort: flag, null flag, key, iota + the value, the key as a
    # value, the two validity planes in one word; the second: flag + a
    # count and a running sum for each of the two columns + the key, its
    # null flag's word
    assert [ln.count("%") - 1 for ln in sorts] == [7, 7], sorts
    # and nothing is addressed by CAP computed indices, gather or scatter
    assert _PER_SLOT not in text and _PER_SLOT in f("scatter")


_PER_SLOT = f"tensor<{CAP}x1xi32>"     # the indices of a gather or scatter


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_float_sums_never_take_differences(dtype):
    """A floating sum keeps the scatter-add inside the scan body: one huge
    value in an earlier group must not cost a later group its digits."""
    keys = jnp.asarray(np.repeat(np.arange(4, dtype=np.int32), 4))
    vals = np.full(16, 0.125, dtype)
    vals[0] = 1e30 if dtype == "float64" else 1e20
    ones = jnp.ones(16, bool)
    got = {path: G.group_aggregate([keys], [None], [None], ones,
                                   ("sum", "count"), [jnp.asarray(vals)] * 2,
                                   [None, None], path=path)
           for path in ("scatter", "scan")}
    _same(got["scatter"], got["scan"], dtype)
    assert np.asarray(got["scan"][1][0][0])[1:4].tolist() == [0.5, 0.5, 0.5]
    lo = W.build_layout([keys], [None], [], [], [], ones)
    assert W.unbounded_path("agg_unbounded_sum", vals.dtype, 1 << 26) \
        == "scatter"
    assert G.segment_path(1 << 26, vals.dtype) == "scatter"
    s, _ok = W.w_agg_unbounded(lo, jnp.asarray(vals), None, "sum")
    assert np.asarray(s)[4:].tolist() == [0.5] * 12


@pytest.mark.parametrize("kind", ["sum", "count", "avg"])
@pytest.mark.parametrize("case", CASES)
def test_window_unbounded_same_on_both_paths(case, kind):
    """A partition's sum, count and average at every row, dead rows too,
    and the same once more after the way back to the input's order."""
    keys, kvalid, vals, vvalid, mask = map(jnp.asarray, _case(case))
    lo = W.build_layout([keys], [kvalid], [], [], [], mask)
    got = {path: W.w_agg_unbounded(lo, vals, vvalid, kind, path=path)
           for path in ("scatter", "scan")}
    _same(got["scatter"], got["scan"], (case, kind))
    back = {path: W.scatter_back(lo, *got["scan"], path=path)
            for path in ("scatter", "scan")}
    _same(back["scatter"], back["scan"], (case, kind, "back"))
    if kind == "sum":
        live = np.asarray(mask)
        k = np.where(np.asarray(kvalid), np.asarray(keys), -1)
        w = live & np.asarray(vvalid)
        x = np.asarray(vals).astype(np.int64)
        with np.errstate(over="ignore"):
            want = [int(x[w & (k == k[i])].sum()) for i in range(CAP)]
        have = np.asarray(back["scan"][0])
        assert have[live].tolist() == np.asarray(want)[live].tolist()


@pytest.mark.parametrize("perm", ["identity", "reversed", "random",
                                  "rotated", "swap_ends"])
@pytest.mark.parametrize("valid", [False, True])
def test_scatter_back_by_sort_is_scatter_back(perm, valid):
    r = np.random.default_rng(3)
    p = {"identity": np.arange(CAP), "reversed": np.arange(CAP)[::-1],
         "random": r.permutation(CAP), "rotated": np.roll(np.arange(CAP), 7),
         "swap_ends": np.r_[CAP - 1, np.arange(1, CAP - 1), 0]}[perm]
    lo = W.WindowLayout(*([jnp.asarray(p.astype(np.int32))] + [None] * 9))
    vals = jnp.asarray(r.integers(_I64.min, _I64.max, CAP))
    ok = jnp.asarray(r.random(CAP) > 0.5) if valid else None
    got = {path: W.scatter_back(lo, vals, ok, path=path)
           for path in ("scatter", "scan")}
    _same(got["scatter"], got["scan"], perm)
    assert np.asarray(got["scan"][0])[p].tolist() == np.asarray(vals).tolist()
    assert (got["scan"][1] is None) == (not valid)


Mi = 1 << 20


@pytest.mark.parametrize("cap,dtype,path", [
    (8 * Mi, None, "scan"),            # v1's aggregate and windows
    (8 * Mi, np.int64, "scan"),
    (1 * Mi, np.int64, "scan"),        # q89's
    (131072, np.int64, "scatter"),     # q3's and q7's aggregates
    (4096, None, "scatter"),           # a test's batch
    (8, None, "scatter"), (0, None, "scatter"),
    (8 * Mi, np.float64, "scatter"),   # a float never takes differences
    (64 * Mi, np.float32, "scatter"),
    (64 * Mi, np.int32, "scan"),
])
def test_segment_path_is_a_rule_of_capacity_and_type(cap, dtype, path):
    assert G.segment_path(cap, dtype) == path


def test_segment_path_is_monotone_in_capacity():
    paths = [G.segment_path(1 << b) for b in range(4, 31)]
    flip = paths.index("scan")
    assert paths == ["scatter"] * flip + ["scan"] * (len(paths) - flip)
    # a sort's fixed cost is what the crossover repays, as the joins' is
    assert (1 << (flip + 3)) * (G.SCATTER_S - G.SCAN_S) < G.SORT_FIXED_S \
        <= (1 << (flip + 4)) * (G.SCATTER_S - G.SCAN_S)
