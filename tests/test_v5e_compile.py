"""The kernels of the main path compiled for a described TPU v5e, at the
size the benchmark runs them: what the chip's compiler would refuse, or
would take minutes over, shows here and costs no chip time. One file, the
topology inside a fixture (only the worker that is given this file loads
the TPU's library), skipped where no v5e can be described."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from spark_tpu.ops import grouping as G
from spark_tpu.ops import joining as J
from spark_tpu.ops import window as W

CAP = 8 << 20      # the slots of q47's `v1` flow (tpcds_sf10_window.dev2)


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, one_chip, *dtypes):
    shapes = [jax.ShapeDtypeStruct((CAP,), dt, sharding=one_chip)
              for dt in dtypes]
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sorts(text):
    """Operands (32-bit words: the compiler splits a 64-bit one in two) of
    each sort in a compiled module."""
    return sorted(len(re.findall(r"\b(?:[suf]\d+|pred)\[", ln.split(" sort(")[0]))
                  for ln in text.splitlines() if " sort(" in ln)


def _per_slot(text, op):
    """`op`s (gather, scatter) whose result has CAP slots."""
    return [ln for ln in text.splitlines()
            if re.search(rf"\[{CAP}\]\S* {op}\(", ln)]


def test_aggregate_by_scans_compiles_at_8mi(one_chip):
    """A GROUP BY's scan body at v1's capacity, at its narrowest (one key,
    one decimal sum: v1's own, with six keys, compiles for ten minutes
    here): two sorts and no gather or scatter of CAP elements. A sort costs
    the TPU compiler 15-35 s for each operand, so their number is pinned:
    slot, count, sum (2), key; and flag, key, iota, price (2), its
    validity's word."""
    def body(key, price, price_ok, mask):
        return G.group_aggregate([key], [None], [None], mask, ("sum",),
                                 [price], [price_ok], path="scan")

    text = _compiled(body, one_chip, jnp.int32, jnp.int64, jnp.bool_,
                     jnp.bool_)
    assert _sorts(text) == [5, 6], _sorts(text)
    assert not _per_slot(text, "gather") and not _per_slot(text, "scatter")


def test_window_by_scans_compiles_at_8mi(one_chip):
    """A window's partition total by scans and the way back by a sort on
    the permutation, at v1's capacity: no scatter of CAP elements, and the
    two sorts' operands pinned (perm, total (2), its validity; flags, key,
    iota)."""
    def body(key, total, total_ok, mask):
        lo = W.build_layout([key], [None], [], [], [], mask)
        out = W.w_agg_unbounded(lo, total, total_ok, "sum", path="scan")
        return W.scatter_back(lo, *out, path="scan")

    text = _compiled(body, one_chip, jnp.int32, jnp.int64, jnp.bool_,
                     jnp.bool_)
    assert _sorts(text) == [3, 4], _sorts(text)
    assert not _per_slot(text, "scatter")


def test_exact_index_and_its_span_compile_at_8mi(one_chip):
    """An int32 key's own index (`key_path`) and the span read off it
    (`observe_span`), at v1's capacity: the one sort of the build side
    (the widened key's two words and the row number), and no gather or
    scatter of CAP elements; the observation is elementwise reads and
    reductions of what that sort left."""
    def body(key, key_ok, mask):
        assert J.key_path([key], [key]) == "exact"
        index = J.build_index([key], [key_ok], mask, "exact")
        return index, J.observe_span(index)

    text = _compiled(body, one_chip, jnp.int32, jnp.bool_, jnp.bool_)
    assert _sorts(text) == [3], _sorts(text)
    assert not _per_slot(text, "gather") and not _per_slot(text, "scatter")


@pytest.mark.parametrize("key,gathers", [("hash", 5), ("exact", 3)])
def test_join_filled_by_position_compiles_at_8mi(one_chip, key, gathers):
    """The date join of q3, q7 and v1 (131 072 probe rows into 8 Mi slots)
    past its build sort, on the body `src_path` picks there: what a slot
    has of its probe row comes by scatters of 131 072 scalars and scans, so
    the only gathers of CAP elements are by the build row (the sorted
    side's permutation, the key, its validity, one build column with its
    validity byte), and nothing of CAP elements is sorted. On an exact
    index (`key_path`: the keys are int32) `_expand` is handed no keys,
    and the key and its validity are not gathered."""
    pcap = 131072
    assert J.src_path(pcap, CAP) == "fill"

    def body(sorted_hash, perm, bkey, bkey_ok, price, price_ok, pkey, year,
             lo, counts, pmask):
        assert J.key_path([bkey], [pkey]) == "exact"
        keys = ([bkey], [bkey_ok], [pkey], [None]) if key == "hash" \
            else ((), (), (), ())
        r = J._expand(J.BuildSide(sorted_hash, perm), *keys, pmask, CAP,
                      "inner", pcap, lo, counts)
        planes = J.take_planes([bkey_ok, price_ok],
                               lambda w: jnp.take(w, r.build_idx))
        return (J.take_probe(r, year), jnp.take(price, r.build_idx), planes,
                r.out_mask, r.needed)

    build = [jax.ShapeDtypeStruct((4 * CAP,), dt, sharding=one_chip)
             for dt in (jnp.int64, jnp.int32, jnp.int32, jnp.bool_,
                        jnp.int32, jnp.bool_)]
    probe = [jax.ShapeDtypeStruct((pcap,), dt, sharding=one_chip)
             for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.int32,
                        jnp.bool_)]
    text = jax.jit(body).lower(*build, *probe).compile().as_text()
    assert len(_per_slot(text, "gather")) == gathers, \
        _per_slot(text, "gather")
    assert len(_per_slot(text, "scatter")) <= 4
    assert not [ln for ln in text.splitlines()
                if " sort(" in ln and f"[{CAP}]" in ln.split(" sort(")[0]]
