"""Pallas MXU kernels vs numpy oracles, interpreted (this suite runs on
the CPU; chip_smoke-time compilation on the chip is recorded in
CHANGES.md, PR 21 — see ops/pallas_kernels.py)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from spark_tpu.ops.pallas_kernels import (  # noqa: E402
    dense_group_sum_f32, partition_histogram,
)


def test_partition_histogram_exact():
    rng = np.random.default_rng(0)
    for cap, parts in [(100, 3), (5000, 37), (8192, 128), (3000, 200)]:
        pids = rng.integers(0, parts, cap)
        mask = rng.random(cap) < 0.8
        got = np.asarray(partition_histogram(
            jnp.asarray(pids, jnp.int32), jnp.asarray(mask), parts,
            interpret=True))
        exp = np.bincount(pids[mask], minlength=parts)
        assert (got == exp).all()


def test_partition_histogram_all_dead_rows():
    pids = jnp.zeros(64, jnp.int32)
    mask = jnp.zeros(64, bool)
    got = np.asarray(partition_histogram(pids, mask, 4, interpret=True))
    assert (got == 0).all()


def test_dense_group_sum_matches_scatter():
    rng = np.random.default_rng(1)
    cap, groups = 4096, 300
    keys = rng.integers(0, groups, cap)
    vals = rng.random(cap).astype(np.float32)
    mask = rng.random(cap) < 0.9
    got = np.asarray(dense_group_sum_f32(
        jnp.asarray(keys, jnp.int32), jnp.asarray(vals),
        jnp.asarray(mask), groups, interpret=True))
    exp = np.zeros(groups, np.float64)
    np.add.at(exp, keys[mask], vals[mask])
    assert np.abs(got - exp).max() < 1e-3


def test_dense_group_sum_non_multiple_block():
    # capacity not a multiple of the block: padding rows must not leak
    keys = jnp.asarray(np.arange(10) % 3, jnp.int32)
    vals = jnp.ones(10, jnp.float32)
    mask = jnp.ones(10, bool)
    got = np.asarray(dense_group_sum_f32(keys, vals, mask, 3,
                                         interpret=True))
    assert got.tolist() == [4.0, 3.0, 3.0]


def test_bucket_count_is_bounded_by_vmem():
    from spark_tpu.ops.pallas_kernels import _MAX_BUCKETS

    with pytest.raises(ValueError, match="VMEM"):
        partition_histogram(jnp.zeros(8, jnp.int32), jnp.ones(8, bool),
                            _MAX_BUCKETS + 1, interpret=True)
