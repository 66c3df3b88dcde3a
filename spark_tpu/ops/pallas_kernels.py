"""Pallas TPU kernels for the engine's hot scatter-shaped ops.

XLA fuses elementwise work well, but data-dependent scatter (histogram,
dense-key group-by) lowers to serialized HBM scatters on TPU. These
kernels recast scatter as ONE-HOT MATMUL on the MXU: each grid step loads
a row block into VMEM, builds `onehot[buckets, block]`, and accumulates
`values @ onehot.T` into a VMEM scratch that lives across the sequential
grid — one HBM write at the end. (Reference analog: the vectorized hash
map of AggregateBenchmark / the shuffle partition histogram in
sqlx/shuffle/ShuffleExchangeExec; rebuilt here for the MXU instead of
per-core hash tables.)

`interpret` is the caller's to say (tests on the CPU pass True; nothing is
inferred from the backend). Counts and blockwise partial sums stay exact
in float32 (≤ 2^24 per bucket); int64-exact sums keep using the XLA
scatter path (see ops/grouping.py).
"""

from __future__ import annotations

import functools

_LANES = 128
_SUBLANES = 8
# the one-hot tile is buckets × block × 4 bytes of VMEM: the row block
# shrinks as buckets grow, and past _MAX_BUCKETS even the narrowest block
# does not fit beside the double-buffered inputs
_ONEHOT_BYTES = 2 << 20
_MAX_BUCKETS = _ONEHOT_BYTES // (4 * _LANES)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.lru_cache(maxsize=64)
def _bucket_sum_fn(rows: int, buckets: int, block: int, interpret: bool):
    """jit(keys[1, rows] int32, vals[1, rows] f32) -> f32[buckets]:
    vals summed by key."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = rows // block

    def kernel(key_ref, val_ref, out_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # one-hot laid out [buckets, block]: the keys broadcast along
        # sublanes, so there is no in-kernel relayout
        onehot = (lax.broadcasted_iota(jnp.int32, (buckets, block), 0)
                  == key_ref[...]).astype(jnp.float32)
        # vals @ onehot.T on the MXU, M padded to one sublane tile
        acc_ref[...] += lax.dot_general(
            jnp.broadcast_to(val_ref[...], (_SUBLANES, block)), onehot,
            (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

        @pl.when(i == grid - 1)
        def _flush():
            out_ref[...] = acc_ref[...]

    def build(keys2, vals2):
        # the engine runs with jax_enable_x64; Mosaic rejects the int64
        # block indices a Python `0` would become under it
        with jax.enable_x64(False):
            out = pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[pl.BlockSpec((1, block), lambda i: (0, i)),
                          pl.BlockSpec((1, block), lambda i: (0, i))],
                out_specs=pl.BlockSpec((_SUBLANES, buckets),
                                       lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((_SUBLANES, buckets),
                                               jnp.float32),
                scratch_shapes=[pltpu.VMEM((_SUBLANES, buckets),
                                           jnp.float32)],
                interpret=interpret,
            )(keys2, vals2)
        return out[0]

    return jax.jit(build)


def _bucket_sum(keys, vals, num_buckets: int, block: int, interpret: bool):
    """Pad to whole blocks (padding rows carry value 0) and run the
    kernel; returns f32[num_buckets]."""
    import jax.numpy as jnp

    cap = int(keys.shape[0])
    buckets = _round_up(max(num_buckets, 1), _LANES)
    if buckets > _MAX_BUCKETS:
        raise ValueError(
            f"{num_buckets} buckets: the one-hot tile would not fit VMEM "
            f"(limit {_MAX_BUCKETS}); use the XLA scatter path")
    fit = (_ONEHOT_BYTES // (4 * buckets)) // _LANES * _LANES
    block = max(_LANES, min(_round_up(block, _LANES), fit,
                            _round_up(cap, _LANES)))
    rows = _round_up(cap, block)
    k2 = jnp.zeros((rows,), jnp.int32).at[:cap].set(
        jnp.clip(keys.astype(jnp.int32), 0, buckets - 1))
    v2 = jnp.zeros((rows,), jnp.float32).at[:cap].set(vals)
    out = _bucket_sum_fn(rows, buckets, block, interpret)(
        k2.reshape(1, rows), v2.reshape(1, rows))
    return out[:num_buckets]


def partition_histogram(pids, mask, num_partitions: int, *,
                        interpret: bool, block: int = 1024):
    """Exact per-partition live-row counts: int32 pids[cap] + bool
    mask[cap] → int32[num_partitions]. One MXU matmul per block."""
    import jax.numpy as jnp

    counts = _bucket_sum(pids, mask.astype(jnp.float32), num_partitions,
                         block, interpret)
    return counts.astype(jnp.int32)


def dense_group_sum_f32(keys, values, mask, num_groups: int, *,
                        interpret: bool, block: int = 1024):
    """Grouped float sum over DENSE int keys in [0, num_groups):
    the MXU one-hot path of the dense-range aggregation fast path
    (float32 accumulation — int64-exact sums stay on the XLA scatter)."""
    import jax.numpy as jnp

    vals = jnp.where(mask, values.astype(jnp.float32), 0.0)
    return _bucket_sum(keys, vals, num_groups, block, interpret)
