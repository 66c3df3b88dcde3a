"""Mesh-native SPMD stage fusion: one sharded dispatch per stage per step.

Role of the reference's whole shuffle stage — map-side pipeline, partition
writer, block transfer, reduce-side read (sqlx/exchange/
ShuffleExchangeExec.scala + the SortShuffleManager data plane) — compiled
as ONE XLA program over a jax.sharding.Mesh: the traced filter/project
pipeline (physical/compile.trace_pipeline), the partition-id computation,
the per-shard bucket-by-destination, and the `lax.all_to_all` over the
ICI all run under a single `shard_map`, so a shuffle stage costs exactly
one dispatch per step regardless of how many batches staged into it
(JAMPI in PAPERS.md: barrier-mode ICI collectives beat host-mediated
shuffle by an order of magnitude; this is ROADMAP direction 1).

Layout discipline (the SpecLayout pattern, SNIPPETS [2]): every operand
declares its canonical PartitionSpec once in `MeshSpecLayout` — row data
is sharded over the data axis, pipeline aux tables are replicated — and
staging `device_put`s against those specs BEFORE the jit call, so no
input is ever resharded implicitly and outputs stay shard-resident for
the reduce-side consumer (each reduce partition's batch wraps its
device's shard directly; the agg partial / join build feed reads it
without a host hop).

Buffer donation: the staged send buffers are dead the moment the program
consumes them, so they ride `donate_argnums` and XLA reuses their HBM
in-place for the all-to-all staging/outputs. Staging is deliberately
sized so each per-shard send plane equals the receive plane
(shard_cap == P * quota) — the donated input aliases its output
one-for-one instead of tripping XLA's "donated buffer not usable" path.
The HBM ledger (obs/resources.DeviceLedger) charges the staged buffers
explicitly and releases them at dispatch when donated (the arrays are
genuinely invalidated by the call) vs. after output registration when
not — the per-query watermark is the scoreboard for the donation win.

Static-shape discipline: each (src→dst) pair gets a fixed row `quota`;
the program psums an overflow count and the host retries with a doubled
quota — the same capacity-bucket contract as the join/aggregate kernels.
Per-partition live counts come back as a sharded [P] array computed
in-program, so building the reduce batches needs one host pull, not one
sync per partition.
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Sequence

import numpy as np

from ..columnar.batch import bucket_capacity

__all__ = ["MeshSpecLayout", "StagedBuffers", "build_fused_stage",
           "build_plain_stage", "expected_donation_residue",
           "mesh_stage_geometry"]

# Donation is the default; tests A/B the HBM watermark by flipping this
# module switch (the undonated program compiles under a distinct cache
# key). Not a SQLConf: there is no reason to run undonated in production.
DONATE_DEFAULT = True

@contextlib.contextmanager
def expected_donation_residue():
    """Suppress jax's 'donated buffers were not usable' warning for ONE
    mesh-stage dispatch: a donated plane whose dtype has no matching
    output (an input column the projection drops) cannot alias, which is
    expected here — the size-matched staging makes every surviving plane
    alias cleanly. Scoped per call site, never process-wide: that warning
    is the only signal a FUTURE donation site regressed its aliasing."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def mesh_stage_geometry(total_cap: int, num_out: int) -> tuple[int, int, int]:
    """(rows_per_shard, shard_cap, quota) for staging `total_cap` input
    slots across `num_out` shards.

    rows_per_shard — input slots assigned to each shard (row-block
    split of the concatenated batches, so every device gets data).
    quota — per-(src,dst) row budget of the first attempt: 2× the
    uniform share, the historical overflow headroom.
    shard_cap — per-shard staged capacity, padded to P*quota so the
    send planes are the SAME size as the receive planes and donation
    aliases in-place. The plan analyzer mirrors these formulas exactly
    (analysis/plan_lint.py mesh model)."""
    rows_per_shard = max(-(-total_cap // num_out), 1)
    base = bucket_capacity(max(rows_per_shard, 64))
    quota = max(16, 2 * base // num_out)
    return rows_per_shard, num_out * quota, quota


# ---------------------------------------------------------------------------
# canonical operand layouts (the SpecLayout pattern)
# ---------------------------------------------------------------------------

class MeshSpecLayout:
    """Canonical PartitionSpecs per operand role for a mesh stage.

    One authority for how every array of the stage program is laid out
    over the mesh: staging places inputs against these specs and the
    shard_map in/out_specs are derived from the same methods, so a batch
    flows shard-resident between stages with no implicit resharding."""

    def __init__(self, axis: str = "data"):
        from jax.sharding import PartitionSpec as P

        self.axis = axis
        self._P = P

    def rows(self):
        """Row-sharded planes: column data, validity, row mask, keys."""
        return self._P(self.axis)

    def replicated(self):
        """Pipeline aux tables (dictionaries' luts) and scalar operands:
        every shard reads the full array."""
        return self._P()

    def row_sharding(self, mesh):
        from jax.sharding import NamedSharding

        return NamedSharding(mesh, self.rows())

    def replicated_sharding(self, mesh):
        from jax.sharding import NamedSharding

        return NamedSharding(mesh, self.replicated())


# ---------------------------------------------------------------------------
# HBM ledger bookkeeping for staged send buffers
# ---------------------------------------------------------------------------

class StagedBuffers:
    """Explicit ledger ownership of one attempt's staged device arrays.

    `release_consumed()` drops the charge of every array the dispatch
    invalidated (donation) the moment it returns — the buffers are
    genuinely gone, and the per-query watermark records the in-place
    reuse. Undonated arrays stay charged until `release_all()` (or GC of
    this holder), which runs after the reduce-side output batches have
    registered — the honest input+output overlap."""

    def __init__(self, arrays: Sequence):
        from ..obs.resources import GLOBAL_LEDGER, ledger_enabled

        self._ledger = GLOBAL_LEDGER if ledger_enabled() else None
        self._entries = []
        if self._ledger is not None:
            for a in arrays:
                if a is None or not hasattr(a, "dtype"):
                    continue
                token = self._ledger.charge_arrays([a])
                if token:
                    self._entries.append((a, token))

    def release_consumed(self) -> None:
        if self._ledger is None:
            return
        kept = []
        for a, token in self._entries:
            if getattr(a, "is_deleted", lambda: False)():
                self._ledger.release_arrays(token)
            else:
                kept.append((a, token))
        self._entries = kept

    def release_all(self) -> None:
        if self._ledger is None:
            return
        for _a, token in self._entries:
            self._ledger.release_arrays(token)
        self._entries = []

    def __del__(self):  # backstop — release_all is idempotent
        try:
            self.release_all()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# the SPMD stage programs
# ---------------------------------------------------------------------------

def _exchange_tail(arrays, pids, row_mask, num_out: int, quota: int,
                   axis: str, stat_spec: tuple = ()):
    """Shared post-pid leg of a stage program, per shard: bucket live
    rows by destination into [P, quota] blocks, all-to-all every plane,
    and report (received arrays, received mask, per-shard live count,
    global overflow, per-shard column stats). `arrays` entries may be
    None (absent validity planes) and pass through as None.

    `stat_spec` = ((data_idx, valid_idx | -1), ...) into `arrays`: for
    each listed integral column the program reduces the RECEIVED rows to
    (min, max, live count) per shard — one [n_stat, 3] int64 block per
    reduce partition, riding the dispatch's outputs. Post-exchange
    per-shard is exactly the union of the map-side per-(src,dst) stats
    MapStatus ships on the host path (same rows, same extrema), so the
    seeded dense-range span equals what the krange3 probe would have
    learned — the plan analyzer's dense-decision model stays exact. The
    empty case returns min/max sentinels with count 0; the host maps
    count 0 to the (0, 0, False) no-live-rows seed."""
    import jax.numpy as jnp
    from jax import lax

    from .collectives import _bucket_by_pid

    gather_idx, slot_valid, overflow = _bucket_by_pid(
        pids, row_mask, num_out, quota)

    def xchg(blocks):
        recv = lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0,
                              tiled=False)
        return recv.reshape(num_out * quota)

    outs = [None if a is None
            else xchg(jnp.take(a, gather_idx).reshape(num_out, quota))
            for a in arrays]
    new_mask = xchg(slot_valid)
    count = jnp.sum(new_mask.astype(jnp.int64)).reshape(1)
    total_overflow = lax.psum(overflow, axis)
    stats = None
    if stat_spec:
        big = jnp.int64(1) << 62
        rows = []
        for di, vi in stat_spec:
            d = outs[di].astype(jnp.int64)
            live = new_mask if vi < 0 else (new_mask & outs[vi])
            rows.append(jnp.stack([
                jnp.min(jnp.where(live, d, big)),
                jnp.max(jnp.where(live, d, -big)),
                jnp.sum(live.astype(jnp.int64))]))
        stats = jnp.stack(rows)  # [n_stat, 3] per shard
    return outs, new_mask, count, total_overflow, stats


def _embed_block(x, shard_cap: int):
    """Per-shard re-layout of a BASE plane block (quota-retry restaging):
    the shard's geometry-independent [base_rows] data block embeds at
    offset 0 of a zero-padded [shard_cap] send plane — the device-side
    equivalent of _pad_shards, so a retry never re-crosses the host."""
    import jax.numpy as jnp

    if x is None:
        return None
    out = jnp.zeros((shard_cap,), dtype=x.dtype)
    return out.at[: x.shape[0]].set(x)


def build_plain_stage(mesh, axis: str, quota: int, num_out: int,
                      n_keys: int, key_valid_sig: tuple,
                      n_payloads: int, donate: bool,
                      base_rows: "int | None" = None,
                      stat_spec: tuple = ()):
    """Jitted mesh stage for PRE-MATERIALIZED batches: pids from staged
    key arrays + all-to-all, payload/mask send buffers donated. Signature:
    f(key_eqs, key_valids, payloads, row_mask) ->
    (out_payloads, new_mask, counts[P], overflow[, stats]).

    With `base_rows`, inputs are PERSISTED base planes ([P*base_rows]
    row-sharded, geometry-independent): each shard embeds its block into
    the [shard_cap] send layout in-program, nothing is donated (the base
    planes survive for the next quota retry), and a retry pays only the
    recompile — not the host->device restage.

    With `stat_spec` (indices into the payloads list), the program also
    reduces each listed integral column's received rows to per-reduce-
    partition (min, max, live count) — the in-program column stats that
    seed the dense-range memo so reduce tiles stop krange3-probing
    (the MapStatus col-stats role on the ICI path)."""
    import jax

    from ..ops.hashing import hash_columns, partition_ids
    from jax import shard_map

    layout = MeshSpecLayout(axis)
    rows = layout.rows()
    shard_cap = num_out * quota

    def local_fn(key_eqs, key_valids, payloads, row_mask):
        if base_rows is not None:
            key_eqs = [_embed_block(k, shard_cap) for k in key_eqs]
            key_valids = [_embed_block(v, shard_cap) for v in key_valids]
            payloads = [_embed_block(p, shard_cap) for p in payloads]
            row_mask = _embed_block(row_mask, shard_cap)
        h = hash_columns(key_eqs, list(key_valids))
        pids = partition_ids(h, num_out)
        outs, new_mask, count, overflow, stats = _exchange_tail(
            payloads, pids, row_mask, num_out, quota, axis, stat_spec)
        if stat_spec:
            return outs, new_mask, count, overflow, stats
        return outs, new_mask, count, overflow

    def sharded(key_eqs, key_valids, payloads, row_mask):
        in_specs = (
            [rows] * n_keys,
            [None if not has else rows for has in key_valid_sig],
            [rows] * n_payloads,
            rows,
        )
        out_specs = ([rows] * n_payloads, rows, rows,
                     layout.replicated())
        if stat_spec:
            # stats are per-shard [n_stat, 3] blocks sharded over the
            # leading axis: the host pull reshapes to [P, n_stat, 3]
            out_specs = out_specs + (rows,)
        f = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
        return f(key_eqs, key_valids, payloads, row_mask)

    # built exclusively through GLOBAL_KERNEL_CACHE.get_or_build
    # (mesh_exchange) — launches ride the dispatch counters
    return jax.jit(sharded,  # tpulint: ignore[raw-jit]
                   donate_argnums=(2, 3) if donate and base_rows is None
                   else ())


def build_fused_stage(mesh, axis: str, shard_cap: int, quota: int,
                      num_out: int, seed: int, input_attrs,
                      filters, outputs, key_idx: tuple, key_bool: tuple,
                      out_valid_sig: tuple, donate: bool,
                      base_rows: "int | None" = None,
                      stat_spec: tuple = (), dict_pos: tuple = ()):
    """Jitted mesh stage for a FUSED shuffle stage: the filter/project
    pipeline traces per shard, partition ids derive from the traced key
    outputs, and the all-to-all ships the pipeline OUTPUT columns — the
    whole stage is one SPMD dispatch. Signature:
    f(datas, valids, row_mask, aux, kluts) ->
    (out_datas, out_valids, new_mask, counts[P], overflow[, stats]),
    where the input planes (datas/valids/row_mask) are the donated send
    buffers. `stat_spec` indexes the pipeline OUTPUT columns whose
    per-reduce-partition (min, max, live count) the program reduces
    in-program (see build_plain_stage). `dict_pos` lists the
    dictionary-encoded partition-key positions (pipe-output indices, in
    key_idx order) whose eq domain is a padded codes→value-hash lut
    shipped in `kluts` as a REPLICATED aux plane: the key hash computes
    over dictionary-independent value hashes inside the shard_map, so
    string-key exchanges fuse instead of materializing the pipeline
    before the collective."""
    import jax
    import jax.numpy as jnp

    from ..physical.compile import trace_pipeline
    from ..ops.hashing import hash_columns, partition_ids
    from jax import shard_map

    layout = MeshSpecLayout(axis)
    rows = layout.rows()
    rep = layout.replicated()
    n_in = len(input_attrs)
    lut_of = {i: j for j, i in enumerate(dict_pos)}

    def local_fn(datas, valids, row_mask, aux, kluts):
        if base_rows is not None:
            # quota-retry restaging: geometry-independent base planes
            # re-lay out to the attempt's [shard_cap] send layout
            # in-program (no host->device restage on retries)
            datas = [_embed_block(d, shard_cap) for d in datas]
            valids = [_embed_block(v, shard_cap) for v in valids]
            row_mask = _embed_block(row_mask, shard_cap)
        out_datas, out_valids, mask = trace_pipeline(
            input_attrs, filters, outputs, datas, valids, row_mask, aux,
            shard_cap)
        eqs = []
        for i, is_bool in zip(key_idx, key_bool):
            kd = out_datas[i]
            if is_bool:
                kd = kd.astype(jnp.int32)
            if i in lut_of:
                lut = kluts[lut_of[i]]
                kd = jnp.take(lut, jnp.clip(kd.astype(jnp.int32), 0,
                                            lut.shape[0] - 1))
            eqs.append(kd)
        kvs = [out_valids[i] for i in key_idx]
        pids = partition_ids(hash_columns(eqs, kvs, seed=seed), num_out)
        planes = list(out_datas) + list(out_valids)
        outs, new_mask, count, overflow, stats = _exchange_tail(
            planes, pids, mask, num_out, quota, axis, stat_spec)
        n = len(out_datas)
        if stat_spec:
            return outs[:n], outs[n:], new_mask, count, overflow, stats
        return outs[:n], outs[n:], new_mask, count, overflow

    def sharded(datas, valids, row_mask, aux, kluts):
        in_specs = (
            [rows] * n_in,
            [None if v is None else rows for v in valids],
            rows,
            [rep] * len(aux),
            [rep] * len(kluts),
        )
        out_specs = ([rows] * len(outputs),
                     [rows if has else None for has in out_valid_sig],
                     rows, rows, rep)
        if stat_spec:
            # per-shard [n_stat, 3] stat blocks, sharded on the leading
            # axis (host reshape → [P, n_stat, 3])
            out_specs = out_specs + (rows,)
        f = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
        return f(datas, valids, row_mask, aux, kluts)

    # built exclusively through GLOBAL_KERNEL_CACHE.get_or_build
    # (mesh_exchange) — launches ride the dispatch counters
    return jax.jit(sharded,  # tpulint: ignore[raw-jit]
                   donate_argnums=(0, 1, 2) if donate and base_rows is None
                   else ())
