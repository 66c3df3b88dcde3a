"""Mesh whole-query compilation: the ENTIRE sharded plan as ONE
shard_map program per execution step.

The single-device whole tier (physical/whole_query.py) collapses a plan
into one jitted program but runs it on one device — exchanges become
in-program gathers and the full working set must fit one HBM. This tier
keeps the plan's PARALLELISM: leaf planes stage row-sharded over the
device mesh (the same [P * rows_per_shard] base layout the per-stage
mesh path persists for quota retries), hash exchanges lower to
`lax.all_to_all` on the sharded planes (parallel/mesh_fusion's
_exchange_tail — identical bucketing, quota and overflow contract), and
every reduce-side consumer (partial/final aggregate, join build+probe,
sort/limit) folds in BEHIND the collective, on the sharded layouts.
Filter/project pipelines ride trace_pipeline unchanged: inside
shard_map, a per-shard [cap] plane is indistinguishable from a
single-device one.

Retry contract — ONE verdict per dispatch: every per-join `needed`
scalar, per-exchange psum'd overflow count and dense-probe guard ride
the single dispatch's outputs; the host checks them ONCE and re-enters
the loop with bumped join caps / doubled quotas / the dense variant
disabled — all verdicts of a round applied together, so a round is
never wasted re-discovering one of them. Quota/capacity retries restage
NOTHING: the first retry stages the leaf planes once as UNDONATED base
planes and every later round (including gang retries after a runtime
fault, once _planes_alive proves them resident) reuses them.

Admission is decided at plan time (whole_query.choose_tier +
supported_mesh_whole): leaf stats known, per-shard HBM estimate within
budget, one power-of-two hash-partition count with enough devices.
Inadmissible plans fall back tier-by-tier with the reason stashed on
the decision. The plan analyzer mirrors the whole loop
(analysis/plan_lint._mesh_whole): {mesh_whole: attempts} predicts the
dispatch count exactly, fusion on and off.
"""

from __future__ import annotations

from ..columnar.batch import Column, ColumnarBatch
from ..errors import ExecutionError
from ..types import BooleanType, dict_encoded
from ..utils.device_memo import device_read
from .compile import (
    GLOBAL_KERNEL_CACHE, module_name, named_jit, note_program,
)
from .operators import attrs_schema
from .whole_query import (
    _MAX_PROGRAM_RETRIES, _Collect, _Lowered, _MCol, _ProgramBuilder,
    WholeQueryExec, _apply_needed, _jnp, _late_take, _record_spans,
    _seeded_caps, _setop_args, is_runtime_fault,
)

__all__ = ["MeshWholeQueryExec"]

# one fresh attempt after a runtime gang fault, same budget as the
# per-stage mesh gang loop (parallel/mesh_exchange.py)
_MAX_GANG_RETRIES = 1


def _np():
    import numpy as np

    return np


# ---------------------------------------------------------------------------
# the sharded program builder
# ---------------------------------------------------------------------------

class _MeshProgramBuilder(_ProgramBuilder):
    """_ProgramBuilder over a device mesh.

    Every intermediate flow carries a FORM: ("shard", part_ids) — planes
    are per-shard [cap] views of a row-sharded [P*cap] array, hash-
    partitioned by the attribute ids in part_ids (() = arbitrary row
    split) — or ("rep",) — every shard holds the identical full array.
    The form decides each operator's lowering: a final aggregate over
    flows co-partitioned on its grouping keys folds in per shard behind
    the collective; anything needing global view gathers first
    (all_gather, tiled) and proceeds exactly like the single-device
    builder. The inherited lowering helpers (_lower_pipe, _lower_agg,
    _join_tail, _lower_sort, ...) are reused verbatim — inside shard_map
    they see ordinary [cap] arrays."""

    def __init__(self, ctx, join_caps, spans_seed=None, dense_off=None,
                 expand_on=None, *, mesh, axis, num_shards, quotas,
                 mesh_seed, leaf_cache, use_base, gang):
        super().__init__(ctx, join_caps, spans_seed=spans_seed,
                         dense_off=dense_off, expand_on=expand_on)
        self.mesh = mesh
        self.axis = axis
        self.P = num_shards
        self._quotas = quotas          # xid -> live quota (doubles on retry)
        self._mesh_seed = mesh_seed    # warm-start manifest mesh quotas
        self.leaf_cache = leaf_cache   # id(leaf) -> host staging record
        self.use_base = use_base       # True after the first retry round
        self.gang = gang               # True on a gang-fault rebuild
        self._roles: list[str] = []    # per arg: "don" | "rows" | "rep"
        self._forms: dict[int, tuple] = {}
        self._partial_merged: set[int] = set()
        self._x_seq = 0
        self.x_ids: list[int] = []     # all_to_all exchanges, emit order
        self.quota_keys: dict[int, str] = {}   # xid -> manifest slot
        self.staged: list = []         # this attempt's donated planes
        from ..parallel.mesh_fusion import MeshSpecLayout

        self._layout = MeshSpecLayout(axis)
        self._rep_sharding = self._layout.replicated_sharding(mesh)
        self._row_sharding = self._layout.row_sharding(mesh)

    # -- argument plumbing -------------------------------------------------
    def arg(self, arr) -> int:
        """Replicated program input (aux tables, luts, rank tables):
        committed to the mesh so jit never guesses a placement."""
        import jax

        self.args.append(jax.device_put(arr, self._rep_sharding))
        self._roles.append("rep")
        return len(self.args) - 1

    def shard_arg(self, arr, donated: bool) -> int:
        self.args.append(arr)
        self._roles.append("don" if donated else "rows")
        return len(self.args) - 1

    def split_args(self):
        """(donated, kept) argument lists, program-call order."""
        don = [a for a, r in zip(self.args, self._roles) if r == "don"]
        keep = [a for a, r in zip(self.args, self._roles) if r != "don"]
        return don, keep

    def arg_slots(self):
        """Per merged arg position: (bucket, index-in-bucket), bucket 0 =
        donated, 1 = kept — local_fn reassembles the flat args list the
        emit closures index."""
        slots = []
        nd = nk = 0
        for r in self._roles:
            if r == "don":
                slots.append((0, nd))
                nd += 1
            else:
                slots.append((1, nk))
                nk += 1
        return slots

    def spec_lists(self):
        rows, rep = self._layout.rows(), self._layout.replicated()
        don = [rows for r in self._roles if r == "don"]
        keep = [rows if r == "rows" else rep
                for r in self._roles if r != "don"]
        return don, keep

    # -- form bookkeeping --------------------------------------------------
    def _form(self, low: _Lowered) -> tuple:
        return self._forms[id(low)]

    def _set_form(self, low: _Lowered, form: tuple) -> None:
        self._forms[id(low)] = form

    def _to_rep(self, low: _Lowered) -> _Lowered:
        """Gather a sharded flow to the replicated form (tiled
        all_gather per plane): cap multiplies by P, row order is
        shard-major — the same concatenation order the host gather of
        the per-stage path produces."""
        if self._form(low)[0] != "shard":
            return low
        axis = self.axis
        cap = low.cap * self.P
        self.key.append(("torep",))
        # a deferred column's row numbers are the shard's own: gathered
        # before the rows leave it
        cols = range(len(low.metas))
        self._late_read(low, cols)

        def emit(args, needed, _low=low):
            from jax import lax

            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, cols)

            def g(x):
                return None if x is None else lax.all_gather(
                    x, axis, axis=0, tiled=True)

            return [g(x) for x in d], [g(x) for x in v], g(m)

        out = _Lowered(list(low.metas), cap, emit)
        self._set_form(out, ("rep",))
        return out

    # -- dispatch ----------------------------------------------------------
    def _scoped(self, low: _Lowered, label: str) -> _Lowered:
        out = super()._scoped(low, label)
        self._set_form(out, self._form(low))
        return out

    def _lower_node(self, node) -> _Lowered:
        from . import operators as O
        from .exchange import BroadcastExchangeExec, ShuffleExchangeExec
        from .fusion import FusedAggregateExec, FusedLimitExec

        if isinstance(node, (O.LocalTableScanExec, O.RangeExec,
                             O.ScanExec)):
            return self._lower_mesh_leaf(node)
        if isinstance(node, FusedAggregateExec):
            self._register_merge(node)
            low = self.lower(node.child)
            f = self._form(low)
            low2 = self._lower_pipe(node.filters, node.pipe_outputs,
                                    node.child.output, node.pipe_attrs,
                                    low)
            self._set_form(low2, f)
            self._member(node)
            return self._lower_mesh_agg(node, node.pipe_attrs, low2)
        if isinstance(node, O.HashAggregateExec):
            self._register_merge(node)
            low = self.lower(node.child)
            self._member(node)
            return self._lower_mesh_agg(node, node.child.output, low)
        if isinstance(node, FusedLimitExec):
            low = self._to_rep(self.lower(node.child))
            low = self._lower_pipe(node.filters, node.pipe_outputs,
                                   node.child.output, node.pipe_attrs,
                                   low)
            self._member(node)
            out = self._lower_limit(node, low)
            self._set_form(out, ("rep",))
            return out
        if isinstance(node, O.LimitExec):
            low = self._to_rep(self.lower(node.child))
            self._member(node)
            out = self._lower_limit(node, low)
            self._set_form(out, ("rep",))
            return out
        if isinstance(node, O.SortExec):
            # a global sort needs the global row set; the gathered flow
            # then rides the inherited single-device lowering
            low = self._to_rep(self.lower(node.child))
            self._member(node)
            out = self._lower_sort(node, low)
            self._set_form(out, ("rep",))
            return out
        if isinstance(node, O.HashJoinExec):
            self._member(node)
            return self._lower_mesh_join(node)
        if isinstance(node, O.ComputeExec):
            low = self.lower(node.child)
            f = self._form(low)
            self._member(node)
            from ..expr.expressions import Alias

            attrs = [o.to_attribute() if isinstance(o, Alias) else o
                     for o in node.outputs]
            out = self._lower_pipe(node.filters, node.outputs,
                                   node.child.output, attrs, low)
            self._set_form(out, f)
            return out
        if isinstance(node, ShuffleExchangeExec):
            return self._lower_mesh_exchange(node)
        if isinstance(node, BroadcastExchangeExec):
            low = self._to_rep(self.lower(node.child))
            self._member(node, "BroadcastExchange -> replicated gather")
            return low
        if isinstance(node, O.CoalescePartitionsExec):
            return self.lower(node.child)
        if isinstance(node, O.UnionExec):
            lows = [self._to_rep(self.lower(c))
                    for c in node.children_plans]
            self._member(node)
            out = self._lower_union(node, lows)
            self._set_form(out, ("rep",))
            return out
        raise ExecutionError(            # admission guarantees this
            f"mesh-whole lowering missing for {type(node).__name__}")

    # -- leaves ------------------------------------------------------------
    def _stage_leaf_host(self, node) -> dict:
        """Host staging, ONCE per execute (cached across retry rounds):
        execute the leaf, flatten its batches to [total_cap] planes with
        strings recoded against a merged global dictionary (codes must
        be comparable across shards after the collective — the mesh
        encoding carry-over), and fix the row split."""
        np = _np()
        parts = node.execute(self.ctx)
        batches = [b for p in parts for b in p]
        schema = attrs_schema(node.output)
        from ..columnar.batch import EMPTY_DICT
        from ..parallel.mesh_exchange import _stage_payloads

        staged = _stage_payloads(batches, schema)
        if staged is None:
            # empty leaf: one all-dead slot per shard keeps every plane
            # shape valid without a special empty program variant
            datas = [np.zeros(1, np.dtype(f.dataType.device_dtype))
                     for f in schema.fields]
            valids = [None] * len(schema.fields)
            mask = np.zeros(1, bool)
            dicts = [EMPTY_DICT if dict_encoded(f.dataType) else None
                     for f in schema.fields]
            total_cap = 1
        else:
            datas, valids, mask, dicts, total_cap = staged
        rps = max(-(-total_cap // self.P), 1)
        return {"datas": datas, "valids": valids, "mask": mask,
                "dicts": dicts, "total_cap": total_cap, "rps": rps,
                "fields": schema.fields, "base": None,
                "base_ledger": None}

    def _leaf_planes(self, rec):
        """Device planes for one leaf, honoring the retry/donation
        contract: attempt 0 stages donated planes (released in-place by
        the dispatch); the first retry stages UNDONATED base planes once
        and every later round — quota/capacity retries AND gang retries
        — reuses them after _planes_alive proves them resident, so
        retries never re-cross the host."""
        import jax

        from ..parallel.mesh_exchange import _pad_base, _planes_alive
        from ..parallel.mesh_fusion import StagedBuffers

        P, rps = self.P, rec["rps"]

        def put(a):
            return None if a is None else jax.device_put(
                _pad_base(a, P, rps), self._row_sharding)

        if not self.use_base:
            datas = [put(a) for a in rec["datas"]]
            valids = [put(v) for v in rec["valids"]]
            mask = put(rec["mask"])
            self.staged.extend(
                [x for x in datas + valids + [mask] if x is not None])
            return datas, valids, mask
        base = rec["base"]
        if base is not None and _planes_alive(
                list(base[0]) + [v for v in base[1]] + [base[2]]):
            if self.gang:
                self.ctx.metrics.add("whole_query.mesh_gang_base_reused")
            else:
                self.ctx.metrics.add(
                    "whole_query.mesh_retry_restage_saved")
            return base
        if base is not None and rec["base_ledger"] is not None:
            rec["base_ledger"].release_all()
        datas = [put(a) for a in rec["datas"]]
        valids = [put(v) for v in rec["valids"]]
        mask = put(rec["mask"])
        rec["base"] = (datas, valids, mask)
        rec["base_ledger"] = StagedBuffers(
            [x for x in datas + valids + [mask] if x is not None])
        return datas, valids, mask

    def _lower_mesh_leaf(self, node) -> _Lowered:
        nid = id(node)
        rec = self.leaf_cache.get(nid)
        if rec is None:
            rec = self.leaf_cache[nid] = self._stage_leaf_host(node)
        self._member(node)
        datas, valids, mask = self._leaf_planes(rec)
        donated = not self.use_base
        arg_of = []
        metas = []
        for i, f in enumerate(rec["fields"]):
            di = self.shard_arg(datas[i], donated)
            vi = None if valids[i] is None \
                else self.shard_arg(valids[i], donated)
            arg_of.append((di, vi))
            metas.append(_MCol(f.dataType, valids[i] is not None,
                               rec["dicts"][i]))
        mi = self.shard_arg(mask, donated)
        rps = rec["rps"]
        self.key.append(("mleaf", rps, tuple(
            (str(d.dtype), v is not None)
            for d, v in zip(datas, valids))))

        def emit(args, needed):
            ds = [args[di] for di, _vi in arg_of]
            vs = [None if vi is None else args[vi] for _di, vi in arg_of]
            return ds, vs, args[mi]

        out = _Lowered(metas, rps, emit)
        self._set_form(out, ("shard", ()))
        return out

    # -- exchanges ---------------------------------------------------------
    def _exchange_keys(self, node, low: _Lowered):
        """(positions, luts, bools) of the hash-partitioning keys in the
        exchange's output flow. Dict-encoded keys hash through their
        merged dictionary's hash lut — the same lanes the host partition
        split and the per-stage mesh path hash, so row placement is
        bit-identical across all three (and the analyzer's host mirror)."""
        pos = {a.expr_id: i for i, a in enumerate(node.output)}
        kidx = tuple(pos[e.expr_id] for e in node.partitioning.exprs)
        luts = [self._eq_lut(low.metas[i]) for i in kidx]
        bools = tuple(isinstance(low.metas[i].dtype, BooleanType)
                      for i in kidx)
        return kidx, luts, bools

    def _quota_for(self, xid: int, node, in_cap: int, kidx) -> int:
        """This exchange's live quota: geometry default, raised by the
        warm-start manifest seed on first use, doubled by the retry loop
        (persisted in self._quotas across rebuilds)."""
        from ..exec.persist_cache import mesh_quota_key
        from ..parallel.mesh_fusion import mesh_stage_geometry

        sig = "|".join(str(a.dtype) for a in node.output)
        mkey = mesh_quota_key("w", self.P, in_cap,
                              f"x{xid}:k{kidx}:s{sig}")
        self.quota_keys[xid] = mkey
        q = self._quotas.get(xid)
        if q is None:
            q = mesh_stage_geometry(self.P * in_cap, self.P)[2]
            seed = (self._mesh_seed or {}).get(mkey)
            if seed and int(seed) > q:
                q = int(seed)
                self.ctx.metrics.add("cache.mesh_quota_seeded")
            self._quotas[xid] = q
        return q

    def _lower_mesh_exchange(self, node) -> _Lowered:
        from .partitioning import HashPartitioning

        low = self.lower(node.child)
        form = self._form(low)
        if node.pipe_fusion is not None:
            filters, outputs = node.pipe_fusion
            low2 = self._lower_pipe(filters, outputs, node.child.output,
                                    node.pipe_attrs, low)
            self._set_form(low2, form)
            low = low2
        p = node.partitioning
        if isinstance(p, HashPartitioning):
            key_ids = tuple(e.expr_id for e in p.exprs)
            if form[0] == "shard":
                return self._exchange_all_to_all(node, low, key_ids)
            return self._exchange_local_filter(node, low, key_ids)
        # range/single/round-robin: the downstream consumer re-groups,
        # re-sorts or reduces globally anyway — gather to the replicated
        # flow (the single-device whole tier's in-program gather)
        self._member(node,
                     f"Exchange[{type(p).__name__}] -> in-program gather")
        self.key.append(("xgather",))
        return self._to_rep(low)

    def _exchange_all_to_all(self, node, low: _Lowered,
                             key_ids) -> _Lowered:
        """Hash exchange on a sharded flow: the collective. Same
        _exchange_tail leg as the per-stage mesh path — bucket live rows
        by destination into [P, quota] blocks, all_to_all every plane,
        psum the overflow — but the received planes stay IN the program
        for the reduce-side consumer instead of crossing to host."""
        jnp = _jnp()
        xid = self._x_seq
        self._x_seq += 1
        kidx, luts, bools = self._exchange_keys(node, low)
        quota = self._quota_for(xid, node, low.cap, kidx)
        P, axis = self.P, self.axis
        out_cap = P * quota
        self.key.append(("mxchg", xid, quota, kidx, bools,
                         tuple(x[1] for x in luts)))
        self._member(
            node, "Exchange[HashPartitioning] -> in-program all_to_all")
        self.x_ids.append(xid)
        cols = range(len(low.metas))
        self._late_read(low, cols)

        def emit(args, needed, _low=low):
            from ..ops.hashing import hash_columns, partition_ids
            from ..parallel.mesh_fusion import _exchange_tail

            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, cols)
            eqs, kvs = [], []
            for j, i in enumerate(kidx):
                kd = d[i]
                if luts[j][0] is not None:
                    lut = args[luts[j][0]]
                    kd = jnp.take(lut, jnp.clip(kd.astype(jnp.int32), 0,
                                                lut.shape[0] - 1))
                elif bools[j]:
                    kd = kd.astype(jnp.int32)
                eqs.append(kd)
                kvs.append(v[i])
            pids = partition_ids(hash_columns(eqs, kvs), P)
            n = len(d)
            planes = list(d) + list(v)
            outs, new_mask, _cnt, overflow, _st = _exchange_tail(
                planes, pids, m, P, quota, axis)
            needed.overflows.append(overflow)
            return outs[:n], outs[n:], new_mask

        out = _Lowered(list(low.metas), out_cap, emit)
        self._set_form(out, ("shard", key_ids))
        return out

    def _exchange_local_filter(self, node, low: _Lowered,
                               key_ids) -> _Lowered:
        """Hash exchange on a REPLICATED flow: every shard already holds
        all rows — keep the rows whose partition id IS this shard. No
        collective, no quota, no overflow."""
        jnp = _jnp()
        kidx, luts, bools = self._exchange_keys(node, low)
        P, axis = self.P, self.axis
        self.key.append(("mxlocal", kidx, bools,
                         tuple(x[1] for x in luts)))
        self._member(
            node, "Exchange[HashPartitioning] -> in-program pid filter")
        cols = range(len(low.metas))
        self._late_read(low, cols)

        def emit(args, needed, _low=low):
            from jax import lax

            from ..ops.hashing import hash_columns, partition_ids

            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, cols)
            eqs, kvs = [], []
            for j, i in enumerate(kidx):
                kd = d[i]
                if luts[j][0] is not None:
                    lut = args[luts[j][0]]
                    kd = jnp.take(lut, jnp.clip(kd.astype(jnp.int32), 0,
                                                lut.shape[0] - 1))
                elif bools[j]:
                    kd = kd.astype(jnp.int32)
                eqs.append(kd)
                kvs.append(v[i])
            pids = partition_ids(hash_columns(eqs, kvs), P)
            m = m & (pids == lax.axis_index(axis))
            return d, v, m

        out = _Lowered(list(low.metas), low.cap, emit)
        self._set_form(out, ("shard", key_ids))
        return out

    # -- aggregates --------------------------------------------------------
    def _register_merge(self, node) -> None:
        """A final-mode aggregate marks the partial aggregate it merges
        (walking through exchange/coalesce wrappers): ONLY a merged
        partial may lower per-shard — the planner also emits partial-as-
        complete (single-partition collapse), and running THAT per shard
        would return P unmerged states as the answer."""
        from . import operators as O
        from .exchange import ShuffleExchangeExec

        if getattr(node, "mode", "") != "final":
            return
        c = node.child
        while isinstance(c, (ShuffleExchangeExec,
                             O.CoalescePartitionsExec)):
            c = c.child
        if isinstance(c, O.HashAggregateExec) \
                and getattr(c, "mode", "") == "partial":
            self._partial_merged.add(id(c))

    def _lower_mesh_agg(self, node, in_attrs, low: _Lowered) -> _Lowered:
        form = self._form(low)
        if form[0] == "shard":
            part_ids = form[1]
            grouping_ids = set(g.expr_id for g in node.grouping)
            co = bool(part_ids) and set(part_ids) <= grouping_ids
            if getattr(node, "mode", "") == "partial" \
                    and id(node) in self._partial_merged:
                # merged partial: per-shard states are exactly what the
                # downstream final merge expects
                out_part = part_ids if (node.grouping and co) else ()
            elif node.grouping and co:
                # co-partitioned on the grouping keys: every group's
                # rows sit on one shard — the per-shard aggregate IS the
                # global one
                out_part = part_ids
            else:
                low = self._to_rep(low)
                form = ("rep",)
                out_part = None
        out = self._lower_agg(node, in_attrs, low)
        self._set_form(out, ("shard", out_part)
                       if form[0] == "shard" else ("rep",))
        return out

    # -- joins -------------------------------------------------------------
    def _lower_mesh_join(self, node) -> _Lowered:
        probe = self.lower(node.left)
        pform = self._form(probe)
        if node.probe_fusion is not None:
            filters, outputs = node.probe_fusion
            probe2 = self._lower_pipe(filters, outputs,
                                      node.left.output,
                                      node.probe_attrs, probe)
            self._set_form(probe2, pform)
            probe = probe2
        build = self.lower(node.right)
        bform = self._form(build)
        lkeys = tuple(k.expr_id for k in node.left_keys)
        rkeys = tuple(k.expr_id for k in node.right_keys)
        if pform[0] == "shard":
            # per-shard probe. The build side joins in per shard when
            # CO-PARTITIONED (both sides hash-split by the join keys,
            # positionally: equal keys landed on equal shards) or
            # replicated (every shard probes the full table); an
            # arbitrarily-split build gathers first.
            co = (bform[0] == "shard" and len(lkeys) > 0
                  and pform[1] == lkeys and bform[1] == rkeys)
            if bform[0] == "shard" and not co:
                build = self._to_rep(build)
            out = self._join_tail(node, probe, build)
            self._set_form(out, ("shard", pform[1]))
            return out
        if bform[0] == "shard":
            build = self._to_rep(build)
        out = self._join_tail(node, probe, build)
        self._set_form(out, ("rep",))
        return out


# ---------------------------------------------------------------------------
# program compilation
# ---------------------------------------------------------------------------

def _build_mesh_program(b: _MeshProgramBuilder, root: _Lowered, key: tuple):
    """jit(shard_map(local program)), named from its cache key. The local function reassembles the
    flat args list from the (donated, kept) buckets, emits the whole
    lowered tree per shard, and centrally reduces every verdict scalar
    (max'd join `needed`s and guards, min/max'd spans; overflows are
    already psum'd) so the host reads ONE value per check after the
    single dispatch. Outputs are replicated (the root is gathered), so
    check_vma=False with P() out_specs is sound by construction."""
    from ..parallel import mesh_fusion as MF
    from jax import shard_map

    slots = b.arg_slots()
    don_specs, keep_specs = b.spec_lists()
    rep = b._layout.replicated()
    n_args = len(b.args)
    axis = b.axis
    valid_sig = tuple(m.valid for m in root.metas)
    njoin = b._join_seq
    nov = len(b.x_ids)
    nsp = len(b.span_jids)
    ng = len(b.guard_jids)
    nu = len(b.unsure_jids)

    def local_fn(don, keep):
        from jax import lax

        args = [None] * n_args
        for pos, (bk, j) in enumerate(slots):
            args[pos] = don[j] if bk == 0 else keep[j]
        jnp = _jnp()

        # not lax.pmax/pmin: these scalars are 64-bit, which the TPU
        # emulates, and it lowers only SUM all-reduces for emulated types
        # ("UNIMPLEMENTED: Supported lowering only of Sum all reduce",
        # v5e, PR 21) — gather the P scalars and reduce locally
        def allmax(x):
            return jnp.max(lax.all_gather(x, axis))

        def allmin(x):
            return jnp.min(lax.all_gather(x, axis))

        needed = _Collect()
        datas, valids, mask = root.emit(args, needed)
        needed_r = tuple(allmax(x) for x in needed)
        ovfs = tuple(needed.overflows)
        spans = tuple((allmin(lo), allmax(hi), allmax(dup))
                      for lo, hi, dup in needed.spans)
        guards = tuple(allmax(g) for g in needed.guards)
        unsure = tuple(allmax(u) for u in needed.unsure)
        return (datas, valids, mask, needed_r, ovfs, spans, guards, unsure)

    out_specs = ([rep] * len(valid_sig),
                 [rep if hv else None for hv in valid_sig],
                 rep,
                 (rep,) * njoin, (rep,) * nov,
                 ((rep, rep, rep),) * nsp, (rep,) * ng, (rep,) * nu)

    def sharded(don, keep):
        f = shard_map(local_fn, mesh=b.mesh,
                      in_specs=(don_specs, keep_specs),
                      out_specs=out_specs, check_vma=False)
        return f(don, keep)

    donate = MF.DONATE_DEFAULT and not b.use_base and len(don_specs) > 0
    return named_jit("mesh_whole", key, sharded, labels=b.scopes,
                     donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

class MeshWholeQueryExec(WholeQueryExec):
    """WholeQueryExec over the device mesh: ONE shard_map dispatch per
    retry round, exchanges as in-program collectives. Inherits the
    runtime-degradation contract (a runtime fault past the gang-retry
    budget falls back to the stage tier with the reason recorded) and
    the obs surface (fused_members attribution, degraded_inner)."""

    def graph_name(self) -> str:
        return "MeshWholeQueryExec"

    def simple_string(self):
        n = sum(1 for _ in self.plan.iter_nodes())
        P = self.decision.details.get("mesh_devices")
        return (f"WholeQuery[ops={n}, tier=mesh-whole, mesh={P}] "
                f"({self.decision.reason[:60]})")

    def _execute_whole(self, ctx) -> list:
        from ..config import DEVICE_MESH_AXIS
        from ..parallel.mesh_exchange import _get_mesh
        from ..parallel.mesh_fusion import (
            StagedBuffers, expected_donation_residue,
        )

        P = int(self.decision.details.get("mesh_devices") or 0)
        axis = str(ctx.conf.get(DEVICE_MESH_AXIS))
        mesh = _get_mesh(P, axis)
        span, sub = self._program_span(ctx, "mesh-whole")
        seed_rec = getattr(ctx, "persist_seed", None) or {}
        join_caps = _seeded_caps(ctx, seed_rec)
        spans_seed = seed_rec.get("join_spans") or None
        mesh_seed = seed_rec.get("mesh_quotas") or {}
        dense_off: set[int] = set()
        expand_on: set[int] = set()
        quotas: dict[int, int] = {}
        leaf_cache: dict[int, dict] = {}
        use_base = False
        gang = False
        gang_left = _MAX_GANG_RETRIES
        rounds = 0
        try:
            with span:
                while rounds < _MAX_PROGRAM_RETRIES:
                    with sub("whole_query.attempt",
                             lambda: self._attempt_args(rounds,
                                                        join_caps)) as att:
                        with sub("whole_query.lower"):
                            b = _MeshProgramBuilder(
                                ctx, join_caps, spans_seed=spans_seed,
                                dense_off=dense_off, expand_on=expand_on,
                                mesh=mesh, axis=axis,
                                num_shards=P, quotas=quotas,
                                mesh_seed=mesh_seed, leaf_cache=leaf_cache,
                                use_base=use_base, gang=gang)
                            gang = False
                            root = b.finish(
                                b._to_rep(b.lower(self.plan)), self.plan)
                            key = ("mesh_whole", axis, P,
                                   "base" if use_base else "don",
                                   tuple(b.key))
                        with sub("whole_query.launch") as launch:
                            kernel = GLOBAL_KERNEL_CACHE.get_or_build(
                                key, lambda _b=b, _r=root, _k=key:
                                _build_mesh_program(_b, _r, _k))
                            launch.set_args(
                                {"program": module_name(kernel)})
                            staged = StagedBuffers(b.staged)
                            don_args, keep_args = b.split_args()
                            note_program(kernel, (don_args, keep_args),
                                         b.members, b.scopes)
                            try:
                                with expected_donation_residue():
                                    (datas, valids, mask, needed, ovfs,
                                     spans, guards, unsure) = kernel(
                                         don_args, keep_args)
                            except Exception as e:
                                staged.release_all()
                                if not is_runtime_fault(e) \
                                        or gang_left <= 0:
                                    raise
                                # gang retry: ONE fresh attempt. Base
                                # planes are undonated by contract — the
                                # rebuilt program reuses them after
                                # _planes_alive proves it
                                # (whole_query.mesh_gang_base_reused)
                                gang_left -= 1
                                gang = True
                                use_base = True
                                ctx.metrics.add(
                                    "whole_query.mesh_gang_retries")
                                att.set_args(
                                    {"program": module_name(kernel),
                                     "discarded": True})
                                continue
                            staged.release_consumed()
                        # the round's ONE verdict: every capacity scalar
                        # of the single dispatch, read in one transfer
                        # and applied together
                        needed, ovfs, guards, spans, unsure = device_read(
                            "whole_query.verdict", needed, ovfs, guards,
                            spans, unsure)
                        bumped = _apply_needed(b, needed, unsure, join_caps,
                                               expand_on)
                        for xid, o in zip(b.x_ids, ovfs):
                            if int(o) > 0:
                                quotas[xid] = quotas[xid] * 2
                                ctx.metrics.add(
                                    "mesh_whole.quota_retries")
                                bumped = True
                        for jid, g in zip(b.guard_jids, guards):
                            if int(g):
                                dense_off.add(jid)
                                ctx.metrics.add(
                                    "whole_query.dense_guard_retries")
                                bumped = True
                        att.set_args({"program": module_name(kernel),
                                      "discarded": bumped,
                                      **_setop_args(b)})
                    if bumped:
                        rounds += 1
                        use_base = True
                        continue
                    if rounds:
                        ctx.metrics.add(
                            "whole_query.capacity_retries", rounds)
                    ctx.metrics.add("whole_query.dispatches", rounds + 1)
                    ctx.metrics.add("mesh_whole.dispatches", rounds + 1)
                    if join_caps:
                        ctx.persist_join_caps = list(join_caps)
                    if b.quota_keys:
                        prior = getattr(ctx, "persist_mesh_quotas",
                                        None) or {}
                        ctx.persist_mesh_quotas = {
                            **prior,
                            **{mk: int(quotas[x])  # tpulint: ignore[host-sync]
                               for x, mk in b.quota_keys.items()}}
                    if b.dense_joins:
                        ctx.metrics.add("whole_query.dense_probe",
                                        len(b.dense_joins))
                    _record_spans(ctx, b, spans, len(join_caps))
                    schema = attrs_schema(self.output)
                    cols = [Column(f.dataType, d, v,
                                   m.sdict if dict_encoded(f.dataType)
                                   else None)
                            for f, d, v, m in
                            zip(schema.fields, datas, valids, root.metas)]
                    batch = ColumnarBatch(schema, cols, mask,
                                          num_rows=None)
                    return [[batch]]
                raise ExecutionError(
                    "mesh whole-query program exceeded its retry budget "
                    f"({_MAX_PROGRAM_RETRIES}) — report this plan")
        finally:
            for rec in leaf_cache.values():
                bl = rec.get("base_ledger")
                if bl is not None:
                    bl.release_all()
