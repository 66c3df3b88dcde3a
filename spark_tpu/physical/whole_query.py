"""Whole-QUERY compilation: collapse a slice-resident plan into ONE jitted
program (Flare's bet, ROADMAP direction 4).

Whole-stage fusion (PR 1) compiles each exchange-free chain into one
program per batch; exchange map-side fusion (PR 5) extends the program to
the shuffle write; mesh stage fusion (PR 8) makes a shuffle stage one
sharded dispatch. The host shuffle ROUND-TRIPS between stages remain: the
scheduler materializes every stage output, pulls grouped columns to host,
and re-ingests them for the next stage. When plan-time statistics show the
whole query's working set fits device-side, those round-trips are pure
overhead — the same tracing machinery that builds the per-stage programs
can trace EVERY stage into one `jax.jit` program per (plan structure,
input signatures, capacities):

  * exchanges lower to in-program GATHERS — on one device a hash/range/rr
    redistribution moves no data, it only re-partitions rows the next
    operator re-groups/re-sorts anyway, so the lowering concatenates the
    flow and lets the consumer's trace do the grouping;
  * aggregates always take the sorted-segment layout (static shapes: the
    output tile has the input capacity) — the value-dependent dense-range
    scatter stays a per-stage optimization, the whole-query program trades
    it for zero host hops;
  * joins run the sorted-probe kernel in-trace; output-capacity overflow
    comes back as a per-join `needed` scalar checked ONCE after the single
    dispatch (the same capacity-bucket retry contract as the per-batch
    kernels — a retry recompiles with the bumped bucket and re-dispatches
    the whole program);
  * a join hands its build side's columns on as its build row numbers
    (`_Late`): a later join or sort moves one index plane for all of them,
    and each column is gathered once, by the first operator that reads
    its values, at that operator's capacity — before a join whose output
    is larger than its input, so no column is gathered at a larger one;
  * intermediate stage outputs never materialize as ColumnarBatches —
    they are XLA values inside one program, resident in HBM only for the
    program's lifetime.

The `minRows` size gate generalizes into a three-tier cost model
(`spark.tpu.compile.tier` = auto | whole | stage | operator):

  whole     — one jitted program per query step (this module);
  stage     — one program per stage per batch (PR 1/5/8 fusion; the
              per-partition minRows runtime gate keeps routing undersized
              partitions to the shared operator kernels, i.e. the
              stage→operator fallback stays a runtime decision);
  operator  — operator-at-a-time shared kernels (the differential oracle;
              forced globally by the tier, per-partition by the gate).

`auto` picks whole-query only when the plan is structurally lowerable,
every leaf row count is known (LocalTableScan/Range statistics), the
plan actually contains exchange round-trips to eliminate (a single-stage
plan is already one program per batch under stage fusion — collapsing it
would trade the value-dependent dense fast paths for nothing), the
batch volume amortizes the bigger compile (spark.tpu.compile.whole.minRows
scaled by program depth — the compile-cost proxy; the measured per-kernel
compile cost from the KernelCache cost table refines the estimate when
available), and the fully-resident working set passes the
`spark.tpu.memory.budget` admission check. Any failed check falls back
tier-by-tier with the reason recorded on the plan
(`explain("analysis")` surfaces the decision).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Optional, Sequence

from ..columnar.batch import (
    EMPTY_DICT, Column, ColumnarBatch, StringDict, bucket_capacity,
    merge_string_dicts,
)
from ..errors import ExecutionError
from ..expr.expressions import Alias, AttributeReference, IsNotNull, IsNull
from ..types import BooleanType, StringType, dict_encoded
from ..utils.device_memo import device_read
from .aggregates import FUSABLE_OPS
from .compile import (
    GLOBAL_KERNEL_CACHE, bind_inputs, canonical_key, module_name, named_jit,
    note_program, pipeline_host_pass, trace_pipeline,
)
from .operators import PhysicalPlan, attrs_schema

__all__ = ["WholeQueryExec", "TierDecision", "choose_tier",
           "apply_compile_tier", "supported_whole_query",
           "supported_mesh_whole", "is_runtime_fault"]

_MAX_PROGRAM_RETRIES = 8

# re-export: tier degradation shares the runtime-fault classifier with
# the mesh gang-failure path (utils/faults.py owns it — no deps)
from ..utils.faults import is_runtime_fault  # noqa: E402


def _jnp():
    import jax.numpy as jnp

    return jnp


def _home_batch(b):
    """Re-home one batch's planes onto the default device (no-op for
    arrays already there). Readmitted plans ingest mesh-materialized
    stages whose partitions live one-per-device; a single jitted
    program cannot take args spread across devices."""
    import jax

    from dataclasses import replace

    dev = jax.devices()[0]

    def put(a):
        return None if a is None else jax.device_put(a, dev)

    cols = [replace(c, data=put(c.data), validity=put(c.validity))
            for c in b.columns]
    return ColumnarBatch(b.schema, cols, put(b.row_mask), b._num_rows)


# ---------------------------------------------------------------------------
# tier decision
# ---------------------------------------------------------------------------

@dataclass
class TierDecision:
    """Outcome of the compile-tier cost model, stashed on the plan so
    explain("analysis") and the execution span can surface it."""

    tier: str                 # "mesh-whole" | "whole" | "stage" | "operator"
    reason: str               # human-readable why (incl. fallback cause)
    details: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"tier": self.tier, "reason": self.reason,
                "details": dict(self.details)}


def _scan_table(node):
    """The backing arrow table of an in-memory ScanExec (io/sources
    InMemorySource), or None for external sources — in-memory scans have
    exact plan-time statistics like LocalTableScan."""
    import pyarrow as pa

    t = getattr(getattr(node, "source", None), "table", None)
    return t if isinstance(t, pa.Table) else None


def _external_scan_rows(node) -> Optional[int]:
    """Plan-time row count of an external scan from file-format
    statistics: io/sources.ParquetSource exposes `plan_time_rows()`
    (exact footer row-group counts, no data read). None for formats
    without trustworthy plan-time statistics."""
    fn = getattr(getattr(node, "source", None), "plan_time_rows", None)
    if fn is None:
        return None
    try:
        r = fn()
    except Exception:
        return None
    return None if r is None else int(r)


def _leaf_rows(node) -> Optional[int]:
    from ..exec.scheduler import _StageOutput
    from . import operators as O

    if isinstance(node, O.LocalTableScanExec):
        return int(node.table.num_rows)  # tpulint: ignore[host-sync]
    if isinstance(node, O.ScanExec):
        t = _scan_table(node)
        if t is None:
            return _external_scan_rows(node)
        return int(t.num_rows)  # tpulint: ignore[host-sync]
    if isinstance(node, O.RangeExec):
        step = node.step
        if step > 0:
            return max(0, -(-(node.end - node.start) // step))
        return max(0, -(-(node.start - node.end) // -step))
    if isinstance(node, _StageOutput) and node.stage.result is not None:
        # materialized parent stage (adaptive re-admission): sizes are
        # OBSERVED, not estimated — host-known batch row counts
        return sum(b.num_rows() for p in node.stage.result for b in p)
    return None


def supported_whole_query(plan, conf,
                          history_ok: bool = False) -> tuple[bool, str]:
    """Structural admission: every operator of the plan must have a
    whole-query lowering. Returns (ok, reason-if-not). `history_ok`
    relaxes the external-scan statistics requirement when a recorded
    QueryProfile run supplies observed volumes instead (adaptive
    history re-planning)."""
    from ..config import ADAPTIVE_PARQUET_STATS
    from ..exec.scheduler import _StageOutput
    from . import operators as O
    from .exchange import BroadcastExchangeExec, ShuffleExchangeExec
    from .fusion import FusedAggregateExec, FusedLimitExec  # noqa: F401
    from .window import WindowExec

    for node in _iter_inner(plan):
        if isinstance(node, (O.LocalTableScanExec, O.RangeExec)):
            continue
        if isinstance(node, _StageOutput):
            if node.stage.result is not None:
                continue   # materialized stage: an ingestable leaf
            return False, (f"stage {node.stage.stage_id} output is not "
                           "materialized")
        if isinstance(node, O.ScanExec):
            if _scan_table(node) is None:
                stats_ok = (bool(  # tpulint: ignore[host-sync] conf flag
                    conf.get(ADAPTIVE_PARQUET_STATS))
                    and _external_scan_rows(node) is not None)
                if not (stats_ok or history_ok):
                    return False, (f"scan [{node.name}] reads an external "
                                   "source (no plan-time statistics)")
            continue
        if isinstance(node, (O.ComputeExec, O.LimitExec, O.SortExec,
                             O.UnionExec, O.CoalescePartitionsExec,
                             BroadcastExchangeExec, ShuffleExchangeExec)):
            continue
        if isinstance(node, O.HashAggregateExec):
            vals = node._plan_values()
            bad = [op for op, _, _ in vals if op not in FUSABLE_OPS]
            if bad:
                return False, (f"aggregate op {bad[0]} needs host-side "
                               "finishing (no in-program lowering)")
            for g in node.grouping:
                if dict_encoded(g.dtype) and not isinstance(g.dtype,
                                                            StringType):
                    return False, (f"grouping key {g.name} is a nested "
                                   "dictionary type (codes are not a "
                                   "canonical group domain)")
            continue
        if isinstance(node, O.HashJoinExec):
            if node.join_type == "full_outer":
                return False, ("full_outer join runs eager host-side "
                               "passes (no in-program lowering)")
            for k in list(node.left_keys) + list(node.right_keys):
                if dict_encoded(k.dtype) and not isinstance(k.dtype,
                                                            StringType):
                    return False, (f"join key {k.name} is a nested "
                                   "dictionary type")
            continue
        if isinstance(node, WindowExec):
            why = _window_refusal(node)
            if why:
                return False, why
            continue
        return False, (f"operator {type(node).__name__} has no "
                       "whole-query lowering")
    return True, ""


# what `_lower_window` traces: ranks over an ORDER BY, and aggregates over
# the whole partition or the default running frame
_WINDOW_RANKS = frozenset({"row_number", "rank", "dense_rank"})
_WINDOW_AGGS = frozenset(f"agg_{frame}_{op}"
                         for frame in ("unbounded", "running")
                         for op in ("sum", "avg", "min", "max", "count"))


def _window_refusal(node) -> Optional[str]:
    """Why this WindowExec has no whole-query lowering, naming the
    function, frame or key at fault; None when it has one. Admitted:
    `_WINDOW_RANKS` and `_WINDOW_AGGS` over numeric, date and decimal
    values; partition keys of any plain or string type (equality on
    dictionary codes); order keys that sort as they are stored."""
    from ..errors import UnsupportedOperationError

    try:
        plans = node._plans()
    except UnsupportedOperationError as e:
        return f"window: {e}"
    for al, (kind, param, arg) in zip(node.window_exprs, plans):
        fn = al.child.function.sql_name()
        if kind not in _WINDOW_RANKS and kind not in _WINDOW_AGGS:
            if kind.startswith("agg_"):
                ftype, lo, hi = al.child.frame
                return (f"window frame {ftype.upper()} BETWEEN {lo} AND "
                        f"{hi} of {fn} has no whole-query lowering "
                        "(offset frames run on the stage tier)")
            return f"window function {fn} has no whole-query lowering"
        if arg is not None and dict_encoded(arg.dtype):
            return (f"window function {fn} over the dictionary-encoded "
                    f"{arg.name} has no whole-query lowering")
    for k in node.partition_keys:
        if dict_encoded(k.dtype) and not isinstance(k.dtype, StringType):
            return (f"window partition key {k.name} is a nested "
                    "dictionary type")
    for o in node.order_keys:
        if dict_encoded(o.child.dtype):
            return (f"window order key {o.child.name} is a string or "
                    "nested dictionary type (its codes do not sort)")
    return None


def _iter_inner(plan):
    """Iterate the plan INCLUDING through fused-exchange absorption (the
    plan tree itself; WholeQueryExec is opaque to the stage cutter but
    this walks its inner plan when given one)."""
    inner = plan.plan if isinstance(plan, WholeQueryExec) else plan
    return inner.iter_nodes()


def supported_mesh_whole(plan, conf) -> tuple[bool, str, dict]:
    """Mesh admission on top of supported_whole_query: every hash
    exchange must lower to an in-program `lax.all_to_all` on ONE
    power-of-two mesh axis known at plan time (plain attribute keys, a
    consistent partition count, enough devices), and at least one such
    exchange must exist — without one the single-device whole program
    already eliminates every round-trip and sharding buys nothing.
    Returns (ok, why-not, details)."""
    from ..config import MESH_ENABLED
    from .exchange import ShuffleExchangeExec
    from .partitioning import HashPartitioning
    from .window import WindowExec

    if not conf.get(MESH_ENABLED):
        return False, "spark.tpu.mesh.enabled=false", {}
    counts: set[int] = set()
    for node in _iter_inner(plan):
        if isinstance(node, WindowExec):
            # admitted on one chip (_lower_window); a partition's rows
            # would have to meet on one shard first
            return False, ("operator WindowExec has no mesh-whole "
                           "lowering"), {}
        if not isinstance(node, ShuffleExchangeExec):
            continue
        p = node.partitioning
        if not isinstance(p, HashPartitioning):
            continue
        if not all(isinstance(e, AttributeReference) for e in p.exprs):
            return False, ("hash exchange keys are computed expressions "
                           "(no in-program partition-id lowering)"), {}
        for e in p.exprs:
            if dict_encoded(e.dtype) and not isinstance(e.dtype,
                                                        StringType):
                return False, (f"exchange key {e.name} is a nested "
                               "dictionary type"), {}
        counts.add(int(p.num_partitions))  # tpulint: ignore[host-sync]
    if not counts:
        return False, ("no hash exchange to run as an in-program "
                       "collective (the single-device whole tier "
                       "already eliminates the round-trips)"), {}
    if len(counts) > 1:
        return False, (f"mixed hash partition counts {sorted(counts)} "
                       "(one mesh axis per program)"), {}
    P = counts.pop()
    if P < 2 or (P & (P - 1)) != 0:
        return False, (f"partition count {P} is not a power-of-two "
                       "mesh axis"), {}
    import jax

    n_dev = len(jax.devices())
    if n_dev < P:
        return False, f"mesh needs {P} devices, {n_dev} visible", {}
    return True, "", {"mesh_devices": P}


def _estimate_resident_bytes(plan, conf) -> Optional[int]:
    """Cheap upper-bound of the fully-resident program's engine bytes:
    every lowered operator's output tile (capacity x row bytes) plus the
    leaf input planes — all live inside ONE XLA program. Pure host
    arithmetic over plan metadata (no value tracing: the tier chooser
    must stay launch-free and cheap enough to run per query)."""
    from ..exec.memory import schema_row_bytes
    from . import operators as O
    from .exchange import BroadcastExchangeExec, ShuffleExchangeExec
    from .fusion import FusedAggregateExec
    from .window import WindowExec

    tile = int(conf.get(  # tpulint: ignore[host-sync]
        "spark.tpu.batch.capacity", 1 << 20))
    memo: dict[int, Optional[int]] = {}

    def cap_of(node) -> Optional[int]:
        hit = memo.get(id(node))
        if hit is not None or id(node) in memo:
            return hit
        memo[id(node)] = out = _cap_of(node)
        return out

    def _cap_of(node) -> Optional[int]:
        rows = _leaf_rows(node)
        if rows is not None:
            # tiling mirror: per-tile buckets, then the gathered concat
            total = 0
            n = rows
            while n > 0:
                total += bucket_capacity(min(tile, n))
                n -= tile
            return bucket_capacity(max(total, 1))
        kids = [cap_of(c) for c in node.children]
        if any(k is None for k in kids):
            return None
        if isinstance(node, O.HashAggregateExec) and not node.grouping:
            return 8
        if isinstance(node, O.HashJoinExec):
            return max(kids[0], 1 << 10)
        if isinstance(node, O.UnionExec):
            return bucket_capacity(sum(kids))
        if isinstance(node, (ShuffleExchangeExec, BroadcastExchangeExec,
                             O.CoalescePartitionsExec)):
            return kids[0]
        return kids[0] if kids else None

    total = 0
    for node in _iter_inner(plan):
        cap = cap_of(node)
        if cap is None:
            return None
        try:
            rb = schema_row_bytes(attrs_schema(node.output))
        except Exception:
            rb = 16
        total += cap * rb
        if isinstance(node, FusedAggregateExec):
            # the traced pipeline's projected planes are live too
            total += cap * 16
        elif isinstance(node, WindowExec):
            total += window_layout_bytes(node, cap)
    return total


def window_layout_bytes(node, cap: int) -> int:
    """Bytes a lowered WindowExec holds beside its output tile: the layout
    sort's operands (a key and its null flag, 12 B at most, per partition
    and order key; the flags and the row index) and
    ops/window.WindowLayout's nine planes. (analysis/plan_lint mirrors
    the estimate with the same function.)"""
    nkeys = len(node.partition_keys) + len(node.order_keys)
    return cap * (12 * nkeys + 8 + 36)


def _avg_compile_ms() -> float:
    """Online per-kernel compile-cost estimate from the KernelCache (PR 7
    cost table companion): total builder+first-invocation time over
    compiled kernels. Falls back to a conservative constant cold."""
    kc = GLOBAL_KERNEL_CACHE
    misses = max(kc.misses, 1)
    avg = kc.compile_ms / misses
    return max(avg, 50.0)


def choose_tier(plan, conf, cluster: bool = False,
                observed_rows: Optional[int] = None) -> TierDecision:
    """The three-tier cost model. See module docstring for the rules.
    `observed_rows` substitutes a RECORDED run's total shuffled volume
    (QueryProfile / warm-start manifest) for leaves whose plan-time row
    count is unknown — adaptive history re-planning for recurring
    queries over external sources."""
    from ..config import (
        COMPILE_TIER, FUSION_ENABLED, MEMORY_BUDGET, WHOLE_MIN_ROWS,
    )

    pref = str(conf.get(COMPILE_TIER)).lower()
    if pref == "operator":
        return TierDecision("operator", "forced by spark.tpu.compile.tier")
    if pref == "stage":
        return TierDecision("stage", "forced by spark.tpu.compile.tier")
    forced_mesh = pref == "mesh-whole"
    forced = pref == "whole" or forced_mesh
    base = "forced by spark.tpu.compile.tier" if forced \
        else "cost model (spark.tpu.compile.tier=auto)"
    if not conf.get(FUSION_ENABLED):
        # the whole-query program IS fusion taken to its limit: with
        # fusion disabled the session asked for the operator-at-a-time
        # differential oracle, and collapsing the plan anyway would make
        # the fusion-on/off comparison compare whole vs whole
        return TierDecision(
            "stage", "whole-query fallback: spark.tpu.fusion.enabled="
            "false (operator-at-a-time differential oracle)")
    if cluster:
        return TierDecision(
            "stage", "cluster scheduler: stages place on workers — the "
            "whole-query program needs the data driver-resident")
    if not forced:
        # cheap disqualifier FIRST: the common exchange-free query must
        # not pay the full admission walk at plan time (auto only — the
        # whole tier's win is ELIMINATING stage round-trips; a plan with
        # no exchanges is already one program per batch under stage
        # fusion, and collapsing it would trade the value-dependent
        # dense fast paths for nothing)
        from .exchange import BroadcastExchangeExec, ShuffleExchangeExec

        n_exch = sum(1 for x in _iter_inner(plan)
                     if isinstance(x, (ShuffleExchangeExec,
                                       BroadcastExchangeExec)))
        if n_exch == 0:
            return TierDecision(
                "stage", "whole-query fallback: no exchange round-trips "
                "to eliminate (single-stage plan — stage fusion already "
                "dispatches once per batch)", {"exchanges": 0})
    ok, why = supported_whole_query(plan, conf,
                                    history_ok=observed_rows is not None)
    if not ok:
        return TierDecision("stage", f"whole-query fallback: {why}")
    rows = []
    n_ops = 0
    unknown_leaves = False
    for node in _iter_inner(plan):
        n_ops += 1
        r = _leaf_rows(node)
        if r is not None:
            rows.append(r)
        elif not node.children:
            if observed_rows is None:
                return TierDecision(
                    "stage", "whole-query fallback: leaf statistics "
                    f"unknown ({type(node).__name__} row count untraced)")
            unknown_leaves = True
    volume = sum(rows)
    if unknown_leaves:
        # recorded volume stands in for the untraced leaves
        volume = max(volume, int(observed_rows))
    details = {"volume_rows": volume, "lowered_ops": n_ops,
               "est_compile_ms": round(_avg_compile_ms() * n_ops, 1)}
    if observed_rows is not None:
        details["observed_rows"] = int(observed_rows)
    est = _estimate_resident_bytes(plan, conf)
    if est is not None:
        details["est_resident_bytes"] = est
    budget = int(conf.get(MEMORY_BUDGET))  # tpulint: ignore[host-sync]
    over_budget = budget > 0 and est is not None and est > budget
    if forced_mesh or (pref == "auto" and over_budget):
        # mesh admission: the whole-program win at 1/P the per-device
        # residency. Forced mesh-whole always tries it; auto reaches for
        # it ONLY in the budget gap (the single-device whole program
        # does not fit, but a per-shard slice does) — under budget the
        # single-device program keeps its value-dependent fast paths
        mok, mwhy, mdet = supported_mesh_whole(plan, conf)
        per_shard = None
        if mok:
            P = mdet["mesh_devices"]
            per_shard = None if est is None else -(-est // P)
            if budget > 0 and per_shard is not None \
                    and per_shard > budget:
                mok = False
                mwhy = ("per-shard resident estimate "
                        f"~{per_shard / (1 << 20):.1f} MiB still "
                        "exceeds spark.tpu.memory.budget")
        if mok:
            details.update(mdet)
            if per_shard is not None:
                details["est_resident_bytes_per_shard"] = per_shard
            reason = base if forced_mesh else (
                base + " — fully-resident set exceeds the single-device "
                "budget but fits per-shard across the mesh")
            return TierDecision("mesh-whole", reason, details)
        # tier-by-tier fallback: the reason rides the decision so
        # explain("analysis") shows why the mesh program was refused
        details["mesh_whole_fallback"] = mwhy
    if over_budget:
        return TierDecision(
            "stage", "whole-query fallback: predicted fully-resident "
            f"working set ~{est / (1 << 20):.1f} MiB exceeds "
            f"spark.tpu.memory.budget ({budget / (1 << 20):.1f} MiB)",
            details)
    if not forced:
        floor = int(conf.get(WHOLE_MIN_ROWS))  # tpulint: ignore[host-sync]
        floor *= max(1, -(-n_ops // 8))
        details["volume_floor"] = floor
        if volume < floor:
            return TierDecision(
                "stage", "whole-query fallback: batch volume "
                f"{volume} rows under the compile-amortization floor "
                f"({floor}; spark.tpu.compile.whole.minRows scaled by "
                "program depth)", details)
    if forced_mesh:
        # mesh admission failed but the plan fits one device: fall back
        # ONE tier (mesh-whole -> whole), not all the way to stage
        return TierDecision(
            "whole", "mesh-whole fallback: "
            f"{details.get('mesh_whole_fallback', 'mesh inadmissible')}",
            details)
    return TierDecision("whole", base, details)


def apply_compile_tier(plan, conf, cluster: bool = False):
    """Planner hook: wrap the plan for the whole tier, or stash the
    decision (with its fallback reason) for explain("analysis")."""
    decision = choose_tier(plan, conf, cluster=cluster)
    if decision.tier == "mesh-whole":
        from .mesh_whole import MeshWholeQueryExec

        return MeshWholeQueryExec(plan, decision)
    if decision.tier == "whole":
        return WholeQueryExec(plan, decision)
    try:
        plan._tier_decision = decision
    except Exception:
        pass
    return plan


# ---------------------------------------------------------------------------
# program builder
# ---------------------------------------------------------------------------

def _plan_key_packs(metas: Sequence["_MCol"]) -> tuple:
    """Which of an aggregate's grouping keys (or a window's partition
    keys) travel as one sort key. Equality is all such keys are for, and
    a string key is dictionary codes below its dictionary's length, so
    several of them fit, a bit field each, in one integer (code + 1, 0
    for NULL: the null flag goes with it). A `lax.sort` costs the TPU
    compiler roughly the square of its number of keys: q89's group-by on
    five strings and a month is 4 sort keys so, and was 13. Returns packs
    of (key position, bits), two keys or more each; field widths are
    powers of two of the dictionary's length, so the program's cache key
    moves only when a dictionary doubles."""
    packs, cur, used = [], [], 0
    for j, mc in enumerate(metas):
        if not isinstance(mc.dtype, StringType) or mc.sdict is None:
            continue
        bits = max(1, len(mc.sdict).bit_length())  # 0 (NULL) .. len(dict)
        if used + bits > 62:
            packs.append(cur)
            cur, used = [], 0
        cur.append((j, bits))
        used += bits
    packs.append(cur)
    return tuple(tuple(p) for p in packs if len(p) > 1)


def _pack_keys(packs: tuple, keys: list, valids: list) -> tuple:
    """(keys, validity planes) with each of `packs` as one key (traced)."""
    if not packs:
        return keys, valids
    jnp = _jnp()
    out_k, out_v = [], []
    for pack in packs:
        dt = jnp.int32 if sum(b for _j, b in pack) < 32 else jnp.int64
        acc, shift = None, 0
        for j, bits in pack:
            code = jnp.clip(keys[j], 0, (1 << bits) - 2).astype(dt) + 1
            if valids[j] is not None:
                code = jnp.where(valids[j], code, 0)
            acc = code if acc is None else acc | (code << shift)
            shift += bits
        out_k.append(acc)
        out_v.append(None)
    packed = {j for pack in packs for j, _b in pack}
    for j, (k, v) in enumerate(zip(keys, valids)):
        if j not in packed:
            out_k.append(k)
            out_v.append(v)
    return out_k, out_v


def _unpack_keys(packs: tuple, packed: list, keys: list,
                 valids: list) -> list:
    """`_pack_keys` undone on an aggregate's output: [(key, validity |
    None)] per original key from the [(key, validity | None)] of the
    packed ones; `keys` and `valids` are the original ones, for their
    types and for which of them has a validity (traced)."""
    jnp = _jnp()
    out = [None] * len(keys)
    for pack, (acc, _none) in zip(packs, packed):
        shift = 0
        for j, bits in pack:
            code = (acc >> shift) & ((1 << bits) - 1)
            out[j] = ((jnp.maximum(code, 1) - 1).astype(keys[j].dtype),
                      None if valids[j] is None else code != 0)
            shift += bits
    rest = iter(packed[len(packs):])
    return [next(rest) if o is None else o for o in out]


class _MCol(NamedTuple):
    """Host-side column metadata threaded through the shadow pass: the
    same (dtype, validity presence, dictionary) triple pipeline_host_pass
    reads off a real batch — intermediate flows never materialize, their
    metadata derives from the producing operator's host pass."""

    dtype: object
    valid: bool
    sdict: Optional[StringDict]


class _MetaColShim:
    """Column-shaped view over _MCol for pipeline_host_pass (which reads
    only `.validity is not None` and `.dictionary`)."""

    __slots__ = ("validity", "dictionary")

    def __init__(self, m: _MCol):
        self.validity = True if m.valid else None
        self.dictionary = m.sdict


class _MetaView:
    __slots__ = ("columns",)

    def __init__(self, metas: Sequence[_MCol]):
        self.columns = [_MetaColShim(m) for m in metas]


class _Lowered(NamedTuple):
    metas: list            # list[_MCol] per output column
    cap: int               # static tile capacity of this flow
    emit: Callable         # emit(args, needed) -> (datas, valids, mask)
    late: tuple = ()       # per column: the _LateTag of a column the emit
    #                        hands on as a `_Late`, or None; () for none


# ---------------------------------------------------------------------------
# late materialisation: a join hands its build side's columns on as row
# numbers, and each column is gathered once, by the first reader of its
# values, at that reader's capacity
# ---------------------------------------------------------------------------

class _Rows:
    """One row-index plane that deferred columns share: `idx` (int32, at
    the flow's capacity) into their source arrays, and `live` (bool, or
    None) an outer join's null extension, ANDed into each validity when
    the column is gathered. Shared by identity: a carry moves it once."""

    __slots__ = ("idx", "live")

    def __init__(self, idx, live=None):
        self.idx = idx
        self.live = live


class _Late(NamedTuple):
    """A column a join handed on instead of gathering it: in a flow's data
    list its values are `src[rows.idx]`; in its validity list `src` is a
    bool plane or None (every row valid), and `rows.live` is ANDed in. A
    column's data and validity are handed on and gathered each on its
    own: IS NULL gathers the validity alone. Exists at trace time only."""

    src: object
    rows: _Rows


class _LateTag(NamedTuple):
    """What the lowering knows of a deferred column, for the counters."""

    join: int     # the join's place in `_ProgramBuilder.late_joins`
    col: int      # the column's place on the join's build side
    fresh: bool   # at the join's own output: nothing has carried it yet


def _late_take(datas: list, valids: list, cols, valid_cols=()) -> tuple:
    """(datas, valids) with the deferred columns among `cols` gathered,
    and of those among `valid_cols` the validity alone (what IS NULL
    reads), under `late_gather`; the validity planes of one index plane
    ride one byte a fetch (`take_planes`)."""
    flags = [i for i in dict.fromkeys((*cols, *valid_cols))
             if isinstance(valids[i], _Late)]
    cols = [i for i in cols if isinstance(datas[i], _Late)]
    if not cols and not flags:
        return datas, valids
    import jax

    from ..ops.joining import take_planes

    jnp = _jnp()
    datas, valids = list(datas), list(valids)
    groups: dict = {}
    for i in flags:
        groups.setdefault(id(valids[i].rows), []).append(i)
    with jax.named_scope("late_gather"):
        for i in cols:
            datas[i] = jnp.take(datas[i].src, datas[i].rows.idx)
        for group in groups.values():
            rows = valids[group[0]].rows
            planes = take_planes([valids[i].src for i in group],
                                 lambda w, _r=rows: jnp.take(w, _r.idx))
            for i, plane in zip(group, planes):
                if plane is None:
                    plane = rows.live if rows.live is not None else \
                        jnp.ones(rows.idx.shape[0], dtype=bool)
                elif rows.live is not None:
                    plane = plane & rows.live
                valids[i] = plane
    return datas, valids


def _late_carry(datas: list, valids: list, fetch, live=None) -> tuple:
    """(datas, valids) with every deferred column's index plane, and its
    `live` plane, moved to the next flow's slots by `fetch`: one fetch a
    distinct plane, however many columns ride on it. `live`, where given,
    is ANDed into the moved one."""
    moved: dict = {}

    def move(x):
        if not isinstance(x, _Late):
            return x
        rows = moved.get(id(x.rows))
        if rows is None:
            lv = None if x.rows.live is None else fetch(x.rows.live)
            if live is not None:
                lv = live if lv is None else lv & live
            rows = moved[id(x.rows)] = _Rows(fetch(x.rows.idx), lv)
        return _Late(x.src, rows)

    return [move(x) for x in datas], [move(x) for x in valids]


def _take_side(datas: list, valids: list, fetch) -> tuple:
    """One side of a join at the output's slots: each column by `fetch`,
    its validity planes as one byte a fetch (`take_planes`); a deferred
    column by its index plane alone."""
    from ..ops.joining import take_planes

    datas, valids = _late_carry(datas, valids, fetch)
    out_d = [x if isinstance(x, _Late) else fetch(x) for x in datas]
    planes = take_planes([None if isinstance(x, _Late) else x
                          for x in valids], fetch)
    return out_d, [x if isinstance(x, _Late) else p
                   for p, x in zip(planes, valids)]


def _hand_on(datas: list, valids: list, idx, live) -> tuple:
    """A join's build side handed on by the join's build row numbers
    `idx` instead of gathered: every column a `_Late` on one shared index
    plane, with `live` (an outer join's matches, else None) ANDed into its
    validity when it is gathered; a column the side already defers has
    its own plane moved by `idx`."""
    jnp = _jnp()
    datas, valids = _late_carry(datas, valids, lambda x: jnp.take(x, idx),
                                live)
    rows = _Rows(idx, live)
    return ([x if isinstance(x, _Late) else _Late(x, rows) for x in datas],
            [x if isinstance(x, _Late) else _Late(x, rows) for x in valids])


def _late_tag(low: _Lowered, i: int) -> Optional[_LateTag]:
    return low.late[i] if low.late else None


def _bare(expr) -> Optional[AttributeReference]:
    """The attribute an output expression hands on unchanged, or None."""
    while isinstance(expr, Alias):
        expr = expr.child
    return expr if isinstance(expr, AttributeReference) else None


def _refs(expr, values: set, flags: set) -> None:
    """Add to `values` the attributes whose values `expr` reads, and to
    `flags` those it reads as the operand of IS [NOT] NULL, which reads
    the validity alone."""
    if isinstance(expr, (IsNull, IsNotNull)) \
            and isinstance(expr.child, AttributeReference):
        flags.add(expr.child.expr_id)
    elif isinstance(expr, AttributeReference):
        values.add(expr.expr_id)
    else:
        for c in expr.children:
            _refs(c, values, flags)


class _Collect(list):
    """Emit-time scalar collector. The list body carries per-join
    `needed` capacities (the capacity-retry contract); the side channels
    carry the dense-probe guard verdicts, the observed build-key spans
    (warm-start manifest food), the rows a semi/anti join's hash index
    left undecided, and per-exchange overflow counts (mesh tier) that ride
    the SAME single dispatch — all checked once, on the host, after the
    program returns."""

    __slots__ = ("spans", "guards", "unsure", "overflows")

    def __init__(self):
        super().__init__()
        self.spans: list = []      # (lo, hi, dup) per span-observed join
        self.guards: list = []     # violation scalar per dense join
        self.unsure: list = []     # undecided rows per hash existence join
        self.overflows: list = []  # psum'd overflow per mesh exchange


class _ProgramBuilder:
    """Lowers an admitted physical plan into one traced program.

    Host pass (per execute): leaf scans execute (launch-free device-cached
    ingest), dictionaries merge, aux luts harvest, and every operator
    contributes a structural key fragment. The traced pass (once per
    program cache key) composes the SAME kernel bodies the per-stage path
    uses — trace_pipeline, ops.grouping, ops.joining, ops.sorting — into
    a single function; XLA fuses across what used to be stage boundaries."""

    def __init__(self, ctx, join_caps: list, spans_seed=None,
                 dense_off=None, expand_on=None):
        self.ctx = ctx
        self.args: list = []           # program inputs, in arg-index order
        self.key: list = []            # cache-key fragments
        self.join_caps = join_caps     # per-join output capacities (shared
        # across the retry loop: a bumped bucket re-enters here)
        self._join_seq = 0
        self.members: list[str] = []   # lowered ops, produce->consume order
        # per members row, the `jax.named_scope` its own work traces
        # under (`m<row>.<Kind>`), or None for a row that lowers to
        # nothing of its own; _member_of finds a node's row again
        self.scopes: list = []
        self._member_of: dict[int, int] = {}
        # warm-start build-side key spans ([lo, hi, unique] per join id,
        # from the persistent manifest) and the joins whose seeded span
        # the data contradicted this run (guard-verdict retry state)
        self._spans_seed = spans_seed
        self._dense_off = dense_off if dense_off is not None else set()
        self.span_jids: list[int] = []   # joins observing their span —
        # append order matches emit-time needed.spans appends (probe
        # subtree lowers AND emits before build subtree before self)
        self.guard_jids: list[int] = []  # dense joins, = guards order
        self.dense_joins: list[int] = [] # joins on the dense fast path
        # semi/anti joins: those an earlier attempt's verdict sent to the
        # expansion (their hash index left rows undecided), the ones whose
        # undecided count rides the dispatch (= needed.unsure order), and
        # per semi/anti join (output slots, expanded)
        self._expand_on = expand_on if expand_on is not None else set()
        self.unsure_jids: list[int] = []
        self.setops: list[tuple[int, bool]] = []
        # per join, lowering order: [node, build columns it hands on,
        # those its own consumer gathered (a set of build positions),
        # the rank note] — `finish` counts them and writes the notes
        self.late_joins: list = []

    # -- plumbing ----------------------------------------------------------
    def arg(self, arr) -> int:
        self.args.append(arr)
        return len(self.args) - 1

    def _member(self, node, text: Optional[str] = None,
                scoped: bool = True) -> None:
        """One row of `members` for `node`, and the scope label that
        lower() puts the node's work under."""
        if text is None:
            text = node.simple_string() if hasattr(node, "simple_string") \
                else type(node).__name__
        kind = type(node).__name__.removesuffix("Exec")
        if scoped:
            self._member_of[id(node)] = len(self.members)
        self.scopes.append(f"m{len(self.members):02d}.{kind}"
                           if scoped else None)
        self.members.append(text[:100])

    # -- dispatch ----------------------------------------------------------
    def lower(self, node) -> _Lowered:
        """Lower `node`, with everything its emit traces under the
        node's scope label. Children emit inside their parent's scope,
        so an instruction's operator is the INNERMOST `mNN.` component
        of its op_name. Scopes exist at trace time only."""
        low = self._lower_node(node)
        row = self._member_of.get(id(node))
        return low if row is None else self._scoped(low, self.scopes[row])

    def _scoped(self, low: _Lowered, label: str) -> _Lowered:
        import jax

        def emit(args, needed, _emit=low.emit):
            with jax.named_scope(label):
                return _emit(args, needed)

        return low._replace(emit=emit)

    def _late_read(self, low: _Lowered, cols) -> None:
        """A reader gathers the deferred columns among `cols` of `low`: a
        fresh one is its join's own consumer's, at the join's capacity."""
        for i in cols:
            tag = _late_tag(low, i)
            if tag is not None and tag.fresh:
                self.late_joins[tag.join][2].add(tag.col)

    def finish(self, root: _Lowered, node) -> _Lowered:
        """The program's root, with every column it still defers gathered
        (under the root operator's scope, where it has one); then each
        join's `late=<handed on>/<build columns>` note, before its rank
        note, and the counters `join.build_deferred` and
        `join.build_gathered`, once a program built."""
        import jax

        cols = range(len(root.metas))
        self._late_read(root, cols)
        for jnode, n, gathered, note in self.late_joins:
            if n:
                self.ctx.metrics.add("join.build_deferred", n - len(gathered))
                self.ctx.metrics.add("join.build_gathered", len(gathered))
                note = f"late={n - len(gathered)}/{n} {note}".rstrip()
            if note:
                self._note(jnode, note)
        if not root.late:
            return root
        row = self._member_of.get(id(node))
        label = None if row is None else self.scopes[row]

        def emit(args, needed, _emit=root.emit):
            from contextlib import nullcontext

            d, v, m = _emit(args, needed)
            with jax.named_scope(label) if label else nullcontext():
                d, v = _late_take(d, v, cols)
            return d, v, m

        return _Lowered(root.metas, root.cap, emit)

    def _lower_node(self, node) -> _Lowered:
        from ..exec.scheduler import _StageOutput
        from . import operators as O
        from .exchange import BroadcastExchangeExec, ShuffleExchangeExec
        from .fusion import FusedAggregateExec, FusedLimitExec
        from .window import WindowExec

        if isinstance(node, (O.LocalTableScanExec, O.RangeExec,
                             O.ScanExec, _StageOutput)):
            # _StageOutput: a materialized parent stage ingests exactly
            # like a scan (adaptive re-admission mid-query)
            return self._lower_leaf(node)
        if isinstance(node, FusedAggregateExec):
            low = self.lower(node.child)
            low = self._lower_pipe(node.filters, node.pipe_outputs,
                                   node.child.output, node.pipe_attrs, low)
            self._member(node)
            return self._lower_agg(node, node.pipe_attrs, low)
        if isinstance(node, O.HashAggregateExec):
            low = self.lower(node.child)
            self._member(node)
            return self._lower_agg(node, node.child.output, low)
        if isinstance(node, FusedLimitExec):
            low = self.lower(node.child)
            low = self._lower_pipe(node.filters, node.pipe_outputs,
                                   node.child.output, node.pipe_attrs, low)
            self._member(node)
            return self._lower_limit(node, low)
        if isinstance(node, O.LimitExec):
            low = self.lower(node.child)
            self._member(node)
            return self._lower_limit(node, low)
        if isinstance(node, O.SortExec):
            low = self.lower(node.child)
            self._member(node)
            return self._lower_sort(node, low)
        if isinstance(node, O.HashJoinExec):
            self._member(node)
            return self._lower_join(node)
        if isinstance(node, WindowExec):
            low = self.lower(node.child)
            self._member(node)
            return self._lower_window(node, low)
        if isinstance(node, O.ComputeExec):
            low = self.lower(node.child)
            self._member(node)
            attrs = [o.to_attribute() if isinstance(o, Alias) else o
                     for o in node.outputs]
            return self._lower_pipe(node.filters, node.outputs,
                                    node.child.output, attrs, low)
        if isinstance(node, ShuffleExchangeExec):
            low = self.lower(node.child)
            if node.pipe_fusion is not None:
                filters, outputs = node.pipe_fusion
                low = self._lower_pipe(filters, outputs, node.child.output,
                                       node.pipe_attrs, low)
            # scoped only for the fused map-side pipeline's sake
            self._member(node,
                         f"Exchange[{type(node.partitioning).__name__}] -> "
                         "in-program gather",
                         scoped=node.pipe_fusion is not None)
            self.key.append(("xgather",))
            return low
        if isinstance(node, BroadcastExchangeExec):
            self._member(node, "BroadcastExchange -> in-program identity",
                         scoped=False)
            return self.lower(node.child)
        if isinstance(node, O.CoalescePartitionsExec):
            return self.lower(node.child)
        if isinstance(node, O.UnionExec):
            lows = [self.lower(c) for c in node.children_plans]
            self._member(node)
            return self._lower_union(node, lows)
        raise ExecutionError(            # admission guarantees this
            f"whole-query lowering missing for {type(node).__name__}")

    # -- leaves ------------------------------------------------------------
    def _lower_leaf(self, node) -> _Lowered:
        from ..exec.scheduler import _StageOutput

        jnp = _jnp()
        parts = node.execute(self.ctx)
        batches = [b for p in parts for b in p]
        if batches and isinstance(node, _StageOutput):
            # a mesh-materialized stage leaves partition i resident on
            # device i; a jitted program's args must share one device —
            # re-home everything (device_put is a no-op for arrays that
            # already live there, so host-shuffled stages pay nothing)
            batches = [_home_batch(b) for b in batches]
        if not batches:
            # all-empty partitions (e.g. an empty materialized stage):
            # one empty batch keeps the concat/pad lowering uniform
            batches = [ColumnarBatch.empty(attrs_schema(node.output))]
        fields = attrs_schema(node.output).fields
        self._member(node)
        caps = [b.capacity for b in batches]
        cap = bucket_capacity(max(sum(caps), 1))
        ncols = len(fields)

        col_args = []      # per col: list[(data_idx, valid_idx|None)]
        luts = []          # per col: list[lut arg idx]|None
        metas = []
        for i, f in enumerate(fields):
            cols = [b.columns[i] for b in batches]
            merged = None
            lut_idx = None
            if dict_encoded(f.dataType):
                dicts = [c.dictionary or EMPTY_DICT for c in cols]
                if all(d is dicts[0] for d in dicts):
                    merged = dicts[0]
                else:
                    merged, lut_list = merge_string_dicts(dicts)
                    lut_idx = [self.arg(jnp.asarray(lt))
                               for lt in lut_list]
            any_valid = any(c.validity is not None for c in cols)
            entry = []
            for c in cols:
                di = self.arg(c.data)
                vi = self.arg(c.validity) if c.validity is not None \
                    else None
                entry.append((di, vi))
            col_args.append(entry)
            luts.append(lut_idx)
            metas.append(_MCol(f.dataType, any_valid, merged))
        mask_idx = [self.arg(b.row_mask) for b in batches]
        self.key.append((
            "leaf", tuple(caps),
            tuple((str(c.data.dtype), c.validity is not None)
                  for b in batches for c in b.columns),
            tuple(None if li is None else len(li) for li in luts)))

        col_args_f = list(col_args)
        luts_f = list(luts)
        metas_f = list(metas)
        bcaps = list(caps)

        def emit(args, needed):
            def pad(a, fill):
                n = sum(bcaps)
                if n < cap:
                    a = jnp.concatenate(
                        [a, jnp.full(cap - n, fill, dtype=a.dtype)])
                return a

            datas, valids = [], []
            for ci in range(ncols):
                chunks = []
                for bi, (di, _vi) in enumerate(col_args_f[ci]):
                    d = args[di]
                    if luts_f[ci] is not None:
                        lt = args[luts_f[ci][bi]]
                        d = jnp.take(lt, jnp.clip(d, 0, lt.shape[0] - 1))
                    chunks.append(d)
                datas.append(pad(jnp.concatenate(chunks), 0))
                if metas_f[ci].valid:
                    vchunks = []
                    for bi, (_di, vi) in enumerate(col_args_f[ci]):
                        if vi is None:
                            vchunks.append(jnp.ones(bcaps[bi], dtype=bool))
                        else:
                            vchunks.append(args[vi])
                    valids.append(pad(jnp.concatenate(vchunks), False))
                else:
                    valids.append(None)
            mask = pad(jnp.concatenate([args[i] for i in mask_idx]), False)
            return datas, valids, mask

        return _Lowered(metas, cap, emit)

    # -- filter/project pipelines ------------------------------------------
    def _lower_pipe(self, filters, outputs, input_attrs, out_attrs,
                    low: _Lowered) -> _Lowered:
        if not filters and all(isinstance(o, AttributeReference)
                               for o in outputs):
            # pure column selection: reorder the flow, zero trace work
            pos = {a.expr_id: i for i, a in enumerate(input_attrs)}
            sel = [pos[o.expr_id] for o in outputs]
            metas = [low.metas[i] for i in sel]
            self.key.append(("reorder", tuple(sel)))

            def emit(args, needed, _low=low, _sel=tuple(sel)):
                d, v, m = _low.emit(args, needed)
                return [d[i] for i in _sel], [v[i] for i in _sel], m

            late = tuple(low.late[i] for i in sel) if low.late else ()
            return _Lowered(metas, low.cap, emit, late)
        hctx, host_outs, aux = pipeline_host_pass(
            input_attrs, filters, outputs, _MetaView(low.metas))
        aux_idx = [self.arg(a) for a in aux]
        id_to_pos = bind_inputs(input_attrs)
        self.key.append((
            "pipe",
            tuple(canonical_key(f, id_to_pos) for f in filters),
            tuple(canonical_key(o, id_to_pos) for o in outputs),
            hctx.signature()))
        metas = [_MCol(a.dtype, hv.validity is not None,
                       hv.sdict if dict_encoded(a.dtype) else None)
                 for a, hv in zip(out_attrs, host_outs)]
        cap = low.cap
        in_attrs = list(input_attrs)
        flt = list(filters)
        outs = list(outputs)
        # the columns whose values the filters and the computed outputs
        # read are gathered here (of one only IS NULL reads, the validity);
        # a deferred column an output only hands on stays deferred
        bare = {j: id_to_pos[a.expr_id] for j, o in enumerate(outs)
                if (a := _bare(o)) is not None and a.expr_id in id_to_pos}
        values, flags = set(), set()
        for e in flt + [o for j, o in enumerate(outs) if j not in bare]:
            _refs(e, values, flags)
        reads = sorted(id_to_pos[r] for r in values if r in id_to_pos)
        nulls = sorted(id_to_pos[r] for r in flags - values
                       if r in id_to_pos)
        self._late_read(low, reads)
        handed = {j: i for j, i in bare.items()
                  if _late_tag(low, i) is not None and i not in reads}
        traced = [o for j, o in enumerate(outs) if j not in handed]

        def emit(args, needed, _low=low):
            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, reads, nulls)
            aux_arrs = [args[i] for i in aux_idx]
            od, ov, mask = trace_pipeline(in_attrs, flt, traced, d, v, m,
                                          aux_arrs, cap)
            if not handed:
                return od, ov, mask
            od, ov = iter(od), iter(ov)
            return ([d[handed[j]] if j in handed else next(od)
                     for j in range(len(outs))],
                    [v[handed[j]] if j in handed else next(ov)
                     for j in range(len(outs))], mask)

        late = tuple(low.late[handed[j]] if j in handed else None
                     for j in range(len(outs))) if handed else ()
        return _Lowered(metas, cap, emit, late)

    # -- aggregation -------------------------------------------------------
    def _lower_agg(self, node, in_attrs, low: _Lowered) -> _Lowered:
        jnp = _jnp()
        pos = {a.expr_id: i for i, a in enumerate(in_attrs)}
        out_fields = attrs_schema(node.output).fields
        vals = node._plan_values()
        ops = tuple(op for op, _, _ in vals)
        val_idx = tuple(pos[attr.expr_id] if attr is not None else -1
                        for _, attr, _ in vals)
        key_idx = tuple(pos[g.expr_id] for g in node.grouping)
        key_bool = tuple(isinstance(in_attrs[i].dtype, BooleanType)
                         for i in key_idx)
        nk = len(key_idx)
        # string MIN/MAX reduces in rank space (same trick as the fused
        # aggregate): rank lut in, winning rank -> code out
        smm = {}
        for bi, (op, attr, _p) in enumerate(vals):
            if op in ("min", "max") and attr is not None \
                    and dict_encoded(attr.dtype):
                sd = low.metas[val_idx[bi]].sdict or EMPTY_DICT
                smm[bi] = (self.arg(sd.device_ranks()),
                           self.arg(sd.device_rank_to_code()),
                           len(sd))
        buf_metas = []
        for bi, (op, attr, _p) in enumerate(vals):
            f = out_fields[nk + bi]
            sdict = None
            if dict_encoded(f.dataType):
                vi = val_idx[bi]
                if vi >= 0:
                    sdict = low.metas[vi].sdict
            buf_metas.append(_MCol(f.dataType,
                                   op not in ("count", "countstar"), sdict))
        packs = _plan_key_packs([low.metas[i] for i in key_idx])
        self.key.append(("agg", node.mode, ops, key_idx, val_idx,
                         key_bool, tuple((bi, n) for bi, (_r, _i, n)
                                         in sorted(smm.items())))
                        + ((packs,) if packs else ()))
        if node.grouping:
            # which body `ops/grouping.group_aggregate` takes at this
            # capacity: the same rule the trace asks, counted, shown in the
            # row, and in the key where it is not the plain one
            from ..ops.grouping import segment_path
            path = segment_path(low.cap)
            self.ctx.metrics.add(f"agg.segment_{path}")
            self._note(node, f"segments[{path}]")
            if path != "scatter":
                self.key.append(("segments", path))
        reads = sorted(set(key_idx) | {i for i in val_idx if i >= 0})
        self._late_read(low, reads)

        def pipe_vals(d, v, m):
            vd, vv = [], []
            for bi, i in enumerate(val_idx):
                dd = d[i] if i >= 0 else m
                if bi in smm:
                    rank = args_box[0][smm[bi][0]]
                    dd = jnp.take(rank, jnp.clip(dd.astype(jnp.int32), 0,
                                                 rank.shape[0] - 1))
                vd.append(dd)
                vv.append(v[i] if i >= 0 else None)
            return vd, vv

        def rank_back(bufs):
            out = []
            for bi, (bd, bv) in enumerate(bufs):
                if bi in smm:
                    inv = args_box[0][smm[bi][1]]
                    bd = jnp.take(inv, jnp.clip(bd.astype(jnp.int32), 0,
                                                inv.shape[0] - 1))
                out.append((bd, bv))
            return out

        def finish(bufs):
            out = []
            for bi, (bd, bv) in enumerate(bufs):
                if bi in smm:
                    out.append((bd, bv))
                    continue
                want = out_fields[nk + bi].dataType.device_dtype
                if str(bd.dtype) != str(want):
                    bd = bd.astype(want)
                out.append((bd, bv))
            return out

        args_box = [None]  # bound to the live args list inside emit

        if not node.grouping:
            metas = list(buf_metas)

            def emit(args, needed, _low=low):
                from ..ops import grouping as G

                args_box[0] = args
                d, v, m = _low.emit(args, needed)
                d, v = _late_take(d, v, reads)
                vd, vv = pipe_vals(d, v, m)
                outs = G.apply_global_ops(ops, vd, vv, m)
                outs = rank_back(outs)
                outs = finish(outs)
                datas, valids = [], []
                for bd, bv in outs:
                    datas.append(jnp.zeros((8,), dtype=bd.dtype)
                                 .at[0].set(bd))
                    valids.append(None if bv is None else
                                  jnp.zeros((8,), dtype=bool)
                                  .at[0].set(bv))
                mask = jnp.zeros((8,), dtype=bool).at[0].set(True)
                return datas, valids, mask

            return _Lowered(metas, 8, emit)

        key_metas = [_MCol(out_fields[j].dataType, low.metas[i].valid,
                           low.metas[i].sdict)
                     for j, i in enumerate(key_idx)]
        metas = key_metas + buf_metas
        cap = low.cap

        def emit(args, needed, _low=low):
            from ..ops import grouping as G

            args_box[0] = args
            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, reads)
            key_eqs = []
            for i, is_bool in zip(key_idx, key_bool):
                kd = d[i]
                if is_bool:
                    kd = kd.astype(jnp.int32)
                key_eqs.append(kd)
            key_valids = [v[i] for i in key_idx]
            vd, vv = pipe_vals(d, v, m)
            # the keys as the sort has them are the output's too: packed
            # ones are taken apart again, a boolean gets its type back
            sort_keys, sort_valids = _pack_keys(packs, key_eqs, key_valids)
            out_keys, bufs, out_mask, _ng = G.group_aggregate(
                sort_keys, sort_valids, [None] * len(sort_keys), m, ops,
                vd, vv)
            out_keys = [(kd.astype(bool) if is_bool else kd, kv)
                        for (kd, kv), is_bool in zip(
                            _unpack_keys(packs, out_keys, key_eqs,
                                         key_valids), key_bool)]
            bufs = finish(rank_back(bufs))
            datas = [kd for kd, _kv in out_keys] + [bd for bd, _ in bufs]
            valids = [kv for _kd, kv in out_keys] + [bv for _, bv in bufs]
            return datas, valids, out_mask

        return _Lowered(metas, cap, emit)

    # -- window ------------------------------------------------------------
    def _lower_window(self, node, low: _Lowered) -> _Lowered:
        """WindowExec inside the program: `physical/window.trace_window`,
        the body the per-partition kernel traces, at the child flow's
        capacity and under its live-row mask. The flow keeps its rows and
        their order and gains one column per window expression. String
        partition keys are the flow's dictionary codes (one dictionary a
        column: equality is all a partition needs), several to a sort key
        where they fit (`_plan_key_packs`)."""
        jnp = _jnp()
        from ..ops.sorting import SortKeySpec
        from .window import trace_window

        pos = {a.expr_id: i for i, a in enumerate(node.child.output)}
        pk = tuple(pos[k.expr_id] for k in node.partition_keys)
        ok = tuple(pos[o.child.expr_id] for o in node.order_keys)
        ospecs = [SortKeySpec(o.ascending, o.nulls_first)
                  for o in node.order_keys]
        plans = node._plans()
        finish = node._finish()
        # value plane per expr: a column, the row mask as ones (count(*)
        # counts frame rows), or nothing (the ranks)
        vi = tuple(pos[arg.expr_id] if arg is not None
                   else -1 if kind.endswith("_count") else None
                   for kind, _param, arg in plans)
        is_bool = {i: isinstance(low.metas[i].dtype, BooleanType)
                   for i in pk + ok}
        packs = _plan_key_packs([low.metas[i] for i in pk])
        self.key.append(("window", pk, ok,
                         tuple((s.ascending, s.nulls_first) for s in ospecs),
                         tuple((kind, v) for (kind, _p, _a), v
                               in zip(plans, vi)),
                         tuple(sig for _want, _avg, sig in finish), packs))
        metas = list(low.metas) + [
            _MCol(al.child.dtype,
                  kind.startswith("agg_") and not kind.endswith("_count"),
                  None)
            for al, (kind, _p, _a) in zip(node.window_exprs, plans)]
        cap = low.cap
        # which bodies `ops/window` takes at this capacity (the rule the
        # trace asks): counted per expression, shown in the row, and in
        # the key where they are not the plain ones
        from ..ops.grouping import segment_path
        from ..ops.window import unbounded_path
        back = "sort" if segment_path(cap) == "scan" else "scatter"
        self.ctx.metrics.add(f"window.unpermute_{back}", len(plans))
        frames = sorted({unbounded_path(
            kind, jnp.int32 if i < 0 else low.metas[i].dtype.device_dtype,
            cap) for (kind, _p, _a), i in zip(plans, vi)
            if i is not None} - {None})
        note = "".join(f"frame={f}," for f in frames) + f"unpermute={back}"
        self._note(node, f"segments[{note}]")
        if "scan" in frames or back == "sort":
            self.key.append(("segments", note))
        # the keys and values are gathered here; the flow keeps its rows
        # and order, so every other deferred column stays deferred
        reads = sorted(set(pk + ok) | {i for i in vi
                                       if i is not None and i >= 0})
        self._late_read(low, reads)
        late = tuple(None if i in reads else t
                     for i, t in enumerate(low.late)) + (None,) * len(plans) \
            if low.late else ()

        def emit(args, needed, _low=low):
            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, reads)

            def key(i):
                return d[i].astype(jnp.int32) if is_bool[i] else d[i]

            ones = m.astype(jnp.int32)
            outs = trace_window(
                plans, ospecs, finish,
                *_pack_keys(packs, [key(i) for i in pk], [v[i] for i in pk]),
                [key(i) for i in ok], [v[i] for i in ok],
                [None if i is None else ones if i < 0 else d[i]
                 for i in vi],
                [None if i is None or i < 0 else v[i] for i in vi], m)
            return (list(d) + [od for od, _ov in outs],
                    list(v) + [ov for _od, ov in outs], m)

        return _Lowered(metas, cap, emit, late)

    # -- limit / sort ------------------------------------------------------
    def _lower_limit(self, node, low: _Lowered) -> _Lowered:
        jnp = _jnp()
        n, offset = node.n, node.offset
        self.key.append(("limit", n, offset))

        def emit(args, needed, _low=low):
            d, v, m = _low.emit(args, needed)
            rank = jnp.cumsum(m.astype(jnp.int64))
            keep = m & (rank > offset) & (rank <= offset + n)
            return d, v, keep

        # reads no column: a deferred one stays deferred
        return _Lowered(low.metas, low.cap, emit, low.late)

    def _lower_sort(self, node, low: _Lowered) -> _Lowered:
        jnp = _jnp()
        from ..ops.sorting import SortKeySpec

        pos = {a.expr_id: i for i, a in enumerate(node.child.output)}
        kidx, specs, rank_idx = [], [], []
        for o in node.orders:
            i = pos[o.child.expr_id]
            kidx.append(i)
            specs.append(SortKeySpec(o.ascending, o.nulls_first))
            mc = low.metas[i]
            if dict_encoded(mc.dtype):
                sd = mc.sdict or EMPTY_DICT
                rank_idx.append((self.arg(sd.device_ranks()), len(sd)))
            else:
                rank_idx.append(None)
        self.key.append(("sort", tuple(kidx),
                         tuple((s.ascending, s.nulls_first)
                               for s in specs),
                         tuple(None if r is None else r[1]
                               for r in rank_idx)))
        kidx_t, specs_t, ranks_t = tuple(kidx), list(specs), list(rank_idx)
        is_bool = tuple(isinstance(low.metas[i].dtype, BooleanType)
                        for i in kidx)
        # the keys are gathered here; every other deferred column has its
        # index plane permuted, once a plane
        self._late_read(low, kidx_t)
        late = tuple(None if i in kidx_t or t is None else t._replace(
            fresh=False) for i, t in enumerate(low.late)) \
            if low.late else ()

        def emit(args, needed, _low=low):
            import jax

            from ..ops.sorting import sort_permutation

            d, v, m = _low.emit(args, needed)
            d, v = _late_take(d, v, kidx_t)
            keys, kvalids = [], []
            for j, i in enumerate(kidx_t):
                kd = d[i]
                if ranks_t[j] is not None:
                    r = args[ranks_t[j][0]]
                    kd = jnp.take(r, jnp.clip(kd, 0, r.shape[0] - 1))
                elif is_bool[j]:
                    kd = kd.astype(jnp.int32)
                keys.append(kd)
                kvalids.append(v[i])
            perm = sort_permutation(keys, kvalids, specs_t, m)
            with jax.named_scope("gather"):
                d, v = _late_carry(d, v, lambda x: jnp.take(x, perm))
                out_d = [x if isinstance(x, _Late) else jnp.take(x, perm)
                         for x in d]
                out_v = [x if x is None or isinstance(x, _Late)
                         else jnp.take(x, perm) for x in v]
                return out_d, out_v, jnp.take(m, perm)

        return _Lowered(low.metas, low.cap, emit, late)

    # -- joins -------------------------------------------------------------
    def _eq_lut(self, mc: _MCol):
        if isinstance(mc.dtype, StringType) or dict_encoded(mc.dtype):
            sd = mc.sdict or EMPTY_DICT
            lut = sd.device_hash_lut()
            return self.arg(lut), int(lut.shape[0])  # tpulint: ignore[host-sync]
        return None, None

    def _lower_join(self, node) -> _Lowered:
        probe = self.lower(node.left)
        if node.probe_fusion is not None:
            filters, outputs = node.probe_fusion
            probe = self._lower_pipe(filters, outputs, node.left.output,
                                     node.probe_attrs, probe)
        build = self.lower(node.right)
        return self._join_tail(node, probe, build)

    def _dense_eligible(self, node) -> bool:
        """Single plain-integral-key equi-join: the shape whose build
        side CAN have a dense direct-address table (operators.py's
        value-dependent fast path) — whether it DOES is decided by the
        warm-start span seed (_dense_span)."""
        from ..config import FUSION_DENSE_KEYS
        from ..types import DateType, IntegralType

        if len(node.left_keys) != 1 or len(node.right_keys) != 1:
            return False
        if not bool(self.ctx.conf.get(  # tpulint: ignore[host-sync]
                FUSION_DENSE_KEYS)):
            return False
        return all(isinstance(k.dtype, (IntegralType, DateType))
                   for k in (node.left_keys[0], node.right_keys[0]))

    def _dense_span(self, join_id: int, build_cap: int):
        """The seeded [lo, hi] span when the manifest proves the build
        keys of this join were unique and dense enough last run — the
        whole program then compiles the direct-address probe variant
        up front, guarded in-program against data drift."""
        if self._spans_seed is None or join_id in self._dense_off:
            return None
        if join_id >= len(self._spans_seed):
            return None
        sp = self._spans_seed[join_id]
        if not sp or len(sp) < 3 or not int(sp[2]):  # tpulint: ignore[host-sync]
            return None
        lo, hi = int(sp[0]), int(sp[1])  # tpulint: ignore[host-sync]
        span = hi - lo + 1
        # same density bound as the per-stage fast path: the table must
        # stay proportional to the build tile (8x) and bounded absolutely
        if span <= 0 or span > min(8 * build_cap, 1 << 23):
            return None
        return lo, hi

    def _join_tail(self, node, probe: _Lowered,
                   build: _Lowered) -> _Lowered:
        jnp = _jnp()
        jt = node.join_type
        lattrs = node._left_attrs
        rattrs = node.right.output
        lpos = {a.expr_id: i for i, a in enumerate(lattrs)}
        rpos = {a.expr_id: i for i, a in enumerate(rattrs)}
        lk = tuple(lpos[k.expr_id] for k in node.left_keys)
        rk = tuple(rpos[k.expr_id] for k in node.right_keys)
        lk_luts = [self._eq_lut(probe.metas[i]) for i in lk]
        rk_luts = [self._eq_lut(build.metas[i]) for i in rk]
        lk_bool = tuple(isinstance(probe.metas[i].dtype, BooleanType)
                        for i in lk)
        rk_bool = tuple(isinstance(build.metas[i].dtype, BooleanType)
                        for i in rk)
        join_id = self._join_seq
        self._join_seq += 1
        if join_id >= len(self.join_caps):
            self.join_caps.append(max(probe.cap, 1 << 10))
        out_cap = self.join_caps[join_id]
        eligible = self._dense_eligible(node)
        dense = self._dense_span(join_id, build.cap) if eligible else None
        if dense is not None:
            # dense 1:1 probe: one output row per probe row, no
            # expansion buffer — the join cap never binds
            out_cap = probe.cap
            self.dense_joins.append(join_id)
            self.ctx.metrics.add("cache.join_span_seeded")
        if eligible:
            self.span_jids.append(join_id)
        semi_anti = jt in ("left_semi", "left_anti")
        # a semi/anti join decides existence (`ops/joining._exists`) unless
        # an earlier attempt's verdict sent it to the expansion
        exists = semi_anti and dense is None \
            and join_id not in self._expand_on
        if semi_anti:
            self.setops.append((out_cap, dense is None and not exists))
        self.key.append(("join", jt, lk, rk, out_cap, lk_bool, rk_bool,
                         tuple(x[1] for x in lk_luts),
                         tuple(x[1] for x in rk_luts),
                         ("dense",) + dense if dense is not None
                         else None, eligible) + (("exists",) if exists
                                                 else ()))
        if semi_anti:
            metas = list(probe.metas)
        else:
            metas = list(probe.metas) + [
                _MCol(m.dtype, True, m.sdict) for m in build.metas]
        # what each side gathers before the join (its keys; and every
        # column it defers where the join's output is larger than the side,
        # which would otherwise be gathered at that larger capacity), and
        # what the output defers: the probe side's deferred columns carried
        # on, and every build column, by the join's own row numbers
        rec = len(self.late_joins)
        self.late_joins.append([node, 0 if semi_anti else len(rattrs),
                                set(), ""])
        # a semi/anti join reads the build side's keys alone
        p_reads = self._side_reads(probe, lk, out_cap)
        b_reads = self._side_reads(build, rk, 0 if semi_anti else out_cap)
        late = tuple(None if i in p_reads or t is None
                     else t if dense is not None else t._replace(fresh=False)
                     for i, t in enumerate(probe.late or
                                           (None,) * len(probe.metas)))
        if not semi_anti:
            late += tuple(_LateTag(rec, j, True) for j in range(len(rattrs)))
        if not any(late):
            late = ()
        if dense is not None:
            return self._join_dense(node, probe, build, metas, lk, rk,
                                    dense, semi_anti, p_reads, b_reads,
                                    late)
        from ..columnar.batch import eq_key_dtype
        from ..ops.joining import key_path

        key = key_path([eq_key_dtype(build.metas[i].dtype) for i in rk],
                       [eq_key_dtype(probe.metas[i].dtype) for i in lk])
        self.late_joins[rec][3] = self._note_ranks(
            probe.cap, build.cap, out_cap, key)
        if exists:
            self.ctx.metrics.add(f"join.{jt.removeprefix('left_')}_exists")
            self.late_joins[rec][3] += " exists"
        elif semi_anti:
            self.ctx.metrics.add("join.setop_expanded")
            self.late_joins[rec][3] += " expand"
        if exists and key == "hash":
            self.unsure_jids.append(join_id)

        def eqs_of(d, v, idx, luts, bools, args):
            eqs, valids = [], []
            for j, i in enumerate(idx):
                kd = d[i]
                if luts[j][0] is not None:
                    lut = args[luts[j][0]]
                    kd = jnp.take(lut, jnp.clip(kd.astype(jnp.int32), 0,
                                                lut.shape[0] - 1))
                elif bools[j]:
                    kd = kd.astype(jnp.int32)
                eqs.append(kd)
                valids.append(v[i])
            return eqs, valids

        def emit(args, needed, _probe=probe, _build=build, _oc=out_cap):
            import jax

            from ..ops import joining as J

            pd, pv, pm = _probe.emit(args, needed)
            bd, bv, bm = _build.emit(args, needed)
            pd, pv = _late_take(pd, pv, p_reads)
            bd, bv = _late_take(bd, bv, b_reads)
            beqs, bvalids = eqs_of(bd, bv, rk, rk_luts, rk_bool, args)
            peqs, pvalids = eqs_of(pd, pv, lk, lk_luts, lk_bool, args)
            bi_ = J.build_index(beqs, bvalids, bm, key)
            r = J.probe_join(bi_, beqs, bvalids, peqs, pvalids, pm, _oc,
                             jt, key, expand=not exists)
            needed.append(r.needed)
            if r.unsure is not None:
                needed.unsure.append(r.unsure)
            if eligible:
                # observe the build-key span + uniqueness so the NEXT
                # same-fingerprint run (via the warm-start manifest)
                # compiles the dense direct-address variant directly
                with jax.named_scope("span_observe"):
                    if key == "exact":
                        # the index is sorted on the keys themselves
                        needed.spans.append(J.observe_span(bi_))
                    else:
                        bk = beqs[0].astype(jnp.int64)
                        blive = bm if bvalids[0] is None \
                            else (bm & bvalids[0])
                        big = jnp.int64(1) << 62
                        lo_o = jnp.min(jnp.where(blive, bk, big))
                        hi_o = jnp.max(jnp.where(blive, bk, -big))
                        sk = jnp.sort(jnp.where(blive, bk, big))
                        dup = jnp.any((sk[1:] == sk[:-1])
                                      & (sk[:-1] != big)) \
                            if sk.shape[0] > 1 else jnp.asarray(False)
                        needed.spans.append(
                            (lo_o, hi_o, dup.astype(jnp.int32)))
            with jax.named_scope("gather"):
                # probe columns by the join's `src` on the body the join
                # took, their validity planes as one byte a fetch; a
                # deferred one's index plane the same way
                datas, valids = _take_side(
                    pd, pv, lambda x: J.take_probe(r, x))
                if semi_anti:
                    return datas, valids, r.out_mask
                bd, bv = _hand_on(bd, bv, r.build_idx,
                                  r.matched if jt == "left_outer" else None)
                return datas + bd, valids + bv, r.out_mask

        return _Lowered(metas, out_cap, emit, late)

    def _side_reads(self, low: _Lowered, keys: tuple, out_cap: int) -> tuple:
        """The columns one side of a join gathers before the join: its
        keys and, where the join's output is larger than the side (a
        fan-out), every column the side defers: gathered there they cost
        no more than at the join that deferred them."""
        reads = set(keys)
        if out_cap > low.cap:
            reads |= {i for i, t in enumerate(low.late) if t is not None}
        reads = tuple(sorted(reads))
        self._late_read(low, reads)
        return reads

    def _note_ranks(self, pcap: int, bcap: int, out_cap: int,
                    key: str) -> str:
        """Which body `ops/joining.rank_sorted` takes at each of the sorted
        join's three call sites (probe_join's two ranks of `pcap` hashes in
        `bcap`, _expand's rank of `out_cap` slots in `pcap` offsets), how
        `_expand` has a probe row's values at the output's slots
        (`src_path`; the fill ranks nothing), and what the build side is
        indexed on (`key_path`'s answer, `key`): the same rules the trace
        asks, counted; returns the note `finish` ends the join's row with."""
        from ..ops.joining import rank_path, src_path

        probe_path = rank_path(bcap, pcap)
        src = src_path(pcap, out_cap)
        expand_path = "none" if src == "fill" else rank_path(pcap, out_cap)
        self.ctx.metrics.add(f"join.rank_{probe_path}", 2)
        if src != "fill":
            self.ctx.metrics.add(f"join.rank_{expand_path}")
        self.ctx.metrics.add(f"join.src_{src}")
        self.ctx.metrics.add(f"join.key_{key}")
        return (f"rank[probe={probe_path},expand={expand_path}] "
                f"src={src} key={key}")

    def _note(self, node, note: str) -> None:
        """`note` at the end of the node's members row (100 characters)."""
        row = self._member_of[id(node)]
        self.members[row] = f"{self.members[row][:99 - len(note)]} {note}"

    def _join_dense(self, node, probe: _Lowered, build: _Lowered, metas,
                    lk, rk, dense, semi_anti, p_reads, b_reads,
                    late) -> _Lowered:
        """Dense direct-address probe inside the whole program: the same
        scatter/take body as the per-stage fast path (operators.py), but
        compiled up front from the warm-start manifest's build-key span
        instead of a host-synced value inspection. A guard scalar rides
        the dispatch: if the data drifted off the seeded span (or grew a
        duplicate) the host disables dense for this join and re-lowers —
        one extra round, never a wrong result. The probe side keeps its
        rows and slots; the build side is handed on by `bidx`."""
        jnp = _jnp()
        lo, hi = dense
        tcap = bucket_capacity(hi - lo + 1)
        jt = node.join_type
        self.guard_jids.append(self._join_seq - 1)
        pcap, bcap = probe.cap, build.cap
        self.ctx.metrics.add("join.dense_fast_path")

        def emit(args, needed, _probe=probe, _build=build):
            from jax import lax

            pd, pv, pm = _probe.emit(args, needed)
            bd, bv, bm = _build.emit(args, needed)
            pd, pv = _late_take(pd, pv, p_reads)
            bd, bv = _late_take(bd, bv, b_reads)
            bk = bd[rk[0]].astype(jnp.int64)
            bvd = bv[rk[0]]
            blive = bm if bvd is None else (bm & bvd)
            big = jnp.int64(1) << 62
            lo_o = jnp.min(jnp.where(blive, bk, big))
            hi_o = jnp.max(jnp.where(blive, bk, -big))
            # dead/out-of-span rows dump past the table: mode="drop"
            # discards out-of-bounds scatters (same idiom as per-stage)
            slot = jnp.where(blive, bk - lo, tcap)
            rowidx = jnp.full((tcap,), 0, jnp.int32).at[slot].set(
                lax.iota(jnp.int32, bcap), mode="drop")
            present = jnp.zeros((tcap,), jnp.int32).at[slot].add(
                1, mode="drop")
            dup = jnp.max(present) > 1
            guard = (lo_o < lo) | (hi_o > hi) | dup
            needed.guards.append(guard.astype(jnp.int32))
            needed.spans.append((lo_o, hi_o, dup.astype(jnp.int32)))
            needed.append(jnp.zeros((), jnp.int64))  # cap-slot alignment
            pk = pd[lk[0]].astype(jnp.int64) - lo
            in_range = (pk >= 0) & (pk < tcap)
            pslot = jnp.clip(pk, 0, tcap - 1)
            usable = pm & in_range
            pvd = pv[lk[0]]
            if pvd is not None:
                usable = usable & pvd
            matched = usable & (jnp.take(present, pslot) > 0)
            bidx = jnp.take(rowidx, pslot)
            if jt in ("inner", "left_semi"):
                out_mask = matched
            elif jt == "left_outer":
                out_mask = pm
            else:  # left_anti (full_outer never admits to this tier)
                out_mask = pm & ~matched
            if semi_anti:
                return list(pd), list(pv), out_mask
            bd, bv = _hand_on(bd, bv, bidx,
                              matched if jt == "left_outer" else None)
            return list(pd) + bd, list(pv) + bv, out_mask

        return _Lowered(metas, pcap, emit, late)

    # -- union -------------------------------------------------------------
    def _lower_union(self, node, lows: list) -> _Lowered:
        jnp = _jnp()
        fields = attrs_schema(node.output).fields
        ncols = len(fields)
        cap = bucket_capacity(sum(lw.cap for lw in lows))
        luts = []
        metas = []
        for ci, f in enumerate(fields):
            merged = None
            lut_idx = None
            if dict_encoded(f.dataType):
                dicts = [lw.metas[ci].sdict or EMPTY_DICT for lw in lows]
                if all(d is dicts[0] for d in dicts):
                    merged = dicts[0]
                else:
                    merged, lut_list = merge_string_dicts(dicts)
                    lut_idx = [self.arg(jnp.asarray(lt))
                               for lt in lut_list]
            luts.append(lut_idx)
            metas.append(_MCol(f.dataType,
                               any(lw.metas[ci].valid for lw in lows),
                               merged))
        self.key.append(("union", tuple(lw.cap for lw in lows)))
        for lw in lows:
            self._late_read(lw, range(ncols))

        def emit(args, needed):
            outs = [(*_late_take(d, v, range(ncols)), m)
                    for d, v, m in (lw.emit(args, needed) for lw in lows)]

            def pad(a, fill):
                n = sum(lw.cap for lw in lows)
                if n < cap:
                    a = jnp.concatenate(
                        [a, jnp.full(cap - n, fill, dtype=a.dtype)])
                return a

            datas, valids = [], []
            for ci in range(ncols):
                chunks = []
                for li, (d, _v, _m) in enumerate(outs):
                    dd = d[ci]
                    if luts[ci] is not None:
                        lt = args[luts[ci][li]]
                        dd = jnp.take(lt, jnp.clip(dd, 0,
                                                   lt.shape[0] - 1))
                    chunks.append(dd)
                datas.append(pad(jnp.concatenate(chunks), 0))
                if metas[ci].valid:
                    vchunks = []
                    for li, (_d, v, _m) in enumerate(outs):
                        vchunks.append(
                            v[ci] if v[ci] is not None
                            else jnp.ones(lows[li].cap, dtype=bool))
                    valids.append(pad(jnp.concatenate(vchunks), False))
                else:
                    valids.append(None)
            mask = pad(jnp.concatenate([m for _d, _v, m in outs]), False)
            return datas, valids, mask

        return _Lowered(metas, cap, emit)


def _seeded_caps(ctx, seed_rec: dict) -> list[int]:
    """The first attempt's join capacities out of the seed record (empty
    without one), counted: `cache.capacity_seeded` a seeded first
    attempt, `cache.capacity_remembered` one whose seed is the
    process's own memory and not the manifest's."""
    join_caps = [int(c)  # tpulint: ignore[host-sync]
                 for c in (seed_rec.get("join_caps") or ())]
    if join_caps:
        ctx.metrics.add("cache.capacity_seeded")
        if seed_rec.get("remembered"):
            ctx.metrics.add("cache.capacity_remembered")
    return join_caps


def _apply_needed(b: _ProgramBuilder, needed, unsure, join_caps: list,
                  expand_on: set) -> bool:
    """The joins' verdict, read on the host: a join whose `needed` passed
    its capacity gets the next bucket, and a semi/anti join whose hash
    index left rows undecided goes to the expansion. True where the
    program must be lowered again."""
    again = False
    for i, nd in enumerate(needed):
        n_i = int(nd)
        if n_i > join_caps[i]:
            join_caps[i] = bucket_capacity(n_i)
            again = True
    for jid, u in zip(b.unsure_jids, unsure):
        if int(u):
            expand_on.add(jid)
            again = True
    return again


def _setop_args(b: _ProgramBuilder) -> dict:
    """A `whole_query.attempt` span's account of its semi/anti joins: how
    many, the output slots they allocate, and how many took the
    expansion."""
    return {"setop_members": len(b.setops),
            "setop_slots": sum(s for s, _ in b.setops),
            "setop_expanded": sum(e for _, e in b.setops)}


def _record_spans(ctx, b: _ProgramBuilder, spans, n_joins: int) -> None:
    """Stash the observed build-side key spans (host values, read with
    the verdict) on the context (aligned
    by join id with persist_join_caps) so the close-time manifest write
    carries them — the NEXT same-fingerprint run seeds the dense
    direct-address probe variant from them (sp[2]=1 means unique)."""
    if not b.span_jids:
        return
    out: list = [None] * n_joins
    for jid, (lo, hi, dup) in zip(b.span_jids, spans):
        lo_i, hi_i = int(lo), int(hi)
        if hi_i < lo_i:
            continue  # empty build side: nothing worth seeding
        uniq = 0 if int(dup) else 1
        out[jid] = [lo_i, hi_i, uniq]
    if any(s is not None for s in out):
        ctx.persist_join_spans = out


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

class WholeQueryExec(PhysicalPlan):
    """The whole query as ONE jitted program per step.

    Opaque to the stage cutter (child_fields = ()): the scheduler sees a
    single stage with no exchanges, so there are zero host shuffle
    round-trips by construction. Leaf scans execute normally (device-
    cached, launch-free); everything above them traces into one program
    whose single dispatch the obs layer re-attributes to the member
    operators via fused_members(). Join output-capacity overflow retries
    re-dispatch the whole program with bumped buckets (counted, and
    mirrored by the plan analyzer's whole-query launch model)."""

    child_fields = ()          # the inner plan is NOT a schedulable child

    def __init__(self, plan, decision: TierDecision):
        self.plan = plan
        self.decision = decision
        self._members_cache: list | None = None
        # set when a runtime fault degraded this execution to the stage
        # tier: the obs walkers then render the INNER plan (per-member
        # attribution through the wrapper — PR 11 follow-on (d))
        self._degraded = False

    @property
    def output(self):
        return self.plan.output

    def output_partitioning(self):
        from .partitioning import SinglePartition

        return SinglePartition()

    def graph_name(self) -> str:
        return "WholeQueryExec"

    def degraded_inner(self, always: bool = False):
        """The inner plan for metric/graph rendering: exposed once a
        runtime fault degraded this run to the stage tier (the inner
        operators then executed individually and own real records), or
        unconditionally for metric-ID pre-assignment (`always=True` —
        ids must exist before execution decides whether to degrade).
        obs/metrics.metric_children is the only caller."""
        return self.plan if (always or self._degraded) else None

    def fused_members(self) -> list:
        """Every lowered operator shares this node's single dispatch.
        A degraded run renders the members as REAL child nodes with
        their own records instead (degraded_inner), so the fused view
        empties — the two renderings must not duplicate each other."""
        if self._degraded:
            return []
        if self._members_cache is None:
            self._members_cache = [
                (n.simple_string() if hasattr(n, "simple_string")
                 else type(n).__name__)[:100]
                for n in self.plan.iter_nodes()]
        return self._members_cache

    def simple_string(self):
        n = sum(1 for _ in self.plan.iter_nodes())
        return (f"WholeQuery[ops={n}, tier=whole] "
                f"({self.decision.reason[:60]})")

    def tree_string(self, depth: int = 0) -> str:
        pad = "  " * depth
        head = pad + ("+- " if depth else "") + self.simple_string()
        return head + "\n" + self.plan.tree_string(depth + 1)

    def execute(self, ctx) -> list:
        try:
            return self._execute_whole(ctx)
        except Exception as e:
            if not is_runtime_fault(e):
                raise
            # the program died AT RUNTIME (XLA fault / RESOURCE_EXHAUSTED
            # the MemoryBudgetExceeded pre-flight could not predict, or
            # an injected chaos fault): degrade to the STAGE tier and
            # re-execute the inner plan stage-at-a-time — smaller
            # programs, host round-trips, value-dependent fast paths.
            # The reason lands on the tier decision so explain() and the
            # degrade span show WHY this query did not run whole.
            return self._degrade_to_stage(ctx, e)

    def _degrade_to_stage(self, ctx, cause: Exception) -> list:
        from contextlib import nullcontext

        from ..exec.scheduler import DAGScheduler

        reason = f"{type(cause).__name__}: {str(cause)[:200]}"
        self.decision.details["runtime_degraded"] = reason
        # flip the obs walkers to per-member rendering: the inner
        # operators are about to execute individually, and their records
        # must be comparable to a stage-tier run's (plan graph, EXPLAIN
        # ANALYZE, and the query profile all descend through the wrapper)
        self._degraded = True
        ctx.metrics.add("whole_query.runtime_degraded")
        live = getattr(ctx, "live_obs", None)
        if live is not None:
            live.add_finding(getattr(ctx, "query_id", None), {
                "severity": "warning", "kind": "tier.degraded",
                "msg": "whole-query program failed at runtime — "
                       f"degraded to the stage tier and re-executed "
                       f"({reason})"})
        tracer = getattr(ctx, "tracer", None)
        sp = tracer.span("whole_query.degrade", cat="operator",
                         args={"tier": "stage", "reason": reason}) \
            if tracer is not None else nullcontext()
        with sp:
            # _run (not run): the ENCLOSING scheduler already owns this
            # query's KernelCache delta accounting — wrapping again would
            # double-count the stage tier's launches in kernel.* metrics
            return DAGScheduler(ctx)._run(self.plan)

    def _program_span(self, ctx, tier: str):
        """The `whole_query.program` span and a maker of the spans under
        it (`args` may be a callable, called only when a span is made);
        with tracing off both are no-ops and no args are built."""
        from ..obs.tracing import _NULL_SPAN

        tracer = getattr(ctx, "tracer", None)
        if tracer is None:
            return _NULL_SPAN, lambda name, args=None: _NULL_SPAN
        span = tracer.span(
            "whole_query.program", cat="operator",
            args={"tier": tier, "reason": self.decision.reason,
                  **{k: v for k, v in self.decision.details.items()
                     if isinstance(v, (int, float, str))}})
        return span, lambda name, args=None: tracer.span(
            name, cat="operator", args=args() if callable(args) else args)

    def _attempt_args(self, attempt: int, join_caps: list) -> dict:
        """What an attempt starts from: its capacities, and the three
        byte counts side by side — the tier chooser's estimate, the
        engine's ledger and the device's own. None of them syncs."""
        import jax

        from ..obs.resources import GLOBAL_LEDGER

        stats = jax.devices()[0].memory_stats() or {}
        return {"attempt": attempt,
                "join_caps": ",".join(str(c) for c in join_caps),
                "est_resident_bytes":
                    self.decision.details.get("est_resident_bytes", -1),
                "device_bytes_in_use": stats.get("bytes_in_use", -1),
                "ledger_bytes": GLOBAL_LEDGER.bytes}

    def _execute_whole(self, ctx) -> list:
        span, sub = self._program_span(ctx, "whole")
        # warm-start seeding (exec/persist_cache.plan_seed): a prior
        # same-fingerprint run's FINAL join output capacities come back
        # onto this execution's first attempt — from the process's own
        # memory, so a plan it has run before launches the ladder's
        # final program at once (already in the KernelCache), or from
        # the persistent manifest, so a restarted server compiles that
        # program directly (one engine compile, served by the XLA disk
        # cache) — instead of replaying the capacity-retry ladder.
        # Absent/short seeds fall back to the normal per-join defaults;
        # an under-sized seed just re-enters the ordinary retry loop.
        seed_rec = getattr(ctx, "persist_seed", None) or {}
        join_caps = _seeded_caps(ctx, seed_rec)
        spans_seed = seed_rec.get("join_spans") or None
        dense_off: set[int] = set()
        expand_on: set[int] = set()
        with span:
            for attempt in range(_MAX_PROGRAM_RETRIES):
                with sub("whole_query.attempt",
                         lambda: self._attempt_args(attempt, join_caps)) \
                        as att:
                    with sub("whole_query.lower"):
                        b = _ProgramBuilder(ctx, join_caps,
                                            spans_seed=spans_seed,
                                            dense_off=dense_off,
                                            expand_on=expand_on)
                        root = b.finish(b.lower(self.plan), self.plan)
                        key = ("whole_query", tuple(b.key))

                    def build(_root=root, _key=key, _scopes=b.scopes):
                        def program(args):
                            needed = _Collect()
                            datas, valids, mask = _root.emit(args, needed)
                            return (datas, valids, mask, tuple(needed),
                                    tuple(needed.spans),
                                    tuple(needed.guards),
                                    tuple(needed.unsure))

                        return named_jit("whole_query", _key, program,
                                         labels=_scopes)

                    with sub("whole_query.launch") as launch:
                        kernel = GLOBAL_KERNEL_CACHE.get_or_build(key,
                                                                  build)
                        launch.set_args({"program": module_name(kernel)})
                        note_program(kernel, (b.args,), b.members,
                                     b.scopes)
                        (datas, valids, mask, needed, spans, guards,
                         unsure) = kernel(b.args)
                    # the program's ONE capacity verdict: the joins'
                    # `needed` scalars, the dense guards and the build
                    # spans come home in one read after the single
                    # dispatch (the query's last device interaction before
                    # collect), so this sync span is the host's view of
                    # the program's device time
                    needed, guards, spans, unsure = device_read(
                        "whole_query.verdict", needed, guards, spans, unsure)
                    bumped = _apply_needed(b, needed, unsure, join_caps,
                                           expand_on)
                    # dense-probe guards: the seeded span no longer
                    # covers the build rows (data drifted under the
                    # fingerprint) — drop the dense variant for that
                    # join and re-lower
                    for jid, g in zip(b.guard_jids, guards):
                        if int(g):
                            dense_off.add(jid)
                            ctx.metrics.add(
                                "whole_query.dense_guard_retries")
                            bumped = True
                    att.set_args({"program": module_name(kernel),
                                  "discarded": bumped,
                                  "window_members": sum(
                                      (sc or "").endswith(".Window")
                                      for sc in b.scopes),
                                  **_setop_args(b)})
                if bumped:
                    continue
                if attempt:
                    ctx.metrics.add("whole_query.capacity_retries",
                                    attempt)
                ctx.metrics.add("whole_query.dispatches", attempt + 1)
                if join_caps:
                    # capacity outcomes for the warm-start manifest
                    # (QueryExecution writes it at query close)
                    ctx.persist_join_caps = list(join_caps)
                if b.dense_joins:
                    ctx.metrics.add("whole_query.dense_probe",
                                    len(b.dense_joins))
                _record_spans(ctx, b, spans, len(join_caps))
                schema = attrs_schema(self.output)
                cols = [Column(f.dataType, d, v,
                               m.sdict if dict_encoded(f.dataType)
                               else None)
                        for f, d, v, m in zip(schema.fields, datas,
                                              valids, root.metas)]
                batch = ColumnarBatch(schema, cols, mask, num_rows=None)
                return [[batch]]
            raise ExecutionError(
                "whole-query program exceeded its capacity-retry budget "
                f"({_MAX_PROGRAM_RETRIES}) — report this plan")
