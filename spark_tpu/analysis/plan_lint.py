"""Plan/trace-level static analyzer: launches-per-batch, fusion boundaries,
recompile and overflow hazards.

Role of the reference's EXPLAIN CODEGEN / debugCodegen surface
(sqlx/execution/debug/package.scala — which operators got whole-stage
codegen and why the rest fell back) extended with the numbers that matter
on a TPU: for every stage of the optimized physical plan, how many XLA
dispatches one warm execution performs, per batch, and why.

The analyzer performs an ABSTRACT interpretation of the physical plan:

  * a layout model — partitions × fixed-capacity batches — propagated from
    the scans (local relations expose exact row counts; capacity-bucket
    math mirrors columnar/batch.bucket_capacity);
  * an identity model — whether a batch's device arrays are the SAME
    objects across repeated executions (device-cached scans) or fresh per
    run: the memoized device-scalar reads (utils/
    device_memo.memo_device_scalars) launch their probe kernel only on fresh arrays;
  * a value model — for columns that trace to local arrow data through
    mask-only operators and literal predicates, exact host statistics
    (span / uniqueness / match cardinality) that decide the value-dependent
    branches: dense-scatter vs sorted-segment aggregation, dense vs sorted
    join build, probe-capacity retries.

Where a branch cannot be decided statically the report degrades honestly:
``exact`` flips to False and the reason is listed. On the fusion
differential suite (single-partition local relations, broadcast joins)
predictions are EXACT and tests/test_plan_analysis.py asserts them against
the measured KernelCache launch counters, fusion on and off.

Kernel-kind legend (KernelCache key tags): pipeline, fused_agg, uagg/dagg/
gagg, ragg (sorted-run RLE segment reduce — no grouping sort),
krange3 (dense-range scalar probe), fused_limit, limit, sort,
join_build/join_probe, fused_probe, djoin_build/djoin_probe,
fused_djoin_probe, shuffle_pids/shuffle_hash/shuffle_rr/shuffle_range,
fused_shuffle (exchange map side fused with its pipeline), mesh_stage
(whole shuffle stage as ONE shard_map dispatch — pipeline + partition ids
+ ICI all-to-all; quota retries re-dispatch), sample.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..columnar.batch import bucket_capacity
from ..config import (
    ADAPTIVE_ENABLED, ADAPTIVE_READMISSION, ADAPTIVE_RUNTIME_FILTER,
    ADVISORY_PARTITION_BYTES, AGG_BLOCK_ROWS, BATCH_CAPACITY,
    BLOOM_JOIN_FILTER, COALESCE_PARTITIONS_ENABLED, ENCODING_ENABLED,
    FUSION_DENSE_KEYS, FUSION_ENABLED, FUSION_EXCHANGE, FUSION_MESH,
    FUSION_MIN_ROWS, MESH_ENABLED, MINMAX_JOIN_FILTER, SQLConf,
)
from ..expr.expressions import (
    Alias, AttributeReference, EqualTo, GreaterThan, GreaterThanOrEqual, In,
    IsNotNull, LessThan, LessThanOrEqual, Literal, NotEqualTo,
)
from ..types import DateType, IntegralType, StringType, dict_encoded

__all__ = ["AnalysisReport", "analyze_plan"]

_EMPTY_CAP = 1 << 10   # ColumnarBatch.empty capacity
_DENSE_AGG_LIMIT = 1 << 23
_DENSE_JOIN_LIMIT = 1 << 23
_TRACE_MAX_ROWS = 1 << 22  # don't drag huge host columns into the analyzer


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------

@dataclass
class _Batch:
    rows: Optional[int]      # live-row upper bound (None = unknown)
    cap: Optional[int]       # device tile capacity (None = unknown)
    stable: bool             # same device arrays across executions
    # shuffle-built tiles carry map-side column stats: the dense-range
    # memo is seeded at build/ingest time, so the krange3 probe never
    # fires even though the arrays are fresh every run. True = every
    # integral column is seeded (the pre-annotation legacy model);
    # a frozenset holds the expr ids the exchange actually accumulates
    # (ShuffleExchangeExec.stat_cols — plan-reachable dense candidates)
    seeded: "bool | frozenset" = False
    # RunInfo-bearing tile: host-ingested (columnar/arrow ingest or
    # shuffle rebuild — True: every column carries run metadata) or a
    # pipeline output whose PASS-THROUGH columns inherited the input
    # tile's RunInfo (a frozenset of the expr ids that kept it; mask-only
    # filters never reorder rows, so ingest sortedness survives them).
    # Any other fresh kernel output drops it (Column.with_data).
    ingest: "bool | frozenset" = False

    def probe_free_for(self, expr_id) -> bool:
        """No krange3 dispatch when THIS column's range is consulted:
        stable arrays hit the memo from a prior run; seeded tiles were
        pre-populated for that column at build time."""
        if self.stable or self.seeded is True:
            return True
        return isinstance(self.seeded, frozenset) and expr_id in self.seeded

    def runs_for(self, expr_id) -> bool:
        """Column carries ingest RunInfo on this tile (the sorted-run
        ragg trigger): whole-tile ingest metadata, or run metadata a
        pass-through pipeline output inherited."""
        if self.ingest is True:
            return True
        return isinstance(self.ingest, frozenset) and expr_id in self.ingest


@dataclass
class _Trace:
    """Host value model: per-attribute raw columns traced to local data."""
    cols: dict            # expr_id -> (np values, np validity | None)
    live: np.ndarray      # row mask after the traced filter chain
    consecutive: bool = True   # rows still slice into batches in order
    # encoding model: expr_id -> tuple of dictionary values in DICT ORDER
    # for columns whose runtime dictionary covers more than the traced
    # rows (join gathers keep the FULL build dictionary; agg/shuffle
    # outputs keep merged input dictionaries). Absent entries derive the
    # domain from the value slice itself — the appearance-order distinct
    # pyarrow's dictionary_encode produces at ingest — but only while
    # `dict_derivable` holds (row subsets break the derivation: the
    # runtime dictionary still covers the DROPPED rows' values)
    dict_domains: dict = field(default_factory=dict)
    dict_derivable: bool = True

    def stats(self, expr_id):
        """(values_under_live_and_valid,) or None."""
        ent = self.cols.get(expr_id)
        if ent is None:
            return None
        vals, valid = ent
        m = self.live if valid is None else (self.live & valid)
        return vals[m]

    def compacted(self) -> "_Trace":
        """Live rows only (the shape of a shuffle/aggregate OUTPUT, where
        masked rows were dropped on the way through the host buffers)."""
        m = self.live
        cols = {k: (v[m], None if val is None else val[m])
                for k, (v, val) in self.cols.items()}
        return _Trace(cols, np.ones(int(m.sum()), bool), self.consecutive,
                      dict(self.dict_domains), False)

    def select(self, sel: np.ndarray, consecutive: bool) -> "_Trace":
        """Row subset (over an already-compacted trace)."""
        cols = {k: (v[sel], None if val is None else val[sel])
                for k, (v, val) in self.cols.items()}
        return _Trace(cols, np.ones(len(sel), bool), consecutive,
                      dict(self.dict_domains), False)


@dataclass
class _Flow:
    parts: list                       # list[list[_Batch]]
    trace: Optional[_Trace] = None
    counted: bool = True              # batch counts are known exactly
    # per-partition traces for multi-partition flows (post-exchange /
    # post-aggregate); when None, `trace` describes the whole flow (the
    # single-partition case every traced scan starts from)
    ptraces: Optional[list] = None

    @property
    def total_batches(self):
        return sum(len(p) for p in self.parts)

    def part_trace(self, i: int) -> Optional[_Trace]:
        if self.ptraces is not None:
            return self.ptraces[i] if i < len(self.ptraces) else None
        return self.trace

    def all_part_traces(self) -> Optional[list]:
        """Per-partition traces covering EVERY partition, or None."""
        if self.ptraces is not None:
            if len(self.ptraces) == len(self.parts) and \
                    all(t is not None for t in self.ptraces):
                return list(self.ptraces)
            return None
        if self.trace is not None and len(self.parts) == 1:
            return [self.trace]
        return None


# ---------------------------------------------------------------------------
# host mirror of the device hash partitioner (ops/hashing.py)
# ---------------------------------------------------------------------------

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _np_mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over uint64 lanes — bit-exact numpy mirror of
    ops/hashing.mix64 (uint64 arithmetic wraps modulo 2^64 on both; the
    errstate silences the 0-d scalar path's overflow warning — wrapping
    IS the hash)."""
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint64(30))
        x = x * _M1
        x = x ^ (x >> np.uint64(27))
        x = x * _M2
        x = x ^ (x >> np.uint64(31))
    return x


def _np_eq_lane(vals: np.ndarray, valid) -> np.ndarray:
    """Host mirror of Column.eq_keys as a uint64 hash lane: numeric
    columns cast to int64; STRING columns map each value to its stable
    dictionary hash (the same StringDict.hashes the runtime lut holds,
    native or blake2b — codes → value hashes is exactly what the padded
    dict-hash aux table computes inside the trace). Null rows get lane 0:
    hash_columns replaces them with the null tag regardless."""
    vals = np.asarray(vals)
    if vals.dtype != object:
        return vals.astype(np.int64).view(np.uint64)
    from ..columnar.batch import StringDict

    if not len(vals):
        return np.zeros(0, np.uint64)
    # vectorized: hash each distinct value once, scatter by inverse index
    # (traced string columns hold only str — nulls are "" placeholders).
    # Invalid rows' lanes are irrelevant: the hash mirror replaces them
    # with the null tag.
    uniq, inv = np.unique(vals, return_inverse=True)
    hashes = StringDict([str(u) for u in uniq]).hashes
    return hashes[inv].view(np.uint64)


def _np_hash_pids(cols: list, num_out: int, seed: int = 42) -> np.ndarray:
    """Partition ids of traced key columns — the host-side hash of traced
    keys that lets multi-stage shuffle plans predict exactly. Mirrors
    hash_columns + partition_ids: eq-key lanes (int64 casts; string
    values via their dictionary hashes), splitmix64 lanes, null tags,
    31x + golden combine, nonlinear seed fold, pmod."""
    h = None
    for i, (vals, valid) in enumerate(cols):
        k = _np_mix64(_np_eq_lane(vals, valid))
        if valid is not None:
            null_tag = _np_mix64(
                np.asarray(0x6E756C6C + i, np.int64).view(np.uint64))
            k = np.where(valid, k, null_tag)
        if h is None:
            h = k
        else:
            with np.errstate(over="ignore"):
                h = _np_mix64(h * np.uint64(31) + k + _GOLDEN)
    seed_u = _np_mix64(np.asarray(seed, np.int64).view(np.uint64))
    final = _np_mix64(h ^ seed_u).view(np.int64)
    return (final % np.int64(num_out)).astype(np.int32)


@dataclass
class AnalysisReport:
    stages: list = field(default_factory=list)
    predicted_launches: dict = field(default_factory=dict)
    exact: bool = True
    # compile-tier decision (physical/whole_query.choose_tier): which of
    # whole / stage / operator ran, and the fallback reason when the
    # cost model declined a higher tier
    tier: Optional[dict] = None
    inexact_reasons: list = field(default_factory=list)
    fusion_boundaries: list = field(default_factory=list)
    recompile_hazards: list = field(default_factory=list)
    overflow_risks: list = field(default_factory=list)
    host_sync_notes: list = field(default_factory=list)
    # memory model (mirrors the layout model the launch counts ride on):
    # per-stage predicted HBM in stages[i]["hbm_bytes"]; the query peak
    # is the SUM of stage outputs — an upper bound on simultaneously
    # resident engine tiles (operators materialize whole output
    # partition lists; GC frees consumed children at uncertain points)
    predicted_peak_hbm: Optional[int] = None
    memory_exact: bool = True
    memory_notes: list = field(default_factory=list)

    @property
    def total(self) -> int:
        return sum(self.predicted_launches.values())

    def to_dict(self) -> dict:
        return {
            "stages": list(self.stages),
            "tier": dict(self.tier) if self.tier else None,
            "predicted_launches": dict(self.predicted_launches),
            "predicted_total": self.total,
            "exact": self.exact,
            "inexact_reasons": list(self.inexact_reasons),
            "fusion_boundaries": list(self.fusion_boundaries),
            "recompile_hazards": list(self.recompile_hazards),
            "overflow_risks": list(self.overflow_risks),
            "host_sync_notes": list(self.host_sync_notes),
            "predicted_peak_hbm": self.predicted_peak_hbm,
            "memory_exact": self.memory_exact,
            "memory_notes": list(self.memory_notes),
        }

    def render(self) -> str:
        out = ["== Plan Analysis =="]
        if self.tier:
            out.append(f"-- compilation tier: {self.tier.get('tier')} — "
                       f"{self.tier.get('reason', '')} --")
        out.append("-- stages (kernel launches per warm execution) --")
        for s in self.stages:
            kinds = ", ".join(f"{k}:{v}" for k, v in sorted(
                s["kinds"].items())) or "none"
            lpb = s.get("launches_per_batch")
            lpb_s = f", {lpb:g}/batch" if lpb is not None else ""
            out.append(f"  {s['op']}: {{{kinds}}} over "
                       f"{s['batches']} batch(es){lpb_s}")
            for n in s.get("notes", ()):
                out.append(f"      - {n}")
        pred = ", ".join(f"{k}:{v}" for k, v in sorted(
            self.predicted_launches.items()))
        tag = "EXACT" if self.exact else "approximate"
        out.append(f"-- predicted launches ({tag}): total {self.total} "
                   f"{{{pred}}} --")
        for r in self.inexact_reasons:
            out.append(f"  ? {r}")
        if self.predicted_peak_hbm is not None:
            mtag = "model exact" if self.memory_exact \
                else "model approximate"
            out.append(f"-- predicted peak HBM ({mtag}): "
                       f"~{self.predicted_peak_hbm / (1 << 20):.1f} MiB "
                       "resident engine tiles --")
            staged = sorted((s for s in self.stages
                             if s.get("hbm_bytes")),
                            key=lambda s: -s["hbm_bytes"])[:5]
            for s in staged:
                out.append(f"  {s['op']:<22} "
                           f"~{s['hbm_bytes'] / (1 << 20):.2f} MiB")
            for n in self.memory_notes:
                out.append(f"  ? {n}")
        if self.fusion_boundaries:
            out.append("-- fusion boundaries --")
            out.extend(f"  * {b}" for b in self.fusion_boundaries)
        if self.recompile_hazards:
            out.append("-- recompile hazards --")
            out.extend(f"  ! {h}" for h in self.recompile_hazards)
        if self.overflow_risks:
            out.append("-- dtype overflow risks --")
            out.extend(f"  ! {h}" for h in self.overflow_risks)
        if self.host_sync_notes:
            out.append("-- host-sync notes --")
            out.extend(f"  . {h}" for h in self.host_sync_notes)
        return "\n".join(out)


# ---------------------------------------------------------------------------
# host predicate evaluation over traced columns
# ---------------------------------------------------------------------------

_CMP = {EqualTo: "==", NotEqualTo: "!=", GreaterThan: ">",
        GreaterThanOrEqual: ">=", LessThan: "<", LessThanOrEqual: "<="}


def _eval_filter(e, trace: _Trace):
    """Boolean mask where the predicate holds (nulls → False), or None when
    the predicate is outside the traced language."""
    if isinstance(e, IsNotNull) and isinstance(e.child, AttributeReference):
        ent = trace.cols.get(e.child.expr_id)
        if ent is None:
            return None
        vals, valid = ent
        return np.ones(len(vals), bool) if valid is None else valid.copy()
    if type(e) in _CMP:
        l, r = e.left, e.right
        if isinstance(l, Literal) and isinstance(r, AttributeReference):
            l, r = r, l
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                  "==": "==", "!=": "!="}[_CMP[type(e)]]
        else:
            op = _CMP[type(e)]
        if not (isinstance(l, AttributeReference) and isinstance(r, Literal)
                and r.value is not None):
            return None
        ent = trace.cols.get(l.expr_id)
        if ent is None:
            return None
        vals, valid = ent
        fns = {"==": np.equal, "!=": np.not_equal, ">": np.greater,
               ">=": np.greater_equal, "<": np.less, "<=": np.less_equal}
        try:
            with np.errstate(all="ignore"):
                m = fns[op](vals, r.value)
        except Exception:
            return None
        if valid is not None:
            m = m & valid
        return np.asarray(m, bool)
    if isinstance(e, In) and isinstance(e.child, AttributeReference) \
            and all(isinstance(i, Literal) for i in e.items):
        ent = trace.cols.get(e.child.expr_id)
        if ent is None:
            return None
        vals, valid = ent
        m = np.isin(vals, [i.value for i in e.items if i.value is not None])
        if valid is not None:
            m = m & valid
        return m
    return None


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------

class _Analyzer:
    def __init__(self, conf: SQLConf, cluster: bool = False):
        self.conf = conf
        self._cluster = cluster
        self.report = AnalysisReport()
        self.predicted = Counter()
        self._fusion_on = bool(conf.get(FUSION_ENABLED))
        self._fusion_exchange = bool(conf.get(FUSION_EXCHANGE))
        self._fusion_mesh = bool(conf.get(FUSION_MESH))
        self._min_rows = int(conf.get(FUSION_MIN_ROWS))
        self._dense_keys = bool(conf.get(FUSION_DENSE_KEYS))
        self._encoding = bool(conf.get(ENCODING_ENABLED))
        self._tile = int(conf.get(BATCH_CAPACITY))
        # memory model state: the stage entry each node produced (so the
        # OUTPUT flow recorded after the handler returns can annotate it)
        self._stage_by_node: dict[int, dict] = {}
        self._hbm_total = 0
        self._hbm_any = False
        # persistent-cache mirror state (exec/persist_cache.py)
        self._plan_root = None
        self._persist_seed = None
        self._persist_seed_done = False

    # -- bookkeeping -------------------------------------------------------
    def _approx(self, reason: str):
        self.report.exact = False
        if reason not in self.report.inexact_reasons:
            self.report.inexact_reasons.append(reason)

    def _hazard(self, text: str):
        if text not in self.report.recompile_hazards:
            self.report.recompile_hazards.append(text)

    def _sync(self, text: str):
        if text not in self.report.host_sync_notes:
            self.report.host_sync_notes.append(text)

    def _stage(self, node, kinds: Counter, batches, notes=()):
        self.predicted.update(kinds)
        lpb = None
        if isinstance(batches, int) and batches:
            per_batch = sum(v for k, v in kinds.items()
                            if k in ("pipeline", "fused_agg", "fused_limit",
                                     "join_probe", "fused_probe",
                                     "djoin_probe", "fused_djoin_probe",
                                     "shuffle_pids", "shuffle_hash",
                                     "shuffle_rr", "shuffle_range",
                                     "fused_shuffle", "sample"))
            if per_batch and batches:
                lpb = round(per_batch / batches, 2)
        detail = node.simple_string() if hasattr(node, "simple_string") \
            else type(node).__name__
        ent = {
            "op": type(node).__name__,
            "detail": detail[:120],
            "kinds": dict(kinds),
            "batches": batches,
            "launches_per_batch": lpb,
            "notes": list(notes),
        }
        self.report.stages.append(ent)
        self._stage_by_node[id(node)] = ent

    # -- persistent-cache mirrors (exec/persist_cache.py) -------------------
    def _persist_seed_record(self):
        """What the analyzed plan's first attempt starts from, by its
        full fingerprint: the warm-start manifest's record, or without
        one the join capacities this process remembers from the plan's
        last execution (None when neither holds outcomes) — the SAME
        lookup QueryExecution performs (persist_cache.plan_seed), so the
        capacity mirrors below predict a seeded first attempt exactly.
        Memoized per analysis."""
        if self._persist_seed_done:
            return self._persist_seed
        self._persist_seed_done = True
        try:
            from ..exec.persist_cache import plan_seed

            if self._plan_root is not None:
                from ..obs.history import plan_fingerprint

                fp = plan_fingerprint(self._plan_root, self.conf)
                self._persist_seed = plan_seed(self.conf,
                                               fp["fingerprint"])
        except Exception:
            self._persist_seed = None
        return self._persist_seed

    def _mesh_quota_seed(self, node, child, fused_mesh: bool,
                         num_out: int):
        """Mirror of the mesh exchanges' warm-start quota lookup: the
        same mesh_quota_key the execution layer computes from the
        staging geometry, resolved against the same manifest record
        (quotas are the manifest's alone: what the process remembers
        without a cache dir holds none)."""
        seed = self._persist_seed_record()
        quotas = (seed or {}).get("mesh_quotas") or {}
        if not quotas:
            return None
        caps = [b.cap for part in child.parts for b in part]
        if not caps or any(c is None for c in caps):
            return None
        from ..exec.persist_cache import (
            mesh_quota_key_fused, mesh_quota_key_plain,
        )
        from ..parallel.mesh_fusion import mesh_stage_geometry

        rows_per_shard, _cap, _q = mesh_stage_geometry(sum(caps), num_out)
        p = node.partitioning
        pos = {a.expr_id: i for i, a in enumerate(node.output)}
        try:
            key_idx = tuple(pos[e.expr_id] for e in p.exprs)
        except (AttributeError, KeyError):
            return None
        dtypes = [str(a.dtype) for a in node.output]
        if fused_mesh:
            mkey = mesh_quota_key_fused(num_out, rows_per_shard, key_idx,
                                        len(node.output), dtypes)
        else:
            mkey = mesh_quota_key_plain(num_out, rows_per_shard, key_idx,
                                        dtypes)
        return quotas.get(mkey)

    # -- entry -------------------------------------------------------------
    def run(self, plan) -> AnalysisReport:
        self._plan_root = plan
        # persistent result cache: a plan whose collect would answer
        # from the on-disk result cache RIGHT NOW launches NOTHING —
        # planning is host-only work and the payload is already on disk.
        # Same key computation as the execution path (result_probe), so
        # the zero-launch hit prediction is exact by construction.
        try:
            from ..exec.persist_cache import result_probe

            hit = result_probe(plan, self.conf)
        except Exception:
            hit = False
        if hit:
            dec = getattr(plan, "_tier_decision", None) \
                or getattr(plan, "decision", None)
            if dec is not None:
                try:
                    self.report.tier = dec.to_dict()
                except Exception:
                    pass
            self._stage(plan, Counter(), 0, notes=(
                "RESULT CACHE HIT: this plan's fingerprint + leaf data "
                "versions match a stored result (spark.tpu.cache.dir) — "
                "the collect answers from the Arrow payload with ZERO "
                "kernel launches",))
            self.report.predicted_launches = {}
            return self.report
        # compile-tier decision: the planner stashes the chooser's verdict
        # (incl. the whole-query fallback reason) on the plan root; the
        # whole tier's own root node carries it directly
        dec = getattr(plan, "_tier_decision", None)
        if dec is not None and self.report.tier is None:
            try:
                self.report.tier = dec.to_dict()
            except Exception:
                pass
        self.visit(plan)
        # adaptive stage-boundary re-admission (physical/adaptive.py):
        # with the layer on, a multi-stage plan may collapse its
        # remaining stages into one whole-tier program after any shuffle
        # materializes — and a recurring query may re-plan from its
        # warm-start history before the first batch. Both re-decisions
        # depend on observed runtime sizes the static model cannot know,
        # so a re-admittable plan is honestly inexact; single-stage and
        # already-whole plans stay exact (nothing left to re-admit).
        if bool(self.conf.get(ADAPTIVE_READMISSION)):
            from ..physical.exchange import ShuffleExchangeExec
            from ..physical.whole_query import WholeQueryExec

            if not isinstance(plan, WholeQueryExec) and any(
                    isinstance(n, ShuffleExchangeExec)
                    for n in plan.iter_nodes()):
                self._approx(
                    "adaptive re-admission: remaining stages may collapse "
                    "into a whole-tier program at a stage boundary "
                    "(spark.tpu.adaptive.readmission — tier re-decision "
                    "uses observed runtime sizes)")
        # zero-count kinds (a probe that never fires on this plan) are
        # bookkeeping, not predictions — the measured delta never lists
        # them either
        self.report.predicted_launches = {
            k: v for k, v in self.predicted.items() if v}
        if self._hbm_any:
            self.report.predicted_peak_hbm = self._hbm_total
        self._explain_boundaries(plan)
        self._overflow_pass(plan)
        return self.report

    # -- memory model ------------------------------------------------------
    def _mem_approx(self, reason: str) -> None:
        self.report.memory_exact = False
        if reason not in self.report.memory_notes:
            self.report.memory_notes.append(reason)

    def _record_memory(self, node, flow: _Flow) -> None:
        """Predicted HBM of one stage's OUTPUT tiles: capacity × device
        row bytes (column data + validity planes + row mask — the same
        schema_row_bytes the MemoryManager budgets with and the same
        planes the runtime ledger registers per batch). Unknown
        capacities fall back to the session tile and degrade the model
        to approximate; the query peak sums stages (everything an
        execution materializes counts once)."""
        try:
            from ..exec.memory import schema_row_bytes
            from ..physical.operators import attrs_schema

            row_bytes = schema_row_bytes(attrs_schema(node.output))
        except Exception:
            row_bytes = None
            self._mem_approx(f"{type(node).__name__}: output schema "
                             "unavailable — stage bytes estimated at "
                             "16 B/row")
        total = 0
        for p in flow.parts:
            for b in p:
                cap = b.cap
                if cap is None:
                    cap = self._tile
                    self._mem_approx(
                        f"{type(node).__name__}: unknown tile capacity "
                        "assumed spark.tpu.batch.capacity")
                total += cap * (row_bytes if row_bytes else 16)
        ent = self._stage_by_node.get(id(node))
        if ent is not None and "hbm_bytes" not in ent:
            ent["hbm_bytes"] = total
            self._hbm_total += total
            self._hbm_any = True

    # -- dispatch ----------------------------------------------------------
    def visit(self, node) -> _Flow:
        flow = self._dispatch(node)
        self._record_memory(node, flow)
        return flow

    def _dispatch(self, node) -> _Flow:
        from ..physical import operators as O
        from ..physical.exchange import (
            BroadcastExchangeExec, ShuffleExchangeExec,
        )
        from ..physical.fusion import FusedAggregateExec, FusedLimitExec
        from ..physical.mesh_whole import MeshWholeQueryExec
        from ..physical.python_eval import PythonEvalExec
        from ..physical.whole_query import WholeQueryExec

        # MeshWholeQueryExec subclasses WholeQueryExec: route it first
        if isinstance(node, MeshWholeQueryExec):
            return self._mesh_whole(node)
        if isinstance(node, WholeQueryExec):
            return self._whole_query(node)
        if isinstance(node, PythonEvalExec):
            return self._python_eval(node)
        if isinstance(node, O.LocalTableScanExec):
            return self._local_scan(node)
        if isinstance(node, O.ScanExec):
            return self._scan(node)
        if isinstance(node, O.RangeExec):
            return self._range(node)
        if isinstance(node, O.ComputeExec):
            return self._compute(node)
        if isinstance(node, FusedAggregateExec):
            return self._fused_agg(node)
        if isinstance(node, O.HashAggregateExec):
            return self._agg(node)
        if isinstance(node, FusedLimitExec):
            return self._fused_limit(node)
        if isinstance(node, O.LimitExec):
            return self._limit(node)
        if isinstance(node, O.SortExec):
            return self._sort(node)
        if isinstance(node, O.HashJoinExec):
            return self._join(node)
        if isinstance(node, O.NestedLoopJoinExec):
            return self._nl_join(node)
        if isinstance(node, BroadcastExchangeExec):
            return self._broadcast(node)
        if isinstance(node, ShuffleExchangeExec):
            return self._exchange(node)
        if isinstance(node, O.UnionExec):
            return self._union(node)
        if isinstance(node, O.CoalescePartitionsExec):
            return self._coalesce(node)
        if isinstance(node, O.SampleExec):
            return self._sample(node)
        return self._unknown(node)

    # -- scans -------------------------------------------------------------
    def _batches_for_rows(self, n: int) -> list:
        if n == 0:
            return [_Batch(0, _EMPTY_CAP, True, ingest=True)]
        out = []
        for start in range(0, n, self._tile):
            rows = min(self._tile, n - start)
            out.append(_Batch(rows, bucket_capacity(rows), True,
                              ingest=True))
        return out

    @staticmethod
    def _is_traced_string(t) -> bool:
        import pyarrow as pa

        return (pa.types.is_string(t) or pa.types.is_large_string(t)
                or (pa.types.is_dictionary(t)
                    and (pa.types.is_string(t.value_type)
                         or pa.types.is_large_string(t.value_type))))

    def _table_trace(self, node) -> tuple:
        """(row count, value trace | None) of a LocalTableScan — shared
        by the per-stage layout model and the whole-query mirror."""
        return self._arrow_trace(node.table, node.attrs)

    def _arrow_trace(self, table, attrs) -> tuple:
        import pyarrow as pa

        n = table.num_rows
        cols = {}
        if 0 < n <= _TRACE_MAX_ROWS:
            names = {a.name: a for a in attrs}
            for fld in table.schema:
                a = names.get(fld.name)
                if a is None:
                    continue
                if pa.types.is_integer(fld.type):
                    arr = table.column(fld.name)
                    if isinstance(arr, pa.ChunkedArray):
                        arr = arr.combine_chunks()
                    valid = np.asarray(arr.is_valid()) \
                        if arr.null_count else None
                    vals = np.asarray(arr.fill_null(0))
                    cols[a.expr_id] = (vals, valid)
                elif self._is_traced_string(fld.type):
                    # encoding model: string values trace as object
                    # arrays — dictionary domains (dense-on-codes
                    # cardinality) and eq-key hash lanes derive from them
                    arr = table.column(fld.name)
                    if isinstance(arr, pa.ChunkedArray):
                        arr = arr.combine_chunks()
                    valid = np.asarray(arr.is_valid()) \
                        if arr.null_count else None
                    vals = np.empty(n, dtype=object)
                    vals[:] = ["" if v is None else v
                               for v in arr.to_pylist()]
                    cols[a.expr_id] = (vals, valid)
        return n, (_Trace(cols, np.ones(n, bool)) if cols else None)

    def _local_scan(self, node) -> _Flow:
        n, trace = self._table_trace(node)
        flow = _Flow([self._batches_for_rows(n)], trace)
        self._stage(node, Counter(), flow.total_batches,
                    [f"{n} rows, device-cached (stable identity)"])
        return flow

    # -- encoding model helpers ---------------------------------------------
    def _trace_domain(self, trace: Optional[_Trace], expr_id,
                      lo=None, hi=None):
        """Ordered dictionary domain of a traced string column over row
        span [lo, hi) (None = whole trace): the EXPLICIT domain when one
        is recorded (join/agg/shuffle outputs whose runtime dictionary
        covers more than the traced rows — slice-independent), else the
        appearance-order distinct of non-null values over the span,
        mirroring pyarrow dictionary_encode at ingest."""
        if trace is None:
            return None
        dom = trace.dict_domains.get(expr_id)
        if dom is not None:
            return dom
        if not trace.dict_derivable:
            return None
        ent = trace.cols.get(expr_id)
        if ent is None or ent[0].dtype != object:
            return None
        vals, valid = ent
        sl = slice(lo, hi)
        v = vals[sl]
        m = np.ones(len(v), bool) if valid is None else valid[sl]
        live = v[m]
        if not len(live):
            return ()
        # appearance-order distinct, vectorized: unique + first-index sort
        uniq, first = np.unique(live, return_index=True)
        return tuple(uniq[np.argsort(first)])

    def _chunk_dict_domain(self, trace: Optional[_Trace], batches,
                           expr_id):
        """Merged dictionary domain of one aggregation chunk (its batches
        concat and unify dictionaries): the explicit per-partition
        domain, or the derived domain when the chunk covers the whole
        traced partition."""
        if trace is None:
            return None
        dom = trace.dict_domains.get(expr_id)
        if dom is not None:
            return dom
        rows = [b.rows for b in batches]
        if all(r is not None for r in rows) \
                and sum(rows) == len(trace.live):
            return self._trace_domain(trace, expr_id)
        return None

    @staticmethod
    def _ordered_union(domains) -> tuple:
        seen: dict = {}
        for dom in domains:
            for v in dom:
                if v not in seen:
                    seen[v] = None
        return tuple(seen)

    def _scan(self, node) -> _Flow:
        nparts = node.source.num_partitions()
        flow = _Flow([[_Batch(None, None, False)] for _ in range(nparts)],
                     None, counted=False)
        self._stage(node, Counter(), None,
                    ["external source: per-partition batch counts unknown"])
        return flow

    def _part_tiles(self, total: int, nparts: int) -> list:
        """Per-partition (rows, capacity) tile layout of `total` rows
        ceil-div split across `nparts` partitions, each partition tiled
        at spark.tpu.batch.capacity — the ONE mirror of
        RangeExec.execute / InMemorySource.read_partition +
        table_to_batches leaf batching (shared by the per-stage layout
        model and the whole-query walk so the formulas cannot drift)."""
        per = -(-total // nparts) if total else 0
        out = []
        for q in range(nparts):
            lo = min(q * per, total)
            hi = min(lo + per, total)
            tiles = [(min(self._tile, hi - s),
                      bucket_capacity(min(self._tile, hi - s)))
                     for s in range(lo, hi, self._tile)] \
                or [(0, _EMPTY_CAP)]
            out.append(tiles)
        return out

    def _range(self, node) -> _Flow:
        step = node.step
        total = max(0, -(-(node.end - node.start) // step)) if step > 0 \
            else max(0, -(-(node.start - node.end) // -step))
        per = -(-total // node.num_partitions)
        parts = [[_Batch(r, c, False) for r, c in tiles]
                 for tiles in self._part_tiles(total,
                                               node.num_partitions)]
        trace = None
        ptraces = None
        if 0 < total <= _TRACE_MAX_ROWS:
            vals = node.start + np.arange(total, dtype=np.int64) * step
            if node.num_partitions == 1:
                trace = _Trace({node.attr.expr_id: (vals, None)},
                               np.ones(total, bool))
            else:
                ptraces = []
                for q in range(node.num_partitions):
                    lo = min(q * per, total)
                    hi = min(lo + per, total)
                    ptraces.append(_Trace(
                        {node.attr.expr_id: (vals[lo:hi], None)},
                        np.ones(hi - lo, bool)))
        flow = _Flow(parts, trace, ptraces=ptraces)
        self._stage(node, Counter(), flow.total_batches, [])
        return flow

    # -- compute -----------------------------------------------------------
    @staticmethod
    def _compute_trivial(node) -> bool:
        return not node.filters and all(
            isinstance(o, AttributeReference) for o in node.outputs)

    @staticmethod
    def _passthrough_runs(outputs, child_attrs,
                          b_ingest) -> "bool | frozenset":
        """RunInfo survival through a pipeline: expr ids of OUTPUTS that
        are pass-through attribute references (or aliases of one) whose
        source column carries run metadata on the input tile — the
        runtime attaches the same RunInfo object to those output columns
        (physical/compile.ExprPipeline), so sorted-run (ragg) aggregation
        stays reachable on filter/project→agg chains."""
        if not b_ingest:
            return False
        src = {a.expr_id for a in child_attrs} if b_ingest is True \
            else set(b_ingest)
        out = set()
        for o in outputs:
            if isinstance(o, AttributeReference) and o.expr_id in src:
                out.add(o.expr_id)
            elif isinstance(o, Alias) \
                    and isinstance(o.child, AttributeReference) \
                    and o.child.expr_id in src:
                out.add(o.expr_id)
        return frozenset(out) if out else False

    def _project_trace(self, trace, filters, outputs) -> Optional[_Trace]:
        if trace is None:
            return None
        live = trace.live.copy()
        for f in filters:
            m = _eval_filter(f, trace)
            if m is None:
                return None
            live &= m
        cols = {}
        domains = {}
        for o in outputs:
            if isinstance(o, AttributeReference):
                if o.expr_id in trace.cols:
                    cols[o.expr_id] = trace.cols[o.expr_id]
                if o.expr_id in trace.dict_domains:
                    domains[o.expr_id] = trace.dict_domains[o.expr_id]
            elif isinstance(o, Alias) and isinstance(o.child,
                                                     AttributeReference):
                if o.child.expr_id in trace.cols:
                    cols[o.expr_id] = trace.cols[o.child.expr_id]
                if o.child.expr_id in trace.dict_domains:
                    domains[o.expr_id] = trace.dict_domains[o.child.expr_id]
        return _Trace(cols, live, trace.consecutive, domains,
                      trace.dict_derivable)

    def _project_ptraces(self, child: _Flow, filters, outputs):
        if child.ptraces is None:
            return None
        return [None if t is None
                else self._project_trace(t, filters, outputs)
                for t in child.ptraces]

    def _compute(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        if self._compute_trivial(node):
            trace = self._project_trace(child.trace, [], node.outputs)
            flow = _Flow(child.parts, trace, counted=child.counted,
                         ptraces=self._project_ptraces(child, [],
                                                       node.outputs))
            self._stage(node, kinds, child.total_batches
                        if child.counted else None,
                        ["pure column selection: shares child arrays, "
                         "zero launches"])
            return flow
        if child.counted:
            kinds["pipeline"] = child.total_batches
        else:
            self._approx(f"pipeline launches of {node.simple_string()[:60]} "
                         "depend on an unknown upstream batch count")
        parts = [[_Batch(b.rows, b.cap, False,
                         ingest=self._passthrough_runs(
                             node.outputs, node.child.output, b.ingest))
                  for b in p]
                 for p in child.parts]
        trace = self._project_trace(child.trace, node.filters, node.outputs)
        flow = _Flow(parts, trace, counted=child.counted,
                     ptraces=self._project_ptraces(child, node.filters,
                                                   node.outputs))
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, [])
        return flow

    # -- aggregation -------------------------------------------------------
    def _key_group_info(self, trace, key_id):
        """(sorted unique valid key values, any-null-keys-live) or None."""
        if trace is None:
            return None
        ent = trace.cols.get(key_id)
        if ent is None:
            return None
        vals, valid = ent
        m = trace.live if valid is None else (trace.live & valid)
        nulls_live = bool(valid is not None and (trace.live & ~valid).any())
        return np.unique(vals[m]), nulls_live

    @staticmethod
    def _agg_out_trace(key_id, uniq, nulls_live) -> _Trace:
        """Aggregate output key trace: live groups in kernel order —
        valid keys ascending (dense iota scatter and sorted-segment both
        emit them sorted), the null-key group last when present."""
        if nulls_live:
            vals = np.append(uniq, 0)
            valid = np.append(np.ones(len(uniq), bool), False)
        else:
            vals, valid = uniq, None
        return _Trace({key_id: (vals, valid)}, np.ones(len(vals), bool))

    def _agg_chunk_kinds(self, node, batches, trace, kinds: Counter,
                         notes: list):
        """Mirror HashAggregateExec._aggregate_chunk over one partition's
        batch list: concat (no launch) + one aggregation kernel, with the
        dense-range scalar probe when the decision is neither memoized
        (stable scan arrays) nor pre-seeded (shuffle-read tiles carry
        map-side stats). Returns the chunk's (output _Batch, output key
        _Trace|None) so downstream stages keep predicting exactly."""
        vals = node._plan_values()
        has_pc = any(op in ("percentile", "collect") for op, _, _ in vals)
        caps = [b.cap for b in batches]
        cap = bucket_capacity(sum(caps)) if all(
            c is not None for c in caps) and caps else None

        if not node.grouping:
            kinds["uagg"] += 1
            for op, _, _ in vals:
                if op == "percentile":
                    kinds["uperc"] += 1
            return _Batch(1, 8, False), None

        if self._encoding and not has_pc and len(node.grouping) == 1 \
                and isinstance(node.grouping[0].dtype, StringType):
            return self._dict_agg_chunk(node, batches, trace, cap, kinds,
                                        notes)

        single_int_key = len(node.grouping) == 1 and isinstance(
            node.grouping[0].dtype, (IntegralType, DateType))
        kid = node.grouping[0].expr_id if single_int_key else None
        probe = len(batches) > 1 or any(not b.probe_free_for(kid)
                                        for b in batches)
        dense = False
        ginfo = None
        span = None
        if single_int_key and not has_pc:
            kinds["krange3"] += 1 if probe else 0
            if not probe:
                if any(b.seeded for b in batches):
                    notes.append("dense-range scalars pre-seeded from "
                                 "map-side shuffle stats — no krange3 "
                                 "probe even on fresh arrays")
                else:
                    notes.append("dense-range scalars memoized on stable "
                                 "scan arrays — no krange3 probe per run")
            ginfo = self._key_group_info(trace, node.grouping[0].expr_id)
            if ginfo is not None and cap is not None:
                uniq, _nulls = ginfo
                if uniq.size:
                    span = int(uniq.max()) - int(uniq.min()) + 1
                    dense = span + 1 <= min(4 * cap, _DENSE_AGG_LIMIT)
            else:
                self._approx("dense-scatter vs sorted-segment aggregation "
                             f"over {node.grouping[0].name} is decided by "
                             "the runtime key span (untraced)")
            self._hazard(
                f"aggregate on {node.grouping[0].name}: the dense-scatter "
                "kernel's output capacity derives from the DATA's key span "
                "— span drift across batches recompiles (value-dependent "
                "cache key)")
        if dense:
            kinds["dagg"] += 1
        elif self._ragg_applies(batches, trace, single_int_key, has_pc,
                                node.grouping[0].expr_id
                                if single_int_key else None):
            kinds["ragg"] += 1
            notes.append("sorted-run RLE fast path: ingest RunInfo says "
                         "the key is already sorted — segment reduce per "
                         "run boundary, no grouping sort")
        else:
            kinds["gagg"] += 1
        for op, _, _ in vals:
            if op == "percentile":
                kinds["gperc"] += 1
        if has_pc:
            self._sync("percentile/collect aggregates build results "
                       "host-side (per-group host loop)")
        # output layout: exact only for the traced single-int-key case
        if single_int_key and not has_pc and ginfo is not None \
                and cap is not None:
            uniq, nulls_live = ginfo
            rows = int(uniq.size) + (1 if nulls_live else 0)
            out_cap = bucket_capacity(span + 1) if dense else cap
            return (_Batch(rows, out_cap, False),
                    self._agg_out_trace(node.grouping[0].expr_id, uniq,
                                        nulls_live))
        return _Batch(None, None, False), None

    def _ragg_applies(self, batches, trace, single_int_key: bool,
                      has_pc: bool, kid) -> bool:
        """Mirror of HashAggregateExec._try_run_sorted: the sorted-run
        (RLE) aggregate runs when the dense path declined, the chunk is
        ONE host-ingested tile (concat of several drops RunInfo), the key
        has no validity plane, and its values are non-decreasing over the
        tile's rows (ingest sortedness survives mask-only filters)."""
        if not self._encoding or not single_int_key or has_pc:
            return False
        from ..columnar.encoding import runs_harvest_enabled

        if not runs_harvest_enabled():
            # tiles ingested by this process carry no RunInfo (session
            # started under the decoded oracle) — ragg is unreachable
            return False
        if len(batches) != 1 or not batches[0].runs_for(kid):
            return False
        b = batches[0]
        if trace is None or b.rows is None or b.rows != len(trace.live):
            return False
        ent = trace.cols.get(kid)
        if ent is None:
            return False
        vals, valid = ent
        if valid is not None or vals.dtype == object:
            return False
        n = b.rows
        return bool(n > 0 and (np.diff(vals[:n]) >= 0).all())

    def _dict_agg_chunk(self, node, batches, trace, cap, kinds: Counter,
                        notes: list):
        """Single dictionary-encoded (string) grouping key: the int32
        codes ARE a dense group domain [0, len(dict)) and the runtime
        decides dense-on-codes from len(dictionary) HOST-SIDE — no
        krange3 probe ever (compressed execution). The model needs the
        dictionary cardinality (traced domain) only for the dense-fit
        check and the output layout."""
        kid = node.grouping[0].expr_id
        name = node.grouping[0].name
        dom = self._chunk_dict_domain(trace, batches, kid)
        if dom is None:
            self._approx(f"dense-on-codes aggregation over {name}: "
                         "dictionary cardinality untraced")
            dense = True  # the overwhelmingly common runtime outcome
        elif cap is None:
            # tile capacities always bucket to >= _EMPTY_CAP, so a small
            # dictionary fits the dense table regardless of the actual
            # (unknown) capacity — the decision stays EXACT
            if len(dom) + 1 <= 4 * _EMPTY_CAP:
                dense = True
            else:
                self._approx(f"dense-on-codes fit for {name} needs tile "
                             "capacities (unknown)")
                dense = True
        else:
            dense = len(dom) + 1 <= min(4 * cap, _DENSE_AGG_LIMIT)
        kinds["dagg" if dense else "gagg"] += 1
        note = ("dictionary-encoded grouping key: codes are a dense "
                "group domain — len(dictionary) decides host-side, no "
                "krange3 probe")
        if note not in notes:
            notes.append(note)
        self._hazard(
            f"aggregate on {name}: the dense-on-codes kernel's output "
            "capacity derives from the dictionary cardinality — "
            "dictionary growth across batches recompiles "
            "(value-dependent cache key)")
        if dom is None or not dense:
            return _Batch(None, None, False), None
        out_cap = bucket_capacity(len(dom) + 1)
        ent = trace.cols.get(kid)
        if ent is None:
            # cardinality known (explicit domain) but row values are not
            # traced: the layout stays unknown while the DOMAIN still
            # propagates — a downstream final aggregate can keep
            # deciding dense-on-codes exactly
            return (_Batch(None, out_cap, False),
                    _Trace({}, np.zeros(0, bool), True, {kid: dom},
                           False))
        vals, valid = ent
        m = trace.live if valid is None else (trace.live & valid)
        live_set = set(vals[m])
        live_vals = [v for v in dom if v in live_set]
        nulls_live = bool(valid is not None
                          and (trace.live & ~valid).any())
        rows = len(live_vals) + (1 if nulls_live else 0)
        ovals = np.empty(rows, dtype=object)
        ovals[: len(live_vals)] = live_vals
        ovalid = None
        if nulls_live:
            ovals[-1] = ""
            ovalid = np.append(np.ones(len(live_vals), bool), False)
        out_trace = _Trace({kid: (ovals, ovalid)}, np.ones(rows, bool),
                           True, {kid: dom}, False)
        return _Batch(rows, out_cap, False), out_trace

    def _merge_group_traces(self, traces: list) -> Optional[_Trace]:
        """Concatenate compacted per-partition traces (coalesced groups:
        partition batch lists concatenate in order)."""
        if any(t is None for t in traces):
            return None
        comp = [t.compacted() for t in traces]
        ids = set(comp[0].cols)
        for t in comp[1:]:
            ids &= set(t.cols)
        if not ids:
            return None
        cols = {}
        for k in ids:
            vals = np.concatenate([t.cols[k][0] for t in comp])
            vs = [t.cols[k][1] for t in comp]
            valid = None
            if any(v is not None for v in vs):
                valid = np.concatenate(
                    [np.ones(len(t.live), bool) if v is None else v
                     for t, v in zip(comp, vs)])
            cols[k] = (vals, valid)
        # merged dictionary domains: concat unifies dictionaries in
        # partition order (first-appearance union)
        domains = {}
        dom_ids = set()
        for t in traces:
            dom_ids |= set(t.dict_domains)
        dom_ids |= {k for k in ids if comp[0].cols[k][0].dtype == object}
        for k in dom_ids:
            per = [self._trace_domain(t, k) for t in traces]
            if all(d is not None for d in per):
                domains[k] = self._ordered_union(per)
        n = sum(len(t.live) for t in comp)
        return _Trace(cols, np.ones(n, bool),
                      all(t.consecutive for t in traces),
                      domains, False)

    def _agg(self, node) -> _Flow:
        from ..physical.adaptive import plan_merge_groups, _row_width
        from ..physical.exchange import ShuffleExchangeExec

        child = self.visit(node.child)
        parts = child.parts
        ptraces = [child.part_trace(i) for i in range(len(parts))]
        notes = []
        if node.mode == "final" and isinstance(node.child,
                                               ShuffleExchangeExec) \
                and len(parts) > 1 \
                and self.conf.get(ADAPTIVE_ENABLED) \
                and self.conf.get(COALESCE_PARTITIONS_ENABLED):
            sizes = [sum(b.rows for b in p) if all(b.rows is not None
                                                   for b in p) else None
                     for p in parts]
            if all(s is not None for s in sizes):
                # exact mirror of adaptive.coalesce_after_exchange: the
                # exchange value model knows per-reducer rows, so the
                # merge plan is deterministic
                if sum(sizes) == 0:
                    groups = [list(range(len(parts)))]
                else:
                    advisory = int(self.conf.get(ADVISORY_PARTITION_BYTES)) \
                        // _row_width(node.child.output)
                    groups = plan_merge_groups(sizes, advisory)
                if len(groups) != len(parts):
                    parts = [[b for i in g for b in parts[i]]
                             for g in groups]
                    ptraces = [self._merge_group_traces(
                        [ptraces[i] for i in g]) for g in groups]
                    notes.append(f"AQE coalescing merges reducer outputs "
                                 f"into {len(parts)} partition(s) "
                                 "(exact: reducer rows traced)")
            else:
                # AQE coalescing merges undersized reducer outputs;
                # assume one merged group (row-count dependent)
                parts = [[b for p in parts for b in p]]
                ptraces = [None]
                notes.append("AQE coalescing assumed to merge all reducer "
                             "outputs into one partition")
                self._approx("AQE partition coalescing before the final "
                             "aggregate depends on runtime row counts")
        kinds = Counter()
        max_rows = int(self.conf.get(AGG_BLOCK_ROWS))
        out_parts, out_traces = [], []
        for p, pt in zip(parts, ptraces):
            caps = [b.cap for b in p]
            known = all(c is not None for c in caps)
            blockwise = known and len(p) > 1 and sum(caps) > max_rows \
                and node.grouping and all(s.mergeable for s in node.specs)
            if not known and not child.counted:
                self._approx("aggregate chunking depends on unknown "
                             "upstream batch sizes")
            if blockwise:
                # fold in blockRows-bounded chunks, then merge partials
                chunk, acc, cs = [], 0, 0
                for b in p:
                    chunk.append(b)
                    cs += b.cap
                    if cs >= max_rows:
                        self._agg_chunk_kinds(node, chunk, pt, kinds,
                                              notes)
                        chunk, cs = [], 0
                        acc += 1
                if chunk:
                    self._agg_chunk_kinds(node, chunk, pt, kinds, notes)
                    acc += 1
                merged = [_Batch(None, None, False)] * acc
                self._agg_chunk_kinds(node, merged, None, kinds, notes)
                notes.append(f"blockwise fold: {acc} chunks + merge")
                out_parts.append([_Batch(None, None, False)])
                out_traces.append(None)
            else:
                ob, ot = self._agg_chunk_kinds(node, p, pt, kinds, notes)
                out_parts.append([ob])
                out_traces.append(ot)
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, notes)
        return _Flow(out_parts, None, counted=child.counted,
                     ptraces=out_traces)

    def _fused_agg(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        notes = []
        single_int_key = len(node.grouping) == 1 and isinstance(
            node.grouping[0].dtype, (IntegralType, DateType))
        single_dict_key = self._encoding and len(node.grouping) == 1 \
            and isinstance(node.grouping[0].dtype, StringType)
        key_passthrough = single_int_key and any(
            isinstance(o, AttributeReference)
            and o.expr_id == node.grouping[0].expr_id
            for o in node.pipe_outputs)
        out_parts, out_traces = [], []
        for i, p in enumerate(child.parts):
            in_trace = child.part_trace(i)
            pipe_trace = self._project_trace(in_trace, node.filters,
                                             node.pipe_outputs)
            # the fused dense decision reads the memoized/seeded range of
            # the INPUT column — a PRE-filter superset (fusion.py
            # _dense_decision) — while the unfused gate branch probes the
            # materialized post-filter pipeline output
            pre_trace = self._project_trace(in_trace, [],
                                            node.pipe_outputs)
            key_span = None
            if single_int_key and pre_trace is not None:
                st = pre_trace.stats(node.grouping[0].expr_id)
                if st is not None and st.size:
                    key_span = int(st.max()) - int(st.min()) + 1
            caps = [b.cap for b in p]
            known = all(c is not None for c in caps)
            if not known:
                self._approx("fusion minRows gate undecidable: unknown "
                             "partition tile capacities")
                known_sum = None
            else:
                known_sum = sum(caps)
            if known_sum is not None and known_sum < self._min_rows:
                # runtime size gate: unfused operator-at-a-time kernels
                # (the materialized pipeline outputs keep pass-through
                # RunInfo, so ragg stays reachable behind the gate)
                kinds["pipeline"] += len(p)
                ob, ot = self._agg_chunk_kinds(node, [
                    _Batch(b.rows, b.cap, False,
                           ingest=self._passthrough_runs(
                               node.pipe_outputs, node.child.output,
                               b.ingest))
                    for b in p],
                    pipe_trace, kinds, notes)
                notes.append(
                    f"partition under spark.tpu.fusion.minRows="
                    f"{self._min_rows}: shared unfused kernels at runtime")
                out_parts.append([ob])
                out_traces.append(ot)
                continue
            kinds["fused_agg"] += len(p)
            if key_passthrough and self._dense_keys:
                kid = node.grouping[0].expr_id
                fresh_in = sum(1 for b in p if not b.probe_free_for(kid))
                kinds["krange3"] += fresh_in
                if fresh_in == 0:
                    notes.append("dense-range decision memoized/seeded per "
                                 "input column (no per-run host sync)")
            if single_dict_key and self._dense_keys:
                note = ("dictionary-encoded grouping key: dense-on-codes "
                        "decided in-kernel from the host-pass dictionary "
                        "— no krange3 probe")
                if note not in notes:
                    notes.append(note)
            dense = key_passthrough and self._dense_keys \
                and key_span is not None \
                and all(c is not None for c in caps) and caps \
                and key_span + 1 <= min(4 * min(caps), _DENSE_AGG_LIMIT)
            # per-batch dictionary domains (slice-derived or explicit):
            # the fused dense-on-codes variant keys its output capacity
            # on len(batch dictionary)
            dict_doms = None
            if single_dict_key and pipe_trace is not None \
                    and all(b.rows is not None for b in p):
                kid = node.grouping[0].expr_id
                dict_doms, r0 = [], 0
                for b in p:
                    dict_doms.append(self._trace_domain(
                        pipe_trace, kid, r0, r0 + b.rows))
                    r0 += b.rows
                if r0 != len(pipe_trace.live) \
                        or any(d is None for d in dict_doms):
                    dict_doms = None
            if len(p) > 1:
                # per-batch partials merge with final-mode ops; the partial
                # output capacity mirrors the fused kernel variant
                pcaps = []
                for bi, b in enumerate(p):
                    if not node.grouping:
                        pcaps.append(8)
                    elif key_passthrough and self._dense_keys \
                            and key_span is not None and b.cap is not None \
                            and key_span + 1 <= min(4 * b.cap,
                                                    _DENSE_AGG_LIMIT):
                        pcaps.append(bucket_capacity(key_span + 1))
                    elif single_dict_key and self._dense_keys \
                            and dict_doms is not None \
                            and b.cap is not None \
                            and len(dict_doms[bi]) + 1 <= min(
                                4 * b.cap, _DENSE_AGG_LIMIT):
                        pcaps.append(
                            bucket_capacity(len(dict_doms[bi]) + 1))
                    else:
                        pcaps.append(b.cap)
                merge = HashAggMergeProxy(node)
                merge_trace = pipe_trace
                if single_dict_key and pipe_trace is not None:
                    # the merged partials' dictionary is the union of the
                    # per-batch dictionaries = the partition-wide domain
                    kid = node.grouping[0].expr_id
                    dom_p = self._trace_domain(pipe_trace, kid)
                    if dom_p is not None:
                        merge_trace = _Trace(
                            pipe_trace.cols, pipe_trace.live,
                            pipe_trace.consecutive,
                            {**pipe_trace.dict_domains, kid: dom_p},
                            False)
                ob, ot = self._agg_chunk_kinds(
                    merge, [_Batch(None, c, False) for c in pcaps],
                    merge_trace, kinds, notes)
                notes.append(f"{len(p)} per-batch partials merge with "
                             "final-mode ops")
                out_parts.append([ob])
                out_traces.append(ot)
                continue
            # single fused batch: the kernel output IS the partition output
            if not node.grouping:
                out_parts.append([_Batch(1, 8, False)])
                out_traces.append(None)
                continue
            if single_dict_key:
                kid = node.grouping[0].expr_id
                dom = dict_doms[0] if dict_doms else None
                dense_d = self._dense_keys and dom is not None \
                    and caps and caps[0] is not None \
                    and len(dom) + 1 <= min(4 * caps[0], _DENSE_AGG_LIMIT)
                if dense_d:
                    fake = Counter()
                    ob, ot = self._dict_agg_chunk(
                        node, p, _Trace(
                            pipe_trace.cols, pipe_trace.live,
                            pipe_trace.consecutive,
                            {**pipe_trace.dict_domains, kid: dom}, False),
                        caps[0], fake, [])
                    out_parts.append([ob])
                    out_traces.append(ot)
                else:
                    out_parts.append([_Batch(None, None, False)])
                    out_traces.append(None)
                continue
            ginfo = self._key_group_info(pipe_trace,
                                         node.grouping[0].expr_id) \
                if single_int_key else None
            if ginfo is not None and caps and caps[0] is not None:
                uniq, nulls_live = ginfo
                rows = int(uniq.size) + (1 if nulls_live else 0)
                out_cap = bucket_capacity(key_span + 1) if dense \
                    else caps[0]
                out_parts.append([_Batch(rows, out_cap, False)])
                out_traces.append(self._agg_out_trace(
                    node.grouping[0].expr_id, uniq, nulls_live))
            else:
                out_parts.append([_Batch(None, None, False)])
                out_traces.append(None)
        self._stage(node, kinds, child.total_batches if child.counted
                    else None,
                    ["FUSED stage: filter/project traced into the partial-"
                     "aggregate kernel — 1 launch/batch"] + notes)
        return _Flow(out_parts, None, counted=child.counted,
                     ptraces=out_traces)

    # -- limit / sort ------------------------------------------------------
    def _limit(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        out_parts = []
        for p in child.parts:
            if p:
                kinds["limit"] += 1
                out_parts.append([_Batch(min(node.n, self._tile), None,
                                         False)])
            else:
                out_parts.append([])
        self._sync("LimitExec compaction host-syncs the live-row count "
                   "per partition")
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, [])
        return _Flow(out_parts, None, counted=child.counted)

    def _fused_limit(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        notes = []
        out_parts = []
        for p in child.parts:
            if not p:
                out_parts.append([])
                continue
            caps = [b.cap for b in p]
            known = all(c is not None for c in caps)
            if known and sum(caps) < self._min_rows:
                kinds["pipeline"] += len(p)
                kinds["limit"] += 1
                notes.append("partition under spark.tpu.fusion.minRows: "
                             "shared unfused kernels at runtime")
            else:
                if not known:
                    self._approx("fusion minRows gate undecidable for "
                                 "FusedLimit (unknown capacities)")
                kinds["fused_limit"] += 1
            out_parts.append([_Batch(min(node.n, self._tile), None, False)])
        self._stage(node, kinds, child.total_batches if child.counted
                    else None,
                    ["FUSED stage: pipeline traced into the limit kernel — "
                     "1 launch per partition (batches concatenate)"] + notes)
        return _Flow(out_parts, None, counted=child.counted)

    def _sort(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        notes = []
        budget = self._sort_budget(node)
        out_parts = []
        for p in child.parts:
            if not p:
                out_parts.append([])
                continue
            caps = [b.cap for b in p]
            known = all(c is not None for c in caps)
            if known and budget is not None and sum(caps) > budget:
                self._approx("external range-bucketed sort: bucket count "
                             "and per-bucket kernels are data-dependent")
                self._hazard("external sort cache keys embed the bucket "
                             "count B (data-dependent) — skewed inputs "
                             "recompile the pid kernel per B")
                notes.append("over device budget: external multi-pass sort")
                out_parts.append([_Batch(None, None, False)])
                continue
            if not known and not child.counted:
                self._approx("sort budget check over unknown capacities")
            kinds["sort"] += 1
            out_parts.append([_Batch(None, None, False)])
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, notes)
        return _Flow(out_parts, None, counted=child.counted)

    def _sort_budget(self, node):
        try:
            from ..exec.memory import MemoryManager

            mm = MemoryManager(self.conf)
            from ..physical.operators import attrs_schema

            return mm.tile_rows(attrs_schema(node.child.output),
                                amplification=3)
        except Exception:
            return None

    # -- joins -------------------------------------------------------------
    def _join(self, node) -> _Flow:
        from ..physical.exchange import ShuffleExchangeExec

        left = self.visit(node.left)
        right = self.visit(node.right)
        kinds = Counter()
        notes = []
        if node.is_broadcast:
            pairs = [(lp, right.parts[0] if right.parts else [])
                     for lp in left.parts]
        else:
            if isinstance(node.left, ShuffleExchangeExec) or isinstance(
                    node.right, ShuffleExchangeExec):
                self._approx("shuffled join: AQE coalescing/skew splitting "
                             "reshape partitions at runtime")
            if len(left.parts) != len(right.parts):
                self._approx("join partition pairing unknown")
                pairs = []
            else:
                pairs = list(zip(left.parts, right.parts))

        rf_on = bool(self.conf.get(MINMAX_JOIN_FILTER)) \
            or bool(self.conf.get(BLOOM_JOIN_FILTER))
        fused = node.probe_fusion is not None and not (
            node.join_type == "full_outer" or rf_on)
        if node.probe_fusion is not None and not fused:
            notes.append("fused probe pipeline materialized up-front "
                         "(full_outer / runtime-filter path reads probe "
                         "keys outside the kernel)")
        if rf_on:
            self._approx("runtime join filters add data-dependent "
                         "filter/compaction kernels")

        single_int_bkey = len(node.right_keys) == 1 and isinstance(
            node.right_keys[0].dtype, (IntegralType, DateType))
        # string build keys: the dense-build fast paths stay int-only,
        # but the MATCH-CARDINALITY trace (probe-capacity retries) works
        # on raw values regardless of type
        single_str_bkey = len(node.right_keys) == 1 and isinstance(
            node.right_keys[0].dtype, StringType)

        # per-pair traces: post-exchange flows carry per-partition traces
        # (mesh/host shuffled layouts), so the probe AND build value
        # models hold through multi-partition joins too
        pair_traces = [left.part_trace(i) for i in range(len(pairs))]
        if fused:
            filters, outputs = node.probe_fusion
            pair_traces = [None if t is None
                           else self._project_trace(t, filters, outputs)
                           for t in pair_traces]
        build_traces = [right.part_trace(0 if node.is_broadcast else i)
                        for i in range(len(pairs))]

        # adaptive runtime join filters (physical/adaptive.py): once the
        # build side materializes, its key domain prunes rows — and whole
        # batches — inside the not-yet-run probe shuffle. Stage run
        # order, dense-range memo hits, and the fusion size gate are all
        # run-dependent, so an ELIGIBLE pattern degrades the launch model
        # honestly; ineligible shapes (broadcast, outer joins, composite
        # or non-integral/string keys, no shuffled probe) are evaluated
        # host-side and stay exact.
        adaptive_rf = (bool(self.conf.get(ADAPTIVE_RUNTIME_FILTER))
                       and not node.is_broadcast
                       and node.join_type in ("inner", "left_semi")
                       and len(node.left_keys) == 1
                       and isinstance(node.left, ShuffleExchangeExec)
                       and isinstance(node.left_keys[0].dtype,
                                      (IntegralType, DateType, StringType)))
        if adaptive_rf:
            self._approx(
                "adaptive runtime join filter: the materialized build "
                "side's key domain prunes probe-shuffle rows/batches at "
                "runtime (spark.tpu.adaptive.runtimeFilter)")
            bt = build_traces[0] if build_traces else None
            bvals = bt.stats(node.right_keys[0].expr_id) \
                if bt is not None else None
            if bvals is not None and bvals.size and isinstance(
                    node.right_keys[0].dtype, (IntegralType, DateType)):
                # the value model CAN evaluate the build domain host-side
                # — surface the evaluated filter in the report
                notes.append(
                    "runtime-filter build domain evaluated host-side: "
                    f"[{int(bvals.min())}, {int(bvals.max())}]")

        out_parts = []
        out_traces = []
        for pi, (lp, rp) in enumerate(pairs):
            probe_trace = pair_traces[pi]
            bstats = build_traces[pi].stats(node.right_keys[0].expr_id) \
                if (build_traces[pi] is not None
                    and (single_int_bkey or single_str_bkey)) \
                else None
            bcaps = [b.cap for b in rp]
            bknown = all(c is not None for c in bcaps) and rp
            bcap = bucket_capacity(sum(bcaps)) if bknown else None
            bkid = node.right_keys[0].expr_id if single_int_bkey else None
            bfresh = (len(rp) != 1) or any(not b.probe_free_for(bkid)
                                           for b in rp)
            grace = False
            if bknown:
                budget = self._join_budget(node)
                if budget is not None and sum(bcaps) > budget:
                    grace = True
            lcaps = [b.cap for b in lp]
            if bknown and not grace and len(pairs) == 1 \
                    and all(c is not None for c in lcaps) \
                    and node.builds_the_larger_side([lcaps], [bcaps]):
                # the roles turn around at run time and the kernels
                # below are the twin's, a probe a tile of the planned
                # build side
                self._approx(
                    "inner join builds its smaller (left) side at run "
                    "time: the launches are the twin join's")
                notes.append("left side a quarter of the right's slots "
                             "or fewer: built on the left at run time")
            if grace:
                self._approx("grace hash join fragments both sides by key "
                             "hash — fragment kernels are data-dependent")
                notes.append("build side over device budget: grace join")
                out_parts.append([_Batch(None, None, False)])
                out_traces.append(None)
                continue
            pair_fused = fused
            if pair_fused:
                pcaps = [b.cap for b in lp]
                if lp and all(c is not None for c in pcaps) \
                        and sum(pcaps) < self._min_rows:
                    # runtime size gate: pipeline materializes up front
                    kinds["pipeline"] += len(lp)
                    pair_fused = False
                    notes.append("probe partition under spark.tpu.fusion."
                                 f"minRows={self._min_rows}: pipeline + "
                                 "shared probe kernels at runtime")
            dense = False
            if single_int_bkey:
                kinds["krange3"] += 1 if bfresh else 0
                if bstats is not None and bcap is not None:
                    span = (int(bstats.max()) - int(bstats.min()) + 1) \
                        if bstats.size else None
                    if span is not None and \
                            span <= min(8 * bcap, _DENSE_JOIN_LIMIT):
                        kinds["djoin_build"] += 1
                        dense = np.unique(bstats).size == bstats.size
                else:
                    self._approx(
                        f"dense vs sorted join build on "
                        f"{node.right_keys[0].name}: key span/uniqueness "
                        "untraced")
                    kinds["djoin_build"] += 1
                self._hazard(
                    f"join build on {node.right_keys[0].name}: dense table "
                    "capacity derives from the key span (value-dependent "
                    "cache key); duplicate keys fall back to sorted probe "
                    "at runtime")
                self._sync("dense join-build verdict is one memoized device "
                           "scalar per build column identity")
            if dense:
                kind = "fused_djoin_probe" if pair_fused else "djoin_probe"
                kinds[kind] += len(lp) if lp else 1
            else:
                kinds["join_build"] += 1
                kind = "fused_probe" if pair_fused else "join_probe"
                launches = 0
                batches = lp if lp else [_Batch(0, _EMPTY_CAP, True)]
                counts = self._build_key_counts(bstats)
                row0 = 0
                for b in batches:
                    launches += 1
                    launches += self._probe_retries(
                        node, b, row0, probe_trace, counts)
                    row0 += b.rows if b.rows is not None else (b.cap or 0)
                kinds[kind] += launches
            if node.join_type == "full_outer":
                notes.append("full_outer unmatched-build pass runs EAGER "
                             "device ops (uncached, uncounted dispatches)")
                self._hazard("full_outer unmatched-build pass bypasses the "
                             "KernelCache (eager per-run dispatches)")
            ob, ot = self._join_output(node, lp, dense, bstats,
                                       probe_trace, build_traces[pi])
            out_parts.append(ob)
            out_traces.append(ot)
        self._stage(node, kinds, left.total_batches if left.counted
                    else None, notes)
        return _Flow(out_parts, None,
                     counted=left.counted and right.counted,
                     ptraces=out_traces)

    def _join_output(self, node, lp, dense, bstats, probe_trace,
                     build_trace=None):
        """Per-pair output layout + value trace through the join. Exact
        for the dense inner case (unique integral build keys: the probe is
        a 1:1 gather in probe-row order); everything else keeps the
        unknown layout the earlier model reported. Dictionary domains of
        build-side string columns ride the output trace (the gather keeps
        the FULL build dictionary), so downstream dense-on-codes
        aggregates keep deciding exactly."""
        # dictionary domains are LAYOUT-independent: the join gathers
        # keep the probe batch's dictionary on probe columns and the
        # FULL build dictionary on build columns, whatever the match
        # cardinality — so they propagate even when the row layout is
        # unknown (downstream dense-on-codes aggregates keep deciding)
        domains = {}
        if probe_trace is not None:
            for k in probe_trace.cols:
                dom = self._trace_domain(probe_trace, k)
                if dom is not None:
                    domains[k] = dom
            for k, dom in probe_trace.dict_domains.items():
                domains.setdefault(k, dom)
        if build_trace is not None:
            for a in node.right.output:
                if isinstance(a.dtype, StringType):
                    dom = self._trace_domain(build_trace, a.expr_id)
                    if dom is not None:
                        domains[a.expr_id] = dom
        dom_trace = _Trace({}, np.zeros(0, bool), True, domains, False) \
            if domains else None
        nb = max(len(lp), 1) + (1 if node.join_type == "full_outer" else 0)
        unknown = ([_Batch(None, None, False) for _ in range(nb)],
                   dom_trace)
        if not (dense and node.join_type == "inner" and lp
                and probe_trace is not None and probe_trace.consecutive
                and bstats is not None and len(node.left_keys) == 1):
            return unknown
        ent = probe_trace.cols.get(node.left_keys[0].expr_id)
        if ent is None:
            return unknown
        vals, valid = ent
        bvals = np.unique(bstats)
        live = probe_trace.live if valid is None \
            else (probe_trace.live & valid)
        matched_mask = live & np.isin(vals, bvals)
        out_batches = []
        row0 = 0
        ok = True
        for b in lp:
            width = b.rows if b.rows is not None else b.cap
            if width is None or b.cap is None:
                ok = False
                break
            lo, hi = row0, min(row0 + width, len(vals))
            out_batches.append(
                _Batch(int(matched_mask[lo:hi].sum()), b.cap, False))
            row0 += width
        if not ok:
            return unknown
        # probe-side columns pass through row-for-row where matched
        sel = np.nonzero(matched_mask)[0]
        cols = {k: (v[sel], None if vv is None else vv[sel])
                for k, (v, vv) in probe_trace.cols.items()}
        return (out_batches,
                _Trace(cols, np.ones(len(sel), bool), True, domains,
                       False))

    def _build_key_counts(self, bstats):
        if bstats is None or bstats.size == 0:
            return None
        vals, counts = np.unique(bstats, return_counts=True)
        return vals, counts

    def _probe_retries(self, node, batch, row0, probe_trace, counts) -> int:
        """Capacity-retry launches for one sorted-probe batch: the kernel
        re-runs with a doubled output bucket when matched pairs overflow
        max(probe_cap, 1024)."""
        if node.join_type != "inner":
            # outer/semi needed-row semantics differ; only retry-predict
            # the inner case, flag the rest
            self._approx(f"{node.join_type} sorted-probe output capacity "
                         "is data-dependent (retry count untraced)")
            return 0
        if batch.cap is None:
            self._approx("sorted-probe retry check needs the probe batch "
                         "capacity (unknown)")
            return 0
        out_cap = max(batch.cap, 1 << 10)
        if counts is None or probe_trace is None \
                or not probe_trace.consecutive:
            self._approx("sorted-probe join expansion untraced: capacity "
                         "retries unpredictable")
            self._hazard("sorted-probe kernels re-launch with doubled "
                         "output capacity on overflow (value-dependent "
                         "cache key + extra dispatches)")
            return 0
        pk = node.left_keys[0] if len(node.left_keys) == 1 else None
        if pk is None:
            self._approx("multi-key sorted-probe expansion untraced")
            return 0
        ent = probe_trace.cols.get(pk.expr_id)
        if ent is None:
            self._approx(f"probe key {pk.name} untraced: sorted-probe "
                         "retries unpredictable")
            return 0
        vals, valid = ent
        m = probe_trace.live if valid is None else (probe_trace.live & valid)
        width = batch.rows if batch.rows is not None else (batch.cap or 0)
        lo, hi = row0, min(row0 + width, len(vals))
        bvals = vals[lo:hi][m[lo:hi]]
        cvals, ccounts = counts
        idx = np.searchsorted(cvals, bvals)
        idx = np.clip(idx, 0, len(cvals) - 1)
        matched = cvals[idx] == bvals
        needed = int(ccounts[idx[matched]].sum())
        if needed > out_cap:
            self._hazard("sorted-probe join overflowed its output bucket "
                         f"(needed {needed} > {out_cap}): one retry launch "
                         "with a doubled capacity (value-dependent key)")
            return 1
        return 0

    def _join_budget(self, node):
        try:
            from ..exec.memory import MemoryManager
            from ..physical.operators import attrs_schema

            mm = MemoryManager(self.conf)
            return mm.tile_rows(attrs_schema(node.right.output),
                                amplification=4)
        except Exception:
            return None

    def _nl_join(self, node) -> _Flow:
        left = self.visit(node.left)
        self.visit(node.right)
        kinds = Counter()
        if node.condition is not None and left.counted:
            kinds["pipeline"] = left.total_batches
        elif node.condition is not None:
            self._approx("nested-loop condition pipeline count unknown")
        self._hazard("NestedLoopJoinExec cross-product runs EAGER device "
                     "ops (uncached, uncounted dispatches; output capacity "
                     "is |probe|x|build|)")
        out_parts = [[_Batch(None, None, False)
                      for _ in range(max(len(p), 1)
                                     * (2 if node.join_type == "left_outer"
                                        else 1))]
                     for p in left.parts]
        self._stage(node, kinds, left.total_batches if left.counted
                    else None, [])
        return _Flow(out_parts, None, counted=left.counted)

    # -- exchanges ---------------------------------------------------------
    def _broadcast(self, node) -> _Flow:
        child = self.visit(node.child)
        merged = [b for p in child.parts for b in p]
        if len(merged) == 1:
            out = [merged[0]]
        else:
            caps = [b.cap for b in merged]
            cap = bucket_capacity(sum(caps)) if merged and all(
                c is not None for c in caps) else None
            rows = sum(b.rows for b in merged) if all(
                b.rows is not None for b in merged) else None
            out = [_Batch(rows, cap, False)]
        trace = child.trace
        if trace is None:
            # multi-partition child (e.g. a mesh/host shuffled flow):
            # the replicate concatenates every partition, so the merged
            # value trace is the per-partition traces in order — build
            # sides over broadcast exchange outputs keep their key stats
            ptr = child.all_part_traces()
            if ptr is not None:
                trace = self._merge_group_traces(ptr)
        self._stage(node, Counter(), child.total_batches if child.counted
                    else None, ["no kernels: host-orchestrated replicate"])
        return _Flow([out], trace, counted=child.counted)

    def _mesh_active(self, num_out: int) -> bool:
        if not self.conf.get(MESH_ENABLED):
            return False
        if num_out < 2 or (num_out & (num_out - 1)) != 0:
            return False
        try:
            import jax

            return len(jax.devices()) >= num_out
        except Exception:
            return False

    # -- mesh stage model ---------------------------------------------------
    def _mesh_exchange(self, node, child, p, fused: bool, kinds: Counter,
                       notes: list) -> _Flow:
        """Launch model of the mesh SPMD stage (parallel/mesh_fusion.py):
        ONE sharded dispatch per step for the whole stage, plus one
        re-dispatch per quota-overflow retry. The staging geometry and
        the retry loop mirror mesh_exchange exactly, so when the key
        values trace the prediction is EXACT — retries included."""
        num_out = p.num_partitions
        if self._cluster:
            # a cluster scheduler splits the plan at exchanges: whether
            # this exchange runs a (worker-)local mesh collective or the
            # host shuffle write + fetch depends on stage placement, and
            # reduce tiles rebuilt from MapStatus arrive pre-seeded —
            # launch counts here are placement-dependent, not exact
            self._approx("cluster scheduler: mesh-capable exchange "
                         "placement (local collective vs host shuffle "
                         "write) is a stage-scheduling decision")
            kinds["mesh_stage"] += 1
            notes.append("mesh-capable exchange under a cluster "
                         "scheduler: placement decided at stage build")
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, notes)
            return _Flow([[_Batch(None, None, False, seeded=True)]
                          for _ in range(num_out)], None, counted=False)
        fused_mesh = fused and self._fusion_mesh
        if fused and not fused_mesh:
            if child.counted:
                kinds["pipeline"] += child.total_batches
            else:
                self._approx("mesh pipeline materialization count depends "
                             "on an unknown upstream batch count")
            notes.append("mesh fallback (spark.tpu.fusion.mesh=false): "
                         "the fused map side materializes the pipeline "
                         "per batch before the all-to-all")
        if fused_mesh:
            notes.append("FUSED mesh stage: pipeline + partition ids + "
                         "all-to-all compiled as ONE shard_map program — "
                         "1 sharded dispatch per step, send buffers "
                         "donated (spark.tpu.fusion.minRows does not "
                         "apply: one program per step, not per batch; "
                         "dictionary-encoded keys hash through replicated "
                         "codes→value-hash lut aux planes)")
        else:
            notes.append("mesh SPMD stage: ONE sharded dispatch "
                         "redistributes the staged batches")
        self._hazard("mesh stage cache key embeds the per-pair row quota "
                     "— skewed data recompiles with a doubled quota")
        key_ids = [e.expr_id for e in p.exprs
                   if isinstance(e, AttributeReference)]
        # the stage program accumulates per-reduce-partition min/max for
        # the exchange's stat columns IN-PROGRAM and seeds the
        # dense-range memo at build time (parallel/mesh_exchange.
        # _seed_mesh_stats) — mesh reduce tiles are probe-free for those
        # columns exactly like host-shuffle rebuilt tiles, and the
        # seeded span equals the tile's own rows' span, so the dense
        # decision model below stays exact
        seeded = self._exchange_seeded(node)
        sim = None
        if len(key_ids) == len(p.exprs) and child.counted:
            in_traces = self._exchange_input_traces(node, child, fused)
            if in_traces is not None:
                sim = self._mesh_sim(child, in_traces, key_ids, num_out,
                                     seeded=seeded,
                                     quota_seed=self._mesh_quota_seed(
                                         node, child, fused_mesh,
                                         num_out))
        if sim is None:
            self._approx("mesh stage quota retries are data-dependent "
                         "and the key values are untraced — assuming one "
                         "dispatch, reduce layout unknown")
            kinds["mesh_stage"] += 1
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, notes)
            return _Flow([[_Batch(None, None, False, seeded=seeded)]
                          for _ in range(num_out)], None, counted=True)
        attempts, flow = sim
        kinds["mesh_stage"] += attempts
        if attempts > 1:
            notes.append(f"{attempts - 1} quota "
                         f"retr{'y' if attempts == 2 else 'ies'}: a "
                         "(src,dst) pair overflowed its row quota and "
                         "the stage re-dispatched doubled")
        notes.append("reduce layout EXACT: staged-shard hash simulation "
                     "decides per-reducer rows and the retry count")
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, notes)
        return flow

    def _mesh_sim(self, child: _Flow, traces: list, key_ids: list,
                  num_out: int, seeded: "bool | frozenset" = False,
                  quota_seed: "int | None" = None):
        """Host mirror of the mesh staging + quota-retry loop. Returns
        (attempts, output _Flow) or None when the layout cannot be
        reconstructed. Mirrors parallel/mesh_exchange: batches flatten
        partition-major into a [total_cap] plane, shard s owns data rows
        [s*rows_per_shard, (s+1)*rows_per_shard), pids come from the
        splitmix64 host mirror, and the quota doubles (one extra
        dispatch) while any (src,dst) bucket overflows. `quota_seed`
        mirrors the persistent warm-start manifest: a seeded first
        attempt starts at the prior run's final quota."""
        # the SAME geometry helper the runtime stages with — the mirror
        # cannot drift from the execution layer
        from ..parallel.mesh_fusion import mesh_stage_geometry

        ids = set(traces[0].cols)
        for t in traces[1:]:
            ids &= set(t.cols)
        if any(k not in ids for k in key_ids):
            return None
        # global staged plane: per-batch capacity slots, data rows first
        total_cap = 0
        spans = []  # (trace, r0, rows_b, off, cap)
        for part, t in zip(child.parts, traces):
            r0 = 0
            for b in part:
                if b.cap is None or b.rows is None:
                    return None
                spans.append((t, r0, b.rows, total_cap, b.cap))
                r0 += b.rows
                total_cap += b.cap
            if r0 != len(t.live):
                return None
        if total_cap == 0:
            return None
        live = np.zeros(total_cap, bool)
        gcols = {}
        for k in ids:
            dt = traces[0].cols[k][0].dtype
            has_valid = any(t.cols[k][1] is not None for t in traces)
            base = np.full(total_cap, "", dtype=object) \
                if dt == object else np.zeros(total_cap, dtype=dt)
            gcols[k] = [base,
                        np.zeros(total_cap, bool) if has_valid else None]
        # mesh staging merges every batch's dictionary into ONE global
        # dictionary (parallel/mesh_exchange._stage_payloads) — every
        # reduce partition shares the merged domain
        global_doms = {}
        for k in ids:
            if traces[0].cols[k][0].dtype == object:
                per = [self._trace_domain(t, k) for t in traces]
                if all(d is not None for d in per):
                    global_doms[k] = self._ordered_union(per)
        for t, r0, rows_b, off, _cap in spans:
            sl = slice(r0, r0 + rows_b)
            live[off: off + rows_b] = t.live[sl]
            for k in ids:
                vals, valid = t.cols[k]
                gvals, gvalid = gcols[k]
                gvals[off: off + rows_b] = vals[sl]
                if gvalid is not None:
                    gvalid[off: off + rows_b] = (
                        np.ones(rows_b, bool) if valid is None
                        else valid[sl])
        pids = _np_hash_pids([(gcols[k][0], gcols[k][1])
                              for k in key_ids], num_out)
        live_idx = np.nonzero(live)[0]
        rows_per_shard, _shard_cap, quota = mesh_stage_geometry(
            total_cap, num_out)
        if quota_seed and int(quota_seed) > quota:
            quota = int(quota_seed)
        shard = live_idx // rows_per_shard
        pid_live = pids[live_idx]
        attempts = 1
        while True:
            counts = np.zeros((num_out, num_out), np.int64)
            np.add.at(counts, (shard, pid_live), 1)
            if not len(live_idx) or counts.max() <= quota:
                break
            if attempts >= 8:
                return None  # degrades to the host shuffle at runtime
            quota *= 2
            attempts += 1
        out_cap = num_out * quota
        parts, ptraces = [], []
        for q in range(num_out):
            sel = live_idx[pid_live == q]  # ascending == shard-major,
            # then original position: the stable per-shard pid sort
            rows_q = int(len(sel))
            parts.append([_Batch(rows_q, out_cap, False, seeded=seeded)])
            cols_q = {k: (gv[sel],
                          None if gvalid is None else gvalid[sel])
                      for k, (gv, gvalid) in gcols.items()}
            ptraces.append(_Trace(cols_q, np.ones(rows_q, bool), True,
                                  dict(global_doms), False))
        return attempts, _Flow(parts, None, counted=True, ptraces=ptraces)

    # -- exchange layout/value helpers -------------------------------------
    @staticmethod
    def _exchange_seeded(node) -> "bool | frozenset":
        """Which output columns the exchange's map-side write accumulates
        stats for — the SAME annotation the execution layer consumes
        (ShuffleExchangeExec.stat_cols, set by annotate_exchange_stat_
        cols to the plan-reachable dense candidates). None = the legacy
        every-integral-column model (bare plans)."""
        sc = getattr(node, "stat_cols", None)
        if sc is None:
            return True
        out = node.output
        return frozenset(out[i].expr_id for i in sc if i < len(out))

    def _built_partition(self, rows_p: int,
                         seeded: "bool | frozenset" = True) -> list:
        """Output tiles of one reduce partition as exec/shuffle._OutBuffer
        builds them: tile rows capped at spark.tpu.batch.capacity,
        power-of-two capacity per tile, every tile pre-seeded with the
        map-side column stats of the exchange's stat columns (fresh
        arrays, no krange3 probe for those columns)."""
        if rows_p == 0:
            return [_Batch(0, _EMPTY_CAP, False, seeded=seeded,
                           ingest=True)]
        out = []
        for start in range(0, rows_p, self._tile):
            n = min(self._tile, rows_p - start)
            # rebuilt tiles are host-ingested (ColumnarBatch.from_numpy):
            # integral columns carry RunInfo, so a reducer whose rows
            # arrive sorted can take the ragg kernel
            out.append(_Batch(n, bucket_capacity(n), False, seeded=seeded,
                              ingest=True))
        return out

    def _exchange_input_traces(self, node, child: _Flow,
                               fused: bool) -> Optional[list]:
        """Per-input-partition traces at the exchange's consumption level
        (the pipeline OUTPUT when the map side is fused)."""
        traces = child.all_part_traces()
        if traces is None:
            return None
        if fused:
            filters, outputs = node.pipe_fusion
            traces = [self._project_trace(t, filters, outputs)
                      for t in traces]
            if any(t is None for t in traces):
                return None
        if not all(t.consecutive for t in traces):
            return None
        return traces

    def _shuffled_flow(self, in_traces: list, pids_per_part: list,
                       num_out: int,
                       seeded: "bool | frozenset" = True,
                       in_parts: Optional[list] = None) -> _Flow:
        """Exact post-shuffle layout + per-reduce-partition value traces:
        reduce partition q = every input partition's live rows with
        pid == q, input order preserved (the stable pid sort groups rows
        without reordering within a pid). With `in_parts` (the exchange
        input's batch layout), per-reduce dictionary domains mirror the
        rebuild: a reduce tile's merged dictionary is the union of the
        FULL dictionaries of every input batch that contributed rows
        (exec/shuffle._OutBuffer chunks carry whole-batch dictionaries)."""
        comp = [t.compacted() for t in in_traces]
        ids = set(comp[0].cols) if comp else set()
        for t in comp[1:]:
            ids &= set(t.cols)
        dict_ids = [k for k in ids
                    if comp and comp[0].cols[k][0].dtype == object]
        # per input partition: (live-row -> source batch index, per-batch
        # domain) for the dictionary-union mirror
        chunk_info = None
        if dict_ids and in_parts is not None:
            chunk_info = []
            for t, part in zip(in_traces, in_parts):
                rows = [b.rows for b in part]
                if any(r is None for r in rows) \
                        or sum(rows) != len(t.live):
                    chunk_info = None
                    break
                src = np.repeat(np.arange(len(part)), rows)[t.live]
                doms = {}
                ok = True
                for k in dict_ids:
                    r0, per = 0, []
                    for r in rows:
                        d = self._trace_domain(t, k, r0, r0 + r)
                        per.append(d)
                        r0 += r
                        if d is None:
                            ok = False
                    doms[k] = per
                if not ok:
                    chunk_info = None
                    break
                chunk_info.append((src, doms))
        parts, ptraces = [], []
        for q in range(num_out):
            sels = [np.nonzero(pids == q)[0] for pids in pids_per_part]
            rows_q = int(sum(len(s) for s in sels))
            built = self._built_partition(rows_q, seeded)
            parts.append(built)
            cols_q = {}
            for k in ids:
                vals = np.concatenate(
                    [t.cols[k][0][s] for t, s in zip(comp, sels)])
                vs = [t.cols[k][1] for t in comp]
                valid = None
                if any(v is not None for v in vs):
                    valid = np.concatenate(
                        [np.ones(len(s), bool) if v is None else v[s]
                         for v, s in zip(vs, sels)])
                cols_q[k] = (vals, valid)
            domains = {}
            if chunk_info is not None and len(built) == 1:
                # single rebuilt tile: its dictionary = ordered union of
                # contributing chunks' full batch dictionaries
                for k in dict_ids:
                    contributing = []
                    for (src, doms), s in zip(chunk_info, sels):
                        hit = np.unique(src[s]) if len(s) else []
                        contributing.extend(doms[k][int(b)] for b in hit)
                    domains[k] = self._ordered_union(contributing)
            ptraces.append(_Trace(cols_q, np.ones(rows_q, bool), True,
                                  domains, False))
        return _Flow(parts, None, counted=True, ptraces=ptraces)

    def _map_side_kinds(self, node, child: _Flow, fused: bool,
                        plain_kind: str, kinds: Counter, notes: list):
        """Map-side launch model: fused exchanges run ONE fused_shuffle
        dispatch per batch (partitions under minRows fall back to the
        shared pipeline + shuffle kernels); unfused exchanges run the
        plain shuffle kind per batch."""
        if not fused:
            if child.counted:
                kinds[plain_kind] += child.total_batches
            else:
                self._approx("host shuffle launches depend on unknown "
                             "upstream batch count")
            return
        gated = False
        for pp in child.parts:
            caps = [b.cap for b in pp]
            if not all(c is not None for c in caps):
                self._approx("fusion minRows gate undecidable for the "
                             "fused exchange (unknown tile capacities)")
                kinds["fused_shuffle"] += len(pp)
            elif sum(caps) < self._min_rows:
                kinds["pipeline"] += len(pp)
                kinds[plain_kind] += len(pp)
                gated = True
            else:
                kinds["fused_shuffle"] += len(pp)
        notes.append("FUSED map side: pipeline + partition-id kernel "
                     "traced into ONE program per batch; shuffle writes "
                     "consume the grouped result directly")
        if gated:
            notes.append(f"map partition under spark.tpu.fusion.minRows="
                         f"{self._min_rows}: pipeline + shared shuffle "
                         "kernels at runtime")

    def _exchange(self, node) -> _Flow:
        from ..physical.partitioning import (
            HashPartitioning, RangePartitioning, SinglePartition,
            UnknownPartitioning,
        )

        child = self.visit(node.child)
        p = node.partitioning
        kinds = Counter()
        notes = []
        fused = getattr(node, "pipe_fusion", None) is not None
        if isinstance(p, SinglePartition):
            merged = [b for part in child.parts for b in part]
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, ["gather: no kernels"])
            return _Flow([merged], child.trace, counted=child.counted)
        if isinstance(p, HashPartitioning):
            if self._mesh_active(p.num_partitions):
                return self._mesh_exchange(node, child, p, fused, kinds,
                                           notes)
            self._map_side_kinds(node, child, fused,
                                 self._host_shuffle_kind(), kinds, notes)
            self._sync("host sort-shuffle pulls grouped columns to host "
                       "once per batch (by design: the DCN path)")
            seeded = self._exchange_seeded(node)
            flow = None
            in_traces = self._exchange_input_traces(node, child, fused)
            key_ids = [e.expr_id for e in p.exprs
                       if isinstance(e, AttributeReference)]
            if in_traces is not None and len(key_ids) == len(p.exprs) \
                    and all(k in t.cols for t in in_traces
                            for k in key_ids):
                pids_per_part = []
                for t in in_traces:
                    tc = t.compacted()
                    pids_per_part.append(_np_hash_pids(
                        [tc.cols[k] for k in key_ids], p.num_partitions))
                flow = self._shuffled_flow(in_traces, pids_per_part,
                                           p.num_partitions, seeded,
                                           child.parts)
                notes.append("reduce layout EXACT: host-side splitmix64 "
                             "of the traced keys decides per-reducer rows")
            if flow is None:
                self._approx("hash exchange reduce layout untraced (key "
                             "values unknown): downstream counts are "
                             "approximate")
                flow = _Flow([[_Batch(None, None, False, seeded=seeded)]
                              for _ in range(p.num_partitions)], None,
                             counted=False)
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, notes)
            return flow
        if isinstance(p, RangePartitioning):
            self._map_side_kinds(node, child, fused, "shuffle_range",
                                 kinds, notes)
            if fused and child.counted:
                # post-pipeline bound sampling materializes the pipeline
                # for ≤3 spread batches per partition
                kinds["pipeline"] += sum(min(3, len(pp))
                                         for pp in child.parts)
                notes.append("fused range bounds sample the POST-pipeline "
                             "key column (≤3 materialized batches per "
                             "partition)")
            self._approx("range exchange: sampled bounds may collapse to a "
                         "single gather (data-dependent)")
            self._sync("range-bound sampling reads per-batch samples "
                       "host-side (fused: fresh pipeline outputs each "
                       "run; unfused: memoized per column identity)")
            out = [[_Batch(None, None, False,
                           seeded=self._exchange_seeded(node))]
                   for _ in range(p.num_partitions)]
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, notes)
            return _Flow(out, None, counted=False)
        if isinstance(p, UnknownPartitioning):
            self._map_side_kinds(node, child, fused, "shuffle_rr", kinds,
                                 notes)
            seeded = self._exchange_seeded(node)
            # the running row offset rides as a kernel argument, so the
            # cache key is (capacity, num_out)-shaped — no recompile
            # hazard (the historical storm keyed by start % num_out;
            # fixed alongside this model)
            notes.append("round-robin start offset rides as a kernel "
                         "argument: one compile per capacity bucket, "
                         "1 launch/batch")
            flow = None
            in_traces = self._exchange_input_traces(node, child, fused)
            if in_traces is not None:
                offset = 0
                pids_per_part = []
                for t in in_traces:
                    n = int(t.live.sum())
                    pids_per_part.append(
                        ((np.arange(n) + offset) % p.num_partitions)
                        .astype(np.int32))
                    offset += n
                flow = self._shuffled_flow(in_traces, pids_per_part,
                                           p.num_partitions, seeded,
                                           child.parts)
                notes.append("reduce layout EXACT: round-robin over the "
                             "traced live-row order")
            if flow is None:
                flow = _Flow([[_Batch(None, None, False, seeded=seeded)]
                              for _ in range(p.num_partitions)], None,
                             counted=False)
            self._stage(node, kinds, child.total_batches if child.counted
                        else None, notes)
            return flow
        self._approx(f"exchange over {type(p).__name__} not modeled")
        return _Flow([[_Batch(None, None, False)]], None, counted=False)

    @staticmethod
    def _host_shuffle_kind() -> str:
        try:
            from ..utils.native import radix_partition  # noqa: F401

            return "shuffle_pids"
        except Exception:
            return "shuffle_hash"

    # -- misc --------------------------------------------------------------
    def _union(self, node) -> _Flow:
        parts = []
        counted = True
        for c in node.children_plans:
            f = self.visit(c)
            parts.extend(f.parts)
            counted = counted and f.counted
        self._stage(node, Counter(), None, ["no kernels: rewraps batches"])
        return _Flow(parts, None, counted=counted)

    def _coalesce(self, node) -> _Flow:
        child = self.visit(node.child)
        n = max(1, min(node.num_partitions, max(len(child.parts), 1)))
        out = [[] for _ in range(n)]
        for i, p in enumerate(child.parts):
            out[i % n].extend(p)
        self._stage(node, Counter(), child.total_batches if child.counted
                    else None, ["no kernels"])
        return _Flow(out, child.trace if len(child.parts) <= 1 else None,
                     counted=child.counted)

    def _sample(self, node) -> _Flow:
        child = self.visit(node.child)
        kinds = Counter()
        if child.counted:
            kinds["sample"] = child.total_batches
        parts = [[_Batch(b.rows, b.cap, False) for b in p]
                 for p in child.parts]
        # the per-(partition,batch) position base is a kernel INPUT, so
        # one compiled kernel per (capacity, seed, fraction) serves every
        # batch — no recompile hazard (the historical storm keyed by
        # batch indices; fixed alongside this model)
        self._stage(node, kinds, child.total_batches if child.counted
                    else None,
                    ["sample offset rides as a kernel argument: one "
                     "compile per capacity bucket, 1 launch/batch"])
        return _Flow(parts, None, counted=child.counted)

    # -- python UDF evaluation ---------------------------------------------
    def _python_eval(self, node) -> _Flow:
        """PythonEvalExec launch model: one argument-pipeline dispatch per
        batch per UDF (the UDF itself runs host-side — zero kernel
        launches); the output batch wraps the SAME input columns plus one
        fresh host-built column, so identity/seed/RunInfo metadata and the
        value trace all pass through (the UDF column stays untraced)."""
        child = self.visit(node.child)
        kinds = Counter()
        notes = []
        nudf = len(node.udf_aliases)
        if child.counted:
            kinds["pipeline"] = nudf * child.total_batches
        else:
            self._approx("python UDF argument-pipeline launches depend on "
                         "an unknown upstream batch count")
        self._sync("python UDFs pull live argument rows to host once per "
                   "batch (by design: host evaluation)")
        if self._encoding:
            for al in node.udf_aliases:
                udf = al.child
                args = getattr(udf, "args", [])
                if len(args) == 1 \
                        and isinstance(args[0], AttributeReference) \
                        and isinstance(args[0].dtype, StringType) \
                        and getattr(udf, "deterministic", True):
                    notes.append(
                        f"{getattr(udf, 'fname', 'udf')}: dictionary-"
                        "domain lane — the UDF evaluates once per "
                        "DISTINCT value of its dictionary-encoded string "
                        "argument and maps over codes (per-row only when "
                        "the domain is not smaller than the live rows)")
                    break
        parts = [[_Batch(b.rows, b.cap, b.stable, seeded=b.seeded,
                         ingest=b.ingest) for b in p]
                 for p in child.parts]
        self._stage(node, kinds, child.total_batches if child.counted
                    else None, notes)
        return _Flow(parts, child.trace, counted=child.counted,
                     ptraces=child.ptraces)

    # -- whole-query tier ---------------------------------------------------
    def _whole_query(self, node) -> _Flow:
        """Launch model of the whole-query tier (physical/whole_query.py):
        the ENTIRE plan is ONE jitted program — leaves execute launch-free
        (device-cached ingest), exchanges lower to in-program gathers, and
        the only dispatches are the program itself plus one re-dispatch
        per join output-capacity retry round. The mirror walks the inner
        plan with the single-flow layout (gathered capacities) and the
        value model to predict the retry count EXACTLY when the join keys
        trace; memory is the fully-resident sum of every lowered
        operator's tile plus the leaf input planes."""
        from ..exec.memory import schema_row_bytes
        from ..physical import operators as O
        from ..physical.exchange import (
            BroadcastExchangeExec, ShuffleExchangeExec,
        )
        from ..physical.fusion import FusedAggregateExec, FusedLimitExec
        from ..physical.operators import attrs_schema
        from ..physical.whole_query import window_layout_bytes
        from ..physical.window import WindowExec

        kinds = Counter()
        notes = []
        dec = getattr(node, "decision", None)
        if dec is not None:
            self.report.tier = dec.to_dict()
            notes.append(f"tier decision: {dec.reason}")
        hbm = [0]
        untraced = [False]
        # retry-loop state shared across simulation rounds: per-join
        # output capacities in lowering order, exactly as the runtime's
        # join_caps list evolves. A persistent warm-start seed
        # (exec/persist_cache.py manifest, same lookup the runtime
        # performs) pre-populates the list — the seeded first attempt is
        # the prior run's FINAL program, so its retry rounds collapse.
        seed_rec = self._persist_seed_record() or {}
        seed_caps = seed_rec.get("join_caps") or ()
        caps_state: dict[int, int] = {i: int(c)
                                      for i, c in enumerate(seed_caps)}
        # dense direct-address probe state (warm-start span seed): which
        # joins compile the dense 1:1 variant up front, and which turned
        # it off after the in-program guard fired — one retry round each,
        # exactly the runtime's dense_off escalation
        spans_seed = seed_rec.get("join_spans") or None
        dense_off: set = set()
        dense_used = [False]
        round_state = {"seq": 0, "overflow": [], "guards": []}

        def mem(n, cap, extra_planes: int = 0):
            try:
                rb = schema_row_bytes(attrs_schema(n.output))
            except Exception:
                rb = 16
                self._mem_approx(f"{type(n).__name__}: output schema "
                                 "unavailable — 16 B/row assumed")
            hbm[0] += (cap + extra_planes) * rb

        def walk(n):
            """(gathered cap, value trace | None) of the lowered flow."""
            if isinstance(n, O.LocalTableScanExec):
                rows, trace = self._table_trace(n)
                caps = [b.cap for b in self._batches_for_rows(rows)]
                cap = bucket_capacity(max(sum(caps), 1))
                mem(n, cap, extra_planes=sum(caps))
                return cap, trace
            if isinstance(n, O.ScanExec):
                from ..physical.whole_query import (
                    _external_scan_rows, _scan_table,
                )

                t = _scan_table(n)
                if t is None:
                    # parquet-stats admission (spark.tpu.adaptive.
                    # parquetStats): footer row-group counts give the
                    # exact layout without reading data — only the
                    # VALUES stay untraced
                    rows = _external_scan_rows(n)
                    if rows is not None:
                        self._approx(
                            f"whole-query external scan [{n.name}]: "
                            "footer statistics model the layout, values "
                            "untraced")
                        caps = [c for tiles in self._part_tiles(
                            rows, n.source.num_partitions())
                            for _r, c in tiles]
                        cap = bucket_capacity(max(sum(caps), 1))
                        mem(n, cap, extra_planes=sum(caps))
                        return cap, None
                    self._approx("whole-query leaf layout unknown "
                                 f"(external scan [{n.name}])")
                    return self._tile, None
                caps = [c for tiles in self._part_tiles(
                    t.num_rows, n.source.num_partitions())
                    for _r, c in tiles]
                cap = bucket_capacity(max(sum(caps), 1))
                _rows2, trace = self._arrow_trace(t, n.attrs)
                mem(n, cap, extra_planes=sum(caps))
                return cap, trace
            if isinstance(n, O.RangeExec):
                step = n.step
                total = max(0, -(-(n.end - n.start) // step)) if step > 0 \
                    else max(0, -(-(n.start - n.end) // -step))
                caps = [c for tiles in self._part_tiles(
                    total, n.num_partitions) for _r, c in tiles]
                cap = bucket_capacity(max(sum(caps), 1))
                trace = None
                if 0 < total <= _TRACE_MAX_ROWS:
                    vals = n.start + np.arange(total, dtype=np.int64) * step
                    trace = _Trace({n.attr.expr_id: (vals, None)},
                                   np.ones(total, bool))
                mem(n, cap, extra_planes=sum(caps))
                return cap, trace
            if isinstance(n, O.ComputeExec):
                cap, tr = walk(n.child)
                mem(n, cap)
                return cap, self._project_trace(tr, n.filters, n.outputs)
            if isinstance(n, ShuffleExchangeExec):
                cap, tr = walk(n.child)
                if n.pipe_fusion is not None:
                    f_, o_ = n.pipe_fusion
                    tr = self._project_trace(tr, f_, o_)
                    mem(n, cap)
                return cap, tr
            if isinstance(n, (BroadcastExchangeExec,
                              O.CoalescePartitionsExec)):
                return walk(n.child)
            if isinstance(n, FusedAggregateExec):
                cap, _tr = walk(n.child)
                out_cap = cap if n.grouping else 8
                mem(n, out_cap)
                return out_cap, None
            if isinstance(n, O.HashAggregateExec):
                cap, _tr = walk(n.child)
                out_cap = cap if n.grouping else 8
                mem(n, out_cap)
                return out_cap, None
            if isinstance(n, (FusedLimitExec, O.LimitExec, O.SortExec)):
                cap, _tr = walk(n.child)
                mem(n, cap)
                return cap, None
            if isinstance(n, WindowExec):
                # whole_query._lower_window: the flow keeps its rows,
                # their order and its capacity and gains a column per
                # expression, inside the same program (no launch of its
                # own); memory as _estimate_resident_bytes counts it
                cap, tr = walk(n.child)
                mem(n, cap)
                hbm[0] += window_layout_bytes(n, cap)
                return cap, tr
            if isinstance(n, O.UnionExec):
                pairs = [walk(c) for c in n.children_plans]
                cap = bucket_capacity(max(sum(c for c, _ in pairs), 1))
                traces = [t for _, t in pairs]
                tr = self._merge_group_traces(traces) \
                    if all(t is not None for t in traces) else None
                mem(n, cap)
                return cap, tr
            if isinstance(n, O.HashJoinExec):
                pcap, ptr = walk(n.left)
                if n.probe_fusion is not None:
                    f_, o_ = n.probe_fusion
                    ptr = self._project_trace(ptr, f_, o_)
                bcap, btr = walk(n.right)
                jid = round_state["seq"]
                round_state["seq"] += 1
                out_cap = caps_state.setdefault(jid, max(pcap, 1 << 10))
                dense = self._whole_dense_span(jid, bcap, spans_seed,
                                               dense_off) \
                    if self._whole_dense_eligible(n) else None
                if dense is not None:
                    # dense direct-address probe (runtime _join_dense):
                    # 1:1 with the probe plane, no expansion buffer —
                    # the join cap never binds, but the in-program span/
                    # dup guard may disable it for the next round
                    dense_used[0] = True
                    guard, out_tr = self._whole_dense_mirror(
                        n, ptr, btr, *dense)
                    if guard is None:
                        untraced[0] = True
                    elif guard:
                        round_state["guards"].append(jid)
                    mem(n, pcap)
                    return pcap, out_tr
                needed = self._whole_join_needed(n, ptr, btr)
                if needed is None:
                    untraced[0] = True
                elif needed > out_cap:
                    round_state["overflow"].append(
                        (jid, bucket_capacity(needed)))
                mem(n, out_cap)
                out_tr = self._whole_join_trace(n, ptr, btr)
                if out_tr is not None and needed is not None \
                        and needed > out_cap:
                    # the failed attempt TRUNCATES at the output bucket:
                    # downstream joins of this round see the prefix (the
                    # kernel fills output slots probe-major, within a
                    # probe row's block in original build-row order —
                    # exactly this expansion's order)
                    if n.join_type == "inner" \
                            and len(out_tr.live) >= out_cap:
                        sel = np.arange(out_cap)
                        out_tr = out_tr.select(sel, True)
                    else:
                        untraced[0] = True
                        out_tr = None
                return out_cap, out_tr
            # admission should prevent this; degrade honestly
            self._approx(f"whole-query mirror missing for "
                         f"{type(n).__name__}")
            return self._tile, None

        # mirror of WholeQueryExec.execute's retry loop: each round
        # re-walks with the bumped capacities; truncated upstream traces
        # make the observed `needed` of cascading joins exact too. The
        # memory model keeps the LAST round's accumulation — the peak
        # attempt runs with the bumped join output buckets
        attempts = 0
        out_cap, out_tr = self._tile, None
        while attempts < 8:
            attempts += 1
            round_state["seq"] = 0
            round_state["overflow"] = []
            round_state["guards"] = []
            hbm[0] = 0
            out_cap, out_tr = walk(node.plan)
            if untraced[0] or not (round_state["overflow"]
                                   or round_state["guards"]):
                break
            for jid, newcap in round_state["overflow"]:
                caps_state[jid] = newcap
            for jid in round_state["guards"]:
                dense_off.add(jid)
        if untraced[0]:
            self._approx("whole-query join output capacity untraced (key "
                         "values outside the traced language): retry "
                         "dispatches unpredictable")
        if attempts > 1:
            notes.append(
                f"{attempts - 1} capacity "
                f"retr{'y' if attempts == 2 else 'ies'}: a join "
                "overflowed its output bucket (or a dense-probe guard "
                "fired) and the whole program re-dispatched with the "
                "bumped capacity")
        if dense_used[0]:
            notes.append("dense direct-address probe compiled up front "
                         "from the warm-start key-span seed (1:1 with "
                         "the probe plane, no expansion buffer), "
                         "guarded in-program")
        kinds["whole_query"] = attempts
        notes.insert(0, "WHOLE-QUERY program: all stages in ONE jitted "
                        "dispatch per step — exchanges lowered to "
                        "in-program gathers, intermediates never leave "
                        "HBM, zero host shuffle round-trips")
        self._sync("whole-query join capacity verdicts sync once after "
                   "the single dispatch (the query's last device "
                   "interaction before collect)")
        self._hazard("whole-query join output capacities are "
                     "value-dependent program-key components — match "
                     "growth recompiles the whole program")
        self._stage(node, kinds, 1, notes)
        ent = self._stage_by_node.get(id(node))
        if ent is not None and "hbm_bytes" not in ent:
            ent["hbm_bytes"] = hbm[0]
            self._hbm_total += hbm[0]
            self._hbm_any = True
        return _Flow([[_Batch(None, out_cap, False)]], out_tr,
                     counted=True)

    def _whole_join_needed(self, node, ptr, btr):
        """Mirror of ops/joining.probe_join's `needed` scalar over the
        single gathered flow: per live probe row, the count of verified
        build matches (an outer join reserves >= 1 slot per live row; a
        semi join takes 1 for a row with a match, an anti join 1 for each
        live row: `ops/joining._exists`; a hash collision's expansion is
        not mirrored). None when the keys/values are outside the traced
        language."""
        if len(node.left_keys) != 1 or ptr is None or btr is None:
            return None
        pent = ptr.cols.get(node.left_keys[0].expr_id)
        bstats = btr.stats(node.right_keys[0].expr_id)
        if pent is None or bstats is None:
            return None
        pv, pvalid = pent
        live = ptr.live
        usable = live if pvalid is None else (live & pvalid)
        counts = np.zeros(len(pv), np.int64)
        if bstats.size:
            bvals, bcounts = np.unique(bstats, return_counts=True)
            if pv.dtype == object or bvals.dtype == object:
                cmap = {v: int(c) for v, c in zip(bvals.tolist(),
                                                  bcounts.tolist())}
                counts = np.array([cmap.get(x, 0) for x in pv.tolist()],
                                  np.int64)
            else:
                idx = np.clip(np.searchsorted(bvals, pv), 0,
                              len(bvals) - 1)
                counts = np.where(bvals[idx] == pv, bcounts[idx],
                                  0).astype(np.int64)
        counts = np.where(usable, counts, 0)
        if node.join_type == "left_semi":
            counts = np.minimum(counts, 1)
        elif node.join_type == "left_anti":
            counts = live.astype(np.int64)
        elif node.join_type != "inner":
            counts = np.maximum(counts, live.astype(np.int64))
        return int(counts.sum())

    def _whole_join_trace(self, node, ptr, btr):
        """Value trace through an in-program join (whole-query mirror):
        the output MULTISET of probe AND build columns — semi/anti select
        probe rows; inner joins expand fully (each live usable probe row
        repeats once per matching build row, duplicate build keys
        included); left_outer maps 1:1 when the build key is unique.
        Downstream whole-query consumers only SUM over these traces
        (further join `needed` counts), so within-group ordering need not
        mirror the kernel's hash-sorted layout."""
        jt = node.join_type
        if ptr is None or btr is None or len(node.left_keys) != 1:
            return None
        pent = ptr.cols.get(node.left_keys[0].expr_id)
        bent = btr.cols.get(node.right_keys[0].expr_id)
        if pent is None or bent is None:
            return None
        pv, pvalid = pent
        live = ptr.live
        usable = live if pvalid is None else (live & pvalid)
        bvals_all, bvalid_all = bent
        blive = btr.live if bvalid_all is None \
            else (btr.live & bvalid_all)
        bsel = np.nonzero(blive)[0]
        bkeys = bvals_all[bsel]

        def probe_only(sel):
            cols = {k: (v[sel], None if vv is None else vv[sel])
                    for k, (v, vv) in ptr.cols.items()}
            return _Trace(cols, np.ones(len(sel), bool), True,
                          dict(ptr.dict_domains), False)

        if jt in ("left_semi", "left_anti"):
            matched = usable & np.isin(pv, bkeys)
            sel_mask = matched if jt == "left_semi" \
                else (live & ~matched)
            return probe_only(np.nonzero(sel_mask)[0])
        if jt == "left_outer":
            if np.unique(bkeys).size != bkeys.size:
                return None  # dup-build outer expansion: layout unclear
            sel = np.nonzero(live)[0]
            out = probe_only(sel)
            # 1:1 build-column mapping: matched rows gather the build
            # row, unmatched rows read NULL
            order = np.argsort(bkeys, kind="stable")
            bs = bkeys[order]
            pos = np.clip(np.searchsorted(bs, pv[sel]), 0,
                          max(len(bs) - 1, 0))
            hit = (len(bs) > 0) & usable[sel]
            if len(bs):
                hit = hit & (bs[pos] == pv[sel])
            pick = bsel[order][pos] if len(bs) else np.zeros(len(sel), int)
            for k, (bv, bvv) in btr.cols.items():
                vals = bv[pick] if len(bs) else np.zeros(len(sel),
                                                         bv.dtype)
                valid = np.asarray(hit, bool).copy()
                if bvv is not None and len(bs):
                    valid &= bvv[pick]
                out.cols.setdefault(k, (vals, valid))
            return out
        if jt != "inner":
            return None
        # inner: full expansion over sorted build keys
        order = np.argsort(bkeys, kind="stable")
        bs = bkeys[order]
        lo = np.searchsorted(bs, pv, side="left")
        hi = np.searchsorted(bs, pv, side="right")
        counts = np.where(usable, hi - lo, 0).astype(np.int64)
        total = int(counts.sum())
        src = np.repeat(np.arange(len(pv)), counts)
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        within = np.arange(total) - starts
        offs = np.repeat(lo, counts) + within
        pick = bsel[order][offs] if total else np.zeros(0, int)
        cols = {k: (v[src], None if vv is None else vv[src])
                for k, (v, vv) in ptr.cols.items()}
        for k, (bv, bvv) in btr.cols.items():
            cols.setdefault(k, (bv[pick],
                                None if bvv is None else bvv[pick]))
        return _Trace(cols, np.ones(total, bool), True,
                      dict(ptr.dict_domains), False)

    def _whole_dense_eligible(self, node) -> bool:
        """Mirror of whole_query._dense_eligible: single plain
        integral/date equi-key on both sides with the dense fast path
        enabled — the shape that CAN compile the direct-address probe."""
        if len(node.left_keys) != 1 or len(node.right_keys) != 1:
            return False
        if not self._dense_keys:
            return False
        return all(isinstance(k.dtype, (IntegralType, DateType))
                   for k in (node.left_keys[0], node.right_keys[0]))

    @staticmethod
    def _whole_dense_span(join_id, build_cap, spans_seed, dense_off):
        """Mirror of whole_query._dense_span: the seeded [lo, hi] span
        when the manifest proves last run's build keys were unique and
        dense enough (and an in-program guard hasn't disabled it)."""
        if spans_seed is None or join_id in dense_off:
            return None
        if join_id >= len(spans_seed):
            return None
        sp = spans_seed[join_id]
        if not sp or len(sp) < 3 or not int(sp[2]):
            return None
        lo, hi = int(sp[0]), int(sp[1])
        span = hi - lo + 1
        if span <= 0 or span > min(8 * build_cap, 1 << 23):
            return None
        return lo, hi

    def _whole_dense_mirror(self, node, ptr, btr, lo, hi):
        """(guard fired, output trace) of whole_query._join_dense — the
        faithful value mirror INCLUDING the drift modes: when the guard
        fires, the round's runtime output is the drifted dense result
        (out-of-span matches missing, duplicate keys last-writer), and
        downstream verdicts of that failed round observe exactly it.
        (None, None) when the keys are outside the traced language."""
        if ptr is None or btr is None:
            return None, None
        pent = ptr.cols.get(node.left_keys[0].expr_id)
        bent = btr.cols.get(node.right_keys[0].expr_id)
        if pent is None or bent is None:
            return None, None
        tcap = bucket_capacity(hi - lo + 1)
        bv_, bvv_ = bent
        blive = btr.live if bvv_ is None else (btr.live & bvv_)
        bk = bv_.astype(np.int64)
        bsel = np.nonzero(blive)[0]
        guard = False
        present = np.zeros(tcap, np.int64)
        rowidx = np.zeros(tcap, np.int64)
        if len(bsel):
            ks = bk[bsel]
            if int(ks.min()) < lo or int(ks.max()) > hi:
                guard = True
            slot = ks - lo
            ok = (slot >= 0) & (slot < tcap)
            np.add.at(present, slot[ok], 1)
            if int(present.max()) > 1:
                guard = True
            # scatter-set semantics: among colliding writes the mirror
            # keeps the last in row order (collisions imply guard anyway)
            rowidx[slot[ok]] = bsel[ok]
        pv_, pvv_ = pent
        live = ptr.live
        pk = pv_.astype(np.int64) - lo
        in_range = (pk >= 0) & (pk < tcap)
        pslot = np.clip(pk, 0, tcap - 1)
        usable = live & in_range
        if pvv_ is not None:
            usable = usable & pvv_
        matched = usable & (present[pslot] > 0)
        bidx = rowidx[pslot]
        jt = node.join_type
        if jt in ("inner", "left_semi"):
            out_live = matched
        elif jt == "left_outer":
            out_live = live.copy()
        else:  # left_anti
            out_live = live & ~matched
        cols = dict(ptr.cols)
        if jt not in ("left_semi", "left_anti"):
            for k, (bvx, bvvx) in btr.cols.items():
                base = np.ones(len(bidx), bool) if bvvx is None \
                    else bvvx[bidx]
                cols.setdefault(k, (bvx[bidx], base & matched))
        return guard, _Trace(cols, out_live, True,
                             dict(ptr.dict_domains), False)

    # -- mesh whole-query tier ----------------------------------------------
    def _mesh_whole(self, node) -> _Flow:
        """Launch model of the mesh whole-query tier
        (physical/mesh_whole.py): the ENTIRE sharded plan is ONE
        shard_map program — leaf planes stage row-sharded over the mesh,
        hash exchanges lower to in-program all_to_alls with the per-stage
        mesh path's quota/overflow contract, reduce-side consumers fold
        in behind the collective on the sharded layouts, and the only
        dispatches are the program itself plus one re-dispatch per retry
        round (join capacity bumps, DOUBLED exchange quotas and
        dense-guard fallbacks — all of a round's verdicts applied
        together, mirroring the runtime's single post-dispatch check).
        The mirror walks the inner plan per shard with the staged-shard
        value model, so {mesh_whole: attempts} is EXACT when the key
        values trace."""
        from ..exec.memory import schema_row_bytes
        from ..exec.persist_cache import mesh_quota_key
        from ..parallel.mesh_fusion import mesh_stage_geometry
        from ..physical import operators as O
        from ..physical.exchange import (
            BroadcastExchangeExec, ShuffleExchangeExec,
        )
        from ..physical.fusion import FusedAggregateExec, FusedLimitExec
        from ..physical.operators import attrs_schema
        from ..physical.partitioning import HashPartitioning
        from ..physical.whole_query import _scan_table

        kinds = Counter()
        notes = []
        dec = getattr(node, "decision", None)
        if dec is not None:
            self.report.tier = dec.to_dict()
            notes.append(f"tier decision: {dec.reason}")
        P = int((dec.details or {}).get("mesh_devices") or 0) \
            if dec is not None else 0
        if P < 2:
            self._approx("mesh-whole mirror: mesh axis unknown on the "
                         "tier decision")
            kinds["mesh_whole"] = 1
            self._stage(node, kinds, 1, notes)
            return _Flow([[_Batch(None, None, False)]], None,
                         counted=True)
        seed_rec = self._persist_seed_record() or {}
        seed_caps = seed_rec.get("join_caps") or ()
        caps_state: dict = {i: int(c) for i, c in enumerate(seed_caps)}
        spans_seed = seed_rec.get("join_spans") or None
        mesh_seed = seed_rec.get("mesh_quotas") or {}
        # persistent across rounds, exactly like the builder's state:
        # per-exchange live quotas (init once from geometry + manifest
        # seed at the FIRST round's staging caps, doubled on overflow)
        # and per-join dense disablement after a guard fired
        quota_state: dict = {}
        dense_off: set = set()
        hbm = [0]
        untraced = [False]
        dense_used = [False]
        partial_merged: set = set()
        rs = {"jseq": 0, "xseq": 0, "cap_over": [], "quota_over": [],
              "guards": []}

        def mem(n, cap, extra_planes: int = 0):
            # per-shard tile x row bytes x P shards (replicated flows
            # hold the full gathered tile on EVERY shard — same scale)
            try:
                rb = schema_row_bytes(attrs_schema(n.output))
            except Exception:
                rb = 16
                self._mem_approx(f"{type(n).__name__}: output schema "
                                 "unavailable — 16 B/row assumed")
            hbm[0] += (cap + extra_planes) * rb * P

        # flow states mirror the builder's forms:
        #   ("shard", per-shard cap, [P traces] | None, part_ids)
        #   ("rep",   gathered cap,  trace | None)
        def to_rep(st):
            if st[0] == "rep":
                return st
            _f, cap, trs, _p = st
            out_cap = cap * P
            if trs is None or any(t is None for t in trs):
                return ("rep", out_cap, None)
            ids = set(trs[0].cols)
            for t in trs[1:]:
                ids &= set(t.cols)
            cols = {}
            for k in ids:
                has_valid = any(t.cols[k][1] is not None for t in trs)
                vals = np.concatenate([t.cols[k][0] for t in trs])
                valid = None
                if has_valid:
                    valid = np.concatenate(
                        [np.ones(len(t.live), bool)
                         if t.cols[k][1] is None else t.cols[k][1]
                         for t in trs])
                cols[k] = (vals, valid)
            live = np.concatenate([t.live for t in trs])
            return ("rep", out_cap,
                    _Trace(cols, live, True,
                           dict(trs[0].dict_domains), False))

        def pipe(st, filters, outputs):
            if st[0] == "rep":
                tr = None if st[2] is None \
                    else self._project_trace(st[2], filters, outputs)
                return ("rep", st[1], tr)
            _f, cap, trs, pids_t = st
            out = None if trs is None else [
                None if t is None
                else self._project_trace(t, filters, outputs)
                for t in trs]
            return ("shard", cap, out, pids_t)

        def leaf_layout(n):
            """([(rows, cap)] tiles, execution order; global trace)."""
            if isinstance(n, O.LocalTableScanExec):
                rows, trace = self._table_trace(n)
                return [(b.rows, b.cap)
                        for b in self._batches_for_rows(rows)], trace
            if isinstance(n, O.ScanExec):
                t = _scan_table(n)
                if t is None:
                    return None, None
                _r, trace = self._arrow_trace(t, n.attrs)
                tiles = [rc for part in self._part_tiles(
                    t.num_rows, n.source.num_partitions())
                    for rc in part]
                return tiles, trace
            if isinstance(n, O.RangeExec):
                step = n.step
                total = max(0, -(-(n.end - n.start) // step)) \
                    if step > 0 \
                    else max(0, -(-(n.start - n.end) // -step))
                tiles = [rc for part in self._part_tiles(
                    total, n.num_partitions) for rc in part]
                trace = None
                if 0 < total <= _TRACE_MAX_ROWS:
                    vals = n.start + np.arange(total,
                                               dtype=np.int64) * step
                    trace = _Trace({n.attr.expr_id: (vals, None)},
                                   np.ones(total, bool))
                return tiles, trace
            return None, None

        def leaf_walk(n):
            """Mirror of _stage_leaf_host + _lower_mesh_leaf: flatten
            the leaf's batches to [total_cap] planes (rows-first per
            batch capacity slot), pad to P*rps, slice per shard."""
            tiles, trace = leaf_layout(n)
            if tiles is None:
                self._approx("mesh-whole leaf layout unknown "
                             f"({type(n).__name__})")
                untraced[0] = True
                return ("shard", self._tile, None, ())
            total_cap = max(sum(c for _r, c in tiles), 1)
            rps = max(-(-total_cap // P), 1)
            mem(n, rps, extra_planes=rps)
            if trace is None and any(r for r, _c in tiles):
                return ("shard", rps, None, ())
            plane = P * rps
            glive = np.zeros(plane, bool)
            cols = {} if trace is None else trace.cols
            gcols = {}
            for k, (v, vv) in cols.items():
                base = np.full(plane, "", dtype=object) \
                    if v.dtype == object else np.zeros(plane, v.dtype)
                gcols[k] = [base,
                            np.zeros(plane, bool)
                            if vv is not None else None]
            off = r0 = 0
            for rows_b, cap_b in tiles:
                if rows_b:
                    glive[off:off + rows_b] = True
                    for k, (v, vv) in cols.items():
                        gcols[k][0][off:off + rows_b] = v[r0:r0 + rows_b]
                        if gcols[k][1] is not None:
                            gcols[k][1][off:off + rows_b] = \
                                vv[r0:r0 + rows_b]
                off += cap_b
                r0 += rows_b
            doms = {}
            for k, (v, _vv) in cols.items():
                if v.dtype == object:
                    d = self._trace_domain(trace, k)
                    if d is not None:
                        doms[k] = d
            strs = []
            for s in range(P):
                sl = slice(s * rps, (s + 1) * rps)
                strs.append(_Trace(
                    {k: (gv[sl], None if gvv is None else gvv[sl])
                     for k, (gv, gvv) in gcols.items()},
                    glive[sl], True, dict(doms), False))
            return ("shard", rps, strs, ())

        def exchange_a2a(n, st, key_ids):
            """Mirror of _exchange_all_to_all / _exchange_tail: per
            (src, dst) keep the FIRST `quota` live rows in row order —
            truncation happens EVERY dispatch, the psum'd overflow
            scalar only reports it for the host's doubling verdict."""
            _f, cap, trs, _p = st
            xid = rs["xseq"]
            rs["xseq"] += 1
            q = quota_state.get(xid)
            if q is None:
                pos = {a.expr_id: i for i, a in enumerate(n.output)}
                kidx = tuple(pos[e.expr_id]
                             for e in n.partitioning.exprs)
                sig = "|".join(str(a.dtype) for a in n.output)
                mkey = mesh_quota_key("w", P, cap,
                                      f"x{xid}:k{kidx}:s{sig}")
                q = mesh_stage_geometry(P * cap, P)[2]
                seed = mesh_seed.get(mkey)
                if seed and int(seed) > q:
                    q = int(seed)
                quota_state[xid] = q
            out_cap = P * q
            mem(n, out_cap)
            if trs is None or any(t is None for t in trs):
                untraced[0] = True
                return ("shard", out_cap, None, key_ids)
            ids = set(trs[0].cols)
            for t in trs[1:]:
                ids &= set(t.cols)
            sent = [[] for _ in range(P)]   # per dst: (trace, sel) rows
            overflow = False
            for t in trs:
                live_idx = np.nonzero(t.live)[0]
                if not len(live_idx):
                    for qd in range(P):
                        sent[qd].append((t, live_idx))
                    continue
                if any(k not in t.cols for k in key_ids):
                    untraced[0] = True
                    return ("shard", out_cap, None, key_ids)
                pids = _np_hash_pids([t.cols[k] for k in key_ids], P)
                pl = pids[live_idx]
                for qd in range(P):
                    sel = live_idx[pl == qd]
                    if len(sel) > q:
                        overflow = True
                        sel = sel[:q]
                    sent[qd].append((t, sel))
            if overflow:
                rs["quota_over"].append(xid)
            out_trs = []
            for qd in range(P):
                cols_q = {}
                for k in ids:
                    has_valid = any(t.cols[k][1] is not None
                                    for t, _s in sent[qd])
                    vals = np.concatenate(
                        [t.cols[k][0][sel] for t, sel in sent[qd]])
                    valid = None
                    if has_valid:
                        valid = np.concatenate(
                            [np.ones(len(sel), bool)
                             if t.cols[k][1] is None
                             else t.cols[k][1][sel]
                             for t, sel in sent[qd]])
                    cols_q[k] = (vals, valid)
                nrows = sum(len(sel) for _t, sel in sent[qd])
                out_trs.append(_Trace(cols_q, np.ones(nrows, bool),
                                      True, dict(trs[0].dict_domains),
                                      False))
            return ("shard", out_cap, out_trs, key_ids)

        def exchange_local(n, st, key_ids):
            """Mirror of _exchange_local_filter: a hash exchange on a
            replicated flow keeps each shard's own pid rows — no
            collective, no quota, no overflow."""
            cap, tr = st[1], st[2]
            mem(n, cap)
            if tr is None:
                untraced[0] = True
                return ("shard", cap, None, key_ids)
            if any(k not in tr.cols for k in key_ids):
                if tr.live.any():
                    untraced[0] = True
                    return ("shard", cap, None, key_ids)
                pids = np.zeros(len(tr.live), np.int32)
            else:
                pids = _np_hash_pids([tr.cols[k] for k in key_ids], P)
            out_trs = [_Trace(dict(tr.cols), tr.live & (pids == s),
                              True, dict(tr.dict_domains), False)
                       for s in range(P)]
            return ("shard", cap, out_trs, key_ids)

        def register_merge(n):
            if getattr(n, "mode", "") != "final":
                return
            c = n.child
            while isinstance(c, (ShuffleExchangeExec,
                                 O.CoalescePartitionsExec)):
                c = c.child
            if isinstance(c, O.HashAggregateExec) \
                    and getattr(c, "mode", "") == "partial":
                partial_merged.add(id(c))

        def agg_out_trace(n, t):
            """Output key trace of an in-program aggregate: live groups
            in the per-stage layout model's order (valid keys ascending,
            the null group last). Single-key groupings only — this is
            what downstream a2a exchanges partition by."""
            if t is None or len(n.grouping) != 1:
                return None
            info = self._key_group_info(t, n.grouping[0].expr_id)
            if info is None:
                return None
            return self._agg_out_trace(n.grouping[0].expr_id, *info)

        def agg_walk(n, st):
            out_part = None
            if st[0] == "shard":
                part_ids = st[3]
                gids = set(g.expr_id for g in n.grouping)
                co = bool(part_ids) and set(part_ids) <= gids
                if getattr(n, "mode", "") == "partial" \
                        and id(n) in partial_merged:
                    out_part = part_ids if (n.grouping and co) else ()
                elif n.grouping and co:
                    out_part = part_ids
                else:
                    st = to_rep(st)
            if st[0] == "shard":
                cap, trs = st[1], st[2]
                out_cap = cap if n.grouping else 8
                mem(n, out_cap)
                out_trs = None if trs is None \
                    else [agg_out_trace(n, t) for t in trs]
                return ("shard", out_cap, out_trs, out_part)
            cap, tr = st[1], st[2]
            out_cap = cap if n.grouping else 8
            mem(n, out_cap)
            return ("rep", out_cap, agg_out_trace(n, tr))

        def join_walk(n):
            pst = walk(n.left)
            if n.probe_fusion is not None:
                f_, o_ = n.probe_fusion
                pst = pipe(pst, f_, o_)
            bst = walk(n.right)
            lkeys = tuple(k.expr_id for k in n.left_keys)
            rkeys = tuple(k.expr_id for k in n.right_keys)
            sharded = pst[0] == "shard"
            if sharded:
                co = (bst[0] == "shard" and len(lkeys) > 0
                      and pst[3] == lkeys and bst[3] == rkeys)
                if bst[0] == "shard" and not co:
                    bst = to_rep(bst)
            elif bst[0] == "shard":
                bst = to_rep(bst)
            pcap, bcap = pst[1], bst[1]
            if sharded:
                pts = pst[2] if pst[2] is not None else [None] * P
                bts = (bst[2] if bst[2] is not None else [None] * P) \
                    if bst[0] == "shard" else [bst[2]] * P
            else:
                pts = [pst[2]]
                bts = [bst[2]]
            jid = rs["jseq"]
            rs["jseq"] += 1
            out_cap = caps_state.setdefault(jid, max(pcap, 1 << 10))
            dense = self._whole_dense_span(jid, bcap, spans_seed,
                                           dense_off) \
                if self._whole_dense_eligible(n) else None
            if dense is not None:
                # dense direct-address probe per shard: 1:1 with the
                # probe plane, the join cap never binds; the pmax'd
                # guard disables it for the next round on drift
                dense_used[0] = True
                out_cap = pcap
                mem(n, out_cap)
                guard_any = False
                out_trs = []
                for pt, bt in zip(pts, bts):
                    g, tr = self._whole_dense_mirror(n, pt, bt, *dense)
                    if g is None:
                        untraced[0] = True
                    else:
                        guard_any = guard_any or g
                    out_trs.append(tr)
                if guard_any:
                    rs["guards"].append(jid)
            else:
                mem(n, out_cap)
                needs = [self._whole_join_needed(n, pt, bt)
                         for pt, bt in zip(pts, bts)]
                out_trs = []
                if any(nd is None for nd in needs):
                    untraced[0] = True
                    out_trs = [None] * len(pts)
                else:
                    # the host reads the pmax'd `needed` — ONE bump
                    # covers every shard's worst case
                    nd_max = max(needs) if needs else 0
                    if nd_max > out_cap:
                        rs["cap_over"].append(
                            (jid, bucket_capacity(nd_max)))
                    for pt, bt, nd in zip(pts, bts, needs):
                        tr = self._whole_join_trace(n, pt, bt)
                        if tr is not None and nd > out_cap:
                            # this shard's failed attempt truncates at
                            # the bucket (probe-major fill order)
                            if n.join_type == "inner" \
                                    and len(tr.live) >= out_cap:
                                tr = tr.select(np.arange(out_cap), True)
                            else:
                                untraced[0] = True
                                tr = None
                        out_trs.append(tr)
            if sharded:
                return ("shard", out_cap, out_trs, pst[3])
            return ("rep", out_cap, out_trs[0])

        def walk(n):
            if isinstance(n, (O.LocalTableScanExec, O.RangeExec,
                              O.ScanExec)):
                return leaf_walk(n)
            if isinstance(n, FusedAggregateExec):
                register_merge(n)
                st = pipe(walk(n.child), n.filters, n.pipe_outputs)
                return agg_walk(n, st)
            if isinstance(n, O.HashAggregateExec):
                register_merge(n)
                return agg_walk(n, walk(n.child))
            if isinstance(n, FusedLimitExec):
                st = to_rep(walk(n.child))
                mem(n, st[1])
                return ("rep", st[1], None)
            if isinstance(n, (O.LimitExec, O.SortExec)):
                st = to_rep(walk(n.child))
                mem(n, st[1])
                return ("rep", st[1], None)
            if isinstance(n, O.HashJoinExec):
                return join_walk(n)
            if isinstance(n, O.ComputeExec):
                st = walk(n.child)
                mem(n, st[1])
                return pipe(st, n.filters, n.outputs)
            if isinstance(n, ShuffleExchangeExec):
                st = walk(n.child)
                if n.pipe_fusion is not None:
                    f_, o_ = n.pipe_fusion
                    st = pipe(st, f_, o_)
                    mem(n, st[1])
                p = n.partitioning
                if isinstance(p, HashPartitioning):
                    key_ids = tuple(e.expr_id for e in p.exprs)
                    if st[0] == "shard":
                        return exchange_a2a(n, st, key_ids)
                    return exchange_local(n, st, key_ids)
                return to_rep(st)
            if isinstance(n, BroadcastExchangeExec):
                return to_rep(walk(n.child))
            if isinstance(n, O.CoalescePartitionsExec):
                return walk(n.child)
            if isinstance(n, O.UnionExec):
                sts = [to_rep(walk(c)) for c in n.children_plans]
                cap = bucket_capacity(max(sum(s[1] for s in sts), 1))
                traces = [s[2] for s in sts]
                tr = self._merge_group_traces(traces) \
                    if all(t is not None for t in traces) else None
                mem(n, cap)
                return ("rep", cap, tr)
            # admission should prevent this; degrade honestly
            self._approx(f"mesh-whole mirror missing for "
                         f"{type(n).__name__}")
            untraced[0] = True
            return ("rep", self._tile, None)

        # mirror of MeshWholeQueryExec's retry loop: all of a round's
        # verdicts (pmax'd join `needed`s, psum'd exchange overflows,
        # pmax'd dense guards) are read together after the ONE dispatch
        # and applied together before the re-dispatch. The memory model
        # keeps the LAST round's accumulation
        attempts = 0
        final = ("rep", self._tile, None)
        while attempts < 8:
            attempts += 1
            rs["jseq"] = 0
            rs["xseq"] = 0
            rs["cap_over"] = []
            rs["quota_over"] = []
            rs["guards"] = []
            hbm[0] = 0
            partial_merged.clear()
            final = to_rep(walk(node.plan))
            if untraced[0]:
                break
            if not (rs["cap_over"] or rs["quota_over"] or rs["guards"]):
                break
            for jid, newcap in rs["cap_over"]:
                caps_state[jid] = newcap
            for xid in rs["quota_over"]:
                quota_state[xid] = quota_state[xid] * 2
            for jid in rs["guards"]:
                dense_off.add(jid)
        if untraced[0]:
            self._approx("mesh-whole verdicts untraced (key values "
                         "outside the traced language): retry "
                         "dispatches unpredictable")
        if attempts > 1:
            notes.append(
                f"{attempts - 1} retry round"
                f"{'' if attempts == 2 else 's'}: join capacity bumps, "
                "doubled exchange quotas and dense-guard fallbacks "
                "re-dispatch the whole program (all of a round's "
                "verdicts applied together; retries restage from the "
                "undonated base planes, never from host)")
        if dense_used[0]:
            notes.append("dense direct-address probe compiled up front "
                         "from the warm-start key-span seed (1:1 with "
                         "the probe plane, no expansion buffer), "
                         "guarded in-program")
        kinds["mesh_whole"] = attempts
        notes.insert(0, f"MESH WHOLE-QUERY program: the entire sharded "
                        f"plan as ONE shard_map dispatch per step over "
                        f"{P} devices — hash exchanges are in-program "
                        "all_to_alls, reduce consumers fold in behind "
                        "the collective, intermediates never leave HBM")
        self._sync("mesh-whole verdict scalars (pmax'd join `needed`s, "
                   "psum'd exchange overflows, dense guards) sync ONCE "
                   "after the single sharded dispatch")
        self._hazard("mesh-whole join output capacities and exchange "
                     "quotas are value-dependent program-key components "
                     "— growth recompiles the whole sharded program")
        self._stage(node, kinds, 1, notes)
        ent = self._stage_by_node.get(id(node))
        if ent is not None and "hbm_bytes" not in ent:
            ent["hbm_bytes"] = hbm[0]
            self._hbm_total += hbm[0]
            self._hbm_any = True
        return _Flow([[_Batch(None, final[1], False)]], final[2],
                     counted=True)

    def _unknown(self, node) -> _Flow:
        flows = [self.visit(c) for c in node.children]
        self._approx(f"{type(node).__name__}: no launch model — counts "
                     "below this operator are a lower bound")
        parts = flows[0].parts if flows else [[_Batch(None, None, False)]]
        self._stage(node, Counter(), None, ["no launch model"])
        return _Flow([[_Batch(None, None, False)] for _ in parts], None,
                     counted=False)

    # -- fusion boundary explanations -------------------------------------
    def _explain_boundaries(self, plan):
        from ..physical import operators as O
        from ..physical.exchange import ShuffleExchangeExec
        from ..physical.fusion import (
            FusedAggregateExec, FusedLimitExec, _compute_nontrivial,
        )
        from ..physical.aggregates import FUSABLE_OPS

        out = self.report.fusion_boundaries
        gate = (f"runtime gate: partitions under spark.tpu.fusion.minRows="
                f"{self._min_rows} tile rows take the shared unfused "
                "kernels (per-structure fused compiles only amortize on "
                "volume)")
        if not self._fusion_on:
            out.append("whole-stage fusion DISABLED "
                       "(spark.tpu.fusion.enabled=false): operator-at-a-"
                       "time oracle — every stage boundary is unfused")
        if (self.report.tier or {}).get("tier") == "operator":
            out.append("compilation tier OPERATOR "
                       "(spark.tpu.compile.tier): shared operator-at-a-"
                       "time kernels — whole-stage fusion rewrites "
                       "skipped at plan time")
        for node in plan.iter_nodes():
            if isinstance(node, FusedAggregateExec):
                out.append(f"FUSED {node.simple_string()[:80]}: pipeline "
                           f"traced into the partial-agg kernel; {gate}")
            elif isinstance(node, FusedLimitExec):
                out.append(f"FUSED {node.simple_string()[:80]}; {gate}")
            elif isinstance(node, ShuffleExchangeExec):
                if getattr(node, "pipe_fusion", None) is not None:
                    out.append(f"FUSED map side "
                               f"{node.simple_string()[:80]}: partition-id "
                               f"kernel traced into the pipeline; {gate}")
                else:
                    reasons = self._exchange_boundary_reasons(node, O)
                    if reasons:
                        out.append(
                            f"UNFUSED exchange "
                            f"{node.simple_string()[:80]}: "
                            + "; ".join(reasons))
            elif isinstance(node, O.HashJoinExec) \
                    and node.probe_fusion is not None:
                out.append(f"FUSED probe {node.simple_string()[:80]}; "
                           f"{gate}")
            elif isinstance(node, O.HashAggregateExec) \
                    and node.mode == "partial":
                reasons = self._agg_boundary_reasons(
                    node, O, FUSABLE_OPS, _compute_nontrivial)
                if reasons:
                    out.append(f"UNFUSED {node.simple_string()[:80]}: "
                               + "; ".join(reasons))
            elif isinstance(node, O.HashJoinExec):
                reasons = self._join_boundary_reasons(
                    node, O, _compute_nontrivial)
                if reasons:
                    out.append(f"UNFUSED probe "
                               f"{node.simple_string()[:80]}: "
                               + "; ".join(reasons))
            elif isinstance(node, O.LimitExec) and not isinstance(
                    node, FusedLimitExec):
                if isinstance(node.child, O.SortExec):
                    msg = ("UNFUSED Limit over Sort: SortExec has no "
                           "fused consume side yet (needs the sort-key "
                           "rank domain inside the trace — ROADMAP item)")
                    if msg not in out:
                        out.append(msg)

    def _agg_boundary_reasons(self, node, O, FUSABLE_OPS,
                              _compute_nontrivial):
        reasons = []
        c = node.child
        if not self._fusion_on:
            return []
        if not isinstance(c, O.ComputeExec):
            if isinstance(c, (O.HashJoinExec,)):
                reasons.append("consume side is a join output (only "
                               "filter/project pipelines splice into the "
                               "agg kernel)")
            elif type(c).__name__.endswith("ExchangeExec"):
                reasons.append("stage boundary is an exchange — fusion "
                               "never crosses exchanges")
            else:
                reasons.append(f"consume side {type(c).__name__} is not a "
                               "fusable pipeline")
            return reasons
        if not _compute_nontrivial(c):
            reasons.append("upstream pipeline is a pure column selection — "
                           "nothing to fuse (zero launches either way)")
            return reasons
        if not all(s.mergeable for s in node.specs):
            reasons.append("non-mergeable aggregate (percentile/collect "
                           "needs host-side finishing)")
        out_ids = {a.expr_id for a in c.output}
        if any(g.expr_id not in out_ids for g in node.grouping):
            reasons.append("grouping key is not produced by the pipeline")
        for op, attr, _ in node._plan_values():
            if op not in FUSABLE_OPS:
                reasons.append(f"op {op} has no fused kernel")
            # string min/max no longer breaks fusion: the fused kernel
            # reduces in rank space with the inverse-rank lut as an aux
            # input
        return reasons or ["not rewritten (unexpected: report this plan)"]

    def _exchange_boundary_reasons(self, node, O) -> list:
        """Why a shuffle exchange over a nontrivial pipeline did NOT fuse
        its map side (mirrors fusion._exchange_fusable)."""
        from ..physical.fusion import _compute_nontrivial
        from ..physical.partitioning import (
            HashPartitioning, RangePartitioning, SinglePartition,
            UnknownPartitioning,
        )

        if not self._fusion_on:
            return []
        c = node.child
        if not isinstance(c, O.ComputeExec) or not _compute_nontrivial(c):
            return []
        p = node.partitioning
        if isinstance(p, SinglePartition):
            return []  # gather launches no partition kernel — nothing lost
        if not self._fusion_exchange:
            return ["exchange map-side fusion disabled "
                    "(spark.tpu.fusion.exchange=false)"]
        out_by_id = {a.expr_id: a for a in c.output}
        if isinstance(p, HashPartitioning):
            for e in p.exprs:
                a = out_by_id.get(getattr(e, "expr_id", -1))
                if a is None:
                    continue
                if isinstance(a.dtype, StringType):
                    if not self._encoding:
                        return [f"partition key {a.name} is a dictionary-"
                                "encoded string and compressed execution "
                                "is off (spark.tpu.encoding.enabled="
                                "false): eq-keys ride host-side "
                                "dictionary hashes"]
                elif dict_encoded(a.dtype):
                    return [f"partition key {a.name} is a nested "
                            "dictionary-encoded type: codes are not a "
                            "cross-dictionary equality domain"]
            return ["not rewritten (unexpected: report this plan)"]
        if isinstance(p, RangePartitioning):
            if len(p.orders) != 1:
                return ["multi-key range partitioning is not fused"]
            oc = p.orders[0].child
            a = out_by_id.get(getattr(oc, "expr_id", -1))
            if a is not None and (isinstance(a.dtype, StringType)
                                  or dict_encoded(a.dtype)):
                return [f"range key {a.name} is a dictionary-encoded "
                        "string: pids ride a host rank→pid lut"]
            # computed sort keys fuse: bounds sample the post-pipeline
            # key column (the sampled batches materialize the pipeline)
            return ["not rewritten (unexpected: report this plan)"]
        if isinstance(p, UnknownPartitioning):
            return ["not rewritten (unexpected: report this plan)"]
        return []

    def _join_boundary_reasons(self, node, O, _compute_nontrivial):
        if not self._fusion_on:
            return []
        c = node.left
        if not isinstance(c, O.ComputeExec):
            return []
        if not _compute_nontrivial(c):
            return ["probe pipeline is a pure column selection — nothing "
                    "to fuse"]
        out_by_id = {a.expr_id: a for a in c.output}
        for k in node.left_keys:
            a = out_by_id.get(k.expr_id)
            if a is None:
                return ["probe key is not produced by the pipeline"]
            if isinstance(a.dtype, StringType):
                if not self._encoding:
                    return [f"probe key {a.name} is a dictionary-encoded "
                            "string and compressed execution is off "
                            "(spark.tpu.encoding.enabled=false): "
                            "equality rides host-side dictionary hashes"]
            elif dict_encoded(a.dtype):
                return [f"probe key {a.name} is a nested dictionary-"
                        "encoded type: codes are not a cross-dictionary "
                        "equality domain"]
        return []

    # -- overflow ----------------------------------------------------------
    def _overflow_pass(self, plan):
        from ..physical import operators as O

        seen = set()
        for node in plan.iter_nodes():
            if not isinstance(node, O.HashAggregateExec):
                continue
            for s in node.specs:
                if id(s) in seen:
                    continue
                seen.add(id(s))
                for op in s.ops:
                    if op not in ("sum", "count", "countstar"):
                        continue
                    name = s.input_expr.name if isinstance(
                        s.input_expr, AttributeReference) else (
                        s.result_alias.name)
                    msg = None
                    if op == "sum" and s.input_expr is not None and \
                            isinstance(s.input_expr.dtype, IntegralType):
                        msg = (f"SUM({name}) accumulates in int64: with "
                               "ANSI off, |value|*rows beyond 2^63 wraps "
                               "silently (partial+final merges compound "
                               "the range)")
                    elif op in ("count", "countstar"):
                        # int64 counter: saturation needs ~9.2e18 rows
                        pass
                    elif op == "sum" and s.input_expr is not None and \
                            str(s.input_expr.dtype) == "float":
                        msg = (f"SUM({name}) over float32 input "
                               "accumulates in float64 (precision, not "
                               "overflow)")
                    if msg and msg not in self.report.overflow_risks:
                        self.report.overflow_risks.append(msg)


class HashAggMergeProxy:
    """Adapter: the fused aggregate's merge step behaves like a final-mode
    HashAggregateExec over the partial buffers (same grouping/specs)."""

    def __init__(self, fused):
        self.grouping = fused.grouping
        self.specs = fused.specs
        self._inner = fused

    def _plan_values(self):
        from ..physical.aggregates import PARTIAL_TO_MERGE

        out = []
        for s in self.specs:
            for i, op in enumerate(s.ops):
                out.append((PARTIAL_TO_MERGE.get(op, op),
                            s.buffer_attrs[i], s.param))
        return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def analyze_plan(plan, conf: SQLConf, cluster: bool = False) -> AnalysisReport:
    """Analyze an optimized PHYSICAL plan. Predictions model one WARM
    execution: kernel caches compiled, device-cached scans resident, and
    the device-scalar memo primed (first runs add one krange3 probe per
    distinct stable column plus the compile misses). `cluster` models
    execution under a cluster scheduler, where exchanges run the host
    shuffle path in worker map tasks instead of the driver-local mesh
    collective."""
    return _Analyzer(conf, cluster=cluster).run(plan)
