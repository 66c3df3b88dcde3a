"""Query flight recorder: plan fingerprints, persistent run profiles,
and deterministic perf-regression detection.

Role of the reference's SQLAppStatusStore + event-log-based history
(sqlx/execution/ui/SQLAppStatusStore.scala, the history server's replay
of per-execution metrics), re-keyed for an engine whose dominant costs
are COMPILES and DISPATCHES rather than task wall-time: every byte of
in-process observability PRs 3/4/6/7 built (attributed traces, live
telemetry, HBM/roofline accounting) dies with the process, so nothing
identifies "the same query" across runs — a restarted server cannot
know a compile is cold (Flare's central lesson: native compilation
makes compile cost the dominant latency tax), and the bench trajectory
cannot gate on the counters we predict exactly.

Four legs, all under the obs contract (ZERO kernel launches, no
mid-query device syncs — every input is host-side metadata the obs
layer already holds, and assembly runs at query close, after the
query's last device interaction):

  * **Plan fingerprinting** — two canonical structural hashes per query.
    `plan_fingerprint` hashes the executed physical plan (operator tree
    with expression/literal detail, input schemas + leaf row counts,
    capacity/partition shape, tier-relevant config) with per-stage
    sub-fingerprints cut at exchange boundaries: the exact key a
    persistent compile cache / result cache reuses (ROADMAP direction
    1 — same plan, same signatures, same tier ⇒ same programs).
    `query_key` hashes the optimized LOGICAL plan + workload-shape
    config only, deliberately EXCLUDING execution strategy (compile
    tier, fusion, encoding): it identifies "the same query" across
    strategy changes, so a tier flip — or a code change that flips the
    tier chooser — lands on the same baseline and surfaces as counter
    drift instead of vanishing under a fresh fingerprint.

  * **QueryProfile** — one JSON record assembled at query close from
    stores that already exist: per-operator metric records, per-kind
    kernel launch/compile deltas (driver + shipped worker totals in
    cluster mode), the tier decision incl. fallback/degrade reasons,
    retry/fault/exclusion counters, straggler/degrade/wasted-work
    findings, HBM watermarks (device ledger + captured XLA temp
    scratch), and per-stage runtime output stats (rows, key spans from
    shuffle col stats, dictionary-domain cardinalities) — the carrier
    ROADMAP direction 3's runtime re-admission reads.

  * **ProfileStore** — append-only JSONL under
    `spark.tpu.obs.profileDir`, one file per structural query key, each
    line one profile stamped with its full fingerprint. Appends are
    process-safe (flock) and the file is a bounded ring
    (`spark.tpu.obs.profileRing`): once it doubles the bound it
    compacts to the newest N. The driver owns all writes — worker
    processes never touch the store.

  * **Regression detection** — at query close the fresh profile
    compares against the MEDIAN of the last N stored profiles for the
    same query key. Deterministic counters (kernel launches by kind,
    compile count, retry/fault attempts) raise severity-`error`
    `obs.regression` findings when they EXCEED the baseline (cold→warm
    improvements never fire); wall-clock and HBM drift raise advisory
    `info` findings (noisy on a shared box — never an error). Findings
    land in the live store, so EXPLAIN ANALYZE and live status surface
    them.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import statistics
import time

__all__ = ["DETERMINISTIC_COUNTERS", "ProfileStore", "build_profile",
           "close_query_profile", "detect_regressions", "plan_fingerprint",
           "query_key"]


# Concurrency note (PR 15, supersedes the PR 12 overlap guard): profile
# deltas are no longer process-snapshot differences. Kernel events come
# from the per-query QueryKernelLedger (obs/metrics.py, carried by a
# contextvar through par_map lanes and scoped_submit pools) and counter
# deltas from ExecContext's ScopedMetrics, so two queries collecting
# concurrently on one process read DISJOINT, exact deltas. Profiles
# recorded under load are therefore baseline-eligible and
# regression-checked like any other — the `overlapped` mark and its
# guard are gone.


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------

# volatile tokens that must not reach a cross-process fingerprint:
# expression ids (#12 and the bare `ids=(0, 1)` tuples logical leaves
# print — both allocated from a per-process counter), memory addresses
# (0x7f..., "at 0x..."), and long hex ids (uuids in shuffle/cache
# names). The hex-id rule requires at least one [a-f]: a pure-decimal
# 12+ digit literal (epoch millis in a WHERE clause) is query IDENTITY
# and must stay in the hash, or two such queries would collide.
_VOLATILE = re.compile(
    r"#\d+|\bids=\([0-9,\s]*\)|0x[0-9a-fA-F]+"
    r"|\b(?=[0-9a-f]*[a-f])[0-9a-f]{12,}\b")


def _sanitize(s: str) -> str:
    return _VOLATILE.sub("#", " ".join(str(s).split()))


def _hash(s: str) -> str:
    return hashlib.sha256(s.encode("utf-8", "replace")).hexdigest()[:16]


def _node_detail(node) -> str:
    d = node.simple_string() if hasattr(node, "simple_string") \
        else type(node).__name__
    return _sanitize(d)[:200]


def _node_schema(node) -> tuple:
    try:
        return tuple((a.name, str(a.dtype), bool(a.nullable))
                     for a in node.output)
    except Exception:
        return ()


def _leaf_rows(node):
    """Exact plan-time leaf row count when known (the same statistics the
    tier chooser reads) — part of the fingerprint's 'input capacities'."""
    from ..physical.whole_query import _leaf_rows as lr

    try:
        return lr(node)
    except Exception:
        return None


def _canon_children(node) -> list:
    """A node's structural children INCLUDING through the whole-query
    wrapper (child_fields=() makes its inner plan invisible to the plan
    walkers, but two different queries wrapped whole must not collide)."""
    kids = list(node.children)
    inner = getattr(node, "plan", None)
    if not kids and inner is not None and hasattr(inner, "children"):
        kids = [inner]
    return kids


def _tier_conf(conf) -> list:
    """Execution-strategy config that changes WHICH programs compile —
    part of the full fingerprint (a compile cache keyed without these
    would serve a stage-tier program to a whole-tier session)."""
    from ..config import (
        AGG_BLOCK_ROWS, BATCH_CAPACITY, COMPILE_TIER, ENCODING_ENABLED,
        FUSION_DENSE_KEYS, FUSION_ENABLED, FUSION_EXCHANGE, FUSION_MESH,
        FUSION_MIN_ROWS, MESH_ENABLED, SHUFFLE_PARTITIONS, WHOLE_MIN_ROWS,
    )

    entries = (COMPILE_TIER, FUSION_ENABLED, FUSION_EXCHANGE, FUSION_MESH,
               FUSION_MIN_ROWS, FUSION_DENSE_KEYS, WHOLE_MIN_ROWS,
               ENCODING_ENABLED, MESH_ENABLED, BATCH_CAPACITY,
               SHUFFLE_PARTITIONS, AGG_BLOCK_ROWS)
    return [(e.key, str(conf.get(e))) for e in entries]


def _workload_conf(conf) -> list:
    """Workload-SHAPE config (tile capacity, partition fan-out) — part
    of the structural query key: changing these changes how much work
    the same query is, so profiles across them must not compare. The
    strategy knobs (tier/fusion/encoding) are deliberately excluded —
    see module docstring."""
    from ..config import BATCH_CAPACITY, SHUFFLE_PARTITIONS

    return [(e.key, str(conf.get(e)))
            for e in (BATCH_CAPACITY, SHUFFLE_PARTITIONS)]


def plan_fingerprint(physical, conf) -> dict:
    """Canonical structural hash of an executed physical plan, with
    per-stage sub-fingerprints cut at exchange boundaries (the stage =
    the compile unit, so the sub-fingerprint is the per-stage compile
    cache key). Pure host work over plan metadata — memoized on the
    plan root keyed by the tier-relevant conf (the persistent-cache
    paths fingerprint the same plan several times per query: the
    result-cache probe, the manifest seed lookup, plan_lint's mirrors,
    and the close-time profile)."""
    from ..physical.exchange import (
        BroadcastExchangeExec, ShuffleExchangeExec,
    )

    memo_key = json.dumps(_tier_conf(conf), sort_keys=True)
    memo = getattr(physical, "_fp_memo", None)
    if memo is not None and memo[0] == memo_key:
        return memo[1]

    stages: list[dict] = []
    leaves: list[tuple] = []

    def canon(node) -> str:
        parts = [type(node).__name__, _node_detail(node),
                 repr(_node_schema(node))]
        kids = _canon_children(node)
        if not kids:
            rows = _leaf_rows(node)
            leaves.append((type(node).__name__, _node_schema(node), rows))
            parts.append(f"rows={rows}")
        for c in kids:
            if isinstance(c, (ShuffleExchangeExec, BroadcastExchangeExec)):
                parts.append(f"<stage:{canon_stage(c)}>")
            else:
                parts.append(canon(c))
        return "(" + "|".join(parts) + ")"

    def canon_stage(root) -> str:
        s = canon(root)
        fp = _hash(s)
        stages.append({"op": type(root).__name__,
                       "detail": _node_detail(root)[:120],
                       "fingerprint": fp})
        return fp

    root = canon_stage(physical)
    full = _hash(json.dumps(
        {"root": root, "stages": [s["fingerprint"] for s in stages],
         "conf": _tier_conf(conf)}, sort_keys=True))
    out = {"fingerprint": full, "root_stage": root,
           "stages": list(reversed(stages)),  # produce->consume order
           "leaves": [{"op": op, "schema": list(map(list, sch)),
                       "rows": rows} for op, sch, rows in leaves]}
    try:
        physical._fp_memo = (memo_key, out)
    except Exception:
        pass  # slotted/frozen plan node: skip the memo
    return out


def query_key(optimized_logical, conf) -> str:
    """Structural identity of 'the same query' across execution-strategy
    changes: the optimized logical plan (tier/fusion/encoding are
    physical concerns and never appear in it) plus workload-shape
    config. The regression baseline is keyed by this."""
    try:
        tree = optimized_logical.tree_string()
    except Exception:
        tree = repr(type(optimized_logical).__name__)
    return _hash(json.dumps(
        {"plan": _sanitize(tree), "conf": _workload_conf(conf)},
        sort_keys=True))


# ---------------------------------------------------------------------------
# QueryProfile assembly
# ---------------------------------------------------------------------------

# per-query counter deltas whose values are DETERMINISTIC given the plan
# and the fault schedule — exact equality is gated on, so anything noisy
# (wall, bytes) must never appear here
DETERMINISTIC_COUNTERS = (
    "join.capacity_retry",
    "whole_query.capacity_retries",
    "whole_query.runtime_degraded",
    "scheduler.stage_retries",
    "scheduler.fetch_failures",
    "scheduler.task_failures_salvaged",
    "shuffle.fetch_retries",
)

# counter-delta prefixes worth persisting beyond the deterministic set
# (profile forensics: what did this run actually do). "compile." and
# "result_cache." carry the persistent-cache attribution (PR 14):
# disk-served vs true cold XLA compiles, result-cache hit/miss/store.
_COUNTER_PREFIXES = ("scheduler.", "shuffle.", "join.", "whole_query.",
                     "adaptive.", "cache.", "mesh.", "compile.",
                     "result_cache.")

_MAX_PROFILE_NODES = 64
_MAX_PROFILE_FINDINGS = 16
_MAX_WASTED = 8


def _tier_section(physical) -> dict | None:
    dec = getattr(physical, "decision", None) \
        or getattr(physical, "_tier_decision", None)
    if dec is None:
        return None
    out = dec.to_dict() if hasattr(dec, "to_dict") else dict(dec)
    if getattr(physical, "_degraded", False):
        out["degraded"] = True
    return out


def _stage_stats(physical, rec: dict | None) -> list:
    """Per-stage runtime OUTPUT statistics from host-side stores that
    already exist: rows/batches from the operator records, key spans
    from the shuffle write's accumulated column stats, and
    dictionary-domain cardinalities from leaf arrow schemas — the
    runtime-readmission carrier (ROADMAP direction 3)."""
    from ..physical.exchange import ShuffleExchangeExec
    from .metrics import metric_key

    rec = rec or {}
    out = []
    for node in physical.iter_nodes():
        ent = rec.get(metric_key(node)) or {}
        st = {"op": type(node).__name__,
              "detail": _node_detail(node)[:120]}
        if ent:
            st["rows"] = ent.get("rows")
            st["batches"] = ent.get("batches")
        if isinstance(node, ShuffleExchangeExec):
            spans: dict = {}
            for cols in (getattr(node, "last_col_stats", None) or
                         {}).values():
                for ci, (lo, hi, any_v) in cols.items():
                    if not any_v:
                        continue
                    cur = spans.get(ci)
                    spans[ci] = (min(cur[0], lo), max(cur[1], hi)) \
                        if cur else (lo, hi)
            if spans:
                st["key_spans"] = {str(ci): [int(lo), int(hi)]
                                   for ci, (lo, hi) in sorted(spans.items())}
        if not node.children:
            doms = _dict_domains(node)
            if doms:
                st["dict_domains"] = doms
        if len(st) > 2:  # only stages that contributed a runtime stat
            out.append(st)
        if len(out) >= _MAX_PROFILE_NODES:
            break
    return out


def _dict_domains(leaf) -> dict:
    """Dictionary-domain cardinality per dictionary-typed leaf column
    (arrow schema metadata only — never touches column values)."""
    import pyarrow as pa

    t = getattr(leaf, "table", None)
    if t is None:
        from ..physical.whole_query import _scan_table

        t = _scan_table(leaf)
    if not isinstance(t, pa.Table):
        return {}
    out = {}
    for i, f in enumerate(t.schema):
        if pa.types.is_dictionary(f.type):
            try:
                chunk = t.column(i).chunk(0)
                out[f.name] = int(len(chunk.dictionary))
            except Exception:
                pass
    return out


def _xla_temp_peak(kinds: dict) -> int | None:
    """Peak XLA temp (scratch) bytes among the kernel kinds this query
    launched, from the cost table's memory_analysis capture
    (spark.tpu.metrics.kernelMemory). Scratch is live only inside one
    kernel, so the concurrent peak is the max, not the sum."""
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    peak = None
    for kind in kinds:
        ent = KC.cost_by_kind.get(kind)
        tb = (ent or {}).get("temp_bytes")
        if tb:
            peak = max(peak or 0, int(tb))
    return peak


def build_profile(qe, ctx, fingerprint: dict, qkey: str, wall_s: float,
                  kinds: dict, counter_deltas: dict, compiles: int,
                  compile_ms: float, compiles_disk_hit: int = 0) -> dict:
    """One QueryProfile record from the close-time state. Everything
    here is host metadata; caps keep a line small enough that the ring
    file stays cheap to compact."""
    from .metrics import iter_plan_metrics
    from .resources import GLOBAL_LEDGER

    physical = qe.physical
    rec = getattr(ctx, "plan_metrics", None)
    ops = []
    if rec:
        for node, depth, key, fields in iter_plan_metrics(physical, rec):
            ops.append({"id": key, "depth": depth,
                        "op": type(node).__name__,
                        "detail": _node_detail(node)[:120],
                        "rows": fields["rows"],
                        "batches": fields["batches"],
                        "ms": fields["ms"],
                        "launches": fields["launches"],
                        "compile_ms": fields["compile_ms"]})
            if len(ops) >= _MAX_PROFILE_NODES:
                break
    counters = {k: v for k, v in counter_deltas.items()
                if v and (k in DETERMINISTIC_COUNTERS
                          or k.startswith(_COUNTER_PREFIXES))}
    hbm: dict = {}
    res = GLOBAL_LEDGER.query_record(getattr(ctx, "query_id", None))
    if res is not None:
        hbm["peak"] = res.get("peak")
        if res.get("remote"):
            hbm["remote"] = {e: v.get("peak")
                             for e, v in res["remote"].items()}
    temp = _xla_temp_peak(kinds)
    if temp is not None:
        hbm["xla_temp_peak"] = temp
    live = getattr(ctx, "live_obs", None)
    findings = []
    if live is not None:
        findings = [
            {"severity": f.get("severity"), "kind": f.get("kind"),
             "msg": str(f.get("msg"))[:200]}
            for f in live.findings_for(getattr(ctx, "query_id", None))
        ][:_MAX_PROFILE_FINDINGS]
    wasted = [
        {k: w.get(k) for k in ("stage", "task", "executor", "error",
                               "kernel_kinds", "launches", "compile_ms",
                               "spans")}
        for w in (getattr(ctx, "failed_attempt_obs", None) or
                  [])][:_MAX_WASTED]
    profile = {
        "v": 1,
        "fingerprint": fingerprint["fingerprint"],
        "query_key": qkey,
        "stages": fingerprint["stages"],
        "ts": round(time.time(), 3),
        "query_id": getattr(ctx, "query_id", None),
        "detail": _node_detail(physical)[:140],
        "cluster": getattr(qe.session, "_sql_cluster", None) is not None,
        "wall_ms": round(wall_s * 1000, 3),
        "phases": {k: round(v * 1000, 3)
                   for k, v in qe.phase_times.items()},
        "tier": _tier_section(physical),
        "launches_by_kind": {k: int(v) for k, v in sorted(kinds.items())},
        "launch_total": int(sum(kinds.values())),
        "compiles": int(compiles),
        # engine compiles whose XLA backend compile was served from the
        # persistent disk cache (exec/persist_cache.py): a warm restart
        # shows compiles == compiles_disk_hit (zero TRUE cold compiles);
        # the per-query compile.disk_hit/miss deltas ride `counters`
        "compiles_disk_hit": int(compiles_disk_hit),
        "compile_ms": round(compile_ms, 3),
        "counters": counters,
        "ops": ops,
        "stage_stats": _stage_stats(physical, rec),
        "hbm": hbm,
    }
    if wasted:
        profile["wasted"] = wasted
    if findings:
        profile["findings"] = findings
    return profile


# ---------------------------------------------------------------------------
# persistent store
# ---------------------------------------------------------------------------

class ProfileStore:
    """Append-only JSONL store, one bounded ring file per query key.

    Writes are driver-only and process-safe; the flock-sidecar +
    ring-compaction mechanics live in the shared utils/diskstore.
    JsonlRing (one locking implementation for every on-disk metadata
    store — the persistent-cache manifest reuses it). Readers
    (HistoryReader-style APIs below, the history-server profiles page)
    take no lock: JSONL lines are self-delimiting and
    a torn tail line is skipped."""

    def __init__(self, root: str, ring: int = 32):
        self.root = root
        self.ring = max(int(ring), 1)
        os.makedirs(root, exist_ok=True)

    def _path(self, qkey: str) -> str:
        safe = re.sub(r"[^0-9a-zA-Z_-]", "_", qkey)
        return os.path.join(self.root, f"{safe}.jsonl")

    def _ring(self, path: str):
        from ..utils.diskstore import JsonlRing

        return JsonlRing(path, ring=self.ring)

    def append(self, profile: dict) -> None:
        self._ring(self._path(profile["query_key"])).append(profile)

    # -- reads (no lock: lines are self-delimiting) ------------------------
    def _load(self, path: str) -> list[dict]:
        return self._ring(path).load()

    def query_keys(self) -> list[str]:
        keys = []
        for name in sorted(os.listdir(self.root)):
            if name.endswith(".jsonl"):
                keys.append(name[:-len(".jsonl")])
        return keys

    def profiles(self, qkey: str, last: int | None = None) -> list[dict]:
        """Stored profiles for one query key, oldest first."""
        out = self._load(self._path(qkey))
        return out[-last:] if last else out

    def fingerprints(self) -> dict:
        """{full fingerprint: {query_key, profiles, last_ts, detail}} —
        the application-list shape the history-server profiles page
        renders."""
        out: dict = {}
        for qk in self.query_keys():
            for p in self.profiles(qk):
                fp = p.get("fingerprint")
                ent = out.setdefault(fp, {"query_key": qk, "profiles": 0,
                                          "last_ts": 0.0,
                                          "detail": p.get("detail", "")})
                ent["profiles"] += 1
                ent["last_ts"] = max(ent["last_ts"], p.get("ts") or 0.0)
                ent["detail"] = p.get("detail", ent["detail"])
        return out

    def profiles_for_fingerprint(self, fp: str) -> list[dict]:
        for qk in self.query_keys():
            hits = [p for p in self.profiles(qk)
                    if p.get("fingerprint") == fp]
            if hits:
                return hits
        return []


# ---------------------------------------------------------------------------
# regression detection
# ---------------------------------------------------------------------------

def _median(vals) -> float:
    vals = list(vals)
    return statistics.median(vals) if vals else 0.0


def detect_regressions(fresh: dict, history: list[dict],
                       baseline_n: int = 5,
                       wall_tolerance: float = 1.5,
                       hbm_tolerance: float = 1.25) -> list[dict]:
    """Compare a fresh profile against the median of the last
    `baseline_n` stored profiles for the same query key. Deterministic
    counters fire severity-`error` findings only on INCREASE (a warm
    run re-using compiles/memos legitimately measures below a cold
    baseline); wall/HBM drift is advisory `info`. Profiles recorded
    under concurrent load are baseline-eligible: their deltas are
    scope-exact (per-query kernel ledger + ScopedMetrics), not
    process-snapshot differences. Returns findings in the EXPLAIN
    ANALYZE shape ({severity, kind, msg, ...})."""
    base = history[-baseline_n:] if baseline_n else list(history)
    if not base:
        return []
    n = len(base)
    findings: list[dict] = []

    def err(metric: str, value, baseline) -> None:
        findings.append({
            "severity": "error", "kind": "obs.regression",
            "metric": metric, "value": value, "baseline": baseline,
            "msg": f"deterministic-counter regression vs stored baseline "
                   f"(median of last {n} run(s) of this query): {metric} "
                   f"= {value} > baseline {baseline:g}"})

    # kernel launches by kind — the primary deterministic signal
    kinds = set(fresh.get("launches_by_kind") or {})
    for p in base:
        kinds |= set(p.get("launches_by_kind") or {})
    for kind in sorted(kinds):
        v = (fresh.get("launches_by_kind") or {}).get(kind, 0)
        b = _median((p.get("launches_by_kind") or {}).get(kind, 0)
                    for p in base)
        if v > b:
            err(f"kernel launches '{kind}'", v, b)
    # compile count — more compiles than the baseline means a cache key
    # stopped hitting (warm runs measuring fewer never fire)
    v = fresh.get("compiles", 0)
    b = _median(p.get("compiles", 0) for p in base)
    if v > b:
        err("kernel compiles", v, b)
    # retry / fault attempts
    for key in DETERMINISTIC_COUNTERS:
        v = (fresh.get("counters") or {}).get(key, 0)
        b = _median((p.get("counters") or {}).get(key, 0) for p in base)
        if v > b:
            err(f"counter {key}", v, b)
    # advisory drift: wall and HBM are noisy — info only
    v = fresh.get("wall_ms") or 0.0
    b = _median(p.get("wall_ms") or 0.0 for p in base)
    if b > 1.0 and v > wall_tolerance * b:
        findings.append({
            "severity": "info", "kind": "obs.regression",
            "metric": "wall_ms", "value": v, "baseline": b,
            "msg": f"wall-clock drift (advisory): {v:.1f} ms > "
                   f"{wall_tolerance:g}x baseline median {b:.1f} ms"})
    v = (fresh.get("hbm") or {}).get("peak") or 0
    b = _median((p.get("hbm") or {}).get("peak") or 0 for p in base)
    if b > 0 and v > hbm_tolerance * b:
        findings.append({
            "severity": "info", "kind": "obs.regression",
            "metric": "hbm_peak", "value": v, "baseline": b,
            "msg": f"HBM watermark drift (advisory): {v} B > "
                   f"{hbm_tolerance:g}x baseline median {b:.0f} B"})
    return findings


# ---------------------------------------------------------------------------
# close hook (called by QueryExecution.execute)
# ---------------------------------------------------------------------------

def close_query_profile(qe, ctx, baseline: dict) -> tuple:
    """Assemble, persist, and regression-check one finished query.
    `baseline` holds the recorder's start-of-query snapshots
    (KernelCache kinds/misses/compile-ms, session counters,
    perf_counter t0) taken by QueryExecution when the recorder is on.
    Returns (profile, regression findings); never raises into the
    query path (the caller guards)."""
    from ..config import (
        OBS_PROFILE_BASELINE_N, OBS_PROFILE_DIR, OBS_PROFILE_REGRESSION,
        OBS_PROFILE_RING, OBS_PROFILE_WALL_TOLERANCE,
    )
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    conf = qe.session.conf
    root = str(conf.get(OBS_PROFILE_DIR) or "")  # tpulint: ignore[host-sync]
    if not root:
        return None, []
    wall_s = time.perf_counter() - baseline["t0"]
    ledger = getattr(ctx, "kernel_ledger", None)
    if ledger is not None:
        # scope-exact per-query deltas (obs/metrics.QueryKernelLedger):
        # concurrent collects on one process cannot contaminate them,
        # so profiles recorded under load stay baseline-eligible
        snap = ledger.snapshot()
        kinds = {k: v for k, v in snap["kinds"].items() if v}
        compiles = snap["compiles"]
        compile_ms = snap["compile_ms"]
        compiles_disk_hit = snap["disk_hit_compiles"]
    else:
        # no ledger on the context (direct build callers): fall back to
        # the recorder's process snapshots — exact only when serial
        kinds = {k: v - baseline["kinds"].get(k, 0)
                 for k, v in KC.launches_by_kind.items()
                 if v != baseline["kinds"].get(k, 0)}
        compiles = KC.misses - baseline["misses"]
        compile_ms = KC.compile_ms - baseline["compile_ms"]
        compiles_disk_hit = KC.disk_hit_compiles \
            - baseline.get("disk_hit_compiles", 0)
    # cluster mode: worker-process deltas shipped with the task results
    # fold into the same per-kind ledger (driver + worker totals)
    for k, v in (getattr(ctx, "worker_kernel_kinds", None) or {}).items():
        kinds[k] = kinds.get(k, 0) + v
    scoped = getattr(ctx.metrics, "local_counters", None)
    if scoped is not None:
        counter_deltas = {k: v for k, v in scoped().items() if v}
    else:
        counters = qe.session._metrics.snapshot()["counters"]
        counter_deltas = {k: v - baseline["counters"].get(k, 0)
                          for k, v in counters.items()
                          if v != baseline["counters"].get(k, 0)}
    fingerprint = qe.plan_fingerprint()
    qkey = query_key(qe.optimized, conf)
    profile = build_profile(
        qe, ctx, fingerprint, qkey, wall_s, kinds, counter_deltas,
        compiles=compiles, compile_ms=compile_ms,
        compiles_disk_hit=compiles_disk_hit)
    store = ProfileStore(root, ring=int(  # tpulint: ignore[host-sync]
        conf.get(OBS_PROFILE_RING)))
    history = store.profiles(qkey)
    store.append(profile)
    findings: list[dict] = []
    if bool(conf.get(  # tpulint: ignore[host-sync]
            OBS_PROFILE_REGRESSION)):
        findings = detect_regressions(
            profile, history,
            baseline_n=int(  # tpulint: ignore[host-sync]
                conf.get(OBS_PROFILE_BASELINE_N)),
            wall_tolerance=float(  # tpulint: ignore[host-sync]
                conf.get(OBS_PROFILE_WALL_TOLERANCE)))
        live = getattr(ctx, "live_obs", None)
        if live is not None:
            for f in findings:
                live.add_finding(getattr(ctx, "query_id", None), f)
    ctx.metrics.add("obs.profiles_recorded")
    if findings:
        ctx.metrics.add("obs.profile_regressions", len(findings))
    return profile, findings
