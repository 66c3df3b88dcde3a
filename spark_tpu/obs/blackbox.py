"""Query black box: anomaly-triggered diagnostic bundles.

Role of the reference's event log + history server postmortem story
(core/scheduler/EventLoggingListener.scala replaying an application's
lifecycle into the SHS), inverted for a serving engine: instead of
logging EVERYTHING always (the ship-always event log whose volume is
the first thing a fleet operator turns off), the engine keeps the
healthy path at structural zero cost and captures a complete,
self-contained diagnostic bundle only WHEN SOMETHING BREAKS — the
tail-sampled capture-on-anomaly discipline fleet-scale serving needs
(ROADMAP direction 2), and the only debuggability story compatible
with whole-query compilation, where a single opaque fused dispatch is
inexplicable without its surrounding evidence.

**Triggers.** Any severity-warning/error finding in the trigger set —
``obs.slo`` breach (PR 18), ``obs.regression`` (PR 12),
``obs.straggler`` (PR 6), ``tier.degraded`` (PR 10),
``exec.excluded`` (PR 11), admission rejection (``serve.rejected``),
query failure incl. chaos retry exhaustion (``query.failed``) — or an
explicit ``session.capture_diagnostics()``. Findings raised DURING a
query are swept at query close (QueryExecution.execute's close hook);
findings raised AFTER close (the SLO verdict lands on ticket release)
reach the LiveObs finding sink, which captures against the recently
closed QueryExecution. A deterministic 1-in-N
``spark.tpu.obs.bundle.sampleHealthy`` (default off) tail-samples
trigger-free queries as comparison baselines.

**Bundle contents** (one directory per bundle under
``spark.tpu.obs.bundleDir``, flock-safe bounded retention ring):

  * ``bundle.json`` — the manifest: triggering finding + full finding
    chain, non-default config, the PR 12 QueryProfile WITH its same-key
    baseline history (embedded — the bundle must render with no access
    to the profile store), DeviceLedger/executor state, the live-store
    snapshot, the metrics time-series ring window, and the pulled
    per-worker diagnostic rings.
  * ``trace.json`` — Chrome trace of the query's spans (driver tracks
    plus the ingested ``worker:<eid>/...`` tracks) with the pulled
    worker post-task rings appended as their own processes.
  * ``explain_simple.txt`` / ``explain_analysis.txt`` /
    ``explain_analyze.txt`` — plan reports; the analyze report is
    rendered from the ALREADY-RECORDED operator metrics (never by
    re-executing — capture launches zero kernels).
  * ``metrics.prom`` — the Prometheus scrape at capture time.

**Pull-on-anomaly.** Cross-process state is PULLED at bundle time via
the workers' ``diagnostic_state`` RPC (bounded post-task
span/counter/fault-registry/lockwatch rings kept in
exec/worker_main.py), never shipped on the healthy path — heartbeat
payloads are byte-identical with bundles armed.

Obs contract (PRs 3-18): everything here is host bookkeeping — zero
kernel launches, no mid-query device syncs. Off
(``spark.tpu.obs.bundles`` false) is structurally zero overhead: call
sites gate on the module bool ``ENABLED`` (one attribute read, the
utils/faults.py discipline). Armed-but-untriggered adds one
finding-chain scan per query close and zero launches
(tests/test_blackbox.py holds the launch-count identity).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
from collections import OrderedDict

from ..utils import lockwatch

__all__ = ["ENABLED", "TRIGGER_KINDS", "capture", "capture_failure",
           "configure", "is_trigger", "list_bundles", "load_bundle",
           "maybe_capture", "most_recent", "on_finding", "pack_bundle",
           "record_rejection", "reset"]

# fast-path flag (utils/faults.py discipline): instrumented call sites
# read ONE module attribute before doing anything — bundles off means
# no registry, no finding scans, no capture, structurally
ENABLED = False

# finding kinds that trigger a capture (at warning/error severity —
# advisory info findings, e.g. wall-clock drift, never bundle)
TRIGGER_KINDS = frozenset({
    "obs.slo", "obs.regression", "obs.straggler", "tier.degraded",
    "exec.excluded", "serve.rejected", "query.failed",
})
_TRIGGER_SEVERITIES = ("warning", "error")

_DIR = ""
_RING = 16
_SAMPLE_N = 0

_MAX_RECENT = 16        # recently closed QueryExecutions (SLO joins)
_MAX_CAPTURED = 256     # capture-once dedup window
_MAX_HISTORY = 8        # same-key baseline profiles embedded per bundle
_MAX_WORKER_TRACE = 4   # pulled worker rings appended to trace.json
_REJECT_MIN_GAP_S = 30.0  # rejection-bundle rate limit (overload guard)

_LOCK = threading.Lock()
lockwatch.register("obs.blackbox._LOCK", sys.modules[__name__], "_LOCK")

_RECENT: "OrderedDict" = OrderedDict()   # qid -> (qe, ctx)
_PENDING: set = set()    # qids whose trigger arrived before close
_CAPTURED: "OrderedDict" = OrderedDict()  # qid -> bundle id (dedup)
_HEALTHY_SEEN = 0
_SEQ = 0
_LAST_REJECT_T = 0.0


def configure(conf) -> None:
    """Apply a session/worker conf to the process-global switches.
    Called by TpuSession.__init__ and the worker-side begin_stage_obs
    (workers arm only their bounded post-task rings — bundle assembly
    is driver-only)."""
    global ENABLED, _DIR, _RING, _SAMPLE_N

    from ..config import (
        OBS_BUNDLE_DIR, OBS_BUNDLE_RING, OBS_BUNDLE_SAMPLE_HEALTHY,
        OBS_BUNDLES,
    )

    # conf values are host data — never touches a device
    _DIR = str(conf.get(OBS_BUNDLE_DIR) or "")
    _RING = max(int(conf.get(OBS_BUNDLE_RING)), 1)
    _SAMPLE_N = max(int(conf.get(OBS_BUNDLE_SAMPLE_HEALTHY)), 0)
    ENABLED = bool(conf.get(OBS_BUNDLES)) and bool(_DIR)


def reset() -> None:
    """Per-test re-init: drop the in-memory registries (the on-disk
    ring is the test's own tmpdir to manage)."""
    global ENABLED, _HEALTHY_SEEN, _LAST_REJECT_T, _SEQ
    with _LOCK:
        _RECENT.clear()
        _PENDING.clear()
        _CAPTURED.clear()
        _HEALTHY_SEEN = 0
        _LAST_REJECT_T = 0.0
        _SEQ = 0
    ENABLED = False


def is_trigger(finding: dict) -> bool:
    return (finding.get("kind") in TRIGGER_KINDS
            and finding.get("severity") in _TRIGGER_SEVERITIES)


def most_recent() -> tuple | None:
    """The most recently closed (qe, ctx), for explicit
    session.capture_diagnostics() with no DataFrame (None when the
    layer is unarmed or nothing closed yet)."""
    with _LOCK:
        if _RECENT:
            return next(reversed(_RECENT.values()))
    return None


# ---------------------------------------------------------------------------
# trigger evaluation
# ---------------------------------------------------------------------------

def maybe_capture(qe, ctx) -> str | None:
    """Query-close sweep (QueryExecution.execute, after the flight
    recorder closed): register the execution for post-close triggers,
    scan the finding chain, capture on any trigger, else apply the
    deterministic healthy sample. Returns the bundle id or None.
    Armed-but-untriggered cost: one findings read + dict upkeep — zero
    kernel launches."""
    global _HEALTHY_SEEN
    if not ENABLED:
        return None
    qid = getattr(ctx, "query_id", None)
    with _LOCK:
        if qid is not None:
            _RECENT[qid] = (qe, ctx)
            while len(_RECENT) > _MAX_RECENT:
                _RECENT.popitem(last=False)
        pending = qid in _PENDING
        _PENDING.discard(qid)
        if qid in _CAPTURED:
            return _CAPTURED[qid]
    live = getattr(qe.session, "live_obs", None)
    findings = live.findings_for(qid) if (live is not None and qid) else []
    trigger = next((f for f in findings if is_trigger(f)), None)
    if trigger is not None or pending:
        return capture(qe.session, qe=qe, ctx=ctx, reason="anomaly",
                       trigger=trigger)
    if _SAMPLE_N > 0:
        with _LOCK:
            _HEALTHY_SEEN += 1
            hit = (_HEALTHY_SEEN % _SAMPLE_N) == 0
        if hit:
            return capture(qe.session, qe=qe, ctx=ctx, reason="sampled")
    return None


def on_finding(session, qid: str | None, finding: dict) -> str | None:
    """LiveObs finding sink: a trigger finding landing AFTER the query
    closed (the obs.slo verdict is raised on ticket release) captures
    against the recently closed QueryExecution; one landing mid-query
    marks the qid pending for the close sweep."""
    if not ENABLED or qid is None or not is_trigger(finding):
        return None
    with _LOCK:
        if qid in _CAPTURED:
            return None
        ent = _RECENT.get(qid)
        if ent is None:
            # query still executing: the close sweep will capture
            _PENDING.add(qid)
            return None
    qe, ctx = ent
    return capture(session, qe=qe, ctx=ctx, reason="anomaly",
                   trigger=finding)


def capture_failure(qe, ctx, error: BaseException) -> str | None:
    """Failed-query capture (chaos retry exhaustion, stage-regeneration
    limit, any fatal execution error): synthesize the query.failed
    finding and bundle the partial evidence before the error
    propagates."""
    if not ENABLED:
        return None
    finding = {
        "severity": "error", "kind": "query.failed",
        "error_class": getattr(error, "error_class", None)
        or type(error).__name__,
        "msg": f"query failed: {type(error).__name__}: "
               f"{str(error)[:300]}"}
    return capture(qe.session, qe=qe, ctx=ctx, reason="failure",
                   trigger=finding, extra_findings=[finding])


def _rejection_analysis(qe) -> dict | None:
    """Predicted-HBM summary of a REJECTED plan from the serving
    pre-flight's AnalysisReport (stashed on the QueryExecution as
    `_preflight_report`): the bundle shows what the admission gate
    believed — predicted peak and the largest stage — without paying a
    second whole-plan analysis at capture time."""
    rep = getattr(qe, "_preflight_report", None) if qe is not None else None
    if rep is None:
        return None
    try:
        stages = list(getattr(rep, "stages", None) or [])
        largest = None
        for s in stages:
            hb = s.get("hbm_bytes")
            if hb and (largest is None
                       or hb > largest.get("hbm_bytes", 0)):
                detail = " ".join(str(s.get("detail") or "").split())
                largest = {"detail": detail[:160], "hbm_bytes": hb}
        return {
            "predicted_peak_hbm": getattr(rep, "predicted_peak_hbm", None),
            "memory_exact": getattr(rep, "memory_exact", None),
            "memory_notes": list(getattr(rep, "memory_notes", None) or []),
            "largest_stage": largest,
        }
    except Exception:
        return None


def record_rejection(session, error: BaseException,
                     pool: str | None = None, qe=None) -> str | None:
    """Admission-rejection capture (PoolQueueFull / AdmissionTimeout /
    memory-budget pre-flight): no query ran, so the bundle carries the
    serving/metrics state that explains the rejection — plus, when the
    rejected QueryExecution is handed over, the pre-flight analysis
    report that drove the verdict. Rate-limited — a saturated pool
    rejecting hundreds of queries must not turn the capture layer into
    its own overload."""
    global _LAST_REJECT_T
    if not ENABLED:
        return None
    now = time.monotonic()
    with _LOCK:
        if now - _LAST_REJECT_T < _REJECT_MIN_GAP_S and _LAST_REJECT_T:
            return None
        _LAST_REJECT_T = now
    analysis = _rejection_analysis(qe)
    finding = {
        "severity": "error", "kind": "serve.rejected",
        "pool": pool,
        "error_class": getattr(error, "error_class", None)
        or type(error).__name__,
        "msg": f"admission rejected: {type(error).__name__}: "
               f"{str(error)[:300]}"}
    if analysis is not None:
        finding["rejection_analysis"] = analysis
        peak = analysis.get("predicted_peak_hbm")
        big = analysis.get("largest_stage") or {}
        if peak:
            finding["msg"] += (
                f" | predicted peak HBM {peak} B"
                + (f", largest stage: {big.get('detail')} "
                   f"({big.get('hbm_bytes')} B)" if big else ""))
    return capture(session, qe=qe, reason="rejection", trigger=finding,
                   extra_findings=[finding],
                   extra_manifest=None if analysis is None
                   else {"rejection_analysis": analysis})


# ---------------------------------------------------------------------------
# bundle assembly (driver-only; every input is host-side metadata)
# ---------------------------------------------------------------------------

def _json_default(o):
    if isinstance(o, (set, frozenset)):
        return sorted(map(str, o))
    if isinstance(o, bytes):
        return o.decode("utf-8", "replace")
    return str(o)


def _query_trace(session, qe, ctx, workers: dict) -> dict | None:
    """Chrome trace of the query's spans: the driver tracer's raw spans
    tagged with this query (worker task spans were ingested there with
    worker:<eid>/ track prefixes during execution), plus the pulled
    worker post-task rings as their own trace processes."""
    from .tracing import to_chrome_trace

    tracer = getattr(session, "tracer", None)
    qid = getattr(ctx, "query_id", None) if ctx is not None else None
    if tracer is None or qid is None:
        return None
    raw = [s for s in tracer.spans() if len(s) > 7 and s[7] == qid]
    trace = to_chrome_trace(raw, process_name="driver", pid=1)
    pid = 2
    for eid in sorted(workers)[:_MAX_WORKER_TRACE]:
        wspans = [tuple(s)
                  for t in (workers[eid] or {}).get("tasks", [])
                  for s in (t.get("spans") or [])]
        if not wspans:
            continue
        sub = to_chrome_trace(wspans, process_name=f"executor {eid}",
                              pid=pid)
        trace["traceEvents"].extend(sub["traceEvents"])
        pid += 1
    return trace


def _analyze_text(qe, ctx, findings: list) -> str:
    """EXPLAIN ANALYZE rendered from the ALREADY-RECORDED run: the
    measured per-operator metrics (plan_graph) and the finding chain.
    Never calls analyzed_report() — that re-executes the query, and
    capture must launch zero kernels."""
    lines = ["== Physical Plan ==", qe.physical.tree_string(), "",
             "== Measured Operator Metrics (recorded run) =="]
    try:
        for n in qe.plan_graph():
            pad = "  " * int(n.get("depth") or 0)
            bits = [f"rows={n.get('rows')}", f"ms={n.get('ms')}"]
            if n.get("launches") is not None:
                bits.append(f"launches={n.get('launches')}")
            lines.append(f"{pad}{n.get('op')}  "
                         f"[{', '.join(bits)}]  {n.get('detail') or ''}")
    except Exception as e:
        lines.append(f"(operator metrics unavailable: {e})")
    lines.append("")
    lines.append("== Findings ==")
    if findings:
        for f in findings:
            lines.append(f"[{f.get('severity')}] {f.get('kind')}: "
                         f"{f.get('msg')}")
    else:
        lines.append("(none)")
    return "\n".join(lines) + "\n"


def _profile_section(qe, session) -> tuple:
    """The close-time QueryProfile plus its same-key baseline history,
    EMBEDDED so diagnose.py renders counter drift with no access to
    the live profile store."""
    profile = getattr(qe, "_last_profile", None) if qe is not None else None
    history: list = []
    if profile is not None:
        from ..config import OBS_PROFILE_DIR, OBS_PROFILE_RING
        from .history import ProfileStore

        root = str(session.conf.get(OBS_PROFILE_DIR) or "")
        if root and os.path.isdir(root):
            try:
                store = ProfileStore(
                    root, ring=int(session.conf.get(OBS_PROFILE_RING)))
                history = store.profiles(profile["query_key"])
                # the fresh profile is the store's newest line — history
                # for drift rendering is everything before it
                history = [p for p in history
                           if p.get("ts") != profile.get("ts")
                           or p.get("query_id") != profile.get("query_id")
                           ][-_MAX_HISTORY:]
            except Exception:
                history = []
    return profile, history


def _pull_workers(session) -> dict:
    """Pull-on-anomaly: the diagnostic_state RPC fan-out, called ONLY
    here (bundle time). Unreachable workers are skipped — a postmortem
    of a sick fleet must capture the healthy remainder."""
    cluster = getattr(session, "_sql_cluster", None)
    pull = getattr(cluster, "diagnostic_state", None)
    if pull is None:
        return {}
    try:
        return pull() or {}
    except Exception:
        return {}


def capture(session, qe=None, ctx=None, reason: str = "manual",
            trigger: dict | None = None,
            extra_findings: list | None = None,
            bundle_dir: str | None = None,
            extra_manifest: dict | None = None) -> str | None:
    """Assemble one self-contained diagnostic bundle. Pure host work at
    capture time: plan/trace/metrics/profile state already recorded,
    worker rings pulled over RPC, everything serialized under the
    flock-safe retention ring. Returns the bundle id (None when no
    bundle dir is configured)."""
    global _SEQ
    from ..utils.diskstore import JsonlRing
    from . import export as _export
    from .resources import GLOBAL_LEDGER

    conf = session.conf
    if bundle_dir is None:
        from ..config import OBS_BUNDLE_DIR

        bundle_dir = _DIR or str(conf.get(OBS_BUNDLE_DIR) or "")
    if not bundle_dir:
        return None
    os.makedirs(bundle_dir, exist_ok=True)
    qid = getattr(ctx, "query_id", None) if ctx is not None else None
    live = getattr(session, "live_obs", None)

    # finding chain: everything the live store holds for this query,
    # plus synthetic findings (query.failed / serve.rejected)
    chain: list = []
    if live is not None and qid:
        try:
            chain = list(live.findings_for(qid))
        except Exception:
            chain = []
    chain.extend(extra_findings or [])
    if trigger is None:
        trigger = next((f for f in chain if is_trigger(f)), None)

    workers = _pull_workers(session)

    with _LOCK:
        _SEQ += 1
        seq = _SEQ
    bid = f"{int(time.time() * 1000):013d}-{os.getpid()}-{seq:03d}"
    bdir = os.path.join(bundle_dir, f"bundle-{bid}")
    os.makedirs(bdir, exist_ok=True)

    files = ["bundle.json"]

    # Chrome trace (driver + ingested worker tracks + pulled rings)
    trace = _query_trace(session, qe, ctx, workers)
    if trace is not None:
        with open(os.path.join(bdir, "trace.json"), "w") as f:
            json.dump(trace, f, default=_json_default)
        files.append("trace.json")

    # plan reports — host-only renders of already-computed state
    explains = {}
    if qe is not None:
        for mode, fname in (("simple", "explain_simple.txt"),
                            ("analysis", "explain_analysis.txt")):
            try:
                txt = qe.explain_string(
                    "formatted" if mode == "simple" else mode)
            except Exception as e:
                txt = f"(explain {mode} failed: {e})\n"
            with open(os.path.join(bdir, fname), "w") as f:
                f.write(txt)
            explains[mode] = fname
            files.append(fname)
        try:
            txt = _analyze_text(qe, ctx, chain)
        except Exception as e:
            txt = f"(explain analyze failed: {e})\n"
        with open(os.path.join(bdir, "explain_analyze.txt"), "w") as f:
            f.write(txt)
        explains["analyze"] = "explain_analyze.txt"
        files.append("explain_analyze.txt")

    # metrics plane: the scrape + the time-series ring window
    try:
        prom = _export.REGISTRY.render_prometheus()
    except Exception:
        prom = ""
    with open(os.path.join(bdir, "metrics.prom"), "w") as f:
        f.write(prom)
    files.append("metrics.prom")
    try:
        timeseries = _export.timeseries_snapshot()
    except Exception:
        timeseries = {}

    profile, history = _profile_section(qe, session)

    hbm: dict = {}
    try:
        hbm["ledger"] = GLOBAL_LEDGER.snapshot()
        if qid:
            hbm["query"] = GLOBAL_LEDGER.query_record(qid)
    except Exception:
        pass

    live_snap = None
    if live is not None:
        try:
            live_snap = live.snapshot()
        except Exception:
            live_snap = None

    manifest = {
        "v": 1,
        "id": bid,
        "ts": round(time.time(), 3),
        "reason": reason,
        "query_id": qid,
        "trigger": trigger,
        "findings": chain,
        "conf_overrides": {k: str(v)
                           for k, v in sorted(conf.overrides().items())},
        "plan": {
            "detail": (qe.physical.simple_string()[:200]
                       if qe is not None
                       and hasattr(qe.physical, "simple_string")
                       else None),
            "phases": {k: round(v * 1000, 3)
                       for k, v in (qe.phase_times if qe is not None
                                    else {}).items()},
            "fingerprint": (profile or {}).get("fingerprint"),
            "query_key": (profile or {}).get("query_key"),
        } if qe is not None else None,
        "profile": profile,
        "profile_history": history,
        "metrics": {"export_enabled": _export.ENABLED,
                    "timeseries": timeseries},
        "hbm": hbm,
        "live": live_snap,
        "workers": workers,
        "explain": explains,
        "files": files,
    }
    if extra_manifest:
        manifest.update(extra_manifest)
    with open(os.path.join(bdir, "bundle.json"), "w") as f:
        json.dump(manifest, f, default=_json_default)

    # flock-safe retention ring: index append + oldest-dir pruning run
    # under one sidecar lock, so concurrent capturing processes agree
    index = JsonlRing(os.path.join(bundle_dir, "index.jsonl"),
                      ring=_RING)
    entry = {"id": bid, "ts": manifest["ts"], "reason": reason,
             "query_id": qid,
             "trigger_kind": (trigger or {}).get("kind"),
             "severity": (trigger or {}).get("severity"),
             "findings": len(chain), "dir": f"bundle-{bid}"}
    with index.locked():
        index.append(entry)
        keep = {e.get("id") for e in index.load()[-_RING:]}
        for name in sorted(os.listdir(bundle_dir)):
            if not name.startswith("bundle-"):
                continue
            if name[len("bundle-"):] not in keep:
                shutil.rmtree(os.path.join(bundle_dir, name),
                              ignore_errors=True)

    if qid is not None:
        with _LOCK:
            _CAPTURED[qid] = bid
            while len(_CAPTURED) > _MAX_CAPTURED:
                _CAPTURED.popitem(last=False)
        # surface the bundle id where operators already look: EXPLAIN
        # ANALYZE findings and pool-status slo_findings both render the
        # live store's finding chain
        if live is not None:
            try:
                live.add_finding(qid, {
                    "severity": "info", "kind": "obs.bundle",
                    "bundle_id": bid,
                    "msg": f"diagnostic bundle {bid} captured "
                           f"({reason}) under {bundle_dir}"})
            except Exception:
                pass
    return bid


# ---------------------------------------------------------------------------
# offline readers (history server /bundles, dev/diagnose.py)
# ---------------------------------------------------------------------------

def list_bundles(bundle_dir: str) -> list[dict]:
    """Index entries whose bundle directory still exists, newest first.
    Lockless (JSONL lines are self-delimiting; a torn tail is
    skipped)."""
    from ..utils.diskstore import JsonlRing

    path = os.path.join(bundle_dir, "index.jsonl")
    if not os.path.isfile(path):
        return []
    out = []
    for e in JsonlRing(path).load():
        d = e.get("dir")
        if d and os.path.isdir(os.path.join(bundle_dir, d)):
            out.append(e)
    out.reverse()
    return out


def load_bundle(bundle_dir: str, bundle_id: str) -> dict | None:
    """One bundle's manifest by id (None when unknown/pruned)."""
    path = os.path.join(bundle_dir, f"bundle-{bundle_id}", "bundle.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def pack_bundle(bundle_dir: str, bundle_id: str,
                out: str | None = None) -> str:
    """Pack one bundle directory into a single .tar.gz for attaching to
    a ticket / shipping off-host (dev/diagnose.py --tar). The archive
    root is the bundle directory name, so unpacking next to a bundle dir
    round-trips into something list_bundles/load_bundle/diagnose can
    read directly. Returns the archive path."""
    import tarfile

    bdir = os.path.join(bundle_dir, f"bundle-{bundle_id}")
    if not os.path.isdir(bdir):
        raise FileNotFoundError(f"no such bundle: {bundle_id}")
    if out is None:
        out = os.path.join(bundle_dir, f"bundle-{bundle_id}.tar.gz")
    tmp = out + ".tmp"
    with tarfile.open(tmp, "w:gz") as tf:
        tf.add(bdir, arcname=f"bundle-{bundle_id}")
    os.replace(tmp, out)   # readers never see a torn archive
    return out
