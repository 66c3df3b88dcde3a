"""Executor worker process entry point (gRPC backend).

Role of the reference's CoarseGrainedExecutorBackend.main
(core/executor/CoarseGrainedExecutorBackend.scala:181 LaunchTask →
core/executor/Executor.scala TaskRunner): register with the driver over
the network, serve task-launch RPCs, heartbeat until the driver goes
away.

Each worker's single RpcServer also serves the BLOCK plane (role of the
executor-side shuffle-block transport, common/network-shuffle
ExternalBlockHandler.java): map-stage outputs persist in this process
under (shuffle_id, reduce_id) and reducers running on OTHER workers (or
the driver) stream them directly in 4 MiB chunks — the driver never
carries shuffle bytes. Workers are joinable by address: any process that
can reach the driver's control endpoint and knows the cluster secret may
register (the standalone Worker/ExternalShuffleService deployment
model), which is what the two-"host" cluster test exercises.
"""

from __future__ import annotations

import os
import pickle
import sys
import threading
import time
import traceback

from ..net.transport import (
    BEST_EFFORT_RETRY, CHUNK_BYTES, RpcClient, RpcServer,
)
from ..utils import faults, lockwatch
from ..utils.counters import LockedCounter

# (shuffle_id, reduce_id) → Arrow IPC bytes; lives for the worker process
BLOCK_STORE: dict = {}
BLOCK_ADDR: str = ""
_STORE_LOCK = threading.Lock()
lockwatch.register("exec.worker_main._STORE_LOCK",
                   sys.modules[__name__], "_STORE_LOCK")


_PUSH_CLIENT = None


def _push_client() -> "RpcClient | None":
    global _PUSH_CLIENT

    push_addr = os.environ.get("SPARK_TPU_SHUFFLE_PUSH_ADDR")
    if not push_addr:
        return None
    with _STORE_LOCK:  # one client per process (racy init leaks)
        if _PUSH_CLIENT is None:
            _PUSH_CLIENT = RpcClient(
                push_addr, os.environ["SPARK_TPU_WORKER_KEY"])
        return _PUSH_CLIENT


def store_map_block(shuffle_id: str, map_id: int, num_maps: int,
                    reduce_id: int, data: bytes) -> None:
    """Store one map task's block for one reduce partition:
    in this worker's memory (serves reducer pulls), in the shared spill
    dir when the external shuffle service runs over one (durability),
    and — push mode — PUSHED to the service's per-reduce-partition
    merger over the network (ShuffleBlockPusher →
    RemoteBlockPushResolver push-merge path; no shared filesystem)."""
    from .map_output import map_block_id

    bid = map_block_id(shuffle_id, map_id, num_maps)
    if faults.ENABLED:
        faults.maybe_fail("shuffle.write", detail=f"{bid}:{reduce_id}")
    with _STORE_LOCK:
        BLOCK_STORE[(bid, reduce_id)] = data
    root = os.environ.get("SPARK_TPU_SHUFFLE_DIR")
    if root:
        from .shuffle_service import persist_block

        persist_block(root, bid, reduce_id, data)
    client = _push_client()
    if client is not None:
        # pushes are idempotent (the merger dedups by (map, reduce)) —
        # absorb a transient service flap instead of failing the task
        client.call(
            "push_block",
            pickle.dumps((shuffle_id, map_id, reduce_id, data)),
            timeout=120, retry=BEST_EFFORT_RETRY)


def put_block(shuffle_id: str, reduce_id: int, data: bytes) -> None:
    store_map_block(shuffle_id, 0, 1, reduce_id, data)


# ---------------------------------------------------------------------------
# Worker-side observability (cluster-mode SQL stage tasks)
# ---------------------------------------------------------------------------

# stage tasks currently running in THIS process, registered for live
# telemetry: the heartbeat loop snapshots each into the next heartbeat
# payload (collect_live_obs) — the reference's periodic Heartbeater
# shipping accumulator updates mid-task
_LIVE_TASKS: dict[int, dict] = {}

# black-box post-task ring (obs/blackbox pull-on-anomaly capture): with
# spark.tpu.obs.bundles armed, every finished stage task leaves a
# bounded summary here (spans capped, host counters only) that the
# driver pulls over the `diagnostic_state` RPC ONLY at bundle time —
# healthy-path heartbeat payloads carry none of it
_DIAG_RING: list[dict] = []
_DIAG_RING_MAX = 32
_DIAG_SPAN_CAP = 200
_DIAG_LOCK = threading.Lock()
lockwatch.register("exec.worker_main._DIAG_LOCK",
                   sys.modules[__name__], "_DIAG_LOCK")


def begin_stage_obs(conf, query_id: str | None = None,
                    stage_id: str | None = None,
                    task_id: int = 0) -> dict | None:
    """Install a process-local observability recorder for one stage task
    (the executor half of the reference's heartbeat-shipped executor
    metrics): a task-lived Tracer, a per-operator metric-record dict for
    the ExecContext, and baselines of THIS process's KernelCache
    counters, so the driver can reconcile attributed launches against
    driver+worker totals. With spark.tpu.heartbeat.obs on, the state is
    also registered for LIVE flushing: every heartbeat ships a
    cumulative snapshot of the task's host counters, closed spans since
    the last flush, and currently-open spans (collect_live_obs). Same
    zero-launch/no-mid-query-sync contract as the driver recorder —
    everything here is host bookkeeping. Returns None when the session
    disabled obs shipping."""
    from ..config import (CLUSTER_OBS_SHIPPING, HEARTBEAT_FLUSH_BUDGET,
                          HEARTBEAT_OBS, KERNEL_ATTRIBUTION, TRACE_ENABLED,
                          TRACE_MAX_SPANS, UI_OPERATOR_METRICS)
    from ..obs import resources as _resources
    from ..obs.tracing import Tracer
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    # ledger + kernel-cost switches follow the shipped session conf (the
    # worker-process analog of TpuSession.__init__'s configure call)
    _resources.configure(conf)
    from ..columnar import encoding as _encoding

    # compressed-execution ingest harvest follows the shipped conf too
    _encoding.configure(conf)
    # fault-injection rules ship with the session conf exactly like the
    # other process-global switches — chaos runs exercise the worker's
    # task/heartbeat/shuffle-write seams, healthy conf disables them
    faults.configure(conf)
    # lock-discipline watching follows the shipped conf as well (the
    # env-var path SPARK_TPU_LOCKWATCH=1 already covered import time)
    lockwatch.configure(conf)
    from . import persist_cache as _persist

    # persistent XLA compile cache: worker processes compile their own
    # stage kernels, so a warm cluster restart needs the same disk cache
    # wired here (spark.tpu.cache.dir ships with the conf)
    _persist.configure(conf)
    from ..obs import export as _export

    # service metrics plane: with spark.tpu.metrics.export on, this
    # worker's heartbeats attach its registry counter snapshot so the
    # driver scrape shows worker-labeled series
    _export.configure(conf)
    from ..obs import blackbox as _blackbox

    # black-box arming ships with the conf too: armed workers retain
    # bounded post-task diagnostic summaries for the driver's
    # pull-on-anomaly `diagnostic_state` RPC (nothing extra ships on
    # the healthy path — the heartbeat payload is unchanged)
    _blackbox.configure(conf)

    # conf values are host data — bool() here never touches device
    if not bool(conf.get(  # tpulint: ignore[host-sync]
            CLUSTER_OBS_SHIPPING)):
        return None
    trace_on = bool(conf.get(TRACE_ENABLED))  # tpulint: ignore[host-sync]
    metrics_on = bool(conf.get(  # tpulint: ignore[host-sync]
        UI_OPERATOR_METRICS))
    attribution = bool(conf.get(  # tpulint: ignore[host-sync]
        KERNEL_ATTRIBUTION))
    tracer = Tracer(enabled=trace_on,
                    max_spans=int(  # tpulint: ignore[host-sync]
                        conf.get(TRACE_MAX_SPANS)))
    state = {"tracer": tracer if trace_on else None,
             "rec": {} if metrics_on else None,
             "attribution": attribution,
             "kinds0": dict(KC.launches_by_kind),
             "launches0": KC.launches,
             "compile_ms0": KC.compile_ms,
             "disk0": _persist.disk_counters(),
             "query_id": query_id, "stage_id": stage_id,
             "task_id": task_id, "flush_seq": 0,
             "span_mark": tracer.mark() if trace_on else 0,
             "unsent_spans": [], "sent_spans": 0,
             "flush_budget": int(conf.get(  # tpulint: ignore[host-sync]
                 HEARTBEAT_FLUSH_BUDGET))}
    if bool(conf.get(HEARTBEAT_OBS)):  # tpulint: ignore[host-sync]
        with _STORE_LOCK:
            _LIVE_TASKS[id(state)] = state
    return state


# heartbeat flush-budget bookkeeping: tasks trimmed to a minimal delta
# because a beat hit spark.tpu.heartbeat.flushBudget (cumulative — the
# driver surfaces it in live status, and stage tasks / tests read it
# concurrently with the heartbeat thread's bumps), and a rotation
# cursor so the trim never starves the same tasks every beat
FLUSH_OVERFLOWS = LockedCounter("exec.worker_main.FLUSH_OVERFLOWS")
# race-lint: ignore[worker-reinit] — rotation cursor, not a metric: a
# fresh worker starting at 0 is exactly the intended semantics
_FLUSH_RR = 0

# rough per-element payload estimates (pickled size order-of-magnitude):
# exact accounting would pickle twice per beat for no benefit
_DELTA_BASE_COST = 256
_OP_RECORD_COST = 160
_SPAN_COST = 240
_OPEN_SPAN_COST = 96


def collect_live_obs() -> list:
    """Snapshot every registered in-flight stage task into live obs
    deltas for the next heartbeat. Each delta is CUMULATIVE since task
    start (snapshots replace on the driver, so a dropped heartbeat loses
    nothing) except closed spans, which ship incrementally via the
    tracer's monotonic sequence mark — carried in a per-task unsent
    buffer until `ack_live_obs` confirms the heartbeat RPC succeeded,
    so a failed beat re-sends them instead of silently dropping them
    (at-least-once across failures; exactly-once on a healthy channel).

    Very wide executors cap the payload per beat at
    spark.tpu.heartbeat.flushBudget: once the (estimated) budget is
    spent, remaining tasks ship minimal counter-only deltas — their
    closed spans STAY in the (bounded) carry buffer for a later beat,
    the overflow is counted (FLUSH_OVERFLOWS → live status), and the
    collection order rotates so no task is trimmed forever; a task
    closing more spans than the carry bound before its rotation turn
    loses its oldest from the LIVE stream only (the task-return record
    ships the tracer's full ring regardless).

    Host counters only: parked row-masks stay parked
    (export_op_records_partial), no kernel is launched, no device array
    is read."""
    global _FLUSH_RR

    from ..obs.metrics import export_op_records_partial
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    with _STORE_LOCK:
        states = list(_LIVE_TASKS.values())
        if states:
            _FLUSH_RR = (_FLUSH_RR + 1) % len(states)
    if states:
        states = states[_FLUSH_RR:] + states[:_FLUSH_RR]
    budget = next((s["flush_budget"] for s in states
                   if s.get("flush_budget")), 0)
    spent = 0
    out = []
    for state in states:
        state["flush_seq"] += 1
        trimmed = budget > 0 and spent >= budget
        # a trimmed task still ships its rolled-up counters — it just
        # drops the per-operator breakdown from the payload
        full = export_op_records_partial(state["rec"])
        recs = {} if trimmed else full
        rows = sum(e.get("rows", 0) for e in full.values())
        rows_exact = all(e.get("rows_exact", True) for e in full.values())
        batches = sum(e.get("batches", 0) for e in full.values())
        tracer = state["tracer"]
        spans_closed: list = []
        open_spans: list = []
        if tracer is not None:
            mark = state["span_mark"]
            state["span_mark"] = tracer.mark()
            carry = state["unsent_spans"]
            carry.extend(tracer.since(mark))
            del carry[:-512]  # bound the carry across a long outage
            if not trimmed:
                spans_closed = list(carry)
                open_spans = tracer.open_spans()
        state["sent_spans"] = len(spans_closed)
        if trimmed:
            FLUSH_OVERFLOWS.bump()
        kinds = {k: v - state["kinds0"].get(k, 0)
                 for k, v in KC.launches_by_kind.items()
                 if v != state["kinds0"].get(k, 0)}
        spent += (_DELTA_BASE_COST + _OP_RECORD_COST * len(recs)
                  + _SPAN_COST * len(spans_closed)
                  + _OPEN_SPAN_COST * len(open_spans))
        out.append({
            "query": state["query_id"], "stage": state["stage_id"],
            "task": state["task_id"], "seq": state["flush_seq"],
            "executor_pid": os.getpid(),
            "rows": rows,
            "rows_exact": rows_exact,
            "batches": batches,
            "launches": KC.launches - state["launches0"],
            "compile_ms": round(KC.compile_ms - state["compile_ms0"], 3),
            "kernel_kinds": kinds,
            "op_records": recs if not trimmed else None,
            "spans_closed": spans_closed,
            "open_spans": open_spans if not trimmed else None,
        })
    return out


def ack_live_obs() -> None:
    """The heartbeat carrying the last `collect_live_obs` snapshot
    reached the driver — drop the closed spans that beat actually
    INCLUDED (a flush-budget trim keeps its carry for the next beat).
    Called only from the (single) heartbeat thread, strictly alternating
    with collect, so nothing is appended to the unsent buffers in
    between (new spans land in the tracer ring and are picked up by the
    next collect's mark)."""
    with _STORE_LOCK:
        states = list(_LIVE_TASKS.values())
    for state in states:
        del state["unsent_spans"][:state.get("sent_spans", 0)]
        state["sent_spans"] = 0


def finish_stage_obs(state: dict | None) -> dict | None:
    """Package the task's observability for the ride back to the driver
    alongside the MapStatus payload: exported per-operator records
    (parked masks resolved — the batches are already host-side for block
    storage), raw spans + the (wall, perf) clock anchor for cross-process
    rebasing, and this process's KernelCache launch/compile deltas.
    Deregisters the task from live flushing FIRST, so no heartbeat can
    ship a partial that postdates the final record."""
    if state is None:
        return None
    from ..obs.metrics import export_op_records
    from ..obs.resources import GLOBAL_LEDGER
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    with _STORE_LOCK:
        _LIVE_TASKS.pop(id(state), None)
    kinds = {k: v - state["kinds0"].get(k, 0)
             for k, v in KC.launches_by_kind.items()
             if v != state["kinds0"].get(k, 0)}
    from . import persist_cache as _pc

    disk = {k: v - state.get("disk0", {}).get(k, 0)
            for k, v in _pc.disk_counters().items()
            if v != state.get("disk0", {}).get(k, 0)}
    tracer = state["tracer"]
    # this process's HBM accounting for the task's query (the ledger is
    # per-process; the driver merges it as the executor's remote peak)
    hbm = GLOBAL_LEDGER.query_record(state["query_id"])
    out = {
        "op_records": export_op_records(state["rec"]),
        "spans": tracer.spans() if tracer is not None else [],
        "anchor": tracer.anchor if tracer is not None else None,
        "kernel_kinds": kinds,
        "kernel_launches": KC.launches - state["launches0"],
        "kernel_compile_ms": round(KC.compile_ms - state["compile_ms0"], 3),
        "compile_disk": disk or None,
        "hbm": {"bytes": hbm["bytes"], "peak": hbm["peak"],
                "ops": {k: v["peak"] for k, v in hbm["ops"].items()}}
        if hbm is not None else None,
        "pid": os.getpid(),
    }
    from ..obs import blackbox as _blackbox

    if _blackbox.ENABLED:
        # armed black box: retain a bounded post-task summary for the
        # driver's pull-on-anomaly diagnostic_state RPC. Host dict
        # copies only — no kernel launch, no device read, and nothing
        # added to the heartbeat payload.
        entry = {"ts": time.time(), "query_id": state["query_id"],
                 "stage_id": state["stage_id"],
                 "task_id": state["task_id"],
                 "spans": out["spans"][-_DIAG_SPAN_CAP:],
                 "anchor": out["anchor"],
                 "kernel_kinds": out["kernel_kinds"],
                 "kernel_launches": out["kernel_launches"],
                 "hbm": out["hbm"], "pid": out["pid"]}
        with _DIAG_LOCK:
            _DIAG_RING.append(entry)
            del _DIAG_RING[:-_DIAG_RING_MAX]
    return out


def _handle_get_block(payload: bytes):
    sid, rid = pickle.loads(payload)
    with _STORE_LOCK:
        data = BLOCK_STORE.get((sid, rid))
    if data is None:
        yield b"missing"
        return
    yield b"ok"
    for off in range(0, len(data), CHUNK_BYTES):
        yield data[off:off + CHUNK_BYTES]


def _handle_block_stats(payload: bytes) -> bytes:
    """Block-store introspection for tests: the chaos suite
    asserts failed queries leave ZERO blocks behind on every worker."""
    with _STORE_LOCK:
        return pickle.dumps({
            "blocks": len(BLOCK_STORE),
            "bytes": sum(len(v) for v in BLOCK_STORE.values()),
        })


def _handle_free_shuffle(payload: bytes) -> bytes:
    sid = pickle.loads(payload)
    with _STORE_LOCK:
        # base id and per-map block ids ('<sid>#m<i>') alike
        for k in [k for k in BLOCK_STORE
                  if k[0] == sid or k[0].startswith(sid + "#m")]:
            BLOCK_STORE.pop(k, None)
    return b"ok"


def _handle_lockwatch_edges(_payload: bytes) -> bytes:
    """Worker-side lock-discipline observations for the executor
    cross-check in tests/test_race_lint.py: the acquisition-
    order edges, registered slot names, and guard violations THIS
    worker process recorded under SPARK_TPU_LOCKWATCH=1. Pure host
    reads of the lockwatch observation tables."""
    return pickle.dumps({
        "enabled": lockwatch.ENABLED,
        "edges": [[a, b, n]
                  for (a, b), n in lockwatch.order_edges().items()],
        "names": lockwatch.registered_names(),
        "violations": lockwatch.violations(),
        "acquires": sum(lockwatch.acquire_counts().values()),
    })


def _handle_diagnostic_state(_payload: bytes) -> bytes:
    """Black-box fleet state pull (obs/blackbox): the driver calls this
    ONLY while assembling a diagnostic bundle — never on the healthy
    path — and gets this worker's bounded post-task ring plus its
    fault-registry, lockwatch, and metrics-registry state. Pure host
    reads; zero kernel launches."""
    from ..obs import blackbox as _blackbox
    from ..obs import export as _export
    from ..obs.resources import GLOBAL_LEDGER

    with _DIAG_LOCK:
        tasks = [dict(e) for e in _DIAG_RING]
    return pickle.dumps({
        "enabled": _blackbox.ENABLED,
        "pid": os.getpid(),
        "tasks": tasks,
        "hbm": GLOBAL_LEDGER.snapshot(),
        "faults": {"enabled": faults.ENABLED,
                   "fired": faults.fire_counts()},
        "lockwatch": {
            "enabled": lockwatch.ENABLED,
            "violations": lockwatch.violations(),
            "acquires": sum(lockwatch.acquire_counts().values()),
        },
        "metrics": _export.executor_payload() if _export.ENABLED else None,
    })


def _handle_launch_task(payload: bytes) -> bytes:
    """Runs one cloudpickled (fn, args) task. Task failures are data
    (('err', traceback, salvaged_obs)), not transport errors — a
    deterministic task error must not look like an executor loss to the
    driver. The third element carries the failed attempt's packaged
    observability when the task body stamped one onto the exception
    (cluster_sql._run_stage_store) — the wasted-work record the driver
    surfaces in chaos-path EXPLAIN ANALYZE and the query profile."""
    import cloudpickle

    try:
        fn, args = cloudpickle.loads(payload)
        result = fn(*args)
        return pickle.dumps(("ok", result))
    except SystemExit:
        raise
    except BaseException as e:
        salvage = getattr(e, "_salvaged_obs", None)
        try:
            return pickle.dumps(("err", traceback.format_exc(), salvage))
        except Exception:
            # unpicklable salvage (should not happen — it is plain
            # dicts) must not mask the task error
            return pickle.dumps(("err", traceback.format_exc(), None))


def serve_worker(driver_addr: str, token: str, host_label: str = "localhost",
                 bind_host: str = "127.0.0.1",
                 block: bool = True) -> RpcServer:
    """Start the worker server, register with the driver, heartbeat.
    Returns the running RpcServer (caller blocks or not via `block`).
    `bind_host` is bound AND advertised — a worker on another machine
    passes an IP the driver and peer workers can reach."""
    global BLOCK_ADDR

    server = RpcServer(token, host=bind_host)
    server.register("launch_task", _handle_launch_task)
    server.register("free_shuffle", _handle_free_shuffle)
    server.register("block_stats", _handle_block_stats)
    server.register("lockwatch_edges", _handle_lockwatch_edges)
    server.register("diagnostic_state", _handle_diagnostic_state)
    server.register("ping", lambda _p: b"pong")
    server.register_stream("get_block", _handle_get_block)
    addr = server.start()
    BLOCK_ADDR = addr

    driver = RpcClient(driver_addr, token)
    driver.wait_ready()

    def register() -> str:
        return driver.call("register_executor", pickle.dumps({
            "addr": addr, "host": host_label, "pid": os.getpid()}),
            timeout=10).decode()

    eid = register()

    interval = float(os.environ.get(  # tpulint: ignore[host-sync]
        "SPARK_TPU_HEARTBEAT_INTERVAL", "3.0"))

    def heartbeat_loop():
        nonlocal eid
        misses = 0
        while True:
            time.sleep(interval)
            try:
                # chaos seam: an injected heartbeat blackout models the
                # DRIVER never receiving the beat (a receive-path
                # partition) — from the driver's view the executor went
                # silent mid-task, which is exactly what the straggler
                # silence deadline and speculative execution must
                # absorb. The detail carries busy/idle so rules can
                # target beats DURING a task (`@busy`) — an idle-phase
                # blackout would be consumed before the task exists.
                if faults.ENABLED:
                    with _STORE_LOCK:
                        busy = bool(_LIVE_TASKS)
                    faults.maybe_fail(
                        "heartbeat.flush",
                        detail="busy" if busy else "idle")
                # live telemetry rides the liveness heartbeat: snapshots
                # of every in-flight stage task's obs counters/spans
                # (empty list when nothing runs or streaming is off).
                # Span-heavy payloads compress well — gzip them on the
                # wire instead of raising the frame budget.
                obs = collect_live_obs()
                # executor-level HBM occupancy (device ledger snapshot —
                # metadata counters only) rides EVERY beat, so cluster
                # live status shows per-executor HBM even between tasks
                from ..obs.resources import GLOBAL_LEDGER

                body = {
                    "eid": eid, "obs": obs,
                    "hbm": GLOBAL_LEDGER.snapshot(),
                    "obs_overflows": FLUSH_OVERFLOWS.value}
                # per-executor metrics deltas (cumulative snapshots —
                # a lost beat loses nothing) ride the same payload;
                # structurally absent when the metrics plane is off
                from ..obs import export as _export

                if _export.ENABLED:
                    body["metrics"] = _export.executor_payload()
                payload = pickle.dumps(body)
                reply = driver.call("heartbeat", payload, timeout=5,
                                    compress=bool(obs))
                if reply != b"unknown":
                    # the driver ingested the obs payload (it skips the
                    # sink for unknown executors) — drop the span carry
                    ack_live_obs()
                misses = 0
                if reply == b"unknown":
                    # driver declared us lost (e.g. one transient task
                    # RPC failure) — re-register under a fresh id, the
                    # reference's "executor told to re-register" path
                    eid = register()
            except faults.InjectedFault:
                # injected blackout: the beat was "lost on the wire",
                # not a send failure — the worker itself is healthy and
                # must not count it toward the driver-gone suicide
                continue
            except Exception:
                misses += 1
                if misses >= 5:  # driver gone — shut down
                    os._exit(0)

    # race-lint: ignore[bare-submit] — process-lifetime service thread:
    # heartbeats aggregate across every query on this worker and must
    # NOT inherit any single query's contextvar scope
    threading.Thread(target=heartbeat_loop, daemon=True).start()
    if block:
        threading.Event().wait()
    return server


def main() -> None:
    # under `python -m`, this file runs as __main__ while tasks import the
    # canonical spark_tpu.exec.worker_main module — publish the block-store
    # state THERE so both sides share one dict/address
    from spark_tpu.exec import worker_main as canonical

    canonical.serve_worker(
        os.environ["SPARK_TPU_DRIVER_ADDR"],
        os.environ["SPARK_TPU_WORKER_KEY"],
        os.environ.get("SPARK_TPU_WORKER_HOST", "localhost"),
        os.environ.get("SPARK_TPU_BIND_HOST", "127.0.0.1"))


if __name__ == "__main__":
    main()
