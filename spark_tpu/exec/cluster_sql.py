"""Distributed SQL stage execution over the process cluster.

Role of the reference's cluster-mode SQL execution (DAGScheduler map
stages running on executors, shuffle blocks fetched between them —
core/scheduler/DAGScheduler.scala + ShuffleBlockFetcherIterator): a
stage's physical subtree is cloudpickled to a worker process, which
STORES its output partitions in its local block server and returns only
a MapStatus (address + per-partition rows/bytes). Consumer stages
receive Fetch leaves and pull the blocks directly from the producing
worker — shuffle data never rides through the driver. A failed fetch
(worker died after producing) surfaces as FetchFailedError and the
scheduler regenerates the lost map stage from lineage, exactly the
reference's FetchFailed → resubmit path. The result (final) stage always
runs in the driver so device caches and session services stay local.

The columnar kernels are identical on driver and workers — a worker is
just another process with its own XLA client (CPU in the local cluster;
one chip per host in a real multi-host deployment, where this same
contract rides DCN instead of localhost pipes)."""

from __future__ import annotations

import uuid
from concurrent.futures import ThreadPoolExecutor

import cloudpickle

from ..physical.operators import PhysicalPlan
from .map_output import (
    FetchFailedError, MapOutputTracker, MapStatus, MergeStatus,
    ShuffleStatus, fetch_block, fetch_merged, free_shuffle, map_block_id,
    merge_flow_id,
)
from .scheduler import DAGScheduler, Stage, _StageOutput, build_stage_graph


def _partitions_to_ipc(parts):
    import pyarrow as pa

    out = []
    for p in parts:
        tabs = []
        for b in p:
            t = b.to_arrow()
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, t.schema) as w:
                w.write_table(t)
            tabs.append(sink.getvalue().to_pybytes())
        out.append(tabs)
    return out


def _partition_to_ipc_encoded(part):
    """Compressed shuffle wire format: each batch serializes with its
    StringType columns DICTIONARY-ENCODED (arrow dictionary arrays —
    int32 codes + the dictionary, never decoded row values). The
    per-column dictionary TOKENS (StringDict.token content fingerprints)
    are returned SEPARATELY — they ride the MapStatus (`dict_ids`), the
    control-plane carrier the reduce side consults to recognize equal
    dictionaries across blocks and remap by reference. Returns
    (("enc1", ipc_list), {col_idx: (token per batch, ...)})."""
    import pyarrow as pa

    from ..columnar.batch import EMPTY_DICT
    from ..types import StringType

    tabs = []
    dtokens: dict[int, list] = {}
    for bi, b in enumerate(part):
        t = b.to_arrow(encoded=True)
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
        tabs.append(sink.getvalue().to_pybytes())
        for ci, (f, c) in enumerate(zip(b.schema.fields, b.columns)):
            if isinstance(f.dataType, StringType):
                dtokens.setdefault(ci, []).append(
                    (c.dictionary or EMPTY_DICT).token())
    return ("enc1", tabs), {ci: tuple(ts) for ci, ts in dtokens.items()}


def _ipc_to_partition(payload, schema, seed_ranges=None, dict_cache=None,
                      dict_tokens=None):
    """Rebuild one block's batches. `dict_tokens` ({col_idx: (token per
    batch, ...)}, from the producing MapStatus.dict_ids) + `dict_cache`
    intern equal dictionaries to one shared StringDict object."""
    import pyarrow as pa

    from ..columnar.arrow import record_batch_to_columnar

    if isinstance(payload, tuple) and payload and payload[0] == "enc1":
        _tag, tabs = payload
        out = []
        for bi, raw in enumerate(tabs):
            toks = None
            if dict_tokens:
                toks = {ci: ts[bi] for ci, ts in dict_tokens.items()
                        if bi < len(ts)}
            out.append(record_batch_to_columnar(
                pa.ipc.open_stream(pa.BufferReader(raw)).read_all(),
                schema, seed_ranges=seed_ranges,
                dict_cache=dict_cache, dict_tokens=toks))
        return out
    return [record_batch_to_columnar(
        pa.ipc.open_stream(pa.BufferReader(raw)).read_all(), schema,
        seed_ranges=seed_ranges)
        for raw in payload]


def _ipc_to_partitions(payload, attrs):
    from ..physical.operators import attrs_schema

    schema = attrs_schema(attrs)
    dict_cache: dict = {}
    return [_ipc_to_partition(tabs, schema, dict_cache=dict_cache)
            for tabs in payload]


class FetchExec(PhysicalPlan):
    """Leaf that pulls a parent shuffle's partitions (the
    BlockStoreShuffleReader role). Each reduce partition is the ordered
    concatenation of every map task's block for it; when the parent was
    push-merged, the service's merged chunk is fetched FIRST and only
    map ids missing from it (or a corrupt chunk) fall back to the
    per-map original blocks — the reference's push-merged read path
    (ShuffleBlockFetcherIterator merged chunks + fallbackFetch).

    `part_indices` restricts the fetch to a subset of reduce partitions:
    the leaf-slicing handle that turns a consumer stage into multiple
    map tasks."""

    child_fields = ()
    # adaptive.coalesce_after_exchange treats this leaf as the shuffle
    # it stands in for: cluster reduce stages coalesce like local runs
    is_shuffle_read = True

    def __init__(self, attrs, shuffle_id: str, maps: list,
                 authkey_hex: str, num_partitions: int,
                 fallback_addr: str | None = None,
                 merge: tuple | None = None,
                 part_indices: list | None = None,
                 col_stats: dict | None = None,
                 dict_ids: dict | None = None,
                 fetch_retries: int = 2,
                 fetch_wait_ms: float = 50.0):
        self.attrs = list(attrs)
        self.shuffle_id = shuffle_id
        self.maps = list(maps)              # [(map_id, block_addr), ...]
        self.authkey_hex = authkey_hex
        self.num_partitions = num_partitions
        self.fallback_addr = fallback_addr  # external shuffle service
        self.merge = merge       # (service_addr, {rid: (map ids merged)})
        self.part_indices = part_indices
        # bounded-fetch-retry knobs, captured as plain values at plan
        # substitution time (the leaf ships to worker processes, which
        # must retry with the DRIVER session's settings)
        self.fetch_retries = fetch_retries
        self.fetch_wait_ms = fetch_wait_ms
        # {rid: {col_idx: (kmin, kmax, any)}} merged across map tasks —
        # seeds the dense-range memo on rebuild (no krange3 probe on
        # post-shuffle dense decisions; same stats the local write seeds)
        self.col_stats = col_stats
        # {map_id: {rid: {col_idx: (StringDict.token per batch, ...)}}} —
        # the dictionary IDENTITY each map task registered on its
        # MapStatus: rebuilds intern equal dictionaries by token and
        # remap blocks by reference (no re-encode, no host sync)
        self.dict_ids = dict_ids

    @property
    def output(self):
        return self.attrs

    def output_partitioning(self):
        from ..physical.partitioning import UnknownPartitioning

        n = (len(self.part_indices) if self.part_indices is not None
             else self.num_partitions)
        return UnknownPartitioning(max(n, 1))

    def _flow_parents(self) -> list:
        """Deterministic flow ids of the spans that produced this
        shuffle's blocks: the map-task spans (`_run_stage_store` stamps
        `map_block_id` on its task root span) and — when the shuffle was
        push-merged — the driver's merge-finalize span
        (`merge_flow_id`), so exchange edges run map task → merge →
        reduce fetch instead of stopping at the fetch. The exporter
        draws the arrows across processes; capped so args stay small on
        very wide shuffles."""
        num_maps = len(self.maps)
        parents = [map_block_id(self.shuffle_id, mid, num_maps)
                   for mid, _ in sorted(self.maps)[:16]]
        if self.merge is not None and any(self.merge[1].values()):
            # merged chunks have a producing span on the driver
            # (ClusterDAGScheduler._finalize_merge) — parent to it too
            parents.append(merge_flow_id(self.shuffle_id))
        return parents

    def _fetch_rid(self, rid: int, clients: dict, schema, ctx,
                   dict_cache: dict | None = None) -> list:
        """One reduce partition: merged chunk first, per-map fallback."""
        import pickle

        from ..net.transport import RpcClient
        from .map_output import BlockClient

        num_maps = len(self.maps)
        frames: dict[int, bytes] = {}
        if self.merge is not None and num_maps > 0:
            service_addr, merged_index = self.merge
            if merged_index.get(rid):
                if "merged" not in clients:
                    clients["merged"] = RpcClient(service_addr,
                                                  self.authkey_hex)
                got = fetch_merged(clients["merged"], self.shuffle_id, rid)
                if got is not None:
                    frames = dict(got)
                    ctx.metrics.add("shuffle.merged_chunks_fetched")
        part: list = []
        for map_id, addr in sorted(self.maps):
            raw = frames.get(map_id)
            if raw is None:
                bid = map_block_id(self.shuffle_id, map_id, num_maps)
                key = ("map", map_id)
                if key not in clients:
                    clients[key] = BlockClient(
                        addr, self.authkey_hex, bid,
                        fallback_addr=self.fallback_addr,
                        max_retries=self.fetch_retries,
                        retry_wait_ms=self.fetch_wait_ms)
                try:
                    raw = clients[key].get(rid)
                except FetchFailedError as e:
                    # last alternate source before the expensive lineage
                    # regen: a push-merged chunk that failed its FIRST
                    # read (or was skipped) may hold this map's frame
                    raw = self._merged_rescue(clients, rid, map_id)
                    if raw is None:
                        # re-key to the BASE shuffle id: the scheduler
                        # regenerates the whole map stage, not one task
                        raise FetchFailedError(self.shuffle_id,
                                               str(e)) from None
                    ctx.metrics.add("shuffle.fetch_merged_rescues")
                ctx.metrics.add("shuffle.blocks_fetched")
            seed = (self.col_stats or {}).get(rid)
            toks = ((self.dict_ids or {}).get(map_id) or {}).get(rid)
            part.extend(_ipc_to_partition(pickle.loads(raw), schema, seed,
                                          dict_cache=dict_cache,
                                          dict_tokens=toks))
        return part

    def _merged_rescue(self, clients: dict, rid: int,
                       map_id: int) -> bytes | None:
        """Retry the push-merged chunk as an ALTERNATE SOURCE for one
        map's frame after its per-map block fetch exhausted retries."""
        if self.merge is None:
            return None
        service_addr, merged_index = self.merge
        if map_id not in (merged_index.get(rid) or ()):
            return None
        from ..net.transport import RpcClient

        if "merged" not in clients:
            clients["merged"] = RpcClient(service_addr, self.authkey_hex)
        got = fetch_merged(clients["merged"], self.shuffle_id, rid)
        if got is None:
            return None
        return dict(got).get(map_id)

    def execute(self, ctx):
        from contextlib import nullcontext

        from ..physical.operators import attrs_schema

        schema = attrs_schema(self.attrs)
        rids = (self.part_indices if self.part_indices is not None
                else range(self.num_partitions))
        clients: dict = {}
        # one dictionary intern table per fetch: encoded blocks carrying
        # the same StringDict.token rebuild to ONE shared dictionary
        # object across map tasks and reduce partitions (identity remap)
        dict_cache: dict = {}
        tracer = getattr(ctx, "tracer", None)
        # exchange-edge flow: this fetch's span parents to the map-task
        # spans that stored the blocks (possibly in another process —
        # the ids are derived from the shuffle id on both sides)
        sp = tracer.span(f"fetch[{self.shuffle_id}]", cat="exchange",
                         args={"flow_parent": self._flow_parents()}) \
            if tracer is not None else nullcontext()
        try:
            with sp:
                return [self._fetch_rid(rid, clients, schema, ctx,
                                        dict_cache)
                        for rid in rids]
        finally:
            retries = sum(getattr(c, "retries_used", 0)
                          for c in clients.values())
            if retries:
                # transient flaps this fetch absorbed WITHOUT paying a
                # lineage regen (the chaos gate's zero-regen assertion)
                ctx.metrics.add("shuffle.fetch_retries", retries)
            for c in clients.values():
                c.close()

    def simple_string(self):
        sl = (f" slice{list(self.part_indices)}"
              if self.part_indices is not None else "")
        return f"Fetch[{self.shuffle_id}×{len(self.maps)}maps]" \
               f"({self.num_partitions} parts{sl})"


def _run_stage_store(plan_bytes: bytes, conf_overrides: dict,
                     shuffle_id: str, map_id: int = 0, num_maps: int = 1,
                     query_id: str | None = None,
                     flow_parent: str | None = None):
    """Map-task body: execute the (possibly leaf-sliced) subtree, store
    each output partition as a block in THIS worker's store (and push it
    to the merge service in push mode), return per-partition
    (rows, bytes) — the MapStatus payload — plus the task's shipped
    observability (per-operator records, spans, kernel deltas). While
    the task RUNS, the same recorder streams partial snapshots on the
    executor heartbeat (worker_main.collect_live_obs — the reference's
    periodic Heartbeater), keyed by the (query, shuffle, map) identity
    passed here so the driver's LiveObs merges them per task.
    Runs in a worker process: the obs recorder is process-local, spans
    record under the driver's query scope, and the task root span
    carries a deterministic flow id (`map_block_id`) so reduce-side
    fetches can draw cross-process arrows to it."""
    import pickle

    import jax

    # cluster workers are CPU workers on purpose (a chip belongs to one
    # process — the driver); worker_env already says so in the
    # environment, this holds for a worker started any other way
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from ..config import SQLConf
    from ..obs.tracing import pop_query, push_query
    from . import worker_main as WM
    from .context import ExecContext

    plan = cloudpickle.loads(plan_bytes)
    conf = SQLConf(dict(conf_overrides))
    obs = WM.begin_stage_obs(conf, query_id=query_id,
                             stage_id=shuffle_id, task_id=map_id)
    ctx = ExecContext(conf=conf)
    if obs is not None:
        if obs["rec"] is not None:
            ctx.plan_metrics = obs["rec"]
            ctx.kernel_attribution = obs["attribution"]
        ctx.tracer = obs["tracer"]
    qtoken = push_query(query_id) if query_id is not None else None
    try:  # noqa: SIM105 — failed tasks must deregister from live flushing
        # chaos seam (rules just installed from the shipped conf by
        # begin_stage_obs): an injected raise surfaces to the driver as
        # a TRANSIENT task failure (retried on another executor,
        # counted toward this executor's exclusion window); kill mode
        # hard-exits the process mid-task (the worker-death scenario).
        # Inside the try: a raise must deregister the live recorder or
        # the task would stream ghost partials forever
        from ..utils import faults

        if faults.ENABLED:
            faults.maybe_fail("worker.task",
                              detail=f"{shuffle_id}#m{map_id}")
        task_span = ctx.tracer.span(
            f"task[{map_block_id(shuffle_id, map_id, num_maps)}]",
            cat="worker",
            args={"flow_id": map_block_id(shuffle_id, map_id, num_maps),
                  **({"flow_parent": flow_parent}
                     if flow_parent is not None else {})},
            flow=True) if ctx.tracer is not None else None
        if task_span is not None:
            task_span.__enter__()
        try:
            from ..columnar.encoding import encoding_enabled

            encoded = encoding_enabled(conf)
            parts = plan.execute(ctx)
            rows, sizes = [], []
            dict_ids: dict = {}
            for rid, part in enumerate(parts):
                if encoded:
                    # ship dictionary codes + per-column dictionaries
                    # (tokens identify them on the MapStatus) instead of
                    # decoded values — compressed execution's wire format
                    ipc, toks = _partition_to_ipc_encoded(part)
                    if toks:
                        dict_ids[rid] = toks
                else:
                    ipc = _partitions_to_ipc([part])[0]
                raw = pickle.dumps(ipc)
                WM.store_map_block(shuffle_id, map_id, num_maps, rid, raw)
                rows.append(sum(b.num_rows() for b in part))
                sizes.append(len(raw))
        finally:
            if task_span is not None:
                task_span.__exit__(None, None, None)
    except BaseException as e:
        # the task failed: stop streaming its partials NOW (the retry
        # will register a fresh recorder under the same identity). The
        # packaged obs is NOT discarded with the error: stamped onto the
        # exception, it rides the launch_task error payload back to the
        # driver (chaos salvage — a failed attempt's wasted work shows
        # in EXPLAIN ANALYZE findings and the query profile instead of
        # vanishing with the traceback)
        salvage = WM.finish_stage_obs(obs)
        if salvage is not None:
            try:
                e._salvaged_obs = salvage
            except Exception:
                pass  # exceptions with __slots__ just lose the ride
        raise
    finally:
        if qtoken is not None:
            pop_query(qtoken)
    counters = ctx.metrics.snapshot()["counters"]
    # map-side column stats (shuffle-exchange roots accumulate them while
    # slicing rows host-side) ride the MapStatus payload: the reduce side
    # seeds its dense-range memo from them instead of probing on device
    col_stats = getattr(plan, "last_col_stats", None) or None
    return ("mapstatus", WM.BLOCK_ADDR, rows, sizes, counters,
            WM.finish_stage_obs(obs), col_stats, dict_ids or None)


class ClusterDAGScheduler(DAGScheduler):
    """DAGScheduler that ships non-result stages to cluster workers.

    Stage = unit of distribution AND recovery: executor loss during a
    task retries via the cluster's attempt loop; executor loss AFTER a
    map stage completed surfaces as FetchFailedError in a consumer and
    regenerates the lost stage from lineage."""

    def __init__(self, ctx, cluster, conf_overrides: dict,
                 max_attempts: int = 2, listener_bus=None):
        super().__init__(ctx, max_attempts, listener_bus)
        self.cluster = cluster
        self.conf_overrides = dict(conf_overrides)
        self.map_outputs = MapOutputTracker()
        self._run_id = uuid.uuid4().hex[:12]
        import threading

        self._obs_lock = threading.Lock()  # worker obs merges race
        from ..config import SPECULATION

        if ctx.conf.get(SPECULATION):
            cluster.speculation = True
        # live telemetry: heartbeat-streamed partials land in the
        # session's LiveObs (obs/live.py); the final task-return record
        # supersedes them (_run_remote → task_finished). The straggler
        # detector doubles as the speculative-execution signal hook.
        self.live = getattr(ctx, "live_obs", None)
        # excludeOnFailure: configure the cluster's HealthTracker from
        # session conf and hook exclusion events into the live store
        # (console executor rows, live status, EXPLAIN ANALYZE findings)
        from ..config import (
            EXCLUDE_MAX_FAILURES, EXCLUDE_ON_FAILURE, EXCLUDE_TIMEOUT_SECS,
            EXCLUDE_WINDOW_SECS,
        )

        health = getattr(cluster, "health", None)
        if health is not None:
            health.configure(
                enabled=bool(ctx.conf.get(  # tpulint: ignore[host-sync]
                    EXCLUDE_ON_FAILURE)),
                max_failures=int(ctx.conf.get(  # tpulint: ignore[host-sync]
                    EXCLUDE_MAX_FAILURES)),
                window_s=float(ctx.conf.get(  # tpulint: ignore[host-sync]
                    EXCLUDE_WINDOW_SECS)),
                exclude_s=float(ctx.conf.get(  # tpulint: ignore[host-sync]
                    EXCLUDE_TIMEOUT_SECS)))
            health.on_exclude = self._on_executor_excluded
            health.on_exclude_host = self._on_host_excluded
        if self.live is not None:
            if getattr(cluster, "obs_sink", None) is None:
                cluster.obs_sink = self.live.on_heartbeat
            if getattr(cluster, "speculation", False):
                # keyed on (stage sid, map_id): the speculative wait
                # consults the signal for ITS OWN task, so one flagged
                # straggler doesn't collapse the threshold for every
                # in-flight task (key=None keeps the any-straggler view)
                cluster.speculation_signal = (
                    lambda key=None, live=self.live: any(
                        key is None or (f[1], f[2]) == key
                        for f in live.active_stragglers()))

    def _on_executor_excluded(self, eid: str, until: float,
                              failures: int) -> None:
        """HealthTracker exclusion hook: surface the event in the live
        store so console executor rows, live status, and EXPLAIN
        ANALYZE findings all show WHY an executor stopped taking tasks
        (the reference's TaskSetExcludelist → UI excludelist view)."""
        if self.live is None:
            return
        import math

        from ..obs.tracing import current_query

        horizon = None if math.isinf(until) else until
        self.live.executor_excluded(eid, horizon, failures)
        self.live.add_finding(current_query(), {
            "severity": "warning", "kind": "exec.excluded",
            "executor": eid,
            "msg": f"executor {eid} excluded after {failures} task "
                   "failure(s) in the excludeOnFailure window"
                   + ("" if horizon is None else
                      " (timed re-inclusion pending)")})

    def _on_host_excluded(self, host: str, until: float,
                          eids: list) -> None:
        """Host-granular escalation hook: every executor on one host
        tripped the failure window, so the HealthTracker excluded the
        box as a unit — surfaced exactly like executor exclusion (live
        status host row + a finding on the current query)."""
        if self.live is None:
            return
        import math

        from ..obs.tracing import current_query

        horizon = None if math.isinf(until) else until
        self.live.host_excluded(host, horizon, eids)
        self.live.add_finding(current_query(), {
            "severity": "warning", "kind": "host.excluded",
            "host": host, "executors": list(eids),
            "msg": f"host {host} excluded: all {len(eids)} of its "
                   "executors tripped the excludeOnFailure window"
                   + ("" if horizon is None else
                      " (timed re-inclusion pending)")})

    def _run(self, plan):
        # DAGScheduler.run wraps this with the driver-process KernelCache
        # delta accounting; worker-process deltas merge in via each
        # task's shipped obs payload (_merge_task_obs), so kernel.*
        # query metrics are driver+worker totals in cluster mode
        import threading
        from collections import defaultdict

        from ..config import STAGE_MAX_REGENS
        from ..errors import StageRegenerationLimitError

        max_regens = int(self.ctx.conf.get(  # tpulint: ignore[host-sync]
            STAGE_MAX_REGENS))
        regens = [0]   # FetchFailed-driven regenerations THIS query
        # sibling stages materialize on pool threads and can catch
        # FetchFailed concurrently — the cap counter must not lose
        # increments to a torn read-modify-write
        regen_lock = threading.Lock()

        result_stage, stages = build_stage_graph(plan)
        done: set[int] = set()
        # per-stage locks serialize materialization/invalidation of a
        # SHARED parent reached from concurrently-materializing consumers
        # (diamond DAGs) — lock order is always child→parent, a DAG, so
        # no cycles
        locks: dict[int, threading.Lock] = defaultdict(threading.Lock)

        def invalidate_if_stale(stage: Stage, failed_sid: str) -> None:
            """Under the stage's lock: drop its outputs only if they are
            still the ones the fetch failed against (another consumer may
            have regenerated it already)."""
            with locks[stage.stage_id]:
                cur = self._shuffle_id(stage)
                st = self.map_outputs.get(cur)
                if cur == failed_sid or st is None:
                    done.discard(stage.stage_id)
                    stage.result = None
                    if st is not None:
                        # free the stale attempt's blocks + merged chunks
                        # NOW — once unregistered, _free_shuffles can no
                        # longer see this sid and the service state leaks
                        self._free_one(st)
                    self.map_outputs.unregister(cur)

        def materialize(stage: Stage) -> None:
            with locks[stage.stage_id]:
                _materialize_locked(stage)

        def _materialize_locked(stage: Stage) -> None:
            if stage.stage_id in done:
                return
            if len(stage.parents) > 1:
                from ..obs.metrics import scoped_submit

                # copied contextvars Context per submit: the pool threads
                # start with an EMPTY context, which would silently drop
                # the query-scope tag and re-bucket kernel attribution
                # (matching scheduler.par_map's lane discipline)
                with ThreadPoolExecutor(len(stage.parents)) as pool:
                    futures = [scoped_submit(pool, materialize, p)
                               for p in stage.parents]
                    for f in futures:
                        f.result()
            else:
                for p in stage.parents:
                    materialize(p)
            tracer = getattr(self.ctx, "tracer", None)
            last_err = None
            for attempt in range(self.max_attempts):
                stage.attempts = attempt + 1
                try:
                    self._post("stageSubmitted", stage)
                    from contextlib import nullcontext

                    sp = tracer.span(f"stage-{stage.stage_id}", cat="stage",
                                     args={"attempt": attempt + 1},
                                     flow=True) \
                        if tracer is not None else nullcontext()
                    with sp:
                        if stage is result_stage:
                            root = _substitute_parents(stage.root, self)
                            stage.result = root.execute(self.ctx)
                        else:
                            stage.result = self._run_remote(stage)
                    self.ctx.metrics.add("scheduler.stages_completed")
                    self._post("stageCompleted", stage)
                    done.add(stage.stage_id)
                    return
                except Exception as e:
                    last_err = e
                    if self.live is not None:
                        # the retry runs under a NEW sid — close the
                        # failed attempt's live entries or they trip the
                        # heartbeat-silence straggler deadline forever
                        from ..obs.tracing import current_query as _cq

                        self.live.stage_abandoned(
                            _cq(), self._shuffle_id(stage))
                    # (partial map outputs of the failed attempt are
                    # freed by _run_remote's own handler, closest to
                    # the failure and covering BaseException too)
                    sid = _fetch_failed_shuffle_id(e)
                    if sid is not None:
                        # a parent's blocks are gone — regenerate it from
                        # lineage before retrying this stage. Bounded per
                        # query: an executor set losing outputs faster
                        # than lineage regenerates them must terminate in
                        # a CLASSIFIED error, not an infinite loop
                        with regen_lock:
                            regens[0] += 1
                            n_regens = regens[0]
                        if n_regens > max_regens:
                            raise StageRegenerationLimitError(
                                n_regens, max_regens, sid) from e
                        self.ctx.metrics.add("scheduler.fetch_failures")
                        self._record_lost_shuffle_executors(sid, str(e))
                        for p in stage.parents:
                            invalidate_if_stale(p, sid)
                        for p in stage.parents:
                            materialize(p)
                    else:
                        self.ctx.metrics.add("scheduler.stage_retries")
                    self._post("stageFailed", stage, error=str(e))
            raise last_err  # noqa: B904

        try:
            materialize(result_stage)
            return result_stage.result
        finally:
            self._free_shuffles()

    # ------------------------------------------------------------------
    def _shuffle_id(self, stage: Stage) -> str:
        return f"{self._run_id}.{stage.stage_id}.{stage.attempts}"

    def _map_task_count(self, shipped) -> int:
        """How many map tasks to split this stage into. >1 only when the
        stage root is a hash/round-robin shuffle exchange and every
        multi-partition Fetch leaf has the same partition count (the
        co-partitioned zip contract — all such leaves are sliced by the
        same index set). Range exchanges never slice: each task samples
        its own bounds, which would break the global order contract."""
        from ..config import SHUFFLE_MAP_PARALLELISM
        from ..physical.exchange import ShuffleExchangeExec
        from ..physical.partitioning import (
            HashPartitioning, UnknownPartitioning,
        )

        want = self.ctx.conf.get(SHUFFLE_MAP_PARALLELISM)
        if want == 1:
            return 1
        if not isinstance(shipped, ShuffleExchangeExec):
            return 1
        if not isinstance(shipped.partitioning,
                          (HashPartitioning, UnknownPartitioning)):
            return 1
        counts = {f.num_partitions
                  for f in shipped.iter_nodes()
                  if isinstance(f, FetchExec) and f.num_partitions > 1}
        if len(counts) != 1:
            return 1
        p = counts.pop()
        n_workers = max(len(self.cluster.registry.alive()), 1)
        cap = n_workers if want <= 0 else want
        return max(1, min(cap, p, n_workers))

    def _run_remote(self, stage: Stage):
        from ..obs.metrics import scoped_submit
        from ..obs.tracing import current_flow, current_query

        shipped = _substitute_parents(stage.root, self)
        sid = self._shuffle_id(stage)
        num_maps = self._map_task_count(shipped)
        # the driver's query scope + the enclosing stage span's flow id
        # ride into the task so worker spans tag and link correctly
        qid = current_query()
        flow_parent = current_flow()

        def run_map(map_id: int):
            import time as _time

            plan = (_slice_fetch_leaves(shipped, map_id, num_maps)
                    if num_maps > 1 else shipped)
            t_start = _time.time()
            result, worker = self.cluster.run_task_traced(
                _run_stage_store, cloudpickle.dumps(plan),
                self.conf_overrides, sid, map_id, num_maps,
                qid, flow_parent, task_key=(sid, map_id),
                on_failed_attempt=lambda eid, err, salvage, _m=map_id:
                    self._record_failed_attempt(qid, sid, _m, eid, err,
                                                salvage))
            (tag, addr, rows, sizes, counters, obs, col_stats,
             dict_ids) = result
            assert tag == "mapstatus", tag
            # close the task in the live store the moment ITS result
            # lands (not at the stage barrier): the final record
            # supersedes the heartbeat partials, and a completed peer's
            # rate immediately becomes the straggler bar for siblings
            # still running (TaskSetManager marks success per task).
            # started= gives fast no-heartbeat tasks their real duration
            # (first_seen alone would make their rate explode)
            if self.live is not None:
                self.live.task_finished(qid, sid, map_id, obs,
                                        rows=sum(rows),
                                        executor=worker.executor_id,
                                        started=t_start)
            return (MapStatus(map_block_id(sid, map_id, num_maps), addr,
                              worker.executor_id, rows, sizes, map_id,
                              col_stats, dict_ids),
                    counters, obs, worker.executor_id)

        try:
            if num_maps == 1:
                outcomes = [run_map(0)]
            else:
                with ThreadPoolExecutor(num_maps) as pool:
                    futures = [scoped_submit(pool, run_map, m)
                               for m in range(num_maps)]
                    outcomes = [f.result() for f in futures]
        except BaseException:
            # sibling map tasks that SUCCEEDED stored blocks under this
            # sid; the status never registers, so free them now or they
            # leak on the workers (the stage retry uses a fresh sid)
            self._free_sid_best_effort(sid)
            raise
        status = ShuffleStatus(sid, [ms for ms, *_ in outcomes])
        self.map_outputs.register(status)
        if getattr(self.cluster, "push_shuffle", False) and \
                self.cluster.shuffle_service_addr:
            status.merge = self._finalize_merge(sid, num_maps)
        # fold worker-side operator metrics into the driver's view
        # (task-return records already closed the live store per task,
        # inside run_map)
        for ms, counters, obs, eid in outcomes:
            for k, v in counters.items():
                self.ctx.metrics.add(k, v)
            self._merge_task_obs(obs, eid, qid)
        self.ctx.metrics.add("scheduler.stages_remote")
        self.ctx.metrics.add("scheduler.map_tasks", num_maps)
        self.ctx.metrics.add("shuffle.bytes_written", status.total_bytes)
        return status

    def _record_failed_attempt(self, qid: str | None, sid: str,
                               map_id: int, executor_id: str,
                               err: Exception,
                               salvage: dict | None) -> None:
        """Chaos salvage (PR 11 follow-on (a)): a failed task attempt's
        worker-side obs rode the error payload instead of dying with
        it. Record the WASTED work — kernel deltas, span count, compile
        ms — on the ExecContext (the query profile's `wasted` section),
        ingest the attempt's spans into the tracer so the timeline
        shows the abandoned attempt, and raise a warning finding so
        chaos-path EXPLAIN ANALYZE names the waste. Deliberately NOT
        merged into plan_metrics or worker_kernel_kinds: launch
        reconciliation must keep counting only work that produced the
        result."""
        # tail of the error text: a cross-process traceback buries the
        # actual failure (the injected-fault marker, the XLA error) at
        # the END of the string
        entry = {"stage": sid, "task": map_id, "executor": executor_id,
                 "error": str(err)[-200:]}
        launches = 0
        if salvage:
            kinds = salvage.get("kernel_kinds") or {}
            launches = salvage.get("kernel_launches", 0)
            entry.update({
                "kernel_kinds": dict(kinds),
                "launches": launches,
                "compile_ms": salvage.get("kernel_compile_ms", 0.0),
                "spans": len(salvage.get("spans") or ())})
            tracer = getattr(self.ctx, "tracer", None)
            if tracer is not None and salvage.get("spans"):
                tracer.ingest(salvage["spans"],
                              anchor=salvage.get("anchor"),
                              track=f"worker:{executor_id}", query_id=qid)
        with self._obs_lock:
            if self.ctx.failed_attempt_obs is None:
                self.ctx.failed_attempt_obs = []
            self.ctx.failed_attempt_obs.append(entry)
        self.ctx.metrics.add("scheduler.task_failures_salvaged")
        if self.live is not None:
            self.live.add_finding(qid, {
                "severity": "warning", "kind": "obs.wasted-work",
                "executor": executor_id,
                "msg": f"task {sid}#m{map_id} attempt on {executor_id} "
                       f"failed after {launches} kernel launch(es) — "
                       "its obs rode the error payload (salvaged wasted "
                       "work; retried elsewhere)"})

    def _merge_task_obs(self, obs: dict | None, executor_id: str,
                        qid: str | None) -> None:
        """Fold one map task's shipped observability into the driver's
        query view: per-operator records by `_metric_id` (so EXPLAIN
        ANALYZE / plan_graph / history server render identical shape
        local vs cluster), spans into the session tracer under the
        worker's own track, and the worker process's KernelCache deltas
        into the query metrics + the per-query worker launch ledger
        (`ctx.worker_kernel_kinds` — EXPLAIN ANALYZE reconciles measured
        launches against driver+worker totals with it)."""
        if obs is None:
            return
        if self.ctx.plan_metrics is not None and obs.get("op_records"):
            from ..obs.metrics import merge_op_records

            merge_op_records(self.ctx.plan_metrics, obs["op_records"])
        tracer = getattr(self.ctx, "tracer", None)
        if tracer is not None and obs.get("spans"):
            tracer.ingest(obs["spans"], anchor=obs.get("anchor"),
                          track=f"worker:{executor_id}", query_id=qid)
        if obs.get("kernel_launches"):
            self.ctx.metrics.add("kernel.launches", obs["kernel_launches"])
        if obs.get("kernel_compile_ms"):
            # round, not truncate — matching the driver-side wrapper in
            # DAGScheduler.run so many small tasks don't bias totals low
            self.ctx.metrics.add("kernel.compile_ms",
                                 round(obs["kernel_compile_ms"]))
        kinds = obs.get("kernel_kinds")
        if kinds:
            with self._obs_lock:
                wk = self.ctx.worker_kernel_kinds
                if wk is None:
                    wk = self.ctx.worker_kernel_kinds = {}
                for k, v in kinds.items():
                    wk[k] = wk.get(k, 0) + v
        disk = obs.get("compile_disk")
        if disk:
            # worker-process XLA disk-cache traffic folds into the same
            # per-query compile.disk_* metrics the driver deltas record
            # (exec/persist_cache.py) — a warm cluster restart's
            # "zero true cold compiles" claim covers workers too
            for k, v in disk.items():
                if v:
                    self.ctx.metrics.add(k, v)
        if obs.get("hbm"):
            # worker HBM is a DIFFERENT device's memory: it folds into
            # the query record as a per-executor remote peak (EXPLAIN
            # ANALYZE's memory section), never into the driver balance
            from ..obs.resources import GLOBAL_LEDGER

            GLOBAL_LEDGER.merge_remote(qid, executor_id, obs["hbm"])

    def _finalize_merge(self, sid: str, num_maps: int):
        """Close the shuffle to late pushes and register which map ids
        each reduce partition's merged chunk holds (the reference's
        shuffleMergeFinalized → MergeStatus registration,
        core/scheduler/MergeStatus.scala). The finalize records a
        PRODUCING span for the merged chunks (the service process has no
        tracer): it claims the deterministic `merge_flow_id` and parents
        to the map-task spans, so exchange edges run map task → merge →
        reduce fetch instead of stopping at the fetch."""
        import pickle
        from contextlib import nullcontext

        from ..net.transport import RetryPolicy, RpcClient

        addr = self.cluster.shuffle_service_addr
        tracer = getattr(self.ctx, "tracer", None)
        sp = tracer.span(
            f"merge[{sid}]", cat="exchange",
            args={"flow_id": merge_flow_id(sid),
                  "flow_parent": [map_block_id(sid, m, num_maps)
                                  for m in range(min(num_maps, 16))],
                  "service": addr},
            flow=True) if tracer is not None else nullcontext()
        with sp:
            try:
                with RpcClient(addr, self.cluster.authkey_hex) as c:
                    # idempotent (finalize twice returns the same index)
                    # — absorb a transient service flap with backoff
                    merged = pickle.loads(
                        c.call("finalize_merge", pickle.dumps(sid),
                               timeout=30,
                               retry=RetryPolicy.from_conf(self.ctx.conf)))
            except Exception:
                return None    # merge unavailable — per-map fetch works
        merge = MergeStatus(sid, addr, num_maps, merged)
        self.map_outputs.register_merge(merge)
        return merge

    def _record_lost_shuffle_executors(self, sid: str,
                                       error_text: str = "") -> None:
        """A FetchFailed names a lost shuffle — count the failure
        against the executor whose block server actually failed (the
        reference's fetch-failure → HealthTracker attribution): the
        error text carries the failing block address, so only producers
        whose address appears in it are blamed (blaming every producer
        of a wide shuffle would exclude healthy executors). Falls back
        to all producers only when no address matches (e.g. a
        re-serialized error lost the detail)."""
        health = getattr(self.cluster, "health", None)
        st = self.map_outputs.get(sid)
        if health is None or st is None:
            return
        producers = {ms.executor_id: ms.block_addr
                     for ms in st.maps if ms.executor_id}
        blamed = [eid for eid, addr in producers.items()
                  if addr and addr in error_text]
        for eid in (blamed or producers):
            try:
                health.record_failure(eid)
            except Exception:
                pass

    def _free_sid_best_effort(self, sid: str) -> None:
        """Free one shuffle id's blocks on EVERY registered worker
        (INCLUDING excluded ones — an executor excluded mid-stage still
        holds its stored blocks) plus the shuffle service — the cleanup
        path for sids that never made it into the MapOutputTracker (a
        stage attempt that stored some map blocks and then failed):
        _free_shuffles can only free what was registered, so partial
        outputs would leak worker memory for the life of the process."""
        key = self.cluster.authkey_hex
        for w in getattr(self.cluster, "registered_workers", list)():
            try:
                free_shuffle(w.client.addr, key, sid)
            except Exception:
                pass
        service = getattr(self.cluster, "shuffle_service_addr", None)
        if service:
            free_shuffle(service, key, sid)

    def _free_one(self, st: ShuffleStatus) -> None:
        """Best-effort release of one shuffle's blocks on its executors
        and its originals/merged chunks at the service."""
        key = self.cluster.authkey_hex
        for ms in st.maps:
            free_shuffle(ms.block_addr, key, ms.shuffle_id)
        service = getattr(self.cluster, "shuffle_service_addr", None)
        if service:
            free_shuffle(service, key, st.shuffle_id)

    def _free_shuffles(self) -> None:
        for sid in self.map_outputs.shuffle_ids():
            st = self.map_outputs.get(sid)
            if st is not None:
                self._free_one(st)
            self.map_outputs.unregister(sid)


def _fetch_failed_shuffle_id(e: Exception) -> str | None:
    """Extract the shuffle id from a FetchFailedError, including one that
    crossed a process boundary as a RemoteTaskError traceback string."""
    if isinstance(e, FetchFailedError):
        return e.shuffle_id
    text = str(e)
    marker = FetchFailedError.MARKER + ":"
    if marker in text:
        return text.split(marker, 1)[1].split(":", 1)[0]
    return None


def _merged_col_stats(maps: list) -> dict | None:
    """Union the per-map-task column stats into per-reduce-partition
    stats: min of mins, max of maxes, any OR — the reduce partition's
    rows are exactly the union of every map task's slice for it."""
    out: dict = {}
    for ms in maps:
        for rid, cols in (ms.col_stats or {}).items():
            cur = out.setdefault(rid, {})
            for ci, (lo, hi, any_v) in cols.items():
                if ci in cur:
                    plo, phi, seen = cur[ci]
                    if any_v and seen:
                        cur[ci] = (min(plo, lo), max(phi, hi), True)
                    elif any_v:
                        cur[ci] = (lo, hi, True)
                else:
                    cur[ci] = (lo, hi, any_v)
    return out or None


def _substitute_parents(node, sched: ClusterDAGScheduler):
    """Replace _StageOutput leaves with Fetch leaves bound to the
    executors holding the parent's map outputs (plus the merge index
    when the parent shuffle was push-merged)."""
    if isinstance(node, _StageOutput):
        from ..config import FETCH_MAX_RETRIES, FETCH_RETRY_WAIT_MS

        st = node.stage
        status = st.result
        assert isinstance(status, ShuffleStatus), \
            f"parent stage {st.stage_id} not materialized"
        merge = None
        if status.merge is not None:
            merge = (status.merge.service_addr, status.merge.merged)
        return FetchExec(node.attrs, status.shuffle_id,
                         [(m.map_id, m.block_addr) for m in status.maps],
                         sched.cluster.authkey_hex, status.num_partitions,
                         fallback_addr=getattr(sched.cluster,
                                               "shuffle_service_addr", None),
                         merge=merge,
                         col_stats=_merged_col_stats(status.maps),
                         dict_ids={m.map_id: m.dict_ids
                                   for m in status.maps
                                   if m.dict_ids} or None,
                         fetch_retries=int(  # tpulint: ignore[host-sync]
                             sched.ctx.conf.get(FETCH_MAX_RETRIES)),
                         fetch_wait_ms=float(  # tpulint: ignore[host-sync]
                             sched.ctx.conf.get(FETCH_RETRY_WAIT_MS)))
    return node.map_children(lambda c: _substitute_parents(c, sched))


def _slice_fetch_leaves(node, map_id: int, num_maps: int):
    """Restrict every multi-partition Fetch leaf to the round-robin
    slice `map_id::num_maps` of its reduce partitions — the unit of work
    of one map task. Single-partition leaves (broadcast relations) are
    left whole so every task sees the full build side."""
    if isinstance(node, FetchExec) and node.num_partitions > 1:
        return FetchExec(
            node.attrs, node.shuffle_id, node.maps, node.authkey_hex,
            node.num_partitions, fallback_addr=node.fallback_addr,
            merge=node.merge,
            part_indices=list(range(map_id, node.num_partitions,
                                    num_maps)),
            col_stats=node.col_stats, dict_ids=node.dict_ids,
            fetch_retries=node.fetch_retries,
            fetch_wait_ms=node.fetch_wait_ms)
    return node.map_children(
        lambda c: _slice_fetch_leaves(c, map_id, num_maps))
