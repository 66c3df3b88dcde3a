"""Typed configuration system.

Role of the reference's SparkConf + SQLConf (core/internal/config/package.scala,
sqlcat/.../internal/SQLConf.scala — typed ConfigBuilder entries with defaults,
docs, versioning; see SURVEY.md §5 "Config / flag system"), reduced to a
registry of typed entries with per-session overrides.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable


@dataclass(frozen=True)
class ConfigEntry:
    key: str
    default: Any
    doc: str = ""
    value_type: Callable[[str], Any] = str
    since: str = "0.1.0"


_REGISTRY: dict[str, ConfigEntry] = {}


def _register(entry: ConfigEntry) -> ConfigEntry:
    _REGISTRY[entry.key] = entry
    return entry


def _bool(v):
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes")


# --- core entries ----------------------------------------------------------

SHUFFLE_PARTITIONS = _register(ConfigEntry(
    "spark.sql.shuffle.partitions", 8,
    "Default number of partitions for exchanges (reference default: 200; "
    "TPU default is sized to a pod-slice's device count).", int))

BATCH_CAPACITY = _register(ConfigEntry(
    "spark.tpu.batch.capacity", 1 << 16,
    "Static row capacity of a ColumnarBatch tile. All kernels are compiled "
    "for power-of-two capacity buckets to bound XLA recompilation.", int))

AUTO_BROADCAST_THRESHOLD = _register(ConfigEntry(
    "spark.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Max estimated build-side bytes for broadcast hash join "
    "(reference: SQLConf.AUTO_BROADCASTJOIN_THRESHOLD).", int))

ADAPTIVE_ENABLED = _register(ConfigEntry(
    "spark.sql.adaptive.enabled", True,
    "Re-optimize at exchange boundaries from runtime stats "
    "(reference: sqlx/adaptive/AdaptiveSparkPlanExec.scala).", _bool))

COALESCE_PARTITIONS_ENABLED = _register(ConfigEntry(
    "spark.sql.adaptive.coalescePartitions.enabled", True,
    "AQE partition coalescing (reference: CoalesceShufflePartitions.scala).",
    _bool))

ADVISORY_PARTITION_BYTES = _register(ConfigEntry(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes", 64 * 1024 * 1024,
    "Target partition size for AQE coalescing.", int))

SKEW_JOIN_ENABLED = _register(ConfigEntry(
    "spark.sql.adaptive.skewJoin.enabled", True,
    "Split skewed shuffle partitions (reference: OptimizeSkewedJoin.scala:57).",
    _bool))

ADAPTIVE_RUNTIME_FILTER = _register(ConfigEntry(
    "spark.tpu.adaptive.runtimeFilter", False,
    "Sideways information passing: when a hash-join build side "
    "materializes, harvest its key domain host-side from stats the "
    "engine already accumulates (dense-range memo min/max, StringDict "
    "code domains — ZERO extra syncs or launches) and push a filter "
    "into the not-yet-executed probe-side exchange. Probe map batches "
    "prune rows inside the existing fused shuffle kernel (aux "
    "operands, no new dispatch) or skip whole batches whose seeded "
    "range misses the domain. Distinct from the per-batch kernels of "
    "spark.tpu.join.runtimeFilter.", _bool))

ADAPTIVE_READMISSION = _register(ConfigEntry(
    "spark.tpu.adaptive.readmission", False,
    "Stage-boundary tier re-admission: after shuffle stages "
    "materialize, feed measured output stats back through the compile-"
    "tier chooser so the remaining plan can collapse into one whole-"
    "tier program; recurring queries re-plan from their warm-start "
    "manifest's observed volume before the first batch moves.", _bool))

ADAPTIVE_PARQUET_STATS = _register(ConfigEntry(
    "spark.tpu.adaptive.parquetStats", True,
    "Admit external parquet scans to the whole compile tier from "
    "footer statistics (row-group row counts + min/max) instead of "
    "excluding them categorically for lack of plan-time stats.", _bool))

ADAPTIVE_SKEW_REPARTITION = _register(ConfigEntry(
    "spark.tpu.adaptive.skewRepartition", True,
    "When mesh-exchange quota retries exhaust on pathological skew, "
    "split the remaining batches and re-plan the exchange as smaller "
    "mesh programs instead of falling straight back to the host "
    "shuffle (which stays as the terminal fallback).", _bool))

CASE_SENSITIVE = _register(ConfigEntry(
    "spark.sql.caseSensitive", False,
    "Case sensitivity of identifier resolution.", _bool))

ANSI_ENABLED = _register(ConfigEntry(
    "spark.sql.ansi.enabled", False,
    "ANSI SQL semantics (errors on overflow/invalid cast instead of null).",
    _bool))

SESSION_TIMEZONE = _register(ConfigEntry(
    "spark.sql.session.timeZone", "UTC", "Session timezone.", str))

DEFAULT_PARALLELISM = _register(ConfigEntry(
    "spark.default.parallelism", 8,
    "Default partition count for parallelize / scans.", int))

MAX_RESULT_ROWS = _register(ConfigEntry(
    "spark.tpu.collect.maxRows", 1 << 26,
    "Safety cap on rows materialized to the host by collect().", int))

DEVICE_MESH_AXIS = _register(ConfigEntry(
    "spark.tpu.mesh.dataAxis", "data",
    "Name of the mesh axis partitions are sharded over.", str))

MESH_ENABLED = _register(ConfigEntry(
    "spark.tpu.mesh.enabled", True,
    "Lower hash exchanges to lax.all_to_all over the device mesh when the "
    "partition count fits the mesh (the ICI data plane; reference analog: "
    "ShuffleExchangeExec lowering to the core shuffle). Falls back to the "
    "host sort-shuffle otherwise.", _bool))

DPP_ENABLED = _register(ConfigEntry(
    "spark.sql.dynamicPartitionPruning.enabled", True,
    "Prune probe-side scan splits from the join build side's distinct keys "
    "(reference: sqlx/dynamicpruning/PartitionPruning.scala).", _bool))

DPP_BUILD_THRESHOLD = _register(ConfigEntry(
    "spark.sql.dynamicPartitionPruning.buildThreshold", 4 << 20,
    "Max build-side rows for which distinct join-key values are collected "
    "for dynamic partition pruning.", int))

PARQUET_FILTER_PUSHDOWN = _register(ConfigEntry(
    "spark.sql.parquet.filterPushdown", True,
    "Prune parquet splits by hive partition values and row-group min/max "
    "statistics (reference: ParquetFileFormat/ParquetFilters).", _bool))

BLOOM_JOIN_FILTER = _register(ConfigEntry(
    "spark.tpu.join.runtimeFilter.bloom", False,
    "Device bloom-filter probe-side rows before the join sort-probe "
    "(reference: InjectRuntimeFilter.scala bloom branch).", _bool))

MINMAX_JOIN_FILTER = _register(ConfigEntry(
    "spark.tpu.join.runtimeFilter", False,
    "Min-max runtime join filter on single integral keys.", _bool))

SPECULATION = _register(ConfigEntry(
    "spark.speculation", False,
    "Re-launch straggler host tasks on another executor; first success "
    "wins, file commits arbitrated by the OutputCommitCoordinator "
    "(reference: TaskSetManager.scala:80-88).", _bool))

SHUFFLE_MAP_PARALLELISM = _register(ConfigEntry(
    "spark.tpu.shuffle.mapParallelism", 1,
    "Max map tasks per cluster shuffle map stage. 1 = stage-granular "
    "(one mapper computes the whole subtree); >1 slices the stage's "
    "multi-partition Fetch leaves across that many tasks on different "
    "executors; 0 = auto (min of alive executors and input partitions). "
    "Only hash/round-robin exchanges slice (range bounds are sampled "
    "per task, so slicing a range exchange would break global order).",
    int))

STATE_STORE_PARTITIONS = _register(ConfigEntry(
    "spark.sql.streaming.stateStore.numPartitions", 4,
    "Hash partitions for streaming state: each partition keeps its own "
    "snapshot+changelog lineage and a batch persists only touched "
    "partitions (reference: per-partition StateStore instances, "
    "sqlx/streaming/state/StateStore.scala:285).", int))

FUSION_ENABLED = _register(ConfigEntry(
    "spark.tpu.fusion.enabled", True,
    "Whole-stage kernel fusion: collapse each exchange-free chain of "
    "fusable operators (filter/project feeding a partial aggregate, limit, "
    "or hash-join probe) into ONE jitted program per batch "
    "(reference: WholeStageCodegenExec produce/consume splicing, "
    "sqlx/WholeStageCodegenExec.scala:673). Off = operator-at-a-time "
    "execution, kept as the differential-testing oracle.", _bool))

PARTITION_PARALLELISM = _register(ConfigEntry(
    "spark.tpu.exec.partitionParallelism", 0,
    "Concurrent partition-dispatch lanes inside an operator (async XLA "
    "dispatch pipelines across partitions instead of serial list "
    "comprehensions). 0 = auto (min(4, cpus)); 1 = serial.", int))

FUSION_MIN_ROWS = _register(ConfigEntry(
    "spark.tpu.fusion.minRows", 1 << 17,
    "Partition tile-capacity floor for running the whole-stage FUSED "
    "kernel. A fused program is compiled per (stage structure, signature, "
    "capacity) while the operator-at-a-time kernels are shared across "
    "query structures — below this many rows the XLA compile costs more "
    "than the dispatches it saves, so small partitions take the unfused "
    "kernels (same plan, runtime dispatch). 0 = always fuse.", int))

FUSION_DENSE_KEYS = _register(ConfigEntry(
    "spark.tpu.fusion.denseKeys", True,
    "Allow the fused partial aggregate to take the dense-range direct "
    "scatter path when the grouping key is a pass-through integral column "
    "whose (memoized) range fits a capacity bucket.", _bool))

FUSION_MESH = _register(ConfigEntry(
    "spark.tpu.fusion.mesh", True,
    "Mesh-native SPMD stage fusion: a fused shuffle exchange whose "
    "partition count matches the device mesh runs its WHOLE stage — "
    "traced filter/project pipeline, partition-id computation, per-shard "
    "bucket-by-destination and the ICI all-to-all — as ONE shard_map "
    "program per step, with the staged send buffers donated "
    "(donate_argnums) so the all-to-all reuses their HBM in-place. Off: "
    "the legacy composition materializes the pipeline per batch before "
    "the collective. Requires spark.tpu.fusion.enabled and "
    "spark.tpu.fusion.exchange; the minRows gate does not apply (the "
    "mesh stage is one program per step, not per batch).", _bool))

FUSION_EXCHANGE = _register(ConfigEntry(
    "spark.tpu.fusion.exchange", True,
    "Exchange map-side fusion: a stage whose terminal is a shuffle "
    "exchange traces its filter/project pipeline AND the partition-id "
    "computation (hash/range/round-robin) into ONE jitted kernel per map "
    "batch that emits the pid-grouped pipeline output; shuffle writes "
    "consume it directly — no intermediate materialized batch, <=1 "
    "dispatch per map batch. Requires spark.tpu.fusion.enabled; subject "
    "to the spark.tpu.fusion.minRows size gate.", _bool))

COMPILE_TIER = _register(ConfigEntry(
    "spark.tpu.compile.tier", "auto",
    "Compilation tier: 'mesh-whole' compiles the ENTIRE sharded query "
    "into ONE shard_map program per step — leaf planes row-sharded over "
    "the device mesh, hash exchanges as in-program lax.all_to_all, "
    "reduce-side consumers folded in behind the collective "
    "(physical/mesh_whole.py; needs spark.tpu.mesh.enabled, plain hash "
    "keys, one power-of-two partition count and enough devices — else "
    "falls back tier-by-tier with the reason on the decision); 'whole' "
    "compiles the query — exchanges lowered to in-program gathers — "
    "into ONE single-device jitted program per step (zero host shuffle "
    "round-trips; physical/whole_query.py); 'stage' compiles one "
    "program per stage per batch (PR 1/5/8 fusion, with the "
    "per-partition minRows runtime gate as the stage->operator "
    "fallback); 'operator' forces the shared operator-at-a-time kernels "
    "(the differential oracle). 'auto' (default) chooses from predicted "
    "compile cost, predicted fully-resident HBM (spark.tpu.memory.budget "
    "admission), and batch volume (spark.tpu.compile.whole.minRows), "
    "falling back tier-by-tier when statistics are unknown or budgets "
    "are exceeded — the generalization of the spark.tpu.fusion.minRows "
    "gate to whole programs; mesh-whole admits in auto ONLY when the "
    "single-device program exceeds the budget but a per-shard slice "
    "fits.", str))

WHOLE_MIN_ROWS = _register(ConfigEntry(
    "spark.tpu.compile.whole.minRows", 1 << 17,
    "Leaf-row volume floor for the auto tier to choose whole-query "
    "compilation (scaled up with program depth: deeper programs need "
    "more volume to amortize the bigger XLA compile). The whole-query "
    "analog of spark.tpu.fusion.minRows. Forced tier=whole ignores the "
    "floor (structural and memory admission still apply).", int))

ENCODING_ENABLED = _register(ConfigEntry(
    "spark.tpu.encoding.enabled", True,
    "Compressed execution: kernels operate directly on encoded columns. "
    "Single dictionary-encoded (string) grouping keys aggregate by direct "
    "scatter over the dense code domain (the dictionary IS the group "
    "table — no sort, no range probe), string join/exchange keys fuse "
    "into stage kernels via padded dictionary-hash aux tables, sorted "
    "run-length-encoded keys reduce per run without sorting, and cluster "
    "shuffle ships dictionary codes + one dictionary per map task instead "
    "of decoded values. Off = the decode-at-boundary oracle for "
    "differential testing.", _bool))

# --- entries below were historically read by string literal at their use
# sites; registered here so config has a single typed source of truth
# (found and enforced by dev/tpulint.py's config-key rule) -----------------

VALIDATE_BATCHES = _register(ConfigEntry(
    "spark.tpu.debug.validateBatches", False,
    "Validate every operator's output batches (shape/dtype/mask "
    "invariants; columnar/validate.py). Debug only — syncs per batch.",
    _bool))

UI_OPERATOR_METRICS = _register(ConfigEntry(
    "spark.tpu.ui.operatorMetrics", True,
    "Record per-operator rows/time SQLMetrics for the plan graph/UI "
    "(exec/query_execution.py). One dict lookup per execute when off.",
    _bool))

AGG_BLOCK_ROWS = _register(ConfigEntry(
    "spark.tpu.agg.blockRows", 1 << 22,
    "Tile-capacity ceiling for a single aggregation chunk; larger "
    "partitions fold blockwise and merge partials (the sort-based "
    "fallback role of TungstenAggregationIterator).", int))

JOIN_RF_MIN_CAPACITY = _register(ConfigEntry(
    "spark.tpu.join.runtimeFilter.minCapacity", 1 << 20,
    "Probe batches below this capacity skip the runtime min-max join "
    "filter (the sort-probe is already cheap).", int))

DSV2_FILTER_PUSHDOWN = _register(ConfigEntry(
    "spark.tpu.datasource.filterPushdown", True,
    "Negotiate predicate pushdown with SupportsPushDownFilters sources "
    "(V2ScanRelationPushDown role).", _bool))

DSV2_AGG_PUSHDOWN = _register(ConfigEntry(
    "spark.tpu.datasource.aggPushdown", True,
    "Push whole group-by aggregates into SupportsPushDownAggregation "
    "sources.", _bool))

CLUSTER_MASTER = _register(ConfigEntry(
    "spark.tpu.master", "",
    "grpc://host:port of a standalone master to attach to "
    "(deploy/standalone.py; the spark-submit --master flow).", str))

CLUSTER_MASTER_SECRET = _register(ConfigEntry(
    "spark.tpu.master.secret", "",
    "Shared secret for the standalone master (or env "
    "SPARK_TPU_MASTER_SECRET).", str))

CLUSTER_ENABLED = _register(ConfigEntry(
    "spark.tpu.cluster.enabled", False,
    "Spawn a local process cluster for SQL execution (the reference's "
    "local-cluster mode).", _bool))

CLUSTER_WORKERS = _register(ConfigEntry(
    "spark.tpu.cluster.workers", 2,
    "Worker process count for the local process cluster.", int))

PUSH_SHUFFLE = _register(ConfigEntry(
    "spark.tpu.shuffle.push", False,
    "Push-based shuffle: mappers push blocks to reducer-side merged "
    "files (reference: push-based shuffle, core/shuffle/push).", _bool))

# --- observability (spark_tpu/obs/) ---------------------------------------

TRACE_ENABLED = _register(ConfigEntry(
    "spark.tpu.trace.enabled", True,
    "Always-on span tracing of the query lifecycle (parse/analyze/"
    "optimize/plan/stage/partition/exchange/collect; obs/tracing.py). "
    "Pure host bookkeeping — zero kernel launches, zero device syncs; "
    "export with session.tracer.write_chrome_trace().", _bool))

TRACE_MAX_SPANS = _register(ConfigEntry(
    "spark.tpu.trace.maxSpans", 100_000,
    "Span-buffer cap per session tracer; spans past it are dropped and "
    "counted so a long-lived session stays bounded.", int))

KERNEL_ATTRIBUTION = _register(ConfigEntry(
    "spark.tpu.metrics.kernelAttribution", True,
    "Attribute KernelCache launch/compile-ms counters to the executing "
    "physical operator (obs/metrics.py contextvar scope, propagated into "
    "par_map lanes). Requires spark.tpu.ui.operatorMetrics; one "
    "contextvar read per kernel launch when on.", _bool))

CLUSTER_OBS_SHIPPING = _register(ConfigEntry(
    "spark.tpu.cluster.obsShipping", True,
    "Ship worker-side observability (per-operator metric records, spans, "
    "kernel-launch deltas) back with each cluster stage-task result and "
    "merge it into the driver's QueryMetrics/Tracer (the executor "
    "heartbeat metrics channel, reduced to per-task return). Off = "
    "cluster queries report driver-side observability only (saves the "
    "payload bytes on very wide fan-outs).", _bool))

# --- live telemetry (spark_tpu/obs/live.py) --------------------------------

HEARTBEAT_INTERVAL = _register(ConfigEntry(
    "spark.tpu.heartbeat.interval", 3.0,
    "Executor heartbeat period in seconds (exec/worker_main.py → driver; "
    "the reference's spark.executor.heartbeatInterval). Live obs deltas "
    "ride the same call, so this is also the worker-side flush cadence.",
    float))

HEARTBEAT_OBS = _register(ConfigEntry(
    "spark.tpu.heartbeat.obs", True,
    "Stream incremental observability deltas (open/closed spans since "
    "last flush, per-operator rows/batches/wall-ms, per-kind KernelCache "
    "launch/compile deltas) of running stage tasks on the executor "
    "heartbeat, feeding the driver's live store (obs/live.py). Pure host "
    "bookkeeping — zero kernel launches, no mid-query device syncs "
    "(parked row-masks stay parked until task end).", _bool))

PROGRESS_CONSOLE = _register(ConfigEntry(
    "spark.tpu.progress.console", False,
    "Render live per-stage progress bars (tasks done, rows/launches so "
    "far, straggler flags) to stderr while queries run, fed by the live "
    "telemetry store (reference: spark.ui.showConsoleProgress / "
    "ConsoleProgressBar).", _bool))

PROGRESS_UPDATE_INTERVAL = _register(ConfigEntry(
    "spark.tpu.progress.updateInterval", 0.5,
    "Console progress / local-mode flush repaint period in seconds.",
    float))

STRAGGLER_ENABLED = _register(ConfigEntry(
    "spark.tpu.straggler.enabled", True,
    "Flag straggling stage tasks from live heartbeat telemetry "
    "(obs.straggler findings in live status and EXPLAIN ANALYZE; signal "
    "hook for speculative execution).", _bool))

STRAGGLER_RATE_FRACTION = _register(ConfigEntry(
    "spark.tpu.straggler.rateFraction", 0.2,
    "A running task is a straggler when its progress rate (rows+batches+"
    "launches per second) falls below this fraction of the stage-wide "
    "median rate.", float))

STRAGGLER_MIN_SECONDS = _register(ConfigEntry(
    "spark.tpu.straggler.minSeconds", 1.0,
    "Minimum task runtime before rate-based straggler detection may "
    "fire (healthy short tasks must never be flagged).", float))

STRAGGLER_HEARTBEAT_DEADLINE = _register(ConfigEntry(
    "spark.tpu.straggler.heartbeatDeadline", 30.0,
    "A running task whose live telemetry goes silent for this many "
    "seconds is flagged as a straggler regardless of rate (executor "
    "frozen or partitioned).", float))

STRAGGLER_RATE_WEIGHTS = _register(ConfigEntry(
    "spark.tpu.straggler.rateWeights", "1,1,1",
    "Comma-separated rows,batches,launches weights of the straggler "
    "progress-rate unit (weighted sum per second vs the stage median). "
    "The default 1,1,1 preserves the original equal weighting; skew "
    "the weights for workloads where one dimension dominates cost "
    "(e.g. '1,0,0' for row-bound scans) so cost-skewed stages stop "
    "false-flagging.", str))

# --- resource observability (spark_tpu/obs/resources.py) -------------------

MEMORY_LEDGER = _register(ConfigEntry(
    "spark.tpu.memory.ledger", True,
    "Attributed HBM shadow ledger: every engine-held device buffer "
    "(columnar batches — column data, validity planes, row masks) "
    "registers its metadata-derived byte size to the current "
    "query/operator scope and deregisters on GC, giving live occupancy "
    "and per-query/per-stage watermarks (obs/resources.py). Pure host "
    "bookkeeping — zero kernel launches, no device syncs.", _bool))

MEMORY_BUDGET = _register(ConfigEntry(
    "spark.tpu.memory.budget", 0,
    "Per-query HBM admission budget in bytes (0 = unlimited): before "
    "dispatch, the plan analyzer's memory model predicts peak resident "
    "HBM and the query fails with MemoryBudgetExceeded naming the "
    "offending stage instead of an opaque XLA OOM mid-query (role of "
    "the reference's ExecutionMemoryPool acquireMemory refusal).", int))

KERNEL_COST = _register(ConfigEntry(
    "spark.tpu.metrics.kernelCost", True,
    "Capture each compiled kernel's XLA cost_analysis() (flops, bytes "
    "accessed) at first invocation via the lowering — no second backend "
    "compile — with an argument/output-metadata fallback; launches then "
    "attribute flops/bytes to the executing operator for EXPLAIN "
    "ANALYZE's achieved-GB/s roofline view.", _bool))

MEMORY_PEAK_GBPS = _register(ConfigEntry(
    "spark.tpu.memory.peakGbps", 0.0,
    "Peak HBM bandwidth (GB/s) for achieved-vs-peak rendering; 0 = auto "
    "from the device kind (CPU backends report no roofline).", float))

KERNEL_MEMORY = _register(ConfigEntry(
    "spark.tpu.metrics.kernelMemory", False,
    "Capture each compiled kernel's XLA memory_analysis() temp (scratch) "
    "bytes at first invocation and fold them into EXPLAIN ANALYZE's HBM "
    "reconciliation and the query profile (the device ledger tracks "
    "engine-held tiles only — fused-kernel scratch is invisible to it). "
    "Off by default: the AOT lowering compile this requires is NOT "
    "shared with the dispatch path on this jax version, so capture "
    "costs one extra backend compile per distinct kernel.", _bool))

# --- query flight recorder (spark_tpu/obs/history.py) ----------------------

OBS_PROFILE_DIR = _register(ConfigEntry(
    "spark.tpu.obs.profileDir", "",
    "Directory for the persistent query flight recorder: at query close "
    "the driver appends a QueryProfile (plan fingerprint, per-operator "
    "metrics, launches/compile-ms by kind, tier decision, retry/fault "
    "counters, HBM watermarks, per-stage runtime stats) as one JSONL "
    "line keyed by the query's structural fingerprint, then compares "
    "the fresh profile against the fingerprint's stored baseline and "
    "raises obs.regression findings on deterministic-counter drift. "
    "Empty (default) = recorder off. Driver-owned: worker processes "
    "never write profiles regardless of this setting. Pure host "
    "bookkeeping — zero kernel launches, no mid-query device syncs "
    "(assembly runs after the query's last device interaction).", str))

OBS_PROFILE_RING = _register(ConfigEntry(
    "spark.tpu.obs.profileRing", 32,
    "Profiles retained per query fingerprint in the on-disk store (the "
    "JSONL file compacts to the newest N once it doubles the bound).",
    int))

OBS_PROFILE_BASELINE_N = _register(ConfigEntry(
    "spark.tpu.obs.profileBaselineN", 5,
    "Regression baseline window: the fresh profile compares against the "
    "MEDIAN of the last N stored profiles for the same structural query "
    "key.", int))

OBS_PROFILE_REGRESSION = _register(ConfigEntry(
    "spark.tpu.obs.profileRegression", True,
    "Raise obs.regression findings at query close when the fresh "
    "profile's deterministic counters (kernel launches by kind, compile "
    "count, retry/fault attempts) EXCEED the stored baseline (severity "
    "error), or wall/HBM drift past the advisory tolerance (severity "
    "info). Requires spark.tpu.obs.profileDir.", _bool))

OBS_PROFILE_WALL_TOLERANCE = _register(ConfigEntry(
    "spark.tpu.obs.profileWallTolerance", 1.5,
    "Advisory wall-clock drift factor: a fresh profile slower than "
    "tolerance x the baseline median wall-ms raises an info-severity "
    "obs.regression finding (wall time is noisy — never an error).",
    float))

# --- persistent caches (spark_tpu/exec/persist_cache.py) -------------------

CACHE_DIR = _register(ConfigEntry(
    "spark.tpu.cache.dir", "",
    "Root directory for the persistent caches: the XLA compile cache "
    "(<dir>/xla unless JAX_COMPILATION_CACHE_DIR places it elsewhere — "
    "jitted programs compiled once hit disk on every later "
    "process's first dispatch), the warm-start manifest (<dir>/"
    "manifest.jsonl — per-fingerprint tier decisions and join/mesh "
    "capacity outcomes, so a restarted server skips capacity-retry "
    "recompiles), and the result cache (<dir>/result — full "
    "plan-fingerprint + data-version keyed Arrow IPC payloads; a hit "
    "answers with ZERO kernel launches). Empty (default) = manifest and "
    "result cache off, and the XLA compile cache at the fixed "
    "in-checkout .cache/xla (tier-1 exact-count tests pin it off with "
    "jax_enable_compilation_cache).", str))

CACHE_COMPILE = _register(ConfigEntry(
    "spark.tpu.cache.compile.enabled", True,
    "Keep jax's XLA persistent compilation cache on (at "
    "JAX_COMPILATION_CACHE_DIR, else <spark.tpu.cache.dir>/xla, else "
    "the in-checkout .cache/xla) so every jitted program's backend "
    "compile is written to disk once and served from disk in later "
    "processes (the normal jax.jit dispatch path stays intact — no AOT "
    "lowered.compile(), whose compile is unshared with dispatch on this "
    "jax version). The obs layer counts compile.disk_hit distinctly "
    "from true cold compiles.", _bool))

CACHE_COMPILE_MAX_BYTES = _register(ConfigEntry(
    "spark.tpu.cache.compile.maxBytes", 0,
    "LRU byte bound for the on-disk XLA compile cache "
    "(jax_compilation_cache_max_size; least-recently-used entries are "
    "evicted past it). 0 = unbounded.", int))

CACHE_RESULT = _register(ConfigEntry(
    "spark.tpu.cache.result.enabled", True,
    "With spark.tpu.cache.dir set, cache full query RESULTS on disk "
    "keyed by plan fingerprint + a data-version component (warehouse "
    "parquet file identity, in-memory table content hash) — a repeated "
    "identical query answers from the Arrow IPC payload with zero "
    "kernel launches, shared across connect sessions, processes, and "
    "the cluster driver. Plans with non-deterministic expressions or "
    "unknown leaf data identity bypass the cache. Invalidated through "
    "the catalog write path on append/overwrite (and by the file "
    "identity in the key).", _bool))

CACHE_RESULT_MAX_BYTES = _register(ConfigEntry(
    "spark.tpu.cache.result.maxBytes", 256 << 20,
    "Byte budget for the on-disk result cache; past it the "
    "least-recently-hit payloads are evicted (flock-safe across "
    "processes). One result larger than an eighth of the budget is "
    "never cached.", int))

# --- chaos hardening (PR 11): fault injection, retry/backoff, exclusion ---

FAULTS_ENABLED = _register(ConfigEntry(
    "spark.tpu.faults.enabled", False,
    "Deterministic fault injection (utils/faults.py): named fault "
    "points threaded through the stack (rpc.call, block.fetch, "
    "worker.task, heartbeat.flush, kernel.compile, kernel.dispatch, "
    "shuffle.write) fire per spark.tpu.faults.points rules. Off "
    "(default) short-circuits every point to one module-bool read — "
    "zero overhead on healthy runs. Ships to workers like all conf.",
    _bool))

LOCKWATCH_ENABLED = _register(ConfigEntry(
    "spark.tpu.lockwatch.enabled", False,
    "Runtime lock-discipline validation (utils/lockwatch.py): swap "
    "registered process-global locks for watching proxies that record "
    "acquisition orders and held-lock sets at instrumented mutation "
    "sites; tests/test_race_lint.py cross-checks the records "
    "against the static race_lint model. Off (default) runs raw "
    "unwrapped locks — zero overhead. SPARK_TPU_LOCKWATCH=1 enables at "
    "import time and ships to cluster workers via their environment.",
    _bool))

FAULTS_SEED = _register(ConfigEntry(
    "spark.tpu.faults.seed", 0,
    "Seed for probabilistic fault rules; identical seed + call order "
    "reproduces the identical fault schedule per process.", int))

FAULTS_POINTS = _register(ConfigEntry(
    "spark.tpu.faults.points", "",
    "';'-separated fault rules, each point=trigger[:arg][:action[:arg]]"
    "[@scope]. Triggers: once | nth:N | first:N | after:N (every call "
    "past the Nth — the blackout shape) | prob:P | always. "
    "Actions: raise (default) | kill (os._exit) | sleep:S. @scope "
    "restricts to processes with that host label or calls whose detail "
    "contains it (e.g. kernel.dispatch=once@whole_query).", str))

RPC_MAX_RETRIES = _register(ConfigEntry(
    "spark.tpu.rpc.maxRetries", 3,
    "Bounded retry count for transient RpcUnavailableError on "
    "conf-driven idempotent control-plane calls (finalize_merge; any "
    "caller constructing RetryPolicy.from_conf) — the reference's "
    "spark.rpc.numRetries role. Fire-and-forget cleanup RPCs "
    "(free_shuffle, push_block) use a fixed small best-effort policy "
    "instead, so a flapping peer can never stall shutdown on a "
    "generous conf.", int))

RPC_RETRY_BACKOFF_MS = _register(ConfigEntry(
    "spark.tpu.rpc.retryBackoffMs", 50.0,
    "Base backoff between control-plane RPC retries; grows "
    "exponentially per attempt with full jitter, capped at 2s.", float))

RPC_RETRY_DEADLINE = _register(ConfigEntry(
    "spark.tpu.rpc.retryDeadline", 10.0,
    "Wall-clock budget in seconds for one logical control-plane call "
    "including all its retries — retries never extend past it.", float))

FETCH_MAX_RETRIES = _register(ConfigEntry(
    "spark.tpu.shuffle.fetch.maxRetries", 2,
    "Bounded shuffle-block fetch retries (primary then shuffle-service "
    "fallback per round) BEFORE raising FetchFailedError — a transient "
    "block-server flap stops paying a full lineage stage regeneration "
    "(reference: spark.shuffle.io.maxRetries).", int))

FETCH_RETRY_WAIT_MS = _register(ConfigEntry(
    "spark.tpu.shuffle.fetch.retryWaitMs", 50.0,
    "Wait between shuffle fetch retry rounds (scaled linearly by "
    "attempt; reference: spark.shuffle.io.retryWait).", float))

EXCLUDE_ON_FAILURE = _register(ConfigEntry(
    "spark.tpu.excludeOnFailure.enabled", True,
    "Window-based executor exclusion (reference: TaskSetExcludelist / "
    "HealthTracker, spark.excludeOnFailure.*): executors accumulating "
    "maxFailures task failures inside windowSecs stop receiving tasks "
    "for timeoutSecs, then rejoin automatically (timed re-inclusion). "
    "Surfaced in live status, console executor rows, and EXPLAIN "
    "ANALYZE findings.", _bool))

EXCLUDE_MAX_FAILURES = _register(ConfigEntry(
    "spark.tpu.excludeOnFailure.maxFailures", 2,
    "Task failures inside the window before an executor is excluded "
    "(reference: spark.excludeOnFailure.task.maxTaskAttemptsPerExecutor "
    "family).", int))

EXCLUDE_WINDOW_SECS = _register(ConfigEntry(
    "spark.tpu.excludeOnFailure.windowSecs", 60.0,
    "Sliding window over which executor failures count toward "
    "exclusion; older failures expire.", float))

EXCLUDE_TIMEOUT_SECS = _register(ConfigEntry(
    "spark.tpu.excludeOnFailure.timeoutSecs", 30.0,
    "How long an excluded executor stays out of scheduling before "
    "timed re-inclusion (reference: spark.excludeOnFailure.timeout).",
    float))

STAGE_MAX_REGENS = _register(ConfigEntry(
    "spark.tpu.scheduler.maxStageRegens", 8,
    "Per-query cap on FetchFailed-driven stage regenerations; past it "
    "the query fails with the classified StageRegenerationLimitError "
    "instead of looping (reference: spark.stage.maxConsecutiveAttempts "
    "+ the DAGScheduler abort-on-repeated-fetch-failure path).", int))

HEARTBEAT_FLUSH_BUDGET = _register(ConfigEntry(
    "spark.tpu.heartbeat.flushBudget", 1 << 18,
    "Approximate byte cap on the live-obs payload of ONE executor "
    "heartbeat. Beyond it, remaining in-flight tasks ship minimal "
    "counter-only deltas and an overflow counter surfaces in live "
    "status; their closed spans stay in a bounded carry buffer and the "
    "trim rotates across tasks, so each task periodically ships in "
    "full (only a task closing more spans than the carry bound before "
    "its rotation turn loses its oldest — the task-return record still "
    "carries the complete set). 0 = uncapped.", int))


# --- multi-tenant serving (spark_tpu/serve/) -------------------------------

SERVE_POOLS = _register(ConfigEntry(
    "spark.tpu.scheduler.pools", "",
    "Comma-separated fair-scheduler pool declarations 'name[:weight]' "
    "(e.g. 'dash:2,batch:1'). The 'default' pool (weight 1) always "
    "exists. Per-pool overrides ride "
    "spark.tpu.scheduler.pool.<name>.{weight,maxConcurrent,queueSize,"
    "queueTimeout,hbmBudget}. Role of the reference's "
    "FairSchedulableBuilder + fairscheduler.xml pools.", str))

SERVE_POOL = _register(ConfigEntry(
    "spark.tpu.scheduler.pool", "default",
    "Fair-scheduler pool this session's queries are admitted under "
    "(SET spark.tpu.scheduler.pool=... — the reference's thread-local "
    "spark.scheduler.pool selection). Undeclared pools are created on "
    "demand with default settings.", str))

SERVE_MAX_CONCURRENT = _register(ConfigEntry(
    "spark.tpu.serve.maxConcurrent", 4,
    "Global cap on concurrently EXECUTING queries across all pools "
    "(fair-share slots; queued queries wait their pool's weighted "
    "turn). 0 = unlimited.", int))

SERVE_QUEUE_SIZE = _register(ConfigEntry(
    "spark.tpu.serve.queueSize", 64,
    "Default per-pool admission-queue bound; a query arriving at a "
    "full queue is rejected immediately with POOL_QUEUE_FULL (load "
    "shedding) instead of queueing unboundedly.", int))

SERVE_QUEUE_TIMEOUT = _register(ConfigEntry(
    "spark.tpu.serve.queueTimeout", 30.0,
    "Default per-pool queue timeout in seconds: a query that has not "
    "won a slot within it is rejected with ADMISSION_TIMEOUT.", float))

SERVE_SESSION_MODE = _register(ConfigEntry(
    "spark.tpu.serve.sessionMode", "isolated",
    "SQL-endpoint session model: 'isolated' (default) clones one "
    "session per connection (connection-local SET/temp views, shared "
    "KernelCache/warehouse/persistent caches — the reference's "
    "ThriftServer session-per-connection model); 'shared' keeps the "
    "legacy all-connections-share-one-session behavior (a connection "
    "can also opt in per-request with {\"session\": \"shared\"}).",
    str))

SERVE_DRAIN_TIMEOUT = _register(ConfigEntry(
    "spark.tpu.serve.drainTimeout", 30.0,
    "Graceful-drain budget in seconds for SQLEndpoint.stop()/SIGTERM: "
    "new queries are rejected with SERVER_DRAINING immediately; "
    "in-flight (and already-queued) queries get this long to finish "
    "and flush their query profiles before the socket closes.", float))

SERVE_SLO_MS = _register(ConfigEntry(
    "spark.tpu.serve.sloMs", 0.0,
    "Default per-query end-to-end latency SLO target in ms (submit to "
    "release, queue wait included) for every fair-scheduler pool; 0 "
    "disables SLO accounting. Per-pool overrides ride "
    "spark.tpu.serve.pool.<name>.sloMs. Queries over target bump the "
    "pool's burn counter and raise obs.slo findings in live status and "
    "EXPLAIN ANALYZE.", float))

SERVE_POOL_SLO = _register(ConfigEntry(
    "spark.tpu.serve.pool.<name>.sloMs", 0.0,
    "Per-pool end-to-end latency SLO target in ms, overriding "
    "spark.tpu.serve.sloMs for pool <name> (documentation template — "
    "substitute the pool name; read via the per-pool override path "
    "like the spark.tpu.scheduler.pool.<name>.* family).", float))

# --- service metrics plane (spark_tpu/obs/export.py) -----------------------

METRICS_EXPORT = _register(ConfigEntry(
    "spark.tpu.metrics.export", False,
    "Service metrics plane master switch: the process-wide "
    "MetricsRegistry scrape surface (Prometheus text /metrics on the "
    "history server, {\"metrics\": true} on the SQL endpoint), the "
    "time-series ticker thread, and per-executor registry deltas on "
    "the heartbeat. Structurally zero overhead when off (module-bool "
    "fast path; no ticker thread, no heartbeat field, no scrape "
    "collection). Role of the reference's spark.metrics.conf + "
    "PrometheusServlet.", _bool))

METRICS_TICK_INTERVAL = _register(ConfigEntry(
    "spark.tpu.metrics.tickInterval", 5.0,
    "Seconds between time-series ticker samples of the metric surface "
    "into the bounded in-memory ring (sparklines in serve status, the "
    "drain-time snapshot). Host-counter reads only — a tick launches "
    "no kernels and never syncs the device.", float))

METRICS_RING_SIZE = _register(ConfigEntry(
    "spark.tpu.metrics.ringSize", 120,
    "Points retained in the in-memory metrics time-series ring (at the "
    "default 5s tick interval, 120 points = 10 minutes of sparkline "
    "history; memory stays bounded regardless of uptime).", int))

# --- query black box (spark_tpu/obs/blackbox.py) ---------------------------

OBS_BUNDLES = _register(ConfigEntry(
    "spark.tpu.obs.bundles", False,
    "Anomaly-triggered diagnostic bundle capture: on any severity-error "
    "finding (obs.slo breach, obs.regression, obs.straggler, "
    "tier.degraded, exec.excluded, admission rejection, query failure) "
    "the driver assembles a self-contained postmortem bundle (Chrome "
    "trace, EXPLAIN reports, metrics snapshot + time-series window, "
    "QueryProfile with same-key baseline history, executor/HBM state, "
    "non-default config, the finding chain, pulled worker diagnostic "
    "rings) under spark.tpu.obs.bundleDir. Structurally zero overhead "
    "when off (module-bool fast path); armed-but-untriggered runs "
    "launch zero extra kernels — capture is pull-on-anomaly, never "
    "ship-always.", _bool))

OBS_BUNDLE_DIR = _register(ConfigEntry(
    "spark.tpu.obs.bundleDir", "",
    "Directory holding diagnostic bundles (one subdirectory per bundle "
    "plus a flock-safe index.jsonl retention ring). Empty (default) "
    "disables capture even when spark.tpu.obs.bundles is on; "
    "session.capture_diagnostics() requires it. dev/diagnose.py and "
    "the history server's /bundles pages read it offline.", str))

OBS_BUNDLE_RING = _register(ConfigEntry(
    "spark.tpu.obs.bundle.ring", 16,
    "Retention bound on stored bundles: once more than this many exist "
    "the oldest bundle directories are deleted at capture time (under "
    "the index flock), so disk stays bounded no matter how unhealthy "
    "the fleet gets.", int))

OBS_BUNDLE_SAMPLE_HEALTHY = _register(ConfigEntry(
    "spark.tpu.obs.bundle.sampleHealthy", 0,
    "Deterministic 1-in-N tail-sampling of HEALTHY queries into "
    "bundles (reason 'sampled') for comparison baselines: every Nth "
    "trigger-free query close captures. 0 (default) samples none — "
    "healthy runs write nothing.", int))


class SQLConf:
    """Session-local config with string overrides over typed defaults.

    Thread-safe; `get` accepts either a ConfigEntry or a string key.
    """

    def __init__(self, overrides: dict[str, Any] | None = None):
        self._lock = threading.RLock()
        self._values: dict[str, Any] = dict(overrides or {})

    def set(self, key: str | ConfigEntry, value: Any) -> "SQLConf":
        k = key.key if isinstance(key, ConfigEntry) else key
        with self._lock:
            self._values[k] = value
        return self

    def overrides(self) -> dict:
        """Snapshot of explicit overrides (for shipping to executors)."""
        with self._lock:
            return dict(self._values)

    def unset(self, key: str | ConfigEntry) -> None:
        k = key.key if isinstance(key, ConfigEntry) else key
        with self._lock:
            self._values.pop(k, None)

    def get(self, key: str | ConfigEntry, default: Any = None) -> Any:
        entry = key if isinstance(key, ConfigEntry) else _REGISTRY.get(key)
        k = entry.key if entry else key
        with self._lock:
            if k in self._values:
                raw = self._values[k]
                if entry is not None and isinstance(raw, str):
                    return entry.value_type(raw)
                return raw
        if entry is not None:
            return entry.default
        return default

    def copy(self) -> "SQLConf":
        with self._lock:
            return SQLConf(dict(self._values))

    # convenience typed accessors used on hot paths
    @property
    def shuffle_partitions(self) -> int:
        return int(self.get(SHUFFLE_PARTITIONS))

    @property
    def batch_capacity(self) -> int:
        return int(self.get(BATCH_CAPACITY))

    @property
    def case_sensitive(self) -> bool:
        return bool(self.get(CASE_SENSITIVE))


def registry() -> dict[str, ConfigEntry]:
    return dict(_REGISTRY)
