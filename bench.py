#!/usr/bin/env python
"""Benchmark suite: all five BASELINE.json configs on the TPU.

Prints one JSON line per config — {"metric", "value", "unit",
"vs_baseline", "hbm_gbps"?, "platform", "device_kind", "n_devices"} —
then a final summary line whose value is the geometric mean of
vs_baseline across configs. A measuring run that finds no TPU fails with
a one-line reason; `--smoke` is the explicit forced-CPU functional gate
and reports counts only. A config that raises, times out or is skipped
for budget makes the exit code non-zero (records already emitted stay).

Reference numbers (BASELINE.md; 1× EPYC 7763, JDK 17, "Best Time"):
  #1 groupBy-sum randomized keys ....... 75.5 M rows/s
     (sql/core/benchmarks/AggregateBenchmark-results.txt)
  #2 radix sort long keys .............. 27.5 M rows/s
     (sql/core/benchmarks/SortBenchmark-results.txt:14)
  #3 shuffled hash join ................ 10.1 M rows/s
     (sql/core/benchmarks/JoinBenchmark-results.txt:73)
  #4 TPC-DS q3 / q7 / q19 SF1 .......... 252 / 595 / 361 ms
     (sql/core/benchmarks/TPCDSQueryBenchmark-results.txt:17,41,119)

Steady-state methodology matches the reference harness: data in memory
(device-resident scan cache), one warm-up run (device upload + XLA
compile), best of N timed runs. vs_baseline > 1 means faster than the
reference for every config (for wall-clock configs it is ref_ms/our_ms).
"""

import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Scale knob for --smoke runs: SPARK_TPU_BENCH_SCALE=0.01 shrinks every
# dataset 100×. A measuring run is 1.0 on the chip.
SCALE = float(os.environ.get("SPARK_TPU_BENCH_SCALE", "1.0"))

# --smoke: functional gate, not a perf number. Tiny scales, forced-CPU,
# single timed run; asserts the whole suite executes (rc=0) and emits
# kernel-launch counts so dispatch-count regressions surface in CI
# (tests/test_bench_smoke.py runs this in the tier-1 pass). The
# environment alone decides the platform, so it is set here, before jax
# is imported; the persistent XLA cache is pinned off with jax's own
# switch because the counts dev/perfcheck.py gates assume none (the
# serve legs, which measure that cache, turn it back on for themselves).
SMOKE = "--smoke" in sys.argv
if SMOKE:
    sys.argv = [a for a in sys.argv if a != "--smoke"]
    SCALE = min(SCALE, 0.002)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# --analyze: before timing each config, run the static plan analyzer
# (spark_tpu/analysis/plan_lint.py) on its main query and emit one JSON
# record with the predicted per-kind launch counts — the measured
# kernel_launches delta on the same record trail is its ground truth.
ANALYZE = "--analyze" in sys.argv
if ANALYZE:
    sys.argv = [a for a in sys.argv if a != "--analyze"]

# --trace: run with span tracing + per-operator metrics ON and write a
# Perfetto/Chrome-trace JSON (obs/tracing.py) next to the results —
# SPARK_TPU_TRACE_PATH overrides the destination. dev/run_all.sh's trace
# gate loads and validates the emitted file (dev/validate_trace.py).
TRACE = "--trace" in sys.argv
if TRACE:
    sys.argv = [a for a in sys.argv if a != "--trace"]
TRACE_PATH = os.environ.get("SPARK_TPU_TRACE_PATH", "bench_trace.json")
_TRACE_TRACERS: list = []  # host-only span buffers (never pin sessions)

# --cluster: run every config's session over a local process cluster
# (ClusterDAGScheduler ships map stages to worker processes) so the
# trace gate exercises worker-side metric/span shipping end to end —
# worker spans land in the exported trace as their own tracks and
# dev/validate_trace.py --cluster requires at least one.
CLUSTER = "--cluster" in sys.argv
if CLUSTER:
    sys.argv = [a for a in sys.argv if a != "--cluster"]
_CLUSTER_SESSIONS: list = []  # stopped at exit (kills worker processes)

# --progress: live console stage bars while configs run (obs/live.py
# ConsoleProgressReporter over heartbeat-streamed worker telemetry; a
# fast heartbeat so even short stages repaint). The reporter writes to
# stderr — the JSON record stream on stdout stays machine-clean.
PROGRESS = "--progress" in sys.argv
if PROGRESS:
    sys.argv = [a for a in sys.argv if a != "--progress"]

# --mesh: add the mesh SPMD shuffle-stage config (parallel/mesh_fusion):
# a power-of-two hash repartition whose whole stage — traced pipeline,
# partition ids, ICI all-to-all — is ONE shard_map dispatch per step.
# Reports dispatches_per_stage (mesh_stage launches per warm run) and the
# donated vs undonated send-buffer HBM watermark (DeviceLedger window).
# Needs >=2 jax devices; `python bench.py mesh` also selects it directly.
MESH = "--mesh" in sys.argv
if MESH:
    sys.argv = [a for a in sys.argv if a != "--mesh"]

# --encoded: add the compressed-execution config (columnar/encoding.py):
# a dictionary-heavy filter→repartition(string key)→group-by(string) whose
# encoded path groups directly on dictionary codes, fuses string pids via
# dict-hash luts, and ships codes + dictionaries through the shuffle.
# Reports shuffle bytes moved and hbm_gbps encoded vs decoded
# (spark.tpu.encoding.enabled=false oracle). `python bench.py encoded`
# also selects it directly.
ENCODED = "--encoded" in sys.argv
if ENCODED:
    sys.argv = [a for a in sys.argv if a != "--encoded"]

# --adaptive: add the runtime-adaptive execution config
# (physical/adaptive.py): a selective shuffled hash join measured with
# the runtime join filter off (oracle) and on. The build side's key
# domain is harvested host-side at the stage boundary and pushed into
# the not-yet-run probe shuffle, pruning probe rows before they ship.
# Reports probe rows shuffled + kernel launches per run both ways and
# the on/off speedup. `python bench.py adaptive` also selects it.
ADAPTIVE = "--adaptive" in sys.argv
if ADAPTIVE:
    sys.argv = [a for a in sys.argv if a != "--adaptive"]

# --whole-query: add the whole-query compilation config
# (physical/whole_query.py): a TPC-DS-mini-shaped join+agg plan compiled
# as ONE jitted program per step (spark.tpu.compile.tier=whole) vs the
# per-stage tier. Reports dispatches-per-query both ways and the tier
# speedup. `python bench.py whole_query` also selects it directly.
WHOLE_QUERY = "--whole-query" in sys.argv
if WHOLE_QUERY:
    sys.argv = [a for a in sys.argv if a != "--whole-query"]

# --mesh-whole: add the mesh whole-query compilation config
# (physical/mesh_whole.py): the ENTIRE sharded star-join+agg plan —
# leaves, in-program all-to-alls, join build+probe, partial and final
# aggregate — as ONE shard_map dispatch per execution step
# (spark.tpu.compile.tier=mesh-whole) vs the single-device whole tier
# and the per-stage tier. Reports dispatches-per-query for all three
# tiers, the tier speedups, and the donated vs undonated leaf-plane HBM
# watermark. Needs >=4 jax devices; `python bench.py mesh_whole` also
# selects it directly.
MESH_WHOLE = "--mesh-whole" in sys.argv
if MESH_WHOLE:
    sys.argv = [a for a in sys.argv if a != "--mesh-whole"]

# --serve-restart: measure the persistent-cache restart story
# (spark_tpu/exec/persist_cache.py): run the smoke query set in a child
# process with spark.tpu.cache.dir pointed at a scratch dir (cold leg),
# re-exec a FRESH process against the same cache dir (warm leg), and
# report cold vs warm compile counts (engine compiles, XLA disk
# hits/misses — a warm restart must show zero disk misses) plus
# repeated-query latency (first execution vs the zero-launch result-
# cache hit). `python bench.py serve_restart` also selects it directly.
SERVE_RESTART = "--serve-restart" in sys.argv
if SERVE_RESTART:
    sys.argv = [a for a in sys.argv if a != "--serve-restart"]

# internal: one serve-restart child leg (invoked by bench_serve_restart
# in a subprocess with SPARK_TPU_CACHE_DIR set) — runs the query set
# against the persistent caches and prints one SERVE-LEG json line
SERVE_LEG = "--serve-leg" in sys.argv
if SERVE_LEG:
    sys.argv = [a for a in sys.argv if a != "--serve-leg"]

# --serve: the multi-tenant serving load test (spark_tpu/serve/): 8
# concurrent per-connection sessions replay a mixed dashboard query set
# through 2 fair-scheduler pools (weights 2:1) in a COLD process, then a
# warm-restarted process replays the identical load against the same
# persistent caches. Reports p50/p99 latency per pool, peak queue depth,
# the contended-grant fairness ratio, per-query attributed launches vs
# the global counter delta (must match — scope-exact ledger), overlapped
# profile count (must be 0), and the warm leg's XLA disk misses /
# result-cache zero-launch hits. `python bench.py serve` also selects it.
SERVE = "--serve" in sys.argv
if SERVE:
    sys.argv = [a for a in sys.argv if a != "--serve"]

# internal: one serve-load child leg (invoked by bench_serve in a
# subprocess; SPARK_TPU_CACHE_DIR + SPARK_TPU_SERVE_PROFILES set) —
# prints one SERVE-LOAD json line
SERVE_LOAD_LEG = "--serve-load-leg" in sys.argv
if SERVE_LOAD_LEG:
    sys.argv = [a for a in sys.argv if a != "--serve-load-leg"]

# --profile: record a QueryProfile for every query the suite executes
# (obs/history.py flight recorder) into SPARK_TPU_PROFILE_DIR (default
# ./bench_profiles): fingerprint-keyed JSONL with per-kind launch/compile
# deltas, tier decisions, retry counters, and HBM watermarks.
# dev/perfcheck.py runs `bench.py --smoke --profile` and diffs the
# profiles' deterministic counters against dev/perf_baseline.json — the
# flight recorder's counters ARE the CI perf gate.
PROFILE = "--profile" in sys.argv
if PROFILE:
    sys.argv = [a for a in sys.argv if a != "--profile"]
PROFILE_DIR = os.environ.get("SPARK_TPU_PROFILE_DIR", "bench_profiles")


# per-config predicted peak HBM (plan_lint memory model) captured by
# _maybe_analyze so the timed record can print predicted vs measured
_PREDICTED_PEAKS: dict = {}


def _maybe_analyze(df, name: str):
    """`df` may be a DataFrame or a zero-arg callable producing one (so
    plan construction also stays inside the never-sink-the-bench guard)."""
    if not ANALYZE:
        return
    try:
        if callable(df):
            df = df()
        rep = df.query_execution.analysis_report()
        _PREDICTED_PEAKS[name] = rep.predicted_peak_hbm
        _emit({"metric": f"analysis:{name}", "value": rep.total,
               "unit": "predicted launches/run", "vs_baseline": 1.0,
               "exact": rep.exact,
               "predicted_launches": rep.predicted_launches,
               "predicted_peak_hbm": rep.predicted_peak_hbm,
               "memory_exact": rep.memory_exact,
               "fusion_boundaries": rep.fusion_boundaries[:6],
               "recompile_hazards": rep.recompile_hazards[:6]})
    except Exception as e:  # analysis must never sink a bench run
        _emit({"metric": f"analysis:{name} FAILED", "value": 0,
               "unit": "error", "vs_baseline": 0.0,
               "error": f"{type(e).__name__}: {e}"[:200]})


_CONFIG_TIMEOUT_S = int(os.environ.get("SPARK_TPU_BENCH_TIMEOUT", "1500"))
# Whole-suite deadline: configs that would start past it are skipped (and
# fail the run) so the records and the summary line still come out.
_SUITE_BUDGET_S = int(os.environ.get("SPARK_TPU_BENCH_BUDGET", "5400"))


class _ConfigTimeout(Exception):
    pass


def _with_timeout(fn, seconds: int):
    """Run one config under a SIGALRM deadline so a pathological compile
    can't eat the whole suite run."""
    import signal

    def on_alarm(signum, frame):
        raise _ConfigTimeout(f"config exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        return fn()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _session(extra=None):
    from spark_tpu import TpuSession

    conf = {
        "spark.tpu.batch.capacity": 1 << 24,
        "spark.sql.shuffle.partitions": 1,
        # no per-operator profiling overhead in measured runs
        "spark.tpu.ui.operatorMetrics": "false",
        "spark.tpu.trace.enabled": "false",
    }
    if TRACE:
        # --trace is an observability run: spans + attributed metrics on
        # (collection is launch-free, so dispatch counts stay honest)
        conf["spark.tpu.ui.operatorMetrics"] = "true"
        conf["spark.tpu.trace.enabled"] = "true"
    if CLUSTER:
        # local process cluster; >1 shuffle partition so plans keep real
        # exchanges (= remote map stages shipped to workers)
        conf["spark.tpu.cluster.enabled"] = "true"
        conf["spark.tpu.cluster.workers"] = "2"
        conf["spark.sql.shuffle.partitions"] = 2
    if PROGRESS:
        conf["spark.tpu.progress.console"] = "true"
        conf["spark.tpu.progress.updateInterval"] = "0.2"
        conf["spark.tpu.heartbeat.interval"] = "0.25"
    if PROFILE:
        # flight recorder on: every executed query appends a
        # fingerprint-keyed profile (close-time host work only — the
        # measured dispatch counts stay honest)
        conf["spark.tpu.obs.profileDir"] = PROFILE_DIR
    conf.update(extra or {})
    if SMOKE:
        conf["spark.tpu.batch.capacity"] = min(
            int(conf["spark.tpu.batch.capacity"]), 1 << 18)
    session = TpuSession("bench", conf)
    if TRACE:
        # keep only the tracer (host span buffer): retaining the session
        # would pin every config's device-resident scan caches at once
        _TRACE_TRACERS.append(session.tracer)
    if CLUSTER:
        # cluster sessions ARE retained, then stopped at exit — worker
        # processes must not outlive the bench run
        _CLUSTER_SESSIONS.append(session)
    return session


def _df_from_table(session, table, name):
    """Device-cached single-partition DataFrame over an arrow table.
    --cluster splits the scan so aggregations keep a real exchange in
    the plan (a single-partition partial agg completes locally and never
    ships a map stage to the workers)."""
    from spark_tpu.api.dataframe import DataFrame
    from spark_tpu.expr.expressions import AttributeReference
    from spark_tpu.io.sources import InMemorySource
    from spark_tpu.plan.logical import LogicalRelation
    from spark_tpu.types import from_arrow_type

    source = InMemorySource(table, num_partitions=2 if CLUSTER else 1)
    source.cache_device_batches = True
    attrs = [AttributeReference(f.name, from_arrow_type(f.type), True)
             for f in table.schema]
    return DataFrame(session, LogicalRelation(source, attrs, name))


def _run_blocked(df) -> float:
    """Execute a DataFrame and block until all device output is ready.

    Blocks via block_until_ready AND an 8-byte host read of each output
    buffer: a host read cannot complete before the producing computation
    has."""
    t0 = time.perf_counter()
    parts = df.query_execution.execute()
    for part in parts:
        for batch in part:
            for c in batch.columns:
                c.data.block_until_ready()
                np.asarray(c.data[:1])
    return time.perf_counter() - t0


# resource evidence of the best timed run: XLA "bytes accessed" of every
# kernel dispatched in it (per-launch captured cost × launches — see
# physical/compile._capture_kernel_cost) and the device ledger's HBM
# watermark across the measured window
_LAST_RUN = {"bytes": 0.0, "hbm_peak": 0}


def _best_of(fn, n=5):
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    fn()  # warm-up: upload + compile
    if SMOKE:
        n = 1
    GLOBAL_LEDGER.begin_window()
    best, best_bytes = None, 0.0
    for _ in range(n):
        b0 = KC.bytes_total
        t = fn()
        if best is None or t < best:
            best, best_bytes = t, KC.bytes_total - b0
    _LAST_RUN["bytes"] = best_bytes
    _LAST_RUN["hbm_peak"] = GLOBAL_LEDGER.window_peak()
    return best


def _hbm_fields(name: str, best: float, est_bytes: float) -> dict:
    """Per-config HBM evidence: `hbm_gbps` is MEASURED — the best run's
    captured kernel bytes over its wall time — with the historical
    row-count estimate only as a tagged fallback when cost capture found
    nothing (kernelCost off / lowering unavailable). Under --analyze the
    record also carries the plan analyzer's predicted peak HBM next to
    the ledger's measured watermark."""
    by = _LAST_RUN["bytes"]
    # under --cluster the map stages run in worker processes whose
    # KernelCache/ledger are per-process — the driver-side capture only
    # covers its own dispatches, so the tag says so instead of claiming
    # a full measurement
    src = ("measured-driver" if CLUSTER else "measured") if by \
        else "estimated"
    out = {"hbm_gbps": round((by or est_bytes) / best / 1e9, 1),
           "hbm_gbps_source": src}
    if ANALYZE:
        out["hbm_peak_predicted"] = _PREDICTED_PEAKS.get(name)
        out["hbm_peak_measured"] = _LAST_RUN["hbm_peak"]
    return out


def _kernel_counters():
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE

    return GLOBAL_KERNEL_CACHE.counters()


def _attach_kernel_delta(rec, before):
    """Per-config kernel dispatch/compile evidence: a fusion regression
    shows up as a launch-count jump before it shows up as wall-clock."""
    after = _kernel_counters()
    rec["kernel_launches"] = after["kernel_cache.launches"] \
        - before["kernel_cache.launches"]
    rec["kernel_compiles"] = after["kernel_cache.misses"] \
        - before["kernel_cache.misses"]
    return rec


# --------------------------------------------------------------------------
# #1 groupBy-sum
# --------------------------------------------------------------------------

def bench_groupby():
    import pyarrow as pa

    import spark_tpu.api.functions as F

    n_rows = int(10_000_000 * SCALE)
    n_keys = 1 << 20
    baseline = 75.5e6

    session = _session()
    rng = np.random.default_rng(42)
    table = pa.table({
        "k": rng.integers(0, n_keys, n_rows).astype(np.int64),
        "v": rng.integers(0, 1000, n_rows).astype(np.int64),
    })
    df = _df_from_table(session, table, "agg_bench")
    q = df.groupBy("k").agg(F.sum("v").alias("s"))
    _maybe_analyze(q, "groupby")
    best = _best_of(lambda: _run_blocked(q))
    rate = n_rows / best
    return {
        "metric": "groupBy-sum 1e7 rows (randomized int keys, 1M groups)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(rate / baseline, 3),
        **_hbm_fields("groupby", best, n_rows * 16),
    }


# --------------------------------------------------------------------------
# #2 global sort
# --------------------------------------------------------------------------

def bench_sort():
    import pyarrow as pa

    n_rows = int(100_000_000 * SCALE)
    baseline = 27.5e6  # reference radix sort, long keys

    session = _session({"spark.tpu.batch.capacity": 1 << 27})
    rng = np.random.default_rng(7)
    table = pa.table({"k": rng.integers(np.iinfo(np.int64).min,
                                        np.iinfo(np.int64).max,
                                        n_rows, dtype=np.int64)})
    df = _df_from_table(session, table, "sort_bench")
    q = df.orderBy("k")
    _maybe_analyze(q, "sort")
    best = _best_of(lambda: _run_blocked(q))
    rate = n_rows / best
    return {
        "metric": "global sort 1e8 random int64",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(rate / baseline, 3),
        **_hbm_fields("sort", best, n_rows * 8),
    }


# --------------------------------------------------------------------------
# #3 shuffled join (store_sales ⋈ date_dim shape)
# --------------------------------------------------------------------------

def bench_join():
    import pyarrow as pa

    import spark_tpu.api.functions as F

    n_fact = int(20_000_000 * SCALE)
    baseline = 10.1e6  # reference shuffled hash join, codegen on

    # 4M-row probe tiles: one moderate-size jitted join program reused
    # across tiles beats one giant 2^25 compile
    session = _session({"spark.tpu.batch.capacity": 1 << 22})
    rng = np.random.default_rng(3)
    # date_dim shape: 73049 consecutive date surrogate keys over 1998-2002
    d_date_sk = np.arange(2_450_816, 2_450_816 + 73_049, dtype=np.int64)
    d_year = 1998 + ((d_date_sk - 2_450_816) // 365).astype(np.int64)
    dim = pa.table({"d_date_sk": d_date_sk, "d_year": d_year})
    fact = pa.table({
        "ss_sold_date_sk": rng.integers(
            2_450_816, 2_450_816 + 73_049, n_fact).astype(np.int64),
        "ss_ext_sales_price": rng.random(n_fact),
    })
    f = _df_from_table(session, fact, "fact")
    d = _df_from_table(session, dim, "dim")
    q = (f.join(d, f["ss_sold_date_sk"] == d["d_date_sk"])
          .groupBy("d_year")
          .agg(F.sum("ss_ext_sales_price").alias("rev")))
    _maybe_analyze(q, "join")
    best = _best_of(lambda: _run_blocked(q))
    rate = n_fact / best
    return {
        "metric": "join store_sales-shape ⋈ date_dim (2e7 ⋈ 73k) + agg",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(rate / baseline, 3),
        **_hbm_fields("join", best, n_fact * 16),
    }


# --------------------------------------------------------------------------
# #3b shuffle-heavy map stage: exchange map-side fusion on/off
# --------------------------------------------------------------------------

_MAP_SIDE_KINDS = ("fused_shuffle", "pipeline", "shuffle_pids",
                   "shuffle_hash", "shuffle_rr", "shuffle_range")


def bench_shuffle():
    """Filter→project→hash-repartition→agg: the map side is the product
    under test. With spark.tpu.fusion.exchange on (default) the stage
    runs ONE fused dispatch per map batch; off pays pipeline + partition
    kernels plus an intermediate batch. Reports map-side kernel launches
    per batch both ways; vs_baseline is the speedup over our own unfused
    oracle. Partition count 5 (non-power-of-two) keeps the exchange on
    the host shuffle path rather than a mesh all-to-all."""
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE

    n_rows = int(20_000_000 * SCALE)
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        # the bench measures the fused path at every scale
                        "spark.tpu.fusion.minRows": "0"})
    cap = int(session.conf.get("spark.tpu.batch.capacity"))
    n_batches = max(1, -(-n_rows // cap))
    rng = np.random.default_rng(23)
    table = pa.table({
        "k": rng.integers(0, 1 << 16, n_rows).astype(np.int64),
        "v": rng.integers(0, 1000, n_rows).astype(np.int64),
    })
    df = _df_from_table(session, table, "shuffle_bench")

    def q():
        # repartition terminal: every launch in the query IS map-side
        # work (a downstream agg would add its own pipeline launches and
        # muddy the per-batch metric)
        return (df.filter(F.col("v") > 25)
                .withColumn("v2", F.col("v") * 3)
                .repartition(5, "k"))

    _maybe_analyze(q, "shuffle")
    results = {}
    hbm = {}
    for mode, flag in (("fused", "true"), ("unfused", "false")):
        session.conf.set("spark.tpu.fusion.exchange", flag)
        best = _best_of(lambda: _run_blocked(q()))
        if mode == "fused":
            hbm = _hbm_fields("shuffle", best, n_rows * 16)
        before = dict(GLOBAL_KERNEL_CACHE.launches_by_kind)
        _run_blocked(q())
        after = GLOBAL_KERNEL_CACHE.launches_by_kind
        map_launches = sum(after.get(k, 0) - before.get(k, 0)
                           for k in _MAP_SIDE_KINDS)
        results[mode] = (best, map_launches)
    session.conf.unset("spark.tpu.fusion.exchange")
    best_fused, map_fused = results["fused"]
    best_unfused, map_unfused = results["unfused"]
    rate = n_rows / best_fused
    return {
        "metric": "shuffle map stage filter+project+repartition(5,k) 2e7 "
                  "rows (exchange map-side fusion; vs_baseline = speedup "
                  "over the unfused oracle)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(best_unfused / best_fused, 3),
        **hbm,
        "map_launches_per_batch_fused": round(map_fused / n_batches, 2),
        "map_launches_per_batch_unfused": round(map_unfused / n_batches, 2),
    }


# --------------------------------------------------------------------------
# #3b2 runtime-adaptive join filter: build-side domain pushed into the
# not-yet-run probe shuffle (physical/adaptive.install_runtime_filters)
# --------------------------------------------------------------------------

def bench_adaptive():
    """Selective shuffled hash join (2e7-row probe ⋈ 300-key contiguous
    dim) run twice: spark.tpu.adaptive.runtimeFilter off (oracle) and on.
    With the filter on, the materialized build side's dense key range is
    harvested host-side at the stage boundary and pushed into the probe
    shuffle, which prunes ~98.5% of probe rows BEFORE they are shuffled.
    Reports probe rows shuffled and kernel launches per run both ways;
    vs_baseline is the speedup over our own filter-off oracle. Partition
    count 5 (non-power-of-two) keeps the exchanges on the host shuffle
    path so byte/row accounting is exact."""
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE

    n_fact = int(20_000_000 * SCALE)
    n_keys = 100_000
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        "spark.sql.shuffle.partitions": 5,
                        "spark.sql.autoBroadcastJoinThreshold": -1})
    rng = np.random.default_rng(41)
    fact = pa.table({
        "k": rng.integers(0, n_keys, n_fact).astype(np.int64),
        "v": rng.integers(0, 1000, n_fact).astype(np.int64),
    })
    dim = pa.table({"k": np.arange(40_000, 40_300, dtype=np.int64),
                    "w": np.arange(300, dtype=np.int64)})
    # multi-partition inputs keep real hash exchanges in the join plan
    # (single-partition sources co-locate and the probe never shuffles)
    f = _df_from_table(session, fact, "rf_fact").repartition(5)
    d = _df_from_table(session, dim, "rf_dim").repartition(2)

    def q():
        return (f.join(d, on="k").groupBy("k")
                .agg(F.sum("v").alias("sv")))

    _maybe_analyze(q, "adaptive")
    results, hbm = {}, {}
    for mode, flag in (("on", "true"), ("off", "false")):
        session.conf.set("spark.tpu.adaptive.runtimeFilter", flag)
        best = _best_of(lambda: _run_blocked(q()))
        if mode == "on":
            hbm = _hbm_fields("adaptive", best, n_fact * 16)
        c0 = session._metrics.snapshot()["counters"]
        l0 = GLOBAL_KERNEL_CACHE.counters()["kernel_cache.launches"]
        _run_blocked(q())
        c1 = session._metrics.snapshot()["counters"]
        launches = GLOBAL_KERNEL_CACHE.counters()["kernel_cache.launches"] \
            - l0
        pruned = c1.get("adaptive.filter_rows_pruned", 0) \
            - c0.get("adaptive.filter_rows_pruned", 0)
        installed = c1.get("adaptive.runtime_filters_installed", 0) \
            - c0.get("adaptive.runtime_filters_installed", 0)
        results[mode] = (best, launches, pruned, installed)
    session.conf.unset("spark.tpu.adaptive.runtimeFilter")
    best_on, launches_on, pruned_on, installed_on = results["on"]
    best_off, launches_off, pruned_off, _ = results["off"]
    rate = n_fact / best_on
    return {
        "metric": "adaptive runtime join filter 2e7 probe ⋈ 300-key dim "
                  "+ agg (vs_baseline = speedup over the filter-off "
                  "oracle)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(best_off / best_on, 3),
        **hbm,
        "filters_installed": installed_on,
        "probe_rows_shuffled_off": n_fact,
        "probe_rows_shuffled_on": n_fact - pruned_on,
        "probe_rows_pruned": pruned_on,
        "launches_per_run_on": launches_on,
        "launches_per_run_off": launches_off,
    }


# --------------------------------------------------------------------------
# #3c mesh SPMD shuffle stage: one sharded dispatch per stage per step
# --------------------------------------------------------------------------

def bench_mesh():
    """Filter→project→hash-repartition over the device mesh: the whole
    map stage (traced pipeline + partition ids + all-to-all) is ONE
    shard_map dispatch per step with donated send buffers. vs_baseline is
    the speedup over our own legacy composition (spark.tpu.fusion.mesh=
    false: per-batch pipeline materialization before the collective);
    the record also carries dispatches_per_stage measured from the
    KernelCache and the donated vs undonated staged-buffer HBM peaks
    from the DeviceLedger window watermark."""
    import gc

    import jax
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.parallel import mesh_fusion as MF
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE

    ndev = len(jax.devices())
    if ndev < 2:
        return {"metric": "mesh shuffle stage SKIPPED (needs >=2 devices)",
                "value": 0, "unit": "status", "vs_baseline": 1.0}
    num_out = 8 if ndev >= 8 else (4 if ndev >= 4 else 2)
    n_rows = int(20_000_000 * SCALE)
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        "spark.tpu.fusion.minRows": "0"})
    rng = np.random.default_rng(29)
    table = pa.table({
        "k": rng.integers(0, 1 << 16, n_rows).astype(np.int64),
        "v": rng.integers(0, 1000, n_rows).astype(np.int64),
    })
    df = _df_from_table(session, table, "mesh_bench")

    def q():
        return (df.filter(F.col("v") > 25)
                .withColumn("v2", F.col("v") * 3)
                .repartition(num_out, "k"))

    _maybe_analyze(q, "mesh")
    results = {}
    for mode, flag in (("fused", "true"), ("legacy", "false")):
        session.conf.set("spark.tpu.fusion.mesh", flag)
        best = _best_of(lambda: _run_blocked(q()))
        before = dict(GLOBAL_KERNEL_CACHE.launches_by_kind)
        _run_blocked(q())
        after = GLOBAL_KERNEL_CACHE.launches_by_kind
        dispatches = after.get("mesh_stage", 0) - before.get("mesh_stage", 0)
        results[mode] = (best, dispatches)
    session.conf.unset("spark.tpu.fusion.mesh")

    def hbm_window():
        gc.collect()
        GLOBAL_LEDGER.begin_window()
        _run_blocked(q())
        return GLOBAL_LEDGER.window_peak()

    donate_was = MF.DONATE_DEFAULT
    try:
        MF.DONATE_DEFAULT = False
        _run_blocked(q())  # compile the undonated oracle program
        peak_undonated = hbm_window()
        MF.DONATE_DEFAULT = True
        peak_donated = hbm_window()
    finally:
        MF.DONATE_DEFAULT = donate_was

    best_fused, disp_fused = results["fused"]
    best_legacy, _disp_legacy = results["legacy"]
    rate = n_rows / best_fused
    return {
        "metric": f"mesh SPMD shuffle stage filter+project+repartition"
                  f"({num_out},k) {n_rows:.0e} rows over {num_out} devices "
                  "(one sharded dispatch per stage per step; vs_baseline "
                  "= speedup over the materialize-then-collective legacy "
                  "path)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(best_legacy / best_fused, 3),
        **_hbm_fields("mesh", best_fused, n_rows * 16),
        "dispatches_per_stage": disp_fused,
        "hbm_peak_donated": peak_donated,
        "hbm_peak_undonated": peak_undonated,
        "donated_hbm_saving": peak_undonated - peak_donated,
    }


# --------------------------------------------------------------------------
# #3d compressed execution: dictionary/RLE-native kernels + code shuffle
# --------------------------------------------------------------------------

def bench_encoded():
    """Dictionary-heavy filter→hash-repartition(string key)→group-by
    (string key)→sum: the compressed-execution scoreboard. Encoded
    (spark.tpu.encoding.enabled, default on): the aggregate groups
    directly on dictionary codes (dense-on-codes, no sort, no range
    probe), the fused map dispatch computes string pids from the padded
    dict-hash lut inside the stage kernel, and the shuffle ships int32
    codes + shared dictionary references. Decoded oracle (off): hashed
    eq-key staging, sorted-segment grouping. vs_baseline is the speedup
    over the oracle; the record carries shuffle bytes moved and hbm_gbps
    both ways. Partition count 5 keeps the exchange on the host path."""
    import pyarrow as pa

    import spark_tpu.api.functions as F  # noqa: F401

    n_rows = int(20_000_000 * SCALE)
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        "spark.tpu.fusion.minRows": "0"})
    rng = np.random.default_rng(31)
    # long repeated strings: the decoded wire format pays them per row
    cats = [f"category-{i:04d}-with-a-long-repeated-name" for i in
            range(4096)]
    codes = rng.integers(0, len(cats), n_rows)
    table = pa.table({
        "s": pa.DictionaryArray.from_arrays(
            pa.array(codes, type=pa.int32()), pa.array(cats)),
        "v": rng.integers(0, 1000, n_rows).astype(np.int64),
    })
    df = _df_from_table(session, table, "encoded_bench")

    def q():
        return (df.filter(F.col("v") > 25)
                .repartition(5, "s")
                .groupBy("s").agg(F.sum("v").alias("sv")))

    _maybe_analyze(q, "encoded")
    results = {}
    for mode, flag in (("encoded", "true"), ("decoded", "false")):
        session.conf.set("spark.tpu.encoding.enabled", flag)
        best = _best_of(lambda: _run_blocked(q()))
        results[mode] = (best,
                         _hbm_fields(f"encoded[{mode}]", best, n_rows * 12))
    session.conf.unset("spark.tpu.encoding.enabled")

    # wire bytes: the CLUSTER block format is where codes + one dict per
    # map task beat decoded row values (the local path shares host
    # buffers either way) — a 2-worker process cluster at bounded scale
    # measures the pickled block sizes (MapStatus bytes) both ways
    wire = {}
    wn = min(n_rows, 500_000)
    wtable = table.slice(0, wn)
    for mode, flag in (("encoded", "true"), ("decoded", "false")):
        from spark_tpu.api.session import TpuSession
        from spark_tpu.exec.cluster import LocalCluster

        s2 = TpuSession(f"bench-encoded-wire-{mode}", {
            "spark.sql.shuffle.partitions": "3",
            "spark.tpu.batch.capacity": 1 << 18,
            "spark.sql.adaptive.enabled": "false",
            "spark.tpu.fusion.minRows": "0",
            "spark.tpu.encoding.enabled": flag,
        })
        s2.attachSqlCluster(LocalCluster(num_workers=2))
        try:
            wdf = s2.createDataFrame(wtable)
            (wdf.filter(F.col("v") > 25).repartition(3, "s")
             .groupBy("s").agg(F.sum("v").alias("sv")).toArrow())
            wire[mode] = s2._metrics.snapshot()["counters"].get(
                "shuffle.bytes_written", 0)
        finally:
            s2.stop()

    best_enc, hbm_enc = results["encoded"]
    best_dec, hbm_dec = results["decoded"]
    rate = n_rows / best_enc
    return {
        "metric": "compressed execution filter+repartition(5,s)+groupBy(s) "
                  f"{n_rows:.0e} rows, 4096-entry dictionary (dense-on-"
                  "codes agg + fused dict-hash pids + code-shipping "
                  "shuffle; vs_baseline = speedup over the decoded oracle)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(best_dec / best_enc, 3),
        **{k: v for k, v in hbm_enc.items()},
        "hbm_gbps_decoded": hbm_dec.get("hbm_gbps"),
        "shuffle_wire_bytes_encoded": int(wire["encoded"]),
        "shuffle_wire_bytes_decoded": int(wire["decoded"]),
        "shuffle_wire_bytes_ratio": round(
            wire["encoded"] / wire["decoded"], 3)
        if wire["decoded"] else None,
    }


def bench_whole_query():
    """Whole-query compilation scoreboard: a q3-shaped star join
    (fact scan -> filter -> two broadcast dim joins -> group-by sum)
    executed under the whole tier (ONE jitted program per step, exchanges
    lowered to in-program gathers, zero host shuffle round-trips) vs the
    per-stage tier (PR 1/5 fusion). vs_baseline is the tier speedup;
    the record carries measured dispatches-per-query for both tiers."""
    import pyarrow as pa

    import spark_tpu.api.functions as F  # noqa: F401
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    n_rows = int(10_000_000 * SCALE)
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        "spark.tpu.fusion.minRows": "0"})
    rng = np.random.default_rng(23)
    n_dim = 2048
    fact = pa.table({
        "date_sk": rng.integers(0, n_dim, n_rows).astype(np.int64),
        "item_sk": rng.integers(0, n_dim, n_rows).astype(np.int64),
        "price": rng.integers(0, 10_000, n_rows).astype(np.int64),
    })
    dates = pa.table({
        "d_date_sk": np.arange(n_dim, dtype=np.int64),
        "d_year": (1998 + (np.arange(n_dim) // 366)).astype(np.int64),
        "d_moy": (1 + np.arange(n_dim) % 12).astype(np.int64),
    })
    items = pa.table({
        "i_item_sk": np.arange(n_dim, dtype=np.int64),
        "i_brand_id": (np.arange(n_dim) % 37).astype(np.int64),
        "i_manufact_id": (np.arange(n_dim) % 100).astype(np.int64),
    })
    fdf = _df_from_table(session, fact, "wq_fact")
    ddf = _df_from_table(session, dates, "wq_dates")
    idf = _df_from_table(session, items, "wq_items")
    fdf.createOrReplaceTempView("wq_fact")
    ddf.createOrReplaceTempView("wq_dates")
    idf.createOrReplaceTempView("wq_items")
    sql = ("select d_year, i_brand_id, sum(price) s from wq_fact "
           "join wq_dates on date_sk = d_date_sk "
           "join wq_items on item_sk = i_item_sk "
           "where d_moy = 11 and i_manufact_id = 28 "
           "group by d_year, i_brand_id")

    def q():
        return session.sql(sql)

    session.conf.set("spark.tpu.compile.tier", "whole")
    _maybe_analyze(q, "whole_query")  # the whole-tier launch model
    results = {}
    dispatches = {}
    for tier in ("whole", "stage"):
        session.conf.set("spark.tpu.compile.tier", tier)
        q().toArrow()  # warm: compile the tier's programs
        before = KC.launches
        q().toArrow()
        dispatches[tier] = KC.launches - before
        best = _best_of(lambda: _run_blocked(q()))
        results[tier] = (best, _hbm_fields(f"whole_query[{tier}]", best,
                                           n_rows * 24))
    session.conf.unset("spark.tpu.compile.tier")
    best_w, hbm_w = results["whole"]
    best_s, _hbm_s = results["stage"]
    rate = n_rows / best_w
    return {
        "metric": "whole-query compilation: q3-shaped star join+agg "
                  f"{n_rows:.0e} fact rows as ONE jitted dispatch per "
                  "step (spark.tpu.compile.tier=whole; vs_baseline = "
                  "speedup over the per-stage tier)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(best_s / best_w, 3),
        **{k: v for k, v in hbm_w.items()},
        "dispatches_per_query_whole": int(dispatches["whole"]),
        "dispatches_per_query_stage": int(dispatches["stage"]),
        "wall_ms_whole": round(best_w * 1e3, 1),
        "wall_ms_stage": round(best_s * 1e3, 1),
    }


def bench_mesh_whole():
    """Mesh whole-query compilation scoreboard: the q3-shaped star join
    (fact scan -> filter -> two dim joins -> hash repartition -> group-by
    sum) executed as ONE shard_map program over the device mesh per step
    (spark.tpu.compile.tier=mesh-whole: leaves staged sharded, exchanges
    lowered to in-program all-to-alls, join and aggregate folded in
    behind the collectives) vs the single-device whole tier and the
    per-stage tier. vs_baseline is the speedup over the stage tier; the
    record carries measured dispatches-per-query for all three tiers and
    the donated vs undonated leaf-plane HBM watermark."""
    import gc

    import jax
    import pyarrow as pa

    import spark_tpu.api.functions as F
    from spark_tpu.obs.resources import GLOBAL_LEDGER
    from spark_tpu.parallel import mesh_fusion as MF
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    ndev = len(jax.devices())
    if ndev < 4:
        return {"metric": "mesh whole-query SKIPPED (needs >=4 devices)",
                "value": 0, "unit": "status", "vs_baseline": 1.0}
    P = 8 if ndev >= 8 else 4
    n_rows = int(10_000_000 * SCALE)
    session = _session({"spark.tpu.batch.capacity": 1 << 22,
                        "spark.tpu.fusion.minRows": "0",
                        "spark.sql.shuffle.partitions": P})
    rng = np.random.default_rng(23)
    n_dim = 2048
    fact = pa.table({
        "date_sk": rng.integers(0, n_dim, n_rows).astype(np.int64),
        "item_sk": rng.integers(0, n_dim, n_rows).astype(np.int64),
        "price": rng.integers(0, 10_000, n_rows).astype(np.int64),
    })
    dates = pa.table({
        "d_date_sk": np.arange(n_dim, dtype=np.int64),
        "d_year": (1998 + (np.arange(n_dim) // 366)).astype(np.int64),
        "d_moy": (1 + np.arange(n_dim) % 12).astype(np.int64),
    })
    items = pa.table({
        "i_item_sk": np.arange(n_dim, dtype=np.int64),
        "i_brand_id": (np.arange(n_dim) % 37).astype(np.int64),
        "i_manufact_id": (np.arange(n_dim) % 100).astype(np.int64),
    })
    _df_from_table(session, fact, "mwq_fact") \
        .createOrReplaceTempView("mwq_fact")
    _df_from_table(session, dates, "mwq_dates") \
        .createOrReplaceTempView("mwq_dates")
    _df_from_table(session, items, "mwq_items") \
        .createOrReplaceTempView("mwq_items")
    sql = ("select d_year, i_brand_id, price from mwq_fact "
           "join mwq_dates on date_sk = d_date_sk "
           "join mwq_items on item_sk = i_item_sk "
           "where d_moy = 11 and i_manufact_id = 28")

    def q():
        return (session.sql(sql).repartition(P, "i_brand_id")
                .groupBy("d_year", "i_brand_id")
                .agg(F.sum("price").alias("s")))

    session.conf.set("spark.tpu.compile.tier", "mesh-whole")
    _maybe_analyze(q, "mesh_whole")  # the mesh launch + retry model
    results = {}
    dispatches = {}
    for tier in ("mesh-whole", "whole", "stage"):
        session.conf.set("spark.tpu.compile.tier", tier)
        q().toArrow()  # warm: compile the tier's programs
        before = KC.launches
        q().toArrow()
        dispatches[tier] = KC.launches - before
        results[tier] = _best_of(lambda: _run_blocked(q()))

    session.conf.set("spark.tpu.compile.tier", "mesh-whole")

    def hbm_window():
        gc.collect()
        GLOBAL_LEDGER.begin_window()
        _run_blocked(q())
        return GLOBAL_LEDGER.window_peak()

    donate_was = MF.DONATE_DEFAULT
    try:
        MF.DONATE_DEFAULT = False
        _run_blocked(q())  # compile the undonated oracle program
        peak_undonated = hbm_window()
        MF.DONATE_DEFAULT = True
        _run_blocked(q())
        peak_donated = hbm_window()
    finally:
        MF.DONATE_DEFAULT = donate_was
    session.conf.unset("spark.tpu.compile.tier")

    best_m = results["mesh-whole"]
    rate = n_rows / best_m
    return {
        "metric": "mesh whole-query compilation: q3-shaped star join+agg "
                  f"{n_rows:.0e} fact rows as ONE shard_map dispatch per "
                  f"step over {P} devices (spark.tpu.compile.tier="
                  "mesh-whole; vs_baseline = speedup over the per-stage "
                  "tier)",
        "value": round(rate / 1e6, 2),
        "unit": "M rows/s",
        "vs_baseline": round(results["stage"] / best_m, 3),
        **_hbm_fields("mesh_whole", best_m, n_rows * 24),
        "dispatches_per_query_mesh_whole": int(dispatches["mesh-whole"]),
        "dispatches_per_query_whole": int(dispatches["whole"]),
        "dispatches_per_query_stage": int(dispatches["stage"]),
        "speedup_vs_whole": round(results["whole"] / best_m, 3),
        "hbm_peak_donated": peak_donated,
        "hbm_peak_undonated": peak_undonated,
        "donated_hbm_saving": peak_undonated - peak_donated,
        "wall_ms_mesh_whole": round(best_m * 1e3, 1),
        "wall_ms_whole": round(results["whole"] * 1e3, 1),
        "wall_ms_stage": round(results["stage"] * 1e3, 1),
    }


# --------------------------------------------------------------------------
# #4/#5 TPC-DS q3 / q7 / q19 wall-clock at SF1-equivalent volume
# --------------------------------------------------------------------------

TPCDS_REF_MS = {"q3": 252.0, "q7": 595.0, "q19": 361.0}
# tests/tpcds/datagen.py scale=1.0 ≈ 30k store_sales rows; real SF1 is
# 2 880 404 rows (reference GenTPCDSData) → scale 96 ≈ SF1 fact volume.
TPCDS_GEN_SCALE = 96.0


def _gen_tpcds_subset(scale):
    """Generate only the tables q3/q7/q19 touch (dims + store_sales).
    Cached as parquet under /tmp — datagen at SF1 volume is ~2 min of
    host work and deterministic (seed 17), so regeneration is waste."""
    import pyarrow.parquet as pq

    cache = f"/tmp/sparktpu_bench_tpcds_{scale:g}"
    names = ["date_dim", "time_dim", "item", "customer_address",
             "customer_demographics", "household_demographics",
             "income_band", "customer", "store", "warehouse", "ship_mode",
             "reason", "call_center", "catalog_page", "web_site",
             "web_page", "promotion", "store_sales"]
    if os.path.isdir(cache):
        try:
            return {n: pq.read_table(os.path.join(cache, f"{n}.parquet"))
                    for n in names}
        except Exception:
            pass
    _here = os.path.dirname(os.path.abspath(__file__))
    if _here not in sys.path:
        sys.path.insert(0, _here)
    from tests.tpcds.datagen import _Gen

    g = _Gen(scale, 17)
    g.date_dim()
    g.time_dim()
    g.item()
    g.customer_address()
    g.customer_demographics()
    g.household_demographics()
    g.income_band()
    g.customer()
    g.store()
    g.warehouse()
    g.ship_mode()
    g.reason()
    g.call_center()
    g.catalog_page()
    g.web_site()
    g.web_page()
    g.promotion()
    g.store_sales()
    try:
        os.makedirs(cache, exist_ok=True)
        for n in names:
            pq.write_table(g.tables[n], os.path.join(cache, f"{n}.parquet"))
    except Exception:
        pass
    return g.tables


def bench_tpcds():
    here = os.path.dirname(os.path.abspath(__file__))
    qdir = os.path.join(here, "tests", "tpcds", "queries")
    tables = _gen_tpcds_subset(TPCDS_GEN_SCALE * SCALE)
    n_ss = tables["store_sales"].num_rows

    session = _session({"spark.tpu.batch.capacity": 1 << 22})
    for name, tab in tables.items():
        session.createDataFrame(tab).createOrReplaceTempView(name)

    from tests.tpcds.oracle import strip_trailing_limit

    out = []
    for qname, ref_ms in TPCDS_REF_MS.items():
        sql = strip_trailing_limit(
            open(os.path.join(qdir, f"{qname}.sql")).read())
        _maybe_analyze(lambda: session.sql(sql), f"tpcds-{qname}")

        def run():
            t0 = time.perf_counter()
            session.sql(sql).toArrow()
            return time.perf_counter() - t0

        best = _best_of(run, n=5)
        out.append({
            "metric": f"TPC-DS {qname} wall-clock "
                      f"(SF1-equivalent, {n_ss} fact rows)",
            "value": round(best * 1e3, 1),
            "unit": "ms",
            "vs_baseline": round(ref_ms / (best * 1e3), 3),
        })
    return out


# --------------------------------------------------------------------------
# serve-restart: persistent-cache warm restarts (exec/persist_cache.py)
# --------------------------------------------------------------------------

def _serve_leg() -> int:
    """One serve-restart child leg: run the query set against the
    persistent caches rooted at SPARK_TPU_CACHE_DIR and print one
    SERVE-LEG json line. Phase 1 runs with the result cache DISABLED so
    queries actually execute (that is what proves the XLA disk cache:
    engine compiles happen, backend compiles hit disk on the warm leg);
    phase 2 enables the result cache and measures the repeated-query
    path (zero-launch Arrow-payload answer)."""
    import pyarrow as pa

    import spark_tpu.api.functions as F
    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    cache_dir = os.environ["SPARK_TPU_CACHE_DIR"]
    device = _require_platform()
    session = _session({
        "spark.tpu.cache.dir": cache_dir,
        "spark.tpu.cache.result.enabled": "false",
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 14,
        "spark.tpu.fusion.minRows": "0",
    })
    rng = np.random.default_rng(11)
    n = max(4000, int(100_000 * SCALE))
    table = pa.table({"k": rng.integers(0, 64, n).astype(np.int64),
                      "v": rng.integers(0, 1000, n).astype(np.int64)})
    df = _df_from_table(session, table, "serve_t")
    queries = {
        "groupby": lambda: df.groupBy("k").agg(F.sum("v").alias("s")),
        "filter_sort": lambda: df.where(F.col("v") > 500).orderBy("k"),
    }
    exec_ms = {}
    for name, q in queries.items():
        t0 = time.perf_counter()
        q().toArrow()
        exec_ms[name] = round((time.perf_counter() - t0) * 1000, 2)
    # phase 2: repeated identical query through the result cache (the
    # cold leg populates the entry; the warm leg's first lookup already
    # hits it CROSS-PROCESS)
    session.conf.set("spark.tpu.cache.result.enabled", "true")
    queries["groupby"]().toArrow()
    l0 = KC.launches
    t0 = time.perf_counter()
    queries["groupby"]().toArrow()
    repeat_ms = round((time.perf_counter() - t0) * 1000, 2)
    counters = session._metrics.snapshot()["counters"]
    print("SERVE-LEG " + json.dumps({
        "device": device,
        "compiles": KC.misses,
        "disk_hit_compiles": KC.disk_hit_compiles,
        "disk": pc.disk_counters(),
        "exec_ms": exec_ms,
        "repeat_ms": repeat_ms,
        "repeat_launches": KC.launches - l0,
        "result_cache_hits": int(counters.get("result_cache.hit", 0)),
    }), flush=True)
    return 0


# set by main() the moment this process initialises a jax backend: from
# then on it holds the chip, and a child that needs the chip fails or hangs
_BACKEND_TOUCHED = False

# configs whose legs are child processes: main() runs them first
_CHILD_LEG_CONFIGS = ("serve_restart", "serve")


def _run_legs(config: str, leg_flag: str, tag: str) -> list:
    """Two SEQUENTIAL fresh processes (cold, warm) sharing one cache
    directory: a fixed path under the checkout, emptied first so the cold
    leg is cold. Each leg owns the device for its lifetime, so the parent
    must not have initialised a backend yet."""
    import shutil
    import subprocess

    if _BACKEND_TOUCHED:
        raise RuntimeError(
            f"{config}: this process has already initialised a jax "
            "backend and holds the device; the legs are child processes "
            "that need it — run this config first or on its own")
    cache_dir = os.path.join(HERE, ".cache", f"bench_{config}")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    env = dict(os.environ)
    env["SPARK_TPU_CACHE_DIR"] = cache_dir
    env["SPARK_TPU_BENCH_SCALE"] = str(SCALE)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"  # --smoke pins it off
    legs = []
    for leg in ("cold", "warm"):
        env["SPARK_TPU_SERVE_PROFILES"] = os.path.join(
            cache_dir, f"profiles_{leg}")
        cmd = [sys.executable, os.path.abspath(__file__), leg_flag]
        if SMOKE:
            cmd.append("--smoke")
        proc = subprocess.run(
            cmd, env=env, cwd=HERE, stdout=subprocess.PIPE, text=True,
            timeout=min(_CONFIG_TIMEOUT_S, 600))
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith(tag)]
        if proc.returncode != 0 or not lines:
            raise RuntimeError(
                f"{config} {leg} leg failed rc={proc.returncode}: "
                f"{proc.stdout[-400:]}")
        legs.append(json.loads(lines[-1][len(tag):]))
    return legs


def bench_serve_restart():
    """Cold→warm restart differential: the SAME query set in two real
    processes sharing one cache dir. The warm process must show zero
    XLA disk misses (every backend compile served from the cold run's
    disk cache) and answer the repeated query from the result cache
    with zero kernel launches."""
    legs = _run_legs("serve_restart", "--serve-leg", "SERVE-LEG ")
    cold, warm = legs
    return [{
        **warm["device"],
        "metric": "serve-restart warm XLA disk misses "
                  "(0 = restart pays no cold compiles)",
        "value": warm["disk"]["compile.disk_miss"],
        "unit": "cold XLA compiles in a fresh process",
        "vs_baseline": 1.0,
        "cold_disk_misses": cold["disk"]["compile.disk_miss"],
        "warm_disk_hits": warm["disk"]["compile.disk_hit"],
        "cold_engine_compiles": cold["compiles"],
        "warm_engine_compiles": warm["compiles"],
        "warm_disk_hit_compiles": warm["disk_hit_compiles"],
    }, {
        **warm["device"],
        "metric": "serve-restart repeated-query latency "
                  "(cross-process result-cache hit)",
        "value": warm["repeat_ms"],
        "unit": "ms",
        "vs_baseline": 1.0,
        "first_execution_ms": warm["exec_ms"].get("groupby"),
        "cold_repeat_ms": cold["repeat_ms"],
        "repeat_kernel_launches": warm["repeat_launches"],
        "result_cache_hits_warm_leg": warm["result_cache_hits"],
    }]


# --------------------------------------------------------------------------
# serve: multi-tenant serving load (spark_tpu/serve/)
# --------------------------------------------------------------------------

_SERVE_QUERIES = [
    "select k, sum(v) as s from serve_load_t group by k",
    "select k, v from serve_load_t where v > 500 order by v limit 32",
    "select count(*) c from serve_load_t where k < 32",
]


def _serve_load_leg() -> int:
    """One serve-load child leg: start a serving session with 2 pools
    (dash:2, batch:1), drive 8 concurrent cloned sessions through the
    mixed query set (phase 1: result cache DISABLED so queries really
    execute and contend), then replay through the result cache
    (phase 2), and print one SERVE-LOAD json line with fairness,
    latency, attribution, and cache evidence."""
    import pyarrow as pa

    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import QueryService
    from spark_tpu.serve.loadgen import run_serve_load

    cache_dir = os.environ["SPARK_TPU_CACHE_DIR"]
    profile_dir = os.environ["SPARK_TPU_SERVE_PROFILES"]
    device = _require_platform()
    session = _session({
        "spark.tpu.cache.dir": cache_dir,
        "spark.tpu.cache.result.enabled": "false",
        "spark.tpu.obs.profileDir": profile_dir,
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 14,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.serve.maxConcurrent": "2",
        # metrics plane on for the whole leg: the scrape at end-of-load
        # and the drain-time series snapshot are part of the report
        "spark.tpu.metrics.export": "true",
        "spark.tpu.metrics.tickInterval": "0.25",
    })
    rng = np.random.default_rng(7)
    n = max(4000, int(100_000 * SCALE))
    session.createDataFrame(pa.table({
        "k": rng.integers(0, 64, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })).createOrReplaceTempView("serve_load_t")
    service = QueryService(session)
    # serial warmup: compile every kernel once BEFORE the concurrent
    # phase — concurrent FIRST invocations race the XLA disk-cache
    # write (two threads compile, one persists), which made the warm
    # leg's disk_miss flap 0/1. Warm kernels take the cache-hit path,
    # so the contended phase measures admission, not compile races.
    warmup = service.open_session()
    for q in _SERVE_QUERIES:
        service.execute_sql(warmup, q)
    # phase 1: real execution under contention (8 sessions, 2 pools)
    load = run_serve_load(service, _SERVE_QUERIES, sessions=8, reps=2,
                          pools=("dash", "batch"))
    # phase 2: repeated dashboard queries through the result cache
    session.conf.set("spark.tpu.cache.result.enabled", "true")
    l0 = KC.launches
    t0 = time.perf_counter()
    repeat = run_serve_load(service, _SERVE_QUERIES, sessions=4, reps=1,
                            pools=("dash", "batch"))
    repeat_ms = round((time.perf_counter() - t0) * 1000, 2)
    repeat_launches = KC.launches - l0
    rc_hits = int(repeat["counters"].get("result_cache.hit", 0))
    # end-of-load Prometheus scrape: parse it back and reconcile the
    # per-pool e2e histogram counts against the queries the load
    # actually completed (the metrics-plane acceptance identity)
    from spark_tpu.obs import export as mx

    scrape = mx.render_prometheus()
    parsed = mx.parse_prometheus(scrape)
    e2e_count = sum(
        v for (name, _labels), v in parsed["samples"].items()
        if name == "spark_tpu_serve_pool_e2e_ms_count")
    service.drain()
    drain_ts = service.drain_snapshot or {}
    # attribution: per-query scope-exact launch totals (stored profiles)
    # must sum to the process-global KernelCache delta
    store = ProfileStore(profile_dir)
    attributed = 0
    overlapped = 0
    profiles = 0
    for qk in store.query_keys():
        for p in store.profiles(qk):
            profiles += 1
            attributed += int(p.get("launch_total", 0))
            if p.get("overlapped"):
                overlapped += 1
    print("SERVE-LOAD " + json.dumps({
        "device": device,
        "load": load,
        "repeat": {"wall_ms": repeat_ms, "launches": repeat_launches,
                   "errors": repeat["errors"],
                   "result_cache_hits": rc_hits},
        "profiles": profiles,
        "attributed_launches": attributed,
        "global_launches": KC.launches,
        "overlapped_profiles": overlapped,
        "disk": pc.disk_counters(),
        "compiles": KC.misses,
        "disk_hit_compiles": KC.disk_hit_compiles,
        "metrics": {
            "scrape_bytes": len(scrape),
            "scrape_samples": len(parsed["samples"]),
            "e2e_hist_count": int(e2e_count),
            "drain_series": len(drain_ts.get("series", {})),
        },
    }), flush=True)
    return 0


def bench_serve():
    """Serving load test, cold process then warm restart: 8 concurrent
    sessions on 2 weighted pools; the warm leg must pay zero XLA disk
    misses and answer the repeated query set from the result cache
    with zero launches."""
    legs = _run_legs("serve", "--serve-load-leg", "SERVE-LOAD ")
    cold, warm = legs
    pools = cold["load"]["pools"]
    out = [{
        "metric": "serve p99 latency (8 sessions, pools dash:2/batch:1, "
                  "maxConcurrent=2)",
        "value": max(p["p99_ms"] or 0 for p in pools.values()),
        "unit": "ms",
        "vs_baseline": 1.0,
        "per_pool": {name: {"p50_ms": p["p50_ms"], "p95_ms": p["p95_ms"],
                            "p99_ms": p["p99_ms"],
                            "completed": p["completed"]}
                     for name, p in pools.items()},
        "queue_depth_peak": cold["load"]["queue_depth_peak"],
        "errors": (cold["load"]["errors"] + warm["load"]["errors"])[:4],
        "metrics_scrape": cold["metrics"],
    }, {
        "metric": "serve weighted fairness (contended-grant ratio "
                  "normalized by 2:1 weights; 1.0 = proportional)",
        "value": cold["load"]["fairness_ratio"] or 0.0,
        "unit": "x proportional share",
        "vs_baseline": 1.0,
        "contended_grants": cold["load"]["contended_grants"],
    }, {
        "metric": "serve attribution drift (sum of per-query attributed "
                  "launches - global counter delta; 0 = scope-exact)",
        "value": abs(cold["attributed_launches"]
                     - cold["global_launches"]),
        "unit": "launches",
        "vs_baseline": 1.0,
        "attributed": cold["attributed_launches"],
        "global": cold["global_launches"],
        "profiles": cold["profiles"],
        "overlapped_profiles": cold["overlapped_profiles"]
        + warm["overlapped_profiles"],
    }, {
        "metric": "serve warm-restart XLA disk misses (0 = replayed "
                  "load pays no cold compiles)",
        "value": warm["disk"]["compile.disk_miss"],
        "unit": "cold XLA compiles",
        "vs_baseline": 1.0,
        "cold_disk_misses": cold["disk"]["compile.disk_miss"],
        "warm_disk_hits": warm["disk"]["compile.disk_hit"],
        "warm_disk_hit_compiles": warm["disk_hit_compiles"],
    }, {
        "metric": "serve warm repeated-load kernel launches (0 = every "
                  "dashboard query answered by the result cache)",
        "value": warm["repeat"]["launches"],
        "unit": "launches",
        "vs_baseline": 1.0,
        "repeat_wall_ms": warm["repeat"]["wall_ms"],
        "result_cache_hits_warm": warm["repeat"]["result_cache_hits"],
    }]
    return [{**cold["device"], **rec} for rec in out]


# --------------------------------------------------------------------------

CONFIGS = {
    "groupby": bench_groupby,
    "sort": bench_sort,
    "join": bench_join,
    "shuffle": bench_shuffle,
    "adaptive": bench_adaptive,
    "mesh": bench_mesh,
    "encoded": bench_encoded,
    "whole_query": bench_whole_query,
    "mesh_whole": bench_mesh_whole,
    "serve_restart": bench_serve_restart,
    "serve": bench_serve,
    "tpcds": bench_tpcds,
}


def _emit(rec):
    """Flush each record as it's produced: a suite that dies or times out
    must still leave a valid evidence trail."""
    print(json.dumps(rec), flush=True)


def _require_platform() -> dict:
    """Initialise the backend and name the device every record carries.
    A measuring run needs the chip: anything but a TPU exits with a
    one-line reason (--smoke is the CPU gate, forced through the
    environment before jax was imported)."""
    global _BACKEND_TOUCHED
    import jax

    _BACKEND_TOUCHED = True
    jax.config.update("jax_enable_x64", True)
    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind, "n_devices": len(devs)}
    if not SMOKE and device["platform"] != "tpu":
        sys.exit(f"bench.py: no TPU — jax.devices()[0].platform is "
                 f"'{device['platform']}'. A measuring run needs the chip; "
                 "use --smoke for the CPU functional gate.")
    return device


def main() -> int:
    t_start = time.monotonic()
    if SERVE_LEG:
        # internal serve-restart child: one query-set run against the
        # shared cache dir, one SERVE-LEG json line, exit
        return _serve_leg()
    if SERVE_LOAD_LEG:
        # internal serve-load child: one concurrent serving run against
        # the shared cache dir, one SERVE-LOAD json line, exit
        return _serve_load_leg()

    default = [c for c in CONFIGS
               if not (SMOKE and c == "tpcds")
               and (MESH or c != "mesh")       # mesh config is opt-in
               and (ENCODED or c != "encoded")  # encoded too
               and (ADAPTIVE or c != "adaptive")  # and adaptive
               and (WHOLE_QUERY or c != "whole_query")  # and whole-query
               and (MESH_WHOLE or c != "mesh_whole")   # and mesh-whole
               and (SERVE_RESTART or c != "serve_restart")  # and restart
               and (SERVE or c != "serve")]  # and the serving load test
    # configs whose legs are child processes go first, while this process
    # has not initialised a backend: one process per chip (the children
    # check the platform themselves and report the device they ran on)
    only = sorted(sys.argv[1:] or default,
                  key=lambda c: c not in _CHILD_LEG_CONFIGS)
    device = None
    records, failed = [], []
    for name in only:
        in_process = name not in _CHILD_LEG_CONFIGS
        if in_process and device is None:
            device = _require_platform()
        remaining = _SUITE_BUDGET_S - (time.monotonic() - t_start)
        if remaining < 30:
            failed.append(name)
            _emit({"metric": f"{name} SKIPPED (suite budget exhausted)",
                   "value": 0, "unit": "error", "vs_baseline": 0.0})
            continue
        kc_before = _kernel_counters() if in_process else None
        try:
            r = _with_timeout(CONFIGS[name],
                              int(min(_CONFIG_TIMEOUT_S, remaining)))
        except Exception as e:  # later configs still run; the run fails
            failed.append(name)
            _emit({"metric": f"{name} FAILED",
                   "value": 0, "unit": "error",
                   "vs_baseline": 0.0,
                   "error": f"{type(e).__name__}: {e}"[:400]})
            continue
        recs = r if isinstance(r, list) else [r]
        if recs and in_process:
            _attach_kernel_delta(recs[0], kc_before)
        for rec in recs:
            if in_process:
                rec.update(device)
            if SCALE != 1.0:
                # scaled smoke runs compare against full-scale reference
                # numbers — flag the ratio as not meaningful
                rec["scale"] = SCALE
                rec["metric"] += f" [SCALED {SCALE:g}x — vs_baseline invalid]"
            records.append(rec)
            _emit(rec)
    if TRACE:
        from spark_tpu.obs.tracing import to_chrome_trace

        spans = []
        for t in _TRACE_TRACERS:
            spans.extend(t.spans())
        with open(TRACE_PATH, "w") as f:
            json.dump(to_chrome_trace(spans, process_name="bench"), f)
        _emit({"metric": "trace written", "value": len(spans),
               "unit": "spans", "vs_baseline": 1.0,
               "path": os.path.abspath(TRACE_PATH)})
    for s in _CLUSTER_SESSIONS:
        try:
            s.stop()
        except Exception:
            pass
    # floor at 0.001 so a catastrophically slow config drags the geomean
    # instead of vanishing from it (round() can produce exact 0.0)
    ok = [max(r["vs_baseline"], 0.001) for r in records]
    # failed configs drag the geomean honestly: each counts as 0.01x
    ok += [0.01] * len(failed)
    geo = math.exp(sum(math.log(v) for v in ok) / len(ok)) if ok else 0.0
    label = (f"bench suite geomean vs reference CPU baseline "
             f"({len(records)} metrics over {len(only)} configs")
    if SMOKE:
        label += "; --smoke: forced CPU, scaled, counts only"
    label += f"; FAILED: {','.join(failed)})" if failed else ")"
    _emit({
        "metric": label,
        "value": round(geo, 2),
        "unit": "x baseline",
        "vs_baseline": round(geo, 3),
        # only child-leg configs ran: the legs' records name the device
        **(device or {k: v for k, v in (records[0] if records else {}).items()
                      if k in ("platform", "device_kind", "n_devices")}),
    })
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
