#!/usr/bin/env bash
# Full verification ON THE CPU: native build, tests (with batch validation),
# examples, the bench --smoke functional gates, micro-benchmarks (role of the
# reference's dev/run-tests.py). Nothing here touches a device: the proof
# that the query path starts on the chip is `python chip_smoke.py`, run on
# the machine with the chip, and a measuring `python bench.py` needs one too.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tpulint (static analysis vs baseline) =="
python dev/tpulint.py spark_tpu --baseline dev/tpulint_baseline.json

echo "== racecheck (static race & lock-discipline model vs baseline) =="
python dev/racecheck.py spark_tpu --baseline dev/race_baseline.json

echo "== native build =="
make -C native

echo "== tests (batch validation on) =="
SPARK_TPU_VALIDATE=1 python -m pytest tests/ -q

echo "== examples =="
for ex in examples/*.py; do
    echo "-- $ex"
    python "$ex" > /dev/null
done

echo "== trace gate (bench --smoke --trace + validation + drift + resources) =="
SPARK_TPU_TRACE_PATH=/tmp/sparktpu_smoke_trace.json \
    python bench.py --smoke --trace
JAX_PLATFORMS=cpu python dev/validate_trace.py /tmp/sparktpu_smoke_trace.json

echo "== cluster trace gate (worker shipping + flows + live telemetry) =="
SPARK_TPU_TRACE_PATH=/tmp/sparktpu_cluster_trace.json \
    python bench.py --smoke --trace --cluster groupby
JAX_PLATFORMS=cpu python dev/validate_trace.py --cluster --live \
    /tmp/sparktpu_cluster_trace.json

echo "== mesh gate (SPMD stage fusion on the 8-device virtual mesh) =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python dev/validate_trace.py --mesh

echo "== encoded gate (compressed execution: dict-native kernels, code shuffle) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --encoded
python bench.py --smoke --encoded encoded

echo "== adaptive gate (runtime join filters: on/off identity, honest drift) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --adaptive
python bench.py --smoke --adaptive adaptive

echo "== whole-query gate (one jitted program per step, 3-tier differential) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --whole-query
python bench.py --smoke --whole-query whole_query

echo "== mesh whole-query gate (entire sharded plan as ONE shard_map program) =="
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python dev/validate_trace.py --mesh-whole
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python bench.py --smoke --mesh-whole mesh_whole

echo "== chaos gate (fault injection: retry/exclusion/degrade, fixed seed) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --chaos

echo "== profile gate (flight recorder: fingerprints, store, regression) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --profile

echo "== persist gate (cold→warm subprocess restart: disk-hit/zero-launch) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --persist
python bench.py --smoke --serve-restart serve_restart

echo "== serve gate (fair pools, admission, scope-exact attribution, drain) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --serve
python bench.py --smoke --serve serve

echo "== metrics gate (export plane: scrape identity, zero overhead, drain ring) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --metrics

echo "== bundles gate (black box: chaos-seeded SLO capture, zero overhead, retention) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --bundles

echo "== race gate (lockwatch: guard checks + acquisition orders vs static model) =="
JAX_PLATFORMS=cpu python dev/validate_trace.py --race

echo "== perfcheck (deterministic counters of bench --smoke vs baseline) =="
python dev/perfcheck.py

echo "== micro-benchmarks =="
python benchmarks/run_benchmarks.py --rows "${BENCH_ROWS:-2000000}"

echo "== bench functional gate (forced CPU, counts only) =="
python bench.py --smoke

echo "== chip smoke at a tiny size on the CPU (python chip_smoke.py on the chip) =="
python chip_smoke.py --cpu
