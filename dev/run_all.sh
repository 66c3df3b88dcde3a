#!/usr/bin/env bash
# Full verification ON THE CPU: static analysis, native build, tests (with
# batch validation), examples, and the chip smoke at a tiny size (role of the
# reference's dev/run-tests.py). Nothing here touches a device or takes a
# time: the proof that the query path starts on the chip is
# `python chip_smoke.py`, and the benchmark is `python3 perfbench/run.py`,
# both run on the machine with the chip (PERF.md says what was measured).
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

echo "== chip smoke at a tiny size on the CPU (python chip_smoke.py on the chip) =="
python chip_smoke.py --cpu
