"""bench.py --smoke: the benchmark harness itself is tier-1-gated — a
broken bench path would otherwise only surface in the (slow) BENCH run."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_runs_and_reports_kernel_launches():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_TPU_BENCH_SCALE"] = "0.001"  # CI: smallest honest scale
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench.py"), "--smoke",
         "groupby", "join"],
        env=env, cwd=HERE, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    recs = [json.loads(line) for line in r.stdout.splitlines()
            if line.strip().startswith("{")]
    assert recs, r.stdout
    # dispatch-count evidence present for each measured config
    with_launches = [x for x in recs if "kernel_launches" in x]
    assert len(with_launches) >= 2, recs
    assert all(x["kernel_launches"] > 0 for x in with_launches), recs
    # summary line last
    assert "geomean" in recs[-1]["metric"]
