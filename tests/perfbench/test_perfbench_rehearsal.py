"""The command itself at the configurations' rehearsal scale on the CPU:
the cell comes out correct and prints counts and no metric, and a run
with no chip and no rehearsal switch fails with no result."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import run as pb  # noqa: E402

SESSION = "tpcds_sf10_session.power2"


@pytest.mark.parametrize("workload,streams", [(SESSION, 1)])
def test_cell_rehearses_correct_and_prints_no_metric(workload, streams,
                                                     capsys):
    rc = pb.main(["--workload", workload, "--seed", str(2 ** 31 + 7),
                  "--seconds", "3", "--trace", "0", "--rehearse"])
    cap = capsys.readouterr()
    out = json.loads(cap.out.strip().splitlines()[-1])
    assert rc == 0
    # each number compared beside its limit, as the last lines on stderr
    tail = cap.err.strip().splitlines()[-len(out["compared"]):]
    assert all("compared" in ln and "limit" in ln for ln in tail)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= streams
    assert out["metrics"] == {}
    assert out["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": out["device"]["count"]}
    assert list(out)[-1] == "compared"       # the numbers compared come last
    for c in out["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]


def test_without_a_chip_and_without_the_switch_it_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", SESSION, "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not 'tpu'" in r.stderr


@pytest.mark.parametrize("values,p,want", [
    ([9.5, 19.7, 9.6, 19.8], 50, 9.6),      # a window of q3 q7 q3 q7
    ([9.5, 19.7, 9.6, 19.8], 95, 19.8),
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([5.0], 95, 5.0),
    (list(range(1, 101)), 95, 95)])
def test_percentiles_are_latencies_some_query_had(values, p, want):
    """Nearest rank, for the median as for the tail: never a mean of two."""
    assert pb.percentile(values, p) == want
