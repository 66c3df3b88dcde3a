"""A later PR adds a cell, a configuration, a traffic mix, a query with
its reference and a per-layer metric by adding files and one entry each in
BENCHMARK.json, and edits no file the benchmark has: shown on a copy."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

Q_SQL = """SELECT d_moy, sum(ss_ext_sales_price) total
FROM store_sales, date_dim
WHERE ss_sold_date_sk = d_date_sk AND d_year = 2001
GROUP BY d_moy
ORDER BY d_moy
LIMIT 100
"""

Q_REF = '''"""Sales by month of one year (a later PR's query)."""
import numpy as np
from perfbench.reference import group, position, valid

READS = {"store_sales": ["ss_sold_date_sk", "ss_ext_sales_price"],
         "date_dim": ["d_date_sk", "d_year", "d_moy"]}
KEY_COLUMNS = (0,)


def run(t, arith):
    ss, d = t["store_sales"], t["date_dim"]
    dpos = position(ss["ss_sold_date_sk"], d["d_date_sk"])
    keep = valid(ss["ss_sold_date_sk"]) & (d["d_year"].values == 2001)[dpos]
    rows = np.flatnonzero(keep)
    uniq, inv = group(d["d_moy"].values[dpos[rows]])
    sums = arith.sum_decimal(inv, ss["ss_ext_sales_price"].take(rows),
                             len(uniq))
    return sorted(((int(k), s) for (k,), s in zip(uniq.tolist(), sums)),
                  key=order_key)


def order_key(row):
    return (row[0],)
'''

METRIC = '''"""Queries the window completed (a later PR's counter)."""
LAYER = "entry and plan"
SOURCE = "program_counter"
MOVES = "fact_rows_per_s"
UNIT = "count"


def read(run):
    return float(len(run["records"]))
'''


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

    cfg = json.load(open(root / "perfbench/configs/tpcds_sf10_session.json"))
    cfg["name"] = "tpcds_sf10_store"
    cfg["source"] += " (by store)"
    cfg["query_templates"] = ["q_store"]
    (root / "perfbench/configs/tpcds_sf10_store.json").write_text(
        json.dumps(cfg))
    (root / "perfbench/traffic/store1.json").write_text(json.dumps(
        {"why": "a later PR's", "streams": [["q_store", "q3"]]}))
    (root / "perfbench/queries/q_store.sql").write_text(Q_SQL)
    (root / "perfbench/reference/q_store.py").write_text(Q_REF)
    (root / "perfbench/metrics/queries_done.py").write_text(METRIC)
    bench["configs"].append({
        "name": "tpcds_sf10_store", "source": cfg["source"],
        "file": "perfbench/configs/tpcds_sf10_store.json",
        "reduced": ["tables", "query_templates", "distributions"],
        "why": "a later PR's"})
    bench["workloads"].append({
        "name": "tpcds_sf10_store.store1", "config": "tpcds_sf10_store",
        "traffic": "store1", "chips": 1, "why": "a later PR's"})
    bench["per_layer"].append({
        "name": "queries_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry and plan",
        "moves": "fact_rows_per_s",
        "workloads": ["tpcds_sf10_store.store1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, str(root / "perfbench" / "run.py"), "--workload",
         "tpcds_sf10_store.store1", "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1
    # nothing the benchmark had was edited, and the new reader is found
    for p, body in before.items():
        assert p.read_bytes() == body, p
    sys.path.insert(0, str(root))
    try:
        for m in [m for m in sys.modules if m.startswith("perfbench")]:
            del sys.modules[m]
        from perfbench import spec
        cell = spec.cell("tpcds_sf10_store.store1")
        names = [m["name"] for m in cell["per_layer"]]
        assert "queries_done" in names and "plan_ms" not in names
        assert spec.metric_reader("queries_done").read(
            {"records": [1, 2]}) == 2.0
    finally:
        sys.path.remove(str(root))
        for m in [m for m in sys.modules if m.startswith("perfbench")]:
            del sys.modules[m]


def test_with_nothing_but_the_benchmark_it_fails_and_prints_no_result(
        tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: there is no system to measure."""
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "5", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300, cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "spark_tpu" in r.stderr
