"""A later PR adds a cell, a configuration, a traffic mix, a query with
its reference and a per-layer metric by adding files and one entry each in
BENCHMARK.json, and edits no file the benchmark has: shown on a copy. So
is a deployment that is more than the one star: a second fact table, held
whole, a dimension widened by a column module, and the general readers
in its cell; and the contract's tests, run on such a copy with a fault
planted, fail."""

import contextlib
import json
import os
import shutil
import subprocess
import sys

import pytest

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


def _checkout(tmp_path):
    """A copy of what the benchmark has: (its root, every file's bytes,
    BENCHMARK.json loaded)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return root, before, json.load(f)


def _rehearse(root, cell: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, str(root / "perfbench" / "run.py"), "--workload",
         cell, "--seed", "5", "--seconds", "1", "--trace", "0",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@contextlib.contextmanager
def _imported_from(root):
    """`import perfbench` finds the copy's, and the repo's again after."""
    def forget():
        for m in [m for m in sys.modules if m.startswith("perfbench")]:
            del sys.modules[m]

    sys.path.insert(0, str(root))
    forget()
    try:
        yield
    finally:
        sys.path.remove(str(root))
        forget()


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    root, before, bench = _checkout(tmp_path)

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

    out = _rehearse(root, "tpcds_sf10_store.store1")
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1
    # nothing the benchmark had was edited, and the new reader is found
    for p, body in before.items():
        assert p.read_bytes() == body, p
    with _imported_from(root):
        from perfbench import spec
        cell = spec.cell("tpcds_sf10_store.store1")
        names = [m["name"] for m in cell["per_layer"]]
        assert "queries_done" in names and "plan_ms" not in names
        assert spec.metric_reader("queries_done").read(
            {"records": [1, 2]}) == 2.0


# --- a deployment of two fact tables, one dimension widened by a file ------

RETURNS_TABLE = '''"""store_returns (a later PR's; four columns stand for dsdgen's twenty).
A return is a line item of store_sales, so its item and ticket are that
row's: read from store_sales' own streams, by the rows this table draws."""
import numpy as np

from perfbench.gen import Col, rng_for
from perfbench.gen.tables import store_sales

COLUMNS = ("sr_item_sk", "sr_ticket_number", "sr_return_quantity",
           "sr_return_amt")


def generate(seeds, rows, columns, sizes):
    sold = store_sales.generate(seeds, sizes["store_sales"],
                                ["ss_item_sk", "ss_ticket_number"], sizes)
    line = np.sort(rng_for(seeds, "store_returns", "_line").choice(
        sizes["store_sales"], rows, replace=False))
    made = {
        "sr_item_sk": lambda: Col(sold["ss_item_sk"].values[line]),
        "sr_ticket_number": lambda: Col(
            sold["ss_ticket_number"].values[line]),
        "sr_return_quantity": lambda: Col(rng_for(
            seeds, "store_returns", "sr_return_quantity").integers(
                1, 101, rows, dtype=np.int32)),
        "sr_return_amt": lambda: Col(rng_for(
            seeds, "store_returns", "sr_return_amt").integers(
                0, 10 ** 6, rows).astype(np.int64), scale=2, precision=7)}
    return {c: made[c]() for c in columns if c in made}
'''

STORE_COLUMNS = '''"""s_state, which gen/tables/store.py does not make (a later PR's): by
the key the table's own module made."""
import numpy as np

from perfbench.gen import Col

MAKES = ("s_state",)
STATES = ["AL", "GA", "SD", "TN"]


def generate(seeds, rows, columns, sizes, made):
    sk = made["s_store_sk"].values
    return {"s_state": Col((sk % len(STATES)).astype(np.int32),
                           pool=list(STATES))}
'''

R_SQL = """SELECT s_state, sum(sr_return_amt) returned
FROM store_sales, store_returns, store
WHERE ss_item_sk = sr_item_sk AND ss_ticket_number = sr_ticket_number
  AND ss_store_sk = s_store_sk
GROUP BY s_state
ORDER BY s_state
LIMIT 100
"""

R_REF = '''"""Returns by the state of the store that sold (a later PR's query):
two fact tables joined on (item, ticket)."""
import numpy as np
from perfbench.reference import group, position, valid

READS = {"store_sales": ["ss_item_sk", "ss_ticket_number", "ss_store_sk"],
         "store_returns": ["sr_item_sk", "sr_ticket_number",
                           "sr_return_amt"],
         "store": ["s_store_sk", "s_state"]}
KEY_COLUMNS = (0,)


def run(t, arith):
    ss, sr, s = t["store_sales"], t["store_returns"], t["store"]
    sold = ss["ss_ticket_number"].values.astype(np.int64) * 2 ** 20 \\
        + ss["ss_item_sk"].values
    back = sr["sr_ticket_number"].values.astype(np.int64) * 2 ** 20 \\
        + sr["sr_item_sk"].values
    order = np.argsort(sold, kind="stable")     # a ticket's items differ
    at = np.minimum(np.searchsorted(sold[order], back), len(sold) - 1)
    line = order[at]
    store = ss["ss_store_sk"].take(line)
    rows = np.flatnonzero((sold[line] == back) & valid(store))
    spos = position(store, s["s_store_sk"])[rows]
    uniq, inv = group(s["s_state"].values[spos])
    sums = arith.sum_decimal(inv, sr["sr_return_amt"].take(rows), len(uniq))
    states = s["s_state"].pool
    return sorted(((states[k], x) for (k,), x in zip(uniq.tolist(), sums)),
                  key=order_key)


def order_key(row):
    return (row[0],)
'''

GENERAL = {"programs_per_query", "discarded_program_s_per_query",
           "stage_launches_per_query", "setup_h2d_s", "setup_program_load_s",
           "collect_ms"}
RETURNS = "tpcds_sf10_returns.returns1"


def _add_the_returns_deployment(root, bench, fault=None):
    """What a later PR writes: files, and entries in BENCHMARK.json."""
    with open(root / "perfbench/configs/tpcds_sf10_session.json") as f:
        cfg = json.load(f)
    cfg["name"] = "tpcds_sf10_returns"
    cfg["source"] = "TPC-DS v2.13.0, dsdgen -scale 10: a later PR's " \
        "sales net of returns"
    cfg["query_templates"] = ["q_returns"]
    cfg["fact_tables"] = ["store_sales", "store_returns"]
    cfg["published"]["store_returns_columns"] = 4
    sales = next(t for t in cfg["tables"] if t["name"] == "store_sales")
    cfg["foreign_domains"].update(
        {t["name"]: t["rows"] for t in cfg["tables"] if t is not sales})
    returns = ["sr_item_sk", "sr_ticket_number", "sr_return_quantity",
               "sr_return_amt"]
    store = ["s_store_sk", "s_state"]
    if fault == "fact_table_short_of_a_column":
        returns.remove("sr_return_quantity")
    if fault == "dimension_with_an_unread_column":
        store.append("s_store_name")
    cfg["tables"] = [
        sales,
        {"name": "store_returns", "rows": 2875432, "scales": True,
         "columns": returns},
        {"name": "store", "rows": 102, "scales": False, "columns": store}]
    files = {
        "perfbench/configs/tpcds_sf10_returns.json": json.dumps(cfg),
        "perfbench/traffic/returns1.json": json.dumps(
            {"why": "a later PR's", "streams": [["q_returns"]]}),
        "perfbench/queries/q_returns.sql": R_SQL,
        "perfbench/reference/q_returns.py": R_REF,
        "perfbench/gen/tables/store_returns.py": RETURNS_TABLE,
        "perfbench/gen/columns/store/geography.py": STORE_COLUMNS}
    if fault == "column_made_twice":
        files["perfbench/gen/columns/store/again.py"] = STORE_COLUMNS
    if fault == "column_module_nothing_reads":
        files["perfbench/gen/columns/store/county.py"] = \
            STORE_COLUMNS.replace("s_state", "s_county")
    for name, body in files.items():
        (root / name).parent.mkdir(exist_ok=True)
        (root / name).write_text(body)
    bench["configs"].append({
        "name": "tpcds_sf10_returns", "source": cfg["source"],
        "file": "perfbench/configs/tpcds_sf10_returns.json",
        "reduced": ["tables", "query_templates", "distributions"],
        "why": "a later PR's"})
    bench["workloads"].append({
        "name": RETURNS, "config": "tpcds_sf10_returns",
        "traffic": "returns1", "chips": 1, "why": "a later PR's"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cfg


def _contract_tests(root, which: str):
    """The contract's tests, run on the copy (the file finds its
    repository from where it lies)."""
    there = root / "tests" / "perfbench"
    there.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "tests", "perfbench",
                             "test_perfbench_contract.py"), there)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-k", which,
         str(there / "test_perfbench_contract.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=root)


def test_a_second_fact_table_and_a_widened_dimension_are_files(tmp_path):
    root, before, bench = _checkout(tmp_path)
    cfg = _add_the_returns_deployment(root, bench)
    out = _rehearse(root, RETURNS)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1
    for p, body in before.items():
        assert p.read_bytes() == body, p
    r = _contract_tests(root, "not repo_templates")
    assert r.returncode == 0, r.stdout[-3000:]
    with _imported_from(root):
        from perfbench import gen, spec
        cell = spec.cell(RETURNS)
        names = {m["name"] for m in cell["per_layer"]}
        assert GENERAL <= names
        assert not names & {"plan_ms", "dispatch_ms", "wire_encode_ms"}
        # both fact tables whole; the accepted tables value for value
        # under the new ones; every return is a line of a ticket
        data = gen.generate(cfg, 5, 0.002)
        old = gen.generate(spec.cell(
            "tpcds_sf10_window.dev2")["config"], 5, 0.002)
        assert len(data["store_sales"]) == 23
        assert len(data["store_returns"]) == 4
        assert len(data["store_returns"]["sr_item_sk"].values) \
            == int(2875432 * 0.002)
        for c, col in old["store_sales"].items():
            new = data["store_sales"][c]
            assert (col.values == new.values).all(), c
            assert col.valid is new.valid is None \
                or (col.valid == new.valid).all(), c
        assert (old["store"]["s_store_sk"].values
                == data["store"]["s_store_sk"].values).all()
        assert set(data["store"]["s_state"].strings()) \
            == {"AL", "GA", "SD", "TN"}
        sold = set(zip(data["store_sales"]["ss_ticket_number"].values,
                       data["store_sales"]["ss_item_sk"].values))
        back = list(zip(data["store_returns"]["sr_ticket_number"].values,
                        data["store_returns"]["sr_item_sk"].values))
        assert set(back) <= sold and len(set(back)) == len(back)


@pytest.mark.parametrize("fault,failing,says", [
    ("fact_table_short_of_a_column", "test_config_entry_and_file",
     "sr_return_quantity"),
    ("dimension_with_an_unread_column", "test_config_entry_and_file",
     "s_store_name"),
    ("column_made_twice", "test_every_file_of_the_benchmark_serves_a_cell",
     "s_state"),
    ("column_module_nothing_reads",
     "test_every_file_of_the_benchmark_serves_a_cell", "county")])
def test_the_contract_refuses_a_deployment_with_a_fault(
        fault, failing, says, tmp_path):
    root, _before, bench = _checkout(tmp_path)
    _add_the_returns_deployment(root, bench, fault)
    r = _contract_tests(root, failing)
    assert r.returncode == 1, r.stdout[-3000:]
    failed = [ln for ln in r.stdout.splitlines() if ln.startswith("FAILED")]
    assert failed and all(failing in ln for ln in failed), r.stdout[-3000:]
    assert says in r.stdout


@pytest.mark.parametrize("fault", ["column_made_twice", "column_made_by_none"])
def test_a_column_made_twice_or_by_no_module_names_the_directory(
        fault, tmp_path):
    root, _before, bench = _checkout(tmp_path)
    cfg = _add_the_returns_deployment(root, bench, fault)
    if fault == "column_made_by_none":
        (root / "perfbench/gen/columns/store/geography.py").unlink()
    with _imported_from(root):
        from perfbench import gen
        with pytest.raises(KeyError, match="store.s_state") as e:
            gen.generate(cfg, 5, 0.001)
        assert os.path.join("gen", "columns", "store") in str(e.value)
        assert ("none" if fault == "column_made_by_none"
                else "again") in str(e.value)


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
