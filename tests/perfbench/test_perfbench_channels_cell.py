"""The cross-channel deployment: the configuration `tpcds_sf10_channels`
(TPC-DS's cross-channel set reports q38 and q87 with their plain
references, the tables `customer`, `catalog_sales` and `web_sales`), its cell
`tpcds_sf10_channels.sets2`, traffic `sets2`, and two per-layer readers of
the whole-query program's set operations. The cell rehearses correct on
the tier the planner chooses; the fact table and `date_dim` are the
accepted ones value for value; the reports' texts are the templates' but
for two `date_dim` columns, and count what the templates count; the
control (NULL not equal to NULL) and a planted fault are not correct."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402
from test_perfbench_faults import _wrap_clients, rehearse  # noqa: E402
from test_perfbench_spans import _plant, _span  # noqa: E402

CELL = "tpcds_sf10_channels.sets2"
CONFIG = spec.cell(CELL)["config"]
QUERIES = ("q38", "q87")
SCALE = float(CONFIG["rehearsal"]["scale"])
BIG_SEED = 2 ** 31 + 4141
READERS = ("setop_program_s_per_query", "setop_slots_per_query")
CHANNELS = (("catalog_sales", "cs_sold_date_sk", "cs_bill_customer_sk",
             14_401_261),
            ("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk",
             7_197_566))


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, BIG_SEED, SCALE)


# ---------------------------------------------------------------------------
# the data
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("other", ["tpcds_sf10_session.power2",
                                   "tpcds_sf10_onerow.onerow2"])
def test_the_fact_table_is_the_accepted_one_value_for_value(data, other):
    accepted = spec.cell(other)["config"]
    assert CONFIG["fact_tables"] == accepted["fact_tables"] \
        == ["store_sales"]
    assert CONFIG["seeding"]["structure_seed"] \
        == accepted["seeding"]["structure_seed"] == 2147750005
    ours = [s for s in CONFIG["seeding"]["from_the_run_seed"]
            if s.startswith("store_sales.")]
    assert ours == accepted["seeding"]["from_the_run_seed"]
    sizes, theirs = gen.table_rows(CONFIG), gen.table_rows(accepted)
    assert all(sizes[t] == n for t, n in theirs.items() if t in sizes)
    want = gen.generate(accepted, BIG_SEED, SCALE)["store_sales"]
    made = data["store_sales"]
    assert list(made) == list(want) and len(made) == 23
    for c, col in want.items():
        assert np.array_equal(made[c].values, col.values), c
        assert (made[c].valid is None) == (col.valid is None), c
        assert col.valid is None \
            or np.array_equal(made[c].valid, col.valid), c


def test_customer_has_dense_keys_and_weighted_names(data):
    c = data["customer"]
    assert list(c) == ["c_customer_sk", "c_first_name", "c_last_name"]
    sk = c["c_customer_sk"].values
    assert np.array_equal(sk, np.arange(1, 500_001))
    assert c["c_customer_sk"].valid is None
    for name, pool in (("c_first_name", 1600), ("c_last_name", 4800)):
        col = c[name]
        assert len(col.pool) == len(set(col.pool)) == pool
        assert 0.030 < 1 - col.valid.mean() < 0.040        # 3.5 % NULL
        counts = np.bincount(col.values, minlength=pool)
        # Zipf: the first name of the list is the commonest, by far
        assert counts.argmax() == 0 and counts[0] > 20 * np.median(counts)
    # names from the run's seed, the nulls from the structure's
    other = gen.generate(CONFIG, BIG_SEED + 1, SCALE)["customer"]
    for name in ("c_first_name", "c_last_name"):
        assert not np.array_equal(c[name].values, other[name].values)
        assert np.array_equal(c[name].valid, other[name].valid)


@pytest.mark.parametrize("table,date,customer,rows", CHANNELS)
def test_the_other_channels_are_orders_of_the_structure_seed(
        data, table, date, customer, rows):
    f = data[table]
    assert list(f) == [date, customer]
    assert len(f[date].values) == int(rows * SCALE)
    assert gen.table_rows(CONFIG)[table] == rows
    d = data["date_dim"]
    for col in (f[date], f[customer]):
        assert 0.040 < 1 - col.valid.mean() < 0.050        # 4.5 % NULL
    days = f[date].values[f[date].valid]
    years = d["d_year"].values[days - d["d_date_sk"].values[0]]
    assert years.min() == 1998 and years.max() == 2002
    cust = f[customer].values[f[customer].valid]
    assert 1 <= cust.min() and cust.max() <= 500_000
    # an order's lines share the date and the customer: runs of rows
    same = (np.diff(f[date].values) == 0) & (np.diff(f[customer].values) == 0)
    assert same.mean() > 0.6
    other = gen.generate(CONFIG, BIG_SEED + 1, SCALE)[table]
    for c in f:
        assert np.array_equal(f[c].values, other[c].values), c


def test_date_dim_is_the_accepted_one_with_its_year(data):
    """date_dim's own module makes what the reports read: the key and the
    year, the accepted table's columns value for value."""
    d = data["date_dim"]
    assert list(d) == ["d_date_sk", "d_year"]
    want = gen.generate(spec.cell("tpcds_sf10_session.power2")["config"],
                        BIG_SEED, SCALE)["date_dim"]
    for c in d:
        assert np.array_equal(d[c].values, want[c].values), c
    in_2000 = d["d_date_sk"].values[d["d_year"].values == 2000]
    assert in_2000[0] == 2451545 and len(in_2000) == 366


# ---------------------------------------------------------------------------
# the queries and their references
# ---------------------------------------------------------------------------

# the template's two references to date_dim columns its module does not
# make, and what the cell reads in their place (the same rows)
REWRITE = (("d_month_seq BETWEEN 1200 AND 1200 + 11", "d_year = 2000"),
           ("d_date\n", "d_date_sk\n"))


@pytest.mark.parametrize("q", QUERIES)
def test_queries_are_the_repo_templates_but_for_two_date_columns(q):
    with open(os.path.join(REPO, "tests", "tpcds", "queries",
                           q + ".sql")) as f, \
            open(os.path.join(REPO, "perfbench", "queries", q + ".sql")) as g:
        template, ours = f.read(), g.read()
    for old, new in REWRITE:
        assert template.count(old) == 3
        template = template.replace(old, new)
    assert template == ours
    assert "d_month_seq" not in ours and "limit" not in \
        spec.query_text(q).lower().split()[-2:]


@pytest.fixture(scope="module")
def small():
    """Forty customers, so that the channels meet: 9 600 store tickets,
    6 400 catalog and 2 400 web orders in the twelve months."""
    import copy

    cfg = copy.deepcopy(CONFIG)
    for t in cfg["tables"]:
        if t["name"] == "customer":
            t["rows"] = 40
    return gen.generate(cfg, BIG_SEED, 0.02)


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_sqlite_oracle(q, small):
    from tests.tpcds.oracle import load_sqlite, rewrite_for_sqlite

    tables = {t: tab for t, tab in gen.arrow_tables(small).items()
              if t != "store_sales"}
    ss = small["store_sales"]
    tables["store_sales"] = gen.arrow_tables({"store_sales": {
        c: ss[c] for c in ("ss_sold_date_sk", "ss_customer_sk")}})[
            "store_sales"]
    # the template's own text too, on a date_dim with the two columns it
    # names: d_month_seq counted from January 1900, d_date the day
    import pyarrow as pa

    dd = tables["date_dim"]
    sk = dd.column("d_date_sk").to_numpy()
    day = np.datetime64("1900-01-02") + (sk - 2415022)
    month = day.astype("datetime64[M]").astype(np.int64) + 70 * 12
    tables["date_dim"] = dd.append_column(
        "d_month_seq", pa.array(month.astype(np.int32))).append_column(
        "d_date", pa.array(day.astype("datetime64[D]")))
    with open(os.path.join(REPO, "tests", "tpcds", "queries",
                           q + ".sql")) as f:
        template = f.read().rstrip()
    conn = load_sqlite(tables)
    try:
        got = conn.execute(rewrite_for_sqlite(spec.query_text(q), q)) \
            .fetchall()
        as_written = conn.execute(rewrite_for_sqlite(template, q)).fetchall()
    finally:
        conn.close()
    ref = reference.load(q)
    want = ref.run(small, reference.Exact())
    assert [tuple(r) for r in got] == want == [tuple(r) for r in as_written]
    assert ref.KEY_COLUMNS == () and ref.order_key(want[0]) == ()
    assert want[0][0] > 0


def test_the_references_read_what_the_bytes_model_counts(data):
    from perfbench.bytes_model import query_bytes

    rows = {t: len(next(iter(data[t].values())).values) for t in data}
    want = (rows["store_sales"] + rows["catalog_sales"]
            + rows["web_sales"]) * 8 + 73049 * 8 + 500_000 * 12 + 8
    for q in QUERIES:
        assert query_bytes(reference.load(q).READS, data, 1, 1) == want


def test_a_wrong_count_is_a_wrong_row(data):
    for q in QUERIES:
        ref = reference.load(q)
        want = ref.run(data, reference.Exact())
        assert check.over(check.compare_rows([(want[0][0] + 1,)], want,
                                             ref)) == ["rows_wrong"]
        assert check.compare_rows(want + want, want, ref)["rows_wrong"] == 1


def test_the_control_is_not_correct_and_float32_changes_no_count(small):
    """No precision below the configuration's moves a count (the counts
    are far under float32's 2 ** 24), so the control is the semantics a
    hurried change would give: NULL not equal to NULL, as an equi-join on
    the raw columns has it. It is not correct, by `rows_wrong` alone."""
    total = {"unanswered": 0, "tier_mismatch": 0,
             "hidden_counters_moved": 0}
    for q in QUERIES:
        ref = reference.load(q)
        want = ref.run(small, reference.Exact())
        assert ref.run(small, reference.Float32()) == want
        check.merge(total, check.compare_rows(
            [(ref.count(small, null_equal=False),)], want, ref))
    ok, compared = check.verdict(total)
    assert not ok
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert over == ["rows_wrong"]


# ---------------------------------------------------------------------------
# the configuration, the traffic and the cell
# ---------------------------------------------------------------------------

def test_the_configuration_forces_no_tier():
    conf = CONFIG["session_conf"]
    assert CONFIG["entry"] == "session"
    assert "spark.tpu.compile.tier" not in conf
    assert set(conf) == set(CONFIG["assumed"])
    assert CONFIG["query_templates"] == list(QUERIES)
    assert CONFIG["substitutions"] == {"DMS": 1200}
    assert [(t["name"], t["rows"]) for t in CONFIG["tables"]] == [
        ("store_sales", 28_800_991), ("catalog_sales", 14_401_261),
        ("web_sales", 7_197_566), ("date_dim", 73049),
        ("customer", 500_000)]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG["name"])
    assert entry["reduced"] == ["tables", "query_templates", "distributions"]
    for word in ("query38.tpl", "query87.tpl", "-scale 10", "DMS=1200",
                 "Power Test"):
        assert word in entry["source"]
    assert {"counts", "distinct", "set_semantics", "joins", "tier"} \
        <= set(CONFIG["guarantees"])
    assert {"c_first_name", "c_last_name"} == {
        s.split(".")[1] for s in CONFIG["seeding"]["from_the_run_seed"]
        if s.startswith("customer.")}


def test_the_traffic_is_one_closed_loop_of_the_two_reports():
    with open(os.path.join(REPO, "perfbench", "traffic", "sets2.json")) as f:
        traffic = json.load(f)
    assert set(traffic) == {"why", "streams", "rounds_at_most"}
    assert traffic["streams"] == [["q38", "q87"]]
    assert 3 <= traffic["rounds_at_most"] <= 12
    cell = next(w for w in spec.benchmark()["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "sets2"
    # at least the readers every cell reads and the cell's own two
    names = {m["name"] for m in spec.cell(CELL)["per_layer"]}
    assert set(READERS) | {"programs_per_query", "hbm_roofline_pct",
                           "device_idle_pct", "stage_launches_per_query",
                           "discarded_program_s_per_query"} <= names
    for m in spec.benchmark()["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["source"] == "program_span"
            assert m["layer"] == "whole-query program"


@pytest.mark.parametrize("seconds,rounds", [(0, 1), (3600, None)],
                         ids=["the_round_that_always_runs",
                              "the_rounds_the_traffic_file_allows"])
def test_cell_rehearses_correct_on_the_tier_the_planner_chose(seconds,
                                                              rounds):
    rounds = rounds or spec.cell(CELL)["traffic"]["rounds_at_most"]
    out = rehearse(CELL, seconds=seconds)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 2 * rounds
    assert out["window"]["queries"] == {"q38": rounds, "q87": rounds}
    assert out["metrics"] == {}


def _count_one_more(entry, session, tables):
    """The count one more, where the answer is produced."""
    def around(run, text, annotate):
        import pyarrow as pa

        table, info = run(text, annotate)
        col = [v + 1 for v in table.column(0).to_pylist()]
        return table.set_column(0, table.column_names[0],
                                pa.array(col, table.schema.field(0).type)), \
            info
    _wrap_clients(entry, around)


def test_a_planted_fault_is_not_correct():
    out = rehearse(CELL, _count_one_more, seconds=0)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] == 2


# ---------------------------------------------------------------------------
# the two readers
# ---------------------------------------------------------------------------

RECORDS = [{"t_submit": 100.0, "t_done": 120.0, "error": None},
           {"t_submit": 120.5, "t_done": 140.0, "error": None}]
SPANS = [
    _span("whole_query.attempt", 50.0, 900.0, discarded=False,
          setop_members=2, setop_slots=1 << 23, setop_expanded=0),
    _span("whole_query.attempt", 100.1, 700.0, discarded=True,
          setop_members=2, setop_slots=1 << 23, setop_expanded=0),
    _span("whole_query.attempt", 100.9, 19000.0, discarded=False,
          setop_members=2, setop_slots=(1 << 23) + (1 << 22),
          setop_expanded=1),
    _span("whole_query.attempt", 120.6, 18000.0, discarded=False,
          setop_members=2, setop_slots=1 << 24, setop_expanded=0),
    _span("whole_query.attempt", 139.0, 500.0, discarded=False,
          setop_members=0, setop_slots=0, setop_expanded=0),
    _span("whole_query.attempt", 150.0, 7000.0, discarded=False,
          setop_members=2, setop_slots=1 << 23, setop_expanded=0),
]
EXPECTED = {"setop_program_s_per_query": (19.0 + 18.0) / 2,
            "setop_slots_per_query": ((1 << 23) + (1 << 22) + (1 << 24)) / 2}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    value = spec.metric_reader(name).read({"records": RECORDS})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_on_the_parents_spans(name, monkeypatch):
    """A program whose attempt spans say nothing of set operations, and
    one without `recorded_spans`, have nothing to read: the traced run
    leaves the metric out and does not raise."""
    import spark_tpu.obs.tracing as tracing

    bare = [_span("whole_query.attempt", s["ts"], s["dur_ms"],
                  discarded=s["args"]["discarded"]) for s in SPANS]
    _plant(monkeypatch, bare)
    assert spec.metric_reader(name).read({"records": RECORDS}) is None
    _plant(monkeypatch, SPANS)
    assert spec.metric_reader(name).read({"records": []}) is None
    monkeypatch.delattr(tracing, "recorded_spans")
    assert spec.metric_reader(name).read({"records": RECORDS}) is None


def test_the_two_readers_on_a_real_whole_tier_window():
    """The engine's own spans, read by the readers: the two reports at a
    small size on the CPU, on the whole tier, leave each program's seconds
    and two existence joins at the probe's capacity a query."""
    import time

    from spark_tpu import TpuSession

    s = TpuSession("pb-channels-readers", dict(CONFIG["session_conf"]))
    try:
        for name, tab in gen.arrow_tables(
                gen.generate(CONFIG, BIG_SEED, 0.002)).items():
            s.createDataFrame(tab).createOrReplaceTempView(name)
        s.conf.set("spark.tpu.compile.tier", "whole")
        for q in QUERIES:          # the ladder, before the window
            s.sql(spec.query_text(q)).toArrow()
        records = []
        for q in QUERIES:
            t0 = time.perf_counter()
            s.sql(spec.query_text(q)).toArrow()
            records.append({"t_submit": t0, "t_done": time.perf_counter(),
                            "error": None})
    finally:
        s.stop()
    run = {"records": records}
    seconds = spec.metric_reader("setop_program_s_per_query").read(run)
    window = records[-1]["t_done"] - records[0]["t_submit"]
    assert 0 < seconds <= window / 2
    slots = spec.metric_reader("setop_slots_per_query").read(run)
    # two joins a report, each at a power-of-two capacity
    assert slots > 0 and slots % 2 == 0
