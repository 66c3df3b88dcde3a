"""PR 37's additions: the configuration `tpcds_sf10_onerow` (TPC-DS's
one-row reports q28 and q88 with their plain references, the tables
`household_demographics` and `time_dim`), its cell
`tpcds_sf10_onerow.onerow2`, traffic `onerow2`, and three per-layer
readers of the stage tier's spans. The cell rehearses correct on the
tier the planner chooses; the fact table and `store` are the accepted
ones value for value; the float32 control is not correct."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402
from test_perfbench_faults import rehearse  # noqa: E402
from test_perfbench_spans import _plant, _span  # noqa: E402

CELL = "tpcds_sf10_onerow.onerow2"
CONFIG = spec.cell(CELL)["config"]
QUERIES = ("q28", "q88")
SCALE = float(CONFIG["rehearsal"]["scale"])
BIG_SEED = 2 ** 31 + 3737
READERS = ("stage_run_s_per_query", "host_shuffle_ms",
           "host_shuffle_mb_per_query")


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, BIG_SEED, SCALE)


# ---------------------------------------------------------------------------
# the data
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("other", ["tpcds_sf10_session.power2",
                                   "tpcds_sf10_window.dev2"])
def test_the_fact_table_is_the_accepted_one_value_for_value(data, other):
    """One `store_sales` for every configuration: the same structure
    seed, the same run-seed streams, the same dimension sizes behind the
    foreign keys, at the rehearsal's scale."""
    accepted = spec.cell(other)["config"]
    assert CONFIG["fact_tables"] == accepted["fact_tables"] \
        == ["store_sales"]
    assert CONFIG["seeding"]["structure_seed"] \
        == accepted["seeding"]["structure_seed"] == 2147750005
    assert CONFIG["seeding"]["from_the_run_seed"] \
        == accepted["seeding"]["from_the_run_seed"]
    sizes, theirs = gen.table_rows(CONFIG), gen.table_rows(accepted)
    assert all(sizes[t] == n for t, n in theirs.items() if t in sizes)
    assert sizes["store_sales"] == 28_800_991
    want = gen.generate(accepted, BIG_SEED, SCALE)["store_sales"]
    made = data["store_sales"]
    assert list(made) == list(want) and len(made) == 23
    for c, col in want.items():
        assert made[c].values.dtype == col.values.dtype, c
        assert np.array_equal(made[c].values, col.values), c
        assert (made[c].valid is None) == (col.valid is None), c
        assert col.valid is None \
            or np.array_equal(made[c].valid, col.valid), c
        assert (made[c].scale, made[c].precision) \
            == (col.scale, col.precision), c


def test_store_is_the_accepted_one_value_for_value(data):
    want = gen.generate(spec.cell("tpcds_sf10_window.dev2")["config"],
                        BIG_SEED, SCALE)["store"]
    made = data["store"]
    assert list(made) == ["s_store_sk", "s_store_name"]
    for c, col in made.items():
        assert np.array_equal(col.values, want[c].values), c
        assert col.pool == want[c].pool and col.valid is want[c].valid
    ese = made["s_store_name"].strings() == "ese"
    assert made["s_store_sk"].values[ese].tolist() == list(range(4, 103, 10))


def test_household_demographics_is_the_cross_product_in_key_order(data):
    hd = data["household_demographics"]
    assert list(hd) == ["hd_demo_sk", "hd_dep_count", "hd_vehicle_count"]
    sk = hd["hd_demo_sk"].values
    assert sk.tolist() == list(range(1, 7201))
    dep, cars = hd["hd_dep_count"].values, hd["hd_vehicle_count"].values
    assert sorted(set(dep.tolist())) == list(range(10))
    assert sorted(set(cars.tolist())) == list(range(-1, 5))
    # 20 income bands x 6 buy potentials x 10 x 6, income band fastest:
    # every (dependants, vehicles) pair 120 times, dependants turning
    # every 120 keys and vehicles every 1 200
    pairs, counts = np.unique(np.stack([dep, cars], 1), axis=0,
                              return_counts=True)
    assert len(pairs) == 60 and set(counts.tolist()) == {120}
    assert np.array_equal(dep, (sk - 1) // 120 % 10)
    assert np.array_equal(cars, (sk - 1) // 1200 % 6 - 1)
    assert all(c.valid is None for c in hd.values())
    # q88's households: dependants 4, 2 (any vehicles), 0 (at most 2)
    assert np.count_nonzero(((dep == 4) & (cars <= 6)) | (
        (dep == 2) & (cars <= 4)) | ((dep == 0) & (cars <= 2))) \
        == 120 * (6 + 6 + 4)
    key = data["store_sales"]["ss_hdemo_sk"]
    assert 1 <= key.values[key.valid].min() \
        and key.values[key.valid].max() <= 7200


def test_time_dim_is_the_days_seconds(data):
    td = data["time_dim"]
    assert list(td) == ["t_time_sk", "t_hour", "t_minute"]
    sk = td["t_time_sk"].values
    assert len(sk) == 86400 and sk[0] == 0 and sk[-1] == 86399
    assert np.array_equal(sk, np.arange(86400))
    assert np.array_equal(td["t_hour"].values, sk // 3600)
    assert np.array_equal(td["t_minute"].values, sk // 60 % 60)
    assert td["t_hour"].values.max() == 23
    assert td["t_minute"].values.max() == 59
    # a half hour is 1 800 keys, and every sale's second is a row
    late9 = (td["t_hour"].values == 9) & (td["t_minute"].values >= 30)
    assert np.count_nonzero(late9) == 1800
    key = data["store_sales"]["ss_sold_time_sk"]
    assert 0 <= key.values[key.valid].min() \
        and key.values[key.valid].max() <= 86399


def test_the_dimensions_do_not_scale_and_the_run_seed_leaves_them(data):
    other = gen.generate(CONFIG, BIG_SEED + 1, SCALE)
    for t in ("household_demographics", "time_dim", "store"):
        spec_t = next(x for x in CONFIG["tables"] if x["name"] == t)
        assert spec_t["scales"] is False
        for c, col in data[t].items():
            assert len(col.values) == spec_t["rows"]
            assert np.array_equal(col.values, other[t][c].values)
    # the run seed moves q28's four columns and none of q88's three
    a, b = data["store_sales"], other["store_sales"]
    for c in reference.load("q28").READS["store_sales"]:
        assert not np.array_equal(a[c].values, b[c].values), c
    for c in reference.load("q88").READS["store_sales"]:
        assert np.array_equal(a[c].values, b[c].values), c


# ---------------------------------------------------------------------------
# the queries and their references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", QUERIES)
def test_queries_are_the_repo_templates(q):
    with open(os.path.join(REPO, "tests", "tpcds", "queries",
                           q + ".sql"), "rb") as f, \
            open(os.path.join(REPO, "perfbench", "queries", q + ".sql"),
                 "rb") as g:
        assert f.read() == g.read()
    assert "limit" not in spec.query_text(q).lower().split()[-2:]


@pytest.fixture(scope="module")
def sqlite_rows(data):
    from tests.tpcds.oracle import load_sqlite, rewrite_for_sqlite

    conn = load_sqlite(gen.arrow_tables(data))
    try:
        return {q: conn.execute(rewrite_for_sqlite(
            spec.query_text(q), q)).fetchall() for q in QUERIES}
    finally:
        conn.close()


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_sqlite_oracle(q, data, sqlite_rows):
    from tests.tpcds.oracle import compare_rows

    ref = reference.load(q)
    want = ref.run(data, reference.Exact())
    assert len(want) == 1 == len(sqlite_rows[q])
    assert ref.KEY_COLUMNS == () and ref.order_key(want[0]) == ()
    ok, msg = compare_rows(want, sqlite_rows[q])
    assert ok, msg
    # every bucket of both reports is non-empty at the rehearsal's scale
    counts = want[0] if q == "q88" else want[0][1::3] + want[0][2::3]
    assert all(isinstance(c, int) and c > 0 for c in counts), want
    n = check.compare_rows(list(want), want, ref)
    assert not any(n.values())


def test_the_references_read_what_the_bytes_model_counts(data):
    """q28 four planes of the fact table, 28 B a row; q88 three, 12 B a
    row, and the three dimensions."""
    from perfbench.bytes_model import query_bytes

    rows = len(data["store_sales"]["ss_quantity"].values)
    q28 = query_bytes(reference.load("q28").READS, data, 1, 18)
    assert q28 == rows * (4 + 3 * 8) + 18 * 8
    q88 = query_bytes(reference.load("q88").READS, data, 1, 8)
    assert q88 == rows * 12 + 7200 * 12 + 86400 * 12 + 102 * 8 + 8 * 8


def test_a_wrong_count_and_a_wrong_distinct_count_are_wrong_rows(data):
    ref = reference.load("q28")
    want = ref.run(data, reference.Exact())
    for at in (1, 2):                    # B1_CNT, B1_CNTD
        row = list(want[0])
        row[at] += 1
        n = check.compare_rows([tuple(row)], want, ref)
        assert n["rows_wrong"] == 1 and check.over(n) == ["rows_wrong"]
    n = check.compare_rows(want + want, want, ref)       # the row twice
    assert n["rows_wrong"] == 1
    assert check.compare_rows([], want, ref)["rows_wrong"] == 1


def test_float32_control_comes_out_not_correct(data):
    """The references with sums and averages carried in float32, in the
    program's place: not correct, by q28's averages alone (q88 is
    counts, which float32 does not touch)."""
    total = {"unanswered": 0, "tier_mismatch": 0,
             "hidden_counters_moved": 0}
    for q in QUERIES:
        ref = reference.load(q)
        check.merge(total, check.compare_rows(
            ref.run(data, reference.Float32()),
            ref.run(data, reference.Exact()), ref))
    ok, compared = check.verdict(total)
    assert not ok
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert over == ["decimal_avg_max_abs_units"]


# ---------------------------------------------------------------------------
# the configuration, the traffic and the cell
# ---------------------------------------------------------------------------

def test_the_configuration_forces_no_tier():
    conf = CONFIG["session_conf"]
    assert CONFIG["entry"] == "session"
    assert "spark.tpu.compile.tier" not in conf
    assert conf == {"spark.tpu.batch.capacity": 4194304,
                    "spark.tpu.cache.result.enabled": False,
                    "spark.tpu.fusion.denseKeys": False}
    assert set(conf) == set(CONFIG["assumed"])
    assert CONFIG["query_templates"] == list(QUERIES)
    assert [t["name"] for t in CONFIG["tables"]] == [
        "store_sales", "household_demographics", "time_dim", "store"]
    assert [t["rows"] for t in CONFIG["tables"]] == [28_800_991, 7200,
                                                     86400, 102]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG["name"])
    assert entry["reduced"] == ["tables", "query_templates",
                                "distributions"]
    for word in ("query28.tpl", "query88.tpl", "-scale 10", "Power Test"):
        assert word in entry["source"]
    assert {"counts", "distinct_counts", "decimal_averages",
            "empty_buckets", "joins", "tier"} <= set(CONFIG["guarantees"])


def test_the_traffic_is_one_closed_loop_of_the_two_reports():
    with open(os.path.join(REPO, "perfbench", "traffic",
                           "onerow2.json")) as f:
        traffic = json.load(f)
    assert set(traffic) == {"why", "streams", "rounds_at_most"}
    assert traffic["streams"] == [["q28", "q88"]]
    assert 3 <= traffic["rounds_at_most"] <= 12
    cell = next(w for w in spec.benchmark()["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "onerow2"
    # the cell reads the ten readers every cell reads and its own three
    names = [m["name"] for m in spec.cell(CELL)["per_layer"]]
    assert set(READERS) <= set(names) and len(names) == 13
    assert "stage_launches_per_query" in names
    for m in spec.benchmark()["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["source"] == "program_span"


@pytest.mark.parametrize("seconds,rounds", [(0, 1), (3600, None)],
                         ids=["the_round_that_always_runs",
                              "the_rounds_the_traffic_file_allows"])
def test_cell_rehearses_correct_on_the_tier_the_planner_chose(seconds,
                                                              rounds):
    rounds = rounds or spec.cell(CELL)["traffic"]["rounds_at_most"]
    out = rehearse(CELL, seconds=seconds)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 2 * rounds
    assert out["window"]["queries"] == {"q28": rounds, "q88": rounds}
    assert out["window"]["rounds"] == [rounds]
    assert out["metrics"] == {}
    for c in out["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------

# two queries in a window from 100 s to 140 s; set-up before it
RECORDS = [{"t_submit": 100.0, "t_done": 120.0, "error": None},
           {"t_submit": 120.5, "t_done": 140.0, "error": None}]
SPANS = [
    _span("stage.run", 50.0, 900.0, stage=1, launches=7),     # warm-up
    _span("shuffle.host", 50.5, 30.0, kind="hash", partitions=4,
          bytes_d2h=10 ** 9, bytes_h2d=10 ** 9),
    _span("stage.run", 100.1, 700.0, stage=1, launches=7, tiles=1),
    _span("shuffle.host", 100.7, 40.0, kind="broadcast", partitions=1,
          bytes_d2h=0, bytes_h2d=0),
    _span("stage.run", 101.0, 18000.0, stage=2, launches=90, tiles=7),
    _span("shuffle.host", 118.0, 60.0, kind="hash", partitions=4,
          bytes_d2h=3_000_000, bytes_h2d=4_000_000),
    _span("collect", 119.9, 90.0),
    _span("stage.run", 120.6, 19000.0, stage=1, launches=120, tiles=7),
    _span("shuffle.host", 139.0, 20.0, kind="fused", partitions=4,
          bytes_d2h=500_000, bytes_h2d=500_000),
    _span("stage.run", 150.0, 7000.0, stage=1, launches=1),   # later
]
EXPECTED = {"stage_run_s_per_query": (0.7 + 18.0 + 19.0) / 2,
            "host_shuffle_ms": (40 + 60 + 20) / 2,
            "host_shuffle_mb_per_query": (3 + 4 + 0.5 + 0.5) / 2}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    value = spec.metric_reader(name).read({"records": RECORDS})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_where_no_stage_ran(name, monkeypatch):
    """A window of whole-query programs, a window with no query, and a
    failed query: nothing to read, or nothing to divide by."""
    _plant(monkeypatch, [s for s in SPANS if s["name"] == "collect"])
    assert spec.metric_reader(name).read({"records": RECORDS}) is None
    _plant(monkeypatch, SPANS)
    assert spec.metric_reader(name).read({"records": []}) is None
    failed = [dict(RECORDS[0], error="boom"), RECORDS[1]]
    assert spec.metric_reader(name).read({"records": failed}) \
        == pytest.approx(2 * EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_on_a_program_without_recorded_spans(
        name, monkeypatch):
    """The parent of PR 37 has `recorded_spans` and neither span, a
    program from before PR 25 has no `recorded_spans`: the traced run
    leaves the metric out and does not raise."""
    import spark_tpu.obs.tracing as tracing

    monkeypatch.delattr(tracing, "recorded_spans")
    assert spec.metric_reader(name).read({"records": RECORDS}) is None


def test_an_exchange_that_stays_on_the_device_reads_zero_megabytes(
        monkeypatch):
    """The cell's own plans: broadcasts of one-row results."""
    _plant(monkeypatch, [s for s in SPANS
                         if s.get("args", {}).get("kind") == "broadcast"])
    read = spec.metric_reader("host_shuffle_mb_per_query").read
    assert read({"records": RECORDS}) == 0.0
    assert spec.metric_reader("host_shuffle_ms").read(
        {"records": RECORDS}) == pytest.approx(20.0)


def test_the_three_readers_on_a_real_stage_tier_window():
    """The engine's own spans, read by the readers: a window of the two
    reports on the CPU leaves a stage's seconds, an exchange's
    milliseconds, and no megabyte (every exchange is a broadcast)."""
    import time

    from spark_tpu import TpuSession

    data = gen.generate(CONFIG, BIG_SEED, 0.002)
    s = TpuSession("pb-onerow-readers", dict(CONFIG["session_conf"]))
    try:
        for name, tab in gen.arrow_tables(data).items():
            s.createDataFrame(tab).createOrReplaceTempView(name)
        records = []
        for q in QUERIES:
            t0 = time.perf_counter()
            s.sql(spec.query_text(q)).toArrow()
            records.append({"t_submit": t0, "t_done": time.perf_counter(),
                            "error": None})
    finally:
        s.stop()
    run = {"records": records}
    stage_s = spec.metric_reader("stage_run_s_per_query").read(run)
    window = records[-1]["t_done"] - records[0]["t_submit"]
    assert 0 < stage_s <= window / 2
    assert spec.metric_reader("host_shuffle_ms").read(run) > 0
    assert spec.metric_reader("host_shuffle_mb_per_query").read(run) == 0.0
